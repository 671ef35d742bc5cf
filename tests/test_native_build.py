"""Hard gate: the native runtime must BUILD — a compile error in cpp/ must
fail CI, not silently skip every native test (the reference treats libmxnet
build failure as fatal, not optional)."""
import os
import subprocess

import pytest

from mxnet_tpu import _native


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled explicitly")
def test_native_library_builds_and_loads():
    cpp_dir = os.path.join(os.path.dirname(os.path.dirname(_native.__file__)),
                           "cpp")
    r = subprocess.run(["make", "-C", cpp_dir], capture_output=True, text=True)
    assert r.returncode == 0, "native build failed:\n" + r.stderr[-4000:]
    assert _native.lib() is not None, "libmxtpu.so built but failed to load"


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled explicitly")
def test_cpp_package_builds_and_reads_python_checkpoint(tmp_path):
    """The C++ high-level wrapper (cpp-package/) must build and exchange
    models with the Python frontend (reference: cpp-package/ on the C API)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    root = os.path.dirname(os.path.dirname(_native.__file__))
    pkg = os.path.join(root, "cpp-package")
    r = subprocess.run(["make", "-C", pkg], capture_output=True, text=True)
    assert r.returncode == 0, "cpp-package build failed:\n" + r.stderr[-4000:]

    data = mx.sym.Variable("data")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"), name="softmax")
    sym_path = str(tmp_path / "m-symbol.json")
    par_path = str(tmp_path / "m.params")
    out.save(sym_path)
    nd.save(par_path, {"fc_weight": nd.array(np.ones((4, 8), np.float32)),
                       "fc_bias": nd.array(np.zeros(4, np.float32))})
    r = subprocess.run([os.path.join(pkg, "build", "inspect_model"),
                        sym_path, par_path], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "arg: fc_weight" in r.stdout
    assert "output: softmax_output" in r.stdout
    assert "total parameters: 36" in r.stdout


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled explicitly")
def test_cpp_trains_mlp_through_embedded_runtime():
    """The C++ train loop (executor + kvstore over libmxtpu_rt.so) must run
    end to end and learn (reference: cpp-package mlp.cpp judge config)."""
    root = os.path.dirname(os.path.dirname(_native.__file__))
    binary = os.path.join(root, "cpp-package", "build", "train_mlp")
    if not os.path.exists(binary):
        r = subprocess.run(["make", "-C", os.path.join(root, "cpp-package")],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-4000:]
    assert os.path.exists(binary), "train_mlp not built (python3-config absent?)"
    env = dict(os.environ,
               MXTPU_RT_PLATFORM="cpu", MXTPU_RT_HOME=root)
    r = subprocess.run([binary], capture_output=True, text=True, env=env,
                       timeout=500, cwd=root)
    assert r.returncode == 0, \
        f"train_mlp failed (rc={r.returncode}):\n{r.stdout}\n{r.stderr}"
    assert "final train accuracy" in r.stdout


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled explicitly")
def test_perl_binding_builds_and_passes():
    """The Perl XS binding (perl-package/) must build against the embedded
    runtime and pass its own test suite (reference: perl-package/AI-MXNet)."""
    import shutil

    if shutil.which("perl") is None:
        pytest.skip("perl not installed")
    root = os.path.dirname(os.path.dirname(_native.__file__))
    pkg = os.path.join(root, "perl-package", "MXTPU")
    if not os.path.exists(os.path.join(root, "cpp", "build",
                                       "libmxtpu_rt.so")):
        r = subprocess.run(["make", "-C", os.path.join(root, "cpp")],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-3000:]
    env = dict(os.environ, MXTPU_RT_PLATFORM="cpu", MXTPU_RT_HOME=root)
    r = subprocess.run(["perl", "Makefile.PL"], capture_output=True,
                       text=True, cwd=pkg, env=env)
    if r.returncode != 0:
        pytest.skip(f"ExtUtils::MakeMaker unavailable: {r.stderr[-200:]}")
    r = subprocess.run(["make"], capture_output=True, text=True, cwd=pkg,
                       env=env)
    assert r.returncode == 0, "perl binding build failed:\n" + r.stderr[-3000:]
    r = subprocess.run(["make", "test"], capture_output=True, text=True,
                       cwd=pkg, env=env, timeout=500)
    assert r.returncode == 0, \
        f"perl tests failed:\n{r.stdout[-3000:]}\n{r.stderr[-1000:]}"
    assert "All tests successful" in r.stdout


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled explicitly")
def test_native_im2rec_cli_packs_readable_records(tmp_path):
    """The native im2rec CLI (cpp/tools/im2rec.cc; reference tools/im2rec.cc)
    packs a JPEG list into RecordIO that the Python recordio reader and the
    native image pipeline both consume."""
    import numpy as np

    PIL = pytest.importorskip("PIL.Image")

    from mxnet_tpu import recordio

    root = os.path.dirname(os.path.dirname(_native.__file__))
    exe = os.path.join(root, "cpp", "build", "im2rec")
    if not os.path.exists(exe):
        r = subprocess.run(["make", "-C", os.path.join(root, "cpp")],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]
    assert os.path.exists(exe)

    rng = np.random.RandomState(0)
    img_dir = tmp_path / "imgs"
    os.makedirs(img_dir)
    entries = []
    for i in range(6):
        arr = rng.randint(0, 255, (24 + i, 32, 3)).astype("uint8")
        name = f"im{i}.jpg"
        PIL.fromarray(arr).save(str(img_dir / name), quality=95)
        entries.append((i, i % 3, name))
    lst = tmp_path / "data.lst"
    with open(lst, "w") as f:
        for i, label, name in entries:
            f.write(f"{i}\t{label}\t{name}\n")

    # pass-through pack
    rec = str(tmp_path / "data.rec")
    r = subprocess.run([exe, str(lst), str(img_dir), rec],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    reader = recordio.MXRecordIO(rec, "r")
    seen = []
    while True:
        item = reader.read()
        if item is None:
            break
        header, img = recordio.unpack(item)
        seen.append((header.id, header.label, len(img)))
    assert [s[0] for s in seen] == [0, 1, 2, 3, 4, 5]
    assert [s[1] for s in seen] == [0.0, 1.0, 2.0, 0.0, 1.0, 2.0]
    # pass-through: bytes identical to the source file
    src = open(str(img_dir / "im0.jpg"), "rb").read()
    reader2 = recordio.MXRecordIO(rec, "r")
    _h, img0 = recordio.unpack(reader2.read())
    assert img0 == src
    # .idx written and consistent
    idx_lines = open(str(tmp_path / "data.idx")).read().strip().splitlines()
    assert len(idx_lines) == 6 and idx_lines[0].split("\t")[0] == "0"

    # resize pack: decoded shapes have short side == 16
    rec2 = str(tmp_path / "small.rec")
    r = subprocess.run([exe, str(lst), str(img_dir), rec2, "--resize", "16",
                        "--quality", "90", "--num-thread", "2"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    reader3 = recordio.MXRecordIO(rec2, "r")
    import io

    count = 0
    while True:
        item = reader3.read()
        if item is None:
            break
        _h, img = recordio.unpack(item)
        with PIL.open(io.BytesIO(bytes(img))) as im:
            assert min(im.size) == 16
        count += 1
    assert count == 6


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled explicitly")
def test_cpp_predictor_wrapper(tmp_path):
    """mxtpu::Predictor (the c_predict_api analogue for C++ deployers):
    graph JSON + Python-written checkpoint -> inference from pure C++."""
    import json

    import numpy as np

    from mxnet_tpu import nd

    root = os.path.dirname(os.path.dirname(_native.__file__))
    rt = os.path.join(root, "cpp", "build", "libmxtpu_rt.so")
    if not os.path.exists(rt):
        r = subprocess.run(["make", "-C", os.path.join(root, "cpp")],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]
    w = np.random.RandomState(0).rand(3, 4).astype(np.float32)
    params = str(tmp_path / "p.params")
    nd.save(params, {"arg:qfc_weight": nd.array(w),
                     "arg:qfc_bias": nd.array(np.zeros(3, np.float32))})
    graph = {
        "nodes": [
            {"op": "null", "name": "data", "attrs": {}, "inputs": []},
            {"op": "null", "name": "qfc_weight", "attrs": {}, "inputs": []},
            {"op": "null", "name": "qfc_bias", "attrs": {}, "inputs": []},
            {"op": "FullyConnected", "name": "qfc",
             "attrs": {"num_hidden": "3"},
             "inputs": [[0, 0, 0], [1, 0, 0], [2, 0, 0]]},
        ],
        "arg_nodes": [0, 1, 2], "heads": [[3, 0, 0]],
    }
    sym = str(tmp_path / "p-symbol.json")
    with open(sym, "w") as f:
        json.dump(graph, f)
    src = tmp_path / "drive.cc"
    src.write_text(r'''
#include <cstdio>
#include <cmath>
#include <fstream>
#include <sstream>
#include "mxtpu.hpp"
int main(int argc, char **argv) {
  std::ifstream f(argv[1]);
  std::stringstream ss; ss << f.rdbuf();
  mxtpu::Predictor pred(ss.str(), argv[2], {{"data", {2, 4}}});
  float x[8];
  for (int i = 0; i < 8; ++i) x[i] = 0.25f * i;
  pred.SetInput("data", x, {2, 4});
  pred.Forward();
  auto out = pred.Output(0);
  if (out.size() != 6) return 1;
  for (float v : out) std::printf("%g ", v);
  std::printf("\n");
  return 0;
}
''')
    exe = str(tmp_path / "drive")
    r = subprocess.run(
        ["g++", "-O1", "-std=c++17", str(src), "-o", exe,
         "-I", os.path.join(root, "cpp-package", "include"),
         "-I", os.path.join(root, "cpp", "include"),
         "-L", os.path.join(root, "cpp", "build"),
         f"-Wl,-rpath,{os.path.join(root, 'cpp', 'build')}",
         "-lmxtpu_rt"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    env = dict(os.environ, MXTPU_RT_PLATFORM="cpu", MXTPU_RT_HOME=root)
    r = subprocess.run([exe, sym, params], capture_output=True, text=True,
                       timeout=200, env=env, cwd=root)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-1000:]}"
    got = np.array([float(v) for v in r.stdout.split()]).reshape(2, 3)
    x = (0.25 * np.arange(8, dtype=np.float32)).reshape(2, 4)
    assert np.allclose(got, x @ w.T, atol=1e-4)


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled")
def test_cpp_unit_suite_passes():
    """C++-side unit tests (reference: tests/cpp/ gtest suite — engine
    stress, storage, recordio — here plain-assert, cpp/tests/test_native.cc):
    multi-threaded pusher contention and pool reuse can only be probed from
    native threads, not through the GIL-serialized ctypes tier."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(["make", "-C", os.path.join(root, "cpp")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    binary = os.path.join(root, "cpp", "build", "test_native")
    r = subprocess.run([binary], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, f"{r.stdout[-500:]}\n{r.stderr[-2000:]}"
    assert "ALL CPP TESTS PASSED" in r.stdout
