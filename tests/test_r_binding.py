"""R binding (R-package/) over the embedded-runtime C ABI.

The image has no R toolchain, so CI drives the binding hermetically: the
.Call shim (R-package/src/mxtpu_r.c) is compiled UNMODIFIED against a stub
of the R extension API (tests/r_stub/Rinternals.h) and a C driver performs
the exact .Call sequence R-package/R/model.R makes for the train-MLP
parity task (mirroring cpp-package/example/train_mlp.cc, reference
R-package/ on the C API).  Where Rscript exists,
R-package/tests/train_mlp.R runs the same flow through real R."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_runtime():
    r = subprocess.run(["make", "-C", os.path.join(ROOT, "cpp")],
                       capture_output=True, text=True)
    assert r.returncode == 0, "cpp build failed:\n" + r.stderr[-3000:]
    rt = os.path.join(ROOT, "cpp", "build", "libmxtpu_rt.so")
    assert os.path.exists(rt), "libmxtpu_rt.so missing"
    return rt


@pytest.mark.skipif(bool(os.environ.get("MXTPU_NO_NATIVE")),
                    reason="native runtime disabled explicitly")
def test_r_shim_trains_mlp(tmp_path):
    rt = _build_runtime()
    exe = str(tmp_path / "r_drive")
    r = subprocess.run(
        ["gcc", "-O2", "-Wall", "-Werror",
         "-I", os.path.join(ROOT, "tests", "r_stub"),
         "-I", os.path.join(ROOT, "cpp", "include"),
         os.path.join(ROOT, "tests", "r_stub", "r_stub.c"),
         os.path.join(ROOT, "tests", "r_stub", "r_binding_drive.c"),
         os.path.join(ROOT, "R-package", "src", "mxtpu_r.c"),
         "-o", exe, "-ldl", "-lm"],
        capture_output=True, text=True)
    assert r.returncode == 0, "R shim build failed:\n" + r.stderr[-3000:]
    env = dict(os.environ, MXTPU_RT_PLATFORM="cpu", MXTPU_RT_HOME=ROOT,
               MXTPU_RT_LIB=rt)
    r = subprocess.run([exe], capture_output=True, text=True, timeout=500,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, \
        f"R shim train-MLP drive failed:\n{r.stdout[-2000:]}\n{r.stderr[-1000:]}"
    assert "final train accuracy" in r.stdout


@pytest.mark.skipif(shutil.which("Rscript") is None,
                    reason="no R toolchain in this image")
def test_r_package_real_r(tmp_path):
    rt = _build_runtime()
    env = dict(os.environ, MXTPU_RT_PLATFORM="cpu", MXTPU_RT_HOME=ROOT,
               MXTPU_RT_LIB=rt)
    lib = str(tmp_path / "rlib")
    os.makedirs(lib)
    r = subprocess.run(["R", "CMD", "INSTALL", "-l", lib,
                        os.path.join(ROOT, "R-package")],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    env["R_LIBS"] = lib
    r = subprocess.run(
        ["Rscript", os.path.join(ROOT, "R-package", "tests", "train_mlp.R")],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    assert "R binding train-MLP parity: OK" in r.stdout


def test_r_symbol_json_matches_python_format():
    """The JSON the R symbol composer emits (symbol.R mx.symbol.tojson)
    must parse in the Python frontend — validated here by feeding the C
    driver's literal copy of that JSON to mx.sym.load_json and binding."""
    import re

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    src = open(os.path.join(ROOT, "tests", "r_stub",
                            "r_binding_drive.c")).read()
    m = re.search(r'kMlpJson =\n((?:\s*"(?:[^"\\]|\\.)*"\n?)+);', src)
    assert m, "kMlpJson literal not found"
    json_str = "".join(
        part.encode().decode("unicode_escape")
        for part in re.findall(r'"((?:[^"\\]|\\.)*)"', m.group(1)))
    sym = mx.sym.load_json(json_str)
    assert sym.list_arguments() == [
        "data", "fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias",
        "softmax_label"]
    exe = sym.simple_bind(ctx=mx.cpu(), data=(2, 32))
    exe.arg_dict["data"][:] = nd.array(
        np.random.rand(2, 32).astype(np.float32))
    exe.forward(is_train=False)
    assert exe.outputs[0].shape == (2, 10)
