"""End-to-end distributed training through the real stack — the analogue
of the reference's nightly ``dist_lenet.py`` run via
``tools/launch.py -n W --launcher local`` (tests/nightly/test_all.sh:55):
real processes over localhost, Module.fit with kvstore ``dist_sync``,
per-rank data shards, BSP weights identical across workers at the end."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, REPO_ROOT)
import jax

jax.config.update("jax_platforms", "cpu")

import mxnet_tpu as mx

rank = int(os.environ["MXTPU_PROC_ID"])
kv = mx.kv.create("dist_sync")

rng = np.random.RandomState(0)
wstar = rng.randn(8, 3).astype(np.float32)
X = rng.rand(128, 8).astype(np.float32)
Y = np.argmax(X @ wstar, axis=1).astype(np.float32)
# per-rank shard (the DataParallelExecutorGroup slice the reference takes)
Xs, Ys = X[kv.rank::kv.num_workers], Y[kv.rank::kv.num_workers]

data = mx.sym.Variable("data")
net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(
        mx.sym.Activation(
            mx.sym.FullyConnected(data, num_hidden=16, name="fc1"),
            act_type="relu"),
        num_hidden=3, name="fc2"), name="softmax")

it = mx.io.NDArrayIter(Xs, Ys, batch_size=16, label_name="softmax_label")
metric = mx.metric.Accuracy()
mod = mx.mod.Module(net, label_names=["softmax_label"])
mod.fit(it, num_epoch=30, optimizer="sgd", kvstore=kv,
        optimizer_params={"learning_rate": 0.3},
        initializer=mx.init.Xavier(), eval_metric=metric)
acc = metric.get()[1]
w = mod._exec.arg_dict["fc1_weight"].asnumpy()
with open(os.path.join(OUT_DIR, f"result_{kv.rank}.json"), "w") as f:
    json.dump({"rank": kv.rank, "acc": float(acc),
               "wsum": float(np.abs(w).sum())}, f)
"""


def test_dist_sync_training_via_launcher(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(f"REPO_ROOT = {ROOT!r}\n"
                      f"OUT_DIR = {str(tmp_path)!r}\n" + WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", "--coordinator", "127.0.0.1:19761", "--",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    import json

    results = []
    for rank in (0, 1):
        path = tmp_path / f"result_{rank}.json"
        assert path.exists(), f"{r.stdout[-1000:]}\n{r.stderr[-1000:]}"
        results.append(json.loads(path.read_text()))
    for res in results:
        assert res["acc"] > 0.8, results
    # BSP: both workers end on identical weights
    assert abs(results[0]["wsum"] - results[1]["wsum"]) < 1e-4, results
