"""The run's compilation cache (tests/conftest.py) and the shared greedy
oracle (tests/oracle.py): what docs/testing.md says of them."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import transformer as tr
from oracle import CFG, greedy_oracle

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)


def _entries(path):
    return [n for n in os.listdir(path) if n.endswith("-cache")]


def test_the_runs_cache_is_placed_before_jax_and_takes_every_executable():
    placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == placed
    assert jax.config.jax_enable_compilation_cache
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    assert os.path.abspath(placed) != os.path.join(REPO, ".jax_cache")
    before = set(_entries(placed))
    # a program no other test compiles
    jax.jit(lambda x: jnp.cumsum(x * 3.25) - 0.125)(
        np.arange(37, dtype=np.float32)).block_until_ready()
    assert set(_entries(placed)) - before


_INNER = textwrap.dedent("""
    import os

    import jax
    import numpy as np


    def test_say_where_the_cache_is():
        placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert jax.config.jax_compilation_cache_dir == placed
        jax.jit(lambda x: x * 2 + 1)(np.arange(5.0)).block_until_ready()
        assert [n for n in os.listdir(placed) if n.endswith("-cache")]
        with open(os.path.join(
                os.environ["SAY_DIR"],
                os.environ.get("PYTEST_XDIST_WORKER", "alone")), "w") as f:
            f.write(placed)
""")


@pytest.mark.parametrize("outer", [False, True], ids=["made", "outer"])
def test_two_workers_share_one_directory_and_an_outer_one_wins(tmp_path,
                                                               outer):
    """A run of its own under this conftest, two xdist workers: both read
    the directory the controller placed, which is made for the run and gone
    after it — or is the outer ``JAX_COMPILATION_CACHE_DIR``, left as it
    is."""
    (tmp_path / "test_inner.py").write_text(_INNER)
    said = tmp_path / "said"
    said.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTEST_XDIST_WORKER",
                        "PYTEST_XDIST_WORKER_COUNT", "PYTEST_CURRENT_TEST")}
    env.update(PYTHONPATH=os.pathsep.join([TESTS, REPO]), SAY_DIR=str(said))
    if outer:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "outer")
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "test_inner.py", "-q", "-p",
         "conftest", "-p", "xdist", "-n", "2", "--dist", "each", "-p",
         "no:cacheprovider", "-p", "no:randomly"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stdout + run.stderr
    assert sorted(os.listdir(said)) == ["gw0", "gw1"]
    placed = {(said / w).read_text() for w in ("gw0", "gw1")}
    assert len(placed) == 1
    placed = placed.pop()
    assert placed != os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert os.path.abspath(placed) != os.path.join(REPO, ".jax_cache")
    if outer:
        assert placed == str(tmp_path / "outer") and _entries(placed)
    else:
        assert not os.path.exists(placed)


def test_the_padded_oracle_returns_the_growing_loops_tokens():
    """The one place the un-jitted loop over growing lengths lives on: five
    prompts whose lengths overlap, so that it compiles seven lengths and
    not fifteen."""
    params = tr.transformer_lm_init(CFG, jax.random.PRNGKey(0))
    rs = np.random.RandomState(5)
    for plen in (5, 6, 7, 8, 9):
        prompt = rs.randint(0, CFG.vocab, plen)
        toks = [int(t) for t in prompt]
        for _ in range(3):
            logits = tr.transformer_lm_apply(
                params, jnp.asarray([toks], dtype=jnp.int32),
                jnp.arange(len(toks), dtype=jnp.int32), CFG)
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert greedy_oracle(params, prompt, 3) == toks[plen:]
