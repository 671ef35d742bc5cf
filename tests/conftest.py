"""Test configuration: tests run on the CPU backend with 8 virtual devices.

The platform is forced through the jax *config* at conftest import, before
any test touches jax (backends initialize lazily), so the whole session runs
on 8 virtual CPU devices — which is how the multi-chip sharding tests run
without real chips (SURVEY.md §4's "distributed without a real cluster"
analogue).  Pallas kernels run in interpret mode here; the chip's compiler
is asked separately (tests/test_chip_compile.py).
"""
import contextlib
import os
import shutil
import tempfile


def _place_compile_cache():
    """One persistent compilation cache for the run (docs/testing.md): every
    executable is written once and read by whichever test or xdist worker
    needs it next.  The xdist controller (or the single process) makes the
    directory and the workers inherit it; an outer
    ``JAX_COMPILATION_CACHE_DIR`` is used as it is and left in place.
    Returns the directory this process must remove at session end."""
    for name in ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                 "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"):
        os.environ.setdefault(name, "0")
    # XLA:CPU logs two 3 KB lines at ERROR level for every executable it
    # reads back (`prefer-no-scatter` / `prefer-no-gather` are tuning
    # flags the host's feature list never has): they bury a failure's
    # captured output and fill the pipe of a child nobody reads yet
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    if (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or "PYTEST_XDIST_WORKER" in os.environ):
        return None
    made = tempfile.mkdtemp(prefix="tpumx-tests-xla-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = made
    return made


_OWN_COMPILE_CACHE = _place_compile_cache()

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip() \
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "") \
    else os.environ["XLA_FLAGS"]

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as _np
import pytest


@contextlib.contextmanager
def compile_cache_off():
    """The run's compilation cache off and forgotten, then back as it was."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()


@pytest.fixture
def no_compile_cache():
    """For a test that counts XLA's own compiles: an executable read from
    the run's cache is no compile event, so a program first asked for after
    warm-up would go uncounted if another test had compiled it."""
    with compile_cache_off():
        yield


@pytest.fixture(autouse=True)
def _seed():
    """Reproducible seeds per test (reference: tests/python/unittest/common.py
    @with_seed)."""
    _np.random.seed(0)
    import mxnet_tpu as mx

    mx.random.seed(0)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: example-family smoke runs too slow for the default tier "
        "(run with `pytest -m slow tests/test_examples_smoke.py`)")
    config.addinivalue_line(
        "markers",
        "serving: online inference serving subsystem (mxnet_tpu.serving; "
        "select with `pytest -m serving`)")
    config.addinivalue_line(
        "markers",
        "fused: fused whole-train-step execution (Executor.fused_step, "
        "docs/fused_step.md; select with `pytest -m fused`)")
    config.addinivalue_line(
        "markers",
        "spmd: multi-device SPMD data-parallel training (shard_map fused "
        "step over the dp mesh, docs/multichip.md; select with "
        "`pytest -m spmd`)")
    config.addinivalue_line(
        "markers",
        "amp: automatic mixed precision (mxnet_tpu.amp — casting policy, "
        "traced loss scaling, fused master weights, docs/amp.md; select "
        "with `pytest -m amp`)")
    config.addinivalue_line(
        "markers",
        "generation: continuous-batching LM generation engine "
        "(mxnet_tpu.serving.generation — paged KV cache, iteration-level "
        "scheduling, streaming, docs/generation.md; select with "
        "`pytest -m generation`)")
    config.addinivalue_line(
        "markers",
        "sharding: partition-rule-driven sharded model parallelism (tensor "
        "parallel + FSDP state sharding over the (dp,mp) mesh, "
        "mxnet_tpu.parallel.partition_rules, docs/sharding.md; select with "
        "`pytest -m sharding`)")
    config.addinivalue_line(
        "markers",
        "pallas: Pallas hot-path kernel layer (TPUMX_PALLAS gate — paged "
        "decode attention, flash-attention backward, fused LayerNorm; "
        "docs/pallas.md; select with `pytest -m pallas`)")
    config.addinivalue_line(
        "markers",
        "pp: pipeline-parallel training (TPUMX_PP_DEVICES — stage-stacked "
        "symbol staging + GPipe microbatch round-robin inside the fused "
        "step over the (dp,pp,mp) mesh, parallel/pipeline.py + "
        "symbol/staging.py, docs/sharding.md; select with `pytest -m pp`)")
    config.addinivalue_line(
        "markers",
        "observability: unified runtime observability (mxnet_tpu."
        "observability — metrics registry, structured tracing, recompile "
        "explainer, device-side train telemetry, docs/observability.md; "
        "select with `pytest -m observability`)")
    config.addinivalue_line(
        "markers",
        "router: multi-replica generation routing (mxnet_tpu.serving."
        "router — least-loaded dispatch, health probes + circuit breaker, "
        "dead-replica resubmission, drain-aware shutdown; "
        "docs/generation.md; select with `pytest -m router`)")
    config.addinivalue_line(
        "markers",
        "tracing: end-to-end request tracing + flight recorder "
        "(mxnet_tpu.observability.tracing trace contexts, wide-event "
        "records, mxnet_tpu.observability.flight_recorder; "
        "docs/observability.md; select with `pytest -m tracing`)")
    config.addinivalue_line(
        "markers",
        "fault: fault-tolerant training (mxnet_tpu.checkpoint async "
        "checkpointing + mxnet_tpu.fault preemption/injection, kvstore "
        "retry/backoff, serving graceful shutdown; "
        "docs/fault_tolerance.md; select with `pytest -m fault`)")
    config.addinivalue_line(
        "markers",
        "quantization: int8 serving density (mxnet_tpu.quantization — "
        "calibration tables, the shared-rewrite-engine int8 graph "
        "conversion, ServingConfig.quantize, and the int8 paged KV "
        "cache; docs/quantization.md; select with "
        "`pytest -m quantization`)")
    config.addinivalue_line(
        "markers",
        "prefix: prefix caching (mxnet_tpu.serving.generation."
        "prefix_cache — chained-hash block index, copy-on-write shared "
        "KV blocks, LRU eviction ahead of preemption, router "
        "shared-prefix affinity; docs/generation.md; select with "
        "`pytest -m prefix`)")
    config.addinivalue_line(
        "markers",
        "speculative: speculative + multi-token decoding "
        "(mxnet_tpu.serving.generation.speculative — n-gram/draft-model "
        "proposers, the multi-query verify step, exact-match rejection "
        "sampling; docs/generation.md "
        "\"Speculative decoding\"; select with `pytest -m speculative`)")


def pytest_unconfigure(config):
    if _OWN_COMPILE_CACHE:
        shutil.rmtree(_OWN_COMPILE_CACHE, ignore_errors=True)


def pytest_collection_modifyitems(config, items):
    if config.getoption("-m"):
        return  # explicit marker expression given — let it rule
    import pytest as _pytest

    skip_slow = _pytest.mark.skip(
        reason="slow tier: run with -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
