"""Int8 serving density (mxnet_tpu.quantization, docs/quantization.md):
calibration statistics + checksummed table serialization, graph conversion
over the shared rewrite engine, quantized FC/conv numerics, the
ServingConfig.quantize / TPUMX_QUANT serving path with its byte-identity
guarantee, BlockAllocator refcounts, and the int8 paged KV cache — block
budget, decode parity vs the float pool, batch-composition bitwise
self-consistency, and the zero-recompile/freeze discipline with int8
program keys.
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu import observability as obs
from mxnet_tpu import quantization as quant
from mxnet_tpu.base import MXNetError
from mxnet_tpu.executor import compile_cache_stats
from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving import InferenceService
from mxnet_tpu.serving.batcher import ServingConfig
from mxnet_tpu.serving.generation import (BlockAllocator, GenerationConfig,
                                          GenerationService, PagedKVCache)

pytestmark = pytest.mark.quantization

CFG = tr.TransformerConfig(vocab=40, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64)


@pytest.fixture(autouse=True)
def _fresh_observability():
    yield
    obs.recompile.reset()


@pytest.fixture(scope="module")
def lm_params():
    return tr.transformer_lm_init(CFG, jax.random.PRNGKey(0))


def _mlp_sym(nh=16, classes=4):
    data = sym.Variable("data")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=nh, name="fc1"),
                       act_type="relu")
    return sym.FullyConnected(h, num_hidden=classes, name="fc2")


def _mlp_params(rng, nh=16, classes=4, dim=8):
    return {"fc1_weight": rng.randn(nh, dim).astype(np.float32) * 0.3,
            "fc1_bias": np.zeros(nh, np.float32),
            "fc2_weight": rng.randn(classes, nh).astype(np.float32) * 0.3,
            "fc2_bias": np.zeros(classes, np.float32)}


def _calib_iter(rng, n=64, dim=8, batch=16):
    return mx.io.NDArrayIter(rng.rand(n, dim).astype(np.float32), None,
                             batch_size=batch)


# -- calibration ---------------------------------------------------------------------
def test_calibrate_collects_stats_and_weight_channels():
    rng = np.random.RandomState(0)
    s, params = _mlp_sym(), _mlp_params(rng)
    table = quant.calibrate(s, params, _calib_iter(rng), entropy=True)
    assert set(table.activations) == {"fc1", "fc2"}
    assert set(table.weights) == {"fc1_weight", "fc2_weight"}
    ent = table.activations["fc1"]
    # data is U[0,1): min >= 0, absmax == max <= 1, percentile <= absmax
    assert 0.0 <= ent["min"] <= ent["max"] <= 1.0001
    assert ent["absmax"] == pytest.approx(ent["max"])
    assert ent["percentile"] <= ent["absmax"] + 1e-6
    assert ent["entropy"] > 0
    # per-channel weight absmax, channel axis 0
    np.testing.assert_allclose(
        table.weights["fc1_weight"]["absmax"],
        np.abs(params["fc1_weight"]).max(axis=1), rtol=1e-6)
    assert tuple(table.weights["fc1_weight"]["shape"]) == (16, 8)
    # method resolution
    assert table.threshold("fc1") == pytest.approx(ent["absmax"])
    assert table.threshold("fc1", "percentile") == \
        pytest.approx(ent["percentile"])
    assert table.threshold("nonexistent") is None


def test_table_save_load_convert_identical(tmp_path):
    """Satellite: save -> load -> convert produces an IDENTICAL converted
    graph (the table alone carries scales + weight shapes)."""
    rng = np.random.RandomState(1)
    s, params = _mlp_sym(), _mlp_params(rng)
    table = quant.calibrate(s, params, _calib_iter(rng))
    path = str(tmp_path / "model.calib.json")
    table.save(path)
    loaded = quant.CalibrationTable.load(path)
    assert quant.convert_symbol(s, loaded).tojson() == \
        quant.convert_symbol(s, table).tojson()
    assert loaded.method == table.method


def test_corrupt_table_raises_naming_file(tmp_path):
    """Satellite: truncation and bit flips raise MXNetError NAMING the
    file (the PR 10 manifest pattern), before any scale is consumed."""
    rng = np.random.RandomState(2)
    s, params = _mlp_sym(), _mlp_params(rng)
    table = quant.calibrate(s, params, _calib_iter(rng))
    path = str(tmp_path / "model.calib.json")
    table.save(path)

    # truncated
    raw = open(path).read()
    with open(path, "w") as f:
        f.write(raw[: len(raw) // 2])
    with pytest.raises(MXNetError, match="model.calib.json"):
        quant.CalibrationTable.load(path)

    # hand-edited value (checksum mismatch)
    with open(path, "w") as f:
        f.write(raw.replace('"method"', '"methoX"', 1))
    with pytest.raises(MXNetError, match="model.calib.json"):
        quant.CalibrationTable.load(path)

    # missing entirely
    with pytest.raises(MXNetError, match="nope.json"):
        quant.CalibrationTable.load(str(tmp_path / "nope.json"))


# -- graph conversion ----------------------------------------------------------------
def test_convert_swaps_weight_args_and_counts_nodes():
    rng = np.random.RandomState(3)
    s, params = _mlp_sym(), _mlp_params(rng)
    table = quant.calibrate(s, params, _calib_iter(rng))
    conv = quant.convert_symbol(s, table)
    assert quant.count_quantized_nodes(conv) == 2
    args = conv.list_arguments()
    assert "fc1_weight_int8" in args and "fc1_weight_scale" in args
    assert "fc1_weight" not in args
    assert "fc1_bias" in args  # biases stay float, shared
    assert quant.count_quantized_nodes(s) == 0  # input untouched
    # exclusion leaves the named node float
    part = quant.convert_symbol(s, table, exclude=["fc1"])
    assert quant.count_quantized_nodes(part) == 1
    assert "fc1_weight" in part.list_arguments()


def test_converted_fc_numerics_close_to_float():
    rng = np.random.RandomState(4)
    s, params = _mlp_sym(), _mlp_params(rng)
    X = rng.rand(16, 8).astype(np.float32)
    table = quant.calibrate(s, params, _calib_iter(rng))
    conv = quant.convert_symbol(s, table)
    qargs = quant.quantize_weights(s, params, table=table)
    binds = {k: nd.array(v) for k, v in qargs.items()}
    binds["data"] = nd.array(X)
    e = conv.bind(ctx=mx.cpu(), args=binds, args_grad=None, grad_req="null")
    e.forward(is_train=False)
    got = e.outputs[0].asnumpy()
    ref_args = {k: nd.array(v) for k, v in params.items()}
    ref_args["data"] = nd.array(X)
    e2 = s.bind(ctx=mx.cpu(), args=ref_args, args_grad=None,
                grad_req="null")
    e2.forward(is_train=False)
    ref = e2.outputs[0].asnumpy()
    assert np.abs(got - ref).max() <= 0.03 * max(np.abs(ref).max(), 1e-6)
    assert np.abs(got - ref).max() > 0  # int8 rounding actually happened


def test_converted_conv_numerics_close_to_float():
    rng = np.random.RandomState(5)
    data = sym.Variable("data")
    c = sym.Convolution(data, kernel=(3, 3), num_filter=4, pad=(1, 1),
                        name="c1")
    params = {"c1_weight": rng.randn(4, 2, 3, 3).astype(np.float32) * 0.2,
              "c1_bias": rng.randn(4).astype(np.float32) * 0.1}
    X = rng.rand(4, 2, 6, 6).astype(np.float32)
    it = mx.io.NDArrayIter(rng.rand(8, 2, 6, 6).astype(np.float32), None,
                           batch_size=4)
    table = quant.calibrate(c, params, it)
    conv = quant.convert_symbol(c, table)
    qargs = quant.quantize_weights(c, params, table=table)
    binds = {k: nd.array(v) for k, v in qargs.items()}
    binds["data"] = nd.array(X)
    e = conv.bind(ctx=mx.cpu(), args=binds, args_grad=None, grad_req="null")
    e.forward(is_train=False)
    got = e.outputs[0].asnumpy()
    rb = {k: nd.array(v) for k, v in params.items()}
    rb["data"] = nd.array(X)
    e2 = c.bind(ctx=mx.cpu(), args=rb, args_grad=None, grad_req="null")
    e2.forward(is_train=False)
    ref = e2.outputs[0].asnumpy()
    assert np.abs(got - ref).max() <= 0.03 * max(np.abs(ref).max(), 1e-6)


def test_convert_without_table_needs_param_shapes():
    s = _mlp_sym()
    with pytest.raises(MXNetError, match="fc1_weight"):
        quant.convert_symbol(s)
    conv = quant.convert_symbol(
        s, param_shapes={"fc1_weight": (16, 8), "fc2_weight": (4, 16)})
    assert quant.count_quantized_nodes(conv) == 2


def test_shared_input_pays_one_quantize_node():
    """The engine's conversion cache: one tensor feeding two quantized
    consumers at the same scale inserts ONE quantize node."""
    from mxnet_tpu.symbol.graph import topo_order

    data = sym.Variable("data")
    a = sym.FullyConnected(data, num_hidden=4, name="fa")
    b = sym.FullyConnected(data, num_hidden=4, name="fb")
    g = sym.Group([a, b])
    conv = quant.convert_symbol(
        g, param_shapes={"fa_weight": (4, 8), "fb_weight": (4, 8)})
    n_q = sum(1 for n in topo_order(conv._entries)
              if n.kind == "op" and n.op.name == "_tpumx_quantize_int8")
    assert n_q == 1


# -- serving path --------------------------------------------------------------------
def _bound_mlp_module(rng):
    mod = mx.mod.Module(_mlp_sym(), label_names=None, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 8))], for_training=False)
    mod.init_params()
    return mod


def test_serving_quantize_int8(tmp_path):
    rng = np.random.RandomState(6)
    mod = _bound_mlp_module(rng)
    X = rng.rand(64, 8).astype(np.float32)
    table = quant.calibrate_module(mod, _calib_iter(rng))
    path = str(tmp_path / "t.calib.json")
    table.save(path)
    svc = InferenceService(mod, ServingConfig(
        max_batch_size=4, quantize="int8", quantize_calibration=path))
    got = np.asarray(svc.submit(X[0]).result()[0])
    svc.stop()
    ref_svc = InferenceService(mod, ServingConfig(max_batch_size=4,
                                                  quantize=None))
    ref = np.asarray(ref_svc.submit(X[0]).result()[0])
    ref_svc.stop()
    assert np.abs(got - ref).max() <= 0.05 * max(np.abs(ref).max(), 1e-6)
    assert np.abs(got - ref).max() > 0


def test_quant_env_gate_and_invalid(monkeypatch):
    monkeypatch.setenv("TPUMX_QUANT", "int8")
    assert ServingConfig().quantize == "int8"
    assert quant.enabled()
    monkeypatch.setenv("TPUMX_QUANT", "0")
    assert ServingConfig().quantize is None
    assert not quant.enabled()
    monkeypatch.setenv("TPUMX_QUANT", "fp4")
    with pytest.raises(MXNetError, match="TPUMX_QUANT"):
        quant.active_dtype()


def test_quant_off_byte_identical_keys_and_outputs(monkeypatch):
    """Acceptance: TPUMX_QUANT=0 leaves every program key and output
    byte-identical to unset (the TPUMX_AMP/TPUMX_PALLAS standard)."""
    rng = np.random.RandomState(7)
    mod = _bound_mlp_module(rng)
    X = rng.rand(4, 8).astype(np.float32)

    def leg():
        from mxnet_tpu import executor as _ex

        mod._exec._jit_cache.clear()
        out = np.asarray(mod._exec.forward(is_train=False,
                                           data=X)[0].asnumpy())
        keys = sorted(map(repr, mod._exec._jit_cache.keys()))
        return out, keys

    monkeypatch.delenv("TPUMX_QUANT", raising=False)
    out_unset, keys_unset = leg()
    monkeypatch.setenv("TPUMX_QUANT", "0")
    out_zero, keys_zero = leg()
    assert keys_unset == keys_zero
    np.testing.assert_array_equal(out_unset, out_zero)
    # and no key anywhere mentions the quant component
    assert not any("quant" in k for k in keys_unset)


def test_quantized_executor_keys_distinct(tmp_path):
    """A quantized bind keys its own program family: the executor
    signature gains ("quant","int8") and never shares a float program."""
    rng = np.random.RandomState(8)
    s, params = _mlp_sym(), _mlp_params(rng)
    table = quant.calibrate(s, params, _calib_iter(rng))
    conv = quant.convert_symbol(s, table)
    qargs = quant.quantize_weights(s, params, table=table)
    binds = {k: nd.array(v) for k, v in qargs.items()}
    binds["data"] = nd.array(rng.rand(4, 8).astype(np.float32))
    e = conv.bind(ctx=mx.cpu(), args=binds, args_grad=None,
                  grad_req="null")
    e.forward(is_train=False)
    assert any(("quant", "int8") in key[1] for key in e._jit_cache)


# -- BlockAllocator refcounts (satellite) --------------------------------------------
def test_allocator_refcounts():
    a = BlockAllocator(8)
    blocks = a.allocate(3)
    assert all(a.refcount(b) == 1 for b in blocks)
    assert a.num_used == 3
    a.incref(blocks[:2])
    assert a.refcount(blocks[0]) == 2
    # one decref releases the share, blocks stay allocated
    assert a.decref(blocks[:2]) == []
    assert a.num_used == 3
    # final release frees at zero
    assert sorted(a.decref(blocks)) == sorted(blocks)
    assert a.num_used == 0
    assert all(a.refcount(b) == 0 for b in blocks)


def test_allocator_refcount_errors():
    a = BlockAllocator(8)
    blocks = a.allocate(2)
    with pytest.raises(ValueError, match="incref of unallocated"):
        a.incref([7])
    a.free(blocks)
    with pytest.raises(ValueError, match="double free"):
        a.free([blocks[0]])
    with pytest.raises(ValueError, match="out of range"):
        a.decref([0])


def test_allocator_free_only_at_zero_reuse():
    """A shared block survives one owner's free and is only handed out
    again after the last reference drops."""
    a = BlockAllocator(4)   # 3 allocatable
    blocks = a.allocate(3)
    assert a.allocate(1) is None
    a.incref([blocks[0]])
    a.free(blocks)          # blocks[1:] free; blocks[0] still shared
    assert a.num_used == 1
    got = a.allocate(2)
    assert blocks[0] not in got
    a.decref([blocks[0]])
    assert a.refcount(blocks[0]) == 0
    assert a.num_used == 2


# -- int8 paged KV cache -------------------------------------------------------------
def test_block_budget_doubles_at_same_bytes():
    """Acceptance: >= 1.9x the bf16 pool's block budget at identical
    bytes (scales cost 8/(block_size*d_head) of the win)."""
    # serving-realistic shapes: the scales cost 8/(block_size*d_head) of
    # the 2x, so any d_head*block_size >= 256 clears 1.9 (a toy
    # d_head=8/bs=8 pool pays ~6% and lands at 1.88 — documented)
    budget = 1 << 24
    for (L, H, D, bs) in [(4, 8, 64, 16), (CFG.n_layers, CFG.n_heads,
                                           16, 16)]:
        bf16 = PagedKVCache.num_blocks_for_bytes(
            budget, L, H, D, bs, dtype=jnp.bfloat16)
        int8 = PagedKVCache.num_blocks_for_bytes(
            budget, L, H, D, bs, dtype=jnp.bfloat16, kv_dtype="int8")
        assert int8 >= 1.9 * bf16, (L, H, D, bs, bf16, int8)


def test_quantized_pool_arrays_and_nbytes():
    c = PagedKVCache(2, 4, 8, 16, 8, kv_dtype="int8")
    assert c.quantized and c.k.dtype == jnp.int8
    assert c.k_scale.shape == (2, 16, 4)
    f = PagedKVCache(2, 4, 8, 16, 8, dtype=jnp.float32)
    assert not f.quantized and f.k_scale is None
    assert c.nbytes() < f.nbytes()
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVCache(2, 4, 8, 16, 8, kv_dtype="int4")


def _gc(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("seq_buckets", [16, 32])
    kw.setdefault("max_new_tokens", 8)
    return GenerationConfig(**kw)


def _drive(lm_params, kv_dtype, prompts, order=None, **cfg_kw):
    order = order if order is not None else list(range(len(prompts)))
    svc = GenerationService(lm_params, CFG,
                            _gc(kv_dtype=kv_dtype, **cfg_kw), start=False)
    warmed = svc.warmup()
    svc.start()
    outs = {i: svc.generate(prompts[i], seed=11 + i, timeout=120)
            for i in order}
    stats, cstats = svc.stats(), svc.compile_stats()
    svc.stop()
    return [outs[i] for i in range(len(prompts))], stats, cstats, warmed


def test_int8_kv_greedy_close_to_float(lm_params):
    """Acceptance: greedy tokens under the int8 pool match the float pool
    within the documented tolerance, and per-step logits stay close."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, CFG.vocab, n) for n in (5, 19, 30)]
    f_out, _, _, _ = _drive(lm_params, None, prompts)
    q_out, stats, _, _ = _drive(lm_params, "int8", prompts)
    assert stats["kv_dtype"] == "int8"
    total = sum(len(o) for o in f_out)
    agree = sum(a == b for o1, o2 in zip(f_out, q_out)
                for a, b in zip(o1, o2))
    assert agree / total >= 0.75, (agree, total, f_out, q_out)


def test_int8_kv_decode_logits_close(lm_params):
    """Direct decode-level check: one prefill + one decode step under the
    int8 pool tracks the float pool's logits within ~2% relative."""
    rng = np.random.RandomState(10)
    T, W, bs = 16, 4, 8
    toks = rng.randint(0, CFG.vocab, (1, T)).astype(np.int32)
    pos = np.arange(T, dtype=np.int32)[None, :]
    ln = np.array([T], np.int32)
    tables = np.array([[1, 2, 3, 4]], np.int32)
    shape = (CFG.n_layers, 8, bs, CFG.d_model)
    lf, kf, vf = tr.transformer_lm_decode(
        lm_params, toks, pos, ln, jnp.zeros(shape), jnp.zeros(shape),
        tables, CFG, attention_kernel="gather")
    sc = jnp.ones((CFG.n_layers, 8, CFG.n_heads))
    lq, kq, vq, ks, vs = tr.transformer_lm_decode(
        lm_params, toks, pos, ln, jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape, jnp.int8), tables, CFG,
        attention_kernel="gather", k_scale=sc, v_scale=sc)
    scale = float(jnp.max(jnp.abs(lf)))
    assert float(jnp.max(jnp.abs(lq - lf))) <= 0.02 * scale
    # decode step against each cache
    t2 = np.array([[7]], np.int32)
    p2 = np.array([[T]], np.int32)
    l2 = np.array([1], np.int32)
    lf2, _, _ = tr.transformer_lm_decode(
        lm_params, t2, p2, l2, kf, vf, tables, CFG,
        attention_kernel="gather")
    lq2, *_ = tr.transformer_lm_decode(
        lm_params, t2, p2, l2, kq, vq, tables, CFG,
        attention_kernel="gather", k_scale=ks, v_scale=vs)
    assert float(jnp.max(jnp.abs(lq2 - lf2))) <= \
        0.02 * float(jnp.max(jnp.abs(lf2)))


def test_int8_kv_bitwise_across_batch_composition(lm_params):
    """Acceptance: int8 greedy tokens are bit-identical to themselves
    across batch-composition changes (submission order shuffled, slots
    shared differently)."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, CFG.vocab, n) for n in (4, 17, 27, 9)]
    a, _, _, _ = _drive(lm_params, "int8", prompts, order=[0, 1, 2, 3],
                        max_slots=3)
    b, _, _, _ = _drive(lm_params, "int8", prompts, order=[3, 1, 0, 2],
                        max_slots=2)
    assert a == b


def test_int8_kv_zero_recompiles_under_freeze(lm_params, monkeypatch):
    """Acceptance: zero post-warmup recompiles under
    TPUMX_FREEZE_COMPILES=1 with the int8 program keys showing up in
    compile_cache_stats()["by_site"]."""
    svc = GenerationService(lm_params, CFG, _gc(kv_dtype="int8",
                                                max_slots=3), start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(12)
    svc.start()
    handles = [svc.submit(rs.randint(0, CFG.vocab, n),
                          max_new_tokens=3 + (i % 4), seed=i)
               for i, n in enumerate([3, 16, 29, 9, 22, 31])]
    for h in handles:
        h.result(120)
    stats = svc.compile_stats()
    svc.stop()
    for key, st in stats.items():
        assert st["misses"] == 1, f"recompile at {key}: {st}"
    # every program key carries the kv_dtype component...
    assert all(("kv_dtype", "int8") in key[1] for key in stats)
    # ...and the int8 sites are visible in the process-wide by_site view
    sites = compile_cache_stats()["by_site"]
    assert any(s.startswith("gen_prefill") and s.endswith("_int8")
               for s in sites), sites
    assert any(s.startswith("gen_decode") and s.endswith("_int8")
               for s in sites), sites


def test_kv_dtype_off_keys_byte_identical(lm_params, monkeypatch):
    """Acceptance: with kv_dtype off (or TPUMX_GEN_KV_DTYPE=0) every
    program key is byte-identical to the pre-quantization layout — no
    kv_dtype component anywhere."""
    monkeypatch.setenv("TPUMX_GEN_KV_DTYPE", "0")
    assert GenerationConfig(max_slots=2, num_blocks=8).kv_dtype is None
    monkeypatch.delenv("TPUMX_GEN_KV_DTYPE", raising=False)
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, CFG.vocab, 9)]
    _, _, cstats, _ = _drive(lm_params, None, prompts)
    for key in cstats:
        assert not any("kv_dtype" in str(c) for c in key[1]), key
    monkeypatch.setenv("TPUMX_GEN_KV_DTYPE", "int8")
    assert GenerationConfig(max_slots=2, num_blocks=8).kv_dtype == "int8"


def test_int8_kv_paged_kernel_matches_gather(lm_params, monkeypatch):
    """The Pallas int8-pool kernel (interpreter leg) tracks the
    dequantizing gather path closely on the same int8 cache."""
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(14)
    T, bs = 16, 8
    toks = rng.randint(0, CFG.vocab, (1, T)).astype(np.int32)
    pos = np.arange(T, dtype=np.int32)[None, :]
    ln = np.array([T], np.int32)
    tables = np.array([[1, 2, 3, 4]], np.int32)
    shape = (CFG.n_layers, 8, bs, CFG.d_model)
    sc = jnp.ones((CFG.n_layers, 8, CFG.n_heads))
    lg, kg, vg, ksg, vsg = tr.transformer_lm_decode(
        lm_params, toks, pos, ln, jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape, jnp.int8), tables, CFG,
        attention_kernel="gather", k_scale=sc, v_scale=sc)
    lp, kp, vp, ksp, vsp = tr.transformer_lm_decode(
        lm_params, toks, pos, ln, jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape, jnp.int8), tables, CFG,
        attention_kernel="paged", k_scale=sc, v_scale=sc)
    # layer-0 pool writes are bitwise identical (same scatter math);
    # logits differ only by the kernels' f32 reduction-order noise
    # amplified through layer-1 requantization (docs/quantization.md)
    assert bool(jnp.all(kg[0] == kp[0]))
    scale = float(jnp.max(jnp.abs(lg)))
    assert float(jnp.max(jnp.abs(lp - lg))) <= 0.02 * scale


def test_int8_kv_with_amp_dtype(lm_params):
    """kv_dtype composes with amp_dtype: bf16 compute, int8 pool."""
    rng = np.random.RandomState(15)
    prompts = [rng.randint(0, CFG.vocab, 11)]
    out, stats, _, _ = _drive(lm_params, "int8", prompts,
                              amp_dtype="bfloat16")
    assert stats["kv_dtype"] == "int8"
    assert len(out[0]) == 8
