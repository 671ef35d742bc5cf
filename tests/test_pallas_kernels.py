"""Pallas 2-bit compression kernels (interpret mode on CPU — the
same-kernel-two-backends oracle; reference: gradient_compression tests in
tests/nightly/dist_sync_kvstore.py:28-50)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk


def _roundtrip(g, res, t):
    packed, newres = pk.twobit_pack(jnp.asarray(g), jnp.asarray(res), t)
    out = pk.twobit_unpack(packed, g.shape, t, dtype=jnp.float32)
    return np.asarray(out), np.asarray(newres), np.asarray(packed)


def test_twobit_pack_semantics():
    t = 0.5
    g = np.array([0.7, -0.6, 0.1, 0.0, 2.0, -3.0], np.float32)
    res = np.zeros_like(g)
    out, newres, _ = _roundtrip(g, res, t)
    np.testing.assert_allclose(out[:6], [t, -t, 0.0, 0.0, t, -t])
    # error feedback: residual keeps what quantization lost
    np.testing.assert_allclose(newres, g - out[:6].reshape(g.shape), atol=1e-6)


def test_twobit_error_feedback_accumulates():
    t = 1.0
    g = np.full((64,), 0.4, np.float32)
    res = np.zeros_like(g)
    # three pushes of 0.4 accumulate: residuals 0.4, 0.8, then fire at 1.2
    for step in range(3):
        packed, res_j = pk.twobit_pack(jnp.asarray(g), jnp.asarray(res), t)
        out = np.asarray(pk.twobit_unpack(packed, g.shape, t))
        res = np.asarray(res_j)
        if step < 2:
            np.testing.assert_allclose(out, 0.0)
        else:
            np.testing.assert_allclose(out, t)
    np.testing.assert_allclose(res, 3 * 0.4 - 1.0, atol=1e-5)


def test_twobit_roundtrip_random_shapes():
    rs = np.random.RandomState(0)
    for shape in [(5,), (127,), (16, 129), (3, 4, 5)]:
        g = rs.randn(*shape).astype(np.float32)
        res = rs.randn(*shape).astype(np.float32) * 0.1
        out, newres, packed = _roundtrip(g, res, 0.5)
        eff = g + res
        expect = np.where(eff >= 0.5, 0.5, np.where(eff <= -0.5, -0.5, 0.0))
        np.testing.assert_allclose(out, expect.astype(np.float32), atol=1e-6)
        np.testing.assert_allclose(newres, eff - expect, atol=1e-6)
        assert packed.dtype == np.uint32
        # 16x compression vs f32 (modulo block padding)
        assert packed.size * 4 <= (g.size * 4) / 4 + 128 * 4


def test_gradient_compression_uses_pallas_backend():
    from mxnet_tpu.parallel.compression import GradientCompression

    gc = GradientCompression(type="2bit", threshold=0.5)
    g = jnp.asarray(np.random.RandomState(1).randn(1000).astype(np.float32))
    packed, res = gc.quantize(g)
    out = gc.dequantize(packed, (1000,))
    eff = np.asarray(g)
    expect = np.where(eff >= 0.5, 0.5, np.where(eff <= -0.5, -0.5, 0.0))
    np.testing.assert_allclose(np.asarray(out), expect, atol=1e-6)


def test_flash_attention_matches_oracle():
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    from mxnet_tpu.parallel.ring_attention import local_attention
    r = np.random.RandomState(0)
    q = jnp.asarray(r.rand(2, 64, 4, 16).astype(np.float32))
    k = jnp.asarray(r.rand(2, 64, 4, 16).astype(np.float32))
    v = jnp.asarray(r.rand(2, 64, 4, 16).astype(np.float32))
    assert float(jnp.abs(flash_attention(q, k, v)
                         - local_attention(q, k, v)).max()) < 1e-5
    assert float(jnp.abs(flash_attention(q, k, v, True)
                         - local_attention(q, k, v, causal=True)).max()) < 1e-5


def test_flash_attention_multi_block_and_grad():
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    from mxnet_tpu.parallel.ring_attention import local_attention
    r = np.random.RandomState(1)
    # T=256 > block 128: exercises the online-softmax accumulation
    q = jnp.asarray(r.rand(1, 256, 2, 8).astype(np.float32))
    k = jnp.asarray(r.rand(1, 256, 2, 8).astype(np.float32))
    v = jnp.asarray(r.rand(1, 256, 2, 8).astype(np.float32))
    assert float(jnp.abs(flash_attention(q, k, v, True)
                         - local_attention(q, k, v, causal=True)).max()) < 1e-5
    g1 = jax.grad(lambda q_: flash_attention(q_, k, v, True).sum())(q)
    g2 = jax.grad(lambda q_: local_attention(q_, k, v, causal=True).sum())(q)
    assert float(jnp.abs(g1 - g2).max()) < 1e-4


def test_flash_attention_nd_op():
    from mxnet_tpu import nd
    r = np.random.RandomState(2)
    q = nd.array(r.rand(1, 32, 2, 8).astype(np.float32))
    out = nd.contrib.flash_attention(q, q, q, causal=True)
    assert out.shape == (1, 32, 2, 8)


def test_bn_train_fused_parity():
    """Fused BN stats+normalize kernel (one read of the activation for
    both batch statistics; reference src/operator/nn/batch_norm.cc): fwd +
    grads match the jnp var-form implementation, bf16 preserved."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(3)
    x = rng.randn(2, 5, 5, 256).astype(np.float32) * 2 + 0.7
    g = rng.rand(256).astype(np.float32) + 0.5
    b = rng.randn(256).astype(np.float32)
    out, mean, var = pk.bn_train_fused(jnp.asarray(x), jnp.asarray(g),
                                       jnp.asarray(b), 1e-3, -1)
    m = x.reshape(-1, 256).mean(0)
    v = x.reshape(-1, 256).var(0)
    ref = (x - m) / np.sqrt(v + 1e-3) * g + b
    assert np.allclose(np.asarray(out), ref, atol=1e-3)
    assert np.allclose(np.asarray(mean), m, atol=1e-4)
    assert np.allclose(np.asarray(var), v, rtol=1e-4, atol=1e-5)

    def loss_fused(x_, g_, b_):
        return jnp.sum(pk.bn_train_fused(x_, g_, b_, 1e-3, -1)[0] ** 2)

    def loss_ref(x_, g_, b_):
        mm = jnp.mean(x_, axis=(0, 1, 2))
        vv = jnp.var(x_, axis=(0, 1, 2))
        return jnp.sum(((x_ - mm) * jax.lax.rsqrt(vv + 1e-3) * g_ + b_) ** 2)

    ga = jax.grad(loss_fused, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    for a, r in zip(ga, gr):
        assert np.allclose(np.asarray(a), np.asarray(r), atol=2e-2)

    outb, _, _ = pk.bn_train_fused(jnp.asarray(x, jnp.bfloat16),
                                   jnp.asarray(g), jnp.asarray(b), 1e-3, -1)
    assert outb.dtype == jnp.bfloat16

    # odd row count (M = 3*5*5): kernel-hostile, must fall back cleanly
    xo = rng.randn(3, 5, 5, 128).astype(np.float32)
    oo, mo, vo = pk.bn_train_fused(jnp.asarray(xo), jnp.asarray(g[:128]),
                                   jnp.asarray(b[:128]), 1e-3, -1)
    assert np.allclose(np.asarray(mo), xo.reshape(-1, 128).mean(0),
                       atol=1e-4)


def test_batch_norm_pallas_env_flag(monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import batch_norm

    monkeypatch.setenv("MXTPU_BN_PALLAS", "1")
    rng = np.random.RandomState(5)
    x = rng.randn(3, 4, 4, 128).astype(np.float32)
    g = rng.rand(128).astype(np.float32)
    b = rng.randn(128).astype(np.float32)
    out = batch_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b),
                     jnp.zeros(128), jnp.ones(128), eps=1e-3,
                     fix_gamma=False, axis=-1, _training=True)
    m = x.reshape(-1, 128).mean(0)
    v = x.reshape(-1, 128).var(0)
    ref = (x - m) / np.sqrt(v + 1e-3) * g + b
    assert np.allclose(np.asarray(out), ref, atol=1e-3)


@pytest.mark.pallas
def test_layer_norm_fused_parity():
    """Fused LN stats+normalize kernel: forward AND grads match the jnp
    two-pass reference; bf16 preserved; odd row counts are padded."""
    rng = np.random.RandomState(7)
    x = rng.randn(4, 8, 256).astype(np.float32) * 2 + 0.5
    g = rng.rand(256).astype(np.float32) + 0.5
    b = rng.randn(256).astype(np.float32)

    def ref(x_, g_, b_):
        mu = jnp.mean(x_, axis=-1, keepdims=True)
        var = jnp.var(x_, axis=-1, keepdims=True)
        return (x_ - mu) * jax.lax.rsqrt(var + 1e-5) * g_ + b_

    out = pk.layer_norm_fused(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    want = ref(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    ga = jax.grad(lambda *a: jnp.sum(pk.layer_norm_fused(*a) ** 2),
                  argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    for a, r in zip(ga, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-3, atol=1e-3)

    outb = pk.layer_norm_fused(jnp.asarray(x, jnp.bfloat16),
                               jnp.asarray(g), jnp.asarray(b))
    assert outb.dtype == jnp.bfloat16

    # odd row count (M = 3*5): padded to a legal block, still the kernel
    xo = rng.randn(3, 5, 128).astype(np.float32)
    oo = pk.layer_norm_fused(jnp.asarray(xo), jnp.asarray(g[:128]),
                             jnp.asarray(b[:128]))
    mu = xo.mean(-1, keepdims=True)
    ref_o = (xo - mu) / np.sqrt(xo.var(-1, keepdims=True) + 1e-5) \
        * g[:128] + b[:128]
    np.testing.assert_allclose(np.asarray(oo), ref_o, rtol=1e-4, atol=1e-4)


@pytest.mark.pallas
@pytest.mark.parametrize("rows", [1, 4, 15, 1023])
def test_layer_norm_runs_the_kernel_at_any_row_count(rows):
    """No quiet reference: row counts that are not a multiple of 8 (the
    engine's max_slots=4 decode step, the 1023 prefill bucket) are padded
    to a legal block and still go through the Pallas call."""
    x = jnp.ones((rows, 128), jnp.float32)
    g, b = jnp.ones((128,)), jnp.zeros((128,))
    jaxpr = str(jax.make_jaxpr(
        lambda x_: pk.layer_norm_fused(x_, g, b))(x))
    assert "pallas_call" in jaxpr
    assert pk.layer_norm_fused(x, g, b).shape == (rows, 128)


@pytest.mark.pallas
def test_bn_fused_reference_fallback_warns_with_shape():
    """bn_train_fused cannot pad batch statistics for free: a row count
    that is not a multiple of 8 runs the reference, and says so."""
    x = jnp.ones((3, 5, 128), jnp.float32)
    with pytest.warns(RuntimeWarning, match=r"15 rows.*\(15, 128\)"):
        pk.bn_train_fused(x, jnp.ones((128,)), jnp.zeros((128,)), 1e-5, 2)


@pytest.mark.pallas
def test_layer_norm_gelu_epilogue():
    rng = np.random.RandomState(8)
    x = rng.randn(16, 128).astype(np.float32)
    g = rng.rand(128).astype(np.float32) + 0.5
    b = rng.randn(128).astype(np.float32)
    out = pk.layer_norm_fused(jnp.asarray(x), jnp.asarray(g),
                              jnp.asarray(b), gelu=True)
    mu = jnp.mean(jnp.asarray(x), axis=-1, keepdims=True)
    var = jnp.var(jnp.asarray(x), axis=-1, keepdims=True)
    want = jax.nn.gelu((jnp.asarray(x) - mu) * jax.lax.rsqrt(var + 1e-5)
                       * jnp.asarray(g) + jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.pallas
def test_nn_layer_norm_takes_fused_path(monkeypatch):
    """The registered LayerNorm op routes channels-minor shapes through
    the fused kernel under TPUMX_PALLAS=1 and matches the XLA path."""
    from mxnet_tpu.ops.nn import layer_norm

    rng = np.random.RandomState(9)
    x = rng.randn(4, 8, 64).astype(np.float32)
    g = rng.rand(64).astype(np.float32)
    b = rng.randn(64).astype(np.float32)
    monkeypatch.setenv("TPUMX_PALLAS", "0")
    want = layer_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    monkeypatch.setenv("TPUMX_PALLAS", "1")
    got = layer_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # transformer _ln goes through the same kernel
    from mxnet_tpu.parallel.transformer import _ln
    got_ln = _ln(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got_ln), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.pallas
def test_transformer_train_step_grads_under_gate(monkeypatch):
    """A full LM train step with the fused-LN kernel in the graph matches
    the ungated step (custom-vjp backward is exact)."""
    from mxnet_tpu.parallel import transformer as tr

    cfg = tr.TransformerConfig(vocab=24, d_model=32, n_heads=2, n_layers=2,
                               d_ff=64, max_len=32)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(1))
    momenta = {k: jnp.zeros_like(v) for k, v in params.items()}
    rs = np.random.RandomState(10)
    toks = jnp.asarray(rs.randint(0, 24, (2, 16)).astype(np.int32))
    labels = jnp.asarray(rs.randint(0, 24, (2, 16)).astype(np.int32))
    pos = jnp.arange(16, dtype=jnp.int32)

    def step(gate):
        import os
        os.environ["TPUMX_PALLAS"] = gate
        return tr.train_step(params, momenta, toks, labels, pos, cfg)

    monkeypatch.setenv("TPUMX_PALLAS", "1")
    loss1, p1, _ = step("1")
    loss0, p0, _ = step("0")
    np.testing.assert_allclose(float(loss1), float(loss0), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p0[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_env_name_canonical_and_alias(monkeypatch):
    """TPUMX_PALLAS_INTERPRET is the one spelling; the pre-rename MXTPU_
    alias is gone and no longer read."""
    monkeypatch.delenv("TPUMX_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "1")
    assert pk._use_interpret() is True
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "0")
    assert pk._use_interpret() is False
    monkeypatch.delenv("TPUMX_PALLAS_INTERPRET")
    default = pk._use_interpret()          # CPU tests: the interpreter
    monkeypatch.setenv("MXTPU_" + "PALLAS_INTERPRET", "0")
    assert pk._use_interpret() is default


@pytest.mark.pallas
def test_executor_signature_keys_pallas_gate(monkeypatch):
    """TPUMX_PALLAS=0 executor signatures are byte-identical to the
    pre-kernel layout (no tag); =1 appends a ("pallas", 1) entry so the
    two implementations never share a cached program."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    data = sym.Variable("data")
    net = sym.SoftmaxOutput(sym.FullyConnected(data, num_hidden=4),
                            sym.Variable("softmax_label"))
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 8), softmax_label=(2,))
    monkeypatch.setenv("TPUMX_PALLAS", "0")
    sig_off = ex._signature(True)
    assert not any(isinstance(s, tuple) and s[0] == "pallas"
                   for s in sig_off)
    monkeypatch.setenv("TPUMX_PALLAS", "1")
    sig_on = ex._signature(True)
    assert ("pallas", 1) in sig_on
    assert [s for s in sig_on if s != ("pallas", 1)] == list(sig_off)


def test_pallas_gate_semantics(monkeypatch):
    monkeypatch.setenv("TPUMX_PALLAS", "1")
    assert pk.pallas_enabled() is True
    monkeypatch.setenv("TPUMX_PALLAS", "0")
    assert pk.pallas_enabled() is False
    monkeypatch.delenv("TPUMX_PALLAS")
    # unset: follows the backend (on for TPU, off elsewhere)
    assert pk.pallas_enabled() is (jax.default_backend() == "tpu")


def test_bn_one_pass_stats_precision_large_mean():
    """The one-pass stats are pivot-recentered: large mean/std must not
    cancel catastrophically (raw E[x^2]-mean^2 measured 58% var error on
    this fixture)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops.nn import batch_norm

    rng = np.random.RandomState(11)
    x = (rng.randn(4, 8, 8, 128) * 0.5 + 300.0).astype(np.float32)
    v_ref = x.reshape(-1, 128).astype(np.float64).var(0)

    _, _, var = pk.bn_train_fused(jnp.asarray(x), jnp.ones(128),
                                  jnp.zeros(128), 1e-3, -1)
    rel = np.abs(np.asarray(var) - v_ref) / v_ref
    assert rel.max() < 1e-2, rel.max()

    _, mean2, var2 = batch_norm(
        jnp.asarray(x), jnp.ones(128), jnp.zeros(128), jnp.zeros(128),
        jnp.ones(128), eps=1e-3, fix_gamma=False, axis=-1,
        output_mean_var=True, _training=True)
    rel2 = np.abs(np.asarray(var2) - v_ref) / v_ref
    assert rel2.max() < 1e-2, rel2.max()
