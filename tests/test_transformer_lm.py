"""Long-context Transformer LM (SURVEY §5.7): the mesh-first decoder model
in parallel/transformer.py — causality, sp-sharded forward/step vs the
single-device oracle, and convergence on a learnable corpus.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel import transformer as tr

CFG = tr.TransformerConfig(vocab=40, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=128)
RS = np.random.RandomState(0)


def _params(seed=0):
    return tr.transformer_lm_init(CFG, jax.random.PRNGKey(seed))


def _batch(B=4, T=32):
    tokens = RS.randint(0, CFG.vocab, (B, T)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    return (jnp.asarray(tokens), jnp.asarray(labels),
            jnp.arange(T, dtype=jnp.int32))


def test_causality():
    """Perturbing token t must change logits only at positions >= t."""
    params = _params()
    tokens, _, positions = _batch(B=1, T=16)
    base = tr.transformer_lm_apply(params, tokens, positions, CFG)
    t = 9
    mutated = tokens.at[0, t].set((tokens[0, t] + 1) % CFG.vocab)
    out = tr.transformer_lm_apply(params, mutated, positions, CFG)
    diff = np.abs(np.asarray(out - base))[0].max(axis=-1)
    assert np.all(diff[:t] < 1e-5), "future token leaked into the past"
    assert diff[t] > 1e-4, "perturbation had no effect at its own position"


def test_sp_sharded_step_equals_oracle():
    """One dp×sp=2×4 sharded train step reproduces the single-device step
    (ring attention fwd+bwd, psum'd grads, replicated update)."""
    params = _params()
    tokens, labels, positions = _batch(B=4, T=32)
    mesh = make_mesh({"dp": 2, "sp": 4})
    step = tr.make_sharded_train_step(mesh, CFG, lr=0.1)
    p2 = {k: jnp.array(v) for k, v in params.items()}
    m2 = {k: jnp.zeros_like(v) for k, v in params.items()}
    loss_s, p2, m2 = step(p2, m2, *tr.shard_batch(mesh, tokens, labels,
                                                  positions))
    loss1, p1, _ = jax.jit(
        lambda p, m: tr.train_step(p, m, tokens, labels, positions, CFG,
                                   lr=0.1))(
        {k: jnp.array(v) for k, v in params.items()},
        {k: jnp.zeros_like(v) for k, v in params.items()})
    assert abs(float(loss_s) - float(loss1)) < 1e-4
    for k in p1:
        np.testing.assert_allclose(np.asarray(p2[k]), np.asarray(p1[k]),
                                   atol=2e-4, err_msg=k)


def test_pure_sp_mesh_long_sequence():
    """sp=8 with T=8*shard: the whole sequence axis rides the ring."""
    params = _params(seed=1)
    tokens, labels, positions = _batch(B=2, T=64)
    mesh = make_mesh({"dp": 1, "sp": 8})
    step = tr.make_sharded_train_step(mesh, CFG, lr=0.05)
    p = {k: jnp.array(v) for k, v in params.items()}
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    loss0 = None
    for _ in range(3):
        loss, p, m = step(p, m, *tr.shard_batch(mesh, tokens, labels,
                                                positions))
        loss0 = loss0 if loss0 is not None else float(loss)
    assert float(loss) < loss0, "sharded training did not reduce loss"


def test_converges_on_successor_chain():
    """Deterministic successor corpus: a tiny LM must drive the loss near
    zero (every next token is predictable from the previous one)."""
    params = _params(seed=2)
    B, T = 8, 16
    start = RS.randint(0, CFG.vocab, (B, 1))
    tokens = (start + np.arange(T)[None, :]) % CFG.vocab
    tokens = tokens.astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    toks, labs = jnp.asarray(tokens), jnp.asarray(labels)
    positions = jnp.arange(T, dtype=jnp.int32)
    step = jax.jit(lambda p, m: tr.train_step(p, m, toks, labs, positions,
                                              CFG, lr=0.3))
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    first = None
    for i in range(80):
        loss, params, m = step(params, m)
        first = first if first is not None else float(loss)
    assert float(loss) < 0.15 * first, (first, float(loss))


def test_loss_mask_excludes_padding():
    params = _params()
    tokens, labels, positions = _batch(B=2, T=8)
    mask = jnp.asarray(np.array([[1] * 8, [1] * 4 + [0] * 4], np.float32))
    full = tr.lm_loss(params, tokens, labels, positions, CFG)
    masked = tr.lm_loss(params, tokens, labels, positions, CFG, mask=mask)
    assert not np.isclose(float(full), float(masked))
    # all-masked second row == loss of first row alone
    only_first = tr.lm_loss(params, tokens[:1], labels[:1], positions, CFG)
    m2 = jnp.asarray(np.array([[1] * 8, [0] * 8], np.float32))
    np.testing.assert_allclose(
        float(tr.lm_loss(params, tokens, labels, positions, CFG, mask=m2)),
        float(only_first), rtol=1e-5)


@pytest.mark.parametrize("sp_impl", ["ulysses", "ulysses_flash"])
def test_ulysses_step_equals_oracle(sp_impl):
    """sp_impl="ulysses[_flash]": the all_to_all head-sharding path — with
    dense or streaming-Pallas inner attention — reproduces the same
    single-device step the ring does."""
    params = _params(seed=3)
    tokens, labels, positions = _batch(B=4, T=32)
    mesh = make_mesh({"dp": 2, "sp": 4})  # n_heads=4 % sp=4 == 0
    step = tr.make_sharded_train_step(mesh, CFG, lr=0.1, sp_impl=sp_impl)
    p2 = {k: jnp.array(v) for k, v in params.items()}
    m2 = {k: jnp.zeros_like(v) for k, v in params.items()}
    loss_s, p2, _ = step(p2, m2, *tr.shard_batch(mesh, tokens, labels,
                                                 positions))
    loss1, p1, _ = jax.jit(
        lambda p, m: tr.train_step(p, m, tokens, labels, positions, CFG,
                                   lr=0.1))(
        {k: jnp.array(v) for k, v in params.items()},
        {k: jnp.zeros_like(v) for k, v in params.items()})
    assert abs(float(loss_s) - float(loss1)) < 1e-4
    for k in p1:
        np.testing.assert_allclose(np.asarray(p2[k]), np.asarray(p1[k]),
                                   atol=2e-4, err_msg=k)
    with pytest.raises(ValueError):
        tr.make_sharded_train_step(make_mesh({"dp": 1, "sp": 8}), CFG,
                                   sp_impl="ulysses")  # 4 heads % 8 != 0
    with pytest.raises(ValueError):
        tr.make_sharded_train_step(mesh, CFG, sp_impl="nope")


def test_bf16_compute_trains_close_to_f32():
    """compute_dtype=bfloat16 (f32 master weights): the loss trajectory
    stays close to f32 on a short run — the MXU recipe for the chip."""
    params = _params(seed=4)
    tokens, labels, positions = _batch(B=4, T=16)

    def run(dtype):
        p = {k: jnp.array(v) for k, v in params.items()}
        m = {k: jnp.zeros_like(v) for k, v in params.items()}
        step = jax.jit(lambda p, m: tr.train_step(
            p, m, tokens, labels, positions, CFG, lr=0.1,
            compute_dtype=dtype))
        for _ in range(5):
            loss, p, m = step(p, m)
        return float(loss), p

    (lf32, _), (lbf16, p16) = run(None), run(jnp.bfloat16)
    assert abs(lf32 - lbf16) / lf32 < 0.05, (lf32, lbf16)
    # the TRAINED params under bf16 compute are still f32 master copies
    assert all(v.dtype == jnp.float32 for v in p16.values())


@pytest.mark.generation
@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_prefill_vs_decode_logits_parity(compute_dtype):
    """Satellite (docs/generation.md): a bucketed cache-writing prefill
    followed by T=1 decode steps reproduces transformer_lm_apply's
    full-sequence logits to rtol 1e-5, in f32 and bf16."""
    params = _params(seed=2)
    apply_params = params if compute_dtype is None else \
        jax.tree_util.tree_map(lambda p: p.astype(compute_dtype), params)
    plen, extra, bs = 11, 4, 8
    tokens = RS.randint(0, CFG.vocab, plen + extra).astype(np.int32)
    kp = jnp.zeros((CFG.n_layers, 8, bs, CFG.d_model),
                   compute_dtype or jnp.float32)
    vp = jnp.zeros_like(kp)
    table = np.array([[1, 2]], np.int32)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :plen] = tokens[:plen]
    logits, kp, vp = tr.transformer_lm_decode(
        params, padded, np.arange(16, dtype=np.int32)[None, :],
        np.asarray([plen], np.int32), kp, vp, table, CFG,
        compute_dtype=compute_dtype)
    got = [np.asarray(logits[0, :plen])]
    for i in range(extra):
        step_logits, kp, vp = tr.transformer_lm_decode(
            params, tokens[None, plen + i:plen + i + 1],
            np.asarray([[plen + i]], np.int32), np.asarray([1], np.int32),
            kp, vp, table, CFG, compute_dtype=compute_dtype)
        got.append(np.asarray(step_logits[0]))
    full = np.asarray(tr.transformer_lm_apply(
        apply_params, jnp.asarray(tokens[None, :], dtype=jnp.int32),
        jnp.arange(plen + extra, dtype=jnp.int32), CFG)
    ).astype(np.float32)
    np.testing.assert_allclose(np.concatenate(got, axis=0), full[0],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.generation
def test_single_position_apply_uses_slice_path():
    """T=1 transformer_lm_apply (the decode-shaped call) slices one
    pos_emb row instead of gathering the table — same logits as the
    corresponding column of a full-sequence call."""
    params = _params(seed=3)
    tokens, _, positions = _batch(B=2, T=8)
    full = tr.transformer_lm_apply(params, tokens, positions, CFG)
    one = tr.transformer_lm_apply(params, tokens[:, :1],
                                  jnp.asarray([0], dtype=jnp.int32), CFG)
    np.testing.assert_allclose(np.asarray(one[:, 0]),
                               np.asarray(full[:, 0]), rtol=1e-6,
                               atol=1e-6)
    jaxpr = str(jax.make_jaxpr(
        lambda p, t, pos: tr.transformer_lm_apply(p, t, pos, CFG))(
        params, tokens[:, :1], jnp.asarray([0], dtype=jnp.int32)))
    assert "dynamic_slice" in jaxpr
