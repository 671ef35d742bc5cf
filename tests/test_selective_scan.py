"""The selective scan (ops/selective_scan.py) and differential attention
(ops/diff_attention.py) against forms written out here by hand: the scan
position by position in one ``lax.scan`` that knows no chunk, no slot and no
pool; differential attention as its four softmaxes a KV pair.  The plain
bodies and, through the interpreter, the Pallas calls.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import diff_attention as da
from mxnet_tpu.ops import selective_scan as ss

N, K = 16, 4


def _rand(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _inputs(B, T, di, seed=0):
    step = 0.05 * jnp.abs(_rand(seed, B, T, di)) + 1e-3
    return (step, _rand(seed + 1, B, T, di), _rand(seed + 2, B, T, N),
            _rand(seed + 3, B, T, N), -jnp.abs(_rand(seed + 4, N, di)) - 0.1)


def _by_hand(step, u, Bm, Cm, A, s0):
    """The recurrence, a position at a time over the whole sequence."""
    def one(s, xs):
        d, x, b, c = xs
        s = jnp.exp(d[:, None, :] * A) * s \
            + (d * x)[:, None, :] * b[:, :, None]
        return s, jnp.einsum("bnc,bn->bc", s, c)

    s, y = jax.lax.scan(one, s0, tuple(a.swapaxes(0, 1)
                                       for a in (step, u, Bm, Cm)))
    return y.swapaxes(0, 1), s


def _pool(L, S, di, seed=9):
    return _rand(seed, L, S, N + 8, di)


@pytest.fixture(autouse=True)
def _interpreted(monkeypatch):
    monkeypatch.setenv("TPUMX_PALLAS", "1")


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("T,chunk", [(32, 8), (32, 16), (16, 16)])
def test_chunks_carry_the_state_of_one_long_scan(kernel, T, chunk):
    """A sequence cut into chunks, the state carried through the pool from
    one call to the next, is the scan of the whole: outputs at every
    position and the state at the end; a chunk boundary is no seam."""
    B, di = 2, 128
    step, u, Bm, Cm, A = _inputs(B, T, di)
    want_y, want_s = _by_hand(step, u, Bm, Cm, A, jnp.zeros((B, N, di)))
    pool, slots = _pool(3, 5, di), jnp.asarray([3, 1])
    conv = _rand(7, B, K - 1, di)
    ys = []
    for t0 in range(0, T, chunk):
        cut = lambda a: a[:, t0:t0 + chunk]  # noqa: E731,B023
        y, pool = ss.selective_scan(
            cut(step), cut(u), cut(Bm), cut(Cm), A,
            jnp.full((B,), t0 == 0), pool, slots, conv, layer=1,
            kernel=kernel)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), want_y,
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(pool[1, slots, :N], want_s, atol=2e-5, rtol=0)
    # the convolution's inputs ride beside the state
    assert np.array_equal(
        ss.conv_state(pool, 1, slots, K, kernel=kernel), conv)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_a_leftover_chunks_padding_is_an_identity(kernel):
    """A chunk whose row ends before it does: the padded positions carry a
    step of 0, and the state behind the chunk is the state behind its last
    valid position, whatever the padding's inputs hold."""
    B, T, di, n = 1, 16, 256, 11
    step, u, Bm, Cm, A = _inputs(B, T, di, seed=20)
    step = step.at[:, n:].set(0.0)
    want_y, want_s = _by_hand(step[:, :n], u[:, :n], Bm[:, :n], Cm[:, :n], A,
                              jnp.zeros((B, N, di)))
    pool, slots = _pool(2, 3, di), jnp.asarray([2])
    y, pool = ss.selective_scan(
        step, u.at[:, n:].set(1e6), Bm, Cm, A, jnp.asarray([True]), pool,
        slots, _rand(3, B, K - 1, di), layer=0, kernel=kernel)
    np.testing.assert_allclose(y[:, :n], want_y, atol=2e-5, rtol=0)
    np.testing.assert_allclose(pool[0, slots, :N], want_s, atol=2e-5, rtol=0)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_the_decode_step_is_one_position_of_the_scan(kernel):
    """Steps of one token a row behind a chunk: the same recurrence; a row
    that is not fresh continues its slot's state, a fresh one starts from
    zero whatever its slot held; the other slots stay bit-equal."""
    B, T, di = 2, 12, 128
    step, u, Bm, Cm, A = _inputs(B, T, di, seed=40)
    want_y, _ = _by_hand(step, u, Bm, Cm, A, jnp.zeros((B, N, di)))
    pool, slots = _pool(2, 6, di), jnp.asarray([4, 2])
    untouched = np.asarray(pool[:, [0, 1, 3, 5]])
    conv = _rand(5, B, K - 1, di)
    _, pool = ss.selective_scan(
        step[:, :8], u[:, :8], Bm[:, :8], Cm[:, :8], A,
        jnp.asarray([True, True]), pool, slots, conv, layer=1, kernel=kernel)
    for t in range(8, T):
        cut = lambda a: a[:, t:t + 1]  # noqa: E731,B023
        y, pool = ss.selective_scan(
            cut(step), cut(u), cut(Bm), cut(Cm), A,
            jnp.asarray([False, False]), pool, slots, conv, layer=1,
            kernel=kernel)
        np.testing.assert_allclose(y[:, 0], want_y[:, t], atol=2e-5, rtol=0)
    assert np.array_equal(untouched[1], pool[1, [0, 1, 3, 5]])
    assert np.array_equal(untouched[0, :, :N], pool[0, [0, 1, 3, 5], :N])


def test_the_kernels_are_the_plain_bodies():
    B, di = 3, 256
    pool = _pool(2, 5, di)
    slots, fresh = jnp.asarray([1, 4, 0]), jnp.asarray([False, True, False])
    conv = _rand(6, B, K - 1, di)
    for T in (1, 8):
        args = _inputs(B, T, di, seed=60 + T)
        ya, pa = ss.selective_scan(*args, fresh, pool, slots, conv, layer=0,
                                   kernel=False)
        yb, pb = ss.selective_scan(*args, fresh, pool, slots, conv, layer=0,
                                   kernel=True)
        np.testing.assert_allclose(ya, yb, atol=2e-6, rtol=0)
        np.testing.assert_allclose(pa[:, :, :N + K - 1], pb[:, :, :N + K - 1],
                                   atol=2e-6, rtol=0)
    assert ss.state_shapes(5120, 16, 4) == (("ssm", (24, 5120)),)


# -- differential attention ---------------------------------------------------

def _four_softmaxes(q, k, v, mask, scale, lam, lam0, gain, eps):
    """Differential attention written out: query pair ``i`` = heads ``2i``,
    ``2i + 1`` reads KV pair ``i // 2`` = heads ``2p``, ``2p + 1``; each of
    its two softmaxes weights ``v[2p] | v[2p + 1]``."""
    B, T, H, d = q.shape
    out = []
    for i in range(H // 2):
        p = i // 2
        vv = jnp.concatenate([v[:, :, 2 * p], v[:, :, 2 * p + 1]], axis=-1)
        a = []
        for qh, kh in ((2 * i, 2 * p), (2 * i + 1, 2 * p + 1)):
            s = jnp.einsum("btd,bjd->btj", q[:, :, qh], k[:, :, kh]) * scale
            w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            a.append(jnp.einsum("btj,bje->bte", w, vv))
        x = a[0] - lam * a[1]
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        out.append(x * gain * (1.0 - lam0))
    return jnp.concatenate(out, axis=-1)


@pytest.mark.parametrize("window", [0, 6])
def test_one_read_of_k_and_v_is_the_four_softmaxes(window):
    """A KV pair as ONE head twice as wide, its four queries padded ``q1 |
    0`` and ``0 | q2``, then the subtraction, the norm and the scale: the
    four-softmax form."""
    B, T, H, hkv, d = 2, 10, 8, 4, 8
    q, k, v = (_rand(s, B, T, n, d) for s, n in ((1, H), (2, hkv), (3, hkv)))
    at = jnp.arange(T)
    mask = at[None, :] <= at[:, None]
    if window:
        mask &= at[:, None] - at[None, :] < window
    gain, lam, lam0, eps = 1 + 0.1 * _rand(4, 2 * d), 0.37, 0.61, 1e-5
    a = da.diff_attention_gathered(
        q, k.reshape(B, T, hkv * d), v.reshape(B, T, hkv * d),
        jnp.broadcast_to(mask, (B, T, T)), d ** -0.5)
    assert a.shape == (B, T, H, 2 * d)
    got = da.diff_combine(a, lam, lam0, gain, eps)
    want = _four_softmaxes(q, k, v, mask[None], d ** -0.5, lam, lam0, gain,
                           eps)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    padded = da.pad_queries(q)
    assert np.array_equal(padded[..., 0, :d], q[..., 0, :]) \
        and not padded[..., 0, d:].any()
    assert np.array_equal(padded[..., 1, d:], q[..., 1, :]) \
        and not padded[..., 1, :d].any()


@pytest.mark.parametrize("window,T,starts", [(0, 8, (0, 16)), (8, 8, (0, 16)),
                                             (0, 1, (3, 21)), (8, 1, (3, 21))])
def test_the_tiles_body_is_the_gathered_form(window, T, starts):
    """Through the interpreter: paged pools, a table of scattered blocks (a
    window kind's a ring), chunks and single queries."""
    from mxnet_tpu.parallel.hybrid_moe import _ring_positions

    B, H, hkv, d, bs, W, NB = 2, 8, 4, 8, 4, 8, 40
    kp, vp = _rand(1, 2, NB, bs, hkv * d), _rand(2, 2, NB, bs, hkv * d)
    table = jnp.asarray(np.random.default_rng(3).permutation(
        np.arange(1, NB))[:B * W].reshape(B, W), jnp.int32)
    q = _rand(4, B, T, H, d)
    pos = jnp.asarray(starts)[:, None] + jnp.arange(T)[None, :]
    got = da.diff_attention_paged(q, kp, vp, table, pos, pos[:, -1], 0.35,
                                  layer=1, call="test", window=window)
    if window:
        at = _ring_positions(jnp.maximum(pos[:, 0] - (window - 1), 0) // bs,
                             W, bs)[:, None, :]
        mask = (at <= pos[:, :, None]) & (at > pos[:, :, None] - window)
    else:
        mask = jnp.arange(W * bs)[None, None] <= pos[:, :, None]
    gather = lambda pool: pool[1][table].reshape(B, -1, hkv * d)  # noqa: E731
    want = da.diff_attention_gathered(q, gather(kp), gather(vp), mask, 0.35)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
