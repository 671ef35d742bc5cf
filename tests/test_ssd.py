"""Mamba-2's scan (ops/ssd.py) against the recurrence written out here by
hand: a position at a time in one ``lax.scan`` over a head's ``P x N``
matrix state, which knows no chunk, no slot, no pool and no duality.  The
plain bodies (the matrix form over one chunk, the one-token step) and,
through the interpreter, the Pallas calls.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import ssd

N, K = 16, 4
TOL = 3e-5      # float32 sums in another order; outputs of spread ~1


def _rand(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _inputs(B, T, H, P, seed=0, fast=False):
    """Steps of 1e-3..5e-2 a head a position — or, ``fast``, up to 3: a
    head that forgets everything in one position (``D A`` of -40), which a
    decay folded into the operands could not hold."""
    step = (3.0 if fast else 0.05) * jnp.abs(_rand(seed, B, T, H)) + 1e-3
    A = -jnp.abs(_rand(seed + 4, H)) * (8.0 if fast else 1.0) - 0.1
    return (step, _rand(seed + 1, B, T, H * P), _rand(seed + 2, B, T, N),
            _rand(seed + 3, B, T, N), A)


def _by_hand(step, x, Bm, Cm, A, s0=None):
    """The recurrence over the whole sequence: ``(y (B, T, H P), S (B, H,
    P, N))``."""
    B, T, H = step.shape
    P = x.shape[2] // H
    s0 = jnp.zeros((B, H, P, N)) if s0 is None else s0

    def one(s, xs):
        d, u, b, c = xs
        s = jnp.exp(d * A)[:, :, None, None] * s \
            + (d[:, :, None] * u.reshape(B, H, P))[..., None] \
            * b[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, c).reshape(B, H * P)

    s, y = jax.lax.scan(one, s0, tuple(a.swapaxes(0, 1)
                                       for a in (step, x, Bm, Cm)))
    return y.swapaxes(0, 1), s


def _as_stored(s):
    """``S (B, H, P, N)`` as the pool keeps it: ``(B, N, H P)``."""
    B, H, P, n = s.shape
    return s.transpose(0, 3, 1, 2).reshape(B, n, H * P)


def _pool(L, S, di, seed=9):
    return _rand(seed, L, S, N + 8, di)


def _conv(seed, B, di):
    return _rand(seed, B, K - 1, di + 2 * N)


@pytest.fixture(autouse=True)
def _interpreted(monkeypatch):
    monkeypatch.setenv("TPUMX_PALLAS", "1")


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("T,chunk,fast", [
    (32, 8, False), (32, 16, False), (16, 16, False), (48, 12, False),
    (24, 8, True)])
def test_every_chunking_of_one_sequence_gives_one_answer(kernel, T, chunk,
                                                         fast):
    """A sequence cut into chunks, the state carried through the pool from
    one call to the next, is the recurrence over the whole: outputs at
    every position and the state at the end; a chunk boundary is no seam
    (one of 12 positions is not a multiple of 8: its last sublanes are
    padding).  ``fast``: decays that underflow inside one chunk."""
    B, H, P = 2, 4, 32
    step, x, Bm, Cm, A = _inputs(B, T, H, P, fast=fast)
    want_y, want_s = _by_hand(step, x, Bm, Cm, A)
    pool, slots = _pool(3, 5, H * P), jnp.asarray([3, 1])
    conv = _conv(7, B, H * P)
    ys = []
    for t0 in range(0, T, chunk):
        cut = lambda a: a[:, t0:t0 + chunk]  # noqa: E731,B023
        y, pool = ssd.ssd(cut(step), cut(x), cut(Bm), cut(Cm), A,
                          jnp.full((B,), t0 == 0), pool, slots, conv,
                          layer=1, kernel=kernel)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), want_y,
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(pool[1, slots, :N], _as_stored(want_s),
                               atol=TOL, rtol=TOL)
    # the convolution's inputs ride beside the state
    assert np.array_equal(
        ssd.conv_state(pool, 1, slots, K, 2 * N, kernel=kernel), conv)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_a_chunk_of_several_sub_chunks_is_the_recurrence(kernel):
    """320 positions in ONE call: the kernel walks 3 sub-chunks of 128 (the
    last 64 positions padding it adds itself), the state riding from one to
    the next inside the call; heads of 64 two to a lane group, as
    published."""
    B, T, H, P = 1, 320, 4, 64
    step, x, Bm, Cm, A = _inputs(B, T, H, P, seed=11)
    want_y, want_s = _by_hand(step, x, Bm, Cm, A)
    pool, slots = _pool(1, 2, H * P), jnp.asarray([1])
    y, pool = ssd.ssd(step, x, Bm, Cm, A, jnp.asarray([True]), pool, slots,
                      _conv(2, B, H * P), layer=0, kernel=kernel)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(pool[0, slots, :N], _as_stored(want_s),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_a_leftover_chunks_padding_is_an_identity(kernel):
    """A chunk whose row ends before it does: the padded positions carry a
    step of 0, and the state behind the chunk is the state behind its last
    valid position, whatever the padding's inputs hold."""
    B, T, H, P, n = 1, 16, 2, 128, 11
    step, x, Bm, Cm, A = _inputs(B, T, H, P, seed=20)
    step = step.at[:, n:].set(0.0)
    want_y, want_s = _by_hand(step[:, :n], x[:, :n], Bm[:, :n], Cm[:, :n], A)
    pool, slots = _pool(2, 3, H * P), jnp.asarray([2])
    y, pool = ssd.ssd(step, x.at[:, n:].set(1e6), Bm, Cm, A,
                      jnp.asarray([True]), pool, slots, _conv(3, B, H * P),
                      layer=0, kernel=kernel)
    np.testing.assert_allclose(y[:, :n], want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(pool[0, slots, :N], _as_stored(want_s),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_decode_after_prefill_is_the_full_pass(kernel):
    """Steps of one token a row behind a chunk: the same recurrence; a row
    that is not fresh continues its slot's state; an IDLE row (step 0,
    slot 0) and every other slot stay bit-equal."""
    B, T, H, P = 3, 12, 4, 32
    step, x, Bm, Cm, A = _inputs(B, T, H, P, seed=40)
    want_y, _ = _by_hand(step, x, Bm, Cm, A)
    step = step.at[2].set(0.0)                          # the idle row
    pool, slots = _pool(2, 6, H * P), jnp.asarray([4, 2, 0])
    untouched = np.asarray(pool[:, [0, 1, 3, 5]])
    conv = _conv(5, B, H * P).at[2].set(pool[1, 0, N:N + K - 1, :1])
    fresh = jnp.asarray([True, True, False])
    _, pool = ssd.ssd(step[:, :8], x[:, :8], Bm[:, :8], Cm[:, :8], A, fresh,
                      pool, slots, conv, layer=1, kernel=kernel)
    for t in range(8, T):
        cut = lambda a: a[:, t:t + 1]  # noqa: E731,B023
        y, pool = ssd.ssd(cut(step), cut(x), cut(Bm), cut(Cm), A,
                          jnp.zeros((B,), bool), pool, slots, conv, layer=1,
                          kernel=kernel)
        np.testing.assert_allclose(y[:2, 0], want_y[:2, t], atol=TOL, rtol=TOL)
    assert np.array_equal(untouched[0], pool[0, [0, 1, 3, 5]])
    assert np.array_equal(untouched[1, :, :N], pool[1, [0, 1, 3, 5], :N])


def test_a_fresh_row_starts_from_zero_whatever_its_slot_held():
    B, T, H, P = 2, 8, 4, 32
    args = _inputs(B, T, H, P, seed=50)
    slots, conv = jnp.asarray([1, 2]), _conv(1, B, H * P)
    for kernel in (False, True):
        ya, _ = ssd.ssd(*args, jnp.asarray([True, True]),
                        _pool(1, 3, H * P, seed=1), slots, conv, layer=0,
                        kernel=kernel)
        yb, _ = ssd.ssd(*args, jnp.asarray([True, True]),
                        jnp.zeros((1, 3, N + 8, H * P)), slots, conv, layer=0,
                        kernel=kernel)
        assert np.array_equal(ya, yb)


def test_the_kernels_are_the_plain_bodies():
    B, H, P = 3, 2, 128
    pool = _pool(2, 5, H * P)
    slots, fresh = jnp.asarray([1, 4, 0]), jnp.asarray([False, True, False])
    conv = _conv(6, B, H * P)
    for T in (1, 8, 20):
        args = _inputs(B, T, H, P, seed=60 + T)
        ya, pa = ssd.ssd(*args, fresh, pool, slots, conv, layer=0,
                         kernel=False)
        yb, pb = ssd.ssd(*args, fresh, pool, slots, conv, layer=0,
                         kernel=True)
        np.testing.assert_allclose(ya, yb, atol=TOL, rtol=TOL)
        np.testing.assert_allclose(pa, pb, atol=TOL, rtol=TOL)


def test_the_state_as_stored():
    """64 heads of 64 over 128 entries: 136 sublanes of 4,096 lanes a
    layer, 80.2 MB a slot over 36 layers for the 77.4 of the mathematics;
    the convolution's 3 x 4,352 inputs in the last tile, ``x`` over ``B |
    C``."""
    assert ssd.state_shapes(64, 64, 128, 4) == (("ssd", (136, 4096)),)
    assert 36 * 136 * 4096 * 4 == 80216064
    assert 36 * (64 * 64 * 128 + 3 * 4352) * 4 == 77377536
    conv = _rand(0, 2, 3, 256 + 32)
    tile = ssd._pack_conv(conv, 256)
    assert tile.shape == (2, 8, 256)
    assert np.array_equal(tile[:, :3], conv[:, :, :256])
    assert np.array_equal(tile[:, 3:6, :32], conv[:, :, 256:])
    assert not tile[:, 3:6, 32:].any() and not tile[:, 6:].any()
    assert np.array_equal(ssd._unpack_conv(tile, 4, 32), conv)
