"""``ops/retention.py``: the state's layout, and the two Pallas calls
(through the interpreter) against their plain ``jax.numpy`` bodies and
against the attention form written out by hand.

Float32 on every side: the kernel folds a chunk's decay
into its operands where the plain body masks, and takes its sums a row of
``phi`` at a time, so the two differ by float32 rounding of sums whose terms
CANCEL (the entries of ``phi(q) . phi(k)`` sum in absolute value to many
times ``(q . k)^2``: some 50 times at d = 128, a few at d = 8) — 1e-4 at
d = 8 and 1e-3 over the 8,320 entries at d = 128, on outputs of spread
about 1.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import retention as rt

TOL = {8: 1e-4, 128: 1e-3}      # by the head's size


@pytest.fixture(autouse=True)
def _interpreted(monkeypatch):
    monkeypatch.setenv("TPUMX_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("d", [2, 8, 128])
def test_phi_is_the_symmetric_square(d):
    """``phi(a) . phi(b) = (a . b)^2``, in ``d / 2 + 1`` rows of d lanes:
    40 lanes at d = 8 for the 36 entries of the packed half, 8,320 at 128
    for its 8,256."""
    rng = np.random.default_rng(d)
    a, b = rng.normal(size=(2, 5, d)).astype(np.float32)
    pa, pb = rt.phi(jnp.asarray(a)), rt.phi(jnp.asarray(b))
    assert pa.shape == (5, rt.phi_rows(d), d)
    assert rt.phi_width(d) == (d // 2 + 1) * d >= rt.packed_width(d)
    # (the entries' terms cancel: the rounding is that of their absolute
    # sum, which |a|^2 |b|^2 bounds)
    np.testing.assert_allclose(
        np.sum(np.asarray(pa * pb), axis=(-1, -2)),
        np.sum(a * b, axis=-1) ** 2, rtol=2e-5,
        atol=1e-6 * float(np.max(np.sum(a * a, -1) * np.sum(b * b, -1))))
    if d == 128:
        assert (rt.phi_width(d), rt.packed_width(d)) == (8320, 8256)
    if d == 8:
        assert (rt.phi_width(d), rt.packed_width(d)) == (40, 36)


def _inputs(B, T, Hq, Hkv, d, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
    g = -rng.uniform(0.001, 0.2, size=(B, T, Hkv)).astype(np.float32)
    return f(B, T, Hq, d), f(B, T, Hkv, d), f(B, T, Hkv, d), g


def _pools(L, slots, Hkv, d, seed=1):
    """States as rows leave them: every slot of every layer carries 12
    seeded positions (a normaliser of random numbers would cancel where a
    real one, a sum of squares, cannot)."""
    pools = tuple(jnp.zeros((L, slots) + s, jnp.float32)
                  for _, s in rt.state_shapes(Hkv, d, d))
    for layer in range(L):
        q, k, v, g = (jnp.asarray(a) for a in _inputs(
            slots, 12, Hkv, Hkv, d, seed=seed + layer))
        _, *pools = rt.retention_prefill_reference(
            q, k, v, g, jnp.ones((slots,), bool), *pools,
            jnp.arange(slots), layer, 1e-6)
    return tuple(pools)


def _both(q, k, v, g, fresh, slots, pools, layer=1):
    out = []
    for kernel in (False, True):
        out.append(rt.retention(
            *(jnp.asarray(a) for a in (q, k, v, g)), jnp.asarray(fresh),
            *pools, jnp.asarray(slots, jnp.int32), layer=layer, eps=1e-6,
            kernel=kernel))
    return out


def _close(got, want, d):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL[d],
                                   rtol=TOL[d])


@pytest.mark.parametrize("Hq,Hkv,d", [(8, 2, 8), (5, 1, 128)],
                         ids=["tiny", "lanes128"])
def test_the_decode_call_is_its_plain_body(Hq, Hkv, d):
    """One token a row over carried states: a fresh row (its slot's old
    state must not show), a row on a carried state, an idle row on the
    scratch; the other layers and slots bit-equal."""
    B = 3
    q, k, v, g = _inputs(B, 1, Hq, Hkv, d)
    k[2], g[2] = 0.0, 0.0                   # the idle row: an identity
    pools = _pools(2, 4, Hkv, d)
    plain, call = _both(q, k, v, g, [True, False, False], [2, 3, 0], pools)
    _close(call, plain, d)
    for mine, was in zip(call[1:], pools):
        assert np.array_equal(np.asarray(mine[0]), np.asarray(was[0]))
        assert np.array_equal(np.asarray(mine[1, 1]), np.asarray(was[1, 1]))
        # the idle row wrote the scratch back as it was
        assert np.array_equal(np.asarray(mine[1, 0]), np.asarray(was[1, 0]))


@pytest.mark.parametrize("T,Hq,Hkv,d", [(8, 8, 2, 8), (16, 8, 2, 8),
                                        (8, 5, 1, 128)],
                         ids=["t8", "t16", "lanes128"])
def test_the_prefill_call_is_its_plain_body(T, Hq, Hkv, d):
    """A chunk a row: one row fresh, one on a carried state, the second
    half of whose chunk is padding (``g = 0``, ``k = 0``)."""
    B = 2
    q, k, v, g = _inputs(B, T, Hq, Hkv, d, seed=T)
    k[1, T // 2:], g[1, T // 2:] = 0.0, 0.0
    pools = _pools(2, 3, Hkv, d)
    plain, call = _both(q, k, v, g, [True, False], [1, 2], pools)
    # (a padded query's output is whatever: compare the valid ones)
    _close([call[0][0], call[0][1, :T // 2], *call[1:]],
           [plain[0][0], plain[0][1, :T // 2], *plain[1:]], d)
    for mine, was in zip(call[1:], pools):
        assert np.array_equal(np.asarray(mine[0]), np.asarray(was[0]))
        assert np.array_equal(np.asarray(mine[1, 0]), np.asarray(was[1, 0]))


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "call"])
@pytest.mark.parametrize("chunks", [[24], [8, 16], [16, 1, 1, 1, 1, 1, 1, 1,
                                                    1]],
                         ids=["whole", "chunks", "chunk_then_steps"])
def test_the_three_forms_agree(kernel, chunks):
    """The attention form written out by hand = the chunked form at any
    cut = a chunk and then one-token steps through the state."""
    B, T, Hq, Hkv, d = 2, 24, 4, 2, 8
    q, k, v, g = _inputs(B, T, Hq, Hkv, d, seed=5)
    G = np.cumsum(g, axis=1)
    want = np.zeros((B, T, Hq, d))
    for b in range(B):
        for h in range(Hq):
            n = h // (Hq // Hkv)
            for t in range(T):
                w = np.exp(G[b, t, n] - G[b, :t + 1, n]) \
                    * (k[b, :t + 1, n] @ q[b, t, h]) ** 2
                want[b, t, h] = w @ v[b, :t + 1, n] / (w.sum() + 1e-6)
    pools = _pools(2, 3, Hkv, d)
    outs, off = [], 0
    for c in chunks:
        at = slice(off, off + c)
        o, *pools = rt.retention(
            *(jnp.asarray(a[:, at]) for a in (q, k, v, g)),
            jnp.asarray([off == 0] * B), *pools, jnp.asarray([1, 2]),
            layer=0, eps=1e-6, kernel=kernel)
        outs.append(np.asarray(o))
        off += c
    np.testing.assert_allclose(np.concatenate(outs, axis=1), want,
                               atol=TOL[d], rtol=TOL[d])


def test_a_padded_chunk_and_an_idle_step_are_identities_on_the_state():
    """``g = 0`` and ``k = 0`` everywhere: ``S 1 + 0`` — both calls hand
    the state (``S`` over ``z``) back bit-equal (the plain bodies too)."""
    Hq, Hkv, d = 8, 2, 8
    pools = _pools(1, 2, Hkv, d)
    for T in (1, 8):
        q, k, v, g = _inputs(1, T, Hq, Hkv, d)
        k[:], g[:] = 0.0, 0.0
        for _, state in _both(q, k, v, g, [False], [1], pools, layer=0):
            assert np.array_equal(np.asarray(state), np.asarray(pools[0]))
