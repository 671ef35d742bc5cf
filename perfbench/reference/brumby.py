"""Plain reference of ``Brumby-14B-Base`` (``model_type`` ``brumby``: the
Qwen3-14B block with POWER RETENTION in the place of softmax attention) as
the ``retention_decode`` driver serves it.  Imports nothing of the program.

The forward pass: the whole sequence at once in ``jax.numpy``, float32 at
matmul precision ``highest``; no state, no chunks, no cache, no kernel, no
batching.  Retention in its ATTENTION form, the weights materialised, a
block of queries at a time against all the keys (``h`` the residual stream,
``t`` a position, ``n`` a KV head, ``m`` one of the 5 query heads of its
group; d = 128; degree p = 2)::

    x      = rms(h, g1)
    q[t,m] = rope_t(rms_head(Wq x_t)[m]);  k[t,n] = rope_t(rms_head(Wk x_t)[n]);  v[t,n] = (Wv x_t)[n]
    g[t,n] = log sigmoid((Wgate x_t + bgate)[n])                 <= 0: the log of the decay
    G[t,n] = sum_{s<=t} g[s,n]
    w[t,j] = exp(G[t,n] - G[j,n]) (q[t,m] . k[j,n])^p            j <= t, else 0
    o[t,m] = sum_j w[t,j] v[j,n] / (sum_j w[t,j] + eps)
    h = h + Wo concat_m o[t,m];   h = h + Wd (silu(Wg y) * Wu y),  y = rms(h, g2)
    logits = rms(h, gf) Wh                                       (untied head)

which the serving path keeps as ``S_t = e^g S_{t-1} + phi(k_t) v_t^T``,
``z_t = e^g z_{t-1} + phi(k_t)``, ``o = phi(q)^T S / (phi(q) . z + eps)``
with ``phi`` the symmetric square: the same sums in another order.  A scale
on ``q . k`` cancels between numerator and denominator, so none is a term.

What the source's ``config.json`` does not carry, each the program's too
(``assumed`` in the configuration's file): p = 2; the gate a per-KV-head
linear map with bias through log-sigmoid; per-head q/k RMS norms and
rotate-half rotary over the whole head; ``eps`` 1e-6; the package's switch
to this form below a sequence length is left out (the same sums).

``dtype`` float32 is the reference; bfloat16 is the control, one precision
down: norms, gates and their running sums, the powers, the weighted sums
and their normaliser, and every product's result in bfloat16.  ``fault``
plants one on the reference's side: ``"no_gate"`` (g = 0: nothing is ever
forgotten) and ``"no_norm"`` (the denominator left out).

The weights are the benchmark's: bfloat16 values made on the device from
the seed, one jitted call a layer, in the parameter layout the service
takes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128        # queries a block of the weights
F_BLOCK = 1024       # rows a block of the feed-forward layer


def _dims(c):
    """The sizes the functions here read from a configuration file."""
    a = c.get("assumed_values", {})
    return dict(
        n_layers=c["num_hidden_layers"], d=c["hidden_size"],
        H=c["num_attention_heads"], hkv=c["num_key_value_heads"],
        D=c["head_dim"], F=c["intermediate_size"], vocab=c["vocab_size"],
        theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        p=int(a.get("power", 2)), ret_eps=float(a.get("retention_eps", 1e-6)))


def init_params(seed, c, dtype="bfloat16"):
    """Seeded scaled-normal weights in ``dtype`` (scales as the other
    references': embedding 0.1, products 1/sqrt(fan-in), residual outputs
    divided by sqrt(2 x layers), norm gains 1 + 0.1 n, so that one left
    out shows).  The gate: weights 0.2/sqrt(fan-in) and a bias uniform in
    5.2-7.0 a head, so that a head's decay a step, sigmoid of their sum,
    lies between about 0.99 and 0.9995 — a gate at sigmoid(0) forgets in
    ten positions and would hide every fault of the carried state."""
    m = _dims(c)
    d, H, hkv, D, F, V = m["d"], m["H"], m["hkv"], m["D"], m["F"], m["vocab"]
    dt = jnp.dtype(dtype)
    res = 1.0 / math.sqrt(2.0 * m["n_layers"])

    def normal(key, i, shape, scale):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dt)

    def gain(key, i, n):
        return (1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                              (n,), jnp.float32)).astype(dt)

    @jax.jit
    def ends(key):
        return {"tok_emb": normal(key, 0, (V, d), 0.1),
                "head": normal(key, 1, (d, V), d ** -0.5),
                "norm_f": gain(key, 2, d)}

    @jax.jit
    def layer(key):
        return {"norm1": gain(key, 0, d),
                "wq": normal(key, 1, (d, H * D), d ** -0.5),
                "wk": normal(key, 2, (d, hkv * D), d ** -0.5),
                "wv": normal(key, 3, (d, hkv * D), d ** -0.5),
                "wo": normal(key, 4, (H * D, d), (H * D) ** -0.5 * res),
                "q_norm": gain(key, 5, D), "k_norm": gain(key, 6, D),
                "wgate": normal(key, 7, (d, hkv), 0.2 * d ** -0.5),
                "bgate": jax.random.uniform(
                    jax.random.fold_in(key, 8), (hkv,), jnp.float32, 5.2,
                    7.0).astype(dt),
                "norm2": gain(key, 9, d),
                "wg": normal(key, 10, (d, F), d ** -0.5),
                "wu": normal(key, 11, (d, F), d ** -0.5),
                "wd": normal(key, 12, (F, d), F ** -0.5 * res)}

    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    p = ends(key)
    for i in range(m["n_layers"]):
        for name, w in layer(jax.random.fold_in(key, 100 + i)).items():
            p[f"l{i}_{name}"] = w
    return p


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x`` (T, H, D) at positions 0.. ,
    over the whole head."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos.astype(x.dtype) + rot * sin.astype(x.dtype)


def _retention(q, k, v, g, n_valid, p, eps, fault):
    """Retention in the attention form: ``q`` (T, Hkv, G, D) against ``k``,
    ``v`` (T, Hkv, D) with log decays ``g`` (T, Hkv), a block of queries at
    a time against all the keys; a key at or past ``n_valid`` is masked."""
    T = q.shape[0]
    qb = min(Q_BLOCK, T)
    assert T % qb == 0
    if fault == "no_gate":
        g = jnp.zeros_like(g)
    Gs = jnp.cumsum(g, axis=0)                            # (T, Hkv)
    keys = jnp.arange(T)

    def block(args):
        qi, Gi, i0 = args
        at = i0 + jnp.arange(qb)
        mask = (keys[None, :] <= at[:, None]) & (keys[None, :] < n_valid)
        s = jnp.einsum("qngd,knd->ngqk", qi, k)
        decay = jnp.exp(jnp.where(mask[None], Gi.T[:, :, None]
                                  - Gs.T[:, None, :], -jnp.inf))
        w = s ** p * decay[:, None]                       # (Hkv, G, qb, T)
        num = jnp.einsum("ngqk,knd->qngd", w, v)
        if fault == "no_norm":
            return num
        den = jnp.sum(w, axis=-1).transpose(2, 0, 1)      # (qb, Hkv, G)
        return num / (den[..., None] + jnp.asarray(eps, den.dtype))

    out = jax.lax.map(block, (q.reshape(T // qb, qb, *q.shape[1:]),
                              Gs.reshape(T // qb, qb, -1),
                              jnp.arange(0, T, qb)))
    return out.reshape(T, -1)


def _gated(h, wg, wu, wd, dt):
    """The feed-forward layer, a block of rows at a time."""
    T = h.shape[0]
    fb = min(F_BLOCK, T)
    assert T % fb == 0
    wg, wu, wd = wg.astype(dt), wu.astype(dt), wd.astype(dt)
    out = jax.lax.map(lambda y: (jax.nn.silu(y @ wg) * (y @ wu)) @ wd,
                      h.reshape(T // fb, fb, -1))
    return out.reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("dtype", "n_at", "dims",
                                             "fault"))
def _forward(p, tokens, n_valid, at0, *, dtype, n_at, dims, fault):
    m = dict(dims)
    dt = jnp.dtype(dtype)
    up = lambda a: a.astype(dt)  # noqa: E731
    T = tokens.shape[0]
    H, hkv, D, eps = m["H"], m["hkv"], m["D"], m["eps"]
    x = up(p["tok_emb"][tokens])
    for i in range(m["n_layers"]):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        h = _rms(x, up(g("norm1")), eps)
        q = _rope(_rms((h @ up(g("wq"))).reshape(T, H, D), up(g("q_norm")),
                       eps), m["theta"])
        k = _rope(_rms((h @ up(g("wk"))).reshape(T, hkv, D), up(g("k_norm")),
                       eps), m["theta"])
        v = (h @ up(g("wv"))).reshape(T, hkv, D)
        gate = jax.nn.log_sigmoid(h @ up(g("wgate")) + up(g("bgate")))
        # query head m reads KV head m // (H / Hkv)
        o = _retention(q.reshape(T, hkv, H // hkv, D), k, v, gate, n_valid,
                       m["p"], m["ret_eps"], fault)
        x = x + o @ up(g("wo"))
        x = x + _gated(_rms(x, up(g("norm2")), eps), g("wg"), g("wu"),
                       g("wd"), dt)
    x = jax.lax.dynamic_slice_in_dim(x, at0, n_at, axis=0)
    return (_rms(x, up(p["norm_f"]), eps) @ up(p["head"])).astype(
        jnp.float32)


def logits(params, c, tokens, n_valid, at0, n_at, *, dtype="float32",
           fault=None):
    """``(n_at, vocab)`` float32 logits at positions ``at0 .. at0 + n_at -
    1`` of one token sequence ``(T,)`` of which the first ``n_valid``
    exist (pad behind them to one length and it compiles once: a key at
    or past ``n_valid`` is masked; ``T`` a multiple of ``F_BLOCK`` or
    under ``Q_BLOCK``, or a multiple of ``Q_BLOCK`` under ``F_BLOCK``).
    Row ``i`` predicts the token at position ``at0 + i + 1``."""
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(n_valid, jnp.int32),
                        jnp.asarray(at0, jnp.int32), n_at=int(n_at),
                        dtype=str(jnp.dtype(dtype)), fault=fault,
                        dims=tuple(sorted(_dims(c).items())))
