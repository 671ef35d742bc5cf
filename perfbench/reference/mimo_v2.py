"""Plain reference of ``MiMo-V2.5``'s language model (``model_type``
``mimo_v2``: window and full attention layers with head counts of their
own, a sink in the window layers, keys wider than values, a gated dense
layer, sigmoid-routed experts) as the ``hybrid_decode`` driver serves it.
Imports nothing of the program.

The forward pass: the whole sequence at once in ``jax.numpy``, float32 at
matmul precision ``highest``; no cache, no kernel, no batching; attention
materialised over the whole sequence, a block of queries at a time against
all the keys, the window a mask and the sink a column of the scores;
routing by explicit loops over the choices; the experts one at a time as a
dense masked sum (each upcast where it is used)::

    h = rms(x, g1)
    q = (h Wq).reshape(H, dq);  k = (h Wk).reshape(Hkv, dq);  v = (h Wv).reshape(Hkv, dv) * 0.707
    q = [rope(q[:, :dr]) | q[:, dr:]];  k likewise          dr = int(0.334 dq) = 64
    z[t,h,j] = q[t,h] . k[j, h // (H/Hkv)] dq^-0.5      F: j <= t;   W: t - 128 < j <= t
    F: p = softmax_j(z)      W: p[t,h,j] = exp(z[t,h,j]) / (exp(s_h) + sum_i exp(z[t,h,i]))
    a[t,h] = sum_j p[t,h,j] v[j, h // (H/Hkv)];   x = x + a Wo
    h = rms(x, g2)
    layer 0:   x = x + (silu(h Wg) * (h Wu)) Wd
    layers 1-: sc = sigmoid(h Wr);  c = sc + b          (b in choosing only)
               e = the 8 largest c;  w = sc[e] / (sum sc[e] + 1e-20)
               x = x + sum_i w_i E_{e_i}(h)
    logits = rms(x, gf) Wh

A layer's kind is ``hybrid_layer_pattern``'s entry (0: ``F``, 4 KV heads,
base 1e7; 1: ``W``, 8 KV heads, base 1e4, the window, the sink), its feed-
forward ``moe_layer_freq``'s.

The chip's share: the router scores all the published experts; the
reference is given the same share as the program (``experts_held`` of the
configuration file) and leaves out, as the program does, what the experts
held elsewhere would have added.  The vocabulary is the configuration's
slice.

Departures from the source, each the program's too:
  * ``attention_projection_layout: fused_qkv`` says how ``Wq | Wk | Wv``
    are stored: three matrices here, which seeded weights absorb;
  * ``attention_chunk_size: 128`` (equal to the window) is read as the
    source's tiling of its window attention and is no term of the sums;
  * the window counts the query's own position among its 128 (``j > t -
    128``, the Hugging Face convention);
  * ``n_group`` 1, ``topk_group`` 1: one group, always kept, so the choice
    is the 8 largest corrected scores; an expert tied with another goes to
    the lower index; ``routed_scaling_factor`` null is 1;
  * no multi-token-prediction layers, no vision or audio tower.

``dtype`` float32 is the reference; bfloat16 is the control, one precision
down: router scores, norms, softmax and every product's result in
bfloat16.  ``fault`` plants one on the reference's side: ``"no_sink"``
leaves the sink out, ``"short_window"`` reads a window one cache block (16
positions) short.

The weights are the benchmark's: bfloat16 values made on the device from
the seed, one jitted call a layer, in the parameter layout the service
takes.  A routed expert's weights depend on the seed, the layer and the
expert's own number, so a share holds what the whole layer would.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256        # queries a block of the attention


def _dims(c):
    """The sizes the functions here read from a configuration file."""
    lo, hi = c.get("experts_held") or (0, c["n_routed_experts"])
    scaling = c.get("routed_scaling_factor")
    n = c["num_hidden_layers"]      # the lists may be the published, whole
    return dict(
        pattern=tuple(c["hybrid_layer_pattern"][:n]),
        moe=tuple(c["moe_layer_freq"][:n]), d=c["hidden_size"],
        H=c["num_attention_heads"],
        hkv=(c["num_key_value_heads"], c["swa_num_key_value_heads"]),
        dq=c["head_dim"], dv=c["v_head_dim"],
        dr=int(c["partial_rotary_factor"] * c["head_dim"]),
        theta=(float(c["rope_theta"]), float(c["swa_rope_theta"])),
        sink=(bool(c["add_full_attention_sink_bias"]),
              bool(c["add_swa_attention_sink_bias"])),
        window=c["sliding_window"], v_scale=float(c["attention_value_scale"]),
        F=c["intermediate_size"], f=c["moe_intermediate_size"],
        E=c.get("published", c)["n_routed_experts"], lo=int(lo), hi=int(hi),
        k=c["num_experts_per_tok"], norm_topk=bool(c["norm_topk_prob"]),
        scaling=1.0 if scaling is None else float(scaling),
        vocab=c["vocab_size"], eps=float(c["layernorm_epsilon"]))


def init_params(seed, c, dtype="bfloat16"):
    """Seeded scaled-normal weights in ``dtype`` (scales as the other
    references': embedding 0.1, products 1/sqrt(fan-in), the router too —
    a sigmoid router twice as wide saturates, PERF.md PR 30 — residual
    outputs divided by sqrt(2 x layers); norm gains 1 + 0.1 n and the
    correction bias 0.01 n, so that one left out shows; the sinks n(0, 1):
    a window's 128 scores have a spread near 1, so a sink of that size
    takes a share of the softmax that shows when it is left out)."""
    m = _dims(c)
    d, H, E, f, F, V = m["d"], m["H"], m["E"], m["f"], m["F"], m["vocab"]
    dq, dv = m["dq"], m["dv"]
    n_layers = len(m["pattern"])
    dt = jnp.dtype(dtype)
    res = 1.0 / math.sqrt(2.0 * n_layers)

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

    @functools.partial(jax.jit, static_argnames=("kind", "experts"))
    def layer(key, kind, experts):
        hkv = m["hkv"][kind]
        p = {"norm1": gain(key, 0, d),
             "wq": normal(key, 1, (d, H * dq), d ** -0.5),
             "wk": normal(key, 2, (d, hkv * dq), d ** -0.5),
             "wv": normal(key, 3, (d, hkv * dv), d ** -0.5),
             "wo": normal(key, 4, (H * dv, d), (H * dv) ** -0.5 * res),
             "norm2": gain(key, 5, d)}
        if m["sink"][kind]:
            p["sink"] = normal(key, 6, (H,), 1.0)
        if not experts:
            return dict(p, wg=normal(key, 9, (d, F), d ** -0.5),
                        wu=normal(key, 10, (d, F), d ** -0.5),
                        wd=normal(key, 11, (F, d), F ** -0.5 * res))

        def expert(e):      # an expert's weights: its own number's
            ke = jax.random.fold_in(key, 1000 + e)
            return (normal(ke, 0, (d, f), d ** -0.5),
                    normal(ke, 1, (d, f), d ** -0.5),
                    normal(ke, 2, (f, d), f ** -0.5 * res))

        wg, wu, wd = jax.lax.map(expert, jnp.arange(m["lo"], m["hi"]))
        return dict(p, wg=wg, wu=wu, wd=wd,
                    router=normal(key, 9, (d, E), d ** -0.5),
                    router_bias=normal(key, 10, (E,), 0.01))

    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    p = ends(key)
    for i, kind in enumerate(m["pattern"]):
        made = layer(jax.random.fold_in(key, 100 + i), int(kind),
                     bool(m["moe"][i]))
        for name, w in made.items():
            p[f"l{i}_{name}"] = w
    return p


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x`` (T, H, D) at positions 0.. ."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos.astype(x.dtype) + rot * sin.astype(x.dtype)


def route(logits, bias, *, k, norm_topk, scaling):
    """The router, by an explicit loop over the choices: ``logits`` (T, E)
    -> ``(w, e)`` (T, k).  One group, always kept; the first of equal
    scores wins."""
    sc = jax.nn.sigmoid(logits)
    choice = sc + bias.astype(sc.dtype)
    rows = jnp.arange(logits.shape[0])
    es = []
    for _ in range(k):
        at = jnp.argmax(choice, axis=-1)
        es.append(at)
        choice = choice.at[rows, at].set(-jnp.inf)
    e = jnp.stack(es, axis=1)
    w = jnp.take_along_axis(sc, e, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scaling, e


def _gated(h, wg, wu, wd, dt):
    return (jax.nn.silu(h @ wg.astype(dt)) * (h @ wu.astype(dt))) \
        @ wd.astype(dt)


def _experts(h, w, e, wg, wu, wd, lo, dt):
    """sum_i w_i expert_{e_i}(h) over the experts held here (``lo`` the
    first's number): every held expert over every token, weighted by the
    token's routing weight for it (0 where it was not chosen)."""
    T, held = h.shape[0], wg.shape[0]
    local = e - lo
    mine = (local >= 0) & (local < held)
    gate = jnp.zeros((T, held + 1), dt).at[
        jnp.arange(T)[:, None], jnp.where(mine, local, held)].add(
        jnp.where(mine, w, 0).astype(dt))[:, :held]

    def one(acc, xs):
        g_e, wg_e, wu_e, wd_e = xs
        return acc + g_e[:, None] * _gated(h, wg_e, wu_e, wd_e, dt), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate.T, wg, wu, wd))
    return acc


def _attention(q, k, v, n_valid, scale, window, sink):
    """Attention of ``q`` (T, H, dq) over ``k`` (T, H, dq), ``v`` (T, H,
    dv), a block of queries at a time against all the keys: causal, over
    ``window`` positions where that is not 0, with ``sink`` (H,) or None a
    column of the scores that takes weight and adds no value."""
    T = q.shape[0]
    qb = min(Q_BLOCK, T)
    assert T % qb == 0
    keys = jnp.arange(T)

    def block(args):
        qi, i0 = args
        at = i0 + jnp.arange(qb)
        mask = (keys[None, :] <= at[:, None]) & (keys[None, :] < n_valid)
        if window:
            mask &= keys[None, :] > at[:, None] - window
        s = jnp.where(mask[None], jnp.einsum("qhd,khd->hqk", qi, k) * scale,
                      -1e30)
        if sink is not None:
            s = jnp.concatenate(
                [s, jnp.broadcast_to(sink.astype(s.dtype)[:, None, None],
                                     s.shape[:2] + (1,))], axis=-1)
        a = jax.nn.softmax(s, axis=-1)[..., :T]
        return jnp.einsum("hqk,khd->qhd", a, v)

    out = jax.lax.map(block, (q.reshape(T // qb, qb, *q.shape[1:]),
                              jnp.arange(0, T, qb)))
    return out.reshape(T, *out.shape[2:])


@functools.partial(jax.jit, static_argnames=("dtype", "n_at", "dims",
                                             "fault"))
def _forward(p, tokens, n_valid, at0, *, dtype, n_at, dims, fault):
    m = dict(dims)
    dt = jnp.dtype(dtype)
    up = lambda a: a.astype(dt)  # noqa: E731
    T = tokens.shape[0]
    H, dq, dv, dr, eps = m["H"], m["dq"], m["dv"], m["dr"], m["eps"]
    x = up(p["tok_emb"][tokens])
    for i, kind in enumerate(m["pattern"]):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        hkv, theta = m["hkv"][kind], m["theta"][kind]
        window = m["window"] if kind else 0
        if window and fault == "short_window":
            window -= 16
        h = _rms(x, up(g("norm1")), eps)
        q = (h @ up(g("wq"))).reshape(T, H, dq)
        k = (h @ up(g("wk"))).reshape(T, hkv, dq)
        v = (h @ up(g("wv"))).reshape(T, hkv, dv) * m["v_scale"]
        q = jnp.concatenate([_rope(q[..., :dr], theta), q[..., dr:]], -1)
        k = jnp.concatenate([_rope(k[..., :dr], theta), k[..., dr:]], -1)
        # query head h reads KV head h // (H / Hkv): repeated here, plainly
        k, v = (jnp.repeat(t, H // hkv, axis=1) for t in (k, v))
        sink = up(g("sink")) if m["sink"][kind] and fault != "no_sink" \
            else None
        a = _attention(q, k, v, n_valid, dq ** -0.5, window, sink)
        x = x + a.reshape(T, H * dv) @ up(g("wo"))
        h = _rms(x, up(g("norm2")), eps)
        if not m["moe"][i]:
            x = x + _gated(h, g("wg"), g("wu"), g("wd"), dt)
            continue
        w, e = route(h @ up(g("router")), g("router_bias"), k=m["k"],
                     norm_topk=m["norm_topk"], scaling=m["scaling"])
        x = x + _experts(h, w, e, g("wg"), g("wu"), g("wd"), m["lo"], dt)
    x = jax.lax.dynamic_slice_in_dim(x, at0, n_at, axis=0)
    return (_rms(x, up(p["norm_f"]), eps) @ up(p["head"])).astype(
        jnp.float32)


def logits(params, c, tokens, n_valid, at0, n_at, *, dtype="float32",
           fault=None):
    """``(n_at, vocab)`` float32 logits at positions ``at0 .. at0 + n_at -
    1`` of one token sequence ``(T,)`` of which the first ``n_valid``
    exist (pad behind them to one length and it compiles once: a key at
    or past ``n_valid`` is masked; ``T`` a multiple of ``Q_BLOCK`` or
    under it).  Row ``i`` predicts the token at position ``at0 + i + 1``."""
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(n_valid, jnp.int32),
                        jnp.asarray(at0, jnp.int32), n_at=int(n_at),
                        dtype=str(jnp.dtype(dtype)), fault=fault,
                        dims=tuple(sorted(_dims(c).items())))
