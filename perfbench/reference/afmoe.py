"""Plain reference of ``Trinity-Mini`` (``model_type`` ``afmoe``: window
layers with rotary and full layers without, gated attention, a norm over
each head's q and k, sandwich norms, sigmoid-routed experts with a
balancing bias and a shared expert) as the ``afmoe_decode`` driver serves
it.  Imports nothing of the program.

The forward pass: the whole sequence at once in ``jax.numpy``, float32 at
matmul precision ``highest``; no cache, no kernel, no batching; attention
materialised over the whole sequence, a block of queries at a time against
all the keys, the window a mask; routing by explicit loops over the
choices; the experts one at a time as a dense masked sum (each upcast where
it is used).  With d = ``hidden_size``, H / Hkv query / KV heads of dh, W
``sliding_window``, ``rms`` the RMS norm with a gain and ``rms_norm_eps``::

    x = sqrt(d) * Emb[token]                             (mup_enabled)
    x = x + rms(Attn_l(rms(x, g1)), g1')                 (sandwich: the OUTPUT is normed too)
    x = x + rms(Ffn_l(rms(x, g2)), g2')
    logits = rms(x, gf) Wh                               (untied)

    Attn_l(h):  q = (h Wq).reshape(H, dh);  k = (h Wk).reshape(Hkv, dh);  v = (h Wv).reshape(Hkv, dh)
                gate = sigmoid(h Wgate)                  (d -> H dh)
                q = rms(q, gq);  k = rms(k, gk)          (over a head's dh lanes, one gain a layer each)
                sliding layer:  q, k = rope(q), rope(k)  (rotate-half over all dh lanes, theta)
                full layer:     no positional term
                z[t,h,j] = q[t,h] . k[j, h // (H/Hkv)] / sqrt(dh);  sliding: t - W < j <= t;  full: j <= t
                a = softmax_j(z) v
                out = (a.reshape(H dh) * gate) Wo
    Ffn_l(h), l < num_dense_layers:  (silu(h Wg) * (h Wu)) Wd
    Ffn_l(h) otherwise:  s = sigmoid(h Wr);  e = the k largest of s + b     (b in choosing only)
                w = route_scale * s[e] / (sum s[e] + 1e-20)                  (route_norm)
                out = Shared(h) + sum_i w_i Expert_{e_i}(h)

A layer's kind is ``layer_types``' entry (the list may be the published,
whole: the first ``num_hidden_layers`` are read).

The chip's share: the router scores all the published experts; the
reference is given the same share as the program (``experts_held`` of the
configuration file) and leaves out, as the program does, what the experts
held elsewhere would have added.  The shared expert is whole.

Read into the source, each the program's too (the configuration file's
``assumed`` gives the reasons): the gate, the QK-norm, the four norms a
layer and the missing rotary of the full layers are the ``afmoe`` block's
and no key of ``config.json``; the window counts the query's own position
(``j > t - W``); ``n_group`` 1 / ``topk_group`` 1 is one group, always
kept, a tie goes to the lower index; ``load_balance_coeff`` is a training
term.

``dtype`` float32 is the reference; bfloat16 is the control, one precision
down: router scores, norms, sigmoids, softmax and every product's result in
bfloat16.  ``fault`` plants one on the reference's side: ``"no_gate"`` (the
gate is 1), ``"rope_on_full"`` (the full layers rotate too),
``"short_window"`` (the window an eighth short: 1,792 of 2,048),
``"no_post_norm"`` (the branches' outputs are added as they are).

The weights are the benchmark's: values made on the device from the seed,
one jitted call a layer, in the parameter layout the service takes.  The
embedding is normal over ``sqrt(d)``, so that the scaled embedding has rms
1 (at rms 0.1 x sqrt(d) the stream would drown every layer's branch, whose
output a norm holds at 1, and no fault would show); products are normal
over the square root of their fan-in; all six norms' gains are 1 + 0.1 n
and the balancing bias 0.01 n, so that one left out shows.  A routed
expert's weights depend on the seed, the layer and the expert's own number,
so a share holds what the whole layer would.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256        # queries a block of the attention
HEAD_ROWS = 512      # rows a call of the head takes

FAULTS = ("no_gate", "rope_on_full", "short_window", "no_post_norm")


def _dims(c):
    """The sizes the functions here read from a configuration file."""
    E = c.get("published", c)["num_experts"]
    lo, hi = c.get("experts_held") or (0, E)
    n = c["num_hidden_layers"]      # the list may be the published, whole
    return dict(
        sliding=tuple(t == "sliding_attention"
                      for t in c["layer_types"][:n]),
        dense=int(c["num_dense_layers"]), d=c["hidden_size"],
        H=c["num_attention_heads"], hkv=c["num_key_value_heads"],
        dh=c["head_dim"], theta=float(c["rope_theta"]),
        window=c["sliding_window"], F=c["intermediate_size"],
        f=c["moe_intermediate_size"], E=E, lo=int(lo), hi=int(hi),
        k=c["num_experts_per_tok"], norm_topk=bool(c["route_norm"]),
        scaling=float(c["route_scale"]), shared=int(c["num_shared_experts"]),
        mult=math.sqrt(c["hidden_size"]) if c["mup_enabled"] else 1.0,
        vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]))


def init_params(seed, c, dtype="bfloat16"):
    """Seeded scaled-normal weights in ``dtype`` (the module's docstring
    has the scales)."""
    m = _dims(c)
    d, H, hkv, dh = m["d"], m["H"], m["hkv"], m["dh"]
    E, f, F, V, fs = m["E"], m["f"], m["F"], m["vocab"], m["shared"] * m["f"]
    dt = jnp.dtype(dtype)

    def normal(key, i, shape, scale):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dt)

    def gain(key, i, n):
        return (1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                              (n,), jnp.float32)).astype(dt)

    @jax.jit
    def ends(key):
        return {"tok_emb": normal(key, 0, (V, d), 1.0 / m["mult"]),
                "head": normal(key, 1, (d, V), d ** -0.5),
                "norm_f": gain(key, 2, d)}

    @functools.partial(jax.jit, static_argnames=("experts",))
    def layer(key, experts):
        p = {"norm1": gain(key, 0, d),
             "wq": normal(key, 1, (d, H * dh), d ** -0.5),
             "wk": normal(key, 2, (d, hkv * dh), d ** -0.5),
             "wv": normal(key, 3, (d, hkv * dh), d ** -0.5),
             "wo": normal(key, 4, (H * dh, d), (H * dh) ** -0.5),
             "norm2": gain(key, 5, d),
             "wgate": normal(key, 6, (d, H * dh), d ** -0.5),
             "q_norm": gain(key, 7, dh), "k_norm": gain(key, 8, dh),
             "norm1_post": gain(key, 12, d), "norm2_post": gain(key, 13, d)}
        if not experts:
            return dict(p, wg=normal(key, 9, (d, F), d ** -0.5),
                        wu=normal(key, 10, (d, F), d ** -0.5),
                        wd=normal(key, 11, (F, d), F ** -0.5))

        def expert(e):      # an expert's weights: its own number's
            ke = jax.random.fold_in(key, 1000 + e)
            return (normal(ke, 0, (d, f), d ** -0.5),
                    normal(ke, 1, (d, f), d ** -0.5),
                    normal(ke, 2, (f, d), f ** -0.5))

        wg, wu, wd = jax.lax.map(expert, jnp.arange(m["lo"], m["hi"]))
        p = dict(p, wg=wg, wu=wu, wd=wd,
                 router=normal(key, 9, (d, E), d ** -0.5),
                 router_bias=normal(key, 10, (E,), 0.01))
        if fs:
            p.update(sg=normal(key, 14, (d, fs), d ** -0.5),
                     su=normal(key, 15, (d, fs), d ** -0.5),
                     sd=normal(key, 16, (fs, d), fs ** -0.5))
        return p

    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    p = ends(key)
    for i in range(len(m["sliding"])):
        made = layer(jax.random.fold_in(key, 100 + i), i >= m["dense"])
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


def _attention(q, k, v, n_valid, scale, window):
    """Attention of ``q`` (T, H, dh) over ``k``, ``v`` (T, H, dh), a block
    of queries at a time against all the keys: causal, over ``window``
    positions (the query's own among them) where that is not 0."""
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
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(T // qb, qb, *q.shape[1:]),
                              jnp.arange(0, T, qb)))
    return out.reshape(T, *out.shape[2:])


@functools.partial(jax.jit, static_argnames=("dtype", "dims", "fault"))
def _forward(p, tokens, n_valid, *, dtype, dims, fault):
    m = dict(dims)
    dt = jnp.dtype(dtype)
    up = lambda a: a.astype(dt)  # noqa: E731
    T = tokens.shape[0]
    H, hkv, dh, eps = m["H"], m["hkv"], m["dh"], m["eps"]
    post = (lambda y, g: y) if fault == "no_post_norm" \
        else (lambda y, g: _rms(y, up(g), eps))
    x = up(p["tok_emb"][tokens]) * jnp.asarray(m["mult"], dt)
    for i, sliding in enumerate(m["sliding"]):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        window = m["window"] if sliding else 0
        if window and fault == "short_window":
            window -= max(1, window // 8)
        h = _rms(x, up(g("norm1")), eps)
        q = _rms((h @ up(g("wq"))).reshape(T, H, dh), up(g("q_norm")), eps)
        k = _rms((h @ up(g("wk"))).reshape(T, hkv, dh), up(g("k_norm")), eps)
        v = (h @ up(g("wv"))).reshape(T, hkv, dh)
        gate = 1.0 if fault == "no_gate" \
            else jax.nn.sigmoid(h @ up(g("wgate")))
        if sliding or fault == "rope_on_full":
            q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
        # query head h reads KV head h // (H / Hkv): repeated here, plainly
        k, v = (jnp.repeat(t, H // hkv, axis=1) for t in (k, v))
        a = _attention(q, k, v, n_valid, dh ** -0.5, window)
        x = x + post((a.reshape(T, H * dh) * gate) @ up(g("wo")),
                     g("norm1_post"))
        h = _rms(x, up(g("norm2")), eps)
        if i < m["dense"]:
            y = _gated(h, g("wg"), g("wu"), g("wd"), dt)
        else:
            w, e = route(h @ up(g("router")), g("router_bias"), k=m["k"],
                         norm_topk=m["norm_topk"], scaling=m["scaling"])
            y = _experts(h, w, e, g("wg"), g("wu"), g("wd"), m["lo"], dt)
            if m["shared"]:
                y = y + _gated(h, g("sg"), g("su"), g("sd"), dt)
        x = x + post(y, g("norm2_post"))
    return _rms(x, up(p["norm_f"]), eps)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _head(w, rows, *, dtype):
    dt = jnp.dtype(dtype)
    return (rows.astype(dt) @ w.astype(dt)).astype(jnp.float32)


def _precision(dtype):
    return jax.default_matmul_precision(
        "highest" if jnp.dtype(dtype) == jnp.float32 else "default")


def hidden(params, c, tokens, n_valid, *, dtype="float32", fault=None):
    """The stream behind the last norm, ``(T, d)``, at every position of
    one token sequence ``(T,)`` of which the first ``n_valid`` exist (pad
    behind them to one length and it compiles once: a key at or past
    ``n_valid`` is masked; ``T`` a multiple of ``Q_BLOCK`` or under it)."""
    assert fault is None or fault in FAULTS, fault
    with _precision(dtype):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(n_valid, jnp.int32),
                        dtype=str(jnp.dtype(dtype)), fault=fault,
                        dims=tuple(sorted(_dims(c).items())))


def head(params, rows, *, dtype="float32"):
    """``(n, vocab)`` float32 logits of ``n`` rows of :func:`hidden`,
    ``HEAD_ROWS`` of them a call (the head is 1.6 GB upcast and a row 0.8
    MB at the published vocabulary)."""
    n = rows.shape[0]
    pad = -(-n // HEAD_ROWS) * HEAD_ROWS if n > HEAD_ROWS else n
    rows = jnp.pad(rows, ((0, pad - n), (0, 0)))
    with _precision(dtype):
        out = [_head(params["head"], rows[i:i + HEAD_ROWS],
                     dtype=str(jnp.dtype(dtype)))
               for i in range(0, pad, HEAD_ROWS)]
    return jnp.concatenate(out)[:n] if len(out) > 1 else out[0][:n]


def logits(params, c, tokens, n_valid, at0, n_at, *, dtype="float32",
           fault=None):
    """``(n_at, vocab)`` float32 logits at positions ``at0 .. at0 + n_at -
    1``.  Row ``i`` predicts the token at position ``at0 + i + 1``."""
    x = hidden(params, c, tokens, n_valid, dtype=dtype, fault=fault)
    return head(params, x[at0:at0 + n_at], dtype=dtype)
