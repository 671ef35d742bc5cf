"""Plain reference of the SDAR-MoE block (``model_type`` ``sdar_moe``) the
``block_diffusion`` driver serves, and of its generation procedure.
Imports nothing of the program.

The forward pass: the whole sequence at once in ``jax.numpy``, float32 at
matmul precision ``highest``, under the block mask (causal over blocks of
``block_length``, bidirectional inside one; no shift: the logits at a
position predict that position's own token); no cache, no kernel, no
batching; K and V repeated for their query heads; the experts as a dense
masked sum, one expert at a time (each upcast where it is used, so one
layer's float32 experts never exist at once)::

    h = rms(x, g1);  q, k, v = h Wq, h Wk, h Wv
    q = rope(rms(q, gq), pos);  k = rope(rms(k, gk), pos)
    a = softmax(q k^T / sqrt(D)  over j <= (i // L + 1) * L - 1) v
    x = x + a Wo;  h = rms(x, g2);  p = softmax(h Wr)
    (w, e) = top_k(p, k);  w = w / sum(w)
    x = x + sum_i w_i (silu(h Wg[e_i]) * (h Wu[e_i])) Wd[e_i]
    logits = rms(x, gf) Wh

``dtype`` float32 is the reference; bfloat16 is the control, one precision
down: router, norms, softmaxes and every product's result in bfloat16.

The weights are the benchmark's: bfloat16 values made on the device from
the seed, one jitted call a layer, in the parameter layout the service
takes (``tok_emb``, ``head``, ``norm_f``, ``l<i>_wq`` ... ``l<i>_wd``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(c):
    """The sizes the functions here read, from a configuration file's
    ``published`` keys (depth from ``num_hidden_layers`` as it is run)."""
    return dict(n_layers=c["num_hidden_layers"], d=c["hidden_size"],
                hq=c["num_attention_heads"], hkv=c["num_key_value_heads"],
                dh=c["head_dim"], f=c["moe_intermediate_size"],
                e=c["num_experts"], k=c["num_experts_per_tok"],
                vocab=c["vocab_size"], eps=c["rms_norm_eps"],
                theta=float(c["rope_theta"]),
                norm_topk=bool(c["norm_topk_prob"]))


def init_params(seed, c, dtype="bfloat16"):
    """Seeded scaled-normal weights in ``dtype`` (norm gains near one so
    that a gain left out shows; a router twice as wide as the other
    products, so that the top experts carry most of the mass)."""
    m = _dims(c)
    d, D, F, E, V = m["d"], m["dh"], m["f"], m["e"], m["vocab"]
    hq, hkv = m["hq"] * D, m["hkv"] * D
    dt = jnp.dtype(dtype)
    s = d ** -0.5
    res = s / math.sqrt(2.0 * m["n_layers"])

    def normal(key, i, shape, scale):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dt)

    def gain(key, i, n):
        return (1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                              (n,), jnp.float32)).astype(dt)

    @jax.jit
    def ends(key):
        return {"tok_emb": normal(key, 0, (V, d), 0.1),
                "head": normal(key, 1, (d, V), s),
                "norm_f": gain(key, 2, d)}

    @jax.jit
    def layer(key):
        return {"norm1": gain(key, 0, d),
                "wq": normal(key, 1, (d, hq), s),
                "wk": normal(key, 2, (d, hkv), s),
                "wv": normal(key, 3, (d, hkv), s),
                "wo": normal(key, 4, (hq, d), hq ** -0.5 * res / s),
                "q_norm": gain(key, 5, D), "k_norm": gain(key, 6, D),
                "norm2": gain(key, 7, d),
                "router": normal(key, 8, (d, E), 2.0 * s),
                "wg": normal(key, 9, (E, d, F), s),
                "wu": normal(key, 10, (E, d, F), s),
                "wd": normal(key, 11, (E, F, d), F ** -0.5 * res / s)}

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
    """Rotate-half rotary embedding of ``x`` (T, H, D) at positions 0.."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos.astype(x.dtype) + rot * sin.astype(x.dtype))


def _experts(h, w, e, wg, wu, wd, dt):
    """sum_i w_i expert_{e_i}(h): every expert over every token, weighted
    by the token's routing weight for it (0 for the experts not chosen)."""
    T = h.shape[0]
    gate = jnp.zeros((T, wg.shape[0]), dt).at[
        jnp.arange(T)[:, None], e].set(w)

    def one(acc, xs):
        g_e, wg_e, wu_e, wd_e = xs
        y = (jax.nn.silu(h @ wg_e.astype(dt)) * (h @ wu_e.astype(dt))) \
            @ wd_e.astype(dt)
        return acc + g_e[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate.T, wg, wu, wd))
    return acc


@functools.partial(jax.jit, static_argnames=(
    "n_layers", "hq", "hkv", "dh", "k", "eps", "theta", "norm_topk",
    "block_length", "dtype", "n_at", "d", "f", "e", "vocab"))
def _forward(p, tokens, n_valid, at0, *, n_layers, hq, hkv, dh, k, eps,
             theta, norm_topk, block_length, dtype, n_at, d, f, e, vocab):
    dt = jnp.dtype(dtype)
    up = lambda a: a.astype(dt)  # noqa: E731
    T = tokens.shape[0]
    x = up(p["tok_emb"][tokens])
    i_pos = jnp.arange(T)
    block_end = (i_pos // block_length + 1) * block_length - 1
    mask = (i_pos[None, :] <= block_end[:, None]) & (i_pos[None, :] < n_valid)
    for i in range(n_layers):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        h = _rms(x, up(g("norm1")), eps)
        q = _rope(_rms((h @ up(g("wq"))).reshape(T, hq, dh),
                       up(g("q_norm")), eps), theta)
        kk = _rope(_rms((h @ up(g("wk"))).reshape(T, hkv, dh),
                        up(g("k_norm")), eps), theta)
        v = (h @ up(g("wv"))).reshape(T, hkv, dh)
        kk, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (kk, v))
        s = jnp.einsum("qhd,khd->hqk", q, kk) / math.sqrt(dh)
        s = jnp.where(mask[None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1)
        x = x + jnp.einsum("hqk,khd->qhd", a, v).reshape(T, hq * dh) \
            @ up(g("wo"))
        h = _rms(x, up(g("norm2")), eps)
        pr = jax.nn.softmax(h @ up(g("router")), axis=-1)
        w, ex = jax.lax.top_k(pr, k)
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        x = x + _experts(h, w, ex, g("wg"), g("wu"), g("wd"), dt)
    x = jax.lax.dynamic_slice_in_dim(x, at0, n_at, axis=0)
    return (_rms(x, up(p["norm_f"]), eps) @ up(p["head"])).astype(
        jnp.float32)


def logits(params, c, tokens, n_valid, at0, n_at, *, block_length,
           dtype="float32"):
    """``(n_at, vocab)`` float32 logits at positions ``at0 .. at0 + n_at -
    1`` of one token sequence ``(T,)`` of which the first ``n_valid``
    exist (pad behind them to one length and it compiles once: a key at
    or past ``n_valid`` is masked).  Row ``i`` predicts the token AT its
    own position."""
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    m = _dims(c)
    with jax.default_matmul_precision(prec):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(n_valid, jnp.int32),
                        jnp.asarray(at0, jnp.int32), n_at=int(n_at),
                        block_length=int(block_length),
                        dtype=str(jnp.dtype(dtype)), **m)


def unmask_schedule(block_length, steps):
    base, rem = divmod(block_length, steps)
    return [base + (s < rem) for s in range(steps)]


def generate(params, c, prompt, max_new, *, block_length, steps, mask_id,
             pad_to=None, dtype="float32"):
    """The generation procedure in its plainest form (greedy,
    ``low_confidence_static`` remasking): a Python loop over blocks and
    denoise passes, each a full forward over the whole sequence so far.

    A block opens as the prompt's leftover ``len(prompt) % L`` tokens
    followed by MASK; a pass takes ``x0 = argmax(logits)`` and ``c =
    softmax(logits)[x0]`` at the masked positions and unmasks the
    schedule's count of them, highest ``c`` first (ties: the lower
    position); a finished block's tokens are emitted, cut at ``max_new``.
    Returns ``(tokens, passes)``: for each generated token, the pass of
    its block that unmasked it."""
    L = int(block_length)
    sched = unmask_schedule(L, steps)
    seq = [int(t) for t in prompt]
    out, passes = [], []
    ctx = (len(seq) // L) * L
    total = -(-(len(seq) + max_new) // L) * L
    pad_to = max(int(pad_to or 0), total)
    while len(out) < max_new:
        known = seq[ctx:]
        block = known + [mask_id] * (L - len(known))
        masked = [False] * len(known) + [True] * (L - len(known))
        at = [-1] * L
        s = 0
        while any(masked):
            toks = np.zeros(pad_to, np.int32)
            toks[:ctx + L] = seq[:ctx] + block
            lg = np.asarray(logits(params, c, toks, ctx + L, ctx, L,
                                   block_length=L, dtype=dtype), np.float64)
            x0 = lg.argmax(axis=-1)
            conf = 1.0 / np.exp(lg - lg.max(axis=-1, keepdims=True)).sum(-1)
            conf = np.where(masked, conf, -np.inf)
            for _ in range(sched[min(s, len(sched) - 1)]):
                j = int(np.argmax(conf))      # the first maximum
                if conf[j] == -np.inf:
                    break
                block[j], masked[j], at[j] = int(x0[j]), False, s
                conf[j] = -np.inf
            s += 1
        for j in range(len(known), L):
            if len(out) < max_new:
                seq.append(block[j])
                out.append(block[j])
                passes.append(at[j])
        ctx += L
    return out, passes
