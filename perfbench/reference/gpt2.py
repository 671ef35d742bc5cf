"""Plain reference of the GPT-2 block the ``generation`` driver serves:
a full causal forward pass in ``jax.numpy`` (pre-LN, fused QKV, tanh GELU,
learned positions, tied output embedding), no cache, no kernels, no
batching.  Imports nothing of the program.

``dtype`` float32 (matmul precision ``highest``) is the reference;
bfloat16 is the control, the next precision below the configuration's
float32 parameters and cache.  The weights are the benchmark's: made here
on the device, in one jitted call from the seed, in the parameter layout
the service takes (``tok_emb``, ``pos_emb``, ``l<i>_wqkv`` ...), and
handed to both sides.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def init_params(seed, *, vocab, d_model, n_layers, d_ff, max_len):
    """GPT-2-style scaled-normal weights in float32 from ``seed``."""

    @jax.jit
    def make(key):
        def normal(i, shape, scale):
            return jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * scale

        s = 1.0 / math.sqrt(d_model)
        res = s / math.sqrt(2.0 * n_layers)
        p = {"tok_emb": normal(0, (vocab, d_model), 0.02),
             "pos_emb": normal(1, (max_len, d_model), 0.02),
             "lnf_g": jnp.ones((d_model,)), "lnf_b": jnp.zeros((d_model,))}
        for i in range(n_layers):
            b = 10 * (i + 1)
            p[f"l{i}_ln1_g"] = jnp.ones((d_model,))
            p[f"l{i}_ln1_b"] = jnp.zeros((d_model,))
            p[f"l{i}_wqkv"] = normal(b, (d_model, 3 * d_model), s)
            p[f"l{i}_wo"] = normal(b + 1, (d_model, d_model), res)
            p[f"l{i}_ln2_g"] = jnp.ones((d_model,))
            p[f"l{i}_ln2_b"] = jnp.zeros((d_model,))
            p[f"l{i}_w1"] = normal(b + 2, (d_model, d_ff), s)
            p[f"l{i}_b1"] = jnp.zeros((d_ff,))
            p[f"l{i}_w2"] = normal(b + 3, (d_ff, d_model), res)
            p[f"l{i}_b2"] = jnp.zeros((d_model,))
        return p

    return make(jax.random.PRNGKey(int(seed) % (2 ** 31)))


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


@functools.partial(jax.jit, static_argnames=("n_layers", "n_heads", "dtype"))
def _forward(p, tokens, *, n_layers, n_heads, dtype):
    dt = jnp.dtype(dtype)
    p = {k: v.astype(dt) for k, v in p.items()}
    T = tokens.shape[0]
    x = p["tok_emb"][tokens] + p["pos_emb"][:T]
    d = x.shape[-1]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    for i in range(n_layers):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        h = _ln(x, g("ln1_g"), g("ln1_b"))
        q, k, v = jnp.split(h @ g("wqkv"), 3, axis=-1)
        heads = lambda t: t.reshape(T, n_heads, d // n_heads)  # noqa: E731
        s = jnp.einsum("qhd,khd->hqk", heads(q), heads(k)) \
            / math.sqrt(d // n_heads)
        s = jnp.where(causal[None], s, -1e30)
        a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
        o = jnp.einsum("hqk,khd->qhd", a, heads(v)).reshape(T, d)
        x = x + o @ g("wo")
        h = _ln(x, g("ln2_g"), g("ln2_b"))
        x = x + jax.nn.gelu(h @ g("w1") + g("b1")) @ g("w2") + g("b2")
    x = _ln(x, p["lnf_g"], p["lnf_b"])
    return (x @ p["tok_emb"].T).astype(jnp.float32)


def logits(params, tokens, *, n_layers, n_heads, dtype="float32"):
    """``(T, vocab)`` float32 logits of one token sequence ``(T,)``; row
    ``i`` predicts token ``i + 1``.  Pad to one length and it compiles
    once (the model is causal: padding changes nothing before it)."""
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        n_layers=n_layers, n_heads=n_heads,
                        dtype=str(jnp.dtype(dtype)))
