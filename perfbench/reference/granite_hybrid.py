"""Plain reference of ``granite-4.0-h-micro`` (``model_type``
``granitemoehybrid`` with no routed expert: Mamba-2 layers beside a few
no-position grouped-query attention layers, a gated feed-forward layer in
every layer, the four muP multipliers) as the ``ssd_decode`` driver serves
it.  Imports nothing of the program.

The forward pass: the whole sequence at once in ``jax.numpy``, float32 at
matmul precision ``highest``; no cache, no state carried between calls, no
chunks (the scan is ONE recurrence, a plain ``lax.scan`` position by
position), no kernel, no batching.  With ``x`` the residual stream, d =
2048; Mamba-2: 64 heads of P = 64 (d_i = 4096), N = 128, K = 4, one group;
attention: 32 query over 8 KV heads of 64; F = 8192; layers l = 0..39;
``rms`` the RMS norm with a gain and eps 1e-5::

    x_0 = 12 Emb[token]                                   (embedding_multiplier)
    x <- x + 0.22 Mixer_l(rms(x; g_in))                   (residual_multiplier, BOTH branches)
    x <- x + 0.22 W_down (silu(a) * b),  [a | b] = W_up rms(x; g_post)
    logits = (rms(x; g_f) Emb^T) / 8                      (tied head; logits_scaling DIVIDES)

    Mixer_l, l in {5, 15, 25, 35}  (attention; no position term of any kind):
        z[t,m,j] = 0.015625 q[t,m] . k[j, m // 4],  j <= t    (attention_multiplier, NOT 1/sqrt(64))
        out = softmax_j(z) v, heads joined, times W_o         (no bias anywhere)
    Mixer_l, every other l  (Mamba-2, arXiv:2405.21060):
        [z | xBC | dt] = h W_in                     (4096 | 4096 + 2 x 128 | 64)
        xBC' = silu(conv_K(xBC) + b_c)              (depthwise, causal, over x, B and C TOGETHER)
        [x | B | C] = xBC'                          (ONE B, C pair for all heads)
        D_t[m] = softplus(dt_t[m] + dt_bias[m])     (no clamp)
        S_t[m] = exp(D_t[m] A[m]) S_{t-1}[m] + D_t[m] x_t[m] B_t^T,  A[m] = -exp(A_log[m])
        y_t[m] = S_t[m] C_t + Dskip[m] x_t[m]
        out = rms(y_t * silu(z_t); g_n) W_out       (the GATE first, then ONE norm over all 4,096 lanes)

Departures from the published description: none in the arithmetic.  What
the config does not say and this file takes from the Mamba-2 paper and the
``granitemoehybrid`` modelling code is listed in the configuration's
``assumed``.  ``mamba_chunk_size`` is a block of the published
IMPLEMENTATION and appears nowhere here: any chunking of the scan is exact.

``dtype`` float32 is the reference; bfloat16 is the control, one precision
down: every product's result, the norms, softmax, softplus, the decay AND
the carried state in bfloat16.  ``fault`` plants one on the reference's
side (``FAULTS``): ``"no_carry"`` (the state starts from zero at every
multiple of the service's shortest chunk: a chunk boundary that carries
nothing), ``"no_residual_multiplier"`` (1 for 0.22), ``"sqrt_scale"``
(``1/sqrt(64)`` for ``attention_multiplier``), ``"norm_before_gate"``.

The weights are the benchmark's: values made on the device from the seed,
one jitted call a KIND of layer, in the parameter layout the service takes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128        # queries a block of the attention weights
F_BLOCK = 1024       # rows a block of the feed-forward layer
HEAD_ROWS = 512      # rows a call of the head

FAULTS = ("no_carry", "no_residual_multiplier", "sqrt_scale",
          "norm_before_gate")
EMB_STD = 0.01       # see ``init_params``


def layer_types(c):
    """``"mamba"`` or ``"attention"``, a layer."""
    return tuple(c["layer_types"][:c["num_hidden_layers"]])


def _dims(c):
    """The sizes and multipliers the functions here read from a
    configuration file."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    Hm, P = c["mamba_n_heads"], c["mamba_d_head"]
    assert Hm * P == c["mamba_expand"] * d and c["mamba_n_groups"] == 1
    return dict(
        kinds=layer_types(c), d=d, H=H, hkv=c["num_key_value_heads"],
        dh=d // H, F=c["shared_intermediate_size"], vocab=c["vocab_size"],
        eps=float(c["rms_norm_eps"]), Hm=Hm, P=P, N=c["mamba_d_state"],
        K=c["mamba_d_conv"], emb=float(c["embedding_multiplier"]),
        res=float(c["residual_multiplier"]),
        att=float(c["attention_multiplier"]),
        div=float(c["logits_scaling"]),
        # what the "no_carry" fault cuts the scan at
        chunk=int(c.get("service", {}).get("seq_buckets", [128])[0]))


def param_shapes(c):
    """Every parameter's shape, by name."""
    m = _dims(c)
    d, H, hkv, dh, F = (m[k] for k in ("d", "H", "hkv", "dh", "F"))
    di, N, K, Hm = m["Hm"] * m["P"], m["N"], m["K"], m["Hm"]
    s = {"tok_emb": (m["vocab"], d), "g_f": (d,)}
    mixers = {
        "mamba": {"w_in": (d, 2 * di + 2 * N + Hm),
                  "conv_w": (K, di + 2 * N), "conv_b": (di + 2 * N,),
                  "dt_bias": (Hm,), "A_log": (Hm,), "D_skip": (Hm,),
                  "g_n": (di,), "w_out": (di, d)},
        "attention": {"wq": (d, H * dh), "wk": (d, hkv * dh),
                      "wv": (d, hkv * dh), "wo": (H * dh, d)}}
    for i, kind in enumerate(m["kinds"]):
        layer = {"g_in": (d,), "g_post": (d,), "w_up": (d, 2 * F),
                 "w_down": (F, d), **mixers[kind]}
        s.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return s


def init_params(seed, c, dtype="bfloat16"):
    """Seeded weights in ``dtype``: products 1/sqrt(fan-in), gains 1 + 0.1
    n, ``conv_b`` 0.1 n, ``D_skip`` 1 + 0.1 n.  Mamba-2's own: ``A_log`` the
    log of a value uniform in 1..16 a head, ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly in 1e-3..1e-1 — a state that
    forgets in ten positions would hide every fault of the carried state.
    ``wq`` and ``wk`` times ``head size ** 1/4``: muP's ``1/d`` scale
    expects a query and a key that grow with the head, and with it the
    scores' standard deviation is 1 (under ``1/sqrt(d)`` it is 8, and the
    planted scale shows).  The embedding ``EMB_STD`` n: it is the head too,
    and a token's own row, times ``embedding_multiplier``, comes back at
    the tied head as that token's logit; at 0.01 it lies ~3.5 standard
    deviations over the rest, inside the best of the vocabulary's draws
    (at 0.18, where the logits' deviation would be 1, it lies 36 over and
    every served token is its prompt's last, whatever the fault: the
    logits' deviation here is ~0.05, and the gaps are read against
    that)."""
    m = _dims(c)
    shapes = param_shapes(c)
    dt = jnp.dtype(dtype)
    qk = float(m["dh"]) ** 0.25

    def make(key, i, name, shape):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("g_in", "g_post", "g_f", "g_n", "D_skip"):
            z = 1.0 + 0.1 * z
        elif name == "conv_b":
            z = 0.1 * z
        elif name == "tok_emb":
            z = EMB_STD * z
        elif name == "A_log":
            z = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            z = step + jnp.log(-jnp.expm1(-step))
        else:
            z = z * shape[0] ** -0.5 * (qk if name in ("wq", "wk") else 1.0)
        return z.astype(dt)

    @functools.partial(jax.jit, static_argnames=("of", "names"))
    def group(key, of, names):
        # (one compile a KIND of layer: the names here are a layer's own)
        return {n: make(key, i, n, shapes[of + n])
                for i, n in enumerate(names)}

    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    p = group(key, "", ("tok_emb", "g_f"))
    first = {}      # a kind's first layer: each of the kind has its shapes
    for i, kind in enumerate(m["kinds"]):
        of = first.setdefault(kind, f"l{i}_")
        names = tuple(sorted(n[len(of):] for n in shapes
                             if n.startswith(of)))
        made = group(jax.random.fold_in(key, 100 + i), of, names)
        p.update({f"l{i}_{n}": w for n, w in made.items()})
    return p


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def _mamba2(h, g, m, up, fault):
    """The Mamba-2 mixer over the whole sequence ``h (T, d)``."""
    T = h.shape[0]
    Hm, P, N, K = m["Hm"], m["P"], m["N"], m["K"]
    di = Hm * P
    dt = h.dtype
    zxd = h @ up(g("w_in"))
    z, xbc, step = zxd[:, :di], zxd[:, di:2 * di + 2 * N], \
        zxd[:, 2 * di + 2 * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, di + 2 * N), dt), xbc],
                             axis=0)
    w = up(g("conv_w"))
    xbc = jax.nn.silu(sum(w[k] * padded[k:k + T] for k in range(K))
                      + up(g("conv_b")))
    x = xbc[:, :di].reshape(T, Hm, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    step = jax.nn.softplus(step + up(g("dt_bias")))                 # (T, Hm)
    A = -jnp.exp(up(g("A_log")))                                    # (Hm,)
    cut = m["chunk"] if fault == "no_carry" else 0

    def one(s, xs):
        d_t, x_t, b_t, c_t, t = xs
        if cut:     # a chunk boundary that carries nothing
            s = jnp.where(t % cut == 0, jnp.zeros_like(s), s)
        s = jnp.exp(d_t * A)[:, None, None] * s \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s.astype(dt), jnp.sum(s * c_t[None, None, :],
                                     axis=-1).astype(dt)

    _, y = jax.lax.scan(one, jnp.zeros((Hm, P, N), dt),
                        (step, x, Bm, Cm, jnp.arange(T)))
    y = (y + up(g("D_skip"))[:, None] * x).reshape(T, di)
    if fault == "norm_before_gate":
        y = _rms(y, up(g("g_n")), m["eps"]) * jax.nn.silu(z)
    else:
        y = _rms(y * jax.nn.silu(z), up(g("g_n")), m["eps"])
    return y @ up(g("w_out"))


def _attention(q, k, v, scale):
    """Causal softmax attention of ``q (T, H, dh)`` over ``k``, ``v`` ``(T,
    Hkv, dh)``, a block of queries at a time against all the keys: query
    head ``m`` reads KV head ``m // (H / Hkv)``.  Returns ``(T, H dh)``."""
    T, H, dh = q.shape
    hkv = k.shape[1]
    qb = min(Q_BLOCK, T)
    assert T % qb == 0
    dt = q.dtype
    q = q.reshape(T, hkv, H // hkv, dh)
    keys = jnp.arange(T)

    def block(args):
        qi, i0 = args
        mask = keys[None, :] <= (i0 + jnp.arange(qb))[:, None]
        s = jnp.einsum("qngd,knd->ngqk", qi, k) * jnp.asarray(scale, dt)
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("ngqk,knd->qngd", w.astype(dt), v).astype(dt)

    out = jax.lax.map(block, (q.reshape(T // qb, qb, hkv, H // hkv, dh),
                              jnp.arange(0, T, qb)))
    return out.reshape(T, H * dh)


def _gated(h, w_up, w_down, F, dt):
    """The feed-forward layer, a block of rows at a time."""
    T = h.shape[0]
    fb = min(F_BLOCK, T)
    assert T % fb == 0
    w_up, w_down = w_up.astype(dt), w_down.astype(dt)

    def rows(y):
        ab = y @ w_up
        return (jax.nn.silu(ab[:, :F]) * ab[:, F:]) @ w_down

    return jax.lax.map(rows, h.reshape(T // fb, fb, -1)).reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("dtype", "dims", "fault"))
def _forward(p, tokens, *, dtype, dims, fault):
    m = dict(dims)
    dt = jnp.dtype(dtype)
    up = lambda a: a.astype(dt)  # noqa: E731
    T = tokens.shape[0]
    H, hkv, dh, eps = m["H"], m["hkv"], m["dh"], m["eps"]
    res = jnp.asarray(1.0 if fault == "no_residual_multiplier" else m["res"],
                      dt)
    scale = dh ** -0.5 if fault == "sqrt_scale" else m["att"]
    x = up(p["tok_emb"][tokens]) * jnp.asarray(m["emb"], dt)
    for i, kind in enumerate(m["kinds"]):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        h = _rms(x, up(g("g_in")), eps)
        if kind == "mamba":
            out = _mamba2(h, g, m, up, fault)
        else:
            q = (h @ up(g("wq"))).reshape(T, H, dh)
            k = (h @ up(g("wk"))).reshape(T, hkv, dh)
            v = (h @ up(g("wv"))).reshape(T, hkv, dh)
            out = _attention(q, k, v, scale) @ up(g("wo"))
        x = x + res * out
        x = x + res * _gated(_rms(x, up(g("g_post")), eps), g("w_up"),
                             g("w_down"), m["F"], dt)
    return _rms(x, up(p["g_f"]), eps)


@functools.partial(jax.jit, static_argnames=("dtype", "div"))
def _head(emb, rows, *, dtype, div):
    dt = jnp.dtype(dtype)
    return ((rows.astype(dt) @ emb.astype(dt).T)
            / jnp.asarray(div, dt)).astype(jnp.float32)


def _precision(dtype):
    return jax.default_matmul_precision(
        "highest" if jnp.dtype(dtype) == jnp.float32 else "default")


def hidden(params, c, tokens, *, dtype="float32", fault=None):
    """The stream behind the last norm, ``(T, d)``, at every position of
    one token sequence ``(T,)`` (pad behind the tokens that exist to one
    length and it compiles once: every layer is causal, so what lies
    behind a position never reaches it; ``T`` a multiple of ``F_BLOCK`` or
    under ``Q_BLOCK``, or a multiple of ``Q_BLOCK`` under ``F_BLOCK``)."""
    assert fault is None or fault in FAULTS, fault
    with _precision(dtype):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        dtype=str(jnp.dtype(dtype)), fault=fault,
                        dims=tuple(sorted(_dims(c).items())))


def head(params, c, rows, *, dtype="float32"):
    """``(n, vocab)`` float32 logits of ``n`` rows of :func:`hidden`: the
    tied embedding, divided by ``logits_scaling``; ``HEAD_ROWS`` of them a
    call (a row is 0.4 MB at the published vocabulary)."""
    n = rows.shape[0]
    pad = -(-n // HEAD_ROWS) * HEAD_ROWS if n > HEAD_ROWS else n
    rows = jnp.pad(rows, ((0, pad - n), (0, 0)))
    with _precision(dtype):
        out = [_head(params["tok_emb"], rows[i:i + HEAD_ROWS],
                     dtype=str(jnp.dtype(dtype)),
                     div=float(c["logits_scaling"]))
               for i in range(0, pad, HEAD_ROWS)]
    return jnp.concatenate(out)[:n] if len(out) > 1 else out[0][:n]


def logits(params, c, tokens, n_valid, at0, n_at, *, dtype="float32",
           fault=None):
    """``(n_at, vocab)`` float32 logits at positions ``at0 .. at0 + n_at -
    1``.  Row ``i`` predicts the token at position ``at0 + i + 1``
    (``n_valid`` is the sibling references' argument: causal throughout,
    padding needs no mask)."""
    del n_valid
    x = hidden(params, c, tokens, dtype=dtype, fault=fault)
    return head(params, c, x[at0:at0 + n_at], dtype=dtype)
