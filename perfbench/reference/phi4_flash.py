"""Plain reference of ``Phi-4-mini-flash-reasoning`` (``model_type``
``phi4flash``: SambaY, a decoder-hybrid-decoder of Mamba, window attention,
ONE full attention layer whose K and V a cross-decoder of gated memory
units and cross attention reads, every attention differential) as the
``reason_decode`` driver serves it.  Imports nothing of the program.

The forward pass: the whole sequence at once in ``jax.numpy``, float32 at
matmul precision ``highest``; no cache, no state carried between calls (the
scan is a plain ``lax.scan`` over positions), no chunks, no kernel, no
batching and NO SKIP: every layer runs at every position.  With ``x`` the
residual stream, d = 2560, H = 40 query and 20 KV heads of 64, d_i = 5120,
N = 16, K = 4, R = 160, W = 512 and layers l = 0..31::

    x <- x + Mixer_l(LN(x));  x <- x + W_down(silu(g) * u), [g | u] = W_gu LN'(x)
    logits = LN_f(x) E^T        (LayerNorm with gain and bias, eps 1e-5; E tied)

    Mixer_l = Mamba (l even, l <= 16; l = 16 hands on M), DiffAttn over a
    window (l odd, l <= 15), DiffAttn full (l = 17: THE K, V), Cross (l odd,
    l >= 19: W_q, W_o, layer 17's K, V), GMU (l even, l >= 18)

    Mamba: [u | z] = W_in h;  u' = silu(conv_K(u) + b_c)  (depthwise, causal)
           [r | B | C] = W_x u';  D_t = softplus(W_dt r + b_dt)
           s_t = exp(D_t A) s_{t-1} + D_t u'_t B_t,  A = -exp(A_log)
           y_t = s_t . C_t + Dskip u'_t;  out = W_out (y_t * silu(z_t));  M_t = y_t
    GMU:   out = W_o (M_t * silu(W_i h_t))
    DiffAttn: pair i = (q[2i], q[2i+1]) reads KV pair p = i // 2:
           a_i = softmax(q1 k1^T / 8) V - lam softmax(q2 k2^T / 8) V,  V = v[2p] | v[2p+1]
           lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),  lam0(l) = 0.8 - 0.6 exp(-0.3 l)
           o_i = (1 - lam0(l)) RMSNorm_128(a_i);  out = W_o [o_0 | ... | o_19] + b_o
           mask j <= t, a window layer also t - j < W

``dtype`` float32 is the reference; bfloat16 is the control, one precision
down: norms, softmax, the subtraction, softplus, the decay, the state and
every product's result in bfloat16.  ``fault`` plants one on the reference's
side: ``"no_lambda"`` (lam = 0), ``"no_shared_kv"`` (the cross layers read
zeros for layer 17's K and V), ``"no_memory"`` (M = 1).

The weights are the benchmark's: values made on the device from the seed,
one jitted call a layer, in the parameter layout the service takes
(``A_log`` channel-minor, ``(N, d_i)``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128        # queries a block of the attention weights
F_BLOCK = 1024       # rows a block of the feed-forward layer

FAULTS = ("no_lambda", "no_shared_kv", "no_memory")


def layer_kinds(c):
    """Which mixer each layer has: the configuration's ``layer_kinds`` where
    it names them, else the SambaY layout over ``num_hidden_layers`` = 2h
    layers — a self-decoder of h layers, Mamba (even) and window attention
    (odd); Mamba at h, which hands on its memory; full attention at h + 1;
    then GMU (even) and cross attention (odd)."""
    if c.get("layer_kinds"):
        return tuple(c["layer_kinds"])
    n = c["num_hidden_layers"]
    h = n // 2
    assert n % 2 == 0 and h % 2 == 0, n
    return tuple(
        ("ssm" if i % 2 == 0 else "swa") if i < h else
        "ssm" if i == h else "full" if i == h + 1 else
        "gmu" if i % 2 == 0 else "cross" for i in range(n))


def lam0(layer):
    """The differential's constant part, by the layer's depth."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _dims(c):
    """The sizes the functions here read from a configuration file."""
    a = c.get("assumed_values", {})
    d, H = c["hidden_size"], c["num_attention_heads"]
    expand = int(a.get("expand", 2))
    return dict(
        kinds=layer_kinds(c), d=d, H=H, hkv=c["num_key_value_heads"],
        dh=d // H, F=c["intermediate_size"], vocab=c["vocab_size"],
        eps=float(c["layer_norm_eps"]), window=int(c["sliding_window"]),
        di=expand * d, N=int(a.get("d_state", 16)),
        K=int(a.get("d_conv", 4)),
        R=int(a.get("dt_rank", -(-d // 16))))


def param_shapes(c):
    """Every parameter's shape, by name."""
    m = _dims(c)
    d, H, hkv, dh, F, di, N, K, R = (m[k] for k in (
        "d", "H", "hkv", "dh", "F", "di", "N", "K", "R"))
    s = {"tok_emb": (m["vocab"], d), "lnf_g": (d,), "lnf_b": (d,)}
    attn_q = {"wq": (d, H * dh), "bq": (H * dh,), "wo": (H * dh, d),
              "bo": (d,), "lam": (4, dh), "sub_g": (2 * dh,)}
    attn_kv = {"wk": (d, hkv * dh), "bk": (hkv * dh,), "wv": (d, hkv * dh),
               "bv": (hkv * dh,)}
    mixers = {
        "ssm": {"w_in": (d, 2 * di), "conv_w": (K, di), "conv_b": (di,),
                "w_x": (di, R + 2 * N), "w_dt": (R, di), "b_dt": (di,),
                "A_log": (N, di), "D_skip": (di,), "w_out": (di, d)},
        "swa": {**attn_q, **attn_kv}, "full": {**attn_q, **attn_kv},
        "cross": attn_q, "gmu": {"w_i": (d, di), "w_o": (di, d)}}
    for i, kind in enumerate(m["kinds"]):
        layer = {"ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,), "ln2_b": (d,),
                 "wgu": (d, 2 * F), "wd": (F, d), **mixers[kind]}
        s.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return s


def init_params(seed, c, dtype="bfloat16"):
    """Seeded weights in ``dtype``, scaled as the other references':
    embedding 0.1, products 1/sqrt(fan-in), residual outputs divided by
    sqrt(2 x layers), norm gains 1 + 0.1 n and biases 0.1 n, so that one
    left out shows.  The state-space layer's own, as Mamba initialises
    them: ``A_log = log(1..N)`` a channel, ``b_dt`` the inverse softplus of
    a step drawn log-uniformly in 1e-3..1e-1 — a state that forgets in ten
    positions would hide every fault of the carried state —, ``D_skip``
    near one; the lambda vectors n(0, 0.1)."""
    m = _dims(c)
    shapes = param_shapes(c)
    dt = jnp.dtype(dtype)
    res = 1.0 / math.sqrt(2.0 * len(m["kinds"]))
    N = m["N"]

    def make(key, i, name, shape):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("ln1_g", "ln2_g", "lnf_g", "sub_g", "D_skip"):
            z = 1.0 + 0.1 * z
        elif name in ("ln1_b", "ln2_b", "lnf_b", "bq", "bk", "bv", "bo",
                      "conv_b", "lam"):
            z = 0.1 * z
        elif name == "tok_emb":
            z = 0.1 * z
        elif name == "A_log":
            z = jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[:, None], shape)
        elif name == "b_dt":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            z = step + jnp.log(-jnp.expm1(-step))
        else:
            z = z * shape[0] ** -0.5 * (
                res if name in ("wo", "wd", "w_out", "w_o") else 1.0)
        return z.astype(dt)

    @functools.partial(jax.jit, static_argnames=("of", "names"))
    def group(key, of, names):
        # (one compile a KIND of layer: the names here are a layer's own)
        return {n: make(key, i, n, shapes[of + n])
                for i, n in enumerate(names)}

    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    p = group(key, "", ("tok_emb", "lnf_g", "lnf_b"))
    first = {}      # a kind's first layer: every layer of a kind has its shapes
    for i, kind in enumerate(m["kinds"]):
        of = first.setdefault(kind, f"l{i}_")
        names = tuple(sorted(n[len(of):] for n in shapes
                             if n.startswith(of)))
        made = group(jax.random.fold_in(key, 100 + i), of, names)
        p.update({f"l{i}_{n}": w for n, w in made.items()})
    return p


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _mamba(h, g, m, up):
    """The state-space mixer over the whole sequence ``h (T, d)``: returns
    ``(out (T, d), y (T, d_i))`` — ``y`` the scan's output with the skip
    term, before the gate (the memory a last such layer hands on)."""
    T = h.shape[0]
    di, N, K, R = m["di"], m["N"], m["K"], m["R"]
    dt = h.dtype
    uz = h @ up(g("w_in"))
    u, z = uz[:, :di], uz[:, di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, di), dt), u], axis=0)
    w = up(g("conv_w"))
    conv = sum(w[k] * padded[k:k + T] for k in range(K)) + up(g("conv_b"))
    u1 = jax.nn.silu(conv)
    rbc = u1 @ up(g("w_x"))
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    step = jax.nn.softplus(r @ up(g("w_dt")) + up(g("b_dt")))       # (T, di)
    A = -jnp.exp(up(g("A_log")))                                    # (N, di)

    def one(s, xs):
        d_t, u_t, b_t, c_t = xs
        s = jnp.exp(d_t[None, :] * A) * s \
            + (d_t * u_t)[None, :] * b_t[:, None]
        return s.astype(dt), jnp.sum(s * c_t[:, None], axis=0).astype(dt)

    _, y = jax.lax.scan(one, jnp.zeros((N, di), dt), (step, u1, Bm, Cm))
    y = y + up(g("D_skip")) * u1
    return (y * jax.nn.silu(z)) @ up(g("w_out")), y


def _diff_attn(q, k, v, lam, lam_0, sub_g, window, m, fault):
    """Differential attention of ``q (T, H, dh)`` over ``k``, ``v`` ``(T,
    Hkv, dh)``, a block of queries at a time against all the keys: query
    pair ``i`` reads KV pair ``i // 2``; ``window`` 0 keeps every position.
    Returns ``(T, H * dh)``."""
    T = q.shape[0]
    H, hkv, dh, eps = m["H"], m["hkv"], m["dh"], m["eps"]
    P = hkv // 2                               # KV pairs; 2 query pairs each
    qb = min(Q_BLOCK, T)
    assert T % qb == 0
    dt = q.dtype
    q = q.reshape(T, P, 2, 2, dh)              # (pair of a KV pair, 1|2)
    k = k.reshape(T, P, 2, dh)
    vv = v.reshape(T, P, 2 * dh)
    keys = jnp.arange(T)
    scale = jnp.asarray(dh ** -0.5, dt)
    if fault == "no_lambda":
        lam = jnp.zeros_like(lam)

    def block(args):
        qi, i0 = args
        at = i0 + jnp.arange(qb)
        mask = keys[None, :] <= at[:, None]
        if window:
            mask &= at[:, None] - keys[None, :] < window
        s = jnp.einsum("qpcnd,kpnd->pcnqk", qi, k) * scale
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = jnp.einsum("pcnqk,kpe->qpcne", w.astype(dt), vv)
        a = a[..., 0, :] - lam * a[..., 1, :]                   # (qb, P, 2, 2dh)
        a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1,
                                       keepdims=True) + eps) * sub_g
        return (a * jnp.asarray(1.0 - lam_0, dt)).astype(dt)

    out = jax.lax.map(block, (q.reshape(T // qb, qb, P, 2, 2, dh),
                              jnp.arange(0, T, qb)))
    return out.reshape(T, H * dh)


def _gated(h, wgu, wd, F, dt):
    """The feed-forward layer, a block of rows at a time."""
    T = h.shape[0]
    fb = min(F_BLOCK, T)
    assert T % fb == 0
    wgu, wd = wgu.astype(dt), wd.astype(dt)

    def rows(y):
        gu = y @ wgu
        return (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ wd

    return jax.lax.map(rows, h.reshape(T // fb, fb, -1)).reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("dtype", "n_at", "dims",
                                             "fault"))
def _forward(p, tokens, at0, *, dtype, n_at, dims, fault):
    m = dict(dims)
    dt = jnp.dtype(dtype)
    up = lambda a: a.astype(dt)  # noqa: E731
    T = tokens.shape[0]
    H, hkv, dh, eps = m["H"], m["hkv"], m["dh"], m["eps"]
    x = up(p["tok_emb"][tokens])
    memory = shared = None
    for i, kind in enumerate(m["kinds"]):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        h = _ln(x, up(g("ln1_g")), up(g("ln1_b")), eps)
        if kind == "ssm":
            out, memory = _mamba(h, g, m, up)
        elif kind == "gmu":
            mem = jnp.ones_like(memory) if fault == "no_memory" else memory
            out = (mem * jax.nn.silu(h @ up(g("w_i")))) @ up(g("w_o"))
        else:
            q = (h @ up(g("wq")) + up(g("bq"))).reshape(T, H, dh)
            if kind == "cross":
                k, v = shared
                if fault == "no_shared_kv":
                    k, v = jnp.zeros_like(k), jnp.zeros_like(v)
            else:
                k = (h @ up(g("wk")) + up(g("bk"))).reshape(T, hkv, dh)
                v = (h @ up(g("wv")) + up(g("bv"))).reshape(T, hkv, dh)
                if kind == "full":
                    shared = (k, v)
            lv = up(g("lam")).astype(jnp.float32)
            lam = (jnp.exp(jnp.sum(lv[0] * lv[1]))
                   - jnp.exp(jnp.sum(lv[2] * lv[3])) + lam0(i)).astype(dt)
            a = _diff_attn(q, k, v, lam, lam0(i), up(g("sub_g")),
                           m["window"] if kind == "swa" else 0, m, fault)
            out = a @ up(g("wo")) + up(g("bo"))
        x = x + out
        x = x + _gated(_ln(x, up(g("ln2_g")), up(g("ln2_b")), eps),
                       g("wgu"), g("wd"), m["F"], dt)
    x = jax.lax.dynamic_slice_in_dim(x, at0, n_at, axis=0)
    x = _ln(x, up(p["lnf_g"]), up(p["lnf_b"]), eps)
    return (x @ up(p["tok_emb"]).T).astype(jnp.float32)


def logits(params, c, tokens, n_valid, at0, n_at, *, dtype="float32",
           fault=None):
    """``(n_at, vocab)`` float32 logits at positions ``at0 .. at0 + n_at -
    1`` of one token sequence ``(T,)`` of which the first ``n_valid``
    exist (pad behind them to one length and it compiles once: every layer
    is causal, so what lies behind a position never reaches it; ``T`` a
    multiple of ``F_BLOCK`` or under ``Q_BLOCK``, or a multiple of
    ``Q_BLOCK`` under ``F_BLOCK``).  Row ``i`` predicts the token at
    position ``at0 + i + 1``."""
    del n_valid     # causal throughout: padding needs no mask
    assert fault is None or fault in FAULTS, fault
    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        return _forward(params, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(at0, jnp.int32), n_at=int(n_at),
                        dtype=str(jnp.dtype(dtype)), fault=fault,
                        dims=tuple(sorted(_dims(c).items())))
