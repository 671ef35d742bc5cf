"""Plain reference of ``dots.vlm1.inst``'s language model (the DeepSeek-V3
block: latent attention, a gated dense layer, sigmoid-routed experts with a
shared one) as the ``latent_decode`` driver serves it.  Imports nothing of
the program.

The forward pass: the whole sequence at once in ``jax.numpy``, float32 at
matmul precision ``highest``; no cache, no kernel, no batching; attention
in its MATERIALISED form (every head's own K and V from the latent; the
queries taken a block at a time so that the scores fit, each block against
all the keys); routing by explicit loops over the groups and the choices;
the experts one at a time as a dense masked sum (each upcast where it is
used)::

    h = rms(x, g1);  cq = rms(h Wqa, gq)
    [qn | qr] = (cq Wqb).reshape(H, dn + dr);   qr = rope(qr)
    [ckv | kr] = h Wkva;  ckv = rms(ckv, gkv);  kr = rope(kr)      one kr for all heads
    [kn | v] = (ckv Wkvb).reshape(H, dn + dv)
    a[t,h] = softmax_j<=t((qn[t,h].kn[j,h] + qr[t,h].kr[j]) s) @ v[:,h]
    x = x + a Wo;   s = (dn + dr)^-0.5 m^2,  m = 0.1 ln(40) + 1
    h = rms(x, g2)
    layer 0:   x = x + (silu(h Wg) * (h Wu)) Wd
    layers 1-: sc = sigmoid(h Wr);  c = sc + b          (b in choosing only)
               G_g = sum of the 2 largest c of group g;  keep the 4 groups of largest G
               e = the 8 largest c of the kept groups;  w = sc[e] / (sum sc[e] + 1e-20) * 2.5
               x = x + sum_i w_i E_{e_i}(h) + E_shared(h)
    logits = rms(x, gf) Wh

The chip's share: the router scores all the published experts; the
reference is given the same share as the program (``experts_held`` of the
configuration file) and leaves out, as the program does, what the experts
held elsewhere would have added.  The vocabulary is the configuration's
slice.

Departures from the source, each the program's too:
  * the rotary columns: the source stores them interleaved and permutes
    them to halves before it rotates; here (and in the program) the
    weights are taken to be in halves already — a fixed permutation of
    Wqb's and Wkva's columns, which seeded weights absorb;
  * a group or an expert tied with another goes to the lower index;
  * a masked-out group's scores are left out of the choice (the source
    writes 0 there; corrected sigmoid scores are positive here);
  * no multi-token-prediction module, no vision tower.

``dtype`` float32 is the reference; bfloat16 is the control, one precision
down: router scores, norms, softmax and every product's result in bfloat16.

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
import numpy as np

Q_BLOCK = 256        # queries a block of the attention


def _dims(c):
    """The sizes the functions here read from a configuration file."""
    rs = c["rope_scaling"]
    lo, hi = c.get("experts_held") or (0, c["n_routed_experts"])
    return dict(
        n_layers=c["num_hidden_layers"], n_dense=c["first_k_dense_replace"],
        d=c["hidden_size"], H=c["num_attention_heads"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], rq=c["q_lora_rank"], rkv=c["kv_lora_rank"],
        F=c["intermediate_size"], f=c["moe_intermediate_size"],
        E=c.get("published", c)["n_routed_experts"], lo=int(lo), hi=int(hi),
        n_shared=c["n_shared_experts"], k=c["num_experts_per_tok"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        norm_topk=bool(c["norm_topk_prob"]),
        scaling=float(c["routed_scaling_factor"]), vocab=c["vocab_size"],
        eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
        factor=float(rs["factor"]), orig=int(
            rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale_all_dim=float(rs["mscale_all_dim"]))


def init_params(seed, c, dtype="bfloat16"):
    """Seeded scaled-normal weights in ``dtype`` (scales as the SDAR-MoE
    reference's: embedding 0.1, products 1/sqrt(fan-in), residual outputs
    divided by sqrt(2 x layers); norm gains 1 + 0.1 n, so that a gain left
    out shows).  The router is at the products' scale and its correction
    bias 0.01 n (not zero: left out, it shows): a SIGMOID router twice as
    wide saturates — the best scores of 256 lie within 0.02 of one another
    — and a bias of 0.1 then decides every choice: half the experts never
    chosen, the held sixteen's share anywhere from 2% to 13% by the seed
    (PERF.md PR 30).  At these scales the load is even (a held expert's
    share 6.0-7.1%, the fullest expert 2-3 times the mean)."""
    m = _dims(c)
    d, H, E, f, F, V = m["d"], m["H"], m["E"], m["f"], m["F"], m["vocab"]
    dn, dr, dv, rq, rkv = m["dn"], m["dr"], m["dv"], m["rq"], m["rkv"]
    fs = m["n_shared"] * f
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

    def attention(key):
        return {"norm1": gain(key, 0, d),
                "wqa": normal(key, 1, (d, rq), d ** -0.5),
                "q_norm": gain(key, 2, rq),
                "wqb": normal(key, 3, (rq, H * (dn + dr)), rq ** -0.5),
                "wkva": normal(key, 4, (d, rkv + dr), d ** -0.5),
                "kv_norm": gain(key, 5, rkv),
                "wkvb": normal(key, 6, (rkv, H * (dn + dv)), rkv ** -0.5),
                "wo": normal(key, 7, (H * dv, d), (H * dv) ** -0.5 * res),
                "norm2": gain(key, 8, d)}

    @jax.jit
    def dense_layer(key):
        return dict(attention(key),
                    wg=normal(key, 9, (d, F), d ** -0.5),
                    wu=normal(key, 10, (d, F), d ** -0.5),
                    wd=normal(key, 11, (F, d), F ** -0.5 * res))

    @jax.jit
    def expert_layer(key):
        def expert(e):      # an expert's weights: its own number's
            ke = jax.random.fold_in(key, 1000 + e)
            return (normal(ke, 0, (d, f), d ** -0.5),
                    normal(ke, 1, (d, f), d ** -0.5),
                    normal(ke, 2, (f, d), f ** -0.5 * res))

        wg, wu, wd = jax.lax.map(expert, jnp.arange(m["lo"], m["hi"]))
        return dict(attention(key), wg=wg, wu=wu, wd=wd,
                    router=normal(key, 9, (d, E), d ** -0.5),
                    router_bias=normal(key, 10, (E,), 0.01),
                    sg=normal(key, 11, (d, fs), d ** -0.5),
                    su=normal(key, 12, (d, fs), d ** -0.5),
                    sd=normal(key, 13, (fs, d), fs ** -0.5 * res))

    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    p = ends(key)
    for i in range(m["n_layers"]):
        make = dense_layer if i < m["n_dense"] else expert_layer
        for name, w in make(jax.random.fold_in(key, 100 + i)).items():
            p[f"l{i}_{name}"] = w
    return p


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g


def _inv_freq(m):
    """YaRN: the plain frequency where a dimension turns more than
    ``beta_fast`` times over the original context, divided by ``factor``
    where it turns less than ``beta_slow`` times, a linear ramp between."""
    dr, base = m["dr"], m["theta"]
    plain = 1.0 / base ** (np.arange(0, dr, 2, dtype=np.float64) / dr)

    def dim_of(turns):
        return dr * math.log(m["orig"] / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(m["beta_fast"])), 0)
    high = min(math.ceil(dim_of(m["beta_slow"])), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / m["factor"] * ramp + plain * (1 - ramp)).astype(np.float32)


def _rope(x, inv_freq):
    """Rotate-half rotary embedding of ``x`` (T, H, D) at positions 0..;
    cos and sin unscaled (mscale / mscale_all_dim = 1)."""
    T, _, D = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos.astype(x.dtype) + rot * sin.astype(x.dtype)


def _first_max(x):
    """(value, index) of the first largest entry of each row."""
    idx = jnp.argmax(x, axis=-1)
    return jnp.take_along_axis(x, idx[:, None], axis=-1)[:, 0], idx


def route(logits, bias, *, k, n_group, topk_group, norm_topk, scaling):
    """The router, by explicit loops: ``logits`` (T, E) -> ``(w, e)`` (T,
    k).  The first of equal scores wins, at every choice."""
    T, E = logits.shape
    per = E // n_group
    sc = jax.nn.sigmoid(logits)
    c = sc + bias.astype(sc.dtype)
    rows = jnp.arange(T)
    group_score = []
    for g in range(n_group):
        cg = c[:, g * per:(g + 1) * per]
        best, at = _first_max(cg)
        second, _ = _first_max(cg.at[rows, at].set(-jnp.inf))
        group_score.append(best + second)
    group_score = jnp.stack(group_score, axis=1)                # (T, G)
    kept = jnp.zeros((T, n_group), bool)
    for _ in range(topk_group):
        _, at = _first_max(jnp.where(kept, -jnp.inf, group_score))
        kept = kept.at[rows, at].set(True)
    choice = jnp.where(jnp.repeat(kept, per, axis=1), c, -jnp.inf)
    es = []
    for _ in range(k):
        _, at = _first_max(choice)
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


def _attention(q, k, v, n_valid, scale):
    """Causal attention of ``q`` (T, H, D) over ``k`` (T, H, D), ``v`` (T,
    H, dv), a block of queries at a time against all the keys."""
    T = q.shape[0]
    qb = min(Q_BLOCK, T)
    assert T % qb == 0
    keys = jnp.arange(T)

    def block(args):
        qi, i0 = args
        at = i0 + jnp.arange(qb)
        mask = (keys[None, :] <= at[:, None]) & (keys[None, :] < n_valid)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        a = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v)

    out = jax.lax.map(block, (q.reshape(T // qb, qb, *q.shape[1:]),
                              jnp.arange(0, T, qb)))
    return out.reshape(T, *out.shape[2:])


@functools.partial(jax.jit, static_argnames=("dtype", "n_at", "dims"))
def _forward(p, tokens, n_valid, at0, *, dtype, n_at, dims):
    m = dict(dims)
    dt = jnp.dtype(dtype)
    up = lambda a: a.astype(dt)  # noqa: E731
    T = tokens.shape[0]
    H, dn, dr, dv, rkv = m["H"], m["dn"], m["dr"], m["dv"], m["rkv"]
    eps = m["eps"]
    inv_freq = jnp.asarray(_inv_freq(m))
    ms = 0.1 * m["mscale_all_dim"] * math.log(m["factor"]) + 1.0
    scale = (dn + dr) ** -0.5 * ms * ms
    x = up(p["tok_emb"][tokens])
    for i in range(m["n_layers"]):
        g = lambda n: p[f"l{i}_{n}"]  # noqa: B023,E731
        h = _rms(x, up(g("norm1")), eps)
        q = (_rms(h @ up(g("wqa")), up(g("q_norm")), eps) @ up(g("wqb"))
             ).reshape(T, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq)], -1)
        kva = h @ up(g("wkva"))
        ckv = _rms(kva[:, :rkv], up(g("kv_norm")), eps)
        kr = _rope(kva[:, None, rkv:], inv_freq)                # (T, 1, dr)
        kv = (ckv @ up(g("wkvb"))).reshape(T, H, dn + dv)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(kr, (T, H, dr))], axis=-1)
        a = _attention(q, k, kv[..., dn:], n_valid, scale)
        x = x + a.reshape(T, H * dv) @ up(g("wo"))
        h = _rms(x, up(g("norm2")), eps)
        if i < m["n_dense"]:
            x = x + _gated(h, g("wg"), g("wu"), g("wd"), dt)
            continue
        w, e = route(h @ up(g("router")), g("router_bias"), k=m["k"],
                     n_group=m["n_group"], topk_group=m["topk_group"],
                     norm_topk=m["norm_topk"], scaling=m["scaling"])
        x = x + _experts(h, w, e, g("wg"), g("wu"), g("wd"), m["lo"], dt) \
            + _gated(h, g("sg"), g("su"), g("sd"), dt)
    x = jax.lax.dynamic_slice_in_dim(x, at0, n_at, axis=0)
    return (_rms(x, up(p["norm_f"]), eps) @ up(p["head"])).astype(
        jnp.float32)


def logits(params, c, tokens, n_valid, at0, n_at, *, dtype="float32"):
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
                        dtype=str(jnp.dtype(dtype)),
                        dims=tuple(sorted(_dims(c).items())))
