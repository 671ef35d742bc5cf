"""Pallas TPU kernels for ops XLA fuses poorly (SURVEY.md §7: 'Pallas for the
few kernels XLA fuses poorly — e.g. 2-bit compression pack/unpack').

Kernels run natively on TPU; on CPU (tests, virtual meshes) `interpret=True`
executes the same kernel through the Pallas interpreter, which is the
same-op-two-backends oracle the reference used for GPU-vs-CPU tests
(SURVEY.md §4).

2-bit gradient compression (reference: src/kvstore/gradient_compression.cu):
one fused pass computes sign thresholding, error-feedback residual, and the
16-lane bit-pack — three HBM round-trips in the jnp version, one here.
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 16  # 2-bit codes per uint32 word (reference layout)


def pallas_enabled() -> bool:
    """The single ``TPUMX_PALLAS`` gate for the hot-path kernel layer
    (docs/pallas.md): paged decode attention, the flash-attention backward
    kernels, and fused LayerNorm.  Default ON for TPU backends;
    ``TPUMX_PALLAS=0`` restores the XLA-composed paths (and their compile
    keys) byte-identically, ``=1`` forces the kernels on CPU through the
    Pallas interpreter (the tier-1 parity leg).  Read at TRACE time — like
    ``MXTPU_BN_PALLAS``, A/B it across processes, not mid-run.
    """
    forced = os.environ.get("TPUMX_PALLAS")
    if forced is not None:
        return forced != "0"
    return jax.default_backend() == "tpu"


def _twobit_pack_kernel(g_ref, res_ref, thresh_ref, packed_ref, newres_ref):
    t = thresh_ref[0, 0]
    g = g_ref[:] + res_ref[:]                      # error feedback
    pos = (g >= t)
    neg = (g <= -t)
    newres_ref[:] = g - jnp.where(pos, t, 0.0) + jnp.where(neg, t, 0.0)
    codes = pos.astype(jnp.uint32) | (neg.astype(jnp.uint32) << 1)
    # codes: (rows, LANES*128) → pack 16 consecutive lane-groups per word:
    # view as (rows, 128, LANES) words × lanes, shift-or across the lane dim
    rows = codes.shape[0]
    lanes = codes.reshape(rows, _LANES, 128)
    # static unrolled OR-pack: Mosaic has no unsigned reductions
    acc = lanes[:, 0, :]
    for i in range(1, _LANES):
        acc = acc | (lanes[:, i, :] << jnp.uint32(2 * i))
    packed_ref[:] = acc


def _twobit_unpack_kernel(packed_ref, thresh_ref, out_ref):
    t = thresh_ref[0, 0]
    rows = packed_ref.shape[0]
    shifts = (jnp.arange(_LANES, dtype=jnp.uint32) * 2)[None, :, None]
    lanes = (packed_ref[:][:, None, :] >> shifts) & jnp.uint32(0x3)
    vals = jnp.where(lanes == 1, t, jnp.where(lanes == 2, -t, 0.0))
    out_ref[:] = vals.reshape(rows, _LANES * 128).astype(out_ref.dtype)


_ROW_BLOCK = 64  # rows per program: 64×2048 f32 ≈ 0.5 MB per VMEM buffer


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_call(g2d, res2d, thresh, interpret):
    rows = g2d.shape[0]  # caller pads rows to a _ROW_BLOCK multiple
    rb = min(_ROW_BLOCK, rows)
    block = _LANES * 128
    return pl.pallas_call(
        _twobit_pack_kernel,
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, block), lambda i: (i, 0)),
                  pl.BlockSpec((rb, block), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((rb, 128), lambda i: (i, 0)),
                   pl.BlockSpec((rb, block), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
                   jax.ShapeDtypeStruct(g2d.shape, g2d.dtype)),
        interpret=interpret,
        name="_pack_call",
    )(g2d, res2d, thresh)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _unpack_call(packed2d, thresh, dtype, interpret):
    rows = packed2d.shape[0]
    rb = min(_ROW_BLOCK, rows)
    return pl.pallas_call(
        _twobit_unpack_kernel,
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, 128), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, _LANES * 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES * 128), dtype),
        interpret=interpret,
        name="_unpack_call",
    )(packed2d, thresh)


def _use_interpret() -> bool:
    # TPUMX_PALLAS_INTERPRET=1 forces the interpreter even on a TPU host —
    # the two-backend oracle (tools/tpu_parity.py) needs a CPU-interpreted
    # reference leg that is NOT the native Mosaic lowering being checked.
    forced = os.environ.get("TPUMX_PALLAS_INTERPRET")
    if forced is not None:
        return forced == "1"
    return jax.default_backend() != "tpu"


def twobit_pack(grad, residual, threshold):
    """Fused 2-bit quantize with error feedback.

    grad/residual: same shape, any rank. Returns (packed uint32 (W, 128),
    new_residual like grad). Elements are padded to LANES*128 blocks.
    """
    flat = grad.reshape(-1)
    res = residual.reshape(-1)
    block = _LANES * 128
    rows = -(-flat.shape[0] // block)
    if rows > _ROW_BLOCK:  # gridded path needs a whole number of row blocks
        rows = -(-rows // _ROW_BLOCK) * _ROW_BLOCK
    pad = rows * block - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
        res = jnp.concatenate([res, jnp.zeros(pad, res.dtype)])
    thresh = jnp.full((1, 1), threshold, flat.dtype)
    packed, newres = _pack_call(flat.reshape(rows, block),
                                res.reshape(rows, block), thresh,
                                _use_interpret())
    newres = newres.reshape(-1)[:grad.size].reshape(grad.shape)
    return packed, newres


def twobit_unpack(packed, shape, threshold, dtype=jnp.float32):
    """Inverse of twobit_pack: packed (W, 128) → dense tensor of `shape`."""
    rows = packed.shape[0]
    if rows > _ROW_BLOCK and rows % _ROW_BLOCK:
        pad = -(-rows // _ROW_BLOCK) * _ROW_BLOCK - rows
        packed = jnp.concatenate(
            [packed, jnp.zeros((pad, 128), packed.dtype)])
    thresh = jnp.full((1, 1), threshold, jnp.dtype(dtype))
    out = _unpack_call(packed, thresh, jnp.dtype(dtype), _use_interpret())
    n = 1
    for s in shape:
        n *= s
    return out.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# Flash attention moved to its own module (ops/flash_attention.py):
# fori-loop KV streaming with causal block skipping, arbitrary T via
# padding+masking, and a memory-efficient scan backward.  Re-exported here
# so pk.flash_attention remains the stable name (tpu_parity, contrib op).
from .flash_attention import flash_attention  # noqa: E402,F401


# ---------------------------------------------------------------------------
# BatchNorm train-mode stats + normalize (reference:
# src/operator/nn/batch_norm.cc train-mode forward; cuDNN fuses these the
# same way): what train mode adds to the eval forward is the batch-stat
# passes over the activation.  Layout: channels-minor (NHWC collapsed to
# (M, C)) so C rides the 128-lane dim.
#
# stats kernel: ONE read of the activation produces both sum and sum-of-
# squares (TPU grid steps run sequentially, so partial sums accumulate into
# the same (1, C) output block across the grid).  normalize kernel: one
# read + one write applying (x - mean) * scale + shift.
# ---------------------------------------------------------------------------

def _bn_stats_kernel(x_ref, pivot_ref, s1_ref, s2_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # recentered around a per-channel pivot to avoid the E[x^2] - mean^2
    # cancellation at large mean/std (see batch_norm's one-pass comment)
    x = x_ref[...].astype(jnp.float32) - pivot_ref[...]
    s1_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _bn_stats_call(x2d, pivot, block_m, interpret):
    m, c = x2d.shape
    s1, s2 = pl.pallas_call(
        _bn_stats_kernel,
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((block_m, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        interpret=interpret,
        name="_bn_stats_call",
    )(x2d, pivot.reshape(1, c))
    return s1[0], s2[0]


def _bn_norm_kernel(x_ref, scale_ref, shift_ref, o_ref):
    # shift form: mean is folded into shift = beta - mean*scale already
    xf = x_ref[...].astype(jnp.float32)
    o_ref[...] = (xf * scale_ref[...] + shift_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _bn_norm_call(x2d, scale, shift, block_m, interpret):
    m, c = x2d.shape
    bcast = [pl.BlockSpec((1, c), lambda i: (0, 0))] * 2
    return pl.pallas_call(
        _bn_norm_kernel,
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((block_m, c), lambda i: (i, 0))] + bcast,
        out_specs=pl.BlockSpec((block_m, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c), x2d.dtype),
        interpret=interpret,
        name="_bn_norm_call",
    )(x2d, scale.reshape(1, c), shift.reshape(1, c))


def _row_cap(c: int) -> int:
    """Most rows of a ``(rows, c)`` f32 block that stay under ~1 MB of
    VMEM (in and out tiles are each double-buffered): a power of two in
    [8, 1024]."""
    cap = 1024
    while cap > 8 and cap * c * 4 > (1 << 20):
        cap //= 2
    return cap


def _bn_block_m(m: int, c: int = 128) -> int:
    """Largest power-of-two block dividing m that fits VMEM; < 8 means the
    row count is not a multiple of 8 (batch statistics cannot be padded
    for free) and the caller falls back to XLA with a warning."""
    cand = _row_cap(c)
    while cand >= 8:
        if m % cand == 0:
            return cand
        cand //= 2
    return 1


def _bn_train_reference(x, gamma, beta, eps):
    """jnp reference of the fused forward (channels-last) — the vjp donor
    for the backward pass, like _flash_bwd replays local_attention."""
    xf = x.astype(jnp.float32)
    red = tuple(range(x.ndim - 1))
    pivot = jax.lax.stop_gradient(xf[(0,) * (x.ndim - 1)])
    xc = xf - pivot
    mean_c = jnp.mean(xc, axis=red)
    var = jnp.maximum(jnp.mean(xc * xc, axis=red) - mean_c * mean_c, 0.0)
    mean = mean_c + pivot
    inv = jax.lax.rsqrt(var + eps)
    out = ((xf - mean) * (gamma.astype(jnp.float32) * inv)
           + beta.astype(jnp.float32)).astype(x.dtype)
    return out, mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def bn_train_fused(x, gamma, beta, eps, channel_axis):
    """Fused train-mode BN over channels-minor data.  Returns
    (out, mean, var) — mean/var so the stateful frontends can run their
    running-stat update (gluon calls with output_mean_var=True).  x of any
    rank with channels on `channel_axis` == last axis; a row count that is
    not a multiple of 8 runs the jnp reference, with a one-time warning
    that names the shape."""
    out, _res = _bn_fused_fwd(x, gamma, beta, eps, channel_axis)
    return out


def _bn_fused_fwd(x, gamma, beta, eps, channel_axis):
    shape = x.shape
    c = shape[channel_axis]
    x2d = x.reshape(-1, c)
    m = x2d.shape[0]
    block_m = _bn_block_m(m, c)
    if block_m < 8:
        # never silent — on the chip a quiet fallback reads as "the kernel
        # ran".  (The default warning filter shows it once per shape.)
        warnings.warn(
            f"bn_train_fused: {m} rows is not a multiple of 8 — running "
            f"the jnp reference instead of the Pallas kernels for shape "
            f"{x2d.shape}", RuntimeWarning, stacklevel=3)
        out, mean, var = _bn_train_reference(x, gamma, beta, eps)
        return (out, mean, var), (x, gamma, beta)
    interp = _use_interpret()
    pivot = jax.lax.stop_gradient(x2d[0].astype(jnp.float32))
    s1, s2 = _bn_stats_call(x2d, pivot, block_m, interp)
    n = jnp.float32(m)
    mean_c = s1 / n
    var = jnp.maximum(s2 / n - mean_c * mean_c, 0.0)
    mean = mean_c + pivot
    scale = gamma.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    shift = beta.astype(jnp.float32) - mean * scale
    # shift form: out = x*scale + shift == (x-mean)*scale + beta
    out2d = _bn_norm_call(x2d, scale, shift, block_m, interp)
    return (out2d.reshape(shape), mean, var), (x, gamma, beta)


def _bn_fused_bwd(eps, channel_axis, res, g):
    x, gamma, beta = res
    _, vjp = jax.vjp(
        lambda x_, g_, b_: _bn_train_reference(x_, g_, b_, eps), x, gamma,
        beta)
    return vjp(g)


bn_train_fused.defvjp(_bn_fused_fwd, _bn_fused_bwd)


# ---------------------------------------------------------------------------
# Fused LayerNorm(+GELU) — the channels-minor normalization the transformer
# LM runs twice per block per token (parallel/transformer.py _ln and the
# registered LayerNorm op, ops/nn.py).  Same one-read-two-sums shape as
# bn_train_fused, but the reduction is PER ROW (the 128-lane minor dim), so
# stats and normalize fuse into ONE kernel: one HBM read, one write — the
# XLA graph reads the activation twice (mean pass + var/normalize pass) and
# materializes the centered intermediate.  The optional GELU epilogue folds
# the activation of a following MLP in the same write.  Gated behind
# TPUMX_PALLAS (pallas_enabled); backward is the jnp reference's vjp, like
# bn_train_fused.
# ---------------------------------------------------------------------------

def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float, gelu: bool):
    xf = x_ref[...].astype(jnp.float32)
    c = xf.shape[-1]
    # per-row pivot recenter (one lane) keeps the one-pass E[x^2]-mean^2
    # form from cancelling at large mean/std — same trick as _bn_stats
    pivot = xf[:, :1]
    xc = xf - pivot
    mean_c = jnp.sum(xc, axis=1, keepdims=True) / c
    var = jnp.maximum(
        jnp.sum(xc * xc, axis=1, keepdims=True) / c - mean_c * mean_c, 0.0)
    out = (xc - mean_c) * jax.lax.rsqrt(var + eps) \
        * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if gelu:
        out = jax.nn.gelu(out)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("eps", "gelu", "block_m", "interpret"))
def _ln_call(x2d, gamma, beta, eps, gelu, block_m, interpret):
    m, c = x2d.shape
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps, gelu=gelu),
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((block_m, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_m, c), lambda i: (i, 0)),
        # like the flash forward: inside shard_map the output must carry
        # the inputs' varying mesh axes
        out_shape=jax.ShapeDtypeStruct((m, c), x2d.dtype,
                                       vma=jax.typeof(x2d).vma),
        interpret=interpret,
        name="_ln_call_gelu" if gelu else "_ln_call",
    )(x2d, gamma.reshape(1, c), beta.reshape(1, c))


def _ln_reference(x, gamma, beta, eps, gelu):
    """jnp reference of the fused forward — the vjp donor.  f32 stats
    regardless of x dtype (the kernel computes the same way)."""
    xf = x.astype(jnp.float32)
    pivot = jax.lax.stop_gradient(xf[..., :1])
    xc = xf - pivot
    mean_c = jnp.mean(xc, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(xc * xc, axis=-1, keepdims=True)
                      - mean_c * mean_c, 0.0)
    out = (xc - mean_c) * jax.lax.rsqrt(var + eps) \
        * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    if gelu:
        out = jax.nn.gelu(out)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm_fused(x, gamma, beta, eps=1e-5, gelu=False):
    """Fused LayerNorm over the LAST axis of ``x`` (any rank); ``gamma`` /
    ``beta`` are ``(C,)``.  ``gelu=True`` applies the GELU epilogue to the
    normalized output in the same kernel pass.  Any row count runs the
    kernel (rows are padded to a legal block)."""
    out, _res = _ln_fused_fwd(x, gamma, beta, eps, gelu)
    return out


def _ln_fused_fwd(x, gamma, beta, eps, gelu):
    shape = x.shape
    c = shape[-1]
    x2d = x.reshape(-1, c)
    m = x2d.shape[0]
    # rows are independent, so any row count runs the kernel: pad up to a
    # legal block (a multiple of 8 rows) and slice the padding off — the
    # engine's max_slots=4 decode step is such a shape
    block_m = min(_row_cap(c), -(-m // 8) * 8)
    m_pad = -(-m // block_m) * block_m
    if m_pad != m:
        x2d = jnp.pad(x2d, ((0, m_pad - m), (0, 0)))
    out2d = _ln_call(x2d, gamma, beta, float(eps), bool(gelu), block_m,
                     _use_interpret())
    return out2d[:m].reshape(shape), (x, gamma, beta)


def _ln_fused_bwd(eps, gelu, res, g):
    x, gamma, beta = res
    _, vjp = jax.vjp(
        lambda x_, g_, b_: _ln_reference(x_, g_, b_, eps, gelu), x, gamma,
        beta)
    return vjp(g)


layer_norm_fused.defvjp(_ln_fused_fwd, _ln_fused_bwd)
