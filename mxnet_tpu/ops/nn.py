"""Neural-net ops: FC, conv, pooling, norms, softmax family, activation, dropout.

Covers the reference's ``src/operator/nn/*`` (SURVEY.md §2.1; conv/deconv/FC/
pool/norm/softmax/activation/dropout — ~14k LoC CUDA) plus the cuDNN wrapper
surface, as XLA emitters.  Convolutions lower through ``lax.conv_general_dilated``
which XLA tiles onto the MXU.  Mixed precision: matmuls request f32
accumulation via ``preferred_element_type``; convs rely on the MXU's implicit
f32 accumulation for bf16 (jax's conv transpose rule rejects an explicit
``preferred_element_type``), fp16 convs and ALL low-precision deconvs are
computed in f32 and cast back — together the TPU-native analogue of the
reference's fp16-with-fp32-master-weights path
(``python/mxnet/optimizer.py:494``; see also mxnet_tpu.amp / docs/amp.md).

Data layout: the public ops accept the reference's default NCHW ("NCHW" attr)
but also "NHWC"; internally XLA's layout assignment owns the physical layout,
so no manual transposes are inserted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


def _acc(x):
    return jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else None


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


# ---------------------------------------------------------------------------
# FullyConnected (src/operator/nn/fully_connected.cc)
# ---------------------------------------------------------------------------

@register("FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    out = jnp.dot(x, weight.T, preferred_element_type=_acc(x))
    if out.dtype != x.dtype:
        out = out.astype(x.dtype)
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (src/operator/nn/convolution.cc, deconvolution.cc)
# ---------------------------------------------------------------------------

def is_channels_last(layout):
    """True for channels-last layout strings ("NHWC"/"NWC"/"NDHWC"); False for
    None or channels-first ("NC...")."""
    return bool(layout) and layout[1] != "C"


def _conv_dnums(ndim, layout):
    # channels-last kernels follow the reference's convention for layout=N..C:
    # weight is (num_filter, *k, channels/group), i.e. O<spatial>I
    if ndim == 3:  # NCW
        return ("NCH", "OIH", "NCH") if layout in (None, "NCW") else ("NHC", "OHI", "NHC")
    if ndim == 4:
        if layout in (None, "NCHW"):
            return ("NCHW", "OIHW", "NCHW")
        return ("NHWC", "OHWI", "NHWC")
    if layout in (None, "NCDHW"):
        return ("NCDHW", "OIDHW", "NCDHW")
    return ("NDHWC", "ODHWI", "NDHWC")


@register("Convolution")
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                num_filter=0, num_group=1, no_bias=False, layout=None,
                cudnn_tune=None, cudnn_off=False, workspace=1024):
    """NNVM Convolution (reference: src/operator/nn/convolution.cc).

    cudnn_* / workspace attrs accepted and ignored (XLA owns algorithm choice).
    """
    nd = data.ndim - 2
    k = len(kernel) if kernel else nd
    stride = _pair(stride, k) if stride else (1,) * k
    dilate = _pair(dilate, k) if dilate else (1,) * k
    pad = _pair(pad, k) if pad else (0,) * k
    dnums = lax.conv_dimension_numbers(data.shape, weight.shape,
                                       _conv_dnums(data.ndim, layout))
    # fp16 has no implicit f32 accumulation guarantee: compute in f32
    # (bf16 accumulates in f32 on the MXU by construction)
    in_dtype = data.dtype
    if in_dtype == jnp.float16:
        data, weight = data.astype(jnp.float32), weight.astype(jnp.float32)
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dnums,
        feature_group_count=int(num_group),
        # no preferred_element_type: jax's conv transpose rule can't upcast
        # cotangents
    )
    if in_dtype == jnp.float16:
        out = out.astype(in_dtype)
    if not no_bias and bias is not None:
        if layout in (None, "NCHW", "NCW", "NCDHW"):
            out = out + bias.reshape((1, -1) + (1,) * nd)
        else:
            out = out + bias
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                  adj=(), num_filter=0, num_group=1, no_bias=False, layout=None,
                  target_shape=None, cudnn_tune=None, cudnn_off=False, workspace=1024):
    """Transposed convolution (reference: src/operator/nn/deconvolution.cc)."""
    if is_channels_last(layout):
        # the flip/swap/regroup below is channels-first math; refuse rather
        # than silently mis-binding axes (same guard as gluon's Conv*Transpose)
        raise NotImplementedError(
            "channels-last layout is not supported for Deconvolution; "
            "use NC* layout")
    nd = data.ndim - 2
    k = len(kernel) if kernel else nd
    stride = _pair(stride, k) if stride else (1,) * k
    dilate = _pair(dilate, k) if dilate else (1,) * k
    pad = _pair(pad, k) if pad else (0,) * k
    adj = _pair(adj, k) if adj else (0,) * k
    # weight layout for Deconvolution in the reference is (in, out/group, *k)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dnums(data.ndim, layout))
    # conv_transpose via gradient-of-conv: lhs_dilation implements the stride.
    kernel_dims = [weight.shape[i] for i in range(2, 2 + k)]
    padding = []
    for i in range(k):
        eff_k = (kernel_dims[i] - 1) * dilate[i] + 1
        lo = eff_k - 1 - pad[i]
        hi = eff_k - 1 - pad[i] + adj[i]
        padding.append((lo, hi))
    # flip spatial dims and swap in/out channels to express transpose as conv.
    # weight is (in_total, out/group, *k); the group split must happen on the
    # IN axis before the per-group transpose, else the (out, in) channel
    # pairing scrambles for num_group > 1
    num_group = int(num_group)
    wt = jnp.flip(weight, axis=tuple(range(2, 2 + k)))
    ci, og = weight.shape[0], weight.shape[1]
    wt = wt.reshape(num_group, ci // num_group, og, *kernel_dims)
    wt = jnp.swapaxes(wt, 1, 2)                  # (g, out/g, in/g, *k)
    wt = wt.reshape(num_group * og, ci // num_group, *kernel_dims)
    # the conv-transpose lowering can't request preferred_element_type (jax's
    # transpose rule rejects it), so a bf16/fp16 deconv would accumulate in
    # low precision on non-MXU backends: compute in f32 and cast back, like
    # the fp16 Convolution path above
    in_dtype = data.dtype
    if in_dtype in (jnp.float16, jnp.bfloat16):
        data, wt = data.astype(jnp.float32), wt.astype(jnp.float32)
    out = lax.conv_general_dilated(
        data, wt,
        window_strides=(1,) * k,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    )
    if out.dtype != in_dtype:
        out = out.astype(in_dtype)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (src/operator/nn/pooling.cc)
# ---------------------------------------------------------------------------

@register("Pooling")
def pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(), pad=(),
            pooling_convention="valid", cudnn_off=False, count_include_pad=True,
            layout=None):
    nd = data.ndim - 2
    # channels-last layouts put spatial dims at 1..nd; channels-first at 2..nd+1
    channels_last = is_channels_last(layout)
    sp0 = 1 if channels_last else 2
    spatial = tuple(range(sp0, sp0 + nd))
    if global_pool:
        if pool_type == "max":
            return jnp.max(data, axis=spatial, keepdims=True)
        return jnp.mean(data, axis=spatial, keepdims=True)
    k = _pair(kernel, nd)
    # reference PoolingParamParser defaults stride to 1 (pooling.cc:43-54);
    # gluon layers pass their own stride=pool_size default explicitly
    s = _pair(stride, nd) if stride else (1,) * nd
    p = _pair(pad, nd) if pad else (0,) * nd
    if any(v < 1 for v in s):
        from ..base import MXNetError

        raise MXNetError(f"Pooling stride must be >= 1, got {s}")
    for i in range(nd):
        # reference pooling checks kernel <= padded input (pooling-inl.h
        # shape infer); XLA's reduce_window would instead emit a ZERO-SIZE
        # output that silently poisons everything downstream (e.g.
        # inception_v3 fed 224px produced constant logits from an empty
        # matmul instead of this error)
        if k[i] > data.shape[sp0 + i] + 2 * p[i]:
            from ..base import MXNetError

            raise MXNetError(
                f"Pooling kernel {k} exceeds padded input "
                f"{tuple(data.shape[sp0 + j] for j in range(nd))} "
                f"(pad {p})")

    def _full(vals, fill):
        core = list(vals)
        return ((fill,) + tuple(core) + (fill,)) if channels_last \
            else ((fill, fill) + tuple(core))

    window = _full(k, 1)
    strides = _full(s, 1)
    if pooling_convention == "full":
        # ceil-mode: pad high side enough that ceil division is honored
        sp_pads = []
        for i in range(nd):
            in_sz = data.shape[sp0 + i] + 2 * p[i]
            out_sz = -(-(in_sz - k[i]) // s[i]) + 1  # ceil
            needed = (out_sz - 1) * s[i] + k[i] - in_sz
            sp_pads.append((p[i], p[i] + max(0, needed)))
    else:
        sp_pads = [(p[i], p[i]) for i in range(nd)]
    pads = list(_full(sp_pads, (0, 0)))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = 1.0
            for kk in k:
                denom *= kk
            return summed / jnp.asarray(denom, summed.dtype)
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return summed / counts
    raise ValueError(f"unsupported pool_type {pool_type}")


@register("_contrib_AdaptiveAvgPooling2D")
def adaptive_avg_pooling(data, output_size=(1, 1)):
    os = _pair(output_size, 2)
    n, c, h, w = data.shape
    if h % os[0] == 0 and w % os[1] == 0:
        x = data.reshape(n, c, os[0], h // os[0], os[1], w // os[1])
        return x.mean(axis=(3, 5))
    # general: interpolate bin edges via mean over gathered windows
    out = jax.image.resize(data, (n, c, os[0], os[1]), method="linear")
    return out


# ---------------------------------------------------------------------------
# Normalization (batch_norm.cc, layer_norm.cc, instance_norm, l2, lrn)
# ---------------------------------------------------------------------------

@register("BatchNorm", num_outputs=lambda attrs: 3 if attrs.get("output_mean_var") else 1)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
               fix_gamma=True, use_global_stats=False, output_mean_var=False,
               axis=1, cudnn_off=False, _training=True):
    """Reference: src/operator/nn/batch_norm.cc.

    Pure-functional: running-stat update is returned to the caller by the
    stateful frontends (NDArray/Gluon) rather than mutated here — see
    ndarray/__init__.py `_STATEFUL_BN` handling.
    """
    import os

    ax = int(axis) % data.ndim  # normalize axis=-1 (channels-last BN)
    red = tuple(i for i in range(data.ndim) if i != ax)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if _training and not use_global_stats:
        if (os.environ.get("MXTPU_BN_PALLAS") == "1" and ax == data.ndim - 1
                and data.shape[ax] % 128 == 0):
            # fused Pallas stats+normalize for channels-minor layouts
            # (one read for the train-forward batch statistics).  NOTE:
            # the env var is read at TRACE time and baked into jit caches —
            # A/B it across fresh processes, not by flipping os.environ
            # mid-run.
            from . import pallas_kernels as _pk

            out, mean, var = _pk.bn_train_fused(data, g, beta, float(eps), ax)
            if output_mean_var:
                return out, mean, var
            return out
        xf = data.astype(jnp.float32)
        # ONE pass over the activation: sum and sum-of-squares are sibling
        # reductions over the same operand, which XLA multi-output-fuses
        # into a single read (jnp.var's (x - mean)**2 form costs a second
        # full pass).  The raw E[x^2] - mean^2 form cancels catastrophically
        # at large mean/std, so recenter around a cheap per-channel pivot
        # (one sampled row): E[(x-p)^2] - (mean-p)^2 is exact for any
        # constant p and keeps the relative error O(((mean-p)/std)^2) ~ O(1)
        slicer = tuple(slice(None) if i == ax else 0 for i in range(data.ndim))
        pivot = lax.stop_gradient(xf[slicer]).reshape(shape)
        xc = xf - pivot
        mean_c = jnp.mean(xc, axis=red)
        var = jnp.maximum(jnp.mean(xc * xc, axis=red) - mean_c * mean_c, 0.0)
        mean = mean_c + pivot.reshape(-1)
    else:
        mean, var = moving_mean, moving_var
    inv = lax.rsqrt(var + eps)
    out = (data - mean.reshape(shape).astype(data.dtype)) * (g * inv).reshape(shape).astype(data.dtype) \
        + beta.reshape(shape).astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    ax = int(axis) % data.ndim
    if ax == data.ndim - 1 and not output_mean_var:
        # channels-minor path: fused Pallas stats+normalize under the
        # TPUMX_PALLAS gate (docs/pallas.md) — one activation read instead
        # of the mean pass + var/normalize pass XLA composes here.  Trace-
        # time gate, same A/B discipline as MXTPU_BN_PALLAS above.
        from . import pallas_kernels as _pk

        if _pk.pallas_enabled():
            return _pk.layer_norm_fused(data, gamma, beta,
                                        eps=float(eps)).astype(data.dtype)
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN")
def lrn(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    sq = jnp.square(data)
    half = int(nsize) // 2
    padded = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    win = lax.reduce_window(padded, 0.0, lax.add, (1, int(nsize), 1, 1), (1, 1, 1, 1),
                            [(0, 0)] * 4)
    # reference lrn-inl.h:103: salpha = alpha / nsize
    norm = jnp.power(knorm + (alpha / int(nsize)) * win, beta)
    return data / norm


# ---------------------------------------------------------------------------
# Softmax family (softmax.cc, softmax_output.cc)
# ---------------------------------------------------------------------------

@register("softmax")
def softmax(data, axis=-1, temperature=None):
    x = data if temperature in (None, 1.0) else data / temperature
    return jax.nn.softmax(x, axis=int(axis))


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data if temperature in (None, 1.0) else data / temperature
    return jax.nn.log_softmax(x, axis=int(axis))


@register("softmin")
def softmin(data, axis=-1, temperature=None):
    return softmax(-data, axis=axis, temperature=temperature)


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Fused softmax + CE-gradient head (reference: src/operator/nn/softmax_output.cc).

    Forward emits softmax probabilities; the custom backward (grad = p - onehot)
    is expressed via a custom_vjp so autograd matches the reference exactly,
    including ignore_label masking and normalization modes.  ``out_grad=True``
    (reference softmax_output-inl.h kOut grad multiply) makes the head honor
    the incoming cotangent — the hook AMP loss scaling rides (amp.convert_symbol
    flips it so the scaled seed propagates; a ones seed is a no-op).
    """
    from ..symbol.graph import attr_bool

    return _softmax_output_vjp(data, label, float(grad_scale), float(ignore_label),
                               bool(multi_output), bool(use_ignore),
                               str(normalization), float(smooth_alpha),
                               attr_bool(out_grad))


from functools import partial


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _softmax_output_vjp(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, normalization, smooth_alpha, out_grad=False):
    return _softmax_fwd_only(data, multi_output)


def _softmax_fwd_only(data, multi_output):
    if multi_output and data.ndim > 2:
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _so_fwd(data, label, grad_scale, ignore_label, multi_output, use_ignore,
            normalization, smooth_alpha, out_grad=False):
    out = _softmax_fwd_only(data, multi_output)
    return out, (out, label)


def _so_bwd(grad_scale, ignore_label, multi_output, use_ignore, normalization,
            smooth_alpha, out_grad, res, g):
    out, label = res
    # probability labels (label.shape == data.shape): grad = scale*(p - label),
    # no ignore/normalization (softmax_output-inl.h:154-160)
    if tuple(label.shape) == tuple(out.shape):
        grad = (out - label.astype(out.dtype)) * grad_scale
        if out_grad:
            grad = grad * g.astype(grad.dtype)
        return (grad.astype(out.dtype), jnp.zeros_like(label))
    if multi_output and out.ndim > 2:
        nclass = out.shape[1]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, nclass, axis=1, dtype=out.dtype)
        spatial = 1
        for d in out.shape[2:]:
            spatial *= d
    else:
        nclass = out.shape[-1]
        lab = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, nclass, dtype=out.dtype)
        if onehot.ndim < out.ndim:
            onehot = onehot.reshape(out.shape)
        spatial = 1
    if smooth_alpha:
        onehot = onehot * (1.0 - smooth_alpha) + smooth_alpha / nclass
    grad = out - onehot
    if use_ignore:
        if multi_output and out.ndim > 2:
            mask = (label != ignore_label).astype(out.dtype)
            mask = jnp.expand_dims(mask, 1)
        else:
            mask = (label != ignore_label).astype(out.dtype)
            mask = mask.reshape(mask.shape + (1,) * (grad.ndim - mask.ndim))
        grad = grad * mask
    # reference denominator (softmax_output-inl.h:174-201): valid_cnt is N for
    # 'batch', the (non-ignored) label count for 'valid', 1 for 'null'; the
    # multi-output path additionally divides by the spatial size except under
    # 'valid' (whose count already includes it)
    if normalization == "batch":
        denom = float(label.shape[0]) * spatial
    elif normalization == "valid":
        label_count = 1
        for d in label.shape:
            label_count *= d
        if use_ignore:
            denom = jnp.maximum(jnp.sum(label != ignore_label),
                                1).astype(out.dtype)
        else:
            denom = float(label_count)
    else:  # 'null'
        denom = float(spatial)
    grad = grad * (grad_scale / denom)
    if out_grad:  # honor the incoming cotangent (reference out_grad=True;
        # the AMP loss-scale seed enters here — docs/amp.md)
        grad = grad * g.astype(grad.dtype)
    return (grad.astype(out.dtype), jnp.zeros_like(label))


_softmax_output_vjp.defvjp(_so_fwd, _so_bwd)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, lab[:, None], axis=-1)
    return -jnp.sum(picked)


# ---------------------------------------------------------------------------
# Activation / LeakyReLU / Dropout
# ---------------------------------------------------------------------------

@register("Activation")
def activation(data, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError(f"unknown act_type {act_type}")


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * (jnp.exp(data) - 1))
    if act_type == "selu":
        a, l = 1.6732632423543772, 1.0507009873554805
        return l * jnp.where(data >= 0, data, a * (jnp.exp(data) - 1))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, mid * data)
    if act_type == "gelu":
        return jax.nn.gelu(data)
    raise ValueError(f"unknown act_type {act_type}")


@register("Dropout", rng=True)
def dropout(data, rng_key=None, p=0.5, mode="training", axes=(), _training=True):
    """Reference: src/operator/nn/dropout.cc. rng_key injected by the frontend
    from the global PRNG stream (mxnet_tpu.random)."""
    if not _training and mode != "always":
        return data
    if p <= 0.0:
        return data
    keep = 1.0 - p
    shape = list(data.shape)
    for ax in (axes or ()):
        shape[ax] = 1
    mask = jax.random.bernoulli(rng_key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ---------------------------------------------------------------------------
# Upsampling / resize
# ---------------------------------------------------------------------------

@register("UpSampling")
def upsampling(data, *weights, scale=2, sample_type="nearest", num_filter=0,
               multi_input_mode="concat", num_args=1, workspace=512):
    s = int(scale)
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * s, w * s), method="linear")


@register("_contrib_BilinearResize2D")
def bilinear_resize(data, height=1, width=1, scale_height=None, scale_width=None):
    n, c, h, w = data.shape
    if scale_height is not None:
        height, width = int(h * scale_height), int(w * scale_width)
    return jax.image.resize(data, (n, c, int(height), int(width)), method="linear")


# ---------------------------------------------------------------------------
# misc heads
# ---------------------------------------------------------------------------

@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0, out_grad=False):
    from ..symbol.graph import attr_bool

    return _regression_vjp(data, label, float(grad_scale), "linear",
                           attr_bool(out_grad))


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0, out_grad=False):
    from ..symbol.graph import attr_bool

    return _regression_vjp(data, label, float(grad_scale), "mae",
                           attr_bool(out_grad))


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0, out_grad=False):
    from ..symbol.graph import attr_bool

    return _regression_vjp(data, label, float(grad_scale), "logistic",
                           attr_bool(out_grad))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _regression_vjp(data, label, grad_scale, kind, out_grad=False):
    if kind == "logistic":
        return jax.nn.sigmoid(data)
    return data


def _reg_fwd(data, label, grad_scale, kind, out_grad=False):
    out = _regression_vjp(data, label, grad_scale, kind, out_grad)
    return out, (out, label)


def _reg_bwd(grad_scale, kind, out_grad, res, g):
    out, label = res
    lab = label.reshape(out.shape)
    if kind == "mae":
        grad = jnp.sign(out - lab)
    else:
        grad = out - lab
    grad = grad * grad_scale
    if out_grad:  # honor the cotangent (the AMP loss-scale entry point)
        grad = grad * g.astype(grad.dtype)
    return (grad, jnp.zeros_like(label))


_regression_vjp.defvjp(_reg_fwd, _reg_bwd)


@register("MakeLoss")
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return data
