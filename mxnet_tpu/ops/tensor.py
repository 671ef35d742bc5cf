"""Tensor op families: elementwise, broadcast, reduce, matrix, index, init.

Covers the reference's ``src/operator/tensor/*`` families (SURVEY.md §2.1,
~29k LoC of CUDA/C++) as jnp/lax emitters.  Naming follows the reference's
public op names (``python/mxnet/ndarray/register.py`` autogen surface) so that
user code written against mx.nd/mx.sym carries over.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .registry import OP_REGISTRY, register

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _axis_arg(axis):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(int(a) for a in axis)
    return int(axis)


# ---------------------------------------------------------------------------
# elementwise unary (reference: src/operator/tensor/elemwise_unary_op_basic.cc)
# ---------------------------------------------------------------------------

_UNARY = {
    "negative": jnp.negative,
    "abs": jnp.abs,
    "sign": jnp.sign,
    "rint": jnp.rint,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "trunc": jnp.trunc,
    "fix": jnp.trunc,
    "square": jnp.square,
    "sqrt": jnp.sqrt,
    "rsqrt": lambda x: lax.rsqrt(x),
    "cbrt": jnp.cbrt,
    "rcbrt": lambda x: 1.0 / jnp.cbrt(x),
    "exp": jnp.exp,
    "log": jnp.log,
    "log10": jnp.log10,
    "log2": jnp.log2,
    "log1p": jnp.log1p,
    "expm1": jnp.expm1,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "arctan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh,
    "arccosh": jnp.arccosh,
    "arctanh": jnp.arctanh,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
    "reciprocal": jnp.reciprocal,
    "gamma": lambda x: jnp.exp(jax.scipy.special.gammaln(x)),
    "gammaln": lambda x: jax.scipy.special.gammaln(x),
    "erf": jax.scipy.special.erf,
    "erfinv": jax.scipy.special.erfinv,
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "softsign": jax.nn.soft_sign,
    "logical_not": lambda x: (x == 0).astype(x.dtype),
}

for _name, _f in _UNARY.items():
    register(_name, differentiable=_name not in ("logical_not",))(
        (lambda f: lambda x: f(x))(_f)
    )


@register("identity", aliases=("_copy", "stop_gradient_identity"))
def identity(x):
    return x


@register("BlockGrad", aliases=("stop_gradient",))
def block_grad(x):
    return lax.stop_gradient(x)


@register("cast", aliases=("Cast",))
def cast(x, dtype="float32"):
    from ..base import np_dtype

    return x.astype(np_dtype(dtype))


@register("amp_cast")
def amp_cast(x, dtype="float32"):
    """AMP-inserted cast (amp.convert_symbol).  Same math as ``cast`` but a
    distinct op name so ``amp.remove_amp_cast`` can strip exactly the casts
    the policy added, never a user's own Cast nodes."""
    from ..base import np_dtype

    return x.astype(np_dtype(dtype))


@register("clip")
def clip(x, a_min=None, a_max=None):
    return jnp.clip(x, a_min, a_max)


# ---------------------------------------------------------------------------
# elementwise binary + broadcast (elemwise_binary_op*.cc, broadcast ops)
# ---------------------------------------------------------------------------

_BINARY = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "mod": jnp.mod,
    "power": jnp.power,
    "maximum": jnp.maximum,
    "minimum": jnp.minimum,
    "hypot": jnp.hypot,
    "equal": lambda a, b: (a == b),
    "not_equal": lambda a, b: (a != b),
    "greater": lambda a, b: (a > b),
    "greater_equal": lambda a, b: (a >= b),
    "lesser": lambda a, b: (a < b),
    "lesser_equal": lambda a, b: (a <= b),
    "logical_and": lambda a, b: ((a != 0) & (b != 0)),
    "logical_or": lambda a, b: ((a != 0) | (b != 0)),
    "logical_xor": lambda a, b: ((a != 0) ^ (b != 0)),
}

_CMP = {"equal", "not_equal", "greater", "greater_equal", "lesser", "lesser_equal",
        "logical_and", "logical_or", "logical_xor"}


def _binary_impl(f, cmp):
    def impl(a, b):
        r = f(a, b)
        if cmp:
            r = r.astype(a.dtype)  # reference keeps the input dtype
        return r

    return impl


for _name, _f in _BINARY.items():
    impl = _binary_impl(_f, _name in _CMP)
    # elemwise_* requires same shape in the reference; broadcast_* broadcasts.
    # XLA broadcasts natively so one emitter serves both names.
    register("elemwise_" + _name, differentiable=_name not in _CMP,
             aliases=("broadcast_" + _name, "_" + _name))(impl)

# scalar variants (reference: *_scalar ops)
for _name, _f in _BINARY.items():
    impl = (lambda f, cmp: lambda x, scalar=0.0: _binary_impl(f, cmp)(x, jnp.asarray(scalar, dtype=x.dtype)))(_f, _name in _CMP)
    register("_" + _name + "_scalar", differentiable=_name not in _CMP)(impl)


@register("_rsub_scalar")
def _rsub_scalar(x, scalar=0.0):
    return jnp.asarray(scalar, dtype=x.dtype) - x


@register("_rdiv_scalar")
def _rdiv_scalar(x, scalar=0.0):
    return jnp.asarray(scalar, dtype=x.dtype) / x


@register("_rpower_scalar")
def _rpower_scalar(x, scalar=0.0):
    return jnp.power(jnp.asarray(scalar, dtype=x.dtype), x)


@register("_rmod_scalar")
def _rmod_scalar(x, scalar=0.0):
    return jnp.mod(jnp.asarray(scalar, dtype=x.dtype), x)


@register("add_n", aliases=("ElementWiseSum", "_grad_add_n"))
def add_n(*args):
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


@register("where")
def where(cond, x, y):
    return jnp.where(cond != 0, x, y)


# ---------------------------------------------------------------------------
# reductions (broadcast_reduce_op*.cc)
# ---------------------------------------------------------------------------

def _reduce(fn):
    def impl(x, axis=None, keepdims=False, exclude=False):
        ax = _axis_arg(axis)
        if exclude and ax is not None:
            if isinstance(ax, int):
                ax = (ax,)
            ax = tuple(i for i in range(x.ndim) if i not in ax)
        return fn(x, axis=ax, keepdims=bool(keepdims))

    return impl


register("sum", aliases=("sum_axis",))(_reduce(jnp.sum))
register("mean")(_reduce(jnp.mean))
register("prod")(_reduce(jnp.prod))
register("max", aliases=("max_axis",))(_reduce(jnp.max))
register("min", aliases=("min_axis",))(_reduce(jnp.min))
register("nansum")(_reduce(jnp.nansum))
register("nanprod")(_reduce(jnp.nanprod))


@register("norm")
def norm(x, ord=2, axis=None, keepdims=False):
    ax = _axis_arg(axis)
    if ord == 1:
        return jnp.sum(jnp.abs(x), axis=ax, keepdims=bool(keepdims))
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=bool(keepdims)))


@register("argmax", differentiable=False)
def argmax(x, axis=None, keepdims=False):
    ax = _axis_arg(axis)
    r = jnp.argmax(x, axis=ax)
    if keepdims and ax is not None:
        r = jnp.expand_dims(r, ax)
    return r.astype(jnp.float32)


@register("argmin", differentiable=False)
def argmin(x, axis=None, keepdims=False):
    ax = _axis_arg(axis)
    r = jnp.argmin(x, axis=ax)
    if keepdims and ax is not None:
        r = jnp.expand_dims(r, ax)
    return r.astype(jnp.float32)


@register("argsort", differentiable=False)
def argsort(x, axis=-1, is_ascend=True):
    r = jnp.argsort(x, axis=_axis_arg(axis))
    if not is_ascend:
        r = jnp.flip(r, axis=_axis_arg(axis) if axis is not None else 0)
    return r.astype(jnp.float32)


@register("sort")
def sort(x, axis=-1, is_ascend=True):
    r = jnp.sort(x, axis=_axis_arg(axis))
    if not is_ascend:
        r = jnp.flip(r, axis=_axis_arg(axis) if axis is not None else 0)
    return r


@register("topk", differentiable=False, num_outputs=lambda attrs: 2 if attrs.get("ret_typ") == "both" else 1)
def topk(x, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    """Reference: src/operator/tensor/ordered_op. lax.top_k rides the TPU sort unit."""
    if axis is None:
        xm = jnp.reshape(x, (-1,))  # reference: flattened array when no axis
        ax = 0
    else:
        ax = int(axis)
        xm = jnp.moveaxis(x, ax, -1)
    vals, idx = lax.top_k(-xm if is_ascend else xm, k)
    if is_ascend:
        vals = -vals
    if ret_typ == "mask":
        # 0/1 mask of the input shape marking the top-k entries
        mask = jnp.zeros(xm.shape, x.dtype)
        mask = jnp.put_along_axis(mask, idx, jnp.ones_like(
            vals, dtype=x.dtype), axis=-1, inplace=False)
        if axis is None:
            return jnp.reshape(mask, x.shape)
        return jnp.moveaxis(mask, -1, ax)
    if axis is not None:
        vals = jnp.moveaxis(vals, -1, ax)
        idx = jnp.moveaxis(idx, -1, ax).astype(jnp.float32)
    else:
        idx = idx.astype(jnp.float32)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx
    return idx


# ---------------------------------------------------------------------------
# matrix ops (matrix_op.cc: reshape/transpose/slice/…; dot.cc)
# ---------------------------------------------------------------------------

@register("reshape", aliases=("Reshape",))
def reshape(x, shape=None, reverse=False):
    """Supports the reference's special codes 0 (keep), -1 (infer), -2 (copy rest),
    -3 (merge two), -4 (split) — src/operator/tensor/matrix_op.cc docstring."""
    shape = tuple(int(s) for s in shape)
    if not any(s in (0, -2, -3, -4) for s in shape):
        return jnp.reshape(x, shape)
    src = list(x.shape)[::-1] if reverse else list(x.shape)
    if reverse:
        # the reference reverses BOTH the source shape and the target spec,
        # computes left-to-right, then reverses the result (matrix_op.cc:166).
        # -4 split groups travel as (-4, a, b): re-order each reversed
        # (b, a, -4) window and swap its pair so splits stay adjacent.
        rev = list(reversed(shape))
        fixed = []
        j = 0
        while j < len(rev):
            if j + 2 < len(rev) and rev[j + 2] == -4:
                fixed.extend([-4, rev[j + 1], rev[j]])
                j += 3
            else:
                fixed.append(rev[j])
                j += 1
        shape = tuple(fixed)
    out = []
    i = 0
    it = iter(range(len(shape)))
    src_i = 0
    shape = list(shape)
    j = 0
    while j < len(shape):
        s = shape[j]
        if s == 0:
            out.append(src[src_i]); src_i += 1
        elif s == -1:
            out.append(-1); src_i += 1
        elif s == -2:
            out.extend(src[src_i:]); src_i = len(src)
        elif s == -3:
            out.append(src[src_i] * src[src_i + 1]); src_i += 2
        elif s == -4:
            a, b = shape[j + 1], shape[j + 2]
            if a == -1:
                a = src[src_i] // b
            if b == -1:
                b = src[src_i] // a
            out.extend([a, b]); src_i += 1; j += 2
        else:
            out.append(s); src_i += 1
        j += 1
    if reverse:
        out = out[::-1]
    return jnp.reshape(x, tuple(out))


@register("reshape_like")
def reshape_like(x, y):
    return jnp.reshape(x, y.shape)


@register("flatten", aliases=("Flatten",))
def flatten(x):
    return jnp.reshape(x, (x.shape[0], -1))


@register("histogram", num_outputs=2, differentiable=False)
def histogram(data, bin_cnt=10, range=None):
    """Reference: src/operator/tensor/histogram.cc. Returns (counts, edges)."""
    lo, hi = (float(range[0]), float(range[1])) if range is not None else \
        (None, None)
    if lo is None:
        lo_v, hi_v = jnp.min(data), jnp.max(data)
    else:
        lo_v, hi_v = jnp.asarray(lo, jnp.float32), jnp.asarray(hi, jnp.float32)
    counts, edges = jnp.histogram(
        data, bins=int(bin_cnt),
        range=(lo_v, hi_v))
    return counts.astype(jnp.int64), edges.astype(jnp.float32)


@register("ravel_multi_index", differentiable=False, aliases=("_ravel_multi_index",))
def ravel_multi_index(data, shape=None):
    """(ndim, N) indices → flat ids (reference: src/operator/tensor/ravel.cc)."""
    dims = tuple(int(d) for d in shape)
    strides = []
    s = 1
    for d in reversed(dims):
        strides.append(s)
        s *= d
    strides = jnp.asarray(list(reversed(strides)), data.dtype)
    return jnp.sum(data * strides[:, None], axis=0)


@register("unravel_index", differentiable=False, aliases=("_unravel_index",))
def unravel_index(data, shape=None):
    """flat ids → (ndim, N) indices (reference: ravel.cc UnravelIndex)."""
    dims = tuple(int(d) for d in shape)
    out = []
    rem = data.astype(jnp.int64)
    for d in reversed(dims):
        out.append(rem % d)
        rem = rem // d
    return jnp.stack(list(reversed(out)), axis=0).astype(data.dtype)


@register("swapaxes", aliases=("SwapAxis",))
def swapaxes(x, dim1=0, dim2=1):
    """Reference: src/operator/swapaxis.cc `SwapAxis`."""
    return jnp.swapaxes(x, int(dim1), int(dim2))


@register("transpose")
def transpose(x, axes=None):
    if axes is None or len(axes) == 0:
        return jnp.transpose(x)
    return jnp.transpose(x, tuple(int(a) for a in axes))


@register("expand_dims")
def expand_dims(x, axis=0):
    return jnp.expand_dims(x, int(axis))


@register("squeeze")
def squeeze(x, axis=None):
    return jnp.squeeze(x, _axis_arg(axis))


@register("slice", aliases=("crop",))
def slice_op(x, begin=None, end=None, step=None):
    slices = []
    begin = begin or ()
    end = end or ()
    step = step or ()
    for i in range(len(begin)):
        b = begin[i]
        e = end[i] if i < len(end) else None
        s = step[i] if i < len(step) and step[i] is not None and step[i] != 0 else 1
        slices.append(slice(b, e, s))
    return x[tuple(slices)]


@register("slice_axis")
def slice_axis(x, axis=0, begin=0, end=None):
    sl = [slice(None)] * x.ndim
    sl[int(axis)] = slice(begin, end)
    return x[tuple(sl)]


@register("slice_like")
def slice_like(x, like, axes=()):
    axes = tuple(axes) if axes else tuple(range(min(x.ndim, like.ndim)))
    sl = [slice(None)] * x.ndim
    for a in axes:
        sl[a] = slice(0, like.shape[a])
    return x[tuple(sl)]


@register("concat", aliases=("Concat",))
def concat(*args, dim=1):
    return jnp.concatenate(args, axis=int(dim))


@register("stack")
def stack(*args, axis=0):
    return jnp.stack(args, axis=int(axis))


@register("split", aliases=("SliceChannel",),
          num_outputs=lambda attrs: int(attrs.get("num_outputs", 1)))
def split(x, num_outputs=1, axis=1, squeeze_axis=False):
    parts = jnp.split(x, int(num_outputs), axis=int(axis))
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=int(axis)) for p in parts]
    return tuple(parts)


@register("tile")
def tile(x, reps=()):
    return jnp.tile(x, tuple(int(r) for r in reps))


@register("repeat")
def repeat(x, repeats=1, axis=None):
    return jnp.repeat(x, int(repeats), axis=_axis_arg(axis))


@register("pad", aliases=("Pad",))
def pad(x, mode="constant", pad_width=(), constant_value=0.0):
    pw = list(pad_width)
    pairs = [(int(pw[i]), int(pw[i + 1])) for i in range(0, len(pw), 2)]
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    if jmode == "constant":
        return jnp.pad(x, pairs, mode="constant", constant_values=constant_value)
    return jnp.pad(x, pairs, mode=jmode)


@register("flip", aliases=("reverse",))
def flip(x, axis=0):
    return jnp.flip(x, _axis_arg(axis))


@register("roll")
def roll(x, shift=0, axis=None):
    return jnp.roll(x, shift, axis=_axis_arg(axis))


@register("broadcast_to")
def broadcast_to(x, shape=()):
    target = tuple(int(s) if int(s) != 0 else x.shape[i] for i, s in enumerate(shape))
    return jnp.broadcast_to(x, target)


@register("broadcast_like")
def broadcast_like(x, like):
    return jnp.broadcast_to(x, like.shape)


@register("broadcast_axis", aliases=("broadcast_axes",))
def broadcast_axis(x, axis=(), size=()):
    axis = (axis,) if isinstance(axis, int) else tuple(axis)
    size = (size,) if isinstance(size, int) else tuple(size)
    target = list(x.shape)
    for a, s in zip(axis, size):
        target[a] = s
    return jnp.broadcast_to(x, tuple(target))


@register("dot")
def dot(a, b, transpose_a=False, transpose_b=False):
    """Reference: src/operator/tensor/dot.cc. Maps straight onto the MXU via
    lax.dot_general; accumulate in f32 when inputs are bf16."""
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2) if b.ndim > 1 else b
    return jnp.dot(a, b, preferred_element_type=_acc_type(a))


def _acc_type(a):
    if a.dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return None


@register("batch_dot")
def batch_dot(a, b, transpose_a=False, transpose_b=False):
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b, preferred_element_type=_acc_type(a))


@register("linalg_gemm2")
def linalg_gemm2(a, b, transpose_a=False, transpose_b=False, alpha=1.0):
    r = batch_dot(a, b, transpose_a, transpose_b)
    return r if alpha == 1.0 else alpha * r


@register("diag")
def diag(x, k=0):
    if x.ndim == 1:
        return jnp.diag(x, k=int(k))
    return jnp.diagonal(x, offset=int(k), axis1=-2, axis2=-1)


@register("L2Normalization")
def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        n = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1) + eps)
        return x / n.reshape((-1,) + (1,) * (x.ndim - 1))
    if mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
        return x / n
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(2, x.ndim)), keepdims=True) + eps)
    return x / n


# ---------------------------------------------------------------------------
# indexing (indexing_op.cc: take/gather/scatter/embedding/one_hot)
# ---------------------------------------------------------------------------

@register("take")
def take(a, indices, axis=0, mode="clip"):
    idx = indices.astype(jnp.int32)
    jmode = "clip" if mode in ("clip", "raise") else "wrap"
    return jnp.take(a, idx, axis=int(axis), mode=jmode)


@register("pick")
def pick(x, index, axis=-1, keepdims=False, mode="clip"):
    n = x.shape[int(axis)]
    idx = index.astype(jnp.int32)
    idx = idx % n if mode == "wrap" else jnp.clip(idx, 0, n - 1)
    r = jnp.take_along_axis(x, jnp.expand_dims(idx, int(axis)), axis=int(axis))
    if not keepdims:
        r = jnp.squeeze(r, int(axis))
    return r


@register("gather_nd")
def gather_nd(data, indices):
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


@register("scatter_nd")
def scatter_nd(data, indices, shape=()):
    out = jnp.zeros(tuple(int(s) for s in shape), dtype=data.dtype)
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return out.at[idx].add(data)


@register("one_hot", differentiable=False)
def one_hot(indices, depth=1, on_value=1.0, off_value=0.0, dtype="float32"):
    from ..base import np_dtype

    oh = jax.nn.one_hot(indices.astype(jnp.int32), int(depth))
    return (oh * (on_value - off_value) + off_value).astype(np_dtype(dtype))


@register("Embedding")
def embedding(data, weight, input_dim=None, output_dim=None, dtype="float32",
              sparse_grad=False):
    """Reference: src/operator/tensor/indexing_op.cc Embedding. On TPU this is a
    gather that XLA lowers efficiently; sparse_grad maps to the same dense
    gather (grads become scatter-adds under vjp)."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register("SequenceMask")
def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    T = data.shape[int(axis)]
    steps = jnp.arange(T)
    if int(axis) == 0:
        mask = steps[:, None] < sequence_length[None, :].astype(jnp.int32)
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = steps[None, :] < sequence_length[:, None].astype(jnp.int32)
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


@register("SequenceLast")
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, -1, axis=int(axis))
    idx = (sequence_length.astype(jnp.int32) - 1)
    if int(axis) == 0:
        return jnp.take_along_axis(
            data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0
        ).squeeze(0)
    return jnp.take_along_axis(
        data, idx.reshape((-1, 1) + (1,) * (data.ndim - 2)), axis=1
    ).squeeze(1)


@register("SequenceReverse")
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, int(axis))
    T = data.shape[0]
    steps = jnp.arange(T)[:, None]
    L = sequence_length.astype(jnp.int32)[None, :]
    rev_idx = jnp.where(steps < L, L - 1 - steps, steps)
    return jnp.take_along_axis(data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


# ---------------------------------------------------------------------------
# init ops (init_op.cc)
# ---------------------------------------------------------------------------

@register("zeros_like")
def zeros_like(x):
    return jnp.zeros_like(x)


@register("ones_like")
def ones_like(x):
    return jnp.ones_like(x)


# ---------------------------------------------------------------------------
# misc (histogram, ravel, linalg basics)
# ---------------------------------------------------------------------------

@register("linalg_potrf")
def linalg_potrf(a):
    return jnp.linalg.cholesky(a)


@register("linalg_trsm")
def linalg_trsm(a, b, transpose=False, rightside=False, lower=True, alpha=1.0):
    import jax.scipy.linalg as jsl

    if rightside:
        x = jsl.solve_triangular(a.swapaxes(-1, -2), b.swapaxes(-1, -2),
                                 lower=not lower, trans=1 if transpose else 0)
        x = x.swapaxes(-1, -2)
    else:
        x = jsl.solve_triangular(a, b, lower=lower, trans=1 if transpose else 0)
    return alpha * x


@register("linalg_syrk")
def linalg_syrk(a, transpose=False, alpha=1.0):
    at = a.swapaxes(-1, -2)
    r = jnp.matmul(at, a) if transpose else jnp.matmul(a, at)
    return alpha * r


@register("linalg_gemm")
def linalg_gemm(a, b, c, transpose_a=False, transpose_b=False, alpha=1.0,
                beta=1.0):
    """alpha*op(A)op(B) + beta*C (reference: la_op.cc:36 _linalg_gemm)."""
    r = batch_dot(a, b, transpose_a, transpose_b)
    return alpha * r + beta * c


@register("linalg_trmm")
def linalg_trmm(a, b, transpose=False, rightside=False, lower=True, alpha=1.0):
    """Triangular matrix multiply out = alpha*op(A)*B (or B*op(A) when
    rightside) with A triangular (reference: la_op.cc _linalg_trmm)."""
    tri = jnp.tril(a) if lower else jnp.triu(a)
    if transpose:
        tri = tri.swapaxes(-1, -2)
    r = jnp.matmul(b, tri) if rightside else jnp.matmul(tri, b)
    return alpha * r


@register("linalg_potri")
def linalg_potri(a, lower=True):
    """Inverse of the SPD matrix whose Cholesky factor is A: out = (A·Aᵀ)⁻¹
    for lower-triangular A (reference: la_op.cc:225 _linalg_potri)."""
    import jax.scipy.linalg as jsl

    eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
    # (A Aᵀ)⁻¹ = A⁻ᵀ A⁻¹ via two triangular solves
    inv_a = jsl.solve_triangular(a, eye, lower=lower)
    return jsl.solve_triangular(a, inv_a, lower=lower, trans=1)


@register("linalg_gelqf", num_outputs=2)
def linalg_gelqf(a):
    """LQ factorization A = L·Q with row-orthonormal Q; returns (Q, L)
    (reference: la_op.cc _linalg_gelqf).  Computed via QR of Aᵀ."""
    q, r = jnp.linalg.qr(a.swapaxes(-1, -2), mode="reduced")
    # sign-normalize so L's diagonal is positive (LAPACK convention parity)
    d = jnp.sign(jnp.diagonal(r, axis1=-2, axis2=-1))
    d = jnp.where(d == 0, 1.0, d).astype(a.dtype)
    q = q * d[..., None, :]
    r = r * d[..., :, None]
    return q.swapaxes(-1, -2), r.swapaxes(-1, -2)


@register("linalg_syevd", num_outputs=2)
def linalg_syevd(a):
    """Symmetric eigendecomposition; returns (U, L) with U·A = diag(L)·U,
    eigenvalues ascending (reference: la_op.cc _linalg_syevd)."""
    w, v = jnp.linalg.eigh(a)
    return v.swapaxes(-1, -2), w


@register("linalg_sumlogdiag")
def linalg_sumlogdiag(a):
    """Sum of log of the diagonal, per matrix (reference: la_op.cc
    _linalg_sumlogdiag)."""
    return jnp.sum(jnp.log(jnp.diagonal(a, axis1=-2, axis2=-1)), axis=-1)


@register("linalg_makediag")
def linalg_makediag(a, offset=0):
    k = int(offset)
    n = a.shape[-1] + abs(k)
    out = jnp.zeros(a.shape[:-1] + (n, n), a.dtype)
    idx = jnp.arange(a.shape[-1])
    rows = idx + max(-k, 0)
    cols = idx + max(k, 0)
    return out.at[..., rows, cols].set(a)


@register("linalg_extractdiag")
def linalg_extractdiag(a, offset=0):
    return jnp.diagonal(a, offset=int(offset), axis1=-2, axis2=-1)


@register("smooth_l1")
def smooth_l1(x, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * jnp.square(x),
                     jnp.abs(x) - 0.5 / s2)


# ---------------------------------------------------------------------------
# init / shape-reflection / layout ops (reference: init_op.cc, matrix_op.cc)
# ---------------------------------------------------------------------------

@register("_zeros", differentiable=False)
def _zeros_op(shape=(), dtype="float32", ctx=None):
    from ..base import np_dtype
    return jnp.zeros(tuple(int(s) for s in shape), np_dtype(dtype))


@register("_ones", differentiable=False)
def _ones_op(shape=(), dtype="float32", ctx=None):
    from ..base import np_dtype
    return jnp.ones(tuple(int(s) for s in shape), np_dtype(dtype))


@register("_full", differentiable=False)
def _full_op(shape=(), value=0.0, dtype="float32", ctx=None):
    from ..base import np_dtype
    return jnp.full(tuple(int(s) for s in shape), value, np_dtype(dtype))


@register("_eye", differentiable=False)
def _eye_op(N=0, M=0, k=0, dtype="float32", ctx=None):
    from ..base import np_dtype
    return jnp.eye(int(N), int(M) if M else None, int(k), dtype=np_dtype(dtype))


@register("_arange", differentiable=False)
def _arange_op(start=0.0, stop=None, step=1.0, repeat=1, dtype="float32",
               ctx=None, infer_range=False):
    from ..base import np_dtype
    r = jnp.arange(start, stop, step, dtype=np_dtype(dtype))
    if int(repeat) != 1:
        r = jnp.repeat(r, int(repeat))
    return r


@register("shape_array", differentiable=False)
def shape_array(data):
    # reference contract is int64; jax without x64 truncates, so request
    # int32 explicitly to avoid per-call truncation warnings
    return jnp.asarray(data.shape, dtype=jnp.int32)


@register("size_array", differentiable=False)
def size_array(data):
    return jnp.asarray([data.size], dtype=jnp.int32)


@register("round")
def round_op(x):
    return jnp.round(x)


@register("hard_sigmoid")
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


@register("depth_to_space")
def depth_to_space(data, block_size=1):
    b = int(block_size)
    N, C, H, W = data.shape
    x = data.reshape(N, b, b, C // (b * b), H, W)
    x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
    return x.reshape(N, C // (b * b), H * b, W * b)


@register("space_to_depth")
def space_to_depth(data, block_size=1):
    b = int(block_size)
    N, C, H, W = data.shape
    x = data.reshape(N, C, H // b, b, W // b, b)
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return x.reshape(N, C * b * b, H // b, W // b)


@register("batch_take")
def batch_take(a, indices):
    return jnp.take_along_axis(
        a, indices.astype(jnp.int32)[:, None], axis=1).squeeze(1)


@register("argmax_channel", differentiable=False)
def argmax_channel(data):
    return jnp.argmax(data, axis=1).astype(data.dtype)


@register("khatri_rao")
def khatri_rao(*args):
    """Column-wise Kronecker product (reference: la_op khatri_rao)."""
    out = args[0]
    for m in args[1:]:
        out = (out[:, None, :] * m[None, :, :]).reshape(-1, out.shape[1])
    return out


@register("make_loss")
def make_loss_op(data, grad_scale=1.0, valid_thresh=0.0,
                 normalization="null"):
    """Identity marking a loss head (reference: make_loss.cc); grad handled
    by the autograd head-gradient path."""
    return data


@register("_square_sum")
def square_sum(data, axis=None, keepdims=False):
    return jnp.sum(jnp.square(data), axis=_axis_arg(axis),
                   keepdims=bool(keepdims))


@register("_grad_add")
def grad_add(a, b):
    return a + b


@register("_identity_with_attr_like_rhs")
def identity_with_attr_like_rhs(lhs, rhs):
    return lhs


@register("IdentityAttachKLSparseReg")
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1, penalty=0.001,
                                  momentum=0.9):
    return data


@register("_slice_assign")
def slice_assign(lhs, rhs, begin=(), end=(), step=()):
    idx = _slice_index(lhs.shape, begin, end, step)
    return lhs.at[idx].set(rhs)


@register("_slice_assign_scalar")
def slice_assign_scalar(data, scalar=0.0, begin=(), end=(), step=()):
    idx = _slice_index(data.shape, begin, end, step)
    return data.at[idx].set(jnp.asarray(scalar, data.dtype))


def _slice_index(shape, begin, end, step):
    step = step if step else (1,) * len(begin)
    return tuple(
        slice(None if b is None else int(b), None if e is None else int(e),
              int(s) if s else 1)
        for b, e, s in zip(begin, end, step))


# reference scalar-op spelling aliases (_plus_scalar == _add_scalar etc.)
for _ref, _ours in (("_plus_scalar", "_add_scalar"),
                    ("_minus_scalar", "_sub_scalar"),
                    ("_rminus_scalar", "_rsub_scalar")):
    if _ref not in OP_REGISTRY and _ours in OP_REGISTRY:
        OP_REGISTRY[_ref] = OP_REGISTRY[_ours]
