"""Sparse NDArrays: row_sparse and csr.

Reference: ``include/mxnet/ndarray.h:61-66`` (kDefaultStorage/kRowSparseStorage/
kCSRStorage), ``src/operator/tensor/cast_storage*``, sparse dot
(``src/operator/tensor/dot.cc``).

TPU-native design (SURVEY.md §7 "hard parts"): XLA wants static shapes, so
sparse arrays are *fixed-capacity* — a row_sparse array holds (indices[K],
values[K, ...cols]) for a capacity K fixed at construction; csr holds
(data[NNZ], indices[NNZ], indptr[R+1]).  Kernels are masked dense ops
(gather/scatter/segment-sum), which XLA lowers well; storage fallback to dense
mirrors the reference's dispatch-mode fallback.

Capacity-overflow semantics (defined; the reference grows dynamically,
include/mxnet/ndarray.h:61-66 + CheckAndAllocData):
- EAGER ops GROW ON HOST: ``elemwise_add`` and the kvstore reduce produce a
  duplicate-merged ("compacted") result, so K stays bounded by the number of
  distinct nonzero rows no matter how many accumulations run — never by the
  number of adds.  Dense write-back re-sparsifies from the written value, so
  rows outside the old pattern are kept, not dropped.
- TRACED contexts (inside jit) keep the static capacity they were traced
  with; growth there is impossible by construction, and write-back falls
  back to the fixed-pattern update.
"""
from __future__ import annotations

import numpy as _np
import jax
import jax.numpy as jnp

from ..base import np_dtype
from ..context import Context, current_context
from .ndarray import NDArray, array as _dense_array

__all__ = ["RowSparseNDArray", "CSRNDArray", "row_sparse_array", "csr_matrix",
           "zeros", "cast_storage", "retain", "dot", "add", "elemwise_add"]


class BaseSparseNDArray(NDArray):
    pass


class RowSparseNDArray(BaseSparseNDArray):
    """Row-sparse: full-shape semantics, only rows in `indices` are non-zero."""

    __slots__ = ("indices_", "values_", "_shape_full")

    def __init__(self, values, indices, shape):
        self.values_ = values            # (K, *cols) jax array
        self.indices_ = indices          # (K,) int32, padded with -1 (invalid)
        self._shape_full = tuple(shape)
        super().__init__(None, stype="row_sparse")

    # dense materialization is lazy
    @property
    def _data(self):
        return self._to_dense_jax()

    @_data.setter
    def _data(self, v):
        if v is None:
            return
        if isinstance(v, jax.core.Tracer):
            # in-trace write-back: shapes are static — keep the traced
            # sparsity pattern (capacity cannot grow under jit)
            idx = jnp.clip(self.indices_, 0, self._shape_full[0] - 1)
            self.values_ = jnp.take(v, idx, axis=0)
            return
        # eager dense write-back: re-sparsify from the value itself so rows
        # outside the old pattern GROW the capacity instead of being dropped.
        # The nonzero-row reduce runs ON DEVICE; only the (rows,) bool mask
        # crosses to host (a full dense pull here would serialize every
        # backward-accumulation step on a device-to-host copy)
        flat = v.reshape(v.shape[0], -1)
        mask = _np.asarray(jnp.any(flat != 0, axis=1))
        nz = _np.where(mask)[0].astype(_np.int32)
        self.indices_ = jnp.asarray(nz)
        self.values_ = jnp.take(v, jnp.asarray(nz), axis=0)

    def compact(self):
        """Merge duplicate indices and drop invalid (-1) slots in place;
        after this, indices are sorted unique and K == distinct nonzero
        rows.  The growth bound for every eager accumulation path."""
        idx = _np.asarray(self.indices_)
        valid = _np.where(idx >= 0)[0]
        uniq, inv = _np.unique(idx[valid], return_inverse=True)
        out = jnp.zeros((len(uniq),) + tuple(self.values_.shape[1:]),
                        self.values_.dtype)
        out = out.at[jnp.asarray(inv)].add(
            jnp.take(self.values_, jnp.asarray(valid), axis=0))
        self.values_ = out
        self.indices_ = jnp.asarray(uniq.astype(_np.int32))
        return self

    def _to_dense_jax(self):
        out = jnp.zeros(self._shape_full, dtype=self.values_.dtype)
        valid = self.indices_ >= 0
        idx = jnp.where(valid, self.indices_, 0)
        vals = jnp.where(valid.reshape((-1,) + (1,) * (self.values_.ndim - 1)),
                         self.values_, 0)
        return out.at[idx].add(vals)

    @property
    def shape(self):
        return self._shape_full

    @property
    def dtype(self):
        return _np.dtype(self.values_.dtype)

    @property
    def indices(self):
        valid = _np.asarray(self.indices_) >= 0
        return _dense_array(_np.asarray(self.indices_)[valid].astype(_np.int64))

    @property
    def data(self):
        valid = _np.asarray(self.indices_) >= 0
        return _dense_array(_np.asarray(self.values_)[valid])

    def asnumpy(self):
        return _np.asarray(self._to_dense_jax())

    def tostype(self, stype):
        return cast_storage(self, stype)

    def copyto(self, other):
        if isinstance(other, RowSparseNDArray):
            other.values_, other.indices_ = self.values_, self.indices_
            other._shape_full = self._shape_full
            return other
        return super().copyto(other)

    def wait_to_read(self):
        self.values_.block_until_ready()
        return self

    def __repr__(self):
        return (f"\n<RowSparseNDArray {'x'.join(map(str, self.shape))} "
                f"nnz-rows={int((_np.asarray(self.indices_) >= 0).sum())}>")


class CSRNDArray(BaseSparseNDArray):
    __slots__ = ("data_", "indices_", "indptr_", "_shape_full")

    def __init__(self, data, indices, indptr, shape):
        self.data_ = data
        self.indices_ = indices
        self.indptr_ = indptr
        self._shape_full = tuple(shape)
        super().__init__(None, stype="csr")

    @property
    def _data(self):
        return self._to_dense_jax()

    @_data.setter
    def _data(self, v):
        pass

    def _to_dense_jax(self):
        R, C = self._shape_full
        nnz = self.data_.shape[0]
        row_of = jnp.searchsorted(self.indptr_, jnp.arange(nnz), side="right") - 1
        out = jnp.zeros((R, C), dtype=self.data_.dtype)
        return out.at[row_of, self.indices_.astype(jnp.int32)].add(self.data_)

    @property
    def shape(self):
        return self._shape_full

    @property
    def dtype(self):
        return _np.dtype(self.data_.dtype)

    @property
    def data(self):
        return _dense_array(_np.asarray(self.data_))

    @property
    def indices(self):
        return _dense_array(_np.asarray(self.indices_).astype(_np.int64))

    @property
    def indptr(self):
        return _dense_array(_np.asarray(self.indptr_).astype(_np.int64))

    def asnumpy(self):
        return _np.asarray(self._to_dense_jax())

    def tostype(self, stype):
        return cast_storage(self, stype)

    def wait_to_read(self):
        self.data_.block_until_ready()
        return self

    def __getitem__(self, key):
        if isinstance(key, slice):
            d = self._to_dense_jax()[key]
            return _from_dense_csr(d)
        return NDArray(self._to_dense_jax())[key]

    def __repr__(self):
        return (f"\n<CSRNDArray {'x'.join(map(str, self.shape))} "
                f"nnz={self.data_.shape[0]}>")


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def row_sparse_array(arg1, shape=None, ctx=None, dtype=None):
    if isinstance(arg1, (tuple, list)) and len(arg1) == 2 and not _np.isscalar(arg1[0]):
        values, indices = arg1
        values = _np.asarray(values, dtype=np_dtype(dtype) if dtype else _np.float32)
        indices = _np.asarray(indices, dtype=_np.int32)
        if shape is None:
            nrows = int(indices.max()) + 1 if indices.size else 0
            shape = (nrows,) + values.shape[1:]
        return RowSparseNDArray(jnp.asarray(values), jnp.asarray(indices), shape)
    dense = _np.asarray(arg1, dtype=np_dtype(dtype) if dtype else None)
    return _from_dense_rsp(jnp.asarray(dense))


def csr_matrix(arg1, shape=None, ctx=None, dtype=None):
    if isinstance(arg1, (tuple, list)) and len(arg1) == 3:
        data, indices, indptr = arg1
        data = jnp.asarray(_np.asarray(data, dtype=np_dtype(dtype) if dtype else _np.float32))
        indices = jnp.asarray(_np.asarray(indices, dtype=_np.int32))
        indptr = jnp.asarray(_np.asarray(indptr, dtype=_np.int32))
        if shape is None:
            shape = (len(indptr) - 1, int(indices.max()) + 1 if indices.size else 0)
        return CSRNDArray(data, indices, indptr, shape)
    if hasattr(arg1, "tocsr"):  # scipy matrix
        m = arg1.tocsr()
        return CSRNDArray(jnp.asarray(m.data.astype(_np.float32)),
                          jnp.asarray(m.indices.astype(_np.int32)),
                          jnp.asarray(m.indptr.astype(_np.int32)), m.shape)
    dense = jnp.asarray(_np.asarray(arg1, dtype=np_dtype(dtype) if dtype else _np.float32))
    return _from_dense_csr(dense)


def _from_dense_rsp(dense):
    dn = _np.asarray(dense)
    nz = _np.where(_np.any(dn.reshape(dn.shape[0], -1) != 0, axis=1))[0]
    if nz.size == 0:
        nz = _np.zeros((0,), dtype=_np.int32)
    return RowSparseNDArray(jnp.asarray(dn[nz]), jnp.asarray(nz.astype(_np.int32)),
                            dn.shape)


def _from_dense_csr(dense):
    dn = _np.asarray(dense)
    rows, cols = _np.nonzero(dn)
    data = dn[rows, cols]
    indptr = _np.zeros(dn.shape[0] + 1, dtype=_np.int32)
    _np.add.at(indptr, rows + 1, 1)
    indptr = _np.cumsum(indptr).astype(_np.int32)
    return CSRNDArray(jnp.asarray(data), jnp.asarray(cols.astype(_np.int32)),
                      jnp.asarray(indptr), dn.shape)


def zeros(stype, shape, ctx=None, dtype="float32"):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    if stype == "row_sparse":
        cols = shape[1:]
        return RowSparseNDArray(
            jnp.zeros((0,) + cols, dtype=np_dtype(dtype)),
            jnp.zeros((0,), dtype=jnp.int32), shape)
    if stype == "csr":
        return CSRNDArray(jnp.zeros((0,), dtype=np_dtype(dtype)),
                          jnp.zeros((0,), dtype=jnp.int32),
                          jnp.zeros((shape[0] + 1,), dtype=jnp.int32), shape)
    if stype == "default":
        from . import zeros as dzeros

        return dzeros(shape, ctx=ctx, dtype=dtype)
    raise ValueError(f"unknown stype {stype}")


# ---------------------------------------------------------------------------
# storage casts + sparse kernels
# ---------------------------------------------------------------------------

def cast_storage(arr, stype):
    """Reference: src/operator/tensor/cast_storage.cc."""
    if stype == arr.stype:
        return arr
    if stype == "default":
        return NDArray(arr._to_dense_jax() if isinstance(arr, BaseSparseNDArray)
                       else arr._data)
    dense = arr._data if not isinstance(arr, BaseSparseNDArray) else arr._to_dense_jax()
    if stype == "row_sparse":
        return _from_dense_rsp(dense)
    if stype == "csr":
        return _from_dense_csr(dense)
    raise ValueError(f"unknown stype {stype}")


def retain(arr, indices):
    """Keep only given rows of a row_sparse array (reference: _retain op)."""
    assert isinstance(arr, RowSparseNDArray)
    want = jnp.asarray(_np.asarray(indices.asnumpy() if isinstance(indices, NDArray)
                                   else indices, dtype=_np.int32))
    dense_rows = jnp.take(arr._to_dense_jax(), want, axis=0)
    return RowSparseNDArray(dense_rows, want, arr.shape)


def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """Sparse-aware dot: csr×dense, csr^T×dense (→ used by linear models), and
    dense fallbacks (reference: src/operator/tensor/dot.cc sparse paths)."""
    if isinstance(lhs, CSRNDArray):
        d = lhs._to_dense_jax()
        if transpose_a:
            d = d.T
        out = jnp.dot(d, rhs._data)
        return NDArray(out)
    if isinstance(lhs, NDArray) and isinstance(rhs, BaseSparseNDArray):
        return NDArray(jnp.dot(lhs._data, rhs._to_dense_jax()))
    from . import dot as dense_dot

    return dense_dot(lhs, rhs, transpose_a=transpose_a, transpose_b=transpose_b)


def elemwise_add(lhs, rhs):
    if isinstance(lhs, RowSparseNDArray) and isinstance(rhs, RowSparseNDArray):
        idx = jnp.concatenate([lhs.indices_, rhs.indices_])
        vals = jnp.concatenate([lhs.values_, rhs.values_])
        out = RowSparseNDArray(vals, idx, lhs.shape)
        if isinstance(idx, jax.core.Tracer):
            return out  # traced: static concat capacity (see module docs)
        return out.compact()  # eager: K bounded by distinct rows, not #adds
    a = lhs._to_dense_jax() if isinstance(lhs, BaseSparseNDArray) else lhs._data
    b = rhs._to_dense_jax() if isinstance(rhs, BaseSparseNDArray) else rhs._data
    return NDArray(a + b)


add = elemwise_add


def sparse_retain(arr, indices):
    return retain(arr, indices)
