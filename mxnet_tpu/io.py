"""Data iterators (reference: python/mxnet/io.py — DataIter base, NDArrayIter
:546, PrefetchingIter :349, ResizeIter; native iters in src/io/*).

The native-side pipeline (chunked RecordIO read → parallel decode → batch →
prefetch, src/io/iter_image_recordio_2.cc) maps to: recordio.py readers +
thread-pool decode + a background prefetch thread here.  Device transfer is
async via JAX, so the prefetcher overlaps host decode with TPU compute the way
the reference's PrefetcherIter overlaps with GPU kernels.
"""
from __future__ import annotations

import collections
import queue
import threading
from collections import namedtuple
from typing import List, Optional

import numpy as _np

from . import observability as _obs
from .base import MXNetError
from .ndarray import array as nd_array
from .ndarray.ndarray import NDArray

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
           "PrefetchingIter", "CSVIter", "MNISTIter", "ImageRecordIter", "ImageRecordIterNative",
           "LibSVMIter", "shard_data_batch", "fast_forward"]


def fast_forward(data_iter, num_batches: int) -> int:
    """Advance an iterator by ``num_batches`` without training on them —
    the mid-epoch resume path of ``Module.fit(resume=True)``
    (docs/fault_tolerance.md).

    Iterators exposing ``seek(batch_index)`` (``NDArrayIter`` and
    subclasses) jump without materializing the skipped batches; anything
    else is consumed batch by batch.  Returns the number of batches
    actually skipped (< ``num_batches`` when the epoch is shorter, e.g.
    after a dataset change between runs).
    """
    n = int(num_batches or 0)
    if n <= 0:
        return 0
    seek = getattr(data_iter, "seek", None)
    if callable(seek):
        try:
            seek(n)
            return n
        except Exception:
            pass  # fall through to plain consumption
    consumed = 0
    for _ in range(n):
        try:
            next(data_iter)
        except StopIteration:
            break
        consumed += 1
    return consumed


def shard_data_batch(batch: "DataBatch", mesh, axis: str = "dp",
                     strict: bool = False) -> "DataBatch":
    """Place a batch over the batch axis of an SPMD mesh for the fused
    train step.

    One ``jax.device_put`` with a ``NamedSharding`` on ``axis`` per array —
    the input pipeline never materializes per-device Python splits (the
    reference's ``_split_input_slice`` host slicing).  ``axis`` is any
    named axis of ``mesh`` (``"dp"`` for the training mesh; on a 2-D
    ``("dp","mp")`` mesh the batch shards on dp and replicates across mp).
    Arrays are re-placed IN PLACE on the batch's NDArrays so every
    downstream consumer (executor feed, device-side metrics comparing
    labels against sharded outputs) sees consistently-sharded values.

    Arrays whose leading dim doesn't divide by the axis size are left
    untouched by default (the Module caller pre-checks and falls back to
    the legacy path for those batches); ``strict=True`` raises a
    :class:`MXNetError` naming the batch size and the mesh axis size
    instead — ask for it at pipeline boundaries where an indivisible batch
    is a configuration bug, not a final partial batch (the old failure mode
    was an opaque XLA reshape error much later).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    axis_names = tuple(str(a) for a in mesh.axis_names)
    if axis not in axis_names:
        raise MXNetError(
            f"shard_data_batch: axis {axis!r} is not an axis of the mesh "
            f"(axes: {axis_names})")
    ndev = int(mesh.shape[axis])
    sharding = NamedSharding(mesh, PartitionSpec(axis))
    for arr in list(batch.data or []) + list(batch.label or []):
        if not (isinstance(arr, NDArray) and arr._data is not None
                and arr.shape):
            continue
        if arr.shape[0] % ndev:
            if strict:
                raise MXNetError(
                    f"shard_data_batch: batch size {arr.shape[0]} is not "
                    f"divisible by mesh axis {axis!r} of size {ndev}; pad "
                    f"the final batch or pick a batch size that is a "
                    f"multiple of {ndev}")
            continue
        arr._data = jax.device_put(arr._data, sharding)
    return batch


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    def __new__(cls, name, shape, dtype=_np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    def __repr__(self):
        return f"DataDesc[{self.name},{self.shape},{self.dtype},{self.layout}]"

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None and not isinstance(data, (list, tuple)):
            data = [data]
        if label is not None and not isinstance(label, (list, tuple)):
            label = [label]
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        data_shapes = [d.shape for d in self.data] if self.data else []
        label_shapes = [l.shape for l in self.label] if self.label else []
        return f"{self.__class__.__name__}: data shapes: {data_shapes} label shapes: {label_shapes}"


class DataIter:
    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        return None


class NDArrayIter(DataIter):
    """Iterate over ndarray/numpy data (reference: io.py:546).

    The arrays are referenced, not copied.  A batch whose rows are one
    ascending, contiguous run of the source (every whole batch of an
    unshuffled iterator) is handed to ``device_put`` as a view, with no
    host copy; any other batch (a shuffled order, the wrapped last batch
    of ``pad`` / ``roll_over``) is gathered into a fresh buffer.  Either
    way a batch that ``next()`` has returned is not changed by a later
    write to the source: the view path waits for the batch's transfer,
    because ``device_put`` keeps reading a host array after it returns.
    Rows written before their batch is taken do show in it
    (docs/perf_guide.md section 6)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data", label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.idx = _np.arange(self.data[0][1].shape[0])
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.num_data = self.idx.shape[0]
        if last_batch_handle == "discard":
            self.num_data = (self.num_data // batch_size) * batch_size
        self.cursor = -batch_size
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def reset(self):
        # roll_over: the final batch of the previous epoch wrapped around and
        # consumed some samples from the FRONT of the old order; remember
        # which ones BEFORE reshuffling, else the skip lands on different
        # samples and epochs stop being permutations of the dataset
        consumed = None
        if self.last_batch_handle == "roll_over" and \
                getattr(self, "_rolled", 0):
            consumed = self.idx[:self._rolled].copy()
        if self.shuffle:
            _np.random.shuffle(self.idx)
        start = 0
        if consumed is not None:
            mask = _np.isin(self.idx, consumed)
            self.idx = _np.concatenate([self.idx[mask], self.idx[~mask]])
            start = len(consumed)
        self._rolled = 0
        self.cursor = -self.batch_size + start

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def seek(self, batch_index: int) -> None:
        """Position the cursor so the NEXT batch served is ``batch_index``
        (0-based) of the current epoch order — checkpoint-resume
        fast-forward without materializing the skipped batches.  The
        shuffle order in effect is whatever the last ``reset()``
        produced."""
        if batch_index < 0:
            raise ValueError(f"seek: negative batch index {batch_index}")
        self.cursor = -self.batch_size + batch_index * self.batch_size

    def tell(self) -> int:
        """Batches already served this epoch (the value ``seek`` would
        need to reproduce the current position)."""
        return max(0, (self.cursor + self.batch_size) // self.batch_size)

    def _rows(self):
        """Source rows of the batch under the cursor: a ``slice`` where they
        are one ascending run ``a, a+1, ...`` of the source, so that
        ``v[rows]`` is a view; otherwise the index array NumPy gathers by.
        Read off the batch's own indices: a shuffled order, the wrapped
        last batch of ``pad`` / ``roll_over`` and anything else that
        permuted ``self.idx`` gather as they always did."""
        end = self.cursor + self.batch_size
        if end > self.num_data:
            pad = end - self.num_data
            if self.last_batch_handle == "roll_over":
                self._rolled = pad
            return _np.concatenate([self.idx[self.cursor:], self.idx[:pad]])
        sel = self.idx[max(self.cursor, 0):end]
        if len(sel) and (_np.diff(sel) == 1).all():
            first = int(sel[0])
            return slice(first, first + len(sel))
        return sel

    def _take(self, arrays, rows):
        out = [nd_array(v[rows]) for _, v in arrays]
        if isinstance(rows, slice):
            # a view is the caller's own memory, and device_put still reads
            # it after it has returned (on the CPU backend too): wait for
            # the transfers, so that no later write to the source reaches
            # the batch served.  The gather hands over a private temporary.
            for a in out:
                a.wait_to_read()
        return out

    def getdata(self):
        rows = self._rows()
        _obs.registry().counter(
            "io_ndarrayiter_batches_total",
            labels={"path": "view" if isinstance(rows, slice) else "gather"},
            help="NDArrayIter batches served as a view of the source (one "
                 "contiguous run of rows, no host copy) or by a gather"
        ).inc()
        return self._take(self.data, rows)

    def getlabel(self):
        return self._take(self.label, self._rows())

    def getpad(self):
        if self.last_batch_handle == "pad" and self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


def _init_data(data, allow_empty, default_name):
    if data is None:
        if not allow_empty:
            raise ValueError("data cannot be None")
        return []
    if isinstance(data, (_np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, (list, tuple)):
        data = collections.OrderedDict(
            [(default_name if len(data) == 1 else f"_{i}_{default_name}", d)
             for i, d in enumerate(data)])
    out = []
    for k, v in data.items():
        if isinstance(v, NDArray):
            v = v.asnumpy()
        out.append((k, _np.asarray(v)))
    return out


class ResizeIter(DataIter):
    """Resize another iterator's epoch length (reference: io.py ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Background-thread prefetch over one or more iterators
    (reference: io.py:349; native PrefetcherIter src/io/iter_prefetcher.h:47)."""

    def __init__(self, iters, rename_data=None, rename_label=None, prefetch_depth=2):
        iters = iters if isinstance(iters, list) else [iters]
        super().__init__(iters[0].batch_size)
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self._depth = prefetch_depth
        self._queue: "queue.Queue" = queue.Queue(maxsize=prefetch_depth)
        self._stop = threading.Event()
        self._thread = None
        self._gen = 0          # epoch generation: stale puts are discarded
        self._exhausted = False
        self.current_batch = None
        self._start()

    def _start(self):
        gen = self._gen
        q = self._queue
        stop = self._stop

        def worker():
            while not stop.is_set():
                try:
                    batches = [it.next() for it in self.iters]
                except StopIteration:
                    q.put((gen, None))
                    return
                q.put((gen, batches))

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        # stop the worker FOR REAL before touching the underlying iterators:
        # a short join would race it.reset() against an in-flight it.next()
        # and let a pre-reset batch leak into the new epoch
        self._stop.set()
        while self._thread is not None and self._thread.is_alive():
            try:
                while True:
                    self._queue.get_nowait()  # unblock a worker stuck in put()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
        for it in self.iters:
            it.reset()
        self._gen += 1
        self._exhausted = False
        self._stop = threading.Event()
        self._queue = queue.Queue(maxsize=self._depth)
        self._start()

    def iter_next(self):
        if self._exhausted:
            return False  # worker already exited; get() would hang forever
        while True:
            gen, batches = self._queue.get()
            if gen == self._gen:
                break  # discard stale entries from a pre-reset worker
        if batches is None:
            self._exhausted = True
            return False
        self.current_batch = batches[0] if len(batches) == 1 else DataBatch(
            sum([b.data for b in batches], []),
            sum([(b.label or []) for b in batches], []),
            batches[0].pad, batches[0].index)
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class CSVIter(NDArrayIter):
    """CSV reader (reference: src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        data = _np.loadtxt(data_csv, delimiter=",", dtype=_np.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        if label_csv is not None:
            label = _np.loadtxt(label_csv, delimiter=",", dtype=_np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
            if label.shape[1:] == (1,):
                label = label[:, 0]
        else:
            # reference iter_csv.cc: "If NULL, all labels will be returned
            # as 0" — a dummy zero label per instance
            label = _np.zeros((data.shape[0],), _np.float32)
        # reference BatchLoader semantics: round_batch=True carries the
        # wrap-around overflow into the next epoch (roll_over); False emits
        # the final partial batch with padding (pad), never discards
        super().__init__(data, label, batch_size=batch_size,
                         last_batch_handle="roll_over" if round_batch
                         else "pad",
                         label_name="label")


class MNISTIter(NDArrayIter):
    """MNIST reader (reference: src/io/iter_mnist.cc). Reads idx-format files;
    generates a deterministic synthetic set when files are absent (CI use)."""

    def __init__(self, image="train-images-idx3-ubyte", label="train-labels-idx1-ubyte",
                 batch_size=128, shuffle=True, flat=False, seed=0, silent=False,
                 num_parts=1, part_index=0, **kwargs):
        import gzip
        import os
        import struct

        def read_idx(path):
            opener = gzip.open if path.endswith(".gz") else open
            with opener(path, "rb") as f:
                magic = struct.unpack(">I", f.read(4))[0]
                ndim = magic & 0xFF
                shape = tuple(struct.unpack(">I", f.read(4))[0] for _ in range(ndim))
                return _np.frombuffer(f.read(), dtype=_np.uint8).reshape(shape)

        if image and _exists_any(image):
            imgs = read_idx(_first_existing(image)).astype(_np.float32) / 255.0
            labs = read_idx(_first_existing(label)).astype(_np.float32)
        else:
            rng = _np.random.RandomState(seed)
            n = 6000
            labs = rng.randint(0, 10, size=(n,)).astype(_np.float32)
            imgs = _np.zeros((n, 28, 28), dtype=_np.float32)
            # class-dependent pattern so models can actually learn
            for c in range(10):
                mask = labs == c
                base = rng.rand(28, 28) * 0.1
                base[c * 2:c * 2 + 6, c * 2:c * 2 + 6] += 0.9
                imgs[mask] = base + rng.rand(int(mask.sum()), 28, 28) * 0.1
        if num_parts > 1:
            imgs = imgs[part_index::num_parts]
            labs = labs[part_index::num_parts]
        data = imgs.reshape(-1, 784) if flat else imgs.reshape(-1, 1, 28, 28)
        # forward ONLY the naming kwargs so custom-named heads (e.g.
        # SVMOutput's svm_label) bind, while other reference-config kwargs
        # (prefetch_buffer etc.) stay ignored as before
        naming = {k: kwargs[k] for k in ("data_name", "label_name")
                  if k in kwargs}
        super().__init__(data, labs, batch_size=batch_size, shuffle=shuffle,
                         **naming)


def _exists_any(path):
    import os

    return os.path.exists(path) or os.path.exists(path + ".gz")


def _first_existing(path):
    import os

    return path if os.path.exists(path) else path + ".gz"


class ImageRecordIterNative(DataIter):
    """Native threaded decode+augment image pipeline.

    TPU-native replacement for the reference's ImageRecordIOParser2 OMP
    decode stage (src/io/iter_image_recordio_2.cc:138-171): C++ workers
    (cpp/src/imagedec.cc) decode JPEG/RAW0 off the GIL, resize/crop/mirror,
    and emit uint8 NHWC batches; the *device* does transpose + mean/std
    normalization inside one cached XLA program, so only 1 byte/pixel
    crosses the host link.
    """

    def __init__(self, path_imgrec, data_shape=(3, 224, 224), batch_size=128,
                 resize=-1, rand_crop=False, rand_mirror=False, shuffle=False,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0,
                 std_r=1.0, std_g=1.0, std_b=1.0, scale=1.0,
                 preprocess_threads=4, prefetch_buffer=4,
                 num_parts=1, part_index=0, label_width=1, seed=0,
                 data_name="data", label_name="softmax_label", **kwargs):
        super().__init__(batch_size)
        from . import _native

        c, h, w = data_shape
        if resize <= 0:
            resize = max(h, w)
        self._pipe = _native.ImagePipeline(
            path_imgrec, batch_size, data_shape=data_shape, resize=resize,
            num_threads=preprocess_threads, queue_depth=prefetch_buffer,
            shard_index=part_index, num_shards=num_parts,
            rand_crop=rand_crop, rand_mirror=rand_mirror, shuffle=shuffle,
            label_width=label_width, seed=seed)
        self._shape = data_shape
        self._label_width = label_width
        self.data_name, self.label_name = data_name, label_name
        self.provide_data = [DataDesc(data_name, (batch_size,) + tuple(data_shape))]
        lshape = (batch_size,) if label_width == 1 else (batch_size, label_width)
        self.provide_label = [DataDesc(label_name, lshape)]
        self._mean = _np.asarray([mean_r, mean_g, mean_b], _np.float32)
        self._std = _np.asarray([std_r, std_g, std_b], _np.float32)
        self._scale = float(scale)
        self._prep = None

    def _preprocess(self, img_u8):
        import jax
        import jax.numpy as jnp

        if self._prep is None:
            mean, std, scale = self._mean, self._std, self._scale

            @jax.jit
            def prep(u8):
                x = u8.astype(jnp.float32)
                x = (x - mean) / std
                if scale != 1.0:
                    x = x * scale
                return jnp.transpose(x, (0, 3, 1, 2))  # NHWC -> NCHW

            self._prep = prep
        return self._prep(img_u8)

    def next(self):
        from .ndarray.ndarray import NDArray

        img, lab, count = next(self._pipe)
        data = NDArray(self._preprocess(img))
        label = lab[:, 0] if self._label_width == 1 else lab
        # trailing batches arrive padded to batch_size (fixed shapes keep the
        # jitted step from recompiling); pad counts the repeated rows
        return DataBatch([data], [NDArray(_jnp_asarray(label))],
                         pad=self.batch_size - count)

    def reset(self):
        self._pipe.reset()

    def close(self):
        self._pipe.close()


def _jnp_asarray(x):
    import jax.numpy as jnp

    return jnp.asarray(x)


def ImageRecordIter(**kwargs):
    """RecordIO image pipeline (reference: src/io/iter_image_recordio_2.cc:727).

    Uses the native C++ decode pipeline when the native runtime is available
    (pass use_native=False to force the Python ImageIter path, e.g. for
    augmenter plugins the native stage doesn't implement)."""
    use_native = kwargs.pop("use_native", True)
    if use_native:
        from . import _native

        err = _native.build_error()
        if err:
            # the native pipeline was asked for (it is the default) and its
            # build failed: an error, not a quiet switch to the Python path
            # (use_native=False or MXTPU_NO_NATIVE=1 choose that path)
            raise MXNetError(err)
        native_ok = _native.lib() is not None and \
            kwargs.get("path_imgrec") and \
            tuple(kwargs.get("data_shape", (3, 224, 224)))[0] == 3
        if native_ok:
            try:
                return ImageRecordIterNative(**kwargs)
            except (_native.NativeUnsupportedError, TypeError) as e:
                # only configurations the native stage declares unsupported
                # (or kwargs it doesn't take) fall back; real IO errors raise
                import logging

                logging.getLogger("mxnet_tpu").warning(
                    "native image pipeline unavailable for this "
                    "configuration (%s); using the Python path", e)
    from .image import ImageRecordIterImpl

    return ImageRecordIterImpl(**kwargs)


class RecordIOIter:
    """Streaming iterator over raw RecordIO records with native background
    prefetch and round-robin sharding for data parallelism (reference:
    PrefetcherIter over chunked reads, src/io/iter_prefetcher.h:47,
    src/io/iter_image_recordio_2.cc:175-206; sharding per dmlc InputSplit).

    Uses the C++ prefetch pipeline (cpp/src/recordio.cc) when available and
    falls back to the pure-Python `MXRecordIO` reader otherwise. Yields
    `bytes` payloads; pair with `recordio.unpack`/`unpack_img` to decode.
    """

    def __init__(self, path, batch_records=64, queue_depth=4, part_index=0,
                 num_parts=1):
        from . import _native

        self._path = path
        self._native = _native.lib() is not None
        if self._native:
            self._reader = _native.RecordReader(
                path, batch_records=batch_records, queue_depth=queue_depth,
                shard_index=part_index, num_shards=num_parts)
        else:
            from .recordio import MXRecordIO

            self._reader = MXRecordIO(path, "r")
            self._part_index, self._num_parts = part_index, num_parts
            self._ordinal = 0

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        if self._native:
            return next(self._reader)
        while True:
            buf = self._reader.read()
            if buf is None:
                raise StopIteration
            mine = (self._ordinal % self._num_parts) == self._part_index
            self._ordinal += 1
            if mine:
                return buf

    def reset(self):
        self._reader.reset()
        if not self._native:
            self._ordinal = 0

    def close(self):
        close = getattr(self._reader, "close", None)
        if close:
            close()


class LibSVMIter(DataIter):
    """LibSVM sparse reader (reference: src/io/iter_libsvm.cc)."""

    def __init__(self, data_libsvm, data_shape, label_shape=(1,), batch_size=1,
                 data_name="data", label_name="label", **kwargs):
        super().__init__(batch_size)
        feats = []
        labels = []
        ncol = int(data_shape[0]) if isinstance(data_shape, (tuple, list)) else int(data_shape)
        with open(data_libsvm) as f:
            for line in f:
                parts = line.strip().split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                row = _np.zeros(ncol, dtype=_np.float32)
                for kv in parts[1:]:
                    k, v = kv.split(":")
                    row[int(k)] = float(v)
                feats.append(row)
        self._inner = NDArrayIter(_np.stack(feats), _np.asarray(labels),
                                  batch_size=batch_size, data_name=data_name,
                                  label_name=label_name)
        self.provide_data = self._inner.provide_data
        self.provide_label = self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        batch = self._inner.next()
        from .ndarray import sparse as _sp

        batch.data = [_sp.csr_matrix(d.asnumpy()) for d in batch.data]
        return batch
