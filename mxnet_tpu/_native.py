"""ctypes binding to the native runtime (cpp/ → libmxtpu.so).

The reference reaches its native core through a C API loaded from libmxnet.so
(python/mxnet/base.py _load_lib); same shape here, minus the codegen: the
native surface is small (engine, recordio, pool) because XLA owns the compute
path. If the library is missing it is built on demand with `make` (toolchain
is baked into the image); if that fails, callers fall back to pure Python —
`lib()` returns None and every consumer must handle it.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

__all__ = ["lib", "last_error", "NativeEngine", "RecordReader", "RecordWriter",
           "ImagePipeline", "rec_count", "pool_stats",
           "NativeUnsupportedError"]


class NativeUnsupportedError(ValueError):
    """A configuration the native pipeline intentionally does not support;
    callers may fall back to the Python path on exactly this error."""


_lock = threading.Lock()
_lib = None
_tried = False
_build_error = None

_CPP_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "cpp")
_LIB_PATH = os.path.join(_CPP_DIR, "build", "libmxtpu.so")

MXTPU_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)


def _declare(lib):
    u64 = ctypes.c_uint64
    p = ctypes.c_void_p
    lib.mxtpu_engine_create.argtypes = [ctypes.c_int, ctypes.POINTER(p)]
    lib.mxtpu_engine_destroy.argtypes = [p]
    lib.mxtpu_engine_new_var.argtypes = [p]
    lib.mxtpu_engine_new_var.restype = u64
    lib.mxtpu_engine_push.argtypes = [p, MXTPU_FN, p, ctypes.POINTER(u64),
                                      ctypes.c_int, ctypes.POINTER(u64),
                                      ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.mxtpu_engine_wait_var.argtypes = [p, u64, ctypes.POINTER(u64)]
    lib.mxtpu_engine_wait_all.argtypes = [p, ctypes.POINTER(u64)]
    lib.mxtpu_engine_delete_var.argtypes = [p, u64]
    lib.mxtpu_engine_num_pending.argtypes = [p]
    lib.mxtpu_rec_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                                   ctypes.c_int, ctypes.c_int, ctypes.POINTER(p)]
    lib.mxtpu_rec_close.argtypes = [p]
    lib.mxtpu_rec_next_batch.argtypes = [p, ctypes.POINTER(p),
                                         ctypes.POINTER(ctypes.c_int)]
    lib.mxtpu_rec_get.argtypes = [p, ctypes.c_int,
                                  ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                                  ctypes.POINTER(u64)]
    lib.mxtpu_rec_free_batch.argtypes = [p]
    lib.mxtpu_rec_reset.argtypes = [p]
    lib.mxtpu_rec_count.argtypes = [ctypes.c_char_p]
    lib.mxtpu_rec_count.restype = ctypes.c_int64
    lib.mxtpu_rec_writer_open.argtypes = [ctypes.c_char_p, ctypes.POINTER(p)]
    lib.mxtpu_rec_write.argtypes = [p, ctypes.c_char_p, u64]
    lib.mxtpu_rec_writer_tell.argtypes = [p]
    lib.mxtpu_rec_writer_tell.restype = ctypes.c_int64
    lib.mxtpu_rec_writer_close.argtypes = [p]
    lib.mxtpu_imgpipe_open.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, u64,
        ctypes.POINTER(p)]
    lib.mxtpu_imgpipe_close.argtypes = [p]
    lib.mxtpu_imgpipe_next.argtypes = [p, ctypes.POINTER(p)]
    lib.mxtpu_imgpipe_get.argtypes = [
        p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.c_int)]
    lib.mxtpu_imgpipe_free.argtypes = [p]
    lib.mxtpu_imgpipe_reset.argtypes = [p]
    lib.mxtpu_pool_alloc.argtypes = [ctypes.c_size_t]
    lib.mxtpu_pool_alloc.restype = p
    lib.mxtpu_pool_free.argtypes = [p, ctypes.c_size_t]
    lib.mxtpu_pool_stats.argtypes = [ctypes.POINTER(u64)]
    lib.mxtpu_nd_create.argtypes = [ctypes.c_char_p, ctypes.POINTER(u64),
                                    ctypes.c_int, ctypes.POINTER(p)]
    lib.mxtpu_nd_free.argtypes = [p]
    lib.mxtpu_nd_ndim.argtypes = [p]
    lib.mxtpu_nd_shape.argtypes = [p, ctypes.POINTER(u64)]
    lib.mxtpu_nd_dtype.argtypes = [p]
    lib.mxtpu_nd_dtype.restype = ctypes.c_char_p
    lib.mxtpu_nd_size.argtypes = [p]
    lib.mxtpu_nd_size.restype = u64
    lib.mxtpu_nd_data.argtypes = [p]
    lib.mxtpu_nd_data.restype = p
    lib.mxtpu_nd_nbytes.argtypes = [p]
    lib.mxtpu_nd_nbytes.restype = u64
    lib.mxtpu_nd_copy_from.argtypes = [p, p, u64]
    lib.mxtpu_nd_save.argtypes = [ctypes.c_char_p, ctypes.POINTER(p),
                                  ctypes.POINTER(ctypes.c_char_p),
                                  ctypes.c_int]
    lib.mxtpu_nd_load.argtypes = [ctypes.c_char_p, ctypes.POINTER(p),
                                  ctypes.POINTER(ctypes.c_int)]
    lib.mxtpu_nd_list_get.argtypes = [p, ctypes.c_int,
                                      ctypes.POINTER(ctypes.c_char_p)]
    lib.mxtpu_nd_list_get.restype = p
    lib.mxtpu_nd_list_take.argtypes = [p, ctypes.c_int]
    lib.mxtpu_nd_list_take.restype = p
    lib.mxtpu_nd_list_free.argtypes = [p]
    lib.mxtpu_sym_load_json.argtypes = [ctypes.c_char_p, ctypes.POINTER(p)]
    lib.mxtpu_sym_load_file.argtypes = [ctypes.c_char_p, ctypes.POINTER(p)]
    lib.mxtpu_sym_free.argtypes = [p]
    lib.mxtpu_sym_num_args.argtypes = [p]
    lib.mxtpu_sym_arg_name.argtypes = [p, ctypes.c_int]
    lib.mxtpu_sym_arg_name.restype = ctypes.c_char_p
    lib.mxtpu_sym_num_outputs.argtypes = [p]
    lib.mxtpu_sym_output_name.argtypes = [p, ctypes.c_int]
    lib.mxtpu_sym_output_name.restype = ctypes.c_char_p
    lib.mxtpu_sym_num_nodes.argtypes = [p]
    lib.mxtpu_sym_node_op.argtypes = [p, ctypes.c_int]
    lib.mxtpu_sym_node_op.restype = ctypes.c_char_p
    lib.mxtpu_sym_node_name.argtypes = [p, ctypes.c_int]
    lib.mxtpu_sym_node_name.restype = ctypes.c_char_p
    lib.mxtpu_sym_to_json.argtypes = [p]
    lib.mxtpu_sym_to_json.restype = ctypes.c_char_p
    lib.mxtpu_sym_save_file.argtypes = [p, ctypes.c_char_p]
    lib.mxtpu_last_error.restype = ctypes.c_char_p
    lib.mxtpu_version.restype = ctypes.c_char_p
    return lib


def lib():
    """The loaded native library, or None if unavailable."""
    global _lib, _tried, _build_error
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("MXTPU_NO_NATIVE"):
            return None
        # always invoke make: the dependency rule makes it a no-op when the
        # .so is current, and it rebuilds stale libraries after C ABI changes
        try:
            subprocess.run(["make", "-C", _CPP_DIR], check=True,
                           capture_output=True, timeout=300)
            _lib = _declare(ctypes.CDLL(_LIB_PATH))
        except Exception as e:
            # kept for build_error(): optional users (the host engine, the
            # raw record reader) carry on without the library, a caller
            # that ASKED for a native path raises it
            detail = (getattr(e, "stderr", None) or b"").decode(
                errors="replace")[-2000:]
            _build_error = (f"native runtime build failed "
                            f"(make -C {_CPP_DIR}): {e}\n{detail}")
            import logging
            logging.getLogger("mxnet_tpu").error("%s", _build_error)
            _lib = None
        return _lib


def build_error():
    """Why :func:`lib` has no library although one was wanted: the failed
    build's message, or None (library loaded, or ``MXTPU_NO_NATIVE`` set)."""
    lib()
    return _build_error


def last_error() -> str:
    l = lib()
    return l.mxtpu_last_error().decode() if l else ""


class NativeEngine:
    """Dependency engine over the native scheduler.

    Python callables are pushed with read/write variable ids; exceptions
    raised inside a callable poison the op's write-vars and re-raise at
    wait_var/wait_all, matching the reference's engine exception semantics
    (src/engine/threaded_engine.h:179,450-465; tests test_exc_handling.py).
    """

    def __init__(self, num_workers: int = 4):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = l
        handle = ctypes.c_void_p()
        if l.mxtpu_engine_create(num_workers, ctypes.byref(handle)):
            raise RuntimeError(last_error())
        self._h = handle
        self._next_id = 1
        self._callbacks = {}   # id -> (CFUNCTYPE ref, fn)
        self._errors = {}      # id -> exception, kept until consumed by a wait
        self._inflight = 0     # pushes registered but not yet handed to C
        self._cb_lock = threading.Lock()

    def new_var(self) -> int:
        return int(self._lib.mxtpu_engine_new_var(self._h))

    def push(self, fn, read_vars=(), write_vars=(), priority=0, sync=False):
        with self._cb_lock:
            op_id = self._next_id
            self._next_id += 1

        def trampoline(_ctx, _op_id=op_id, _fn=fn):
            try:
                _fn()
                return 0
            except BaseException as e:  # noqa: BLE001 — crossing C boundary
                with self._cb_lock:
                    self._errors[_op_id] = e
                return 1

        cfn = MXTPU_FN(trampoline)
        with self._cb_lock:
            self._callbacks[op_id] = (cfn, fn)
            self._inflight += 1

        try:
            reads = (ctypes.c_uint64 * len(read_vars))(*read_vars)
            writes = (ctypes.c_uint64 * len(write_vars))(*write_vars)
            rc = self._lib.mxtpu_engine_push(
                self._h, cfn, ctypes.c_void_p(op_id), reads, len(read_vars),
                writes, len(write_vars), priority, 1 if sync else 0)
        finally:
            with self._cb_lock:
                self._inflight -= 1
        if rc:
            raise RuntimeError(last_error())
        if sync:
            self._raise_if(op_id)
        return op_id

    def _raise_if(self, failed_id: int):
        with self._cb_lock:
            exc = self._errors.pop(failed_id, None)
        if exc is not None:
            raise exc

    def wait_var(self, var: int):
        failed = ctypes.c_uint64()
        if self._lib.mxtpu_engine_wait_var(self._h, var, ctypes.byref(failed)):
            self._raise_if(int(failed.value))
            raise RuntimeError(f"engine op {failed.value} failed")
        self._gc_callbacks()

    def wait_all(self):
        failed = ctypes.c_uint64()
        if self._lib.mxtpu_engine_wait_all(self._h, ctypes.byref(failed)):
            self._gc_callbacks()
            self._raise_if(int(failed.value))
            raise RuntimeError(f"engine op {failed.value} failed")
        self._gc_callbacks()

    def _gc_callbacks(self):
        # Once the engine drained AND no push is mid-registration, completed
        # trampolines are unreachable from C — safe to drop refs. Stored
        # exceptions stay until the wait that surfaces them consumes them.
        with self._cb_lock:
            if self._inflight == 0 and \
                    self._lib.mxtpu_engine_num_pending(self._h) == 0:
                self._callbacks.clear()

    def delete_var(self, var: int):
        self._lib.mxtpu_engine_delete_var(self._h, var)

    def num_pending(self) -> int:
        return int(self._lib.mxtpu_engine_num_pending(self._h))

    def close(self):
        if getattr(self, "_h", None):
            self.wait_all()
            self._lib.mxtpu_engine_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class RecordReader:
    """Prefetching sharded RecordIO reader (native). Iterates bytes records."""

    def __init__(self, path, batch_records=64, queue_depth=4, shard_index=0,
                 num_shards=1):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = l
        handle = ctypes.c_void_p()
        if l.mxtpu_rec_open(path.encode(), batch_records, queue_depth,
                            shard_index, num_shards, ctypes.byref(handle)):
            raise IOError(last_error())
        self._h = handle

    def __iter__(self):
        return self

    def __next__(self) -> bytes:
        buf = getattr(self, "_pending", None)
        if not buf:
            batch = ctypes.c_void_p()
            count = ctypes.c_int()
            if self._lib.mxtpu_rec_next_batch(self._h, ctypes.byref(batch),
                                              ctypes.byref(count)):
                raise IOError(last_error())
            if not batch.value:
                raise StopIteration
            records = []
            data = ctypes.POINTER(ctypes.c_uint8)()
            length = ctypes.c_uint64()
            for i in range(count.value):
                self._lib.mxtpu_rec_get(batch, i, ctypes.byref(data),
                                        ctypes.byref(length))
                records.append(ctypes.string_at(data, length.value))
            self._lib.mxtpu_rec_free_batch(batch)
            records.reverse()
            self._pending = buf = records
        return buf.pop()

    def reset(self):
        self._pending = None
        self._lib.mxtpu_rec_reset(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxtpu_rec_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class RecordWriter:
    """Native sequential RecordIO writer."""

    def __init__(self, path):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = l
        handle = ctypes.c_void_p()
        if l.mxtpu_rec_writer_open(path.encode(), ctypes.byref(handle)):
            raise IOError(last_error())
        self._h = handle

    def write(self, buf: bytes):
        if self._lib.mxtpu_rec_write(self._h, buf, len(buf)):
            raise IOError("record write failed")

    def tell(self) -> int:
        return int(self._lib.mxtpu_rec_writer_tell(self._h))

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxtpu_rec_writer_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ImagePipeline:
    """Native threaded decode+augment pipeline (cpp/src/imagedec.cc).

    Yields (uint8 NHWC ndarray (B,H,W,3), float32 labels (B,label_width))
    per batch; the device side does transpose/normalize (uint8 crosses the
    host link, 4x cheaper than float32).
    """

    def __init__(self, path, batch_size, data_shape=(3, 224, 224),
                 resize=256, num_threads=4, queue_depth=4, shard_index=0,
                 num_shards=1, rand_crop=False, rand_mirror=False,
                 shuffle=False, label_width=1, seed=0):
        l = lib()
        if l is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = l
        c, h, w = data_shape
        if c != 3:
            raise NativeUnsupportedError(
                "native image pipeline is RGB-only (C=3)")
        self.batch_size = batch_size
        self.h, self.w = h, w
        self.label_width = label_width
        handle = ctypes.c_void_p()
        if l.mxtpu_imgpipe_open(path.encode(), batch_size, h, w, resize,
                                num_threads, queue_depth, shard_index,
                                num_shards, int(rand_crop), int(rand_mirror),
                                int(shuffle), label_width, seed,
                                ctypes.byref(handle)):
            raise IOError(last_error())
        self._h = handle

    def __iter__(self):
        return self

    def __next__(self):
        batch = ctypes.c_void_p()
        if self._lib.mxtpu_imgpipe_next(self._h, ctypes.byref(batch)):
            raise IOError(last_error())
        if not batch.value:
            raise StopIteration
        data = ctypes.POINTER(ctypes.c_uint8)()
        labels = ctypes.POINTER(ctypes.c_float)()
        count = ctypes.c_int()
        self._lib.mxtpu_imgpipe_get(batch, ctypes.byref(data),
                                    ctypes.byref(labels), ctypes.byref(count))
        import numpy as np

        # the native side pads trailing batches to batch_size by repeating
        # rows; count is the real sample count (DataBatch.pad = B - count)
        B = self.batch_size
        img = np.ctypeslib.as_array(data, (B, self.h, self.w, 3)).copy()
        lab = np.ctypeslib.as_array(labels, (B, self.label_width)).copy()
        self._lib.mxtpu_imgpipe_free(batch)
        return img, lab, count.value

    def reset(self):
        if self._lib.mxtpu_imgpipe_reset(self._h):
            raise IOError(last_error())

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxtpu_imgpipe_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def rec_count(path: str) -> int:
    l = lib()
    if l is None:
        raise RuntimeError("native runtime unavailable")
    return int(l.mxtpu_rec_count(path.encode()))


def pool_stats():
    l = lib()
    if l is None:
        return None
    out = (ctypes.c_uint64 * 4)()
    l.mxtpu_pool_stats(out)
    return {"os_bytes": out[0], "reused_bytes": out[1], "live": out[2],
            "pooled_bytes": out[3]}
