// Embedded-runtime C API: executor + kvstore surfaces callable from plain C.
//
// Reference parity: include/mxnet/c_api.h MXExecutor* (MXExecutorSimpleBind,
// MXExecutorForward/Backward/Outputs) and MXKVStore* (MXKVStoreCreate/Init/
// Push/Pull/SetOptimizer).  The reference's C API fronts its own C++ runtime;
// here the runtime IS the Python/XLA stack, so the C surface embeds a CPython
// interpreter and drives the public mxnet_tpu API through it.  That keeps one
// executor implementation (no C++ re-implementation to drift) while giving
// foreign bindings (C++, or anything with a C FFI) the full train/infer loop.
//
// Threading: every entry point takes the GIL via PyGILState_Ensure, so the C
// API is safe to call from any single foreign thread at a time.
//
// Environment: MXTPU_RT_HOME adds a directory to sys.path before importing
// mxnet_tpu (defaults to $PWD); MXTPU_RT_PLATFORM forces the jax platform
// ("cpu" keeps the embedded interpreter off the accelerator).

#include <Python.h>

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <cstdarg>
#include <string>

extern "C" {

static PyObject* g_ns = nullptr;  // namespace dict holding the helper fns
static char g_err[1024];

static void set_err_from_python() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      snprintf(g_err, sizeof(g_err), "%s", PyUnicode_AsUTF8(s));
      Py_DECREF(s);
    }
  } else {
    snprintf(g_err, sizeof(g_err), "unknown python error");
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

const char* mxtpu_rt_last_error(void) { return g_err; }

// The Python-side helper layer: a handle registry over the public API.
static const char kPrelude[] = R"PY(
import os
import sys

# Embedded CPython resolves its prefix from the host program's environment;
# when the caller's Python lives in a venv (VIRTUAL_ENV), its site-packages
# must be added by hand or numpy/jax resolve to the bare system install.
_venv = os.environ.get("VIRTUAL_ENV")
if _venv:
    _site = os.path.join(_venv, "lib",
                         "python%d.%d" % sys.version_info[:2],
                         "site-packages")
    if os.path.isdir(_site) and _site not in sys.path:
        sys.path.insert(0, _site)

import numpy as _np

if os.environ.get("MXTPU_RT_PLATFORM"):
    import jax
    jax.config.update("jax_platforms", os.environ["MXTPU_RT_PLATFORM"])

import mxnet_tpu as _mx

_H = {}
_NEXT = [1]


def _put(obj):
    h = _NEXT[0]
    _NEXT[0] += 1
    _H[h] = obj
    return h


def rt_exec_create(js):
    return _put({"sym": _mx.sym.load_json(js)})


def rt_exec_bind(h, names, shapes):
    st = _H[h]
    kw = {n: tuple(int(d) for d in s) for n, s in zip(names, shapes)}
    st["exe"] = st["sym"].simple_bind(ctx=_mx.cpu(), **kw)
    return 0


def rt_exec_set_arg(h, name, mv, shape):
    exe = _H[h]["exe"]
    # .copy(): the ABI contract lets callers free the buffer on return, but
    # the jnp write below reads it lazily (async dispatch / zero-copy
    # aliasing) — without the copy a prompt free() is a use-after-free
    a = _np.frombuffer(mv, dtype=_np.float32).reshape(tuple(shape)).copy()
    exe.arg_dict[name][:] = _mx.nd.array(a)
    return 0


def rt_exec_arg_names(h):
    return list(_H[h]["exe"].arg_dict)


def rt_exec_forward(h, is_train):
    _H[h]["exe"].forward(is_train=bool(is_train))
    return 0


def rt_exec_backward(h):
    _H[h]["exe"].backward()
    return 0


def rt_exec_num_outputs(h):
    return len(_H[h]["exe"].outputs)


def rt_exec_output_shape(h, i):
    return list(_H[h]["exe"].outputs[i].shape)


def rt_exec_output(h, i, mv):
    out = _H[h]["exe"].outputs[i].asnumpy().astype(_np.float32).ravel()
    buf = _np.frombuffer(mv, dtype=_np.float32)
    if buf.size != out.size:
        # a partial fill would hand every binding silent garbage (and a
        # heap info-leak) in the unwritten tail
        raise ValueError(
            f"output {i} has {out.size} elements; caller buffer has "
            f"{buf.size}")
    buf[:] = out
    return 0


def rt_exec_grad(h, name, mv):
    g = _H[h]["exe"].grad_dict[name].asnumpy().astype(_np.float32).ravel()
    buf = _np.frombuffer(mv, dtype=_np.float32)
    if buf.size != g.size:
        raise ValueError(
            f"grad {name!r} has {g.size} elements; caller buffer has "
            f"{buf.size}")
    buf[:] = g
    return 0


def rt_kv_create(kind):
    return _put({"kv": _mx.kv.create(kind)})


def rt_kv_init(h, key, mv, shape):
    a = _np.frombuffer(mv, dtype=_np.float32).reshape(tuple(shape)).copy()
    _H[h].setdefault("shapes", {})[int(key)] = tuple(int(d) for d in shape)
    _H[h]["kv"].init(key, _mx.nd.array(a))
    return 0


def rt_kv_push(h, key, mv, shape):
    a = _np.frombuffer(mv, dtype=_np.float32).reshape(tuple(shape)).copy()
    _H[h]["kv"].push(key, _mx.nd.array(a))
    return 0


def rt_kv_pull(h, key, mv):
    out = _mx.nd.zeros(_H[h]["shapes"][int(key)])
    _H[h]["kv"].pull(key, out=out)
    vals = out.asnumpy().astype(_np.float32).ravel()
    buf = _np.frombuffer(mv, dtype=_np.float32)
    if buf.size != vals.size:
        raise ValueError(
            f"key {key} has {vals.size} elements; caller buffer has "
            f"{buf.size}")
    buf[:] = vals
    return 0


def rt_kv_set_optimizer(h, name, lr):
    _H[h]["kv"].set_optimizer(_mx.optimizer.create(name, learning_rate=lr))
    return 0


def rt_free(h):
    _H.pop(h, None)
    return 0


def rt_pred_create(sym_json, params_path, names, shapes):
    """Inference-only predictor (reference: src/c_api/c_predict_api.cc
    MXPredCreate): graph JSON + a .params checkpoint (either the native or
    the stock-MXNet binary format via nd.load auto-detection) + input
    shapes -> a bound executor with weights installed."""
    h = rt_exec_create(sym_json)
    try:
        rt_exec_bind(h, names, shapes)
        exe = _H[h]["exe"]
        if params_path:
            loaded = _mx.nd.load(params_path)
            if not isinstance(loaded, dict):
                raise ValueError("predictor needs a keyed .params file")
            for k, v in loaded.items():
                name = k.split(":", 1)[1] if ":" in k else k
                if name in exe.arg_dict and name not in names:
                    exe.arg_dict[name][:] = v
                elif name in exe.aux_dict:
                    exe.aux_dict[name][:] = v
    except Exception:
        # a failed create must not leak the registered handle (long-lived
        # servers retry pred_create on user models)
        rt_free(h)
        raise
    return h





)PY";

int mxtpu_rt_init(void) {
  if (g_ns) return 0;
  int we_initialized = 0;
  if (!Py_IsInitialized()) {
    // When the host (e.g. perl, or any dlopen-based embedder) loaded this
    // library RTLD_LOCAL, libpython's symbols are invisible to the extension
    // modules numpy/jax dlopen later (they expect the interpreter to export
    // them globally).  Promote the already-mapped libpython to global scope.
    char soname[64];
    snprintf(soname, sizeof(soname), "libpython%d.%d.so.1.0",
             PY_MAJOR_VERSION, PY_MINOR_VERSION);
    if (!dlopen(soname, RTLD_NOW | RTLD_GLOBAL | RTLD_NOLOAD)) {
      dlopen(soname, RTLD_NOW | RTLD_GLOBAL);
    }
    // Embedded CPython may resolve its prefix outside the caller's venv, and
    // sitecustomize (which can import numpy/jax) runs during Py_Initialize —
    // so the venv's site-packages must lead PYTHONPATH BEFORE init.  The
    // mutation is undone right after init so child processes the host spawns
    // later see their original environment.
    const char* venv = getenv("VIRTUAL_ENV");
    char* saved_pp = nullptr;
    int had_pp = 0;
    if (venv) {
      const char* old = getenv("PYTHONPATH");
      had_pp = old != nullptr;
      if (old) saved_pp = strdup(old);
      size_t n = strlen(venv) + 64 + (old ? strlen(old) + 1 : 0);
      char* merged = (char*)malloc(n);
      if (old && old[0]) {
        snprintf(merged, n, "%s/lib/python%d.%d/site-packages:%s", venv,
                 PY_MAJOR_VERSION, PY_MINOR_VERSION, old);
      } else {
        snprintf(merged, n, "%s/lib/python%d.%d/site-packages", venv,
                 PY_MAJOR_VERSION, PY_MINOR_VERSION);
      }
      setenv("PYTHONPATH", merged, 1);
      free(merged);
    }
    Py_InitializeEx(0);
    we_initialized = 1;
    if (venv) {
      if (had_pp) {
        setenv("PYTHONPATH", saved_pp, 1);
      } else {
        unsetenv("PYTHONPATH");
      }
      free(saved_pp);
    }
  }
  PyGILState_STATE gil = PyGILState_Ensure();
  int rc = -1;
  do {
    PyObject* sys_path = PySys_GetObject("path");  // borrowed
    const char* home = getenv("MXTPU_RT_HOME");
    PyObject* dir = PyUnicode_FromString(home ? home : ".");
    if (sys_path && dir) PyList_Insert(sys_path, 0, dir);
    Py_XDECREF(dir);

    PyObject* mod = PyImport_AddModule("__mxtpu_rt__");  // borrowed
    if (!mod) break;
    g_ns = PyModule_GetDict(mod);  // borrowed, lives with the module
    Py_INCREF(g_ns);
    PyObject* r = PyRun_String(kPrelude, Py_file_input, g_ns, g_ns);
    if (!r) {
      set_err_from_python();
      Py_CLEAR(g_ns);
      break;
    }
    Py_DECREF(r);
    rc = 0;
  } while (0);
  PyGILState_Release(gil);
  if (we_initialized) {
    // Py_InitializeEx leaves this thread holding the GIL outside any
    // PyGILState pairing; release it so other foreign threads can Ensure.
    PyEval_SaveThread();
  }
  return rc;
}

// call helper fn by name; returns new ref or nullptr (error recorded)
static PyObject* rt_call(const char* fn, PyObject* args) {
  PyObject* f = PyDict_GetItemString(g_ns, fn);  // borrowed
  if (!f) {
    snprintf(g_err, sizeof(g_err), "runtime fn %s missing (init not run?)", fn);
    return nullptr;
  }
  PyObject* r = PyObject_CallObject(f, args);
  if (!r) set_err_from_python();
  return r;
}

static PyObject* shape_list(const int64_t* shape, int ndim) {
  PyObject* l = PyList_New(ndim);
  for (int i = 0; i < ndim; ++i)
    PyList_SetItem(l, i, PyLong_FromLongLong(shape[i]));
  return l;
}

// Build args AND call under the GIL: ctypes (and any foreign caller) does not
// hold the GIL during the call, so no Python C API use may precede Ensure.
static int64_t call_fmt(const char* fn, const char* fmt, ...) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  va_list ap;
  va_start(ap, fmt);
  PyObject* args = Py_VaBuildValue(fmt, ap);
  va_end(ap);
  int64_t out = -1;
  if (args) {
    PyObject* r = rt_call(fn, args);
    Py_DECREF(args);
    if (r) {
      out = PyLong_Check(r) ? PyLong_AsLongLong(r) : 0;
      Py_DECREF(r);
    }
  } else {
    set_err_from_python();
  }
  PyGILState_Release(gil);
  return out;
}

int64_t mxtpu_exec_create(const char* symbol_json) {
  return call_fmt("rt_exec_create", "(s)", symbol_json);
}

int mxtpu_exec_simple_bind(int64_t h, const char** names,
                           const int64_t* shapes, const int* ndims, int n) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* nlist = PyList_New(n);
  PyObject* slist = PyList_New(n);
  const int64_t* p = shapes;
  for (int i = 0; i < n; ++i) {
    PyList_SetItem(nlist, i, PyUnicode_FromString(names[i]));
    PyList_SetItem(slist, i, shape_list(p, ndims[i]));
    p += ndims[i];
  }
  PyObject* args = Py_BuildValue("(LNN)", (long long)h, nlist, slist);
  int rc = -1;
  PyObject* r = rt_call("rt_exec_bind", args);
  Py_XDECREF(args);
  if (r) { rc = 0; Py_DECREF(r); }
  PyGILState_Release(gil);
  return rc;
}

static int buffer_call(const char* fn, int64_t h, const char* name,
                       const float* data, const int64_t* shape, int ndim,
                       int64_t nelem) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* mv = PyMemoryView_FromMemory(
      (char*)data, nelem * (int64_t)sizeof(float),
      shape ? PyBUF_READ : PyBUF_WRITE);
  PyObject* args;
  if (shape) {
    args = Py_BuildValue("(LsNN)", (long long)h, name, mv,
                         shape_list(shape, ndim));
  } else {
    args = Py_BuildValue("(LsN)", (long long)h, name, mv);
  }
  int rc = -1;
  PyObject* r = rt_call(fn, args);
  Py_XDECREF(args);
  if (r) { rc = 0; Py_DECREF(r); }
  PyGILState_Release(gil);
  return rc;
}

int mxtpu_exec_set_arg(int64_t h, const char* name, const float* data,
                       const int64_t* shape, int ndim) {
  int64_t n = 1;
  for (int i = 0; i < ndim; ++i) n *= shape[i];
  return buffer_call("rt_exec_set_arg", h, name, data, shape, ndim, n);
}

int mxtpu_exec_forward(int64_t h, int is_train) {
  return call_fmt("rt_exec_forward", "(Li)", (long long)h, is_train) < 0 ? -1 : 0;
}

int mxtpu_exec_backward(int64_t h) {
  return call_fmt("rt_exec_backward", "(L)", (long long)h) < 0 ? -1 : 0;
}

int mxtpu_exec_num_outputs(int64_t h) {
  return (int)call_fmt("rt_exec_num_outputs", "(L)", (long long)h);
}

int mxtpu_exec_output_shape(int64_t h, int idx, int64_t* shape, int* ndim,
                            int cap) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* args = Py_BuildValue("(Li)", (long long)h, idx);
  int rc = -1;
  PyObject* r = rt_call("rt_exec_output_shape", args);
  Py_XDECREF(args);
  if (r) {
    int n = (int)PyList_Size(r);
    if (n > cap) {
      snprintf(g_err, sizeof(g_err),
               "output rank %d exceeds caller capacity %d", n, cap);
      Py_DECREF(r);
      PyGILState_Release(gil);
      return -1;
    }
    *ndim = n;
    for (int i = 0; i < n; ++i)
      shape[i] = PyLong_AsLongLong(PyList_GetItem(r, i));
    Py_DECREF(r);
    rc = 0;
  }
  PyGILState_Release(gil);
  return rc;
}

int mxtpu_exec_output(int64_t h, int idx, float* buf, int64_t nelem) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* mv = PyMemoryView_FromMemory((char*)buf,
                                         nelem * (int64_t)sizeof(float),
                                         PyBUF_WRITE);
  PyObject* args = Py_BuildValue("(LiN)", (long long)h, idx, mv);
  int rc = -1;
  PyObject* r = rt_call("rt_exec_output", args);
  Py_XDECREF(args);
  if (r) { rc = 0; Py_DECREF(r); }
  PyGILState_Release(gil);
  return rc;
}

int mxtpu_exec_grad(int64_t h, const char* name, float* buf, int64_t nelem) {
  return buffer_call("rt_exec_grad", h, name, buf, nullptr, 0, nelem);
}

int64_t mxtpu_kv_create(const char* kind) {
  return call_fmt("rt_kv_create", "(s)", kind);
}

static int kv_data_call(const char* fn, int64_t h, int key, const float* data,
                        const int64_t* shape, int ndim) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  int64_t n = 1;
  for (int i = 0; i < ndim; ++i) n *= shape[i];
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* mv = PyMemoryView_FromMemory((char*)data,
                                         n * (int64_t)sizeof(float),
                                         PyBUF_READ);
  PyObject* args = Py_BuildValue("(LiNN)", (long long)h, key, mv,
                                 shape_list(shape, ndim));
  int rc = -1;
  PyObject* r = rt_call(fn, args);
  Py_XDECREF(args);
  if (r) { rc = 0; Py_DECREF(r); }
  PyGILState_Release(gil);
  return rc;
}

int mxtpu_kv_init(int64_t h, int key, const float* data, const int64_t* shape,
                  int ndim) {
  return kv_data_call("rt_kv_init", h, key, data, shape, ndim);
}

int mxtpu_kv_push(int64_t h, int key, const float* data, const int64_t* shape,
                  int ndim) {
  return kv_data_call("rt_kv_push", h, key, data, shape, ndim);
}

int mxtpu_kv_pull(int64_t h, int key, float* buf, int64_t nelem) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* mv = PyMemoryView_FromMemory((char*)buf,
                                         nelem * (int64_t)sizeof(float),
                                         PyBUF_WRITE);
  PyObject* args = Py_BuildValue("(LiN)", (long long)h, key, mv);
  int rc = -1;
  PyObject* r = rt_call("rt_kv_pull", args);
  Py_XDECREF(args);
  if (r) { rc = 0; Py_DECREF(r); }
  PyGILState_Release(gil);
  return rc;
}

int mxtpu_kv_set_optimizer(int64_t h, const char* name, float lr) {
  return call_fmt("rt_kv_set_optimizer", "(Lsd)", (long long)h, name,
                  (double)lr) < 0 ? -1 : 0;
}

int mxtpu_rt_free(int64_t h);

/* ---- inference-only predict surface (reference c_predict_api.cc:
 * MXPredCreate / MXPredSetInput / MXPredForward / MXPredGetOutput /
 * MXPredFree).  Thin aliases over the executor runtime: same handles, so
 * mxtpu_exec_set_arg / mxtpu_exec_output_shape / mxtpu_exec_output serve
 * SetInput / GetOutputShape / GetOutput. */
int64_t mxtpu_pred_create(const char* symbol_json, const char* params_path,
                          const char** input_names,
                          const int64_t* shapes_concat, const int* ndims,
                          int n_inputs) {
  if (!g_ns && mxtpu_rt_init() != 0) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  PyObject* nlist = PyList_New(n_inputs);
  PyObject* slist = PyList_New(n_inputs);
  const int64_t* p = shapes_concat;
  for (int i = 0; i < n_inputs; ++i) {
    PyList_SetItem(nlist, i, PyUnicode_FromString(input_names[i]));
    PyObject* shp = PyList_New(ndims[i]);
    for (int d = 0; d < ndims[i]; ++d)
      PyList_SetItem(shp, d, PyLong_FromLongLong((long long)*p++));
    PyList_SetItem(slist, i, shp);
  }
  PyObject* args = Py_BuildValue("(ssNN)", symbol_json,
                                 params_path ? params_path : "", nlist,
                                 slist);
  int64_t h = -1;
  PyObject* r = rt_call("rt_pred_create", args);
  Py_XDECREF(args);
  if (r) {
    h = PyLong_AsLongLong(r);
    Py_DECREF(r);
  }
  PyGILState_Release(gil);
  return h;
}

int mxtpu_pred_set_input(int64_t h, const char* name, const float* data,
                         const int64_t* shape, int ndim) {
  return mxtpu_exec_set_arg(h, name, data, shape, ndim);
}

int mxtpu_pred_forward(int64_t h) { return mxtpu_exec_forward(h, 0); }

int mxtpu_pred_get_output_shape(int64_t h, int idx, int64_t* shape,
                                int* ndim, int cap) {
  return mxtpu_exec_output_shape(h, idx, shape, ndim, cap);
}

int mxtpu_pred_get_output(int64_t h, int idx, float* buf, int64_t nelem) {
  return mxtpu_exec_output(h, idx, buf, nelem);
}

int mxtpu_pred_free(int64_t h) { return mxtpu_rt_free(h); }

int mxtpu_rt_free(int64_t h) {
  return call_fmt("rt_free", "(L)", (long long)h) < 0 ? -1 : 0;
}

}  // extern "C"
