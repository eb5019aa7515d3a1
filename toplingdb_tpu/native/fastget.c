/* CPython C-extension fast path for the native point-read call.
 *
 * The hot Get's per-call cost under ctypes is dominated by argument
 * marshaling (~0.6-0.8us of a ~2.2us call). This METH_FASTCALL shim
 * calls tpulsm_getctx_get directly (symbols resolved from the already-
 * built _tpulsm_native.so via dlopen) and returns the value as bytes —
 * the reference's JNI/C-API binding-layer role for the read path.
 *
 * Protocol: get(ctx_addr, key, snap_seq) ->
 *   bytes  found (value)
 *   None   decisive miss
 *   False  native fallback (the Python state machine must run)
 * The GIL is released around the native chain walk, matching the ctypes
 * path's concurrency (ctx is per-thread).
 *
 * wb_put_values is the write side's one native pass: WriteBatch.put keeps
 * default-CF records as (key, value) references and the batch's wire
 * image is encoded here when it is first read (db/write_batch.py _settle).
 * It needs no bound symbols.
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <dlfcn.h>
#include <stdint.h>

typedef int32_t (*getctx_get_fn)(void*, const uint8_t*, int32_t, uint64_t);
typedef int64_t* (*getctx_out_fn)(void*);
typedef uint8_t* (*getctx_val_fn)(void*);
typedef int32_t (*getctx_mget_fn)(void*, const uint8_t*, const int64_t*,
                                  const int32_t*, int64_t, uint64_t,
                                  int8_t*, int64_t*, int64_t*, uint8_t*,
                                  int64_t, int64_t*, int64_t*);

static getctx_get_fn p_get;
static getctx_out_fn p_out;
static getctx_val_fn p_val;
static getctx_mget_fn p_mget;

/* Result-arena cache: taking/returning happens WHILE HOLDING the GIL, so
 * no lock is needed; a second thread entering mid-call simply allocates
 * its own arena. Grown capacity persists (a fresh 1MiB alloc per batch —
 * an mmap + page faults — previously dominated small-batch multigets). */
static uint8_t* g_arena_cache = NULL;
static int64_t g_arena_cache_cap = 0;

static uint8_t* arena_take(int64_t* cap) {
  if (g_arena_cache) {
    uint8_t* a = g_arena_cache;
    *cap = g_arena_cache_cap;
    g_arena_cache = NULL;
    g_arena_cache_cap = 0;
    return a;
  }
  *cap = 1 << 20;
  return (uint8_t*)PyMem_Malloc((size_t)*cap);
}

static void arena_give(uint8_t* a, int64_t cap) {
  if (!a) return;
  if (!g_arena_cache || cap > g_arena_cache_cap) {
    PyMem_Free(g_arena_cache);
    g_arena_cache = a;
    g_arena_cache_cap = cap;
  } else {
    PyMem_Free(a);
  }
}

static PyObject* fg_bind(PyObject* self, PyObject* args) {
  const char* path;
  (void)self;
  if (!PyArg_ParseTuple(args, "s", &path)) return NULL;
  void* h = dlopen(path, RTLD_NOW | RTLD_GLOBAL);
  if (!h) {
    PyErr_SetString(PyExc_OSError, dlerror());
    return NULL;
  }
  p_get = (getctx_get_fn)dlsym(h, "tpulsm_getctx_get");
  p_out = (getctx_out_fn)dlsym(h, "tpulsm_getctx_out");
  p_val = (getctx_val_fn)dlsym(h, "tpulsm_getctx_val");
  p_mget = (getctx_mget_fn)dlsym(h, "tpulsm_getctx_multiget");
  if (!p_get || !p_out || !p_val) {
    PyErr_SetString(PyExc_OSError, "tpulsm_getctx_* symbols missing");
    return NULL;
  }
  Py_RETURN_NONE;
}

/* multiget(ctx_addr, keys: list[bytes], snap_seq) ->
 *   (results: list[bytes | None | False], counters: tuple[int x 6])
 * False entries need the Python state machine (merge/blob/entity...).
 * The whole batch walk + result materialization happens here — the
 * per-key Python/numpy assembly dominated the batched read wall. */
static PyObject* fg_multiget(PyObject* self, PyObject* const* args,
                             Py_ssize_t nargs) {
  (void)self;
  if (nargs != 3) {
    PyErr_SetString(PyExc_TypeError, "multiget(ctx_addr, keys, snap_seq)");
    return NULL;
  }
  if (!p_mget) {
    PyErr_SetString(PyExc_RuntimeError, "multiget symbol unavailable");
    return NULL;
  }
  void* ctx = PyLong_AsVoidPtr(args[0]);
  if (!ctx && PyErr_Occurred()) return NULL;
  PyObject* keys = args[1];
  if (!PyList_Check(keys)) {
    PyErr_SetString(PyExc_TypeError, "keys must be a list of bytes");
    return NULL;
  }
  unsigned long long seq = PyLong_AsUnsignedLongLong(args[2]);
  if (PyErr_Occurred()) return NULL;
  Py_ssize_t n = PyList_GET_SIZE(keys);
  if (n == 0) return Py_BuildValue("([], (iiiiii))", 0, 0, 0, 0, 0, 0);

  int64_t* offs = (int64_t*)PyMem_Malloc(sizeof(int64_t) * n);
  int32_t* lens = (int32_t*)PyMem_Malloc(sizeof(int32_t) * n);
  int8_t* status = (int8_t*)PyMem_Malloc(n);
  int64_t* voffs = (int64_t*)PyMem_Malloc(sizeof(int64_t) * n);
  int64_t* vlens = (int64_t*)PyMem_Malloc(sizeof(int64_t) * n);
  uint8_t* keybuf = NULL;
  uint8_t* arena = NULL;
  PyObject* out = NULL;
  PyObject* cctr = NULL;
  PyObject* res = NULL;
  int64_t total = 0;
  int64_t arena_cap = 1 << 20;
  int64_t used = 0;
  int64_t ctr[6] = {0, 0, 0, 0, 0, 0};
  int32_t rc = -2;
  Py_ssize_t i;
  int oom = 0;

  if (!offs || !lens || !status || !voffs || !vlens) goto oom_exit;
  for (i = 0; i < n; i++) {
    PyObject* k = PyList_GET_ITEM(keys, i);
    char* kb;
    Py_ssize_t kl;
    if (PyBytes_AsStringAndSize(k, &kb, &kl) != 0) goto fail_exit;
    offs[i] = total;
    lens[i] = (int32_t)kl;
    total += kl;
  }
  keybuf = (uint8_t*)PyMem_Malloc(total ? (size_t)total : 1);
  if (!keybuf) goto oom_exit;
  for (i = 0; i < n; i++) {
    PyObject* k = PyList_GET_ITEM(keys, i);
    memcpy(keybuf + offs[i], PyBytes_AS_STRING(k),
           (size_t)PyBytes_GET_SIZE(k));
  }
  arena = arena_take(&arena_cap);
  while (rc == -2 && arena_cap <= ((int64_t)1 << 32)) {
    if (!arena) goto oom_exit;
    Py_BEGIN_ALLOW_THREADS
    rc = p_mget(ctx, keybuf, offs, lens, (int64_t)n, (uint64_t)seq,
                status, voffs, vlens, arena, arena_cap, &used, ctr);
    Py_END_ALLOW_THREADS
    if (rc == -2) {
      arena_cap *= 4;
      PyMem_Free(arena);
      arena = (uint8_t*)PyMem_Malloc((size_t)arena_cap);
    }
  }
  if (rc != 0) {
    /* batch-level fallback: caller uses the ctypes/Python path */
    res = Py_None;
    Py_INCREF(res);
    goto cleanup;
  }
  out = PyList_New(n);
  if (!out) goto oom_exit;
  for (i = 0; i < n; i++) {
    PyObject* v;
    if (status[i] == 1) {
      v = PyBytes_FromStringAndSize((const char*)arena + voffs[i],
                                    (Py_ssize_t)vlens[i]);
      if (!v) goto oom_exit;
    } else if (status[i] == 2) {
      v = Py_False;
      Py_INCREF(v);
    } else {
      v = Py_None;
      Py_INCREF(v);
    }
    PyList_SET_ITEM(out, i, v);
  }
  cctr = Py_BuildValue("(LLLLLL)", (long long)ctr[0], (long long)ctr[1],
                       (long long)ctr[2], (long long)ctr[3],
                       (long long)ctr[4], (long long)ctr[5]);
  if (!cctr) goto fail_exit;
  res = PyTuple_Pack(2, out, cctr);
  goto cleanup;

oom_exit:
  oom = 1;
fail_exit:
  if (oom) PyErr_NoMemory();
  res = NULL;
cleanup:
  Py_XDECREF(out);
  Py_XDECREF(cctr);
  PyMem_Free(keybuf);
  arena_give(arena, arena_cap);
  PyMem_Free(offs);
  PyMem_Free(lens);
  PyMem_Free(status);
  PyMem_Free(voffs);
  PyMem_Free(vlens);
  return res;
}

static PyObject* fg_get(PyObject* self, PyObject* const* args,
                        Py_ssize_t nargs) {
  (void)self;
  if (nargs != 3) {
    PyErr_SetString(PyExc_TypeError, "get(ctx_addr, key, snap_seq)");
    return NULL;
  }
  if (!p_get) {
    PyErr_SetString(PyExc_RuntimeError, "bind() not called");
    return NULL;
  }
  void* ctx = PyLong_AsVoidPtr(args[0]);
  if (!ctx && PyErr_Occurred()) return NULL;
  char* kbuf;
  Py_ssize_t klen;
  if (PyBytes_AsStringAndSize(args[1], &kbuf, &klen) != 0) return NULL;
  unsigned long long seq = PyLong_AsUnsignedLongLong(args[2]);
  if (PyErr_Occurred()) return NULL;
  int32_t rc;
  Py_BEGIN_ALLOW_THREADS
  rc = p_get(ctx, (const uint8_t*)kbuf, (int32_t)klen, (uint64_t)seq);
  Py_END_ALLOW_THREADS
  if (rc == 1) {
    int64_t* out = p_out(ctx);
    return PyBytes_FromStringAndSize((const char*)p_val(ctx),
                                     (Py_ssize_t)out[0]);
  }
  if (rc == 0) Py_RETURN_NONE;
  Py_RETURN_FALSE; /* fallback: run the Python chain */
}

static inline Py_ssize_t varint32_len(uint32_t v) {
  Py_ssize_t n = 1;
  while (v >= 0x80) {
    v >>= 7;
    n++;
  }
  return n;
}

static inline char* put_varint32(char* p, uint32_t v) {
  while (v >= 0x80) {
    *p++ = (char)(v | 0x80);
    v >>= 7;
  }
  *p++ = (char)v;
  return p;
}

/* wb_put_values(rep: bytearray, pending: list[tuple[bytes, bytes]]) -> int
 * Appends one default-CF VALUE record per pair to rep in ONE resize +
 * memcpy pass: 0x01 | varint32 klen | key | varint32 vlen | value, byte
 * for byte what WriteBatch._add_record writes (lengths masked to 32 bits
 * like coding.encode_varint32). Empties `pending` and returns how many
 * records it encoded. The GIL is held throughout: nothing else can touch
 * the list or the bytearray between the sizing and the copy. */
static PyObject* fg_wb_put_values(PyObject* self, PyObject* const* args,
                                  Py_ssize_t nargs) {
  (void)self;
  if (nargs != 2 || !PyByteArray_CheckExact(args[0]) ||
      !PyList_CheckExact(args[1])) {
    PyErr_SetString(PyExc_TypeError,
                    "wb_put_values(rep: bytearray, pending: list)");
    return NULL;
  }
  PyObject* rep = args[0];
  PyObject* pend = args[1];
  Py_ssize_t n = PyList_GET_SIZE(pend);
  Py_ssize_t add = 0;
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* kv = PyList_GET_ITEM(pend, i);
    if (!PyTuple_CheckExact(kv) || PyTuple_GET_SIZE(kv) != 2 ||
        !PyBytes_CheckExact(PyTuple_GET_ITEM(kv, 0)) ||
        !PyBytes_CheckExact(PyTuple_GET_ITEM(kv, 1))) {
      PyErr_SetString(PyExc_TypeError,
                      "pending records must be (bytes, bytes) pairs");
      return NULL;
    }
    Py_ssize_t kl = PyBytes_GET_SIZE(PyTuple_GET_ITEM(kv, 0));
    Py_ssize_t vl = PyBytes_GET_SIZE(PyTuple_GET_ITEM(kv, 1));
    Py_ssize_t rec = 1 + varint32_len((uint32_t)kl) + kl +
                     varint32_len((uint32_t)vl) + vl;
    if (rec > PY_SSIZE_T_MAX - add) return PyErr_NoMemory();
    add += rec;
  }
  Py_ssize_t old = PyByteArray_GET_SIZE(rep);
  if (add > PY_SSIZE_T_MAX - old) return PyErr_NoMemory();
  if (PyByteArray_Resize(rep, old + add) < 0) return NULL;
  char* p = PyByteArray_AS_STRING(rep) + old;
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* kv = PyList_GET_ITEM(pend, i);
    PyObject* k = PyTuple_GET_ITEM(kv, 0);
    PyObject* v = PyTuple_GET_ITEM(kv, 1);
    Py_ssize_t kl = PyBytes_GET_SIZE(k);
    Py_ssize_t vl = PyBytes_GET_SIZE(v);
    *p++ = 0x01; /* ValueType.VALUE */
    p = put_varint32(p, (uint32_t)kl);
    memcpy(p, PyBytes_AS_STRING(k), (size_t)kl);
    p += kl;
    p = put_varint32(p, (uint32_t)vl);
    memcpy(p, PyBytes_AS_STRING(v), (size_t)vl);
    p += vl;
  }
  if (PyList_SetSlice(pend, 0, n, NULL) < 0) return NULL;
  return PyLong_FromSsize_t(n);
}

static PyMethodDef fg_methods[] = {
    {"bind", fg_bind, METH_VARARGS,
     "bind(native_so_path): resolve the getctx symbols"},
    {"get", (PyCFunction)(void (*)(void))fg_get, METH_FASTCALL,
     "get(ctx_addr, key, snap_seq) -> bytes | None | False"},
    {"multiget", (PyCFunction)(void (*)(void))fg_multiget, METH_FASTCALL,
     "multiget(ctx_addr, keys, snap_seq) -> (results, counters) | None"},
    {"wb_put_values", (PyCFunction)(void (*)(void))fg_wb_put_values,
     METH_FASTCALL,
     "wb_put_values(rep, pending) -> n: encode deferred default-CF puts"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef fg_module = {
    PyModuleDef_HEAD_INIT, "tpulsm_fastget",
    "ctypes-free fast path for tpulsm_getctx_get", -1, fg_methods,
    NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC PyInit_tpulsm_fastget(void) {
  return PyModule_Create(&fg_module);
}
