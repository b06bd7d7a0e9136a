// libmxtpu_train.so — the C training ABI (c_train_api.h). Embeds CPython and
// drives mxnet_tpu.c_train; same layering as the reference's c_api.cc over
// the full runtime (here the runtime is Python-on-JAX, so the binding embeds
// it). Only buffers and strings cross the boundary.
#include <Python.h>

#include <dlfcn.h>

#include <cstring>
#include <string>
#include <vector>

#include "c_train_api.h"

namespace {

std::string g_tr_error;

void TrSetError(const std::string& msg) { g_tr_error = msg; }

void TrCapturePyError() {
  PyObject *type, *value, *trace;
  PyErr_Fetch(&type, &value, &trace);
  PyErr_NormalizeException(&type, &value, &trace);
  std::string msg = "python error";
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      msg = PyUnicode_AsUTF8(s);
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(trace);
  TrSetError(msg);
}

bool g_tr_owns_python = false;  // this library started the interpreter

bool TrEnsurePython() {
  if (!Py_IsInitialized()) {
    // hosts that dlopen this library (perl XS, dlopen-based bindings) load
    // libpython with local visibility; CPython extension modules need its
    // symbols GLOBAL. Promote before interpreter init.
    char soname[64];
    snprintf(soname, sizeof soname, "libpython%d.%d.so.1.0",
             PY_MAJOR_VERSION, PY_MINOR_VERSION);
    dlopen(soname, RTLD_NOW | RTLD_GLOBAL);
    Py_InitializeEx(0);
    g_tr_owns_python = true;
    PyEval_SaveThread();  // entry points re-acquire via PyGILState_Ensure
  }
  return true;
}

// call mxnet_tpu.c_train.<fn>(args...); returns new ref or null (error set)
PyObject* CallDriver(const char* fn_name, PyObject* args) {
  PyObject* mod = PyImport_ImportModule("mxnet_tpu.c_train");
  if (!mod) {
    TrCapturePyError();
    return nullptr;
  }
  PyObject* fn = PyObject_GetAttrString(mod, fn_name);
  Py_DECREF(mod);
  if (!fn) {
    TrCapturePyError();
    return nullptr;
  }
  PyObject* res = PyObject_CallObject(fn, args);
  Py_DECREF(fn);
  if (!res) TrCapturePyError();
  return res;
}

// call a method on a wrapped python object
PyObject* CallMethod(void* handle, const char* name, PyObject* args) {
  PyObject* obj = static_cast<PyObject*>(handle);
  PyObject* m = PyObject_GetAttrString(obj, name);
  if (!m) {
    TrCapturePyError();
    return nullptr;
  }
  PyObject* res = PyObject_CallObject(m, args);
  Py_DECREF(m);
  if (!res) TrCapturePyError();
  return res;
}

// copy a python bytes result into a float buffer of `size` elements
int BytesToFloats(PyObject* bytes, float* out, unsigned size) {
  char* raw;
  Py_ssize_t n;
  if (PyBytes_AsStringAndSize(bytes, &raw, &n) != 0) {
    TrCapturePyError();
    return -1;
  }
  if (static_cast<Py_ssize_t>(size * sizeof(float)) != n) {
    TrSetError("buffer size mismatch: have " + std::to_string(n) +
               " bytes, caller expects " + std::to_string(size) + " floats");
    return -1;
  }
  std::memcpy(out, raw, n);
  return 0;
}

struct Gil {
  PyGILState_STATE st;
  Gil() : st(PyGILState_Ensure()) {}
  ~Gil() { PyGILState_Release(st); }
};

}  // namespace

extern "C" {

const char* MXTrGetLastError() { return g_tr_error.c_str(); }

int MXTrSymbolVariable(const char* name, void** out) {
  if (!TrEnsurePython()) return -1;
  Gil gil;
  PyObject* args = Py_BuildValue("(s)", name);
  PyObject* res = CallDriver("sym_variable", args);
  Py_DECREF(args);
  if (!res) return -1;
  *out = res;
  return 0;
}

int MXTrSymbolCreate(const char* op_name, const char* name, void** inputs,
                     unsigned num_inputs, const char* attrs_json, void** out) {
  if (!TrEnsurePython()) return -1;
  Gil gil;
  PyObject* ins = PyList_New(num_inputs);
  for (unsigned i = 0; i < num_inputs; ++i) {
    PyObject* s = static_cast<PyObject*>(inputs[i]);
    Py_INCREF(s);
    PyList_SetItem(ins, i, s);
  }
  PyObject* args = Py_BuildValue("(ssNs)", op_name, name ? name : "", ins,
                                 attrs_json ? attrs_json : "");
  PyObject* res = CallDriver("sym_create", args);
  Py_DECREF(args);
  if (!res) return -1;
  *out = res;
  return 0;
}

int MXTrSymbolFree(void* sym) {
  if (!sym || !Py_IsInitialized()) return 0;  // after MXTrShutdown: gone
  Gil gil;
  Py_DECREF(static_cast<PyObject*>(sym));
  return 0;
}

int MXTrSimpleBind(void* sym, const char* shapes_json, void** out_exec) {
  Gil gil;
  PyObject* s = static_cast<PyObject*>(sym);
  Py_INCREF(s);
  PyObject* args = Py_BuildValue("(Ns)", s, shapes_json);
  PyObject* res = CallDriver("simple_bind", args);
  Py_DECREF(args);
  if (!res) return -1;
  *out_exec = res;
  return 0;
}

int MXTrExecutorFree(void* exec) { return MXTrSymbolFree(exec); }

int MXTrExecutorListArguments(void* exec, unsigned* num, char** names_blob) {
  Gil gil;
  PyObject* args = PyTuple_New(0);
  PyObject* res = CallMethod(exec, "list_arguments", args);
  Py_DECREF(args);
  if (!res) return -1;
  std::string blob;
  unsigned n = static_cast<unsigned>(PyList_Size(res));
  for (unsigned i = 0; i < n; ++i) {
    blob += PyUnicode_AsUTF8(PyList_GetItem(res, i));
    blob.push_back('\0');
  }
  Py_DECREF(res);
  char* out = static_cast<char*>(std::malloc(blob.size()));
  std::memcpy(out, blob.data(), blob.size());
  *names_blob = out;
  *num = n;
  return 0;
}

static int ShapeSize(void* exec, const char* method, PyObject* key,
                     unsigned* size) {
  PyObject* args = PyTuple_Pack(1, key);
  PyObject* res = CallMethod(exec, method, args);
  Py_DECREF(args);
  if (!res) return -1;
  unsigned long total = 1;
  for (Py_ssize_t i = 0; i < PyList_Size(res); ++i)
    total *= PyLong_AsUnsignedLong(PyList_GetItem(res, i));
  Py_DECREF(res);
  *size = static_cast<unsigned>(total);
  return 0;
}

int MXTrExecutorArgSize(void* exec, const char* name, unsigned* size) {
  Gil gil;
  PyObject* key = PyUnicode_FromString(name);
  int rc = ShapeSize(exec, "arg_shape", key, size);
  Py_DECREF(key);
  return rc;
}

int MXTrExecutorOutputSize(void* exec, unsigned index, unsigned* size) {
  Gil gil;
  PyObject* key = PyLong_FromUnsignedLong(index);
  int rc = ShapeSize(exec, "output_shape", key, size);
  Py_DECREF(key);
  return rc;
}

int MXTrExecutorSetArg(void* exec, const char* name, const float* data,
                       unsigned size) {
  Gil gil;
  PyObject* buf = PyBytes_FromStringAndSize(
      reinterpret_cast<const char*>(data), size * sizeof(float));
  PyObject* args = Py_BuildValue("(sN)", name, buf);
  PyObject* res = CallMethod(exec, "set_arg", args);
  Py_DECREF(args);
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

static int GetBuffer(void* exec, const char* method, PyObject* key,
                     float* data, unsigned size) {
  PyObject* args = PyTuple_Pack(1, key);
  PyObject* res = CallMethod(exec, method, args);
  Py_DECREF(args);
  if (!res) return -1;
  int rc = BytesToFloats(res, data, size);
  Py_DECREF(res);
  return rc;
}

int MXTrExecutorGetArg(void* exec, const char* name, float* data,
                       unsigned size) {
  Gil gil;
  PyObject* key = PyUnicode_FromString(name);
  int rc = GetBuffer(exec, "get_arg", key, data, size);
  Py_DECREF(key);
  return rc;
}

int MXTrExecutorGetGrad(void* exec, const char* name, float* data,
                        unsigned size) {
  Gil gil;
  PyObject* key = PyUnicode_FromString(name);
  int rc = GetBuffer(exec, "get_grad", key, data, size);
  Py_DECREF(key);
  return rc;
}

int MXTrExecutorGetOutput(void* exec, unsigned index, float* data,
                          unsigned size) {
  Gil gil;
  PyObject* key = PyLong_FromUnsignedLong(index);
  int rc = GetBuffer(exec, "get_output", key, data, size);
  Py_DECREF(key);
  return rc;
}

int MXTrExecutorForward(void* exec, int is_train) {
  Gil gil;
  PyObject* args = Py_BuildValue("(i)", is_train);
  PyObject* res = CallMethod(exec, "forward", args);
  Py_DECREF(args);
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int MXTrExecutorBackward(void* exec) {
  Gil gil;
  PyObject* args = PyTuple_New(0);
  PyObject* res = CallMethod(exec, "backward", args);
  Py_DECREF(args);
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int MXTrOptimizerCreate(const char* type, const char* params_json, void** out) {
  if (!TrEnsurePython()) return -1;
  Gil gil;
  PyObject* args = Py_BuildValue("(ss)", type, params_json ? params_json : "");
  PyObject* res = CallDriver("optimizer_create", args);
  Py_DECREF(args);
  if (!res) return -1;
  *out = res;
  return 0;
}

int MXTrOptimizerFree(void* opt) { return MXTrSymbolFree(opt); }

int MXTrOptimizerUpdate(void* opt, void* exec, const char* arg_name,
                        int index) {
  Gil gil;
  PyObject* e = static_cast<PyObject*>(exec);
  Py_INCREF(e);
  PyObject* args = Py_BuildValue("(Nsi)", e, arg_name, index);
  PyObject* res = CallMethod(opt, "update", args);
  Py_DECREF(args);
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

void MXTrBufFree(char* buf) { std::free(buf); }

int MXTrShutdown() {
  // exit() runs the runtime's static destructors while the interpreter this
  // library started still has threads alive: a host that returned from main
  // without this call segfaulted there now and then (2 of 10 runs on a
  // loaded machine), after its work was done. Finalizing first is what
  // `python` itself does. An interpreter the host started is the host's.
  if (!g_tr_owns_python || !Py_IsInitialized()) return 0;
  g_tr_owns_python = false;
  PyGILState_Ensure();
  return Py_FinalizeEx() < 0 ? -1 : 0;
}

}  // extern "C"
