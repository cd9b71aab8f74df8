// Shared CPython-embedding plumbing for the C ABI libraries
// (c_predict.cc, c_api.cc): interpreter bring-up, bridge import,
// last-error capture.  Every entry point takes the GIL via
// PyGILState_Ensure around its bridge call.
#ifndef MXTPU_C_EMBED_H_
#define MXTPU_C_EMBED_H_

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <dlfcn.h>

#include <mutex>
#include <string>

namespace mxtpu {

inline thread_local std::string g_last_error;

inline PyObject*& BridgeModule() {
  static PyObject* mod = nullptr;
  return mod;
}

inline void InitPython(const char* bridge_name) {
  static std::once_flag flag;
  std::call_once(flag, [bridge_name]() {
    if (!Py_IsInitialized()) {
      // When this library is dlopen'd by a host runtime (Perl XS, JNI,
      // MATLAB loadlibrary) libpython arrives as a private dependency,
      // and Python's OWN extension modules (numpy, _datetime, ...)
      // later fail with undefined Py* symbols.  Promote libpython to
      // the global namespace first (RTLD_NOLOAD: it is already
      // loaded; this only flips visibility).
      Dl_info info;
      if (dladdr(reinterpret_cast<void*>(&Py_InitializeEx), &info) &&
          info.dli_fname != nullptr) {
        dlopen(info.dli_fname, RTLD_LAZY | RTLD_GLOBAL | RTLD_NOLOAD);
      }
      Py_InitializeEx(0);
      PyEval_SaveThread();   // release the GIL for arbitrary callers
    }
    PyGILState_STATE st = PyGILState_Ensure();
    // make the repo importable for embedded use: cwd + $MXTPU_HOME
    PyRun_SimpleString(
        "import sys, os\n"
        "for p in (os.getcwd(), os.environ.get('MXTPU_HOME', '')):\n"
        "    if p and p not in sys.path:\n"
        "        sys.path.insert(0, p)\n");
    // MXTPU_FORCE_CPU=1: run the embedded core on the XLA CPU backend
    // (CI / machines whose accelerator another process holds).
    PyRun_SimpleString(
        "import os\n"
        "if os.environ.get('MXTPU_FORCE_CPU'):\n"
        "    import jax\n"
        "    jax.config.update('jax_platforms', 'cpu')\n");
    BridgeModule() = PyImport_ImportModule(bridge_name);
    if (BridgeModule() == nullptr) PyErr_Print();
    PyGILState_Release(st);
  });
}

inline void CaptureError() {
  PyObject *type, *value, *tb;
  PyErr_Fetch(&type, &value, &tb);
  if (value != nullptr) {
    PyObject* s = PyObject_Str(value);
    g_last_error = s ? PyUnicode_AsUTF8(s) : "unknown python error";
    Py_XDECREF(s);
  } else {
    g_last_error = "unknown error";
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

// UTF-8 conversion with error capture: returns false (and sets
// g_last_error) instead of crashing on unencodable strings.
inline bool SafeUTF8(PyObject* obj, std::string* out) {
  const char* s = PyUnicode_AsUTF8(obj);
  if (s == nullptr) {
    CaptureError();
    return false;
  }
  *out = s;
  return true;
}

// (keys, indptr-encoded shapes) -> Python lists, shared by the predict
// and general ABIs.
inline PyObject* KeysToList(unsigned num, const char** keys) {
  PyObject* l = PyList_New(num);
  for (unsigned i = 0; i < num; ++i)
    PyList_SET_ITEM(l, i, PyUnicode_FromString(keys[i]));
  return l;
}

inline PyObject* ShapesToList(unsigned num, const unsigned* indptr,
                              const unsigned* data) {
  PyObject* shapes = PyList_New(num);
  for (unsigned i = 0; i < num; ++i) {
    unsigned lo = indptr[i], hi = indptr[i + 1];
    PyObject* s = PyList_New(hi - lo);
    for (unsigned j = lo; j < hi; ++j)
      PyList_SET_ITEM(s, j - lo, PyLong_FromUnsignedLong(data[j]));
    PyList_SET_ITEM(shapes, i, s);
  }
  return shapes;
}

// Calls bridge.<fn>(*args); steals the args reference; returns a new
// reference or nullptr with g_last_error set.
inline PyObject* CallBridge(const char* fn, PyObject* args) {
  if (BridgeModule() == nullptr) {
    g_last_error = "bridge module failed to import "
                   "(set MXTPU_HOME to the repo root)";
    Py_XDECREF(args);
    return nullptr;
  }
  PyObject* f = PyObject_GetAttrString(BridgeModule(), fn);
  if (f == nullptr) {
    CaptureError();
    Py_XDECREF(args);
    return nullptr;
  }
  PyObject* r = PyObject_CallObject(f, args);
  Py_DECREF(f);
  Py_XDECREF(args);
  if (r == nullptr) CaptureError();
  return r;
}

}  // namespace mxtpu

#endif  // MXTPU_C_EMBED_H_
