"""paddle_tpu.core — native (C++) runtime components.

The reference keeps its runtime in C++ (pybind module ``core_avx``,
``pybind/pybind.cc:558``); here the XLA runtime owns kernels/streams/memory,
and this package holds the host-side native pieces that remain OUR runtime's
job rather than the compiler's:

- ``tcp_store.cc`` — rendezvous/barrier KV store
  (reference ``distributed/store/tcp_store.cc``);
- ``host_tracer.cc`` — nanosecond RecordEvent sink for the profiler
  (reference ``platform/profiler/host_tracer.cc``).

Sources live in ``native/`` and are compiled on demand with g++ into a
shared library loaded via ctypes (no pybind11 in this environment — the
C-ABI + ctypes route is the binding layer, reference L5). The library is
named after a hash of its sources, so what gets loaded was built from the
sources git carries: a ``_build/`` copied over from another checkout or
machine (it is git-ignored) is never trusted on its modification time.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
import warnings

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_HERE, "native")
_BUILD_DIR = os.path.join(_HERE, "_build")
_SOURCES = ("tcp_store.cc", "host_tracer.cc")

_lock = threading.Lock()
_lib = None
_load_error = None


def _lib_path():
    digest = hashlib.sha1()
    for s in _SOURCES:
        with open(os.path.join(_SRC_DIR, s), "rb") as f:
            digest.update(f.read())
    return os.path.join(
        _BUILD_DIR, f"libpaddle_tpu_core.{digest.hexdigest()[:12]}.so")


def _build(lib_path):
    os.makedirs(_BUILD_DIR, exist_ok=True)
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    tmp = lib_path + f".tmp{os.getpid()}"
    cmd = [
        "g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-o", tmp, *srcs,
    ]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, lib_path)  # atomic wrt concurrent builders
    for old in glob.glob(os.path.join(_BUILD_DIR, "libpaddle_tpu_core*.so")):
        if old != lib_path:
            os.remove(old)  # built from other sources


def _declare(lib):
    c = ctypes
    lib.pt_tcpstore_server_start.restype = c.c_void_p
    lib.pt_tcpstore_server_start.argtypes = [c.c_int]
    lib.pt_tcpstore_server_port.restype = c.c_int
    lib.pt_tcpstore_server_port.argtypes = [c.c_void_p]
    lib.pt_tcpstore_server_stop.argtypes = [c.c_void_p]
    lib.pt_tcpstore_connect.restype = c.c_void_p
    lib.pt_tcpstore_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pt_tcpstore_close.argtypes = [c.c_void_p]
    lib.pt_tcpstore_set.restype = c.c_int
    lib.pt_tcpstore_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
    lib.pt_tcpstore_get.restype = c.c_int
    lib.pt_tcpstore_get.argtypes = [
        c.c_void_p, c.c_char_p, c.c_char_p, c.c_int, c.c_int]
    lib.pt_tcpstore_add.restype = c.c_longlong
    lib.pt_tcpstore_add.argtypes = [
        c.c_void_p, c.c_char_p, c.c_longlong, c.POINTER(c.c_int)]
    lib.pt_tcpstore_wait.restype = c.c_int
    lib.pt_tcpstore_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.pt_tracer_start.restype = c.c_int
    lib.pt_tracer_start.argtypes = [c.c_longlong]
    lib.pt_tracer_record.restype = c.c_int
    lib.pt_tracer_record.argtypes = [c.c_char_p, c.c_longlong, c.c_longlong]
    lib.pt_tracer_now_ns.restype = c.c_longlong
    lib.pt_tracer_count.restype = c.c_longlong
    lib.pt_tracer_dump.restype = c.c_longlong
    lib.pt_tracer_dump.argtypes = [c.c_char_p, c.c_longlong]
    return lib


def load_native():
    """Build (if needed) and load the native library. When it cannot be
    built or loaded (no g++, a failing compile) this returns None, remembers
    the error (:func:`native_load_error`) and says so once: callers that
    have a pure-python path take it, but not silently."""
    global _lib, _load_error
    with _lock:
        if _lib is not None or _load_error is not None:
            return _lib
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path):
                _build(lib_path)
            _lib = _declare(ctypes.CDLL(lib_path))
        except (OSError, subprocess.CalledProcessError) as e:
            _load_error = e
            detail = getattr(e, "stderr", None) or e
            warnings.warn(
                f"paddle_tpu native core library unavailable, using the "
                f"pure-python paths: {detail}", RuntimeWarning, stacklevel=2)
        return _lib


def native_load_error():
    return _load_error


from .tcp_store import TCPStore  # noqa: E402,F401
