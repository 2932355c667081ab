"""Native (C++) runtime pieces + the load/build bridge.

Role parity: tfplus's custom-op scaffold (``tfplus/tfplus/cc/demo.{h,cc}``,
``tfplus/tfplus/python/demo.py:10`` ``_load_library`` bridge) — but with
real kernels behind it: the shared-memory batch ring
(``native/src/shm_ring.cc``, the atorch ``shm_context`` data path) and
host-side batch-prep ops (``native/src/host_ops.cc``).

The library is built on demand with a plain ``g++`` invocation (no
pybind11 in this environment; the ABI is a C API consumed over ctypes).
``CMakeLists.txt`` provides the standalone build scaffold.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_BUILD_ERROR: Optional[str] = None

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_DIR = os.path.join(os.path.dirname(__file__), "lib")
_SOURCES = ("shm_ring.cc", "host_ops.cc")


def _source_hash(srcs) -> str:
    h = hashlib.sha256()
    for path in srcs:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:12]


def _build() -> str:
    """The library for THESE sources, built if absent. The file name
    carries a hash of ``src/*.cc``: ``lib/`` is git-ignored yet travels
    with a copied tree, and a copy does not preserve mtimes, so a
    timestamp cannot say whether a ``.so`` found there is current."""
    os.makedirs(_LIB_DIR, exist_ok=True)
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    lib_path = os.path.join(
        _LIB_DIR, f"libdlrover_tpu_native-{_source_hash(srcs)}.so")
    if os.path.exists(lib_path):
        return lib_path
    # compile to a private temp path, then atomically rename: a second
    # cold-starting process must never dlopen a half-written .so
    tmp_path = f"{lib_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-std=c++17", "-O3", "-shared", "-fPIC",
        "-Wall", "-Wextra",
        *srcs,
        "-o", tmp_path,
        "-lpthread", "-lrt",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp_path, lib_path)
    finally:
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)
    return lib_path


def load_library() -> ctypes.CDLL:
    """Build (if absent for these sources) and load the native library; raises RuntimeError
    with the compiler output when the toolchain is unavailable/broken."""
    global _LIB, _BUILD_ERROR
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        if _BUILD_ERROR is not None:
            raise RuntimeError(_BUILD_ERROR)
        try:
            path = _build()
            lib = ctypes.CDLL(path)
        except (subprocess.CalledProcessError, OSError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            _BUILD_ERROR = f"native library unavailable: {detail}"
            raise RuntimeError(_BUILD_ERROR) from e
        _declare_signatures(lib)
        _LIB = lib
        return lib


def native_available() -> bool:
    try:
        load_library()
        return True
    except RuntimeError:
        return False


def _declare_signatures(lib: ctypes.CDLL):
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.shm_ring_create.restype = ctypes.c_void_p
    lib.shm_ring_create.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64
    ]
    lib.shm_ring_attach.restype = ctypes.c_void_p
    lib.shm_ring_attach.argtypes = [ctypes.c_char_p]
    lib.shm_ring_push.restype = ctypes.c_int
    lib.shm_ring_push.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_uint64, ctypes.c_long
    ]
    lib.shm_ring_pop.restype = ctypes.c_long
    lib.shm_ring_pop.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_uint64, ctypes.c_long
    ]
    lib.shm_ring_size.restype = ctypes.c_long
    lib.shm_ring_size.argtypes = [ctypes.c_void_p]
    lib.shm_ring_slot_size.restype = ctypes.c_long
    lib.shm_ring_slot_size.argtypes = [ctypes.c_void_p]
    lib.shm_ring_close.restype = None
    lib.shm_ring_close.argtypes = [ctypes.c_void_p]
    lib.shm_ring_free.restype = None
    lib.shm_ring_free.argtypes = [ctypes.c_void_p]

    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.pack_sequences.restype = None
    lib.pack_sequences.argtypes = [
        i32p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        i32p, i32p,
    ]
    lib.shuffle_indices.restype = None
    lib.shuffle_indices.argtypes = [i64p, ctypes.c_int64, ctypes.c_uint64]
    lib.shift_labels.restype = None
    lib.shift_labels.argtypes = [
        i32p, i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, i32p,
    ]
