"""Loader for the native C++ data plane (ctypes; no pybind11).

Builds native/libtpumpi_native.so with make on first use; make itself
decides what is stale against the tracked sources.  Every consumer has
a pure-Python fallback, so a missing compiler only costs performance,
never correctness — but it says so on stderr, once.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import sys
import threading
from typing import Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libtpumpi_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    try:
        # process-ranks of one job all reach here at once on a fresh
        # checkout: one builds, the rest find nothing to do
        with open(os.path.join(_NATIVE_DIR, "Makefile")) as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            r = subprocess.run(["make", "-C", _NATIVE_DIR, "-j2"],
                               capture_output=True, timeout=120)
        why = r.stderr.decode(errors="replace").strip()[-400:]
        ok = r.returncode == 0 and os.path.exists(_LIB_PATH)
    except (OSError, subprocess.TimeoutExpired) as e:
        ok, why = False, repr(e)
    if not ok:
        sys.stderr.write(
            f"ompi_tpu.native: build of {_LIB_PATH} failed ({why}); "
            f"using the pure-Python fallback\n")
    return ok


def load() -> Optional[ctypes.CDLL]:
    """The native library, or None (pure-Python fallback)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            sys.stderr.write(
                f"ompi_tpu.native: cannot load {_LIB_PATH} ({e}); "
                f"using the pure-Python fallback\n")
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.tpumpi_ring_push.argtypes = [u8p, ctypes.c_uint64, u8p,
                                         ctypes.c_uint64]
        lib.tpumpi_ring_push.restype = ctypes.c_int
        lib.tpumpi_ring_push2.argtypes = [u8p, ctypes.c_uint64, u8p,
                                          ctypes.c_uint64, u8p,
                                          ctypes.c_uint64]
        lib.tpumpi_ring_push2.restype = ctypes.c_int
        lib.tpumpi_ring_peek.argtypes = [u8p, ctypes.c_uint64]
        lib.tpumpi_ring_peek.restype = ctypes.c_int64
        lib.tpumpi_ring_pop.argtypes = [u8p, ctypes.c_uint64, u8p,
                                        ctypes.c_uint64]
        lib.tpumpi_ring_pop.restype = ctypes.c_int
        lib.tpumpi_ring_readable.argtypes = [u8p]
        lib.tpumpi_ring_readable.restype = ctypes.c_uint64
        lib.tpumpi_pack_strided.argtypes = [u8p, u8p, ctypes.c_uint64,
                                            ctypes.c_int64, ctypes.c_uint64]
        lib.tpumpi_pack_strided.restype = None
        lib.tpumpi_unpack_strided.argtypes = [u8p, u8p, ctypes.c_uint64,
                                              ctypes.c_int64,
                                              ctypes.c_uint64]
        lib.tpumpi_unpack_strided.restype = None
        lib.tpumpi_seg_coll.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int64]
        lib.tpumpi_seg_coll.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None
