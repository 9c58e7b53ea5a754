"""Builds/loads the native C++ runtime core (``dl4j_tpu_native.cpp``).

The reference's host-side heavy lifting is native (libnd4j host ops, DataVec
readers); here the equivalent C++ library is compiled once with the system
toolchain and loaded via ctypes.  Everything degrades gracefully: if the
toolchain is unavailable the pure-Python fallbacks in the calling modules
take over, so the framework never hard-depends on the .so.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "src" / "dl4j_tpu_native.cpp"
_SO = _HERE / "_dl4j_tpu_native.so"
# sha256 of the source the .so was built from.  A copied tree keeps no
# meaningful mtimes, so freshness is decided by content: what loads was
# built from the source that sits beside it.
_SO_HASH = _HERE / "_dl4j_tpu_native.so.srchash"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

ABI_VERSION = 1


def _src_hash() -> str:
    return hashlib.sha256(_SRC.read_bytes()).hexdigest()


def _fresh() -> bool:
    try:
        return _SO.exists() and _SO_HASH.read_text().strip() == _src_hash()
    except OSError:
        return False


def _build() -> bool:
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        str(_SRC), "-o", str(_SO),
    ]
    _SO_HASH.unlink(missing_ok=True)
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return False
    if proc.returncode != 0 or not _SO.exists():
        return False
    _SO_HASH.write_text(_src_hash() + "\n")
    return True


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c_long = ctypes.c_long
    c_fp = ctypes.POINTER(ctypes.c_float)
    c_i64p = ctypes.POINTER(ctypes.c_int64)
    c_u8p = ctypes.POINTER(ctypes.c_uint8)

    lib.dl4j_native_abi_version.restype = ctypes.c_int

    lib.csv_dims.argtypes = [ctypes.c_char_p, c_long, ctypes.c_char, c_long,
                             ctypes.POINTER(c_long)]
    lib.csv_dims.restype = c_long
    lib.csv_parse.argtypes = [ctypes.c_char_p, c_long, ctypes.c_char, c_long,
                              c_fp, c_long, c_long, ctypes.c_int]
    lib.csv_parse.restype = c_long

    lib.idx_images.argtypes = [ctypes.c_char_p, c_long, c_fp, c_long,
                               ctypes.c_int]
    lib.idx_images.restype = c_long
    lib.idx_labels.argtypes = [ctypes.c_char_p, c_long, c_fp, c_long, c_long]
    lib.idx_labels.restype = c_long

    lib.gather_rows_f32.argtypes = [c_fp, c_long, c_i64p, c_long, c_fp,
                                    ctypes.c_int]
    lib.gather_rows_f32.restype = None

    lib.batcher_create.argtypes = [c_fp, c_fp, c_long, c_long, c_long, c_long,
                                   ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
                                   ctypes.c_int, ctypes.c_int]
    lib.batcher_create.restype = ctypes.c_void_p
    lib.batcher_next.argtypes = [ctypes.c_void_p, c_fp, c_fp,
                                 ctypes.POINTER(c_long)]
    lib.batcher_next.restype = ctypes.c_int
    lib.batcher_reset.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.batcher_reset.restype = None
    lib.batcher_destroy.argtypes = [ctypes.c_void_p]
    lib.batcher_destroy.restype = None

    lib.dataset_write.argtypes = [ctypes.c_char_p, c_fp, c_fp, c_long, c_long,
                                  c_long]
    lib.dataset_write.restype = c_long
    lib.dataset_read_header.argtypes = [ctypes.c_char_p, c_i64p, c_i64p, c_i64p]
    lib.dataset_read_header.restype = c_long
    lib.dataset_read.argtypes = [ctypes.c_char_p, c_fp, c_fp]
    lib.dataset_read.restype = c_long
    return lib


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first use; None if the
    toolchain is unavailable or the build fails."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("DL4J_TPU_DISABLE_NATIVE"):
            return None
        if not _fresh() and not _build():
            return None
        try:
            candidate = _bind(ctypes.CDLL(str(_SO)))
            stale = candidate.dl4j_native_abi_version() != ABI_VERSION
        except (OSError, AttributeError):
            stale = True  # unloadable or missing symbols: rebuild once
        if stale:
            _SO.unlink(missing_ok=True)
            if not _build():
                return None
            try:
                candidate = _bind(ctypes.CDLL(str(_SO)))
            except (OSError, AttributeError):
                return None
        _lib = candidate
        return _lib


def available() -> bool:
    return lib() is not None
