"""ctypes loader for the first-party native codec library.

Compiles ``_native/codec.cpp`` with g++ on first use (no pip deps, no
pybind11 — plain C ABI via ctypes).  Returns None if no toolchain is
available; callers fall back to the NumPy implementation of the identical
wire formats.
"""

from __future__ import annotations

import ctypes
import threading

_lock = threading.Lock()
_lib = None
_tried = False


def load():
    """The loaded ctypes library, or None if unavailable.

    A rebuild-needing (missing OR stale) library that fails to build
    yields None — the NumPy fallback, announced on stderr — never the
    stale binary."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        from ..utils._nativebuild import load_library
        lib = load_library("codec", "libdefercodec.so", "NumPy codec")
        if lib is None:
            return None
        c_i64, c_int = ctypes.c_int64, ctypes.c_int
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.bf_max_compressed_size.restype = c_i64
        lib.bf_max_compressed_size.argtypes = [c_i64, c_int]
        lib.bf_compress.restype = c_i64
        lib.bf_compress.argtypes = [f32p, c_i64, c_int, u8p]
        lib.bf_decompress.restype = c_i64
        lib.bf_decompress.argtypes = [u8p, c_i64, f32p]
        lib.bf_peek_count.restype = c_i64
        lib.bf_peek_count.argtypes = [u8p, c_i64]
        lib.lzb_max_compressed_size.restype = c_i64
        lib.lzb_max_compressed_size.argtypes = [c_i64]
        lib.lzb_compress.restype = c_i64
        lib.lzb_compress.argtypes = [u8p, c_i64, u8p]
        lib.lzb_decompressed_size.restype = c_i64
        lib.lzb_decompressed_size.argtypes = [u8p, c_i64]
        lib.lzb_decompress.restype = c_i64
        lib.lzb_decompress.argtypes = [u8p, c_i64, u8p, c_i64]
        _lib = lib
        return _lib
