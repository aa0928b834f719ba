"""ctypes bindings for the tnd native host runtime (native/tnd.cpp).

Reference analog: the JavaCPP-generated ``Nd4jCpu`` bindings over libnd4j's
NativeOps C ABI (SURVEY §2.1 N13 / §2.2 J5). ctypes is the binding layer
(pybind11 is not in this image); calls release the GIL, so the parsers and
codecs run truly parallel to the training loop's Python thread.

The library lazily builds from source on first use (g++ is baked into the
image) and caches next to this file under a name keyed to the source's
content hash (``_build``), so a stale binary is never loaded; set
``TDL_NATIVE_DISABLE=1`` to force the numpy fallbacks in
``parallel.compression`` / ``data.records``.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional, Tuple

import numpy as np

from . import _build

_LIB: Optional[ctypes.CDLL] = None
_LOCK = threading.Lock()
_BUILD_FAILED = False

_SOURCES = ("tnd.cpp", "tnd.h")


def _lib_path() -> Optional[str]:
    return _build.build_or_reuse("libtnd", _SOURCES, lambda out: [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-I", _build.SRC_DIR, os.path.join(_build.SRC_DIR, "tnd.cpp"),
        "-o", out], timeout=120)


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _LIB, _BUILD_FAILED
    if _LIB is not None:
        return _LIB
    if _BUILD_FAILED or os.environ.get("TDL_NATIVE_DISABLE") == "1":
        return None
    with _LOCK:
        if _LIB is not None:
            return _LIB
        path = _lib_path()
        if path is None:
            _BUILD_FAILED = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _BUILD_FAILED = True
            return None
        lib.tnd_version.restype = ctypes.c_int64
        lib.tnd_threshold_encode.restype = ctypes.c_int64
        lib.tnd_threshold_encode.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
        lib.tnd_threshold_decode.restype = None
        lib.tnd_threshold_decode.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
        lib.tnd_threshold_encode_residual.restype = ctypes.c_int64
        lib.tnd_threshold_encode_residual.argtypes = lib.tnd_threshold_encode.argtypes
        lib.tnd_bitmap_encode.restype = None
        lib.tnd_bitmap_encode.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_uint8)]
        lib.tnd_bitmap_decode.restype = None
        lib.tnd_bitmap_decode.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_float)]
        lib.tnd_csv_parse_f32.restype = ctypes.c_int32
        lib.tnd_csv_parse_f32.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
        if lib.tnd_version() != 1:
            _BUILD_FAILED = True
            return None
        _LIB = lib
        return _LIB


def available() -> bool:
    return get_lib() is not None


# ------------------------------------------------------------ typed wrappers


def _fp(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _ip(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def threshold_encode(grad: np.ndarray, threshold: float) -> np.ndarray:
    lib = get_lib()
    flat = np.ascontiguousarray(grad, np.float32).reshape(-1)
    cap = max(16, flat.size // 8)
    while True:
        out = np.empty(cap, np.int64)
        n = lib.tnd_threshold_encode(_fp(flat), flat.size, threshold, _ip(out), cap)
        if n >= 0:
            return np.concatenate([[flat.size], out[:n]]).astype(np.int64)
        cap = -n


def threshold_decode(encoded: np.ndarray, threshold: float) -> np.ndarray:
    lib = get_lib()
    size = int(encoded[0])
    body = np.ascontiguousarray(encoded[1:], np.int64)
    out = np.zeros(size, np.float32)
    lib.tnd_threshold_decode(_ip(body), body.size, threshold, _fp(out), size)
    return out


def threshold_encode_residual(grad: np.ndarray, threshold: float) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (encoded_with_header, residual) — residual computed in-place
    natively in one pass."""
    lib = get_lib()
    flat = np.ascontiguousarray(grad, np.float32).reshape(-1).copy()
    cap = max(16, flat.size // 8)
    while True:
        out = np.empty(cap, np.int64)
        n = lib.tnd_threshold_encode_residual(_fp(flat), flat.size, threshold, _ip(out), cap)
        if n >= 0:
            enc = np.concatenate([[flat.size], out[:n]]).astype(np.int64)
            return enc, flat.reshape(np.shape(grad))
        cap = -n
        flat = np.ascontiguousarray(grad, np.float32).reshape(-1).copy()


def csv_parse(text_bytes: bytes, delimiter: str = ",", skip_rows: int = 0,
              max_vals: Optional[int] = None) -> Optional[np.ndarray]:
    """Parse numeric CSV bytes → float32 [rows, cols]; None on parse failure
    (caller falls back to the python csv module)."""
    lib = get_lib()
    if lib is None:
        return None
    cap = max_vals or max(1024, len(text_bytes) // 2)
    out = np.empty(cap, np.float32)
    rows = ctypes.c_int64(0)
    cols = ctypes.c_int64(0)
    rc = lib.tnd_csv_parse_f32(text_bytes, len(text_bytes),
                               delimiter.encode()[0:1], skip_rows,
                               _fp(out), cap, ctypes.byref(rows), ctypes.byref(cols))
    if rc == -2:
        return csv_parse(text_bytes, delimiter, skip_rows, cap * 4)
    if rc != 0:
        return None
    r, c = rows.value, cols.value
    return out[: r * c].reshape(r, c).copy()
