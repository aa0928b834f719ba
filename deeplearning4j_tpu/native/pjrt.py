"""ctypes wrapper for the tnd PJRT C-API smoke surface (native/tnd_pjrt.cpp).

Reference analog: the JavaCPP ``Nd4jCuda`` bindings that let libnd4j own the
accelerator without the JVM in the hot path (SURVEY §2.1 N13). Here the
accelerator ABI is PJRT: this module builds the C++ surface lazily (g++ +
the ``pjrt_c_api.h`` header shipped inside the tensorflow wheel) and drives
a real PJRT plugin (``libtpu.so``) from C — version negotiation, client,
device enumeration, H2D/D2H, compile+execute — with Python only
orchestrating the smoke test.

The production compute path stays on JAX's in-process PJRT client (see the
README native-boundary memo): re-implementing NDArray over raw PJRT buffers
would duplicate jax.Array without its fusion/sharding machinery. This
surface exists to prove the C ABI route works for deployment scenarios that
need it.
"""

from __future__ import annotations

import ctypes
import glob
import os
import threading
from typing import Optional

import numpy as np

from . import _build

_LIB: Optional[ctypes.CDLL] = None
_LOCK = threading.Lock()
_BUILD_FAILED = False


def _tf_include_dir() -> Optional[str]:
    """The tensorflow wheel ships xla/pjrt/c/pjrt_c_api.h; no TF libs are
    linked — the header alone defines the C ABI."""
    try:
        import tensorflow as tf  # noqa: F401  (heavy; only for the path)

        inc = os.path.join(os.path.dirname(tf.__file__), "include")
    except Exception:
        hits = glob.glob("/opt/venv/lib/python*/site-packages/tensorflow/include")
        inc = hits[0] if hits else None
    if inc and os.path.exists(os.path.join(inc, "xla", "pjrt", "c", "pjrt_c_api.h")):
        return inc
    return None


def default_plugin_path() -> Optional[str]:
    """Locate a PJRT plugin .so: libtpu from its wheel, else $PJRT_PLUGIN."""
    env = os.environ.get("PJRT_PLUGIN")
    if env and os.path.exists(env):
        return env
    try:
        import libtpu
    except ImportError:  # no TPU wheel on this host: caller falls back
        return None
    mod_file = getattr(libtpu, "__file__", None)
    if mod_file is None:  # namespace-package remnant of a broken uninstall
        return None
    path = os.path.join(os.path.dirname(mod_file), "libtpu.so")
    if os.path.exists(path):
        return path
    return None


def _lib_path() -> Optional[str]:
    """The smoke surface built from the current ``tnd_pjrt.cpp`` (name keyed
    to its content hash — see ``_build``), compiling on first use."""
    def command(out):
        inc = _tf_include_dir()
        if inc is None:
            return None
        return ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-I", inc,
                os.path.join(_build.SRC_DIR, "tnd_pjrt.cpp"), "-o", out,
                "-ldl"]

    return _build.build_or_reuse("libtnd_pjrt", ("tnd_pjrt.cpp",), command)


def get_lib() -> Optional[ctypes.CDLL]:
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
        lib.tnd_pjrt_open.restype = ctypes.c_int
        lib.tnd_pjrt_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int]
        lib.tnd_pjrt_api_version.restype = ctypes.c_int
        lib.tnd_pjrt_api_version.argtypes = [ctypes.POINTER(ctypes.c_int)] * 2
        lib.tnd_pjrt_client_create.restype = ctypes.c_int
        lib.tnd_pjrt_client_create.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.tnd_pjrt_platform_name.restype = ctypes.c_int
        lib.tnd_pjrt_platform_name.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.tnd_pjrt_device_count.restype = ctypes.c_int
        lib.tnd_pjrt_device_count.argtypes = [ctypes.c_int]
        FP = ctypes.POINTER(ctypes.c_float)
        lib.tnd_pjrt_roundtrip.restype = ctypes.c_int
        lib.tnd_pjrt_roundtrip.argtypes = [FP, FP, ctypes.c_longlong,
                                           ctypes.c_char_p, ctypes.c_int]
        lib.tnd_pjrt_execute_add.restype = ctypes.c_int
        lib.tnd_pjrt_execute_add.argtypes = [FP, FP, FP, ctypes.c_longlong,
                                             ctypes.c_char_p, ctypes.c_int]
        lib.tnd_pjrt_close.restype = None
        _LIB = lib
        return _LIB


def buildable() -> bool:
    """True when the smoke surface can be (or was) built on this machine."""
    return get_lib() is not None


class PjrtSmokeError(RuntimeError):
    pass


class PjrtSmoke:
    """Thin session over the C surface. One plugin per process (libtpu does
    not support re-initialization)."""

    def __init__(self, plugin_path: Optional[str] = None):
        self.lib = get_lib()
        if self.lib is None:
            raise PjrtSmokeError("tnd_pjrt unavailable (g++ or pjrt_c_api.h missing)")
        self.plugin_path = plugin_path or default_plugin_path()
        if self.plugin_path is None:
            raise PjrtSmokeError("no PJRT plugin found (set $PJRT_PLUGIN)")
        self._err = ctypes.create_string_buffer(2048)

    def _raise(self, tag: str):
        raise PjrtSmokeError(f"{tag}: {self._err.value.decode(errors='replace')}")

    def open(self) -> "PjrtSmoke":
        if self.lib.tnd_pjrt_open(self.plugin_path.encode(), self._err, 2048):
            self._raise("open")
        return self

    def api_version(self):
        major, minor = ctypes.c_int(), ctypes.c_int()
        if self.lib.tnd_pjrt_api_version(ctypes.byref(major), ctypes.byref(minor)):
            raise PjrtSmokeError("api_version before open")
        return major.value, minor.value

    def create_client(self):
        if self.lib.tnd_pjrt_client_create(self._err, 2048):
            self._raise("client_create")

    def platform_name(self) -> str:
        buf = ctypes.create_string_buffer(256)
        if self.lib.tnd_pjrt_platform_name(buf, 256):
            raise PjrtSmokeError("platform_name failed")
        return buf.value.decode()

    def device_count(self, addressable_only: bool = True) -> int:
        n = self.lib.tnd_pjrt_device_count(1 if addressable_only else 0)
        if n < 0:
            raise PjrtSmokeError("device_count failed")
        return n

    def roundtrip(self, arr: np.ndarray) -> np.ndarray:
        flat = np.ascontiguousarray(arr, np.float32).reshape(-1)
        out = np.empty_like(flat)
        FP = ctypes.POINTER(ctypes.c_float)
        if self.lib.tnd_pjrt_roundtrip(flat.ctypes.data_as(FP),
                                       out.ctypes.data_as(FP), flat.size,
                                       self._err, 2048):
            self._raise("roundtrip")
        return out.reshape(arr.shape)

    def execute_add(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        fa = np.ascontiguousarray(a, np.float32).reshape(-1)
        fb = np.ascontiguousarray(b, np.float32).reshape(-1)
        out = np.empty_like(fa)
        FP = ctypes.POINTER(ctypes.c_float)
        if self.lib.tnd_pjrt_execute_add(fa.ctypes.data_as(FP), fb.ctypes.data_as(FP),
                                         out.ctypes.data_as(FP), fa.size,
                                         self._err, 2048):
            self._raise("execute_add")
        return out.reshape(a.shape)

    def close(self):
        self.lib.tnd_pjrt_close()
