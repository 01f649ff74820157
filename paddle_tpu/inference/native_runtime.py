"""ctypes binding to the native PJRT serving runtime
(native/predictor_capi.cpp).

This is the same no-Python C API a C/Go client would link against —
bound here for tests and for Python users who want the native path
(reference analog: inference/capi consumed from Python in
capi_tester).  The heavy lifting (PJRT client, compile, execute) all
happens inside the native library; Python only marshals numpy buffers.
"""
from __future__ import annotations

import ctypes
import os
from typing import Dict, List, Optional

import numpy as np

from .export import DTYPE_CODES as _NP_TO_DTYPE  # single source of truth

PD_MAX_RANK = 8

_DTYPE_TO_NP = {v: k for k, v in _NP_TO_DTYPE.items()}


class _PDNativeTensor(ctypes.Structure):
    _fields_ = [
        ("dtype", ctypes.c_int32),
        ("ndim", ctypes.c_int32),
        ("dims", ctypes.c_int64 * PD_MAX_RANK),
        ("data", ctypes.c_void_p),
        ("nbytes", ctypes.c_size_t),
    ]


def _load_lib():
    from ..native.build import load_library

    lib = load_library("predictor_capi")
    lib.PD_NativePredictorCreate.restype = ctypes.c_void_p
    lib.PD_NativePredictorCreate.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                             ctypes.c_char_p]
    lib.PD_NativePredictorNumInputs.argtypes = [ctypes.c_void_p]
    lib.PD_NativePredictorNumOutputs.argtypes = [ctypes.c_void_p]
    lib.PD_NativePredictorInputName.restype = ctypes.c_char_p
    lib.PD_NativePredictorInputName.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.PD_NativePredictorOutputName.restype = ctypes.c_char_p
    lib.PD_NativePredictorOutputName.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.PD_NativePredictorRun.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(_PDNativeTensor), ctypes.c_int,
        ctypes.POINTER(_PDNativeTensor), ctypes.c_int,
    ]
    lib.PD_NativeTensorFree.argtypes = [ctypes.POINTER(_PDNativeTensor)]
    lib.PD_NativePredictorDestroy.argtypes = [ctypes.c_void_p]
    lib.PD_NativeLastError.restype = ctypes.c_char_p
    return lib


def default_plugin_path() -> Optional[str]:
    """libtpu.so from the installed libtpu wheel, if present."""
    env = os.environ.get("PD_PJRT_PLUGIN")
    if env:
        return env
    try:
        import importlib.util

        spec = importlib.util.find_spec("libtpu")
        if spec and spec.submodule_search_locations:
            cand = os.path.join(spec.submodule_search_locations[0],
                                "libtpu.so")
            if os.path.exists(cand):
                return cand
    except Exception:
        pass
    return None


def _encode_options(options: Dict[str, object]) -> bytes:
    lines = []
    for k, v in options.items():
        if isinstance(v, (int, np.integer)):
            lines.append(f"{k} int {int(v)}")
        else:
            lines.append(f"{k} str {v}")
    return "\n".join(lines).encode()


class NativePredictor:
    """Python face of the C API (PD_NativePredictor*)."""

    def __init__(self, export_dir: str, plugin_path: Optional[str] = None,
                 options: Optional[Dict[str, object]] = None):
        self._lib = _load_lib()
        plugin_path = plugin_path or default_plugin_path()
        if plugin_path is None:
            raise RuntimeError(
                "no PJRT plugin found; set PD_PJRT_PLUGIN to a PJRT C-API "
                ".so (e.g. libtpu.so)")
        # libtpu on a TPU VM needs no create-options
        self._handle = self._lib.PD_NativePredictorCreate(
            export_dir.encode(), plugin_path.encode(),
            _encode_options(options or {}))
        if not self._handle:
            raise RuntimeError(
                "PD_NativePredictorCreate failed: "
                + self._lib.PD_NativeLastError().decode())

    def input_names(self) -> List[str]:
        n = self._lib.PD_NativePredictorNumInputs(self._handle)
        return [self._lib.PD_NativePredictorInputName(self._handle, i).decode()
                for i in range(n)]

    def output_names(self) -> List[str]:
        n = self._lib.PD_NativePredictorNumOutputs(self._handle)
        return [
            self._lib.PD_NativePredictorOutputName(self._handle, i).decode()
            for i in range(n)]

    def run(self, feed: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        names = self.input_names()
        ins = (_PDNativeTensor * len(names))()
        keepalive = []
        for i, name in enumerate(names):
            arr = np.ascontiguousarray(feed[name])
            keepalive.append(arr)
            t = ins[i]
            t.dtype = _NP_TO_DTYPE[str(arr.dtype)]
            t.ndim = arr.ndim
            for d in range(arr.ndim):
                t.dims[d] = arr.shape[d]
            t.data = arr.ctypes.data_as(ctypes.c_void_p)
            t.nbytes = arr.nbytes
        n_out = self._lib.PD_NativePredictorNumOutputs(self._handle)
        outs = (_PDNativeTensor * max(n_out, 1))()
        got = self._lib.PD_NativePredictorRun(
            self._handle, ins, len(names), outs, n_out)
        if got < 0:
            raise RuntimeError("PD_NativePredictorRun failed: "
                               + self._lib.PD_NativeLastError().decode())
        out_names = self.output_names()
        result = {}
        for i in range(got):
            t = outs[i]
            shape = tuple(t.dims[d] for d in range(t.ndim))
            npdt = _DTYPE_TO_NP[t.dtype]
            if npdt == "bfloat16":
                import jax.numpy as jnp

                raw = ctypes.string_at(t.data, t.nbytes)
                arr = np.frombuffer(raw, np.uint16).reshape(shape)
                arr = arr.view(jnp.bfloat16).copy()
            else:
                raw = ctypes.string_at(t.data, t.nbytes)
                arr = np.frombuffer(raw, npdt).reshape(shape).copy()
            result[out_names[i] if i < len(out_names) else f"out_{i}"] = arr
            self._lib.PD_NativeTensorFree(ctypes.byref(t))
        return result

    def __del__(self):
        try:
            if getattr(self, "_handle", None):
                self._lib.PD_NativePredictorDestroy(self._handle)
                self._handle = None
        except Exception:
            pass
