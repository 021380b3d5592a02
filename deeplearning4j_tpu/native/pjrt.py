"""ctypes binding for the PJRT C-API shim (``src/pjrt_shim.cpp``).

The JavaCPP-preset-for-PJRT analog (SURVEY N5/N10): loads
``libdl4jtpu_pjrt.so`` (built on demand by the package Makefile), which in
turn dlopens any conforming PJRT plugin — ``libtpu.so`` for real TPU
hardware, or any other ``GetPjrtApi``-exporting library — and drives the
full compile/transfer/execute cycle on it from Python with zero Python-level
jax involvement. This is the path a non-Python frontend (the reference's
Java API) would bind against.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sysconfig
from typing import Optional, Sequence

import numpy as np

from deeplearning4j_tpu.native import _build

_ERRLEN = 4096


def default_tpu_plugin_path() -> Optional[str]:
    """Path of the bundled libtpu PJRT plugin, if installed."""
    p = os.path.join(sysconfig.get_paths()["purelib"], "libtpu", "libtpu.so")
    return p if os.path.exists(p) else None


def _lib() -> ctypes.CDLL:
    try:
        lib = ctypes.CDLL(_build("libdl4jtpu_pjrt.so"))
    except (OSError, subprocess.SubprocessError) as e:
        raise RuntimeError(
            "libdl4jtpu_pjrt.so not built (pjrt_c_api.h unavailable?)") from e
    lib.nd4j_pjrt_load_plugin.restype = ctypes.c_void_p
    lib.nd4j_pjrt_load_plugin.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                          ctypes.c_int]
    lib.nd4j_pjrt_api_version.restype = ctypes.c_int
    lib.nd4j_pjrt_api_version.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.nd4j_pjrt_client_create.restype = ctypes.c_void_p
    lib.nd4j_pjrt_client_create.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                            ctypes.c_int]
    lib.nd4j_pjrt_client_destroy.argtypes = [ctypes.c_void_p]
    lib.nd4j_pjrt_platform_name.restype = ctypes.c_int
    lib.nd4j_pjrt_platform_name.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                            ctypes.c_int]
    lib.nd4j_pjrt_device_count.restype = ctypes.c_int
    lib.nd4j_pjrt_device_count.argtypes = [ctypes.c_void_p]
    lib.nd4j_pjrt_compile.restype = ctypes.c_void_p
    lib.nd4j_pjrt_compile.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
        ctypes.c_int64, ctypes.c_char_p, ctypes.c_int]
    lib.nd4j_pjrt_executable_destroy.argtypes = [ctypes.c_void_p]
    lib.nd4j_pjrt_execute_f32.restype = ctypes.c_int
    return lib


def compile_options_bytes() -> bytes:
    """Serialized CompileOptionsProto for a 1-replica/1-partition program."""
    from jax._src.lib import xla_client
    return xla_client.CompileOptions().SerializeAsString()


class PjrtPlugin:
    """A loaded PJRT plugin (its PJRT_Api function table)."""

    def __init__(self, plugin_path: str):
        self._libshim = _lib()
        err = ctypes.create_string_buffer(_ERRLEN)
        self._api = self._libshim.nd4j_pjrt_load_plugin(
            plugin_path.encode(), err, _ERRLEN)
        if not self._api:
            raise RuntimeError(f"PJRT plugin load failed: "
                               f"{err.value.decode(errors='replace')}")
        self.plugin_path = plugin_path

    def api_version(self) -> tuple:
        major = ctypes.c_int()
        minor = ctypes.c_int()
        rc = self._libshim.nd4j_pjrt_api_version(
            self._api, ctypes.byref(major), ctypes.byref(minor))
        if rc != 0:
            raise RuntimeError("api_version failed")
        return major.value, minor.value

    def create_client(self) -> "PjrtClient":
        err = ctypes.create_string_buffer(_ERRLEN)
        client = self._libshim.nd4j_pjrt_client_create(self._api, err, _ERRLEN)
        if not client:
            raise RuntimeError(f"PJRT client create failed: "
                               f"{err.value.decode(errors='replace')}")
        return PjrtClient(self._libshim, client)


class PjrtClient:
    def __init__(self, libshim, client):
        self._libshim = libshim
        self._client = client

    def platform_name(self) -> str:
        buf = ctypes.create_string_buffer(256)
        n = self._libshim.nd4j_pjrt_platform_name(self._client, buf, 256)
        if n < 0:
            raise RuntimeError("platform_name failed")
        return buf.value.decode()

    def device_count(self) -> int:
        return self._libshim.nd4j_pjrt_device_count(self._client)

    def compile_mlir(self, mlir: str,
                     options: Optional[bytes] = None) -> "PjrtExecutable":
        """Compile a StableHLO module (text) into a loaded executable."""
        opts = options if options is not None else compile_options_bytes()
        err = ctypes.create_string_buffer(_ERRLEN)
        code = mlir.encode() if isinstance(mlir, str) else mlir
        exe = self._libshim.nd4j_pjrt_compile(
            self._client, code, len(code), opts, len(opts), err, _ERRLEN)
        if not exe:
            raise RuntimeError(f"PJRT compile failed: "
                               f"{err.value.decode(errors='replace')}")
        return PjrtExecutable(self._libshim, exe)

    def close(self):
        if self._client:
            self._libshim.nd4j_pjrt_client_destroy(self._client)
            self._client = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class PjrtExecutable:
    def __init__(self, libshim, exe):
        self._libshim = libshim
        self._exe = exe

    def execute(self, inputs: Sequence[np.ndarray],
                out_shapes: Sequence[tuple]) -> list:
        """Run on device 0: f32 dense inputs → f32 dense outputs."""
        ins = [np.ascontiguousarray(np.asarray(a, np.float32))
               for a in inputs]
        n_in = len(ins)
        in_data = (ctypes.POINTER(ctypes.c_float) * n_in)(
            *[a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) for a in ins])
        dims_arrays = [(ctypes.c_int64 * a.ndim)(*a.shape) for a in ins]
        in_dims = (ctypes.POINTER(ctypes.c_int64) * n_in)(*dims_arrays)
        in_ranks = (ctypes.c_int32 * n_in)(*[a.ndim for a in ins])

        outs = [np.empty(s, np.float32) for s in out_shapes]
        n_out = len(outs)
        out_data = (ctypes.POINTER(ctypes.c_float) * n_out)(
            *[o.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
              for o in outs])
        out_elems = (ctypes.c_int64 * n_out)(*[o.size for o in outs])
        err = ctypes.create_string_buffer(_ERRLEN)
        rc = self._libshim.nd4j_pjrt_execute_f32(
            self._exe, in_data, in_dims, in_ranks, n_in,
            out_data, out_elems, n_out, err, _ERRLEN)
        if rc != 0:
            raise RuntimeError(f"PJRT execute failed: "
                               f"{err.value.decode(errors='replace')}")
        return outs

    def close(self):
        if self._exe:
            self._libshim.nd4j_pjrt_executable_destroy(self._exe)
            self._exe = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
