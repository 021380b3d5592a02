"""ctypes bindings for the native host-ops library.

The JavaCPP-preset analog (SURVEY N10): a thin binding layer over a flat C
ABI (``src/host_ops.cpp``). The library is built on first use with ``make``
(g++) from the committed sources — no binary is committed or shipped, and
a binary the build could not (re)produce is never loaded. Every function
has a pure-numpy fallback so the package works without a toolchain —
``is_native()`` reports which path is live.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_log = logging.getLogger(__name__)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build(target: str) -> str:
    """(Re)build one Makefile target from the sources on disk and return
    its path. Always invokes make: a no-op when fresh, a rebuild after
    source edits. Raises when the build fails, even if an old binary is
    lying around — a binary that does not come from the sources on disk
    must not decide what runs."""
    subprocess.run(["make", "-C", _DIR, target], check=True,
                   capture_output=True, timeout=120)
    return os.path.join(_DIR, target)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            lib = ctypes.CDLL(_build("libdl4jtpu_host.so"))
        except (OSError, subprocess.SubprocessError) as e:
            _log.warning("native host-ops library not built (%r); using "
                         "the numpy fallback", e)
            _build_failed = True
            return None
        lib.threshold_encode.restype = ctypes.c_int64
        lib.threshold_encode.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
        lib.threshold_decode.restype = ctypes.c_int64
        lib.threshold_decode.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_float,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
        lib.csv_count.restype = ctypes.c_int64
        lib.csv_count.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                  ctypes.c_int64,
                                  ctypes.POINTER(ctypes.c_int64)]
        lib.csv_parse.restype = ctypes.c_int64
        lib.csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                  ctypes.c_int64,
                                  ctypes.POINTER(ctypes.c_float),
                                  ctypes.c_int64, ctypes.c_int64]
        lib.shuffle_indices.restype = None
        lib.shuffle_indices.argtypes = [ctypes.POINTER(ctypes.c_int64),
                                        ctypes.c_int64, ctypes.c_uint64]
        _lib = lib
        return _lib


def is_native() -> bool:
    """True when the C++ library is loaded (vs numpy fallback)."""
    return _load() is not None


# -------------------------------------------------------------- threshold
def threshold_encode_host(residual: np.ndarray, threshold: float,
                          capacity: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side codec: returns (encoded int32 (capacity+1,), new residual).

    The residual passed in is NOT mutated (a copy is updated), matching the
    jax codec's functional signature.
    """
    res = np.ascontiguousarray(residual, dtype=np.float32).copy()
    flat = res.reshape(-1)
    out = np.zeros(capacity + 1, dtype=np.int32)
    lib = _load()
    if lib is not None:
        lib.threshold_encode(
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            flat.size, ctypes.c_float(threshold),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), capacity)
        return out, res
    # numpy fallback
    hit = np.nonzero(np.abs(flat) >= threshold)[0][:capacity]
    sign = np.sign(flat[hit])
    out[0] = len(hit)
    out[1:1 + len(hit)] = ((hit + 1) * sign).astype(np.int32)
    flat[hit] -= sign.astype(np.float32) * threshold
    return out, res


def threshold_decode_host(encoded: np.ndarray, threshold: float,
                          target: np.ndarray) -> np.ndarray:
    """Accumulate the decoded update into a copy of ``target``."""
    tgt = np.ascontiguousarray(target, dtype=np.float32).copy()
    flat = tgt.reshape(-1)
    enc = np.ascontiguousarray(encoded, dtype=np.int32)
    lib = _load()
    if lib is not None:
        lib.threshold_decode(
            enc.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_float(threshold),
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), flat.size)
        return tgt
    n = enc[0]
    entries = enc[1:1 + n]
    entries = entries[entries != 0]
    idx = np.abs(entries) - 1
    np.add.at(flat, idx, np.sign(entries).astype(np.float32) * threshold)
    return tgt


# ------------------------------------------------------------------- csv
def csv_read_floats(path: str, delimiter: str = ",",
                    skip_rows: int = 0) -> np.ndarray:
    """Parse a numeric CSV file into a (rows, cols) float32 array; fields
    that fail to parse are NaN. Native fast path with numpy fallback."""
    lib = _load()
    if lib is not None:
        cols = ctypes.c_int64(0)
        rows = lib.csv_count(path.encode(), delimiter.encode(), skip_rows,
                             ctypes.byref(cols))
        if rows < 0:
            raise FileNotFoundError(path)
        out = np.empty((rows, cols.value), dtype=np.float32)
        got = lib.csv_parse(path.encode(), delimiter.encode(), skip_rows,
                            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                            rows, cols.value)
        return out[:got]
    # fallback — skip_rows counts non-blank rows, like the native path
    rows = []
    seen = 0
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            seen += 1
            if seen <= skip_rows:
                continue
            vals = []
            for tok in line.rstrip("\n").split(delimiter):
                try:
                    vals.append(float(tok))
                except ValueError:
                    vals.append(float("nan"))
            rows.append(vals)
    width = max((len(r) for r in rows), default=0)
    out = np.full((len(rows), width), np.nan, dtype=np.float32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def shuffle_indices(n: int, seed: int = 0) -> np.ndarray:
    """Native Fisher-Yates permutation of [0, n)."""
    idx = np.arange(n, dtype=np.int64)
    lib = _load()
    if lib is not None:
        lib.shuffle_indices(idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                            n, ctypes.c_uint64(seed))
        return idx
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    rng.shuffle(idx)
    return idx
