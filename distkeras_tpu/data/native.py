"""ctypes binding for the native batch assembler (native_src/batcher.cc).

The one thing the program compiles for itself. Built on first use with
g++ from the tracked source ONLY, into ``native_src/libdkbatch-<hash>.so``
where ``<hash>`` is the SHA-256 of that source — so a binary built from
another revision (or dropped in from elsewhere) has another name and can
never load. Without a toolchain every entry point takes the NumPy path
(:func:`available` says which is live); with one, a failed build raises
with the compiler's message instead of silently degrading. The native
path is a throughput optimization for the host side of the input
pipeline; results are identical either way.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

import numpy as np

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native_src", "batcher.cc")


def _build() -> Optional[str]:
    """Path of the library built from the tracked source (building it if
    this revision's binary is not there yet), or None with no g++."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(os.path.dirname(_SRC), f"libdkbatch-{digest}.so")
    if os.path.exists(so):
        return so
    if shutil.which("g++") is None:
        return None
    # build under a private name, then rename: a concurrent process never
    # loads a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC, "-lpthread"],
            check=True, capture_output=True, text=True, timeout=120)
        os.replace(tmp, so)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building {_SRC} failed (g++ exit {e.returncode}):\n"
            f"{e.stderr}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        so = _build()
        _TRIED = True
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        lib.dk_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32]
        lib.dk_gather_rows.restype = None
        lib.dk_permutation.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
        lib.dk_permutation.restype = None
        _LIB = lib
        return _LIB


def available() -> bool:
    """True when the native library is live, False on the NumPy path."""
    return _lib() is not None


def gather_rows(src: np.ndarray, idx: np.ndarray,
                num_threads: int = 0) -> np.ndarray:
    """out[i] = src[idx[i]] — native threaded memcpy gather with numpy
    fallback. src may have any row shape; idx is int64 [n]."""
    lib = _lib()
    idx = np.ascontiguousarray(idx, np.int64)
    src = np.asarray(src)
    if lib is None or src.dtype.hasobject:
        # object rows are PyObject pointers — memcpy without incref corrupts
        # the interpreter; those columns stay on the numpy path
        return src[idx]
    if idx.size and (idx.min() < 0 or idx.max() >= len(src)):
        raise IndexError(  # match the numpy fallback, don't memcpy OOB
            f"gather indices out of range [0, {len(src)}): "
            f"[{idx.min()}, {idx.max()}]")
    src = np.ascontiguousarray(src)
    n = len(idx)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    out = np.empty((n,) + src.shape[1:], src.dtype)
    if num_threads <= 0:
        num_threads = min(8, os.cpu_count() or 1)
    lib.dk_gather_rows(
        src.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        idx.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n), ctypes.c_int64(row_bytes),
        ctypes.c_int32(num_threads))
    return out


def permutation(n: int, seed: int) -> np.ndarray:
    """Deterministic Fisher-Yates permutation of [0, n); native xoshiro256**
    with numpy fallback (NOTE: the two paths draw different sequences — both
    deterministic by seed, but not bit-identical to each other)."""
    lib = _lib()
    if lib is None:
        return np.random.default_rng(seed).permutation(n).astype(np.int64)
    out = np.empty(n, np.int64)
    lib.dk_permutation(out.ctypes.data_as(ctypes.c_void_p),
                       ctypes.c_int64(n), ctypes.c_uint64(seed & (2**64 - 1)))
    return out
