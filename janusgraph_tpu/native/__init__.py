"""ctypes loader for the native host-runtime kernels (graphcsr.cpp).

Compiles the shared library on first use with g++ (cached next to the
source, keyed by a source hash) and exposes numpy-friendly wrappers. Every
entry point has a pure-numpy fallback, so the framework works without a
compiler; `available()` reports which path is active.

pybind11 is not in the image, so the boundary is plain C ABI + ctypes with
raw array pointers (no copies).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "graphcsr.cpp")

_lib = None
_tried = False
#: how the library came to be: "compiled" (g++ ran in this process),
#: "loaded" (a hash-named .so was already there) or "unavailable"
_status = "unavailable"
_lock = threading.Lock()


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        h = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_graphcsr_{h}.so")


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _status
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("JG_TPU_NO_NATIVE"):
            return None
        so = _so_path()
        status = "loaded"
        if not os.path.exists(so):
            status = "compiled"
            # unique tmp name: concurrent processes may compile at once;
            # os.replace makes whoever finishes last win atomically
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                # one-time g++ compile deliberately holds _lock: concurrent
                # callers should wait for the native library rather than
                # silently falling back to numpy for the whole process life
                # graphlint: disable=JG203 -- intentional: first-use compile gate; waiting beats losing the native path
                subprocess.run(
                    [
                        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                        "-pthread", "-o", tmp, _SRC,
                    ],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(tmp, so)
            except (OSError, subprocess.SubprocessError):
                if os.path.exists(tmp):
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        I64, I32, F32 = (
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        )

        class F32OrNull:
            """float32 C-contiguous ndpointer that also accepts None —
            keeps ctypes' dtype/contiguity validation for real arrays
            instead of a raw c_void_p passthrough."""

            @classmethod
            def from_param(cls, obj):
                if obj is None:
                    return None
                return F32.from_param(obj)

        lib.build_csr.argtypes = [
            ctypes.c_int64, ctypes.c_int64, I32, I32,
            I64, I32, I64, I64, I32, I64,
        ]
        lib.segment_ids.argtypes = [ctypes.c_int64, ctypes.c_int64, I64, I32]
        lib.ell_fill.argtypes = [
            ctypes.c_int64, ctypes.c_int64, I64, I64, I32,
            F32OrNull, I32, F32OrNull, F32OrNull,
        ]
        lib.rmat_edges.argtypes = [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64,
            ctypes.c_double, ctypes.c_double, ctypes.c_double, I32, I32,
        ]
        _lib = lib
        _status = status
        return _lib


def available() -> bool:
    return _load() is not None


def load_status() -> str:
    """"compiled", "loaded" or "unavailable" (the numpy fallbacks are in
    use) — for launchers that must not run on the fallback unnoticed."""
    _load()
    return _status


# ------------------------------------------------------------- entry points

def build_csr(n: int, src: np.ndarray, dst: np.ndarray):
    """Both CSR orientations + stable sort permutations.

    Returns (out_indptr, out_dst, out_perm, in_indptr, in_src, in_perm).
    """
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    m = len(src)
    lib = _load()
    if lib is not None:
        out_indptr = np.empty(n + 1, dtype=np.int64)
        out_dst = np.empty(m, dtype=np.int32)
        out_perm = np.empty(m, dtype=np.int64)
        in_indptr = np.empty(n + 1, dtype=np.int64)
        in_src = np.empty(m, dtype=np.int32)
        in_perm = np.empty(m, dtype=np.int64)
        lib.build_csr(
            n, m, src, dst,
            out_indptr, out_dst, out_perm, in_indptr, in_src, in_perm,
        )
        return out_indptr, out_dst, out_perm, in_indptr, in_src, in_perm
    # numpy fallback
    out_perm = np.argsort(src, kind="stable")
    in_perm = np.argsort(dst, kind="stable")
    out_indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(out_indptr, src.astype(np.int64) + 1, 1)
    np.cumsum(out_indptr, out=out_indptr)
    in_indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(in_indptr, dst.astype(np.int64) + 1, 1)
    np.cumsum(in_indptr, out=in_indptr)
    return (
        out_indptr, dst[out_perm], out_perm,
        in_indptr, src[in_perm], in_perm,
    )


def segment_ids(indptr: np.ndarray, m: int) -> np.ndarray:
    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    lib = _load()
    if lib is not None:
        seg = np.empty(m, dtype=np.int32)
        lib.segment_ids(len(indptr) - 1, m, indptr, seg)
        return seg
    return np.repeat(
        np.arange(len(indptr) - 1, dtype=np.int32), np.diff(indptr)
    )[:m]


def ell_fill(cap, starts, degs, sorted_src, sorted_w, idx, wmat, valid) -> bool:
    """Fill one ELL bucket in place (wmat/valid may be None for unweighted
    packs — the device kernel then relies on the sentinel slot alone).
    Returns False if native is unavailable (caller keeps its numpy path)."""
    lib = _load()
    if lib is None:
        return False
    rows = len(starts)
    lib.ell_fill(
        rows, cap,
        np.ascontiguousarray(starts, dtype=np.int64),
        np.ascontiguousarray(degs, dtype=np.int64),
        np.ascontiguousarray(sorted_src, dtype=np.int32),
        sorted_w, idx, wmat, valid,
    )
    return True


def rmat_edges(
    scale: int, m: int, seed: int, a: float = 0.57, b: float = 0.19, c: float = 0.19
):
    """Multi-threaded R-MAT edge synthesis; returns (src, dst) or None when
    native is unavailable."""
    lib = _load()
    if lib is None:
        return None
    src = np.empty(m, dtype=np.int32)
    dst = np.empty(m, dtype=np.int32)
    lib.rmat_edges(scale, m, seed & 0xFFFFFFFFFFFFFFFF, a, b, c, src, dst)
    return src, dst
