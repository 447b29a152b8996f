"""ctypes bindings for the native data-loader kernels (dtt_native.cpp).

Build-on-first-import: compiles ``dtt_native.cpp`` with g++ into a
shared library cached beside the source (keyed on a hash of the source
and the compiler flags, so edits rebuild automatically). Everything degrades gracefully — if no
compiler is present or the build fails, ``available()`` is False and
callers (data/datasets.py) fall back to NumPy. Both entry points are
**bit-identical** across paths (gather: same fancy-index semantics;
fill_tokens: the NumPy path replays the native SplitMix64 stream) —
only speed differs, never data.

This is the framework's native runtime component for host-side IO: the
TPU analogue of torch's C++ DataLoader workers the reference trains
through (src/distributed_trainer.py:204-211). Device-side compute stays
in XLA/Pallas — host batch assembly is the part that belongs in C++.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading

import numpy as np

logger = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "dtt_native.cpp")
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False

DEFAULT_THREADS = int(os.environ.get("DTT_NATIVE_THREADS", "0"))  # 0=auto


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(__file__), "_build")
    os.makedirs(d, exist_ok=True)
    return d


# Portable flags only (no -march=native): the tree — build directory
# included — gets copied between machines, and a binary tuned to the
# builder's CPU must not be what another machine loads.
_CXX_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]


def _lib_path() -> str:
    # Keyed on source AND flags: a binary left behind by an older build
    # recipe never matches, so it is rebuilt rather than trusted.
    h = hashlib.sha256(" ".join(_CXX_FLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_build_dir(),
                        f"dtt_native_{h.hexdigest()[:16]}.so")


def _compile(path: str) -> None:
    cmd = ["g++", *_CXX_FLAGS, _SRC, "-o", path]
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=os.path.dirname(path))
    os.close(fd)  # g++ rewrites the (safely created) file in place
    try:
        subprocess.run(cmd[:-1] + [tmp], check=True,
                       capture_output=True)
        os.replace(tmp, path)  # atomic under concurrent builders
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> ctypes.CDLL | None:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("DTT_NATIVE_DISABLE"):
            return None
        try:
            path = _lib_path()
            if not os.path.exists(path):
                _compile(path)
            lib = ctypes.CDLL(path)
            i64, i32p = ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)
            lib.dtt_gather_rows.restype = ctypes.c_int
            lib.dtt_gather_rows.argtypes = [
                ctypes.c_char_p, i64, i64,
                ctypes.POINTER(ctypes.c_int64), i64,
                ctypes.c_char_p, ctypes.c_int]
            lib.dtt_fill_tokens.restype = None
            lib.dtt_fill_tokens.argtypes = [i64, i64, i32p, i64,
                                            ctypes.c_int]
            _LIB = lib
        except Exception as e:  # compiler missing, bad toolchain, ...
            logger.warning("native kernels unavailable (%s); "
                           "falling back to NumPy", e)
    return _LIB


def available() -> bool:
    return _load() is not None


def gather_rows(src: np.ndarray, indices: np.ndarray,
                n_threads: int = DEFAULT_THREADS) -> np.ndarray:
    """``src[indices]`` (row gather) — multithreaded when the native
    library is available, NumPy fancy-indexing otherwise. Exact-equal
    outputs either way, including NumPy's negative-index wrapping and
    its IndexError on out-of-range."""
    idx = np.ascontiguousarray(indices, dtype=np.int64)
    lib = _load()
    # Fall back for shapes the kernel doesn't cover: 0-d/non-row
    # sources, multi-dim index arrays, and non-contiguous sources
    # (copying a whole non-contiguous column would cost O(dataset) per
    # batch — NumPy gathers views without that).
    if (lib is None or src.ndim == 0 or idx.ndim != 1
            or not src.flags.c_contiguous):
        return src[idx]
    row_bytes = src.dtype.itemsize * int(
        np.prod(src.shape[1:], dtype=np.int64))
    if row_bytes == 0:
        return src[idx]
    n = src.shape[0]
    if idx.size and (idx.min() < -n or idx.max() >= n):
        raise IndexError(f"gather index out of range [-{n}, {n})")
    if idx.size and idx.min() < 0:  # NumPy wrap semantics
        idx = np.where(idx < 0, idx + n, idx)
    out = np.empty((len(idx),) + src.shape[1:], dtype=src.dtype)
    rc = lib.dtt_gather_rows(
        src.ctypes.data_as(ctypes.c_char_p), n, row_bytes,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(idx),
        out.ctypes.data_as(ctypes.c_char_p), n_threads)
    if rc != 0:
        raise IndexError(f"gather index out of range [-{n}, {n})")
    return out


_FILL_BLOCK = 4096  # must match dtt_native.cpp's block constant
_SM64_GAMMA = 0x9E3779B97F4A7C15
_SM64_M1 = 0xBF58476D1CE4E5B9
_SM64_M2 = 0x94D4A2CA9C8DE917
_FILL_STREAM = 0xD1342543DE82EF95


def _fill_tokens_numpy(seed: int, vocab: int, n: int) -> np.ndarray:
    """Vectorized uint64 NumPy reproduction of the native SplitMix64
    stream (dtt_native.cpp: dtt_fill_tokens) — *bit-identical* output.

    This matters on multi-host pods: every host builds the synthetic
    corpus locally and the data path assumes the copies are identical.
    If native build availability differed across hosts and the fallback
    drew a different stream, per-host corpora would silently diverge —
    so the fallback is exact, not merely "equally valid".

    Per 4096-token block ``b``: state ``s0 = seed ^ (STREAM * (b+1))``;
    draw ``i`` mixes ``s0 + (i+1) * GAMMA`` through the SplitMix64
    finalizer; token = mix % vocab. All modular uint64 — NumPy unsigned
    arithmetic wraps exactly like C.
    """
    n_blocks = (n + _FILL_BLOCK - 1) // _FILL_BLOCK
    seed_u = np.uint64(seed & 0xFFFFFFFFFFFFFFFF)
    b = np.arange(1, n_blocks + 1, dtype=np.uint64)
    s0 = seed_u ^ (np.uint64(_FILL_STREAM) * b)          # (n_blocks,)
    i = np.arange(1, _FILL_BLOCK + 1, dtype=np.uint64)
    z = s0[:, None] + i[None, :] * np.uint64(_SM64_GAMMA)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(_SM64_M1)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(_SM64_M2)
    z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(vocab)).astype(np.int32).reshape(-1)[:n]


def fill_tokens(seed: int, vocab: int, n: int,
                n_threads: int = DEFAULT_THREADS) -> np.ndarray:
    """n int32 tokens uniform in [0, vocab), deterministic in seed
    (thread-count independent). Native and NumPy paths produce
    bit-identical streams, so mixed-availability hosts agree."""
    out = np.empty(n, dtype=np.int32)
    lib = _load()
    if lib is None:
        return _fill_tokens_numpy(seed, vocab, n)
    lib.dtt_fill_tokens(
        seed, vocab, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, n_threads)
    return out
