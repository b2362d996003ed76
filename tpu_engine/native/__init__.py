"""Native runtime helpers: build + ctypes bindings for ``tpunative.cpp``.

The reference has zero first-party native code (SURVEY.md §2.2); this is the
TPU build's native surface — a mmap'd tokenized-dataset reader with threaded
gather and double-buffered prefetch, plus a /proc host-telemetry probe.

``ensure_built()`` compiles the shared library with g++ on first use. The
built file is named by a hash of ``tpunative.cpp``, so only a binary of the
tracked source is ever loaded: a stale ``_build/`` copied along with the
tree has another name and is never picked up. Every entry point has a
pure-NumPy stand-in, so the engine runs — slower — where no toolchain
exists; ``tpu_engine.data`` picks the fastest available and :func:`status`
says which, and why.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "tpunative.cpp")
_BUILD_DIR = os.path.join(_DIR, "_build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed: Optional[str] = None


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libtpunative-{digest}.so")


class _TnHostStats(ctypes.Structure):
    _fields_ = [
        ("mem_total_gb", ctypes.c_double),
        ("mem_available_gb", ctypes.c_double),
        ("load_1m", ctypes.c_double),
        ("load_5m", ctypes.c_double),
        ("n_cpus", ctypes.c_int64),
    ]


def ensure_built(force: bool = False) -> Optional[str]:
    """Compile the native library if needed; returns its path or None."""
    global _build_failed
    with _lock:
        lib = _lib_path()
        if not force and os.path.exists(lib):
            return lib
        if _build_failed is not None and not force:
            return None
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # Build under a per-process name, then rename: a concurrent builder
        # (another worker on this host) never sees a half-written library.
        tmp = f"{lib}.{os.getpid()}.tmp"
        cmd = [
            "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
            _SRC, "-o", tmp,
        ]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired) as e:
            _build_failed = str(e)
        else:
            _build_failed = proc.stderr[-2000:] if proc.returncode != 0 else None
        if _build_failed is not None:
            log.warning(
                "native library build failed — NumPy readers in use: %s",
                _build_failed,
            )
            return None
        os.replace(tmp, lib)
        return lib


def build_error() -> Optional[str]:
    return _build_failed


def status() -> str:
    """Which reader is in use and why, without triggering a build:
    ``native (<file>)``, ``numpy (native build failed: ...)`` or
    ``not built yet (compiles on first dataset use)``."""
    if _lib is not None:
        return f"native ({os.path.basename(_lib._name)})"
    if _build_failed is not None:
        return f"numpy (native build failed: {_build_failed.strip()[-200:]})"
    if os.path.exists(_lib_path()):
        return f"native ({os.path.basename(_lib_path())}, not loaded yet)"
    return "not built yet (compiles on first dataset use)"


def load() -> Optional[ctypes.CDLL]:
    """Load (building if necessary) the native library; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    path = ensure_built()
    if path is None:
        return None
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(path)
            lib.tn_open.restype = ctypes.c_void_p
            lib.tn_open.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int]
            lib.tn_num_sequences.restype = ctypes.c_int64
            lib.tn_num_sequences.argtypes = [ctypes.c_void_p]
            lib.tn_num_tokens.restype = ctypes.c_int64
            lib.tn_num_tokens.argtypes = [ctypes.c_void_p]
            lib.tn_read_batch.restype = ctypes.c_int
            lib.tn_read_batch.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ]
            lib.tn_prefetch_start.restype = ctypes.c_int
            lib.tn_prefetch_start.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int,
            ]
            lib.tn_next_batch.restype = ctypes.c_int
            lib.tn_next_batch.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
            lib.tn_epoch.restype = ctypes.c_int64
            lib.tn_epoch.argtypes = [ctypes.c_void_p]
            lib.tn_close.restype = None
            lib.tn_close.argtypes = [ctypes.c_void_p]
            lib.tn_host_stats.restype = ctypes.c_int
            lib.tn_host_stats.argtypes = [ctypes.POINTER(_TnHostStats)]
            _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def host_stats() -> Optional[dict]:
    """Host memory/load facts from the native /proc probe; None if no lib."""
    lib = load()
    if lib is None:
        return None
    st = _TnHostStats()
    if lib.tn_host_stats(ctypes.byref(st)) != 0:
        return None
    return {
        "mem_total_gb": round(st.mem_total_gb, 3),
        "mem_available_gb": round(st.mem_available_gb, 3),
        "load_1m": st.load_1m,
        "load_5m": st.load_5m,
        "n_cpus": int(st.n_cpus),
    }


class NativeTokenReader:
    """ctypes wrapper over the native mmap reader.

    Token files are flat binary arrays of uint16 (``dtype_code=2``) or int32
    (``dtype_code=4``) token ids; sequences are consecutive, stride
    ``seq_len``.
    """

    def __init__(self, path: str, seq_len: int, dtype_code: int = 2):
        lib = load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {build_error()}")
        self._lib = lib
        self.seq_len = int(seq_len)
        self._h = lib.tn_open(path.encode(), self.seq_len, dtype_code)
        if not self._h:
            raise FileNotFoundError(
                f"tn_open failed for {path!r} (missing file, bad seq_len, or "
                f"file smaller than one sequence)"
            )
        self._prefetch_batch: Optional[int] = None

    @property
    def num_sequences(self) -> int:
        return int(self._lib.tn_num_sequences(self._h))

    @property
    def num_tokens(self) -> int:
        return int(self._lib.tn_num_tokens(self._h))

    @property
    def epoch(self) -> int:
        return int(self._lib.tn_epoch(self._h))

    def read_batch(self, indices: np.ndarray, n_threads: int = 4) -> np.ndarray:
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        out = np.empty((len(idx), self.seq_len), dtype=np.int32)
        rc = self._lib.tn_read_batch(
            self._h,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(idx),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n_threads,
        )
        if rc != 0:
            raise IndexError("tn_read_batch failed (index out of range?)")
        return out

    def start_prefetch(self, batch: int, seed: int = 0, shuffle: bool = True) -> None:
        rc = self._lib.tn_prefetch_start(self._h, batch, seed, int(shuffle))
        if rc != 0:
            raise ValueError("tn_prefetch_start failed (batch > num_sequences?)")
        self._prefetch_batch = int(batch)

    def next_batch(self) -> np.ndarray:
        if self._prefetch_batch is None:
            raise RuntimeError("call start_prefetch first")
        out = np.empty((self._prefetch_batch, self.seq_len), dtype=np.int32)
        rc = self._lib.tn_next_batch(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        )
        if rc != 0:
            raise RuntimeError(f"tn_next_batch failed (rc={rc})")
        return out

    def close(self) -> None:
        if self._h:
            self._lib.tn_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
