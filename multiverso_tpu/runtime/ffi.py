"""ctypes bindings for the native host runtime (libmvtpu_host.so).

Auto-builds with g++ on first import if the shared object is missing or
stale (the image bakes a toolchain but no pip/pybind11 — plain ctypes over a
flat C ABI, like the reference's ``binding/python`` over ``c_api``,
``binding/python/multiverso/utils.py:15-40``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmvtpu_host.so")
_SRCS = [os.path.join(_DIR, "src", "mv_runtime.cpp"),
         os.path.join(_DIR, "src", "mv_client.cpp")]

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()


class NativeRuntimeUnavailable(RuntimeError):
    pass


def _build() -> None:
    # No -ffast-math: it links crtfastmath.o, which flips FTZ/DAZ for the
    # whole process at dlopen and silently changes numpy/JAX numerics.
    # Compile to a name of our own beside the target, then rename over it:
    # _lib_lock is per PROCESS, and N freshly spawned ranks on a clean
    # checkout all find the .so missing at once — none of them may dlopen
    # a file another is still writing. The rename is atomic; a rank that
    # loses the race loads the winner's complete file.
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-fPIC", "-Wall", "-pthread",
           "-fno-math-errno", "-shared", "-o", tmp, *_SRCS]
    try:
        result = subprocess.run(cmd, capture_output=True, text=True)
        if result.returncode != 0:
            raise NativeRuntimeUnavailable(
                f"native runtime build failed:\n{result.stderr}")
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def load() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        stale = (not os.path.exists(_SO) or
                 os.path.getmtime(_SO) < max(os.path.getmtime(s)
                                             for s in _SRCS))
        if stale:
            # _lib_lock held across the compile ON PURPOSE: exactly one
            # builder per process; latecomers must wait for the finished
            # .so, not race a second g++ at the same output path.
            # graftlint: disable=lock-held-across-blocking
            _build()
        lib = ctypes.CDLL(_SO)
        _declare(lib)
        _lib = lib
        return lib


def available() -> bool:
    try:
        load()
        return True
    except (NativeRuntimeUnavailable, OSError):
        return False


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    f32p = c.POINTER(c.c_float)
    i32p = c.POINTER(c.c_int32)

    lib.mvq_create.restype = c.c_void_p
    lib.mvq_destroy.argtypes = [c.c_void_p]
    lib.mvq_push.argtypes = [c.c_void_p, c.c_uint64]
    lib.mvq_pop.argtypes = [c.c_void_p, c.POINTER(c.c_uint64), c.c_long]
    lib.mvq_pop.restype = c.c_int
    lib.mvq_size.argtypes = [c.c_void_p]
    lib.mvq_size.restype = c.c_int64
    lib.mvq_exit.argtypes = [c.c_void_p]

    lib.mvw_create.argtypes = [c.c_int]
    lib.mvw_create.restype = c.c_void_p
    lib.mvw_destroy.argtypes = [c.c_void_p]
    lib.mvw_wait.argtypes = [c.c_void_p, c.c_long]
    lib.mvw_wait.restype = c.c_int
    lib.mvw_notify.argtypes = [c.c_void_p]
    lib.mvw_reset.argtypes = [c.c_void_p, c.c_int]

    lib.mva_create.argtypes = [c.c_long]
    lib.mva_create.restype = c.c_void_p
    lib.mva_destroy.argtypes = [c.c_void_p]
    lib.mva_alloc.argtypes = [c.c_void_p, c.c_long]
    lib.mva_alloc.restype = c.c_void_p
    lib.mva_free.argtypes = [c.c_void_p, c.c_void_p, c.c_long]
    lib.mva_pool_hits.argtypes = [c.c_void_p]
    lib.mva_pool_hits.restype = c.c_uint64

    lib.mvbuf_create.argtypes = [c.c_int64, c.c_int64]
    lib.mvbuf_create.restype = c.c_void_p
    lib.mvbuf_destroy.argtypes = [c.c_void_p]
    lib.mvbuf_add_dense.argtypes = [c.c_void_p, f32p, c.c_float]
    lib.mvbuf_add_rows.argtypes = [c.c_void_p, i32p, c.c_int64, f32p,
                                   c.c_float]
    lib.mvbuf_drain_dense.argtypes = [c.c_void_p, f32p]
    lib.mvbuf_drain_dense.restype = c.c_int64
    lib.mvbuf_drain_rows.argtypes = [c.c_void_p, i32p, f32p, c.c_int64]
    lib.mvbuf_drain_rows.restype = c.c_int64
    lib.mvbuf_pending.argtypes = [c.c_void_p]
    lib.mvbuf_pending.restype = c.c_int64


def _f32ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i32ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


# ---------------------------------------------------------------------------
# Pythonic wrappers
# ---------------------------------------------------------------------------
class MtQueue:
    """Blocking MPMC queue of u64 handles (ref mt_queue.h:18-145)."""

    def __init__(self) -> None:
        self._lib = load()
        self._h = self._lib.mvq_create()

    def push(self, item: int) -> None:
        self._lib.mvq_push(self._h, item)

    def pop(self, timeout_ms: int = -1) -> Optional[int]:
        out = ctypes.c_uint64()
        if self._lib.mvq_pop(self._h, ctypes.byref(out), timeout_ms):
            return out.value
        return None

    def __len__(self) -> int:
        return self._lib.mvq_size(self._h)

    def exit(self) -> None:
        self._lib.mvq_exit(self._h)

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.mvq_destroy(self._h)
            self._h = None


class Waiter:
    """Counted latch (ref waiter.h:9-33)."""

    def __init__(self, count: int = 1) -> None:
        self._lib = load()
        self._h = self._lib.mvw_create(count)

    def wait(self, timeout_ms: int = -1) -> bool:
        return bool(self._lib.mvw_wait(self._h, timeout_ms))

    def notify(self) -> None:
        self._lib.mvw_notify(self._h)

    def reset(self, count: int) -> None:
        self._lib.mvw_reset(self._h, count)

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.mvw_destroy(self._h)
            self._h = None


class DeltaBuffer:
    """Striped-lock float32 staging buffer; threads accumulate without the
    GIL, drain hands one merged delta to the device update."""

    def __init__(self, rows: int, cols: int = 1) -> None:
        self._lib = load()
        self.rows = int(rows)
        self.cols = int(cols)
        self._h = self._lib.mvbuf_create(self.rows, self.cols)

    def add_dense(self, delta: np.ndarray, alpha: float = 1.0) -> None:
        delta = np.ascontiguousarray(delta, dtype=np.float32)
        assert delta.size == self.rows * self.cols
        self._lib.mvbuf_add_dense(self._h, _f32ptr(delta), alpha)

    def add_rows(self, row_ids: np.ndarray, deltas: np.ndarray,
                 alpha: float = 1.0) -> None:
        row_ids = np.ascontiguousarray(row_ids, dtype=np.int32)
        deltas = np.ascontiguousarray(deltas, dtype=np.float32)
        assert deltas.shape == (len(row_ids), self.cols)
        self._lib.mvbuf_add_rows(self._h, _i32ptr(row_ids), len(row_ids),
                                 _f32ptr(deltas), alpha)

    def drain_dense(self) -> tuple[np.ndarray, int]:
        out = np.empty((self.rows, self.cols), dtype=np.float32)
        n = self._lib.mvbuf_drain_dense(self._h, _f32ptr(out))
        if self.cols == 1:
            out = out.reshape(self.rows)
        return out, int(n)

    def drain_rows(self, max_rows: int) -> Optional[tuple[np.ndarray,
                                                          np.ndarray]]:
        """Merged (row_ids, rows) of touched rows, or None if more than
        max_rows rows are dirty (fall back to drain_dense)."""
        ids = np.empty(max_rows, dtype=np.int32)
        rows = np.empty((max_rows, self.cols), dtype=np.float32)
        n = self._lib.mvbuf_drain_rows(self._h, _i32ptr(ids), _f32ptr(rows),
                                       max_rows)
        if n < 0:
            return None
        return ids[:n].copy(), rows[:n].copy()

    @property
    def pending(self) -> int:
        return int(self._lib.mvbuf_pending(self._h))

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.mvbuf_destroy(self._h)
            self._h = None
