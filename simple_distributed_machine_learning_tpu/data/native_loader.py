"""ctypes bindings for the native C++ data loader (``native/data_loader.cpp``).

Builds ``libsdml_data.so`` on demand with ``make`` (g++ is in the image;
pybind11 is not, hence the plain C ABI + ctypes). If the toolchain or .so is
unavailable, callers fall back to the pure-NumPy paths in ``mnist.py`` and
one line on stderr says so.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import sys
from typing import Iterator

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libsdml_data.so")

_lib = None  # None = not attempted; False = attempted and unavailable


def _warn(why: str) -> None:
    """The fallback is allowed, never silent."""
    sys.stderr.write(f"native_loader: {why}; using the pure-NumPy data "
                     f"path\n")


def _load() -> ctypes.CDLL | None:
    global _lib
    if _lib is not None:
        return _lib or None  # False (cached failure) -> None
    # always invoke make: it is a no-op when the .so is newer than the
    # sources, and rebuilds when data_loader.cpp changed (a pre-existing .so
    # must never mask an edited source file). flock serializes concurrent
    # processes (every rank of a multi-process launch lands here at startup)
    # so none can dlopen a half-written .so.
    try:
        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                           capture_output=True, timeout=120)
    except Exception as e:  # noqa: BLE001 - any build failure = fallback
        if not os.path.exists(_SO_PATH):
            _warn(f"`make -C {_NATIVE_DIR}` failed ({type(e).__name__}: "
                  f"{str(e)[:200]}) and no built library exists")
            _lib = False
            return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        _warn(f"cannot load {_SO_PATH} ({e})")
        _lib = False
        return None
    lib.idx_read.argtypes = [ctypes.c_char_p,
                             ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
                             ctypes.POINTER(ctypes.c_int64),
                             ctypes.POINTER(ctypes.c_int)]
    lib.idx_read.restype = ctypes.c_int
    lib.idx_free.argtypes = [ctypes.POINTER(ctypes.c_float)]
    lib.prefetcher_create.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.prefetcher_create.restype = ctypes.c_void_p
    lib.prefetcher_next.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_float),
                                    ctypes.POINTER(ctypes.c_int32)]
    lib.prefetcher_next.restype = ctypes.c_int64
    lib.prefetcher_num_batches.argtypes = [ctypes.c_void_p]
    lib.prefetcher_num_batches.restype = ctypes.c_int64
    lib.prefetcher_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def idx_read_native(path: str) -> np.ndarray | None:
    """Parse an IDX file via the C++ codec. None if native lib unavailable."""
    lib = _load()
    if lib is None:
        return None
    data = ctypes.POINTER(ctypes.c_float)()
    dims = (ctypes.c_int64 * 4)()
    ndim = ctypes.c_int()
    rc = lib.idx_read(path.encode(), ctypes.byref(data), dims,
                      ctypes.byref(ndim))
    if rc != 0:
        raise IOError(f"idx_read({path!r}) failed with code {rc}")
    shape = tuple(dims[i] for i in range(ndim.value))
    n = int(np.prod(shape))
    out = np.ctypeslib.as_array(data, shape=(n,)).reshape(shape).copy()
    lib.idx_free(data)
    return out


class NativePrefetcher:
    """Background-thread batch assembly over (x, y) arrays.

    Iterates ``(x_batch, y_batch, n_valid)`` in ``order``; the final ragged
    batch arrives zero-padded, mirroring ``mnist.batches(pad_last=True)``.
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, batch: int,
                 order: np.ndarray | None = None, depth: int = 2):
        lib = _load()
        if lib is None:
            raise RuntimeError("native loader unavailable")
        self._lib = lib
        self.x = np.ascontiguousarray(x, np.float32).reshape(len(x), -1)
        y2 = np.ascontiguousarray(y, np.int32)
        self.y = y2.reshape(len(y2), -1)
        self.batch = batch
        self.row_x = self.x.shape[1]
        self.row_y = self.y.shape[1]
        self._x_shape = x.shape[1:]
        self._y_shape = y.shape[1:]
        order = (np.arange(len(x), dtype=np.int64) if order is None
                 else np.ascontiguousarray(order, np.int64))
        self._h = lib.prefetcher_create(
            self.x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self.y.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(self.x), self.row_x, self.row_y, batch,
            order.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), depth)
        self.n_batches = lib.prefetcher_num_batches(self._h)

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray, int]]:
        bx = np.empty((self.batch, self.row_x), np.float32)
        by = np.empty((self.batch, self.row_y), np.int32)
        while True:
            n_valid = self._lib.prefetcher_next(
                self._h,
                bx.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                by.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            if n_valid < 0:
                return
            yield (bx.reshape((self.batch,) + self._x_shape).copy(),
                   by.reshape((self.batch,) + self._y_shape).copy(),
                   int(n_valid))

    def close(self) -> None:
        if self._h:
            self._lib.prefetcher_destroy(self._h)
            self._h = None

    def __del__(self):  # noqa: D105
        try:
            self.close()
        except Exception:
            pass
