"""ctypes bindings for the C++ async file-IO backend (csrc/aio).

Parity: deepspeed/ops/aio (AsyncIOBuilder + aio_handle). Built on first use
with g++ (no pybind11 in this image); the .so is cached next to the source,
keyed on the source's content (utils/native_build.py).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Dict, Optional

import numpy as np

from ..utils.native_build import build_shared_lib

_CSRC = os.path.join(os.path.dirname(__file__), "..", "..", "csrc", "aio")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None


def _build_lib() -> str:
    return build_shared_lib(
        os.path.join(_CSRC, "aio.cpp"), "dsaio", flags=("-pthread",)
    )


def _lib() -> ctypes.CDLL:
    global _LIB
    with _LOCK:
        if _LIB is None:
            lib = ctypes.CDLL(_build_lib())
            lib.dsaio_create.restype = ctypes.c_void_p
            lib.dsaio_create.argtypes = [ctypes.c_int, ctypes.c_int]
            lib.dsaio_destroy.argtypes = [ctypes.c_void_p]
            lib.dsaio_submit.restype = ctypes.c_int64
            lib.dsaio_submit.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ]
            lib.dsaio_wait.restype = ctypes.c_int
            lib.dsaio_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.dsaio_poll.restype = ctypes.c_int
            lib.dsaio_poll.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.dsaio_pending.restype = ctypes.c_int
            lib.dsaio_pending.argtypes = [ctypes.c_void_p]
            _LIB = lib
    return _LIB


class AsyncIOHandle:
    """Parity surface: deepspeed.ops.aio.aio_handle (submit/wait model).

    Buffers must be kept alive by the caller until their request is waited —
    this class pins them in ``_inflight``.
    """

    def __init__(self, num_threads: int = 4, use_direct: bool = False):
        self._lib = _lib()
        self._h = self._lib.dsaio_create(num_threads, int(use_direct))
        self._inflight: Dict[int, np.ndarray] = {}

    def submit_write(self, path: str, array: np.ndarray, offset: int = 0) -> int:
        arr = np.ascontiguousarray(array)
        req = self._lib.dsaio_submit(
            self._h, path.encode(), arr.ctypes.data_as(ctypes.c_void_p),
            arr.nbytes, offset, 1,
        )
        self._inflight[req] = arr
        return req

    def submit_read(self, path: str, array: np.ndarray, offset: int = 0) -> int:
        assert array.flags["C_CONTIGUOUS"], "read target must be contiguous"
        req = self._lib.dsaio_submit(
            self._h, path.encode(), array.ctypes.data_as(ctypes.c_void_p),
            array.nbytes, offset, 0,
        )
        self._inflight[req] = array
        return req

    def wait(self, req: int) -> None:
        rc = self._lib.dsaio_wait(self._h, req)
        self._inflight.pop(req, None)
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc))

    def poll(self, req: int) -> bool:
        return bool(self._lib.dsaio_poll(self._h, req))

    def wait_all(self) -> None:
        for req in list(self._inflight):
            self.wait(req)

    def close(self) -> None:
        if self._h is not None:
            self.wait_all()
            self._lib.dsaio_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
