"""Indexed binary token dataset: the pretraining-data backbone.

Parity: fills the role of Megatron-style .bin/.idx indexed datasets in the
reference's data pipeline (megatron/data/indexed_dataset.py
MMapIndexedDataset + its C gather backend; deepspeed/runtime/data_pipeline
reads them for curriculum/analysis). The on-disk layout is this package's
OWN format (magic ``DSTPUIDX``; write with IndexedDatasetBuilder, read
back with MMapIndexedDataset) — it is NOT byte-compatible with
Megatron/DeepSpeed ``MMIDIDX`` files; pointing this reader at one raises
"bad magic". Tokens live in one flat .bin; the .idx carries cumulative
offsets, so a dataset of millions of variable-length documents costs two
mmaps and zero Python objects per document.

The gather hot path (a batch of documents → one padded [n, seqlen] int32
array) runs in C++ (csrc/data/indexed_reader.cpp, built on first use like
the aio backend); a pure-numpy fallback keeps every feature available
when a toolchain isn't (same files, same results).

Format (version 1):
  <name>.idx : b"DSTPUIDX" | u32 version=1 | u32 dtype (0=u16, 1=i32)
               | u64 count | u64 cum-offsets [count+1]
  <name>.bin : tokens little-endian, back to back.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional, Sequence

import numpy as np

from ..utils.logging import log_dist, warning_once
from ..utils.native_build import build_shared_lib

_MAGIC = b"DSTPUIDX"
_CSRC = os.path.join(
    os.path.dirname(__file__), "..", "..", "csrc", "data"
)
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_FAILED = False


def _build_lib() -> str:
    return build_shared_lib(
        os.path.join(_CSRC, "indexed_reader.cpp"), "dsidx"
    )


def _lib() -> Optional[ctypes.CDLL]:
    """The C++ reader, or None when it can't build (numpy fallback)."""
    global _LIB, _LIB_FAILED
    with _LOCK:
        if _LIB is None and not _LIB_FAILED:
            try:
                lib = ctypes.CDLL(_build_lib())
            except Exception as e:  # no g++ / sandboxed: numpy fallback
                _LIB_FAILED = True
                warning_once(
                    f"indexed_dataset: C++ reader unavailable ({e}); "
                    "using the numpy fallback"
                )
                return None
            lib.dsidx_open.restype = ctypes.c_void_p
            lib.dsidx_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
            lib.dsidx_close.argtypes = [ctypes.c_void_p]
            lib.dsidx_len.restype = ctypes.c_int64
            lib.dsidx_len.argtypes = [ctypes.c_void_p]
            lib.dsidx_seq_len.restype = ctypes.c_int64
            lib.dsidx_seq_len.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.dsidx_fill_batch.restype = ctypes.c_int
            lib.dsidx_fill_batch.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_void_p,
            ]
            lib.dsidx_get.restype = ctypes.c_int64
            lib.dsidx_get.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_int64,
            ]
            _LIB = lib
    return _LIB


class IndexedDatasetBuilder:
    """Stream documents into the .bin/.idx pair.

    u16 storage is picked automatically while every token fits (vocab
    < 65536 — half the disk/IO of i32); the first larger token upgrades
    the .bin in place."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self._bin = open(prefix + ".bin", "wb")
        self._offsets = [0]
        self._dtype = np.uint16

    def add_document(self, tokens: Sequence[int]) -> None:
        arr = np.asarray(tokens)
        if self._dtype == np.uint16 and (arr.max(initial=0) > 65535
                                         or arr.min(initial=0) < 0):
            self._upgrade_to_i32()
        self._bin.write(arr.astype(self._dtype).tobytes())
        self._offsets.append(self._offsets[-1] + len(arr))

    def _upgrade_to_i32(self) -> None:
        # stream the u16 -> i32 rewrite in bounded chunks: the .bin may be
        # many GB by the time the first >65535 token arrives
        self._bin.close()
        old_path = self.prefix + ".bin"
        tmp_path = old_path + ".i32tmp"
        chunk = 1 << 22  # 4M tokens = 8 MiB read / 16 MiB write per step
        with open(old_path, "rb") as src, open(tmp_path, "wb") as dst:
            while True:
                buf = src.read(chunk * 2)
                if not buf:
                    break
                dst.write(
                    np.frombuffer(buf, np.uint16).astype(np.int32).tobytes()
                )
        os.replace(tmp_path, old_path)
        self._dtype = np.int32
        self._bin = open(old_path, "ab")

    def finalize(self) -> None:
        self._bin.close()
        count = len(self._offsets) - 1
        with open(self.prefix + ".idx", "wb") as f:
            f.write(_MAGIC)
            f.write(np.uint32(1).tobytes())
            f.write(np.uint32(0 if self._dtype == np.uint16 else 1).tobytes())
            f.write(np.uint64(count).tobytes())
            f.write(np.asarray(self._offsets, np.uint64).tobytes())
        log_dist(
            f"indexed_dataset: wrote {count} docs, "
            f"{self._offsets[-1]} tokens ({np.dtype(self._dtype).name}) "
            f"to {self.prefix}.bin/.idx"
        )


class MMapIndexedDataset:
    """Read side. ``ds[i]`` → the i-th document (int32 1-D);
    ``ds.get_batch(indices, seqlen)`` → padded [n, seqlen] int32 via the
    C++ gather (or the numpy fallback). With ``seqlen`` set at
    construction, ``ds[i]`` returns {"input_ids": padded row} — the shape
    the engine's dataloader feeds straight into train_batch."""

    def __init__(self, prefix: str, seqlen: Optional[int] = None,
                 pad_id: int = 0):
        self.prefix = prefix
        self.seqlen = seqlen
        self.pad_id = int(pad_id)
        bin_path, idx_path = prefix + ".bin", prefix + ".idx"
        if not (os.path.exists(bin_path) and os.path.exists(idx_path)):
            raise FileNotFoundError(f"{prefix}.bin/.idx not found")
        self._h = None
        lib = _lib()
        if lib is not None:
            self._h = lib.dsidx_open(bin_path.encode(), idx_path.encode())
            if not self._h:
                raise ValueError(f"{prefix}: bad or corrupt index file")
            self._count = int(lib.dsidx_len(self._h))
        if self._h is None:
            self._np_open(bin_path, idx_path)

    # ------------------------------------------------- numpy fallback side
    def _np_open(self, bin_path: str, idx_path: str) -> None:
        with open(idx_path, "rb") as f:
            head = f.read(24)
            if head[:8] != _MAGIC:
                raise ValueError(f"{idx_path}: bad magic")
            version = np.frombuffer(head, np.uint32, 1, 8)[0]
            dtype_code = np.frombuffer(head, np.uint32, 1, 12)[0]
            count = int(np.frombuffer(head, np.uint64, 1, 16)[0])
            if version != 1 or dtype_code > 1:
                raise ValueError(f"{idx_path}: unsupported version/dtype")
            self._np_offsets = np.fromfile(f, np.uint64, count + 1)
        dtype = np.uint16 if dtype_code == 0 else np.int32
        if os.path.getsize(bin_path) == 0:  # zero-token dataset is valid
            self._np_tokens = np.empty(0, dtype)
        else:
            self._np_tokens = np.memmap(bin_path, dtype=dtype, mode="r")
        self._count = count

    def __len__(self) -> int:
        return self._count

    def seq_len(self, i: int) -> int:
        if self._h is not None:
            n = int(_lib().dsidx_seq_len(self._h, i))
            if n < 0:
                raise IndexError(i)
            return n
        o = self._np_offsets
        if not 0 <= i < self._count:
            raise IndexError(i)
        return int(o[i + 1] - o[i])

    def get(self, i: int) -> np.ndarray:
        """Raw (unpadded) document tokens, int32."""
        n = self.seq_len(i)
        if self._h is not None:
            out = np.empty(n, np.int32)
            got = _lib().dsidx_get(
                self._h, i, out.ctypes.data_as(ctypes.c_void_p), n
            )
            if got < 0:
                raise IndexError(i)
            return out[:got]
        o = self._np_offsets
        return np.asarray(
            self._np_tokens[int(o[i]):int(o[i + 1])], np.int32
        )

    def get_batch(self, indices, seqlen: int, start: int = 0,
                  pad_id: Optional[int] = None) -> np.ndarray:
        """[n, seqlen] int32: tokens [start, start+seqlen) of each doc,
        truncated at the doc's end, padded with pad_id."""
        if start < 0 or seqlen < 0:
            # the C++ side rejects these too; validating here keeps both
            # backends on one contract (no Python negative-slice semantics)
            raise ValueError(f"start/seqlen must be >= 0, got {start}/{seqlen}")
        idx = np.ascontiguousarray(indices, np.int64)
        pad = self.pad_id if pad_id is None else int(pad_id)
        out = np.empty((len(idx), seqlen), np.int32)
        if self._h is not None:
            rc = _lib().dsidx_fill_batch(
                self._h, idx.ctypes.data_as(ctypes.c_void_p), len(idx),
                seqlen, start, pad, out.ctypes.data_as(ctypes.c_void_p),
            )
            if rc != 0:
                raise IndexError(f"index out of range in {list(idx[:5])}...")
            return out
        for k, i in enumerate(idx):
            doc = self.get(int(i))[start:start + seqlen]
            out[k, : len(doc)] = doc
            out[k, len(doc):] = pad
        return out

    def __getitem__(self, i: int):
        if self.seqlen is None:
            return self.get(int(i))
        return {"input_ids": self.get_batch([int(i)], self.seqlen)[0]}

    def close(self) -> None:
        if self._h is not None:
            _lib().dsidx_close(self._h)
            self._h = None

    def __del__(self):  # best effort; mmaps also die with the process
        try:
            self.close()
        except Exception:
            pass
