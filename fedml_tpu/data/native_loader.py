"""ctypes bridge to the native C++ data plane (native/dataplane).

TPU-native counterpart of the reference's torch DataLoader worker pool
(``data/data_loader.py`` loaders feed torch DataLoaders): shards are
written once as flat binary files, mmap'd by C++, and batches are gathered
(shuffled, per-epoch reseeded) by a background C++ thread into
double-buffered slots — the Python side does one memcpy into a numpy array
per batch, with no GIL-held gather loop. Falls back cleanly when no C++
toolchain is available: ``NativeBatchLoader.available()`` gates use.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger(__name__)

_DP_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "native", "dataplane")
_DP_DIR = os.path.normpath(_DP_DIR)
_LIB_PATH = os.path.join(_DP_DIR, "build", "libfedml_dataplane.so")

_DTYPES = {
    np.dtype(np.float32): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.uint8): 3,
    np.dtype(np.int64): 4,
}
_DTYPES_INV = {v: k for k, v in _DTYPES.items()}

_lib = None
_build_error: Optional[str] = None
_lock = threading.Lock()


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            # always through make (a no-op when fresh): the binary comes from
            # the tracked sources, never from whatever .so sits in build/
            proc = subprocess.run(
                ["make", "-C", _DP_DIR], capture_output=True, text=True
            )
            if proc.returncode != 0:
                _build_error = proc.stderr[-2000:]
                log.warning("native dataplane build failed; python fallback only")
                return None
            lib = ctypes.CDLL(_LIB_PATH)
        except Exception as e:  # no make on PATH, stale/partial .so, ...
            _build_error = f"{type(e).__name__}: {e}"
            log.warning("native dataplane unavailable (%s); python fallback only", _build_error)
            return None
        lib.fdlp_last_error.restype = ctypes.c_char_p
        lib.fdlp_write_shard.argtypes = [
            ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_void_p,
        ]
        lib.fdlp_shard_info.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.fdlp_prefetcher_create.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_uint32,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ]
        lib.fdlp_prefetcher_create.restype = ctypes.c_void_p
        lib.fdlp_batches_per_epoch.argtypes = [ctypes.c_void_p]
        lib.fdlp_batches_per_epoch.restype = ctypes.c_uint64
        lib.fdlp_prefetcher_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p)]
        lib.fdlp_prefetcher_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def _err(lib) -> str:
    return lib.fdlp_last_error().decode()


def write_shard(path: str, array: np.ndarray) -> None:
    """Write one array as a binary shard (leading dim = samples)."""
    lib = _load()
    arr = np.ascontiguousarray(array)
    if arr.dtype not in _DTYPES:
        raise ValueError(f"unsupported shard dtype {arr.dtype}")
    if lib is None:
        # pure-python fallback writer (same format)
        with open(path, "wb") as f:
            f.write(b"FDLP")
            f.write(np.asarray([1, _DTYPES[arr.dtype], arr.ndim], np.uint32).tobytes())
            f.write(np.asarray(arr.shape, np.uint64).tobytes())
            f.write(arr.tobytes())
        return
    dims = (ctypes.c_uint64 * arr.ndim)(*arr.shape)
    rc = lib.fdlp_write_shard(
        path.encode(), _DTYPES[arr.dtype], arr.ndim, dims,
        arr.ctypes.data_as(ctypes.c_void_p),
    )
    if rc != 0:
        raise RuntimeError(f"shard write failed: {_err(lib)}")


def shard_info(path: str) -> Tuple[np.dtype, Tuple[int, ...]]:
    lib = _load()
    if lib is None:
        with open(path, "rb") as f:
            head = f.read(16)
            assert head[:4] == b"FDLP", "bad shard magic"
            _, dt, ndim = np.frombuffer(head[4:], np.uint32)
            dims = np.frombuffer(f.read(8 * ndim), np.uint64)
        return _DTYPES_INV[int(dt)], tuple(int(d) for d in dims)
    dt = ctypes.c_uint32()
    dims = (ctypes.c_uint64 * 8)()
    ndim = lib.fdlp_shard_info(path.encode(), ctypes.byref(dt), dims)
    if ndim < 0:
        raise RuntimeError(f"shard open failed: {_err(lib)}")
    return _DTYPES_INV[dt.value], tuple(dims[i] for i in range(ndim))


class NativeBatchLoader:
    """Iterate shuffled (x, y, ...) batches gathered by the C++ prefetcher."""

    def __init__(self, shard_paths: Sequence[str], batch_size: int, seed: int = 0, slots: int = 3):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native dataplane unavailable: {_build_error}")
        self._lib = lib
        self.batch_size = int(batch_size)
        self._specs: List[Tuple[np.dtype, Tuple[int, ...]]] = [shard_info(p) for p in shard_paths]
        paths = (ctypes.c_char_p * len(shard_paths))(*[p.encode() for p in shard_paths])
        self._h = lib.fdlp_prefetcher_create(
            paths, len(shard_paths), self.batch_size, int(seed), int(slots)
        )
        if not self._h:
            raise RuntimeError(f"prefetcher create failed: {_err(lib)}")
        self.batches_per_epoch = int(lib.fdlp_batches_per_epoch(self._h))

    @staticmethod
    def available() -> bool:
        return _load() is not None

    def next_batch(self) -> Tuple[bool, List[np.ndarray]]:
        """(more_in_epoch, [array_k]) — arrays are freshly-owned copies."""
        outs = []
        ptrs = (ctypes.c_void_p * len(self._specs))()
        for k, (dt, dims) in enumerate(self._specs):
            buf = np.empty((self.batch_size, *dims[1:]), dt)
            outs.append(buf)
            ptrs[k] = buf.ctypes.data_as(ctypes.c_void_p)
        rc = self._lib.fdlp_prefetcher_next(self._h, ptrs)
        if rc < 0:
            raise RuntimeError(f"prefetcher next failed: {_err(self._lib)}")
        return rc == 1, outs

    def epoch(self) -> Iterator[List[np.ndarray]]:
        while True:
            more, arrays = self.next_batch()
            yield arrays
            if not more:
                return

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.fdlp_prefetcher_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
