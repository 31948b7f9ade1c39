"""ctypes bridge to the native edge engine.

Reference analogue: the JNI bridge
``android/fedmlsdk/src/main/jni/JniFedMLClientManager.cpp`` — here the host
is Python, so the bridge is the C ABI in ``native/edge/src/c_api.cpp``. The
shared library is built on demand with the plain Makefile (no deps beyond
g++); environments without a toolchain get a clear RuntimeError and callers
gate on :func:`native_engine_available`.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_EDGE_DIR = os.path.join(_REPO_ROOT, "native", "edge")
_LIB_PATH = os.path.join(_EDGE_DIR, "build", "libfedml_edge.so")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _build_library() -> None:
    proc = subprocess.run(
        ["make", "-C", _EDGE_DIR], capture_output=True, text=True, timeout=300
    )
    if proc.returncode != 0:
        raise RuntimeError(f"edge engine build failed:\n{proc.stderr[-2000:]}")


def _load() -> ctypes.CDLL:
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise RuntimeError(_build_error)
        try:
            # always through make (a no-op when fresh): the binary comes from
            # the tracked sources, never from whatever .so sits in build/
            _build_library()
            lib = ctypes.CDLL(_LIB_PATH)
        except Exception as e:
            _build_error = f"native edge engine unavailable: {e}"
            raise RuntimeError(_build_error) from e
        lib.edge_create.restype = ctypes.c_void_p
        lib.edge_destroy.argtypes = [ctypes.c_void_p]
        lib.edge_init.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_int,
        ]
        lib.edge_train.argtypes = [ctypes.c_void_p]
        lib.edge_train.restype = ctypes.c_char_p
        lib.edge_get_epoch_and_loss.argtypes = [ctypes.c_void_p]
        lib.edge_get_epoch_and_loss.restype = ctypes.c_char_p
        lib.edge_stop_training.argtypes = [ctypes.c_void_p]
        lib.edge_evaluate.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.edge_evaluate.restype = ctypes.c_double
        lib.edge_num_params.argtypes = [ctypes.c_void_p]
        lib.edge_num_params.restype = ctypes.c_int64
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        lib.edge_configure_model.argtypes = [ctypes.c_void_p, i32p, ctypes.c_int, ctypes.c_uint64]
        lib.edge_configure_conv_model.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            i32p, ctypes.c_int, i32p, ctypes.c_int, ctypes.c_uint64,
        ]
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        lib.edge_get_model.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int64]
        lib.edge_set_model.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int64]
        lib.edge_lsa_encode_mask.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int64, ctypes.c_uint64,
        ]
        lib.edge_lsa_encode_mask.restype = ctypes.c_int64
        lib.edge_lsa_get_share.argtypes = [ctypes.c_void_p, ctypes.c_int, i64p, ctypes.c_int64]
        lib.edge_lsa_masked_model.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int64, i64p, ctypes.c_int64,
        ]
        lib.edge_lsa_aggregate_shares.argtypes = [
            ctypes.c_void_p, i64p, ctypes.c_int, ctypes.c_int64, ctypes.c_int64, i64p,
        ]
        _lib = lib
        return lib


def native_engine_available() -> bool:
    try:
        _load()
        return True
    except RuntimeError:
        return False


class NativeEdgeEngine:
    """One on-device trainer instance (reference FedMLClientManager shape)."""

    def __init__(self, model_path: str = "", data_path: str = "", dataset: str = "synthetic",
                 train_size: int = 0, test_size: int = 0, batch_size: int = 32,
                 learning_rate: float = 0.05, epochs: int = 1, dims=None, seed: int = 0):
        self._lib = _load()
        self._h = self._lib.edge_create()
        self._lib.edge_init(
            self._h, model_path.encode(), data_path.encode(), dataset.encode(),
            train_size, test_size, batch_size, learning_rate, epochs,
        )
        if dims is not None:
            self.configure_model(dims, seed)

    def configure_model(self, dims, seed: int = 0) -> None:
        """Define the dense architecture (e.g. [784, 10] for LR) so weights
        can be exchanged before the first train()."""
        d = np.ascontiguousarray(dims, np.int32)
        if self._lib.edge_configure_model(self._h, d, len(d), seed) != 0:
            raise ValueError(f"bad model dims {list(dims)}")

    def configure_conv_model(self, in_h: int, in_w: int, in_c: int,
                             conv_channels, dense_dims, seed: int = 0) -> None:
        """LeNet-style conv graph: conv3x3+ReLU+maxpool2 per entry of
        conv_channels, then dense layers ending in num_classes (reference
        mobile engine LeNet training, FedMLMNNTrainer.cpp). Every conv
        stage's input dims must be even (2x2 pool halves them)."""
        cc = np.ascontiguousarray(conv_channels, np.int32)
        dd = np.ascontiguousarray(dense_dims, np.int32)
        rc = self._lib.edge_configure_conv_model(
            self._h, in_h, in_w, in_c, cc, len(cc), dd, len(dd), seed
        )
        if rc != 0:
            raise ValueError(
                f"bad conv model spec ({in_h}x{in_w}x{in_c}, conv {list(cc)}, dense {list(dd)})"
            )

    def __del__(self):  # pragma: no cover - gc timing
        try:
            if getattr(self, "_h", None):
                self._lib.edge_destroy(self._h)
                self._h = None
        except Exception:
            pass

    def train(self) -> str:
        return self._lib.edge_train(self._h).decode()

    def get_epoch_and_loss(self) -> str:
        return self._lib.edge_get_epoch_and_loss(self._h).decode()

    def stop_training(self) -> bool:
        return bool(self._lib.edge_stop_training(self._h))

    def evaluate(self, limit: int = 0) -> float:
        return float(self._lib.edge_evaluate(self._h, limit))

    # --- model exchange ---------------------------------------------------
    @property
    def num_params(self) -> int:
        return int(self._lib.edge_num_params(self._h))

    def get_model_flat(self) -> np.ndarray:
        out = np.empty(self.num_params, np.float32)
        if self._lib.edge_get_model(self._h, out, out.size) != 0:
            raise RuntimeError("edge_get_model size mismatch")
        return out

    def set_model_flat(self, flat: np.ndarray) -> None:
        flat = np.ascontiguousarray(flat, np.float32)
        if self._lib.edge_set_model(self._h, flat, flat.size) != 0:
            raise RuntimeError("edge_set_model size mismatch")

    # --- LightSecAgg ------------------------------------------------------
    def lsa_encode_mask(self, num_clients: int, target_active: int,
                        privacy_guarantee: int, prime: int, seed: int) -> int:
        chunk = int(self._lib.edge_lsa_encode_mask(
            self._h, num_clients, target_active, privacy_guarantee, prime, seed
        ))
        if chunk < 0:
            raise ValueError("invalid LightSecAgg parameters")
        return chunk

    def lsa_get_share(self, peer: int, chunk: int) -> np.ndarray:
        out = np.empty(chunk, np.int64)
        if self._lib.edge_lsa_get_share(self._h, peer, out, chunk) != 0:
            raise RuntimeError("edge_lsa_get_share failed")
        return out

    def lsa_masked_model(self, q_bits: int, prime: int) -> np.ndarray:
        out = np.empty(self.num_params, np.int64)
        if self._lib.edge_lsa_masked_model(self._h, q_bits, prime, out, out.size) != 0:
            raise RuntimeError("edge_lsa_masked_model failed")
        return out

    def lsa_aggregate_shares(self, shares: np.ndarray, prime: int) -> np.ndarray:
        shares = np.ascontiguousarray(shares, np.int64)
        n_active, chunk = shares.shape
        out = np.empty(chunk, np.int64)
        self._lib.edge_lsa_aggregate_shares(
            self._h, shares.reshape(-1), n_active, chunk, prime, out
        )
        return out
