"""ctypes binding for the native data runtime (native/npair_data.cpp).

The C++ library is the TPU-side equivalent of the reference's C++
MultibatchData layer (SURVEY.md §1 L1, §3.5): list-file dataset,
identity-balanced sampler, JPEG (system libjpeg)/PPM/BMP/NPY decode +
bilinear resize, and a worker-pool prefetch ring — all off the GIL.

It is compiled on demand with g++ (no pip deps) from the checkout's own
``native/npair_data.cpp``, and the built library is keyed by a hash of
that source (``native/build/libnpair_data.<sha>.so``): a leftover
binary from another source can never stand in for it, and without the
source there is nothing to vouch for, so nothing is loaded.  When the
runtime is unavailable, ``native="auto"`` callers use the pure-Python
pipeline (``data.loader``, identical contract semantics) and say so
once in the log; ``native="require"`` fails.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "npair_data.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

log = logging.getLogger(__name__)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_error: Optional[str] = None


def _lib_path() -> str:
    """The library path for the source AS IT IS ON DISK NOW: its
    content hash is in the name, so staleness is never a guess."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libnpair_data.{digest}.so")


def _build(lib_path: str) -> str:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # Atomic build: compile to a temp name, rename over the target, so
    # concurrent processes never dlopen a half-written .so.
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
    os.close(fd)
    base = [
        "g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
        _SRC, "-o", tmp,
    ]
    # First choice links the system libjpeg (JPEG datasets — CUB/SOP —
    # stay native).  Retry without JPEG ONLY on a jpeg-specific link
    # failure (header present, runtime library missing): any other
    # failure must surface, not silently cache a JPEG-less .so forever.
    try:
        subprocess.run(
            base + ["-ljpeg"], check=True, capture_output=True, text=True
        )
        os.replace(tmp, lib_path)
        return lib_path
    except (subprocess.CalledProcessError, FileNotFoundError) as exc:
        stderr = getattr(exc, "stderr", "") or str(exc)
        if "jpeg" not in stderr.lower():
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise RuntimeError(f"native build failed: {stderr}") from exc
        log.warning(
            "libjpeg link failed (%s); rebuilding native runtime without "
            "JPEG — JPEG datasets will use the Python/PIL path",
            stderr.strip().splitlines()[-1] if stderr.strip() else exc,
        )
    try:
        subprocess.run(
            base + ["-DND_NO_JPEG"], check=True, capture_output=True, text=True
        )
        os.replace(tmp, lib_path)
        return lib_path
    except (subprocess.CalledProcessError, FileNotFoundError) as exc:
        if os.path.exists(tmp):
            os.unlink(tmp)
        detail = getattr(exc, "stderr", "") or str(exc)
        raise RuntimeError(f"native build failed: {detail}") from exc


def _load() -> ctypes.CDLL:
    global _lib, _lib_error
    with _lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise RuntimeError(_lib_error)
        try:
            lib_path = _lib_path()
            built = not os.path.exists(lib_path)
            if built:
                _build(lib_path)
            try:
                lib = ctypes.CDLL(lib_path)
            except OSError:
                # A present-but-unloadable .so (wrong arch/glibc): rebuild
                # from source once rather than caching unavailability.
                if built:
                    raise
                _build(lib_path)
                lib = ctypes.CDLL(lib_path)
        except (OSError, RuntimeError) as exc:
            _lib_error = f"native data runtime unavailable: {exc}"
            log.warning("%s", _lib_error)
            raise RuntimeError(_lib_error) from exc
        lib.nd_last_error.restype = ctypes.c_char_p
        lib.nd_has_jpeg.restype = ctypes.c_int
        lib.nd_dataset_dims.restype = ctypes.c_int
        lib.nd_dataset_dims.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        lib.nd_dataset_open.restype = ctypes.c_void_p
        lib.nd_dataset_open.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_longlong),
        ]
        lib.nd_dataset_labels.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong)]
        lib.nd_dataset_load.restype = ctypes.c_int
        lib.nd_dataset_load.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_ubyte),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        lib.nd_dataset_close.argtypes = [ctypes.c_void_p]
        lib.nd_loader_create.restype = ctypes.c_void_p
        lib.nd_loader_create.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_ulonglong, ctypes.c_int, ctypes.c_int,
        ]
        lib.nd_loader_next.restype = ctypes.c_int
        lib.nd_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_ubyte),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.nd_loader_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def native_available() -> bool:
    """True when the compiled runtime can be (or was) loaded."""
    try:
        _load()
        return True
    except RuntimeError:
        return False


def require_native() -> None:
    """Raise ``RuntimeError`` (with the build/load failure) unless the
    runtime is loaded — the ``--native require`` contract."""
    _load()


def native_jpeg_supported() -> bool:
    """True when the compiled runtime decodes JPEG (linked libjpeg)."""
    try:
        return bool(_load().nd_has_jpeg())
    except RuntimeError:
        return False


def native_suffixes() -> Tuple[str, ...]:
    """Image-file suffixes the loaded native runtime decodes itself —
    the routing contract for data.loader.multibatch_loader."""
    base = (".ppm", ".pgm", ".bmp", ".npy")
    if native_jpeg_supported():
        return base + (".jpg", ".jpeg")
    return base


def _err(lib) -> str:
    return lib.nd_last_error().decode("utf-8", "replace")


class NativeListFileDataset:
    """Native-decode counterpart of ``data.dataset.ListFileDataset``:
    same "relative/path label" list contract, decode in C++
    (JPEG when built with libjpeg, PPM/PGM/BMP/NPY-u8),
    OpenCV-convention bilinear resize."""

    def __init__(self, root_folder: str, source: str,
                 new_height: int = 0, new_width: int = 0):
        self._lib = _load()
        n = ctypes.c_longlong()
        self._handle = self._lib.nd_dataset_open(
            root_folder.encode(), source.encode(),
            int(new_height), int(new_width), ctypes.byref(n),
        )
        if not self._handle:
            raise RuntimeError(_err(self._lib))
        self._n = int(n.value)
        self.new_height = int(new_height)
        self.new_width = int(new_width)
        labels = np.empty(self._n, np.int64)
        self._lib.nd_dataset_labels(
            self._handle,
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        )
        self.labels = labels

    def __len__(self) -> int:
        return self._n

    def dims(self, index: int) -> Tuple[int, int]:
        """(h, w) of the item's output buffer before loading: the fixed
        resize dims, or the decoded native dims when unset."""
        if self._handle is None:
            raise RuntimeError("dataset is closed")
        oh, ow = ctypes.c_int(), ctypes.c_int()
        rc = self._lib.nd_dataset_dims(
            self._handle, int(index), ctypes.byref(oh), ctypes.byref(ow)
        )
        if rc != 0:
            raise RuntimeError(_err(self._lib))
        return int(oh.value), int(ow.value)

    def load(self, index: int) -> np.ndarray:
        if self._handle is None:
            raise RuntimeError("dataset is closed")
        if not (self.new_height and self.new_width):
            raise ValueError(
                "load() without new_height/new_width needs variable-size "
                "buffers; set the resize dims (the MultibatchData contract)"
            )
        out = np.empty((self.new_height, self.new_width, 3), np.uint8)
        oh, ow = ctypes.c_int(), ctypes.c_int()
        rc = self._lib.nd_dataset_load(
            self._handle, int(index),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
            ctypes.byref(oh), ctypes.byref(ow),
        )
        if rc != 0:
            raise RuntimeError(_err(self._lib))
        return out

    def load_batch(self, indices) -> np.ndarray:
        return np.stack([self.load(int(i)) for i in indices])

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.nd_dataset_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativePrefetcher:
    """Iterator of (uint8 images [B,H,W,3], int32 labels [B]) batches,
    produced by the C++ worker pool — sampling, decode, resize and batch
    assembly all run off the GIL."""

    def __init__(self, dataset: NativeListFileDataset,
                 identity_num_per_batch: int, img_num_per_identity: int,
                 rand_identity: bool = True, shuffle: bool = True,
                 seed: int = 0, threads: int = 2, prefetch: int = 2):
        self._ds = dataset  # keep alive: loader holds a raw pointer
        self._lib = dataset._lib
        self.batch_size = identity_num_per_batch * img_num_per_identity
        self.h, self.w = dataset.new_height, dataset.new_width
        self._handle = self._lib.nd_loader_create(
            dataset._handle, int(identity_num_per_batch),
            int(img_num_per_identity), int(bool(rand_identity)),
            int(bool(shuffle)), int(seed), int(threads), int(prefetch),
        )
        if not self._handle:
            raise RuntimeError(_err(self._lib))

    def __iter__(self):
        return self

    def __next__(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._handle is None:
            raise StopIteration("loader is closed")
        images = np.empty((self.batch_size, self.h, self.w, 3), np.uint8)
        labels = np.empty(self.batch_size, np.int32)
        rc = self._lib.nd_loader_next(
            self._handle,
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        )
        if rc != 0:
            raise RuntimeError(_err(self._lib))
        return images, labels

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.nd_loader_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
