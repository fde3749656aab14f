"""ctypes bindings for the native data-path component (native/decode.cpp).

The C++ side does the bandwidth-heavy work — libjpeg decode, crop, bilinear
resize, straight into one preallocated uint8 batch buffer with an internal
thread pool.  Crop-rectangle RANDOMNESS stays in Python
(data/imagenet.py) so augmentation remains a pure function of
(seed, epoch, index).

The library is built lazily with g++ on first use under native/build/,
under a name that carries a hash of ``decode.cpp`` and the compile command:
only what the source in THIS checkout produces is ever loaded — a binary
left on disk by another source or another command has another name and is
ignored.  If the toolchain or libjpeg is missing, callers fall back to the
PIL path (``load() returns None``); the dataset's ``decoder`` property
(data/imagenet.py) says which one a run got.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from ..utils.logging import get_logger

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")


def _build_cmd(src: str, out: str) -> list:
    return ["g++", "-O3", "-fPIC", "-std=c++17", "-shared", src,
            "-o", out, "-ljpeg", "-lpthread"]


_lock = threading.Lock()
_lib = None
_load_failed = False


def so_path(native_dir: str = _NATIVE_DIR) -> Optional[str]:
    """The one library file a checkout may load:
    ``<native_dir>/build/libaldata-<hash>.so``, the hash taken over the
    bytes of ``decode.cpp`` and the compile command.  None without the
    source."""
    try:
        with open(os.path.join(native_dir, "decode.cpp"), "rb") as fh:
            digest = hashlib.sha256(fh.read())
    except OSError:
        return None
    digest.update(" ".join(_build_cmd("decode.cpp", "libaldata.so")).encode())
    return os.path.join(native_dir, "build",
                        f"libaldata-{digest.hexdigest()[:16]}.so")


def _build(native_dir: str, target: str) -> bool:
    os.makedirs(os.path.dirname(target), exist_ok=True)
    # Compile to a process-unique temp name, then rename: the publish is
    # atomic, so concurrent first-users can never dlopen a half-written .so.
    tmp = f"{target}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            _build_cmd(os.path.join(native_dir, "decode.cpp"), tmp),
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, target)
        return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        get_logger().warning(
            f"native decode build failed ({e!r}); using the PIL path")
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _open_library(native_dir: str = _NATIVE_DIR) -> Optional[ctypes.CDLL]:
    """Build (if its file is not there yet) and open the library of the
    ``decode.cpp`` in ``native_dir``; None if unavailable.  No other file
    in ``build/`` is ever opened."""
    target = so_path(native_dir)
    if target is None or (not os.path.exists(target)
                          and not _build(native_dir, target)):
        return None
    try:
        lib = ctypes.CDLL(target)
    except OSError as e:
        get_logger().warning(f"native decode load failed ({e!r})")
        return None
    lib.al_jpeg_dims.restype = ctypes.c_int
    lib.al_jpeg_dims.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
    lib.al_decode_crop_resize.restype = ctypes.c_int
    lib.al_decode_crop_resize.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int]
    return lib


def load() -> Optional[ctypes.CDLL]:
    """The shared library, building it if needed; None if unavailable."""
    global _lib, _load_failed
    with _lock:
        if _lib is None and not _load_failed:
            _lib = _open_library()
            _load_failed = _lib is None
        return _lib


def _path_array(paths: Sequence[str]):
    arr = (ctypes.c_char_p * len(paths))()
    arr[:] = [p.encode() for p in paths]
    return arr


def jpeg_dims(paths: Sequence[str], n_threads: int = 4
              ) -> Optional[np.ndarray]:
    """[N, 2] (height, width) from JPEG headers; rows are (-1, -1) for
    files libjpeg can't parse (caller decides the fallback).  None if the
    native library is unavailable."""
    lib = load()
    if lib is None:
        return None
    out = np.empty((len(paths), 2), dtype=np.int32)
    lib.al_jpeg_dims(
        _path_array(paths), len(paths),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n_threads)
    return out


def decode_crop_resize(paths: Sequence[str], rects: np.ndarray,
                       out_size: int, n_threads: int = 4):
    """Decode + crop (rects[i] = top, left, ch, cw) + bilinear resize into
    a uint8 [N, out_size, out_size, 3] batch.  Returns (batch, failed_mask)
    — failed rows (e.g. CMYK JPEGs) are zeroed for the caller to re-decode
    individually — or None if the native library is unavailable."""
    lib = load()
    if lib is None:
        return None
    rects = np.ascontiguousarray(rects, dtype=np.int32)
    assert rects.shape == (len(paths), 4)
    out = np.empty((len(paths), out_size, out_size, 3), dtype=np.uint8)
    failed = np.zeros(len(paths), dtype=np.uint8)
    lib.al_decode_crop_resize(
        _path_array(paths), len(paths),
        rects.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), out_size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        failed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n_threads)
    return out, failed.astype(bool)
