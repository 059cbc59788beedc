"""ctypes binding for the native host ops (native/hostops.cc).

Importing this module loads ``libkdlthostops.so`` from, in order: the
explicit ``KDLT_NATIVE_LIB`` (images that ship a prebuilt library); a build
of the checkout's own ``native/*.cc`` with g++, cached per source content
under the user cache dir; a library packaged next to this module (installed
wheels with no source tree).  The source build comes before any prebuilt
file so that what runs is what git tracks -- an untracked ``native/build/``
left in a working tree is never picked up.  Any failure raises ImportError,
which ``ops.preprocess`` treats as "no native path" and falls back to PIL --
the package must keep working on machines without a toolchain.

The resize kernels are bit-exact with PIL's (see hostops.cc), verified by
tests/test_native.py, so the gateway can use whichever is available without
perturbing golden logits.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

_LIB_NAME = "libkdlthostops.so"


def _repo_native_dir() -> str | None:
    # <repo>/kubernetes_deep_learning_tpu/ops/_native.py -> <repo>/native
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    candidate = os.path.join(os.path.dirname(pkg), "native")
    return candidate if os.path.isfile(os.path.join(candidate, "hostops.cc")) else None


_SOURCES = ("hostops.cc", "batchqueue.cc")


def _build(source_dir: str) -> str:
    """Compile the sources into the user cache, keyed by their content (a
    fresh checkout has fresh mtimes, so mtimes cannot say "unchanged")."""
    srcs = [os.path.join(source_dir, s) for s in _SOURCES]
    digest = hashlib.sha256()
    for src in srcs:
        with open(src, "rb") as f:
            digest.update(f.read())
    cache = os.path.join(
        os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")),
        "kdlt",
    )
    os.makedirs(cache, exist_ok=True)
    out = os.path.join(cache, f"{digest.hexdigest()[:16]}-{_LIB_NAME}")
    if os.path.isfile(out):
        return out
    cxx = os.environ.get("CXX", "g++")
    tmp = f"{out}.{os.getpid()}.tmp"  # concurrent first imports must not load a half-written file
    cmd = [cxx, "-O3", "-std=c++17", "-fPIC", "-shared", "-o", tmp, *srcs, "-pthread"]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, out)
    return out


def _find_or_build() -> str:
    explicit = os.environ.get("KDLT_NATIVE_LIB")
    if explicit:
        return explicit
    native_dir = _repo_native_dir()
    if native_dir is not None:
        try:
            return _build(native_dir)
        except (OSError, subprocess.CalledProcessError):
            pass  # no compiler here: a packaged library may still exist
    packaged = os.path.join(os.path.dirname(os.path.abspath(__file__)), _LIB_NAME)
    if os.path.isfile(packaged):
        return packaged
    raise ImportError("no compiler for native/*.cc and no packaged libkdlthostops.so")


try:
    _lib = ctypes.CDLL(_find_or_build())
except Exception as e:  # toolchain or source missing: PIL fallback
    raise ImportError(f"native host ops unavailable: {e}") from e

_u8p = ctypes.POINTER(ctypes.c_uint8)
_f32p = ctypes.POINTER(ctypes.c_float)
_i64p = ctypes.POINTER(ctypes.c_int64)
try:
    for _fn, _args, _ret in (
        ("kdlt_resize_bilinear", [_u8p] + [ctypes.c_int] * 3 + [_u8p] + [ctypes.c_int] * 2, ctypes.c_int),
        ("kdlt_resize_nearest", [_u8p] + [ctypes.c_int] * 3 + [_u8p] + [ctypes.c_int] * 2, ctypes.c_int),
        ("kdlt_resize_batch", [_u8p] + [ctypes.c_int] * 4 + [_u8p] + [ctypes.c_int] * 4, ctypes.c_int),
        # Batch queue (native/batchqueue.cc), consumed by runtime.native_batcher.
        ("kdlt_bq_create", [ctypes.c_int, ctypes.c_int64, ctypes.c_int], ctypes.c_void_p),
        ("kdlt_bq_destroy", [ctypes.c_void_p], None),
        ("kdlt_bq_submit", [ctypes.c_void_p, _u8p], ctypes.c_int64),
        ("kdlt_bq_take", [ctypes.c_void_p, _u8p, ctypes.c_int, ctypes.c_double, ctypes.c_double, _i64p], ctypes.c_int),
        ("kdlt_bq_complete", [ctypes.c_void_p, _i64p, ctypes.c_int, _f32p, ctypes.c_int], None),
        ("kdlt_bq_fail", [ctypes.c_void_p, _i64p, ctypes.c_int], None),
        ("kdlt_bq_wait", [ctypes.c_void_p, ctypes.c_int64, _f32p, ctypes.c_double], ctypes.c_int),
        ("kdlt_bq_close", [ctypes.c_void_p], None),
        ("kdlt_bq_abort", [ctypes.c_void_p], None),
        ("kdlt_bq_pending", [ctypes.c_void_p], ctypes.c_int),
    ):
        fn = getattr(_lib, _fn)
        fn.argtypes = _args
        fn.restype = _ret
except AttributeError as e:
    # A stale prebuilt library missing newer symbols must surface as the
    # ImportError the module contract promises (callers fall back on it).
    raise ImportError(f"native library is stale: {e}") from e

lib = _lib  # raw handle for runtime.native_batcher


def _check(img: np.ndarray) -> np.ndarray:
    img = np.ascontiguousarray(img)
    if img.dtype != np.uint8 or img.ndim != 3:
        raise ValueError(f"expected uint8 HWC array, got {img.dtype} {img.shape}")
    return img


def resize_bilinear(img: np.ndarray, h: int, w: int) -> np.ndarray:
    img = _check(img)
    out = np.empty((h, w, img.shape[2]), np.uint8)
    rc = _lib.kdlt_resize_bilinear(
        img.ctypes.data_as(_u8p), img.shape[0], img.shape[1], img.shape[2],
        out.ctypes.data_as(_u8p), h, w,
    )
    if rc != 0:
        raise ValueError(f"kdlt_resize_bilinear failed (rc={rc})")
    return out


def resize_nearest(img: np.ndarray, h: int, w: int) -> np.ndarray:
    img = _check(img)
    out = np.empty((h, w, img.shape[2]), np.uint8)
    rc = _lib.kdlt_resize_nearest(
        img.ctypes.data_as(_u8p), img.shape[0], img.shape[1], img.shape[2],
        out.ctypes.data_as(_u8p), h, w,
    )
    if rc != 0:
        raise ValueError(f"kdlt_resize_nearest failed (rc={rc})")
    return out


def resize_batch(
    imgs: np.ndarray, h: int, w: int, filter: str = "bilinear", num_threads: int = 0
) -> np.ndarray:
    """Resize a (N,H,W,C) uint8 batch; shards across C++ threads (GIL-free)."""
    imgs = np.ascontiguousarray(imgs)
    if imgs.dtype != np.uint8 or imgs.ndim != 4:
        raise ValueError(f"expected uint8 NHWC array, got {imgs.dtype} {imgs.shape}")
    n, _, _, c = imgs.shape
    if num_threads <= 0:
        num_threads = min(n, os.cpu_count() or 1)
    out = np.empty((n, h, w, c), np.uint8)
    rc = _lib.kdlt_resize_batch(
        imgs.ctypes.data_as(_u8p), n, imgs.shape[1], imgs.shape[2], c,
        out.ctypes.data_as(_u8p), h, w,
        {"nearest": 0, "bilinear": 1}[filter], num_threads,
    )
    if rc != 0:
        raise ValueError(f"kdlt_resize_batch failed (rc={rc})")
    return out
