"""Image preprocessing: host-side decode/resize, device-side normalization.

The reference delegates all of this to the ``keras-image-helper`` package
(reference model_server.py:8,18,53: ``create_preprocessor('xception',
target_size=(299, 299)).from_url(url)``), which downloads the image, resizes
with PIL, and normalizes on the *host*.  TPU-first redesign:

- host side does only what must be on host: HTTP fetch, JPEG/PNG decode, and
  resize to the model's input resolution, staying in **uint8** (3x smaller on
  the gateway->server wire than f32);
- normalization (the elementwise scale/shift) runs **on device**, where XLA
  fuses it into the first convolution -- it never costs a separate HBM pass.

A C++ fast path for resize lives in native/ (see ``_native.resize`` below);
PIL is the fallback so the package works without the compiled library.
"""

from __future__ import annotations

import io
import os
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

try:  # optional C++ fast path (native/hostops.cc)
    from kubernetes_deep_learning_tpu.ops import _native
except ImportError:  # no toolchain and no KDLT_NATIVE_LIB: PIL resize
    _native = None

# Which resize implementation this process runs (bit-exact with each
# other, tests/test_native.py); surfaced on the model server's status page.
RESIZE_IMPL = "native" if _native is not None else "pil"

# Normalization constants, index-aligned with `modelspec.ModelSpec.preprocessing`.
#   tf    : x / 127.5 - 1            (Keras "tf" mode; Xception, reference
#           keras-image-helper behavior for create_preprocessor('xception'))
#   caffe : BGR, subtract ImageNet channel means (Keras "caffe" mode; ResNet50)
#   torch : x / 255, ImageNet mean/std (EfficientNet via torchvision convention)
_CAFFE_MEAN_BGR = np.array([103.939, 116.779, 123.68], np.float32)
_TORCH_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
_TORCH_STD = np.array([0.229, 0.224, 0.225], np.float32)

USER_AGENT = "kdlt-gateway/0.1"
FETCH_TIMEOUT_S = 10.0
MAX_FETCH_BYTES = 32 * 1024 * 1024  # reject pathological/streaming URLs

# Decode-pool sizing for the model tier's raw-bytes ingest stage (GUIDE
# 10q): threads running PIL/native decode+resize with the GIL released.
# Sized to the host's cores but capped -- decode work overlaps device
# execution, and an unbounded pool would let a burst of bytes-wire
# requests steal every core from the dispatch threads.
DECODE_POOL_ENV = "KDLT_DECODE_POOL"
DEFAULT_DECODE_POOL = max(2, min(8, os.cpu_count() or 4))


def resolve_decode_pool(explicit: int | None = None) -> int:
    """Explicit arg > $KDLT_DECODE_POOL > core-scaled default; always >= 1."""
    if explicit is not None:
        return max(1, int(explicit))
    raw = os.environ.get(DECODE_POOL_ENV, "")
    try:
        return max(1, int(raw)) if raw.strip() else DEFAULT_DECODE_POOL
    except ValueError:
        return DEFAULT_DECODE_POOL


def fetch_image_bytes(
    url: str, timeout: float = FETCH_TIMEOUT_S, max_bytes: int = MAX_FETCH_BYTES
) -> bytes:
    """Download raw image bytes (the reference gateway's .from_url step).

    The read is bounded: an attacker-supplied URL pointing at a multi-GB or
    endless stream must not OOM the gateway (the timeout only bounds
    inactivity, not transferred bytes).
    """
    req = urllib.request.Request(url, headers={"User-Agent": USER_AGENT})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        data = resp.read(max_bytes + 1)
    if len(data) > max_bytes:
        raise ValueError(f"image at {url!r} exceeds {max_bytes} byte limit")
    return data


def decode_image(data: bytes) -> np.ndarray:
    """Decode JPEG/PNG bytes to an RGB uint8 HWC array."""
    from PIL import Image

    with Image.open(io.BytesIO(data)) as img:
        if img.mode != "RGB":
            img = img.convert("RGB")
        # kdlt-lint: disable=hot-path-sync -- host decode IS the materialization: it runs in the GIL-released decode pool before any device dispatch, never on the dispatch side
        return np.asarray(img, dtype=np.uint8)


def resize_uint8(
    img: np.ndarray, size: tuple[int, int], filter: str = "bilinear"
) -> np.ndarray:
    """Resize an RGB uint8 HWC array to (H, W).

    ``filter`` comes from ModelSpec.resize_filter: the clothing model uses
    "nearest" because keras-image-helper (the reference's preprocessor,
    reference model_server.py:18) resizes with Image.NEAREST, and the filter
    choice shifts logits far beyond numerical tolerance.  Uses the in-tree
    C++ kernel when available (native/hostops.cc -- bit-exact with PIL for
    both filters, tests/test_native.py), else PIL.
    """
    if filter not in ("bilinear", "nearest"):
        raise ValueError(f"unknown resize filter {filter!r}")
    h, w = int(size[0]), int(size[1])
    if img.shape[0] == h and img.shape[1] == w:
        return np.ascontiguousarray(img)
    if _native is not None:
        fn = _native.resize_bilinear if filter == "bilinear" else _native.resize_nearest
        return fn(img, h, w)
    from PIL import Image

    filters = {"bilinear": Image.BILINEAR, "nearest": Image.NEAREST}
    pil = Image.fromarray(img)
    # kdlt-lint: disable=hot-path-sync -- PIL-fallback resize materializes on host by design (decode-pool stage, pre-dispatch); the native kernel path above avoids the copy
    return np.asarray(pil.resize((w, h), filters[filter]), dtype=np.uint8)


def preprocess_bytes(
    data: bytes, size: tuple[int, int], *, filter: str = "bilinear"
) -> np.ndarray:
    """bytes -> resized RGB uint8 HWC; the full host-side gateway pipeline."""
    return resize_uint8(decode_image(data), size, filter)


class BatchDecoder:
    """The model tier's vectorized decode stage (GUIDE 10q): a bytes-wire
    request's JPEG/PNG blobs -> one resized RGB uint8 (N,H,W,C) batch.

    Decode and resize run in a bounded thread pool: both PIL's decoders
    and the native resize kernel release the GIL, so a 32-image batch
    costs ~one image's wall time on an 8-thread pool instead of 32x
    serial Python.  Per-image failures raise ValueError naming the index
    -- the transports map that to a 400 (a corrupt blob is the CLIENT's
    error, never a 500, and never a crashed worker).

    This is the serving hot path's decode entry point: kdlt-lint's
    hot-path-sync pass roots here, so any future device-blocking call
    slipped into the stage is caught statically.
    """

    def __init__(self, workers: int | None = None):
        self.workers = resolve_decode_pool(workers)
        self._pool = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="kdlt-decode"
        )

    def _decode_one(self, i: int, blob: bytes, size, filter: str) -> np.ndarray:
        try:
            return preprocess_bytes(blob, size, filter=filter)
        except ValueError:
            raise
        except Exception as e:  # noqa: BLE001 - undecodable client bytes
            raise ValueError(f"image {i}: undecodable image bytes ({e})") from e

    def decode_batch(
        self, blobs: list[bytes], size: tuple[int, int], *,
        filter: str = "bilinear",
    ) -> np.ndarray:
        """Encoded blobs -> stacked uint8 (N,H,W,C) batch at ``size``."""
        if not blobs:
            raise ValueError("empty image batch")
        if len(blobs) == 1:
            # No pool hop for the single-image common case: the handler
            # thread decodes inline (the GIL releases either way).
            return self._decode_one(0, blobs[0], size, filter)[None]
        futures = [
            self._pool.submit(self._decode_one, i, blob, size, filter)
            for i, blob in enumerate(blobs)
        ]
        return np.stack([f.result() for f in futures])

    def close(self) -> None:
        self._pool.shutdown(wait=False)


def normalize(x, mode: str):
    """uint8/float image batch -> normalized float input, in jax or numpy.

    Works on both np.ndarray and jax.Array (pure elementwise ops); inside jit
    XLA fuses this into the consuming convolution.
    """
    if mode == "none":
        return x
    # Keep jax out of the pure-numpy (gateway host) path: jax init is heavy
    # and the gateway should not pay it. astype(np.float32) works for both.
    x = x.astype(np.float32)
    if mode == "tf":
        return x / 127.5 - 1.0
    if mode == "caffe":
        # RGB -> BGR, then subtract channel means (no scaling).
        x = x[..., ::-1]
        return x - _CAFFE_MEAN_BGR
    if mode == "torch":
        return (x / 255.0 - _TORCH_MEAN) / _TORCH_STD
    raise ValueError(f"unknown preprocessing mode {mode!r}")
