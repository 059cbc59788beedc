"""The traffic's pictures, from a seed: sources for the URL entry and
model-sized pixels for the tensor entry.

Every picture is a smooth two-dimensional wave per channel plus a few flat
rectangles, so JPEG has structure to encode and a resize has edges to
blur.  The wave is separable, ``cos(y/b + q) * sin(x/a + p)``, so a picture
costs two short cosines and an outer product, not a cosine per pixel.
"""

from __future__ import annotations

import io
import struct
import zlib

import numpy as np


def picture(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """One uint8 RGB picture of h x w."""
    yy, xx = np.arange(h, dtype=np.float32), np.arange(w, dtype=np.float32)
    img = np.empty((h, w, 3), np.float32)
    for c in range(3):
        row = np.sin(xx / rng.uniform(20, 90) + rng.uniform(0, 6))
        col = np.cos(yy / rng.uniform(20, 90) + rng.uniform(0, 6))
        img[:, :, c] = 127.5 + 127.5 * np.outer(col, row)
    for _ in range(4):
        y0, x0 = int(rng.integers(0, h - h // 8)), int(rng.integers(0, w - w // 8))
        dy, dx = int(rng.integers(h // 16, h // 3)), int(rng.integers(w // 16, w // 3))
        img[y0:y0 + dy, x0:x0 + dx] = rng.integers(0, 256, size=3)
    return np.clip(img, 0, 255).astype(np.uint8)


def tensor_pool(seed: int, n: int, shape: tuple[int, int, int]) -> np.ndarray:
    """n pictures at the model's own input size: uint8 (n, H, W, 3)."""
    rng = np.random.default_rng([int(seed), 0x71C])
    h, w, _ = shape
    return np.stack([picture(rng, h, w) for _ in range(n)])


def encoded_pool(seed: int, params: dict) -> list[tuple[str, bytes]]:
    """The URL entry's sources: (format, bytes), alternating the formats,
    sides drawn between ``side_min`` and ``side_max``."""
    from PIL import Image

    rng = np.random.default_rng([int(seed), 0x71C])
    formats = params["formats"]
    out = []
    for i in range(int(params["pool"])):
        h = int(rng.integers(params["side_min"], params["side_max"] + 1))
        w = int(rng.integers(params["side_min"], params["side_max"] + 1))
        fmt = formats[i % len(formats)]
        buf = io.BytesIO()
        img = Image.fromarray(picture(rng, h, w))
        if fmt == "jpeg":
            img.save(buf, "JPEG", quality=int(params["jpeg_quality"]))
        elif fmt == "png":
            img.save(buf, "PNG", compress_level=int(params.get("png_level", 1)))
        else:
            raise ValueError(f"unknown picture format {fmt!r}")
        out.append((fmt, buf.getvalue()))
    return out


def calibration_pixels(seed: int, n: int, side: int) -> np.ndarray:
    """A few pictures at a reduced size, for weights.calibrate."""
    rng = np.random.default_rng([int(seed), 0xCA1])
    return np.stack([picture(rng, side, side) for _ in range(n)])


def stamp(fmt: str, data: bytes, note: bytes) -> bytes:
    """The same picture in bytes no other request has: a JPEG comment
    segment after SOI, or a PNG tEXt chunk after IHDR -- what a timestamp in
    an upload's metadata does.  The decoded pixels do not change."""
    if fmt == "jpeg":
        if data[:2] != b"\xff\xd8":
            raise ValueError("not a JPEG")
        return data[:2] + b"\xff\xfe" + struct.pack(">H", len(note) + 2) + note + data[2:]
    if fmt == "png":
        end_ihdr = 8 + 4 + 4 + 13 + 4
        body = b"tEXt" + b"Comment\x00" + note
        chunk = struct.pack(">I", len(body) - 4) + body + struct.pack(">I", zlib.crc32(body))
        return data[:end_ihdr] + chunk + data[end_ihdr:]
    raise ValueError(f"unknown picture format {fmt!r}")
