"""Gateway <-> model-server wire protocol.

The reference marshals numpy -> TensorProto -> gRPC PredictRequest
(reference model_server.py:35-43) and unmarshals ``float_val`` lists back
(reference model_server.py:46-49).  Here the wire is msgpack over HTTP with
**raw little-endian tensor bytes**, for two TPU-first reasons:

- images travel as uint8 (3x smaller than the reference's float32
  TensorProto; normalization happens on-device at the server), and
- zero-copy decode: the request's uint8 payload is never unpacked -- the
  envelope around it is walked and the batch is np.frombuffer over the
  request body itself (decode_msgpack_tensor), no per-float protobuf
  parsing and no copy of the pixels under the interpreter's lock.

A JSON fallback (``{"instances": [...]}``, TF-Serving REST style) is kept for
debuggability with curl.
"""

from __future__ import annotations

import json
import math
import os
import struct
from typing import Any

import msgpack
import numpy as np

MSGPACK_CONTENT_TYPE = "application/x-msgpack"
JSON_CONTENT_TYPE = "application/json"

# Raw-encoded-bytes ingest wire (GUIDE 10q): the request body carries the
# fetched JPEG/PNG bytes VERBATIM (msgpack list of bin blobs) and the model
# tier decodes+resizes them itself -- the wire cost per image is the encoded
# payload size, not a materialized uint8 tensor, and the fan-in gateway pays
# no per-image decode CPU.  Strictly opt-in both ways: a server advertises
# the capability on its spec-discovery response (INGEST_HEADER below) and a
# gateway only sends this content type to a tier that advertised it, so a
# mixed-version deployment degrades to the legacy tensor wire, never to an
# error.
BYTES_CONTENT_TYPE = "application/x-kdlt-image-bytes"

# Ingest-capability negotiation, carried on the existing spec-discovery
# handshake: the model tier stamps GET /v1/models/<name> responses with
# this header listing its ingest capabilities (comma-separated members of
# INGEST_CAPS); the gateway records it per model when it fetches the spec.
# An absent header (an old server) means tensor-wire only.  The capability
# vocabulary is CLOSED (kdlt-lint's closed-vocab pass keys on INGEST_CAPS):
# negotiation must never grow ad-hoc tokens two tiers spell differently.
INGEST_HEADER = "X-Kdlt-Ingest"
INGEST_BYTES_CAP = "bytes"
INGEST_CAPS = (INGEST_BYTES_CAP,)

# KDLT_INGEST gates the whole raw-bytes path on either tier: the server
# stops advertising (and accepting) the bytes content type, the gateway
# stops sending it.  Default ON -- negotiation already protects
# mixed-version fleets, so the knob is a rollback lever, not a ramp.
INGEST_ENV = "KDLT_INGEST"

# Per-blob byte bound on the decode side, mirroring the gateway's fetch
# bound (ops.preprocess.MAX_FETCH_BYTES): the tiers are separate processes
# and the model tier must bound memory on its own evidence.
MAX_ENCODED_IMAGE_BYTES = 32 * 1024 * 1024

# JPEG/PNG magic prefixes: the gateway's per-request fallback sniff.  Only
# payloads positively identified as one of the two supported container
# formats ride the bytes wire; anything exotic decodes at the gateway and
# falls back to the tensor wire for that request.
_JPEG_MAGIC = b"\xff\xd8\xff"
_PNG_MAGIC = b"\x89PNG\r\n\x1a\n"


def ingest_enabled(explicit: bool | None = None) -> bool:
    """Explicit arg > $KDLT_INGEST > enabled-by-default (the kill switch
    reverts both tiers to the legacy tensor-only wire)."""
    if explicit is not None:
        return bool(explicit)
    raw = os.environ.get(INGEST_ENV, "").strip().lower()
    return raw not in ("0", "false", "off", "no")


def parse_ingest_caps(raw: str | None) -> tuple[str, ...]:
    """Normalize an X-Kdlt-Ingest header into known capability tokens;
    unknown tokens are dropped (an old gateway meeting a future server
    must only ever see capabilities it understands)."""
    if not raw:
        return ()
    return tuple(
        tok for tok in (t.strip().lower() for t in raw.split(","))
        if tok in INGEST_CAPS
    )


def sniff_image_format(data: bytes) -> str | None:
    """JPEG/PNG container sniff by magic bytes; None for anything else
    (the per-request tensor-wire fallback trigger)."""
    if data.startswith(_JPEG_MAGIC):
        return "jpeg"
    if data.startswith(_PNG_MAGIC):
        return "png"
    return None


def encode_bytes_predict_request(blobs: list[bytes]) -> bytes:
    """Encoded image blobs -> msgpack request body (the bytes wire)."""
    return msgpack.packb({"images": [bytes(b) for b in blobs]})


def decode_bytes_predict_request(
    body: bytes, max_images: int | None = None,
) -> list[bytes]:
    """Inverse of :func:`encode_bytes_predict_request`, with the bounds a
    network-facing decoder needs: a list of non-empty bin blobs, each
    under MAX_ENCODED_IMAGE_BYTES, optionally capped in count.  Raises
    ValueError (the transports map it to a 400 -- malformed input is the
    CLIENT's error, never a 500)."""
    try:
        msg = msgpack.unpackb(body)
    except Exception as e:  # noqa: BLE001 - mapped to 400 by the caller
        raise ValueError(f"invalid msgpack body: {e}") from e
    if not isinstance(msg, dict) or "images" not in msg:
        raise ValueError('bytes request must be a msgpack map with "images"')
    blobs = msg["images"]
    if not isinstance(blobs, list) or not blobs:
        raise ValueError('"images" must be a non-empty list of image blobs')
    if max_images is not None and len(blobs) > max_images:
        raise ValueError(
            f"{len(blobs)} images exceeds the {max_images}-image limit"
        )
    for i, blob in enumerate(blobs):
        if not isinstance(blob, (bytes, bytearray)) or not blob:
            raise ValueError(f"image {i} is not a non-empty binary blob")
        if len(blob) > MAX_ENCODED_IMAGE_BYTES:
            raise ValueError(
                f"image {i} ({len(blob)} bytes) exceeds the "
                f"{MAX_ENCODED_IMAGE_BYTES}-byte per-image limit"
            )
    return [bytes(b) for b in blobs]

# The generative lane's streamed response body: Server-Sent Events over
# HTTP/1.1 chunked transfer.  Every streamed token is one ``data:`` event;
# the terminal event carries ``"done": true`` plus the per-token SLO
# numbers (TTFT/TPOT) so clients never have to clock the stream
# themselves.  A response with this content type is a live connection,
# not a value: the response cache and singleflight refuse it by predicate
# (serving.cache.storable_response).
EVENT_STREAM_CONTENT_TYPE = "text/event-stream"

# Multi-model routing header: names the served model a /predict request
# targets when the URL path carries no model segment (the gateway's
# /predict/<model> form wins when both are present).  Lives here -- the
# wire-contract module -- so the dependency-light client never has to
# import the gateway to spell it.
MODEL_HEADER = "X-Kdlt-Model"

# Response-cache wire surface (serving.cache).  Request: a client salts the
# gateway's content hash with X-Kdlt-Cache-Bust to deliberately opt a load
# test out of the cache (identical salts still coalesce).  Response: the
# gateway stamps every /predict answer with its cache disposition
# (hit | miss | coalesced) so clients and load tools can account for it.
CACHE_BUST_HEADER = "X-Kdlt-Cache-Bust"
CACHE_STATUS_HEADER = "X-Kdlt-Cache"

# The model tier stamps every 200 :predict response with the serving
# artifact's sha256 identity (serving.registry.artifact_hash).  The
# gateway's response cache keys validity on it: a hot reload that changes
# the bytes changes the hash and drops that model's entries, while a
# version bump with identical bytes keeps them.
ARTIFACT_HASH_HEADER = "X-Kdlt-Artifact-Hash"

# A model-tier 503 carrying this header declares a terminal dispatch
# stall (the engine watchdog fired: /healthz is failing, only a restart
# recovers).  The gateway's upstream pool takes the replica out of
# rotation IMMEDIATELY on seeing it -- unlike an overload 503, which is
# transient evidence that takes consecutive failures to act on.
STALLED_HEADER = "X-Kdlt-Stalled"

# Request priority class (DAGOR-style bounded set).  Propagated
# client -> gateway -> model tier so BOTH admission controllers shed the
# lowest class first and the scheduler relaxes low-class effective
# deadlines.  The set is closed by construction: an unknown or absent
# header value falls back to the default, so the ``class`` metric label
# stays bounded no matter what a caller sends.  Lives here -- the
# wire-contract module -- so the dependency-light client can spell it
# without importing the serving tiers.
PRIORITY_HEADER = "X-Kdlt-Priority"
PRIORITY_CLASSES = ("interactive", "batch", "best-effort")
DEFAULT_PRIORITY = "interactive"
# Shed order: HIGHER rank sheds first (best-effort before batch before
# interactive); grant order is the reverse.
PRIORITY_RANK = {name: rank for rank, name in enumerate(PRIORITY_CLASSES)}


def parse_priority(raw: str | None) -> str:
    """Normalize an X-Kdlt-Priority header value into the bounded class
    set; anything absent, empty, or unrecognized is ``interactive`` (the
    default must be the HIGHEST class: a legacy client that never heard of
    priorities keeps its pre-priority service level)."""
    if not raw:
        return DEFAULT_PRIORITY
    value = raw.strip().lower()
    return value if value in PRIORITY_RANK else DEFAULT_PRIORITY


def encode_tensor(arr: np.ndarray) -> dict[str, Any]:
    arr = np.ascontiguousarray(arr)
    return {
        "shape": list(arr.shape),
        "dtype": arr.dtype.name,
        "data": arr.tobytes(),
    }


def decode_tensor(d: dict[str, Any]) -> np.ndarray:
    arr = np.frombuffer(d["data"], dtype=np.dtype(d["dtype"]))
    return arr.reshape(d["shape"])


def encode_predict_request(images: np.ndarray) -> bytes:
    """uint8 (N,H,W,C) batch -> msgpack request body."""
    return msgpack.packb({"inputs": encode_tensor(images)})


# --- the tensor wire's envelope, walked without unpacking the pixels -------
# msgpack.unpackb copies the ``data`` bin into a fresh bytes object inside
# one C call that holds the interpreter's lock for the whole copy (~160 ms
# for a 137 MB batch), and every other handler's socket read stands still
# meanwhile.  The envelope around the pixels is a few dozen bytes, so it is
# walked here and the pixels are handed on as a view of the request body.
# Each entry: (first, last) format byte of the fix form carrying its own
# length, then the wide forms' big-endian length fields.
_MAP = ((0x80, 0x8F), {0xDE: ">H", 0xDF: ">I"})
_ARRAY = ((0x90, 0x9F), {0xDC: ">H", 0xDD: ">I"})
_STR = ((0xA0, 0xBF), {0xD9: ">B", 0xDA: ">H", 0xDB: ">I"})
_BIN = (None, {0xC4: ">B", 0xC5: ">H", 0xC6: ">I"})
_UINT = ((0x00, 0x7F), {0xCC: ">B", 0xCD: ">H", 0xCE: ">I", 0xCF: ">Q"})
# What encode_tensor names a one-byte element.  Wider elements would come
# out unaligned at the bin's offset in the body; they take the copy.
_BYTE_DTYPES = {name: np.dtype(name.decode()) for name in (b"uint8", b"int8", b"bool")}


class _NotPlain(Exception):
    """The body is not the plain tensor envelope: unpack it the long way."""


def _length(buf: memoryview, off: int, kind) -> tuple[int, int]:
    """The length (or value, for _UINT) a header at ``off`` carries, and
    the offset just past the header."""
    fix, wide = kind
    head = buf[off]
    if fix is not None and fix[0] <= head <= fix[1]:
        return head - fix[0], off + 1
    fmt = wide.get(head)
    if fmt is None:
        raise _NotPlain
    return struct.unpack_from(fmt, buf, off + 1)[0], off + 1 + struct.calcsize(fmt)


def _text(buf: memoryview, off: int) -> tuple[bytes, int]:
    n, off = _length(buf, off, _STR)
    if off + n > len(buf):
        raise _NotPlain
    return bytes(buf[off:off + n]), off + n


def _walk_envelope(buf: memoryview) -> np.ndarray:
    """``{"inputs": {"shape": [...], "dtype": <one byte>, "data": bin}}``,
    inner keys in any order, nothing before or after -> the bin as an
    array over ``buf``.  Raises _NotPlain (or runs off the end) otherwise."""
    n, off = _length(buf, 0, _MAP)
    key, off = _text(buf, off)
    if n != 1 or key != b"inputs":
        raise _NotPlain
    n, off = _length(buf, off, _MAP)
    if n != 3:
        raise _NotPlain
    seen = {}
    for _ in range(3):
        key, off = _text(buf, off)
        if key in seen:
            raise _NotPlain
        if key == b"shape":
            rank, off = _length(buf, off, _ARRAY)
            dims = []
            for _ in range(rank):
                dim, off = _length(buf, off, _UINT)
                dims.append(dim)
            seen[key] = dims
        elif key == b"dtype":
            name, off = _text(buf, off)
            seen[key] = _BYTE_DTYPES.get(name)
        elif key == b"data":
            nbytes, off = _length(buf, off, _BIN)
            seen[key] = (off, nbytes)
            off += nbytes
        else:
            raise _NotPlain
    start, nbytes = seen[b"data"]
    if off != len(buf) or seen[b"dtype"] is None or math.prod(seen[b"shape"]) != nbytes:
        raise _NotPlain
    pixels = np.frombuffer(buf[start:start + nbytes], dtype=seen[b"dtype"])
    return pixels.reshape(seen[b"shape"])


def decode_msgpack_tensor(body: bytes) -> tuple[np.ndarray, bool]:
    """The tensor wire's request body -> (batch, zero_copy).

    A plain envelope around one-byte elements (the production wire: uint8
    pixels) comes back as a read-only view of ``body`` whose base keeps
    the body alive -- nothing of the payload's size is created and the
    interpreter's lock is held for microseconds.  Anything else (float32
    debug tensors, extra keys, ext types, a bin that does not match its
    shape, a truncated or over-long body) goes through msgpack.unpackb and
    answers, or raises, exactly what it always did.
    """
    try:
        return _walk_envelope(memoryview(body)), True
    except (_NotPlain, IndexError, struct.error):
        pass
    msg = msgpack.unpackb(body)
    return decode_tensor(msg["inputs"]), False


def decode_predict_request(body: bytes, content_type: str) -> np.ndarray:
    if content_type.startswith(MSGPACK_CONTENT_TYPE):
        return decode_msgpack_tensor(body)[0]
    if content_type.startswith(JSON_CONTENT_TYPE) or not content_type:
        msg = json.loads(body)
        arr = np.asarray(msg["instances"])
        if arr.dtype.kind in "iu":
            if arr.size and (arr.min() < 0 or arr.max() > 255):
                raise ValueError(
                    "integer pixel values must be in [0, 255]; send floats "
                    "for pre-normalized data"
                )
            arr = arr.astype(np.uint8)
        elif arr.dtype != np.float32:
            arr = arr.astype(np.float32)
        return arr
    raise ValueError(f"unsupported content type {content_type!r}")


def encode_predict_response(
    logits: np.ndarray, labels: tuple[str, ...], content_type: str
) -> tuple[bytes, str]:
    if content_type.startswith(MSGPACK_CONTENT_TYPE):
        body = msgpack.packb(
            {"outputs": encode_tensor(logits), "labels": list(labels)}
        )
        return body, MSGPACK_CONTENT_TYPE
    scores = [dict(zip(labels, map(float, row))) for row in logits]
    return json.dumps({"predictions": scores}).encode(), JSON_CONTENT_TYPE


def decode_predict_response(body: bytes, content_type: str) -> tuple[np.ndarray, list[str]]:
    if content_type.startswith(MSGPACK_CONTENT_TYPE):
        msg = msgpack.unpackb(body)
        return decode_tensor(msg["outputs"]), list(msg["labels"])
    msg = json.loads(body)
    preds = msg["predictions"]
    labels = list(preds[0].keys())
    return np.asarray([[p[l] for l in labels] for p in preds], np.float32), labels


# --- generative lane --------------------------------------------------------
# JSON request, SSE response.  A request carries exactly one of ``prompt``
# (text: byte-level tokenization happens in the decode engine, for a model
# that has a text codec) and ``token_ids`` (the model sees them as they
# are: nothing is put before them; the lane checks them against its
# vocabulary).  Every knob has a server-side cap.

GENERATE_MAX_NEW_TOKENS_CAP = 1024
GENERATE_TOP_LOGITS_CAP = 32
GENERATE_MAX_PROMPT_IDS = 1 << 17


def _int_field(msg: dict, name: str, default: int, low: int, high: int) -> int:
    raw = msg.get(name, default)
    if isinstance(raw, bool):
        raise ValueError(f'"{name}" must be an integer')
    try:
        n = int(raw)
    except (TypeError, ValueError) as e:
        raise ValueError(f'"{name}" must be an integer') from e
    if n < low or n > high:
        raise ValueError(f'"{name}" must be in [{low}, {high}]')
    return n


def decode_generate_request(body: bytes) -> dict[str, Any]:
    """Parse and validate a /generate JSON body.

    Returns ``{"prompt": str | None, "token_ids": list[int] | None,
    "max_new_tokens": int, "ignore_eos": bool, "top_logits": int, "stream":
    bool}``: exactly one of ``prompt`` and ``token_ids`` is set;
    ``top_logits`` k > 0 asks every token frame for the k largest logits of
    its step with their ids.  Raises ValueError on anything malformed --
    the transports map that to a 400, same as a bad /predict body.
    """
    try:
        msg = json.loads(body)
    except Exception as e:  # noqa: BLE001 - mapped to 400 by the caller
        raise ValueError(f"invalid JSON body: {e}") from e
    if not isinstance(msg, dict) or ("prompt" in msg) == ("token_ids" in msg):
        raise ValueError(
            'generate body must be a JSON object with exactly one of "prompt" '
            'and "token_ids"'
        )
    prompt = msg.get("prompt")
    token_ids = msg.get("token_ids")
    if token_ids is None:
        if not isinstance(prompt, str) or not prompt:
            raise ValueError('"prompt" must be a non-empty string')
    else:
        if (not isinstance(token_ids, list) or not token_ids
                or len(token_ids) > GENERATE_MAX_PROMPT_IDS):
            raise ValueError('"token_ids" must be a non-empty list of token ids')
        if any(isinstance(t, bool) or not isinstance(t, int) or t < 0
               for t in token_ids):
            raise ValueError('"token_ids" must hold non-negative integers')
    return {
        "prompt": prompt,
        "token_ids": token_ids,
        "max_new_tokens": _int_field(
            msg, "max_new_tokens", 16, 1, GENERATE_MAX_NEW_TOKENS_CAP),
        "ignore_eos": bool(msg.get("ignore_eos", False)),
        "top_logits": _int_field(msg, "top_logits", 0, 0, GENERATE_TOP_LOGITS_CAP),
        "stream": bool(msg.get("stream", True)),
    }


def sse_event(payload: dict[str, Any]) -> bytes:
    """One Server-Sent Events frame: ``data: <json>\\n\\n``."""
    return b"data: " + json.dumps(payload, separators=(",", ":")).encode() + b"\n\n"


def sse_token_event(index: int, token: int, text: str, top_ids=None,
                    top_logits=None) -> bytes:
    """A per-token event: position, token id, its decoded text and, where
    the request asked for them, the step's largest logits with their ids
    (largest first, so greedy decoding has ``top_ids[0] == token``)."""
    payload = {"index": index, "token": token, "text": text}
    if top_ids is not None:
        payload["top_ids"] = top_ids
        payload["top_logits"] = top_logits
    return sse_event(payload)


def sse_done_event(
    *, tokens: int, ttft_ms: float, tpot_ms: float, finish_reason: str,
    text: str,
) -> bytes:
    """The terminal event: totals plus the per-token SLO observations."""
    return sse_event({
        "done": True,
        "tokens": tokens,
        "ttft_ms": round(ttft_ms, 3),
        "tpot_ms": round(tpot_ms, 3),
        "finish_reason": finish_reason,
        "text": text,
    })


def parse_sse_events(raw: bytes) -> list[dict[str, Any]]:
    """Split a complete SSE body back into its JSON payloads (client and
    test-side helper; tolerant of a trailing partial frame)."""
    events: list[dict[str, Any]] = []
    for frame in raw.split(b"\n\n"):
        frame = frame.strip()
        if not frame.startswith(b"data:"):
            continue
        try:
            events.append(json.loads(frame[len(b"data:"):].strip()))
        except Exception:  # noqa: BLE001 - partial tail frame
            continue
    return events
