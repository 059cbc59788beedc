"""The msgpack tensor wire: the pixels reach the engine as a view of the body.

`protocol.decode_msgpack_tensor` walks the envelope around the ``data`` bin
and hands the bin on in place when the body is the plain production wire
(one-byte elements); everything else goes through msgpack.unpackb and
answers, or raises, what it did before the walker existed.  The reference
in these tests is that older decode, spelled out.
"""

from __future__ import annotations

import gc
import itertools
import struct
import tempfile
import threading
import time

import msgpack
import numpy as np
import pytest
import requests

from kubernetes_deep_learning_tpu.export import artifact as art
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
from kubernetes_deep_learning_tpu.serving import protocol
from kubernetes_deep_learning_tpu.serving.model_server import ModelServer
from kubernetes_deep_learning_tpu.serving.tracing import REQUEST_ID_HEADER

MSGPACK = protocol.MSGPACK_CONTENT_TYPE


def unpack_and_copy(body: bytes) -> np.ndarray:
    """The decode as it was: the whole body unpacked, the bin copied."""
    return protocol.decode_tensor(msgpack.unpackb(body)["inputs"])


def pixels(*shape: int, dtype=np.uint8) -> np.ndarray:
    rng = np.random.default_rng(sum(shape))
    return rng.integers(0, 100, size=shape).astype(dtype)


def pack_ordered(arr: np.ndarray, order=("shape", "dtype", "data")) -> bytes:
    fields = protocol.encode_tensor(arr)
    return msgpack.packb({"inputs": {k: fields[k] for k in order}})


def body_span(body: bytes) -> np.ndarray:
    return np.frombuffer(body, np.uint8)


# --- the view path ---------------------------------------------------------


@pytest.mark.parametrize(
    "order", list(itertools.permutations(("shape", "dtype", "data"))),
    ids="-".join,
)
def test_any_key_order_comes_back_as_a_view_of_the_body(order):
    arr = pixels(3, 8, 8, 3)
    body = pack_ordered(arr, order)
    got, zero_copy = protocol.decode_msgpack_tensor(body)
    assert zero_copy
    assert np.shares_memory(got, body_span(body))
    assert not got.flags.writeable
    assert got.dtype == np.uint8 and got.shape == arr.shape
    np.testing.assert_array_equal(got, arr)
    np.testing.assert_array_equal(got, unpack_and_copy(body))


@pytest.mark.parametrize(
    "n, header",
    [(200, b"\xc4\xc8"), (60_000, b"\xc5\xea\x60"), (70_000, b"\xc6\x00\x01\x11\x70")],
    ids=["bin8", "bin16", "bin32"],
)
def test_every_bin_width_is_a_view(n, header):
    arr = pixels(n)
    body = protocol.encode_predict_request(arr)
    assert header + arr.tobytes() in body
    got, zero_copy = protocol.decode_msgpack_tensor(body)
    assert zero_copy and np.shares_memory(got, body_span(body))
    np.testing.assert_array_equal(got, arr)


@pytest.mark.parametrize("dtype", ["uint8", "int8", "bool"])
def test_one_byte_dtypes_are_views(dtype):
    arr = pixels(4, 5).astype(dtype)
    body = protocol.encode_predict_request(arr)
    got, zero_copy = protocol.decode_msgpack_tensor(body)
    assert zero_copy and got.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(got, arr)


def wide_envelope(arr: np.ndarray) -> bytes:
    """The same envelope as another client's packer may spell it: map16,
    str8 keys, array16, every dimension a uint of another width."""
    def str8(s: str) -> bytes:
        return b"\xd9" + bytes([len(s)]) + s.encode()

    widths = [(0xCC, ">B"), (0xCD, ">H"), (0xCE, ">I"), (0xCF, ">Q")]
    dims = b"".join(
        bytes([widths[i % 4][0]]) + struct.pack(widths[i % 4][1], d)
        for i, d in enumerate(arr.shape)
    )
    return (
        b"\xde\x00\x01" + str8("inputs") + b"\xdf\x00\x00\x00\x03"
        + str8("shape") + b"\xdc" + struct.pack(">H", arr.ndim) + dims
        + str8("dtype") + str8(arr.dtype.name)
        + str8("data") + b"\xc6" + struct.pack(">I", arr.size) + arr.tobytes()
    )


def test_wide_headers_are_walked_too():
    arr = pixels(2, 3, 4, 5)
    body = wide_envelope(arr)
    np.testing.assert_array_equal(unpack_and_copy(body), arr)
    got, zero_copy = protocol.decode_msgpack_tensor(body)
    assert zero_copy and np.shares_memory(got, body_span(body))
    np.testing.assert_array_equal(got, arr)


@pytest.mark.parametrize("shape", [(), (0, 4, 4, 3), (1,)], ids=str)
def test_edge_shapes_agree_with_the_copy(shape):
    arr = pixels(*shape) if shape else np.uint8(7)
    body = protocol.encode_predict_request(np.asarray(arr))
    got, zero_copy = protocol.decode_msgpack_tensor(body)
    want = unpack_and_copy(body)
    assert zero_copy and got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_the_view_keeps_the_body_alive():
    arr = pixels(64, 64, 3)
    got = protocol.decode_predict_request(
        protocol.encode_predict_request(arr), MSGPACK
    )
    # The array holds the only reference to the body now.
    gc.collect()
    bytes(len(arr.tobytes()) + 64)  # something else may take a freed block
    np.testing.assert_array_equal(got, arr)


# --- the copy path: bodies that are answered, just not in place -----------


def _with_outer_key(arr):
    return msgpack.packb({"inputs": protocol.encode_tensor(arr), "note": "x"})


def _with_inner_key(arr):
    return msgpack.packb({"inputs": {**protocol.encode_tensor(arr), "v": 1}})


def _ext_beside(arr):
    return msgpack.packb(
        {"inputs": protocol.encode_tensor(arr), "t": msgpack.ExtType(5, b"ab")}
    )


def _inferred_dim(arr):
    fields = protocol.encode_tensor(arr)
    return msgpack.packb({"inputs": {**fields, "shape": [-1, *arr.shape[1:]]}})


def _duplicate_key(arr):
    # A hand-made map of three entries whose first two are both "dtype":
    # unpackb keeps the last.
    fields = protocol.encode_tensor(arr)
    k = msgpack.packb
    return (
        b"\x81" + k("inputs") + b"\x84"
        + k("dtype") + k("float64") + k("dtype") + k(fields["dtype"])
        + k("shape") + k(fields["shape"]) + k("data") + k(fields["data"])
    )


def _bytes_keys(arr):
    return msgpack.packb(
        {"inputs": {k.encode(): v for k, v in protocol.encode_tensor(arr).items()}}
    )


def _dtype_by_code(arr):
    return msgpack.packb({"inputs": {**protocol.encode_tensor(arr), "dtype": "B"}})


COPY_BODIES = {
    "float32": lambda a: protocol.encode_predict_request(a.astype(np.float32)),
    "int16": lambda a: protocol.encode_predict_request(a.astype(np.int16)),
    "outer-key": _with_outer_key,
    "inner-key": _with_inner_key,
    "ext-type": _ext_beside,
    "inferred-dim": _inferred_dim,
    "duplicate-key": _duplicate_key,
    "dtype-by-code": _dtype_by_code,
}


@pytest.mark.parametrize("kind", sorted(COPY_BODIES))
def test_unusual_bodies_take_the_copy_and_equal_the_old_result(kind):
    body = COPY_BODIES[kind](pixels(2, 6, 6, 3))
    want = unpack_and_copy(body)
    got, zero_copy = protocol.decode_msgpack_tensor(body)
    assert not zero_copy
    assert not np.shares_memory(got, body_span(body))
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        protocol.decode_predict_request(body, MSGPACK), want
    )


# --- bodies that were refused are refused the same way --------------------

GOOD = protocol.encode_predict_request(pixels(2, 6, 6, 3))


def _fields(**changed):
    return msgpack.packb(
        {"inputs": {**protocol.encode_tensor(pixels(2, 6, 6, 3)), **changed}}
    )


BAD_BODIES = {
    "bin-longer-than-shape": _fields(data=bytes(2 * 6 * 6 * 3 + 1)),
    "bin-shorter-than-shape": _fields(data=bytes(5)),
    "truncated-in-the-bin": GOOD[:-1],
    "truncated-in-the-envelope": GOOD[:12],
    "truncated-in-a-wide-header": wide_envelope(pixels(2, 3))[:2],
    "empty": b"",
    "trailing-byte": GOOD + b"\x00",
    "trailing-map": GOOD + GOOD,
    "shape-is-text": _fields(shape="2x6x6x3"),
    "shape-holds-text": _fields(shape=[2, 6, "6", 3]),
    "dtype-is-a-number": _fields(dtype=8),
    "dtype-unknown": _fields(dtype="pixels"),
    "data-is-a-list": _fields(data=[1, 2, 3]),
    "data-is-text": _fields(data="abc"),
    "inputs-missing": msgpack.packb({"instances": [1]}),
    "inputs-not-a-map": msgpack.packb({"inputs": [1, 2]}),
    "data-missing": msgpack.packb({"inputs": {"shape": [1], "dtype": "uint8"}}),
    "top-level-list": msgpack.packb([1, 2, 3]),
    "reserved-byte": b"\xc1",
    "bytes-keys": _bytes_keys(pixels(2, 6, 6, 3)),
}


@pytest.mark.parametrize("kind", sorted(BAD_BODIES))
def test_refused_bodies_raise_what_unpackb_and_decode_tensor_raise(kind):
    body = BAD_BODIES[kind]
    with pytest.raises(Exception) as old:  # noqa: PT011 - the class is the datum
        unpack_and_copy(body)
    with pytest.raises(Exception) as new:  # noqa: PT011
        protocol.decode_msgpack_tensor(body)
    assert type(new.value) is type(old.value), (old.value, new.value)
    assert str(new.value) == str(old.value)
    with pytest.raises(type(old.value)):
        protocol.decode_predict_request(body, MSGPACK)


# --- the interpreter's lock ------------------------------------------------


def _longest_stall_while(fn, trials: int = 5) -> float:
    """The longest time a second thread's tight Python loop stood still
    while ``fn`` ran on this one: the least over a few trials, since the
    machine's other work only ever adds to it."""
    best = float("inf")
    for _ in range(trials):
        stop, spinning, worst = threading.Event(), threading.Event(), [0.0]

        def spin():
            last = time.perf_counter()
            spinning.set()
            while not stop.is_set():
                now = time.perf_counter()
                worst[0] = max(worst[0], now - last)
                last = now
            # The gap in which stop was set is the one that held fn.
            worst[0] = max(worst[0], time.perf_counter() - last)

        t = threading.Thread(target=spin)
        t.start()
        spinning.wait()
        time.sleep(0.02)  # the spinner owns the interpreter now
        worst[0] = 0.0
        fn()
        stop.set()
        t.join()
        best = min(best, worst[0])
    return best


def test_decoding_a_64mb_body_does_not_hold_up_another_thread():
    """What the front's rate rests on: while one handler decodes a batch,
    the others' socket reads (a Python loop of recv_into calls) keep the
    interpreter.  unpackb held it for the whole copy of the payload."""
    body = protocol.encode_predict_request(np.ones((64, 1024, 1024), np.uint8))
    out = []
    view_stall = _longest_stall_while(
        lambda: out.append(protocol.decode_msgpack_tensor(body))
    )
    assert out[-1][1] and out[-1][0].shape == (64, 1024, 1024)
    assert view_stall < 5e-3, f"decode held the interpreter {view_stall * 1e3:.1f} ms"
    # The test can tell: the old decode of the same body stalls the spinner
    # for the length of its copy.
    copy_stall = _longest_stall_while(lambda: unpack_and_copy(body), trials=3)
    assert copy_stall > 3 * view_stall, (copy_stall, view_stall)


# --- through the server: the counter and the span's attribute --------------


@pytest.fixture(scope="module")
def stub_server(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("tensor-wire"))
    spec = register_spec(
        ModelSpec(
            name="tensor-wire-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(16, 16, 3),
            labels=("a", "b", "c"),
        )
    )
    root = tempfile.mkdtemp(prefix="kdlt-tensor-wire-", dir=tmp)
    art.save_artifact(
        art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
    )
    server = ModelServer(
        root, port=0, buckets=(1, 2), max_delay_ms=1.0, host="127.0.0.1",
        batcher_impl="python",
        engine_factory=lambda a, **kw: StubEngine(
            a, device_ms_per_batch=1.0, async_device=True, **kw
        ),
    )
    server.warmup()
    server.start()
    yield spec, server
    server.shutdown()


def _unpack_counts(server) -> dict:
    text = requests.get(f"http://127.0.0.1:{server.port}/metrics", timeout=5).text
    counts = {}
    for line in text.splitlines():
        if line.startswith("kdlt_server_unpack_total{"):
            labels, value = line.rsplit(" ", 1)
            counts[labels.split('path="')[1].split('"')[0]] = float(value)
    return counts


def _unpack_span(server, rid: str) -> dict:
    deadline = time.monotonic() + 3.0
    while True:  # the root span records just after the response went out
        r = requests.get(
            f"http://127.0.0.1:{server.port}/debug/trace/{rid}", timeout=5
        )
        spans = r.json()["spans"] if r.status_code == 200 else []
        by = {s["name"]: s for s in spans}
        if "server.request" in by or time.monotonic() > deadline:
            return by["server.unpack"]
        time.sleep(0.02)


@pytest.mark.parametrize(
    "dtype, path", [(np.uint8, "view"), (np.float32, "copy")],
    ids=["uint8-view", "float32-copy"],
)
def test_server_counts_the_path_and_tags_the_span(stub_server, dtype, path):
    spec, server = stub_server
    other = "copy" if path == "view" else "view"
    before = _unpack_counts(server)
    assert set(before) == {"view", "copy"}
    rid = f"tensor-wire-{path}"
    r = requests.post(
        f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict",
        data=protocol.encode_predict_request(np.zeros((2, 16, 16, 3), dtype)),
        headers={"Content-Type": MSGPACK, REQUEST_ID_HEADER: rid},
        timeout=30,
    )
    assert r.status_code == 200
    logits, labels = protocol.decode_predict_response(
        r.content, r.headers["Content-Type"]
    )
    assert logits.shape == (2, 3) and labels == list(spec.labels)
    after = _unpack_counts(server)
    assert after[path] == before[path] + 1
    assert after[other] == before[other]
    span = _unpack_span(server, rid)
    assert span["tags"]["zero_copy"] is (path == "view")


def test_json_and_refused_msgpack_bodies_leave_the_counter_alone(stub_server):
    spec, server = stub_server
    url = f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict"
    before = _unpack_counts(server)
    r = requests.post(
        url, json={"instances": np.zeros((1, 16, 16, 3), int).tolist()}, timeout=30
    )
    assert r.status_code == 200
    r = requests.post(
        url, data=BAD_BODIES["truncated-in-the-bin"],
        headers={"Content-Type": MSGPACK}, timeout=30,
    )
    assert r.status_code == 400  # as before: unpackb's ValueError
    assert _unpack_counts(server) == before
