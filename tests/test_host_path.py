"""Host-path components: StubEngine, engine_factory injection, gateway
upstream micro-batching.

These are the moving parts of the HTTP + protocol + batcher path with the
device taken out, so their correctness is tested in isolation: checksum
logits must be per-image (misrouted batcher responses fail loudly),
and the micro-batcher must coalesce without crossing responses.
"""

from __future__ import annotations

import socket
import threading

import numpy as np
import pytest

from kubernetes_deep_learning_tpu.export import artifact as art
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.ops import preprocess
from kubernetes_deep_learning_tpu.runtime.stub import StubEngine, stub_logits
from kubernetes_deep_learning_tpu.serving.microbatch import UpstreamMicroBatcher


@pytest.fixture(scope="module")
def stub_spec():
    return register_spec(
        ModelSpec(
            name="hostpath-stub",
            family="xception",  # family is never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )


@pytest.fixture(scope="module")
def stub_server(stub_spec, tmp_path_factory):
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    root = tmp_path_factory.mktemp("stub-models")
    art.save_artifact(
        art.version_dir(str(root), stub_spec.name, 1), stub_spec, {"params": {}}, None, {}
    )
    server = ModelServer(
        str(root), port=0, buckets=(1, 2, 4, 8), max_delay_ms=1.0,
        host="127.0.0.1", engine_factory=StubEngine,
    )
    server.warmup()
    server.start()
    yield stub_spec, server
    server.shutdown()


def test_stub_logits_distinguish_images(stub_spec):
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, size=(4, *stub_spec.input_shape), dtype=np.uint8)
    out = stub_logits(imgs, stub_spec.num_classes)
    assert out.shape == (4, 3)
    # class offsets are exactly [0, 1, 2] on top of the per-image checksum
    np.testing.assert_array_equal(out[:, 1] - out[:, 0], np.ones(4, np.float32))
    assert len({float(v) for v in out[:, 0]}) > 1  # images distinguish


def test_stub_engine_through_batcher_routes_correctly(stub_spec, stub_server):
    """Concurrent single-image predicts through the REAL server + batcher:
    every client must get its own image's checksum back."""
    import requests

    from kubernetes_deep_learning_tpu.serving import protocol

    spec, server = stub_server
    rng = np.random.default_rng(1)
    imgs = [
        rng.integers(0, 256, size=(1, *spec.input_shape), dtype=np.uint8)
        for _ in range(16)
    ]
    url = f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict"
    results: list = [None] * len(imgs)

    def post(i):
        r = requests.Session().post(
            url,
            data=protocol.encode_predict_request(imgs[i]),
            headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE},
            timeout=30,
        )
        assert r.status_code == 200
        logits, _ = protocol.decode_predict_response(
            r.content, r.headers["Content-Type"]
        )
        results[i] = np.asarray(logits)

    threads = [threading.Thread(target=post, args=(i,)) for i in range(len(imgs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, img in enumerate(imgs):
        np.testing.assert_array_equal(
            results[i], stub_logits(img, spec.num_classes)
        )


def test_microbatcher_coalesces_and_routes():
    calls: list[int] = []
    labels = ["a", "b"]
    release = threading.Event()

    def predict_batch(images, request_id):
        release.wait(5)  # hold the first flush so followers queue up
        calls.append(images.shape[0])
        return [img.sum() * np.ones(2) for img in images], labels

    mb = UpstreamMicroBatcher(predict_batch, max_batch=8, max_delay_ms=5.0)
    imgs = [np.full((2, 2, 3), i, np.uint8) for i in range(12)]
    results: list = [None] * len(imgs)

    def submit(i):
        results[i] = mb.predict(imgs[i])

    threads = [threading.Thread(target=submit, args=(i,)) for i in range(len(imgs))]
    for t in threads:
        t.start()
    import time

    time.sleep(0.2)  # let every request enqueue behind the held flush
    release.set()
    for t in threads:
        t.join()
    mb.close()

    for i, img in enumerate(imgs):
        row, got_labels = results[i]
        assert got_labels == labels
        np.testing.assert_array_equal(row, img.sum() * np.ones(2))
    assert sum(calls) == len(imgs)
    assert max(calls) > 1  # coalescing actually happened


def test_microbatcher_overlaps_flushes_up_to_pipeline_depth():
    """Pipelined flushes: with depth 2 the dispatcher must START upstream
    flush N+1 while flush N is still in flight (held open here by an
    event), and block at the depth limit -- the gateway-tier mirror of the
    engine's in-flight dispatch pipeline."""
    import time

    started = []
    release = threading.Event()
    labels = ["a", "b"]

    def predict_batch(images, request_id):
        started.append(images.shape[0])
        release.wait(5)  # every flush holds until the test releases
        return [img.sum() * np.ones(2) for img in images], labels

    mb = UpstreamMicroBatcher(
        predict_batch, max_batch=1, max_delay_ms=0.0, pipeline_depth=2
    )
    imgs = [np.full((2, 2, 3), i, np.uint8) for i in range(4)]
    results: list = [None] * len(imgs)
    threads = [
        threading.Thread(target=lambda i=i: results.__setitem__(i, mb.predict(imgs[i])))
        for i in range(len(imgs))
    ]
    for t in threads:
        t.start()
    # Two flushes must be IN FLIGHT concurrently (neither has returned)...
    deadline = time.monotonic() + 5
    while len(started) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(started) == 2
    # ...and the third must be held back by the depth-2 slot limit.
    time.sleep(0.1)
    assert len(started) == 2
    release.set()
    for t in threads:
        t.join()
    mb.close()
    for i, img in enumerate(imgs):
        row, _ = results[i]
        np.testing.assert_array_equal(row, img.sum() * np.ones(2))


def test_microbatcher_propagates_upstream_failure():
    def predict_batch(images, request_id):
        raise RuntimeError("upstream down")

    mb = UpstreamMicroBatcher(predict_batch, max_batch=4, max_delay_ms=1.0)
    with pytest.raises(RuntimeError, match="upstream down"):
        mb.predict(np.zeros((2, 2, 3), np.uint8))
    mb.close()
    with pytest.raises(RuntimeError, match="closed"):
        mb.predict(np.zeros((2, 2, 3), np.uint8))


def test_gateway_upstream_batching_e2e(stub_server, monkeypatch):
    """Gateway with upstream_batch: concurrent /predict single-image requests
    coalesce into fat upstream calls and every client gets its own scores."""
    import io

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.serving.gateway import Gateway

    spec, server = stub_server
    gw = Gateway(
        serving_host=f"127.0.0.1:{server.port}",
        model=spec.name,
        port=0,
        host="127.0.0.1",
        upstream_batch=8,
        upstream_delay_ms=5.0,
        # The micro-batcher coalesces decoded tensors; the raw-bytes wire,
        # which this server would negotiate, bypasses it by design.
        ingest=False,
    )
    rng = np.random.default_rng(2)
    imgs = {
        f"http://img.test/{i}.png": rng.integers(
            0, 256, size=spec.input_shape, dtype=np.uint8
        )
        for i in range(10)
    }

    def png(url: str) -> bytes:
        buf = io.BytesIO()
        Image.fromarray(imgs[url]).save(buf, format="PNG")
        return buf.getvalue()

    # The one seam every gateway fetch goes through, on either wire.
    monkeypatch.setattr(
        preprocess, "fetch_image_bytes", lambda url, *a, **k: png(url)
    )
    gw.start()
    try:
        results: dict = {}
        lock = threading.Lock()

        def post(url):
            r = requests.post(
                f"http://127.0.0.1:{gw.port}/predict", json={"url": url}, timeout=30
            )
            assert r.status_code == 200, r.text
            with lock:
                results[url] = r.json()

        threads = [threading.Thread(target=post, args=(u,)) for u in imgs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for url, img in imgs.items():
            want = stub_logits(img[None], spec.num_classes)[0]
            got = np.array([results[url][l] for l in spec.labels])
            np.testing.assert_allclose(got, want, rtol=1e-6)
    finally:
        gw.shutdown()


def _connect_ex(address):
    with socket.socket() as s:
        s.connect_ex(address)


@pytest.mark.parametrize(
    "attempt, names",
    [
        (lambda: socket.getaddrinfo("img", 80), "'img'"),
        (lambda: socket.gethostbyname("img.test"), "'img.test'"),
        # 192.0.2.0/24 is reserved for documentation; refused before a packet.
        (lambda: socket.create_connection(("192.0.2.1", 80), 0.1), "'192.0.2.1'"),
        (lambda: _connect_ex(("192.0.2.1", 80)), "'192.0.2.1'"),
        # urllib wraps it: how a URL that slips past a stubbed fetch seam
        # shows itself.
        (lambda: preprocess.fetch_image_bytes("http://img/x.png"), "'img'"),
    ],
    ids=["getaddrinfo", "gethostbyname", "create_connection", "connect_ex", "fetch"],
)
def test_outside_connections_are_refused(attempt, names):
    """tests/conftest.py lets the suite reach loopback and nothing else: a
    lookup of or a connection to any other host fails at once with an error
    that names the host."""
    from conftest import OutsideConnectionRefused

    # Before anything below may touch a socket: the guard is in place.
    for fn in (socket.getaddrinfo, socket.gethostbyname,
               socket.socket.connect, socket.socket.connect_ex):
        assert getattr(fn, "loopback_only", False), fn
    with pytest.raises(OSError, match=f"{names} refused: tests reach only loopback") as e:
        attempt()
    refusal = getattr(e.value, "reason", e.value)  # urllib's URLError wraps it
    assert isinstance(refusal, OutsideConnectionRefused)


def test_loopback_by_name_and_by_address_is_untouched():
    with socket.socket() as listener:
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        assert socket.getaddrinfo("localhost", port)
        with socket.create_connection(("127.0.0.1", port), timeout=5.0):
            pass
