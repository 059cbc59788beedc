"""NativeBatcher (C++ batchqueue.cc) tests: mirrors test_batcher.py's
scenarios so both implementations provably share policy and surface."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from types import SimpleNamespace

import numpy as np
import pytest

pytest.importorskip(
    "kubernetes_deep_learning_tpu.ops._native",
    reason="native library unavailable (no toolchain)",
)

from kubernetes_deep_learning_tpu.runtime import create_batcher
from kubernetes_deep_learning_tpu.runtime.batcher import BatcherClosed, QueueFull
from kubernetes_deep_learning_tpu.runtime.native_batcher import NativeBatcher


class FakeEngine:
    """Deterministic stand-in: logit row = [sum(image), 2*sum(image)]."""

    max_batch = 8
    spec = SimpleNamespace(input_shape=(2, 2, 3), num_classes=2)

    def __init__(self, delay_s=0.0, fail=False):
        self.delay_s = delay_s
        self.fail = fail
        self.batch_sizes = []
        self._lock = threading.Lock()

    def predict(self, images: np.ndarray) -> np.ndarray:
        with self._lock:
            self.batch_sizes.append(images.shape[0])
        if self.fail:
            raise RuntimeError("boom")
        if self.delay_s:
            time.sleep(self.delay_s)
        sums = images.reshape(images.shape[0], -1).sum(axis=1).astype(np.float32)
        return np.stack([sums, sums * 2], axis=1)


def _img(value: int) -> np.ndarray:
    return np.full((2, 2, 3), value, np.uint8)


def test_create_batcher_auto_respects_core_count(monkeypatch):
    import os

    from kubernetes_deep_learning_tpu.runtime import DynamicBatcher

    # With a core to overlap with, auto picks the C++ queue.  (The check is
    # affinity-aware, so patch sched_getaffinity where it exists.)
    monkeypatch.setattr(os, "cpu_count", lambda: 4)
    if hasattr(os, "sched_getaffinity"):
        monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1, 2, 3})
    b = create_batcher(FakeEngine(), impl="auto", max_delay_ms=1)
    try:
        assert isinstance(b, NativeBatcher)
    finally:
        b.close()
    # On a single-core host the GIL convoys the native pipeline's
    # cross-thread handoffs, so auto
    # degrades to the one-thread Python dispatcher.
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    if hasattr(os, "sched_getaffinity"):
        monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0})
    b = create_batcher(FakeEngine(), impl="auto", max_delay_ms=1)
    try:
        assert isinstance(b, DynamicBatcher)
    finally:
        b.close()


def test_native_batcher_async_stub_correctness():
    """The depth-2 pipeline against the async serial-device stub
    (runtime.stub async_device): concurrent requests must map back to
    their own checksum rows even with a batch in flight during assembly
    -- the aliasing/ping-pong contract under real overlap."""
    import tempfile

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine, stub_logits

    spec = register_spec(
        ModelSpec(
            name="nb-async-stub",
            family="xception",
            input_shape=(8, 8, 3),
            labels=("a", "b"),
            preprocessing="tf",
        )
    )
    root = tempfile.mkdtemp()
    art.save_artifact(art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {})
    artifact = art.load_artifact(art.version_dir(root, spec.name, 1))
    eng = StubEngine(artifact, device_ms_per_batch=1.0, async_device=True)
    eng.warmup()
    assert hasattr(eng, "predict_async")
    b = NativeBatcher(eng, max_delay_ms=1)
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (24, *spec.input_shape), np.uint8)
    try:
        with ThreadPoolExecutor(max_workers=12) as pool:
            outs = list(pool.map(b.predict, imgs))
        want = stub_logits(imgs, spec.num_classes)
        np.testing.assert_allclose(np.stack(outs), want)
    finally:
        b.close()
        eng.close()


def test_single_request_roundtrip():
    b = NativeBatcher(FakeEngine(), max_delay_ms=1)
    try:
        out = b.predict(_img(3))
        assert out.tolist() == [36.0, 72.0]
    finally:
        b.close()


def test_concurrent_requests_batch_and_map_correctly():
    eng = FakeEngine(delay_s=0.02)
    b = NativeBatcher(eng, max_delay_ms=5)
    results: dict[int, np.ndarray] = {}
    errors = []

    def worker(v):
        try:
            results[v] = b.predict(_img(v))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(v,)) for v in range(40)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        for v in range(40):
            assert results[v].tolist() == [v * 12.0, v * 24.0], v
        # while the engine sleeps, the queue must coalesce into real batches
        assert max(eng.batch_sizes) > 1
        assert all(s <= eng.max_batch for s in eng.batch_sizes)
    finally:
        b.close()


def test_engine_error_propagates_and_batcher_survives():
    eng = FakeEngine(fail=True)
    b = NativeBatcher(eng, max_delay_ms=1)
    try:
        with pytest.raises(RuntimeError, match="boom"):
            b.predict(_img(1))
        eng.fail = False
        assert b.predict(_img(2)).tolist() == [24.0, 48.0]
    finally:
        b.close()


def test_queue_cap_rejects():
    # Capacity 2, engine busy 300 ms per batch: 8 concurrent requests cannot
    # all fit, so at least one must be rejected with the retryable QueueFull
    # (and the accepted ones must all succeed).
    eng = FakeEngine(delay_s=0.3)
    b = NativeBatcher(eng, max_delay_ms=0, queue_cap=2)
    ok, rejected, other = [], [], []

    def worker(v):
        try:
            ok.append(b.predict(_img(v)))
        except QueueFull:
            rejected.append(v)
        except Exception as e:  # pragma: no cover
            other.append(e)

    threads = [threading.Thread(target=worker, args=(v,)) for v in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not other
        assert rejected, "capacity-2 queue accepted 8 concurrent requests"
        assert len(ok) == 8 - len(rejected)
    finally:
        b.close()


def test_timeout_reclaims_capacity():
    eng = FakeEngine(delay_s=0.2)
    b = NativeBatcher(eng, max_delay_ms=0, queue_cap=2)
    try:
        with pytest.raises(FuturesTimeout):
            b.predict(_img(1), timeout=0.01)
        # The timed-out slot must be reclaimed: capacity-2 queue still
        # accepts and serves 2 concurrent requests afterwards.
        time.sleep(0.3)
        outs = []
        pool = [
            threading.Thread(target=lambda v=v: outs.append(b.predict(_img(v))))
            for v in (2, 3)
        ]
        for t in pool:
            t.start()
        for t in pool:
            t.join()
        assert len(outs) == 2
    finally:
        b.close()


def test_dispatcher_survives_all_abandoned_round():
    # Regression: while the engine is stuck on batch 1, every queued waiter
    # times out (slots abandoned).  The dispatcher's next take() pops only
    # abandoned slots -- it must go back to waiting, NOT exit as if closed;
    # the batcher has to keep serving afterwards.
    eng = FakeEngine(delay_s=0.3)
    b = NativeBatcher(eng, max_delay_ms=0, queue_cap=8)
    try:
        first = threading.Thread(target=lambda: b.predict(_img(0)))
        first.start()
        time.sleep(0.05)  # batch 1 in flight; engine busy 300 ms
        for v in (1, 2):
            with pytest.raises(FuturesTimeout):
                b.predict(_img(v), timeout=0.01)  # queued, then abandoned
        first.join()
        time.sleep(0.2)  # let the dispatcher churn through the abandoned round
        eng.delay_s = 0.0
        assert b.predict(_img(5)).tolist() == [60.0, 120.0]
    finally:
        b.close()


def test_close_without_drain_rejects_new_requests():
    b = NativeBatcher(FakeEngine(), max_delay_ms=0)
    b.close(drain=False)
    with pytest.raises(BatcherClosed):
        b.predict(_img(1))


def test_close_with_drain_serves_queued_work():
    # Parity with DynamicBatcher.close(drain=True): requests queued at close
    # time must be SERVED, not failed with BatcherClosed.
    eng = FakeEngine(delay_s=0.1)
    b = NativeBatcher(eng, max_delay_ms=0, queue_cap=8)
    outs, errs = [], []

    def worker(v):
        try:
            outs.append(b.predict(_img(v)))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(v,)) for v in range(4)]
    for t in threads:
        t.start()
    # Positive handshake, not a sleep: close only once all 4 requests are
    # observably submitted (taken into a batch or still pending in C++) --
    # a fixed delay races thread startup on a loaded machine.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        submitted = sum(eng.batch_sizes) + b._lib.kdlt_bq_pending(b._q)
        if submitted >= 4:
            break
        time.sleep(0.005)
    else:  # pragma: no cover
        pytest.fail("requests never queued")
    b.close(drain=True)
    for t in threads:
        t.join()
    assert not errs
    assert len(outs) == 4


def test_served_through_model_server(tmp_path):
    # End to end: a real artifact served with batcher_impl="native".
    import requests

    from kubernetes_deep_learning_tpu.export.exporter import export_model
    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    spec = register_spec(
        ModelSpec(
            name="native-bq-model",
            family="vit-tiny",
            input_shape=(16, 16, 3),
            labels=("a", "b"),
            preprocessing="tf",
        )
    )
    export_model(spec, init_variables(spec, seed=0), str(tmp_path))
    server = ModelServer(
        str(tmp_path), port=0, buckets=(1, 2), batcher_impl="native"
    )
    try:
        assert isinstance(server.models["native-bq-model"].batcher, NativeBatcher)
        server.warmup()
        server.start()
        r = requests.post(
            f"http://localhost:{server.port}/v1/models/native-bq-model:predict",
            json={"instances": np.zeros((1, 16, 16, 3), np.uint8).tolist()},
            timeout=30,
        )
        assert r.status_code == 200
        assert set(r.json()["predictions"][0]) == {"a", "b"}
    finally:
        server.shutdown()


class FakeAsyncEngine(FakeEngine):
    """Engine exposing the predict_async pipelining hook."""

    def predict_async(self, images):
        return self.predict(np.array(images)), images.shape[0]


class LazyFailure:
    """predict_async result whose materialization (device sync) fails."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device exploded at sync")


def test_async_pipeline_roundtrip_and_mapping():
    eng = FakeAsyncEngine(delay_s=0.01)
    b = NativeBatcher(eng, max_delay_ms=2)
    results, errors = {}, []

    def worker(v):
        try:
            results[v] = b.predict(_img(v))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(v,)) for v in range(30)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        for v in range(30):
            assert results[v].tolist() == [v * 12.0, v * 24.0], v
        assert max(eng.batch_sizes) > 1  # pipelined batches still coalesce
    finally:
        b.close()


def test_async_sync_failure_fails_only_its_batch():
    eng = FakeAsyncEngine()
    b = NativeBatcher(eng, max_delay_ms=1)
    try:
        real = eng.predict_async
        eng.predict_async = lambda images: (LazyFailure(), images.shape[0])
        with pytest.raises(RuntimeError, match="device exploded"):
            b.predict(_img(1))
        eng.predict_async = real
        assert b.predict(_img(2)).tolist() == [24.0, 48.0]
    finally:
        b.close()
