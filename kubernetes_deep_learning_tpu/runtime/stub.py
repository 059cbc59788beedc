"""StubEngine: the serving host path with the device taken out.

The stub implements the engine surface the server and batchers consume
(spec/buckets/predict/predict_async/...) but "computes" logits with a
trivially cheap, still-verifiable function:
``logits[i, j] = checksum(image_i) + j`` -- so the tests of the host path
(HTTP, protocol, batchers, scheduler, pool) can assert responses are real
per-image results (not dropped or reordered) without paying for
convolutions.

``device_ms_per_batch`` optionally simulates device latency with a GIL-free
sleep (flush cadence under a busy device).  ``async_device=True``
additionally models the device as a SERIAL dispatch queue behind
``predict_async`` -- the engine surface the in-flight dispatch pipeline
overlaps with -- so tests see dispatch overlap at controlled latencies.
"""

from __future__ import annotations

import queue as queue_lib
import threading
import time

import numpy as np

from kubernetes_deep_learning_tpu.runtime.engine import DEFAULT_BUCKETS


def stub_logits(images: np.ndarray, num_classes: int) -> np.ndarray:
    """Deterministic, cheap, per-image-distinct 'logits' (f32 (N, C)).

    Sum over a fixed pixel subsample keeps the checksum O(1)-ish per image
    while still depending on the content, so misrouted batcher responses
    are caught by tests.
    """
    n = images.shape[0]
    flat = images.reshape(n, -1)
    sub = flat[:, ::1009].astype(np.int64)  # prime stride: touches ~220 B/img
    checksum = (sub.sum(axis=1) % 9973).astype(np.float32)
    return checksum[:, None] + np.arange(num_classes, dtype=np.float32)[None, :]


class _PendingLogits:
    """Future-like handle predict_async returns: np.asarray() blocks until
    the simulated device finishes the batch (mirrors a jax device array's
    materialization sync)."""

    def __init__(self):
        self._ev = threading.Event()
        self._out: np.ndarray | None = None

    def _set(self, out: np.ndarray) -> None:
        self._out = out
        self._ev.set()

    def __array__(self, dtype=None, copy=None):
        self._ev.wait()
        out = self._out
        return out if dtype is None else out.astype(dtype)


class StubEngine:
    """Engine-shaped stand-in; see module docstring."""

    def __init__(
        self,
        artifact,
        buckets=DEFAULT_BUCKETS,
        registry=None,
        device_ms_per_batch: float = 0.0,
        async_device: bool = False,
        **_ignored,
    ):
        self.spec = artifact.spec
        self.buckets = tuple(sorted(buckets))
        self.max_batch = self.buckets[-1]
        self._device_s = device_ms_per_batch / 1e3
        self._ready = threading.Event()
        self._m_images = None
        if registry is not None:
            self._m_images = registry.counter(
                "kdlt_engine_images_total", "images predicted (stub engine)"
            )
        self._dev_thread = None
        if async_device:
            # Serial device queue: one batch executes at a time, each taking
            # device_ms_per_batch; dispatch (predict_async) never blocks on
            # execution.  Same aliasing contract as the real engine: the
            # caller's image buffer must stay valid until materialization.
            self._dq: queue_lib.Queue = queue_lib.Queue()
            self._dev_thread = threading.Thread(
                target=self._device_loop, daemon=True, name="stub-device"
            )
            self._dev_thread.start()

            def predict_async(images: np.ndarray):
                handle = _PendingLogits()
                self._dq.put((np.asarray(images), handle))
                return handle, images.shape[0]

            def record_completed(n: int, seconds: float) -> None:
                if self._m_images is not None:
                    self._m_images.inc(n)

            self.predict_async = predict_async
            self.record_completed = record_completed

    def _device_loop(self) -> None:
        while True:
            item = self._dq.get()
            if item is None:  # close() sentinel
                return
            images, handle = item
            if self._device_s:
                time.sleep(self._device_s)
            handle._set(stub_logits(images, self.spec.num_classes))

    def close(self) -> None:
        """Stop the simulated-device thread (async_device engines only).
        Without this every engine instance parks a thread in Queue.get()
        forever, pinning the engine for the process lifetime."""
        if self._dev_thread is not None:
            self._dq.put(None)
            self._dev_thread.join(timeout=5)
            self._dev_thread = None

    @property
    def ready(self) -> bool:
        return self._ready.is_set()

    def warmup(self) -> float:
        self._ready.set()
        return 0.0

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch

    def predict(self, images: np.ndarray) -> np.ndarray:
        if self._device_s:
            time.sleep(self._device_s)  # GIL-free, like a real device wait
        if self._m_images is not None:
            self._m_images.inc(images.shape[0])
        return stub_logits(images, self.spec.num_classes)

    # predict_async/record_completed deliberately absent: the batchers fall
    # back to their synchronous path (hasattr checks), which is the honest
    # host-path cost -- there is no device pipeline to overlap with.
