"""NativeBatcher: the C++ dynamic batcher (native/batchqueue.cc) binding.

Same policy and surface as runtime.batcher.DynamicBatcher -- continuous
batching with a bounded linger for stragglers, blocking ``predict`` with the
reference's 20 s deadline -- but the queue, the linger timer, and the
gather of request images into one contiguous batch live in C++ outside the
GIL (ctypes releases it around every call).  This is the in-tree analog of
the batching TF-Serving does in its C++ binary (SURVEY.md component 7):
request threads block in native code, so a Python-side GC pause or GIL
convoy cannot stretch the batching window.

Falls back is the caller's job: model_server picks this when the native
library is importable, else DynamicBatcher (identical semantics, pure
Python).
"""

from __future__ import annotations

import ctypes
import threading
import time
from typing import Any

import numpy as np

from kubernetes_deep_learning_tpu.runtime.batcher import BatcherClosed, QueueFull
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

from concurrent.futures import TimeoutError as FuturesTimeout


class NativeBatcher:
    def __init__(
        self,
        engine,
        max_batch: int | None = None,
        max_delay_ms: float = 2.0,
        queue_cap: int = 2048,
        registry: metrics_lib.Registry | None = None,
        pipeline_depth: int | None = None,
    ):
        """``pipeline_depth`` bounds how many dispatched-but-unmaterialized
        batches ride the device at once (None = $KDLT_PIPELINE_DEPTH or 2;
        1 = the pre-pipelining behavior of at most one batch in flight
        while the next assembles)."""
        from kubernetes_deep_learning_tpu.ops import _native

        self._lib = _native.lib
        self._engine = engine
        self.spec = engine.spec
        self.max_batch = max_batch or engine.max_batch
        self.max_delay = max_delay_ms / 1000.0
        self.queue_cap = queue_cap
        self._item_shape = tuple(self.spec.input_shape)
        self._item_bytes = int(np.prod(self._item_shape))
        self._out_floats = self.spec.num_classes

        self._q = self._lib.kdlt_bq_create(
            queue_cap, self._item_bytes, self._out_floats
        )
        if not self._q:
            raise RuntimeError("kdlt_bq_create failed")
        self._closed = False
        self._destroyed = False
        self._close_lock = threading.Lock()
        # Failed-batch errors keyed by ticket, so each waiter raises ITS
        # batch's exception (a shared last-error field would misattribute
        # failures across batches).  Entries whose waiters never woke
        # (abandoned after timeout) are pruned by AGE -- any live waiter
        # reads its entry within its own predict timeout, so expiring well
        # past that can never steal an error from a live request.
        self._errors: dict[int, tuple[BaseException, float]] = {}
        self._errors_lock = threading.Lock()
        self._error_ttl_s = 120.0

        registry = registry or getattr(engine, "registry", None) or metrics_lib.Registry()
        self._m_batch_size = registry.histogram(
            "kdlt_batcher_batch_size",
            "dispatched batch sizes",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
        )
        self._m_queue_full = registry.counter(
            "kdlt_batcher_rejected_total", "requests rejected because queue was full"
        )
        # Dispatcher-owned staging buffers; only this thread touches them.
        # pipeline_depth + 1 buffers, rotated: predict_async's aliasing
        # contract forbids touching a dispatched batch until its sync, so
        # with up to ``pipeline_depth`` batches in flight one more buffer
        # is needed for the batch being assembled.
        from kubernetes_deep_learning_tpu.runtime.engine import resolve_pipeline_depth

        self._max_inflight = resolve_pipeline_depth(pipeline_depth)
        self._batch_bufs = [
            np.empty((self.max_batch, *self._item_shape), np.uint8)
            for _ in range(self._max_inflight + 1)
        ]
        self._tickets = np.empty(self.max_batch, np.int64)
        self._thread = threading.Thread(
            target=self._run, name="kdlt-native-batcher", daemon=True
        )
        self._thread.start()

    # --- dispatcher --------------------------------------------------------

    def _run(self) -> None:
        from collections import deque

        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        tix = self._tickets.ctypes.data_as(i64p)
        # Multi-in-flight pipeline: while the device executes batches
        # N..N+depth-1 (each staged in its own buffer), this thread takes,
        # assembles (into a free buffer), and DISPATCHES the next batch,
        # then syncs the OLDEST in-flight batch only when the depth limit is
        # reached (backpressure).  The device never idles between batches on
        # dispatch/assembly time; completions stay FIFO in dispatch order.
        use_async = hasattr(self._engine, "predict_async")
        pending: deque = deque()  # (tickets_copy, n, device_logits, dispatched_at)
        slot = 0
        while True:
            # Waits in C (GIL released).  With batches in flight the wait is
            # BOUNDED: on an idle queue the dispatcher must come back to sync
            # the in-flight work rather than strand its waiters; take
            # returns -1 when the bounded wait expires with no work.
            wait_s = self.max_delay if pending else -1.0
            staging = self._batch_bufs[slot]
            n = self._lib.kdlt_bq_take(
                self._q, staging.ctypes.data_as(u8p), self.max_batch,
                self.max_delay, wait_s, tix,
            )
            if n == -1:  # no new work while batches are in flight: sync one
                self._finish(*pending.popleft())
                continue
            if n == 0:
                while pending:
                    self._finish(*pending.popleft())
                return
            self._m_batch_size.observe(n)
            tickets = self._tickets[:n].copy()
            try:
                if use_async:
                    device_logits, _ = self._engine.predict_async(staging[:n])
                    pending.append(
                        (tickets, n, device_logits, time.perf_counter())
                    )
                    # The dispatched buffer is off-limits until its sync;
                    # rotate to the next free staging buffer.
                    slot = (slot + 1) % len(self._batch_bufs)
                else:  # plain engines (tests, wrappers): dispatch+sync now
                    self._finish(
                        tickets, n, self._engine.predict(staging[:n]), None
                    )
            except Exception as e:
                self._fail(tickets, n, e)
            while len(pending) > self._max_inflight:  # depth backpressure
                self._finish(*pending.popleft())

    def _finish(self, tickets: np.ndarray, n: int, logits, dispatched_at) -> None:
        """Sync a dispatched batch and publish its rows (or its failure).

        MUST NOT raise: an exception escaping here kills the dispatcher
        thread on an open queue -- the silently-dead-model state the C++
        take() contract exists to prevent.  Anything unexpected fails the
        batch's tickets instead.
        """
        i64p = ctypes.POINTER(ctypes.c_int64)
        f32p = ctypes.POINTER(ctypes.c_float)
        try:
            rows = np.ascontiguousarray(np.asarray(logits)[:n], dtype=np.float32)
            if dispatched_at is not None and hasattr(self._engine, "record_completed"):
                # Async dispatch skips the engine's own sync-side accounting;
                # report AFTER materialization succeeded so failed batches
                # never inflate the success counters.
                self._engine.record_completed(n, time.perf_counter() - dispatched_at)
        except Exception as e:  # device-side failure surfaces at sync
            self._fail(tickets, n, e)
            return
        try:
            self._lib.kdlt_bq_complete(
                self._q,
                tickets.ctypes.data_as(i64p),
                n,
                rows.ctypes.data_as(f32p),
                self._out_floats,
            )
        except Exception as e:  # pragma: no cover - ctypes-layer failure
            self._fail(tickets, n, e)

    def _fail(self, tickets: np.ndarray, n: int, e: BaseException) -> None:
        """Record the error per ticket and wake the batch's waiters."""
        i64p = ctypes.POINTER(ctypes.c_int64)
        now = time.monotonic()
        with self._errors_lock:
            expired = [
                t for t, (_, ts) in self._errors.items()
                if now - ts > self._error_ttl_s
            ]
            for t in expired:
                del self._errors[t]
            for t in tickets[:n]:
                self._errors[int(t)] = (e, now)
        self._lib.kdlt_bq_fail(self._q, tickets.ctypes.data_as(i64p), n)

    # --- request side ------------------------------------------------------

    def predict(
        self, image: np.ndarray, timeout: float = 20.0, trace=None
    ) -> np.ndarray:
        """Blocking single-image predict (the reference's 20 s deadline,
        reference model_server.py:55).

        ``trace`` (utils.trace.RequestTrace, optional) records ONE coarse
        ``batcher.wait`` span covering queue + dispatch + execute +
        readback: the C++ ticket queue cannot carry per-request Python
        objects through to the dispatch loop, so the native path trades
        per-stage attribution for its GIL-free hot path (the Python
        batcher gives the full stage breakdown).
        """
        if self._closed:
            raise BatcherClosed("batcher is shut down")
        image = np.ascontiguousarray(image)
        if tuple(image.shape) != self._item_shape:
            raise ValueError(
                f"image shape {tuple(image.shape)} != expected {self._item_shape}"
            )
        if image.dtype != np.uint8:
            raise ValueError(f"batcher takes uint8 images, got {image.dtype}")
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        ticket = self._lib.kdlt_bq_submit(self._q, image.ctypes.data_as(u8p))
        if ticket == -1:
            self._m_queue_full.inc()
            raise QueueFull("request queue full")
        if ticket == -2:
            raise BatcherClosed("batcher is shut down")
        out = np.empty(self._out_floats, np.float32)
        if trace is not None:
            from kubernetes_deep_learning_tpu.utils import trace as trace_lib

            w0 = trace_lib.now_s()
            rc = self._lib.kdlt_bq_wait(
                self._q, ticket, out.ctypes.data_as(f32p), timeout
            )
            trace.record("batcher.wait", w0, trace_lib.now_s() - w0, rc=rc)
        else:
            rc = self._lib.kdlt_bq_wait(
                self._q, ticket, out.ctypes.data_as(f32p), timeout
            )
        if rc == 0:
            return out
        if rc == 1:
            raise FuturesTimeout(f"predict timed out after {timeout}s")
        if rc == 2:
            with self._errors_lock:
                entry = self._errors.pop(int(ticket), None)
            if entry is not None:
                raise entry[0]
            raise BatcherClosed("request failed during batcher shutdown")
        raise BatcherClosed(f"batcher ticket invalid (rc={rc})")

    # --- lifecycle ---------------------------------------------------------

    def close(self, drain: bool = True) -> None:
        """Stop intake; with drain, let queued work finish first.

        The C++ queue is NOT freed here: a handler thread that has passed
        the closed-flag check may still be inside submit/wait, so freeing
        now would be use-after-free.  close only stops the world (new
        predicts raise BatcherClosed; native waiters are woken); the free
        happens in __del__, which cannot run while any thread is inside a
        method of this object.
        """
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            if drain:
                self._lib.kdlt_bq_close(self._q)   # queued work still served
            else:
                self._lib.kdlt_bq_abort(self._q)   # queued waiters fail now
            self._thread.join(timeout=30.0)

    def __del__(self):  # the only place the C++ queue is freed
        try:
            if not getattr(self, "_q", None) or self._destroyed:
                return
            if not self._closed:
                self.close(drain=False)
            if not self._thread.is_alive():
                self._destroyed = True
                # destroy additionally blocks in C until any last native
                # waiter (possible only via a stale ticket) has left.
                self._lib.kdlt_bq_destroy(self._q)
        except Exception:
            pass
