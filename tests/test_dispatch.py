"""InFlightDispatcher: the bounded multi-in-flight dispatch pipeline.

Contracts under test (runtime.engine.InFlightDispatcher):

- FIFO ordering + per-future wiring: each Future resolves to ITS batch's
  rows, completions in submit order;
- backpressure: submit blocks once ``depth`` batches are in flight;
- exception propagation: a dispatch failure resolves that submit's Future,
  a sync-side failure resolves the in-flight batch's Future, and neither
  kills the pipeline;
- clean shutdown: close() drains in-flight work (every Future resolves)
  and subsequent submits raise DispatcherClosed.

The engine stand-in exposes the same predict_async surface as the real
engine but with CONTROLLABLE completion: each dispatched batch's handle
materializes only when the test releases it, so overlap is asserted by
construction, not by timing luck.
"""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from kubernetes_deep_learning_tpu.runtime.engine import (
    DispatcherClosed,
    InFlightDispatcher,
    resolve_pipeline_depth,
)


class _Handle:
    """Device-array stand-in: np.asarray blocks until release()."""

    def __init__(self, out, fail=False):
        self._out = out
        self._fail = fail
        self._ev = threading.Event()

    def release(self):
        self._ev.set()

    def __array__(self, dtype=None, copy=None):
        assert self._ev.wait(timeout=10), "handle never released"
        if self._fail:
            raise RuntimeError("device fault at sync")
        return self._out


class ControlledEngine:
    """predict_async surface with test-controlled completion per batch."""

    def __init__(self, fail_dispatch_at=(), fail_sync_at=()):
        self.handles: list[_Handle] = []
        self.dispatches = 0
        self.completed: list[int] = []
        self._fail_dispatch_at = set(fail_dispatch_at)
        self._fail_sync_at = set(fail_sync_at)
        self._lock = threading.Lock()

    def predict_async(self, images: np.ndarray):
        with self._lock:
            i = self.dispatches
            self.dispatches += 1
        if i in self._fail_dispatch_at:
            raise ValueError(f"dispatch {i} rejected")
        n = images.shape[0]
        # Row r of batch i -> [i, r]: distinct per (batch, row) so wiring
        # mistakes are visible in the values themselves.
        out = np.stack(
            [np.full(2, i, np.float32) + np.array([0, 0.001], np.float32) * r
             for r in range(n)]
        )
        out[:, 1] = np.arange(n)
        out[:, 0] = i
        h = _Handle(out, fail=i in self._fail_sync_at)
        self.handles.append(h)
        return h, n

    def record_completed(self, n: int, seconds: float) -> None:
        self.completed.append(n)


def _imgs(n):
    return np.zeros((n, 2, 2, 3), np.uint8)


def test_resolve_pipeline_depth(monkeypatch):
    monkeypatch.delenv("KDLT_PIPELINE_DEPTH", raising=False)
    assert resolve_pipeline_depth() == 2
    assert resolve_pipeline_depth(4) == 4
    assert resolve_pipeline_depth(0) == 1  # clamped
    monkeypatch.setenv("KDLT_PIPELINE_DEPTH", "3")
    assert resolve_pipeline_depth() == 3
    assert resolve_pipeline_depth(1) == 1  # explicit beats env
    monkeypatch.setenv("KDLT_PIPELINE_DEPTH", "banana")
    assert resolve_pipeline_depth() == 2  # typo degrades to default


def test_ordering_and_future_wiring():
    eng = ControlledEngine()
    d = InFlightDispatcher(eng, depth=2)
    try:
        f0 = d.submit(_imgs(3))
        f1 = d.submit(_imgs(2))
        eng.handles[0].release()
        out0 = f0.result(timeout=5)
        assert out0.shape == (3, 2) and set(out0[:, 0]) == {0.0}
        eng.handles[1].release()
        out1 = f1.result(timeout=5)
        assert out1.shape == (2, 2) and set(out1[:, 0]) == {1.0}
        # async completions were accounted through record_completed
        assert eng.completed == [3, 2]
    finally:
        d.close()


def test_backpressure_blocks_at_depth_limit():
    eng = ControlledEngine()
    d = InFlightDispatcher(eng, depth=2)
    try:
        d.submit(_imgs(1))
        d.submit(_imgs(1))
        third_submitted = threading.Event()
        fut3 = []

        def submit_third():
            fut3.append(d.submit(_imgs(1)))
            third_submitted.set()

        t = threading.Thread(target=submit_third, daemon=True)
        t.start()
        # With 2 batches in flight the third submit must block...
        assert not third_submitted.wait(timeout=0.2)
        assert eng.dispatches == 2
        # ...until a slot frees (batch 0 materializes).
        eng.handles[0].release()
        assert third_submitted.wait(timeout=5)
        eng.handles[1].release()
        eng.handles[2].release()
        assert fut3[0].result(timeout=5)[0, 0] == 2.0
        t.join(timeout=5)
    finally:
        d.close()


def test_sync_failure_lands_on_the_right_future():
    eng = ControlledEngine(fail_sync_at={1})
    d = InFlightDispatcher(eng, depth=3)
    try:
        futs = [d.submit(_imgs(1)) for _ in range(3)]
        for h in eng.handles:
            h.release()
        assert futs[0].result(timeout=5)[0, 0] == 0.0
        with pytest.raises(RuntimeError, match="device fault at sync"):
            futs[1].result(timeout=5)
        # The pipeline survives the failed batch; batch 2 still lands,
        # and the failed batch never inflated the success accounting.
        assert futs[2].result(timeout=5)[0, 0] == 2.0
        assert eng.completed == [1, 1]
    finally:
        d.close()


def test_dispatch_failure_resolves_that_submits_future():
    eng = ControlledEngine(fail_dispatch_at={0})
    d = InFlightDispatcher(eng, depth=2)
    try:
        bad = d.submit(_imgs(1))
        with pytest.raises(ValueError, match="dispatch 0 rejected"):
            bad.result(timeout=5)
        ok = d.submit(_imgs(1))  # the failed dispatch released its slot
        eng.handles[0].release()
        assert ok.result(timeout=5)[0, 0] == 1.0
    finally:
        d.close()


def test_close_drains_inflight_and_rejects_new_submits():
    eng = ControlledEngine()
    d = InFlightDispatcher(eng, depth=2)
    futs = [d.submit(_imgs(1)) for _ in range(2)]

    def release_soon():
        time.sleep(0.1)
        for h in eng.handles:
            h.release()

    threading.Thread(target=release_soon, daemon=True).start()
    d.close()  # must wait out both in-flight batches
    for i, f in enumerate(futs):
        assert f.result(timeout=1)[0, 0] == float(i)  # already resolved
    with pytest.raises(DispatcherClosed):
        d.submit(_imgs(1))
    d.close()  # idempotent


def test_dynamic_batcher_dispatches_next_batch_before_previous_completes():
    """The tentpole behavior at the batcher level: with a pipelined engine
    the dispatch thread must start (assemble AND dispatch) batch N+1 while
    batch N is still executing -- held open here by batch N's unreleased
    handle, so the overlap is structural, not a timing race."""
    from kubernetes_deep_learning_tpu.runtime.batcher import DynamicBatcher

    eng = ControlledEngine()
    eng.spec = SimpleNamespace(input_shape=(2, 2, 3))
    eng.max_batch = 1  # one request per batch -> submit order is batch order
    b = DynamicBatcher(eng, max_delay_ms=0, pipeline_depth=2)
    try:
        img = np.zeros((2, 2, 3), np.uint8)
        f0 = b.submit(img)
        f1 = b.submit(img)
        # Batch 0 has NOT completed (handle unreleased), yet batch 1 must
        # reach the engine: dispatch count hits 2 with zero completions.
        deadline = time.monotonic() + 5
        while eng.dispatches < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.dispatches == 2
        assert eng.completed == []
        eng.handles[0].release()
        eng.handles[1].release()
        assert f0.result(timeout=5)[0] == 0.0
        assert f1.result(timeout=5)[0] == 1.0
    finally:
        b.close()


def test_dynamic_batcher_serial_engine_unchanged():
    """Engines without predict_async keep the dispatch-then-sync loop (no
    dispatcher thread, no behavioral change for plain engines)."""
    from kubernetes_deep_learning_tpu.runtime.batcher import DynamicBatcher

    class Plain:
        max_batch = 4
        spec = SimpleNamespace(input_shape=(2, 2, 3))

        def predict(self, images):
            s = images.reshape(images.shape[0], -1).sum(axis=1)
            return np.stack([s, s * 2], axis=1).astype(np.float32)

    b = DynamicBatcher(Plain(), max_delay_ms=1, pipeline_depth=2)
    try:
        assert b._dispatcher is None
        out = b.predict(np.full((2, 2, 3), 3, np.uint8))
        assert out.tolist() == [36.0, 72.0]
    finally:
        b.close()


def test_dispatcher_emits_stage_metrics():
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    reg = metrics_lib.Registry()
    eng = ControlledEngine()
    d = InFlightDispatcher(eng, depth=2, registry=reg)
    try:
        f = d.submit(_imgs(1))
        eng.handles[0].release()
        f.result(timeout=5)
        text = reg.render()
        for stage in ("enqueue_wait", "dispatch", "execute", "readback"):
            assert f"kdlt_pipeline_{stage}_seconds" in text
        assert "kdlt_pipeline_depth 2.0" in text
    finally:
        d.close()


# --- device-idle seconds by cause -------------------------------------------


class TimedEngine:
    """predict_async takes ``dispatch_s`` of host time; the "device" is a
    serial queue that holds each batch ``device_s`` (a batch starts when
    its dispatch returned and its predecessor finished); the sync returns
    when the batch's device time is over."""

    class _Timed:
        def __init__(self, ready_at, out, fail):
            self._ready_at, self._out, self._fail = ready_at, out, fail

        def __array__(self, dtype=None, copy=None):
            time.sleep(max(0.0, self._ready_at - time.perf_counter()))
            if self._fail:
                raise RuntimeError("device fault at sync")
            return self._out

    def __init__(self, dispatch_s, device_s, fail_dispatch_at=(), fail_sync_at=()):
        self.dispatch_s, self.device_s = dispatch_s, device_s
        self._fail_dispatch_at = set(fail_dispatch_at)
        self._fail_sync_at = set(fail_sync_at)
        self._free_at = 0.0
        self._i = 0

    def predict_async(self, images):
        i, self._i = self._i, self._i + 1
        time.sleep(self.dispatch_s)
        if i in self._fail_dispatch_at:
            raise ValueError(f"dispatch {i} rejected")
        self._free_at = max(self._free_at, time.perf_counter()) + self.device_s
        n = images.shape[0]
        return self._Timed(
            self._free_at, np.zeros((n, 2), np.float32), i in self._fail_sync_at
        ), n


def _serial(d, n):
    for _ in range(n):
        d.submit(_imgs(1)).result(timeout=5)


def _with_gap(d, n):
    d.submit(_imgs(1)).result(timeout=5)
    time.sleep(0.15)
    d.submit(_imgs(1)).result(timeout=5)


def _overlapped(d, n):
    for f in [d.submit(_imgs(1)) for _ in range(n)]:
        f.result(timeout=5)


def _tolerant(d, n):
    for f in [d.submit(_imgs(1)) for _ in range(n)]:
        try:
            f.result(timeout=5)
        except (ValueError, RuntimeError):
            pass


@pytest.mark.parametrize(
    "drive, engine_kw, want",
    [
        # Each batch staged only after the last was read back: the device
        # waits out every dispatch, and is held for every device time.
        pytest.param(
            _serial, dict(dispatch_s=0.03, device_s=0.03),
            dict(idle_dispatch=(0.15, 0.45), inflight=(0.15, 0.45),
                 idle_no_batch=(0.0, 0.05)),
            id="serial-accrues-idle-dispatch",
        ),
        pytest.param(
            _with_gap, dict(dispatch_s=0.01, device_s=0.02),
            dict(idle_no_batch=(0.15, 0.40), idle_dispatch=(0.02, 0.12)),
            id="gap-accrues-idle-no-batch",
        ),
        # Depth 2, dispatch shorter than the device time: after the first
        # dispatch something is always in flight.
        pytest.param(
            _overlapped, dict(dispatch_s=0.01, device_s=0.05),
            dict(idle_dispatch=(0.01, 0.06), idle_no_batch=(0.0, 0.05),
                 inflight=(0.24, 0.60)),
            id="overlapped-accrues-neither",
        ),
        pytest.param(
            _tolerant, dict(dispatch_s=0.02, device_s=0.02, fail_dispatch_at=(1, 3)),
            dict(idle_dispatch=(0.04, 0.30)),
            id="failing-dispatch",
        ),
        pytest.param(
            _tolerant, dict(dispatch_s=0.01, device_s=0.03, fail_sync_at=(0, 2, 4)),
            dict(inflight=(0.12, 0.45)),
            id="failing-sync",
        ),
    ],
)
def test_idle_seconds_by_cause(drive, engine_kw, want):
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    reg = metrics_lib.Registry()
    wall0 = time.perf_counter()
    d = InFlightDispatcher(TimedEngine(**engine_kw), depth=2, registry=reg,
                           watchdog=False)
    born = d._state_since
    try:
        drive(d, 5)
    finally:
        d.close()
    wall = time.perf_counter() - wall0
    got = {cause: c.value for cause, c in d._m_idle.items()}
    for cause, (lo, hi) in want.items():
        assert lo <= got[cause] <= hi, (cause, got)
    # The state machine ends where it began: nothing in flight, no submit
    # inside predict_async -- also after failed dispatches and syncs.
    assert d._submitting == 0 and not d._inflight
    # Every instant is booked to exactly one cause: the three sum to the
    # dispatcher's lifetime, which is the wall time the test saw.
    assert sum(got.values()) == pytest.approx(d._state_since - born, rel=1e-6)
    assert sum(got.values()) == pytest.approx(wall, rel=0.01, abs=0.02)
    page = reg.render()
    for cause in got:
        assert f"kdlt_pipeline_{cause}_seconds_total " in page


def test_idle_counters_advance_on_the_watchdog_tick_while_idle():
    """An idle dispatcher's idle_no_batch must not wait for the next submit
    to be booked: the watchdog's scan accrues it."""
    d = InFlightDispatcher(TimedEngine(0.0, 0.0), depth=2, stall_floor_s=0.05)
    try:
        deadline = time.monotonic() + 5.0
        while (d._m_idle["idle_no_batch"].value < 0.05
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert d._m_idle["idle_no_batch"].value >= 0.05
        assert d._m_idle["inflight"].value == 0.0
    finally:
        d.close()
