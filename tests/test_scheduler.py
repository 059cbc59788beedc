"""Unified SLO-aware scheduling core (runtime/scheduler.py): per-model
lanes, cross-model arbitration (fifo vs weighted earliest-effective-
deadline with weight floors), the shared multi-engine dispatcher, engine
hot-swap semantics, and the invariant metrics contract.  All device-free
(StubEngine simulated devices)."""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.runtime import BatcherClosed, QueueFull
from kubernetes_deep_learning_tpu.runtime.scheduler import (
    Lane,
    UnifiedScheduler,
    resolve_policy,
    resolve_weights,
)
from kubernetes_deep_learning_tpu.runtime.stub import StubEngine, stub_logits
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

SHAPE = (8, 8, 3)


def _spec(name: str, n_labels: int = 3) -> ModelSpec:
    return register_spec(ModelSpec(
        name=name, family="xception", input_shape=SHAPE,
        labels=tuple("abcdefg"[:n_labels]),
    ))


def _engine(name: str, device_ms=0.0, buckets=(1, 2, 4), n_labels=3):
    return StubEngine(
        SimpleNamespace(spec=_spec(name, n_labels)), buckets=buckets,
        async_device=True, device_ms_per_batch=device_ms,
    )


def _imgs(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(n, *SHAPE), dtype=np.uint8)


# --- knob resolution -------------------------------------------------------


def test_resolve_policy_and_weights(monkeypatch):
    assert resolve_policy("fifo") == "fifo"
    assert resolve_policy("WEIGHTED_DEADLINE") == "weighted_deadline"
    assert resolve_policy("garbage") == "weighted_deadline"  # degrade, not die
    monkeypatch.setenv("KDLT_SCHED_POLICY", "fifo")
    assert resolve_policy() == "fifo"
    assert resolve_weights("a=2, b=0.5,junk,c=oops,=1,d=-3") == {
        "a": 2.0, "b": 0.5, "d": 1e-3,  # non-positive clamped, junk skipped
    }
    monkeypatch.setenv("KDLT_SCHED_WEIGHTS", "m=4")
    assert resolve_weights() == {"m": 4.0}


# --- correctness: routing, fan-out, chunks ---------------------------------


def test_two_models_share_one_dispatcher_with_correct_fanout():
    ea, eb = _engine("sched-a", 2.0), _engine("sched-b", 2.0, n_labels=2)
    reg = metrics_lib.Registry()
    s = UnifiedScheduler(registry=reg)
    s.register("sched-a", ea)
    s.register("sched-b", eb)
    try:
        imgs = _imgs(8)
        futs_a = [s.submit("sched-a", imgs[i]) for i in range(4)]
        futs_b = [s.submit("sched-b", imgs[i + 4]) for i in range(4)]
        rows_a = [f.result(timeout=10) for f in futs_a]
        rows_b = [f.result(timeout=10) for f in futs_b]
        want_a, want_b = stub_logits(imgs[:4], 3), stub_logits(imgs[4:], 2)
        for i in range(4):  # per-request rows, never another model's
            assert np.array_equal(rows_a[i], want_a[i])
            assert np.array_equal(rows_b[i], want_b[i])
        # A pre-formed chunk stays contiguous and ordered.
        chunk = s.submit_batch("sched-b", imgs[:3]).result(timeout=10)
        assert np.array_equal(chunk, stub_logits(imgs[:3], 2))
        page = reg.render()
        # The invariant metric contract: batcher series under the model
        # label, pipeline stages attributed per model, scheduler gauges.
        assert 'kdlt_batcher_batch_size_count{model="sched-a"}' in page
        assert 'kdlt_pipeline_execute_seconds_count{model="sched-b"}' in page
        assert "kdlt_sched_models 2.0" in page
        assert 'kdlt_sched_policy{policy="weighted_deadline"} 1.0' in page
    finally:
        s.close()
        ea.close()
        eb.close()


def test_submit_validates_model_shape_dtype_and_chunk_size():
    e = _engine("sched-val")
    s = UnifiedScheduler(registry=metrics_lib.Registry())
    s.register("sched-val", e)
    try:
        with pytest.raises(ValueError, match="no scheduling lane"):
            s.submit("nope", _imgs(1)[0])
        with pytest.raises(ValueError, match="uint8"):
            s.submit("sched-val", _imgs(1)[0].astype(np.float32))
        with pytest.raises(ValueError, match="shape"):
            s.submit("sched-val", np.zeros((4, 4, 3), np.uint8))
        with pytest.raises(ValueError, match="max bucket"):
            s.submit_batch("sched-val", _imgs(5))  # max bucket is 4
    finally:
        s.close()
        e.close()


def test_queue_cap_sheds_with_queue_full():
    e = _engine("sched-cap", device_ms=50.0)
    s = UnifiedScheduler(registry=metrics_lib.Registry(), queue_cap=4)
    s.register("sched-cap", e)
    try:
        futs = [s.submit("sched-cap", _imgs(1)[0]) for _ in range(4)]
        with pytest.raises(QueueFull):
            for _ in range(8):  # the dispatch thread may drain a few
                s.submit("sched-cap", _imgs(1)[0])
        for f in futs:
            f.result(timeout=10)
    finally:
        s.close()
        e.close()


# --- lifecycle: hot-swap, unregister, close --------------------------------


def test_engine_hot_swap_preserves_lane_and_stale_close_is_noop():
    e1 = _engine("sched-swap", 1.0)
    s = UnifiedScheduler(registry=metrics_lib.Registry())
    lane = s.register("sched-swap", e1)
    try:
        assert s.submit("sched-swap", _imgs(1)[0]).result(timeout=10) is not None
        e2 = _engine("sched-swap", 1.0)
        assert s.register("sched-swap", e2) is lane  # same lane, new engine
        assert lane.engine is e2
        # The superseded owner's unregister must NOT tear down the lane.
        s.unregister("sched-swap", engine=e1)
        assert s.lane("sched-swap") is lane
        assert s.submit("sched-swap", _imgs(1)[0]).result(timeout=10) is not None
        # The current owner's unregister does, failing queued work loudly.
        s.unregister("sched-swap", engine=e2)
        assert s.lane("sched-swap") is None
        with pytest.raises(ValueError, match="no scheduling lane"):
            s.submit("sched-swap", _imgs(1)[0])
        e2.close()
    finally:
        s.close()
        e1.close()


def test_close_without_drain_fails_queued_waiters():
    e = _engine("sched-close", device_ms=200.0)
    s = UnifiedScheduler(registry=metrics_lib.Registry())
    s.register("sched-close", e)
    futs = [s.submit("sched-close", _imgs(1)[0]) for _ in range(6)]
    s.close(drain=False)
    e.close()
    outcomes = []
    for f in futs:
        try:
            f.result(timeout=10)
            outcomes.append("ok")
        except BatcherClosed:
            outcomes.append("closed")
        except Exception:
            outcomes.append("other")
    # Every waiter resolves (no strands); queued-but-undispatched ones get
    # the typed BatcherClosed the server maps to a retryable 5xx.
    assert "other" not in outcomes
    with pytest.raises(BatcherClosed):
        s.submit("sched-close", _imgs(1)[0])


# --- arbitration policy ----------------------------------------------------


def _lane(name, weight=1.0, cost_s=0.0, deadlines=(), enq_ts=(), served=0.0):
    lane = Lane(
        name, engine=SimpleNamespace(max_batch=4), weight=weight,
        max_delay_s=0.002, queue_cap=2048,
        metrics=metrics_lib.scheduler_lane_metrics(
            metrics_lib.Registry(), name
        ),
    )
    lane.cost_per_image_s = cost_s or None
    now = time.monotonic()
    for i, d in enumerate(deadlines):
        u = SimpleNamespace(
            n=1, deadline_abs=None if d is None else now + d,
            enq_t=now + (enq_ts[i] if i < len(enq_ts) else 0.0),
            priority=None,  # no class: no slack on the effective deadline
        )
        lane.queue.append(u)
        lane.pending_images += 1
    lane.served_s = served
    return lane


def test_fifo_policy_picks_the_oldest_head():
    s = UnifiedScheduler(registry=metrics_lib.Registry(), policy="fifo")
    try:
        old = _lane("old", deadlines=[5.0], enq_ts=[-3.0])
        young = _lane("young", deadlines=[0.01], enq_ts=[0.0])
        # FIFO ignores urgency entirely: the older head wins even though
        # the young lane's deadline is about to pass.
        assert s._choose([old, young], time.monotonic()) is old
    finally:
        s.close()


def test_weighted_policy_orders_by_effective_deadline():
    s = UnifiedScheduler(registry=metrics_lib.Registry())
    try:
        now = time.monotonic()
        loose = _lane("loose", deadlines=[5.0], enq_ts=[-3.0])
        tight = _lane("tight", deadlines=[0.2], enq_ts=[0.0])
        assert s._choose([loose, tight], now) is tight
        # The cost estimate shifts urgency: same wire deadline, but the
        # expensive model must START earlier (latest viable start wins).
        slow = _lane("slow", cost_s=0.3, deadlines=[1.0])
        fast = _lane("fast", cost_s=0.001, deadlines=[1.0])
        assert s._choose([slow, fast], now) is slow
    finally:
        s.close()


def test_weight_floor_rescues_a_starved_lane():
    s = UnifiedScheduler(registry=metrics_lib.Registry())
    try:
        now = time.monotonic()
        # The hog consumed ~all recent device time AND holds the earlier
        # deadline (the EDF-under-overload domino); the starved lane is
        # below its 50% fair-share floor, so the floor preempts EDF.
        hog = _lane("hog", served=10.0, deadlines=[0.05])
        starved = _lane("starved", served=0.0, deadlines=[1.0])
        assert s._choose([hog, starved], now) is starved
        assert starved.m["floor_boosts"].value == 1.0
        # With shares in balance the floor stands down and EDF decides.
        hog2 = _lane("hog2", served=1.0, deadlines=[0.05])
        fed = _lane("fed", served=1.0, deadlines=[1.0])
        assert s._choose([hog2, fed], now) is hog2
    finally:
        s.close()


def test_fifo_starves_tight_deadlines_where_weighted_serves_them():
    """The multimodel-ab scenario in miniature: a heavy overloaded lane +
    a light tight-deadline lane.  Weighted serves the light lane inside
    its deadline; FIFO leaves it behind the heavy backlog."""

    def run(policy: str) -> float:
        heavy = _engine(f"mm-{policy}-heavy", device_ms=60.0)
        light = _engine(f"mm-{policy}-light", device_ms=1.0, n_labels=2)
        s = UnifiedScheduler(registry=metrics_lib.Registry(), policy=policy)
        s.register(f"mm-{policy}-heavy", heavy)
        s.register(f"mm-{policy}-light", light)
        from kubernetes_deep_learning_tpu.serving.admission import Deadline

        try:
            # Saturate the heavy lane (each batch 60 ms, bucket 4).
            heavy_futs = [
                s.submit(f"mm-{policy}-heavy", _imgs(1)[0],
                         deadline=Deadline(10.0))
                for _ in range(40)
            ]
            time.sleep(0.15)  # let the heavy backlog establish itself
            t0 = time.monotonic()
            light_fut = s.submit(
                f"mm-{policy}-light", _imgs(1)[0], deadline=Deadline(0.25)
            )
            light_fut.result(timeout=10)
            light_latency = time.monotonic() - t0
            for f in heavy_futs:
                f.result(timeout=30)
            return light_latency
        finally:
            s.close()
            heavy.close()
            light.close()

    weighted = run("weighted_deadline")
    fifo = run("fifo")
    # Weighted: the light request preempts the backlog (sub-deadline).
    # FIFO: it waits out most of the remaining heavy queue head-of-line.
    assert weighted < 0.25, f"weighted served the light lane in {weighted:.3f}s"
    assert fifo > 2 * weighted, (weighted, fifo)


# --- request traces --------------------------------------------------------


def test_scheduler_records_queue_wait_and_pipeline_spans():
    from kubernetes_deep_learning_tpu.utils import trace as trace_lib

    e = _engine("sched-trace", 1.0)
    s = UnifiedScheduler(registry=metrics_lib.Registry())
    s.register("sched-trace", e)
    tracer = trace_lib.Tracer("test")
    try:
        rt = tracer.request_trace("rid-sched")
        s.submit("sched-trace", _imgs(1)[0], trace=rt).result(timeout=10)
        names = {sp["name"] for sp in tracer.spans("rid-sched")}
        # The same span contract the batchers uphold: queue wait + the
        # four pipeline stages.
        assert "batcher.queue_wait" in names
        for stage in ("enqueue_wait", "dispatch", "execute", "readback"):
            assert f"pipeline.{stage}" in names
    finally:
        s.close()
        e.close()
