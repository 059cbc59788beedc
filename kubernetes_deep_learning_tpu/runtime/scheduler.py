"""Unified SLO-aware scheduling core: which requests run next, for which model.

Before this module, "which requests run next, at what batch size, with what
budget" was split across four subsystems -- DynamicBatcher / NativeBatcher
(coalescing + linger), UpstreamMicroBatcher (the same policy one tier up),
AdaptiveLimiter (how many requests may wait at all), and InFlightDispatcher
(how many batches may ride the device) -- each owning a piece of the
decision for exactly ONE model.  Multi-model serving (Clipper NSDI'17,
INFaaS ATC'21) needs the decision in one place: many models share one
accelerator, and the interesting question is *whose* batch runs next.

This scheduler is that place.  The interface is deliberately small:

    per request in:   (model, payload, deadline budget, implicit cost
                       estimate from the model's observed service times)
    dispatch plan out: one (model, batch) handed to ONE shared
                       InFlightDispatcher -- a single bounded in-flight
                       budget and a single FIFO completion thread for the
                       whole tier, because the device runs one program at a
                       time no matter which model compiled it.

Per model there is a *lane*: a bounded queue with the classic continuous-
batching flush policy (dispatch when full; linger up to ``max_delay`` for
stragglers when small -- the DynamicBatcher policy, unchanged and now in
one place).  Across lanes a :class:`SchedulerPolicy` arbitrates:

- ``fifo`` -- the naive baseline: whichever lane's head request arrived
  first.  Head-of-line blocking across models is the failure mode this
  exists to demonstrate.
- ``weighted_deadline`` (default) -- earliest *effective* deadline first:
  a lane's urgency is its earliest absolute deadline minus the estimated
  service time of the batch (latest viable start), so a slow model's
  request with the same deadline correctly outranks a fast model's.  On
  top, per-model *weight floors*: each lane is guaranteed
  ``WEIGHT_FLOOR_FRACTION`` of its weight's fair share of observed device
  time; a lane starved below its floor preempts the deadline order (the
  guard that keeps a heavy model with tight deadlines from starving a
  light one into 100% misses).

Knobs: ``KDLT_SCHED_POLICY`` (weighted_deadline | fifo) and
``KDLT_SCHED_WEIGHTS`` ("modelA=2,modelB=1"; unlisted models weigh 1).

Invariant contract kept during the refactor: requests still see
``kdlt_batcher_batch_size`` / ``kdlt_batcher_rejected_total`` (now under
the bounded ``model`` label), batches still land in the
``kdlt_pipeline_*_seconds`` stage histograms (model-labeled via the shared
dispatcher), and traced requests still get their ``batcher.queue_wait``
span ahead of the four pipeline-stage spans.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future

import numpy as np

from kubernetes_deep_learning_tpu.runtime.batcher import BatcherClosed, QueueFull
from kubernetes_deep_learning_tpu.runtime.engine import InFlightDispatcher
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from kubernetes_deep_learning_tpu.utils import trace as trace_lib

SCHED_POLICY_ENV = "KDLT_SCHED_POLICY"
SCHED_WEIGHTS_ENV = "KDLT_SCHED_WEIGHTS"
POLICIES = ("weighted_deadline", "fifo")
DEFAULT_POLICY = "weighted_deadline"

# A lane is guaranteed this fraction of its weight's fair share of device
# time before the starvation guard preempts the deadline order.  Below 1.0
# on purpose: the guard is a floor against starvation, not a fair-share
# enforcer -- deadline order should win whenever nobody is being starved.
WEIGHT_FLOOR_FRACTION = 0.5

# Served-share accounting decays with this half-life so the floor guard
# reacts to the CURRENT mix, not the whole process history.
SHARE_HALFLIFE_S = 10.0

# Requests without a deadline budget get this implicit slack for ordering
# purposes (the reference's 20 s ceiling): among deadline-less traffic the
# weighted policy therefore degrades to FIFO, which is the legacy behavior.
DEFAULT_SLACK_S = 20.0

# Priority classes modulate a unit's EFFECTIVE deadline (ordering only --
# the real deadline still decides timeouts): lower classes concede this
# much slack, so an interactive unit outranks a batch unit enqueued with
# the same budget, without ever starving the lower class outright (its
# relaxed deadline still comes due).  The class names mirror
# serving.protocol.PRIORITY_CLASSES; spelled locally because the runtime
# layer sits below the serving wire contract.  Unknown/absent classes get
# zero slack (legacy submitters keep their exact ordering).
PRIORITY_SLACK_S = {"interactive": 0.0, "batch": 1.0, "best-effort": 5.0}


def resolve_policy(policy: str | None = None) -> str:
    """Explicit arg > $KDLT_SCHED_POLICY > weighted_deadline.  Unknown
    values degrade to the default rather than killing serving."""
    if policy is None:
        policy = os.environ.get(SCHED_POLICY_ENV, "").strip().lower()
    else:
        policy = str(policy).strip().lower()
    return policy if policy in POLICIES else DEFAULT_POLICY


def resolve_weights(raw: str | None = None) -> dict[str, float]:
    """Parse "modelA=2,modelB=0.5" (the $KDLT_SCHED_WEIGHTS format) into a
    name -> weight map; malformed entries are skipped, non-positive weights
    clamped to a small positive value (a zero weight would mean "never
    guaranteed anything", which is a misconfiguration, not a policy)."""
    if raw is None:
        raw = os.environ.get(SCHED_WEIGHTS_ENV, "")
    weights: dict[str, float] = {}
    for part in str(raw).split(","):
        name, sep, value = part.strip().partition("=")
        if not sep or not name:
            continue
        try:
            weights[name] = max(float(value), 1e-3)
        except ValueError:
            continue
    return weights


class _Unit:
    """One queued unit of work: a single image or a pre-formed chunk.
    Units are never split across batches (a chunk's rows stay contiguous,
    which is what makes results bit-identical to the unscheduled path)."""

    __slots__ = (
        "images", "n", "future", "deadline_abs", "trace", "enq_t", "enq_w",
        "single", "priority",
    )

    def __init__(self, images, n, deadline_abs, trace, single, priority=None):
        self.images = images
        self.n = n
        self.future: Future = Future()
        self.deadline_abs = deadline_abs  # absolute time.monotonic, or None
        self.trace = trace
        self.enq_t = time.monotonic()
        self.enq_w = trace_lib.now_s() if trace is not None else 0.0
        self.single = single  # resolve to one row (True) or the row block
        self.priority = priority  # PRIORITY_SLACK_S key, or None (legacy)


class Lane:
    """Per-model scheduling state: queue + flush policy + share accounting.

    The lane survives engine hot-swaps (version reloads replace
    ``engine``; queued units are engine-agnostic until dispatch), which is
    what makes a reload of model A invisible to model B's in-flight work.

    Mesh engines (data-sharded or tensor-parallel, runtime.engine mesh=)
    are ordinary lanes: the engine already rounded its bucket ladder up to
    multiples of the DATA-axis size at construction (model_parallel > 1
    shrinks that axis, not the rounding rule), so max_batch / bucket_for
    need no sharding awareness here.
    """

    def __init__(self, name: str, engine, weight: float, max_delay_s: float,
                 queue_cap: int, metrics: dict):
        self.name = name
        self.engine = engine
        self.weight = weight
        self.max_delay_s = max_delay_s
        self.queue_cap = queue_cap
        self.queue: list[_Unit] = []
        self.pending_images = 0
        self.m = metrics
        self.m["weight"].set(weight)
        # Decayed device-seconds this lane consumed (the share the weight
        # floor guards) and the per-image service-time EWMA (the cost
        # estimate behind effective deadlines).  Own lock: the dispatch
        # thread reads shares under the scheduler lock while the
        # dispatcher's completion thread reports served time without it.
        self._share_lock = threading.Lock()
        self.served_s = 0.0          # guarded-by: _share_lock
        self._served_at = time.monotonic()  # guarded-by: _share_lock
        self.cost_per_image_s: float | None = None  # guarded-by: _share_lock

    @property
    def max_batch(self) -> int:
        return self.engine.max_batch

    def decayed_served(self, now: float) -> float:
        with self._share_lock:
            return self._decayed_served_locked(now)

    def _decayed_served_locked(self, now: float) -> float:
        dt = max(0.0, now - self._served_at)
        if dt > 0:
            self.served_s *= 0.5 ** (dt / SHARE_HALFLIFE_S)
            self._served_at = now
        return self.served_s

    def observe_served(self, seconds: float, n_images: int) -> None:
        now = time.monotonic()
        with self._share_lock:
            self._decayed_served_locked(now)
            self.served_s += seconds
            per_image = seconds / max(n_images, 1)
            self.cost_per_image_s = (
                per_image if self.cost_per_image_s is None
                else 0.7 * self.cost_per_image_s + 0.3 * per_image
            )
        self.m["device_seconds"].inc(seconds)

    def cost_estimate_s(self, n_images: int) -> float:
        """Estimated service time of an ``n_images`` batch (0 until the
        first completion seeds the EWMA -- an optimistic cold estimate only
        biases the first batch's ordering)."""
        with self._share_lock:
            return (self.cost_per_image_s or 0.0) * n_images

    def effective_deadline(self, now: float) -> float:
        """The lane's urgency: earliest absolute deadline among queued
        units minus the estimated service time of the head batch -- the
        latest moment a dispatch can still start and make its deadline."""
        batch = min(self.pending_images, self.max_batch)
        est = self.cost_estimate_s(batch)
        earliest = min(
            (
                u.deadline_abs if u.deadline_abs is not None
                else u.enq_t + DEFAULT_SLACK_S
            )
            + PRIORITY_SLACK_S.get(u.priority, 0.0)
            for u in self.queue
        )
        return earliest - est

    def oldest_enq_t(self) -> float:
        return self.queue[0].enq_t if self.queue else float("inf")


class UnifiedScheduler:
    """The model tier's one queue/scheduler: requests in, dispatch plans out.

    One dispatch thread owns every decision; one shared InFlightDispatcher
    executes the plans (bounded in-flight depth = the whole tier's device
    budget).  See the module docstring for the policy semantics.
    """

    def __init__(
        self,
        registry: metrics_lib.Registry | None = None,
        policy: str | None = None,
        weights: dict[str, float] | None = None,
        pipeline_depth: int | None = None,
        queue_cap: int = 2048,
        dispatcher: InFlightDispatcher | None = None,
    ):
        self.registry = registry or metrics_lib.Registry()
        self.policy = resolve_policy(policy)
        self._weights = dict(weights) if weights is not None else resolve_weights()
        self._queue_cap = queue_cap
        self.dispatcher = dispatcher or InFlightDispatcher(
            None, depth=pipeline_depth, registry=self.registry
        )
        self._owns_dispatcher = dispatcher is None
        self._cond = threading.Condition()
        self._lanes: dict[str, Lane] = {}  # guarded-by: _cond
        # Lane metrics persist across unregister/re-register cycles (the
        # central mint dedupes by (name, labels); re-minting would raise).
        self._lane_metrics: dict[str, dict] = {}  # guarded-by: _cond
        self._closed = False         # guarded-by: _cond
        self._m_models = self.registry.gauge(
            "kdlt_sched_models", "models registered with the scheduler"
        )
        self._m_policy = {
            p: self.registry.with_labels(policy=p).gauge(
                "kdlt_sched_policy", "1 for the active arbitration policy"
            )
            for p in POLICIES
        }
        self._m_policy[self.policy].set(1.0)
        self._thread = threading.Thread(
            target=self._run, name="kdlt-scheduler", daemon=True
        )
        self._thread.start()

    @property
    def stalled(self) -> bool:
        return self.dispatcher.stalled

    # --- lane lifecycle -----------------------------------------------------

    def register(self, name: str, engine, weight: float | None = None,
                 max_delay_ms: float = 2.0) -> Lane:
        """Add a model lane, or hot-swap an existing lane's engine (version
        reload): queued units are engine-agnostic, so a swap never drops or
        reorders work, and other lanes are untouched."""
        if weight is None:
            weight = self._weights.get(name, 1.0)
        with self._cond:
            if self._closed:
                raise BatcherClosed("scheduler is shut down")
            lane = self._lanes.get(name)
            if lane is not None:
                lane.engine = engine
                lane.weight = weight
                lane.m["weight"].set(weight)
                return lane
            metrics = self._lane_metrics.get(name)
            if metrics is None:
                metrics = metrics_lib.scheduler_lane_metrics(self.registry, name)
                self._lane_metrics[name] = metrics
            lane = Lane(
                name, engine, weight, max_delay_ms / 1e3, self._queue_cap,
                metrics,
            )
            self._lanes[name] = lane
            self._m_models.set(float(len(self._lanes)))
            return lane

    def unregister(self, name: str, engine=None) -> None:
        """Remove a lane (model unloaded).  ``engine`` guards the hot-swap
        race: a superseded ServedModel's close must not tear down the lane
        its replacement already owns."""
        with self._cond:
            lane = self._lanes.get(name)
            if lane is None or (engine is not None and lane.engine is not engine):
                return
            del self._lanes[name]
            self._m_models.set(float(len(self._lanes)))
            pending = lane.queue[:]
            lane.queue.clear()
            lane.pending_images = 0
            lane.m["queue_depth"].set(0.0)
        for u in pending:
            if not u.future.cancelled():
                u.future.set_exception(
                    BatcherClosed(f"model {name!r} was unloaded")
                )

    def lane(self, name: str) -> Lane | None:
        with self._cond:
            return self._lanes.get(name)

    def lanes_snapshot(self) -> dict:
        """Point-in-time per-lane state for the incident flight recorder
        (utils/flightrecorder.py): what each model's queue looked like at
        capture time -- depth, pending images, decayed device-second
        share, cost EWMA.  Cheap and lock-consistent; JSON-ready."""
        now = time.monotonic()
        with self._cond:
            return {
                "policy": self.policy,
                "stalled": self.stalled,
                "lanes": {
                    name: {
                        "weight": lane.weight,
                        "queue_depth": len(lane.queue),
                        "pending_images": lane.pending_images,
                        "queue_cap": lane.queue_cap,
                        "max_delay_s": lane.max_delay_s,
                        "served_s": round(lane.decayed_served(now), 6),
                        "cost_per_image_s": (
                            round(lane.cost_per_image_s, 6)
                            if lane.cost_per_image_s is not None else None
                        ),
                    }
                    for name, lane in self._lanes.items()
                },
            }

    # --- request intake -----------------------------------------------------

    def submit(self, model: str, image: np.ndarray, deadline=None,
               trace=None, priority=None) -> Future:
        """One HWC uint8 image; the future resolves to its logits row.

        ``deadline`` is a serving.admission Deadline (or None); its
        remaining budget becomes the request's absolute deadline in the
        arbitration order.  ``priority`` (a PRIORITY_SLACK_S key) relaxes
        the unit's effective deadline for lower classes.  ``trace`` gets
        the ``batcher.queue_wait`` span plus the pipeline-stage spans,
        exactly like the batchers."""
        image = np.asarray(image)
        return self._enqueue(
            model, image[None], 1, deadline, trace, single=True,
            priority=priority,
        )

    def submit_batch(self, model: str, images: np.ndarray, deadline=None,
                     trace=None, priority=None) -> Future:
        """A pre-formed uint8 chunk (n <= the model's max bucket); the
        future resolves to its n logits rows, contiguous and in order."""
        images = np.asarray(images)
        return self._enqueue(
            model, images, images.shape[0], deadline, trace, single=False,
            priority=priority,
        )

    def _enqueue(self, model, images, n, deadline, trace, single,
                 priority=None) -> Future:
        if images.dtype != np.uint8:
            raise ValueError(f"scheduler takes uint8 images, got {images.dtype}")
        deadline_abs = None
        if deadline is not None:
            deadline_abs = time.monotonic() + max(deadline.remaining_s(), 0.0)
        with self._cond:
            if self._closed:
                raise BatcherClosed("scheduler is shut down")
            lane = self._lanes.get(model)
            if lane is None:
                raise ValueError(f"no scheduling lane for model {model!r}")
            expected = tuple(lane.engine.spec.input_shape)
            if tuple(images.shape[1:]) != expected:
                raise ValueError(
                    f"image shape {tuple(images.shape[1:])} != expected {expected}"
                )
            if n > lane.max_batch:
                raise ValueError(
                    f"chunk of {n} exceeds model {model!r}'s max bucket "
                    f"{lane.max_batch}; chunk before submitting"
                )
            if lane.pending_images + n > lane.queue_cap:
                lane.m["queue_full"].inc()
                raise QueueFull(f"request queue full for model {model!r}")
            unit = _Unit(images, n, deadline_abs, trace, single,
                         priority=priority)
            lane.queue.append(unit)
            lane.pending_images += n
            lane.m["queue_depth"].set(float(lane.pending_images))
            self._cond.notify()
        return unit.future

    # --- the dispatch loop --------------------------------------------------

    def _lane_ready_locked(self, lane: Lane, now: float) -> bool:
        """The continuous-batching flush rule, per lane: dispatch when the
        batch is full, the linger expired, or we are draining for close.
        Deadline pressure also readies a lane early: once the effective
        deadline is upon us, lingering for stragglers only converts a
        viable request into a missed one."""
        if not lane.queue:
            return False
        if lane.pending_images >= lane.max_batch or self._closed:
            return True
        if now - lane.queue[0].enq_t >= lane.max_delay_s:
            return True
        return lane.effective_deadline(now) <= now

    def _choose(self, ready: list[Lane], now: float) -> Lane:
        if len(ready) == 1:
            return ready[0]
        if self.policy == "fifo":
            return min(ready, key=Lane.oldest_enq_t)
        # weighted_deadline: weight floors first, then earliest effective
        # deadline.  Shares/floors are computed over the lanes CURRENTLY
        # contending -- an idle model neither earns nor loses share.
        total_w = sum(l.weight for l in ready) or 1.0
        served = {l.name: l.decayed_served(now) for l in ready}
        total_served = sum(served.values())
        if total_served > 0:
            starved = []
            for l in ready:
                fair = l.weight / total_w
                actual = served[l.name] / total_served
                deficit = fair * WEIGHT_FLOOR_FRACTION - actual
                if deficit > 0:
                    starved.append((deficit, l))
            if starved:
                deficit, lane = max(starved, key=lambda d_l: d_l[0])
                lane.m["floor_boosts"].inc()
                return lane
        return min(ready, key=lambda l: l.effective_deadline(now))

    def _take_plan(self):
        """Block until a dispatch plan exists: (lane, units) -- or None
        when closed and drained."""
        with self._cond:
            while True:
                lanes = [l for l in self._lanes.values() if l.queue]
                if not lanes:
                    if self._closed:
                        return None
                    self._cond.wait()
                    continue
                now = time.monotonic()
                ready = [l for l in lanes if self._lane_ready_locked(l, now)]
                if not ready:
                    # Sleep until the earliest linger/deadline readiness;
                    # new submits notify and re-evaluate sooner.
                    wake = min(
                        min(
                            l.queue[0].enq_t + l.max_delay_s,
                            l.effective_deadline(now),
                        )
                        for l in lanes
                    )
                    self._cond.wait(timeout=max(wake - now, 1e-4))
                    continue
                lane = self._choose(ready, now)
                units: list[_Unit] = []
                total = 0
                taken_at = time.monotonic()
                while lane.queue and total + lane.queue[0].n <= lane.max_batch:
                    unit = lane.queue.pop(0)
                    units.append(unit)
                    total += unit.n
                    # Queue age at scheduling: the cross-model arbitration
                    # delay an autoscaler/operator reads per lane
                    # (kdlt_sched_queue_age_seconds{model=...}).
                    lane.m["queue_age"].observe(max(0.0, taken_at - unit.enq_t))
                lane.pending_images -= total
                lane.m["queue_depth"].set(float(lane.pending_images))
                return lane, units, total

    def _run(self) -> None:
        while True:
            plan = self._take_plan()
            if plan is None:
                return
            lane, units, total = plan
            lane.m["batch_size"].observe(total)
            lane.m["dispatch"].inc()
            traces = [u.trace for u in units if u.trace is not None]
            if traces:
                taken_w = trace_lib.now_s()
                for u in units:
                    if u.trace is not None:
                        u.trace.record(
                            "batcher.queue_wait", u.enq_w, taken_w - u.enq_w,
                            batch=total, model=lane.name,
                        )
            batch = (
                units[0].images if len(units) == 1
                else np.concatenate([u.images for u in units])
            )
            t_sub = time.monotonic()
            try:
                fut = self.dispatcher.submit(
                    batch, traces=traces, engine=lane.engine, model=lane.name
                )
            except Exception as e:  # stalled/closed dispatcher, bad batch
                for u in units:
                    if not u.future.cancelled():
                        u.future.set_exception(e)
                continue
            fut.add_done_callback(
                lambda f, lane=lane, units=units, total=total, t=t_sub:
                self._publish(lane, units, total, t, f)
            )

    def _publish(self, lane: Lane, units, total: int, t_sub: float,
                 fut_batch: Future) -> None:
        """Fan one completed plan's rows (or failure) out to its units.
        Runs on the dispatcher's completion thread; must not raise."""
        lane.observe_served(max(time.monotonic() - t_sub, 0.0), total)
        exc = fut_batch.exception()
        if exc is not None:
            for u in units:
                if not u.future.cancelled():
                    u.future.set_exception(exc)
            return
        rows = fut_batch.result()
        off = 0
        for u in units:
            if not u.future.cancelled():
                u.future.set_result(
                    rows[off] if u.single else rows[off:off + u.n]
                )
            off += u.n

    def close(self, drain: bool = True) -> None:
        with self._cond:
            self._closed = True
            if not drain:
                for lane in self._lanes.values():
                    pending = lane.queue[:]
                    lane.queue.clear()
                    lane.pending_images = 0
                    lane.m["queue_depth"].set(0.0)
                    for u in pending:
                        if not u.future.cancelled():
                            u.future.set_exception(
                                BatcherClosed("scheduler shut down")
                            )
            self._cond.notify_all()
        self._thread.join(timeout=30.0)
        if self._owns_dispatcher:
            self.dispatcher.close(drain=True)
