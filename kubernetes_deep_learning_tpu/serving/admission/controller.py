"""AdmissionController: a tier's front door, plus graceful drain.

One controller sits in front of each serving tier's request handling
(gateway /predict, model-server :predict).  Per request it applies, in
order: drain refusal, deadline-exhausted rejection, and the adaptive
concurrency limiter's bounded queue -- raising a typed Shed for the
transport to map to 503/504 + Retry-After -- and tracks the in-flight
count that graceful drain waits on.  All decisions land in the
``kdlt_admission_*`` series (utils.metrics.admission_metrics) under the
tier's label.

``enabled=False`` (or KDLT_ADMISSION=0) keeps the controller as a pure
in-flight tracker: no limiter, no deadline rejection -- the exact legacy
behavior -- but drain still works (shutdown semantics are not load policy).
"""

from __future__ import annotations

import os
import signal
import threading
import time

from kubernetes_deep_learning_tpu.serving.admission.deadline import Deadline
from kubernetes_deep_learning_tpu.serving.admission.limiter import AdaptiveLimiter
from kubernetes_deep_learning_tpu.serving.admission.shed import Shed
from kubernetes_deep_learning_tpu.serving.protocol import DEFAULT_PRIORITY
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

ADMISSION_ENV = "KDLT_ADMISSION"
DRAIN_TIMEOUT_ENV = "KDLT_DRAIN_TIMEOUT_S"
# Inside the k8s terminationGracePeriodSeconds (30 gateway / 60 model tier)
# minus the preStop sleep, so the drain always finishes before the kill.
DEFAULT_DRAIN_TIMEOUT_S = 25.0
DRAIN_RETRY_AFTER_S = 1.0  # "come back via a replica that is not dying"


def admission_enabled(explicit: bool | None = None) -> bool:
    """Explicit arg > $KDLT_ADMISSION > enabled-by-default."""
    if explicit is not None:
        return bool(explicit)
    raw = os.environ.get(ADMISSION_ENV, "").strip().lower()
    return raw not in ("0", "false", "off", "no")


# Observed-latency AIMD bands, as fractions of the deadline budget spent by
# the time the ticket is released.  Above CONGESTION the completion counts
# as a congestion signal even though it technically made it (the NEXT
# request one queue-slot further back will not); below HEADROOM it earns an
# additive increase; between the two the limit holds.  The hold band keeps
# the equilibrium below the everything-finishes-exactly-at-the-deadline
# regime.
LATENCY_CONGESTION_FRACTION = 0.5
LATENCY_HEADROOM_FRACTION = 0.25


class Ticket:
    """Proof of admission; must be released exactly once (finally block).

    ``mark_overloaded()`` before release feeds the limiter's multiplicative
    decrease: the handler observed downstream congestion (deadline miss,
    full batcher queue, upstream 503) while holding this slot.  A release
    that finds more than LATENCY_CONGESTION_FRACTION of the deadline budget
    spent is treated the same way.
    """

    __slots__ = (
        "_controller", "queue_wait_s", "_deadline", "_overloaded", "_released",
        "model", "_t0",
    )

    def __init__(
        self,
        controller: "AdmissionController",
        queue_wait_s: float,
        deadline: Deadline | None = None,
        model: str | None = None,
    ):
        self._controller = controller
        self.queue_wait_s = queue_wait_s
        self._deadline = deadline
        self._overloaded = False
        self._released = False
        self.model = model
        self._t0 = time.monotonic()

    def mark_overloaded(self) -> None:
        self._overloaded = True

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        overloaded = self._overloaded
        headroom = True
        if self._deadline is not None:
            spent_fraction = 1.0 - (
                self._deadline.remaining_s() / max(self._deadline.budget_s, 1e-9)
            )
            overloaded = overloaded or spent_fraction > LATENCY_CONGESTION_FRACTION
            headroom = spent_fraction < LATENCY_HEADROOM_FRACTION
        self._controller._release(
            self.queue_wait_s, overloaded, headroom,
            model=self.model, held_s=time.monotonic() - self._t0,
        )


class AdmissionController:
    def __init__(
        self,
        registry: metrics_lib.Registry,
        tier: str,
        enabled: bool | None = None,
        limiter: AdaptiveLimiter | None = None,
    ):
        self.tier = tier
        self.enabled = admission_enabled(enabled)
        self._limiter = (
            limiter if limiter is not None
            else (AdaptiveLimiter() if self.enabled else None)
        )
        self._tier_registry = registry.with_labels(tier=tier)
        self._m = metrics_lib.admission_metrics(self._tier_registry)
        # Per-priority-class admitted/shed (bounded `class` label, minted
        # centrally): which class pays for an overload is the question the
        # brownout gates and --tenant-ab read.
        self._class_m = metrics_lib.admission_class_metrics(self._tier_registry)
        # Per-model kdlt_admission_* slices (bounded `model` label, minted
        # centrally): lazily created per model name the handlers pass in.
        self._model_m: dict[str, dict] = {}  # guarded-by: _model_m_lock
        self._model_m_lock = threading.Lock()
        if self._limiter is not None:
            self._m["limit"].set(self._limiter.limit)
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._inflight = 0           # guarded-by: _lock
        # Monotonic one-way flag (False -> True, never back): admit()
        # reads it lock-free; a request racing the flip is equivalently
        # ordered either way, so no lock is needed.
        self._draining = False

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    @property
    def limit(self) -> float | None:
        return self._limiter.limit if self._limiter is not None else None

    @property
    def limiter(self) -> AdaptiveLimiter | None:
        return self._limiter

    def retry_after_s(self, fallback: float = 0.05) -> float:
        """A live Retry-After for sheds decided outside the limiter: the
        limiter's queue-depth/hold-time derivation (jittered) when one
        exists, else the caller's fallback."""
        if self._limiter is not None:
            return self._limiter.retry_after_s()
        return fallback

    def _model_metrics(self, model: str | None) -> dict | None:
        if model is None:
            return None
        with self._model_m_lock:
            mm = self._model_m.get(model)
            if mm is None:
                if len(self._model_m) >= 2 * metrics_lib.MODEL_LABEL_CAP:
                    # Memo cap: past it, unmemoized names go straight to
                    # the overflow bucket so a hostile stream of distinct
                    # names cannot grow this dict (the label itself is
                    # already capped by the central mint).
                    return metrics_lib.admission_model_metrics(
                        self._tier_registry, metrics_lib.MODEL_LABEL_OVERFLOW
                    )
                mm = metrics_lib.admission_model_metrics(
                    self._tier_registry, model
                )
                self._model_m[model] = mm
            return mm

    def admit(
        self,
        deadline: Deadline | None = None,
        model: str | None = None,
        priority: str = DEFAULT_PRIORITY,
    ) -> Ticket:
        """Admit or raise Shed.  Order: drain, deadline, concurrency.

        ``model`` attributes the decision to the per-model
        kdlt_admission_* slice (the bounded ``model`` label) AND keys the
        limiter's per-model budget; callers pass it once routing has
        resolved a REGISTERED model name, which is what keeps the label's
        value set bounded by the model registry.  ``priority`` (a
        protocol.PRIORITY_CLASSES member, already normalized by
        parse_priority) orders queue grants and eviction: the lowest class
        sheds first.
        """
        mm = self._model_metrics(model)
        self._m["requests"].inc()
        if mm is not None:
            mm["requests"].inc()
        if self._draining:
            self._shed(Shed(
                "draining", 503, retry_after_s=DRAIN_RETRY_AFTER_S,
                detail=f"{self.tier} is draining for shutdown",
            ), priority=priority)
        if self.enabled and deadline is not None and deadline.expired:
            self._shed(Shed(
                "deadline_exhausted", 504,
                detail=(
                    f"deadline budget exhausted before execution "
                    f"({deadline.budget_s * 1e3:.0f}ms budget)"
                ),
            ), priority=priority)
        queue_wait = 0.0
        if self._limiter is not None:
            budget = deadline.remaining_s() if deadline is not None else None
            try:
                queue_wait = self._limiter.acquire(
                    budget, model=model, priority=priority
                )
            except Shed as e:
                self._shed(e, priority=priority)
            self._m["limit"].set(self._limiter.limit)
        self._m["queue_wait"].observe(queue_wait)
        if deadline is not None:
            self._m["deadline_remaining_ms"].observe(max(deadline.remaining_ms(), 0.0))
        self._m["admitted"].inc()
        if mm is not None:
            mm["admitted"].inc()
        cm = self._class_m.get(priority)
        if cm is not None:
            cm["admitted"].inc()
        with self._lock:
            self._inflight += 1
            self._m["inflight"].set(float(self._inflight))
        return Ticket(
            self, queue_wait, deadline if self.enabled else None, model=model
        )

    def _shed(self, e: Shed, priority: str | None = None) -> None:
        counter = self._m["shed"].get(e.reason)
        if counter is not None:
            counter.inc()
        if priority is not None:
            cm = self._class_m.get(priority)
            if cm is not None:
                cm["shed"].inc()
        raise e

    def count_shed(self, reason: str, priority: str | None = None) -> None:
        """Record a shed decided OUTSIDE admit() (e.g. the gateway's circuit
        breaker refusing the upstream call mid-request, or a brownout class
        shed ahead of admission)."""
        counter = self._m["shed"].get(reason)
        if counter is not None:
            counter.inc()
        if priority is not None:
            cm = self._class_m.get(priority)
            if cm is not None:
                cm["shed"].inc()

    def class_stats(self) -> dict:
        """Per-priority-class admitted/shed counts (the /debug/brownout and
        kdlt-client --stats surface)."""
        return {
            cls: {
                "admitted": m["admitted"].value,
                "shed": m["shed"].value,
            }
            for cls, m in self._class_m.items()
        }

    def count_coalesced(self, model: str | None = None) -> None:
        """Record a cache-coalesced singleflight follower: admitted-but-
        not-dispatched.  It IS served (through the leader's flight), so it
        counts as seen + admitted -- but it consumes no limiter slot and
        no in-flight ledger entry, because exactly one request (the
        leader) holds real gateway capacity for the whole flight.
        kdlt_cache_coalesced_total carries the distinction."""
        mm = self._model_metrics(model)
        self._m["requests"].inc()
        self._m["admitted"].inc()
        if mm is not None:
            mm["requests"].inc()
            mm["admitted"].inc()

    def _release(
        self,
        queue_wait_s: float,
        overloaded: bool,
        headroom: bool,
        model: str | None = None,
        held_s: float | None = None,
    ) -> None:
        if self._limiter is not None:
            self._limiter.release(
                queue_wait_s, overloaded=overloaded, headroom=headroom,
                model=model, held_s=held_s,
            )
            self._m["limit"].set(self._limiter.limit)
        with self._lock:
            self._inflight = max(0, self._inflight - 1)
            self._m["inflight"].set(float(self._inflight))
            self._idle.notify_all()

    # --- graceful drain -----------------------------------------------------

    def begin_drain(self) -> None:
        """Stop admitting (every new request sheds "draining", /readyz goes
        503 so the endpoint pool stops routing here); in-flight work keeps
        running to completion."""
        self._draining = True
        self._m["draining"].set(1.0)

    def wait_idle(self, timeout_s: float | None = None) -> bool:
        """Block until every admitted request has released (True) or the
        timeout passes (False)."""
        if timeout_s is None:
            timeout_s = drain_timeout_s()
        giveup = time.monotonic() + timeout_s
        with self._lock:
            while self._inflight > 0:
                remaining = giveup - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(remaining)
        return True


def drain_timeout_s() -> float:
    raw = os.environ.get(DRAIN_TIMEOUT_ENV, "")
    try:
        return float(raw) if raw.strip() else DEFAULT_DRAIN_TIMEOUT_S
    except ValueError:
        return DEFAULT_DRAIN_TIMEOUT_S


def install_sigterm_drain(controller: AdmissionController, stop, timeout_s=None):
    """SIGTERM -> graceful drain -> ``stop()``.

    The handler flips drain immediately (readiness fails, admission sheds)
    and hands the bounded wait-for-idle plus the final ``stop()`` (e.g.
    httpd shutdown) to a daemon thread -- signal handlers run between
    bytecodes of the serve_forever thread and must not block there.  Pairs
    with the k8s manifests' terminationGracePeriodSeconds/preStop settings:
    kubelet sends SIGTERM after preStop, and the drain budget
    ($KDLT_DRAIN_TIMEOUT_S, default 25 s) fits inside the grace period.
    """

    def _finish():
        controller.wait_idle(timeout_s)
        stop()

    def _handler(signum, frame):  # noqa: ARG001 - signal signature
        controller.begin_drain()
        threading.Thread(target=_finish, name="kdlt-drain", daemon=True).start()

    signal.signal(signal.SIGTERM, _handler)
