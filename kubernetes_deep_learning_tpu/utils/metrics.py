"""Minimal thread-safe metrics: counters + histograms + Prometheus text.

The reference has no observability at all (SURVEY.md section 5: no /metrics,
no structured logs); both tiers here expose a /metrics endpoint rendered from
one of these registries.
"""

from __future__ import annotations

import bisect
import os
import threading
import time

# Default latency buckets in seconds (sub-ms to 20 s, the reference's
# implicit deadline ceiling, reference model_server.py:55).
DEFAULT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.015, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0,
)

# Pipeline-stage buckets reach below the request buckets: the dispatch and
# readback stages of a well-overlapped pipeline are tens of microseconds to
# single-digit milliseconds, which DEFAULT_BUCKETS would collapse into its
# first bin.
PIPELINE_STAGE_BUCKETS = (
    0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
    0.025, 0.05, 0.1, 0.25, 1.0, 5.0,
)

# The in-flight dispatch pipeline's stages (runtime.engine.InFlightDispatcher),
# in hot-path order.  Stage semantics under JAX async dispatch:
#
# - enqueue_wait: submit() blocked waiting for an in-flight slot -- the
#   backpressure stage; nonzero means the device (not the host) is the
#   bottleneck, which is the healthy steady state.
# - dispatch: host batch assembly + uint8 H2D transfer ENQUEUE (the
#   predict_async call).  JAX returns as soon as the transfer+execution are
#   queued, so this is pure host cost -- the part pipelining hides.
# - execute: dispatch-return -> readback-start on the completion thread.
#   Under overlap this is the time the batch waited in flight while the
#   device worked (on it or its predecessors).
# - readback: the blocking materialization (device sync + D2H copy).
PIPELINE_STAGES = (
    ("enqueue_wait", "submit blocked on the in-flight depth limit (backpressure)"),
    ("dispatch", "host batch assembly + H2D transfer enqueue (predict_async)"),
    ("execute", "in-flight wait: dispatch return to readback start (overlapped device execution)"),
    ("readback", "blocking device sync + D2H materialization"),
)


def pipeline_stage_histograms(
    registry: "Registry", engine: str | None = None, model: str | None = None
) -> dict:
    """The per-stage histograms every in-flight dispatcher emits.

    Centralized so the dispatcher, the bench A/B mode, and any future
    pipelined caller emit the SAME series names (kdlt_pipeline_<stage>_seconds)
    and dashboards/alerts need one set of queries.  ``engine`` labels the
    series (engine="crosshost" for the cross-host dispatch pipeline) so
    one dashboard separates per-chip dispatch from fleet rounds; None
    keeps the unlabeled single-host series.  ``model`` adds the bounded
    serving-model label (multi-model scheduling: the SHARED dispatcher
    attributes each batch's stage time to the model that dispatched it);
    callers must memoize per model -- re-minting the same (name, labels)
    pair is a registry error by design.
    """
    if engine:
        registry = registry.with_labels(engine=engine)

    def mint(reg):
        return {
            stage: reg.histogram(
                f"kdlt_pipeline_{stage}_seconds", help,
                buckets=PIPELINE_STAGE_BUCKETS,
            )
            for stage, help in PIPELINE_STAGES
        }

    if model is None:
        return mint(registry)
    return _memo_on_child(
        model_registry(registry, model), "_kdlt_pipeline_stages", mint
    )


# Where a dispatcher's wall time went, seen from the host
# (runtime.engine.InFlightDispatcher): every instant belongs to exactly one
# of these, so the three counters sum to the dispatcher's age.  One NAME per
# cause, not one name with a label: consumers that sum a series over its
# label sets would otherwise read back the age.
PIPELINE_IDLE_CAUSES = (
    ("inflight", "at least one batch dispatched and not yet read back"),
    ("idle_dispatch", "nothing in flight while a submit is inside "
     "predict_async: the device waits for the host to stage, transfer and "
     "launch a batch"),
    ("idle_no_batch", "nothing in flight and no submit in progress: no "
     "batch was offered (handlers, scheduler or the client)"),
)


def pipeline_idle_counters(registry: "Registry", engine: str | None = None) -> dict:
    """The dispatcher's device-idle-by-cause counters
    (kdlt_pipeline_<cause>_seconds_total), labelled like its stage
    histograms.  Utilisation as the host sees it is 1 - rate(the two idle
    counters); the device finishes a batch a little before its readback
    returns, so this reads slightly LESS idle than a device trace does."""
    if engine:
        registry = registry.with_labels(engine=engine)
    return {
        cause: registry.counter(
            f"kdlt_pipeline_{cause}_seconds_total",
            f"dispatcher wall seconds with {help}",
        )
        for cause, help in PIPELINE_IDLE_CAUSES
    }


# How the model server's tensor wire got its pixels out of a msgpack body
# (serving.protocol.decode_msgpack_tensor).  The ``path`` label's value set
# is exactly this tuple.
SERVER_UNPACK_PATHS = (
    ("view", "the pixels handed on as a view of the request body "
     "(plain envelope, one-byte elements): nothing copied"),
    ("copy", "the body unpacked by msgpack.unpackb (wider dtypes, extra "
     "keys, anything unusual): the payload copied under the interpreter's "
     "lock"),
)


def server_unpack_counters(registry: "Registry") -> dict:
    """kdlt_server_unpack_total{path}: one count per msgpack tensor request
    decoded in the server.unpack span."""
    return {
        path: registry.with_labels(path=path).counter(
            "kdlt_server_unpack_total", f"msgpack tensor requests with {help}"
        )
        for path, help in SERVER_UNPACK_PATHS
    }


# How a uint8 batch reached its bucket's program (runtime.engine.stage): in
# the engine's wire form as a view of the caller's memory, or after a host
# copy.  The ``path`` label's value set is exactly this tuple.
ENGINE_INPUT_PATHS = (
    ("view", "a whole bucket of contiguous rows: the wire form is a view "
     "of the caller's array, nothing copied on the host"),
    ("copy", "a host copy first: rows padded up to the bucket, or an "
     "array that was not C-contiguous"),
)


def engine_input_counters(registry: "Registry") -> dict:
    """kdlt_engine_input_total{path}: one count per uint8 batch handed to
    a compiled program by predict_async / predict_ingest_async."""
    return {
        path: registry.with_labels(path=path).counter(
            "kdlt_engine_input_total", f"uint8 batches dispatched with {help}"
        )
        for path, help in ENGINE_INPUT_PATHS
    }


# --- the bounded ``model`` label (multi-model serving) ----------------------
#
# Every per-model series on a shared /metrics page carries a ``model`` label
# minted HERE and nowhere else (tools/check_metrics.py lints for stray
# with_labels(model=...) calls).  Central minting is what keeps the label's
# cardinality bounded: values come from the model registry's directory scan,
# and even a hostile/buggy caller cannot mint more than MODEL_LABEL_CAP
# distinct values per root registry -- the overflow bucket absorbs the rest
# instead of growing the exposition without bound.

MODEL_LABEL_CAP = 32
MODEL_LABEL_OVERFLOW = "__other__"

_model_children_lock = threading.Lock()


def model_registry(registry: "Registry", model: str) -> "Registry":
    """The child registry carrying the bounded ``model`` label.

    Memoized per root registry (the same model always lands on the same
    child, so helpers minting through it dedupe naturally); past
    MODEL_LABEL_CAP distinct models every further name collapses into the
    MODEL_LABEL_OVERFLOW bucket.
    """
    model = str(model)
    with _model_children_lock:
        children = getattr(registry, "_kdlt_model_children", None)
        if children is None:
            children = {}
            registry._kdlt_model_children = children
        if model not in children:
            if len(children) >= MODEL_LABEL_CAP:
                model = MODEL_LABEL_OVERFLOW
                if model in children:
                    return children[model]
            children[model] = registry.with_labels(model=model)
        return children[model]


def model_version_registry(
    registry: "Registry", model: str, version: int
) -> "Registry":
    """A served model VERSION's labeled child registry (one per ServedModel;
    dropped via registry.remove on unload, so version is not
    cardinality-bounded the way ``model`` is -- at most one version per
    model is live at a time)."""
    return registry.with_labels(model=model, version=str(version))


def _memo_on_child(child: "Registry", attr: str, factory):
    """Mint-once-per-child memoization for the model-labeled helpers.

    Two distinct raw model names can land on the SAME child registry (the
    overflow bucket), so memoizing by raw name in the caller is not enough
    -- the second name would re-mint the same (name, labels) series and
    raise.  Stamping the minted dict on the child itself makes every
    helper idempotent per label set.
    """
    with _model_children_lock:
        got = getattr(child, attr, None)
        if got is None:
            got = factory(child)
            setattr(child, attr, got)
        return got


def model_request_counter(registry: "Registry", model: str) -> "Counter":
    """Per-model request count on a tier's /metrics page (bounded label)."""
    child = model_registry(registry, model)
    return _memo_on_child(
        child, "_kdlt_model_requests", lambda c: c.counter(
            "kdlt_model_requests_total", "predict requests by served model"
        ),
    )


def admission_model_metrics(registry: "Registry", model: str) -> dict:
    """Per-model admission accounting (requests seen / admitted), the
    model-granular slice of the kdlt_admission_* contract.  The registry
    passed in is the controller's tier-labeled registry, so the series is
    distinguished by (tier, model)."""
    child = model_registry(registry, model)
    return _memo_on_child(
        child, "_kdlt_admission_model", lambda c: {
            "requests": c.counter(
                "kdlt_admission_requests_total",
                "requests seen by admission control",
            ),
            "admitted": c.counter(
                "kdlt_admission_admitted_total",
                "requests admitted to execution",
            ),
        },
    )


def scheduler_lane_metrics(registry: "Registry", model: str) -> dict:
    """One scheduling lane's series (runtime.scheduler.UnifiedScheduler).

    kdlt_batcher_batch_size keeps the historical batcher series name (the
    invariant dashboard contract) under the model label; the kdlt_sched_*
    series are the scheduler's own: queue depth, dispatch count, the
    weight-floor starvation guard, and estimated device-time consumption
    (the share the weighted policy arbitrates).
    """
    child = model_registry(registry, model)
    return _memo_on_child(child, "_kdlt_sched_lane", _mint_lane_metrics)


def _mint_lane_metrics(child: "Registry") -> dict:
    return {
        "batch_size": child.histogram(
            "kdlt_batcher_batch_size",
            "dispatched batch sizes",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
        ),
        "queue_full": child.counter(
            "kdlt_batcher_rejected_total",
            "requests rejected because queue was full",
        ),
        "queue_depth": child.gauge(
            "kdlt_sched_queue_depth", "images queued awaiting dispatch"
        ),
        "dispatch": child.counter(
            "kdlt_sched_dispatch_total", "batches dispatched for this model"
        ),
        "floor_boosts": child.counter(
            "kdlt_sched_floor_boosts_total",
            "dispatches granted by the weight-floor starvation guard ahead "
            "of the deadline order",
        ),
        "device_seconds": child.counter(
            "kdlt_sched_device_seconds_total",
            "observed dispatch->completion device time consumed by this "
            "model (the share the weighted policy arbitrates)",
        ),
        "weight": child.gauge(
            "kdlt_sched_weight", "configured scheduling weight"
        ),
        "queue_age": child.histogram(
            "kdlt_sched_queue_age_seconds",
            "age of queued units when their dispatch plan was taken "
            "(enqueue -> scheduled): the queuing-delay component of "
            "cross-model arbitration",
            buckets=PIPELINE_STAGE_BUCKETS,
        ),
    }


# --- SLO engine series (utils.slo) -----------------------------------------
#
# kdlt_slo_* is the second observability layer on top of the admission/
# pipeline substrate: per-model sliding-window goodput and multi-window burn
# rates against $KDLT_SLO_TARGET.  Minted HERE and nowhere else
# (tools/check_metrics.py rejects kdlt_slo_ mints outside this module): the
# ``model`` label stays bounded through model_registry, and the ``window``
# label's value set is exactly utils.slo.WINDOWS.

def slo_tier_metrics(registry: "Registry") -> dict:
    """The per-tier SLO statics: the configured objective itself."""
    return {
        "target": registry.gauge(
            "kdlt_slo_target",
            "configured SLO target (KDLT_SLO_TARGET): the fraction of "
            "requests that must complete in-deadline",
        ),
    }


def slo_model_window_metrics(
    registry: "Registry", model: str, window: str
) -> dict:
    """One (model, window) cell of the SLO engine's gauge matrix.

    Memoized per (model child, window) like the other model-labeled
    helpers; ``window`` values come from utils.slo.WINDOWS (e.g. "5m",
    "1h"), so both labels are bounded by construction.
    """
    child = model_registry(registry, model)

    def mint(c: "Registry") -> dict:
        w = c.with_labels(window=window)
        return {
            "goodput_ratio": w.gauge(
                "kdlt_slo_goodput_ratio",
                "fraction of SLO-eligible requests completed in-deadline "
                "over the window",
            ),
            "burn_rate": w.gauge(
                "kdlt_slo_burn_rate",
                "error-budget burn rate over the window (bad fraction / "
                "(1 - target)); 1.0 = burning exactly at the sustainable rate",
            ),
            "shed_ratio": w.gauge(
                "kdlt_slo_shed_ratio",
                "fraction of SLO-eligible requests shed (503/504) over the "
                "window",
            ),
            "error_ratio": w.gauge(
                "kdlt_slo_error_ratio",
                "fraction of SLO-eligible requests failed server-side over "
                "the window",
            ),
            "requests": w.gauge(
                "kdlt_slo_window_requests",
                "SLO-eligible requests observed in the window",
            ),
        }

    return _memo_on_child(child, f"_kdlt_slo_{window}", mint)


# Tail-based trace retention (utils.trace.Tracer): every finished trace is
# classified into exactly one of these, and eviction prefers dropping
# ``routine`` traces first -- the label set is this tuple, nothing else.
TRACE_RETENTION_CLASSES = (
    ("incident", "the trace is pinned by a flight-recorder incident bundle"),
    ("error", "the request failed server-side (5xx/disconnect)"),
    ("shed", "the request was shed (503/504)"),
    ("deadline", "the request completed but violated its deadline budget"),
    ("slow", "the request landed in the tier's slowest percentile"),
    ("routine", "an unremarkable request"),
)


def trace_retention_metrics(registry: "Registry") -> dict:
    """The tracer's retention accounting: traces classified (retained) and
    traces evicted from the ring (dropped), by retention class.  A rising
    dropped{class!="routine"} means interesting traces are being lost --
    grow the ring or scrape /debug/trace faster."""
    return {
        "retained": {
            cls: registry.with_labels(**{"class": cls}).counter(
                "kdlt_trace_retained_total",
                f"traces classified for retention: {help}",
            )
            for cls, help in TRACE_RETENTION_CLASSES
        },
        "dropped": {
            cls: registry.with_labels(**{"class": cls}).counter(
                "kdlt_trace_dropped_total",
                f"traces evicted from the ring buffer: {help}",
            )
            for cls, help in TRACE_RETENTION_CLASSES
        },
    }


def crosshost_metrics(registry: "Registry") -> dict:
    """The cross-host round series (kdlt_crosshost_*), one set per serving
    engine/version (parallel.crosshost.CrossHostForward.attach_metrics).

    Centralized like pipeline_stage_histograms so the leader and
    dashboards key one set of names.  Stage semantics
    mirror the round protocol: ``broadcast`` is the leader's DCN
    control+payload broadcast (host-blocking, the part pipelining
    overlaps), ``collective`` is dispatch->device-completion of the SPMD
    program (execution incl. the on-device logits all-gather), ``gather``
    is the leader-local D2H materialization.
    """
    return {
        "depth": registry.gauge(
            "kdlt_crosshost_pipeline_depth",
            "configured cross-host in-flight round budget (KDLT_XH_PIPELINE_DEPTH)",
        ),
        "inflight": registry.gauge(
            "kdlt_crosshost_inflight_rounds",
            "rounds broadcast+dispatched but not yet materialized",
        ),
        "rounds": registry.counter(
            "kdlt_crosshost_rounds_total", "cross-host predict rounds dispatched"
        ),
        "reloads": registry.counter(
            "kdlt_crosshost_reload_total", "fleet-wide RELOAD rounds broadcast"
        ),
        "broadcast": registry.histogram(
            "kdlt_crosshost_broadcast_seconds",
            "leader DCN control+payload broadcast per round",
            buckets=PIPELINE_STAGE_BUCKETS,
        ),
        "collective": registry.histogram(
            "kdlt_crosshost_collective_seconds",
            "round dispatch -> device completion (SPMD execution incl. "
            "on-device logits all-gather; overlapped under pipelining)",
        ),
        "gather": registry.histogram(
            "kdlt_crosshost_gather_seconds",
            "leader-local D2H materialization of a round's replicated logits",
            buckets=PIPELINE_STAGE_BUCKETS,
        ),
    }


# The mesh axes the per-axis device-count gauge enumerates -- a BOUNDED
# label set by construction (parallel.mesh's axis convention).
MESH_AXES = ("data", "model")


def mesh_metrics(registry: "Registry") -> dict:
    """The mesh-serving series (kdlt_mesh_*), one set per engine/version.

    Static layout facts set once at engine construction --
    ``model_parallel`` (the model-axis degree), per-axis device counts
    (labelled ``axis``, bounded to MESH_AXES), and per-device resident
    param bytes (the "fits where it didn't" number, shrinking ~1/mp as the
    partition rules shard the wide kernels) -- plus cumulative
    dispatch->sync device seconds, the denominator for estimating the
    collective overhead a model axis adds over an mp=1 baseline.
    """
    return {
        "model_parallel": registry.gauge(
            "kdlt_mesh_model_parallel",
            "model-axis size of the serving mesh (1 = pure data-parallel)",
        ),
        "axis_devices": {
            axis: registry.with_labels(axis=axis).gauge(
                "kdlt_mesh_axis_devices", "devices along one mesh axis"
            )
            for axis in MESH_AXES
        },
        "param_bytes": registry.gauge(
            "kdlt_mesh_param_bytes_per_device",
            "resident parameter bytes per device under the partition rules",
        ),
        "collective": registry.counter(
            "kdlt_mesh_collective_seconds_total",
            "cumulative dispatch->sync device seconds on the mesh (includes "
            "the model-axis collectives XLA inserted)",
        ),
    }


# Admission control (serving.admission): every way a tier can refuse work,
# as the ``shed_reason`` label on kdlt_admission_shed_total.  Shared between
# both tiers so one dashboard query covers the whole path.
ADMISSION_SHED_REASONS = (
    ("deadline_exhausted", "the deadline budget was spent before execution (504)"),
    ("queue_timeout", "no concurrency slot freed within the bounded queue wait"),
    ("queue_full", "the admission queue's waiter cap was reached"),
    ("breaker_open", "the model-tier circuit breaker refused the call"),
    ("draining", "the tier is draining for shutdown"),
    ("budget_exhausted", "the model's per-tenant admission budget was spent "
                         "and no borrowed slot could be reclaimed"),
    ("preempted", "a queued waiter was evicted by a higher-priority or "
                  "under-budget arrival (borrowed slots shed first)"),
    ("brownout", "rejected by the brownout controller's staged class "
                 "shedding (429: the caller's class is out of budget, not "
                 "a server failure)"),
)

# Priority classes (serving.protocol.PRIORITY_CLASSES): the bounded value
# set of the ``class`` label on the per-class admission series.  Spelled
# here too so the mint below cannot drift cardinality with a caller's
# typo'd header -- admission normalizes through parse_priority first.
ADMISSION_PRIORITY_CLASSES = ("interactive", "batch", "best-effort")


def admission_class_metrics(registry: "Registry") -> dict:
    """Per-priority-class admission accounting (admitted / shed), keyed by
    the bounded ``class`` label.  One dict per tier registry: which class
    is paying for an overload is THE question during a brownout, and
    per-class goodput is what the ISSUE's class-shedding gates read."""
    out: dict = {}
    for cls in ADMISSION_PRIORITY_CLASSES:
        child = registry.with_labels(**{"class": cls})
        out[cls] = {
            "admitted": child.counter(
                "kdlt_admission_class_admitted_total",
                "requests admitted to execution, by priority class",
            ),
            "shed": child.counter(
                "kdlt_admission_class_shed_total",
                "requests shed, by priority class (lowest class sheds first)",
            ),
        }
    return out


# Brownout controller (serving.admission.brownout): staged graceful
# degradation driven by the SLO engine's burn rate.  kdlt_brownout_* is
# minted HERE and nowhere else (tools/check_metrics.py confines the prefix
# and the ``stage``/``direction`` labels to this module): the stage set is
# exactly 1..4 and direction is up|down, both bounded by construction.
BROWNOUT_STAGES = (1, 2, 3, 4)


def brownout_metrics(registry: "Registry") -> dict:
    """The brownout controller's series: the current stage (0 = healthy;
    alert on ``kdlt_brownout_stage > 0``) and every stage-boundary
    transition, labeled by the stage being entered (up) or left (down)."""
    return {
        "stage": registry.gauge(
            "kdlt_brownout_stage",
            "current brownout degradation stage (0 = off, 1 = hedging "
            "disabled, 2 = stale-while-revalidate serving, 3 = shedding "
            "best-effort, 4 = shedding batch)",
        ),
        "transitions": {
            (stage, direction): registry.with_labels(
                stage=str(stage), direction=direction
            ).counter(
                "kdlt_brownout_transitions_total",
                "brownout stage transitions: direction=up counts entering "
                "this stage from below, direction=down counts leaving it "
                "downward (a flapping controller shows as paired up/down "
                "increments)",
            )
            for stage in BROWNOUT_STAGES
            for direction in ("up", "down")
        },
    }

# Incident flight recorder (utils.flightrecorder): trigger-driven diagnostic
# bundle capture.  kdlt_incident_* is minted HERE and nowhere else
# (tools/check_metrics.py confines the prefix and the ``trigger`` label to
# this module); the trigger vocabulary is exactly this tuple -- the trigger
# parser rejects unknown names, so the label is bounded by construction.
INCIDENT_TRIGGERS = (
    "burn-crossing", "brownout", "dispatch-stall", "replica-unhealthy",
)


def incident_metrics(registry: "Registry") -> dict:
    """The flight recorder's series: bundles captured / suppressed (dedup or
    hysteresis swallowed a repeat fire) / dropped (dir caps evicted an old
    bundle), per trigger, plus how many bundles are currently on disk.
    Alert on rate(kdlt_incident_captures_total[5m]) > 0 (GUIDE 10m).

    Idempotent per registry (the _memo_on_child pattern): a tier that
    builds its recorder twice against one registry must not re-mint."""
    return _memo_on_child(registry, "_kdlt_incident", _mint_incident)


def _mint_incident(registry: "Registry") -> dict:
    return {
        "captures": {
            trig: registry.with_labels(trigger=trig).counter(
                "kdlt_incident_captures_total",
                "incident bundles captured, by firing trigger",
            )
            for trig in INCIDENT_TRIGGERS
        },
        "suppressed": {
            trig: registry.with_labels(trigger=trig).counter(
                "kdlt_incident_suppressed_total",
                "trigger fires suppressed inside the dedup window (a "
                "flapping signal yields ONE bundle plus this counter)",
            )
            for trig in INCIDENT_TRIGGERS
        },
        "dropped": {
            trig: registry.with_labels(trigger=trig).counter(
                "kdlt_incident_dropped_total",
                "incident bundles evicted oldest-first by the "
                "KDLT_INCIDENT_MAX_BUNDLES / KDLT_INCIDENT_MAX_MB caps, "
                "by the evicted bundle's trigger",
            )
            for trig in INCIDENT_TRIGGERS
        },
        "open": registry.gauge(
            "kdlt_incident_open",
            "incident bundles currently retained on disk under "
            "KDLT_INCIDENT_DIR",
        ),
    }


# Deadline budgets are ms-scale; the request-latency buckets (seconds) would
# collapse every remaining-budget observation into two bins.
DEADLINE_MS_BUCKETS = (
    1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000,
    10_000, 20_000, 60_000, 120_000,
)


def admission_metrics(registry: "Registry") -> dict:
    """The per-tier admission series (kdlt_admission_*).

    Centralized like pipeline_stage_histograms: the gateway controller, the
    model-tier controller, and the overload bench all emit the SAME names,
    distinguished only by the registry's tier label.
    """
    return {
        "requests": registry.counter(
            "kdlt_admission_requests_total", "requests seen by admission control"
        ),
        "admitted": registry.counter(
            "kdlt_admission_admitted_total", "requests admitted to execution"
        ),
        "queue_wait": registry.histogram(
            "kdlt_admission_queue_wait_seconds",
            "wait for a concurrency slot before execution",
            buckets=PIPELINE_STAGE_BUCKETS,
        ),
        "deadline_remaining_ms": registry.histogram(
            "kdlt_admission_deadline_remaining_ms",
            "remaining deadline budget at admission (propagation evidence: "
            "each tier down the path observes strictly less)",
            buckets=DEADLINE_MS_BUCKETS,
        ),
        "limit": registry.gauge(
            "kdlt_admission_concurrency_limit", "current AIMD concurrency limit"
        ),
        "inflight": registry.gauge(
            "kdlt_admission_inflight", "admitted requests currently executing"
        ),
        "draining": registry.gauge(
            "kdlt_admission_draining", "1 while the tier refuses new work for shutdown"
        ),
        "shed": {
            reason: registry.with_labels(shed_reason=reason).counter(
                "kdlt_admission_shed_total", help
            )
            for reason, help in ADMISSION_SHED_REASONS
        },
    }


# Serving-path fault tolerance (serving.upstream, serving.faults, the
# dispatcher watchdog).  Centralized like the helpers above so the gateway
# pool and the model tier emit the SAME series names.


def upstream_pool_metrics(registry: "Registry") -> dict:
    """The gateway-tier replica-pool series (failover + hedging)."""
    return {
        "failover": registry.counter(
            "kdlt_upstream_failover_total",
            "upstream attempts redirected to another replica after a failure",
        ),
        "hedge_fired": registry.counter(
            "kdlt_hedge_fired_total",
            "hedged second attempts fired after the hedge delay",
        ),
        "hedge_won": registry.counter(
            "kdlt_hedge_won_total",
            "hedged attempts whose response was the one used",
        ),
    }


# Gateway response cache + singleflight coalescing (serving.cache).  Every
# way an entry can leave the cache, as the bounded ``reason`` label on
# kdlt_cache_evictions_total; minted HERE and nowhere else
# (tools/check_metrics.py confines the kdlt_cache_ prefix and the reason
# label to this module).
CACHE_EVICTION_REASONS = (
    ("lru", "evicted to fit the KDLT_CACHE_MAX_MB byte budget"),
    ("ttl", "expired past KDLT_CACHE_TTL_S"),
    ("reload", "dropped because the model's artifact hash changed (hot "
               "reload with different bytes)"),
)


def cache_metrics(registry: "Registry") -> dict:
    """The gateway-tier response-cache series (kdlt_cache_*).

    Centralized like the helpers above so the cache and /debug/cache
    key one set of names.  ``hits`` never touched
    admission or the upstream; ``coalesced`` rode another request's
    flight (admitted-but-not-dispatched); ``misses`` paid the full path.
    """
    return {
        "hits": registry.counter(
            "kdlt_cache_hits_total",
            "requests served from the response cache (no admission slot, "
            "no upstream call, no device work)",
        ),
        "misses": registry.counter(
            "kdlt_cache_misses_total",
            "cacheable requests that missed and led their own upstream flight",
        ),
        "coalesced": registry.counter(
            "kdlt_cache_coalesced_total",
            "requests coalesced onto another identical request's in-flight "
            "upstream call (singleflight followers)",
        ),
        "stale_hits": registry.counter(
            "kdlt_cache_stale_hits_total",
            "requests served a TTL-expired entry under brownout "
            "stale-while-revalidate (within KDLT_CACHE_SWR_S past expiry; "
            "marked X-Kdlt-Cache: stale)",
        ),
        "neg_hits": registry.counter(
            "kdlt_cache_negative_hits_total",
            "requests answered from a negative-cache entry (a recent 404/"
            "400 for the same content key, held for KDLT_CACHE_NEG_TTL_S)",
        ),
        "bytes": registry.counter(
            "kdlt_cache_bytes_total",
            "response bytes inserted into the cache",
        ),
        "resident": registry.gauge(
            "kdlt_cache_resident_bytes",
            "response bytes currently held by the cache",
        ),
        "entries": registry.gauge(
            "kdlt_cache_entries", "entries currently held by the cache"
        ),
        "hit_ratio": registry.gauge(
            "kdlt_cache_hit_ratio",
            "lifetime hits / (hits + misses) of the response cache",
        ),
        "evictions": {
            reason: registry.with_labels(reason=reason).counter(
                "kdlt_cache_evictions_total", help
            )
            for reason, help in CACHE_EVICTION_REASONS
        },
    }


# Decoded-uint8 cache tier (serving.cache.DecodedCache): content-addressed
# decode results shared across models.  kdlt_cache_decoded_* rides the
# kdlt_cache_ central prefix, so it is minted HERE and nowhere else.
def cache_decoded_metrics(registry: "Registry") -> dict:
    """The decoded-uint8 cache tier's series (kdlt_cache_decoded_*).

    Keys are (payload content hash, resolved preprocess params), so a hit
    means a previously decoded image's pixels were reused -- across
    requests AND across models sharing an input contract -- skipping the
    JPEG/PNG decode + resize entirely.  Entries are content-addressed and
    therefore immutable: there is no TTL and no artifact invalidation,
    only the LRU byte budget (KDLT_CACHE_DECODED_MB)."""
    return {
        "hits": registry.counter(
            "kdlt_cache_decoded_hits_total",
            "decode-stage lookups served a previously decoded uint8 tensor "
            "(no JPEG/PNG decode, no resize)",
        ),
        "misses": registry.counter(
            "kdlt_cache_decoded_misses_total",
            "decode-stage lookups that paid the full decode+resize",
        ),
        "resident": registry.gauge(
            "kdlt_cache_decoded_resident_bytes",
            "decoded uint8 tensor bytes currently held by the decoded tier",
        ),
        "entries": registry.gauge(
            "kdlt_cache_decoded_entries",
            "entries currently held by the decoded tier",
        ),
        "evictions": registry.counter(
            "kdlt_cache_decoded_evictions_total",
            "decoded entries evicted to fit the KDLT_CACHE_DECODED_MB "
            "byte budget (content-addressed entries never expire; LRU is "
            "the only way out)",
        ),
    }


# Raw-bytes ingest wire (serving/protocol + GUIDE 10q).  The ``reason``
# label's value set is exactly this tuple (bounded by construction); the
# kdlt_ingest_ prefix is confined to this module by kdlt-lint.
INGEST_FALLBACK_REASONS = (
    ("format", "payload failed the JPEG/PNG magic-byte sniff (exotic "
               "format decodes at the gateway, rides the tensor wire)"),
    ("negotiation", "the model tier did not advertise the bytes capability "
                    "on its spec response (old server or KDLT_INGEST=0)"),
    ("rejected", "a bytes-wire POST came back 4xx and the request was "
                 "re-sent decoded on the legacy tensor wire"),
)


def ingest_gateway_metrics(registry: "Registry") -> dict:
    """The gateway tier's raw-bytes ingest series (kdlt_ingest_*): how
    much traffic rides the bytes wire, why the rest fell back, and the
    wire bytes actually shipped (the payload-diet receipt)."""
    return {
        "bytes_requests": registry.counter(
            "kdlt_ingest_bytes_requests_total",
            "upstream predict calls sent on the raw-bytes wire",
        ),
        "wire_bytes": registry.counter(
            "kdlt_ingest_wire_bytes_total",
            "request-body bytes shipped on the raw-bytes wire",
        ),
        "fallbacks": {
            reason: registry.with_labels(reason=reason).counter(
                "kdlt_ingest_fallbacks_total", help
            )
            for reason, help in INGEST_FALLBACK_REASONS
        },
    }


def ingest_server_metrics(registry: "Registry") -> dict:
    """The model tier's decode-stage series (kdlt_ingest_*): images
    decoded at this tier and the per-batch decode latency (the stage a
    trace waterfall shows as server.ingest_decode)."""
    return {
        "decoded_images": registry.counter(
            "kdlt_ingest_decoded_images_total",
            "images decoded+resized by the model tier's decode stage",
        ),
        "decode_seconds": registry.histogram(
            "kdlt_ingest_decode_seconds",
            "wall seconds per bytes-wire batch in the thread-pooled "
            "decode stage",
            buckets=PIPELINE_STAGE_BUCKETS,
        ),
    }


# Quantization serving state (ops.quantize + runtime.engine).  The scheme
# label's value set is exactly this tuple (bounded by construction); minted
# HERE and nowhere else -- tools/check_metrics.py confines the kdlt_quant_
# prefix and the ``scheme`` label to this module.
QUANT_SCHEMES = (
    ("float32", "unquantized float serving"),
    ("int8-weight-only", "int8 weights dequantized inline; float activations"),
    ("int8-w8a8", "int8 weights AND calibrated int8 activations (MXU 2x path)"),
)


def quant_metrics(registry: "Registry") -> dict:
    """One engine's quantization accounting: which scheme is ACTIVE (the
    gauge is 1 for exactly one scheme -- post-tolerance-gate, post-
    $KDLT_QUANT_SCHEME override, so a silently-downgraded pod is
    alertable) and how many times the warmup tolerance gate refused
    int8 activations (kdlt_quant_gate_failures_total)."""
    return {
        "scheme": {
            scheme: registry.with_labels(scheme=scheme).gauge(
                "kdlt_quant_scheme",
                f"1 while this scheme is the one actually serving: {help}",
            )
            for scheme, help in QUANT_SCHEMES
        },
        "gate_failures": registry.counter(
            "kdlt_quant_gate_failures_total",
            "warmup golden-logits tolerance gate failures: a calibrated "
            "int8-w8a8 artifact drifted past KDLT_QUANT_TOL (or top-1 "
            "agreement) and was downgraded to weight-only serving",
        ),
    }


def pool_membership_metrics(registry: "Registry") -> dict:
    """Pool-level dynamic-membership series (kdlt_pool_*).

    Minted HERE and nowhere else (tools/check_metrics.py confines the
    kdlt_pool_ prefix to this module).  ``members`` counts replicas in
    rotation OR quarantine (everything the resolver currently believes
    in); joins/leaves count membership transitions, which is what any
    flap alert keys on.
    """
    return {
        "members": registry.gauge(
            "kdlt_pool_members",
            "upstream replicas currently known to the pool (in rotation, "
            "quarantined, or draining)",
        ),
        "joins": registry.counter(
            "kdlt_pool_joins_total",
            "replicas added to the pool by dynamic membership (resolver "
            "or set_membership)",
        ),
        "leaves": registry.counter(
            "kdlt_pool_leaves_total",
            "replicas removed from the pool by dynamic membership",
        ),
    }


def pool_replica_metrics(registry: "Registry", host: str) -> dict:
    """One replica's pool series, minted under a single labeled child so
    dynamic membership can retire ALL of a departed replica's series
    atomically (``registry.remove(child)``) without leaving stale samples
    on /metrics.  ``child`` is that handle; callers never mint through it
    directly."""
    child = registry.with_labels(replica=host)
    return {
        "child": child,
        "healthy": child.gauge(
            "kdlt_upstream_replica_healthy",
            "1 while the upstream replica is considered healthy",
        ),
        "picks": child.counter(
            "kdlt_pool_pick_total",
            "times power-of-two-choices selection routed a primary "
            "attempt to this replica",
        ),
        "ewma_ms": child.gauge(
            "kdlt_pool_replica_ewma_ms",
            "EWMA of this replica's observed request latency (the "
            "power-of-two-choices ranking signal)",
        ),
    }


def engine_warm_source_metrics(registry: "Registry") -> dict:
    """Per-engine warmup provenance: how many buckets of the ladder came
    up as persistent-compile-cache hits vs live XLA compiles.  The
    ``source`` label's value set is exactly these two (bounded by
    construction); a scaled-up pod whose AOT-warmed image is working
    reports ``compile`` == 0, which is the zero-cold-start proof the
    churn bench and the GUIDE §10k recipe key on."""
    return {
        source: registry.with_labels(source=source).counter(
            "kdlt_engine_warm_source", help
        )
        for source, help in (
            ("cache", "warmup buckets satisfied from the persistent "
                      "compile cache (fast path)"),
            ("compile", "warmup buckets that paid a live XLA compile"),
        )
    }


def compile_event_counters(registry: "Registry") -> dict:
    """Process-wide XLA compile accounting, fed from jax.monitoring by
    utils.compilecache.CompileWatch.  ``requests`` counts every program
    that reached the backend (compiled live OR loaded from the persistent
    cache): after /readyz it must stay flat, since every served shape was
    warmed.  ``cache_hits`` / ``cache_writes`` split the persistent
    cache's part of it: a restart against a filled cache is all hits and
    no writes."""
    return {
        "requests": registry.counter(
            "kdlt_xla_compile_requests_total",
            "programs that reached the XLA backend (live compile or "
            "persistent-cache load)",
        ),
        "cache_hits": registry.counter(
            "kdlt_xla_compile_cache_hits_total",
            "compile requests served from the persistent compile cache",
        ),
        "cache_writes": registry.counter(
            "kdlt_xla_compile_cache_writes_total",
            "live compiles written to the persistent compile cache",
        ),
    }


def dispatch_stall_counter(registry: "Registry") -> "Counter":
    """In-flight dispatch handles the watchdog declared stuck and failed."""
    return registry.counter(
        "kdlt_dispatch_stall_total",
        "in-flight dispatches failed by the engine watchdog as stuck",
    )


# --- generative decode lane (runtime.decode / serving.generate) -------------
#
# kdlt_decode_* is the generative lane's per-token observability surface:
# TTFT and TPOT distributions (the per-token SLO signals the SloEngine and
# the brownout ladder consume), token/generation/step throughput, and the
# continuous-batching occupancy gauges.  Minted HERE and nowhere else
# (kdlt-lint's metrics pass confines the kdlt_decode_ prefix to this
# module) with the bounded ``model`` label.

# TTFT spans prefill (tens of ms on CPU, sub-ms warm on device) up to
# queue-dominated seconds; TPOT is one decode step amortized per token.
DECODE_TTFT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
    5.0, 10.0,
)
DECODE_TPOT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 1.0,
)


def decode_metrics(registry: "Registry", model: str) -> dict:
    """One generative model's decode-lane series (bounded model label,
    memoized per child like every model-labeled helper)."""
    child = model_registry(registry, model)

    def mint(c: "Registry") -> dict:
        return {
            "ttft": c.histogram(
                "kdlt_decode_ttft_seconds",
                "time to first token: generation admitted -> first token "
                "materialized (prefill + queue wait included)",
                buckets=DECODE_TTFT_BUCKETS,
            ),
            "tpot": c.histogram(
                "kdlt_decode_tpot_seconds",
                "time per output token after the first "
                "((t_last - t_first) / (n - 1)) for each finished "
                "generation",
                buckets=DECODE_TPOT_BUCKETS,
            ),
            "tokens": c.counter(
                "kdlt_decode_tokens_total", "output tokens emitted"
            ),
            "generations": c.counter(
                "kdlt_decode_generations_total", "generations finished"
            ),
            "steps": c.counter(
                "kdlt_decode_steps_total",
                "batched decode steps executed (each advances every "
                "active slot by one token)",
            ),
            "step_seconds": c.histogram(
                "kdlt_decode_step_seconds",
                "wall time of one batched decode step (dispatch + "
                "materialize)",
                buckets=PIPELINE_STAGE_BUCKETS,
            ),
            "prefill_seconds": c.histogram(
                "kdlt_decode_prefill_seconds",
                "wall time of one prefill program: one chunk of a prompt "
                "(a prompt that fits one program is one chunk)",
                buckets=PIPELINE_STAGE_BUCKETS,
            ),
            "active_slots": c.gauge(
                "kdlt_decode_active_slots",
                "decode batch slots currently occupied by live generations",
            ),
            "queue_depth": c.gauge(
                "kdlt_decode_queue_depth",
                "admitted generations waiting for a free decode slot",
            ),
            "pages_in_use": c.gauge(
                "kdlt_decode_kv_pages_in_use",
                "KV-cache pages currently allocated to live generations",
            ),
            # Routing of a decoder with an expert layer (zeros otherwise):
            # the top-k assignments of live slots, summed over expert layers
            # and decode steps (prefills are not counted), by where the
            # chosen expert lives.
            # One series a kind, not a ``kind`` label: whoever reads a
            # /metrics page by series name alone can still tell them apart.
            "assignments_held": c.counter(
                "kdlt_decode_expert_held_assignments_total",
                "router assignments to a real expert this replica holds",
            ),
            "assignments_absent": c.counter(
                "kdlt_decode_expert_absent_assignments_total",
                "router assignments to a real expert another replica holds "
                "(their part of the result is left out here)",
            ),
            "assignments_zero": c.counter(
                "kdlt_decode_expert_zero_assignments_total",
                "router assignments to a zero-compute (identity) expert",
            ),
            "experts_touched": c.counter(
                "kdlt_decode_experts_touched_total",
                "held experts with at least one live slot's token, summed "
                "over expert layers and decode steps",
            ),
            "context_positions": c.counter(
                "kdlt_decode_context_positions_total",
                "cached positions a decode step attends over, summed over "
                "live slots and steps",
            ),
            "prefill_prompt_tokens": c.counter(
                "kdlt_decode_prefill_prompt_tokens_total",
                "prompt tokens prefilled",
            ),
            "prefill_padding_tokens": c.counter(
                "kdlt_decode_prefill_padding_tokens_total",
                "padding positions prefilled (bucket size minus prompt)",
            ),
            # Chunked prefill, one series a kind as above.
            "prefill_chunks": c.counter(
                "kdlt_decode_prefill_chunks_total",
                "prefill programs run: chunks of prompts",
            ),
            "prefill_tokens": c.counter(
                "kdlt_decode_prefill_tokens_total",
                "true prompt positions prefilled",
            ),
            "prefill_padded_tokens": c.counter(
                "kdlt_decode_prefill_padded_tokens_total",
                "positions the prefill programs computed: every chunk's "
                "compiled shape, padding included",
            ),
            "prefill_attended_pairs": c.counter(
                "kdlt_decode_prefill_attended_pairs_total",
                "(query, key) pairs of true prompt positions the prefill "
                "chunks attended over, causally: n (n + 1) / 2 a prompt of n",
            ),
            "prefill_routed_rows": c.counter(
                "kdlt_decode_prefill_routed_rows_total",
                "router assignments of true prompt positions to a real "
                "expert this replica holds, summed over expert layers "
                "(prefills only: the decode steps' are the held assignments)",
            ),
            "prefill_expert_rows": c.counter(
                "kdlt_decode_prefill_expert_rows_total",
                "(row, held expert) products the prefill programs computed, "
                "summed over expert layers: equal to the routed rows where "
                "nothing is computed for a row an expert was not routed",
            ),
            "shared_expert_tokens": c.counter(
                "kdlt_decode_shared_expert_tokens_total",
                "true rows a shared expert computed, summed over expert "
                "layers, prefill chunks and decode steps",
            ),
        }

    return _memo_on_child(child, "_kdlt_decode", mint)


# --- OpenMetrics exemplars ---------------------------------------------------
#
# Behind $KDLT_METRICS_EXEMPLARS=1 the latency histograms annotate bucket
# samples with the trace id of a recent observation that landed there
# (``... # {trace_id="..."} value timestamp``), so a burn-rate spike on a
# dashboard links DIRECTLY to /debug/trace/<rid> waterfalls of the requests
# that caused it.  Off (the default) the exposition is byte-identical to the
# legacy format -- classic Prometheus text-format parsers never see the
# annotation.  Exemplars exist on histograms ONLY (the OpenMetrics rule);
# tools/check_metrics.py rejects exemplar= on counter/gauge mutations.

EXEMPLARS_ENV = "KDLT_METRICS_EXEMPLARS"


def exemplars_enabled() -> bool:
    """Read the env gate afresh (cheap: a handful of calls per request);
    in-process A/B arms flip the env between servers."""
    return os.environ.get(EXEMPLARS_ENV, "").strip() == "1"


def _escape_label_value(v) -> str:
    """Prometheus text-format label escaping: backslash, quote, newline.
    Without it a label value containing '"' or '\\n' desyncs strict
    parsers for the whole exposition."""
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _escape_help(text: str) -> str:
    """HELP text escaping per the exposition format: backslash + newline."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_labels(labels: dict[str, str] | None, extra: str = "") -> str:
    parts = [f'{k}="{_escape_label_value(v)}"' for k, v in (labels or {}).items()]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class Counter:
    kind = "counter"

    def __init__(self, name: str, help: str = "", labels: dict[str, str] | None = None):
        self.name, self.help, self.labels = name, help, labels
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def sample_lines(self) -> list[str]:
        return [f"{self.name}{_fmt_labels(self.labels)} {self._value}"]

    def render(self) -> str:
        return (
            f"# HELP {self.name} {_escape_help(self.help)}\n"
            f"# TYPE {self.name} {self.kind}\n"
            + "\n".join(self.sample_lines()) + "\n"
        )


class Gauge(Counter):
    kind = "gauge"

    def set(self, v: float) -> None:
        with self._lock:
            self._value = v


class Histogram:
    def __init__(
        self,
        name: str,
        help: str = "",
        buckets=DEFAULT_BUCKETS,
        labels: dict[str, str] | None = None,
    ):
        self.name, self.help, self.labels = name, help, labels
        self.buckets = tuple(buckets)
        self._counts = [0] * (len(self.buckets) + 1)  # +inf bucket
        self._sum = 0.0
        self._n = 0
        # Last exemplar per bucket index: (trace_id, value, unix_ts).  Only
        # ever populated by callers passing exemplar= (the request-latency
        # observe sites, behind the env gate), so plain histograms pay one
        # None check.
        self._exemplars: dict[int, tuple[str, float, float]] = {}
        self._lock = threading.Lock()

    def observe(self, v: float, exemplar: str | None = None) -> None:
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._n += 1
            if exemplar is not None:
                self._exemplars[i] = (str(exemplar), v, time.time())

    def percentile(self, q: float) -> float:
        """Approximate percentile from bucket upper bounds (q in [0,1])."""
        with self._lock:
            n = self._n
            if n == 0:
                return 0.0
            target = q * n
            cum = 0
            for i, c in enumerate(self._counts):
                cum += c
                if cum >= target:
                    return self.buckets[i] if i < len(self.buckets) else float("inf")
        return float("inf")

    @property
    def count(self) -> int:
        return self._n

    @property
    def sum(self) -> float:
        return self._sum

    kind = "histogram"

    def _exemplar_suffix(self, i: int, with_exemplars: bool) -> str:
        """The OpenMetrics exemplar annotation for bucket index ``i``, or ""
        (always "" unless the env gate is on, so the legacy exposition is
        byte-identical with the flag off)."""
        if not with_exemplars:
            return ""
        ex = self._exemplars.get(i)
        if ex is None:
            return ""
        trace_id, value, ts = ex
        return (
            f' # {{trace_id="{_escape_label_value(trace_id)}"}} '
            f"{value:.6g} {ts:.3f}"
        )

    def sample_lines(self) -> list[str]:
        out = []
        cum = 0
        with_ex = bool(self._exemplars) and exemplars_enabled()
        with self._lock:
            for i, (le, c) in enumerate(zip(self.buckets, self._counts)):
                cum += c
                le_label = f'le="{le}"'
                out.append(
                    f"{self.name}_bucket{_fmt_labels(self.labels, le_label)} {cum}"
                    + self._exemplar_suffix(i, with_ex)
                )
            cum += self._counts[-1]
            inf_label = 'le="+Inf"'
            out.append(
                f"{self.name}_bucket{_fmt_labels(self.labels, inf_label)} {cum}"
                + self._exemplar_suffix(len(self.buckets), with_ex)
            )
            out.append(f"{self.name}_sum{_fmt_labels(self.labels)} {self._sum}")
            out.append(f"{self.name}_count{_fmt_labels(self.labels)} {self._n}")
        return out

    def render(self) -> str:
        return (
            f"# HELP {self.name} {_escape_help(self.help)}\n"
            f"# TYPE {self.name} {self.kind}\n"
            + "\n".join(self.sample_lines()) + "\n"
        )


class Registry:
    def __init__(self, labels: dict[str, str] | None = None):
        """``labels`` are applied to every metric created through this
        registry (e.g. Registry(labels={"model": name}) per served model, so
        two models' engines never emit colliding series)."""
        self._metrics: list = []
        self._labels = dict(labels or {})
        self._keys: set = set()
        self._lock = threading.Lock()

    def counter(self, name: str, help: str = "") -> Counter:
        return self._add(Counter(name, help, labels=self._labels or None))

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._add(Gauge(name, help, labels=self._labels or None))

    def histogram(self, name: str, help: str = "", buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._add(Histogram(name, help, buckets, labels=self._labels or None))

    def with_labels(self, **labels: str) -> "Registry":
        """Child registry sharing this one's output but adding labels."""
        child = Registry({**self._labels, **labels})
        self._add(child)
        return child

    def _add(self, m):
        with self._lock:
            name = getattr(m, "name", None)
            if name is not None:
                key = (name, tuple(sorted((m.labels or {}).items())))
                if key in self._keys:
                    raise ValueError(f"duplicate metric {name!r} with same labels")
                self._keys.add(key)
            self._metrics.append(m)
        return m

    def remove(self, m) -> None:
        """Drop a metric or child registry (e.g. an unloaded model version's
        series) from this registry's output."""
        with self._lock:
            if m in self._metrics:
                self._metrics.remove(m)
                name = getattr(m, "name", None)
                if name is not None:
                    self._keys.discard(
                        (name, tuple(sorted((m.labels or {}).items())))
                    )

    def _leaves(self):
        """Every leaf metric under this registry, depth-first, in creation
        order (child registries flattened in place)."""
        with self._lock:
            metrics = list(self._metrics)
        for m in metrics:
            if isinstance(m, Registry):
                yield from m._leaves()
            else:
                yield m

    def render(self) -> str:
        """Prometheus text exposition, grouped by metric name.

        Labeled series sharing a name (e.g. the per-reason
        kdlt_admission_shed_total counters) must render under ONE
        ``# HELP``/``# TYPE`` block: the format forbids repeating the
        metadata lines, and strict parsers (promtool, the Prometheus
        scraper in some configurations) reject the duplicate blocks the
        naive per-metric concatenation used to produce.  First-seen
        ordering keeps the page stable across renders; the first series'
        HELP/TYPE wins for its name.
        """
        order: list[str] = []
        meta: dict[str, tuple[str, str]] = {}
        samples: dict[str, list[str]] = {}
        for m in self._leaves():
            name = m.name
            if name not in meta:
                order.append(name)
                meta[name] = (m.kind, m.help)
                samples[name] = []
            samples[name].extend(m.sample_lines())
        out: list[str] = []
        for name in order:
            kind, help = meta[name]
            out.append(f"# HELP {name} {_escape_help(help)}")
            out.append(f"# TYPE {name} {kind}")
            out.extend(samples[name])
        return "\n".join(out) + "\n" if out else ""
