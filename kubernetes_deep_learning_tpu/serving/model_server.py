"""The TPU model server: in-tree replacement for TF-Serving.

The reference's model tier is the external ``tensorflow/serving:2.3.0`` C++
binary: versioned model loading from /models/<name>/<n>, a PredictionService
on :8500, batched graph execution (reference tf-serving.dockerfile:1-5,
SURVEY.md component 7).  This server reproduces those capabilities in-tree:

- scans an artifact root for every model's highest version (same layout rule),
- executes on the local accelerator through InferenceEngine (XLA:TPU is the
  "native layer" here -- the compiled StableHLO program is what C++ was to
  TF-Serving),
- server-side dynamic batching (TF-Serving has it; the reference never
  configured it),
- /healthz liveness, /readyz readiness gated on warm compiles, /metrics.

Endpoints::

    POST /v1/models/<name>:predict     msgpack or JSON predict
    GET  /v1/models                    list served models
    GET  /v1/models/<name>             the ModelSpec (the discoverable
                                       contract; replaces saved_model_cli)
    GET  /healthz | /readyz | /metrics
    POST /debug/profile                capture a jax.profiler trace
                                       ({"seconds": s}): the device's planes,
                                       Python and host tracers off; with
                                       "annotations": true also this tier's
                                       live spans as host annotations;
                                       traces land in fresh directories
                                       under the server's --profile-dir
                                       (never a client-chosen path).  The
                                       tracing hook SURVEY.md section 5
                                       notes the reference lacks entirely;
                                       disable with --no-profiling
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from concurrent.futures import TimeoutError as FuturesTimeout

import numpy as np

from kubernetes_deep_learning_tpu.export import artifact as art
from kubernetes_deep_learning_tpu.runtime import (
    BatcherClosed,
    DispatcherClosed,
    DispatchStall,
    InferenceEngine,
    InFlightDispatcher,
    QueueFull,
    create_batcher,
    resolve_pipeline_depth,
    resolve_weights,
)
from kubernetes_deep_learning_tpu.serving import faults as faults_lib
from kubernetes_deep_learning_tpu.serving.admission import (
    DEADLINE_HEADER,
    AdaptiveLimiter,
    AdmissionController,
    Deadline,
    Shed,
    admission_enabled,
    install_sigterm_drain,
    retry_after_headers,
)
from kubernetes_deep_learning_tpu.serving.admission import limiter as limiter_mod
from kubernetes_deep_learning_tpu.serving.tracing import (
    PARENT_SPAN_HEADER,
    REQUEST_ID_HEADER,
    TRACE_HEADER,
    ensure_request_id,
    ensure_span_id,
    log_request,
)
from kubernetes_deep_learning_tpu.utils import flightrecorder as incident_lib
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from kubernetes_deep_learning_tpu.utils import slo as slo_lib
from kubernetes_deep_learning_tpu.utils import trace as trace_lib

_PREDICT_RE = re.compile(r"^/v1/models/([^/:]+):predict$")
_GENERATE_RE = re.compile(r"^/v1/models/([^/:]+):generate$")
_STATUS_RE = re.compile(r"^/v1/models/([^/:]+):status$")
_MODEL_RE = re.compile(r"^/v1/models/([^/:]+)$")

DEFAULT_PORT = 8500  # the reference model tier's port (tf-serving-clothing-model-service.yaml:9-10)
MAX_IMAGES_PER_REQUEST = 2048  # bounds one request's decoded-image memory
PROFILE_DIR_ENV = "KDLT_PROFILE_DIR"  # base dir for /debug/profile captures
# KDLT_AOT_WARM=1: run the kdlt-warm AOT pass (every model, the FULL
# default bucket ladder, into the persistent compile cache) before
# serving starts -- the pod-init half of zero-cold-start scale-up; the
# --aot-warm flag runs the same pass and exits (image build / init
# container).  See export.warm.
AOT_WARM_ENV = "KDLT_AOT_WARM"
# Deploy-side default for --model-parallel: devices per tensor-parallel
# group on the serving mesh's inner (fastest-ICI) axis.  1 = pure
# data-parallel (the partition rules replicate everything); > 1 shards
# wide kernels per parallel.mesh.PARTITION_RULES, shrinking per-device
# param bytes ~1/mp -- the knob that makes a model fit where it didn't.
MESH_MODEL_PARALLEL_ENV = "KDLT_MESH_MODEL_PARALLEL"


def resolve_mesh_model_parallel(explicit: int = 0) -> int:
    """--model-parallel wins; else $KDLT_MESH_MODEL_PARALLEL; else 1."""
    if explicit > 0:
        return explicit
    raw = os.environ.get(MESH_MODEL_PARALLEL_ENV, "").strip()
    try:
        return max(1, int(raw)) if raw else 1
    except ValueError:
        return 1


def _warmed_line(what: str, seconds: float, engine) -> str:
    """The boot log's line for one warmed model; where the fused path is
    engaged it names the blocks each bucket's program runs as Pallas
    kernels (the status page's ``fused_blocks``)."""
    fused = getattr(engine, "fused_blocks", dict)()
    tail = f", fused blocks {json.dumps(fused)}" if fused else ""
    return f"warmed {what}: {seconds:.1f}s{tail}"


class ServedModel:
    def __init__(
        self, artifact, buckets, max_delay_ms, registry, use_batcher=True,
        batcher_impl="auto", mesh=None, mesh_mode="data", engine_factory=None,
        pipeline_depth=None, scheduler=None, weight=None,
    ):
        # engine_factory: swap the execution engine (default InferenceEngine).
        # runtime.stub.StubEngine is the host path with the device taken
        # out (the tests' engine).
        # scheduler: the server's shared UnifiedScheduler (runtime.scheduler)
        # -- when set and the engine supports async dispatch, this model
        # serves through a per-model scheduling lane + the tier's ONE shared
        # InFlightDispatcher instead of a private batcher/dispatcher pair,
        # so device time is arbitrated ACROSS models (weight = this model's
        # share in that arbitration).
        engine_factory = engine_factory or InferenceEngine
        from kubernetes_deep_learning_tpu.ops import preprocess as preprocess_lib

        # Which resize kernel the decode stage runs (native C++ or PIL) is
        # settled at import by the toolchain; the status page names it.
        self.host_resize = preprocess_lib.RESIZE_IMPL
        self.artifact = artifact
        self.name = artifact.spec.name
        self.version = int(artifact.path.rstrip("/").rsplit("/", 1)[-1])
        # The registry's identity key (sha256 of the artifact dir); stamped
        # by ModelRegistry.poll after a successful load.
        self.artifact_hash: str | None = None
        # Each model version gets a labeled child registry so two models (or
        # two versions across a hot reload) never emit colliding series on
        # the shared /metrics page; the child is dropped when the version is
        # unloaded (ModelServer.poll_versions).
        self.registry_child = metrics_lib.model_version_registry(
            registry, artifact.spec.name, self.version
        )
        # The deadline budget handed to the batcher/dispatcher wait, in ms:
        # the last hop of the gateway -> model tier -> batcher propagation
        # chain, so the chain is observable end to end on /metrics (each
        # tier's kdlt_admission_deadline_remaining_ms shrinks, then this).
        self._m_batcher_budget = self.registry_child.histogram(
            "kdlt_admission_batcher_budget_ms",
            "remaining deadline budget when the request reached the "
            "batcher/dispatcher wait",
            buckets=metrics_lib.DEADLINE_MS_BUCKETS,
        )
        try:
            self.engine = engine_factory(
                artifact, buckets=buckets, registry=self.registry_child,
                mesh=mesh, mesh_mode=mesh_mode,
            )
            # Scheduler mode: the model's device work rides a scheduling
            # lane on the shared dispatcher.  Engines that carry their OWN
            # in-flight budget (CrossHostEngine: the fleet-wide
            # KDLT_XH_PIPELINE_DEPTH is a protocol parameter every process
            # must agree on) keep a dedicated dispatcher instead, as do
            # engines with no async hook (plain StubEngine: there is no
            # device pipeline to arbitrate).
            self._scheduler = None
            self._max_delay_ms = max_delay_ms
            self._weight = weight
            if (
                scheduler is not None
                and use_batcher
                and hasattr(self.engine, "predict_async")
                and getattr(self.engine, "preferred_pipeline_depth", None) is None
            ):
                self._scheduler = scheduler
                self.dispatcher = None
                self.batcher = None
                self.batcher_kind = "scheduler"
            else:
                # Legacy per-model pipeline: ONE in-flight dispatch pipeline
                # per model version, shared by the single-image batcher and
                # the chunked multi-image path, so both draw from the same
                # bounded in-flight budget.  None when depth=1 (serial) or
                # the engine has no async dispatch hook.
                depth = getattr(self.engine, "preferred_pipeline_depth", None)
                if depth is None:
                    depth = resolve_pipeline_depth(pipeline_depth)
                self.dispatcher = (
                    InFlightDispatcher(
                        self.engine, depth=depth, registry=self.registry_child
                    )
                    if depth > 1 and hasattr(self.engine, "predict_async")
                    else None
                )
                self.batcher = (
                    create_batcher(
                        self.engine,
                        impl=batcher_impl,
                        max_delay_ms=max_delay_ms,
                        registry=self.registry_child,
                        pipeline_depth=depth,
                        dispatcher=self.dispatcher,
                    )
                    if use_batcher
                    else None
                )
                # create_batcher picks native vs python from the toolchain
                # and core count; the status page says which one it was.
                self.batcher_kind = (
                    type(self.batcher).__name__ if self.batcher else "none"
                )
        except BaseException:
            # with_labels already hooked the child into the shared registry;
            # a failed construction must not leave the orphan behind (the
            # version watcher retries every poll).
            registry.remove(self.registry_child)
            raise

    def activate(self) -> None:
        """Flip live routing to this version's engine.

        In scheduler mode this registers/swaps the model's scheduling lane
        -- called AFTER warmup, so the lane never routes to a cold engine
        (the warmed-before-swap contract), and called BEFORE the models
        dict rebinds, so there is no window where a handler resolved this
        ServedModel but the lane still points at the predecessor.  Queued
        requests survive the swap: lanes are engine-agnostic until
        dispatch.  No-op in legacy batcher mode (construction already wired
        the private batcher)."""
        if self._scheduler is not None:
            self._scheduler.register(
                self.name, self.engine, weight=self._weight,
                max_delay_ms=self._max_delay_ms,
            )

    def predict(
        self,
        images: np.ndarray,
        deadline: Deadline | None = None,
        trace=None,
        priority: str | None = None,
    ) -> np.ndarray:
        # ``trace`` (utils.trace.RequestTrace): the handler's server.predict
        # span carrier; the batcher/dispatcher record this request's
        # queue-wait and pipeline-stage spans under it.
        # Deadline-aware waits (serving.admission): every blocking wait
        # below -- the batcher future, the chunked dispatcher futures -- is
        # bounded by the request's REMAINING budget instead of a fixed
        # constant, so a request never occupies a handler thread past the
        # point its caller stopped listening.  deadline=None (admission
        # off, gRPC path) keeps the legacy fixed bounds.
        batcher_timeout, future_timeout = 20.0, 120.0
        if deadline is not None:
            remaining = max(deadline.remaining_s(), 0.0)
            self._m_batcher_budget.observe(remaining * 1e3)
            batcher_timeout = min(batcher_timeout, remaining)
            future_timeout = min(future_timeout, remaining)
        max_b = self.engine.max_batch
        if self._scheduler is not None and images.dtype == np.uint8:
            # Scheduler mode: EVERY uint8 batch rides the shared scheduler
            # -- single images coalesce in the model's lane, pre-formed
            # batches enter as indivisible chunks -- so cross-model
            # arbitration covers all device work, not just the single-image
            # path.  Bucket padding/dispatch is unchanged underneath
            # (engine.predict_async), so logits stay bit-identical to
            # single-model serving.
            try:
                if images.shape[0] == 1:
                    return self._scheduler.submit(
                        self.name, images[0], deadline=deadline, trace=trace,
                        priority=priority,
                    ).result(timeout=batcher_timeout)[None]
                futs = [
                    self._scheduler.submit_batch(
                        self.name, images[i : i + max_b],
                        deadline=deadline, trace=trace, priority=priority,
                    )
                    for i in range(0, images.shape[0], max_b)
                ]
                return np.concatenate(
                    [f.result(timeout=future_timeout) for f in futs]
                )
            except BatcherClosed:
                # Shutdown/unload race: the lane is gone but this handler
                # still holds the engine -- serve it directly rather than
                # surfacing a client-visible 500.
                pass
        # Multi-image requests go straight to the engine (they are already a
        # batch); single uint8 images go through the batcher to coalesce
        # across concurrent requests (the batcher is uint8-only so mixed
        # dtypes never end up in one np.stack).
        if (
            self.batcher is not None
            and images.shape[0] == 1
            and images.dtype == np.uint8
        ):
            try:
                return self.batcher.predict(
                    images[0], timeout=batcher_timeout, trace=trace
                )[None]
            except BatcherClosed:
                # A hot reload closed this version's batcher while the
                # handler already held a reference to it; the engine is
                # still valid, so the in-flight request must not become
                # a client-visible 500.
                pass
        if images.shape[0] <= max_b:
            if trace is not None:
                with trace.span(trace_lib.SPAN_ENGINE_PREDICT, batch=int(images.shape[0])):
                    return self.engine.predict(images)
            return self.engine.predict(images)
        # Batches beyond the bucket ladder are served in max-bucket chunks
        # rather than erroring: the client's batch size should not have to
        # know this server's compiled shapes.  With the pipeline on, the
        # chunks ride the shared dispatcher so chunk i+1's H2D overlaps
        # chunk i's execution instead of serializing dispatch->sync per
        # chunk; the futures keep per-chunk order for the concatenate.
        if self.dispatcher is not None and images.dtype == np.uint8:
            try:
                futs = [
                    self.dispatcher.submit(
                        images[i : i + max_b],
                        traces=(trace,) if trace is not None else (),
                    )
                    for i in range(0, images.shape[0], max_b)
                ]
                return np.concatenate(
                    [f.result(timeout=future_timeout) for f in futs]
                )
            except DispatcherClosed:
                pass  # hot reload race: fall through to the serial engine path
        return np.concatenate(
            [
                self.engine.predict(images[i : i + max_b])
                for i in range(0, images.shape[0], max_b)
            ]
        )

    def close(self, drain: bool = True) -> None:
        if self._scheduler is not None:
            # Drop the lane only if this engine still owns it: a superseded
            # version's close after a hot-swap is a no-op (the lane -- and
            # its queued requests -- belong to the replacement).
            self._scheduler.unregister(self.name, engine=self.engine)
        if self.batcher is not None:
            self.batcher.close(drain=drain)
        if self.dispatcher is not None:
            # After the batcher's dispatch thread exits, only in-flight
            # handler threads can race this close; they fall back to the
            # engine path on DispatcherClosed.
            self.dispatcher.close(drain=drain)


class ModelServer:
    def __init__(
        self,
        model_root: str,
        port: int = DEFAULT_PORT,
        buckets=(1, 2, 4, 8, 16, 32, 64, 128),
        max_delay_ms: float = 2.0,
        use_batcher: bool = True,
        host: str = "0.0.0.0",
        batcher_impl: str = "auto",
        mesh=None,
        mesh_mode: str = "data",
        profile_base: str | None = "",
        request_log: bool = False,
        engine_factory=None,
        pipeline_depth: int | None = None,
        admission: bool | None = None,
        sched_policy: str | None = None,
        sched_weights: dict[str, float] | None = None,
        slo: bool | None = None,
        incident: bool | None = None,
        incident_dir: str | None = None,
        incident_triggers: str | None = None,
        incident_dedup_s: float | None = None,
        decode: bool | None = None,
        ingest: bool | None = None,
        decode_pool: int | None = None,
    ):
        # request_log: one traced stdout line per predict (rid, model, batch,
        # status, duration) -- the model-tier half of the gateway's
        # X-Request-Id propagation.  Errors are always logged with the rid.
        self.request_log = request_log
        # profile_base: directory for /debug/profile traces; "" means
        # $KDLT_PROFILE_DIR (or a default under the system temp dir), None
        # disables the endpoint.
        if profile_base == "":
            profile_base = os.environ.get(PROFILE_DIR_ENV, "").strip()
        if profile_base == "":
            import tempfile as _tf

            profile_base = os.path.join(_tf.gettempdir(), "kdlt-traces")
        self._profile_base = profile_base
        self.registry = metrics_lib.Registry()
        # Per-request span traces (utils.trace): the model-tier half of the
        # cross-tier waterfall, keyed by the propagated X-Request-Id and
        # served at /debug/trace/<rid>.  The registry wires the tail-based
        # retention accounting (kdlt_trace_{retained,dropped}_total).  Every
        # live span is also a profiler annotation, so a /debug/profile
        # capture shows the handler's stages beside the device's programs.
        from jax.profiler import TraceAnnotation

        self.tracer = trace_lib.Tracer(
            "model-server", registry=self.registry, annotate=TraceAnnotation
        )
        # The interpreter's garbage-collection pauses, process-wide (one
        # hook however many servers a process builds): seconds by
        # generation on this page, each pause a ``gc.pause`` annotation.
        metrics_lib.gc_pause_counters(
            self.registry, trace_lib.watch_gc_pauses(TraceAnnotation))
        # SLO engine (utils.slo): per-model sliding-window goodput and
        # multi-window burn rates against $KDLT_SLO_TARGET, fed from the
        # same handler boundary as kdlt_server_request_seconds; serves
        # /debug/slo and the kdlt_slo_* gauges.  slo=None -> $KDLT_SLO ->
        # enabled.
        self.slo = slo_lib.SloEngine(self.registry, tier="model-server",
                                     enabled=slo)
        # Fault injection (serving.faults): the server.predict point; None
        # (zero-overhead) unless $KDLT_FAULTS configures rules.
        self._faults = faults_lib.from_env()
        if self._faults is not None:
            self._faults.attach(self.registry)
        # XLA compile accounting (kdlt_xla_compile_*): installed before
        # the first model loads so the boot's own compiles are counted;
        # after /readyz the request counter must stay flat.
        from kubernetes_deep_learning_tpu.utils.compilecache import CompileWatch

        self._compile_watch = CompileWatch(self.registry)
        self._m_requests = self.registry.counter(
            "kdlt_server_requests_total", "predict requests"
        )
        self._m_errors = self.registry.counter(
            "kdlt_server_errors_total", "failed predict requests"
        )
        self._m_latency = self.registry.histogram(
            "kdlt_server_request_seconds", "request handling latency"
        )
        self._m_unpack = metrics_lib.server_unpack_counters(self.registry)
        # Admission control (serving.admission): the model tier's front
        # door -- deadline-exhausted rejection before the TPU is touched,
        # AIMD concurrency limiting, and graceful drain.  admission=None ->
        # $KDLT_ADMISSION -> enabled.  The concurrency floor is 2x the max
        # bucket: the admitted handlers ARE the batcher's supply, so a
        # lower limit would starve batch formation and DESTROY throughput
        # (batches of 1) without reducing anyone's latency -- below the
        # floor, overload belongs to the shed path, not the limiter.  The
        # ceiling is reconciled with that floor (2x headroom, or the env
        # override if higher): the env default (64) sits BELOW the default
        # buckets' floor (256), and an inverted pair would turn the AIMD
        # decrease into an increase.
        floor = 2.0 * max(buckets)
        self.admission = AdmissionController(
            self.registry, tier="model-server", enabled=admission,
            limiter=(
                AdaptiveLimiter(
                    min_limit=floor,
                    max_limit=max(2.0 * floor, limiter_mod.env_max_limit()),
                )
                if admission_enabled(admission) else None
            ),
        )
        # Incident flight recorder (utils.flightrecorder): the compute
        # tier's black box.  Dispatch stalls, registry (un)loads, quant-
        # gate failures, and warm-source cold compiles record into its
        # timeline; the dispatch-stall trigger captures a bundle with the
        # causal trace and (opt-in, $KDLT_INCIDENT_PROFILE_S) a short
        # device profile.  Built BEFORE the model registry: the initial
        # poll_versions() below already emits registry.load events.
        self.recorder = incident_lib.FlightRecorder(
            "model-server", self.registry, tracer=self.tracer,
            enabled=incident, incident_dir=incident_dir,
            triggers=incident_triggers, dedup_s=incident_dedup_s,
            profiler=self._incident_profile,
        )
        self.recorder.add_snapshot_provider("slo", self.slo.debug_payload)
        # Raw-bytes ingest wire (GUIDE 10q): when enabled (KDLT_INGEST,
        # default on; ``ingest`` arg overrides), the spec-discovery GET
        # advertises the capability via X-Kdlt-Ingest and :predict accepts
        # the packed-encoded-blobs content type, decoding at THIS tier on
        # a GIL-released thread pool (KDLT_DECODE_POOL / ``decode_pool``).
        # The decoded-uint8 cache is content-addressed -- (payload hash,
        # preprocess params) -- so repeat images skip decode+resize across
        # models and across the wire format.
        from kubernetes_deep_learning_tpu.ops import preprocess as preprocess_lib
        from kubernetes_deep_learning_tpu.serving import cache as cache_lib
        from kubernetes_deep_learning_tpu.serving import protocol as protocol_lib

        self._ingest_enabled = protocol_lib.ingest_enabled(ingest)
        self._ingest_decoder = preprocess_lib.BatchDecoder(decode_pool)
        self._decoded_cache = cache_lib.DecodedCache(registry=self.registry)
        self._m_ingest = (
            metrics_lib.ingest_server_metrics(self.registry)
            if self._ingest_enabled
            else None
        )
        self.model_root = model_root
        self._buckets = buckets
        self._max_delay_ms = max_delay_ms
        self._use_batcher = use_batcher
        self._batcher_impl = batcher_impl
        self._mesh = mesh
        self._mesh_mode = mesh_mode
        self._engine_factory = engine_factory
        self._pipeline_depth = pipeline_depth
        # Unified SLO-aware scheduling core (runtime.scheduler): ONE queue/
        # scheduler for every served model, arbitrating the shared
        # dispatcher's device time by deadline budget + per-model weights
        # ($KDLT_SCHED_POLICY / $KDLT_SCHED_WEIGHTS).  batcher_impl
        # "native" opts out: the C++ ticket queue is a single-model
        # GIL-free fast path and keeps its private pipeline.
        self.scheduler = None
        if use_batcher and batcher_impl != "native":
            from kubernetes_deep_learning_tpu.runtime import UnifiedScheduler

            self.scheduler = UnifiedScheduler(
                registry=self.registry,
                policy=sched_policy,
                weights=sched_weights,
                pipeline_depth=pipeline_depth,
            )
            self.recorder.add_snapshot_provider(
                "scheduler", self.scheduler.lanes_snapshot
            )
        # Multi-model registry (serving.registry): scans the artifact root
        # for EVERY model's highest version, keys loads by artifact hash,
        # owns the name -> ServedModel map the handlers route by.
        from kubernetes_deep_learning_tpu.serving.registry import ModelRegistry

        self.model_registry = ModelRegistry(
            model_root, loader=self._load_model, unloader=self._unload_model
        )
        # Generative serving lane (serving.generate): the :generate route's
        # decode subsystem -- continuous batching over a block-paged
        # KV-cache with streamed SSE token responses.  Opt-in (--decode /
        # $KDLT_DECODE=1): the image path's behavior is byte-identical with
        # the lane off.  Shares this tier's registry, SLO engine, tracer,
        # and flight recorder, so decode burn and image burn read off the
        # same dashboards.
        from kubernetes_deep_learning_tpu.serving import generate as generate_lib

        self.generate: generate_lib.GenerateLane | None = None
        if generate_lib.decode_enabled(decode):
            self.generate = generate_lib.GenerateLane(
                registry=self.registry, slo=self.slo, tracer=self.tracer,
                recorder=self.recorder, model_root=model_root,
            )
            self.recorder.add_snapshot_provider(
                "decode", self.generate.debug_payload
            )
        self._watcher: threading.Thread | None = None
        self._watcher_stop = threading.Event()
        self._shutdown_done = threading.Event()
        self._profile_lock = threading.Lock()
        self.poll_versions()
        if not self.models and self.generate is None:
            # the generative lane alone is a server too
            raise FileNotFoundError(f"no model artifacts under {model_root!r}")
        # The listen backlog: a closed loop of a hundred and more streams
        # connects all at once, and what the default of 5 turns away waits
        # for TCP's retransmission, a second and more.
        httpd_class = type("_Httpd", (ThreadingHTTPServer,), {"request_queue_size": 256})
        self._httpd = httpd_class((host, port), self._make_handler())
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread: threading.Thread | None = None

    def warmup(self) -> None:
        for m in self.models.values():
            if m.engine.ready:
                # Loaded through the registry: warmed before activation
                # (_load_model).  A second pass would find every program
                # compiled and overwrite each bucket's warm-up seconds --
                # the boot's compile record -- with a no-op's.
                continue
            dt = m.engine.warmup()
            print(_warmed_line(m.artifact.spec.name, dt, m.engine), file=sys.stderr)
        if self.generate is not None:
            rep = self.generate.warmup()
            total = sum(rep["buckets"].values()) + rep["step_s"]
            print(
                f"warmed decode {rep['model']}: {total:.1f}s "
                f"(prefill buckets {sorted(rep['buckets'])}, one step)",
                file=sys.stderr,
            )

    @property
    def ready(self) -> bool:
        return all(m.engine.ready for m in self.models.values())

    def models_status(self) -> dict:
        """GET /v1/models: the registry's image models and, under its
        served name, the generative lane with its ``decode`` block."""
        status = self.model_registry.status()
        if self.generate is not None:
            status[self.generate.model] = self.generate.status()
        return status

    @property
    def fast_degraded(self) -> bool:
        """True when any served engine fell off its fused path at warmup."""
        return any(
            getattr(m.engine, "fast_degraded", False)
            for m in self.models.values()
        )

    @property
    def models(self) -> dict[str, ServedModel]:
        """The name -> ServedModel routing map (owned by the registry)."""
        return self.model_registry.models

    @property
    def stalled(self) -> bool:
        """True once any dispatch watchdog declared an in-flight pipeline
        stuck.  /healthz follows this flag: a wedged device sync cannot be
        recovered in-process, so the orchestrator must restart the pod
        (liveness probe failure), while the gateway's replica pool routes
        around it in the meantime."""
        if self.scheduler is not None and self.scheduler.stalled:
            return True
        return any(
            m.dispatcher is not None and m.dispatcher.stalled
            for m in self.models.values()
        )

    # --- version watching --------------------------------------------------

    def poll_versions(self) -> list[str]:
        """One scan of the artifact root: load any new model or higher version.

        TF-Serving's convention -- watch /models/<name>/ and hot-load the
        highest numeric version dir (SURVEY.md section 5, checkpoint/resume) --
        which the reference ships but never exercises (it redeploys the image
        instead, reference tf-serving.dockerfile:5).  Serves as both the
        initial load (from __init__) and the watcher's periodic scan.
        Scan/compare/swap live in serving.registry.ModelRegistry (scans
        serialized, copy-on-write swaps, artifact-hash dedupe); this server
        owns only the ServedModel construction below.
        """
        return self.model_registry.poll()

    def _load_model(self, name: str, version: int, directory: str):
        """ModelRegistry loader: construct, warm, and ACTIVATE one version.

        The version is fully loaded and warmed before activation, so
        serving never routes to a cold engine; activation (the scheduling-
        lane swap) happens here, before the registry rebinds its models
        dict.  Layout invariant: the artifact's spec.name must equal its
        directory name -- it is the serving key, URL path, and version-
        comparison key at once; mismatched artifacts are skipped loudly.
        """
        artifact = art.load_artifact(directory)
        if artifact.spec.name != name:
            print(
                f"version watcher: skipping {directory}: spec.name "
                f"{artifact.spec.name!r} != directory name {name!r}"
            )
            return None
        fresh = ServedModel(
            artifact,
            self._buckets,
            self._max_delay_ms,
            self.registry,
            self._use_batcher,
            self._batcher_impl,
            self._mesh,
            self._mesh_mode,
            self._engine_factory,
            self._pipeline_depth,
            scheduler=self.scheduler,
        )
        try:
            warm_s = fresh.engine.warmup()
            print(_warmed_line(f"{name} v{version}", warm_s, fresh.engine), file=sys.stderr)
        except Exception:
            # Warmup failed post-construction: the registry skips this
            # version (and retries next poll); the orphaned child registry
            # must not leak series onto /metrics.
            fresh.close()
            self.registry.remove(fresh.registry_child)
            raise
        fresh.activate()
        self.recorder.record("registry.load", model=name, version=version)
        if getattr(fresh.engine, "quant_gate_failed", False):
            # The int8 warmup tolerance gate refused activations and the
            # engine downgraded to weight-only: exactly the quiet-but-
            # consequential edge the incident timeline exists for.
            self.recorder.record(
                "quant.gate_fail", model=name, version=version,
            )
        report = getattr(fresh.engine, "warm_report", None) or {}
        for bucket, info in (report.get("buckets") or {}).items():
            if (info or {}).get("source") == "compile":
                # A cold compile during warmup: on a fleet that expects
                # warm-from-cache boots (KDLT_AOT_WARM), this is the
                # scale-up latency regression signal.
                self.recorder.record(
                    "warm.compile", model=name, bucket=bucket,
                    seconds=(info or {}).get("seconds"),
                )
        return fresh

    def _unload_model(self, old: ServedModel) -> None:
        """ModelRegistry unloader for a superseded version."""
        old.close()
        self.registry.remove(old.registry_child)
        try:
            self.recorder.record(
                "registry.unload", model=old.artifact.spec.name,
            )
        except Exception:  # noqa: BLE001 - unload must finish regardless
            pass

    def start_version_watcher(self, interval_s: float = 10.0) -> None:
        """Poll the artifact root for new versions in a daemon thread."""

        def loop():
            while not self._watcher_stop.wait(interval_s):
                try:
                    self.poll_versions()
                except Exception as e:
                    print(f"version watcher error: {e}", file=sys.stderr)

        self._watcher = threading.Thread(
            target=loop, name="kdlt-version-watcher", daemon=True
        )
        self._watcher.start()

    # --- raw-bytes ingest (GUIDE 10q) --------------------------------------

    def _decode_blobs(self, shape, resize_filter: str, blobs: list[bytes]) -> np.ndarray:
        """Bytes-wire decode stage: encoded blobs -> uint8 (N,H,W,C) batch
        at ``shape`` (the model's input resolution, or the staging
        resolution under KDLT_INGEST_DEVICE_RESIZE), through the
        decoded-uint8 cache.

        Cache keys are (content hash, resolved preprocess params): an
        identical image hits across models sharing a resolution/filter and
        across repeat requests, skipping decode+resize entirely.  Misses
        fan out on the GIL-released decode pool; a corrupt blob raises
        ValueError (-> 400, the client's error).
        """
        from kubernetes_deep_learning_tpu.serving import cache as cache_lib

        t0 = time.perf_counter()
        params = cache_lib.decoded_params(shape, resize_filter)
        keys = [cache_lib.decoded_key(b, params) for b in blobs]
        out: list = [self._decoded_cache.get(k) for k in keys]
        miss = [i for i, arr in enumerate(out) if arr is None]
        if miss:
            decoded = self._ingest_decoder.decode_batch(
                [blobs[i] for i in miss], shape[:2], filter=resize_filter,
            )
            for j, i in enumerate(miss):
                self._decoded_cache.put(keys[i], decoded[j])
                out[i] = decoded[j]
        images = np.stack(out)
        if self._m_ingest is not None:
            self._m_ingest["decoded_images"].inc(len(blobs))
            self._m_ingest["decode_seconds"].observe(time.perf_counter() - t0)
        return images

    def _predict_encoded(self, model, blobs: list[bytes], trace=None) -> np.ndarray:
        """Cross-host bytes shortcut: engines exposing predict_encoded_async
        (CrossHostEngine) get the wire's encoded blobs verbatim, so the
        fleet broadcast carries compact JPEG/PNG bytes instead of the
        padded uint8 tensor; decode happens once per process, fleet-wide
        deterministic.  Chunked to the bucket ladder like the serial
        engine path."""
        eng = model.engine
        max_b = eng.max_batch
        traces = (trace,) if trace is not None else ()
        outs = []
        for i in range(0, len(blobs), max_b):
            handle, n = eng.predict_encoded_async(blobs[i : i + max_b], traces=traces)
            outs.append(np.asarray(handle)[:n])
        if self._m_ingest is not None:
            self._m_ingest["decoded_images"].inc(len(blobs))
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def _predict_staged(self, model, images: np.ndarray) -> np.ndarray:
        """Device-resize staging dispatch (KDLT_INGEST_DEVICE_RESIZE):
        staging-resolution uint8 batches go straight to the engine's fused
        resize+forward program -- the batcher/scheduler lanes carry
        input_shape tensors only, so this opt-in path bypasses them
        (chunked to the bucket ladder, serial like the fallback path)."""
        eng = model.engine
        max_b = eng.max_batch
        outs = []
        for i in range(0, images.shape[0], max_b):
            handle, n = eng.predict_ingest_async(images[i : i + max_b])
            outs.append(np.asarray(handle)[:n])
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    # --- HTTP plumbing -----------------------------------------------------

    def _make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # TCP_NODELAY: same two-send() response stall as the gateway
            # handler (see its comment) -- without it a pooled upstream
            # connection can eat a ~40 ms delayed-ACK pause per response.
            disable_nagle_algorithm = True

            def log_message(self, fmt, *args):  # quiet; metrics cover it
                pass

            def _send(
                self, code: int, body: bytes, ctype: str = "application/json",
                headers: dict[str, str] | None = None,
            ):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                if self.close_connection:
                    # Make the closure explicit so a pooling client
                    # (the gateway's requests.Session) retires the
                    # connection instead of reusing a dead socket.
                    self.send_header("Connection", "close")
                if getattr(self, "_rid", ""):
                    self.send_header(REQUEST_ID_HEADER, self._rid)
                    # Server-Timing-style span summary for THIS tier: the
                    # spans recorded so far (admission, decode, batcher
                    # queue, pipeline stages -- all finish before the
                    # response is sent; only the root request span, which
                    # by definition closes after the send, is absent).
                    summary = server.tracer.summary(self._rid)
                    if summary:
                        self.send_header(TRACE_HEADER, summary)
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _send_json(self, code: int, obj, headers=None):
                self._send(code, json.dumps(obj).encode(), headers=headers)

            def _send_stream(
                self, code: int, chunks, ctype: str,
                headers: dict[str, str] | None = None,
            ) -> bool:
                """Stream an iterator of byte chunks as one chunked-transfer
                response (the SSE token path).  _send always sets
                Content-Length, which a live stream cannot know; here the
                HTTP/1.1 chunked framing is written by hand -- hex size,
                CRLF, payload, CRLF, with a zero-length terminator -- and
                every chunk is flushed so tokens reach the client as they
                decode, not when the generation ends.  Returns False if the
                client went away mid-stream (the caller closes the
                iterator, which cancels the generation)."""
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Transfer-Encoding", "chunked")
                if getattr(self, "_rid", ""):
                    self.send_header(REQUEST_ID_HEADER, self._rid)
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                try:
                    for chunk in chunks:
                        if not chunk:
                            continue
                        self.wfile.write(
                            f"{len(chunk):X}\r\n".encode() + chunk + b"\r\n"
                        )
                        self.wfile.flush()
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()
                    return True
                except OSError:
                    # Client disconnect mid-stream: stop the generation
                    # (the iterator's close -> GeneratorExit -> cancel) and
                    # retire the connection.
                    self.close_connection = True
                    return False
                finally:
                    closer = getattr(chunks, "close", None)
                    if closer is not None:
                        closer()

            # Bodies at most this size are drained (not closed over) when a
            # response goes out before the body was read: sheds happen
            # under overload, exactly when the gateway's pooled keep-alive
            # connections are most valuable.
            _DRAIN_LIMIT = 1 << 20

            def _discard_body(self):
                """Settle an unread request body before connection reuse.

                A response sent before the body is read (shed, 404) leaves
                the payload in the socket; the keep-alive handler loop
                would parse it as the next request line, desyncing the
                gateway's pooled connection and failing innocent follow-on
                requests with garbage 400s.  Drain small bodies to keep
                the connection poolable; close on large or unsized ones.
                """
                if getattr(self, "_body_consumed", True):
                    return
                self._body_consumed = True
                if "chunked" in self.headers.get("Transfer-Encoding", "").lower():
                    self.close_connection = True
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0) or 0)
                except (TypeError, ValueError):
                    length = -1
                if not 0 <= length <= self._DRAIN_LIMIT:
                    self.close_connection = True
                    return
                try:
                    while length > 0:
                        chunk = self.rfile.read(min(length, 65536))
                        if not chunk:
                            self.close_connection = True
                            return
                        length -= len(chunk)
                except OSError:
                    self.close_connection = True

            def do_GET(self):
                self._rid = ""  # keep-alive: never echo a previous POST's id
                if self.path == "/healthz":
                    if server.stalled:
                        # A stalled dispatch pipeline is unrecoverable
                        # in-process: fail liveness so the orchestrator
                        # restarts the pod (the watchdog already failed
                        # the stranded waiters retryably).
                        return self._send(503, b"dispatch stalled", "text/plain")
                    return self._send(200, b"ok", "text/plain")
                if self.path == "/readyz":
                    if server.admission.draining:
                        # Drain flips readiness FIRST: the Service endpoint
                        # pool stops routing here while in-flight batches
                        # complete (the gateway has the same semantics).
                        return self._send(503, b"draining", "text/plain")
                    if server.stalled:
                        # Readiness too: the Service endpoint pool drops
                        # this pod faster than the liveness restart lands.
                        return self._send(503, b"dispatch stalled", "text/plain")
                    if server.ready:
                        # A replica whose fused path failed to compile still
                        # serves (on the exact graph), but never as a plain
                        # "ready": the body names the degrade.
                        body = b"ready fast_degraded" if server.fast_degraded else b"ready"
                        return self._send(200, body, "text/plain")
                    return self._send(503, b"warming up", "text/plain")
                if self.path == "/metrics":
                    # Pull-model freshness: the SLO window gauges are
                    # recomputed at scrape time, not on a timer.
                    server.slo.refresh()
                    return self._send(200, server.registry.render().encode(), "text/plain")
                if self.path == "/debug/slo":
                    payload = server.slo.debug_payload()
                    if server.generate is not None:
                        # Per-token view alongside the per-request windows:
                        # TTFT/TPOT percentiles, budgets, occupancy --
                        # what kdlt-client --stats renders as its decode
                        # columns.
                        payload["decode"] = server.generate.debug_payload()
                    return self._send_json(200, payload)
                if self.path in ("/debug", "/debug/"):
                    # The debug INDEX: every debug surface this tier
                    # serves, one line each (operators should not have to
                    # memorize the route list).
                    return self._send_json(200, server.debug_index())
                if self.path in ("/debug/incidents", "/debug/incidents/"):
                    return self._send_json(
                        200, server.recorder.debug_payload()
                    )
                if self.path.startswith("/debug/incidents/"):
                    bundle_id = self.path.rsplit("/", 1)[-1]
                    bundle = server.recorder.get(bundle_id)
                    if bundle is None:
                        return self._send_json(
                            404,
                            {"error": f"no incident bundle {bundle_id!r}"},
                        )
                    return self._send_json(200, bundle)
                if self.path.startswith("/debug/trace/"):
                    rid = ensure_request_id(self.path.rsplit("/", 1)[-1])
                    info = server.tracer.trace_info(rid)
                    if info is None:
                        # Ring accounting on the 404: "evicted" and "never
                        # instrumented" are different debugging paths.
                        return self._send_json(
                            404, {"error": f"no trace for {rid!r} (evicted "
                                  "from the ring buffer or never seen)",
                                  "ring": server.tracer.stats()}
                        )
                    return self._send_json(
                        200,
                        {"trace_id": rid, "tier": "model-server", **info},
                    )
                if self.path.split("?", 1)[0] == "/debug/profile":
                    # GET /debug/profile?seconds=N: the curl-friendly form
                    # of the POST endpoint below (same capture, same lock).
                    return self._profile()
                if self.path == "/v1/models":
                    # The registry's multi-model status page: per model
                    # {version, ready, artifact_hash, buckets, family,
                    # labels} -- version/ready keep the original contract.
                    return self._send_json(200, server.models_status())
                m = _STATUS_RE.match(self.path)
                if m:
                    status = server.models_status().get(m.group(1))
                    if status is None:
                        return self._send_json(
                            404, {"error": f"no model {m.group(1)!r}"}
                        )
                    return self._send_json(200, status)
                m = _MODEL_RE.match(self.path)
                if m:
                    model = server.models.get(m.group(1))
                    if model is None:
                        return self._send_json(404, {"error": f"no model {m.group(1)!r}"})
                    from kubernetes_deep_learning_tpu.serving import protocol

                    # Spec discovery doubles as the ingest negotiation
                    # (GUIDE 10q): the header's presence is the
                    # capability; an old server simply never sends it and
                    # a new gateway stays on the tensor wire.
                    ingest_headers = (
                        {protocol.INGEST_HEADER: protocol.INGEST_BYTES_CAP}
                        if server._ingest_enabled
                        else None
                    )
                    return self._send(
                        200, model.artifact.spec.to_json().encode(),
                        "application/json", headers=ingest_headers,
                    )
                self._send_json(404, {"error": "not found"})

            def do_POST(self):
                from kubernetes_deep_learning_tpu.serving import protocol

                self._rid = ""  # keep-alive: never echo a previous request's id
                if self.path == "/debug/profile":
                    return self._profile()
                t0 = time.perf_counter()
                # The traced id from the gateway (or minted here for direct
                # clients): echoed in the response and stamped on this tier's
                # log line, completing the cross-tier trace.  The gateway's
                # upstream-attempt span id arrives in X-Kdlt-Parent-Span, so
                # this tier's root span nests under the exact attempt
                # (primary, failover, or hedge) that carried the request.
                rid = ensure_request_id(self.headers.get(REQUEST_ID_HEADER))
                self._rid = rid
                parent = ensure_span_id(self.headers.get(PARENT_SPAN_HEADER))
                rt = server.tracer.request_trace(rid, parent)
                w_start = trace_lib.now_s()
                status = 500
                batch = 0
                self._body_consumed = False
                server._m_requests.inc()
                g = _GENERATE_RE.match(self.path)
                if g is not None:
                    return self._generate(g.group(1), rid, parent, rt, w_start, t0)
                m = _PREDICT_RE.match(self.path)
                if not m:
                    server._m_errors.inc()
                    self._discard_body()
                    return self._send_json(404, {"error": "not found"})
                model = server.models.get(m.group(1))
                if model is None:
                    server._m_errors.inc()
                    self._discard_body()
                    return self._send_json(404, {"error": f"no model {m.group(1)!r}"})
                # Per-model request count (bounded `model` label, minted
                # centrally): only REGISTERED model names reach here, so
                # the label's value set is the registry's scan, not client
                # input.
                metrics_lib.model_request_counter(
                    server.registry, m.group(1)
                ).inc()
                # The propagated deadline budget (gateway or deadline-aware
                # client); parsed only when admission is on so the disabled
                # posture is exactly the legacy fixed-timeout behavior.
                deadline = (
                    Deadline.from_header(self.headers.get(DEADLINE_HEADER))
                    if server.admission.enabled
                    else None
                )
                # Priority class (gateway-propagated or direct-client):
                # bounded header values, unknown/absent -> interactive.
                priority = protocol.parse_priority(
                    self.headers.get(protocol.PRIORITY_HEADER)
                )
                ticket = None
                try:
                    # Admission BEFORE the body is read or decoded: an
                    # exhausted or shed request must cost no decode work and
                    # never touch the TPU.
                    with rt.span(trace_lib.SPAN_SERVER_ADMISSION):
                        ticket = server.admission.admit(
                            deadline, model=m.group(1), priority=priority
                        )
                    if server._faults is not None:
                        # server.predict fault point: error/latency/hang/
                        # disconnect strike the handler here (admitted, body
                        # unread); corrupt applies to the response below.
                        server._faults.fire("server.predict")
                    length = int(self.headers.get("Content-Length", 0))
                    spec = model.artifact.spec
                    # Enforce the byte bound BEFORE reading/decoding: a cap
                    # checked after np-materializing the body would not bound
                    # memory at all.  Sized for the production wire (msgpack
                    # uint8, ~1 byte/pixel) with 8x headroom for debug JSON;
                    # verbose float JSON (~10-20 chars/pixel) hits this byte
                    # bound before the image-count cap below -- intended,
                    # since memory protection is the primary goal here.
                    limit = (
                        MAX_IMAGES_PER_REQUEST * int(np.prod(spec.input_shape)) * 8
                        + 1_048_576
                    )
                    if length > limit:
                        # The unread body is still in the socket; a
                        # keep-alive handler loop would parse it as the next
                        # request line.  Close instead of draining gigabytes.
                        self.close_connection = True
                        raise ValueError(
                            f"request body {length} bytes exceeds the "
                            f"{limit}-byte limit "
                            f"({MAX_IMAGES_PER_REQUEST}-image cap)"
                        )
                    with rt.span(trace_lib.SPAN_SERVER_DECODE, bytes=length) as dt:
                        with dt.span(trace_lib.SPAN_SERVER_READ_BODY):
                            body = self.rfile.read(length)
                        self._body_consumed = True
                        ctype = self.headers.get("Content-Type", "")
                        encoded_wire = (
                            ctype.split(";")[0].strip()
                            == protocol.BYTES_CONTENT_TYPE
                        )
                        # Raw-bytes ingest wire (GUIDE 10q): the payload is
                        # the packed encoded JPEG/PNG blobs; pixel decode
                        # happens below, at the model tier, on the
                        # GIL-released pool (through the decoded-uint8
                        # cache), instead of at the gateway fan-in.  A
                        # disabled server 400s -- the gateway's negotiation
                        # normally prevents this, and on a
                        # stale-negotiation race it decodes and resends on
                        # the tensor wire.
                        if encoded_wire and not server._ingest_enabled:
                            raise ValueError(
                                "raw-bytes ingest is disabled on this "
                                f"server (set {protocol.INGEST_ENV}=1 or "
                                "use the tensor wire)"
                            )
                        with dt.span(trace_lib.SPAN_SERVER_UNPACK) as ut:
                            if encoded_wire:
                                blobs = protocol.decode_bytes_predict_request(
                                    body, max_images=MAX_IMAGES_PER_REQUEST
                                )
                            elif ctype.startswith(protocol.MSGPACK_CONTENT_TYPE):
                                # The pixels stay where rfile.read put
                                # them: `images` is a view of `body`, which
                                # it owns from here on (never pooled -- the
                                # runtime stages from it after
                                # predict_async has returned).
                                images, zero_copy = (
                                    protocol.decode_msgpack_tensor(body)
                                )
                                ut.tags["zero_copy"] = zero_copy
                                server._m_unpack[
                                    "view" if zero_copy else "copy"
                                ].inc()
                            else:
                                images = protocol.decode_predict_request(
                                    body, ctype
                                )
                    if encoded_wire:
                        batch = len(blobs)
                        src_shape = tuple(
                            getattr(
                                model.engine, "ingest_source_shape",
                                spec.input_shape,
                            )
                        )
                        if hasattr(model.engine, "predict_encoded_async"):
                            # Cross-host: blobs ride the fleet broadcast
                            # verbatim; decode is inside the engine round.
                            with rt.span(
                                trace_lib.SPAN_SERVER_PREDICT, batch=batch
                            ) as pt:
                                logits = server._predict_encoded(
                                    model, blobs, trace=pt
                                )
                            images = None
                        elif src_shape != tuple(spec.input_shape):
                            # Device-resize staging: decode stops at the
                            # staging resolution; the engine's fused
                            # program resizes on device ahead of the
                            # forward.
                            with rt.span(
                                trace_lib.SPAN_SERVER_INGEST_DECODE,
                                images=batch, bytes=length,
                            ):
                                staged = server._decode_blobs(
                                    src_shape, spec.resize_filter, blobs
                                )
                            with rt.span(
                                trace_lib.SPAN_SERVER_PREDICT, batch=batch
                            ):
                                logits = server._predict_staged(model, staged)
                            images = None
                        else:
                            with rt.span(
                                trace_lib.SPAN_SERVER_INGEST_DECODE,
                                images=batch, bytes=length,
                            ):
                                images = server._decode_blobs(
                                    spec.input_shape, spec.resize_filter, blobs
                                )
                    if images is not None:
                        if images.ndim == 3:
                            images = images[None]
                        if images.shape[1:] != spec.input_shape:
                            raise ValueError(
                                f"input shape {images.shape[1:]} != {spec.input_shape}"
                            )
                        if images.shape[0] > MAX_IMAGES_PER_REQUEST:
                            raise ValueError(
                                f"batch {images.shape[0]} exceeds the "
                                f"{MAX_IMAGES_PER_REQUEST}-image request limit"
                            )
                        batch = images.shape[0]
                        with rt.span(trace_lib.SPAN_SERVER_PREDICT, batch=batch) as pt:
                            logits = model.predict(
                                images, deadline=deadline, trace=pt,
                                priority=priority,
                            )
                    with rt.span(trace_lib.SPAN_SERVER_RESPOND):
                        out, out_ctype = protocol.encode_predict_response(
                            logits, spec.labels, ctype
                        )
                        if server._faults is not None:
                            out = server._faults.corrupt("server.predict", out)
                        status = 200
                        # The serving artifact's sha256 identity rides every
                        # success: the gateway's response cache keys
                        # validity on it (a reload with changed bytes
                        # changes the hash and drops that model's entries; a
                        # byte-identical version bump keeps them).
                        ah = getattr(model, "artifact_hash", None)
                        self._send(
                            200, out, out_ctype,
                            headers=(
                                {protocol.ARTIFACT_HASH_HEADER: ah}
                                if ah else None
                            ),
                        )
                except faults_lib.InjectedDisconnect:
                    # Injected abrupt connection loss: no response bytes at
                    # all -- the client sees the socket die mid-request,
                    # exactly like a crashed replica.
                    server._m_errors.inc()
                    status = -1
                    self.close_connection = True
                except Shed as e:  # admission refusal, not a fault
                    server._m_errors.inc()
                    status = e.http_status
                    # admit() sheds BEFORE the body is read: settle it now
                    # so the response can announce Connection: close when
                    # the body was too large to drain.
                    self._discard_body()
                    self._send_json(
                        status,
                        {"error": str(e), "shed_reason": e.reason},
                        headers=e.headers(),
                    )
                except ValueError as e:  # malformed request
                    server._m_errors.inc()
                    status = 400
                    self._send_json(400, {"error": str(e)})
                except DispatchStall as e:
                    # The engine watchdog declared the dispatch pipeline
                    # stuck: retryable for the CLIENT (another replica can
                    # serve it; the gateway's pool fails over on the 503),
                    # terminal for this pod (/healthz is already failing).
                    # The X-Kdlt-Stalled header distinguishes this from an
                    # overload 503: the gateway's pool takes the replica
                    # out of rotation on the FIRST observation.
                    server._m_errors.inc()
                    status = 503
                    # Flight recorder: the stall edge, with the causal
                    # request pinned.  The recorder's dedup window folds
                    # the storm of per-request DispatchStall responses a
                    # wedged pipeline produces into ONE bundle.
                    server.recorder.record(
                        "dispatch.stall", rid=rid, model=m.group(1),
                    )
                    self._send_json(
                        503,
                        {"error": f"dispatch stalled: {e}"},
                        headers={
                            **retry_after_headers(1.0),
                            protocol.STALLED_HEADER: "1",
                        },
                    )
                except (QueueFull, FuturesTimeout) as e:  # transient overload
                    server._m_errors.inc()
                    status = 503
                    if ticket is not None:
                        # AIMD congestion signal: an ADMITTED request still
                        # missed its budget / found the batcher full, so the
                        # concurrency limit is too high for current service
                        # times.
                        ticket.mark_overloaded()
                    self._send_json(
                        503,
                        {"error": f"overloaded: {e or 'timed out'}"},
                        # Live, jittered backoff hint (queue depth x hold
                        # time), so the shed cohort cannot return as one
                        # synchronized retry storm.
                        headers=retry_after_headers(
                            server.admission.retry_after_s()
                        ),
                    )
                except Exception as e:  # internal failure
                    server._m_errors.inc()
                    status = 500
                    self._send_json(500, {"error": str(e)})
                finally:
                    # Covers every pre-body-read error response (the Shed
                    # path foremost: admit() runs before the read); no-op
                    # once the body was consumed.
                    self._discard_body()
                    if ticket is not None:
                        ticket.release()
                    dt = time.perf_counter() - t0
                    # "slow" for trace retention = past the tier's own p99,
                    # judged against the distribution BEFORE this sample and
                    # only once it is meaningful.
                    slow = (
                        server._m_latency.count >= 100
                        and dt >= server._m_latency.percentile(0.99)
                    )
                    server._m_latency.observe(
                        dt,
                        exemplar=(
                            rid if metrics_lib.exemplars_enabled() else None
                        ),
                    )
                    deadline_exceeded = (
                        deadline is not None and deadline.expired
                    )
                    # SLO accounting at the same boundary as the latency
                    # histogram, so /debug/slo reconciles against /metrics.
                    server.slo.record(
                        m.group(1), status, dt,
                        deadline_exceeded=deadline_exceeded,
                    )
                    # Root span last: it closes after the response went out,
                    # which is why the X-Kdlt-Trace header carries only the
                    # sub-spans while /debug/trace/<rid> has everything.
                    server.tracer.record(
                        rid, trace_lib.SPAN_SERVER_REQUEST, w_start,
                        trace_lib.now_s() - w_start,
                        parent_id=parent, span_id=rt.span_id,
                        status=status, batch=batch,
                    )
                    # Tail-based retention: errors/sheds/deadline misses/
                    # slowest-percentile traces outlive routine ones.
                    server.tracer.classify(
                        rid,
                        trace_lib.retention_class(
                            status, deadline_exceeded, slow
                        ),
                    )
                    # Sheds (503/504) are excluded from the always-log rule:
                    # rejection must stay cheap under overload (a log line
                    # per shed IS load), and kdlt_admission_shed_total
                    # already counts them.  request_log=True still logs all.
                    if server.request_log or (
                        status >= 500 and status not in (503, 504)
                    ):
                        log_request(
                            "model-server predict",
                            rid,
                            status=status,
                            t0=t0,
                            span_id=rt.span_id,
                            model=m.group(1),
                            batch=batch,
                        )

            def _generate(self, name, rid, parent, rt, w_start, t0):
                """POST /v1/models/<name>:generate -- the generative lane.

                Same front door as :predict (admission before the body is
                read, priority-aware shed, deadline propagation), different
                back half: a 200 with ``stream`` is a chunked
                text/event-stream of per-token SSE frames, written as the
                decode loop emits them.  The lane does its own SLO
                accounting at generation end (per-token budgets decide
                deadline_exceeded), so this handler records SLO only for
                requests the lane never saw (sheds, internal errors).
                """
                from kubernetes_deep_learning_tpu.serving import (
                    generate as generate_lib,
                )
                from kubernetes_deep_learning_tpu.serving import protocol

                lane = server.generate
                status = 500
                if lane is None:
                    server._m_errors.inc()
                    self._discard_body()
                    return self._send_json(
                        404,
                        {"error": "generative lane disabled (start the "
                         "server with --decode or KDLT_DECODE=1)"},
                    )
                if name != lane.model:
                    server._m_errors.inc()
                    self._discard_body()
                    return self._send_json(
                        404, {"error": f"no generative model {name!r}"}
                    )
                metrics_lib.model_request_counter(
                    server.registry, name
                ).inc()
                # A stream with no stated deadline has none: the default
                # budget is a closed request's (20 s), and a generation's
                # length is bounded by max_new_tokens, its pace held by the
                # per-token budgets (TTFT/TPOT).  An explicit header is
                # honoured, mid-stream expiry included.
                raw_deadline = self.headers.get(DEADLINE_HEADER)
                deadline = (
                    Deadline.from_header(raw_deadline)
                    if server.admission.enabled and raw_deadline
                    else None
                )
                priority = protocol.parse_priority(
                    self.headers.get(protocol.PRIORITY_HEADER)
                )
                ticket = None
                lane_recorded = False
                try:
                    with rt.span(trace_lib.SPAN_SERVER_ADMISSION):
                        ticket = server.admission.admit(
                            deadline, model=name, priority=priority
                        )
                    length = int(self.headers.get("Content-Length", 0) or 0)
                    if length > generate_lib.MAX_GENERATE_BODY_BYTES:
                        self.close_connection = True
                        raise ValueError(
                            f"generate body {length} bytes exceeds the "
                            f"{generate_lib.MAX_GENERATE_BODY_BYTES}-byte limit"
                        )
                    with rt.span(trace_lib.SPAN_SERVER_DECODE, bytes=length):
                        body = self.rfile.read(length)
                        self._body_consumed = True
                    status, payload, ctype, extra = lane.handle_generate(
                        body, rid=rid, deadline=deadline, priority=priority
                    )
                    lane_recorded = True  # the lane owns SLO from here on
                    if status != 200:
                        server._m_errors.inc()
                    if (
                        status == 200
                        and ctype == protocol.EVENT_STREAM_CONTENT_TYPE
                    ):
                        # The admission ticket is held for the STREAM's
                        # lifetime (released in the finally): an active
                        # generation is exactly the concurrency the
                        # limiter should be counting.
                        self._send_stream(200, payload, ctype, headers=extra)
                    else:
                        self._send(status, payload, ctype, headers=extra or None)
                except Shed as e:  # admission refusal, not a fault
                    server._m_errors.inc()
                    status = e.http_status
                    self._discard_body()
                    self._send_json(
                        status,
                        {"error": str(e), "shed_reason": e.reason},
                        headers=e.headers(),
                    )
                except ValueError as e:  # malformed request
                    server._m_errors.inc()
                    status = 400
                    self._send_json(400, {"error": str(e)})
                except Exception as e:  # internal failure
                    server._m_errors.inc()
                    status = 500
                    self._send_json(500, {"error": str(e)})
                finally:
                    self._discard_body()
                    if ticket is not None:
                        ticket.release()
                    dt = time.perf_counter() - t0
                    server._m_latency.observe(
                        dt,
                        exemplar=(
                            rid if metrics_lib.exemplars_enabled() else None
                        ),
                    )
                    if not lane_recorded:
                        server.slo.record(
                            lane.model, status, dt, deadline_exceeded=False
                        )
                    deadline_exceeded = (
                        deadline is not None and deadline.expired
                    )
                    server.tracer.record(
                        rid, trace_lib.SPAN_SERVER_GENERATE, w_start,
                        trace_lib.now_s() - w_start,
                        parent_id=parent, span_id=rt.span_id, status=status,
                    )
                    server.tracer.classify(
                        rid,
                        trace_lib.retention_class(
                            status, deadline_exceeded, False
                        ),
                    )
                    if server.request_log or (
                        status >= 500 and status not in (503, 504)
                    ):
                        log_request(
                            "model-server generate",
                            rid,
                            status=status,
                            t0=t0,
                            span_id=rt.span_id,
                            model=name,
                        )

            def _profile(self):
                """Capture a jax.profiler trace while live traffic runs.

                Blocks the calling client for ``seconds``; serving continues
                on the other handler threads, which is the point -- the
                trace shows real request execution on the device, with this
                tier's live spans beside it (ModelServer._capture_profile).
                """
                if self.command == "GET":
                    # GET /debug/profile?audit=buckets: the bucket-shape
                    # audit (padding waste + FLOPs/img) -- pure host-side
                    # bookkeeping, served even where device profiling is
                    # disabled.  ?seconds=N is the curl-friendly capture.
                    from urllib.parse import parse_qs, urlparse

                    q = parse_qs(urlparse(self.path).query)
                    if q.get("audit", [""])[0] == "buckets":
                        return self._send_json(200, server.bucket_audit())
                if server._profile_base is None:
                    return self._send_json(404, {"error": "profiling disabled"})
                try:
                    if self.command == "GET":
                        seconds = float(q.get("seconds", ["2.0"])[0])
                        annotations = q.get("annotations", ["0"])[0] == "1"
                    else:
                        length = int(self.headers.get("Content-Length", 0))
                        req = json.loads(self.rfile.read(length)) if length else {}
                        if not isinstance(req, dict):
                            raise ValueError("body must be a JSON object")
                        seconds = float(req.get("seconds", 2.0))
                        annotations = req.get("annotations") is True
                    if not 0 < seconds <= 60:
                        raise ValueError("seconds must be in (0, 60]")
                except (ValueError, TypeError, json.JSONDecodeError) as e:
                    return self._send_json(400, {"error": str(e)})
                try:
                    reply = server._capture_profile(
                        seconds, "kdlt-trace-", annotations
                    )
                except Exception as e:
                    return self._send_json(500, {"error": str(e)})
                if reply is None:
                    return self._send_json(
                        409, {"error": "a profile capture is already running"}
                    )
                self._send_json(200, reply)

        return Handler

    def start(self, block: bool = False) -> None:
        self._serving = True
        if block:
            self._httpd.serve_forever()
        else:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever, name="kdlt-model-server", daemon=True
            )
            self._thread.start()

    def begin_drain(self) -> None:
        """Graceful-drain entry: /readyz goes 503, new predicts shed with
        reason "draining", in-flight batches run to completion (observable
        via admission.wait_idle).  The CLI wires SIGTERM here."""
        self.admission.begin_drain()

    def debug_index(self) -> dict:
        """GET /debug/: this tier's debug routes, one line each."""
        return {
            "tier": "model-server",
            "routes": {
                "/debug/slo": "per-model goodput and burn-rate windows "
                "as this replica observed them (plus the decode lane's "
                "per-token TTFT/TPOT view when --decode is on)",
                "/debug/incidents": "flight-recorder bundles captured on "
                "this replica",
                "/debug/incidents/<id>": "one full incident bundle "
                "(timeline, pinned traces, snapshots, metrics delta)",
                "/debug/trace/<rid>": "this tier's span waterfall for "
                "one request id",
                "/debug/profile?seconds=N": "capture a jax.profiler trace "
                "under KDLT_PROFILE_DIR: the device's planes, Python and "
                "host tracers off; &annotations=1 adds this tier's live "
                "spans as host annotations (costly under tensor traffic)",
                "/debug/profile?audit=buckets": "per-model bucket-shape "
                "audit: padding-waste ratio + compiled FLOPs/img per bucket",
            },
        }

    def bucket_audit(self) -> dict:
        """GET /debug/profile?audit=buckets: every served model's per-bucket
        padding-waste + FLOPs audit (runtime.engine.bucket_audit)."""
        models = {}
        for name, served in self.model_registry.models.items():
            audit_fn = getattr(served.engine, "bucket_audit", None)
            if callable(audit_fn):
                models[name] = audit_fn()
        return {"tier": "model-server", "models": models}

    def _capture_profile(self, seconds: float, prefix: str,
                         annotations: bool = False) -> dict | None:
        """One profiler capture of ``seconds`` into a fresh directory under
        the profile base; None when another capture holds the lock.

        The Python tracer is always off: with it the profiler stalled the
        device ~2 s at the start and wrote 200 MB for 3 s (PERF.md, PR 23).
        The host tracer is off unless ``annotations``: level 1 is the
        lowest that records this tier's TraceAnnotations (the live spans,
        the dispatcher's stages), but no level separates them from the
        runtime's own level-1 events, and under tensor traffic the
        runtime's input re-tiling alone emits ~1 M of those a second -- a
        2 s capture wrote 75 MB and stopped the process for a minute while
        it was written (PERF.md, PR 24).  So the default is the device's
        planes alone, which costs nothing while it runs, and annotations
        are asked for where the bodies are small or the stall is
        acceptable.  Client input never chooses the path: an arbitrary
        directory would let any in-cluster client write into e.g. the
        artifact root the version watcher scans.
        """
        import tempfile

        import jax

        if not self._profile_lock.acquire(blocking=False):
            return None
        try:
            os.makedirs(self._profile_base, exist_ok=True)
            trace_dir = tempfile.mkdtemp(prefix=prefix, dir=self._profile_base)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1 if annotations else 0
            t0 = time.perf_counter()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            t1 = time.perf_counter()
            try:
                time.sleep(seconds)
            finally:
                t2 = time.perf_counter()
                jax.profiler.stop_trace()
            return {
                "trace_dir": trace_dir, "seconds": seconds,
                "annotations": annotations,
                "start_took_s": t1 - t0,
                "stop_took_s": time.perf_counter() - t2,
            }
        finally:
            self._profile_lock.release()

    def _incident_profile(self, seconds: float) -> dict:
        """Flight-recorder profile hook (KDLT_INCIDENT_PROFILE_S > 0): the
        same capture as /debug/profile, same lock -- a concurrent operator
        capture wins and the bundle notes the skip instead of waiting."""
        if self._profile_base is None:
            return {"skipped": "profiling disabled"}
        return self._capture_profile(seconds, "kdlt-incident-") or {
            "skipped": "a profile capture is already running"
        }

    def shutdown(self) -> None:
        try:
            self._watcher_stop.set()
            self._compile_watch.close()
            if self.generate is not None:
                self.generate.close()
            self.recorder.close()
            if self._watcher is not None:
                self._watcher.join(timeout=5)
            # BaseServer.shutdown() blocks on serve_forever's exit event;
            # only call it if serve_forever actually ran (a constructed-but-
            # never-started server is a legitimate lifecycle, e.g. load-only
            # tooling).
            if getattr(self, "_serving", False):
                self._httpd.shutdown()
            self._httpd.server_close()
            for m in self.models.values():
                m.close(drain=False)
            if self.scheduler is not None:
                self.scheduler.close(drain=False)
        finally:
            self._shutdown_done.set()

    def wait_shutdown(self, timeout: float = 60.0) -> bool:
        """Block until a shutdown() running on another thread has finished.

        ``start(block=True)`` returns the moment shutdown() stops the
        listener, while the thread that called it (the SIGTERM drain
        thread) is still closing dispatchers and engines.  A main thread
        that returned right away would finalize the interpreter under it;
        on the TPU that killed the process with SIGABRT mid-teardown."""
        return self._shutdown_done.wait(timeout)


def _serve_cross_host(args) -> int:
    """--cross-host: leader serves HTTP, followers run the lockstep loop."""
    import jax

    from kubernetes_deep_learning_tpu.parallel.crosshost import (
        CrossHostEngine,
        CrossHostForward,
    )
    from kubernetes_deep_learning_tpu.parallel.mesh import make_mesh

    n = args.data_parallel or len(jax.devices())
    if n != len(jax.devices()):
        # ADVICE r2: the lockstep shard math requires every process of the
        # runtime to own mesh devices; a sub-mesh would leave processes
        # with no shard (or unequal blocks) and mis-drive the broadcast.
        raise SystemExit(
            f"--cross-host requires the mesh to cover all {len(jax.devices())} "
            f"global devices (got --data-parallel {n}); scale by adding hosts"
        )
    mesh = make_mesh(
        n,
        model_parallel=resolve_mesh_model_parallel(args.model_parallel),
        devices=jax.devices()[:n],
    )
    # Every process loads the same artifact (shared storage or identical
    # image) and builds the same CrossHostForward; only the leader binds
    # the HTTP socket.
    (name,) = _single_model_name(args.models)
    version = art.latest_version(args.models, name)
    artifact = art.load_artifact(art.version_dir(args.models, name, version))
    from kubernetes_deep_learning_tpu.parallel.crosshost import (
        artifact_variables_for_sharding,
    )

    # kdlt-quantize'd artifacts dequantize host-side before sharding (the
    # partition rules address float kernel leaves) -- same helper the
    # RELOAD path uses.
    variables = artifact_variables_for_sharding(artifact)
    xh = CrossHostForward(
        artifact.spec,
        mesh,
        variables,
        buckets=tuple(
            int(b) for b in str(args.cross_host_bucket).split(",")
        ),
        model_root=args.models,
        model_name=name,
        round_timeout_s=args.cross_host_round_timeout,
    )
    xh.version = version  # the booted version; reload() tracks from here
    # xh holds the (device-sharded) weights; drop the host-RAM copy before
    # ModelServer loads its own artifact (whose copy CrossHostEngine also
    # frees) -- large models must not sit in host memory twice for the
    # server's lifetime.
    del artifact, variables
    if jax.process_index() != 0:
        print(
            f"cross-host follower {jax.process_index()}/{jax.process_count()} "
            "entering lockstep loop"
        )
        rounds = xh.follower_loop()
        print(f"cross-host follower done after {rounds} rounds")
        return 0

    server = ModelServer(
        args.models,
        port=args.port,
        buckets=(xh.bucket,),
        use_batcher=not args.no_batching,
        batcher_impl=args.batcher,
        request_log=not args.no_request_log,
        engine_factory=lambda artifact, **kw: CrossHostEngine(artifact, xh, **kw),
    )
    server.warmup()
    # Fleet-wide hot reload: the standard version watcher drives it -- a
    # higher version dir makes poll_versions construct a fresh
    # CrossHostEngine, whose __init__ broadcasts RELOAD to the followers
    # (parallel.crosshost).  Round-2 limitation closed.
    server.start_version_watcher()
    print(
        f"cross-host model server on :{server.port} "
        f"({jax.process_count()} processes, {n} global devices, "
        f"buckets {xh.buckets})"
    )
    try:
        server.start(block=True)
    finally:
        xh.shutdown()
    return 0


def _single_model_name(model_root: str) -> tuple[str]:
    """Cross-host serving drives exactly one model; resolve its name.

    The error paths are explicit and actionable (a bare tuple-unpack
    failure at the call site told an operator nothing): an empty root and
    a multi-entry root are different mistakes with different fixes.  For
    multi-model roots, the standard (non-cross-host) server is the path --
    its ModelRegistry serves every model concurrently.
    """
    names = [
        n for n in sorted(os.listdir(model_root))
        if art.latest_version(model_root, n) is not None
    ]
    if not names:
        raise ValueError(
            f"--cross-host found no versioned model under {model_root!r} "
            "(expected <root>/<name>/<version>/ with an exported artifact)"
        )
    if len(names) > 1:
        raise ValueError(
            f"--cross-host serves exactly one model, but {model_root!r} "
            f"holds {len(names)}: {names}.  Either point --models at a "
            "single-model root, or drop --cross-host to serve them all "
            "from one process (the multi-model registry + scheduler path)"
        )
    return (names[0],)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description="TPU model server")
    p.add_argument("--models", required=True, help="artifact root (/models)")
    p.add_argument("--port", type=int, default=DEFAULT_PORT)
    p.add_argument("--buckets", default="1,2,4,8,16,32,64,128")
    p.add_argument("--max-delay-ms", type=float, default=2.0)
    p.add_argument(
        "--pipeline-depth",
        type=int,
        default=0,
        help="max batches in flight on the device (dispatch pipelining): "
        "batch N+1's host gather + H2D overlap batch N's execution.  "
        "0 = $KDLT_PIPELINE_DEPTH or the default 2; 1 = serial dispatch.  "
        "Depth > 2 buys nothing on one chip (one program executes at a "
        "time); it only queues latency",
    )
    p.add_argument("--no-batching", action="store_true")
    p.add_argument(
        "--batcher",
        default="auto",
        choices=["auto", "native", "python"],
        help="batching queue implementation (native = C++ batchqueue.cc)",
    )
    p.add_argument(
        "--data-parallel",
        type=int,
        default=0,
        help="serve over a mesh of this many LOCAL chips total (0 = one "
        "device); with --model-parallel M the mesh is (N/M data, M model), "
        "so the batch is sharded N/M ways",
    )
    p.add_argument(
        "--parallel-mode",
        default="data",
        choices=["data", "sequence"],
        help="with --data-parallel: shard the batch (data) or the token "
        "sequence via ring attention (sequence; vit families only)",
    )
    p.add_argument(
        "--model-parallel",
        type=int,
        default=0,
        help="devices per tensor-parallel group on the mesh's inner "
        "(fastest-ICI) axis; wide kernels shard their output dim per "
        "parallel.mesh's family rules.  0 = $KDLT_MESH_MODEL_PARALLEL or 1. "
        "With --data-parallel N the mesh is (N/M data, M model); with "
        "--data-parallel 0 and M > 1 the mesh spans all local devices",
    )
    p.add_argument(
        "--profile-dir",
        default="",
        help="base directory for /debug/profile traces (default: a kdlt-traces "
        "dir under the system temp dir)",
    )
    p.add_argument(
        "--no-profiling",
        action="store_true",
        help="disable the /debug/profile endpoint",
    )
    p.add_argument(
        "--grpc-port",
        type=int,
        default=-1,
        help="ALSO serve the TF-Serving-compatible gRPC PredictionService on "
        "this port (-1 = off, 0 = ephemeral; the reference's model tier is "
        "gRPC on 8500, reference tf-serving-clothing-model-service.yaml:9-10)",
    )
    p.add_argument(
        "--watch-interval",
        type=float,
        default=10.0,
        help="seconds between artifact-root scans for new versions (0 = off)",
    )
    p.add_argument(
        "--platform",
        default=None,
        help="the jax platform this server must run on (tpu, or cpu for "
        "dev); default $KDLT_PLATFORM, else whatever JAX finds.  When "
        "given, finding any other platform is fatal at start-up",
    )
    p.add_argument(
        "--no-request-log",
        action="store_true",
        help="disable the per-request traced log line (rid, model, batch, status)",
    )
    p.add_argument(
        "--cross-host",
        action="store_true",
        help="serve ONE model sharded across every process of the "
        "multi-host runtime (requires the KDLT_COORDINATOR env triplet or "
        "KDLT_MULTIHOST=1 on a TPU pod slice): process 0 runs the HTTP "
        "frontend and broadcasts each dispatch; the other processes run "
        "lockstep followers.  --data-parallel then counts GLOBAL devices.",
    )
    p.add_argument(
        "--cross-host-bucket",
        default="0",
        help="dispatch bucket ladder for --cross-host, comma-separated "
             "(each rounded up to the data-axis size; 0 = the axis size)",
    )
    p.add_argument(
        "--cross-host-round-timeout",
        type=float,
        default=300.0,
        help="leader watchdog: exit(70) for a gang restart if one lockstep "
             "round exceeds this many seconds (dead follower); 0 disables",
    )
    p.add_argument(
        "--sched-policy",
        default=None,
        choices=["weighted_deadline", "fifo"],
        help="cross-model arbitration policy for the unified scheduler "
        "(default $KDLT_SCHED_POLICY or weighted_deadline): "
        "weighted_deadline = earliest effective deadline with per-model "
        "weight floors; fifo = naive arrival order (the A/B baseline)",
    )
    p.add_argument(
        "--sched-weights",
        default=None,
        help='per-model scheduling weights, e.g. "clothing-model=2,vit=1" '
        "(default $KDLT_SCHED_WEIGHTS; unlisted models weigh 1.0)",
    )
    p.add_argument(
        "--no-admission",
        action="store_true",
        help="disable admission control (deadline rejection + AIMD "
        "concurrency limiting); graceful drain stays on",
    )
    p.add_argument(
        "--no-slo",
        action="store_true",
        help="disable the SLO engine (per-model goodput/burn-rate windows, "
        "kdlt_slo_* gauges, /debug/slo); default $KDLT_SLO or enabled",
    )
    p.add_argument(
        "--compile-cache-dir",
        default="",
        help="persistent XLA compilation-cache directory.  Order: "
        "$JAX_COMPILATION_CACHE_DIR, this flag, $KDLT_COMPILE_CACHE_DIR, "
        "<checkout>/.jax_cache.  A restart then re-reads prior compiles "
        "from disk in seconds instead of re-paying minutes of bucket warmup "
        "(the k8s deployment mounts a cache volume for exactly this)",
    )
    p.add_argument(
        "--decode",
        action="store_true",
        help="serve the generative lane (/v1/models/<m>:generate), beside "
        "the image models or alone: continuous-batching autoregressive "
        "decode over a block-paged cache with streamed text/event-stream "
        "token responses and per-token TTFT/TPOT SLOs; a body carries "
        "\"prompt\" or \"token_ids\", \"ignore_eos\", \"top_logits\".  "
        "Default $KDLT_DECODE=1; the model name is $KDLT_DECODE_MODEL "
        "(gen-default): a decoder artifact of that name under --models, "
        "else the byte-level toy; sizes from $KDLT_DECODE_SLOTS, "
        "_PAGE_SIZE, _MAX_PAGES, _PROMPT_BUCKETS",
    )
    p.add_argument(
        "--aot-warm",
        action="store_true",
        help="AOT-compile every model's FULL default bucket ladder into "
        "the persistent compile cache and EXIT (the kdlt-warm pass; run "
        "at image build or in an init container sharing the cache "
        "volume).  $KDLT_AOT_WARM=1 runs the same pass at boot and then "
        "serves -- either way a scaled pod's warmup is cache-hits only",
    )
    args = p.parse_args(argv)

    from kubernetes_deep_learning_tpu.utils.platform import (
        force_platform,
        require_platform,
    )

    requested_platform = force_platform(args.platform)

    from kubernetes_deep_learning_tpu.utils.compilecache import enable_compile_cache

    cache_path = enable_compile_cache(args.compile_cache_dir or None)
    print(f"persistent compile cache: {cache_path or 'off'}", file=sys.stderr)

    aot_warm_env = os.environ.get(AOT_WARM_ENV, "").strip().lower() in (
        "1", "true", "yes",
    )
    if args.aot_warm or aot_warm_env:
        from kubernetes_deep_learning_tpu.export.warm import warm_models

        report = warm_models(
            args.models, cache_dir=args.compile_cache_dir or None
        )
        failed = [n for n, m in report["models"].items() if "error" in m]
        if args.aot_warm:
            # Init-container / image-build mode: the pass IS the job.
            return 1 if failed or not report["models"] else 0
        # Boot mode (KDLT_AOT_WARM=1): the pass primed the cache for the
        # FULL ladder; fall through and serve -- this server's own warmup
        # (possibly over a trimmed --buckets) now hits that cache.

    from kubernetes_deep_learning_tpu.utils.distributed import initialize

    if initialize():
        import jax

        print(
            f"multi-host runtime: process {jax.process_index()} of "
            f"{jax.process_count()}, {len(jax.devices())} global devices"
        )

    # Name the device this process serves from; a platform that was asked
    # for and not found is fatal here, before any model loads.
    found = require_platform(requested_platform)
    print(
        f"serving on platform={found['platform']} "
        f"device_kind={found['device_kind']!r} "
        f"devices={found['device_count']} "
        f"(requested: {requested_platform or 'any'})",
        file=sys.stderr,
    )

    if args.cross_host:
        # One frontend, model sharded over every process: process 0 serves
        # HTTP and broadcasts dispatches; the rest run lockstep followers
        # (parallel.crosshost).  This is the cross-host mode the per-request
        # local-mesh path below deliberately does not attempt.
        return _serve_cross_host(args)

    mesh = None
    model_parallel = resolve_mesh_model_parallel(args.model_parallel)
    if args.data_parallel > 0 or model_parallel > 1:
        import jax

        from kubernetes_deep_learning_tpu.parallel.mesh import make_mesh

        # LOCAL devices only: without --cross-host the per-request HTTP
        # handler cannot drive a cross-host SPMD program (every process
        # must enter the same dispatch in lockstep).  Scaling across hosts
        # is replica scaling (the reference's mechanism) or --cross-host.
        # model_parallel > 1 without an explicit --data-parallel spans all
        # local devices (the deploy-env KDLT_MESH_MODEL_PARALLEL path).
        mesh = make_mesh(
            args.data_parallel or len(jax.local_devices()),
            model_parallel=model_parallel,
            devices=jax.local_devices(),
        )

    server = ModelServer(
        args.models,
        port=args.port,
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        max_delay_ms=args.max_delay_ms,
        use_batcher=not args.no_batching,
        batcher_impl=args.batcher,
        mesh=mesh,
        mesh_mode=args.parallel_mode,
        profile_base=None if args.no_profiling else args.profile_dir,
        request_log=not args.no_request_log,
        pipeline_depth=args.pipeline_depth or None,
        admission=False if args.no_admission else None,
        sched_policy=args.sched_policy,
        sched_weights=(
            None if args.sched_weights is None
            else resolve_weights(args.sched_weights)
        ),
        slo=False if args.no_slo else None,
        decode=True if args.decode else None,
    )
    # SIGTERM -> flip /readyz, stop admission, let in-flight batches finish,
    # then stop; fits inside the k8s terminationGracePeriodSeconds budget.
    install_sigterm_drain(server.admission, server.shutdown)
    server.warmup()
    if args.watch_interval > 0:
        server.start_version_watcher(args.watch_interval)
    grpc_server = None
    if args.grpc_port >= 0:
        from kubernetes_deep_learning_tpu.serving.grpc_predict import serve_grpc

        grpc_server, grpc_port = serve_grpc(server, args.grpc_port)
        print(f"gRPC PredictionService listening on :{grpc_port}")
    print(f"model server listening on :{server.port}")
    try:
        server.start(block=True)
    finally:
        if grpc_server is not None:
            grpc_server.stop(grace=5)
    server.wait_shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
