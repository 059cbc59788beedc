"""InferenceEngine: the in-tree replacement for TF-Serving's execution core.

Where the reference delegates model execution to the external
``tensorflow/serving:2.3.0`` C++ binary (reference tf-serving.dockerfile:1-5),
this engine executes the exported StableHLO module (or the in-tree flax model)
under jit on the local accelerator.

TPU-first design decisions:

- **Bucketed batch shapes.** Everything under jit compiles per concrete
  shape; serving arbitrary batch sizes naively would recompile constantly.
  Requests are padded up to a fixed bucket ladder (1, 2, 4, ..., max) and all
  buckets are compiled at startup ("warmup"), so steady-state serving never
  recompiles.  This is SURVEY.md section 7's hard part (b).
- **Normalization on device.** The engine takes uint8 batches straight off
  the wire; the scale/shift fuses into the first conv (see models.build_forward).
- **The batch crosses in the host's byte order.** Callers pass
  ``uint8[n, H, W, C]``; the compiled program's parameter is the WIRE FORM
  of its bucket, ``uint8[bucket, H, W*C]`` -- a reshape of the caller's
  memory (wire_form / to_wire / stage below) -- and the program's first
  operation splits the rows back into NHWC on the device.  A parameter of
  the NHWC shape itself gets a device layout far from the host's (batch on
  the lanes, or channel planes), and the runtime then transposes every
  batch pixel by pixel on the host's threads before the program may start;
  kdlt_engine_input_total{path} counts the batches whose wire form was a
  view against those that took a host copy first.
- **Pipelined dispatch, serialized enqueue.** predict() is thread-safe;
  only the ENQUEUE of a program is serialized by a lock (one accelerator
  executes one program at a time anyway, and JAX's async dispatch returns
  as soon as the execution is queued).  The host work around a batch --
  gather/pad, H2D transfer, result readback -- is what must NOT serialize
  against device execution: InFlightDispatcher below keeps a bounded
  number of batches in flight so batch N+1's host side overlaps batch N's
  device time, with readback on a dedicated completion thread.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Sequence

import numpy as np

from kubernetes_deep_learning_tpu.export.artifact import ModelArtifact
from kubernetes_deep_learning_tpu.runtime import flops as flops_lib
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from kubernetes_deep_learning_tpu.utils import trace as trace_lib

DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

PIPELINE_DEPTH_ENV = "KDLT_PIPELINE_DEPTH"
DEFAULT_PIPELINE_DEPTH = 2

# Engine watchdog (serving-path fault tolerance): an in-flight dispatch
# handle stuck beyond ``multiple`` x the bucket's expected latency (EWMA of
# observed completions; ``floor`` seconds until there are samples, and
# never below the floor) is declared stalled -- its future fails with the
# retryable DispatchStall, the dispatcher flips unhealthy (the model
# server's /healthz follows, so the orchestrator restarts the pod), and
# kdlt_dispatch_stall_total counts it.  KDLT_WATCHDOG=0 disables.
WATCHDOG_ENV = "KDLT_WATCHDOG"
WATCHDOG_MULTIPLE_ENV = "KDLT_WATCHDOG_MULTIPLE"
WATCHDOG_FLOOR_S_ENV = "KDLT_WATCHDOG_FLOOR_S"
DEFAULT_WATCHDOG_MULTIPLE = 10.0
DEFAULT_WATCHDOG_FLOOR_S = 30.0

# Buffer donation on the jitted forward (KDLT_DONATE=0 disables): the batch
# argument is donated (donate_argnums), so once the program consumes the
# uint8 batch its HBM is returned to XLA for intermediates instead of
# pinning a dead buffer for the call's duration.  The engine's own dispatch
# path always passes a freshly-assembled (or padded) batch, so nothing
# aliases a donated buffer after dispatch; on backends where the donation
# cannot be used the program is bit-identical and jax merely drops it (the
# advisory warning is silenced below -- it would fire once per bucket
# compile on every CPU dev run).
DONATE_ENV = "KDLT_DONATE"

# Warmup provenance (the zero-cold-start proof): a bucket whose warmup
# compile+run stays under this many seconds WHILE the persistent compile
# cache is active is counted as a cache hit on
# kdlt_engine_warm_source{source="cache"}; anything slower (or any warm
# with the cache off) paid a live XLA compile.  A wall-time threshold is
# the honest signal available from outside XLA: cache hits are disk
# reads (ms to ~100 ms even for the chunked big-bucket programs) while
# the compiles they replace take tens of seconds on the v5e, and
# enable_compile_cache sets min_compile_time_secs=0.5 so a program fast
# enough to sit under the default threshold was never cache-eligible
# anyway.
WARM_CACHE_HIT_ENV = "KDLT_WARM_CACHE_HIT_S"
DEFAULT_WARM_CACHE_HIT_S = 1.0

# Device-resize staging for the raw-bytes ingest path (GUIDE 10q):
# KDLT_INGEST_DEVICE_RESIZE=HxW makes the decode stage stop resizing on
# host at HxW and hands the engine that staging resolution; a fused jitted
# program then resizes to spec.input_shape ON DEVICE (jax.image.resize)
# ahead of the forward.  Default OFF: jax.image.resize is not bit-exact
# with the host kernels (native/PIL), and the serving contract is that
# bytes-wire logits equal tensor-wire logits -- so host resize stays
# authoritative and this knob is an explicit staging/experiment opt-in.
INGEST_DEVICE_RESIZE_ENV = "KDLT_INGEST_DEVICE_RESIZE"


def ingest_device_resize(explicit: str | None = None) -> tuple[int, int] | None:
    """Parse the staging resolution: 'HxW' -> (H, W); unset/off -> None."""
    raw = explicit if explicit is not None else os.environ.get(
        INGEST_DEVICE_RESIZE_ENV, ""
    )
    raw = (raw or "").strip().lower()
    if not raw or raw in ("0", "off", "false", "no"):
        return None
    try:
        h_s, w_s = raw.split("x")
        h, w = int(h_s), int(w_s)
    except ValueError:
        raise ValueError(
            f"{INGEST_DEVICE_RESIZE_ENV} must be 'HxW' (e.g. 512x512), got {raw!r}"
        ) from None
    if h <= 0 or w <= 0:
        raise ValueError(f"{INGEST_DEVICE_RESIZE_ENV} dims must be positive, got {raw!r}")
    return (h, w)


def warm_cache_hit_threshold_s() -> float:
    try:
        return float(os.environ.get(WARM_CACHE_HIT_ENV, ""))
    except ValueError:
        return DEFAULT_WARM_CACHE_HIT_S


def donation_enabled(explicit: bool | None = None) -> bool:
    if explicit is not None:
        return bool(explicit)
    return os.environ.get(DONATE_ENV, "").strip() != "0"


def _donate_jit(fn, donate: bool):
    """jax.jit with the batch argument donated (argnum 1) when enabled."""
    import jax

    if not donate:
        return jax.jit(fn)
    import warnings

    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable"
    )
    return jax.jit(fn, donate_argnums=(1,))


# --- the wire form of a uint8 batch ---------------------------------------
#
# A compiled program's parameter has a device layout of the compiler's
# choosing, and for ``uint8[bucket, H, W, 3]`` that layout is far from the
# host's byte order: on a v5e the batch goes on the lanes for Xception's
# 512x299x299x3 (major to minor H, C, W, B) and B7's 64x600x600x3 becomes
# channel planes (B, C, H, W).  Handed such an array, the runtime
# transposes it ON THE HOST, three-byte pixels apart, on its own threads,
# after predict_async has returned and before the program may start.  So
# the batch crosses as ``uint8[bucket, H, W*C]`` -- the WIRE FORM: the same
# bytes with a pixel's channels folded into its row, a reshape of the
# caller's memory and never a copy -- whose device layout keeps a row's
# bytes together (whole 128-byte runs move, not single bytes), and the
# first operation of the bucket's own program splits the rows back into
# NHWC: the device re-tiles 137 MB of bytes in 0.4 ms where the host took
# 138.  One helper pair for every program the engine compiles: to_wire
# (host) / wired (program).  The table of forms tried on the chip, word
# views and flat forms among them, is in PERF.md (PR 29) and
# exp/stage_forms.py reads it again.


def wire_form(batch_shape: Sequence[int]) -> tuple[int, ...]:
    """The wire form's shape for ``uint8[bucket, ..., W, C]``: the last two
    axes folded into one.  Shape arithmetic alone; the leading axis stays
    the batch, which is the axis a mesh engine shards."""
    *lead, w, c = (int(d) for d in batch_shape)
    return (*lead, w * c)


def to_wire(batch: np.ndarray) -> np.ndarray:
    """The host's half: a C-contiguous ``uint8[bucket, H, W, C]`` in its wire
    form.  A view of the same memory, never a copy; read-only and
    unaligned memory (a request body's pixels start at an arbitrary byte
    offset inside the msgpack envelope) is accepted as it is."""
    return batch.reshape(wire_form(batch.shape))


def from_wire(wire, image_shape: Sequence[int]):
    """The program's half, its first operation: wire form -> uint8 NHWC.

    The barrier pins the reshape on the bytes, so that what follows is the
    program as it was for an NHWC parameter and the preamble costs one
    re-tiling of uint8 whatever the model (v5e: +0.35 ms on Xception's
    106.5 at bucket 512, +0.10 ms on B7's 168.9 at bucket 64).  Left free,
    XLA moves elementwise work across the reshape: that saved Xception
    0.06 ms and cost B7 0.98 -- it split the per-channel normalisation
    around the reshape through a float32 copy of the batch."""
    import jax

    pixels = wire.reshape((wire.shape[0], *image_shape))
    return jax.lax.optimization_barrier(pixels)


def wired(forward, image_shape: Sequence[int]):
    """``forward(variables, images)`` as ``f(variables, wire)``, under
    forward's own name (a jitted program is named after its function, and
    the benchmark's trace reduction counts programs named ``jit_*``).  An
    argument of the images' own rank is pixels already -- the float32 debug
    path shares the jit -- and passes through."""
    import functools

    @functools.wraps(forward)
    def call(variables, wire):
        if wire.ndim == len(image_shape):
            wire = from_wire(wire, image_shape)
        return forward(variables, wire)

    return call


def stage(images: np.ndarray, bucket: int) -> tuple[np.ndarray, bool]:
    """``uint8[n, *image]`` as the wire form of its bucket, and whether that
    took a host copy: padding ``n`` up to the bucket, or an array that is
    not C-contiguous.  A whole bucket of contiguous rows is a view."""
    n = images.shape[0]
    copied = False
    if bucket != n:
        padded = np.zeros((bucket, *images.shape[1:]), images.dtype)
        padded[:n] = images
        images, copied = padded, True
    elif not images.flags.c_contiguous:
        images, copied = np.ascontiguousarray(images), True
    return to_wire(images), copied


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw.strip() else default
    except ValueError:
        return default


def resolve_pipeline_depth(depth: int | None = None) -> int:
    """The in-flight dispatch depth: explicit arg > $KDLT_PIPELINE_DEPTH > 2.

    Depth 1 is serial dispatch (each batch fully materialized before the
    next is assembled).  Depth 2 overlaps batch N+1's host-side gather and
    H2D transfer with batch N's device execution.  That is the whole win on
    a single chip WHERE STAGING A BATCH TAKES NO LONGER THAN RUNNING ONE:
    the device runs one program at a time, so once the next batch's input
    is there in time depth 3+ only queues more work behind the same
    execution stream and adds latency without adding throughput.  Where
    staging is the longer of the two, two slots leave the device waiting:
    in the benchmark's Xception cell (137 MB a batch; PERF.md, PR 29) the
    runtime took ~138 ms to re-tile an NHWC batch on the host against a
    106 ms program, a batch held its slot ~245 ms, and the device idled
    12-13% at depth 2 (4,200 img/s); since the batch crosses in its wire
    form staging takes ~52 ms, the device idles 0.2% (4,800 img/s), and a
    third slot has nothing left to hide.  The default stays 2.  Clamped to
    >=1; a typo'd env value degrades to the default rather than killing
    serving.
    """
    if depth is None:
        raw = os.environ.get(PIPELINE_DEPTH_ENV, "")
        try:
            depth = int(raw) if raw.strip() else DEFAULT_PIPELINE_DEPTH
        except ValueError:
            depth = DEFAULT_PIPELINE_DEPTH
    return max(1, int(depth))


class DispatcherClosed(RuntimeError):
    """The in-flight dispatcher has been permanently shut down."""


class DispatchStall(RuntimeError):
    """An in-flight dispatch was declared stuck by the watchdog.

    Retryable from the caller's point of view (another replica can serve
    the request); for THIS process it is terminal evidence -- the
    completion thread is wedged on a device sync that never returns, so
    the dispatcher stops intake and the serving health check fails until
    the orchestrator restarts the pod.
    """


class InFlightDispatcher:
    """Bounded multi-in-flight dispatch pipeline over an engine.

    Replaces the lock-scoped dispatch->execute->readback round trip with a
    pipeline: ``submit(images)`` enqueues a compiled-bucket execution via
    ``engine.predict_async`` and returns a Future immediately, so the
    caller starts assembling the NEXT batch while this one executes; a
    dedicated completion thread materializes results (the blocking device
    sync) in FIFO dispatch order and resolves each Future.  Backpressure:
    submit blocks while ``depth`` batches are already in flight, so host
    assembly can run at most ``depth`` batches ahead of the device.

    Guarantees:

    - **Ordering**: completions happen in submit order (single FIFO
      completion queue), and each Future resolves to exactly its own
      batch's rows -- never another caller's.
    - **Byte-identical results**: the same predict_async + np.asarray
      materialization path as the engine's own synchronous predict().
    - **Exception wiring**: a dispatch failure resolves THAT submit's
      Future with the exception; a device-side failure surfacing at sync
      resolves the in-flight batch's Future.  Neither kills the pipeline.
    - **Clean shutdown**: close(drain=True) completes every in-flight
      batch before the completion thread exits; submits after close raise
      DispatcherClosed.

    Aliasing contract (inherited from predict_async): a submitted ``images``
    array must stay unmodified until its Future resolves.  Callers with
    reusable staging buffers must rotate >= depth+1 buffers.

    Per-stage latency lands in the kdlt_pipeline_*_seconds histograms
    (utils.metrics.PIPELINE_STAGES documents the stage semantics), and the
    live regions of enqueue_wait, dispatch and readback are profiler
    annotations under their span names, on the thread that runs them.

    Why the device waits, from where the work happens: every instant of the
    dispatcher's life is booked to one of kdlt_pipeline_{inflight,
    idle_dispatch,idle_no_batch}_seconds_total (utils.metrics.
    PIPELINE_IDLE_CAUSES) by a three-state machine under _inflight_lock.
    """

    def __init__(self, engine=None, depth: int | None = None,
                 registry: metrics_lib.Registry | None = None,
                 watchdog: bool | None = None,
                 stall_multiple: float | None = None,
                 stall_floor_s: float | None = None):
        # ``engine=None`` is the multi-engine (scheduler) mode: the unified
        # scheduler owns ONE dispatcher for the whole model tier and passes
        # each batch's engine per submit() -- one bounded in-flight budget
        # (the device runs one program at a time no matter which model
        # compiled it), one FIFO completion thread, one watchdog.
        self._engine = engine
        self.depth = resolve_pipeline_depth(depth)
        self._slots = threading.Semaphore(self.depth)
        import queue as queue_lib

        self._completions: queue_lib.Queue = queue_lib.Queue()
        self._closed = False         # guarded-by: _close_lock
        self._close_lock = threading.Lock()
        registry = registry or getattr(engine, "registry", None) or metrics_lib.Registry()
        self._registry = registry
        # Engines that are themselves a pipeline front (the cross-host
        # round protocol) label their stage series so dashboards separate
        # per-chip dispatch from fleet rounds; plain engines keep the
        # unlabeled single-host series.  Per-model stage series (scheduler
        # mode) are minted lazily in _stages_for.
        label = getattr(engine, "pipeline_engine_label", None)
        self._m_stage = metrics_lib.pipeline_stage_histograms(registry, engine=label)
        self._m_stage_models: dict[str, dict] = {}
        self._m_idle = metrics_lib.pipeline_idle_counters(registry, engine=label)
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        # Trace-aware engines (CrossHostEngine) take the member requests'
        # RequestTrace carriers through predict_async and record their own
        # protocol spans (crosshost.*) under the same waterfall the
        # pipeline-stage spans land in.  Cached per engine TYPE: the
        # signature is a class property, and the scheduler swaps engine
        # instances across hot reloads.
        self._takes_traces_cache: dict[type, bool] = {}
        self._m_depth = registry.gauge(
            "kdlt_pipeline_depth", "configured in-flight dispatch depth"
        )
        self._m_depth.set(float(self.depth))
        self._m_stalls = metrics_lib.dispatch_stall_counter(registry)
        # Fault injection (serving.faults): dispatch.submit / dispatch.complete
        # points; None (the inert fast path) unless $KDLT_FAULTS configures.
        from kubernetes_deep_learning_tpu.serving import faults as faults_lib

        self._faults = faults_lib.from_env()
        # Watchdog state: in-flight ledger (token -> (future, (engine,
        # bucket) key, dispatch time)) the watchdog scans, per-key EWMA of
        # observed dispatch->sync latency, and the terminal "stalled" flag.
        self._stalled = threading.Event()
        self._inflight: dict[int, tuple[Future, tuple, float]] = {}  # guarded-by: _inflight_lock
        self._inflight_lock = threading.Lock()
        self._seq = 0                # guarded-by: _inflight_lock
        # The idle-cause state machine: the state is a function of
        # (_inflight non-empty, _submitting > 0); _accrue_locked books the
        # time since the last transition to it before either changes.
        self._submitting = 0         # guarded-by: _inflight_lock
        self._state_since = time.perf_counter()  # guarded-by: _inflight_lock
        self._expected_s: dict[tuple, float] = {}  # guarded-by: _inflight_lock
        if watchdog is None:
            watchdog = os.environ.get(WATCHDOG_ENV, "").strip() != "0"
        self._stall_multiple = (
            stall_multiple if stall_multiple is not None
            else _env_float(WATCHDOG_MULTIPLE_ENV, DEFAULT_WATCHDOG_MULTIPLE)
        )
        self._stall_floor_s = (
            stall_floor_s if stall_floor_s is not None
            else _env_float(WATCHDOG_FLOOR_S_ENV, DEFAULT_WATCHDOG_FLOOR_S)
        )
        self._watchdog_stop = threading.Event()
        self._watchdog_thread = None
        if watchdog and self._stall_floor_s > 0:
            self._watchdog_thread = threading.Thread(
                target=self._watchdog_loop, name="kdlt-dispatch-watchdog",
                daemon=True,
            )
            self._watchdog_thread.start()
        self._thread = threading.Thread(
            target=self._complete_loop, name="kdlt-dispatch-readback", daemon=True
        )
        self._thread.start()

    @property
    def stalled(self) -> bool:
        """True once the watchdog declared an in-flight dispatch stuck; the
        dispatcher no longer accepts work and serving health should fail."""
        return self._stalled.is_set()

    def _takes_traces(self, engine) -> bool:
        key = type(engine)
        got = self._takes_traces_cache.get(key)
        if got is None:
            import inspect as _inspect

            got = "traces" in _inspect.signature(
                engine.predict_async
            ).parameters if hasattr(engine, "predict_async") else False
            self._takes_traces_cache[key] = got
        return got

    def _stages_for(self, model: str | None) -> dict:
        """The stage histograms a batch's times land in: the unlabeled
        (or engine-labeled) default, or the model-labeled set when the
        scheduler attributes device time per model.  Lazily minted, memoized
        (the central helper's registry dedupe makes re-minting an error)."""
        if model is None:
            return self._m_stage
        stages = self._m_stage_models.get(model)
        if stages is None:
            stages = metrics_lib.pipeline_stage_histograms(
                self._registry, model=model
            )
            self._m_stage_models[model] = stages
        return stages

    def _accrue_locked(self) -> None:
        """Book the seconds since the last transition to the state they
        were spent in.  Called under _inflight_lock BEFORE every change to
        _inflight or _submitting (and on the watchdog's tick, so an idle
        dispatcher's counters lag by at most that)."""
        now = time.perf_counter()
        if self._inflight:
            cause = "inflight"
        elif self._submitting:
            cause = "idle_dispatch"
        else:
            cause = "idle_no_batch"
        self._m_idle[cause].inc(now - self._state_since)
        self._state_since = now

    def _engine_key(self, engine):
        spec = getattr(engine, "spec", None)
        return getattr(spec, "name", None) or id(engine)

    def submit(self, images: np.ndarray, traces=(), engine=None,
               model: str | None = None) -> Future:
        """Dispatch one uint8 batch; returns a Future of its logits rows.

        Blocks only while ``depth`` batches are in flight (backpressure) --
        never on device execution of the batch itself.

        ``traces`` carries the member requests' utils.trace.RequestTrace
        objects (one per coalesced request; the batchers pass theirs
        through).  Each member's waterfall gets the four pipeline-stage
        spans -- the exact boundaries that feed kdlt_pipeline_*_seconds --
        recorded at completion, so a slow request shows WHICH stage of its
        batch ate the time, not just that the batch was slow.

        ``engine`` overrides the construction-time engine for THIS batch
        (the unified scheduler's multi-model mode: many engines, one
        in-flight budget); ``model`` attributes the batch's stage times to
        the model-labeled kdlt_pipeline_* series.
        """
        engine = engine if engine is not None else self._engine
        if engine is None:
            raise ValueError("no engine: pass engine= per submit or at init")
        stages = self._stages_for(model)
        if self._stalled.is_set():
            # The completion thread is wedged on a sync that never returns;
            # slots will never free, so blocking on one would hang the
            # caller.  Fail fast and retryably (another replica can serve).
            raise DispatchStall("dispatch pipeline is stalled")
        traces = tuple(t for t in traces if t is not None)
        t0 = time.perf_counter()
        w0 = trace_lib.now_s() if traces else 0.0
        with self._annotate(trace_lib.SPAN_PIPELINE_ENQUEUE_WAIT):
            self._slots.acquire()
        # kdlt-lint: disable=guarded-by -- the slot-semaphore handshake orders this read: close() drains every slot before flipping _closed, so a submit holding a slot observes the flip or the drain, never a torn state
        if self._closed:
            self._slots.release()
            raise DispatcherClosed("dispatcher is shut down")
        if self._stalled.is_set():
            self._slots.release()
            raise DispatchStall("dispatch pipeline is stalled")
        stages["enqueue_wait"].observe(time.perf_counter() - t0)
        w1 = trace_lib.now_s() if traces else 0.0
        fut: Future = Future()
        with self._inflight_lock:
            self._accrue_locked()
            self._submitting += 1
        t1 = time.perf_counter()
        try:
            with self._annotate(trace_lib.SPAN_PIPELINE_DISPATCH):
                if self._faults is not None:
                    self._faults.fire("dispatch.submit")
                if self._takes_traces(engine):
                    handle, n = engine.predict_async(images, traces=traces)
                else:
                    handle, n = engine.predict_async(images)
        except Exception as e:  # dispatch failure belongs to THIS future
            with self._inflight_lock:
                self._accrue_locked()
                self._submitting -= 1
            self._slots.release()
            fut.set_exception(e)
            return fut
        stages["dispatch"].observe(time.perf_counter() - t1)
        dispatched_at = time.perf_counter()
        w2 = trace_lib.now_s() if traces else 0.0
        bkey = (self._engine_key(engine), self._bucket_of(engine, n))
        with self._inflight_lock:
            self._accrue_locked()
            self._submitting -= 1
            token = self._seq
            self._seq += 1
            self._inflight[token] = (fut, bkey, dispatched_at)
        self._completions.put(
            (handle, n, fut, dispatched_at, token, traces, (w0, w1, w2),
             engine, stages, bkey)
        )
        return fut

    def _complete_loop(self) -> None:
        while True:
            item = self._completions.get()
            if item is None:
                return
            self._complete_one(*item)

    def _complete_one(
        self, handle, n: int, fut: Future, dispatched_at: float, token: int,
        traces=(), walls=(0.0, 0.0, 0.0), engine=None, stages=None, bkey=None,
    ) -> None:
        """MUST NOT raise: an exception escaping here kills the completion
        thread, which strands every later batch's waiters AND deadlocks
        close() -- so anything unexpected fails THIS future instead."""
        engine = engine if engine is not None else self._engine
        stages = stages if stages is not None else self._m_stage
        w3 = trace_lib.now_s() if traces else 0.0
        t0 = time.perf_counter()
        try:
            with self._annotate(trace_lib.SPAN_PIPELINE_READBACK):
                if self._faults is not None:
                    self._faults.fire("dispatch.complete")
                rows = np.asarray(handle)[:n]  # blocking device sync + D2H
        except Exception as e:  # device-side failure surfaces at sync
            with self._inflight_lock:
                self._accrue_locked()
                self._inflight.pop(token, None)
            self._slots.release()
            if not fut.cancelled():
                fut.set_exception(e)
            return
        t1 = time.perf_counter()
        stages["execute"].observe(t0 - dispatched_at)
        stages["readback"].observe(t1 - t0)
        self._observe_latency(bkey, t1 - dispatched_at)
        with self._inflight_lock:
            self._accrue_locked()
            self._inflight.pop(token, None)
        try:
            if hasattr(engine, "record_completed"):
                # The engine accounts only its own synchronous path;
                # pipelined batches report here after materialization
                # succeeds (failed batches never inflate the counters).
                engine.record_completed(n, t1 - dispatched_at)
        except Exception:  # noqa: BLE001 - accounting must not stall results
            pass
        if traces:
            # Per-member pipeline-stage spans from the SHARED perf-counter
            # boundaries (one batch, one set of intervals): exactly
            # contiguous and non-overlapping in every member's waterfall.
            # Recorded BEFORE the future resolves so a handler that sends
            # its response right after result() always finds them.
            w0, w1, w2 = walls
            w4 = w3 + (t1 - t0)
            try:
                for tr in traces:
                    tr.record(trace_lib.SPAN_PIPELINE_ENQUEUE_WAIT, w0, w1 - w0)
                    tr.record(trace_lib.SPAN_PIPELINE_DISPATCH, w1, w2 - w1)
                    tr.record(trace_lib.SPAN_PIPELINE_EXECUTE, w2, w3 - w2)
                    tr.record(trace_lib.SPAN_PIPELINE_READBACK, w3, w4 - w3)
            except Exception:  # noqa: BLE001 - tracing must not stall results
                pass
        self._slots.release()
        try:
            if not fut.cancelled():
                fut.set_result(rows)
        except Exception:  # noqa: BLE001 - cancel race on an abandoned future
            pass

    # --- watchdog ----------------------------------------------------------

    def _bucket_of(self, engine, n: int) -> int:
        bucket_for = getattr(engine, "bucket_for", None)
        if bucket_for is None:
            return n
        try:
            return bucket_for(n)
        except Exception:  # noqa: BLE001 - accounting key only
            return n

    def _observe_latency(self, bkey, seconds: float) -> None:
        """Per-(engine, bucket) EWMA of dispatch->sync latency; the
        watchdog's notion of "expected".  Keyed per engine so a heavy
        model's 100 ms buckets never inflate a light model's stall bound."""
        with self._inflight_lock:
            prev = self._expected_s.get(bkey)
            self._expected_s[bkey] = (
                seconds if prev is None else 0.7 * prev + 0.3 * seconds
            )

    def _stall_bound_s(self, bkey) -> float:
        """How long an in-flight dispatch with this (engine, bucket) key may
        run before it is stuck: multiple x the key's EWMA, never below the
        floor (and exactly the floor until the key has a sample)."""
        with self._inflight_lock:
            expected = self._expected_s.get(bkey)
        if expected is None:
            return self._stall_floor_s
        return max(self._stall_floor_s, self._stall_multiple * expected)

    def _watchdog_loop(self) -> None:
        interval = max(0.01, min(1.0, self._stall_floor_s / 5.0))
        while not self._watchdog_stop.wait(interval):
            if self._check_stall():
                return  # terminal: the pipeline is declared dead

    def _check_stall(self) -> bool:
        """One watchdog scan; returns True when a stall was declared."""
        now = time.perf_counter()
        with self._inflight_lock:
            self._accrue_locked()
            entries = list(self._inflight.items())
        overdue = [
            (token, fut, bkey)
            for token, (fut, bkey, t0) in entries
            if now - t0 > self._stall_bound_s(bkey)
        ]
        if not overdue:
            return False
        import logging

        logging.getLogger(__name__).error(
            "dispatch watchdog: %d in-flight batch(es) stuck past their "
            "stall bound (oldest %.1fs); failing waiters and marking the "
            "pipeline stalled",
            len(overdue),
            max(now - t0 for _, (_, _, t0) in entries),
        )
        self.declare_stall()
        return True

    def declare_stall(self) -> None:
        """Declare the pipeline terminally stalled: fail every in-flight
        waiter retryably, stop intake, flip unhealthy.

        The completion thread materializes in FIFO order, so one stuck
        handle blocks every later in-flight batch too -- this process
        needs a restart, its callers need another replica.  The watchdog
        is the normal caller; a test calls it directly to stage a wedged
        replica without waiting out a real device hang.
        """
        self._stalled.set()
        with self._inflight_lock:
            self._accrue_locked()
            stranded = list(self._inflight.items())
            self._inflight.clear()
        for _token, (fut, _n, _t0) in stranded:
            self._m_stalls.inc()
            try:
                if not fut.done():
                    fut.set_exception(
                        DispatchStall(
                            "in-flight dispatch exceeded its stall bound"
                        )
                    )
            except Exception:  # noqa: BLE001 - racing completion
                pass

    def close(self, drain: bool = True) -> None:
        """Stop intake, drain every in-flight batch, stop the completion
        thread.

        Quiesces through the slot semaphore: acquiring all ``depth`` slots
        both waits for in-flight work to finish materializing (each slot is
        released only after its Future resolves) and blocks any racing
        submit, which then observes ``_closed`` and raises -- so no Future
        can be stranded by a close/submit race.  drain=False is accepted
        for signature symmetry with the batchers but behaves identically:
        work already dispatched is on the device regardless, so its waiters
        are always resolved.

        A STALLED dispatcher cannot quiesce (the completion thread is
        wedged and its slots never free): close skips the drain, leaving
        the daemon threads to die with the process -- which is imminent,
        since the stall already failed the health check.
        """
        del drain
        self._watchdog_stop.set()
        with self._close_lock:
            if self._closed:
                return
            if not self._stalled.is_set():
                for _ in range(self.depth):  # wait out the in-flight batches
                    self._slots.acquire()
                self._closed = True
                for _ in range(self.depth):  # wake blocked submits -> raise
                    self._slots.release()
            else:
                self._closed = True
        self._completions.put(None)
        self._thread.join(timeout=0.5 if self._stalled.is_set() else 30.0)
        with self._inflight_lock:
            self._accrue_locked()  # the counters' last word: they sum to the age
        if self._watchdog_thread is not None:
            self._watchdog_thread.join(timeout=5.0)


class InferenceEngine:
    def __init__(
        self,
        artifact: ModelArtifact,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        use_exported: bool = True,
        device=None,
        registry: metrics_lib.Registry | None = None,
        mesh=None,
        mesh_mode: str = "data",
        fast: bool | str = "auto",
    ):
        """``mesh`` switches the engine to SPMD serving over the mesh.
        mesh_mode "data": the batch is sharded over the ``data`` axis
        (params replicated or tensor-parallel per parallel.dataparallel's
        rules) and buckets are rounded up to multiples of the axis size so
        every chip gets an equal shard.  mesh_mode "sequence": context
        parallelism -- the TOKEN axis is sharded and attention runs the ring
        schedule (parallel.longseq; vit families only), for inputs whose
        sequence would not fit one chip.  Either way the exported-module
        path is bypassed: the module was traced for one device; the live
        forward jits SPMD instead."""
        import jax

        if mesh_mode not in ("data", "sequence"):
            raise ValueError(f"unknown mesh_mode {mesh_mode!r}")
        self.spec = artifact.spec
        self.mesh = mesh
        self.mesh_mode = mesh_mode
        if mesh is not None and mesh_mode == "data":
            from kubernetes_deep_learning_tpu.parallel.mesh import DATA_AXIS

            n_data = mesh.shape[DATA_AXIS]
            buckets = sorted({-(-b // n_data) * n_data for b in buckets})
        self.buckets = tuple(sorted(buckets))
        self.max_batch = self.buckets[-1]
        # local_devices, not devices: after jax.distributed.initialize the
        # global list includes other hosts' chips, which this process cannot
        # device_put to -- each serving process drives its own chips.
        self._device = device or jax.local_devices()[0]
        self._lock = threading.Lock()
        self._ready = threading.Event()
        # Set by warmup() when a fused-fast-path compile failure forced the
        # engine back onto the exact flax graph (see _degrade_fast).
        self.fast_degraded = False
        self._fast_engaged = False

        from kubernetes_deep_learning_tpu.models import build_forward

        # Compute dtype recorded at export time; the f32 debug path must use
        # the same dtype or it would disagree numerically with the wire path.
        self._compute_dtype = artifact.metadata.get("compute_dtype", "bfloat16")
        # fast: forwarded to models.build_forward for the live-jit paths.
        # Exact-parity consumers (golden verification) pass False so the
        # flax graph -- not the approximate fused kernel -- is what gets
        # checked (xception_fast.py's stated invariant).
        self._fast = fast
        # int8 artifacts (ops.quantize), dispatched on the scheme tag:
        # "int8-weight-only" keeps weights int8 in HBM and dequantizes
        # inline inside the jit (fused into the convs' operand path -- the
        # small-batch weight-bandwidth win); "int8-w8a8" additionally
        # quantizes activations with the artifact's calibrated static
        # scales so conv/dense matmuls run int8 x int8 -> int32 on the
        # MXU's 2x path -- gated at warmup by the golden-logits tolerance
        # check (_run_quant_gate): past $KDLT_QUANT_TOL the engine refuses
        # the int8-activation program and serves weight-only, loudly.
        # Mesh serving composes: the partition rules address the {_q8,
        # _q8_scale} wire form directly (parallel.mesh.leaf_partition_spec),
        # so int8 leaves stay int8 in HBM on every chip and the live
        # forward dequantizes inline inside the sharded jit.
        self._donate = donation_enabled()
        self._quantization = artifact.metadata.get("quantization") or None
        self._quantization_active = self._quantization
        self.quant_gate_failed = False
        if self._quantization is not None:
            from kubernetes_deep_learning_tpu.ops import quantize as quant_lib

            if self._quantization not in quant_lib.SCHEMES:
                raise ValueError(
                    f"unknown quantization scheme {self._quantization!r}"
                )
            if (
                self._quantization == quant_lib.SCHEME_W8A8
                and quant_lib.resolve_scheme_override() == "weight-only"
            ):
                # Operator rollback knob: serve the calibrated artifact as
                # weight-only fleet-wide without re-exporting.
                import logging

                logging.getLogger(__name__).warning(
                    "%s=weight-only: serving %s without int8 activations",
                    quant_lib.QUANT_SCHEME_ENV, self.spec.name,
                )
                self._quantization_active = quant_lib.SCHEME
            if mesh is not None and mesh_mode == "sequence":
                import dataclasses

                # Host-side numpy dequant: longseq's ring forward addresses
                # float kernel leaves only (params declared replicated), so
                # sequence-parallel serving still dequantizes at load.
                self._quantization_active = None
                artifact = dataclasses.replace(
                    artifact,
                    variables=quant_lib.dequantize_variables_host(
                        artifact.variables
                    ),
                )
        from kubernetes_deep_learning_tpu.parallel import mesh as mesh_par

        if mesh is None:
            self._sharding_scheme = mesh_par.sharding_scheme("single")
        elif mesh_mode == "sequence":
            self._sharding_scheme = mesh_par.sharding_scheme("mesh-sequence")
        else:
            self._sharding_scheme = mesh_par.sharding_scheme("mesh-data")
        if mesh is not None:
            import jax.numpy as jnp

            if mesh_mode == "sequence":
                from jax.sharding import NamedSharding, PartitionSpec

                from kubernetes_deep_learning_tpu.parallel.longseq import (
                    build_sequence_parallel_forward,
                )

                # longseq declares params replicated (P()); sharding them on
                # the model axis here would just force an all-gather per
                # dispatch (and build_sequence_parallel_forward rejects
                # model-parallel meshes outright).
                self._variables = jax.device_put(
                    artifact.variables, NamedSharding(mesh, PartitionSpec())
                )
                sharded_call = build_sequence_parallel_forward(
                    self.spec, mesh, dtype=jnp.dtype(self._compute_dtype)
                )
                # A jit of the jitted forward inlines it: one program a batch.
                self._jitted = jax.jit(wired(sharded_call, self.spec.input_shape))
                self._jitted_f32 = sharded_call
                self._f32_lock = threading.Lock()
                self._init_metrics(registry)
                return
            from kubernetes_deep_learning_tpu.parallel.dataparallel import (
                resolve_sharded_fast,
                shard_variables,
            )

            # One device_put per leaf to its NamedSharding, once at load
            # (parallel.mesh.partition_spec rules, quantized wire form
            # included -- int8 leaves shard like the kernels they replaced).
            self._variables = shard_variables(
                artifact.variables, mesh, family=self.spec.family
            )
            # Mesh serving runs the fused fast path under shard_map
            # when it resolves (round 2 forfeited the +29% here);
            # _fast_engaged arms the same warmup degrade as
            # single-device serving.
            self._fast_engaged = resolve_sharded_fast(
                self.spec, mesh, jnp.dtype(self._compute_dtype), self._fast
            )
            self._fast = self._fast_engaged
            from kubernetes_deep_learning_tpu.ops import quantize as quant_lib

            if self._quantization_active == quant_lib.SCHEME_W8A8:
                # Same int8-activation discipline as single-device serving:
                # the w8a8 program is the exact graph with int8 operands,
                # gated at warmup; the fused path only re-enters if the
                # tolerance gate downgrades to weight-only.
                self._fast_after_downgrade = self._fast
                self._fast = False
                self._fast_engaged = False
            self._build_live_jit()
            self._f32_lock = threading.Lock()
            self._init_metrics(registry)
            return
        self._variables = jax.device_put(artifact.variables, self._device)
        platform = self._device.platform
        # On TPU, a family with a fused-Pallas fast path serves through the
        # live-jit forward even when the artifact carries StableHLO: same
        # variables, measurably faster program (models.xception_fast).  The
        # exported module remains the portable format and the path for
        # families with no in-tree model.  Resolution is keyed to THIS
        # engine's device platform, not the process default backend, so an
        # engine pinned off-TPU never traces a program it cannot compile.
        import jax.numpy as jnp

        from kubernetes_deep_learning_tpu.models import resolve_fast

        # Whether the fused path can compile on THIS device at all ("auto"
        # semantics, device-keyed).  The exported-module bypass keys off
        # viability -- an explicit fast=True must not skip a present exported
        # module on a device where the fused program is guaranteed to fail.
        fast_viable = resolve_fast(
            self.spec, jnp.dtype(self._compute_dtype), "auto", backend=platform
        )
        prefer_live = fast_viable and self._fast != False  # noqa: E712 - "auto" is truthy
        if (
            use_exported
            and not prefer_live
            and self._quantization is None  # modules are traced float-only
            and artifact.module_bytes_for(platform) is not None
        ):
            self._jitted = _donate_jit(
                wired(artifact.exported_for(platform).call, self.spec.input_shape),
                self._donate,
            )
            # The exported module is traced for the uint8 wire path only;
            # float32 "pre-normalized" input (protocol.decode_predict_request's
            # JSON debug path) runs through the in-tree forward instead,
            # built lazily: a StableHLO artifact stays servable even when its
            # spec.family has no in-tree model, and the (slow) build/compile
            # is deferred to first debug use.  _fast is concretized so that
            # lazy build also never traces a fused program this device
            # cannot compile (prefer_live is statically False here).
            self._fast = False
            self._jitted_f32 = None
        else:
            # build_forward branches on input dtype at trace time and jit
            # specializes per dtype, so one jitted fn serves both paths.
            # _fast becomes a concrete bool here: build_forward must not
            # re-resolve "auto" against the default backend when this
            # engine's device decided otherwise.  An explicit fast=True is
            # honored even where non-viable (tests force the failure path;
            # warmup degrades it with a loud log).
            self._fast = resolve_fast(
                self.spec, jnp.dtype(self._compute_dtype), self._fast, backend=platform
            )
            self._fast_engaged = self._fast
            from kubernetes_deep_learning_tpu.ops import quantize as quant_lib

            if self._quantization_active == quant_lib.SCHEME_W8A8:
                # The w8a8 program is the exact graph with int8 operands;
                # the fused fast path only re-enters if the tolerance gate
                # downgrades to weight-only (_downgrade_w8a8 restores it).
                self._fast_after_downgrade = self._fast
                self._fast = False
                self._fast_engaged = False
            self._build_live_jit()
        # The f32 debug path dispatches under its own lock: its lazy first
        # compile (tens of seconds on TPU) must never stall warm uint8
        # traffic serialized on _lock.  Concurrent dispatch of two programs
        # is safe -- the device runtime serializes execution.
        self._f32_lock = threading.Lock()
        self._init_metrics(registry)

    def _init_metrics(self, registry: metrics_lib.Registry | None) -> None:
        registry = registry or metrics_lib.Registry()
        self.registry = registry
        self._m_infer_latency = registry.histogram(
            "kdlt_engine_infer_seconds",
            "batch latency dispatch->sync (pipelined serving may include "
            "bounded queue-wait/assembly overlap)",
        )
        self._m_images = registry.counter("kdlt_engine_images_total", "images executed")
        self._m_batches = registry.counter("kdlt_engine_batches_total", "batches executed")
        self._m_pad_waste = registry.counter(
            "kdlt_engine_pad_images_total", "padding rows executed (bucket waste)"
        )
        self._m_input = metrics_lib.engine_input_counters(registry)
        self._m_warmup = registry.gauge("kdlt_engine_warmup_seconds", "total warmup compile time")
        self._m_fast_degraded = registry.gauge(
            "kdlt_engine_fast_degraded",
            "1 when a fused fast-path compile failure forced the exact graph",
        )
        # Quantization scheme + tolerance-gate accounting (kdlt_quant_*,
        # minted centrally): the scheme gauge is 1 for the ACTIVE scheme
        # (post-gate, post-override), so a downgraded pod is alertable.
        self._m_quant = metrics_lib.quant_metrics(registry)
        self._refresh_scheme_gauge()
        # Mesh-serving series (kdlt_mesh_*, minted centrally): static layout
        # facts -- model_parallel degree, per-axis device counts, per-device
        # resident param bytes (the "fits where it didn't" number) -- plus
        # cumulative dispatch->sync device seconds, the denominator for
        # estimating collective overhead against an mp=1 baseline.
        self._m_mesh = None
        if self.mesh is not None:
            from kubernetes_deep_learning_tpu.parallel import mesh as mesh_par

            mesh_shape = dict(self.mesh.shape)
            self._m_mesh = metrics_lib.mesh_metrics(registry)
            self._m_mesh["model_parallel"].set(
                float(mesh_shape.get(mesh_par.MODEL_AXIS, 1))
            )
            for axis, gauge in self._m_mesh["axis_devices"].items():
                gauge.set(float(mesh_shape.get(axis, 0)))
            self._m_mesh["param_bytes"].set(
                float(mesh_par.param_bytes_per_device(self._variables))
            )
        # Recent admitted-batch sizes per dispatch, feeding the
        # /debug/profile?audit=buckets padding-waste audit.
        # guarded-by: GIL -- deque.append is atomic; readers snapshot with list()
        self._bucket_history: deque[tuple[int, int]] = deque(maxlen=2048)
        # kdlt-lint: disable=guarded-by -- construction: _init_metrics runs only from __init__, before the engine escapes to any other thread
        self._audit_flops: dict[int, float | None] = {}  # guarded-by: _audit_flops_lock
        self._audit_flops_lock = threading.Lock()
        # Warmup provenance (kdlt_engine_warm_source, minted centrally):
        # cache-hit vs live-compile counts per warmed bucket, the scaled
        # pod's zero-cold-start proof.
        self._m_warm_source = metrics_lib.engine_warm_source_metrics(registry)
        self._warm_bucket_seconds: dict[int, float] = {}
        self.warm_report: dict[str, Any] = {}

    def _refresh_scheme_gauge(self) -> None:
        active = self._quantization_active or "float32"
        for scheme, gauge in self._m_quant["scheme"].items():
            gauge.set(1.0 if scheme == active else 0.0)

    @property
    def ready(self) -> bool:
        return self._ready.is_set()

    @property
    def quantization(self) -> str | None:
        """The artifact's requested quantization scheme tag (or None)."""
        return self._quantization

    @property
    def quantization_active(self) -> str | None:
        """The scheme actually serving: the requested one unless the
        warmup tolerance gate or $KDLT_QUANT_SCHEME downgraded int8-w8a8
        to weight-only (or mesh serving dequantized to float)."""
        return self._quantization_active

    def warmup(self, workers: int = 4) -> float:
        """Compile every bucket shape; gate readiness on completion.

        The reference has no readiness probes, so a cold TF-Serving pod can
        receive traffic before the model loads (SURVEY.md section 5); here
        k8s readiness is wired to this warmup being done.

        Buckets compile CONCURRENTLY (``workers`` threads): jax.jit is
        thread-safe and XLA releases the GIL while compiling, so cold-start
        wall time approaches the slowest bucket's compile rather than the
        sum -- which matters since the chunked 32/64 bucket programs
        compile in minutes each (models/xception_fast.py round 4).

        If a bucket fails to compile on the fused fast path (a Mosaic
        legality regression at some shape), the engine degrades to the exact
        flax graph and re-warms every bucket rather than killing the model
        (round-2's failure mode: the default TPU config could not boot).
        """
        if self.mesh is not None:
            # Every mesh program carries a cross-device collective (tensor-
            # parallel reductions on a model axis; on a data-only mesh the
            # all-gather that replicates the logits), and warm_one EXECUTES
            # each bucket program: two executions racing from different
            # threads can enqueue in different per-device orders and
            # deadlock the collective rendezvous (observed wedging the
            # host-platform CPU backend with a model axis; jax documents
            # the same hazard for any multi-device program launched from
            # several threads).  Serial warmup costs boot time only, never
            # serving latency -- serving dispatch is already serialized.
            workers = 1
        t0 = time.perf_counter()
        while True:
            failure = self._warm_buckets(max(1, workers))
            if failure is not None:
                bucket, exc = failure
                if not self._degrade_fast(bucket, exc):
                    raise exc
                # Degraded: loop re-warms every bucket on the exact graph,
                # with its own per-bucket retry budget.
                continue
            if self._quant_gate_pending() and not self._run_quant_gate():
                # The calibrated int8-activation program drifted past
                # KDLT_QUANT_TOL: refuse w8a8, fall back to weight-only,
                # loop to re-warm the replacement programs.  Readiness is
                # still gated on the REPLACEMENT being warm -- a gate
                # failure costs boot time, never cold-compile stalls on
                # live traffic.
                self._downgrade_w8a8()
                continue
            break
        dt = time.perf_counter() - t0
        self._record_warm_sources(dt)
        self._m_warmup.set(dt)
        self._ready.set()
        return dt

    def _record_warm_sources(self, total_s: float) -> None:
        """Classify each bucket's FINAL warm (degrade/gate loops overwrite
        earlier passes) as cache-hit vs live compile, count it on
        kdlt_engine_warm_source, and keep the per-bucket breakdown on
        ``self.warm_report`` for /v1/models introspection and kdlt-warm."""
        from kubernetes_deep_learning_tpu.utils import compilecache

        cache_dir = compilecache.active_cache_dir()
        threshold = warm_cache_hit_threshold_s()
        buckets: dict[int, dict[str, Any]] = {}
        for b in self.buckets:
            secs = self._warm_bucket_seconds.get(b)
            if secs is None:
                continue
            source = (
                "cache" if cache_dir and secs <= threshold else "compile"
            )
            self._m_warm_source[source].inc()
            buckets[int(b)] = {"seconds": secs, "source": source}
        self.warm_report = {
            "total_seconds": total_s,
            "cache_dir": cache_dir,
            "threshold_s": threshold,
            "buckets": buckets,
        }

    # --- w8a8 tolerance gate ----------------------------------------------

    def _quant_gate_pending(self) -> bool:
        from kubernetes_deep_learning_tpu.ops import quantize as quant_lib

        return (
            self._quantization_active == quant_lib.SCHEME_W8A8
            and not getattr(self, "_quant_gate_checked", False)
        )

    def _run_quant_gate(self) -> bool:
        """Golden-logits tolerance check: the w8a8 program's logits on a
        deterministic uint8 batch vs the weight-only float reference (the
        exact program the fallback would serve).  Passes iff top-1
        agreement >= GATE_TOP1 AND relative max-abs drift <= KDLT_QUANT_TOL.

        Runs AFTER the buckets warmed, so the w8a8 side reuses a compiled
        bucket program; the reference costs one extra (smallest-gate-
        bucket) compile at boot -- the price of never activating a
        mis-calibrated artifact.
        """
        import logging

        import jax
        import jax.numpy as jnp

        from kubernetes_deep_learning_tpu.ops import quantize as quant_lib

        self._quant_gate_checked = True
        tol = quant_lib.resolve_quant_tol()
        b = self.bucket_for(min(8, self.max_batch))
        rng = np.random.default_rng(0)
        x = rng.integers(
            0, 256, size=(b, *self.spec.input_shape), dtype=np.uint8
        )
        got = np.asarray(self._jitted(self._variables, to_wire(x)))[:b]
        prev = self._quantization_active
        try:
            # The reference IS the fallback program: _live_forward with the
            # weight-only scheme active (inline dequant, same compute dtype).
            # On a mesh engine the reference runs over the same mesh (the
            # variables are committed to their NamedShardings; a plain jit
            # would work, but building it through the mesh builder keeps the
            # comparison program-for-program with what the fallback serves).
            self._quantization_active = quant_lib.SCHEME
            if self.mesh is not None:
                from kubernetes_deep_learning_tpu.parallel.dataparallel import (
                    build_mesh_serving_jit,
                )

                ref_fn = build_mesh_serving_jit(
                    self.spec, self.mesh, jnp.dtype(self._compute_dtype),
                    fast=False,
                    forward=self._wired_live_forward(),
                )
            else:
                ref_fn = jax.jit(self._wired_live_forward())
        finally:
            self._quantization_active = prev
        ref = np.asarray(ref_fn(self._variables, to_wire(x)))[:b]
        drift = float(
            np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        )
        top1 = float((got.argmax(-1) == ref.argmax(-1)).mean())
        ok = drift <= tol and top1 >= quant_lib.GATE_TOP1
        log = logging.getLogger(__name__)
        if ok:
            log.info(
                "w8a8 tolerance gate PASSED for %s: top-1 agreement %.4f "
                "(>= %.2f), relative max-abs logit drift %.4f (<= %s=%.3g) "
                "over a %d-image golden batch; serving int8 activations",
                self.spec.name, top1, quant_lib.GATE_TOP1, drift,
                quant_lib.QUANT_TOL_ENV, tol, b,
            )
        else:
            log.error(
                "w8a8 tolerance gate FAILED for %s: top-1 agreement %.4f "
                "(need >= %.2f), relative max-abs logit drift %.4f (need "
                "<= %s=%.3g) over a %d-image golden batch; REFUSING int8 "
                "activations and serving weight-only -- re-calibrate the "
                "artifact (kdlt-export --calibrate / kdlt-quantize "
                "--scheme int8-w8a8)",
                self.spec.name, top1, quant_lib.GATE_TOP1, drift,
                quant_lib.QUANT_TOL_ENV, tol, b,
            )
        self.quant_gate_drift = drift
        self.quant_gate_top1 = top1
        return ok

    def _downgrade_w8a8(self) -> None:
        """Swap the forward to weight-only after a gate failure (the
        warmup loop re-warms the replacement buckets)."""
        from kubernetes_deep_learning_tpu.ops import quantize as quant_lib

        self.quant_gate_failed = True
        self._quantization_active = quant_lib.SCHEME
        self._m_quant["gate_failures"].inc()
        self._refresh_scheme_gauge()
        # Weight-only serving regains the fused fast path the w8a8 program
        # had to bypass (its operand layouts are a float kernel contract).
        self._fast = getattr(self, "_fast_after_downgrade", self._fast)
        self._fast_engaged = self._fast
        self._build_live_jit()

    def _warm_buckets(self, workers: int) -> tuple[int, Exception] | None:
        """Compile+run every bucket, ``workers`` at a time; returns the
        first persistently-failing (bucket, exception) or None.

        Each bucket gets one retry: a deterministic Mosaic/lowering failure
        fails again immediately, but a transient runtime error (device
        busy, brief HBM pressure from a neighbor) must not lock a healthy
        pod onto the slower exact graph for life.  Retries run SERIALLY
        after the pool has drained -- retrying while sibling warmup threads
        still compile/execute would re-create the very contention that
        caused a transient failure and convert it into a permanent
        degrade.  A persistent failure still lets in-flight sibling
        compiles finish before returning (wasted only in the rare
        fail-then-degrade boot, and compile failures typically raise in
        seconds at lowering, not after minutes).
        """

        def warm_one(b: int) -> None:
            x = self._zero_wire(b)
            t0 = time.perf_counter()
            np.asarray(self._jitted(self._variables, x))  # compile+run
            # Per-bucket wall time feeds the cache-hit/compile provenance
            # classification in warmup(); concurrent siblings inflate it
            # only marginally (XLA releases the GIL, and a cache hit is a
            # disk read orders of magnitude under the threshold).
            self._warm_bucket_seconds[b] = time.perf_counter() - t0

        failures: list[tuple[int, Exception]] = []
        if workers == 1 or len(self.buckets) == 1:
            for b in self.buckets:
                try:
                    warm_one(b)
                except Exception as exc:  # noqa: BLE001 - vary by backend
                    failures.append((b, exc))
        else:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                max_workers=min(workers, len(self.buckets))
            ) as ex:
                futures = [(b, ex.submit(warm_one, b)) for b in self.buckets]
                for b, fut in futures:
                    try:
                        fut.result()
                    except Exception as exc:  # noqa: BLE001
                        failures.append((b, exc))
        for b, _first_exc in failures:  # serial second chance, quiet device
            try:
                warm_one(b)
            except Exception as exc:  # noqa: BLE001
                return b, exc
        return None

    def _degrade_fast(self, bucket: int, exc: Exception) -> bool:
        """Swap the forward to the exact flax graph after a fast-path
        compile failure; returns False when there is nothing to degrade to
        (exported/already-exact/sequence-mesh engines re-raise)."""
        if not self._fast_engaged:
            return False
        import logging

        logging.getLogger(__name__).error(
            "fused fast-path compile FAILED at bucket %d; serving the exact "
            "flax graph instead (fast=False). Cause: %s", bucket, exc,
        )
        self._fast = False
        self._fast_engaged = False
        self.fast_degraded = True
        # Surface on /metrics: a silently-degraded pod serves ~20% slower for
        # its lifetime, which operators must be able to alert on.
        self._m_fast_degraded.set(1.0)
        self._build_live_jit()
        return True

    def _build_live_jit(self) -> None:
        """(Re)build the live-jit forward pair; __init__, _degrade_fast and
        _downgrade_w8a8 must construct it identically or a degraded engine
        would run a differently-configured program.  The batch argument is
        donated (KDLT_DONATE=0 disables): the dispatch path always hands
        the program a freshly-assembled batch, so its device buffer can be
        recycled into the program's own working set."""
        import jax.numpy as jnp

        if self.mesh is not None:
            # The mesh scheme's jit: batch in_sharded P(data), params keep
            # their committed (possibly tensor-parallel) shardings, logits
            # replicated on device, batch donated -- a real jax.jit, so
            # donation_info / memory analysis work identically to the
            # single-device path.
            from kubernetes_deep_learning_tpu.parallel.dataparallel import (
                build_mesh_serving_jit,
            )

            self._jitted = build_mesh_serving_jit(
                self.spec, self.mesh, jnp.dtype(self._compute_dtype),
                fast=self._fast, forward=self._wired_live_forward(),
                donate=self._donate,
            )
            self._jitted_f32 = self._jitted
            return
        self._jitted = _donate_jit(self._wired_live_forward(), self._donate)
        self._jitted_f32 = self._jitted

    def _wired_live_forward(self):
        """The live forward for the active scheme, taking the wire form."""
        import jax.numpy as jnp

        return wired(
            self._live_forward(jnp.dtype(self._compute_dtype)),
            self.spec.input_shape,
        )

    def _zero_wire(self, bucket: int) -> np.ndarray:
        """A zero batch of one bucket in the form serving hands over: what
        warms, lowers or places a bucket's program must give it the
        argument serving gives it, or it is another program."""
        return np.zeros(wire_form((bucket, *self.spec.input_shape)), np.uint8)

    def _live_forward(self, dtype):
        """The live-jit forward for the ACTIVE quantization scheme: plain
        float graph, inline weight dequantization (int8-weight-only), or
        the calibrated int8 x int8 -> int32 program (int8-w8a8)."""
        from kubernetes_deep_learning_tpu.models import build_forward
        from kubernetes_deep_learning_tpu.ops import quantize as quant_lib

        if self._quantization_active == quant_lib.SCHEME_W8A8:
            # Exact flax graph with every calibrated conv/dense swapped for
            # the int8-operand form; the fused Pallas fast path is bypassed
            # (its kernels are a float operand-layout contract).
            return quant_lib.build_w8a8_forward(self.spec)
        base = build_forward(self.spec, dtype=dtype, fast=self._fast)
        if self._quantization is None:
            return base

        def forward(variables, images):
            return base(quant_lib.dequantize_variables(variables), images)

        return forward

    def donation_info(self, bucket: int) -> dict[str, bool]:
        """Whether the compiled forward donates its arguments at one bucket
        shape, from jax.stages.Lowered.args_info (trace+lower only -- no
        XLA compile, no device work).  The regression surface for the
        donation audit: ``images`` must be True on every bucket (unless
        KDLT_DONATE=0), ``variables`` must ALWAYS be False -- donating the
        weights would free them under the next request.
        """
        import jax

        (var_info, img_info), _kwargs = self._jitted.lower(
            self._variables, self._zero_wire(bucket)
        ).args_info
        return {
            "variables": any(
                bool(i.donated) for i in jax.tree_util.tree_leaves(var_info)
            ),
            "images": all(
                bool(i.donated) for i in jax.tree_util.tree_leaves(img_info)
            ),
        }

    @property
    def sharding(self) -> str:
        """The engine's sharding-scheme tag (parallel.mesh.SHARDING_SCHEMES)."""
        return self._sharding_scheme

    def sharding_info(self) -> dict[str, Any]:
        """The registry/status surface for GET /v1/models: scheme tag,
        model-parallel degree, mesh shape, per-device resident param bytes."""
        info: dict[str, Any] = {
            "sharding": self._sharding_scheme,
            "model_parallel": 1,
            "mesh_shape": None,
        }
        if self.mesh is not None:
            from kubernetes_deep_learning_tpu.parallel import mesh as mesh_par

            mesh_shape = dict(self.mesh.shape)
            info["model_parallel"] = int(mesh_shape.get(mesh_par.MODEL_AXIS, 1))
            info["mesh_shape"] = {
                str(axis): int(size) for axis, size in mesh_shape.items()
            }
            info["param_bytes_per_device"] = mesh_par.param_bytes_per_device(
                self._variables
            )
            if self.mesh_mode == "data":
                info["batch_rows_per_device"] = self._batch_rows_per_device()
        return info

    _batch_rows: dict[str, int] | None = None

    def _batch_rows_per_device(self) -> dict[str, int]:
        """Rows of a max-bucket batch each device really holds, observed
        from ``addressable_shards`` of a batch placed with the sharding the
        serving jit declares for its input (parallel.mesh.batch_sharding):
        the proof that a batch is split over the chips rather than sitting
        whole on the first.  Placed once, then cached."""
        if self._batch_rows is None:
            import jax

            from kubernetes_deep_learning_tpu.parallel import mesh as mesh_par

            placed = jax.device_put(
                self._zero_wire(self.max_batch),
                mesh_par.batch_sharding(self.mesh),
            )
            self._batch_rows = {
                str(shard.device.id): int(shard.data.shape[0])
                for shard in placed.addressable_shards
            }
        return self._batch_rows

    def fused_blocks(self) -> dict[str, list[str]]:
        """By warmed bucket, the blocks its program runs as Pallas kernels:
        what the fast forward decided while it traced (the same shape
        arithmetic, models.fused_blocks); empty off the fused path.  A
        mesh engine's forward sees one shard's rows."""
        if not self._fast_engaged:
            return {}
        from kubernetes_deep_learning_tpu.models import fused_blocks

        shards = 1
        if self.mesh is not None:
            from kubernetes_deep_learning_tpu.parallel.mesh import DATA_AXIS

            shards = self.mesh.shape[DATA_AXIS]
        return {
            str(b): fused_blocks(self.spec, b // shards)
            for b in sorted(self._warm_bucket_seconds)
        }

    def device_info(self) -> dict[str, Any]:
        """The status surface that keeps a green boot honest (GET
        /v1/models): the device as JAX reports it, its dense peak (None = a
        device_kind the table does not know), whether the fused path is in
        the served programs or was degraded away and which blocks each
        warmed bucket's program fuses, each bucket's warm-up seconds, and
        the device's memory as its allocator reports it (absent where the
        backend reports none, as the CPU's does)."""
        import jax

        info = {
            "platform": self._device.platform,
            "device_kind": self._device.device_kind,
            "device_count": len(jax.local_devices()),
            "peak_tflops": flops_lib.peak_tflops(
                self._device, str(self._compute_dtype)
            ),
            "fast_engaged": bool(self._fast_engaged),
            "fast_degraded": bool(self.fast_degraded),
            "fused_blocks": self.fused_blocks(),
            "warm": dict(self.warm_report),
        }
        stats = self._device.memory_stats()
        if stats:
            # A program's scratch is booked under *_reserved, apart from
            # the buffers under *_in_use; both occupy the chip.
            info["memory"] = {
                k: int(stats[k])
                for k in ("bytes_in_use", "peak_bytes_in_use",
                          "peak_bytes_reserved", "bytes_limit")
                if k in stats
            }
        return info

    def bucket_audit(self) -> dict[str, Any]:
        """Per-bucket padding-waste + FLOPs audit (/debug/profile?audit=
        buckets): admitted-vs-bucket sizes over the recent dispatch history
        plus FLOPs/img from the lowered cost analysis (cached, trace-only
        -- never an XLA compile).  A high padding_waste_ratio means the
        bucket ladder, not the program, is burning the flops; MFU off-box is
        kdlt_engine_images_total's rate x flops_per_image / peak_tflops."""
        hist = list(self._bucket_history)
        out: dict[str, Any] = {"window": len(hist), "buckets": {}}
        for b in self.buckets:
            admitted = [n for bucket, n in hist if bucket == b]
            total = sum(admitted)
            out["buckets"][int(b)] = {
                "batches": len(admitted),
                "mean_admitted": (total / len(admitted)) if admitted else None,
                "padding_waste_ratio": (
                    1.0 - total / (len(admitted) * b) if admitted else None
                ),
                "flops_per_image": self._audit_flops_for(b),
            }
        return out

    def _audit_flops_for(self, bucket: int) -> float | None:
        """FLOPs/img for the audit: computed on the first ask, then cached
        (lowering-only analysis, seconds of host time on the asking
        thread; nothing lowers unasked)."""
        with self._audit_flops_lock:
            if bucket in self._audit_flops:
                return self._audit_flops[bucket]
        try:
            val = self._flops_per_image(bucket)
        except Exception:  # noqa: BLE001 - exported-only families raise inside
            val = None
        with self._audit_flops_lock:
            self._audit_flops[bucket] = val
        return val

    def _flops_per_image(self, bucket: int) -> float | None:
        """FLOPs/image at one bucket shape, for the bucket audit.

        Uses the NON-fused flax graph (cost analysis cannot see inside
        Pallas custom calls) and the LOWERING-level
        analysis -- trace only, never an XLA compile.  Families with no
        in-tree model (exported-only artifacts) raise inside; the audit
        reports None for them.
        """
        import jax
        import jax.numpy as jnp

        from kubernetes_deep_learning_tpu.models import build_forward

        base = build_forward(
            self.spec, dtype=jnp.dtype(self._compute_dtype), fast=False
        )
        # _quantization_active is None exactly when the variables were
        # host-dequantized at load (sequence-mesh serving); everywhere else
        # the tree still carries the {_q8, _q8_scale} wire form.
        if self._quantization is not None and self._quantization_active is not None:
            from kubernetes_deep_learning_tpu.ops.quantize import (
                dequantize_variables,
            )

            exact = base

            def base(variables, images):  # noqa: F811 - wrapped exact forward
                return exact(dequantize_variables(variables), images)

        return flops_lib.lowered_flops_per_image(
            jax.jit(wired(base, self.spec.input_shape)), bucket,
            self._variables, self._zero_wire(bucket),
        )

    def _f32_forward(self):
        """Lazily build the float32 debug-path fn (exported artifacts only)."""
        if self._jitted_f32 is None:
            with self._f32_lock:
                if self._jitted_f32 is None:
                    import jax
                    import jax.numpy as jnp

                    self._jitted_f32 = jax.jit(
                        self._live_forward(jnp.dtype(self._compute_dtype))
                    )
        return self._jitted_f32

    # --- raw-bytes ingest dispatch (GUIDE 10q) ---------------------------
    # Class-level defaults so the three construction paths (mesh spmd,
    # mesh replicated, single-device) need no per-path __init__ wiring;
    # the first predict_ingest_async resolves and caches them lazily.
    _ingest_staging: tuple[int, int] | None = None
    _ingest_staging_resolved = False
    _ingest_jitted = None

    def _resolve_ingest_staging(self) -> tuple[int, int] | None:
        if not self._ingest_staging_resolved:
            with self._f32_lock:
                if not self._ingest_staging_resolved:
                    staging = None if self.mesh is not None else ingest_device_resize()
                    if staging == tuple(self.spec.input_shape[:2]):
                        staging = None  # no-op resize: use the plain forward
                    self._ingest_staging = staging
                    self._ingest_staging_resolved = True
        return self._ingest_staging

    @property
    def ingest_source_shape(self) -> tuple[int, int, int]:
        """Per-image (H, W, C) the bytes-wire decode stage must produce.

        spec.input_shape normally; the staging resolution when
        KDLT_INGEST_DEVICE_RESIZE is set (mesh engines ignore the knob:
        the fused resize program is single-device, and the mesh jit's
        sharding constraints are built for input_shape).
        """
        staging = self._resolve_ingest_staging()
        if staging is None:
            return self.spec.input_shape
        return (*staging, self.spec.input_shape[2])

    def _ingest_fused(self):
        """Lazily build the fused device resize -> forward program.

        One jitted program: uint8 staging batch -> f32 -> jax.image.resize
        to spec HxW (method from spec.resize_filter) -> round/clip back to
        uint8 -> the live forward (whose first op is the fused-into-conv
        normalization, so resize+normalize+conv all sit in one XLA
        program, one H2D of the staging-resolution batch).  Requires an
        in-tree model family (exported-only artifacts have no live
        forward); _live_forward raises for those, at first use.
        """
        if self._ingest_jitted is None:
            src = self.ingest_source_shape  # takes _f32_lock itself, once
            with self._f32_lock:
                if self._ingest_jitted is None:
                    import jax
                    import jax.numpy as jnp

                    h, w, c = self.spec.input_shape
                    method = (
                        "nearest" if self.spec.resize_filter == "nearest" else "linear"
                    )
                    inner = self._live_forward(jnp.dtype(self._compute_dtype))

                    def fused(variables, wire):
                        batch = from_wire(wire, src)
                        x = batch.astype(jnp.float32)
                        x = jax.image.resize(
                            x, (batch.shape[0], h, w, c), method=method
                        )
                        x = jnp.clip(jnp.round(x), 0.0, 255.0).astype(jnp.uint8)
                        return inner(variables, x)

                    self._ingest_jitted = _donate_jit(fused, self._donate)
        return self._ingest_jitted

    def predict_ingest_async(self, images: np.ndarray):
        """Bytes-wire dispatch hook: uint8 batch at ``ingest_source_shape``.

        Default (no staging): exactly predict_async -- the decode stage
        already resized to spec.input_shape on host (bit-exact with the
        legacy gateway preprocessing), and normalization fuses into the
        first conv on device, so bytes-wire logits equal tensor-wire
        logits by construction.  With KDLT_INGEST_DEVICE_RESIZE=HxW the
        decode stage hands over HxW uint8 and the fused program resizes
        on device ahead of the forward (approximate numerics; staging
        only).  Same aliasing/pipelining contract as predict_async.
        """
        staging = self._resolve_ingest_staging()
        if staging is None:
            return self.predict_async(images)
        # kdlt-lint: disable=hot-path-sync -- normalizes the caller's host input (list -> ndarray); no device handle is involved, so nothing can block on device work
        images = np.asarray(images)
        src = self.ingest_source_shape
        if images.ndim != 4 or images.shape[1:] != src:
            raise ValueError(f"expected (N, {src}), got {images.shape}")
        if images.dtype != np.uint8:
            raise ValueError(
                f"predict_ingest_async takes uint8 images, got {images.dtype}"
            )
        n = images.shape[0]
        batch, copied = stage(images, self.bucket_for(n))
        self._m_input["copy" if copied else "view"].inc()
        self._ingest_fused()  # build outside the dispatch lock
        with self._lock:
            # kdlt-lint: disable=lock-around-jit -- same serialized-enqueue contract as predict_async: dispatch is async, the lock covers only the enqueue, and donated-buffer dispatches must not interleave
            logits = self._ingest_jitted(self._variables, batch)
        return logits, n

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"batch {n} exceeds max bucket {self.max_batch}")

    def predict_async(self, images: np.ndarray):
        """Dispatch a uint8 batch WITHOUT the host sync; returns (device_logits, n).

        The caller materializes with ``np.asarray(device_logits)[:n]`` when
        it needs the values -- letting it stage and dispatch the NEXT batch
        while this one executes (the batcher's pipelining hook).

        Aliasing contract: ``images`` must stay unmodified until the result
        is materialized.  A whole bucket of C-contiguous rows is handed to
        the program as a VIEW of the caller's memory (its wire form;
        read-only and unaligned memory is fine), and this call returns when
        the transfer is enqueued, not when it is done: the runtime reads
        that memory on its own threads afterwards, until the transfer
        completes on a TPU, and for as long as the program runs wherever
        the client aliases host memory zero-copy (the CPU client can).
        Only a batch the engine had to copy first (n < bucket, or not
        contiguous: counted ``path="copy"``) is free at return.  So a
        caller with a reusable staging buffer must rotate depth+1 buffers
        or copy -- see NativeBatcher's staging-buffer ring.
        InFlightDispatcher is the general pipelining wrapper over this
        hook: bounded in-flight depth, FIFO completion thread, futures.
        """
        # kdlt-lint: disable=hot-path-sync -- normalizes the caller's host input (list/bytes -> ndarray); no device handle is involved, so nothing can block on device work
        images = np.asarray(images)
        if images.ndim != 4 or images.shape[1:] != self.spec.input_shape:
            raise ValueError(
                f"expected (N, {self.spec.input_shape}), got {images.shape}"
            )
        if images.dtype != np.uint8:
            raise ValueError(f"predict_async takes uint8 images, got {images.dtype}")
        n = images.shape[0]
        batch, copied = stage(images, self.bucket_for(n))
        self._m_input["copy" if copied else "view"].inc()
        with self._lock:
            # kdlt-lint: disable=lock-around-jit -- serialized enqueue is the documented contract: dispatch is async (returns an unmaterialized handle), so the lock covers only the enqueue, and XLA requires donated-buffer dispatches not to interleave
            logits = self._jitted(self._variables, batch)
        return logits, n

    def record_completed(self, n: int, seconds: float) -> None:
        """Account a successfully SYNCED async batch (counters + latency).

        predict() accounts its own sync path; async callers (NativeBatcher.
        _finish) report here after materialization succeeds, so failed
        batches never inflate the success counters, and
        kdlt_engine_infer_seconds keeps emitting on the pipelined path.
        The reported interval is dispatch->sync, which under pipelining can
        include bounded queue-wait/assembly overlap (see the histogram help).
        """
        self._m_infer_latency.observe(seconds)
        self._m_images.inc(n)
        self._m_batches.inc()
        bucket = self.bucket_for(n)
        self._m_pad_waste.inc(bucket - n)
        self._bucket_history.append((bucket, n))
        if self._m_mesh is not None:
            self._m_mesh["collective"].inc(seconds)

    def predict(self, images: np.ndarray) -> np.ndarray:
        """uint8 (N,H,W,C) -> float32 logits (N,num_classes); pads to bucket."""
        images = np.asarray(images)
        if images.dtype == np.uint8:
            t0 = time.perf_counter()
            logits, n = self.predict_async(images)
            out = np.asarray(logits)  # device sync
            self.record_completed(n, time.perf_counter() - t0)
            return out[:n]
        if images.dtype != np.float32:
            raise ValueError(
                f"dtype {images.dtype} unsupported: send uint8 pixels or "
                "float32 pre-normalized data"
            )
        if images.ndim != 4 or images.shape[1:] != self.spec.input_shape:
            raise ValueError(
                f"expected (N, {self.spec.input_shape}), got {images.shape}"
            )
        fn = self._f32_forward()
        n = images.shape[0]
        bucket = self.bucket_for(n)
        if bucket != n:
            pad = np.zeros((bucket - n, *self.spec.input_shape), images.dtype)
            batch = np.concatenate([images, pad], axis=0)
        else:
            batch = images
        # No latency sample here: the debug path's lazy first compile would
        # land a tens-of-seconds outlier in the serving histogram.
        with self._f32_lock:
            out = np.asarray(fn(self._variables, batch))
        self._m_images.inc(n)
        self._m_batches.inc()
        self._m_pad_waste.inc(bucket - n)
        return out[:n]

    def predict_scores(self, images: np.ndarray) -> list[dict[str, float]]:
        """Labelled score dicts, the reference's response shape
        (reference model_server.py:46-49)."""
        logits = self.predict(images)
        labels = self.spec.labels
        return [dict(zip(labels, map(float, row))) for row in logits]
