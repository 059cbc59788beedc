"""Cross-host SPMD serving: one frontend, a model sharded over processes.

The reference scales across hosts only by k8s replicas -- each pod holds a
whole model (SURVEY.md section 2).  Round 1 kept that limit ("serving meshes
are host-local", VERDICT r1 weak-4): a per-request HTTP handler cannot drive
a multi-process SPMD program, because EVERY process of the global runtime
must enter the same dispatch in lockstep with its shard of the data.

This module supplies the missing coordination.  After
``utils.distributed.initialize`` joins all processes into one runtime:

- every process builds the same ``CrossHostForward`` over a global mesh;
- **followers** (process_id > 0) block in ``follower_loop()``;
- the **leader** (process 0, where the HTTP/gRPC frontend lives) calls
  ``predict(images)`` / ``predict_async(images)`` per round: the batch is
  broadcast to all processes (``multihost_utils.broadcast_one_to_all`` --
  DCN), each process device_puts its LOCAL batch shard, all enter the
  jitted SPMD forward (collectives ride ICI within a slice / DCN across).

Dispatch protocol (round 3 -- two-phase; round 5 -- side-channel): each
round carries a tiny CONTROL header ``(flag, aux)`` plus a payload whose
shape the control determined -- so the fleet supports a real bucket LADDER
instead of round 2's single fixed dispatch shape, plus hot version reload.
Since round 5 the control+payload ride a dedicated host-side TCP control
channel (leader -> every follower; bootstrapped once through the
jax.distributed key-value store) instead of device-collective broadcasts.
Two reasons: (a) a device-collective broadcast executes on the SAME cores
as the serving program, so it can never overlap an in-flight round's
collective -- the side channel is what makes pipelining possible at all;
(b) on the CPU Gloo backend, collective ops from concurrently executing
programs corrupt each other on the shared TCP pairs (ops match by wire
order), so the data plane must be the ONLY collective traffic.  The aux
rides as an int64 header field (exact for timestamp-sized version
numbers, ADVICE r3's concern; no float/int32 canonicalization applies --
the header never touches a device).

- ``PREDICT``/``PREDICT_FAST``: aux = bucket; payload = the (bucket, H, W,
  C) uint8 batch.  The flag carries the fleet-wide execution mode: the
  LEADER resolves fast vs exact once (AOT-probing the fused program's
  compile on every bucket -- resolve_mode) and every round broadcasts the
  decision, so a fused-path compile failure degrades the WHOLE fleet to
  the exact graph in lockstep; a follower never discovers a Mosaic
  failure mid-collective on its own.
- ``RELOAD``:  aux = version; no payload.  Every process loads that version
  from its OWN model root (shared storage or identical image -- the same
  assumption boot-time loading already makes) and re-shards the variables.
- ``SHUTDOWN``: no payload; followers return.

**Pipelined dispatch (round 5).**  Rounds used to run strict lockstep: the
leader blocked on a host-side ``process_allgather`` before the next round
could even start, serializing DCN broadcast/gather time against device
execution.  Two changes remove that serialization:

1. The jitted forward's logits are now FULLY REPLICATED
   (``build_sharded_jit(replicate_out=True)``): the gather happens ON
   DEVICE inside the program, so readback is a plain local
   ``np.asarray`` -- no host collective.  The only remaining host-side
   cross-process operations are the control/payload broadcasts.
2. ``predict_async`` broadcasts + dispatches round N+1 WITHOUT
   synchronizing on round N's result, bounded by a per-round in-flight
   budget (``KDLT_XH_PIPELINE_DEPTH``, default 2; depth 1 reproduces
   lockstep exactly).  Readback happens whenever the returned handle is
   materialized -- in serving, on the InFlightDispatcher's FIFO
   completion thread (runtime.engine), which also yields the per-stage
   ``kdlt_pipeline_*`` metrics with an ``engine="crosshost"`` label.

Ordering safety: because readback carries no collective, every process
enqueues the SAME sequence of cross-process operations (control, payload,
forward program) from exactly ONE thread (the leader's round lock / the
follower's loop), so overlapped rounds can never reorder a collective
against a peer -- the classic multi-controller deadlock.  Followers keep
accepting rounds without blocking on each round's device result, bounded
by the same depth, with their own EWMA-based stall detection
(``KDLT_XH_STALL_FLOOR_S`` / ``KDLT_XH_STALL_MULTIPLE``): a wedged
collective (dead peer) exits 70 for a gang restart, exactly like the
leader's watchdog.

Crash semantics (k8s restart story): the fleet is one gang.  If a follower
dies mid-round, the leader's broadcast or collective wedges -- the
leader's EWMA round watchdog (armed only after a (mode, bucket)'s first
compile completes; ``round_timeout_s`` floors the steady-state bound)
exits the process (code 70), and the pod's restart then restarts the
WHOLE fleet together (jax.distributed processes cannot rejoin a live
runtime).  If the leader dies, followers' pending broadcast errors out of
``follower_loop`` and their pods restart the same way.  Failure modes are
provable, not assumed: the ``crosshost.broadcast`` and
``crosshost.collective`` fault points (serving.faults, ``KDLT_FAULTS``)
inject errors/hangs on either side of the protocol.
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time
from collections import deque
from typing import Any

import numpy as np

from kubernetes_deep_learning_tpu.modelspec import ModelSpec
from kubernetes_deep_learning_tpu.parallel.mesh import DATA_AXIS
from kubernetes_deep_learning_tpu.utils import trace as trace_lib

_SHUTDOWN, _PREDICT, _RELOAD, _PREDICT_FAST = 0, 1, 2, 3
# Compressed-payload variants of the two predict flags (the cross-host
# payload diet): same control header, payload = 1 codec byte + compressed
# batch bytes.  The FLAG is the negotiation -- the leader resolves
# $KDLT_XH_COMPRESS once and every follower dispatches on the flag it
# receives, so a fleet needs no config agreement for this knob, and with
# compression off the wire (legacy flags, raw payload) is byte-identical
# to pre-diet builds.
_PREDICT_Z, _PREDICT_FAST_Z = 4, 5
# Raw-bytes ingest variants (GUIDE 10q): payload is the packed ENCODED
# JPEG/PNG blobs (protocol.encode_bytes_predict_request), aux still the
# bucket; every process decodes locally (ops.preprocess.BatchDecoder --
# deterministic, so the fleet stays bit-identical).  The leader decodes
# BEFORE broadcasting: a corrupt client blob raises there (-> HTTP 400)
# and followers only ever receive decodable rounds, so bad bytes can
# never wedge or gang-restart the fleet.  No codec composition with
# _PREDICT_Z: the blobs are already entropy-coded.
_PREDICT_ENC, _PREDICT_ENC_FAST = 6, 7

# Broadcast payload codec: "", "0", "off", "none" -> raw legacy wire;
# "1"/"on"/"zlib" -> zlib level 1 (stdlib, fast, padded uint8 batches
# compress well -- the pad rows are pure zeros); "lz4" -> lz4.frame when
# the package is importable, degrading to zlib on stdlib-only containers.
XH_COMPRESS_ENV = "KDLT_XH_COMPRESS"
_XH_CODEC_ZLIB, _XH_CODEC_LZ4 = 1, 2

# Watchdog slack for rounds that include a compile: the first round per
# (mode, bucket) after an install traces+compiles the SPMD program (tens
# of seconds; minutes on big models), which a flat round timeout would
# misread as a dead peer -- exit(70) -> recompile -> crash loop.
# The steady-state watchdog arms only once a (mode, bucket) has a completed
# round to base an EWMA on; until then only this slack multiple of the
# round timeout backstops an infinitely wedged compile round.
_COMPILE_TIMEOUT_FACTOR = 10.0

# Per-round in-flight budget for cross-host dispatch (the cross-host analog
# of runtime.engine's KDLT_PIPELINE_DEPTH): how many rounds the leader may
# broadcast+dispatch ahead of the oldest unmaterialized result, and how
# many rounds a follower accepts ahead of its own device completions.
# Depth 1 is exact lockstep (each round fully materialized before the next
# broadcast); depth 2 overlaps round N+1's DCN broadcast + host assembly
# with round N's collective execution.  Every process of a fleet must run
# the same depth (same env, like every other fleet-wide config).
XH_PIPELINE_DEPTH_ENV = "KDLT_XH_PIPELINE_DEPTH"
DEFAULT_XH_PIPELINE_DEPTH = 2

# Follower-side stall detection (the followers' counterpart of the leader's
# round watchdog, EWMA-based like the PR 3 engine watchdog): an in-flight
# round stuck past max(floor, multiple x the (mode, bucket)'s EWMA) exits
# 70 for a gang restart.  Floor <= 0 disables.
XH_STALL_FLOOR_S_ENV = "KDLT_XH_STALL_FLOOR_S"
XH_STALL_MULTIPLE_ENV = "KDLT_XH_STALL_MULTIPLE"
DEFAULT_XH_STALL_FLOOR_S = 30.0
DEFAULT_XH_STALL_MULTIPLE = 10.0


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw.strip() else default
    except ValueError:
        return default


def resolve_xh_compress(raw: str | None = None) -> str | None:
    """$KDLT_XH_COMPRESS -> the broadcast payload codec name, or None.

    Leader-side only: the per-round control flag carries the decision to
    followers (see _PREDICT_Z), so only the leader's environment matters.
    An unknown value fails loudly at boot -- a typo silently serving
    uncompressed would defeat the knob without a trace.
    """
    value = (os.environ.get(XH_COMPRESS_ENV, "") if raw is None else raw)
    value = value.strip().lower()
    if value in ("", "0", "off", "none", "false"):
        return None
    if value in ("1", "on", "true", "zlib"):
        return "zlib"
    if value == "lz4":
        try:
            import lz4.frame  # noqa: F401
        except ImportError:
            return "zlib"
        return "lz4"
    raise ValueError(
        f"{XH_COMPRESS_ENV}={value!r}: expected off, zlib, or lz4"
    )


def _compress_payload(codec: str, raw: bytes) -> bytes:
    """codec byte + compressed blob (the _PREDICT_Z payload layout)."""
    if codec == "lz4":
        import lz4.frame

        return bytes((_XH_CODEC_LZ4,)) + lz4.frame.compress(raw)
    import zlib

    # Level 1: the broadcast is latency-bound, and the zero pad rows of a
    # partially filled bucket compress to nothing at any level.
    return bytes((_XH_CODEC_ZLIB,)) + zlib.compress(raw, 1)


def _decompress_payload(payload: bytes) -> bytes:
    """Inverse of _compress_payload, dispatching on the codec byte."""
    if not payload:
        raise ValueError("compressed cross-host round with empty payload")
    codec, blob = payload[0], payload[1:]
    if codec == _XH_CODEC_LZ4:
        import lz4.frame

        return lz4.frame.decompress(blob)
    if codec == _XH_CODEC_ZLIB:
        import zlib

        return zlib.decompress(blob)
    raise ValueError(f"unknown cross-host payload codec byte {codec}")


# Control-channel wire format: one fixed header per round -- flag (i32),
# aux (i64: bucket or version), payload byte count (i64) -- followed by the
# raw uint8 payload (the padded batch; empty for RELOAD/SHUTDOWN).
_CTL_HEADER = struct.Struct("<iqq")
_CTL_ADDR_KEY = "kdlt/xh/control-addr"
# Control-channel bring-up shares the runtime's join deadline: every
# process is inside CrossHostForward.__init__ at the same boot phase.
_CTL_SETUP_TIMEOUT_ENV = "KDLT_DIST_INIT_TIMEOUT_S"
_DEFAULT_CTL_SETUP_TIMEOUT_S = 300.0


def _dist_kv_client():
    """The jax.distributed coordination-service client (its KV store
    bootstraps the control channel); raises if the runtime never joined."""
    from jax._src import distributed

    client = getattr(distributed.global_state, "client", None)
    if client is None:
        raise RuntimeError(
            "cross-host serving requires jax.distributed (utils.distributed."
            "initialize) -- the control channel bootstraps through its "
            "key-value store"
        )
    return client


def _advertised_host() -> str:
    """The address followers can reach THIS process on: the local address
    of a (connectionless) route toward the coordinator -- every process
    can reach the coordinator, so the reverse path serves the control
    channel too.  Falls back to the hostname (k8s StatefulSet pod DNS)."""
    from kubernetes_deep_learning_tpu.utils import distributed as dist_mod

    coord = os.environ.get(dist_mod.COORDINATOR_ENV, "")
    if coord and ":" in coord:
        host, port = coord.rsplit(":", 1)
        try:
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.connect((host, int(port)))
                return s.getsockname()[0]
        except OSError:
            pass
    return socket.gethostname()


def resolve_xh_pipeline_depth(depth: int | None = None) -> int:
    """Cross-host in-flight budget: explicit arg > $KDLT_XH_PIPELINE_DEPTH
    > 2.  Clamped to >= 1; a typo'd env value degrades to the default
    rather than killing the fleet boot."""
    if depth is None:
        raw = os.environ.get(XH_PIPELINE_DEPTH_ENV, "")
        try:
            depth = int(raw) if raw.strip() else DEFAULT_XH_PIPELINE_DEPTH
        except ValueError:
            depth = DEFAULT_XH_PIPELINE_DEPTH
    return max(1, int(depth))


def artifact_variables_for_sharding(artifact):
    """An artifact's variables ready for shard_variables: int8 weight-only
    trees (ops.quantize) dequantize host-side first, because the partition
    rules address float kernel leaves (same handling as the engine's mesh
    path and _serve_cross_host's boot path)."""
    if artifact.metadata.get("quantization"):
        from kubernetes_deep_learning_tpu.ops.quantize import (
            SCHEME,
            dequantize_variables_host,
        )

        if artifact.metadata["quantization"] != SCHEME:
            raise ValueError(
                f"unknown quantization scheme {artifact.metadata['quantization']!r}"
            )
        return dequantize_variables_host(artifact.variables)
    return artifact.variables


class RoundStallWatch:
    """EWMA-based stall detection over an in-flight round ledger.

    The cross-host analog of runtime.engine's dispatch watchdog, shared by
    the leader (round watchdog) and the followers (completion-side stall
    detection).  Entries are begun at dispatch and completed at
    materialization; a scanning thread declares a stall when the oldest
    in-flight entry outlives its bound:

    - a (mode, bucket) key with NO completed sample yet is a COMPILE
      round: the steady-state watchdog is not armed for it (a compile
      takes tens of seconds and a flat bound would misread it as a dead
      peer); only ``compile_slack_s`` (0 = unbounded) backstops an
      infinitely wedged compile.
    - once a key has a sample, bound = max(floor, multiple x EWMA).

    A blocked DCN collective cannot be interrupted from Python, so the
    stall action defaults to exit(70) -- the pod restart then restarts the
    whole gang.  ``on_stall`` is injectable for tests.  ``reset()`` drops
    the EWMA table (a reload rebuilds every program, so first rounds per
    key regain compile slack).
    """

    def __init__(
        self,
        floor_s: float,
        multiple: float,
        compile_slack_s: float = 0.0,
        label: str = "round",
        on_stall=None,
    ):
        self._floor_s = floor_s
        self._multiple = multiple
        self._compile_slack_s = compile_slack_s
        self._label = label
        self._on_stall = on_stall
        self._lock = threading.Lock()
        self._inflight: dict[int, tuple[Any, float]] = {}  # seq -> (key, t0)
        self._ewma: dict[Any, float] = {}
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self.enabled = floor_s > 0

    def begin(self, seq: int, key: Any) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._inflight[seq] = (key, time.perf_counter())
            if self._thread is None:
                interval = max(0.01, min(1.0, self._floor_s / 5.0))
                self._thread = threading.Thread(
                    target=self._loop, args=(interval,),
                    name=f"kdlt-xh-watch-{self._label}", daemon=True,
                )
                self._thread.start()

    def complete(self, seq: int, seconds: float | None = None) -> None:
        if not self.enabled:
            return
        with self._lock:
            entry = self._inflight.pop(seq, None)
            if entry is not None and seconds is not None:
                key = entry[0]
                prev = self._ewma.get(key)
                self._ewma[key] = (
                    seconds if prev is None else 0.7 * prev + 0.3 * seconds
                )

    def reset(self) -> None:
        """Drop expectations (hot reload: every program recompiles)."""
        with self._lock:
            self._ewma.clear()

    def stop(self) -> None:
        self._stop.set()

    def _bound_s(self, key: Any) -> float:
        expected = self._ewma.get(key)
        if expected is None:  # compile round: steady-state watchdog unarmed
            return self._compile_slack_s if self._compile_slack_s > 0 else float("inf")
        return max(self._floor_s, self._multiple * expected)

    def _loop(self, interval: float) -> None:
        while not self._stop.wait(interval):
            now = time.perf_counter()
            with self._lock:
                overdue = [
                    (seq, key, now - t0)
                    for seq, (key, t0) in self._inflight.items()
                    if now - t0 > self._bound_s(key)
                ]
            if overdue:
                self._fire(overdue)
                return

    def _fire(self, overdue) -> None:
        seq, key, age = min(overdue)
        msg = (
            f"CRITICAL cross-host {self._label} round {seq} (key {key}) "
            f"in flight {age:.1f}s, past its stall bound (dead peer?); "
            "exiting 70 for a gang restart"
        )
        if self._on_stall is not None:
            self._on_stall(msg)
            return
        print(msg, flush=True)
        os._exit(70)


class _PendingRound:
    """Async handle for one dispatched cross-host round.

    ``np.asarray(handle)`` (or ``block_until_ready``) performs the
    materialization -- a LOCAL device sync + D2H with no cross-process
    host collective, thanks to the replicated-output program -- and
    releases the round's in-flight slot exactly once.  Safe to call from
    any thread (the serving path materializes on the InFlightDispatcher's
    completion thread while the next round broadcasts).
    """

    __slots__ = (
        "_owner", "_arr", "_seq", "_key", "_t_dispatch", "_walls",
        "_traces", "_lock", "_result", "_exc",
    )

    def __init__(self, owner, arr, seq, key, t_dispatch, walls, traces):
        self._owner = owner
        self._arr = arr
        self._seq = seq
        self._key = key
        self._t_dispatch = t_dispatch
        self._walls = walls  # (w_bcast_start, w_dispatched)
        self._traces = traces
        self._lock = threading.Lock()
        self._result = None
        self._exc = None

    def block_until_ready(self):
        self._materialize()
        return self

    def __array__(self, dtype=None):
        out = self._materialize()
        return out if dtype is None else out.astype(dtype, copy=False)

    def _materialize(self) -> np.ndarray:
        with self._lock:
            if self._result is None and self._exc is None:
                seconds = None
                try:
                    self._arr.block_until_ready()
                    t_exec = time.perf_counter()
                    w_exec = trace_lib.now_s() if self._traces else 0.0
                    out = np.asarray(self._arr)  # local D2H; no collective
                    seconds = t_exec - self._t_dispatch
                    self._owner._record_round(
                        self._key, seconds,
                        time.perf_counter() - t_exec,
                    )
                    if self._traces:
                        _, w1 = self._walls
                        for tr in self._traces:
                            tr.record(
                                "crosshost.collective", w1, w_exec - w1,
                                bucket=self._key[1],
                            )
                            tr.record(
                                "crosshost.gather", w_exec,
                                trace_lib.now_s() - w_exec,
                            )
                    self._result = out
                except Exception as e:  # device-side failure surfaces here
                    self._exc = e
                finally:
                    self._arr = None  # free the device reference
                    self._owner._finish_round(self._seq, seconds)
        if self._exc is not None:
            raise self._exc
        return self._result


class CrossHostForward:
    """Pipelined SPMD forward over all processes of the global runtime."""

    def __init__(
        self,
        spec: ModelSpec,
        mesh,
        variables: Any,
        buckets: Any = (0,),
        dtype: Any = None,
        model_root: str | None = None,
        model_name: str | None = None,
        round_timeout_s: float = 0.0,
        fast: Any = "auto",
        pipeline_depth: int | None = None,
    ):
        """``buckets``: dispatch ladder; each entry is rounded up to a
        multiple of the data-axis size (0 = the axis size itself).
        ``model_root``/``model_name`` enable RELOAD (every process must see
        the same versioned artifact tree).  ``round_timeout_s`` > 0 arms
        the leader's per-round watchdog: it floors the EWMA-based stall
        bound for steady-state rounds, and x10 of it backstops compile
        rounds (see module docstring).  ``fast`` resolves per
        parallel.dataparallel.resolve_sharded_fast; when it resolves, the
        leader AOT-probes the fused program at every bucket and broadcasts
        fast/exact per round (module docstring).  ``pipeline_depth``: the
        per-round in-flight budget (None = $KDLT_XH_PIPELINE_DEPTH or 2;
        1 = exact lockstep)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from kubernetes_deep_learning_tpu.parallel.dataparallel import (
            resolve_sharded_fast,
        )
        from kubernetes_deep_learning_tpu.serving import faults as faults_lib

        self.spec = spec
        self.mesh = mesh
        n_data = mesh.shape[DATA_AXIS]
        self.buckets = tuple(sorted({-(-(b or n_data) // n_data) * n_data for b in buckets}))
        self.bucket = self.buckets[-1]  # largest; also the legacy attr
        self._batch_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self._dtype = dtype or jnp.bfloat16
        self.model_root = model_root
        self.model_name = model_name
        self.round_timeout_s = round_timeout_s
        self.version: int | None = None
        self.pipeline_depth = resolve_xh_pipeline_depth(pipeline_depth)
        # In-flight budget: predict_async blocks here once ``depth`` rounds
        # are dispatched but not yet materialized; reload/shutdown drain by
        # acquiring every slot.  Acquire ORDER is always slot -> round
        # lock, so a drainer holding all slots can never deadlock a
        # submitter holding the lock.
        self._slots = threading.BoundedSemaphore(self.pipeline_depth)
        self._seq = 0
        # Fault injection (serving.faults): crosshost.broadcast fires
        # before each round's control/payload broadcast (either side),
        # crosshost.collective before the SPMD dispatch; None (the inert
        # fast path) unless $KDLT_FAULTS configures rules.
        self._faults = faults_lib.from_env()
        # Broadcast payload codec (leader-side; carried per round in the
        # control flag, so followers ignore their own env for this).
        self._xh_codec = resolve_xh_compress()
        self._metrics: dict | None = None
        # Leader round watchdog: EWMA-based (PR 3 style), armed per
        # (mode, bucket) only after that key's first -- compiling -- round
        # completes; round_timeout_s floors the steady-state bound and x10
        # of it backstops a wedged compile round.
        self._watch = RoundStallWatch(
            floor_s=round_timeout_s,
            multiple=_env_float(XH_STALL_MULTIPLE_ENV, DEFAULT_XH_STALL_MULTIPLE),
            compile_slack_s=round_timeout_s * _COMPILE_TIMEOUT_FACTOR,
            label="leader",
        )
        # Whether the fused fast path is statically possible on this mesh
        # (same resolution on every process -- identical config).  The
        # actual fleet mode is the LEADER's decision, carried per round in
        # the control flag; followers build the fast program lazily on the
        # first PREDICT_FAST round.
        self._fast_possible = resolve_sharded_fast(spec, mesh, self._dtype, fast)
        self.mode: str | None = "exact" if not self._fast_possible else None
        self.fast_degraded = False
        # Serializes the BROADCAST+DISPATCH half of every leader round
        # across every consumer of this forward: during a hot reload the
        # version watcher constructs a fresh engine while the old one still
        # serves, and a reload broadcast interleaved with a predict round
        # would corrupt the lockstep protocol fleet-wide.  Materialization
        # happens OUTSIDE the lock (it carries no collective), which is
        # what lets round N+1 broadcast while round N executes.
        self._round_lock = threading.Lock()
        # Per-bucket (local device -> batch index) maps, derived from the
        # mesh's actual device->process ownership (ADVICE r2: the old
        # process_count() equal-split silently mis-sharded any mesh that
        # did not cover all devices with equal per-process counts).  The
        # global batch is assembled from LOCAL per-device puts only
        # (make_array_from_single_device_arrays): a device_put against a
        # sharding with non-addressable devices runs a hidden
        # cross-process assert_equal COLLECTIVE on some jax versions,
        # which would race the in-flight rounds' collectives -- the exact
        # interleaving pipelining must never produce.  Built BEFORE the
        # first _install_variables (the chain token needs the device list).
        self._local_imap: dict[int, list] = {}
        self._local_devices = [
            d for d in self.mesh.devices.flat
            if d.process_index == jax.process_index()
        ]
        if not self._local_devices:
            raise ValueError(
                f"process {jax.process_index()} owns no devices of the "
                "serving mesh; every process in the runtime must "
                "participate (build the mesh over all of jax.devices())"
            )
        for b in self.buckets:
            imap = self._batch_sharding.devices_indices_map((b, *spec.input_shape))
            self._local_imap[b] = [
                (d, imap[d]) for d in self._local_devices
            ]
        self._install_variables(variables)
        # Host-side TCP control channel (module docstring): leader binds +
        # advertises through the runtime's KV store, followers connect.
        # Set up at construction on EVERY process -- the whole fleet is in
        # __init__ at the same boot phase, so nobody blocks mid-serving.
        self._followers: list = []      # leader: one socket per follower
        self._ctl_sock = None           # follower: the socket to the leader
        self._setup_control_channel()

    @property
    def inflight_rounds(self) -> int:
        """Rounds dispatched but not yet materialized (<= pipeline_depth)."""
        return self.pipeline_depth - self._slots._value

    def sharding_info(self) -> dict:
        """The registry status surface's sharding block (same shape as
        runtime.InferenceEngine.sharding_info): scheme tag, model-parallel
        degree, and the full mesh axis map."""
        from kubernetes_deep_learning_tpu.parallel import mesh as mesh_par

        shape = dict(self.mesh.shape)
        return {
            "sharding": mesh_par.sharding_scheme("cross-host"),
            "model_parallel": int(shape.get(mesh_par.MODEL_AXIS, 1)),
            "mesh_shape": {str(k): int(v) for k, v in shape.items()},
        }

    def attach_metrics(self, registry) -> None:
        """Mint the kdlt_crosshost_* series on ``registry`` (the serving
        engine's per-version child registry); idempotent per registry
        because a fresh engine hands over a fresh child."""
        from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

        self._metrics = metrics_lib.crosshost_metrics(registry)
        self._metrics["depth"].set(float(self.pipeline_depth))
        if self._faults is not None:
            self._faults.attach(registry)

    def _install_variables(self, variables: Any) -> None:
        from kubernetes_deep_learning_tpu.parallel.dataparallel import (
            build_sharded_jit,
            shard_variables,
        )

        # Sharded/replicated per dataparallel's partition rules; identical
        # on every process because `variables` must be identical (same
        # artifact/seed) on every process.  replicate_out: the logits
        # all-gather runs ON DEVICE inside the program so readback needs
        # no host collective; chain_token: overlapped rounds' executions
        # serialize per process so their collectives can never interleave
        # on the transport (build_sharded_jit documents both).
        self._variables = shard_variables(variables, self.mesh)
        self._jitted_exact = build_sharded_jit(
            self.spec, self.mesh, self._dtype, fast=False,
            replicate_out=True, chain_token=True,
        )
        self._jitted_fast = None  # built lazily (followers: first fast round)
        self._fast_aot: dict = {}  # bucket -> AOT executable (leader probe)
        self._token = self._fresh_token()
        # New jit instances -> every (mode, bucket) recompiles; the round
        # watchdog must re-grant first-round compile slack after a reload.
        self._compiled_rounds: set = set()
        self._watch.reset()

    def _fresh_token(self):
        """The round-chain token: a replicated f32 scalar array (see
        build_sharded_jit chain_token).  Assembled from local puts only --
        same no-hidden-collective constraint as _make_global_batch."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        zero = np.zeros((1,), np.float32)
        return jax.make_array_from_single_device_arrays(
            (1,),
            NamedSharding(self.mesh, P()),
            [jax.device_put(zero, d) for d in self._local_devices],
        )

    def _token_struct(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.ShapeDtypeStruct(
            (1,), np.float32, sharding=NamedSharding(self.mesh, P())
        )

    def _fast_jitted(self):
        if self._jitted_fast is None:
            from kubernetes_deep_learning_tpu.parallel.dataparallel import (
                build_sharded_jit,
            )

            self._jitted_fast = build_sharded_jit(
                self.spec, self.mesh, self._dtype, fast=True,
                replicate_out=True, chain_token=True,
            )
        return self._jitted_fast

    def resolve_mode(self) -> str:
        """Leader: decide the fleet-wide execution mode ("fast"/"exact").

        AOT-compiles the fused shard_map program for EVERY bucket before
        any fast round is broadcast: compilation is process-local (no
        collectives), so the leader can probe alone, and a Mosaic legality
        failure at any bucket degrades the whole fleet to the exact graph
        -- matching single-host serving's warmup degrade
        (runtime.engine._degrade_fast) but decided once, fleet-wide,
        BEFORE followers would trace the same program mid-round.

        Buckets compile CONCURRENTLY, like engine warmup (XLA releases
        the GIL while compiling; the chunked 32-64 bucket programs take
        ~1-3 min each, runtime.engine.warmup round 4), so the probe costs
        about the slowest bucket's compile rather than the sum.  Lowering
        (tracing) stays serial -- it is Python-side and cheap; only the
        ``.compile()`` calls fan out.
        """
        import jax

        if self.mode is not None:
            return self.mode
        try:
            fn = self._fast_jitted()
            lowered = {}
            for b in self.buckets:
                x = jax.ShapeDtypeStruct(
                    (b, *self.spec.input_shape), np.uint8,
                    sharding=self._batch_sharding,
                )
                lowered[b] = fn.lower(self._variables, x, self._token_struct())
            from concurrent.futures import ThreadPoolExecutor

            aot = {}
            failed = []
            with ThreadPoolExecutor(
                max_workers=min(4, len(self.buckets))
            ) as ex:
                futures = {
                    b: ex.submit(low.compile) for b, low in lowered.items()
                }
                for b, fut in futures.items():
                    try:
                        aot[b] = fut.result()
                    except Exception:  # noqa: BLE001 - vary by backend
                        failed.append(b)
            # Serial second chance after the pool drains, mirroring
            # runtime.engine._warm_buckets: a transient error caused by the
            # sibling compiles' own contention must not degrade a healthy
            # fleet to the exact graph for the process lifetime.
            for b in failed:
                aot[b] = lowered[b].compile()
            self._fast_aot = aot
            self.mode = "fast"
        except Exception as exc:  # noqa: BLE001 - compile errors vary by backend
            import logging

            logging.getLogger(__name__).error(
                "cross-host fused fast-path compile FAILED; the fleet "
                "serves the exact flax graph (fast=False). Cause: %s", exc,
            )
            self.fast_degraded = True
            self._jitted_fast = None
            self._fast_aot = {}
            self.mode = "exact"
        return self.mode

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch {n} exceeds cross-host max bucket {self.bucket}")

    def _make_global_batch(self, batch: np.ndarray):
        """The globally-sharded device batch, from LOCAL per-device puts
        only (every process holds the full padded batch -- the control
        channel delivers it whole -- so each just uploads its own devices'
        index slices; no cross-process operation of any kind)."""
        import jax

        return jax.make_array_from_single_device_arrays(
            batch.shape,
            self._batch_sharding,
            [
                jax.device_put(np.ascontiguousarray(batch[idx]), d)
                for d, idx in self._local_imap[batch.shape[0]]
            ],
        )

    # --- leader (process 0) ----------------------------------------------

    def predict_async(self, images: np.ndarray, traces=()):
        """Leader entry, pipelined: broadcast + dispatch one round WITHOUT
        waiting for its device result; returns ``(handle, n)`` where
        ``np.asarray(handle)[:n]`` materializes the f32 logits.

        Blocks only while ``pipeline_depth`` rounds are in flight
        (backpressure) -- never on device execution of the round itself.
        ``traces`` carries the member requests' utils.trace.RequestTrace
        carriers; each gets ``crosshost.{broadcast,collective,gather}``
        spans in its waterfall (broadcast at dispatch; the other two at
        materialization).
        """
        import jax

        assert jax.process_index() == 0, "predict_async() is the leader's call"
        n = images.shape[0]
        bucket = self.bucket_for(n)
        pad = np.zeros((bucket - n, *self.spec.input_shape), np.uint8)
        batch = np.concatenate([images, pad])
        return self._leader_dispatch(batch, n, None, traces)

    def predict_encoded_async(self, blobs, traces=()):
        """Leader entry for the raw-bytes ingest wire (GUIDE 10q): encoded
        JPEG/PNG blobs in, ``(handle, n)`` out, same pipelining contract
        as predict_async.

        Decodes FIRST (BatchDecoder; a corrupt blob raises ValueError here,
        before anything touches the control channel), then broadcasts the
        packed encoded blobs -- typically 10-50x smaller than the padded
        uint8 tensor the legacy flags carry -- and every follower decodes
        the same bytes with the same deterministic host kernels, so the
        fleet's batches stay bit-identical.
        """
        import jax

        assert jax.process_index() == 0, (
            "predict_encoded_async() is the leader's call"
        )
        from kubernetes_deep_learning_tpu.serving import protocol

        decoded = self._ingest_decoder().decode_batch(
            list(blobs), self.spec.input_shape[:2],
            filter=self.spec.resize_filter,
        )
        n = decoded.shape[0]
        bucket = self.bucket_for(n)
        pad = np.zeros((bucket - n, *self.spec.input_shape), np.uint8)
        batch = np.concatenate([decoded, pad])
        payload = protocol.encode_bytes_predict_request(blobs)
        return self._leader_dispatch(batch, n, payload, traces)

    # Lazily-built decode pool; class-level default so neither the leader
    # nor the follower construction path needs wiring.
    _decoder = None

    def _ingest_decoder(self):
        """Lazy per-process decode pool (leader and followers alike)."""
        if self._decoder is None:
            with self._round_lock:
                if self._decoder is None:
                    from kubernetes_deep_learning_tpu.ops import preprocess

                    self._decoder = preprocess.BatchDecoder()
        return self._decoder

    def _leader_dispatch(self, batch, n, enc_payload, traces):
        """Shared broadcast+dispatch round body for both leader wires:
        ``enc_payload`` None -> legacy tensor wire (codec-compressible);
        else the packed encoded blobs to broadcast verbatim."""
        traces = tuple(t for t in traces if t is not None)
        bucket = batch.shape[0]
        self._slots.acquire()
        seq = None
        try:
            with self._round_lock:
                fast = self.resolve_mode() == "fast"
                key = ("fast" if fast else "exact", bucket)
                raw_len = batch.nbytes
                if enc_payload is not None:
                    flag = _PREDICT_ENC_FAST if fast else _PREDICT_ENC
                    payload = enc_payload
                elif self._xh_codec is not None:
                    flag = _PREDICT_FAST_Z if fast else _PREDICT_Z
                    payload = _compress_payload(self._xh_codec, batch.tobytes())
                else:
                    flag = _PREDICT_FAST if fast else _PREDICT
                    payload = batch.tobytes()
                seq = self._seq
                self._seq += 1
                self._watch.begin(seq, key)
                w0 = trace_lib.now_s() if traces else 0.0
                t0 = time.perf_counter()
                if self._faults is not None:
                    self._faults.fire("crosshost.broadcast")
                self._send_round(flag, bucket, payload)
                t1 = time.perf_counter()
                if self._faults is not None:
                    self._faults.fire("crosshost.collective")
                arr = self._dispatch_round(batch, fast)
                self._compiled_rounds.add((fast, bucket))
                if self._metrics is not None:
                    self._metrics["broadcast"].observe(t1 - t0)
                    self._metrics["rounds"].inc()
                    self._metrics["inflight"].set(self.inflight_rounds)
                w1 = trace_lib.now_s() if traces else 0.0
                if traces:
                    for tr in traces:
                        # raw vs wire bytes: the payload diet's per-round
                        # receipt (equal when compression is off).
                        tr.record(
                            "crosshost.broadcast", w0, w1 - w0, bucket=bucket,
                            raw_bytes=raw_len, wire_bytes=len(payload),
                        )
        except BaseException:
            if seq is not None:
                self._watch.complete(seq)
            self._slots.release()
            raise
        handle = _PendingRound(
            self, arr, seq, key, time.perf_counter(), (w0, w1), traces
        )
        return handle, n

    def predict(self, images: np.ndarray, traces=()) -> np.ndarray:
        """Leader entry, synchronous: uint8 (N,H,W,C), N <= max bucket ->
        f32 (N, classes).  Equivalent to predict_async + immediate
        materialization (exact lockstep when called back to back)."""
        handle, n = self.predict_async(images, traces=traces)
        return np.asarray(handle)[:n]

    def _record_round(self, key, exec_seconds: float, gather_seconds: float) -> None:
        if self._metrics is not None:
            self._metrics["collective"].observe(exec_seconds)
            self._metrics["gather"].observe(gather_seconds)

    def _finish_round(self, seq: int, seconds: float | None) -> None:
        self._watch.complete(seq, seconds)
        self._slots.release()
        if self._metrics is not None:
            self._metrics["inflight"].set(self.inflight_rounds)

    def _drain(self):
        """Acquire every in-flight slot (waits for all dispatched rounds to
        materialize); returns a context manager releasing them."""
        acquired = 0
        try:
            for _ in range(self.pipeline_depth):
                self._slots.acquire()
                acquired += 1
        except BaseException:
            for _ in range(acquired):
                self._slots.release()
            raise

        class _Release:
            def __enter__(_s):
                return _s

            def __exit__(_s, *exc):
                for _ in range(acquired):
                    self._slots.release()
                return False

        return _Release()

    def reload(self, version: int, variables: Any = None) -> None:
        """Leader: hot-swap the fleet to artifact ``version``.

        The leader loads (or is handed) and VALIDATES the new variables
        BEFORE broadcasting RELOAD: a leader-side failure then raises with
        the fleet untouched and still version-consistent.  Broadcasting
        first would let followers swap while the leader kept the old
        weights -- silent mixed-version logits.  A FOLLOWER-side reload
        failure (e.g. shared-storage race) raises out of follower_loop and
        kills that process; the gang restart (module docstring) restores
        consistency.  In-flight pipelined rounds are DRAINED first, so a
        reload can never split an overlapped round across versions.
        """
        import jax

        assert jax.process_index() == 0, "reload() is the leader's call"
        if self.model_root is None or self.model_name is None:
            raise RuntimeError("reload requires model_root/model_name")
        if variables is None:
            variables = self._load_version_variables(int(version))
        # Same slack as first-compile predict rounds: a RELOAD round makes
        # every follower disk-load and re-shard the whole model inside the
        # round, which a flat warm-round timeout would misread as a dead
        # peer (exit 70 -> the watcher re-attempts -> crash loop).
        with self._drain(), self._round_lock, self._watchdog(
            f"reload to v{version}",
            self.round_timeout_s * _COMPILE_TIMEOUT_FACTOR,
        ):
            self._send_round(_RELOAD, int(version))
            self._install_variables(variables)
            self.version = int(version)
            if self._metrics is not None:
                self._metrics["reloads"].inc()

    def shutdown(self) -> None:
        """Leader: release followers from follower_loop() (drains in-flight
        rounds first so no round is abandoned mid-pipeline)."""
        import jax

        if jax.process_index() == 0:
            with self._drain(), self._round_lock:
                self._send_round(_SHUTDOWN, 0)
        self._close_control_channel()
        self._watch.stop()

    # --- follower (process > 0) ------------------------------------------

    def follower_loop(self) -> int:
        """Serve rounds until the leader shuts down; returns the number of
        predict rounds served.

        Pipelined counterpart of the leader's predict_async: the loop
        accepts and dispatches round N+1 WITHOUT blocking on round N's
        device result, bounded by the same ``pipeline_depth`` budget; a
        dedicated completion thread materializes rounds in FIFO order and
        feeds the follower's OWN stall detection (KDLT_XH_STALL_FLOOR_S /
        KDLT_XH_STALL_MULTIPLE, EWMA-based) -- a wedged collective (dead
        peer) exits 70 for a gang restart instead of hanging forever.  A
        dead leader surfaces as an exception from the pending broadcast;
        the caller's process exits and the pod restart restarts the gang.
        """
        import jax

        assert jax.process_index() != 0, "follower_loop() is for processes > 0"
        watch = RoundStallWatch(
            floor_s=_env_float(XH_STALL_FLOOR_S_ENV, DEFAULT_XH_STALL_FLOOR_S),
            multiple=_env_float(XH_STALL_MULTIPLE_ENV, DEFAULT_XH_STALL_MULTIPLE),
            label="follower",
        )
        pending: deque = deque()  # (seq, key, arr, t0)
        done = threading.Semaphore(0)
        failure: list = []

        def complete_loop() -> None:
            # FIFO materialization: device completion order IS dispatch
            # order (the chain token serializes executions), so waiting
            # oldest-first both bounds memory and gives the watch honest
            # per-round samples.  A round is popped only AFTER it
            # completes, so ``pending`` always counts truly-in-flight
            # rounds (the drain barrier and the budget check rely on it).
            while True:
                done.acquire()
                item = pending[0]
                if item is None:
                    return
                seq, key, arr, t0 = item
                try:
                    arr.block_until_ready()
                    watch.complete(seq, time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 - surfaced to the loop
                    watch.complete(seq)
                    failure.append(e)
                    pending.popleft()
                    return
                pending.popleft()

        completer = threading.Thread(
            target=complete_loop, name="kdlt-xh-follower-complete", daemon=True
        )
        completer.start()

        def drain() -> None:
            # Wait until the completion thread has materialized every
            # dispatched round (RELOAD/SHUTDOWN barrier).
            while pending and not failure:
                time.sleep(0.001)

        rounds = 0
        seq = 0
        try:
            while True:
                if failure:
                    raise failure[0]
                if self._faults is not None:
                    self._faults.fire("crosshost.broadcast")
                flag, aux, payload = self._recv_round()
                if flag == _SHUTDOWN:
                    drain()
                    return rounds
                if flag == _RELOAD:
                    drain()
                    if failure:
                        raise failure[0]
                    self._do_reload(int(aux))
                    continue
                if flag in (_PREDICT_Z, _PREDICT_FAST_Z):
                    # The flag is the codec negotiation; legacy flags carry
                    # the raw payload untouched (byte-identical wire when
                    # the leader runs with compression off).
                    payload = _decompress_payload(payload)
                encoded = flag in (_PREDICT_ENC, _PREDICT_ENC_FAST)
                fast = flag in (_PREDICT_FAST, _PREDICT_FAST_Z, _PREDICT_ENC_FAST)
                if fast and not self._fast_possible:
                    # The leader resolved "fast" where this process statically
                    # cannot build it: the fleet is misconfigured (mixed code
                    # or config versions).  Die loudly -> gang restart, rather
                    # than wedging the collective.
                    raise RuntimeError(
                        "received PREDICT_FAST but the fused path does not "
                        "resolve on this process; fleet config mismatch"
                    )
                if encoded:
                    # Raw-bytes ingest round: decode the broadcast blobs
                    # with the same deterministic host kernels the leader
                    # used (it already decoded this exact payload, so a
                    # decode failure here is a code-version mismatch, not
                    # client data -- die loudly like the fast-mismatch
                    # case) and zero-pad to the bucket the leader padded to.
                    from kubernetes_deep_learning_tpu.serving import protocol

                    blobs = protocol.decode_bytes_predict_request(payload)
                    decoded = self._ingest_decoder().decode_batch(
                        blobs, self.spec.input_shape[:2],
                        filter=self.spec.resize_filter,
                    )
                    if decoded.shape[0] != int(aux):
                        pad = np.zeros(
                            (int(aux) - decoded.shape[0], *self.spec.input_shape),
                            np.uint8,
                        )
                        decoded = np.concatenate([decoded, pad])
                    batch = decoded
                else:
                    batch = np.frombuffer(payload, np.uint8).reshape(
                        int(aux), *self.spec.input_shape
                    )
                # Backpressure: once ``depth`` rounds are in flight, stop
                # reading the channel until the completion thread catches
                # up -- TCP flow control then pushes back on the leader,
                # the fleet-wide half of the in-flight budget.
                while len(pending) >= self.pipeline_depth:
                    if failure:
                        raise failure[0]
                    time.sleep(0.0005)
                if self._faults is not None:
                    self._faults.fire("crosshost.collective")
                t0 = time.perf_counter()
                arr = self._dispatch_round(batch, fast)
                key = ("fast" if fast else "exact", batch.shape[0])
                self._compiled_rounds.add((fast, batch.shape[0]))
                watch.begin(seq, key)
                pending.append((seq, key, arr, t0))
                done.release()
                seq += 1
                rounds += 1
        finally:
            watch.stop()
            pending.append(None)
            done.release()
            completer.join(timeout=5.0)
            self._close_control_channel()

    # --- control channel ---------------------------------------------------

    def _setup_control_channel(self) -> None:
        """Leader binds + advertises via the runtime KV store; followers
        connect.  Single-process runtimes have no channel at all."""
        import jax

        n = jax.process_count()
        if n == 1:
            return
        timeout = _env_float(
            _CTL_SETUP_TIMEOUT_ENV, _DEFAULT_CTL_SETUP_TIMEOUT_S
        )
        client = _dist_kv_client()
        if jax.process_index() == 0:
            srv = socket.create_server(("0.0.0.0", 0))
            port = srv.getsockname()[1]
            client.key_value_set(_CTL_ADDR_KEY, f"{_advertised_host()}:{port}")
            srv.settimeout(timeout)
            try:
                for _ in range(n - 1):
                    conn, _addr = srv.accept()
                    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    self._followers.append(conn)
            except socket.timeout:
                raise RuntimeError(
                    f"control channel: only {len(self._followers)} of "
                    f"{n - 1} followers connected within {timeout}s"
                ) from None
            finally:
                srv.close()
            return
        addr = client.blocking_key_value_get(_CTL_ADDR_KEY, int(timeout * 1e3))
        host, port = addr.rsplit(":", 1)
        deadline = time.monotonic() + timeout
        while True:
            try:
                sock = socket.create_connection((host, int(port)), timeout=5.0)
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.2)
        sock.settimeout(None)  # rounds arrive whenever the leader sends
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._ctl_sock = sock

    def _send_round(self, flag: int, aux: int, payload: bytes = b"") -> None:
        """Leader: one round's control header (+ payload) to every
        follower.  Plain host TCP: overlaps in-flight device collectives
        on any backend (the point of the side channel)."""
        aux = int(aux)
        if not 0 <= aux < 2**62:
            raise ValueError(f"control aux {aux} out of range")
        header = _CTL_HEADER.pack(flag, aux, len(payload))
        for s in self._followers:
            s.sendall(header)
            if payload:
                s.sendall(payload)

    def _recv_round(self) -> tuple[int, int, bytes]:
        """Follower: block for the next round; raises ConnectionError on a
        dead leader (the caller's process exit restarts the gang)."""
        header = self._recv_exact(_CTL_HEADER.size)
        flag, aux, nbytes = _CTL_HEADER.unpack(header)
        payload = self._recv_exact(nbytes) if nbytes else b""
        return flag, aux, payload

    def _recv_exact(self, nbytes: int) -> bytes:
        buf = bytearray(nbytes)
        view = memoryview(buf)
        got = 0
        while got < nbytes:
            k = self._ctl_sock.recv_into(view[got:], nbytes - got)
            if k == 0:
                raise ConnectionError(
                    "cross-host control channel closed (leader died?)"
                )
            got += k
        return bytes(buf)

    def _close_control_channel(self) -> None:
        for s in self._followers:
            try:
                s.close()
            except OSError:
                pass
        self._followers = []
        if self._ctl_sock is not None:
            try:
                self._ctl_sock.close()
            except OSError:
                pass
            self._ctl_sock = None

    # --- shared plumbing ---------------------------------------------------

    def _do_reload(self, version: int) -> None:
        """Follower side of a RELOAD round."""
        self._install_variables(self._load_version_variables(version))
        self.version = version

    def _load_version_variables(self, version: int):
        """Load a version's variables from this process's model root, with
        the same quantized-artifact handling as the boot path (the
        shard/forward path addresses float kernel leaves, so int8 wire
        trees must dequantize host-side before sharding)."""
        if self.model_root is None or self.model_name is None:
            raise RuntimeError(
                "RELOAD requires model_root/model_name on every process"
            )
        from kubernetes_deep_learning_tpu.export import artifact as art

        artifact = art.load_artifact(
            art.version_dir(self.model_root, self.model_name, version)
        )
        return artifact_variables_for_sharding(artifact)

    def _dispatch_round(self, batch: np.ndarray, fast: bool = False):
        """Enter the SPMD forward WITHOUT synchronizing on the result.

        Returns the (async-dispatched) fully-replicated device logits; the
        caller materializes with a plain local ``np.asarray`` whenever it
        needs the values -- the pipelining hook.  The chain token threads
        round N's completion into round N+1's start (execution-order
        safety, build_sharded_jit); callers are single-threaded per
        process (leader: under _round_lock; follower: the loop thread), so
        the token hand-off needs no extra lock.
        """
        global_batch = self._make_global_batch(batch)
        # The leader dispatches fast rounds through the AOT executable its
        # mode probe already compiled (resolve_mode); followers (and any
        # bucket compiled after a reload) jit-dispatch, compiling lazily.
        exe = self._fast_aot.get(batch.shape[0]) if fast else None
        fn = exe if exe is not None else (
            self._fast_jitted() if fast else self._jitted_exact
        )
        logits, self._token = fn(self._variables, global_batch, self._token)
        return logits

    def _watchdog(self, what: str, timeout_s: float):
        """Context manager: exit(70) if a BLOCKING protocol round (reload)
        wedges.  A blocked collective cannot be interrupted from Python,
        so process exit -- and the pod restart it triggers -- is the only
        clean recovery; the whole gang restarts together.  Predict rounds
        are covered by the EWMA RoundStallWatch instead."""

        class _Arm:
            def __init__(self, timeout, what):
                self._timer = None
                if timeout > 0:
                    def boom():
                        print(
                            f"CRITICAL cross-host {what} exceeded {timeout}s "
                            "(dead peer?); exiting 70 for a gang restart",
                            flush=True,
                        )
                        os._exit(70)

                    self._timer = threading.Timer(timeout, boom)
                    self._timer.daemon = True

            def __enter__(self):
                if self._timer is not None:
                    self._timer.start()

            def __exit__(self, *exc):
                if self._timer is not None:
                    self._timer.cancel()
                return False

        return _Arm(timeout_s, what)


class CrossHostEngine:
    """Engine-shaped adapter: plugs CrossHostForward into the model server.

    Matches the engine surface ServedModel consumes (runtime.stub documents
    it), INCLUDING the ``predict_async`` pipelining hook: the single HTTP
    frontend on process 0 serves a model sharded across every process of
    the fleet, and the server's InFlightDispatcher overlaps round N+1's
    broadcast + batch assembly with round N's collective execution
    (``preferred_pipeline_depth`` hands the fleet's KDLT_XH_PIPELINE_DEPTH
    budget to the dispatcher; ``pipeline_engine_label`` labels the
    kdlt_pipeline_* stage metrics with engine="crosshost").  Use via
    ModelServer's ``engine_factory`` (serving.model_server main wires
    --cross-host).
    """

    pipeline_engine_label = "crosshost"

    def __init__(self, artifact, xh: CrossHostForward, registry=None, **_ignored):
        self.spec = artifact.spec
        self._xh = xh
        self.buckets = xh.buckets
        self.max_batch = xh.bucket
        self.preferred_pipeline_depth = xh.pipeline_depth
        self._ready = False
        # Hot version reload: ModelServer's version watcher constructs a
        # fresh engine for a higher version dir through engine_factory --
        # for cross-host serving the SWAP must happen fleet-wide, so
        # construction broadcasts RELOAD when this artifact's version
        # differs from the fleet's current one.  A failed reload raises
        # here, and poll_versions keeps serving the old version.
        try:
            version = int(artifact.path.rstrip("/").rsplit("/", 1)[-1])
        except (AttributeError, ValueError):
            version = None
        if (
            version is not None
            and xh.version is not None
            and version != xh.version
        ):
            # poll_versions already loaded this artifact; hand its
            # variables over so the leader does not re-read the same
            # version dir (and hold two host-RAM copies) during the swap.
            xh.reload(version, variables=artifact_variables_for_sharding(artifact))
        # Serializes SYNCHRONOUS consumers (warmup, reload, the serial
        # predict path); the pipelined predict_async path is serialized by
        # xh's own round lock + in-flight budget instead, so overlapped
        # rounds are not flattened back into lockstep here.
        self._lock = threading.Lock()
        self._m_images = None
        self._m_fast_degraded = None
        if registry is not None:
            xh.attach_metrics(registry)
            self._m_images = registry.counter(
                "kdlt_engine_images_total", "images predicted (cross-host engine)"
            )
            # Same gauge name/semantics as runtime.InferenceEngine: operators
            # alert on a fleet silently serving the slower exact graph.
            self._m_fast_degraded = registry.gauge(
                "kdlt_engine_fast_degraded",
                "1 when a fused fast-path compile failure forced the exact graph",
            )
        # The engine computes from xh's device-sharded weights; drop the
        # artifact's redundant host-RAM copy of the variable tree (the
        # leader already loaded one copy to build xh).
        artifact.variables = None

    @property
    def ready(self) -> bool:
        return self._ready

    @property
    def fast_degraded(self) -> bool:
        return self._xh.fast_degraded

    def sharding_info(self) -> dict:
        return self._xh.sharding_info()

    def warmup(self) -> float:
        import time

        t0 = time.perf_counter()
        with self._lock:
            for b in self.buckets:
                self._xh.predict(np.zeros((b, *self.spec.input_shape), np.uint8))
        if self._m_fast_degraded is not None:
            self._m_fast_degraded.set(1.0 if self._xh.fast_degraded else 0.0)
        self._ready = True
        return time.perf_counter() - t0

    def bucket_for(self, n: int) -> int:
        return self._xh.bucket_for(n)

    def _check_images(self, images: np.ndarray) -> None:
        if images.dtype != np.uint8:
            raise ValueError(
                f"cross-host serving takes uint8 images, got {images.dtype}"
            )

    def predict_async(self, images: np.ndarray, traces=()):
        """The pipelining hook (runtime.engine.InFlightDispatcher consumes
        it): broadcast + dispatch one round, return (handle, n) without
        the device sync.  Backpressure rides xh's in-flight budget."""
        self._check_images(images)
        handle, n = self._xh.predict_async(images, traces=traces)
        if self._m_images is not None:
            self._m_images.inc(n)
        return handle, n

    def predict_encoded_async(self, blobs, traces=()):
        """Raw-bytes ingest hook (GUIDE 10q): the model server hands the
        wire's encoded blobs straight through, so the cross-host broadcast
        carries compact JPEG/PNG bytes instead of the padded uint8 tensor
        and every process decodes locally.  ValueError (corrupt blob)
        raises here on the leader before any broadcast -> HTTP 400."""
        handle, n = self._xh.predict_encoded_async(blobs, traces=traces)
        if self._m_images is not None:
            self._m_images.inc(n)
        return handle, n

    def predict(self, images: np.ndarray) -> np.ndarray:
        self._check_images(images)
        with self._lock:
            out = self._xh.predict(images)
        if self._m_images is not None:
            self._m_images.inc(images.shape[0])
        return out

    def reload(self, version: int) -> None:
        """Fleet-wide hot version swap (drains in-flight pipelined rounds,
        serialized against synchronous predicts)."""
        with self._lock:
            self._xh.reload(version)
        self._ready = True
