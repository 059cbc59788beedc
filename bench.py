#!/usr/bin/env python
"""Benchmark the flagship serving path on the local accelerator.

Measures the model tier's raw throughput/latency (the hot loop the reference
delegates to TF-Serving's C++ binary) on the Xception clothing classifier:
batch-swept images/sec plus per-batch device latency, against the
BASELINE.json target of >=4000 images/sec/chip at p50 <= 15 ms.

Measurement method -- two independent methods, cross-checked:

1. *Chained scan*: K forward passes run inside ONE jit program via lax.scan,
   where each iteration's INPUT depends on the previous iteration's logits
   (a data-dependent low-bit flip of the image).  Round 1 chained only an
   accumulator, leaving ``fwd(v, x)`` loop-invariant; XLA's while-loop
   invariant code motion hoisted the forward out of the loop and the bench
   reported physically impossible numbers (~690% of v5e bf16 peak).  The
   data dependence makes hoisting illegal.
2. *Pipelined dispatch*: K independent jit calls dispatched asynchronously,
   blocked on together.  The device queue runs them back to back, which
   amortizes the host's per-dispatch cost.

Each batch point also records ``serial_img_per_s`` (every call materialized
before the next dispatches -- the pre-pipelining serving cadence) next to
the pipelined number, so the official record carries the serial-vs-
pipelined A/B per point; ``--pipeline-ab`` is the device-free counterpart,
measuring the in-flight dispatcher against a stub with known per-stage
costs and a known device-execute-only bound.

The headline is the **minimum** of the two methods at the best batch size
within the p50<=15 ms bound, and the JSON self-flags impossibility: it
reports MFU = img/s x FLOPs/image / device peak, computed from XLA's own
cost analysis.  MFU > 100% means the measurement is wrong, by construction.

Fault isolation: each batch point runs in its OWN subprocess
(run_isolated_sweep), so a device-side fault -- which nullified the official
record in rounds 1-3 by killing the single shared process -- costs exactly
one point: it is retried once, recorded in the JSON's "faults" list, and
the headline comes from the surviving points.

Prints ONE JSON line to stdout:
    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
     "mfu_pct": N}
Detail goes to stderr.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time
from functools import partial

import numpy as np

TARGET_IMG_S = 4000.0  # BASELINE.json north star: >=4000 img/s/chip on v5e
TARGET_P50_MS = 15.0   # ...at p50 <= 15 ms (the north star's latency bound)

# Clamp on the chained-scan length: one device execution is kept to a few
# seconds (2000 iterations of a ~2 ms forward), far below any watchdog on
# single long executions.
SCAN_LEN_CAP = 2000


def auto_scan_len(est_s: float, target_s: float = 4.0) -> int:
    """Size the chained-scan iteration count from a warm per-iteration probe.

    Targets ~``target_s`` per timed scan call (per-call dispatch cost
    amortizes to noise), quantized to a power of two so every run reuses
    the same compiled scan program (the length is baked into its HLO and a
    timing-jittered k would defeat the persistent compile cache).

    The SCAN_LEN_CAP clamp is re-applied AFTER quantizing: round-to-nearest
    rounds any k_raw in (1448, 2000] up to 2048, past the documented
    bound the first min() was meant to enforce (ADVICE r5).
    """
    k_raw = max(24.0, min(float(SCAN_LEN_CAP), target_s / max(est_s, 1e-9)))
    return int(min(SCAN_LEN_CAP, 2 ** round(math.log2(k_raw))))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class SweepTerminated(Exception):
    """Raised by the SIGTERM handler so a driver-killed sweep still lands
    on the final-headline print instead of dying mid-point (VERDICT r4 #1:
    rc=124 with zero parsable output nullified the round-4 record)."""


def _sigterm_handler(signum, frame):  # noqa: ARG001 - signal signature
    raise SweepTerminated(f"signal {signum}")


def _env_float(name: str, default: float) -> float:
    """Parse a float env override; a typo'd value must degrade to the
    default, not kill the process before it can emit any record."""
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        log(f"{name}={os.environ[name]!r} is not a number; using {default}")
        return default


def compose_headline(model, dtype, params_dtype, results, faults, flops_img,
                     *, dropped=(), terminated=False, points_total=None):
    """Build the one-line official-record JSON from whatever points exist.

    Called after EVERY completed batch point, not just at sweep end, so the
    last stdout line of a truncated run (driver timeout, SIGTERM, OOM kill)
    is always a parsable record of the best measurement so far -- later
    emissions overwrite earlier ones in the driver's last-line parse.
    Returns (out_dict, rc).
    """
    if not results:
        if terminated and not faults:
            why = ("sweep terminated by signal before any batch point "
                   "completed; no measurements")
        elif terminated:
            why = ("sweep terminated by signal; every attempted batch "
                   "point had faulted, see faults")
        else:
            why = "EVERY batch point faulted; no surviving measurements"
        out = {
            "metric": f"{model} images/sec/chip ({why})",
            "value": 0.0,
            "unit": "images/sec/chip",
            "vs_baseline": 0.0,
            "faults": faults,
        }
        if terminated:
            out["terminated"] = True
        if dropped:
            out["dropped_points"] = list(dropped)
        return out, 1

    # Headline: the north star is ">=4000 img/s/chip at p50 <= 15 ms"
    # (BASELINE.json) -- the best MIN-of-both-methods throughput among batch
    # sizes that MEET the latency bound AND pass the physics check
    # (MFU <= 100% when peak is known).  Full sweep is in the "sweep" field;
    # faulted points are in "faults" (nothing hidden -- a fault zeroes one
    # point, not the record).
    def valid(r):
        return r["mfu_pct"] is None or r["mfu_pct"] <= 100.0

    valid_pool = {b: r for b, r in results.items() if valid(r)}
    eligible = {
        b: r for b, r in valid_pool.items() if r["p50_ms"] <= TARGET_P50_MS
    }
    pool = eligible or valid_pool or results
    headline_batch = max(pool, key=lambda b: pool[b]["img_per_s"])
    r = results[headline_batch]
    value = r["img_per_s"]
    if not valid_pool:
        bound_note = (
            "INVALID: every batch failed the MFU<=100% physics check; "
            "number is not trustworthy"
        )
    elif headline_batch in eligible:
        bound_note = f"within p50<={TARGET_P50_MS:.0f}ms bound"
    else:
        bound_note = (
            f"NO valid batch met the p50<={TARGET_P50_MS:.0f}ms bound; "
            "best valid overall"
        )
    fault_note = f"; {len(faults)} faulted point attempt(s), see faults" if faults else ""
    progress_note = ""
    if points_total is not None and len(results) < points_total:
        progress_note = f"; partial sweep {len(results)}/{points_total} points"
        if terminated:
            progress_note += " (terminated by signal)"
        elif dropped:
            progress_note += " (budget trimmed)"
    out = {
        "metric": f"{model} images/sec/chip (best batch={headline_batch} "
        f"{bound_note}; min of {r.get('headline_methods', 'scan/pipelined')} "
        f"methods, agreement={r['method_agreement']:.2f}; device "
        f"p50={r['p50_ms']:.2f}ms/batch, {dtype} compute, "
        f"{params_dtype} params"
        + (f", {flops_img / 1e9:.2f} GFLOPs/img" if flops_img else "")
        + fault_note
        + progress_note
        + ")",
        "value": round(value, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(value / TARGET_IMG_S, 3),
        "mfu_pct": r["mfu_pct"],
        # Conservative cross-method p50 (max of the two headline methods)
        # next to the LIKE-FOR-LIKE device-trace pair: trace_p50_ms and
        # p99_ms come from the same per-iteration trace-span estimator, so
        # the tail reads against its own median (VERDICT r4 weak-4: the
        # old table paired cross-method p50 with trace p99 and inverted on
        # every row).
        "p50_ms": round(r["p50_ms"], 2),
        "p50_source": "cross-method-max",
        "trace_p50_ms": (
            round(r["trace_p50_ms"], 2) if r.get("trace_p50_ms") is not None else None
        ),
        "p99_ms": round(r["p99_ms"], 2) if r.get("p99_ms") is not None else None,
        "p99_source": r.get("p99_source"),
        "sweep": {
            str(b): {k: (round(v, 3) if isinstance(v, float) else v)
                     for k, v in row.items()}
            for b, row in sorted(results.items())
        },
        "faults": faults,
    }
    if dropped:
        out["dropped_points"] = list(dropped)
    if terminated:
        out["terminated"] = True
    # rc=0 iff the in-bound headline exists: a valid (physics-passing) batch
    # met the latency bound and survived.  Faults at other points (e.g. the
    # out-of-bound 256 ceiling probe) are reported but do not nullify
    # an in-bound record.
    return out, 0 if (valid_pool and headline_batch in eligible) else 1


# Device peaks + FLOP counting live in the runtime (runtime/flops.py): the
# status page reports the peak and the bucket audit the FLOPs/image.
from kubernetes_deep_learning_tpu.runtime.flops import (  # noqa: E402
    PEAK_TFLOPS_BY_KIND,
    compiled_flops_per_image,
    peak_tflops,
)


def trace_span_stats(fwd_jit, variables, x, k):
    """Third estimator: per-iteration DEVICE time from jax.profiler spans.

    Dispatches ``k`` independent forwards in one pipelined burst under a
    profiler trace and reads the device stream's own timeline -- immune to
    host dispatch cost, which depresses the pipelined method at small
    batches.
    Iterations are split at recurrences of the stream's first op name (one
    jit program executes at a time on a TPU core, so per-iteration spans
    do not overlap); if the split does not come out exact, only the
    packed-stream mean is returned.  This also yields the only honest
    device p99: the scan/pipelined methods time multi-iteration bursts,
    and a percentile over burst MEANS structurally cannot see tail
    latency.

    Returns {p50_s, p99_s|None, mean_s, exact_iters} or None (no device
    events -- e.g. CPU backend, where the profiler emits host events only).
    """
    import glob
    import gzip
    import shutil
    import tempfile

    import jax

    trace_dir = tempfile.mkdtemp(prefix="kdlt-bench-trace-")
    try:
        np.asarray(fwd_jit(variables, x))  # keep compile out; real sync
        with jax.profiler.trace(trace_dir):
            outs = [fwd_jit(variables, x) for _ in range(k)]
            jax.block_until_ready(outs)
            np.asarray(outs[-1])  # a real D2H transfer closes the window
        files = glob.glob(
            os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
        )
        if not files:
            return None
        with gzip.open(files[0], "rt") as f:
            trace = json.load(f)
        events = trace.get("traceEvents", [])
        names = {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                names[e["pid"]] = e["args"].get("name", "")
        dev_pids = [
            pid for pid, n in names.items()
            if "TPU" in n or "/device" in n.lower()
        ]
        ops = [
            e for e in events
            if e.get("ph") == "X" and e.get("pid") in dev_pids
            and e.get("dur", 0) > 0
        ]
        if not ops:
            return None
        by_tid: dict = {}
        for e in ops:
            by_tid.setdefault((e["pid"], e["tid"]), []).append(e)
        evs = max(by_tid.values(), key=len)
        evs.sort(key=lambda e: e["ts"])
        span_s = (evs[-1]["ts"] + evs[-1]["dur"] - evs[0]["ts"]) / 1e6
        starts = [i for i, e in enumerate(evs) if e["name"] == evs[0]["name"]]
        if len(starts) != k:
            return {
                "p50_s": span_s / k, "p99_s": None, "mean_s": span_s / k,
                "exact_iters": False,
            }
        bounds = starts + [len(evs)]
        iters_s = []
        for a, b in zip(bounds, bounds[1:]):
            t1 = max(e["ts"] + e["dur"] for e in evs[a:b])
            iters_s.append((t1 - evs[a]["ts"]) / 1e6)
        arr = np.array(iters_s)
        return {
            "p50_s": float(np.percentile(arr, 50)),
            "p99_s": float(np.percentile(arr, 99)),
            "mean_s": float(arr.mean()),
            "exact_iters": True,
        }
    except Exception as e:  # noqa: BLE001 - the estimator is best-effort
        log(f"trace-span estimator unavailable: {e!r}")
        return None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def bench_forward(model, batch_sizes, scan_len, reps, dtype_name, params_dtype_name,
                  peak_override=0.0, flops_img_known=0.0):
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.export.exporter import cast_params
    from kubernetes_deep_learning_tpu.models import build_forward, init_variables
    from kubernetes_deep_learning_tpu.modelspec import get_spec

    spec = get_spec(model)
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
    dev = jax.devices()[0]
    log(f"device: {dev}, compute dtype: {dtype_name}, params dtype: {params_dtype_name}")

    variables = init_variables(spec, seed=0)
    if params_dtype_name == "bfloat16":
        variables = cast_params(variables, jnp.bfloat16)
    variables = jax.device_put(variables, dev)
    fwd = build_forward(spec, dtype=dtype)
    fwd_jit = jax.jit(fwd)

    @partial(jax.jit, static_argnums=2)
    def chained(v, x, k):
        # Each iteration's input depends on the previous iteration's logits
        # (flip every pixel's low bit whenever the running logit sum goes
        # negative), so the forward is NOT loop-invariant and XLA cannot
        # hoist it out of the scan.  Round 1 chained only an accumulator,
        # which LICM hoisted, yielding impossible numbers.
        # The perturbation is one elementwise xor -- noise next to the
        # ~17 GFLOP forward -- and keeps uint8 inputs uint8.
        def body(carry, _):
            acc, xi = carry
            s = fwd(v, xi).sum()
            bit = jnp.signbit(s).astype(xi.dtype)
            return (acc + s.astype(jnp.float32), xi ^ bit), None

        (acc, _), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), x), None, length=k
        )
        return acc

    rng = np.random.default_rng(0)
    peak = peak_override * 1e12 if peak_override else None
    if peak is None:
        p = peak_tflops(dev, dtype_name)
        peak = p * 1e12 if p else None
    results = {}
    flops_img = flops_img_known or None
    for b in batch_sizes:
        x = jax.device_put(
            rng.integers(0, 256, size=(b, *spec.input_shape), dtype=np.uint8), dev
        )
        # Auto-size the CHAINED-SCAN iteration count so the per-call
        # dispatch cost amortizes to noise on that method (a fixed small
        # count charges it to every iteration).  A short pipelined probe
        # estimates the warm per-iteration time, then k targets ~4 s per
        # timed scan call (SCAN_LEN_CAP bounds a single execution).  The
        # PIPELINED method is separately burst-capped below and keeps a
        # larger residual at tiny batches; the method agreement check
        # still applies.
        np.asarray(fwd_jit(variables, x))  # compile/warm this shape (real sync)
        if scan_len:
            k = scan_len
        else:
            probe_n = max(8, min(64, 25000 // b))
            t0 = time.perf_counter()
            probe = [fwd_jit(variables, x) for _ in range(probe_n)]
            jax.block_until_ready(probe)
            # REAL materialization closes the probe window: a garbage
            # (dispatch-rate) estimate here would silently max k out and
            # produce minute-long single device executions.
            np.asarray(probe[-1])
            est = (time.perf_counter() - t0) / probe_n
            # Sizing + power-of-two quantization + post-quantize re-clamp
            # live in auto_scan_len (the quantization moves the timed
            # execution by at most sqrt(2) -- still >=2.8 s and <<30 s).
            k = auto_scan_len(est)
        if flops_img is None:
            # Cost analysis on the flax graph (see compiled_flops_per_image);
            # the TIMED forward may be the fused fast path.
            ref_jit = jax.jit(build_forward(spec, dtype=dtype, fast=False))
            flops_img = compiled_flops_per_image(ref_jit, b, variables, x)
            if flops_img:
                log(f"compiled forward: {flops_img / 1e9:.2f} GFLOPs/image (XLA cost analysis, unfused graph)")

        # Method 1: data-dependent chained scan.
        t0 = time.perf_counter()
        float(chained(variables, x, k))  # compile + first run
        compile_s = time.perf_counter() - t0
        per_step = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(chained(variables, x, k))
            per_step.append((time.perf_counter() - t0) / k)
        per_step = np.array(per_step)
        scan_p50_ms = float(np.percentile(per_step, 50) * 1e3)
        scan_img_s = b / float(np.median(per_step))

        # Method 2: pipelined async dispatch of independent forwards.  Each
        # call materializes its own output buffer, so the device must run
        # every one; dispatches overlap execution, amortizing dispatch cost.
        # Burst capped at 200: beyond a few hundred queued dispatches the
        # HOST dispatch rate becomes the bottleneck, which would
        # mis-measure the device.  The residual dispatch share errs in the
        # conservative (min-of-methods) direction at tiny batches.
        kp = min(k, 200)
        np.asarray(fwd_jit(variables, x))  # warm + sync this shape
        pipe_times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            outs = [fwd_jit(variables, x) for _ in range(kp)]
            jax.block_until_ready(outs)
            np.asarray(outs[-1])  # readback inside the timed window
            pipe_times.append((time.perf_counter() - t0) / kp)
        pipe_p50_ms = float(np.percentile(pipe_times, 50) * 1e3)
        pipe_img_s = b / float(np.median(pipe_times))

        # Method 2b: SERIAL dispatch -- the same forward, but each call is
        # fully materialized before the next dispatches (the pre-pipelining
        # engine cadence: dispatch -> execute -> readback, no overlap).
        # pipelined/serial is the per-point record of what multi-in-flight
        # dispatch buys; it never enters the headline.  Short burst, few
        # reps: this is an informational column, each serial iteration
        # costs a full round trip, and past ~16 iterations the estimate has
        # converged -- the sweep budget belongs to the headline methods.
        ks = min(kp, 16)
        serial_times = []
        for _ in range(min(reps, 2)):
            t0 = time.perf_counter()
            for _ in range(ks):
                np.asarray(fwd_jit(variables, x))
            serial_times.append((time.perf_counter() - t0) / ks)
        serial_img_s = b / float(np.median(serial_times))

        # Method 3: profiler trace spans -- per-iteration device time read
        # off the device's own timeline (see trace_span_stats).
        tr = trace_span_stats(
            fwd_jit, variables, x, k=min(100, max(20, 3000 // b))
        )
        trace_img_s = (b / tr["mean_s"]) if tr else None
        trace_p50_ms = tr["p50_s"] * 1e3 if tr else None

        # Headline candidate: conservative minimum of two INDEPENDENT
        # methods.  The pipelined method carries residual host dispatch
        # cost at tiny batches (burst cap note above), so when it
        # disagrees with the scan by >10% the cross-check pairs the scan
        # with the trace-span method instead (VERDICT r3 #6: the promised
        # two-method bind did not actually bind below batch 8).
        pipe_agree = min(scan_img_s, pipe_img_s) / max(scan_img_s, pipe_img_s)
        if pipe_agree >= 0.9 or trace_img_s is None:
            img_s = min(scan_img_s, pipe_img_s)
            p50 = max(scan_p50_ms, pipe_p50_ms)
            agree, methods = pipe_agree, "scan/pipelined"
        else:
            img_s = min(scan_img_s, trace_img_s)
            p50 = max(scan_p50_ms, trace_p50_ms)
            agree = min(scan_img_s, trace_img_s) / max(scan_img_s, trace_img_s)
            methods = "scan/trace"
        # Device p99 comes from per-iteration trace spans (the only honest
        # tail estimate here: the scan/pipelined methods time bursts, and a
        # percentile over burst MEANS cannot see tail latency).  Absent an
        # exact span split, p99 is null rather than a fake.
        p99 = tr["p99_s"] * 1e3 if tr and tr["p99_s"] is not None else None
        mfu = (img_s * flops_img / peak) if (peak and flops_img) else None
        results[b] = {
            "img_per_s": float(img_s),
            "scan_img_per_s": float(scan_img_s),
            "pipelined_img_per_s": float(pipe_img_s),
            "serial_img_per_s": float(serial_img_s),
            "pipeline_speedup": float(pipe_img_s / serial_img_s),
            "trace_img_per_s": float(trace_img_s) if trace_img_s else None,
            "method_agreement": float(agree),
            "headline_methods": methods,
            "p50_ms": p50,
            # p50 is the conservative cross-method max; the trace method's
            # own p50 accompanies the trace-derived p99 so the tail can be
            # read against a like-for-like median (p99 may sit below the
            # cross-method p50 -- that is the other method's overhead, not
            # a statistics bug).
            "trace_p50_ms": trace_p50_ms,
            "p99_ms": p99,
            "p99_source": "device-trace-span" if p99 is not None else None,
            "best_ms": float(min(per_step.min(), min(pipe_times)) * 1e3),
            "worst_ms": float(max(per_step.max(), max(pipe_times)) * 1e3),
            "compile_s": float(compile_s),
            "mfu_pct": round(mfu * 100, 1) if mfu is not None else None,
        }
        mfu_s = f"  MFU {results[b]['mfu_pct']:5.1f}%" if mfu is not None else ""
        tr_s = f"{trace_img_s:.0f}" if trace_img_s else "n/a"
        p99_s = f"{p99:7.2f}" if p99 is not None else "    n/a"
        log(
            f"batch {b:4d}: {img_s:9.1f} img/s (scan {scan_img_s:.0f} / "
            f"pipelined {pipe_img_s:.0f} / serial {serial_img_s:.0f} / "
            f"trace {tr_s}; {methods} "
            f"agree {agree:.2f})  p50 {p50:7.2f} ms  p99 {p99_s} ms{mfu_s}"
            f"  (compile {compile_s:.1f}s, pipeline x{pipe_img_s / serial_img_s:.2f})"
        )
        if mfu is not None and mfu > 1.0:
            log(
                f"batch {b:4d}: WARNING: MFU {mfu * 100:.0f}% > 100% -- measurement "
                "is physically impossible and will be excluded from the headline"
            )
    return spec, results, flops_img


def run_isolated_sweep(args, batch_sizes, emit=None, state=None):
    """Run each batch point of the forward sweep in its OWN subprocess.

    Round-3 postmortem (BENCH_r03.json): the device runtime died with a
    "kernel fault" at one batch point, and because all 12 points shared one
    process the whole official record was nullified -- for the third round
    running.  Per-point isolation bounds the blast radius of any single
    fault to that point: the crash is recorded as ``{"fault": ...}`` with
    the child's stderr tail, the sweep continues, and the headline comes
    from surviving points.  A faulted point is retried once after a pause;
    both attempts are recorded.  One process per chip: the parent never
    initialises a jax backend, so each child finds the chip free.

    Round-4 postmortem (rc=124): fault isolation was not
    enough -- the DRIVER's wall-clock budget killed the sweep mid-run and
    the headline JSON, printed only at the end, never appeared.  Three
    defenses:

    * ``emit`` is called with the running (results, faults, flops_img)
      after every point, so the caller keeps the last stdout line a
      parsable current-best headline at all times;
    * an overall time budget (``--budget-s`` / KDLT_BENCH_BUDGET_S) bounds
      the run: remaining points are trimmed -- and recorded in ``dropped``
      -- when the next one probably would not finish, each attempt's child
      timeout is clamped to the remaining budget, and a retry that no
      longer fits is skipped;
    * SIGTERM raises SweepTerminated (installed by main), caught here: the
      in-flight child is stopped and the partial results survive for a
      final headline print during the termination grace period.

    Progress is also mirrored into ``state`` (a caller-owned dict) as it
    happens, so even an exception that escapes this function -- e.g. a
    second SIGTERM landing inside the except block's cleanup -- leaves the
    caller holding every completed point.

    Returns (results, faults, flops_img, dropped, terminated).
    """
    st = state if state is not None else {}
    results: dict[int, dict] = st.setdefault("results", {})
    faults: list[dict] = st.setdefault("faults", [])
    dropped: list[int] = st.setdefault("dropped", [])
    st.setdefault("flops_img", 0.0)
    st.setdefault("terminated", False)
    t_sweep0 = time.perf_counter()
    slowest_point_s = 0.0
    proc = None
    try:
        for i, b in enumerate(batch_sizes):
            elapsed = time.perf_counter() - t_sweep0
            if args.budget_s and i > 0:
                # Would starting this point probably blow the budget?  The
                # estimate is the slowest completed point so far (compile
                # time dominates and grows with batch; still conservative
                # enough), floored at 60 s.
                est = max(60.0, slowest_point_s)
                if elapsed + est > args.budget_s:
                    dropped.extend(batch_sizes[i:])
                    log(
                        f"budget: {elapsed:.0f}s elapsed + ~{est:.0f}s/point "
                        f"> {args.budget_s:.0f}s budget -- dropping remaining "
                        f"points {dropped}"
                    )
                    break
            t_point0 = time.perf_counter()
            row = None
            for attempt in (1, 2):
                # Clamp each attempt's child timeout to the budget REMAINING
                # at the moment it starts (not once per point: a first
                # attempt that hangs to its timeout must not grant the
                # retry that same stale allowance).  When what remains
                # cannot fit even a minimal attempt, do not start one at
                # all -- flooring the timeout would overrun the budget and
                # re-create the driver-axe failure the budget exists to
                # prevent.
                elapsed = time.perf_counter() - t_sweep0
                point_timeout = args.point_timeout
                if args.budget_s:
                    remaining = args.budget_s - elapsed
                    first_ever = i == 0 and attempt == 1
                    if first_ever:
                        # The sweep's very first attempt always runs, with
                        # at least 120 s: a record with ONE measured point
                        # beats an empty record emitted punctually, and the
                        # SIGTERM/incremental machinery still bounds the
                        # damage if an external axe is tighter than that.
                        point_timeout = min(point_timeout, max(remaining, 120.0))
                    elif remaining < 90.0:
                        if attempt == 1:
                            # Never-attempted point: that is budget
                            # TRIMMING, not a fault -- recording it in
                            # faults made the official record's "N faulted
                            # point attempt(s)" note misattribute planned
                            # trimming as failures (ADVICE r5).
                            dropped.append(b)
                            log(
                                f"batch {b:4d}: attempt skipped -- "
                                f"{remaining:.0f}s of budget left; "
                                "point dropped"
                            )
                        else:
                            # The point DID fault on attempt 1 (already in
                            # faults); the skipped retry stays a fault note
                            # so the record shows the retry never ran.
                            log(
                                f"batch {b:4d}: retry skipped -- "
                                f"{remaining:.0f}s of budget left"
                            )
                            faults.append({
                                "batch": b, "attempt": attempt,
                                "fault": "retry skipped: budget exhausted",
                            })
                        break
                    else:
                        point_timeout = min(point_timeout, remaining)
                cmd = [
                    sys.executable, os.path.abspath(__file__),
                    "--child-batch", str(b),
                    "--model", args.model,
                    "--scan-len", str(args.scan_len),
                    "--reps", str(args.reps),
                    "--dtype", args.dtype,
                    "--params-dtype", args.params_dtype,
                    "--peak-tflops", str(args.peak_tflops),
                ]
                if st["flops_img"]:
                    cmd += ["--flops-img", repr(st["flops_img"])]
                fault_msg = None
                proc = subprocess.Popen(
                    cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE
                )
                try:
                    out_b, err_b = proc.communicate(timeout=point_timeout)
                    timed_out = False
                except subprocess.TimeoutExpired:
                    # SIGTERM first, grace, then SIGKILL: let the child
                    # release the chip cleanly before the next one needs it.
                    proc.terminate()
                    try:
                        out_b, err_b = proc.communicate(timeout=30)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                        out_b, err_b = proc.communicate()
                    timed_out = True
                child_rc = proc.returncode
                proc = None
                stderr_text = (err_b or b"").decode(errors="replace")
                if stderr_text:
                    sys.stderr.write(stderr_text)
                    sys.stderr.flush()
                if timed_out:
                    fault_msg = (
                        f"timeout after {point_timeout:.0f}s: "
                        + stderr_text.strip()[-200:]
                    )
                elif child_rc != 0:
                    fault_msg = (
                        f"child exited rc={child_rc}: "
                        + stderr_text.strip()[-400:]
                    )
                else:
                    last = (out_b or b"").decode(errors="replace").strip().splitlines()
                    try:
                        payload = json.loads(last[-1]) if last else {}
                        row = payload["row"]
                        st["flops_img"] = payload.get("flops_img") or st["flops_img"]
                    except (json.JSONDecodeError, KeyError, IndexError,
                            TypeError, AttributeError) as e:
                        # TypeError/AttributeError: the last line parsed as
                        # a JSON scalar (stray library print) -- a fault on
                        # this point, never a sweep-killer.
                        row = None
                        fault_msg = f"child rc=0 but unparsable output ({e!r})"
                if row is not None:
                    break
                log(f"batch {b:4d}: FAULT (attempt {attempt}/2): {fault_msg}")
                faults.append({"batch": b, "attempt": attempt, "fault": fault_msg})
                if attempt == 1:
                    # Let the device runtime settle before retrying; a
                    # runtime crash ("kernel fault") takes substantially
                    # longer to clear than an ordinary child error.
                    # Skip the pause when the budget cannot admit the retry
                    # anyway -- idling 90 s inside the driver's grace window
                    # would waste exactly the margin the budget protects.
                    pause = 90.0 if "crashed or restarted" in (fault_msg or "") else 10.0
                    if args.budget_s and (
                        time.perf_counter() - t_sweep0
                    ) + pause + 60.0 > args.budget_s:
                        continue
                    time.sleep(pause)
            if row is not None:
                results[b] = row
            slowest_point_s = max(
                slowest_point_s, time.perf_counter() - t_point0
            )
            if emit is not None:
                emit(results, faults, st["flops_img"])
    except SweepTerminated:
        # Ignore further SIGTERMs from here on: a second signal during this
        # cleanup or the caller's final print would otherwise raise again
        # and truncate the very record this path exists to save.
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        st["terminated"] = True
        log("sweep: SIGTERM received -- finalizing partial record")
        if proc is not None:
            try:
                # Same graceful order as the timeout path: a child killed
                # hard may not have released the chip for the NEXT run.
                proc.terminate()
                try:
                    proc.communicate(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.communicate(timeout=5)
            except Exception:  # noqa: BLE001 - dying anyway, record first
                pass
    return results, faults, st["flops_img"], dropped, st["terminated"]


def bench_soak(duration_s, model, buckets):
    """Reliability soak: drive the REAL serving engine (fused fast path and
    all) across every bucket repeatedly for ``duration_s`` seconds,
    counting completed batches and faults.

    Round-3 postmortem: a device "kernel fault" was twice written off
    as transient with zero soak evidence anywhere in the repo (VERDICT r3
    weak-1); the k8s liveness probe silently depends on the engine NOT
    faulting under sustained bucket-ladder traffic.  This converts "not
    reproducible" into a measured rate.  A faulting predict is recorded and
    the soak CONTINUES (the next predict tells us whether the device
    recovered); 5 consecutive faults aborts the run as wedged.

    The value of the p50/p99 columns is drift detection; the fault count
    is the headline.  Prints the one-line JSON and returns rc 0 only
    for a fault-free soak.
    """
    import tempfile

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.runtime.engine import InferenceEngine

    spec = get_spec(model)
    root = tempfile.mkdtemp(prefix="kdlt-soak-")
    art.save_artifact(
        art.version_dir(root, spec.name, 1), spec,
        init_variables(spec, seed=0), None, {"compute_dtype": "bfloat16"},
    )
    artifact = art.load_artifact(art.version_dir(root, spec.name, 1))
    engine = InferenceEngine(artifact, buckets=buckets)
    log(f"soak: warming {len(buckets)} buckets ({buckets})...")
    warm_s = engine.warmup()
    log(f"soak: warmup {warm_s:.1f}s, fast_degraded={engine.fast_degraded}; "
        f"running {duration_s:.0f}s")

    rng = np.random.default_rng(0)
    imgs = {
        b: rng.integers(0, 256, size=(b, *spec.input_shape), dtype=np.uint8)
        for b in buckets
    }
    lat: dict[int, list] = {b: [] for b in buckets}
    faults: list[dict] = []
    consecutive = 0
    images_done = 0
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < duration_s:
        for b in buckets:
            t0 = time.perf_counter()
            try:
                out = engine.predict(imgs[b])
                assert out.shape == (b, spec.num_classes)
                lat[b].append(time.perf_counter() - t0)
                images_done += b
                consecutive = 0
            except Exception as e:  # noqa: BLE001 - faults are the measurement
                consecutive += 1
                faults.append({
                    "bucket": b,
                    "t_s": round(time.perf_counter() - t_start, 1),
                    "error": repr(e)[:300],
                })
                log(f"soak FAULT at bucket {b} "
                    f"(t+{faults[-1]['t_s']}s, consecutive {consecutive}): {e!r}")
                if consecutive >= 5:
                    log("soak: 5 consecutive faults -- device wedged, aborting")
                    break
        if consecutive >= 5:
            break
    elapsed = time.perf_counter() - t_start
    batches_done = sum(len(v) for v in lat.values())
    for b in buckets:
        a = np.array(lat[b]) * 1e3
        if a.size:
            log(f"  bucket {b:4d}: {a.size:6d} batches  p50 {np.percentile(a, 50):7.2f} ms  "
                f"p99 {np.percentile(a, 99):7.2f} ms (incl. host dispatch+RTT)")
    path = (
        "degraded-exact" if engine.fast_degraded
        else ("fused-fast" if engine._fast_engaged else "exact")
    )
    out = {
        "metric": (
            f"{spec.name} soak: batches completed across buckets {buckets} "
            f"in {elapsed:.0f}s on {path} "
            "engine (fault count is the reliability headline)"
        ),
        "value": batches_done,
        "unit": "batches",
        "vs_baseline": 1.0 if not faults else 0.0,
        "images": images_done,
        "elapsed_s": round(elapsed, 1),
        "fault_count": len(faults),
        "faults": faults,
    }
    print(json.dumps(out), flush=True)
    return 0 if not faults else 1


def bench_serving(duration_s, clients, batcher_impl, max_delay_ms, buckets):
    """End-to-end serving benchmark: concurrent single-image requests through
    the real HTTP model server (dynamic batcher included), measuring e2e
    p50/p99 and aggregate throughput.

    The whole ModelServer -- warm-up and device work included -- runs in
    THIS process, which therefore holds the chip: main() returns right after
    this mode and never goes on to spawn sweep children.
    """
    import tempfile
    import threading

    import requests as rq

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.serving import protocol
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    spec = get_spec("clothing-model")
    root = tempfile.mkdtemp(prefix="kdlt-bench-")
    # Params-only artifact (no StableHLO): the engine live-jits for the local
    # platform, skipping a multi-minute export trace the bench doesn't need.
    art.save_artifact(
        art.version_dir(root, spec.name, 1),
        spec,
        init_variables(spec, seed=0),
        None,
        {"compute_dtype": "bfloat16"},
    )
    server = ModelServer(
        root, port=0, buckets=buckets, max_delay_ms=max_delay_ms,
        batcher_impl=batcher_impl, host="127.0.0.1",
    )
    batcher_kind = server.models[spec.name].batcher_kind
    log(f"serving bench: batcher={batcher_kind}, warming {len(buckets)} buckets...")
    server.warmup()
    server.start()

    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, size=(1, *spec.input_shape), dtype=np.uint8)
    body = protocol.encode_predict_request(img)
    url = f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict"
    headers = {"Content-Type": protocol.MSGPACK_CONTENT_TYPE}

    latencies: list[float] = []
    errors = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def client():
        s = rq.Session()
        local = []
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                r = s.post(url, data=body, headers=headers, timeout=30)
                ok = r.status_code == 200
            except Exception:
                ok = False
            dt = time.perf_counter() - t0
            if ok:
                local.append(dt)
            else:
                with lock:
                    errors[0] += 1
        with lock:
            latencies.extend(local)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    server.shutdown()

    lat = np.array(sorted(latencies))
    if lat.size == 0:
        log("serving bench: no successful requests")
        return None
    result = {
        "batcher": batcher_kind,
        "clients": clients,
        "img_per_s": round(lat.size / elapsed, 1),
        "e2e_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "e2e_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "errors": errors[0],
    }
    log(
        f"serving e2e [{batcher_kind}]: {result['img_per_s']} img/s with "
        f"{clients} clients, p50 {result['e2e_p50_ms']} ms, "
        f"p99 {result['e2e_p99_ms']} ms, {errors[0]} errors"
    )
    return result


def bench_batcher_sweep(duration_s, clients, device_ms_list, max_delay_ms):
    """C++ vs Python batcher at controlled simulated device latencies.

    The native batcher's claimed advantages are structural -- GIL-free
    linger and depth-2 dispatch pipelining (assemble batch N+1 while batch
    N executes).  This isolates them: both batchers drive the SAME
    StubEngine with an async serial device (runtime.stub async_device) at
    each latency in ``device_ms_list``; the difference is pure batcher
    architecture, not device speed.  VERDICT r2 weak-6: replace the
    'sized for PCIe-latency serving' hand-waving with this curve.
    """
    import tempfile
    import threading

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.runtime.batcher import DynamicBatcher
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine

    spec = get_spec("clothing-model")
    rng = np.random.default_rng(0)
    root = tempfile.mkdtemp(prefix="kdlt-bsweep-")
    art.save_artifact(
        art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
    )
    artifact = art.load_artifact(art.version_dir(root, spec.name, 1))

    def make_native(engine):
        from kubernetes_deep_learning_tpu.runtime.native_batcher import NativeBatcher

        return NativeBatcher(engine, max_delay_ms=max_delay_ms)

    impls = [("python", lambda e: DynamicBatcher(e, max_delay_ms=max_delay_ms))]
    try:
        import kubernetes_deep_learning_tpu.ops._native  # noqa: F401

        impls.append(("native", make_native))
    except Exception as e:  # noqa: BLE001
        log(f"native batcher unavailable ({e!r}); sweeping python only")

    results = {}
    log(f"batcher sweep: {clients} client threads, {duration_s:.0f}s per point")
    for dev_ms in device_ms_list:
        row = {}
        for name, make in impls:
            engine = StubEngine(
                artifact, device_ms_per_batch=dev_ms, async_device=True
            )
            engine.warmup()
            batcher = make(engine)
            stop = threading.Event()
            counts = [0] * clients
            lat = [[] for _ in range(clients)]
            # Per-worker images generated BEFORE the threads start: numpy
            # Generators are not thread-safe.
            imgs = [
                rng.integers(0, 256, size=(*spec.input_shape,), dtype=np.uint8)
                for _ in range(clients)
            ]

            def worker(i, batcher=batcher, stop=stop, counts=counts, lat=lat):
                img = imgs[i]
                while not stop.is_set():
                    t0 = time.perf_counter()
                    batcher.predict(img)
                    lat[i].append(time.perf_counter() - t0)
                    counts[i] += 1

            threads = [
                threading.Thread(target=worker, args=(i,)) for i in range(clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(duration_s)
            stop.set()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            rps = sum(counts) / wall
            all_lat = np.concatenate([np.asarray(x) for x in lat if x]) * 1e3
            row[name] = {
                "img_per_s": round(rps, 1),
                "p50_ms": round(float(np.percentile(all_lat, 50)), 2),
                "p99_ms": round(float(np.percentile(all_lat, 99)), 2),
            }
            batcher.close()
            engine.close()
        line = f"  device {dev_ms:5.1f} ms/batch: " + "  ".join(
            f"{n} {r['img_per_s']:8.0f} img/s (p50 {r['p50_ms']:6.2f} ms)"
            for n, r in row.items()
        )
        if "native" in row and "python" in row:
            adv = row["native"]["img_per_s"] / max(row["python"]["img_per_s"], 1e-9)
            line += f"  native/python = {adv:.2f}x"
            row["native_advantage"] = round(adv, 3)
        log(line)
        results[dev_ms] = row
    return results


def bench_pipeline_ab(n_batches=150, batch=16, host_ms=3.0, device_ms=10.0,
                      depths=(1, 2)):
    """Pipelined vs serial dispatch, measured against a KNOWN device bound.

    Device-free acceptance microbenchmark for the in-flight dispatch
    pipeline (runtime.engine.InFlightDispatcher): a StubEngine with
    injected per-stage costs -- ``host_ms`` of batch gather + H2D enqueue
    on the dispatching thread, ``device_ms`` of serial device execution --
    is driven through the dispatcher at each depth.  The
    device-execute-only bound is ``n_batches * device_ms``; at depth 1
    every batch pays host + device back to back, a wall-clock gap of
    host/(host+device) below the bound, while depth 2 overlaps the host
    stage with the previous batch's execution and must land within a few
    percent of the bound (the acceptance bar: <=5% at depth 2, >=15% at
    depth 1 with the default stage costs).  Stage costs well above the
    ~0.1-0.2 ms time.sleep overshoot are deliberate defaults: at 1 ms
    device granularity the sleep jitter itself reads as a fake
    pipeline gap.

    Also verifies the pipelining contract the speedup must not cost:
    results at every depth are byte-identical to serial dispatch, and each
    future resolves to ITS batch's rows (per-request wiring/ordering).
    Returns (json_dict, rc); rc=0 iff the deepest depth meets the 5% bound
    and all checks pass.
    """
    from types import SimpleNamespace

    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.runtime.engine import InFlightDispatcher
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine, stub_logits

    spec = get_spec("clothing-model")
    rng = np.random.default_rng(0)
    # A small ring of distinct batches so misrouted futures are detectable
    # (every batch has a distinct checksum row) without allocating
    # n_batches full images.
    ring = [
        rng.integers(0, 256, size=(batch, *spec.input_shape), dtype=np.uint8)
        for _ in range(8)
    ]
    want = [stub_logits(x, spec.num_classes) for x in ring]
    bound_s = n_batches * device_ms / 1e3
    rows = {}
    outs_by_depth = {}
    log(
        f"pipeline A/B: {n_batches} batches of {batch}, host {host_ms}ms + "
        f"device {device_ms}ms per batch; device-execute-only bound "
        f"{bound_s:.2f}s"
    )
    for depth in depths:
        engine = StubEngine(
            SimpleNamespace(spec=spec),
            device_ms_per_batch=device_ms,
            async_device=True,
            host_ms_per_batch=host_ms,
        )
        engine.warmup()
        disp = InFlightDispatcher(engine, depth=depth)
        futs = []
        t0 = time.perf_counter()
        for i in range(n_batches):
            futs.append(disp.submit(ring[i % len(ring)]))
        outs = [np.asarray(f.result(timeout=120)) for f in futs]
        wall = time.perf_counter() - t0
        disp.close()
        engine.close()
        miswired = sum(
            0 if np.array_equal(outs[i], want[i % len(ring)]) else 1
            for i in range(n_batches)
        )
        gap = max(0.0, wall / bound_s - 1.0)
        rows[depth] = {
            "wall_s": round(wall, 3),
            "img_per_s": round(n_batches * batch / wall, 1),
            "gap_vs_device_bound": round(gap, 4),
            "miswired_futures": miswired,
        }
        outs_by_depth[depth] = outs
        log(
            f"  depth {depth}: {wall:7.3f}s wall "
            f"({rows[depth]['img_per_s']:9.1f} img/s), "
            f"{gap * 100:5.1f}% above the device bound"
            + (f", {miswired} MISWIRED futures" if miswired else "")
        )
    first = outs_by_depth[depths[0]]
    identical = all(
        all(np.array_equal(a, b) for a, b in zip(first, outs_by_depth[d]))
        for d in depths[1:]
    )
    deepest = max(depths)
    speedup = rows[depths[0]]["wall_s"] / rows[deepest]["wall_s"]
    ok = (
        identical
        and all(r["miswired_futures"] == 0 for r in rows.values())
        and rows[deepest]["gap_vs_device_bound"] <= 0.05
    )
    out = {
        "metric": (
            f"pipelined dispatch A/B (stub engine, host {host_ms}ms + device "
            f"{device_ms}ms per batch x {n_batches} batches): depth-{deepest} "
            f"wall-clock speedup over depth-{depths[0]}; depth-{deepest} gap "
            f"vs device-execute-only bound "
            f"{rows[deepest]['gap_vs_device_bound'] * 100:.1f}%, results "
            + ("byte-identical across depths" if identical else "NOT identical")
            + ")"
        ),
        "value": round(speedup, 3),
        "unit": "x wall-clock speedup",
        "vs_baseline": round(speedup, 3),
        "device_bound_s": round(bound_s, 3),
        "identical_across_depths": identical,
        "depths": {str(d): rows[d] for d in depths},
    }
    return out, 0 if ok else 1


_CROSSHOST_AB_WORKER = r"""
import json, os, sys, time
from collections import deque
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from kubernetes_deep_learning_tpu.utils.platform import force_platform
force_platform("cpu")
from kubernetes_deep_learning_tpu.utils.distributed import initialize
assert initialize(), "env triplet must trigger jax.distributed.initialize"
import jax
import numpy as np
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.parallel.mesh import make_mesh
from kubernetes_deep_learning_tpu.parallel.crosshost import CrossHostForward
from kubernetes_deep_learning_tpu.models import init_variables

cfg = json.loads(sys.argv[1])
spec = register_spec(ModelSpec(
    name="xh-bench", family="vit-tiny", input_shape=(32, 32, 3),
    labels=("a", "b", "c", "d"), preprocessing="tf",
))
variables = init_variables(spec, seed=7)  # same seed -> identical everywhere
mesh = make_mesh(len(jax.devices()), devices=jax.devices())
xh = CrossHostForward(
    spec, mesh, variables, buckets=(cfg["batch"],),
    pipeline_depth=max(cfg["depths"]),
)

if jax.process_index() != 0:
    xh.follower_loop()
    sys.exit(0)

rng = np.random.default_rng(cfg["seed"])
ring = [
    rng.integers(0, 256, (cfg["batch"], *spec.input_shape), np.uint8)
    for _ in range(8)
]
xh.predict(ring[0])  # compile round (off the clock)

host_ms = cfg["host_ms"]
if host_ms <= 0:
    # Calibrate the simulated per-round host work (the batcher's
    # assembly/decode stand-in) to the measured round time, the regime
    # where overlap matters most: pipelined wall ~= max(host, round)
    # while lockstep pays host + round.
    t0 = time.perf_counter()
    for i in range(10):
        xh.predict(ring[i % len(ring)])
    host_ms = 1e3 * (time.perf_counter() - t0) / 10

def run_arm(depth):
    outs = [None] * cfg["rounds"]
    lat = []
    pending = deque()  # (t_submit, handle, n, i)

    def complete_oldest():
        t_sub, h, n, i = pending.popleft()
        outs[i] = np.asarray(h)[:n]
        lat.append(time.perf_counter() - t_sub)

    t_start = time.perf_counter()
    for i in range(cfg["rounds"]):
        time.sleep(host_ms / 1e3)  # simulated host assembly for round i
        if depth == 0:  # pure lockstep reference: the synchronous API
            t_sub = time.perf_counter()
            outs[i] = xh.predict(ring[i % len(ring)])
            lat.append(time.perf_counter() - t_sub)
            continue
        t_sub = time.perf_counter()
        h, n = xh.predict_async(ring[i % len(ring)])
        pending.append((t_sub, h, n, i))
        while len(pending) >= depth:
            complete_oldest()
    while pending:
        complete_oldest()
    wall = time.perf_counter() - t_start
    lat_ms = sorted(1e3 * x for x in lat)
    return outs, {
        "wall_s": round(wall, 3),
        "img_per_s": round(cfg["rounds"] * cfg["batch"] / wall, 1),
        "p50_ms": round(lat_ms[len(lat_ms) // 2], 2),
        "p95_ms": round(lat_ms[int(len(lat_ms) * 0.95)], 2),
    }

arms = {}
outs_by_arm = {}
outs_by_arm["lockstep"], arms["lockstep"] = run_arm(0)
for d in cfg["depths"]:
    outs_by_arm[f"depth{d}"], arms[f"depth{d}"] = run_arm(d)
xh.shutdown()

ref = outs_by_arm["lockstep"]
identical = {
    name: all(np.array_equal(a, b) for a, b in zip(ref, outs))
    for name, outs in outs_by_arm.items()
}
print("CROSSHOST-AB " + json.dumps({
    "host_ms": round(host_ms, 3),
    "arms": arms,
    "identical_to_lockstep": identical,
}), flush=True)
"""


def bench_crosshost_ab(n_rounds=60, batch=32, host_ms=0.0, processes=2,
                       depths=(1, 2), seed=0, speedup_floor=1.15):
    """Pipelined vs lockstep CROSS-HOST dispatch on a real multi-process
    CPU fleet (utils.distributed + Gloo collectives, no device needed).

    Spawns ``processes`` python processes that join one jax runtime (the
    same env-triplet bring-up tests/test_crosshost.py uses), shards one
    model over all of them, and drives the leader through three arms over
    the identical round sequence:

    - ``lockstep``: the synchronous predict() API -- broadcast, collective,
      readback fully materialized per round (the pre-round-5 cadence);
    - ``depth1``: predict_async at in-flight budget 1 -- must reproduce
      lockstep timing AND logits exactly (the safe fallback);
    - ``depthN``: the pipelined path -- round N+1's simulated host
      assembly (``host_ms``; 0 calibrates it to the measured round time)
      overlaps round N's collective execution.

    rc=0 iff every arm's logits are bit-identical to lockstep and the
    deepest arm's throughput is >= ``speedup_floor`` x lockstep.
    """
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = {
        "rounds": n_rounds, "batch": batch, "host_ms": host_ms,
        "depths": sorted(set(depths)), "seed": seed,
    }
    env_base = {
        **os.environ,
        "KDLT_COORDINATOR": f"127.0.0.1:{port}",
        "KDLT_NUM_PROCESSES": str(processes),
        "KDLT_DIST_INIT_TIMEOUT_S": os.environ.get(
            "KDLT_DIST_INIT_TIMEOUT_S", "120"
        ),
        # Followers size their in-flight budget from the env (the leader
        # constructs explicitly); every process must agree, like any other
        # fleet-wide config.
        "KDLT_XH_PIPELINE_DEPTH": str(max(cfg["depths"])),
    }
    # The fleet is CPU-only by construction (the worker script pins
    # JAX_PLATFORMS=cpu before importing jax), so its processes never need
    # a chip and this parent never imports jax at all.
    log(
        f"cross-host A/B: {processes}-process CPU fleet, {n_rounds} rounds "
        f"of batch {batch} per arm, depths {cfg['depths']} "
        f"(host_ms {'auto' if host_ms <= 0 else host_ms})"
    )
    procs = []
    for pid in range(processes):
        env = {**env_base, "KDLT_PROCESS_ID": str(pid)}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CROSSHOST_AB_WORKER, json.dumps(cfg)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=os.path.dirname(os.path.abspath(__file__)),
        ))
    outputs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            return {"metric": "cross-host A/B", "error": "fleet timed out"}, 1
        outputs.append((p.returncode, out))
    for rc, out in outputs:
        if rc != 0:
            return {
                "metric": "cross-host A/B",
                "error": f"worker rc {rc}: {out[-2000:]}",
            }, 1
    line = next(
        (ln for ln in outputs[0][1].splitlines()[::-1]
         if ln.startswith("CROSSHOST-AB ")), None,
    )
    if line is None:
        return {
            "metric": "cross-host A/B",
            "error": f"no result line: {outputs[0][1][-2000:]}",
        }, 1
    res = json.loads(line[len("CROSSHOST-AB "):])
    arms, identical = res["arms"], res["identical_to_lockstep"]
    deepest = f"depth{max(cfg['depths'])}"
    speedup = arms[deepest]["img_per_s"] / arms["lockstep"]["img_per_s"]
    for name, row in arms.items():
        log(
            f"  {name:>9}: {row['img_per_s']:8.1f} img/s "
            f"(wall {row['wall_s']:6.3f}s, p50 {row['p50_ms']:6.2f}ms)"
            + ("" if identical.get(name, False) else "  LOGITS DIVERGE")
        )
    ok = all(identical.values()) and speedup >= speedup_floor
    out = {
        "metric": (
            f"pipelined cross-host dispatch A/B ({processes}-process CPU "
            f"fleet, {n_rounds} rounds of batch {batch}, simulated host "
            f"work {res['host_ms']}ms/round): {deepest} throughput over "
            "lockstep; logits "
            + ("bit-identical across arms" if all(identical.values())
               else "NOT identical")
        ),
        "value": round(speedup, 3),
        "unit": "x img/s over lockstep",
        "vs_baseline": round(speedup, 3),
        "host_ms": res["host_ms"],
        "identical_to_lockstep": identical,
        "p50_delta_ms": round(
            arms[deepest]["p50_ms"] - arms["lockstep"]["p50_ms"], 2
        ),
        "arms": arms,
    }
    return out, 0 if ok else 1


def bench_overload_ab(duration_s=8.0, device_ms=100.0, deadline_ms=600.0,
                      rate_x=2.0, buckets=(1, 2), max_delay_ms=2.0):
    """Admission control A/B under overload: goodput with vs without.

    Device-free acceptance harness for serving.admission.  A REAL
    ModelServer fronts a StubEngine whose predict sleeps ``device_ms`` per
    batch (GIL-free, like a device wait), so the tier's capacity is known by
    construction: max_bucket / device_ms images/sec.  An open-loop client
    fires single-image predicts at ``rate_x`` times that capacity for
    ``duration_s`` -- each request carrying a ``deadline_ms`` budget in the
    X-Request-Deadline-Ms header -- once against a server with admission ON
    and once with admission OFF (the legacy posture: header ignored, no
    shedding, fixed 20 s batcher wait).

    Open-loop semantics: latency is measured from each request's SCHEDULED
    send time, so server-side backlog counts against it exactly as a real
    client would experience.  Goodput = completions within their deadline
    per second.  Without admission every request queues and degrades
    together (the ramping backlog pushes all but the earliest past the
    deadline); with admission the tiers shed what they cannot finish and
    the admitted work completes inside its budget.

    Returns (json_dict, rc); rc=0 iff goodput(admission) >=
    goodput(baseline) AND in-deadline p99(admission) < p99(baseline).
    """
    import tempfile
    import threading

    import requests

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving import protocol
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    spec = register_spec(
        ModelSpec(
            name="overload-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    buckets = tuple(sorted(buckets))
    capacity_rps = buckets[-1] / (device_ms / 1e3)
    offered_rps = rate_x * capacity_rps
    deadline_s = deadline_ms / 1e3
    n_requests = int(duration_s * offered_rps)
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, size=(1, *spec.input_shape), dtype=np.uint8)
    body = protocol.encode_predict_request(img)
    log(
        f"overload A/B: stub capacity {capacity_rps:.0f} img/s "
        f"({buckets[-1]}-bucket / {device_ms}ms), offered {offered_rps:.0f} "
        f"req/s x {duration_s}s = {n_requests} requests, deadline "
        f"{deadline_ms:.0f}ms per request"
    )

    def run_arm(admission_on: bool) -> dict:
        root = tempfile.mkdtemp(prefix="kdlt-overload-")
        art.save_artifact(
            art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
        )
        server = ModelServer(
            root, port=0, buckets=buckets, max_delay_ms=max_delay_ms,
            host="127.0.0.1",
            engine_factory=lambda a, **kw: StubEngine(
                a, device_ms_per_batch=device_ms, **kw
            ),
            admission=admission_on,
        )
        server.warmup()
        server.start()
        url = f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict"
        headers = {
            "Content-Type": protocol.MSGPACK_CONTENT_TYPE,
            DEADLINE_HEADER: f"{deadline_ms:.1f}",
        }
        session = requests.Session()
        session.mount("http://", requests.adapters.HTTPAdapter(
            pool_connections=4, pool_maxsize=1024,
        ))
        results: list = [None] * n_requests

        def fire(i: int, at: float) -> None:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                r = session.post(url, data=body, headers=headers, timeout=30.0)
                status = r.status_code
            except Exception:
                status = -1
            # Open-loop latency: measured from the SCHEDULED send time.
            results[i] = (time.monotonic() - at, status)

        t_base = time.monotonic() + 0.25
        threads = [
            threading.Thread(
                target=fire, args=(i, t_base + i / offered_rps), daemon=True
            )
            for i in range(n_requests)
        ]
        for t in threads:
            t.start()
        # Give stragglers a bounded grace past the send window, then force
        # the end: shutdown fails the still-queued waiters fast (their
        # latency is far past the deadline either way, so the goodput and
        # in-deadline percentiles are already decided).
        end_by = t_base + duration_s + max(2.0, 4 * deadline_s)
        for t in threads:
            t.join(timeout=max(0.0, end_by - time.monotonic()))
        # Server-side SLO view (utils.slo), fetched before shutdown: the
        # acceptance cross-check that /debug/slo's goodput/burn agrees with
        # this arm's client-side ground truth.  Reported, never gating.
        slo_view = None
        try:
            slo = session.get(
                f"http://127.0.0.1:{server.port}/debug/slo", timeout=5.0
            ).json()
            slo_view = (slo.get("models") or {}).get(spec.name)
        except Exception:  # noqa: BLE001 - diagnostics only
            pass
        server.shutdown()
        for t in threads:
            t.join(timeout=10.0)
        done = [r for r in results if r is not None]
        ok_lat = sorted(lat for lat, status in done if status == 200)
        in_deadline = [lat for lat in ok_lat if lat <= deadline_s]
        shed = sum(1 for _, status in done if status in (503, 504))
        arm = {
            "offered_rps": round(offered_rps, 1),
            "completed_200": len(ok_lat),
            "shed_5xx": shed,
            "unresolved": n_requests - len(done),
            "goodput_rps": round(len(in_deadline) / duration_s, 2),
            "p99_in_deadline_ms": (
                round(float(np.percentile(in_deadline, 99)) * 1e3, 1)
                if in_deadline else float("inf")
            ),
            "p50_in_deadline_ms": (
                round(float(np.percentile(in_deadline, 50)) * 1e3, 1)
                if in_deadline else float("inf")
            ),
            "p99_all_completions_ms": (
                round(float(np.percentile(ok_lat, 99)) * 1e3, 1)
                if ok_lat else float("inf")
            ),
            "slo_view": slo_view,
        }
        log(
            f"  admission={'on ' if admission_on else 'off'}: "
            f"goodput {arm['goodput_rps']:7.2f}/s of {offered_rps:.0f} offered, "
            f"{arm['completed_200']} x 200 ({len(in_deadline)} in-deadline), "
            f"{shed} shed, in-deadline p99 {arm['p99_in_deadline_ms']} ms, "
            f"all-200 p99 {arm['p99_all_completions_ms']} ms"
        )
        return arm

    arm_on = run_arm(True)
    arm_off = run_arm(False)
    ok = (
        arm_on["goodput_rps"] >= arm_off["goodput_rps"]
        and arm_on["p99_in_deadline_ms"] < arm_off["p99_in_deadline_ms"]
    )
    ratio = arm_on["goodput_rps"] / max(arm_off["goodput_rps"], 1e-9)
    out = {
        "metric": (
            f"admission-control overload A/B (stub backend, capacity "
            f"{capacity_rps:.0f} req/s, {rate_x:g}x offered load, "
            f"{deadline_ms:.0f}ms deadline): goodput ratio admission-on / "
            f"admission-off; in-deadline p99 "
            f"{arm_on['p99_in_deadline_ms']} vs {arm_off['p99_in_deadline_ms']} ms"
        ),
        "value": round(ratio, 2),
        "unit": "x goodput (in-deadline completions/s)",
        "vs_baseline": round(ratio, 2),
        "capacity_rps": round(capacity_rps, 1),
        "deadline_ms": deadline_ms,
        "rate_x": rate_x,
        "arms": {"admission": arm_on, "baseline": arm_off},
    }
    return out, 0 if ok else 1


def bench_multimodel_ab(duration_s=6.0, heavy_device_ms=120.0,
                        light_device_ms=5.0, heavy_deadline_ms=2000.0,
                        light_deadline_ms=300.0, rate_x=2.0, light_rps=40.0,
                        buckets=(1, 2, 4)):
    """Multi-model scheduling A/B: weighted deadline-aware vs naive FIFO.

    Two stub-backed models share ONE UnifiedScheduler + one in-flight
    dispatcher (the multi-model serving core, runtime/scheduler.py): a
    HEAVY model (``heavy_device_ms`` per batch, generous deadline) offered
    at ``rate_x`` times its known capacity, and a LIGHT model (cheap
    batches, tight deadline) offered at a rate costing only a few percent
    of device time.  This is the INFaaS/Clipper mixed-tenancy scenario:
    under overload the heavy model's backlog grows without bound, and a
    naive arrival-order (FIFO) arbiter starves the light model behind it
    -- every light request waits out the ever-older heavy queue head and
    blows its tight deadline, even though serving it would cost almost
    nothing.  The weighted deadline-aware policy fixes exactly this: the
    light lane's earlier effective deadlines and its weight-floor share
    guarantee let it preempt the doomed heavy backlog.

    Open-loop semantics (as in --overload-ab): latency is measured from
    each request's SCHEDULED send time.  Per model, goodput is in-deadline
    completions as a FRACTION of offered load; the headline is the
    worst-model goodput -- the number a platform operator must defend per
    tenant.  rc=0 iff the weighted arm beats FIFO on worst-model goodput
    by >= 1.2x AND does not lose on the heavy model (the light model's
    rescue must come out of the doomed backlog, not the heavy model's
    viable completions).
    """
    import threading

    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.scheduler import UnifiedScheduler
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving.admission import Deadline
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    class _Artifact:
        def __init__(self, spec):
            self.spec = spec

    buckets = tuple(sorted(buckets))
    shape = (32, 32, 3)
    heavy = register_spec(ModelSpec(
        name="mm-heavy", family="xception", input_shape=shape,
        labels=("a", "b", "c"),
    ))
    light = register_spec(ModelSpec(
        name="mm-light", family="xception", input_shape=shape,
        labels=("x", "y"),
    ))
    heavy_capacity = buckets[-1] / (heavy_device_ms / 1e3)
    heavy_rps = rate_x * heavy_capacity
    plans = {
        heavy.name: (heavy_rps, heavy_deadline_ms, heavy_device_ms),
        light.name: (light_rps, light_deadline_ms, light_device_ms),
    }
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, size=shape, dtype=np.uint8)
    log(
        f"multimodel A/B: heavy capacity {heavy_capacity:.0f} img/s "
        f"({buckets[-1]}-bucket / {heavy_device_ms}ms), offered "
        f"{heavy_rps:.0f} rps @ {heavy_deadline_ms:.0f}ms deadline; light "
        f"{light_rps:.0f} rps @ {light_deadline_ms:.0f}ms deadline "
        f"({light_device_ms}ms/batch); {duration_s}s per arm"
    )

    def run_arm(policy: str) -> dict:
        engines = {
            name: StubEngine(
                _Artifact(spec), buckets=buckets, async_device=True,
                device_ms_per_batch=plans[name][2],
            )
            for name, spec in ((heavy.name, heavy), (light.name, light))
        }
        sched = UnifiedScheduler(
            registry=metrics_lib.Registry(), policy=policy, weights={},
        )
        for name, engine in engines.items():
            sched.register(name, engine, max_delay_ms=2.0)
        results: dict[str, list] = {name: [] for name in plans}
        results_lock = threading.Lock()
        threads = []
        t_base = time.monotonic() + 0.25

        def fire(name: str, at: float, deadline_s: float) -> None:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                fut = sched.submit(name, img, deadline=Deadline(deadline_s))
                fut.result(timeout=deadline_s * 4 + 2.0)
                ok = True
            except Exception:
                ok = False
            lat = time.monotonic() - at  # open-loop: from the SCHEDULED send
            with results_lock:
                results[name].append((lat, ok))

        for name, (rps, deadline_ms, _dev) in plans.items():
            n = int(duration_s * rps)
            for i in range(n):
                threads.append(threading.Thread(
                    target=fire,
                    args=(name, t_base + i / rps, deadline_ms / 1e3),
                    daemon=True,
                ))
        for t in threads:
            t.start()
        end_by = t_base + duration_s + max(
            2.0, 4 * heavy_deadline_ms / 1e3
        )
        for t in threads:
            t.join(timeout=max(0.0, end_by - time.monotonic()))
        sched.close(drain=False)
        for e in engines.values():
            e.close()
        arm: dict = {"policy": policy, "models": {}}
        worst = None
        for name, (rps, deadline_ms, _dev) in plans.items():
            offered = int(duration_s * rps)
            done = results[name]
            in_deadline = sum(
                1 for lat, ok in done if ok and lat <= deadline_ms / 1e3
            )
            frac = in_deadline / max(offered, 1)
            arm["models"][name] = {
                "offered": offered,
                "completed": sum(1 for _, ok in done if ok),
                "in_deadline": in_deadline,
                "goodput_frac": round(frac, 3),
                "goodput_rps": round(in_deadline / duration_s, 2),
            }
            worst = frac if worst is None else min(worst, frac)
        arm["worst_model_goodput_frac"] = round(worst or 0.0, 3)
        log(
            f"  policy={policy:17s}: worst-model goodput "
            f"{arm['worst_model_goodput_frac']:.3f} "
            + " ".join(
                f"{n}={m['goodput_frac']:.3f}" for n, m in arm["models"].items()
            )
        )
        return arm

    arm_weighted = run_arm("weighted_deadline")
    arm_fifo = run_arm("fifo")
    w_worst = arm_weighted["worst_model_goodput_frac"]
    f_worst = arm_fifo["worst_model_goodput_frac"]
    w_heavy = arm_weighted["models"][heavy.name]["goodput_frac"]
    f_heavy = arm_fifo["models"][heavy.name]["goodput_frac"]
    ratio = w_worst / max(f_worst, 1e-9)
    # The light model's rescue must not come out of the heavy model's
    # viable completions: heavy goodput may dip only within noise (the
    # light lane costs a few percent of device time by construction).
    ok = ratio >= 1.2 and w_heavy >= 0.8 * f_heavy
    out = {
        "metric": (
            f"multi-model scheduling A/B (2 stub models, one shared "
            f"dispatcher; heavy {rate_x:g}x overloaded @ "
            f"{heavy_deadline_ms:.0f}ms, light {light_rps:g} rps @ "
            f"{light_deadline_ms:.0f}ms): worst-model in-deadline goodput, "
            f"weighted_deadline vs fifo"
        ),
        "value": round(ratio, 2),
        "unit": "x worst-model in-deadline goodput (weighted / fifo)",
        "vs_baseline": round(ratio, 2),
        "arms": {"weighted_deadline": arm_weighted, "fifo": arm_fifo},
    }
    return out, 0 if ok else 1


def bench_tenant_ab(duration_s=5.0, device_ms=50.0, deadline_ms=1500.0,
                    rate_x=3.0, b_rps=12.0, buckets=(1, 2), flood_s=6.0,
                    tail_s=12.0, interactive_rps=10.0, batch_rps=5.0,
                    besteffort_rps=100.0, brownout_deadline_ms=1000.0,
                    seed=0):
    """Tenant isolation + brownout acceptance: budgets A/B, then the ladder.

    Two proofs in one harness (serving/admission, GUIDE 10l):

    PART 1 -- per-model admission budgets.  ONE real ModelServer serves two
    stub-backed models ("tenant-a", "tenant-b") from one registry; tenant A
    is offered ``rate_x`` times the tier's whole capacity while tenant B
    asks for a modest, comfortably-servable ``b_rps``.  Run twice: budgets
    ON (KDLT_ADMIT_BUDGETS=tenant-a=1,tenant-b=1) vs the legacy SHARED
    limiter (KDLT_ADMIT_BUDGETS=0); everything else -- scheduler weights
    included -- is identical, so the delta is attributable to admission
    partitioning alone.  Under the shared limiter A's flood owns the
    admission queue (B's arrivals find it full of equal-priority earlier
    waiters and shed queue_full); with budgets B's under-share arrivals
    evict A's over-share waiters and grant first.  Gate: tenant B holds
    >= 95% in-deadline goodput with budgets while the shared baseline
    collapses below 0.8x of that.

    PART 2 -- SLO-burn brownout.  A real Gateway (cache on, short injected
    "5m" SLO window, fast brownout dwell) fronts one stub model tier.
    Interactive clients fetch a small cacheable URL universe for the whole
    run; a best-effort flood of always-distinct URLs overloads the model
    tier mid-run.  The tier's sheds blow the 5m burn past the enter
    thresholds, the ladder climbs to stage >= 3, best-effort is shed 429
    at the gateway front door (excluded from the burn denominator -- the
    recovery mechanism), the window rolls the bad epoch off, and the
    ladder walks back down.  Gates: interactive in-deadline goodput >= 95%
    across the WHOLE run (flood included), final 5m burn < 1.0, peak stage
    >= 3, and zero stage flaps (the transition log is monotone: never an
    up-transition after a down-transition).

    Returns (json_dict, rc); rc=0 iff all gates above hold.
    """
    import tempfile
    import threading
    from contextlib import contextmanager
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving import protocol
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    @contextmanager
    def scoped_env(overrides: dict):
        old = {k: os.environ.get(k) for k in overrides}
        os.environ.update(overrides)
        try:
            yield
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    shape = (32, 32, 3)
    specs = {
        name: register_spec(ModelSpec(
            name=name, family="xception",  # never instantiated by StubEngine
            input_shape=shape, labels=("a", "b", "c"),
        ))
        for name in ("tenant-a", "tenant-b")
    }
    buckets = tuple(sorted(buckets))
    capacity_rps = buckets[-1] / (device_ms / 1e3)
    a_rps = rate_x * capacity_rps
    deadline_s = deadline_ms / 1e3
    rng = np.random.default_rng(seed)
    img = rng.integers(0, 256, size=(1, *shape), dtype=np.uint8)
    body = protocol.encode_predict_request(img)
    log(
        f"tenant A/B part 1: capacity {capacity_rps:.0f} img/s "
        f"({buckets[-1]}-bucket / {device_ms}ms); tenant-a {a_rps:.0f} rps "
        f"({rate_x:g}x), tenant-b {b_rps:g} rps, deadline "
        f"{deadline_ms:.0f}ms, {duration_s}s per arm"
    )

    def run_budget_arm(budgets_on: bool) -> dict:
        env = {
            "KDLT_ADMIT_BUDGETS": (
                "tenant-a=1,tenant-b=1" if budgets_on else "0"
            ),
            # Identical in both arms: a tight admission ceiling (the flood
            # must contend for slots, not hide behind a huge limit) and
            # fair DEVICE-time weights, so admission partitioning is the
            # only delta under test.
            "KDLT_ADMISSION_MAX_CONCURRENCY": "8",
            "KDLT_ADMISSION_INITIAL_CONCURRENCY": "8",
            "KDLT_SCHED_WEIGHTS": "tenant-a=1,tenant-b=1",
        }
        with scoped_env(env):
            root = tempfile.mkdtemp(prefix="kdlt-tenant-")
            for spec in specs.values():
                art.save_artifact(
                    art.version_dir(root, spec.name, 1), spec,
                    {"params": {}}, None, {},
                )
            server = ModelServer(
                root, port=0, buckets=buckets, max_delay_ms=1.0,
                host="127.0.0.1",
                engine_factory=lambda a, **kw: StubEngine(
                    a, device_ms_per_batch=device_ms, **kw
                ),
            )
            server.warmup()
            server.start()
        session = requests.Session()
        session.mount("http://", requests.adapters.HTTPAdapter(
            pool_connections=4, pool_maxsize=1024,
        ))
        headers = {
            "Content-Type": protocol.MSGPACK_CONTENT_TYPE,
            DEADLINE_HEADER: f"{deadline_ms:.1f}",
        }
        plans = {"tenant-a": a_rps, "tenant-b": b_rps}
        results: dict[str, list] = {name: [] for name in plans}
        results_lock = threading.Lock()

        def fire(name: str, at: float) -> None:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                r = session.post(
                    f"http://127.0.0.1:{server.port}/v1/models/{name}:predict",
                    data=body, headers=headers, timeout=30.0,
                )
                status = r.status_code
            except Exception:
                status = -1
            lat = time.monotonic() - at  # open-loop: from the SCHEDULED send
            with results_lock:
                results[name].append((lat, status))

        t_base = time.monotonic() + 0.25
        threads = []
        for name, rps in plans.items():
            for i in range(int(duration_s * rps)):
                threads.append(threading.Thread(
                    target=fire, args=(name, t_base + i / rps), daemon=True,
                ))
        for t in threads:
            t.start()
        end_by = t_base + duration_s + max(2.0, 3 * deadline_s)
        for t in threads:
            t.join(timeout=max(0.0, end_by - time.monotonic()))
        # Budget shares snapshot (in-process: the bench owns the server);
        # reported, never gating.
        limiter = server.admission.limiter
        shares = limiter.shares() if limiter is not None else None
        server.shutdown()
        for t in threads:
            t.join(timeout=10.0)
        arm: dict = {"budgets": budgets_on, "models": {}, "admission": shares}
        for name, rps in plans.items():
            offered = int(duration_s * rps)
            done = results[name]
            in_deadline = sum(
                1 for lat, status in done
                if status == 200 and lat <= deadline_s
            )
            arm["models"][name] = {
                "offered": offered,
                "resolved": len(done),
                "completed_200": sum(1 for _, s in done if s == 200),
                "shed": sum(1 for _, s in done if s in (429, 503, 504)),
                "in_deadline": in_deadline,
                "goodput_frac": round(in_deadline / max(offered, 1), 3),
            }
        log(
            f"  budgets={'on ' if budgets_on else 'off'}: "
            + " ".join(
                f"{n} goodput {m['goodput_frac']:.3f} "
                f"({m['in_deadline']}/{m['offered']}, {m['shed']} shed)"
                for n, m in arm["models"].items()
            )
        )
        return arm

    arm_budgets = run_budget_arm(True)
    arm_shared = run_budget_arm(False)
    b_budget = arm_budgets["models"]["tenant-b"]["goodput_frac"]
    b_shared = arm_shared["models"]["tenant-b"]["goodput_frac"]
    part1_ok = b_budget >= 0.95 and b_shared < 0.8 * b_budget

    # ---- PART 2: the brownout ladder over a real gateway + model tier ----
    class QuietImageHandler(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    brown_deadline_s = brownout_deadline_ms / 1e3
    total_s = flood_s + tail_s
    window_s = 6.0
    dwell_s = 1.0
    img_dir = tempfile.mkdtemp(prefix="kdlt-tenant-img-")
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(os.path.join(img_dir, "img.png"))
    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(QuietImageHandler, directory=img_dir)
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    base_url = f"http://127.0.0.1:{img_httpd.server_address[1]}/img.png"
    log(
        f"tenant A/B part 2: brownout ladder -- interactive "
        f"{interactive_rps:g} rps + batch {batch_rps:g} rps for {total_s:g}s,"
        f" best-effort flood {besteffort_rps:g} rps for {flood_s:g}s; "
        f"'5m' window {window_s:g}s, dwell {dwell_s:g}s, deadline "
        f"{brownout_deadline_ms:.0f}ms"
    )

    root = tempfile.mkdtemp(prefix="kdlt-tenant-gw-")
    art.save_artifact(
        art.version_dir(root, "tenant-a", 1), specs["tenant-a"],
        {"params": {}}, None, {},
    )
    server = ModelServer(
        root, port=0, buckets=buckets, max_delay_ms=1.0, host="127.0.0.1",
        engine_factory=lambda a, **kw: StubEngine(
            a, device_ms_per_batch=device_ms, **kw
        ),
    )
    server.warmup()
    server.start()
    gw = Gateway(
        serving_host=f"127.0.0.1:{server.port}", model="tenant-a",
        port=0, host="127.0.0.1", cache=True, cache_swr_s=30.0,
        slo_windows=(("5m", window_s),),
        brownout_dwell_s=dwell_s, brownout_eval_s=0.2,
    )
    gw.start()
    gw.spec  # discover the contract before the clock starts

    session = requests.Session()
    session.mount("http://", requests.adapters.HTTPAdapter(
        pool_connections=4, pool_maxsize=1024,
    ))
    class_results: dict[str, list] = {
        "interactive": [], "batch": [], "best-effort": [],
    }
    class_lock = threading.Lock()

    def fire_gw(cls: str, url_tag: str, at: float) -> None:
        delay = at - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            r = session.post(
                f"http://127.0.0.1:{gw.port}/predict",
                json={"url": f"{base_url}?{url_tag}"},
                headers={
                    DEADLINE_HEADER: f"{brownout_deadline_ms:.1f}",
                    protocol.PRIORITY_HEADER: cls,
                },
                timeout=brown_deadline_s + 5.0,
            )
            status = r.status_code
        except Exception:
            status = -1
        lat = time.monotonic() - at
        with class_lock:
            class_results[cls].append((lat, status))

    threads = []
    t_base = time.monotonic() + 0.25
    for i in range(int(total_s * interactive_rps)):
        threads.append(threading.Thread(
            target=fire_gw,
            args=("interactive", f"i={i % 8}", t_base + i / interactive_rps),
            daemon=True,
        ))
    for i in range(int(total_s * batch_rps)):
        threads.append(threading.Thread(
            target=fire_gw,
            args=("batch", f"b={i % 4}", t_base + i / batch_rps),
            daemon=True,
        ))
    flood_t0 = t_base + 1.0  # one clean second first: burn starts at 0
    for i in range(int(flood_s * besteffort_rps)):
        threads.append(threading.Thread(
            target=fire_gw,
            args=("best-effort", f"f={i}", flood_t0 + i / besteffort_rps),
            daemon=True,
        ))
    for t in threads:
        t.start()
    end_by = t_base + total_s + max(2.0, 3 * brown_deadline_s)
    for t in threads:
        t.join(timeout=max(0.0, end_by - time.monotonic()))
    brownout_view: dict = {}
    cache_view: dict = {}
    try:
        brownout_view = session.get(
            f"http://127.0.0.1:{gw.port}/debug/brownout", timeout=5.0
        ).json()
        cache_view = session.get(
            f"http://127.0.0.1:{gw.port}/debug/cache", timeout=5.0
        ).json()
    except Exception:  # noqa: BLE001 - gates below then fail loudly
        pass
    gw.shutdown()
    server.shutdown()
    img_httpd.shutdown()
    for t in threads:
        t.join(timeout=10.0)

    part2: dict = {"classes": {}}
    for cls, rows in class_results.items():
        offered = {
            "interactive": int(total_s * interactive_rps),
            "batch": int(total_s * batch_rps),
            "best-effort": int(flood_s * besteffort_rps),
        }[cls]
        in_deadline = sum(
            1 for lat, status in rows
            if status == 200 and lat <= brown_deadline_s
        )
        part2["classes"][cls] = {
            "offered": offered,
            "resolved": len(rows),
            "completed_200": sum(1 for _, s in rows if s == 200),
            "shed_429": sum(1 for _, s in rows if s == 429),
            "shed_5xx": sum(1 for _, s in rows if s in (503, 504)),
            "in_deadline": in_deadline,
            "goodput_frac": round(in_deadline / max(offered, 1), 3),
        }
    transitions = brownout_view.get("transitions") or []
    stages = [int(tr.get("to", 0)) for tr in transitions]
    peak_stage = max(stages, default=0)
    seen_down = False
    flap_free = True
    for tr in transitions:
        if int(tr.get("to", 0)) < int(tr.get("from", 0)):
            seen_down = True
        elif seen_down:
            flap_free = False
    burn_final = float(brownout_view.get("burn") or 0.0)
    inter_frac = part2["classes"]["interactive"]["goodput_frac"]
    part2.update({
        "burn_final": round(burn_final, 3),
        "peak_stage": peak_stage,
        "final_stage": int(brownout_view.get("stage") or 0),
        "transitions": transitions,
        "flap_free": flap_free,
        "stale_hits": cache_view.get("stale_hits", 0),
        "brownout": {
            k: brownout_view.get(k)
            for k in ("enabled", "burn_enter", "burn_exit", "dwell_s")
        },
    })
    part2_ok = (
        inter_frac >= 0.95
        and burn_final < 1.0
        and peak_stage >= 3
        and flap_free
    )
    log(
        f"  brownout arm: interactive goodput {inter_frac:.3f}, peak stage "
        f"{peak_stage}, final stage {part2['final_stage']}, final 5m burn "
        f"{burn_final:.3f}, {len(transitions)} transitions "
        f"({'monotone' if flap_free else 'FLAPPED'})"
    )

    ok = part1_ok and part2_ok
    out = {
        "metric": (
            f"tenant isolation + brownout A/B (2 stub tenants, tenant-a at "
            f"{rate_x:g}x capacity; budgets vs shared limiter; then a "
            f"best-effort flood through the real gateway): victim tenant-b "
            f"in-deadline goodput, and the brownout ladder's recovery"
        ),
        "value": b_budget,
        "unit": "tenant-b in-deadline goodput frac (budgets on)",
        "vs_baseline": round(b_budget / max(b_shared, 1e-9), 2),
        "part1_ok": part1_ok,
        "part2_ok": part2_ok,
        "arms": {"budgets": arm_budgets, "shared": arm_shared},
        "brownout_arm": part2,
        "capacity_rps": round(capacity_rps, 1),
        "rate_x": rate_x,
        "seed": seed,
    }
    return out, 0 if ok else 1


def bench_obs_overhead_ab(duration_s=5.0, device_ms=0.0, clients=16,
                          buckets=(1, 2, 4, 8), deadline_ms=2000.0,
                          rounds=2):
    """Observability-overhead A/B: the full layer ON vs OFF, ≤2% tax.

    The always-on observability stack -- span tracing with tail-based
    retention, per-model SLO windows (utils.slo), per-model admission/
    pipeline series, OpenMetrics exemplars -- rides the request hot path,
    so its cost must be proven, not assumed.  Both arms run the REAL
    ModelServer over an instantaneous StubEngine (device_ms=0 by default:
    the tier is host-path-bound, so any observability cost shows at full
    strength instead of hiding under device time) with ``clients``
    closed-loop threads hammering single-image predicts for ``duration_s``.
    The ON arm enables the SLO engine and exemplars and scrapes /metrics +
    /debug/slo once a second (scrape load is part of the layer); the OFF
    arm disables them.  Each arm runs ``rounds`` times interleaved and the
    best round counts (closed-loop HTTP throughput on a shared host is
    noisy; the best round is the arm's honest capability).

    rc=0 iff img/s(on) >= 0.98 x img/s(off) AND the on arm demonstrably
    engaged the layer (exemplars on /metrics, the model on /debug/slo) --
    so the A/B cannot rot into comparing off against off.
    """
    import tempfile
    import threading

    import requests

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving import protocol
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    spec = register_spec(
        ModelSpec(
            name="obs-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    buckets = tuple(sorted(buckets))
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, size=(1, *spec.input_shape), dtype=np.uint8)
    body = protocol.encode_predict_request(img)
    log(
        f"obs-overhead A/B: {clients} closed-loop clients x {duration_s}s "
        f"x {rounds} rounds/arm, stub device {device_ms}ms/batch, "
        f"buckets {buckets}"
    )

    def run_round(obs_on: bool) -> dict:
        root = tempfile.mkdtemp(prefix="kdlt-obs-")
        art.save_artifact(
            art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
        )
        prev_ex = os.environ.get(metrics_lib.EXEMPLARS_ENV)
        os.environ[metrics_lib.EXEMPLARS_ENV] = "1" if obs_on else "0"
        try:
            server = ModelServer(
                root, port=0, buckets=buckets, host="127.0.0.1",
                batcher_impl="python",
                engine_factory=lambda a, **kw: StubEngine(
                    a, device_ms_per_batch=device_ms, async_device=True, **kw
                ),
                admission=True,
                slo=obs_on,
            )
            server.warmup()
            server.start()
            url = f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict"
            base = f"http://127.0.0.1:{server.port}"
            headers = {
                "Content-Type": protocol.MSGPACK_CONTENT_TYPE,
                DEADLINE_HEADER: f"{deadline_ms:.1f}",
            }
            stop_at = time.monotonic() + duration_s
            counts = [0] * clients
            has_exemplars = [False]
            slo_engaged = [False]

            def hammer(i: int) -> None:
                session = requests.Session()
                while time.monotonic() < stop_at:
                    try:
                        r = session.post(
                            url, data=body, headers=headers, timeout=10.0
                        )
                        if r.status_code == 200:
                            counts[i] += 1
                    except Exception:
                        pass

            def scrape() -> None:
                session = requests.Session()
                while time.monotonic() < stop_at:
                    try:
                        page = session.get(f"{base}/metrics", timeout=5.0).text
                        slo = session.get(f"{base}/debug/slo", timeout=5.0).json()
                        if "# {trace_id=" in page:
                            has_exemplars[0] = True
                        if spec.name in (slo.get("models") or {}):
                            slo_engaged[0] = True
                    except Exception:
                        pass
                    time.sleep(1.0)

            threads = [
                threading.Thread(target=hammer, args=(i,), daemon=True)
                for i in range(clients)
            ]
            threads.append(threading.Thread(target=scrape, daemon=True))
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=duration_s + 15.0)
            elapsed = max(time.monotonic() - t0, 1e-9)
            server.shutdown()
            return {
                "img_per_s": round(sum(counts) / elapsed, 1),
                "completed": sum(counts),
                "has_exemplars": has_exemplars[0],
                "slo_engaged": slo_engaged[0],
            }
        finally:
            if prev_ex is None:
                os.environ.pop(metrics_lib.EXEMPLARS_ENV, None)
            else:
                os.environ[metrics_lib.EXEMPLARS_ENV] = prev_ex

    arms: dict[str, list[dict]] = {"on": [], "off": []}
    for _ in range(max(1, int(rounds))):
        for name, flag in (("off", False), ("on", True)):  # interleaved
            r = run_round(flag)
            arms[name].append(r)
            log(
                f"  obs={name:3s}: {r['img_per_s']:8.1f} img/s "
                f"({r['completed']} completed"
                + (
                    f", exemplars={r['has_exemplars']}, "
                    f"slo={r['slo_engaged']})" if name == "on" else ")"
                )
            )
    best_on = max(r["img_per_s"] for r in arms["on"])
    best_off = max(r["img_per_s"] for r in arms["off"])
    engaged = any(
        r["has_exemplars"] and r["slo_engaged"] for r in arms["on"]
    )
    ratio = best_on / max(best_off, 1e-9)
    ok = ratio >= 0.98 and engaged
    out = {
        "metric": (
            f"observability-overhead A/B (stub tier, {clients} closed-loop "
            f"clients, best of {rounds} interleaved rounds/arm): img/s with "
            "the full layer (SLO windows + exemplars + retention) on vs off"
        ),
        "value": round(ratio, 4),
        "unit": "x img/s (observability on / off)",
        "vs_baseline": round(ratio, 4),
        "layer_engaged": engaged,
        "arms": {
            "on": {"best_img_per_s": best_on, "rounds": arms["on"]},
            "off": {"best_img_per_s": best_off, "rounds": arms["off"]},
        },
    }
    return out, 0 if ok else 1


def bench_chaos_ab(duration_s=6.0, device_ms=30.0, deadline_ms=2000.0,
                   rate_rps=24.0, hedge_delay_ms=150.0, probe_interval_s=0.5,
                   kill_at_frac=0.4, seed=0, mode="kill"):
    """Fault-tolerance A/B: hard-kill 1 of 2 model-tier replicas mid-run.

    ``mode="stall"`` is the cross-host LEADER arm (ROADMAP cross-host gap
    #1): instead of killing the victim, its shared dispatcher declares a
    terminal stall (InFlightDispatcher.declare_stall -- exactly what the
    engine watchdog does when a wedged device sync strands the pipeline),
    so the replica keeps answering fast 503s carrying X-Kdlt-Stalled and
    fails its own /healthz.  The gateway must treat that declared stall
    like a replica death -- immediate mark-out + in-request failover --
    so a coalesced flight that dialed the stalled leader fails over
    instead of stranding all its waiters.

    Device-free acceptance harness for the serving-path fault-tolerance
    layer (serving.upstream + serving.faults + the dispatcher watchdog's
    health wiring).  A REAL Gateway fronts TWO stub-backed ModelServer
    replicas via the comma-separated KDLT_SERVING_HOST form; an open-loop
    client fires single-image /predict requests (each fetching a local
    image, each carrying a ``deadline_ms`` budget) at ``rate_rps`` for
    ``duration_s``; at ``kill_at_frac`` of the way through, replica A is
    shut down cold (connects refused from that instant).

    Two arms: failover+hedging ON (per-replica health, breakers, /healthz
    probing every ``probe_interval_s``, hedge after ``hedge_delay_ms``)
    vs OFF (KDLT_FAILOVER=0 semantics: blind round-robin, one attempt,
    failures surface).  With failover on, requests that dial the dead
    replica fail over in-request, so post-kill goodput holds; with it off,
    success collapses toward the single-replica share (~50%).

    Returns (json_dict, rc); rc=0 iff the ON arm keeps >= 95% of post-kill
    requests succeeding in-deadline AND recovers within one probe interval
    (last post-kill failure lands within probe_interval_s + grace of the
    kill) AND the OFF arm demonstrably collapses (< 85%).
    """
    import re
    import tempfile
    import threading
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving import faults as faults_lib
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    class QuietImageHandler(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    spec = register_spec(
        ModelSpec(
            name="chaos-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    deadline_s = deadline_ms / 1e3
    n_requests = int(duration_s * rate_rps)
    kill_after_s = kill_at_frac * duration_s
    rng = np.random.default_rng(seed)
    img_dir = tempfile.mkdtemp(prefix="kdlt-chaos-img-")
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(os.path.join(img_dir, "img.png"))
    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(QuietImageHandler, directory=img_dir)
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    img_url = f"http://127.0.0.1:{img_httpd.server_address[1]}/img.png"
    verb = "killed" if mode == "kill" else "dispatch-stalled"
    log(
        f"chaos A/B ({mode}): 2 stub replicas ({device_ms}ms/batch), "
        f"{rate_rps:g} req/s x {duration_s}s = {n_requests} requests, "
        f"deadline {deadline_ms:.0f}ms, replica A {verb} at "
        f"t+{kill_after_s:.1f}s, hedge {hedge_delay_ms:.0f}ms, probe "
        f"{probe_interval_s:g}s, seed {seed}"
    )

    def start_replica() -> ModelServer:
        root = tempfile.mkdtemp(prefix="kdlt-chaos-")
        art.save_artifact(
            art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
        )
        server = ModelServer(
            root, port=0, buckets=(1, 2), max_delay_ms=1.0, host="127.0.0.1",
            # The stall arm needs the async engine surface: ServedModel
            # then serves through the scheduler's shared
            # InFlightDispatcher, the thing whose stall is being staged.
            engine_factory=lambda a, **kw: StubEngine(
                a, device_ms_per_batch=device_ms,
                async_device=(mode == "stall"), **kw
            ),
        )
        server.warmup()
        server.start()
        return server

    def run_arm(failover_on: bool) -> dict:
        victim, survivor = start_replica(), start_replica()
        gw = Gateway(
            serving_host=f"127.0.0.1:{victim.port},127.0.0.1:{survivor.port}",
            model=spec.name, port=0, host="127.0.0.1",
            failover=failover_on,
            hedge_delay_ms=hedge_delay_ms if failover_on else 0,
            probe_interval_s=probe_interval_s,
            # One repeated URL: the response cache would absorb every
            # request after the first and nothing would touch upstream --
            # this A/B measures the failover path (bench.py --cache-ab
            # owns the cache's own A/B).
            cache=False,
        )
        gw.start()
        gw.spec  # discover the contract before the clock starts
        url = f"http://127.0.0.1:{gw.port}/predict"
        session = requests.Session()
        session.mount("http://", requests.adapters.HTTPAdapter(
            pool_connections=4, pool_maxsize=256,
        ))
        results: list = [None] * n_requests

        def fire(i: int, at: float) -> None:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                r = session.post(
                    url, json={"url": img_url},
                    headers={DEADLINE_HEADER: f"{deadline_ms:.1f}"},
                    timeout=deadline_s + 5.0,
                )
                status = r.status_code
            except Exception:
                status = -1
            # Open-loop latency from the SCHEDULED send time.
            results[i] = (time.monotonic() - at, status)

        t_base = time.monotonic() + 0.25
        kill_at = t_base + kill_after_s
        threads = [
            threading.Thread(
                target=fire, args=(i, t_base + i / rate_rps), daemon=True
            )
            for i in range(n_requests)
        ]
        for t in threads:
            t.start()

        stall_mark: dict = {}

        def kill() -> None:
            delay = kill_at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if mode == "stall":
                # The leader arm: the replica stays up but its dispatch
                # pipeline is declared terminally stalled (the watchdog's
                # own action, invoked directly).  From this instant every
                # predict answers a fast 503 + X-Kdlt-Stalled and
                # /healthz fails, so the prober can never rejoin it.
                stall_mark["pre"] = victim._m_requests.value
                victim.scheduler.dispatcher.declare_stall()
                return
            # Hard-fail the replica: every in-flight/keep-alive predict
            # drops its connection mid-request (deterministic injected
            # disconnect, seeded), and the listener closes so new connects
            # -- including the gateway's /healthz probes -- are refused.
            # Both are needed: shutdown() alone leaves the gateway's pooled
            # keep-alive sockets happily served by their handler threads.
            victim._faults = faults_lib.FaultInjector(
                faults_lib.parse_rules("server.predict:disconnect:1.0"),
                seed=seed,
            )
            victim.shutdown()

        killer = threading.Thread(target=kill, daemon=True)
        killer.start()
        end_by = t_base + duration_s + max(2.0, 2 * deadline_s)
        for t in threads:
            t.join(timeout=max(0.0, end_by - time.monotonic()))
        killer.join(timeout=10.0)
        gw_metrics = gw.registry.render()
        # Stall mode's fix-proving signal: how many requests the gateway
        # kept feeding the wedged replica AFTER the stall was declared.
        # With the mark-out fix one observation suffices; blind
        # round-robin keeps dialing it for its share of the traffic.
        victim_touches = (
            int(victim._m_requests.value - stall_mark["pre"])
            if mode == "stall" and "pre" in stall_mark else None
        )
        gw.shutdown()
        survivor.shutdown()
        if mode == "stall":
            victim.shutdown()  # kill mode shut it down mid-run
        sched = [t_base + i / rate_rps for i in range(n_requests)]
        done = [
            (sched[i], lat, status)
            for i, r in enumerate(results) if r is not None
            for lat, status in [r]
        ]
        ok = [
            (at, lat) for at, lat, status in done
            if status == 200 and lat <= deadline_s
        ]
        post_kill = [(at, lat, status) for at, lat, status in done if at >= kill_at]
        post_ok = [
            (at, lat) for at, lat, status in post_kill
            if status == 200 and lat <= deadline_s
        ]
        post_failures = [
            at for at, lat, status in post_kill
            if not (status == 200 and lat <= deadline_s)
        ]
        # Recovery: how long after the kill failures kept being SCHEDULED.
        recovery_s = (max(post_failures) - kill_at) if post_failures else 0.0

        def metric(name: str) -> float:
            m = re.search(rf"^{name}(?:\{{[^}}]*\}})? (\S+)$", gw_metrics, re.M)
            return float(m.group(1)) if m else 0.0

        arm = {
            "failover": failover_on,
            "requests": n_requests,
            "resolved": len(done),
            "in_deadline_rate": round(len(ok) / max(1, len(done)), 4),
            "post_kill_requests": len(post_kill),
            "post_kill_in_deadline_rate": round(
                len(post_ok) / max(1, len(post_kill)), 4
            ),
            "post_kill_failures": len(post_failures),
            "recovery_s": round(recovery_s, 3),
            "post_kill_victim_requests": victim_touches,
            "failover_total": metric("kdlt_upstream_failover_total"),
            "hedge_fired_total": metric("kdlt_hedge_fired_total"),
            "hedge_won_total": metric("kdlt_hedge_won_total"),
        }
        touched = (
            "" if victim_touches is None
            else f", {victim_touches} requests fed to the stalled replica"
        )
        log(
            f"  failover={'on ' if failover_on else 'off'}: post-kill "
            f"{arm['post_kill_in_deadline_rate'] * 100:5.1f}% in-deadline "
            f"({len(post_ok)}/{len(post_kill)}), recovery {recovery_s:.2f}s, "
            f"{arm['failover_total']:.0f} failovers, "
            f"{arm['hedge_fired_total']:.0f} hedges fired "
            f"({arm['hedge_won_total']:.0f} won){touched}"
        )
        return arm

    try:
        arm_on = run_arm(True)
        arm_off = run_arm(False)
    finally:
        img_httpd.shutdown()
    # Recovery bound: in-request failover means failures should stop almost
    # immediately; one probe interval (+ scheduling grace) is the ceiling.
    recovery_bound_s = probe_interval_s + 0.5
    if mode == "stall":
        # A declared stall answers FAST 503s, so even the blind arm's
        # backoff retry recovers inside a generous deadline -- goodput
        # alone cannot separate the arms.  The fix's signal is traffic
        # placement: the health-aware pool stops feeding the wedged
        # replica after the FIRST X-Kdlt-Stalled observation (<= 3 allows
        # concurrent in-flight observers), while blind round-robin keeps
        # sending it its full share.
        off_share = arm_off["post_kill_victim_requests"] / max(
            1, arm_off["post_kill_requests"]
        )
        ok = (
            arm_on["post_kill_in_deadline_rate"] >= 0.95
            and arm_on["post_kill_victim_requests"] <= 3
            and off_share >= 0.25
        )
    else:
        ok = (
            arm_on["post_kill_in_deadline_rate"] >= 0.95
            and arm_on["recovery_s"] <= recovery_bound_s
            and arm_off["post_kill_in_deadline_rate"] < 0.85
        )
    out = {
        "metric": (
            f"serving-path chaos A/B (2 stub replicas, 1 "
            f"{'hard-killed' if mode == 'kill' else 'dispatch-stalled'} at "
            f"t+{kill_after_s:.1f}s of {duration_s:g}s, {deadline_ms:.0f}ms "
            f"deadline): post-kill in-deadline success with failover+hedging "
            f"on vs off; recovery {arm_on['recovery_s']:.2f}s "
            f"(bound {recovery_bound_s:.2f}s)"
        ),
        "value": round(arm_on["post_kill_in_deadline_rate"], 4),
        "unit": "post-kill in-deadline success rate (failover on)",
        "vs_baseline": round(
            arm_on["post_kill_in_deadline_rate"]
            / max(arm_off["post_kill_in_deadline_rate"], 1e-9),
            2,
        ),
        "mode": mode,
        "deadline_ms": deadline_ms,
        "rate_rps": rate_rps,
        "hedge_delay_ms": hedge_delay_ms,
        "probe_interval_s": probe_interval_s,
        "seed": seed,
        "arms": {"failover_on": arm_on, "failover_off": arm_off},
    }
    return out, 0 if ok else 1


def bench_incident_ab(duration_s=6.0, device_ms=40.0, deadline_ms=1500.0,
                      rate_rps=24.0, seed=0):
    """Incident flight-recorder A/B (GUIDE 10m): flapping failures -> ONE
    bundle each, captured fast, merged at the gateway, and free.

    Three parts, all device-free (stub engines):

    1. STALL ARM -- a real gateway fronts two stub replicas (recorders ON,
       each tier with its own bundle dir); mid-run the victim's dispatcher
       declares a terminal stall (the engine watchdog's own action), then
       the victim is hammered with several more requests, each of which
       records another dispatch.stall event -- a flapping condition.  The
       victim must capture EXACTLY ONE dispatch-stall bundle (dedup window
       eats the re-fires, counted in kdlt_incident_suppressed_total), its
       timeline must be monotonic-ordered, it must pin the causal trace of
       the firing request, and the capture must land in < 2 s.  The
       gateway observes X-Kdlt-Stalled, flips the replica unhealthy, and
       captures its own replica-unhealthy bundle; its /debug/incidents
       must list the victim's bundle (fetchable by id THROUGH the
       gateway) and group the two tiers' captures into one causal window.

    2. BROWNOUT ARM -- a best-effort flood through a real gateway with a
       compressed SLO window + fast dwell makes the brownout ladder climb
       several stages (several brownout.enter events); hysteresis +
       dedup must yield EXACTLY ONE brownout bundle carrying the slo +
       brownout snapshots.

    3. OVERHEAD -- closed-loop throughput against a stub model tier with
       the recorder ON vs OFF (interleaved rounds, best counts): the
       recorder hooks only failure edges, so ON must hold >= 0.98x OFF.

    Returns (json_dict, rc); rc=0 iff all three parts' gates hold.
    """
    import re
    import tempfile
    import threading
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving import protocol
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    class QuietImageHandler(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    spec = register_spec(
        ModelSpec(
            name="incident-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    deadline_s = deadline_ms / 1e3
    rng = np.random.default_rng(seed)
    img_dir = tempfile.mkdtemp(prefix="kdlt-incident-img-")
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(os.path.join(img_dir, "img.png"))
    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(QuietImageHandler, directory=img_dir)
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    img_url = f"http://127.0.0.1:{img_httpd.server_address[1]}/img.png"
    log(
        f"incident A/B: stall + brownout + overhead arms, stub tier "
        f"{device_ms:g}ms/batch, {rate_rps:g} req/s, deadline "
        f"{deadline_ms:.0f}ms, seed {seed}"
    )

    def start_replica(stall_capable=False, incident=True, stub_ms=device_ms):
        root = tempfile.mkdtemp(prefix="kdlt-incident-ms-")
        art.save_artifact(
            art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
        )
        server = ModelServer(
            root, port=0, buckets=(1, 2), max_delay_ms=1.0, host="127.0.0.1",
            engine_factory=lambda a, **kw: StubEngine(
                a, device_ms_per_batch=stub_ms,
                async_device=stall_capable, **kw
            ),
            incident=incident,
            incident_dir=tempfile.mkdtemp(prefix="kdlt-incident-dir-"),
        )
        server.warmup()
        server.start()
        return server

    def metric(rendered: str, name: str, **labels) -> float:
        sel = "".join(
            rf'(?=[^}}]*{k}="{v}")' for k, v in labels.items()
        )
        pat = rf"^{name}\{{{sel}[^}}]*\}} (\S+)$" if labels else (
            rf"^{name}(?:\{{[^}}]*\}})? (\S+)$"
        )
        m = re.search(pat, rendered, re.M)
        return float(m.group(1)) if m else 0.0

    session = requests.Session()
    session.mount("http://", requests.adapters.HTTPAdapter(
        pool_connections=4, pool_maxsize=256,
    ))
    failures: list[str] = []

    def gate(ok: bool, why: str) -> bool:
        if not ok:
            failures.append(why)
        return ok

    # ---- Part 1: the stall arm -------------------------------------------
    victim = start_replica(stall_capable=True)
    survivor = start_replica()
    gw = Gateway(
        serving_host=f"127.0.0.1:{victim.port},127.0.0.1:{survivor.port}",
        model=spec.name, port=0, host="127.0.0.1",
        probe_interval_s=0.3, cache=False,
        incident=True,
        incident_dir=tempfile.mkdtemp(prefix="kdlt-incident-gw-"),
    )
    gw.start()
    gw.spec

    def fire_gw(results: list) -> None:
        try:
            r = session.post(
                f"http://127.0.0.1:{gw.port}/predict",
                json={"url": img_url},
                headers={DEADLINE_HEADER: f"{deadline_ms:.1f}"},
                timeout=deadline_s + 5.0,
            )
            results.append(r.status_code)
        except Exception:
            results.append(-1)

    pre_results: list = []
    n_pre = max(6, int(rate_rps * min(2.0, duration_s / 3.0)))
    pre_threads = [
        threading.Thread(target=fire_gw, args=(pre_results,), daemon=True)
        for _ in range(n_pre)
    ]
    for t in pre_threads:
        t.start()
        time.sleep(1.0 / rate_rps)
    for t in pre_threads:
        t.join(timeout=deadline_s + 10.0)

    # The watchdog's own action, invoked directly: from this instant the
    # victim answers fast 503s carrying X-Kdlt-Stalled and fails /healthz.
    victim.scheduler.dispatcher.declare_stall()
    # Flap it: several more requests hit the stalled dispatcher DIRECTLY,
    # each recording another dispatch.stall event inside the dedup window.
    stall_payload = protocol.encode_predict_request(
        rng.integers(0, 256, size=(1, 32, 32, 3), dtype=np.uint8)
    )
    stall_statuses = []
    for _ in range(5):
        r = session.post(
            f"http://127.0.0.1:{victim.port}/v1/models/{spec.name}:predict",
            data=stall_payload,
            headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE},
            timeout=10.0,
        )
        stall_statuses.append(r.status_code)
    # ... and a few through the gateway, so it observes the stall header
    # and flips the replica unhealthy (its own replica-unhealthy trigger).
    post_results: list = []
    post_threads = [
        threading.Thread(target=fire_gw, args=(post_results,), daemon=True)
        for _ in range(6)
    ]
    for t in post_threads:
        t.start()
        time.sleep(1.0 / rate_rps)
    for t in post_threads:
        t.join(timeout=deadline_s + 10.0)

    victim.recorder.wait_idle(timeout=10.0)
    gw.recorder.wait_idle(timeout=10.0)

    stall_events = [
        e for e in victim.recorder.events() if e["kind"] == "dispatch.stall"
    ]
    stall_bundles = [
        e for e in victim.recorder.index() if e["trigger"] == "dispatch-stall"
    ]
    gate(len(stall_statuses) == 5 and all(s == 503 for s in stall_statuses),
         f"stalled victim answered {stall_statuses}, expected five 503s")
    gate(len(stall_events) >= 2,
         f"only {len(stall_events)} dispatch.stall events; the flap never "
         "flapped")
    gate(len(stall_bundles) == 1,
         f"{len(stall_bundles)} dispatch-stall bundles captured, expected "
         "exactly 1 (dedup)")
    victim_metrics = victim.registry.render()
    suppressed = metric(
        victim_metrics, "kdlt_incident_suppressed_total",
        trigger="dispatch-stall",
    )
    gate(suppressed >= 1,
         f"suppressed counter {suppressed}; dedup left no evidence")
    stall_arm: dict = {
        "stall_events": len(stall_events),
        "bundles": len(stall_bundles),
        "suppressed": suppressed,
    }
    if stall_bundles:
        bundle = victim.recorder.get(stall_bundles[0]["id"])
        mono = [e["m"] for e in bundle["events"]]
        gate(mono == sorted(mono), "stall bundle timeline is out of order")
        fired_rid = (bundle["event"] or {}).get("rid")
        gate(bool(fired_rid) and fired_rid in (bundle.get("traces") or {}),
             f"stall bundle does not pin the causal trace (rid={fired_rid})")
        gate(bundle["capture_latency_s"] < 2.0,
             f"capture latency {bundle['capture_latency_s']}s >= 2s")
        stall_arm.update({
            "id": bundle["id"],
            "events": len(bundle["events"]),
            "traces": sorted((bundle.get("traces") or {}).keys()),
            "capture_latency_s": bundle["capture_latency_s"],
        })
        # The gateway must serve the victim's bundle BY ID (merge path).
        r = session.get(
            f"http://127.0.0.1:{gw.port}/debug/incidents/{bundle['id']}",
            timeout=5.0,
        )
        gate(r.status_code == 200 and r.json().get("id") == bundle["id"],
             f"gateway could not serve the victim's bundle ({r.status_code})")
    merged = session.get(
        f"http://127.0.0.1:{gw.port}/debug/incidents", timeout=5.0
    ).json()
    windows = merged.get("windows") or []
    cross_tier = [
        w for w in windows
        if len(w.get("incidents", [])) >= 2
        and len({i.get("origin") for i in w["incidents"]}) >= 2
    ]
    gate(bool(cross_tier),
         "no merged causal window spans both tiers' captures")
    stall_arm["windows"] = len(windows)
    stall_arm["cross_tier_window"] = bool(cross_tier)
    gw_unhealthy = [
        e for e in gw.recorder.index() if e["trigger"] == "replica-unhealthy"
    ]
    gate(len(gw_unhealthy) >= 1,
         "gateway never captured a replica-unhealthy bundle")
    stall_arm["gateway_bundles"] = len(gw.recorder.index())
    log(
        f"  stall arm: {len(stall_events)} stall events -> "
        f"{len(stall_bundles)} bundle(s), {suppressed:.0f} suppressed, "
        f"capture {stall_arm.get('capture_latency_s', '-')}s, "
        f"{len(windows)} merged window(s) "
        f"(cross-tier={'yes' if cross_tier else 'NO'})"
    )
    gw.shutdown()
    victim.shutdown()
    survivor.shutdown()

    # ---- Part 2: the brownout arm ----------------------------------------
    window_s = 5.0
    flood_deadline_ms = 300.0
    server = start_replica()
    gw2 = Gateway(
        serving_host=f"127.0.0.1:{server.port}", model=spec.name,
        port=0, host="127.0.0.1", cache=False,
        slo_windows=(("5m", window_s),),
        brownout_dwell_s=0.4, brownout_eval_s=0.2,
        incident=True,
        incident_dir=tempfile.mkdtemp(prefix="kdlt-incident-gw2-"),
    )
    gw2.start()
    gw2.spec

    def fire_flood(i: int, at: float) -> None:
        delay = at - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            session.post(
                f"http://127.0.0.1:{gw2.port}/predict",
                json={"url": f"{img_url}?f={i}"},
                headers={
                    DEADLINE_HEADER: f"{flood_deadline_ms:.1f}",
                    protocol.PRIORITY_HEADER: "best-effort",
                },
                timeout=5.0,
            )
        except Exception:
            pass

    flood_s = max(3.0, duration_s / 2.0)
    flood_rps = 10.0 * rate_rps
    t_base = time.monotonic() + 0.25
    flood_threads = [
        threading.Thread(
            target=fire_flood, args=(i, t_base + i / flood_rps), daemon=True
        )
        for i in range(int(flood_s * flood_rps))
    ]
    for t in flood_threads:
        t.start()
    for t in flood_threads:
        t.join(timeout=15.0)
    deadline = time.monotonic() + 3 * window_s
    while time.monotonic() < deadline:
        # Let the ladder exit so the trigger re-arms (hysteresis proof
        # lives in the suppressed counter from the climb's extra enters).
        if gw2.brownout.stage == 0:
            break
        time.sleep(0.3)
    gw2.recorder.wait_idle(timeout=10.0)
    brown_bundles = [
        e for e in gw2.recorder.index() if e["trigger"] == "brownout"
    ]
    brown_events = [
        e for e in gw2.recorder.events() if e["kind"] == "brownout.enter"
    ]
    gate(len(brown_events) >= 1, "brownout never engaged; no enter events")
    gate(len(brown_bundles) == 1,
         f"{len(brown_bundles)} brownout bundles, expected exactly 1 "
         "(hysteresis + dedup)")
    brown_arm: dict = {
        "enter_events": len(brown_events),
        "bundles": len(brown_bundles),
        "peak_stage": max(
            (int(e.get("attrs", {}).get("stage", 0)) for e in brown_events),
            default=0,
        ),
    }
    if brown_bundles:
        bundle = gw2.recorder.get(brown_bundles[0]["id"])
        mono = [e["m"] for e in bundle["events"]]
        gate(mono == sorted(mono), "brownout bundle timeline out of order")
        gate(bundle["capture_latency_s"] < 2.0,
             f"brownout capture latency {bundle['capture_latency_s']}s >= 2s")
        snaps = set((bundle.get("snapshots") or {}).keys())
        gate({"slo", "brownout", "pool"} <= snaps,
             f"brownout bundle snapshots incomplete: {sorted(snaps)}")
        brown_arm.update({
            "id": bundle["id"],
            "capture_latency_s": bundle["capture_latency_s"],
            "snapshots": sorted(snaps),
        })
    log(
        f"  brownout arm: {len(brown_events)} enter event(s), peak stage "
        f"{brown_arm['peak_stage']} -> {len(brown_bundles)} bundle(s), "
        f"capture {brown_arm.get('capture_latency_s', '-')}s"
    )
    gw2.shutdown()
    server.shutdown()

    # ---- Part 3: the overhead arm ----------------------------------------
    # Host-path-bound stub (0 ms device): recorder overhead, if any, shows
    # at full strength.  Interleaved rounds, best counts (steady-state).
    on_server = start_replica(incident=True, stub_ms=0.0)
    off_server = start_replica(incident=False, stub_ms=0.0)
    thr_payload = protocol.encode_predict_request(
        rng.integers(0, 256, size=(1, 32, 32, 3), dtype=np.uint8)
    )

    def throughput(server, seconds=1.2, clients=8) -> float:
        url = f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict"
        stop_at = time.monotonic() + seconds
        counts = [0] * clients

        def worker(slot: int) -> None:
            s = requests.Session()
            while time.monotonic() < stop_at:
                r = s.post(
                    url, data=thr_payload,
                    headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE},
                    timeout=10.0,
                )
                if r.status_code == 200:
                    counts[slot] += 1
        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(clients)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=seconds + 10.0)
        return sum(counts) / (time.monotonic() - t0)

    best_on = best_off = 0.0
    for _ in range(2):
        best_on = max(best_on, throughput(on_server))
        best_off = max(best_off, throughput(off_server))
    ratio = best_on / max(best_off, 1e-9)
    gate(ratio >= 0.98,
         f"recorder-on throughput {ratio:.3f}x recorder-off (< 0.98)")
    log(
        f"  overhead arm: recorder on {best_on:.0f} img/s vs off "
        f"{best_off:.0f} img/s = {ratio:.3f}x (gate >= 0.98)"
    )
    on_server.shutdown()
    off_server.shutdown()
    img_httpd.shutdown()

    for why in failures:
        log(f"  GATE FAILED: {why}")
    out = {
        "metric": (
            "incident flight-recorder A/B (stall flap + brownout flood + "
            "overhead): exactly-one deduped bundle per trigger with ordered "
            "causal timeline, gateway cross-tier merge, capture < 2s, "
            "recorder-on >= 0.98x recorder-off throughput"
        ),
        "value": round(ratio, 4),
        "unit": "recorder-on / recorder-off throughput ratio",
        "vs_baseline": round(ratio, 2),
        "stall_arm": stall_arm,
        "brownout_arm": brown_arm,
        "overhead": {
            "on_img_s": round(best_on, 1),
            "off_img_s": round(best_off, 1),
            "ratio": round(ratio, 4),
        },
        "failures": failures,
        "seed": seed,
    }
    return out, 0 if not failures else 1


def bench_churn_ab(duration_s=10.0, device_ms=40.0, deadline_ms=1000.0,
                   rate_rps=32.0, hedge_delay_ms=400.0, probe_interval_s=0.25,
                   resolve_interval_s=0.35, join_at_frac=0.35,
                   leave_at_frac=0.55, seed=0):
    """Elastic-fleet churn A/B: replicas JOIN and LEAVE mid-run under load.

    The dynamic-membership acceptance harness (serving/upstream.py
    set_membership + quarantine + drain watch, ISSUE 11).  A REAL Gateway
    fronts stub-backed ModelServer replicas whose membership comes from an
    injected resolver (the bench stand-in for re-resolving a headless
    Service name); an open-loop client fires deadline-carrying /predict
    requests at ``rate_rps`` -- sized so TWO replicas hold the load
    comfortably and ONE collapses (~1.5x a single replica's capacity, the
    "2x load" regime relative to the post-leave survivor).  Mid-run, two
    membership events:

    - t+``join_at_frac``: replica C (already warm -- the kdlt-warm story
      makes that the normal case) appears in the resolved view.  It must
      enter via health-probe QUARANTINE and take primaries only after its
      first /readyz 200.
    - t+``leave_at_frac``: replica A is SIGTERM'd (begin_drain: /readyz
      flips, in-flight completes) and simultaneously drops out of the
      resolved view -- the k8s scale-down sequence.  Nothing in flight on
      A may be dropped.

    Baseline arm: the same gateway with a STATIC host list {A, B} (no
    resolver; failover/hedging still on -- membership is the variable
    under test, not failover).  It never learns about C, so after A
    leaves the survivor B carries ~1.5x its capacity and goodput decays;
    the churn arm rides B+C and holds.

    Returns (json_dict, rc); rc=0 iff the churn arm keeps >= 95%
    in-deadline goodput overall (through BOTH membership changes), the
    joiner demonstrably served primaries after quarantine release, ZERO
    requests failed in the leave window, the pool's join/leave counters
    minted, and the churn arm beats the static baseline.
    """
    import re
    import tempfile
    import threading
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    class QuietImageHandler(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    spec = register_spec(
        ModelSpec(
            name="churn-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    deadline_s = deadline_ms / 1e3
    n_requests = int(duration_s * rate_rps)
    join_after_s = join_at_frac * duration_s
    leave_after_s = leave_at_frac * duration_s
    rng = np.random.default_rng(seed)
    img_dir = tempfile.mkdtemp(prefix="kdlt-churn-img-")
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(os.path.join(img_dir, "img.png"))
    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(QuietImageHandler, directory=img_dir)
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    img_url = f"http://127.0.0.1:{img_httpd.server_address[1]}/img.png"
    log(
        f"churn A/B: stub replicas ({device_ms}ms/batch), {rate_rps:g} "
        f"req/s x {duration_s}s = {n_requests} requests, deadline "
        f"{deadline_ms:.0f}ms, C joins at t+{join_after_s:.1f}s, A drains "
        f"out at t+{leave_after_s:.1f}s, resolve {resolve_interval_s:g}s, "
        f"probe {probe_interval_s:g}s, hedge {hedge_delay_ms:.0f}ms"
    )

    def start_replica() -> ModelServer:
        root = tempfile.mkdtemp(prefix="kdlt-churn-")
        art.save_artifact(
            art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
        )
        # Bucket 1 ONLY: with bucket 2 in the ladder a backlogged replica
        # doubles its throughput by batching, and a single survivor absorbs
        # the whole offered load -- the capacity cliff this A/B needs is
        # one request per device_ms.
        server = ModelServer(
            root, port=0, buckets=(1,), max_delay_ms=1.0, host="127.0.0.1",
            engine_factory=lambda a, **kw: StubEngine(
                a, device_ms_per_batch=device_ms, **kw
            ),
        )
        server.warmup()
        server.start()
        return server

    def run_arm(churn: bool) -> dict:
        a, b = start_replica(), start_replica()
        c = start_replica() if churn else None
        host_a, host_b = f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"
        view = [host_a, host_b]  # the resolver's mutable membership view
        gw = Gateway(
            serving_host=f"{host_a},{host_b}",
            model=spec.name, port=0, host="127.0.0.1",
            failover=True,
            hedge_delay_ms=hedge_delay_ms,
            probe_interval_s=probe_interval_s,
            pool_resolve_s=resolve_interval_s if churn else 0,
            # One repeated URL: the response cache would absorb everything
            # after the first request (--cache-ab owns that A/B).
            cache=False,
        )
        if churn:
            # The bench stand-in for DNS: membership IS this list.
            gw.pool.resolver = lambda: list(view)
        gw.start()
        gw.spec  # discover the contract before the clock starts
        url = f"http://127.0.0.1:{gw.port}/predict"
        session = requests.Session()
        session.mount("http://", requests.adapters.HTTPAdapter(
            pool_connections=4, pool_maxsize=256,
        ))
        results: list = [None] * n_requests

        def fire(i: int, at: float) -> None:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                r = session.post(
                    url, json={"url": img_url},
                    headers={DEADLINE_HEADER: f"{deadline_ms:.1f}"},
                    timeout=deadline_s + 5.0,
                )
                status = r.status_code
            except Exception:
                status = -1
            results[i] = (time.monotonic() - at, status)

        t_base = time.monotonic() + 0.25
        join_at = t_base + join_after_s
        leave_at = t_base + leave_after_s
        threads = [
            threading.Thread(
                target=fire, args=(i, t_base + i / rate_rps), daemon=True
            )
            for i in range(n_requests)
        ]
        for t in threads:
            t.start()

        def stage_join() -> None:
            delay = join_at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            view.append(f"127.0.0.1:{c.port}")

        def stage_leave() -> None:
            delay = leave_at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            # The k8s scale-down sequence: SIGTERM (drain begins, /readyz
            # flips -- the drain watch pulls A from new-primary rotation)
            # and the endpoint leaves DNS; the process exits only after
            # in-flight work completes.
            a.begin_drain()
            if churn:
                view.remove(host_a)
            time.sleep(min(1.2, 2 * deadline_s))
            a.shutdown()

        stagers = [threading.Thread(target=stage_leave, daemon=True)]
        if churn:
            stagers.append(threading.Thread(target=stage_join, daemon=True))
        for t in stagers:
            t.start()
        end_by = t_base + duration_s + max(2.0, 2 * deadline_s)
        for t in threads:
            t.join(timeout=max(0.0, end_by - time.monotonic()))
        for t in stagers:
            t.join(timeout=10.0)
        gw_metrics = gw.registry.render()
        pool_debug = gw.pool.debug_payload()
        gw.shutdown()
        b.shutdown()
        if c is not None:
            c.shutdown()

        sched = [t_base + i / rate_rps for i in range(n_requests)]
        done = [
            (sched[i], lat, status)
            for i, r in enumerate(results) if r is not None
            for lat, status in [r]
        ]

        def window_rate(lo: float, hi: float) -> tuple[float, int]:
            """(in-deadline rate, DROPPED count) for requests scheduled in
            [lo, hi).  Dropped = non-200 (connection died, shed, error);
            a late-but-successful response is a goodput miss, not a drop
            -- the zero-drop leave gate is about work, not latency."""
            win = [(lat, st) for at, lat, st in done if lo <= at < hi]
            ok = [1 for lat, st in win if st == 200 and lat <= deadline_s]
            drops = [1 for _, st in win if st != 200]
            return round(len(ok) / max(1, len(win)), 4), len(drops)

        in_deadline = [
            1 for _, lat, st in done if st == 200 and lat <= deadline_s
        ]
        # The leave window: requests scheduled around the drain+departure.
        leave_rate, leave_drops = window_rate(
            leave_at - 0.5, leave_at + 1.5
        )
        join_rate, _ = window_rate(join_at - 0.5, join_at + 1.5)
        post_leave_rate, _ = window_rate(leave_at, t_base + duration_s)

        def metric(name: str) -> float:
            m = re.search(rf"^{name}(?:\{{[^}}]*\}})? (\S+)$", gw_metrics, re.M)
            return float(m.group(1)) if m else 0.0

        joiner_picks = 0
        if c is not None:
            for rep in pool_debug["replicas"]:
                if rep["host"] == f"127.0.0.1:{c.port}":
                    joiner_picks = rep["picks"]
        arm = {
            "churn": churn,
            "requests": n_requests,
            "resolved": len(done),
            "in_deadline_rate": round(
                len(in_deadline) / max(1, len(done)), 4
            ),
            "join_window_in_deadline_rate": join_rate if churn else None,
            "leave_window_in_deadline_rate": leave_rate,
            "leave_window_drops": leave_drops,
            "post_leave_in_deadline_rate": post_leave_rate,
            "members_final": pool_debug["members"],
            "pool_joins_total": metric("kdlt_pool_joins_total"),
            "pool_leaves_total": metric("kdlt_pool_leaves_total"),
            "pool_members_gauge": metric("kdlt_pool_members"),
            "joiner_picks": joiner_picks,
            "failover_total": metric("kdlt_upstream_failover_total"),
            "hedge_fired_total": metric("kdlt_hedge_fired_total"),
        }
        log(
            f"  {'churn   ' if churn else 'baseline'}: "
            f"{arm['in_deadline_rate'] * 100:5.1f}% in-deadline overall, "
            f"leave window {leave_rate * 100:5.1f}% "
            f"({leave_drops} dropped), post-leave "
            f"{post_leave_rate * 100:5.1f}%, members={arm['members_final']}"
            + (
                f", joins={arm['pool_joins_total']:.0f} "
                f"leaves={arm['pool_leaves_total']:.0f} "
                f"joiner_picks={joiner_picks}" if churn else ""
            )
        )
        return arm

    try:
        arm_churn = run_arm(True)
        arm_base = run_arm(False)
    finally:
        img_httpd.shutdown()
    ok = (
        arm_churn["in_deadline_rate"] >= 0.95
        and arm_churn["pool_joins_total"] >= 1
        and arm_churn["pool_leaves_total"] >= 1
        and arm_churn["joiner_picks"] > 0
        and arm_churn["leave_window_drops"] == 0
        and arm_churn["in_deadline_rate"] > arm_base["in_deadline_rate"]
    )
    out = {
        "metric": (
            f"elastic-fleet churn A/B (C joins at t+{join_after_s:.1f}s, A "
            f"drains out at t+{leave_after_s:.1f}s of {duration_s:g}s, "
            f"{deadline_ms:.0f}ms deadline, {rate_rps:g} req/s): in-deadline "
            "goodput with dynamic membership vs a static {A,B} list"
        ),
        "value": round(arm_churn["in_deadline_rate"], 4),
        "unit": "in-deadline success rate (dynamic membership)",
        "vs_baseline": round(
            arm_churn["in_deadline_rate"]
            / max(arm_base["in_deadline_rate"], 1e-9),
            2,
        ),
        "deadline_ms": deadline_ms,
        "rate_rps": rate_rps,
        "hedge_delay_ms": hedge_delay_ms,
        "probe_interval_s": probe_interval_s,
        "resolve_interval_s": resolve_interval_s,
        "seed": seed,
        "arms": {"churn": arm_churn, "static_baseline": arm_base},
    }
    return out, 0 if ok else 1


def bench_quant_ab(reps=3, size=32, buckets=(1, 2), calib_images=8,
                   percentile=None, seed=0, min_size=4096, tol=None):
    """f32 vs int8-weight-only vs int8-w8a8 on the REAL engine path.

    Three InferenceEngines serve the same random-init xception weights at
    ``(size, size, 3)`` input over the same bucket ladder: the float
    artifact, the weight-only quantized one, and the calibrated w8a8 one
    (whose warmup runs the production tolerance gate -- its measured
    drift/top-1 land in the record).  Per bucket the arm reports measured
    img/s, mfu_pct (None off-TPU: the peak table keys on device kind),
    and w8/w8a8 logit drift + top-1 agreement against the f32 engine on a
    seeded golden fixture batch.

    The throughput GATE runs on roofline proxy numbers modeled with v5e
    constants (weight-bytes / HBM bandwidth vs FLOPs / scheme peak, int8
    matmul peak = 2x bf16 -- the MXU's 2x int8 path): XLA:CPU has no
    vectorized s8xs8 conv, so measured CPU img/s for w8a8 is reported
    honestly but cannot stand in for the device.  rc=0 iff the w8a8 arm's
    proxy img/s at the SMALLEST bucket is >= 1.2x the f32 arm's AND
    top-1 agreement >= 0.99 AND relative max-abs drift <= KDLT_QUANT_TOL
    AND the engine's own warmup gate accepted the calibrated artifact.
    """
    from kubernetes_deep_learning_tpu.export.artifact import ModelArtifact
    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.ops import quantize as quant_lib
    from kubernetes_deep_learning_tpu.runtime import InferenceEngine
    from kubernetes_deep_learning_tpu.runtime import flops as flops_lib

    import jax

    if percentile is None:
        percentile = quant_lib.DEFAULT_CALIB_PERCENTILE
    tol = quant_lib.resolve_quant_tol(tol)
    buckets = tuple(sorted(buckets))
    spec = register_spec(
        ModelSpec(
            name="quant-ab",
            family="xception",
            input_shape=(size, size, 3),
            labels=tuple(f"c{i}" for i in range(10)),
            preprocessing="tf",
        )
    )
    log(
        f"quant A/B: xception @{size}x{size}, buckets {buckets}, "
        f"{reps} reps/bucket, calib {calib_images} imgs @p{percentile:g}, "
        f"min_size {min_size}, tol {tol:g}"
    )
    variables = jax.tree_util.tree_map(np.asarray, init_variables(spec, seed=1))
    qvars = quant_lib.quantize_variables(variables, min_size=min_size)
    rng = np.random.default_rng(seed)
    calib = rng.integers(
        0, 256, size=(calib_images, *spec.input_shape), dtype=np.uint8
    )
    scales = quant_lib.calibrate_activation_scales(
        spec, variables, qvars, calib, percentile=percentile
    )
    w8a8_vars = {
        **qvars,
        "params": quant_lib.attach_activation_scales(qvars["params"], scales),
    }
    # float32 compute on every arm: the comparison is quantization noise,
    # not bf16 noise.
    meta = {"compute_dtype": "float32"}
    arms_spec = {
        "f32": ModelArtifact(spec, variables, None, dict(meta)),
        "w8": ModelArtifact(
            spec, qvars, None, {**meta, "quantization": quant_lib.SCHEME}
        ),
        "w8a8": ModelArtifact(
            spec, w8a8_vars, None,
            {**meta, "quantization": quant_lib.SCHEME_W8A8},
        ),
    }

    # Roofline proxy constants (v5e datasheet): the modeled device the
    # CPU run cannot be.
    proxy_bw_gbps = 819.0
    proxy_peak_tflops = flops_lib.PEAK_TFLOPS_BY_KIND["v5e"]["bfloat16"]

    def weight_bytes(tree) -> int:
        total = 0

        def walk(t):
            nonlocal total
            if isinstance(t, dict):
                for v in t.values():
                    walk(v)
            elif hasattr(t, "nbytes"):
                total += int(t.nbytes)

        walk(tree)
        return total

    engines: dict[str, InferenceEngine] = {}
    fixtures = {
        b: rng.integers(0, 256, size=(b, *spec.input_shape), dtype=np.uint8)
        for b in buckets
    }
    results: dict[str, dict] = {}
    golden: dict[str, dict[int, np.ndarray]] = {}
    for name, artifact in arms_spec.items():
        t0 = time.perf_counter()
        eng = InferenceEngine(artifact, buckets=buckets, use_exported=False)
        warm_s = eng.warmup()
        engines[name] = eng
        golden[name] = {
            b: eng.predict(fixtures[b]) for b in buckets
        }
        per_bucket = {}
        flops_img = eng._flops_per_image(buckets[0])
        peak = flops_lib.peak_tflops(eng._device, "float32")
        for b in buckets:
            x = fixtures[b]
            eng.predict(x)  # warm the timing path
            t1 = time.perf_counter()
            for _ in range(reps):
                eng.predict(x)
            dt = (time.perf_counter() - t1) / reps
            img_s = b / dt
            mfu = (
                round(100.0 * img_s * flops_img / (peak * 1e12), 1)
                if peak and flops_img else None
            )
            # Modeled v5e time/batch: weight-bandwidth term vs MXU term
            # (int8 operands run the 2x path; weight-only still feeds the
            # MXU floats, so only w8a8 earns the multiplier).
            wbytes = weight_bytes(arms_spec[name].variables)
            mult = 2.0 if name == "w8a8" else 1.0
            t_model = max(
                wbytes / (proxy_bw_gbps * 1e9),
                (flops_img or 0.0) * b / (proxy_peak_tflops * 1e12 * mult),
            )
            per_bucket[b] = {
                "img_per_s": round(img_s, 2),
                "mfu_pct": mfu,
                "proxy_img_per_s": round(b / t_model, 1) if t_model else None,
                "weight_bytes": wbytes,
            }
        results[name] = {
            "warmup_s": round(warm_s, 2),
            "buckets": per_bucket,
        }
        log(
            f"  {name:<4s}: warmup {warm_s:5.1f}s  "
            + "  ".join(
                f"b{b}: {per_bucket[b]['img_per_s']:8.2f} img/s "
                f"(proxy {per_bucket[b]['proxy_img_per_s']})"
                for b in buckets
            )
        )

    drift_table: dict[str, dict[int, dict]] = {}
    for name in ("w8", "w8a8"):
        drift_table[name] = {}
        for b in buckets:
            a, q = golden["f32"][b], golden[name][b]
            drift = float(np.abs(a - q).max() / (np.abs(a).max() + 1e-9))
            top1 = float((a.argmax(-1) == q.argmax(-1)).mean())
            drift_table[name][b] = {
                "rel_maxabs_drift": round(drift, 4),
                "top1_agreement": round(top1, 4),
            }
    w8a8_eng = engines["w8a8"]
    gate_ok = (
        w8a8_eng.quantization_active == quant_lib.SCHEME_W8A8
        and not w8a8_eng.quant_gate_failed
    )
    b0 = buckets[0]
    # The golden-fixture check aggregates every bucket's fixture rows (the
    # gate bar is over the whole fixture, not the friendliest bucket).
    worst_drift = max(
        drift_table["w8a8"][b]["rel_maxabs_drift"] for b in buckets
    )
    total = sum(buckets)
    agree = sum(
        drift_table["w8a8"][b]["top1_agreement"] * b for b in buckets
    ) / total
    proxy_speedup = (
        results["w8a8"]["buckets"][b0]["proxy_img_per_s"]
        / max(results["f32"]["buckets"][b0]["proxy_img_per_s"], 1e-9)
    )
    measured_speedup = (
        results["w8a8"]["buckets"][b0]["img_per_s"]
        / max(results["f32"]["buckets"][b0]["img_per_s"], 1e-9)
    )
    ok = (
        gate_ok
        and proxy_speedup >= 1.2
        and agree >= quant_lib.GATE_TOP1
        and worst_drift <= tol
    )
    log(
        f"  w8a8 vs f32 @b{b0}: proxy {proxy_speedup:.2f}x, measured "
        f"{measured_speedup:.2f}x ({'no int8 fast path on ' + jax.default_backend() if measured_speedup < 1 else 'real'}), "
        f"top1 {agree:.4f}, worst drift {worst_drift:.4f} (tol {tol:g}), "
        f"gate {'accepted' if gate_ok else 'REFUSED'}"
    )
    out = {
        "metric": (
            f"full-int8 quantization A/B (xception @{size}, buckets "
            f"{list(buckets)}): w8a8 vs f32 img/s on the v5e weight-"
            f"bandwidth/MXU roofline proxy at the smallest bucket "
            f"(measured CPU numbers reported alongside; XLA:CPU has no "
            f"vectorized s8xs8 conv)"
        ),
        "value": round(proxy_speedup, 3),
        "unit": "x proxy img/s (w8a8 / f32, smallest bucket)",
        "vs_baseline": round(proxy_speedup, 3),
        "measured_speedup": round(measured_speedup, 3),
        "top1_agreement": round(agree, 4),
        "worst_rel_maxabs_drift": round(worst_drift, 4),
        "tol": tol,
        "gate_accepted": gate_ok,
        "gate_drift": round(getattr(w8a8_eng, "quant_gate_drift", -1.0), 4),
        "gate_top1": round(getattr(w8a8_eng, "quant_gate_top1", -1.0), 4),
        "calib_images": calib_images,
        "percentile": percentile,
        "min_size": min_size,
        "seed": seed,
        "arms": results,
        "drift": {
            name: {str(b): row for b, row in table.items()}
            for name, table in drift_table.items()
        },
    }
    return out, 0 if ok else 1


def bench_mesh_ab(reps=3, size=96, buckets=(8, 16), arms=(1, 2, 4), seed=0,
                  tol=1e-4, bytes_slack=0.15, floor_frac=0.2):
    """Model-parallel serving A/B on the 2-D named-sharding mesh.

    One InferenceEngine per arm serves the same random-init ViT-S/16
    weights over an 8-virtual-device CPU mesh shaped (8/mp, mp) for mp in
    ``arms``: mp=1 is the replicated (pure data-parallel) baseline, mp>1
    shards the qkv/mlp kernels over the model axis per
    parallel.mesh.PARTITION_RULES.  A transformer family on purpose: its
    params are almost entirely wide dense kernels, so the per-device
    param-byte shrink can actually approach 1/mp (a depthwise-separable
    tower keeps its convs replicated and could never show it).

    Per arm the record carries per-bucket img/s, the per-device resident
    param bytes (parallel.mesh.param_bytes_per_device over the engine's
    sharded tree), the compiled program's own per-device argument bytes
    (jit .lower().compile().memory_analysis() -- XLA's account, not ours),
    and logit drift vs the mp=1 arm on seeded fixtures.

    rc=0 iff every mp>1 arm (a) agrees with the replicated arm within
    ``tol`` relative max-abs drift, (b) shrinks per-device param bytes to
    <= 1/mp + ``bytes_slack``, and (c) holds >= ``floor_frac`` of the
    mp=1 arm's img/s at the two largest buckets (collectives over host
    ICI-stand-in memory are not free; the floor catches a catastrophic
    layout, not a speedup claim), and the kdlt_mesh_* series landed on the
    engine registry.
    """
    # The 8 virtual CPU devices must exist before the first BACKEND
    # INITIALIZATION (the first jax.devices() call), not the first import
    # -- bench.py's own module imports pull jax in transitively, but
    # XLA_FLAGS is read lazily at backend bring-up, so setting it here
    # still works as long as nothing has touched a device yet (--mesh-ab
    # runs INSTEAD of the sweep, so nothing has).  An inherited
    # device-count flag is respected.
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    from kubernetes_deep_learning_tpu.export.artifact import ModelArtifact
    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.parallel import mesh as mesh_lib
    from kubernetes_deep_learning_tpu.runtime import InferenceEngine
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    n_dev = len(jax.devices())
    arms = tuple(
        mp for mp in sorted(set(int(a) for a in arms))
        if mp >= 1 and n_dev % mp == 0
    )
    if len(arms) < 2 or 1 not in arms:
        out = {
            "metric": "mesh model-parallel A/B",
            "error": (
                f"need the mp=1 baseline plus at least one mp>1 arm on "
                f"{n_dev} devices (arms resolved to {list(arms)}; was jax "
                "imported before the device-count flag could be set?)"
            ),
        }
        return out, 1
    buckets = tuple(sorted(set(int(b) for b in buckets)))
    spec = register_spec(
        ModelSpec(
            name="mesh-ab",
            family="vit-s16",
            input_shape=(size, size, 3),
            labels=tuple(f"c{i}" for i in range(10)),
            preprocessing="tf",
        )
    )
    log(
        f"mesh A/B: vit-s16 @{size}x{size} on {n_dev} devices, arms "
        f"mp={list(arms)}, buckets {list(buckets)}, {reps} reps/bucket, "
        f"tol {tol:g}, bytes slack {bytes_slack:g}, floor {floor_frac:g}"
    )
    variables = jax.tree_util.tree_map(np.asarray, init_variables(spec, seed=1))
    rng = np.random.default_rng(seed)
    fixtures = {
        b: rng.integers(0, 256, size=(b, *spec.input_shape), dtype=np.uint8)
        for b in buckets
    }
    # float32 on every arm: the comparison is sharding noise, not bf16 noise.
    meta = {"compute_dtype": "float32"}
    results: dict[str, dict] = {}
    golden: dict[int, dict[int, np.ndarray]] = {}
    metrics_ok = True
    for mp in arms:
        registry = metrics_lib.Registry()
        mesh = mesh_lib.make_mesh(
            n_dev, model_parallel=mp, devices=jax.devices()
        )
        eng = InferenceEngine(
            ModelArtifact(spec, variables, None, dict(meta)),
            buckets=buckets, use_exported=False, mesh=mesh,
            registry=registry, fast=False,
        )
        warm_s = eng.warmup()
        if eng.buckets != buckets:
            # make_mesh grouped (8/mp, mp): every bucket here is a multiple
            # of each arm's data-axis size, so the engine's rounding must be
            # a no-op -- rounded ladders would bench different shapes.
            raise AssertionError(
                f"mp={mp}: engine rounded buckets {buckets} -> {eng.buckets}"
            )
        info = eng.sharding_info()
        golden[mp] = {b: eng.predict(fixtures[b]) for b in buckets}
        # XLA's own per-device account of the compiled program's arguments
        # at the largest bucket (donated batch + resident params).
        compiled_arg_bytes = None
        try:
            ma = (
                eng._jitted.lower(eng._variables, eng._zero_wire(buckets[-1]))
                .compile()
                .memory_analysis()
            )
            compiled_arg_bytes = int(ma.argument_size_in_bytes)
        except Exception as e:  # noqa: BLE001 - reporting extra, not gated
            log(f"  mp={mp}: no compiled memory analysis ({e})")
        per_bucket = {}
        for b in buckets:
            x = fixtures[b]
            eng.predict(x)  # warm the timing path
            t1 = time.perf_counter()
            for _ in range(reps):
                eng.predict(x)
            dt = (time.perf_counter() - t1) / reps
            per_bucket[b] = {"img_per_s": round(b / dt, 2)}
        if "kdlt_mesh_model_parallel" not in registry.render():
            metrics_ok = False
        results[str(mp)] = {
            "mesh_shape": info["mesh_shape"],
            "sharding": info["sharding"],
            "warmup_s": round(warm_s, 2),
            "param_bytes_per_device": info["param_bytes_per_device"],
            "compiled_argument_bytes_per_device": compiled_arg_bytes,
            "buckets": per_bucket,
        }
        log(
            f"  mp={mp}: warmup {warm_s:5.1f}s  params/dev "
            f"{info['param_bytes_per_device'] / 1e6:7.2f} MB  "
            + "  ".join(
                f"b{b}: {per_bucket[b]['img_per_s']:8.2f} img/s"
                for b in buckets
            )
        )

    base = results["1"]
    base_bytes = base["param_bytes_per_device"]
    gate_arms: dict[str, dict] = {}
    ok = metrics_ok
    for mp in arms:
        if mp == 1:
            continue
        arm = results[str(mp)]
        drift = max(
            float(
                np.abs(golden[1][b] - golden[mp][b]).max()
                / (np.abs(golden[1][b]).max() + 1e-9)
            )
            for b in buckets
        )
        bytes_ratio = arm["param_bytes_per_device"] / max(base_bytes, 1)
        floors = {}
        for b in buckets[-2:]:
            ref = base["buckets"][b]["img_per_s"]
            floors[str(b)] = round(
                arm["buckets"][b]["img_per_s"] / max(ref, 1e-9), 3
            )
        arm_ok = (
            drift <= tol
            and bytes_ratio <= 1.0 / mp + bytes_slack
            and all(f >= floor_frac for f in floors.values())
        )
        gate_arms[str(mp)] = {
            "rel_maxabs_drift": round(drift, 7),
            "bytes_ratio": round(bytes_ratio, 4),
            "bytes_bound": round(1.0 / mp + bytes_slack, 4),
            "throughput_frac": floors,
            "ok": arm_ok,
        }
        ok = ok and arm_ok
        log(
            f"  mp={mp} vs mp=1: drift {drift:.2e} (tol {tol:g}), "
            f"bytes {bytes_ratio:.3f}x (bound "
            f"{1.0 / mp + bytes_slack:.3f}), throughput "
            + " ".join(f"b{b}: {f:.2f}x" for b, f in floors.items())
            + f" (floor {floor_frac:g}) -> {'ok' if arm_ok else 'FAIL'}"
        )
    if not metrics_ok:
        log("  kdlt_mesh_* series MISSING from the engine registry")
    biggest = max(a for a in arms if a > 1)
    out = {
        "metric": (
            f"mesh model-parallel A/B (vit-s16 @{size}, {n_dev} devices, "
            f"buckets {list(buckets)}): per-device param bytes and logit "
            f"parity vs the replicated mp=1 arm"
        ),
        "value": gate_arms[str(biggest)]["bytes_ratio"],
        "unit": f"x per-device param bytes (mp={biggest} / mp=1)",
        "vs_baseline": gate_arms[str(biggest)]["bytes_ratio"],
        "tol": tol,
        "bytes_slack": bytes_slack,
        "floor_frac": floor_frac,
        "seed": seed,
        "mesh_metrics_present": metrics_ok,
        "arms": results,
        "gate": gate_arms,
    }
    return out, 0 if ok else 1


def bench_decode_ab(n_requests=16, slots=4, step_ms=15.0, deadline_ms=2500.0,
                    ttft_budget_ms=5000.0, seed=0):
    """Continuous vs static request-boundary batching on the decode lane.

    The generative lane's acceptance gate (GUIDE 10p): one real
    DecodeEngine (paged KV-cache, donated step program) serves both arms;
    the ONLY variable is DecodeScheduler's admission policy.  A closed
    burst of ``n_requests`` generations with mixed prompt lengths (all
    three prefill buckets) and mixed ``max_new_tokens`` is submitted to
    each arm under a per-request deadline:

    - **continuous** (Orca-style): freed decode slots are re-filled from
      the queue at every step, so a short generation retires and hands
      its slot to a queued request mid-batch;
    - **static** (the classic serve-then-swap baseline): admission waits
      for the WHOLE batch to drain, so every wave convoys on its longest
      member and late-wave requests burn their deadline in the queue.

    A fixed per-step sleep (``step_ms``) stands in for a real LLM's step
    time -- the toy model steps in ~0.5 ms on CPU, which would hide the
    scheduling difference the A/B exists to measure; the sleep slows both
    arms identically and leaves the computed tokens untouched.

    rc=0 iff (1) the continuous arm's in-deadline token goodput beats
    static, (2) its TTFT p99 is within ``ttft_budget_ms`` (the lane's
    KDLT_DECODE_TTFT_MS contract), and (3) token streams from the
    shifting continuous batch are BIT-IDENTICAL to the same prompts
    decoded solo on the same engine -- one request per prefill bucket is
    re-decoded alone and compared token-for-token.
    """
    import random
    import threading

    from kubernetes_deep_learning_tpu.runtime import decode as decode_lib
    from kubernetes_deep_learning_tpu.serving.admission.deadline import Deadline

    class SlowedEngine(decode_lib.DecodeEngine):
        # Same compiled programs, same tokens -- plus a fixed sleep so
        # scheduling effects appear at a realistic step granularity.
        def step_async(self):
            if step_ms > 0:
                time.sleep(step_ms / 1e3)
            return super().step_async()

    engine = SlowedEngine("gen-bench", max_slots=slots)
    engine.warmup()

    rng = random.Random(seed)
    prompt_lens = [6, 24, 48]  # one per prefill bucket (16/32/64 with BOS)
    token_budgets = [8, 16, 24, 40]
    requests = []
    for i in range(n_requests):
        n_chars = prompt_lens[i % len(prompt_lens)]
        prompt = "".join(chr(97 + rng.randrange(26)) for _ in range(n_chars))
        requests.append((prompt, token_budgets[i % len(token_budgets)]))

    def run_arm(continuous):
        sched = decode_lib.DecodeScheduler(engine, continuous=continuous)
        sched.start()
        rows = [None] * n_requests
        threads = []

        def drive(i, prompt, mnt):
            t0 = time.perf_counter()
            try:
                gen = sched.submit(
                    prompt, mnt, rid=f"req-{i}",
                    deadline=Deadline(deadline_ms / 1e3),
                )
            except Exception as e:  # noqa: BLE001 - recorded as a lost row
                rows[i] = {"tokens": [], "ttft_ms": None,
                           "finish": f"submit:{e}"}
                return
            tokens = []
            ttft_ms = None
            finish = "?"
            for ev in gen.iter_events(timeout_s=120.0):
                if ev[0] == "token":
                    if not tokens:
                        ttft_ms = (time.perf_counter() - t0) * 1e3
                    tokens.append(ev[2])
                else:
                    finish = ev[1]
            rows[i] = {"tokens": tokens, "ttft_ms": ttft_ms, "finish": finish}

        t0 = time.perf_counter()
        for i, (prompt, mnt) in enumerate(requests):
            t = threading.Thread(target=drive, args=(i, prompt, mnt))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=180.0)
        wall = time.perf_counter() - t0
        sched.close()
        in_deadline = [
            r for r in rows
            if r is not None and r["finish"] in ("stop", "length")
        ]
        ttfts = sorted(
            r["ttft_ms"] for r in rows
            if r is not None and r["ttft_ms"] is not None
        )
        tokens_in_deadline = sum(len(r["tokens"]) for r in in_deadline)
        return rows, {
            "wall_s": round(wall, 3),
            "completed_in_deadline": len(in_deadline),
            "expired": sum(
                1 for r in rows if r is not None and r["finish"] == "deadline"
            ),
            "tokens_in_deadline": tokens_in_deadline,
            "token_goodput_per_s": round(tokens_in_deadline / wall, 1),
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)), 1)
            if ttfts else None,
            "ttft_p99_ms": round(float(np.percentile(ttfts, 99)), 1)
            if ttfts else None,
        }

    log(
        f"decode A/B: {n_requests} generations (prompts {prompt_lens} chars, "
        f"{token_budgets} new tokens, cycled), {slots} slots, "
        f"{step_ms:g} ms/step, deadline {deadline_ms:g} ms per request"
    )
    cont_rows, cont = run_arm(continuous=True)
    static_rows, static = run_arm(continuous=False)
    for name, arm in (("continuous", cont), ("static", static)):
        log(
            f"  {name:<11s}: {arm['tokens_in_deadline']:4d} in-deadline "
            f"tokens in {arm['wall_s']:6.3f}s "
            f"({arm['token_goodput_per_s']:7.1f} tok/s), "
            f"{arm['completed_in_deadline']}/{n_requests} completed, "
            f"{arm['expired']} expired, ttft p99 "
            f"{arm['ttft_p99_ms'] if arm['ttft_p99_ms'] is not None else '-'} ms"
        )

    # Bit-exactness: one continuous-arm stream per prefill bucket, decoded
    # again ALONE on the same engine; every token must match (the same
    # compiled step program serves every batch composition).
    exact = True
    for i in range(min(len(prompt_lens), n_requests)):
        row = cont_rows[i]
        if row is None or row["finish"] not in ("stop", "length"):
            continue
        solo = engine.decode_solo(requests[i][0], requests[i][1])
        if solo[: len(row["tokens"])] != row["tokens"]:
            exact = False
            log(f"  BIT-EXACTNESS FAIL req-{i}: batch={row['tokens'][:8]}... "
                f"solo={solo[:8]}...")
    goodput_ok = (
        cont["tokens_in_deadline"] > static["tokens_in_deadline"]
        or (static["expired"] == 0
            and cont["tokens_in_deadline"] >= static["tokens_in_deadline"])
    )
    ttft_ok = (
        cont["ttft_p99_ms"] is not None
        and cont["ttft_p99_ms"] <= ttft_budget_ms
    )
    ok = goodput_ok and ttft_ok and exact
    log(
        f"  gates: goodput {'ok' if goodput_ok else 'FAIL'} "
        f"(cont {cont['tokens_in_deadline']} vs static "
        f"{static['tokens_in_deadline']} in-deadline tokens), ttft p99 "
        f"{'ok' if ttft_ok else 'FAIL'} (budget {ttft_budget_ms:g} ms), "
        f"bit-exact {'ok' if exact else 'FAIL'}"
    )
    out = {
        "metric": (
            f"decode continuous-batching A/B ({n_requests} mixed-length "
            f"generations, {slots} slots, {step_ms:g} ms/step, deadline "
            f"{deadline_ms:g} ms): in-deadline token goodput, continuous "
            "vs static request-boundary batching"
        ),
        "value": cont["token_goodput_per_s"],
        "unit": "in-deadline tokens/s (continuous arm)",
        "vs_baseline": round(
            cont["tokens_in_deadline"] / max(1, static["tokens_in_deadline"]),
            3,
        ),
        "deadline_ms": deadline_ms,
        "ttft_budget_ms": ttft_budget_ms,
        "step_ms": step_ms,
        "seed": seed,
        "bit_exact_vs_solo": exact,
        "arms": {"continuous": cont, "static": static},
    }
    return out, 0 if ok else 1


def bench_cache_ab(duration_s=6.0, device_ms=50.0, deadline_ms=800.0,
                   rate_rps=60.0, zipf_alpha=1.1, universe=64, probe_n=16,
                   seed=0):
    """Content-addressed cache + singleflight A/B on a Zipf workload.

    A REAL Gateway fronts ONE stub-backed ModelServer replica; an
    open-loop client fires single-image /predict requests for
    ``duration_s`` at ``rate_rps``, with URLs drawn Zipf(``zipf_alpha``)
    over ``universe`` distinct URLs -- every URL serves the same local
    PNG bytes under a distinct query string, so the cache sees distinct
    identities while the model tier's work per miss is identical.  The
    offered load is set ~2x the stub tier's capacity (``device_ms`` per
    batch over buckets (1, 2)), so the cache-off arm sheds: the win the
    cache claims -- goodput under overload -- is the thing measured.

    Two arms on the same seeded schedule: cache+coalescing ON vs OFF
    (the KDLT_CACHE=0 posture).  After the timed arms, two proofs run on
    the ON gateway: a singleflight probe (``probe_n`` identical
    concurrent requests against a fresh URL must produce EXACTLY ONE
    upstream dispatch) and a miss-parity check (a fresh URL's response
    through the ON arm must be bit-identical to the OFF arm's for the
    same URL -- the cache must never perturb the miss path).

    Returns (json_dict, rc); rc=0 iff hit_ratio >= 0.5 AND on-arm
    in-deadline goodput strictly beats off-arm AND the singleflight probe
    counted exactly 1 upstream dispatch AND miss-path responses are
    bit-identical.
    """
    import tempfile
    import threading
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    class QuietImageHandler(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    spec = register_spec(
        ModelSpec(
            name="cache-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    deadline_s = deadline_ms / 1e3
    n_requests = int(duration_s * rate_rps)
    rng = np.random.default_rng(seed)
    # Zipf(alpha) over exactly `universe` ranks (np.random's zipf samples
    # an unbounded tail; serving workloads have a finite catalog).
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    pmf = ranks ** (-zipf_alpha)
    pmf /= pmf.sum()
    url_ranks = rng.choice(universe, size=n_requests, p=pmf)
    img_dir = tempfile.mkdtemp(prefix="kdlt-cache-img-")
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(os.path.join(img_dir, "img.png"))
    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(QuietImageHandler, directory=img_dir)
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    base_url = f"http://127.0.0.1:{img_httpd.server_address[1]}/img.png"
    log(
        f"cache A/B: Zipf(alpha={zipf_alpha:g}) over {universe} urls, "
        f"{rate_rps:g} req/s x {duration_s}s = {n_requests} requests, "
        f"stub tier {device_ms}ms/batch (buckets 1-2), deadline "
        f"{deadline_ms:.0f}ms, seed {seed}"
    )

    def start_stack(cache_on: bool) -> tuple:
        root = tempfile.mkdtemp(prefix="kdlt-cache-")
        art.save_artifact(
            art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
        )
        server = ModelServer(
            root, port=0, buckets=(1, 2), max_delay_ms=1.0, host="127.0.0.1",
            engine_factory=lambda a, **kw: StubEngine(
                a, device_ms_per_batch=device_ms, **kw
            ),
        )
        server.warmup()
        server.start()
        gw = Gateway(
            serving_host=f"127.0.0.1:{server.port}", model=spec.name,
            port=0, host="127.0.0.1", cache=cache_on,
        )
        gw.start()
        gw.spec  # discover the contract before the clock starts
        return server, gw

    def run_arm(cache_on: bool) -> tuple[dict, object, object]:
        server, gw = start_stack(cache_on)
        url = f"http://127.0.0.1:{gw.port}/predict"
        session = requests.Session()
        session.mount("http://", requests.adapters.HTTPAdapter(
            pool_connections=4, pool_maxsize=256,
        ))
        results: list = [None] * n_requests

        def fire(i: int, at: float) -> None:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                r = session.post(
                    url,
                    json={"url": f"{base_url}?u={int(url_ranks[i])}"},
                    headers={DEADLINE_HEADER: f"{deadline_ms:.1f}"},
                    timeout=deadline_s + 5.0,
                )
                status = r.status_code
            except Exception:
                status = -1
            # Open-loop latency from the SCHEDULED send time.
            results[i] = (time.monotonic() - at, status)

        t_base = time.monotonic() + 0.25
        threads = [
            threading.Thread(
                target=fire, args=(i, t_base + i / rate_rps), daemon=True
            )
            for i in range(n_requests)
        ]
        for t in threads:
            t.start()
        end_by = t_base + duration_s + max(2.0, 2 * deadline_s)
        for t in threads:
            t.join(timeout=max(0.0, end_by - time.monotonic()))
        done = [r for r in results if r is not None]
        ok = [lat for lat, status in done if status == 200 and lat <= deadline_s]
        cache_stats = requests.get(
            f"http://127.0.0.1:{gw.port}/debug/cache", timeout=5
        ).json()
        arm = {
            "cache": cache_on,
            "requests": n_requests,
            "resolved": len(done),
            "in_deadline": len(ok),
            "goodput_rps": round(len(ok) / duration_s, 2),
            "in_deadline_rate": round(len(ok) / max(1, len(done)), 4),
            "p50_ms": round(
                float(np.median(ok)) * 1e3, 1
            ) if ok else None,
            "hit_ratio": cache_stats.get("hit_ratio", 0.0),
            "hits": cache_stats.get("hits", 0),
            "misses": cache_stats.get("misses", 0),
            "coalesced": cache_stats.get("coalesced", 0),
        }
        log(
            f"  cache={'on ' if cache_on else 'off'}: goodput "
            f"{arm['goodput_rps']:6.1f} req/s in-deadline "
            f"({arm['in_deadline']}/{len(done)}), hit_ratio "
            f"{arm['hit_ratio']:.3f}, {arm['coalesced']} coalesced"
        )
        return arm, server, gw

    def parity_scores(gw_port: int, tag: str) -> dict:
        r = requests.post(
            f"http://127.0.0.1:{gw_port}/predict",
            json={"url": f"{base_url}?{tag}"},
            timeout=30.0,
        )
        r.raise_for_status()
        return r.json()

    try:
        arm_on, server_on, gw_on = run_arm(True)
        arm_off, server_off, gw_off = run_arm(False)
        # Singleflight proof on the ON stack: N identical concurrent
        # requests against a never-seen URL -> exactly 1 upstream dispatch
        # (the stub tier's request counter is the ground truth).
        probe_url = f"{base_url}?probe=1"
        before = server_on._m_requests.value
        barrier = threading.Barrier(probe_n)

        def probe() -> None:
            barrier.wait()
            try:
                requests.post(
                    f"http://127.0.0.1:{gw_on.port}/predict",
                    json={"url": probe_url}, timeout=30.0,
                )
            except Exception:  # noqa: BLE001 - the dispatch count is the proof
                pass

        probes = [
            threading.Thread(target=probe, daemon=True) for _ in range(probe_n)
        ]
        for t in probes:
            t.start()
        for t in probes:
            t.join(timeout=30.0)
        upstream_dispatches = int(server_on._m_requests.value - before)
        # Miss-parity proof: a fresh URL through the ON gateway (a cache
        # miss) must produce byte-identical scores to the OFF gateway.
        on_scores = parity_scores(gw_on.port, "parity=1")
        off_scores = parity_scores(gw_off.port, "parity=1")
        miss_bit_identical = json.dumps(on_scores, sort_keys=True) == (
            json.dumps(off_scores, sort_keys=True)
        )
        gw_on.shutdown()
        server_on.shutdown()
        gw_off.shutdown()
        server_off.shutdown()
    finally:
        img_httpd.shutdown()
    log(
        f"  singleflight probe: {probe_n} identical concurrent requests -> "
        f"{upstream_dispatches} upstream dispatch(es); miss parity "
        f"{'bit-identical' if miss_bit_identical else 'DIVERGED'}"
    )
    ok = (
        arm_on["hit_ratio"] >= 0.5
        and arm_on["goodput_rps"] > arm_off["goodput_rps"]
        and upstream_dispatches == 1
        and miss_bit_identical
    )
    out = {
        "metric": (
            f"gateway cache+singleflight A/B (Zipf alpha={zipf_alpha:g} "
            f"over {universe} urls at {rate_rps:g} req/s, stub tier "
            f"{device_ms:.0f}ms/batch, {deadline_ms:.0f}ms deadline): "
            f"in-deadline goodput with the cache on vs off"
        ),
        "value": arm_on["goodput_rps"],
        "unit": "in-deadline goodput req/s (cache on)",
        "vs_baseline": round(
            arm_on["goodput_rps"] / max(arm_off["goodput_rps"], 1e-9), 2
        ),
        "hit_ratio": arm_on["hit_ratio"],
        "singleflight_upstream_dispatches": upstream_dispatches,
        "singleflight_probe_n": probe_n,
        "miss_bit_identical": miss_bit_identical,
        "zipf_alpha": zipf_alpha,
        "universe": universe,
        "rate_rps": rate_rps,
        "deadline_ms": deadline_ms,
        "seed": seed,
        "arms": {"cache_on": arm_on, "cache_off": arm_off},
    }
    return out, 0 if ok else 1


def bench_ingest_ab(n_images=200, source_px=768, input_px=64, clients=8,
                    seed=0):
    """Raw-bytes ingest wire A/B: decode at the model tier vs the gateway.

    A REAL Gateway fronts ONE stub-backed ModelServer; ``clients``
    closed-loop threads drive ``n_images`` single-image ``apply_model``
    calls over a catalog of distinct smooth-gradient JPEGs
    (``source_px``^2 source, ``input_px``^2 model input: a small file
    whose decode cost is proportional to source pixels -- the workload
    the bytes wire is for).  Two arms on the same seeded schedule:

    - bytes wire: KDLT_INGEST negotiated on both tiers; the gateway
      forwards fetched bytes verbatim and the model tier decodes.
    - tensor wire: ingest off on both tiers (the old posture); the
      gateway decodes + preprocesses and ships the uint8 tensor.

    The decoded-uint8 cache is forced OFF on both tiers for the run
    (KDLT_CACHE_DECODED_MB=0) so the A/B measures the distinct-content
    steady state, not cache hits.  Gateway-tier CPU is isolated with
    per-thread ``time.thread_time()`` around the ``apply_model`` loop
    (the model tier's decode pool runs in other threads and is excluded
    -- that is the point: the work MOVED).  Wire bytes are counted by
    wrapping the gateway's single upstream POST seam.

    Returns (json_dict, rc); rc=0 iff no request errored in either arm
    AND (bytes-arm img/s >= 1.3x tensor arm OR gateway CPU/image >= 2x
    lower) AND bytes-arm wire bytes/image <= 1.2x the mean encoded blob
    size AND per-image scores are identical across wires AND the bytes
    arm really used the bytes wire (zero fallbacks).
    """
    import itertools
    import tempfile
    import threading
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving import cache as cache_lib
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    class QuietImageHandler(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    spec = register_spec(
        ModelSpec(
            name="ingest-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(input_px, input_px, 3),
            labels=("a", "b", "c"),
        )
    )
    rng = np.random.default_rng(seed)
    universe = min(32, n_images)
    img_dir = tempfile.mkdtemp(prefix="kdlt-ingest-img-")
    yy, xx = np.mgrid[0:source_px, 0:source_px]
    for k in range(universe):
        # Smooth phase-shifted gradients: distinct content per file (the
        # decoded cache is content-addressed), small JPEG, full-cost
        # decode.  Noise would also decode slowly but bloats the file,
        # which is the opposite of the workload this wire targets.
        ph = 2.0 * np.pi * k / universe
        img = np.stack([
            127.5 + 127.5 * np.sin(xx / 41.0 + ph),
            127.5 + 127.5 * np.sin(yy / 53.0 + 2.0 * ph),
            127.5 + 127.5 * np.sin((xx + yy) / 67.0 + 3.0 * ph),
        ], axis=-1).astype(np.uint8)
        Image.fromarray(img).save(
            os.path.join(img_dir, f"img{k}.jpg"), quality=85
        )
    blob_sizes = [
        os.path.getsize(os.path.join(img_dir, f"img{k}.jpg"))
        for k in range(universe)
    ]
    mean_blob = float(np.mean(blob_sizes))
    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(QuietImageHandler, directory=img_dir)
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    urls = [
        f"http://127.0.0.1:{img_httpd.server_address[1]}/img{k}.jpg"
        for k in range(universe)
    ]
    order = rng.integers(0, universe, size=n_images)
    log(
        f"ingest A/B: {n_images} images over {universe} distinct "
        f"{source_px}x{source_px} JPEGs (mean {mean_blob / 1024:.1f} KiB) "
        f"-> {input_px}x{input_px} input, {clients} client threads, "
        f"decoded cache off, seed {seed}"
    )

    def run_arm(bytes_wire: bool) -> tuple[dict, dict]:
        root = tempfile.mkdtemp(prefix="kdlt-ingest-")
        art.save_artifact(
            art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
        )
        server = ModelServer(
            root, port=0, buckets=(1, 2), max_delay_ms=1.0, host="127.0.0.1",
            ingest=bytes_wire,
            engine_factory=lambda a, **kw: StubEngine(a, **kw),
        )
        server.warmup()
        server.start()
        gw = Gateway(
            serving_host=f"127.0.0.1:{server.port}", model=spec.name,
            port=0, host="127.0.0.1", cache=False, ingest=bytes_wire,
        )
        gw.start()
        gw.spec  # negotiate the contract (and ingest caps) off the clock
        wire = {"bytes": 0, "posts": 0}
        orig_post = gw._post_once

        def counting_post(replica, body, *a, **kw):
            wire["bytes"] += len(body)
            wire["posts"] += 1
            return orig_post(replica, body, *a, **kw)

        gw._post_once = counting_post
        idx = itertools.count()
        cpu = [0.0] * clients
        done = [0] * clients
        errors = [0] * clients

        def worker(w: int) -> None:
            t0 = time.thread_time()
            while True:
                i = next(idx)
                if i >= n_images:
                    break
                try:
                    gw.apply_model(urls[int(order[i])])
                    done[w] += 1
                except Exception:  # noqa: BLE001 - the error count is the gate
                    errors[w] += 1
            cpu[w] = time.thread_time() - t0

        t_start = time.monotonic()
        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        wall = time.monotonic() - t_start
        n_done = sum(done)
        # Per-image score parity probes (off the clock, still counted in
        # the wire tally -- per-post averaging keeps that fair).
        scores = {}
        for k in range(universe):
            try:
                scores[k] = gw.apply_model(urls[k])
            except Exception:  # noqa: BLE001
                errors[0] += 1
        m = gw._m_ingest
        arm = {
            "wire": "bytes" if bytes_wire else "tensor",
            "images": n_done,
            "errors": sum(errors),
            "wall_s": round(wall, 3),
            "img_per_s": round(n_done / max(wall, 1e-9), 1),
            "gateway_cpu_ms_per_img": round(
                sum(cpu) * 1e3 / max(n_done, 1), 3
            ),
            "wire_bytes_per_img": round(wire["bytes"] / max(wire["posts"], 1)),
            "bytes_requests": int(m["bytes_requests"].value),
            "fallbacks": {
                reason: int(c.value) for reason, c in m["fallbacks"].items()
            },
        }
        gw.shutdown()
        server.shutdown()
        log(
            f"  wire={arm['wire']:6s}: {arm['img_per_s']:7.1f} img/s, "
            f"gateway CPU {arm['gateway_cpu_ms_per_img']:6.2f} ms/img, "
            f"{arm['wire_bytes_per_img']} wire B/img, "
            f"{arm['errors']} errors"
        )
        return arm, scores

    # The decoded-uint8 cache is a separate win with its own tests; force
    # it off on BOTH tiers so the arms compare decode placement, not
    # cache hits (every request would otherwise hit after round one).
    saved_mb = os.environ.get(cache_lib.DECODED_MB_ENV)
    os.environ[cache_lib.DECODED_MB_ENV] = "0"
    try:
        arm_bytes, scores_bytes = run_arm(True)
        arm_tensor, scores_tensor = run_arm(False)
    finally:
        if saved_mb is None:
            os.environ.pop(cache_lib.DECODED_MB_ENV, None)
        else:
            os.environ[cache_lib.DECODED_MB_ENV] = saved_mb
        img_httpd.shutdown()
    parity = all(
        json.dumps(scores_bytes.get(k), sort_keys=True)
        == json.dumps(scores_tensor.get(k), sort_keys=True)
        for k in range(universe)
    )
    speedup = arm_bytes["img_per_s"] / max(arm_tensor["img_per_s"], 1e-9)
    cpu_ratio = arm_tensor["gateway_cpu_ms_per_img"] / max(
        arm_bytes["gateway_cpu_ms_per_img"], 1e-9
    )
    wire_ratio = arm_bytes["wire_bytes_per_img"] / max(mean_blob, 1e-9)
    used_bytes_wire = (
        arm_bytes["bytes_requests"] > 0
        and sum(arm_bytes["fallbacks"].values()) == 0
    )
    log(
        f"  speedup {speedup:.2f}x img/s, gateway CPU ratio "
        f"{cpu_ratio:.2f}x, wire {wire_ratio:.2f}x encoded blob, parity "
        f"{'identical' if parity else 'DIVERGED'}"
    )
    ok = (
        arm_bytes["errors"] == 0
        and arm_tensor["errors"] == 0
        and (speedup >= 1.3 or cpu_ratio >= 2.0)
        and wire_ratio <= 1.2
        and parity
        and used_bytes_wire
    )
    out = {
        "metric": (
            f"raw-bytes ingest wire A/B ({source_px}x{source_px} JPEG -> "
            f"{input_px}x{input_px} input, {clients} clients, decoded "
            f"cache off): decode at the model tier vs the gateway"
        ),
        "value": round(cpu_ratio, 2),
        "unit": "x lower gateway CPU per image (bytes wire)",
        "speedup_img_per_s": round(speedup, 2),
        "cpu_ratio": round(cpu_ratio, 2),
        "wire_ratio_vs_encoded": round(wire_ratio, 3),
        "mean_encoded_blob_bytes": round(mean_blob),
        "parity_identical": parity,
        "used_bytes_wire": used_bytes_wire,
        "n_images": n_images,
        "universe": universe,
        "clients": clients,
        "seed": seed,
        "arms": {"bytes": arm_bytes, "tensor": arm_tensor},
    }
    return out, 0 if ok else 1


def bench_trace_breakdown(n_requests=30, device_ms=60.0, deadline_ms=5000.0,
                          max_delay_ms=1.0):
    """Span-trace latency attribution on a stub serving stack.

    A REAL gateway fronts a stub-backed ModelServer (async stub device:
    the in-flight dispatch pipeline and its four stage spans engage); a
    sequential client sends traced /predict requests and, for each, pulls
    the merged cross-tier waterfall from the gateway's /debug/trace/<rid>.
    Per-stage p50/p99 come from the span durations; **coverage** is the
    fraction of each request's measured wall time attributed to named
    spans (the gateway root span over the client-observed latency).

    Returns (json_dict, rc); rc=0 iff mean coverage >= 0.95 AND every
    request's waterfall has >= 8 spans -- the tracing layer's acceptance
    bar: if the spans cannot account for where a stub request's time
    went, they will not account for a real one's either.
    """
    import tempfile
    import threading
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer
    from kubernetes_deep_learning_tpu.serving.tracing import REQUEST_ID_HEADER

    class QuietImageHandler(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    spec = register_spec(
        ModelSpec(
            name="trace-stub",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    rng = np.random.default_rng(0)
    img_dir = tempfile.mkdtemp(prefix="kdlt-trace-img-")
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(os.path.join(img_dir, "img.png"))
    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(QuietImageHandler, directory=img_dir)
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    img_url = f"http://127.0.0.1:{img_httpd.server_address[1]}/img.png"

    root = tempfile.mkdtemp(prefix="kdlt-trace-bd-")
    art.save_artifact(
        art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
    )
    server = ModelServer(
        root, port=0, buckets=(1, 2), max_delay_ms=max_delay_ms,
        host="127.0.0.1", batcher_impl="python",
        engine_factory=lambda a, **kw: StubEngine(
            a, device_ms_per_batch=device_ms, async_device=True, **kw
        ),
    )
    server.warmup()
    server.start()
    gateway = Gateway(
        serving_host=f"127.0.0.1:{server.port}", model=spec.name, port=0,
        host="127.0.0.1",
        # Repeated URLs: with the cache on every request after the first
        # would be a 2-span cache hit; this mode attributes the FULL
        # gateway->model-tier path.
        cache=False,
    )
    gateway.start()
    log(
        f"trace breakdown: stub stack ({device_ms}ms device/batch), "
        f"{n_requests} sequential traced requests"
    )
    session = requests.Session()
    base = f"http://127.0.0.1:{gateway.port}"
    # One untimed warmup request: spec discovery, connection setup, and the
    # stub's first dispatch are one-time costs, not steady-state breakdown.
    session.post(base + "/predict", json={"url": img_url}, timeout=30)

    stage_ms: dict[str, list[float]] = {}
    coverage: list[float] = []
    span_counts: list[int] = []
    try:
        for i in range(n_requests):
            rid = f"trace-bd-{i}"
            t0 = time.monotonic()
            r = session.post(
                base + "/predict", json={"url": img_url},
                headers={
                    REQUEST_ID_HEADER: rid,
                    DEADLINE_HEADER: f"{deadline_ms:.1f}",
                },
                timeout=30,
            )
            wall_s = time.monotonic() - t0
            r.raise_for_status()
            tr = session.get(base + f"/debug/trace/{rid}", timeout=5)
            tr.raise_for_status()
            spans = tr.json()["spans"]
            span_counts.append(len(spans))
            root_dur_ms = 0.0
            for s in spans:
                stage_ms.setdefault(s["name"], []).append(s["dur_ms"])
                if s["name"] == "gateway.request":
                    root_dur_ms = s["dur_ms"]
            coverage.append(min(1.0, root_dur_ms / 1e3 / max(wall_s, 1e-9)))
    finally:
        gateway.shutdown()
        server.shutdown()
        img_httpd.shutdown()

    stages = {
        name: {
            "p50_ms": round(float(np.percentile(durs, 50)), 2),
            "p99_ms": round(float(np.percentile(durs, 99)), 2),
            "n": len(durs),
        }
        for name, durs in sorted(stage_ms.items())
    }
    mean_cov = float(np.mean(coverage)) if coverage else 0.0
    min_spans = min(span_counts) if span_counts else 0
    for name, st in stages.items():
        log(f"  {name:<24s} p50 {st['p50_ms']:8.2f} ms  p99 {st['p99_ms']:8.2f} ms")
    log(
        f"  coverage: mean {mean_cov:.3f} of client wall attributed to "
        f"named spans; min spans/request {min_spans}"
    )
    ok = mean_cov >= 0.95 and min_spans >= 8
    out = {
        "metric": (
            "span-trace breakdown (stub stack): fraction of client-"
            "measured request wall time attributed to named spans; "
            "per-stage p50/p99 from the merged waterfall"
        ),
        "value": round(mean_cov, 4),
        "unit": "fraction of wall time attributed",
        "requests": n_requests,
        "device_ms": device_ms,
        "min_spans_per_request": min_spans,
        "stages": stages,
    }
    return out, 0 if ok else 1


def bench_host_saturation(duration_s, clients, batch_sizes, batcher_impl,
                          max_delay_ms, stub_device_ms=0.0):
    """Can the HTTP + protocol + batcher host path carry the target WITHOUT
    the device?  (VERDICT r1: the device bench alone doesn't prove the stack
    sustains >=4000 img/s.)

    Serves a StubEngine (runtime.stub: checksum logits, zero device time)
    behind the REAL ModelServer and measures loopback throughput with
    keep-alive http.client workers at several request batch sizes, plus
    no-HTTP microbenches (protocol codec alone; batcher alone) so the cost
    attribution is explicit.  Results are per-CPU-core costs: this box has
    one core shared by clients and server, so the img/s numbers here are a
    LOWER bound on a production pod.
    """
    import http.client
    import os
    import tempfile
    import threading

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine, stub_logits
    from kubernetes_deep_learning_tpu.serving import protocol
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    spec = get_spec("clothing-model")
    rng = np.random.default_rng(0)

    # --- microbench 1: protocol codec alone (per request) ------------------
    img1 = rng.integers(0, 256, size=(1, *spec.input_shape), dtype=np.uint8)
    body1 = protocol.encode_predict_request(img1)
    logits1 = stub_logits(img1, spec.num_classes)
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        images = protocol.decode_predict_request(body1, protocol.MSGPACK_CONTENT_TYPE)
        protocol.encode_predict_response(logits1, spec.labels, protocol.MSGPACK_CONTENT_TYPE)
    codec_us = (time.perf_counter() - t0) / n * 1e6
    log(f"host-path codec (decode+encode, batch 1): {codec_us:.0f} us/request")

    # --- microbench 2: batcher + stub engine, no HTTP ----------------------
    root = tempfile.mkdtemp(prefix="kdlt-hostsat-")
    art.save_artifact(
        art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
    )
    # stub_device_ms > 0 makes the stub a SERIAL async device at that
    # latency per batch (runtime.stub async_device) -- e.g. 3.3 ms is the
    # real chip's measured batch-16 p50, so the host path is proven against
    # the device cadence it must actually feed (VERDICT r4 #4), rather
    # than against an infinitely fast device.
    if stub_device_ms > 0:
        def make_engine(artifact, **kw):
            return StubEngine(
                artifact, device_ms_per_batch=stub_device_ms,
                async_device=True, **kw,
            )
    else:
        make_engine = StubEngine
    server = ModelServer(
        root, port=0, buckets=(1, 2, 4, 8, 16, 32, 64, 128),
        max_delay_ms=max_delay_ms, batcher_impl=batcher_impl,
        host="127.0.0.1", engine_factory=make_engine,
    )
    server.warmup()
    model = server.models[spec.name]
    stop = threading.Event()
    counts = [0] * clients

    def batcher_worker(i):
        img = rng.integers(0, 256, size=(*spec.input_shape,), dtype=np.uint8)
        while not stop.is_set():
            model.batcher.predict(img)
            counts[i] += 1

    threads = [
        threading.Thread(target=batcher_worker, args=(i,)) for i in range(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join()
    batcher_rps = sum(counts) / (time.perf_counter() - t0)
    log(
        f"host-path batcher+stub (no HTTP, {clients} threads): "
        f"{batcher_rps:.0f} img/s ({1e6 / max(batcher_rps, 1):.0f} us/img)"
    )

    # --- full loopback HTTP sweep ------------------------------------------
    server.start()
    url_path = f"/v1/models/{spec.name}:predict"
    results = {}
    for b in batch_sizes:
        imgs = rng.integers(0, 256, size=(b, *spec.input_shape), dtype=np.uint8)
        body = protocol.encode_predict_request(imgs)
        lat: list[float] = []
        errors = [0]
        lock = threading.Lock()
        stop = threading.Event()

        def client(body=body):
            conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
            local = []
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    conn.request(
                        "POST", url_path, body,
                        {"Content-Type": protocol.MSGPACK_CONTENT_TYPE},
                    )
                    r = conn.getresponse()
                    r.read()
                    ok = r.status == 200
                except Exception:
                    ok = False
                    conn.close()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", server.port, timeout=60
                    )
                if ok:
                    local.append(time.perf_counter() - t0)
                else:
                    with lock:
                        errors[0] += 1
            conn.close()
            with lock:
                lat.extend(local)

        threads = [threading.Thread(target=client) for _ in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        a = np.array(sorted(lat))
        if a.size == 0:
            log(f"req-batch {b:4d}: NO successful requests ({errors[0]} errors)")
            continue
        rps = a.size / elapsed
        results[b] = {
            "req_per_s": round(rps, 1),
            "img_per_s": round(rps * b, 1),
            "p50_ms": round(float(np.percentile(a, 50)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 2),
            "errors": errors[0],
        }
        log(
            f"req-batch {b:4d}: {rps:7.1f} req/s = {rps * b:9.1f} img/s  "
            f"p50 {results[b]['p50_ms']:6.2f} ms  p99 {results[b]['p99_ms']:7.2f} ms"
            f"  ({errors[0]} errors)"
        )
    server.shutdown()

    best = max(results, key=lambda b: results[b]["img_per_s"]) if results else None
    out = {
        "metric": (
            "host-path images/sec (HTTP+protocol+batcher with stub engine, "
            f"{clients} loopback clients on {os.cpu_count()} CPU core(s); "
            "best request-batch "
            f"{best}; codec {codec_us:.0f}us/req; batcher-only {batcher_rps:.0f} img/s)"
        ),
        "value": results[best]["img_per_s"] if best else 0.0,
        "unit": "images/sec",
        "vs_baseline": round((results[best]["img_per_s"] if best else 0) / TARGET_IMG_S, 3),
        "sweep": results,
    }
    print(json.dumps(out), flush=True)
    return out


def _setup_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at a repo-local dir.

    VERDICT r4 weak-1(b): every per-point bench subprocess re-paid a
    20-55 s XLA compile because no cache was configured anywhere.  The
    parent and each child call this; the directory follows the shared
    contract in utils.compilecache ($JAX_COMPILATION_CACHE_DIR, else
    $KDLT_COMPILE_CACHE_DIR, else <checkout>/.jax_cache), so every
    process of a run -- and the next run -- compiles against the same one.
    It sets a jax config value only; it never initialises a backend, so
    the parent stays off the chip.  Disable with
    KDLT_COMPILE_CACHE_DIR=off.
    """
    from kubernetes_deep_learning_tpu.utils.compilecache import enable_compile_cache

    path = enable_compile_cache()
    log(f"persistent compile cache: {path or 'off'}")
    return path


def _fake_child_row(batch: int) -> dict:
    """Synthetic per-point row for the sweep-robustness tests ONLY
    (KDLT_BENCH_FAKE_CHILD=1): exercises the parent's isolation, budget,
    incremental-emission, and SIGTERM paths without touching jax or a
    device.  Values follow a plausible saturation curve
    so headline selection logic is exercised too.
    """
    time.sleep(float(os.environ.get("KDLT_BENCH_FAKE_CHILD_SLEEP_S", "0")))
    per_img_us = 200.0 / (1.0 + batch / 12.0) + 3.0  # saturating device
    img_s = 1e6 / per_img_us
    p50 = batch * per_img_us / 1e3
    return {
        "img_per_s": img_s,
        "scan_img_per_s": img_s,
        "pipelined_img_per_s": img_s * 1.02,
        "serial_img_per_s": img_s * 0.85,
        "pipeline_speedup": 1.2,
        "trace_img_per_s": img_s * 1.05,
        "method_agreement": 0.98,
        "headline_methods": "scan/pipelined",
        "p50_ms": p50,
        "trace_p50_ms": p50 * 0.95,
        "p99_ms": p50 * 1.1,
        "p99_source": "device-trace-span",
        "best_ms": p50 * 0.9,
        "worst_ms": p50 * 1.2,
        "compile_s": 0.0,
        "mfu_pct": None,
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="clothing-model",
                   help="ModelSpec name to bench (see modelspec.list_specs)")
    # Same point set as BASELINE.json's 1..128 sweep (+48/56 bracketing the
    # p50<=15ms bound on v5e, 256 probing the unbound ceiling), but ordered
    # HEADLINE-FIRST: the round-4 official run (rc=124) spent its whole
    # budget compiling batches 1..8 in ascending order and timed out before
    # the record-bearing batch-16 point's JSON could land.  With this order
    # plus incremental emission, the in-bound >=4000 img/s headline is on
    # stdout within the first ~2 points; everything after refines the sweep.
    p.add_argument("--batches", default="16,32,8,64,48,56,4,2,1,128,256")
    p.add_argument("--scan-len", type=int, default=0,
                   help="fwd passes per timed chained-scan call (0 = auto-size "
                        "per batch to amortize dispatch RTT); the pipelined "
                        "method's burst is always capped at 200 dispatches")
    p.add_argument("--reps", type=int, default=5, help="timed calls per batch size")
    p.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    p.add_argument(
        # Measured indistinguishable from float32 at batch>=32 on v5e (the
        # conv weights are cast once and cached); bfloat16 mainly halves the
        # artifact, so the serving default stays float32 for logit parity.
        "--params-dtype", default="float32", choices=["bfloat16", "float32"]
    )
    p.add_argument(
        "--serving", type=float, default=0,
        help="INSTEAD of the sweep: run the e2e serving bench for this many "
             "seconds (0 = off).  The server runs in this process, which then "
             "holds the chip, so no sweep child is started afterwards",
    )
    p.add_argument(
        "--host-saturation", type=float, default=0,
        help="INSTEAD of the device bench: saturate the HTTP+batcher host "
        "path with a stub engine for this many seconds per batch size",
    )
    p.add_argument(
        "--stub-device-ms", type=float, default=0.0,
        help="host-saturation only: simulate a SERIAL async device at this "
             "many ms per batch (0 = instantaneous stub); 3.3 is the real "
             "chip's measured batch-16 p50",
    )
    p.add_argument(
        "--request-batches", default="1,4,16,64,256",
        help="host-saturation request batch sizes",
    )
    p.add_argument("--clients", type=int, default=32, help="serving-bench client threads")
    p.add_argument(
        "--batcher", default="auto", choices=["auto", "native", "python"],
        help="serving-bench batching queue implementation",
    )
    p.add_argument("--max-delay-ms", type=float, default=2.0)
    p.add_argument(
        "--peak-tflops", type=float, default=0.0,
        help="device peak TFLOP/s for MFU (0 = auto-detect from device kind)",
    )
    p.add_argument(
        "--batcher-sweep", type=float, default=0,
        help="seconds per point: C++ vs Python batcher at simulated device "
             "latencies (--device-ms list), no real device needed",
    )
    p.add_argument(
        "--pipeline-ab", type=int, default=0,
        help="INSTEAD of the sweep: drive this many stub batches through "
             "the in-flight dispatcher at each --pipeline-ab-depths depth "
             "and report wall-clock vs the device-execute-only bound "
             "(serial-vs-pipelined A/B, no device needed; rc=0 iff the "
             "deepest depth lands within 5% of the bound)",
    )
    p.add_argument(
        "--pipeline-ab-depths", default="1,2",
        help="comma-separated in-flight depths for --pipeline-ab",
    )
    p.add_argument(
        "--pipeline-ab-batch", type=int, default=16,
        help="images per stub batch for --pipeline-ab",
    )
    p.add_argument(
        "--pipeline-ab-host-ms", type=float, default=3.0,
        help="simulated host gather+H2D ms per batch for --pipeline-ab",
    )
    p.add_argument(
        "--pipeline-ab-device-ms", type=float, default=10.0,
        help="simulated device execute ms per batch for --pipeline-ab "
             "(keep well above time.sleep jitter or the jitter itself "
             "reads as a pipeline gap)",
    )
    p.add_argument(
        "--overload-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: admission-control A/B -- drive a "
             "stub-backed model tier at --overload-rate-x times its known "
             "capacity for this many seconds per arm (admission on vs off) "
             "and report goodput (in-deadline completions/s) plus "
             "in-deadline p99 (no device needed; rc=0 iff admission wins "
             "on both)",
    )
    p.add_argument(
        "--overload-device-ms", type=float, default=100.0,
        help="simulated device ms per batch for --overload-ab (sets the "
             "tier's capacity: max-bucket / device-ms)",
    )
    p.add_argument(
        "--overload-deadline-ms", type=float, default=600.0,
        help="per-request deadline budget for --overload-ab",
    )
    p.add_argument(
        "--overload-rate-x", type=float, default=2.0,
        help="offered load as a multiple of the stub tier's capacity",
    )
    p.add_argument(
        "--overload-buckets", default="1,2",
        help="bucket ladder for the --overload-ab stub tier",
    )
    p.add_argument(
        "--crosshost-ab", type=int, default=0, metavar="ROUNDS",
        help="INSTEAD of the sweep: pipelined vs lockstep cross-host "
             "dispatch A/B on a real multi-process CPU fleet "
             "(utils.distributed; no device needed) -- drive this many "
             "rounds per arm and report img/s + p50 per arm (rc=0 iff the "
             "pipelined arm's throughput is >= 1.15x lockstep with "
             "bit-identical logits and depth 1 reproduces lockstep)",
    )
    p.add_argument(
        "--crosshost-ab-batch", type=int, default=32,
        help="images per round for --crosshost-ab",
    )
    p.add_argument(
        "--crosshost-ab-host-ms", type=float, default=0.0,
        help="simulated per-round host assembly ms for --crosshost-ab "
             "(0 = calibrate to the measured round time)",
    )
    p.add_argument(
        "--crosshost-ab-processes", type=int, default=2,
        help="fleet size for --crosshost-ab (>= 2 for a real cross-host path)",
    )
    p.add_argument(
        "--crosshost-ab-depths", default="1,2",
        help="comma-separated in-flight round budgets for --crosshost-ab",
    )
    p.add_argument(
        "--multimodel-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: multi-model scheduling A/B -- two stub "
             "models share one UnifiedScheduler + dispatcher; a heavy model "
             "overloaded at --mm-rate-x with a generous deadline, a light "
             "model with a tight deadline; weighted_deadline vs fifo "
             "arbitration for this many seconds per arm (no device needed; "
             "rc=0 iff the weighted arm wins worst-model in-deadline "
             "goodput by >= 1.2x without degrading the heavy model)",
    )
    p.add_argument(
        "--mm-heavy-device-ms", type=float, default=120.0,
        help="simulated device ms per heavy-model batch for --multimodel-ab",
    )
    p.add_argument(
        "--mm-light-device-ms", type=float, default=5.0,
        help="simulated device ms per light-model batch for --multimodel-ab",
    )
    p.add_argument(
        "--mm-heavy-deadline-ms", type=float, default=2000.0,
        help="heavy-model per-request deadline for --multimodel-ab",
    )
    p.add_argument(
        "--mm-light-deadline-ms", type=float, default=300.0,
        help="light-model per-request deadline for --multimodel-ab",
    )
    p.add_argument(
        "--mm-rate-x", type=float, default=2.0,
        help="heavy-model offered load as a multiple of its capacity",
    )
    p.add_argument(
        "--mm-light-rps", type=float, default=40.0,
        help="light-model offered request rate for --multimodel-ab",
    )
    p.add_argument(
        "--tenant-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: tenant isolation + brownout acceptance "
             "-- part 1 drives two stub tenants on one model tier (tenant-a "
             "at --tenant-rate-x times capacity) for this many seconds per "
             "arm, per-model admission budgets vs the legacy shared "
             "limiter; part 2 floods a real gateway with best-effort "
             "traffic and proves the SLO-burn brownout ladder climbs, "
             "sheds, recovers, and never flaps (no device needed; rc=0 iff "
             "tenant-b holds >=95% in-deadline goodput under budgets while "
             "the shared baseline collapses, AND the brownout arm ends "
             "with 5m burn < 1.0, interactive goodput >= 95%, peak stage "
             ">= 3, zero flaps)",
    )
    p.add_argument(
        "--tenant-device-ms", type=float, default=50.0,
        help="simulated device ms per batch for the --tenant-ab stub tier "
             "(sets capacity: max-bucket / device-ms)",
    )
    p.add_argument(
        "--tenant-deadline-ms", type=float, default=1500.0,
        help="per-request deadline budget for --tenant-ab part 1",
    )
    p.add_argument(
        "--tenant-rate-x", type=float, default=3.0,
        help="tenant-a offered load as a multiple of the tier's capacity",
    )
    p.add_argument(
        "--tenant-b-rps", type=float, default=12.0,
        help="victim tenant-b offered rate for --tenant-ab (must be "
             "comfortably under capacity)",
    )
    p.add_argument(
        "--tenant-flood-s", type=float, default=6.0,
        help="--tenant-ab part 2 best-effort flood duration",
    )
    p.add_argument(
        "--tenant-seed", type=int, default=0,
        help="deterministic seed for the --tenant-ab fixtures",
    )
    p.add_argument(
        "--quant-ab", type=int, default=0, metavar="REPS",
        help="INSTEAD of the sweep: full-int8 quantization A/B -- f32 vs "
             "int8-weight-only vs calibrated int8-w8a8 InferenceEngines on "
             "the same weights, reporting per-bucket img/s, mfu_pct, and "
             "logit drift/top-1 vs f32, this many timed reps per bucket.  "
             "The throughput gate runs on the v5e roofline proxy (XLA:CPU "
             "has no s8xs8 fast path); rc=0 iff w8a8 proxy img/s >= 1.2x "
             "f32 at the smallest bucket AND top-1 >= 0.99 AND drift <= "
             "KDLT_QUANT_TOL AND the engine's warmup tolerance gate "
             "accepted the calibrated artifact",
    )
    p.add_argument(
        "--quant-size", type=int, default=32,
        help="square input size for --quant-ab (small keeps the CPU int8 "
             "reference lowering tractable; kernel shapes -- the weight "
             "bytes that drive the roofline -- are size-independent)",
    )
    p.add_argument(
        "--quant-buckets", default="1,2",
        help="bucket ladder for --quant-ab",
    )
    p.add_argument(
        "--quant-calib-images", type=int, default=8,
        help="calibration images for the --quant-ab w8a8 arm",
    )
    p.add_argument(
        "--quant-percentile", type=float, default=0.0,
        help="calibration percentile clip (0 = the ops.quantize default)",
    )
    p.add_argument(
        "--quant-min-size", type=int, default=4096,
        help="min kernel elements to quantize (raise on CPU to confine "
             "the slow int8 reference lowering to the biggest matmuls)",
    )
    p.add_argument(
        "--quant-tol", type=float, default=0.0,
        help="relative max-abs logit drift bound (0 = $KDLT_QUANT_TOL or "
             "the default)",
    )
    p.add_argument(
        "--quant-seed", type=int, default=0,
        help="seed for --quant-ab fixtures and calibration stream",
    )
    p.add_argument(
        "--mesh-ab", type=int, default=0, metavar="REPS",
        help="INSTEAD of the sweep: model-parallel mesh serving A/B -- one "
             "InferenceEngine per mp arm on an 8-virtual-device CPU mesh "
             "shaped (8/mp, mp), vit-s16 weights shared across arms, this "
             "many timed reps per bucket.  rc=0 iff every mp>1 arm matches "
             "the replicated mp=1 arm's logits within --mesh-tol, shrinks "
             "per-device param bytes to <= 1/mp + --mesh-bytes-slack, "
             "holds >= --mesh-floor of mp=1 img/s at the two largest "
             "buckets, and the kdlt_mesh_* series landed on the registry",
    )
    p.add_argument(
        "--mesh-size", type=int, default=96,
        help="square input size for --mesh-ab (must be a multiple of the "
             "ViT patch size 16)",
    )
    p.add_argument(
        "--mesh-buckets", default="8,16",
        help="bucket ladder for --mesh-ab (each entry must be a multiple "
             "of every arm's data-axis size so all arms bench the same "
             "shapes)",
    )
    p.add_argument(
        "--mesh-arms", default="1,2,4",
        help="model-parallel degrees for --mesh-ab (must include 1, the "
             "replicated baseline; each must divide the device count)",
    )
    p.add_argument(
        "--mesh-tol", type=float, default=1e-4,
        help="relative max-abs logit drift bound vs the mp=1 arm for "
             "--mesh-ab (sharded matmuls reassociate float sums; measured "
             "drift on CPU f32 is ~1e-6)",
    )
    p.add_argument(
        "--mesh-bytes-slack", type=float, default=0.15,
        help="additive slack on the 1/mp per-device param-byte bound for "
             "--mesh-ab (embeddings/layernorms/biases stay replicated)",
    )
    p.add_argument(
        "--mesh-floor", type=float, default=0.2,
        help="min fraction of the mp=1 arm's img/s an mp>1 arm must hold "
             "at the two largest buckets for --mesh-ab (catastrophic-"
             "layout catch, not a speedup claim: virtual CPU devices "
             "share one memory bus)",
    )
    p.add_argument(
        "--mesh-seed", type=int, default=0,
        help="seed for the --mesh-ab fixtures",
    )
    p.add_argument(
        "--chaos-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: serving-path fault-tolerance A/B -- "
             "front two stub model-tier replicas with the real gateway, "
             "hard-kill one mid-run, and report post-kill in-deadline "
             "success + recovery time with failover+hedging on vs off "
             "(no device needed; rc=0 iff the on arm holds >=95% and "
             "recovers within one probe interval while the off arm "
             "collapses toward the single-replica share)",
    )
    p.add_argument(
        "--chaos-device-ms", type=float, default=30.0,
        help="simulated device ms per batch for the --chaos-ab stub replicas",
    )
    p.add_argument(
        "--chaos-deadline-ms", type=float, default=2000.0,
        help="per-request deadline budget for --chaos-ab",
    )
    p.add_argument(
        "--chaos-rate-rps", type=float, default=24.0,
        help="offered request rate for --chaos-ab",
    )
    p.add_argument(
        "--chaos-hedge-ms", type=float, default=150.0,
        help="hedge delay for the --chaos-ab failover-on arm",
    )
    p.add_argument(
        "--chaos-probe-s", type=float, default=0.5,
        help="replica /healthz probe interval for --chaos-ab",
    )
    p.add_argument(
        "--chaos-seed", type=int, default=0,
        help="deterministic seed for the --chaos-ab request schedule",
    )
    p.add_argument(
        "--chaos-mode", default="kill", choices=["kill", "stall"],
        help="--chaos-ab failure mode: 'kill' hard-kills the victim "
             "replica (listener closed, connections dropped); 'stall' is "
             "the cross-host LEADER arm -- the victim's dispatch pipeline "
             "declares a terminal stall (watchdog semantics), so it keeps "
             "answering fast X-Kdlt-Stalled 503s and the gateway must "
             "mark it out on the FIRST observation",
    )
    p.add_argument(
        "--incident-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: incident flight-recorder acceptance "
             "(GUIDE 10m) -- a stall arm (flapping dispatch-stall on a "
             "stub replica behind the real gateway), a brownout arm "
             "(best-effort flood climbs the ladder), and an overhead arm "
             "(recorder on vs off closed-loop throughput); rc=0 iff each "
             "flapping trigger yields EXACTLY ONE deduped bundle with a "
             "monotonic causal timeline (the stall bundle pinning the "
             "firing request's trace), captures land < 2s, the gateway "
             "merges both tiers' bundles into one causal window, and "
             "recorder-on holds >= 0.98x recorder-off img/s",
    )
    p.add_argument(
        "--incident-device-ms", type=float, default=40.0,
        help="simulated device ms per batch for the --incident-ab stub "
             "tiers (the overhead arm always uses 0)",
    )
    p.add_argument(
        "--incident-deadline-ms", type=float, default=1500.0,
        help="per-request deadline budget for the --incident-ab stall arm",
    )
    p.add_argument(
        "--incident-rate-rps", type=float, default=24.0,
        help="offered request rate for --incident-ab (the brownout flood "
             "runs at 10x this)",
    )
    p.add_argument(
        "--incident-seed", type=int, default=0,
        help="deterministic seed for the --incident-ab fixtures",
    )
    p.add_argument(
        "--churn-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: elastic-fleet churn A/B -- front stub "
             "model-tier replicas with the real gateway under dynamic "
             "membership (injected resolver), have a warm replica JOIN "
             "mid-run (quarantine until its first /readyz 200) and "
             "another DRAIN OUT (SIGTERM + DNS departure), vs a static "
             "host-list baseline that never learns about either (no "
             "device needed; rc=0 iff the churn arm holds >=95% "
             "in-deadline goodput through both membership changes, the "
             "joiner served primaries, zero requests failed in the leave "
             "window, and it beats the baseline)",
    )
    p.add_argument(
        "--churn-device-ms", type=float, default=40.0,
        help="simulated device ms per batch for the --churn-ab stub "
             "replicas (sets per-replica capacity; the offered rate "
             "should overload ONE replica but not two)",
    )
    p.add_argument(
        "--churn-deadline-ms", type=float, default=1000.0,
        help="per-request deadline budget for --churn-ab",
    )
    p.add_argument(
        "--churn-rate-rps", type=float, default=32.0,
        help="offered request rate for --churn-ab (~1.5x one replica's "
             "capacity at the default device-ms)",
    )
    p.add_argument(
        "--churn-hedge-ms", type=float, default=400.0,
        help="hedge delay for --churn-ab (both arms)",
    )
    p.add_argument(
        "--churn-probe-s", type=float, default=0.25,
        help="replica probe interval for --churn-ab (quarantine release "
             "and drain-watch latency are bounded by this)",
    )
    p.add_argument(
        "--churn-resolve-s", type=float, default=0.35,
        help="membership re-resolve interval for the --churn-ab churn arm "
             "(the KDLT_POOL_RESOLVE_S knob)",
    )
    p.add_argument(
        "--churn-seed", type=int, default=0,
        help="deterministic seed for the --churn-ab request schedule",
    )
    p.add_argument(
        "--cache-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: gateway cache+singleflight A/B -- "
             "drive a real gateway + stub model tier with a Zipf-"
             "distributed URL workload at ~2x capacity for this many "
             "seconds per arm (cache on vs KDLT_CACHE=0 off; no device "
             "needed; rc=0 iff hit_ratio >= 0.5, the on arm wins "
             "in-deadline goodput, N identical concurrent requests "
             "produce exactly 1 upstream dispatch, and miss-path "
             "responses are bit-identical to cache-off)",
    )
    p.add_argument(
        "--cache-device-ms", type=float, default=50.0,
        help="simulated device ms per batch for the --cache-ab stub tier "
             "(sets capacity; the offered rate should overload it)",
    )
    p.add_argument(
        "--cache-deadline-ms", type=float, default=800.0,
        help="per-request deadline budget for --cache-ab",
    )
    p.add_argument(
        "--cache-rate-rps", type=float, default=60.0,
        help="offered request rate for --cache-ab",
    )
    p.add_argument(
        "--cache-zipf-alpha", type=float, default=1.1,
        help="Zipf exponent of the --cache-ab URL popularity distribution",
    )
    p.add_argument(
        "--cache-universe", type=int, default=64,
        help="distinct URLs in the --cache-ab workload",
    )
    p.add_argument(
        "--cache-probe-n", type=int, default=16,
        help="identical concurrent requests for the --cache-ab "
             "singleflight proof (must produce exactly 1 upstream dispatch)",
    )
    p.add_argument(
        "--cache-seed", type=int, default=0,
        help="deterministic seed for the --cache-ab URL schedule",
    )
    p.add_argument(
        "--ingest-ab", type=int, default=0, metavar="IMAGES",
        help="INSTEAD of the sweep: raw-bytes ingest wire A/B -- drive "
             "this many single-image requests through a real gateway + "
             "stub model tier with the bytes wire (model-tier decode) vs "
             "the legacy tensor wire (gateway decode), decoded cache off "
             "on both tiers (no device needed; rc=0 iff the bytes arm "
             "wins >=1.3x img/s OR >=2x lower gateway CPU/image, its "
             "wire bytes/image stay <=1.2x the encoded blob, scores are "
             "identical across wires, and zero fallbacks fired)",
    )
    p.add_argument(
        "--ingest-size", type=int, default=768,
        help="source JPEG edge (pixels) for --ingest-ab; decode cost "
             "scales with this, file size barely does (smooth gradients)",
    )
    p.add_argument(
        "--ingest-input", type=int, default=64,
        help="model input edge (pixels) for --ingest-ab",
    )
    p.add_argument(
        "--ingest-clients", type=int, default=8,
        help="closed-loop client threads for --ingest-ab",
    )
    p.add_argument(
        "--ingest-seed", type=int, default=0,
        help="deterministic seed for the --ingest-ab image schedule",
    )
    p.add_argument(
        "--decode-ab", type=int, default=0, metavar="REQUESTS",
        help="INSTEAD of the sweep: generative-lane continuous-batching "
             "A/B -- drive this many mixed-prompt-length generations "
             "through one real DecodeEngine (paged KV-cache) under "
             "continuous (token-boundary slot-fill) vs static "
             "(request-boundary) admission with per-request deadlines "
             "(rc=0 iff continuous wins in-deadline token goodput, its "
             "TTFT p99 lands within the lane's budget, and continuous-"
             "batch token streams are bit-identical to solo decode)",
    )
    p.add_argument(
        "--decode-slots", type=int, default=4,
        help="decode batch slots (fixed step width) for --decode-ab",
    )
    p.add_argument(
        "--decode-step-ms", type=float, default=15.0,
        help="injected per-step sleep for --decode-ab (stands in for a "
             "real LLM's step time; the toy model steps in ~0.5 ms, which "
             "would hide the scheduling difference under measurement)",
    )
    p.add_argument(
        "--decode-deadline-ms", type=float, default=2500.0,
        help="per-generation deadline budget for --decode-ab",
    )
    p.add_argument(
        "--decode-ttft-budget-ms", type=float, default=5000.0,
        help="TTFT p99 gate for the --decode-ab continuous arm (the "
             "KDLT_DECODE_TTFT_MS contract)",
    )
    p.add_argument(
        "--decode-seed", type=int, default=0,
        help="deterministic seed for the --decode-ab prompt fixtures",
    )
    p.add_argument(
        "--trace-breakdown", type=int, default=0, metavar="N",
        help="INSTEAD of the sweep: send N traced requests through a stub "
             "gateway->model-server stack and attribute each request's "
             "wall time to named spans from /debug/trace/<rid> (per-stage "
             "p50/p99 + coverage; rc=0 iff >=95%% of wall time is "
             "attributed and every waterfall has >=8 spans)",
    )
    p.add_argument(
        "--trace-device-ms", type=float, default=60.0,
        help="simulated device ms per batch for --trace-breakdown",
    )
    p.add_argument(
        "--obs-overhead-ab", type=float, default=0, metavar="SECONDS",
        help="INSTEAD of the sweep: observability-overhead A/B -- hammer a "
             "stub-backed model tier with closed-loop clients for this many "
             "seconds per round, with the full observability layer (SLO "
             "windows + exemplars + tail retention) on vs off (no device "
             "needed; rc=0 iff the on arm holds >= 98%% of the off arm's "
             "img/s and the layer demonstrably engaged)",
    )
    p.add_argument(
        "--obs-clients", type=int, default=16,
        help="closed-loop client threads for --obs-overhead-ab",
    )
    p.add_argument(
        "--obs-device-ms", type=float, default=0.0,
        help="simulated device ms per batch for --obs-overhead-ab (0 = "
             "instantaneous stub: host-path-bound, overhead shows at full "
             "strength)",
    )
    p.add_argument(
        "--obs-rounds", type=int, default=2,
        help="interleaved rounds per arm for --obs-overhead-ab (best counts)",
    )
    p.add_argument(
        "--dry-run", action="store_true",
        help="parse arguments, echo the resolved run configuration as one "
             "JSON line, and exit 0 -- a CI smoke so bench refactors can "
             "never break the driver's exact invocation",
    )
    p.add_argument(
        "--device-ms", default="0.5,1,2,5,10",
        help="simulated device ms/batch for --batcher-sweep",
    )
    p.add_argument(
        "--no-isolate", action="store_true",
        help="run the whole forward sweep in THIS process instead of one "
             "subprocess per batch point (faster on CPU; a device fault then "
             "kills the whole sweep, see run_isolated_sweep)",
    )
    p.add_argument(
        "--point-timeout", type=float, default=1200.0,
        help="per-batch-point subprocess timeout (seconds); a hung point is "
             "recorded as a fault and the sweep continues",
    )
    p.add_argument(
        "--budget-s", type=float,
        default=_env_float("KDLT_BENCH_BUDGET_S", 1140.0),
        help="overall sweep wall-clock budget (seconds, 0 = unlimited; env "
             "KDLT_BENCH_BUDGET_S overrides the default): remaining points "
             "are dropped -- and recorded as dropped -- when the next one "
             "probably would not finish.  Default 19 min: the round-4 "
             "driver killed the official run at ~25 min (rc=124), so the "
             "sweep must self-trim well inside that",
    )
    p.add_argument("--child-batch", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--flops-img", type=float, default=0.0, help=argparse.SUPPRESS)
    p.add_argument(
        "--soak", type=float, default=0,
        help="INSTEAD of the sweep: soak the real serving engine across "
             "every bucket for this many seconds, counting faults "
             "(reliability evidence; rc=0 only if fault-free)",
    )
    p.add_argument(
        "--soak-buckets", default="1,2,4,8,16,32,64,128",
        help="bucket ladder for --soak (the engine default ladder)",
    )
    args = p.parse_args()

    if args.dry_run:
        # The resolved configuration the run WOULD use, on one parsable
        # line; no jax import, no device dial, no subprocesses.
        mode = "sweep"
        for flag in ("soak", "child_batch", "pipeline_ab", "crosshost_ab",
                     "batcher_sweep", "host_saturation", "overload_ab",
                     "chaos_ab", "churn_ab", "cache_ab", "trace_breakdown",
                     "multimodel_ab", "obs_overhead_ab", "quant_ab",
                     "tenant_ab", "incident_ab", "mesh_ab", "decode_ab",
                     "ingest_ab"):
            if getattr(args, flag):
                mode = flag
                break
        print(json.dumps({
            "dry_run": True,
            "mode": mode,
            "model": args.model,
            "batches": [int(b) for b in args.batches.split(",")],
            "dtype": args.dtype,
            "params_dtype": args.params_dtype,
            "reps": args.reps,
            "scan_len": args.scan_len,
            "point_timeout": args.point_timeout,
            "budget_s": args.budget_s,
            "isolate": not args.no_isolate,
            "overload": {
                "device_ms": args.overload_device_ms,
                "deadline_ms": args.overload_deadline_ms,
                "rate_x": args.overload_rate_x,
                "buckets": [int(b) for b in args.overload_buckets.split(",")],
            },
            "chaos": {
                "device_ms": args.chaos_device_ms,
                "deadline_ms": args.chaos_deadline_ms,
                "rate_rps": args.chaos_rate_rps,
                "hedge_ms": args.chaos_hedge_ms,
                "probe_s": args.chaos_probe_s,
                "seed": args.chaos_seed,
                "mode": args.chaos_mode,
            },
            "churn": {
                "duration_s": args.churn_ab,
                "device_ms": args.churn_device_ms,
                "deadline_ms": args.churn_deadline_ms,
                "rate_rps": args.churn_rate_rps,
                "hedge_ms": args.churn_hedge_ms,
                "probe_s": args.churn_probe_s,
                "resolve_s": args.churn_resolve_s,
                "seed": args.churn_seed,
            },
            "quant": {
                "reps": args.quant_ab,
                "size": args.quant_size,
                "buckets": [int(b) for b in args.quant_buckets.split(",")],
                "calib_images": args.quant_calib_images,
                "percentile": args.quant_percentile,
                "min_size": args.quant_min_size,
                "tol": args.quant_tol,
                "seed": args.quant_seed,
            },
            "cache": {
                "duration_s": args.cache_ab,
                "device_ms": args.cache_device_ms,
                "deadline_ms": args.cache_deadline_ms,
                "rate_rps": args.cache_rate_rps,
                "zipf_alpha": args.cache_zipf_alpha,
                "universe": args.cache_universe,
                "probe_n": args.cache_probe_n,
                "seed": args.cache_seed,
            },
            "trace": {
                "requests": args.trace_breakdown,
                "device_ms": args.trace_device_ms,
            },
            "obs_overhead": {
                "duration_s": args.obs_overhead_ab,
                "clients": args.obs_clients,
                "device_ms": args.obs_device_ms,
                "rounds": args.obs_rounds,
            },
            "multimodel": {
                "duration_s": args.multimodel_ab,
                "heavy_device_ms": args.mm_heavy_device_ms,
                "light_device_ms": args.mm_light_device_ms,
                "heavy_deadline_ms": args.mm_heavy_deadline_ms,
                "light_deadline_ms": args.mm_light_deadline_ms,
                "rate_x": args.mm_rate_x,
                "light_rps": args.mm_light_rps,
            },
            "incident": {
                "duration_s": args.incident_ab,
                "device_ms": args.incident_device_ms,
                "deadline_ms": args.incident_deadline_ms,
                "rate_rps": args.incident_rate_rps,
                "seed": args.incident_seed,
            },
            "tenant": {
                "duration_s": args.tenant_ab,
                "device_ms": args.tenant_device_ms,
                "deadline_ms": args.tenant_deadline_ms,
                "rate_x": args.tenant_rate_x,
                "b_rps": args.tenant_b_rps,
                "flood_s": args.tenant_flood_s,
                "seed": args.tenant_seed,
            },
            "mesh": {
                "reps": args.mesh_ab,
                "size": args.mesh_size,
                "buckets": [int(b) for b in args.mesh_buckets.split(",")],
                "arms": [int(a) for a in args.mesh_arms.split(",")],
                "tol": args.mesh_tol,
                "bytes_slack": args.mesh_bytes_slack,
                "floor_frac": args.mesh_floor,
                "seed": args.mesh_seed,
            },
            "ingest": {
                "images": args.ingest_ab,
                "source_px": args.ingest_size,
                "input_px": args.ingest_input,
                "clients": args.ingest_clients,
                "seed": args.ingest_seed,
            },
            "decode": {
                "requests": args.decode_ab,
                "slots": args.decode_slots,
                "step_ms": args.decode_step_ms,
                "deadline_ms": args.decode_deadline_ms,
                "ttft_budget_ms": args.decode_ttft_budget_ms,
                "seed": args.decode_seed,
            },
            "crosshost": {
                "rounds": args.crosshost_ab,
                "batch": args.crosshost_ab_batch,
                "host_ms": args.crosshost_ab_host_ms,
                "processes": args.crosshost_ab_processes,
                "depths": [
                    int(d) for d in args.crosshost_ab_depths.split(",")
                ],
            },
        }), flush=True)
        return 0

    if args.soak > 0:
        return bench_soak(
            args.soak, args.model,
            tuple(int(b) for b in args.soak_buckets.split(",")),
        )

    if args.child_batch:
        # Subprocess mode for run_isolated_sweep: bench ONE batch point and
        # emit its row as the last stdout line.
        if os.environ.get("KDLT_BENCH_FAKE_CHILD"):
            print(json.dumps({
                "child": True,
                "batch": args.child_batch,
                "row": _fake_child_row(args.child_batch),
                "flops_img": 0.0,
            }), flush=True)
            return 0
        _setup_compile_cache()
        spec, results, flops_img = bench_forward(
            args.model, [args.child_batch], args.scan_len, args.reps,
            args.dtype, args.params_dtype, args.peak_tflops,
            flops_img_known=args.flops_img,
        )
        print(json.dumps({
            "child": True,
            "batch": args.child_batch,
            "row": results[args.child_batch],
            "flops_img": flops_img,
        }), flush=True)
        return 0

    if args.pipeline_ab > 0:
        out, rc = bench_pipeline_ab(
            n_batches=args.pipeline_ab,
            batch=args.pipeline_ab_batch,
            host_ms=args.pipeline_ab_host_ms,
            device_ms=args.pipeline_ab_device_ms,
            depths=tuple(int(d) for d in args.pipeline_ab_depths.split(",")),
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.crosshost_ab > 0:
        out, rc = bench_crosshost_ab(
            n_rounds=args.crosshost_ab,
            batch=args.crosshost_ab_batch,
            host_ms=args.crosshost_ab_host_ms,
            processes=args.crosshost_ab_processes,
            depths=tuple(int(d) for d in args.crosshost_ab_depths.split(",")),
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.overload_ab > 0:
        out, rc = bench_overload_ab(
            duration_s=args.overload_ab,
            device_ms=args.overload_device_ms,
            deadline_ms=args.overload_deadline_ms,
            rate_x=args.overload_rate_x,
            buckets=tuple(int(b) for b in args.overload_buckets.split(",")),
            max_delay_ms=args.max_delay_ms,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.multimodel_ab > 0:
        out, rc = bench_multimodel_ab(
            duration_s=args.multimodel_ab,
            heavy_device_ms=args.mm_heavy_device_ms,
            light_device_ms=args.mm_light_device_ms,
            heavy_deadline_ms=args.mm_heavy_deadline_ms,
            light_deadline_ms=args.mm_light_deadline_ms,
            rate_x=args.mm_rate_x,
            light_rps=args.mm_light_rps,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.obs_overhead_ab > 0:
        out, rc = bench_obs_overhead_ab(
            duration_s=args.obs_overhead_ab,
            device_ms=args.obs_device_ms,
            clients=args.obs_clients,
            rounds=args.obs_rounds,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.decode_ab > 0:
        out, rc = bench_decode_ab(
            n_requests=args.decode_ab,
            slots=args.decode_slots,
            step_ms=args.decode_step_ms,
            deadline_ms=args.decode_deadline_ms,
            ttft_budget_ms=args.decode_ttft_budget_ms,
            seed=args.decode_seed,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.chaos_ab > 0:
        out, rc = bench_chaos_ab(
            duration_s=args.chaos_ab,
            device_ms=args.chaos_device_ms,
            deadline_ms=args.chaos_deadline_ms,
            rate_rps=args.chaos_rate_rps,
            hedge_delay_ms=args.chaos_hedge_ms,
            probe_interval_s=args.chaos_probe_s,
            seed=args.chaos_seed,
            mode=args.chaos_mode,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.churn_ab > 0:
        out, rc = bench_churn_ab(
            duration_s=args.churn_ab,
            device_ms=args.churn_device_ms,
            deadline_ms=args.churn_deadline_ms,
            rate_rps=args.churn_rate_rps,
            hedge_delay_ms=args.churn_hedge_ms,
            probe_interval_s=args.churn_probe_s,
            resolve_interval_s=args.churn_resolve_s,
            seed=args.churn_seed,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.incident_ab > 0:
        out, rc = bench_incident_ab(
            duration_s=args.incident_ab,
            device_ms=args.incident_device_ms,
            deadline_ms=args.incident_deadline_ms,
            rate_rps=args.incident_rate_rps,
            seed=args.incident_seed,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.tenant_ab > 0:
        out, rc = bench_tenant_ab(
            duration_s=args.tenant_ab,
            device_ms=args.tenant_device_ms,
            deadline_ms=args.tenant_deadline_ms,
            rate_x=args.tenant_rate_x,
            b_rps=args.tenant_b_rps,
            flood_s=args.tenant_flood_s,
            seed=args.tenant_seed,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.mesh_ab > 0:
        out, rc = bench_mesh_ab(
            reps=args.mesh_ab,
            size=args.mesh_size,
            buckets=tuple(int(b) for b in args.mesh_buckets.split(",")),
            arms=tuple(int(a) for a in args.mesh_arms.split(",")),
            seed=args.mesh_seed,
            tol=args.mesh_tol,
            bytes_slack=args.mesh_bytes_slack,
            floor_frac=args.mesh_floor,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.quant_ab > 0:
        out, rc = bench_quant_ab(
            reps=args.quant_ab,
            size=args.quant_size,
            buckets=tuple(int(b) for b in args.quant_buckets.split(",")),
            calib_images=args.quant_calib_images,
            percentile=args.quant_percentile or None,
            seed=args.quant_seed,
            min_size=args.quant_min_size,
            tol=args.quant_tol or None,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.cache_ab > 0:
        out, rc = bench_cache_ab(
            duration_s=args.cache_ab,
            device_ms=args.cache_device_ms,
            deadline_ms=args.cache_deadline_ms,
            rate_rps=args.cache_rate_rps,
            zipf_alpha=args.cache_zipf_alpha,
            universe=args.cache_universe,
            probe_n=args.cache_probe_n,
            seed=args.cache_seed,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.ingest_ab > 0:
        out, rc = bench_ingest_ab(
            n_images=args.ingest_ab,
            source_px=args.ingest_size,
            input_px=args.ingest_input,
            clients=args.ingest_clients,
            seed=args.ingest_seed,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.trace_breakdown > 0:
        out, rc = bench_trace_breakdown(
            n_requests=args.trace_breakdown,
            device_ms=args.trace_device_ms,
        )
        print(json.dumps(out), flush=True)
        return rc

    if args.batcher_sweep > 0:
        bench_batcher_sweep(
            args.batcher_sweep,
            args.clients,
            [float(d) for d in args.device_ms.split(",")],
            args.max_delay_ms,
        )
        return 0

    if args.host_saturation > 0:
        bench_host_saturation(
            args.host_saturation,
            args.clients,
            [int(b) for b in args.request_batches.split(",")],
            args.batcher,
            args.max_delay_ms,
            stub_device_ms=args.stub_device_ms,
        )
        return 0

    if args.serving > 0:
        result = bench_serving(
            args.serving,
            args.clients,
            args.batcher,
            args.max_delay_ms,
            buckets=(1, 2, 4, 8, 16, 32, 64),
        )
        print(json.dumps({"metric": "serving e2e", **result}), flush=True)
        # One process per chip: bench_serving ran a full ModelServer on the
        # device in THIS process, which now holds the chip -- a sweep child
        # started from here would fail or hang waiting for it.
        return 0

    batch_sizes = [int(b) for b in args.batches.split(",")]
    dropped: list[int] = []
    terminated = False
    if args.no_isolate:
        _, results, flops_img = bench_forward(
            args.model, batch_sizes, args.scan_len, args.reps, args.dtype,
            args.params_dtype, args.peak_tflops,
        )
        faults = []
    else:
        # The official-record path.  Survivability contract (VERDICT r4 #1):
        # the last stdout line is ALWAYS a parsable headline once the first
        # point completes -- emitted incrementally per point, re-emitted on
        # SIGTERM, and the budget trims the tail before the driver's axe.
        _setup_compile_cache()
        signal.signal(signal.SIGTERM, _sigterm_handler)

        def emit(res, fts, fpi):
            out, _ = compose_headline(
                args.model, args.dtype, args.params_dtype, res, fts, fpi,
                points_total=len(batch_sizes),
            )
            print(json.dumps(out), flush=True)

        # The sweep mirrors progress into ``st`` as it happens, so even a
        # SweepTerminated that escapes the sweep's own handler (a second
        # SIGTERM mid-cleanup) leaves the completed points printable.
        st: dict = {}
        try:
            run_isolated_sweep(args, batch_sizes, emit=emit, state=st)
        except SweepTerminated:
            st["terminated"] = True
        finally:
            # The record is about to be finalized; nothing a further TERM
            # could add but a truncated last line.
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
        results = st.get("results", {})
        faults = st.get("faults", [])
        flops_img = st.get("flops_img", 0.0)
        dropped = st.get("dropped", [])
        terminated = st.get("terminated", False)

    if terminated:
        # The signal may have interrupted an in-flight emission mid-line;
        # start fresh so the final record is guaranteed to stand alone as
        # the last stdout line.
        print(flush=True)
    out, rc = compose_headline(
        args.model, args.dtype, args.params_dtype, results, faults, flops_img,
        dropped=dropped, terminated=terminated, points_total=len(batch_sizes),
    )
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
