#!/usr/bin/env python3
"""Why the chip waits, read off one /debug/profile capture (PR 24; the
token cells' lane since PR 37).

    python3 exp/profile_gaps.py capture --workload <cell> --seed N --out DIR [--annotations]
    python3 exp/profile_gaps.py analyze <trace dir or *.xplane.pb> [--out F.json]

``capture`` boots a cell of BENCHMARK.json as perfbench does (same artifact,
server flags, bodies and closed-loop callers, through the cell's own entry:
tensors, or token streams for a ``server-generate`` cell), and while the
traffic runs asks the program's own ``GET /debug/profile?seconds=2`` for a
trace: the device's planes and, with ``--annotations``, the model tier's
live spans, the decode loop's phases and the GC pauses as host annotations.
It reports what the capture cost (start/stop seconds, bytes, pictures or
tokens answered per second before, during and after it), the program's
counters over the capture (the dispatcher's three, or the decode loop's
phases, dry-ups, CPU seconds and GC pauses, scraped just before and after
it), and then runs ``analyze``; for a token cell it sets the loop's dry
seconds beside the trace's idle between programs.

``analyze`` is the hand-made prototype of what a later ``benchmark`` PR
puts into ``perfbench/reduce_trace.py``:

- the clock check: end of each ``pipeline.readback`` annotation minus end
  of the ``XLA Modules`` event it waited for -- small, positive and steady
  when host annotations and device events share a clock;
- launch to start: from the end of each ``pipeline.dispatch`` annotation
  to the start of the next device program -- how long the device waits for
  a batch the host already counts as in flight (its input's transfer);
- the lane's clock check: end of each ``decode.loop.read`` minus end of
  the program it waited for;
- the ten longest gaps between device programs, each with the annotation
  open meanwhile on the dispatching thread, the readback thread, the
  decode loop's thread and the handler threads, and the GC pauses on any;
- how many events the host plane holds, by name.

The parent never imports jax (one process per chip): ``analyze`` runs as a
child pinned to the CPU.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import statistics
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DISPATCH, READBACK = "pipeline.dispatch", "pipeline.readback"
LOOP, LOOP_READ, GC_PAUSE = "decode.loop.", "decode.loop.read", "gc.pause"
TOP = 10


# --- analyze -----------------------------------------------------------------


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def analyze(path: str) -> dict:
    import jax  # the format's reader; JAX_PLATFORMS=cpu in the child

    from perfbench.reduce_trace import find_xplane

    xplane = find_xplane(path)
    out = {"xplane": xplane, "xplane_bytes": os.path.getsize(xplane)}
    out.update(analyze_profile(jax.profiler.ProfileData.from_file(xplane)))
    return out


def analyze_profile(profile) -> dict:
    from kubernetes_deep_learning_tpu.utils.trace import SPAN_NAMES
    from perfbench.reduce_trace import union

    modules, ops, threads = [], [], []
    host_names: collections.Counter = collections.Counter()
    host_first = None
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.start_ns, e.end_ns, e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.start_ns, e.end_ns) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans = []
                for e in line.events:
                    host_names[e.name.split("(")[0][:48]] += 1
                    host_first = e.start_ns if host_first is None else min(host_first, e.start_ns)
                    if e.name in SPAN_NAMES:
                        spans.append((e.start_ns, e.end_ns, e.name))
                if spans:
                    threads.append((line.name, sorted(spans)))
    out = {
        "host_events": sum(host_names.values()),
        "host_top": host_names.most_common(12),
        "transpose_chunk_events": sum(
            n for name, n in host_names.items() if name.startswith("Transpose::")),
        "annotations": dict(collections.Counter(
            name for _, spans in threads for _, _, name in spans)),
        "device_modules": len(modules),
    }
    if not modules or not ops:
        return out
    modules.sort()
    busy = union(ops)
    t0, t1 = busy[0][0], busy[-1][1]
    out["device_window_s"] = (t1 - t0) / 1e9
    out["device_idle_pct"] = 100.0 * (1 - sum(b - a for a, b in busy) / (t1 - t0))
    first = [(a, min(b, t0 + 1e9)) for a, b in busy if a < t0 + 1e9]
    out["device_idle_pct_first_second"] = 100.0 * (1 - sum(b - a for a, b in first) / 1e9)
    # how long after the host's first event the device's first one came:
    # a stall at the capture's start would show here (and in first_second)
    out["first_device_event_after_host_s"] = (t0 - host_first) / 1e9 if host_first else None

    # the clock checks, over every readback (the image dispatcher's) and
    # every read (the decode loop's) that ended inside the device window
    ends = [m[1] for m in modules]
    for key, sync in (("readback", READBACK), ("loop_read", LOOP_READ)):
        deltas = []
        for _, spans in threads:
            for s, e, name in spans:
                if name != sync or e < ends[0]:
                    continue
                done = max(m for m in ends if m <= e)
                deltas.append((e - done) / 1e3)
        if deltas:
            out[f"{key}_end_minus_module_end_us"] = {
                "n": len(deltas), "median": statistics.median(deltas),
                "min": min(deltas), "max": max(deltas),
                "quartiles": statistics.quantiles(deltas, n=4) if len(deltas) > 3 else None}

    # launch to start: the next program's start after each dispatch returned
    starts = [m[0] for m in modules]
    lags = []
    for _, spans in threads:
        for s, e, name in spans:
            later = [m for m in starts if m >= e]
            if name == DISPATCH and later:
                lags.append((min(later) - e) / 1e6)
    if lags:
        out["dispatch_end_to_module_start_ms"] = {
            "n": len(lags), "median": statistics.median(lags),
            "min": min(lags), "max": max(lags)}
    durs = [(m[1] - m[0]) / 1e6 for m in modules]
    out["module_ms"] = {"median": statistics.median(durs), "min": min(durs), "max": max(durs)}

    def role(spans):
        names = {n for _, _, n in spans}
        if DISPATCH in names:
            return "dispatch"
        if any(n.startswith(LOOP) for n in names):
            return "loop"
        return "readback" if READBACK in names else "handler"

    gaps = sorted(((b0 - a1, a1, b0) for (_, a1), (b0, _) in zip(busy, busy[1:])),
                  reverse=True)[:TOP]
    table = []
    for length, a, b in gaps:
        row = {"at_ms": (a - t0) / 1e6, "gap_ms": length / 1e6,
               "dispatch": {}, "readback": {}, "loop": {}, "handlers": {}, "gc_ms": 0.0}
        for _, spans in threads:
            r = role(spans)
            for s, e, name in spans:
                ov = _overlap(s, e, a, b)
                if ov <= 0:
                    continue
                if name == GC_PAUSE:
                    # a pause stops every thread, whichever one it ran on
                    row["gc_ms"] += ov / 1e6
                elif r == "handler":
                    # thread-milliseconds of the gap under each annotation,
                    # summed over handler threads (nested spans both count)
                    row["handlers"][name] = row["handlers"].get(name, 0.0) + ov / 1e6
                else:
                    row[r][name] = row[r].get(name, 0.0) + 100.0 * ov / length
        table.append(row)
    gap_lengths = [b0 - a1 for (_, a1), (b0, _) in zip(busy, busy[1:])]
    out["gaps_total_ms"] = sum(gap_lengths) / 1e6
    # what launch to launch costs with the next program queued is tens of
    # microseconds; a gap of a millisecond or more is the device left without one
    out["gaps_over_1ms"] = {"n": sum(g >= 1e6 for g in gap_lengths),
                            "total_ms": sum(g for g in gap_lengths if g >= 1e6) / 1e6}
    # over the whole device window: the share of it the dispatching, the
    # readback and the decode loop's thread spend under each annotation, and
    # the mean number of handler threads under each
    whole: dict = {"dispatch": {}, "readback": {}, "loop": {}, "handler": {}}
    for _, spans in threads:
        r = role(spans)
        for s, e, name in spans:
            whole[r][name] = whole[r].get(name, 0.0) + _overlap(s, e, t0, t1) / (t1 - t0)
    out["window_shares"] = whole
    out["longest_gaps"] = table
    return out


# --- capture -----------------------------------------------------------------


# the decode loop's counters (runtime.decode.LoopClock), summed over labels
START_S = 0.05      # /debug/profile's start of the trace, from the call
LOOP_PHASES = ("wait", "admit", "dispatch", "flush", "read", "book")
LANE = ([f"kdlt_decode_loop_{p}_seconds_total" for p in LOOP_PHASES]
        + ["kdlt_decode_loop_cpu_seconds_total", "kdlt_decode_dry_seconds_total",
           "kdlt_decode_dry_total", "kdlt_gc_pause_seconds_total",
           "kdlt_decode_steps_total", "kdlt_decode_prefill_chunks_total"])
PIPELINE = ["kdlt_pipeline_idle_dispatch_seconds_total",
            "kdlt_pipeline_idle_no_batch_seconds_total",
            "kdlt_pipeline_inflight_seconds_total"]


def _lane_reading(d: dict) -> dict:
    """The four per-layer quantities of the token cells over a counter delta."""
    life = sum(d[f"kdlt_decode_loop_{p}_seconds_total"] for p in LOOP_PHASES)
    host = sum(d[f"kdlt_decode_loop_{p}_seconds_total"]
               for p in ("admit", "dispatch", "flush", "book"))
    reads = d["kdlt_decode_steps_total"] + d["kdlt_decode_prefill_chunks_total"]
    return dict(d, life_s=life, decode_dry_pct=100 * d["kdlt_decode_dry_seconds_total"] / life,
                decode_host_ms=1000 * host / max(reads, 1.0),
                decode_offcpu_pct=100 * (host - d["kdlt_decode_loop_cpu_seconds_total"]) / host,
                gc_pause_pct=100 * d["kdlt_gc_pause_seconds_total"] / life)


def capture(workload: str, seed: int, out_dir: str, seconds: float, window: float,
            annotations: bool = False, root: str = ROOT, platform: str = "tpu") -> dict:
    from perfbench import manifest as manifest_lib
    from perfbench import procs, tokens, traffic
    from perfbench import run as run_lib

    os.makedirs(out_dir, exist_ok=True)
    manifest = manifest_lib.Manifest(root)
    run = run_lib.CellRun(manifest, manifest.cell(workload), seed, window, False,
                          platform=platform)
    report: dict = {"workload": workload, "seed": seed}
    lead = float(run.mix["lead_in_s"])
    try:
        run.prepare()
        st = run.boot_server()
        report["status_memory"] = st.get("memory")
        run.warm()
        run.mark("warm")
        lane = hasattr(run, "pool")     # the token wire's entry made a pool of prompts
        if lane:
            entry = tokens.ServerGenerate(run.server, run.model, run.bodies)
            rows, names = [(i,) for i in range(len(run.pool))], LANE
        else:
            entry = traffic.ServerTensor(run.server, run.model, run.bodies)
            rows, names = run.body_rows, PIPELINE
        box: dict = {}

        def drive():
            box["outcomes"], box["t_zero"] = traffic.run_closed(
                entry, run.mix, seed, lead, window, rows)

        def counters_now():
            page = procs.parse_metrics(procs.scrape(run.server))
            return time.monotonic(), {s: page.get(s, 0.0) for s in names}

        th = threading.Thread(target=drive)
        th.start()
        time.sleep(lead)
        first_page = procs.parse_metrics(procs.scrape(run.server))
        t_first, first = counters_now()
        time.sleep(window * 0.4)
        before = procs.parse_metrics(procs.scrape(run.server))
        inside: list = []

        def scrape_inside(at):
            # the trace's own seconds: the profiler starts ~50 ms into the
            # call, and the process may stand still at its stop
            for t in (at + START_S, at + START_S + seconds):
                time.sleep(max(0.0, t - time.monotonic()))
                inside.append((time.monotonic() - at, counters_now()[1]))

        p0 = time.monotonic()
        scraper = threading.Thread(target=scrape_inside, args=(p0,))
        scraper.start()
        reply = procs.get_json(
            run.server,
            f"/debug/profile?seconds={seconds}&annotations={int(annotations)}",
            timeout=seconds + 900)
        p1 = time.monotonic()
        scraper.join()
        after = procs.parse_metrics(procs.scrape(run.server))
        th.join(timeout=window + 1200)
        t_zero = box["t_zero"]
        report["profile_reply"] = reply
        report["profile_call_s"] = p1 - p0
        report["status_after"] = procs.get_json(run.server, "/v1/models")[run.model].get("memory")

        def rate(a, b):
            if lane:   # token frames as they arrived (monotonic stamps)
                return sum(a <= x - t_zero < b for o in box["outcomes"]
                           if getattr(o, "stream", None) for x in o.stream.arrivals) / (b - a)
            done = [o for o in box["outcomes"] if o.status == 200 and a <= o.done_s < b]
            return sum(len(o.rows) for o in done) / (b - a)

        a, b = p0 - t_zero, p1 - t_zero
        report["tokens_per_s" if lane else "images_per_s"] = {
            "before": rate(1.0, a), "during": rate(a, b), "after": rate(b, window),
            "whole": rate(0.0, window), "capture_from_s": a, "capture_to_s": b}
        report["failed"] = sum(1 for o in box["outcomes"] if o.status != 200)
        d = {s: after.get(s, 0.0) - before.get(s, 0.0) for s in names}
        if lane:
            report["counters_during_capture"] = _lane_reading(d)
        else:
            report["counters_during_capture"] = dict(
                d, sum_s=sum(d.values()),
                starved_pct=100.0 * (1 - d[names[2]] / sum(d.values())))
        # every instant is booked to one phase or cause: between two scrapes
        # their sum advances by the wall seconds that passed (a scrape lags by
        # the time since the last transition: a batch, a read or 0.5 s idle)
        t_last, last = counters_now()
        booked = LANE[:len(LOOP_PHASES)] if lane else names
        report["counters_window"] = {
            "wall_s": t_last - t_first,
            "sum_s": sum(last[s] - first[s] for s in booked),
            "totals": last}
        if lane:
            report["counters_window"]["reading"] = _lane_reading(
                {s: last[s] - first[s] for s in names})
        else:
            # each pipeline stage's mean over the run, from the histograms
            report["stage_ms"] = {
                st: 1000.0 * (after[f"kdlt_pipeline_{st}_seconds_sum"]
                              - first_page[f"kdlt_pipeline_{st}_seconds_sum"])
                / max(1.0, after[f"kdlt_pipeline_{st}_seconds_count"]
                      - first_page[f"kdlt_pipeline_{st}_seconds_count"])
                for st in ("enqueue_wait", "dispatch", "execute", "readback")}
        # by label set, at the run's end: the engine's input paths (PR 29;
        # whole buckets sent as views of the body should all read "view")
        # and the compile requests, which stand still once the server is warm
        report["engine_input_total"] = {
            series: float(value)
            for series, _, value in (line.rpartition(" ")
                                     for line in procs.scrape(run.server).splitlines())
            if series.startswith(("kdlt_engine_input_total{", "kdlt_engine_batches_total",
                                  "kdlt_decode_dry_seconds_total{"))}
        report["compile_requests"] = {
            "at_warm": first_page.get("kdlt_xla_compile_requests_total"),
            "at_end": after.get("kdlt_xla_compile_requests_total")}
        report["trace_bytes"] = sum(
            os.path.getsize(os.path.join(base, f))
            for base, _d, files in os.walk(reply["trace_dir"]) for f in files)
        run.stop_servers()
        # read the trace where it lies (the run's directory is emptied when
        # the next run starts); keep a copy only if it is small enough to
        # bring back from the chip's machine
        analysis_path = os.path.join(out_dir, "analysis.json")
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "analyze", reply["trace_dir"],
             "--out", analysis_path],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True)
        if report["trace_bytes"] < 16 << 20:
            shutil.copytree(reply["trace_dir"], os.path.join(out_dir, "trace"),
                            dirs_exist_ok=True)
    finally:
        run.children.kill_all()
    report["analyze_rc"] = child.returncode
    if child.returncode:
        report["analyze_err"] = child.stderr[-2000:]
    elif lane:
        # the program's dry seconds over the capture beside the trace's idle
        # between programs: the scrapes hold the start and stop of the capture
        # besides its seconds, and a dry-up is booked when it ends
        with open(analysis_path) as f:
            found = json.load(f)
        (a0, c0), (a1, c1) = inside
        dry = {s: c1[s] - c0[s] for s in names}
        report["dry_vs_trace"] = {
            "dry_ms": 1000 * dry["kdlt_decode_dry_seconds_total"],
            "dry_ups": dry["kdlt_decode_dry_total"],
            "scraped_at_s": [a0, a1], "reading": _lane_reading(dry),
            "dry_ms_scrape_to_scrape": 1000 * d["kdlt_decode_dry_seconds_total"],
            "trace_gaps_ms": found.get("gaps_total_ms"),
            "trace_gaps_over_1ms": found.get("gaps_over_1ms"),
            "trace_window_s": found.get("device_window_s"),
            "scrape_to_scrape_s": p1 - p0}
    with open(os.path.join(out_dir, "capture.json"), "w") as f:
        json.dump(report, f, indent=1)
    return report


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("capture")
    c.add_argument("--workload", required=True)
    c.add_argument("--seed", type=int, required=True)
    c.add_argument("--out", required=True)
    c.add_argument("--seconds", type=float, default=2.0)
    c.add_argument("--window", type=float, default=30.0)
    c.add_argument("--annotations", action="store_true")
    c.add_argument("--root", default=ROOT, help="where BENCHMARK.json lies (a rehearsal's)")
    c.add_argument("--platform", default="tpu", help="cpu for a rehearsal at a tiny size")
    a = sub.add_parser("analyze")
    a.add_argument("path")
    a.add_argument("--out")
    args = p.parse_args()
    if args.cmd == "capture":
        report = capture(args.workload, args.seed, args.out, args.seconds, args.window,
                         args.annotations, args.root, args.platform)
        print(json.dumps(report))
        return 0
    result = analyze(args.path)
    text = json.dumps(result, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
