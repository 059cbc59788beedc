#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: one boot, one window per
offered rate, and for each the share answered, the median and the 95th
percentile from the due time, and the backlog at the window's close.

    python3 perfbench/sweep.py --workload effnetb7-url-open --seed 1 \
        --seconds 12 --rates 40,80,120,160,200

The knee is the highest rate whose window ends with no backlog growing (the
last second's latencies no worse than the window's).  The number goes into
the traffic file by hand, with the sweep in PERF.md; the benchmark itself
never searches.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import manifest as manifest_lib  # noqa: E402
from perfbench import run as run_lib  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--rates", required=True)
    p.add_argument("--trace", type=int, default=0,
                   help="1: a device trace inside each window, for the busy share")
    args = p.parse_args(argv)
    manifest = manifest_lib.Manifest(run_lib.ROOT)
    cell = manifest.cell(args.workload)
    run = run_lib.CellRun(manifest, cell, args.seed, args.seconds, bool(args.trace))
    try:
        run.prepare()
        run.boot()
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            run.mix["rate_per_s"] = rate
            run.seed = args.seed + i
            before = run.snapshot()["server"]
            run.drive()
            after = run.after["server"]
            w = run.window
            ok = [o for o in w if o.status == 200]
            lat = sorted(1000 * (o.done_s - o.due_s) for o in ok)
            tail = sorted(1000 * (o.done_s - o.due_s) for o in ok
                          if o.due_s >= args.seconds - 2)
            busy = None
            if args.trace:
                import subprocess

                out = subprocess.run(
                    [sys.executable, os.path.join(run_lib.HERE, "reduce_trace.py"),
                     run.trace_reply["trace_dir"]], env=run.host_env,
                    capture_output=True, text=True, check=True).stdout
                reduced = json.loads(out.strip().splitlines()[-1])
                busy = reduced["busy_s"] / reduced["window_s"] if reduced["window_s"] else None
            batches = after.get("kdlt_engine_batches_total", 0) - before.get(
                "kdlt_engine_batches_total", 0)
            images = after.get("kdlt_engine_images_total", 0) - before.get(
                "kdlt_engine_images_total", 0)
            print(json.dumps({
                "rate": rate, "sent": len(w), "ok": len(ok),
                "p50_ms": run_lib.percentile(lat, 50) if lat else None,
                "p95_ms": run_lib.percentile(lat, 95) if lat else None,
                "last2s_p50_ms": run_lib.percentile(tail, 50) if tail else None,
                "late_p95_ms": run_lib.percentile(
                    sorted(1000 * (o.sent_s - o.due_s) for o in w), 95),
                "mean_batch": images / batches if batches else None,
                "device_busy_share": busy,
                "images_per_s": sum(len(o.rows) for o in ok
                                    if o.done_s <= args.seconds) / args.seconds,
                "statuses": sorted({o.status for o in w}),
            }), flush=True)
        run.stop_servers()
    finally:
        run.children.kill_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
