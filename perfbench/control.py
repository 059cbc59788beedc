#!/usr/bin/env python3
"""The control of ``correct``, on the chip at a cell's own size: the plain
reference put in the program's place and computed one precision below the
one the configuration states (fp8 for bfloat16), over the same pictures
and weights as a run of that seed.  Its reading, by the run's own measure
(widest |logit difference| over the pool, relative to the largest
|reference logit|), has to lie above the limit; the limits in the
configuration files were set from these readings (PERF.md section 2).

    python3 perfbench/control.py --workload <name> --seeds 1,2,3

No server is started: the artifact child writes the weights, the reference
child runs twice on the chip.  Not part of a benchmark run.
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
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    import numpy as np

    manifest = manifest_lib.Manifest(run_lib.ROOT)
    cell = manifest.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = run_lib.CellRun(manifest, cell, seed, 1.0, False)
        try:
            run.prepare()
            if run.host_proc is not None:
                run.children.stop("image_host", run.host_proc)
            outs = {}
            for precision in ("float32", "fp8"):
                out = os.path.join(run.work, f"{precision}.npy")
                child = run.children.spawn(f"reference-{precision}", [
                    run.child_script("reference.py"), "--config", run.config_path(),
                    "--params", os.path.join(run.work, "models", run.model, "1",
                                             "params.msgpack"),
                    "--seed", str(seed), "--out", out, "--precision", precision,
                    "--cache-dir", run.compile_cache, *run.reference_inputs])
                run.children.wait_exit(f"reference-{precision}", child)
                outs[precision] = np.load(out)
            ref, ctl = outs["float32"], outs["fp8"]
            scale = float(np.abs(ref).max())
            per_row = np.abs(ctl - ref).max(axis=1) / scale
            print(json.dumps({
                "workload": args.workload, "seed": seed, "rows": len(ref),
                "logit_scale": scale, "control_logit_err": float(per_row.max()),
                "control_smallest_row_err": float(per_row.min()),
                "control_median_row_err": float(np.median(per_row)),
                "limit": run.config["limits"]["logit_err"],
            }), flush=True)
        finally:
            run.children.kill_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
