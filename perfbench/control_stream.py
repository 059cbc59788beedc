#!/usr/bin/env python3
"""The control of ``correct`` for a token-stream cell, on the chip at the
cell's own size: the plain reference put in the program's place and
computed with every contraction's operands read through a narrower type
(float8 e4m3 for a configuration that states bfloat16), over the streams of
a run of that seed.  Its readings, by the run's own measures, have to lie
above the limits; the limits in the configuration file were set from them
(PERF.md section 2).

    python3 perfbench/control_stream.py --workload <cell> --seed S \\
        --requests .perfbench_cache/run/<cell>/reference_requests.json \\
        [--operands float8_e4m3fn,bfloat16]

``--requests`` is what a run of the cell with that seed left behind: the
sampled streams, each prompt with its served tokens.  No server is started:
the artifact child writes the weights again from the seed, the reference
child runs once in float32 -- with ``routes`` (its routers' choices) and,
for each narrower pass, the logits at the ids that pass puts first
(``probe``) -- and once per narrower type.  A line a type:

- ``control_logit_err``: widest |narrow logit - float32 logit| over the ids
  the streams returned, over the largest |float32 logit|;
- ``control_argmax_gap``: widest (float32 best - float32 logit at the
  narrow pass's choice), over the same;
- ``flipped``: (position, layer) pairs whose chosen experts differ from the
  float32 pass's, of ``routed``; ``flipped_held``: those where a held
  expert is in one choice and not the other.

Not part of a benchmark run.
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
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--requests", required=True)
    p.add_argument("--operands", default="float8_e4m3fn")
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    import numpy as np

    manifest = manifest_lib.Manifest(run_lib.ROOT)
    cell = manifest.cell(args.workload)
    with open(args.requests) as f:
        requests = json.load(f)["requests"]
    run = run_lib.CellRun(manifest, cell, args.seed, 1.0, False, platform=args.platform,
                          work_root=os.path.join(run_lib.ROOT, ".perfbench_cache", "control"))
    lo, hi = run.config["held_experts"]

    def reference(name: str, request_list: list, operand: str) -> dict:
        path = os.path.join(run.work, f"{name}.json")
        with open(path, "w") as f:
            json.dump({"requests": request_list}, f)
        out = os.path.join(run.work, f"{name}.npz")
        child = run.children.spawn(f"reference-{name}", [
            run.child_script(run.config["reference_child"]), "--config", run.config_path(),
            "--seed", str(args.seed), "--requests", path, "--out", out,
            "--artifact", os.path.join(run.work, "models"),
            "--cache-dir", run.compile_cache, *(["--operand", operand] if operand else [])])
        run.children.wait_exit(f"reference-{name}", child)
        return np.load(out)

    try:
        run.children.wait_exit("artifact", run.start_artifact())
        narrow = {op: reference(op, requests, op) for op in args.operands.split(",")}
        # float32 again for each: the probes are that narrower pass's choices;
        # "float32" as the operand type changes nothing and brings the routes
        plain = {}
        for op in narrow:
            probed = [dict(r, probe=narrow[op][f"argmax_{i}"].tolist())
                      for i, r in enumerate(requests)]
            plain[op] = reference(f"float32-for-{op}", probed, "float32")
        for op, ctl in narrow.items():
            ref = plain[op]
            scale = float(ref["scale"])
            err = gap = 0.0
            flipped = flipped_held = routed = 0
            for i in range(len(requests)):
                err = max(err, float(np.abs(ctl[f"top_{i}"] - ref[f"top_{i}"]).max()))
                gap = max(gap, float((ref[f"best_{i}"] - ref[f"probe_{i}"]).max()))
                a, b = ctl[f"route_{i}"], ref[f"route_{i}"]
                differs = (a != b).any(axis=-1)
                # a held expert in one choice and not in the other (rows are sorted)
                held_a = np.where((a >= lo) & (a < hi), a, -1)
                held_b = np.where((b >= lo) & (b < hi), b, -1)
                held_differs = (np.sort(held_a, axis=-1) != np.sort(held_b, axis=-1)).any(axis=-1)
                flipped += int(differs.sum())
                flipped_held += int(held_differs.sum())
                routed += differs.size
            print(json.dumps({
                "workload": args.workload, "seed": args.seed, "operand": op,
                "streams": len(requests), "logit_scale": scale,
                "control_logit_err": err / scale, "control_argmax_gap": gap / scale,
                "flipped": flipped, "flipped_held": flipped_held, "routed": routed,
                "limits": run.config["limits"]}), flush=True)
    finally:
        run.children.kill_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
