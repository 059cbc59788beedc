#!/usr/bin/env python3
"""A scratch manifest around the stand-in lane: a copy of ``perfbench/``
with ``lane_server.py`` in ``children/serve.py``'s place, and -- as a later
PR adds a cell -- the family's file, its artifact child, a configuration,
two traffic files (a closed loop and Poisson arrivals), per-layer data
files and the entries of a ``BENCHMARK.json`` that is in no committed file.
``tests/test_generate_entry.py`` rehearses whole runs from it on the CPU;
run as a command it makes one run on the device that JAX finds, the token
entry's proof of plumbing on the real host (PERF.md, section 6, PR 26):

    python3 perfbench/tests/standin/build.py --root .scratch/standin \
        --workload standin-closed --seed 5 --seconds 20 --trace 1
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(BENCH))

CONFIG = {
    "name": "standin-lm", "served_name": "gen-default", "reference": "byte_lm",
    "artifact_child": "lane_artifact.py", "reference_child": "reference_stream.py",
    "d_model": 32, "n_layers": 2, "n_heads": 2, "vocab_size": 258, "vocab_held": 258,
    "assumed": {"reference_block": 16},
    # the lane at the device's default matmul precision against float32 at
    # highest: 5e-7 on the CPU, where both are float32; the two faults of
    # test_generate_entry.py read 0.46 and 0.57 there.  A toy's limits, set
    # wide of the chip's readings (PERF.md, section 6, PR 26); no cell's.
    "limits": {"logit_err": 0.05, "argmax_gap": 0.05},
}
MIX = {
    "entry": "server-generate", "pool": 24, "top_logits": 8, "compare_requests": 6,
    "prompt_tokens": {"choice": [[8, 48, 1]]}, "output_tokens": {"choice": [[64, 64, 1]]},
    "slots": 4, "page_size": 16, "max_pages": 8, "prompt_buckets": [16, 32, 64],
    "request_timeout_s": 60, "drain_s": 60, "lead_in_s": 1.0,
    "warm": {"steady_rounds": 2, "steady_within": 1.5, "settle_timeout_s": 20, "min_seconds": 0},
    "trace_offset_s": 1.0, "trace_seconds": 2.0, "span_recent": 50, "span_sample": 4,
}
MIXES = {"standin-closed": dict(MIX, generator="closed", callers=4),
         "standin-open": dict(MIX, generator="open-poisson", rate_per_s=6.0, workers=16)}
E2E = {"output_tokens_per_s": ("tokens/s", "higher"), "ttft_p50_ms": ("ms", "lower"),
       "ttft_p95_ms": ("ms", "lower"), "itl_p50_ms": ("ms", "lower"),
       "itl_p95_ms": ("ms", "lower"), "latency_p50_ms": ("ms", "lower"),
       "latency_p95_ms": ("ms", "lower")}
LAYER = {
    "lane_tokens_per_s": {"reader": "metrics_delta", "scale": 1.0,
                          "num": [["server", "lane_tokens_total", 1]],
                          "den": [["server", "lane_seconds_total", 1]]},
    "device_idle_pct.standin": {"reader": "trace_busy", "value": "idle_pct"},
}


def build(root: str) -> None:
    """The scratch tree under ``root``."""
    bench = os.path.join(root, "perfbench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    children = os.path.join(bench, "children")
    os.rename(os.path.join(children, "serve.py"), os.path.join(children, "serve_side.py"))
    shutil.copy(os.path.join(HERE, "lane_server.py"), os.path.join(children, "serve.py"))
    shutil.copy(os.path.join(HERE, "lane_artifact.py"), children)
    shutil.copy(os.path.join(HERE, "byte_lm.py"), os.path.join(bench, "reference"))
    config, mixes = CONFIG, MIXES
    with open(os.path.join(bench, "configs", config["name"] + ".json"), "w") as f:
        json.dump(config, f)
    for name, mix in mixes.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    for name, spec in LAYER.items():
        with open(os.path.join(bench, "layer_metrics", name + ".json"), "w") as f:
            json.dump(spec, f)
    manifest = {
        "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"], "run_seconds": 20,
        "configs": [{"name": config["name"], "source": "perfbench/tests/standin", "reduced": [],
                     "file": f"perfbench/configs/{config['name']}.json", "why": "a toy"}],
        "workloads": [{"name": n, "config": config["name"], "traffic": n, "chips": 1,
                       "why": "the token entry's plumbing"} for n in mixes],
        "end_to_end": [{"name": n, "unit": u, "better": b, "bound": 0.1, "source": "host_clock"}
                       for n, (u, b) in E2E.items()]
        + [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
            "source": "host_clock"}],
        "per_layer": [{"name": n, "unit": u, "better": b, "source": s, "layer": "the lane",
                       "moves": "output_tokens_per_s"}
                      for n, u, b, s in (("lane_tokens_per_s", "tokens/s", "higher",
                                          "program_counter"),
                                         ("device_idle_pct.standin", "%", "lower",
                                          "device_trace"))],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--root", required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    from perfbench import manifest as manifest_lib
    from perfbench import run as run_lib

    if not os.path.exists(os.path.join(args.root, "BENCHMARK.json")):
        build(args.root)
    manifest = manifest_lib.Manifest(args.root)
    manifest.validate()
    run = run_lib.CellRun(manifest, manifest.cell(args.workload), args.seed, args.seconds,
                          bool(args.trace), platform=args.platform,
                          work_root=os.path.join(args.root, "work"))
    try:
        line = run.run()
    finally:
        run.children.kill_all()
    run_lib.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
