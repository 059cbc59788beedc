#!/usr/bin/env python3
"""A scratch manifest around the stand-in lane, made as a later PR adds a
cell: a copy of ``perfbench/`` gains files alone -- the family's reference,
its artifact child, the lane itself (``children/lane_server.py``), a
configuration, two traffic files (a closed loop and Poisson arrivals),
per-layer data files -- and a ``BENCHMARK.json`` that is in no committed
file.  Two manifests:

- ``build(root)``: the token entry's own, all seven of its quantities as
  end-to-end metrics (``tests/test_generate_entry.py``);
- ``build(root, appended=True)``: the committed ``BENCHMARK.json`` with the
  stand-in's configuration, cells and per-layer metrics appended at the ends
  of their lists and ``end_to_end`` untouched, as a program PR may: the
  token cells report ``setup_s`` and ``latency_p50_ms``, which list no
  cells (``tests/test_extend.py``).

The one thing no file of the benchmark can stand in for is the program, so
``LaneRun`` starts the lane where a run starts ``children/serve.py``.  Run
as a command this makes one run on the device that JAX finds, the proof of
plumbing on the real host (PERF.md, section 6, PRs 26 and 28):

    python3 perfbench/tests/standin/build.py --root .scratch/standin \
        --appended --workload standin-closed --seed 5 --seconds 20 --trace 1
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

from perfbench import manifest as manifest_lib  # noqa: E402
from perfbench import run as run_lib  # noqa: E402

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
LAYER = {   # name -> unit, better, source, the data file
    "lane_tokens_per_s": ("tokens/s", "higher", "program_counter", {
        "reader": "metrics_delta", "scale": 1.0,
        "num": [["server", "lane_tokens_total", 1]],
        "den": [["server", "lane_seconds_total", 1]]}),
    "device_idle_pct.standin": ("%", "lower", "device_trace", {
        "reader": "trace_busy", "value": "idle_pct"}),
}


class LaneRun(run_lib.CellRun):
    """A run whose server child is the stand-in lane, in the program's place."""

    def child_script(self, name: str) -> str:
        return super().child_script("lane_server.py" if name == "serve.py" else name)


def entries(moves: str) -> dict:
    """The stand-in's entries of a ``BENCHMARK.json``, list by list."""
    return {
        "configs": [{"name": CONFIG["name"], "source": "perfbench/tests/standin", "reduced": [],
                     "file": f"perfbench/configs/{CONFIG['name']}.json", "why": "a toy"}],
        "workloads": [{"name": n, "config": CONFIG["name"], "traffic": n, "chips": 1,
                       "why": "the token entry's plumbing"} for n in MIXES],
        "per_layer": [{"name": n, "unit": u, "better": b, "source": s, "layer": "the lane",
                       "moves": moves, "workloads": list(MIXES)}
                      for n, (u, b, s, _spec) in LAYER.items()],
    }


def build(root: str, appended: bool = False) -> None:
    """The scratch tree under ``root``: files added, none edited."""
    bench = os.path.join(root, "perfbench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    children = os.path.join(bench, "children")
    shutil.copy(os.path.join(HERE, "lane_server.py"), children)
    shutil.copy(os.path.join(HERE, "lane_artifact.py"), children)
    shutil.copy(os.path.join(HERE, "byte_lm.py"), os.path.join(bench, "reference"))
    with open(os.path.join(bench, "configs", CONFIG["name"] + ".json"), "w") as f:
        json.dump(CONFIG, f)
    for name, mix in MIXES.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    for name, (*_entry, spec) in LAYER.items():
        with open(os.path.join(bench, "layer_metrics", name + ".json"), "w") as f:
            json.dump(spec, f)
    if appended:
        with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
            manifest = json.load(f)
        for key, new in entries("latency_p50_ms").items():
            manifest[key] += new
    else:
        manifest = {
            "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
            "run_seconds": 20, **entries("output_tokens_per_s"),
            "end_to_end": [{"name": n, "unit": u, "better": b, "bound": 0.1,
                            "source": "host_clock"}
                           for n, (u, b) in dict(E2E, setup_s=("s", "lower")).items()],
        }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--root", required=True)
    p.add_argument("--appended", action="store_true")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    if not os.path.exists(os.path.join(args.root, "BENCHMARK.json")):
        build(args.root, args.appended)
    manifest = manifest_lib.Manifest(args.root)
    manifest.validate()
    run = LaneRun(manifest, manifest.cell(args.workload), args.seed, args.seconds,
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
