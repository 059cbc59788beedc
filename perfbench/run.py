#!/usr/bin/env python3
"""perfbench/run.py: one run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A parent that never imports jax (one process per chip).  It

1. starts a host-only child that writes the cell's artifact from the seed
   (the configuration's ``artifact_child``) and meanwhile makes the
   traffic's inputs,
2. boots the model server as the program does (``python -m
   ...serving.model_server --platform tpu`` with the entry's arguments and
   otherwise default flags, through ``children/serve.py``, which adds a side
   port for ``memory_stats()``), and whatever the entry puts in front of it,
3. warms every shape, round after round until the rounds are steady, leads
   in with the cell's own traffic and then measures for ``--seconds``; with
   ``--trace 1`` a profiler trace of the serving process is taken inside
   the window,
4. stops every child (exit code 0 required), then runs the plain reference
   (the configuration's ``reference_child``) on the freed chip over the
   run's own inputs and compares what the window answered with it,
5. prints the contract's line.

It fails -- no fallback, no result line -- if the server reports another
platform than asked, a status page the entry refuses, a compile request
inside the window, or a ``device_kind`` that ``peaks.json`` does not hold.

What a run knows of one wire of the system -- a modality -- sits in
``entries/<traffic["entry"]>.py``, found by that name.  An entry is a
module with, and this file asks it for nothing else:

- ``GENERATORS``: the traffic file's ``generator`` values it takes;
- ``make_inputs(run)``: the inputs from the seed;
- ``server_args(run)`` -> (arguments, environment) beyond the common ones,
  and ``check_status(run, page, at_boot)`` for ``GET /v1/models``;
- ``boot_front(run)`` / ``stop_front(run)``: the tiers before the server;
- ``warming(run)``: a context manager that yields ``one_round(k)``;
- ``drive(run, on_window_start)``: lead-in and window; leaves
  ``run.outcomes``, ``run.t_zero`` and ``run.window``;
- ``reference_args(run)`` (called once the window has closed) and
  ``REFERENCE_OUT``, the name of the file the reference child writes;
- ``compare(run)`` -> (the ``compared`` block, each number beside its
  limit, and the indices of the outcomes that are good);
- ``QUANTITIES`` {name: unit} and ``quantities(run)`` -> {name: value}: the
  end-to-end quantities it can give, ``latency_p50_ms`` among them: a
  request's time from its send (closed loop) or its due time (open loop) to
  its last byte, which every cell reports (``manifest.py``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import threading
import time

T0 = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench import manifest as manifest_lib  # noqa: E402
from perfbench import procs  # noqa: E402
from perfbench.procs import RunFailure  # noqa: E402

PACKAGE = "kubernetes_deep_learning_tpu"   # the program under test
TIME_LIMIT_S = 1150.0     # a first run may take 1200 s, compilation included
FAILED_LATENCY_MS = 120_000.0   # a request with no answer, in a percentile


def load_reader(bench_dir: str, name: str):
    return manifest_lib.load_module(bench_dir, "readers", name)


def percentile(sorted_values: list[float], pct: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    k = max(0, math.ceil(pct / 100.0 * len(sorted_values)) - 1)
    return sorted_values[k]


class CellRun:
    """One run: its children, directories and what it gathered."""

    FAILED_LATENCY_MS = FAILED_LATENCY_MS
    percentile = staticmethod(percentile)

    def __init__(self, manifest, cell, seed: int, seconds: float, trace: bool,
                 platform: str = "tpu", work_root: str | None = None):
        self.manifest, self.cell = manifest, cell
        self.seed, self.seconds, self.trace, self.platform = seed, seconds, trace, platform
        self.root = ROOT                    # the checkout: children run from it
        self.bench_dir = manifest.bench_dir
        self.cache_root = work_root or os.path.join(self.root, ".perfbench_cache")
        self.work = os.path.join(self.cache_root, "run", cell.name)
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        env["PYTHONUNBUFFERED"] = "1"
        env["PYTHONPATH"] = self.root + os.pathsep + env.get("PYTHONPATH", "")
        if platform != "tpu":
            env["JAX_PLATFORMS"] = platform
        self.env = env
        self.host_env = dict(env, JAX_PLATFORMS="cpu")
        self.children = procs.Children(self.root, os.path.join(self.work, "logs"),
                                       env, TIME_LIMIT_S)
        self.config, self.mix = cell.config, cell.traffic
        self.entry = manifest.entry(cell)
        self.model = self.config["served_name"]
        self.compile_cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
                              or os.path.join(self.root, ".jax_cache"))
        self.server = self.server_proc = self.host_proc = None
        self.tiers: dict[str, str] = {}   # tier name -> base URL, for /metrics and spans

    def child_script(self, name: str) -> str:
        return os.path.join(self.bench_dir, "children", name)

    # --- set-up ------------------------------------------------------------

    def start_artifact(self):
        return self.children.spawn("artifact", [
            self.child_script(self.config.get("artifact_child", "make_artifact.py")),
            "--config", self.config_path(), "--seed", str(self.seed),
            "--out", os.path.join(self.work, "models"),
            "--module-cache", os.path.join(self.cache_root, "modules"),
            "--platform", self.platform,
        ], env=self.host_env)

    def config_path(self) -> str:
        return os.path.join(self.manifest.root,
                            self.manifest.configs[self.cell.config_name]["file"])

    def boot_server(self) -> dict:
        port, side = procs.free_port(), procs.free_port()
        entry_argv, entry_env = self.entry.server_args(self)
        self.server_proc = self.children.spawn("server", [
            self.child_script("serve.py"), "--models", os.path.join(self.work, "models"),
            "--port", str(port), *entry_argv, "--platform", self.platform,
            "--profile-dir", os.path.join(self.work, "program-traces"),
        ], env=dict(self.env, **entry_env, PERFBENCH_DEVICE_PORT=str(side),
                    PERFBENCH_TRACE_DIR=os.path.join(self.work, "traces")))
        self.server, self.side = f"http://127.0.0.1:{port}", f"http://127.0.0.1:{side}"
        self.tiers["server"] = self.server
        body = procs.wait_ready(self.children, "server", self.server_proc, self.server)
        if body.strip() != "ready":
            raise RunFailure(f"/readyz says {body!r}, not 'ready'")
        page = procs.get_json(self.server, "/v1/models")
        device = procs.get_json(self.side, "/device")
        if device["platform"] != self.platform:
            raise RunFailure(f"server runs on {device['platform']!r}, not {self.platform!r}")
        if device["count"] < self.cell.chips:
            raise RunFailure(f"{device['count']} devices, the cell needs {self.cell.chips}")
        self.entry.check_status(self, page, True)
        if self.platform == "tpu":
            self.peaks = manifest_lib.load_peaks(self.bench_dir, device["kind"])
        else:  # a rehearsal has no peak; device metrics are not reported
            self.peaks = None
        return page.get(self.model)

    def warm(self) -> None:
        """Every shape the traffic uses, dispatched through the live path
        round after round (a round is the entry's), for ``min_seconds`` at
        the least and until ``steady_rounds`` rounds in a row take no longer
        than ``steady_within`` times the quickest so far.  A shape's first
        dispatches run slower than its later ones -- programs loaded from
        the compile cache, buffers allocated for the first time, whatever
        the program still does lazily -- and the window has to start on
        the steady rate; a boot that never settles is reported and goes on
        after ``settle_timeout_s``.  No rule names a part of the program."""
        warm = self.mix["warm"]
        deadline = time.monotonic() + float(warm["settle_timeout_s"])
        times: list[float] = []
        with self.entry.warming(self) as one_round:
            while True:
                t = time.monotonic()
                one_round(len(times))
                times.append(time.monotonic() - t)
                steady = int(warm["steady_rounds"])
                if (sum(times) >= float(warm["min_seconds"]) and len(times) >= steady
                        and max(times[-steady:]) <= float(warm["steady_within"]) * min(times)):
                    break
                if time.monotonic() > deadline:
                    print(f"warm-up: rounds took {[round(x, 3) for x in times]} s and did "
                          "not settle; going on", file=sys.stderr)
                    break
        self.warm_rounds = times

    # --- the window ----------------------------------------------------------

    def snapshot(self) -> dict:
        return {tier: procs.parse_metrics(procs.scrape(base))
                for tier, base in self.tiers.items()}

    def drive(self) -> None:
        self.before = self.trace_reply = self.trace_error = None
        threads = []

        def on_window_start(t_zero: float) -> None:
            self.setup_s = t_zero - T0

            def at_start():
                self.before = self.snapshot()
            threads.append(threading.Thread(target=at_start))
            if self.trace:
                def tracing():
                    try:
                        self.take_trace(t_zero)
                    except (RunFailure, OSError) as e:
                        self.trace_error = e
                threads.append(threading.Thread(target=tracing))
            for t in threads:
                t.start()

        self.entry.drive(self, on_window_start)
        for t in threads:
            t.join(timeout=180)
        if self.trace_error is not None or (self.trace and self.trace_reply is None):
            raise RunFailure(f"no profiler trace: {self.trace_error!r}")
        self.after = self.snapshot()

    def take_trace(self, t_zero: float) -> None:
        offset, seconds = float(self.mix["trace_offset_s"]), float(self.mix["trace_seconds"])
        seconds = min(seconds, max(0.5, self.seconds - offset - 0.5))
        time.sleep(max(0.0, t_zero + offset - time.monotonic()))
        self.trace_reply = procs.get_json(self.side, f"/trace?seconds={seconds}",
                                          timeout=seconds + 170)

    def sample_spans(self) -> list[list[dict]]:
        """Span lists of a seeded sample of the window's last requests (each
        tier keeps its newest few hundred traces)."""
        import numpy as np

        done = [o for o in self.window if o.status == 200]
        recent = sorted(done, key=lambda o: o.done_s)[-int(self.mix["span_recent"]):]
        rng = np.random.default_rng([self.seed, 0x59A7])
        picks = rng.permutation(len(recent))[:int(self.mix["span_sample"])]
        out = []
        for i in picks:
            spans = []
            for tier in reversed(self.tiers.values()):   # the front first
                status, body = procs.http_get(tier, f"/debug/trace/{recent[i].rid}")
                if status == 200:
                    spans += json.loads(body).get("spans", [])
            if spans:
                out.append(spans)
        return out

    def after_window(self) -> None:
        compiles = (self.after["server"].get("kdlt_xla_compile_requests_total", 0.0)
                    - self.compiles_at_ready)
        if compiles:
            raise RunFailure(f"{compiles:.0f} compile requests after warm-up")
        self.spans = self.sample_spans() if self.trace else []
        self.device = procs.get_json(self.side, "/device")
        self.entry.check_status(self, procs.get_json(self.server, "/v1/models"), False)

    def stop_servers(self) -> None:
        self.entry.stop_front(self)
        self.children.stop("server", self.server_proc)

    # --- after the window: reference and trace -------------------------------------

    def reference_and_trace(self) -> None:
        ref_out = os.path.join(self.work, self.entry.REFERENCE_OUT)
        argv = [self.child_script(self.config.get("reference_child", "reference.py")),
                "--config", self.config_path(), "--seed", str(self.seed), "--out", ref_out,
                "--cache-dir", self.compile_cache, *self.entry.reference_args(self)]
        ref = self.children.spawn("reference", argv)
        reducer = None
        if self.trace:
            reduced = os.path.join(self.work, "trace.json")
            reducer = self.children.spawn("reduce_trace", [
                os.path.join(self.bench_dir, "reduce_trace.py"), self.trace_reply["trace_dir"],
                "--out", reduced], env=self.host_env)
        self.children.wait_exit("reference", ref)
        import numpy as np

        self.reference = np.load(ref_out)
        self.trace_data = None
        if reducer is not None:
            self.children.wait_exit("reduce_trace", reducer)
            with open(reduced) as f:
                self.trace_data = json.load(f)

    # --- the verdict and the line ---------------------------------------------------

    def compare(self) -> dict:
        """What the window answered against the reference, by the entry's
        rule.  Returns {name: {"value", "limit" or "limit_at_least"}}."""
        compared, self.good = self.entry.compare(self)
        return compared

    def end_to_end(self) -> dict:
        values = dict(self.entry.quantities(self), setup_s=self.setup_s)
        out = {}
        for m in self.cell.end_to_end:
            kind = m["name"].split(".")[0]   # Manifest.entry checked it and its unit
            if kind not in values:           # a window that answered nothing
                raise RunFailure(f"the window gives no value for {m['name']!r}")
            out[m["name"]] = {"value": values[kind], "unit": m["unit"]}
        return out

    def per_layer(self) -> dict:
        run = {
            "before": self.before or {}, "after": self.after, "spans": self.spans,
            "trace": self.trace_data, "outcomes": self.window, "seconds": self.seconds,
            "config": self.config, "traffic": self.mix, "peaks": self.peaks,
            "chips": self.cell.chips, "bench_dir": self.bench_dir,
        }
        out = {}
        for m, spec in self.cell.per_layer:
            reader = load_reader(self.bench_dir, spec["reader"])
            value = reader.read(spec, run)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def mark(self, phase: str) -> None:
        print(f"[{time.monotonic() - T0:7.1f}s] {phase}", file=sys.stderr, flush=True)

    def prepare(self) -> None:
        """The artifact from the seed (a child) and, beside it, the inputs."""
        self.limits = self.config["limits"]
        artifact = self.start_artifact()
        self.entry.make_inputs(self)
        self.mark("inputs made")
        self.children.wait_exit("artifact", artifact)
        self.mark("artifact written")

    def boot(self) -> None:
        """Servers up and warm; from here a compile request is a failure."""
        self.boot_server()
        self.mark("server ready")
        self.entry.boot_front(self)
        self.warm()
        self.mark(f"warm: rounds of {[round(t, 3) for t in self.warm_rounds]} s")
        self.compiles_at_ready = procs.parse_metrics(procs.scrape(self.server)).get(
            "kdlt_xla_compile_requests_total", 0.0)

    def run(self) -> dict:
        self.prepare()
        self.boot()
        self.drive()
        self.mark("window closed and drained")
        self.after_window()
        self.stop_servers()
        self.mark("servers stopped")
        self.reference_and_trace()
        self.mark("reference and trace read")
        compared = self.compare()
        correct = all(c["value"] <= c["limit"] if "limit" in c
                      else c["value"] >= c["limit_at_least"] for c in compared.values())
        metrics = self.end_to_end()   # computed in both kinds of run, printed in one
        device = {"platform": self.device["platform"], "kind": self.device["kind"],
                  "count": self.device["count"],
                  "memory_peak_bytes": self.device["memory_peak_bytes"]}
        print(f"device memory: {json.dumps(self.device['memory_stats'])}", file=sys.stderr)
        line = {"correct": bool(correct), "attempted": len(self.window),
                "failed": sum(1 for o in self.window if o.index not in self.good),
                "metrics": metrics, "device": device}
        if self.trace:
            line["metrics"] = self.per_layer()
            if self.trace_data and self.trace_data.get("devices"):
                from perfbench import reduce_trace

                device["busy_s"] = self.trace_data["busy_s"]
                device["window_s"] = self.trace_data["window_s"]
                line["breakdown"] = reduce_trace.breakdown(self.trace_data)
        line["compared"] = compared
        return line


def report(line: dict) -> None:
    parts = []
    for name, c in line["compared"].items():
        limit = c.get("limit", c.get("limit_at_least"))
        word = "limit" if "limit" in c else "at_least"
        parts.append(f"{name}={c['value']:.6g} {word}={limit:.6g}")
    print(f"correct={line['correct']}: " + "; ".join(parts), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, PACKAGE)):
        print(f"perfbench: no {PACKAGE}/ beside perfbench/: nothing to measure",
              file=sys.stderr)
        return 2
    try:
        manifest = manifest_lib.Manifest(ROOT)
        cell = manifest.cell(args.workload)
        run = CellRun(manifest, cell, args.seed, args.seconds, bool(args.trace))
    except manifest_lib.ManifestError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        line = run.run()
    except (RunFailure, manifest_lib.ManifestError) as e:
        print(f"perfbench: FAILED after {time.monotonic() - T0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        run.children.kill_all()
        # the run's weights, pictures and trace are made anew from the seed
        # every time: only the logs and the reduced trace stay behind
        for name in ("models", "pool", "traces", "program-traces"):
            shutil.rmtree(os.path.join(run.work, name), ignore_errors=True)
    print(f"setup {run.setup_s:.1f}s, total {time.monotonic() - T0:.1f}s", file=sys.stderr)
    report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
