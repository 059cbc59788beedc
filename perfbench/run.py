#!/usr/bin/env python3
"""perfbench/run.py: one run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A parent that never imports jax (one process per chip).  It

1. starts a host-only child that writes the cell's artifact from the seed
   (``children/make_artifact.py``) and meanwhile makes the traffic's
   pictures,
2. boots the model server as the program does (``python -m
   ...serving.model_server --platform tpu`` with the traffic's buckets and
   otherwise default flags, through ``children/serve.py``, which adds a side
   port for ``memory_stats()``), and the gateway with default flags where
   the traffic enters there,
3. warms every bucket, waits for the program's per-bucket bookkeeping to
   settle, leads in with the cell's own traffic and then measures for
   ``--seconds``; with ``--trace 1`` a profiler trace of the serving process
   is taken inside the window,
4. stops every child (exit code 0 required), then runs the plain reference
   on the freed chip over the run's own pictures and compares every answer
   of the window with it,
5. prints the contract's line.

It fails -- no fallback, no result line -- if the server reports another
platform than asked, a degraded fused path, a compile request inside the
window, or a ``device_kind`` that ``peaks.json`` does not hold.
"""

from __future__ import annotations

import argparse
import importlib.util
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
from perfbench import pictures, procs, traffic  # noqa: E402
from perfbench.procs import RunFailure  # noqa: E402

PACKAGE = "kubernetes_deep_learning_tpu"
TIME_LIMIT_S = 1150.0     # a first run may take 1200 s, compilation included
FAILED_LATENCY_MS = 120_000.0   # a request with no answer, in a percentile


def load_reader(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "readers", name + ".py")
    if not os.path.exists(path):
        raise manifest_lib.ManifestError(f"no reader perfbench/readers/{name}.py")
    spec = importlib.util.spec_from_file_location(f"perfbench_reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def percentile(sorted_values: list[float], pct: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    k = max(0, math.ceil(pct / 100.0 * len(sorted_values)) - 1)
    return sorted_values[k]


class CellRun:
    """One run: its children, directories and what it gathered."""

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
        self.model = self.config["served_name"]
        self.compile_cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
                              or os.path.join(self.root, ".jax_cache"))
        self.server = self.gateway = self.image_host = None
        self.server_proc = self.gateway_proc = self.host_proc = None

    def child_script(self, name: str) -> str:
        return os.path.join(HERE, "children", name)

    # --- set-up ------------------------------------------------------------

    def start_artifact(self):
        return self.children.spawn("artifact", [
            self.child_script("make_artifact.py"),
            "--config", self.config_path(), "--seed", str(self.seed),
            "--out", os.path.join(self.work, "models"),
            "--module-cache", os.path.join(self.cache_root, "modules"),
            "--platform", self.platform,
        ], env=self.host_env)

    def config_path(self) -> str:
        return os.path.join(self.manifest.root,
                            self.manifest.configs[self.cell.config_name]["file"])

    def make_inputs(self) -> None:
        """The traffic's pictures, and what the reference will read."""
        mix, shape = self.mix, tuple(self.config["input_shape"])
        if mix["entry"] == "gateway-url":
            pool_dir = os.path.join(self.work, "pool")
            os.makedirs(pool_dir)
            pool = pictures.encoded_pool(self.seed, mix["pictures"])
            for i, (fmt, data) in enumerate(pool):
                with open(os.path.join(pool_dir, f"{i:04d}.{fmt}"), "wb") as f:
                    f.write(data)
            self.pool_size = len(pool)
            self.reference_inputs = ["--inputs", pool_dir]
            port = procs.free_port()
            self.host_proc = self.children.spawn(
                "image_host", [self.child_script("image_host.py"), pool_dir, str(port)],
                env=self.host_env)
            self.image_host = f"http://127.0.0.1:{port}"
        elif mix["entry"] == "server-tensor":
            n, per = int(mix["pool"]), int(mix["images_per_request"])
            pool = pictures.tensor_pool(self.seed, n, shape)
            self.pool_size = n
            self.reference_inputs = ["--tensor-pool", str(n)]
            rows = traffic.balanced_rows(self.seed, n, per * int(mix["bodies"]))
            self.body_rows = [tuple(int(r) for r in rows[i * per:(i + 1) * per])
                              for i in range(int(mix["bodies"]))]
            self.bodies = [traffic.encode_tensor_body(pool[list(r)]) for r in self.body_rows]
        else:
            raise manifest_lib.ManifestError(f"unknown entry {mix['entry']!r}")

    def boot_server(self) -> dict:
        port, side = procs.free_port(), procs.free_port()
        buckets = ",".join(str(b) for b in self.mix["server_buckets"])
        self.server_proc = self.children.spawn("server", [
            self.child_script("serve.py"), "--models", os.path.join(self.work, "models"),
            "--port", str(port), "--buckets", buckets, "--platform", self.platform,
            "--profile-dir", os.path.join(self.work, "program-traces"),
        ], env=dict(self.env, PERFBENCH_DEVICE_PORT=str(side),
                    PERFBENCH_TRACE_DIR=os.path.join(self.work, "traces")))
        self.server, self.side = f"http://127.0.0.1:{port}", f"http://127.0.0.1:{side}"
        body = procs.wait_ready(self.children, "server", self.server_proc, self.server)
        if body.strip() != "ready":
            raise RunFailure(f"/readyz says {body!r}, not 'ready'")
        st = procs.get_json(self.server, "/v1/models")[self.model]
        device = procs.get_json(self.side, "/device")
        if st["platform"] != self.platform or device["platform"] != self.platform:
            raise RunFailure(f"server runs on {st['platform']!r}, not {self.platform!r}")
        if device["count"] < self.cell.chips:
            raise RunFailure(f"{device['count']} devices, the cell needs {self.cell.chips}")
        if st["fast_degraded"]:
            raise RunFailure("the fused path degraded at warm-up")
        if bool(st["fast_engaged"]) != bool(self.config["fast_path"]):
            raise RunFailure(f"fast_engaged is {st['fast_engaged']}, the configuration "
                             f"states fast_path {self.config['fast_path']}")
        if list(st["buckets"]) != list(self.mix["server_buckets"]):
            raise RunFailure(f"server buckets {st['buckets']} != {self.mix['server_buckets']}")
        if self.platform == "tpu":
            self.peaks = manifest_lib.load_peaks(self.bench_dir, device["kind"])
        else:  # a rehearsal has no peak; device metrics are not reported
            self.peaks = None
        self.labels = list(st["labels"])
        return st

    def boot_gateway(self) -> None:
        port = procs.free_port()
        self.gateway_proc = self.children.spawn("gateway", [
            "-m", f"{PACKAGE}.serving.gateway", "--serving-host",
            self.server.split("//")[1], "--port", str(port), "--model", self.model,
        ], env=self.host_env)
        self.gateway = f"http://127.0.0.1:{port}"
        procs.wait_ready(self.children, "gateway", self.gateway_proc, self.gateway)

    def warm(self) -> None:
        """Each bucket dispatched through the live path, round after round,
        for ``min_seconds`` at the least and until a round takes no longer
        than the quickest so far: whatever the program does at a bucket's
        first dispatches (the MFU accountant lowers the whole graph on a
        background thread, 4-5 s a bucket, one bucket after another) has
        then run its course.  No rule names a part of the program."""
        import numpy as np

        warm = self.mix["warm"]
        shape = tuple(self.config["input_shape"])
        buckets = [int(b) for b in self.mix["server_buckets"]]
        entry = traffic.ServerTensor(self.server, self.model, [
            traffic.encode_tensor_body(np.zeros((b, *shape), np.uint8)) for b in buckets])
        deadline = time.monotonic() + float(warm["settle_timeout_s"])
        times: list[float] = []
        conn_box = [None]
        while True:
            t = time.monotonic()
            for i in range(len(buckets)):
                o = traffic.Outcome(i, f"pbwarm{self.seed}-{len(times)}-{i}", 0.0, ())
                entry.send(conn_box, o, 120.0, i)
                if o.status != 200:
                    raise RunFailure(f"warm-up of bucket {buckets[i]} -> {o.status}: {o.error}")
            times.append(time.monotonic() - t)
            steady = int(warm["steady_rounds"])
            if (sum(times) >= float(warm["min_seconds"]) and len(times) >= steady
                    and max(times[-steady:]) <= float(warm["steady_within"]) * min(times)):
                break
            if time.monotonic() > deadline:
                print(f"warm-up: rounds took {[round(x, 3) for x in times]} s and did not "
                      "settle; going on", file=sys.stderr)
                break
        if conn_box[0] is not None:
            conn_box[0].close()
        self.warm_rounds = times

    # --- the window ----------------------------------------------------------

    def snapshot(self) -> dict:
        out = {"server": procs.parse_metrics(procs.scrape(self.server))}
        if self.gateway:
            out["gateway"] = procs.parse_metrics(procs.scrape(self.gateway))
        return out

    def drive(self) -> None:
        mix = self.mix
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

        lead = float(mix["lead_in_s"])
        if mix["generator"] == "open-poisson":
            entry = traffic.GatewayUrl(self.gateway, self.image_host, self.labels, self.seed)
            self.outcomes, self.t_zero = traffic.run_open(
                entry, mix, self.seed, lead, self.seconds, self.pool_size, on_window_start)
        elif mix["generator"] == "closed":
            entry = traffic.ServerTensor(self.server, self.model, self.bodies)
            self.outcomes, self.t_zero = traffic.run_closed(
                entry, mix, self.seed, lead, self.seconds, self.body_rows, on_window_start)
        else:
            raise manifest_lib.ManifestError(f"unknown generator {mix['generator']!r}")
        for t in threads:
            t.join(timeout=180)
        if self.trace_error is not None or (self.trace and self.trace_reply is None):
            raise RunFailure(f"no profiler trace: {self.trace_error!r}")
        self.after = self.snapshot()
        # open loop: the requests due in the window; closed loop: those
        # answered after its start, whenever they were sent
        closed = mix["generator"] == "closed"
        self.window = [o for o in self.outcomes if (o.done_s if closed else o.due_s) >= 0]

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
            for tier in (self.gateway, self.server):
                if tier is None:
                    continue
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
        st = procs.get_json(self.server, "/v1/models")[self.model]
        if st["fast_degraded"]:
            raise RunFailure("the fused path degraded during the window")

    def stop_servers(self) -> None:
        if self.gateway_proc is not None:
            self.children.stop("gateway", self.gateway_proc)
        self.children.stop("server", self.server_proc)
        if self.host_proc is not None:
            self.children.stop("image_host", self.host_proc)

    # --- after the window: reference and trace -------------------------------------

    def reference_and_trace(self) -> None:
        ref_out = os.path.join(self.work, "reference.npy")
        argv = [self.child_script("reference.py"), "--config", self.config_path(),
                "--params", os.path.join(self.work, "models", self.model, "1", "params.msgpack"),
                "--seed", str(self.seed), "--out", ref_out,
                "--cache-dir", self.compile_cache, *self.reference_inputs]
        ref = self.children.spawn("reference", argv)
        reducer = None
        if self.trace:
            reduced = os.path.join(self.work, "trace.json")
            reducer = self.children.spawn("reduce_trace", [
                os.path.join(HERE, "reduce_trace.py"), self.trace_reply["trace_dir"],
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
        """Every answer of the window against the reference's row for the
        same picture.  Returns {name: {"value", "limit"}}."""
        import numpy as np

        ref = self.reference
        scale = float(np.abs(ref).max())
        worst, rows, wrong, unanswered = 0.0, 0, 0, 0
        self.good = set()
        for o in self.window:
            if o.status == 0:
                unanswered += 1
                continue
            if o.status != 200:
                continue            # refused or shed: failed, not wrong
            want = ref[list(o.rows)]
            if o.scores is None or o.scores.shape != want.shape \
                    or not np.isfinite(o.scores).all():
                wrong += 1
                continue
            err = float(np.abs(o.scores.astype(np.float64) - want).max()) / scale
            worst, rows = max(worst, err), rows + len(o.rows)
            if err <= self.limits["logit_err"]:
                self.good.add(o.index)
        return {
            "logit_err": {"value": worst, "limit": self.limits["logit_err"]},
            "wrong_answers": {"value": wrong, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0},
            "rows_compared": {"value": rows, "limit_at_least": 1},
        }

    def end_to_end(self) -> dict:
        w, s = self.window, self.seconds
        values = {"setup_s": self.setup_s}
        images = sum(len(o.rows) for o in w if o.index in self.good and o.done_s <= s)
        values["images_per_s"] = images / s
        lat = sorted((1000.0 * (o.done_s - o.due_s) if o.index in self.good
                      else FAILED_LATENCY_MS) for o in w)
        if lat:
            values["latency_p50_ms"] = percentile(lat, 50)
            values["latency_p95_ms"] = percentile(lat, 95)
        out = {}
        for m in self.cell.end_to_end:
            # which quantity: the name up to its first dot; what follows
            # only tells cells apart that are held to different bounds
            kind = m["name"].split(".")[0]
            if kind not in values:
                raise manifest_lib.ManifestError(
                    f"the harness computes no end-to-end metric {m['name']!r}")
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
        """The artifact from the seed (a child) and, beside it, the pictures."""
        self.limits = self.config["limits"]
        artifact = self.start_artifact()
        self.make_inputs()
        self.mark("inputs made")
        self.children.wait_exit("artifact", artifact)
        self.mark("artifact written")

    def boot(self) -> None:
        """Servers up and warm; from here a compile request is a failure."""
        self.boot_server()
        self.mark("server ready")
        if self.mix["entry"] == "gateway-url":
            self.boot_gateway()
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
        correct = (compared["logit_err"]["value"] <= compared["logit_err"]["limit"]
                   and compared["wrong_answers"]["value"] == 0
                   and compared["unanswered"]["value"] == 0
                   and compared["rows_compared"]["value"] >= 1)
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
    except manifest_lib.ManifestError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    run = CellRun(manifest, cell, args.seed, args.seconds, bool(args.trace))
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
