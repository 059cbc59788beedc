#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the serving path starts on the chip.

Drives the system the way GUIDE.md sections 1-2 tell a user to, at the full
width of the flagship (clothing-model: Xception, 299x299x3, bf16 compute,
seed-0 random weights, fused Pallas kernels on), through the normal entry
points started as children from the checkout root:

    python -m kubernetes_deep_learning_tpu.export.exporter   (exits first)
    python -m kubernetes_deep_learning_tpu.serving.model_server --platform tpu
                                        (the ONE process that holds the chip)
    python -m kubernetes_deep_learning_tpu.serving.gateway   (host-only)

then sends a few dozen requests (single images by URL over the raw-bytes
ingest wire, a concurrent burst, client-batched msgpack predicts of 16 and
32, one token stream), checks every answer against the float32 flax graph
computed once by a JAX_PLATFORMS=cpu child on the same variables and
images, stops the server, boots it a second time and requires that warm-up
added no entry to the compile cache.

One process per chip: THIS process never imports jax.  Nothing here falls
back: a child that dies, a timeout, a non-200, a degraded fused path or a
platform other than the TPU ends the run with a non-zero exit and no result
line.  On success the last two lines of stdout are the run's summary (one
JSON object: versions, boot seconds, compile counts, request counts, ...)
and then the result, one JSON object with exactly these keys, the device as
JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python3 chip_smoke.py                      one chip (what the driver runs)
    python3 chip_smoke.py --data-parallel 4    four chips, batch sharded
    python3 chip_smoke.py --rehearse-on-cpu    same control flow, 96x96, CPU
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from http.server import SimpleHTTPRequestHandler, ThreadingHTTPServer

ROOT = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "kubernetes_deep_learning_tpu"
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

TIME_LIMIT_S = 1100.0   # the driver allows 1200 s, compilation included
BUCKETS = (1, 16, 32)   # sublane-padded, native 16-image, chunked (2 x 16)
N_IMAGES = 32
N_SINGLE = 4            # sequential single-image /predict requests
N_BURST = 16            # concurrent single-image /predict requests
# Served bf16 logits vs the float32 flax graph: max abs difference over the
# batch, relative to the largest |reference logit|.  The fused path measured
# 0.4-0.9% on the v5e at seed-0 weights; 3% is ~3x that, and far below what a
# wrong kernel produces (a dropped block or a mis-tiled batch is O(100%)).
LOGIT_REL_TOL = 3e-2
DECODE_PROMPT = "the quick brown fox"
DECODE_TOKENS = 8

_PROBE = (
    "import json, importlib.metadata as md, jax, jaxlib\n"
    "d = jax.devices()\n"
    "try:\n    libtpu = md.version('libtpu')\n"
    "except md.PackageNotFoundError:\n    libtpu = None\n"
    "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind,"
    " 'count': len(d), 'jax': jax.__version__, 'jaxlib': jaxlib.__version__,"
    " 'libtpu': libtpu}))\n"
)


class SmokeFailure(Exception):
    """A phase failed; the run ends non-zero with no result line."""


# --- the CPU-pinned reference child -----------------------------------------


def reference_child(artifact_dir: str, images_dir: str, out_path: str) -> int:
    """float32 flax graph (fast=False) on the artifact's own variables and
    the run's own image files, decoded and resized exactly as the model tier
    does.  Runs with JAX_PLATFORMS=cpu, so it never needs the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.models import build_forward
    from kubernetes_deep_learning_tpu.ops import preprocess

    if jax.devices()[0].platform != "cpu":
        print("reference child must run on the CPU", file=sys.stderr)
        return 1
    artifact = art.load_artifact(artifact_dir)
    spec = artifact.spec
    names = sorted(os.listdir(images_dir))
    batch = np.stack([
        preprocess.preprocess_bytes(
            open(os.path.join(images_dir, n), "rb").read(),
            spec.input_shape[:2], filter=spec.resize_filter,
        )
        for n in names
    ])
    forward = jax.jit(build_forward(spec, dtype=jnp.float32, fast=False))
    logits = np.concatenate([
        np.asarray(forward(artifact.variables, batch[i : i + 8]))
        for i in range(0, len(batch), 8)
    ])
    with open(out_path, "w") as f:
        json.dump({"names": names, "logits": logits.tolist()}, f)
    return 0


# --- process and HTTP plumbing (parent; no jax) -----------------------------


class Run:
    """The run's children, clock and log directory."""

    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.t0 = time.monotonic()
        self.children: list[tuple[str, subprocess.Popen]] = []
        os.makedirs(OUT_DIR, exist_ok=True)
        self.env = dict(os.environ, PYTHONUNBUFFERED="1")
        if rehearsal:
            self.env["JAX_PLATFORMS"] = "cpu"

    def remaining(self) -> float:
        left = TIME_LIMIT_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise SmokeFailure(f"time limit of {TIME_LIMIT_S:.0f}s exceeded")
        return left

    def spawn(self, name: str, argv: list[str], env: dict | None = None):
        log = open(os.path.join(OUT_DIR, f"{name}.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=ROOT, env=env or self.env,
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )
        log.close()  # the child holds its own descriptor
        self.children.append((name, proc))
        return proc

    def log_tail(self, name: str, n: int = 25) -> str:
        try:
            with open(os.path.join(OUT_DIR, f"{name}.log"), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def wait_exit(self, name: str, proc: subprocess.Popen) -> None:
        try:
            rc = proc.wait(timeout=self.remaining())
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name} did not finish inside the time limit") from None
        if rc != 0:
            raise SmokeFailure(f"{name} exited rc={rc}:\n{self.log_tail(name)}")

    def stop(self, name: str, proc: subprocess.Popen, grace_s: float = 40.0) -> None:
        """SIGTERM (the servers drain and exit), then SIGKILL the group.  The
        chip is free for the next holder only once the process is gone."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=10)
                raise SmokeFailure(f"{name} ignored SIGTERM for {grace_s:.0f}s") from None
        if proc.returncode != 0:
            raise SmokeFailure(
                f"{name} did not exit cleanly on SIGTERM (rc={proc.returncode}):\n"
                f"{self.log_tail(name)}"
            )

    def kill_all(self) -> None:
        for _name, proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait(timeout=10)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(run: Run, name: str, proc: subprocess.Popen, url: str) -> str:
    """Poll ``url`` until 200; the child dying first is a failure."""
    import requests

    while True:
        run.remaining()
        if proc.poll() is not None:
            raise SmokeFailure(
                f"{name} died before ready (rc={proc.returncode}):\n"
                f"{run.log_tail(name)}"
            )
        try:
            r = requests.get(url, timeout=2)
            if r.status_code == 200:
                return r.text
        except requests.RequestException:
            pass
        time.sleep(0.5)


def metric(text: str, name: str) -> float:
    """Sum of every sample of ``name`` (all label sets) on a /metrics page."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and line[len(name) : len(name) + 1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    if not seen:
        raise SmokeFailure(f"metric {name} not on /metrics")
    return total


def cache_entries(path: str) -> set[str]:
    return {n for n in os.listdir(path) if n.endswith("-cache")}


def make_images(directory: str, n: int, seed: int = 0) -> list[str]:
    """n seeded images (smooth gradients plus rectangles, so JPEG has
    structure to encode), alternating PNG and JPEG, in varied sizes."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    os.makedirs(directory)
    names = []
    for i in range(n):
        h, w = int(rng.integers(200, 420)), int(rng.integers(200, 420))
        yy, xx = np.mgrid[0:h, 0:w]
        img = np.stack(
            [
                127.5 + 127.5 * np.sin(xx / rng.uniform(20, 90) + rng.uniform(0, 6))
                * np.cos(yy / rng.uniform(20, 90) + rng.uniform(0, 6))
                for _ in range(3)
            ],
            axis=-1,
        )
        for _ in range(4):
            y0, x0 = int(rng.integers(0, h - 40)), int(rng.integers(0, w - 40))
            img[y0 : y0 + int(rng.integers(20, 120)), x0 : x0 + int(rng.integers(20, 120))] = (
                rng.integers(0, 256, size=3)
            )
        ext = "png" if i % 2 == 0 else "jpg"
        name = f"img_{i:02d}.{ext}"
        Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
            os.path.join(directory, name), quality=92
        )
        names.append(name)
    return names


def serve_directory(directory: str) -> ThreadingHTTPServer:
    class Handler(SimpleHTTPRequestHandler):
        def __init__(self, *a, **kw):
            super().__init__(*a, directory=directory, **kw)

        def log_message(self, fmt, *args):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


# --- the checks --------------------------------------------------------------


def check(name: str, ok: bool, detail: str) -> None:
    print(f"check {name}: {'ok' if ok else 'FAIL'} ({detail})", flush=True)
    if not ok:
        raise SmokeFailure(f"{name}: {detail}")


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def check_scores(scores: dict, labels: list[str]) -> list[float]:
    if list(scores) != labels:
        raise SmokeFailure(f"labels {list(scores)} != {labels}")
    row = [float(scores[k]) for k in labels]
    if not all(math.isfinite(v) for v in row):
        raise SmokeFailure(f"non-finite scores {row}")
    return row


def boot_server(run: Run, name: str, models: str, platform: str, data_parallel: int):
    """Start the model server; returns (proc, base_url, boot_s, status, metrics)."""
    import requests

    port = free_port()
    argv = [
        "-m", f"{PACKAGE}.serving.model_server", "--models", models,
        "--port", str(port), "--buckets", ",".join(map(str, BUCKETS)),
        "--decode", "--platform", platform,
        # A 25 ms linger (default 2) so the burst below reliably coalesces;
        # this run checks that the path works, not how fast it answers.
        "--max-delay-ms", "25",
    ]
    if data_parallel:
        argv += ["--data-parallel", str(data_parallel)]
    t0 = time.monotonic()
    proc = run.spawn(name, argv)
    base = f"http://127.0.0.1:{port}"
    body = wait_ready(run, name, proc, f"{base}/readyz")
    boot_s = time.monotonic() - t0
    check(f"{name}.readyz", body == "ready", f"/readyz body {body!r}")
    status = requests.get(f"{base}/v1/models", timeout=10).json()
    metrics = requests.get(f"{base}/metrics", timeout=10).text
    return proc, base, boot_s, status, metrics


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="rehearse the same control flow on the CPU at the 96x96 spec "
        "(never automatic; the summary says rehearsal: true)",
    )
    p.add_argument(
        "--data-parallel", type=int, default=0,
        help="pass --data-parallel N to the model server (N chips, batch "
        "sharded); default: the one-chip path",
    )
    p.add_argument("--reference-child", nargs=3, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.reference_child:
        return reference_child(*args.reference_child)

    if not os.path.isdir(os.path.join(ROOT, PACKAGE)):
        print(f"chip_smoke: no {PACKAGE}/ next to chip_smoke.py", file=sys.stderr)
        return 2
    run = Run(args.rehearse_on_cpu)
    try:
        summary = smoke(run, args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED after {time.monotonic() - run.t0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        run.kill_all()
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, separators=(",", ":")), flush=True)
    # The result line: exactly these keys, and the last thing on stdout.
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


def smoke(run: Run, args) -> dict:
    import numpy as np
    import requests

    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.ops import preprocess
    from kubernetes_deep_learning_tpu.serving import protocol
    from kubernetes_deep_learning_tpu.utils import compilecache

    rehearsal = run.rehearsal
    want_platform = "cpu" if rehearsal else "tpu"
    model = "clothing-model-96" if rehearsal else "clothing-model"

    # --- 0. what does JAX find?  (a child: it exits before the next starts)
    probe = run.spawn("probe", ["-c", _PROBE])
    run.wait_exit("probe", probe)
    found = json.loads(
        [ln for ln in run.log_tail("probe").splitlines() if ln.startswith("{")][-1]
    )
    print(f"jax {found['jax']} jaxlib {found['jaxlib']} libtpu {found['libtpu']}; "
          f"JAX finds platform={found['platform']} kind={found['kind']!r} "
          f"count={found['count']}", flush=True)
    check("platform", found["platform"] == want_platform,
          f"JAX found {found['platform']!r} ({found['kind']}), need {want_platform!r}")
    if args.data_parallel:
        check("device_count", found["count"] >= args.data_parallel,
              f"{found['count']} devices for --data-parallel {args.data_parallel}")

    work = tempfile.mkdtemp(prefix="kdlt-chip-smoke-")
    models = os.path.join(work, "models")
    # The same resolution every entry point applies: JAX_COMPILATION_CACHE_DIR
    # where set, else <checkout>/.jax_cache (the module imports no jax).
    expected_cache = compilecache.resolve_cache_dir()
    check("cache_on", bool(expected_cache), f"compile cache at {expected_cache!r}")

    # --- 1. export (kdlt-export); exits before the server needs the chip
    t0 = time.monotonic()
    export = run.spawn("export", [
        "-m", f"{PACKAGE}.export.exporter", "--model", model, "--seed", "0",
        "--output", models,
    ])
    run.wait_exit("export", export)
    export_s = time.monotonic() - t0
    artifact_dir = os.path.join(models, model, "1")
    check("export", os.path.isdir(artifact_dir), f"{artifact_dir} in {export_s:.1f}s")

    # --- 2. images from a seed, served over local HTTP; the float32
    # reference starts now and runs on the CPU beside the server's boot
    images_dir = os.path.join(work, "images")
    names = make_images(images_dir, N_IMAGES)
    httpd = serve_directory(images_dir)
    image_base = f"http://127.0.0.1:{httpd.server_address[1]}"
    ref_path = os.path.join(work, "reference.json")
    reference = run.spawn(
        "reference",
        [os.path.join(ROOT, "chip_smoke.py"), "--reference-child",
         artifact_dir, images_dir, ref_path],
        env=dict(run.env, JAX_PLATFORMS="cpu"),
    )

    # --- 3. first boot: the one process that holds the chip
    server, base, cold_s, status, metrics_ready = boot_server(
        run, "server", models, want_platform, args.data_parallel
    )
    st = status[model]
    warm1 = {b: round(v["seconds"], 2) for b, v in st["warm"]["buckets"].items()}
    print(f"cold boot {cold_s:.1f}s; per-bucket warm seconds {warm1}; "
          f"batcher={st['batcher']} host_resize={st['host_resize']}", flush=True)
    check("server.platform", st["platform"] == want_platform,
          f"model server reports {st['platform']!r} {st['device_kind']!r} x{st['device_count']}")
    check("server.device", (st["device_kind"], st["device_count"]) == (found["kind"], found["count"]),
          f"server {st['device_kind']!r} x{st['device_count']} vs probe {found['kind']!r} x{found['count']}")
    check("ready", st["ready"] is True, f"buckets {st['buckets']}")
    check("fast_degraded", st["fast_degraded"] is False
          and metric(metrics_ready, "kdlt_engine_fast_degraded") == 0.0,
          "kdlt_engine_fast_degraded == 0")
    if not rehearsal:
        check("fast_engaged", st["fast_engaged"] is True,
              f"fused path in every bucket program {st['buckets']}")
        check("peak_tflops", bool(st["peak_tflops"]),
              f"{st['peak_tflops']} TFLOP/s for device_kind {st['device_kind']!r}")
    check("cache_dir", st["warm"]["cache_dir"] == expected_cache,
          f"server compiles against {st['warm']['cache_dir']!r}, expected {expected_cache!r}")
    after_boot1 = cache_entries(expected_cache)
    check("cache_filled", len(after_boot1) > 0, f"{len(after_boot1)} entries after first boot")
    if args.data_parallel:
        rows = st["batch_rows_per_device"] or {}
        per = st["buckets"][-1] // args.data_parallel
        check("sharding", st["sharding"] == "mesh-data"
              and st["mesh_shape"]["data"] == args.data_parallel
              and len(rows) == args.data_parallel
              and set(rows.values()) == {per},
              f"{st['sharding']} mesh {st['mesh_shape']}; a {st['buckets'][-1]}-image "
              f"batch lands {rows} rows per device")
    compiles_at_ready = metric(metrics_ready, "kdlt_xla_compile_requests_total")

    # --- 4. the gateway: host-only, started while the server holds the chip
    gw_port = free_port()
    gateway = run.spawn("gateway", [
        "-m", f"{PACKAGE}.serving.gateway", "--serving-host", base.split("//")[1],
        "--port", str(gw_port), "--model", model,
    ])
    gw = f"http://127.0.0.1:{gw_port}"
    wait_ready(run, "gateway", gateway, f"{gw}/readyz")
    print("gateway ready beside the chip-holding server", flush=True)

    run.wait_exit("reference", reference)
    with open(ref_path) as f:
        ref = json.load(f)
    ref_logits = dict(zip(ref["names"], ref["logits"]))

    # --- 5. traffic
    labels = list(st["labels"])
    check("labels", labels == list(get_spec("clothing-model").labels),
          f"the {len(labels)} clothing labels")
    requests_sent, worst = 0, 0.0

    def predict_url(name: str) -> tuple[list[float], str]:
        r = requests.post(f"{gw}/predict", json={"url": f"{image_base}/{name}"}, timeout=60)
        if r.status_code != 200:
            raise SmokeFailure(f"/predict {name} -> {r.status_code}: {r.text[:300]}")
        return check_scores(r.json(), labels), r.headers.get("X-Request-Id", "")

    rid = ""
    for name in names[:N_SINGLE]:
        row, rid = predict_url(name)
        worst = max(worst, rel_err(row, ref_logits[name]))
        requests_sent += 1
    check("single", worst <= LOGIT_REL_TOL,
          f"{N_SINGLE} URL requests (PNG+JPEG, raw-bytes wire), rel err {worst:.4f}")
    # Not a check: where one single-image request's time went, layer by
    # layer, from the spans both tiers already record (utils/trace.py).
    spans = []
    for tier in (gw, base):
        r = requests.get(f"{tier}/debug/trace/{rid}", timeout=10)
        spans += r.json().get("spans", []) if r.status_code == 200 else []
    trace_ms = {sp["name"]: sp["dur_ms"] for sp in sorted(spans, key=lambda sp: sp["start_s"])}
    print(f"one single-image request, span ms: {trace_ms}", flush=True)

    audit_url = f"{base}/debug/profile?audit=buckets"
    before = requests.get(audit_url, timeout=10).json()["models"][model]["buckets"]
    burst_names = names[N_SINGLE : N_SINGLE + N_BURST]
    with ThreadPoolExecutor(N_BURST) as pool:
        burst = [row for row, _rid in pool.map(predict_url, burst_names)]
    requests_sent += N_BURST
    burst_err = max(rel_err(row, ref_logits[n]) for row, n in zip(burst, burst_names))
    after = requests.get(audit_url, timeout=10).json()["models"][model]["buckets"]
    formed = {}
    for b in after:
        nb = after[b]["batches"] - before[b]["batches"]
        if nb:
            images = (after[b]["mean_admitted"] * after[b]["batches"]
                      - (before[b]["mean_admitted"] or 0) * before[b]["batches"])
            formed[b] = {"batches": nb, "images": round(images)}
    check("burst", burst_err <= LOGIT_REL_TOL
          and sum(f["images"] for f in formed.values()) == N_BURST
          and sum(f["batches"] for f in formed.values()) < N_BURST,
          f"{N_BURST} concurrent requests, batches formed per bucket {formed}, "
          f"rel err {burst_err:.4f}")
    worst = max(worst, burst_err)

    spec = requests.get(f"{base}/v1/models/{model}", timeout=10).json()
    tensors = np.stack([
        preprocess.preprocess_bytes(
            open(os.path.join(images_dir, n), "rb").read(),
            tuple(spec["input_shape"][:2]), filter=spec["resize_filter"],
        )
        for n in names
    ])
    direct = {}
    for n in (16, 32):
        r = requests.post(
            f"{base}/v1/models/{model}:predict",
            data=protocol.encode_predict_request(tensors[:n]),
            headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE}, timeout=120,
        )
        if r.status_code != 200:
            raise SmokeFailure(f":predict batch {n} -> {r.status_code}: {r.text[:300]}")
        logits, got_labels = protocol.decode_predict_response(
            r.content, r.headers["Content-Type"]
        )
        ok_shape = logits.shape == (n, len(labels)) and got_labels == labels
        err = rel_err(logits, [ref_logits[x] for x in names[:n]])
        check(f"batch{n}", ok_shape and bool(np.isfinite(logits).all())
              and err <= LOGIT_REL_TOL,
              f"client-batched msgpack :predict of {n}, shape {logits.shape}, rel err {err:.4f}")
        worst = max(worst, err)
        direct[n] = logits
        requests_sent += 1

    r = requests.post(
        f"{gw}/generate",
        json={"prompt": DECODE_PROMPT, "max_new_tokens": DECODE_TOKENS, "stream": True},
        timeout=120,
    )
    events = protocol.parse_sse_events(r.content) if r.status_code == 200 else []
    tokens = [e for e in events if "token" in e]
    done = [e for e in events if e.get("done")]
    check("generate", r.status_code == 200 and len(tokens) == DECODE_TOKENS
          and len(done) == 1 and done[0]["tokens"] == DECODE_TOKENS,
          f"/generate -> {r.status_code}, {len(tokens)} token events, "
          f"done {done[0] if done else None}")
    requests_sent += 1

    # --- 6. what the two tiers counted
    metrics_end = requests.get(f"{base}/metrics", timeout=10).text
    gw_metrics = requests.get(f"{gw}/metrics", timeout=10).text
    new_compiles = metric(metrics_end, "kdlt_xla_compile_requests_total") - compiles_at_ready
    check("no_compile_after_ready", new_compiles == 0,
          f"{new_compiles:.0f} compile requests after /readyz "
          f"({compiles_at_ready:.0f} during boot)")
    check("ingest_wire", metric(gw_metrics, "kdlt_ingest_bytes_requests_total") >= N_SINGLE + N_BURST
          and metric(metrics_end, "kdlt_ingest_decoded_images_total") >= N_SINGLE + N_BURST,
          "every URL request rode the raw-bytes wire and was decoded at the model tier")
    failures = metric(metrics_end, "kdlt_server_errors_total") + metric(gw_metrics, "kdlt_gateway_errors_total")
    check("failures", failures == 0, f"{requests_sent} requests, {failures:.0f} failures")
    check("fast_degraded_end", metric(metrics_end, "kdlt_engine_fast_degraded") == 0.0,
          "still 0 after traffic")
    boot1_counts = {
        k: int(metric(metrics_end, f"kdlt_xla_compile_{k}_total"))
        for k in ("requests", "cache_hits", "cache_writes")
    }

    run.stop("gateway", gateway)
    run.stop("server", server)
    before_boot2 = cache_entries(expected_cache)

    # --- 7. second boot: the cache path is stable and the keys repeat
    server2, base2, warm_s, status2, metrics2 = boot_server(
        run, "server2", models, want_platform, args.data_parallel
    )
    st2 = status2[model]
    warm2 = {b: round(v["seconds"], 2) for b, v in st2["warm"]["buckets"].items()}
    boot2_counts = {
        k: int(metric(metrics2, f"kdlt_xla_compile_{k}_total"))
        for k in ("requests", "cache_hits", "cache_writes")
    }
    new_entries = sorted(cache_entries(expected_cache) - before_boot2)
    print(f"warm boot {warm_s:.1f}s; per-bucket warm seconds {warm2}; "
          f"compile counts {boot2_counts}", flush=True)
    check("second_boot_cache", not new_entries and boot2_counts["cache_writes"] == 0
          and boot2_counts["cache_hits"] > 0,
          f"{len(new_entries)} new cache entries {[n[:48] for n in new_entries]}, "
          f"counts {boot2_counts} (first boot: {boot1_counts})")
    r = requests.post(
        f"{base2}/v1/models/{model}:predict",
        data=protocol.encode_predict_request(tensors[:16]),
        headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE}, timeout=120,
    )
    again = protocol.decode_predict_response(r.content, r.headers["Content-Type"])[0] \
        if r.status_code == 200 else None
    check("second_boot_serves", again is not None and np.array_equal(again, direct[16]),
          "the cached programs return bit-identical logits for the batch of 16")
    requests_sent += 1
    run.stop("server2", server2)
    httpd.shutdown()
    shutil.rmtree(work, ignore_errors=True)  # kept after a failure, for the logs' sake

    return {
        "ok": True,
        # As JAX reported it to the probe child; the model server's own
        # report was checked equal to it above ("server.device").
        "device": {"platform": found["platform"], "kind": found["kind"],
                   "count": found["count"]},
        "platform": st["platform"],
        "rehearsal": rehearsal,
        "versions": {k: found[k] for k in ("jax", "jaxlib", "libtpu")},
        "model": model,
        "input_shape": spec["input_shape"],
        "data_parallel": args.data_parallel,
        "batch_rows_per_device": st.get("batch_rows_per_device"),
        "fast_engaged": st["fast_engaged"],
        "fused_blocks": st.get("fused_blocks"),
        "fast_degraded": 0,
        "peak_tflops": st["peak_tflops"],
        "batcher": st["batcher"],
        "host_resize": st["host_resize"],
        "export_s": round(export_s, 1),
        "cold_boot_s": round(cold_s, 1),
        "warm_boot_s": round(warm_s, 1),
        "cold_bucket_s": warm1,
        "warm_bucket_s": warm2,
        "compiles_first_boot": boot1_counts,
        "compiles_second_boot": boot2_counts,
        "compiles_after_ready": 0,
        "new_cache_entries_second_boot": 0,
        "cache_dir": expected_cache,
        "cache_entries": len(before_boot2),
        "burst_batches": formed,
        "single_request_span_ms": trace_ms,
        "requests": requests_sent,
        "failures": 0,
        "logit_rel_err_max": round(worst, 5),
        "logit_rel_tol": LOGIT_REL_TOL,
        "elapsed_s": round(time.monotonic() - run.t0, 1),
        "claim": None,
    }


if __name__ == "__main__":
    sys.exit(main())
