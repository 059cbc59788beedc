"""Regression tests for the ADVICE round-5 findings + the bench CLI smoke.

Each of the three fixed findings gets a failing-before/passing-after test,
and --dry-run pins the driver's exact invocation surface so a bench
refactor cannot silently break the official-record command.  Everything
here is device-free: unit-level calls plus fake-child subprocesses (the
same machinery as test_bench_isolation) that never import jax or touch a
device.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")


def _bench_module():
    spec = importlib.util.spec_from_file_location("kdlt_bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- ADVICE r5 #1: scan-length quantization must respect the 2000 clamp ----


def test_auto_scan_len_never_exceeds_worker_clamp():
    bench = _bench_module()
    # The failing-before shape: any k_raw in (1448, 2000] used to
    # round-to-nearest up to 2^11 = 2048, past the documented worker-safety
    # clamp.  est = 4.0/k_raw inverts the sizing formula exactly.
    for k_raw in (1449.0, 1500.0, 1750.0, 1999.0, 2000.0):
        k = bench.auto_scan_len(4.0 / k_raw)
        assert k <= bench.SCAN_LEN_CAP, (k_raw, k)
    # Quantization itself still works and stays a power of two below the cap.
    assert bench.auto_scan_len(4.0 / 100.0) == 128
    assert bench.auto_scan_len(1.0) == 32  # floor region: k_raw=24 -> 2^5
    # A zero/absurd probe estimate must not divide-by-zero or blow the cap.
    assert 24 <= bench.auto_scan_len(0.0) <= bench.SCAN_LEN_CAP


# --- ADVICE r5 #2: attempt-1 budget skips are trimming, not faults --------


def test_budget_skip_is_recorded_as_dropped_not_fault():
    env = dict(os.environ)
    env["KDLT_BENCH_FAKE_CHILD"] = "1"
    env["KDLT_BENCH_FAKE_CHILD_SLEEP_S"] = "2"
    # Budget window chosen so the per-point pre-check passes (elapsed +
    # 60s floor <= 70) but the attempt-level guard trips (remaining < 90):
    # point 1 runs (~2s), points 2 and 3 hit the attempt-1 skip.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--batches", "4,8,16", "--budget-s", "70"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=env, timeout=120,
    )
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert len(out["sweep"]) == 1
    # The never-attempted points are budget TRIMMING: dropped, zero faults,
    # and the metric note says trimmed -- not "faulted point attempt(s)".
    assert out["dropped_points"] == [8, 16]
    assert out["faults"] == []
    assert "budget trimmed" in out["metric"]
    assert "faulted" not in out["metric"]
    assert proc.returncode == 0  # the surviving point is in-bound


# --- ADVICE r5 #3: empty-string cache env var means unset, not off --------


def test_compile_cache_empty_env_is_unset_not_disable(monkeypatch):
    from kubernetes_deep_learning_tpu.utils.compilecache import (
        DEFAULT_CACHE_DIR,
        resolve_cache_dir,
    )

    monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", "")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/jax-cc")
    # Before the fix "" was a disable sentinel and suppressed the fallback.
    assert resolve_cache_dir() == "/tmp/jax-cc"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # Nothing set: the one fixed path, <checkout>/.jax_cache -- never a
    # temp dir, a pid or a clock.
    assert resolve_cache_dir() == DEFAULT_CACHE_DIR == os.path.join(_REPO, ".jax_cache")
    # The explicit sentinels still disable everything downstream...
    for sentinel in ("off", "none", "0", " OFF "):
        monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", sentinel)
        assert resolve_cache_dir() is None
    # ...but never an explicit programmatic argument.
    assert resolve_cache_dir("/tmp/explicit") == "/tmp/explicit"
    # JAX's own variable, where set, beats ours, the flag and the argument
    # (the flag IS the argument: main() passes --compile-cache-dir through).
    monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", "/tmp/kdlt-cc")
    assert resolve_cache_dir() == "/tmp/kdlt-cc"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/jax-cc")
    assert resolve_cache_dir() == "/tmp/jax-cc"
    assert resolve_cache_dir("/tmp/explicit") == "/tmp/jax-cc"
    monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", "off")
    assert resolve_cache_dir() == "/tmp/jax-cc"


# --- one process per chip: --serving must not go on to spawn children -----


def test_serving_mode_returns_without_spawning_sweep_children(monkeypatch, capsys):
    bench = _bench_module()
    calls = []

    def fake_serving(duration_s, clients, batcher, max_delay_ms, buckets):
        calls.append(("serving", duration_s))
        return {"batcher": "scheduler", "img_per_s": 1.0, "errors": 0}

    def no_children(*a, **kw):
        raise AssertionError(
            "bench.py --serving started a child after using the device"
        )

    # bench_serving runs a full ModelServer on the device in the parent; any
    # child that needs the chip afterwards would fail or hang.
    monkeypatch.setattr(bench, "bench_serving", fake_serving)
    monkeypatch.setattr(bench, "run_isolated_sweep", no_children)
    monkeypatch.setattr(bench.subprocess, "Popen", no_children)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--serving", "3"])
    assert bench.main() == 0
    assert calls == [("serving", 3.0)]
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "serving e2e" and out["errors"] == 0


# --- CLI smoke: the driver's invocation surface must keep parsing ---------


def test_dry_run_parses_the_driver_invocation():
    # The official-record invocation is bare `python bench.py` (plus the
    # KDLT_BENCH_BUDGET_S env); --dry-run must echo the resolved config
    # without importing jax, spawning children, or touching a device.
    env = dict(os.environ)
    env["KDLT_BENCH_BUDGET_S"] = "1140"
    proc = subprocess.run(
        [sys.executable, _BENCH, "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "sweep"
    assert out["model"] == "clothing-model"
    # Headline-first point order and the self-trim budget are part of the
    # survivability contract (VERDICT r4); pin them.
    assert out["batches"][0] == 16 and 256 in out["batches"]
    assert out["budget_s"] == 1140.0
    assert out["isolate"] is True


def test_dry_run_covers_the_auxiliary_modes():
    for flags, mode in (
        (["--soak", "60"], "soak"),
        (["--pipeline-ab", "10"], "pipeline_ab"),
        (["--host-saturation", "5"], "host_saturation"),
        (["--batcher-sweep", "5"], "batcher_sweep"),
        (["--overload-ab", "6"], "overload_ab"),
        (["--chaos-ab", "6"], "chaos_ab"),
        (["--cache-ab", "6"], "cache_ab"),
        (["--crosshost-ab", "30"], "crosshost_ab"),
        (["--mesh-ab", "2"], "mesh_ab"),
        (["--obs-overhead-ab", "5"], "obs_overhead_ab"),
        (["--tenant-ab", "5"], "tenant_ab"),
        (["--incident-ab", "6"], "incident_ab"),
        (["--decode-ab", "16"], "decode_ab"),
        (["--ingest-ab", "120"], "ingest_ab"),
    ):
        proc = subprocess.run(
            [sys.executable, _BENCH, *flags, "--dry-run"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, timeout=60,
        )
        assert proc.returncode == 0
        out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        assert out["mode"] == mode, flags


# --- admission-control overload A/B: CLI surface smoke --------------------


def test_dry_run_overload_ab_echoes_the_admission_config():
    # The --overload-ab invocation surface (serving.admission's acceptance
    # harness) must keep parsing and echo its resolved knobs without
    # importing jax, binding ports, or spawning servers.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--overload-ab", "6", "--dry-run",
         "--overload-deadline-ms", "450", "--overload-rate-x", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "overload_ab"
    assert out["overload"]["deadline_ms"] == 450.0
    assert out["overload"]["rate_x"] == 3.0
    assert out["overload"]["buckets"] == [1, 2]
    assert out["overload"]["device_ms"] == 100.0


def test_dry_run_chaos_ab_echoes_the_fault_tolerance_config():
    # The --chaos-ab invocation surface (the serving-path fault-tolerance
    # acceptance harness) must keep parsing and echo its resolved knobs.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--chaos-ab", "6", "--dry-run",
         "--chaos-hedge-ms", "80", "--chaos-probe-s", "0.25",
         "--chaos-seed", "7", "--chaos-mode", "stall"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "chaos_ab"
    assert out["chaos"]["hedge_ms"] == 80.0
    assert out["chaos"]["probe_s"] == 0.25
    assert out["chaos"]["seed"] == 7
    assert out["chaos"]["deadline_ms"] == 2000.0
    # The cross-host leader arm (ISSUE 8 satellite): the stall mode must
    # round-trip the CLI.
    assert out["chaos"]["mode"] == "stall"


def test_dry_run_incident_ab_echoes_the_flight_recorder_config():
    # The --incident-ab invocation surface (the incident flight-recorder
    # acceptance harness, GUIDE 10m) must keep parsing and echo its
    # resolved knobs without importing jax, binding ports, or spawning
    # servers.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--incident-ab", "6", "--dry-run",
         "--incident-device-ms", "25", "--incident-rate-rps", "16",
         "--incident-seed", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "incident_ab"
    assert out["incident"]["duration_s"] == 6.0
    assert out["incident"]["device_ms"] == 25.0
    assert out["incident"]["rate_rps"] == 16.0
    assert out["incident"]["seed"] == 3
    assert out["incident"]["deadline_ms"] == 1500.0


def test_dry_run_cache_ab_echoes_the_cache_config():
    # The --cache-ab invocation surface (the gateway cache + singleflight
    # acceptance harness, ISSUE 8) must keep parsing and echo its resolved
    # knobs without importing jax, binding ports, or spawning servers.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--cache-ab", "6", "--dry-run",
         "--cache-zipf-alpha", "1.3", "--cache-universe", "32",
         "--cache-rate-rps", "80", "--cache-probe-n", "12",
         "--cache-seed", "5"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "cache_ab"
    assert out["cache"]["duration_s"] == 6.0
    assert out["cache"]["zipf_alpha"] == 1.3
    assert out["cache"]["universe"] == 32
    assert out["cache"]["rate_rps"] == 80.0
    assert out["cache"]["probe_n"] == 12
    assert out["cache"]["seed"] == 5
    assert out["cache"]["device_ms"] == 50.0
    assert out["cache"]["deadline_ms"] == 800.0


def test_dry_run_crosshost_ab_echoes_the_pipeline_config():
    # The --crosshost-ab invocation surface (the cross-host dispatch
    # pipelining acceptance harness, ISSUE 5) must keep parsing and echo
    # its resolved knobs without importing jax or spawning the fleet.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--crosshost-ab", "40", "--dry-run",
         "--crosshost-ab-batch", "16", "--crosshost-ab-processes", "3",
         "--crosshost-ab-depths", "1,2,4", "--crosshost-ab-host-ms", "5"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "crosshost_ab"
    assert out["crosshost"]["rounds"] == 40
    assert out["crosshost"]["batch"] == 16
    assert out["crosshost"]["processes"] == 3
    assert out["crosshost"]["depths"] == [1, 2, 4]
    assert out["crosshost"]["host_ms"] == 5.0


def test_dry_run_mesh_ab_echoes_the_mesh_config():
    # The --mesh-ab invocation surface (the 2-D named-sharding mesh
    # acceptance harness) must keep parsing and echo its resolved knobs
    # without importing jax or bringing up the 8-way host-platform mesh.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--mesh-ab", "3", "--dry-run",
         "--mesh-size", "64", "--mesh-buckets", "4,8",
         "--mesh-arms", "1,2", "--mesh-tol", "1e-3",
         "--mesh-bytes-slack", "0.2", "--mesh-floor", "0.1"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "mesh_ab"
    assert out["mesh"]["reps"] == 3
    assert out["mesh"]["size"] == 64
    assert out["mesh"]["buckets"] == [4, 8]
    assert out["mesh"]["arms"] == [1, 2]
    assert out["mesh"]["tol"] == 1e-3
    assert out["mesh"]["bytes_slack"] == 0.2
    assert out["mesh"]["floor_frac"] == 0.1


def test_dry_run_decode_ab_echoes_the_decode_config():
    # The --decode-ab invocation surface (the generative lane's
    # continuous-batching acceptance gate, GUIDE 10p) is pinned here
    # without importing jax or compiling the decode ladder.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--decode-ab", "12", "--dry-run",
         "--decode-slots", "2", "--decode-step-ms", "5",
         "--decode-deadline-ms", "1500", "--decode-ttft-budget-ms", "800",
         "--decode-seed", "7"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, timeout=60,
    )
    assert proc.returncode == 0
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "decode_ab"
    assert out["decode"]["requests"] == 12
    assert out["decode"]["slots"] == 2
    assert out["decode"]["step_ms"] == 5.0
    assert out["decode"]["deadline_ms"] == 1500.0
    assert out["decode"]["ttft_budget_ms"] == 800.0
    assert out["decode"]["seed"] == 7


def test_dry_run_multimodel_ab_echoes_the_scheduler_config():
    # The --multimodel-ab invocation surface (the unified scheduler's
    # acceptance harness) must round-trip the CLI.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--multimodel-ab", "5", "--dry-run",
         "--mm-heavy-device-ms", "80", "--mm-light-deadline-ms", "200",
         "--mm-rate-x", "3"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "multimodel_ab"
    assert out["multimodel"]["duration_s"] == 5.0
    assert out["multimodel"]["heavy_device_ms"] == 80.0
    assert out["multimodel"]["light_deadline_ms"] == 200.0
    assert out["multimodel"]["rate_x"] == 3.0
    assert out["multimodel"]["light_rps"] == 40.0


# --- observability-overhead A/B: CLI surface smoke + the 2% bar -----------


def test_dry_run_obs_overhead_ab_echoes_the_observability_config():
    # The --obs-overhead-ab invocation surface (the SLO/attribution/
    # exemplar layer's cost guard) must keep parsing and echo its resolved
    # knobs without importing jax, binding ports, or spawning servers.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--obs-overhead-ab", "4", "--dry-run",
         "--obs-clients", "8", "--obs-device-ms", "1.5", "--obs-rounds", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, timeout=60,
    )
    assert proc.returncode == 0
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "obs_overhead_ab"
    assert out["obs_overhead"]["duration_s"] == 4.0
    assert out["obs_overhead"]["clients"] == 8
    assert out["obs_overhead"]["device_ms"] == 1.5
    assert out["obs_overhead"]["rounds"] == 3


@pytest.mark.slow
def test_obs_overhead_ab_full_layer_costs_at_most_two_percent():
    """ISSUE 7's acceptance bar (slow: several closed-loop HTTP rounds):
    the full observability layer -- SLO windows, exemplars, tail-based
    retention -- holds >= 98% of the observability-off throughput, and the
    on arm proves the layer actually engaged (exemplars on /metrics, the
    model on /debug/slo)."""
    bench = _bench_module()
    out, rc = bench.bench_obs_overhead_ab(
        duration_s=3.0, clients=8, rounds=2
    )
    assert rc == 0, out
    assert out["value"] >= 0.98, out
    assert out["layer_engaged"] is True


@pytest.mark.slow
def test_overload_ab_slo_view_agrees_with_client_ground_truth():
    """The /debug/slo acceptance cross-check: the admission arm's
    server-side SLO window must account every request the open-loop client
    resolved (completions + sheds), and its good count must reconcile with
    the client-side in-deadline 200s.  Exact equality is not required --
    the deadline clock is measured at two different points (client
    scheduled-send vs server header receipt) -- but the counts must agree
    closely, not directionally."""
    bench = _bench_module()
    out, rc = bench.bench_overload_ab(duration_s=4.0)
    assert rc == 0, out
    arm = out["arms"]["admission"]
    slo = arm["slo_view"]
    assert slo is not None, "admission arm must expose /debug/slo"
    row = slo["5m"]
    resolved = arm["completed_200"] + arm["shed_5xx"]
    # Every client-resolved request is in the server's window (the server
    # can additionally have seen requests the client gave up on).
    assert row["total"] >= resolved - 1
    # In-deadline goodput: server-side good within a small tolerance of the
    # client-side in-deadline completions (both clocks run the same budget).
    client_good = round(arm["goodput_rps"] * 4.0)
    assert abs(row["good"] - client_good) <= max(3, 0.1 * client_good), (
        row, arm,
    )


@pytest.mark.slow
def test_multimodel_ab_weighted_beats_fifo_on_worst_model_goodput():
    """ISSUE 6's acceptance bar (slow: two ~4s open-loop arms with
    hundreds of client threads): under mixed 2x load the weighted
    deadline-aware scheduler beats naive FIFO on worst-model in-deadline
    goodput by >= 1.2x, without degrading the overloaded heavy model."""
    bench = _bench_module()
    out, rc = bench.bench_multimodel_ab(duration_s=4.0)
    assert rc == 0, out
    assert out["value"] >= 1.2, out
    arms = out["arms"]
    w, f = arms["weighted_deadline"], arms["fifo"]
    assert w["worst_model_goodput_frac"] > f["worst_model_goodput_frac"]
    # The rescue must come from the doomed backlog, not the heavy model.
    assert (
        w["models"]["mm-heavy"]["goodput_frac"]
        >= 0.8 * f["models"]["mm-heavy"]["goodput_frac"]
    )


@pytest.mark.slow
def test_decode_ab_continuous_wins_goodput_and_stays_bit_exact():
    """ISSUE 17's acceptance bar (slow: compiles the decode ladder and
    runs two timed arms): under a closed burst of mixed-length
    generations with per-request deadlines, continuous (token-boundary)
    admission beats static request-boundary batching on in-deadline
    token goodput, holds TTFT p99 within the lane's budget, and every
    sampled continuous-batch token stream is bit-identical to the same
    prompt decoded solo on the same engine."""
    bench = _bench_module()
    out, rc = bench.bench_decode_ab(n_requests=12, step_ms=10.0,
                                    deadline_ms=2000.0)
    assert rc == 0, out
    arms = out["arms"]
    assert (
        arms["continuous"]["tokens_in_deadline"]
        >= arms["static"]["tokens_in_deadline"]
    ), arms
    assert arms["continuous"]["ttft_p99_ms"] <= out["ttft_budget_ms"], arms
    assert out["bit_exact_vs_solo"] is True
    # The convoy effect is the mechanism: static's TTFT p99 must reflect
    # late waves queuing behind full batch drains.
    assert arms["static"]["ttft_p99_ms"] > arms["continuous"]["ttft_p99_ms"], arms


def test_dry_run_ingest_ab_echoes_the_ingest_config():
    # The --ingest-ab invocation surface (the raw-bytes ingest wire
    # acceptance harness, ISSUE 20) must keep parsing and echo its
    # resolved knobs without importing jax, binding ports, or encoding
    # a single JPEG.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--ingest-ab", "150", "--dry-run",
         "--ingest-size", "512", "--ingest-input", "96",
         "--ingest-clients", "4", "--ingest-seed", "7"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "ingest_ab"
    assert out["ingest"]["images"] == 150
    assert out["ingest"]["source_px"] == 512
    assert out["ingest"]["input_px"] == 96
    assert out["ingest"]["clients"] == 4
    assert out["ingest"]["seed"] == 7


@pytest.mark.slow
def test_ingest_ab_bytes_wire_moves_the_decode_and_keeps_parity():
    """ISSUE 20's acceptance bar (slow: two closed-loop HTTP arms over a
    real gateway + stub model tier): the bytes wire clears >=1.3x img/s
    OR >=2x lower gateway CPU/image, wire bytes/image stay <=1.2x the
    encoded blob, per-image scores are identical across wires, and the
    bytes arm fires zero fallbacks."""
    bench = _bench_module()
    out, rc = bench.bench_ingest_ab(n_images=96, clients=6)
    assert rc == 0, out
    assert out["speedup_img_per_s"] >= 1.3 or out["cpu_ratio"] >= 2.0, out
    assert out["wire_ratio_vs_encoded"] <= 1.2, out
    assert out["parity_identical"] is True, out
    assert out["used_bytes_wire"] is True, out
    assert out["arms"]["bytes"]["errors"] == 0, out
    assert out["arms"]["tensor"]["errors"] == 0, out
    # The tensor arm must not have touched the bytes wire at all.
    assert out["arms"]["tensor"]["bytes_requests"] == 0, out


@pytest.mark.slow
def test_cache_ab_hit_ratio_goodput_and_singleflight_proof():
    """ISSUE 8's acceptance bar (slow: two ~4s open-loop HTTP arms): on a
    Zipf(1.1) workload at ~2x stub-tier capacity, the cache-on arm holds
    hit_ratio >= 0.5 and beats the cache-off arm's in-deadline goodput;
    a probe of N identical concurrent requests produces EXACTLY ONE
    upstream dispatch (singleflight), and a fresh URL's miss-path
    response is bit-identical to the cache-off arm's."""
    bench = _bench_module()
    out, rc = bench.bench_cache_ab(duration_s=4.0)
    assert rc == 0, out
    assert out["hit_ratio"] >= 0.5, out
    assert out["vs_baseline"] > 1.0, out
    assert out["singleflight_upstream_dispatches"] == 1, out
    assert out["miss_bit_identical"] is True, out
    on = out["arms"]["cache_on"]
    assert on["hits"] > 0 and on["misses"] > 0


@pytest.mark.slow
def test_crosshost_ab_pipelined_beats_lockstep():
    """The tentpole's acceptance bar on a REAL 2-process fleet (slow:
    spawns a fleet + compiles): pipelined >= 1.15x lockstep img/s with
    bit-identical logits, depth 1 == lockstep.  Serialized behind the
    fleet flock like every multi-process test."""
    from tests.test_crosshost import _fleet_lock

    bench = _bench_module()
    with _fleet_lock():
        out, rc = bench.bench_crosshost_ab(n_rounds=40, batch=32)
    assert rc == 0, out
    assert all(out["identical_to_lockstep"].values()), out
    assert out["value"] >= 1.15, out


# --- the pipelined-vs-serial A/B acceptance bound -------------------------


def test_pipeline_ab_depth2_closes_the_host_gap():
    """The tentpole's acceptance criterion, in-process (conftest already
    forces the CPU backend): with injected per-stage costs the depth-1
    pipeline pays host+device serially (>=15% above the device-execute
    bound at 3ms host / 10ms device) while depth 2 overlaps the host stage
    and lands within 5% -- with byte-identical, correctly-wired results."""
    bench = _bench_module()
    out, rc = bench.bench_pipeline_ab(
        n_batches=60, batch=8, host_ms=3.0, device_ms=10.0, depths=(1, 2)
    )
    assert rc == 0, out
    assert out["identical_across_depths"] is True
    d1, d2 = out["depths"]["1"], out["depths"]["2"]
    assert d1["miswired_futures"] == 0 and d2["miswired_futures"] == 0
    assert d1["gap_vs_device_bound"] >= 0.15, d1
    assert d2["gap_vs_device_bound"] <= 0.05, d2
    assert out["value"] > 1.1  # wall-clock speedup from pipelining alone


# --- full-int8 quantization A/B (ISSUE 9) ---------------------------------


def test_dry_run_quant_ab_echoes_the_quant_config():
    proc = subprocess.run(
        [sys.executable, _BENCH, "--quant-ab", "3", "--quant-size", "48",
         "--quant-buckets", "1,4", "--quant-calib-images", "16",
         "--quant-min-size", "500000", "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, timeout=60,
    )
    assert proc.returncode == 0
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["mode"] == "quant_ab"
    q = out["quant"]
    assert q["reps"] == 3
    assert q["size"] == 48
    assert q["buckets"] == [1, 4]
    assert q["calib_images"] == 16
    assert q["min_size"] == 500000


# --- tenant isolation + brownout A/B (ISSUE 12) ---------------------------


def test_dry_run_tenant_ab_echoes_the_isolation_config():
    # The --tenant-ab invocation surface (per-model budgets + brownout
    # acceptance harness) must keep parsing and echo its resolved knobs
    # without importing jax, binding ports, or spawning servers.
    proc = subprocess.run(
        [sys.executable, _BENCH, "--tenant-ab", "5", "--dry-run",
         "--tenant-device-ms", "40", "--tenant-deadline-ms", "1200",
         "--tenant-rate-x", "2.5", "--tenant-b-rps", "10",
         "--tenant-flood-s", "4", "--tenant-seed", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["dry_run"] is True
    assert out["mode"] == "tenant_ab"
    t = out["tenant"]
    assert t["duration_s"] == 5.0
    assert t["device_ms"] == 40.0
    assert t["deadline_ms"] == 1200.0
    assert t["rate_x"] == 2.5
    assert t["b_rps"] == 10.0
    assert t["flood_s"] == 4.0
    assert t["seed"] == 3


@pytest.mark.slow
def test_tenant_ab_budgets_isolate_and_brownout_recovers():
    """ISSUE 12's acceptance bar (slow: two open-loop model-tier arms plus
    a gateway brownout arm with a best-effort flood): with per-model
    budgets, victim tenant-b holds >= 95% in-deadline goodput while
    tenant-a floods at 3x capacity, vs collapse under the shared limiter;
    the brownout ladder then climbs to >= stage 3 under the flood, keeps
    interactive goodput >= 95%, recovers the 5m burn below 1.0, and walks
    back down with ZERO up/down flaps."""
    bench = _bench_module()
    out, rc = bench.bench_tenant_ab(duration_s=4.0)
    assert rc == 0, out
    assert out["part1_ok"] is True, out
    assert out["part2_ok"] is True, out
    b_budget = out["arms"]["budgets"]["models"]["tenant-b"]["goodput_frac"]
    b_shared = out["arms"]["shared"]["models"]["tenant-b"]["goodput_frac"]
    assert b_budget >= 0.95, out["arms"]["budgets"]
    assert b_shared < 0.8 * b_budget, out["arms"]["shared"]
    arm = out["brownout_arm"]
    assert arm["classes"]["interactive"]["goodput_frac"] >= 0.95, arm
    assert arm["peak_stage"] >= 3, arm
    assert arm["burn_final"] < 1.0, arm
    assert arm["flap_free"] is True, arm
    # The flood was actually shed by the ladder, not absorbed.
    assert arm["classes"]["best-effort"]["shed_429"] > 0, arm


@pytest.mark.slow
def test_quant_ab_w8a8_beats_f32_on_proxy_within_tolerance():
    """ISSUE 9's acceptance bar (slow: three engine warmups incl. the CPU
    int8 reference lowering): w8a8 >= 1.2x f32 img/s on the v5e roofline
    proxy at the smallest bucket, top-1 agreement >= 0.99 and max-abs
    logit drift within KDLT_QUANT_TOL on the golden fixture, and the
    engine's own warmup tolerance gate ACCEPTED the calibrated artifact
    (measured CPU img/s is reported alongside -- XLA:CPU has no s8xs8
    fast path, so the device claim rides the proxy + the gate numerics)."""
    bench = _bench_module()
    out, rc = bench.bench_quant_ab(
        reps=2, size=32, buckets=(1, 2), calib_images=16,
        percentile=100.0, min_size=700_000,
    )
    assert rc == 0, out
    assert out["value"] >= 1.2, out
    assert out["gate_accepted"] is True, out
    assert out["top1_agreement"] >= 0.99, out
    assert out["worst_rel_maxabs_drift"] <= out["tol"], out
    # Weight bytes: the roofline's numerator is real, not assumed.  This
    # config confines int8 to the three biggest kernels (CPU economy), so
    # the drop is partial; the full-ladder ~4x is pinned by
    # test_quantize.py's artifact-size assertion.
    f32_b = next(iter(out["arms"]["f32"]["buckets"].values()))["weight_bytes"]
    w8a8_b = next(iter(out["arms"]["w8a8"]["buckets"].values()))["weight_bytes"]
    assert w8a8_b < f32_b * 0.85, (f32_b, w8a8_b)
