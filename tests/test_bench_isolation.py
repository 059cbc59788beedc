"""bench.py's official-record survivability contract, via the real CLI.

Two failure modes have nullified the driver-captured record in past
rounds, and each has a contract tested here:

* r1-r3: a TPU worker fault in the single shared process killed the whole
  sweep -> per-point subprocess isolation (a faulting batch point must be
  retried, recorded in ``faults``, and must NOT abort the sweep);
* r4 (rc=124): the DRIVER's wall-clock budget killed the sweep before the
  end-of-run JSON printed -> the current-best headline is re-emitted after
  every completed point, an overall --budget-s trims the tail, and SIGTERM
  triggers a final emission -- so the last stdout line parses no matter
  when the run is cut down.

Device-free forcing functions: an unknown model name makes a child die
before any device use (get_spec raises first), and KDLT_BENCH_FAKE_CHILD=1
makes children emit synthetic rows without importing jax -- either way the
tests never touch a device.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys

_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py")


def _fake_env(sleep_s: float = 0.0) -> dict:
    env = dict(os.environ)
    env["KDLT_BENCH_FAKE_CHILD"] = "1"
    env["KDLT_BENCH_FAKE_CHILD_SLEEP_S"] = str(sleep_s)
    return env


def _parse_lines(stdout: bytes) -> list[dict]:
    lines = [ln for ln in stdout.decode().strip().splitlines() if ln.strip()]
    return [json.loads(ln) for ln in lines]


def test_every_point_emits_a_parsable_headline():
    proc = subprocess.run(
        [sys.executable, _BENCH, "--batches", "4,8,16", "--budget-s", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=_fake_env(), timeout=120,
    )
    assert proc.returncode == 0
    outs = _parse_lines(proc.stdout)
    # One emission per completed point plus the final record; EVERY line is
    # a complete same-schema headline, so a cut at any moment still parses.
    assert len(outs) == 4
    for out in outs:
        assert out["unit"] == "images/sec/chip"
        assert out["value"] > 0
        assert "sweep" in out and "metric" in out
    assert [len(o["sweep"]) for o in outs] == [1, 2, 3, 3]
    # Final record equals the last incremental one (later overwrites earlier)
    # except for the progress note dropping once the sweep is complete.
    assert outs[-1]["value"] == outs[-2]["value"]


def test_budget_trims_remaining_points_and_records_them():
    proc = subprocess.run(
        [sys.executable, _BENCH, "--batches", "4,8,16,32", "--budget-s", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=_fake_env(sleep_s=0.5), timeout=120,
    )
    outs = _parse_lines(proc.stdout)
    final = outs[-1]
    # The per-point estimate is floored at 60s, so a 3s budget admits only
    # the first point; the rest must be recorded as dropped, not vanish.
    assert final["dropped_points"] == [8, 16, 32]
    assert len(final["sweep"]) == 1
    assert "partial sweep 1/4" in final["metric"]
    assert proc.returncode == 0  # the surviving point is in-bound


def test_sigterm_mid_sweep_still_parses():
    # 5 points x 2s each; SIGTERM lands mid-point-2.  The driver's timeout
    # does exactly this (rc=124 killed round 4's record); the contract is
    # that the last stdout line is still a parsable headline carrying every
    # completed point.
    proc = subprocess.Popen(
        [sys.executable, _BENCH, "--batches", "4,8,16,32,64", "--budget-s", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=_fake_env(sleep_s=2.0),
    )
    # Wait for the first incremental emission so at least one point exists.
    first = proc.stdout.readline()
    assert json.loads(first)["value"] > 0
    proc.send_signal(signal.SIGTERM)
    out_b, _ = proc.communicate(timeout=60)
    outs = _parse_lines(first + out_b)
    final = outs[-1]
    assert final["terminated"] is True
    assert len(final["sweep"]) >= 1
    assert final["value"] > 0
    assert "terminated by signal" in final["metric"]


def test_faulted_points_are_recorded_not_fatal():
    proc = subprocess.run(
        [
            sys.executable, _BENCH,
            "--batches", "2,4",
            "--model", "no-such-model",
            "--point-timeout", "120",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        timeout=300,
    )
    # Every point faulted -> rc=1, but the parent still emits its one JSON
    # line with the full fault record (nothing hidden, nothing crashed).
    assert proc.returncode == 1
    out = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert out["value"] == 0.0 and out["vs_baseline"] == 0.0
    assert "EVERY batch point faulted" in out["metric"]
    # Both points, both attempts each: the sweep continued past the first
    # fault and each fault carries the child's stderr tail.
    attempts = [(f["batch"], f["attempt"]) for f in out["faults"]]
    assert attempts == [(2, 1), (2, 2), (4, 1), (4, 2)]
    assert all("no-such-model" in f["fault"] for f in out["faults"])
