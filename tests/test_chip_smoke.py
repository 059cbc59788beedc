"""chip_smoke.py's contract, as far as a machine without a chip can check it.

The chip run itself is made through the chip tool; here: (a) without the
rehearsal argument a CPU-only JAX is an error within seconds, named; (b) with
it the whole control flow (export -> model server -> gateway -> requests ->
second boot) runs at 96x96 on the CPU; (c) the parent never initialises a
JAX backend -- it never even imports jax.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def test_without_a_tpu_it_fails_fast_and_names_the_platform():
    proc = subprocess.run(
        [sys.executable, _SMOKE], capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "JAX found 'cpu'" in proc.stderr and "need 'tpu'" in proc.stderr
    # No result line: nothing on stdout parses as the {"ok": ...} summary.
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_rehearsal_runs_the_whole_flow_and_the_parent_stays_off_jax(tmp_path):
    cache = str(tmp_path / "xla-cache")
    # Run main() in a fresh interpreter and report, after it returns, which
    # jax modules that interpreter holds: the parent must hold none.
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke\n"
        "rc = chip_smoke.main(['--rehearse-on-cpu'])\n"
        "print('PARENT_JAX', [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib')][:3], file=sys.stderr)\n"
        "sys.exit(rc)\n" % _REPO
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
    env.pop("KDLT_COMPILE_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: one is enough
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=env, cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "PARENT_JAX []" in proc.stderr
    lines = proc.stdout.strip().splitlines()
    # The last line is the result, with exactly these keys; the line before
    # it is the run's summary.
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    summary = json.loads(lines[-2])
    assert summary["ok"] is True
    assert summary["platform"] == "cpu" and summary["rehearsal"] is True
    assert summary["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert summary["input_shape"] == [96, 96, 3]
    assert summary["failures"] == 0 and summary["requests"] >= 24
    assert summary["compiles_after_ready"] == 0
    assert summary["new_cache_entries_second_boot"] == 0
    assert summary["compiles_second_boot"]["cache_writes"] == 0
    # JAX_COMPILATION_CACHE_DIR placed the cache for every process of the run.
    assert summary["cache_dir"] == cache and summary["cache_entries"] > 0
    assert summary["logit_rel_err_max"] <= summary["logit_rel_tol"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None
