"""kdlt-lint wired into tier-1: every rule in the unified suite has a
known-bad fixture it flags and a suppression path that silences it, the
donation pass catches a reconstruction of the PR 9 checkpoint bug, and the
production tree itself lints clean (zero unsuppressed findings) inside the
<10 s budget the pre-commit posture depends on."""

from __future__ import annotations

import json
import os
import sys
import textwrap
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
))

from kdlt_lint import cli  # noqa: E402
from kdlt_lint.core import PACKAGE, REPO, default_passes, run_lint  # noqa: E402
from kdlt_lint.passes.closed_vocab import ClosedVocabPass  # noqa: E402
from kdlt_lint.passes.donation import DonationSafetyPass  # noqa: E402
from kdlt_lint.passes.env_knobs import EnvKnobsPass  # noqa: E402
from kdlt_lint.passes.hotpath import HotPathSyncPass  # noqa: E402
from kdlt_lint.passes.locks import LockDisciplinePass  # noqa: E402
from kdlt_lint.passes.metrics_names import MetricsNamingPass  # noqa: E402

ENGINE_REL = f"{PACKAGE}/runtime/engine.py"
TRACE_REL = f"{PACKAGE}/utils/trace.py"
FAULTS_REL = f"{PACKAGE}/serving/faults.py"
RECORDER_REL = f"{PACKAGE}/utils/flightrecorder.py"


def lint_fixture(tmp_path, sources, passes, copy_real=()):
    """Write fixture modules into a scratch repo and lint just them.

    ``sources`` maps repo-relative paths to source text; ``copy_real``
    names real production files to copy in verbatim (registry modules the
    closed-vocab pass reads its vocabularies from)."""
    merged = dict(sources)
    for rel in copy_real:
        with open(os.path.join(REPO, rel)) as f:
            merged[rel] = f.read()
    paths = []
    for rel, src in merged.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        paths.append(str(p))
    return run_lint(passes, repo=str(tmp_path), files=paths)


def active(findings, rule=None):
    return [
        f for f in findings
        if not f.suppressed and (rule is None or f.rule == rule)
    ]


# --- lock-discipline ---------------------------------------------------------

GUARDED_BAD = """\
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0  # guarded-by: _lock

        def bump(self):
            self._n += 1
"""


def test_guarded_by_flags_unlocked_access(tmp_path):
    findings = lint_fixture(
        tmp_path, {"box.py": GUARDED_BAD}, [LockDisciplinePass()])
    hits = active(findings, "guarded-by")
    assert len(hits) == 1
    assert "Box.bump" in hits[0].message
    assert "_lock" in hits[0].message


def test_guarded_by_accepts_locked_access_and_locked_suffix(tmp_path):
    src = """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition(self._lock)
                self._n = 0  # guarded-by: _lock

            def bump(self):
                with self._lock:
                    self._n += 1

            def wait_bump(self):
                with self._cond:
                    self._n += 1

            def _bump_locked(self):
                self._n += 1
    """
    findings = lint_fixture(tmp_path, {"box.py": src}, [LockDisciplinePass()])
    assert active(findings) == []


def test_lock_order_cycle_flagged(tmp_path):
    src = """\
        import threading

        class AB:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    with self._a:
                        pass
    """
    findings = lint_fixture(tmp_path, {"ab.py": src}, [LockDisciplinePass()])
    hits = active(findings, "lock-order")
    assert len(hits) == 1
    assert "AB._a" in hits[0].message and "AB._b" in hits[0].message


def test_blocking_under_lock_flagged(tmp_path):
    src = """\
        import threading
        import time
        import requests

        class Fetcher:
            def __init__(self):
                self._lock = threading.Lock()

            def fetch(self):
                with self._lock:
                    time.sleep(0.1)
                    return requests.get("http://upstream/healthz")
    """
    findings = lint_fixture(tmp_path, {"f.py": src}, [LockDisciplinePass()])
    hits = active(findings, "blocking-under-lock")
    messages = " | ".join(f.message for f in hits)
    assert len(hits) == 2
    assert "time.sleep" in messages and "requests.get" in messages


# --- hot-path-sync / lock-around-jit ----------------------------------------

def test_hot_path_sync_flags_asarray_on_dispatch_path(tmp_path):
    src = """\
        import numpy as np

        class InFlightDispatcher:
            def submit(self, x):
                return self._pack(x)

            def _pack(self, x):
                return np.asarray(x)
    """
    findings = lint_fixture(
        tmp_path, {ENGINE_REL: src}, [HotPathSyncPass()])
    hits = active(findings, "hot-path-sync")
    assert len(hits) == 1
    assert "numpy.asarray" in hits[0].message
    assert "InFlightDispatcher.submit" in hits[0].message


def test_lock_around_jit_flagged_on_hot_path(tmp_path):
    src = """\
        import threading
        import jax

        class InFlightDispatcher:
            def __init__(self, fn):
                self._lock = threading.Lock()
                self._jitted = jax.jit(fn)

            def submit(self, x):
                with self._lock:
                    return self._jitted(x)
    """
    findings = lint_fixture(
        tmp_path, {ENGINE_REL: src}, [HotPathSyncPass()])
    hits = active(findings, "lock-around-jit")
    assert len(hits) == 1


def test_cold_path_sync_not_flagged(tmp_path):
    # The same np.asarray in a function unreachable from the roots is fine.
    src = """\
        import numpy as np

        def offline_eval(x):
            return np.asarray(x)
    """
    findings = lint_fixture(
        tmp_path, {ENGINE_REL: src}, [HotPathSyncPass()])
    assert active(findings) == []


# --- donation-safety ---------------------------------------------------------

def test_donation_use_after_donate_flagged(tmp_path):
    src = """\
        import jax

        class Trainer:
            def __init__(self, fn):
                self._step = jax.jit(fn, donate_argnums=(0,))

            def train(self, state, batch):
                new_state = self._step(state, batch)
                self._log(state)
                return new_state
    """
    findings = lint_fixture(
        tmp_path, {"t.py": src}, [DonationSafetyPass()])
    hits = active(findings, "donation-safety")
    assert len(hits) == 1
    assert "state was donated" in hits[0].message


def test_donation_pr9_checkpoint_bug_reconstruction(tmp_path):
    # The PR 9 training/checkpoint.py bug class: the loop donates ``state``
    # into the next step, then hands the SAME array to the checkpointer
    # whose background serializer reads the already-recycled device buffer.
    src = """\
        import jax

        def train_step(state, batch):
            return state

        step = jax.jit(train_step, donate_argnums=(0,))

        def train_loop(state, batches, checkpointer):
            for batch in batches:
                new_state = step(state, batch)
                checkpointer.save(state)
                state = new_state
            return state
    """
    findings = lint_fixture(
        tmp_path, {"loop.py": src}, [DonationSafetyPass()])
    hits = active(findings, "donation-safety")
    assert len(hits) == 1
    assert "use-after-donate" in hits[0].message


def test_donation_rebind_is_clean(tmp_path):
    # The canonical safe idiom: the donated name is rebound by the call.
    src = """\
        import jax

        def train_step(state, batch):
            return state

        step = jax.jit(train_step, donate_argnums=(0,))

        def train_loop(state, batches):
            for batch in batches:
                state = step(state, batch)
            return state
    """
    findings = lint_fixture(
        tmp_path, {"loop.py": src}, [DonationSafetyPass()])
    assert active(findings) == []


# --- closed-vocab ------------------------------------------------------------

def test_closed_vocab_flags_unknown_span_and_fault_point(tmp_path):
    src = """\
        def handle(tr, faults):
            faults.fire("gateway.upstrem")
            with tr.span("gateway.requset"):
                pass
    """
    findings = lint_fixture(
        tmp_path, {"h.py": src}, [ClosedVocabPass()],
        copy_real=(TRACE_REL, FAULTS_REL, RECORDER_REL))
    hits = active(findings, "closed-vocab")
    messages = " | ".join(f.message for f in hits)
    assert len(hits) == 2
    assert "gateway.requset" in messages and "gateway.upstrem" in messages


def test_closed_vocab_accepts_registry_members(tmp_path):
    src = """\
        def handle(tr, faults, recorder):
            faults.fire("gateway.upstream")
            recorder.record("pool.drain", model="m")
            with tr.span("gateway.request"):
                pass
    """
    findings = lint_fixture(
        tmp_path, {"h.py": src}, [ClosedVocabPass()],
        copy_real=(TRACE_REL, FAULTS_REL, RECORDER_REL))
    assert active(findings) == []


# --- metrics-naming / env-knobs ---------------------------------------------

def test_metrics_naming_flags_unprefixed_name(tmp_path):
    src = """\
        def build(reg):
            return reg.counter("requests_total", "help text")
    """
    findings = lint_fixture(
        tmp_path, {"m.py": src}, [MetricsNamingPass()])
    hits = active(findings, "metrics-naming")
    assert len(hits) == 1
    assert "kdlt_-prefixed" in hits[0].message


def test_env_knobs_flags_undocumented_knob(tmp_path):
    # Run the env pass with the real repo's GUIDE/manifests but only this
    # fixture contributing code literals: its bogus knob is undocumented.
    src = 'KNOB = "KDLT_DEFINITELY_NOT_DOCUMENTED"\n'
    p = tmp_path / "fixture.py"
    p.write_text(src)
    findings = run_lint([EnvKnobsPass()], repo=REPO, files=[str(p)])
    hits = [
        f for f in active(findings, "env-knobs")
        if "KDLT_DEFINITELY_NOT_DOCUMENTED" in f.message
    ]
    assert len(hits) == 1
    assert "never mentioned in GUIDE.md" in hits[0].message


# --- suppression grammar -----------------------------------------------------

def test_suppression_silences_finding(tmp_path):
    src = """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0  # guarded-by: _lock

            def bump(self):
                # kdlt-lint: disable=guarded-by -- benign monotonic counter
                self._n += 1
    """
    findings = lint_fixture(tmp_path, {"box.py": src}, [LockDisciplinePass()])
    assert active(findings) == []
    suppressed = [f for f in findings if f.suppressed]
    assert len(suppressed) == 1 and suppressed[0].rule == "guarded-by"


def test_unused_suppression_is_itself_flagged(tmp_path):
    src = """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def bump(self):
                # kdlt-lint: disable=guarded-by -- nothing to silence here
                self._n += 1
    """
    findings = lint_fixture(tmp_path, {"box.py": src}, [LockDisciplinePass()])
    hits = active(findings, "unused-suppression")
    assert len(hits) == 1
    assert "matched no finding" in hits[0].message


# --- the production tree itself ----------------------------------------------

def test_production_tree_lints_clean_within_budget(capsys):
    t0 = time.monotonic()
    findings = run_lint(default_passes(), repo=REPO)
    elapsed = time.monotonic() - t0
    bad = active(findings)
    assert bad == [], "\n".join(f.format() for f in bad)
    # Every suppression that survives review carries a justification; the
    # count is the number that stand, so adding one is a conscious test edit.
    assert len([f for f in findings if f.suppressed]) <= 9
    assert elapsed < 10.0, f"full-tree lint took {elapsed:.1f}s (budget 10s)"


def test_cli_clean_run_and_stable_json(capsys):
    assert cli.main([]) == 0
    out = capsys.readouterr().out
    assert "kdlt-lint: clean" in out

    assert cli.main(["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == 1
    assert doc["summary"]["active"] == 0
    for f in doc["findings"]:
        assert set(f) >= {"rule", "file", "line", "message", "suppressed"}


def test_cli_lists_every_rule(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in (
        "guarded-by", "lock-order", "blocking-under-lock", "hot-path-sync",
        "lock-around-jit", "donation-safety", "closed-vocab",
        "metrics-naming", "env-knobs", "unused-suppression",
    ):
        assert rule in out, rule
