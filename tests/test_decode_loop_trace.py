"""The decode loop's own account of its time (runtime/decode.py::LoopClock):
six phases that sum to the loop's life, the device's dry-ups booked to the
phase they began in, the loop's CPU seconds, the interpreter's GC pauses,
and each phase as a profiler annotation on the loop's thread.

Modelled on tests/test_dispatch.py::test_idle_seconds_by_cause; the engine
is the lane's toy at a tiny size on the CPU (one step program, two prefill
buckets, compiled once for the module).
"""

from __future__ import annotations

import gc
import time
from contextlib import contextmanager

import pytest

from kubernetes_deep_learning_tpu.runtime import decode as decode_lib
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from kubernetes_deep_learning_tpu.utils import trace as trace_lib

PHASES = [phase for phase, _ in metrics_lib.DECODE_LOOP_PHASES]


@pytest.fixture(scope="module")
def engine():
    return decode_lib.DecodeEngine(
        "gen-loop-test", max_slots=2, page_size=8, max_pages_per_seq=4,
    )


@pytest.fixture(scope="module")
def wide_engine():
    # a step of ~3 ms on the CPU (the toy's is ~0.1 ms): long enough that the
    # probe at a read's return finds the program behind it still running
    return decode_lib.DecodeEngine(
        "gen-loop-wide", max_slots=2, page_size=8, max_pages_per_seq=4,
        d_model=512, n_layers=8,
    )


def _series(registry) -> dict:
    """{series name: sum over label sets}, and {(name, phase): value}."""
    out: dict = {}
    for line in registry.render().splitlines():
        if line.startswith("#") or not line:
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        out[name] = out.get(name, 0.0) + float(value)
        if 'phase="' in head:
            out[name, head.split('phase="', 1)[1].split('"', 1)[0]] = float(value)
    return out


def _loop_seconds(series) -> dict:
    return {p: series[f"kdlt_decode_loop_{p}_seconds_total"] for p in PHASES}


def test_the_six_phases_sum_to_the_loops_life(engine):
    """Idle, then streams (one cancelled while it runs), then idle again:
    every instant of the loop's thread is in one phase, so the six sum to
    its lifetime and to the wall time the test saw."""
    registry = metrics_lib.Registry()
    sched = decode_lib.DecodeScheduler(engine, registry=registry)
    wall0 = time.perf_counter()
    sched.start()
    try:
        time.sleep(0.3)                         # an empty lane: wait
        keep = sched.submit(None, 9, token_ids=[3, 4, 5], ignore_eos=True)
        gone = sched.submit(None, 20, token_ids=[6] * 7, ignore_eos=True)
        it = gone.iter_events(timeout_s=60.0)
        next(it)
        gone.cancel()
        assert list(it)[-1] == ("done", decode_lib.FINISH_CANCELLED)
        assert list(keep.iter_events(timeout_s=60.0))[-1] == ("done", "length")
        time.sleep(0.3)
    finally:
        sched.close()
    wall = time.perf_counter() - wall0
    series = _series(registry)
    got = _loop_seconds(series)
    clock = sched.clock
    assert sum(got.values()) == pytest.approx(clock.ended - clock.born, rel=1e-6)
    assert sum(got.values()) == pytest.approx(wall, rel=0.01, abs=0.02)
    assert got["wait"] >= 0.5 and all(got[p] > 0 for p in PHASES), got
    # the loop's CPU seconds lie inside the wall seconds of its host phases
    host = sum(got[p] for p in metrics_lib.DECODE_HOST_PHASES)
    assert 0 < series["kdlt_decode_loop_cpu_seconds_total"] <= host * 1.01 + 1e-3
    # a dry-up is never longer than the loop's time outside wait
    assert series["kdlt_decode_dry_seconds_total"] <= sum(got.values()) - got["wait"]


def test_an_empty_lane_is_waiting_not_dry(engine):
    """No work for the device is not a dry-up: an idle lane, and one whose
    streams have all ended, book nothing as dry."""
    registry = metrics_lib.Registry()
    sched = decode_lib.DecodeScheduler(engine, registry=registry)
    sched.start()
    try:
        time.sleep(0.6)                         # two of the wait's timeouts
        mid = _series(registry)
        gen = sched.submit(None, 1, token_ids=[1, 2], ignore_eos=True)
        assert list(gen.iter_events(timeout_s=60.0))[-1] == ("done", "length")
        time.sleep(0.6)
    finally:
        sched.close()
    series = _series(registry)
    # the wait is published while it lasts, not only when it ends
    assert mid["kdlt_decode_loop_wait_seconds_total"] >= 0.4
    assert series["kdlt_decode_loop_wait_seconds_total"] >= 1.1
    # one prefill, its token the last one asked for: nothing to give the device after it
    assert series["kdlt_decode_dry_seconds_total"] == 0.0
    assert series["kdlt_decode_dry_total"] == 0.0


def test_a_slow_book_is_booked_as_dry_under_book(wide_engine, monkeypatch):
    """A sleep in the booking after a read, long enough for the round
    dispatched behind it to finish: the device runs dry while the loop
    books, and the dry seconds land under phase="book"."""
    nap = 0.04
    take = decode_lib.DecodeScheduler._take

    def slow_take(self, *args, **kwargs):
        time.sleep(nap)
        return take(self, *args, **kwargs)

    monkeypatch.setattr(decode_lib.DecodeScheduler, "_take", slow_take)
    registry = metrics_lib.Registry()
    sched = decode_lib.DecodeScheduler(wide_engine, registry=registry)
    sched.start()
    try:
        gen = sched.submit(None, 10, token_ids=[9, 8, 7], ignore_eos=True)
        assert list(gen.iter_events(timeout_s=60.0))[-1] == ("done", "length")
    finally:
        sched.close()
    series = _series(registry)
    book = series["kdlt_decode_dry_seconds_total", "book"]
    # nine of the ten naps have a step behind them (the last token's has none)
    assert book >= 0.5 * 9 * nap, series
    assert book == max(series["kdlt_decode_dry_seconds_total", p] for p in PHASES[1:])
    assert series["kdlt_decode_dry_total", "book"] >= 5
    assert book <= series["kdlt_decode_loop_book_seconds_total"] + 0.02


def test_a_gc_collect_advances_the_pause_counter(monkeypatch):
    entered = []

    @contextmanager
    def recording(name):
        entered.append(name)
        yield

    pauses = trace_lib.watch_gc_pauses()
    monkeypatch.setattr(pauses, "annotate", recording)
    registry = metrics_lib.Registry()
    metrics_lib.gc_pause_counters(registry, pauses)
    before = _series(registry)["kdlt_gc_pause_seconds_total"]
    gc.collect()
    page = registry.render()
    assert _series(registry)["kdlt_gc_pause_seconds_total"] > before
    assert 'kdlt_gc_pause_seconds_total{generation="2"}' in page
    assert trace_lib.SPAN_GC_PAUSE in entered
    # one hook a process, however many times it is asked for
    trace_lib.watch_gc_pauses()
    assert gc.callbacks.count(pauses) == 1


def test_the_loop_annotates_its_phases_in_order(engine):
    """A recording annotate factory, handed in the way the model server
    hands its Tracer TraceAnnotation, sees each phase entered and left on
    the loop's thread, in the loop's order."""
    log = []

    @contextmanager
    def recording(name):
        log.append(("enter", name))
        yield
        log.append(("exit", name))

    tracer = trace_lib.Tracer("model-server", annotate=recording)
    sched = decode_lib.DecodeScheduler(engine, tracer=tracer)
    sched.start()
    try:
        time.sleep(0.1)
        gen = sched.submit(None, 4, token_ids=[1, 2, 3], ignore_eos=True)
        assert list(gen.iter_events(timeout_s=60.0))[-1] == ("done", "length")
    finally:
        sched.close()
    names = [name for what, name in log if what == "enter"]
    assert set(names) == {f"decode.loop.{p}" for p in PHASES}
    assert set(names) <= trace_lib.SPAN_NAMES
    # one open at a time: each enter follows the exit of the one before
    assert [what for what, _ in log] == ["enter", "exit"] * len(names)
    assert names[0] == "decode.loop.admit"
    short = [n.rsplit(".", 1)[1] for n in names]
    for i, phase in enumerate(short):
        if phase == "read":
            assert short[i - 1] == "flush" and short[i + 1] == "book"
        if phase == "dispatch":
            assert short[i - 1] == "admit" and short[i + 1] == "flush"
    # one prefill and three steps: four reads
    assert short.count("read") == 4
