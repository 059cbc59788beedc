"""The twelve per-layer metrics that say why the chip waits (PR 24): six
quantities, one entry each for the B7 cell (``.tensor``) and the Xception
cell (``.x512``).  All are data for the readers that were there
(``span_sample``, ``metrics_delta``); against a program that lacks the
spans and counters they read nothing and do not raise; and a rehearsed run
on the CPU carries all six of its cell in its line."""

import pytest

from perfbench import manifest as M
from perfbench import run as R
from perfbench.tests.test_run import drive, tiny  # noqa: F401 - the fixture

QUANTITIES = {
    # name: (reader, source, layer)
    "body_read_ms": ("span_sample", "program_span", "model server front"),
    "unpack_ms": ("span_sample", "program_span", "model server front"),
    "queue_wait_ms": ("span_sample", "program_span", "batching"),
    "enqueue_wait_ms": ("metrics_delta", "program_counter", "dispatch"),
    "device_starved_pct": ("metrics_delta", "program_counter", "device"),
    "starved_dispatch_pct": ("metrics_delta", "program_counter", "dispatch"),
}
CELLS = {
    "tensor": ("effnetb7-tensor64-closed", "images_per_s"),
    "x512": ("xception-tensor512-closed", "images_per_s.x512"),
}
IDLE = ["kdlt_pipeline_idle_dispatch_seconds_total",
        "kdlt_pipeline_idle_no_batch_seconds_total"]
INFLIGHT = "kdlt_pipeline_inflight_seconds_total"


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("quantity", sorted(QUANTITIES))
def test_entry_resolves_its_data_file_and_reader(quantity, suffix):
    m = M.Manifest()
    m.validate()
    cell_name, moves = CELLS[suffix]
    reader, source, layer = QUANTITIES[quantity]
    name = f"{quantity}.{suffix}"
    entry = [e for e in m.data["per_layer"] if e["name"] == name]
    assert len(entry) == 1, name
    unit = "%" if quantity.endswith("_pct") else "ms"
    assert entry[0] == {"name": name, "unit": unit, "better": "lower", "source": source,
                        "layer": layer, "moves": moves, "workloads": [cell_name]}
    spec = next(s for e, s in m.cell(cell_name).per_layer if e["name"] == name)
    assert spec["reader"] == reader and spec["what"]
    assert hasattr(R.load_reader(m.bench_dir, reader), "read")
    other = CELLS["x512" if suffix == "tensor" else "tensor"][0]
    assert name not in {e["name"] for e, _ in m.cell(other).per_layer}


def test_the_twelve_are_appended_and_nothing_before_them_moved():
    names = [e["name"] for e in M.Manifest().data["per_layer"]]
    assert names[:11] == [
        "server_front_ms.tensor", "dispatch_ms.tensor", "step_device_ms.tensor",
        "step_mfu_pct.tensor", "device_idle_pct.tensor", "server_front_ms.x512",
        "dispatch_ms.x512", "step_device_ms.x512", "step_mfu_pct.x512",
        "device_idle_pct.x512", "sepconv_roofline"]
    assert sorted(names[11:]) == sorted(f"{q}.{s}" for q in QUANTITIES for s in CELLS)


def _specs(suffix):
    m = M.Manifest()
    return m, {e["name"].rsplit(".", 1)[0]: s
               for e, s in m.cell(CELLS[suffix][0]).per_layer
               if e["name"].rsplit(".", 1)[0] in QUANTITIES}


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_the_arithmetic_of_each_reader_on_made_up_numbers(suffix):
    m, specs = _specs(suffix)
    before = {"server": {IDLE[0]: 10.0, IDLE[1]: 2.0, INFLIGHT: 30.0,
                         "kdlt_pipeline_enqueue_wait_seconds_sum": 1.0,
                         "kdlt_pipeline_enqueue_wait_seconds_count": 100.0}}
    after = {"server": {IDLE[0]: 26.0, IDLE[1]: 6.0, INFLIGHT: 50.0,
                        "kdlt_pipeline_enqueue_wait_seconds_sum": 1.5,
                        "kdlt_pipeline_enqueue_wait_seconds_count": 300.0}}
    spans = [[{"name": "server.read_body", "dur_ms": 900.0},
              {"name": "server.unpack", "dur_ms": 60.0},
              {"name": "batcher.queue_wait", "dur_ms": 4.0}],
             [{"name": "server.read_body", "dur_ms": 700.0},
              {"name": "server.unpack", "dur_ms": 40.0},
              {"name": "batcher.queue_wait", "dur_ms": 8.0}]]
    run = {"before": before, "after": after, "spans": spans}
    got = {q: R.load_reader(m.bench_dir, s["reader"]).read(s, run)
           for q, s in specs.items()}
    # 16 + 4 idle seconds of 40: 50% starved, 40% of the whole in dispatch
    assert got == pytest.approx({
        "body_read_ms": 800.0, "unpack_ms": 50.0, "queue_wait_ms": 6.0,
        "enqueue_wait_ms": 2.5, "device_starved_pct": 50.0,
        "starved_dispatch_pct": 40.0})


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_a_program_without_the_spans_and_counters_reads_nothing(suffix):
    """The parent commit: no such counter on /metrics, no such span in a
    request's page.  The line leaves the metric out; nothing raises."""
    m, specs = _specs(suffix)
    page = {"kdlt_pipeline_dispatch_seconds_sum": 3.0,
            "kdlt_pipeline_dispatch_seconds_count": 9.0}
    run = {"before": {"server": dict(page)}, "after": {"server": dict(page)},
           "spans": [[{"name": "server.request", "dur_ms": 5.0},
                      {"name": "server.decode", "dur_ms": 3.0}]]}
    for q in ("body_read_ms", "unpack_ms", "device_starved_pct", "starved_dispatch_pct"):
        assert R.load_reader(m.bench_dir, specs[q]["reader"]).read(specs[q], run) is None


@pytest.fixture(scope="module")
def rehearsed(tiny):
    """A whole traced run of ``test_run.py``'s tiny cell on the CPU; it
    reports the Xception cell's metrics."""
    return drive(tiny, 2**31 + 24, trace=True)


def test_a_rehearsed_line_carries_the_six_of_its_cell(rehearsed):
    _run, line = rehearsed
    assert line["correct"] is True and line["failed"] == 0
    for q in QUANTITIES:
        v = line["metrics"][f"{q}.x512"]
        assert v["unit"] == ("%" if q.endswith("_pct") else "ms") and v["value"] >= 0
    starved = line["metrics"]["device_starved_pct.x512"]["value"]
    assert 0 <= line["metrics"]["starved_dispatch_pct.x512"]["value"] <= starved <= 100
    assert line["metrics"]["body_read_ms.x512"]["value"] > 0


def test_the_three_counters_cover_the_time_between_the_snapshots(rehearsed):
    """Every instant of the dispatcher's life is booked to one cause, so
    between the window's two scrapes the three advance by the seconds that
    passed: the window (3 s) and the drain after it."""
    run, _line = rehearsed
    delta = sum(run.after["server"][s] - run.before["server"][s] for s in IDLE + [INFLIGHT])
    assert 2.5 <= delta <= 3.0 + 10.0
    # and the program's page has no trace of what this PR removed
    assert not [s for s in run.after["server"] if "mfu" in s or "busy_ratio" in s]
