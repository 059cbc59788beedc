"""The reduction from a trace to busy time, time per operation and idle
gaps, on a small recorded trace, and the readers that take metrics from it."""

import os

import pytest

from perfbench import manifest as M
from perfbench import reduce_trace as R
from perfbench.run import load_reader

BENCH = os.path.join(M.ROOT, "perfbench")


@pytest.fixture(scope="module")
def reduced():
    import jax

    with open(os.path.join(BENCH, "testdata", "small_trace.textproto")) as f:
        profile = jax.profiler.ProfileData.from_text_proto(f.read())
    names = [p["plane"] for p in R.describe(profile)]
    assert names == ["/device:TPU:0", "/host:CPU"]
    return R.reduce_profile(profile)


def test_busy_window_ops_and_gaps(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(9e-3)     # the device's span, not the host's
    assert reduced["busy_s"] == pytest.approx(6.5e-3)
    (module, (seconds, runs)), = reduced["modules"].items()
    assert module.startswith("jit_call(") and runs == 2 and seconds == pytest.approx(7e-3)
    by_op = {k.split(" ")[0]: v for k, v in reduced["ops"].items()}
    assert by_op["%convert_reduce_fusion.7"] == [pytest.approx(4.5e-3), 2]
    assert by_op["%copy-done.640"] == [pytest.approx(2e-3), 2]
    gaps = reduced["gaps"]
    assert gaps[f"inside {module}"] == [pytest.approx(5e-4), 1]
    assert gaps[f"between {module} and {module}"] == [pytest.approx(2e-3), 1]
    b = R.breakdown(reduced)
    assert b["device_ops"][0][0].startswith("%convert_reduce_fusion.7")
    assert len(b["device_ops"][0][0]) <= 123 and b["idle_gaps"][0][1] == pytest.approx(2e-3)


def test_union_merges_overlaps():
    assert R.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_trace_readers(reduced):
    busy = load_reader(BENCH, "trace_busy")
    run = {"trace": reduced}
    assert busy.read({"value": "idle_pct"}, run) == pytest.approx(100 * (1 - 6.5 / 9))
    assert busy.read({"value": "busy_ms_per_program", "module_pattern": "^jit_"},
                     run) == pytest.approx(3.25)
    assert busy.read({"value": "busy_ms_per_program", "module_pattern": "^nothing"},
                     run) is None
    # nothing to read: nothing returned, never 0
    empty = {"trace": {"devices": 0, "busy_s": 0.0, "window_s": 0.0}}
    assert busy.read({"value": "idle_pct"}, empty) is None
    assert busy.read({"value": "idle_pct"}, {"trace": None}) is None


def test_counter_and_span_readers():
    delta = load_reader(BENCH, "metrics_delta")
    run = {"before": {"server": {"a_sum": 1.0, "a_count": 10.0}},
           "after": {"server": {"a_sum": 3.0, "a_count": 20.0}}}
    spec = {"num": [["server", "a_sum", 1]], "den": [["server", "a_count", 1]],
            "scale": 1000.0}
    assert delta.read(spec, run) == pytest.approx(200.0)
    assert delta.read(dict(spec, den=[["gateway", "a_count", 1]]), run) is None
    assert delta.read(spec, {"before": run["before"], "after": run["before"]}) is None
    spans = load_reader(BENCH, "span_sample")
    sample = [[{"name": "server.request", "dur_ms": 10.0},
               {"name": "server.predict", "dur_ms": 7.0}],
              [{"name": "server.request", "dur_ms": 12.0}]]
    spec = {"spans": [["server.request", 1], ["server.predict", -1]]}
    assert spans.read(spec, {"spans": sample}) == pytest.approx(3.0)
    assert spans.read(spec, {"spans": []}) is None


def test_kernel_roofline_from_the_traces_own_lines():
    """The fused middle-flow kernel under the name the chip's trace printed
    (xception-tensor256-closed, PR 23): 38 calls, 80.5 ms together."""
    import json

    reader = load_reader(BENCH, "trace_ops")
    with open(os.path.join(BENCH, "layer_metrics", "sepconv_roofline.json")) as f:
        spec = json.load(f)
    op = ("%tpu_custom_call.14 = bf16[19,19,256,728]{3,2,1,0:T(8,128)(2,1)} custom-call("
          "bf16[19,19,256,728]{3,2,1,0:T(8,128)(2,1)} %tpu_custom_call.13, "
          "f32[3,3,3,728]{3,2,1,0:T(4,128)S(1)} %copy-done.36)")
    other = "%fusion.76 = bf16[256,37,37,728]{3,0,2,1:T(8,128)(2,1)} fusion(...)"
    peaks = M.load_peaks(BENCH, "TPU v5 lite")
    run = {"trace": {"ops": {op: [0.0805, 38.0], other: [0.19, 38.0]}}, "peaks": peaks}
    from perfbench import flops

    ops, nbytes = flops.sepconv_block(19, 19, 256, 728)
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "compute"
    assert reader.read(spec, run) == pytest.approx(100 * 38 * least / 0.0805)
    assert 50 < reader.read(spec, run) < 100
    assert reader.read(spec, {"trace": {"ops": {other: [0.19, 38.0]}}, "peaks": peaks}) is None
