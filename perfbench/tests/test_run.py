"""A rehearsal of a whole run at a tiny size on the CPU, never a cell: the
harness's look for a chip is skipped (``platform="cpu"``) and the rest of a
run is driven as on the chip -- artifact child, model server, traffic,
window, reference child, verdict, line.  Then the same with the timed path
broken underneath, which has to come out as not correct, and the control
(the reference in fp8 in the program's place), which has to read at least
three times what the program reads."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import run as R
from perfbench import traffic

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A manifest of one tiny cell, made the way a later PR adds one: new
    files beside the benchmark's own, new entries."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(os.path.join(M.ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "perfbench"
    cfg = json.load(open(bench / "configs" / "xception-clothing-299.json"))
    cfg.update(name="tiny-xception", input_shape=[96, 96, 3], fast_path=False)
    cfg["assumed"].update(calibration={"pictures": 4, "side": 64}, reference_block=4)
    json.dump(cfg, open(bench / "configs" / "tiny-xception.json", "w"))
    mix = json.load(open(bench / "traffic" / "tensor64-closed.json"))
    mix.update(callers=2, images_per_request=4, pool=16, bodies=4, server_buckets=[4],
               lead_in_s=0.5, trace_offset_s=0.3, trace_seconds=0.5)
    mix["warm"].update(min_seconds=0, settle_timeout_s=0)
    json.dump(mix, open(bench / "traffic" / "tiny-tensor.json", "w"))
    d = json.load(open(os.path.join(M.ROOT, "BENCHMARK.json")))
    d["configs"] = [{"name": "tiny-xception", "source": "x", "reduced": [], "why": "y",
                     "file": "perfbench/configs/tiny-xception.json"}]
    d["workloads"] = [{"name": "tiny", "config": "tiny-xception",
                       "traffic": "tiny-tensor", "chips": 1, "why": "y"}]
    for m in d["end_to_end"] + d["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny"] if "xception-tensor512-closed" in m["workloads"] else []
    d["end_to_end"] = [m for m in d["end_to_end"] if m.get("workloads", ["tiny"])]
    d["per_layer"] = [m for m in d["per_layer"] if m["workloads"]]
    json.dump(d, open(root / "BENCHMARK.json", "w"))
    m = M.Manifest(str(root))
    m.validate()
    return m, str(root / "work")


def drive(tiny, seed, trace=False):
    manifest, work = tiny
    run = R.CellRun(manifest, manifest.cell("tiny"), seed, 3.0, trace,
                    platform="cpu", work_root=work)
    try:
        return run, run.run()
    finally:
        run.children.kill_all()


def test_whole_run_line_and_control(tiny, capfd):
    run, line = drive(tiny, 2**31 + 5, trace=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "compared"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    # a traced run's metrics are the cell's per-layer metrics, and only
    # those that found something to read (no device trace on the CPU)
    names = {m["name"] for m, _ in run.cell.per_layer}
    assert set(line["metrics"]) <= names and "dispatch_ms.x512" in line["metrics"]
    assert "device_idle_pct.x512" not in line["metrics"]
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] >= 0   # a true zero reads 0
    R.report(line)
    out, err = capfd.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert last["correct"] is True and len(out.strip().splitlines()[-1]) < 8000
    assert "logit_err=" in err.strip().splitlines()[-1]

    # the control: the reference in fp8, put in the program's place
    ctl = os.path.join(run.work, "control.npy")
    subprocess.run([sys.executable, run.child_script("reference.py"),
                    "--config", run.config_path(), "--precision", "fp8",
                    "--params", os.path.join(run.work, "models", run.model, "1",
                                             "params.msgpack"),
                    "--seed", str(run.seed), "--out", ctl, *run.reference_inputs],
                   check=True, env=run.host_env, cwd=M.ROOT)
    ref = run.reference
    control_err = float(np.abs(np.load(ctl) - ref).max() / np.abs(ref).max())
    assert control_err >= 3 * line["compared"]["logit_err"]["value"]

    # half of a batch answered with the other half's rows: every row is
    # compared, so the run's own verdict turns
    for o in run.window:
        if o.scores is not None:
            o.scores = np.concatenate([o.scores[:2], o.scores[:2]])
    assert run.compare()["logit_err"]["value"] > control_err


def test_an_altered_answer_is_not_correct(tiny, monkeypatch):
    """The timed path broken where an answer is produced: one row of one
    reply in ten comes back altered."""
    send = traffic.ServerTensor.send

    def altered(self, conn_box, o, timeout, body_index):
        send(self, conn_box, o, timeout, body_index)
        if o.scores is not None and o.index % 10 == 3:
            o.scores = o.scores.copy()
            o.scores[1] = o.scores[1][::-1]
    monkeypatch.setattr(traffic.ServerTensor, "send", altered)
    _run, line = drive(tiny, 77)
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["logit_err"]["value"] > line["compared"]["logit_err"]["limit"]
    assert "images_per_s.x512" in line["metrics"] and "setup_s" in line["metrics"]


def test_url_entry_rehearsal(tiny, tmp_path):
    """The gateway's URL entry, which no cell enters yet (PERF.md section
    7): picture host, gateway, open-loop Poisson arrivals, latency from the
    due time -- a cell made of new files and entries alone."""
    manifest, _work = tiny
    root = tmp_path / "root"
    shutil.copytree(manifest.root, root, ignore=shutil.ignore_patterns("work"))
    bench = root / "perfbench"
    mix = json.load(open(bench / "traffic" / "url-open-poisson.json"))
    mix.update(rate_per_s=6.0, workers=8, server_buckets=[1, 4], lead_in_s=0.5,
               pictures_per_request=2)
    mix["pictures"].update(pool=4, side_min=80, side_max=120)
    mix["warm"].update(min_seconds=0, settle_timeout_s=0)
    json.dump(mix, open(bench / "traffic" / "tiny-url.json", "w"))
    d = json.load(open(root / "BENCHMARK.json"))
    d["workloads"].append({"name": "tiny-url", "config": "tiny-xception",
                           "traffic": "tiny-url", "chips": 1, "why": "y"})
    d["end_to_end"].append({"name": "latency_p95_ms", "unit": "ms", "better": "lower",
                            "bound": 0.05, "source": "host_clock", "workloads": ["tiny-url"]})
    d["per_layer"] += [{"name": n, "unit": "ms", "better": "lower", "source": "host_clock",
                        "layer": "x", "moves": "latency_p50_ms", "workloads": ["tiny-url"]}
                       for n in ("gateway_self_ms", "ingest_decode_ms", "gen_late_p95_ms")]
    json.dump(d, open(root / "BENCHMARK.json", "w"))
    m = M.Manifest(str(root))
    m.validate()
    run = R.CellRun(m, m.cell("tiny-url"), 2**31 + 9, 3.0, True, platform="cpu",
                    work_root=str(root / "work"))
    try:
        line = run.run()
    finally:
        run.children.kill_all()
    assert line["correct"] is True and line["attempted"] == 18 and line["failed"] == 0
    assert line["compared"]["rows_compared"]["value"] == 36
    assert {"gateway_self_ms", "ingest_decode_ms", "gen_late_p95_ms"} <= set(line["metrics"])
    e2e = run.end_to_end()
    assert 0 < e2e["latency_p50_ms"]["value"] <= e2e["latency_p95_ms"]["value"] < 60_000


def test_no_program_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and perfbench/ the
    command exits non-zero and prints no result."""
    shutil.copytree(os.path.join(M.ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(M.ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    r = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "effnetb7-tensor64-closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True)
    assert r.returncode != 0 and r.stdout.strip() == ""
