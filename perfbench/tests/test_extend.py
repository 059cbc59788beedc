"""The proof that no edit is needed.  A copy of ``perfbench/`` gets new
files alone -- a family's reference the benchmark has never seen
(``extend/resnet.py``: ResNet50, which the program serves and the benchmark
has no configuration of), an entry it has never seen
(``extend/server-bytes.py``: the model server's bytes wire), their
configuration, traffic files and entries in ``BENCHMARK.json`` -- and whole
runs are rehearsed on the CPU; every file that was in the copy keeps the
hash it had.  Then the same for a wire the benchmark has no cell of, as a
program PR has to bring it: the committed ``BENCHMARK.json`` with entries
appended to ``configs``, ``workloads`` and ``per_layer`` alone (the token
stand-in of ``standin/``), whose cell reports the two end-to-end metrics
that list no cells, ``setup_s`` and ``latency_p50_ms``."""

import hashlib
import json
import os
import shutil

import pytest

from perfbench import manifest as M
from perfbench import run as R
from perfbench.tests.standin import build as standin

EXTEND = os.path.join(os.path.dirname(os.path.abspath(__file__)), "extend")


def hashes(root) -> dict:
    out = {}
    for base, _dirs, files in os.walk(root):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    root = tmp_path_factory.mktemp("extend")
    shutil.copytree(os.path.join(M.ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = root / "perfbench"
    before = hashes(bench)

    shutil.copy(os.path.join(EXTEND, "resnet.py"), bench / "reference" / "resnet.py")
    shutil.copy(os.path.join(EXTEND, "server-bytes.py"), bench / "entries" / "server-bytes.py")
    config = {
        "name": "tiny-resnet50", "served_name": "tiny-resnet50", "family": "resnet50",
        "reference": "resnet", "input_shape": [64, 64, 3], "num_classes": 10,
        "preprocessing": "tf", "resize_filter": "bilinear", "compute_dtype": "bfloat16",
        "fast_path": False, "artifact_module": False,
        "assumed": {"calibration": {"pictures": 4, "side": 64}, "reference_block": 4},
        "limits": {"logit_err": 0.08},
    }
    json.dump(config, open(bench / "configs" / "tiny-resnet50.json", "w"))
    common = {
        "generator": "closed", "callers": 2, "images_per_request": 4, "bodies": 4,
        "request_timeout_s": 120, "server_buckets": [4], "lead_in_s": 0.5,
        "warm": {"steady_rounds": 2, "steady_within": 1.5, "settle_timeout_s": 0,
                 "min_seconds": 0},
        "trace_offset_s": 0.3, "trace_seconds": 0.5, "span_recent": 50, "span_sample": 4,
    }
    json.dump(dict(common, entry="server-bytes",
                   pictures={"pool": 8, "side_min": 80, "side_max": 120,
                             "formats": ["jpeg", "png"], "jpeg_quality": 92, "png_level": 1}),
              open(bench / "traffic" / "tiny-bytes.json", "w"))
    json.dump(dict(common, entry="server-tensor", pool=16),
              open(bench / "traffic" / "tiny-tensor4.json", "w"))
    json.dump({"reader": "metrics_delta", "scale": 1.0,
               "num": [["server", "kdlt_engine_images_total", 1]],
               "den": [["server", "kdlt_engine_batches_total", 1]]},
              open(bench / "layer_metrics" / "mean_batch.tiny.json", "w"))
    d = json.load(open(os.path.join(M.ROOT, "BENCHMARK.json")))
    d["configs"] = [{"name": "tiny-resnet50", "source": "https://arxiv.org/abs/1512.03385",
                     "reduced": [], "why": "y",
                     "file": "perfbench/configs/tiny-resnet50.json"}]
    d["workloads"] = [{"name": "new-entry", "config": "tiny-resnet50",
                       "traffic": "tiny-bytes", "chips": 1, "why": "y"},
                      {"name": "new-family", "config": "tiny-resnet50",
                       "traffic": "tiny-tensor4", "chips": 1, "why": "y"}]
    d["end_to_end"] = [
        {"name": "images_per_s", "unit": "images/s", "better": "higher", "bound": 0.01,
         "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"}]
    d["per_layer"] = [{"name": "mean_batch.tiny", "unit": "images", "better": "higher",
                       "source": "program_counter", "layer": "batching",
                       "moves": "images_per_s"}]
    json.dump(d, open(root / "BENCHMARK.json", "w"))
    m = M.Manifest(str(root))
    m.validate()
    yield m, str(root / "work")
    after = hashes(bench)
    assert {k: after.get(k) for k in before} == before     # no file edited or removed
    assert len(after) == len(before) + 6                    # and six added


@pytest.mark.parametrize("cell", ["new-entry", "new-family"])
def test_a_family_and_an_entry_arrive_as_files(extended, cell):
    """``new-entry``: the family the benchmark has never seen on an entry it
    has never seen.  ``new-family``: the same family through the tensor
    entry the benchmark has, with the reference's file alone."""
    manifest, work = extended
    run = R.CellRun(manifest, manifest.cell(cell), 2**31 + 26, 2.0, True,
                    platform="cpu", work_root=work)
    try:
        line = run.run()
    finally:
        run.children.kill_all()
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["rows_compared"]["value"] == 4 * line["attempted"]
    assert 0 < line["compared"]["logit_err"]["value"] < 0.08
    assert line["metrics"]["mean_batch.tiny"]["value"] == 4.0
    e2e = run.end_to_end()
    assert set(e2e) == {"images_per_s", "setup_s"} and e2e["images_per_s"]["value"] > 0


def test_an_unknown_entry_generator_or_quantity_is_an_error(extended, tmp_path):
    manifest, _work = extended
    cell = manifest.cell("new-family")
    for change, match in ((dict(entry="no-such-entry"), "no perfbench/entries/no-such-entry"),
                          (dict(generator="open-poisson"), "takes the generators")):
        odd = M.Cell(**{**cell.__dict__, "traffic": dict(cell.traffic, **change)})
        with pytest.raises(M.ManifestError, match=match):
            R.CellRun(manifest, odd, 1, 1.0, False, platform="cpu", work_root=str(tmp_path))
    for metric, match in (({"name": "ttft_p50_ms", "unit": "ms"},
                           "gives no end-to-end quantity for 'ttft_p50_ms'"),
                          ({"name": "latency_p50_ms.tiny", "unit": "s"}, "is in ms, not s")):
        odd = M.Cell(**{**cell.__dict__, "end_to_end": cell.end_to_end + (metric,)})
        with pytest.raises(M.ManifestError, match=match):     # before anything runs
            R.CellRun(manifest, odd, 1, 1.0, False, platform="cpu", work_root=str(tmp_path))


def test_a_metric_arrives_with_its_first_cell(tmp_path):
    """The committed manifest validates, and a metric that lists no cell is
    refused as the driver refuses it: the token quantities are the entry's to
    give, and their ``end_to_end`` entries come with the first cell that
    reports them."""
    m = M.Manifest()
    m.validate()
    tokens = M.load_module(m.bench_dir, "entries", "server-generate").QUANTITIES
    assert {"output_tokens_per_s", "ttft_p50_ms", "itl_p95_ms"} <= set(tokens)
    # all the two wires share is the one that lists no cells
    assert set(tokens) & {e["name"] for e in m.data["end_to_end"]} == {"latency_p50_ms"}
    d = json.loads(json.dumps(m.data))
    d["end_to_end"].append({"name": "ttft_p50_ms", "unit": "ms", "better": "lower",
                            "bound": 0.1, "source": "host_clock", "workloads": []})
    json.dump(d, open(tmp_path / "BENCHMARK.json", "w"))
    with pytest.raises(M.ManifestError, match="lists no cell"):
        M.Manifest(str(tmp_path), bench_dir=m.bench_dir).validate()


# --- a new wire's first cell, as a program PR may bring it ---------------------------------


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    root = tmp_path_factory.mktemp("appended")
    standin.build(str(root), appended=True)
    m = M.Manifest(str(root))
    m.validate()
    yield m, str(root / "work")
    committed = hashes(os.path.join(M.ROOT, "perfbench"))
    after = hashes(m.bench_dir)
    kept = {k: v for k, v in committed.items()
            if "__pycache__" not in k and not k.startswith("tests" + os.sep)}
    assert {k: after.get(k) for k in kept} == kept          # no committed file edited
    assert len(after) == len(kept) + 8                       # and eight added


def test_a_new_wires_first_cell_arrives_by_appended_entries(appended):
    """What PR 27 could not do and the next ``model_config`` PR has to: no
    end-to-end entry is added, and the token cell still reports two."""
    manifest, work = appended
    committed = M.Manifest().data
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert manifest.data[key] == committed[key]
    for key in ("configs", "workloads", "per_layer"):
        n = len(committed[key])
        assert manifest.data[key][:n] == committed[key] and len(manifest.data[key]) > n
    cell = manifest.cell("standin-closed")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "latency_p50_ms"]
    assert {m["moves"] for m, _ in cell.per_layer} == {"latency_p50_ms"}
    run = standin.LaneRun(manifest, cell, 2**31 + 28, 3.0, False, platform="cpu",
                          work_root=work)
    try:
        line = run.run()
    finally:
        run.children.kill_all()
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["tokens_compared"]["value"] == 6 * 64
    assert set(line["metrics"]) == {"setup_s", "latency_p50_ms"}
    assert line["metrics"]["latency_p50_ms"] == {
        "value": run.entry.quantities(run)["latency_p50_ms"], "unit": "ms"}
    assert 0 < line["metrics"]["latency_p50_ms"]["value"] < 3000.0
    # the image cells report it too, from the picture entries' own arithmetic
    for name in committed["workloads"]:
        assert "latency_p50_ms" in {m["name"] for m in manifest.cell(name["name"]).end_to_end}


def test_a_quantity_that_lists_no_cells_binds_every_entry(appended, tmp_path):
    """An end-to-end metric without a ``workloads`` list is reported by every
    cell, so a cell whose entry does not give it is an error that names it:
    a wire's own metrics list their cells."""
    manifest, _work = appended
    d = json.loads(json.dumps(manifest.data))
    d["end_to_end"].append({"name": "output_tokens_per_s", "unit": "tokens/s",
                            "better": "higher", "bound": 0.05, "source": "host_clock"})
    os.symlink(manifest.bench_dir, tmp_path / "perfbench")
    json.dump(d, open(tmp_path / "BENCHMARK.json", "w"))
    with pytest.raises(M.ManifestError, match="'server-tensor' gives no end-to-end quantity "
                                              "for 'output_tokens_per_s'"):
        M.Manifest(str(tmp_path)).validate()
    d["end_to_end"][-1]["workloads"] = ["standin-closed", "standin-open"]
    json.dump(d, open(tmp_path / "BENCHMARK.json", "w"))
    M.Manifest(str(tmp_path)).validate()
