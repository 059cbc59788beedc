"""The manifest and every file it names parse, resolve by name and keep to
the contract's letter; a cell with a missing file or an unknown device
fails; a later PR adds one of each kind of file and edits none."""

import json
import os
import re
import shutil

import pytest

from perfbench import manifest as M

ROOT = M.ROOT


def test_manifest_validates_and_every_cell_resolves():
    m = M.Manifest()
    m.validate()
    no_list = [e["name"] for e in m.data["end_to_end"] if "workloads" not in e]
    assert no_list == ["setup_s", "latency_p50_ms"]      # what every cell reports
    for name in m.workloads:
        cell = m.cell(name)
        assert set(no_list) < {e["name"] for e in cell.end_to_end}
        assert cell.config["name"] == cell.config_name
        assert cell.traffic["generator"] in ("open-poisson", "closed")
        assert cell.traffic["entry"] in ("gateway-url", "server-tensor")
        for _, spec in cell.per_layer:
            assert os.path.exists(os.path.join(m.bench_dir, "readers", spec["reader"] + ".py"))


def test_contract_letter():
    d = M.Manifest().data
    assert set(d) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    assert len(json.dumps(d)) < 64 * 1024
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perfbench/") and len(c["reduced"]) <= 16
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    text = [x["why"] for x in d["configs"] + d["workloads"]]
    text += [x["source"] for x in d["configs"]] + [m["layer"] for m in d["per_layer"]]
    text += d["command"]
    for t in text:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    mfu = [m["name"] for m in d["per_layer"] if "mfu" in re.split(r"[._\-]", m["name"])]
    assert mfu, "the whole step's share of the peak is reported"


def test_file_names_use_the_names_characters():
    for base, _dirs, files in os.walk(os.path.join(ROOT, "perfbench")):
        if "__pycache__" in base:
            continue
        for f in files:
            assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), os.path.join(base, f)


def _copy(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def test_missing_file_is_an_error_not_a_skip(tmp_path):
    root = _copy(tmp_path)
    os.remove(root / "perfbench" / "traffic" / "tensor64-closed.json")
    m = M.Manifest(str(root))
    with pytest.raises(M.ManifestError, match="missing file"):
        m.cell("effnetb7-tensor64-closed")
    os.remove(root / "perfbench" / "layer_metrics" / "sepconv_roofline.json")
    with pytest.raises(M.ManifestError, match="missing file"):
        m.cell("xception-tensor512-closed")
    with pytest.raises(M.ManifestError, match="unknown workload"):
        m.cell("no-such-cell")


def test_unknown_device_kind_is_an_error():
    bench = os.path.join(ROOT, "perfbench")
    assert M.load_peaks(bench, "TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(M.ManifestError, match="not in perfbench/peaks.json"):
        M.load_peaks(bench, "TPU v9 imaginary")


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """One new configuration, traffic mix, per-layer metric (with a reader
    of a new kind) and cell: new files, new entries, no edit."""
    root = _copy(tmp_path)
    bench = root / "perfbench"
    cfg = json.load(open(bench / "configs" / "xception-clothing-299.json"))
    cfg["name"] = "xception-other"
    json.dump(cfg, open(bench / "configs" / "xception-other.json", "w"))
    mix = json.load(open(bench / "traffic" / "tensor256-closed.json"))
    mix["callers"] = 2
    json.dump(mix, open(bench / "traffic" / "tensor256-two.json", "w"))
    json.dump({"reader": "constant", "value": 7.0},
              open(bench / "layer_metrics" / "new_metric.json", "w"))
    (bench / "readers" / "constant.py").write_text(
        "def read(spec, run):\n    return spec['value']\n")
    d = json.load(open(root / "BENCHMARK.json"))
    d["configs"].append({"name": "xception-other", "source": "x", "reduced": [],
                         "file": "perfbench/configs/xception-other.json", "why": "y"})
    d["workloads"].append({"name": "other.two", "config": "xception-other",
                           "traffic": "tensor256-two", "chips": 1, "why": "y"})
    d["end_to_end"][0]["workloads"].append("other.two")
    d["per_layer"].append({"name": "new_metric", "unit": "ms", "better": "lower",
                           "source": "program_counter", "layer": "new layer",
                           "moves": "images_per_s", "workloads": ["other.two"]})
    json.dump(d, open(root / "BENCHMARK.json", "w"))
    m = M.Manifest(str(root))
    m.validate()
    cell = m.cell("other.two")
    assert cell.traffic["callers"] == 2 and cell.config["name"] == "xception-other"
    from perfbench.run import load_reader

    (entry, spec), = cell.per_layer
    assert load_reader(m.bench_dir, spec["reader"]).read(spec, {}) == 7.0
