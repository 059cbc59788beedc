"""``BENCHMARK.json`` and the files it names: found by name, never by edit.

A later PR adds a cell by adding files under ``perfbench/`` and entries in
``BENCHMARK.json``; it edits no file that is there.  What it may add, by
kind of file:

- ``configs/<name>.json``: a configuration as it is run, with its limits;
  it names its ``reference`` and, where the defaults do not fit, its
  ``artifact_child`` and ``reference_child``.
- ``reference/<config["reference"]>.py``: the plain reference of a family,
  importing nothing of the program.
- ``traffic/<name>.json``: the parameters of a mix; it names its ``entry``
  and its ``generator``.
- ``entries/<traffic["entry"]>.py``: all that a run knows of one wire of
  the system: inputs, server arguments and checks, warm-up, drive,
  comparison, end-to-end quantities (``perfbench/run.py`` lists them).
- ``children/<name>.py``: a child process a configuration names to write
  its artifact or to run its reference.
- ``layer_metrics/<name>.json``: one per-layer metric; it names its reader.
- ``readers/<reader>.py``: one kind of reading from spans, counters, trace.

Nothing here knows a name: a cell whose file is missing is an error, not a
skip.

Which PR may add what.  A PR that changes the program may add files under
``perfbench/`` and entries at the ends of ``configs``, ``workloads`` and
``per_layer``, and nothing else: an ``end_to_end`` entry can refuse a PR,
so it is a ``benchmark`` PR's to add, with a bound from measured runs.  An
end-to-end metric without a ``workloads`` list is reported by every cell
there is and every cell to come (``Manifest.reports``): ``setup_s`` and
``latency_p50_ms``, which every entry has to give.  So the first cell of a
new wire arrives in a program PR reporting those two, its per-layer
metrics moving ``latency_p50_ms``; the wire's own end-to-end metrics come
in the next ``benchmark`` PR, listing the cell that then exists.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    """The manifest or a file it names is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            out = json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file {os.path.relpath(path, ROOT)}") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{os.path.relpath(path, ROOT)}: {e}") from None
    if not isinstance(out, dict):
        raise ManifestError(f"{os.path.relpath(path, ROOT)}: not a JSON object")
    return out


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # perfbench/configs/<config>.json as it is run
    traffic_name: str
    traffic: dict         # perfbench/traffic/<traffic>.json
    end_to_end: tuple     # the manifest's end-to-end entries this cell reports
    per_layer: tuple      # (entry, layer_metrics/<name>.json) this cell reports


class Manifest:
    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "perfbench")
        self.data = _load_json(os.path.join(root, "BENCHMARK.json"))
        for key in ("command", "paths", "run_seconds", "configs", "workloads",
                    "end_to_end", "per_layer"):
            if key not in self.data:
                raise ManifestError(f"BENCHMARK.json lacks {key!r}")
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.workloads = {w["name"]: w for w in self.data["workloads"]}

    def reports(self, metric: dict, cell_name: str) -> bool:
        return "workloads" not in metric or cell_name in metric["workloads"]

    def cell(self, name: str) -> Cell:
        try:
            w = self.workloads[name]
        except KeyError:
            raise ManifestError(
                f"unknown workload {name!r}; known: {sorted(self.workloads)}"
            ) from None
        try:
            entry = self.configs[w["config"]]
        except KeyError:
            raise ManifestError(f"workload {name!r} names unknown config "
                                f"{w['config']!r}") from None
        config = _load_json(os.path.join(self.root, entry["file"]))
        traffic = _load_json(
            os.path.join(self.bench_dir, "traffic", w["traffic"] + ".json"))
        e2e = tuple(m for m in self.data["end_to_end"] if self.reports(m, name))
        layer = tuple(
            (m, _load_json(os.path.join(self.bench_dir, "layer_metrics",
                                        m["name"] + ".json")))
            for m in self.data["per_layer"] if self.reports(m, name)
        )
        return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                    traffic, e2e, layer)

    def entry(self, cell: Cell):
        """``entries/<traffic["entry"]>.py`` of a cell, held to what the cell
        asks of it before anything runs: its generator, and every end-to-end
        metric the cell reports, by quantity (the name up to its first dot;
        what follows only tells cells apart that are held to different
        bounds) and unit."""
        mix = cell.traffic
        entry = load_module(self.bench_dir, "entries", mix["entry"])
        if mix["generator"] not in entry.GENERATORS:
            raise ManifestError(f"entry {mix['entry']!r} takes the generators "
                                f"{entry.GENERATORS}, not {mix['generator']!r}")
        units = dict(entry.QUANTITIES, setup_s="s")
        for m in cell.end_to_end:
            kind = m["name"].split(".")[0]
            if kind not in units:
                raise ManifestError(
                    f"entry {mix['entry']!r} gives no end-to-end quantity for "
                    f"{m['name']!r}, which {cell.name} reports")
            if m["unit"] != units[kind]:
                raise ManifestError(f"{m['name']} is in {units[kind]}, not {m['unit']}")
        return entry

    def validate(self) -> None:
        """The contract's rules that a file can be checked against here."""
        d = self.data
        names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
        for group in (names, list(self.workloads), list(self.configs)):
            if len(set(group)) != len(group):
                raise ManifestError(f"duplicate name in {group}")
        for n in (names + list(self.workloads) + list(self.configs)
                  + [w["traffic"] for w in d["workloads"]]):
            if not NAME_RE.match(n):
                raise ManifestError(f"bad name {n!r}")
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("no setup_s among end_to_end")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                raise ManifestError(f"bad unit {m['unit']!r} on {m['name']}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"bad 'better' on {m['name']}")
            if m["source"] not in SOURCES:
                raise ManifestError(f"bad source on {m['name']}")
            if "workloads" in m and not m["workloads"]:
                # a metric arrives with the first cell that reports it
                raise ManifestError(f"{m['name']} lists no cell")
            for wl in m.get("workloads", ()):
                if wl not in self.workloads:
                    raise ManifestError(f"{m['name']} lists unknown cell {wl!r}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"end-to-end {m['name']} from {m['source']}")
            if not 0 < m["bound"] <= 0.1:
                raise ManifestError(f"bound of {m['name']} outside (0, 0.1]")
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                raise ManifestError(f"{m['name']} moves unknown {m['moves']!r}")
        used = {w["config"] for w in d["workloads"]}
        if used != set(self.configs):
            raise ManifestError(f"configs unused or unknown: {used ^ set(self.configs)}")
        for name in self.workloads:
            cell = self.cell(name)  # every file resolves
            self.entry(cell)        # and its entry gives what the cell reports
            reported = {m["name"] for m in cell.end_to_end}
            if "setup_s" not in reported or len(reported) < 2:
                raise ManifestError(f"{name} reports too few end-to-end metrics")
            if not cell.per_layer:
                raise ManifestError(f"{name} reports no per-layer metric")
            for m, _ in cell.per_layer:
                if m["moves"] not in reported:
                    raise ManifestError(
                        f"{m['name']} moves {m['moves']}, which {name} does not report")


def load_module(bench_dir: str, kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module, found by its name."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not NAME_RE.match(name) or not os.path.exists(path):
        raise ManifestError(f"no perfbench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_%s_%s" % (kind, re.sub(r"\W", "_", name)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_peaks(bench_dir: str, device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = _load_json(os.path.join(bench_dir, "peaks.json"))
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise ManifestError(
            f"device_kind {device_kind!r} is not in perfbench/peaks.json "
            f"(known: {sorted(table.get('devices', {}))})") from None
