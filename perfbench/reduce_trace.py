"""From a profiler trace (``*.xplane.pb``) to busy time, time per operation
and idle gaps.  The yardstick's own reduction: every PR computes the same
numbers in the same way.

A trace holds planes; the device's planes are named ``/device:TPU:<n>``
(PERF.md, "What a trace holds").  On a device plane the line ``XLA Ops``
has one event per executed operation and ``XLA Modules`` one per executed
program.  Busy time is the union of the operations' intervals, averaged
over the device planes; the window is the span from the first to the last
event of the device planes.  The host's planes are not read: with the
Python tracer on they hold millions of events, and they run on for seconds
after the device's tracer has stopped, so their extent is not the window.

    python perfbench/reduce_trace.py <trace dir or file> [--out FILE.json]

needs jax for its reader of the format (``jax.profiler.ProfileData``), so
the benchmark's parent runs it as a child with ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys

DEVICE_PREFIXES = ("/device:TPU:",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def describe(profile, most: int = 20000) -> list[dict]:
    """Planes and lines with their event counts (up to ``most`` a line) and
    a few names: what a trace holds, for the look by hand before a
    reduction is trusted."""
    out = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            names: dict[str, int] = {}
            n = 0
            for e in line.events:
                n += 1
                if len(names) < 12:
                    names[e.name] = names.get(e.name, 0) + 1
                if n >= most:
                    break
            lines.append({"line": line.name, "events": n, "names": names})
        out.append({"plane": plane.name, "lines": lines})
    return out


def reduce_profile(profile) -> dict:
    """Times in seconds.  ``busy_s`` is the mean over device planes of the
    union of operation intervals; ``ops`` every operation name with its
    summed time and count (mean over devices); ``modules`` every executed
    program likewise; ``gaps`` the first device's idle time summed by where
    it lies (inside a program, or between two), as {label: [seconds,
    count]}, and ``longest_gaps`` the longest as (at, seconds, label)."""
    first, last = None, None
    devices = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIXES):
            continue
        ops, modules = [], []
        for line in plane.lines:
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                first = a if first is None else min(first, a)
                last = b if last is None else max(last, b)
                if line.name == OPS_LINE:
                    ops.append((a, b, e.name))
                elif line.name == MODULES_LINE:
                    modules.append((a, b, e.name))
        if ops:
            devices.append((plane.name, ops, modules))
    if not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0, "ops": {},
                "modules": {}, "gaps": {}, "longest_gaps": []}
    window_s = (last - first) / 1e9
    busy, op_time, module_time = [], {}, {}
    for _name, ops, modules in reversed(devices):     # ends on the first device
        merged = union([(a, b) for a, b, _ in ops])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for table, events in ((op_time, ops), (module_time, modules)):
            for a, b, name in events:
                t = table.setdefault(name, [0.0, 0])
                t[0] += (b - a) / 1e9 / len(devices)
                t[1] += 1 / len(devices)
    modules = sorted(modules)       # the first device's, as ``merged`` is
    starts = [m[0] for m in modules]
    gaps = []
    edges = [(first, first)] + merged + [(last, last)]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start <= end:
            continue
        k = bisect.bisect_right(starts, end) - 1     # the program begun last
        if k >= 0 and modules[k][1] >= start:
            label = f"inside {modules[k][2]}"
        else:
            before = modules[k][2] if k >= 0 else "trace start"
            after = modules[k + 1][2] if k + 1 < len(modules) else "trace end"
            label = f"between {before} and {after}"
        gaps.append(((end - first) / 1e9, (start - end) / 1e9, label))
    by_label: dict[str, list] = {}
    for _at, seconds, label in gaps:
        t = by_label.setdefault(label, [0.0, 0])
        t[0] += seconds
        t[1] += 1
    return {"window_s": window_s, "busy_s": sum(busy) / len(busy),
            "devices": len(devices), "ops": op_time, "modules": module_time,
            "gaps": by_label,
            "longest_gaps": sorted(gaps, key=lambda g: -g[1])[:TOP]}


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``: the operations that took most device
    time, and the idle time summed by what ran before and after the gap."""
    ops = sorted(((n, t[0]) for n, t in reduced["ops"].items()),
                 key=lambda x: -x[1])[:TOP]
    gaps = sorted(((n, t[0]) for n, t in reduced["gaps"].items()),
                  key=lambda x: -x[1])[:TOP]
    return {"device_ops": [[short_name(n), t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}


def short_name(op: str, most: int = 120) -> str:
    """An operation as the trace prints it is its whole HLO line; keep the
    name and the result's shape."""
    return op if len(op) <= most else op[:most] + "..."


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace")
    p.add_argument("--out", default="")
    p.add_argument("--describe", action="store_true")
    args = p.parse_args(argv)
    import jax

    profile = jax.profiler.ProfileData.from_file(find_xplane(args.trace))
    reduced = reduce_profile(profile)
    if args.describe:
        reduced["describe"] = describe(profile)
    text = json.dumps(reduced)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
