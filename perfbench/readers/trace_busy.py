"""Device busy and idle time from the profiler's trace.

spec ``value``: ``idle_pct`` = 100 * (1 - busy / window), or
``busy_ms_per_program`` = busy time over the programs the device executed
in the traced window (events of the line "XLA Modules" whose name matches
``module_pattern``; one per batch on the serving path).
"""

import re


def read(spec: dict, run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("devices") or trace["busy_s"] <= 0:
        return None
    if spec["value"] == "idle_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if spec["value"] == "busy_ms_per_program":
        pattern = re.compile(spec["module_pattern"])
        runs = sum(t[1] for name, t in trace["modules"].items() if pattern.search(name))
        if not runs:
            return None
        return 1000.0 * trace["busy_s"] / runs
    raise ValueError(f"unknown value {spec['value']!r}")
