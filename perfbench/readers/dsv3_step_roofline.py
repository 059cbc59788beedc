"""A decode step's share of its roofline, for a DeepSeek-V3-family
configuration: the least time the chip could take to read what one step has
to read (``dsv3_flops.decode_step_bytes``: counted from the configuration
and from the program's counters over the window -- held experts touched and
live positions a step -- never from the trace's operations, so it reads the
same work whatever implements it) against the device time of one
decode-step program (events of "XLA Modules" whose name matches
``module_pattern``).  A step of a few dozen rows is bound by bytes, so the
bandwidth is the peak it is held to.

spec: ``module_pattern``.
"""

import re

SERIES = {
    "steps": "kdlt_decode_steps_total",
    "touched": "kdlt_decode_experts_touched_total",
    "context": "kdlt_decode_context_positions_total",
}


def read(spec: dict, run: dict):
    from perfbench import dsv3_flops
    from perfbench.readers import lm_mfu

    trace, d = run.get("trace"), lm_mfu.deltas(run, SERIES)
    if not trace or not trace.get("modules") or d is None or not d["steps"] \
            or not run.get("peaks"):
        return None
    pattern = re.compile(spec["module_pattern"])
    spent = calls = 0.0
    for name, (seconds, count) in trace["modules"].items():
        if pattern.search(name):
            spent, calls = spent + seconds, calls + count
    if not calls or spent <= 0:
        return None
    nbytes = dsv3_flops.decode_step_bytes(
        run["config"], d["touched"] / d["steps"], d["context"] / d["steps"])
    least = nbytes / (float(run["peaks"]["hbm_gb_per_s"]) * 1e9)
    return 100.0 * least / (spent / calls)
