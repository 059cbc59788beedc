"""A kernel's share of its roofline, from the trace's operations.

spec: ``pattern``, a regular expression on the operation's name as the
trace prints it (the HLO line, shapes included) with named groups that
``count`` needs; ``count``, the name of a function in perfbench/flops.py
that turns those groups into (operations, bytes) of one call.  The value is
100 * (sum over calls of the least time the chip could take) / (sum of the
calls' device time); the least time is the larger of operations over the
bf16 peak and bytes over the memory bandwidth.  Finds no such operation:
returns nothing.
"""

import re


def read(spec: dict, run: dict):
    from perfbench import flops

    trace = run.get("trace")
    if not trace or not trace.get("ops") or not run.get("peaks"):
        return None
    pattern, count = re.compile(spec["pattern"]), getattr(flops, spec["count"])
    least = spent = 0.0
    for name, (seconds, calls) in trace["ops"].items():
        m = pattern.search(name)
        if not m:
            continue
        ops, nbytes = count(**{k: int(v) for k, v in m.groupdict().items()})
        least += calls * flops.roofline_seconds(ops, nbytes, run["peaks"])[0]
        spent += seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
