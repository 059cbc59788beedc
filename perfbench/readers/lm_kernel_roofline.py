"""The paged latent-attention kernel's share of its roofline.

spec: ``pattern``, a regular expression on the operation's name as the
trace prints it, with the named groups ``slots``, ``heads`` and ``rank``
(the kernel's result is ``[slots, heads, rank]``).  Operations and bytes of
one call are ``lm_flops.mla_decode_kernel``'s, from those shapes and from
the mean live positions a step (the program's counters over the window);
the least time is the larger of operations over the bfloat16 peak and bytes
over the bandwidth; the value is 100 * calls * least / the calls' device
time.  No such operation or no counters: nothing.
"""

import re

SERIES = {"steps": "kdlt_decode_steps_total",
          "context": "kdlt_decode_context_positions_total"}


def read(spec: dict, run: dict):
    from perfbench import flops, lm_flops
    from perfbench.readers import lm_mfu

    trace, d = run.get("trace"), lm_mfu.deltas(run, SERIES)
    if not trace or not trace.get("ops") or d is None or not d["steps"] \
            or not run.get("peaks"):
        return None
    pattern = re.compile(spec["pattern"])
    least = spent = 0.0
    for name, (seconds, calls) in trace["ops"].items():
        m = pattern.search(name)
        if not m:
            continue
        ops, nbytes = lm_flops.mla_decode_kernel(
            run["config"], int(m["slots"]), int(m["heads"]), int(m["rank"]),
            d["context"] / d["steps"])
        least += calls * flops.roofline_seconds(ops, nbytes, run["peaks"])[0]
        spent += seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
