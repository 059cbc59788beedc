"""Where does the batch-32/48 throughput dip come from?

The round-4 sweep showed per-image device time of the FUSED
serving path is non-monotonic in batch: 0.205 ms/img at batch 16 but
0.257 at 32 and 0.254 at 48, recovering to 0.226 at 64 and 0.215 at 128.
This probe traces the fast forward at several batches and aggregates
device-stream op durations by name, printing a side-by-side per-op table
(ms and ms-per-16-image-tile) so the non-scaling region is attributable
to a specific op family (entry-flow XLA fusions vs fused Pallas calls vs
transposes/head).

Usage: python exp/batch_dip_trace.py --batches 16 32 48 64 [--top 14]
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def trace_batch(batch: int, iters: int, model: str = "clothing-model"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_deep_learning_tpu.models import build_forward, init_variables
    from kubernetes_deep_learning_tpu.modelspec import get_spec

    spec = get_spec(model)
    dev = jax.devices()[0]
    variables = jax.device_put(init_variables(spec, seed=0), dev)
    fwd = jax.jit(build_forward(spec, dtype=jnp.bfloat16, fast="auto"))
    rng = np.random.default_rng(0)
    x = jax.device_put(
        rng.integers(0, 256, (batch, *spec.input_shape), np.uint8), dev
    )
    jax.block_until_ready(fwd(variables, x))  # compile

    trace_dir = tempfile.mkdtemp(prefix=f"kdlt-dip-{batch}-")
    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            jax.block_until_ready(fwd(variables, x))

    files = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    assert files, f"no trace files under {trace_dir}"
    with gzip.open(files[0], "rt") as f:
        trace = json.load(f)

    pids = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pids[ev["pid"]] = ev["args"].get("name", "")
    device_pids = {
        pid for pid, name in pids.items() if name.startswith("/device:TPU")
    }
    agg: dict[str, float] = defaultdict(float)
    details: dict[str, str] = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") != "X" or ev.get("pid") not in device_pids:
            continue
        name = ev.get("name", "?")
        if name.startswith("jit_"):
            continue
        # Collapse instance suffixes (fusion.123 -> fusion) lightly: keep
        # the numbered name (distinct ops) but strip duplicate-run suffixes.
        agg[name] += ev.get("dur", 0) / 1e3 / iters  # -> ms/iter
        a = ev.get("args") or {}
        d = a.get("long_name") or a.get("hlo_op") or a.get("tf_op") or ""
        if d:  # don't pin "" from an argless first event
            details.setdefault(name, d)
    return dict(agg), details


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batches", type=int, nargs="+", default=[16, 32, 48, 64])
    p.add_argument("--iters", type=int, default=6)
    p.add_argument("--top", type=int, default=16)
    p.add_argument("--model", default="clothing-model")
    args = p.parse_args()

    per_batch: dict[int, dict[str, float]] = {}
    per_batch_details: dict[int, dict[str, str]] = {}
    for b in args.batches:
        per_batch[b], per_batch_details[b] = trace_batch(b, args.iters, args.model)
        total = sum(per_batch[b].values())
        print(
            f"batch {b:4d}: total {total:7.2f} ms/iter, "
            f"{total / b * 1000:6.1f} us/img"
        )

    # Rank ops by their time at the LARGEST traced batch, show all batches.
    big = max(args.batches)
    names = sorted(per_batch[big], key=lambda n: -per_batch[big][n])[: args.top]
    hdr = "op".ljust(34) + "".join(f"  b{b:<4d} (us/img)" for b in args.batches)
    print("\n" + hdr)
    # Detail strings come from the ranked (largest) batch's own program:
    # op names like fusion.123 are per-compile identities and must not be
    # annotated from a different batch size's trace.
    details = per_batch_details[big]
    for n in names:
        row = n[:33].ljust(34)
        for b in args.batches:
            ms = per_batch[b].get(n, 0.0)
            row += f"  {ms:6.2f} ({ms / b * 1000:5.1f})"
        d = details.get(n, "")
        print(row + ("   " + d[:90] if d else ""))

    # Bucket into families for the summary.  NB: "conv" must not be a bare
    # prefix test -- XLA names elementwise-cast fusions "CONVert_*_fusion",
    # which a "conv" prefix match silently books under convolution (this
    # inflated the B3 convolution row by ~5x before round 5; the SE-pool
    # convert_reduce_fusions are reduce/fusion family, not convs).
    fam_of = lambda n: (  # noqa: E731
        "pallas-fused" if "custom-call" in n or "tpu_custom_call" in n
        else "reduce-fusion" if n.startswith(("convert_reduce_fusion", "reduce"))
        else "convolution" if n.startswith(("convolution", "conv"))
        and not n.startswith("convert")
        else "fusion" if n.startswith(("fusion", "loop_fusion", "input_fusion"))
        or n.startswith(("convert", "add_convert"))
        else "copy/transpose" if re.match(r"(copy|transpose|bitcast)", n)
        else "other"
    )
    print("\nfamily summary (ms/iter):")
    fams = sorted({fam_of(n) for m in per_batch.values() for n in m})
    print("family".ljust(16) + "".join(f"  b{b:<8d}" for b in args.batches))
    for f in fams:
        row = f.ljust(16)
        for b in args.batches:
            tot = sum(ms for n, ms in per_batch[b].items() if fam_of(n) == f)
            row += f"  {tot:8.2f}"
        print(row)


if __name__ == "__main__":
    main()
