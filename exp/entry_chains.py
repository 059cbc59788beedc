"""Do the entry flow's blocks 3 and 4 pay as fused sepconv chains?  (PR 31)

Until PR 31 the Xception fast path ran the whole entry flow as XLA fusions:
every separable convolution two fusions, the depthwise output written to HBM
and read back by the pointwise product, pool and residual a third pass.  The
mechanism to fuse a downsample block existed (``xception_fast.downsample_t``,
what block 13 runs) and had never been timed for blocks 3 and 4 without the
block-2 entry kernel.  This script builds the bucket program of
``xception-clothing-299`` as the engine builds it (fast forward, wire form,
donated batch) in four arrangements --

    parent   blocks 2, 3, 4 on XLA            b4   block 4 chained
    b34      blocks 3 and 4 chained           b3   block 3 alone chained

-- by standing in for ``xception_fast.chained_entry_blocks`` (the forward
itself takes no argument for it), and reads for each, on the chip:

(i)   the whole program's device time (``XLA Modules`` of a device trace
      around warmed calls) and compile seconds;
(ii)  its operations by device time (``XLA Ops``, summed by name over the
      traced programs, a program's share), every ``tpu_custom_call`` and
      every ``copy``/``transpose`` among them: the layout change to
      (H, W, B, C) shows here if it costs anything;
(iii) the VMEM each chain's tile holds by ``chain_vmem_bytes`` (what the
      rule compares with the limit);
(iv)  ``logit_err`` against ``perfbench/reference/`` (float32, ``highest``)
      on a seeded pool of pictures with the benchmark's seeded weights.

    chiprun --timeout 3300 -- python3 exp/entry_chains.py --out chiprun_out/entry_chains
    python3 exp/entry_chains.py --describe       # no chip: what Mosaic says each
                                                 # chain needs, for a described v5e
    python3 exp/entry_chains.py --rehearse-on-cpu --batches 8   # control flow, 96x96

What it found (PERF.md section 6, PR 31) is what ``chain_batch_tile``
ships: chains from a batch of 256 up.  Give a run at a smaller batch a time
limit (``timeout 600 python3 exp/entry_chains.py --batches 16 ...``): at 16
and at 64 an arrangement with a chain did not come back from the chip
(bucket 64 with blocks 3 and 4 chained did, 3% slower than the parent).
On no cell's path; nothing imports it.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "perfbench/configs/xception-clothing-299.json"
ARRANGEMENTS = {"parent": (), "b4": (4,), "b34": (3, 4), "b3": (3,)}
POOL = 64  # distinct pictures; a larger batch repeats them


def entry_shapes(input_hw):
    """{block: (h, w, widths)} of the entry flow's downsample blocks."""
    from kubernetes_deep_learning_tpu.models.xception_fast import entry_block_shapes

    return {idx: (h, w, widths) for idx, h, w, widths in entry_block_shapes(input_hw)}


def arrange(rule, blocks):
    """Stand in for the forward's own decision (``rule``, the module's
    ``chained_entry_blocks``): ``blocks`` chained at the tile the rule
    would give them (8 where it would not chain them)."""
    from kubernetes_deep_learning_tpu.models import xception_fast

    def forced(input_hw, batch):
        own = rule(input_hw, batch)
        return {idx: own.get(idx, 8) for idx in blocks}

    xception_fast.chained_entry_blocks = forced


def device_ops(trace_dir: str):
    """(ms of each ``XLA Modules`` event, {op name: ms a program})."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    modules, ops = [], collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [e.duration_ns / 1e6 for e in line.events]
            elif line.name == "XLA Ops":
                for e in line.events:
                    ops[e.name] += e.duration_ns / 1e6
        break
    n = max(len(modules), 1)
    return modules, {name: ms / n for name, ms in ops.items()}


def time_program(compiled, variables, wire, reps: int):
    import jax

    for _ in range(2):
        jax.block_until_ready(compiled(variables, jax.device_put(wire)))
    trace_dir = tempfile.mkdtemp(prefix="entry-chains-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(reps):
        placed = jax.device_put(wire)
        placed.block_until_ready()
        out = jax.block_until_ready(compiled(variables, placed))
    jax.profiler.stop_trace()
    modules, ops = device_ops(trace_dir)
    return modules, ops, out


def short(name: str) -> str:
    """``%fusion.77 = bf16[512,37,37,728]{...} fusion(...)`` -> name = shape."""
    head, _, rest = name.partition(" = ")
    return f"{head} = {rest.split('{')[0].split(' ')[0]}"[:96]


def describe(batch: int) -> int:
    """No chip: compile each entry chain alone for a described v5e at a
    ladder of VMEM limits and print the first that passes and what Mosaic
    says of the ones that do not, beside ``chain_vmem_bytes``."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from kubernetes_deep_learning_tpu.ops.fused_sepconv import (
        chain_vmem_bytes,
        fused_sepconv_chain_t,
    )

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    with open(os.path.join(ROOT, CONFIG)) as f:
        input_hw = json.load(f)["input_shape"][:2]
    for idx, (h, w, widths) in entry_shapes(input_hw).items():
        stages = [
            {"dw": shape((3, 3, ci), jnp.float32), "pw": shape((ci, co), jnp.bfloat16),
             "scale": shape((co,), jnp.float32), "shift": shape((co,), jnp.float32)}
            for ci, co in zip(widths, widths[1:])
        ]
        for bt in (8, 16):
            row = {"block": idx, "h": h, "w": w, "widths": widths, "bt": bt,
                   "chain_vmem_bytes": chain_vmem_bytes(h, w, bt, widths)}
            for limit in (64, 96, 110, 126):

                def chain(x, stages, limit=limit, bt=bt):
                    return fused_sepconv_chain_t(
                        x, [dict(s, pre_relu=True, post_relu=False) for s in stages],
                        bt=bt, vmem_limit_bytes=limit << 20)

                t0 = time.perf_counter()
                try:
                    jax.jit(chain).lower(
                        shape((h, w, batch, widths[0]), jnp.bfloat16), stages).compile()
                    row.update(compiles_at_mib=limit, compile_s=time.perf_counter() - t0)
                    break
                except Exception as e:  # noqa: BLE001 - the compiler's words are the result
                    said = str(e)
                    at = said.find("Used ")
                    row[f"refused_at_{limit}_mib"] = (
                        said[at:at + 90] if at >= 0 else said[:120]).replace("\n", " ")
                    if at >= 0 or "would exceed" in said:
                        break  # more than the chip has: no limit admits it
            print(json.dumps(row), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batches", default="512,256,64,16,8")
    p.add_argument("--arrangements", default=",".join(ARRANGEMENTS))
    p.add_argument("--reps", type=int, default=6)
    p.add_argument("--seed", type=int, default=3100000031)
    p.add_argument("--top", type=int, default=14, help="operations printed a program")
    p.add_argument("--out", default="chiprun_out/entry_chains")
    p.add_argument("--describe", action="store_true")
    p.add_argument("--rehearse-on-cpu", action="store_true",
                   help="control flow only: 96x96, interpret mode, rows marked")
    args = p.parse_args()
    batches = [int(b) for b in args.batches.split(",") if b]
    if args.describe:
        return describe(max(batches))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_deep_learning_tpu.models import build_forward, xception_fast
    from kubernetes_deep_learning_tpu.ops.fused_sepconv import chain_vmem_bytes
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize
    from kubernetes_deep_learning_tpu.runtime.engine import to_wire, wire_form, wired
    from perfbench import pictures, reference
    from perfbench.children.make_artifact import build_weights, model_spec
    from perfbench.children.reference import flatten
    from perfbench.reference.ops import Net
    from perfbench.reference.ops import normalize as reference_normalize

    rule = xception_fast.chained_entry_blocks

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse_on_cpu:
        print(f"entry_chains measures a chip; found {device.platform}", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    if args.rehearse_on_cpu:
        config["input_shape"] = [96, 96, 3]
        config["assumed"]["calibration"]["side"] = 96
    spec = model_spec(config)
    dtype = jnp.dtype(config["compute_dtype"])
    os.makedirs(args.out, exist_ok=True)

    pool = min(POOL, max(batches))
    pixels = pictures.tensor_pool(args.seed, pool, tuple(config["input_shape"]))
    variables = build_weights(config, args.seed)
    ref_forward = reference.load(config["reference"]).forward
    block = int(config["assumed"]["reference_block"])
    flat = jax.device_put(flatten(variables))
    run_ref = jax.jit(lambda w, px: ref_forward(
        Net(w, precision="float32"), reference_normalize(px, config["preprocessing"]), config))
    want = np.concatenate([
        np.asarray(run_ref(flat, np.resize(pixels[i:i + block], (block, *pixels.shape[1:]))))
        [:len(pixels[i:i + block])] for i in range(0, pool, block)
    ]).astype(np.float32)
    del flat, run_ref
    variables = jax.device_put(variables)
    print(json.dumps({"reference_rows": len(want), "largest_logit": float(np.abs(want).max()),
                      "device": {"platform": device.platform, "kind": device.device_kind},
                      "rule": {b: rule(config["input_shape"][:2], b) for b in batches}}),
          flush=True)

    for batch in batches:
        images = np.resize(pixels, (batch, *pixels.shape[1:]))
        wire = to_wire(images)
        chunk = (xception_fast._chunk_sizes(batch) or [batch])[0]
        shapes = entry_shapes(config["input_shape"][:2])
        for name in [a for a in args.arrangements.split(",") if a]:
            arrange(rule, ARRANGEMENTS[name])
            if args.rehearse_on_cpu:
                inner = xception_fast.build_fast_forward(spec, dtype=dtype, interpret=True)
                forward = lambda v, x, inner=inner: inner(  # noqa: E731
                    v, normalize(x, spec.preprocessing)).astype(jnp.float32)
            else:
                forward = build_forward(spec, dtype=dtype, fast=True)
            jitted = jax.jit(wired(forward, spec.input_shape), donate_argnums=(1,))
            t0 = time.perf_counter()
            compiled = jitted.lower(
                variables, jax.ShapeDtypeStruct(wire_form(images.shape), jnp.uint8)).compile()
            row = {"batch": batch, "arrangement": name, "rehearsal": args.rehearse_on_cpu,
                   "compile_s": time.perf_counter() - t0,
                   "chained": {
                       f"block{idx}": {"bt": bt, "vmem_bytes": chain_vmem_bytes(
                           *shapes[idx][:2], bt, shapes[idx][2])}
                       for idx, bt in xception_fast.chained_entry_blocks(
                           config["input_shape"][:2], chunk).items()}}
            modules, ops, out = time_program(compiled, variables, wire, args.reps)
            got = np.asarray(out, np.float32)[:pool]
            row["logit_err"] = float(np.abs(got - want[:len(got)]).max() / np.abs(want).max())
            if modules:
                row["program_ms"] = {"median": statistics.median(modules), "min": min(modules),
                                     "max": max(modules), "n": len(modules)}
            by_time = sorted(ops.items(), key=lambda kv: -kv[1])
            row["ops_ms"] = [(short(n), round(ms, 4)) for n, ms in by_time[:args.top]]
            row["custom_calls_ms"] = [(short(n), round(ms, 4)) for n, ms in by_time
                                      if "custom-call" in n or "custom_call" in n]
            row["copies_ms"] = [(short(n), round(ms, 4)) for n, ms in by_time
                                if n.startswith(("%copy", "%transpose"))]
            row["ops_total_ms"] = sum(ops.values())
            print(json.dumps(row), flush=True)
            with open(os.path.join(args.out, "table.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
            del compiled, jitted
    xception_fast.chained_entry_blocks = rule
    return 0


if __name__ == "__main__":
    sys.exit(main())
