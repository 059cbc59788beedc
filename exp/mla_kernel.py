"""The paged latent-attention kernel alone, at the token cell's shapes.  (PR 33)

``ops/mla_decode.py``'s kernel is called eight times in every decode step of
``longcat-agent-decode-closed128`` (128 slots, 64 heads, latent width 640,
rank 512, 96 pages of 16 a slot, an 8-sublayer cache of 12,289 pages).  This
script runs that one call, warmed, on one batch whose lengths and page lists
are what the cell's closed loop leaves in the lane at a step of its window
(the pool's prompts in the run's order, 128 callers on a shared counter, one
token a step, pages taken from and returned to the engine's free list as
``runtime/decode.py`` does), and reads for each side, on the chip:

(i)   the call's device time (``XLA Ops`` of a device trace around warmed
      calls: the ``custom-call`` event), its median, least and most;
(ii)  the roofline's least time for the batch by the benchmark's own count
      (``perfbench/lm_flops.py::mla_decode_kernel``) and the share of it;
(iii) whether the result is bit for bit the first side's, and its widest
      distance from ``impl="gather"``.

A side is ``tree`` (the file as it lies in the checkout), a git revision, or
a path ending in ``.py`` (a variant of the file, tried before it ships):
``--sides 3b58212,tree`` runs the parent's file and the tree's in one
process on one machine.  The machine with the chip has no git: a revision's
file is staged under ``.scratch/mla_kernel/`` (gitignored, copied with the
checkout) the first time the script meets it here, so

    python3 exp/mla_kernel.py --sides 3b58212,tree --describe   # no chip: stages
                                  # the file, compiles both for a described v5e
    chiprun -- python3 exp/mla_kernel.py --sides 3b58212,tree
    python3 exp/mla_kernel.py --sides 3b58212,tree --rehearse-on-cpu   # control
                                  # flow at a toy size, interpret mode

What it found is in PERF.md section 6, PR 33.  On no cell's path; nothing
imports it.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import statistics
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KERNEL_FILE = "kubernetes_deep_learning_tpu/ops/mla_decode.py"
CONFIG = "perfbench/configs/longcat-flash-chat-ep32.json"
TRAFFIC = "perfbench/traffic/agent-decode-closed128.json"
STAGED = os.path.join(ROOT, ".scratch", "mla_kernel")
HEADS, WIDTH, RANK, SUBLAYERS = 64, 640, 512, 8
WINDOW_STEPS = (1100, 2800)   # the cell's window at ~23 ms a step after a 25 s lead-in


def side_module(side: str):
    """``ops/mla_decode.py`` of the tree, of a git revision (staged), or a
    variant of it in a file of its own (a path from the checkout's root)."""
    if side == "tree":
        path = os.path.join(ROOT, KERNEL_FILE)
    elif side.endswith(".py"):
        path = os.path.join(ROOT, side)
    else:
        path = os.path.join(STAGED, f"mla_decode@{side}.py")
        if not os.path.exists(path):
            os.makedirs(STAGED, exist_ok=True)
            text = subprocess.run(["git", "-C", ROOT, "show", f"{side}:{KERNEL_FILE}"],
                                  check=True, capture_output=True, text=True).stdout
            with open(path, "w") as f:
                f.write(text)
    spec = importlib.util.spec_from_file_location(
        "mla_decode_" + "".join(c if c.isalnum() else "_" for c in side), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lane_state(seed: int, mix: dict, slots: int, page: int, max_pages: int, steps):
    """(lengths [slots], page table [slots, max_pages], the step looked at):
    the lane as the cell's closed loop leaves it at a step of the window."""
    import numpy as np

    from perfbench import tokens

    pool = tokens.make_pool(seed, mix, 2)
    order = np.random.default_rng([int(seed), 0xB0D1]).permutation(len(pool))
    at = int(np.random.default_rng([int(seed), 0x33A]).integers(*steps))
    free = list(range(slots * max_pages, 0, -1))
    held: dict[int, list[int]] = {}
    prompt = np.zeros(slots, np.int64)
    asked = np.zeros(slots, np.int64)
    made = np.zeros(slots, np.int64)
    table = np.zeros((slots, max_pages), np.int32)
    sent = 0
    for _ in range(at):
        for s in np.flatnonzero(made >= asked):
            free.extend(reversed(held.pop(int(s), [])))
            p = pool[int(order[sent % len(order)])]
            sent += 1
            prompt[s], asked[s], made[s] = len(p.ids), p.max_new_tokens, 0
            held[int(s)] = [free.pop() for _ in range(-(-(prompt[s] + asked[s]) // page))]
            table[s] = 0
            table[s, :len(held[int(s)])] = held[int(s)]
        made += 1
    return (prompt + made).astype(np.int32), table, at


def call_times(trace_dir: str) -> list[float]:
    """ms of every ``custom-call`` event on the first TPU plane's ``XLA Ops``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            return [e.duration_ns / 1e6 for line in plane.lines if line.name == "XLA Ops"
                    for e in line.events if "custom-call" in e.name]
    return []


def time_call(run, args, reps: int) -> list[float]:
    import jax

    for _ in range(3):
        jax.block_until_ready(run(*args))
    trace_dir = tempfile.mkdtemp(prefix="mla-kernel-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(reps):
        jax.block_until_ready(run(*args))
    jax.profiler.stop_trace()
    return call_times(trace_dir)


def describe(sides: list[str], slots: int, page: int, max_pages: int) -> int:
    """No chip: each side's kernel compiled for a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    for side in sides:
        module = side_module(side)
        compiled = jax.jit(lambda q, cache, pt, n, m=module: m.paged_mla_attention(
            q, cache, SUBLAYERS // 2, pt, n, rank=RANK)).lower(
            shape((slots, HEADS, WIDTH), jnp.bfloat16),
            shape((SUBLAYERS, 1 + slots * max_pages, page, WIDTH), jnp.bfloat16),
            shape((slots, max_pages), jnp.int32), shape((slots,), jnp.int32)).compile()
        calls = [ln.strip()[:160] for ln in compiled.as_text().splitlines()
                 if "custom-call(" in ln or "custom_call_target" in ln]
        print(json.dumps({"side": side, "compiles": True, "calls": calls[:2]}), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--sides", default="tree", help="comma list: tree, or a git revision")
    p.add_argument("--seeds", default="3300000033,3300000071")
    p.add_argument("--reps", type=int, default=30)
    p.add_argument("--out", default="chiprun_out/mla_kernel")
    p.add_argument("--describe", action="store_true")
    p.add_argument("--rehearse-on-cpu", action="store_true",
                   help="control flow only: a toy size, interpret mode, rows marked")
    args = p.parse_args()
    sides = [s for s in args.sides.split(",") if s]
    with open(os.path.join(ROOT, TRAFFIC)) as f:
        mix = json.load(f)
    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    slots, page, max_pages = int(mix["slots"]), int(mix["page_size"]), int(mix["max_pages"])
    if args.describe:
        return describe(sides, slots, page, max_pages)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import flops, lm_flops

    heads, width, rank, sublayers, steps = HEADS, WIDTH, RANK, SUBLAYERS, WINDOW_STEPS
    impl = "kernel"
    if args.rehearse_on_cpu:
        slots, heads, width, rank, sublayers, steps = 6, 8, 128, 64, 2, (300, 900)
        impl = "interpret"
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse_on_cpu:
        print(f"mla_kernel measures a chip; found {device.platform}", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["devices"].get(device.device_kind)
    os.makedirs(args.out, exist_ok=True)
    modules = {side: side_module(side) for side in sides}
    sub = sublayers // 2
    print(json.dumps({"device": {"platform": device.platform, "kind": device.device_kind},
                      "shapes": {"slots": slots, "heads": heads, "width": width, "rank": rank,
                                 "page": page, "max_pages": max_pages, "sublayers": sublayers},
                      "rehearsal": args.rehearse_on_cpu}), flush=True)

    for seed in (int(s) for s in args.seeds.split(",") if s):
        lengths, table, at = lane_state(seed, mix, slots, page, max_pages, steps)
        key_q, key_c = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)))
        q = (jax.random.normal(key_q, (slots, heads, width), jnp.float32) * 0.3
             ).astype(jnp.bfloat16)
        cache = jax.random.normal(
            key_c, (sublayers, 1 + slots * max_pages, page, width), jnp.bfloat16)
        operands = (q, cache, jnp.asarray(table), jnp.asarray(lengths))
        chunk = page * modules[sides[0]].pages_per_chunk(max_pages)
        live = float(lengths.sum())
        row = {"seed": seed, "step": at, "live_positions_a_slot": live / slots,
               "shortest": int(lengths.min()), "longest": int(lengths.max()),
               "chunks_a_slot": float(np.ceil(lengths / chunk).mean())}
        if peaks:
            ops, nbytes = lm_flops.mla_decode_kernel(config, slots, heads, rank, live)
            least, bound = flops.roofline_seconds(ops, nbytes, peaks)
            row.update(least_ms=least * 1e3, bound=bound)
        print(json.dumps(row), flush=True)
        want = np.asarray(jax.jit(lambda *a, m=modules[sides[0]]: m.paged_mla_attention(
            a[0], a[1], sub, a[2], a[3], rank=rank, impl="gather"))(*operands))
        first = None
        for side in sides:
            run = jax.jit(lambda *a, m=modules[side]: m.paged_mla_attention(
                a[0], a[1], sub, a[2], a[3], rank=rank, impl=impl))
            got = np.asarray(run(*operands))
            first = got if first is None else first
            out = {"seed": seed, "side": side, "rehearsal": args.rehearse_on_cpu,
                   "bit_for_bit_the_first_sides": bool(np.array_equal(got, first)),
                   "widest_from_gather": float(np.abs(got - want).max())}
            if not args.rehearse_on_cpu:
                ms = time_call(run, operands, args.reps)
                out["call_ms"] = {"median": statistics.median(ms), "min": min(ms),
                                  "max": max(ms), "n": len(ms)}
                if peaks:
                    out["roofline_pct"] = 100.0 * row["least_ms"] / out["call_ms"]["median"]
            print(json.dumps(out), flush=True)
            with open(os.path.join(args.out, "table.jsonl"), "a") as f:
                f.write(json.dumps({**row, **out}) + "\n")
        del cache, operands
    return 0


if __name__ == "__main__":
    sys.exit(main())
