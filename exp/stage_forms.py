"""In what form should a uint8 batch cross to the chip?  (PR 29)

Until PR 29 the engine handed the compiled program a host
``uint8[bucket, H, W, 3]`` and the program's parameter had that shape, so the
runtime re-tiled the host array into the parameter's device layout on its own
threads before the program might start (~138 ms for the Xception cell's
137 MB, PERF.md).  This script reads, on the chip, for the two cells' shapes
and each candidate
*wire form* -- a reshape or word view of the same host bytes, turned back
into NHWC by the first operation of the program --

(i)   host -> device ready time of ``jax.device_put(x).block_until_ready()``,
      alone and two at once (the dispatcher keeps two batches in flight);
      the host array is a read-only view of a ``bytes`` object at an odd
      offset, as the tensor wire's body is;
(ii)  the device time of the bucket's own program with the un-wiring
      preamble against the NHWC parameter's (``XLA Modules`` events of a device trace
      around warmed calls, and the program's first operations by start);
(iii) the parameter's device layout as the compiled program reports it.

    chiprun -- python3 exp/stage_forms.py --out chiprun_out/stage_forms
    python3 exp/stage_forms.py --describe     # no chip: layouts only, compiled
                                              # for a described v5e

``--programs xception,b7`` compiles the real programs (25-50 s each and
form); without it only (i), (iii) and the un-wiring alone (a jit of the
preamble that returns NHWC: an upper bound of its cost, unfused) are read.
``--forms`` takes names of ``candidate_forms`` and ``<name>+barrier``.
What it found (PERF.md, PR 29): ``b_h_wc+barrier`` is what the engine does
(``runtime/engine.py::wire_form`` / ``from_wire``).  On no cell's path;
nothing imports it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELLS = {
    "xception": {"config": "perfbench/configs/xception-clothing-299.json", "bucket": 512},
    "b7": {"config": "perfbench/configs/efficientnet-b7-600.json", "bucket": 64},
}


def candidate_forms(bucket: int, h: int, w: int, c: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """name -> (shape, dtype) of every wire form of ``uint8[bucket, h, w, c]``;
    word views only where the byte count divides by 4."""
    image, row = h * w * c, w * c
    forms = {
        "nhwc": ((bucket, h, w, c), "uint8"),
        "b_hwc": ((bucket, image), "uint8"),
        "b_h_wc": ((bucket, h, row), "uint8"),
        "flat": ((bucket * image,), "uint8"),
    }
    if image % 4 == 0:
        forms["b_hwc.u32"] = ((bucket, image // 4), "uint32")
    if row % 4 == 0:
        forms["b_h_wc.u32"] = ((bucket, h, row // 4), "uint32")
    if (bucket * image) % 4 == 0:
        forms["flat.u32"] = ((bucket * image // 4,), "uint32")
    return forms


def to_wire(batch, shape, dtype):
    """The host's half: a view of ``batch``'s memory, never a copy."""
    import numpy as np

    wire = batch.reshape(-1).view(np.dtype(dtype)).reshape(shape)
    assert np.shares_memory(wire, batch)
    return wire


def un_wire(wire, nhwc, barrier: bool = False):
    """The device's half, the first operation of the program.  ``barrier``
    (a form named ``<form>+barrier``) pins the reshape on the uint8 pixels:
    without it XLA is free to move elementwise work across the reshape, and
    for B7 it splits the normalisation around it through a float32 copy."""
    import jax
    import jax.numpy as jnp

    if wire.dtype != jnp.uint8:
        wire = jax.lax.bitcast_convert_type(wire, jnp.uint8)
    pixels = wire.reshape(nhwc)
    return jax.lax.optimization_barrier(pixels) if barrier else pixels


def body_view(nbytes: int, seed: int):
    """uint8[nbytes] as the server sees a request's pixels: read-only, inside
    a larger ``bytes`` object, starting at an odd offset."""
    import numpy as np

    rng = np.random.default_rng(seed)
    body = b"\x00" * 37 + rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
    view = np.frombuffer(body, dtype=np.uint8, offset=37, count=nbytes)
    assert not view.flags.writeable
    return view


def spread(values: list[float]) -> dict:
    return {"min": min(values), "median": statistics.median(values), "max": max(values),
            "n": len(values)}


def time_h2d(wire, reps: int) -> dict:
    """Milliseconds until a device_put of ``wire`` is ready: alone, then two
    started together from two threads (timed from the common start to the
    later of the two)."""
    import jax

    jax.device_put(wire).block_until_ready()  # first touch: allocator, pages
    alone = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_put(wire).block_until_ready()
        alone.append((time.perf_counter() - t0) * 1e3)
    pair = []
    for _ in range(reps):
        done = [0.0, 0.0]
        gate = threading.Barrier(3)

        def put(i):
            gate.wait()
            jax.device_put(wire).block_until_ready()
            done[i] = time.perf_counter()

        threads = [threading.Thread(target=put, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        gate.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        pair.append((max(done) - t0) * 1e3)
    return {"alone_ms": spread(alone), "two_at_once_ms": spread(pair)}


def device_modules(trace_dir: str) -> tuple[list[float], list[tuple[str, float]]]:
    """Milliseconds of each ``XLA Modules`` event of a trace, and the first
    operations of the last module by start: (name, ms)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    profile = ProfileData.from_file(path)
    modules, ops = [], []
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [(e.start_ns, e.duration_ns) for e in line.events]
            elif line.name == "XLA Ops":
                ops = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
        break
    if not modules:
        return [], []
    start, dur = modules[-1]
    first = sorted(o for o in ops if start <= o[0] < start + dur)[:8]
    return [d / 1e6 for _, d in modules], [(name[:160], d / 1e6) for _, d, name in first]


def time_program(jitted, variables, wire, reps: int) -> dict:
    """Device time of ``jitted(variables, wire)`` (a compiled program): the input placed
    beforehand (the batch is donated, so each call gets a fresh one), a
    device trace (host and Python tracers off) around ``reps`` calls."""
    import jax

    for _ in range(2):  # warm: the first run of a program pages its code in
        jax.block_until_ready(jitted(variables, jax.device_put(wire)))
    wall = []
    trace_dir = tempfile.mkdtemp(prefix="stage-forms-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(reps):
        placed = jax.device_put(wire)
        placed.block_until_ready()
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(variables, placed))
        wall.append((time.perf_counter() - t0) * 1e3)
    jax.profiler.stop_trace()
    modules, first_ops = device_modules(trace_dir)
    # The whole path as serving drives it: a host array in, logits ready.
    whole = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(variables, wire))
        whole.append((time.perf_counter() - t0) * 1e3)
    return {"wall_ms": spread(wall),
            "host_array_to_logits_ms": spread(whole),
            "module_ms": spread(modules) if modules else None, "first_ops": first_ops}


def build_program(cell: str, config: dict):
    """(variables on the device, forward(variables, uint8 NHWC)) of the
    cell's bucket program as the engine builds it: the fused live-jit
    forward for Xception, the exported StableHLO module for B7."""
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import build_forward, init_variables
    from perfbench.children.make_artifact import model_spec

    spec = model_spec(config)
    dtype = jnp.dtype(config["compute_dtype"])
    variables = init_variables(spec, seed=0)
    if config["artifact_module"]:
        from jax import export as jax_export

        from kubernetes_deep_learning_tpu.export.exporter import trace_forward

        platform = jax.devices()[0].platform
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), variables)
        forward = jax_export.deserialize(
            trace_forward(spec, shapes, dtype=dtype, platforms=(platform,))).call
    else:
        # The fused kernels compile for the chip alone: a rehearsal on the
        # CPU runs the flax graph.
        fast = config["fast_path"] and jax.devices()[0].platform == "tpu"
        forward = build_forward(spec, dtype=dtype, fast=fast)
    return jax.device_put(variables), forward


def describe(cells: list[str]) -> int:
    """No chip: each form's parameter layout as the TPU's compiler gives it
    for a described v5e (the un-wiring alone, a program that returns NHWC)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for cell in cells:
        with open(os.path.join(ROOT, CELLS[cell]["config"])) as f:
            h, w, c = json.load(f)["input_shape"]
        nhwc = (CELLS[cell]["bucket"], h, w, c)
        for name, (shape, dtype) in candidate_forms(*nhwc).items():
            arg = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
            compiled = jax.jit(lambda x: un_wire(x, nhwc)).lower(arg).compile()
            print(json.dumps({"cell": cell, "form": name, "shape": shape, "dtype": dtype,
                              "layout": str(compiled.input_formats[0][0].layout)}), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--cells", default="xception,b7")
    p.add_argument("--forms", default="", help="comma list; default: every candidate")
    p.add_argument("--programs", default="", help="cells whose real program to compile per form")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--out", default="chiprun_out/stage_forms")
    p.add_argument("--describe", action="store_true")
    p.add_argument("--rehearse-on-cpu", action="store_true",
                   help="control flow only, bucket 2, rows marked as a rehearsal")
    args = p.parse_args()
    cells = [c for c in args.cells.split(",") if c]
    if args.describe:
        return describe(cells)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse_on_cpu:
        print(f"stage_forms measures a chip; found {device.platform}", file=sys.stderr)
        return 1
    os.makedirs(args.out, exist_ok=True)
    for cell in cells:
        with open(os.path.join(ROOT, CELLS[cell]["config"])) as f:
            config = json.load(f)
        h, w, c = config["input_shape"]
        nhwc = (2 if args.rehearse_on_cpu else CELLS[cell]["bucket"], h, w, c)
        pixels = body_view(nhwc[0] * h * w * c, seed=29).reshape(nhwc)
        forms = candidate_forms(*nhwc)
        wanted = [f for f in args.forms.split(",") if f] or list(forms)
        program = build_program(cell, config) if cell in args.programs.split(",") else None
        for name in wanted:
            form, _, variant = name.partition("+")
            if form not in forms:
                continue
            shape, dtype = forms[form]
            barrier = variant == "barrier"
            wire = to_wire(pixels, shape, dtype)
            row = {"cell": cell, "form": name, "shape": list(shape), "dtype": dtype,
                   "device": {"platform": device.platform, "kind": device.device_kind},
                   "bytes": int(wire.nbytes), "rehearsal": args.rehearse_on_cpu}
            row.update(time_h2d(wire, args.reps))
            placed = jax.device_put(wire)
            row["layout"] = str(placed.format.layout)
            # The un-wiring alone, unfused: device array in, NHWC device array out.
            alone = jax.jit(lambda x: un_wire(x, nhwc, barrier))
            jax.block_until_ready(alone(placed))
            t = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(alone(placed))
                t.append((time.perf_counter() - t0) * 1e3)
            row["unwire_alone_wall_ms"] = spread(t)
            del placed
            if program is not None:
                variables, forward = program
                jitted = jax.jit(lambda v, x: forward(v, un_wire(x, nhwc, barrier)),
                                 donate_argnums=(1,))
                t0 = time.perf_counter()
                compiled = jitted.lower(variables, wire).compile()
                row["compile_s"] = time.perf_counter() - t0
                row["program_layout"] = str(compiled.input_formats[0][1].layout)
                row["program"] = time_program(compiled, variables, wire, args.reps)
            print(json.dumps(row), flush=True)
            with open(os.path.join(args.out, "table.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
