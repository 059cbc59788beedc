"""EfficientNet-B3 fused-MBConv measurement harness (VERDICT r3 #4).

Round 3 left B3 serving at 12% MFU with a one-line "structural
(depthwise-heavy)" dismissal and zero experiments.  This harness measures,
on the real chip:

1. the stock flax B3 forward (what serving runs today),
2. the fused fast path (models.efficientnet_fast: stride-1 MBConv blocks
   as single Pallas kernels, ops.fused_mbconv),
3. optionally a trace-span breakdown of where the remaining time goes.

Method: pipelined bursts (amortizes per-dispatch host cost)
plus a chained-scan cross-check at the headline batch.  Numerics are asserted against the flax graph before any timing
is believed.

Usage (TPU):  python exp/mbconv_variants.py --batches 64,128 --reps 3
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def time_pipelined(fn, variables, x, k, reps):
    import jax

    # Warm the shape with a real readback before anything is timed.
    np.asarray(fn(variables, x))
    per = []
    for _ in range(reps):
        t0 = time.perf_counter()
        outs = [fn(variables, x) for _ in range(k)]
        jax.block_until_ready(outs)
        np.asarray(outs[-1])
        per.append((time.perf_counter() - t0) / k)
    return float(np.median(per))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="efficientnet-b3-imagenet")
    p.add_argument("--batches", default="64,128")
    p.add_argument("--k", type=int, default=100)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--scan-check", action="store_true",
                   help="also run a data-dependent chained-scan cross-check")
    p.add_argument("--tile-budget-mb", type=int, default=0,
                   help="override ops.fused_mbconv._TILE_BUDGET (MiB): raises "
                        "the fusibility bar so the 75x75 stage-2 blocks fuse "
                        "(bigger bt everywhere too); compile OOM = evidence")
    args = p.parse_args()

    if args.tile_budget_mb:
        from kubernetes_deep_learning_tpu.ops import fused_mbconv

        fused_mbconv._TILE_BUDGET = args.tile_budget_mb << 20
        fused_mbconv.VMEM_LIMIT_BYTES = 110 * 1024 * 1024

    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import build_forward, init_variables
    from kubernetes_deep_learning_tpu.models.efficientnet_fast import (
        build_fast_forward,
    )
    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    spec = get_spec(args.model)
    dev = jax.devices()[0]
    log(f"device: {dev}; model {spec.name} {spec.input_shape}")
    variables = jax.device_put(init_variables(spec, seed=0), dev)

    flax_fwd = jax.jit(build_forward(spec, dtype=jnp.bfloat16, fast=False))
    inner = build_fast_forward(spec, dtype=jnp.bfloat16)
    fast_fwd = jax.jit(
        lambda v, im: inner(v, normalize(im, spec.preprocessing)).astype(jnp.float32)
    )

    rng = np.random.default_rng(0)
    # Numerics gate first (small batch to keep it quick).
    xs = jax.device_put(
        rng.integers(0, 256, (8, *spec.input_shape), np.uint8), dev
    )
    want = np.asarray(flax_fwd(variables, xs))
    got = np.asarray(fast_fwd(variables, xs))
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    log(f"numerics: max rel diff fused-vs-flax = {rel:.2e}")
    assert rel < 2e-2, "fused path numerically diverges; timing would be meaningless"

    for b in (int(x) for x in args.batches.split(",")):
        x = jax.device_put(
            rng.integers(0, 256, (b, *spec.input_shape), np.uint8), dev
        )
        t_flax = time_pipelined(flax_fwd, variables, x, args.k, args.reps)
        t_fast = time_pipelined(fast_fwd, variables, x, args.k, args.reps)
        log(
            f"batch {b:4d}: flax {t_flax * 1e3:7.2f} ms ({b / t_flax:7.0f} img/s)   "
            f"fused {t_fast * 1e3:7.2f} ms ({b / t_fast:7.0f} img/s)   "
            f"speedup {t_flax / t_fast:5.2f}x"
        )
        if args.scan_check:
            from functools import partial

            @partial(jax.jit, static_argnums=(2, 3))
            def chained(v, x, k, use_fast):
                fn = (lambda v, im: inner(v, normalize(im, spec.preprocessing))
                      .astype(jnp.float32)) if use_fast else \
                     build_forward(spec, dtype=jnp.bfloat16, fast=False)

                def body(carry, _):
                    acc, xi = carry
                    s = fn(v, xi).sum()
                    bit = jnp.signbit(s).astype(xi.dtype)
                    return (acc + s.astype(jnp.float32), xi ^ bit), None

                (acc, _), _ = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), x), None, length=k
                )
                return acc

            for use_fast, tag in ((False, "flax"), (True, "fused")):
                # Capped: keep a single device execution to a few seconds.
                kk = max(24, min(500, int(2.0 / (t_fast if use_fast else t_flax))))
                float(chained(variables, x, kk, use_fast))  # compile+run
                t0 = time.perf_counter()
                float(chained(variables, x, kk, use_fast))
                dt = (time.perf_counter() - t0) / kk
                log(f"   scan-check {tag}: {dt * 1e3:7.2f} ms/iter "
                    f"({b / dt:7.0f} img/s)")


if __name__ == "__main__":
    main()
