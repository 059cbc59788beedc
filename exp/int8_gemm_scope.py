"""Scoping: is the MXU's int8 path worth an in-kernel activation-quant
pass at the fused sepconv GEMM shapes?  Times XLA-level GEMM chains
(anti-LICM chained scan) for bf16 vs int8x int8->int32, at the middle-flow
pointwise shapes for serving-relevant batches."""

from __future__ import annotations

import functools
import time

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"device: {dev}")
    rng = np.random.default_rng(0)
    C = 728
    for bt in (1, 8, 16, 64):
        M = 19 * 19 * bt
        results = {}
        for name, dtype, pref in (
            ("bf16", jnp.bfloat16, jnp.float32),
            ("int8", jnp.int8, jnp.int32),
        ):
            if name == "int8":
                a = jnp.asarray(rng.integers(-127, 127, (M, C)), jnp.int8)
                w = jnp.asarray(rng.integers(-127, 127, (C, C)), jnp.int8)
            else:
                a = jnp.asarray(rng.normal(0, 1, (M, C)), dtype)
                w = jnp.asarray(rng.normal(0, 1, (C, C)), dtype)

            @functools.partial(jax.jit, static_argnums=2)
            def chained(a0, w, k):
                def body(carry, _):
                    acc = jax.lax.dot_general(
                        carry, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=pref,
                    )
                    # data-dependent feedback, cast back to operand dtype
                    nxt = acc.astype(a0.dtype) if name == "bf16" else (
                        (acc >> 7).astype(jnp.int8)
                    )
                    return nxt, None

                out, _ = jax.lax.scan(body, a0, None, length=k)
                # fold to a scalar the caller prints: reading back the full
                # (M, C) carry would sit inside the timed call, and a
                # consumed scalar also guards against output elision.
                return out.astype(jnp.int32).sum() if name == "int8" else out.sum()

            # long calls: wall >=0.5 s so the per-call RTT is noise, and
            # achieved rate must stay under the physical peak or the run is
            # rejected (the first version of this harness reported 9.6
            # PFLOP/s -- scan output elision).
            k = max(2000, int(0.5 / (2 * M * C * C / 197e12)))
            float(chained(a, w, k))
            times = []
            for _ in range(4):
                t0 = time.perf_counter()
                float(chained(a, w, k))
                times.append((time.perf_counter() - t0) / k)
            us = float(np.median(times)) * 1e6
            flops = 2 * M * C * C
            tflops = flops / (us * 1e-6) / 1e12  # FLOP / s -> TFLOP/s
            peak = 394.0 if name == "int8" else 197.0  # TFLOP/s (TOPS), v5e
            flag = "  IMPOSSIBLE(>peak)" if tflops > peak else ""
            results[name] = us
            print(f"  bt={bt:3d} {name}: {us:8.2f} us/GEMM "
                  f"({tflops:6.1f} TFLOP/s, {tflops/peak*100:5.1f}% peak){flag}")
        print(f"  bt={bt:3d} int8 speedup: {results['bf16']/results['int8']:.2f}x")


if __name__ == "__main__":
    main()
