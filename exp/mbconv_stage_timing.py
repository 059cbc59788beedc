"""Where does EfficientNet-B3's forward time go, stage by stage?

Times XLA-graph PREFIXES of the functional B3 forward (stem, then through
the end of each stage), pipelined bursts; successive differences give
per-stage cost.  This is the evidence base for the fused-MBConv verdict
(exp/mbconv_variants.py measured the fused path 0.87x at batch 64): if the
time lives in the high-resolution early stages whose expanded tiles cannot
fit VMEM (ops.fused_mbconv.mbconv_fusible), block-level fusion of the
low-resolution stages cannot move the headline, and B3's 12% MFU is
structural under this design.

CAVEAT (recorded after the fact): burst timing on this box is floored at
~2-5 ms/iteration for light programs (a measurement floor), so
the SHORT prefixes here (stem, first stages) read the floor, not their
true sub-millisecond device time, and the first segments absorb that
offset.  The authoritative early-stage attribution for the fused-MBConv
verdict is therefore a per-fusion device trace (exp/trace_profile.py;
trace spans have no floor); this script remains useful for the LONG
prefixes, where successive differences sit well above the floor.

Usage (TPU): python exp/mbconv_stage_timing.py --batch 64
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--k", type=int, default=60)
    p.add_argument("--reps", type=int, default=3)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.models.efficientnet import SCALING
    from kubernetes_deep_learning_tpu.models.layers import KERAS_BN_EPS
    from kubernetes_deep_learning_tpu.models.efficientnet_fast import block_plan
    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    spec = get_spec("efficientnet-b3-imagenet")
    width, depth, _ = SCALING["b3"]
    plan = block_plan(width, depth)
    dtype = jnp.bfloat16
    dev = jax.devices()[0]
    variables = jax.device_put(init_variables(spec, seed=0), dev)

    def conv(x, kernel, stride=1, groups=1):
        return jax.lax.conv_general_dilated(
            x.astype(dtype), jnp.asarray(kernel, dtype), (stride, stride),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
        )

    def bn(x, p, s):
        y = (x - jnp.asarray(s["mean"], dtype)) * jax.lax.rsqrt(
            jnp.asarray(s["var"], dtype) + jnp.asarray(KERAS_BN_EPS, dtype)
        )
        return y * jnp.asarray(p["scale"], dtype) + jnp.asarray(p["bias"], dtype)

    def mbconv(x, bp, bs, stride, features, expand):
        c_in = x.shape[-1]
        y = x
        if expand != 1:
            y = conv(y, bp["expand_conv"]["kernel"])
            y = jax.nn.silu(bn(y, bp["expand_bn"], bs["expand_bn"]))
        y = conv(y, bp["dwconv"]["kernel"], stride=stride, groups=y.shape[-1])
        y = jax.nn.silu(bn(y, bp["dw_bn"], bs["dw_bn"]))
        se = bp["se"]
        m = y.mean(axis=(1, 2), keepdims=True)
        r = jax.nn.silu(conv(m, se["reduce"]["kernel"])
                        + jnp.asarray(se["reduce"]["bias"], dtype))
        g = jax.nn.sigmoid(conv(r, se["expand"]["kernel"])
                           + jnp.asarray(se["expand"]["bias"], dtype))
        y = y * g
        y = conv(y, bp["project_conv"]["kernel"])
        y = bn(y, bp["project_bn"], bs["project_bn"])
        if stride == 1 and c_in == features:
            y = y + x
        return y

    def prefix_forward(n_blocks):
        def f(v, img):
            pp, ss = v["params"], v["batch_stats"]
            x = normalize(img, spec.preprocessing)
            x = conv(x, pp["stem_conv"]["kernel"], stride=2)
            x = jax.nn.silu(bn(x, pp["stem_bn"], ss["stem_bn"]))
            for name, stride, _k, feats, expand in plan[:n_blocks]:
                x = mbconv(x, pp[name], ss[name], stride, feats, expand)
            # Cheap sink so nothing is dead-code-eliminated.
            return x.astype(jnp.float32).mean(axis=(1, 2, 3))
        return jax.jit(f)

    rng = np.random.default_rng(0)
    x = jax.device_put(
        rng.integers(0, 256, (args.batch, *spec.input_shape), np.uint8), dev
    )

    # Stage boundaries: index in `plan` after each stage's last block.
    bounds = [0]
    seen = 0
    last_feat = None
    for i, (_n, _s, _k, feats, _e) in enumerate(plan):
        if last_feat is not None and feats != last_feat:
            bounds.append(i)
        last_feat = feats
        seen = i + 1
    bounds.append(seen)

    def timed(fn):
        np.asarray(fn(variables, x))  # compile + data-plane init
        per = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            outs = [fn(variables, x) for _ in range(args.k)]
            jax.block_until_ready(outs)
            np.asarray(outs[-1])
            per.append((time.perf_counter() - t0) / args.k)
        return float(np.median(per))

    prev = 0.0
    log(f"batch {args.batch}; stage boundaries at blocks {bounds}")
    for i, nb in enumerate(bounds):
        t = timed(prefix_forward(nb))
        seg = t - prev
        what = "stem" if nb == 0 else f"..block{nb - 1}"
        shape_note = ""
        if nb > 0:
            _n, _s, _k, feats, _e = plan[nb - 1]
            shape_note = f" (stage features {feats})"
        log(f"prefix {what:>10}{shape_note}: total {t * 1e3:7.2f} ms  "
            f"segment +{seg * 1e3:6.2f} ms")
        prev = t


if __name__ == "__main__":
    main()
