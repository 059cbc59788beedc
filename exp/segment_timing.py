"""Attribute Xception forward time to entry/middle/exit segments on device.

Times jitted sub-forwards (entry flow to each cut point, middle flow alone,
exit flow alone) at serving-relevant batch sizes, so the Pallas fusion work
targets the segment that actually dominates.  Each timed fn chains K=8
data-dependent iterations (the anti-LICM trick: no iteration can be hoisted) to amortize
per-dispatch host cost.

Usage: python exp/segment_timing.py [--batch 256]
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import numpy as np


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--scan-len", type=int, default=8)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.models.xception import Xception
    from kubernetes_deep_learning_tpu.modelspec import get_spec

    spec = get_spec("clothing-model")
    model = Xception(spec.num_classes, head_hidden=spec.head_hidden, dtype=jnp.bfloat16)
    variables = init_variables(spec, seed=0)
    dev = jax.devices()[0]
    variables = jax.device_put(variables, dev)
    print(f"device: {dev}, batch {args.batch}")

    # Segment boundaries, chosen at the natural Xception flow cuts.  Each
    # segment is expressed as a capture of the full model's intermediate
    # (flax's perturb-free way: run __call__ with a capture_intermediates
    # filter would keep all; instead re-run the model up to a block by
    # monkey-free slicing is messy -- so segments are timed as DELTAS between
    # progressively longer prefixes).
    # prefix k = forward through block k (1=block1 convs, 2..4 entry blocks,
    # 12=middle done, 14=exit convs done, 15=head).
    import flax.linen as nn

    class Prefix(nn.Module):
        upto: int  # inclusive block index; 15 = head included
        dtype: object = jnp.bfloat16

        @nn.compact
        def __call__(self, x):
            from kubernetes_deep_learning_tpu.models.layers import (
                ClassifierHead,
                SeparableConv2D,
                batch_norm,
            )

            conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
            bn = partial(batch_norm, False, self.dtype)
            sep = partial(SeparableConv2D, dtype=self.dtype)
            pool = partial(
                nn.max_pool, window_shape=(3, 3), strides=(2, 2), padding="SAME"
            )
            x = conv(32, (3, 3), strides=2, padding="VALID", name="block1_conv1")(x)
            x = nn.relu(bn("block1_conv1_bn")(x))
            x = conv(64, (3, 3), padding="VALID", name="block1_conv2")(x)
            x = nn.relu(bn("block1_conv2_bn")(x))
            if self.upto <= 1:
                return x
            for idx, feat in ((2, 128), (3, 256), (4, 728)):
                if self.upto < idx:
                    return x
                residual = conv(feat, (1, 1), strides=2, padding="SAME", name=f"block{idx}_res_conv")(x)
                residual = bn(f"block{idx}_res_bn")(residual)
                if idx > 2:
                    x = nn.relu(x)
                x = sep(feat, name=f"block{idx}_sepconv1")(x)
                x = bn(f"block{idx}_sepconv1_bn")(x)
                x = nn.relu(x)
                x = sep(feat, name=f"block{idx}_sepconv2")(x)
                x = bn(f"block{idx}_sepconv2_bn")(x)
                x = pool(x) + residual
            for idx in range(5, 13):
                if self.upto < idx:
                    return x
                residual = x
                for j in (1, 2, 3):
                    x = nn.relu(x)
                    x = sep(728, name=f"block{idx}_sepconv{j}")(x)
                    x = bn(f"block{idx}_sepconv{j}_bn")(x)
                x = x + residual
            if self.upto < 13:
                return x
            residual = conv(1024, (1, 1), strides=2, padding="SAME", name="block13_res_conv")(x)
            residual = bn("block13_res_bn")(residual)
            x = nn.relu(x)
            x = sep(728, name="block13_sepconv1")(x)
            x = bn("block13_sepconv1_bn")(x)
            x = nn.relu(x)
            x = sep(1024, name="block13_sepconv2")(x)
            x = bn("block13_sepconv2_bn")(x)
            x = pool(x) + residual
            if self.upto < 14:
                return x
            x = sep(1536, name="block14_sepconv1")(x)
            x = nn.relu(bn("block14_sepconv1_bn")(x))
            x = sep(2048, name="block14_sepconv2")(x)
            x = nn.relu(bn("block14_sepconv2_bn")(x))
            if self.upto < 15:
                return x
            return ClassifierHead(
                spec.num_classes, hidden=spec.head_hidden, dtype=self.dtype, name="head"
            )(x)

    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    def timed_prefix(upto: int):
        mod = Prefix(upto=upto)

        @partial(jax.jit, static_argnums=2)
        def chained(v, img, k):
            def body(carry, _):
                acc, xi = carry
                out = mod.apply(v, normalize(xi, spec.preprocessing))
                s = out.sum()
                bit = jnp.signbit(s).astype(xi.dtype)
                return (acc + s.astype(jnp.float32), xi ^ bit), None

            (acc, _), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), img), None, length=k
            )
            return acc

        rng = np.random.default_rng(0)
        img = jax.device_put(
            rng.integers(0, 256, (args.batch, *spec.input_shape), np.uint8), dev
        )
        float(chained(variables, img, args.scan_len))  # compile+warm
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(chained(variables, img, args.scan_len))
            times.append((time.perf_counter() - t0) / args.scan_len)
        return float(np.median(times))

    cuts = [1, 2, 3, 4, 12, 14, 15]
    names = {
        1: "block1 convs (299->147x147x64)",
        2: "block2 (147, 64->128, pool->74)",
        3: "block3 (74, 128->256, pool->37)",
        4: "block4 (37, 256->728, pool->19)",
        12: "middle flow (8 blocks @19x19x728)",
        14: "exit flow (blocks 13-14)",
        15: "head + logits",
    }
    prev = 0.0
    total = None
    for c in cuts:
        t = timed_prefix(c)
        total = t
        print(
            f"prefix<=blk{c:2d}: {t * 1e3:8.3f} ms   delta {('%8.3f' % ((t - prev) * 1e3))} ms  {names[c]}"
        )
        prev = t
    b = args.batch
    print(f"full forward: {total * 1e3:.3f} ms -> {b / total:.0f} img/s at batch {b}")


if __name__ == "__main__":
    main()
