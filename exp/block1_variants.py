"""Why is the Xception stem 31% of forward time, and which rewrite fixes it?

Times block1 (normalize + conv 3x3/2 s2 -> 32ch + BN/relu + conv 3x3 -> 64ch
+ BN/relu) as written, then mathematically equivalent TPU-friendlier forms:

- s2d:    space-to-depth(2) input (150,150,12) + 2x2 conv == conv1 3x3/2.
          C_in 12 instead of 3 fills MXU lanes 4x better.
- im2col: extract 3x3 patches -> (B*149*149, 27) @ (27, 32) matmul.
- both stem convs via s2d/im2col combined.

Each variant is checked numerically against the reference formulation before
timing (atol on bf16).  Timing uses the anti-LICM chained scan.
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

    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    dev = jax.devices()[0]
    print(f"device: {dev}, batch {args.batch}")
    rng = np.random.default_rng(0)

    # Standalone stem weights (drawn once, shared by all variants).
    k1 = rng.normal(0, 0.1, (3, 3, 3, 32)).astype(np.float32)
    s1 = rng.uniform(0.5, 1.5, 32).astype(np.float32)   # folded BN scale
    b1 = rng.normal(0, 0.1, 32).astype(np.float32)      # folded BN shift
    k2 = rng.normal(0, 0.05, (3, 3, 32, 64)).astype(np.float32)
    s2 = rng.uniform(0.5, 1.5, 64).astype(np.float32)
    b2 = rng.normal(0, 0.1, 64).astype(np.float32)
    W = {
        "k1": jnp.asarray(k1), "s1": jnp.asarray(s1), "b1": jnp.asarray(b1),
        "k2": jnp.asarray(k2), "s2": jnp.asarray(s2), "b2": jnp.asarray(b2),
    }

    def conv(x, k, stride):
        return jax.lax.conv_general_dilated(
            x, k.astype(x.dtype), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32,
        )

    def stem_ref(w, img):
        x = normalize(img, "tf").astype(jnp.bfloat16)
        x = conv(x, w["k1"], 2)
        x = jnp.maximum(x * w["s1"] + w["b1"], 0.0).astype(jnp.bfloat16)
        x = conv(x, w["k2"], 1)
        x = jnp.maximum(x * w["s2"] + w["b2"], 0.0).astype(jnp.bfloat16)
        return x

    # --- variant: space-to-depth stem conv1 -------------------------------
    # k1 (3,3,3,32) -> k1s (2,2,12,32): s2d cell (di,dj) holds original pixel
    # (2i+di, 2j+dj); kernel tap (p,q) with p=2a+da reads cell (i+a) offset da.
    k1s = np.zeros((2, 2, 2, 2, 3, 32), np.float32)  # (a, da, b, db, cin, cout)
    for pp in range(3):
        for qq in range(3):
            a, da = divmod(pp, 2)
            b_, db = divmod(qq, 2)
            k1s[a, da, b_, db] = k1[pp, qq]
    # s2d channel layout: (di, dj, c) fastest-varying c  -> index di*6+dj*3+c
    k1s = k1s.transpose(0, 2, 1, 3, 4, 5).reshape(2, 2, 12, 32)
    Ws = dict(W, k1s=jnp.asarray(k1s))

    def s2d(x):
        # (B, 299, 299, 3) -> pad to 300 -> (B, 150, 150, 12)
        B = x.shape[0]
        x = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
        x = x.reshape(B, 150, 2, 150, 2, 3)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, 150, 150, 12)

    def stem_s2d(w, img):
        x = normalize(img, "tf").astype(jnp.bfloat16)
        x = s2d(x)
        x = conv(x, w["k1s"], 1)[:, :149, :149, :]
        x = jnp.maximum(x * w["s1"] + w["b1"], 0.0).astype(jnp.bfloat16)
        x = conv(x, w["k2"], 1)
        x = jnp.maximum(x * w["s2"] + w["b2"], 0.0).astype(jnp.bfloat16)
        return x

    # --- variant: im2col stem conv1 ---------------------------------------
    def stem_im2col(w, img):
        x = normalize(img, "tf").astype(jnp.bfloat16)
        patches = jax.lax.conv_general_dilated_patches(
            x, (3, 3), (2, 2), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )  # (B, 149, 149, 27); feature order is channel-major (c, kh, kw)
        k = w["k1"].transpose(2, 0, 1, 3).reshape(27, 32).astype(jnp.bfloat16)
        x = jnp.einsum(
            "bhwk,kc->bhwc", patches, k, preferred_element_type=jnp.float32
        )
        x = jnp.maximum(x * w["s1"] + w["b1"], 0.0).astype(jnp.bfloat16)
        x = conv(x, w["k2"], 1)
        x = jnp.maximum(x * w["s2"] + w["b2"], 0.0).astype(jnp.bfloat16)
        return x

    # --- harness ----------------------------------------------------------
    img_small = jax.device_put(
        rng.integers(0, 256, (2, 299, 299, 3), np.uint8), dev
    )
    ref_out = np.asarray(jax.jit(stem_ref)(W, img_small), np.float32)

    variants = {"ref": (stem_ref, W), "s2d": (stem_s2d, Ws), "im2col": (stem_im2col, W)}
    for name, (fn, w) in variants.items():
        if name != "ref":
            got = np.asarray(jax.jit(fn)(w, img_small), np.float32)
            err = np.abs(got - ref_out).max() / (np.abs(ref_out).max() + 1e-6)
            print(f"{name}: max rel err vs ref = {err:.2e}")
            assert err < 2e-2, f"{name} diverges"

    img = jax.device_put(
        rng.integers(0, 256, (args.batch, 299, 299, 3), np.uint8), dev
    )

    for name, (fn, w) in variants.items():
        @partial(jax.jit, static_argnums=2)
        def chained(v, x, k, fn=fn):
            def body(carry, _):
                acc, xi = carry
                s = fn(v, xi).sum()
                bit = jnp.signbit(s).astype(xi.dtype)
                return (acc + s.astype(jnp.float32), xi ^ bit), None

            (acc, _), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), x), None, length=k
            )
            return acc

        float(chained(w, img, args.scan_len))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(chained(w, img, args.scan_len))
            times.append((time.perf_counter() - t0) / args.scan_len)
        t = float(np.median(times))
        print(f"stem[{name:7s}]: {t * 1e3:8.3f} ms / batch {args.batch}")


if __name__ == "__main__":
    main()
