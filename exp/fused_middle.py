"""Pallas prototype: one fused Xception middle block in VMEM.

One grid instance processes ``bt`` images: the (bt,19,19,728) tile stays in
VMEM through relu -> depthwise 3x3 -> pointwise GEMM -> BN affine, three
times, plus the residual add -- eliminating ~7 HBM round trips per block.
Depthwise is 9 shifted multiply-adds on the VPU; pointwise is an MXU GEMM
(bt*361, 728) @ (728, 728) with f32 accumulation.

Validates numerics against the plain-jnp reference, then times:
  asis (XLA graph) vs fused (pallas) at serving batch.
"""

from __future__ import annotations

import argparse
import functools
import time

import numpy as np

C = 728
H = W = 19


def make_refs():
    import jax.numpy as jnp

    def dw_shifted(x, k):
        import jax.numpy as jnp

        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        acc = jnp.zeros(x.shape, jnp.float32)
        for i in range(3):
            for j in range(3):
                acc = acc + (
                    xp[:, i : i + x.shape[1], j : j + x.shape[2], :].astype(jnp.float32)
                    * k[i, j].astype(jnp.float32)
                )
        return acc

    def block_ref(x, dw, pw, s, b):
        """Plain-jnp reference of the fused block (bf16 in/out, f32 accum)."""
        y = x
        for i in range(3):
            y = jnp.maximum(y, 0)
            a = dw_shifted(y, dw[i]).astype(jnp.bfloat16)
            z = jnp.einsum(
                "bhwc,cd->bhwd", a, pw[i], preferred_element_type=jnp.float32
            )
            y = (z * s[i] + b[i]).astype(jnp.bfloat16)
        return x + y

    return block_ref


def fused_block_v2(x, dw, pw, s, b, *, bt=4, interpret=False):
    """v2: whole batch as one 2D array, images padded to 368 rows.

    x (B,19,19,C) -> (B*368, C); each grid instance handles bt images =
    (bt*368, C) rows, so the pointwise GEMM has M = bt*368 (MXU-efficient)
    and NOTHING reshapes in-kernel.  Depthwise = 9 shifted FMAs along the
    row dim; validity masks (row/col image edges, 361->368 pad rows) are
    host-precomputed (368,1)-per-image vectors tiled to the block.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B = x.shape[0]
    HW, HWp = H * W, 368  # padded rows per image (multiple of 8 sublanes)
    bt = min(bt, B)
    assert B % bt == 0, (B, bt)
    T = bt * HWp

    x2 = jnp.pad(x.reshape(B, HW, C), ((0, 0), (0, HWp - HW), (0, 0)))
    x2 = x2.reshape(B * HWp, C)

    # Host-side masks, one image period, tiled to the block size.
    r = np.arange(HWp)
    h_idx, w_idx = r // W, r % W
    valid = (r < HW).astype(np.float32)
    base = {
        "valid": valid,
        "row0": valid * (h_idx != 0),        # dh=-1 targets need h>0
        "row18": valid * (h_idx != H - 1),   # dh=+1 targets need h<18
        "col0": valid * (w_idx != 0),
        "col18": valid * (w_idx != W - 1),
    }

    def tiled(v):
        return jnp.asarray(np.tile(v, bt)[:, None])

    def tap_mask(dh, dwc):
        m = base["valid"].copy()
        if dh == -1:
            m = m * base["row0"]
        elif dh == 1:
            m = m * base["row18"]
        if dwc == -1:
            m = m * base["col0"]
        elif dwc == 1:
            m = m * base["col18"]
        return m

    taps = [(dh, dwc) for dh in (-1, 0, 1) for dwc in (-1, 0, 1)]
    masks = jnp.concatenate(
        [tiled(tap_mask(dh, dwc)) for dh, dwc in taps], axis=1
    )  # (T, 9)
    mvalid = tiled(base["valid"])  # (T, 1)

    PAD = W + 1  # covers the largest |offset|

    def kernel(x_ref, dw_ref, pw_ref, s_ref, b_ref, mk_ref, mv_ref, o_ref):
        y = x_ref[...]  # (T, C) bf16
        res = y
        for i in range(3):
            y = jnp.maximum(y, 0)
            # bf16 pad buffer (halves VMEM); products accumulate in f32.
            yp = jnp.pad(y, ((PAD, PAD), (0, 0)))
            acc = jnp.zeros((T, C), jnp.float32)
            for t, (dh, dwc) in enumerate(taps):
                o = W * dh + dwc  # row stride is W within an image
                tap = dw_ref[i, dh + 1, dwc + 1, :].astype(jnp.float32)
                contrib = yp[PAD + o : PAD + o + T, :].astype(jnp.float32) * tap
                acc = acc + contrib * mk_ref[:, t : t + 1]
            z = jax.lax.dot_general(
                acc.astype(jnp.bfloat16),
                pw_ref[i],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            y = ((z * s_ref[i] + b_ref[i]) * mv_ref[...]).astype(jnp.bfloat16)
        o_ref[...] = res + y

    try:
        from jax.experimental.pallas import tpu as pltpu

        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        )
    except Exception:  # older API name
        from jax.experimental.pallas import tpu as pltpu

        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        )

    out = pl.pallas_call(
        kernel,
        grid=(B // bt,),
        in_specs=[
            pl.BlockSpec((T, C), lambda g: (g, 0)),
            pl.BlockSpec((3, 3, 3, C), lambda g: (0, 0, 0, 0)),
            pl.BlockSpec((3, C, C), lambda g: (0, 0, 0)),
            pl.BlockSpec((3, C), lambda g: (0, 0)),
            pl.BlockSpec((3, C), lambda g: (0, 0)),
            pl.BlockSpec((T, 9), lambda g: (0, 0)),
            pl.BlockSpec((T, 1), lambda g: (0, 0)),
        ],
        out_specs=pl.BlockSpec((T, C), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((B * HWp, C), x.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(x2, dw, pw, s, b, masks, mvalid)
    return out.reshape(B, HWp, C)[:, :HW, :].reshape(B, H, W, C)


def fused_block_v3(xt, dw, pw, s, b, *, bt=8, interpret=False):
    """v3: (H, W, B, C) layout -- batch on sublanes, channels on lanes.

    Depthwise shifts become OUTER-dim slices (no sublane/lane relayout at
    all, the v1/v2 killer); the whole 19x19 spatial extent of ``bt`` images
    sits in one VMEM tile, so zero-padding h/w gives exact SAME-conv halos
    with no masks; the pointwise GEMM collapses (19,19,bt) -> M rows over a
    full (bt sublane, C lane) tile, which Mosaic reshapes for free.

    Takes and returns the TRANSPOSED activation (H, W, B, C): chained middle
    blocks stay in this layout, paying the NHWC transpose once at entry and
    once at exit of the whole middle flow.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hh, Ww, B, Cc = xt.shape
    assert (Hh, Ww, Cc) == (H, W, C)
    bt = min(bt, B)
    assert B % bt == 0

    def kernel(x_ref, dw_ref, pw_ref, s_ref, b_ref, o_ref):
        y = x_ref[...]  # (H, W, bt, C) bf16
        for i in range(3):
            y = jnp.maximum(y, 0)
            yp = jnp.pad(y, ((1, 1), (1, 1), (0, 0), (0, 0)))
            acc = jnp.zeros((H, W, bt, C), jnp.float32)
            for dh in range(3):
                for dwc in range(3):
                    tap = dw_ref[i, dh, dwc, :].astype(jnp.float32)
                    acc = acc + (
                        yp[dh : dh + H, dwc : dwc + W, :, :].astype(jnp.float32)
                        * tap
                    )
            z = jax.lax.dot_general(
                acc.astype(jnp.bfloat16).reshape(H * W * bt, C),
                pw_ref[i],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            y = (
                (z * s_ref[i] + b_ref[i])
                .astype(jnp.bfloat16)
                .reshape(H, W, bt, C)
            )
        o_ref[...] = x_ref[...] + y

    return pl.pallas_call(
        kernel,
        grid=(B // bt,),
        in_specs=[
            pl.BlockSpec((H, W, bt, C), lambda g: (0, 0, g, 0)),
            pl.BlockSpec((3, 3, 3, C), lambda g: (0, 0, 0, 0)),
            pl.BlockSpec((3, C, C), lambda g: (0, 0, 0)),
            pl.BlockSpec((3, C), lambda g: (0, 0)),
            pl.BlockSpec((3, C), lambda g: (0, 0)),
        ],
        out_specs=pl.BlockSpec((H, W, bt, C), lambda g: (0, 0, g, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, xt.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
        interpret=interpret,
    )(xt, dw, pw, s, b)


def fused_block_v3_nhwc(x, dw, pw, s, b, *, bt=8, interpret=False):
    """NHWC wrapper for the numeric check / standalone timing: transpose in,
    run v3, transpose out (chained use pays the transposes once per flow)."""
    xt = x.transpose(1, 2, 0, 3)
    out = fused_block_v3(xt, dw, pw, s, b, bt=bt, interpret=interpret)
    return out.transpose(2, 0, 1, 3)


def fused_block(x, dw, pw, s, b, *, bt=1, interpret=False):
    """x (B,19,19,728) bf16; dw (3,3,3,C) f32; pw (3,C,C) bf16; s,b (3,C) f32.

    Kernel layout: spatial is flattened OUTSIDE the kernel to (B, 361, C) --
    Mosaic cannot shape-cast (19,19) sublanes in-kernel.  The depthwise conv
    becomes 9 statically-shifted multiply-adds along the flattened dim
    (row shift = +-19, col shift = +-1) with column-edge masks passed in as
    (361, 1) constants (a col shift crosses image rows at w=0/18; row
    overflow lands outside the padded range and is zero).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B = x.shape[0]
    HW = H * W
    x2 = x.reshape(B, HW, C)

    # Column-edge validity masks, by col-shift direction (target-side).
    w_idx = np.arange(HW) % W
    m_m1 = jnp.asarray((w_idx != 0).astype(np.float32)[:, None])    # dwc=-1
    m_p1 = jnp.asarray((w_idx != W - 1).astype(np.float32)[:, None])  # dwc=+1

    def kernel(x_ref, dw_ref, pw_ref, s_ref, b_ref, mm_ref, mp_ref, o_ref):
        y = x_ref[0]  # (361, C) bf16
        res = y
        for i in range(3):
            y = jnp.maximum(y, 0)
            yp = jnp.pad(
                y.astype(jnp.float32), ((W + 1, W + 1), (0, 0))
            )  # (361 + 40, C)
            acc = jnp.zeros((HW, C), jnp.float32)
            for dh in (-1, 0, 1):
                for dwc in (-1, 0, 1):
                    o = W * dh + dwc
                    tap = dw_ref[i, dh + 1, dwc + 1, :].astype(jnp.float32)
                    contrib = yp[W + 1 + o : W + 1 + o + HW, :] * tap
                    if dwc == -1:
                        contrib = contrib * mm_ref[...]
                    elif dwc == 1:
                        contrib = contrib * mp_ref[...]
                    acc = acc + contrib
            z = jax.lax.dot_general(
                acc.astype(jnp.bfloat16),
                pw_ref[i],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            y = (z * s_ref[i] + b_ref[i]).astype(jnp.bfloat16)
        o_ref[0] = res + y

    out = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, HW, C), lambda g: (g, 0, 0)),
            pl.BlockSpec((3, 3, 3, C), lambda g: (0, 0, 0, 0)),
            pl.BlockSpec((3, C, C), lambda g: (0, 0, 0)),
            pl.BlockSpec((3, C), lambda g: (0, 0)),
            pl.BlockSpec((3, C), lambda g: (0, 0)),
            pl.BlockSpec((HW, 1), lambda g: (0, 0)),
            pl.BlockSpec((HW, 1), lambda g: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, HW, C), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, HW, C), x.dtype),
        interpret=interpret,
    )(x2, dw, pw, s, b, m_m1, m_p1)
    return out.reshape(B, H, W, C)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--bt", type=int, default=4)
    p.add_argument("--scan-len", type=int, default=16)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--interpret", action="store_true")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"device: {dev}, batch {args.batch}, bt {args.bt}")
    rng = np.random.default_rng(0)
    dw = jnp.asarray(rng.normal(0, 0.2, (3, 3, 3, C)), jnp.float32)
    pw = jnp.asarray(rng.normal(0, 0.03, (3, C, C)), jnp.bfloat16)
    s = jnp.asarray(rng.uniform(0.8, 1.2, (3, C)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (3, C)), jnp.float32)

    block_ref = make_refs()
    x_small = jnp.asarray(rng.normal(0, 1, (4, H, W, C)), jnp.bfloat16)
    want = np.asarray(jax.jit(block_ref)(x_small, dw, pw, s, b), np.float32)
    for vname, vfn in (
        ("fused", fused_block),
        ("fused_v2", fused_block_v2),
        ("fused_v3", fused_block_v3_nhwc),
    ):
        got = np.asarray(
            jax.jit(functools.partial(vfn, bt=4, interpret=args.interpret))(
                x_small, dw, pw, s, b
            ),
            np.float32,
        )
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        print(f"{vname} vs ref max rel err: {rel:.2e}")
        assert rel < 3e-2, f"{vname} diverges"
    if args.interpret:
        print("interpret-mode check PASS")
        return

    x = jax.device_put(jnp.asarray(rng.normal(0, 1, (args.batch, H, W, C)), jnp.bfloat16), dev)
    gemm_tf = 3 * args.batch * H * W * C * C * 2 / 1e12

    for name, fn in (
        ("asis", block_ref),
        ("fused_v3_bt8", functools.partial(fused_block_v3_nhwc, bt=8)),
        ("fused_v3_bt16", functools.partial(fused_block_v3_nhwc, bt=16)),
        ("fused_v3_bt4", functools.partial(fused_block_v3_nhwc, bt=4)),
    ):
        @functools.partial(jax.jit, static_argnums=6)
        def chained(xx, dw, pw, s, b, _unused, k, fn=fn):
            def body(carry, _):
                acc, xi = carry
                out = fn(xi, dw, pw, s, b)
                ss = out.sum()
                xi = xi + (jnp.sign(ss) * 1e-3).astype(xi.dtype)
                return (acc + ss.astype(jnp.float32), xi), None

            (acc, _), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), xx), None, length=k
            )
            return acc

        try:
            float(chained(x, dw, pw, s, b, None, args.scan_len))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                float(chained(x, dw, pw, s, b, None, args.scan_len))
                times.append((time.perf_counter() - t0) / args.scan_len)
            t = float(np.median(times))
            print(
                f"{name:12s}: {t * 1e3:8.3f} ms  GEMM-only MFU {gemm_tf / t / 197 * 100:4.1f}%"
            )
        except Exception as e:
            print(f"{name:12s}: FAILED {str(e).splitlines()[0][:120]}")


if __name__ == "__main__":
    main()
