"""Fused Xception entry segment: conv2 + block2 in one Pallas kernel.

The entry flow is the fast path's remaining bottleneck (round-2 trace:
~30 ms of the batch-256 forward; 4.4 ms of the 16.6 ms batch-64 forward,
running at only 10-28% MFU in XLA's fusions).  This kernel fuses the
segment the trace attributes most of that to:

    block1_conv2 3x3 VALID (C_IN->C_B) + BN + relu
    block2 residual 1x1 stride-2 conv + BN
    block2 sepconv1 (C_B->C_OUT) + BN + relu
    block2 sepconv2 (C_OUT->C_OUT) + BN
    maxpool 3x3/2 SAME + residual add

so the 147x147 intermediates (2.8-5.5 MB/image each) never round-trip
through HBM.  Reference analog: the whole entry flow happens inside the
TF-Serving binary's fused GPU graph (reference tf-serving.dockerfile:1);
here the hot segment is the framework's own kernel.

Design (same layout discipline as ops.fused_sepconv, see the round-2
lessons there):

- Layout (rows, W, bt, C): batch on sublanes, channels on lanes -- the
  layout XLA itself picks for these tensors.  Depthwise shifts and
  stride-2 selections move only along untiled outer dims.
- conv2 as in-kernel im2col: 9 lane-concatenated shifted slices make one
  (M, 9*C_IN) @ (9*C_IN, C_B) GEMM -- 9 accumulated K=32 GEMMs would
  waste 3/4 of every MXU pass.
- Spatial tiling with halos: output rows are tiled by ``rt``; overlapping
  input windows are not expressible in BlockSpec units, so the input is
  pre-gathered into per-tile slabs in XLA-land (~20-35% extra *input*
  traffic depending on rt -- input is the smallest tensor in the segment,
  so this trade wins over manual DMA complexity).
- Row-validity masks re-zero rows the BN affines contaminate in the halo
  region, and invalid rows are sent to -1e9 before the max-pool so they
  cannot win a window.

Geometry is parameterized (h_in, c_in, c_b, c_out) so tests exercise the
same code at small shapes in interpret mode; serving uses the Xception
numbers (149, 32, 64, 128).
"""

from __future__ import annotations

import functools

from kubernetes_deep_learning_tpu.ops.fused_sepconv import _legal_bt


@functools.cache
def _entry_compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    # The physical cap is 128 MiB on v5e; rt=13/bt=8 at the Xception shape
    # peaks just under 110 MiB.
    return pltpu.CompilerParams(vmem_limit_bytes=110 * 1024 * 1024)


def entry_block_reference(a, w):
    """Plain-jnp semantics, NHWC (B, h, h, c_in) -> (B, h_out, h_out, c_out).

    Mirrors models.xception's conv2+block2 ops with BN folded to f32
    affines (the kernel's numerics); used by tests and as documentation of
    the contract.
    """
    import jax
    import jax.numpy as jnp

    def conv(x, k, stride=1, padding="VALID", fgc=1):
        return jax.lax.conv_general_dilated(
            x, k.astype(x.dtype), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=fgc,
        )

    c_b = w["conv2"].shape[-1]
    c_out = w["pw1"].shape[-1]
    b = conv(a, w["conv2"])
    b = jnp.maximum(
        b.astype(jnp.float32) * w["conv2_s"] + w["conv2_b"], 0
    ).astype(jnp.bfloat16)
    r = jnp.einsum("bhwc,cd->bhwd", b[:, ::2, ::2, :], w["res"].astype(jnp.bfloat16))
    r = (r.astype(jnp.float32) * w["res_s"] + w["res_b"]).astype(jnp.bfloat16)
    c = conv(b, w["dw1"][:, :, None, :].astype(jnp.bfloat16), padding="SAME", fgc=c_b)
    c = jnp.einsum("bhwc,cd->bhwd", c, w["pw1"].astype(jnp.bfloat16))
    c = jnp.maximum(
        c.astype(jnp.float32) * w["bn1_s"] + w["bn1_b"], 0
    ).astype(jnp.bfloat16)
    d = conv(c, w["dw2"][:, :, None, :].astype(jnp.bfloat16), padding="SAME", fgc=c_out)
    d = jnp.einsum("bhwc,cd->bhwd", d, w["pw2"].astype(jnp.bfloat16))
    d = (d.astype(jnp.float32) * w["bn2_s"] + w["bn2_b"]).astype(jnp.bfloat16)
    pooled = jax.lax.reduce_window(
        d, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    return pooled + r


def fused_entry_block_t(a_t, w, *, bt: int = 8, rt: int = 13, interpret: bool = False):
    """The kernel, on (h_in, h_in, B, c_in) bf16; returns (h_out, h_out, B, c_out).

    ``w`` is a dict of f32 weights: conv2 (3,3,c_in,c_b), res (c_b,c_out),
    dw1 (3,3,c_b), pw1 (c_b,c_out), dw2 (3,3,c_out), pw2 (c_out,c_out),
    plus folded-BN affine pairs conv2_s/conv2_b, res_s/res_b, bn1_s/bn1_b,
    bn2_s/bn2_b (see ops.fused_sepconv.fold_bn).

    B must be a multiple of 8 (callers pad, as for the sepconv kernels);
    ``rt`` is output rows per grid step (13 measured best at batch 64 --
    fewer tiles means less halo re-read, larger tiles blow scoped VMEM).

    The overlapping input row windows are staged as a SINGLE row-gather
    (one XLA op): the round-2 prototype stacked per-tile slices, which XLA
    compiled to six ~0.24 ms staging fusions (~1.7 ms total at batch 64,
    more than the kernel saved).  Manual HBM->VMEM DMA would avoid staging
    entirely but is impossible here: Mosaic requires sliced-DMA lane dims
    to be 128-aligned and the input has 32 channels (probed on v5e,
    "Slice shape along dimension 3 must be aligned to tiling (128)").
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    h_in, _, B, c_in = a_t.shape
    c_b = w["conv2"].shape[-1]
    c_out = w["pw1"].shape[-1]
    h_b = h_in - 2           # conv2 VALID
    h_out = -(-h_b // 2)     # pool stride 2 SAME
    assert B % 8 == 0, f"pad the batch to a multiple of 8 first (got {B})"
    bt = _legal_bt(bt, B)
    n_tiles = -(-h_out // rt)
    nb = B // bt
    ht_b = 2 * rt + 5        # b rows a tile needs (pool +-1, two dws +-1 each)
    ht_a = ht_b + 2          # conv2 VALID consumes 2 more
    # Top pad 3 (tile g starts at global a row 2*rt*g - 3), bottom pad to
    # cover the last slab.  No W pad: conv2's VALID column reach tops out
    # at h_in - 1.
    bottom = max(0, 2 * rt * (n_tiles - 1) + ht_a - (h_in + 3))
    a_pad = jnp.pad(a_t, ((3, bottom), (0, 0), (0, 0), (0, 0)))
    wp = h_in

    def compute_tile(a, g_r, refs, o_ref):
        """The fused segment for one (row-tile, batch-tile) step.
        ``a``: (ht_a, wp, bt, c_in) bf16 value; writes o_ref[0]."""
        (cv_ref, cvs_ref, cvb_ref, res_ref, ress_ref, resb_ref,
         dw1_ref, pw1_ref, s1_ref, b1_ref, dw2_ref, pw2_ref, s2_ref,
         b2_ref) = refs

        # --- conv2 3x3 VALID: im2col on lanes -> ONE K=9*c_in GEMM --------
        patches = jnp.concatenate(
            [
                a[dh : dh + ht_b, dwc : dwc + h_b, :, :]
                for dh in range(3)
                for dwc in range(3)
            ],
            axis=-1,
        )  # (ht_b, h_b, bt, 9*c_in), taps (dh, dwc)-major like cv's reshape
        z = jax.lax.dot_general(
            patches.reshape(ht_b * h_b * bt, 9 * c_in),
            cv_ref[...].reshape(9 * c_in, c_b).astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        b = jnp.maximum(z * cvs_ref[...] + cvb_ref[...], 0).astype(
            jnp.bfloat16
        ).reshape(ht_b, h_b, bt, c_b)

        # Validity of local b rows (global row = 2*rt*g - 3 + L).  Masks
        # carry full (bt, C) extent: Mosaic cannot broadcast one value over
        # sublanes AND lanes at once; int compares only (no bf16 compare).
        row0_b = 2 * rt * g_r - 3

        def row_mask(c):
            rows = (
                jax.lax.broadcasted_iota(jnp.int32, (ht_b, 1, bt, c), 0)
                + row0_b
            )
            return (rows >= 0) & (rows < h_b)

        valid_b = row_mask(c_b)
        b = b * valid_b.astype(jnp.bfloat16)

        # --- stride-2 selection: slice+reshape on OUTER dims (a
        # double-strided slice lowers to an unsupported Mosaic gather) ----
        def every_other(x, start, count, axis):
            idx = [slice(None)] * x.ndim
            idx[axis] = slice(start, start + 2 * count)
            x = x[tuple(idx)]
            shape = list(x.shape)
            shape[axis : axis + 1] = [count, 2]
            x = x.reshape(shape)
            idx = [slice(None)] * x.ndim
            idx[axis + 1] = 0
            out = x[tuple(idx)]
            return out.reshape(
                [s for i, s in enumerate(x.shape) if i != axis + 1]
            )

        # Residual 1x1/2 on b: row0_b is odd, so local rows 3,5,... are the
        # global even rows 2*rt*g, 2*rt*g + 2, ...
        b_rows = every_other(b, 3, rt + 1, 0)
        b_rows = jnp.pad(b_rows, ((0, 0), (0, 1), (0, 0), (0, 0)))
        b_even = every_other(b_rows, 0, (h_b + 1) // 2, 1)
        hr, wr = b_even.shape[0], b_even.shape[1]
        r = jax.lax.dot_general(
            b_even.reshape(hr * wr * bt, c_b),
            res_ref[...].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        r = (r * ress_ref[...] + resb_ref[...]).astype(jnp.bfloat16).reshape(
            hr, wr, bt, c_out
        )

        # --- the two sepconvs --------------------------------------------
        def dw(x, dwk):
            xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0), (0, 0)))
            acc = jnp.zeros(x.shape, jnp.float32)
            for dh in range(3):
                for dwc in range(3):
                    acc = acc + (
                        xp[dh : dh + x.shape[0], dwc : dwc + x.shape[1], :, :]
                        .astype(jnp.float32) * dwk[dh, dwc, :]
                    )
            return acc

        c = dw(b, dw1_ref[...])
        c = jax.lax.dot_general(
            c.astype(jnp.bfloat16).reshape(ht_b * h_b * bt, c_b),
            pw1_ref[...].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        c = jnp.maximum(c * s1_ref[...] + b1_ref[...], 0).astype(
            jnp.bfloat16
        ).reshape(ht_b, h_b, bt, c_out)
        valid_out = row_mask(c_out)
        c = c * valid_out.astype(jnp.bfloat16)  # re-zero contaminated rows

        d = dw(c, dw2_ref[...])
        d = jax.lax.dot_general(
            d.astype(jnp.bfloat16).reshape(ht_b * h_b * bt, c_out),
            pw2_ref[...].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        d = (d * s2_ref[...] + b2_ref[...]).reshape(ht_b, h_b, bt, c_out)
        # Invalid rows must lose the max-pool, not win it.
        d = jnp.where(valid_out, d, -1e9).astype(jnp.bfloat16)
        # SAME pool (1,1) col pad; one spare row/col keeps the stride-2
        # selections of the last window in range.
        d = jnp.pad(d, ((0, 0), (1, 1), (0, 0), (0, 0)), constant_values=-1e9)
        d = jnp.pad(d, ((0, 1), (0, 1), (0, 0), (0, 0)), constant_values=-1e9)

        # --- maxpool 3x3/2 + residual ------------------------------------
        # Out row j: window d rows 2*(rt*g+j)-1..+1 = local rows 2j+2..2j+4.
        pooled = None
        for dh in range(3):
            for dwc in range(3):
                sl = every_other(d, 2 + dh, rt, 0)
                sl = every_other(sl, dwc, h_out, 1)
                pooled = sl if pooled is None else jnp.maximum(pooled, sl)
        o_ref[0] = pooled + r[:rt, :h_out, :, :]

    weight_args = (
        w["conv2"], w["conv2_s"], w["conv2_b"], w["res"], w["res_s"],
        w["res_b"], w["dw1"], w["pw1"], w["bn1_s"], w["bn1_b"], w["dw2"],
        w["pw2"], w["bn2_s"], w["bn2_b"],
    )
    weight_shapes = tuple(tuple(x.shape) for x in weight_args)
    out_shape = jax.ShapeDtypeStruct((n_tiles, rt, h_out, B, c_out), jnp.bfloat16)

    # One row-gather stages every tile's overlapping window; the reshape to
    # the 5D slab stack is free (contiguous rows).
    import numpy as np

    row_idx = np.concatenate(
        [np.arange(2 * rt * g, 2 * rt * g + ht_a) for g in range(n_tiles)]
    )
    slabs = a_pad[row_idx].reshape(n_tiles, ht_a, wp, B, c_in)

    def kernel_slab(a_ref, *rest):
        compute_tile(a_ref[0], pl.program_id(0), rest[:14], rest[14])

    out = pl.pallas_call(
        kernel_slab,
        grid=(n_tiles, nb),
        in_specs=[
            pl.BlockSpec(
                (1, ht_a, wp, bt, c_in), lambda gr, gb: (gr, 0, 0, gb, 0)
            ),
            *(
                pl.BlockSpec(shp, functools.partial(lambda n, *_: (0,) * n, len(shp)))
                for shp in weight_shapes
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, rt, h_out, bt, c_out), lambda gr, gb: (gr, 0, 0, gb, 0)
        ),
        out_shape=out_shape,
        compiler_params=_entry_compiler_params(),
        interpret=interpret,
    )(slabs, *weight_args)
    # (n_tiles, rt, h_out, B, c_out) -> (h_out(+crop), h_out, B, c_out)
    return out.reshape(n_tiles * rt, h_out, B, c_out)[:h_out]


def entry_block_weights(params: dict, stats: dict):
    """Assemble the kernel's weight dict from the Xception flax tree
    (conv2 = block1_conv2 + bn; block2 residual + sepconv1/2 + bns),
    BN folded to f32 affines (ops.fused_sepconv.fold_bn)."""
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.ops.fused_sepconv import fold_bn

    cv_s, cv_b = fold_bn(params["block1_conv2_bn"], stats["block1_conv2_bn"])
    res_s, res_b = fold_bn(params["block2_res_bn"], stats["block2_res_bn"])
    bn1_s, bn1_b = fold_bn(params["block2_sepconv1_bn"], stats["block2_sepconv1_bn"])
    bn2_s, bn2_b = fold_bn(params["block2_sepconv2_bn"], stats["block2_sepconv2_bn"])
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return {
        "conv2": f32(params["block1_conv2"]["kernel"]),
        "conv2_s": cv_s, "conv2_b": cv_b,
        "res": f32(params["block2_res_conv"]["kernel"])[0, 0],
        "res_s": res_s, "res_b": res_b,
        "dw1": f32(params["block2_sepconv1"]["depthwise"]["kernel"])[:, :, 0, :],
        "pw1": f32(params["block2_sepconv1"]["pointwise"]["kernel"])[0, 0],
        "bn1_s": bn1_s, "bn1_b": bn1_b,
        "dw2": f32(params["block2_sepconv2"]["depthwise"]["kernel"])[:, :, 0, :],
        "pw2": f32(params["block2_sepconv2"]["pointwise"]["kernel"])[0, 0],
        "bn2_s": bn2_s, "bn2_b": bn2_b,
    }
