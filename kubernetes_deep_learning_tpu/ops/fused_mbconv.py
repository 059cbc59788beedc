"""Fused MBConv residual block: one Pallas kernel per EfficientNet
stride-1 block (expand 1x1 -> depthwise kxk -> squeeze-excite -> project
1x1 -> +residual).

Why: EfficientNet-B3 served at 12% MFU (round-3 sweep) -- the MBConv
block is the Xception sepconv pattern (ops.fused_sepconv) plus an expand
GEMM, an SE gate, and silu epilogues, and XLA runs it as 4+ fusions with
the 6x-expanded activation round-tripping HBM between them.  This kernel
keeps the whole (H, W) extent of a batch tile resident in VMEM across the
entire block, exactly like the sepconv kernels:

- **Layout (H, W, bt, C)**: batch on sublanes, channels on lanes; the
  depthwise shifts move along OUTER dims only (no Mosaic relayouts), and
  each pointwise GEMM collapses (H*W*bt, C) rows onto the MXU.
- **Squeeze-excite in-kernel**: the tile holds the full spatial extent of
  its images, so SE's global mean is one in-VMEM reduction to (bt, C_mid);
  the two bottleneck GEMMs are FLOP-trivial.
- **BN folded** (fold_bn), **silu on the VPU** in f32 before the cast back.

Scope, stated: stride-1 blocks only, and only at spatial extents whose
expanded tile fits VMEM (B3's stages at <=38x38 -- which hold most of the
depth: the stride-2 stage openers and the two high-resolution early stages
stay on XLA).  The reference's analog of all of this is "use the
TF-Serving GPU image" (reference tf-serving.dockerfile:1); here the hot
block IS the framework's own kernel.
"""

from __future__ import annotations

import functools

from kubernetes_deep_learning_tpu.ops.fused_sepconv import (
    _legal_bt,
    _pad_batch_to_8,
    fold_bn,
)


def mbconv_block_weights(params: dict, stats: dict, block: str):
    """One stride-1 MBConv block's weights from the flax variable tree
    (models.efficientnet.MBConvBlock's parameter naming), BN folded.

    Returns a dict of arrays ready for fused_mbconv_block_t:
    expand_w (C_in, C_mid) bf16, expand_s/expand_b (C_mid,) f32,
    dw (k, k, C_mid) f32, dw_s/dw_b (C_mid,) f32,
    se_r_w (C_mid, S) bf16, se_r_b (S,) f32,
    se_e_w (S, C_mid) bf16, se_e_b (C_mid,) f32,
    proj_w (C_mid, C_out) bf16, proj_s/proj_b (C_out,) f32.
    """
    import jax.numpy as jnp

    p = params[block]
    s = stats[block]
    exp_s, exp_b = fold_bn(p["expand_bn"], s["expand_bn"])
    dw_s, dw_b = fold_bn(p["dw_bn"], s["dw_bn"])
    pr_s, pr_b = fold_bn(p["project_bn"], s["project_bn"])
    return {
        "expand_w": jnp.asarray(p["expand_conv"]["kernel"], jnp.float32)[0, 0].astype(
            jnp.bfloat16
        ),
        "expand_s": exp_s,
        "expand_b": exp_b,
        "dw": jnp.asarray(p["dwconv"]["kernel"], jnp.float32)[:, :, 0, :],
        "dw_s": dw_s,
        "dw_b": dw_b,
        "se_r_w": jnp.asarray(p["se"]["reduce"]["kernel"], jnp.float32)[0, 0].astype(
            jnp.bfloat16
        ),
        "se_r_b": jnp.asarray(p["se"]["reduce"]["bias"], jnp.float32),
        "se_e_w": jnp.asarray(p["se"]["expand"]["kernel"], jnp.float32)[0, 0].astype(
            jnp.bfloat16
        ),
        "se_e_b": jnp.asarray(p["se"]["expand"]["bias"], jnp.float32),
        "proj_w": jnp.asarray(p["project_conv"]["kernel"], jnp.float32)[0, 0].astype(
            jnp.bfloat16
        ),
        "proj_s": pr_s,
        "proj_b": pr_b,
    }


def mbconv_block_reference(x, w):
    """Plain-jnp semantics of the fused kernel (NHWC), for tests and CPU.

    Matches models.efficientnet.MBConvBlock with expand_ratio != 1,
    stride 1, SE enabled, residual (c_in == c_out), inference BN.
    """
    import jax
    import jax.numpy as jnp

    k = w["dw"].shape[0]
    pad = k // 2
    y = jnp.einsum(
        "bhwc,cd->bhwd",
        x.astype(jnp.bfloat16),
        w["expand_w"],
        preferred_element_type=jnp.float32,
    )
    y = jax.nn.silu(y * w["expand_s"] + w["expand_b"]).astype(jnp.bfloat16)

    yp = jnp.pad(y, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    acc = jnp.zeros(y.shape, jnp.float32)
    for a in range(k):
        for b in range(k):
            acc = acc + (
                yp[:, a : a + y.shape[1], b : b + y.shape[2], :].astype(jnp.float32)
                * w["dw"][a, b, :].astype(jnp.float32)
            )
    y = jax.nn.silu(acc * w["dw_s"] + w["dw_b"]).astype(jnp.bfloat16)

    m = y.astype(jnp.float32).mean(axis=(1, 2))  # (N, C_mid)
    r = jax.nn.silu(
        jnp.einsum("nc,cs->ns", m.astype(jnp.bfloat16), w["se_r_w"],
                   preferred_element_type=jnp.float32)
        + w["se_r_b"]
    )
    g = jax.nn.sigmoid(
        jnp.einsum("ns,sc->nc", r.astype(jnp.bfloat16), w["se_e_w"],
                   preferred_element_type=jnp.float32)
        + w["se_e_b"]
    )
    y = (y.astype(jnp.float32) * g[:, None, None, :]).astype(jnp.bfloat16)

    z = jnp.einsum(
        "bhwc,cd->bhwd", y, w["proj_w"], preferred_element_type=jnp.float32
    )
    z = z * w["proj_s"] + w["proj_b"]
    return x + z.astype(x.dtype)


# The expanded activation's VMEM working set is ~8 bytes/element: the bf16
# tile (2) + its zero-padded copy (2) + the f32 depthwise accumulator (4),
# before register-allocator spill headroom -- the batch-64 B3 compile with
# a bf16-only (2 B/elem) budget OOM'd VMEM at 159.5/128 MiB, 114 MiB of it
# spill slots (recorded in exp/mbconv_variants.py's first run).
_WORKING_SET_BYTES_PER_ELEM = 8
_TILE_BUDGET = 32 << 20
# Scoped-VMEM cap handed to the Mosaic compiler; module-level so
# experiments can raise it alongside _TILE_BUDGET without monkeypatching
# private internals (exp/mbconv_variants.py --tile-budget-mb).
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def mbconv_fusible(h: int, w: int, c_mid: int) -> bool:
    """Whether the fused kernel's SMALLEST legal tile (bt=8) fits the VMEM
    budget at this spatial extent; callers keep bigger blocks on XLA."""
    return h * w * 8 * c_mid * _WORKING_SET_BYTES_PER_ELEM <= _TILE_BUDGET


def pick_mbconv_bt(h: int, w: int, batch: int, c_mid: int) -> int:
    """Largest 8-multiple batch tile whose working set fits the budget."""
    for cand in (32, 24, 16, 8):
        if (
            batch % cand == 0
            and h * w * cand * c_mid * _WORKING_SET_BYTES_PER_ELEM <= _TILE_BUDGET
        ):
            return cand
    return 8


@functools.cache
def _compiler_params(limit_bytes: int):
    from jax.experimental.pallas import tpu as pltpu

    # Same 96 MiB default as fused_sepconv (via VMEM_LIMIT_BYTES): the
    # largest fused B3 tile under the default budget peaks well under
    # 64 MiB, so the limit leaves headroom below the 128 MiB physical cap.
    return pltpu.CompilerParams(vmem_limit_bytes=limit_bytes)


def fused_mbconv_block_t(xt, w, *, bt: int = 0, residual: bool = True,
                         interpret: bool = False):
    """The kernel, on (H, W, B, C_in) bf16 input; returns (H, W, B, C_out).

    Stride-1, SAME padding.  ``residual`` adds the input (caller guarantees
    C_out == C_in then); residual=False serves stride-1 stage openers whose
    channel count changes.  ``bt`` 0 = auto; non-8-aligned batches are
    sublane-padded (see fused_sepconv._pad_batch_to_8).  The SE mean
    reduces the spatial extent only -- padded batch rows are junk anyway
    and sliced off.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    xt, B_orig = _pad_batch_to_8(xt)
    H, W, B, C_in = xt.shape
    C_mid = w["expand_w"].shape[1]
    C_out = w["proj_w"].shape[1]
    if residual and C_out != C_in:
        raise ValueError(f"residual block needs C_out == C_in, got {C_in}->{C_out}")
    S = w["se_r_w"].shape[1]
    k = w["dw"].shape[0]
    pad = k // 2
    if bt == 0:
        bt = pick_mbconv_bt(H, W, B, C_mid)
    bt = _legal_bt(bt, B)

    def kernel(x_ref, ew_ref, es_ref, eb_ref, dw_ref, ds_ref, db_ref,
               rw_ref, rb_ref, xw_ref, xb_ref, pw_ref, ps_ref, pb_ref, o_ref):
        x = x_ref[...]  # (H, W, bt, C_in) bf16
        # expand 1x1 -> bn -> silu
        z = jax.lax.dot_general(
            x.reshape(H * W * bt, C_in), ew_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        z = z * es_ref[...] + eb_ref[...]
        z = (z * jax.nn.sigmoid(z)).astype(jnp.bfloat16).reshape(H, W, bt, C_mid)
        # depthwise kxk (zero halos = SAME) -> bn -> silu, f32 accumulation
        zp = jnp.pad(z, ((pad, pad), (pad, pad), (0, 0), (0, 0)))
        acc = jnp.zeros((H, W, bt, C_mid), jnp.float32)
        for dh in range(k):
            for dwc in range(k):
                tap = dw_ref[dh, dwc, :].astype(jnp.float32)
                acc = acc + (
                    zp[dh : dh + H, dwc : dwc + W, :, :].astype(jnp.float32) * tap
                )
        acc = acc * ds_ref[...] + db_ref[...]
        y32 = acc * jax.nn.sigmoid(acc)  # (H, W, bt, C_mid) f32
        # squeeze-excite: global spatial mean on the resident tile
        m = y32.mean(axis=(0, 1))  # (bt, C_mid)
        r = jax.lax.dot_general(
            m.astype(jnp.bfloat16), rw_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ) + rb_ref[...]
        r = r * jax.nn.sigmoid(r)  # silu, (bt, S)
        g = jax.lax.dot_general(
            r.astype(jnp.bfloat16), xw_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ) + xb_ref[...]
        g = jax.nn.sigmoid(g)  # (bt, C_mid)
        y = (y32 * g[None, None, :, :]).astype(jnp.bfloat16)
        # project 1x1 -> bn [-> +residual]
        z = jax.lax.dot_general(
            y.reshape(H * W * bt, C_mid), pw_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        z = z * ps_ref[...] + pb_ref[...]
        z = z.astype(jnp.bfloat16).reshape(H, W, bt, C_out)
        o_ref[...] = (x_ref[...] + z) if residual else z

    out = pl.pallas_call(
        kernel,
        grid=(B // bt,),
        in_specs=[
            pl.BlockSpec((H, W, bt, C_in), lambda g: (0, 0, g, 0)),
            pl.BlockSpec((C_in, C_mid), lambda g: (0, 0)),
            pl.BlockSpec((C_mid,), lambda g: (0,)),
            pl.BlockSpec((C_mid,), lambda g: (0,)),
            pl.BlockSpec((k, k, C_mid), lambda g: (0, 0, 0)),
            pl.BlockSpec((C_mid,), lambda g: (0,)),
            pl.BlockSpec((C_mid,), lambda g: (0,)),
            pl.BlockSpec((C_mid, S), lambda g: (0, 0)),
            pl.BlockSpec((S,), lambda g: (0,)),
            pl.BlockSpec((S, C_mid), lambda g: (0, 0)),
            pl.BlockSpec((C_mid,), lambda g: (0,)),
            pl.BlockSpec((C_mid, C_out), lambda g: (0, 0)),
            pl.BlockSpec((C_out,), lambda g: (0,)),
            pl.BlockSpec((C_out,), lambda g: (0,)),
        ],
        out_specs=pl.BlockSpec((H, W, bt, C_out), lambda g: (0, 0, g, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W, B, C_out), xt.dtype),
        compiler_params=_compiler_params(VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(
        xt, w["expand_w"], w["expand_s"], w["expand_b"],
        w["dw"], w["dw_s"], w["dw_b"],
        w["se_r_w"], w["se_r_b"], w["se_e_w"], w["se_e_b"],
        w["proj_w"], w["proj_s"], w["proj_b"],
    )
    return out if B_orig == B else out[:, :, :B_orig, :]


def fused_mbconv_block(x, w, *, bt: int = 0, residual: bool = True,
                       interpret: bool = False):
    """NHWC convenience wrapper (transposes in and out; for single use)."""
    xt = x.transpose(1, 2, 0, 3)
    out = fused_mbconv_block_t(xt, w, bt=bt, residual=residual,
                               interpret=interpret)
    return out.transpose(2, 0, 1, 3)
