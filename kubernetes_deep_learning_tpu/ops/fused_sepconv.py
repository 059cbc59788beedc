"""Fused separable-conv residual block: one Pallas kernel per Xception
middle block.

What the XLA graph does for one middle block is 3 sepconv fusions, each a
round trip through HBM (seen in the device trace): relu -> depthwise 3x3
-> pointwise GEMM -> BN affine, x3, + residual.  This kernel keeps the whole
(H, W) extent of a tile of images resident in VMEM across all three
sepconvs, eliminating the intermediate HBM traffic, and arranges the data
so TPU units are used on their terms (measured 83 -> 69 ms on the full
batch-256 Xception forward, exp/fused_middle.py progression):

- **Layout (H, W, B, C)** -- batch on sublanes, channels on lanes (the same
  layout XLA itself picks for these tensors: ``{0,3,2,1:T(8,128)}``).  The
  depthwise conv's 9 shifted reads then move only along OUTER dims -- no
  sublane/lane relayout (a naive (rows, C) layout spends more time in
  Mosaic relayouts than the GEMMs take).
- **Depthwise on the VPU** as 9 shifted multiply-adds over a zero-padded
  copy; zero halos give exact SAME-conv behavior with no masks.
- **Pointwise on the MXU**: (H*W*bt, C) @ (C, C) with f32 accumulation;
  the collapse is tile-aligned because bt is a multiple of 8 (or the whole
  batch) and C rides the lane dim.
- **BN folded**: inference-mode BatchNorm arrives as per-channel
  scale/shift (see ``fold_bn``), applied in f32 before the cast back.

The reference's analog of all of this is "use the TF-Serving GPU image"
(reference tf-serving.dockerfile:1); here the hot block IS the framework's
own kernel.
"""

from __future__ import annotations

import functools
from typing import Any

from kubernetes_deep_learning_tpu.models.layers import KERAS_BN_EPS


def fold_bn(bn_params: dict, bn_stats: dict, eps: float = KERAS_BN_EPS):
    """Inference BN -> (scale, shift): y = x * scale + shift, float32.

    jnp ops so it works on tracers (inside a jitted forward) as well as
    concrete arrays.  eps defaults to the model zoo's Keras-parity epsilon
    (models.layers.KERAS_BN_EPS) -- NOT flax's 1e-5 default.
    """
    import jax
    import jax.numpy as jnp

    gamma = jnp.asarray(bn_params["scale"], jnp.float32)
    beta = jnp.asarray(bn_params["bias"], jnp.float32)
    mean = jnp.asarray(bn_stats["mean"], jnp.float32)
    var = jnp.asarray(bn_stats["var"], jnp.float32)
    scale = gamma * jax.lax.rsqrt(var + eps)
    return scale, beta - mean * scale


def middle_block_weights(params: dict, stats: dict, block: str):
    """Stack one Xception middle block's 3 sepconvs for the fused kernel.

    Returns (dw (3,3,3,C) f32, pw (3,C,C) bf16, scale (3,C) f32,
    shift (3,C) f32) from the framework's flax variable tree (the layout
    models.keras_import produces and models.xception consumes).
    """
    import jax.numpy as jnp

    dws, pws, ss, bs = [], [], [], []
    for j in (1, 2, 3):
        sep = params[f"{block}_sepconv{j}"]
        dw = jnp.asarray(sep["depthwise"]["kernel"], jnp.float32)  # (3,3,1,C)
        pw = jnp.asarray(sep["pointwise"]["kernel"], jnp.float32)  # (1,1,C,C)
        scale, shift = fold_bn(
            params[f"{block}_sepconv{j}_bn"], stats[f"{block}_sepconv{j}_bn"]
        )
        dws.append(dw[:, :, 0, :])
        pws.append(pw[0, 0])
        ss.append(scale)
        bs.append(shift)
    return (
        jnp.stack(dws),
        jnp.stack(pws).astype(jnp.bfloat16),
        jnp.stack(ss),
        jnp.stack(bs),
    )


def pick_batch_tile(batch: int, h: int, w: int, c: int, budget_bytes: int = 9 << 20) -> int:
    """Largest bt in {16, 8} whose bf16 tile fits the budget (bt=16 at the
    Xception middle shape measured fastest); 8 otherwise.

    Only 8-multiples are ever returned: the kernel collapses (H, W, bt) into
    MXU rows, and Mosaic rejects that reshape unless the sublane-adjacent
    dim is 8-aligned (``(361,728)->(19,19,1,728)`` at bt=1 failed to compile
    on the v5e).  Callers with ``batch % 8 != 0`` must pad the batch axis up
    to a multiple of 8 first -- ``fused_sepconv_block_t`` and
    ``fused_sepconv_chain_t`` do this internally.
    """
    for bt in (16, 8):
        if batch % bt == 0 and h * w * bt * c * 2 <= budget_bytes:
            return bt
    return 8


# What a chain whose whole-extent tile is larger than the serving default
# (96 MiB, _compiler_params) may ask of the v5e's 128 MiB of VMEM.
CHAIN_VMEM_LIMIT_BYTES = 116 << 20


def chain_vmem_bytes(h: int, w: int, bt: int, widths: tuple[int, ...]) -> int:
    """VMEM one grid step of ``fused_sepconv_chain_t`` holds for a
    (h, w, bt, widths[0]) tile whose stages are widths[i] -> widths[i+1]:
    the input and output tiles (bf16, double-buffered by the pipeline), the
    weights (likewise), and the widest float32 value of the body (a
    depthwise sum or a GEMM's output).  Channels ride the lanes, so a
    width counts as its multiple of 128.  Within 5% of what Mosaic reports
    for the v5e at the four tiles read (``exp/entry_chains.py --describe``:
    205.2 and 159.9 MiB asked against 214.3 and 152.7 reckoned)."""
    lanes = [-(-c // 128) * 128 for c in widths]
    rows = h * w * bt
    tiles = 2 * rows * (lanes[0] + lanes[-1]) * 2
    weights = 2 * sum(
        9 * ci * 4 + ci * co * 2 + 2 * co * 4 for ci, co in zip(lanes, lanes[1:])
    )
    return tiles + weights + rows * max(lanes) * 4


# The smallest padded batch an entry chain runs at: what exp/entry_chains.py
# has timed on the chip and seen win (PERF.md section 6, PR 31).  Below it
# XLA keeps a program's small activations and weights in VMEM beside the
# kernel (``S(1)`` in the compiled bucket-16 and -64 programs), a chain that
# really holds 107 of the 128 MiB leaves them no room, and one bucket-64
# arrangement never returned from the chip.
CHAIN_MIN_BATCH = 256


def chain_batch_tile(batch: int, h: int, w: int, widths: tuple[int, ...]) -> int:
    """Whether a downsample block's two sepconv stages should run as one
    Pallas chain over (h, w, batch, widths[0]), by shape arithmetic alone:
    the batch tile to run it with, or 0 to leave the block to XLA.

    A chain wants every stage's channels to fill the 128 lanes (Xception's
    block 2 reads 64: its depthwise stencil would run on half-empty
    vectors, the negative result of exp/fused_entry.py), a batch of at
    least ``CHAIN_MIN_BATCH``, and its whole-extent tile, reckoned 5% high
    because ``chain_vmem_bytes`` may read that much low, inside the limit
    the kernel is compiled with -- what the rule admits must compile, or
    warm-up degrades the whole engine to the flax graph.  ``batch`` is the
    padded batch (a multiple of 8).
    """
    if min(widths) < 128 or batch < CHAIN_MIN_BATCH:
        return 0
    for bt in (16, 8):
        if batch % bt == 0 and 1.05 * chain_vmem_bytes(h, w, bt, widths) <= CHAIN_VMEM_LIMIT_BYTES:
            return bt
    return 0


def sepconv_block_reference(x, dw, pw, scale, shift):
    """Plain-jnp semantics of the fused kernel (NHWC), for tests and CPU."""
    import jax.numpy as jnp

    y = x
    for i in range(3):
        y = jnp.maximum(y, 0)
        yp = jnp.pad(y, ((0, 0), (1, 1), (1, 1), (0, 0)))
        acc = jnp.zeros(y.shape, jnp.float32)
        for a in range(3):
            for b in range(3):
                acc = acc + (
                    yp[:, a : a + y.shape[1], b : b + y.shape[2], :].astype(jnp.float32)
                    * dw[i, a, b, :].astype(jnp.float32)
                )
        z = jnp.einsum(
            "bhwc,cd->bhwd",
            acc.astype(jnp.bfloat16),
            pw[i],
            preferred_element_type=jnp.float32,
        )
        y = (z * scale[i] + shift[i]).astype(x.dtype)
    return x + y


def _pad_batch_to_8(xt):
    """Pad the (H, W, B, C) batch axis up to a multiple of 8 (min 8).

    The kernels collapse (H, W, bt) rows for the MXU; Mosaic only accepts
    that reshape when bt is 8-aligned, so any other batch is served by
    padding the sublane axis with zeros and slicing the result.  Returns
    (padded, original_B).  At small batches the waste is latency-trivial:
    the middle-flow tile is weight-bandwidth-bound, not row-bound.
    """
    import jax.numpy as jnp

    B = xt.shape[2]
    pad = (-B) % 8
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return xt, B


def _legal_bt(bt: int, B: int) -> int:
    """Clamp a (possibly caller-supplied) batch tile to a Mosaic-legal one:
    a multiple of 8 that divides the (already 8-aligned) padded batch."""
    bt = min(-(-bt // 8) * 8, B)
    while B % bt:
        bt -= 8
    return bt


def fused_sepconv_block_t(xt, dw, pw, scale, shift, *, bt: int = 0, interpret: bool = False):
    """The kernel, on (H, W, B, C) bf16 input; returns the same layout.

    Chain middle blocks in this transposed layout and pay the NHWC
    transpose once per flow (see models.xception_fast).  ``bt`` 0 = auto.
    Any batch size is legal: non-8-aligned batches are zero-padded on the
    sublane axis around the kernel (see _pad_batch_to_8).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    xt, B_orig = _pad_batch_to_8(xt)
    H, W, B, C = xt.shape
    bt = _legal_bt(bt or pick_batch_tile(B, H, W, C), B)

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
                        yp[dh : dh + H, dwc : dwc + W, :, :].astype(jnp.float32) * tap
                    )
            z = jax.lax.dot_general(
                acc.astype(jnp.bfloat16).reshape(H * W * bt, C),
                pw_ref[i],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            y = (z * s_ref[i] + b_ref[i]).astype(jnp.bfloat16).reshape(H, W, bt, C)
        o_ref[...] = x_ref[...] + y

    out = pl.pallas_call(
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
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(xt, dw, pw, scale, shift)
    return out if B_orig == B else out[:, :, :B_orig, :]


@functools.cache
def _compiler_params(limit_bytes: int = 96 * 1024 * 1024) -> Any:
    from jax.experimental.pallas import tpu as pltpu

    # The default 16 MiB scoped-vmem cap rejects the bt=16 tile; v5e has
    # 128 MiB physical VMEM.  Default 96 MiB: the middle and exit flows'
    # tiles need far less, and it leaves headroom below the physical cap.
    # Only the entry flow's chains ask for more (CHAIN_VMEM_LIMIT_BYTES):
    # block 3's (74x74, 128->256 channels) holds ~107 MiB at bt=8.
    return pltpu.CompilerParams(vmem_limit_bytes=limit_bytes)


def fused_sepconv_block(x, dw, pw, scale, shift, *, bt: int = 0, interpret: bool = False):
    """NHWC convenience wrapper (transposes in and out; for single use)."""
    xt = x.transpose(1, 2, 0, 3)
    out = fused_sepconv_block_t(xt, dw, pw, scale, shift, bt=bt, interpret=interpret)
    return out.transpose(2, 0, 1, 3)


def fused_sepconv_chain_t(
    xt,
    stages,
    *,
    bt: int = 0,
    interpret: bool = False,
    vmem_limit_bytes: int = 0,
):
    """A chain of sepconv+BN stages in one kernel, (H, W, B, C) layout.

    ``stages``: sequence of dicts with keys ``dw`` (3,3,C_in) f32, ``pw``
    (C_in, C_out) bf16, ``scale``/``shift`` (C_out,) f32, ``pre_relu`` /
    ``post_relu`` bools -- covering both Xception exit patterns
    (block13: relu -> sep -> bn; block14: sep -> bn -> relu).  No residual,
    no pooling: those stay in XLA around the call.  Channel widths may grow
    along the chain (728 -> 1024 -> 1536 -> 2048 in the exit flow).

    Same layout argument as fused_sepconv_block_t: depthwise shifts move
    only along untiled outer dims; each pointwise GEMM takes the whole
    (H*W*bt) row extent.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    xt, B_orig = _pad_batch_to_8(xt)
    H, W, B, C0 = xt.shape
    bt = _legal_bt(
        bt or pick_batch_tile(B, H, W, max(s["pw"].shape[1] for s in stages)), B
    )
    c_out_final = stages[-1]["pw"].shape[1]
    pre = tuple(bool(s["pre_relu"]) for s in stages)
    post = tuple(bool(s["post_relu"]) for s in stages)

    def kernel(x_ref, *refs):
        o_ref = refs[-1]
        stage_refs = [refs[i * 4 : i * 4 + 4] for i in range(len(stages))]
        y = x_ref[...]
        for i, (dw_ref, pw_ref, s_ref, b_ref) in enumerate(stage_refs):
            c_in = y.shape[-1]
            if pre[i]:
                y = jnp.maximum(y, 0)
            yp = jnp.pad(y, ((1, 1), (1, 1), (0, 0), (0, 0)))
            acc = jnp.zeros((H, W, bt, c_in), jnp.float32)
            for dh in range(3):
                for dwc in range(3):
                    tap = dw_ref[dh, dwc, :].astype(jnp.float32)
                    acc = acc + (
                        yp[dh : dh + H, dwc : dwc + W, :, :].astype(jnp.float32) * tap
                    )
            z = jax.lax.dot_general(
                acc.astype(jnp.bfloat16).reshape(H * W * bt, c_in),
                pw_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            z = z * s_ref[...] + b_ref[...]
            if post[i]:
                z = jnp.maximum(z, 0)
            y = z.astype(jnp.bfloat16).reshape(H, W, bt, pw_ref.shape[1])
        o_ref[...] = y

    in_specs = [pl.BlockSpec((H, W, bt, C0), lambda g: (0, 0, g, 0))]
    args = [xt]
    for s in stages:
        c_in, c_out = s["pw"].shape
        in_specs += [
            pl.BlockSpec((3, 3, c_in), lambda g: (0, 0, 0)),
            pl.BlockSpec((c_in, c_out), lambda g: (0, 0)),
            pl.BlockSpec((c_out,), lambda g: (0,)),
            pl.BlockSpec((c_out,), lambda g: (0,)),
        ]
        args += [s["dw"], s["pw"], s["scale"], s["shift"]]

    out = pl.pallas_call(
        kernel,
        grid=(B // bt,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((H, W, bt, c_out_final), lambda g: (0, 0, g, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W, B, c_out_final), xt.dtype),
        compiler_params=(
            _compiler_params(vmem_limit_bytes) if vmem_limit_bytes
            else _compiler_params()
        ),
        interpret=interpret,
    )(*args)
    return out if B_orig == B else out[:, :, :B_orig, :]


def sepconv_stage_weights(params: dict, stats: dict, sep_name: str, bn_name: str,
                          pre_relu: bool, post_relu: bool):
    """One chain stage from the flax tree (see middle_block_weights)."""
    import jax.numpy as jnp

    sep = params[sep_name]
    scale, shift = fold_bn(params[bn_name], stats[bn_name])
    return {
        "dw": jnp.asarray(sep["depthwise"]["kernel"], jnp.float32)[:, :, 0, :],
        "pw": jnp.asarray(sep["pointwise"]["kernel"], jnp.float32)[0, 0].astype(
            jnp.bfloat16
        ),
        "scale": scale,
        "shift": shift,
        "pre_relu": pre_relu,
        "post_relu": post_relu,
    }
