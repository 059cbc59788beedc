"""TPU fast path for Xception: the flax graph with its separable-convolution
blocks swapped for fused Pallas kernels (ops.fused_sepconv).

A pure function over the SAME variable tree the flax module owns -- the
module stays the single source of structure (init, .h5 import, export,
training all unchanged); this path only changes how serving COMPUTES the
forward.  conv1, conv2 and block 2 mirror flax.linen numerics op for op as
XLA fusions (bf16 compute, Keras BN epsilon; block 2's 64-channel stencils
would run on half-empty lanes).  From there on everything runs in the
kernels' (H, W, B, C) layout: the entry flow's downsample blocks whose
whole-extent tile the chain kernel can hold at a batch the chip has timed
(blocks 3 and 4 at 299x299 from a bucket of 256 up; the rule is shape
arithmetic, ``chained_entry_blocks``; where it says no they stay XLA
fusions too) and block 13 through ``downsample_t``, the middle flow's eight blocks through the fused residual
kernel, block 14 as a chain, and the head mean over the leading axes -- one
transpose in, none out.  What it is worth on a v5e chip is measured by the
benchmark's Xception cell (PERF.md section 5).

Numerics: the kernels fold BN to an f32 affine and sum the depthwise taps
in f32, so logits differ from the flax path by bf16-rounding-level noise
(asserted < 1% relative in tests/test_fused_sepconv.py); exact-parity paths
(golden verification, export) keep using the flax graph.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubernetes_deep_learning_tpu.models.layers import KERAS_BN_EPS
from kubernetes_deep_learning_tpu.modelspec import ModelSpec
from kubernetes_deep_learning_tpu.ops.fused_entry import (
    entry_block_weights,
    fused_entry_block_t,
)
from kubernetes_deep_learning_tpu.ops.fused_sepconv import (
    CHAIN_VMEM_LIMIT_BYTES,
    chain_batch_tile,
    fold_bn,
    fused_sepconv_block_t,
    fused_sepconv_chain_t,
    middle_block_weights,
    sepconv_stage_weights,
)

_ENTRY_BLOCKS = ((2, 128), (3, 256), (4, 728))  # keep in sync with models.xception
_MIDDLE_BLOCKS = tuple(range(5, 13))

# Microbatch chunking (round 4).  The fused path's device time per image is
# non-monotonic in batch: 197 us/img at batch 16 but 222/232/209 at
# 32/48/64 (exp/batch_dip_trace.py) -- XLA picks worse entry-flow fusion
# schedules at those sizes.  Running those batches as UNROLLED 16-image
# chunks inside one jitted program restores the batch-16 schedule per
# chunk: 0.88x/0.84x/0.92x device span at 32/48/64, while 128 is faster
# monolithic (1.07x chunked) -- measured on a v5e chip
# (exp/chunked_forward.py).  8-multiples that are not 16-multiples (40,
# 56) take a trailing 8-image chunk (batch-8 also beats the 32-64
# monoliths per image): 0.87x at 40.  lax.map chunking is NOT equivalent:
# the loop body compiles ~2x slower than the same chunk standalone
# (1.7-1.8x net).
_CHUNK = 16
_TAIL = 8  # trailing-chunk granularity (the kernels' sublane alignment)
_CHUNK_MIN, _CHUNK_MAX = 32, 64


def _chunk_sizes(batch: int) -> list[int] | None:
    """Chunk sizes to split ``batch`` into, or None for monolithic."""
    if batch % _TAIL or not _CHUNK_MIN <= batch <= _CHUNK_MAX:
        return None
    k, r = divmod(batch, _CHUNK)
    sizes = [_CHUNK] * k + ([r] if r else [])
    return sizes if len(sizes) > 1 else None


def entry_block_shapes(input_hw: tuple[int, int]):
    """(block, h, w, (c_in, c_mid, c_out)) of what each downsample block of
    the entry flow reads, for images of ``input_hw``."""
    h, w = ((d - 3) // 2 - 1 for d in input_hw)  # conv1 3x3/2, conv2 3x3, VALID
    c_in = 64
    for idx, feat in _ENTRY_BLOCKS:
        yield idx, h, w, (c_in, feat, feat)
        h, w, c_in = -(-h // 2), -(-w // 2), feat  # the block's 3x3/2 SAME pool


def chained_entry_blocks(input_hw: tuple[int, int], batch: int) -> dict[int, int]:
    """{entry block -> batch tile} of the entry flow's downsample blocks that
    run as a fused chain (``downsample_t``) in a forward over ``batch``
    images of ``input_hw``; the others stay XLA fusions.  Shape arithmetic
    alone (``ops.fused_sepconv.chain_batch_tile`` decides each block): the
    forward asks it while tracing and ``fused_blocks`` asks it for the
    status page, so the two cannot disagree."""
    padded = batch + (-batch) % 8
    tiles = {
        idx: chain_batch_tile(padded, h, w, widths)
        for idx, h, w, widths in entry_block_shapes(input_hw)
    }
    return {idx: bt for idx, bt in tiles.items() if bt}


def fused_blocks(spec: ModelSpec, batch: int) -> list[str]:
    """Names of the blocks that run as Pallas kernels in the default fast
    forward's program for ``batch`` images, in order of execution (a
    chunked bucket runs them once a chunk, and every chunk decides for
    itself: a block is named if any chunk fuses it)."""
    sizes = _chunk_sizes(batch) or [batch]
    entry = sorted({
        idx for n in sizes for idx in chained_entry_blocks(spec.input_shape[:2], n)
    })
    return [f"block{idx}" for idx in (*entry, *_MIDDLE_BLOCKS, 13, 14)]


def downsample_t(
    xt, p, s, block, *, dtype=jnp.bfloat16, bt=0, interpret=False,
    vmem_limit_bytes=0,
):
    """Residual 1x1/2 conv (XLA einsum) + fused 2-sepconv chain + max-pool +
    add, in the (H, W, B, C) layout -- the one implementation of the
    pattern blocks 3, 4 and 13 share (relu -> sep -> bn, twice, then
    pool + res).  The entry blocks pass the batch tile ``chain_batch_tile``
    chose and a raised VMEM limit: the 74x74 chain holds ~107 MiB at bt=8."""
    res_scale, res_shift = fold_bn(p[f"{block}_res_bn"], s[f"{block}_res_bn"])
    res = jnp.einsum(
        "hwbc,cd->hwbd",
        xt[::2, ::2],
        jnp.asarray(p[f"{block}_res_conv"]["kernel"], dtype)[0, 0],
    )
    res = (res.astype(jnp.float32) * res_scale + res_shift).astype(dtype)
    y = fused_sepconv_chain_t(
        xt,
        [
            sepconv_stage_weights(
                p, s, f"{block}_sepconv1", f"{block}_sepconv1_bn",
                pre_relu=True, post_relu=False,
            ),
            sepconv_stage_weights(
                p, s, f"{block}_sepconv2", f"{block}_sepconv2_bn",
                pre_relu=True, post_relu=False,
            ),
        ],
        bt=bt,
        interpret=interpret,
        vmem_limit_bytes=vmem_limit_bytes,
    )
    pooled = jax.lax.reduce_window(
        y, -jnp.inf, jax.lax.max, (3, 3, 1, 1), (2, 2, 1, 1), "SAME"
    )
    return pooled + res


def build_fast_forward(
    spec: ModelSpec,
    dtype: Any = jnp.bfloat16,
    interpret: bool = False,
    entry_kernel: bool = False,
    conv1_t: bool = False,
    chunk: bool = True,
) -> Callable:
    """Return ``f(variables, normalized_f32_images) -> logits (dtype)``.

    The caller (models.build_forward) handles uint8 normalization and the
    final f32 cast, exactly as for the flax path.

    ``chunk`` (default on) runs 8-multiple batches in [32, 64] (i.e.
    32/40/48/56/64; 40 and 56 take a trailing 8-image chunk) as unrolled
    16-image microbatches
    inside the same program, which sidesteps XLA's worse
    entry-flow schedules at those sizes (+9-19% device throughput,
    exp/chunked_forward.py; see ``_chunk_sizes``).  Per-image numerics are
    those of the batch-16 program -- same bf16-noise tolerance vs flax.
    Off for the experimental entry-kernel paths so their measurements stay
    monolithic and attributable.

    ``entry_kernel`` (EXPERIMENTAL, default off) also routes conv2+block2
    through the fused entry Pallas kernel (ops.fused_entry), so everything
    from conv1's output to the head runs in the (H, W, B, C) layout, blocks
    3/4 as fused chains at every batch (the default path sends them there
    by ``chained_entry_blocks``, from a batch of 256 up, since PR 31).
    Round-3 verdict, from a machine that is gone: the kernel
    body (4.18 ms at batch 64) beats the XLA fusions it replaces
    (4.43 ms), but the halo-slab staging it needs costs another ~1.4 ms
    XLA-side, so the net is a LOSS (exp/model_fused_entry.py: 21.1 vs
    19.0 ms full-forward) -- manual DMA staging is blocked by Mosaic's
    128-aligned-lane sliced-DMA rule at c_in=32.  Kept off the serving
    path (models.build_forward never enables it) until the staging cost is
    solved.

    ``conv1_t`` (EXPERIMENTAL, requires entry_kernel) attacks that staging
    loss from the other side (VERDICT r3 #5): transpose the INPUT once
    (3 channels -- the cheapest tensor in the model) and run conv1/bn/relu
    directly in the (H, W, B, C) layout via conv dimension_numbers
    ("HWNC", "HWIO", "HWNC"), so the entry kernel's halo-slab gather reads
    a tensor already resident in its layout and the output-side staging
    transpose disappears.  Whether XLA:TPU compiles the HWNC conv without
    re-transposing internally is exactly what exp/model_fused_entry.py
    measures.
    """
    if conv1_t and not entry_kernel:
        raise ValueError(
            "conv1_t requires entry_kernel=True (without the entry kernel "
            "there is no transposed consumer; silently measuring the plain "
            "XLA path would misattribute results)"
        )

    def conv(x, kernel, stride=1, padding="SAME"):
        # flax nn.Conv(dtype=...) semantics: operands promoted to dtype,
        # no preferred accumulation type override.
        return jax.lax.conv_general_dilated(
            x.astype(dtype),
            jnp.asarray(kernel, dtype),
            (stride, stride),
            padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def depthwise(x, kernel):
        return jax.lax.conv_general_dilated(
            x.astype(dtype),
            jnp.asarray(kernel, dtype),
            (1, 1),
            "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1],
        )

    def bn(x, p, s):
        # flax BatchNorm(use_running_average=True, dtype=...): stats and
        # params promoted to dtype, computed in dtype.
        mean = jnp.asarray(s["mean"], dtype)
        var = jnp.asarray(s["var"], dtype)
        scale = jnp.asarray(p["scale"], dtype)
        bias = jnp.asarray(p["bias"], dtype)
        y = (x - mean) * jax.lax.rsqrt(var + jnp.asarray(KERAS_BN_EPS, dtype))
        return y * scale + bias

    def sepconv(x, p):
        x = depthwise(x, p["depthwise"]["kernel"])
        return conv(x, p["pointwise"]["kernel"])

    pool = lambda x: nn.max_pool(  # noqa: E731 - mirrors models.xception
        x, window_shape=(3, 3), strides=(2, 2), padding="SAME"
    )

    down = functools.partial(downsample_t, dtype=dtype, interpret=interpret)

    def downsample_xla(x, p, s, idx):
        """The same block as XLA fusions in NHWC, flax-identical op for op
        (block 2 has no leading relu)."""
        residual = conv(x, p[f"block{idx}_res_conv"]["kernel"], stride=2)
        residual = bn(residual, p[f"block{idx}_res_bn"], s[f"block{idx}_res_bn"])
        if idx > 2:
            x = nn.relu(x)
        x = sepconv(x, p[f"block{idx}_sepconv1"])
        x = bn(x, p[f"block{idx}_sepconv1_bn"], s[f"block{idx}_sepconv1_bn"])
        x = nn.relu(x)
        x = sepconv(x, p[f"block{idx}_sepconv2"])
        x = bn(x, p[f"block{idx}_sepconv2_bn"], s[f"block{idx}_sepconv2_bn"])
        return pool(x) + residual

    def forward_one(variables, x):
        p = variables["params"]
        s = variables["batch_stats"]

        # Batch rides the sublane axis in the kernels' (H, W, B, C) layout,
        # and their (H, W, bt) -> rows collapse is only Mosaic-legal when
        # the batch tile is 8-aligned (a batch-1 tile failed to compile
        # on the v5e).  Pad the batch ONCE to a multiple of 8 and slice after
        # the head mean, so the per-kernel padding in ops.fused_sepconv
        # stays a no-op and small serving buckets (1, 2, 4) compile the
        # same fused program.
        batch, *input_hw = x.shape[:3]
        pad_rows = (-batch) % 8

        def to_t(x):
            # NHWC -> the kernels' layout; a batch already padded stays.
            pad = (-x.shape[0]) % 8
            if pad:
                x = jnp.pad(x, ((0, pad), (0, 0), (0, 0), (0, 0)))
            return x.transpose(1, 2, 0, 3)

        if entry_kernel and conv1_t:
            # --- transposed from the INPUT: conv1 computes directly in
            # (H, W, B, C), so the entry kernel's slab gather reads data
            # already resident in its layout (VERDICT r3 #5) -------------
            if pad_rows:
                x = jnp.pad(x, ((0, pad_rows), (0, 0), (0, 0), (0, 0)))
            xt = x.transpose(1, 2, 0, 3)  # (H, W, B, 3): the cheap transpose
            xt = jax.lax.conv_general_dilated(
                xt.astype(dtype),
                jnp.asarray(p["block1_conv1"]["kernel"], dtype),
                (2, 2),
                "VALID",
                dimension_numbers=("HWNC", "HWIO", "HWNC"),
            )
            xt = nn.relu(bn(xt, p["block1_conv1_bn"], s["block1_conv1_bn"]))
            xt = fused_entry_block_t(
                xt.astype(jnp.bfloat16), entry_block_weights(p, s),
                interpret=interpret,
            ).astype(dtype)
            xt = down(xt, p, s, "block3", vmem_limit_bytes=CHAIN_VMEM_LIMIT_BYTES)
            xt = down(xt, p, s, "block4", vmem_limit_bytes=CHAIN_VMEM_LIMIT_BYTES)
        elif entry_kernel:
            x = conv(x, p["block1_conv1"]["kernel"], stride=2, padding="VALID")
            x = nn.relu(bn(x, p["block1_conv1_bn"], s["block1_conv1_bn"]))
            # --- transposed from conv1 out to the head: conv2+block2 in
            # the fused entry kernel, blocks 3/4 as fused chains ---------
            if pad_rows:
                x = jnp.pad(x, ((0, pad_rows), (0, 0), (0, 0), (0, 0)))
            xt = x.transpose(1, 2, 0, 3).astype(jnp.bfloat16)
            xt = fused_entry_block_t(
                xt, entry_block_weights(p, s), interpret=interpret
            ).astype(dtype)
            xt = down(xt, p, s, "block3", vmem_limit_bytes=CHAIN_VMEM_LIMIT_BYTES)
            xt = down(xt, p, s, "block4", vmem_limit_bytes=CHAIN_VMEM_LIMIT_BYTES)
        else:
            x = conv(x, p["block1_conv1"]["kernel"], stride=2, padding="VALID")
            x = nn.relu(bn(x, p["block1_conv1_bn"], s["block1_conv1_bn"]))
            x = conv(x, p["block1_conv2"]["kernel"], padding="VALID")
            x = nn.relu(bn(x, p["block1_conv2_bn"], s["block1_conv2_bn"]))
            # --- entry flow: each downsample block as a fused chain where
            # its tile fits the kernel (shape arithmetic, see
            # chained_entry_blocks), as XLA fusions where not.  The layout
            # changes to (H, W, B, C), batch padded, where the first chain
            # starts: XLA's own layout for these activations is already
            # H, W, B, C, so the transpose is a relabelling.
            chained = chained_entry_blocks(input_hw, batch)
            xt = None
            for idx, _feat in _ENTRY_BLOCKS:
                if idx in chained:
                    if xt is None:
                        xt = to_t(x)
                    xt = down(
                        xt, p, s, f"block{idx}", bt=chained[idx],
                        vmem_limit_bytes=CHAIN_VMEM_LIMIT_BYTES,
                    )
                else:
                    if xt is not None:
                        x, xt = xt.transpose(2, 0, 1, 3), None
                    x = downsample_xla(x, p, s, idx)
            if xt is None:
                xt = to_t(x)

        # --- middle + exit flows: fused Pallas chains ---------------------
        # Everything stays in (H, W, B, C): the exit flow's pool/residual
        # are layout-agnostic XLA ops, so the transpose back never happens
        # -- the head mean reduces over the leading spatial axes directly.
        for idx in _MIDDLE_BLOCKS:
            dw, pw, scale, shift = middle_block_weights(p, s, f"block{idx}")
            xt = fused_sepconv_block_t(xt, dw, pw, scale, shift, interpret=interpret)

        xt = down(xt, p, s, "block13")

        # block14: two sepconvs (sep -> bn -> relu pattern), fused.
        xt = fused_sepconv_chain_t(
            xt,
            [
                sepconv_stage_weights(
                    p, s, "block14_sepconv1", "block14_sepconv1_bn",
                    pre_relu=False, post_relu=True,
                ),
                sepconv_stage_weights(
                    p, s, "block14_sepconv2", "block14_sepconv2_bn",
                    pre_relu=False, post_relu=True,
                ),
            ],
            interpret=interpret,
        )

        # --- head (ClassifierHead semantics; spatial = leading axes) ---
        x = xt.mean(axis=(0, 1))[:batch]
        head = p["head"]
        i = 0
        while f"hidden_{i}" in head:
            h = head[f"hidden_{i}"]
            x = nn.relu(
                x @ jnp.asarray(h["kernel"], dtype) + jnp.asarray(h["bias"], dtype)
            )
            i += 1
        logits = head["logits"]
        return x @ jnp.asarray(logits["kernel"], dtype) + jnp.asarray(
            logits["bias"], dtype
        )

    def forward(variables, x):
        sizes = _chunk_sizes(x.shape[0]) if chunk and not entry_kernel else None
        if sizes:
            outs, lo = [], 0
            for n in sizes:
                outs.append(forward_one(variables, x[lo : lo + n]))
                lo += n
            return jnp.concatenate(outs, axis=0)
        return forward_one(variables, x)

    return forward
