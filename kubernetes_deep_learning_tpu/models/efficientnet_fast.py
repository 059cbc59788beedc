"""TPU fast path for EfficientNet: the flax graph with stride-1 MBConv
blocks swapped for the fused Pallas kernel (ops.fused_mbconv).

Same design as models.xception_fast: a pure function over the SAME variable
tree the flax module owns (init/import/export/training unchanged); only how
serving COMPUTES the forward changes.  Round-3 context: B3 served at 12%
MFU with the whole block graph on XLA fusions, the 6x-expanded activation
round-tripping HBM between them (VERDICT r3 #4).

Layout strategy: the network alternates XLA segments (stem, expand-ratio-1
stage 1, stride-2 stage openers) with runs of fusible stride-1 blocks.
Fusible runs execute in the kernels' (H, W, B, C) layout; the forward
transposes lazily on entry to a run and back on exit, so consecutive
fused blocks -- including stride-1 stage openers, fused with
``residual=False`` -- pay no intermediate transposes.  Fusibility is
decided at trace time from static shapes: stride 1, expand_ratio > 1, and
the expanded bf16 tile at bt=8 within a VMEM budget (the two
high-resolution early stages stay on XLA).

Numerics: BN folded to f32 affines, silu in f32 before the bf16 cast back
(asserted <2% relative against the flax block in tests/test_fused_mbconv.py
and end-to-end in tests/test_efficientnet_fast.py); exact-parity paths
(golden verification, export) keep the flax graph.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from kubernetes_deep_learning_tpu.models.efficientnet import (
    _BASE_BLOCKS,
    _SE_RATIO,
    SCALING,
    round_filters,
    round_repeats,
)
from kubernetes_deep_learning_tpu.models.layers import KERAS_BN_EPS
from kubernetes_deep_learning_tpu.modelspec import ModelSpec
from kubernetes_deep_learning_tpu.ops.fused_mbconv import (
    fused_mbconv_block_t,
    mbconv_block_weights,
    mbconv_fusible,
)


def block_plan(width: float, depth: float):
    """Static per-block structure: (name, stride, kernel, features, expand)."""
    plan = []
    block_id = 0
    for expand, channels, repeats, stride, kernel in _BASE_BLOCKS:
        features = round_filters(channels, width)
        for rep in range(round_repeats(repeats, depth)):
            plan.append((
                f"block{block_id}",
                stride if rep == 0 else 1,
                kernel,
                features,
                expand,
            ))
            block_id += 1
    return plan


def build_fast_forward(
    spec: ModelSpec,
    dtype: Any = jnp.bfloat16,
    interpret: bool = False,
) -> Callable:
    """Return ``f(variables, normalized_f32_images) -> logits (dtype)``.

    The caller (models.build_forward) handles uint8 normalization and the
    final f32 cast, exactly as for the flax path.
    """
    variant = spec.family.removeprefix("efficientnet-")
    width, depth, _ = SCALING[variant]
    plan = block_plan(width, depth)

    def conv(x, kernel, stride=1, groups=1):
        return jax.lax.conv_general_dilated(
            x.astype(dtype),
            jnp.asarray(kernel, dtype),
            (stride, stride),
            "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
        )

    def bn(x, p, s):
        mean = jnp.asarray(s["mean"], dtype)
        var = jnp.asarray(s["var"], dtype)
        scale = jnp.asarray(p["scale"], dtype)
        bias = jnp.asarray(p["bias"], dtype)
        y = (x - mean) * jax.lax.rsqrt(var + jnp.asarray(KERAS_BN_EPS, dtype))
        return y * scale + bias

    silu = jax.nn.silu

    def mbconv_xla(x, bp, bs, stride, features, expand):
        """flax MBConvBlock semantics, functionally (NHWC, XLA fusions)."""
        c_in = x.shape[-1]
        y = x
        if expand != 1:
            y = conv(y, bp["expand_conv"]["kernel"])
            y = silu(bn(y, bp["expand_bn"], bs["expand_bn"]))
        y = conv(y, bp["dwconv"]["kernel"], stride=stride, groups=y.shape[-1])
        y = silu(bn(y, bp["dw_bn"], bs["dw_bn"]))
        se = bp["se"]
        m = y.mean(axis=(1, 2), keepdims=True)
        r = silu(
            conv(m, se["reduce"]["kernel"]) + jnp.asarray(se["reduce"]["bias"], dtype)
        )
        g = jax.nn.sigmoid(
            conv(r, se["expand"]["kernel"]) + jnp.asarray(se["expand"]["bias"], dtype)
        )
        y = y * g
        y = conv(y, bp["project_conv"]["kernel"])
        y = bn(y, bp["project_bn"], bs["project_bn"])
        if stride == 1 and c_in == features:
            y = y + x
        return y

    def fusible(h, w, stride, expand, c_in):
        return (
            stride == 1
            and expand != 1
            and mbconv_fusible(h, w, c_in * expand)
        )

    def forward(variables, x):
        p = variables["params"]
        s = variables["batch_stats"]
        batch = x.shape[0]
        # Batch rides the sublane axis in the fused runs; pad once to a
        # multiple of 8 (Mosaic row-collapse legality, see fused_sepconv)
        # and slice after the head mean.
        pad_rows = (-batch) % 8

        x = conv(x, p["stem_conv"]["kernel"], stride=2)
        x = silu(bn(x, p["stem_bn"], s["stem_bn"]))
        if pad_rows:
            x = jnp.pad(x, ((0, pad_rows), (0, 0), (0, 0), (0, 0)))

        xt = None  # transposed (H, W, B, C) tensor while inside a fused run
        for name, stride, _kernel, features, expand in plan:
            h, w = (xt.shape[0], xt.shape[1]) if xt is not None else (x.shape[1], x.shape[2])
            c_in = xt.shape[3] if xt is not None else x.shape[-1]
            if fusible(h, w, stride, expand, c_in):
                if xt is None:
                    xt = x.transpose(1, 2, 0, 3).astype(jnp.bfloat16)
                xt = fused_mbconv_block_t(
                    xt,
                    mbconv_block_weights(p, s, name),
                    residual=(c_in == features),
                    interpret=interpret,
                ).astype(dtype)
            else:
                if xt is not None:
                    x = xt.transpose(2, 0, 1, 3)
                    xt = None
                x = mbconv_xla(x, p[name], s[name], stride, features, expand)
        if xt is not None:
            x = xt.transpose(2, 0, 1, 3)

        x = conv(x, p["top_conv"]["kernel"])
        x = silu(bn(x, p["top_bn"], s["top_bn"]))

        x = x.mean(axis=(1, 2))[:batch]
        head = p["head"]
        i = 0
        while f"hidden_{i}" in head:
            hdn = head[f"hidden_{i}"]
            x = jax.nn.relu(
                x @ jnp.asarray(hdn["kernel"], dtype) + jnp.asarray(hdn["bias"], dtype)
            )
            i += 1
        logits = head["logits"]
        return x @ jnp.asarray(logits["kernel"], dtype) + jnp.asarray(
            logits["bias"], dtype
        )

    return forward
