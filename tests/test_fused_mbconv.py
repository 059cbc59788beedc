"""Fused MBConv kernel, validated on CPU (interpret mode; conftest.py).

Pinned here: kernel-vs-reference numerics (3x3 and 5x5 taps, sublane-padded
batches), weight extraction + the whole fused block against the REAL
flax.linen MBConvBlock on the same initialized variables.  The real-TPU
speed claim is exp/mbconv_variants.py's job.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_deep_learning_tpu.models.efficientnet import MBConvBlock
from kubernetes_deep_learning_tpu.ops.fused_mbconv import (
    fused_mbconv_block,
    mbconv_block_reference,
    mbconv_block_weights,
)


def _random_weights(rng, c_in, expand, k, se):
    c_mid = c_in * expand
    f32 = lambda *s: jnp.asarray(rng.normal(0, 0.15, s), jnp.float32)  # noqa: E731
    return {
        "expand_w": f32(c_in, c_mid).astype(jnp.bfloat16),
        "expand_s": jnp.asarray(rng.uniform(0.8, 1.2, c_mid), jnp.float32),
        "expand_b": f32(c_mid),
        "dw": f32(k, k, c_mid),
        "dw_s": jnp.asarray(rng.uniform(0.8, 1.2, c_mid), jnp.float32),
        "dw_b": f32(c_mid),
        "se_r_w": f32(c_mid, se).astype(jnp.bfloat16),
        "se_r_b": f32(se),
        "se_e_w": f32(se, c_mid).astype(jnp.bfloat16),
        "se_e_b": f32(c_mid),
        "proj_w": f32(c_mid, c_in).astype(jnp.bfloat16),
        "proj_s": jnp.asarray(rng.uniform(0.8, 1.2, c_in), jnp.float32),
        "proj_b": f32(c_in),
    }


@pytest.mark.parametrize(
    "shape,k",
    [
        ((4, 6, 6, 128), 3),
        ((2, 5, 7, 128), 5),
        # non-8-multiple batches run via sublane padding
        ((3, 6, 6, 128), 3),
        ((1, 4, 4, 128), 5),
    ],
)
def test_kernel_matches_reference(shape, k):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
    w = _random_weights(rng, shape[-1], expand=2, k=k, se=32)
    want = np.asarray(mbconv_block_reference(x, w), np.float32)
    got = np.asarray(
        jax.jit(lambda x: fused_mbconv_block(x, w, interpret=True))(x), np.float32
    )
    assert got.shape == shape
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"kernel diverges from reference: {rel:.2e}"


def test_fused_block_matches_flax_mbconv():
    """Weight extraction + kernel vs the real flax MBConvBlock (inference
    BN, expand 6x, SE, residual) on the same initialized variables."""
    rng = np.random.default_rng(2)
    c = 128
    block = MBConvBlock(
        features=c, expand_ratio=6, kernel=3, strides=1,
        se_features=max(1, c // 4), dtype=jnp.bfloat16, name="blk",
    )
    x0 = jnp.asarray(rng.normal(0, 1, (4, 7, 7, c)), jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x0, train=False)
    # Realistic (non-init) BN stats so folding is actually exercised.
    stats = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype),
        variables["batch_stats"],
    )
    variables = {"params": variables["params"], "batch_stats": stats}

    want = np.asarray(
        block.apply(variables, x0.astype(jnp.bfloat16), train=False), np.float32
    )
    w = mbconv_block_weights(
        {"blk": variables["params"]}, {"blk": stats}, "blk"
    )
    got = np.asarray(
        jax.jit(
            lambda x: fused_mbconv_block(x.astype(jnp.bfloat16), w, interpret=True)
        )(x0),
        np.float32,
    )
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"fused block diverges from flax MBConv: {rel:.2e}"
