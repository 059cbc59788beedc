"""EfficientNet fast path vs the stock flax graph, on CPU interpret mode.

End-to-end logits parity on a small B0 spec whose stages exercise BOTH
paths at trace time: XLA segments (stem, expand-ratio-1 stage 1, stride-2
openers) and fused runs (stride-1 repeats AND the stride-1 stage-5/7
openers fused with residual=False).  Real-TPU speed is
exp/mbconv_variants.py's job.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_deep_learning_tpu.models import build_forward, init_variables
from kubernetes_deep_learning_tpu.models.efficientnet_fast import (
    block_plan,
    build_fast_forward,
)
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec

_SPEC = register_spec(
    ModelSpec(
        name="effnet-fast-test",
        family="efficientnet-b0",
        input_shape=(64, 64, 3),
        labels=("a", "b", "c"),
        preprocessing="tf",
        description="test-only fast-path EfficientNet",
    )
)


def test_block_plan_b3_structure():
    """The static plan must reproduce the flax module's block layout (same
    round_filters/round_repeats math): B3 = 26 blocks, stage channel
    ladder 24/32/48/96/136/232/384."""
    plan = block_plan(1.2, 1.4)
    assert len(plan) == 26
    feats = sorted({f for _, _, _, f, _ in plan})
    assert feats == [24, 32, 48, 96, 136, 232, 384]
    # Stage openers carry the stage stride; repeats are stride 1.
    assert plan[0] == ("block0", 1, 3, 24, 1)
    strides = [st for _, st, _, _, _ in plan]
    assert strides.count(2) == 4  # stages 2, 3, 4, 6


def test_fast_forward_matches_flax():
    variables = init_variables(_SPEC, seed=3)
    rng = np.random.default_rng(0)
    # 5 images: exercises the sublane batch padding (5 -> 8) end to end.
    images = rng.integers(0, 256, size=(5, *_SPEC.input_shape), dtype=np.uint8)

    want = np.asarray(
        jax.jit(build_forward(_SPEC, dtype=jnp.bfloat16, fast=False))(
            variables, images
        )
    )

    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    inner = build_fast_forward(_SPEC, dtype=jnp.bfloat16, interpret=True)
    got = np.asarray(
        jax.jit(
            lambda v, im: inner(v, normalize(im, _SPEC.preprocessing)).astype(
                jnp.float32
            )
        )(variables, images)
    )
    assert got.shape == want.shape == (5, 3)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"fast path diverges from flax: {rel:.2e}"
