"""Fused sepconv kernel + Xception fast path, validated on CPU.

The Pallas kernel runs in interpret mode here (tests are hermetic-CPU,
conftest.py); speed is the benchmark's business (perfbench/).  What IS pinned
here: kernel-vs-reference numerics, BN folding against flax.linen.BatchNorm
(including the Keras-parity epsilon), batch-tile picking rules, and the
full fast-forward's logits against the stock flax graph on the same
variables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_deep_learning_tpu.models import build_forward, init_variables
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.ops.fused_sepconv import (
    fold_bn,
    fused_sepconv_block,
    middle_block_weights,
    pick_batch_tile,
    sepconv_block_reference,
)


def _random_block_weights(rng, c):
    dw = jnp.asarray(rng.normal(0, 0.2, (3, 3, 3, c)), jnp.float32)
    pw = jnp.asarray(rng.normal(0, 0.05, (3, c, c)), jnp.bfloat16)
    s = jnp.asarray(rng.uniform(0.8, 1.2, (3, c)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (3, c)), jnp.float32)
    return dw, pw, s, b


@pytest.mark.parametrize(
    "shape",
    [
        (4, 6, 6, 256),
        (2, 5, 7, 128),
        # non-8-multiple batches (serving buckets whose tile Mosaic refused)
        # run via sublane padding and must match on the real rows
        (1, 6, 6, 128),
        (3, 6, 6, 128),
        (6, 6, 6, 128),
    ],
)
def test_kernel_matches_reference(shape):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
    dw, pw, s, b = _random_block_weights(rng, shape[-1])
    want = np.asarray(sepconv_block_reference(x, dw, pw, s, b), np.float32)
    got = np.asarray(
        jax.jit(lambda *a: fused_sepconv_block(*a, interpret=True))(x, dw, pw, s, b),
        np.float32,
    )
    assert got.shape == shape
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"kernel diverges from reference: {rel:.2e}"


def test_fold_bn_matches_flax_batchnorm():
    import flax.linen as nn

    from kubernetes_deep_learning_tpu.models.layers import KERAS_BN_EPS, batch_norm

    rng = np.random.default_rng(1)
    c = 32
    x = jnp.asarray(rng.normal(0, 1, (4, c)), jnp.float32)
    p = {
        "scale": jnp.asarray(rng.uniform(0.8, 1.2, c), jnp.float32),
        "bias": jnp.asarray(rng.normal(0, 0.1, c), jnp.float32),
    }
    s = {
        "mean": jnp.asarray(rng.normal(0, 0.5, c), jnp.float32),
        "var": jnp.asarray(rng.uniform(0.5, 1.5, c), jnp.float32),
    }
    mod = batch_norm(False, None, "bn")
    want = mod.apply({"params": p, "batch_stats": s}, x)
    scale, shift = fold_bn(p, s)  # defaults to the Keras epsilon
    got = x * scale + shift
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # and it is the KERAS epsilon, not flax's 1e-5 default
    assert KERAS_BN_EPS == 1e-3
    bad_scale, _ = fold_bn(p, s, eps=1e-5)
    assert not np.allclose(np.asarray(bad_scale), np.asarray(scale))


def test_pick_batch_tile_rules():
    # divisible batches take the largest tile under budget
    assert pick_batch_tile(256, 19, 19, 728) == 16
    assert pick_batch_tile(8, 19, 19, 728) == 8
    # huge spatial extents fall back to the smallest aligned tile
    assert pick_batch_tile(256, 74, 74, 728) == 8
    # NEVER a non-8-multiple: Mosaic rejects the kernel's (H, W, bt) row
    # collapse for unaligned bt (a batch-1 tile failed on the v5e).  Unaligned
    # batches are padded by the kernel wrappers, which then see a multiple
    # of 8 -- but pick_batch_tile itself must stay safe for any input.
    assert pick_batch_tile(6, 19, 19, 728) == 8
    assert pick_batch_tile(12, 19, 19, 728) == 8
    assert pick_batch_tile(1, 19, 19, 728) == 8


def test_fused_entry_kernel_matches_reference():
    """The fused entry kernel (conv2 + block2, ops.fused_entry) vs its
    plain-jnp reference at a small parameterized geometry, interpret mode:
    pins the halo/mask/stride-selection math, including a final partial
    row tile (h_out=11, rt=4) and the batch-pad path (B=2 -> 8)."""
    from kubernetes_deep_learning_tpu.ops.fused_entry import (
        entry_block_reference,
        fused_entry_block_t,
    )

    rng = np.random.default_rng(3)
    h_in, c_in, c_b, c_out = 23, 8, 16, 32  # h_b=21, h_out=11
    w = {
        "conv2": rng.normal(0, 0.2, (3, 3, c_in, c_b)).astype(np.float32),
        "conv2_s": rng.uniform(0.8, 1.2, c_b).astype(np.float32),
        "conv2_b": rng.normal(0, 0.1, c_b).astype(np.float32),
        "res": rng.normal(0, 0.1, (c_b, c_out)).astype(np.float32),
        "res_s": rng.uniform(0.8, 1.2, c_out).astype(np.float32),
        "res_b": rng.normal(0, 0.1, c_out).astype(np.float32),
        "dw1": rng.normal(0, 0.2, (3, 3, c_b)).astype(np.float32),
        "pw1": rng.normal(0, 0.1, (c_b, c_out)).astype(np.float32),
        "bn1_s": rng.uniform(0.8, 1.2, c_out).astype(np.float32),
        "bn1_b": rng.normal(0, 0.1, c_out).astype(np.float32),
        "dw2": rng.normal(0, 0.2, (3, 3, c_out)).astype(np.float32),
        "pw2": rng.normal(0, 0.1, (c_out, c_out)).astype(np.float32),
        "bn2_s": rng.uniform(0.8, 1.2, c_out).astype(np.float32),
        "bn2_b": rng.normal(0, 0.1, c_out).astype(np.float32),
    }
    w = {k: jnp.asarray(v) for k, v in w.items()}
    for batch in (2, 8):  # 2 exercises the pad-to-8 assert path upstream
        a = jnp.asarray(rng.normal(0, 0.5, (8, h_in, h_in, c_in)), jnp.bfloat16)
        a = a[:batch] if batch < 8 else a
        want = np.asarray(entry_block_reference(a, w), np.float32)
        a_t = jnp.pad(a, ((0, 8 - batch), (0, 0), (0, 0), (0, 0))).transpose(
            1, 2, 0, 3
        )
        got_t = jax.jit(
            lambda xt: fused_entry_block_t(xt, w, rt=4, interpret=True)
        )(a_t)
        got = np.asarray(got_t.transpose(2, 0, 1, 3)[:batch], np.float32)
        assert got.shape == want.shape
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        assert rel < 2e-2, f"entry kernel diverges (batch {batch}): {rel:.2e}"


def test_fast_forward_entry_kernel_matches_flax(fast_spec):
    """The EXPERIMENTAL entry_kernel=True fast path end to end (fused entry
    + block3/4 chains + middle + exit, interpret mode) vs the stock flax
    graph -- kept tested even though serving does not enable it."""
    from kubernetes_deep_learning_tpu.models.xception_fast import build_fast_forward
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    rng = np.random.default_rng(5)
    variables = jax.tree_util.tree_map(np.asarray, init_variables(fast_spec, seed=4))
    images = rng.integers(0, 256, (2, *fast_spec.input_shape), np.uint8)
    ref = jax.jit(build_forward(fast_spec, dtype=jnp.bfloat16, fast=False))
    want = np.asarray(ref(variables, images))

    fast = build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, entry_kernel=True
    )
    x = normalize(jnp.asarray(images), fast_spec.preprocessing)
    got = np.asarray(jax.jit(fast)(variables, x), np.float32)

    # 2e-2: the pallas interpreter's bf16 accumulation rounds slightly
    # differently from the XLA graph; the compiled Mosaic kernels are held
    # to the tighter bound chip_smoke.py states against the f32 graph.
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"entry-kernel fast path diverges from flax: {rel:.2e}"

    # conv1_t variant (VERDICT r3 #5): conv1 computed in (H, W, B, C) via
    # HWNC dimension_numbers must be numerically identical layout-math.
    fast_t = build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, entry_kernel=True,
        conv1_t=True,
    )
    got_t = np.asarray(jax.jit(fast_t)(variables, x), np.float32)
    rel = np.abs(got_t - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"conv1_t fast path diverges from flax: {rel:.2e}"


@pytest.fixture(scope="module")
def fast_spec():
    return register_spec(
        ModelSpec(
            name="fast-xception",
            family="xception",
            input_shape=(96, 96, 3),
            labels=("a", "b", "c", "d"),
            preprocessing="tf",
            head_hidden=(16,),
        )
    )


def test_fast_forward_matches_flax(fast_spec):
    """Full fast path (entry/exit lax ops + fused middle, interpret mode)
    vs the stock flax graph on identical variables with jittered BN stats."""
    from kubernetes_deep_learning_tpu.models.xception_fast import build_fast_forward
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    rng = np.random.default_rng(2)
    variables = jax.tree_util.tree_map(np.asarray, init_variables(fast_spec, seed=3))

    def jitter(tree):
        for k, v in tree.items():
            if isinstance(v, dict):
                jitter(v)
            elif k == "mean":
                tree[k] = rng.normal(0, 0.05, v.shape).astype(np.float32)
            elif k == "var":
                tree[k] = rng.uniform(0.5, 1.5, v.shape).astype(np.float32)

    jitter(variables["batch_stats"])

    images = rng.integers(0, 256, (2, *fast_spec.input_shape), np.uint8)
    ref = jax.jit(build_forward(fast_spec, dtype=jnp.bfloat16, fast=False))
    want = np.asarray(ref(variables, images))

    fast = build_fast_forward(fast_spec, dtype=jnp.bfloat16, interpret=True)
    x = normalize(jnp.asarray(images), fast_spec.preprocessing)
    got = np.asarray(jax.jit(fast)(variables, x), np.float32)

    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 1e-2, f"fast path diverges from flax graph: {rel:.2e}"


def test_chunk_size_rules():
    """Microbatch chunking engages exactly for 8-multiples in [32, 64]
    (measured win zone, exp/chunked_forward.py); everything else
    monolithic.  Non-16-multiples take a trailing 8-chunk."""
    from kubernetes_deep_learning_tpu.models.xception_fast import _chunk_sizes

    assert _chunk_sizes(32) == [16, 16]
    assert _chunk_sizes(40) == [16, 16, 8]
    assert _chunk_sizes(48) == [16, 16, 16]
    assert _chunk_sizes(56) == [16, 16, 16, 8]
    assert _chunk_sizes(64) == [16, 16, 16, 16]
    for n in (1, 8, 16, 24, 36, 96, 128, 256):
        assert _chunk_sizes(n) is None, n


def test_chunked_fast_forward_matches_monolithic(fast_spec, monkeypatch):
    """The chunk wrapper (slice -> forward_one -> concat) must be a pure
    batching identity.  Scaled down (chunk=1 over batch 2) so interpret-mode
    cost stays test-sized; the production chunk geometry (16 over 32-64) is
    run by no cell of the benchmark (ROADMAP D17)."""
    from kubernetes_deep_learning_tpu.models import xception_fast
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    monkeypatch.setattr(xception_fast, "_CHUNK", 1)
    monkeypatch.setattr(xception_fast, "_TAIL", 1)
    monkeypatch.setattr(xception_fast, "_CHUNK_MIN", 2)
    monkeypatch.setattr(xception_fast, "_CHUNK_MAX", 2)

    rng = np.random.default_rng(5)
    variables = init_variables(fast_spec, seed=1)
    images = rng.integers(0, 256, (2, *fast_spec.input_shape), np.uint8)
    x = normalize(jnp.asarray(images), fast_spec.preprocessing)

    mono = xception_fast.build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, chunk=False
    )
    chunked = xception_fast.build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, chunk=True
    )
    want = np.asarray(jax.jit(mono)(variables, x), np.float32)
    got = np.asarray(jax.jit(chunked)(variables, x), np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_middle_block_weights_shapes(fast_spec):
    variables = init_variables(fast_spec, seed=0)
    dw, pw, s, b = middle_block_weights(
        variables["params"], variables["batch_stats"], "block5"
    )
    assert dw.shape == (3, 3, 3, 728) and dw.dtype == jnp.float32
    assert pw.shape == (3, 728, 728) and pw.dtype == jnp.bfloat16
    assert s.shape == (3, 728) and b.shape == (3, 728)


def test_build_forward_fast_flag_dispatch(fast_spec):
    """fast='auto' on the CPU backend must stay on the flax graph (pallas
    TPU kernels cannot lower for CPU outside interpret mode)."""
    fwd = build_forward(fast_spec, dtype=jnp.bfloat16)  # auto
    images = np.zeros((1, *fast_spec.input_shape), np.uint8)
    variables = init_variables(fast_spec, seed=0)
    out = jax.jit(fwd)(variables, images)
    assert out.shape == (1, fast_spec.num_classes)
