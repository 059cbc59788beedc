"""Flash-attention kernel + partial-merge algebra vs reference softmax."""

from __future__ import annotations

import numpy as np
import pytest
import jax.numpy as jnp

from kubernetes_deep_learning_tpu.ops.attention import (
    attend_block,
    combine_partials,
    finalize_partials,
    flash_attention,
    mha_reference,
)


def _rand_qkv(rng, b=2, h=2, s=256, d=64, dtype=np.float32):
    shape = (b, h, s, d)
    return tuple(rng.standard_normal(shape).astype(dtype) for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference_f32(causal):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_bf16_close_to_f32_reference():
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng)
    got = flash_attention(
        *(x.astype(jnp.bfloat16) for x in (q, k, v)), causal=False, interpret=True
    )
    want = mha_reference(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=0.05, rtol=0.05
    )


def test_flash_rejects_ragged_seq():
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, s=100)
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, k, v, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_partial_merge_equals_full(causal):
    """Splitting KV into blocks and lse-merging partials is exact."""
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, s=128)
    half = 64
    p1 = attend_block(q, k[..., :half, :], v[..., :half, :], causal=causal, k_offset=0)
    p2 = attend_block(q, k[..., half:, :], v[..., half:, :], causal=causal, k_offset=half)
    got = finalize_partials(combine_partials(p1, p2))
    want = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_fully_masked_rows_are_zero():
    """causal + k_offset beyond the sequence: every key is in the future of
    every query; empty softmax is defined as zeros, not mean(v)."""
    rng = np.random.default_rng(5)
    q, k, v = _rand_qkv(rng, s=128)
    got = flash_attention(q, k, v, causal=True, k_offset=10_000, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros_like(got))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_partials_match_attend_block(causal):
    """Partial-output kernel returns the same (acc, m, l) algebra as the
    reference einsum path, so ring attention can swap one for the other."""
    rng = np.random.default_rng(6)
    q, k, v = _rand_qkv(rng, s=128)
    ref = attend_block(q, k, v, causal=causal, k_offset=0)
    got = flash_attention(
        q, k, v, causal=causal, block_q=64, block_k=64,
        interpret=True, return_partials=True,
    )
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref[1]), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(finalize_partials(got)),
        np.asarray(finalize_partials(ref)),
        atol=2e-5, rtol=2e-5,
    )


def test_flash_partials_merge_across_kv_shards():
    """lse-merging two flash partials over split KV equals full attention --
    the exact composition ring attention performs."""
    rng = np.random.default_rng(7)
    q, k, v = _rand_qkv(rng, s=128)
    half = 64
    p1 = flash_attention(
        q, k[..., :half, :], v[..., :half, :], causal=True,
        block_q=64, block_k=64, interpret=True, return_partials=True,
    )
    # Remote "past" shard in ring order: fully visible, no mask needed.
    p2 = flash_attention(
        q, k[..., half:, :], v[..., half:, :], causal=True, k_offset=half,
        block_q=64, block_k=64, interpret=True, return_partials=True,
    )
    got = finalize_partials(combine_partials(p1, p2))
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_causal_negative_offset_matches_reference():
    """KV shard from the past (ring attention): every row partially visible,
    so flash and plain softmax agree everywhere."""
    rng = np.random.default_rng(8)
    q, k, v = _rand_qkv(rng, s=128)
    got = flash_attention(q, k, v, causal=True, k_offset=-64, interpret=True)
    want = mha_reference(q, k, v, causal=True, k_offset=-64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_causal_positive_offset_bounded_stream():
    """KV shard shifted into the future: visible rows must stay exact under
    the diagonal-bounded KV stream; fully-masked rows are defined as zero."""
    rng = np.random.default_rng(9)
    q, k, v = _rand_qkv(rng, s=128)
    got = np.asarray(
        flash_attention(q, k, v, causal=True, k_offset=64, interpret=True)
    )
    want = np.asarray(mha_reference(q, k, v, causal=True, k_offset=64))
    # Rows 0..63 see no keys (key j sits at global position j+64): zeros.
    np.testing.assert_array_equal(got[..., :64, :], np.zeros_like(got[..., :64, :]))
    np.testing.assert_allclose(got[..., 64:, :], want[..., 64:, :], atol=2e-5, rtol=2e-5)


def test_finalize_zero_l_rows_are_zero_not_nan():
    """A flash partial over a fully-masked shard carries l=0; finalizing it
    directly must yield zeros (the empty-softmax convention), not 0/0."""
    rng = np.random.default_rng(10)
    q, k, v = _rand_qkv(rng, s=128)
    p = flash_attention(
        q, k, v, causal=True, k_offset=10_000, interpret=True, return_partials=True
    )
    out = np.asarray(finalize_partials(p))
    np.testing.assert_array_equal(out, np.zeros_like(out))


def test_fully_masked_block_is_neutral_in_merge():
    """A KV block entirely in the causal future must not perturb the merge."""
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, s=64)
    real = attend_block(q, k, v, causal=True, k_offset=0)
    # Block whose every key is in the future of every query.
    future = attend_block(q, k, v, causal=True, k_offset=10_000)
    got = finalize_partials(combine_partials(real, future))
    want = finalize_partials(real)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("seq", [257, 13, 100])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_padded_ragged_seq(seq, causal):
    """Ragged sequence lengths (no 8-aligned divisor, e.g. ViT's prime 257
    tokens) run the flash kernel via pad + kv_len masking and must match
    the einsum reference exactly on the real rows."""
    from kubernetes_deep_learning_tpu.ops.attention import (
        flash_attention_padded,
        mha_reference,
    )

    rng = np.random.default_rng(seq)
    shape = (2, 3, seq, 16)
    q = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    got = np.asarray(
        flash_attention_padded(q, k, v, causal=causal, interpret=True)
    )
    want = np.asarray(mha_reference(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_attention_serving_routing_and_equivalence():
    """Shape routing (round 4): einsum while S <= EINSUM_MAX_SEQ, flash
    past it; the einsum route must be exactly mha_reference."""
    import numpy as np

    from kubernetes_deep_learning_tpu.ops.attention import (
        EINSUM_MAX_SEQ,
        attention_serving,
        mha_reference,
        use_einsum_attention,
    )

    assert use_einsum_attention(256, 256)
    assert use_einsum_attention(EINSUM_MAX_SEQ, EINSUM_MAX_SEQ)
    assert not use_einsum_attention(EINSUM_MAX_SEQ + 8, EINSUM_MAX_SEQ)
    assert not use_einsum_attention(1024, 1024)

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 16, 8)), jnp.float32)
        for _ in range(3)
    )
    got = np.asarray(attention_serving(q, k, v))
    want = np.asarray(mha_reference(q, k, v))
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def test_flash_attention_bf16_dots_match_reference():
    """The bf16 in-kernel dot path (the dtype production serving runs --
    f32 softmax statistics, bf16 MXU operands, round 4) must stay at
    bf16-noise distance from the f32 reference on the same data."""
    import numpy as np

    from kubernetes_deep_learning_tpu.ops.attention import (
        flash_attention,
        mha_reference,
    )

    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 3, 256, 64)), jnp.float32)
        for _ in range(3)
    )
    want = np.asarray(mha_reference(q, k, v), np.float32)
    got = np.asarray(
        flash_attention(
            q.astype(jnp.bfloat16),
            k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16),
            block_q=128,
            block_k=128,
            interpret=True,
        ),
        np.float32,
    )
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 2e-2, f"bf16 flash dots diverge from f32 reference: {rel:.2e}"


def test_flash_attention_padded_cross_attention_ragged():
    """sq != sk must pad each side independently (a q-derived pad on k
    either misaligns or crashes the kernel's divisibility check)."""
    import numpy as np

    from kubernetes_deep_learning_tpu.ops.attention import (
        flash_attention_padded,
        mha_reference,
    )

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 2, 250, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 520, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 520, 16)), jnp.float32)
    got = np.asarray(flash_attention_padded(q, k, v, interpret=True))
    want = np.asarray(mha_reference(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    # Tileable-but-unequal lengths take the unpadded fast exit.
    q2 = jnp.asarray(rng.standard_normal((1, 2, 512, 16)), jnp.float32)
    got2 = np.asarray(flash_attention_padded(q2, k[:, :, :640], v[:, :, :640], interpret=True))
    want2 = np.asarray(mha_reference(q2, k[:, :, :640], v[:, :, :640]))
    np.testing.assert_allclose(got2, want2, rtol=2e-4, atol=2e-4)
