"""Ring attention over the 8-device CPU mesh vs single-device reference.

Exactness is the point: ring attention is a communication schedule, not an
approximation, so results must match full attention to float tolerance
even though KV shards arrive via 7 ppermute hops.
"""

from __future__ import annotations

import numpy as np
import pytest

from kubernetes_deep_learning_tpu.ops.attention import mha_reference
from kubernetes_deep_learning_tpu.parallel.mesh import make_mesh
from kubernetes_deep_learning_tpu.parallel.ring import ring_attention


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8, model_parallel=1)


def _rand_qkv(rng, b=1, h=2, s=128, d=32):
    shape = (b, h, s, d)
    return tuple(rng.standard_normal(shape).astype(np.float32) for _ in range(3))


@pytest.mark.parametrize("use_flash", [False, True, None])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(mesh8, causal, use_flash):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng)
    # The einsum attend needs no interpreter, so it runs as the compiled TPU
    # path does: with shard_map's check_vma on.
    got = ring_attention(
        q, k, v, mesh8, causal=causal, use_flash=use_flash,
        interpret=use_flash is not False,
    )
    want = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)


def test_ring_falls_back_when_shard_has_no_tiling():
    """s_local = 9 has no MXU block size: auto mode must use the einsum
    path instead of failing, and explicit use_flash=True must raise."""
    mesh2 = make_mesh(2, model_parallel=1)
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, s=18)
    got = ring_attention(q, k, v, mesh2, causal=True, interpret=True)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)
    with pytest.raises(ValueError, match="no MXU tiling"):
        ring_attention(q, k, v, mesh2, use_flash=True, interpret=True)


def test_ring_output_keeps_sequence_sharding(mesh8):
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng)
    out = ring_attention(q, k, v, mesh8, interpret=True)
    # S stays sharded over the data axis: 8 shards, one per device.
    assert len(out.sharding.device_set) == 8
    spec = out.sharding.spec
    assert spec[2] == "data"


def test_ring_rejects_indivisible_sequence(mesh8):
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, s=100)
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, k, v, mesh8)


def test_ring_on_subset_mesh():
    mesh2 = make_mesh(2, model_parallel=1)
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, s=64)
    got = ring_attention(q, k, v, mesh2, causal=True, interpret=True)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize(
    "attend",
    [
        # the flash attend in the Pallas interpreter (check_vma off with it)
        {"interpret": True},
        # the einsum attend with shard_map's check_vma ON: the trace-time
        # validation the compiled TPU path runs under, which a bare-zeros
        # scan carry in the backward ring failed on the four-chip host
        {"use_flash": False},
    ],
    ids=["flash-interpret", "einsum-check-vma"],
)
def test_ring_trainable_matches_autodiff_reference(mesh8, attend):
    """Gradients through the trainable ring == autodiff of the full einsum
    reference, for both causal and bidirectional attention (the backward
    ring: dq local, dk/dv rotated home; ROADMAP r1 closed)."""
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.ops.attention import mha_reference
    from kubernetes_deep_learning_tpu.parallel.ring import (
        build_ring_attention_trainable,
    )

    rng = np.random.default_rng(11)
    b, h, s, d = 1, 2, 64, 16
    q = jnp.asarray(rng.normal(0, 1, (b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, h, s, d)), jnp.float32)

    for causal in (False, True):
        ring_fn = build_ring_attention_trainable(mesh8, causal=causal, **attend)

        def loss_ring(q, k, v):
            return (ring_fn(q, k, v) ** 2).sum()

        def loss_ref(q, k, v):
            return (mha_reference(q, k, v, causal=causal) ** 2).sum()

        out_ring = ring_fn(q, k, v)
        out_ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out_ring), np.asarray(out_ref), rtol=2e-3, atol=2e-3
        )
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, bb, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), rtol=5e-3, atol=5e-3,
                err_msg=f"d{name} mismatch (causal={causal})",
            )
