"""Ring attention: context/sequence parallelism over the device mesh.

The reference's workload is fixed-shape image classification with no
long-context mechanism anywhere (SURVEY.md section 5); this module is the
framework's first-class long-context component.  Sequences longer than one
chip's HBM/VMEM budget are sharded along the sequence axis over the mesh,
and attention runs as a **ring**: each device computes partial attention of
its local queries against the KV shard it currently holds, while
``lax.ppermute`` rotates KV shards around the ring over ICI -- the permute
for step t+1 overlaps the compute for step t, so with enough local work the
collective is free (the blockwise/ring-attention schedule of Liu et al.).

Partial attentions over KV shards merge with the log-sum-exp rule
(ops.attention.combine_partials), which is exact -- ring attention returns
bitwise-close results to full attention, it is not an approximation.

Layout convention: (B, H, S, D) with S sharded over the mesh's ``data``
axis (context parallelism reuses the batch axis: a long-sequence request is
one "batch" spread over chips).  Composes with tensor parallelism by
sharding H over ``model`` in the caller's sharding annotations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubernetes_deep_learning_tpu.ops.attention import (
    NEG_INF,
    attend_block,
    combine_partials,
    finalize_partials,
    flash_attention,
    pick_block as _flash_block,
)
from kubernetes_deep_learning_tpu.parallel.mesh import DATA_AXIS


# flash_attention keeps the whole local K and V resident in VMEM (~16 MB/core
# shared with the q tile, accumulator, and double-buffering); beyond roughly
# half of it for KV, Mosaic fails to allocate.  Auto mode falls back to the
# einsum path above this, so pre-existing large-shard calls keep working.
_FLASH_KV_VMEM_BUDGET = 8 * 2**20


@functools.lru_cache(maxsize=None)
def build_ring_attention(
    mesh: Mesh,
    *,
    causal: bool = False,
    axis_name: str = DATA_AXIS,
    use_flash: bool | None = None,
    interpret: bool = False,
):
    """Build the jitted ring-attention fn for a mesh (compile-once factory).

    Cached per (mesh, causal, axis_name, use_flash, interpret) so repeated
    calls reuse one jit cache (same convention as parallel.dataparallel.
    build_sharded_forward).

    ``use_flash`` selects the per-shard attend: the fused Pallas kernel in
    partial-output mode (O(S_local * D) memory -- required for long
    contexts) vs the reference einsum path (materializes the
    (S_local, S_local) score matrix; fine for short shards, used as the
    fallback when S_local does not tile).  None = auto by shape.

    ``interpret`` runs the flash attend in the Pallas interpreter (CPU
    tests pass True); it is never inferred from the mesh's devices.
    """
    n = mesh.shape[axis_name]
    seq_spec = P(None, None, axis_name, None)
    inner = jax.shard_map(
        functools.partial(
            _ring_shard, axis_name=axis_name, n=n, causal=causal,
            use_flash=use_flash, interpret=interpret,
        ),
        mesh=mesh,
        in_specs=(seq_spec,) * 3,
        out_specs=seq_spec,
        # The pallas interpreter loses vma tracking on its internal
        # dynamic_slice when a pallas_call sits under shard_map; jax itself
        # prescribes check_vma=False as the workaround.  The compiled
        # (non-interpret) path keeps the trace-time vma validation; in
        # interpret mode sharding correctness is still covered by
        # test_ring_output_keeps_sequence_sharding and the vs-reference
        # exactness tests.
        check_vma=not interpret,
    )
    return jax.jit(inner)


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    causal: bool = False,
    axis_name: str = DATA_AXIS,
    use_flash: bool | None = None,
    interpret: bool = False,
):
    """Exact attention with S sharded over ``axis_name``.  (B,H,S,D) in/out.

    S must divide evenly by the axis size.  Inputs may be host arrays; they
    are placed with the sequence sharding, and the output keeps it.
    """
    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(f"sequence {q.shape[2]} not divisible by ring size {n}")
    seq_sharding = NamedSharding(mesh, P(None, None, axis_name, None))
    q, k, v = (jax.device_put(x, seq_sharding) for x in (q, k, v))
    return build_ring_attention(
        mesh, causal=causal, axis_name=axis_name, use_flash=use_flash,
        interpret=interpret,
    )(q, k, v)


def _ring_shard(
    q_blk, k_blk, v_blk, *, axis_name: str, n: int, causal: bool,
    use_flash: bool | None, interpret: bool = False,
):
    """Per-device body: local q vs rotating KV shards, merged partials.

    One schedule implementation only: this is the with-lse variant with the
    lse dropped, so the inference and training (trainable-ring) paths can
    never desynchronize.
    """
    out, _ = _ring_shard_with_lse(
        q_blk, k_blk, v_blk, axis_name=axis_name, n=n, causal=causal,
        use_flash=use_flash, interpret=interpret,
    )
    return out


# --- trainable ring attention ----------------------------------------------
# Round 1 deferred gradients through the ring: the fused flash attend has no
# VJP, so context-parallel TRAINING forced the einsum attend, materializing
# (S_local, S_local) scores (ROADMAP r1).  The custom_vjp below closes it:
#
# - forward: the same flash ring (partials + log-sum-exp merge), saving only
#   out and the per-row lse -- O(S_local * D) residuals;
# - backward: a SECOND ring.  Each device recomputes score blocks of
#   (q_local x kv_src) from q, k and the saved GLOBAL lse (exactly the
#   FlashAttention-2 recomputation, so no (S, S) tensor ever exists), adds
#   the shard's (dk, dv) into an accumulator that rotates WITH the shard --
#   after n hops every dkv lands back on its owner -- and dq accumulates
#   locally.  Causal skipping mirrors the forward (a future shard's grads
#   are identically zero, so the cond skips the whole pair).


def _pair_grads(q32, k_j, v_j, lse, delta, do32, *, causal: bool, scale: float):
    """Gradients of one (q_local, kv_shard) pair given the global lse.

    Scans over KV blocks within the shard so peak memory is
    O(S_local * block), not O(S_local^2).  causal=True means this is the
    DIAGONAL pair (same shard: lower-triangular mask at offset 0).
    """
    from kubernetes_deep_learning_tpu.ops.attention import block_grads

    sk = k_j.shape[2]
    block = _flash_block(sk) or sk
    nk = sk // block
    sq = q32.shape[2]

    def body(dq_acc, j):
        k_b = jax.lax.dynamic_slice_in_dim(k_j, j * block, block, axis=2).astype(
            jnp.float32
        )
        v_b = jax.lax.dynamic_slice_in_dim(v_j, j * block, block, axis=2).astype(
            jnp.float32
        )
        mask = None
        if causal:
            # j * block is traced (scan counter); the iota mask handles it.
            rows = jax.lax.broadcasted_iota(jnp.int32, (sq, block), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (sq, block), 1) + j * block
            mask = rows >= cols
        dq_b, dk_b, dv_b = block_grads(
            q32, k_b, v_b, lse, delta, do32, scale, mask=mask
        )
        return dq_acc + dq_b, (dk_b, dv_b)

    # zeros_like, not zeros(shape): under shard_map's check_vma the carry
    # must vary over the same mesh axes as the dq the body adds to it (a
    # bare zeros is unvarying and the scan's carry types would not match).
    dq, (dks, dvs) = jax.lax.scan(body, jnp.zeros_like(q32), jnp.arange(nk))
    b, h = q32.shape[:2]
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, sk, -1)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, sk, -1)
    return dq, dk, dv


def _ring_shard_with_lse(
    q_blk, k_blk, v_blk, *, axis_name, n, causal, use_flash, interpret=False
):
    """The ring schedule, returning (out, lse).

    The single implementation of the rotation/skip schedule: _ring_shard
    (inference) drops the lse; build_ring_attention_trainable's forward
    saves it for the backward ring.
    """
    s_local = q_blk.shape[2]
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    block = _flash_block(s_local)
    kv_bytes = 2 * s_local * k_blk.shape[-1] * jnp.dtype(k_blk.dtype).itemsize
    if use_flash is None:
        use_flash = block is not None and kv_bytes <= _FLASH_KV_VMEM_BUDGET
    elif use_flash and block is None:
        raise ValueError(
            f"use_flash=True but local sequence {s_local} has no MXU tiling"
        )

    def attend(kv_pair, *, causal, k_offset):
        if use_flash:
            return flash_attention(
                q_blk, kv_pair[0], kv_pair[1], causal=causal, k_offset=k_offset,
                block_q=block, block_k=block, return_partials=True,
                interpret=interpret,
            )
        return attend_block(
            q_blk, kv_pair[0], kv_pair[1], causal=causal, k_offset=k_offset
        )

    partial_out = None
    kv = (k_blk, v_blk)
    for step in range(n):
        kv_next = jax.lax.ppermute(kv, axis_name, perm) if step < n - 1 else None
        if not causal:
            p = attend(kv, causal=False, k_offset=0)
        elif step == 0:
            p = attend(kv, causal=True, k_offset=0)
        else:

            def compute(kv_pair):
                return attend(kv_pair, causal=False, k_offset=0)

            def skip(kv_pair):
                zero = jnp.sum(
                    kv_pair[0][..., :1, :1].astype(jnp.float32) * 0.0, axis=(-2, -1)
                )
                acc = zero[..., None, None] + jnp.zeros(
                    (*q_blk.shape[:3], v_blk.shape[-1]), jnp.float32
                )
                m = zero[..., None] + jnp.full(q_blk.shape[:3], NEG_INF, jnp.float32)
                l = zero[..., None] + jnp.zeros(q_blk.shape[:3], jnp.float32)
                return acc, m, l

            p = jax.lax.cond(rank >= step, compute, skip, kv)
        partial_out = p if partial_out is None else combine_partials(partial_out, p)
        if kv_next is not None:
            kv = kv_next

    # Shared epilogue with attention_trainable: the saved lse must follow
    # the exact convention the attention backward assumes (incl. the l==0
    # fully-masked-row guard).
    from kubernetes_deep_learning_tpu.ops.attention import _finalize_with_lse

    return _finalize_with_lse(partial_out, q_blk.dtype)


def _ring_bwd_shard(
    q_blk, k_blk, v_blk, out, lse, dout, *, axis_name, n, causal
):
    """Backward ring: dq accumulates locally; (dk, dv) rotate home."""
    import math as _math  # local: keep the module surface jax-only

    scale = 1.0 / _math.sqrt(q_blk.shape[-1])
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    do32 = dout.astype(jnp.float32)
    q32 = q_blk.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)

    dq = jnp.zeros(q_blk.shape, jnp.float32)
    kv = (k_blk, v_blk)
    dkv = (
        jnp.zeros(k_blk.shape, jnp.float32),
        jnp.zeros(v_blk.shape, jnp.float32),
    )
    for step in range(n):
        # At step t this device holds shard src = (rank - t) % n and ITS
        # gradient accumulator.  The kv rotation launches BEFORE the
        # compute (same overlap trick as the forward) and skips the useless
        # final hop; dkv must rotate AFTER the compute (this step's grads
        # go into it first) and does need the final hop -- n total
        # rotations land each accumulator back on its shard's owner.
        kv_next = jax.lax.ppermute(kv, axis_name, perm) if step < n - 1 else None

        def compute(args):
            kv_pair, dkv_pair, dq_in = args
            dq_p, dk_p, dv_p = _pair_grads(
                q32, kv_pair[0], kv_pair[1], lse, delta, do32,
                causal=(causal and step == 0), scale=scale,
            )
            return (dkv_pair[0] + dk_p, dkv_pair[1] + dv_p), dq_in + dq_p

        def skip(args):
            _, dkv_pair, dq_in = args
            return dkv_pair, dq_in

        if not causal or step == 0:
            dkv, dq = compute((kv, dkv, dq))
        else:
            dkv, dq = jax.lax.cond(rank >= step, compute, skip, (kv, dkv, dq))
        dkv = jax.lax.ppermute(dkv, axis_name, perm)
        if kv_next is not None:
            kv = kv_next

    return (
        dq.astype(q_blk.dtype),
        dkv[0].astype(k_blk.dtype),
        dkv[1].astype(v_blk.dtype),
    )


@functools.lru_cache(maxsize=None)
def build_ring_attention_trainable(
    mesh: Mesh,
    *,
    causal: bool = False,
    axis_name: str = DATA_AXIS,
    use_flash: bool | None = None,
    interpret: bool = False,
):
    """Differentiable ring attention over ``mesh`` (compile-once factory).

    Same exactness/layout contract as build_ring_attention; gradients flow
    with O(S_local * block) activation memory via the backward ring (module
    comment above).  Closes ROADMAP r1's "ring attention with flash attend
    under gradients".
    """
    n = mesh.shape[axis_name]
    seq_spec = P(None, None, axis_name, None)
    check = not interpret  # same interpreter vma caveat as build_ring_attention

    fwd_inner = jax.shard_map(
        functools.partial(
            _ring_shard_with_lse, axis_name=axis_name, n=n, causal=causal,
            use_flash=use_flash, interpret=interpret,
        ),
        mesh=mesh,
        in_specs=(seq_spec,) * 3,
        out_specs=(seq_spec, P(None, None, axis_name)),
        check_vma=check,
    )
    bwd_inner = jax.shard_map(
        functools.partial(_ring_bwd_shard, axis_name=axis_name, n=n, causal=causal),
        mesh=mesh,
        in_specs=(seq_spec,) * 4 + (P(None, None, axis_name), seq_spec),
        out_specs=(seq_spec,) * 3,
        check_vma=check,
    )

    @jax.custom_vjp
    def ring_trainable(q, k, v):
        out, _ = fwd_inner(q, k, v)
        return out

    def fwd(q, k, v):
        out, lse = fwd_inner(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        return bwd_inner(q, k, v, out, lse, dout)

    ring_trainable.defvjp(fwd, bwd)
    return jax.jit(ring_trainable)
