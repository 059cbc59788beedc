"""Attention ops: fused Pallas flash attention (TPU) + reference softmax path.

The reference system serves a fixed-shape CNN and has no attention anywhere
(SURVEY.md section 5: long-context "absent and inapplicable"); this module
exists because long-context support is first-class in this framework: it is
the single-device building block under ``parallel.ring`` (ring attention /
context parallelism over a device mesh).

Design (TPU-first):

- **Online softmax** (flash attention): the (S, S) score matrix is never
  materialized in HBM.  The Pallas kernel keeps one (block_q, d) query tile
  in VMEM and streams key/value tiles through a fori_loop, carrying the
  running row-max m, normalizer l, and unnormalized accumulator in f32.
- **MXU-shaped blocks**: default 128x128 score tiles, f32 accumulation via
  ``preferred_element_type`` so bf16 inputs still reduce exactly.
- **Partial outputs for ring composition**: ``attend_block`` returns
  (acc, m, l) so callers (ring attention) can combine partial attentions
  over KV shards with the standard log-sum-exp merge; ``flash_attention``
  is the fused single-shot form.
- ``interpret=True`` (passed explicitly by CPU tests, never inferred) runs
  the same kernel through the Pallas interpreter, so tests exercise the
  real kernel logic without a TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30  # large-but-finite: -inf breaks exp(m - m_new) when a row is fully masked


def _causal_mask(q_offset: int, k_offset, block_q: int, block_k: int):
    """(block_q, block_k) bool mask: query global index >= key global index."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + k_offset
    return rows >= cols


def pick_block(seq: int) -> int | None:
    """Largest MXU-friendly flash block (<=256, 8-aligned) dividing ``seq``.

    None means no legal tiling exists for ``seq`` AS IS; callers should go
    through ``flash_attention_padded`` (pad + kv_len masking) rather than
    falling back to the einsum path.  Single source of the kernel's tiling
    rule -- consumed by flash_attention_padded and parallel.ring.

    256 leads: fewer, fatter grid steps and k-iterations measured
    2.2-2.5x faster than 128x128 blocks at every swept S -- fast enough
    to beat even the einsum path at S=1024 (the kernel is
    per-step-overhead-bound at D=64; exp/vit_attn_variants.py, round 4).
    """
    for block in (256, 128, 64, 32, 16, 8):
        if seq % block == 0:
            return block
    return None


def mha_reference(q, k, v, *, causal: bool = False, k_offset: int = 0):
    """Plain softmax attention, (..., S, D) layout.  Ground truth for tests.

    ``k_offset`` is the global position of k[0] relative to q[0] (used when
    the KV block is a remote shard in ring attention).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = _causal_mask(0, k_offset, q.shape[-2], k.shape[-2])
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)


def attend_block(q, k, v, *, causal: bool = False, k_offset: int = 0):
    """Unnormalized attention partials of q against one KV block.

    Returns ``(acc, m, l)`` with acc: (..., S_q, D) f32 unnormalized output,
    m: (..., S_q) f32 row max, l: (..., S_q) f32 row sum of exp(s - m).
    Partials over different KV blocks combine with ``combine_partials``;
    ``acc / l`` recovers the softmax-attention output.  This is the ring
    attention inner step (parallel.ring).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = _causal_mask(0, k_offset, q.shape[-2], k.shape[-2])
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32))
    return acc, m, l


def combine_partials(a, b):
    """Merge two (acc, m, l) partials (log-sum-exp over the KV axis)."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    alpha = jnp.exp(m_a - m)
    beta = jnp.exp(m_b - m)
    return (
        acc_a * alpha[..., None] + acc_b * beta[..., None],
        m,
        l_a * alpha + l_b * beta,
    )


def finalize_partials(partial):
    """(acc, m, l) -> normalized attention output.

    Rows that attended nothing (l == 0, e.g. a flash partial over a fully
    causal-masked shard) are defined as zeros rather than 0/0 NaN, matching
    the fused kernel's empty-softmax convention.
    """
    acc, _, l = partial
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return jnp.where((l == 0.0)[..., None], 0.0, acc / safe_l[..., None])


# --- Pallas fused kernel ---------------------------------------------------


def _flash_body(q_ref, k_ref, v_ref, *, block_k, causal, k_offset, kv_len=None):
    """One (1, block_q, d) query tile vs the local KV, online softmax.

    Returns the running ``(acc, m, l)`` carried state: unnormalized output,
    row max, and normalizer, each f32 with m/l shaped (block_q, 1).

    ``kv_len``: number of VALID local kv rows (ragged sequences padded up
    to a block multiple -- e.g. ViT's 257 tokens padded to 264); columns at
    or beyond it are masked to -inf so pad keys never enter the softmax.
    """
    # Dots run on the INPUT dtype with f32 accumulation
    # (preferred_element_type): for bf16 serving inputs that's the MXU's
    # full bf16 rate -- upcasting operands to f32 ran the dots as multi-pass
    # f32 MXU ops at ~1/4 rate, which made this kernel 46% of ViT-B's
    # device time at ~5% MFU (exp/batch_dip_trace.py --model
    # vit-b16-imagenet, round 4).  Softmax statistics stay f32 throughout;
    # f32 inputs keep exact f32 dots (tests, exact paths).
    q = q_ref[0]                              # (block_q, d), input dtype
    in_dtype = q.dtype
    block_q, d = q.shape
    seq_k = k_ref.shape[1]
    num_k = seq_k // block_k
    scale = 1.0 / math.sqrt(d)
    q_start = pl.program_id(1) * block_q

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                              # (block_q, block_k) f32
        if kv_len is not None:
            cols = (
                jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                + j * block_k
            )
            s = jnp.where(cols < kv_len, s, NEG_INF)
        if causal:
            mask = _causal_mask(q_start, j * block_k + k_offset, block_q, block_k)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p in [0, 1] cast to the input dtype for the PV dot (bf16 MXU
        # rate; standard flash practice), f32 accumulate.
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(in_dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l

    if causal:
        # KV blocks whose first key lies beyond this tile's last query are
        # fully in the causal future: stop the stream at the diagonal block
        # instead of computing-then-masking them (~2x FLOPs/bandwidth saved
        # on average; the diagonal tile itself still masks elementwise).
        hi = (q_start + block_q - k_offset + block_k - 1) // block_k
        hi = jnp.clip(hi, 0, num_k)
    else:
        hi = num_k

    acc = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    return jax.lax.fori_loop(0, hi, body, (acc, m, l))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal, k_offset,
                  kv_len=None):
    """Fused form: normalize in-kernel, write the attention output tile."""
    acc, m, l = _flash_body(
        q_ref, k_ref, v_ref, block_k=block_k, causal=causal, k_offset=k_offset,
        kv_len=kv_len,
    )
    # A row masked across EVERY key (causal with k_offset pushing the whole
    # block into the future) ends with m still at NEG_INF and p=exp(0)=1
    # everywhere, i.e. acc/l = mean(v); define empty-softmax as zeros instead.
    masked = m <= NEG_INF * 0.5
    o_ref[0] = jnp.where(masked, 0.0, acc / jnp.where(masked, 1.0, l)).astype(
        o_ref.dtype
    )


def _flash_kernel_partials(
    q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *, block_k, causal, k_offset,
    kv_len=None,
):
    """Partial form: write raw (acc, m, l) for cross-shard lse merging."""
    acc, m, l = _flash_body(
        q_ref, k_ref, v_ref, block_k=block_k, causal=causal, k_offset=k_offset,
        kv_len=kv_len,
    )
    acc_ref[0] = acc
    m_ref[0] = m  # (block_q, 1): trailing singleton keeps Mosaic tiling legal
    l_ref[0] = l


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    k_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    return_partials: bool = False,
    kv_len: int | None = None,
):
    """Fused flash attention.  q, k, v: (B, H, S, D) -> (B, H, S, D).

    ``kv_len``: valid kv rows when the sequences are PADDED to a block
    multiple (ragged lengths, e.g. ViT's 257 tokens); pad keys are masked
    out of the softmax.  See ``flash_attention_padded`` for the wrapper
    that does the padding/slicing.

    The full local KV for one (batch, head) lives in VMEM while query tiles
    stream over it, so S_local * D must fit VMEM (~16 MB/core) -- e.g.
    S=8192 at D=128 bf16 is 2 MB/tensor.  Longer sequences shard S over the
    mesh and wrap this kernel with parallel.ring.ring_attention, which is
    exactly the regime ring attention exists for.

    With ``return_partials=True`` the kernel skips in-kernel normalization
    and returns ``(acc, m, l)`` in ``attend_block``'s layout (acc f32
    (B,H,S,D); m, l f32 (B,H,S)) so ring attention can lse-merge partial
    attentions over KV shards while keeping O(S*D) memory -- attend_block's
    einsum would materialize the (S_local, S_local) score matrix per shard.

    ``interpret`` is never inferred from the device: serving passes nothing
    and compiles through Mosaic; CPU tests pass ``interpret=True`` to run
    the identical kernel logic in the Pallas interpreter.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({sq}, {sk}) must be multiples of blocks "
            f"({block_q}, {block_k}); pad the sequence"
        )
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)

    # Inside shard_map, outputs must declare which mesh axes they vary over
    # (check_vma); propagate the query's vma so the kernel composes with
    # parallel.ring.  Outside shard_map this is the empty set.
    vma = jax.typeof(qf).vma

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda g, i: (g, i, 0)),
        pl.BlockSpec((1, sk, d), lambda g, i: (g, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda g, i: (g, 0, 0)),
    ]
    grid = (b * h, sq // block_q)

    if return_partials:
        kernel = functools.partial(
            _flash_kernel_partials, block_k=block_k, causal=causal,
            k_offset=k_offset, kv_len=kv_len,
        )
        # (B*H, S, 1) with trailing singleton: Mosaic requires the last two
        # block dims be (8k, 128k)-divisible or equal to the array dims; a
        # plain (1, block_q) row block violates that on TPU.
        row_spec = pl.BlockSpec((1, block_q, 1), lambda g, i: (g, i, 0))
        acc, m, l = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda g, i: (g, i, 0)),
                row_spec,
                row_spec,
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, sq, d), jnp.float32, vma=vma),
                jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32, vma=vma),
                jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32, vma=vma),
            ],
            interpret=interpret,
        )(qf, kf, vf)
        return (
            acc.reshape(b, h, sq, d),
            m.reshape(b, h, sq),
            l.reshape(b, h, sq),
        )

    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, k_offset=k_offset,
        kv_len=kv_len,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype, vma=vma),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)


def flash_attention_padded(q, k, v, *, causal: bool = False,
                           interpret: bool = False):
    """Flash attention for ANY sequence length: pads S up to the nearest
    block multiple, masks the pad keys via ``kv_len``, slices the output.

    Without this, a sequence with no 8-aligned divisor (ViT-B/16 at 256
    squared has 257 tokens -- prime) silently fell back to the einsum
    reference and materialized the (S, S) score matrix in HBM.  Pad-query
    rows are zeros; their outputs are garbage-free (finite) and sliced off.
    """
    sq, sk = q.shape[2], k.shape[2]
    block_q, block_k = pick_block(sq), pick_block(sk)
    if block_q is not None and block_k is not None:
        return flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    # Pad to a multiple of 128, NOT the minimal 8: pick_block(next-8-
    # multiple) would tile the MXU at 8x8 for most ragged lengths (e.g.
    # 257 -> 264 -> block 8), wasting ~15/16 of every pass.  The extra pad
    # rows are masked by kv_len and cost <=127 rows of FLOPs.  Query and
    # KV pad INDEPENDENTLY: cross-attention arrives with sq != sk, and a
    # q-derived pad on k either misaligns or crashes the kernel's
    # divisibility check.
    sqp = -(-sq // 128) * 128
    skp = -(-sk // 128) * 128
    pad_q = ((0, 0), (0, 0), (0, sqp - sq), (0, 0))
    pad_k = ((0, 0), (0, 0), (0, skp - sk), (0, 0))
    out = flash_attention(
        jnp.pad(q, pad_q), jnp.pad(k, pad_k), jnp.pad(v, pad_k),
        causal=causal, block_q=pick_block(sqp), block_k=pick_block(skp),
        interpret=interpret, kv_len=sk if skp != sk else None,
    )
    return out[:, :, :sq, :]


# Sequence length up to which inference routes to the einsum path.  Not a
# perf crossover -- einsum never lost to the kernel in the round-4 sweep
# (6.5x faster at ViT-B's (32,12,256,64), still 1.4x at S=1024, because
# D=64 heads give each flash grid step only ~4 MFLOP of work against
# ~1.7 us of fixed per-step cost) -- but an HBM-comfort bound on the
# (B, H, S, S) f32 scores it materializes: <=1.6 GiB at the largest
# default bucket (128) for ViT-B.  Sequence-only (not batch) so the rule
# stays decidable under the exporter's SYMBOLIC batch dimension and every
# bucket of one artifact routes identically.
EINSUM_MAX_SEQ = 512


def use_einsum_attention(sq: int, sk: int) -> bool:
    """Trace-time routing rule for ``attention_serving`` (pure, testable)."""
    return sq <= EINSUM_MAX_SEQ and sk <= EINSUM_MAX_SEQ


def attention_serving(q, k, v, *, causal: bool = False):
    """Inference MHA with measured shape routing (round 4).

    Short/serving-scale sequences take the einsum path: materializing the
    f32 score matrix in HBM costs far less than the flash kernel's
    per-grid-step overhead (see ``EINSUM_MAX_SEQ``).  Beyond
    the sequence budget -- long-context, ring-attention shards -- the
    fused kernel takes over: that memory wall is what it exists for.  The
    kernel branch resolves per LOWERING platform (the exporter traces one
    module for cpu and tpu; a trace-time backend check would bake the
    wrong mode into one of them), while the einsum branch is
    platform-portable as-is.
    """
    sq, sk = q.shape[2], k.shape[2]
    if use_einsum_attention(sq, sk):
        return mha_reference(q, k, v, causal=causal)
    return jax.lax.platform_dependent(
        q, k, v,
        tpu=functools.partial(
            flash_attention_padded, causal=causal, interpret=False
        ),
        default=functools.partial(mha_reference, causal=causal),
    )


# --- trainable memory-efficient attention ----------------------------------
# The Pallas kernel defines no VJP, so training previously fell back to the
# full einsum reference, materializing the (S, S) score matrix in HBM --
# exactly what flash attention exists to avoid, and the memory wall for
# long-context fine-tuning.  attention_trainable closes the gap with a
# custom_vjp: the primal is the fused kernel (per lowering platform, like
# models.vit), and the backward is the standard FlashAttention recomputation
# -- a lax.scan over KV blocks that rebuilds each score block from q, k and
# the saved logsumexp, so backward memory is O(S * block) instead of O(S^2).


def _finalize_with_lse(partials, dtype):
    """(acc, m, l) -> (normalized out, lse = m + log l), shared epilogue."""
    _, m, l = partials
    out = finalize_partials(partials).astype(dtype)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return out, m + jnp.log(safe_l)


def _forward_with_lse(q, k, v, causal: bool):
    """(out, lse) with lse the softmax log-normalizer per row."""
    # Cross-attention (sq != sk) tiles each side independently.
    block_q = pick_block(q.shape[2])
    block_k = pick_block(k.shape[2])

    def via_flash(q, k, v):
        partials = flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=False, return_partials=True,
        )
        return _finalize_with_lse(partials, q.dtype)

    def via_reference(q, k, v):
        return _finalize_with_lse(attend_block(q, k, v, causal=causal), q.dtype)

    if block_q is None or block_k is None:
        return via_reference(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, tpu=via_flash, default=via_reference
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attention_trainable(q, k, v, causal: bool = False):
    """Differentiable attention, (B, H, S, D), O(S * block) activation memory.

    Forward runs the fused flash kernel in TPU lowerings (einsum reference
    elsewhere); backward recomputes score blocks from (q, k, lse) in a scan
    over KV blocks.  The building block for long-context *training* --
    inference-only callers can keep using flash_attention directly.
    """
    out, _ = _forward_with_lse(q, k, v, causal)
    return out


def _attn_fwd(q, k, v, causal: bool):
    out, lse = _forward_with_lse(q, k, v, causal)
    return out, (q, k, v, out, lse)


def block_grads(q32, k32, v32, lse_q, delta_q, do32_q, scale, mask=None):
    """One (q-block, kv-block) backward pair from the saved logsumexp.

    THE single implementation of the FlashAttention-2 recomputation body --
    shared by the non-causal scan, the 2D-tiled causal backward, and the
    trainable ring's per-shard gradients (parallel.ring), so the score/p/ds
    algebra can never drift between them.  All inputs f32; ``mask`` is an
    optional (sq_blk, sk_blk) bool visibility mask.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q32, k32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_q[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do32_q)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do32_q, v32)
    ds = p * (dp - delta_q[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k32) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q32) * scale
    return dq, dk, dv


def _attn_bwd(causal: bool, res, dout):
    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    block = pick_block(sk) or sk
    nk = sk // block

    do32 = dout.astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    # D_i = sum_d dO_i * O_i, the softmax-backward row correction.
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # (B,H,Sq)

    if causal:
        return _attn_bwd_2d(q32, k, v, do32, lse, delta, scale, block, q.dtype)

    # Bidirectional: every (q, kv) pair contributes, so there is nothing to
    # skip and the single-level KV scan has the least loop overhead.
    def body(dq_acc, j):
        k32 = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=2).astype(
            jnp.float32
        )
        v32 = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=2).astype(
            jnp.float32
        )
        dq_j, dk_j, dv_j = block_grads(q32, k32, v32, lse, delta, do32, scale)
        return dq_acc + dq_j, (dk_j, dv_j)

    dq, (dks, dvs) = jax.lax.scan(
        body, jnp.zeros(q.shape, jnp.float32), jnp.arange(nk)
    )
    # scan stacks per-block grads as (nk, B, H, block, D); reorder the block
    # axis next to its intra-block dim before flattening to (B, H, Sk, D).
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, sk, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, sk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _attn_bwd_2d(q32, k, v, do32, lse, delta, scale, block, q_dtype):
    """Causal backward, 2D-tiled: (q block x kv block) pairs strictly above
    the diagonal are SKIPPED via lax.cond, reclaiming the triangular FLOPs
    the round-1 backward paid (its single-level KV scan had no q tiling, so
    no block was ever fully masked).  Memory stays O(S * block)."""
    b, h, sq, d = q32.shape
    sk = k.shape[2]
    block_q = pick_block(sq) or sq
    nq, nk = sq // block_q, sk // block

    def kv_body(dq_full, j):
        k32 = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=2).astype(
            jnp.float32
        )
        v32 = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=2).astype(
            jnp.float32
        )

        def q_body(carry, i):
            dq_full, dk_acc, dv_acc = carry
            q_i = jax.lax.dynamic_slice_in_dim(q32, i * block_q, block_q, axis=2)
            do_i = jax.lax.dynamic_slice_in_dim(do32, i * block_q, block_q, axis=2)
            lse_i = jax.lax.dynamic_slice_in_dim(lse, i * block_q, block_q, axis=2)
            dl_i = jax.lax.dynamic_slice_in_dim(delta, i * block_q, block_q, axis=2)

            def compute(args):
                dq_full, dk_acc, dv_acc = args
                rows = (
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block), 0)
                    + i * block_q
                )
                cols = (
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block), 1)
                    + j * block
                )
                dq_i, dk_i, dv_i = block_grads(
                    q_i, k32, v32, lse_i, dl_i, do_i, scale, mask=rows >= cols
                )
                dq_full = jax.lax.dynamic_update_slice_in_dim(
                    dq_full,
                    jax.lax.dynamic_slice_in_dim(
                        dq_full, i * block_q, block_q, axis=2
                    )
                    + dq_i,
                    i * block_q,
                    axis=2,
                )
                return dq_full, dk_acc + dk_i, dv_acc + dv_i

            # Skip pairs strictly above the diagonal: the last row of q
            # block i is i*bq + bq - 1; it sees no key >= that + 1.
            visible = (i + 1) * block_q > j * block
            return jax.lax.cond(visible, compute, lambda a: a, carry), None

        (dq_full, dk_j, dv_j), _ = jax.lax.scan(
            q_body,
            (
                dq_full,
                jnp.zeros((b, h, block, d), jnp.float32),
                jnp.zeros((b, h, block, d), jnp.float32),
            ),
            jnp.arange(nq),
        )
        return dq_full, (dk_j, dv_j)

    dq, (dks, dvs) = jax.lax.scan(
        kv_body, jnp.zeros(q32.shape, jnp.float32), jnp.arange(nk)
    )
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, sk, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, sk, d)
    return dq.astype(q_dtype), dk.astype(k.dtype), dv.astype(v.dtype)


attention_trainable.defvjp(_attn_fwd, _attn_bwd)
