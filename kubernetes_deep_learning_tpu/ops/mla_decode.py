"""Latent (MLA) attention of one decode step over a paged latent cache.

What a position leaves in the cache of a latent-attention model is one
vector a sublayer, shared by every head: the normed, scaled compression
``c_kv`` (``rank`` values) and the rotated shared key ``k_r`` (``rope``
values).  The decode step never expands it to per-head keys and values: the
query is carried into the latent space (``q_nope W_uk^T``, done by the
caller), scored against the cached vectors as they lie, and the softmax's
weighted sum of ``c_kv`` goes through ``W_uv`` afterwards (the caller's
again).  Same arithmetic as the expanded form, reassociated.

``paged_mla_attention`` is that middle part, for all slots of a step:

    q        [S, H, width]   queries in the latent space, scale folded in;
                             width is rank + rope padded by the caller to
                             the chip's 128 lanes (zeros in the padding)
    cache    [sublayers, P, page, width]   the whole paged cache
    sub      which sublayer's plane (static)
    page_table [S, max_pages] int32, n_ctx [S] int32 (0 for an idle slot)
    ->       [S, H, rank] float32: softmax(q . latent) @ latent[:rank]

- ``impl="kernel"``: one Pallas kernel (``name="mla_paged_decode"``) walks
  each slot's page list through the page table in scalar memory and copies
  its live pages, ``pages_per_chunk`` at a time and double-buffered, from
  HBM into VMEM; scores, the running softmax and the weighted sum stay
  there.  Pages past ``n_ctx`` are never read.  A gather in XLA would write
  and re-read ``[S, context, rank + rope]`` a sublayer.
- ``impl="interpret"``: the same kernel in the Pallas interpreter (CPU
  tests pass it; nothing infers it from the device).
- ``impl="gather"``: the plain XLA form over the gathered context, which is
  what a backend without Mosaic serves and what the tests hold the kernel
  to.
"""

from __future__ import annotations

import functools

KERNEL_NAME = "mla_paged_decode"
_MASKED = -1e30


def pages_per_chunk(max_pages: int, most: int = 16) -> int:
    """The largest divisor of ``max_pages`` up to ``most``: a slot's page
    list is walked in whole chunks."""
    return max(c for c in range(1, min(most, max_pages) + 1) if max_pages % c == 0)


def gather_mla_attention(q, cache, sub: int, page_table, n_ctx, rank: int):
    import jax
    import jax.numpy as jnp

    s_slots, max_pages = page_table.shape
    page = cache.shape[2]
    ctx = max_pages * page
    kv = cache[sub][page_table].reshape(s_slots, ctx, cache.shape[3])
    if jax.default_backend() == "cpu":   # no bfloat16 batched product there
        q, kv = q.astype(jnp.float32), kv.astype(jnp.float32)
    scores = jnp.einsum("shc,stc->sht", q, kv, preferred_element_type=jnp.float32)
    live = jnp.arange(ctx, dtype=jnp.int32)[None, :] < n_ctx[:, None]
    scores = jnp.where(live[:, None, :], scores, _MASKED)
    w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    w = jnp.where(live[:, None, :], w, 0.0)
    total = w.sum(axis=-1, keepdims=True)
    out = jnp.einsum("sht,stc->shc", w.astype(kv.dtype), kv[..., :rank],
                     preferred_element_type=jnp.float32)
    return out / jnp.where(total > 0, total, 1.0)


def _kernel(pt_ref, n_ref, q_ref, cache_ref, o_ref, buf, sem, *, sub: int, page: int,
            chunk_pages: int, max_pages: int, rank: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    n = n_ref[s]
    chunk_len = page * chunk_pages
    n_chunks = (n + chunk_len - 1) // chunk_len
    heads = q_ref.shape[1]

    def copies(chunk, b):
        first = s * max_pages + chunk * chunk_pages
        return [pltpu.make_async_copy(
            cache_ref.at[sub, pt_ref[first + j]],
            buf.at[b, pl.ds(j * page, page)], sem.at[b]) for j in range(chunk_pages)]

    @pl.when(n_chunks > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    q = q_ref[0]

    def body(i, carry):
        m, total, acc = carry
        b = i % 2

        @pl.when(i + 1 < n_chunks)
        def _():
            for c in copies(i + 1, 1 - b):
                c.start()

        for c in copies(i, b):
            c.wait()
        kv = buf[b]
        scores = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        at = i * chunk_len + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(at < n, scores, _MASKED)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.where(at < n, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = alpha * acc + jnp.dot(p.astype(kv.dtype), kv[:, :rank],
                                    preferred_element_type=jnp.float32)
        return m_new, alpha * total + p.sum(axis=-1, keepdims=True), acc

    m, total, acc = jax.lax.fori_loop(0, n_chunks, body, (
        jnp.full((heads, 1), _MASKED, jnp.float32), jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, rank), jnp.float32)))
    o_ref[0] = acc / jnp.where(total > 0, total, 1.0)


def paged_mla_attention(q, cache, sub: int, page_table, n_ctx, *, rank: int,
                        impl: str = "kernel"):
    if impl == "gather":
        return gather_mla_attention(q, cache, sub, page_table, n_ctx, rank)
    if impl not in ("kernel", "interpret"):
        raise ValueError(f"unknown latent-attention implementation {impl!r}")
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_slots, heads, width = q.shape
    max_pages = page_table.shape[1]
    page = cache.shape[2]
    chunk_pages = pages_per_chunk(max_pages)
    kernel = functools.partial(_kernel, sub=sub, page=page, chunk_pages=chunk_pages,
                               max_pages=max_pages, rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, rank), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_pages * page, width), cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s_slots, heads, rank), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=impl == "interpret",
        name=KERNEL_NAME,
    )(page_table.reshape(-1).astype(jnp.int32), n_ctx.astype(jnp.int32),
      q.astype(cache.dtype), cache)
