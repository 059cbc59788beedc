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
    sub      which sublayer's plane
    page_table [S, max_pages] int32, n_ctx [S] int32 (0 for an idle slot)
    ->       [S, H, rank] float32: softmax(q . latent) @ latent[:rank]

- ``impl="kernel"``: one Pallas kernel (``name="mla_paged_decode"``) copies
  the live pages from HBM into VMEM, found through the page table in scalar
  memory, ``pages_per_chunk`` at a time; scores, the running softmax and the
  weighted sum stay there.  The (slot, chunk) pairs of the whole call are
  one stream: the copies run ``DEPTH`` chunks ahead of the scoring through
  a ring of ``DEPTH + 1`` buffers, across slots and grid steps (which
  buffer comes next and what to fetch next are kept in scalar memory from
  step to step), so while a slot's last chunks are scored the next live
  slot's first are on their way, idle slots are passed over, and only the
  call's first chunk is waited for with nothing to compute.  Every scored
  chunk sends for one chunk -- a stand-in once the stream has run out, and
  the last grid step waits for those ``DEPTH`` -- so that no branch parts
  the copies' starts from the products, and they are started after the
  scores' product: the scalar work of a chunk's sixteen starts then runs
  beside the softmax and not before the product (on a v5e the call is
  bound by that work, not by the bytes: PERF.md section 6, PR 33).  Two
  slots a grid step where the slots are even.  A slot's chunks are scored
  in order, so every row is what a slot alone would give; pages past a
  slot's last live chunk are never read (the stand-ins re-read the last
  slot's first chunk, which is the trash page where that slot is idle).  A
  gather in XLA would write and re-read ``[S, context, rank + rope]`` a
  sublayer.
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
DEPTH = 3           # chunks on their way while one is scored
SLOTS_A_STEP = 2    # slots a grid step (one where the slots are odd)


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


def _kernel(pt_ref, n_ref, q_ref, cache_ref, o_ref, buf, sem, state, *, page: int,
            chunk_pages: int, max_pages: int, rank: int):
    """The chunks of all slots are one stream, copied ``DEPTH`` chunks ahead
    of the scoring into a ring of ``DEPTH + 1`` buffers.  ``n_ref`` holds
    each slot's context and, last, the sublayer.  ``state`` (scalar memory,
    kept from grid step to grid step): [0] the buffer the next chunk to
    score lands in; [1], [2] the slot and chunk to fetch next (slot = slots
    once the stream has run out)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(0)
    slots_a_step, heads = q_ref.shape[:2]
    slots = pl.num_programs(0) * slots_a_step
    sub = n_ref[slots]
    chunk_len = page * chunk_pages
    ring = DEPTH + 1

    def copies(slot, chunk, b):
        first = slot * max_pages + chunk * chunk_pages
        return [pltpu.make_async_copy(
            cache_ref.at[sub, pt_ref[first + j]],
            buf.at[b, pl.ds(j * page, page)], sem.at[b]) for j in range(chunk_pages)]

    def live_from(slot, wanted=True):
        """The first slot at or past ``slot`` that has a context, or ``slots``."""
        return jax.lax.while_loop(
            lambda t: wanted & (t < slots) & (n_ref[jnp.minimum(t, slots - 1)] == 0),
            lambda t: t + 1, slot)

    def take_next():
        """The (slot, chunk) to fetch now, and the stream moves on: to the
        slot's next chunk or, past its last live one, to chunk 0 of the next
        slot that has a context.  Once the stream has run out it is a
        stand-in (the last slot's chunk 0), so that every scored chunk sends
        for one, with no branch between the copies' starts and the products
        they are scheduled among; the call's last grid step waits for the
        ``DEPTH`` stand-ins."""
        slot, chunk = state[1], state[2]
        live = slot < slots
        at = jnp.minimum(slot, slots - 1)
        ends = live & ((chunk + 1) * chunk_len >= n_ref[at])
        state[1] = live_from(jnp.where(ends, slot + 1, slot), ends)
        state[2] = jnp.where(live & ~ends, chunk + 1, 0)
        return at, jnp.where(live, chunk, 0)

    @pl.when(g == 0)
    def _():
        state[0] = 0
        state[1] = live_from(0)
        state[2] = 0

        @pl.loop(0, DEPTH)
        def _(b):
            for c in copies(*take_next(), b):
                c.start()

    def score(k, first):
        """Slot ``k`` of this grid step, its chunk 0 in buffer ``first``;
        returns the buffer of the chunk after its last."""
        s = g * slots_a_step + k
        n = n_ref[s]
        n_chunks = (n + chunk_len - 1) // chunk_len
        q = q_ref[k]

        def body(i, carry):
            m, total, acc = carry
            b = (first + i) % ring
            ahead = take_next()
            for c in copies(s, i, b):
                c.wait()
            kv = buf[b]
            scores = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
            # here, not at the top of the body, where the starts' scalar work
            # delays the product: here it runs beside the softmax.  The buffer
            # is the one the last chunk was scored from.
            for c in copies(*ahead, (b + DEPTH) % ring):
                c.start()
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
        o_ref[k] = acc / jnp.where(total > 0, total, 1.0)
        return (first + n_chunks) % ring

    first = jax.lax.fori_loop(0, slots_a_step, score, state[0])
    state[0] = first

    @pl.when(g == pl.num_programs(0) - 1)
    def _():
        @pl.loop(0, DEPTH)
        def _(d):
            for c in copies(0, 0, (first + d) % ring):
                c.wait()


def mla_paged_decode(pages, n_sub, q, cache, *, max_pages: int, rank: int,
                     interpret: bool):
    """The kernel's call: ``pages`` the flat page table, ``n_sub`` each
    slot's context and, last, the sublayer.  (The chip's trace names a
    jitted function's custom call after the function: ``%mla_paged_decode.N``.)"""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_slots, heads, width = q.shape
    page = cache.shape[2]
    chunk_pages = pages_per_chunk(max_pages)
    slots_a_step = SLOTS_A_STEP if s_slots % SLOTS_A_STEP == 0 else 1
    kernel = functools.partial(_kernel, page=page, chunk_pages=chunk_pages,
                               max_pages=max_pages, rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_slots // slots_a_step,),
        in_specs=[
            pl.BlockSpec((slots_a_step, heads, width), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((slots_a_step, heads, rank), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((DEPTH + 1, chunk_pages * page, width), cache.dtype),
            pltpu.SemaphoreType.DMA((DEPTH + 1,)),
            pltpu.SMEM((3,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s_slots, heads, rank), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(pages, n_sub, q, cache)


@functools.lru_cache(maxsize=None)
def _jitted_call():
    """``mla_paged_decode`` under a ``jit`` of its own: the sublayer is an
    operand, so the sublayers of one program are one traced call and the
    kernel is traced and lowered once a program, not once a sublayer (a
    server's boot pays that even where the compile cache holds the program,
    and a round program holds a step's calls beside a chunk's)."""
    import jax

    return jax.jit(mla_paged_decode, static_argnames=("max_pages", "rank", "interpret"))


def paged_mla_attention(q, cache, sub: int, page_table, n_ctx, *, rank: int,
                        impl: str = "kernel"):
    if impl == "gather":
        return gather_mla_attention(q, cache, sub, page_table, n_ctx, rank)
    if impl not in ("kernel", "interpret"):
        raise ValueError(f"unknown latent-attention implementation {impl!r}")
    import jax.numpy as jnp

    n_sub = jnp.concatenate([n_ctx.astype(jnp.int32), jnp.full((1,), sub, jnp.int32)])
    return _jitted_call()(page_table.reshape(-1).astype(jnp.int32), n_sub,
                          q.astype(cache.dtype), cache, max_pages=page_table.shape[1],
                          rank=rank, interpret=impl == "interpret")
