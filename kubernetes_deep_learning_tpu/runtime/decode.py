"""Autoregressive decode: paged KV-cache + continuous batching.

The generative lane's device half.  Two design commitments, both taken
from the systems that defined this regime:

- **Continuous batching** (Orca, OSDI '22): requests join and leave the
  device batch at *token* boundaries.  The batch program runs at one
  fixed shape (``max_slots``); a per-step scheduler slot-fills freed
  decode slots from the admission queue instead of waiting for the whole
  batch to drain, so a short generation never rides shotgun on a long
  one's tail.  Membership changes are an active-mask flip plus a prefill
  -- never a recompile.

- **Paged KV-cache** (vLLM, SOSP '23): the cache is a pool of fixed-size
  pages; each slot owns a page list (the page table), allocated at
  admission and returned at retirement.  No per-request max-context
  reservation, no copy-on-grow -- fragmentation is bounded by one page
  per sequence.  Page 0 is the trash page: inactive slots and prompt
  padding write there, so the batched scatter needs no branch.

Buffer donation carries over from the image engine (``KDLT_DONATE``
semantics, runtime.engine.donation_enabled): the cache argument is
donated into both the prefill and the step program, so each step writes
K/V in place instead of materializing a second full cache.  kdlt-lint's
donation-safety pass is the guardrail -- the cache is rebound from the
program's return in the same statement, every time.

Bit-exactness across batch composition is a load-bearing property (the
``--decode-ab`` gate asserts it): one slot's computation reads only its
own page list, its own length, and its own last token; masked (garbage)
context positions get exactly-zero softmax weight; and the SAME compiled
step program serves every batch composition, solo included.  So the
token stream of a request decoded in a shifting continuous batch is
bit-identical to the same request decoded alone.

The model is a *decoder* the engine is handed (``DecodeEngine(decoder=)``):
its vocabulary, its cache layout (``cache_spec``) and two pure functions,
``prefill`` and ``decode_step``, that return logits and a few counts
(``N_COUNTS``).  ``ToyDecoder`` here implements that small interface: a
deliberately tiny byte-level causal transformer with per-head K/V pages
(weights derived deterministically from the model name) -- the default
when no artifact is named, so the lane's shape- and schedule-level
contracts are testable without a checkpoint.  The others are loaded from an
artifact directory by the ``family`` its index file states
(``load_decoder``: the module of ``models/`` of that name), and their pages
hold latents.  The greedy choice, the top logits a request may ask for and
the packing of everything a step returns into ONE device array (so the
step's single host sync carries it all) are the engine's, once, for every
decoder.

**Chunked prefill** (Sarathi-Serve, OSDI '24).  ``prefill(params, cache,
tokens, start, length, page_ids)`` computes one *chunk* of a prompt: rows at
positions ``start .. start + T``, true below ``length``; it writes their
K/V (or latents) and attends over ``[0, start + T)`` -- the pages already
written and the chunk itself.  A prompt's first chunk is handed the Python
int ``start = 0`` and attends to itself alone: a prompt that fits one
program is exactly that one chunk.  A prompt longer than ``PREFILL_CHUNK``
is full chunks of that many rows and one last chunk at the smallest rung of
the ladder that holds the remainder, and the scheduler dispatches at most
one chunk between two decode steps: a long prompt holds the live slots for
one chunk's time, not for its whole length.  ``prompt_buckets`` is the
ladder a chunk pads to and the longest prompt admitted; rungs above the
chunk size admit long prompts and compile nothing.

The host is not in the step's period.  The token a slot consumes next stays
on the device (a prefill and a step each leave their greedy choice in
``DecodeEngine._next_tokens``), and lengths, pages and who is live are known
without the tokens, so the scheduler's loop dispatches step N + 1 -- and any
prefill -- before it reads step N (``STEPS_AHEAD``).  The one sync a step is
still there; it runs beside the next step instead of before it.
"""

from __future__ import annotations

import math
import os
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass, field
from queue import Empty, Queue

import numpy as np

from kubernetes_deep_learning_tpu.runtime.batcher import QueueFull
from kubernetes_deep_learning_tpu.runtime.engine import donation_enabled
from kubernetes_deep_learning_tpu.serving import protocol
from kubernetes_deep_learning_tpu.serving.admission.deadline import Deadline
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from kubernetes_deep_learning_tpu.utils import trace as trace_lib

# The toy's byte-level vocabulary: 256 raw bytes + BOS + EOS.  Text prompts
# are encoded here; a request may also carry ``token_ids``, which any
# decoder sees as they are (checked against its vocabulary).
BOS_TOKEN = 256
EOS_TOKEN = 257
VOCAB_SIZE = 258

# Decode-lane knobs.  Slots is the fixed device batch width (one compiled
# step program); page size and max pages bound one sequence's context at
# page_size * max_pages_per_seq tokens.
SLOTS_ENV = "KDLT_DECODE_SLOTS"
PAGE_SIZE_ENV = "KDLT_DECODE_PAGE_SIZE"
MAX_PAGES_ENV = "KDLT_DECODE_MAX_PAGES"
QUEUE_CAP_ENV = "KDLT_DECODE_QUEUE_CAP"
PROMPT_BUCKETS_ENV = "KDLT_DECODE_PROMPT_BUCKETS"

DEFAULT_SLOTS = 4
DEFAULT_PAGE_SIZE = 16
DEFAULT_MAX_PAGES = 8
DEFAULT_QUEUE_CAP = 64

# The DEFAULT prefill compile ladder (prompt positions INCLUDING the toy's
# BOS token, like the image engine's batch buckets): each bucket is one
# compiled program, prompts pad up to the next rung.  A deployment states
# its own in $KDLT_DECODE_PROMPT_BUCKETS ("64,128,256"); kdlt-warm walks
# the same ladder so scaled pods never pay a prefill compile on their
# first generation.
PROMPT_BUCKETS = (16, 32, 64)

# What a step returns beside the tokens: the largest logits of every slot
# (a request asks for up to this many, serving.protocol caps it there).
TOP_LOGITS = protocol.GENERATE_TOP_LOGITS_CAP
# Counts a decoder returns with its logits (zeros from one without experts),
# over the call's true rows and summed over its expert layers: held / absent /
# zero-compute assignments, held experts touched, (row, held expert) products
# computed (padding and masked rows included), rows a shared expert met.
N_COUNTS = 6
# Rows of one prefill program at the most: a longer prompt is prefilled in
# chunks of this many, one between two decode steps.  Measured on a v5e with
# the second decoder at its published widths (``exp/kimi_lane.py``; PERF.md
# section 6, PR 34), an 8,192-token prompt chunk by chunk: chunks of 512 rows
# take 15-47 ms each (16.1k prompt tokens/s), of 1,024 rows 41-125 ms
# (12.5k), of 2,048 rows 111-258 ms (11.3k), beside a decode step of 14.8 ms.
# A smaller chunk prefills faster alone (the chunk's own scores grow with
# its square) but with one chunk between two steps a prompt of n tokens
# costs n / chunk rounds of a chunk and a step: 1,024 rows is the least
# device time a request at that cell's lengths (3,460-token prompts, 192
# tokens out), and holds the live slots for a tenth of a second at the most.
# Where prompts are chunked it has to be a whole number of pages.
PREFILL_CHUNK = 1024
# Steps the scheduler keeps dispatched: the one it reads next and the one
# the device goes on to meanwhile.
STEPS_AHEAD = 2


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw.strip() else default
    except ValueError:
        return default


def env_prompt_buckets() -> tuple[int, ...] | None:
    """$KDLT_DECODE_PROMPT_BUCKETS as a ladder, or None where unset or
    malformed (the default ladder then stands)."""
    raw = os.environ.get(PROMPT_BUCKETS_ENV, "").strip()
    try:
        buckets = tuple(sorted({int(b) for b in raw.split(",") if b.strip()}))
    except ValueError:
        return None
    return buckets if buckets and buckets[0] > 0 else None


def encode_prompt(prompt: str) -> list[int]:
    """Text -> [BOS, *bytes].  Byte-level: any unicode string encodes."""
    return [BOS_TOKEN, *prompt.encode("utf-8")]


def decode_tokens(tokens: list[int]) -> str:
    """Emitted token ids -> text (EOS and any non-byte ids drop out)."""
    return bytes(t for t in tokens if 0 <= t < 256).decode(
        "utf-8", errors="replace"
    )


def prompt_bucket(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket >= n (the prefill program the prompt pads into)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(
        f"prompt of {n} tokens exceeds the largest prefill bucket "
        f"{buckets[-1]}"
    )


# --- the pure functional core (jitted) --------------------------------------


def _build_params(seed: int, d_model: int, n_layers: int, n_heads: int):
    """Deterministic toy-LM weights: same seed -> bit-identical params."""
    import jax
    import jax.numpy as jnp

    head_dim = d_model // n_heads
    key = jax.random.PRNGKey(seed)
    keys = iter(jax.random.split(key, 4 + 6 * n_layers))

    def mat(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale)

    params = {
        "embed": mat((VOCAB_SIZE, d_model), 0.05),
        # Learned positions up to the hard context cap; sliced per program.
        "pos": mat((4096, d_model), 0.02),
        "ln_f": jnp.ones((d_model,), jnp.float32),
        "layers": [],
    }
    for _ in range(n_layers):
        params["layers"].append({
            "ln1": jnp.ones((d_model,), jnp.float32),
            "wqkv": mat((d_model, 3 * d_model), 1.0 / math.sqrt(d_model)),
            "wo": mat((d_model, d_model), 1.0 / math.sqrt(d_model)),
            "ln2": jnp.ones((d_model,), jnp.float32),
            "w1": mat((d_model, 4 * d_model), 1.0 / math.sqrt(d_model)),
            "w2": mat((4 * d_model, d_model), 0.5 / math.sqrt(d_model)),
        })
    del head_dim
    return params


def _rms(x, scale):
    import jax.numpy as jnp

    return x * scale / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _qkv(layer, x, n_heads: int):
    import jax.numpy as jnp

    d = x.shape[-1]
    h = _rms(x, layer["ln1"]) @ layer["wqkv"]
    q, k, v = jnp.split(h, 3, axis=-1)
    shape = (*x.shape[:-1], n_heads, d // n_heads)
    return q.reshape(shape), k.reshape(shape), v.reshape(shape)


def _mlp(layer, x):
    import jax.numpy as jnp

    h = _rms(x, layer["ln2"])
    return jnp.maximum(h @ layer["w1"], 0.0) @ layer["w2"]


def _logits(params, x):
    return _rms(x, params["ln_f"]) @ params["embed"].T


def _decode_step(params, cache, page_table, lengths, last_tokens, active):
    """One batched decode step at fixed width S = max_slots.

    ``cache``       [L, 2, P, page, H, Dh]   (donated)
    ``page_table``  [S, max_pages]  int32    (page 0 = trash)
    ``lengths``     [S]             int32    tokens already written
    ``last_tokens`` [S]             int32    the token each slot consumes
    ``active``      [S]             bool

    Writes each active slot's K/V at logical position ``lengths[s]``,
    attends over positions 0..lengths[s] inclusive, and returns
    ``(cache, logits [S, V], counts)`` -- the engine's epilogue takes the
    greedy argmax, so decoding is deterministic.  Per-slot independence is
    the bit-exactness invariant: no cross-slot reduction anywhere in this
    function.
    """
    import jax.numpy as jnp

    n_layers = len(params["layers"])
    page = cache.shape[3]
    n_heads, head_dim = cache.shape[4], cache.shape[5]
    s_slots, max_pages = page_table.shape
    ctx = max_pages * page

    x = params["embed"][last_tokens] + params["pos"][lengths]      # [S, D]
    write_page = jnp.take_along_axis(
        page_table, (lengths // page)[:, None], axis=1
    )[:, 0]
    write_page = jnp.where(active, write_page, 0)                  # trash
    write_off = lengths % page
    pos_ids = jnp.arange(ctx, dtype=jnp.int32)                     # [ctx]
    att_mask = pos_ids[None, :] <= lengths[:, None]                # [S, ctx]

    for li in range(n_layers):
        layer = params["layers"][li]
        q, k, v = _qkv(layer, x, n_heads)                          # [S, H, Dh]
        cache = cache.at[li, 0, write_page, write_off].set(k)
        cache = cache.at[li, 1, write_page, write_off].set(v)
        k_ctx = cache[li, 0][page_table].reshape(
            s_slots, ctx, n_heads, head_dim
        )
        v_ctx = cache[li, 1][page_table].reshape(
            s_slots, ctx, n_heads, head_dim
        )
        scores = jnp.einsum("shd,sthd->sht", q, k_ctx) / math.sqrt(head_dim)
        scores = jnp.where(att_mask[:, None, :], scores, -1e9)
        w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        w = w / w.sum(axis=-1, keepdims=True)
        attn = jnp.einsum("sht,sthd->shd", w, v_ctx).reshape(s_slots, -1)
        x = x + attn @ layer["wo"]
        x = x + _mlp(layer, x)

    return cache, _logits(params, x), jnp.zeros((N_COUNTS,), jnp.int32)


def _prefill(params, cache, tokens, start, length, page_ids):
    """One chunk of a prompt at one compiled shape T = len(tokens).

    ``tokens``   [T] int32  rows at positions ``start ..`` (BOS + prompt
                 bytes, padded to the shape)
    ``start``    the Python int 0 for a prompt's first chunk, else a
                 traced int32: the positions already in the slot's pages
    ``length``   scalar int32: rows at positions below it are true
    ``page_ids`` [max_pages] int32 -- this slot's page list

    K/V written to the slot's pages (padding positions to the trash page);
    causal attention over ``[0, start + T)`` -- a first chunk within itself
    (it never reads the cache), a later one over the slot's gathered pages.
    Returns ``(cache, logits [V] of the last true position, counts)``: the
    first generated token is the last chunk's greedy argmax.
    """
    import jax.numpy as jnp

    n_layers = len(params["layers"])
    page = cache.shape[3]
    n_heads = cache.shape[4]
    t_len = tokens.shape[0]
    first = isinstance(start, int) and start == 0

    pos = start + jnp.arange(t_len, dtype=jnp.int32)
    x = params["embed"][tokens] + params["pos"][pos]                # [T, D]
    real = pos < length
    slot_page = jnp.minimum(pos // page, page_ids.shape[0] - 1)
    write_page = jnp.where(real, page_ids[slot_page], 0)
    write_off = pos % page
    if first:
        keys = pos
        visible = (pos[None, :] <= pos[:, None]) & real[None, :]    # [T, T]
    else:
        keys = jnp.arange(page_ids.shape[0] * page, dtype=jnp.int32)
        visible = (keys[None, :] <= pos[:, None]) & (keys[None, :] < length)

    for li in range(n_layers):
        layer = params["layers"][li]
        q, k, v = _qkv(layer, x, n_heads)                           # [T, H, Dh]
        cache = cache.at[li, 0, write_page, write_off].set(k)
        cache = cache.at[li, 1, write_page, write_off].set(v)
        head_dim = q.shape[-1]
        if not first:                                               # the slot's whole context
            k = cache[li, 0][page_ids].reshape(keys.shape[0], n_heads, head_dim)
            v = cache[li, 1][page_ids].reshape(keys.shape[0], n_heads, head_dim)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
        scores = jnp.where(visible[None, :, :], scores, -1e9)
        w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        w = w / w.sum(axis=-1, keepdims=True)
        attn = jnp.einsum("hqk,khd->qhd", w, v).reshape(t_len, -1)
        x = x + attn @ layer["wo"]
        x = x + _mlp(layer, x)

    return (cache, _logits(params, x[length - 1 - start]),
            jnp.zeros((N_COUNTS,), jnp.int32))


class ToyDecoder:
    """The byte-level toy behind the decoder interface: text prompts,
    BOS/EOS, float32 per-head K/V pages ``[L, 2, P, page, H, Dh]``."""

    family = "toy"
    text = True
    eos_token = EOS_TOKEN
    vocab_size = VOCAB_SIZE

    def __init__(self, seed: int, d_model: int = 32, n_layers: int = 2, n_heads: int = 2):
        if d_model % n_heads:
            raise ValueError("d_model must divide into n_heads")
        self.d_model, self.n_layers, self.n_heads = d_model, n_layers, n_heads
        self.params = _build_params(seed, d_model, n_layers, n_heads)

    def cache_spec(self, num_pages: int, page_size: int):
        import jax.numpy as jnp

        return (self.n_layers, 2, num_pages, page_size, self.n_heads,
                self.d_model // self.n_heads), jnp.float32

    prefill = staticmethod(_prefill)
    decode_step = staticmethod(_decode_step)

    def describe(self) -> dict:
        return {"family": self.family, "layers": self.n_layers}


def load_decoder(model_root: str | None, model: str):
    """The decoder of the artifact ``<model_root>/<model>/<highest version>/``
    (found as image artifacts are found), or None where there is none: the
    lane then serves the toy.  The artifact's index file states its
    ``family``: the module of ``models/`` of that name serves it, through
    the class it calls ``DECODER``."""
    import importlib

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.models import latent_attention

    if not model_root:
        return None
    version = art.latest_version(model_root, model)
    if version is None:
        return None
    directory = art.version_dir(model_root, model, version)
    if not art.is_decoder_dir(directory):
        return None
    family = str(latent_attention.read_meta(directory).get("family", ""))
    module = None
    if family.isidentifier():
        try:
            module = importlib.import_module(
                f"kubernetes_deep_learning_tpu.models.{family}")
        except ImportError:
            pass
    if getattr(module, "DECODER", None) is None:
        raise ValueError(f"{directory}: no decoder of family {family!r} under models/")
    return module.DECODER.load(directory)


def _pack(logits, counts, top: int):
    """Everything the host reads of a step, as ONE int32 array ``[2 * S + 1,
    top]``: rows 0..S-1 the ids of each slot's ``top`` largest logits
    (largest first: column 0 is the greedy token), rows S..2S-1 those
    logits' float32 bits, the last row the routing counts."""
    import jax
    import jax.numpy as jnp

    # The barrier keeps top_k's outputs one consumer each: with a second
    # reader of the ids (the step's next tokens) the chip's compiler sorts
    # the whole vocabulary in place of its TopK call (0.71 against 0.39 ms).
    values, ids = jax.lax.optimization_barrier(
        jax.lax.top_k(logits.astype(jnp.float32), top))
    tail = jnp.zeros((1, top), jnp.int32).at[0, :counts.shape[0]].set(counts)
    return jnp.concatenate(
        [ids.astype(jnp.int32), jax.lax.bitcast_convert_type(values, jnp.int32), tail])


@dataclass
class StepOutput:
    """One materialized step (or prefill, S = 1) on the host."""
    tokens: np.ndarray        # [S] int32, the greedy choice
    top_ids: np.ndarray       # [S, top] int32
    top_logits: np.ndarray    # [S, top] float32
    counts: np.ndarray        # [N_COUNTS] int64


# --- the engine -------------------------------------------------------------


class DecodeEngine:
    """The decode lane's device state: weights, paged cache, slot tables.

    NOT thread-safe by itself -- the DecodeScheduler's loop thread is the
    single caller of everything that touches device state (the same
    single-dispatcher discipline as the image tier's scheduler).

    ``step_async`` is deliberately dispatch-only (kdlt-lint's
    hot-path-sync pass is rooted there): it enqueues the jitted step and
    returns the unmaterialized token handle.  The ONE host sync per
    iteration is ``materialize()``, called by the scheduler loop -- for a
    step dispatched one iteration earlier, since nothing a dispatch needs
    waits for a read.
    """

    def __init__(
        self,
        model: str = "gen-default",
        *,
        max_slots: int | None = None,
        page_size: int | None = None,
        max_pages_per_seq: int | None = None,
        d_model: int = 32,
        n_layers: int = 2,
        n_heads: int = 2,
        prompt_buckets: tuple[int, ...] | None = None,
        donate: bool | None = None,
        seed: int | None = None,
        decoder=None,
    ):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.max_slots = max_slots or _env_int(SLOTS_ENV, DEFAULT_SLOTS)
        self.page_size = page_size or _env_int(PAGE_SIZE_ENV, DEFAULT_PAGE_SIZE)
        self.max_pages_per_seq = (
            max_pages_per_seq or _env_int(MAX_PAGES_ENV, DEFAULT_MAX_PAGES)
        )
        self.max_context = self.page_size * self.max_pages_per_seq
        self.prompt_buckets = tuple(sorted(
            b for b in (prompt_buckets or env_prompt_buckets() or PROMPT_BUCKETS)
            if b <= self.max_context
        ))
        if not self.prompt_buckets:
            raise ValueError(
                "no prefill bucket fits inside the "
                f"{self.max_context}-token context"
            )
        # The shapes a prefill program is compiled at: the rungs up to the
        # chunk size, and the chunk size itself where a longer rung admits
        # prompts that are prefilled in chunks.
        self.prefill_chunk = PREFILL_CHUNK
        self.chunked = self.prompt_buckets[-1] > self.prefill_chunk
        if self.chunked and self.prefill_chunk % self.page_size:
            raise ValueError(
                f"prompts over {self.prefill_chunk} tokens are prefilled in chunks "
                f"of that many, which the page size {self.page_size} has to divide"
            )
        self.chunk_shapes = tuple(
            b for b in self.prompt_buckets if b < self.prefill_chunk
        ) + ((self.prefill_chunk,) if self.prompt_buckets[-1] >= self.prefill_chunk
             else ())
        self._donate = donation_enabled(donate)
        if decoder is None:
            # No artifact: the toy, its weights keyed by the model name.
            decoder = ToyDecoder(
                seed if seed is not None
                else zlib.crc32(model.encode()) & 0x7FFFFFFF,
                d_model, n_layers, n_heads,
            )
        self.decoder = decoder
        self.vocab_size = int(decoder.vocab_size)
        self.top_logits = min(TOP_LOGITS, self.vocab_size)
        self._params = decoder.params

        # Page pool: page 0 is the trash page (inactive-slot and padding
        # writes land there), never allocated.
        self.num_pages = 1 + self.max_slots * self.max_pages_per_seq
        cache_shape, cache_dtype = decoder.cache_spec(self.num_pages, self.page_size)
        self._cache = jnp.zeros(cache_shape, cache_dtype)
        self.cache_bytes = int(self._cache.nbytes)
        self._free_pages = list(range(self.num_pages - 1, 0, -1))
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._slot_pages: dict[int, list[int]] = {}

        # Host-side slot tables, mirrored to the device on every dispatch
        # (tiny [S]-shaped ints; the cache itself never round-trips).
        self.page_table = np.zeros(
            (self.max_slots, self.max_pages_per_seq), np.int32
        )
        self.lengths = np.zeros((self.max_slots,), np.int32)
        self.active = np.zeros((self.max_slots,), bool)
        # The token every slot consumes next stays ON THE DEVICE: a prefill
        # and a step each leave their greedy choice there, so the next step
        # can be dispatched before the host has read this one.
        self._next_tokens = jnp.zeros((self.max_slots,), jnp.int32)

        top, slots = self.top_logits, self.max_slots

        def step(params, cache, page_table, lengths, next_tokens, active):
            cache, logits, counts = decoder.decode_step(
                params, cache, page_table, lengths, next_tokens, active)
            packed = _pack(logits, counts, top)
            return cache, packed, jnp.where(active, packed[:slots, 0], next_tokens)

        def prefill(params, cache, next_tokens, tokens, start, length, page_ids, slot):
            cache, logits, counts = decoder.prefill(
                params, cache, tokens, start, length, page_ids)
            packed = _pack(logits[None], counts, top)
            return cache, packed, next_tokens.at[slot].set(packed[0, 0])

        def prefill_first(params, cache, next_tokens, tokens, length, page_ids, slot):
            return prefill(params, cache, next_tokens, tokens, 0, length, page_ids, slot)

        donated = (1,) if self._donate else ()
        if self._donate:
            import warnings

            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
        self._step_jit = jax.jit(step, donate_argnums=donated)
        # A prompt's first chunk (start 0, nothing cached: it attends to
        # itself) and a later chunk (start traced) are two programs a shape.
        self._prefill_jit = jax.jit(prefill_first, donate_argnums=donated)
        self._prefill_next_jit = jax.jit(prefill, donate_argnums=donated)

    def status(self) -> dict:
        """The ``decode`` block of GET /v1/models: the sizes the lane was
        booted at and what its decoder says of itself."""
        return {
            "slots": self.max_slots, "page_size": self.page_size,
            "max_pages": self.max_pages_per_seq,
            "prompt_buckets": list(self.prompt_buckets),
            "prefill_chunk": self.prefill_chunk,
            "vocab_size": self.vocab_size, "cache_bytes": self.cache_bytes,
            "top_logits": self.top_logits, **self.decoder.describe(),
        }

    # --- slot/page bookkeeping (host-side) ---------------------------------

    def pages_needed(self, total_tokens: int) -> int:
        return -(-total_tokens // self.page_size)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free_pages)

    @property
    def active_slots(self) -> int:
        return int(self.active.sum())

    def has_capacity(self, total_tokens: int) -> bool:
        return bool(self._free_slots) and (
            self.pages_needed(total_tokens) <= len(self._free_pages)
        )

    def acquire_slot(self, total_tokens: int) -> int | None:
        """Claim a slot + its page list for a generation of at most
        ``total_tokens`` positions; None when slots or pages are short."""
        n = self.pages_needed(total_tokens)
        if total_tokens > self.max_context:
            raise ValueError(
                f"{total_tokens} tokens exceed the {self.max_context}-token "
                "context (page_size * max_pages_per_seq)"
            )
        if not self._free_slots or n > len(self._free_pages):
            return None
        slot = self._free_slots.pop()
        pages = [self._free_pages.pop() for _ in range(n)]
        self._slot_pages[slot] = pages
        row = np.zeros((self.max_pages_per_seq,), np.int32)
        row[: len(pages)] = pages
        self.page_table[slot] = row
        self.lengths[slot] = 0
        self.active[slot] = False  # flips on at prefill
        return slot

    def release_slot(self, slot: int) -> None:
        self.active[slot] = False
        self.lengths[slot] = 0
        self.page_table[slot] = 0
        self._free_pages.extend(reversed(self._slot_pages.pop(slot, [])))
        self._free_slots.append(slot)

    # --- device dispatch ----------------------------------------------------

    def chunk_at(self, n: int, start: int) -> tuple[int, int]:
        """(true rows, compiled shape) of the chunk of an ``n``-token prompt
        that begins at ``start``: a full chunk, or the rest of the prompt at
        the smallest shape that holds it."""
        rows = min(n - start, self.prefill_chunk)
        return rows, prompt_bucket(rows, self.chunk_shapes)

    def prefill_chunk_async(self, slot: int, prompt_tokens: list[int], start: int):
        """Dispatch the chunk of the prompt that begins at ``start`` into
        ``slot``; returns (the unmaterialized handle of its packed output,
        S = 1; true rows; compiled shape).  With its last chunk the slot is
        live: its length covers the prompt and the next step consumes the
        first token, which that chunk leaves on the device."""
        n = len(prompt_tokens)
        rows, shape = self.chunk_at(n, start)
        padded = np.zeros((shape,), np.int32)
        padded[:rows] = prompt_tokens[start:start + rows]
        args = (np.int32(start + rows), self.page_table[slot].copy(), np.int32(slot))
        if start == 0:
            self._cache, out, self._next_tokens = self._prefill_jit(
                self._params, self._cache, self._next_tokens, padded, *args)
        else:
            self._cache, out, self._next_tokens = self._prefill_next_jit(
                self._params, self._cache, self._next_tokens, padded,
                np.int32(start), *args)
        if start + rows == n:
            self.lengths[slot] = n
            self.active[slot] = True
        return out, rows, shape

    def prefill(self, slot: int, prompt_tokens: list[int]):
        """Dispatch one prompt's prefill into ``slot``, every chunk of it
        back to back; returns the handle of the last chunk's packed output,
        whose greedy choice is the first token."""
        start = 0
        while True:
            out, rows, _ = self.prefill_chunk_async(slot, prompt_tokens, start)
            start += rows
            if start >= len(prompt_tokens):
                return out

    def step_async(self):
        """Dispatch one batched decode step; returns the unmaterialized
        handle of its packed output.  No host sync in here -- the scheduler
        loop materializes exactly once per iteration.  The slot tables go
        in as copies: the caller may change them for the next dispatch
        while this one has not run (a backend may read a numpy argument in
        place)."""
        self._cache, nxt, self._next_tokens = self._step_jit(
            self._params, self._cache, self.page_table.copy(), self.lengths,
            self._next_tokens, self.active.copy(),
        )
        self.lengths = self.lengths + self.active.astype(np.int32)
        return nxt

    def materialize(self, handle) -> StepOutput:
        """The per-iteration host sync: one device array -> the step's
        tokens, top logits and routing counts on the host."""
        packed = np.asarray(handle)
        n = (packed.shape[0] - 1) // 2
        return StepOutput(
            tokens=packed[:n, 0], top_ids=packed[:n],
            top_logits=packed[n:2 * n].view(np.float32),
            counts=packed[2 * n, :N_COUNTS].astype(np.int64),
        )

    # --- reference + warmup -------------------------------------------------

    def decode_solo(self, prompt, max_new_tokens: int) -> list[int]:
        """The bit-exactness reference: decode one request (text, or a list
        of token ids) alone through the SAME compiled programs.  Requires
        an idle engine."""
        if self.active.any() or self._slot_pages:
            raise RuntimeError("decode_solo requires an idle engine")
        tokens = encode_prompt(prompt) if isinstance(prompt, str) else list(prompt)
        slot = self.acquire_slot(len(tokens) + max_new_tokens)
        if slot is None:
            raise RuntimeError("no capacity for a solo decode")
        eos = self.decoder.eos_token
        try:
            out: list[int] = []
            tok = int(self.materialize(self.prefill(slot, tokens)).tokens[0])
            out.append(tok)
            while tok != eos and len(out) < max_new_tokens:
                tok = int(self.materialize(self.step_async()).tokens[slot])
                out.append(tok)
            return out
        finally:
            self.release_slot(slot)

    def warmup(self, buckets: tuple[int, ...] | None = None) -> dict:
        """Compile the decode ladder: every shape a prompt's first chunk
        can take (the rungs up to the chunk size), every shape a later
        chunk can take where prompts are chunked, plus the step program
        (the prompt-length x batch-slot grid is one step compile wide --
        the step runs at fixed width by construction).  Rungs above the
        chunk size compile nothing.  Returns the per-program wall times for
        kdlt-warm's report."""
        report = {"model": self.model, "buckets": {}, "chunks": {}, "step_s": 0.0}

        def run(n: int) -> float | None:
            t0 = time.perf_counter()
            slot = self.acquire_slot(min(n + 1, self.max_context))
            if slot is None:
                return None
            try:
                self.materialize(self.prefill(slot, [0] * n))
            finally:
                self.release_slot(slot)
            return round(time.perf_counter() - t0, 4)

        for b in buckets or self.prompt_buckets:
            if b > self.max_context:
                continue
            took = run(min(b, self.prefill_chunk))
            if took is None:
                break
            report["buckets"][str(b)] = took
        if self.chunked:
            for b in self.chunk_shapes:     # a full chunk, then the rest at shape b
                if self.prefill_chunk + b > self.max_context:
                    continue
                took = run(self.prefill_chunk + b)
                if took is not None:
                    report["chunks"][str(b)] = took
        t0 = time.perf_counter()
        slot = self.acquire_slot(2)
        if slot is not None:
            try:
                self.materialize(self.prefill(slot, [0]))
                self.materialize(self.step_async())
            finally:
                self.release_slot(slot)
        report["step_s"] = round(time.perf_counter() - t0, 4)
        return report


# --- the continuous-batching scheduler --------------------------------------


FINISH_STOP = "stop"          # EOS emitted
FINISH_LENGTH = "length"      # max_new_tokens reached
FINISH_DEADLINE = "deadline"  # budget expired mid-stream
FINISH_CANCELLED = "cancelled"  # client went away


@dataclass
class Generation:
    """One in-flight generation: the scheduler's bookkeeping plus the
    event queue its transport thread drains."""

    rid: str
    prompt_tokens: list[int]
    max_new_tokens: int
    priority: str = protocol.DEFAULT_PRIORITY
    deadline: Deadline | None = None
    ignore_eos: bool = False
    top_logits: int = 0      # the largest logits each token event carries
    t_submit: float = field(default_factory=time.perf_counter)
    t_first: float | None = None
    t_last: float | None = None
    tokens: list[int] = field(default_factory=list)
    finish_reason: str | None = None
    slot: int | None = None
    prefilled: int = 0       # prompt positions whose chunks are dispatched
    t_prefill: float | None = None   # when its first chunk began on the device
    prefill_span: str = ""           # the span its chunks' spans hang under
    dispatched: int = 0      # tokens the device was asked for (read or not)
    events: Queue = field(default_factory=Queue)
    _cancel: threading.Event = field(default_factory=threading.Event)

    def cancel(self) -> None:
        self._cancel.set()

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    def ttft_s(self) -> float | None:
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit

    def tpot_s(self) -> float | None:
        if self.t_first is None or self.t_last is None or len(self.tokens) < 2:
            return None
        return (self.t_last - self.t_first) / (len(self.tokens) - 1)

    def iter_events(self, timeout_s: float = 60.0):
        """Drain the event queue: yields ("token", index, id, text, top ids
        or None, top logits or None) then one ("done", finish_reason);
        transport-thread side."""
        while True:
            try:
                ev = self.events.get(timeout=timeout_s)
            except Empty:
                return
            yield ev
            if ev[0] == "done":
                return


@dataclass
class Chunk:
    """One dispatched chunk of a generation's prompt."""
    gen: Generation
    start: int      # the position of its first row
    rows: int       # true prompt positions in it
    shape: int      # rows computed: the compiled shape it was padded to
    last: bool      # the prompt's last chunk: its output is the first token


class DecodeScheduler:
    """The per-step scheduler: admission queue in, token events out.

    Continuous batching (the lane's reason to exist): every loop
    iteration first slot-fills freed decode slots from the queue (by
    (priority rank, absolute deadline) order -- same shed order as the
    image tier), dispatches ONE chunk of the oldest admitted prompt and ONE
    batched step behind what is already on the device, then reads the
    oldest of what is dispatched and fans its tokens out to their
    generations.
    """

    def __init__(
        self,
        engine: DecodeEngine,
        *,
        registry: metrics_lib.Registry | None = None,
        recorder=None,
        tracer=None,
        queue_cap: int | None = None,
    ):
        self.engine = engine
        self.registry = registry
        self.recorder = recorder
        self.tracer = tracer
        # Unset, the cap leaves room for every slot's next request to wait
        # while the loop prefills: closed-loop callers, one a slot, all
        # arrive at once at a start and never again.
        self.queue_cap = queue_cap or _env_int(
            QUEUE_CAP_ENV, max(DEFAULT_QUEUE_CAP, 2 * engine.max_slots))
        self.metrics = (
            metrics_lib.decode_metrics(registry, engine.model)
            if registry is not None else None
        )
        self._queue: list[Generation] = []
        self._live: dict[int, Generation] = {}
        self._prefilling: deque[Generation] = deque()   # admitted, chunks to go
        self._cond = threading.Condition()
        self._seq = 0
        self._closed = False
        self._saturated = False
        self._thread = threading.Thread(
            target=self._loop, name="kdlt-decode", daemon=True
        )
        self._started = False

    def start(self) -> None:
        if not self._started:
            self._started = True
            self._thread.start()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._started:
            self._thread.join(timeout=10.0)

    # --- submission (transport threads) ------------------------------------

    def submit(
        self,
        prompt: str | None,
        max_new_tokens: int,
        *,
        token_ids: list[int] | None = None,
        ignore_eos: bool = False,
        top_logits: int = 0,
        rid: str = "",
        priority: str | None = None,
        deadline: Deadline | None = None,
    ) -> Generation:
        """Enqueue one generation of a text ``prompt`` or of ``token_ids``
        (the model sees those as they are); raises QueueFull at the cap
        (mapped to a retryable 503 by the transports, like the image
        batcher) and ValueError for prompts that cannot fit or ids outside
        the vocabulary (a 400)."""
        decoder = self.engine.decoder
        if token_ids is not None:
            tokens = [int(t) for t in token_ids]
            if any(not 0 <= t < self.engine.vocab_size for t in tokens):
                raise ValueError(
                    f"token id outside the vocabulary [0, {self.engine.vocab_size})"
                )
        elif not decoder.text:
            raise ValueError(
                f"model {self.engine.model!r} has no text codec: send token_ids"
            )
        else:
            tokens = encode_prompt(prompt)
        if top_logits > self.engine.top_logits:
            raise ValueError(
                f"top_logits {top_logits} exceeds the lane's {self.engine.top_logits}"
            )
        total = len(tokens) + max_new_tokens
        if total > self.engine.max_context:
            raise ValueError(
                f"prompt ({len(tokens)} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds the {self.engine.max_context}-"
                "token context"
            )
        prompt_bucket(len(tokens), self.engine.prompt_buckets)  # raises early
        gen = Generation(
            rid=rid, prompt_tokens=tokens, max_new_tokens=max_new_tokens,
            priority=protocol.parse_priority(priority), deadline=deadline,
            ignore_eos=ignore_eos, top_logits=top_logits,
        )
        with self._cond:
            if self._closed:
                raise QueueFull("decode scheduler is shut down")
            if len(self._queue) >= self.queue_cap:
                if self.recorder is not None:
                    self.recorder.record(
                        "decode.shed", rid=rid or None, reason="queue_full",
                    )
                raise QueueFull(
                    f"decode admission queue at capacity ({self.queue_cap})"
                )
            self._seq += 1
            gen._order = (  # type: ignore[attr-defined]
                protocol.PRIORITY_RANK.get(gen.priority, 0),
                deadline.remaining_s() + time.monotonic()
                if deadline is not None else float("inf"),
                self._seq,
            )
            self._queue.append(gen)
            if self.metrics:
                self.metrics["queue_depth"].set(len(self._queue))
            self._cond.notify_all()
        return gen

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    # --- the decode loop (single thread owns all device state) --------------

    def _admit_locked(self) -> list[Generation]:
        """Pop admissible generations under the lock, slot-filling
        whatever is free."""
        admitted: list[Generation] = []
        self._queue.sort(key=lambda g: g._order)  # type: ignore[attr-defined]
        remaining: list[Generation] = []
        for gen in self._queue:
            if gen.cancelled or (gen.deadline is not None and gen.deadline.expired):
                gen.finish_reason = (
                    FINISH_CANCELLED if gen.cancelled else FINISH_DEADLINE
                )
                gen.events.put(("done", gen.finish_reason))
                if self.recorder is not None and gen.finish_reason == FINISH_DEADLINE:
                    self.recorder.record(
                        "decode.shed", rid=gen.rid or None, reason="deadline",
                    )
                continue
            total = len(gen.prompt_tokens) + gen.max_new_tokens
            if self.engine.has_capacity(total):
                slot = self.engine.acquire_slot(total)
                if slot is not None:
                    gen.slot = slot
                    admitted.append(gen)
                    continue
            remaining.append(gen)
        self._queue = remaining
        if remaining and not admitted and self.engine.active_slots:
            if not self._saturated and self.recorder is not None:
                self.recorder.record(
                    "decode.saturated",
                    queued=len(remaining), slots=self.engine.max_slots,
                )
            self._saturated = True
        else:
            self._saturated = False
        if self.metrics:
            self.metrics["queue_depth"].set(len(self._queue))
        return admitted

    def _take(self, gen: Generation, out: StepOutput, row: int, now: float,
              outbox: list) -> int:
        """Book one token of ``gen`` and queue its event in ``outbox``: the
        loop hands the events to the transport threads (``_flush``) only
        once the next dispatch is made, so that their framing and socket
        writes do not take the interpreter from it."""
        token = int(out.tokens[row])
        idx = len(gen.tokens)
        gen.tokens.append(token)
        if gen.t_first is None:
            gen.t_first = now
            if self.tracer is not None:
                self.tracer.record(
                    gen.rid, trace_lib.SPAN_DECODE_FIRST_TOKEN,
                    gen.t_submit, now - gen.t_submit,
                )
        gen.t_last = now
        top_ids = top_values = None
        if gen.top_logits:
            top_ids = out.top_ids[row, :gen.top_logits].tolist()
            top_values = out.top_logits[row, :gen.top_logits].tolist()
        text = decode_tokens([token]) if self.engine.decoder.text else ""
        outbox.append((gen, ("token", idx, token, text, top_ids, top_values)))
        if self.metrics:
            self.metrics["tokens"].inc()
        return token

    @staticmethod
    def _flush(outbox: list) -> None:
        for gen, event in outbox:
            gen.events.put(event)
        outbox.clear()

    def _count_routing(self, counts: np.ndarray) -> None:
        held, absent, zero, touched = (int(c) for c in counts[:4])
        self.metrics["assignments_held"].inc(held)
        self.metrics["assignments_absent"].inc(absent)
        self.metrics["assignments_zero"].inc(zero)
        self.metrics["experts_touched"].inc(touched)
        self.metrics["shared_expert_tokens"].inc(int(counts[5]))

    def _stops(self, gen: Generation, token: int) -> bool:
        return token == self.engine.decoder.eos_token and not gen.ignore_eos

    def _retire(self, gen: Generation, reason: str, outbox: list) -> None:
        gen.finish_reason = reason
        if gen.slot is not None:
            self.engine.release_slot(gen.slot)
            self._live.pop(gen.slot, None)
            gen.slot = None
        if self.metrics:
            self.metrics["generations"].inc()
            ttft, tpot = gen.ttft_s(), gen.tpot_s()
            if ttft is not None:
                self.metrics["ttft"].observe(ttft)
            if tpot is not None:
                self.metrics["tpot"].observe(tpot)
            self.metrics["active_slots"].set(self.engine.active_slots)
            self.metrics["pages_in_use"].set(self.engine.pages_in_use)
        if self.recorder is not None and reason == FINISH_DEADLINE:
            self.recorder.record(
                "decode.shed", rid=gen.rid or None, reason="deadline",
            )
        outbox.append((gen, ("done", reason)))

    def _loop(self) -> None:
        """Dispatch runs ahead of reading.  A step's input token is on the
        device (``DecodeEngine._next_tokens``) and everything else a dispatch
        needs -- lengths, pages, who is live, who has its last token coming
        -- the host knows without the tokens, so the loop keeps ``STEPS_AHEAD``
        rounds on the device and reads the oldest: the device goes from one
        program to the next with no host in between, and the host has a
        whole step's time for the one before.  A round is one chunk of the
        oldest admitted prompt, where one waits, then one step, where a slot
        is live: a prompt of many chunks holds the live slots for one
        chunk's time at once.  What was dispatched for a stream that the
        read then ends (EOS, cancel, deadline) is computed into its own
        pages and dropped."""
        outbox: list = []
        inflight: deque = deque()   # dispatched and not read, oldest first
        rounds_ahead = 0
        read_at = 0.0               # when the last read returned
        while True:
            with self._cond:
                if not inflight and not self._live:
                    self._flush(outbox)
                while (not inflight and not self._closed and not self._queue
                       and not self._live):
                    self._cond.wait(timeout=0.5)
                if self._closed:
                    for gen in self._queue:
                        gen.finish_reason = FINISH_CANCELLED
                        gen.events.put(("done", FINISH_CANCELLED))
                    self._queue.clear()
                    for gen in list(self._live.values()):
                        self._retire(gen, FINISH_CANCELLED, outbox)
                    self._flush(outbox)
                    return
                admitted = self._admit_locked()

            for gen in admitted:
                self._live[gen.slot] = gen
                self._prefilling.append(gen)
            while rounds_ahead < STEPS_AHEAD:
                items = self._dispatch_round(outbox)
                if not items:
                    break
                inflight.extend(
                    (*item, i == len(items) - 1) for i, item in enumerate(items))
                rounds_ahead += 1
            # The last read's events go out beside the device's work.
            self._flush(outbox)
            if not inflight:
                continue

            first, dispatched, handle, ends_round = inflight.popleft()
            out = self.engine.materialize(handle)      # the one host sync
            now = time.perf_counter()
            t0, read_at = max(dispatched, read_at), now   # behind another: from its end
            rounds_ahead -= ends_round
            if isinstance(first, Chunk):
                self._read_chunk(first, out, dispatched, t0, now, outbox)
                continue
            rows, context = first
            if self.metrics:
                self.metrics["steps"].inc()
                self.metrics["step_seconds"].observe(now - t0)
                self.metrics["context_positions"].inc(context)
                self._count_routing(out.counts)
            for slot, gen in rows:
                if gen.done:
                    continue            # ended at an earlier read: dropped
                tok = self._take(gen, out, slot, now, outbox)
                if gen.cancelled:
                    self._retire(gen, FINISH_CANCELLED, outbox)
                elif self._stops(gen, tok):
                    self._retire(gen, FINISH_STOP, outbox)
                elif len(gen.tokens) >= gen.max_new_tokens:
                    self._retire(gen, FINISH_LENGTH, outbox)
                elif gen.deadline is not None and gen.deadline.expired:
                    self._retire(gen, FINISH_DEADLINE, outbox)

    def _dispatch_round(self, outbox: list) -> list:
        """One chunk of the oldest admitted prompt that still has chunks to
        go (a stream cancelled or out of time between two of its chunks
        gives its slot and pages back instead), then one step over the live
        slots.  Returns what was dispatched, in the device's order, as
        (what, dispatch time, unmaterialized output); nothing where neither
        was there to do."""
        items = []
        while self._prefilling:
            gen = self._prefilling[0]
            if not gen.done and not gen.cancelled and not (
                    gen.deadline is not None and gen.deadline.expired):
                break
            self._prefilling.popleft()
            if not gen.done:
                self._retire(gen, FINISH_CANCELLED if gen.cancelled else FINISH_DEADLINE,
                             outbox)
        if self._prefilling:
            gen = self._prefilling[0]
            start, t = gen.prefilled, time.perf_counter()
            handle, rows, shape = self.engine.prefill_chunk_async(
                gen.slot, gen.prompt_tokens, start)
            gen.prefilled += rows
            last = gen.prefilled >= len(gen.prompt_tokens)
            if last:        # the first token is on the device
                self._prefilling.popleft()
                self._dispatched(gen)
            items.append((Chunk(gen, start, rows, shape, last), t, handle))
        if self.engine.active.any():
            items.append(self._dispatch_step())
        return items

    def _read_chunk(self, chunk: "Chunk", out: StepOutput, dispatched: float,
                    t0: float, now: float, outbox: list) -> None:
        """One chunk's program has run: ``t0`` to ``now`` was its time on the
        device (behind another program: from that one's read)."""
        gen = chunk.gen
        if self.metrics:
            m = self.metrics
            m["prefill_seconds"].observe(now - t0)
            m["prefill_chunks"].inc()
            m["prefill_tokens"].inc(chunk.rows)
            m["prefill_padded_tokens"].inc(chunk.shape)
            m["prefill_prompt_tokens"].inc(chunk.rows)
            m["prefill_padding_tokens"].inc(chunk.shape - chunk.rows)
            m["prefill_attended_pairs"].inc(
                chunk.rows * chunk.start + chunk.rows * (chunk.rows + 1) // 2)
            m["prefill_routed_rows"].inc(int(out.counts[0]))
            m["prefill_expert_rows"].inc(int(out.counts[4]))
            m["shared_expert_tokens"].inc(int(out.counts[5]))
            m["active_slots"].set(self.engine.active_slots)
            m["pages_in_use"].set(self.engine.pages_in_use)
        if gen.t_prefill is None:
            gen.t_prefill, gen.prefill_span = t0, trace_lib.new_span_id()
            if self.tracer is not None:
                self.tracer.record(
                    gen.rid, trace_lib.SPAN_DECODE_QUEUE_WAIT,
                    gen.t_submit, dispatched - gen.t_submit,
                )
        if self.tracer is not None:
            self.tracer.record(
                gen.rid, trace_lib.SPAN_DECODE_PREFILL_CHUNK, t0, now - t0,
                parent_id=gen.prefill_span, start=chunk.start, rows=chunk.rows,
            )
            if chunk.last:
                self.tracer.record(
                    gen.rid, trace_lib.SPAN_DECODE_PREFILL, gen.t_prefill,
                    now - gen.t_prefill, span_id=gen.prefill_span,
                )
        if not chunk.last or gen.done:
            return
        first = self._take(gen, out, 0, now, outbox)
        if gen.cancelled:
            self._retire(gen, FINISH_CANCELLED, outbox)
        elif self._stops(gen, first) or len(gen.tokens) >= gen.max_new_tokens:
            self._retire(
                gen,
                FINISH_STOP if self._stops(gen, first) else FINISH_LENGTH,
                outbox,
            )

    def _dispatched(self, gen: Generation) -> None:
        """One more token of ``gen`` is on the device.  Was it the last it
        asked for, its slot sits the following steps out (it is released
        when that token is read)."""
        gen.dispatched += 1
        if gen.dispatched >= gen.max_new_tokens:
            self.engine.active[gen.slot] = False

    def _dispatch_step(self):
        """((the rows the step decodes, the positions it reads), dispatch
        time, its unmaterialized output): every live slot's context, the
        consumed token included."""
        rows = [(slot, gen) for slot, gen in self._live.items()
                if self.engine.active[slot]]
        context = int(self.engine.lengths[self.engine.active].sum()) + len(rows)
        item = (rows, context), time.perf_counter(), self.engine.step_async()
        for _, gen in rows:
            self._dispatched(gen)
        return item
