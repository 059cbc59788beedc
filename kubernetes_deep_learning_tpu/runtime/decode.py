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

The model itself is a deliberately tiny byte-level causal transformer
(weights derived deterministically from the model name), standing in for
a real checkpoint: the contracts under test -- paging, donation,
continuous batching, streaming, per-token SLOs -- are all shape- and
schedule-level, not weight-level.
"""

from __future__ import annotations

import math
import os
import threading
import time
import zlib
from dataclasses import dataclass, field
from queue import Empty, Queue

import numpy as np

from kubernetes_deep_learning_tpu.runtime.batcher import QueueFull
from kubernetes_deep_learning_tpu.runtime.engine import donation_enabled
from kubernetes_deep_learning_tpu.serving import protocol
from kubernetes_deep_learning_tpu.serving.admission.deadline import Deadline
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from kubernetes_deep_learning_tpu.utils import trace as trace_lib

# Byte-level vocabulary: 256 raw bytes + BOS + EOS.  No tokenizer on the
# wire -- prompts travel as text and are encoded here, so the protocol
# carries no vocab contract.
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

DEFAULT_SLOTS = 4
DEFAULT_PAGE_SIZE = 16
DEFAULT_MAX_PAGES = 8
DEFAULT_QUEUE_CAP = 64

# The prefill compile ladder (prompt positions INCLUDING the BOS token,
# like the image engine's batch buckets): each bucket is one compiled
# program, prompts pad up to the next rung.  kdlt-warm walks this ladder
# so scaled pods never pay a prefill compile on their first generation.
PROMPT_BUCKETS = (16, 32, 64)


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw.strip() else default
    except ValueError:
        return default


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
    ``(cache, next_tokens)`` -- greedy argmax, so decoding is
    deterministic.  Per-slot independence is the bit-exactness invariant:
    no cross-slot reduction anywhere in this function.
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

    nxt = jnp.argmax(_logits(params, x), axis=-1).astype(jnp.int32)
    return cache, nxt


def _prefill(params, cache, tokens, length, page_ids):
    """One prompt's prefill at one bucket shape T = len(tokens).

    ``tokens``   [T] int32  (BOS + prompt bytes, padded to the bucket)
    ``length``   scalar int32 (true token count)
    ``page_ids`` [max_pages] int32 -- this slot's page list

    Full causal self-attention within the prompt (never reads the cache),
    K/V written to the slot's pages (padding positions to the trash
    page), and the first generated token taken greedily from the last
    true position's logits.  Returns ``(cache, first_token)``.
    """
    import jax.numpy as jnp

    n_layers = len(params["layers"])
    page = cache.shape[3]
    n_heads = cache.shape[4]
    t_len = tokens.shape[0]

    pos = jnp.arange(t_len, dtype=jnp.int32)
    x = params["embed"][tokens] + params["pos"][pos]                # [T, D]
    real = pos < length
    write_page = jnp.where(real, page_ids[pos // page], 0)
    write_off = pos % page
    causal = (pos[None, :] <= pos[:, None]) & real[None, :]         # [T, T]

    for li in range(n_layers):
        layer = params["layers"][li]
        q, k, v = _qkv(layer, x, n_heads)                           # [T, H, Dh]
        cache = cache.at[li, 0, write_page, write_off].set(k)
        cache = cache.at[li, 1, write_page, write_off].set(v)
        head_dim = q.shape[-1]
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
        scores = jnp.where(causal[None, :, :], scores, -1e9)
        w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        w = w / w.sum(axis=-1, keepdims=True)
        attn = jnp.einsum("hqk,khd->qhd", w, v).reshape(t_len, -1)
        x = x + attn @ layer["wo"]
        x = x + _mlp(layer, x)

    first = jnp.argmax(_logits(params, x[length - 1]), axis=-1)
    return cache, first.astype(jnp.int32)


# --- the engine -------------------------------------------------------------


class DecodeEngine:
    """The decode lane's device state: weights, paged cache, slot tables.

    NOT thread-safe by itself -- the DecodeScheduler's loop thread is the
    single caller of everything that touches device state (the same
    single-dispatcher discipline as the image tier's scheduler).

    ``step_async`` is deliberately dispatch-only (kdlt-lint's
    hot-path-sync pass is rooted there): it enqueues the jitted step and
    returns the unmaterialized token handle.  The ONE host sync per
    iteration is ``materialize()``, called by the scheduler loop.
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
            b for b in (prompt_buckets or PROMPT_BUCKETS)
            if b <= self.max_context
        ))
        if not self.prompt_buckets:
            raise ValueError(
                "no prefill bucket fits inside the "
                f"{self.max_context}-token context"
            )
        if d_model % n_heads:
            raise ValueError("d_model must divide into n_heads")
        self.d_model, self.n_layers, self.n_heads = d_model, n_layers, n_heads
        self._donate = donation_enabled(donate)
        self._seed = (
            seed if seed is not None else zlib.crc32(model.encode()) & 0x7FFFFFFF
        )
        self._params = _build_params(self._seed, d_model, n_layers, n_heads)

        # Page pool: page 0 is the trash page (inactive-slot and padding
        # writes land there), never allocated.
        self.num_pages = 1 + self.max_slots * self.max_pages_per_seq
        head_dim = d_model // n_heads
        self._cache = jnp.zeros(
            (n_layers, 2, self.num_pages, self.page_size, n_heads, head_dim),
            jnp.float32,
        )
        self._free_pages = list(range(self.num_pages - 1, 0, -1))
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._slot_pages: dict[int, list[int]] = {}

        # Host-side slot tables, mirrored to the device on every dispatch
        # (tiny [S]-shaped ints; the cache itself never round-trips).
        self.page_table = np.zeros(
            (self.max_slots, self.max_pages_per_seq), np.int32
        )
        self.lengths = np.zeros((self.max_slots,), np.int32)
        self.last_tokens = np.zeros((self.max_slots,), np.int32)
        self.active = np.zeros((self.max_slots,), bool)

        if self._donate:
            import warnings

            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            self._step_jit = jax.jit(_decode_step, donate_argnums=(1,))
            self._prefill_jit = jax.jit(_prefill, donate_argnums=(1,))
        else:
            self._step_jit = jax.jit(_decode_step)
            self._prefill_jit = jax.jit(_prefill)

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
        self.last_tokens[slot] = 0
        self.active[slot] = False  # flips on at prefill
        return slot

    def release_slot(self, slot: int) -> None:
        self.active[slot] = False
        self.lengths[slot] = 0
        self.page_table[slot] = 0
        self._free_pages.extend(reversed(self._slot_pages.pop(slot, [])))
        self._free_slots.append(slot)

    # --- device dispatch ----------------------------------------------------

    def prefill(self, slot: int, prompt_tokens: list[int]):
        """Dispatch one prompt's prefill into ``slot``; returns the
        unmaterialized first-token handle.  The slot is live afterwards:
        its length covers the prompt and the next step consumes the
        first token (once materialized and stored via ``seed_token``)."""
        n = len(prompt_tokens)
        bucket = prompt_bucket(n, self.prompt_buckets)
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = prompt_tokens
        self._cache, first = self._prefill_jit(
            self._params, self._cache, padded,
            np.int32(n), self.page_table[slot],
        )
        self.lengths[slot] = n
        self.active[slot] = True
        return first

    def seed_token(self, slot: int, token: int) -> None:
        """Store the token the next step consumes for ``slot``."""
        self.last_tokens[slot] = token

    def step_async(self):
        """Dispatch one batched decode step; returns the unmaterialized
        next-token handle.  No host sync in here -- the scheduler loop
        materializes exactly once per iteration."""
        self._cache, nxt = self._step_jit(
            self._params, self._cache, self.page_table, self.lengths,
            self.last_tokens, self.active,
        )
        self.lengths = self.lengths + self.active.astype(np.int32)
        return nxt

    def materialize(self, handle) -> np.ndarray:
        """The per-iteration host sync: handle -> host int32 array."""
        return np.asarray(handle)

    # --- reference + warmup -------------------------------------------------

    def decode_solo(self, prompt: str, max_new_tokens: int) -> list[int]:
        """The bit-exactness reference: decode one request alone through
        the SAME compiled programs.  Requires an idle engine."""
        if self.active.any() or self._slot_pages:
            raise RuntimeError("decode_solo requires an idle engine")
        tokens = encode_prompt(prompt)
        slot = self.acquire_slot(len(tokens) + max_new_tokens)
        if slot is None:
            raise RuntimeError("no capacity for a solo decode")
        try:
            out: list[int] = []
            tok = int(self.materialize(self.prefill(slot, tokens)))
            out.append(tok)
            while tok != EOS_TOKEN and len(out) < max_new_tokens:
                self.seed_token(slot, tok)
                step = self.step_async()
                tok = int(self.materialize(step)[slot])
                out.append(tok)
            return out
        finally:
            self.release_slot(slot)

    def warmup(self, buckets: tuple[int, ...] | None = None) -> dict:
        """Compile the decode ladder: every prefill bucket plus the step
        program (the prompt-length x batch-slot grid is one step compile
        wide -- the step runs at fixed width by construction).  Returns
        the per-program wall times for kdlt-warm's report."""
        report = {"model": self.model, "buckets": {}, "step_s": 0.0}
        for b in buckets or self.prompt_buckets:
            if b > self.max_context:
                continue
            t0 = time.perf_counter()
            slot = self.acquire_slot(min(b + 1, self.max_context))
            if slot is None:
                break
            try:
                self.materialize(self.prefill(slot, [BOS_TOKEN] * b))
            finally:
                self.release_slot(slot)
            report["buckets"][str(b)] = round(time.perf_counter() - t0, 4)
        t0 = time.perf_counter()
        slot = self.acquire_slot(2)
        if slot is not None:
            try:
                self.materialize(self.prefill(slot, [BOS_TOKEN]))
                self.seed_token(slot, BOS_TOKEN)
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
    t_submit: float = field(default_factory=time.perf_counter)
    t_first: float | None = None
    t_last: float | None = None
    tokens: list[int] = field(default_factory=list)
    finish_reason: str | None = None
    slot: int | None = None
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
        """Drain the event queue: yields ("token", index, id, text) then
        one ("done", finish_reason); transport-thread side."""
        while True:
            try:
                ev = self.events.get(timeout=timeout_s)
            except Empty:
                return
            yield ev
            if ev[0] == "done":
                return


class DecodeScheduler:
    """The per-step scheduler: admission queue in, token events out.

    Continuous batching (the lane's reason to exist): every loop
    iteration first slot-fills freed decode slots from the queue (by
    (priority rank, absolute deadline) order -- same shed order as the
    image tier), then runs ONE batched step and fans the materialized
    tokens out to their generations.
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
        self.queue_cap = queue_cap or _env_int(QUEUE_CAP_ENV, DEFAULT_QUEUE_CAP)
        self.metrics = (
            metrics_lib.decode_metrics(registry, engine.model)
            if registry is not None else None
        )
        self._queue: list[Generation] = []
        self._live: dict[int, Generation] = {}
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
        prompt: str,
        max_new_tokens: int,
        *,
        rid: str = "",
        priority: str | None = None,
        deadline: Deadline | None = None,
    ) -> Generation:
        """Enqueue one generation; raises QueueFull at the cap (mapped to
        a retryable 503 by the transports, like the image batcher) and
        ValueError for prompts that cannot fit (a 400)."""
        tokens = encode_prompt(prompt)
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

    def _emit(self, gen: Generation, token: int, now: float) -> None:
        idx = len(gen.tokens)
        gen.tokens.append(int(token))
        if gen.t_first is None:
            gen.t_first = now
            if self.tracer is not None:
                self.tracer.record(
                    gen.rid, trace_lib.SPAN_DECODE_FIRST_TOKEN,
                    gen.t_submit, now - gen.t_submit,
                )
        gen.t_last = now
        text = decode_tokens([int(token)])
        gen.events.put(("token", idx, int(token), text))
        if self.metrics:
            self.metrics["tokens"].inc()

    def _retire(self, gen: Generation, reason: str) -> None:
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
        gen.events.put(("done", reason))

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._closed and not self._queue and not self._live:
                    self._cond.wait(timeout=0.5)
                if self._closed:
                    for gen in self._queue:
                        gen.finish_reason = FINISH_CANCELLED
                        gen.events.put(("done", FINISH_CANCELLED))
                    self._queue.clear()
                    for gen in list(self._live.values()):
                        self._retire(gen, FINISH_CANCELLED)
                    return
                admitted = self._admit_locked()

            # Prefill the admissions (one compiled bucket each); the first
            # token comes straight out of prefill -- that materialization
            # IS the TTFT moment.
            for gen in admitted:
                t0 = time.perf_counter()
                handle = self.engine.prefill(gen.slot, gen.prompt_tokens)
                first = int(self.engine.materialize(handle))
                now = time.perf_counter()
                if self.metrics:
                    self.metrics["prefill_seconds"].observe(now - t0)
                    self.metrics["active_slots"].set(self.engine.active_slots)
                    self.metrics["pages_in_use"].set(self.engine.pages_in_use)
                if self.tracer is not None:
                    self.tracer.record(
                        gen.rid, trace_lib.SPAN_DECODE_PREFILL, t0, now - t0,
                    )
                self._live[gen.slot] = gen
                self._emit(gen, first, now)
                if first == EOS_TOKEN or len(gen.tokens) >= gen.max_new_tokens:
                    self._retire(
                        gen,
                        FINISH_STOP if first == EOS_TOKEN else FINISH_LENGTH,
                    )
                else:
                    self.engine.seed_token(gen.slot, first)

            if not self._live:
                continue

            # One batched step: dispatch, then the single host sync.
            t0 = time.perf_counter()
            handle = self.engine.step_async()
            toks = self.engine.materialize(handle)
            now = time.perf_counter()
            if self.metrics:
                self.metrics["steps"].inc()
                self.metrics["step_seconds"].observe(now - t0)
            for slot, gen in list(self._live.items()):
                tok = int(toks[slot])
                self._emit(gen, tok, now)
                if gen.cancelled:
                    self._retire(gen, FINISH_CANCELLED)
                elif tok == EOS_TOKEN:
                    self._retire(gen, FINISH_STOP)
                elif len(gen.tokens) >= gen.max_new_tokens:
                    self._retire(gen, FINISH_LENGTH)
                elif gen.deadline is not None and gen.deadline.expired:
                    self._retire(gen, FINISH_DEADLINE)
                else:
                    self.engine.seed_token(slot, tok)
