"""The decode subsystem's device half (runtime/decode.py): paged
KV-cache bookkeeping, the continuous-batching scheduler, and the lane's
load-bearing invariant -- token streams from a shifting continuous batch
are bit-identical to solo decode.

The engine under test is the lane's real engine (tiny byte-level
transformer, real jitted prefill/step programs on CPU), sized small
(2 slots, 8-token pages) so the whole file compiles two prefill buckets
plus one step program once, module-scoped.  Pure token/SSE plumbing
tests run first and need no jax at all.
"""

from __future__ import annotations

import threading
import time

import pytest

from kubernetes_deep_learning_tpu.runtime import decode as decode_lib
from kubernetes_deep_learning_tpu.runtime.batcher import QueueFull
from kubernetes_deep_learning_tpu.serving import protocol
from kubernetes_deep_learning_tpu.serving.admission import Deadline


# --- token + SSE plumbing (no device, no jax) --------------------------------


def test_encode_decode_prompt_round_trip():
    tokens = decode_lib.encode_prompt("hello, tpu!")
    assert tokens[0] == decode_lib.BOS_TOKEN
    assert decode_lib.decode_tokens(tokens[1:]) == "hello, tpu!"
    # Specials never decode into text.
    assert decode_lib.decode_tokens(
        [decode_lib.BOS_TOKEN, 104, 105, decode_lib.EOS_TOKEN]
    ) == "hi"


def test_prompt_bucket_picks_smallest_fit_and_raises_on_overflow():
    buckets = (16, 32, 64)
    assert decode_lib.prompt_bucket(1, buckets) == 16
    assert decode_lib.prompt_bucket(16, buckets) == 16
    assert decode_lib.prompt_bucket(17, buckets) == 32
    assert decode_lib.prompt_bucket(64, buckets) == 64
    with pytest.raises(ValueError):
        decode_lib.prompt_bucket(65, buckets)


def test_generation_ttft_tpot_math():
    gen = decode_lib.Generation(rid="r", prompt_tokens=[256], max_new_tokens=4)
    assert gen.ttft_s() is None and gen.tpot_s() is None
    gen.t_first = gen.t_submit + 0.5
    gen.t_last = gen.t_first + 0.3
    gen.tokens = [1, 2, 3, 4]
    assert gen.ttft_s() == pytest.approx(0.5)
    # TPOT is the inter-token mean EXCLUDING the first token (that one is
    # TTFT's): 0.3s over 3 gaps.
    assert gen.tpot_s() == pytest.approx(0.1)
    # A single-token generation has no inter-token gap to average.
    gen.tokens = [1]
    assert gen.tpot_s() is None


def test_sse_events_round_trip_through_the_parser():
    frames = (
        protocol.sse_token_event(0, 104, "h")
        + protocol.sse_token_event(1, 105, "i")
        + protocol.sse_done_event(
            tokens=2, ttft_ms=1.5, tpot_ms=0.5,
            finish_reason="length", text="hi",
        )
    )
    events = protocol.parse_sse_events(frames)
    assert [e.get("token") for e in events[:-1]] == [104, 105]
    done = events[-1]
    assert done["done"] is True
    assert done["finish_reason"] == "length"
    assert done["text"] == "hi"
    assert done["tokens"] == 2


def test_decode_generate_request_validation():
    ok = protocol.decode_generate_request(b'{"prompt": "hi"}')
    assert ok == {"prompt": "hi", "token_ids": None, "max_new_tokens": 16,
                  "ignore_eos": False, "top_logits": 0, "stream": True}
    ok = protocol.decode_generate_request(
        b'{"prompt": "hi", "max_new_tokens": 3, "stream": false}'
    )
    assert ok["max_new_tokens"] == 3 and ok["stream"] is False
    for bad in (
        b"notjson",
        b'["prompt"]',
        b'{"nope": 1}',
        b'{"prompt": ""}',
        b'{"prompt": 3}',
        b'{"prompt": "x", "max_new_tokens": 0}',
        b'{"prompt": "x", "max_new_tokens": "many"}',
        (
            '{"prompt": "x", "max_new_tokens": %d}'
            % (protocol.GENERATE_MAX_NEW_TOKENS_CAP + 1)
        ).encode(),
    ):
        with pytest.raises(ValueError):
            protocol.decode_generate_request(bad)


# --- the paged engine (real jitted programs, CPU) ----------------------------


@pytest.fixture(scope="module")
def engine():
    # 2 slots x 4 pages of 8 tokens = 32-token context -> two prefill
    # buckets (16, 32); one compile of each + the step program serves the
    # whole module.
    return decode_lib.DecodeEngine(
        "gen-test", max_slots=2, page_size=8, max_pages_per_seq=4,
    )


def test_paged_allocation_frees_on_release(engine):
    assert engine.pages_in_use == 0
    slot = engine.acquire_slot(20)  # 20 tokens -> 3 pages of 8
    try:
        assert slot is not None
        assert engine.pages_in_use == 3
        # active_slots tracks the step mask, which flips at prefill --
        # an acquired-but-unprefilled slot holds pages but is not active.
        assert engine.active_slots == 0
        # Page 0 is the trash page: never handed to a sequence.
        assert 0 not in engine._slot_pages[slot]
    finally:
        engine.release_slot(slot)
    assert engine.pages_in_use == 0
    assert engine.active_slots == 0


def test_slot_exhaustion_returns_none_not_error(engine):
    slots = [engine.acquire_slot(8) for _ in range(engine.max_slots)]
    try:
        assert all(s is not None for s in slots)
        assert engine.acquire_slot(8) is None  # full: admission queues
    finally:
        for s in slots:
            engine.release_slot(s)


def test_solo_decode_is_deterministic(engine):
    a = engine.decode_solo("abc", 6)
    b = engine.decode_solo("abc", 6)
    assert a == b and len(a) <= 6


def test_continuous_batch_streams_bit_identical_to_solo(engine):
    """The lane's load-bearing invariant: a request decoded in a
    SHIFTING continuous batch (members joining and retiring around it)
    yields exactly the tokens of the same request decoded alone.  Mixed
    prompt lengths cover both prefill buckets; mixed budgets force slot
    churn mid-flight."""
    requests = [
        ("short", 10),
        ("a much longer prompt string", 4),
        ("mid-size prompt", 8),
        ("x", 12),
        ("long-ish prompt here", 6),
    ]
    sched = decode_lib.DecodeScheduler(engine)
    sched.start()
    streamed: dict[int, list[int]] = {}

    def drive(i, prompt, mnt):
        gen = sched.submit(prompt, mnt, rid=f"r{i}")
        toks = [ev[2] for ev in gen.iter_events(timeout_s=60.0)
                if ev[0] == "token"]
        streamed[i] = toks

    threads = [
        threading.Thread(target=drive, args=(i, p, n))
        for i, (p, n) in enumerate(requests)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    sched.close()
    assert sorted(streamed) == list(range(len(requests)))
    for i, (prompt, mnt) in enumerate(requests):
        solo = engine.decode_solo(prompt, mnt)
        assert streamed[i] == solo, (
            f"req {i}: continuous batch diverged from solo decode"
        )


def test_freed_slot_is_refilled_before_the_batch_drains(engine):
    """Admission is at token boundaries: with both slots taken, a queued
    request starts when the SHORT neighbour retires, while the long one is
    still decoding -- not when the whole batch has drained (the
    serve-then-swap batch server, whose queued requests wait out the longest
    member)."""
    long_prompt, long_budget = "a much longer prompt", 10
    assert len(engine.decode_solo(long_prompt, long_budget)) == long_budget
    sched = decode_lib.DecodeScheduler(engine)
    # Queued before the loop starts, so admission order is submission order:
    # the long and the short one take the two slots, the third waits.
    long_gen = sched.submit(long_prompt, long_budget, rid="long")
    short_gen = sched.submit("short", 2, rid="short")
    queued_gen = sched.submit("x", 2, rid="queued")
    sched.start()
    try:
        for gen in (long_gen, short_gen, queued_gen):
            events = list(gen.iter_events(timeout_s=60.0))
            assert events[-1][0] == "done", (gen.rid, events)
    finally:
        sched.close()
    assert len(long_gen.tokens) == long_budget
    assert queued_gen.tokens == engine.decode_solo("x", 2)
    # The queued request's first token came after the short one's last (it
    # took that slot) and before the long one's last (nobody waited for it).
    assert short_gen.t_last <= queued_gen.t_first < long_gen.t_last


def test_scheduler_submit_rejects_oversize_prompts(engine):
    sched = decode_lib.DecodeScheduler(engine)
    # 40 chars + budget 10 > the 32-token context (with BOS): a 400, not
    # an admission.
    with pytest.raises(ValueError):
        sched.submit("x" * 40, 10)
    sched.close()


def test_scheduler_queue_cap_sheds_with_queuefull(engine):
    sched = decode_lib.DecodeScheduler(engine, queue_cap=1)
    # Loop NOT started: the first admission sits in the queue, the second
    # hits the cap.
    sched.submit("a", 2)
    with pytest.raises(QueueFull):
        sched.submit("b", 2)
    sched.close()


def test_expired_deadline_finishes_as_deadline_without_tokens(engine):
    sched = decode_lib.DecodeScheduler(engine)
    sched.start()
    gen = sched.submit("abc", 4, deadline=Deadline(0.0))
    events = list(gen.iter_events(timeout_s=30.0))
    sched.close()
    assert events == [("done", decode_lib.FINISH_DEADLINE)]
    assert gen.tokens == []


def test_cancel_stops_a_queued_generation(engine):
    sched = decode_lib.DecodeScheduler(engine)
    gen = sched.submit("abc", 4)
    gen.cancel()
    sched.start()
    deadline = time.monotonic() + 30.0
    while not gen.done and time.monotonic() < deadline:
        time.sleep(0.01)
    sched.close()
    assert gen.finish_reason == decode_lib.FINISH_CANCELLED


# --- dispatch ahead of the reads -----------------------------------------------


def _prefill_then_steps(engine, prompt, steps):
    """One slot's prefill and ``steps`` steps, the host naming no token."""
    slot = engine.acquire_slot(len(prompt) + steps + 1)
    try:
        out = [int(engine.materialize(engine.prefill(slot, prompt)).tokens[0])]
        for _ in range(steps):
            out.append(int(engine.materialize(engine.step_async()).tokens[slot]))
        return out
    finally:
        engine.release_slot(slot)


@pytest.mark.parametrize("prompt, budget", [
    ("ahead", 9), ("x", 12), ("a much longer prompt string", 4)])
def test_a_step_consumes_the_token_the_device_left(engine, prompt, budget):
    """The prefill's and each step's greedy choice stays on the device and
    feeds the next step; dispatching every step before reading any gives
    the stream that reading each first gives."""
    tokens = decode_lib.encode_prompt(prompt)
    one_by_one = _prefill_then_steps(engine, tokens, budget - 1)
    slot = engine.acquire_slot(len(tokens) + budget)
    try:
        handles = [engine.prefill(slot, tokens)]
        handles += [engine.step_async() for _ in range(budget - 1)]
        outs = [engine.materialize(h) for h in handles]
    finally:
        engine.release_slot(slot)
    ahead = [int(outs[0].tokens[0])] + [int(o.tokens[slot]) for o in outs[1:]]
    assert ahead == one_by_one
    solo = engine.decode_solo(prompt, budget)       # stops at EOS
    assert one_by_one[:len(solo)] == solo


def test_a_slot_that_sits_steps_out_keeps_its_token(engine):
    """A step leaves the next token of a slot it does not decode as it
    was: the slot goes on, two steps later, where it stood."""
    stays = decode_lib.encode_prompt("it waits")
    want = _prefill_then_steps(engine, stays, 4)
    a = engine.acquire_slot(len(stays) + 5)
    b = engine.acquire_slot(10)
    try:
        got = [int(engine.materialize(engine.prefill(a, stays)).tokens[0])]
        engine.materialize(engine.prefill(b, decode_lib.encode_prompt("busy")))
        got.append(int(engine.materialize(engine.step_async()).tokens[a]))
        engine.active[a] = False
        for _ in range(2):
            engine.materialize(engine.step_async())     # only b moves
        engine.active[a] = True
        for _ in range(3):
            got.append(int(engine.materialize(engine.step_async()).tokens[a]))
    finally:
        engine.release_slot(a)
        engine.release_slot(b)
    assert got == want


def _series(registry):
    out = {}
    for line in registry.render().splitlines():
        if line.startswith("kdlt_decode_"):
            name = line.split("{", 1)[0].split(" ", 1)[0]
            out[name] = out.get(name, 0.0) + float(line.rsplit(" ", 1)[1])
    return out


@pytest.mark.parametrize("budget", [1, 2, 7])
def test_a_slot_whose_last_token_is_dispatched_sits_the_next_steps_out(engine, budget):
    """The loop dispatches ahead, but never a step for a stream whose last
    token is already on the device: ``budget`` tokens cost one prefill and
    ``budget - 1`` steps, and each step reads the context it should."""
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    registry = metrics_lib.Registry()
    sched = decode_lib.DecodeScheduler(engine, registry=registry)
    sched.start()
    try:
        gen = sched.submit(None, budget, token_ids=[1, 2, 3, 4, 5], ignore_eos=True)
        events = list(gen.iter_events(timeout_s=60.0))
    finally:
        sched.close()
    assert [e[0] for e in events] == ["token"] * budget + ["done"]
    assert [e[1] for e in events[:-1]] == list(range(budget))
    series = _series(registry)
    assert series["kdlt_decode_steps_total"] == budget - 1
    assert series["kdlt_decode_tokens_total"] == budget
    # step j reads the 5 prompt positions, the j tokens before and its own
    assert series["kdlt_decode_context_positions_total"] == sum(
        5 + j + 1 for j in range(budget - 1))
    assert engine.pages_in_use == 0 and engine.active_slots == 0


def test_step_and_prefill_seconds_are_read_to_read(engine):
    """With two steps dispatched, a step's dispatch-to-read time would
    count its predecessor's too; the histograms take a program's time from
    the read before it, so their sums cannot exceed the wall clock."""
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    registry = metrics_lib.Registry()
    sched = decode_lib.DecodeScheduler(engine, registry=registry)
    t0 = time.perf_counter()
    sched.start()
    try:
        gens = [sched.submit(None, n, token_ids=[7] * p, ignore_eos=True)
                for p, n in ((3, 12), (9, 6), (4, 9))]
        for gen in gens:
            assert list(gen.iter_events(timeout_s=60.0))[-1] == ("done", "length")
    finally:
        sched.close()
    wall = time.perf_counter() - t0
    series = _series(registry)
    assert series["kdlt_decode_step_seconds_count"] == series["kdlt_decode_steps_total"]
    assert series["kdlt_decode_prefill_seconds_count"] == 3
    assert 0 < series["kdlt_decode_step_seconds_sum"] \
        + series["kdlt_decode_prefill_seconds_sum"] <= wall


def test_a_stream_cancelled_with_steps_ahead_leaves_its_neighbour_exact(engine):
    """A cancel is seen at a read, when the next step is already on the
    device with that slot live: what it computes there is dropped, the slot
    and its pages come back, the neighbour's stream is the solo decode's,
    and the next stream in the freed slot is too."""
    sched = decode_lib.DecodeScheduler(engine)
    sched.start()
    try:
        keeper = sched.submit("the one that stays", 12, rid="keep")
        goner = sched.submit("gone", 25, rid="gone")
        it = goner.iter_events(timeout_s=60.0)
        next(it)
        goner.cancel()
        assert list(it)[-1] == ("done", decode_lib.FINISH_CANCELLED)
        after = sched.submit("after", 6, rid="after")
        kept = [e[2] for e in keeper.iter_events(timeout_s=60.0) if e[0] == "token"]
        late = [e[2] for e in after.iter_events(timeout_s=60.0) if e[0] == "token"]
    finally:
        sched.close()
    assert len(goner.tokens) < 25
    assert kept == engine.decode_solo("the one that stays", 12)
    assert late == engine.decode_solo("after", 6)
    assert engine.pages_in_use == 0 and len(engine._free_slots) == engine.max_slots


# --- chunked prefill ---------------------------------------------------------------


CHUNK = 16      # the lane's chunk size in these tests (PREFILL_CHUNK is 1,024)


@pytest.fixture(scope="module")
def chunked_engine():
    """The toy at a chunk size of 16 rows: rungs 8 and 16 compile, the rung
    of 64 admits prompts that take up to four chunks."""
    saved, decode_lib.PREFILL_CHUNK = decode_lib.PREFILL_CHUNK, CHUNK
    try:
        return decode_lib.DecodeEngine(
            "gen-chunked", max_slots=3, page_size=8, max_pages_per_seq=10,
            prompt_buckets=(8, 16, 64))
    finally:
        decode_lib.PREFILL_CHUNK = saved


@pytest.mark.parametrize("buckets, chunk, shapes, chunked", [
    ((16, 32, 64), 1024, (16, 32, 64), False),     # every prompt is one chunk
    ((8, 16, 64), 16, (8, 16), True),              # the chunk size is a rung
    ((8, 24, 64), 16, (8, 16), True),              # ... or it is not: it compiles all the same
    ((256, 512, 1024, 2048, 4096, 8192), 1024, (256, 512, 1024), True),
])
def test_the_shapes_a_prefill_program_is_compiled_at(monkeypatch, buckets, chunk, shapes,
                                                     chunked):
    monkeypatch.setattr(decode_lib, "PREFILL_CHUNK", chunk)
    engine = decode_lib.DecodeEngine("gen-shapes", max_slots=1, page_size=8,
                                     max_pages_per_seq=1100, prompt_buckets=buckets)
    assert engine.chunk_shapes == shapes and engine.chunked is chunked
    assert engine.status()["prompt_buckets"] == list(buckets)
    assert engine.status()["prefill_chunk"] == chunk


@pytest.mark.parametrize("n, plan", [
    (5, [(0, 5, 8)]), (16, [(0, 16, 16)]), (17, [(0, 16, 16), (16, 1, 8)]),
    (41, [(0, 16, 16), (16, 16, 16), (32, 9, 16)]), (64, [(s, 16, 16) for s in (0, 16, 32, 48)]),
])
def test_a_prompt_is_full_chunks_and_a_rest_at_the_smallest_rung(chunked_engine, n, plan):
    got, start = [], 0
    while start < n:
        rows, shape = chunked_engine.chunk_at(n, start)
        got.append((start, rows, shape))
        start += rows
    assert got == plan


def test_a_page_size_that_does_not_divide_the_chunk_is_refused(monkeypatch):
    monkeypatch.setattr(decode_lib, "PREFILL_CHUNK", 20)
    with pytest.raises(ValueError, match="page size"):
        decode_lib.DecodeEngine("gen-odd", max_slots=1, page_size=8, max_pages_per_seq=10,
                                prompt_buckets=(8, 64))


@pytest.mark.parametrize("n", [17, 24, 33, 40, 49, 64], ids=lambda n: f"{n}-tokens")
def test_the_toy_in_chunks_gives_the_logits_of_one_program(chunked_engine, n):
    """A rest in each rung after one, two and three full chunks.  The toy
    is float32: a later chunk's softmax over the gathered context against
    one softmax differ by rounding alone (1e-5 of the largest logit)."""
    import numpy as np

    whole = decode_lib.DecodeEngine(
        "gen-chunked", max_slots=3, page_size=8, max_pages_per_seq=10,
        prompt_buckets=(8, 16, 64))
    assert not whole.chunked and chunked_engine.chunked
    prompt = [decode_lib.BOS_TOKEN] + [(7 * i + n) % 256 for i in range(n - 1)]
    rows = []
    for engine in (whole, chunked_engine):
        slot = engine.acquire_slot(n + 4)
        try:
            outs = [engine.materialize(engine.prefill(slot, prompt))]
            outs += [engine.materialize(engine.step_async()) for _ in range(3)]
        finally:
            engine.release_slot(slot)
        rows.append((np.stack([outs[0].top_ids[0]] + [o.top_ids[slot] for o in outs[1:]]),
                     np.stack([outs[0].top_logits[0]] + [o.top_logits[slot] for o in outs[1:]])))
    (ids, logits), (ids_c, logits_c) = rows
    assert (ids_c[:, 0] == ids[:, 0]).all()
    assert float(np.abs(logits_c - logits).max()) < 1e-5 * float(np.abs(logits).max())


def test_at_most_one_chunk_goes_between_two_steps(chunked_engine, monkeypatch):
    """With a stream live, a prompt of four chunks arrives: the device's
    order is chunk, step, chunk, step, never two chunks running; the live
    stream is the solo decode's all the same, and so is the long one."""
    order = []
    chunk_async, step_async = chunked_engine.prefill_chunk_async, chunked_engine.step_async
    monkeypatch.setattr(chunked_engine, "prefill_chunk_async",
                        lambda *a: (order.append("chunk"), chunk_async(*a))[1])
    monkeypatch.setattr(chunked_engine, "step_async",
                        lambda: (order.append("step"), step_async())[1])
    long_prompt = [decode_lib.BOS_TOKEN] + [(3 * i) % 256 for i in range(60)]
    sched = decode_lib.DecodeScheduler(chunked_engine)
    sched.start()
    try:
        live = sched.submit("stays live", 30, ignore_eos=True)
        it = live.iter_events(timeout_s=60.0)
        next(it)
        late = sched.submit(None, 5, token_ids=long_prompt, ignore_eos=True)
        late_tokens = [e[2] for e in late.iter_events(timeout_s=60.0) if e[0] == "token"]
        live_tokens = [live.tokens[0]] + [e[2] for e in it if e[0] == "token"]
    finally:
        sched.close()
    assert order.count("chunk") == 1 + 4      # "stays live" is BOS + 10 bytes: one chunk
    first_late = order.index("chunk", 1)      # past the live stream's own
    window = order[first_late:]
    window = window[:len(window) - window[::-1].index("chunk")]     # to the last chunk
    assert "step" in window and all(
        not (a == b == "chunk") for a, b in zip(window, window[1:]))
    monkeypatch.undo()
    solo = chunked_engine.decode_solo("stays live", 30)          # stops at EOS
    assert len(live_tokens) == 30 and live_tokens[:len(solo)] == solo
    solo = chunked_engine.decode_solo(long_prompt, 5)
    assert len(late_tokens) == 5 and late_tokens[:len(solo)] == solo


def test_a_stream_cancelled_between_two_of_its_chunks_frees_slot_and_pages(chunked_engine):
    """The cancel is seen when the prompt's next chunk is due: no more of it
    is dispatched, slot and pages come back, the neighbour's stream is the
    solo decode's, and the next stream in the freed slot is too."""
    dispatched = []
    chunk_async = chunked_engine.prefill_chunk_async
    goner_prompt = [decode_lib.BOS_TOKEN] + [(5 * i) % 256 for i in range(63)]

    def watched(slot, tokens, start):
        dispatched.append((len(tokens), start))
        if len(tokens) == 64 and start == 16:
            goner.cancel()              # between its second chunk and its third
        return chunk_async(slot, tokens, start)

    chunked_engine.prefill_chunk_async = watched
    sched = decode_lib.DecodeScheduler(chunked_engine)
    sched.start()
    try:
        keeper = sched.submit("the one that stays", 12, rid="keep")
        goner = sched.submit(None, 8, token_ids=goner_prompt, rid="gone")
        assert list(goner.iter_events(timeout_s=60.0)) == [("done", decode_lib.FINISH_CANCELLED)]
        after = sched.submit("after", 6, rid="after")
        kept = [e[2] for e in keeper.iter_events(timeout_s=60.0) if e[0] == "token"]
        late = [e[2] for e in after.iter_events(timeout_s=60.0) if e[0] == "token"]
    finally:
        sched.close()
        del chunked_engine.prefill_chunk_async
    assert [d for d in dispatched if d[0] == 64] == [(64, 0), (64, 16)]
    assert goner.tokens == [] and goner.slot is None
    assert kept == chunked_engine.decode_solo("the one that stays", 12)
    assert late == chunked_engine.decode_solo("after", 6)
    assert chunked_engine.pages_in_use == 0
    assert len(chunked_engine._free_slots) == chunked_engine.max_slots


def test_no_program_is_compiled_after_the_warm_up(monkeypatch):
    """``warmup`` compiles every shape a prompt can meet -- a first chunk
    and a later chunk at each rung up to the chunk size, and the step --
    so prompts in every rung, of one chunk and of several, compile nothing."""
    monkeypatch.setattr(decode_lib, "PREFILL_CHUNK", CHUNK)
    engine = decode_lib.DecodeEngine("gen-warm", max_slots=2, page_size=8,
                                     max_pages_per_seq=10, prompt_buckets=(8, 16, 32, 64))
    report = engine.warmup()
    assert sorted(report["buckets"]) == ["16", "32", "64", "8"]
    assert sorted(report["chunks"]) == ["16", "8"]
    programs = (engine._prefill_jit, engine._prefill_next_jit, engine._step_jit)
    compiled = [p._cache_size() for p in programs]
    assert compiled == [2, 2, 1]
    for n in (1, 8, 9, 16, 17, 24, 25, 32, 40, 41, 63, 64):
        engine.decode_solo([decode_lib.BOS_TOKEN] + [65] * (n - 1), 3)
    assert [p._cache_size() for p in programs] == compiled
