"""The long-prompt cell's choices, measured alone on the chip.  (PRs 34, 42)

``kimi-code-longprompt-closed64`` prefills prompts of 1k-8k tokens in chunks
between decode steps of 64 slots.  Two constants of the program were set from
this script's readings (PERF.md section 6, PR 34):

(a) ``models/kimi_k2.py::GROUPED_FROM_ROWS``: the held experts' products
    (12 experts of 7168 x 2048, each row routed to 8 of 384) as one masked
    product over every row and expert, as the grouped loop over tiles of
    rows sorted by expert (tiles of 128 and 256), and as
    ``jax.lax.ragged_dot`` over the sorted rows at their worst-case count --
    at 64, 256, 512 and 1,024 rows;
(b) ``runtime/decode.py::PREFILL_CHUNK``: the whole model (the dense layer
    and five expert layers at the published widths, random weights made on
    the device) on the lane's own engine at the cell's sizes: every chunk of
    an 8,192-token prompt at chunk sizes 512, 1,024 and 2,048, and the
    decode step over 64 live slots of ~3.6k positions.
(c) the round as one program (``DecodeEngine.round_async``: the live slots'
    step inside the chunk's program) against the chunk and the step as two:
    for this decoder a later chunk of 1,024 rows over 1k / 4k / 7k cached
    positions beside 48 live slots of ~3.5k positions (of 64); for the first
    decoder (``longcat-agent-decode-closed128``) each first-chunk bucket
    beside none and 128 live slots of ~700 positions.  ``--parts rounds``.

    python3 exp/kimi_lane.py --describe        # no chip: compiles the programs
                                               # of (b) and (c) for a described v5e
    python3 exp/kimi_lane.py --tiny            # control flow at a toy size, CPU
    python3 exp/kimi_lane.py [--parts products,chunks,rounds]  # one v5e chip

On no cell's path; nothing imports it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "perfbench/configs/kimi-k2.7-code-ep32.json"
TRAFFIC = "perfbench/traffic/code-longprompt-closed64.json"
LC_CONFIG = "perfbench/configs/longcat-flash-chat-ep32.json"
LC_TRAFFIC = "perfbench/traffic/agent-decode-closed128.json"
LC_TINY = dict(hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32,
               num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=16,
               qk_nope_head_dim=16, v_head_dim=16, vocab_held=64)
TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=16,
            qk_nope_head_dim=16, v_head_dim=16, vocab_held=64)


def load(tiny: bool):
    from perfbench import dsv3_weights

    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, TRAFFIC)) as f:
        mix = json.load(f)
    if tiny:
        config.update(TINY)
        mix.update(slots=4, page_size=8, max_pages=40, prompt_buckets=[8, 16, 32, 256])
    return dsv3_weights.program_config(config), mix


def timed(fn, *args, repeat: int = 8):
    """Median seconds of a warmed call, each waited for."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeat):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return statistics.median(times)


# --- (a) the held experts' products --------------------------------------------------------


def products(program: dict, tiny: bool) -> None:
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import kimi_k2 as kk

    cfg = kk.KimiConfig.from_dict(program)
    held, d, f = cfg.n_held, cfg.hidden_size, cfg.moe_intermediate_size
    dtype = jnp.dtype(cfg.compute_dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    e = {"w_gate": jax.random.normal(keys[0], (held, d, f), dtype) * d ** -0.5,
         "w_up": jax.random.normal(keys[1], (held, d, f), dtype) * d ** -0.5,
         "w_down": jax.random.normal(keys[2], (held, f, d), dtype) * f ** -0.5}
    k = cfg.num_experts_per_tok

    # the experts' weights are an argument of every form: closed over they
    # would be compiled into the program as a gigabyte of constants
    def masked(e, u, index, weights):
        hit = index[:, :, None] == jnp.arange(held, dtype=jnp.int32)
        per_expert = jnp.where(hit, weights[:, :, None], 0.0).sum(axis=1)
        return kk.masked_experts(cfg, e, u, per_expert)

    def grouped(tile):
        return lambda e, u, index, weights: kk.grouped_experts(
            cfg, e, u, index, weights, tile)[0]

    def ragged(e, u, index, weights):
        """Rows sorted by expert into ``jax.lax.ragged_dot`` at the worst-case
        count (every row to min(k, held) held experts): shapes are static."""
        n = u.shape[0]
        flat = index.reshape(-1)
        key = jnp.where((flat >= 0) & (flat < held), flat, held)
        order = jnp.argsort(key, stable=True)[:n * min(k, held)]
        sizes = (key[:, None] == jnp.arange(held)).sum(axis=0).astype(jnp.int32)
        x = u[order // k].astype(dtype)
        mid = (jax.nn.silu(jax.lax.ragged_dot(x, e["w_gate"], sizes,
                                              preferred_element_type=jnp.float32))
               * jax.lax.ragged_dot(x, e["w_up"], sizes, preferred_element_type=jnp.float32))
        out = jax.lax.ragged_dot(mid.astype(dtype), e["w_down"], sizes,
                                 preferred_element_type=jnp.float32)
        live = jnp.arange(order.shape[0]) < sizes.sum()
        out = out * jnp.where(live, weights.reshape(-1)[order], 0.0)[:, None]
        return jnp.zeros((n, d), jnp.float32).at[jnp.where(live, order // k, n)].add(
            out, mode="drop")

    forms = {"masked": masked, "grouped128": grouped(128), "grouped256": grouped(256),
             "ragged_dot": ragged}
    rng = jax.random.PRNGKey(1)
    for rows in ((16, 64) if tiny else (64, 256, 512, 1024)):
        ku, kc, kw = jax.random.split(jax.random.fold_in(rng, rows), 3)
        u = jax.random.normal(ku, (rows, d), jnp.float32)
        # k distinct experts of all the router's, uniformly: 8 * 12 / 384 held a row
        chosen = jnp.argsort(jax.random.uniform(kc, (rows, cfg.n_routed_experts)))[:, :k]
        index = (chosen - cfg.held_experts[0]).astype(jnp.int32)
        weights = jax.random.uniform(kw, (rows, k), jnp.float32)
        routed = int(((index >= 0) & (index < held)).sum())
        line = {"rows": rows, "routed_rows": routed}
        want = None
        for name, form in forms.items():
            try:
                fn = jax.jit(form)
                got = fn(e, u, index, weights)
                if want is None:
                    want = got
                line[name + "_ms"] = round(1e3 * timed(fn, e, u, index, weights), 4)
                line[name + "_err"] = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            except Exception as err:   # a form the compiler refuses is a finding
                line[name + "_ms"] = f"failed: {type(err).__name__}: {str(err)[:120]}"
        print("products " + json.dumps(line), flush=True)
    del e
    gc.collect()


# --- (b) the chunk size, on the lane's own engine --------------------------------------------


def device_params(cfg):
    """Random weights made on the device, a tensor at a time."""
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import latent_attention as la

    flat = {}
    for i, (name, shape) in enumerate(cfg.tensor_shapes().items()):
        dtype = jnp.float32 if la.tensor_dtype(name) == "float32" else jnp.dtype(cfg.compute_dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(2), i)
        fan_in = shape[-2] if len(shape) > 1 else 1
        if name.endswith("norm"):
            flat[name] = jnp.ones(shape, dtype)
        else:
            flat[name] = (jax.random.normal(key, shape, jnp.float32)
                          * (0.5 * fan_in ** -0.5)).astype(dtype)
    return la.nest(flat)


def chunks(program: dict, mix: dict, tiny: bool, sizes: list[int]) -> None:
    import jax
    import numpy as np

    from kubernetes_deep_learning_tpu.models import kimi_k2 as kk
    from kubernetes_deep_learning_tpu.runtime import decode

    cfg = kk.KimiConfig.from_dict(program)
    decoder = kk.KimiDecoder(cfg, device_params(cfg))
    longest = mix["prompt_buckets"][-1]
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, longest).tolist()
    for chunk in sizes:
        decode.PREFILL_CHUNK = chunk
        try:
            engine = decode.DecodeEngine(
                "kimi", decoder=decoder, max_slots=mix["slots"], page_size=mix["page_size"],
                max_pages_per_seq=mix["max_pages"], prompt_buckets=tuple(mix["prompt_buckets"]))
            slot = engine.acquire_slot(longest + 1)
            for warm in (True, False):         # the first pass compiles
                times, start = [], 0
                while start < longest:
                    t = time.perf_counter()
                    out, rows, _ = engine.prefill_chunk_async(slot, prompt, start)
                    engine.materialize(out)
                    times.append(time.perf_counter() - t)
                    start += rows
                engine.active[slot] = False
            engine.release_slot(slot)
            line = {"chunk": chunk, "chunks": len(times),
                    "first_ms": round(1e3 * times[0], 2), "last_ms": round(1e3 * times[-1], 2),
                    "mean_ms": round(1e3 * statistics.mean(times), 2),
                    "prompt_s": round(sum(times), 4),
                    "tokens_per_s": round(longest / sum(times), 1)}
            # the decode step: every slot live, ~3.6k positions a slot
            rng = np.random.default_rng(1)
            lengths = rng.integers(longest // 8, longest * 3 // 4, mix["slots"])
            for n in lengths:
                s = engine.acquire_slot(int(n) + 64)
                engine.lengths[s], engine.active[s] = int(n), True
            engine.materialize(engine.step_async())
            t = time.perf_counter()
            steps = 3 if tiny else 16
            handles = [engine.step_async() for _ in range(steps)]
            for h in handles:
                engine.materialize(h)
            line["step_ms"] = round(1e3 * (time.perf_counter() - t) / steps, 3)
            line["mean_context"] = int(lengths.mean())
            stats = jax.devices()[0].memory_stats() or {}
            line["peak_gb"] = round(stats.get("peak_bytes_in_use", 0) / 1e9, 2)
        except Exception as err:
            line = {"chunk": chunk, "failed": f"{type(err).__name__}: {str(err)[:300]}"}
        print("chunks " + json.dumps(line), flush=True)
        engine = None
        gc.collect()


# --- (c) a round as one program against a chunk and a step ----------------------------------


def load_longcat(tiny: bool):
    from perfbench import lm_weights

    with open(os.path.join(ROOT, LC_CONFIG)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, LC_TRAFFIC)) as f:
        mix = json.load(f)
    if tiny:
        config.update(LC_TINY)
        mix.update(slots=4, page_size=8, max_pages=20, prompt_buckets=[8, 16])
    program = lm_weights.program_config(config)
    if tiny:
        program["compute_dtype"] = "float32"
    return program, mix


def rounds(engine, slot, prompt, starts, live: int, context: int, tiny: bool) -> list[dict]:
    """For each chunk start: the chunk alone, the round program with no step
    row live, the step of ``live`` other slots of about ``context`` positions
    alone, and chunk and step as one round program -- each the median of
    warmed calls from the same tables, read once each (ms)."""
    import numpy as np

    others = []
    for n in np.random.default_rng(3).integers(context * 3 // 4, context * 5 // 4 + 1, live):
        s = engine.acquire_slot(min(int(n) + 64, engine.max_context))
        engine.lengths[s], engine.active[s] = int(n), True
        others.append(s)
    repeat = 2 if tiny else 5

    def median_ms(call, read=engine.materialize):
        times = []
        for _ in range(1 + repeat):          # the first call compiles
            active, lengths = engine.active.copy(), engine.lengths.copy()
            t = time.perf_counter()
            read(call())
            times.append(time.perf_counter() - t)
            engine.active, engine.lengths = active, lengths
        return round(1e3 * statistics.median(times[1:]), 3)

    lines = []
    for start in starts:
        def chunk():
            return engine.prefill_chunk_async(slot, prompt, start)[0]

        def round_():
            return engine.round_async(slot, prompt, start)[0]

        live_slots = engine.active.copy()
        engine.active[:] = False
        alone = median_ms(chunk)
        round_alone = median_ms(round_, engine.materialize_round)
        engine.active = live_slots
        step = median_ms(engine.step_async) if live else 0.0
        both = median_ms(round_, engine.materialize_round)
        rows, shape = engine.chunk_at(len(prompt), start)
        lines.append({"start": start, "rows": rows, "shape": shape, "live": live,
                      "context": context, "chunk_ms": alone, "round_no_live_ms": round_alone,
                      "step_ms": step, "chunk_then_step_ms": round(alone + step, 3),
                      "round_ms": both, "saved_ms": round(alone + step - both, 3)})
    for s in others:
        engine.release_slot(s)
    return lines


def round_programs(program: dict, mix: dict, tiny: bool) -> None:
    import numpy as np

    from kubernetes_deep_learning_tpu.models import kimi_k2 as kk
    from kubernetes_deep_learning_tpu.models import longcat_flash as lf
    from kubernetes_deep_learning_tpu.runtime import decode

    def engine_of(decoder, mix):
        return decode.DecodeEngine(
            "rounds", decoder=decoder, max_slots=mix["slots"], page_size=mix["page_size"],
            max_pages_per_seq=mix["max_pages"], prompt_buckets=tuple(mix["prompt_buckets"]))

    # this decoder: later chunks over 1k / 4k / 7k cached, 48 of 64 slots live
    decode.PREFILL_CHUNK = 32 if tiny else 1024       # the lane's (``chunks`` moves it)
    cfg = kk.KimiConfig.from_dict(program)
    engine = engine_of(kk.KimiDecoder(cfg, device_params(cfg)), mix)
    chunk = engine.prefill_chunk
    longest = mix["prompt_buckets"][-1]
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, longest).tolist()
    slot = engine.acquire_slot(longest)
    starts = [chunk, 4 * chunk, 7 * chunk] if not tiny else [chunk, 2 * chunk]
    for line in rounds(engine, slot, prompt, starts, mix["slots"] * 3 // 4,
                       longest * 7 // 16, tiny):
        print("rounds " + json.dumps(dict(line, decoder="kimi_k2")), flush=True)
    engine = None
    gc.collect()
    # the first decoder: each first-chunk bucket, beside none and all slots live
    program, mix = load_longcat(tiny)
    cfg = lf.LongcatConfig.from_dict(program)
    engine = engine_of(lf.LongcatDecoder(cfg, device_params(cfg)), mix)
    for bucket in engine.chunk_shapes:
        prompt = np.random.default_rng(bucket).integers(0, cfg.vocab_size, bucket).tolist()
        for live in (0, mix["slots"] - 1):
            slot = engine.acquire_slot(bucket + 1)
            for line in rounds(engine, slot, prompt, [0], live, 16 if tiny else 700, tiny):
                print("rounds " + json.dumps(dict(line, decoder="longcat_flash")), flush=True)
            engine.release_slot(slot)
    engine = None
    gc.collect()


def round_ops(program: dict, mix: dict, tiny: bool, sizes: list[int]) -> None:
    """(c) for this decoder at each chunk size of ``sizes`` (a later chunk
    over ~4k cached, 48 live slots), and the device's operations of three
    calls of the chunk alone and of the round with no live row, each in a
    trace of its own: where the round's rows cost more than the chunk's."""
    import tempfile

    import jax
    import numpy as np

    from kubernetes_deep_learning_tpu.models import kimi_k2 as kk
    from kubernetes_deep_learning_tpu.runtime import decode
    from perfbench import reduce_trace

    cfg = kk.KimiConfig.from_dict(program)
    decoder = kk.KimiDecoder(cfg, device_params(cfg))
    longest = mix["prompt_buckets"][-1]
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, longest).tolist()

    def ops_of(call, read):
        read(call())
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            for _ in range(3):
                read(call())
            jax.profiler.stop_trace()
            reduced = reduce_trace.reduce_profile(
                jax.profiler.ProfileData.from_file(reduce_trace.find_xplane(d)))
        top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1][0])[:25]
        return {"busy_ms": round(1e3 * reduced["busy_s"] / 3, 3),
                "ops": [[reduce_trace.short_name(n, 110), round(1e3 * t / 3, 3), c / 3]
                        for n, (t, c) in top]}

    for chunk in sizes:
        decode.PREFILL_CHUNK = chunk
        engine = decode.DecodeEngine(
            "rounds", decoder=decoder, max_slots=mix["slots"], page_size=mix["page_size"],
            max_pages_per_seq=mix["max_pages"], prompt_buckets=tuple(mix["prompt_buckets"]))
        slot = engine.acquire_slot(longest)
        start = 4 * chunk
        for line in rounds(engine, slot, prompt, [start], mix["slots"] * 3 // 4,
                           longest * 7 // 16, tiny):
            print("roundops " + json.dumps(dict(line, decoder="kimi_k2", chunk=chunk)),
                  flush=True)
        engine.active[:] = False
        for name, call, read in (
                ("chunk", lambda: engine.prefill_chunk_async(slot, prompt, start)[0],
                 engine.materialize),
                ("round_no_live", lambda: engine.round_async(slot, prompt, start)[0],
                 engine.materialize_round)):
            active, lengths = engine.active.copy(), engine.lengths.copy()
            got = ops_of(call, read)
            engine.active, engine.lengths = active, lengths
            print("roundops " + json.dumps({"chunk": chunk, "program": name, **got}),
                  flush=True)
        engine = None
        gc.collect()


# --- no chip: the programs of (b) and (c), compiled for a described v5e -----------------------


def describe(program: dict, mix: dict, sizes: list[int]) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from kubernetes_deep_learning_tpu.models import kimi_k2 as kk
    from kubernetes_deep_learning_tpu.models import latent_attention as la
    from kubernetes_deep_learning_tpu.runtime import decode

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # the program's CPU branches are not what ships
    cfg = kk.KimiConfig.from_dict(program)
    decoder = kk.KimiDecoder(cfg, None)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=chip)

    params = la.nest({
        name: shape(s, jnp.float32 if la.tensor_dtype(name) == "float32" else jnp.bfloat16)
        for name, s in cfg.tensor_shapes().items()})
    slots, pages = mix["slots"], mix["max_pages"]
    cache_shape, cache_dtype = decoder.cache_spec(1 + slots * pages, mix["page_size"])
    cache = shape(cache_shape, cache_dtype)
    i32 = lambda *s: shape(s, jnp.int32)       # noqa: E731

    def step(params, cache, page_table, lengths, next_tokens, active):
        cache, logits, counts = decoder.decode_step(
            params, cache, page_table, lengths, next_tokens, active)
        return cache, decode._pack(logits, counts, 32)

    def prefill(first):
        def run(params, cache, tokens, start, length, page_ids):
            cache, logits, counts = decoder.prefill(
                params, cache, tokens, 0 if first else start, length, page_ids)
            return cache, decode._pack(logits[None], counts, 32)
        return run

    def round_(first):
        def run(params, cache, page_table, lengths, next_tokens, active, tokens, start,
                length, page_ids):
            cache, chunk_logits, step_logits, counts = decoder.prefill_and_step(
                params, cache, tokens, 0 if first else start, length, page_ids, page_table,
                lengths, next_tokens, active)
            return cache, decode._pack(jnp.concatenate([chunk_logits[None], step_logits]),
                                       counts, 32)
        return run

    tables = (i32(slots, pages), i32(slots), i32(slots), shape((slots,), jnp.bool_))
    programs = {"step": (step, (params, cache, *tables))}
    for rows in sizes:
        for first in (True, False):
            which = "first" if first else "later"
            programs[f"prefill {rows} {which}"] = (
                prefill(first), (params, cache, i32(rows), i32(), i32(), i32(pages)))
            programs[f"round {rows} {which}"] = (
                round_(first), (params, cache, *tables, i32(rows), i32(), i32(), i32(pages)))
    for name, (fn, args) in programs.items():
        t = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        m = compiled.memory_analysis()
        print(f"{name}: compiled for {topo.devices[0].device_kind} in "
              f"{time.perf_counter() - t:.0f}s; arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB", flush=True)
    return 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--describe", action="store_true")
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--parts", default="products,chunks")
    p.add_argument("--chunk-sizes", default="")
    args = p.parse_args(argv)
    if args.tiny or args.describe:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    program, mix = load(args.tiny)
    if args.tiny:
        program["compute_dtype"] = "float32"
    sizes = [int(s) for s in args.chunk_sizes.split(",") if s] or (
        [16, 32] if args.tiny else [512, 1024, 2048])
    if args.describe:
        return describe(program, mix, sizes)
    import jax

    print(f"device: {jax.devices()[0].device_kind} x {len(jax.devices())}", flush=True)
    if "products" in args.parts:
        products(program, args.tiny)
    if "chunks" in args.parts:
        chunks(program, mix, args.tiny, sizes)
    if "rounds" in args.parts:
        round_programs(program, mix, args.tiny)
    if "roundops" in args.parts:
        round_ops(program, mix, args.tiny, sizes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
