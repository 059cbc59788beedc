"""LongCat-Flash on the generative lane, on the CPU at a small size (hidden
64, 2 layers, 4 heads, 8 real + 4 zero-compute experts, top-3, vocabulary
64): the program's prefill then decode through the paged latent cache
against the plain reference's full forward (``perfbench/reference``), the
share test of the expert layer, absorbed against expanded attention, the
kernel against the gather, the routing counters, and the token wire through
the model server booted with the lane alone."""

from __future__ import annotations

import http.client
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_deep_learning_tpu.models import latent_attention as la
from kubernetes_deep_learning_tpu.models import longcat_flash as lf
from kubernetes_deep_learning_tpu.ops import mla_decode
from kubernetes_deep_learning_tpu.runtime import decode as decode_lib
from kubernetes_deep_learning_tpu.serving import protocol
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from perfbench import lm_weights
from perfbench.reference import longcat_flash as ref

SEED = 7
CONFIG = {
    "served_name": "lc-tiny", "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 16, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "zero_expert_num": 4, "moe_topk": 3, "routed_scaling_factor": 6,
    "rms_norm_eps": 1e-5, "rope_theta": 1e7, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "published": {"n_routed_experts": 8},
    "held_experts": [2, 6], "vocab_held": 64,
    "assumed": {"weight_scales": {
        "attention_logit_std": 2.5, "residual_branch_scale": 0.5, "router_logit_std": 3.0,
        "router_bias_std": 5e-4, "norm_jitter": 0.05}},
}
EXPERT_LAYERS = CONFIG["num_layers"]
TOPK = CONFIG["moe_topk"]


def write_artifact(root, config=CONFIG, compute_dtype="float32"):
    program = dict(lm_weights.program_config(config), compute_dtype=compute_dtype)
    shapes = lf.LongcatConfig.from_dict(program).tensor_shapes()
    directory = os.path.join(root, config["served_name"], "1")
    lf.write_artifact(directory, program,
                      lm_weights.tensors(config, SEED, shapes, lf.tensor_dtype))
    return directory


@pytest.fixture(scope="module")
def models_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("models"))
    write_artifact(root)
    return root


@pytest.fixture(scope="module")
def reference_weights(models_root):
    return {k: jnp.asarray(v) for k, v in ref.weights(CONFIG, SEED, models_root).items()}


def make_engine(models_root, attention, **kwargs):
    decoder = lf.LongcatDecoder.load(os.path.join(models_root, "lc-tiny", "1"),
                                     attention=attention)
    sizes = dict(max_slots=3, page_size=8, max_pages_per_seq=8, prompt_buckets=(16, 32))
    return decode_lib.DecodeEngine("lc-tiny", decoder=decoder, **{**sizes, **kwargs})


def reference_logits(weights, prompt, served):
    ids = jnp.asarray(list(prompt) + list(served[:-1]), jnp.int32)
    return np.asarray(ref.forward(weights, ids, CONFIG))[len(prompt) - 1:]


def stream_error(full, ids, logits):
    """The widest |served - reference| logit over the scale of the reference."""
    got = np.take_along_axis(full, np.asarray(ids), axis=1)
    return float(np.abs(got - np.asarray(logits)).max() / np.abs(full).max())


# --- prefill then decode through the paged latent cache, against the full forward -----


@pytest.mark.parametrize("attention", ["gather", "interpret"])
def test_prefill_then_decode_matches_the_references_full_forward(
        models_root, reference_weights, attention):
    """Slots join and leave while one stream runs across five page
    boundaries: every step's top logits are the full forward's at that
    position, whoever else is in the batch."""
    engine = make_engine(models_root, attention)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, n).tolist() for n in (11, 16, 5)]
    budgets = [30, 6, 12]
    slots, tokens, ids, logits = {}, {}, {}, {}

    def join(k):
        slot = engine.acquire_slot(len(prompts[k]) + budgets[k])
        out = engine.materialize(engine.prefill(slot, prompts[k]))
        slots[k] = slot
        tokens[k], ids[k], logits[k] = ([int(out.tokens[0])], [out.top_ids[0]],
                                        [out.top_logits[0]])

    join(0)
    for step in range(budgets[0] - 1):
        if step == 3:
            join(1)
        if step == 12:
            join(2)
        out = engine.materialize(engine.step_async())
        for k, slot in list(slots.items()):
            if len(tokens[k]) >= budgets[k]:
                continue
            tokens[k].append(int(out.tokens[slot]))
            ids[k].append(out.top_ids[slot])
            logits[k].append(out.top_logits[slot])
            if len(tokens[k]) >= budgets[k] and k != 0:
                engine.release_slot(slot)       # leaves mid-run; its pages return
                del slots[k]
    assert [len(tokens[k]) for k in range(3)] == budgets
    for k in range(3):
        full = reference_logits(reference_weights, prompts[k], tokens[k])
        assert stream_error(full, ids[k], logits[k]) < 2e-5
        assert full.argmax(axis=1).tolist() == tokens[k]
        assert all(row[0] == t for row, t in zip(ids[k], tokens[k]))


def test_bfloat16_as_served_stays_near_the_reference(tmp_path, reference_weights):
    root = str(tmp_path)
    write_artifact(root, compute_dtype="bfloat16")
    engine = make_engine(root, "gather")
    prompt = np.random.default_rng(5).integers(0, 64, 9).tolist()
    served = engine.decode_solo(prompt, 20)
    assert len(served) == 20
    full = reference_logits(reference_weights, prompt, served)
    best = full.max(axis=1) - full[np.arange(20), served]
    assert float(best.max() / np.abs(full).max()) < 0.05


# --- the expert layer -------------------------------------------------------------------


def expert_layer(held):
    """The program's layer-0 expert weights for the real experts [lo, hi)."""
    config = dict(CONFIG, held_experts=list(held))
    program = dict(lm_weights.program_config(config), compute_dtype="float32")
    cfg = lf.LongcatConfig.from_dict(program)
    whole = dict(lm_weights.tensors(
        dict(CONFIG, held_experts=[0, 8]), SEED,
        {k: v for k, v in lf.LongcatConfig.from_dict(dict(program, held_experts=[0, 8]))
         .tensor_shapes().items() if k.startswith("layers.0.") and "attn" not in k
         and "ffn" not in k}, lf.tensor_dtype))
    width = CONFIG["expert_ffn_hidden_size"]
    cols = slice(held[0] * width, held[1] * width)
    import ml_dtypes

    bf = lambda a: jnp.asarray(a.view(ml_dtypes.bfloat16))       # noqa: E731
    layer = {"router": bf(whole["layers.0.router"]),
             "router_bias": jnp.asarray(whole["layers.0.router_bias"]),
             "experts": {"w_gate": bf(whole["layers.0.experts.w_gate"])[:, cols],
                         "w_up": bf(whole["layers.0.experts.w_up"])[:, cols],
                         "w_down": bf(whole["layers.0.experts.w_down"])[cols, :]}}
    flat = {"layers.0." + k: np.asarray(v, np.float32) for k, v in (
        ("router", layer["router"]), ("router_bias", layer["router_bias"]),
        ("experts.w_gate", layer["experts"]["w_gate"]),
        ("experts.w_up", layer["experts"]["w_up"]),
        ("experts.w_down", layer["experts"]["w_down"]))}
    return cfg, layer, {k: jnp.asarray(v) for k, v in flat.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """The held parts of all shares, with the zero-compute experts' part
    (which every share computes alike) counted once, are the uncut
    reference's MoE(x)."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((24, 64)), jnp.float32)
    live = jnp.ones((24,), bool)
    _cfg, _layer, whole = expert_layer((0, 8))
    uncut = np.asarray(ref.moe(whole, "layers.0.", x, CONFIG, held=(0, 8)))
    chosen, gates = ref.route(whole, "layers.0.", x, CONFIG)
    zero_part = np.asarray(jnp.where(chosen >= 8, gates, 0.0).sum(-1, keepdims=True) * x)
    total = np.zeros_like(uncut)
    assignments = np.zeros(3, np.int64)
    for held in ((0, 2), (2, 6), (6, 8)):
        cfg, layer, _ = expert_layer(held)
        y, counts = lf.moe(cfg, layer, x, live)
        total += np.asarray(y) - zero_part
        assignments += np.asarray(counts[:3])
        assert int(counts[:3].sum()) == 24 * TOPK
        assert int(counts[0]) == int(((chosen >= held[0]) & (chosen < held[1])).sum())
        assert 0 <= int(counts[3]) <= held[1] - held[0]
    np.testing.assert_allclose(total + zero_part, uncut, rtol=2e-5, atol=2e-5)


def test_a_token_routed_only_to_zero_experts_returns_its_weighted_self():
    cfg, layer, _ = expert_layer((2, 6))
    bias = np.zeros(12, np.float32)
    bias[8:] = 10.0                     # the four zero-compute experts win every draw
    layer = dict(layer, router_bias=jnp.asarray(bias))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((5, 64)), jnp.float32)
    y, counts = lf.moe(cfg, layer, x, jnp.ones((5,), bool))
    chosen, gates = lf.route(cfg, layer, x)
    assert bool((chosen >= 8).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(gates.sum(-1, keepdims=True) * x),
                               rtol=1e-6, atol=1e-6)
    assert counts.tolist() == [0, 0, 5 * TOPK, 0, 5 * 4, 0]    # 5 rows x 4 held experts


# --- attention ------------------------------------------------------------------------------


def test_absorbed_attention_is_the_expanded_form_reassociated(models_root):
    """One sequence's last position: the decode step's absorbed form over
    the pages against the prefill's expanded form, on one set of weights."""
    decoder = lf.LongcatDecoder.load(os.path.join(models_root, "lc-tiny", "1"), "gather")
    cfg, a = decoder.cfg, decoder.params["layers"][0]["attn"][1]
    t, page, max_pages = 21, 8, 4
    x = jnp.asarray(np.random.default_rng(4).standard_normal((t, 64)), jnp.float32)
    pos = jnp.arange(t)
    cos, sin = la.rope_angles(cfg.mla, pos)
    q_nope, q_rope, latent = la.queries_and_latent(cfg.mla, a, x, cos, sin)
    expanded = la.expanded_attention(cfg.mla, a, q_nope, q_rope, latent,
                                     pos[None, :] <= pos[:, None])[-1]
    page_ids = jnp.asarray([3, 1, 5, 0])
    cache = jnp.zeros((cfg.sublayers, 7, page, cfg.cache_width), jnp.float32)
    cache = cache.at[1, page_ids[pos // page], pos % page].set(latent)
    absorbed = la.absorbed_attention(cfg.mla, a, q_nope[-1:], q_rope[-1:], cache, 1,
                                     page_ids[None, :max_pages], jnp.asarray([t]), "gather")[0]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=2e-5, atol=2e-5)


def _kernel_case(max_pages, lengths, seed=None):
    """Operands of one call: pages in any order through the page table."""
    rng = np.random.default_rng(max_pages if seed is None else seed)
    slots, heads, width, page = len(lengths), 8, 128, 16
    pool = 1 + slots * max_pages
    cache = jnp.asarray(rng.standard_normal((2, pool, page, width)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((slots, heads, width)) * 0.3, jnp.bfloat16)
    table = rng.permutation(np.arange(1, pool)).reshape(slots, max_pages).astype(np.int32)
    return q, cache, 1, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("max_pages, lengths", [
    (8, [40, 128, 0, 1]), (32, [500, 17, 256, 129]), (6, [96, 95, 1, 33]),
    # what a pipeline that runs on from slot to slot can get wrong (64 pages
    # a slot: up to four chunks of 256 positions)
    (64, [0, 77, 600]),                 # the first slot idle
    (64, [300, 0, 0, 31, 1024]),        # two idle slots in a row between live ones
    (64, [128, 700, 0]),                # the last slot idle
    (8, [0, 0, 0]),                     # every slot idle
    (64, [697]),                        # one slot alone
    (64, [256, 512, 768, 1024]),        # lengths that are whole chunks
    (64, [9, 1024, 300, 2]),            # one chunk, then many, then fewer
    (64, [1000, 3, 0, 700, 255, 257]),  # many chunks, then one; odd and even counts
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else str(v))
def test_the_kernel_is_the_gather(max_pages, lengths):
    """Pages in any order through the page table, several chunks a slot, an
    idle slot, a context that ends inside a page; the next slot's first
    chunk in flight while the last one's is scored."""
    args = _kernel_case(max_pages, lengths)
    want = mla_decode.paged_mla_attention(*args, rank=64, impl="gather")
    got = mla_decode.paged_mla_attention(*args, rank=64, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)
    assert not np.asarray(got)[np.asarray(lengths) == 0].any()
    assert mla_decode.pages_per_chunk(max_pages) in (8, 16, 6)


@pytest.mark.parametrize("order", [[5, 4, 3, 2, 1, 0], [2, 0, 5, 1, 4, 3]],
                         ids=["reversed", "shuffled"])
def test_a_slots_row_does_not_depend_on_its_neighbours(order):
    """The same slots in another order give the same rows bit for bit:
    nothing of one slot's buffer, or of its place in the pipeline (which
    buffer of the ring its first chunk lands in), reaches its result."""
    q, cache, sub, table, lengths = _kernel_case(64, [130, 0, 7, 600, 256, 1000], seed=33)
    got = np.asarray(mla_decode.paged_mla_attention(
        q, cache, sub, table, lengths, rank=64, impl="interpret"))
    order = np.asarray(order)
    moved = np.asarray(mla_decode.paged_mla_attention(
        q[order], cache, sub, table[order], lengths[order], rank=64, impl="interpret"))
    np.testing.assert_array_equal(moved, got[order])


# --- the lane's counters ----------------------------------------------------------------------


def test_assignments_sum_to_topk_times_live_slots_times_expert_layers(models_root):
    engine = make_engine(models_root, "gather")
    registry = metrics_lib.Registry()
    scheduler = decode_lib.DecodeScheduler(engine, registry=registry)
    scheduler.start()
    try:
        rng = np.random.default_rng(9)
        gens = [scheduler.submit(None, n, token_ids=rng.integers(0, 64, p).tolist(),
                                 ignore_eos=True, top_logits=4)
                for p, n in ((7, 9), (20, 5))]
        events = [list(g.iter_events(timeout_s=120.0)) for g in gens]
    finally:
        scheduler.close()
    assert [len(e) for e in events] == [10, 6] and events[0][-1] == ("done", "length")
    assert all(ev[4][0] == ev[2] and len(ev[5]) == 4 for ev in events[0][:-1])
    page = registry.render()
    series = {}
    for line in page.splitlines():
        if line.startswith("kdlt_decode_"):
            name = line.split("{", 1)[0].split(" ", 1)[0]
            series[name] = series.get(name, 0.0) + float(line.rsplit(" ", 1)[1])
    rows = (9 - 1) + (5 - 1)            # slot-steps decoded (prefills are not counted)
    assigned = sum(series[f"kdlt_decode_expert_{k}_assignments_total"]
                   for k in ("held", "absent", "zero"))
    assert assigned == TOPK * rows * EXPERT_LAYERS
    assert series["kdlt_decode_tokens_total"] == 14
    assert series["kdlt_decode_prefill_prompt_tokens_total"] == 27
    assert (series["kdlt_decode_prefill_padded_tokens_total"]
            - series["kdlt_decode_prefill_tokens_total"]) == (16 - 7) + (32 - 20)
    assert 0 < series["kdlt_decode_experts_touched_total"] <= 4 * EXPERT_LAYERS * (
        series["kdlt_decode_steps_total"])
    # a step reads every live slot's context, the consumed token included
    assert series["kdlt_decode_context_positions_total"] == (
        sum(7 + j for j in range(1, 9)) + sum(20 + j for j in range(1, 5)))


# --- the wire, through the model server booted with the lane alone ---------------------------


@pytest.fixture(scope="module")
def lane_server(models_root):
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    env = {"KDLT_DECODE_MODEL": "lc-tiny", "KDLT_DECODE_SLOTS": "3",
           "KDLT_DECODE_PAGE_SIZE": "8", "KDLT_DECODE_MAX_PAGES": "8",
           "KDLT_DECODE_PROMPT_BUCKETS": "16,32"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        server = ModelServer(models_root, port=0, host="127.0.0.1", decode=True)
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
    server.warmup()
    server.start()
    yield server
    server.shutdown()


def post(server, body, model="lc-tiny"):
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
    try:
        conn.request("POST", f"/v1/models/{model}:generate", body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def get_json(server, path):
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def test_the_server_boots_with_the_lane_alone_and_shows_its_decode_block(lane_server):
    status, page = get_json(lane_server, "/v1/models")
    assert status == 200 and list(page) == ["lc-tiny"]
    lane = page["lc-tiny"]["decode"]
    assert {k: lane[k] for k in ("slots", "page_size", "max_pages", "prompt_buckets",
                                 "vocab_size", "held_experts")} == {
        "slots": 3, "page_size": 8, "max_pages": 8, "prompt_buckets": [16, 32],
        "vocab_size": 64, "held_experts": [2, 6]}
    assert lane["cache_bytes"] == 4 * 25 * 8 * 128 * 4 and lane["family"] == "longcat_flash"
    status, one = get_json(lane_server, "/v1/models/lc-tiny:status")
    assert status == 200 and one == page["lc-tiny"]
    assert lane_server.ready and not lane_server.models


@pytest.mark.parametrize("k", [0, 8])
def test_token_frames_carry_the_top_logits_asked_for(lane_server, reference_weights, k):
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    status, raw = post(lane_server, {"token_ids": prompt, "max_new_tokens": 12,
                                     "ignore_eos": True, "top_logits": k, "stream": True})
    assert status == 200
    *frames, done = protocol.parse_sse_events(raw)
    assert done["done"] is True and done["finish_reason"] == "length" and done["tokens"] == 12
    assert [f["index"] for f in frames] == list(range(12))
    served = [f["token"] for f in frames]
    full = reference_logits(reference_weights, prompt, served)
    assert full.argmax(axis=1).tolist() == served       # the model saw the ids as they are
    if k == 0:
        assert all("top_ids" not in f for f in frames)
    else:
        assert all(len(f["top_ids"]) == k and f["top_ids"][0] == f["token"]
                   and f["top_logits"] == sorted(f["top_logits"], reverse=True)
                   for f in frames)
        assert stream_error(full, [f["top_ids"] for f in frames],
                            [f["top_logits"] for f in frames]) < 2e-5


@pytest.mark.parametrize("body, why", [
    ({"token_ids": [1, 2], "prompt": "x"}, "exactly one"),
    ({"max_new_tokens": 4}, "exactly one"),
    ({"token_ids": [1, 64]}, "outside the vocabulary"),
    ({"token_ids": [1, -2]}, "non-negative"),
    ({"token_ids": []}, "non-empty"),
    ({"token_ids": [1, 2], "top_logits": 33}, "top_logits"),
    ({"token_ids": [1, 2], "top_logits": -1}, "top_logits"),
    ({"prompt": "text"}, "no text codec"),
    ({"token_ids": [1] * 33}, "bucket"),
    ({"token_ids": [1] * 30, "max_new_tokens": 40}, "context"),
])
def test_a_malformed_generate_body_is_a_400(lane_server, body, why):
    status, raw = post(lane_server, body)
    assert status == 400 and why in json.loads(raw)["error"]


def test_the_toy_stays_the_default_and_ignore_eos_decodes_past_eos():
    """No artifact named: the byte-level toy, text prompts and token ids
    alike; a stream that stops at EOS goes on to its length with
    ``ignore_eos``."""
    engine = decode_lib.DecodeEngine("gen-default", seed=11, max_slots=2)
    assert engine.decoder.family == "toy" and engine.status()["vocab_size"] == 258
    scheduler = decode_lib.DecodeScheduler(engine)
    scheduler.start()
    try:
        by_text = scheduler.submit("a toy prompt", 8)
        text_tokens = [ev[2] for ev in by_text.iter_events(60.0) if ev[0] == "token"]
        by_ids = scheduler.submit(None, 8, token_ids=decode_lib.encode_prompt("a toy prompt"))
        id_tokens = [ev[2] for ev in by_ids.iter_events(60.0) if ev[0] == "token"]
        assert text_tokens == id_tokens and len(id_tokens) >= 1
        # a prompt whose greedy stream meets EOS early, if this toy has one
        stopped = None
        for word in ("a", "b", "c", "d", "e", "f", "g", "h", "toy", "tpu", "xyz", "12"):
            g = scheduler.submit(word, 24)
            events = list(g.iter_events(60.0))
            if events[-1] == ("done", decode_lib.FINISH_STOP):
                stopped = (word, len(events) - 1)
                break
        if stopped is not None:
            word, n = stopped
            g = scheduler.submit(word, 24, ignore_eos=True)
            events = list(g.iter_events(60.0))
            assert events[-1] == ("done", decode_lib.FINISH_LENGTH) and len(events) == 25
            assert events[n - 1][2] == decode_lib.EOS_TOKEN
        with pytest.raises(ValueError):
            scheduler.submit(None, 4, token_ids=[1, 258])
    finally:
        scheduler.close()


def test_prompt_buckets_come_from_the_environment(monkeypatch):
    monkeypatch.setenv("KDLT_DECODE_PROMPT_BUCKETS", "8, 24,16")
    assert decode_lib.env_prompt_buckets() == (8, 16, 24)
    engine = decode_lib.DecodeEngine("gen-default", max_slots=1, max_pages_per_seq=2)
    assert engine.prompt_buckets == (8, 16, 24) and engine.status()["prompt_buckets"] == [8, 16, 24]
    monkeypatch.setenv("KDLT_DECODE_PROMPT_BUCKETS", "many")
    assert decode_lib.env_prompt_buckets() is None
    assert decode_lib.DecodeEngine("gen-default", max_slots=1).prompt_buckets == (16, 32, 64)


def test_the_image_registry_passes_a_decoder_artifact_by(models_root):
    from kubernetes_deep_learning_tpu.serving.registry import iter_latest_versions

    assert iter_latest_versions(models_root) == []
    assert decode_lib.load_decoder(models_root, "no-such-model") is None
    assert decode_lib.load_decoder(None, "lc-tiny") is None


# --- the served logits are the parent's, bit for bit ----------------------------------------


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_served_logits_are_bit_for_bit_what_they_were(tmp_path, compute_dtype):
    """``tests/golden/longcat_lane_logits.json`` holds what the lane served
    at this size on seed 7 before the latent-attention code moved into
    ``models/latent_attention.py`` and the prefill learned chunks (PR 34,
    written by the parent commit's code): a prefill of 13 tokens and six
    steps, the eight largest logits of each and their ids, as float32 bits."""
    with open(os.path.join(os.path.dirname(__file__), "golden",
                           "longcat_lane_logits.json")) as f:
        golden = json.load(f)[compute_dtype]
    root = str(tmp_path)
    write_artifact(root, compute_dtype=compute_dtype)
    engine = make_engine(root, "gather")
    prompt = golden["prompt"]
    assert prompt == np.random.default_rng(11).integers(0, 64, 13).tolist()
    slot = engine.acquire_slot(len(prompt) + 8)
    out = engine.materialize(engine.prefill(slot, prompt))
    ids, logits = [out.top_ids[0]], [out.top_logits[0]]
    for _ in range(6):
        out = engine.materialize(engine.step_async())
        ids.append(out.top_ids[slot])
        logits.append(out.top_logits[slot])
    assert np.asarray(ids)[:, :8].tolist() == golden["top_ids"]
    bits = np.asarray(logits, np.float32)[:, :8].view(np.uint32)
    assert bits.tolist() == golden["top_logit_bits"]


# --- a chunk and a step as one program, against the two programs ------------------------


# (prompt tokens, the start of the chunk under test) at chunks of 16 rows
ROUND_CASES = [(10, 0), (40, 0), (40, 16), (40, 32)]
ROUND_IDS = ["first-and-last", "first-of-three", "later", "later-and-last"]
# |one program - two| over the largest |logit| of the two; over the largest
# |value| a written cache row holds.  float32: rounding alone (the head's
# product runs at 1 + S rows in place of 1; on the CPU the widest reading is
# 4e-7); bfloat16: a value rounded to its 8 bits may land one step (2^-8)
# the other way.
ROUND_TOLERANCE = {"float32": 2e-6, "bfloat16": 2 ** -8}


def round_against_chunk_then_step(engine, n, start, live):
    """``live`` streams a few steps into their decode and a prompt of ``n``
    tokens prefilled up to ``start``; from that state the chunk at ``start``
    and the live slots' step run once as two programs (the chunk, then the
    step without the prompt's slot) and once as one (``round_async``).
    Returns each side's (chunk output, step output, cache, next tokens,
    lengths, active), the prompt's slot and the (page, offset) rows the
    programs wrote."""
    rng = np.random.default_rng(n + 7 * start + 31 * live)
    slots = []
    for size in (11, 5)[:live]:
        slot = engine.acquire_slot(size + 8)
        engine.materialize(engine.prefill(slot, rng.integers(0, 64, size).tolist()))
        slots.append(slot)
    for _ in range(2):
        engine.materialize(engine.step_async())
    prompt = rng.integers(0, 64, n).tolist()
    slot = engine.acquire_slot(n + 4)
    at = 0
    while at < start:
        out, rows, _ = engine.prefill_chunk_async(slot, prompt, at)
        engine.materialize(out)
        at += rows
    rows = engine.chunk_at(n, start)[0]
    pages = engine.page_table
    written = {(int(pages[slot, p // engine.page_size]), p % engine.page_size)
               for p in range(start, start + rows)}
    written |= {(int(pages[s, engine.lengths[s] // engine.page_size]),
                 int(engine.lengths[s] % engine.page_size)) for s in slots}
    state = (engine._cache, engine._next_tokens, engine.lengths.copy(), engine.active.copy())
    sides = []
    try:
        # two programs: the chunk, then the step of the slots live before it
        handle, _, _ = engine.prefill_chunk_async(slot, prompt, start)
        chunk_out = engine.materialize(handle)
        joined, engine.active[slot] = bool(engine.active[slot]), False
        step_out = engine.materialize(engine.step_async())
        engine.active[slot] = joined
        sides.append((chunk_out, step_out, np.asarray(engine._cache),
                      np.asarray(engine._next_tokens), engine.lengths.copy(),
                      engine.active.copy()))
        engine._cache, engine._next_tokens = state[:2]
        engine.lengths, engine.active = state[2].copy(), state[3].copy()
        # one program
        handle, _, _ = engine.round_async(slot, prompt, start)
        chunk_out, step_out = engine.materialize_round(handle)
        sides.append((chunk_out, step_out, np.asarray(engine._cache),
                      np.asarray(engine._next_tokens), engine.lengths.copy(),
                      engine.active.copy()))
    finally:
        for s in (*slots, slot):
            engine.release_slot(s)
    return sides, slot, slots, written


def assert_one_program_is_the_two(engine, dtype, n, start, live, step_form_differs=False):
    """``step_form_differs``: the step alone computes its experts' products
    in another form than the round does, so that the one count that says
    what the form computed (the fifth) is left to the caller."""
    (two, one), slot, slots, written = round_against_chunk_then_step(engine, n, start, live)
    tol = ROUND_TOLERANCE[dtype]
    (chunk_2, step_2, cache_2, next_2, lengths_2, active_2) = two
    (chunk_1, step_1, cache_1, next_1, lengths_1, active_1) = one
    # the chunk's logits and the live slots' (its greedy token, then its top logits)
    scale = float(np.abs(chunk_2.top_logits[0]).max())
    assert chunk_1.top_ids[0, 0] == chunk_2.top_ids[0, 0]
    assert float(np.abs(chunk_1.top_logits[0] - chunk_2.top_logits[0]).max()) <= tol * scale
    for s in slots:
        scale = float(np.abs(step_2.top_logits[s]).max())
        assert step_1.top_ids[s, 0] == step_2.top_ids[s, 0]
        assert float(np.abs(step_1.top_logits[s] - step_2.top_logits[s]).max()) <= tol * scale
    # every part's counts are the counts of its own program
    assert chunk_1.counts.tolist() == chunk_2.counts.tolist()
    same = [i for i in range(decode_lib.N_COUNTS) if not (step_form_differs and i == 4)]
    assert step_1.counts[same].tolist() == step_2.counts[same].tolist()
    # the token each slot consumes next, and the host's tables
    assert next_1.tolist() == next_2.tolist()
    assert lengths_1.tolist() == lengths_2.tolist() and active_1.tolist() == active_2.tolist()
    # the cache: the rows the programs wrote within the tolerance; every other
    # row of every page but the trash page (padding and idle slots) bit for bit
    mask = np.zeros(cache_1.shape[1:3], bool)
    for page, offset in written:
        mask[page, offset] = True
    f1, f2 = cache_1.astype(np.float32), cache_2.astype(np.float32)
    rows_1, rows_2 = f1[:, mask], f2[:, mask]
    assert float(np.abs(rows_1 - rows_2).max()) <= tol * float(np.abs(rows_2).max())
    mask[0] = True
    assert np.array_equal(cache_1[:, ~mask], cache_2[:, ~mask])
    return two, one


@pytest.fixture(scope="module")
def round_engines(tmp_path_factory):
    """The decoder at both precisions at chunks of 16 rows, buffers not
    donated (each test runs two sides from one state)."""
    saved, decode_lib.PREFILL_CHUNK = decode_lib.PREFILL_CHUNK, 16
    try:
        engines = {}
        for dtype in ROUND_TOLERANCE:
            root = str(tmp_path_factory.mktemp("lc-" + dtype))
            write_artifact(root, compute_dtype=dtype)
            engines[dtype] = make_engine(root, "gather", max_pages_per_seq=10,
                                         prompt_buckets=(8, 16, 64), donate=False)
        return engines
    finally:
        decode_lib.PREFILL_CHUNK = saved


@pytest.mark.parametrize("live", [0, 1, 2], ids=lambda k: f"{k}-live")
@pytest.mark.parametrize("n, start", ROUND_CASES, ids=ROUND_IDS)
@pytest.mark.parametrize("dtype", list(ROUND_TOLERANCE))
def test_a_chunk_and_a_step_as_one_program_are_the_two_programs(round_engines, dtype, n,
                                                                start, live):
    """First and later chunks, a prompt's last and not, beside no, one and
    two live slots: the round's chunk logits, step logits, next tokens,
    counts and cache are those of the chunk's program and then the step's."""
    engine = round_engines[dtype]
    assert engine.rides
    assert_one_program_is_the_two(engine, dtype, n, start, live)
