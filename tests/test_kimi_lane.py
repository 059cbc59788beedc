"""Kimi-K2 (DeepSeek-V3's layer) on the generative lane, on the CPU at a small
size (hidden 64, one dense and two expert layers, 4 heads, 16 routed experts
of which 4 are held, top-3, one shared expert, vocabulary 64, YaRN): the
program's prefill in chunks then decode through the paged latent cache
against the plain reference's full forward (``perfbench/reference``), the
share test of the expert layer with the shared expert counted once, the
grouped against the masked product, YaRN's numbers at the published
configuration worked by hand, chunks of every rung against one program for
the lane's artifact decoders, and the artifact found by its family."""

from __future__ import annotations

import json
import math
import os

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
import test_longcat_lane as lc

from kubernetes_deep_learning_tpu.models import kimi_k2 as kk
from kubernetes_deep_learning_tpu.models import latent_attention as la
from kubernetes_deep_learning_tpu.models import longcat_flash as lf
from kubernetes_deep_learning_tpu.runtime import decode as decode_lib
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib
from perfbench import dsv3_weights
from perfbench.reference import kimi_k2 as ref

SEED = 7
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
CONFIG = {
    "served_name": "kimi-tiny", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 16,
    "qk_nope_head_dim": 16, "v_head_dim": 16, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "routed_scaling_factor": 2.827, "norm_topk_prob": True, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "rope_scaling": YARN, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
    "hidden_act": "silu", "published": {"n_routed_experts": 16}, "held_experts": [4, 8],
    "vocab_held": 64,
    "assumed": {"reference_block": 16, "weight_scales": {
        "attention_logit_std": 2.5, "residual_branch_scale": 0.5, "bias_feature": 1.0,
        "router_logit_std": 3.0, "router_logit_offset": 6.0, "router_bias_std": 1e-4,
        "norm_jitter": 0.05}},
}
# the catalog's numbers for Kimi-K2.7-Code, as far as YaRN reads them
PUBLISHED = dict(qk_rope_head_dim=64, qk_nope_head_dim=128, rope_theta=50000, rope_scaling=YARN)
TOPK = CONFIG["num_experts_per_tok"]
CHUNK = 16          # the lane's chunk size in these tests (PREFILL_CHUNK is 1,024)
SIZES = dict(max_slots=3, page_size=8, max_pages_per_seq=10, prompt_buckets=(8, 16, 64))


def write_artifact(root, config=CONFIG, compute_dtype="float32"):
    program = dict(dsv3_weights.program_config(config), compute_dtype=compute_dtype)
    shapes = kk.KimiConfig.from_dict(program).tensor_shapes()
    directory = os.path.join(root, config["served_name"], "1")
    kk.write_artifact(directory, program,
                      dsv3_weights.tensors(config, SEED, shapes, kk.tensor_dtype))
    return directory


@pytest.fixture(scope="module")
def models_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("models"))
    write_artifact(root)
    return root


@pytest.fixture(scope="module")
def reference_weights(models_root):
    return {k: jnp.asarray(v) for k, v in ref.weights(CONFIG, SEED, models_root).items()}


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(decode_lib, "PREFILL_CHUNK", CHUNK)


def make_engine(models_root, attention="gather", **kwargs):
    decoder = kk.KimiDecoder.load(os.path.join(models_root, "kimi-tiny", "1"),
                                  attention=attention)
    return decode_lib.DecodeEngine("kimi-tiny", decoder=decoder, **{**SIZES, **kwargs})


def reference_logits(weights, prompt, served):
    ids = jnp.asarray(list(prompt) + list(served[:-1]), jnp.int32)
    return np.asarray(ref.forward(weights, ids, CONFIG))[len(prompt) - 1:]


def serve(engine, prompt, budget):
    """One stream alone: its tokens, and every step's top ids and logits."""
    slot = engine.acquire_slot(len(prompt) + budget)
    try:
        out = engine.materialize(engine.prefill(slot, prompt))
        tokens, ids, logits = [int(out.tokens[0])], [out.top_ids[0]], [out.top_logits[0]]
        while len(tokens) < budget:
            out = engine.materialize(engine.step_async())
            tokens.append(int(out.tokens[slot]))
            ids.append(out.top_ids[slot])
            logits.append(out.top_logits[slot])
    finally:
        engine.release_slot(slot)
    return tokens, np.asarray(ids), np.asarray(logits)


# --- prefill in chunks, then decode through the cache, against the full forward --------


@pytest.mark.parametrize("attention", ["gather", "interpret"])
@pytest.mark.parametrize("n", [5, 16, 23, 61])
def test_chunked_prefill_then_decode_matches_the_references_full_forward(
        models_root, reference_weights, small_chunks, attention, n):
    """Prompts of one chunk, of one full chunk, of a chunk and a rest, and
    of three chunks and a rest: every step's top logits are the full
    forward's at that position."""
    engine = make_engine(models_root, attention)
    assert engine.chunk_shapes == (8, 16) and engine.chunked
    prompt = np.random.default_rng(n).integers(0, 64, n).tolist()
    tokens, ids, logits = serve(engine, prompt, 10)
    full = reference_logits(reference_weights, prompt, tokens)
    assert lc.stream_error(full, ids, logits) < 2e-5
    assert full.argmax(axis=1).tolist() == tokens


def test_bfloat16_as_served_stays_near_the_reference(tmp_path, reference_weights,
                                                     small_chunks):
    root = str(tmp_path)
    write_artifact(root, compute_dtype="bfloat16")
    engine = make_engine(root)
    prompt = np.random.default_rng(5).integers(0, 64, 37).tolist()
    served = engine.decode_solo(prompt, 20)
    full = reference_logits(reference_weights, prompt, served)
    best = full.max(axis=1) - full[np.arange(20), served]
    assert float(best.max() / np.abs(full).max()) < 0.05


def test_the_reference_a_layer_at_a_time_is_its_forward(reference_weights):
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 64, 40), jnp.int32)
    h = ref.embed(reference_weights, ids)
    for i in range(CONFIG["num_hidden_layers"]):
        h = ref.layer(reference_weights, i, h, CONFIG)
    np.testing.assert_allclose(np.asarray(ref.head(reference_weights, h, CONFIG)),
                               np.asarray(ref.forward(reference_weights, ids, CONFIG)),
                               rtol=1e-6, atol=1e-6)
    # in blocks of 16 keys or all 40 at once: the same softmax
    whole = dict(CONFIG, assumed=dict(CONFIG["assumed"], reference_block=64))
    np.testing.assert_allclose(np.asarray(ref.forward(reference_weights, ids, whole)),
                               np.asarray(ref.forward(reference_weights, ids, CONFIG)),
                               rtol=2e-5, atol=2e-5)


# --- chunks of every rung against one program, for the lane's artifact decoders ---------


def _one_program_and_chunked(make, monkeypatch, n):
    """The logits of a prompt's first token and of four steps after it, the
    prompt prefilled by one program and in chunks of ``CHUNK``."""
    prompt = np.random.default_rng(n).integers(0, 64, n).tolist()
    out = []
    for chunk in (decode_lib.PREFILL_CHUNK, CHUNK):
        monkeypatch.setattr(decode_lib, "PREFILL_CHUNK", chunk)
        engine = make()
        assert engine.chunked == (chunk == CHUNK)
        out.append(serve(engine, prompt, 5))
    return out


@pytest.mark.parametrize("n", [17, 24, 32, 40, 56, 64], ids=lambda n: f"{n}-tokens")
@pytest.mark.parametrize("family", ["kimi", "longcat"])
def test_a_prompt_in_chunks_gives_the_logits_of_one_program(
        models_root, tmp_path_factory, monkeypatch, family, n):
    """A rest in every rung (1..8 and 9..16 rows) after one, two and three
    full chunks.  float32: the chunks' running softmax against one softmax
    differ by rounding alone (2e-5 of the largest logit)."""
    if family == "kimi":
        make = lambda: make_engine(models_root)                           # noqa: E731
    else:
        root = str(tmp_path_factory.mktemp("lc"))
        lc.write_artifact(root)
        make = lambda: lc.make_engine(root, "gather", max_pages_per_seq=10,   # noqa: E731
                                      prompt_buckets=(8, 16, 64))
    (tokens, ids, logits), (tokens_c, ids_c, logits_c) = _one_program_and_chunked(
        make, monkeypatch, n)
    assert tokens_c == tokens and (ids_c[:, 0] == ids[:, 0]).all()
    scale = float(np.abs(logits).max())
    assert float(np.abs(np.sort(logits_c, axis=1) - np.sort(logits, axis=1)).max()) < 2e-5 * scale


# --- the expert layer ----------------------------------------------------------------------


def expert_layer(held):
    """The program's layer-1 expert weights for the routed experts [lo, hi)
    (the whole layer's draws, cut), and the reference's view of them."""
    program = dict(dsv3_weights.program_config(dict(CONFIG, held_experts=[0, 16])),
                   compute_dtype="float32")
    shapes = {k: v for k, v in kk.KimiConfig.from_dict(program).tensor_shapes().items()
              if k.startswith("layers.1.") and "attn" not in k and "norm" not in k}
    whole = dict(dsv3_weights.tensors(CONFIG, SEED, shapes, kk.tensor_dtype))
    bf = lambda a: jnp.asarray(a.view(ml_dtypes.bfloat16))       # noqa: E731
    lo, hi = held
    layer = {"router": bf(whole["layers.1.router"]),
             "router_bias": jnp.asarray(whole["layers.1.router_bias"]),
             "experts": {k: bf(whole[f"layers.1.experts.{k}"])[lo:hi]
                         for k in ("w_gate", "w_up", "w_down")},
             "shared": {k: bf(whole[f"layers.1.shared.{k}"])
                        for k in ("w_gate", "w_up", "w_down")}}
    flat = {"layers.1.router": layer["router"], "layers.1.router_bias": layer["router_bias"]}
    for group in ("experts", "shared"):
        for k, v in layer[group].items():
            flat[f"layers.1.{group}.{k}"] = v
    cfg = kk.KimiConfig.from_dict(dict(program, held_experts=list(held)))
    return cfg, layer, {k: jnp.asarray(np.asarray(v, np.float32)) for k, v in flat.items()}


@pytest.mark.parametrize("grouped", [False, True], ids=["masked", "grouped"])
def test_the_shares_add_up_to_the_uncut_layer(grouped):
    """The routed parts of all four shares, with the shared expert (which
    every share computes alike) counted once, are the uncut reference's
    expert layer."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((24, 64)), jnp.float32)
    live = jnp.ones((24,), bool)
    _cfg, _layer, whole = expert_layer((0, 16))
    uncut = np.asarray(ref.moe(whole, "layers.1.", x, CONFIG, held=(0, 16)))
    chosen, _gates = ref.route(whole, "layers.1.", x, CONFIG)
    shared = np.asarray(ref.moe(whole, "layers.1.", x, CONFIG, held=(0, 0)))
    total, held_total = np.zeros_like(uncut), 0
    for held in ((0, 4), (4, 8), (8, 12), (12, 16)):
        cfg, layer, _ = expert_layer(held)
        y, counts = kk.moe(cfg, layer, x, live, grouped=grouped)
        total += np.asarray(y) - shared
        assert int(counts[0]) + int(counts[1]) == 24 * TOPK and int(counts[2]) == 0
        assert int(counts[0]) == int(((chosen >= held[0]) & (chosen < held[1])).sum())
        assert 0 <= int(counts[3]) <= 4 and int(counts[5]) == 24
        # the masked form computes every row for every held expert; the grouped
        # one the routed rows, rounded up to whole tiles an expert it touched
        assert int(counts[4]) == (int(counts[3]) * kk.GROUP_TILE if grouped else 24 * 4)
        held_total += int(counts[0])
    assert held_total == 24 * TOPK
    np.testing.assert_allclose(total + shared, uncut, rtol=2e-5, atol=2e-5)


def test_the_grouped_product_is_the_masked_one_at_several_tiles_an_expert():
    """300 rows on 4 held experts with a tile of 32: experts with several
    tiles, a last tile part full, an expert nobody chose."""
    cfg, layer, _ = expert_layer((4, 8))
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((300, 64)), jnp.float32)
    chosen, weights = kk.route(cfg, layer, x)
    index = jnp.where(chosen - 4 == 2, 99, chosen - 4)         # nobody reaches held expert 2
    hit = index[:, :, None] == jnp.arange(4)
    per_expert = jnp.where(hit, weights[:, :, None], 0.0).sum(axis=1)
    masked = kk.masked_experts(cfg, layer["experts"], x, per_expert)
    grouped, computed = kk.grouped_experts(cfg, layer["experts"], x, index, weights, tile=32)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(masked), rtol=2e-5, atol=2e-5)
    counts = np.asarray(hit.sum(axis=(0, 1)))
    assert counts[2] == 0 and counts.max() > 32
    assert int(computed) == int((-(-counts // 32) * 32).sum())


def test_the_router_is_a_renormalised_sigmoid_with_a_selection_bias():
    cfg, layer, whole = expert_layer((4, 8))
    x = jnp.asarray(np.random.default_rng(3).standard_normal((9, 64)), jnp.float32)
    bias = np.zeros(16, np.float32)
    bias[11] = 10.0                       # wins every draw, whatever its score
    layer = dict(layer, router_bias=jnp.asarray(bias))
    chosen, weights = kk.route(cfg, layer, x)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x) @ np.asarray(whole["layers.1.router"])))
    assert bool((chosen == 11).any(axis=1).all())
    picked = np.take_along_axis(s, np.asarray(chosen), axis=1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(axis=1, keepdims=True) * 2.827, rtol=1e-5)
    # the bias chose expert 11; its weight is its own score's share, not the bias's
    at = np.asarray(chosen) == 11
    np.testing.assert_allclose(np.asarray(weights)[at],
                               (s[:, 11] / picked.sum(axis=1) * 2.827), rtol=1e-5)


def test_options_the_module_does_not_compute_are_refused():
    program = dsv3_weights.program_config(CONFIG)
    for key, value in (("scoring_func", "softmax"), ("n_group", 8), ("topk_method", "greedy")):
        with pytest.raises(ValueError, match=key):
            kk.KimiConfig.from_dict(dict(program, **{key: value}))
    with pytest.raises(ValueError, match="yarn"):
        kk.KimiConfig.from_dict(dict(program, rope_scaling={"type": "linear", "factor": 2}))


# --- YaRN at the published configuration, worked by hand ---------------------------------


def test_yarn_frequencies_and_scale_are_the_published_configurations():
    """rope 64, theta 50,000, factor 64, original context 4,096:
    d(32) = 64 ln(4096 / (2 pi 32)) / (2 ln 50000) = 8.91 -> low 8;
    d(1) = 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> high 20.
    Frequencies 0..8 stay, 20..31 are divided by 64, 9..19 are blended by
    (i - 8) / 12; m = 0.1 ln 64 + 1 = 1.41589, scale = 192 ** -0.5 * m ** 2."""
    yarn = la.Yarn.from_dict(YARN)
    keep = yarn.keep_mask(64, 50000.0)
    want = 1.0 - np.clip((np.arange(32) - 8) / 12.0, 0.0, 1.0)
    np.testing.assert_allclose(keep, want, atol=1e-7)
    assert keep[8] == 1.0 and keep[20] == 0.0 and abs(keep[14] - 0.5) < 1e-7
    spec = kk.KimiConfig.from_dict(dict(
        dsv3_weights.program_config(CONFIG), **PUBLISHED)).mla
    _cos, sin = la.rope_angles(spec, jnp.asarray([1]))
    inv = np.arcsin(np.asarray(sin[0], np.float64))     # position 1: the angle, at most 1
    base = 50000.0 ** (-np.arange(32) / 32.0)
    hand = base / 64.0 * (1 - want) + base * want
    np.testing.assert_allclose(inv, hand, rtol=1e-5)
    assert abs(hand[0] - 1.0) < 1e-12 and abs(hand[31] - 50000.0 ** (-31 / 32) / 64) < 1e-12
    assert abs(hand[14] - 50000.0 ** (-14 / 32) * (0.5 + 0.5 / 64)) < 1e-12
    np.testing.assert_allclose(ref.yarn_inv_freq(PUBLISHED), hand, rtol=1e-12)
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.41589) < 1e-5 and yarn.rotation_mscale == 1.0
    assert abs(spec.score_scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(spec.score_scale - 0.14468) < 1e-5      # 0.0721688 * 2.00474
    assert abs(ref.softmax_scale(PUBLISHED) - spec.score_scale) < 1e-12


# --- the artifact is found by its family ---------------------------------------------------


def test_load_decoder_finds_the_decoder_by_the_artifacts_family(models_root, tmp_path):
    decoder = decode_lib.load_decoder(models_root, "kimi-tiny")
    assert isinstance(decoder, kk.KimiDecoder) and decoder.describe()["family"] == "kimi_k2"
    root = str(tmp_path)
    lc.write_artifact(root)
    assert isinstance(decode_lib.load_decoder(root, "lc-tiny"), lf.LongcatDecoder)
    # a family no module of models/ serves, and one that is no module's name
    for family in ("no_such_family", "../kimi_k2", "resnet"):
        path = os.path.join(lc.write_artifact(str(tmp_path / family.strip("./"))),
                            "decoder.json")
        with open(path) as f:
            meta = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(meta, family=family), f)
        with pytest.raises(ValueError, match="no decoder of family"):
            decode_lib.load_decoder(os.path.dirname(os.path.dirname(os.path.dirname(path))),
                                    "lc-tiny")


def test_the_lane_shows_the_decoder_and_counts_chunks_and_the_shared_expert(
        models_root, small_chunks):
    """Through the scheduler: a prompt of three chunks and one of one, the
    counters of chunks, of true and computed positions, of the experts'
    rows and of the shared expert's tokens."""
    registry = metrics_lib.Registry()
    engine = make_engine(models_root)
    lane = engine.status()
    assert lane["family"] == "kimi_k2" and lane["prefill_chunk"] == CHUNK
    assert lane["held_experts"] == [4, 8] and lane["shared_experts"] == 1
    assert lane["cache_bytes"] == 3 * 31 * 8 * 128 * 4
    scheduler = decode_lib.DecodeScheduler(engine, registry=registry)
    scheduler.start()
    try:
        rng = np.random.default_rng(4)
        gens = [scheduler.submit(None, 4, token_ids=rng.integers(0, 64, n).tolist(),
                                 ignore_eos=True) for n in (37, 6)]
        for g in gens:
            assert list(g.iter_events(60.0))[-1] == ("done", decode_lib.FINISH_LENGTH)
    finally:
        scheduler.close()
    series = {}
    for line in registry.render().splitlines():
        if line.startswith("kdlt_decode_"):
            name = line.split("{", 1)[0].split(" ", 1)[0]
            series[name] = series.get(name, 0.0) + float(line.rsplit(" ", 1)[1])
    assert series["kdlt_decode_prefill_chunks_total"] == 4          # 16 + 16 + 5, and 6
    assert series["kdlt_decode_prefill_seconds_count"] == 4
    assert series["kdlt_decode_prefill_tokens_total"] == 43
    assert series["kdlt_decode_prefill_padded_tokens_total"] == 16 + 16 + 8 + 8
    assert series["kdlt_decode_prefill_prompt_tokens_total"] == 43
    assert series["kdlt_decode_prefill_padding_tokens_total"] == 5
    # the masked form at these rows: every computed row through 4 held experts, 2 layers
    assert series["kdlt_decode_prefill_expert_rows_total"] == 48 * 4 * 2
    assert 0 <= series["kdlt_decode_prefill_routed_rows_total"] <= 43 * TOPK * 2
    # the shared expert: every true prompt position and every decoded token, 2 layers
    assert series["kdlt_decode_shared_expert_tokens_total"] == (43 + 2 * 3) * 2
