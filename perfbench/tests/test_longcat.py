"""LongCat-Flash in the benchmark, on the CPU: the plain reference against a
hand-written einsum of one layer and its layer-at-a-time child against its
``forward``; the appended ``BENCHMARK.json``; ``lm_flops`` against hand
arithmetic; the readers over a made-up run; and whole rehearsed runs of the
configuration at toy widths through the program's own model server from a
scratch manifest (``lctoy/``): ``correct``, and not ``correct`` with the
timed path broken where an answer is produced."""

import json
import math
import os

import numpy as np
import pytest

from perfbench import lm_flops, lm_weights
from perfbench import manifest as M
from perfbench import reference
from perfbench.tests.lctoy import build as lctoy

REAL = "longcat-flash-chat-ep32"
CELL = "longcat-agent-decode-closed128"


@pytest.fixture(scope="module")
def real_config():
    with open(os.path.join(M.ROOT, "perfbench", "configs", REAL + ".json")) as f:
        return json.load(f)


# --- the reference ---------------------------------------------------------------------


def toy_weights(config, seed=5):
    """Every tensor of the toy as float32, by the artifact's names, made as
    the artifact child makes them but never written."""
    from kubernetes_deep_learning_tpu.models import longcat_flash as lf

    shapes = lf.LongcatConfig.from_dict(lm_weights.program_config(config)).tensor_shapes()
    out = {}
    for name, value in lm_weights.tensors(config, seed, shapes, lf.tensor_dtype):
        if value.dtype == np.uint16:
            value = (value.astype(np.uint32) << 16).view(np.float32)
        out[name] = value
    return out


def test_one_layer_is_the_hand_written_einsum():
    """The whole layer written again from the published equations, in
    numpy and float64, token by token where the reference is batched."""
    import jax.numpy as jnp

    family = reference.load("longcat_flash")
    c = dict(lctoy.CONFIG, held_experts=[0, 8])         # every real expert held
    w = toy_weights(c)
    t, d, heads = 9, c["hidden_size"], c["num_attention_heads"]
    nope, rope, rank = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["kv_lora_rank"]
    h = np.random.default_rng(0).standard_normal((t, d))
    w64 = {k: np.asarray(v, np.float64) for k, v in w.items()}

    def rms(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + c["rms_norm_eps"]) * g

    def turn(x, pos):          # pairs (2i, 2i+1) by pos * theta ** (-2i / dim)
        out = x.copy()
        for i in range(x.shape[-1] // 2):
            a = pos * c["rope_theta"] ** (-2.0 * i / x.shape[-1])
            out[..., 2 * i] = x[..., 2 * i] * math.cos(a) - x[..., 2 * i + 1] * math.sin(a)
            out[..., 2 * i + 1] = x[..., 2 * i] * math.sin(a) + x[..., 2 * i + 1] * math.cos(a)
        return out

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def swiglu(x, p, cols=slice(None)):
        return (silu(x @ w64[p + "w_gate"][:, cols]) * (x @ w64[p + "w_up"][:, cols])) \
            @ w64[p + "w_down"][cols, :]

    def mla(x, p):
        q = rms(x @ w64[p + "wq_a"], w64[p + "q_norm"]) @ w64[p + "wq_b"]
        q = (q * math.sqrt(d / c["q_lora_rank"])).reshape(t, heads, nope + rope)
        ckr = x @ w64[p + "wkv_a"]
        c_kv = rms(ckr[:, :rank], w64[p + "kv_norm"]) * math.sqrt(d / rank)
        out = np.zeros((t, heads, c["v_head_dim"]))
        for i in range(t):
            for hd in range(heads):
                scores = []
                for j in range(i + 1):
                    k_nope = c_kv[j] @ w64[p + "w_uk"][hd]
                    scores.append((q[i, hd, :nope] @ k_nope
                                   + turn(q[i, hd, nope:], i) @ turn(ckr[j, rank:], j))
                                  / math.sqrt(nope + rope))
                e = np.exp(np.asarray(scores) - max(scores))
                for j in range(i + 1):
                    out[i, hd] += e[j] / e.sum() * (c_kv[j] @ w64[p + "w_uv"][hd])
        return out.reshape(t, -1) @ w64[p + "wo"]

    def moe(x):
        y = np.zeros_like(x)
        for i in range(t):
            logits = x[i] @ w64["layers.0.router"]
            s = np.exp(logits - logits.max())
            s /= s.sum()
            for e in np.argsort(-(s + w64["layers.0.router_bias"]), kind="stable")[:3]:
                gate = c["routed_scaling_factor"] * s[e]
                if e >= 8:
                    y[i] += gate * x[i]
                else:
                    cols = slice(e * 32, (e + 1) * 32)
                    y[i] += gate * swiglu(x[i:i + 1], "layers.0.experts.", cols)[0]
        return y

    a0 = h + mla(rms(h, w64["layers.0.attn.0.norm"]), "layers.0.attn.0.")
    u0 = rms(a0, w64["layers.0.ffn.0.norm"])
    b0 = a0 + swiglu(u0, "layers.0.ffn.0.")
    a1 = b0 + mla(rms(b0, w64["layers.0.attn.1.norm"]), "layers.0.attn.1.")
    want = a1 + swiglu(rms(a1, w64["layers.0.ffn.1.norm"]), "layers.0.ffn.1.") + moe(u0)
    got = family.layer({k: jnp.asarray(v) for k, v in w.items()}, 0,
                       jnp.asarray(h, jnp.float32), c)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_the_layer_at_a_time_child_is_the_forward(tmp_path):
    """``children/reference_longcat.py``'s pass over an artifact on disk
    against ``forward`` over the same weights in memory."""
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import longcat_flash as lf
    from perfbench.children import reference_longcat

    family = reference.load("longcat_flash")
    c = lctoy.CONFIG
    program = lm_weights.program_config(c)
    shapes = lf.LongcatConfig.from_dict(program).tensor_shapes()
    lf.write_artifact(str(tmp_path / c["served_name"] / "1"), program,
                      lm_weights.tensors(c, 5, shapes, lf.tensor_dtype))
    ids = [np.arange(16, dtype=np.int32) % 7, (np.arange(32, dtype=np.int32) * 5) % 64]
    got = reference_longcat.run_layers(family, c, str(tmp_path), ids)
    w = {k: jnp.asarray(v) for k, v in family.weights(c, 5, str(tmp_path)).items()}
    assert set(w) == set(toy_weights(c)) and all(
        np.array_equal(np.asarray(w[k]), v) for k, v in toy_weights(c).items())
    for row, full in zip(ids, got):
        np.testing.assert_allclose(np.asarray(full),
                                   np.asarray(family.forward(w, jnp.asarray(row), c)),
                                   rtol=1e-4, atol=1e-4)


# --- the manifest and the counts ---------------------------------------------------------


def test_the_appended_manifest_validates_and_the_cell_is_the_issues(real_config):
    m = M.Manifest()
    m.validate()
    cell = m.cell(CELL)
    assert cell.chips == 1 and cell.config_name == REAL
    assert [e["name"] for e in cell.end_to_end] == ["setup_s", "latency_p50_ms"]
    mix = cell.traffic
    assert (mix["generator"], mix["entry"], mix["callers"], mix["slots"]) == (
        "closed", "server-generate", 128, 128)
    assert (mix["page_size"], mix["max_pages"], mix["prompt_buckets"], mix["pool"]) == (
        16, 96, [64, 128, 256, 512], 256)
    assert mix["prompt_tokens"] == {"choice": [[32, 128, 0.7], [129, 512, 0.3]]}
    assert mix["output_tokens"] == {"choice": [[128, 512, 0.7], [513, 1024, 0.3]]}
    assert (mix["top_logits"], mix["compare_requests"], mix["lead_in_s"]) == (8, 8, 25.0)
    names = {e["name"] for e, _ in cell.per_layer}
    assert {"step_mfu_pct.lc", "decode_step_roofline.lc", "mla_decode_roofline.lc",
            "device_idle_pct.lc", "zero_expert_share_pct.lc", "held_expert_tokens.lc",
            "decode_step_ms.lc", "prefill_ms.lc", "prefill_share_pct.lc",
            "queue_wait_ms.lc", "first_token_ms.lc"} == names
    assert all(e["moves"] == "latency_p50_ms" and e["workloads"] == [CELL]
               for e, _ in cell.per_layer)
    with open(os.path.join(M.ROOT, "BENCHMARK.json")) as f:
        entry = json.load(f)["configs"][-1]
    assert entry["reduced"] == real_config["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    # the longest stream fits a slot's pages and the largest prompt a bucket
    assert 512 + 1024 <= mix["page_size"] * mix["max_pages"]


def test_the_configuration_holds_the_published_widths(real_config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Chat")
    assert real_config["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if real_config.get(k) != v}
    assert changed == set(real_config["reduced"])
    assert (real_config["num_layers"], real_config["n_routed_experts"],
            real_config["vocab_size"]) == (4, 16, 16384)
    assert real_config["published"]["n_routed_experts"] == row["config"]["n_routed_experts"]
    assert real_config["held_experts"] == [0, 16] and real_config["vocab_held"] == 16384


def test_counts_against_hand_arithmetic(real_config):
    p = lm_flops.params(real_config)
    assert p["mla"] == 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144
    assert p["ffn"] == 3 * 6144 * 12288 and p["expert"] == 3 * 6144 * 2048
    assert p["router"] == 6144 * 768 and p["head"] == 6144 * 16384
    dense = lm_flops.layer_dense_params(real_config)
    assert dense == 2 * p["mla"] + 2 * p["ffn"] + p["router"] == 638_844_928
    # the issue's step: 128 slots, ~700 live positions a slot, 87% of 64 experts touched
    nbytes = lm_flops.decode_step_bytes(real_config, 0.87 * 64, 128 * 700)
    assert nbytes == pytest.approx(
        2 * (4 * dense + p["head"]) + 2 * 0.87 * 64 * p["expert"] + 128 * 700 * 9216)
    assert 10.2e9 < nbytes < 10.5e9
    assert lm_flops.attention_flops_per_pair(real_config) == 2 * 64 * 320
    flops = lm_flops.forward_flops(real_config, tokens=10, heads_computed=4,
                                   context_pairs=1000, held_assignments=7)
    assert flops == pytest.approx(2 * 10 * 4 * dense + 2 * 4 * p["head"]
                                  + 1000 * 8 * 2 * 64 * 320 + 2 * 7 * p["expert"])
    ops, moved = lm_flops.mla_decode_kernel(real_config, 128, 64, 512, 128 * 700)
    assert ops == 2 * 128 * 700 * 64 * (576 + 512)
    assert moved == 128 * 700 * 1152 + 128 * 64 * (576 * 2 + 512 * 4)
    assert 90 < ops / moved < 121                       # under the chip's ridge of 240


def test_the_readers_over_a_made_up_run(real_config):
    """What each new reader computes, on numbers small enough to check by
    hand; a program without the counters (the parent) reads as nothing."""
    from perfbench import tokens

    class O:
        status, error = 200, ""

    o = O()
    o.stream = tokens.Stream(0, arrivals=[-0.5, 1.0, 2.0, 41.0], finished=True)
    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    after = {"kdlt_decode_tokens_total": 4.0, "kdlt_decode_prefill_seconds_count": 1.0,
             "kdlt_decode_prefill_prompt_tokens_total": 10.0,
             "kdlt_decode_context_positions_total": 400.0,
             "kdlt_decode_expert_held_assignments_total": 6.0,
             "kdlt_decode_steps_total": 2.0, "kdlt_decode_experts_touched_total": 100.0}
    run = {"before": {"server": {}}, "after": {"server": after}, "outcomes": [o],
           "seconds": 40.0, "config": real_config, "peaks": peaks, "chips": 1,
           "trace": {"modules": {"jit_step(1)": [0.050, 2], "jit_prefill(2)": [1.0, 1]},
                     "ops": {"%mla_paged_decode.3 = f32[128,64,512]{2,1,0:T(8,128)} "
                             "custom-call(s32[12288]{0} %a)": [0.004, 16]}}}
    load = lambda name: M.load_module(M.HERE, "readers", name)       # noqa: E731
    mfu = load("lm_mfu").read({}, run)
    # 2 of the 4 counted tokens arrived in the window; 3 were produced by steps
    want = lm_flops.forward_flops(real_config, tokens=2 + 10, heads_computed=2,
                                  context_pairs=200.0, held_assignments=6 / 3 * 12)
    assert mfu == pytest.approx(100 * want / 40 / 197e12)
    step = load("lm_step_roofline").read({"module_pattern": "^jit_step"}, run)
    assert step == pytest.approx(
        100 * lm_flops.decode_step_bytes(real_config, 50.0, 200.0) / 819e9 / 0.025)
    kernel = load("lm_kernel_roofline").read(
        M._load_json(os.path.join(M.HERE, "layer_metrics", "mla_decode_roofline.lc.json")), run)
    ops, moved = lm_flops.mla_decode_kernel(real_config, 128, 64, 512, 200.0)
    assert kernel == pytest.approx(100 * 16 * max(ops / 197e12, moved / 819e9) / 0.004)
    bare = dict(run, after={"server": {"kdlt_decode_tokens_total": 4.0}})
    assert all(load(r).read(s, bare) is None for r, s in (
        ("lm_mfu", {}), ("lm_step_roofline", {"module_pattern": "^jit_step"}),
        ("lm_kernel_roofline", {"pattern": "^%mla_paged_decode"})))


# --- whole rehearsed runs at toy widths through the program's model server -----------------


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = tmp_path_factory.mktemp("lctoy")
    lctoy.build(str(root))
    m = M.Manifest(str(root))
    m.validate()
    return m, str(root / "work")


def drive(toy, seed, trace=False):
    manifest, work = toy
    run = lctoy.FaultyRun(manifest, manifest.cell(lctoy.CELL), seed, 3.0, trace,
                          platform="cpu", work_root=work)
    try:
        return run, run.run()
    finally:
        run.children.kill_all()


def test_a_rehearsed_run_is_correct_and_reads_its_counters(toy):
    run, line = drive(toy, 2**31 + 32, trace=True)
    c = line["compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert 0 < c["logit_err"]["value"] < 0.1 and c["tokens_compared"]["value"] > 40
    assert set(run.end_to_end()) == {"setup_s", "latency_p50_ms"}
    got = line["metrics"]             # no device on the CPU: counters and spans only
    assert set(got) == {"decode_step_ms.lc", "prefill_ms.lc", "prefill_share_pct.lc",
                        "zero_expert_share_pct.lc", "held_expert_tokens.lc",
                        "queue_wait_ms.lc", "first_token_ms.lc"}
    assert 0 < got["zero_expert_share_pct.lc"]["value"] < 100
    assert got["held_expert_tokens.lc"]["value"] >= 1
    assert got["first_token_ms.lc"]["value"] >= got["queue_wait_ms.lc"]["value"] >= 0


@pytest.mark.parametrize("fault", ["top_logits", "cache"])
def test_a_broken_timed_path_is_not_correct(toy, monkeypatch, fault):
    monkeypatch.setenv("LANE_FAULT", fault)
    _run, line = drive(toy, 77)
    c = line["compared"]
    assert line["correct"] is False and line["failed"] > 0
    assert c["logit_err"]["value"] > c["logit_err"]["limit"]
    assert c["wrong_answers"]["value"] == 0 and c["unanswered"]["value"] == 0
