"""Kimi-K2.7-Code in the benchmark, on the CPU: the appended
``BENCHMARK.json`` and the configuration against the catalog;
``dsv3_flops`` against hand arithmetic; the new readers over a made-up run;
and whole rehearsed runs of the configuration at toy widths through the
program's own model server from a scratch manifest (``kimitoy/``), prompts
prefilled in chunks: ``correct``, and not ``correct`` with a piece of the
model left out of the timed path."""

import json
import os

import pytest

from perfbench import dsv3_flops
from perfbench import manifest as M
from perfbench.tests.kimitoy import build as kimitoy

REAL = "kimi-k2.7-code-ep32"
CELL = "kimi-code-longprompt-closed64"


@pytest.fixture(scope="module")
def real_config():
    with open(os.path.join(M.ROOT, "perfbench", "configs", REAL + ".json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_published_widths(real_config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2.7-Code")
    assert real_config["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if real_config.get(k) != v}
    assert changed == set(real_config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (real_config["num_hidden_layers"], real_config["n_routed_experts"],
            real_config["vocab_size"]) == (6, 12, 20480)
    assert real_config["published"] == dict(
        real_config["published"], num_hidden_layers=61, n_routed_experts=384,
        vocab_size=163840)
    assert real_config["held_experts"] == [0, 12] and real_config["vocab_held"] == 20480
    assert real_config["num_experts_per_tok"] == 8 and real_config["n_shared_experts"] == 1
    limits = real_config["limits"]
    assert 0 < limits["logit_err"] < 0.1 and 0 < limits["argmax_gap"] < 0.1


def test_the_cell_is_appended_with_the_issues_traffic():
    m = M.Manifest(M.ROOT)
    m.validate()
    assert list(m.workloads)[-1] == CELL and list(m.configs)[-1] == REAL
    cell = m.cell(CELL)
    mix = cell.traffic
    assert (mix["callers"], mix["slots"], mix["page_size"], mix["max_pages"]) == (64, 64, 64, 136)
    assert mix["prompt_buckets"] == [256, 512, 1024, 2048, 4096, 8192] and mix["pool"] == 64
    assert mix["prompt_tokens"] == {"choice": [[1024, 4096, 0.75], [4097, 8192, 0.25]]}
    assert mix["output_tokens"] == {"choice": [[128, 256, 1.0]]}
    assert (mix["top_logits"], mix["compare_requests"], mix["lead_in_s"]) == (8, 6, 25.0)
    assert {e["name"] for e in cell.end_to_end} == {"setup_s", "latency_p50_ms"}
    names = [e["name"] for e, _ in cell.per_layer]
    assert len(names) == 12 and all(n.endswith(".k2") for n in names)
    tail = m.data["per_layer"][-12:]
    assert [e["name"] for e in tail] == names
    assert all(e["workloads"] == [CELL] and e["moves"] == "latency_p50_ms" for e in tail)


def test_counts_against_hand_arithmetic(real_config):
    p = dsv3_flops.params(real_config)
    assert p["mla"] == 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    assert p["ffn"] == 3 * 7168 * 18432 and p["expert"] == 3 * 7168 * 2048
    assert p["router"] == 7168 * 384 and p["head"] == 7168 * 20480
    assert dsv3_flops.layers(real_config) == (1, 5)
    dense = dsv3_flops.dense_params(real_config)
    assert dense == 6 * p["mla"] + p["ffn"] + 5 * (p["expert"] + p["router"]) == 1_237_057_536
    # the issue's step: 64 slots, ~3.6k live positions a slot, 9 of 12 experts touched a layer
    nbytes = dsv3_flops.decode_step_bytes(real_config, 45, 64 * 3600)
    assert nbytes == pytest.approx(2 * (dense + p["head"]) + 2 * 45 * p["expert"]
                                   + 64 * 3600 * 576 * 2 * 6)
    assert 8.2e9 < nbytes < 8.4e9
    assert dsv3_flops.attention_flops_per_pair(real_config) == 2 * 64 * 320
    flops = dsv3_flops.forward_flops(real_config, tokens=10, heads_computed=4, pairs=1000,
                                     routed=7)
    assert flops == pytest.approx(2 * 10 * dense + 2 * 4 * p["head"]
                                  + 1000 * 6 * 2 * 64 * 320 + 2 * 7 * p["expert"])
    # a prefill token with its routed rows only: 8 * 12 / 384 of an expert a layer
    token = dsv3_flops.forward_flops(real_config, 1, 0, 0, 5 * 8 * 12 / 384)
    assert 2.5e9 < token < 2.7e9


def test_the_readers_over_a_made_up_run(real_config):
    """What each new reader computes, on numbers small enough to check by
    hand; a program without the counters (the parent) reads as nothing."""
    from perfbench import tokens

    class O:
        status, error = 200, ""

    o = O()
    o.stream = tokens.Stream(0, arrivals=[-0.5, 1.0, 2.0, 41.0], finished=True)
    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    after = {"kdlt_decode_tokens_total": 4.0, "kdlt_decode_prefill_tokens_total": 10.0,
             "kdlt_decode_prefill_attended_pairs_total": 55.0,
             "kdlt_decode_prefill_routed_rows_total": 3.0,
             "kdlt_decode_context_positions_total": 400.0,
             "kdlt_decode_expert_held_assignments_total": 6.0,
             "kdlt_decode_steps_total": 2.0, "kdlt_decode_experts_touched_total": 80.0}
    run = {"before": {"server": {}}, "after": {"server": after}, "outcomes": [o],
           "seconds": 40.0, "config": real_config, "peaks": peaks, "chips": 1,
           "trace": {"modules": {"jit_step(1)": [0.050, 2], "jit_prefill(2)": [1.0, 1]}}}
    load = lambda name: M.load_module(M.HERE, "readers", name)       # noqa: E731
    # 2 of the 4 counted tokens arrived in the window: half the steps' pairs and rows
    want = dsv3_flops.forward_flops(real_config, tokens=2 + 10, heads_computed=2,
                                    pairs=55 + 200.0, routed=3 + 3.0)
    assert load("dsv3_mfu").read({}, run) == pytest.approx(100 * want / 40 / 197e12)
    step = load("dsv3_step_roofline").read({"module_pattern": "^jit_step"}, run)
    assert step == pytest.approx(
        100 * dsv3_flops.decode_step_bytes(real_config, 40.0, 200.0) / 819e9 / 0.025)
    bare = dict(run, after={"server": {"kdlt_decode_tokens_total": 4.0}})
    assert load("dsv3_mfu").read({}, bare) is None
    assert load("dsv3_step_roofline").read({"module_pattern": "^jit_step"}, bare) is None


# --- whole rehearsed runs at toy widths through the program's model server -----------------


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = tmp_path_factory.mktemp("kimitoy")
    kimitoy.build(str(root))
    m = M.Manifest(str(root))
    m.validate()
    return m, str(root / "work")


def drive(toy, seed, trace=False):
    manifest, work = toy
    run = kimitoy.FaultyRun(manifest, manifest.cell(kimitoy.CELL), seed, 3.0, trace,
                            platform="cpu", work_root=work)
    try:
        return run, run.run()
    finally:
        run.children.kill_all()


def test_a_rehearsed_run_is_correct_and_reads_its_counters(toy):
    run, line = drive(toy, 2**31 + 34, trace=True)
    c = line["compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert 0 < c["logit_err"]["value"] < 0.1 and c["tokens_compared"]["value"] > 40
    assert set(run.end_to_end()) == {"setup_s", "latency_p50_ms"}
    got = line["metrics"]             # no device on the CPU: counters and spans only
    assert set(got) == {"prefill_chunk_ms.k2", "prefill_share_pct.k2", "prefill_pad_pct.k2",
                        "prefill_expert_rows_pct.k2", "decode_step_ms.k2",
                        "held_expert_tokens.k2", "queue_wait_ms.k2", "first_token_ms.k2"}
    assert 0 < got["prefill_pad_pct.k2"]["value"] < 100
    # the toy's chunks are under the rows from which the product is grouped:
    # every row through each of 4 held experts, 3 * 4 / 16 of them routed
    assert 5 < got["prefill_expert_rows_pct.k2"]["value"] < 40
    assert got["held_expert_tokens.k2"]["value"] >= 1
    assert got["first_token_ms.k2"]["value"] >= got["queue_wait_ms.k2"]["value"] >= 0
    # a third of the prompts took two to four chunks of 16 rows and a rest
    after = run.after["server"]
    assert after["kdlt_decode_prefill_chunks_total"] > 1.5 * after["kdlt_decode_generations_total"]


@pytest.mark.parametrize("fault", ["shared_expert", "yarn_mscale"])
def test_a_model_with_a_piece_left_out_is_not_correct(toy, monkeypatch, fault):
    monkeypatch.setenv("LANE_FAULT", fault)
    _run, line = drive(toy, 77)
    c = line["compared"]
    assert line["correct"] is False and line["failed"] > 0
    assert c["logit_err"]["value"] > c["logit_err"]["limit"]
    assert c["wrong_answers"]["value"] == 0 and c["unanswered"]["value"] == 0
