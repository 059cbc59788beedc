"""A scratch manifest around Kimi-K2 at toy widths, for rehearsed runs on the
CPU through the program's own model server: the committed ``BENCHMARK.json``
and ``perfbench/`` with one configuration, one cell and its per-layer
metrics appended (the committed cell's own metric files, so the readers are
exercised), as a later PR would add them.  ``FaultyRun`` starts
``faulty_serve.py`` where a run starts ``children/serve.py``: the program
with the lane's chunk size brought down to the toy's prompts and, where
``LANE_FAULT`` says so, a piece of the mathematics left out.
"""

from __future__ import annotations

import json
import os
import shutil

from perfbench import run as run_lib

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
CELL = "kimitoy-closed"
REAL_CELL = "kimi-code-longprompt-closed64"
CHUNK = 16      # the chunk size ``faulty_serve.py`` gives the lane

CONFIG = {
    "name": "kimitoy", "served_name": "kimi-toy", "reference": "kimi_k2",
    "artifact_child": "make_kimi_artifact.py", "reference_child": "reference_kimi.py",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 16, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "routed_scaling_factor": 2.827, "norm_topk_prob": True, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "moe_layer_freq": 1, "hidden_act": "silu", "n_routed_experts": 4,
    "published": {"n_routed_experts": 16}, "held_experts": [4, 8],
    "vocab_size": 64, "vocab_held": 64,
    "assumed": {"reference_block": 16, "weight_scales": {
        "attention_logit_std": 2.5, "residual_branch_scale": 0.5, "bias_feature": 1.0,
        "router_logit_std": 3.0, "router_logit_offset": 6.0, "router_bias_std": 1e-4,
        "norm_jitter": 0.05}},
    # bfloat16 operands at width 64 against float32: 0.01-0.03 on the CPU, a
    # routing flip included; the two faults read 0.2 and more.  A toy's.
    "limits": {"logit_err": 0.1, "argmax_gap": 0.1},
}
MIX = {
    "generator": "closed", "entry": "server-generate", "callers": 4, "slots": 4,
    "page_size": 8, "max_pages": 12, "prompt_buckets": [8, 16, 64], "pool": 12,
    # a third of the prompts are two to four chunks of 16 and a rest
    "prompt_tokens": {"choice": [[6, 14, 2], [20, 60, 1]]},
    "output_tokens": {"choice": [[12, 30, 1]]}, "ignore_eos": True, "top_logits": 8,
    "compare_requests": 16, "request_timeout_s": 60, "lead_in_s": 1.0,
    "warm": {"steady_rounds": 2, "steady_within": 1.5, "settle_timeout_s": 20,
             "min_seconds": 0},
    "trace_offset_s": 0.5, "trace_seconds": 1.0, "span_recent": 50, "span_sample": 8,
}


class FaultyRun(run_lib.CellRun):
    def child_script(self, name: str) -> str:
        return super().child_script("faulty_serve.py" if name == "serve.py" else name)


def build(root: str) -> None:
    bench = os.path.join(root, "perfbench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(HERE, "faulty_serve.py"), os.path.join(bench, "children"))
    with open(os.path.join(bench, "configs", CONFIG["name"] + ".json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(bench, "traffic", CELL + ".json"), "w") as f:
        json.dump(MIX, f)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": CONFIG["name"], "source": "perfbench/tests/kimitoy", "reduced": [],
        "file": f"perfbench/configs/{CONFIG['name']}.json", "why": "a toy"})
    manifest["workloads"].append({"name": CELL, "config": CONFIG["name"], "traffic": CELL,
                                  "chips": 1, "why": "the decoder's plumbing"})
    for m in manifest["per_layer"]:
        if REAL_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
