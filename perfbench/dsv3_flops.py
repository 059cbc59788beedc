"""Operations and bytes of a DeepSeek-V3-family decoder configuration
(Kimi-K2's: ``configs/*.json`` with ``first_k_dense_replace``,
``n_shared_experts``, ``moe_intermediate_size``, ...), counted from its keys
as the algorithm needs them: no padding, no recomputation, each weight read
once, a token through the experts it was routed to and no others.  The
readers ``readers/dsv3_*.py`` divide these by what a run measured.
(``lm_flops.py`` counts LongCat-Flash's layer: two sublayers, two FFNs,
zero-compute experts.)

Parameters (a multiply-add each, 2 bytes each as served):

- MLA: W_qa, W_qb, W_kva, W_kvb (keys and values of every head), W_o;
- the dense layers' FFN, one expert (routed or shared): gate, up, down;
- the router; the head.  Norm weights and the router's bias are left out
  (a few thousand values).
"""

from __future__ import annotations

WEIGHT_BYTES = 2          # bfloat16
CACHE_BYTES = 2


def params(config: dict) -> dict:
    """Parameter counts of the pieces of a layer, and of the head."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    return {
        "mla": (d * q_rank + q_rank * heads * (nope + rope) + d * (kv_rank + rope)
                + kv_rank * heads * (nope + v) + heads * v * d),
        "ffn": 3 * d * config["intermediate_size"],
        "expert": 3 * d * config["moe_intermediate_size"],
        "router": d * config["published"]["n_routed_experts"],
        "head": d * config["vocab_held"],
    }


def layers(config: dict) -> tuple[int, int]:
    """(dense layers, expert layers) of the configuration as it is run."""
    dense = min(config["first_k_dense_replace"], config["num_hidden_layers"])
    return dense, config["num_hidden_layers"] - dense


def dense_params(config: dict) -> int:
    """What every token goes through, over the whole stack: MLA in every
    layer, the FFN of the dense layers, the shared expert and the router of
    the expert layers."""
    p = params(config)
    dense, expert = layers(config)
    return ((dense + expert) * p["mla"] + dense * p["ffn"]
            + expert * (config["n_shared_experts"] * p["expert"] + p["router"]))


def attention_flops_per_pair(config: dict) -> int:
    """Multiply-adds x 2 of one query position against one context position
    in one layer, all heads, in the expanded form a full forward uses:
    scores over nope + rope, the weighted sum over v."""
    return 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"])


def forward_flops(config: dict, tokens: float, heads_computed: float, pairs: float,
                  routed: float) -> float:
    """FLOPs a full forward needs for ``tokens`` positions of which
    ``heads_computed`` go through the head (one a generated token), whose
    queries meet ``pairs`` (query, context position) pairs a layer, with
    ``routed`` (token, held expert) assignments summed over the layers."""
    p = params(config)
    return (2.0 * tokens * dense_params(config) + 2.0 * heads_computed * p["head"]
            + float(pairs) * config["num_hidden_layers"] * attention_flops_per_pair(config)
            + 2.0 * routed * p["expert"])


def decode_step_bytes(config: dict, experts_touched: float, context_positions: float) -> float:
    """The least bytes one decode step reads: the weights every token goes
    through, the head, the held experts that met a token
    (``experts_touched``, summed over layers), and the cached latents of
    every live position (``context_positions``, summed over slots) in every
    layer.  Activations of a few dozen rows are left out."""
    p = params(config)
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return (WEIGHT_BYTES * (dense_params(config) + p["head"])
            + WEIGHT_BYTES * experts_touched * p["expert"]
            + CACHE_BYTES * context_positions * width * config["num_hidden_layers"])
