"""Operations and bytes of a decoder configuration, counted from its keys as
the algorithm needs them: no padding, no recomputation, each weight read
once.  For LongCat-Flash's family of configurations (``configs/*.json``
with ``hidden_size``, ``kv_lora_rank``, ``held_experts``, ...); the readers
``readers/lm_*.py`` divide these by what a run measured.

Parameters (a multiply-add each, 2 bytes each as served):

- one MLA sublayer: W_qa, W_qb, W_kva, W_kvb (keys and values of every
  head), W_o;
- one dense FFN and one expert: gate, up, down;
- the router; the head.  Norm weights and the router's bias are left out
  (a few thousand values).
"""

from __future__ import annotations

WEIGHT_BYTES = 2          # bfloat16
CACHE_BYTES = 2


def params(config: dict) -> dict:
    """Parameter counts of the pieces of one layer, and of the head."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    experts = config["published"]["n_routed_experts"] + config["zero_expert_num"]
    return {
        "mla": (d * q_rank + q_rank * heads * (nope + rope) + d * (kv_rank + rope)
                + kv_rank * heads * (nope + v) + heads * v * d),
        "ffn": 3 * d * config["ffn_hidden_size"],
        "expert": 3 * d * config["expert_ffn_hidden_size"],
        "router": d * experts,
        "head": d * config["vocab_held"],
    }


def layer_dense_params(config: dict) -> int:
    """What every token of a layer goes through: two MLA sublayers, two
    dense FFNs, the router."""
    p = params(config)
    return 2 * p["mla"] + 2 * p["ffn"] + p["router"]


def latent_width(config: dict) -> int:
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def attention_flops_per_pair(config: dict) -> int:
    """Multiply-adds x 2 of one query position against one context
    position in one sublayer, all heads, in the expanded form a full
    forward uses: scores over nope + rope, the weighted sum over v."""
    return 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"])


def forward_flops(config: dict, tokens: float, heads_computed: float,
                  context_pairs: float, held_assignments: float) -> float:
    """FLOPs a full forward needs for ``tokens`` positions of which
    ``heads_computed`` go through the head (one a generated token), whose
    queries meet ``context_pairs`` (query, context position) pairs a
    sublayer, with ``held_assignments`` (token, held expert) assignments
    summed over the layers."""
    p = params(config)
    layers = config["num_layers"]
    return (2.0 * tokens * layers * layer_dense_params(config)
            + 2.0 * heads_computed * p["head"]
            + float(context_pairs) * 2 * layers * attention_flops_per_pair(config)
            + 2.0 * held_assignments * p["expert"])


def decode_step_bytes(config: dict, experts_touched: float, context_positions: float) -> float:
    """The least bytes one decode step reads: the non-expert weights of the
    kept layers, the head, the held experts that met a token
    (``experts_touched``, summed over layers), and the cached latents of
    every live position (``context_positions``, summed over slots) in every
    sublayer.  Activations of a few hundred rows are left out."""
    p = params(config)
    layers = config["num_layers"]
    return (WEIGHT_BYTES * (layers * layer_dense_params(config) + p["head"])
            + WEIGHT_BYTES * experts_touched * p["expert"]
            + CACHE_BYTES * context_positions * latent_width(config) * 2 * layers)


def mla_decode_kernel(config: dict, slots: int, heads: int, rank: int,
                      context_positions: float) -> tuple[float, float]:
    """(operations, bytes) of one call of the paged latent-attention kernel
    (one sublayer, one step): every live position (``context_positions``,
    summed over slots) is scored against ``heads`` latent queries (latent
    width) and summed into ``rank`` values; its latent is read once; the
    queries are read and the result written once."""
    width = latent_width(config)
    ops = 2.0 * context_positions * heads * (width + rank)
    nbytes = (CACHE_BYTES * context_positions * width
              + slots * heads * (width * CACHE_BYTES + rank * 4))
    return ops, nbytes
