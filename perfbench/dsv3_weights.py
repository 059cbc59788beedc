"""A DeepSeek-V3-family decoder's weights from the seed (Kimi-K2's
configurations), on the host, in bulk: ``lm_weights.py``'s draw -- numpy
alone, a tensor at a time in row blocks by a pool of threads, each block
from its own stream, rounded to bfloat16 with integer arithmetic -- with
this family's names, shapes and scales.  Used by
``children/make_kimi_artifact.py`` and by the tests.

The scales are the configuration's ``assumed.weight_scales``:

- every product is scaled by its fan-in, so that a unit-RMS input gives a
  unit-RMS output;
- ``attention_logit_std``: ``wq_b`` is scaled so that a score, after YaRN's
  ``mscale ** 2`` on the softmax scale, has about this standard deviation
  before the softmax (keys have 1);
- ``residual_branch_scale`` on the products that write into the residual
  stream (``wo``, every ``w_down``), so that activations stay of order one;
- ``bias_feature``, ``router_logit_std`` and ``router_logit_offset``: a
  sigmoid saturates -- with the eight chosen scores near 1 every weight is
  ``routed_scaling_factor / 8`` and a bfloat16 flip at the cut moves the
  layer's output by an eighth -- so the router's largest logits are put
  below zero, where the sigmoid is an exponential, the renormalised weights
  are a softmax over the chosen logits whatever the offset, and the eighth's
  weight is a few hundredths of the first's.  A router without a bias term
  can be offset only through a constant component of its input, and a
  constant direction that every matrix reads makes every token route alike
  (tried first, as a mean of the embedding: a touched expert then met 43
  tokens a decode step where 1.3 were due).  So one feature of the hidden
  state, feature 0, is a bias line: every embedding row holds ``bias_feature
  * sqrt(hidden_size)`` there, no matrix writes to it (column 0 of ``wo`` and
  of every ``w_down`` is zero) and none but the router reads it (row 0 of
  every matrix that takes the hidden state is zero; the router's row 0 is
  the constant that makes the offset ``router_logit_offset`` at the
  embedding's RMS, about a third less after the last layer).  The other
  features see it through the norms' RMS alone.  The router's other rows
  have the standard deviation that gives its logits ``router_logit_std``
  over a unit-RMS input;
- ``router_bias_std``: ``e_score_correction_bias``, a constant when served,
  small against the chosen scores (it is added to the sigmoid's output,
  0.001-0.2 for the chosen eight: the offset must leave them that large, or
  the bias alone chooses the experts, the same for every token);
- ``norm_jitter``: norm weights are 1 plus this much noise, so that a norm
  weight left out shows.
"""

from __future__ import annotations

import math
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.lm_weights import BLOCK_VALUES, to_bfloat16_bits

PROGRAM_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "first_k_dense_replace", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "num_experts_per_tok",
    "n_shared_experts", "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
    "rope_theta", "rope_scaling", "scoring_func", "topk_method", "n_group", "topk_group",
    "moe_layer_freq", "hidden_act")


def program_config(config: dict) -> dict:
    """The benchmark's configuration in the keys the program's decoder reads
    (``models/kimi_k2.KimiConfig``: the published ones): the router scores
    the published number of experts, of which ``held_experts`` live here."""
    out = {k: config[k] for k in PROGRAM_KEYS}
    out["n_routed_experts"] = config["published"]["n_routed_experts"]
    out["held_experts"] = list(config["held_experts"])
    out["vocab_size"] = config["vocab_held"]
    return out


def softmax_mscale(config: dict) -> float:
    scaling = config.get("rope_scaling") or {}
    factor = scaling.get("factor", 1)
    return 1.0 if factor <= 1 else 0.1 * scaling.get("mscale_all_dim", 0) * math.log(factor) + 1.0


def scale(name: str, shape: tuple, config: dict) -> tuple[float, float]:
    """(mean, standard deviation) of a tensor's draws."""
    scales = config["assumed"]["weight_scales"]
    leaf = name.rsplit(".", 1)[-1]
    fan_in = shape[-2] if len(shape) > 1 else shape[0]
    if leaf.endswith("norm"):
        return 1.0, float(scales["norm_jitter"])
    if leaf == "embed":
        return 0.0, 1.0
    if leaf == "router_bias":
        return 0.0, float(scales["router_bias_std"])
    if leaf == "router":
        return 0.0, float(scales["router_logit_std"]) / math.sqrt(shape[0])
    if leaf == "wq_b":
        return 0.0, (float(scales["attention_logit_std"]) / softmax_mscale(config) ** 2
                     / math.sqrt(fan_in))
    if leaf in ("wo", "w_down"):
        return 0.0, float(scales["residual_branch_scale"]) / math.sqrt(fan_in)
    return 0.0, 1.0 / math.sqrt(fan_in)     # w_uk, w_uv [heads, rank, dim]: the rank


def bias_line(name: str, value: np.ndarray, config: dict) -> None:
    """Feature 0 of the hidden state as the router's bias line (the module's
    docstring), written over the drawn ``value`` in place."""
    scales = config["assumed"]["weight_scales"]
    hidden, leaf = config["hidden_size"], name.rsplit(".", 1)[-1]
    line = float(scales["bias_feature"])

    def put(where, x: float) -> None:
        where[...] = (to_bfloat16_bits(np.full(1, x, np.float32))[0]
                      if value.dtype == np.uint16 else x)

    if leaf == "embed":
        put(value[:, 0], line * math.sqrt(hidden))
    elif leaf == "router":     # u_0 = line * sqrt(hidden / (1 + line^2)) at the embedding
        put(value[0, :], -float(scales["router_logit_offset"])
            * math.sqrt((1 + line * line) / hidden) / line)
    elif value.ndim > 1 and value.shape[-2] == hidden:      # takes the hidden state
        put(value[..., 0, :], 0.0)
    elif leaf in ("wo", "w_down"):                          # writes into it
        put(value[..., 0], 0.0)


def tensor(name: str, shape: tuple, dtype: str, config: dict, seed: int,
           pool: ThreadPoolExecutor | None = None) -> np.ndarray:
    """One tensor: float32, or uint16 holding bfloat16 bits."""
    mean, std = scale(name, shape, config)
    total = int(np.prod(shape))
    out = np.empty(total, np.uint16 if dtype == "bfloat16" else np.float32)
    key = zlib.crc32(name.encode())

    def block(k: int) -> None:
        lo, hi = k * BLOCK_VALUES, min(total, (k + 1) * BLOCK_VALUES)
        rng = np.random.Generator(np.random.SFC64(np.random.SeedSequence([int(seed), key, k])))
        x = rng.standard_normal(hi - lo, dtype=np.float32)
        x *= np.float32(std)
        if mean:
            x += np.float32(mean)
        out[lo:hi] = to_bfloat16_bits(x) if dtype == "bfloat16" else x

    blocks = range(-(-total // BLOCK_VALUES))
    if pool is None:
        for k in blocks:
            block(k)
    else:
        list(pool.map(block, blocks))
    out = out.reshape(shape)
    bias_line(name, out, config)
    return out


def tensors(config: dict, seed: int, shapes: dict, dtypes, threads: int = 1):
    """(name, array) for every entry of ``shapes`` ({name: shape});
    ``dtypes(name)`` is "bfloat16" or "float32"."""
    with ThreadPoolExecutor(max(1, threads)) as pool:
        for name, shape in shapes.items():
            yield name, tensor(name, tuple(shape), dtypes(name), config, seed,
                               pool if threads > 1 else None)
