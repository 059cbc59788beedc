"""A decoder's weights from the seed, on the host, in bulk: numpy alone, a
tensor at a time, drawn in row blocks by a pool of threads (each block from
its own stream, so the result does not depend on the pool), rounded to
bfloat16 with integer arithmetic.  Used by
``children/make_decoder_artifact.py`` and by the tests.

The scales are the configuration's ``assumed.weight_scales``:

- every product is scaled by its fan-in, so that a unit-RMS input gives a
  unit-RMS output;
- ``attention_logit_std``: ``wq_b`` and ``w_uk`` are scaled so that, after
  the published ``mla_scale_*`` factors, queries have this standard
  deviation and keys 1: a score's, before the softmax, is then about this;
- ``residual_branch_scale`` on the products that write into the residual
  stream (``wo``, every ``w_down``), so that activations stay of order one
  through the sublayers;
- ``router_logit_std``: the router's outputs have this standard deviation,
  so that the softmax over all experts is neither flat nor one-hot;
- ``router_bias_std``: ``e_score_correction_bias``, a constant when served;
- ``norm_jitter``: norm weights are 1 plus this much noise, so that a norm
  weight left out shows.
"""

from __future__ import annotations

import math
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_VALUES = 1 << 22


def program_config(config: dict) -> dict:
    """The benchmark's configuration in the keys the program's decoder reads
    (``models/longcat_flash.LongcatConfig``): the router scores the
    published number of real experts, of which ``held_experts`` live here."""
    keys = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
            "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
            "qk_nope_head_dim", "v_head_dim", "zero_expert_num", "moe_topk",
            "routed_scaling_factor", "rms_norm_eps", "rope_theta", "mla_scale_q_lora",
            "mla_scale_kv_lora")
    out = {k: config[k] for k in keys}
    out["n_routed_experts"] = config["published"]["n_routed_experts"]
    out["held_experts"] = list(config["held_experts"])
    out["vocab_size"] = config["vocab_held"]
    return out


def to_bfloat16_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> the 16 bits of the nearest bfloat16 (ties to even).
    Rounds ``x`` in place and returns a view of its high halves."""
    bits = x.view(np.uint32)
    carry = bits >> 16
    carry &= 1
    carry += 0x7FFF
    bits += carry
    return bits.view(np.uint16)[1::2]        # little-endian: the high half


def _scale(name: str, shape: tuple, config: dict) -> tuple[float, float]:
    """(mean, standard deviation) of a tensor's draws."""
    scales = config["assumed"]["weight_scales"]
    leaf = name.rsplit(".", 1)[-1]
    hidden = config["hidden_size"]
    branch = float(scales["residual_branch_scale"])
    if leaf.endswith("norm"):
        return 1.0, float(scales["norm_jitter"])
    if leaf == "embed":
        return 0.0, 1.0
    if leaf == "router_bias":
        return 0.0, float(scales["router_bias_std"])
    if leaf == "router":
        return 0.0, float(scales["router_logit_std"]) / math.sqrt(shape[0])
    if leaf == "wq_b":
        published = math.sqrt(hidden / config["q_lora_rank"]) if config["mla_scale_q_lora"] else 1.0
        return 0.0, float(scales["attention_logit_std"]) / published / math.sqrt(shape[0])
    if leaf in ("w_uk", "w_uv"):      # [heads, rank, dim]: fan-in is the rank
        published = math.sqrt(hidden / config["kv_lora_rank"]) if config["mla_scale_kv_lora"] else 1.0
        return 0.0, 1.0 / published / math.sqrt(shape[1])
    if leaf in ("wo", "w_down"):
        fan_in = config["expert_ffn_hidden_size"] if ".experts." in name else shape[0]
        return 0.0, branch / math.sqrt(fan_in)
    return 0.0, 1.0 / math.sqrt(shape[0])


def tensor(name: str, shape: tuple, dtype: str, config: dict, seed: int,
           pool: ThreadPoolExecutor | None = None) -> np.ndarray:
    """One tensor: float32, or uint16 holding bfloat16 bits."""
    mean, std = _scale(name, shape, config)
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
    return out.reshape(shape)


def tensors(config: dict, seed: int, shapes: dict, dtypes, threads: int = 1):
    """(name, array) for every entry of ``shapes`` ({name: shape});
    ``dtypes(name)`` is "bfloat16" or "float32"."""
    with ThreadPoolExecutor(max(1, threads)) as pool:
        for name, shape in shapes.items():
            yield name, tensor(name, tuple(shape), dtypes(name), config, seed,
                               pool if threads > 1 else None)
