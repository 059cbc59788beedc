"""LongCat-Flash: a decoder for the generative lane (``runtime.decode``).

One layer holds two latent-attention (MLA) sublayers, two dense SwiGLU FFNs
and one expert layer on a shortcut: the expert layer reads the first
sublayer's normed output and lands at the end of the second, so its product
can run beside the second attention and FFN (``h`` is the layer's input):

    a0  = h  + MLA_0(RMS(h))
    u0  = RMS(a0)
    m   = MoE(u0)                   # leaves here ...
    b0  = a0 + FFN_0(u0)
    a1  = b0 + MLA_1(RMS(b0))
    out = a1 + FFN_1(RMS(a1)) + m   # ... and lands here

``MoE``: a float32 softmax over ``n_routed_experts + zero_expert_num``
router outputs; the ``moe_topk`` largest of ``s + bias`` are chosen, their
weights are ``routed_scaling_factor * s`` (not renormalised).  A real
expert is a SwiGLU FFN; a zero-compute expert returns its input.  This
module is told which real experts it holds (``held_experts`` = [lo, hi) of
``n_routed_experts``, one chip's share of an expert-parallel deployment):
it routes over all of them, computes the held experts' part and the
zero-compute experts' part, and leaves out what the absent experts would
add.  No token is dropped; there is no capacity factor and nothing stands
in for the absent chips.  The held experts' products are one masked wide
FFN (all tokens through all held experts, a token's weight for an expert it
was not routed to being zero): at a decode step's few rows the product is
bound by the experts' bytes, which it reads once.

``MLA``: the cache holds, a position a sublayer, the normed and scaled
compression ``c_kv`` and the rotated shared key ``k_r`` (``latent_width``
values, padded to ``cache_width``, a multiple of the chip's 128 lanes).
The prefill attends in the expanded form (per-head keys and values from
``c_kv``); the decode step attends in the absorbed form over the paged
cache (``ops.mla_decode``).  Weights and matmul operands are bfloat16 with
float32 accumulation; the residual stream, the norms' statistics, the
router and the softmaxes are float32.

The interface the lane asks of a decoder (``runtime.decode.DecodeEngine``):
``vocab_size``, ``eos_token``, ``text`` (whether prompts may be text),
``params``, ``cache_spec``, ``prefill``, ``decode_step``, ``describe``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

from kubernetes_deep_learning_tpu.export.artifact import DECODER_FILE

FAMILY = "longcat_flash"


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    hidden_size: int
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    num_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    q_lora_rank: int
    qk_rope_head_dim: int
    qk_nope_head_dim: int
    v_head_dim: int
    n_routed_experts: int          # real experts the router scores
    zero_expert_num: int
    moe_topk: int
    routed_scaling_factor: float
    vocab_size: int
    held_experts: tuple[int, int]  # [lo, hi) of n_routed_experts held here
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    compute_dtype: str = "bfloat16"   # matmul operands and the cache; tests use float32

    @classmethod
    def from_dict(cls, d: dict) -> "LongcatConfig":
        names = [f.name for f in dataclasses.fields(cls)]
        kwargs = {k: d[k] for k in names if k in d}
        kwargs["held_experts"] = tuple(int(x) for x in d["held_experts"])
        cfg = cls(**kwargs)
        lo, hi = cfg.held_experts
        if not 0 <= lo < hi <= cfg.n_routed_experts:
            raise ValueError(f"held_experts {cfg.held_experts} outside "
                             f"[0, {cfg.n_routed_experts})")
        return cfg

    @property
    def n_held(self) -> int:
        return self.held_experts[1] - self.held_experts[0]

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        return -(-self.latent_width // 128) * 128

    @property
    def sublayers(self) -> int:
        return 2 * self.num_layers

    def tensor_shapes(self) -> dict[str, tuple[int, ...]]:
        """Every weight by its name in the artifact, with its shape."""
        d, h = self.hidden_size, self.num_attention_heads
        ef = self.n_held * self.expert_ffn_hidden_size
        out = {"embed": (self.vocab_size, d), "final_norm": (d,),
               "head": (d, self.vocab_size)}
        for i in range(self.num_layers):
            p = f"layers.{i}."
            for j in range(2):
                a = f"{p}attn.{j}."
                out[a + "norm"] = (d,)
                out[a + "wq_a"] = (d, self.q_lora_rank)
                out[a + "q_norm"] = (self.q_lora_rank,)
                out[a + "wq_b"] = (self.q_lora_rank,
                                   h * (self.qk_nope_head_dim + self.qk_rope_head_dim))
                out[a + "wkv_a"] = (d, self.latent_width)
                out[a + "kv_norm"] = (self.kv_lora_rank,)
                out[a + "w_uk"] = (h, self.kv_lora_rank, self.qk_nope_head_dim)
                out[a + "w_uv"] = (h, self.kv_lora_rank, self.v_head_dim)
                out[a + "wo"] = (h * self.v_head_dim, d)
                f = f"{p}ffn.{j}."
                out[f + "norm"] = (d,)
                out[f + "w_gate"] = (d, self.ffn_hidden_size)
                out[f + "w_up"] = (d, self.ffn_hidden_size)
                out[f + "w_down"] = (self.ffn_hidden_size, d)
            out[p + "router"] = (d, self.n_routed_experts + self.zero_expert_num)
            out[p + "router_bias"] = (self.n_routed_experts + self.zero_expert_num,)
            out[p + "experts.w_gate"] = (d, ef)
            out[p + "experts.w_up"] = (d, ef)
            out[p + "experts.w_down"] = (ef, d)
        return out


FLOAT32_TENSORS = ("router_bias",)     # every other tensor is bfloat16


def tensor_dtype(name: str) -> str:
    return "float32" if name.rsplit(".", 1)[-1] in FLOAT32_TENSORS else "bfloat16"


# --- the artifact ---------------------------------------------------------------
#
# <root>/<name>/<version>/decoder.json: {"family", "config", "tensors": {name:
# {"file", "shape", "dtype"}}}; each tensor a raw little-endian file beside
# it (bfloat16 as its 16 bits).  Written by whoever makes the checkpoint
# (``write_artifact``), read here.


def write_artifact(directory: str, config: dict, tensors) -> None:
    """``tensors``: (name, numpy array) pairs, float32 or uint16 (bfloat16
    bits); shapes are held to ``tensor_shapes``."""
    cfg = LongcatConfig.from_dict(config)
    shapes = cfg.tensor_shapes()
    os.makedirs(directory, exist_ok=True)
    index = {}
    for name, value in tensors:
        if tuple(value.shape) != shapes[name]:
            raise ValueError(f"{name}: shape {value.shape}, not {shapes[name]}")
        wanted = np.uint16 if tensor_dtype(name) == "bfloat16" else np.float32
        if value.dtype != wanted:
            raise ValueError(f"{name}: dtype {value.dtype}, not {wanted.__name__}")
        value.tofile(os.path.join(directory, name + ".bin"))
        index[name] = {"file": name + ".bin", "shape": list(value.shape),
                       "dtype": tensor_dtype(name)}
    if set(index) != set(shapes):
        raise ValueError(f"tensors missing: {sorted(set(shapes) - set(index))}")
    with open(os.path.join(directory, DECODER_FILE), "w") as f:
        json.dump({"family": FAMILY, "config": config, "tensors": index}, f)


def read_artifact(directory: str) -> tuple[LongcatConfig, dict]:
    """The configuration and a flat {name: memory-mapped array} (bfloat16
    where the index says so)."""
    import ml_dtypes

    with open(os.path.join(directory, DECODER_FILE)) as f:
        meta = json.load(f)
    if meta.get("family") != FAMILY:
        raise ValueError(f"{directory}: decoder family {meta.get('family')!r}, "
                         f"not {FAMILY!r}")
    cfg = LongcatConfig.from_dict(meta["config"])
    flat = {}
    for name, shape in cfg.tensor_shapes().items():
        entry = meta["tensors"][name]
        if tuple(entry["shape"]) != shape or entry["dtype"] != tensor_dtype(name):
            raise ValueError(f"{directory}: {name} is {entry}, the configuration "
                             f"asks for {shape} {tensor_dtype(name)}")
        raw = np.memmap(os.path.join(directory, entry["file"]), mode="r",
                        dtype=np.uint16 if entry["dtype"] == "bfloat16" else np.float32,
                        shape=shape)
        flat[name] = raw.view(ml_dtypes.bfloat16) if entry["dtype"] == "bfloat16" else raw
    return cfg, flat


def nest(flat: dict) -> dict:
    """{"layers.0.attn.1.wo": x} -> {"layers": [{"attn": [.., {"wo": x}]}]}."""
    root: dict = {}
    for name, value in flat.items():
        node = root
        parts = name.split(".")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value

    def lists(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [lists(node[str(i)]) for i in range(len(node))]
        return {k: lists(v) for k, v in node.items()}

    return lists(root)


# --- the mathematics ----------------------------------------------------------------


def _rms(x, weight, eps: float):
    """RMSNorm with float32 statistics; float32 out."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def _contract(cfg: LongcatConfig, spec: str, a, b):
    """An einsum over operands in the compute dtype (bfloat16 as served)
    with float32 accumulation.  XLA's CPU backend lacks some bfloat16
    products: there the operands are rounded all the same and multiplied as
    float32."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg.compute_dtype)
    a, b = a.astype(dtype), b.astype(dtype)
    if jax.default_backend() == "cpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                      precision="highest" if dtype == jnp.float32 else None)


def _mm(cfg: LongcatConfig, x, w):
    return _contract(cfg, "nk,kd->nd", x, w)


def _rope_angles(cfg: LongcatConfig, positions):
    import jax.numpy as jnp

    half = cfg.qk_rope_head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """Rotate the interleaved pairs (2i, 2i+1) of the last axis; ``cos`` and
    ``sin`` broadcast over the axes between the first and the last."""
    import jax.numpy as jnp

    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.stack([even * c - odd * s, even * s + odd * c], axis=-1).reshape(x.shape)


def _queries_and_latent(cfg: LongcatConfig, a: dict, x, cos, sin):
    """From a sublayer's input ``x`` [N, D] (float32): queries ``q_nope``
    [N, H, nope], rotated ``q_rope`` [N, H, rope] (float32) and what the
    positions leave in the cache, [N, cache_width] bfloat16."""
    import jax.numpy as jnp

    n = x.shape[0]
    h = _rms(x, a["norm"], cfg.rms_norm_eps)
    cq = _rms(_mm(cfg, h, a["wq_a"]), a["q_norm"], cfg.rms_norm_eps)
    q = _mm(cfg, cq, a["wq_b"])
    if cfg.mla_scale_q_lora:
        q = q * math.sqrt(cfg.hidden_size / cfg.q_lora_rank)
    q = q.reshape(n, cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    ckr = _mm(cfg, h, a["wkv_a"])
    c = _rms(ckr[:, :cfg.kv_lora_rank], a["kv_norm"], cfg.rms_norm_eps)
    if cfg.mla_scale_kv_lora:
        c = c * math.sqrt(cfg.hidden_size / cfg.kv_lora_rank)
    kr = _rope(ckr[:, cfg.kv_lora_rank:], cos, sin)
    latent = jnp.concatenate(
        [c, kr, jnp.zeros((n, cfg.cache_width - cfg.latent_width), jnp.float32)],
        axis=-1).astype(jnp.dtype(cfg.compute_dtype))
    q_rope = _rope(q[..., cfg.qk_nope_head_dim:], cos, sin)
    return q[..., :cfg.qk_nope_head_dim], q_rope, latent


def _score_scale(cfg: LongcatConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _ffn(cfg: LongcatConfig, f: dict, u):
    import jax

    mid = jax.nn.silu(_mm(cfg, u, f["w_gate"])) * _mm(cfg, u, f["w_up"])
    return _mm(cfg, mid, f["w_down"])


def route(cfg: LongcatConfig, layer: dict, u):
    """``u`` [N, D] float32 (the normed input) -> chosen experts [N, topk]
    int32 and their weights [N, topk] float32."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(u, layer["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(s + layer["router_bias"], cfg.moe_topk)
    weights = cfg.routed_scaling_factor * jnp.take_along_axis(s, chosen, axis=-1)
    return chosen.astype(jnp.int32), weights


def moe(cfg: LongcatConfig, layer: dict, u, live):
    """The held share of ``MoE(u)`` [N, D] float32 and the routing counts
    over the ``live`` rows: [held, absent, zero assignments, held experts
    with at least one live token]."""
    import jax
    import jax.numpy as jnp

    n = u.shape[0]
    lo, hi = cfg.held_experts
    chosen, weights = route(cfg, layer, u)
    is_zero = chosen >= cfg.n_routed_experts
    is_held = (chosen >= lo) & (chosen < hi)
    zero_weight = jnp.where(is_zero, weights, 0.0).sum(axis=-1)
    hit = (chosen - lo)[:, :, None] == jnp.arange(hi - lo, dtype=jnp.int32)   # [N, k, E]
    per_expert = jnp.where(hit, weights[:, :, None], 0.0).sum(axis=1)        # [N, E]
    e = layer["experts"]
    mid = jax.nn.silu(_mm(cfg, u, e["w_gate"])) * _mm(cfg, u, e["w_up"])     # [N, E * F]
    mid = (mid.reshape(n, hi - lo, -1) * per_expert[:, :, None]).reshape(n, -1)
    y = _mm(cfg, mid, e["w_down"]) + zero_weight[:, None] * u
    alive = live[:, None]
    touched = (hit & alive[:, :, None]).any(axis=(0, 1)).sum()
    counts = jnp.stack([(is_held & alive).sum(), (~is_held & ~is_zero & alive).sum(),
                        (is_zero & alive).sum(), touched]).astype(jnp.int32)
    return y, counts


def expanded_attention(cfg: LongcatConfig, a: dict, q_nope, q_rope, latent, mask):
    """Causal attention within one sequence in the expanded form: per-head
    keys and values from the latent [T, cache_width]; ``mask`` [T, T]."""
    import jax.numpy as jnp

    c = latent[:, :cfg.kv_lora_rank]
    k_rope = latent[:, cfg.kv_lora_rank:cfg.latent_width]
    k_nope = _contract(cfg, "tc,hcn->thn", c, a["w_uk"])
    v = _contract(cfg, "tc,hcv->thv", c, a["w_uv"])
    scores = (_contract(cfg, "thn,uhn->htu", q_nope, k_nope)
              + _contract(cfg, "thr,ur->htu", q_rope, k_rope)) * _score_scale(cfg)
    scores = jnp.where(mask[None], scores, -1e30)
    w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    w = w / w.sum(axis=-1, keepdims=True)
    out = _contract(cfg, "htu,uhv->thv", w, v)
    return _mm(cfg, out.reshape(out.shape[0], -1), a["wo"])


def absorbed_attention(cfg: LongcatConfig, a: dict, q_nope, q_rope, cache, sub: int,
                       page_table, n_ctx, impl: str):
    """One decode step's attention over the paged latent cache."""
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.ops.mla_decode import paged_mla_attention

    s_slots, heads = q_nope.shape[:2]
    q_lat = _contract(cfg, "shn,hcn->shc", q_nope, a["w_uk"])
    q = jnp.concatenate(
        [q_lat, q_rope,
         jnp.zeros((s_slots, heads, cfg.cache_width - cfg.latent_width), jnp.float32)],
        axis=-1) * _score_scale(cfg)
    o_lat = paged_mla_attention(q.astype(cache.dtype), cache, sub, page_table, n_ctx,
                                rank=cfg.kv_lora_rank, impl=impl)
    out = _contract(cfg, "shc,hcv->shv", o_lat, a["w_uv"])
    return _mm(cfg, out.reshape(s_slots, -1), a["wo"])


def _layers(cfg: LongcatConfig, params: dict, x, cache, live, attend):
    """The stack over ``x`` [N, D] float32.  ``attend(a, sub, x, cache)`` ->
    (cache, the sublayer's attention output)."""
    counts = 0        # [held, absent, zero assignments, held experts touched]
    for i, layer in enumerate(params["layers"]):
        shortcut = None
        for j in range(2):
            cache, out = attend(layer["attn"][j], 2 * i + j, x, cache)
            x = x + out
            u = _rms(x, layer["ffn"][j]["norm"], cfg.rms_norm_eps)
            if j == 0:      # issued before FFN_0, joined after FFN_1
                shortcut, c = moe(cfg, layer, u, live)
                counts = counts + c
            x = x + _ffn(cfg, layer["ffn"][j], u)
        x = x + shortcut
    return cache, x, counts


def prefill(cfg: LongcatConfig, params: dict, cache, tokens, length, page_ids):
    """One prompt at one bucket shape: ``tokens`` [T] (``length`` of them
    true), the slot's ``page_ids`` [max_pages].  Writes the latent of every
    true position (padding goes to the trash page 0) and returns (cache,
    the last true position's logits [V] float32, routing counts)."""
    import jax.numpy as jnp

    page = cache.shape[2]
    t = tokens.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    real = pos < length
    write_page = jnp.where(real, page_ids[pos // page], 0)
    write_off = pos % page
    mask = (pos[None, :] <= pos[:, None]) & real[None, :]
    cos, sin = _rope_angles(cfg, pos)

    def attend(a, sub, x, cache):
        q_nope, q_rope, latent = _queries_and_latent(cfg, a, x, cos, sin)
        cache = cache.at[sub, write_page, write_off].set(latent)
        return cache, expanded_attention(cfg, a, q_nope, q_rope, latent, mask)

    x = params["embed"][tokens].astype(jnp.float32)
    cache, x, counts = _layers(cfg, params, x, cache, real, attend)
    last = _rms(x[length - 1][None], params["final_norm"], cfg.rms_norm_eps)
    return cache, _mm(cfg, last, params["head"])[0], counts


def decode_step(cfg: LongcatConfig, params: dict, cache, page_table, lengths, last_tokens,
                active, *, attention: str):
    """One token for every slot (``runtime.decode``'s step contract): the
    consumed token's latent is written at position ``lengths[s]`` and the
    slot attends over 0..lengths[s].  Returns (cache, logits [S, V]
    float32, routing counts)."""
    import jax.numpy as jnp

    page = cache.shape[2]
    write_page = jnp.take_along_axis(page_table, (lengths // page)[:, None], axis=1)[:, 0]
    write_page = jnp.where(active, write_page, 0)
    write_off = lengths % page
    n_ctx = jnp.where(active, lengths + 1, 0)
    cos, sin = _rope_angles(cfg, lengths)

    def attend(a, sub, x, cache):
        q_nope, q_rope, latent = _queries_and_latent(cfg, a, x, cos, sin)
        cache = cache.at[sub, write_page, write_off].set(latent)
        return cache, absorbed_attention(cfg, a, q_nope, q_rope, cache, sub, page_table,
                                         n_ctx, attention)

    x = params["embed"][last_tokens].astype(jnp.float32)
    cache, x, counts = _layers(cfg, params, x, cache, active, attend)
    x = _rms(x, params["final_norm"], cfg.rms_norm_eps)
    return cache, _mm(cfg, x, params["head"]), counts


# --- the decoder the lane loads ------------------------------------------------------


class LongcatDecoder:
    """``runtime.decode``'s decoder interface over one artifact."""

    family = FAMILY
    text = False            # no tokenizer here: prompts are token ids
    eos_token = None

    def __init__(self, cfg: LongcatConfig, params: dict, attention: str | None = None):
        import jax

        self.cfg, self.params = cfg, params
        self.vocab_size = cfg.vocab_size
        # Mosaic compiles the kernel on a TPU; elsewhere the same attention
        # runs as XLA's gather (the interpreter is for tests, never served)
        self.attention = attention or (
            "kernel" if jax.default_backend() == "tpu" else "gather")

    @classmethod
    def load(cls, directory: str, attention: str | None = None) -> "LongcatDecoder":
        import jax

        cfg, flat = read_artifact(directory)
        return cls(cfg, nest({k: jax.device_put(v) for k, v in flat.items()}), attention)

    def cache_spec(self, num_pages: int, page_size: int):
        import jax.numpy as jnp

        return ((self.cfg.sublayers, num_pages, page_size, self.cfg.cache_width),
                jnp.dtype(self.cfg.compute_dtype))

    def prefill(self, params, cache, tokens, length, page_ids):
        return prefill(self.cfg, params, cache, tokens, length, page_ids)

    def decode_step(self, params, cache, page_table, lengths, last_tokens, active):
        return decode_step(self.cfg, params, cache, page_table, lengths, last_tokens,
                           active, attention=self.attention)

    def describe(self) -> dict:
        cfg = self.cfg
        return {"family": FAMILY, "layers": cfg.num_layers,
                "held_experts": list(cfg.held_experts),
                "routed_experts": cfg.n_routed_experts,
                "zero_experts": cfg.zero_expert_num, "experts_per_token": cfg.moe_topk,
                "latent_width": cfg.latent_width, "attention": self.attention}
