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
cache (``ops.mla_decode``).  That code, and the artifact's layout, is
``models.latent_attention``'s, shared with the lane's other decoders; what
is this model's own there is the pair of ``mla_scale_*`` factors.  Weights
and matmul operands are bfloat16 with float32 accumulation; the residual
stream, the norms' statistics, the router and the softmaxes are float32.

The interface the lane asks of a decoder (``runtime.decode.DecodeEngine``):
``vocab_size``, ``eos_token``, ``text`` (whether prompts may be text),
``params``, ``cache_spec``, ``prefill``, ``decode_step``, ``describe``, and
the one this decoder offers beside them, ``prefill_and_step``.
"""

from __future__ import annotations

import dataclasses
import math

from kubernetes_deep_learning_tpu.models import latent_attention as la
from kubernetes_deep_learning_tpu.models.latent_attention import (  # noqa: F401
    nest,
    tensor_dtype,
)

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
    def mla(self) -> la.LatentSpec:
        """What the shared latent-attention code reads of this model: the
        published widths and LongCat's own ``mla_scale_*`` factors."""
        return la.LatentSpec(
            self.hidden_size, self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rms_norm_eps, self.rope_theta, self.compute_dtype,
            q_scale=(math.sqrt(self.hidden_size / self.q_lora_rank)
                     if self.mla_scale_q_lora else 1.0),
            kv_scale=(math.sqrt(self.hidden_size / self.kv_lora_rank)
                      if self.mla_scale_kv_lora else 1.0))

    @property
    def latent_width(self) -> int:
        return self.mla.latent_width

    @property
    def cache_width(self) -> int:
        return self.mla.cache_width

    @property
    def sublayers(self) -> int:
        return 2 * self.num_layers

    def tensor_shapes(self) -> dict[str, tuple[int, ...]]:
        """Every weight by its name in the artifact, with its shape."""
        d = self.hidden_size
        ef = self.n_held * self.expert_ffn_hidden_size
        out = {"embed": (self.vocab_size, d), "final_norm": (d,),
               "head": (d, self.vocab_size)}
        for i in range(self.num_layers):
            p = f"layers.{i}."
            for j in range(2):
                out.update(self.mla.tensor_shapes(f"{p}attn.{j}."))
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


# --- the artifact (layout: ``models.latent_attention``) ----------------------------------


def write_artifact(directory: str, config: dict, tensors) -> None:
    """``tensors``: (name, numpy array) pairs, float32 or uint16 (bfloat16
    bits); shapes are held to ``tensor_shapes``."""
    la.write_artifact(directory, FAMILY, config,
                      LongcatConfig.from_dict(config).tensor_shapes(), tensors)


def read_artifact(directory: str) -> tuple[LongcatConfig, dict]:
    """The configuration and a flat {name: memory-mapped array} (bfloat16
    where the index says so)."""
    config, flat = la.read_artifact(
        directory, FAMILY, lambda c: LongcatConfig.from_dict(c).tensor_shapes())
    return LongcatConfig.from_dict(config), flat


# --- the mathematics (latent attention: ``models.latent_attention``) ------------------


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
    """The held share of ``MoE(u)`` [N, D] float32 and the lane's counts
    (``runtime.decode.N_COUNTS``): over the ``live`` rows [held, absent, zero
    assignments, held experts with at least one live token], then the (row,
    held expert) products computed -- every row through every held expert --
    and the rows a shared expert met (none here).  ``live`` may be a tuple
    of masks, one a part of the rows in their order: the counts are then
    [parts, N_COUNTS]."""
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
    mid = jax.nn.silu(la.mm(cfg, u, e["w_gate"])) * la.mm(cfg, u, e["w_up"])     # [N, E * F]
    mid = (mid.reshape(n, hi - lo, -1) * per_expert[:, :, None]).reshape(n, -1)
    y = la.mm(cfg, mid, e["w_down"]) + zero_weight[:, None] * u

    def tally(rows, live):
        h, held, zero, alive = hit[rows], is_held[rows], is_zero[rows], live[:, None]
        touched = (h & alive[:, :, None]).any(axis=(0, 1)).sum()
        return jnp.stack([(held & alive).sum(), (~held & ~zero & alive).sum(),
                          (zero & alive).sum(), touched, h.shape[0] * (hi - lo),
                          0]).astype(jnp.int32)

    return y, la.tally_parts(tally, live)


def _layers(cfg: LongcatConfig, params: dict, x, cache, live, attend):
    """The stack over ``x`` [N, D] float32.  ``attend(a, sub, x, cache)`` ->
    (cache, the sublayer's attention output).  ``live``: a mask, or a tuple
    of them by part (``moe``)."""
    counts = 0        # summed over the expert layers (``moe``)
    for i, layer in enumerate(params["layers"]):
        shortcut = None
        for j in range(2):
            cache, out = attend(layer["attn"][j], 2 * i + j, x, cache)
            x = x + out
            u = la.rms(x, layer["ffn"][j]["norm"], cfg.rms_norm_eps)
            if j == 0:      # issued before FFN_0, joined after FFN_1
                shortcut, c = moe(cfg, layer, u, live)
                counts = counts + c
            x = x + la.ffn(cfg, layer["ffn"][j], u)
        x = x + shortcut
    return cache, x, counts


def prefill(cfg: LongcatConfig, params: dict, cache, tokens, start, length, page_ids, *,
            attention: str = "gather"):
    """One chunk of a prompt at one compiled shape (``runtime.decode``'s
    prefill contract): ``tokens`` [T] at positions ``start ..``, true below
    ``length``; the slot's ``page_ids`` [max_pages].  Writes the latent of
    every true position (padding goes to the trash page 0), attends over
    [0, start + T) and returns (cache, the last true position's logits [V]
    float32, counts)."""
    import jax.numpy as jnp

    geometry = la.chunk_positions(cache, tokens, start, length, page_ids)
    pos, real = geometry[:2]
    cos, sin = la.rope_angles(cfg.mla, pos)

    def attend(a, sub, x, cache):
        return la.attend_chunk(cfg.mla, a, sub, x, cache, geometry, cos, sin, page_ids, start,
                               attention)

    x = params["embed"][tokens].astype(jnp.float32)
    cache, x, counts = _layers(cfg, params, x, cache, real, attend)
    last = la.rms(x[length - 1 - start][None], params["final_norm"], cfg.rms_norm_eps)
    return cache, la.mm(cfg, last, params["head"])[0], counts


def decode_step(cfg: LongcatConfig, params: dict, cache, page_table, lengths, last_tokens,
                active, *, attention: str):
    """One token for every slot (``runtime.decode``'s step contract): the
    consumed token's latent is written at position ``lengths[s]`` and the
    slot attends over 0..lengths[s].  Returns (cache, logits [S, V]
    float32, counts)."""
    import jax.numpy as jnp

    cos, sin = la.rope_angles(cfg.mla, lengths)

    def attend(a, sub, x, cache):
        return la.attend_step(cfg.mla, a, sub, x, cache, page_table, lengths, active,
                              cos, sin, attention)

    x = params["embed"][last_tokens].astype(jnp.float32)
    cache, x, counts = _layers(cfg, params, x, cache, active, attend)
    x = la.rms(x, params["final_norm"], cfg.rms_norm_eps)
    return cache, la.mm(cfg, x, params["head"]), counts


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

    def prefill(self, params, cache, tokens, start, length, page_ids):
        return prefill(self.cfg, params, cache, tokens, start, length, page_ids,
                       attention=self.attention)

    def prefill_attention(self, rows: int, first: bool) -> str:
        """The form a chunk's attention takes at a compiled shape."""
        return la.chunk_attention_form(rows, first, self.attention)

    def decode_step(self, params, cache, page_table, lengths, last_tokens, active):
        return decode_step(self.cfg, params, cache, page_table, lengths, last_tokens,
                           active, attention=self.attention)

    def prefill_and_step(self, params, cache, tokens, start, length, page_ids, page_table,
                         lengths, last_tokens, active):
        """A chunk and a step as one program (``la.round_forward``)."""
        return la.round_forward(self.cfg, params, _layers, cache, tokens, start, length,
                                page_ids, page_table, lengths, last_tokens, active,
                                self.attention)

    def describe(self) -> dict:
        cfg = self.cfg
        return {"family": FAMILY, "layers": cfg.num_layers,
                "held_experts": list(cfg.held_experts),
                "routed_experts": cfg.n_routed_experts,
                "zero_experts": cfg.zero_expert_num, "experts_per_token": cfg.moe_topk,
                "latent_width": cfg.latent_width, "attention": self.attention}


DECODER = LongcatDecoder      # what ``runtime.decode.load_decoder`` asks a family's module for
