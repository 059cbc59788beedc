"""Kimi-K2 (``model_type`` ``kimi_k2``: DeepSeek-V3's layer), the plain
reference: float32 ``jax.numpy``, expanded MLA, a loop over experts, no
cache, no chunks, no kernels; imports nothing of the program.

One layer, as published (RMSNorm eps 1e-5, no bias anywhere; ``h`` is the
layer's input):

    a   = h + MLA(RMS(h))
    out = a + MLP(RMS(a))

``MLP`` is a dense SwiGLU FFN (width ``intermediate_size``) in the first
``first_k_dense_replace`` layers and the expert layer in every other.  After
the last layer: RMSNorm, then the untied head.

``MLA(x)``: ``c_q = RMS(x W_qa)``, ``q = c_q W_qb`` -> per head ``nope +
rope``; ``[c, k_r] = x W_kva``, ``c_kv = RMS(c)``; per-head keys and values
from ``c_kv``; RoPE on the rope part of ``q`` and on ``k_r``, which all
heads share; scores times ``scale``, causal softmax; output through ``W_o``.
YaRN (``rope_scaling``), DeepSeek-V3's form: over the ``rope / 2``
frequencies ``f_i = theta ** (-2i / rope)``,

    inv_freq_i = (f_i / factor) * (1 - m_i) + f_i * m_i
    m_i = 1 - clamp((i - low) / (high - low), 0, 1)
    low = floor(d(beta_fast)), high = ceil(d(beta_slow)), both inside [0, rope - 1]
    d(r) = rope * ln(original_max_position_embeddings / (2 pi r)) / (2 ln theta)

cos and sin are multiplied by ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)`` and ``scale = (nope + rope) ** -0.5 * m ** 2`` with ``m =
mscale(factor, mscale_all_dim)``, ``mscale(f, s) = 0.1 s ln f + 1``.

``MoE(x)``: ``s = sigmoid(x W_r)`` over all routed experts; the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` are
chosen (``noaux_tc`` with ``n_group = topk_group = 1``: the group limit
selects everything; ties to the lower index); weights ``s[chosen] / (sum +
1e-20) * routed_scaling_factor``; ``y = sum_k w_k E_k(x) + E_shared(x)``,
every expert a SwiGLU of width ``moe_intermediate_size`` (the shared one
``n_shared_experts`` times that).

Departures from the published description, each shared with the program:

- the chip's share: of the ``published.n_routed_experts`` routed experts
  only ``held_experts`` = [lo, hi) are held; the router scores all of them
  and what an absent expert would add is left out.  The shared expert is
  whole.  The vocabulary is the slice ``vocab_held``; ``num_hidden_layers``
  is cut.  No vision tower: the catalog's config holds the language model
  alone.
- RoPE pairs are interleaved (2i, 2i+1), DeepSeek's (``assumed``).
- the per-head up-projection is read as two tensors ``w_uk`` and ``w_uv``
  [heads, rank, dim], the published ``kv_b_proj`` split by rows; the held
  experts' matrices are stacked in ``experts.w_*`` [held, in, out].
- attention is computed ``assumed.reference_block`` keys at a time under a
  running softmax, so that the scores of 8,448 positions need not exist at
  once; the arithmetic is the softmax's.

``weights`` reads back what the artifact child wrote (the readers are the
first decoder family's, ``reference/longcat_flash.py``: one artifact layout
serves both); ``forward`` is the
whole model; ``embed`` / ``layer`` / ``head`` are the same a layer at a
time, for a child that cannot hold the cut in float32.  ``OPERAND`` (None
here) is a function every contraction's operands pass through: the control
sets it to a rounding through a narrower type and nothing else uses it.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

OPERAND = None


def _dot(spec: str, a, b):
    if OPERAND is not None:
        a, b = OPERAND(a), OPERAND(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# --- the weights: the artifact's layout is one for every decoder family -------------

from perfbench.reference.longcat_flash import read_tensors, weights, widen  # noqa: E402,F401


# --- the mathematics ----------------------------------------------------------------


def _rms(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(config: dict) -> np.ndarray:
    """The ``rope / 2`` rotary frequencies (float64) under ``rope_scaling``."""
    rope, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    freq = theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
    scaling = config.get("rope_scaling")
    if not scaling:
        return freq

    def d(rotations: float) -> float:
        return rope * math.log(scaling["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(d(scaling["beta_fast"])), 0)
    high = min(math.ceil(d(scaling["beta_slow"])), rope - 1)
    ramp = np.clip((np.arange(rope // 2) - low) / ((high - low) or 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return freq / scaling["factor"] * (1.0 - keep) + freq * keep


def softmax_scale(config: dict) -> float:
    scale = 1.0 / math.sqrt(config["qk_nope_head_dim"] + config["qk_rope_head_dim"])
    scaling = config.get("rope_scaling")
    if scaling:
        scale *= _mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)) ** 2
    return scale


def _rope(x, positions, config: dict):
    """x [..., T, dim] rotated pair by pair: (x[2i], x[2i+1]) is a complex
    number turned by ``position * inv_freq_i``."""
    scaling = config.get("rope_scaling")
    grow = 1.0
    if scaling:
        grow = (_mscale(scaling["factor"], scaling.get("mscale", 1))
                / _mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)))
    freq = jnp.asarray(yarn_inv_freq(config), jnp.float32)
    turn = jnp.exp(1j * positions[:, None].astype(jnp.float32) * freq[None, :]) * grow
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * turn
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def _swiglu(x, w_gate, w_up, w_down):
    return _dot("tf,fd->td", jax.nn.silu(_dot("td,df->tf", x, w_gate))
                * _dot("td,df->tf", x, w_up), w_down)


def mla(w: dict, p: str, x, config: dict):
    """x [T, D] (already normed) -> [T, D]; causal."""
    t = x.shape[0]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    positions = jnp.arange(t)
    c_q = _rms(_dot("td,dr->tr", x, w[p + "wq_a"]), w[p + "q_norm"], eps)
    q = _dot("tr,rk->tk", c_q, w[p + "wq_b"]).reshape(t, heads, nope + rope).transpose(1, 0, 2)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, config)   # [H, T, .]
    ckr = _dot("td,dk->tk", x, w[p + "wkv_a"])
    c_kv = _rms(ckr[:, :rank], w[p + "kv_norm"], eps)
    k_rope = _rope(ckr[:, rank:], positions, config)                        # [T, rope]
    k_nope = _dot("tc,hcn->htn", c_kv, w[p + "w_uk"])
    v = _dot("tc,hcv->htv", c_kv, w[p + "w_uv"])
    # the causal softmax, ``block`` keys at a time under a running maximum; a
    # scan, so that one block's scores exist at a time
    block = min(t, int(config["assumed"]["reference_block"]))
    blocks = -(-t // block)
    pad = blocks * block - t         # keys past the sequence's end: seen by no row

    def one_block(carry, keys):
        best, total, acc = carry
        k_nope_b, k_rope_b, v_b, positions_b = keys
        scores = (_dot("htn,hun->htu", q_nope, k_nope_b)
                  + _dot("htr,ur->htu", q_rope, k_rope_b)) * softmax_scale(config)
        seen = positions_b[None, :] <= positions[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        new_best = jnp.maximum(best, scores.max(axis=-1, keepdims=True))
        p_block = jnp.exp(scores - new_best)       # block 0 holds key 0, which every row sees
        shrink = jnp.exp(best - new_best)
        return (new_best, shrink * total + p_block.sum(axis=-1, keepdims=True),
                shrink * acc + _dot("htu,huv->htv", p_block, v_b)), None

    start = (jnp.full((heads, t, 1), -jnp.inf), jnp.zeros((heads, t, 1)),
             jnp.zeros((heads, t, v.shape[-1])))
    (_, total, acc), _ = jax.lax.scan(one_block, start, (
        jnp.pad(k_nope, ((0, 0), (0, pad), (0, 0))).reshape(
            heads, blocks, block, nope).transpose(1, 0, 2, 3),
        jnp.pad(k_rope, ((0, pad), (0, 0))).reshape(blocks, block, rope),
        jnp.pad(v, ((0, 0), (0, pad), (0, 0))).reshape(
            heads, blocks, block, -1).transpose(1, 0, 2, 3),
        jnp.pad(positions, (0, pad), constant_values=t).reshape(blocks, block)))
    out = (acc / total).transpose(1, 0, 2).reshape(t, -1)
    return _dot("tk,kd->td", out, w[p + "wo"])


def route(w: dict, p: str, x, config: dict):
    """The chosen experts [T, topk] and their weights."""
    s = jax.nn.sigmoid(_dot("td,de->te", x, w[p + "router"]))
    order = jnp.argsort(-(s + w[p + "router_bias"]), axis=-1, stable=True)
    chosen = order[:, :config["num_experts_per_tok"]]
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    if config.get("norm_topk_prob", True) and config["num_experts_per_tok"] > 1:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * config["routed_scaling_factor"]


def moe(w: dict, p: str, x, config: dict, held=None, shared: bool = True,
        routing: bool = False):
    """The share of ``MoE(x)`` that the routed experts in ``held`` = [lo, hi)
    (the configuration's ``held_experts`` by default) give, with the shared
    expert's term where ``shared``.  ``w[p + "experts.*"]`` holds experts
    lo..hi-1.  With ``routing`` also the chosen experts [T, topk]."""
    lo, hi = held if held is not None else config["held_experts"]
    chosen, gates = route(w, p, x, config)
    y = jnp.zeros_like(x)
    if shared:
        y = _swiglu(x, w[p + "shared.w_gate"], w[p + "shared.w_up"], w[p + "shared.w_down"])
    for e in range(lo, hi):
        gate = jnp.where(chosen == e, gates, 0.0).sum(axis=-1, keepdims=True)
        y = y + gate * _swiglu(x, w[p + "experts.w_gate"][e - lo], w[p + "experts.w_up"][e - lo],
                               w[p + "experts.w_down"][e - lo])
    return (y, chosen) if routing else y


def embed(w: dict, ids):
    return w["embed"][ids]


def layer(w: dict, i: int, h, config: dict, routing: bool = False):
    """One layer over ``h`` [T, D]; ``w`` holds at least ``layers.<i>.*``.
    The layer is dense or an expert layer by the tensors it has.  With
    ``routing`` also the experts its router chose, [T, topk] (an empty
    [T, 0] from a dense layer)."""
    p, eps = f"layers.{i}.", config["rms_norm_eps"]
    a = h + mla(w, p + "attn.", _rms(h, w[p + "attn.norm"], eps), config)
    u = _rms(a, w[p + "mlp_norm"], eps)
    if p + "router" in w:
        y, chosen = moe(w, p, u, config, routing=True)
    else:
        y = _swiglu(u, w[p + "ffn.w_gate"], w[p + "ffn.w_up"], w[p + "ffn.w_down"])
        chosen = jnp.zeros((h.shape[0], 0), jnp.int32)
    return (a + y, chosen) if routing else a + y


def head(w: dict, h, config: dict):
    return _dot("td,dv->tv", _rms(h, w["final_norm"], config["rms_norm_eps"]), w["head"])


def forward(w: dict, ids, config: dict):
    """float32 logits [T, vocab_held] of a causal full forward over ``ids``."""
    h = embed(w, ids)
    for i in range(config["num_hidden_layers"]):
        h = layer(w, i, h, config)
    return head(w, h, config)
