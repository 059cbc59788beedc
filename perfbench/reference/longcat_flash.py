"""LongCat-Flash, the plain reference: float32 ``jax.numpy``, expanded MLA,
a loop over experts, no cache, no kernels; imports nothing of the program.

One layer, as published (hidden 6144, RMSNorm eps 1e-5, no bias anywhere;
``h`` is the layer's input):

    a0  = h  + MLA_0(RMS(h));   u0 = RMS(a0);   m = MoE(u0)
    b0  = a0 + FFN_0(u0)
    a1  = b0 + MLA_1(RMS(b0));  u1 = RMS(a1)
    out = a1 + FFN_1(u1) + m            # the shortcut lands here

``MoE(x)``: ``s = softmax(x W_r)`` over the real and the zero-compute
experts; the ``moe_topk`` largest of ``s + bias`` are chosen (ties to the
lower index), weights ``routed_scaling_factor * s`` (not renormalised); a
real expert is SwiGLU, a zero-compute expert returns ``x``.  ``MLA(x)``:
``c_q = RMS(x W_qa)``, ``q = c_q W_qb * sqrt(hidden / q_lora_rank)``;
``[c, k_r] = x W_kva``, ``c_kv = RMS(c) * sqrt(hidden / kv_lora_rank)``;
per-head keys and values from ``c_kv``; RoPE (theta 1e7) on the rope part of
``q`` and on ``k_r``, which all heads share; scores over ``sqrt(nope +
rope)``, causal softmax; output through ``W_o``.  After the last layer:
RMSNorm, then the head.

Departures from the published description, each shared with the program:

- the chip's share: of the ``published.n_routed_experts`` real experts only
  ``held_experts`` = [lo, hi) are held; the router scores all of them and
  what an absent expert would add is left out.  The vocabulary is the
  slice ``vocab_held``.  ``num_layers`` is cut.
- the config gives ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` as booleans:
  the factors' placement above, the absence of a router bias term in the
  product and of a renormalisation of the chosen scores, and the RoPE
  pairing (interleaved pairs 2i, 2i+1, DeepSeek's) follow the release's
  modeling code as the issue recalls it (``assumed`` in the configuration).
- the per-head up-projection is read as two tensors ``w_uk`` and ``w_uv``
  [heads, rank, dim], the published ``kv_b_proj`` split by rows; the held
  experts' matrices lie side by side in ``experts.w_*`` (expert ``e`` of
  the held range owns columns ``e * F .. (e + 1) * F``).

``weights`` reads back what the artifact child wrote; ``forward`` is the
whole model; ``embed`` / ``layer`` / ``head`` are the same a layer at a
time, for a child that cannot hold the cut in float32.  ``OPERAND`` (None
here) is a function every contraction's operands pass through: the control
sets it to a rounding through a narrower type and nothing else uses it.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

OPERAND = None


def _dot(spec: str, a, b):
    if OPERAND is not None:
        a, b = OPERAND(a), OPERAND(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# --- the weights -----------------------------------------------------------------


def _index(config: dict, artifact_dir: str) -> tuple[str, dict]:
    model_dir = os.path.join(artifact_dir, config["served_name"])
    version = max(int(d) for d in os.listdir(model_dir) if d.isdigit())
    directory = os.path.join(model_dir, str(version))
    with open(os.path.join(directory, "decoder.json")) as f:
        return directory, json.load(f)["tensors"]


def read_tensors(config: dict, artifact_dir: str, prefix: str = "", bits: bool = False) -> dict:
    """The artifact's tensors whose name starts with ``prefix``, as float32
    (bfloat16 lies on disk as its 16 bits: the high half of a float32).
    With ``bits`` a bfloat16 tensor comes as those 16 bits, memory-mapped
    and untouched, for ``widen`` to turn into float32 on the device."""
    directory, index = _index(config, artifact_dir)
    out = {}
    for name, entry in index.items():
        if not name.startswith(prefix):
            continue
        path = os.path.join(directory, entry["file"])
        if entry["dtype"] != "bfloat16":
            value = np.fromfile(path, np.float32)
        elif bits:
            value = np.memmap(path, mode="r", dtype=np.uint16)
        else:
            value = (np.fromfile(path, np.uint16).astype(np.uint32) << 16).view(np.float32)
        out[name] = value.reshape(entry["shape"])
    return out


def widen(value):
    """A device array of bfloat16 bits (uint16) as the float32 they name;
    anything else as it is."""
    value = jnp.asarray(value)
    if value.dtype != jnp.uint16:
        return value
    return jax.lax.bitcast_convert_type(value.astype(jnp.uint32) << 16, jnp.float32)


def weights(config: dict, seed: int, artifact_dir: str) -> dict:
    """Every tensor, float32, by its name in the artifact."""
    return read_tensors(config, artifact_dir)


# --- the mathematics ----------------------------------------------------------------


def _rms(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x [..., T, dim] rotated pair by pair: (x[2i], x[2i+1]) is a complex
    number turned by ``position * theta ** (-2i / dim)``."""
    dim = x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    turn = jnp.exp(1j * positions[:, None].astype(jnp.float32) * freq[None, :])
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
    hidden = config["hidden_size"]
    positions = jnp.arange(t)
    c_q = _rms(_dot("td,dr->tr", x, w[p + "wq_a"]), w[p + "q_norm"], eps)
    q = _dot("tr,rk->tk", c_q, w[p + "wq_b"])
    if config["mla_scale_q_lora"]:
        q = q * math.sqrt(hidden / config["q_lora_rank"])
    q = q.reshape(t, heads, nope + rope).transpose(1, 0, 2)             # [H, T, .]
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, config["rope_theta"])
    ckr = _dot("td,dk->tk", x, w[p + "wkv_a"])
    c_kv = _rms(ckr[:, :rank], w[p + "kv_norm"], eps)
    if config["mla_scale_kv_lora"]:
        c_kv = c_kv * math.sqrt(hidden / rank)
    k_rope = _rope(ckr[:, rank:], positions, config["rope_theta"])     # [T, rope]
    k_nope = _dot("tc,hcn->htn", c_kv, w[p + "w_uk"])
    v = _dot("tc,hcv->htv", c_kv, w[p + "w_uv"])
    scores = (_dot("htn,hun->htu", q_nope, k_nope)
              + _dot("htr,ur->htu", q_rope, k_rope)) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((t, t), bool))
    weights_ = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = _dot("htu,huv->thv", weights_, v).reshape(t, -1)
    return _dot("tk,kd->td", out, w[p + "wo"])


def route(w: dict, p: str, x, config: dict):
    """The chosen experts [T, topk] and their weights."""
    s = jax.nn.softmax(_dot("td,de->te", x, w[p + "router"]), axis=-1)
    order = jnp.argsort(-(s + w[p + "router_bias"]), axis=-1, stable=True)
    chosen = order[:, :config["moe_topk"]]
    return chosen, config["routed_scaling_factor"] * jnp.take_along_axis(s, chosen, axis=-1)


def moe(w: dict, p: str, x, config: dict, held=None, routing: bool = False):
    """The share of ``MoE(x)`` that the real experts in ``held`` = [lo, hi)
    (the configuration's ``held_experts`` by default) and the zero-compute
    experts give.  ``w[p + "experts.*"]`` holds experts lo..hi-1.  With
    ``routing`` also the chosen experts [T, topk]."""
    lo, hi = held if held is not None else config["held_experts"]
    real = config["published"]["n_routed_experts"]
    width = config["expert_ffn_hidden_size"]
    chosen, gates = route(w, p, x, config)
    y = jnp.where(chosen >= real, gates, 0.0).sum(axis=-1, keepdims=True) * x
    for e in range(lo, hi):
        cols = slice((e - lo) * width, (e - lo + 1) * width)
        gate = jnp.where(chosen == e, gates, 0.0).sum(axis=-1, keepdims=True)
        y = y + gate * _swiglu(x, w[p + "experts.w_gate"][:, cols],
                               w[p + "experts.w_up"][:, cols],
                               w[p + "experts.w_down"][cols, :])
    return (y, chosen) if routing else y


def embed(w: dict, ids):
    return w["embed"][ids]


def layer(w: dict, i: int, h, config: dict, routing: bool = False):
    """One layer over ``h`` [T, D]; ``w`` holds at least ``layers.<i>.*``.
    With ``routing`` also the experts its router chose, [T, topk]."""
    p, eps = f"layers.{i}.", config["rms_norm_eps"]
    a0 = h + mla(w, p + "attn.0.", _rms(h, w[p + "attn.0.norm"], eps), config)
    u0 = _rms(a0, w[p + "ffn.0.norm"], eps)
    m, chosen = moe(w, p, u0, config, routing=True)
    b0 = a0 + _swiglu(u0, w[p + "ffn.0.w_gate"], w[p + "ffn.0.w_up"], w[p + "ffn.0.w_down"])
    a1 = b0 + mla(w, p + "attn.1.", _rms(b0, w[p + "attn.1.norm"], eps), config)
    u1 = _rms(a1, w[p + "ffn.1.norm"], eps)
    out = a1 + _swiglu(u1, w[p + "ffn.1.w_gate"], w[p + "ffn.1.w_up"],
                       w[p + "ffn.1.w_down"]) + m
    return (out, chosen) if routing else out


def head(w: dict, h, config: dict):
    return _dot("td,dv->tv", _rms(h, w["final_norm"], config["rms_norm_eps"]), w["head"])


def forward(w: dict, ids, config: dict):
    """float32 logits [T, vocab_held] of a causal full forward over ``ids``."""
    h = embed(w, ids)
    for i in range(config["num_layers"]):
        h = layer(w, i, h, config)
    return head(w, h, config)
