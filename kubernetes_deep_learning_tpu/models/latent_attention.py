"""Latent (MLA) attention and the decoder artifact, shared by the generative
lane's decoders (``models.longcat_flash``, ``models.kimi_k2``).

``LatentSpec`` is what one model says of its attention: the published
widths, the factors a model may put on ``q`` and on ``c_kv`` (LongCat's
``mla_scale_*``; 1 elsewhere) and the rotary frequencies' scaling (YaRN,
DeepSeek-V3's form; none elsewhere).  The cache holds, a position a
sublayer, the normed (and scaled) compression ``c_kv`` and the rotated
shared key ``k_r`` -- ``latent_width`` values padded to ``cache_width``, a
multiple of the chip's 128 lanes.  Four forms of the same attention:

- ``expanded_attention``: one sequence against itself, per-head keys and
  values expanded from the latents (a prompt's first chunk, in XLA);
- ``paged_chunk_attention``: a later chunk of a prompt -- its queries
  against the positions already in the paged cache, read ``KEY_BLOCK``
  positions at a time through the slot's page list and expanded block by
  block under a running softmax, and against the chunk's own positions,
  causally (in XLA: every block's ``[heads, rows, block]`` scores cross
  HBM);
- ``kernel_chunk_attention``: either kind of chunk in ``ops.mla_chunk``'s
  kernel -- the slot's positions, the chunk's own included, read through
  the page list as one stream and expanded block by block, the scores and
  the running softmax never leaving VMEM.  Which chunks take it is a rule
  of their compiled shape (``chunk_attention_form``); the two forms above
  are what it is held to and what a backend without Mosaic serves;
- ``absorbed_attention``: a decode step's single query a slot, carried into
  the latent space and scored against the cache as it lies
  (``ops.mla_decode``).

``round_forward`` runs a prefill chunk and a decode step as one forward:
every product reads its weights once for both, and only attention is split.

Weights and matmul operands are ``compute_dtype`` (bfloat16 as served) with
float32 accumulation; norms' statistics, rotations and softmaxes are
float32.  ``cfg`` below is anything with a ``compute_dtype``: a model's
configuration or its ``LatentSpec``.

The artifact: ``<root>/<name>/<version>/decoder.json`` = {"family",
"config", "tensors": {name: {"file", "shape", "dtype"}}}; each tensor a raw
little-endian file beside it (bfloat16 as its 16 bits).  ``family`` names
the module of ``models/`` that serves it (``runtime.decode.load_decoder``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

from kubernetes_deep_learning_tpu.export.artifact import DECODER_FILE

# Cached positions a later chunk reads and expands at a time: scores of
# [heads, chunk, KEY_BLOCK] float32 (134 MB at 64 heads and 1,024 rows).
KEY_BLOCK = 512
_MASKED = -1e30
# Rows from which a prompt's first chunk runs the chunk kernel too (every
# later chunk does): the lane's chunk size.  A shorter first chunk is a whole
# short prompt in one program, whose scores are small (PERF.md section 6,
# PRs 35-36), and keeps the plain form and the program it compiled before.
KERNEL_FIRST_ROWS = 1024


@dataclasses.dataclass(frozen=True)
class Yarn:
    """``rope_scaling`` of type ``yarn`` as DeepSeek-V3's modeling code reads it."""
    factor: float
    beta_fast: float
    beta_slow: float
    original_max_position_embeddings: int
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @classmethod
    def from_dict(cls, d: dict | None) -> "Yarn | None":
        if not d:
            return None
        if d.get("type", d.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling of type {d.get('type')!r}: only yarn is served")
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(**{k: d[k] for k in names if k in d})

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def softmax_mscale(self) -> float:
        """``m``: the softmax scale is multiplied by its square."""
        return self._mscale(self.factor, self.mscale_all_dim)

    @property
    def rotation_mscale(self) -> float:
        """What cos and sin are multiplied by (1 where the two mscales agree)."""
        return self._mscale(self.factor, self.mscale) / self._mscale(
            self.factor, self.mscale_all_dim)

    def keep_mask(self, dim: int, theta: float) -> np.ndarray:
        """``m_i`` over the ``dim / 2`` frequencies: 1 where a frequency stays
        as it is (it turns more than ``beta_fast`` times inside the original
        context), 0 where it is divided by ``factor``, a ramp between."""
        def turns_at(rotations: float) -> float:
            return dim * math.log(self.original_max_position_embeddings
                                  / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(turns_at(self.beta_fast)), 0)
        high = min(math.ceil(turns_at(self.beta_slow)), dim - 1)
        span = (high - low) or 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / span, 0.0, 1.0)
        return (1.0 - ramp).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    compute_dtype: str
    q_scale: float = 1.0       # on q after W_qb
    kv_scale: float = 1.0      # on c_kv after its norm
    yarn: Yarn | None = None

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        return -(-self.latent_width // 128) * 128

    @property
    def score_scale(self) -> float:
        scale = 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)
        if self.yarn is not None:
            scale *= self.yarn.softmax_mscale ** 2
        return scale

    def tensor_shapes(self, prefix: str) -> dict[str, tuple[int, ...]]:
        """One sublayer's weights under ``prefix`` (its input norm included)."""
        d, h = self.hidden_size, self.num_attention_heads
        return {
            prefix + "norm": (d,),
            prefix + "wq_a": (d, self.q_lora_rank),
            prefix + "q_norm": (self.q_lora_rank,),
            prefix + "wq_b": (self.q_lora_rank,
                              h * (self.qk_nope_head_dim + self.qk_rope_head_dim)),
            prefix + "wkv_a": (d, self.latent_width),
            prefix + "kv_norm": (self.kv_lora_rank,),
            prefix + "w_uk": (h, self.kv_lora_rank, self.qk_nope_head_dim),
            prefix + "w_uv": (h, self.kv_lora_rank, self.v_head_dim),
            prefix + "wo": (h * self.v_head_dim, d),
        }


# --- the artifact ---------------------------------------------------------------

FLOAT32_TENSORS = ("router_bias",)     # every other tensor is bfloat16


def tensor_dtype(name: str) -> str:
    return "float32" if name.rsplit(".", 1)[-1] in FLOAT32_TENSORS else "bfloat16"


def write_artifact(directory: str, family: str, config: dict, shapes: dict, tensors) -> None:
    """``tensors``: (name, numpy array) pairs, float32 or uint16 (bfloat16
    bits); held to ``shapes`` ({name: shape}, the family's ``tensor_shapes``)."""
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
        json.dump({"family": family, "config": config, "tensors": index}, f)


def read_meta(directory: str) -> dict:
    with open(os.path.join(directory, DECODER_FILE)) as f:
        return json.load(f)


def read_artifact(directory: str, family: str, shapes_of) -> tuple[dict, dict]:
    """The artifact's ``config`` and a flat {name: memory-mapped array}
    (bfloat16 where the index says so); ``shapes_of(config)`` gives the
    shapes the family asks for."""
    import ml_dtypes

    meta = read_meta(directory)
    if meta.get("family") != family:
        raise ValueError(f"{directory}: decoder family {meta.get('family')!r}, "
                         f"not {family!r}")
    flat = {}
    for name, shape in shapes_of(meta["config"]).items():
        entry = meta["tensors"][name]
        if tuple(entry["shape"]) != shape or entry["dtype"] != tensor_dtype(name):
            raise ValueError(f"{directory}: {name} is {entry}, the configuration "
                             f"asks for {shape} {tensor_dtype(name)}")
        raw = np.memmap(os.path.join(directory, entry["file"]), mode="r",
                        dtype=np.uint16 if entry["dtype"] == "bfloat16" else np.float32,
                        shape=shape)
        flat[name] = raw.view(ml_dtypes.bfloat16) if entry["dtype"] == "bfloat16" else raw
    return meta["config"], flat


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


def rms(x, weight, eps: float):
    """RMSNorm with float32 statistics; float32 out."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


def contract(cfg, spec: str, a, b):
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


def mm(cfg, x, w):
    return contract(cfg, "nk,kd->nd", x, w)


def ffn(cfg, f: dict, u):
    """SwiGLU: ``f`` holds ``w_gate``, ``w_up`` [D, F] and ``w_down`` [F, D]."""
    import jax

    mid = jax.nn.silu(mm(cfg, u, f["w_gate"])) * mm(cfg, u, f["w_up"])
    return mm(cfg, mid, f["w_down"])


def rope_angles(spec: LatentSpec, positions):
    """cos and sin [N, rope / 2] of the rotary angles at ``positions``."""
    import jax.numpy as jnp

    half = spec.qk_rope_head_dim // 2
    inv = spec.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if spec.yarn is not None:
        keep = jnp.asarray(spec.yarn.keep_mask(spec.qk_rope_head_dim, spec.rope_theta))
        inv = inv / spec.yarn.factor * (1.0 - keep) + inv * keep
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if spec.yarn is not None and spec.yarn.rotation_mscale != 1.0:
        cos, sin = cos * spec.yarn.rotation_mscale, sin * spec.yarn.rotation_mscale
    return cos, sin


def rope(x, cos, sin):
    """Rotate the interleaved pairs (2i, 2i+1) of the last axis; ``cos`` and
    ``sin`` broadcast over the axes between the first and the last."""
    import jax.numpy as jnp

    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.stack([even * c - odd * s, even * s + odd * c], axis=-1).reshape(x.shape)


def queries_and_latent(spec: LatentSpec, a: dict, x, cos, sin):
    """From a sublayer's input ``x`` [N, D] (float32): queries ``q_nope``
    [N, H, nope], rotated ``q_rope`` [N, H, rope] (float32) and what the
    positions leave in the cache, [N, cache_width] in the compute dtype."""
    import jax.numpy as jnp

    n = x.shape[0]
    h = rms(x, a["norm"], spec.rms_norm_eps)
    cq = rms(mm(spec, h, a["wq_a"]), a["q_norm"], spec.rms_norm_eps)
    q = mm(spec, cq, a["wq_b"])
    if spec.q_scale != 1.0:
        q = q * spec.q_scale
    q = q.reshape(n, spec.num_attention_heads, spec.qk_nope_head_dim + spec.qk_rope_head_dim)
    ckr = mm(spec, h, a["wkv_a"])
    c = rms(ckr[:, :spec.kv_lora_rank], a["kv_norm"], spec.rms_norm_eps)
    if spec.kv_scale != 1.0:
        c = c * spec.kv_scale
    kr = rope(ckr[:, spec.kv_lora_rank:], cos, sin)
    latent = jnp.concatenate(
        [c, kr, jnp.zeros((n, spec.cache_width - spec.latent_width), jnp.float32)],
        axis=-1).astype(jnp.dtype(spec.compute_dtype))
    q_rope = rope(q[..., spec.qk_nope_head_dim:], cos, sin)
    return q[..., :spec.qk_nope_head_dim], q_rope, latent


def _expanded_scores(spec: LatentSpec, a: dict, q_nope, q_rope, latent):
    """Scores [H, T, U] of queries [T, ...] against the keys expanded from
    ``latent`` [U, cache_width], and those positions' values [U, H, v]."""
    c = latent[:, :spec.kv_lora_rank]
    k_rope = latent[:, spec.kv_lora_rank:spec.latent_width]
    k_nope = contract(spec, "tc,hcn->thn", c, a["w_uk"])
    v = contract(spec, "tc,hcv->thv", c, a["w_uv"])
    scores = (contract(spec, "thn,uhn->htu", q_nope, k_nope)
              + contract(spec, "thr,ur->htu", q_rope, k_rope)) * spec.score_scale
    return scores, v


def project(spec: LatentSpec, a: dict, heads, wo: bool):
    """The heads' values [N, H * v] through the output projection, or as
    they are where ``wo`` is false (the caller projects them, with others)."""
    return mm(spec, heads, a["wo"]) if wo else heads


def expanded_attention(spec: LatentSpec, a: dict, q_nope, q_rope, latent, mask,
                       wo: bool = True):
    """Causal attention within one sequence in the expanded form: per-head
    keys and values from the latent [T, cache_width]; ``mask`` [T, T].  The
    output projection is ``project``'s (so in the three forms below)."""
    import jax.numpy as jnp

    scores, v = _expanded_scores(spec, a, q_nope, q_rope, latent)
    scores = jnp.where(mask[None], scores, _MASKED)
    w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    w = w / w.sum(axis=-1, keepdims=True)
    out = contract(spec, "htu,uhv->thv", w, v)
    return project(spec, a, out.reshape(out.shape[0], -1), wo)


def paged_chunk_attention(spec: LatentSpec, a: dict, q_nope, q_rope, latent, mask, cache,
                          sub: int, page_ids, start, wo: bool = True):
    """A later chunk of a prompt: its queries [T, ...] against positions
    [0, ``start``) of the slot's pages (all of them visible to every row of
    the chunk) and, under ``mask`` [T, T], against the chunk's own
    ``latent``.  The cached positions are read ``KEY_BLOCK`` at a time, only
    as many blocks as ``start`` asks for, and join a running softmax."""
    import jax
    import jax.numpy as jnp

    page = cache.shape[2]
    block_pages = max(1, KEY_BLOCK // page)
    block = block_pages * page
    padded = -(-page_ids.shape[0] // block_pages) * block_pages
    page_ids = jnp.zeros((padded,), jnp.int32).at[:page_ids.shape[0]].set(page_ids)

    own, v_own = _expanded_scores(spec, a, q_nope, q_rope, latent)
    own = jnp.where(mask[None], own, _MASKED)
    m0 = own.max(axis=-1, keepdims=True)
    # a padding row sees nothing, its own position included: it stays finite
    p0 = jnp.where(mask[None], jnp.exp(own - m0), 0.0)
    carry0 = (m0, p0.sum(axis=-1, keepdims=True),
              contract(spec, "htu,uhv->htv", p0, v_own))

    def body(j, carry):
        m, total, acc = carry
        pages = jax.lax.dynamic_slice(page_ids, (j * block_pages,), (block_pages,))
        cached = cache[sub, pages].reshape(block, cache.shape[3])
        scores, v = _expanded_scores(spec, a, q_nope, q_rope, cached)
        seen = (j * block + jnp.arange(block, dtype=jnp.int32) < start)[None, None, :]
        scores = jnp.where(seen, scores, _MASKED)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * total + p.sum(axis=-1, keepdims=True),
                alpha * acc + contract(spec, "htu,uhv->htv", p, v))

    _, total, acc = jax.lax.fori_loop(0, (start + block - 1) // block, body, carry0)
    out = (acc / jnp.where(total > 0, total, 1.0)).transpose(1, 0, 2)
    return project(spec, a, out.reshape(out.shape[0], -1), wo)


def absorbed_attention(spec: LatentSpec, a: dict, q_nope, q_rope, cache, sub: int,
                       page_table, n_ctx, impl: str, wo: bool = True):
    """One decode step's attention over the paged latent cache."""
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.ops.mla_decode import paged_mla_attention

    s_slots, heads = q_nope.shape[:2]
    q_lat = contract(spec, "shn,hcn->shc", q_nope, a["w_uk"])
    q = jnp.concatenate(
        [q_lat, q_rope,
         jnp.zeros((s_slots, heads, spec.cache_width - spec.latent_width), jnp.float32)],
        axis=-1) * spec.score_scale
    o_lat = paged_mla_attention(q.astype(cache.dtype), cache, sub, page_table, n_ctx,
                                rank=spec.kv_lora_rank, impl=impl)
    out = contract(spec, "shc,hcv->shv", o_lat, a["w_uv"])
    return project(spec, a, out.reshape(s_slots, -1), wo)


def chunk_positions(cache, tokens, start, length, page_ids):
    """The geometry of one prefill chunk (``runtime.decode``'s contract):
    rows at positions ``start ..``, true below ``length``.  Returns
    (positions [T], which rows are true, the page and the offset each row's
    latent is written to -- padding goes to the trash page 0 -- the chunk's
    own causal mask [T, T], and ``length``)."""
    import jax.numpy as jnp

    page = cache.shape[2]
    pos = start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
    real = pos < length
    slot_page = jnp.minimum(pos // page, page_ids.shape[0] - 1)
    write_page = jnp.where(real, page_ids[slot_page], 0)
    mask = (pos[None, :] <= pos[:, None]) & real[None, :]
    return pos, real, write_page, pos % page, mask, length


def chunk_attention_form(rows: int, first: bool, impl: str) -> str:
    """Which form a prefill chunk's attention takes, by its compiled shape
    alone (asked while the program traces, and again for the status page):
    ``"kernel"`` for every later chunk and for a first chunk of at least
    ``KERNEL_FIRST_ROWS`` rows where ``impl`` is a kernel's; else the plain
    form, ``"expanded"`` for a first chunk and ``"paged"`` for a later one."""
    if impl != "gather" and (not first or rows >= KERNEL_FIRST_ROWS):
        return "kernel"
    return "expanded" if first else "paged"


def kernel_chunk_attention(spec: LatentSpec, a: dict, q_nope, q_rope, cache, sub: int,
                           page_ids, start, length, impl: str, wo: bool = True):
    """A chunk's queries [T, ...] against positions [0, ``length``) of the
    slot's pages, its own included (already written), in ``ops.mla_chunk``'s
    kernel."""
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.ops.mla_chunk import chunk_mla_attention

    rows, heads = q_nope.shape[:2]
    q = jnp.concatenate(
        [q_nope, q_rope,
         jnp.zeros((rows, heads, spec.cache_width - spec.latent_width), jnp.float32)],
        axis=-1)
    out = chunk_mla_attention(q.transpose(1, 0, 2), a["w_uk"], a["w_uv"], cache, sub,
                              page_ids, start, length, rank=spec.kv_lora_rank,
                              scale=spec.score_scale, impl=impl)
    return project(spec, a, out, wo)


def attend_chunk(spec: LatentSpec, a: dict, sub: int, x, cache, geometry, cos, sin,
                 page_ids, start, impl: str = "gather", wo: bool = True):
    """One sublayer of a prefill chunk: write the rows' latents, attend.
    ``start`` is the Python int 0 for a prompt's first chunk (nothing of it
    is cached yet: the sequence attends to itself) or a traced scalar."""
    _, _, write_page, write_off, mask, length = geometry
    q_nope, q_rope, latent = queries_and_latent(spec, a, x, cos, sin)
    cache = cache.at[sub, write_page, write_off].set(latent)
    first = isinstance(start, int) and start == 0
    form = chunk_attention_form(x.shape[0], first, impl)
    if form == "kernel":
        return cache, kernel_chunk_attention(spec, a, q_nope, q_rope, cache, sub, page_ids,
                                             start, length, impl, wo)
    if form == "expanded":
        return cache, expanded_attention(spec, a, q_nope, q_rope, latent, mask, wo)
    return cache, paged_chunk_attention(spec, a, q_nope, q_rope, latent, mask, cache,
                                        sub, page_ids, start, wo)


def attend_step(spec: LatentSpec, a: dict, sub: int, x, cache, page_table, lengths, active,
                cos, sin, impl: str, wo: bool = True):
    """One sublayer of a decode step: every live slot's consumed token is
    written at position ``lengths[s]`` and attends over 0..lengths[s]."""
    import jax.numpy as jnp

    page = cache.shape[2]
    write_page = jnp.take_along_axis(page_table, (lengths // page)[:, None], axis=1)[:, 0]
    write_page = jnp.where(active, write_page, 0)
    q_nope, q_rope, latent = queries_and_latent(spec, a, x, cos, sin)
    cache = cache.at[sub, write_page, lengths % page].set(latent)
    n_ctx = jnp.where(active, lengths + 1, 0)
    return cache, absorbed_attention(spec, a, q_nope, q_rope, cache, sub, page_table,
                                     n_ctx, impl, wo)


def tally_parts(tally, live):
    """An expert layer's counts: ``tally(rows, live)`` over all its rows for
    one ``live`` mask, or, for a tuple of masks (one a part of the rows, in
    their order), stacked by part."""
    import jax.numpy as jnp

    if not isinstance(live, tuple):
        return tally(slice(None), live)
    parts, at = [], 0
    for mask in live:
        parts.append(tally(slice(at, at + mask.shape[0]), mask))
        at += mask.shape[0]
    return jnp.stack(parts)


def round_forward(cfg, params: dict, layers, cache, tokens, start, length, page_ids,
                  page_table, lengths, last_tokens, active, impl: str):
    """A prefill chunk and a decode step as one forward (``runtime.decode``'s
    round contract).  Every layer runs once over the chunk's ``T`` rows
    followed by the step's ``S``: embedding, norms, projections, FFNs and
    experts read their weights once for both.  Only attention is split: the
    chunk's rows take ``attend_chunk``, the step's ``attend_step``, and their
    cache writes land on disjoint pages (the prefilling slot is not live in
    the step; padding and idle slots write the trash page).  ``layers(cfg,
    params, x, cache, live, attend)`` is the decoder's stack, here given
    ``live`` as the pair (the chunk's true rows, the live slots): it counts
    each apart (``tally_parts``).  Returns (cache, the chunk's last true
    row's logits [V], the step's logits [S, V], counts [2, N_COUNTS]: the
    chunk's, then the step's)."""
    import jax.numpy as jnp

    spec = cfg.mla
    t = tokens.shape[0]
    geometry = chunk_positions(cache, tokens, start, length, page_ids)
    pos, real = geometry[:2]
    cos, sin = rope_angles(spec, jnp.concatenate([pos, lengths]))

    # The step's attention first, then the chunk's, and the heads of both
    # through one output projection, which reads ``wo`` once and joins the
    # residual and the next norm as the chunk's own does.  On a v5e at the
    # long-prompt cell's sizes (a chunk of 1,024 rows beside 64 slots), with
    # the chunk's attention first and a projection each the compiler
    # recomputed the chunk's projection to hold it across the step's
    # attention, and the round took 8.5 ms more than the chunk alone; with
    # the step's first, 3.6 ms more, 0.63 ms a layer in a projection apart.
    def attend(a, sub, x, cache):
        cache, step = attend_step(spec, a, sub, x[t:], cache, page_table, lengths, active,
                                  cos[t:], sin[t:], impl, wo=False)
        cache, own = attend_chunk(spec, a, sub, x[:t], cache, geometry, cos[:t], sin[:t],
                                  page_ids, start, impl, wo=False)
        return cache, mm(spec, jnp.concatenate([own, step]), a["wo"])

    x = params["embed"][jnp.concatenate([tokens, last_tokens])].astype(jnp.float32)
    cache, x, counts = layers(cfg, params, x, cache, (real, active), attend)
    rows = jnp.concatenate([x[length - 1 - start][None], x[t:]])
    logits = mm(cfg, rms(rows, params["final_norm"], cfg.rms_norm_eps), params["head"])
    return cache, logits[0], logits[1:], counts
