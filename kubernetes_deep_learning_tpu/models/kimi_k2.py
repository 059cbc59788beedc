"""Kimi-K2 (``model_type`` ``kimi_k2``, DeepSeek-V3's layer): a decoder for
the generative lane (``runtime.decode``).

    h <- h + MLA(RMS(h));   h <- h + MLP(RMS(h))

``MLP`` is a dense SwiGLU FFN in the first ``first_k_dense_replace`` layers
and the expert layer in every other; a final RMS norm, an untied head.

``MLA`` is ``models.latent_attention``'s, shared with the lane's other
decoders, with YaRN on the rotary frequencies (``rope_scaling``) and its
``mscale`` squared on the softmax scale; no factor on ``q`` or ``c_kv``.
One sublayer a layer: the cache holds, a position a layer, the normed
``c_kv`` and the rotated ``k_r``.

The expert layer: ``s = sigmoid(u W_r)`` in float32 over all
``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b``
(``e_score_correction_bias``; ``topk_method`` ``noaux_tc`` with one group,
so the group limit selects everything) are chosen; their weights are
``s[chosen] / (sum + 1e-20) * routed_scaling_factor``; and
``y = sum_k w_k E_k(u) + E_shared(u)``, the shared expert a SwiGLU of width
``n_shared_experts * moe_intermediate_size`` that every token goes through.
This module is told which routed experts it holds (``held_experts`` =
[lo, hi), one chip's share of an expert-parallel deployment): it routes
over all of them, computes the held experts' terms and the shared expert,
and leaves out what the absent experts would add.  No token is dropped;
there is no capacity factor and nothing stands in for the absent chips.

The held experts' products take one of two forms, by the rows of the call
(``GROUPED_FROM_ROWS``, measured: PERF.md section 6, PR 34):

- *masked*: every row through every held expert, a row's weight for an
  expert it was not routed to being zero.  A decode step's few rows are
  bound by the experts' bytes, which this reads once.
- *grouped*: the (row, expert) assignments sorted by expert and computed
  ``GROUP_TILE`` rows at a time, a tile's rows all of one expert, in a loop
  that runs as many tiles as the routing asks for.  A prefill chunk of a
  thousand rows sends a held expert a few dozen: the masked form would
  compute every row twelve times.

Weights and matmul operands are bfloat16 with float32 accumulation; the
residual stream, the norms' statistics, the router and the softmaxes are
float32.
"""

from __future__ import annotations

import dataclasses
import functools

from kubernetes_deep_learning_tpu.models import latent_attention as la

FAMILY = "kimi_k2"

# Rows of a call from which the held experts' products are grouped by expert
# and below which they are one masked product, and the rows of one tile of
# the grouped form.  On a v5e, 12 held experts of 7168 x 2048, 8 of 384 a row
# (PERF.md section 6, PR 34): masked 2.17 / 2.29 / 3.85 / 7.17 ms at 64 / 256 /
# 512 / 1,024 rows, grouped in tiles of 128 2.71 / 2.70 / 3.02 / 2.75 ms (a
# tile a touched expert: the loop is bound by the experts' bytes); tiles of
# 256 and ``jax.lax.ragged_dot`` at the worst-case count are slower at every
# row count.
GROUPED_FROM_ROWS = 512
GROUP_TILE = 128


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    hidden_size: int
    intermediate_size: int            # the dense layers' FFN
    moe_intermediate_size: int        # one expert, routed or shared
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    kv_lora_rank: int
    q_lora_rank: int
    qk_rope_head_dim: int
    qk_nope_head_dim: int
    v_head_dim: int
    n_routed_experts: int             # experts the router scores
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    vocab_size: int
    held_experts: tuple[int, int]     # [lo, hi) of n_routed_experts held here
    rope_scaling: tuple | None = None     # la.Yarn's fields, in order
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    compute_dtype: str = "bfloat16"   # matmul operands and the cache; tests use float32

    # what this module computes of the published options, and nothing else
    SERVED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
              "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu"}

    @classmethod
    def from_dict(cls, d: dict) -> "KimiConfig":
        for key, served in cls.SERVED.items():
            if d.get(key, served) != served:
                raise ValueError(f"{key} = {d[key]!r}: only {served!r} is served")
        names = [f.name for f in dataclasses.fields(cls)]
        kwargs = {k: d[k] for k in names if k in d}
        kwargs["held_experts"] = tuple(int(x) for x in d["held_experts"])
        yarn = la.Yarn.from_dict(d.get("rope_scaling"))
        kwargs["rope_scaling"] = dataclasses.astuple(yarn) if yarn else None
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
        return la.LatentSpec(
            self.hidden_size, self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rms_norm_eps, self.rope_theta, self.compute_dtype,
            yarn=la.Yarn(*self.rope_scaling) if self.rope_scaling else None)

    def tensor_shapes(self) -> dict[str, tuple[int, ...]]:
        """Every weight by its name in the artifact, with its shape.  The
        held experts' matrices are stacked, [held, in, out]: a tile of the
        grouped product reads one expert's, the masked product all."""
        d, f = self.hidden_size, self.moe_intermediate_size
        out = {"embed": (self.vocab_size, d), "final_norm": (d,),
               "head": (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            p = f"layers.{i}."
            out.update(self.mla.tensor_shapes(p + "attn."))
            out[p + "mlp_norm"] = (d,)
            if i < self.first_k_dense_replace:
                widths = {"ffn.": self.intermediate_size}
            else:
                widths = {"shared.": self.n_shared_experts * f}
                out[p + "router"] = (d, self.n_routed_experts)
                out[p + "router_bias"] = (self.n_routed_experts,)
                out[p + "experts.w_gate"] = (self.n_held, d, f)
                out[p + "experts.w_up"] = (self.n_held, d, f)
                out[p + "experts.w_down"] = (self.n_held, f, d)
            for name, width in widths.items():
                out[p + name + "w_gate"] = (d, width)
                out[p + name + "w_up"] = (d, width)
                out[p + name + "w_down"] = (width, d)
        return out


tensor_dtype = la.tensor_dtype


def write_artifact(directory: str, config: dict, tensors) -> None:
    """``tensors``: (name, numpy array) pairs, float32 or uint16 (bfloat16
    bits); shapes are held to ``tensor_shapes``."""
    la.write_artifact(directory, FAMILY, config,
                      KimiConfig.from_dict(config).tensor_shapes(), tensors)


def read_artifact(directory: str) -> tuple[KimiConfig, dict]:
    config, flat = la.read_artifact(
        directory, FAMILY, lambda c: KimiConfig.from_dict(c).tensor_shapes())
    return KimiConfig.from_dict(config), flat


# --- the expert layer ------------------------------------------------------------------


def route(cfg: KimiConfig, layer: dict, u):
    """``u`` [N, D] float32 (the normed input) -> chosen experts [N, topk]
    int32 and their weights [N, topk] float32."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(u, layer["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + layer["router_bias"], cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.norm_topk_prob and cfg.num_experts_per_tok > 1:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * cfg.routed_scaling_factor


def masked_experts(cfg: KimiConfig, e: dict, u, per_expert):
    """Every row of ``u`` [N, D] through every held expert, weighted by
    ``per_expert`` [N, held] (zero where a row was not routed)."""
    import jax
    import jax.numpy as jnp

    rows = jnp.broadcast_to(u[None], (cfg.n_held, *u.shape))
    mid = (jax.nn.silu(la.contract(cfg, "end,edf->enf", rows, e["w_gate"]))
           * la.contract(cfg, "end,edf->enf", rows, e["w_up"]))
    out = la.contract(cfg, "enf,efd->end", mid, e["w_down"])
    return (out * per_expert.T[:, :, None]).sum(axis=0)


def grouped_experts(cfg: KimiConfig, e: dict, u, held_index, weights, tile: int = GROUP_TILE):
    """The routed rows only: ``held_index`` [N, k] is an assignment's expert
    within the held range (anything outside it: not held), ``weights`` [N,
    k] its weight.  The held assignments are sorted by expert and computed
    ``tile`` at a time, a tile within one expert's run; the loop runs the
    tiles there are.  Returns ([N, D] float32, the rows the tiles computed)."""
    import jax
    import jax.numpy as jnp

    n, k = held_index.shape
    held = cfg.n_held
    flat = held_index.reshape(-1)
    key = jnp.where((flat >= 0) & (flat < held), flat, held)      # not held: sorted last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = (key[:, None] == jnp.arange(held, dtype=jnp.int32)).sum(axis=0)
    ends = jnp.cumsum(counts)
    tiles = (counts + tile - 1) // tile
    tile_ends = jnp.cumsum(tiles)
    order = jnp.concatenate([order, jnp.zeros((tile,), jnp.int32)])   # a slice never runs off
    gates = weights.reshape(-1)

    def body(i, y):
        ex = (tile_ends <= i).sum()                    # the expert this tile belongs to
        first = ends[ex] - counts[ex] + (i - (tile_ends[ex] - tiles[ex])) * tile
        picks = jax.lax.dynamic_slice(order, (first,), (tile,))
        valid = first + jnp.arange(tile, dtype=jnp.int32) < ends[ex]
        rows = picks // k
        x = u[rows]
        w_gate, w_up, w_down = (jax.lax.dynamic_index_in_dim(e[name], ex, keepdims=False)
                                for name in ("w_gate", "w_up", "w_down"))
        mid = jax.nn.silu(la.mm(cfg, x, w_gate)) * la.mm(cfg, x, w_up)
        out = la.mm(cfg, mid, w_down) * jnp.where(valid, gates[picks], 0.0)[:, None]
        return y.at[jnp.where(valid, rows, n)].add(out, mode="drop")

    total = tile_ends[-1]
    y = jax.lax.fori_loop(0, total, body, jnp.zeros((n, u.shape[1]), jnp.float32))
    return y, total * tile


@functools.lru_cache(maxsize=None)
def _grouped_jit():
    """``grouped_experts`` under a ``jit`` of its own: the expert layers of a
    program are one traced loop, traced and lowered once a program and not
    once a layer (a server's boot pays that even where the compile cache
    holds the program)."""
    import jax

    return jax.jit(grouped_experts, static_argnums=(0, 5))


def moe(cfg: KimiConfig, layer: dict, u, live, grouped: bool | None = None):
    """The held share of the expert layer over ``u`` [N, D] float32 -- the
    held experts' terms and the shared expert -- and the lane's counts
    (``runtime.decode.N_COUNTS``): over the ``live`` rows [held, absent, 0
    (no zero-compute experts), held experts with at least one live token],
    then the (row, held expert) products computed and the live rows the
    shared expert met.  ``grouped``: the form of the held experts' products
    (by the rows of the call where not given).  ``live`` may be a tuple of
    masks, one a part of the rows in their order (``la.round_forward``):
    the counts are then [parts, N_COUNTS], each part's products as the
    call's form computes them for its rows alone (all of them through every
    held expert, or its assignments in whole tiles)."""
    import jax.numpy as jnp

    n = u.shape[0]
    lo, hi = cfg.held_experts
    chosen, weights = route(cfg, layer, u)
    is_held = (chosen >= lo) & (chosen < hi)
    hit = (chosen - lo)[:, :, None] == jnp.arange(hi - lo, dtype=jnp.int32)   # [N, k, E]
    if grouped is None:
        grouped = n >= GROUPED_FROM_ROWS
    if grouped:
        y, _ = _grouped_jit()(cfg, layer["experts"], u, chosen - lo, weights)
    else:
        per_expert = jnp.where(hit, weights[:, :, None], 0.0).sum(axis=1)    # [N, E]
        y = masked_experts(cfg, layer["experts"], u, per_expert)
    y = y + la.ffn(cfg, layer["shared"], u)

    def tally(rows, live):
        h, held, alive = hit[rows], is_held[rows], live[:, None]
        touched = (h & alive[:, :, None]).any(axis=(0, 1)).sum()
        if grouped:     # whole tiles an expert the rows reached
            computed = ((h.sum(axis=(0, 1)) + GROUP_TILE - 1) // GROUP_TILE).sum() * GROUP_TILE
        else:           # every row through every held expert
            computed = h.shape[0] * (hi - lo)
        return jnp.stack([(held & alive).sum(), (~held & alive).sum(), 0, touched, computed,
                          live.sum()]).astype(jnp.int32)

    return y, la.tally_parts(tally, live)


# --- the stack ---------------------------------------------------------------------------


def _layers(cfg: KimiConfig, params: dict, x, cache, live, attend):
    """The stack over ``x`` [N, D] float32.  ``attend(a, sub, x, cache)`` ->
    (cache, the layer's attention output).  ``live``: a mask, or a tuple of
    them by part (``moe``)."""
    import jax.numpy as jnp

    counts = jnp.zeros(((len(live), 6) if isinstance(live, tuple) else (6,)), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        cache, out = attend(layer["attn"], i, x, cache)
        x = x + out
        u = la.rms(x, layer["mlp_norm"], cfg.rms_norm_eps)
        if i < cfg.first_k_dense_replace:
            x = x + la.ffn(cfg, layer["ffn"], u)
        else:
            y, c = moe(cfg, layer, u, live)
            x, counts = x + y, counts + c
    return cache, x, counts


def prefill(cfg: KimiConfig, params: dict, cache, tokens, start, length, page_ids, *,
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


def decode_step(cfg: KimiConfig, params: dict, cache, page_table, lengths, last_tokens,
                active, *, attention: str):
    """One token for every slot (``runtime.decode``'s step contract)."""
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


class KimiDecoder:
    """``runtime.decode``'s decoder interface over one artifact."""

    family = FAMILY
    text = False            # no tokenizer here: prompts are token ids
    eos_token = None

    def __init__(self, cfg: KimiConfig, params: dict, attention: str | None = None):
        import jax

        self.cfg, self.params = cfg, params
        self.vocab_size = cfg.vocab_size
        # Mosaic compiles the kernel on a TPU; elsewhere the same attention
        # runs as XLA's gather (the interpreter is for tests, never served)
        self.attention = attention or (
            "kernel" if jax.default_backend() == "tpu" else "gather")

    @classmethod
    def load(cls, directory: str, attention: str | None = None) -> "KimiDecoder":
        import jax

        cfg, flat = read_artifact(directory)
        return cls(cfg, la.nest({k: jax.device_put(v) for k, v in flat.items()}), attention)

    def cache_spec(self, num_pages: int, page_size: int):
        import jax.numpy as jnp

        return ((self.cfg.num_hidden_layers, num_pages, page_size, self.cfg.mla.cache_width),
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
        return {"family": FAMILY, "layers": cfg.num_hidden_layers,
                "dense_layers": cfg.first_k_dense_replace,
                "held_experts": list(cfg.held_experts),
                "routed_experts": cfg.n_routed_experts,
                "shared_experts": cfg.n_shared_experts,
                "experts_per_token": cfg.num_experts_per_tok,
                "latent_width": cfg.mla.latent_width, "attention": self.attention}


DECODER = KimiDecoder      # what ``runtime.decode.load_decoder`` asks a family's module for
