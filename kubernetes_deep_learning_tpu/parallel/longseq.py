"""Sequence-parallel ViT serving: context parallelism end to end.

parallel.ring gives exact attention over a sequence sharded across the mesh;
this module puts a whole MODEL on top of it -- a ViT forward in which the
token axis never materializes on one device:

- patch embedding + position add happen under jit with the token axis
  sharded (XLA partitions the patchify matmul tokenwise),
- every transformer block runs inside ONE shard_map: LayerNorm/qkv/MLP are
  tokenwise (purely local), attention is the ring schedule (_ring_shard --
  the same per-device body jit'd by parallel.ring, composed here directly so
  the whole stack stays in a single SPMD program with no resharding between
  layers),
- the final mean-pool is a local partial sum + psum, so only the pooled
  (B, width) vector is ever replicated.

Per-device memory is O(S/n * width): a sequence too long for one chip's HBM
serves on a mesh of n.  The weights are the UNMODIFIED flax ViT params --
this is an alternative execution schedule for models.vit.ViT, not a separate
model (tests assert logit equality against the single-device module).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from kubernetes_deep_learning_tpu.models.vit import VIT_CONFIGS, ViTConfig
from kubernetes_deep_learning_tpu.modelspec import ModelSpec
from kubernetes_deep_learning_tpu.ops.preprocess import normalize
from kubernetes_deep_learning_tpu.parallel.mesh import DATA_AXIS
from kubernetes_deep_learning_tpu.parallel.ring import _ring_shard

_LN_EPS = 1e-6  # flax.linen.LayerNorm default, which models.vit uses


def _layer_norm(x, scale, bias):
    """Tokenwise LayerNorm in f32 (matches the module's f32-LN policy)."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + _LN_EPS) * scale + bias


def _block_shard(
    x, params, *, cfg: ViTConfig, axis_name: str, n: int, dtype, use_flash,
    interpret: bool = False,
):
    """One transformer block on a (B, S_local, C) token shard.

    Everything except attention is tokenwise; attention is the ring
    schedule over the mesh axis.
    """
    heads = cfg.heads

    y = _layer_norm(x, params["ln_attn"]["scale"], params["ln_attn"]["bias"])
    y = y.astype(dtype)
    proj = lambda name: (
        jnp.einsum("bsc,chd->bhsd", y, params["attn"][name]["kernel"].astype(dtype))
        + params["attn"][name]["bias"].astype(dtype)[:, None, :]  # (H,1,D)
    )
    q, k, v = proj("query"), proj("key"), proj("value")
    o = _ring_shard(
        q, k, v, axis_name=axis_name, n=n, causal=False, use_flash=use_flash,
        interpret=interpret,
    )
    o = jnp.einsum(
        "bhsd,hdc->bsc", o.astype(dtype), params["attn"]["out"]["kernel"].astype(dtype)
    ) + params["attn"]["out"]["bias"].astype(dtype)
    x = x + o

    y = _layer_norm(x, params["ln_mlp"]["scale"], params["ln_mlp"]["bias"])
    y = y.astype(dtype)
    y = y @ params["mlp_in"]["kernel"].astype(dtype) + params["mlp_in"]["bias"].astype(dtype)
    y = jax.nn.gelu(y)
    y = y @ params["mlp_out"]["kernel"].astype(dtype) + params["mlp_out"]["bias"].astype(dtype)
    return x + y


def _stack_shard(
    x, params, *, cfg: ViTConfig, axis_name: str, n: int, dtype, seq: int,
    use_flash, interpret: bool = False,
):
    """All blocks + final LN + the LOCAL half of the mean pool."""
    for i in range(cfg.depth):
        x = _block_shard(
            x, params[f"block_{i}"], cfg=cfg, axis_name=axis_name, n=n,
            dtype=dtype, use_flash=use_flash, interpret=interpret,
        )
    x = _layer_norm(x, params["ln_final"]["scale"], params["ln_final"]["bias"])
    pooled = x.sum(axis=1) / seq            # local partial of the token mean
    return jax.lax.psum(pooled, axis_name)  # (B, width), replicated


@functools.lru_cache(maxsize=None)
def build_sequence_parallel_forward(
    spec: ModelSpec,
    mesh: Mesh,
    dtype=jnp.bfloat16,
    axis_name: str = DATA_AXIS,
    differentiable: bool = False,
    interpret: bool = False,
):
    """Jitted ``f(variables, uint8_images) -> f32 logits`` with the token
    sequence sharded over ``axis_name``.  ViT families only; the patch-grid
    token count must divide the axis size.

    ``differentiable=True`` forces the ring's einsum attend (the Pallas
    kernel has no VJP), making the whole forward grad-able through
    shard_map/ppermute -- context-parallel FINE-TUNING: per-device
    activations stay O(S/n), gradients ride the same ring.  Serving keeps
    the default (flash attend where it tiles, compiled through Mosaic);
    ``interpret=True`` is for CPU tests only (see parallel.ring)."""
    cfg = VIT_CONFIGS.get(spec.family)
    if cfg is None:
        raise ValueError(
            f"sequence parallelism needs a vit family, got {spec.family!r}"
        )
    from kubernetes_deep_learning_tpu.parallel.mesh import MODEL_AXIS

    if MODEL_AXIS in mesh.shape and mesh.shape[MODEL_AXIS] > 1:
        raise ValueError(
            "sequence parallelism uses the data axis only; a model-parallel "
            f"mesh axis of {mesh.shape[MODEL_AXIS]} would duplicate every "
            "token shard -- use model_parallel=1"
        )
    h, w = spec.input_shape[:2]
    seq = (h // cfg.patch) * (w // cfg.patch)
    n = mesh.shape[axis_name]
    if seq % n:
        raise ValueError(f"token count {seq} not divisible by mesh axis {n}")

    token_sharding = NamedSharding(mesh, P(None, axis_name, None))
    stack = jax.shard_map(
        functools.partial(
            _stack_shard, cfg=cfg, axis_name=axis_name, n=n, dtype=dtype,
            seq=seq, use_flash=False if differentiable else None,
            interpret=interpret,
        ),
        mesh=mesh,
        in_specs=(P(None, axis_name, None), P()),
        out_specs=P(),
        # Same pallas-interpreter vma caveat as parallel.ring.
        check_vma=not interpret,
    )

    def forward(variables, images):
        params = variables["params"]
        if images.dtype == jnp.uint8:
            x = normalize(images, spec.preprocessing)
        else:
            x = images.astype(jnp.float32)
        x = x.astype(dtype)
        b = x.shape[0]
        p = cfg.patch
        # Patchify as reshape + one matmul (the conv kernel flattened to
        # (p*p*3, width) in the conv's own (kh, kw, cin) order), so the
        # token axis exists -- and can be sharded -- from the first op.
        x = x.reshape(b, h // p, p, w // p, p, 3).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, seq, p * p * 3)
        kernel = params["patch_embed"]["kernel"].astype(dtype).reshape(-1, cfg.width)
        x = x @ kernel + params["patch_embed"]["bias"].astype(dtype)
        x = x + params["pos_embed"].astype(dtype)
        x = jax.lax.with_sharding_constraint(x, token_sharding)
        pooled = stack(x, params)
        logits = pooled @ params["head"]["kernel"] + params["head"]["bias"]
        return logits.astype(jnp.float32)

    return jax.jit(forward)


def build_sequence_parallel_train_step(
    spec: ModelSpec,
    tx,
    mesh: Mesh,
    dtype=jnp.bfloat16,
    axis_name: str = DATA_AXIS,
):
    """Context-parallel fine-tuning step: gradients through the ring.

    Same contract as training.trainer.build_train_step -- jitted
    ``step(state, uint8_images, labels) -> (state, metrics)`` on a
    trainer.TrainState -- but the TOKEN axis (not the batch) is sharded over
    the mesh, so sequences too long for one chip's activations fine-tune on
    a mesh of n.  ViT families only (BN-free, so batch_stats stays empty).
    """
    import optax

    from kubernetes_deep_learning_tpu.training.trainer import TrainState

    fwd = build_sequence_parallel_forward(
        spec, mesh, dtype=dtype, axis_name=axis_name, differentiable=True
    )

    def loss_fn(params, images, labels):
        logits = fwd({"params": params}, images)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return loss, acc

    def train_step(state: TrainState, images, labels):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, images, labels
        )
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            state.step + 1, new_params, state.batch_stats, new_opt_state
        )
        return new_state, {"loss": loss, "accuracy": acc}

    return jax.jit(train_step, donate_argnums=(0,))
