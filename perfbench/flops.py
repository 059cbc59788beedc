"""Operations a configuration's forward pass needs per image, and the
operations and bytes of the kernels whose roofline the benchmark reports.

``model_flops_per_image`` asks XLA: the cost analysis of the lowered,
NON-fused flax graph (a Pallas call is opaque to it), at the compute dtype
the configuration states.  It is the number kept in each configuration file
as ``flops_per_image``; a test holds the file to it.  The kernel functions
count from shapes alone, as the algorithm needs them: no recomputation, no
padding, each operand moved once.
"""

from __future__ import annotations


def model_flops_per_image(config: dict, batch: int) -> float:
    """2 x multiply-adds plus elementwise, per image (needs jax; host only)."""
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import build_forward, create_model
    from perfbench.children.make_artifact import model_spec

    spec = model_spec(config)
    model = create_model(spec)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *spec.input_shape), jnp.float32)))
    forward = build_forward(spec, dtype=jnp.dtype(config["compute_dtype"]), fast=False)
    images = jax.ShapeDtypeStruct((batch, *spec.input_shape), jnp.uint8)
    cost = jax.jit(forward).lower(shapes, images).cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"]) / batch


def sepconv_block(h: int, w: int, batch: int, channels: int, stages: int = 3,
                  act_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one fused residual block of ``stages``
    separable convolutions at constant width (Xception's middle flow): per
    stage a depthwise 3x3 (9 multiply-adds a value) and a pointwise
    channels x channels product; activations read once and written once,
    weights read once."""
    values = h * w * batch * channels
    ops = stages * (values * 9 * 2 + values * channels * 2)
    weights = stages * (9 * channels * 4 + channels * channels * 2 + 2 * channels * 4)
    return float(ops), float(2 * values * act_bytes + weights)


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_compute = ops / (peaks["bf16_tflops"] * 1e12)
    by_memory = nbytes / (peaks["hbm_gb_per_s"] * 1e9)
    return (by_compute, "compute") if by_compute >= by_memory else (by_memory, "memory")
