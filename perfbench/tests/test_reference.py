"""The plain references against the program at a small size on the CPU: the
leaves they declare are the program's, float32 agrees to rounding, the
stated bfloat16 stays near and the fp8 control does not."""

import json
import os

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import pictures, reference, weights
from perfbench.reference.ops import Net, normalize


def _config(name, **changes):
    with open(os.path.join(M.HERE, "configs", name + ".json")) as f:
        return dict(json.load(f), **changes)


CASES = {
    "efficientnet": lambda: _config("efficientnet-b7-600", family="efficientnet-b0",
                                    width_coefficient=1.0, depth_coefficient=1.0,
                                    input_shape=[96, 96, 3], num_classes=10),
    "xception": lambda: _config("xception-clothing-299", input_shape=[96, 96, 3]),
}


@pytest.mark.parametrize("family", sorted(CASES))
def test_reference_agrees_with_the_program(family):
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import build_forward, create_model
    from perfbench.children.make_artifact import model_spec

    config = CASES[family]()
    forward = reference.load(config["reference"]).forward
    flat = weights.make(weights.declare(forward, config), 11)
    weights.calibrate(forward, config, flat, pictures.calibration_pixels(11, 8, 96))
    tree = weights.nest(flat)
    spec = model_spec(config)
    model = create_model(spec)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *spec.input_shape), jnp.float32)))
    leaf = lambda t: {jax.tree_util.keystr(p): tuple(x.shape)  # noqa: E731
                      for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert leaf(tree) == leaf(shapes)

    px = pictures.tensor_pool(3, 4, tuple(config["input_shape"]))
    run = lambda prec: np.asarray(jax.jit(lambda p: forward(  # noqa: E731
        Net(flat, prec), normalize(p, config["preprocessing"]), config))(px))
    ref, control = run("float32"), run("fp8")
    with jax.default_matmul_precision("highest"):
        f32 = np.asarray(jax.jit(build_forward(spec, dtype=jnp.float32, fast=False))(tree, px))
    bf16 = np.asarray(jax.jit(build_forward(spec, dtype=jnp.bfloat16, fast=False))(tree, px))
    scale = np.abs(ref).max()
    err = lambda a: float(np.abs(a - ref).max() / scale)  # noqa: E731
    assert 0.1 < scale < 100          # calibrated: logits of order one
    assert err(f32) < 1e-4            # the same mathematics
    assert err(bf16) < 0.15           # the stated precision stays near
    assert err(control) > 3 * err(bf16)   # the precision below does not


def test_configs_hold_the_counted_flops():
    from perfbench import flops

    for name, batch in (("xception-clothing-299", 256), ("efficientnet-b7-600", 64)):
        config = _config(name)
        counted = flops.model_flops_per_image(config, batch)
        assert abs(counted - config["flops_per_image"]) / counted < 1e-3


def test_sepconv_block_counts():
    from perfbench import flops

    ops, nbytes = flops.sepconv_block(19, 19, 256, 728)
    values = 19 * 19 * 256 * 728
    assert ops == 3 * values * (18 + 2 * 728)
    assert nbytes > 4 * values
    seconds, bound = flops.roofline_seconds(ops, nbytes, {"bf16_tflops": 197.0,
                                                          "hbm_gb_per_s": 819.0})
    assert bound == "compute" and abs(seconds - ops / 197e12) < 1e-12
