"""Weights from a seed, for whatever leaves a reference declares.

The benchmark makes the weights; the program under test is handed them in
its artifact and the reference reads the same ones, so neither takes what
the other has made.  Random weights carry no trained BatchNorm to hold the
activations' scale, so every contraction is drawn variance-preserving
(He-style, ``gain / fan_in``) and each reference sets its gains: a sound
run then has logits of order one whatever the depth.
"""

from __future__ import annotations

import numpy as np


def declare(forward, config: dict) -> list:
    """The leaves (path, shape, kind, gain) ``forward`` reads, in order."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference.ops import Net

    net = Net(None)
    h, w, c = config["input_shape"]
    jax.eval_shape(lambda x: forward(net, x, config),
                   jax.ShapeDtypeStruct((1, h, w, c), jnp.float32))
    return net.declared


def make(declared: list, seed: int) -> dict:
    """{path: float32 array}; the same seed gives the same weights."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    out = {}
    for path, shape, kind, gain in declared:
        if kind in ("conv", "dwconv", "dense"):
            fan_in = shape[0] if kind == "dense" else shape[0] * shape[1] * shape[2]
            w = rng.standard_normal(shape, dtype=np.float32)
            w *= np.float32(np.sqrt(gain / fan_in))
        elif kind in ("bn_scale", "bn_var"):
            w = (rng.uniform(0.8, 1.2, shape) * np.sqrt(gain)).astype(np.float32)
        elif kind in ("bn_bias", "bn_mean", "bias"):
            w = (rng.standard_normal(shape, dtype=np.float32) * np.float32(0.1))
        else:
            raise ValueError(f"unknown leaf kind {kind!r} at {'/'.join(path)}")
        out[path] = w
    return out


def calibrate(forward, config: dict, flat: dict, pixels) -> dict:
    """Give every BatchNorm the statistics of the activations that reach it.

    Random weights have no trained BatchNorm, and without one the scale of
    the activations runs away (swish and the squeeze-excite gate pass more
    of a large signal than of a small one: a factor 1.4 a block, 1e6 over
    EfficientNet-B7's 55 blocks).  One float32 pass over ``pixels`` (a few
    of the traffic's own pictures, uint8 NHWC at a reduced size) sets each
    layer's running mean and variance, as training would have.  The pass is
    one jitted program whose weights are arguments, so it compiles once for
    a configuration, whatever the seed.  Changes ``flat`` in place.
    """
    import jax

    from perfbench.reference.ops import Net, normalize

    def run(weights, px):
        net = Net(weights, calibrate=True)
        forward(net, normalize(px, config["preprocessing"]), config)
        return net.collected

    for path, value in jax.jit(run)(flat, pixels).items():
        flat[path] = np.asarray(value, np.float32)
    return flat


def nest(flat: dict) -> dict:
    """{("params", "a", "kernel"): w} -> {"params": {"a": {"kernel": w}}}."""
    tree: dict = {}
    for path, w in flat.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = w
    return tree
