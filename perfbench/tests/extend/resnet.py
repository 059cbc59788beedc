"""ResNet50 (He et al. 2015, v1 bottleneck), as keras.applications.ResNet50
lays it out: plain float32.  A family the benchmark has no configuration
of: ``tests/test_extend.py`` adds it as a later PR would, by this file.

Stem: 7x7/2 convolution (3 pixels of padding), 3x3/2 max pool.  Four stages
of 3, 4, 6 and 3 bottleneck blocks (1x1 reduce, 3x3, 1x1 expand to four
times the width, every convolution with a bias and its own BatchNorm at
Keras' ResNet epsilon), the first of a stage with a projected shortcut and,
from the second stage on, stride 2; relu after every add.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.ops import Net

BN_EPS = 1.001e-5
STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))
BRANCH_GAIN = 0.1   # the last BatchNorm of a residual branch, as trained networks have it


def bn(net: Net, x, name: str, scope: tuple, gain: float = 1.0):
    """``Net.bn`` at this family's epsilon."""
    c = (x.shape[-1],)
    scale = net.get(("params", *scope, name, "scale"), c, "bn_scale", gain)
    bias = net.get(("params", *scope, name, "bias"), c, "bn_bias")
    mean = net.get(("batch_stats", *scope, name, "mean"), c, "bn_mean")
    var = net.get(("batch_stats", *scope, name, "var"), c, "bn_var")
    if net.calibrate:
        mean, var = x.mean(axis=(0, 1, 2)), x.var(axis=(0, 1, 2))
        var = jnp.maximum(var, 0.05 * var.mean())
        net.collected[("batch_stats", *scope, name, "mean")] = mean
        net.collected[("batch_stats", *scope, name, "var")] = var
    return (x - mean) * (scale * lax.rsqrt(var + BN_EPS)) + bias


def forward(net: Net, x, config: dict):
    """``x``: normalized float32 NHWC.  Returns float32 logits."""
    relu = jax.nn.relu
    x = net.conv(x, "conv1_conv", 64, 7, stride=2, padding=[(3, 3), (3, 3)], bias=True)
    x = relu(bn(net, x, "conv1_bn", ()))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for stage, (width, blocks) in enumerate(STAGES, start=2):
        for block in range(1, blocks + 1):
            scope = (f"conv{stage}_block{block}",)
            stride = 2 if block == 1 and stage > 2 else 1
            shortcut = x
            if block == 1:
                shortcut = net.conv(x, "0_conv", 4 * width, stride=stride, gain=2.0,
                                    bias=True, scope=scope)
                shortcut = bn(net, shortcut, "0_bn", scope)
            y = net.conv(x, "1_conv", width, stride=stride, gain=2.0, bias=True, scope=scope)
            y = relu(bn(net, y, "1_bn", scope))
            y = net.conv(y, "2_conv", width, 3, gain=2.0, bias=True, scope=scope)
            y = relu(bn(net, y, "2_bn", scope))
            y = net.conv(y, "3_conv", 4 * width, gain=2.0, bias=True, scope=scope)
            y = bn(net, y, "3_bn", scope, gain=BRANCH_GAIN)
            x = relu(y + shortcut)
    return net.head(x, int(config["num_classes"]), tuple(config.get("head_hidden", ())))
