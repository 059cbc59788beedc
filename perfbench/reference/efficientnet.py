"""EfficientNet (Tan & Le 2019), B0-B7 by compound scaling: plain float32.

MBConv = 1x1 expand (BN, swish) -> depthwise kxk (BN, swish) ->
squeeze-excite (global mean, 1x1 reduce + swish, 1x1 expand + sigmoid gate,
sized from the block's input width at ratio 0.25) -> 1x1 project (BN), with
an identity skip where stride is 1 and the width is unchanged.

Departures from keras.applications.EfficientNetB7, shared with the program
under test and noted here: convolutions at stride 2 pad "SAME" (Keras pads
explicitly and convolves "VALID"; the two differ by one pixel of offset at
even sizes), and drop-connect and dropout are left out (inert at inference).
"""

from __future__ import annotations

import math

import jax

from perfbench.reference.ops import Net

# (expand ratio, channels, repeats, stride, kernel) of EfficientNet-B0.
BASE_BLOCKS = (
    (1, 16, 1, 1, 3), (6, 24, 2, 2, 3), (6, 40, 2, 2, 5), (6, 80, 3, 2, 3),
    (6, 112, 3, 1, 5), (6, 192, 4, 2, 5), (6, 320, 1, 1, 3),
)
SE_RATIO = 0.25
# Variance a residual branch adds to the trunk, through its last
# BatchNorm's scale.  With unit branches a perturbation (bfloat16's
# rounding) is amplified at every one of the 48 adds and the random network
# is chaotic; a trained one is not.
BRANCH_GAIN = 0.05
# A contraction's gain undoes what the nonlinearity before it took from the
# second moment: E[swish(z)^2] is 0.36 for unit z, and the squeeze-excite
# gate halves the signal once more (a factor 4) ahead of the projection.
SWISH_GAIN = 2.8


def round_filters(filters: float, width: float, divisor: int = 8) -> int:
    filters *= width
    new = max(divisor, int(filters + divisor / 2) // divisor * divisor)
    if new < 0.9 * filters:
        new += divisor
    return int(new)


def forward(net: Net, x, config: dict):
    """``x``: normalized float32 NHWC.  Returns float32 logits."""
    width, depth = float(config["width_coefficient"]), float(config["depth_coefficient"])
    swish = jax.nn.silu
    x = net.conv(x, "stem_conv", round_filters(32, width), 3, stride=2)
    x = swish(net.bn(x, "stem_bn"))
    block = 0
    for expand, channels, repeats, stride, kernel in BASE_BLOCKS:
        features = round_filters(channels, width)
        for rep in range(int(math.ceil(depth * repeats))):
            scope = (f"block{block}",)
            c_in = x.shape[-1]
            y = x
            if expand != 1:
                y = net.conv(y, "expand_conv", c_in * expand, scope=scope)
                y = swish(net.bn(y, "expand_bn", scope=scope))
            y = net.conv(y, "dwconv", 0, kernel, stride=stride if rep == 0 else 1,
                         depthwise=True, gain=SWISH_GAIN, scope=scope)
            y = swish(net.bn(y, "dw_bn", scope=scope))
            s = y.mean(axis=(1, 2), keepdims=True)
            se = scope + ("se",)
            s = swish(net.conv(s, "reduce", max(1, int(c_in * SE_RATIO)), gain=SWISH_GAIN,
                               bias=True, scope=se))
            s = net.conv(s, "expand", y.shape[-1], bias=True, scope=se)
            y = y * jax.nn.sigmoid(s)
            skip = (stride if rep == 0 else 1) == 1 and c_in == features
            y = net.conv(y, "project_conv", features, gain=SWISH_GAIN * 4.0, scope=scope)
            y = net.bn(y, "project_bn", scope=scope, gain=BRANCH_GAIN if skip else 1.0)
            x = y + x if skip else y
            block += 1
    x = net.conv(x, "top_conv", round_filters(1280, width))
    x = swish(net.bn(x, "top_bn"))
    return net.head(x, int(config["num_classes"]), tuple(config.get("head_hidden", ())))
