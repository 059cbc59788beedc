"""Xception (Chollet 2017), as keras.applications.xception lays it out:
plain float32.

Entry flow: two 3x3 convolutions ("VALID"), then three residual blocks of
two separable convolutions and a 3x3/2 max pool, each with a strided 1x1
shortcut.  Middle flow: eight residual blocks of three 728-wide separable
convolutions.  Exit flow: one more strided residual block, then separable
convolutions of 1536 and 2048, global average pool and the classifier head
(the clothing model's: Dense 100 + relu, then 10 logits).  A separable
convolution is a depthwise 3x3 and a pointwise 1x1, both without bias.
"""

from __future__ import annotations

import jax

from perfbench.reference.ops import Net, max_pool_3x3_s2

BRANCH_GAIN = 0.1  # see efficientnet.BRANCH_GAIN


def forward(net: Net, x, config: dict):
    """``x``: normalized float32 NHWC.  Returns float32 logits."""
    relu = jax.nn.relu

    def sep(x, name, features, gain=2.0):
        # gain: 2 where a relu comes before (it halves the second moment);
        # the pointwise half reads the depthwise half's linear output.
        x = net.conv(x, "depthwise", 0, 3, depthwise=True, gain=gain, scope=(name,))
        return net.conv(x, "pointwise", features, scope=(name,))

    x = net.conv(x, "block1_conv1", 32, 3, stride=2, padding="VALID")
    x = relu(net.bn(x, "block1_conv1_bn"))
    x = net.conv(x, "block1_conv2", 64, 3, padding="VALID", gain=2.0)
    x = relu(net.bn(x, "block1_conv2_bn"))

    def strided_block(x, idx, first, second, lead_relu):
        res = net.conv(x, f"block{idx}_res_conv", second, stride=2,
                       gain=1.0 if lead_relu else 2.0)
        res = net.bn(res, f"block{idx}_res_bn")
        if lead_relu:
            x = relu(x)
        x = net.bn(sep(x, f"block{idx}_sepconv1", first), f"block{idx}_sepconv1_bn")
        x = relu(x)
        x = net.bn(sep(x, f"block{idx}_sepconv2", second), f"block{idx}_sepconv2_bn")
        return max_pool_3x3_s2(x) + res

    for idx, feat in ((2, 128), (3, 256), (4, 728)):
        x = strided_block(x, idx, feat, feat, lead_relu=idx > 2)
    for idx in range(5, 13):
        res = x
        for j in (1, 2, 3):
            x = relu(x)
            x = sep(x, f"block{idx}_sepconv{j}", 728)
            x = net.bn(x, f"block{idx}_sepconv{j}_bn",
                       gain=BRANCH_GAIN if j == 3 else 1.0)
        x = x + res
    x = strided_block(x, 13, 728, 1024, lead_relu=True)
    x = relu(net.bn(sep(x, "block14_sepconv1", 1536), "block14_sepconv1_bn"))
    x = relu(net.bn(sep(x, "block14_sepconv2", 2048), "block14_sepconv2_bn"))
    return net.head(x, int(config["num_classes"]), tuple(config.get("head_hidden", ())))
