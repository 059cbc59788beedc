"""The handful of operations both references are written in.

``Net`` carries the one thing that differs between a declaring pass, the
float32 reference and its lower-precision control: where a weight comes
from and in which precision a contraction's operands are read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-3  # Keras BatchNormalization's epsilon, which both sources use

# How an 8-bit control reads the operands of every contraction: through
# float8 (e4m3: 3 mantissa bits), accumulated in float32.  It is the
# nearest precision below the bfloat16 the configurations state.
PRECISIONS = ("float32", "fp8")


class Net:
    """Weights by path, declared on first use.

    ``weights=None`` is the declaring pass: every ``get`` records the leaf
    (path, shape, kind, gain) and hands back zeros, so one definition of the
    architecture yields both the list of leaves and the forward.
    """

    def __init__(self, weights: dict | None, precision: str = "float32",
                 calibrate: bool = False):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.weights = weights
        self.precision = precision
        # Calibrating pass (weights.calibrate): every BatchNorm takes its
        # mean and variance from the activations that reach it, and the
        # pass hands them back in ``collected``.
        self.calibrate = calibrate
        self.collected: dict = {}
        self.declared: list[tuple[tuple[str, ...], tuple[int, ...], str, float]] = []

    def get(self, path: tuple[str, ...], shape: tuple[int, ...], kind: str,
            gain: float = 1.0):
        shape = tuple(int(s) for s in shape)
        if self.weights is None:
            self.declared.append((path, shape, kind, float(gain)))
            return jnp.zeros(shape, jnp.float32)
        w = self.weights[path]
        if tuple(w.shape) != shape:
            raise ValueError(f"{'/'.join(path)}: shape {w.shape} != {shape}")
        return jnp.asarray(w, jnp.float32)

    def operand(self, a):
        if self.precision == "fp8":
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a

    # --- layers ---------------------------------------------------------

    def conv(self, x, name: str, features: int, kernel: int = 1, stride: int = 1,
             padding: str = "SAME", depthwise: bool = False, gain: float = 1.0,
             bias: bool = False, scope: tuple[str, ...] = ()):
        c_in = x.shape[-1]
        if depthwise:
            shape, groups, kind = (kernel, kernel, 1, c_in), c_in, "dwconv"
        else:
            shape, groups, kind = (kernel, kernel, c_in, features), 1, "conv"
        w = self.get(("params", *scope, name, "kernel"), shape, kind, gain)
        y = lax.conv_general_dilated(
            self.operand(x), self.operand(w), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=lax.Precision.HIGHEST,
        )
        if bias:
            y = y + self.get(("params", *scope, name, "bias"), (shape[-1],), "bias")
        return y

    def bn(self, x, name: str, scope: tuple[str, ...] = (), gain: float = 1.0):
        """Inference BatchNorm.  ``gain`` scales the variance it lets through:
        small on the last BatchNorm of a residual branch, as trained
        networks have it (and as zero-gamma initialisation starts them)."""
        c = (x.shape[-1],)
        scale = self.get(("params", *scope, name, "scale"), c, "bn_scale", gain)
        bias = self.get(("params", *scope, name, "bias"), c, "bn_bias")
        mean = self.get(("batch_stats", *scope, name, "mean"), c, "bn_mean")
        var = self.get(("batch_stats", *scope, name, "var"), c, "bn_var")
        if self.calibrate:
            mean, var = x.mean(axis=(0, 1, 2)), x.var(axis=(0, 1, 2))
            # A channel that the few calibration pictures barely excite
            # must not be amplified without bound on others.
            var = jnp.maximum(var, 0.05 * var.mean())
            self.collected[("batch_stats", *scope, name, "mean")] = mean
            self.collected[("batch_stats", *scope, name, "var")] = var
        return (x - mean) * (scale * lax.rsqrt(var + BN_EPS)) + bias

    def dense(self, x, name: str, features: int, gain: float = 1.0,
              scope: tuple[str, ...] = ()):
        w = self.get(("params", *scope, name, "kernel"), (x.shape[-1], features),
                     "dense", gain)
        b = self.get(("params", *scope, name, "bias"), (features,), "bias")
        return jnp.dot(self.operand(x), self.operand(w),
                       precision=lax.Precision.HIGHEST) + b

    def head(self, x, num_classes: int, hidden: tuple[int, ...] = ()):
        """Global average pool, optional hidden Dense+relu, logits."""
        x = x.mean(axis=(1, 2))
        for i, width in enumerate(hidden):
            x = jax.nn.relu(self.dense(x, f"hidden_{i}", width, scope=("head",)))
        return self.dense(x, "logits", num_classes, scope=("head",))


def normalize(pixels, mode: str):
    """uint8 RGB -> the network's float input."""
    x = pixels.astype(jnp.float32)
    if mode == "tf":       # Keras "tf" mode (Xception): [-1, 1]
        return x / 127.5 - 1.0
    if mode == "torch":    # ImageNet mean/std on [0, 1] (EfficientNet)
        mean = jnp.array([0.485, 0.456, 0.406], jnp.float32)
        std = jnp.array([0.229, 0.224, 0.225], jnp.float32)
        return (x / 255.0 - mean) / std
    raise ValueError(f"unknown preprocessing {mode!r}")


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
