"""Model zoo registry: ModelSpec.family -> flax module factory.

``build_forward`` is the one entry point the rest of the framework uses: it
returns a pure function ``f(variables, uint8_images) -> float32 logits`` with
normalization fused on-device (see ops.preprocess.normalize) -- the unit the
exporter traces and the serving engine compiles.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp

from kubernetes_deep_learning_tpu.modelspec import ModelSpec
from kubernetes_deep_learning_tpu.ops.preprocess import normalize


def create_model(spec: ModelSpec, dtype: Any = None):
    """Instantiate the flax module for a spec (dtype = compute dtype)."""
    if spec.family == "xception":
        from kubernetes_deep_learning_tpu.models.xception import Xception

        return Xception(spec.num_classes, head_hidden=spec.head_hidden, dtype=dtype)
    if spec.family == "resnet50":
        from kubernetes_deep_learning_tpu.models.resnet import ResNet50

        return ResNet50(spec.num_classes, dtype=dtype)
    if spec.family.startswith("efficientnet-"):
        from kubernetes_deep_learning_tpu.models.efficientnet import (
            SCALING,
            build_efficientnet,
        )

        variant = spec.family.removeprefix("efficientnet-")
        if variant in SCALING:  # else fall through to the registry error
            return build_efficientnet(
                variant,
                spec.num_classes,
                head_hidden=spec.head_hidden,
                dtype=dtype,
            )
    if spec.family in _vit_families():
        from kubernetes_deep_learning_tpu.models.vit import VIT_CONFIGS, ViT

        return ViT(spec.num_classes, config=VIT_CONFIGS[spec.family], dtype=dtype)
    raise KeyError(f"unknown model family {spec.family!r}")


def _vit_families() -> tuple[str, ...]:
    from kubernetes_deep_learning_tpu.models.vit import VIT_CONFIGS

    return tuple(VIT_CONFIGS)


def init_variables(spec: ModelSpec, seed: int = 0, dtype: Any = None):
    """Random-init variables with the spec's input shape (for tests/bench)."""
    import jax

    model = create_model(spec, dtype=dtype)
    dummy = jnp.zeros((1, *spec.input_shape), jnp.float32)
    return model.init(jax.random.PRNGKey(seed), dummy)


def has_fast_forward(spec: ModelSpec) -> bool:
    """Whether a fused-Pallas fast path exists for this family."""
    return spec.family == "xception"


def fused_blocks(spec: ModelSpec, batch: int) -> list[str]:
    """Names of the blocks the fast path's program for ``batch`` images
    runs as Pallas kernels (the status page's ``fused_blocks``): the same
    shape arithmetic the forward asks while it traces."""
    if not has_fast_forward(spec):
        return []
    from kubernetes_deep_learning_tpu.models import xception_fast

    return xception_fast.fused_blocks(spec, batch)


def resolve_fast(
    spec: ModelSpec, dtype: Any, fast: bool | str, backend: str | None = None
) -> bool:
    """The fast-flag resolution build_forward applies, exposed so callers
    (the serving engine's compile-failure fallback) can know ahead of time
    whether the fused Pallas path will be in the traced program.

    ``backend`` defaults to jax.default_backend(); the serving engine passes
    its actual device's platform instead, so an engine pinned to a non-TPU
    device on a TPU-backend host resolves "auto" to the graph that can
    actually compile there.
    """
    if fast == "auto":
        if backend is None:
            import jax

            backend = jax.default_backend()
        return (
            has_fast_forward(spec)
            and jnp.dtype(dtype) == jnp.bfloat16
            and backend == "tpu"
        )
    return bool(fast) and has_fast_forward(spec)


def build_forward(
    spec: ModelSpec, dtype: Any = jnp.bfloat16, fast: bool | str = "auto"
) -> Callable[[Any, Any], Any]:
    """Return ``f(variables, images) -> logits`` ready for jit/export.

    ``images`` may be uint8 HWC batches straight off the wire (the gateway
    ships uint8; see serving.protocol) or pre-normalized float32.  The uint8
    path normalizes on device so the scale/shift fuses into the first conv.
    Logits are returned as float32 regardless of compute dtype.

    ``fast``: "auto" uses the fused-Pallas fast path (models.xception_fast)
    when the family has one and the default backend is TPU -- same variable
    tree, bf16-noise-level logit difference, ~20% faster in round 2.  True
    forces it (tests use interpret mode via the module directly); False
    keeps the flax graph (exact parity; the exporter uses this so artifacts
    stay portable across platforms).
    """
    if resolve_fast(spec, dtype, fast):
        from kubernetes_deep_learning_tpu.models.xception_fast import (
            build_fast_forward,
        )

        inner = build_fast_forward(spec, dtype=dtype)
    else:
        model = create_model(spec, dtype=dtype)
        inner = lambda variables, x: model.apply(variables, x, train=False)  # noqa: E731

    def forward(variables, images):
        if images.dtype == jnp.uint8:
            x = normalize(images, spec.preprocessing)
        else:
            x = images.astype(jnp.float32)
        return inner(variables, x).astype(jnp.float32)

    return forward
