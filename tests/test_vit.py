import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_deep_learning_tpu.models import build_forward, create_model, init_variables
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, get_spec, register_spec


@pytest.fixture(scope="module")
def tiny_vit_spec() -> ModelSpec:
    return register_spec(
        ModelSpec(
            name="tiny-vit",
            family="vit-tiny",
            input_shape=(32, 32, 3),
            labels=("a", "b", "c", "d"),
            preprocessing="tf",
            description="test-only tiny vit (16 tokens)",
        )
    )


def test_forward_shape_and_dtype(tiny_vit_spec):
    variables = init_variables(tiny_vit_spec, seed=0)
    fwd = build_forward(tiny_vit_spec, dtype=None)
    x = np.zeros((2, *tiny_vit_spec.input_shape), np.uint8)
    logits = jax.jit(fwd)(variables, x)
    assert logits.shape == (2, tiny_vit_spec.num_classes)
    assert logits.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(logits)))


def test_flash_and_reference_attention_agree(tiny_vit_spec):
    # train=False routes attention through jax.lax.platform_dependent (the
    # Pallas flash kernel on TPU, einsum on CPU); train=True routes through
    # attention_trainable (flash forward + custom-VJP blockwise backward,
    # einsum primal on CPU).  No dropout/batchnorm, so both paths compute
    # the same function and must agree.
    model = create_model(tiny_vit_spec)
    variables = init_variables(tiny_vit_spec, seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((2, *tiny_vit_spec.input_shape)), jnp.float32
    )
    infer = model.apply(variables, x, train=False)
    train = model.apply(variables, x, train=True)
    np.testing.assert_allclose(np.asarray(infer), np.asarray(train), atol=1e-4)


def test_vit_short_seq_exports_portable_and_serves(tiny_vit_spec, tmp_path):
    # Since the round-4 shape routing, short-S ViTs (S <= EINSUM_MAX_SEQ)
    # run the platform-portable einsum attention, so export emits ONE
    # portable module -- no per-platform fallback needed -- and the engine
    # serves it.
    import os

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.export.exporter import export_model
    from kubernetes_deep_learning_tpu.runtime.engine import InferenceEngine

    variables = init_variables(tiny_vit_spec, seed=0)
    directory = export_model(tiny_vit_spec, variables, str(tmp_path))
    files = set(os.listdir(directory))
    assert art.MODULE_FILE in files

    a = art.load_artifact(directory)
    engine = InferenceEngine(a, buckets=(1, 2), use_exported=True)
    engine.warmup()
    out = engine.predict(np.zeros((2, *tiny_vit_spec.input_shape), np.uint8))
    assert out.shape == (2, tiny_vit_spec.num_classes)
    assert np.all(np.isfinite(out))


def test_vit_long_seq_exports_per_platform(tmp_path):
    # Past the einsum sequence budget the flash branch is back in the
    # traced module; its platform_dependent cannot co-lower into one
    # cpu+tpu module, so export_model must fall back to one module per
    # platform, and the artifact must load with the per-platform layout.
    import os

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.export.exporter import export_model
    from kubernetes_deep_learning_tpu.ops.attention import EINSUM_MAX_SEQ

    spec = register_spec(
        ModelSpec(
            name="tiny-vit-long",
            family="vit-tiny",
            # patch 8 -> (256/8)^2 = 1024 tokens > EINSUM_MAX_SEQ: the
            # serving attention routes to the flash kernel.
            input_shape=(256, 256, 3),
            labels=("a", "b"),
            preprocessing="tf",
            description="test-only long-sequence vit (1024 tokens)",
        )
    )
    assert (256 // 8) ** 2 > EINSUM_MAX_SEQ

    variables = init_variables(spec, seed=0)
    directory = export_model(spec, variables, str(tmp_path))
    files = set(os.listdir(directory))
    # platform_dependent branches survive into the traced module, which
    # cannot co-lower cpu+tpu -> per-platform layout.
    assert art.platform_module_file("cpu") in files
    assert art.platform_module_file("tpu") in files
    assert art.MODULE_FILE not in files
    a = art.load_artifact(directory)
    assert a.metadata["module_layout"] == "per-platform"
    assert a.module_bytes_for("cpu") is not None

    # The engine must pick its device's module at load and serve from it
    # (CPU here -> the einsum branch of the platform-dependent module).
    from kubernetes_deep_learning_tpu.runtime.engine import InferenceEngine

    engine = InferenceEngine(a, buckets=(1,), use_exported=True)
    engine.warmup()
    out = engine.predict(np.zeros((1, *spec.input_shape), np.uint8))
    assert out.shape == (1, spec.num_classes)
    assert np.all(np.isfinite(out))


def test_vit_b16_structure():
    spec = get_spec("vit-b16-imagenet")
    model = create_model(spec)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, *spec.input_shape)))
    )
    params = variables["params"]
    # 256x256 / 16 -> 16x16 = 256 tokens, width 768.
    assert params["pos_embed"].shape == (1, 256, 768)
    assert params["head"]["kernel"].shape == (768, 1000)
    assert params["block_11"]["attn"]["query"]["kernel"].shape == (768, 12, 64)


def test_train_step_on_vit(tiny_vit_spec):
    # BN-free family: the train step must run without batch_stats updates.
    import optax

    from kubernetes_deep_learning_tpu.training.trainer import (
        build_train_step,
        create_train_state,
    )

    tx = optax.sgd(1e-3)
    state = create_train_state(tiny_vit_spec, tx, seed=0)
    step = build_train_step(tiny_vit_spec, tx)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(4, *tiny_vit_spec.input_shape), dtype=np.uint8)
    labels = rng.integers(0, tiny_vit_spec.num_classes, size=(4,), dtype=np.int32)
    state, metrics = step(state, images, labels)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
