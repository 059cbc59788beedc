import numpy as np
import pytest

from kubernetes_deep_learning_tpu.export import export_model, load_artifact
from kubernetes_deep_learning_tpu.export.artifact import version_dir
from kubernetes_deep_learning_tpu.models import build_forward, init_variables
from kubernetes_deep_learning_tpu.runtime import InferenceEngine


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec

    spec = register_spec(
        ModelSpec(
            name="engine-xception",
            family="xception",
            input_shape=(96, 96, 3),
            labels=("a", "b", "c", "d"),
            preprocessing="tf",
        )
    )
    root = tmp_path_factory.mktemp("models")
    variables = init_variables(spec, seed=1)
    export_model(spec, variables, str(root), dtype=np.float32)
    artifact = load_artifact(version_dir(str(root), spec.name, 1))
    eng = InferenceEngine(artifact, buckets=(1, 2, 4, 8))
    return eng, variables, spec


def test_warmup_sets_ready(engine):
    eng, _, _ = engine
    assert not eng.ready or True  # warmup may already have run in other tests
    dt = eng.warmup()
    assert eng.ready and dt >= 0


def test_padding_does_not_change_results(engine):
    import jax

    eng, variables, spec = engine
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(3, 96, 96, 3), dtype=np.uint8)  # pads to 4
    got = eng.predict(x)
    assert got.shape == (3, 4)
    fwd = jax.jit(build_forward(spec, dtype=None))
    want = np.asarray(fwd(variables, x))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bucket_selection(engine):
    eng, _, _ = engine
    assert eng.bucket_for(1) == 1
    assert eng.bucket_for(3) == 4
    assert eng.bucket_for(8) == 8
    with pytest.raises(ValueError):
        eng.bucket_for(9)


def test_engine_flops_per_image_from_lowered_cost_analysis(engine):
    # The live-MFU FLOPs path: lowering-only cost analysis of the exact
    # flax graph (no XLA compile, no device work).  On any backend that
    # supports cost analysis it must produce a positive, batch-normalized
    # figure; None is the accepted degraded answer elsewhere.
    eng, _, _ = engine
    flops = eng._flops_per_image(2)
    assert flops is not None and flops > 0
    # FLOPs/image is ~batch-invariant (same math per row).
    flops1 = eng._flops_per_image(1)
    assert flops1 == pytest.approx(flops, rel=0.2)


def test_nothing_lowers_unasked_and_the_audit_computes_on_demand(engine):
    # The MFU accountant is gone (ISSUE 24): completions start no background
    # lowering and mint no gauge; FLOPs/image exists only where it is asked
    # for, on the audit page, computed once and cached.
    import threading

    eng, _, _ = engine
    eng.predict(np.zeros((2, *eng.spec.input_shape), np.uint8))
    assert not [t for t in threading.enumerate() if t.name == "kdlt-mfu-flops"]
    page = eng.registry.render()
    assert "mfu" not in page and "busy_ratio" not in page
    assert not eng._audit_flops
    audit = eng.bucket_audit()
    assert audit["buckets"][2]["flops_per_image"] > 0
    assert set(eng._audit_flops) == set(eng.buckets)
    assert eng.bucket_audit()["buckets"][2] == audit["buckets"][2]


def test_device_info_memory_block_follows_the_backend(engine, monkeypatch):
    # GET /v1/models' device block: `memory` as the allocator reports it,
    # absent where the backend reports none (the CPU's memory_stats() is
    # None) -- never a made-up zero.
    from types import SimpleNamespace

    eng, _, _ = engine
    assert "memory" not in eng.device_info()
    stats = {
        "bytes_in_use": 5, "peak_bytes_in_use": 7, "peak_bytes_reserved": 11,
        "bytes_limit": 13, "num_allocs": 99,
    }
    monkeypatch.setattr(eng, "_device", SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite", memory_stats=lambda: stats,
    ))
    info = eng.device_info()
    assert info["memory"] == {
        "bytes_in_use": 5, "peak_bytes_in_use": 7, "peak_bytes_reserved": 11,
        "bytes_limit": 13,
    }
    assert info["peak_tflops"] == 98.5  # float32 artifact on a v5e


def test_input_validation(engine):
    eng, _, _ = engine
    with pytest.raises(ValueError, match="expected"):
        eng.predict(np.zeros((1, 10, 10, 3), np.uint8))


def test_predict_scores_labels(engine):
    eng, _, spec = engine
    out = eng.predict_scores(np.zeros((2, 96, 96, 3), np.uint8))
    assert len(out) == 2
    assert set(out[0]) == set(spec.labels)


def test_metrics_populated(engine):
    eng, _, _ = engine
    eng.predict(np.zeros((1, 96, 96, 3), np.uint8))
    text = eng.registry.render()
    assert "kdlt_engine_images_total" in text
    assert "kdlt_engine_infer_seconds" in text


def test_fast_compile_failure_degrades_to_exact_graph(engine):
    """Round-2 P0 regression: a Mosaic compile failure on the fused fast
    path must degrade the engine to the flax graph, not kill the model.

    fast=True on the CPU backend is a REAL reproduction, not a mock: the
    Pallas TPU kernel cannot lower for CPU outside interpret mode, so the
    first warmup bucket raises at compile exactly like a batch-1 Mosaic
    rejection did on TPU.
    """
    _, variables, spec = engine
    import jax

    if jax.default_backend() != "cpu":  # conftest forces cpu; belt and braces
        pytest.skip("reproduction requires a backend where Pallas cannot lower")

    from kubernetes_deep_learning_tpu.export import export_model, load_artifact
    from kubernetes_deep_learning_tpu.export.artifact import version_dir
    import tempfile

    with tempfile.TemporaryDirectory() as root:
        export_model(spec, variables, root, dtype=np.float32)
        artifact = load_artifact(version_dir(root, spec.name, 1))
        eng = InferenceEngine(
            artifact, buckets=(1, 2), use_exported=False, fast=True
        )
        assert eng._fast_engaged
        dt = eng.warmup()
        assert eng.ready and dt >= 0
        assert eng.fast_degraded
        assert not eng._fast_engaged
        # ...and the degrade can never be read as a clean boot: the status
        # surface (GET /v1/models) and the gauge both say it.
        info = eng.device_info()
        assert info["fast_degraded"] is True and info["fast_engaged"] is False
        assert info["platform"] == "cpu" and info["device_count"] >= 1
        assert "kdlt_engine_fast_degraded 1.0" in eng.registry.render()
        # and it actually serves, matching the exact graph
        x = np.zeros((2, *spec.input_shape), np.uint8)
        got = eng.predict(x)
        want = np.asarray(jax.jit(build_forward(spec, dtype=None))(variables, x))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_donation_engaged_on_every_bucket(engine):
    # The donation audit's regression surface (ISSUE 9): every bucket's
    # compiled forward donates the batch argument (Lowered.args_info is
    # trace+lower only -- no XLA compile), and NEVER the variables --
    # donating the weights would free them under the next request.
    eng, _, _ = engine
    for b in eng.buckets:
        info = eng.donation_info(b)
        assert info["images"] is True, f"bucket {b}: batch not donated"
        assert info["variables"] is False, f"bucket {b}: variables donated!"


@pytest.mark.slow  # one extra full-engine compile
def test_donated_logits_bit_identical_to_nondonated(engine):
    # Donation is a memory-lifetime annotation, not a numerics change: the
    # same forward jitted WITHOUT donate_argnums must produce bit-identical
    # logits for the same batch.
    import jax
    import jax.numpy as jnp

    eng, _, spec = engine
    x = np.random.default_rng(5).integers(
        0, 256, size=(1, *spec.input_shape), dtype=np.uint8
    )
    donated = eng.predict(x)
    plain = jax.jit(eng._live_forward(jnp.dtype(eng._compute_dtype)))
    want = np.asarray(plain(eng._variables, x))[:1]
    assert np.array_equal(donated, want)


def test_donation_env_kill_switch(monkeypatch):
    # KDLT_DONATE=0 must build a non-donating program (the A/B lever the
    # bit-identity contract above is verified against on real devices).
    from kubernetes_deep_learning_tpu.runtime.engine import donation_enabled

    assert donation_enabled() is True
    monkeypatch.setenv("KDLT_DONATE", "0")
    assert donation_enabled() is False
    monkeypatch.delenv("KDLT_DONATE")
    assert donation_enabled(False) is False
