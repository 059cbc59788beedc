"""Data-parallel serving through the full HTTP model server: the engine's
``mesh`` mode (BASELINE.json config 5) on the 8-virtual-device CPU mesh."""

import threading

import numpy as np
import pytest
import requests

from kubernetes_deep_learning_tpu.export.exporter import export_model
from kubernetes_deep_learning_tpu.models import build_forward, init_variables
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.parallel.mesh import make_mesh
from kubernetes_deep_learning_tpu.runtime import InferenceEngine
from kubernetes_deep_learning_tpu.serving.model_server import ModelServer


@pytest.fixture(scope="module")
def shard_spec() -> ModelSpec:
    return register_spec(
        ModelSpec(
            name="shard-vit",
            family="vit-tiny",
            input_shape=(16, 16, 3),
            labels=("a", "b", "c"),
            preprocessing="tf",
            description="test-only sharded-serving model",
        )
    )


@pytest.fixture(scope="module")
def artifact_root(shard_spec, tmp_path_factory):
    root = tmp_path_factory.mktemp("shard-models")
    export_model(shard_spec, init_variables(shard_spec, seed=0), str(root))
    return str(root)


def test_mesh_engine_buckets_round_to_data_axis(shard_spec, artifact_root):
    from kubernetes_deep_learning_tpu.export import artifact as art

    mesh = make_mesh(8, model_parallel=2)  # data axis = 4
    a = art.load_artifact(art.version_dir(artifact_root, shard_spec.name, 1))
    eng = InferenceEngine(a, buckets=(1, 2, 6, 16), mesh=mesh)
    assert eng.buckets == (4, 8, 16)


def test_mesh_engine_matches_single_device(shard_spec, artifact_root):
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.export import artifact as art

    mesh = make_mesh(8)
    a = art.load_artifact(art.version_dir(artifact_root, shard_spec.name, 1))
    eng = InferenceEngine(a, buckets=(8,), mesh=mesh)
    eng.warmup()
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(5, *shard_spec.input_shape), dtype=np.uint8)
    got = eng.predict(images)
    fwd = build_forward(shard_spec, dtype=jnp.dtype(eng._compute_dtype))
    want = np.asarray(fwd(a.variables, images))
    # bfloat16 compute: differently-fused programs legitimately differ at
    # ~1e-2 on unit-scale logits; the check is placement/mapping, not ulps.
    np.testing.assert_allclose(got, want, atol=5e-2)


def test_profile_endpoint_captures_trace(shard_spec, artifact_root):
    import os

    server = ModelServer(artifact_root, port=0, buckets=(1,), use_batcher=False)
    try:
        server.warmup()
        server.start()
        base = f"http://localhost:{server.port}"
        r = requests.post(base + "/debug/profile", json={"seconds": 0.3}, timeout=30)
        assert r.status_code == 200, r.text
        trace_dir = r.json()["trace_dir"]
        assert any(os.scandir(trace_dir)), "trace dir is empty"
        r = requests.post(
            base + "/debug/profile", json={"seconds": 100}, timeout=30
        )
        assert r.status_code == 400
    finally:
        server.shutdown()


def test_served_data_parallel_over_mesh(shard_spec, artifact_root):
    server = ModelServer(
        artifact_root, port=0, buckets=(1, 2, 8, 16), mesh=make_mesh(8),
        max_delay_ms=5.0,
    )
    try:
        server.warmup()
        server.start()
        url = f"http://localhost:{server.port}/v1/models/{shard_spec.name}:predict"

        # Concurrent single-image requests must coalesce into mesh-sharded
        # batches and map back to the right requester.
        results, errors = {}, []

        def worker(v):
            try:
                body = {"instances": np.full((1, 16, 16, 3), v, np.uint8).tolist()}
                r = requests.post(url, json=body, timeout=60)
                assert r.status_code == 200, r.text
                results[v] = r.json()["predictions"][0]
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(v,)) for v in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(results) == 12
        # Distinct inputs must give distinct logits (mapping not scrambled).
        eng = server.models[shard_spec.name].engine
        direct = eng.predict(
            np.stack([np.full((16, 16, 3), v, np.uint8) for v in range(12)])
        )
        for v in range(12):
            got = [results[v][label] for label in shard_spec.labels]
            # Different bucket shapes fuse differently in bfloat16; the
            # check is that request->row mapping isn't scrambled.
            np.testing.assert_allclose(got, direct[v], atol=5e-2)
    finally:
        server.shutdown()


@pytest.fixture(scope="module")
def xc_spec() -> ModelSpec:
    return register_spec(
        ModelSpec(
            name="shard-xc",
            family="xception",
            input_shape=(96, 96, 3),
            labels=("a", "b", "c", "d"),
            preprocessing="tf",
            description="test-only sharded fused-fast-path model",
        )
    )


def test_shard_map_fast_path_matches_flax(xc_spec, monkeypatch):
    """The fused fast forward under shard_map (each chip runs the fused
    Pallas program on its local batch shard -- what mesh serving runs on
    TPU) vs the flax graph on identical variables.  Interpret mode stands
    in for Mosaic on CPU; real-TPU engagement is covered by
    resolve_sharded_fast + the engine wiring below."""
    import functools

    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import xception_fast
    from kubernetes_deep_learning_tpu.parallel.dataparallel import (
        build_sharded_forward,
    )

    monkeypatch.setattr(
        xception_fast,
        "build_fast_forward",
        functools.partial(xception_fast.build_fast_forward, interpret=True),
    )
    mesh = make_mesh(8)
    variables = init_variables(xc_spec, seed=2)
    call = build_sharded_forward(mesh=mesh, spec=xc_spec, dtype=jnp.bfloat16, fast=True)
    rng = np.random.default_rng(3)
    images = rng.integers(0, 256, size=(16, *xc_spec.input_shape), dtype=np.uint8)
    got = np.asarray(call(variables, images))
    want = np.asarray(
        build_forward(xc_spec, dtype=jnp.bfloat16, fast=False)(variables, images)
    )
    # 2e-2: same interpreter bf16-rounding bound as test_fused_sepconv.
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"shard_map fast path diverges: {rel:.2e}"


def test_mesh_engine_fast_resolution_and_degrade(xc_spec, tmp_path):
    """resolve_sharded_fast gates on platform/model-axis; a mesh engine
    with the fast path FORCED on CPU reproduces a real Mosaic-style compile
    failure under shard_map and must degrade to the flax graph fleet-wide,
    same contract as single-device serving."""
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.parallel.dataparallel import (
        resolve_sharded_fast,
    )

    mesh = make_mesh(8)
    # auto on a CPU mesh: exact graph (no Pallas on CPU outside interpret)
    assert not resolve_sharded_fast(xc_spec, mesh, jnp.bfloat16, "auto")
    # model axis > 1: exact graph even where fast would otherwise resolve
    assert not resolve_sharded_fast(
        xc_spec, make_mesh(8, model_parallel=2), jnp.bfloat16, True
    )

    export_model(xc_spec, init_variables(xc_spec, seed=1), str(tmp_path))
    a = art.load_artifact(art.version_dir(str(tmp_path), xc_spec.name, 1))
    eng = InferenceEngine(a, buckets=(8,), mesh=mesh, fast=True)
    assert eng._fast_engaged
    eng.warmup()
    assert eng.ready and eng.fast_degraded
    out = eng.predict(np.zeros((3, *xc_spec.input_shape), np.uint8))
    assert out.shape == (3, 4)
