"""The wire form of a uint8 batch (ISSUE 29): the engine hands a compiled
program the caller's bytes as ``uint8[bucket, H, W*C]`` -- a view, never a
copy -- and the program's first operation turns them back into NHWC.
Callers still pass ``uint8[n, H, W, C]``; nothing they can see changes,
bit for bit.

Three engines on the CPU: the live-jit forward, the exported StableHLO
module and a mesh engine over four of conftest's virtual devices.
"""

from __future__ import annotations

import numpy as np
import pytest

from kubernetes_deep_learning_tpu.export import export_model, load_artifact
from kubernetes_deep_learning_tpu.export.artifact import version_dir
from kubernetes_deep_learning_tpu.models import init_variables
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.runtime import engine as engine_lib
from kubernetes_deep_learning_tpu.runtime.engine import InferenceEngine
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

KINDS = ("live", "exported", "mesh")
BUCKETS = (1, 4, 16)


def _artifact(tmp_path_factory, name, family, input_shape):
    spec = register_spec(ModelSpec(
        name=name, family=family, input_shape=input_shape,
        labels=("a", "b", "c"), preprocessing="tf",
        description="test-only wire-form model",
    ))
    root = tmp_path_factory.mktemp(name)
    export_model(spec, init_variables(spec, seed=3), str(root), dtype=np.float32)
    return load_artifact(version_dir(str(root), spec.name, 1))


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    """kind -> (engine, the same program taking NHWC as before this change)."""
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.parallel.dataparallel import (
        build_mesh_serving_jit,
    )
    from kubernetes_deep_learning_tpu.parallel.mesh import make_mesh

    xception = _artifact(tmp_path_factory, "wire-xception", "xception", (49, 49, 3))
    vit = _artifact(tmp_path_factory, "wire-vit", "vit-tiny", (16, 16, 3))
    out = {}
    live = InferenceEngine(xception, buckets=BUCKETS, use_exported=False,
                           registry=metrics_lib.Registry())
    out["live"] = (live, jax.jit(live._live_forward(jnp.dtype(live._compute_dtype))))
    exported = InferenceEngine(vit, buckets=BUCKETS, registry=metrics_lib.Registry())
    assert exported._jitted_f32 is None, "the exported module is not what serves"
    out["exported"] = (exported, jax.jit(vit.exported_for("cpu").call))
    mesh = make_mesh(4)
    meshed = InferenceEngine(vit, buckets=BUCKETS, mesh=mesh,
                             registry=metrics_lib.Registry())
    dtype = jnp.dtype(meshed._compute_dtype)
    out["mesh"] = (meshed, build_mesh_serving_jit(
        meshed.spec, mesh, dtype, fast=False, forward=meshed._live_forward(dtype)))
    return out


def _pixels(image, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=(n, *image), dtype=np.uint8)


def _body_view(image, n, seed=0):
    """``uint8[n, H, W, C]`` as the tensor wire hands it over: a read-only
    view of a bytes object, starting at an odd offset."""
    pixels = _pixels(image, n, seed)
    body = b"\x00" * 37 + pixels.tobytes()
    view = np.frombuffer(body, np.uint8, offset=37).reshape(pixels.shape)
    assert not view.flags.writeable and view.ctypes.data % 2 == 1
    return view, pixels


def _count(eng, path):
    for line in eng.registry.render().splitlines():
        if line.startswith("kdlt_engine_input_total{") and f'path="{path}"' in line:
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"no kdlt_engine_input_total{{path={path!r}}} series")


@pytest.mark.parametrize("shape,want", [
    ((512, 299, 299, 3), (512, 299, 897)),   # the Xception cell
    ((64, 600, 600, 3), (64, 600, 1800)),    # the B7 cell
    ((1, 16, 16, 3), (1, 16, 48)),
    ((8, 49, 49, 1), (8, 49, 49)),
    ((4, 512, 384, 3), (4, 512, 1152)),      # an ingest staging shape
])
def test_wire_form_is_shape_arithmetic(shape, want):
    assert engine_lib.wire_form(shape) == want
    # The leading axis stays the batch: a mesh engine shards axis 0.
    assert engine_lib.wire_form(shape)[0] == shape[0]


@pytest.mark.parametrize("image", [(49, 49, 3), (16, 16, 3), (6, 6, 4)])
def test_to_wire_is_a_view_that_from_wire_undoes(image):
    import jax

    view, pixels = _body_view(image, 4)
    wire = engine_lib.to_wire(view)
    assert np.shares_memory(wire, view) and not wire.flags.writeable
    assert wire.shape == engine_lib.wire_form(view.shape) and wire.dtype == np.uint8
    back = jax.jit(lambda w: engine_lib.from_wire(w, image))(wire)
    assert back.dtype == np.uint8 and np.array_equal(np.asarray(back), pixels)


def test_stage_counts_a_copy_only_where_one_is_made():
    x = np.zeros((6, 16, 16, 3), np.uint8)
    wire, copied = engine_lib.stage(x[:4], 4)  # NativeBatcher's staging[:n]
    assert not copied and np.shares_memory(wire, x)
    wire, copied = engine_lib.stage(x[:3], 4)
    assert copied and wire.shape[0] == 4 and not np.shares_memory(wire, x)
    wire, copied = engine_lib.stage(x[::2][:2], 2)
    assert copied and not np.shares_memory(wire, x)


def _fills():
    return [(b, fill) for b in BUCKETS for fill in ("whole", "short")
            if not (b == 1 and fill == "short")]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("bucket,fill", _fills())
def test_logits_equal_the_old_program_bit_for_bit(engines, kind, bucket, fill):
    eng, old = engines[kind]
    b = eng.bucket_for(bucket)  # a mesh engine rounds up to its data axis
    n = b if fill == "whole" else b - 1
    images = _pixels(eng.spec.input_shape, n, seed=bucket)
    padded = np.zeros((b, *eng.spec.input_shape), np.uint8)
    padded[:n] = images
    want = np.asarray(old(eng._variables, padded))[:n]
    got = eng.predict(images)
    assert got.shape == want.shape and np.array_equal(got, want)


@pytest.mark.parametrize("kind", KINDS)
def test_a_readonly_view_at_an_odd_offset_is_a_view(engines, kind):
    eng, old = engines[kind]
    b = eng.buckets[-1]
    view, pixels = _body_view(eng.spec.input_shape, b, seed=7)
    before = _count(eng, "view"), _count(eng, "copy")
    got = eng.predict(view)
    assert (_count(eng, "view"), _count(eng, "copy")) == (before[0] + 1, before[1])
    assert np.array_equal(got, np.asarray(old(eng._variables, pixels)))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("why", ["padded", "non_contiguous"])
def test_a_padded_or_non_contiguous_batch_is_a_copy(engines, kind, why):
    eng, old = engines[kind]
    b = eng.buckets[-1]
    if why == "padded":
        images = _pixels(eng.spec.input_shape, b - 1, seed=8)
    else:
        images = _pixels(eng.spec.input_shape, 2 * b, seed=9)[::2]
        assert not images.flags.c_contiguous
    padded = np.zeros((b, *eng.spec.input_shape), np.uint8)
    padded[:len(images)] = images
    before = _count(eng, "view"), _count(eng, "copy")
    got = eng.predict(images)
    assert (_count(eng, "view"), _count(eng, "copy")) == (before[0], before[1] + 1)
    assert np.array_equal(got, np.asarray(old(eng._variables, padded))[:len(images)])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_batch_is_donated_and_the_weights_are_not(engines, kind, bucket):
    eng, _ = engines[kind]
    info = eng.donation_info(eng.bucket_for(bucket))
    assert info == {"variables": False, "images": True}


@pytest.mark.parametrize("kind", KINDS)
def test_warmup_compiles_exactly_what_serving_runs(engines, kind):
    import jax

    from kubernetes_deep_learning_tpu.utils.compilecache import CompileWatch

    eng, _ = engines[kind]
    registry = metrics_lib.Registry()
    watch = CompileWatch(registry)

    def compiles():
        for line in registry.render().splitlines():
            if line.startswith("kdlt_xla_compile_requests_total"):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError("no kdlt_xla_compile_requests_total")

    try:
        jax.jit(lambda x: x * 3 + len(kind))(np.ones(5, np.float32))
        assert compiles() >= 1, "the counter does not see compiles here"
        eng.warmup()
        warmed = compiles()
        for b in eng.buckets:
            eng.predict(_body_view(eng.spec.input_shape, b, seed=b)[0])
            if b > 1:
                eng.predict(_pixels(eng.spec.input_shape, b - 1, seed=b))
        assert compiles() == warmed
    finally:
        watch.close()


class _Spy:
    def __init__(self, jitted):
        self.jitted, self.calls = jitted, []

    def __call__(self, variables, batch):
        out = self.jitted(variables, batch)
        self.calls.append((batch, out))
        return out

    def __getattr__(self, name):
        return getattr(self.jitted, name)


@pytest.mark.parametrize("kind", KINDS)
def test_one_executable_a_batch(engines, kind, monkeypatch):
    """The un-wiring is inside the bucket's program, not a program of its
    own: the dispatch path makes one jitted call, on a host view of the
    caller's memory, and returns that call's result untouched."""
    eng, _ = engines[kind]
    spy = _Spy(eng._jitted)
    monkeypatch.setattr(eng, "_jitted", spy)
    b = eng.buckets[-1]
    view, _ = _body_view(eng.spec.input_shape, b, seed=11)
    handle, n = eng.predict_async(view)
    assert n == b and len(spy.calls) == 1
    batch, out = spy.calls[0]
    assert isinstance(batch, np.ndarray) and np.shares_memory(batch, view)
    shape = engine_lib.wire_form(view.shape)
    assert batch.shape == shape and batch.dtype == np.uint8
    assert handle is out
    # One entry computation, whose parameter is the wire form itself.
    text = spy.jitted.lower(eng._variables, batch).as_text()
    assert text.count("func.func public @main") == 1
    assert f"tensor<{shape[0]}x{shape[1]}x{shape[2]}xui8>" in text


def test_the_ingest_program_takes_the_same_form(monkeypatch, tmp_path_factory):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv(engine_lib.INGEST_DEVICE_RESIZE_ENV, "20x24")
    eng = InferenceEngine(
        _artifact(tmp_path_factory, "wire-ingest", "vit-tiny", (16, 16, 3)),
        buckets=(4,), use_exported=False, registry=metrics_lib.Registry())
    assert eng.ingest_source_shape == (20, 24, 3)
    staged = _body_view((20, 24, 3), 4, seed=12)[0]
    eng._ingest_fused()
    spy = _Spy(eng._ingest_jitted)
    monkeypatch.setattr(eng, "_ingest_jitted", spy)
    handle, n = eng.predict_ingest_async(staged)
    (batch, out), = spy.calls
    assert handle is out and np.shares_memory(batch, staged)
    assert batch.shape == engine_lib.wire_form(staged.shape) == (4, 20, 72)
    assert _count(eng, "view") == 1 and _count(eng, "copy") == 0
    # Against the program as it was: resize on the device, then the forward.
    inner = eng._live_forward(jnp.dtype(eng._compute_dtype))

    def old(variables, x):
        x = jax.image.resize(x.astype(jnp.float32), (4, 16, 16, 3), method="linear")
        return inner(variables, jnp.clip(jnp.round(x), 0.0, 255.0).astype(jnp.uint8))

    want = np.asarray(jax.jit(old)(eng._variables, np.asarray(staged)))
    assert np.array_equal(np.asarray(handle), want)
    # n < bucket at the staging resolution is padded, and counted so.
    eng.predict_ingest_async(staged[:3])
    assert _count(eng, "copy") == 1


def test_float32_pixels_still_share_the_live_jit(engines):
    eng, old = engines["live"]
    x = np.random.default_rng(2).normal(size=(1, *eng.spec.input_shape)).astype(np.float32)
    assert np.array_equal(eng.predict(x), np.asarray(old(eng._variables, x)))


def test_the_mesh_batch_is_still_proved_split_over_the_chips(engines):
    eng, _ = engines["mesh"]
    rows = eng._batch_rows_per_device()
    assert len(rows) == 4 and set(rows.values()) == {eng.max_batch // 4}
