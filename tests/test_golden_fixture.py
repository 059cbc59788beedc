"""Committed golden regression: the importer+engine numeric chain, pinned.

The reference's correctness baseline is a set of expected logits for a known
image against the real trained artifact (reference guide.md:623-625), which
this environment cannot fetch (no egress).  This fixture pins the SAME
numeric chain -- Keras-layout .h5 -> keras_import -> exporter -> artifact ->
InferenceEngine predict -- against logits recorded once and committed
(tests/golden/xception_synthetic.json), so any numeric regression in the
importer, exporter, or engine fails CI even without the real weights
(VERDICT r1 item 5).  ``kdlt-verify-golden`` remains the check for the real
artifact where it is available.

Weights and inputs are generated with numpy's default_rng, whose bit stream
is stable across numpy versions by policy (NEP 19) -- no jax PRNG in the
chain.  Comparison tolerance absorbs XLA CPU codegen variation (fused f32
reductions differ across instruction sets), NOT algorithmic drift.

Regenerate after an INTENTIONAL numeric change:
    python tests/test_golden_fixture.py --regenerate
"""

from __future__ import annotations

import json
import os

import numpy as np

import pytest

from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden", "xception_synthetic.json")

SPEC = ModelSpec(
    name="golden-xception",
    family="xception",
    input_shape=(96, 96, 3),
    labels=("dress", "hat", "pants", "shirt"),
    preprocessing="tf",
    resize_filter="nearest",
    head_hidden=(16,),
)


def _deterministic_variables(spec: ModelSpec):
    """Variables in the module's exact tree, filled by numpy rng in sorted
    path order (independent of jax PRNG internals)."""
    import jax

    from kubernetes_deep_learning_tpu.models import init_variables

    shapes = jax.eval_shape(lambda: init_variables(spec, seed=0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    flat = sorted(flat, key=lambda kv: jax.tree_util.keystr(kv[0]))
    rng = np.random.default_rng(20260730)
    leaves = {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if key.endswith("['var']"):
            arr = rng.uniform(0.5, 1.5, leaf.shape)
        elif key.endswith("['scale']"):
            arr = rng.uniform(0.8, 1.2, leaf.shape)
        else:
            arr = rng.normal(0.0, 0.08, leaf.shape)
        leaves[key] = arr.astype(np.float32)
    # Rebuild in original structure order.
    orig_flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    rebuilt = [leaves[jax.tree_util.keystr(p)] for p, _ in orig_flat]
    return jax.tree_util.tree_unflatten(treedef, rebuilt)


def _golden_inputs(spec: ModelSpec) -> np.ndarray:
    rng = np.random.default_rng(7301)
    return rng.integers(0, 256, size=(2, *spec.input_shape), dtype=np.uint8)


def _compute_chain_logits(tmp_dir: str) -> np.ndarray:
    """The full chain: variables -> keras .h5 -> import -> export -> engine."""
    from test_keras_import import _flax_to_keras_h5

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.export import export_model
    from kubernetes_deep_learning_tpu.models.keras_import import load_keras_h5
    from kubernetes_deep_learning_tpu.runtime import InferenceEngine

    spec = register_spec(SPEC)
    variables = _deterministic_variables(spec)
    h5_path = os.path.join(tmp_dir, "golden.h5")
    _flax_to_keras_h5(h5_path, variables)

    imported = load_keras_h5(spec, h5_path)
    root = os.path.join(tmp_dir, "models")
    # float32 end to end: the golden chain pins algorithmic numerics, and
    # bf16 rounding would drown the signal a regression produces.
    export_model(spec, imported, root, dtype=np.float32)
    engine = InferenceEngine(
        art.load_artifact(art.version_dir(root, spec.name, 1)), buckets=(2,)
    )
    engine.warmup()
    return np.asarray(engine.predict(_golden_inputs(spec)), np.float32)


def test_golden_chain_matches_committed_logits(tmp_path):
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    want = np.asarray(golden["logits"], np.float32)
    got = _compute_chain_logits(str(tmp_path))
    assert got.shape == tuple(golden["shape"])
    # rtol absorbs XLA CPU fused-reduction variation across hosts; a real
    # importer/exporter/engine regression shows up orders of magnitude above.
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


if __name__ == "__main__":
    import argparse
    import sys
    import tempfile

    sys.path.insert(0, os.path.dirname(__file__))
    # The committed fixture is a CPU artifact: regenerate it on the CPU
    # backend whatever the environment exports.
    from kubernetes_deep_learning_tpu.utils.platform import force_platform

    force_platform("cpu")

    p = argparse.ArgumentParser()
    p.add_argument("--regenerate", action="store_true")
    if not p.parse_args().regenerate:
        p.error("run with --regenerate to rewrite the committed fixture")
    with tempfile.TemporaryDirectory() as td:
        logits = _compute_chain_logits(td)
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump(
            {
                "comment": "expected f32 logits of the synthetic golden chain; "
                "see test_golden_fixture.py",
                "shape": list(logits.shape),
                "logits": [[float(v) for v in row] for row in logits],
            },
            f,
            indent=1,
        )
    print(f"wrote {GOLDEN_PATH}\n{logits}")
