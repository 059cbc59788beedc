"""Test env: force JAX onto a virtual 8-device CPU mesh.

Must run before jax is first imported anywhere, which pytest guarantees by
importing conftest first.  All multi-chip sharding tests run against these
virtual devices; real-TPU behavior is exercised by chip_smoke.py on the
chip, not by tests (SURVEY.md section 4: fake/CPU backend so the serving
path is testable without TPUs).

The suite does not turn the persistent compile cache on: in-process servers
(ModelServer(...)) leave it alone, only the entry points' main() functions
enable it, and the tests that drive those pass their own directory.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec  # noqa: E402


@pytest.fixture(scope="session")
def tiny_spec() -> ModelSpec:
    """A small Xception spec so CPU tests stay fast."""
    return register_spec(
        ModelSpec(
            name="tiny-xception",
            family="xception",
            input_shape=(96, 96, 3),
            labels=("a", "b", "c", "d"),
            preprocessing="tf",
            head_hidden=(16,),
            description="test-only small-input xception",
        )
    )
