"""The compile-cache contract (utils/compilecache.py): where the cache
lives is decided from outside the program, a cache that cannot be used is an
error, and the keys of the fused programs repeat from boot to boot.
"""

from __future__ import annotations

import os
import re

import pytest


@pytest.fixture
def restore_jax_cache_config(monkeypatch):
    """enable_compile_cache edits process-global jax config; start from a
    clean environment and put the config back."""
    import jax
    from jax._src import compilation_cache as jax_cc

    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    tracebacks = jax.config.jax_include_full_tracebacks_in_locations
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_include_full_tracebacks_in_locations", tracebacks)
    jax_cc.reset_cache()


def test_compile_cache_empty_env_is_unset_not_disable(monkeypatch):
    from kubernetes_deep_learning_tpu.utils.compilecache import (
        DEFAULT_CACHE_DIR,
        resolve_cache_dir,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", "")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/jax-cc")
    # "" was once a disable sentinel and suppressed the fallback.
    assert resolve_cache_dir() == "/tmp/jax-cc"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # Nothing set: the one fixed path, <checkout>/.jax_cache -- never a
    # temp dir, a pid or a clock.
    assert resolve_cache_dir() == DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    # The explicit sentinels still disable everything downstream...
    for sentinel in ("off", "none", "0", " OFF "):
        monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", sentinel)
        assert resolve_cache_dir() is None
    # ...but never an explicit programmatic argument.
    assert resolve_cache_dir("/tmp/explicit") == "/tmp/explicit"
    # JAX's own variable, where set, beats ours, the flag and the argument
    # (the flag IS the argument: main() passes --compile-cache-dir through).
    monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", "/tmp/kdlt-cc")
    assert resolve_cache_dir() == "/tmp/kdlt-cc"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/jax-cc")
    assert resolve_cache_dir() == "/tmp/jax-cc"
    assert resolve_cache_dir("/tmp/explicit") == "/tmp/jax-cc"
    monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", "off")
    assert resolve_cache_dir() == "/tmp/jax-cc"


def test_enable_compile_cache_follows_the_contract_or_raises(
    tmp_path, monkeypatch, restore_jax_cache_config
):
    import jax

    from kubernetes_deep_learning_tpu.export import warm
    from kubernetes_deep_learning_tpu.utils.compilecache import (
        active_cache_dir,
        enable_compile_cache,
    )

    jax_dir, flag_dir = str(tmp_path / "jax"), str(tmp_path / "flag")
    monkeypatch.setenv("KDLT_COMPILE_CACHE_DIR", str(tmp_path / "kdlt"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", jax_dir)
    # kdlt-warm's --compile-cache-dir loses to JAX_COMPILATION_CACHE_DIR,
    # and the live jax config never holds another value.
    (tmp_path / "models").mkdir()
    warm.main(["--models", str(tmp_path / "models"), "--compile-cache-dir", flag_dir])
    assert jax.config.jax_compilation_cache_dir == jax_dir == active_cache_dir()
    assert os.path.isdir(jax_dir) and not os.path.exists(flag_dir)
    # A directory that cannot be created is an error, not a silent cold run.
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    with pytest.raises(OSError):
        enable_compile_cache(str(blocker / "cache"))


def test_fused_kernel_bytes_do_not_depend_on_trace_order(
    tmp_path, monkeypatch, restore_jax_cache_config
):
    """A Pallas kernel is serialized INTO the program with its MLIR
    locations, out of reach of jax's own location stripping, so whatever a
    location holds is part of the cache key.  With full tracebacks in the
    locations the batch-16 program's bytes depend on whether the batch-32
    program (which shares its kernels) was traced first -- the race between
    warm-up threads that made two bucket programs miss the cache on every
    second boot on the v5e.  enable_compile_cache turns them off; the bytes
    must then be the same in either order.  (Cross-lowered for the TPU: no
    device needed.)"""
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.models import build_forward, init_variables
    from kubernetes_deep_learning_tpu.modelspec import get_spec
    from kubernetes_deep_learning_tpu.utils.compilecache import enable_compile_cache

    enable_compile_cache(str(tmp_path / "cache"))
    spec = get_spec("clothing-model-96")
    variables = jax.eval_shape(lambda: init_variables(spec, seed=0))

    def kernel_bodies(order):
        jax.clear_caches()  # each order starts like a fresh process
        fwd = jax.jit(build_forward(spec, dtype=jnp.bfloat16, fast=True))
        out = {}
        for batch in order:
            x = jax.ShapeDtypeStruct((batch, *spec.input_shape), jnp.uint8)
            text = fwd.trace(variables, x).lower(lowering_platforms=("tpu",)).as_text()
            out[batch] = re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', text)
        return out

    first, second = kernel_bodies((16, 32)), kernel_bodies((32, 16))
    assert len(first[16]) == 10 and len(first[32]) == 20  # the fused kernels
    assert first[16] == second[16] and first[32] == second[32]
