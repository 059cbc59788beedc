"""Persistent XLA compilation-cache wiring (shared by bench + serving).

Every fresh process pays tens of seconds of XLA compile per bucket program
on the v5e, which makes a serving pod restart -- and every run of a
measurement tool that starts from nothing -- cost minutes of warmup.  JAX
ships a persistent compilation cache keyed on the compiled HLO + compile
options; pointing it at a directory that outlives the process makes every
re-compile of an already-seen program a disk read instead.

Where the cache lives is decided from OUTSIDE the program, in this order:

1. ``JAX_COMPILATION_CACHE_DIR`` -- JAX's own variable.  When set it wins
   over everything below, and this module never points jax anywhere else.
2. an explicit ``cache_dir`` argument (``--compile-cache-dir``);
3. ``KDLT_COMPILE_CACHE_DIR`` (the deploy manifests' volume mount);
4. the one fixed default ``<checkout>/.jax_cache``.

The directory is part of what makes a cache hit repeatable, so it is never
built from a temp dir, a pid or a clock.  The cache is content-addressed
and concurrency-safe for our use: parallel writers of the same key race
benignly (last rename wins, identical bytes), so bench subprocesses and
serving warmup threads can share one directory.
"""

from __future__ import annotations

import os

ENV_VAR = "KDLT_COMPILE_CACHE_DIR"
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: the parent of the package directory (listed in
# .gitignore).  Images that install the package set one of the env vars.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_OFF = ("off", "none", "0")


def resolve_cache_dir(cache_dir: str | None = None) -> str | None:
    """Pick the cache directory (order in the module docstring).

    ``KDLT_COMPILE_CACHE_DIR=off`` (or ``none``/``0``) disables the cache
    unless ``JAX_COMPILATION_CACHE_DIR`` or an explicit ``cache_dir`` names
    one.  An EMPTY ``KDLT_COMPILE_CACHE_DIR`` is treated as UNSET, not as a
    disable sentinel: k8s manifests commonly template the var to "" to
    mean "no override".
    """
    jax_env = os.environ.get(JAX_ENV_VAR, "").strip()
    if jax_env:
        return jax_env
    if cache_dir:
        return cache_dir
    env = os.environ.get(ENV_VAR, "").strip()
    if env.lower() in _OFF:
        return None
    return env or DEFAULT_CACHE_DIR


def active_cache_dir() -> str | None:
    """The cache directory the CURRENT process compiles against, or None
    when no cache is on.  Read-only: never flips the cache on."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def enable_compile_cache(cache_dir: str | None = None) -> str | None:
    """Enable JAX's persistent compilation cache in THIS process.

    Returns the cache directory, or None only when it was switched off
    (``KDLT_COMPILE_CACHE_DIR=off``).  A directory that cannot be created
    or written raises OSError: a process that was given a cache and
    silently compiles cold spends its start-up budget without saying so.
    """
    path = resolve_cache_dir(cache_dir)
    if not path:
        return None
    os.makedirs(path, exist_ok=True)
    if not os.access(path, os.W_OK | os.X_OK):
        raise PermissionError(f"compile cache directory {path!r} is not writable")
    import jax

    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax latches an is-the-cache-used verdict per process on the FIRST
        # compile; a process that compiled anything before this call (bench
        # preamble, an embedding app) would keep that stale "no" forever
        # and silently never read or write the cache.  Un-latch it so
        # enabling mid-process takes effect from the next compile on.
        from jax._src import compilation_cache as _jax_cc

        _jax_cc.reset_cache()
    # Default thresholds skip "cheap" compiles; a boot is many small
    # compiles next to the big bucket programs, and a second boot must find
    # every one of them (a program timed just under a threshold on one boot
    # and just over it on the next would be written late), so cache all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # Keys must repeat from one boot to the next.  jax strips MLIR locations
    # from a program before hashing it, but a Pallas kernel travels inside
    # the program as serialized bytecode WITH its locations, and a full
    # Python traceback in a location depends on the call path (chunked vs
    # monolithic forward, pool thread vs main thread) and on which warm-up
    # thread traced a shared inner function first.  On the v5e that made two
    # of the three fused bucket programs miss the cache on every second
    # boot.  The innermost frame alone (the kernel's own line) is the same
    # on every path.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return path


class CompileWatch:
    """Count this process's XLA compile requests into a metrics registry
    (utils.metrics.compile_event_counters), from jax.monitoring's events.

    jax's listeners are process-global; ``close()`` unregisters this one,
    so a process that builds several servers (tests) does not accumulate
    them.
    """

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"
    _CACHE_WRITE = "/jax/compilation_cache/cache_misses"  # fires on the write

    def __init__(self, registry):
        import jax.monitoring

        from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

        self._m = metrics_lib.compile_event_counters(registry)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == self._BACKEND_COMPILE:
            self._m["requests"].inc()

    def _on_event(self, event: str, **kwargs) -> None:
        if event == self._CACHE_HIT:
            self._m["cache_hits"].inc()
        elif event == self._CACHE_WRITE:
            self._m["cache_writes"].inc()

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
