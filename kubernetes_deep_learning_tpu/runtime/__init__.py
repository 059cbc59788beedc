from kubernetes_deep_learning_tpu.runtime.engine import (
    DispatcherClosed,
    DispatchStall,
    InferenceEngine,
    InFlightDispatcher,
    resolve_pipeline_depth,
)
from kubernetes_deep_learning_tpu.runtime.batcher import BatcherClosed, DynamicBatcher, QueueFull
from kubernetes_deep_learning_tpu.runtime.scheduler import (
    UnifiedScheduler,
    resolve_policy,
    resolve_weights,
)


def create_batcher(engine, impl: str = "auto", dispatcher=None, **kwargs):
    """Pick the batching implementation.

    "native" -> the C++ queue (native/batchqueue.cc); "python" -> the
    pure-Python DynamicBatcher; "auto" -> native when the compiled library
    is available AND the host has a core to overlap with, else Python.
    Both have identical policy and surface, including the multi-in-flight
    dispatch pipeline (``pipeline_depth`` kwarg / $KDLT_PIPELINE_DEPTH).
    ``dispatcher`` injects a shared InFlightDispatcher into the Python
    batcher (the native queue pipelines in its own dispatch loop instead,
    so the kwarg is dropped for it).

    Why the core check: the native batcher's multi-in-flight pipeline
    spreads dispatch across threads (dispatcher, device sync, C++
    completion), and on a single-core host the GIL convoys those handoffs
    -- the Python batcher's one-thread dispatch loop beat it at every
    simulated device latency (0.5-10 ms) on a stub device.  The pipeline
    needs a second core to pay off.
    """
    import os

    if impl not in ("auto", "native", "python"):
        raise ValueError(f"unknown batcher impl {impl!r}")
    if impl == "auto":
        # Affinity-aware count: os.cpu_count() reports HOST cores, so a
        # 1-CPU-pinned container on a 64-core node would wrongly pick the
        # native pipeline and hit the measured convoy.
        try:
            cores = len(os.sched_getaffinity(0))
        except (AttributeError, OSError):  # non-Linux
            cores = os.cpu_count() or 1
        if cores < 2:
            impl = "python"
    if impl in ("auto", "native"):
        try:
            from kubernetes_deep_learning_tpu.runtime.native_batcher import NativeBatcher

            return NativeBatcher(engine, **kwargs)
        except ImportError:
            if impl == "native":
                raise
    return DynamicBatcher(engine, dispatcher=dispatcher, **kwargs)


__all__ = [
    "BatcherClosed",
    "DispatchStall",
    "DispatcherClosed",
    "DynamicBatcher",
    "InferenceEngine",
    "InFlightDispatcher",
    "QueueFull",
    "UnifiedScheduler",
    "create_batcher",
    "resolve_pipeline_depth",
    "resolve_policy",
    "resolve_weights",
]
