"""kdlt-warm: AOT-compile every registry model's bucket ladder into the
persistent compile cache (zero-cold-start scale-up).

A bucket's live XLA compile takes tens of seconds on the chip (a cell's
first set-up reads 71-83 s against 34-40 s warm: PERF.md), which makes a
freshly scaled model-server pod dead weight exactly when the HPA added it
because load spiked.  The persistent compile cache (utils.compilecache,
GUIDE §10b) already makes a RE-compile a disk read; what was missing is
anything that fills the cache BEFORE the first pod boots.  This CLI is
that filler, with two call sites:

- **image build**: ``RUN kdlt-warm --models /models --compile-cache-dir
  /var/cache/kdlt-xla`` in the serving Dockerfile bakes a hot cache into
  the image layer, so every pod the image ever starts warms from disk;
- **pod init**: ``kdlt-model-server --aot-warm`` (or ``KDLT_AOT_WARM=1``
  on an init container sharing the cache volume) runs the same pass
  against a persistent volume before serving starts.

Either way, a scaled pod's ``InferenceEngine.warmup()`` is cache-hits
only -- ``kdlt_engine_warm_source{source="compile"} == 0`` is the proof
-- while readiness stays gated on all-buckets-warm exactly as before.

The scan rule is shared with the serving registry
(serving.registry.iter_latest_versions): the set of models pre-warmed is
exactly the set a booted server would load.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from kubernetes_deep_learning_tpu.utils import compilecache


def warm_decode(engine_factory=None, model_root: str | None = None) -> dict:
    """Warm the generative lane's decode ladder; returns its report dict.

    The engine is built as a pod builds it: the lane's sizes and its
    prefill ladder from the same environment ($KDLT_DECODE_SLOTS, ...,
    $KDLT_DECODE_PROMPT_BUCKETS), its decoder from the artifact that
    $KDLT_DECODE_MODEL names under ``model_root`` (the toy without one).

    The decode lane has its own compile grid, disjoint from the image
    bucket ladder: one prefill program per prompt-length bucket up to the
    lane's chunk size (and, where the ladder admits longer prompts, one
    more each for a chunk that follows others), plus the
    single fixed-width step program that serves every batch-slot
    composition (continuous batching admits into a fixed [S]-slot step,
    so slot count never recompiles -- the grid is buckets x slots wide
    but only buckets + 1 programs deep).  A scaled pod started with
    KDLT_DECODE=1 compiles exactly these programs in
    GenerateLane.warmup(); running them here lands them in the same
    persistent cache the pod reads.
    """
    from kubernetes_deep_learning_tpu.runtime import decode as decode_lib
    from kubernetes_deep_learning_tpu.serving.generate import (
        DECODE_MODEL_ENV,
        DEFAULT_DECODE_MODEL,
    )

    model = os.environ.get(DECODE_MODEL_ENV) or DEFAULT_DECODE_MODEL
    if engine_factory is not None:
        engine = engine_factory(model=model)
    else:
        engine = decode_lib.DecodeEngine(
            model=model, decoder=decode_lib.load_decoder(model_root, model))
    entry = dict(engine.warmup())
    # The learned grid: every (prompt bucket, batch slots) cell the two
    # program families above cover.  Asserted by tests/test_warm.py.
    entry["grid"] = {
        "prompt_buckets": [int(b) for b in entry.get("buckets", {})],
        "slots": int(getattr(engine, "max_slots", 0)),
    }
    return entry


def warm_models(
    model_root: str,
    buckets=None,
    cache_dir: str | None = None,
    workers: int = 4,
    engine_factory=None,
    decode: bool | None = None,
    decode_engine_factory=None,
) -> dict:
    """Warm every model under ``model_root``; returns the report dict.

    One engine per model's latest version, full bucket ladder (the
    DEFAULT_BUCKETS every serving pod compiles), warmup() per engine --
    the compiled programs land in the persistent cache as a side effect.
    ``engine_factory`` swaps the engine class (tests); the default is the
    serving InferenceEngine, so the programs cached here are bit-the-same
    programs a pod will look up.
    """
    from kubernetes_deep_learning_tpu.runtime import engine as engine_lib
    from kubernetes_deep_learning_tpu.serving.registry import (
        iter_latest_versions,
    )

    resolved = compilecache.enable_compile_cache(cache_dir)
    factory = engine_factory or _default_factory
    report: dict = {
        "cache_dir": resolved,
        "buckets": list(buckets or engine_lib.DEFAULT_BUCKETS),
        "models": {},
    }
    for name, version, directory in iter_latest_versions(model_root):
        t0 = time.perf_counter()
        try:
            engine = factory(
                directory, buckets or engine_lib.DEFAULT_BUCKETS
            )
            engine.warmup(workers=workers)
        except Exception as e:  # noqa: BLE001 - warm the REST of the fleet
            report["models"][name] = {
                "version": version, "error": str(e),
            }
            print(
                f"kdlt-warm: {name} v{version} FAILED: {e}", file=sys.stderr
            )
            continue
        entry = {
            "version": version,
            "seconds": round(time.perf_counter() - t0, 3),
            **getattr(engine, "warm_report", {}),
        }
        report["models"][name] = entry
        srcs = [
            b.get("source") for b in entry.get("buckets", {}).values()
        ] if isinstance(entry.get("buckets"), dict) else []
        print(
            f"kdlt-warm: {name} v{version}: {entry['seconds']}s "
            f"({srcs.count('cache')} cached / {srcs.count('compile')} "
            "compiled buckets)",
            file=sys.stderr,
        )
    # The decode ladder rides the same pass when the generative lane is
    # on (--decode, or KDLT_DECODE=1 -- the same switch the pods read),
    # so an image baked with the lane enabled boots with prefill + step
    # programs already cached.
    from kubernetes_deep_learning_tpu.serving.generate import decode_enabled

    if decode_enabled(decode):
        t0 = time.perf_counter()
        try:
            report["decode"] = warm_decode(decode_engine_factory, model_root)
        except Exception as e:  # noqa: BLE001 - image models still warmed
            report["decode"] = {"error": str(e)}
            print(f"kdlt-warm: decode ladder FAILED: {e}", file=sys.stderr)
        else:
            grid = report["decode"]["grid"]
            print(
                f"kdlt-warm: decode {report['decode'].get('model')}: "
                f"{round(time.perf_counter() - t0, 3)}s (prefill buckets "
                f"{grid['prompt_buckets']} x {grid['slots']} slots + step)",
                file=sys.stderr,
            )
    return report


def _default_factory(directory: str, buckets):
    from kubernetes_deep_learning_tpu.export.artifact import load_artifact
    from kubernetes_deep_learning_tpu.runtime.engine import InferenceEngine

    return InferenceEngine(load_artifact(directory), buckets=buckets)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="AOT-compile every registry model's bucket ladder into "
        "the persistent compile cache (zero-cold-start scale-up; run at "
        "image build or pod init)"
    )
    p.add_argument(
        "--models",
        default=os.environ.get("KDLT_MODEL_ROOT", "/models"),
        help="artifact root (the model server's --models; default "
        "$KDLT_MODEL_ROOT or /models)",
    )
    p.add_argument(
        "--buckets",
        default=None,
        help="comma-separated bucket ladder override (default: the "
        "serving DEFAULT_BUCKETS, which is what pods compile)",
    )
    p.add_argument(
        "--compile-cache-dir",
        default=None,
        help="persistent compile cache directory ($JAX_COMPILATION_CACHE_DIR "
        "wins over this flag; default $KDLT_COMPILE_CACHE_DIR or "
        f"{compilecache.DEFAULT_CACHE_DIR})",
    )
    p.add_argument(
        "--workers", type=int, default=4,
        help="concurrent bucket compiles per model",
    )
    p.add_argument(
        "--platform",
        default=None,
        help="force a JAX platform (e.g. cpu; default $KDLT_PLATFORM) -- an "
        "image BUILD host usually has no TPU; note cache keys include "
        "the target platform, so warming on cpu only serves cpu pods",
    )
    p.add_argument(
        "--decode", action="store_true", default=None,
        help="also warm the generative lane's decode ladder (prompt-length "
        "buckets x batch slots; default: follows KDLT_DECODE, the same "
        "switch serving pods read)",
    )
    p.add_argument(
        "--json", action="store_true",
        help="print the full warm report as JSON on stdout",
    )
    args = p.parse_args(argv)
    from kubernetes_deep_learning_tpu.utils.platform import force_platform

    force_platform(args.platform)
    buckets = None
    if args.buckets:
        buckets = tuple(
            sorted({int(b) for b in args.buckets.split(",") if b.strip()})
        )
    report = warm_models(
        args.models,
        buckets=buckets,
        cache_dir=args.compile_cache_dir,
        workers=args.workers,
        decode=args.decode,
    )
    if args.json:
        print(json.dumps(report, indent=2))
    failed = [
        n for n, m in report["models"].items() if "error" in m
    ]
    if "error" in (report.get("decode") or {}):
        failed.append("decode")
    if not report["models"]:
        print(f"kdlt-warm: no models under {args.models}", file=sys.stderr)
        return 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
