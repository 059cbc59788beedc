"""kdlt-warm (export/warm.py) + warmup provenance accounting: the
zero-cold-start scale-up path.  All device-free: engines are stubbed (the
real cache-hit speedup is a slow-marked/bench concern; see PR 9's note in
tests/conftest.py on why tier-1 never enables a real persistent XLA
cache in-process)."""

from __future__ import annotations

import re

from kubernetes_deep_learning_tpu.export import artifact as art
from kubernetes_deep_learning_tpu.export import warm
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib


def _metric(text: str, name: str, **labels: str) -> float:
    for m in re.finditer(rf"^{re.escape(name)}(\{{[^}}]*\}})? (\S+)$", text, re.M):
        got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1) or ""))
        if all(got.get(k) == v for k, v in labels.items()):
            return float(m.group(2))
    raise AssertionError(f"no sample {name} with {labels} in:\n{text}")


def _save_model(root, name, version=1):
    spec = register_spec(
        ModelSpec(
            name=name,
            family="xception",  # never instantiated by the stub factory
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    art.save_artifact(
        art.version_dir(str(root), name, version), spec, {"params": {}}, None, {}
    )
    return spec


class _FakeEngine:
    """Records warmup calls and exposes a warm_report like the real engine."""

    calls: list = []

    def __init__(self, directory, buckets):
        self.directory = directory
        self.buckets = tuple(buckets)

    def warmup(self, workers=4):
        _FakeEngine.calls.append((self.directory, self.buckets, workers))
        self.warm_report = {
            "total_seconds": 0.01,
            "buckets": {
                int(b): {"seconds": 0.001, "source": "cache"}
                for b in self.buckets
            },
        }
        return 0.01


def test_warm_models_covers_every_registry_model(tmp_path, monkeypatch):
    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    root = tmp_path / "models"
    _save_model(root, "warm-a")
    _save_model(root, "warm-b", version=1)
    _save_model(root, "warm-b", version=2)  # only the LATEST version warms
    _FakeEngine.calls = []
    report = warm.warm_models(
        str(root),
        buckets=(1, 2),
        cache_dir=str(tmp_path / "cache"),
        engine_factory=_FakeEngine,
    )
    assert sorted(report["models"]) == ["warm-a", "warm-b"]
    assert report["models"]["warm-b"]["version"] == 2
    assert report["buckets"] == [1, 2]
    # The scan rule is the serving registry's: one engine per latest
    # version, full requested ladder each.
    assert len(_FakeEngine.calls) == 2
    assert all(buckets == (1, 2) for _, buckets, _ in _FakeEngine.calls)
    # The engine's own warm_report rides along (per-bucket provenance).
    assert report["models"]["warm-a"]["buckets"][1]["source"] == "cache"


def test_warm_models_fail_soft_warms_the_rest(tmp_path, monkeypatch):
    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    root = tmp_path / "models"
    _save_model(root, "aaa-bad")
    _save_model(root, "bbb-good")

    def factory(directory, buckets):
        if "aaa-bad" in directory:
            raise RuntimeError("compile exploded")
        return _FakeEngine(directory, buckets)

    report = warm.warm_models(
        str(root), buckets=(1,), cache_dir=str(tmp_path / "cache"),
        engine_factory=factory,
    )
    # The failure is reported, not raised -- and the REST of the fleet
    # still warmed (an image bake must not lose every model to one).
    assert report["models"]["aaa-bad"]["error"] == "compile exploded"
    assert "error" not in report["models"]["bbb-good"]
    assert report["models"]["bbb-good"]["seconds"] >= 0


def test_warm_main_rc_and_json(tmp_path, monkeypatch, capsys):
    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    root = tmp_path / "models"
    _save_model(root, "warm-cli")
    monkeypatch.setattr(warm, "_default_factory", _FakeEngine)
    _FakeEngine.calls = []
    rc = warm.main([
        "--models", str(root),
        "--buckets", "2,1,2",
        "--compile-cache-dir", str(tmp_path / "cache"),
        "--json",
    ])
    assert rc == 0
    import json

    report = json.loads(capsys.readouterr().out)
    assert report["buckets"] == [1, 2]  # deduped, sorted
    assert "warm-cli" in report["models"]
    # An empty root is rc=1 loudly: a warm pass that warmed NOTHING must
    # fail the image build rather than bake a cold cache silently.
    assert warm.main([
        "--models", str(tmp_path / "empty"),
        "--compile-cache-dir", str(tmp_path / "cache"),
    ]) == 1


def test_warm_main_rc_1_when_any_model_fails(tmp_path, monkeypatch):
    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    root = tmp_path / "models"
    _save_model(root, "warm-fail")

    def factory(directory, buckets):
        raise RuntimeError("boom")

    monkeypatch.setattr(warm, "_default_factory", factory)
    assert warm.main([
        "--models", str(root), "--compile-cache-dir", str(tmp_path / "c"),
    ]) == 1


# --- decode bucket ladder (generative lane) ----------------------------------


class _FakeDecodeEngine:
    """Device-free stand-in for runtime.decode.DecodeEngine in warm tests."""

    max_slots = 4

    def __init__(self, model="gen-default"):
        self.model = model

    def warmup(self):
        return {
            "model": self.model,
            "buckets": {"16": 0.01, "32": 0.01, "64": 0.01},
            "step_s": 0.01,
        }


def test_warm_learns_decode_grid_when_lane_enabled(tmp_path, monkeypatch):
    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    monkeypatch.delenv("KDLT_DECODE", raising=False)
    root = tmp_path / "models"
    _save_model(root, "warm-dec")
    report = warm.warm_models(
        str(root), buckets=(1,), cache_dir=str(tmp_path / "cache"),
        engine_factory=_FakeEngine, decode=True,
        decode_engine_factory=_FakeDecodeEngine,
    )
    # The learned ladder is the prompt-length x batch-slot grid: one
    # prefill program per bucket, one fixed-width step for every slot
    # composition.
    grid = report["decode"]["grid"]
    assert grid["prompt_buckets"] == [16, 32, 64]
    assert grid["slots"] == 4
    assert report["decode"]["model"] == "gen-default"
    assert report["decode"]["step_s"] >= 0


def test_warm_decode_follows_kdlt_decode_env(tmp_path, monkeypatch):
    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    root = tmp_path / "models"
    _save_model(root, "warm-nodec")
    # Lane off (default): the image ladder warms alone.
    monkeypatch.delenv("KDLT_DECODE", raising=False)
    report = warm.warm_models(
        str(root), buckets=(1,), cache_dir=str(tmp_path / "cache"),
        engine_factory=_FakeEngine, decode_engine_factory=_FakeDecodeEngine,
    )
    assert "decode" not in report
    # Lane on via the same env switch serving pods read.
    monkeypatch.setenv("KDLT_DECODE", "1")
    report = warm.warm_models(
        str(root), buckets=(1,), cache_dir=str(tmp_path / "cache"),
        engine_factory=_FakeEngine, decode_engine_factory=_FakeDecodeEngine,
    )
    assert report["decode"]["grid"]["slots"] == 4


def test_warm_decode_failure_is_fail_soft_and_reported(tmp_path, monkeypatch):
    monkeypatch.delenv("KDLT_COMPILE_CACHE_DIR", raising=False)
    root = tmp_path / "models"
    _save_model(root, "warm-decfail")

    def exploding_factory(model="gen-default"):
        raise RuntimeError("decode compile exploded")

    report = warm.warm_models(
        str(root), buckets=(1,), cache_dir=str(tmp_path / "cache"),
        engine_factory=_FakeEngine, decode=True,
        decode_engine_factory=exploding_factory,
    )
    # Image models still warmed; the decode failure is an error entry.
    assert "error" not in report["models"]["warm-decfail"]
    assert report["decode"]["error"] == "decode compile exploded"


# --- warmup provenance classification (runtime/engine.py) --------------------


def _provenance_probe(registry, bucket_seconds, cache_dir, monkeypatch):
    """Drive _record_warm_sources on a bare engine shell: the
    classification is pure accounting over (bucket timings, active cache
    dir, threshold) -- no device or artifact needed."""
    from kubernetes_deep_learning_tpu.runtime.engine import InferenceEngine
    from kubernetes_deep_learning_tpu.utils import compilecache

    monkeypatch.setattr(compilecache, "active_cache_dir", lambda: cache_dir)
    eng = object.__new__(InferenceEngine)
    eng.buckets = tuple(sorted(bucket_seconds))
    eng._warm_bucket_seconds = dict(bucket_seconds)
    eng._m_warm_source = metrics_lib.engine_warm_source_metrics(registry)
    eng.warm_report = {}
    eng._record_warm_sources(sum(bucket_seconds.values()))
    return eng


def test_warm_source_classifies_fast_buckets_as_cache_hits(monkeypatch):
    registry = metrics_lib.Registry()
    eng = _provenance_probe(
        registry,
        {1: 0.05, 2: 0.08, 4: 5.0},  # two disk reads, one live compile
        cache_dir="/var/cache/kdlt-xla",
        monkeypatch=monkeypatch,
    )
    text = registry.render()
    assert _metric(text, "kdlt_engine_warm_source", source="cache") == 2.0
    assert _metric(text, "kdlt_engine_warm_source", source="compile") == 1.0
    assert eng.warm_report["buckets"][1]["source"] == "cache"
    assert eng.warm_report["buckets"][4]["source"] == "compile"
    assert eng.warm_report["cache_dir"] == "/var/cache/kdlt-xla"


def test_warm_source_without_cache_is_always_compile(monkeypatch):
    # No active cache: even a fast warm cannot claim a cache hit (the
    # proof metric must never flatter a cold image).
    registry = metrics_lib.Registry()
    eng = _provenance_probe(
        registry, {1: 0.01}, cache_dir=None, monkeypatch=monkeypatch
    )
    text = registry.render()
    assert _metric(text, "kdlt_engine_warm_source", source="compile") == 1.0
    assert _metric(text, "kdlt_engine_warm_source", source="cache") == 0.0
    assert eng.warm_report["buckets"][1]["source"] == "compile"


def test_warm_source_threshold_env_override(monkeypatch):
    monkeypatch.setenv("KDLT_WARM_CACHE_HIT_S", "10.0")
    registry = metrics_lib.Registry()
    eng = _provenance_probe(
        registry, {1: 5.0}, cache_dir="/c", monkeypatch=monkeypatch
    )
    assert eng.warm_report["threshold_s"] == 10.0
    assert eng.warm_report["buckets"][1]["source"] == "cache"
