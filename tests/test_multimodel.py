"""Multi-model serving end to end: two models in ONE model-server process
(shared scheduler + dispatcher), gateway routing by path and header, the
client's --model surface, per-model metrics -- and the acceptance bar:
logits from concurrent two-model serving are BIT-IDENTICAL to single-model
serving of each.  Real engines on the CPU backend (tiny specs)."""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import numpy as np
import pytest
import requests

from kubernetes_deep_learning_tpu.export import export_model
from kubernetes_deep_learning_tpu.models import init_variables
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.serving import protocol
from kubernetes_deep_learning_tpu.serving.gateway import Gateway
from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

SHAPE = (64, 64, 3)  # tier-1 budget: the smallest shape xception builds at


def _spec(name: str, labels) -> ModelSpec:
    return register_spec(ModelSpec(
        name=name, family="xception", input_shape=SHAPE,
        labels=tuple(labels), preprocessing="tf", resize_filter="nearest",
    ))


@pytest.fixture(scope="module")
def duo(tmp_path_factory):
    """Two exported models under ONE root + the server + gateway stack."""
    spec_a = _spec("mm-alpha", ("dress", "hat", "pants"))
    spec_b = _spec("mm-beta", ("cat", "dog"))
    root = tmp_path_factory.mktemp("models")
    vars_a = init_variables(spec_a, seed=11)
    vars_b = init_variables(spec_b, seed=22)
    export_model(spec_a, vars_a, str(root), dtype=np.float32)
    export_model(spec_b, vars_b, str(root), dtype=np.float32)

    server = ModelServer(str(root), port=0, buckets=(1, 2), max_delay_ms=1.0)
    server.warmup()
    server.start()
    gateway = Gateway(
        serving_host=f"localhost:{server.port}", model=spec_a.name, port=0
    )
    gateway.start()
    yield spec_a, spec_b, root, server, gateway
    gateway.shutdown()
    server.shutdown()


def _predict_direct(server, name, imgs):
    r = requests.post(
        f"http://localhost:{server.port}/v1/models/{name}:predict",
        data=protocol.encode_predict_request(imgs),
        headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE},
        timeout=30,
    )
    r.raise_for_status()
    return protocol.decode_predict_response(
        r.content, r.headers.get("Content-Type", "")
    )


def test_two_models_bit_identical_to_single_model_serving(duo):
    """The acceptance criterion: each model served CONCURRENTLY from the
    two-model process returns logits bit-identical to a single-model
    server of the same artifact (same buckets, same padding, same
    programs -- the scheduler changes WHO runs next, never WHAT runs)."""
    spec_a, spec_b, root, server, _ = duo
    rng = np.random.default_rng(7)
    imgs = rng.integers(0, 256, size=(2, *SHAPE), dtype=np.uint8)

    # Concurrent requests against both models of the shared process.
    results: dict = {}

    def hit(name):
        results[name] = _predict_direct(server, name, imgs)

    threads = [
        threading.Thread(target=hit, args=(s.name,))
        for s in (spec_a, spec_b)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)

    # Single-model references: the same artifact served alone, through the
    # same execution path (InferenceEngine, same buckets => same compiled
    # programs + padding).  Engine-level rather than a second HTTP server:
    # the wire is already covered above, and the claim under test is about
    # the EXECUTION, which is identical from ServedModel down.
    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.runtime import InferenceEngine

    for spec in (spec_a, spec_b):
        # buckets=(2,): the batch-2 request runs the bucket-2 program on
        # both sides, and that program is identical whether or not bucket
        # 1 also exists -- one compile per reference instead of two.
        solo = InferenceEngine(
            art.load_artifact(
                art.version_dir(str(root), spec.name, 1)
            ),
            buckets=(2,),
        )
        solo.warmup()
        want = solo.predict(imgs)
        got, got_labels = results[spec.name]
        assert got_labels == list(spec.labels)
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)


def test_registry_status_lists_both_models(duo):
    spec_a, spec_b, _, server, _ = duo
    base = f"http://localhost:{server.port}"
    models = requests.get(f"{base}/v1/models", timeout=5).json()
    assert set(models) >= {spec_a.name, spec_b.name}
    for name in (spec_a.name, spec_b.name):
        st = models[name]
        assert st["ready"] is True and st["version"] == 1
        assert st["artifact_hash"]  # the registry's identity key
    # Per-model status endpoint agrees.
    st = requests.get(
        f"{base}/v1/models/{spec_b.name}:status", timeout=5
    ).json()
    assert st == models[spec_b.name]
    assert requests.get(
        f"{base}/v1/models/nope:status", timeout=5
    ).status_code == 404


def test_gateway_routes_by_path_and_header(duo, tmp_path):
    spec_a, spec_b, _, _, gateway = duo
    # Local image host.
    from functools import partial
    from http.server import SimpleHTTPRequestHandler

    rng = np.random.default_rng(3)
    pixels = rng.integers(0, 256, size=(64, 48, 3), dtype=np.uint8)
    from PIL import Image

    Image.fromarray(pixels).save(tmp_path / "img.png")
    httpd = HTTPServer(
        ("127.0.0.1", 0),
        partial(SimpleHTTPRequestHandler, directory=str(tmp_path)),
    )
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}/img.png"
    base = f"http://localhost:{gateway.port}"
    try:
        # Bare /predict -> the default model's label set (back-compat).
        r = requests.post(f"{base}/predict", json={"url": url}, timeout=30)
        assert r.status_code == 200 and set(r.json()) == set(spec_a.labels)
        # Path routing.
        r = requests.post(
            f"{base}/predict/{spec_b.name}", json={"url": url}, timeout=30
        )
        assert r.status_code == 200 and set(r.json()) == set(spec_b.labels)
        # Header routing.
        r = requests.post(
            f"{base}/predict", json={"url": url},
            headers={protocol.MODEL_HEADER: spec_b.name}, timeout=30,
        )
        assert r.status_code == 200 and set(r.json()) == set(spec_b.labels)
        # Unknown model: a clean 404, not a 502 outage costume.
        r = requests.post(
            f"{base}/predict/not-a-model", json={"url": url}, timeout=30
        )
        assert r.status_code == 404
        # Malformed model name: rejected before any upstream is dialed.
        r = requests.post(
            f"{base}/predict/bad%2Fname", json={"url": url}, timeout=30
        )
        assert r.status_code == 404
        # The batch extension routes too.
        r = requests.post(
            f"{base}/predict/{spec_b.name}", json={"urls": [url, url]},
            timeout=30,
        )
        preds = r.json()["predictions"]
        assert len(preds) == 2
        assert all(set(p) == set(spec_b.labels) for p in preds)
    finally:
        httpd.shutdown()


def test_per_model_metrics_on_both_tiers(duo):
    spec_a, spec_b, _, server, gateway = duo
    server_page = requests.get(
        f"http://localhost:{server.port}/metrics", timeout=5
    ).text
    # Bounded `model` label on request counts + pipeline stages + the
    # scheduler lane series (kdlt_batcher_* kept as the invariant name).
    for name in (spec_a.name, spec_b.name):
        assert f'kdlt_model_requests_total{{model="{name}"}}' in server_page
        assert f'model="{name}"' in server_page
    assert 'kdlt_admission_requests_total{tier="model-server",model=' in server_page
    assert "kdlt_sched_dispatch_total" in server_page
    assert "kdlt_pipeline_execute_seconds_count" in server_page
    gw_page = requests.get(
        f"http://localhost:{gateway.port}/metrics", timeout=5
    ).text
    assert "kdlt_model_requests_total" in gw_page


# --- the client's --model surface (satellite regression) -------------------


class _CaptureHandler(BaseHTTPRequestHandler):
    seen: list = []

    def log_message(self, fmt, *args):
        pass

    def do_POST(self):
        type(self).seen.append(
            (self.path, self.headers.get(protocol.MODEL_HEADER))
        )
        body = json.dumps({"ok": 1.0}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_client_default_model_wire_shape_unchanged():
    """kdlt-client without --model must keep the exact legacy wire shape:
    bare /predict, NO X-Kdlt-Model header (the satellite's regression
    bar); --model sets both the path segment and the header."""
    from kubernetes_deep_learning_tpu.serving.client import predict_url

    _CaptureHandler.seen = []
    httpd = HTTPServer(("127.0.0.1", 0), _CaptureHandler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        assert predict_url(base, "http://example/img.png") == {"ok": 1.0}
        assert predict_url(
            base, "http://example/img.png", model="vit"
        ) == {"ok": 1.0}
    finally:
        httpd.shutdown()
    assert _CaptureHandler.seen[0] == ("/predict", None)
    assert _CaptureHandler.seen[1] == ("/predict/vit", "vit")


def test_client_cli_passes_model(monkeypatch, capsys):
    from kubernetes_deep_learning_tpu.serving import client as client_mod

    calls = {}

    def fake_predict_url(gateway, image_url, retries=2, deadline_ms=None,
                         stats=None, model=None, cache_bust=None,
                         priority=None):
        calls.update(model=model)
        return {"x": 1.0}

    monkeypatch.setattr(client_mod, "predict_url", fake_predict_url)
    assert client_mod.main(["--model", "mm-beta"]) == 0
    assert calls["model"] == "mm-beta"
    assert client_mod.main([]) == 0
    assert calls["model"] is None
