"""The generative lane's transport half (serving/generate.py): request
handling, SSE framing, per-token SLO closure, and the /debug/slo decode
section.  The lane runs its real engine + scheduler (tiny model, CPU);
one module-scoped lane serves every test.  The full HTTP path --
model server ``:generate`` route, gateway ``/generate`` relay, chunked
streaming, kdlt-client -- is covered by the slow-marked end-to-end test
at the bottom.
"""

from __future__ import annotations

import json
import threading

import pytest

from kubernetes_deep_learning_tpu.serving import generate as generate_lib
from kubernetes_deep_learning_tpu.serving import protocol
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib


class _SloSpy:
    def __init__(self):
        self.calls = []

    def record(self, model, status, dt, deadline_exceeded=False):
        self.calls.append((model, status, deadline_exceeded))


@pytest.fixture(scope="module")
def slo_spy():
    return _SloSpy()


@pytest.fixture(scope="module")
def registry():
    return metrics_lib.Registry()


@pytest.fixture(scope="module")
def lane(slo_spy, registry):
    lane = generate_lib.GenerateLane(
        "gen-test", registry=registry, slo=slo_spy,
        engine_kwargs=dict(max_slots=2, page_size=8, max_pages_per_seq=4),
    )
    yield lane
    lane.close()


def test_decode_enabled_reads_the_env_switch(monkeypatch):
    monkeypatch.delenv("KDLT_DECODE", raising=False)
    assert generate_lib.decode_enabled(None) is False
    assert generate_lib.decode_enabled(True) is True
    assert generate_lib.decode_enabled(False) is False
    monkeypatch.setenv("KDLT_DECODE", "1")
    assert generate_lib.decode_enabled(None) is True
    # Explicit wins over env either way.
    assert generate_lib.decode_enabled(False) is False


def test_json_mode_answers_one_document(lane, slo_spy):
    slo_spy.calls.clear()
    status, body, ctype, extra = lane.handle_generate(
        json.dumps({"prompt": "hi", "max_new_tokens": 4,
                    "stream": False}).encode(),
        rid="json-1",
    )
    assert status == 200 and ctype == protocol.JSON_CONTENT_TYPE
    doc = json.loads(body)
    assert doc["tokens"] == len(doc["text"].encode("utf-8", "replace")) or \
        doc["tokens"] >= 1  # EOS may cut the text short of the budget
    assert doc["finish_reason"] in ("stop", "length")
    assert doc["ttft_ms"] >= 0
    # The lane recorded exactly one SLO outcome for the request.
    assert slo_spy.calls == [("gen-test", 200, False)]


def test_stream_mode_yields_sse_frames_with_terminal_done(lane):
    status, payload, ctype, extra = lane.handle_generate(
        json.dumps({"prompt": "hello", "max_new_tokens": 5}).encode(),
        rid="sse-1",
    )
    assert status == 200
    assert ctype == protocol.EVENT_STREAM_CONTENT_TYPE
    # Streams must never enter any cache along the way.
    assert extra["Cache-Control"] == "no-store"
    events = protocol.parse_sse_events(b"".join(payload))
    done = events[-1]
    assert done["done"] is True
    assert done["tokens"] == len(events) - 1
    # The done event's transcript equals the concatenated token texts.
    assert done["text"] == "".join(e["text"] for e in events[:-1])


def test_stream_and_json_agree_on_the_same_prompt(lane):
    _, payload, _, _ = lane.handle_generate(
        json.dumps({"prompt": "same prompt", "max_new_tokens": 6}).encode()
    )
    streamed = protocol.parse_sse_events(b"".join(payload))[-1]["text"]
    _, body, _, _ = lane.handle_generate(
        json.dumps({"prompt": "same prompt", "max_new_tokens": 6,
                    "stream": False}).encode()
    )
    assert json.loads(body)["text"] == streamed


def test_malformed_and_unfittable_bodies_are_400(lane, slo_spy):
    slo_spy.calls.clear()
    status, body, ctype, _ = lane.handle_generate(b"notjson")
    assert status == 400 and b"error" in body
    # Prompt + budget beyond the 32-token context: rejected at submit.
    status, body, _, _ = lane.handle_generate(
        json.dumps({"prompt": "x" * 40, "max_new_tokens": 10}).encode()
    )
    assert status == 400 and b"exceeds" in body
    assert [c[1] for c in slo_spy.calls] == [400, 400]


def test_queue_at_capacity_is_a_retryable_503(lane, slo_spy):
    slo_spy.calls.clear()
    old_cap = lane.scheduler.queue_cap
    lane.scheduler.queue_cap = 0  # every admission is over cap
    try:
        status, body, _, _ = lane.handle_generate(
            json.dumps({"prompt": "hi"}).encode()
        )
    finally:
        lane.scheduler.queue_cap = old_cap
    assert status == 503 and b"capacity" in body
    assert slo_spy.calls == [("gen-test", 503, False)]


def test_budget_violation_counts_as_deadline_exceeded(lane, slo_spy,
                                                      monkeypatch):
    # A completed stream whose TTFT blows the per-token budget is LATE
    # for SLO purposes -- that is what feeds burn rates and the brownout
    # ladder, per-token SLOs being the lane's product surface.
    monkeypatch.setenv(generate_lib.TTFT_BUDGET_ENV, "0.000001")
    slo_spy.calls.clear()
    _, body, _, _ = lane.handle_generate(
        json.dumps({"prompt": "hi", "max_new_tokens": 3,
                    "stream": False}).encode()
    )
    assert json.loads(body)["finish_reason"] in ("stop", "length")
    assert slo_spy.calls == [("gen-test", 200, True)]


def test_debug_payload_has_window_budgets_and_occupancy(lane):
    payload = lane.debug_payload()
    assert payload["model"] == "gen-test"
    assert set(payload["budgets_ms"]) == {"ttft", "tpot"}
    w = payload["window"]
    assert w["generations"] >= 1  # earlier tests populated the window
    assert set(w["ttft_ms"]) == {"p50", "p95", "p99"}
    occ = payload["occupancy"]
    assert occ["max_slots"] == 2
    assert occ["active_slots"] == 0 and occ["queue_depth"] == 0
    assert occ["pages_total"] == lane.engine.num_pages - 1
    assert sum(payload["finish_reasons"].values()) == w["generations"]


def test_decode_series_minted_centrally_on_the_lane_registry(lane, registry):
    text = registry.render()
    for series in (
        "kdlt_decode_ttft_seconds",
        "kdlt_decode_tpot_seconds",
        "kdlt_decode_tokens_total",
        "kdlt_decode_generations_total",
        "kdlt_decode_steps_total",
        "kdlt_decode_kv_pages_in_use",
    ):
        assert series in text, series
    assert 'model="gen-test"' in text


def test_client_disconnect_mid_stream_cancels_the_generation(lane,
                                                             monkeypatch):
    # Slow the step down so the stream is demonstrably mid-flight when
    # the client vanishes (full speed would race the close against a
    # finished generation).
    import time as time_lib

    orig_step = lane.engine.step_async

    def slow_step():
        time_lib.sleep(0.01)
        return orig_step()

    monkeypatch.setattr(lane.engine, "step_async", slow_step)
    status, payload, _, _ = lane.handle_generate(
        json.dumps({"prompt": "hi", "max_new_tokens": 25}).encode(),
        rid="gone-1",
    )
    assert status == 200
    it = iter(payload)
    next(it)  # first token is on the wire...
    it.close()  # ...then the client goes away (transport closes the iterator)
    # The finally must cancel the generation so the decode loop frees the
    # slot instead of spending 29 more steps on a gone client.
    deadline = threading.Event()
    for _ in range(300):
        if lane.engine.active_slots == 0 and lane.engine.pages_in_use == 0:
            break
        deadline.wait(0.02)
    assert lane.engine.active_slots == 0
    assert lane.engine.pages_in_use == 0
    assert lane.debug_payload()["finish_reasons"].get("cancelled", 0) >= 1


# --- end-to-end: server route -> gateway relay -> client ---------------------


@pytest.mark.slow
def test_generate_streams_end_to_end_through_gateway_and_client(tmp_path):
    """The full wire path (slow: exports a model, warms two tiers): a
    token stream leaves the model server's ``:generate`` route as
    chunked SSE, relays through the gateway's ``/generate`` without
    buffering or caching, and lands in kdlt-client's incremental parser
    bit-identical to the non-streamed JSON answer."""
    import numpy as np

    from kubernetes_deep_learning_tpu.export import export_model
    from kubernetes_deep_learning_tpu.models import init_variables
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.serving import client as client_lib
    from kubernetes_deep_learning_tpu.serving.gateway import Gateway
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    spec = register_spec(ModelSpec(
        name="gen-e2e-xception", family="xception", input_shape=(96, 96, 3),
        labels=("a", "b"), preprocessing="tf", head_hidden=(8,),
    ))
    root = tmp_path / "models"
    export_model(spec, init_variables(spec, seed=0), str(root),
                 dtype=np.float32)
    server = ModelServer(str(root), port=0, buckets=(1, 2), decode=True)
    server.start()
    gw = Gateway(serving_host=f"localhost:{server.port}", model=spec.name,
                 port=0)
    gw.start()
    base = f"http://localhost:{gw.port}"
    try:
        stats: dict = {}
        events = list(client_lib.generate_stream(
            base, "hello tpu", max_new_tokens=6, stats=stats,
        ))
        done = events[-1]
        assert done["done"] is True and done["tokens"] == 6
        assert stats["request_id"]
        import requests

        r = requests.post(
            f"{base}/generate",
            json={"prompt": "hello tpu", "max_new_tokens": 6,
                  "stream": False},
            timeout=60,
        )
        assert r.status_code == 200
        assert r.json()["text"] == done["text"]  # greedy: same stream
        # Wrong model on the explicit route: 404 passthrough.
        r = requests.post(f"{base}/generate/nope", json={"prompt": "x"},
                          timeout=60)
        assert r.status_code == 404
        # The decode section rides each replica's /debug/slo through the
        # gateway merge -- the data kdlt-client's TTFT/TPOT table renders.
        slo = client_lib.fetch_slo(base)
        decs = [
            body.get("decode") for body in slo["replicas"].values()
            if isinstance(body, dict)
        ]
        assert any(d and d["window"]["generations"] >= 2 for d in decs)
        table = client_lib.render_decode_slo(slo)
        assert "gen-default" in table
    finally:
        gw.shutdown()
        server.shutdown()
