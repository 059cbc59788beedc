"""Gateway content-addressed cache + singleflight coalescing (ISSUE 8).

Three layers of coverage: the cache/singleflight primitives in isolation
(serving/cache.py), the gateway wiring with stubbed fetch/upstream (hit
vs miss vs coalesced dispositions, per-waiter deadlines, hot-reload
invalidation, the KDLT_CACHE kill switch), and one real HTTP stack e2e
(stub model tier, real gateway, kdlt-client stats) proving the
subsystem's wire surface: X-Kdlt-Cache dispositions, the cache-bust salt,
/debug/cache, and the artifact-hash header round trip.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from kubernetes_deep_learning_tpu.serving import cache as cache_lib
from kubernetes_deep_learning_tpu.serving.admission import Deadline
from kubernetes_deep_learning_tpu.serving.gateway import Gateway
from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib


# --- content addressing ------------------------------------------------------


def test_content_key_is_deterministic_and_field_separated():
    k1 = cache_lib.content_key("m", "h", "p", "payload")
    assert k1 == cache_lib.content_key("m", "h", "p", "payload")
    assert len(k1) == 64  # sha256 hex
    # Length-prefixed fields: shifting bytes between adjacent fields must
    # not collide.
    assert cache_lib.content_key("m", "ab", "c", "x") != (
        cache_lib.content_key("m", "a", "bc", "x")
    )
    # Every canonical field participates.
    base = ("model", "hash", "params", "url")
    for i in range(4):
        other = list(base)
        other[i] = other[i] + "!"
        assert cache_lib.content_key(*other) != cache_lib.content_key(*base)
    # The salt splits identities; identical salts agree.
    assert cache_lib.content_key(*base, salt="s") != (
        cache_lib.content_key(*base)
    )
    assert cache_lib.content_key(*base, salt="s") == (
        cache_lib.content_key(*base, salt="s")
    )


def test_cache_enabled_env_gate(monkeypatch):
    monkeypatch.delenv(cache_lib.CACHE_ENV, raising=False)
    assert cache_lib.cache_enabled() is True
    for off in ("0", "false", "off", "no"):
        monkeypatch.setenv(cache_lib.CACHE_ENV, off)
        assert cache_lib.cache_enabled() is False
    monkeypatch.setenv(cache_lib.CACHE_ENV, "1")
    assert cache_lib.cache_enabled() is True
    # Explicit argument wins over the env.
    monkeypatch.setenv(cache_lib.CACHE_ENV, "0")
    assert cache_lib.cache_enabled(True) is True


# --- ResponseCache primitives ------------------------------------------------


def test_response_cache_put_get_and_ttl_expiry():
    c = cache_lib.ResponseCache(ttl_s=0.05, max_mb=1.0)
    assert c.get("k") is None
    c.put("k", b"body", "application/json", "m", "h1")
    assert c.get("k") == (b"body", "application/json")
    time.sleep(0.08)
    assert c.get("k") is None  # expired
    assert c.evictions["ttl"] == 1


def test_response_cache_lru_eviction_respects_byte_budget():
    c = cache_lib.ResponseCache(ttl_s=60.0, max_mb=1.0)
    c.max_bytes = 100  # three 40-byte bodies cannot coexist
    c.put("a", b"x" * 40, "t", "m", "h")
    c.put("b", b"x" * 40, "t", "m", "h")
    assert c.get("a") is not None  # LRU-touch: "b" is now the oldest
    c.put("c", b"x" * 40, "t", "m", "h")
    assert c.get("b") is None and c.get("a") is not None
    assert c.get("c") is not None
    assert c.evictions["lru"] == 1
    # A body larger than the whole budget is never stored.
    assert c.put("huge", b"x" * 200, "t", "m", "h") is False
    assert c.get("huge") is None


def test_event_stream_responses_are_never_storable():
    # ISSUE 17 regression: a text/event-stream body is a live token
    # stream's transcript -- caching or singleflight-fanning one would
    # replay client A's generation to client B as a dead recording.  The
    # store predicate refuses the content type outright, for every
    # otherwise-storable status, so no future route can wire a stream
    # into the cache by accident.
    c = cache_lib.ResponseCache(ttl_s=60.0, max_mb=1.0, neg_ttl_s=5.0)
    assert c.storable_response(200, "application/json") is True
    assert c.storable_response(200, "text/event-stream") is False
    # Parameters and casing do not re-admit it.
    assert c.storable_response(200, "TEXT/EVENT-STREAM; charset=utf-8") is False
    assert c.storable_response(200, " text/event-stream ") is False
    assert c.storable_response(404, "text/event-stream") is False
    # No content type (legacy callers) falls back to the status rule.
    assert c.storable_response(200, None) is True
    # put() enforces the same predicate end to end.
    assert c.put("s", b"data: {}\n\n", "text/event-stream", "m", "h") is False
    assert c.get("s") is None
    assert c.put("j", b"{}", "application/json", "m", "h") is True


def test_response_cache_artifact_hash_invalidation_semantics():
    c = cache_lib.ResponseCache(ttl_s=60.0, max_mb=1.0)
    assert c.resolved_hash("m") == cache_lib.UNRESOLVED_HASH
    c.note_artifact_hash("m", "h1")
    c.put("k1", b"one", "t", "m", "h1")
    c.put("other-model", b"two", "t", "n", "zz")
    # Same hash again (e.g. a byte-identical version bump): entries kept.
    c.note_artifact_hash("m", "h1")
    assert c.get("k1") is not None
    # Changed bytes -> changed hash: m's entries drop, other models keep.
    c.note_artifact_hash("m", "h2")
    assert c.get("k1") is None
    assert c.get("other-model") is not None
    assert c.evictions["reload"] == 1
    assert c.resolved_hash("m") == "h2"


def test_response_cache_invalidate_model_scoped_drop():
    c = cache_lib.ResponseCache(ttl_s=60.0, max_mb=1.0)
    c.put("a", b"1", "t", "m", "h")
    c.put("b", b"2", "t", "m", "h")
    c.put("c", b"3", "t", "n", "h")
    assert c.invalidate_model("m") == 2
    assert c.get("a") is None and c.get("b") is None
    assert c.get("c") is not None


def test_cache_metrics_minted_centrally_and_updated():
    reg = metrics_lib.Registry()
    c = cache_lib.ResponseCache(registry=reg, ttl_s=60.0, max_mb=1.0)
    c.put("k", b"body", "t", "m", "h")
    c.get("k")
    c.count_miss()
    c.count_coalesced()
    page = reg.render()
    assert "kdlt_cache_hits_total 1" in page
    assert "kdlt_cache_misses_total 1" in page
    assert "kdlt_cache_coalesced_total 1" in page
    assert "kdlt_cache_bytes_total 4" in page
    assert "kdlt_cache_resident_bytes 4" in page
    assert 'kdlt_cache_evictions_total{reason="lru"} 0' in page
    assert "kdlt_cache_hit_ratio 0.5" in page


# --- singleflight primitives -------------------------------------------------


def test_singleflight_leader_resolves_followers():
    sf = cache_lib.SingleFlight()
    flight, leader = sf.begin("k")
    assert leader is True
    same, again = sf.begin("k")
    assert again is False and same is flight
    results = []
    t = threading.Thread(target=lambda: results.append(same.wait(5.0)))
    t.start()
    sf.finish("k", flight)
    flight.resolve("answer")
    t.join(timeout=5)
    assert results == ["answer"]
    # After finish, the key starts a fresh flight.
    _, leader2 = sf.begin("k")
    assert leader2 is True


def test_singleflight_wait_timeout_and_failure_propagation():
    sf = cache_lib.SingleFlight()
    flight, _ = sf.begin("k")
    with pytest.raises(cache_lib.FlightTimeout):
        flight.wait(0.02)  # the waiter's own budget, leader uncancelled
    flight.fail(RuntimeError("leader died"))
    with pytest.raises(RuntimeError, match="leader died"):
        flight.wait(1.0)


def test_singleflight_finish_is_identity_checked(monkeypatch):
    sf = cache_lib.SingleFlight()
    flight, _ = sf.begin("k")
    sf.finish("k", flight)
    replacement, leader = sf.begin("k")
    assert leader is True
    sf.finish("k", flight)  # stale leader must not evict the replacement
    joined, leader2 = sf.begin("k")
    assert leader2 is False and joined is replacement


# --- gateway wiring (stubbed fetch + upstream) -------------------------------


def _png_bytes() -> bytes:
    import io

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(np.zeros((8, 8, 3), np.uint8)).save(buf, format="PNG")
    return buf.getvalue()


def _offline_gateway(monkeypatch, fetch, **kw):
    """A bind=False Gateway that reaches nothing outside the process.

    The download is stubbed at the one seam every fetch goes through on
    either wire (ops/preprocess.fetch_image_bytes), and the model's contract
    is in place as if discovered from a server that advertises no ingest
    capability, so requests ride the tensor wire (_predict_batch, which the
    tests stub) and decode here.
    """
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec
    from kubernetes_deep_learning_tpu.ops import preprocess

    monkeypatch.setattr(
        preprocess, "fetch_image_bytes", lambda url, *a, **k: fetch(url)
    )
    gw = Gateway(
        serving_host="127.0.0.1:1", model="stub-model", bind=False, **kw
    )
    gw.pool.reference_spec = ModelSpec(
        name="stub-model", family="xception",  # never instantiated
        input_shape=(8, 8, 3), labels=("a", "b", "c"),
    )
    return gw


def _stub_gateway(monkeypatch, upstream_delay_s=0.0, **kw):
    """An offline gateway whose fetch succeeds and whose upstream hop is
    stubbed; the upstream call count is the singleflight/caching ground
    truth."""
    png = _png_bytes()
    gw = _offline_gateway(monkeypatch, lambda url: png, **kw)
    calls = {"n": 0}

    def fake_predict_batch(images, request_id="", deadline=None, trace=None,
                           model=None, priority=None):
        calls["n"] += 1
        if upstream_delay_s:
            time.sleep(upstream_delay_s)
        if gw.cache is not None:
            gw.cache.note_artifact_hash(model or gw.model, "hash-v1")
        return [np.arange(3, dtype=np.float32)], ["a", "b", "c"]

    gw._predict_batch = fake_predict_batch
    return gw, calls


def test_gateway_hit_skips_upstream_and_admission_slot(monkeypatch):
    gw, calls = _stub_gateway(monkeypatch)
    try:
        body = json.dumps({"url": "http://img/x.png"}).encode()
        s1, out1, _, h1 = gw.handle_predict(body, "rid-1")
        s2, out2, _, h2 = gw.handle_predict(body, "rid-2")
        assert (s1, s2) == (200, 200)
        assert h1[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert h2[cache_lib.CACHE_STATUS_HEADER] == "hit"
        assert out1 == out2
        assert calls["n"] == 1
        # The hit consumed no admission slot: exactly one request (the
        # miss) was seen/admitted by the controller.
        assert gw.admission._m["requests"].value == 1
        assert gw.admission._m["admitted"].value == 1
        # Both requests landed in the latency/SLO boundary.
        assert gw._m_latency.count == 2
        # The hit's trace carries the gateway.cache span.
        spans = gw.tracer.spans("rid-2")
        cache_span = next(s for s in spans if s["name"] == "gateway.cache")
        assert cache_span["tags"]["result"] == "hit"
    finally:
        gw.shutdown()


def test_gateway_kill_switch_disables_cache_and_coalescing(monkeypatch):
    monkeypatch.setenv(cache_lib.CACHE_ENV, "0")
    gw, calls = _stub_gateway(monkeypatch)
    try:
        assert gw.cache is None
        body = json.dumps({"url": "http://img/x.png"}).encode()
        _, _, _, h1 = gw.handle_predict(body, "rid-1")
        _, _, _, h2 = gw.handle_predict(body, "rid-2")
        assert cache_lib.CACHE_STATUS_HEADER not in h1
        assert cache_lib.CACHE_STATUS_HEADER not in h2
        assert calls["n"] == 2  # the legacy path, exactly
    finally:
        gw.shutdown()


def test_gateway_batch_requests_bypass_the_cache(monkeypatch):
    gw, calls = _stub_gateway(monkeypatch)
    try:
        body = json.dumps({"urls": ["http://img/x.png"]}).encode()
        s1, _, _, h1 = gw.handle_predict(body, "rid-1")
        s2, _, _, h2 = gw.handle_predict(body, "rid-2")
        assert (s1, s2) == (200, 200)
        assert cache_lib.CACHE_STATUS_HEADER not in h1
        assert cache_lib.CACHE_STATUS_HEADER not in h2
        assert calls["n"] == 2
    finally:
        gw.shutdown()


def test_gateway_cache_bust_salt_coalesces_but_never_stores(monkeypatch):
    gw, calls = _stub_gateway(monkeypatch)
    try:
        body = json.dumps({"url": "http://img/x.png"}).encode()
        _, _, _, h1 = gw.handle_predict(body, "rid-1", cache_bust="salt-a")
        _, _, _, h2 = gw.handle_predict(body, "rid-2", cache_bust="salt-a")
        # Sequential identical salted requests: both full misses -- the
        # salt opts out of storage (identical CONCURRENT salted requests
        # would still coalesce via singleflight).
        assert h1[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert h2[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert calls["n"] == 2
        assert gw.cache.stats()["entries"] == 0
        # And the unsalted request is independent of the salted ones.
        _, _, _, h3 = gw.handle_predict(body, "rid-3")
        assert h3[cache_lib.CACHE_STATUS_HEADER] == "miss"
        _, _, _, h4 = gw.handle_predict(body, "rid-4")
        assert h4[cache_lib.CACHE_STATUS_HEADER] == "hit"
    finally:
        gw.shutdown()


def test_hung_flight_waiters_honor_their_own_deadlines(monkeypatch):
    """ISSUE 8 satellite: a follower whose budget expires gets its OWN 504
    without cancelling the leader, whose flight completes and is cached."""
    gw, calls = _stub_gateway(monkeypatch, upstream_delay_s=1.0)
    try:
        body = json.dumps({"url": "http://img/slow.png"}).encode()
        leader_result: dict = {}

        def lead():
            leader_result["resp"] = gw.handle_predict(
                body, "rid-leader", Deadline(10.0)
            )

        t = threading.Thread(target=lead, daemon=True)
        t.start()
        deadline_t0 = time.monotonic()
        while not gw._singleflight.stats()["inflight_flights"]:
            assert time.monotonic() - deadline_t0 < 5.0, "leader never took off"
            time.sleep(0.005)
        w0 = time.monotonic()
        status, out, _, headers = gw.handle_predict(
            body, "rid-follower", Deadline(0.15)
        )
        follower_wait = time.monotonic() - w0
        assert status == 504
        assert headers[cache_lib.CACHE_STATUS_HEADER] == "coalesced"
        assert "coalesced" in json.loads(out)["error"]
        assert follower_wait < 0.8  # its own budget, not the leader's 1s
        t.join(timeout=5)
        assert leader_result["resp"][0] == 200  # the leader was NOT cancelled
        assert calls["n"] == 1
        # The leader's answer was cached despite the follower's 504.
        status, _, _, headers = gw.handle_predict(body, "rid-after")
        assert status == 200
        assert headers[cache_lib.CACHE_STATUS_HEADER] == "hit"
    finally:
        gw.shutdown()


def test_concurrent_identical_requests_coalesce_to_one_upstream_call(monkeypatch):
    gw, calls = _stub_gateway(monkeypatch, upstream_delay_s=0.25)
    try:
        body = json.dumps({"url": "http://img/popular.png"}).encode()
        results: list = []

        def fire(i):
            results.append(gw.handle_predict(body, f"rid-{i}", Deadline(10.0)))

        threads = [
            threading.Thread(target=fire, args=(i,), daemon=True)
            for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert len(results) == 8
        assert all(r[0] == 200 for r in results)
        assert all(
            json.loads(r[1].decode()) == json.loads(results[0][1].decode())
            for r in results
        )
        assert calls["n"] == 1, "singleflight must collapse to ONE dispatch"
        stats = gw.cache.stats()
        assert stats["misses"] == 1 and stats["coalesced"] == 7
        # Followers are admitted-but-not-dispatched: the admission counters
        # saw all 8, the limiter slots only the leader.
        assert gw.admission._m["requests"].value == 8
        assert gw.admission._m["admitted"].value == 8
    finally:
        gw.shutdown()


def test_upstream_error_is_shared_with_followers_but_never_cached(monkeypatch):
    """ISSUE 8 satellite (cache x faults): a failed flight's error fans
    out to its waiters, but the NEXT request retries upstream -- errors
    must never be served from the cache."""
    gw, calls = _stub_gateway(monkeypatch)
    fail = {"on": True}
    real_predict = gw._predict_batch

    def flaky(images, request_id="", deadline=None, trace=None, model=None,
              priority=None):
        if fail["on"]:
            calls["n"] += 1
            from kubernetes_deep_learning_tpu.serving.gateway import (
                UpstreamError,
            )

            raise UpstreamError("injected model tier failure", 502)
        return real_predict(images, request_id, deadline, trace, model,
                            priority=priority)

    gw._predict_batch = flaky
    try:
        body = json.dumps({"url": "http://img/flaky.png"}).encode()
        s1, out1, _, h1 = gw.handle_predict(body, "rid-1")
        assert s1 == 502 and "injected" in json.loads(out1)["error"]
        assert h1[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert gw.cache.stats()["entries"] == 0  # the 502 was NOT cached
        fail["on"] = False
        s2, _, _, h2 = gw.handle_predict(body, "rid-2")
        assert s2 == 200  # a real retry, not a cached error
        assert h2[cache_lib.CACHE_STATUS_HEADER] == "miss"
        s3, _, _, h3 = gw.handle_predict(body, "rid-3")
        assert s3 == 200 and h3[cache_lib.CACHE_STATUS_HEADER] == "hit"
    finally:
        gw.shutdown()


def test_hot_reload_with_changed_bytes_evicts_cached_entries(monkeypatch):
    """ISSUE 8 satellite: the artifact hash is the invalidation key -- a
    reload with changed bytes drops the model's entries; a byte-identical
    version bump (same hash) keeps them."""
    gw, calls = _stub_gateway(monkeypatch)
    current = {"hash": "artifact-v1"}
    real_predict = gw._predict_batch

    def versioned(images, request_id="", deadline=None, trace=None,
                  model=None, priority=None):
        calls["n"] += 1
        gw.cache.note_artifact_hash(model or gw.model, current["hash"])
        return [np.arange(3, dtype=np.float32)], ["a", "b", "c"]

    del real_predict
    gw._predict_batch = versioned
    try:
        body = json.dumps({"url": "http://img/x.png"}).encode()
        gw.handle_predict(body, "rid-1")
        _, _, _, h = gw.handle_predict(body, "rid-2")
        assert h[cache_lib.CACHE_STATUS_HEADER] == "hit"
        assert calls["n"] == 1
        # Byte-identical re-export under a higher version: same hash ->
        # entries kept (note arrives via some other model's response).
        gw.cache.note_artifact_hash(gw.model, "artifact-v1")
        _, _, _, h = gw.handle_predict(body, "rid-3")
        assert h[cache_lib.CACHE_STATUS_HEADER] == "hit"
        # Hot reload with CHANGED bytes: the hash changes, entries drop,
        # and the next request re-dispatches upstream.
        current["hash"] = "artifact-v2"
        gw.cache.note_artifact_hash(gw.model, "artifact-v2")
        _, _, _, h = gw.handle_predict(body, "rid-4")
        assert h[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert calls["n"] == 2
        assert gw.cache.stats()["evictions"]["reload"] >= 1
        # And the re-primed entry serves hits under the new hash.
        _, _, _, h = gw.handle_predict(body, "rid-5")
        assert h[cache_lib.CACHE_STATUS_HEADER] == "hit"
    finally:
        gw.shutdown()


def test_debug_cache_endpoint_payload(monkeypatch):
    gw, _calls = _stub_gateway(monkeypatch)
    try:
        body = json.dumps({"url": "http://img/x.png"}).encode()
        gw.handle_predict(body, "rid-1")
        gw.handle_predict(body, "rid-2")
        status, payload, ctype = gw.handle_get("/debug/cache")
        assert status == 200 and ctype == "application/json"
        data = json.loads(payload)
        assert data["enabled"] is True
        assert data["entries"] == 1
        assert data["hits"] == 1 and data["misses"] == 1
        assert data["hit_ratio"] == 0.5
        assert data["entries_by_model"] == {gw.model: 1}
        assert data["artifact_hashes"] == {gw.model: "hash-v1"}
        assert data["inflight_flights"] == 0
        assert data["resident_bytes"] == data["max_bytes"] or (
            data["resident_bytes"] <= data["max_bytes"]
        )
    finally:
        gw.shutdown()


def test_debug_cache_reports_disabled_posture(monkeypatch):
    monkeypatch.setenv(cache_lib.CACHE_ENV, "0")
    gw, _calls = _stub_gateway(monkeypatch)
    try:
        status, payload, _ = gw.handle_get("/debug/cache")
        assert status == 200
        data = json.loads(payload)
        # The response tier is off and says nothing else; the decoded-uint8
        # tier is its own switch and still reports.
        assert data.pop("decoded")["enabled"] is True
        assert data == {"enabled": False}
    finally:
        gw.shutdown()


# --- real HTTP stack: wire surface + kdlt-client stats ----------------------


def test_e2e_client_sees_cache_dispositions_and_bust(tmp_path):
    """One real stack (stub model tier, real gateway, HTTP): the client's
    stats['cache'] column (ISSUE 8 satellite), the cache-bust salt, the
    artifact-hash header round trip into /debug/cache, and the
    singleflight counter on /metrics."""
    import os as _os
    import threading as _threading
    from functools import partial
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    import requests
    from PIL import Image

    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
    from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
    from kubernetes_deep_learning_tpu.serving.client import predict_url
    from kubernetes_deep_learning_tpu.serving.model_server import ModelServer

    spec = register_spec(
        ModelSpec(
            name="cache-e2e",
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    root = tmp_path / "models"
    art.save_artifact(
        art.version_dir(str(root), spec.name, 1), spec, {"params": {}}, None, {}
    )
    server = ModelServer(
        str(root), port=0, buckets=(1, 2), max_delay_ms=1.0, host="127.0.0.1",
        engine_factory=StubEngine,
    )
    server.warmup()
    server.start()
    gw = Gateway(
        serving_host=f"127.0.0.1:{server.port}", model=spec.name,
        port=0, host="127.0.0.1",
    )
    gw.start()

    class Quiet(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    img_dir = tmp_path / "img"
    img_dir.mkdir()
    rng = np.random.default_rng(0)
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(_os.path.join(str(img_dir), "img.png"))
    httpd = HTTPServer(
        ("127.0.0.1", 0), partial(Quiet, directory=str(img_dir))
    )
    _threading.Thread(target=httpd.serve_forever, daemon=True).start()
    img_url = f"http://127.0.0.1:{httpd.server_address[1]}/img.png"
    base = f"http://127.0.0.1:{gw.port}"
    try:
        stats: dict = {}
        first = predict_url(base, img_url, stats=stats)
        assert stats["cache"] == "miss"
        stats = {}
        second = predict_url(base, img_url, stats=stats)
        assert stats["cache"] == "hit"
        assert first == second
        # --cache-bust semantics: a salted request bypasses the cached
        # answer but computes the same scores.
        stats = {}
        busted = predict_url(base, img_url, stats=stats, cache_bust="salt-1")
        assert stats["cache"] == "miss"
        assert busted == second
        # The model tier's artifact hash round-tripped into the cache.
        dbg = requests.get(f"{base}/debug/cache", timeout=5).json()
        served = list(server.models.values())[0]
        assert dbg["artifact_hashes"][spec.name] == served.artifact_hash
        assert dbg["hits"] == 1
        # The cache series render on /metrics (strict exposition is
        # covered by test_exposition; here: the counters moved).
        page = requests.get(f"{base}/metrics", timeout=5).text
        assert "kdlt_cache_hits_total 1" in page
    finally:
        gw.shutdown()
        server.shutdown()
        httpd.shutdown()


# --- negative caching (ISSUE 9 satellite / ROADMAP cache follow-on #1) ------


def test_negative_cache_put_lookup_expiry_and_5xx_refusal():
    c = cache_lib.ResponseCache(ttl_s=60.0, max_mb=1.0, neg_ttl_s=0.05)
    # 404/400 are storable under the negative TTL; 5xx never.
    assert c.storable_status(200) and c.storable_status(404)
    assert c.storable_status(400)
    for status in (500, 502, 503, 504):
        assert not c.storable_status(status)
        assert c.put("k5", b"boom", "t", "m", "h", status=status) is False
    assert c.lookup("k5") is None
    # A stored 404 answers with ITS status and counts as a negative hit.
    assert c.put("k", b'{"error":"no"}', "application/json", "m", "h",
                 status=404) is True
    assert c.lookup("k") == (404, b'{"error":"no"}', "application/json")
    assert c.negative_hits == 1 and c.hits == 1
    assert c.stats()["negative_entries"] == 1
    assert c.stats()["negative_hits"] == 1
    # ...and expires on the SHORT ttl, not the positive one.
    time.sleep(0.06)
    assert c.lookup("k") is None
    assert c.evictions["ttl"] == 1
    # A positive entry under the same clock survives (ttl_s=60).
    c.put("pos", b"ok", "t", "m", "h")
    time.sleep(0.06)
    assert c.lookup("pos") == (200, b"ok", "t")


def test_negative_cache_disabled_when_ttl_zero():
    c = cache_lib.ResponseCache(ttl_s=60.0, max_mb=1.0, neg_ttl_s=0.0)
    assert not c.storable_status(404)
    assert c.put("k", b"x", "t", "m", "h", status=404) is False
    # 200s still cache normally.
    assert c.put("k", b"x", "t", "m", "h") is True


def test_negative_cache_metrics_minted_centrally(monkeypatch):
    reg = metrics_lib.Registry()
    c = cache_lib.ResponseCache(registry=reg, ttl_s=60.0, max_mb=1.0,
                                neg_ttl_s=5.0)
    c.put("k", b"e", "t", "m", "h", status=400)
    c.lookup("k")
    page = reg.render()
    assert "kdlt_cache_negative_hits_total 1" in page


def _failing_fetch_gateway(monkeypatch, neg_ttl_s, fail_with=None, **kw):
    """An offline gateway whose image fetch always fails (the
    hammered-bad-URL scenario); ``fetches`` is the cost ground truth."""
    fetches = {"n": 0}

    def fake_fetch(url):
        fetches["n"] += 1
        if fail_with is not None:
            raise fail_with
        raise ValueError("404 Not Found fetching image")

    gw = _offline_gateway(
        monkeypatch, fake_fetch, cache_neg_ttl_s=neg_ttl_s, **kw
    )
    return gw, fetches


def test_gateway_negative_caches_repeated_bad_url(monkeypatch):
    gw, fetches = _failing_fetch_gateway(monkeypatch, neg_ttl_s=5.0)
    try:
        body = json.dumps({"url": "http://img/broken.png"}).encode()
        s1, out1, _, h1 = gw.handle_predict(body, "rid-1")
        assert s1 == 400
        assert h1[cache_lib.CACHE_STATUS_HEADER] == "miss"
        s2, out2, _, h2 = gw.handle_predict(body, "rid-2")
        assert s2 == 400 and out2 == out1
        assert h2[cache_lib.CACHE_STATUS_HEADER] == "hit"
        assert fetches["n"] == 1  # the hammered bad URL paid the path ONCE
        assert gw.cache.negative_hits == 1
        # A different URL is its own identity.
        s3, _, _, h3 = gw.handle_predict(
            json.dumps({"url": "http://img/other.png"}).encode(), "rid-3"
        )
        assert s3 == 400 and h3[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert fetches["n"] == 2
    finally:
        gw.shutdown()


def test_gateway_negative_cache_expires_and_disabled_posture(monkeypatch):
    gw, fetches = _failing_fetch_gateway(monkeypatch, neg_ttl_s=0.05)
    try:
        body = json.dumps({"url": "http://img/broken.png"}).encode()
        gw.handle_predict(body, "rid-1")
        time.sleep(0.06)
        _, _, _, h2 = gw.handle_predict(body, "rid-2")
        assert h2[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert fetches["n"] == 2  # expired: the bad URL is re-checked
    finally:
        gw.shutdown()
    gw, fetches = _failing_fetch_gateway(monkeypatch, neg_ttl_s=0.0)
    try:
        body = json.dumps({"url": "http://img/broken.png"}).encode()
        gw.handle_predict(body, "rid-1")
        _, _, _, h2 = gw.handle_predict(body, "rid-2")
        assert h2[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert fetches["n"] == 2  # negative caching off: full path per hit
    finally:
        gw.shutdown()


def test_gateway_never_negative_caches_5xx(monkeypatch):
    from kubernetes_deep_learning_tpu.serving.gateway import UpstreamError

    gw, fetches = _failing_fetch_gateway(
        monkeypatch, neg_ttl_s=5.0, fail_with=UpstreamError("replica down", http_status=502)
    )
    try:
        body = json.dumps({"url": "http://img/x.png"}).encode()
        s1, _, _, _ = gw.handle_predict(body, "rid-1")
        s2, _, _, h2 = gw.handle_predict(body, "rid-2")
        assert (s1, s2) == (502, 502)
        assert h2[cache_lib.CACHE_STATUS_HEADER] == "miss"
        assert fetches["n"] == 2  # a transient upstream failure is never replayed
        assert gw.cache.stats()["entries"] == 0
    finally:
        gw.shutdown()
