"""Serving-path fault tolerance through the real tiers (stub backend):
multi-replica failover when a replica dies mid-run, active-probe recovery,
budget-aware hedged requests, the engine watchdog failing hung dispatches
and flipping health, per-replica spec re-validation on failover, and the
client's connect-error retries.  All device-free."""

from __future__ import annotations

import re
import threading
import time

import numpy as np
import pytest

from kubernetes_deep_learning_tpu.export import artifact as art
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
from kubernetes_deep_learning_tpu.serving import faults, protocol
from kubernetes_deep_learning_tpu.serving.admission import Deadline
from kubernetes_deep_learning_tpu.serving.gateway import Gateway, UpstreamError
from kubernetes_deep_learning_tpu.serving.model_server import ModelServer
from kubernetes_deep_learning_tpu.serving.upstream import UpstreamPool


def _metric(text: str, name: str, **labels: str) -> float:
    for m in re.finditer(rf"^{re.escape(name)}(\{{[^}}]*\}})? (\S+)$", text, re.M):
        got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1) or ""))
        if all(got.get(k) == v for k, v in labels.items()):
            return float(m.group(2))
    raise AssertionError(f"no sample {name} with {labels} in:\n{text}")


def _make_stub_server(
    name, tmp_path, subdir="models", device_ms=0.0, labels=("a", "b", "c"), **kw
):
    spec = register_spec(
        ModelSpec(
            name=name,
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=tuple(labels),
        )
    )
    root = tmp_path / subdir
    art.save_artifact(
        art.version_dir(str(root), spec.name, 1), spec, {"params": {}}, None, {}
    )
    factory = kw.pop("engine_factory", None) or (
        lambda a, **ekw: StubEngine(a, device_ms_per_batch=device_ms, **ekw)
    )
    server = ModelServer(
        str(root), port=kw.pop("port", 0), buckets=kw.pop("buckets", (1, 2)),
        max_delay_ms=1.0, host="127.0.0.1", engine_factory=factory, **kw,
    )
    server.warmup()
    server.start()
    return spec, server


def _hard_kill(server) -> None:
    """The chaos kill: in-flight/keep-alive predicts drop their connection
    (injected disconnect) and the listener closes, so new connects --
    including health probes -- are refused.  shutdown() alone is not a kill:
    pooled keep-alive sockets keep being served by their handler threads."""
    server._faults = faults.FaultInjector(
        faults.parse_rules("server.predict:disconnect:1.0")
    )
    server.shutdown()


IMG = np.zeros((1, 32, 32, 3), np.uint8)


# --- pool unit behavior -----------------------------------------------------


def test_pool_round_robins_and_prefers_healthy():
    pool = UpstreamPool(["h1:1", "h2:2"], failover=True, probe_interval_s=0)
    a, b = pool.replicas
    first = pool.choose()
    second = pool.choose()
    assert {first, second} == {a, b}  # round-robin spreads load
    # Two consecutive failures mark a replica unhealthy and route around it.
    pool.record_failure(a)
    pool.record_failure(a)
    assert not a.healthy
    assert pool.choose() is b and pool.choose() is b
    # ...but it stays reachable as a last resort (breaker-gated recovery).
    assert pool.choose(exclude=[b]) is a
    pool.record_success(a)
    assert a.healthy


def test_pool_blind_mode_ignores_health():
    pool = UpstreamPool(["h1:1", "h2:2"], failover=False, probe_interval_s=0)
    a, b = pool.replicas
    for _ in range(3):
        pool.record_failure(a)
    got = {pool.choose() for _ in range(4)}
    assert got == {a, b}  # dead or alive, every replica takes its turn
    assert not pool.has_healthy_candidate(exclude=[b])


def test_pool_mark_stalled_takes_replica_out_on_first_observation():
    """ISSUE 8 satellite (ROADMAP cross-host gap #1): a DECLARED dispatch
    stall (the model tier's X-Kdlt-Stalled 503) is terminal until restart,
    so one observation suffices -- unlike ordinary failures, which take
    UNHEALTHY_AFTER consecutive ones."""
    pool = UpstreamPool(["h1:1", "h2:2"], failover=True, probe_interval_s=0)
    a, b = pool.replicas
    # One ORDINARY failure does not unhealth a replica...
    pool.record_failure(a)
    assert a.healthy
    pool.record_success(a)
    # ...but one declared stall does, immediately.
    pool.mark_stalled(a)
    assert not a.healthy
    assert pool.choose() is b and pool.choose() is b
    # The stall mark is sticky against the consecutive-failure reset
    # logic: only an actual health-probe rejoin brings it back.
    assert not pool.has_healthy_candidate(exclude=[b])
    pool.record_success(a)  # e.g. the prober's rejoin path
    assert a.healthy


def test_pool_parse_hosts():
    from kubernetes_deep_learning_tpu.serving.upstream import parse_hosts

    assert parse_hosts("a:1, b:2,a:1,") == ["a:1", "b:2"]
    with pytest.raises(ValueError):
        parse_hosts(" , ")


# --- failover through the real gateway --------------------------------------


def test_gateway_fails_over_to_surviving_replica(tmp_path):
    spec, victim = _make_stub_server("fo-live", tmp_path, subdir="a")
    _, survivor = _make_stub_server("fo-live", tmp_path, subdir="b")
    gw = Gateway(
        serving_host=f"127.0.0.1:{victim.port},127.0.0.1:{survivor.port}",
        model=spec.name, port=0, bind=False, probe_interval_s=0.2,
    )
    try:
        gw.spec  # discover the reference contract while both are alive
        _hard_kill(victim)
        # Every request succeeds: dialing the dead replica fails over
        # in-request to the survivor.
        for _ in range(4):
            logits, labels = gw._predict_batch(IMG)
            assert list(labels) == ["a", "b", "c"]
            assert np.asarray(logits).shape == (1, 3)
        metrics = gw.registry.render()
        assert _metric(metrics, "kdlt_upstream_failover_total") >= 1
        assert _metric(
            metrics, "kdlt_upstream_replica_healthy",
            replica=f"127.0.0.1:{victim.port}",
        ) == 0.0
        assert _metric(
            metrics, "kdlt_upstream_replica_healthy",
            replica=f"127.0.0.1:{survivor.port}",
        ) == 1.0
    finally:
        gw.shutdown()
        survivor.shutdown()


def test_prober_rejoins_recovered_replica(tmp_path):
    spec, victim = _make_stub_server("fo-rejoin", tmp_path, subdir="a")
    _, survivor = _make_stub_server("fo-rejoin", tmp_path, subdir="b")
    victim_port = victim.port
    gw = Gateway(
        serving_host=f"127.0.0.1:{victim_port},127.0.0.1:{survivor.port}",
        model=spec.name, port=0, bind=False, probe_interval_s=0.1,
    )
    revived = None
    try:
        gw.spec
        _hard_kill(victim)
        gw._predict_batch(IMG)  # trips passive health marking
        gw._predict_batch(IMG)
        victim_replica = gw.pool.replicas[0]
        assert not victim_replica.healthy
        # Revive a replica on the SAME port; the active prober must rejoin
        # it within a probe interval or two.
        _, revived = _make_stub_server(
            "fo-rejoin", tmp_path, subdir="a2", port=victim_port
        )
        deadline = time.monotonic() + 5.0
        while not victim_replica.healthy and time.monotonic() < deadline:
            time.sleep(0.05)
        assert victim_replica.healthy, "prober never rejoined the replica"
        # The rejoined replica's spec was re-validated (fresh fetch).
        gw.pool._rr = 0  # next choose targets the rejoined replica
        logits, _ = gw._predict_batch(IMG)
        assert np.asarray(logits).shape == (1, 3)
    finally:
        gw.shutdown()
        survivor.shutdown()
        if revived is not None:
            revived.shutdown()


def test_spec_mismatch_on_failover_surfaces_as_502(tmp_path):
    # Replica B serves the same model NAME with a different contract
    # (different labels): failover must 502 loudly, not mix responses.
    spec, good = _make_stub_server("fo-spec", tmp_path, subdir="a")
    _, bad = _make_stub_server(
        "fo-spec", tmp_path, subdir="b", labels=("x", "y", "z")
    )
    gw = Gateway(
        serving_host=f"127.0.0.1:{good.port},127.0.0.1:{bad.port}",
        model=spec.name, port=0, bind=False, probe_interval_s=0,
    )
    try:
        gw.pool._rr = 0
        gw.spec  # reference contract discovered from the good replica
        assert gw.pool.reference_spec.labels == ("a", "b", "c")
        _hard_kill(good)
        with pytest.raises(UpstreamError) as exc:
            gw._predict_batch(IMG)
        assert exc.value.http_status == 502
        assert "different model contract" in str(exc.value)
        # The mismatching replica is routed around from now on.
        assert not gw.pool.replicas[1].healthy
    finally:
        gw.shutdown()
        bad.shutdown()


def test_gateway_upstream_fault_point_counts_and_exhausts_pool(
    tmp_path, monkeypatch
):
    # gateway.upstream:error:1.0 faults EVERY upstream attempt: the gateway
    # fails over through the whole pool, then surfaces a retryable 5xx --
    # and every injection is visible on the gateway's own /metrics.
    spec, a = _make_stub_server("gw-fault", tmp_path, subdir="a")
    _, b = _make_stub_server("gw-fault", tmp_path, subdir="b")
    monkeypatch.setenv(faults.FAULTS_ENV, "gateway.upstream:error:1.0")
    gw = Gateway(
        serving_host=f"127.0.0.1:{a.port},127.0.0.1:{b.port}",
        model=spec.name, port=0, bind=False, probe_interval_s=0,
    )
    try:
        gw.spec  # discovery GETs are not a fault point; only predicts are
        with pytest.raises(UpstreamError) as exc:
            gw._predict_batch(IMG)
        assert exc.value.http_status >= 500
        assert "injected fault" in str(exc.value)
        assert _metric(
            gw.registry.render(), "kdlt_fault_injected_total",
            point="gateway.upstream", kind="error",
        ) == 2.0  # one per replica attempt: the pool was actually swept
    finally:
        gw.shutdown()
        a.shutdown()
        b.shutdown()


# --- hedged requests --------------------------------------------------------


def test_hedge_fires_when_budget_allows_and_wins(tmp_path):
    spec, slow = _make_stub_server(
        "hedge-ab", tmp_path, subdir="a", device_ms=500.0
    )
    _, fast = _make_stub_server("hedge-ab", tmp_path, subdir="b")
    gw = Gateway(
        serving_host=f"127.0.0.1:{slow.port},127.0.0.1:{fast.port}",
        model=spec.name, port=0, bind=False,
        hedge_delay_ms=50.0, probe_interval_s=0,
    )
    try:
        gw.spec
        gw.pool._rr = 0  # primary = the slow replica
        t0 = time.perf_counter()
        logits, _ = gw._predict_batch(IMG, deadline=Deadline(5.0))
        dt = time.perf_counter() - t0
        assert np.asarray(logits).shape == (1, 3)
        assert dt < 0.45, f"hedge should beat the 500ms primary, took {dt:.3f}s"
        metrics = gw.registry.render()
        assert _metric(metrics, "kdlt_hedge_fired_total") == 1.0
        assert _metric(metrics, "kdlt_hedge_won_total") == 1.0
    finally:
        gw.shutdown()
        slow.shutdown()
        fast.shutdown()


def test_hedge_skipped_when_budget_cannot_cover_it(tmp_path):
    spec, slow = _make_stub_server(
        "hedge-budget", tmp_path, subdir="a", device_ms=300.0
    )
    _, fast = _make_stub_server("hedge-budget", tmp_path, subdir="b")
    gw = Gateway(
        serving_host=f"127.0.0.1:{slow.port},127.0.0.1:{fast.port}",
        model=spec.name, port=0, bind=False,
        hedge_delay_ms=50.0, probe_interval_s=0,
    )
    try:
        gw.spec
        gw.pool._rr = 0  # primary = the slow replica
        # Budget below hedge_delay + floor: the hedge must NOT fire -- it
        # would be spent work that cannot finish either.
        with pytest.raises(UpstreamError):
            gw._predict_batch(IMG, deadline=Deadline(0.08))
        assert _metric(gw.registry.render(), "kdlt_hedge_fired_total") == 0.0
    finally:
        gw.shutdown()
        slow.shutdown()
        fast.shutdown()


# --- engine watchdog --------------------------------------------------------


def test_watchdog_fails_hung_dispatch(tmp_path, monkeypatch):
    from types import SimpleNamespace

    from kubernetes_deep_learning_tpu.runtime import (
        DispatchStall,
        InFlightDispatcher,
    )

    monkeypatch.setenv(faults.FAULTS_ENV, "dispatch.complete:hang:1.0:60")
    spec = register_spec(
        ModelSpec(
            name="wd-unit", family="xception",
            input_shape=(32, 32, 3), labels=("a", "b", "c"),
        )
    )
    engine = StubEngine(
        SimpleNamespace(spec=spec), buckets=(1, 2),
        device_ms_per_batch=1.0, async_device=True,
    )
    disp = InFlightDispatcher(engine, depth=2, stall_floor_s=0.2)
    try:
        fut = disp.submit(IMG)
        with pytest.raises(DispatchStall):
            fut.result(timeout=10.0)
        assert disp.stalled
        # After the stall: intake fails fast and retryably, no hang.
        with pytest.raises(DispatchStall):
            disp.submit(IMG)
    finally:
        t0 = time.perf_counter()
        disp.close()  # must not wait out the 60s hang
        assert time.perf_counter() - t0 < 5.0
        engine.close()


def test_watchdog_stall_flips_health_endpoints(tmp_path, monkeypatch):
    import requests

    monkeypatch.setenv(faults.FAULTS_ENV, "dispatch.complete:hang:1.0:60")
    monkeypatch.setenv("KDLT_WATCHDOG_FLOOR_S", "0.3")
    spec, server = _make_stub_server(
        "wd-health", tmp_path, device_ms=1.0,
        engine_factory=lambda a, **kw: StubEngine(
            a, device_ms_per_batch=1.0, async_device=True, **kw
        ),
        pipeline_depth=2, use_batcher=False,
    )
    base = f"http://127.0.0.1:{server.port}"
    try:
        assert requests.get(f"{base}/healthz", timeout=5).status_code == 200
        # A 4-image request rides the chunked dispatcher path (buckets max
        # 2); the injected hang wedges its completion, the watchdog fails
        # the futures, and the handler maps it to a retryable 503.
        img = np.zeros((4, *spec.input_shape), np.uint8)
        r = requests.post(
            f"{base}/v1/models/{spec.name}:predict",
            data=protocol.encode_predict_request(img),
            headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE},
            timeout=30.0,
        )
        assert r.status_code == 503
        assert "stalled" in r.json()["error"]
        assert "Retry-After" in r.headers
        # Liveness AND readiness follow: the orchestrator restarts the pod,
        # the endpoint pool drops it, the gateway's prober routes around it.
        r = requests.get(f"{base}/healthz", timeout=5)
        assert (r.status_code, r.text) == (503, "dispatch stalled")
        assert requests.get(f"{base}/readyz", timeout=5).status_code == 503
        metrics = requests.get(f"{base}/metrics", timeout=5).text
        assert _metric(
            metrics, "kdlt_dispatch_stall_total",
            model=spec.name, version="1",
        ) >= 1.0
    finally:
        server.shutdown()


def test_watchdog_leaves_healthy_pipeline_alone(tmp_path, monkeypatch):
    from types import SimpleNamespace

    from kubernetes_deep_learning_tpu.runtime import InFlightDispatcher

    monkeypatch.delenv(faults.FAULTS_ENV, raising=False)
    spec = register_spec(
        ModelSpec(
            name="wd-clean", family="xception",
            input_shape=(32, 32, 3), labels=("a", "b", "c"),
        )
    )
    engine = StubEngine(
        SimpleNamespace(spec=spec), buckets=(1, 2),
        device_ms_per_batch=5.0, async_device=True,
    )
    disp = InFlightDispatcher(engine, depth=2, stall_floor_s=0.5)
    try:
        futs = [disp.submit(IMG) for _ in range(6)]
        rows = [np.asarray(f.result(timeout=10)) for f in futs]
        assert all(r.shape == (1, 3) for r in rows)
        assert not disp.stalled
    finally:
        disp.close()
        engine.close()


# --- client connect-error retries -------------------------------------------


def test_client_retries_connect_errors_with_distinct_label():
    import socket

    import requests

    from kubernetes_deep_learning_tpu.serving.client import predict_url

    # A port that was just closed: connects are refused deterministically.
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    stats: dict = {}
    t0 = time.monotonic()
    with pytest.raises(requests.ConnectionError):
        predict_url(
            f"http://127.0.0.1:{port}", "http://x/img.png",
            timeout=10.0, retries=2, stats=stats,
        )
    assert stats["retried_connect"] == 2  # labeled distinctly from sheds
    assert stats["retried_shed"] == 0
    assert time.monotonic() - t0 < 5.0  # jittered short backoffs, bounded


def test_client_connect_retry_bounded_by_timeout():
    import socket

    import requests

    from kubernetes_deep_learning_tpu.serving.client import predict_url

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    stats: dict = {}
    with pytest.raises(requests.ConnectionError):
        # A budget smaller than any backoff sleep: no retry is affordable,
        # the connect error surfaces immediately.
        predict_url(
            f"http://127.0.0.1:{port}", "http://x/img.png",
            timeout=0.01, retries=5, stats=stats,
        )
    assert stats["retried_connect"] == 0


# --- a replica lost under traffic, through the gateway's own /predict -------


@pytest.fixture
def image_url(tmp_path):
    """One PNG behind a loopback http.server: what /predict fetches."""
    from functools import partial
    from http.server import HTTPServer, SimpleHTTPRequestHandler

    from PIL import Image

    class Quiet(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    img_dir = tmp_path / "img"
    img_dir.mkdir()
    Image.fromarray(
        np.random.default_rng(0).integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(img_dir / "img.png")
    httpd = HTTPServer(("127.0.0.1", 0), partial(Quiet, directory=str(img_dir)))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}/img.png"
    httpd.shutdown()
    httpd.server_close()


def _two_replica_gateway(name, tmp_path, probe_interval_s=0.2, **server_kw):
    spec, victim = _make_stub_server(name, tmp_path, subdir="a", **server_kw)
    _, survivor = _make_stub_server(name, tmp_path, subdir="b", **server_kw)
    gw = Gateway(
        serving_host=f"127.0.0.1:{victim.port},127.0.0.1:{survivor.port}",
        model=spec.name, port=0, host="127.0.0.1",
        hedge_delay_ms=100.0, probe_interval_s=probe_interval_s,
        # One repeated URL: the response cache would answer every request
        # after the first and nothing would reach the pool.
        cache=False,
    )
    gw.start()
    gw.spec  # discover the contract while both replicas are alive
    return victim, survivor, gw


def _predict_concurrently(gw, image_url, n_threads, per_thread, deadline_ms):
    """[(status, seconds)] of n_threads x per_thread POST /predict."""
    import requests

    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER

    results: list = []

    def client() -> None:
        with requests.Session() as session:
            for _ in range(per_thread):
                t0 = time.monotonic()
                try:
                    status = session.post(
                        f"http://127.0.0.1:{gw.port}/predict",
                        json={"url": image_url},
                        headers={DEADLINE_HEADER: f"{deadline_ms:.1f}"},
                        timeout=deadline_ms / 1e3 + 5.0,
                    ).status_code
                except requests.RequestException:
                    status = -1
                results.append((status, time.monotonic() - t0))

    threads = [threading.Thread(target=client, daemon=True) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not any(t.is_alive() for t in threads)
    return results


def test_pool_holds_goodput_when_a_replica_is_killed(tmp_path, image_url):
    """One of two replicas is hard-killed under concurrent traffic: every
    request sent after the kill is still answered 200 inside its deadline,
    because a request that dials the dead replica fails over in-request."""
    deadline_ms = 2000.0
    victim, survivor, gw = _two_replica_gateway("fo-goodput", tmp_path, device_ms=5.0)
    try:
        before = _predict_concurrently(gw, image_url, 2, 3, deadline_ms)
        assert [s for s, _ in before] == [200] * 6
        # Both replicas took traffic, so the pool holds keep-alive sockets
        # to the one about to die as well as refused connects.
        assert victim._m_requests.value >= 1 and survivor._m_requests.value >= 1
        _hard_kill(victim)
        survivor_before = survivor._m_requests.value
        after = _predict_concurrently(gw, image_url, 4, 6, deadline_ms)
        assert [s for s, _ in after] == [200] * 24, after
        assert max(dt for _, dt in after) <= deadline_ms / 1e3
        assert survivor._m_requests.value - survivor_before >= 24
        metrics = gw.registry.render()
        assert _metric(metrics, "kdlt_upstream_failover_total") >= 1
        assert _metric(
            metrics, "kdlt_upstream_replica_healthy",
            replica=f"127.0.0.1:{victim.port}",
        ) == 0.0
    finally:
        gw.shutdown()
        survivor.shutdown()


def test_stalled_replica_is_marked_out_on_first_observation(tmp_path, image_url):
    """A replica whose dispatcher declared a stall keeps answering -- fast
    503s with X-Kdlt-Stalled, /healthz failing.  The pool takes it out at the
    first such answer (an ordinary 5xx takes UNHEALTHY_AFTER of them): of the
    requests that follow, sent one at a time, the victim sees exactly one,
    every one is answered 200 by the survivor, and the prober cannot bring
    the victim back."""
    victim, survivor, gw = _two_replica_gateway(
        "fo-stall", tmp_path,
        # The prober's /readyz watch would take the victim out of rotation
        # too, within an interval; this test is about the answer itself, so
        # the prober sleeps through the traffic and is run by hand after it.
        probe_interval_s=30.0,
        # The async engine surface: the model then serves through the
        # scheduler's shared InFlightDispatcher, the thing that stalls.
        engine_factory=lambda a, **kw: StubEngine(
            a, device_ms_per_batch=5.0, async_device=True, **kw
        ),
    )
    try:
        before = _predict_concurrently(gw, image_url, 2, 3, 2000.0)
        assert [s for s, _ in before] == [200] * 6
        victim_before = victim._m_requests.value
        victim_replica = gw.pool.replicas[0]
        # Two-choices ranks by latency: without a sample the victim leads,
        # so the next request is the one that observes the stall.
        victim_replica.ewma_ms = None
        victim.scheduler.dispatcher.declare_stall()  # what the watchdog does
        after = _predict_concurrently(gw, image_url, 1, 12, 2000.0)
        assert [s for s, _ in after] == [200] * 12, after
        fed = victim._m_requests.value - victim_before
        assert fed == 1, f"the pool fed the stalled replica {fed} requests"
        assert not victim_replica.healthy
        gw.pool.probe_once()  # /healthz says "dispatch stalled": no rejoin
        assert not victim_replica.healthy
    finally:
        gw.shutdown()
        survivor.shutdown()
        victim.shutdown()
