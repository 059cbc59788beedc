"""Span tracing end to end: the Dapper-style waterfall across both tiers.

Acceptance surface of the tracing layer (utils/trace.py):

- one traced request through gateway -> model tier yields a MERGED
  waterfall (the gateway's /debug/trace/<rid> pulls the model tier's spans
  in) with >= 8 spans, correct parent/child nesting, and monotonic
  non-overlapping pipeline-stage intervals;
- a hedged request's trace shows BOTH upstream attempt spans with the
  winner marked;
- the root span of a request's merged waterfall covers >= 95% of the wall
  time its client measured, on a stub run.

Everything runs on stub engines (async device: the in-flight dispatch
pipeline and its stage spans engage) -- no compiles, CPU-only.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time
from functools import partial
from http.server import HTTPServer, SimpleHTTPRequestHandler

import numpy as np
import pytest
import requests

from kubernetes_deep_learning_tpu.export import artifact as art
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.runtime.stub import StubEngine
from kubernetes_deep_learning_tpu.serving.gateway import Gateway
from kubernetes_deep_learning_tpu.serving.model_server import ModelServer
from kubernetes_deep_learning_tpu.serving.tracing import (
    PARENT_SPAN_HEADER,
    REQUEST_ID_HEADER,
    TRACE_HEADER,
)
from kubernetes_deep_learning_tpu.utils import trace as trace_lib


# --- unit: the tracer core -------------------------------------------------


def test_tracer_ring_buffer_evicts_oldest_trace():
    t = trace_lib.Tracer("test", max_traces=3, max_spans=4)
    for i in range(5):
        t.record(f"trace-{i}", "root", trace_lib.now_s(), 0.001)
    assert t.spans("trace-0") is None and t.spans("trace-1") is None
    assert t.spans("trace-4") is not None


def test_tracer_caps_spans_per_trace():
    t = trace_lib.Tracer("test", max_spans=4)
    for _ in range(10):
        t.record("rid", "s", trace_lib.now_s(), 0.001)
    assert len(t.spans("rid")) == 4


def test_request_trace_span_nesting_and_tags():
    t = trace_lib.Tracer("test")
    rt = t.request_trace("rid")
    with rt.span("outer") as outer:
        with outer.span("inner") as inner:
            inner.tags["k"] = "v"
    spans = {s["name"]: s for s in t.spans("rid")}
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["outer"]["parent_id"] == rt.span_id
    assert spans["inner"]["tags"]["k"] == "v"
    # summary header: Server-Timing style, record order (inner closed first)
    assert t.summary("rid").startswith("inner;dur=")


def test_span_recorded_even_when_block_raises():
    t = trace_lib.Tracer("test")
    rt = t.request_trace("rid")
    with pytest.raises(RuntimeError):
        with rt.span("failing"):
            raise RuntimeError("boom")
    assert [s["name"] for s in t.spans("rid")] == ["failing"]


def test_span_enters_the_annotate_hook_with_its_name_and_exits_on_error():
    """Live spans are profiler annotations where the tier passes a hook
    (the model server passes jax.profiler.TraceAnnotation): entered with the
    span's name around the block, exited even when the block raises."""
    from contextlib import contextmanager

    log = []

    @contextmanager
    def annotate(name):
        log.append(("enter", name))
        try:
            yield
        finally:
            log.append(("exit", name))

    t = trace_lib.Tracer("test", annotate=annotate)
    rt = t.request_trace("rid")
    with rt.span("server.decode") as dt:
        with dt.span("server.read_body"):
            log.append("body")
    with pytest.raises(RuntimeError):
        with rt.span("server.predict"):
            raise RuntimeError("boom")
    assert log == [
        ("enter", "server.decode"), ("enter", "server.read_body"), "body",
        ("exit", "server.read_body"), ("exit", "server.decode"),
        ("enter", "server.predict"), ("exit", "server.predict"),
    ]
    # the waterfall is what it was without the hook
    assert [s["name"] for s in t.spans("rid")] == [
        "server.read_body", "server.decode", "server.predict",
    ]


def test_tracer_without_the_hook_calls_nothing_and_gateway_passes_none(traced_stack):
    t = trace_lib.Tracer("test")
    assert t.annotate is None
    with t.request_trace("rid").span("gateway.request"):
        pass
    assert [s["name"] for s in t.spans("rid")] == ["gateway.request"]
    # The tiers: the model server's live spans are TraceAnnotations; the
    # gateway's tracer has no hook (that process never imports jax).
    import jax

    _, server, gateway, _ = traced_stack
    assert server.tracer.annotate is jax.profiler.TraceAnnotation
    assert gateway.tracer.annotate is None


@pytest.mark.parametrize(
    "name", ["server.read_body", "server.unpack", "server.respond"]
)
def test_front_spans_are_in_the_closed_vocabulary(name):
    assert name in trace_lib.SPAN_NAMES


def test_tracer_counts_dropped_spans_instead_of_silently_evicting():
    # The pre-PR-7 bug: spans past the cap vanished without a trace, so a
    # truncated waterfall read as missing instrumentation.
    t = trace_lib.Tracer("test", max_spans=4)
    for _ in range(10):
        t.record("rid", "s", trace_lib.now_s(), 0.001)
    info = t.trace_info("rid")
    assert len(info["spans"]) == 4
    assert info["spans_dropped"] == 6
    assert t.stats()["spans_dropped_total"] == 6


def test_tail_based_retention_protects_interesting_traces():
    t = trace_lib.Tracer("test", max_traces=4)
    for i in range(4):
        t.record(f"t{i}", "root", trace_lib.now_s(), 0.001)
    t.classify("t0", "error")   # oldest, but protected
    t.classify("t1", "shed")
    # Two new traces force two evictions: the ROUTINE t2/t3 go first even
    # though t0/t1 are older.
    t.record("t4", "root", trace_lib.now_s(), 0.001)
    t.record("t5", "root", trace_lib.now_s(), 0.001)
    assert t.spans("t0") is not None and t.spans("t1") is not None
    assert t.spans("t2") is None and t.spans("t3") is None
    assert t.evicted_traces == 2
    # All protected: the ring still stays bounded (oldest protected goes).
    t.classify("t4", "deadline")
    t.classify("t5", "slow")
    t.record("t6", "root", trace_lib.now_s(), 0.001)
    assert t.spans("t0") is None  # oldest protected was the fallback victim


def test_classify_upgrades_only():
    t = trace_lib.Tracer("test")
    t.record("rid", "root", trace_lib.now_s(), 0.001)
    t.classify("rid", "error")
    t.classify("rid", "slow")  # must not downgrade
    assert t.trace_info("rid")["retention_class"] == "error"
    t.classify("missing", "error")  # unknown trace: a no-op, not a KeyError


def test_retention_metrics_count_retained_and_dropped():
    from kubernetes_deep_learning_tpu.utils import metrics as metrics_lib

    r = metrics_lib.Registry()
    t = trace_lib.Tracer("test", max_traces=2, registry=r)
    t.record("a", "root", trace_lib.now_s(), 0.001)
    t.classify("a", "error")
    t.record("b", "root", trace_lib.now_s(), 0.001)
    t.classify("b", "routine")
    t.record("c", "root", trace_lib.now_s(), 0.001)  # evicts routine b
    page = r.render()
    assert 'kdlt_trace_retained_total{class="error"} 1' in page
    assert 'kdlt_trace_dropped_total{class="routine"} 1' in page
    assert t.spans("a") is not None


def test_retention_class_mapping():
    rc = trace_lib.retention_class
    assert rc(503) == "shed" and rc(504) == "shed"
    assert rc(500) == "error" and rc(-1) == "error"
    assert rc(200, deadline_exceeded=True) == "deadline"
    assert rc(200, slow=True) == "slow"
    assert rc(200) == "routine"
    assert rc(400) == "routine"  # the caller's fault is not worth retaining


def test_ensure_span_id_sanitizes():
    assert trace_lib.ensure_span_id(None) is None
    assert trace_lib.ensure_span_id("abc\r\nX: 1") == "abcX1"
    assert trace_lib.ensure_span_id("!!!") is None


def test_render_waterfall_smoke():
    t = trace_lib.Tracer("tier")
    rt = t.request_trace("rid")
    with rt.span("child"):
        pass
    t.record("rid", "root", trace_lib.now_s() - 0.01, 0.01, span_id=rt.span_id)
    out = trace_lib.render_waterfall(t.spans("rid"))
    assert "root" in out and "child" in out and "ms" in out


# --- e2e: the merged cross-tier waterfall ----------------------------------


def _make_stack(tmp, name, device_ms=5.0):
    spec = register_spec(
        ModelSpec(
            name=name,
            family="xception",  # never instantiated by StubEngine
            input_shape=(32, 32, 3),
            labels=("a", "b", "c"),
        )
    )
    root = tempfile.mkdtemp(prefix=f"kdlt-{name}-", dir=tmp)
    art.save_artifact(
        art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
    )
    server = ModelServer(
        root, port=0, buckets=(1, 2), max_delay_ms=1.0, host="127.0.0.1",
        batcher_impl="python",
        engine_factory=lambda a, **kw: StubEngine(
            a, device_ms_per_batch=device_ms, async_device=True, **kw
        ),
    )
    server.warmup()
    server.start()
    return spec, server


@pytest.fixture(scope="module")
def traced_stack(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("trace-e2e"))
    spec, server = _make_stack(tmp, "trace-e2e-stub")
    gateway = Gateway(
        serving_host=f"127.0.0.1:{server.port}", model=spec.name, port=0,
        host="127.0.0.1",
        # The response cache would serve repeat fixture URLs without an
        # upstream hop at all; these tests trace the FULL path (the cached
        # path's gateway.cache span is covered by test_cache.py).
        cache=False,
    )
    gateway.start()

    img_dir = tmp_path_factory.mktemp("trace-img")
    from PIL import Image

    rng = np.random.default_rng(0)
    Image.fromarray(
        rng.integers(0, 256, size=(48, 48, 3), dtype=np.uint8)
    ).save(img_dir / "img.png")

    class Quiet(SimpleHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

    img_httpd = HTTPServer(
        ("127.0.0.1", 0), partial(Quiet, directory=str(img_dir))
    )
    threading.Thread(target=img_httpd.serve_forever, daemon=True).start()
    img_url = f"http://127.0.0.1:{img_httpd.server_address[1]}/img.png"

    yield spec, server, gateway, img_url

    gateway.shutdown()
    server.shutdown()
    img_httpd.shutdown()


def _merged_trace(gateway, rid, want_names=(), timeout_s=3.0):
    """Poll the gateway's merged /debug/trace/<rid> until the expected span
    names appear (the model tier's root span records microseconds after its
    response is sent, so an immediate fetch can race it)."""
    base = f"http://127.0.0.1:{gateway.port}"
    deadline = time.monotonic() + timeout_s
    spans: list = []
    while time.monotonic() < deadline:
        r = requests.get(f"{base}/debug/trace/{rid}", timeout=5)
        if r.status_code == 200:
            spans = r.json()["spans"]
            names = [s["name"] for s in spans]
            if all(w in names for w in want_names):
                return spans
        time.sleep(0.02)
    return spans


def test_single_request_merged_waterfall(traced_stack):
    """The tentpole acceptance: >= 8 spans, correct nesting, monotonic
    non-overlapping pipeline-stage intervals, trace headers on the wire."""
    _, _, gateway, img_url = traced_stack
    rid = "waterfall-req-1"
    r = requests.post(
        f"http://127.0.0.1:{gateway.port}/predict",
        json={"url": img_url},
        headers={REQUEST_ID_HEADER: rid},
        timeout=30,
    )
    assert r.status_code == 200, r.text
    assert r.headers[REQUEST_ID_HEADER] == rid
    # Server-Timing-style summary on the response, root span included
    # (the transports build it after handle_predict records the root).
    assert "gateway.request;dur=" in r.headers[TRACE_HEADER]

    spans = _merged_trace(
        gateway, rid, want_names=("server.request", "gateway.request")
    )
    assert len(spans) >= 8, [s["name"] for s in spans]
    by_name = {s["name"]: s for s in spans}
    by_id = {s["span_id"]: s for s in spans}

    # Exactly one root: the gateway's request span.
    roots = [s for s in spans if s.get("parent_id") not in by_id]
    assert [s["name"] for s in roots] == ["gateway.request"]

    # Cross-tier nesting: the model tier's root hangs off the exact
    # gateway upstream attempt that carried it.
    up = by_name["gateway.upstream"]
    assert by_name["server.request"]["parent_id"] == up["span_id"]
    assert up["parent_id"] == by_name["gateway.request"]["span_id"]
    assert up["tags"]["winner"] is True
    assert up["tags"]["status"] == 200

    # The model tier's own nesting: admission/decode/predict under the
    # request root, batcher + pipeline stages under the predict span.
    srv_root = by_name["server.request"]["span_id"]
    predict = by_name["server.predict"]
    assert predict["parent_id"] == srv_root
    assert by_name["server.admission"]["parent_id"] == srv_root
    assert by_name["batcher.queue_wait"]["parent_id"] == predict["span_id"]

    stages = [
        by_name[f"pipeline.{s}"]
        for s in ("enqueue_wait", "dispatch", "execute", "readback")
    ]
    for st in stages:
        assert st["parent_id"] == predict["span_id"]
        assert st["tier"] == "model-server"
    # Monotonic, non-overlapping, contiguous-in-order intervals: each
    # stage starts exactly where its predecessor ended (shared perf-counter
    # boundaries), and all sit inside the predict span's window.  Slack:
    # start_s rounds to 1e-6 s and dur_ms to 1e-6 s (trace.py to_dict), so
    # end_a vs start_b carries up to three half-ulp roundings -- 1e-6 was
    # exactly reachable and flaked (~1/500 runs).
    for a, b in zip(stages, stages[1:]):
        end_a = a["start_s"] + a["dur_ms"] / 1e3
        assert b["start_s"] >= end_a - 2e-6, (a["name"], b["name"])
    assert stages[0]["start_s"] >= predict["start_s"] - 2e-6
    # Sibling gateway spans are sequential too (admission, preprocess,
    # then the upstream hop).
    gw_seq = [by_name["gateway.admission"], by_name["gateway.preprocess"], up]
    for a, b in zip(gw_seq, gw_seq[1:]):
        assert b["start_s"] >= a["start_s"] + a["dur_ms"] / 1e3 - 2e-6


def test_trace_endpoint_unknown_rid_404(traced_stack):
    _, server, gateway, _ = traced_stack
    for port in (gateway.port, server.port):
        r = requests.get(
            f"http://127.0.0.1:{port}/debug/trace/never-seen-rid", timeout=5
        )
        assert r.status_code == 404


def test_client_fetch_trace_and_render(traced_stack):
    from kubernetes_deep_learning_tpu.serving.client import (
        fetch_trace,
        predict_url,
    )

    _, _, gateway, img_url = traced_stack
    base = f"http://127.0.0.1:{gateway.port}"
    stats: dict = {}
    predict_url(base, img_url, stats=stats)
    assert stats["request_id"]
    assert "gateway.request;dur=" in stats["trace_summary"]
    spans = _merged_trace(gateway, stats["request_id"],
                          want_names=("server.request",))
    out = trace_lib.render_waterfall(spans)
    assert "gateway.request" in out and "[model-server]" in out


def test_model_tier_response_carries_trace_header(traced_stack):
    from kubernetes_deep_learning_tpu.serving import protocol

    spec, server, _, _ = traced_stack
    img = np.zeros((1, 32, 32, 3), np.uint8)
    r = requests.post(
        f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict",
        data=protocol.encode_predict_request(img),
        headers={
            "Content-Type": protocol.MSGPACK_CONTENT_TYPE,
            REQUEST_ID_HEADER: "direct-model-req",
            PARENT_SPAN_HEADER: "cafe0123",
        },
        timeout=30,
    )
    assert r.status_code == 200
    assert "server.predict;dur=" in r.headers[TRACE_HEADER]
    # The propagated parent became the model-tier root's parent.
    spans = requests.get(
        f"http://127.0.0.1:{server.port}/debug/trace/direct-model-req",
        timeout=5,
    ).json()["spans"]
    root = next(s for s in spans if s["name"] == "server.request")
    assert root["parent_id"] == "cafe0123"


def test_msgpack_predict_waterfall_has_the_front_spans(traced_stack):
    """server.decode divides into the socket read and the unpack; what
    follows the predict is server.respond -- the root's self time is then
    header parsing and bookkeeping only."""
    from kubernetes_deep_learning_tpu.serving import protocol

    spec, server, _, _ = traced_stack
    rid = "front-spans-req"
    r = requests.post(
        f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict",
        data=protocol.encode_predict_request(np.zeros((2, 32, 32, 3), np.uint8)),
        headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE,
                 REQUEST_ID_HEADER: rid},
        timeout=30,
    )
    assert r.status_code == 200
    deadline = time.monotonic() + 3.0
    while True:  # the root span records just after the response went out
        spans = requests.get(
            f"http://127.0.0.1:{server.port}/debug/trace/{rid}", timeout=5
        ).json()["spans"]
        by = {s["name"]: s for s in spans}
        if "server.request" in by or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    for name in ("server.read_body", "server.unpack", "server.respond",
                 "server.decode", "server.predict", "server.request"):
        assert name in by, (name, sorted(by))
    decode = by["server.decode"]
    for child in ("server.read_body", "server.unpack"):
        assert by[child]["parent_id"] == decode["span_id"]
    assert (by["server.read_body"]["dur_ms"] + by["server.unpack"]["dur_ms"]
            <= decode["dur_ms"] + 1e-3)
    root = by["server.request"]
    assert by["server.respond"]["parent_id"] == root["span_id"]
    assert by["server.respond"]["start_s"] >= by["server.predict"]["start_s"]
    children = sum(by[n]["dur_ms"] for n in (
        "server.admission", "server.decode", "server.predict", "server.respond"))
    assert children <= root["dur_ms"] + 1e-3


def test_hedged_request_trace_shows_both_attempts_with_winner(
    tmp_path_factory,
):
    """Replica A is slow (400 ms device), B fast; with a 60 ms hedge delay
    the hedge fires, B answers first, and the trace must show BOTH
    gateway.upstream attempt spans -- the hedge marked winner."""
    tmp = str(tmp_path_factory.mktemp("trace-hedge"))
    spec, slow = _make_stack(tmp, "trace-hedge-stub", device_ms=400.0)
    _, fast = _make_stack(tmp, "trace-hedge-stub", device_ms=5.0)
    gateway = Gateway(
        serving_host=f"127.0.0.1:{slow.port},127.0.0.1:{fast.port}",
        model=spec.name, port=0, host="127.0.0.1",
        hedge_delay_ms=60.0, probe_interval_s=0.0,
    )
    gateway.start()
    try:
        from kubernetes_deep_learning_tpu.serving import protocol

        rid = "hedged-req-1"
        img = np.zeros((1, 32, 32, 3), np.uint8)
        body = protocol.encode_predict_request(img)
        rt = gateway.tracer.request_trace(rid)
        t0 = time.monotonic()
        logits, labels = gateway._predict_batch(img, rid, trace=rt)
        took = time.monotonic() - t0
        assert len(logits) == 1 and list(labels) == list(spec.labels)
        del body
        # The hedge won: the request finished far below the slow replica's
        # 400 ms device time.
        assert took < 0.35, took

        # The losing primary's span records when its (abandoned) response
        # eventually lands; poll for both attempts.
        deadline = time.monotonic() + 3.0
        attempts = []
        while time.monotonic() < deadline:
            spans = gateway.tracer.spans(rid) or []
            attempts = [s for s in spans if s["name"] == "gateway.upstream"]
            if len(attempts) == 2:
                break
            time.sleep(0.02)
        assert len(attempts) == 2, attempts
        by_role = {s["tags"]["role"]: s for s in attempts}
        assert set(by_role) == {"primary", "hedge"}
        assert by_role["hedge"]["tags"].get("winner") is True
        assert "winner" not in by_role["primary"]["tags"]
        assert by_role["primary"]["tags"]["replica"].endswith(str(slow.port))
        assert by_role["hedge"]["tags"]["replica"].endswith(str(fast.port))
    finally:
        gateway.shutdown()
        slow.shutdown()
        fast.shutdown()


# --- /debug/profile --------------------------------------------------------


def test_debug_profile_get_captures_into_profile_dir(
    tmp_path, monkeypatch, traced_stack
):
    """GET /debug/profile?seconds=N captures a jax.profiler trace into a
    fresh dir under $KDLT_PROFILE_DIR (wired here via profile_base since
    the fixture server predates the monkeypatch)."""
    import os

    _, server, _, _ = traced_stack
    profile_dir = str(tmp_path / "profiles")
    monkeypatch.setattr(server, "_profile_base", profile_dir)
    r = requests.get(
        f"http://127.0.0.1:{server.port}/debug/profile?seconds=0.05",
        timeout=30,
    )
    assert r.status_code == 200, r.text
    out = r.json()
    assert out["seconds"] == 0.05
    # by default the host tracer is off too: the device's planes alone
    assert out["annotations"] is False and out["start_took_s"] >= 0
    assert out["trace_dir"].startswith(profile_dir)
    assert os.path.isdir(out["trace_dir"])
    # jax.profiler writes its plugin tree into the capture dir.
    assert os.listdir(out["trace_dir"]), "profile capture produced no files"

    # Bad input stays a 400, never a capture.
    r = requests.get(
        f"http://127.0.0.1:{server.port}/debug/profile?seconds=999", timeout=30
    )
    assert r.status_code == 400


def test_debug_profile_reply_times_itself_and_the_trace_holds_the_dispatch(
    tmp_path, monkeypatch, traced_stack
):
    """The capture's own cost is in the reply (start_took_s, stop_took_s),
    and the written trace holds the dispatcher's live regions as host
    annotations beside whatever the device planes hold."""
    import glob

    import jax

    from kubernetes_deep_learning_tpu.serving import protocol

    spec, server, _, _ = traced_stack
    monkeypatch.setattr(server, "_profile_base", str(tmp_path / "profiles"))
    reply = {}

    def capture():
        reply["r"] = requests.get(
            f"http://127.0.0.1:{server.port}/debug/profile?seconds=0.2"
            "&annotations=1",
            timeout=60,
        )

    th = threading.Thread(target=capture)
    th.start()
    body = protocol.encode_predict_request(np.zeros((1, 32, 32, 3), np.uint8))
    t_end = time.monotonic() + 10.0
    while th.is_alive() and time.monotonic() < t_end:
        r = requests.post(
            f"http://127.0.0.1:{server.port}/v1/models/{spec.name}:predict",
            data=body,
            headers={"Content-Type": protocol.MSGPACK_CONTENT_TYPE},
            timeout=30,
        )
        assert r.status_code == 200
    th.join(timeout=60)
    assert not th.is_alive()
    assert reply["r"].status_code == 200, reply["r"].text
    out = reply["r"].json()
    assert out["seconds"] == 0.2 and out["annotations"] is True
    assert out["start_took_s"] >= 0 and out["stop_took_s"] >= 0
    files = glob.glob(out["trace_dir"] + "/plugins/profile/*/*.xplane.pb")
    assert files, "the capture wrote no xplane file"
    names = {
        ev.name
        for plane in jax.profiler.ProfileData.from_file(files[0]).planes
        for line in plane.lines
        for ev in line.events
    }
    for want in ("pipeline.dispatch", "pipeline.readback", "server.read_body",
                 "server.predict"):
        assert want in names, (want, sorted(names)[:40])
    # the Python tracer is off: no frame of this file's or the server's code
    assert not [n for n in names if n.startswith("$")]


def test_profile_dir_env_is_honored(monkeypatch, tmp_path):
    from kubernetes_deep_learning_tpu.serving import model_server as ms

    monkeypatch.setenv(ms.PROFILE_DIR_ENV, str(tmp_path / "via-env"))
    spec = register_spec(
        ModelSpec(
            name="profile-env-stub", family="xception",
            input_shape=(16, 16, 3), labels=("a",),
        )
    )
    root = str(tmp_path / "models")
    art.save_artifact(
        art.version_dir(root, spec.name, 1), spec, {"params": {}}, None, {}
    )
    server = ModelServer(
        root, port=0, buckets=(1,), host="127.0.0.1",
        engine_factory=StubEngine, use_batcher=False,
    )
    try:
        assert server._profile_base == str(tmp_path / "via-env")
    finally:
        server.shutdown()


# --- structured logging (KDLT_LOG_FORMAT=json) -----------------------------


def test_log_request_json_format(monkeypatch, capsys):
    from kubernetes_deep_learning_tpu.serving.tracing import log_request

    monkeypatch.setenv("KDLT_LOG_FORMAT", "json")
    t0 = time.perf_counter()
    log_request(
        "gateway predict", "rid-1", status=200, t0=t0, span_id="abcd1234",
        urls=3,
    )
    line = capsys.readouterr().out.strip()
    rec = json.loads(line)
    assert rec["rid"] == "rid-1" and rec["trace_id"] == "rid-1"
    assert rec["tier"] == "gateway predict"
    assert rec["status"] == 200 and rec["span_id"] == "abcd1234"
    assert rec["urls"] == 3 and isinstance(rec["dur_ms"], float)


def test_log_request_default_format_unchanged(monkeypatch, capsys):
    from kubernetes_deep_learning_tpu.serving.tracing import log_request

    monkeypatch.delenv("KDLT_LOG_FORMAT", raising=False)
    log_request("tier", "rid-2", status=500, t0=time.perf_counter())
    out = capsys.readouterr().out
    assert out.startswith("[rid=rid-2] tier status=500 dur_ms=")


# --- coverage: the spans account for where a request's time went ------------


def test_spans_account_for_request_wall_time(tmp_path_factory, traced_stack):
    """The root span of the merged waterfall covers >= 95% of the wall time
    the client measured, over a dozen sequential requests, and each
    waterfall names every stage: spans that cannot say where a stub
    request's time went will not say where a real one's did."""
    from kubernetes_deep_learning_tpu.serving.admission import DEADLINE_HEADER

    _, _, _, img_url = traced_stack
    # A stack of its own at 80 ms a batch: at the shared fixture's 5 ms the
    # loopback's own connect and parse would be a tenth of the request.
    tmp = str(tmp_path_factory.mktemp("trace-coverage"))
    spec, server = _make_stack(tmp, "trace-coverage-stub", device_ms=80.0)
    gateway = Gateway(
        serving_host=f"127.0.0.1:{server.port}", model=spec.name, port=0,
        host="127.0.0.1", cache=False,
    )
    gateway.start()
    base = f"http://127.0.0.1:{gateway.port}"
    coverage, span_counts, names = [], [], set()
    try:
        with requests.Session() as session:
            # Untimed: spec discovery, connection set-up, first dispatch.
            session.post(f"{base}/predict", json={"url": img_url}, timeout=30)
            for i in range(12):
                rid = f"coverage-{i}"
                t0 = time.monotonic()
                r = session.post(
                    f"{base}/predict", json={"url": img_url},
                    headers={REQUEST_ID_HEADER: rid, DEADLINE_HEADER: "5000.0"},
                    timeout=30,
                )
                wall_s = time.monotonic() - t0
                assert r.status_code == 200, r.text
                spans = _merged_trace(
                    gateway, rid, want_names=("server.request", "gateway.request")
                )
                span_counts.append(len(spans))
                names.update(s["name"] for s in spans)
                root_ms = next(
                    s["dur_ms"] for s in spans if s["name"] == "gateway.request"
                )
                coverage.append(min(1.0, root_ms / 1e3 / wall_s))
    finally:
        gateway.shutdown()
        server.shutdown()
    assert min(span_counts) >= 8, span_counts
    assert {"gateway.request", "server.predict", "pipeline.readback"} <= names
    assert float(np.mean(coverage)) >= 0.95, coverage
