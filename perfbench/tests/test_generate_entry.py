"""The token-stream entry on the CPU.  First its parts against a scripted
stream server inside the test: frames stamped on arrival, the lengths a
traffic file states, the window's edges in both loops, a failed stream in
every latency, every kind of wrong answer.  Then whole rehearsed runs from
a scratch manifest around the stand-in lane (``standin/``): ``correct``
true in both loops, and false with the timed path broken where an answer
is produced.  Last, the stand-in's plain forward against the program's own
lane, which it mirrors."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import run as R
from perfbench import tokens, traffic
from perfbench.tests.standin import build as standin

BIG_SEED = 2**31 + 2026


# --- a scripted stream server ---------------------------------------------------------


@pytest.fixture()
def scripted():
    """Serves, for any POST, the frames of ``script``: (delay before it in
    seconds, payload) pairs, chunk by chunk."""
    script: list = []

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            if script and script[0] == "refuse":
                body = b'{"error": "queue full"}'
                self.send_response(503)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                return self.wfile.write(body)
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            for delay, payload in script:
                time.sleep(delay)
                data = b"data: " + json.dumps(payload).encode() + b"\n\n"
                self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
                self.wfile.flush()
            self.wfile.write(b"0\r\n\r\n")

        def log_message(self, fmt, *a):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}", script
    httpd.shutdown()


def frame(i, token, k=2):
    return {"index": i, "token": token, "top_ids": [token, token + 1][:k],
            "top_logits": [2.0, 1.0][:k]}


def test_frames_are_stamped_as_they_arrive(scripted):
    base, script = scripted
    script += [(0.10, frame(0, 7)), (0.05, frame(1, 8)), (0.05, frame(2, 9)),
               (0.0, {"done": True, "tokens": 3})]
    client = tokens.ServerGenerate(base, "m", [b"{}"])
    conn_box = [None]
    for rid in ("a", "b"):          # the second on the kept connection
        o = traffic.Outcome(0, rid, 0.0, (0,))
        t0 = time.monotonic()
        client.send(conn_box, o, 10.0)
        s = o.stream
        assert o.status == 200 and s.finished and s.tokens == [7, 8, 9] and s.prompt == 0
        assert s.indices == [0, 1, 2] and s.top_ids[1] == [8, 9] and s.top_logits[2] == [2.0, 1.0]
        tokens.settle([o], t0)
        first, gaps = s.arrivals[0], np.diff(s.arrivals)
        assert 0.09 < first < 0.2 and all(0.04 < g < 0.12 for g in gaps)
        assert tokens.structure_error(s, 3, 2, 100) == ""
    conn_box[0].close()
    script[:] = ["refuse"]
    o = traffic.Outcome(1, "c", 0.0, (0,))
    client.send([None], o, 10.0)
    assert o.status == 503 and "queue full" in o.error and o.stream.tokens == []


# --- what a traffic file states --------------------------------------------------------


def test_lengths_are_the_traffic_files_and_seeds_share_the_work():
    short = {"choice": [[8, 48, 1]]}
    both = {"choice": [[8, 48, 3], [400, 500, 1]]}
    v = tokens.band_values(short, 41)
    assert sorted(v) == list(range(8, 49))              # uniform inside the band
    v = tokens.band_values(both, 40)
    assert sum(x <= 48 for x in v) == 30 and sum(x >= 400 for x in v) == 10
    assert list(tokens.band_values({"choice": [[64, 64, 1]]}, 5)) == [64] * 5
    with pytest.raises(ValueError):
        tokens.band_values({"choice": [[9, 8, 1]]}, 4)

    mix = {"pool": 24, "prompt_tokens": both, "output_tokens": {"choice": [[16, 64, 1]]}}
    a, b, c = (tokens.make_pool(s, mix, 1000) for s in (BIG_SEED, BIG_SEED, 7))
    assert a == b and a != c
    pairs = lambda pool: sorted((len(p.ids), p.max_new_tokens) for p in pool)  # noqa: E731
    assert pairs(a) == pairs(c)                         # the same work, another order
    assert [len(p.ids) for p in a] != [len(p.ids) for p in c]
    assert all(0 <= t < 1000 for p in a for t in p.ids)
    body = json.loads(tokens.encode_body(a[0], 8))
    assert body == {"token_ids": list(a[0].ids), "max_new_tokens": a[0].max_new_tokens,
                    "ignore_eos": True, "top_logits": 8, "stream": True}


# --- from stamps to quantities --------------------------------------------------------


def outcome(index, due, arrivals, done=None):
    o = traffic.Outcome(index, f"r{index}", due, (0,), sent_s=due,
                        done_s=arrivals[-1] if done is None else done, status=200)
    o.stream = tokens.Stream(0, arrivals=list(arrivals), finished=True)
    return o


def test_quantities_and_the_windows_edges():
    # a 10 s window.  Stream 0 began in the lead-in and has two tokens
    # before the window's start; stream 1 lies inside; stream 2 runs past
    # the window's end; stream 3 failed.
    w = [outcome(0, -0.5, [-0.30, -0.10, 0.10, 0.30]),
         outcome(1, 2.0, [2.05, 2.15, 2.25]),
         outcome(2, 9.5, [9.70, 9.90, 10.10, 10.30]),
         outcome(3, 5.0, [5.1], done=5.2)]
    good = {0, 1, 2}
    q = tokens.quantities(w, good, 10.0, R.FAILED_LATENCY_MS, R.percentile)
    assert q["output_tokens_per_s"] == (2 + 3 + 2) / 10.0      # by arrival, good only
    ttft = sorted([200.0, 50.0, 200.0, 120_000.0])
    assert q["ttft_p50_ms"] == pytest.approx(ttft[1]) and q["ttft_p95_ms"] == 120_000.0
    gaps = sorted([200.0] * 3 + [100.0] * 2 + [200.0] * 3 + [120_000.0])
    assert q["itl_p50_ms"] == pytest.approx(gaps[4]) and q["itl_p95_ms"] == 120_000.0
    assert q["latency_p50_ms"] == pytest.approx(800.0) and q["latency_p95_ms"] == 120_000.0
    assert set(q) == set(tokens.QUANTITIES)
    # all good: the tail is a stream's, not the failure's
    q = tokens.quantities(w[:3], good, 10.0, R.FAILED_LATENCY_MS, R.percentile)
    assert q["itl_p95_ms"] == pytest.approx(200.0) and q["ttft_p95_ms"] == pytest.approx(200.0)


def test_the_window_in_both_loops(scripted, tmp_path):
    """Closed loop: the streams that ended after the window's start,
    whenever they were sent.  Open loop: the requests due in it."""
    base, script = scripted
    script += [(0.02, frame(i, i, k=1)) for i in range(4)] + [(0.0, {"done": True})]

    class Run:
        server, model, seed, seconds, vocab = base, "m", BIG_SEED, 1.0, 100
        pool = [tokens.Prompt((1, 2, 3), 4)] * 3
        bodies = [b"{}"] * 3
    entry = M.load_module(M.HERE, "entries", "server-generate")
    for mix, inside in (
            ({"generator": "closed", "callers": 2, "lead_in_s": 0.3},
             lambda o: o.done_s >= 0),
            ({"generator": "open-poisson", "rate_per_s": 20.0, "workers": 8, "lead_in_s": 0.3},
             lambda o: o.due_s >= 0)):
        run = Run()
        run.mix = dict(mix, top_logits=1)
        started = []
        entry.drive(run, started.append)
        assert started == [run.t_zero]
        assert run.window == [o for o in run.outcomes if inside(o)]
        assert len(run.window) < len(run.outcomes)          # the lead-in sent some
        assert run.formed == run.window and not run.wrong and not run.unanswered
        assert all(o.status == 200 and o.stream.finished for o in run.outcomes)
        lead = [o for o in run.outcomes if o.due_s < 0]
        assert lead and all(-0.4 < a < 1.6 for o in run.outcomes for a in o.stream.arrivals)
        if mix["generator"] == "open-poisson":
            assert len(run.window) == 20 and not any(o in run.window for o in lead)
        else:   # a stream sent in the lead-in and ended inside the window counts
            assert any(o.sent_s < 0 <= o.done_s for o in run.window)


WRONG = {
    "no done frame": dict(finished=False),
    "a token short": dict(tokens=[5, 6], indices=[0, 1], top_ids=[[5, 1], [6, 1]],
                          top_logits=[[2.0, 1.0]] * 2),
    "frames out of order": dict(indices=[0, 2, 1]),
    "an id outside the slice": dict(tokens=[5, 6, 100], top_ids=[[5, 1], [6, 1], [100, 1]]),
    "a top id outside the slice": dict(top_ids=[[5, 1], [6, 100], [7, 1]]),
    "a logit that is not finite": dict(top_logits=[[2.0, 1.0], [float("nan"), 1.0], [2.0, 1.0]]),
    "one top logit too few": dict(top_ids=[[5, 1], [6], [7, 1]]),
    "no top logits at all": dict(top_ids=[None] * 3, top_logits=[None] * 3),
    "not the token of the largest logit": dict(top_ids=[[5, 1], [1, 6], [7, 1]]),
}


@pytest.mark.parametrize("case", sorted(WRONG))
def test_every_wrong_answer(case):
    right = dict(arrivals=[0.1, 0.2, 0.3], indices=[0, 1, 2], tokens=[5, 6, 7],
                 top_ids=[[5, 1], [6, 1], [7, 1]], top_logits=[[2.0, 1.0]] * 3, finished=True)
    assert tokens.structure_error(tokens.Stream(0, **right), 3, 2, 100) == ""
    assert tokens.structure_error(tokens.Stream(0, **{**right, **WRONG[case]}), 3, 2, 100)


def test_the_sample_is_the_seeds_and_holds_the_longest():
    items = list(range(40))
    length = lambda i: 100 if i == 17 else i % 7        # noqa: E731
    a = tokens.sample(BIG_SEED, items, 6, length)
    assert a == tokens.sample(BIG_SEED, items, 6, length) and len(set(a)) == 6 and 17 in a
    assert a != tokens.sample(BIG_SEED + 1, items, 6, length)
    assert tokens.sample(BIG_SEED, items[:4], 6, length) == items[:4]


def test_stream_errors_by_hand():
    s = tokens.Stream(0, tokens=[3, 1], top_logits=[[4.0, 2.0], [1.0, 0.5]])
    ref_top = np.array([[4.5, 2.0], [1.0, 0.25]])
    err, gap = tokens.stream_errors(s, ref_top, np.array([4.5, 3.0]), np.array([4.5, 1.0]), 10.0)
    assert err == pytest.approx(0.05) and gap == pytest.approx(0.2)


# --- whole rehearsed runs around the stand-in lane ---------------------------------------

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def lane(tmp_path_factory):
    root = tmp_path_factory.mktemp("standin")
    standin.build(str(root))
    m = M.Manifest(str(root))
    m.validate()
    return m, str(root / "work")


def drive(lane, cell, seed, trace=False):
    manifest, work = lane
    run = standin.LaneRun(manifest, manifest.cell(cell), seed, 3.0, trace, platform="cpu",
                          work_root=work)
    try:
        return run, run.run()
    finally:
        run.children.kill_all()


@pytest.mark.parametrize("cell", ["standin-closed", "standin-open"])
def test_whole_run_in_both_loops(lane, cell, capfd):
    run, line = drive(lane, cell, BIG_SEED, trace=cell == "standin-closed")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "compared"
    c = line["compared"]
    assert list(c) == ["logit_err", "argmax_gap", "wrong_answers", "unanswered",
                       "tokens_compared"]
    assert c["tokens_compared"]["value"] == 6 * 64         # compare_requests x output tokens
    assert 0 < c["logit_err"]["value"] < 1e-4 and c["argmax_gap"]["value"] < 1e-4
    e2e = run.end_to_end()
    assert set(e2e) == set(tokens.QUANTITIES) | {"setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    assert e2e["ttft_p50_ms"]["value"] <= e2e["latency_p50_ms"]["value"]
    if cell == "standin-open":
        assert line["attempted"] == 18                      # 6 requests a second for 3 s
        assert line["metrics"] == e2e
    else:                       # traced: what the readers found, no device on the CPU
        assert set(line["metrics"]) == {"lane_tokens_per_s"}
    R.report(line)
    out, err = capfd.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert "logit_err=" in err.strip().splitlines()[-1]


@pytest.mark.parametrize("fault, number", [("top_logits", "logit_err"), ("cache", "logit_err")])
def test_a_broken_lane_is_not_correct(lane, monkeypatch, fault, number):
    """The timed path broken where an answer is produced: the served top
    logits altered, or one cached position's keys zeroed once the prefill
    has run, so that the prefill answers rightly and every decode step
    after it does not."""
    monkeypatch.setenv("LANE_FAULT", fault)
    _run, line = drive(lane, "standin-closed", 77)
    c = line["compared"]
    assert line["correct"] is False and line["failed"] > 0
    assert c[number]["value"] > c[number]["limit"]
    assert c["wrong_answers"]["value"] == 0 and c["unanswered"]["value"] == 0


def test_the_stand_in_mirrors_the_programs_lane():
    """The program's generative lane, prefill then decode through its paged
    cache, emits the tokens the stand-in's plain full forward puts first:
    one seed gives both the same weights, draw for draw."""
    import jax

    from kubernetes_deep_learning_tpu.runtime import decode
    from perfbench.tests.standin import byte_lm

    config = {"d_model": 32, "n_layers": 2, "n_heads": 2, "vocab_size": decode.VOCAB_SIZE}
    engine = decode.DecodeEngine("gen-default", seed=11)
    served = engine.decode_solo("a toy prompt", 24)
    if decode.EOS_TOKEN in served:      # the lane stops there; so does the comparison
        served = served[:served.index(decode.EOS_TOKEN) + 1]
    prompt = decode.encode_prompt("a toy prompt")
    ids = np.asarray(prompt + served[:-1], np.int32)
    logits = np.asarray(jax.jit(lambda w, t: byte_lm.forward(w, t, config))(
        byte_lm.build_params(11, config), ids))[len(prompt) - 1:]
    assert len(served) >= 8
    assert logits.argmax(axis=1).tolist() == served
