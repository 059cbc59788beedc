"""A stand-in for the model server's generative lane, for the token entry's
tests and its proof of plumbing: added beside ``children/serve.py`` in a
scratch copy of the benchmark, it is started in that file's place
(``build.py::LaneRun``) with the same arguments and environment, and speaks
what a run asks of the program -- ``/readyz``, ``GET /v1/models``,
``/metrics``, the side port's ``/device`` and ``/trace``
(``children/serve.py``'s own) and the wire of
``perfbench/tokens.py`` -- over ``byte_lm.py``'s prefill and decode step,
one jitted program each, a request a thread, ``slots`` at a time.  Nothing
of the program under test runs here, so no number it gives is the
program's.

``LANE_FAULT`` breaks the timed path where an answer is produced:
``top_logits`` scales the served logits of every fifth step by 1.5;
``cache`` zeroes the keys of the prompt's second position once the prefill
has run (prefill right, decode wrong).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--models", required=True)
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--decode", action="store_true")
    p.add_argument("--platform", required=True)
    p.add_argument("--profile-dir")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from perfbench import reference
    from perfbench.children import serve   # the side port, as committed

    lm = reference.load("byte_lm")
    model = os.environ["KDLT_DECODE_MODEL"]
    with open(os.path.join(args.models, model, "lane.json")) as f:
        lane = json.load(f)
    seed, config = lane["seed"], lane["config"]
    slots = int(os.environ["KDLT_DECODE_SLOTS"])
    page_size = int(os.environ["KDLT_DECODE_PAGE_SIZE"])
    max_pages = int(os.environ["KDLT_DECODE_MAX_PAGES"])
    buckets = sorted(int(b) for b in os.environ["KDLT_DECODE_PROMPT_BUCKETS"].split(","))
    context = page_size * max_pages
    fault = os.environ.get("LANE_FAULT", "")
    if jax.devices()[0].platform != args.platform:
        print(f"no {args.platform} device", file=sys.stderr)
        return 1
    params = jax.device_put(lm.build_params(seed, config))
    prefill = jax.jit(lambda w, ids, n: lm.prefill(w, ids, n, config, context))
    step = jax.jit(lambda w, cache, pos, tok: lm.decode_step(w, cache, pos, tok, config))
    for b in buckets:        # every program compiled before /readyz
        cache, _ = prefill(params, np.zeros(b, np.int32), np.int32(1))
    jax.block_until_ready(step(params, cache, np.int32(1), np.int32(0)))
    free = threading.Semaphore(slots)
    counters = {"tokens": 0}
    t_start = time.monotonic()

    def generate(ids: list[int], n: int, k: int):
        """Yields (token, top ids, top logits) a step."""
        bucket = next(b for b in buckets if len(ids) <= b)
        padded = np.zeros(bucket, np.int32)
        padded[:len(ids)] = ids
        cache, logits = prefill(params, padded, np.int32(len(ids)))
        if fault == "cache":
            cache = cache.at[:, 0, 1].set(0.0)
        for j in range(n):
            logits = np.asarray(logits)
            top = np.argsort(-logits, kind="stable")[:k]
            served = logits[top] * (1.5 if fault == "top_logits" and j % 5 == 4 else 1.0)
            yield int(top[0]), top.tolist(), served.tolist()
            if j + 1 < n:
                cache, logits = step(params, cache, np.int32(len(ids) + j), np.int32(top[0]))

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def reply(self, status: int, body: bytes, ctype: str = "application/json"):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/readyz":
                return self.reply(200, b"ready", "text/plain")
            if self.path == "/v1/models":
                return self.reply(200, json.dumps({model: {
                    "platform": args.platform,
                    "decode": {"slots": slots, "page_size": page_size,
                               "max_pages": max_pages, "prompt_buckets": buckets,
                               "vocab_size": config["vocab_size"]}}}).encode())
            if self.path == "/metrics":
                return self.reply(200, (
                    "kdlt_xla_compile_requests_total 0\n"
                    f"lane_tokens_total {counters['tokens']}\n"
                    f"lane_seconds_total {time.monotonic() - t_start}\n").encode(),
                    "text/plain")
            self.reply(404, b"{}")

        def do_POST(self):
            body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
            ids, n = body["token_ids"], int(body["max_new_tokens"])
            if (self.path != f"/v1/models/{model}:generate" or not body.get("stream")
                    or not body.get("ignore_eos") or len(ids) + n > context
                    or any(not 0 <= t < config["vocab_size"] for t in ids)):
                return self.reply(400, b'{"error": "bad request"}')
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            def frame(payload: dict) -> None:
                data = b"data: " + json.dumps(payload, separators=(",", ":")).encode() + b"\n\n"
                self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
                self.wfile.flush()

            with free:
                for j, (tok, ids_k, logits_k) in enumerate(
                        generate(ids, n, int(body["top_logits"]))):
                    frame({"index": j, "token": tok, "top_ids": ids_k, "top_logits": logits_k})
                    counters["tokens"] += 1
            frame({"done": True, "tokens": n, "finish_reason": "length"})
            self.wfile.write(b"0\r\n\r\n")

        def log_message(self, fmt, *a):
            pass

    serve.start_side_port(int(os.environ["PERFBENCH_DEVICE_PORT"]))
    httpd = ThreadingHTTPServer(("127.0.0.1", args.port), Handler)
    httpd.daemon_threads = True
    signal.signal(signal.SIGTERM, lambda *_: threading.Thread(target=httpd.shutdown).start())
    httpd.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
