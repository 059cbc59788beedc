"""The model server, started as the program starts it, with one addition:
a side port for what only the process that holds the chip can do.

``GET /device``: the device as JAX reports it and its ``memory_stats()``
(the server's status page names the device but no memory figure).
``GET /trace?seconds=N``: a profiler trace of N seconds of the device's
planes alone -- Python and host tracers off -- into ``PERFBENCH_TRACE_DIR``.
The program's own ``/debug/profile`` starts the profiler with its defaults,
Python tracer on: on the chip that stalled every thread for ~2 s at the
start, ran on ~5 s past the end and wrote 200 MB for 3 s (PERF.md, PR 23).

The launcher starts the listener on ``PERFBENCH_DEVICE_PORT`` and then calls
``model_server.main`` with the arguments it was given, unchanged.

    python perfbench/children/serve.py <model_server arguments...>
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def device_report() -> dict:
    import jax

    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        # What the allocator held at its peak plus what programs reserved at
        # theirs: the runtime accounts a program's scratch ("temp") apart
        # from buffers, under *_reserved, and both occupy the chip's memory.
        "memory_peak_bytes": max((s.get("peak_bytes_in_use", 0)
                                  + s.get("peak_bytes_reserved", 0) for s in stats),
                                 default=0),
        "memory_stats": stats,
    }


def device_trace(seconds: float) -> dict:
    import jax

    base = os.environ["PERFBENCH_TRACE_DIR"]
    os.makedirs(base, exist_ok=True)
    trace_dir = tempfile.mkdtemp(prefix="trace-", dir=base)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    t0 = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t1 = time.time()
    time.sleep(seconds)
    jax.profiler.stop_trace()
    return {"trace_dir": trace_dir, "seconds": seconds, "start_took_s": t1 - t0,
            "stop_took_s": time.time() - t1 - seconds}


def start_side_port(port: int) -> None:
    trace_lock = threading.Lock()

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.startswith("/trace"):
                seconds = float(self.path.partition("seconds=")[2] or 2.0)
                with trace_lock:
                    reply = device_trace(seconds)
            else:
                reply = device_report()
            body = json.dumps(reply).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, name="perfbench-device",
                     daemon=True).start()


def main(argv: list[str]) -> int:
    from kubernetes_deep_learning_tpu.serving import model_server

    start_side_port(int(os.environ["PERFBENCH_DEVICE_PORT"]))
    return model_server.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
