"""The benchmark's own picture host: ``GET /<pool index>/<note>`` answers
the pool's picture with ``note`` stamped into its metadata, so every
request fetches bytes that no other request has.  A child of its own: it
shares no interpreter lock with the load generator.

    python perfbench/children/image_host.py <pool dir> <port>
"""

from __future__ import annotations

import os
import sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import pictures  # noqa: E402

CONTENT_TYPES = {"jpeg": "image/jpeg", "png": "image/png"}


def load_pool(directory: str) -> list[tuple[str, bytes]]:
    pool = []
    for name in sorted(os.listdir(directory)):
        with open(os.path.join(directory, name), "rb") as f:
            pool.append((name.rsplit(".", 1)[1], f.read()))
    return pool


def main(argv: list[str]) -> int:
    directory, port = argv[0], int(argv[1])
    pool = load_pool(directory)

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_GET(self):
            try:
                _, index, note = self.path.split("/", 2)
                fmt, data = pool[int(index)]
            except (ValueError, IndexError):
                self.send_error(404)
                return
            body = pictures.stamp(fmt, data, note.encode())
            self.send_response(200)
            self.send_header("Content-Type", CONTENT_TYPES[fmt])
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    httpd.daemon_threads = True
    print(f"image host: {len(pool)} pictures on :{port}", flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    sys.exit(main(sys.argv[1:]))
