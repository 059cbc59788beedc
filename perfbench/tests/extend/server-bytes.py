"""A toy entry for ``tests/test_extend.py``: encoded pictures straight at
the model server's bytes wire (``POST /v1/models/<name>:predict``, a msgpack
list of JPEG and PNG blobs), a closed loop of callers.  The server decodes
and resizes; no gateway, no picture host.  It stands for the entry a later
PR brings: a new file, found by the traffic file's ``entry``.
"""

import json
import os

import numpy as np

from perfbench import pictures, traffic
from perfbench.image_serving import (  # noqa: F401 - the entry's interface
    QUANTITIES,
    REFERENCE_OUT,
    check_status,
    compare,
    quantities,
    reference_args,
    server_args,
    warming,
)

GENERATORS = ("closed",)
BYTES = "application/x-kdlt-image-bytes"


class ServerBytes:
    def __init__(self, server: str, model: str, bodies: list[bytes], labels: list[str]):
        self.base, self.path = server, f"/v1/models/{model}:predict"
        self.bodies, self.labels = bodies, labels

    def send(self, conn_box: list, o, timeout: float, body_index: int) -> None:
        status, data, _ = traffic._post(
            conn_box, self.base, self.path, self.bodies[body_index],
            {"Content-Type": BYTES, "X-Request-Id": o.rid}, timeout)
        o.status = status
        if status == 200:    # this wire answers in JSON, a {label: score} a picture
            answers = json.loads(data)["predictions"]
            o.scores = np.asarray([[a[k] for k in self.labels] for a in answers], np.float32)
        else:
            o.error = data[:200].decode(errors="replace")


def make_inputs(run) -> None:
    import msgpack

    mix = run.mix
    pool_dir = os.path.join(run.work, "pool")
    os.makedirs(pool_dir)
    pool = pictures.encoded_pool(run.seed, mix["pictures"])
    for i, (fmt, data) in enumerate(pool):
        with open(os.path.join(pool_dir, f"{i:04d}.{fmt}"), "wb") as f:
            f.write(data)
    run.pool_size = len(pool)
    run.reference_inputs = ["--inputs", pool_dir]
    per = int(mix["images_per_request"])
    rows = traffic.balanced_rows(run.seed, len(pool), per * int(mix["bodies"]))
    run.body_rows = [tuple(int(r) for r in rows[i * per:(i + 1) * per])
                     for i in range(int(mix["bodies"]))]
    run.bodies = [msgpack.packb({"images": [pool[r][1] for r in body]})
                  for body in run.body_rows]


def boot_front(run) -> None:
    pass


def stop_front(run) -> None:
    pass


def drive(run, on_window_start) -> None:
    run.outcomes, run.t_zero = traffic.run_closed(
        ServerBytes(run.server, run.model, run.bodies, run.labels), run.mix, run.seed,
        float(run.mix["lead_in_s"]), run.seconds, run.body_rows, on_window_start)
    run.window = [o for o in run.outcomes if o.done_s >= 0]
