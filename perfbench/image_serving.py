"""What the picture entries (``entries/gateway-url.py``,
``entries/server-tensor.py``) share: the model server's arguments and the
checks of its status page, the warm-up round, the comparison of every
answered row with the reference's, and the end-to-end quantities of whole
answers.  Moved here from ``run.py`` as it stood; ``run`` is the ``CellRun``.
"""

from __future__ import annotations

import contextlib
import os

from perfbench import traffic
from perfbench.procs import RunFailure

# the end-to-end quantities a picture entry can give, with their units
QUANTITIES = {"images_per_s": "images/s", "latency_p50_ms": "ms", "latency_p95_ms": "ms"}
REFERENCE_OUT = "reference.npy"


def server_args(run) -> tuple[list[str], dict]:
    """The model server's arguments and environment beyond the common ones."""
    return ["--buckets", ",".join(str(b) for b in run.mix["server_buckets"])], {}


def check_status(run, page: dict, at_boot: bool) -> None:
    """``GET /v1/models`` as an image model has to read: at the boot and
    again once the window has closed."""
    st = page[run.model]
    if st["fast_degraded"]:
        raise RunFailure("the fused path degraded "
                         + ("at warm-up" if at_boot else "during the window"))
    if not at_boot:
        return
    if st["platform"] != run.platform:
        raise RunFailure(f"server runs on {st['platform']!r}, not {run.platform!r}")
    if bool(st["fast_engaged"]) != bool(run.config["fast_path"]):
        raise RunFailure(f"fast_engaged is {st['fast_engaged']}, the configuration "
                         f"states fast_path {run.config['fast_path']}")
    if list(st["buckets"]) != list(run.mix["server_buckets"]):
        raise RunFailure(f"server buckets {st['buckets']} != {run.mix['server_buckets']}")
    run.labels = list(st["labels"])


@contextlib.contextmanager
def warming(run):
    """One warm-up round: each bucket dispatched once through the live path,
    a tensor of zeros at the model server whatever the entry."""
    import numpy as np

    shape = tuple(run.config["input_shape"])
    buckets = [int(b) for b in run.mix["server_buckets"]]
    entry = traffic.ServerTensor(run.server, run.model, [
        traffic.encode_tensor_body(np.zeros((b, *shape), np.uint8)) for b in buckets])
    conn_box = [None]

    def one_round(k: int) -> None:
        for i in range(len(buckets)):
            o = traffic.Outcome(i, f"pbwarm{run.seed}-{k}-{i}", 0.0, ())
            entry.send(conn_box, o, 120.0, i)
            if o.status != 200:
                raise RunFailure(f"warm-up of bucket {buckets[i]} -> {o.status}: {o.error}")

    try:
        yield one_round
    finally:
        if conn_box[0] is not None:
            conn_box[0].close()


def reference_args(run) -> list[str]:
    """What the reference child reads: the weights the benchmark wrote into
    the artifact and the run's own pictures."""
    return ["--params", os.path.join(run.work, "models", run.model, "1", "params.msgpack"),
            *run.reference_inputs]


def compare(run) -> tuple[dict, set]:
    """Every answer of the window against the reference's row for the
    same picture.  Returns {name: {"value", "limit"}} and the indices of
    the requests answered correctly."""
    import numpy as np

    ref = run.reference
    scale = float(np.abs(ref).max())
    worst, rows, wrong, unanswered = 0.0, 0, 0, 0
    good = set()
    for o in run.window:
        if o.status == 0:
            unanswered += 1
            continue
        if o.status != 200:
            continue            # refused or shed: failed, not wrong
        want = ref[list(o.rows)]
        if o.scores is None or o.scores.shape != want.shape \
                or not np.isfinite(o.scores).all():
            wrong += 1
            continue
        err = float(np.abs(o.scores.astype(np.float64) - want).max()) / scale
        worst, rows = max(worst, err), rows + len(o.rows)
        if err <= run.limits["logit_err"]:
            good.add(o.index)
    return {
        "logit_err": {"value": worst, "limit": run.limits["logit_err"]},
        "wrong_answers": {"value": wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "rows_compared": {"value": rows, "limit_at_least": 1},
    }, good


def quantities(run) -> dict:
    w, s = run.window, run.seconds
    values = {}
    images = sum(len(o.rows) for o in w if o.index in run.good and o.done_s <= s)
    values["images_per_s"] = images / s
    lat = sorted((1000.0 * (o.done_s - o.due_s) if o.index in run.good
                  else run.FAILED_LATENCY_MS) for o in w)
    if lat:
        values["latency_p50_ms"] = run.percentile(lat, 50)
        values["latency_p95_ms"] = run.percentile(lat, 95)
    return values
