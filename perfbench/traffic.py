"""The one general load generator: a traffic file's parameters in, timed
outcomes out.  ``generator`` is ``open-poisson`` or ``closed``; ``entry`` is
``gateway-url`` or ``server-tensor``.  A new mix is a new data file.

Everything a seed decides is decided before the clock starts, and every
seed gets the same work in another order: the same multiset of arrival gaps
(the exponential distribution's quantiles, shuffled), every pool picture
equally often.  Only the order differs, so two seeds differ no more than two
runs of one.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import queue
import threading
import time

import numpy as np

from perfbench import procs

MSGPACK = "application/x-msgpack"


@dataclasses.dataclass
class Outcome:
    index: int
    rid: str
    due_s: float            # when the schedule said to send, from the clock's zero
    rows: tuple             # pool index of each image sent
    sent_s: float = 0.0
    done_s: float = 0.0
    status: int = 0         # HTTP status; 0 = no answer (transport error)
    scores: np.ndarray | None = None   # (len(rows), classes) as answered
    error: str = ""


def poisson_gaps(seed: int, rate_per_s: float, n: int) -> np.ndarray:
    """n arrival gaps of a Poisson process of that rate: the exponential's
    quantiles at (i + 1/2) / n, in an order drawn from the seed."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    np.random.default_rng([int(seed), 0xA771]).shuffle(gaps)
    return gaps


def balanced_rows(seed: int, pool: int, n: int) -> np.ndarray:
    """n pool indices, each picture equally often, in a seeded order."""
    rng = np.random.default_rng([int(seed), 0x9001])
    reps = -(-n // pool)
    rows = np.tile(np.arange(pool), reps)
    rng.shuffle(rows)
    return rows[:n]


def _post(conn_box: list, base: str, path: str, body: bytes, headers: dict,
          timeout: float) -> tuple[int, bytes, str]:
    """POST on this worker's kept-alive connection; one reconnect if the
    server closed it between requests."""
    for attempt in (0, 1):
        if conn_box[0] is None:
            conn_box[0] = procs.connect(base, timeout)
        try:
            conn_box[0].request("POST", path, body=body, headers=headers)
            r = conn_box[0].getresponse()
            data = r.read()
            return r.status, data, r.getheader("Content-Type", "")
        except (http.client.RemoteDisconnected, BrokenPipeError, ConnectionResetError):
            conn_box[0].close()
            conn_box[0] = None
            if attempt:
                raise
    raise AssertionError("unreachable")


# --- entries -----------------------------------------------------------------


class GatewayUrl:
    """``POST /predict`` at the gateway: ``{"url": ...}`` for one picture a
    request (the reference's schema), ``{"urls": [...]}`` for several."""

    def __init__(self, gateway: str, image_host: str, labels: list[str], seed: int):
        self.base, self.image_host, self.labels, self.seed = gateway, image_host, labels, seed

    def send(self, conn_box: list, o: Outcome, timeout: float) -> None:
        urls = [f"{self.image_host}/{row}/{self.seed}-{o.rid}-{j}"
                for j, row in enumerate(o.rows)]
        body = {"url": urls[0]} if len(urls) == 1 else {"urls": urls}
        status, data, _ = _post(
            conn_box, self.base, "/predict", json.dumps(body).encode(),
            {"Content-Type": "application/json", "X-Request-Id": o.rid}, timeout)
        o.status = status
        if status == 200:
            reply = json.loads(data)
            answers = [reply] if len(urls) == 1 else reply.get("predictions", [])
            if len(answers) != len(urls) or any(list(a) != self.labels for a in answers):
                o.error = f"answers differ in number or labels: {str(reply)[:120]}"
            else:
                o.scores = np.asarray([[a[k] for k in self.labels] for a in answers],
                                      np.float32)
        else:
            o.error = data[:200].decode(errors="replace")


class ServerTensor:
    """``POST /v1/models/<name>:predict``, a msgpack uint8 tensor a request."""

    def __init__(self, server: str, model: str, bodies: list[bytes]):
        self.base, self.path, self.bodies = server, f"/v1/models/{model}:predict", bodies

    def send(self, conn_box: list, o: Outcome, timeout: float, body_index: int) -> None:
        import msgpack

        status, data, _ctype = _post(
            conn_box, self.base, self.path, self.bodies[body_index],
            {"Content-Type": MSGPACK, "X-Request-Id": o.rid}, timeout)
        o.status = status
        if status == 200:
            out = msgpack.unpackb(data)["outputs"]
            o.scores = np.frombuffer(out["data"], np.dtype(out["dtype"])).reshape(out["shape"])
        else:
            o.error = data[:200].decode(errors="replace")


def encode_tensor_body(images: np.ndarray) -> bytes:
    """The model server's msgpack tensor wire (serving/protocol.py's format)."""
    import msgpack

    images = np.ascontiguousarray(images)
    return msgpack.packb({"inputs": {"shape": list(images.shape),
                                     "dtype": images.dtype.name,
                                     "data": images.tobytes()}})


# --- generators ----------------------------------------------------------------


def run_open(entry: GatewayUrl, params: dict, seed: int, lead_in_s: float,
             seconds: float, pool: int, on_window_start=None) -> tuple[list[Outcome], float]:
    """Open loop: arrivals on the schedule whatever the system does.  Returns
    the outcomes (lead-in included, ``due_s`` < 0 there) and the clock's zero
    (``time.monotonic()`` at the window's start)."""
    rate = float(params["rate_per_s"])
    n_lead, n_win = int(round(rate * lead_in_s)), int(round(rate * seconds))
    gaps = np.concatenate([poisson_gaps(seed + 1, rate, n_lead) if n_lead else [],
                           poisson_gaps(seed, rate, n_win)])
    due = np.cumsum(gaps) - gaps[0]
    zero_offset = due[n_lead] if n_lead else 0.0
    due = due - zero_offset
    per = int(params.get("pictures_per_request", 1))
    rows = np.concatenate([balanced_rows(seed + 1, pool, n_lead * per),
                           balanced_rows(seed, pool, n_win * per)]).astype(int)
    outcomes = [Outcome(i, f"pb{seed}-{i}", float(due[i]),
                        tuple(int(r) for r in rows[i * per:(i + 1) * per]))
                for i in range(len(due))]
    work: queue.Queue = queue.Queue()
    timeout = float(params.get("request_timeout_s", 60))
    t_zero = time.monotonic() + 0.2 - float(due[0])

    def worker():
        conn_box = [None]
        while True:
            o = work.get()
            if o is None:
                break
            o.sent_s = time.monotonic() - t_zero
            try:
                entry.send(conn_box, o, timeout)
            except (OSError, http.client.HTTPException, ValueError) as e:
                o.error = repr(e)
                if conn_box[0] is not None:
                    conn_box[0].close()
                    conn_box[0] = None
            o.done_s = time.monotonic() - t_zero
        if conn_box[0] is not None:
            conn_box[0].close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(params["workers"]))]
    for t in threads:
        t.start()
    started = False
    for o in outcomes:
        if not started and o.due_s >= 0:
            started = True
            if on_window_start:
                on_window_start(t_zero)
        delay = t_zero + o.due_s - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        work.put(o)
    for _ in threads:
        work.put(None)
    deadline = time.monotonic() + float(params.get("drain_s", 60))
    for t in threads:
        t.join(timeout=max(0.1, deadline - time.monotonic()))
    return outcomes, t_zero


def run_closed(entry: ServerTensor, params: dict, seed: int, lead_in_s: float,
               seconds: float, body_rows: list[tuple], on_window_start=None
               ) -> tuple[list[Outcome], float]:
    """Closed loop: ``callers`` callers, each sending its next request when
    the last returns, from the lead-in until the window closes; what is in
    flight then is waited for and carries ``done_s`` past the window, and
    what the lead-in left in flight at its start is answered inside it."""
    callers = int(params["callers"])
    timeout = float(params.get("request_timeout_s", 120))
    order = np.random.default_rng([int(seed), 0xB0D1]).permutation(len(body_rows))
    outcomes: list[Outcome] = []
    lock = threading.Lock()
    counter = [0]
    t_zero = time.monotonic() + lead_in_s
    t_end = t_zero + seconds

    def caller():
        conn_box = [None]
        while time.monotonic() < t_end:
            with lock:
                i = counter[0]
                counter[0] += 1
            b = int(order[i % len(order)])
            o = Outcome(i, f"pb{seed}-{i}", 0.0, body_rows[b])
            o.sent_s = o.due_s = time.monotonic() - t_zero
            try:
                entry.send(conn_box, o, timeout, b)
            except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
                o.error = repr(e)
                if conn_box[0] is not None:
                    conn_box[0].close()
                    conn_box[0] = None
            o.done_s = time.monotonic() - t_zero
            with lock:
                outcomes.append(o)
        if conn_box[0] is not None:
            conn_box[0].close()

    threads = [threading.Thread(target=caller, daemon=True) for _ in range(callers)]
    for t in threads:
        t.start()
    time.sleep(max(0.0, t_zero - time.monotonic()))
    if on_window_start:
        on_window_start(t_zero)
    for t in threads:
        t.join(timeout=seconds + lead_in_s + timeout)
    outcomes.sort(key=lambda o: o.sent_s)
    return outcomes, t_zero
