"""Token streams: what ``entries/server-generate.py`` stands on.  The
lengths and prompts a traffic file states, the client of the generative
lane's wire, the structural check of a stream, and the arithmetic from
stamped token arrivals to end-to-end quantities and to the numbers that
decide ``correct``.  (``traffic.py`` is for pictures; its generators and
its Poisson gaps are used from here as they are.)

The wire.  ``POST /v1/models/<served_name>:generate`` with the JSON body

    {"token_ids": [...], "max_new_tokens": n, "ignore_eos": true,
     "top_logits": k, "stream": true}

is answered by a Server-Sent-Events stream: one ``data: {json}`` frame a
token, ``{"index": i, "token": id, "top_ids": [k ids], "top_logits": [k
numbers]}`` -- the ``k`` largest logits of the step that produced the token,
largest first, so that greedy decoding has ``top_ids[0] == token`` -- and a
last frame ``{"done": true, ...}``.  The model sees ``token_ids`` as they
are: nothing is put before them.  Every frame is stamped on arrival with
the host's clock.

Everything a seed decides is decided before the clock starts, and every
seed gets the same work in another order: the same multiset of (prompt
length, output length) pairs, each pool prompt equally often, the same
arrival gaps.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import math
import time

import numpy as np

from perfbench import procs


# --- what a traffic file states -------------------------------------------------


def band_values(spec: dict, n: int) -> np.ndarray:
    """n whole numbers from ``{"choice": [[low, high, weight], ...]}``: each
    band its share of n by weight (largest remainders), and inside a band
    the uniform distribution's quantiles over low..high.  No seed: the
    multiset is the traffic file's."""
    bands = [(int(lo), int(hi), float(w)) for lo, hi, w in spec["choice"]]
    if not bands or any(lo < 1 or hi < lo or w <= 0 for lo, hi, w in bands):
        raise ValueError(f"bad bands {spec['choice']!r}")
    total = sum(w for _, _, w in bands)
    share = [n * w / total for _, _, w in bands]
    counts = [int(math.floor(x)) for x in share]
    by_remainder = sorted(range(len(bands)), key=lambda i: (counts[i] - share[i], i))
    for i in by_remainder[:n - sum(counts)]:
        counts[i] += 1
    out = []
    for (lo, hi, _), c in zip(bands, counts):
        q = (np.arange(c) + 0.5) / max(c, 1)
        out.append(lo + np.floor(q * (hi - lo + 1)).astype(np.int64))
    return np.concatenate(out)


@dataclasses.dataclass(frozen=True)
class Prompt:
    ids: tuple              # the prompt's token ids
    max_new_tokens: int     # the output length asked for


def make_pool(seed: int, mix: dict, vocab: int) -> list[Prompt]:
    """``pool`` prompts: lengths and output lengths paired the same way for
    every seed, their order and the ids (uniform over [0, vocab)) from the
    seed."""
    n = int(mix["pool"])
    prompt_len = band_values(mix["prompt_tokens"], n)
    output_len = band_values(mix["output_tokens"], n)
    np.random.default_rng(0x70C5).shuffle(output_len)      # the pairing: no seed
    order = np.random.default_rng([int(seed), 0x70C6]).permutation(n)
    rng = np.random.default_rng([int(seed), 0x70C7])
    return [Prompt(tuple(int(t) for t in rng.integers(0, vocab, int(prompt_len[i]))),
                   int(output_len[i])) for i in order]


def encode_body(prompt: Prompt, top_logits: int) -> bytes:
    return json.dumps({"token_ids": list(prompt.ids),
                       "max_new_tokens": prompt.max_new_tokens, "ignore_eos": True,
                       "top_logits": int(top_logits), "stream": True},
                      separators=(",", ":")).encode()


# --- the client -------------------------------------------------------------------


@dataclasses.dataclass
class Stream:
    """What one request's stream brought, frame by frame."""
    prompt: int                     # index into the pool
    arrivals: list = dataclasses.field(default_factory=list)    # s, from the clock's zero
    indices: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    top_ids: list = dataclasses.field(default_factory=list)
    top_logits: list = dataclasses.field(default_factory=list)
    finished: bool = False          # the ``done`` frame came


class ServerGenerate:
    """The generative lane's entry for ``traffic.run_open`` and
    ``traffic.run_closed``: ``send`` posts one pool prompt and reads its
    stream to the end, stamping every token frame as it arrives.  The
    stamps are ``time.monotonic()`` until ``settle`` moves them to the
    clock's zero, which the generators only hand back at the end."""

    def __init__(self, server: str, model: str, bodies: list[bytes]):
        self.base, self.path, self.bodies = server, f"/v1/models/{model}:generate", bodies

    def send(self, conn_box: list, o, timeout: float, body_index: int | None = None) -> None:
        b = o.rows[0] if body_index is None else body_index
        o.stream = s = Stream(int(b))
        headers = {"Content-Type": "application/json", "Accept": "text/event-stream",
                   "X-Request-Id": o.rid}
        for attempt in (0, 1):
            if conn_box[0] is None:
                conn_box[0] = procs.connect(self.base, timeout)
            try:
                conn_box[0].request("POST", self.path, body=self.bodies[b], headers=headers)
                r = conn_box[0].getresponse()
                break
            except (http.client.RemoteDisconnected, BrokenPipeError, ConnectionResetError):
                conn_box[0].close()
                conn_box[0] = None
                if attempt:
                    raise
        o.status = r.status
        if r.status != 200:
            o.error = r.read()[:200].decode(errors="replace")
            return
        while True:
            line = r.readline()
            now = time.monotonic()
            if not line:
                break                       # the body's end
            if not line.startswith(b"data:"):
                continue
            frame = json.loads(line[5:])
            if not isinstance(frame, dict):
                raise ValueError(f"a frame that is no object: {line[:80]!r}")
            if frame.get("done"):
                s.finished = True
                continue
            s.arrivals.append(now)
            s.indices.append(frame.get("index"))
            s.tokens.append(frame.get("token"))
            s.top_ids.append(frame.get("top_ids"))
            s.top_logits.append(frame.get("top_logits"))
        if r.will_close:
            conn_box[0].close()
            conn_box[0] = None


def settle(outcomes: list, t_zero: float) -> None:
    """Move every stream's stamps to the generator's clock."""
    for o in outcomes:
        s = getattr(o, "stream", None)
        if s is not None:
            s.arrivals = [a - t_zero for a in s.arrivals]


# --- a stream's structure -----------------------------------------------------------


def structure_error(s: Stream, asked: int, top_logits: int, vocab: int) -> str:
    """Why a 200's stream is a wrong answer, or ''."""
    if not s.finished:
        return "the stream ended without its done frame"
    if len(s.tokens) != asked:
        return f"{len(s.tokens)} tokens, {asked} asked"
    if s.indices != list(range(asked)):
        return "frames out of order"
    for tok, ids, logits in zip(s.tokens, s.top_ids, s.top_logits):
        if not isinstance(tok, int) or not 0 <= tok < vocab:
            return f"token id {tok!r} outside [0, {vocab})"
        if not isinstance(ids, list) or not isinstance(logits, list) \
                or len(ids) != top_logits or len(logits) != top_logits:
            return f"not {top_logits} top logits with their ids"
        if any(not isinstance(i, int) or not 0 <= i < vocab for i in ids):
            return "a top id outside the vocabulary"
        if any(not isinstance(x, (int, float)) or not math.isfinite(x) for x in logits):
            return "a logit that is no finite number"
        if ids[0] != tok:
            return "the token is not the one with the largest logit"
    return ""


# --- end-to-end quantities -------------------------------------------------------------

QUANTITIES = {"output_tokens_per_s": "tokens/s", "ttft_p50_ms": "ms", "ttft_p95_ms": "ms",
              "itl_p50_ms": "ms", "itl_p95_ms": "ms", "latency_p50_ms": "ms",
              "latency_p95_ms": "ms"}


def quantities(window: list, good: set, seconds: float, failed_ms: float, percentile) -> dict:
    """``window``: the outcomes that count (open loop: due in the window;
    closed loop: ended after its start).  Tokens count where they arrived
    inside the window; a first token is timed from ``due_s`` (the schedule's
    time in an open loop, the send in a closed one); gaps between one
    stream's successive arrivals are pooled over all good streams; a request
    not answered correctly enters every latency once, as ``failed_ms``."""
    tokens = 0
    ttft, itl, latency = [], [], []
    for o in window:
        if o.index not in good:
            ttft.append(failed_ms)
            itl.append(failed_ms)
            latency.append(failed_ms)
            continue
        a = o.stream.arrivals
        tokens += sum(1 for t in a if 0.0 <= t <= seconds)
        ttft.append(1000.0 * (a[0] - o.due_s))
        itl.extend(1000.0 * (y - x) for x, y in zip(a, a[1:]))
        latency.append(1000.0 * (o.done_s - o.due_s))
    values = {"output_tokens_per_s": tokens / seconds}
    for name, xs in (("ttft", ttft), ("itl", itl), ("latency", latency)):
        xs.sort()
        if xs:
            values[f"{name}_p50_ms"] = percentile(xs, 50)
            values[f"{name}_p95_ms"] = percentile(xs, 95)
    return values


# --- correct ---------------------------------------------------------------------------


def sample(seed: int, candidates: list, n: int, length) -> list:
    """n of the candidates, drawn from the seed once the window has closed,
    the longest among them (all of them where there are fewer)."""
    if len(candidates) <= n:
        return list(candidates)
    longest = max(range(len(candidates)), key=lambda i: (length(candidates[i]), -i))
    rest = [i for i in range(len(candidates)) if i != longest]
    picks = np.random.default_rng([int(seed), 0x5A3F]).permutation(len(rest))[:n - 1]
    return [candidates[i] for i in sorted([longest, *(rest[j] for j in picks)])]


def reference_request(o, pool: list[Prompt]) -> dict:
    """What the reference child reads of one request: the prompt, the
    served tokens, and the ids whose logits the stream gave."""
    return {"index": o.index, "prompt": list(pool[o.stream.prompt].ids),
            "served": o.stream.tokens, "top_ids": o.stream.top_ids}


def stream_errors(s: Stream, ref_top: np.ndarray, ref_best: np.ndarray,
                  ref_served: np.ndarray, scale: float) -> tuple[float, float]:
    """(logit_err, argmax_gap) of one stream against the reference's full
    forward over prompt and served tokens: the widest |served logit -
    reference logit| over every returned (position, id), and the widest
    (reference's largest logit - reference's logit at the served token),
    both over ``scale``, the largest |reference logit| of the sample."""
    served = np.asarray(s.top_logits, np.float64)
    return (float(np.abs(served - ref_top).max()) / scale,
            float((ref_best - ref_served).max()) / scale)
