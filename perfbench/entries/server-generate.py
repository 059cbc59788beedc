"""The generative lane's token-stream entry: ``POST
/v1/models/<served_name>:generate`` at the model server, answered by a
stream of token frames (the wire: ``perfbench/tokens.py``).

Traffic file: ``generator`` ``closed`` (``callers`` sessions, each sends
its next request when the last one's stream has ended) or ``open-poisson``
(``rate_per_s``, ``workers``); ``pool`` prompts with ``prompt_tokens`` and
``output_tokens``, each ``{"choice": [[low, high, weight], ...]}``;
``top_logits`` (at least 1); ``compare_requests``; and the lane's sizes the
server is booted with: ``slots``, ``page_size``, ``max_pages``,
``prompt_buckets``.  Configuration: ``served_name``, ``vocab_held`` (ids are
drawn from [0, vocab_held) and every served id has to lie there),
``reference``, ``limits`` (``logit_err``, ``argmax_gap``).

What the program is asked for.  The server is started with ``--decode`` and
the lane's sizes in ``KDLT_DECODE_MODEL``, ``KDLT_DECODE_SLOTS``,
``KDLT_DECODE_PAGE_SIZE``, ``KDLT_DECODE_MAX_PAGES`` and
``KDLT_DECODE_PROMPT_BUCKETS``; its ``GET /v1/models`` has to show under the
served name a ``decode`` block with the same ``slots``, ``page_size``,
``max_pages`` and ``prompt_buckets`` and a ``vocab_size`` of at least
``vocab_held``: a lane booted at other sizes is another cell.

``correct``.  Once the window has closed and the server has gone, the
reference child is handed a sample, drawn then from the seed, of
``compare_requests`` of the window's rightly-formed answers, the longest
among them: each prompt with its served tokens.  It runs one float32 full
forward over their concatenation; position ``len(prompt) - 1 + j`` of it is
what the lane's step ``j`` computed -- the prefill for ``j = 0``, the cache
and the decode step after -- so prefill-then-decode is held to the full
forward.  ``logit_err`` and ``argmax_gap`` are ``tokens.stream_errors``'s,
the widest over the sample; ``wrong_answers`` counts every 200 of the
window whose stream is not what was asked (``tokens.structure_error``),
``unanswered`` every request that got no answer or whose stream broke off.
"""

import contextlib
import json
import os
import sys

from perfbench import manifest, tokens, traffic
from perfbench.procs import RunFailure
from perfbench.tokens import QUANTITIES  # noqa: F401 - the entry's interface

GENERATORS = ("closed", "open-poisson")
REFERENCE_OUT = "reference.npz"
LANE_SIZES = ("slots", "page_size", "max_pages", "prompt_buckets")


def make_inputs(run) -> None:
    """The pool's prompts from the seed, and each one's request body."""
    if int(run.mix["top_logits"]) < 1:
        raise manifest.ManifestError("top_logits has to be at least 1: logit_err reads them")
    run.vocab = int(run.config["vocab_held"])
    run.pool = tokens.make_pool(run.seed, run.mix, run.vocab)
    run.bodies = [tokens.encode_body(p, run.mix["top_logits"]) for p in run.pool]


def server_args(run) -> tuple[list[str], dict]:
    mix = run.mix
    return ["--decode"], {
        "KDLT_DECODE_MODEL": run.model,
        "KDLT_DECODE_SLOTS": str(mix["slots"]),
        "KDLT_DECODE_PAGE_SIZE": str(mix["page_size"]),
        "KDLT_DECODE_MAX_PAGES": str(mix["max_pages"]),
        "KDLT_DECODE_PROMPT_BUCKETS": ",".join(str(b) for b in mix["prompt_buckets"]),
    }


def check_status(run, page: dict, at_boot: bool) -> None:
    if not at_boot:
        return
    lane = page.get(run.model, {}).get("decode")
    if not isinstance(lane, dict):
        raise RunFailure(f"/v1/models shows no decode lane under {run.model!r}")
    for key in LANE_SIZES:
        if lane.get(key) != run.mix[key]:
            raise RunFailure(f"the lane's {key} is {lane.get(key)!r}, the traffic "
                             f"states {run.mix[key]!r}")
    if int(lane.get("vocab_size", 0)) < run.vocab:
        raise RunFailure(f"the lane's vocabulary {lane.get('vocab_size')!r} is under "
                         f"the configuration's vocab_held {run.vocab}")


def boot_front(run) -> None:
    """Nothing stands in front of the model server."""


def stop_front(run) -> None:
    pass


@contextlib.contextmanager
def warming(run):
    """One warm-up round: for every prefill bucket the pool's prompts fall
    in, the longest such prompt, two tokens long (the prefill's and one
    decode step's).  The shapes this traffic uses and no others."""
    buckets = sorted(int(b) for b in run.mix["prompt_buckets"])
    longest: dict[int, int] = {}
    for i, p in enumerate(run.pool):
        fits = [b for b in buckets if len(p.ids) <= b]
        if not fits:
            raise manifest.ManifestError(f"a prompt of {len(p.ids)} tokens fits no bucket")
        if fits[0] not in longest or len(p.ids) > len(run.pool[longest[fits[0]]].ids):
            longest[fits[0]] = i
    picks = [longest[b] for b in sorted(longest)]
    client = tokens.ServerGenerate(run.server, run.model, [
        tokens.encode_body(tokens.Prompt(run.pool[i].ids, 2), run.mix["top_logits"])
        for i in picks])
    conn_box = [None]

    def one_round(k: int) -> None:
        for j, i in enumerate(picks):
            o = traffic.Outcome(j, f"pbwarm{run.seed}-{k}-{j}", 0.0, (i,))
            client.send(conn_box, o, 120.0, j)
            why = o.error if o.status != 200 else tokens.structure_error(
                o.stream, 2, int(run.mix["top_logits"]), run.vocab)
            if why:
                raise RunFailure(f"warm-up of a {len(run.pool[i].ids)}-token prompt "
                                 f"-> {o.status}: {why}")

    try:
        yield one_round
    finally:
        if conn_box[0] is not None:
            conn_box[0].close()


def drive(run, on_window_start) -> None:
    mix, lead = run.mix, float(run.mix["lead_in_s"])
    client = tokens.ServerGenerate(run.server, run.model, run.bodies)
    if mix["generator"] == "closed":
        run.outcomes, run.t_zero = traffic.run_closed(
            client, mix, run.seed, lead, run.seconds,
            [(i,) for i in range(len(run.pool))], on_window_start)
        # the streams that ended after the window's start
        run.window = [o for o in run.outcomes if o.done_s >= 0]
    else:
        run.outcomes, run.t_zero = traffic.run_open(
            client, mix, run.seed, lead, run.seconds, len(run.pool), on_window_start)
        run.window = [o for o in run.outcomes if o.due_s >= 0]   # the requests due in it
    tokens.settle(run.outcomes, run.t_zero)
    sort_answers(run)


def sort_answers(run) -> None:
    """The window's answers: unanswered, wrongly formed, rightly formed."""
    k = int(run.mix["top_logits"])
    run.unanswered, run.wrong, run.formed = [], [], []
    for o in run.window:
        s = getattr(o, "stream", None)
        if o.status == 0 or (o.status == 200 and o.error and not s.finished):
            run.unanswered.append(o)
        elif o.status == 200:
            why = tokens.structure_error(s, run.pool[s.prompt].max_new_tokens, k, run.vocab)
            if why:
                o.error = why
                run.wrong.append(o)
            else:
                run.formed.append(o)
    for o in (run.unanswered + run.wrong)[:5]:
        print(f"request {o.rid}: status {o.status}: {o.error}", file=sys.stderr)


def reference_args(run) -> list[str]:
    """Draw the sample, now that the window has closed and the server has
    gone, and write it down for the reference child."""
    run.sampled = tokens.sample(
        run.seed, run.formed, int(run.mix["compare_requests"]),
        lambda o: len(run.pool[o.stream.prompt].ids) + len(o.stream.tokens))
    path = os.path.join(run.work, "reference_requests.json")
    with open(path, "w") as f:
        json.dump({"requests": [tokens.reference_request(o, run.pool)
                                for o in run.sampled]}, f)
    return ["--requests", path, "--artifact", os.path.join(run.work, "models")]


def compare(run) -> tuple[dict, set]:
    ref, limits = run.reference, run.limits
    scale = float(ref["scale"]) if run.sampled else 1.0
    logit_err = argmax_gap = 0.0
    compared = 0
    good = {o.index for o in run.formed}
    for i, o in enumerate(run.sampled):
        err, gap = tokens.stream_errors(
            o.stream, ref[f"top_{i}"], ref[f"best_{i}"], ref[f"served_{i}"], scale)
        logit_err, argmax_gap = max(logit_err, err), max(argmax_gap, gap)
        compared += len(o.stream.tokens)
        if err > limits["logit_err"] or gap > limits["argmax_gap"]:
            good.discard(o.index)
    return {
        "logit_err": {"value": logit_err, "limit": limits["logit_err"]},
        "argmax_gap": {"value": argmax_gap, "limit": limits["argmax_gap"]},
        "wrong_answers": {"value": len(run.wrong), "limit": 0},
        "unanswered": {"value": len(run.unanswered), "limit": 0},
        "tokens_compared": {"value": compared, "limit_at_least": 1},
    }, good


def quantities(run) -> dict:
    return tokens.quantities(run.window, run.good, run.seconds, run.FAILED_LATENCY_MS,
                             run.percentile)
