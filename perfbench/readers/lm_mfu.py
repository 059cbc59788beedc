"""The whole step's share of the chip's peak on the token wire: the FLOPs a
full forward needs (``perfbench/lm_flops.py``) for the output tokens that
arrived in the window (host clock: the stamps of the window's streams that
ended well) and for the prompt tokens prefilled in it, over the window's
seconds and the chip's bfloat16 peak.  The context each decoded token met
and the held-expert assignments come from the program's counters over the
same window; prompts' own attention is left out (under a hundredth of their
FLOPs at these lengths), as are padding and anything computed twice.
No counters (a program without them) or no peak: nothing.
"""

SERIES = {
    "tokens": "kdlt_decode_tokens_total",
    "prefills": "kdlt_decode_prefill_seconds_count",
    "prompt": "kdlt_decode_prefill_prompt_tokens_total",
    "context": "kdlt_decode_context_positions_total",
    "held": "kdlt_decode_expert_held_assignments_total",
}


def deltas(run: dict, series: dict, tier: str = "server"):
    before, after = run["before"].get(tier), run["after"].get(tier)
    if before is None or after is None or any(s not in after for s in series.values()):
        return None
    return {k: after[s] - before.get(s, 0.0) for k, s in series.items()}


def read(spec: dict, run: dict):
    from perfbench import lm_flops

    d = deltas(run, SERIES)
    if d is None or not run["peaks"]:
        return None
    arrived = sum(
        sum(1 for t in o.stream.arrivals if 0.0 <= t <= run["seconds"])
        for o in run["outcomes"]
        if o.status == 200 and not o.error and getattr(o, "stream", None) is not None
        and o.stream.finished)
    stepped = d["tokens"] - d["prefills"]        # tokens that decode steps produced
    if not arrived or stepped <= 0:
        return None
    share = arrived / d["tokens"]                # of the counters' window, what arrived
    rows = arrived + d["prompt"]
    flops = lm_flops.forward_flops(
        run["config"], tokens=rows, heads_computed=arrived,
        context_pairs=d["context"] * share,
        held_assignments=d["held"] / stepped * rows)
    peak = float(run["peaks"]["bf16_tflops"]) * 1e12 * run["chips"]
    return 100.0 * flops / run["seconds"] / peak
