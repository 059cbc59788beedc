"""The whole step's share of the chip's peak on the token wire, for a
DeepSeek-V3-family configuration: the FLOPs a full forward needs
(``perfbench/dsv3_flops.py``) for the output tokens that arrived in the
window (host clock: the stamps of the window's streams that ended well) and
for the prompt tokens prefilled in it, over the window's seconds and the
chip's bfloat16 peak.  The pairs the prompts attended over (n (n + 1) / 2 a
prompt), the context each decoded token met and the routed (token, held
expert) assignments come from the program's counters over the same window;
padding, rows an expert computed for a token not routed to it and anything
computed twice do not count.  No counters (a program without them) or no
peak: nothing.
"""

SERIES = {
    "tokens": "kdlt_decode_tokens_total",
    "prompt": "kdlt_decode_prefill_tokens_total",
    "prompt_pairs": "kdlt_decode_prefill_attended_pairs_total",
    "prompt_routed": "kdlt_decode_prefill_routed_rows_total",
    "context": "kdlt_decode_context_positions_total",
    "held": "kdlt_decode_expert_held_assignments_total",
}


def read(spec: dict, run: dict):
    from perfbench import dsv3_flops
    from perfbench.readers import lm_mfu

    d = lm_mfu.deltas(run, SERIES)
    if d is None or not run["peaks"] or not d["tokens"]:
        return None
    arrived = sum(
        sum(1 for t in o.stream.arrivals if 0.0 <= t <= run["seconds"])
        for o in run["outcomes"]
        if o.status == 200 and not o.error and getattr(o, "stream", None) is not None
        and o.stream.finished)
    if not arrived:
        return None
    share = arrived / d["tokens"]                # of the counters' window, what arrived
    flops = dsv3_flops.forward_flops(
        run["config"], tokens=arrived + d["prompt"], heads_computed=arrived,
        pairs=d["prompt_pairs"] + d["context"] * share,
        routed=d["prompt_routed"] + d["held"] * share)
    peak = float(run["peaks"]["bf16_tflops"]) * 1e12 * run["chips"]
    return 100.0 * flops / run["seconds"] / peak
