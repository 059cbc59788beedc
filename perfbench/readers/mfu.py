"""The whole step's share of the chip's peak: the configuration's FLOPs per
image (counted by perfbench/flops.py on the non-fused graph) times the
images answered in the window, over the window's seconds and the chip's
bfloat16 peak.  Padding rows and recomputation do not count as work."""


def read(spec: dict, run: dict):
    images = sum(len(o.rows) for o in run["outcomes"]
                 if o.scores is not None and 0 <= o.done_s <= run["seconds"])
    if not images or not run["peaks"]:   # no peak: not on the chip, no share of it
        return None
    flops = float(run["config"]["flops_per_image"]) * images
    peak = float(run["peaks"]["bf16_tflops"]) * 1e12 * run["chips"]
    return 100.0 * flops / run["seconds"] / peak
