"""A decoder's artifact from the seed: the checkpoint the generative lane
loads, ``<out>/<served_name>/1/`` (``decoder.json`` and a raw file a
tensor, bfloat16), written by the program's own writer from weights drawn on
the host (``perfbench/lm_weights.py``).  Host only: it never touches jax.
The arguments are those every artifact child is started with.

    python perfbench/children/make_decoder_artifact.py --config F --seed S --out DIR
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--module-cache")
    p.add_argument("--platform")
    args = p.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)

    from kubernetes_deep_learning_tpu.models import longcat_flash
    from perfbench import lm_weights

    t0 = time.monotonic()
    program = lm_weights.program_config(config)
    shapes = longcat_flash.LongcatConfig.from_dict(program).tensor_shapes()
    directory = os.path.join(args.out, config["served_name"], "1")
    longcat_flash.write_artifact(directory, program, lm_weights.tensors(
        config, args.seed, shapes, longcat_flash.tensor_dtype,
        threads=min(16, os.cpu_count() or 1)))
    values = sum(math.prod(s) for s in shapes.values())
    print(f"decoder artifact: {values / 1e9:.3f} B parameters in "
          f"{time.monotonic() - t0:.1f}s -> {directory}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
