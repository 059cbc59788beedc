"""A Kimi-K2 decoder's artifact from the seed: the checkpoint the generative
lane loads, ``<out>/<served_name>/1/`` (``decoder.json`` with its ``family``
and a raw file a tensor, bfloat16), written by the program's own writer from
weights drawn on the host (``perfbench/dsv3_weights.py``).  Host only: it
never touches jax.  The arguments are those every artifact child is started
with.  A program without ``models/kimi_k2.py`` fails here, at once.

    python perfbench/children/make_kimi_artifact.py --config F --seed S --out DIR
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

    from kubernetes_deep_learning_tpu.models import kimi_k2
    from perfbench import dsv3_weights

    t0 = time.monotonic()
    program = dsv3_weights.program_config(config)
    shapes = kimi_k2.KimiConfig.from_dict(program).tensor_shapes()
    directory = os.path.join(args.out, config["served_name"], "1")
    kimi_k2.write_artifact(directory, program, dsv3_weights.tensors(
        config, args.seed, shapes, kimi_k2.tensor_dtype,
        threads=min(16, os.cpu_count() or 1)))
    values = sum(math.prod(s) for s in shapes.values())
    print(f"decoder artifact: {values / 1e9:.3f} B parameters in "
          f"{time.monotonic() - t0:.1f}s -> {directory}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
