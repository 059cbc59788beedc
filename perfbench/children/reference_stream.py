"""The plain reference over a sample of the window's token streams, after
the window: one float32 full forward over each prompt with its served
tokens, a request at a time.  A configuration whose traffic streams tokens
names this child (``"reference_child": "reference_stream.py"``) or brings
its own with the same arguments.

The family's file ``reference/<config["reference"]>.py`` gives

- ``weights(config, seed, artifact_dir)``: the weights the benchmark made
  (from the seed, or read back from what the artifact child wrote there),
- ``forward(weights, token_ids, config)``: float32 logits ``[T, vocab]`` of
  a causal full forward over ``token_ids`` ``[T]``, no cache.

Sequences are padded at the end to a multiple of ``assumed.reference_block``
(causal: what follows a position does not reach it), so that a run compiles
a few programs and not one a length.  Writes an ``.npz``: for request ``i``
``top_i`` ``[n, k]`` (the reference's logits at the ids the stream gave),
``best_i`` ``[n]`` (its largest logit) and ``served_i`` ``[n]`` (its logit
at the served token), each at the position that produced served token
``j``, ``len(prompt) - 1 + j``; and ``scale``, the largest |logit| over
all those positions.  Imports nothing of the program under test.

    python perfbench/children/reference_stream.py --config F --seed S
        --requests FILE.json --artifact DIR --out FILE.npz [--cache-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--requests", required=True)
    p.add_argument("--artifact", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--cache-dir", default="")
    args = p.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    with open(args.requests) as f:
        requests = json.load(f)["requests"]

    import jax
    import numpy as np

    from perfbench import reference

    if args.cache_dir:
        jax.config.update("jax_compilation_cache_dir", args.cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t0 = time.monotonic()
    family = reference.load(config["reference"])
    weights = family.weights(config, args.seed, args.artifact)
    block = int(config["assumed"]["reference_block"])
    forward = jax.jit(lambda w, ids: family.forward(w, ids, config))
    out, scale, positions = {}, 0.0, 0
    for i, req in enumerate(requests):
        prompt, served = req["prompt"], req["served"]
        ids = np.asarray(prompt + served[:-1], np.int32)
        padded = np.zeros(-(-len(ids) // block) * block, np.int32)
        padded[:len(ids)] = ids
        logits = np.asarray(forward(weights, padded), np.float32)[len(prompt) - 1:len(ids)]
        rows = np.arange(len(served))
        out[f"top_{i}"] = np.take_along_axis(
            logits, np.asarray(req["top_ids"], np.int64), axis=1)
        out[f"best_{i}"] = logits.max(axis=1)
        out[f"served_{i}"] = logits[rows, np.asarray(served, np.int64)]
        scale = max(scale, float(np.abs(logits).max()))
        positions += len(served)
    np.savez(args.out, scale=np.float64(scale), **out)
    print(f"reference: {positions} positions of {len(requests)} streams on "
          f"{jax.devices()[0].platform} in {time.monotonic() - t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
