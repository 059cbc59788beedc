"""The plain reference over a sample of the window's token streams, a layer
at a time, for Kimi-K2's configurations: ``reference_stream.py``'s arguments
and output, for a cut that does not fit the chip in float32 beside 8,448
positions' activations (16.7 GB of weights; the dense layer is 2.0 GB, an
expert layer 2.7 GB).  The activations of every sampled stream go through
layer 0, then layer 1, ...: each layer's weights are read from the artifact,
put on the device as float32, used and dropped; the head runs over the
positions that are compared and no others.

The family's file ``reference/<config["reference"]>.py`` gives
``read_tensors(config, artifact_dir, prefix)``, ``embed``, ``layer`` and
``head`` (its ``forward`` is their composition; a test holds the two to
each other).  Everything runs at ``highest`` matmul precision.  Every
sequence is padded at the end (causal: what follows a position does not
reach it) to the sample's longest, rounded up to a multiple of
``assumed.reference_block``: the sample always holds the window's longest
stream, which at this cell's lengths ends in the same block run after run, so
a run compiles two programs (a dense and an expert layer at that length) and
later runs find them in the compile cache.  Imports nothing of the program
under test.

For the control (``perfbench/control_stream.py``), and for nothing a
benchmark run does: ``--operand DTYPE`` reads every contraction's operands
through that type (the family's ``OPERAND``); the output then also holds
``argmax_i``, the ids the pass itself puts first, and ``route_i``
``[expert layers, n, topk]``, the experts each expert layer's router chose
(sorted), at the stream's positions; a request's ``probe`` (n ids) gives
``probe_i``, the logit at each.

    python perfbench/children/reference_kimi.py --config F --seed S
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


def run_layers(family, config: dict, artifact: str, padded: list, spans: list,
               routes: list | None = None):
    """float32 logits of every padded id array at its ``spans`` (start,
    stop) positions, a layer at a time; into ``routes``, where given, each
    expert layer's chosen experts of every array."""
    import jax
    import jax.numpy as jnp

    def put(prefix: str, rename: str | None = None) -> dict:
        # bfloat16 crosses as its 16 bits and is widened on the device
        return {(rename + k[len(prefix):] if rename is not None else k): family.widen(v)
                for k, v in family.read_tensors(config, artifact, prefix, bits=True).items()}

    with jax.default_matmul_precision("highest"):
        outer = put("embed")
        hidden = [family.embed(outer, jnp.asarray(ids)) for ids in padded]
        # every layer under layer 0's names: one compiled program a length
        # and a kind of layer (dense, expert)
        layer = jax.jit(lambda w, h: family.layer(w, 0, h, config, routing=True))
        for i in range(config["num_hidden_layers"]):
            weights = put(f"layers.{i}.", "layers.0.")
            hidden, chosen = zip(*(layer(weights, h) for h in hidden))
            jax.block_until_ready(hidden)
            if routes is not None and chosen[0].shape[-1]:
                routes.append([jnp.sort(c, axis=-1) for c in chosen])
            del weights
        outer = put("final_norm") | put("head")
        head = jax.jit(lambda w, h: family.head(w, h, config))
        return [head(outer, h[lo:hi]) for h, (lo, hi) in zip(hidden, spans)]


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--requests", required=True)
    p.add_argument("--artifact", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--cache-dir", default="")
    p.add_argument("--operand", default="")
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
    routes = None
    if args.operand:
        import jax.numpy as jnp

        narrow = jnp.dtype(args.operand)
        family.OPERAND = lambda x: x.astype(narrow).astype(jnp.float32)
        routes = []
    block = int(config["assumed"]["reference_block"])
    padded, spans = [], []
    longest = max(len(req["prompt"]) + len(req["served"]) - 1 for req in requests)
    for req in requests:
        ids = np.asarray(req["prompt"] + req["served"][:-1], np.int32)
        row = np.zeros(-(-longest // block) * block, np.int32)
        row[:len(ids)] = ids
        padded.append(row)
        spans.append((len(req["prompt"]) - 1, len(ids)))
    logits_all = run_layers(family, config, args.artifact, padded, spans, routes)
    out, scale, positions = {}, 0.0, 0
    for i, (req, (lo, hi), full) in enumerate(zip(requests, spans, logits_all)):
        served = req["served"]
        logits = np.asarray(full, np.float32)
        out[f"top_{i}"] = np.take_along_axis(
            logits, np.asarray(req["top_ids"], np.int64), axis=1)
        out[f"best_{i}"] = logits.max(axis=1)
        out[f"served_{i}"] = logits[np.arange(len(served)), np.asarray(served, np.int64)]
        if "probe" in req:
            out[f"probe_{i}"] = logits[np.arange(len(served)), np.asarray(req["probe"])]
        if routes is not None:
            out[f"argmax_{i}"] = logits.argmax(axis=1)
            out[f"route_{i}"] = np.stack([np.asarray(per_layer[i])[lo:hi]
                                          for per_layer in routes])
        scale = max(scale, float(np.abs(logits).max()))
        positions += len(served)
    np.savez(args.out, scale=np.float64(scale), **out)
    print(f"reference: {positions} positions of {len(requests)} streams, a layer at a "
          f"time, on {jax.devices()[0].platform} in {time.monotonic() - t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
