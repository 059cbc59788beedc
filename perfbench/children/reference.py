"""The plain reference over the run's own pictures, after the window.

Reads the weights the benchmark wrote (``params.msgpack``, through flax's
msgpack reader: a library, not the program), the pictures the benchmark
made (encoded sources, decoded and resized here with PIL, or model-sized
pixels), and computes float32 logits at ``highest`` precision, a block of
rows at a time.  ``--precision fp8`` is the control: the same reference
with every contraction's operands read through float8.  Imports nothing of
the program under test.

    python perfbench/children/reference.py --config F --params P
        (--inputs DIR | --tensor-pool N --seed S) --out FILE.npy
        [--precision float32|fp8]
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def flatten(tree: dict, prefix: tuple = ()) -> dict:
    out = {}
    for key, value in tree.items():
        if isinstance(value, dict):
            out.update(flatten(value, prefix + (key,)))
        else:
            out[prefix + (key,)] = value
    return out


def load_pixels(inputs: str, config: dict):
    """The encoded sources of a directory, decoded and resized as the
    configuration states: uint8 (n, H, W, 3) at the model's input size."""
    import numpy as np
    from PIL import Image

    filters = {"bilinear": Image.BILINEAR, "nearest": Image.NEAREST}
    h, w, _ = config["input_shape"]
    rows = []
    for name in sorted(os.listdir(inputs)):
        with open(os.path.join(inputs, name), "rb") as f:
            img = Image.open(io.BytesIO(f.read())).convert("RGB")
        if img.size != (w, h):
            img = img.resize((w, h), filters[config["resize_filter"]])
        rows.append(np.asarray(img, np.uint8))
    return np.stack(rows)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--params", required=True)
    p.add_argument("--inputs", default="", help="directory of encoded sources")
    p.add_argument("--tensor-pool", type=int, default=0,
                   help="instead: the tensor entry's pool of that many pictures, "
                        "made again from --seed")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", required=True)
    p.add_argument("--precision", default="float32")
    p.add_argument("--cache-dir", default="")
    args = p.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)

    import flax.serialization
    import jax
    import numpy as np

    from perfbench import reference
    from perfbench.reference.ops import Net, normalize

    if args.cache_dir:
        jax.config.update("jax_compilation_cache_dir", args.cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t0 = time.monotonic()
    with open(args.params, "rb") as f:
        flat = flatten(flax.serialization.msgpack_restore(f.read()))
    if args.tensor_pool:
        from perfbench import pictures

        pixels = pictures.tensor_pool(args.seed, args.tensor_pool,
                                      tuple(config["input_shape"]))
    else:
        pixels = load_pixels(args.inputs, config)
    forward = reference.load(config["reference"]).forward
    block = int(config["assumed"]["reference_block"])

    @jax.jit
    def run(weights, px):
        net = Net(weights, precision=args.precision)
        return forward(net, normalize(px, config["preprocessing"]), config)

    weights = jax.device_put(flat)
    out = []
    for i in range(0, len(pixels), block):
        rows = pixels[i:i + block]
        pad = block - len(rows)
        if pad:
            rows = np.concatenate([rows, np.zeros((pad, *rows.shape[1:]), rows.dtype)])
        out.append(np.asarray(run(weights, rows))[:block - pad])
    logits = np.concatenate(out).astype(np.float32)
    np.save(args.out, logits)
    print(f"reference {args.precision}: {len(logits)} rows on "
          f"{jax.devices()[0].platform} in {time.monotonic() - t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
