"""Write one cell's artifact: the benchmark's weights from ``--seed`` in the
layout the model server loads (``<out>/<served name>/1/``).

Runs on the host (``JAX_PLATFORMS=cpu``) and exits before the server takes
the chip.  The weights are the benchmark's (``perfbench/weights.py``: drawn
from the seed, BatchNorm statistics calibrated on a few of the traffic's
pictures); the program contributes its artifact format and, where the
configuration serves the exported module, the StableHLO traced from its
model.  That module depends on the configuration and not on the seed, so it
is kept beside the compile cache and traced once per checkout.

    python perfbench/children/make_artifact.py --config F --seed N --out DIR
        --module-cache DIR --platform tpu
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def model_spec(config: dict):
    """The program's ModelSpec, built from the configuration file's terms."""
    from kubernetes_deep_learning_tpu.modelspec import ModelSpec

    return ModelSpec(
        name=config["served_name"],
        family=config["family"],
        input_shape=tuple(config["input_shape"]),
        labels=tuple(f"class_{i}" for i in range(int(config["num_classes"]))),
        preprocessing=config["preprocessing"],
        resize_filter=config["resize_filter"],
        head_hidden=tuple(config.get("head_hidden", ())),
        description=f"perfbench configuration {config['name']}",
    )


def build_weights(config: dict, seed: int) -> dict:
    from perfbench import pictures, reference, weights

    forward = reference.load(config["reference"]).forward
    flat = weights.make(weights.declare(forward, config), seed)
    cal = config["assumed"]["calibration"]
    weights.calibrate(
        forward, config, flat,
        pictures.calibration_pixels(seed, int(cal["pictures"]), int(cal["side"])),
    )
    return weights.nest(flat)


def exported_module(config: dict, spec, variables, cache_dir: str, platform: str) -> bytes:
    import jax
    import jax.numpy as jnp

    from kubernetes_deep_learning_tpu.export.exporter import trace_forward

    path = os.path.join(cache_dir, f"{config['name']}.{platform}.stablehlo")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return f.read()
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), variables)
    blob = trace_forward(spec, shapes, dtype=jnp.dtype(config["compute_dtype"]),
                         platforms=(platform,))
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return blob


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--module-cache", required=True)
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)

    import jax

    import kubernetes_deep_learning_tpu
    from kubernetes_deep_learning_tpu.export import artifact as art
    from kubernetes_deep_learning_tpu.parallel import mesh as mesh_lib

    t0 = time.monotonic()
    spec = model_spec(config)
    variables = build_weights(config, args.seed)
    t1 = time.monotonic()
    module = None
    if config["artifact_module"]:
        module = exported_module(config, spec, variables, args.module_cache, args.platform)
    t2 = time.monotonic()
    metadata = {  # the keys export_model writes
        "jax_version": jax.__version__,
        "platforms": [args.platform],
        "module_layout": "single",
        "compute_dtype": config["compute_dtype"],
        "params_dtype": None,
        "framework_version": kubernetes_deep_learning_tpu.__version__,
        "partition_rule": dict(mesh_lib.partition_rule(spec.family)),
    }
    directory = art.version_dir(args.out, spec.name, 1)
    art.save_artifact(directory, spec, variables, module, metadata)
    print(f"artifact {directory}: weights {t1 - t0:.1f}s, module {t2 - t1:.1f}s, "
          f"write {time.monotonic() - t2:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
