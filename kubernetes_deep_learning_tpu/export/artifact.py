"""Versioned model-artifact layout: ``<root>/<model-name>/<version>/``.

Mirrors the reference's TF-Serving convention of ``/models/<name>/<n>``
(reference tf-serving.dockerfile:5) where the server scans for the highest
numeric version directory.  An artifact directory contains:

- ``spec.json``        -- the ModelSpec (single source of truth; replaces the
                          reference's saved_model_cli-then-hardcode contract,
                          reference guide.md:199-236)
- ``params.msgpack``   -- flax variables ({params, batch_stats}), float32
- ``module.stablehlo`` -- jax.export-serialized StableHLO of the forward fn
                          with a symbolic batch dimension (the SavedModel
                          equivalent, per BASELINE.json north star)
- ``module.<platform>.stablehlo`` -- per-platform modules, emitted instead of
                          the single multi-platform file when the forward
                          contains platform-gated code that cannot co-lower
                          (e.g. the ViT's Pallas flash-attention branch: a
                          multi-platform module keeps every
                          jax.lax.platform_dependent branch, so the Mosaic
                          kernel would hit the CPU lowering rule)
- ``metadata.json``    -- export provenance (jax version, platforms, dtype)
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any

from kubernetes_deep_learning_tpu.modelspec import ModelSpec

SPEC_FILE = "spec.json"
PARAMS_FILE = "params.msgpack"
MODULE_FILE = "module.stablehlo"
META_FILE = "metadata.json"
# Marks a version directory as a decoder's artifact (models.longcat_flash
# writes and reads it): the image registry passes such a directory by.
DECODER_FILE = "decoder.json"


def is_decoder_dir(directory: str) -> bool:
    return os.path.exists(os.path.join(directory, DECODER_FILE))
_PLATFORM_MODULE_RE = re.compile(r"^module\.([a-z0-9_]+)\.stablehlo$")


def platform_module_file(platform: str) -> str:
    return f"module.{platform}.stablehlo"


@dataclasses.dataclass
class ModelArtifact:
    spec: ModelSpec
    variables: Any                 # nested dict of np arrays
    exported_bytes: bytes | None   # serialized multi-platform Exported, if present
    metadata: dict
    path: str = ""
    # platform -> serialized Exported, for artifacts exported per-platform.
    platform_modules: dict[str, bytes] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self._exported_cache: dict[str | None, Any] = {}

    def module_bytes_for(self, platform: str) -> bytes | None:
        """Best serialized module for ``platform`` (multi-platform wins)."""
        if self.exported_bytes is not None:
            return self.exported_bytes
        return self.platform_modules.get(platform)

    def exported_for(self, platform: str):
        """Deserialized jax.export.Exported usable on ``platform`` (lazy)."""
        if self.exported_bytes is not None:
            return self.exported  # multi-platform module: one shared deserialize
        blob = self.platform_modules.get(platform)
        if blob is None:
            raise ValueError(
                f"artifact at {self.path!r} has no StableHLO module for "
                f"{platform!r} (available: {sorted(self.platform_modules)})"
            )
        if platform not in self._exported_cache:
            from jax import export as jax_export

            self._exported_cache[platform] = jax_export.deserialize(blob)
        return self._exported_cache[platform]

    @property
    def exported(self):
        """The deserialized multi-platform Exported module (lazy).

        For per-platform artifacts use ``exported_for(platform)``.
        """
        if None not in self._exported_cache:
            if self.exported_bytes is None:
                raise ValueError(f"artifact at {self.path!r} has no StableHLO module")
            from jax import export as jax_export

            self._exported_cache[None] = jax_export.deserialize(self.exported_bytes)
        return self._exported_cache[None]


def save_artifact(
    directory: str,
    spec: ModelSpec,
    variables: Any,
    exported_bytes: "bytes | dict[str, bytes] | None",
    metadata: dict,
) -> str:
    """Write one artifact dir.  ``exported_bytes`` may be a single
    multi-platform module or a {platform: module} dict (see module doc)."""
    import flax.serialization

    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, SPEC_FILE), "w") as f:
        f.write(spec.to_json())
    with open(os.path.join(directory, PARAMS_FILE), "wb") as f:
        f.write(flax.serialization.to_bytes(variables))
    if isinstance(exported_bytes, dict):
        for platform, blob in exported_bytes.items():
            with open(os.path.join(directory, platform_module_file(platform)), "wb") as f:
                f.write(blob)
    elif exported_bytes is not None:
        with open(os.path.join(directory, MODULE_FILE), "wb") as f:
            f.write(exported_bytes)
    with open(os.path.join(directory, META_FILE), "w") as f:
        json.dump(metadata, f, indent=2, sort_keys=True)
    return directory


def load_artifact(directory: str) -> ModelArtifact:
    import flax.serialization

    with open(os.path.join(directory, SPEC_FILE)) as f:
        spec = ModelSpec.from_json(f.read())
    with open(os.path.join(directory, PARAMS_FILE), "rb") as f:
        # msgpack_restore needs no template: restores a plain nested dict.
        variables = flax.serialization.msgpack_restore(f.read())
    exported_bytes = None
    module_path = os.path.join(directory, MODULE_FILE)
    if os.path.exists(module_path):
        with open(module_path, "rb") as f:
            exported_bytes = f.read()
    platform_modules: dict[str, bytes] = {}
    for entry in os.listdir(directory):
        m = _PLATFORM_MODULE_RE.match(entry)
        if m:
            with open(os.path.join(directory, entry), "rb") as f:
                platform_modules[m.group(1)] = f.read()
    metadata = {}
    meta_path = os.path.join(directory, META_FILE)
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            metadata = json.load(f)
    return ModelArtifact(
        spec,
        variables,
        exported_bytes,
        metadata,
        path=directory,
        platform_modules=platform_modules,
    )


def scan_versions(root: str, name: str) -> list[int]:
    """Numeric version dirs under <root>/<name>/, ascending (TF-Serving rule)."""
    model_dir = os.path.join(root, name)
    if not os.path.isdir(model_dir):
        return []
    versions = [
        int(d) for d in os.listdir(model_dir)
        if re.fullmatch(r"\d+", d) and os.path.isdir(os.path.join(model_dir, d))
    ]
    return sorted(versions)


def latest_version(root: str, name: str) -> int | None:
    versions = scan_versions(root, name)
    return versions[-1] if versions else None


def version_dir(root: str, name: str, version: int) -> str:
    return os.path.join(root, name, str(version))
