"""ModelRegistry: many models, one TPU tier.

The reference bakes exactly ONE SavedModel into its serving image and
selects it by env var (reference tf-serving.dockerfile:5); the in-tree
server until now scanned the artifact root but the whole deployment story
-- gateway, client, benches -- assumed a single model.  This registry is
the multi-model half of the TF-Serving convention done properly (Clipper
NSDI'17, INFaaS ATC'21: model-granular routing over shared accelerators):

- scans ``<root>/<name>/<version>/`` for EVERY model's highest numeric
  version (same layout rule as before, per model);
- keys loaded artifacts by **artifact hash** (sha256 over the version
  dir's files): a re-export of byte-identical content under a new version
  number is recognized and skipped instead of burning minutes of warmup
  compiling the same weights, and the hash is the stable identity
  dashboards/status pages can correlate across replicas;
- owns the ``name -> ServedModel`` map the server routes by
  (copy-on-write swaps, warmed-before-swap -- the single-model
  concurrency contract, now per model);
- answers ``GET /v1/models`` (all models' status) and the per-model
  status surface.

Construction policy stays with the caller: the registry takes a
``loader(name, version, directory) -> served`` callback (the server's
ServedModel factory, which knows buckets/batchers/meshes) and an
``unloader(served)`` for superseded versions, so this module owns only
scan/swap/identity -- no engine details.
"""

from __future__ import annotations

import hashlib
import os
import sys
import threading

from kubernetes_deep_learning_tpu.export import artifact as art


def artifact_hash(directory: str) -> str:
    """sha256 over the version dir's file names and bytes (sorted, streamed).

    The identity key of a loaded artifact: stable across hosts for the
    same exported bytes, different for any weight/spec/module change.
    """
    h = hashlib.sha256()
    for entry in sorted(os.listdir(directory)):
        path = os.path.join(directory, entry)
        if not os.path.isfile(path):
            continue
        h.update(entry.encode())
        h.update(b"\0")
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        h.update(b"\1")
    return h.hexdigest()


def iter_latest_versions(model_root: str) -> list[tuple[str, int, str]]:
    """Every model's highest numeric version under ``model_root``, as
    (name, version, directory) tuples in name order.

    THE scan rule -- shared by the serving registry's poll below and the
    kdlt-warm AOT pass (export.warm) -- so the set of models an image
    pre-warms is exactly the set a booted server would load.
    """
    out: list[tuple[str, int, str]] = []
    names = (
        sorted(os.listdir(model_root)) if os.path.isdir(model_root) else []
    )
    for name in names:
        version = art.latest_version(model_root, name)
        if version is None:
            continue
        directory = art.version_dir(model_root, name, version)
        if art.is_decoder_dir(directory):
            # a decoder's artifact: the generative lane's to load
            # (runtime.decode.load_decoder), not an image model
            continue
        out.append((name, version, directory))
    return out


class ModelRegistry:
    """Scan/compare/swap for every model under one artifact root.

    Thread contract (inherited from the single-model poll loop): scans are
    serialized on a lock; the ``models`` dict is rebound copy-on-write so
    handler threads iterating a snapshot never observe a mutation; a new
    version is fully loaded and warmed by the loader BEFORE the swap.
    """

    def __init__(self, model_root: str, loader, unloader=None):
        self.model_root = model_root
        self._loader = loader
        self._unloader = unloader
        self.models: dict = {}
        self._hashes: dict[str, str] = {}  # name -> served artifact hash
        self._lock = threading.Lock()

    def __contains__(self, name: str) -> bool:
        return name in self.models

    def get(self, name: str):
        return self.models.get(name)

    def poll(self) -> list[str]:
        """One scan of the artifact root: load any new model or higher
        version whose CONTENT actually changed.  Returns "name vN" per
        swap (the single-model poll's contract, now per model)."""
        with self._lock:
            return self._poll_locked()

    def _poll_locked(self) -> list[str]:
        updated: list[str] = []
        for name, version, directory in iter_latest_versions(self.model_root):
            current = self.models.get(name)
            if current is not None and current.version >= version:
                continue
            try:
                digest = artifact_hash(directory)
            except OSError as e:
                print(
                    f"model registry: skipping {name} v{version}: {e}",
                    file=sys.stderr,
                )
                continue
            if current is not None and self._hashes.get(name) == digest:
                # Same bytes under a higher version number: adopt the
                # version without reloading/re-warming -- the hash, not the
                # directory name, is the artifact's identity.  (The metric
                # series keep the originally loaded version's label; the
                # artifact_hash in /v1/models is the stable join key.)
                current.version = version
                print(
                    f"model registry: {name} v{version} is byte-identical to "
                    f"the served artifact ({digest[:12]}); adopted without "
                    "reload",
                    file=sys.stderr,
                )
                continue
            try:
                fresh = self._loader(name, version, directory)
            except Exception as e:
                # A half-written or broken version dir must never take down
                # the serving versions; skip and retry on the next poll.
                print(
                    f"version watcher: skipping {name} v{version}: {e}",
                    file=sys.stderr,
                )
                continue
            if fresh is None:  # loader declined (e.g. spec/dir name mismatch)
                continue
            fresh.artifact_hash = digest
            old = self.models.get(name)
            self.models = {**self.models, name: fresh}
            self._hashes[name] = digest
            if old is not None and self._unloader is not None:
                self._unloader(old)
            updated.append(f"{name} v{version}")
            print(f"loaded {name} v{version} from {directory}", file=sys.stderr)
        return updated

    def status(self) -> dict:
        """GET /v1/models: per-model serving status, keyed by name."""
        out = {}
        for name, m in self.models.items():
            out[name] = self.model_status(name, m)
        return out

    def model_status(self, name: str, served=None) -> dict | None:
        served = served if served is not None else self.models.get(name)
        if served is None:
            return None
        engine = served.engine
        return {
            "version": served.version,
            "ready": bool(engine.ready),
            "artifact_hash": getattr(served, "artifact_hash", None)
            or self._hashes.get(name),
            "buckets": list(getattr(engine, "buckets", ())),
            "family": getattr(served.artifact.spec, "family", None),
            "labels": list(served.artifact.spec.labels),
            # Quantization scheme, requested vs ACTIVE: these differ when
            # the warmup tolerance gate (or $KDLT_QUANT_SCHEME) downgraded
            # an int8-w8a8 artifact to weight-only serving -- the status
            # page is how an operator confirms which program a replica
            # actually runs after a hot reload.
            "quantization": (
                getattr(engine, "quantization", None)
                or getattr(served.artifact, "metadata", {}).get("quantization")
            ),
            "quantization_active": getattr(
                engine, "quantization_active",
                getattr(served.artifact, "metadata", {}).get("quantization"),
            ),
            # Sharding tag (parallel.mesh.SHARDING_SCHEMES), alongside the
            # quantization tag: a hot reload rebuilds the engine against
            # the SAME mesh (ServedModel keeps it), so the tag surviving a
            # reload is the re-sharding proof, and {model_parallel,
            # mesh_shape} tell an operator what layout a replica runs.
            **self._sharding_status(engine),
            # What the replica really runs on and through: platform,
            # device_kind, device count, fused path engaged/degraded,
            # per-bucket warm-up (engine.device_info), plus the host-path
            # implementations that are chosen silently by toolchain and
            # core count (batching queue, resize kernel).
            **self._device_status(engine),
            "batcher": getattr(served, "batcher_kind", None),
            "host_resize": getattr(served, "host_resize", None),
        }

    @staticmethod
    def _device_status(engine) -> dict:
        info_fn = getattr(engine, "device_info", None)
        return info_fn() if callable(info_fn) else {}

    @staticmethod
    def _sharding_status(engine) -> dict:
        info_fn = getattr(engine, "sharding_info", None)
        info = info_fn() if callable(info_fn) else {}
        return {
            "sharding": info.get("sharding"),
            "model_parallel": info.get("model_parallel", 1),
            "mesh_shape": info.get("mesh_shape"),
            "batch_rows_per_device": info.get("batch_rows_per_device"),
        }
