"""ModelSpec: the single source of truth for a served model.

The reference system splits the model contract across four places that must be
kept in sync by hand: the exporter output inspected with ``saved_model_cli``
(reference guide.md:199-236), hardcoded tensor/signature names in the gateway
(reference model_server.py:40-47), a hardcoded label list
(reference model_server.py:21-32), and a hardcoded preprocessor config
(reference model_server.py:18).  Here all of that lives in one dataclass that
the exporter, model server, and gateway all consume.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Everything needed to export, serve, and query one model."""

    name: str                       # served model name, e.g. "clothing-model"
    family: str                     # architecture family key in models.registry
    input_shape: tuple[int, int, int]   # (H, W, C), batch dim excluded
    labels: tuple[str, ...]         # output class labels, index-aligned
    preprocessing: str = "tf"       # "tf" | "caffe" | "torch" | "none"
    resize_filter: str = "bilinear"  # "bilinear" | "nearest" (host resize filter)
    input_dtype: str = "uint8"      # wire dtype gateway -> server (normalize on device)
    input_name: str = "image"       # request tensor key
    output_name: str = "scores"     # response tensor key
    head_hidden: tuple[int, ...] = ()   # hidden Dense sizes between pool and logits
    description: str = ""
    # Legacy tensor names from the reference's SavedModel signature
    # (reference guide.md:220-231: input_8/dense_7), accepted/emitted by the
    # gRPC PredictionService frontend so reference-era gRPC clients
    # (reference model_server.py:35-49) work against this server unmodified.
    compat_input_name: str = ""
    compat_output_name: str = ""

    @property
    def num_classes(self) -> int:
        return len(self.labels)

    @property
    def batched_shape(self) -> tuple[int, ...]:
        return (-1, *self.input_shape)

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        return json.dumps(d, indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ModelSpec":
        d: dict[str, Any] = json.loads(s)
        d["input_shape"] = tuple(d["input_shape"])
        d["labels"] = tuple(d["labels"])
        d["head_hidden"] = tuple(d.get("head_hidden", ()))
        return cls(**d)


_REGISTRY: dict[str, ModelSpec] = {}


def register_spec(spec: ModelSpec) -> ModelSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_spec(name: str) -> ModelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown model spec {name!r}; known: {sorted(_REGISTRY)}"
        ) from None


# The flagship model: the reference's 10-class clothing classifier
# (labels from reference model_server.py:21-32, input contract from
# reference guide.md:220-231: (-1, 299, 299, 3) f32 -> (-1, 10) f32).
# head_hidden=(100,) mirrors the bookcamp transfer-learning head that
# produced xception_v4_large_08_0.894.h5 (reference guide.md:176).
CLOTHING_MODEL = register_spec(
    ModelSpec(
        name="clothing-model",
        family="xception",
        input_shape=(299, 299, 3),
        labels=(
            "dress",
            "hat",
            "longsleeve",
            "outwear",
            "pants",
            "shirt",
            "shoes",
            "shorts",
            "skirt",
            "t-shirt",
        ),
        preprocessing="tf",
        # keras-image-helper (the reference gateway's preprocessor,
        # reference model_server.py:18) resizes with NEAREST; match it so the
        # reference's expected logits (guide.md:623-625) reproduce exactly.
        resize_filter="nearest",
        head_hidden=(100,),
        description="Xception clothing classifier (reference flagship model)",
        compat_input_name="input_8",
        compat_output_name="dense_7",
    )
)

# The flagship at the CPU rehearsal size: ``chip_smoke.py --rehearse-on-cpu``
# drives the same export -> model server -> gateway flow with this spec, so
# the control flow is debugged on the CPU before chip time is spent.  Never
# a benchmark cell: at 96x96 a measurement is a measurement of overheads.
CLOTHING_MODEL_96 = register_spec(
    dataclasses.replace(
        CLOTHING_MODEL,
        name="clothing-model-96",
        input_shape=(96, 96, 3),
        description="clothing classifier at 96x96 (CPU rehearsal of chip_smoke.py)",
    )
)

_IMAGENET_LABELS = tuple(f"class_{i}" for i in range(1000))

# BASELINE.json config 3: ResNet50/ImageNet served via the same gateway path.
RESNET50_IMAGENET = register_spec(
    ModelSpec(
        name="resnet50-imagenet",
        family="resnet50",
        input_shape=(224, 224, 3),
        labels=_IMAGENET_LABELS,
        preprocessing="caffe",
        description="ResNet50 ImageNet classifier",
    )
)

# BASELINE.json config 4: EfficientNet-B3 with server-side dynamic batching.
EFFICIENTNET_B3_IMAGENET = register_spec(
    ModelSpec(
        name="efficientnet-b3-imagenet",
        family="efficientnet-b3",
        input_shape=(300, 300, 3),
        labels=_IMAGENET_LABELS,
        preprocessing="torch",
        description="EfficientNet-B3 ImageNet classifier",
    )
)

# Transformer classifier: the serving-path consumer of the in-tree flash
# attention kernel (ops.attention) -- 256x256/16 gives a 256-token sequence,
# an exact multiple of the kernel's 128-wide MXU tiles.  Inception-style
# [-1, 1] scaling per the original ViT recipe.
VIT_B16_IMAGENET = register_spec(
    ModelSpec(
        name="vit-b16-imagenet",
        family="vit-b16",
        input_shape=(256, 256, 3),
        labels=_IMAGENET_LABELS,
        preprocessing="tf",
        description="ViT-B/16 ImageNet classifier (Pallas flash attention)",
    )
)
