"""Model factory: dataset/model-name -> SSLClassifier.

Mirrors src/utils/get_networks.py (MODEL_ARGS/DATA_ARGS tables and
``get_networks(dataset, model)``), with the CIFAR stem driven explicitly by
the dataset's class count like the reference's ``num_classes == 10`` trigger
(resnet_simclr.py:17-18).
"""

from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp

from ..registry import MODELS
from . import mla_moe as _mla_moe  # noqa: F401  (registers its presets)
from . import shortcut_moe as _shortcut_moe  # noqa: F401  (likewise)
from .resnet import resnet18, resnet50

MODELS.register("SSLResNet18", resnet18)
MODELS.register("SSLResNet50", resnet50)

# Compute-precision names accepted by configs/CLI.  "auto" resolves by the
# live backend: the TPU MXU is bf16-native, everything else gets float32.
_DTYPE_NAMES = {
    "float32": jnp.float32, "f32": jnp.float32, "fp32": jnp.float32,
    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
}


def resolve_dtype(spec: Any) -> Any:
    """Resolve a config dtype spec (name string, jnp dtype, or "auto") to
    the jnp compute dtype.  Parameters and BN statistics stay float32
    regardless — this only selects the conv/matmul precision
    (models/resnet.py)."""
    if spec is None or spec == "auto":
        import jax
        return (jnp.bfloat16 if jax.default_backend() == "tpu"
                else jnp.float32)
    if isinstance(spec, str):
        try:
            return _DTYPE_NAMES[spec.lower()]
        except KeyError:
            raise ValueError(
                f"Unknown dtype {spec!r}; expected one of "
                f"{sorted(_DTYPE_NAMES)} or 'auto'")
    return spec


def resolve_bn_stats_dtype(spec: Any, compute_dtype: Any) -> Any:
    """BN-statistics read precision: "auto" follows the COMPUTE dtype —
    bf16 models get the fused bf16-read/f32-accumulate statistics path
    (models/resnet.FusedBatchNorm), f32 models keep flax's BatchNorm so
    CPU/parity numerics are untouched.  Accumulation and the stored
    running statistics are float32 in every mode."""
    if spec is None or spec == "auto":
        return jnp.bfloat16 if compute_dtype == jnp.bfloat16 else None
    resolved = resolve_dtype(spec)
    return jnp.bfloat16 if resolved == jnp.bfloat16 else None

# Dataset -> class count (get_networks.py:3-6).
DATASET_NUM_CLASSES = {
    "cifar10": 10,
    "imbalanced_cifar10": 10,
    "imagenet": 1000,
    "imbalanced_imagenet": 1000,
    "synthetic": 10,
    "synthetic_tokens": 16,
}


def get_network(
    dataset: str,
    model_name: str,
    freeze_feature: bool = False,
    num_classes: Optional[int] = None,
    dtype: Any = "auto",
    stem: str = "default",
    bn_stats_dtype: Any = "auto",
) :
    """A backbone (models/backbone.py) by its registered name."""
    if num_classes is None:
        try:
            num_classes = DATASET_NUM_CLASSES[dataset]
        except KeyError:
            raise KeyError(
                f"Unknown dataset '{dataset}'; pass num_classes explicitly")
    factory = MODELS.get(model_name)
    # The reference applies the SimCLR CIFAR stem whenever num_classes == 10
    # (resnet_simclr.py:17-18); keep that behavior.  (The image models'
    # options mean nothing to a token encoder, which takes what it knows.)
    cifar_stem = num_classes == 10
    if stem in (None, "auto"):
        stem = "default"
    if stem == "s2d" and cifar_stem:
        # The CLI/arg-pool stem choice is global; CIFAR datasets keep their
        # SimCLR stem (there is no 7x7 conv to fold) rather than erroring.
        stem = "default"
    compute = resolve_dtype(dtype)
    return factory(num_classes=num_classes, cifar_stem=cifar_stem,
                   freeze_feature=freeze_feature, dtype=compute, stem=stem,
                   bn_stats_dtype=resolve_bn_stats_dtype(bn_stats_dtype,
                                                         compute))
