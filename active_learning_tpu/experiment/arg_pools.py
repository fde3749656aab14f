"""Registered per-dataset training presets ("arg pools").

The reference ships five arg-pool modules, each a ``{dataset: dict}`` of
training hyperparameters selected with ``--arg_pool`` and imported via
``exec()`` (src/main_al.py:48).  Here each pool is a plain
``{dataset: TrainConfig}`` mapping registered under the same name in the
ARG_POOLS registry — same data, no dynamic import.

Sources:
  * "default"                — src/arg_pools/default.py:5-46
  * "ssp_finetuning"         — src/arg_pools/ssp_finetuning.py:4-39
  * "ssp_linear_evaluation"  — src/arg_pools/ssp_linear_evaluation.py:4-26
  * "ssp_finetuning_imbalanced_cifar10_imb_0_1"  /  "..._0_01"
                             — src/arg_pools/ssp_finetuning_imbalanced_*.py

Pretrained checkpoint paths are configurable (the reference hardcodes
relative paths into ``../pretrained_ckpt``); pass ``pretrained_root`` to
``get_train_config`` to rebase them, or leave the default relative layout.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from ..config import (LoaderConfig, OptimizerConfig, PretrainedConfig,
                      SchedulerConfig, TrainConfig)
from ..registry import ARG_POOLS

# Loader presets.  The reference uses 12 torch DataLoader workers for
# ImageNet (default.py:29-38); here num_workers counts decode threads in the
# host pipeline (data/pipeline.py) — same role, same knob.
_CIFAR_TR = LoaderConfig(batch_size=128, num_workers=0)
_CIFAR_TE = LoaderConfig(batch_size=100, num_workers=0)
_IMAGENET_TR = LoaderConfig(batch_size=128, num_workers=12, prefetch=2)
_IMAGENET_TE = LoaderConfig(batch_size=128, num_workers=12, prefetch=2)

_SIMCLR_CIFAR = PretrainedConfig(
    path="pretrained_ckpt/cifar10/simclr.pth.tar",
    required_key=("encoder",), skip_key=("linear",))
# MoCo-v2 checkpoints store the backbone as ``encoder_q``; the surgery keeps
# only those keys, renames them to ``encoder``, and drops the MoCo fc head
# (ssp_finetuning.py:34-37).
_MOCO_IMAGENET = PretrainedConfig(
    path="pretrained_ckpt/imagenet/moco_v2_800ep_pretrain.pth.tar",
    required_key=("encoder_q",), skip_key=("fc",),
    replace_key=(("encoder_q", "encoder"),))


DEFAULT_POOL: Dict[str, TrainConfig] = {
    "cifar10": TrainConfig(
        eval_split=0.01, loader_tr=_CIFAR_TR, loader_te=_CIFAR_TE,
        optimizer=OptimizerConfig("sgd", lr=0.1, weight_decay=5e-4,
                                  momentum=0.9),
        scheduler=SchedulerConfig("cosine", t_max=200)),
    "imbalanced_cifar10": TrainConfig(
        eval_split=0.01, loader_tr=_CIFAR_TR, loader_te=_CIFAR_TE,
        optimizer=OptimizerConfig("sgd", lr=0.1, weight_decay=5e-4,
                                  momentum=0.9),
        scheduler=SchedulerConfig("cosine", t_max=200),
        imbalanced_training=True),
    "imagenet": TrainConfig(
        eval_split=0.01, loader_tr=_IMAGENET_TR, loader_te=_IMAGENET_TE,
        optimizer=OptimizerConfig("sgd", lr=0.1, weight_decay=1e-4,
                                  momentum=0.9),
        scheduler=SchedulerConfig("step", step_size=60, gamma=0.1)),
    # Extension beyond the reference (whose default pool has no
    # imbalanced_imagenet entry, so the dataset can't run at all there):
    # the ImageNet recipe + class-weighted loss.
    "imbalanced_imagenet": TrainConfig(
        eval_split=0.01, loader_tr=_IMAGENET_TR, loader_te=_IMAGENET_TE,
        optimizer=OptimizerConfig("sgd", lr=0.1, weight_decay=1e-4,
                                  momentum=0.9),
        scheduler=SchedulerConfig("step", step_size=60, gamma=0.1),
        imbalanced_training=True),
}

SSP_FINETUNING_POOL: Dict[str, TrainConfig] = {
    "cifar10": TrainConfig(
        eval_split=0.1, loader_tr=_CIFAR_TR, loader_te=_CIFAR_TE,
        optimizer=OptimizerConfig("sgd", lr=0.001, weight_decay=5e-4,
                                  momentum=0.9),
        scheduler=SchedulerConfig("cosine", t_max=200),
        pretrained=_SIMCLR_CIFAR),
    "imagenet": TrainConfig(
        eval_split=0.01, loader_tr=_IMAGENET_TR, loader_te=_IMAGENET_TE,
        optimizer=OptimizerConfig("sgd", lr=0.001, weight_decay=0.0,
                                  momentum=0.9),
        scheduler=SchedulerConfig("step", step_size=10, gamma=0.1),
        pretrained=_MOCO_IMAGENET),
}

SSP_LINEAR_EVALUATION_POOL: Dict[str, TrainConfig] = {
    "imagenet": TrainConfig(
        eval_split=0.01,
        loader_tr=LoaderConfig(batch_size=128, num_workers=8, prefetch=2),
        loader_te=LoaderConfig(batch_size=128, num_workers=8, prefetch=2),
        optimizer=OptimizerConfig("sgd", lr=15.0, weight_decay=1e-4,
                                  momentum=0.9),
        scheduler=SchedulerConfig("step", step_size=20, gamma=0.1),
        pretrained=_MOCO_IMAGENET),
}


def _imb_cifar_pool(ckpt: str) -> Dict[str, TrainConfig]:
    return {
        "imbalanced_cifar10": TrainConfig(
            eval_split=0.1, loader_tr=_CIFAR_TR, loader_te=_CIFAR_TE,
            optimizer=OptimizerConfig("sgd", lr=0.002, weight_decay=0.0,
                                      momentum=0.9),
            scheduler=SchedulerConfig("cosine", t_max=200),
            pretrained=PretrainedConfig(
                path=ckpt, required_key=("encoder",), skip_key=("linear",)),
            imbalanced_training=True),
    }


ARG_POOLS.register("default", DEFAULT_POOL)
ARG_POOLS.register("ssp_finetuning", SSP_FINETUNING_POOL)
ARG_POOLS.register("ssp_linear_evaluation", SSP_LINEAR_EVALUATION_POOL)
ARG_POOLS.register(
    "ssp_finetuning_imbalanced_cifar10_imb_0_1",
    _imb_cifar_pool("pretrained_ckpt/cifar10/simclr_imb_pretrain0_1.tar"))
ARG_POOLS.register(
    "ssp_finetuning_imbalanced_cifar10_imb_0_01",
    _imb_cifar_pool("pretrained_ckpt/cifar10/simclr_imb_pretrain0_01.tar"))

# Synthetic dataset (no reference counterpart; used by tests/benchmarks and
# egress-free e2e runs) trains fine with the CIFAR default recipe.
ARG_POOLS.register("synthetic", {
    "synthetic": TrainConfig(
        eval_split=0.1, loader_tr=_CIFAR_TR, loader_te=_CIFAR_TE,
        optimizer=OptimizerConfig("sgd", lr=0.05, weight_decay=5e-4,
                                  momentum=0.9),
        scheduler=SchedulerConfig("cosine", t_max=200)),
})


# Rows of token ids under a frozen encoder (models/mla_moe.py): the
# linear-evaluation recipe at the scale of a unit-RMS embedding, no
# validation split at this size, a loader batch that is already a step of
# batch x T tokens.
_TOKENS_LINEAR = TrainConfig(
    eval_split=0.0,
    loader_tr=LoaderConfig(batch_size=16, num_workers=0),
    loader_te=LoaderConfig(batch_size=16, num_workers=0),
    optimizer=OptimizerConfig("sgd", lr=0.1, weight_decay=0.0, momentum=0.9),
    scheduler=SchedulerConfig("step", step_size=20, gamma=0.1))
SSP_LINEAR_EVALUATION_POOL["synthetic_tokens"] = _TOKENS_LINEAR
ARG_POOLS.get("synthetic")["synthetic_tokens"] = _TOKENS_LINEAR


def get_train_config(arg_pool: str, dataset: str,
                     pretrained_root: Optional[str] = None) -> TrainConfig:
    """Resolve ``(arg_pool, dataset) -> TrainConfig``; rebases any relative
    pretrained path onto ``pretrained_root`` when given (the reference's
    hardcoded ``../pretrained_ckpt`` layout, ssp_finetuning.py:13)."""
    pool = ARG_POOLS.get(arg_pool)
    try:
        cfg = pool[dataset]
    except KeyError:
        known = ", ".join(sorted(pool))
        raise KeyError(
            f"arg pool '{arg_pool}' has no entry for dataset '{dataset}' "
            f"(has: {known})") from None
    if (pretrained_root and cfg.pretrained.path
            and not os.path.isabs(cfg.pretrained.path)):
        import dataclasses
        new_pre = dataclasses.replace(
            cfg.pretrained,
            path=os.path.join(pretrained_root, cfg.pretrained.path))
        cfg = dataclasses.replace(cfg, pretrained=new_pre)
    return cfg
