"""Pretrained (SSL / transfer) checkpoint ingestion: torch state_dict ->
Flax variables.

Reference: src/utils/load_pretrained_weights.py:5-66 — state-dict surgery
(``module.`` prefix stripping, substring ``skip_key``/``required_key``
filtering, ``replace_key`` renaming) followed by a PARTIAL update of the
network's dict (``init_dict.update(net_dict)``), so layers absent from the
checkpoint (the fresh linear head) keep their random init.  The MoCo-v2
mapping (``encoder_q`` -> ``encoder``, skip ``fc``) comes from
src/arg_pools/ssp_finetuning.py:34-37.

The TPU-side extra work is the layout conversion from torchvision ResNet
naming/shapes to this repo's Flax model (models/resnet.py):

  torch key                         flax path
  ------------------------------------------------------------------
  encoder.conv1.weight              params/encoder/conv_stem/kernel (OIHW->HWIO)
  encoder.bn1.{weight,bias}         params/encoder/bn_stem/{scale,bias}
  encoder.bn1.running_{mean,var}    batch_stats/encoder/bn_stem/{mean,var}
  encoder.layerL.B.convN.weight     params/encoder/stageL_blockB/Conv_{N-1}/kernel
  encoder.layerL.B.bnN.*            params/encoder/stageL_blockB/BatchNorm_{N-1}/*
  encoder.layerL.B.downsample.0/1   .../downsample_conv / downsample_bn
  linear.weight                     params/linear/kernel ([C,D] -> [D,C])

``num_batches_tracked`` has no Flax counterpart and is dropped.  Unmappable
leftover keys are an error — silently ignoring them is how a wrong
checkpoint goes unnoticed.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, Mapping, Optional, Tuple

import numpy as np

from ..config import PretrainedConfig
from .logging import get_logger

FlaxPath = Tuple[str, ...]  # (collection, module..., leaf)


def load_torch_state_dict(path: str) -> Dict[str, np.ndarray]:
    """Read a torch checkpoint into {key: np.ndarray} (CPU, no grads).
    Handles the common ``{"state_dict": ...}`` wrapper (MoCo et al.),
    matching load_pretrained_weights.py:24-26."""
    import torch
    try:
        # Mapped, not read: a tensor's bytes come off the disk when it is
        # used (an encoder of gigabytes is uploaded leaf by leaf).
        ckpt = torch.load(path, map_location="cpu", weights_only=False,
                          mmap=True)
    except (RuntimeError, ValueError):     # a pre-zipfile checkpoint
        ckpt = torch.load(path, map_location="cpu", weights_only=False)
    if isinstance(ckpt, dict) and "state_dict" in ckpt:
        ckpt = ckpt["state_dict"]
    return {k: _host_array(v) for k, v in ckpt.items()}


def _host_array(value) -> np.ndarray:
    """A checkpoint tensor as a host array of its own dtype (bfloat16,
    which numpy lacks, as ``ml_dtypes.bfloat16`` over the same bytes)."""
    if not hasattr(value, "detach"):
        return np.asarray(value)
    import torch
    value = value.detach()
    if value.dtype == torch.bfloat16:
        import ml_dtypes
        return value.view(torch.int16).numpy().view(ml_dtypes.bfloat16)
    return np.asarray(value.numpy())


def surgery(
    state: Mapping[str, np.ndarray],
    required_key: Optional[Iterable[str]] = None,
    skip_key: Optional[Iterable[str]] = None,
    replace_map: Optional[Mapping[str, str]] = None,
) -> Dict[str, np.ndarray]:
    """The reference's key filtering/renaming, verbatim semantics
    (load_pretrained_weights.py:27-61): drop keys containing any
    ``skip_key`` substring; drop keys containing NO ``required_key``
    substring; strip a ``module.`` DataParallel prefix; then apply the
    first matching ``replace_map`` substring rename."""
    replace_map = dict(replace_map or {})
    required = tuple(required_key or ())
    skip = tuple(skip_key or ())

    def keep(k: str) -> bool:
        if any(s in k for s in skip):
            return False
        if required and not any(s in k for s in required):
            return False
        return True

    def rename(k: str) -> str:
        for old, new in replace_map.items():
            if old in k:
                return k.replace(old, new)
        return k

    out: Dict[str, np.ndarray] = {}
    for k, v in state.items():
        if not keep(k):
            continue
        if k.startswith("module."):
            k = k[len("module."):]
        out[rename(k)] = v
    return out


_BN_LEAF = {"weight": ("params", "scale"), "bias": ("params", "bias"),
            "running_mean": ("batch_stats", "mean"),
            "running_var": ("batch_stats", "var")}


def torch_key_to_flax(key: str) -> Optional[Tuple[FlaxPath, Optional[str]]]:
    """Map one torchvision-ResNet-style key to (flax path, transform).

    transform: None | "conv" (OIHW->HWIO) | "dense" (transpose).
    Returns None for keys with no Flax counterpart
    (``num_batches_tracked``).  Raises KeyError for unrecognized keys.
    """
    if key.endswith("num_batches_tracked"):
        return None
    parts = key.split(".")
    if parts[0] == "encoder":
        rest = parts[1:]
        # Stem: conv1 / bn1 at the top level of the torchvision encoder.
        if rest[0] == "conv1" and rest[1] == "weight":
            return (("params", "encoder", "conv_stem", "kernel"), "conv")
        if rest[0] == "bn1":
            coll, leaf = _BN_LEAF[rest[1]]
            return ((coll, "encoder", "bn_stem", leaf), None)
        m = re.fullmatch(r"layer(\d+)", rest[0])
        if m:
            stage = int(m.group(1))
            block = int(rest[1])
            mod = f"stage{stage}_block{block}"
            sub = rest[2]
            leaf = rest[3]
            cm = re.fullmatch(r"conv(\d+)", sub)
            if cm and leaf == "weight":
                return (("params", "encoder", mod,
                         f"Conv_{int(cm.group(1)) - 1}", "kernel"), "conv")
            bm = re.fullmatch(r"bn(\d+)", sub)
            if bm:
                coll, l = _BN_LEAF[leaf]
                return ((coll, "encoder", mod,
                         f"BatchNorm_{int(bm.group(1)) - 1}", l), None)
            if sub == "downsample":
                which = rest[3]
                leaf = rest[4]
                if which == "0" and leaf == "weight":
                    return (("params", "encoder", mod, "downsample_conv",
                             "kernel"), "conv")
                if which == "1":
                    coll, l = _BN_LEAF[leaf]
                    return ((coll, "encoder", mod, "downsample_bn", l), None)
        if rest[0] == "fc":
            # The encoder's original fc: replaced by Identity in the
            # reference (resnet_simclr.py:21); nothing to load into.
            return None
    if parts[0] == "linear":
        if parts[1] == "weight":
            return (("params", "linear", "kernel"), "dense")
        if parts[1] == "bias":
            return (("params", "linear", "bias"), None)
    raise KeyError(f"No Flax mapping for torch key '{key}'")


def _transform(value: np.ndarray, kind: Optional[str]) -> np.ndarray:
    if kind == "conv":
        return np.transpose(value, (2, 3, 1, 0))  # OIHW -> HWIO
    if kind == "dense":
        return np.transpose(value, (1, 0))  # [C, D] -> [D, C]
    return value


def map_torch_state(like: Mapping[FlaxPath, Any],
                    torch_state: Mapping[str, np.ndarray],
                    strict: bool = True,
                    key_map=None) -> Dict[FlaxPath, np.ndarray]:
    """Every mappable checkpoint tensor as the model's own leaf: keyed
    by its Flax path, transposed to the Flax layout and cast to the
    dtype of ``like[path]``.  ``like`` is the flattened variable tree;
    only ``.shape`` and ``.dtype`` of its leaves are read, so an abstract
    tree (``jax.eval_shape`` of ``model.init``) serves and no device
    array is fetched.  Shape mismatches always raise; unknown keys raise
    when ``strict``.  ``key_map``: the backbone's own checkpoint layout
    (``torch_key_to_flax`` of models/backbone.py's contract; default the
    ResNets').  A transform ``("slot", i)`` puts the tensor at index i
    of a stacked leaf (one expert of a layer's experts)."""
    key_map = key_map or torch_key_to_flax
    covered: Dict[FlaxPath, np.ndarray] = {}
    for key, value in torch_state.items():
        try:
            mapped = key_map(key)
        except KeyError:
            if strict:
                raise
            continue
        if mapped is None:
            continue
        path, kind = mapped
        if path not in like:
            raise KeyError(
                f"Checkpoint key '{key}' maps to {'/'.join(path)}, absent "
                f"from the model (wrong depth/variant?)")
        if isinstance(kind, tuple):
            slot, shape = kind[1], tuple(like[path].shape)
            if slot >= shape[0]:
                continue               # an expert another chip holds
            if tuple(value.shape) != shape[1:]:
                raise ValueError(
                    f"Shape mismatch for '{key}' -> {'/'.join(path)}"
                    f"[{slot}]: ckpt {value.shape} vs model {shape[1:]}")
            if path not in covered:
                covered[path] = np.empty(shape, like[path].dtype)
            covered[path][slot] = value
            continue
        arr = _transform(np.asarray(value), kind)
        shape = tuple(like[path].shape)
        if (path[-2:] == ("conv_stem", "kernel") and arr.shape[:2] == (7, 7)
                and shape[:2] == (4, 4)):
            # s2d-stem model consuming a standard 7x7-stem checkpoint:
            # fold the kernel exactly (models/resnet.s2d_stem_kernel) —
            # the loaded network computes the identical convolution.
            from ..models.resnet import s2d_stem_kernel
            arr = np.asarray(s2d_stem_kernel(arr))
        if shape != tuple(arr.shape):
            raise ValueError(
                f"Shape mismatch for '{key}' -> {'/'.join(path)}: "
                f"ckpt {arr.shape} vs model {shape}")
        covered[path] = arr.astype(like[path].dtype, copy=False)
    get_logger().info(f"Overlaid {len(covered)} pretrained tensors")
    return covered


def overlay_torch_state(variables: Dict[str, Any],
                        torch_state: Mapping[str, np.ndarray],
                        strict: bool = True) -> Dict[str, Any]:
    """Partial update: write every mappable checkpoint tensor into a copy of
    ``variables`` (the reference's ``init_dict.update(net_dict)``,
    load_pretrained_weights.py:64-65)."""
    from flax.traverse_util import flatten_dict, unflatten_dict
    flat = flatten_dict(variables)
    flat.update(map_torch_state(flat, torch_state, strict))
    return unflatten_dict(flat)


def pretrained_leaves(like: Mapping[FlaxPath, Any], cfg: PretrainedConfig,
                      state: Mapping[str, np.ndarray], key_map=None
                      ) -> Dict[FlaxPath, np.ndarray]:
    """Surgery -> mapping: the leaves the checkpoint ``state`` covers
    under ``cfg``'s key filters, as ``map_torch_state`` gives them.
    Strategy.init_network_weights builds its device-resident template
    from these, once per checkpoint file."""
    state = surgery(state, required_key=cfg.required_key,
                    skip_key=cfg.skip_key, replace_map=cfg.replace_map)
    return map_torch_state(like, state, key_map=key_map)


def apply_pretrained(variables: Dict[str, Any], cfg: PretrainedConfig
                     ) -> Dict[str, Any]:
    """Full pipeline on a host tree: load -> surgery -> overlay (the
    reference's load_pretrained_weights, strategy.py:185-196)."""
    from flax.traverse_util import flatten_dict, unflatten_dict
    flat = flatten_dict(variables)
    flat.update(pretrained_leaves(flat, cfg,
                                  load_torch_state_dict(cfg.path)))
    return unflatten_dict(flat)
