"""The backbone contract: what the trainer, the strategies and the
checkpoint path ask of a model, whatever its family.

Implementers: the ResNets (models/resnet.py), the MLA + sparse-experts token
encoder (models/mla_moe.py) and the shortcut-connected MoE token encoder
(models/shortcut_moe.py).  A backbone is a ``flax.linen.Module`` with

  ``apply(variables, x, train=..., return_features=False)``
      -> logits, or ``(logits, embedding)``; ``x`` is a batch of rows as the
      dataset's view hands them over (``data/augment.apply_view``).
  ``embed_dim``
      the embedding's width.
  ``freeze_feature``
      linear evaluation: the fit moves the head only.
  mutable state (optional)
      a ``batch_stats`` collection where the model has BatchNorm, none where
      it has not: ``TrainState.batch_stats`` is then ``{}``.
  ``frozen_prefixes`` (optional)
      the top-level keys of ``params`` a fit never moves: the token
      encoders (models/mla_moe.py, models/shortcut_moe.py), which are built
      under ``freeze_feature`` only (their factory refuses otherwise),
      declare ``("encoder",)``.  A model that
      declares nothing keeps every leaf in ``params`` (the ResNets,
      whose frozen leaves get a zero gradient as they always did: they
      opt in with one line once nothing reads their encoder out of
      ``state.params`` any more, ROADMAP R2).
  ``input_stage(x)`` (optional method)
      the model's own part of the view, applied under the ``view`` scope in
      front of the forward: a token encoder's embedding lookup.
  ``row_counters`` (optional)
      names of the per-row counts the forward sows into the ``counters``
      collection (``[batch]`` int32 each): they ride the scoring pass's and
      the epoch's span as counters.  The token encoders sow ``pairs_real``,
      ``pairs_run`` and ``expert_trips`` (held (token, expert) pairs
      chosen, the slots the tiles were shaped for, and the chunks of slots
      the expert layers ran); the shortcut-connected one also
      ``pairs_zero`` (picks that fell on a zero-compute expert) and
      ``pairs_routed`` (all picks: k x tokens x layers).
  ``torch_key_to_flax(key)`` (optional)
      the checkpoint layout: a torch state-dict key -> (flax path, transform)
      as ``utils/pretrained.torch_key_to_flax`` maps the ResNets'.

The frozen leaves live beside the trainable ones, never among them
(``TrainState.frozen`` against ``TrainState.params``): the optimizer state,
the gradient tree, the round snapshot, the checkpoint files and the
re-initialisation template are shaped like ``params`` alone, and a frozen
leaf is loaded once and never copied, fetched or written again.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Tuple


def frozen_prefixes(model) -> Tuple[str, ...]:
    """The top-level ``params`` keys the model's fit never moves."""
    return tuple(getattr(model, "frozen_prefixes", ()))


def split_params(params: Mapping[str, Any], prefixes: Tuple[str, ...]
                 ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``params`` -> (trainable, frozen) by top-level key."""
    frozen = {k: v for k, v in params.items() if k in prefixes}
    trainable = {k: v for k, v in params.items() if k not in prefixes}
    return trainable, frozen


def merge_params(trainable: Mapping[str, Any], frozen: Mapping[str, Any]
                 ) -> Dict[str, Any]:
    """The tree ``model.apply`` reads: a new dict over the same leaves."""
    if not frozen:
        return trainable
    return {**frozen, **trainable}


def is_frozen_path(path: Tuple[str, ...], prefixes: Tuple[str, ...]) -> bool:
    """``path`` is a flattened variable path ``(collection, key, ...)``."""
    return len(path) > 1 and path[0] == "params" and path[1] in prefixes


def input_stage(model, variables, x):
    """The model's own input stage (``input_stage``), where it has one."""
    if not hasattr(type(model), "input_stage"):
        return x
    return model.apply(variables, x, method="input_stage")


def sum_counters(collection: Mapping[str, Any], names: Tuple[str, ...],
                 mask) -> Dict[str, Any]:
    """The ``counters`` collection a forward sowed -> {name: int32 scalar}.
    A ``[batch]`` count is summed over the rows ``mask`` keeps (a padding
    row counts nothing); a scalar count (what the device ran for the whole
    batch, padding included) is taken as it is."""
    import jax
    import jax.numpy as jnp
    out = {}
    if not names:
        return out
    flat = jax.tree_util.tree_flatten_with_path(collection)[0]
    for name in names:
        total = jnp.zeros((), jnp.int32)
        for path, leaf in flat:
            if any(getattr(k, "key", None) == name for k in path):
                if leaf.ndim:
                    leaf = jnp.sum(leaf * (mask > 0).astype(leaf.dtype))
                total = total + leaf.astype(jnp.int32)
        out[name] = total
    return out
