"""Jitted, mesh-sharded evaluation metrics.

Replaces src/utils/evaluation.py: ``accuracy`` (top-1/top-5/per-class over a
loader, :11-66) and ``gather_parallel_eval`` (NCCL all_gather of counts,
:69-98).  On TPU the per-batch counts are computed in one jitted function
over the sharded batch; the cross-device reduction is a by-product of the
sharding (XLA inserts the collective), so there is no separate gather step.
Final division happens on host once all batches are accumulated — identical
math to the reference's corrects/count bookkeeping.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data.augment import apply_view
from ..data.core import ViewSpec
from ..models import backbone
# The calibration bin count is owned by the host-pure diagnostics layer
# (telemetry/diagnostics.py) so the device counts here and the host ECE
# there can never disagree on the ladder.
from ..telemetry.diagnostics import NUM_CAL_BINS


def batch_metric_counts(logits: jnp.ndarray, labels: jnp.ndarray,
                        mask: jnp.ndarray, num_classes: int,
                        top_k: int = 5) -> Dict[str, jnp.ndarray]:
    """Counts for one batch: top-1/top-k corrects, per-class corrects and
    totals, plus the calibration bins (per-confidence-bin count /
    correct / confidence-sum — additive, so they merge across batches,
    chunks, and shards exactly like the accuracy counts; the host side
    derives ECE in telemetry/diagnostics.ece_from_counts).  Padding rows
    (mask 0) contribute nothing.  The calibration counts piggyback on
    the logits this function already holds — the experiment-truth
    layer's zero-extra-pass rule (DESIGN.md §13)."""
    k = min(top_k, num_classes)
    _, topk_pred = jax.lax.top_k(logits, k)
    hit_topk = (topk_pred == labels[:, None]).any(axis=1)
    top1 = topk_pred[:, 0] == labels
    maskf = mask.astype(jnp.float32)
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32) * maskf[:, None]
    conf = jnp.max(jax.nn.softmax(logits.astype(jnp.float32), axis=-1),
                   axis=-1)
    cal_bin = jnp.clip((conf * NUM_CAL_BINS).astype(jnp.int32), 0,
                       NUM_CAL_BINS - 1)
    cal_onehot = jax.nn.one_hot(cal_bin, NUM_CAL_BINS,
                                dtype=jnp.float32) * maskf[:, None]
    return {
        "top_1_correct": jnp.sum(top1 * maskf),
        "top_k_correct": jnp.sum(hit_topk * maskf),
        "corrects_byclass": jnp.sum(onehot * (top1 * maskf)[:, None], axis=0),
        "count_byclass": jnp.sum(onehot, axis=0),
        "count": jnp.sum(maskf),
        "cal_count": jnp.sum(cal_onehot, axis=0),
        "cal_correct": jnp.sum(cal_onehot * (top1 * maskf)[:, None],
                               axis=0),
        "cal_conf_sum": jnp.sum(cal_onehot * conf[:, None], axis=0),
    }


# Registered step-builder (scripts/al_lint.py recompile-hazard): the
# eval step is built once per (model, view) and cached by the trainer.
_STEP_BUILDERS = ("make_eval_step",)


def view_forward(model, view: ViewSpec, variables, batch, counters=None,
                 **apply_kw):
    """The front every forward-only step shares (evaluation and the
    scoring steps of strategies/scoring.py): the eval view (the dataset's,
    then the backbone's own input stage), then the forward pass, each
    under its named scope (``view``, ``forward``) so a device trace
    attributes the step's operations to them.  Scopes are metadata; the
    compiled program is the same without them.  A step that hands in a
    ``counters`` dict gets the model's ``row_counters`` of this batch put
    into it as ``counter/<name>`` (models/backbone.py)."""
    with jax.named_scope("view"):
        x = apply_view(batch["image"], view, train=False)
        x = backbone.input_stage(model, variables, x)
    with jax.named_scope("forward"):
        names = tuple(getattr(model, "row_counters", ()))
        if counters is None or not names:
            return model.apply(variables, x, train=False, **apply_kw)
        out, mutated = model.apply(variables, x, train=False,
                                   mutable=["counters"], **apply_kw)
        # The step's counts, as [batch] vectors with the count in row 0:
        # they travel with the scores and add up over a pass.
        for name, total in backbone.sum_counters(
                mutated["counters"], names, batch["mask"]).items():
            counters[f"counter/{name}"] = jnp.zeros(
                batch["mask"].shape, jnp.int32).at[0].set(total)
        return out


def make_eval_step(model, view: ViewSpec, num_classes: int):
    """Jitted: uint8 batch -> metric counts.  The batch arrives sharded over
    the mesh's data axis; XLA reduces the counts across devices."""

    @jax.jit
    def eval_step(variables, batch):
        logits = view_forward(model, view, variables, batch)
        with jax.named_scope("score_head"):
            return batch_metric_counts(logits, batch["label"],
                                       batch["mask"], num_classes)

    return eval_step


def accumulate_metrics(count_iter: Iterator[Dict[str, jnp.ndarray]]
                       ) -> Dict[str, np.ndarray]:
    """Sum per-batch counts and derive the reference's metric dict keys
    (evaluation.py:58-66): accuracy, top_5_accuracy, accuracy_byclass,
    corrects_byclass, count_byclass, count."""
    # Accumulate WITHOUT fetching: summing device arrays dispatches a tiny
    # async add per batch, and the single np.asarray at the end is the only
    # host sync — a per-batch fetch would block the host on every batch
    # and serialize the eval pipeline.
    totals = None
    for counts in count_iter:
        if totals is None:
            totals = dict(counts)
        else:
            totals = {k: totals[k] + counts[k] for k in totals}
    if totals is not None:
        totals = {k: np.asarray(v) for k, v in totals.items()}
    if totals is None:
        # Empty eval set (eval_split=0): report zero accuracy instead of
        # crashing mid-fit; callers treat 0 as "no signal".
        return {
            "accuracy": np.float32(0.0), "top_5_accuracy": np.float32(0.0),
            "accuracy_byclass": np.zeros(0, np.float32),
            "corrects_byclass": np.zeros(0, np.float32),
            "count_byclass": np.zeros(0, np.float32),
            "count": np.float32(0.0),
            "cal_count": np.zeros(NUM_CAL_BINS, np.float32),
            "cal_correct": np.zeros(NUM_CAL_BINS, np.float32),
            "cal_conf_sum": np.zeros(NUM_CAL_BINS, np.float32),
        }
    count = max(totals["count"], 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        byclass = totals["corrects_byclass"] / totals["count_byclass"]
    return {
        "accuracy": totals["top_1_correct"] / count,
        "top_5_accuracy": totals["top_k_correct"] / count,
        "accuracy_byclass": byclass,
        "corrects_byclass": totals["corrects_byclass"],
        "count_byclass": totals["count_byclass"],
        "count": count,
        # Calibration bins ride the same accumulation (ECE derives on
        # host: telemetry/diagnostics.ece_from_counts).
        "cal_count": totals["cal_count"],
        "cal_correct": totals["cal_correct"],
        "cal_conf_sum": totals["cal_conf_sum"],
    }
