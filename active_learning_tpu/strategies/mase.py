"""MASE and BASE: decision-boundary-distance acquisition.

Reference: src/query_strategies/mase_sampler.py:6-96 (minimum distance to a
one-vs-one decision boundary of the linear head, in final-embedding space)
and base_sampler.py:6-41 (its class-balanced variant).

The closed-form radii are computed fully on device in one fused pass per
batch (strategies/scoring.boundary_radii); the reference's mathematical
self-check — perturbing an embedding by the optimal epsilon must land it on
the decision boundary (mase_sampler.py:85-90) — is a unit test here
(tests/test_samplers.py) instead of a runtime assert.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ..telemetry import spans as tele_spans
from .base import Strategy, register_strategy


@register_strategy("MASESampler")
class MASESampler(Strategy):
    """Examples closest to ANY decision boundary first
    (mase_sampler.py:20-28)."""

    def speculative_scoring_plan(self):
        """Both MASE and BASE score the UNSHUFFLED available set (no
        rng), so the pipelined round pre-scores it; keys None = every
        output of the mase step (query reads margin, radii, AND pred)."""
        idxs = self.pool.available_query_idxs(shuffle=False)
        if len(idxs) == 0:
            return None
        return {"kind": "mase", "keys": None, "idxs": idxs}

    def compute_margins(self, idxs: np.ndarray):
        """(min_margins, per_class_radii, pred_labels) for ``idxs``
        (mase_sampler.py:30-96, vectorized + sharded)."""
        out = self.collect_scores(idxs, "mase")
        return out["min_margin"], out["radii"], out["pred"]

    def query(self, budget: int) -> Tuple[np.ndarray, int]:
        idxs = self.available_query_idxs(shuffle=False)
        if len(idxs) == 0:
            return idxs, 0
        min_margins, _, _ = self.compute_margins(idxs)
        budget = int(min(len(idxs), budget))
        with tele_spans.get_tracer().span("query/select"):
            order = np.argsort(min_margins, kind="stable")[:budget]
        return idxs[order], budget


@register_strategy("BASESampler")
class BASESampler(MASESampler):
    """Class-balanced MASE: per-(predicted)-class quota of
    ``budget/num_classes`` (+1 for the first ``budget % C`` classes), where
    a point's distance *for class c* is its min margin if it is predicted c,
    else its radius to the c-boundary (base_sampler.py:22-35)."""

    def query(self, budget: int) -> Tuple[np.ndarray, int]:
        idxs = self.available_query_idxs(shuffle=False)
        if len(idxs) == 0:
            return idxs, 0
        min_margins, radii, preds = self.compute_margins(idxs)
        budget = int(min(len(idxs), budget))

        taken = np.zeros(len(idxs), dtype=bool)
        selected = []
        with tele_spans.get_tracer().span("query/select"):
            for c in range(self.num_classes):
                quota = budget // self.num_classes + int(
                    c < budget % self.num_classes)
                if quota == 0:
                    continue
                dist = np.where(preds == c, min_margins, radii[:, c])
                dist = np.where(taken, np.inf, dist)
                picks = np.argsort(dist, kind="stable")[:quota]
                taken[picks] = True
                selected.extend(picks.tolist())
        assert len(selected) == len(set(selected))
        return idxs[np.asarray(selected, dtype=np.int64)], budget
