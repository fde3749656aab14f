"""Softmax-uncertainty acquisition: least-confidence and smallest-margin.

Reference: src/query_strategies/confidence_sampler.py:8-47 and
margin_sampler.py:8-45.  Both run one mesh-parallel scoring pass
(strategies/scoring.make_prob_stats_step) instead of the reference's
single-GPU loader walk; confidence and margin come out of the same fused
top-2 kernel.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ..telemetry import spans as tele_spans
from .base import Strategy, register_strategy


class _ScoreAscendingSampler(Strategy):
    """Shared shape: score every available example, take the ``budget``
    smallest."""

    score_key: str = ""

    def speculative_scoring_plan(self):
        """The coming query scores the UNSHUFFLED available set — a pure
        function of the pool masks, no rng anywhere — so the pipelined
        round can pre-score it chunk by chunk during the fit's patience
        tail (experiment/pipeline.py)."""
        idxs = self.pool.available_query_idxs(shuffle=False)
        if len(idxs) == 0:
            return None
        return {"kind": "prob_stats", "keys": (self.score_key,),
                "idxs": idxs}

    def query(self, budget: int) -> Tuple[np.ndarray, int]:
        idxs = self.available_query_idxs(shuffle=False)
        if len(idxs) == 0:
            return idxs, 0
        scores = self.collect_scores(idxs, "prob_stats",
                                     keys=(self.score_key,))[self.score_key]
        budget = int(min(len(idxs), budget))
        with tele_spans.get_tracer().span("query/select"):
            order = np.argsort(scores, kind="stable")[:budget]
        return idxs[order], budget


@register_strategy("ConfidenceSampler")
class ConfidenceSampler(_ScoreAscendingSampler):
    """Smallest top-1 softmax probability first (confidence_sampler.py:33-36).

    Deliberately FIXES the reference's bug at confidence_sampler.py:41,
    which re-indexes the length-N confidence vector by pool indices
    (``confidence[idxs_for_query]``) before sorting — selecting by a
    scrambled score.  Here scores align 1:1 with ``idxs``.
    """

    score_key = "confidence"


@register_strategy("MarginSampler")
class MarginSampler(_ScoreAscendingSampler):
    """Smallest (top-1 − top-2) softmax probability margin first
    (margin_sampler.py:33-44)."""

    score_key = "margin"
