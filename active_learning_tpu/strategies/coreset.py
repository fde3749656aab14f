"""Coreset (k-Center greedy) and BADGE acquisition, plus their partitioned
variants.

Reference: src/query_strategies/coreset_sampler.py:8-133 (k-center greedy
over final embeddings, Sener & Savarese arXiv:1708.00489),
badge_sampler.py:13-78 (randomized k-center over gradient embeddings,
arXiv:1906.03671), partitioned_coreset_sampler.py:9-84 and
partitioned_badge_sampler.py:5-19 (random-partition escape hatch for the
O(N^2) distance matrix, arXiv:2107.14263).

TPU-first differences (see strategies/kcenter.py for the math):
  * the embedding / gradient-embedding pass is mesh-parallel
    (strategies/scoring.py) instead of a single-GPU loader walk;
  * the greedy selection runs fully on device over factorized embeddings
    (batched farthest-first, q picks per pool pass — cfg.kcenter_batch)
    — the N x N matrix the reference materializes
    (coreset_sampler.py:59-64) never exists, which also removes the reason
    partitioning was mandatory at ImageNet scale (it remains supported for
    parity and for bounding the embedding pass itself).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..telemetry import spans as tele_spans
from . import kcenter as kcenter_lib
from .base import Strategy, register_strategy
from .kcenter import kcenter_greedy

Factors = Tuple[np.ndarray, ...]


@register_strategy("CoresetSampler")
class CoresetSampler(Strategy):
    """k-Center greedy: repeatedly pick the unlabeled point farthest from
    the labeled set in final-embedding space (coreset_sampler.py:66-105)."""

    randomize = False
    # The reference caches its pairwise matrix across rounds when features
    # are frozen (coreset_sampler.py:112-121) — embeddings are constant so
    # the factors are cached here instead (smaller, same validity).  BADGE
    # never populates the cache (its query recomputes gradient embeddings
    # every round; the saved_pairwise_l2_dist assignment is absent from
    # badge_sampler.py:60-65).
    cache_factors = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._saved_factors: Optional[Factors] = None

    # -- pool subsetting (coreset_sampler.py:21-41) -----------------------

    def get_idxs_for_coreset(self, return_sep_idxs: bool = False):
        """The index set the selection runs over: all available + all
        labeled (minus eval), with optional ``subset_labeled`` /
        ``subset_unlabeled`` caps.  The unlabeled cap inherits any unused
        labeled quota (coreset_sampler.py:28-34)."""
        idxs_for_query = self.available_query_idxs(shuffle=True)
        idxs_labeled = self.already_labeled_idxs(shuffle=True)
        subset_labeled = self.cfg.subset_labeled
        subset_unlabeled = self.cfg.subset_unlabeled

        if subset_labeled is not None:
            cap_lb = min(subset_labeled, len(idxs_labeled))
            idxs_labeled = idxs_labeled[:cap_lb]
        if subset_unlabeled is not None:
            if subset_labeled is not None:
                cap_ul = subset_labeled + subset_unlabeled - cap_lb
            else:
                cap_ul = subset_unlabeled
            cap_ul = min(cap_ul, len(idxs_for_query))
            idxs_for_query = idxs_for_query[:cap_ul]

        idxs_for_coreset = np.sort(np.concatenate(
            [idxs_for_query, idxs_labeled])).astype(np.int64)
        if return_sep_idxs:
            return idxs_for_coreset, idxs_labeled, idxs_for_query
        return idxs_for_coreset

    # -- embeddings -------------------------------------------------------

    def get_factors(self, idxs: np.ndarray) -> Factors:
        """Factor matrices for the pairwise distances; one mesh-parallel
        embedding pass (coreset_sampler.py:43-57)."""
        out = self.collect_scores(idxs, "embed", keys=("embedding",))
        return (out["embedding"],)

    def _factors_with_cache(self, idxs: np.ndarray) -> Factors:
        subsets_off = (self.cfg.subset_labeled is None
                       and self.cfg.subset_unlabeled is None)
        cacheable = (self.cache_factors and self.cfg.freeze_feature
                     and subsets_off)
        # Cache validity relies on idxs being identical across rounds,
        # which holds exactly when the subset caps are off: the sorted
        # union of available+labeled is all non-eval indices, a constant.
        if cacheable and self._saved_factors is not None:
            return self._saved_factors
        factors = self.get_factors(idxs)
        if cacheable:
            self._saved_factors = factors
        return factors

    # -- speculative plan (the pipelined round) ---------------------------

    # The scoring pass collect_scores will run: fixed per subclass so
    # the speculative plan and query can never disagree on the
    # statistic.
    spec_kind = "embed"
    spec_keys = ("embedding",)

    def speculative_scoring_plan(self):
        """The coming query's embedding pass, rng-free: with the subset
        caps off, ``idxs_for_coreset`` is the SORTED union of available
        and labeled indices — a pure function of the pool masks — even
        though query() builds it from two rng-shuffled views.  With a
        cap on, the subset IS an rng draw, so the round runs
        un-speculated; same when the frozen-feature factor cache already
        holds the answer (nothing will be scored at all)."""
        if (self.cfg.subset_labeled is not None
                or self.cfg.subset_unlabeled is not None):
            return None
        if (self.cache_factors and self.cfg.freeze_feature
                and self._saved_factors is not None):
            return None
        available = self.pool.available_query_idxs(shuffle=False)
        if len(available) == 0:
            return None
        idxs = np.sort(np.concatenate(
            [available, self.pool.labeled_idxs()])).astype(np.int64)
        return {"kind": self.spec_kind, "keys": self.spec_keys,
                "idxs": idxs}

    # -- query ------------------------------------------------------------

    def query(self, budget: int) -> Tuple[np.ndarray, int]:
        idxs_for_coreset, _, idxs_for_query = self.get_idxs_for_coreset(
            return_sep_idxs=True)
        if len(idxs_for_query) == 0:
            return np.zeros(0, dtype=np.int64), 0
        factors = self._factors_with_cache(idxs_for_coreset)
        labeled_mask = self.already_labeled_mask()[idxs_for_coreset]
        budget = int(min(len(idxs_for_query), budget))
        with tele_spans.get_tracer().span("query/select"):
            picks = kcenter_greedy(factors, labeled_mask, budget,
                                   randomize=self.randomize, rng=self.rng,
                                   batch_q=self.cfg.kcenter_batch,
                                   mesh=self.mesh,
                                   pool_sharding=self.trainer.pool_sharding)
        # Pick-time distance-to-labeled, captured from the selection
        # scan's own values (telemetry/diagnostics, DESIGN.md §13) —
        # one gated call, picks unaffected.
        self._record_pick_dist_diagnostics(kcenter_lib.LAST_PICK_DISTS)
        selected = idxs_for_coreset[picks]
        assert len(np.unique(selected)) == len(selected), (
            "k-center selected a duplicate index")
        self.logger.info(f"Number of queried images: {len(selected)}")
        return selected, len(selected)


@register_strategy("BADGESampler")
class BADGESampler(CoresetSampler):
    """Randomized k-center (k-means++ D^2 draws) over gradient embeddings
    (badge_sampler.py:50-78).  The factors are (softmax - onehot, embedding)
    — the outer product is never formed."""

    randomize = True
    cache_factors = False
    spec_kind = "badge"
    spec_keys = ("grad_a", "grad_e")

    def get_factors(self, idxs: np.ndarray) -> Factors:
        out = self.collect_scores(idxs, "badge", keys=("grad_a", "grad_e"))
        return (out["grad_a"], out["grad_e"])


@register_strategy("PartitionedCoresetSampler")
class PartitionedCoresetSampler(CoresetSampler):
    """Random-partition k-center: split labeled and unlabeled separately
    into ``partitions`` equal shards (so every shard sees the same
    labeled/unlabeled balance), run k-center per shard with a proportional
    budget share (partitioned_coreset_sampler.py:36-84)."""

    def speculative_scoring_plan(self):
        """Partitions are rng draws (generate_partition_idxs_list
        shuffles with the experiment rng), so the per-partition scoring
        order cannot be known ahead of the query — no speculation."""
        return None

    def generate_partition_idxs_list(self, input_idxs: np.ndarray):
        idxs = np.array(input_idxs)
        self.rng.shuffle(idxs)
        n, p = len(idxs), self.cfg.partitions
        parts, cum = [], 0
        for i in range(p):
            cur = n // p + int(i < n % p)
            parts.append(idxs[cum:cum + cur])
            cum += cur
        return parts

    def query(self, budget: int) -> Tuple[np.ndarray, int]:
        return self._query_partitioned(budget)

    def _query_partitioned(self, budget: int) -> Tuple[np.ndarray, int]:
        if self.cfg.partitions > 1 and self.mesh.devices.size > 1:
            # Partitioning was the reference's ONLY answer past the
            # single-chip memory ceiling; the row-sharded pool
            # (--pool_sharding row, DESIGN.md §2b) scales the
            # no-partition scan with chip count instead — and unlike
            # partitioning it keeps the pick sequence identical to the
            # global greedy.  Kept for parity and statistical variants.
            self.logger.warning(
                f"--partitions {self.cfg.partitions} on a "
                f"{self.mesh.devices.size}-device mesh is a legacy "
                "fallback: --pool_sharding row shards the factor matrix "
                "across chips and selects over the FULL pool "
                "(DESIGN.md §3); partitioning remains only for parity "
                "and statistical variety")
        _, idxs_labeled, idxs_for_query = self.get_idxs_for_coreset(
            return_sep_idxs=True)
        if len(idxs_for_query) == 0:
            return np.zeros(0, dtype=np.int64), 0
        labeled_parts = self.generate_partition_idxs_list(idxs_labeled)
        unlabeled_parts = self.generate_partition_idxs_list(idxs_for_query)

        budget = int(min(len(idxs_for_query), budget))
        p = self.cfg.partitions
        selected = []
        for i in range(p):
            part = np.concatenate(
                [labeled_parts[i], unlabeled_parts[i]]).astype(np.int64)
            cur_budget = budget // p + int(i < budget % p)
            # budget <= total unlabeled and both splits use the same
            # i < n % p rule, so cur_budget <= len(unlabeled_parts[i]).
            if cur_budget == 0 or len(part) == 0:
                continue
            factors = self.get_factors(part)
            labeled_mask = np.zeros(len(part), dtype=bool)
            labeled_mask[:len(labeled_parts[i])] = True
            with tele_spans.get_tracer().span("query/select"):
                picks = kcenter_greedy(
                    factors, labeled_mask, cur_budget,
                    randomize=self.randomize, rng=self.rng,
                    batch_q=self.cfg.kcenter_batch, mesh=self.mesh,
                    pool_sharding=self.trainer.pool_sharding)
            # Per-partition pick distances accumulate into the same
            # round diagnostics (each call refreshes the scan global).
            self._record_pick_dist_diagnostics(
                kcenter_lib.LAST_PICK_DISTS)
            selected.append(part[picks])

        selected = (np.sort(np.concatenate(selected)) if selected
                    else np.zeros(0, dtype=np.int64))
        assert len(np.unique(selected)) == len(selected), (
            "partitioned k-center selected a duplicate index")
        self.logger.info(f"Number of queried images: {len(selected)}")
        return selected, len(selected)


@register_strategy("PartitionedBADGESampler")
class PartitionedBADGESampler(PartitionedCoresetSampler):
    """Partitioned randomized k-center over POOLED gradient embeddings
    (partitioned_badge_sampler.py:14-19: adaptive-pool to 512 dims, then
    the partitioned D^2 selection)."""

    randomize = True
    cache_factors = False

    def get_factors(self, idxs: np.ndarray) -> Factors:
        out = self.collect_scores(idxs, "badge_pool",
                                  keys=("grad_a", "grad_e"))
        return (out["grad_a"], out["grad_e"])
