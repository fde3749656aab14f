"""Active-learning pool bookkeeping.

Replaces the mask-bookkeeping spread across the reference's ``Strategy`` base
class (``idxs_lb``/``idxs_lb_recent``/``eval_idxs``/``cumulative_cost`` and
the methods ``available_query_idxs``/``already_labeled_idxs``/``update``,
src/query_strategies/strategy.py:97-163,459-485) with an explicit, picklable
dataclass.  All randomness is taken from an injected ``numpy`` Generator so
runs are reproducible end-to-end (the reference relies on the global
``np.random`` state).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np


def bucket_size(n: int, floor: int = 256) -> int:
    """Bounded-waste geometric bucket: ``n`` rounded up to a multiple of
    1/8 of its enclosing power of two (never below ``floor``).

    THE shape-bucketing rule for everything whose size tracks the growing
    labeled set (or the shrinking unlabeled pool) across AL rounds: the
    trainer's epoch-scan step count, its device-resident row upload, and
    the k-center selection pool are all padded to this bucket so round
    N+1 reuses round N's compiled executables instead of paying a fresh
    XLA compile per round (padding is masked out of every computation by
    the callers).

    Why not plain next-power-of-two: a padded pool row is masked out of
    the RESULTS but not the COMPUTE — it still rides every distance
    matmul — so just past a pow2 boundary pure pow2 buckets would
    re-spend up to ~2x compute on EVERY pick to save one recompile per
    round.  (The trainer's padding costs no compute: the epoch program
    takes its trip count from ``valid`` and runs the real steps only, so
    there the bucket bounds the index matrices and the resident row
    upload — bytes, every epoch and every round.)  The 1/8-octave
    granularity caps that recurring waste at 25% worst-case (just past
    a power of two; typically well under 10%) while keeping the
    distinct-shape count small (8 buckets per doubling) so consecutive
    rounds still reuse executables.  ``floor`` pins tiny inputs to one
    fixed bucket.
    """
    n = max(int(n), int(floor))
    gran = max(int(floor), (1 << (n - 1).bit_length()) // 8)
    return -(-n // gran) * gran


@dataclasses.dataclass
class PoolState:
    """Boolean-mask view of the unlabeled pool.

    Attributes:
      n_pool: total number of candidate examples (== len(al_set)).
      labeled: bool[n_pool]; True where the example has been labeled.
      recent: indices labeled by the most recent ``update`` call.
      eval_idxs: validation indices carved out of the train set; never
        queryable (strategy.py:138,144).
      invalid: bool[n_pool]; True for slots that hold NO real example —
        the streaming subsystem (active_learning_tpu/stream/) grows the
        pool by bucket_size-aligned extents so the resident-upload shape
        ladder stays enumerable, and the padding slots between the valid
        row count and the extent capacity are neither queryable, nor
        labelable, nor eval.  A frozen-disk-pool experiment (the
        reference protocol) never sets any of these.
      cumulative_cost: total budget spent so far.
      round: current AL round.
    """

    n_pool: int
    labeled: np.ndarray
    eval_idxs: np.ndarray
    recent: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, dtype=np.int64))
    cumulative_cost: float = 0.0
    round: int = 0
    invalid: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, dtype=bool))

    def __post_init__(self):
        if self.invalid.size == 0 and self.n_pool:
            self.invalid = np.zeros(self.n_pool, dtype=bool)

    @classmethod
    def create(cls, n_pool: int, eval_idxs: Sequence[int]) -> "PoolState":
        return cls(
            n_pool=int(n_pool),
            labeled=np.zeros(n_pool, dtype=bool),
            eval_idxs=np.asarray(eval_idxs, dtype=np.int64),
        )

    # -- queries ---------------------------------------------------------

    def available_mask(self) -> np.ndarray:
        """Bool mask of queryable examples: unlabeled, not in the eval
        split (strategy.py:139-142), and not a padding/placeholder slot
        (``invalid``)."""
        mask = ~self.labeled
        if self.eval_idxs.size:
            mask[self.eval_idxs] = False
        if self.invalid.size:
            mask &= ~self.invalid
        return mask

    def available_query_idxs(
        self,
        shuffle: bool = True,
        rng: Optional[np.random.Generator] = None,
    ) -> np.ndarray:
        """Indices of queryable examples, optionally shuffled
        (strategy.py:143-145: shuffle precedes eval-idx filtering, so the
        order is a permutation of the unlabeled set)."""
        idxs = np.flatnonzero(self.available_mask())
        if shuffle:
            if rng is None:
                raise ValueError("shuffle=True requires an explicit rng")
            idxs = rng.permutation(idxs)
        return idxs

    def labeled_idxs(
        self,
        shuffle: bool = False,
        rng: Optional[np.random.Generator] = None,
    ) -> np.ndarray:
        idxs = np.flatnonzero(self.labeled)
        if shuffle:
            if rng is None:
                raise ValueError("shuffle=True requires an explicit rng")
            idxs = rng.permutation(idxs)
        return idxs

    def labeled_mask(self) -> np.ndarray:
        return self.labeled.copy()

    @property
    def num_labeled(self) -> int:
        return int(self.labeled.sum())

    @property
    def num_available(self) -> int:
        return int(self.available_mask().sum())

    # -- mutation --------------------------------------------------------

    def update(self, labeled_idxs: Sequence[int], cost: float) -> None:
        """Mark ``labeled_idxs`` as labeled; add ``cost`` to the budget.

        Enforces the reference's invariants (strategy.py:468-471): no
        example may be labeled twice, and a query batch may not contain
        duplicates.
        """
        idxs = np.asarray(labeled_idxs, dtype=np.int64).reshape(-1)
        if idxs.size:
            if idxs.min() < 0 or idxs.max() >= self.n_pool:
                raise ValueError(
                    f"indices out of range [0, {self.n_pool}): "
                    f"{idxs[(idxs < 0) | (idxs >= self.n_pool)][:10].tolist()}")
            if np.unique(idxs).size != idxs.size:
                raise ValueError("query returned duplicate indices")
            if self.labeled[idxs].any():
                dup = idxs[self.labeled[idxs]][:10]
                raise ValueError(
                    f"examples already labeled: {dup.tolist()}")
            if self.eval_idxs.size and np.isin(idxs, self.eval_idxs).any():
                raise ValueError("query returned validation indices")
            if self.invalid.size and self.invalid[idxs].any():
                bad = idxs[self.invalid[idxs]][:10]
                raise ValueError(
                    f"query returned invalid (padding) slots: {bad.tolist()}")
            self.labeled[idxs] = True
        self.recent = idxs
        self.cumulative_cost += float(cost)

    # -- streaming growth (active_learning_tpu/stream/) -------------------

    def grow(self, n_pool: int) -> None:
        """Extend the pool to ``n_pool`` slots.  New slots arrive INVALID
        (padding) — ``set_valid`` opens them once real rows land in them.
        Shrinking is refused: pool slots are append-only so index i means
        the same example for the life of the experiment (the WAL/resume
        contract of the streaming subsystem depends on it)."""
        n_pool = int(n_pool)
        if n_pool < self.n_pool:
            raise ValueError(
                f"pool cannot shrink ({self.n_pool} -> {n_pool}); slots "
                "are append-only")
        if n_pool == self.n_pool:
            return
        extra = n_pool - self.n_pool
        self.labeled = np.concatenate(
            [self.labeled, np.zeros(extra, dtype=bool)])
        self.invalid = np.concatenate(
            [self.invalid if self.invalid.size else
             np.zeros(self.n_pool, dtype=bool),
             np.ones(extra, dtype=bool)])
        self.n_pool = n_pool

    def set_valid(self, n_valid: int) -> None:
        """Rows [0, n_valid) hold real examples; [n_valid, n_pool) stay
        padding.  Monotone: a slot once valid never goes back."""
        n_valid = int(n_valid)
        if n_valid > self.n_pool:
            raise ValueError(f"n_valid {n_valid} exceeds pool {self.n_pool}")
        if self.invalid.size == 0:
            self.invalid = np.zeros(self.n_pool, dtype=bool)
        self.invalid[:n_valid] = False

    def mark_valid(self, idxs: Sequence[int]) -> None:
        """Open specific slots: real (oracle-labeled) rows just landed
        in them — the streaming drain's per-extent validation."""
        idxs = np.asarray(idxs, dtype=np.int64).reshape(-1)
        if idxs.size:
            self.invalid[idxs] = False

    def mark_invalid(self, idxs: Sequence[int]) -> None:
        """Mark specific slots as placeholders (e.g. ingested rows with
        no oracle label yet — scoreable later, but not queryable)."""
        idxs = np.asarray(idxs, dtype=np.int64).reshape(-1)
        if idxs.size:
            if self.labeled[idxs].any():
                raise ValueError("cannot invalidate labeled slots")
            self.invalid[idxs] = True

    def absorb_labels(self, idxs: Sequence[int]) -> None:
        """Mark externally-labeled rows (the streaming /v1/label path) as
        labeled WITHOUT consuming budget or touching ``recent`` — these
        rows were never queried; their labels arrived from outside the
        loop.  Slots become valid as a side effect (a label IS the
        missing oracle information)."""
        idxs = np.asarray(idxs, dtype=np.int64).reshape(-1)
        if idxs.size == 0:
            return
        if idxs.min() < 0 or idxs.max() >= self.n_pool:
            raise ValueError(f"label indices out of range [0, {self.n_pool})")
        if self.labeled[idxs].any():
            dup = idxs[self.labeled[idxs]][:10]
            raise ValueError(f"rows already labeled: {dup.tolist()}")
        if self.eval_idxs.size and np.isin(idxs, self.eval_idxs).any():
            raise ValueError("cannot attach labels to validation rows")
        self.invalid[idxs] = False
        self.labeled[idxs] = True

    # -- (de)serialization ----------------------------------------------

    def to_arrays(self) -> dict:
        return {
            "n_pool": np.asarray(self.n_pool),
            "labeled": self.labeled.copy(),
            "eval_idxs": self.eval_idxs.copy(),
            "recent": self.recent.copy(),
            "cumulative_cost": np.asarray(self.cumulative_cost),
            "round": np.asarray(self.round),
            "invalid": (self.invalid.copy() if self.invalid.size else
                        np.zeros(self.n_pool, dtype=bool)),
        }

    @classmethod
    def from_arrays(cls, arrs: dict) -> "PoolState":
        n_pool = int(arrs["n_pool"])
        # Pre-stream saves carry no invalid mask: all slots are real.
        invalid = (np.array(arrs["invalid"], dtype=bool, copy=True)
                   if "invalid" in arrs else np.zeros(n_pool, dtype=bool))
        return cls(
            n_pool=n_pool,
            labeled=np.array(arrs["labeled"], dtype=bool, copy=True),
            eval_idxs=np.array(arrs["eval_idxs"], dtype=np.int64, copy=True),
            recent=np.array(arrs["recent"], dtype=np.int64, copy=True),
            cumulative_cost=float(arrs["cumulative_cost"]),
            round=int(arrs["round"]),
            invalid=invalid,
        )
