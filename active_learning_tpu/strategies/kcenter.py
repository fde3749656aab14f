"""Device-resident greedy k-center (and k-means++-style randomized variant).

This is the sequential core of Coreset/BADGE acquisition.  The reference
materializes the full N x N squared-L2 matrix on GPU and, per selection
step, recomputes the min over all labeled columns
(src/query_strategies/coreset_sampler.py:59-105) — O(N^2) memory and
O(budget * N * L) work, with a host round-trip per step.

The TPU design keeps only the factor matrices and a length-N min-distance
vector on device and runs the whole selection on device — no N x N
matrix, no per-step host sync:

  * Embeddings are a tuple of FACTOR matrices.  Plain coreset is one factor
    X [N, D] with dot(i,j) = X_i . X_j.  BADGE's gradient embedding
    g_i = (softmax(z_i) - onehot(argmax z_i)) (x) e_i (badge_sampler.py:40)
    is rank-1, so it is stored as TWO factors (A [N, C], E [N, D]) with
    dot(i,j) = (A_i . A_j)(E_i . E_j) — the C*D-dim outer product is never
    materialized.  Adaptive average pooling of a rank-1 matrix is itself
    rank-1 (the mean over a bin rectangle of a_c * e_d is the product of
    the two bin means), so the pooled variant (badge_sampler.py:41-44)
    keeps the same factorized form.
  * Deterministic selection runs BATCHED: each step takes the top-q
    provisionally-farthest candidates, verifies them with an exact
    in-batch re-check (below), and folds all accepted picks into the
    min-distance vector with ONE [N, q] pass — the pool is read once per
    q picks instead of once per pick, and under a pool-sharded layout the
    strip min is shard-local so each step needs a single cross-shard
    reduction (see scoring.batched_min_dist_update).
  * The randomized (k-means++ D^2) mode stays one pick per step — a
    batched draw would change the sampling distribution.

**Batched farthest-first is exact.**  Let v_1 >= ... >= v_q be the top-q
current min-distances and T = v_q.  Candidate picks are accepted one at a
time in-batch: each sub-step recomputes the remaining candidates' exact
min-distances against the already-accepted picks (a [q, q] table — tiny)
and accepts the maximum iff it exceeds T strictly.  Every non-candidate's
distance only shrinks as picks accrue and started <= T, so an accepted
candidate dominates the whole pool — the pick sequence is identical to
q=1 greedy (pinned in tests/test_kcenter.py).  When the re-check fails
the step stops early; progress is still >= 1 pick (the first candidate is
the unbatched argmax).

**Backend.**  The XLA scans are the ONLY backend.  A fused Pallas
kernel existed through r5 behind a measured dispatcher; the on-MXU A/B
ran three times at 0.67x/1.11x/0.93x the XLA scan with
``pallas_picks_match: False`` every time, so it was deleted per the r5
verdict (wrong-on-hardware code behind an env var is a trap, not a
feature).  The decision record survives in DESIGN.md §5;
``LAST_BACKEND`` says which scan answered a call.

Pool shapes are padded to bounded-waste geometric buckets
(pool.bucket_size: 1/8-octave granularity — padded rows ride every
distance matmul, so the recurring compute waste stays bounded, 25%
worst-case) before the jitted scans, so subset-capped pools whose size
drifts across AL rounds reuse the previous round's executables; the
distance / selectable carries are donated, so each step updates them in
place.

Distances are SQUARED L2 throughout, matching the reference (it never
takes a sqrt; the randomized mode's selection probabilities are therefore
k-means++ D^2 weights, coreset_sampler.py:80-92).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel import mesh as mesh_lib
from ..pool import bucket_size

Factors = Tuple[jnp.ndarray, ...]

# Which scan answered the last kcenter_greedy call ("xla" sequential /
# "xla-batched"); tests/test_compile_reuse.py reads it.
LAST_BACKEND: Optional[str] = None

# Which pool layout the last kcenter_greedy call selected over
# ("replicated" / "row"); tests/test_pool_sharding.py reads it.
LAST_SHARDING: Optional[str] = None

# Whether the last kcenter_greedy call fed its initial-min/minimax
# column scans through the ring-permute feed (the row-sharded backend's
# only column feed since ISSUE 15); tests/test_pod_tier.py reads it.
# None until a call runs; False on the replicated backend.
LAST_RING_FEED: Optional[bool] = None

# Each pick's squared distance-to-(labeled ∪ earlier picks) AT PICK
# TIME, host float32 aligned with the last kcenter_greedy call's return
# (NaN marks the once-per-experiment minimax/uniform seed, which has no
# labeled set to be distant from).  The values already exist inside the
# selection scans — the argmax/top-k maximum IS the pick's distance —
# so riding them out beside the picks costs no extra pool pass, no
# extra collective, and cannot perturb the pick sequence (pinned in
# tests/test_diagnostics.py).  The experiment-truth layer
# (telemetry/diagnostics.py) reads this for rd_pick_min_dist /
# rd_pick_mean_dist and the k-center drift histogram.
LAST_PICK_DISTS: Optional[np.ndarray] = None

# Default q for the batched deterministic greedy: the f32 sublane tile
# (8), the smallest batch that both cuts scan steps ~8x and fills an MXU
# strip.  Overridden per experiment via ExperimentConfig.kcenter_batch.
DEFAULT_BATCH_Q = 8

# Pools are padded to the enclosing geometric bucket (>= this floor) so
# the jitted scans compile once per BUCKET, not once per subset-capped
# pool size; padded rows are zero factors masked out via ``selectable``.
POOL_BUCKET_FLOOR = 256

# Registered step-builders (scripts/al_lint.py recompile-hazard): the
# module-level jitted scans compile once per pool bucket; the sharded
# backend's jits live inside _build_sharded_fns (one set per
# (mesh, n_factors), cached in _SHARDED_JITS).  A jax.jit anywhere else
# in this module fails the lint.
_STEP_BUILDERS = ("_min_dist_chunk", "_kcenter_scan",
                  "_kcenter_scan_batched", "_minimax_row",
                  "_build_sharded_fns")


def self_sq_norms(factors: Factors) -> jnp.ndarray:
    """||g_i||^2 = prod_F (F_i . F_i)  — [N]."""
    out = None
    for f in factors:
        s = jnp.sum(f * f, axis=1)
        out = s if out is None else out * s
    return out


def dots_to(factors: Factors, idx) -> jnp.ndarray:
    """g_. . g_idx = prod_F (F @ F_idx)  — [N]."""
    out = None
    for f in factors:
        d = f @ f[idx]
        out = d if out is None else out * d
    return out


def dots_to_many(factors: Factors, idxs) -> jnp.ndarray:
    """g_. . g_j for j in idxs — [N, K] (blocked initial-min helper)."""
    out = None
    for f in factors:
        d = f @ f[idxs].T
        out = d if out is None else out * d
    return out


def dots_between(factors: Factors, idxs) -> jnp.ndarray:
    """g_i . g_j for i, j in idxs — [K, K] (the batched re-check table)."""
    out = None
    for f in factors:
        rows = f[idxs]
        d = rows @ rows.T
        out = d if out is None else out * d
    return out


@functools.partial(jax.jit, donate_argnums=(3,))
def _min_dist_chunk(factors: Factors, sqn: jnp.ndarray, chunk: jnp.ndarray,
                    min_dist: jnp.ndarray) -> jnp.ndarray:
    d = sqn[:, None] + sqn[chunk][None, :] - 2.0 * dots_to_many(factors, chunk)
    return jnp.minimum(min_dist, jnp.min(d, axis=1))


def min_sq_dist_to(factors: Factors, sqn: jnp.ndarray,
                   labeled_idxs: np.ndarray,
                   chunk_size: int = 1024) -> jnp.ndarray:
    """min_j in labeled ||g_i - g_j||^2 for all i, blocked so the live
    [N, chunk] tile stays small (the O(N^2) escape the reference lacks)."""
    n = sqn.shape[0]
    min_dist = jnp.full((n,), jnp.inf, dtype=jnp.float32)
    labeled_idxs = np.asarray(labeled_idxs)
    for start in range(0, len(labeled_idxs), chunk_size):
        chunk = labeled_idxs[start:start + chunk_size]
        if len(chunk) < chunk_size:  # pad with repeats: min is unaffected
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[:1], chunk_size - len(chunk))])
        min_dist = _min_dist_chunk(factors, sqn, jnp.asarray(chunk), min_dist)
    return min_dist


@functools.partial(jax.jit, static_argnames=("budget", "randomize"),
                   donate_argnums=(2, 3))
def _kcenter_scan(factors: Factors, sqn: jnp.ndarray, min_dist: jnp.ndarray,
                  selectable: jnp.ndarray, budget: int, randomize: bool,
                  key: jax.Array) -> jnp.ndarray:
    """The q=1 greedy loop as one scan (randomized mode, and the batched
    path's degenerate case).  ``selectable`` is 1.0 on unlabeled rows;
    labeled rows have min_dist ~ 0 so the deterministic argmax never
    picks them (mirroring the reference, which also relies on that)."""

    def step(carry, key):
        min_dist, selectable = carry
        if randomize:
            # k-means++ D^2 draw over unlabeled rows; if every unlabeled
            # distance is 0 the reference degenerates to a uniform draw via
            # its +=1e-5 retry loop (coreset_sampler.py:83-92).
            p = jnp.clip(min_dist, 0.0, None) * selectable
            total = jnp.sum(p)
            weights = jnp.where(total > 0, p, selectable)
            idx = jax.random.categorical(key, jnp.log(weights))
            # The pick's distance diagnostic is the draw's own weight
            # (clipped min-dist) — already materialized for the draw.
            dval = p[idx]
        else:
            # The reference relies on picked rows having min_dist == 0 to
            # avoid re-selection; under float32 the incremental update can
            # leave a tiny positive residual on dense pools, so mask
            # explicitly — same selection, no duplicate risk.
            masked = jnp.where(selectable > 0, min_dist, -jnp.inf)
            idx = jnp.argmax(masked)
            dval = masked[idx]
        d_new = sqn + sqn[idx] - 2.0 * dots_to(factors, idx)
        min_dist = jnp.minimum(min_dist, d_new)
        selectable = selectable.at[idx].set(0.0)
        return (min_dist, selectable), (idx, dval)

    keys = jax.random.split(key, budget)
    _, (picks, dists) = jax.lax.scan(step, (min_dist, selectable), keys)
    return picks, dists


def _recheck_candidates(cands: jnp.ndarray, vals: jnp.ndarray,
                        d_cc: jnp.ndarray, limit: jnp.ndarray,
                        sentinel: int):
    """Exact in-batch acceptance over the top-q candidates (see module
    docstring).  ``cands``/``vals`` come from top_k of the masked
    min-distances (descending, ties lowest-index first — matching
    argmax); ``d_cc`` is the [q, q] candidate pairwise distance table;
    ``limit`` caps accepted picks (budget remainder).  Returns
    (order [q] of candidate POSITIONS in acceptance order, n_acc,
    dvals [q] — each accepted pick's exact min-distance at acceptance,
    in acceptance order; an accepted candidate dominates the whole pool
    so this IS its distance-to-(labeled ∪ earlier picks), the number
    the experiment-truth diagnostics ride out)."""
    q = cands.shape[0]
    thresh = vals[q - 1]

    def body(_, st):
        cur, accepted, order, dvals, n_acc, last, stop = st
        cur = jnp.minimum(cur, d_cc[:, last])
        avail = jnp.where(accepted, -jnp.inf, cur)
        m = jnp.max(avail)
        # Lowest POOL index among in-batch maxima: the q=1 argmax's
        # tie-break, so batched picks replay the sequential order.
        p = jnp.argmin(jnp.where(avail >= m, cands, sentinel))
        # Strict > T: at == T a non-candidate could tie and win the q=1
        # argmax by index — stop and let the next step re-rank the pool.
        ok = (m > thresh) & (~stop) & (n_acc < limit)
        accepted = accepted.at[p].set(accepted[p] | ok)
        order = jnp.where(ok, order.at[n_acc].set(p.astype(jnp.int32)),
                          order)
        dvals = jnp.where(ok, dvals.at[n_acc].set(m), dvals)
        last = jnp.where(ok, p, last)
        n_acc = n_acc + ok.astype(jnp.int32)
        return (cur, accepted, order, dvals, n_acc, last, stop | ~ok)

    init = (vals, jnp.zeros(q, bool).at[0].set(True),
            jnp.zeros(q, jnp.int32),
            jnp.zeros(q, vals.dtype).at[0].set(vals[0]), jnp.int32(1),
            jnp.int32(0), jnp.asarray(False))
    _, _, order, dvals, n_acc, _, _ = jax.lax.fori_loop(0, q - 1, body,
                                                        init)
    return order, n_acc, dvals


def _accept_pick_batch(masked: jnp.ndarray, q: int, limit, sentinel: int,
                       pair_dists):
    """One batched-greedy candidate round, factored out of the scan body:
    masked top-q, exact in-batch re-check, and the padded accepted sequence
    (unaccepted slots repeat the first pick — the min-fold is a no-op for
    duplicates and the next step overwrites their pick slots).
    ``pair_dists(cands) -> [q, q]`` supplies the candidate pairwise
    squared distances in whichever factor layout the caller holds.
    Returns (seq [q] pool indices, dseq [q] acceptance-time distances,
    n_acc) — dseq slots past n_acc are dead exactly like seq's repeated
    first pick (the next step overwrites their pick slots)."""
    vals, cands = jax.lax.top_k(masked, q)
    order, n_acc, dseq = _recheck_candidates(cands, vals,
                                             pair_dists(cands), limit,
                                             sentinel)
    slot = jnp.arange(q)
    seq = jnp.where(slot < n_acc, cands[order], cands[order[0]])
    return seq, dseq, n_acc


@functools.partial(jax.jit, static_argnames=("budget", "q"),
                   donate_argnums=(2, 3))
def _kcenter_scan_batched(factors: Factors, sqn: jnp.ndarray,
                          min_dist: jnp.ndarray, selectable: jnp.ndarray,
                          budget: int, q: int) -> jnp.ndarray:
    """Batched deterministic greedy: top-q candidates, exact re-check,
    one fused [N, q] distance pass per accepted batch.  Pick-for-pick
    identical to the q=1 scan; ~q x fewer pool reads."""
    from . import scoring

    n = sqn.shape[0]
    # q trailing slots absorb the final step's padded writes; sliced off.
    picks0 = jnp.zeros(budget + q, jnp.int32)
    dists0 = jnp.zeros(budget + q, min_dist.dtype)

    def cond(st):
        return st[4] < budget

    def pair_dists(cands):
        return (sqn[cands][:, None] + sqn[cands][None, :]
                - 2.0 * dots_between(factors, cands))

    def body(st):
        min_dist, selectable, picks, dists, count = st
        masked = jnp.where(selectable > 0, min_dist, -jnp.inf)
        seq, dseq, n_acc = _accept_pick_batch(
            masked, q, jnp.minimum(q, budget - count), n, pair_dists)
        min_dist = scoring.batched_min_dist_update(factors, sqn, min_dist,
                                                   seq)
        selectable = selectable.at[seq].set(0.0)
        picks = jax.lax.dynamic_update_slice(picks, seq.astype(jnp.int32),
                                             (count,))
        dists = jax.lax.dynamic_update_slice(dists, dseq, (count,))
        return (min_dist, selectable, picks, dists, count + n_acc)

    _, _, picks, dists, _ = jax.lax.while_loop(
        cond, body, (min_dist, selectable, picks0, dists0, jnp.int32(0)))
    return picks[:budget], dists[:budget]


@functools.partial(jax.jit, static_argnames=("block",))
def _minimax_row(factors: Factors, sqn: jnp.ndarray, block: int = 2048
                 ) -> jnp.ndarray:
    """argmin_i max_j ||g_i - g_j||^2 — the reference's deterministic seed
    when nothing is labeled (coreset_sampler.py:96-100), computed with a
    blocked scan instead of the full N x N matrix."""
    n = sqn.shape[0]
    pad = (-n) % block
    order = jnp.arange(n + pad) % n

    def body(row_max, cols):
        d = sqn[:, None] + sqn[cols][None, :] - 2.0 * dots_to_many(
            factors, cols)
        return jnp.maximum(row_max, jnp.max(d, axis=1)), None

    row_max, _ = jax.lax.scan(body, jnp.full((n,), -jnp.inf),
                              order.reshape(-1, block))
    return jnp.argmin(row_max)


# -- the row-sharded backend (DESIGN.md §2b) -----------------------------
#
# The factor matrix is the selection scan's resident state (1.28M x 2048
# f32 = 10.5 GB for the full ImageNet pool) and used to be replicated
# per chip, so kcenter_select_maxn could only FIND the single-chip
# ceiling.  Here the pool axis is row-sharded over the mesh and every
# per-step pass runs shard-local inside shard_map, with exactly one
# family of collectives per step:
#
#   * distance strips / running-min updates: shard-local [rows/ndev, q];
#   * the farthest-point argmax / top-q: local reduce, then pmax + a
#     pmin index tie-break (lowest global index — the argmax rule), or
#     local top_k + an all_gather of ndev*q candidates (shard-major
#     order == global index order, so top_k's earliest-position
#     tie-break IS the replicated lowest-index tie-break);
#   * each accepted center's factor row: gathered FROM ITS OWNER by a
#     masked psum (non-owners contribute exact zeros — the sum is the
#     owner's row bit for bit), never by replicating the matrix.
#
# Every reduction is a min/max or a sum of exact zeros plus one value —
# no rounding anywhere — and each row's matvec stays on one shard, so
# the pick sequence is BIT-IDENTICAL to the replicated backend (pinned
# in tests/test_pool_sharding.py).  scripts/trace_lint.py check 6
# statically forbids these functions from full-pool host
# materialization (np.* / jax.device_get / .asarray) and from
# replicating the factor matrix (replicate / replicated_sharding).

# The functions trace_lint check 6 anchors on (renaming one away would
# silently drop the enforcement): the device tier may never touch np /
# host fetches at all; the orchestrator may do host index math but
# never device_get the pool or replicate a row-sharded array.
SHARDED_SELECTION_FNS = ("_build_sharded_fns", "_kcenter_greedy_sharded")

# Jitted sharded-selection programs, one set per (mesh, n_factors):
# AL round N+1 reuses round N's executables (shapes are bucketed the
# same way as the replicated path's — tests/test_compile_reuse.py).
_SHARDED_JITS: Dict = {}


def _build_sharded_fns(mesh, nf: int):
    """The jitted row-sharded selection programs for one mesh and factor
    count.  All bodies run inside shard_map over the data axis; factors
    and the per-row state vectors (sqn / min_dist / selectable /
    row_max) are sharded over pool rows, scalars and picks replicated."""
    axis = mesh_lib.DATA_AXIS
    ndev = mesh.devices.size
    fspec = tuple(P(axis, None) for _ in range(nf))
    vec, rep = P(axis), P()

    def _offset(rows: int, dtype=jnp.int32):
        return (jax.lax.axis_index(axis) * rows).astype(dtype)

    def _owned_or_oob(idxs, rows: int):
        """Global pick indices -> local positions on the owning shard,
        everything else mapped PAST the shard (rows) so scatter
        mode="drop" discards it.  A bare ``idxs - offset`` would go
        NEGATIVE on shards past the owner, and negative scatter indices
        wrap python-style BEFORE the drop check — silently zeroing the
        wrong rows (the bug this helper exists to prevent)."""
        off = _offset(rows, idxs.dtype)
        return jnp.where((idxs >= off) & (idxs < off + rows),
                         idxs - off, rows)

    def _take(factors, sqn, idxs):
        """Factor rows + self-norms for global ``idxs`` [K], gathered
        from their owning shards by masked psum (exact: zeros + the
        owner's value — mesh_lib.owner_rows, the one spelling of the
        idiom shared with resident.sharded_pool_gather)."""
        taken = tuple(mesh_lib.owner_rows(f, idxs, axis)
                      for f in factors)
        tsqn = mesh_lib.owner_rows(sqn, idxs, axis)
        return taken, tsqn

    def _argmax_global(vals, n_total: int):
        """Replicated global (argmax index, max value), ties to the
        LOWEST global index — the full-vector argmax rule, via pmax +
        pmin.  The max value rides out for free (it is the picked row's
        min-distance, the diagnostics layer's number) — no extra
        collective."""
        m_loc = jnp.max(vals)
        m = jax.lax.pmax(m_loc, axis)
        cand = jnp.where(m_loc >= m,
                         jnp.argmax(vals).astype(jnp.int32)
                         + _offset(vals.shape[0]),
                         jnp.int32(n_total))
        return jax.lax.pmin(cand, axis), m

    def _topk_global(vals, q: int):
        """Replicated global (values, indices) top-q.  Local top_k per
        shard, then top_k over the all_gathered ndev*q candidates —
        shard-major gather order is global-index order, so equal values
        resolve to the lowest global index exactly like the replicated
        top_k."""
        v, ix = jax.lax.top_k(vals, q)
        gi = ix.astype(jnp.int32) + _offset(vals.shape[0])
        av = jax.lax.all_gather(v, axis)
        ai = jax.lax.all_gather(gi, axis)
        v2, pos = jax.lax.top_k(av.reshape(-1), q)
        return v2, ai.reshape(-1)[pos]

    def _strip_min(factors, sqn, crows, csqn, min_dist):
        """Shard-local [rows/ndev, K] distance strip against K gathered
        center rows, folded into the running min — the sharded
        batched_min_dist_update."""
        d = None
        for f, r in zip(factors, crows):
            dd = f @ r.T
            d = dd if d is None else d * dd
        d = sqn[:, None] + csqn[None, :] - 2.0 * d
        return jnp.minimum(min_dist, jnp.min(d, axis=1))

    def _ring_min_body(factors, sqn, cidx, cvalid, min_dist):
        # The ring column feed's initial-min fold (DESIGN.md §15): the
        # [L] global labeled-center ids arrive replicated
        # (scoring.ring_center_layout — host index math, never a factor
        # byte); each shard owner-gathers ITS contiguous L/ndev slice
        # of center rows ONCE (mesh_lib.owner_rows — batch-sized,
        # exact), then the blocks rotate around the ring
        # (mesh_lib.ring_shift), each hop folding one shard-local
        # [rows/ndev, L/ndev] distance strip into the running min.  No
        # host column-block uploads, no replicated broadcast; min folds
        # are exact, so the result is bit-identical to the replicated
        # chunk scan.  Pad ids (sentinel, owned by nobody) gather as
        # zero rows and their columns mask to +inf.  The starting
        # blocks are seeded by masked PSUM-SCATTER (owner_rows'
        # reduce-scatter twin): every shard passes the same replicated
        # cidx, contributes the center rows it owns, and receives ITS
        # L/ndev slice of the assembled result — 1/ndev the wire of a
        # full owner_rows broadcast.  (A per-shard-different id slice
        # through owner_rows would cross-sum different gathers — the
        # bug class owner_rows_scattered exists to prevent.)
        lb = cidx.shape[0] // ndev
        me = jax.lax.axis_index(axis)
        vb = jax.lax.dynamic_slice_in_dim(cvalid, me * lb, lb, 0)
        crows = tuple(mesh_lib.owner_rows_scattered(f, cidx, axis)
                      for f in factors)
        csqn = mesh_lib.owner_rows_scattered(sqn, cidx, axis)

        def hop(_, carry):
            min_dist, crows, csqn, vb = carry
            d = None
            for f, r in zip(factors, crows):
                dd = f @ r.T
                d = dd if d is None else d * dd
            d = sqn[:, None] + csqn[None, :] - 2.0 * d
            d = jnp.where(vb[None, :] > 0, d, jnp.inf)
            min_dist = jnp.minimum(min_dist, jnp.min(d, axis=1))
            crows, csqn, vb = mesh_lib.ring_shift((crows, csqn, vb),
                                                  ndev, axis)
            return (min_dist, crows, csqn, vb)

        min_dist, _, _, _ = jax.lax.fori_loop(
            0, ndev, hop, (min_dist, crows, csqn, vb))
        return min_dist

    def _ring_minimax_body(factors, sqn, valid):
        # The minimax seed's all-pairs row-max over the SAME ring feed:
        # each shard's own factor block (with its sqn + validity)
        # rotates around the ring, folding a shard-local
        # [rows/ndev, rows/ndev] strip max per hop — after ndev hops
        # every real column has been seen exactly once.  Pad rows mask
        # to -inf as COLUMNS here (they must not lower any row's max);
        # as ROWS they are masked to +inf by _argmin_body.  Max folds
        # are exact, so the seed is the replicated seed.
        rows = sqn.shape[0]
        row_max0 = jnp.full((rows,), -jnp.inf)

        def hop(_, carry):
            row_max, block, bsqn, bvalid = carry
            d = None
            for f, bf in zip(factors, block):
                dd = f @ bf.T
                d = dd if d is None else d * dd
            d = sqn[:, None] + bsqn[None, :] - 2.0 * d
            d = jnp.where(bvalid[None, :] > 0, d, -jnp.inf)
            row_max = jnp.maximum(row_max, jnp.max(d, axis=1))
            block, bsqn, bvalid = mesh_lib.ring_shift(
                (block, bsqn, bvalid), ndev, axis)
            return (row_max, block, bsqn, bvalid)

        row_max, _, _, _ = jax.lax.fori_loop(
            0, ndev, hop, (row_max0, factors, sqn, valid))
        return row_max

    def _argmin_body(row_max, valid):
        # Pad rows (valid 0) forced to +inf so they can never win the
        # minimax seed's argmin; ties to the lowest global index.
        rm = jnp.where(valid > 0, row_max, jnp.inf)
        m_loc = jnp.min(rm)
        m = jax.lax.pmin(m_loc, axis)
        n_total = ndev * rm.shape[0]
        cand = jnp.where(m_loc <= m,
                         jnp.argmin(rm).astype(jnp.int32)
                         + _offset(rm.shape[0]),
                         jnp.int32(n_total))
        return jax.lax.pmin(cand, axis)

    def _scan_body(factors, sqn, min_dist, selectable, key, budget: int,
                   randomize: bool):
        n_total = sqn.shape[0] * ndev

        def step(carry, key):
            min_dist, selectable = carry
            if randomize:
                # The D^2 draw needs the full weight vector; all_gather
                # the O(N) scores (NOT the [N, D] factors) so the
                # categorical consumes the exact global vector the
                # replicated scan does — same bits, same draw.
                p = jnp.clip(min_dist, 0.0, None) * selectable
                p_all = jax.lax.all_gather(p, axis, tiled=True)
                sel_all = jax.lax.all_gather(selectable, axis, tiled=True)
                total = jnp.sum(p_all)
                weights = jnp.where(total > 0, p_all, sel_all)
                idx = jax.random.categorical(
                    key, jnp.log(weights)).astype(jnp.int32)
                # The already-gathered weight vector holds the pick's
                # clipped min-dist — the replicated scan's diagnostic,
                # same bits, zero extra collectives.
                dval = p_all[idx]
            else:
                masked = jnp.where(selectable > 0, min_dist, -jnp.inf)
                idx, dval = _argmax_global(masked, n_total)
            crows, csqn = _take(factors, sqn, idx[None])
            d = None
            for f, r in zip(factors, crows):
                dd = f @ r[0]  # matvec, like the replicated dots_to
                d = dd if d is None else d * dd
            min_dist = jnp.minimum(min_dist, sqn + csqn[0] - 2.0 * d)
            selectable = selectable.at[_owned_or_oob(idx, sqn.shape[0])
                                       ].set(0.0, mode="drop")
            return (min_dist, selectable), (idx, dval)

        keys = jax.random.split(key, budget)
        _, (picks, dists) = jax.lax.scan(step, (min_dist, selectable),
                                         keys)
        return picks, dists

    def _scan_batched_body(factors, sqn, min_dist, selectable, budget: int,
                           q: int):
        n_total = sqn.shape[0] * ndev
        picks0 = jnp.zeros(budget + q, jnp.int32)
        dists0 = jnp.zeros(budget + q, min_dist.dtype)

        def cond(st):
            return st[4] < budget

        def body(st):
            min_dist, selectable, picks, dists, count = st
            masked = jnp.where(selectable > 0, min_dist, -jnp.inf)
            vals, cands = _topk_global(masked, q)
            crows, csqn = _take(factors, sqn, cands)
            d_cc = None
            for r in crows:
                dd = r @ r.T
                d_cc = dd if d_cc is None else d_cc * dd
            d_cc = csqn[:, None] + csqn[None, :] - 2.0 * d_cc
            order, n_acc, dseq = _recheck_candidates(
                cands, vals, d_cc, jnp.minimum(q, budget - count), n_total)
            slot = jnp.arange(q)
            seq = jnp.where(slot < n_acc, cands[order], cands[order[0]])
            srows, ssqn = _take(factors, sqn, seq)
            min_dist = _strip_min(factors, sqn, srows, ssqn, min_dist)
            selectable = selectable.at[_owned_or_oob(seq, sqn.shape[0])
                                       ].set(0.0, mode="drop")
            picks = jax.lax.dynamic_update_slice(picks, seq, (count,))
            dists = jax.lax.dynamic_update_slice(dists, dseq, (count,))
            return (min_dist, selectable, picks, dists, count + n_acc)

        _, _, picks, dists, _ = jax.lax.while_loop(
            cond, body, (min_dist, selectable, picks0, dists0,
                         jnp.int32(0)))
        return picks[:budget], dists[:budget]

    # No donate_argnums on the sharded jits: the would-be-donated
    # carries are the O(N) min-dist/selectable vectors (KBs-to-MBs,
    # never the factor matrix), and XLA:CPU rejects donation of sharded
    # buffers with a per-call warning — not worth the log spam.
    @functools.partial(jax.jit, static_argnames=("budget", "q"))
    def scan_batched(factors, sqn, min_dist, selectable, budget, q):
        return shard_map(
            lambda f, s, md, sel: _scan_batched_body(f, s, md, sel,
                                                     budget, q),
            mesh=mesh, in_specs=(fspec, vec, vec, vec),
            out_specs=(rep, rep),
            check_vma=False)(factors, sqn, min_dist, selectable)

    @functools.partial(jax.jit, static_argnames=("budget", "randomize"))
    def scan_q1(factors, sqn, min_dist, selectable, key, budget, randomize):
        return shard_map(
            lambda f, s, md, sel, k: _scan_body(f, s, md, sel, k, budget,
                                                randomize),
            mesh=mesh, in_specs=(fspec, vec, vec, vec, rep),
            out_specs=(rep, rep), check_vma=False)(factors, sqn, min_dist,
                                                   selectable, key)

    @jax.jit
    def ring_min(factors, sqn, cidx, cvalid, min_dist):
        return shard_map(
            _ring_min_body, mesh=mesh,
            in_specs=(fspec, vec, rep, rep, vec), out_specs=vec,
            check_vma=False)(factors, sqn, cidx, cvalid, min_dist)

    @jax.jit
    def ring_minimax(factors, sqn, valid):
        return shard_map(
            _ring_minimax_body, mesh=mesh, in_specs=(fspec, vec, vec),
            out_specs=vec, check_vma=False)(factors, sqn, valid)

    @jax.jit
    def argmin_valid(row_max, valid):
        return shard_map(_argmin_body, mesh=mesh, in_specs=(vec, vec),
                         out_specs=rep, check_vma=False)(row_max, valid)

    return {"scan_batched": scan_batched, "scan_q1": scan_q1,
            "ring_min": ring_min, "ring_minimax": ring_minimax,
            "argmin_valid": argmin_valid}


def _record_picks(picks: np.ndarray, dists, n_seed: int) -> np.ndarray:
    """Publish the pick-distance diagnostics (LAST_PICK_DISTS) next to
    the picks being returned: seed slots get NaN (no labeled set to be
    distant from), the rest are the scan's pick-time min-distances.  The
    dists fetch rides the SAME already-computed executable output the
    picks fetch does — no extra pool pass, no effect on the picks."""
    global LAST_PICK_DISTS
    tail = (np.zeros(0, dtype=np.float32) if dists is None
            else np.asarray(dists, dtype=np.float32))
    LAST_PICK_DISTS = np.concatenate(
        [np.full(n_seed, np.nan, dtype=np.float32), tail])
    return picks


def _sharded_jits(mesh, nf: int) -> Dict:
    key = (mesh, nf)
    if key not in _SHARDED_JITS:
        _SHARDED_JITS[key] = _build_sharded_fns(mesh, nf)
    return _SHARDED_JITS[key]


def _kcenter_greedy_sharded(factors_np: Tuple[np.ndarray, ...],
                            labeled_mask: np.ndarray, budget: int,
                            randomize: bool, rng, q: int, key,
                            mesh) -> np.ndarray:
    """Row-sharded greedy k-center: the same selection as the replicated
    scans (bit-identical picks — see _build_sharded_fns), with per-chip
    residency of rows/ndev.  The factors arrive as HOST arrays and are
    uploaded per shard straight into the row sharding
    (mesh_lib.shard_rows) — the full matrix never materializes on any
    one device nor a second (padded) time on host (and on a
    multi-process mesh each host uploads only its own row range).  The
    initial min pass and the minimax seed feed their column blocks over
    the ring-permute feed (mesh_lib.ring_shift, DESIGN.md §15): blocks
    rotate device-to-device around the mesh instead of riding host
    uploads + replicated broadcast — the only host work left is the
    center-id layout (scoring.ring_center_layout, index math only)."""
    from . import scoring

    n = labeled_mask.shape[0]
    n_pad = bucket_size(n, floor=POOL_BUCKET_FLOOR)
    ndev = mesh.devices.size
    fns = _sharded_jits(mesh, len(factors_np))
    vec_sh = jax.sharding.NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
    global LAST_RING_FEED
    LAST_RING_FEED = True

    # Per-shard upload straight into the row sharding (shard_rows with
    # rows=n_pad): the bucket pad materializes only on the tail shard's
    # block, so the matrix never holds a second, padded host copy — at
    # the 10.5 GB full-ImageNet scale that transient double would OOM
    # the very hosts the sharded pool targets.
    factors = tuple(mesh_lib.shard_rows(f, mesh, rows=n_pad)
                    for f in factors_np)
    # Row-wise self-norms: elementwise + a D-axis reduction, so the
    # eager dispatch stays row-sharded with no collectives, and each
    # row's bits match the replicated self_sq_norms.
    sqn = self_sq_norms(factors)

    labeled_idxs = np.flatnonzero(labeled_mask)
    picks_pre: list = []
    if len(labeled_idxs) == 0:
        if randomize:
            seed_idx = int(rng.integers(n))
        else:
            # Sharded minimax seed over the RING feed: each shard's own
            # factor block rotates around the mesh, folding a local
            # strip max per hop (_ring_minimax_body), then a global
            # argmin with pad rows masked to +inf.  max/min folds are
            # exact, so the seed is the replicated seed — with zero
            # host column uploads.
            valid = np.zeros(n_pad, np.float32)
            valid[:n] = 1.0
            valid_dev = jax.device_put(valid, vec_sh)
            row_max = fns["ring_minimax"](factors, sqn, valid_dev)
            seed_idx = int(fns["argmin_valid"](row_max, valid_dev))
        picks_pre.append(seed_idx)
        labeled_idxs = np.asarray([seed_idx])
        budget -= 1
    if budget <= 0:
        return _record_picks(np.asarray(picks_pre, dtype=np.int64),
                             None, len(picks_pre))
    q = max(1, min(q, budget))

    # Initial min pass over the RING column feed: the labeled-center
    # ids ride in replicated on a bucketed layout (index math only —
    # scoring.ring_center_layout), each shard owner-gathers its slice
    # of center rows once, and the blocks rotate around the mesh while
    # every shard folds [rows/ndev, L/ndev] strips into its running
    # min (_ring_min_body).  The pad sentinel n_pad is owned by no
    # shard, so pad columns gather as zeros and mask to +inf.
    cidx, cvalid = scoring.ring_center_layout(labeled_idxs, n_pad, ndev)
    min_dist = jax.device_put(np.full(n_pad, np.inf, np.float32), vec_sh)
    min_dist = fns["ring_min"](factors, sqn, jnp.asarray(cidx),
                               jnp.asarray(cvalid), min_dist)

    selectable = np.zeros(n_pad, dtype=np.float32)
    selectable[:n] = 1.0
    selectable[labeled_idxs] = 0.0
    sel_dev = jax.device_put(selectable, vec_sh)

    global LAST_BACKEND
    if q > 1:
        picks, dists = fns["scan_batched"](factors, sqn, min_dist,
                                           sel_dev, budget, q)
        LAST_BACKEND = "xla-batched"
    else:
        picks, dists = fns["scan_q1"](factors, sqn, min_dist, sel_dev,
                                      key, budget, bool(randomize))
        LAST_BACKEND = "xla"
    picks = np.asarray(picks, dtype=np.int64)
    return _record_picks(
        np.concatenate([np.asarray(picks_pre, dtype=np.int64), picks]),
        dists, len(picks_pre))


def row_capable(n: int, budget: int, mesh, batch_q: Optional[int] = None,
                randomize: bool = False) -> bool:
    """Whether ``kcenter_greedy`` would resolve a non-"replicated"
    ``pool_sharding`` to the row-sharded backend for this geometry:
    a single-process mesh with >1 device, the bucketed pool size
    dividing evenly over it, and at least one candidate batch of rows
    per shard.  This IS the gate ``kcenter_greedy`` applies — callers
    that must know the layout BEFORE paying for a selection pre-check
    here instead of discovering a silent replicated fallback, at ndev
    times the per-chip bytes, after the run."""
    if mesh is None:
        return False
    ndev = mesh.devices.size
    budget = max(1, int(budget))
    q = 1 if randomize else int(batch_q or DEFAULT_BATCH_Q)
    q = max(1, min(q, budget))
    n_pad = bucket_size(n, floor=POOL_BUCKET_FLOOR)
    # Multi-process meshes qualify since the pod tier (DESIGN.md §15):
    # the collective backend's shard_map programs run identically over
    # DCN, and the factor upload assembles per process (shard_rows).
    return ndev > 1 and n_pad % ndev == 0 and n_pad // ndev >= q


def kcenter_greedy(
    factors: Sequence[np.ndarray],
    labeled_mask: np.ndarray,
    budget: int,
    randomize: bool = False,
    rng: Optional[np.random.Generator] = None,
    batch_q: Optional[int] = None,
    mesh=None,
    pool_sharding: Optional[str] = None,
) -> np.ndarray:
    """Select ``budget`` local row indices by greedy k-center over the
    factorized embeddings.  Matches coreset_sampler.coreset(:66-105):
    deterministic mode takes the farthest-point argmax (batched q picks
    per pool pass, pick-for-pick identical — see module docstring);
    randomized mode draws with D^2 probabilities one pick at a time.

    ``mesh`` + ``pool_sharding``: with a single-process multi-device
    mesh and pool_sharding "row" (or None/"auto"), the pool axis is
    ROW-SHARDED over the mesh's data axis and selection runs on the
    collective backend (_build_sharded_fns): distance strips and min
    folds shard-local, one argmax/top-q collective per step, center
    rows gathered from their owners — pick-for-pick identical to the
    replicated scans while each chip holds only rows/ndev of the factor
    matrix.  "replicated" forces the single-chip layout.  Returns
    selections in pick order."""
    labeled_mask = np.asarray(labeled_mask, dtype=bool)
    n = labeled_mask.shape[0]
    budget = int(budget)
    if budget <= 0:
        return _record_picks(np.zeros(0, dtype=np.int64), None, 0)
    if rng is None:
        rng = np.random.default_rng()
    key = jax.random.PRNGKey(int(rng.integers(2 ** 31)))
    q = 1 if randomize else int(batch_q or DEFAULT_BATCH_Q)
    q = max(1, min(q, budget))

    global LAST_SHARDING, LAST_RING_FEED
    use_row = (pool_sharding != "replicated"
               and row_capable(n, budget, mesh, batch_q=batch_q,
                               randomize=randomize))
    if use_row:
        LAST_SHARDING = "row"
        factors_np = tuple(np.asarray(f, dtype=np.float32)
                           for f in factors)
        return _kcenter_greedy_sharded(factors_np, labeled_mask, budget,
                                       randomize, rng, q, key, mesh)
    LAST_SHARDING = "replicated"
    LAST_RING_FEED = False

    factors = tuple(jnp.asarray(np.asarray(f), dtype=jnp.float32)
                    for f in factors)
    sqn = self_sq_norms(factors)
    labeled_idxs = np.flatnonzero(labeled_mask)
    picks_pre: list = []
    if len(labeled_idxs) == 0:
        # Seed point (coreset_sampler.py:95-100): uniform when randomized,
        # else the minimax row.
        if randomize:
            seed_idx = int(rng.integers(n))
        else:
            seed_idx = int(_minimax_row(factors, sqn))
        picks_pre.append(seed_idx)
        labeled_idxs = np.asarray([seed_idx])
        budget -= 1

    if budget <= 0:
        return _record_picks(np.asarray(picks_pre, dtype=np.int64),
                             None, len(picks_pre))

    q = max(1, min(q, budget))

    # Power-of-two pool bucketing: subset-capped pools drift in size
    # across AL rounds; padding to the enclosing bucket (zero factor
    # rows, selectable 0 — they can never win an argmax, a top-k
    # acceptance, or a D^2 draw) lets round N+1 reuse round N's compiled
    # executables instead of paying a fresh XLA compile.  Applied BEFORE
    # the initial min pass so the chunked _min_dist_chunk reuses too
    # (only the once-per-experiment minimax seed above runs unpadded — a
    # zero pad row could win ITS argmin).
    n_pad = bucket_size(n, floor=POOL_BUCKET_FLOOR)
    pad = n_pad - n
    if pad:
        factors = tuple(jnp.pad(f, ((0, pad), (0, 0))) for f in factors)
        sqn = jnp.pad(sqn, (0, pad))
    min_dist = min_sq_dist_to(factors, sqn, labeled_idxs)
    selectable = np.zeros(n_pad, dtype=np.float32)
    selectable[:n] = 1.0
    selectable[labeled_idxs] = 0.0

    global LAST_BACKEND
    sel_dev = jnp.asarray(selectable)
    if q > 1:
        picks, dists = _kcenter_scan_batched(factors, sqn, min_dist,
                                             sel_dev, budget, q)
        LAST_BACKEND = "xla-batched"
    else:
        picks, dists = _kcenter_scan(factors, sqn, min_dist, sel_dev,
                                     budget, bool(randomize), key)
        LAST_BACKEND = "xla"
    picks = np.asarray(picks, dtype=np.int64)
    return _record_picks(
        np.concatenate([np.asarray(picks_pre, dtype=np.int64), picks]),
        dists, len(picks_pre))


def adaptive_avg_pool_matrix(n_in: int, n_out: int) -> np.ndarray:
    """[n_in, n_out] averaging weights with torch adaptive_avg_pool bin
    edges: bin o covers [floor(o*In/Out), ceil((o+1)*In/Out)).  Pooling a
    vector is then ``v @ M`` (badge_sampler.py:41-44 applies the 2-D pool to
    the rank-1 grad embedding; pooling each factor separately is exact)."""
    m = np.zeros((n_in, n_out), dtype=np.float32)
    for o in range(n_out):
        start = int(np.floor(o * n_in / n_out))
        end = int(np.ceil((o + 1) * n_in / n_out))
        m[start:end, o] = 1.0 / (end - start)
    return m
