"""Sharded acquisition-scoring passes over the unlabeled pool.

The reference scores the pool single-process on one GPU inside each
sampler's ``query`` (e.g. src/query_strategies/margin_sampler.py:19-45,
confidence_sampler.py:8-47, mase_sampler.py:30-96): a DataLoader walk with a
per-batch forward, hauling full softmax/embedding tensors back to host.

Here scoring is a first-class, mesh-parallel primitive: one jitted step per
(model, view, statistic) computes the per-example statistics on device over
a batch whose leading axis is sharded across the mesh's data axis, and only
the tiny per-example results (a few floats each) return to host.  This is
the "distributed acquisition scoring" row of SURVEY.md §2's parallelism
table — the big TPU win the reference lacks.

Every step function has signature ``step(variables, batch) -> dict`` where
each dict value has leading batch axis, and every batch row carries its pool
index and a validity mask (data/pipeline.py), so padding never contaminates
scores.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.core import Dataset, ViewSpec, rows_are_tokens
from ..parallel import mesh as mesh_lib
from ..pool import bucket_size
from ..data.pipeline import (batch_index_lists, iterate_batches,
                             padded_batch_layout)
from ..telemetry import runtime as tele_runtime
from ..telemetry import spans as tele_spans
from ..train.evaluation import view_forward

# Registered step-builders (scripts/al_lint.py recompile-hazard): every
# jax.jit in this module sits inside one of these factories (one step
# per (model, view), reused across rounds) or is the module-level
# head_pair_norms; a stray jit outside them fails the lint.
_STEP_BUILDERS = ("make_prob_stats_step", "make_embed_step",
                  "make_badge_step", "make_mase_step", "head_pair_norms")


def batched_min_dist_update(factors, sqn: jnp.ndarray,
                            min_dist: jnp.ndarray,
                            center_idxs: jnp.ndarray) -> jnp.ndarray:
    """One batched k-center distance fold: min_dist <- min(min_dist,
    min_c ||g_. - g_c||^2) over the q centers in ``center_idxs``, in a
    single [N, q] pass over the factor matrices.

    This is the selection hot path's per-step min-reduce, and it lives
    here with the other mesh-parallel scoring primitives because its
    operands follow the pool-axis layout collect_pool produces: with the
    pool axis sharded over the mesh's data axis the [shard, q] distance
    strip, its min over q, and the running-min update are all
    shard-local — the batched greedy step's only cross-shard reduction
    is the subsequent masked top-k, ONE collective per q picks instead
    of one per pick (strategies/kcenter.py wires the sharding).
    """
    from .kcenter import dots_to_many

    d = (sqn[:, None] + sqn[center_idxs][None, :]
         - 2.0 * dots_to_many(factors, center_idxs))
    return jnp.minimum(min_dist, jnp.min(d, axis=1))


# Bucket floor for the ring column feed's center-id plan: labeled sets
# grow round over round, so the padded length rides the pool bucket
# ladder — round N+1 reuses round N's ring executables until the
# labeled count crosses a bucket boundary.
RING_CENTER_FLOOR = 1024


def ring_center_layout(center_idxs: np.ndarray, sentinel: int,
                       ndev: int, floor: int = RING_CENTER_FLOOR
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """The ring column feed's center-block plan (DESIGN.md §15) — the
    column analogue of ``chunk_row_slices``: the [L] global labeled-
    center ids padded up to a ``pool.bucket_size`` ladder length
    rounded to divide the mesh, id-padded with ``sentinel`` (an index
    no shard owns, so ``mesh_lib.owner_rows`` returns exact zeros for
    it) and masked via the returned validity vector.  Shard i of the
    ring starts with the contiguous slice ``[i*L/ndev, (i+1)*L/ndev)``
    of this layout; after ndev ring hops every shard has folded every
    valid center exactly once.  Host index math only — never a factor
    byte (the whole point: the ring feed replaced the host column-block
    uploads)."""
    idxs = np.asarray(center_idxs, dtype=np.int32)
    l_pad = bucket_size(max(1, len(idxs)), floor=floor)
    l_pad += (-l_pad) % max(1, int(ndev))
    cidx = np.full(l_pad, int(sentinel), dtype=np.int32)
    cidx[:len(idxs)] = idxs
    cvalid = np.zeros(l_pad, dtype=np.float32)
    cvalid[:len(idxs)] = 1.0
    return cidx, cvalid


def make_prob_stats_step(model, view: ViewSpec) -> Callable:
    """Per-example softmax statistics in one fused pass: top-1 probability
    (ConfidenceSampler's score, confidence_sampler.py:33-36), top1-top2
    probability margin (MarginSampler's score, margin_sampler.py:33-35),
    the predictive entropy (served by /v1/score — no reference sampler
    uses it, but it rides the same softmax for free), and the predicted
    label.  This step is shared verbatim by the offline samplers and the
    scoring service (serve/executor.py), which is what makes a served
    score bit-for-bit the offline score at the same batch shape."""

    @jax.jit
    def score_prob_stats(variables, batch):
        counters = {}
        logits = view_forward(model, view, variables, batch,
                              counters=counters)
        with jax.named_scope("score_head"):
            logits32 = logits.astype(jnp.float32)
            probs = jax.nn.softmax(logits32, axis=-1)
            logp = jax.nn.log_softmax(logits32, axis=-1)
            top2, top2_idx = jax.lax.top_k(probs, 2)
            return {
                **counters,
                "confidence": top2[:, 0],
                "margin": top2[:, 0] - top2[:, 1],
                # -sum p log p via log_softmax; a prob that underflowed
                # to exactly 0 would make 0 * -inf = NaN, so those
                # entries are pinned to the limit value 0.
                "entropy": -jnp.sum(
                    jnp.where(probs > 0, probs * logp, 0.0), axis=-1),
                "pred": top2_idx[:, 0].astype(jnp.int32),
            }

    return score_prob_stats


def make_embed_step(model, view: ViewSpec, with_probs: bool = False
                    ) -> Callable:
    """Final-embedding extraction (the reference's
    ``return_features='finalembed'`` pass, coreset_sampler.py:43-58), with
    optional softmax margin for MarginClusteringSampler
    (margin_clustering_sampler.py:23-45)."""

    def step(variables, batch):
        out = {}
        logits, embedding = view_forward(model, view, variables, batch,
                                         counters=out, return_features=True)
        out["embedding"] = embedding
        if with_probs:
            with jax.named_scope("score_head"):
                probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
                top2, _ = jax.lax.top_k(probs, 2)
                out["margin"] = top2[:, 0] - top2[:, 1]
                out["pred"] = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return out

    # The program's name in a device trace (jit_<name>); collect_pool
    # names the resident runner after it (run_<name>).
    step.__name__ = "score_embed_margin" if with_probs else "score_embed"
    return jax.jit(step)


def make_badge_step(model, view: ViewSpec, pool_512: bool = False
                    ) -> Callable:
    """BADGE gradient-embedding FACTORS (badge_sampler.py:22-48).

    The gradient of CE(logits, argmax logits) w.r.t. the logits is
    closed-form — softmax(z) - onehot(argmax z) — so no autograd pass is
    needed (the reference runs torch.autograd.grad per batch,
    badge_sampler.py:36-37).  The full gradient embedding is the rank-1
    outer product a (x) e; we return the two factors instead of the [C*D]
    flattened product (see strategies/kcenter.py for why that is exact).

    ``pool_512``: the PartitionedBADGE variant pools the (C, D) grad
    embedding with adaptive average pooling to
    (min(16, C), 512 // min(16, C)) — 16x32=512 dims for ImageNet, 10x51
    for CIFAR, exactly the reference's ``pool_h = min(POOLING_H, C)`` rule
    (badge_sampler.py:9-10,41-44).  Pooling a rank-1 matrix factor-wise is
    exact, so each factor is pooled by its own averaging matrix.
    """
    from .kcenter import adaptive_avg_pool_matrix

    def step(variables, batch):
        logits, embedding = view_forward(model, view, variables, batch,
                                         return_features=True)
        with jax.named_scope("score_head"):
            logits = logits.astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
            pred = jnp.argmax(logits, axis=-1)
            a = probs - jax.nn.one_hot(pred, logits.shape[-1],
                                       dtype=jnp.float32)
            e = embedding
            if pool_512:
                c, d = a.shape[1], e.shape[1]
                pool_h = min(16, c)
                pool_w = int(512 / pool_h)
                a = a @ jnp.asarray(adaptive_avg_pool_matrix(c, pool_h))
                e = e @ jnp.asarray(adaptive_avg_pool_matrix(d, pool_w))
            return {"grad_a": a, "grad_e": e}

    step.__name__ = "score_badge_pool" if pool_512 else "score_badge"
    return jax.jit(step)


@jax.jit
def head_pair_norms(kernel: jnp.ndarray) -> jnp.ndarray:
    """[C, C] table of ||w_c - w_j|| over the head rows, by explicit row
    differences (peak live [C, D]).  Batch-independent: callers that score
    many batches against one head compute this once per head (see
    make_mase_step) — NOT via the Gram identity G_cc + G_jj - 2 G_cj,
    whose float32 cancellation would misreport near-duplicate head columns
    as coincident (denominator 0 -> radius +inf)."""
    w = kernel.T.astype(jnp.float32)  # [C, D]
    return jax.lax.map(
        lambda wc: jnp.linalg.norm(w - wc[None, :], axis=-1), w)


def boundary_radii(embedding: jnp.ndarray, kernel: jnp.ndarray,
                   bias: jnp.ndarray,
                   pair_norms: Optional[jnp.ndarray] = None
                   ) -> Dict[str, jnp.ndarray]:
    """Closed-form distance from each embedding to every one-vs-one decision
    boundary of the linear head (MASE, mase_sampler.py:59-79).

    For predicted class c and any class j, the boundary is the hyperplane
    {e : (w_c - w_j)·e + (b_c - b_j) = 0}; the L2 distance from e is
    ((w_c - w_j)·e + b_c - b_j) / ||w_c - w_j||.  The j == c entry is 0/0
    and mapped to +inf, matching the reference's nan -> inf fix-up.

    The full [B, C, D] boundary tensor the reference materializes per
    batch (mase_sampler.py:62-70 — 2 GB at B=256, C=1000, D=2048) never
    exists here, WITHOUT giving up its float32 exactness:

      numerator   e·(w_c - w_j) + (b_c - b_j), with the weight DIFFERENCE
                  formed first — the algebraically equal logit difference
                  logit_c - logit_j subtracts two large rounded dot
                  products and quantizes away small margins between
                  near-duplicate head columns.  Computed in class blocks
                  (a lax.map over [B, block, D] tiles) so peak memory is
                  bounded while every entry matches the reference's
                  full-tensor einsum;
      denominator ||w_c - w_j||, the batch-independent ``head_pair_norms``
                  table — pass it as ``pair_norms`` when scoring many
                  batches against one head so the C-step map runs once per
                  head, not once per batch.

    kernel is the Flax Dense kernel [D, C]; bias [C].
    """
    e = embedding.astype(jnp.float32)  # [B, D]
    w = kernel.T.astype(jnp.float32)  # [C, D]
    b = bias.astype(jnp.float32)  # [C]
    logits = e @ w.T + b  # [B, C]
    preds = jnp.argmax(logits, axis=-1)  # [B]
    if pair_norms is None:
        pair_norms = head_pair_norms(kernel)  # [C, C]
    denom = pair_norms[preds]  # [B, C]

    c, d = w.shape
    block = min(c, max(1, 2 ** 25 // max(1, e.shape[0] * d)))  # ~128MB tile
    pad = (-c) % block
    w_pad = jnp.pad(w, ((0, pad), (0, 0)))
    b_pad = jnp.pad(b, (0, pad))
    w_pred, b_pred = w[preds], b[preds]  # [B, D], [B]

    def numer_block(args):
        wb, bb = args  # [block, D], [block]
        delta = w_pred[:, None, :] - wb[None, :, :]  # [B, block, D]
        return (jnp.einsum("bd,bkd->bk", e, delta)
                + b_pred[:, None] - bb[None, :])

    numer = jax.lax.map(numer_block,
                        (w_pad.reshape(-1, block, d),
                         b_pad.reshape(-1, block)))  # [nb, B, block]
    numer = jnp.moveaxis(numer, 0, 1).reshape(e.shape[0], c + pad)[:, :c]
    radii = jnp.where(denom > 0, numer / jnp.maximum(denom, 1e-30), jnp.inf)
    return {"radii": radii, "pred": preds.astype(jnp.int32)}


def make_mase_step(model, view: ViewSpec) -> Callable:
    """Per-class boundary radii + min margin, fully on device.

    The reference materializes [B, C, D] tensors per batch on GPU
    (mase_sampler.py:62-79); ``boundary_radii`` reduces both terms
    algebraically so the largest intermediate is [C, D], and the
    batch-independent pair-norm table is computed once per HEAD (a pool
    scan runs thousands of batches against one set of weights) via a
    one-slot cache keyed on the kernel array's identity.
    """
    cache: Dict[str, Any] = {}

    @jax.jit
    def score_mase(variables, batch, pair_norms):
        _, embedding = view_forward(model, view, variables, batch,
                                    return_features=True)
        with jax.named_scope("score_head"):
            kernel = variables["params"]["linear"]["kernel"]
            bias = variables["params"]["linear"]["bias"]
            out = boundary_radii(embedding, kernel, bias,
                                 pair_norms=pair_norms)
            out["min_margin"] = jnp.min(out["radii"], axis=-1)
            return out

    def step(variables, batch):
        kernel = variables["params"]["linear"]["kernel"]
        if isinstance(kernel, jax.core.Tracer):
            # Called under someone else's trace (the resident-pool gather
            # runner, parallel/resident.py): a host-side cache can't help
            # there, so inline the norms into that computation.  Resident
            # pools are in-memory/CIFAR-scale, where the C-step map is
            # trivial; the C=1000 disk datasets always take the host path
            # below.
            return score_mase(variables, batch, None)
        # Identity (not equality) check; holding the reference keeps the
        # id from being reused by a different array.
        if cache.get("kernel") is not kernel:
            cache["kernel"] = kernel
            cache["norms"] = head_pair_norms(kernel)
        return score_mase(variables, batch, cache["norms"])

    step.__name__ = "score_mase"
    return step


# In-memory pools up to this size stay resident on device across ALL
# rounds and samplers (uint8, replicated like the trainer's epoch-scan
# arrays; the per-batch gather output is what gets data-sharded).  This
# constant is only the DIRECT-CALLER default: production callers pass
# the trainer's resolved budget, which auto-sizes from live HBM headroom
# when TrainConfig.resident_scoring_bytes is None
# (parallel/resident.resolve_budget).  The shared pool cache + jitted
# gather-runners live in parallel/resident.py so scoring and evaluation
# upload each pool exactly once between them.
from ..config import RESIDENT_SCORING_BYTES_DEFAULT as RESIDENT_MAX_BYTES
from ..parallel import resident as resident_lib


# -- chunk-resumable scoring (the pipelined round) --------------------------
#
# A scoring pass over (idxs, batch_size) is a SEQUENCE of fixed-shape
# batches, and each jitted step call is independent of its neighbors, so
# the pass can be cut at any batch boundary and resumed — or computed
# out of order, on another thread, from a different-but-equal variables
# tree — without changing a single output bit: collect_pool(idxs[sl])
# over a batch-aligned row slice produces exactly the batches sl covers
# of the monolithic collect_pool(idxs) call (same rows per batch, same
# tail padding, same jitted executable).  The speculative scorer of the
# pipelined round (experiment/pipeline.py) leans on this: it pre-scores
# chunk slices while training still runs, and any chunk invalidated by
# a later best checkpoint is recomputed inline at query time; splicing
# the chunks back together is bit-identical to the sequential pass
# (pinned in tests/test_pipeline.py).

def chunk_row_slices(n_rows: int, batch_size: int,
                     chunk_batches: int) -> List[slice]:
    """Row slices covering ``chunk_batches`` whole batches each (the last
    takes the remainder) — the chunk plan both the speculative scorer
    and the inline-completion path iterate, so the two can never
    disagree on chunk boundaries."""
    from ..data.pipeline import num_batches
    if n_rows <= 0:
        return []
    n_b = num_batches(n_rows, batch_size)
    step = max(1, int(chunk_batches))
    return [slice(b0 * batch_size, min((b0 + step) * batch_size, n_rows))
            for b0 in range(0, n_b, step)]


def splice_chunks(chunks: List[Dict[str, np.ndarray]]
                  ) -> Dict[str, np.ndarray]:
    """Concatenate per-chunk host outputs (in chunk order) back into one
    idxs-aligned dict — the inverse of scoring each chunk_row_slices
    entry separately."""
    if len(chunks) == 1:
        return chunks[0]
    return {k: np.concatenate([c[k] for c in chunks], axis=0)
            for k in chunks[0]}


class _NullGate:
    """The no-lock stand-in for mesh_lib.DispatchGate when collect_pool
    runs single-threaded (every caller outside the pipelined round):
    context enter/exit and drain are all no-ops."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def drain(self, tree):
        return tree


_NULL_GATE = _NullGate()


def _finalize(chunks: Dict[str, list], multi: bool, mesh, n: int
              ) -> Dict[str, np.ndarray]:
    if multi:
        return {k: np.asarray(mesh_lib.fetch(jnp.concatenate(v, axis=0),
                                             mesh))[:n]
                for k, v in chunks.items()}
    return {k: np.concatenate(v, axis=0)[:n] for k, v in chunks.items()}


def collect_pool(
    dataset: Dataset,
    idxs: np.ndarray,
    batch_size: int,
    step_fn: Callable,
    variables,
    mesh,
    num_workers: int = 0,
    prefetch: int = 2,
    keys: Optional[Iterable[str]] = None,
    resident_cache: Optional[Dict] = None,
    resident_max_bytes: int = RESIDENT_MAX_BYTES,
    host_s2d: bool = False,
    pool_sharding: str = "replicated",
    dispatch_lock: Optional[Any] = None,
) -> Dict[str, np.ndarray]:
    """Run ``step_fn`` over ``dataset[idxs]`` in fixed-shape sharded batches
    and return host arrays of length ``len(idxs)``, row i scoring pool index
    ``idxs[i]``.  Alignment is *enforced*: the per-batch index rows carried
    by the pipeline (data/pipeline.py) are collected alongside the scores
    and checked against ``idxs`` — the class of bug the reference has at
    confidence_sampler.py:41 (sorting by a scrambled score vector) cannot
    happen silently here.

    This is the engine behind every sampler's scoring pass — the TPU
    replacement for the reference's per-sampler DataLoader loops.

    ``idxs`` must be non-empty (samplers guard the exhausted-pool case
    before scoring).

    ``dispatch_lock``: a mesh_lib.DispatchGate held around every jitted
    dispatch (never around a host fetch).  The pipelined round's
    speculative scorer and the trainer share one gate
    (Trainer.dispatch_lock) so two threads' collective-bearing
    computations always enqueue in ONE global order on every device —
    and on CPU meshes the gate's drain_mode additionally completes each
    computation before release (XLA:CPU reorders execution behind the
    enqueue order; see DispatchGate).  None (every single-threaded
    caller) costs nothing.
    """
    idxs = np.asarray(idxs)
    if dispatch_lock is None:
        dispatch_lock = _NULL_GATE
    n = len(idxs)
    if n == 0:
        raise ValueError("collect_pool called with empty idxs; guard the "
                         "exhausted-pool case in the sampler")
    # Device-resident fast path for in-memory pools: upload once per
    # experiment (the caller owns ``resident_cache``), then every batch of
    # every round's every sampler is an on-device gather — zero image
    # bytes cross the host<->device boundary after the first round.  A
    # pool that is ALREADY uploaded keeps its fast path even if a budget
    # refresh shrank the budget below its size (resident_lib.cached).
    # ``pool_sharding`` "row": the upload is row-sharded (rows/ndev per
    # chip) and the runner assembles each batch from the shard owners —
    # scores stay bit-identical (tests/test_pool_sharding.py); the
    # runner follows the ENTRY's actual layout either way.
    shard_ways = (mesh.devices.size
                  if pool_sharding == "row" and mesh is not None else 1)
    resident = (resident_cache is not None
                and resident_lib.eligible(dataset, resident_max_bytes,
                                          cache=resident_cache,
                                          shard_ways=shard_ways))
    # ONE span over the whole pass on either path (it also runs on the
    # spec-scorer thread, under whatever span is open there), closed
    # AFTER the final fetch: its end is where the host has the scores,
    # not where the last batch was enqueued.  rows_run counts the padded
    # last batch too — what the device executes.
    with tele_spans.get_tracer().span("collect_pool", args={
            "rows": n, "path": "resident" if resident else "stream"}) as sp:
        if resident:
            out, batches = _collect_resident(
                dataset, idxs, batch_size, step_fn, variables, mesh, keys,
                resident_cache, pool_sharding, dispatch_lock)
        else:
            out, batches = _collect_stream(
                dataset, idxs, batch_size, step_fn, variables, mesh,
                num_workers, prefetch, keys, host_s2d, dispatch_lock)
        sp.args.update(batches=batches, rows_run=batches * batch_size)
        if rows_are_tokens(dataset):
            # Rows of token ids: the pass in tokens, the real rows'.
            sp.args["tokens"] = n * int(dataset.image_shape[0])
        # The model's own counts of the pass (``counter/<name>`` of the
        # step's output) are the span's counters, not scores.
        for k in [k for k in out if k.startswith(COUNTER_PREFIX)]:
            sp.args[k[len(COUNTER_PREFIX):]] = int(out.pop(k).sum())
    return out


COUNTER_PREFIX = "counter/"


def _wanted(out: Dict[str, Any], keys) -> Dict[str, Any]:
    """The step's outputs a caller asked for by ``keys``, and its counters
    whatever was asked for."""
    if keys is None:
        return out
    return {k: v for k, v in out.items()
            if k in keys or k.startswith(COUNTER_PREFIX)}


# Bulk-fetch cadence of the streaming path AND the heartbeat-tick
# granularity of both paths (single-process: keep per-batch outputs ON
# DEVICE and fetch every FETCH_EVERY batches — a per-batch np.asarray
# blocks the host on that batch's compute and serializes the whole
# pipeline — how much it costs on the v5e is not measured; deferred
# fetches let async dispatch overlap decode, h2d, and compute, bounding
# extra HBM to ~FETCH_EVERY batches of outputs).
FETCH_EVERY = 32


def _runner_name(step_fn: Callable) -> str:
    """``run_score_<kind>``: the resident runner's program name, after
    the step it wraps (the factories above name theirs ``score_<kind>``)."""
    kind = getattr(step_fn, "__name__", "step")
    return "run_score_" + kind.removeprefix("score_")


def _collect_resident(dataset, idxs, batch_size, step_fn, variables, mesh,
                      keys, resident_cache, pool_sharding, dispatch_lock
                      ) -> Tuple[Dict[str, np.ndarray], int]:
    """collect_pool over the pinned pool: one jitted gather+step per
    batch, outputs kept on the device, ONE fetch at the end."""
    n = len(idxs)
    tele = tele_runtime.get_run()
    images_dev, _ = resident_lib.pool_arrays(resident_cache, dataset, mesh,
                                             sharding=pool_sharding)
    run = resident_lib.get_runner(
        resident_cache, step_fn, mesh, _runner_name(step_fn),
        dataset.image_shape, sharded=mesh_lib.is_row_sharded(images_dev))
    chunks: Dict[str, list] = {}
    for i, b in enumerate(batch_index_lists(idxs, batch_size)):
        ids, mask = padded_batch_layout(b, batch_size)
        with dispatch_lock:
            small = mesh_lib.replicate((ids.astype(np.int32), mask), mesh)
            out = run(variables, images_dev, *small)
            dispatch_lock.drain(out)
        out = _wanted(out, keys)
        for k, v in out.items():
            # Keep DEVICE arrays: a per-batch np.asarray would block on
            # each batch and stall async dispatch (the host path hides
            # that sync behind its threaded decode; here there is no
            # host work to overlap).  One fetch at the end.
            chunks.setdefault(k, []).append(v)
        if (i + 1) % FETCH_EVERY == 0:
            tele.tick(step=i + 1)
    if mesh_lib.is_multiprocess(mesh):
        return _finalize(chunks, True, mesh, n), i + 1
    return {k: np.asarray(jnp.concatenate(v, axis=0))[:n]
            for k, v in chunks.items()}, i + 1


def _collect_stream(dataset, idxs, batch_size, step_fn, variables, mesh,
                    num_workers, prefetch, keys, host_s2d, dispatch_lock
                    ) -> Tuple[Dict[str, np.ndarray], int]:
    """collect_pool over a pool that is not pinned: the double-buffered
    host feed, a bulk fetch (and a ``collect_pool_chunk`` span that ends
    at it) every FETCH_EVERY batches."""
    n = len(idxs)
    tracer = tele_spans.get_tracer()
    tele = tele_runtime.get_run()

    def chunk_span(t0: float, first_batch: int, n_batches: int,
                   rows: int) -> None:
        tracer.complete(
            "collect_pool_chunk", t0, time.perf_counter(),
            args={"batches": n_batches, "first_batch": first_batch,
                  "rows": rows})
    # On a multi-host mesh each process gathers/decodes only its own rows
    # of every global batch; score rows come back in GLOBAL batch order
    # (mesh_lib.fetch all-gathers sharded outputs), so the global row
    # layout is recomputed here both to check alignment and to map scores
    # back to pool indices.
    local = mesh_lib.process_local_rows(mesh, batch_size)
    multi = mesh_lib.is_multiprocess(mesh)
    layouts = [padded_batch_layout(b, batch_size)[0]
               for b in batch_index_lists(idxs, batch_size)]
    chunks: Dict[str, list] = {}
    pending: Dict[str, list] = {}

    def flush():
        for k, v in pending.items():
            if v:
                merged = v[0] if len(v) == 1 else jnp.concatenate(v, axis=0)
                chunks.setdefault(k, []).append(np.asarray(merged))
                v.clear()

    def checked_host_batches():
        for i, batch in enumerate(iterate_batches(
                dataset, idxs, batch_size, num_threads=num_workers,
                prefetch=prefetch, local=local, s2d=host_s2d)):
            # The threaded prefetcher must deliver batches in order, and
            # this process's rows must be exactly its slice of the global
            # layout — the class of bug the reference has at
            # confidence_sampler.py:41 (scores sorted by a scrambled
            # index) cannot pass silently here.
            if not np.array_equal(batch["index"],
                                  layouts[i][local].astype(np.int32)):
                raise AssertionError(
                    "scoring rows misaligned with the global batch layout")
            yield batch

    # Async double-buffered host->device feed (data/cache.device_prefetch):
    # the gather/decode AND the h2d dispatch of batch n+1 overlap batch
    # n's device compute, so a pool too big for residency is bounded by
    # max(host feed, PCIe, device) instead of their sum — the fallback
    # leg of the pool-residency default.
    from ..data.cache import device_prefetch
    t_chunk, chunk_first = time.perf_counter(), 0
    i = -1
    for i, sharded in enumerate(device_prefetch(
            checked_host_batches(),
            lambda b: mesh_lib.shard_batch(b, mesh))):
        with dispatch_lock:
            out = step_fn(variables, sharded)
            dispatch_lock.drain(out)
        out = _wanted(out, keys)
        for k, v in out.items():
            # Multi-host: keep device arrays and cross-host-gather ONCE
            # after the loop — a per-batch gather would serialize a DCN
            # round-trip into every step of the acquisition hot path.
            (chunks if multi else pending).setdefault(k, []).append(v)
        if (i + 1) % FETCH_EVERY == 0:
            if not multi:
                # Periodic flush (device concat -> ONE host fetch ->
                # buffers freed): bounds the extra HBM to ~FETCH_EVERY
                # batches of outputs even for [B, D] embedding passes.
                flush()
            tele.tick(step=i + 1)
            chunk_span(t_chunk, chunk_first, i + 1 - chunk_first,
                       min((i + 1) * batch_size, n))
            t_chunk, chunk_first = time.perf_counter(), i + 1
    if not multi:
        flush()
    if i + 1 > chunk_first:
        chunk_span(t_chunk, chunk_first, i + 1 - chunk_first, n)
    return _finalize(chunks, multi, mesh, n), i + 1
