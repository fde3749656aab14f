"""Device-resident pools: in-memory dataset rows uploaded once per
experiment and gathered ON DEVICE per batch.

One cache serves every consumer — acquisition scoring
(strategies/scoring.py), evaluation, AND the trainer's resident-gather
train feed (train/trainer.py) — so a pool whose views share storage
(ArrayDataset.with_view) is uploaded exactly once and that single pinned
array feeds scoring, validation, and training.  The byte budget is
accounted across the WHOLE cache: ``eligible`` admits a new array only
when it fits alongside everything already pinned, so "one pinned pool
serves both scoring and training" is also one set of bytes in the
budget, never two.  Entries retain their dataset object: keys include
id()s, and without the reference a recycled id could silently alias
another pool's images.

Residency layout (DESIGN.md §2b): a pool pins either REPLICATED (one
full copy per chip — the pre-sharding behavior, and the only option on
multi-process meshes today) or ROW-SHARDED (``NamedSharding(mesh,
P('data', ...))`` over pool rows: each chip holds ``rows/num_devices``,
so the budget question changes from "does the pool fit on a chip" to
"does rows/num_devices fit").  ``resolve_sharding`` owns the auto rule
(row whenever the single-process mesh has >1 device); ``pinned_bytes``
accounts PER-DEVICE bytes either way, so one budget figure stays a
per-chip HBM figure across both layouts.  Batches are fetched from a
row-sharded pool by ``sharded_pool_gather``: each shard contributes its
owned rows (masked, then psum'd from the owner — batch-sized traffic,
never pool-sized) and the result lands batch-sharded, exactly where the
replicated path's sharding constraint put it — so consumers are
bit-identical across layouts.

Pinned form (DESIGN.md §2b): image rows are NOT pinned as
``[N, H, W, C]``.  The TPU gives that shape a compact layout with the
row index minor-most, and every program that gathers rows from it first
re-lays the WHOLE pool out rows-major (4.93 GB in and out per scoring
dispatch at 32,768 x 224 px: 2.5 s of a 5.7 s round, PERF.md §6 PR 26).
``to_pinned`` reshapes host rows into a form whose device layout keeps
dimension 0 major, so a gather reads whole rows where they lie;
``from_pinned`` brings a gathered batch back to ``[B, H, W, C]`` (same
bytes, a reshape of 256 rows instead of a copy of the pool).  These two
functions are the only code that knows the form; dimension 0 is the row
index in every form, so sharding, ``rows_per_device`` and the in-place
row update read it unchanged.

Layout of a cache dict:
  cache["images"][(id(images), n)] = (dataset, images_dev, labels_dev)
      # images_dev in the pinned form: to_pinned(dataset.images[:n])
  cache["steps"][(id(step_fn), with_labels, sharded, row_shape)] = jitted runner
  cache["lru"] = [key, ...]  # least-recently-used first (eviction order)

Virtual-CPU-mesh caveat: the N replicas' on-device gathers execute
serially on one core there, so resident paths can measure slower on the
test mesh; on real chips the replicas are parallel and the gather
replaces a host->device transfer.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from . import mesh as mesh_lib
from .. import faults
from ..telemetry import profiler as profiler_lib
from ..utils.logging import get_logger

# Device transfer is the classic transient-failure surface (HBM pressure
# beside a live run, a runtime hiccup): the once-per-experiment
# pool upload retries under the ONE RetryPolicy instead of the ad-hoc
# guards that used to live at each transfer site.  OOM is NOT retried —
# re-uploading into the same full HBM fails the same way; the driver's
# degradation ladder owns that case.
_UPLOAD_RETRY = faults.RetryPolicy(site="h2d_upload",
                                   classify=faults.classify_exception,
                                   max_attempts=3)

# The resident cache has CONCURRENT consumers since the pipelined round
# (experiment/pipeline.py): the speculative scorer thread and the
# trainer's per-epoch validation can both resolve the same pool entry
# while training runs.  One process-wide lock around cache mutation
# (first upload, runner build, LRU touch, budget demotion) AND the
# accounting reads that iterate the entry dict (pinned_bytes, cached)
# keeps "upload once per experiment" true under that concurrency and
# keeps a reader from hitting "dict changed size during iteration"
# while the other thread inserts.  Reads of an existing entry still pay
# only the lock handshake.  Reentrant: enforce_budget calls
# pinned_bytes under the lock.
_CACHE_LOCK = threading.RLock()

# Lock discipline, statically enforced (scripts/al_lint.py
# lock-discipline): the cache's shared maps may only be touched under
# _CACHE_LOCK — the speculative scorer, the trainer's validation, and
# the LRU/demotion paths all race on them otherwise.  ``update_warm``
# is the incremental updater's warmed-(layout, shape) marker set.
_GUARDED_BY = {"images": "_CACHE_LOCK",
               "steps": "_CACHE_LOCK",
               "lru": "_CACHE_LOCK",
               "update_warm": "_CACHE_LOCK"}

# Registered step-builders (al_lint recompile-hazard): the jitted
# gather+step runners are built once per (step_fn, labels, layout) and
# cached in the shared resident pool; the incremental row updater is
# built once per (layout, window width) the same way, and its warm-up
# dummy is a once-per-(layout, shape) device-side zeros.
_STEP_BUILDERS = ("get_runner", "_update_runner", "_dummy_like")

# HBM held back from the auto-sized resident budget: training activations,
# XLA workspace, and the model/optimizer trees all coexist with a pinned
# pool.  4 GB covers the ResNet-50 224px train step at 256 rows/chip
# (bf16 activations ~26 KB/row/layer-group, measured envelope well under
# 3 GB) with headroom for compile-time scratch.
AUTO_RESERVE_BYTES = 4 << 30


def local_headroom_stats() -> Dict[str, int]:
    """``memory_stats()`` of the local device with the LEAST headroom
    (bytes_limit - bytes_in_use) — every local device is asked, because a
    pool or a parameter tree that landed whole on one chip of a mesh
    shows only there.  ``{}`` where the backend keeps no statistics
    (CPU).  A TPU device that reports no ``bytes_limit`` is an error,
    not a reason to take the static default: the auto budget would then
    size a 16 GB chip from a constant chosen for hosts without HBM."""
    tightest: Dict[str, int] = {}
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if not stats.get("bytes_limit"):
            if dev.platform == "tpu":
                raise RuntimeError(
                    f"{dev} reports no memory_stats()['bytes_limit']; "
                    "the resident-pool budget cannot be sized from HBM "
                    "headroom (pass --resident_scoring_bytes to set it "
                    "explicitly)")
            return {}
        free = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
        if not tightest or free < (int(tightest["bytes_limit"])
                                   - int(tightest.get("bytes_in_use", 0))):
            tightest = stats
    return tightest


def auto_budget(reserve_bytes: int = AUTO_RESERVE_BYTES,
                stats: Optional[Dict[str, int]] = None,
                pinned: int = 0) -> int:
    """Size the device-resident pool budget from LIVE HBM headroom:
    (bytes_limit − bytes_in_use) − reserve, floored at 0.

    ``pinned``: bytes ALREADY pinned in the caller's resident cache.
    Live headroom has those bytes netted out (they sit in bytes_in_use),
    but the budget is consumed as a TOTAL cap by the shared accounting
    in ``eligible`` — so they are added back, making the auto budget a
    total cap too.  Without this, a round-start refresh would charge
    every pinned pool twice (once inside bytes_in_use, once in
    pinned_bytes) and reject new pools that actually fit.  The static
    fallback budget is already a total cap, so ``pinned`` is NOT added
    there.

    ``stats`` injects a memory_stats dict for tests; by default every
    local device is asked and the tightest one decides
    (``local_headroom_stats``).  Backends that keep no memory statistics
    (CPU) take the conservative static default so tests/parity behavior
    is unchanged off-accelerator."""
    from ..config import RESIDENT_SCORING_BYTES_DEFAULT

    if stats is None:
        stats = local_headroom_stats()
    limit = stats.get("bytes_limit")
    in_use = stats.get("bytes_in_use", 0)
    if not limit:
        budget = RESIDENT_SCORING_BYTES_DEFAULT
    else:
        budget = max(0, int(limit) - int(in_use) - int(reserve_bytes)) \
            + int(pinned)
    if jax.process_count() > 1:
        # Every process must resolve the SAME budget: the budget decides
        # resident-vs-streamed scoring, which are different collective
        # programs — per-process headroom (allocator state differs across
        # hosts) would deadlock the mesh at the first boundary pool.  Take
        # the fleet minimum so a pinned pool fits everywhere.  All
        # processes call this at the same points (trainer init, round
        # start), so the collective is in lockstep.
        from jax.experimental import multihost_utils
        budget = int(np.min(multihost_utils.process_allgather(
            np.asarray(budget, np.int64))))
    return budget


def resolve_budget(spec: Optional[int],
                   stats: Optional[Dict[str, int]] = None,
                   cache: Optional[Dict] = None) -> int:
    """TrainConfig.resident_scoring_bytes -> concrete byte budget:
    None = auto-size from live HBM headroom (pool residency is the
    DEFAULT behavior, not an override); an explicit integer — including
    0 to disable — is taken as-is.  ``cache``: the caller's resident
    cache, so a live-headroom auto budget stays a TOTAL cap alongside
    the shared accounting (see auto_budget's ``pinned``)."""
    if spec is None:
        budget = auto_budget(stats=stats, pinned=pinned_bytes(cache))
        get_logger().debug(
            f"resident pool budget auto-sized to {budget / 1e9:.1f} GB")
        return budget
    return int(spec)


def resolve_sharding(spec: Optional[str], mesh) -> str:
    """TrainConfig.pool_sharding -> the concrete resident layout,
    "replicated" or "row".  "auto" (or None): row whenever the mesh has
    more than one device — per-chip residency then scales 1/ndev with
    chip count for free, and on MULTI-PROCESS meshes (the pod tier,
    DESIGN.md §15) each host additionally assembles only its own shard
    of the upload (mesh_lib.shard_rows' per-process arm), so the pool
    never lands whole on any one host either.  Only single-device
    meshes stay replicated (sharding over one device is replication
    with extra steps)."""
    if spec in (None, "auto"):
        spec = "row"
    if spec not in ("replicated", "row"):
        raise ValueError(
            f"pool_sharding={spec!r} is not one of 'auto'/'replicated'/"
            "'row'")
    if spec == "row" and (mesh is None or mesh.devices.size <= 1):
        return "replicated"
    return spec


def _per_device_bytes(array: Any) -> int:
    """HBM bytes one device holds for ``array``: the largest addressable
    shard (replicated arrays shard as full copies, row-sharded ones as
    rows/ndev) — so the budget stays a per-chip figure across layouts."""
    shards = getattr(array, "addressable_shards", None)
    if shards:
        return max(int(s.data.nbytes) for s in shards)
    return int(array.nbytes)


def pinned_bytes(cache: Optional[Dict]) -> int:
    """Total PER-DEVICE bytes of every image array currently pinned in
    ``cache`` (replicated entries cost their full size per chip,
    row-sharded entries rows/ndev — the budget is a per-chip HBM
    figure either way)."""
    if not cache:
        return 0
    with _CACHE_LOCK:
        return sum(_per_device_bytes(entry[1])
                   for entry in cache.get("images", {}).values())


def rows_per_device(cache: Optional[Dict]) -> list:
    """For every pinned pool array in ``cache``: {device id: rows held}.
    Replicated entries show the full row count on each device,
    row-sharded ones rows/ndev — the placement evidence the round
    journal records (a pool that landed whole on one chip shows here)."""
    if not cache:
        return []
    with _CACHE_LOCK:
        arrays = [entry[1] for entry in cache.get("images", {}).values()]
    out = []
    for array in arrays:
        per_dev: Dict[str, int] = {}
        for shard in array.addressable_shards:
            key = str(shard.device.id)
            per_dev[key] = per_dev.get(key, 0) + int(shard.data.shape[0])
        out.append(per_dev)
    return out


def eligible(dataset: Any, max_bytes: int,
             cache: Optional[Dict] = None,
             shard_ways: int = 1) -> bool:
    """In-memory (ArrayDataset-style) and within the byte budget.

    With a ``cache``, the budget is shared across every pinned array:
    a new pool is admitted only if it fits ALONGSIDE what is already
    resident, and an already-pinned pool is ALWAYS eligible — checked
    before the budget guard, so a pool pinned before the budget shrank
    (even to 0) keeps its fast path: its bytes sit in HBM either way,
    and streaming would pay twice (the rule previously restated as
    ``or cached(...)`` at every call site — this is the one spelling).
    Without a cache (direct callers), the old single-array check
    applies.

    ``shard_ways``: how many devices a prospective upload would be
    row-sharded over (1 = replicated).  Under row sharding a chip pins
    only ceil(rows/ways) rows, so the budget admits pools ~ways times
    larger — the scale-out the sharded pool exists for."""
    if cache is not None and cached(cache, dataset):
        return True
    images = getattr(dataset, "images", None)
    if not (max_bytes > 0 and isinstance(images, np.ndarray)):
        return False
    n = len(dataset)
    ways = max(1, int(shard_ways))
    row_bytes = int(np.prod(images.shape[1:])) * images.itemsize
    need = -(-n // ways) * row_bytes  # ceil: covers the shard pad rows
    return pinned_bytes(cache) + need <= max_bytes


def cached(cache: Optional[Dict], dataset: Any) -> bool:
    """True when ``dataset``'s images are ALREADY uploaded in this cache.
    A pool that is resident stays usable even after an auto-budget
    refresh shrinks the budget below its size — its bytes are part of
    the in-use figure the refresh measured, so dropping to the host path
    would pay streaming cost while the HBM stays pinned anyway."""
    if not cache:
        return False
    images = getattr(dataset, "images", None)
    if not isinstance(images, np.ndarray):
        return False
    # Under the cache lock like every other reader: the speculative
    # scorer resolves entries concurrently with the trainer's uploads,
    # and this membership probe was the one access left bare (found by
    # the lock-discipline checker; the GIL made it merely racy-looking
    # on CPython, but the discipline is the contract).
    with _CACHE_LOCK:
        return (id(images), len(dataset)) in cache.get("images", {})


# One (8, 128) tile of the TPU's compact layouts, in elements.  A
# trailing ``[k, 128]`` keeps the row index major-most exactly when k
# fills whole tiles (k % 8 == 0); otherwise the device folds the ROW
# index into the tile and a gather re-lays the pool again (compiled
# for the v5e: u8[32768,1176,128] pins as {2,1,0}, u8[32768,294,128]
# as {2,0,1} with a pool-sized copy in front of the gather).
_LANES = 128
_TILE = 8 * _LANES


def pinned_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """``[n, *row_shape]`` -> the shape of its pinned form ``[n, ...]``.
    Rows whose elements fill whole (8, 128) tiles pin as
    ``[n, elems/128, 128]``, where the device keeps each row contiguous;
    any other row pins flat as ``[n, elems]`` (it fits no better form:
    the device may still fold n into the tile there, which costs what
    ``[n, H, W, C]`` always cost).  The form follows the row alone —
    never a flag, a config field or a model."""
    elems = int(np.prod(shape[1:], dtype=np.int64))
    if elems and elems % _TILE == 0:
        return (int(shape[0]), elems // _LANES, _LANES)
    return (int(shape[0]), elems)


def to_pinned(rows: Any) -> Any:
    """Host rows ``[n, *row_shape]`` -> the pinned form
    (``pinned_shape``): a reshape — a view of contiguous rows, no bytes
    move and no value changes."""
    return rows.reshape(pinned_shape(rows.shape))


def from_pinned(batch: Any, row_shape: Tuple[int, ...]) -> Any:
    """A batch gathered from a pinned array (or a host copy of pinned
    rows) -> ``[B, *row_shape]``: the inverse reshape of ``to_pinned``,
    bit for bit the rows the host held."""
    return batch.reshape(batch.shape[0], *row_shape)


def pool_arrays(cache: Dict, dataset: Any, mesh,
                sharding: str = "replicated") -> Tuple[Any, Any]:
    """(images_dev, labels_dev) for the dataset, uploaded once per
    (underlying array, length) — views sharing storage share the upload.
    ``images_dev`` is in the pinned form (``to_pinned``): read rows back
    through ``pool_gather`` / ``from_pinned``, never by its shape.
    ``sharding`` "row": rows split over the mesh's data axis
    (mesh_lib.shard_rows — zero-padded to divide evenly; the full array
    never lands on any single device), "replicated": one copy per chip.
    The FIRST upload fixes an entry's layout (the mode is a per-
    experiment deployment choice, resolved once by resolve_sharding);
    consumers detect it off the array itself (mesh_lib.is_row_sharded).
    replicate()/shard_rows device_put EXPLICITLY (transfer-guard
    friendly).  Every access refreshes the entry's position in the LRU
    eviction order."""
    with _CACHE_LOCK:
        images = cache.setdefault("images", {})
        n = len(dataset)
        key = (id(dataset.images), n)
        if key not in images:

            def _upload():
                faults.site("h2d_upload")
                if sharding == "row" and mesh.devices.size > 1:
                    # No ascontiguousarray here: shard_rows slices per
                    # shard (and makes each block contiguous itself), so
                    # the one big host copy the replicated path pays is
                    # exactly what the row path avoids.
                    return (
                        dataset,
                        mesh_lib.shard_rows(
                            to_pinned(dataset.images[:n]), mesh),
                        mesh_lib.shard_rows(
                            dataset.targets[:n].astype(np.int32), mesh))
                return (
                    dataset,
                    mesh_lib.replicate(
                        to_pinned(np.ascontiguousarray(dataset.images[:n])),
                        mesh),
                    mesh_lib.replicate(
                        dataset.targets[:n].astype(np.int32), mesh))

            images[key] = _UPLOAD_RETRY.call(_upload)
        lru = cache.setdefault("lru", [])
        if key in lru:
            lru.remove(key)
        lru.append(key)
        return images[key][1], images[key][2]


def sharded_pool_gather(images, ids, mesh, labels=None):
    """Rows of a ROW-SHARDED pool for a replicated [batch] index vector,
    returned batch-sharded — the sharded pool's one batch-fetch
    primitive, shared by the scoring/eval runners (get_runner) and the
    trainer's resident-gather feed.  Traceable: shard_map composes under
    jit and inside lax.scan, so callers embed it in their own jitted
    steps.

    Mechanics (all inside shard_map over the data axis): every shard
    masks the batch ids it owns, gathers those rows locally, and a psum
    assembles the full batch from the owners (non-owners contribute
    exact zeros — the sum is the owner's bytes, bit for bit, uint8
    included).  Traffic is batch-sized, never pool-sized; each shard
    then keeps only ITS slice of the batch, so the output lands exactly
    where the replicated path's ``with_sharding_constraint(images[ids],
    batch_sharding)`` put it and every downstream consumer partitions
    identically — which is why batches are bit-identical across pool
    layouts (tests/test_pool_sharding.py).

    The global batch must divide the mesh (Trainer.padded_batch_size
    guarantees it for every caller)."""
    axis = mesh_lib.DATA_AXIS
    ndev = mesh.devices.size

    def local_gather(pool, idv):
        full = mesh_lib.owner_rows(pool, idv, axis)
        i = jax.lax.axis_index(axis)
        b_local = idv.shape[0] // ndev
        return jax.lax.dynamic_slice_in_dim(full, i * b_local, b_local, 0)

    img_spec = P(axis, *([None] * (images.ndim - 1)))
    if labels is None:
        return shard_map(local_gather, mesh=mesh,
                         in_specs=(img_spec, P()), out_specs=img_spec,
                         check_vma=False)(images, ids)
    return shard_map(
        lambda im, lb, idv: (local_gather(im, idv), local_gather(lb, idv)),
        mesh=mesh, in_specs=(img_spec, P(axis), P()),
        out_specs=(img_spec, P(axis)), check_vma=False)(images, labels, ids)


def pool_gather(images, ids, mesh, row_shape: Tuple[int, ...], labels=None,
                sharded: bool = False):
    """One batch of pool rows ``[batch, *row_shape]`` (and labels) for a
    replicated [batch] index vector, batch-sharded — the ONE spelling of
    the per-batch gather in the scoring/eval runners, the resident batch
    step and the epoch scan, under the named scope ``pool_gather`` so a
    device trace says which operations are the gather (scopes are
    metadata: the compiled program is the same with and without them).
    ``images`` is a pinned array (``to_pinned``); the gathered rows come
    back through ``from_pinned`` here, so every consumer downstream sees
    the rows as the host held them.  ``sharded`` follows the pool
    entry's actual layout: shard-local pick + owner psum
    (sharded_pool_gather) against a full-array index; both land the
    batch in the same batch sharding."""
    with jax.named_scope("pool_gather"):
        if sharded:
            out = sharded_pool_gather(images, ids, mesh, labels=labels)
            img, lab = out if labels is not None else (out, None)
        else:
            img, lab = images[ids], None if labels is None else labels[ids]
        img = jax.lax.with_sharding_constraint(
            from_pinned(img, row_shape), mesh_lib.batch_sharding(mesh))
        return img if labels is None else (img, lab)


# The incremental row update's FIXED window width (rows): every drain,
# whatever its size, applies as a sequence of exactly-this-wide blocks
# (the tail block slides back over already-current rows, an identity
# rewrite), so ONE jitted updater per (layout, entry shape) covers
# every drain — a 1000-row append can never compile a fresh width
# inside a warm round.
UPDATE_BLOCK_FLOOR = 64


def _update_runner(cache: Dict, mesh, sharded: bool, width: int
                   ) -> Callable:
    """Jitted in-place row updater for a pinned pool entry, one per
    (layout, window width), cached beside the gather runners: a
    ``[width, ...]`` host block lands at row ``lo`` of the resident
    array via ``dynamic_update_slice`` — the ONLY image bytes that
    cross the host->device boundary on an in-extent streaming drain.
    Replicated entries donate the old buffer (XLA updates in place);
    row-sharded entries scatter each block row to its owning shard
    (local index math + mode="drop", no collectives) — donation is
    skipped there, matching the sharded k-center jits (XLA:CPU rejects
    donating sharded buffers with a per-call warning)."""
    key = ("update_rows", bool(sharded), int(width))
    with _CACHE_LOCK:
        steps = cache.setdefault("steps", {})
        if key in steps:
            return steps[key]
    axis = mesh_lib.DATA_AXIS

    if sharded:

        @jax.jit
        def run(images, block, lo):
            def body(img, blk, lo_):
                rows = img.shape[0]
                off = (jax.lax.axis_index(axis) * rows).astype(jnp.int32)
                gidx = lo_.astype(jnp.int32) + jnp.arange(
                    blk.shape[0], dtype=jnp.int32)
                # Off-shard rows park PAST the shard (rows) so
                # mode="drop" discards them — the _owned_or_oob rule
                # (a bare gidx - off would wrap negative indices).
                loc = jnp.where((gidx >= off) & (gidx < off + rows),
                                gidx - off, rows)
                return img.at[loc].set(blk, mode="drop")

            spec = P(axis, *([None] * (images.ndim - 1)))
            return shard_map(body, mesh=mesh, in_specs=(spec, P(), P()),
                             out_specs=spec,
                             check_vma=False)(images, block, lo)
    else:

        @functools.partial(
            jax.jit, donate_argnums=(0,),
            out_shardings=mesh_lib.replicated_sharding(mesh))
        def run(images, block, lo):
            return jax.lax.dynamic_update_slice(
                images, block, (lo,) + (0,) * (images.ndim - 1))

    with _CACHE_LOCK:
        return steps.setdefault(key, run)


def update_rows(cache: Optional[Dict], dataset: Any, mesh,
                row_lo: int, row_hi: int) -> bool:
    """Incrementally refresh a PINNED pool entry after a streaming
    drain that appended rows (or attached labels) WITHOUT growing the
    extent: rows ``[row_lo, row_hi)`` ride h2d as a sequence of
    fixed-width blocks ``dynamic_update_slice``'d into the resident
    array IN PLACE (the tail block slides back over already-current
    rows — an identity rewrite — so every dispatch has the ONE
    prewarmed shape); the pinned extent is never re-uploaded.  Labels
    re-upload whole (a [capacity]-int32 device_put: tiny, never a
    compile) so label-only records are covered by the same call, and
    they upload BEFORE the first donating image dispatch — a transient
    label-upload failure leaves the entry untouched and valid.
    Returns False when the entry is not pinned or smaller than one
    window — the caller falls back to ``release`` + re-upload (the
    extent-boundary path).  A failure INSIDE the donating image
    update drops the entry before re-raising: the old buffer may
    already be consumed, and a cache entry pointing at a deleted
    array would poison every retry (the next access re-uploads
    instead).

    Caller contract: a drain point with no in-flight consumers of the
    entry's arrays (the stream service's single mutation point) — the
    replicated form DONATES the old buffer."""
    images = getattr(dataset, "images", None)
    if not isinstance(images, np.ndarray):
        return False
    n = len(dataset)
    key = (id(images), n)
    with _CACHE_LOCK:
        entry = cache.get("images", {}).get(key) if cache else None
    if entry is None:
        return False
    _, images_dev, _ = entry
    sharded = mesh_lib.is_row_sharded(images_dev)
    width = int(row_hi) - int(row_lo)
    block_rows = UPDATE_BLOCK_FLOOR
    if width > 0 and block_rows > n:
        return False
    # Labels FIRST, under the ONE upload RetryPolicy: no donation is
    # involved, so a transient H2D failure retries (and a final failure
    # propagates) with the entry still intact and valid.
    def _labels():
        if sharded:
            return mesh_lib.shard_rows(
                dataset.targets[:n].astype(np.int32), mesh)
        return mesh_lib.replicate(
            dataset.targets[:n].astype(np.int32), mesh)

    new_labels = _UPLOAD_RETRY.call(_labels)
    new_images = images_dev
    if width > 0:
        run = _update_runner(cache, mesh, sharded, block_rows)
        try:
            for lo0 in range(int(row_lo), int(row_hi), block_rows):
                lo = min(lo0, n - block_rows)
                block = to_pinned(
                    np.ascontiguousarray(images[lo:lo + block_rows]))
                new_images = run(new_images, block, jnp.int32(lo))
        except Exception:
            # The old buffer may be donated-and-gone: drop the entry so
            # the next access re-uploads cleanly instead of dispatching
            # against a deleted array forever.
            release(cache, dataset)
            raise
    with _CACHE_LOCK:
        images_map = cache.get("images", {})
        if key not in images_map:
            return False
        images_map[key] = (dataset, new_images, new_labels)
        lru = cache.setdefault("lru", [])
        if key in lru:
            lru.remove(key)
        lru.append(key)
        cache.setdefault("update_warm",
                         set()).add((sharded, images_dev.shape))
    return True


def prewarm_update(cache: Optional[Dict], dataset: Any, mesh) -> bool:
    """Build + warm the incremental updater for ``dataset``'s pinned
    entry by dispatching it once against a THROWAWAY zeros array of the
    entry's exact shape/layout — so the first real in-extent drain
    dispatches a warm executable instead of paying a compile inside a
    warm round (the jit-delta-0 contract, tests/test_compile_reuse.py).
    The stream service calls this right after each round, landing the
    compile in that round's (already-taxed) window.  Deliberately
    touches NEITHER the entry nor its buffers: the pipelined round's
    speculative scorer may still hold the live array, and a donating
    identity update here would delete it out from under that thread
    (update_rows' no-in-flight-consumers contract is the DRAIN point's
    to establish, not this warm-up's).  A TRUE no-op once the (layout,
    entry shape) pair is warmed — the marker re-arms after extent
    growth (same jit, new shape trace) and skips everything (no h2d,
    no dispatch) otherwise.  False when the entry is not pinned or too
    small to ever use the updater."""
    images = getattr(dataset, "images", None)
    if cache is None or not isinstance(images, np.ndarray) \
            or len(dataset) < UPDATE_BLOCK_FLOOR:
        return False
    key = (id(images), len(dataset))
    with _CACHE_LOCK:
        entry = cache.get("images", {}).get(key)
        if entry is None:
            return False
        images_dev = entry[1]
        sharded = mesh_lib.is_row_sharded(images_dev)
        marker = (sharded, images_dev.shape)
        if marker in cache.get("update_warm", set()):
            return True
    run = _update_runner(cache, mesh, sharded, UPDATE_BLOCK_FLOOR)
    dummy = _dummy_like(images_dev, mesh, sharded)
    block = np.zeros((UPDATE_BLOCK_FLOOR, *images_dev.shape[1:]),
                     images_dev.dtype)
    run(dummy, block, jnp.int32(0))  # warmed; the dummy is garbage now
    with _CACHE_LOCK:
        cache.setdefault("update_warm", set()).add(marker)
    return True


def _dummy_like(images_dev, mesh, sharded: bool):
    """Device-side zeros in a pinned entry's exact shape/dtype/layout —
    the warm-up stand-in prewarm_update dispatches the updater against.
    Built ON DEVICE (``jnp.zeros`` under an out_shardings-pinned jit):
    a host-side zeros of a multi-GB pool would transiently double the
    host allocation AND pay pool-scale H2D per device just to warm an
    executable.  Compiles once per (layout, shape) — exactly the
    cadence prewarm runs it (the marker gates re-entry), inside the
    already-taxed round window."""
    sharding = (mesh_lib.row_sharding(mesh) if sharded
                else mesh_lib.replicated_sharding(mesh))
    return jax.jit(
        functools.partial(jnp.zeros, images_dev.shape, images_dev.dtype),
        out_shardings=sharding)()


def pin_hot(cache: Optional[Dict], tag: str,
            images_dev: Any, labels_dev: Any) -> bool:
    """Register an ALREADY-UPLOADED hot row block under the shared
    budget accounting — the disk tier's HBM leg (DESIGN.md §16): a
    demand-paged pool never pins whole (its ``.images`` raises by
    contract), but the trainer's hot labeled-subset copy is HBM like
    any pinned pool and must show up in ``pinned_bytes`` so the ONE
    per-chip budget figure covers all three tiers.  Keyed by ``tag``
    (one slot per trainer): re-pinning the same tag replaces the entry
    — the previous round's hot copy is released, never double-counted.
    The entry stores no dataset (a paged pool has no id(images) to
    key by); ``pinned_bytes`` and ``enforce_budget`` never inspect
    keys, so the synthetic entry demotes LRU-first like any other —
    a demotion only drops the cache's reference (the running fit holds
    its own), so the budget squeeze lands at the NEXT fit's resolve."""
    if cache is None:
        return False
    key = ("hot", tag)
    with _CACHE_LOCK:
        cache.setdefault("images", {})[key] = (None, images_dev,
                                               labels_dev)
        lru = cache.setdefault("lru", [])
        if key in lru:
            lru.remove(key)
        lru.append(key)
    return True


def unpin_hot(cache: Optional[Dict], tag: str) -> bool:
    """Drop a ``pin_hot`` entry (if present) — the disk tier's release
    hook when a trainer's hot copy is abandoned rather than replaced."""
    if not cache:
        return False
    key = ("hot", tag)
    with _CACHE_LOCK:
        entry = cache.get("images", {}).pop(key, None)
        lru = cache.get("lru", [])
        if key in lru:
            lru.remove(key)
    return entry is not None


def release(cache: Optional[Dict], dataset: Any) -> bool:
    """Drop ``dataset``'s pinned entry (if any) so the NEXT access
    re-uploads — the streaming subsystem's invalidation hook: an ingest
    drain appends real rows into extent slots that were zero padding
    when the pool was pinned, so the device copy is stale row-wise even
    though its shape (the extent capacity) is unchanged.  Dropping the
    entry costs one re-upload at the old shape; it never costs a
    compile, because the gather runners are keyed on (step_fn, layout),
    not on the array.  Returns True when an entry was actually
    dropped."""
    if not cache:
        return False
    images = getattr(dataset, "images", None)
    if not isinstance(images, np.ndarray):
        return False
    key = (id(images), len(dataset))
    with _CACHE_LOCK:
        entry = cache.get("images", {}).pop(key, None)
        lru = cache.get("lru", [])
        if key in lru:
            lru.remove(key)
    return entry is not None


def enforce_budget(cache: Optional[Dict], max_bytes: int) -> list:
    """Demote pinned pools, least-recently-used first, until the cache
    fits ``max_bytes`` — the clean-shrink path for an EXPLICIT budget
    that got smaller mid-run (the AUTO budget never demotes: an
    already-pinned pool's bytes are part of the headroom it measures,
    see ``cached``).  Dropping the entry releases the device buffers;
    consumers notice via ``cached()`` turning False and fall back to
    their host paths at the next call — no shape change, no recompile,
    because the host paths' batch shapes were never a function of
    residency.  Returns the demoted keys."""
    if not cache:
        return []
    demoted = []
    with _CACHE_LOCK:
        images = cache.get("images", {})
        lru = cache.get("lru", [])
        while images and pinned_bytes(cache) > max(0, int(max_bytes)):
            key = next((k for k in lru if k in images), next(iter(images)))
            images.pop(key)
            if key in lru:
                lru.remove(key)
            demoted.append(key)
    if demoted:
        get_logger().info(
            f"resident pool budget shrank to {max_bytes / 1e9:.2f} GB: "
            f"demoted {len(demoted)} pinned pool(s); affected consumers "
            "fall back to host-streamed paths")
    return demoted


def get_runner(cache: Dict, step_fn: Callable, mesh, name: str,
               row_shape: Tuple[int, ...], with_labels: bool = False,
               sharded: bool = False) -> Callable:
    """Jitted gather+step over a resident pool: rows are picked out on
    device and constrained to the batch sharding, so each batch costs one
    tiny [batch]-int32 transfer instead of the image rows.  ``name`` is
    the caller's name for the program (``run_score_<kind>`` from
    scoring.collect_pool, ``run_eval`` from Trainer.evaluate): it is set
    on the function that is jitted, so the device trace shows
    ``jit_<name>`` and the scoring and evaluation runners are told apart
    by name.  ``row_shape`` is the dataset's ``image_shape``: the pinned
    array no longer says it (``to_pinned``), and the step sees
    ``[batch, *row_shape]``.  ``sharded`` (caller reads it off the entry via
    mesh_lib.is_row_sharded): the gather goes through
    sharded_pool_gather — shard-local row pick + owner psum instead of a
    full-array index — landing the batch in the SAME batch sharding, so
    the step partitions identically and scores are bit-identical across
    pool layouts."""
    row_shape = tuple(int(d) for d in row_shape)
    key = (id(step_fn), with_labels, bool(sharded), row_shape)
    with _CACHE_LOCK:
        steps = cache.setdefault("steps", {})
        if key in steps:
            return steps[key]

    if with_labels:

        def run(variables, images, labels, ids, mask):
            img, lab = pool_gather(images, ids, mesh, row_shape,
                                   labels=labels, sharded=sharded)
            batch = {"image": img, "label": lab, "mask": mask}
            return step_fn(variables, batch)
    else:

        def run(variables, images, ids, mask):
            img = pool_gather(images, ids, mesh, row_shape, sharded=sharded)
            batch = {"image": img, "mask": mask}
            return step_fn(variables, batch)

    run.__name__ = run.__qualname__ = name
    run = jax.jit(run)

    # setdefault under the lock: if another thread built the same runner
    # meanwhile, ONE wins and both callers share it — two live runner
    # objects for one (step_fn, layout) would each compile separately.
    with _CACHE_LOCK:
        return steps.setdefault(key, run)


def assert_pool_read_in_place(run: Callable, args: Tuple,
                              pool_arg: int) -> Dict[str, int]:
    """Lower and compile ``run`` (a ``get_runner`` program) for ``args``
    — arrays or ``ShapeDtypeStruct``s; ``args[pool_arg]`` is the pinned
    pool — and raise ``AssertionError`` unless the program reads the
    pool where it lies: its temporaries stay under a quarter of the
    pool's per-device bytes, and no instruction other than a parameter
    produces a pool-sized array (the re-layout ``copy`` this form exists
    to avoid, or a reshape / cast of the whole pool crept into
    ``pool_gather``).  Compiling for the device IS the check: the
    operand's layout is the compiler's choice for the device at hand, so
    the same call guards the form on the chip (chip_smoke.py), for a
    described chip (tests/test_chip_compile.py) and, for a pool-sized
    reshape only, on the CPU.  Returns the figures it judged."""
    pool = args[pool_arg]
    shard = pool.sharding.shard_shape(pool.shape)
    pool_bytes = int(np.prod(shard, dtype=np.int64)) * pool.dtype.itemsize
    compiled = run.lower(*args).compile()
    temp = int(compiled.memory_analysis().temp_size_in_bytes)
    sized = [f"{name} = {opcode}(...)" for name, opcode, nbytes
             in profiler_lib.hlo_text_instructions(compiled.as_text())
             if nbytes >= pool_bytes and opcode != "parameter"]
    if sized or temp >= pool_bytes // 4:
        raise AssertionError(
            f"the program does not read its {pool_bytes}-byte pool "
            f"operand in place: {temp} bytes of temporaries, pool-sized "
            f"instructions {sized}")
    return {"pool_bytes": pool_bytes, "temp_bytes": temp}
