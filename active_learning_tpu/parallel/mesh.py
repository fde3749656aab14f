"""Device mesh + sharding helpers.

Replaces the reference's process-per-GPU DDP world (mp.spawn + NCCL process
group per round, src/query_strategies/strategy.py:288-336) with ONE
persistent JAX runtime and a `jax.sharding.Mesh`:

  * 1-D ``data`` axis today (the reference's only parallelism is data
    parallel, SURVEY.md §2), with the axis names kept open for model axes.
  * Batches are sharded over ``data``; parameters are replicated.  Under
    ``jit``'s automatic partitioning the gradient reduction and batch-norm
    statistics lower to XLA collectives over ICI — the DDP allreduce
    (strategy.py:336), metric all_gather (evaluation.py:69-98) and
    SyncBatchNorm (strategy.py:292) all fall out of the sharding annotations.
  * Multi-host pods: `initialize_distributed()` wires `jax.distributed`
    over DCN; the mesh then spans all processes' devices.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import faults

DATA_AXIS = "data"


def platform_is_cpu() -> bool:
    """True when the configured JAX platform list names cpu first — read
    from the jax config knob OR the env var, WITHOUT initializing a
    backend (callers run before the multi-host rendezvous).  Unset reads
    as not-CPU: accelerator machines rarely set it, CPU test/smoke
    environments always do (conftest, the tier-1 recipe)."""
    spec = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS") or ""
    return spec.split(",")[0].strip().lower() == "cpu"


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host init over DCN (no-op for single-process runs).

    The TPU equivalent of the reference's NCCL rendezvous
    (strategy.py:288-289,315) — but done once per run, not once per round.
    Must run before any JAX backend initializes.  On a TPU pod slice pass
    just ``num_processes`` (the host count) and JAX auto-discovers the
    coordinator and process id; CPU/GPU clusters pass all three.  The CLI
    exposes --coordinator_address / --num_processes / --process_id.
    With no arguments at all this is a no-op (single-process run).
    """
    if num_processes is None and coordinator_address is None:
        return
    if num_processes is not None and num_processes <= 1:
        return
    # XLA:CPU cannot run cross-process computations on its default
    # (in-process) collectives — a 2-process CPU mesh dies at the first
    # jit with "Multiprocess computations aren't implemented on the CPU
    # backend".  The gloo implementation CAN, and it is how the pod-tier
    # contract is tested without hardware (the 2-process localhost
    # harness in tests/test_pod_tier.py).  Armed only when the
    # configured platform is CPU (a jax.config.update("jax_platforms",
    # "cpu") launch must arm too); accelerators keep their native
    # ICI/DCN collectives.
    if platform_is_cpu():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id)


def is_coordinator() -> bool:
    """True on the process that owns run-level side effects (checkpoint
    writes, metric sinks, audit files) — the reference's rank-0 guard
    (strategy.py:425-430)."""
    return jax.process_index() == 0


def is_multiprocess(mesh: Mesh) -> bool:
    """True when ``mesh`` spans devices of more than one process."""
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


class DispatchGate:
    """ONE enqueue order for collective-bearing dispatches when two host
    threads share a mesh (the pipelined round's speculative scorer +
    the trainer — experiment/pipeline.py, DESIGN.md §8).

    Used as a context manager around each jitted dispatch.  Two tiers of
    protection, matched to what each backend actually guarantees:

      * **Enqueue ordering (always).**  The lock makes every device see
        the two streams' computations enqueued in one global order.  On
        TPU that is sufficient: each core executes its enqueued programs
        in FIFO order, so collectives from different executables can
        never interleave across cores.
      * **Execution draining (``drain_mode``, CPU meshes only).**
        XLA:CPU does NOT preserve enqueue order at execution — device
        programs run on one shared thread pool, so computation A's
        program on core 2 can be parked behind computation B's while
        B's core-0 program waits on A's rendezvous: a cross-thread
        collective deadlock (observed live; two AllReduce run_ids
        mutually stuck).  When ``drain_mode`` is on, the dispatch site
        calls ``drain(out)`` BEFORE releasing the gate, so at most one
        collective-bearing computation is ever in flight.  The scorer
        arms it for exactly the window it shares the mesh
        (RoundPipeline.arm -> consume); single-threaded phases and
        sequential rounds never pay the sync.

    Reentrant so a dispatch site may nest helpers that also take the
    gate."""

    def __init__(self):
        self._lock = threading.RLock()
        # Flipped by the pipelined round on CPU meshes only; plain bool
        # write/read (atomic under the GIL).
        self.drain_mode = False
        # Per-thread seconds spent BLOCKED acquiring the gate — i.e.
        # stalled on the other stream's hold.  The overlap accounting
        # reads this to avoid claiming scorer time that actually
        # serialized with the train stream (and vice versa) as overlap.
        self._waits: Dict[int, float] = {}
        self._waits_lock = threading.Lock()

    def __enter__(self) -> "DispatchGate":
        # Fault point BEFORE the acquire: an injected failure here never
        # leaves the gate held (the `with` never entered).
        faults.site("dispatch")
        # Uncontended (and reentrant-by-holder) acquires take the fast
        # path: no clock read, no wait recorded.
        if not self._lock.acquire(blocking=False):
            t0 = time.perf_counter()
            self._lock.acquire()
            dt = time.perf_counter() - t0
            tid = threading.get_ident()
            with self._waits_lock:
                self._waits[tid] = self._waits.get(tid, 0.0) + dt
        return self

    def __exit__(self, *exc) -> bool:
        self._lock.release()
        return False

    def take_wait_s(self) -> float:
        """Seconds THIS thread spent blocked acquiring the gate since
        its last take (reset on read) — the contention the other
        stream's holds cost it."""
        with self._waits_lock:
            return self._waits.pop(threading.get_ident(), 0.0)

    def drain(self, tree: Any) -> Any:
        """Block until ``tree``'s arrays are computed — only in drain
        mode (see above); a no-op everywhere else, preserving the async
        dispatch the trainer's deferred loss materialization relies
        on.  Call while still HOLDING the gate."""
        if self.drain_mode:
            jax.block_until_ready(tree)
        return tree


def process_local_rows(mesh: Mesh, batch_size: int) -> slice:
    """The contiguous row range of a ``[batch_size, ...]`` batch (sharded
    over the data axis) owned by THIS process's devices.

    This is the per-host analogue of the reference's DistributedSampler
    rank slicing (strategy.py:312-314): each host feeds only its own rows,
    so a pod never decodes the full global batch per host.  Row ownership
    is read off the sharding itself, so it stays correct for any device
    order.  Single-process meshes own everything: slice(0, batch_size).
    """
    idx_map = batch_sharding(mesh).addressable_devices_indices_map(
        (batch_size,))
    if not idx_map:
        raise AssertionError(
            "this process owns no devices in the mesh — every process "
            "must contribute all its local devices (see make_mesh)")
    spans = []
    for idx in idx_map.values():
        s = idx[0]
        spans.append((s.start or 0,
                      batch_size if s.stop is None else s.stop))
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans)
    if sum(e - s for s, e in spans) != hi - lo:
        raise AssertionError(
            f"process-local rows are not contiguous: {sorted(spans)}; "
            "the data axis must map each process to one contiguous block")
    return slice(lo, hi)


def process_pool_rows(mesh: Mesh, n_rows: int) -> slice:
    """The contiguous range of REAL pool rows [0, n_rows) owned by this
    process under the row-sharded layout — ``process_local_rows`` over
    the padded row count (``shard_rows`` pads to divide the mesh
    evenly), clamped back to the real rows.  The disk-pool backend
    (data/diskpool.py) reads only this range per host, the same
    per-process slicing ``shard_rows`` uploads through, so a pool never
    lands whole on any one host.  Single-process meshes own everything.
    """
    total = int(n_rows) + row_shard_pad(int(n_rows), mesh)
    local = process_local_rows(mesh, total)
    return slice(min(local.start, int(n_rows)), min(local.stop, int(n_rows)))


def make_mesh(num_devices: int = -1,
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """1-D data-parallel mesh over the first ``num_devices`` devices
    (-1 = all).  Mirrors world_size = torch.cuda.device_count()
    (main_al.py:96)."""
    if devices is None:
        devices = jax.devices()
    if num_devices == -1:
        num_devices = len(devices)
    if jax.process_count() > 1 and num_devices != len(devices):
        # Trimming would drop some processes' devices entirely — those
        # processes would own no rows of any batch and every collective
        # would deadlock or diverge.  Shrink the world, not the mesh.
        raise ValueError(
            f"num_devices={num_devices} would trim a {len(devices)}-device "
            "multi-host mesh; use fewer processes instead")
    devices = np.asarray(devices[:num_devices])
    return Mesh(devices, (DATA_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (batch) dimension split across the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (pool-row) dimension split across the data axis — the
    resident-pool layout of DESIGN.md §2b.  Identical to batch_sharding
    in spec; named separately because the two axes mean different
    things: a batch is transient per step, pool rows are pinned for the
    experiment and their per-chip HBM cost is ``nbytes / num_devices``.
    """
    return NamedSharding(mesh, P(DATA_AXIS))


def row_shard_pad(n: int, mesh: Mesh) -> int:
    """Rows of zero-padding needed to split ``n`` rows evenly over the
    mesh's data axis (row-sharded uploads pad; consumers only ever
    index real rows)."""
    return (-n) % mesh.devices.size


def shard_rows(array: np.ndarray, mesh: Mesh,
               rows: Optional[int] = None) -> Any:
    """Host array -> device array with the leading (row) axis sharded
    over the data axis, zero-padded to ``rows`` total rows (default: the
    array's own length), rounded up to divide evenly.  Built per shard
    (``jax.make_array_from_callback``): each device's row block is
    sliced — and only the tail shard's pad materialized — right before
    its own H2D copy, so the full array never exists padded on host and
    never lands whole on any single device.  That bounds the transient
    host overhead at one shard instead of one pool: a 10.5 GB factor
    matrix costs ~10.5/ndev GB of working copy, not a second 10.5 GB,
    and 10.5/ndev GB per chip once resident.

    Multi-process meshes (the pod tier, DESIGN.md §15): per-process
    shard assembly via ``jax.make_array_from_process_local_data`` —
    each host slices and uploads ONLY its own contiguous row range of
    the global array, so the full pool never lands whole on any one
    host either; the assembled array is identical to the single-process
    layout shard for shard.  ``array`` may be any host sequence that
    slices to the local range (an in-memory pool, a memmap, a
    shard-serving reader) — only the local rows are ever touched."""
    faults.site("shard_upload")
    n = array.shape[0]
    total = n if rows is None else int(rows)
    if total < n:
        raise ValueError(f"rows={total} < array rows {n}")
    total += row_shard_pad(total, mesh)
    tail = array.shape[1:]

    def _block(lo: int, hi: int) -> np.ndarray:
        # Per-shard fault point: one block's H2D can fail while its
        # siblings succeed (the caller's RetryPolicy re-runs the upload).
        faults.site("shard_upload", point="torn")
        block = np.ascontiguousarray(array[lo:min(hi, n)])
        short = (hi - lo) - block.shape[0]
        if short:
            block = np.concatenate(
                [block, np.zeros((short, *tail), array.dtype)])
        return block

    if is_multiprocess(mesh):
        local = process_local_rows(mesh, total)
        return jax.make_array_from_process_local_data(
            row_sharding(mesh), _block(local.start, local.stop),
            (total, *tail))

    def _shard(index):
        rs = index[0]
        lo = rs.start or 0
        return _block(lo, total if rs.stop is None else rs.stop)

    return jax.make_array_from_callback(
        (total, *tail), row_sharding(mesh), _shard)


def owner_rows(arr: Any, idxs: Any, axis: str = DATA_AXIS) -> Any:
    """Inside a ``shard_map`` body over ``axis``: rows of the shard-local
    ``arr`` for GLOBAL row indices ``idxs`` [K], assembled from their
    owning shards by masked psum.  THE exactness-critical primitive of
    the row-sharded pool, shared by ``resident.sharded_pool_gather`` and
    the k-center collective backend's center-row gather: exactly one
    shard owns each global index, non-owners contribute exact zeros, so
    the sum is the owner's value bit for bit (uint8 included) — the
    invariant every pick/score/batch-identity test rests on.  Out-of-
    range indices (pad rows past the last shard) clip to existing rows
    but are owned by nobody, so they come back as zeros."""
    rows = arr.shape[0]
    off = (jax.lax.axis_index(axis) * rows).astype(idxs.dtype)
    loc = jnp.clip(idxs - off, 0, rows - 1)
    mine = (idxs >= off) & (idxs < off + rows)
    picked = jnp.where(mine.reshape((-1,) + (1,) * (arr.ndim - 1)),
                       arr[loc], jnp.zeros((), arr.dtype))
    return jax.lax.psum(picked, axis)


def owner_rows_scattered(arr: Any, idxs: Any, axis: str = DATA_AXIS) -> Any:
    """``owner_rows``' reduce-scatter twin: rows of the shard-local
    ``arr`` for GLOBAL row indices ``idxs`` [K] (REPLICATED — every
    shard passes the same vector), assembled from their owning shards
    and SCATTERED — shard i receives rows [i*K/ndev, (i+1)*K/ndev) of
    the result instead of the full [K].  Exact for the same reason
    owner_rows is (each element sums exactly one owner value plus
    zeros — any reduction order is the owner's bits), at 1/ndev the
    wire of the full psum broadcast.  The ring column feed seeds each
    shard's starting center block with this (strategies/kcenter.py);
    like owner_rows, this is the ONE spelling of the masked-scatter
    idiom (al_lint collective-axis).  K must divide the mesh."""
    rows = arr.shape[0]
    off = (jax.lax.axis_index(axis) * rows).astype(idxs.dtype)
    loc = jnp.clip(idxs - off, 0, rows - 1)
    mine = (idxs >= off) & (idxs < off + rows)
    picked = jnp.where(mine.reshape((-1,) + (1,) * (arr.ndim - 1)),
                       arr[loc], jnp.zeros((), arr.dtype))
    return jax.lax.psum_scatter(picked, axis, scatter_dimension=0,
                                tiled=True)


def ring_shift(tree: Any, ndev: int, axis: str = DATA_AXIS) -> Any:
    """THE ring-permute column-feed primitive — the ONE spelling of the
    ring-feed idiom (statically enforced: al_lint collective-axis allows
    a ring-perm ``ppermute`` only here).  Inside a ``shard_map`` body
    over ``axis``: rotate each shard's block to its RIGHT neighbor
    (shard i's block lands on shard (i+1) % ndev), so after ndev
    successive shifts every shard has held every other shard's block
    exactly once and the blocks are home again.  This is SNIPPETS.md
    [1]'s classic TPU ring pattern spelled with ``lax.ppermute`` (XLA
    lowers it to collective-permute on the ICI ring) instead of a
    hand-rolled Pallas DMA — same wire schedule, composes under jit and
    ``lax.fori_loop``.

    The k-center initial-min/minimax scans fold distance strips over the
    rotating blocks (strategies/kcenter.py): each hop moves one block of
    labeled-center columns between neighbors instead of uploading host
    column blocks and broadcasting them to every device — min/max folds
    over the rotating blocks are exact, so consumers stay bit-identical
    to the replicated column scans.  ``ndev`` must be the mesh's static
    device count (the permutation is a trace-time constant)."""
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]
    return jax.tree.map(
        lambda x: jax.lax.ppermute(x, axis, perm=perm), tree)


def is_row_sharded(array: Any) -> bool:
    """True when a device array's leading axis is split over a mesh axis
    (the row-sharded pool layout), read off the committed sharding —
    host-side introspection, never valid on tracers."""
    spec = getattr(getattr(array, "sharding", None), "spec", None)
    return bool(spec) and spec[0] is not None


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# -- quantized gradient sync (DESIGN.md §4 + §15, "The gradient path") ----

GRAD_ALLREDUCE_MODES = ("f32", "int8", "int8_rs", "auto")

# Elements per quantization block: one f32 scale amortized over 256
# int8 payload bytes (~1.6% scale overhead), small enough that a block
# shares one dynamic range (EQuARX's block-scaling argument: per-tensor
# scales clip outlier-heavy gradients; per-block ones track them).
INT8_BLOCK = 256

# The wire-form crossover (documented since PR 9, now acted on): the
# all_gather-shaped int8_allreduce moves (ndev-1)*n int8 bytes per
# device — a real win over the ~8n-byte f32 ring psum through 8
# devices, INVERTED past ~9.  Above this device count the int8 path
# switches to the reduce-scatter wire form (~2n bytes, ndev-free).
INT8_WIRE_CROSSOVER_NDEV = 8

INT8_WIRE_FORMS = ("allgather", "reduce_scatter")


def resolve_grad_allreduce(mode: str, mesh: Mesh) -> str:
    """The ONE rule for which gradient-sync path a Trainer builds:
    quantized sync (``int8``/``int8_rs``/``auto``) only on multi-device
    meshes (a single device has no wire to save — the quantization
    would cost accuracy for nothing); anything else is the
    partitioner's bit-exact f32 psum.  Returns "f32" or "int8" — the
    WIRE form within int8 (all-gather vs reduce-scatter) is a separate
    resolution, ``resolve_int8_wire``."""
    if mode not in GRAD_ALLREDUCE_MODES:
        raise ValueError(f"grad_allreduce={mode!r} is not one of "
                         f"{'/'.join(GRAD_ALLREDUCE_MODES)}")
    if mesh.devices.size <= 1:
        return "f32"
    if mode in ("int8", "int8_rs", "auto"):
        return "int8"
    return mode


def resolve_int8_wire(mode: str, mesh: Mesh) -> str:
    """Which WIRE the quantized gradient sync uses, from the requested
    mode + the mesh: ``int8_rs`` forces the reduce-scatter form (tests,
    A/B captures); ``int8``/``auto`` pick reduce-scatter above the
    documented ~8-device crossover and keep the proven all-gather form
    on 2-8 device meshes (where (ndev-1)*n < 8n already wins and one
    quantization round-trip beats two).  Meaningless for f32 — callers
    gate on ``resolve_grad_allreduce`` first."""
    if mode == "int8_rs":
        return "reduce_scatter"
    if mesh.devices.size > INT8_WIRE_CROSSOVER_NDEV:
        return "reduce_scatter"
    return "allgather"


def wire_model_bytes(form: str, ndev: int, n: int,
                     block: int = INT8_BLOCK) -> int:
    """Per-device wire bytes to sync one ``n``-element f32 gradient
    tree, by form — the pod-tier wire-model table (DESIGN.md §15),
    cross-checked against measured ``collective_bytes_total`` in
    tests/test_pod_tier.py:

      ``f32``            ring all-reduce: reduce-scatter + all-gather
                         passes, ~2 * 4n * (ndev-1)/ndev  (~8n);
      ``allgather``      PR 9's int8_allreduce: every device receives
                         every other device's quantized payload —
                         (ndev-1) * (n + 4n/block) int8+scale bytes,
                         LINEAR in ndev (the documented blowup);
      ``reduce_scatter`` the EQuARX-shaped form: all_to_all of the
                         quantized shards + all_gather of the
                         re-quantized reduced shards, each moving
                         (ndev-1)/ndev * (n + 4n/block) — ~2n total,
                         ndev-free.
    """
    if ndev <= 1:
        return 0
    scale_bytes = 4 * -(-n // block)
    if form == "f32":
        return int(2 * 4 * n * (ndev - 1) / ndev)
    if form == "allgather":
        return (ndev - 1) * (n + scale_bytes)
    if form == "reduce_scatter":
        return int(2 * (n + scale_bytes) * (ndev - 1) / ndev)
    raise ValueError(f"unknown wire form {form!r}")


def int8_allreduce(tree: Any, axis: str = DATA_AXIS,
                   block: int = INT8_BLOCK) -> Any:
    """EQuARX-style block-scaled int8 gradient all-reduce, inside a
    ``shard_map`` body over ``axis``: each device quantizes its local
    gradients to int8 against a SHARED per-block scale (pmax of the
    local absmax — every device must use one scale or the sums don't
    commute), the collective moves the int8 payload, and each device
    de-quantizes after a float32-accumulated local sum.

    Wire model, honestly: this is the all_gather-then-local-sum form —
    the only quantized reduction expressible in today's XLA ops (EQuARX
    itself requantizes inside a modified ring all-reduce, which is not
    user-expressible).  Per device it moves ``(ndev-1) * n`` int8 bytes
    vs a ring f32 psum's ``~2 * 4 * n``, so the wire win is
    ``8/(ndev-1)``: ~4x at 2-4 devices, still >1 through 8 (the
    single-process single-host meshes this path targets today), and
    INVERTED past ~9 devices — pod-scale needs a quantized
    reduce-scatter and is deliberately out of scope (the auto rules
    never pick int8 there: it is flag-only and the flag is default-off).

    Deterministic and bounded: with a shared scale, round-to-nearest
    per element, and an exact f32 sum of <=127-magnitude integers, the
    result is identical on every device and the per-element error is
    bounded by ``ndev * scale / 2`` with ``scale = blockmax / 127`` —
    the delta the learning probe and tests/test_backward.py pin.  A
    non-finite block (loss spike) poisons to NaN instead of quantizing
    to garbage, so blow-ups stay as visible as on the f32 path.
    Non-float leaves psum exactly.
    """
    def one(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return jax.lax.psum(x, axis)
        shape, dtype = x.shape, x.dtype
        flat = x.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        pad = (-n) % block
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        blocks = flat.reshape(-1, block)
        absmax = jax.lax.pmax(jnp.max(jnp.abs(blocks), axis=1), axis)
        scale = jnp.maximum(absmax, jnp.float32(1e-30)) / 127.0
        q = jnp.clip(jnp.round(blocks / scale[:, None]),
                     -127, 127).astype(jnp.int8)
        # int8 on the wire; the sum accumulates f32 AFTER the gather
        # (summing in int8 would wrap past ndev=2).
        gathered = jax.lax.all_gather(q, axis)
        total = jnp.sum(gathered.astype(jnp.float32), axis=0)
        # Non-finite gradients must SURFACE, exactly as the f32 psum
        # would surface them: an inf/NaN block's scale is non-finite
        # and round(x/inf)=0 would silently launder the blow-up into a
        # zero gradient — poison the whole block to NaN instead so the
        # grad-norm telemetry and any NaN guard still see it.
        out = jnp.where(jnp.isfinite(absmax)[:, None],
                        total * scale[:, None], jnp.float32(jnp.nan))
        out = out.reshape(-1)
        if pad:
            out = out[:n]
        return out.reshape(shape).astype(dtype)

    return jax.tree.map(one, tree)


def int8_reduce_scatter(tree: Any, ndev: int, axis: str = DATA_AXIS,
                        block: int = INT8_BLOCK) -> Any:
    """The pod-tier quantized gradient sync (DESIGN.md §15): EQuARX-
    shaped block-scaled int8 REDUCE-SCATTER + all-gather of the
    re-quantized reduced shards, inside a ``shard_map`` body over
    ``axis``.  Fixes ``int8_allreduce``'s documented wire blowup — that
    form moves ``(ndev-1) * n`` int8 bytes per device (every device
    receives every other device's payload), inverted vs the ~8n f32
    ring psum past ~9 devices; this one moves ``~2n`` regardless of
    ndev (``wire_model_bytes``), which is why ``resolve_int8_wire``
    auto-selects it above the crossover.

    Wire schedule, per leaf:

      1. quantize the local gradient to int8 against a SHARED per-block
         scale (pmax of the block absmax — sums must commute);
      2. ``all_to_all`` the quantized payload: each device sends shard
         j of its blocks to device j and receives ITS shard from every
         peer — ``(ndev-1)/ndev * n`` int8 bytes, the reduce-scatter
         leg (XLA exposes no requantizing reduce-scatter op; EQuARX
         requantizes inside a modified ring, which is not
         user-expressible — all_to_all + local f32 sum is the same
         bytes with the sum hoisted to the shard owner);
      3. each shard owner accumulates its slice in float32 and
         RE-QUANTIZES it against its own fresh per-block scale;
      4. ``all_gather`` the quantized reduced shards + their scales —
         ``(ndev-1)/ndev * n`` int8 bytes + the ~1.6% scale sidecar —
         and dequantize.

    Deterministic and replicated: every device dequantizes the SAME
    owner-produced bytes, and the f32 accumulation order over the
    device axis is fixed — the result is identical on every device.
    Bounded error: first quantization contributes <= ndev * scale1 / 2
    per element (scale1 = blockmax/127), the requantization another
    scale2 / 2 — one quantization round-trip more than the all-gather
    form, which is why the 2-8 device meshes keep that form and why
    BOTH sit behind the same learning probe (driver.
    run_grad_allreduce_probe probes whichever form the mesh resolves).
    Non-finite blocks poison to NaN exactly like ``int8_allreduce``.
    ``ndev`` must be the mesh's static device count."""
    def one(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return jax.lax.psum(x, axis)
        shape, dtype = x.shape, x.dtype
        flat = x.reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        pad = (-n) % (block * ndev)
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        blocks = flat.reshape(-1, block)
        nb = blocks.shape[0]
        m = nb // ndev
        absmax = jax.lax.pmax(jnp.max(jnp.abs(blocks), axis=1), axis)
        scale = jnp.maximum(absmax, jnp.float32(1e-30)) / 127.0
        q = jnp.clip(jnp.round(blocks / scale[:, None]),
                     -127, 127).astype(jnp.int8)
        # Reduce-scatter leg: int8 on the wire, each device ends up
        # holding every peer's copy of ITS m-block shard.
        recv = jax.lax.all_to_all(q.reshape(ndev, m, block), axis,
                                  split_axis=0, concat_axis=0)
        me = jax.lax.axis_index(axis)
        # The shared scale vector is replicated math, so slicing my
        # shard of it is local; the f32 sum over the device axis is the
        # exact sum of <=127-magnitude integers times one scale.
        my_scale = jax.lax.dynamic_slice_in_dim(
            scale.reshape(ndev, m), me, 1, 0)[0]
        reduced = jnp.sum(recv.astype(jnp.float32), axis=0) \
            * my_scale[:, None]
        absmax2 = jnp.max(jnp.abs(reduced), axis=1)
        scale2 = jnp.maximum(absmax2, jnp.float32(1e-30)) / 127.0
        q2 = jnp.clip(jnp.round(reduced / scale2[:, None]),
                      -127, 127).astype(jnp.int8)
        # All-gather leg: quantized reduced shards + the scale sidecar.
        gathered = jax.lax.all_gather(q2, axis)
        scales = jax.lax.all_gather(scale2, axis)
        out = gathered.astype(jnp.float32) * scales[:, :, None]
        # Same poison rule as int8_allreduce: a non-finite block must
        # SURFACE as NaN, never launder into a zero gradient.
        out = jnp.where(jnp.isfinite(absmax).reshape(ndev, m)[:, :, None],
                        out, jnp.float32(jnp.nan))
        out = out.reshape(-1)
        if pad:
            out = out[:n]
        return out.reshape(shape).astype(dtype)

    return jax.tree.map(one, tree)


def shard_batch(batch: Dict[str, np.ndarray], mesh: Mesh) -> Dict[str, Any]:
    """Host batch -> device arrays with the batch axis sharded over the
    mesh.  This is the host->device boundary (the reference's pinned-memory
    H2D copies, strategy.py:264,328).

    Single-process: ``batch`` holds the full global batch.  Multi-process:
    every process passes ONLY its ``process_local_rows`` slice and the
    global array is assembled across hosts — the data-parallel contract of
    the reference's per-rank DataLoader (strategy.py:325-328) without any
    cross-host copy of example data.
    """
    sharding = batch_sharding(mesh)
    if not is_multiprocess(mesh):
        return {k: jax.device_put(v, sharding) for k, v in batch.items()}
    n_local = mesh.local_mesh.devices.size
    scale = mesh.devices.size // n_local
    return {
        k: jax.make_array_from_process_local_data(
            sharding, np.asarray(v), (v.shape[0] * scale, *v.shape[1:]))
        for k, v in batch.items()
    }


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Copy a host pytree to every device (every process passes the same
    values — the usual multi-controller contract)."""
    sharding = replicated_sharding(mesh)
    if not is_multiprocess(mesh):
        return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    return jax.tree.map(put, tree)


def fetch(tree: Any, mesh: Optional[Mesh] = None) -> Any:
    """Device pytree -> host numpy, working for batch-sharded outputs on
    multi-host meshes too (each process sees the full global array — the
    reference's dist.all_gather of eval/score results, evaluation.py:69-98).
    Fully-replicated outputs (losses, metric counts) are fetched directly.
    """
    if mesh is None or not is_multiprocess(mesh):
        return jax.tree.map(np.asarray, tree)
    from jax.experimental import multihost_utils

    def one(x):
        if getattr(x, "is_fully_replicated", True):
            return np.asarray(x)
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    return jax.tree.map(one, tree)
