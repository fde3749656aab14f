"""Decode-once caches for deterministic dataset views.

Two tiers, both exact because the al/val/test views are deterministic —
``gather(i)`` is time-invariant (data/imagenet.py val transform,
independent of ``set_epoch``):

  * ``CachedEvalRows`` — RAM, per-round: the per-epoch validation loop
    re-reads the SAME eval rows every epoch (reference: a fresh
    DataLoader pass over the val subset per epoch, strategy.py:383-398);
    decode each once per round instead.
  * ``DecodedPoolCache`` — disk memmap, per-EXPERIMENT: acquisition
    scoring re-reads the WHOLE unlabeled pool every round, and on
    ImageNet-scale trees the JPEG decode is ~30x slower than the
    device's scoring rate (a capture from before the ledger: 1,048
    img/s/core decode vs 9,742 img/s/chip scoring, h2d ceiling 3,133
    img/s).  Each row is decoded
    exactly once for the life of the cache file and every later round
    (and validation, and the test set) streams uint8 rows at disk/page-
    cache speed.  The reference re-decodes per epoch via DataLoader
    workers (src/query_strategies/strategy.py:325-328).

Memory/disk bounding: CachedEvalRows admits rows until ``max_bytes`` of
RAM; DecodedPoolCache refuses to build at all (factory returns the
dataset unwrapped) when the FULL pool would exceed its byte budget —
the scoring pass touches every row, so a partial disk cache would still
thrash.  Admitted RAM rows are COPIES, never views into a gathered batch
— a view would pin the whole batch while the byte accounting counted one
row.  Thread-safe: the pipelines gather batches from ``num_workers``
threads concurrently (data/pipeline.py); RAM-cache bookkeeping is under
a lock, and the memmap tier writes disjoint rows (row data first, THEN
the valid flag, so a crash mid-write re-decodes instead of serving a
torn row).  On a multi-host mesh each process caches its own rows in its
own file (no cross-process file locking needed).
"""

from __future__ import annotations

import glob
import hashlib
import json
import mmap
import os
import threading
from typing import Dict, Optional

import numpy as np

from .core import Dataset
from ..utils.logging import get_logger


def _msync_range(arr: np.ndarray, lo_byte: int, hi_byte: int) -> bool:
    """msync only the pages covering bytes [lo_byte, hi_byte) of a
    memmap-backed array; returns False when the mmap backing cannot be
    found (the caller falls back to a full flush).  numpy's
    ``memmap.flush`` has no range form, so a per-batch whole-mapping
    flush would sweep the entire multi-GB mapping from every writer."""
    mm = arr
    while mm is not None and not isinstance(mm, mmap.mmap):
        mm = getattr(mm, "base", None)
    if mm is None:
        return False
    gran = mmap.ALLOCATIONGRANULARITY
    start = lo_byte // gran * gran
    end = min(len(mm), -(-hi_byte // gran) * gran)
    if end > start:
        mm.flush(start, end - start)
    return True


class CachedEvalRows:
    """Wrap a dataset whose active view is deterministic; same gather
    contract, rows served from memory after first decode.

    Only sound for augmentation-free views — wrapping a train view would
    freeze the first epoch's crops forever, so callers gate on the view.
    """

    def __init__(self, dataset: Dataset, max_bytes: int = 4 << 30):
        self.dataset = dataset
        self.view = dataset.view
        self.targets = dataset.targets
        self.num_classes = dataset.num_classes
        # Proxied so Trainer.eval_batch_size sees the row size through the
        # wrapper — the scoring and validation passes share one batch-floor
        # policy, and a wrapper hiding image_shape would silently drop the
        # eval pass to the conservative unknown-shape floor.
        self.image_shape = dataset.image_shape
        self._rows: Dict[int, np.ndarray] = {}
        self._bytes = 0
        self._max_bytes = int(max_bytes)
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.dataset)

    def gather(self, idxs: np.ndarray) -> np.ndarray:
        idxs = np.asarray(idxs)
        if len(idxs) == 0:
            # Preserve the wrapped dataset's empty-gather shape contract
            # (a multi-host last batch can leave a process zero real rows).
            return self.dataset.gather(idxs)
        with self._lock:
            missing = sorted({int(i) for i in idxs} - self._rows.keys())
        fetched: Dict[int, np.ndarray] = {}
        if missing:
            rows = self.dataset.gather(np.asarray(missing, dtype=np.int64))
            with self._lock:
                for i, row in zip(missing, rows):
                    fetched[i] = row
                    if (i not in self._rows
                            and self._bytes + row.nbytes <= self._max_bytes):
                        self._rows[i] = row.copy()
                        self._bytes += row.nbytes
        out = []
        with self._lock:
            for j in idxs:
                i = int(j)
                row = self._rows.get(i)
                out.append(row if row is not None else fetched[i])
        return np.stack(out)


class DecodedPoolCache:
    """Disk-memmap decode-once cache over a deterministic-view disk
    dataset: uint8 [N, H, W, C] rows written on first gather, valid flags
    set AFTER the row bytes (torn writes re-decode, never serve).  The
    backing file is sparse — disk usage grows with rows actually decoded.

    Persistent across processes and experiments: the file name carries a
    fingerprint of (paths, image/resize size, row shape), so a changed
    tree or transform gets a fresh cache instead of stale rows.  Build
    via ``maybe_wrap_decoded`` (returns the dataset unwrapped when
    ineligible).  Attribute access falls through to the wrapped dataset
    (``paths``, ``targets``, ``image_shape``, ...), so downstream gates
    like the trainer's eval-cache check keep working.
    """

    # Basenames of caches live in THIS process (the al pool and the test
    # set legitimately share a directory): eviction must never take them.
    _IN_USE: set = set()

    def __init__(self, dataset, cache_dir: str,
                 signature: Optional[str] = None):
        self.dataset = dataset
        n = len(dataset)
        shape = (n, *dataset.image_shape)
        os.makedirs(cache_dir, exist_ok=True)
        # The signature stats every image file; callers that already
        # computed it (maybe_wrap_decoded's eviction pass) hand it in so
        # an ImageNet-scale tree pays the ~1.3M-stat sweep once, not
        # twice.
        sig = signature or self._signature(dataset)
        # Per-process files on pods: each process gathers only its own
        # rows; sharing one file over NFS would need row-range locking.
        proc = 0
        try:
            import jax
            proc = jax.process_index()
        except Exception:
            pass
        base = os.path.join(cache_dir, f"decoded_{sig}_p{proc}")
        self._data_path = base + ".u8"
        self._valid_path = base + ".valid"
        meta_path = base + ".json"
        fresh = not (os.path.exists(self._data_path)
                     and os.path.exists(self._valid_path)
                     and os.path.exists(meta_path))
        if fresh:
            # Sparse-create both files, meta last (its presence marks the
            # pair usable).
            for path, nbytes in ((self._data_path, int(np.prod(shape))),
                                 (self._valid_path, n)):
                with open(path + ".tmp", "wb") as fh:
                    fh.truncate(nbytes)
                os.replace(path + ".tmp", path)
            with open(meta_path + ".tmp", "w") as fh:
                json.dump({"shape": shape, "signature": sig}, fh)
            os.replace(meta_path + ".tmp", meta_path)
        DecodedPoolCache._IN_USE.add(base)
        self._rows = np.memmap(self._data_path, dtype=np.uint8, mode="r+",
                               shape=shape)
        self._valid = np.memmap(self._valid_path, dtype=np.uint8, mode="r+",
                                shape=(n,))
        have = int(np.count_nonzero(self._valid))
        get_logger().info(
            f"Decoded-pool cache at {base}.u8: {have}/{n} rows present "
            f"({'resumed' if not fresh else 'new'}, "
            f"{np.prod(shape) / 1e9:.1f} GB full size, sparse)")

    @staticmethod
    def _signature(dataset) -> str:
        h = hashlib.sha1()
        h.update(str(getattr(dataset, "image_size", "")).encode())
        h.update(str(getattr(dataset, "resize_size", "")).encode())
        h.update(str(len(dataset)).encode())
        for p in dataset.paths[: len(dataset)]:
            h.update(p.encode())
            # Size+mtime per file: images re-encoded IN PLACE at the same
            # paths must produce a fresh cache, not stale pixels.  One
            # stat per file costs seconds even at ImageNet scale, paid
            # once per cache construction.
            try:
                st = os.stat(p)
                h.update(f"|{st.st_size}|{st.st_mtime_ns}".encode())
            except OSError:
                h.update(b"|missing")
        return h.hexdigest()[:16]

    def __len__(self) -> int:
        return len(self.dataset)

    @property
    def images(self):
        """The decoded pool as one uint8 array — exposed ONLY once every
        row is decoded.  A fully-populated cache thereby becomes eligible
        for the device-resident paths (parallel/resident.py:eligible):
        when ``resident_scoring_bytes`` covers the pool, rounds 1+ score
        via on-device gathers instead of host->device streaming.  While
        partial, AttributeError (falling through to the wrapped dataset,
        which has no ``images``): a half-empty memmap must never be
        uploaded as real data."""
        if int(np.count_nonzero(self._valid)) != len(self.dataset):
            raise AttributeError("decoded pool not fully populated")
        return self._rows

    def __getattr__(self, name):
        # Only called for attributes NOT set on self: view/targets/paths/
        # image_shape/num_classes/train_transform all resolve through the
        # wrapped dataset, staying live if it mutates.
        if name == "dataset":  # unpickling guard: no silent recursion
            raise AttributeError(name)
        return getattr(self.dataset, name)

    def gather(self, idxs: np.ndarray) -> np.ndarray:
        idxs = np.asarray(idxs, dtype=np.int64)
        if len(idxs) == 0:
            return self.dataset.gather(idxs)
        valid = self._valid[idxs] != 0
        if not valid.all():
            missing = np.unique(idxs[~valid])
            rows = self.dataset.gather(missing)
            self._rows[missing] = rows
            # Row bytes DURABLY first (msync — paid only on the
            # populating pass, where JPEG decode dominates), THEN the
            # flags: without the flush the kernel may persist a flag page
            # before its row page, and a system crash would leave valid=1
            # over zero bytes — served as a real image for the rest of
            # the cache's life.  With it, a crash at any point costs a
            # re-decode, never a torn row.
            self._flush_row_range(int(missing[0]), int(missing[-1]) + 1)
            self._valid[missing] = 1
        return np.asarray(self._rows[idxs])

    def _flush_row_range(self, lo: int, hi: int) -> None:
        """msync only the pages covering rows [lo, hi): the populating
        pass writes contiguous batches, and a whole-mapping flush per
        batch would sweep the entire multi-GB mapping from every
        pipeline thread (see ``_msync_range``)."""
        row_bytes = int(self._rows.strides[0])
        if not _msync_range(self._rows, lo * row_bytes, hi * row_bytes):
            self._rows.flush()  # unexpected backing; full msync

    def flush(self) -> None:
        self._rows.flush()
        self._valid.flush()


class GrowableRowStore:
    """A row array in one disk file whose capacity grows by
    ``pool.bucket_size``-aligned extents — the backing tier of the
    streaming subsystem's growable candidate pool
    (active_learning_tpu/stream/store.py).

    Why extent-aligned: everything downstream that compiles against the
    array's LEADING dimension (the resident-pool upload and its jitted
    gather runners, parallel/resident.py) sees only capacities from the
    same enumerable shape ladder the trainer and k-center already bucket
    on — so a pool that grows row by row recompiles at most once per
    bucket boundary, never once per append (pinned in
    tests/test_compile_reuse.py).

    Durability model: this file is DERIVED state by default.  The
    streaming subsystem's source of truth is the fsync'd ingest WAL
    (stream/wal.py); the store is rebuilt from base data + WAL replay at
    every service start, so the store itself needs no write atomicity —
    creation is still tmp+rename (a half-created file never masquerades
    as a store) and growth is a plain ftruncate, which keeps every
    EXISTING mapping valid (mappings cover the old length; only new
    pages appear).  ``rows`` is re-mapped only when capacity grows, so
    ``id(store.rows)`` is stable within a capacity epoch — exactly the
    identity the resident cache keys on.

    ``reuse=True`` opts a caller into keeping an existing file instead:
    the WAL-compaction path (stream/store.py) promotes the store to a
    sealed disk extent whose prefix IS durable truth (rows a pruned WAL
    segment can no longer rebuild).  The file is kept only when its
    size is a whole number of rows covering the requested capacity —
    any such size was produced by this class's own bucketed ftruncates,
    so the capacity stays on the bucket ladder; anything else falls back
    to the fresh-create path (``reused`` tells the caller which
    happened, i.e. whether the prefix contents can be trusted).
    """

    def __init__(self, path: str, row_shape, dtype=np.uint8,
                 capacity: int = 0, extent_floor: int = 256,
                 reuse: bool = False):
        from ..pool import bucket_size

        self._bucket = lambda n: bucket_size(max(int(n), 1),
                                             floor=int(extent_floor))
        self.path = path
        self.row_shape = tuple(int(d) for d in row_shape)
        self.dtype = np.dtype(dtype)
        self._row_bytes = int(np.prod(self.row_shape, dtype=np.int64)
                              or 1) * self.dtype.itemsize
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self.capacity = self._bucket(capacity)
        # Written-interval tracking: flush() syncs only the dirty byte
        # range (satellite of the §16 disk tier — the whole-file
        # memmap.flush here used to sweep the full multi-GB mapping on
        # every seal).
        self._dirty: Optional[tuple] = None
        self.reused = False
        if reuse and os.path.exists(path):
            size = os.path.getsize(path)
            if (size >= self.capacity * self._row_bytes
                    and size % self._row_bytes == 0):
                self.capacity = size // self._row_bytes
                self.reused = True
        if not self.reused:
            # Fresh every construction: the store is derived (see
            # docstring), and reusing a stale file would let a crashed
            # run's rows shadow the WAL replay about to rebuild them.
            with open(path + ".tmp", "wb") as fh:
                fh.truncate(self.capacity * self._row_bytes)
            os.replace(path + ".tmp", path)
        self.rows = self._map()

    def _map(self) -> np.ndarray:
        return np.memmap(self.path, dtype=self.dtype, mode="r+",
                         shape=(self.capacity, *self.row_shape))

    def ensure_capacity(self, n_rows: int) -> bool:
        """Grow (sparse ftruncate) to the bucket enclosing ``n_rows``;
        returns True when capacity actually changed (the caller's cue to
        refresh snapshots / re-pin resident uploads)."""
        want = self._bucket(n_rows)
        if want <= self.capacity:
            return False
        os.truncate(self.path, want * self._row_bytes)
        self.capacity = want
        self.rows = self._map()
        return True

    def note_written(self, lo: int, hi: int) -> None:
        """Record rows [lo, hi) as written since the last flush; the
        next ``flush`` syncs only the union of noted intervals."""
        if hi <= lo:
            return
        if self._dirty is None:
            self._dirty = (int(lo), int(hi))
        else:
            self._dirty = (min(self._dirty[0], int(lo)),
                           max(self._dirty[1], int(hi)))

    def flush(self) -> None:
        """Sync the written row range to disk — a no-op when nothing
        was written since the last flush, and never a whole-file sweep
        for a small append (the data/cache.py flush-granularity fix)."""
        if self._dirty is None:
            return
        lo, hi = self._dirty
        hi = min(hi, self.capacity)
        if hi > lo and not _msync_range(self.rows, lo * self._row_bytes,
                                        hi * self._row_bytes):
            self.rows.flush()  # unexpected backing; full msync
        self._dirty = None


def device_prefetch(batches, put, depth: int = 2):
    """Async double-buffered host->device feed: a background thread pulls
    host batches from ``batches`` and calls ``put`` (e.g.
    mesh.shard_batch — jax device transfers are async-dispatch, so the
    h2d of batch n+1 is in flight while batch n computes), yielding
    device batches IN ORDER from a queue bounded at ``depth``.

    This is the residency fallback for pools too big for HBM
    (strategies/scoring.collect_pool): without it the host path serializes
    gather -> transfer -> dispatch per batch, so query time is the SUM of
    host and device time; with it the pass is bounded by max(host feed,
    PCIe, device).  ``depth`` bounds in-flight device batches so the
    prefetcher can never race a whole pool into HBM.  Errors from the
    feeder thread re-raise at the consuming ``next()``; an abandoned
    generator unblocks and joins the thread on close().
    """
    import queue
    import threading

    from .. import faults

    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    DONE, ERROR = object(), object()

    def feed():
        try:
            for batch in batches:
                # Fault point for the feeder thread (raise AND thread
                # death land here): either way the BaseException forward
                # below delivers it to the consuming ``next()``, which
                # fails the PASS, never hangs it — callers retry the
                # whole pass (Strategy.collect_scores) or ride the
                # driver's round-retry ladder (the train feed).
                faults.site("feed_worker")
                item = put(batch)
                while not stop.is_set():
                    try:
                        q.put((None, item), timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
            q.put((DONE, None))
        except BaseException as e:  # noqa: BLE001 - re-raised at consumer
            q.put((ERROR, e))

    t = threading.Thread(target=feed, name="al-device-prefetch",
                         daemon=True)
    t.start()
    try:
        while True:
            tag, item = q.get()
            if tag is DONE:
                return
            if tag is ERROR:
                raise item
            yield item
    finally:
        stop.set()
        while True:  # drain so the feeder's put() can't deadlock join
            try:
                q.get_nowait()
            except Exception:
                break
        t.join(timeout=5.0)


def maybe_wrap_decoded(dataset, cache_dir: Optional[str],
                       max_bytes: int) -> "Dataset":
    """Wrap ``dataset`` in a DecodedPoolCache when it is a disk-backed
    deterministic view whose FULL decoded pool fits ``max_bytes`` (the
    scoring pass touches every row, so a partial cache would thrash);
    otherwise return it unchanged.  Never raises: cache construction
    failures (unwritable dir, full disk) log and fall through."""
    if not cache_dir or max_bytes <= 0:
        return dataset
    if not hasattr(dataset, "paths") or getattr(dataset, "train_transform",
                                                False):
        return dataset
    full = len(dataset) * int(np.prod(dataset.image_shape))
    if full > max_bytes:
        get_logger().info(
            f"Decoded-pool cache disabled: full pool is {full / 1e9:.1f} GB "
            f"> budget {max_bytes / 1e9:.1f} GB")
        return dataset
    try:
        sig = DecodedPoolCache._signature(dataset)
        _evict_stale_caches(cache_dir, full, max_bytes, keep_sig=sig)
        return DecodedPoolCache(dataset, cache_dir, signature=sig)
    except OSError as e:
        get_logger().warning(f"Decoded-pool cache unavailable ({e!r}); "
                             "continuing undecached")
        return dataset


def _evict_stale_caches(cache_dir: str, need_bytes: int, max_bytes: int,
                        keep_sig: str) -> None:
    """Old cache triples (from re-encoded trees, other datasets, dead
    experiments) would otherwise accumulate in the shared persistent dir
    forever; before building a new cache, delete the least-recently-used
    ones until existing + need fits the byte budget.  Allocated (sparse)
    sizes are what count; in-process caches and the current signature's
    files are never taken."""
    groups: Dict[str, list] = {}
    for path in glob.glob(os.path.join(cache_dir, "decoded_*")):
        base = path.rsplit(".", 1)[0]
        groups.setdefault(base, []).append(path)
    entries = []
    total = 0
    for base, paths in groups.items():
        if keep_sig in os.path.basename(base) \
                or base in DecodedPoolCache._IN_USE:
            continue
        try:
            stats = [os.stat(p) for p in paths]
        except OSError:
            continue
        alloc = sum(s.st_blocks * 512 for s in stats)
        entries.append((max(s.st_mtime for s in stats), alloc, paths))
        total += alloc
    entries.sort()  # oldest first
    for mtime, alloc, paths in entries:
        if total + need_bytes <= max_bytes:
            break
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass
        total -= alloc
        get_logger().info(
            f"Evicted stale decoded cache {paths[0].rsplit('.', 1)[0]} "
            f"({alloc / 1e9:.1f} GB)")
