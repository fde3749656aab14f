"""The single device-executor loop behind the scoring service.

One thread owns the accelerator: it drains bucket-padded microbatches
from a thread-safe inbox, runs the jitted scoring steps over the
persistent mesh, and resolves each request entry's future on its event
loop.  Design decisions, each load-bearing:

  * **The steps ARE the offline steps.**  Prediction and acquisition
    scores come from strategies/scoring.make_prob_stats_step and
    make_embed_step — the same factories every sampler's offline pass
    uses — so a served score is bit-for-bit the offline score at the
    same batch shape (pinned in tests/test_serve.py).  No serving-only
    numerics to drift.
  * **Zero request-path compiles.**  ``warmup()`` runs every step over
    every bucket in the batcher's ladder once, before the first request
    is admitted; with the persistent XLA compilation cache enabled
    (experiment/driver.enable_compilation_cache — the serve CLI turns
    it on) those warmup compiles are disk hits after the first server
    start on a machine.  ``compile_counts()`` exposes the jit caches'
    sizes (the tests/test_compile_reuse.py counter) so /metrics can
    show that the request path never compiled.
  * **Double-buffered H2D.**  The inbox drain is wrapped in
    data/cache.device_prefetch: a feeder thread shards + dispatches the
    host->device transfer of batch n+1 while batch n computes, so
    serving throughput is bounded by max(host, PCIe, device), the same
    discipline as the offline pool scan's streaming fallback.
  * **Hot checkpoint reload between batches.**  The executor polls the
    experiment's checkpoint directory at a bounded cadence and swaps in
    a newer round's ``best_rd_{n}`` between batches — a running AL
    experiment's freshest model is served without restarting.  The
    probe is the SHARED ``train/checkpoint.BestCkptWatcher`` (the same
    helper the pipelined round's speculative scorer uses): writes are
    atomic (tmp + rename) so a reload can never observe a torn file,
    and the monotonic (round, epoch) publish tag makes two publishes
    within one mtime granule distinguishable.  Variables are replicated
    fresh and the old tree dropped; the jitted steps are
    weight-agnostic, so a reload costs no recompile.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..parallel import mesh as mesh_lib
from ..strategies import scoring
from ..telemetry import diagnostics as diag_lib
from ..train import checkpoint as ckpt_lib
from ..utils.logging import get_logger

_SHUTDOWN = object()

# Keys the prob-stats step yields that /v1/predict and /v1/score serve.
STAT_KEYS = ("pred", "confidence", "margin", "entropy")


class DeviceExecutor:
    """Owns the mesh, the variables, and the one compute thread.

    ``model``/``view`` define the scoring computation; ``variables``
    seeds the weights (host pytree — e.g. checkpoint.load_variables
    output).  ``ckpt_dir`` (optional) enables hot reload: the newest
    ``best_rd_{n}.msgpack`` under it is loaded at construction when
    ``variables`` is None, and re-polled every ``reload_every_s``
    between batches.
    """

    def __init__(
        self,
        model,
        view,
        mesh,
        image_shape: Tuple[int, int, int],
        variables: Optional[Dict[str, Any]] = None,
        ckpt_dir: Optional[str] = None,
        reload_every_s: float = 5.0,
        prefetch_depth: int = 2,
        host_s2d: bool = False,
    ):
        self.model = model
        self.view = view
        self.mesh = mesh
        # Client-facing row shape; with host_s2d the space-to-depth
        # re-layout (the s2d stem's input contract, data/pipeline.py —
        # same transform the offline scoring pipeline applies) happens
        # on the feeder thread, invisible to clients.
        self.host_s2d = bool(host_s2d)
        self.image_shape = tuple(image_shape)
        self.ckpt_dir = ckpt_dir
        self.reload_every_s = float(reload_every_s)
        self.prefetch_depth = int(prefetch_depth)
        self.logger = get_logger()

        self.served_round = -1
        self._watcher = (ckpt_lib.BestCkptWatcher(ckpt_dir)
                         if ckpt_dir is not None else None)
        if variables is None:
            if ckpt_dir is None:
                raise ValueError("need variables or ckpt_dir")
            variables = self._load_latest(required=True)
        self._variables = mesh_lib.replicate(variables, mesh)

        # The offline factories — served outputs match offline scores
        # bit-for-bit at the same batch shape.
        self._steps: Dict[str, Callable] = {
            "prob_stats": scoring.make_prob_stats_step(model, view),
            "embed": scoring.make_embed_step(model, view, with_probs=True),
        }
        self._inq: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._last_reload_check = 0.0
        self._lock = threading.Lock()
        self.stats = {"batches": 0, "rows": 0, "reloads": 0,
                      "warm_buckets": []}
        self._compile_baseline: Optional[Dict[str, int]] = None
        # Online score drift (telemetry/diagnostics.ServeScoreDrift,
        # DESIGN.md §13): every served batch's margin folds into a live
        # histogram; a hot reload snapshots it as the checkpoint-time
        # baseline, and /metrics serves the live-vs-baseline PSI/JS —
        # the per-model drift signal the streaming-AL loop (ROADMAP
        # item 3) consumes.  Host-pure numpy over arrays the request
        # path already fetched; its own lock (observe on this thread,
        # snapshot on the server thread).
        self.score_drift = diag_lib.ServeScoreDrift(key="margin")

    # -- checkpoint (re)loading ------------------------------------------

    def _load_latest(self, required: bool = False):
        polled = self._watcher.poll()
        if polled is None and required and self.served_round < 0:
            # The watcher also reports None for TRANSIENT conditions (a
            # writer raced between its weight and tag renames, a file
            # rotating away mid-read).  At startup, only "nothing on
            # disk" is fatal; a present-but-racing checkpoint settles
            # within a publish, so retry briefly before giving up.
            path, _ = ckpt_lib.latest_best_ckpt(self.ckpt_dir)
            if path is None:
                raise FileNotFoundError(
                    f"no best_rd_*.msgpack under {self.ckpt_dir}")
            for _ in range(50):
                time.sleep(0.1)
                polled = self._watcher.poll()
                if polled is not None:
                    break
            else:
                raise RuntimeError(
                    f"best checkpoint under {self.ckpt_dir} never "
                    "settled (weights/tag publish kept racing)")
        if polled is None:
            return None
        variables, rd, tag = polled
        self.served_round = rd
        self.logger.info(
            f"serve: loaded best checkpoint of round {rd}"
            + (f" (best epoch {tag[1]})" if tag else ""))
        return variables

    def maybe_reload(self, now: Optional[float] = None) -> bool:
        """Between-batches hot reload: bounded-cadence poll for a newer
        best checkpoint; swap variables if one appeared.  Runs on the
        executor thread; safe to call from tests directly."""
        if self.ckpt_dir is None:
            return False
        now = time.monotonic() if now is None else now
        if now - self._last_reload_check < self.reload_every_s:
            return False
        self._last_reload_check = now
        prev_round = self.served_round
        variables = self._load_latest()
        if variables is None:
            return False
        self._variables = mesh_lib.replicate(variables, self.mesh)
        # What the OUTGOING checkpoint served becomes the drift
        # baseline; the new model's scores accumulate against it.
        self.score_drift.rebaseline(prev_round)
        with self._lock:
            self.stats["reloads"] += 1
        return True

    # -- warmup / compile accounting -------------------------------------

    def warmup(self, buckets: Sequence[int]) -> None:
        """Compile every (step, bucket) pair the request path can reach,
        then snapshot the jit-cache sizes as the zero-request-path-
        compiles baseline.  With the persistent compilation cache on,
        repeat server starts pay disk hits here, not compiles."""
        h, w, c = self.image_shape
        for b in sorted(set(int(x) for x in buckets)):
            host = {"image": np.zeros((b, h, w, c), dtype=np.uint8),
                    "mask": np.ones(b, dtype=np.float32)}
            if self.host_s2d:
                from ..data.pipeline import space_to_depth
                host = dict(host, image=space_to_depth(host["image"]))
            dev = mesh_lib.shard_batch(host, self.mesh)
            for step in self._steps.values():
                out = step(self._variables, dev)
                # Force completion so warmup compile time never leaks
                # into the first request's latency.
                for v in out.values():
                    np.asarray(v)
            with self._lock:
                self.stats["warm_buckets"].append(b)
        self._compile_baseline = self.compile_counts()

    def compile_counts(self) -> Dict[str, int]:
        """Live jit-cache entry counts per step — the compile counter of
        tests/test_compile_reuse.py, servable via /metrics."""
        return {name: int(step._cache_size())
                for name, step in self._steps.items()}

    def request_path_compiles(self) -> int:
        """Compiles since warmup(); 0 is the contract."""
        if self._compile_baseline is None:
            return -1
        counts = self.compile_counts()
        return sum(counts[k] - self._compile_baseline.get(k, 0)
                   for k in counts)

    # -- the device loop --------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run,
                                        name="al-serve-executor",
                                        daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 30.0) -> None:
        """Process everything queued, then stop the thread.  FIFO: the
        shutdown sentinel queues behind in-flight batches, so stop()
        after batcher.drain() completes every admitted request."""
        if self._thread is None:
            return
        self._inq.put(_SHUTDOWN)
        self._thread.join(timeout=timeout)
        self._thread = None

    def submit_batch(self, host_batch: Dict[str, np.ndarray],
                     entries: List, want_embed: bool) -> None:
        """Batcher dispatch target (thread-safe, non-blocking)."""
        self._inq.put((host_batch, entries, want_embed))

    def _put(self, item):
        """Feeder-thread H2D shard.  MUST NOT raise: device_prefetch
        re-raises feeder exceptions at the consuming ``for``, OUTSIDE
        the per-batch try below — one transient device_put failure
        (e.g. HBM pressure beside a live training run) would kill the
        executor thread and leave every queued future hanging.  Errors
        ride along as a marker instead and fail only their own batch."""
        host_batch, entries, want_embed = item
        try:
            if self.host_s2d:
                from ..data.pipeline import space_to_depth
                host_batch = dict(host_batch,
                                  image=space_to_depth(host_batch["image"]))
            dev = mesh_lib.shard_batch(host_batch, self.mesh)
            return (dev, entries, want_embed, None)
        except Exception as exc:  # noqa: BLE001 - per-batch isolation
            return (None, entries, want_embed, exc)

    def _run(self) -> None:
        from ..data.cache import device_prefetch

        def host_items():
            while True:
                item = self._inq.get()
                if item is _SHUTDOWN:
                    return
                yield item

        # The h2d dispatch of batch n+1 overlaps batch n's compute —
        # the same double-buffering as the offline streaming fallback.
        for dev_batch, entries, want_embed, put_exc in device_prefetch(
                host_items(), self._put, depth=self.prefetch_depth):
            if put_exc is not None:
                self.logger.error(f"serve: h2d shard failed: {put_exc!r}")
                for e in entries:
                    _reject(e.future, put_exc)
                continue
            try:
                self.maybe_reload()
                out = self._steps["prob_stats"](self._variables, dev_batch)
                host = {k: np.asarray(out[k]) for k in STAT_KEYS}
                if want_embed:
                    emb = self._steps["embed"](self._variables, dev_batch)
                    host["embedding"] = np.asarray(emb["embedding"])
                with self._lock:
                    self.stats["batches"] += 1
                    self.stats["rows"] += sum(e.n for e in entries)
                for e in entries:
                    sl = slice(e.offset, e.offset + e.n)
                    # Real rows only (the bucket's padding tail would
                    # poison the distribution); the margin array is
                    # already on host for the response.
                    self.score_drift.observe(host["margin"][sl])
                    payload = {k: v[sl] for k, v in host.items()
                               if k != "embedding" or e.want_embed}
                    payload["round"] = self.served_round
                    _resolve(e.future, payload)
            except Exception as exc:  # noqa: BLE001 - per-batch isolation
                self.logger.exception("serve: batch failed")
                for e in entries:
                    _reject(e.future, exc)


def _resolve(future, payload) -> None:
    loop = future.get_loop()
    loop.call_soon_threadsafe(
        lambda: future.set_result(payload) if not future.done() else None)


def _reject(future, exc: Exception) -> None:
    loop = future.get_loop()
    loop.call_soon_threadsafe(
        lambda: future.set_exception(exc) if not future.done() else None)
