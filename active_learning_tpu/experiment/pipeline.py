"""The pipelined AL round coordinator: speculative scoring + select-time
train prefetch (DESIGN.md §8).

The sequential round loop (experiment/driver.py) runs query -> train ->
test strictly one after another while most of the mesh idles inside each
phase's host segments.  But the next query's pool scores depend ONLY on
the round's frozen best checkpoint — which `Strategy.train` knows long
before the fit ends (the early-stop patience tail trains past the best
epoch by construction) — so the Podracer decoupling (PAPERS.md) applies:

  * **Speculative scoring** — a host-side scoring executor starts
    scoring pool chunks as soon as a new best checkpoint publishes
    during the fit (the in-process leg of the best-ckpt bus:
    Trainer.fit's ``on_best`` callback; the disk leg reuses the serve
    executor's hot-reload pattern via train/checkpoint.BestCkptWatcher)
    and restarts from scratch when a later epoch improves best.  Chunk
    dispatches interleave with train steps under ONE shared enqueue
    lock (Trainer.dispatch_lock) so the two streams share the mesh
    without per-device reordering of collectives.
  * **Correctness contract** — the pipelined round's picks are
    BIT-identical to the sequential loop at the same seeds (pinned in
    tests/test_pipeline.py): speculation consumes NO rng (plans come
    from rng-free pool views), chunk slices splice bit-identically to
    the monolithic pass (scoring.chunk_row_slices), and ``consume``
    serves a chunk only when its source tag equals the FINAL
    (round, best_epoch) — anything else is recomputed inline with the
    query-time weights, so a speculative miss costs wall-clock, never a
    score.
  * **Select-time train prefetch** — the moment scores are handed to
    the sampler, a prefetch thread pre-resolves the coming fit's feed
    and warms what it will touch (Trainer.prepare_next_fit), so `fit`
    starts with zero feed stall at step 0 while k-center/BADGE runs its
    collective scans.

The coordinator functions listed in PIPELINE_COORDINATOR_FNS are
statically forbidden from ``block_until_ready``/``device_get``
(scripts/trace_lint.py check 7): the overlap must never sync the train
stream's arrays — the scorer may wait on its OWN chunk outputs (that
blocks only its thread), but a coordinator-level device sync would
serialize the very streams this module exists to overlap.

Off on multi-process meshes by design: every process of a pod must
enqueue the same collectives in the same order, and a per-process
scorer thread cannot guarantee cross-process interleaving — the same
gate row sharding uses (parallel/resident.resolve_sharding).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import faults
from ..strategies import scoring
from ..telemetry import runtime as tele_runtime
from ..telemetry import spans as tele_spans
from ..telemetry import diagnostics as diag_lib
from ..train import checkpoint as ckpt_lib
from ..utils.logging import get_logger

# Batches per speculative chunk — the scorer's dispatch/restart
# granularity: small enough that a late best-ckpt improvement wastes at
# most one chunk of in-flight compute and that chunk dispatches
# interleave train steps at a fine grain, large enough that the
# per-chunk host fetch amortizes.
SPEC_CHUNK_BATCHES = 8

# Disk-poll cadence while no in-process publish has arrived (the
# BestCkptWatcher leg of the bus — e.g. a Strategy.train override that
# never wires on_best).
WATCH_POLL_S = 2.0

# Mirrored by scripts/trace_lint.py check 7 (the lint works without
# importing jax): the coordinator tier of the speculative scorer.  Each
# must exist, and none may call block_until_ready/device_get.
PIPELINE_COORDINATOR_FNS = ("_worker", "_worker_loop", "_score_slice",
                            "_score_chunk", "publish_best", "finalize",
                            "consume")

# Lock discipline, statically enforced (scripts/al_lint.py
# lock-discipline): the plan/bus state below is shared between the
# driver thread (arm/consume/disarm), the trainer thread (publish_best/
# finalize), and the scorer thread (_worker_loop) — every access goes
# through the one condition's lock.  _next_job_locked is the declared
# under-the-lock helper (the *_locked suffix convention).
_GUARDED_BY = {"_plan": "_cv", "_done": "_cv", "_src": "_cv",
               "_final_tag": "_cv", "_consumed": "_cv",
               "_in_flight": "_cv", "_busy_s": "_cv", "_stop": "_cv",
               "stats": "_cv"}


def resolve_round_pipeline(spec: Optional[str], mesh) -> str:
    """The --round_pipeline auto rule: "speculative" on any
    single-process multi-device mesh, "off" on single-device meshes
    (nothing to share) and on pods (per-process scorer threads cannot
    guarantee one cross-process collective order)."""
    spec = spec or "auto"
    if spec not in ("auto", "off", "speculative"):
        raise ValueError(
            f"round_pipeline={spec!r} is not one of 'auto'/'off'/"
            "'speculative'")
    if spec != "auto":
        return spec
    from ..parallel import mesh as mesh_lib
    if mesh.devices.size > 1 and not mesh_lib.is_multiprocess(mesh):
        return "speculative"
    return "off"


class RoundPipeline:
    """One experiment's pipelined-round coordinator: owns the scorer
    thread, the per-round speculative plan, and the select-time
    prefetch thread.  The driver arms it before each fit, the trainer
    publishes best checkpoints into it, and ``Strategy.collect_scores``
    consumes it at the next query."""

    mode = "speculative"

    def __init__(self, strategy):
        self._strategy = strategy
        self._cv = threading.Condition()
        self._plan: Optional[Dict[str, Any]] = None
        self._done: Dict[int, Tuple[Tuple[int, int], Dict]] = {}
        self._src: Optional[Tuple[Tuple[int, int], Any]] = None
        self._final_tag: Optional[Tuple[int, int]] = None
        self._consumed = True
        self._in_flight: Optional[int] = None
        self._stop = False
        self._busy_s = 0.0
        self._thread: Optional[threading.Thread] = None
        self._prefetch_thread: Optional[threading.Thread] = None
        self._watcher: Optional[ckpt_lib.BestCkptWatcher] = None
        self._last_poll = 0.0
        self.logger = get_logger()
        # Cumulative evidence counters; last_consume summarizes the most
        # recent hand-over for the driver's round metrics.
        self.stats = {"publishes": 0, "chunks_scored": 0,
                      "chunks_invalidated": 0, "chunks_inline": 0,
                      "chunks_hit": 0, "plan_misses": 0,
                      # Chunk executions lost to an exception (the
                      # best-effort contract: speculation dies for the
                      # round, the query recomputes sequentially) —
                      # observable so tests can tell an environmental
                      # failure from a correctness bug.
                      "chunks_failed": 0}
        self.last_consume: Dict[str, Any] = {}

    # -- round lifecycle (driver-facing) ----------------------------------

    def arm(self, round_idx: int) -> bool:
        """Install the speculative plan for round ``round_idx + 1``'s
        query — called by the driver right before ``Strategy.train``.
        The plan is rng-FREE by contract (Strategy.speculative_
        scoring_plan); a sampler whose scoring pass depends on rng state
        returns None and the round runs un-speculated.  Returns whether
        a plan was armed."""
        strategy = self._strategy
        self._join_prefetch()
        with self._cv:
            self._plan, self._done, self._src = None, {}, None
            self._final_tag, self._consumed = None, True
            self._in_flight = None
        try:
            plan0 = strategy.speculative_scoring_plan()
        except Exception:  # noqa: BLE001 - speculation must never kill a run
            self.logger.exception("round pipeline: speculative plan failed; "
                                  "round runs sequential")
            return False
        if not plan0:
            return False
        idxs = np.asarray(plan0["idxs"])
        if idxs.size == 0:
            return False
        batch_size = strategy._score_batch_size()
        # Built on THIS thread so the lazy per-strategy step dict never
        # mutates concurrently.
        step_fn = strategy._get_score_step(plan0["kind"])
        loader = strategy.train_cfg.loader_te
        plan = {
            "round": int(round_idx),
            "kind": plan0["kind"],
            # None = every step output (MASE reads all three); collect_
            # pool treats None the same way, so plan and pass agree.
            "keys": (tuple(plan0["keys"])
                     if plan0.get("keys") is not None else None),
            "idxs": idxs,
            "batch_size": int(batch_size),
            "slices": scoring.chunk_row_slices(len(idxs), batch_size,
                                               SPEC_CHUNK_BATCHES),
            "dataset": strategy.al_set,
            "mesh": strategy.mesh,
            "step_fn": step_fn,
            "num_workers": loader.num_workers,
            "prefetch": loader.prefetch,
        }
        self._watcher = ckpt_lib.BestCkptWatcher(
            strategy.weight_paths()["dir"])
        # The newest file on disk right now is a PREVIOUS round's best
        # (or a resumed attempt's — either way superseded the moment
        # this round's fit publishes): mark it seen so the first disk
        # poll doesn't deserialize a full checkpoint just to discard it
        # by round.  The in-process on_best leg still delivers every
        # new best instantly.
        self._watcher.prime()
        # XLA:CPU reorders execution behind the enqueue order, so for
        # the window the scorer thread shares the mesh every dispatch
        # must COMPLETE before its gate releases (mesh_lib.DispatchGate;
        # observed cross-thread AllReduce deadlock without it).  TPU
        # cores execute enqueued programs FIFO — the enqueue lock alone
        # is the contract there, and the async train stream stays async.
        if plan["mesh"].devices.flat[0].platform == "cpu":
            strategy.trainer.dispatch_lock.drain_mode = True
        with self._cv:
            self._plan = plan
            self._consumed = False
            self._cv.notify_all()
        self._ensure_thread()
        return True

    def publish_best(self, round_idx: int, epoch: int, variables) -> None:
        """Trainer-side publish (Trainer.fit's ``on_best``): a new best
        snapshot exists on device.  The scorer restarts from scratch —
        every previously scored chunk depended on the superseded
        weights.  Cheap and sync-free: one lock, no device work."""
        with self._cv:
            plan = self._plan
            if plan is None or plan["round"] != round_idx or self._consumed:
                return
            self._src = ((int(round_idx), int(epoch)), variables)
            self.stats["publishes"] += 1
            self._cv.notify_all()

    def finalize(self, round_idx: int, best_epoch: int) -> None:
        """The fit ended: pin the FINAL (round, best_epoch) tag.  Chunks
        scored from any other tag are dead; chunks from the final tag
        keep accumulating (the scorer keeps running through
        load_best_ckpt/test/save — more overlap) until ``consume``."""
        with self._cv:
            if self._plan is None or self._plan["round"] != round_idx:
                return
            self._final_tag = (int(round_idx), int(best_epoch))
            self._cv.notify_all()

    def join_prefetch(self) -> None:
        """Wait out the select-time prefetch thread.  Strategy.train
        calls this before EVERY fit: arm() joins it too, but the last
        round never arms, and a prefetch left running into that round's
        fit would race it on the trainer's lazily-built jitted forms
        (both sides seeing None and compiling twice)."""
        self._join_prefetch()

    def take_busy_s(self) -> float:
        """Scorer-thread busy seconds since the last take — the 'score'
        stream's contribution to the driver's overlap_frac."""
        with self._cv:
            busy, self._busy_s = self._busy_s, 0.0
        return busy

    def disarm(self, wait_s: float = 60.0) -> None:
        """Quiesce the scorer for THIS round without killing the thread
        (the degradation ladder's pipeline_off rung: the retried round
        runs sequentially, the NEXT round may re-arm).  Kills the plan,
        waits out any in-flight chunk, releases the CPU-mesh drain, and
        joins the prefetch thread.

        The in-flight wait is BOUNDED: disarm is the recovery path, and
        a scorer wedged mid-chunk (a stuck collective — possibly the
        very stall being healed) would otherwise hang it forever.  On
        expiry the chunk is abandoned loudly — its thread may still
        complete later, but the dead plan means nothing consumes it."""
        deadline = time.monotonic() + wait_s
        with self._cv:
            self._plan = None
            self._consumed = True
            self._cv.notify_all()
            while self._in_flight is not None:
                if self._thread is None or not self._thread.is_alive():
                    self._in_flight = None
                    break
                if time.monotonic() >= deadline:
                    self.logger.warning(
                        "round pipeline: disarm abandoned an in-flight "
                        "speculative chunk still running after "
                        f"{wait_s:.0f}s (wedged scorer); the round "
                        "proceeds sequentially")
                    self._in_flight = None
                    break
                self._cv.wait(timeout=1.0)
        self._strategy.trainer.dispatch_lock.drain_mode = False
        self._join_prefetch()
        try:
            tele_runtime.get_run().tick(spec_phase="idle")
        except Exception:  # noqa: BLE001 - best-effort heartbeat
            pass

    def shutdown(self) -> None:
        with self._cv:
            self._stop = True
            self._consumed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=120.0)
            self._thread = None
        self._join_prefetch()
        self._strategy.trainer.dispatch_lock.drain_mode = False

    # -- query-time hand-over (strategy-facing) ---------------------------

    def consume(self, kind: str, keys, idxs: np.ndarray, batch_size: int,
                variables) -> Optional[Dict[str, np.ndarray]]:
        """Hand the speculative scores to the sampler, completing any
        missing or invalidated chunk INLINE with the query-time weights
        (``variables`` — the final best checkpoint ``load_best_ckpt``
        installed).  Returns None when the request doesn't match the
        armed plan (rng-shuffled idxs, different statistic, no plan):
        the caller then runs the ordinary sequential pass.  Either way
        the scorer stops burning mesh time for this round, and the
        select-time prefetch is kicked off — selection runs next."""
        with self._cv:
            plan = self._plan
            req_keys = tuple(keys) if keys is not None else None
            matched = (
                plan is not None and not self._consumed
                and kind == plan["kind"]
                and req_keys == plan["keys"]
                and int(batch_size) == plan["batch_size"]
                and len(idxs) == len(plan["idxs"])
                and bool(np.array_equal(np.asarray(idxs), plan["idxs"])))
            if plan is not None and not self._consumed and not matched:
                self.stats["plan_misses"] += 1
            self._consumed = True
            self._cv.notify_all()
            # Hit or miss, the scorer takes no NEW jobs now (consumed);
            # wait out any in-flight chunk BEFORE releasing the CPU-mesh
            # execution drain — on a miss the caller dispatches the
            # sequential pass immediately, and doing that concurrently
            # with the chunk's collectives un-drained is exactly the
            # cross-thread deadlock the drain exists to prevent.  A DEAD
            # scorer thread (injected ThreadDeath, a hard crash) can
            # never finish its chunk: its death harness clears
            # _in_flight, and the liveness check below bounds the wait
            # even if the harness itself was killed — a dead thread must
            # cost a recompute, never a hang.
            while self._in_flight is not None:
                if self._thread is None or not self._thread.is_alive():
                    self._in_flight = None
                    break
                self._cv.wait(timeout=1.0)
            # The scorer thread is idle for good now (consumed + no
            # in-flight): single-threaded dispatch no longer needs the
            # execution drain.
            self._strategy.trainer.dispatch_lock.drain_mode = False
            if not matched:
                # The scorer's stream ends un-served: mark its heartbeat
                # track idle (a stale spec_phase=score would otherwise
                # merge into every later heartbeat) and still prefetch —
                # selection runs next either way.
                tele_runtime.get_run().tick(spec_phase="idle")
                self._start_prefetch()
                return None
            final = self._final_tag
            done = {}
            stale = 0
            for i, (tag, out, dt) in self._done.items():
                if final is not None and tag == final:
                    done[i] = (out, dt)
                else:
                    stale += 1
            # Chunks scored under a superseded tag are invalidated no
            # matter WHO notices first: if the scorer thread never woke
            # between the late publish and this consume (it had already
            # finished every chunk under the early tag), the dropped
            # entries would otherwise vanish uncounted —
            # chunks_invalidated read 0 after a forced late-best
            # invalidation, a scheduling-dependent accounting hole
            # (_next_job_locked's cleanup counts the same supersession
            # when the worker DOES wake first; both paths remove what
            # they count, so they can never double-count).
            self.stats["chunks_invalidated"] += stale
            slices = list(plan["slices"])
            self._done = {}
        outs: List[Dict[str, np.ndarray]] = []
        hits = inline = 0
        # Scoring COMPUTE seconds behind this hand-over (served chunks'
        # scorer-thread walls + the inline completions here): what the
        # pool_rows_per_sec the sequential pass would have reported
        # actually cost, even though most of it was hidden in the fit.
        score_s = 0.0
        for i, sl in enumerate(slices):
            if i in done:
                out, dt = done[i]
                outs.append(out)
                score_s += dt
                hits += 1
            else:
                t0 = time.perf_counter()
                outs.append(self._score_slice(plan, sl, variables))
                score_s += time.perf_counter() - t0
                inline += 1
        result = scoring.splice_chunks(outs)
        # The experiment-truth layer's chunked histogram (DESIGN.md
        # §13): per-chunk partials summed HERE, at consume — the merged
        # sum is bit-equal to one add over the spliced result (integer
        # bin counts; pinned in tests/test_diagnostics.py), so the
        # strategy records the histogram without re-walking the scores.
        diag = self._strategy.diagnostics
        score_hist = None
        if diag is not None and outs:
            key = diag_lib.primary_score_key(outs[0])
            if key is not None:
                score_hist = {key: diag_lib.histogram_from_chunks(
                    key, [c[key] for c in outs])}
        # Under the lock like every other stats mutation: the worker's
        # death harness can still increment chunks_failed concurrently
        # with this hand-over (found by the lock-discipline checker —
        # a bare += here is a read-modify-write race with that thread).
        with self._cv:
            self.stats["chunks_hit"] += hits
            self.stats["chunks_inline"] += inline
        self.last_consume = {"chunks": len(slices), "hits": hits,
                             "inline": inline,
                             "hit_frac": round(hits / max(1, len(slices)),
                                               4),
                             "score_s": score_s,
                             "score_hist": score_hist}
        self.logger.info(
            f"round pipeline: speculative scores served "
            f"{hits}/{len(slices)} chunks (inline-completed {inline})")
        # The scorer's stream is over for this round: mark its heartbeat
        # track idle so `status` stops reporting a second active phase.
        tele_runtime.get_run().tick(spec_phase="idle")
        self._start_prefetch()
        return result

    # -- the scorer thread -------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._worker,
                                            name="al-spec-scorer",
                                            daemon=True)
            self._thread.start()

    def _worker(self) -> None:
        """Thread entry: the loop plus the death harness.  An exception
        the loop's own guards don't catch — injected ThreadDeath
        (faults.site("spec_scorer")'s ``die`` action), a MemoryError, a
        bug — must not orphan the round: the plan is killed, any
        in-flight marker cleared (consume()'s wait would otherwise hang
        on a chunk that will never finish), the CPU-mesh execution drain
        released, and the heartbeat's scorer track idled.  The round
        then completes sequentially — a dead scorer costs wall-clock,
        never a score and never a hang."""
        try:
            self._worker_loop()
        except BaseException:  # noqa: BLE001 - thread-death harness
            self.logger.exception(
                "round pipeline: speculative scorer thread died; the "
                "round completes sequentially")
            with self._cv:
                self.stats["chunks_failed"] += 1
                self._in_flight = None
                self._plan = None
                self._cv.notify_all()
            self._strategy.trainer.dispatch_lock.drain_mode = False
            try:
                tele_runtime.get_run().tick(spec_phase="idle")
            except Exception:  # noqa: BLE001 - already on the death path
                pass

    def _worker_loop(self) -> None:
        """The scoring executor loop: take the lowest pending chunk for
        the current source checkpoint, score it, store it under its tag.
        Never touches the train stream's arrays (trace_lint check 7) —
        waiting happens on its OWN chunk outputs inside collect_pool's
        host fetch, which blocks only this thread."""
        tele_spans.get_tracer().name_thread("spec-scorer")
        rt = tele_runtime.get_run()
        while True:
            job = None
            need_poll = False
            with self._cv:
                if self._stop:
                    return
                job = self._next_job_locked()
                if job is None:
                    need_poll = (self._plan is not None
                                 and not self._consumed
                                 and self._src is None
                                 and self._final_tag is None)
                    if not need_poll:
                        # Idle: every state transition (arm, publish,
                        # finalize, consume, shutdown) notifies; the
                        # timeout is only a lost-notify backstop, not a
                        # poll cadence.
                        self._cv.wait(timeout=5.0)
                        continue
            if need_poll:
                self._poll_disk()
                with self._cv:
                    # Sleep the poll period ON the condition, so an
                    # in-process publish still wakes the scorer
                    # instantly instead of after the disk cadence.
                    if (not self._stop and self._plan is not None
                            and not self._consumed and self._src is None
                            and self._final_tag is None):
                        self._cv.wait(timeout=WATCH_POLL_S)
                continue
            chunk_i, sl, tag, variables, plan = job
            try:
                out, dt = self._score_chunk(plan, sl, tag, variables,
                                            chunk_i)
            except Exception:  # noqa: BLE001 - speculation is best-effort
                self.logger.exception(
                    "round pipeline: speculative chunk failed; disabling "
                    "speculation for this round")
                with self._cv:
                    self.stats["chunks_failed"] += 1
                    self._in_flight = None
                    self._plan = None
                    self._cv.notify_all()
                # Dead plan = dead scorer for the round: release the
                # CPU-mesh execution drain (the fit would otherwise pay
                # a sync per dispatch for a sharing window that no
                # longer exists) and clear the heartbeat track so
                # `status` stops reporting a scorer that will never run
                # again this round.
                self._strategy.trainer.dispatch_lock.drain_mode = False
                rt.tick(spec_phase="idle")
                continue
            with self._cv:
                self._busy_s += dt
                self._in_flight = None
                # Store even when consume() flagged the plan consumed
                # while this chunk was in flight: consume waits for
                # in-flight to clear BEFORE snapshotting _done, so a
                # just-finished valid chunk still lands as a hit.
                if self._plan is plan:
                    current = self._src[0] if self._src else None
                    valid = (tag == self._final_tag
                             if self._final_tag is not None
                             else tag == current)
                    if valid:
                        self._done[chunk_i] = (tag, out, dt)
                        self.stats["chunks_scored"] += 1
                    else:
                        self.stats["chunks_invalidated"] += 1
                # Tick INSIDE the lock, and only while the plan is still
                # live: consume()'s spec_phase="idle" tick runs after a
                # _cv section ordered AFTER this one, so a stale "score"
                # tick can never land on top of it and merge-persist a
                # phantom active scorer into every later heartbeat.
                if self._plan is plan and not self._consumed:
                    rt.tick(spec_phase="score", spec_chunk=chunk_i,
                            spec_round=tag[0])
                self._cv.notify_all()

    def _next_job_locked(self):
        plan = self._plan
        if plan is None or self._consumed:
            return None
        src = self._src
        if src is None:
            return None
        tag, variables = src
        if self._final_tag is not None and tag != self._final_tag:
            # The source the scorer holds is NOT the final best (e.g. a
            # publish raced the end of fit, or no final-tag source ever
            # arrived): nothing it could score would survive
            # invalidation, so stop here and let consume() complete
            # inline with the query-time weights.
            return None
        # A newer source invalidates everything scored under older tags
        # — "restart from the changed chunks", which for pool scores
        # (a global function of the checkpoint) is all of them.
        for i in [i for i, (t, _out, _dt) in self._done.items()
                  if t != tag]:
            del self._done[i]
            self.stats["chunks_invalidated"] += 1
        for i in range(len(plan["slices"])):
            if i not in self._done:
                self._in_flight = i
                return i, plan["slices"][i], tag, variables, plan
        return None

    def _poll_disk(self) -> None:
        """The disk leg of the best-ckpt bus (the serve executor's
        hot-reload pattern, shared via BestCkptWatcher): used only while
        no in-process publish has arrived for the armed round."""
        now = time.monotonic()
        if now - self._last_poll < WATCH_POLL_S or self._watcher is None:
            return
        self._last_poll = now
        try:
            polled = self._watcher.poll()
        except Exception as exc:  # noqa: BLE001 - classified below
            # The unified classification (faults.classify_exception)
            # instead of a blanket swallow: a transient FS error (NFS
            # hiccup, racing rename) just waits for the next poll; a
            # non-transient one disables THIS plan's disk leg loudly —
            # the in-process publish leg still delivers every best.
            if faults.classify_exception(exc) == faults.FATAL:
                self.logger.exception(
                    "round pipeline: best-ckpt disk poll failed "
                    "(non-transient); disk leg disabled for this round")
                self._watcher = None
            return
        if polled is None:
            return
        variables, rd, tag = polled
        with self._cv:
            plan = self._plan
            if (plan is None or self._consumed or self._src is not None
                    or tag is None or tag[0] != plan["round"]):
                return
            mesh = plan["mesh"]
        from ..parallel import mesh as mesh_lib
        dev_vars = mesh_lib.replicate(variables, mesh)
        with self._cv:
            if (self._plan is plan and not self._consumed
                    and self._src is None):
                self._src = (tag, dev_vars)
                self.stats["publishes"] += 1
                self._cv.notify_all()

    def _score_slice(self, plan: Dict[str, Any], sl: slice, variables
                     ) -> Dict[str, np.ndarray]:
        """One chunk through the SAME engine the sequential pass uses —
        collect_pool over a batch-aligned row slice is bit-identical to
        the same batches of the monolithic call.  Resident kwargs are
        re-resolved per call (the budget may have been refreshed at a
        round boundary); the dispatch lock is the trainer's, so chunk
        enqueues interleave train/eval steps in one global order."""
        strategy = self._strategy
        return scoring.collect_pool(
            plan["dataset"], plan["idxs"][sl], plan["batch_size"],
            plan["step_fn"], variables, plan["mesh"],
            num_workers=plan["num_workers"], prefetch=plan["prefetch"],
            keys=plan["keys"],
            dispatch_lock=strategy.trainer.dispatch_lock,
            **strategy._resident_kwargs())

    def _score_chunk(self, plan, sl, tag, variables, chunk_i: int):
        # The scorer thread's fault point: `raise` exercises the
        # disable-speculation-for-the-round path, `die` the thread-death
        # harness in _worker — both recover to sequential scoring.
        faults.site("spec_scorer")
        gate = self._strategy.trainer.dispatch_lock
        gate.take_wait_s()  # drop waits accrued outside this chunk
        # The chunk's collect_pool span nests under this one on the
        # spec-scorer thread and inherits its round.
        with tele_spans.get_tracer().span(
                "spec_score_chunk",
                args={"chunk": chunk_i, "round": tag[0],
                      "src_epoch": tag[1],
                      "rows": int(sl.stop - sl.start)}) as sp:
            out = self._score_slice(plan, sl, variables)
        # Busy = chunk wall minus this thread's time blocked on the
        # dispatch gate (the train stream held it): gate waits are idle,
        # not scoring compute, and counting them would overstate both
        # the overlap accounting and pool_rows_per_sec.
        busy = max(0.0, sp.duration_s - gate.take_wait_s())
        return out, busy

    # -- select-time train prefetch ---------------------------------------

    def _start_prefetch(self) -> None:
        self._join_prefetch()
        # Snapshot the pool views on THIS (the query) thread, where the
        # pool is still pre-update: the driver calls strategy.update the
        # moment query returns, and a thread reading num_labeled after
        # that would size the coming fit round_budget rows too large
        # (and read the labeled mask mid-mutation).
        strategy = self._strategy
        try:
            labeled_now = strategy.pool.labeled_idxs()
            expected = strategy.pool.num_labeled + min(
                int(strategy.cfg.round_budget), strategy.pool.num_available)
        except Exception:  # noqa: BLE001 - prefetch is best-effort
            self.logger.exception("round pipeline: train-feed prefetch "
                                  "skipped (pool view failed)")
            return
        t = threading.Thread(target=self._prefetch,
                             args=(labeled_now, expected),
                             name="al-feed-prefetch", daemon=True)
        self._prefetch_thread = t
        t.start()

    def _join_prefetch(self) -> None:
        t = self._prefetch_thread
        if t is not None and t.is_alive():
            t.join(timeout=120.0)
        self._prefetch_thread = None

    def _prefetch(self, labeled_now: np.ndarray, expected: int) -> None:
        """Warm the coming fit's feed while selection runs on the main
        thread (Trainer.prepare_next_fit) — rng-free, best-effort.  The
        pool views arrive as arguments, snapshotted by _start_prefetch
        before the driver's strategy.update can race them."""
        tele_spans.get_tracer().name_thread("feed-prefetch")
        strategy = self._strategy
        with tele_spans.get_tracer().span(
                "train_feed_prefetch",
                args={"expected_labeled": expected}) as sp:
            try:
                sp.args["feed"] = strategy.trainer.prepare_next_fit(
                    strategy.train_set, labeled_now, expected)
            except Exception:  # noqa: BLE001 - prefetch is best-effort
                self.logger.exception(
                    "round pipeline: train-feed prefetch failed (fit "
                    "resolves from scratch)")
