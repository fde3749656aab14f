"""The degradation ladder: escalate instead of crashing (DESIGN.md §10).

A failure that survives the site-level RetryPolicy reaches the driver's
round-attempt loop, which restores the round-start snapshot (pool, rng,
init key, model variables — so the retried round is BIT-identical to
the fault-free one) and asks this ladder for a less ambitious mode.
Rung order, each reversible at the next round boundary (``relax``):

  1. pipeline_off     speculative pipelined round -> sequential round
                      (the pipeline's correctness contract makes this
                      bit-identical; it only costs wall-clock)
  2. pool_replicated  row-sharded residency -> replicated (pinned pools
                      demoted; the next upload lands replicated —
                      layouts are bit-identical by the PR 6 contract)
  3. feed_host        resident budget -> 0: every consumer (scoring,
                      eval, the train feed) falls back to its
                      host-streamed path with zero recompiles (the
                      documented demotion path) — feeds are
                      bit-identical by the PR 5 contract
  4. batch_half       OOM only: halve the train batch.  The ONE
                      rung that is not bit-identical — batch size
                      changes BN statistics — which is why OOM is
                      outside the chaos matrix's bit-identity claim.

Rung selection: OOM-classified failures try batch_half first, then fall
through to the HBM-FREEING rungs (feed_host, pipeline_off — never
pool_replicated, which costs more per chip) when the batch is already
at the device floor; failures whose provenance names a subsystem (an
InjectedFault's site, the exception's traceback module) prefer that
subsystem's rung; anything else takes the next un-applied rung in
order.  Every escalation logs,
emits ``degrade_events`` through the MetricsSink at the round boundary,
updates the round journal's ``degrade`` list, and rides the telemetry
gauges — `status --strict` exits 4 while any rung is active.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional

from . import retry as retry_lib
from .registry import InjectedFault, ThreadDeath

RUNGS = ("pipeline_off", "pool_replicated", "feed_host", "batch_half")

# Site/subsystem provenance -> preferred first rung.
_SITE_RUNG = {
    "spec_scorer": "pipeline_off",
    "dispatch": "pipeline_off",
    "shard_upload": "pool_replicated",
    "h2d_upload": "pool_replicated",
    "feed_worker": "feed_host",
}

# Traceback-module provenance for REAL failures (no injected .site):
# the deepest frame inside one of these subsystems names the rung —
# a genuine shard-upload OSError on a multi-hour ImageNet round must
# not waste its first retry attempt on the irrelevant pipeline_off.
_MODULE_RUNG = (
    ("active_learning_tpu/experiment/pipeline", "pipeline_off"),
    ("active_learning_tpu/parallel/resident", "pool_replicated"),
    ("active_learning_tpu/parallel/mesh", "pool_replicated"),
    ("active_learning_tpu/data/cache", "feed_host"),
    ("active_learning_tpu/data/pipeline", "feed_host"),
)


def _provenance_rung(exc: BaseException) -> Optional[str]:
    """The rung the failure's origin names: an injected fault carries
    its site; anything else is attributed by the DEEPEST traceback
    frame inside a mapped subsystem module."""
    if isinstance(exc, (InjectedFault, ThreadDeath)):
        rung = _SITE_RUNG.get(getattr(exc, "site", ""))
        if rung is not None:
            return rung
    frames = []
    tb = exc.__traceback__
    while tb is not None:
        frames.append(tb.tb_frame.f_code.co_filename)
        tb = tb.tb_next
    for fname in reversed(frames):  # innermost first
        norm = fname.replace(os.sep, "/")
        for frag, rung in _MODULE_RUNG:
            if frag in norm:
                return rung
    return None


class DegradeRequested(Exception):
    """Raised at a driver safe point when the stall watchdog (armed with
    --watchdog_action degrade) asked for escalation — consumed by the
    round-attempt loop exactly like a classified failure."""


class DegradationLadder:
    """Owns the active rungs for one experiment run.  The driver calls
    ``relax`` at each round start (degradation is per-round — the next
    round retries at full capability), ``escalate`` when a round attempt
    fails, and ``check_stall`` at safe points."""

    def __init__(self, strategy, logger=None, sink=None, journal=None):
        self.strategy = strategy
        self.logger = logger
        self.sink = sink
        self.journal = journal
        self.active: List[str] = []
        self.events = 0  # cumulative escalations this run
        self._saved: Dict[str, Any] = {}
        self._stall_requested = False

    def max_attempts(self) -> int:
        """Round attempts = one clean try + one per remaining rung."""
        return len(RUNGS) + 1

    # -- stall hand-off (watchdog thread -> driver safe point) -----------

    def request_stall(self) -> None:
        self._stall_requested = True

    def check_stall(self) -> None:
        if self._stall_requested:
            self._stall_requested = False
            raise DegradeRequested("stall watchdog requested degradation")

    # -- escalation ------------------------------------------------------

    def _candidate_rungs(self, exc: BaseException) -> List[str]:
        """Un-applied rungs in preference order for ``exc``.  OOM:
        batch_half first, then the rungs that FREE HBM (demoting the
        resident pool, stopping the scorer's extra buffers) — never
        pool_replicated, whose per-chip residency costs MORE than row.
        Everything else: the failure's provenance rung, then the
        generic order; batch_half stays OOM-only."""
        kind = retry_lib.classify_exception(exc)
        if kind == retry_lib.OOM:
            order = ("batch_half", "feed_host", "pipeline_off")
        else:
            preferred = _provenance_rung(exc)
            order = ([preferred] if preferred
                     and preferred != "batch_half" else [])
            order += [r for r in RUNGS
                      if r != "batch_half" and r not in order]
        return [r for r in order if r not in self.active]

    def escalate(self, exc: BaseException, round_idx: int) -> Optional[str]:
        """Apply the next rung for ``exc``; returns its name, or None
        when the ladder is exhausted (the caller re-raises).  A
        candidate that cannot apply (batch already at the device floor)
        falls through to the next instead of dead-ending the ladder."""
        rung = None
        for candidate in self._candidate_rungs(exc):
            if self._apply(candidate):
                rung = candidate
                break
        if rung is None:
            return None
        self.active.append(rung)
        self.events += 1
        if self.logger is not None:
            self.logger.warning(
                f"degradation ladder: round {round_idx} failed with "
                f"{type(exc).__name__} ({exc}); engaging rung "
                f"{rung!r} (active: {self.active}) and retrying the "
                "round from its start")
        if self.sink is not None:
            self.sink.log_metric("degrade_events", self.events,
                                 step=round_idx)
        if self.journal is not None:
            self.journal.write(degrade=list(self.active), round=round_idx,
                               status="running")
        try:
            from ..telemetry import runtime as tele_runtime
            rt = tele_runtime.get_run()
            rt.set_gauges(degrade_active=len(self.active))
            rt.tick(force=True, degrade=",".join(self.active))
        except Exception:  # noqa: BLE001 - accounting must never crash
            pass
        return rung

    def _apply(self, rung: str) -> bool:
        strategy = self.strategy
        trainer = strategy.trainer
        if rung == "pipeline_off":
            pipe = strategy.pipeline
            self._saved["pipeline"] = pipe
            if pipe is not None:
                pipe.disarm()
            strategy.pipeline = None
            return True
        if rung == "pool_replicated":
            from ..parallel import resident as resident_lib
            self._saved["pool_sharding"] = (trainer.pool_sharding,
                                            trainer._shard_ways)
            # Demote every pinned entry so the next upload lands in the
            # new layout (an entry's layout is fixed at first upload).
            resident_lib.enforce_budget(trainer.resident_pool, 0)
            trainer.pool_sharding = "replicated"
            trainer._shard_ways = 1
            return True
        if rung == "feed_host":
            self._saved["resident_budget"] = trainer.resident_budget
            # pin: the retried attempt's round-start AUTO budget refresh
            # must not re-admit the resident path mid-degraded-round.
            trainer.set_resident_budget(0, pin=True)
            return True
        if rung == "batch_half":
            halved = self._halve_batch(trainer)
            return halved is not None
        return False

    def _halve_batch(self, trainer) -> Optional[int]:
        loader = trainer.cfg.loader_tr
        floor = trainer.n_devices
        new_bs = max(floor, loader.batch_size // 2)
        if new_bs == loader.batch_size:
            return None
        self._saved.setdefault("loader_tr", loader)
        trainer.cfg = dataclasses.replace(
            trainer.cfg, loader_tr=dataclasses.replace(loader,
                                                       batch_size=new_bs))
        if self.logger is not None:
            self.logger.warning(
                f"degradation ladder: train batch halved to {new_bs} "
                "(OOM); reverts at the next round boundary")
        return new_bs

    # -- reversal at the round boundary ----------------------------------

    def relax(self, round_idx: Optional[int] = None) -> List[str]:
        """Revert every active rung (called at round start — each round
        retries at full capability; a systematic failure re-engages the
        ladder, a transient one stays recovered).  Returns the reverted
        rung names."""
        if not self.active:
            self._stall_requested = False
            return []
        strategy = self.strategy
        trainer = strategy.trainer
        reverted = list(self.active)
        if "pipeline_off" in self.active:
            strategy.pipeline = self._saved.get("pipeline")
        if "pool_replicated" in self.active:
            from ..parallel import resident as resident_lib
            sharding, ways = self._saved["pool_sharding"]
            # Demote the replicated-degraded entries so the restored
            # layout's next upload is actually row-sharded again.
            resident_lib.enforce_budget(trainer.resident_pool, 0)
            trainer.pool_sharding = sharding
            trainer._shard_ways = ways
        if "feed_host" in self.active:
            trainer.set_resident_budget(self._saved["resident_budget"])
        if "batch_half" in self.active:
            trainer.cfg = dataclasses.replace(
                trainer.cfg, loader_tr=self._saved["loader_tr"])
        self.active = []
        self._saved = {}
        self._stall_requested = False
        if self.logger is not None:
            self.logger.info(
                f"degradation ladder: reverted {reverted} at the round "
                "boundary (full capability restored)")
        if self.journal is not None:
            self.journal.write(degrade=[], round=round_idx)
        try:
            from ..telemetry import runtime as tele_runtime
            tele_runtime.get_run().set_gauges(degrade_active=0)
        except Exception:  # noqa: BLE001
            pass
        return reverted
