"""The deterministic fault-injection registry (DESIGN.md §10).

Every recovery path this framework claims — retried H2D uploads, torn
checkpoint pairs that read as nothing-to-resume, a speculative scorer
thread that dies without losing the round, a preempted driver that
resumes bit-identically — is only real if a test can MAKE the failure
happen on demand.  This module is that switch: named fault points
(``site("h2d_upload")``) compiled into the production code paths, free
when disarmed (one module-global ``is None`` check — pinned like the
telemetry-off <50µs/step bound) and, when armed via ``--fault_spec`` /
``$AL_FAULT_SPEC``, deterministically raising, tearing a multi-file
write, killing the calling thread, or delaying.

Spec grammar (comma-separated)::

    site:action[@arg]

    h2d_upload:raise@3        raise InjectedFault on the 3rd hit (1-based,
                              fires exactly once)
    ckpt_write:torn@1         raise at the site's TORN point (between the
                              two renames of an atomic multi-file write)
                              on the 1st torn-point hit
    spec_scorer:die@0.5       kill the calling thread (ThreadDeath, a
                              BaseException that sails past
                              ``except Exception`` guards) with seeded
                              probability 0.5 per hit
    dispatch:delay@0.05       sleep 50 ms at every hit
    feed_worker:oom@2         raise InjectedOOM (classified like XLA's
                              RESOURCE_EXHAUSTED) on the 2nd hit

Integer args are Nth-hit triggers (deterministic, fire once); float args
in (0, 1) are per-hit probabilities drawn from a per-(seed, site)
``random.Random`` — replayable across runs; for ``delay`` the arg is
seconds.  No arg = every hit.

Site names are a CLOSED registry (``SITES``): scripts/trace_lint.py
check 8 statically verifies every ``faults.site()`` call site names a
registered site (string literal, registered exactly once) and that every
registered site is wired somewhere — a typo'd site name can never
silently never-fire.

Every site call has two points: ``enter`` (the default — raise/oom/die/
delay fire here, BEFORE the guarded work) and ``torn`` (only the
``torn`` action fires there — placed between the renames of an atomic
write pair so the crash leaves exactly the partial state the readers
must treat as nothing-to-resume).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Dict, Optional, Tuple

# The closed site registry — each name registered EXACTLY once (enforced
# statically by trace_lint check 8 alongside the wiring coverage).
#   h2d_upload    parallel/resident.pool_arrays — the once-per-experiment
#                 resident-pool device upload
#   shard_upload  parallel/mesh.shard_rows — the per-shard H2D of a
#                 row-sharded upload
#   ckpt_write    train/checkpoint.save_variables / save_fit_state /
#                 publish_best + experiment/resume.save_experiment (torn
#                 points between each atomic pair's renames)
#   spec_scorer   experiment/pipeline._score_chunk — the speculative
#                 scorer thread's chunk execution
#   feed_worker   data/cache.device_prefetch — the async H2D feeder
#                 thread behind scoring/serving
#   dispatch      parallel/mesh.DispatchGate.__enter__ — every
#                 collective-bearing jitted dispatch
#   grad_probe    experiment/driver.run_grad_allreduce_probe — the
#                 multichip learning probe gating --grad_allreduce
#                 int8 (an injected failure = a broken probe; the run
#                 must degrade to the f32 sync loudly, never crash)
#   wal_write     stream/wal.IngestWAL.append — the ingest WAL's durable
#                 append (torn point between the half-written line and
#                 its completion: a kill there must replay as a dropped
#                 never-acked record, not corruption)
#   stream_drain  stream/service.StreamService._drain — applying queued
#                 ingest records to the pool between rounds (a failure
#                 here must crash the service BEFORE any round consumes
#                 a half-applied pool; the WAL replay on restart loses
#                 no accepted row)
#   page_read     data/diskpool._DiskPoolCore._load_block — one
#                 bucket-aligned block read off the disk tier (torn
#                 point between the block's two half-reads: a fault
#                 there must never leave a partial block in the host
#                 cache; the gather's RetryPolicy re-reads the block)
#   fleet_journal fleet/journal.write_atomic_json — the fleet
#                 controller's scheduling-state rewrite (torn point
#                 between the tmp write and the rename: a controller
#                 killed there must restart from the PREVIOUS complete
#                 journal, never a spliced one)
SITES = ("h2d_upload", "ckpt_write", "spec_scorer", "feed_worker",
         "shard_upload", "dispatch", "grad_probe", "wal_write",
         "stream_drain", "page_read", "fleet_journal")

ACTIONS = ("raise", "oom", "die", "delay", "torn")


class InjectedFault(RuntimeError):
    """A deliberately injected, transiently-classified failure."""

    def __init__(self, site_name: str, detail: str = ""):
        super().__init__(f"injected fault at site {site_name!r}"
                         + (f" ({detail})" if detail else ""))
        self.site = site_name


class InjectedOOM(InjectedFault):
    """Injected allocator exhaustion — the message carries the XLA
    RESOURCE_EXHAUSTED marker so string-matching classifiers
    (retry.classify_exception) treat it exactly like the real thing."""

    def __init__(self, site_name: str):
        super().__init__(site_name, "RESOURCE_EXHAUSTED (injected)")


class ThreadDeath(BaseException):
    """Injected thread death.  Deliberately a BaseException: it must
    sail past every ``except Exception`` guard on the thread's stack and
    actually KILL the thread, so the survivors' cleanup paths (the
    pipeline worker's finally, device_prefetch's feeder forwarding) are
    what the chaos tests exercise — not a politely caught error."""

    def __init__(self, site_name: str):
        super().__init__(f"injected thread death at site {site_name!r}")
        self.site = site_name


class _SiteState:
    """One armed site: its action, trigger arg, seeded rng, and hit
    counters (per point)."""

    def __init__(self, name: str, action: str, arg, seed: int):
        self.name = name
        self.action = action
        self.arg = arg
        self.hits: Dict[str, int] = {"enter": 0, "torn": 0}
        self.fires = 0
        self._rng = random.Random(f"{seed}:{name}:{action}")

    def hit(self, point: str) -> Optional[float]:
        """Count the hit and fire the action: raising actions raise;
        ``delay`` RETURNS the sleep seconds instead (the caller sleeps
        OUTSIDE the registry lock — sites fire from several threads, and
        a sleep under the shared lock would serialize exactly the
        cross-thread races delays exist to widen)."""
        fire_point = "torn" if self.action == "torn" else "enter"
        if point != fire_point:
            return None
        self.hits[point] += 1
        arg = self.arg
        if self.action == "delay":
            self.fires += 1
            return float(arg) if arg is not None else 0.01
        if arg is None:
            fire = True
        elif isinstance(arg, int):
            fire = self.hits[point] == arg  # Nth hit, exactly once
        else:
            fire = self._rng.random() < float(arg)
        if not fire:
            return None
        self.fires += 1
        if self.action == "oom":
            raise InjectedOOM(self.name)
        if self.action == "die":
            raise ThreadDeath(self.name)
        raise InjectedFault(self.name, self.action)


# Disarmed = None: site() is one global read + identity compare.  The
# lock guards only ARMED-path hit counting (sites fire from several
# threads: the scorer, the prefetch feeder, the trainer).
_ARMED: Optional[Dict[str, _SiteState]] = None
_LOCK = threading.Lock()

# Lock discipline, statically enforced (scripts/al_lint.py
# lock-discipline): per-site hit/fire counters are mutated from every
# thread a site fires on — counted only under _LOCK.  ``hit`` is the
# declared under-the-lock helper (site() holds _LOCK around it).
_GUARDED_BY = {"hits": "_LOCK", "fires": "_LOCK"}
_LOCKED_HELPERS = ("hit",)


def parse_spec(spec: str) -> Dict[str, Tuple[str, Any]]:
    """``"h2d_upload:raise@3,ckpt_write:torn@1"`` ->
    ``{"h2d_upload": ("raise", 3), "ckpt_write": ("torn", 1)}``.
    Unknown sites/actions and malformed args fail fast — a typo'd spec
    arming nothing would make every chaos run silently vacuous."""
    out: Dict[str, Tuple[str, Any]] = {}
    for part in filter(None, (p.strip() for p in spec.split(","))):
        try:
            name, rest = part.split(":", 1)
        except ValueError:
            raise ValueError(f"fault spec entry {part!r}: expected "
                             "site:action[@arg]") from None
        if name not in SITES:
            raise ValueError(f"fault spec names unknown site {name!r} "
                             f"(registered: {', '.join(SITES)})")
        action, _, arg_s = rest.partition("@")
        if action not in ACTIONS:
            raise ValueError(f"fault spec action {action!r} for {name!r} "
                             f"is not one of {', '.join(ACTIONS)}")
        arg: Any = None
        if arg_s:
            try:
                arg = int(arg_s)
                if action != "delay" and arg < 1:
                    raise ValueError
            except ValueError:
                try:
                    arg = float(arg_s)
                except ValueError:
                    raise ValueError(
                        f"fault spec arg {arg_s!r} for {part!r} is "
                        "neither an int hit-count nor a float") from None
                if action != "delay" and not (0.0 < arg < 1.0):
                    raise ValueError(
                        f"fault spec probability {arg} for {part!r} must "
                        "be in (0, 1)")
        if name in out:
            raise ValueError(f"fault spec arms site {name!r} twice")
        out[name] = (action, arg)
    return out


def configure(spec: Optional[str], seed: int = 0) -> None:
    """Arm the registry from a spec string (None/"" disarms).  The spec
    resolution order at the driver is --fault_spec, then $AL_FAULT_SPEC
    — but the driver only calls this when one of them is set, so a test
    that armed programmatically before calling run_experiment keeps its
    arming."""
    global _ARMED
    if not spec:
        _ARMED = None
        return
    parsed = parse_spec(spec)
    _ARMED = {name: _SiteState(name, action, arg, seed)
              for name, (action, arg) in parsed.items()}


def active_spec() -> Optional[Dict[str, Tuple[str, Any]]]:
    armed = _ARMED
    if armed is None:
        return None
    return {name: (st.action, st.arg) for name, st in armed.items()}


def site(name: str, point: str = "enter") -> None:
    """A named fault point.  Disarmed (the production default) this is a
    single module-global check — zero-cost on hot paths (pinned in
    tests/test_faults.py).  Armed, the site's action fires per its
    trigger rule; see the module docstring for the grammar."""
    armed = _ARMED
    if armed is None:
        return
    st = armed.get(name)
    if st is None:
        return
    with _LOCK:
        delay = st.hit(point)
    if delay is not None:
        time.sleep(delay)


def fault_counters() -> Dict[str, Dict[str, int]]:
    """Per-site hit/fire counters of the CURRENT arming ({} when
    disarmed) — chaos tests assert the fault actually fired, so a
    recovered run can never be mistaken for a never-faulted one."""
    armed = _ARMED
    if armed is None:
        return {}
    with _LOCK:
        return {name: {"hits": sum(st.hits.values()), "fires": st.fires}
                for name, st in armed.items()}
