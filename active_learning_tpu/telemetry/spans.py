"""Hierarchical host-span tracing with Chrome-trace-event export.

The framework's only run-time timing signal used to be the driver's
round-granularity ``phase_timer`` wall-clocks — nothing between "a round
took 219 s" and a full XLA profiler capture.  This tracer fills that gap
with ONE span tree per round (experiment → round → phase → the work
inside the phase: ``collect_pool``, ``reinit/*``, ``fit/*``, ``epoch``,
``ckpt/*``, ``test/evaluate`` — DESIGN.md §7 has the table) recorded at
perf_counter resolution and exported as Chrome trace-event JSON
(``trace.json``), loadable in Perfetto or ``chrome://tracing`` with zero
extra tooling.  Every span carries an ``id``, the ``parent`` id (the top
of the opening thread's stack) and the ``round`` it belongs to, so self
time is computed from the record (``self_seconds``), never guessed from
containment.  While an XLA profiler trace is open, every ``span()`` is
also a ``jax.profiler.TraceAnnotation`` of the same name (through the
``annotate`` hook the run installs — telemetry/profiler.trace_annotation,
the one gated route), so the profiler's host plane holds the program's
spans on the device's own clock.

Design constraints, each load-bearing:

  * **Timing is unconditional, recording is opt-in.**  ``span()`` always
    measures (``phase_timer`` derives the ``rd_{name}`` metric from the
    SAME span, so metrics and spans cannot fork — scripts/trace_lint.py
    asserts the routing), but events are only appended when the tracer
    is enabled (TelemetryConfig.export_trace).  A disabled span is two
    ``perf_counter`` calls.
  * **Thread-safe, bounded.**  The serve executor, watchdog, and data
    feeder threads may all open spans; events append under a lock and
    the buffer is capped (oldest runs are multi-hour — an unbounded
    event list is a slow leak) with an explicit drop counter.
  * **No jax dependency.**  Importable from the status verb and tests
    without touching a backend; the device annotation is a callable the
    run hands in (telemetry/runtime.start_run), never an import here.
    JAX's compile stages reach the tracer through ``complete`` from
    telemetry/jax_events.py, the one home of the program's
    ``jax.monitoring`` listeners.

What the host does besides the program's own code is recorded too, and
only while the recorder is on (``start_host_hooks``, installed by
``start_run`` and removed by ``finish``/``uninstall``):

  * ``host/gc`` — a ``gc.callbacks`` hook times every collection and
    keeps their running total (``gc_s``); a collection of
    generation 1 or 2, or one of generation 0 that took
    ``GC_SPAN_MIN_S`` or more, becomes a span under the span open on the
    collecting thread.  The hook never takes ``_lock`` (a collection can
    start inside ``_record`` while it is held, and the lock is not
    re-entrant): it stages the span in a deque that the next
    ``_record``, ``snapshot_events`` or ``export`` drains.
  * process counters — a span opened with ``counters=True`` (the round,
    its epilogue, the round's snapshot and the five phases) carries the
    delta of ``gc_s`` over its interval.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

# Lock discipline, statically enforced (scripts/al_lint.py
# lock-discipline): the event buffer, its drop counter, and the
# thread-name map are appended from every span-opening thread (main,
# spec-scorer, feed-prefetch, watchdog, serve executor) — always under
# the tracer's _lock.
_GUARDED_BY = {"events": "_lock", "dropped": "_lock",
               "_thread_names": "_lock"}


# Span ids are process-wide (one counter, not one per tracer): a span
# recorded by the spec-scorer thread and one recorded by the main thread
# can never collide, and ids stay unique across a run that swaps tracers.
_SPAN_IDS = itertools.count(1)

GC_SPAN = "host/gc"
# A generation-0 collection shorter than this is counted in the totals but
# is not a span of its own: hundreds of them run in a round, and one event
# each would fill the bounded buffer with sub-millisecond pauses.
GC_SPAN_MIN_S = 1e-3


class Span:
    """One completed (or in-flight) host span.  ``parent`` is the id of
    the span that was open on the SAME thread when this one opened
    (None at a thread's root); ``round`` is taken from ``args`` when
    given there, else inherited from the parent."""

    __slots__ = ("name", "args", "t0", "t1", "tid", "id", "parent",
                 "round", "event")

    def __init__(self, name: str, args: Optional[Dict[str, Any]] = None,
                 parent: Optional["Span"] = None):
        self.name = name
        self.args = args
        self.id = next(_SPAN_IDS)
        self.parent = parent.id if parent is not None else None
        rd = args.get("round") if args else None
        self.round = (rd if rd is not None
                      else parent.round if parent is not None else None)
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None
        self.tid = threading.get_ident()
        self.event: Optional[Dict[str, Any]] = None   # its record, once closed

    @property
    def duration_s(self) -> float:
        end = self.t1 if self.t1 is not None else time.perf_counter()
        return end - self.t0


class SpanTracer:
    """Records nested host spans; exports one Chrome trace per run."""

    def __init__(self, enabled: bool = True, max_events: int = 200_000,
                 annotate: Optional[Callable[[str], Any]] = None):
        self.enabled = bool(enabled)
        # name -> context manager that names the enclosed interval in an
        # open XLA profiler trace (a flag test when none is open).
        self.annotate = annotate
        self.max_events = int(max_events)
        self.events: List[Dict[str, Any]] = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._origin = time.perf_counter()
        self._wall_origin = time.time()
        self._local = threading.local()
        self._thread_names: Dict[int, str] = {}
        # The collector hook's total and its staged spans (appended
        # without the lock; see the module docstring).
        self.gc_s = 0.0
        self._gc_t0: Optional[float] = None
        self._staged: collections.deque = collections.deque()

    # -- span stack (per thread, for nesting introspection) ---------------

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    def depth(self) -> int:
        return len(self._stack())

    # -- recording ---------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, args: Optional[Dict[str, Any]] = None,
             counters: bool = False) -> Iterator[Span]:
        """Open a nested span.  Always measures; records only when
        enabled.  The yielded Span's ``duration_s`` is valid after the
        block exits (phase_timer reads it for the metrics sink).  A site
        that learns a count only inside the block (steps run, bytes
        written) adds it to ``sp.args`` before the block exits.  With
        ``counters`` (and the recorder on) the span also carries the
        process counters' deltas over its interval."""
        base = self.process_counters() if counters and self.enabled \
            else None
        stack = self._stack()
        sp = Span(name, args, parent=stack[-1] if stack else None)
        stack.append(sp)
        try:
            if self.annotate is None:
                yield sp
            else:
                with self.annotate(name):
                    yield sp
        finally:
            sp.t1 = time.perf_counter()
            stack.pop()
            if self.enabled:
                if base is not None:
                    now = self.process_counters()
                    sp.args = {**(sp.args or {}),
                               **{k: now[k] - base[k] for k in now}}
                self._record(sp)

    def process_counters(self) -> Dict[str, Any]:
        """The process-wide counters a span carries as deltas: collector
        seconds (this tracer's hook)."""
        return {"gc_s": self.gc_s}

    def complete(self, name: str, t0: float, t1: float,
                 args: Optional[Dict[str, Any]] = None) -> None:
        """Record a span retroactively from perf_counter stamps: the
        stream path's collect_pool chunks, where a ``with`` per chunk
        would contort the control flow, and a JAX compile stage, which
        JAX reports only once it has ended (telemetry/jax_events.py).
        Its parent is whatever span is open on the calling thread at the
        time of the call, and it becomes the parent of what that span's
        record already holds inside its interval on this thread (a
        collection during a compile stage), so the tree stays a tree.  A
        retroactive span cannot be a device annotation."""
        if not self.enabled:
            return
        sp = Span(name, args, parent=self.current())
        sp.t0, sp.t1 = t0, t1
        self._drain()
        ts, tid = (t0 - self._origin) * 1e6, sp.tid % 2**31
        with self._lock:
            # Newest first; a span of this thread that ended before ``t0``
            # was recorded before anything that ran inside ``sp``.
            for e in reversed(self.events):
                a = e.get("args") or {}
                if e.get("tid") != tid or "id" not in a:
                    continue
                if e["ts"] + e["dur"] < ts:
                    break
                if a["parent"] == sp.parent and e["ts"] >= ts:
                    a["parent"] = sp.id
        self._record(sp)

    def name_thread(self, name: str) -> None:
        """Label the CURRENT thread's track in the exported trace (a
        Chrome ``thread_name`` metadata event).  The pipelined round's
        executor threads (spec-scorer, feed-prefetch) call this once at
        start so their spans render as NAMED side-by-side tracks in
        Perfetto next to the main thread's — every thread already gets
        its own ``tid`` (Span stamps ``threading.get_ident()``), which is
        what keeps concurrent spans from corrupting each other's nesting;
        this adds the human-readable label.  Idempotent per (thread,
        name); metadata events don't count against the buffer cap (a
        handful per run, and dropping one would orphan a whole track's
        spans from their label)."""
        if not self.enabled:
            return
        tid = threading.get_ident() % 2**31
        with self._lock:
            if self._thread_names.get(tid) == name:
                return
            self._thread_names[tid] = name
            self.events.append({
                "name": "thread_name", "ph": "M", "pid": os.getpid(),
                "tid": tid, "args": {"name": name},
            })

    def instant(self, name: str, args: Optional[Dict[str, Any]] = None
                ) -> None:
        """A zero-duration marker event (e.g. ``stall_suspected``)."""
        if not self.enabled:
            return
        now = time.perf_counter()
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append({
                "name": name, "ph": "i", "s": "t",
                "ts": (now - self._origin) * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident() % 2**31,
                **({"args": dict(args)} if args else {}),
            })

    def snapshot_events(self) -> List[Dict[str, Any]]:
        """A consistent copy of the recorded events (the profiler's
        per-phase attribution intersects device ops with the host phase
        spans recorded here)."""
        self._drain()
        with self._lock:
            return list(self.events)

    @property
    def origin(self) -> float:
        """The perf_counter stamp exported ts values are relative to —
        the device-truth profiler re-bases spliced device events onto
        this axis (telemetry/profiler.splice_into_tracer)."""
        return self._origin

    def splice_events(self, events: List[Dict[str, Any]]) -> int:
        """Append pre-built Chrome events (the profiler's re-based
        device tracks) to the export buffer.  Not counted against
        ``max_events``: the splice is bounded by the profiler's own cap
        (MAX_SPLICED_EVENTS) and dropping host spans to make room for
        device ops — or vice versa — would orphan one half of the very
        merge the splice exists for.  Returns the number appended (0
        when recording is off)."""
        if not self.enabled:
            return 0
        with self._lock:
            self.events.extend(events)
        return len(events)

    def _record(self, sp: Optional[Span]) -> None:
        """Append the span's event, and those of the collections staged
        since the last call."""
        spans = []
        while True:
            try:
                spans.append(self._staged.popleft())
            except IndexError:      # another thread may drain it too
                break
        if sp is not None:
            spans.append(sp)
        if not spans:
            return
        for s in spans:
            s.event = {
                "name": s.name, "ph": "X", "cat": "host",
                "ts": (s.t0 - self._origin) * 1e6,
                "dur": (s.t1 - s.t0) * 1e6,
                "pid": os.getpid(), "tid": s.tid % 2**31,
                "args": {**(s.args or {}), "id": s.id,
                         "parent": s.parent, "round": s.round},
            }
        with self._lock:
            for s in spans:
                if len(self.events) >= self.max_events:
                    self.dropped += 1
                else:
                    self.events.append(s.event)

    def _drain(self) -> None:
        if self.enabled:
            self._record(None)

    # -- the host's own activity ---------------------------------------------

    def start_host_hooks(self) -> None:
        """Install the collector hook (recording tracers only)."""
        if self.enabled and self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def stop_host_hooks(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` hook.  Takes no lock: the collection may have
        started inside ``_record`` with ``_lock`` held."""
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
            return
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is None:
            return
        self.gc_s += now - t0
        if info["generation"] > 0 or now - t0 >= GC_SPAN_MIN_S:
            sp = Span(GC_SPAN, {"generation": info["generation"],
                                "collected": info["collected"],
                                "uncollectable": info["uncollectable"]},
                      parent=self.current())
            sp.t0, sp.t1 = t0, now
            self._staged.append(sp)

    def amend(self, sp: Span, **counters: Any) -> None:
        """Counters a site learns only after its span closed (a count the
        device was still computing when the span ended at the enqueue):
        written into the span's record, before the round's export."""
        if sp.event is not None:
            with self._lock:
                sp.event["args"].update(counters)

    # -- export ------------------------------------------------------------

    def export(self, path: str, metadata: Optional[Dict[str, Any]] = None
               ) -> Optional[str]:
        """Write Chrome trace-event JSON atomically (tmp + rename), so a
        reader polling mid-run never sees a torn file.  Returns the path
        (None when recording is off — nothing to export)."""
        if not self.enabled:
            return None
        self._drain()
        with self._lock:
            events = list(self.events)
            dropped = self.dropped
        out = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "wall_origin": self._wall_origin,
                # ts is microseconds after this perf_counter stamp: a
                # reader that holds another perf_counter reading (the
                # benchmark's trace anchor) places spans on its clock.
                "perf_origin": self._origin,
                "dropped_events": dropped,
                **(metadata or {}),
            },
        }
        directory = os.path.dirname(os.path.abspath(path))
        os.makedirs(directory, exist_ok=True)
        tmp = f"{path}.tmp"
        # One string, one write: ``json.dump`` encodes chunk by chunk in
        # pure Python, ``dumps`` in one pass of the C encoder.
        text = json.dumps(out)
        with open(tmp, "w") as fh:
            fh.write(text)
        os.replace(tmp, path)
        return path


def self_seconds(events: List[Dict[str, Any]]) -> Dict[int, float]:
    """Self time per span id, from exported events: a span's duration
    minus the union of its children's intervals on the same thread
    (clipped to the span).  Children are the events whose
    ``args.parent`` is the span's id; a child on ANOTHER thread ran
    beside its parent, not inside it, and takes nothing away."""
    spans = [e for e in events
             if e.get("ph") == "X" and "id" in (e.get("args") or {})]
    children: Dict[Any, List[Dict[str, Any]]] = {}
    for e in spans:
        children.setdefault((e["args"]["parent"], e["tid"]), []).append(e)
    out: Dict[int, float] = {}
    for e in spans:
        t0, t1 = e["ts"], e["ts"] + e["dur"]
        covered, edge = 0.0, t0
        for c in sorted(children.get((e["args"]["id"], e["tid"]), ()),
                        key=lambda c: c["ts"]):
            a, b = max(c["ts"], edge), min(c["ts"] + c["dur"], t1)
            if b > a:
                covered += b - a
                edge = b
        out[e["args"]["id"]] = (e["dur"] - covered) / 1e6
    return out


# The process-wide tracer: disabled (timing-only) until a run installs a
# recording one (telemetry/runtime.start_run).  phase_timer and the
# scoring/trainer span sites all route through this, which is exactly
# what lets one install switch the whole stack.
_TRACER = SpanTracer(enabled=False)


def get_tracer() -> SpanTracer:
    return _TRACER


def set_tracer(tracer: Optional[SpanTracer]) -> SpanTracer:
    """Install (or, with None, reset to the disabled default) the
    process-wide tracer; returns the active instance."""
    global _TRACER
    _TRACER = tracer if tracer is not None else SpanTracer(enabled=False)
    return _TRACER
