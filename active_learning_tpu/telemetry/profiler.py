"""The device-truth layer: bounded XLA profiler capture windows, device-op
classification, collective-bytes accounting, and the merged host+device
timeline (DESIGN.md §11).

Everything the run reports elsewhere is HOST wall-clock — the span
tracer (spans.py) times dispatch loops from the host, which cannot
distinguish "the device was busy" from
"the host stalled feeding it" or "the collective waited on a peer".
This module is the one place the framework asks the DEVICE what
happened:

  * **Bounded capture windows.**  ``start_capture``/``finish_capture``
    (and the ``capture_window`` context manager over them) arm
    ``jax.profiler.start_trace``/``stop_trace`` around a chosen slice of
    the run — one warm AL round (``--profile_rounds``) or a serve window
    under live load (``POST /v1/profile``).  One window at a time,
    process-wide;
    never a whole run (a multi-hour trace is unusable and its overhead
    taints every number recorded during it).  This module is the ONLY
    place ``jax.profiler`` may be imported or invoked —
    scripts/trace_lint.py check 10 enforces it statically, the way
    check 9 closes the custom-VJP registry.

  * **Device-op parsing + classification.**  The profiler's trace-viewer
    export (``<host>.trace.json.gz``) is Chrome trace-event JSON whose
    device-side tracks carry one X event per executed XLA op, with
    ``args.hlo_module``/``args.hlo_op`` naming the HLO instruction.
    ``classify_op`` buckets each into compute / collective (psum →
    all-reduce, all_gather, ppermute → collective-permute, ...) /
    transfer (copies, H2D/D2H, infeed) / infra (runtime scaffolding,
    excluded from busy time), and ``summarize_capture`` derives
    ``device_busy_frac`` (fraction of the window with ≥1 device op in
    flight), ``collective_frac``/``transfer_frac`` (share of total
    device-op time), and per-primitive counts and time.

  * **Collective bytes.**  Trace events carry no shapes, but the HLO
    text does: when a capture is armed at run start, ``arm_hlo_dump``
    points ``--xla_dump_to`` at a sidecar directory (XLA latches the
    flag at backend init, so this works from a fresh process — the
    production CLI path — and silently stays empty in a process whose
    backend is already up), and ``hlo_collective_bytes`` parses the
    ``*after_optimizations.txt`` dumps into a {(module, op): bytes}
    table.  Measured execution counts from the trace × exact HLO payload
    bytes = ``collective_bytes_total`` per primitive per round — the
    int8-vs-f32 wire model's first measured byte counts (DESIGN.md §4).

  * **One merged timeline.**  ``splice_into_tracer`` re-bases the device
    events onto the host tracer's clock (via an anchor
    ``TraceAnnotation`` emitted inside the window whose host
    ``perf_counter`` stamp is recorded at emission) and appends them as
    named device tracks, so ONE Perfetto file answers "was the gap host
    stall, H2D, or collective wait" next to the existing host /
    spec-scorer / feed-prefetch tracks.

Parsing and classification are stdlib-only and import no jax — the
tests read capture summaries from hosts that could never initialize
the run's backend.  ``jax.profiler`` is
imported lazily inside the capture entry points only.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

# --------------------------------------------------------------------------
# Capture-window gating (the API trace_lint check 10 pins everything to).
# --------------------------------------------------------------------------

# The anchor annotation emitted inside every window: its trace timestamp
# plus the host perf_counter recorded at emission give the exact offset
# for re-basing device events onto the span tracer's clock.
ANCHOR_NAME = "al_profile_anchor"

# Bound on device events spliced into the merged timeline: a long window
# on a big mesh can carry millions of op events; the merged trace exists
# to answer gap questions, not to archive every op.
MAX_SPLICED_EVENTS = 120_000

# Serve-side bound on a live capture window (seconds).
MAX_SERVE_CAPTURE_S = 30.0

_ACTIVE_LOCK = threading.Lock()
_ACTIVE: Optional["CaptureHandle"] = None


class CaptureBusyError(RuntimeError):
    """A capture window is already open (one at a time, process-wide)."""


class CaptureHandle:
    """An open (or finished) capture window."""

    def __init__(self, out_dir: str, label: str):
        self.out_dir = out_dir
        self.label = label
        self.t0_pc: Optional[float] = None      # window open (perf_counter)
        self.t1_pc: Optional[float] = None      # window close
        self.anchor_pc: Optional[float] = None  # anchor annotation emission
        self.started_wall: Optional[float] = None
        self.session_dir: Optional[str] = None

    @property
    def window_s(self) -> Optional[float]:
        if self.t0_pc is None or self.t1_pc is None:
            return None
        return self.t1_pc - self.t0_pc


def start_capture(out_dir: str, label: str = "capture") -> CaptureHandle:
    """Open the process-wide capture window (raises CaptureBusyError when
    one is already open).  The jax.profiler import is deliberately inside:
    this module must stay importable without a backend."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if _ACTIVE is not None:
            raise CaptureBusyError(
                f"a capture window ({_ACTIVE.label!r}) is already open")
        handle = CaptureHandle(out_dir, label)
        _ACTIVE = handle
    try:
        import jax.profiler
        os.makedirs(out_dir, exist_ok=True)
        handle.started_wall = time.time()
        jax.profiler.start_trace(out_dir)
        handle.t0_pc = time.perf_counter()
        # The re-basing anchor: a zero-work annotation whose host stamp
        # is taken at emission.
        handle.anchor_pc = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANCHOR_NAME):
            pass
    except Exception:
        with _ACTIVE_LOCK:
            _ACTIVE = None
        raise
    return handle


def finish_capture(handle: CaptureHandle) -> CaptureHandle:
    """Close the window (idempotent per handle) and locate the session
    directory the profiler wrote."""
    global _ACTIVE
    try:
        import jax.profiler
        handle.t1_pc = time.perf_counter()
        jax.profiler.stop_trace()
    finally:
        with _ACTIVE_LOCK:
            if _ACTIVE is handle:
                _ACTIVE = None
    handle.session_dir = _newest_session_dir(handle.out_dir)
    return handle


@contextlib.contextmanager
def capture_window(out_dir: str, label: str = "capture"):
    """``with capture_window(dir) as handle: <profiled work>`` — the one
    spelling of a bounded capture.  The trace is stopped on ANY exit
    path (an exception mid-window must not leave the global profiler
    armed for the rest of the process)."""
    handle = start_capture(out_dir, label=label)
    try:
        yield handle
    finally:
        finish_capture(handle)


def trace_annotation(name: str):
    """The context manager that names the enclosed host interval in the
    profiler's own trace (its host plane, on the device planes' clock);
    a flag test when no trace is open, whoever would have opened it.
    ``SpanTracer.span`` is the one caller (the run installs this
    function as the tracer's ``annotate`` hook, runtime.start_run) — one
    name per span, one module touching jax.profiler."""
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


def arm_hlo_dump(dump_dir: str) -> Optional[str]:
    """Point XLA's HLO text dump at ``dump_dir`` for the collective-bytes
    table.  XLA parses ``XLA_FLAGS`` once, at backend initialization
    (re-verified on jax 0.9.0: set after ``jax.devices()`` the
    flag is inert; set before, every module compiled in the run lands in
    the dump) — so the driver arms this BEFORE its multi-host rendezvous,
    which is the run's first backend touch on the production CLI path.
    In a process whose backend is already up (pytest)
    the env change is silently inert and the byte table stays empty —
    the capture then reports counts/time without bytes rather than
    guessing.  Returns the directory armed, or the one an operator
    already set (their flags are never overridden), or None on failure."""
    flags = os.environ.get("XLA_FLAGS", "")
    existing = re.search(r"--xla_dump_to=(\S+)", flags)
    if existing:
        return existing.group(1)
    try:
        os.makedirs(dump_dir, exist_ok=True)
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_dump_to={dump_dir} "
            "--xla_dump_hlo_as_text").strip()
        return dump_dir
    except OSError:
        return None


# --------------------------------------------------------------------------
# Round selection (--profile_rounds).
# --------------------------------------------------------------------------

# The default window: the FIRST warm round.  Round 0 pays the cold
# compile tax (and, under the pipelined driver, is the arming round), so
# its trace answers "how slow is compilation", not "where does the
# steady-state round go" — captures never arm on round 0.
DEFAULT_PROFILE_ROUNDS = (1,)


def parse_profile_rounds(spec: Optional[str]) -> Tuple[Tuple[int, ...],
                                                       List[int]]:
    """``--profile_rounds`` → (rounds, rejected).  Accepts a
    comma-separated int list or the literal ``warm`` (= the default
    first-warm-round window); round 0 and negatives are REJECTED, never
    armed (returned in ``rejected`` so the caller can log why)."""
    if spec is None or str(spec).strip() in ("", "warm"):
        return DEFAULT_PROFILE_ROUNDS, []
    rounds, rejected = [], []
    for tok in str(spec).split(","):
        tok = tok.strip()
        if not tok:
            continue
        try:
            rd = int(tok)
        except ValueError:
            rejected.append(tok)
            continue
        if rd <= 0:
            rejected.append(rd)
        elif rd not in rounds:
            rounds.append(rd)
    return tuple(sorted(rounds)), rejected


# --------------------------------------------------------------------------
# Trace parsing (stdlib only — no jax).
# --------------------------------------------------------------------------

def _newest_session_dir(out_dir: str) -> Optional[str]:
    """The profiler writes <out_dir>/plugins/profile/<stamp>/; newest
    stamp wins (repeat captures into one dir share the tree)."""
    sessions = glob.glob(os.path.join(out_dir, "plugins", "profile", "*"))
    sessions = [s for s in sessions if os.path.isdir(s)]
    if not sessions:
        return None
    return max(sessions, key=os.path.getmtime)


def find_trace_file(out_dir: str) -> Optional[str]:
    """The trace-viewer JSON (``<host>.trace.json.gz``) of the newest
    session under ``out_dir`` — the artifact carrying hlo_module/hlo_op
    args per device event (the perfetto variant drops them).  Accepts
    either the capture's out_dir or a session directory itself."""
    if glob.glob(os.path.join(out_dir, "*.trace.json.gz")):
        session = out_dir
    else:
        session = _newest_session_dir(out_dir)
    if session is None:
        return None
    traces = [p for p in glob.glob(os.path.join(session, "*.trace.json.gz"))
              if "perfetto" not in os.path.basename(p)]
    return max(traces, key=os.path.getmtime) if traces else None


def parse_trace(path: str) -> Dict[str, Any]:
    """One trace-viewer JSON → {"events": [...], "processes": {pid:
    name}, "threads": {(pid, tid): name}}."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        data = json.load(fh)
    events = data["traceEvents"] if isinstance(data, dict) else data
    processes: Dict[int, str] = {}
    threads: Dict[Tuple[int, int], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            processes[e["pid"]] = (e.get("args") or {}).get("name", "")
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = (e.get("args") or {}).get(
                "name", "")
    return {"events": events, "processes": processes, "threads": threads}


# Device-track selection.  TPU/GPU planes arrive as /device:* processes
# (keep only the per-device "XLA Ops" line when one exists — the Steps /
# Modules / Framework lines re-describe the same intervals and would
# double-count busy time); the CPU backend has no device plane, so its
# XLA execution threads (the Eigen compute pool + the TfrtCpuClient
# execute threads) stand in for it.
_CPU_DEVICE_THREAD = re.compile(r"^tf_XLA")


def device_tracks(trace: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(pid, tid) pairs whose events are device-side op executions."""
    device_pids = {pid for pid, name in trace["processes"].items()
                   if str(name).startswith("/device:")}
    tracks: List[Tuple[int, int]] = []
    for pid in device_pids:
        tids = [(p, t) for (p, t), _ in trace["threads"].items()
                if p == pid]
        ops_only = [(p, t) for (p, t) in tids
                    if "XLA Ops" in trace["threads"][(p, t)]]
        tracks.extend(ops_only or tids)
    for (pid, tid), name in trace["threads"].items():
        if pid in device_pids:
            continue
        proc = str(trace["processes"].get(pid, ""))
        if proc.startswith("/host:") and _CPU_DEVICE_THREAD.match(
                str(name)):
            tracks.append((pid, tid))
    return tracks


def device_events(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The X events on device tracks, each tagged with its class."""
    tracks = set(device_tracks(trace))
    out = []
    for e in trace["events"]:
        if e.get("ph") != "X" or (e["pid"], e.get("tid")) not in tracks:
            continue
        out.append(dict(e, cls=classify_op(e.get("name", ""))))
    return out


# --------------------------------------------------------------------------
# Classification (DESIGN.md §11's event table).
# --------------------------------------------------------------------------

# HLO collective opcodes, matched as prefixes of the instruction name
# ("all-reduce.1", "all-gather-start.2", "collective-permute-done", ...).
COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
)
# Data-movement markers: device<->device / host<->device copies, infeed/
# outfeed, and host transfer send/recv.
_TRANSFER_PREFIXES = ("copy", "d2d", "h2d", "d2h", "infeed", "outfeed",
                      "send", "recv", "transfer", "memcpy")
# Runtime scaffolding — never counted as device busy time: these events
# describe the executor driving the ops, not the ops.
_INFRA_MARKERS = ("threadpoollistener", "thunkexecutor", "executehelper",
                  "execute", "parsearguments", "buffer::await",
                  "pjitfunction", "program", "::", "$")


def classify_op(name: str) -> str:
    """One device event name → "collective" | "transfer" | "compute" |
    "infra".  Collectives first (an `all-reduce` IS data movement, but
    its byte accounting is the whole point); infra last-but-one so a
    runtime frame never reads as compute."""
    low = str(name).lower().lstrip("%")
    for op in COLLECTIVE_OPS:
        if low.startswith(op):
            return "collective"
    for p in _TRANSFER_PREFIXES:
        if low.startswith(p):
            return "transfer"
    for m in _INFRA_MARKERS:
        if m in low:
            return "infra"
    return "compute"


def collective_primitive(name: str) -> Optional[str]:
    """"all-reduce-start.17" → "all-reduce"; None for non-collectives."""
    low = str(name).lower().lstrip("%")
    for op in COLLECTIVE_OPS:
        if low.startswith(op):
            return op
    return None


def _is_async_done(name: str) -> bool:
    """The -done half of an async collective pair: its -start twin holds
    the duration and the payload; counting both would double the op."""
    base = str(name).lower().split(".")[0]
    return base.endswith("-done")


# --------------------------------------------------------------------------
# The HLO collective-bytes table.
# --------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"(pred|[a-z]\d+[a-z0-9]*)\[([0-9,]*)\]")
_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)


def _shape_bytes(shape_text: str) -> int:
    """Total payload bytes of every array in an HLO result shape (tuple
    shapes sum their members; unknown dtypes contribute 0 rather than
    guess)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * size
    return total


_INST_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.+?)\s+([\w-]+)\(", re.M)


def hlo_text_instructions(text: str) -> List[Tuple[str, str, int]]:
    """[(name, opcode, result bytes)] of every instruction in ONE
    optimized-HLO module's text (fused computations included) — logical
    bytes of the result arrays (``_shape_bytes``), so a copy into a
    padded layout still counts as its operand's size.  What
    ``resident.assert_pool_read_in_place`` reads a compiled runner by."""
    return [(name, op, _shape_bytes(shape_text))
            for name, shape_text, op in _INST_RE.findall(text)]


def _collective_inst_re() -> "re.Pattern":
    pattern = "|".join(re.escape(op) for op in COLLECTIVE_OPS)
    # The async lowering emits '-start'/'-done' pairs; the -start
    # instruction carries the payload shape (and its NAME is what the
    # trace's hlo_op references), so the opcode match must accept it —
    # without this, every collective on the async-lowering platforms
    # (TPU) would land in collective_events_unattributed.
    return re.compile(
        rf"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.+?)\s+"
        rf"({pattern})(?:-start)?\(",
        re.M)


def hlo_text_collective_bytes(text: str) -> Dict[str, int]:
    """{op_name: payload bytes} from ONE optimized-HLO module's text —
    the parsing core of ``hlo_collective_bytes``, exposed so callers
    holding compiled executables directly (``jitted.lower(...)
    .compile().as_text()`` — the pod-tier wire-bytes cross-check in
    tests/test_pod_tier.py) can measure collective payload bytes
    without arming a disk dump."""
    table: Dict[str, int] = {}
    for name, shape_text, _op in _collective_inst_re().findall(text):
        nbytes = _shape_bytes(shape_text)
        if nbytes > 0:
            table[name] = max(table.get(name, 0), nbytes)
    return table


def hlo_collective_bytes(dump_dir: Optional[str]
                         ) -> Dict[Tuple[str, str], int]:
    """{(hlo_module, op_name): payload bytes} from every
    ``*after_optimizations.txt`` under ``dump_dir``.  Payload = the
    instruction's result arrays (per shard, per execution).  When one
    (module, op) pair appears at several sizes (shape-bucketed
    recompiles share a module name), the LARGEST wins — a bound, not a
    fabrication, and flagged by the caller via ambiguity counting."""
    table: Dict[Tuple[str, str], int] = {}
    if not dump_dir or not os.path.isdir(dump_dir):
        return table
    inst_re = _collective_inst_re()
    for path in glob.glob(os.path.join(dump_dir,
                                       "*after_optimizations.txt")):
        try:
            with open(path) as fh:
                text = fh.read()
        except OSError:
            continue
        m = _HLO_MODULE_RE.search(text)
        module = m.group(1) if m else os.path.basename(path)
        for name, shape_text, _op in inst_re.findall(text):
            nbytes = _shape_bytes(shape_text)
            if nbytes <= 0:
                continue
            key = (module, name)
            table[key] = max(table.get(key, 0), nbytes)
    return table


# --------------------------------------------------------------------------
# Summarisation.
# --------------------------------------------------------------------------

def _union_time_us(intervals: List[Tuple[float, float]]) -> float:
    """Total covered time of possibly-overlapping [t0, t1) intervals."""
    if not intervals:
        return 0.0
    intervals.sort()
    total, cur0, cur1 = 0.0, intervals[0][0], intervals[0][1]
    for t0, t1 in intervals[1:]:
        if t0 > cur1:
            total += cur1 - cur0
            cur0, cur1 = t0, t1
        else:
            cur1 = max(cur1, t1)
    return total + (cur1 - cur0)


def summarize_capture(trace: Dict[str, Any], window_s: Optional[float],
                      byte_table: Optional[Dict[Tuple[str, str], int]]
                      = None) -> Dict[str, Any]:
    """The per-window device-truth summary (the numbers the driver emits
    as metrics):

      device_busy_frac   fraction of the window with >= 1 device op in
                         flight (union over device tracks) — low busy
                         under a slow phase means the gap was HOST side;
      collective_frac /  share of total device-op TIME (sum basis: a
      transfer_frac      collective on every chip counts every chip);
      collectives        per-primitive {count, time_ms, bytes} — counts
                         from the trace, bytes = count x the HLO payload
                         of that exact instruction (None when the dump
                         was not armed / the op is unmatched);
      collective_bytes_total  sum over attributed primitives.
    """
    evs = device_events(trace)
    ops = [e for e in evs if e["cls"] != "infra"]
    busy_us = _union_time_us(
        [(e["ts"], e["ts"] + e.get("dur", 0.0)) for e in ops])
    time_by_cls: Dict[str, float] = {}
    for e in ops:
        time_by_cls[e["cls"]] = time_by_cls.get(e["cls"], 0.0) \
            + e.get("dur", 0.0)
    total_op_us = sum(time_by_cls.values())

    byte_table = byte_table or {}
    collectives: Dict[str, Dict[str, Any]] = {}
    unattributed = 0
    for e in ops:
        prim = collective_primitive(e.get("name", ""))
        if prim is None:
            continue
        entry = collectives.setdefault(
            prim, {"count": 0, "time_ms": 0.0, "bytes": 0,
                   "attributed": 0})
        entry["time_ms"] += e.get("dur", 0.0) / 1000.0
        if _is_async_done(e.get("name", "")):
            continue
        entry["count"] += 1
        args = e.get("args") or {}
        key = (args.get("hlo_module", ""),
               args.get("hlo_op") or e.get("name", ""))
        nbytes = byte_table.get(key)
        if nbytes is None:
            unattributed += 1
        else:
            entry["bytes"] += nbytes
            entry["attributed"] += 1
    for entry in collectives.values():
        entry["time_ms"] = round(entry["time_ms"], 3)
        if entry["attributed"] == 0:
            entry["bytes"] = None  # counts measured, payload unknown
        del entry["attributed"]
    bytes_known = [v["bytes"] for v in collectives.values()
                   if v["bytes"] is not None]
    # No collectives executed -> an honest 0; collectives executed but
    # none byte-attributed (dump not armed) -> None, never a guess.
    if not collectives:
        collective_bytes_total: Optional[int] = 0
    elif bytes_known:
        collective_bytes_total = int(sum(bytes_known))
    else:
        collective_bytes_total = None
    window_us = window_s * 1e6 if window_s else None
    return {
        "window_s": round(window_s, 4) if window_s else None,
        "device_event_count": len(evs),
        "device_op_count": len(ops),
        "device_busy_frac": (round(min(1.0, busy_us / window_us), 4)
                             if window_us else None),
        "collective_frac": (round(
            time_by_cls.get("collective", 0.0) / total_op_us, 4)
            if total_op_us > 0 else None),
        "transfer_frac": (round(
            time_by_cls.get("transfer", 0.0) / total_op_us, 4)
            if total_op_us > 0 else None),
        "device_op_time_ms": {cls: round(us / 1000.0, 3)
                              for cls, us in sorted(time_by_cls.items())},
        "collectives": collectives,
        "collective_bytes_total": collective_bytes_total,
        "collective_events_unattributed": unattributed,
        "byte_table_entries": len(byte_table),
    }


# --------------------------------------------------------------------------
# The merged timeline.
# --------------------------------------------------------------------------

# Device tracks splice under synthetic pids well away from any real one:
# the host spans use os.getpid() and the raw trace reuses it too — the
# offset keeps Perfetto rendering them as separate named processes.
DEVICE_PID_BASE = 1 << 30


def _anchor_offset_us(trace: Dict[str, Any], handle: CaptureHandle,
                      host_origin_pc: float) -> Tuple[float, str]:
    """Offset to add to a raw trace ``ts`` to land on the span tracer's
    microsecond axis.  Exact when the anchor annotation survived into
    the trace; else aligned at the window start (sub-ms skew possible,
    recorded in the export metadata)."""
    anchor_host_us = (handle.anchor_pc - host_origin_pc) * 1e6
    for e in trace["events"]:
        if e.get("ph") == "X" and e.get("name") == ANCHOR_NAME:
            return anchor_host_us - e["ts"], "anchor"
    dev = device_events(trace)
    if dev and handle.t0_pc is not None:
        first = min(e["ts"] for e in dev)
        return (handle.t0_pc - host_origin_pc) * 1e6 - first, \
            "window_start"
    return 0.0, "none"


# Slack around the capture window when clipping spliced events (µs):
# events straddling the window edge keep their place; events whose
# timestamps live in a different epoch (some runtime threads carry
# process-lifetime stamps) are dropped instead of rendering as a bogus
# pre-history track.
_WINDOW_CLIP_SLACK_US = 100_000.0


def build_device_track_events(trace: Dict[str, Any],
                              handle: CaptureHandle,
                              host_origin_pc: float,
                              max_events: int = MAX_SPLICED_EVENTS
                              ) -> Tuple[List[Dict[str, Any]], int, str]:
    """Chrome events (metadata + re-based device OPS) ready to splice
    into the host trace; returns (events, dropped, alignment).  Only
    compute/collective/transfer ops splice — runtime scaffolding (the
    infra class, ThreadpoolListener at ~50 events per dispatched op on
    CPU) would flood the cap with tracks that answer nothing — and ops
    re-based outside the capture window (± slack) are dropped: a
    handful of runtime threads stamp against a different epoch, and a
    merged timeline with one track offset by minutes is worse than a
    missing one."""
    offset_us, alignment = _anchor_offset_us(trace, handle,
                                             host_origin_pc)
    lo = hi = None
    if handle.t0_pc is not None and handle.t1_pc is not None:
        lo = ((handle.t0_pc - host_origin_pc) * 1e6
              - _WINDOW_CLIP_SLACK_US)
        hi = ((handle.t1_pc - host_origin_pc) * 1e6
              + _WINDOW_CLIP_SLACK_US)
    ops = [e for e in device_events(trace) if e["cls"] != "infra"]
    pid_map: Dict[int, int] = {}
    out: List[Dict[str, Any]] = []
    dropped = 0
    n_ops = 0
    body: List[Dict[str, Any]] = []
    used_tracks = set()
    for e in ops:
        ts = e["ts"] + offset_us
        if lo is not None and not (lo <= ts <= hi):
            dropped += 1
            continue
        if n_ops >= max_events:
            dropped += 1
            continue
        n_ops += 1
        used_tracks.add((e["pid"], e.get("tid")))
        mapped = pid_map.setdefault(e["pid"],
                                    DEVICE_PID_BASE + len(pid_map))
        ev = {"name": e.get("name", "?"), "ph": "X", "cat": "device",
              "ts": ts, "dur": e.get("dur", 0.0),
              "pid": mapped, "tid": e.get("tid", 0) % 2**31,
              "args": {"class": e["cls"]}}
        args = e.get("args") or {}
        if args.get("hlo_module"):
            ev["args"]["hlo_module"] = args["hlo_module"]
        body.append(ev)
    # Metadata only for tracks that actually contributed ops (an empty
    # named track per threadpool thread is visual noise).
    for pid in sorted(pid_map):
        proc = str(trace["processes"].get(pid, f"pid{pid}"))
        out.append({"name": "process_name", "ph": "M",
                    "pid": pid_map[pid],
                    "args": {"name": f"XLA device ops ({proc})"}})
    for pid, tid in sorted(used_tracks):
        out.append({"name": "thread_name", "ph": "M",
                    "pid": pid_map[pid], "tid": (tid or 0) % 2**31,
                    "args": {"name": str(
                        trace["threads"].get((pid, tid), tid))}})
    return out + body, dropped, alignment


def splice_into_tracer(tracer, trace: Dict[str, Any],
                       handle: CaptureHandle
                       ) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """Append the capture's device tracks to the span tracer so the next
    export is the merged host+device timeline.  Returns (splice stats,
    the re-based device op events) — the ops feed the per-phase
    attribution, already on the host time axis.  The ONE spelling of
    the splice: RoundProfiler.finalize calls this, not a copy."""
    events, dropped, alignment = build_device_track_events(
        trace, handle, tracer.origin)
    spliced = tracer.splice_events(events)
    stats = {"spliced_events": spliced, "device_events_dropped": dropped,
             "alignment": alignment}
    return stats, [e for e in events if e.get("ph") == "X"]


def phase_device_attribution(host_events: List[Dict[str, Any]], rd: int,
                             device_ops: List[Dict[str, Any]]
                             ) -> Dict[str, Dict[str, float]]:
    """Per-PHASE device attribution: intersect the re-based device ops
    with round ``rd``'s host phase spans (query_time / train_time /
    test_time / ... — the phase_timer spans already in the tracer), so
    "was the gap host stall or collective wait" has a NUMBER per phase,
    not just a picture: {phase: {busy_frac, collective_frac,
    device_ms}}.  ``device_ops`` are chrome X events on the HOST time
    axis (build_device_track_events output)."""
    spans = {}
    for e in host_events:
        if e.get("ph") != "X" or not str(e.get("name", "")).endswith(
                "_time"):
            continue
        if (e.get("args") or {}).get("round") != rd:
            continue
        spans[e["name"]] = (e["ts"], e["ts"] + e.get("dur", 0.0))
    ops = [e for e in device_ops if e.get("ph") == "X"]
    out: Dict[str, Dict[str, float]] = {}
    for name, (t0, t1) in spans.items():
        if t1 <= t0:
            continue
        clipped = []
        coll_us = total_us = 0.0
        for e in ops:
            a = max(e["ts"], t0)
            b = min(e["ts"] + e.get("dur", 0.0), t1)
            if b <= a:
                continue
            clipped.append((a, b))
            total_us += b - a
            if (e.get("args") or {}).get("class") == "collective":
                coll_us += b - a
        busy = _union_time_us(clipped)
        out[name] = {
            "busy_frac": round(busy / (t1 - t0), 4),
            "collective_frac": (round(coll_us / total_us, 4)
                                if total_us > 0 else None),
            "device_ms": round(total_us / 1000.0, 3),
        }
    return out


# --------------------------------------------------------------------------
# The driver hook: bounded per-round capture windows.
# --------------------------------------------------------------------------

def round_scope(rp: Optional["RoundProfiler"], rd: int, **kwargs):
    """The driver's per-round hook: a null context (two attribute reads)
    when profiling is unarmed or the round is not selected — the
    off-path cost tests/test_profiler.py bounds — else the capture
    window.  Round 0 can never arm (RoundProfiler.should_capture)."""
    if rp is None or not rp.should_capture(rd):
        return contextlib.nullcontext()
    return rp.round_capture(rd, **kwargs)


class RoundProfiler:
    """Owns a run's ``--profile_rounds`` windows: which rounds capture,
    where artifacts land, the HLO byte table, and the post-capture
    splice + metric emission."""

    def __init__(self, profile_dir: str,
                 rounds: Sequence[int] = DEFAULT_PROFILE_ROUNDS,
                 hlo_dump_dir: Optional[str] = None, logger=None):
        self.profile_dir = profile_dir
        self.rounds = tuple(int(r) for r in rounds)
        self.hlo_dump_dir = hlo_dump_dir
        self.logger = logger
        self.captures: Dict[int, Dict[str, Any]] = {}

    def should_capture(self, rd: int) -> bool:
        # Round 0 is the compile-tax round: never armed, whatever the
        # spec said (parse_profile_rounds already rejects it; this is
        # the second lock on the same door).
        return rd != 0 and rd in self.rounds

    @contextlib.contextmanager
    def round_capture(self, rd: int, tracer=None, sink=None,
                      telemetry=None):
        """One round's capture window + post-processing.  Post-capture
        failures (parse, splice, IO) are logged and swallowed — the
        profiler observes the round, it must never cost one."""
        out_dir = os.path.join(self.profile_dir, f"round_{rd}")
        if self.logger:
            self.logger.info(
                f"profiler: capture window armed for round {rd} "
                f"-> {out_dir}")
        with capture_window(out_dir, label=f"round_{rd}") as handle:
            yield handle
        try:
            summary = self.finalize(rd, handle, tracer=tracer, sink=sink,
                                    telemetry=telemetry)
            if self.logger and summary:
                self.logger.info(
                    "profiler: round %d device_busy_frac=%s "
                    "collective_frac=%s collective_bytes_total=%s (%s)"
                    % (rd, summary.get("device_busy_frac"),
                       summary.get("collective_frac"),
                       summary.get("collective_bytes_total"),
                       summary.get("summary_path")))
        except Exception as e:  # noqa: BLE001 - observe, never cost
            if self.logger:
                self.logger.warning(
                    f"profiler: round-{rd} capture post-processing "
                    f"failed: {e!r}")

    def finalize(self, rd: int, handle: CaptureHandle, tracer=None,
                 sink=None, telemetry=None) -> Optional[Dict[str, Any]]:
        """Parse + classify + bytes + splice + emit for one window."""
        trace_path = find_trace_file(handle.out_dir)
        if trace_path is None:
            if self.logger:
                self.logger.warning(
                    f"profiler: no trace file under {handle.out_dir} — "
                    "capture produced nothing to merge")
            return None
        trace = parse_trace(trace_path)
        byte_table = hlo_collective_bytes(self.hlo_dump_dir)
        summary = summarize_capture(trace, handle.window_s, byte_table)
        summary["round"] = rd
        summary["trace_path"] = trace_path
        if tracer is not None and getattr(tracer, "enabled", False):
            # One splice serves both consumers: the merged timeline AND
            # the per-phase attribution (device ops vs the round's host
            # phase spans, already on the same axis).
            summary["merge"], ops = splice_into_tracer(tracer, trace,
                                                       handle)
            summary["phase_attribution"] = phase_device_attribution(
                tracer.snapshot_events(), rd, ops)
        summary_path = os.path.join(handle.out_dir,
                                    f"device_profile_rd{rd}.json")
        try:
            with open(summary_path, "w") as fh:
                json.dump(summary, fh, indent=1)
            summary["summary_path"] = summary_path
        except OSError:
            pass
        self.captures[rd] = summary
        self.emit_metrics(rd, summary, sink=sink, telemetry=telemetry)
        return summary

    def emit_metrics(self, rd: int, summary: Dict[str, Any], sink=None,
                     telemetry=None) -> Dict[str, float]:
        """The device-truth metric set, through the MetricsSink AND the
        Prometheus gauges (the scrape-file completeness contract —
        every per-round metric rides both)."""
        metrics: Dict[str, float] = {}
        for name in ("device_busy_frac", "collective_frac",
                     "transfer_frac", "collective_bytes_total"):
            if summary.get(name) is not None:
                metrics[name] = summary[name]
        for prim, entry in (summary.get("collectives") or {}).items():
            slug = prim.replace("-", "_")
            metrics[f"collective_count_{slug}"] = entry["count"]
            if entry.get("bytes") is not None:
                metrics[f"collective_bytes_{slug}"] = entry["bytes"]
        if sink is not None:
            for name, value in metrics.items():
                sink.log_metric(name, value, step=rd)
        if telemetry is not None:
            telemetry.set_gauges(**metrics)
        return metrics


def serve_capture(out_dir: str, seconds: float) -> Dict[str, Any]:
    """The serve verb's bounded live-load capture (blocking; the server
    runs it off the event loop): open the window, sleep, close, parse,
    summarize, write the summary next to the trace.  Device events are
    whatever the executor dispatched during the window."""
    seconds = max(0.05, min(float(seconds), MAX_SERVE_CAPTURE_S))
    with capture_window(out_dir, label="serve") as handle:
        time.sleep(seconds)
    trace_path = find_trace_file(out_dir)
    if trace_path is None:
        return {"ok": False, "error": "capture produced no trace file",
                "out_dir": out_dir}
    summary = summarize_capture(parse_trace(trace_path), handle.window_s)
    summary["trace_path"] = trace_path
    path = os.path.join(out_dir, "device_profile_serve.json")
    try:
        with open(path, "w") as fh:
            json.dump(summary, fh, indent=1)
    except OSError:
        pass
    return {"ok": True, "out_dir": out_dir, "summary_path": path,
            **summary}
