"""The program's span record, read from outside the program.

The program keeps one span tree per round (``telemetry/spans.py``): every
span has an ``id``, its ``parent`` and its ``round``, and is exported to
``trace.json`` with ``ts`` in microseconds after ``otherData.perf_origin``
(a ``perf_counter`` reading).  While a profiler trace is open every span is
also a ``TraceAnnotation`` of the same name, so the host plane of the
``.xplane.pb`` holds the same spans on the device planes' clock.  This file
loads both, computes self time, gives every idle instant of the device to
the deepest span that was open on the round's thread, sums device time per
``jax.named_scope``, and registers the readers the per-layer metrics name.

Nothing here imports the program: the record is a file, and self time is
recomputed here so that the yardstick does not move with the program's own
``spans.self_seconds`` (a test holds the two to the same hand-made tree).
"""

from __future__ import annotations

import bisect
import fnmatch
import glob
import json
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import readers as readers_lib
from . import trace as trace_lib
from . import window as window_lib

UNATTRIBUTED = "unattributed"
HOST_PLANE = re.compile(r"^/host:")
# Where a trace says which code made an operation.  On the v5e an ``XLA
# Ops`` event is named by its HLO line WITHOUT the metadata and its stats
# hold device offsets only (looked at by hand, PERF.md "Spans and
# counters"); the ``op_name`` of every instruction is in the compiled
# modules the profiler stores beside the events, as an ``Hlo Proto`` stat
# on each entry of the ``/host:metadata`` plane.
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


# -- the record ---------------------------------------------------------------

def load_record(path: str) -> Dict:
    """``trace.json`` -> {"perf_origin", "spans"}: each span a dict with
    ``name, id, parent, round, tid, t0, t1`` (``perf_counter`` seconds) and
    ``args`` (its counters).  Events without an id (a program older than
    the span tree, spliced device tracks) are left out."""
    with open(path) as fh:
        doc = json.load(fh)
    origin = float(doc.get("otherData", {}).get("perf_origin", 0.0))
    spans = []
    for e in doc["traceEvents"]:
        args = e.get("args") or {}
        if e.get("ph") != "X" or "id" not in args:
            continue
        t0 = origin + e["ts"] / 1e6
        spans.append({"name": e["name"], "id": args["id"],
                      "parent": args.get("parent"),
                      "round": args.get("round"), "tid": e["tid"],
                      "t0": t0, "t1": t0 + e["dur"] / 1e6, "args": args})
    return {"perf_origin": origin, "spans": spans}


def self_seconds(spans: Sequence[Dict]) -> Dict[int, float]:
    """Self time per span id: its length minus the union of its children's
    intervals on the same thread, clipped to the span."""
    kids: Dict[Tuple, List[Dict]] = {}
    for s in spans:
        kids.setdefault((s["parent"], s["tid"]), []).append(s)
    out = {}
    for s in spans:
        covered, edge = 0.0, s["t0"]
        for c in sorted(kids.get((s["id"], s["tid"]), ()),
                        key=lambda c: c["t0"]):
            a, b = max(c["t0"], edge), min(c["t1"], s["t1"])
            if b > a:
                covered += b - a
                edge = b
        out[s["id"]] = (s["t1"] - s["t0"]) - covered
    return out


def matches(name: str, patterns: Iterable[str]) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def has_ancestor(span: Dict, by_id: Dict[int, Dict], name: str) -> bool:
    parent = by_id.get(span["parent"])
    while parent is not None:
        if parent["name"] == name:
            return True
        parent = by_id.get(parent["parent"])
    return False


def select(spans: Sequence[Dict], names: Sequence[str],
           rounds: Optional[Iterable[int]] = None,
           under: Optional[str] = None) -> List[Dict]:
    """The spans whose name matches one of ``names`` (``ckpt/*`` is a
    pattern), of the given rounds, below an ancestor named ``under``."""
    keep = None if rounds is None else set(rounds)
    by_id = {s["id"]: s for s in spans}
    return [s for s in spans
            if matches(s["name"], names)
            and (keep is None or s["round"] in keep)
            and (under is None or has_ancestor(s, by_id, under))]


# -- idle time, by the deepest open span --------------------------------------

def deepest_timeline(spans: Sequence[Dict], tid) -> List[Tuple[float, float,
                                                                str]]:
    """The thread's time cut into pieces, each named by the deepest span
    open over it: [(t0, t1, name)], in order; time with no span open is
    left out."""
    mine = sorted((s for s in spans if s["tid"] == tid),
                  key=lambda s: (s["t0"], -s["t1"]))
    out: List[Tuple[float, float, str]] = []
    stack: List[Dict] = []
    edge = 0.0

    def close_until(t: float) -> float:
        """Pop every open span that ends by ``t``, each giving its name
        to what is left of it."""
        at = edge
        while stack and stack[-1]["t1"] <= t:
            top = stack.pop()
            if top["t1"] > at:
                out.append((at, top["t1"], top["name"]))
                at = top["t1"]
        return at

    for s in mine:
        edge = close_until(s["t0"])
        if stack and s["t0"] > edge:
            out.append((edge, s["t0"], stack[-1]["name"]))
        edge = max(edge, s["t0"]) if stack else s["t0"]
        stack.append(s)
    close_until(float("inf"))
    return out


def idle_by_span(gaps: Sequence[Tuple[float, float]],
                 timeline: Sequence[Tuple[float, float, str]]
                 ) -> Dict[str, float]:
    """Every idle instant goes to the deepest span open at that instant;
    idle time with no span open is ``unattributed``.  ``gaps`` and
    ``timeline`` share a clock and a unit; both are sorted."""
    out: Dict[str, float] = {}
    starts = [p[0] for p in timeline]
    for g0, g1 in gaps:
        left = g1 - g0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(timeline) and timeline[i][0] < g1:
            a, b = max(g0, timeline[i][0]), min(g1, timeline[i][1])
            if b > a:
                name = timeline[i][2]
                out[name] = out.get(name, 0.0) + (b - a)
                left -= b - a
            i += 1
        if left > 0:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + left
    return out


def device_gaps(ops: Sequence[trace_lib.Event], t0: int, t1: int
                ) -> List[Tuple[int, int]]:
    """The intervals of [t0, t1) in which no operation ran."""
    _, merged = trace_lib.union_ns(trace_lib.clip(ops, t0, t1))
    gaps, edge = [], t0
    for s, e in merged + [(t1, t1)]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    return gaps


# -- the profiler's own trace -------------------------------------------------

def scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of ``scopes`` on an HLO ``op_name`` path
    (``jit(epoch_scan)/while/body/pool_gather/gather`` -> ``pool_gather``)."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return None


def load_trace_extras(path: str, span_names: Iterable[str]) -> Dict:
    """What ``lib/trace.load_xplane`` leaves behind: the host plane's
    annotations named like a program span, [(name, start ns, duration ns,
    thread)], and the compiled programs' ``op_name`` tables (``hlo_op_names``)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as fh:
        xspace = fh.read()
    names = set(span_names)
    host = []
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    host.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns), line.name))
    return {"host": host, "hlo": hlo_op_names(xspace)}


# The protobuf wire format, as far as the trace's own HLO needs it (the
# installation has no compiled xplane or hlo message classes outside
# tensorflow).  Field numbers, from xplane.proto and hlo.proto:
# XSpace.planes=1; XPlane.name=2, .event_metadata=4 (map: key=1, value=2),
# .stat_metadata=5 (map); XEventMetadata.name=2, .stats=5;
# XStatMetadata.name=2; XStat.metadata_id=1, .bytes_value=6;
# HloProto.hlo_module=1; HloModuleProto.computations=3;
# HloComputationProto.instructions=2; HloInstructionProto.name=1,
# .metadata=7; OpMetadata.op_name=2.

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        c = buf[i]
        i += 1
        value |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return value, i


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, the
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in a trace file")


def _all(buf, number: int):
    return (v for f, v in _fields(buf) if f == number)


def _get(buf, number: int, default=b""):
    return next(_all(buf, number), default)


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def hlo_op_names(xspace: bytes) -> Dict[str, List[Dict[str, str]]]:
    """Program name -> one {instruction name: op_name} per compiled module
    of that name (``jit_epoch_scan`` has one per shape bucket), read from
    the serialized trace's metadata plane.  A trace without one gives {}."""
    out: Dict[str, List[Dict[str, str]]] = {}
    for plane in _all(memoryview(xspace), 1):
        if _text(_get(plane, 2)) != METADATA_PLANE:
            continue
        stat_names = {_get(e, 1): _text(_get(_get(e, 2), 2))
                      for e in _all(plane, 5)}
        for entry in _all(plane, 4):
            meta = _get(entry, 2)
            for stat in _all(meta, 5):
                if stat_names.get(_get(stat, 1)) != HLO_STAT:
                    continue
                module = _get(_get(stat, 6), 1)
                table = {_text(_get(ins, 1)): _text(_get(_get(ins, 7), 2))
                         for comp in _all(module, 3)
                         for ins in _all(comp, 2)}
                out.setdefault(trace_lib.program_name(
                    _text(_get(meta, 2))), []).append(table)
    return out


def name_ops(modules: Sequence[trace_lib.Event],
             ops: Sequence[trace_lib.Event],
             hlo: Dict[str, List[Dict[str, str]]]
             ) -> List[Tuple[str, int, int, str]]:
    """Each operation event with the ``op_name`` its instruction carries in
    its program's compiled module: [(op_name, start ns, duration ns,
    ``<program>/<instruction>``)], ``""`` for an ``op_name`` the trace has
    no module for.  An operation belongs to
    the program run that covers its start; of several modules of one name,
    a compiled variant (one name and fingerprint) takes the one that knows
    most of the instructions seen in its runs."""
    runs = sorted((s, s + d, n) for n, s, d in modules)
    starts = [r[0] for r in runs]
    placed = []
    seen: Dict[str, set] = {}
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        variant = runs[i][2] if i >= 0 and s < runs[i][1] else None
        placed.append((variant, name, s, d))
        seen.setdefault(variant, set()).add(name)
    tables = {}
    for variant, names in seen.items():
        found = hlo.get(trace_lib.program_name(variant or ""), ())
        tables[variant] = max(
            found, key=lambda t: sum(n in t for n in names), default={})
    return [(tables[v].get(name, ""), s, d,
             f"{trace_lib.program_name(v)}/{name}" if v else name)
            for v, name, s, d in placed]


def scope_seconds_of(ops: Sequence[Tuple], t0: int, t1: int,
                     scopes: Sequence[str]) -> Dict[str, float]:
    """Device seconds per named scope inside [t0, t1), from ``name_ops``'
    events: the union of the scope's operations' intervals (an operation
    inside a scoped ``call`` or ``conditional`` event is not counted
    twice)."""
    per: Dict[str, List[trace_lib.Event]] = {}
    for path, s, d, *_ in ops:
        scope = scope_of(path, scopes)
        if scope is not None:
            per.setdefault(scope, []).extend(
                trace_lib.clip([(path, s, d)], t0, t1))
    return {scope: trace_lib.union_ns(evs)[0] / 1e9
            for scope, evs in per.items()}


def paired(spans: Sequence[Dict], host: Sequence[Tuple], to_ns,
           within_ns: int = 50_000_000) -> List[Tuple[int, int, int, int]]:
    """Each span beside the annotation the profiler recorded for it:
    [(span start, span end, annotation start, annotation end)] in ns, the
    span's through ``to_ns`` (its ``perf_counter`` stamps tied to the
    trace by an anchor).  The partner is the nearest annotation of the same
    name; one further than ``within_ns`` away is none (the span lies
    outside the traced window)."""
    by_name: Dict[str, List[Tuple[int, int]]] = {}
    for name, start, dur, _ in host:
        by_name.setdefault(name, []).append((start, start + dur))
    for anns in by_name.values():
        anns.sort()
    out = []
    for s in spans:
        anns = by_name.get(s["name"])
        if not anns:
            continue
        a, b = to_ns(s["t0"]), to_ns(s["t1"])
        i = bisect.bisect_left(anns, (a, a))
        near = min(anns[max(0, i - 1):i + 1], key=lambda x: abs(x[0] - a))
        if abs(near[0] - a) <= within_ns:
            out.append((a, b, near[0], near[1]))
    return out


def clock_disagreement(pairs: Sequence[Tuple[int, int, int, int]]
                       ) -> Optional[Dict]:
    """The two routes to the trace's clock, compared span by span (``paired``):
    the largest gap between a span's end points and its annotation's."""
    if not pairs:
        return None
    worst = max(max(abs(c - a), abs(d - b)) for a, b, c, d in pairs)
    return {"spans_compared": len(pairs), "largest_s": worst / 1e9}


# -- the record laid over the traced round ------------------------------------

def load_span_record(trace_dir: str) -> Optional[Dict]:
    """The program's span record (``trace.json`` under the run's log
    directory, beside ``trace_dir``); None where its recorder was off."""
    logs = os.path.join(os.path.dirname(trace_dir), "logs")
    files = sorted(glob.glob(os.path.join(logs, "**", "trace*.json"),
                             recursive=True))
    return load_record(files[-1]) if files else None


def read_spans(ctl, red: Dict, planes: List[Dict],
               scopes: Sequence[str]) -> Dict:
    """Lay the program's span record over the traced round's reduction
    ``red`` (in place): the record itself, every idle instant of the device
    given to the deepest span open on the round's thread (``idle_gaps`` then
    shows that attribution), and device seconds per named scope from the
    operations' ``op_name``.  ``ctl`` holds the runner's ``trace_dir``, its
    ``trace_anchor`` and ``trace_span`` (``perf_counter`` seconds) and its
    ``pauses``.  Returns what the runner prints beside the metrics: the host
    self time of the traced round, the two clocks' disagreement and the
    tree's checks; nothing where the recorder was off."""
    record = load_span_record(ctl.trace_dir)
    if record is None:
        return {}
    spans = record["spans"]
    anchor = trace_lib.first_event_ns(planes, "bench_anchor")
    by_anchor = anchor - int(ctl.trace_anchor * 1e9)    # perf_counter -> trace
    extras = load_trace_extras(
        trace_lib.find_xplane(ctl.trace_dir), {s["name"] for s in spans})
    pairs = paired(spans, extras["host"], lambda t: int(t * 1e9) + by_anchor)
    # Where the host plane has the annotations, tie the record to the
    # trace's clock by them (the median shift of the paired span starts),
    # not by the runner's anchor program.
    offset = by_anchor + (int(statistics.median(
        c - a for a, _, c, _ in pairs)) if pairs else 0)
    t0 = int(ctl.trace_span[0] * 1e9) + by_anchor
    t1 = int(ctl.trace_span[1] * 1e9) + by_anchor
    on_trace = [{**s, "t0": int(s["t0"] * 1e9) + offset,
                 "t1": int(s["t1"] * 1e9) + offset} for s in spans]
    rounds = [s for s in on_trace if s["name"] == "round"]
    if not rounds:
        return {}
    timeline = deepest_timeline(on_trace, rounds[0]["tid"])
    first = planes[0]["lines"]
    ops = first.get(trace_lib.OP_LINE) or first.get(trace_lib.MODULE_LINE, ())
    idle = {k: v / 1e9 for k, v in idle_by_span(
        device_gaps(ops, t0, t1), timeline).items()}
    red["spans_record"] = record
    red["idle_by_span"] = idle
    red["idle_gaps"] = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    out: Dict = {"clock": clock_disagreement(pairs)}
    if extras["hlo"]:
        named = name_ops(first.get(trace_lib.MODULE_LINE, ()),
                         first.get(trace_lib.OP_LINE, ()), extras["hlo"])
        red["scope_s"] = scope_seconds_of(named, t0, t1, scopes)
        out["ops_named"] = [sum(1 for n in named if n[0]), len(named),
                            len(extras["hlo"])]

    # The traced round: the one whose ``round`` span shares most of the
    # window (its end and the trace's stop lie a dispatch apart).
    traced = max(rounds, key=lambda s: min(s["t1"], t1) - max(s["t0"], t0))
    selfs = self_seconds(spans)
    by_id = {s["id"]: s for s in spans}
    total_idle = sum(idle.values())
    # A phase's span is named as its sink metric, less the ``rd_``.
    not_deeper = {m[len("rd_"):] for m in window_lib.PHASE_METRICS} | {
        "round", "experiment", UNATTRIBUTED}
    shallow = sum(v for k, v in idle.items() if k in not_deeper)
    host_self: Dict[str, float] = {}
    for s in spans:
        if s["round"] != traced["round"]:
            continue
        own = selfs[s["id"]]
        if s["name"] == "round_epilogue":
            # The runner stops the trace at a boundary, inside an epilogue:
            # that pause is the runner's, not the program's.
            own -= sum(max(0.0, min(p1, s["t1"]) - max(p0, s["t0"]))
                       for p0, p1 in ctl.pauses)
        host_self[s["name"]] = host_self.get(s["name"], 0.0) + own
    out.update({
        "traced_round": traced["round"],
        "round_span_s": (traced["t1"] - traced["t0"]) / 1e9,
        "subtree_self_sum_s": sum(
            selfs[s["id"]] for s in spans
            if s["round"] == traced["round"]
            and s["tid"] == by_id[traced["id"]]["tid"]
            and (s["id"] == traced["id"]
                 or has_ancestor(s, by_id, "round"))),
        "idle_total_s": total_idle,
        "idle_deeper_than_phase_share": (
            1.0 - shallow / total_idle if total_idle else None),
        "idle_unattributed_share": (
            idle.get(UNATTRIBUTED, 0.0) / total_idle if total_idle else None),
        "host_self": sorted(host_self.items(), key=lambda kv: -kv[1])[:10]})
    return out


# -- the readers --------------------------------------------------------------

def _record(ctx: Dict) -> Optional[Dict]:
    red = ctx.get("trace") or {}
    return ctx.get("spans") or red.get("spans_record")


@readers_lib.reader("span_seconds")
def span_seconds(ctx: Dict, names, under=None) -> Optional[float]:
    """Seconds per round of the spans named so, over the window's rounds."""
    rec = _record(ctx)
    if rec is None or not ctx["rounds"]:
        return None
    hit = select(rec["spans"], names,
                 [r["round"] for r in ctx["rounds"]], under)
    if not hit:
        return None
    return sum(s["t1"] - s["t0"] for s in hit) / len(ctx["rounds"])


@readers_lib.reader("span_idle")
def span_idle(ctx: Dict, names) -> Optional[float]:
    """Device-idle seconds of the traced round whose deepest open span is
    one of ``names``."""
    idle = (ctx.get("trace") or {}).get("idle_by_span")
    if idle is None:
        return None
    hit = [v for k, v in idle.items() if matches(k, names)]
    return sum(hit) if hit else None


@readers_lib.reader("span_ratio")
def span_ratio(ctx: Dict, span, num, den) -> Optional[float]:
    """100 x the sum of counter ``num`` over the sum of counter ``den`` of
    the spans named ``span`` in the window's rounds."""
    rec = _record(ctx)
    if rec is None:
        return None
    hit = select(rec["spans"], [span],
                 [r["round"] for r in ctx["rounds"]])
    total = sum(float(s["args"].get(den, 0)) for s in hit)
    if total <= 0:
        return None
    return 100.0 * sum(float(s["args"].get(num, 0)) for s in hit) / total


@readers_lib.reader("scope_seconds")
def scope_seconds(ctx: Dict, scope) -> Optional[float]:
    """Device seconds of the traced round under the named scope, all
    programs."""
    scopes = (ctx.get("trace") or {}).get("scope_s")
    if not scopes:
        return None
    return scopes.get(scope)
