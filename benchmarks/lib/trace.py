"""From a profiler trace to device time per program, busy time and gaps.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into plain
dictionaries (one per device plane: its lines, each a list of (name, start
ns, duration ns)); everything after that is arithmetic on those lists, so
the tests drive it with hand-made traces.  On the TPU v5e a device plane is
named ``/device:TPU:<n>``; its line ``XLA Modules`` has one event per
executed program, named ``<jit name>(<fingerprint>)``, and ``XLA Ops`` one
per HLO operation.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> List[Dict]:
    """Device planes of a trace: [{"name", "lines": {line name: [Event]}}]."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OP_LINE):
                continue
            lines[line.name] = [
                (op_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def op_name(event_name: str) -> str:
    """An operation's event carries its whole HLO line (``%fusion.12 =
    bf16[...] fusion(...)``); a program's only its name.  Keep the name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def program_name(event_name: str) -> str:
    """``jit_epoch_scan(123456)`` -> ``jit_epoch_scan``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union_ns(events: Iterable[Event]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total length and the merged intervals of the events' union."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[int]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def clip(events: Iterable[Event], t0: int, t1: int) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce_plane(plane: Dict, t0: int, t1: int,
                 host_spans: Sequence[Tuple[str, int, int]] = ()) -> Dict:
    """One device's share of the window [t0, t1) (trace clock, ns): seconds
    per program, busy seconds (the union of its operations, or of its
    programs where the trace has no operation line), the operations that
    took most time, and the longest idle gaps, each named by the host span
    that covers most of it.  An operation is named ``<program>/<op>``."""
    modules = clip(plane["lines"].get(MODULE_LINE, ()), t0, t1)
    ops = clip(plane["lines"].get(OP_LINE, ()), t0, t1)
    per_program: Dict[str, float] = {}
    for name, _, d in modules:
        key = program_name(name)
        per_program[key] = per_program.get(key, 0.0) + d / 1e9
    busy_ns, merged = union_ns(ops or modules)
    # An operation belongs to the program whose event covers its start
    # (one device runs one program at a time).
    runs = sorted((s, s + d, program_name(n)) for n, s, d in modules)
    run_starts = [r[0] for r in runs]
    per_op: Dict[str, float] = {}
    for name, s, d in ops:
        i = bisect.bisect_right(run_starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            name = f"{runs[i][2]}/{name}"
        per_op[name] = per_op.get(name, 0.0) + d / 1e9
    gaps = []
    edge = t0
    for s, e in merged + [(t1, t1)]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    by_span: Dict[str, float] = {}
    for g0, g1 in gaps:
        best, cover = "unattributed", 0
        for name, s0, s1 in host_spans:
            c = min(g1, s1) - max(g0, s0)
            if c > cover:
                best, cover = name, c
        by_span[best] = by_span.get(best, 0.0) + (g1 - g0) / 1e9
    return {
        "programs": per_program,
        "module_events": [(program_name(n), s, d) for n, s, d in modules],
        "busy_s": busy_ns / 1e9,
        "top_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(by_span.items(), key=lambda kv: -kv[1])[:10],
    }


def reduce_trace(planes: List[Dict], t0: int, t1: int,
                 host_spans: Sequence[Tuple[str, int, int]] = ()) -> Dict:
    """The window's reduction averaged over the device planes."""
    if not planes:
        raise ValueError("the trace has no device plane")
    per = [reduce_plane(p, t0, t1, host_spans) for p in planes]
    programs: Dict[str, float] = {}
    for r in per:
        for k, v in r["programs"].items():
            programs[k] = programs.get(k, 0.0) + v / len(per)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(r["busy_s"] for r in per) / len(per),
        "programs": programs,
        "module_events": per[0]["module_events"],
        "top_ops": per[0]["top_ops"],
        "idle_gaps": per[0]["idle_gaps"],
    }


def first_event_ns(planes: List[Dict], match: str) -> Optional[int]:
    """Start of the first program event whose name contains ``match``."""
    starts = [s for p in planes
              for name, s, _ in p["lines"].get(MODULE_LINE, ())
              if match in name]
    return min(starts) if starts else None


def program_seconds(reduction: Dict, match: Sequence[str],
                    within: Optional[Sequence[Tuple[int, int]]] = None
                    ) -> float:
    """Device seconds of the programs whose name contains any of
    ``match``; with ``within``, only of their runs that start inside one of
    those intervals (read off the first device plane)."""
    if within is None:
        return sum(v for k, v in reduction["programs"].items()
                   if any(m in k for m in match))
    return sum(d for k, s, d in reduction["module_events"]
               if any(m in k for m in match)
               and any(a <= s < b for a, b in within)) / 1e9
