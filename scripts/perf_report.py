#!/usr/bin/env python
"""perf_report: the per-phase performance trend table + regression gate.

Reads the bench trajectory (``BENCH_r*.json`` — the harness records of
every bench round — plus, optionally, a fresh ``bench_evidence.json``)
and renders one table per tracked metric across rounds, then exits
NONZERO when the latest capture regressed a pinned metric by more than
the threshold against the best-known value in the series:

  * ``al_round_*``   warm round seconds must not exceed best-known
                     x (1 + threshold) — the end-to-end number a
                     protocol run amortizes to;
  * ``*_train``      images/sec/chip must not fall below best-known
                     x (1 - threshold) — the step-time ceiling.

The gate turns ROADMAP item 5's hardware windows into a machine-checked
verdict: ``python bench.py --assert_no_regression`` (bench's opt-in
wiring) fails CI instead of queueing another by-hand Perfetto read.

Exit codes: 0 no pinned regression / 1 regression(s) / 2 no series
files at all / 3 a ``--current`` file was given but carried no usable
phase data (the gate was asked to judge a run that produced no
evidence — neither "ok" nor a history-vs-itself verdict would be
honest).

The trajectory is hostile input by construction: BENCH_r01 has an
empty tail (no backend), r04's tail truncates a phase fragment past
parseability, r05 carries a parsed compact line (a tail that is a
traceback, and one that died rc=124 mid-line, have occurred too), and
full evidence files rename keys across rounds
(``ips_warm`` -> ``warm_memmap_ips``, ``round_sec_warm`` -> the compact
``warm_s``).  Every shape must degrade to a skip-with-note or an alias
hit — never a KeyError on the trajectory.  Device-truth fields
(``device_busy_frac``, ``collective_frac``, ``collective_bytes_total``
— telemetry/profiler.py) ride the table whenever a capture carried
them.

Stdlib only; no jax import (this runs on hosts that could never
initialize the bench backend).

    python scripts/perf_report.py                    # BENCH_r*.json
    python scripts/perf_report.py A.json B.json      # explicit series
    python scripts/perf_report.py --current bench_evidence.json
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Pinned regression contract (the gate's whole surface, so a reviewer
# can see exactly what trips CI): metric, phase-match, direction.
REGRESSION_THRESHOLD = 0.10
GATED_METRICS = (
    # (metric, phase predicate, "lower"|"higher" is better)
    ("warm_s", lambda name: name.startswith("al_round"), "lower"),
    ("ips_per_chip", lambda name: name.endswith("_train"), "higher"),
    # The disk tier (ISSUE 16): the demand-paged backend's in-loop
    # train rate — a pager regression (cache thrash, stall growth)
    # lands here even when the in-memory phases stay flat.
    ("ips_per_chip", lambda name: name == "disk_pool_feed", "higher"),
)

# Alias chains, newest spelling first — schema drift across bench
# rounds resolves here instead of KeyError-ing on the trajectory.
_ALIASES = {
    "ips_per_chip": ("ips_per_chip",),
    "mfu": ("mfu",),
    "warm_s": ("warm_s", "round_sec_warm"),
    "cold_s": ("cold_s", "round_sec_cold"),
    "warm_ips": ("warm_memmap_ips", "warm_ips", "ips_warm"),
    "acc": ("test_accuracy_rd1", "acc"),
    "overlap_frac": ("overlap_frac", "overlap"),
    "device_busy_frac": ("device_busy_frac",),
    "collective_frac": ("collective_frac",),
    "collective_bytes_total": ("collective_bytes_total",),
}


def _num(v) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v) if math.isfinite(v) else None


def _normalize_phase(entry: Dict[str, Any]) -> Dict[str, Any]:
    """One phase record (full-evidence OR compact-line shape) -> the
    canonical metric dict.  The compact line's ``ips`` is already
    per-chip (bench._compact_line writes ips_per_chip there); the full
    evidence's ``ips`` is the TOTAL rate — disambiguated by the
    presence of ``n_chips``, which only full entries carry."""
    out: Dict[str, Any] = {}
    for canon, aliases in _ALIASES.items():
        for alias in aliases:
            val = _num(entry.get(alias))
            if val is not None:
                out[canon] = val
                break
    if "ips_per_chip" not in out:
        ips = _num(entry.get("ips"))
        if ips is not None:
            if "n_chips" in entry:
                n = _num(entry.get("n_chips")) or 1.0
                out["ips_per_chip"] = ips / max(n, 1.0)
            else:
                out["ips_per_chip"] = ips
    if entry.get("cached"):
        out["cached"] = True
    return out


def _phases_from_dict(obj: Dict[str, Any]) -> Optional[Dict[str, Dict]]:
    """Phase records out of any dict that carries them: a full evidence
    / parsed compact line ({"phases": {name: {...}}}), or a bare child
    phase line ({"phase": name, ...})."""
    phases = obj.get("phases")
    if isinstance(phases, dict) and phases:
        out = {}
        for name, entry in phases.items():
            if isinstance(entry, dict):
                out[name] = _normalize_phase(entry)
            elif _num(entry) is not None:
                # The deepest compact truncation stage: {name: ips}.
                out[name] = {"ips_per_chip": _num(entry)}
        return out or None
    if isinstance(obj.get("phase"), str):
        return {obj["phase"]: _normalize_phase(obj)}
    return None


def _phases_from_tail(tail: str) -> Optional[Dict[str, Dict]]:
    """Salvage phase records from a stdout tail: the LAST parseable
    JSON line carrying phases wins (the compact-line contract); child
    phase lines merge as a fallback."""
    merged: Dict[str, Dict] = {}
    for line in reversed((tail or "").strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(obj, dict):
            continue
        found = _phases_from_dict(obj)
        if found and "phases" in obj:
            return found           # one full line beats any fragments
        if found:
            for name, entry in found.items():
                merged.setdefault(name, entry)
    return merged or None


def extract_phases(obj: Any) -> Tuple[Optional[Dict[str, Dict]], str]:
    """(phases, note) from ANY of the trajectory's file shapes.  None
    phases = nothing salvageable; the note says why (rendered in the
    table header so a skipped round is visible, not silent)."""
    if not isinstance(obj, dict):
        return None, "not a JSON object"
    direct = _phases_from_dict(obj)
    if direct:
        return direct, "ok"
    parsed = obj.get("parsed")
    if isinstance(parsed, dict):
        found = _phases_from_dict(parsed)
        if found:
            return found, "ok (parsed line)"
    tail = obj.get("tail")
    if isinstance(tail, str) and tail.strip():
        found = _phases_from_tail(tail)
        if found:
            return found, "ok (salvaged from tail)"
        low = tail.lower()
        if "traceback" in low or "error" in low:
            return None, "no data (run died: traceback in tail)"
        return None, "no data (tail holds no parseable result)"
    if obj.get("rc") not in (0, None):
        return None, f"no data (rc={obj.get('rc')})"
    return None, "no data (empty record)"


def load_series(paths: List[str]) -> List[Dict[str, Any]]:
    out = []
    for path in paths:
        label = re.sub(r"^BENCH_|\.json$", "",
                       os.path.basename(path)) or os.path.basename(path)
        try:
            with open(path) as fh:
                obj = json.load(fh)
        except (OSError, ValueError) as e:
            out.append({"path": path, "label": label, "phases": None,
                        "note": f"unreadable ({e.__class__.__name__})"})
            continue
        phases, note = extract_phases(obj)
        out.append({"path": path, "label": label, "phases": phases,
                    "note": note})
    return out


# ---------------------------------------------------------------------------
# Rendering.
# ---------------------------------------------------------------------------

# metric -> (row suffix, format)
_ROW_METRICS = (
    ("ips_per_chip", "ips/chip", "{:,.1f}"),
    ("mfu", "mfu", "{:.3f}"),
    ("warm_s", "warm_s", "{:,.2f}"),
    ("cold_s", "cold_s", "{:,.2f}"),
    ("warm_ips", "warm_ips", "{:,.1f}"),
    ("overlap_frac", "overlap", "{:.3f}"),
    ("device_busy_frac", "dev_busy", "{:.3f}"),
    ("collective_frac", "coll_frac", "{:.3f}"),
    ("collective_bytes_total", "coll_bytes", "{:,.0f}"),
)


def _phase_order(series) -> List[str]:
    order: List[str] = []
    for entry in series:
        for name in (entry["phases"] or {}):
            if name not in order:
                order.append(name)
    return order


def render_table(series) -> str:
    lines = ["perf trend (columns = bench rounds; '-' = not captured)"]
    for entry in series:
        if entry["phases"] is None:
            lines.append(f"  [{entry['label']}] skipped: {entry['note']}")
    with_data = [e for e in series if e["phases"]]
    if not with_data:
        lines.append("  (no round in the series carried phase data)")
        return "\n".join(lines)
    labels = [e["label"] for e in with_data]
    width = max(10, max(len(lb) for lb in labels) + 2)
    name_w = 40
    header = " " * name_w + "".join(f"{lb:>{width}}" for lb in labels)
    lines.append(header)
    for phase in _phase_order(with_data):
        for metric, suffix, fmt in _ROW_METRICS:
            vals = [(e["phases"].get(phase) or {}).get(metric)
                    for e in with_data]
            if all(v is None for v in vals):
                continue
            row = f"{phase} {suffix}"
            cells = "".join(
                f"{fmt.format(v) if v is not None else '-':>{width}}"
                for v in vals)
            lines.append(f"{row:<{name_w}}{cells}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The regression gate.
# ---------------------------------------------------------------------------

def check_regressions(series, threshold: float = REGRESSION_THRESHOLD
                      ) -> List[str]:
    """Latest capture vs best-known across the PRIOR rounds, per pinned
    metric.  A phase with no prior data cannot regress (first capture
    IS the baseline); a latest round missing the phase is not a
    regression either (a run that could not capture a phase must not
    fail the gate — absence already shows in the table)."""
    with_data = [e for e in series if e["phases"]]
    if len(with_data) < 2:
        return []
    latest = with_data[-1]
    prior = with_data[:-1]
    problems = []
    for metric, match, direction in GATED_METRICS:
        for phase, entry in latest["phases"].items():
            if not match(phase):
                continue
            value = entry.get(metric)
            if value is None:
                continue
            best = None
            for e in prior:
                v = (e["phases"].get(phase) or {}).get(metric)
                if v is None:
                    continue
                best = v if best is None else (
                    min(best, v) if direction == "lower" else max(best, v))
            if best is None or best <= 0:
                continue
            if direction == "lower" and value > best * (1 + threshold):
                problems.append(
                    f"{phase} {metric}: {value:,.2f} vs best-known "
                    f"{best:,.2f} (>{threshold:.0%} slower) "
                    f"[latest={latest['label']}]")
            if direction == "higher" and value < best * (1 - threshold):
                problems.append(
                    f"{phase} {metric}: {value:,.2f} vs best-known "
                    f"{best:,.2f} (>{threshold:.0%} below) "
                    f"[latest={latest['label']}]")
    return problems


def default_series_paths() -> List[str]:
    return sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/perf_report.py",
        description="Render the bench perf trend table and gate on "
                    "pinned regressions")
    ap.add_argument("files", nargs="*",
                    help="series files in chronological order "
                         "(default: BENCH_r*.json in the repo root)")
    ap.add_argument("--current", type=str, default=None,
                    help="a fresh evidence/compact JSON appended as the "
                         "latest point (what bench --assert_no_regression "
                         "passes)")
    ap.add_argument("--threshold", type=float,
                    default=REGRESSION_THRESHOLD,
                    help="regression tolerance vs best-known "
                         "(default 0.10)")
    args = ap.parse_args(argv)
    paths = list(args.files) or default_series_paths()
    if args.current:
        paths.append(args.current)
    if not paths:
        print("perf_report: no series files found", file=sys.stderr)
        return 2
    series = load_series(paths)
    print(render_table(series))
    if args.current and series[-1]["phases"] is None:
        # The gate was asked to judge THIS run and this run produced no
        # usable evidence: neither a silent "ok" (nothing was checked)
        # nor a regression verdict against history-vs-itself is honest
        # — a distinct exit code, loudly.
        print("perf_report: NO-EVIDENCE — the --current file carried no "
              f"usable phase data ({series[-1]['note']}); the "
              "regression gate did not run", file=sys.stderr)
        return 3
    problems = check_regressions(series, threshold=args.threshold)
    for p in problems:
        print(f"perf_report: REGRESSION {p}", file=sys.stderr)
    if problems:
        return 1
    with_data = sum(1 for e in series if e["phases"])
    print(f"perf_report: ok ({with_data}/{len(series)} rounds carried "
          f"data; no pinned regression past "
          f"{args.threshold:.0%})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
