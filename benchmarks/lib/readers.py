"""The per-layer metric readers.  A metric's data file names one of these
and its parameters; a reader that finds nothing to read returns None and the
metric is left out of the line.  A reader may live in any file of ``lib/``:
the metric's data file names it (``"module": "spans"``; default this file),
``reader_for`` imports it, and importing it registers its readers here."""

from __future__ import annotations

from typing import Callable, Dict, Optional

import importlib
import re

from . import peaks as peaks_lib
from . import trace as trace_lib

READERS: Dict[str, Callable] = {}


def reader(name: str):
    def deco(fn):
        READERS[name] = fn
        return fn
    return deco


def reader_for(spec: Dict) -> Callable:
    """The reader a metric's data file names, its module imported first."""
    module = spec.get("module", "readers")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", module):
        raise ValueError(f"metric {spec.get('name')!r}: module {module!r} "
                         f"is not the name of a file of lib/")
    importlib.import_module(f"{__package__}.{module}")
    try:
        return READERS[spec["reader"]]
    except KeyError:
        raise KeyError(f"metric {spec.get('name')!r}: lib/{module}.py "
                       f"registers no reader {spec['reader']!r}") from None


@reader("sink_phase")
def sink_phase(ctx: Dict, metric: str) -> Optional[float]:
    """Seconds per round of one phase the program reported."""
    rounds = ctx["rounds"]
    vals = [r["phases"][metric] for r in rounds if metric in r["phases"]]
    if not vals:
        return None
    return sum(vals) / len(rounds)


@reader("round_minus")
def round_minus(ctx: Dict, minus) -> Optional[float]:
    """The round's seconds that none of the named phases covers."""
    rounds = ctx["rounds"]
    if not rounds:
        return None
    total = sum(r["seconds"] for r in rounds)
    covered = sum(r["phases"].get(m, 0.0) for r in rounds for m in minus)
    return (total - covered) / len(rounds)


@reader("counter")
def counter(ctx: Dict, counter: str) -> Optional[float]:
    return ctx["counters"].get(counter)


@reader("flops_share")
def flops_share(ctx: Dict, kinds) -> Optional[float]:
    """Required FLOPs of the traced rounds over the traced seconds at the
    chip's peak, in percent."""
    red = ctx.get("trace")
    if red is None:
        return None
    need = sum(ctx["work"][k]["flops"] for k in kinds if k in ctx["work"])
    if need <= 0:
        return None
    return 100.0 * need / (red["window_s"] * ctx["peaks"]["flops_bf16"]
                           * ctx["chips"])


@reader("trace_program")
def trace_program(ctx: Dict, match, kinds, phase=None) -> Optional[float]:
    """Share of the roofline of the programs whose name contains ``match``
    (with ``phase``, of their runs inside that phase's host spans): the
    least time for the named kinds of work over those programs' device time,
    in percent."""
    red = ctx.get("trace")
    if red is None:
        return None
    within = None
    if phase is not None:
        within = [(a, b) for name, a, b in red.get("spans", ())
                  if name == phase]
    seconds = trace_lib.program_seconds(red, match, within)
    work = {"flops": 0.0, "bytes": 0.0}
    for k in kinds:
        for f in work:
            work[f] += ctx["work"].get(k, {}).get(f, 0.0)
    if seconds <= 0 or work["flops"] <= 0:
        return None
    least, bound = peaks_lib.least_seconds(work, ctx["peaks"])
    ctx.setdefault("bounds", {})[",".join(match)] = bound
    return 100.0 * least / seconds


@reader("trace_idle")
def trace_idle(ctx: Dict) -> Optional[float]:
    red = ctx.get("trace")
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
