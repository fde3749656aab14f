"""Readers of what the host does around the program's own code: the process
counter the program's round spans carry (collector seconds) and the
device's idle time that no bookkeeping span names.

The program records it only while its span recorder is on: a round span,
its epilogue and the round's snapshot carry ``gc_s`` as a delta over their
interval, and together they cover the round end to end.  A record without
the counter (a program older than it) gives nothing, and the line leaves
the metric out.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from . import readers as readers_lib
from . import spans as spans_lib

# The spans that carry the counters and lie one after another over a round.
ROUND_SPANS = ("ckpt/round_snapshot", "round", "round_epilogue")
# Where idle time has no name of its own: the round's and the epilogue's
# self time, the experiment's between rounds, and time with no span open.
UNNAMED = ("round", "round_epilogue", "experiment", spans_lib.UNATTRIBUTED)


@readers_lib.reader("span_counter")
def span_counter(ctx: Dict, counter: str,
                 names: Sequence[str] = ROUND_SPANS) -> Optional[float]:
    """The sum of counter ``counter`` over the spans named ``names`` of the
    window's rounds, per round.  ``0.0`` where those spans carry the
    counter and it is 0; None without a record or where no span carries
    it."""
    rec = spans_lib._record(ctx)
    if rec is None or not ctx["rounds"]:
        return None
    hit = [s for s in spans_lib.select(
        rec["spans"], names, [r["round"] for r in ctx["rounds"]])
        if counter in s["args"]]
    if not hit:
        return None
    return sum(float(s["args"][counter]) for s in hit) / len(ctx["rounds"])


@readers_lib.reader("idle_unnamed")
def idle_unnamed(ctx: Dict, names: Sequence[str] = UNNAMED
                 ) -> Optional[float]:
    """Device-idle seconds of the traced round whose deepest open span is
    one of ``names``: ``0.0`` where the trace is there and none of them
    holds idle time, None without a trace."""
    idle = (ctx.get("trace") or {}).get("idle_by_span")
    if idle is None:
        return None
    return float(sum(v for k, v in idle.items() if k in names))
