"""The recording sink and the window arithmetic.

The program reports every phase of a round through its ``MetricsSink``; the
benchmark injects this one, which keeps each event with a host timestamp and
calls back at every round boundary.  A round's boundary is the event
``ROUND_END_METRIC``, the first of the driver's end-of-round gauges: what
the driver does after it (journal, report, budget refresh) falls into the
next round, so every second of a window belongs to exactly one round.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

ROUND_END_METRIC = "jit_cache_miss_delta"
PHASE_METRICS = ("rd_query_time", "rd_init_network_weights_time",
                 "rd_train_time", "rd_load_best_ckpt_time", "rd_test_time")


class RecordingSink:
    """Duck-typed ``MetricsSink``: keeps (time, name, value, step) of every
    metric and calls ``on_round_end(round, now)`` at each boundary."""

    def __init__(self, on_round_end: Optional[Callable[[int, float], None]]
                 = None, clock: Callable[[], float] = time.perf_counter):
        self.events: List[Tuple[float, str, float, Optional[float]]] = []
        self.on_round_end = on_round_end
        self.clock = clock

    def log_parameters(self, params) -> None:
        pass

    def log_metrics(self, metrics: Dict[str, float], step=None) -> None:
        for name, value in metrics.items():
            self.log_metric(name, value, step=step)

    def log_metric(self, name: str, value, step=None) -> None:
        now = self.clock()
        self.events.append((now, name, value, step))
        if name == ROUND_END_METRIC and self.on_round_end is not None:
            self.on_round_end(int(step), now)

    def log_asset(self, name: str, data) -> None:
        pass

    def close(self) -> None:
        pass


def round_ends(events) -> List[Tuple[int, float]]:
    """(round, end time) of every completed round, in order."""
    return [(int(step), t) for t, name, _, step in events
            if name == ROUND_END_METRIC]


def window_rounds(events, t_open: float,
                  pauses: List[Tuple[float, float]] = ()) -> List[Dict]:
    """The rounds that lie wholly inside the window that opened at
    ``t_open``: each with its start (the previous boundary), its end, its
    length and the phase times the program reported for it.  A round that
    straddles the opening is not counted.  ``pauses`` are intervals in which
    the benchmark itself held the loop at a boundary (stopping a trace): a
    round that starts at such a boundary starts at the pause's end."""
    ends = round_ends(events)
    out: List[Dict] = []
    prev_end: Optional[float] = None
    for rd, t_end in ends:
        start = prev_end
        prev_end = t_end
        if start is None:
            continue
        for p0, p1 in pauses:
            if abs(p0 - start) < 1e-6:
                start = p1
        if start < t_open - 1e-6:
            continue
        phases = {name: float(v) for t, name, v, step in events
                  if name in PHASE_METRICS and step == rd
                  and start - 1e-6 <= t <= t_end}
        out.append({"round": rd, "start": start, "end": t_end,
                    "seconds": t_end - start, "phases": phases})
    return out


def round_seconds(rounds: List[Dict]) -> float:
    """``round_s``: all the time of the window's completed rounds over their
    number; stalls, compiles and checkpoint writes included."""
    if not rounds:
        raise ValueError("no round completed inside the window")
    return sum(r["seconds"] for r in rounds) / len(rounds)


def counter_sum(events, name: str, rounds: List[Dict]) -> float:
    keep = {r["round"] for r in rounds}
    return float(sum(v for _, n, v, step in events
                     if n == name and step in keep))
