"""Tracing and profiling — a thin shim over telemetry/spans.

The reference's only tracing is ad-hoc ``time()`` deltas printed per phase
(src/main_al.py:160-178) and per-batch loss prints (strategy.py:274-279).
Here the same per-phase timers are HOST SPANS (telemetry/spans.py): one
measurement feeds the ``rd_{name}`` metric, the log line, the Chrome
trace event, and the heartbeat tick, so the trace can never silently
fork from the metrics (scripts/trace_lint.py asserts this routing).
The device annotation is the span's too: ``SpanTracer.span`` opens one
``TraceAnnotation`` of the span's own name through the gated route
(telemetry/profiler.trace_annotation, which owns EVERY jax.profiler
touch per trace_lint check 10), so ``phase_timer`` opens none of its own
and XLA profiler captures show every program span — phases and what
runs inside them — on the device timeline under one name each.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

from ..telemetry import runtime as _tele_runtime
from ..telemetry import spans as _tele_spans
from .logging import get_logger


@contextlib.contextmanager
def phase_timer(name: str, round_idx: int, sink=None,
                logger=None) -> Iterator[None]:
    """Wall-clock a phase, log it, and emit ``rd_{name}`` to the metrics
    sink — the reference's per-phase prints (main_al.py:160-178).  The
    timing IS the host span's: metric, log, trace event, device
    annotation and heartbeat all read one measurement.  Yields
    the span so callers can read the same ``duration_s`` afterwards (the
    driver's overlap_frac accounting sums phase walls from it — still
    one measurement, never a second clock)."""
    logger = logger or get_logger()
    _tele_runtime.get_run().tick(force=True, phase=name, round=round_idx)
    with _tele_spans.get_tracer().span(
            name, args={"round": round_idx}) as sp:
        yield sp
    seconds = sp.duration_s
    logger.info(f"Rd {round_idx} {name} is {seconds:.3f}s")
    if sink is not None:
        sink.log_metric(f"rd_{name}", seconds, step=round_idx)


