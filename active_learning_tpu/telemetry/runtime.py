"""The per-run telemetry runtime: one object owning the tracer, the
heartbeat, the watchdog, the jit-compile counter, and the Prometheus
scrape file, installed process-wide for the duration of a run.

The driver calls ``start_run`` once (after logging setup, before the
stack is built) and ``finish`` at exit; everything between — the
trainer's per-epoch step stats, ``phase_timer``'s ticks, the scoring
engine's chunk spans — reaches the run through ``get_run()`` /
``spans.get_tracer()`` without any plumbing through constructors.  When
no run is installed the default instance is fully inert: ``tick`` is a
no-op, ``train_metrics`` is False (the trainer skips even the
per-step ``perf_counter`` calls), and nothing touches the filesystem —
library users and unit tests see exactly the pre-telemetry behavior.

The jit registry generalizes the serve executor's compile counter
(serve/executor.compile_counts) to the offline stack: the trainer and
strategies register their jitted steps, ``jit_cache_total()`` sums the
live cache sizes, and the driver emits the per-round DELTA — a nonzero
delta after round 1 is a shape leak (the exact regression
tests/test_compile_reuse.py pins, now visible in production metrics
instead of only under test).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import heartbeat as hb_lib
from . import profiler as profiler_lib
from . import prom as prom_lib
from . import spans as spans_lib

# Lock discipline, statically enforced (scripts/al_lint.py
# lock-discipline): gauges and the jit registry are written by the
# driver thread and read by the watchdog/status paths — always under
# the run's _lock.
_GUARDED_BY = {"_gauges": "_lock", "_jits": "_lock",
               "_jit_sizes_last": "_lock"}


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (same convention as serve/metrics.py and
    scripts/serve_loadgen.py, so step-time and latency percentiles are
    comparable numbers); None on empty."""
    if not values:
        return None
    vals = sorted(values)
    idx = min(len(vals) - 1, max(0, int(round(q * (len(vals) - 1)))))
    return float(vals[idx])


def hbm_high_water_gb() -> Optional[float]:
    """Peak device HBM in GB via ``memory_stats()``, the HIGHEST of the
    local devices (on a mesh the chip that holds the most is the one
    that runs out first) — None where the backend keeps no statistics
    (CPU)."""
    import jax
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
             for dev in jax.local_devices()]
    peaks = [p for p in peaks if p]
    return round(max(peaks) / 2**30, 3) if peaks else None


class RunTelemetry:
    """Everything one run's telemetry owns.  The inert default (no
    config) records nothing and writes nothing."""

    def __init__(self, cfg=None, tracer: Optional[spans_lib.SpanTracer] = None,
                 heartbeat: Optional[hb_lib.HeartbeatWriter] = None,
                 watchdog: Optional[hb_lib.StallWatchdog] = None,
                 trace_path: Optional[str] = None,
                 prometheus_file: Optional[str] = None,
                 logger=None):
        self.cfg = cfg
        self.tracer = tracer or spans_lib.SpanTracer(enabled=False)
        self.heartbeat = heartbeat
        self.watchdog = watchdog
        self.trace_path = trace_path
        self.prometheus_file = prometheus_file
        self.logger = logger
        # Per-step/per-epoch metric collection in the trainer and the
        # pool-scan rate metric in the strategies key off this.
        self.train_metrics = bool(cfg and getattr(cfg, "enabled", False))
        self._lock = threading.Lock()
        self._gauges: Dict[str, float] = {}
        self._jits: Dict[str, Any] = {}
        self._jit_sizes_last: Dict[str, int] = {}
        # Registered programs whose cache grew in the last
        # jit_cache_delta() window — the names behind a nonzero delta.
        self.jit_grown: List[str] = []
        self.finished = False

    # -- progress ----------------------------------------------------------

    def tick(self, force: bool = False, **fields: Any) -> None:
        """One progress event (round/phase/epoch/step...).  Inert when no
        heartbeat is configured."""
        if self.heartbeat is not None:
            self.heartbeat.tick(force=force, **fields)

    # -- gauges / prometheus ----------------------------------------------

    def set_gauges(self, **gauges: Any) -> None:
        with self._lock:
            for k, v in gauges.items():
                if v is None:
                    self._gauges.pop(k, None)
                else:
                    self._gauges[k] = v

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def write_prometheus(self) -> None:
        if not self.prometheus_file:
            return
        text = prom_lib.render(
            prom_lib.gauge_samples(self.gauges(), prefix="al_run_"))
        prom_lib.write_textfile(self.prometheus_file, text)

    # -- jit-compile accounting -------------------------------------------

    def register_jit(self, name: str, fn: Any) -> None:
        """Track a jitted callable's cache size (the serve-side compile
        counter, generalized).  No-op on the inert default so unit-test
        Trainers don't accumulate in a process-global registry."""
        if not self.train_metrics or fn is None:
            return
        with self._lock:
            self._jits[name] = fn

    def jit_cache_sizes(self) -> Dict[str, int]:
        with self._lock:
            jits = dict(self._jits)
        sizes = {}
        for name, fn in jits.items():
            # jax.jit wrappers count their compiled specializations; a
            # registered plain callable has no cache to count.
            cache_size = getattr(fn, "_cache_size", None)
            if cache_size is not None:
                sizes[name] = int(cache_size())
        return sizes

    def jit_cache_total(self) -> int:
        return sum(self.jit_cache_sizes().values())

    def jit_cache_delta(self) -> int:
        """Compiles since the last call — the per-round miss delta.
        ``jit_grown`` then names the registered programs that compiled,
        so a recompile in a window says which step it was."""
        sizes = self.jit_cache_sizes()
        with self._lock:
            last = self._jit_sizes_last
            self.jit_grown = sorted(n for n, size in sizes.items()
                                    if size > last.get(n, 0))
            delta = sum(sizes.values()) - sum(last.values())
            self._jit_sizes_last = sizes
        return delta

    # -- lifecycle ---------------------------------------------------------

    def export_trace(self, metadata: Optional[Dict[str, Any]] = None
                     ) -> Optional[str]:
        if not self.trace_path:
            return None
        return self.tracer.export(self.trace_path, metadata=metadata)

    def finish(self, status: str = "finished") -> None:
        """Final heartbeat + trace export + watchdog stop.  Idempotent —
        the driver's exception path and its normal path may both land
        here."""
        if self.finished:
            return
        self.finished = True
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.heartbeat is not None:
            self.heartbeat.write_now(status=status)
        self.export_trace(metadata={"status": status})
        self.write_prometheus()


# -- process-wide install ----------------------------------------------------

_DEFAULT = RunTelemetry()
_CURRENT = _DEFAULT


def get_run() -> RunTelemetry:
    return _CURRENT


def install(rt: RunTelemetry) -> RunTelemetry:
    global _CURRENT
    _CURRENT = rt
    spans_lib.set_tracer(rt.tracer)
    return rt


def uninstall(rt: Optional[RunTelemetry] = None) -> None:
    """Restore the inert default (only if ``rt`` is still the installed
    one — a nested run that already swapped must not be clobbered)."""
    global _CURRENT
    if rt is None or _CURRENT is rt:
        _CURRENT = _DEFAULT
        spans_lib.set_tracer(None)


def start_run(cfg, log_dir: str, process_index: int = 0,
              process_count: int = 1, logger=None,
              on_stall: Optional[Callable[[float], None]] = None
              ) -> RunTelemetry:
    """Build + install a run's telemetry from its TelemetryConfig.

    ``cfg.enabled`` False returns (and installs) an inert runtime — the
    telemetry-off path must add no per-step work anywhere.  Trace export
    and the watchdog are opt-in on top of enabled.
    """
    import os

    if cfg is None or not cfg.enabled:
        rt = RunTelemetry(logger=logger)
        return install(rt)
    suffix = f"_p{process_index}" if process_count > 1 else ""
    heartbeat = hb_lib.HeartbeatWriter(
        os.path.join(log_dir, hb_lib.heartbeat_filename(process_index,
                                                        process_count)),
        every_s=cfg.heartbeat_every_s,
        stall_deadline_s=cfg.stall_deadline_s,
        static_fields={"process_index": process_index,
                       "process_count": process_count,
                       "status": "running"})
    # Recording is opt-in (export_trace); the device annotation is not:
    # with the recorder off a span is still a name in any profiler trace
    # that happens to be open (--profile_rounds, a benchmark's own).
    tracer = spans_lib.SpanTracer(
        enabled=cfg.export_trace,
        annotate=profiler_lib.trace_annotation)
    trace_path = (os.path.join(log_dir, f"trace{suffix}.json")
                  if cfg.export_trace else None)
    watchdog = None
    if cfg.watchdog:
        def _default_on_stall(stalled_s: float) -> None:
            if logger is not None:
                logger.warning(
                    f"watchdog: no progress for {stalled_s:.0f}s "
                    f"(deadline {cfg.stall_deadline_s:.0f}s) — "
                    "stall suspected")
            tracer.instant("stall_suspected",
                           args={"stalled_s": round(stalled_s, 1)})
        watchdog = hb_lib.StallWatchdog(
            heartbeat, cfg.stall_deadline_s,
            on_stall=on_stall or _default_on_stall)
    rt = RunTelemetry(cfg=cfg, tracer=tracer, heartbeat=heartbeat,
                      watchdog=watchdog, trace_path=trace_path,
                      prometheus_file=cfg.prometheus_file or None,
                      logger=logger)
    install(rt)
    heartbeat.tick(force=True, phase="startup")
    if watchdog is not None:
        watchdog.start()
    return rt
