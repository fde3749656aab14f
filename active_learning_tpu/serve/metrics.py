"""Serving-side observability: counters, a latency reservoir, and the
batch-occupancy histogram.

Request latency is THIS subsystem's headline metric (round wall-clock
is the driver's), so the reservoir keeps the most recent window of
per-request latencies and serves p50/p99 on demand — the same numbers
``scripts/serve_loadgen.py`` measures from the client side.  The
occupancy histogram
(real rows per dispatched bucket) is the direct readout of how well the
microbatcher is filling the shapes it pays for: a service living at
occupancy 1 in a 64-bucket is latency-bound, one pegged at max_batch is
throughput-bound and a queue-depth candidate.

Thread discipline: the event loop thread and the executor thread both
write; everything is under one lock (counters are tiny, contention is
nil at any realistic qps).
"""

from __future__ import annotations

import collections
import math
import threading
import time
from typing import Dict, Optional


def percentile(sorted_vals, q: float) -> Optional[float]:
    """Nearest-rank percentile over an ascending list; None when empty.
    Shared convention with scripts/serve_loadgen.py so server- and
    client-side p50/p99 are comparable."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return float(sorted_vals[idx])


class ServeMetrics:
    def __init__(self, window: int = 8192):
        self._lock = threading.Lock()
        self._latencies = collections.deque(maxlen=window)
        self.requests: Dict[str, int] = collections.defaultdict(int)
        self.responses: Dict[int, int] = collections.defaultdict(int)
        # occupancy[bucket][real_rows] = dispatch count
        self.occupancy: Dict[int, Dict[int, int]] = {}
        self.rows_served = 0
        self.started = time.monotonic()

    def record_request(self, endpoint: str) -> None:
        with self._lock:
            self.requests[endpoint] += 1

    def record_response(self, status: int, latency_s: Optional[float],
                        rows: int = 0) -> None:
        with self._lock:
            self.responses[status] += 1
            self.rows_served += rows
            if latency_s is not None:
                self._latencies.append(latency_s)

    def record_batch(self, bucket: int, rows: int) -> None:
        with self._lock:
            hist = self.occupancy.setdefault(int(bucket), {})
            hist[int(rows)] = hist.get(int(rows), 0) + 1

    def snapshot(self) -> Dict:
        with self._lock:
            lats = sorted(self._latencies)
            uptime = time.monotonic() - self.started
            n_ok = self.responses.get(200, 0)
            return {
                "uptime_s": round(uptime, 1),
                "requests": dict(self.requests),
                "responses": {str(k): v for k, v in self.responses.items()},
                "rows_served": self.rows_served,
                "qps": round(n_ok / uptime, 2) if uptime > 0 else 0.0,
                "latency_ms": {
                    "p50": _ms(percentile(lats, 0.50)),
                    "p99": _ms(percentile(lats, 0.99)),
                    "n": len(lats),
                },
                "batch_occupancy": {
                    str(b): {str(r): c for r, c in sorted(h.items())}
                    for b, h in sorted(self.occupancy.items())
                },
            }


def _ms(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v * 1000.0, 3)


def prometheus_samples(snap: Dict) -> list:
    """The enriched /metrics snapshot (server._metrics output) as
    (name, labels, value) samples for telemetry/prom.render — the
    ``?format=prometheus`` view.  Counter-like values stay gauges with a
    _total suffix: they are process-lifetime snapshots and reset with
    the process."""
    samples = [
        ("al_serve_uptime_seconds", None, snap.get("uptime_s")),
        ("al_serve_rows_served_total", None, snap.get("rows_served")),
        ("al_serve_qps", None, snap.get("qps")),
        ("al_serve_served_round", None, snap.get("served_round")),
    ]
    for endpoint, count in sorted((snap.get("requests") or {}).items()):
        samples.append(("al_serve_requests_total",
                        {"endpoint": endpoint}, count))
    for status, count in sorted((snap.get("responses") or {}).items()):
        samples.append(("al_serve_responses_total",
                        {"status": str(status)}, count))
    lat = snap.get("latency_ms") or {}
    for q, key in (("0.5", "p50"), ("0.99", "p99")):
        if lat.get(key) is not None:
            samples.append(("al_serve_request_latency_ms",
                            {"quantile": q}, lat[key]))
    samples.append(("al_serve_latency_window_size", None, lat.get("n")))
    for bucket, hist in sorted((snap.get("batch_occupancy") or {}).items()):
        for rows, count in sorted(hist.items()):
            samples.append(("al_serve_batch_occupancy_total",
                            {"bucket": str(bucket), "rows": str(rows)},
                            count))
    queue = snap.get("queue") or {}
    samples.append(("al_serve_queue_pending_rows", None,
                    queue.get("pending_rows")))
    samples.append(("al_serve_queue_depth", None, queue.get("depth")))
    ex = snap.get("executor") or {}
    for key in ("batches", "rows", "reloads"):
        if key in ex:
            samples.append((f"al_serve_executor_{key}_total", None,
                            ex[key]))
    compiles = snap.get("compiles") or {}
    # THE serving contract, scrapable: 0 after warmup, forever.
    samples.append(("al_serve_request_path_compiles", None,
                    compiles.get("request_path_compiles")))
    for step, count in sorted((compiles.get("per_step") or {}).items()):
        samples.append(("al_serve_jit_cache_entries",
                        {"step": step}, count))
    # The per-model acquisition-score histogram + live-vs-checkpoint
    # drift (telemetry/diagnostics.ServeScoreDrift): the histogram is
    # exposed Prometheus-style (cumulative buckets with ``le`` labels +
    # _count/_sum), the drift gauges ride beside it — the online drift
    # signal of DESIGN.md §13.
    drift = snap.get("score_drift") or {}
    live = drift.get("live") or {}
    counts = live.get("counts") or []
    if counts:
        key = drift.get("key", "score")
        lo, hi = live.get("lo", 0.0), live.get("hi", 1.0)
        bins = max(1, int(live.get("bins", len(counts))))
        log1p = live.get("transform") == "log1p"
        cum = 0
        for i, c in enumerate(counts):
            cum += int(c)
            edge = lo + (i + 1) * (hi - lo) / bins
            if log1p:
                # The ladder is linear in TRANSFORMED space; `le`
                # labels must be in score space or every scraper
                # misreads the distribution.
                edge = math.expm1(edge)
            samples.append(("al_serve_score_hist_bucket",
                            {"key": key, "le": f"{edge:.6g}"}, cum))
        samples.append(("al_serve_score_hist_bucket",
                        {"key": key, "le": "+Inf"}, cum))
        samples.append(("al_serve_score_hist_count", {"key": key},
                        live.get("n")))
        samples.append(("al_serve_score_hist_sum", {"key": key},
                        live.get("sum")))
    if drift.get("baseline_round") is not None:
        samples.append(("al_serve_score_baseline_round", None,
                        drift.get("baseline_round")))
    for metric in ("psi", "js"):
        if drift.get(metric) is not None:
            samples.append((f"al_serve_score_drift_{metric}", None,
                            drift[metric]))
    return samples
