"""The ONE retry policy (DESIGN.md §10): exponential backoff + jitter,
attempt/wall budgets, and explicit transient-vs-fatal classification —
replacing the ad-hoc guards that grew up around device transfer, shard
upload, checkpoint IO, and the best-ckpt-watcher polls.

Two rules, both enforced statically by scripts/trace_lint.py check 8:

  * every ``RetryPolicy(...)`` construction passes ``classify=``
    explicitly — there is no default classifier to hide behind, so "what
    does this site consider transient" is always written at the site
    (no bare ``except Exception: retry`` anywhere);
  * classification returns one of TRANSIENT (back off and retry), OOM
    (never retried at the same shape — re-raised for the degradation
    ladder's batch-halving rung), FATAL (re-raised immediately).

Every retry is counted process-wide (``retry_counters``) and surfaced
through the run's telemetry: the driver emits ``fault_retries_total`` /
``degrade_events`` into the MetricsSink at round boundaries, the
Prometheus scrape file carries the same gauges, and the site label of
the most recent retry rides the heartbeat as ``fault_last_site`` (a
string, so it travels the heartbeat rather than a numeric gauge).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Callable, Dict, Optional

from .registry import InjectedFault, InjectedOOM, ThreadDeath

TRANSIENT = "transient"
OOM = "oom"
FATAL = "fatal"

_COUNTERS_LOCK = threading.Lock()
_RETRIES_TOTAL = 0
_RETRIES_BY_SITE: Dict[str, int] = {}
_LAST_SITE: Optional[str] = None


def classify_exception(exc: BaseException) -> str:
    """The default classification shared by the infrastructure call
    sites (call sites still name it explicitly — trace_lint check 8):

      OOM        allocator exhaustion (XLA RESOURCE_EXHAUSTED, the
                 injected stand-in) — retrying at the same shape fails
                 the same way; the degradation ladder halves the batch
                 instead;
      TRANSIENT  injected faults, injected thread death (a dead worker
                 thread is rebuilt by re-running the pass), and OSError
                 (full disk, yanked NFS, racing renames — the classic
                 retryable IO surface);
      FATAL      everything else: a programming error retried three
                 times is a programming error that wasted two retries.
    """
    if isinstance(exc, InjectedOOM):
        return OOM
    if "RESOURCE_EXHAUSTED" in str(exc):
        return OOM
    if isinstance(exc, (InjectedFault, ThreadDeath)):
        return TRANSIENT
    if isinstance(exc, OSError):
        return TRANSIENT
    return FATAL


def _record_retry(site: str) -> None:
    global _RETRIES_TOTAL, _LAST_SITE
    with _COUNTERS_LOCK:
        _RETRIES_TOTAL += 1
        _RETRIES_BY_SITE[site] = _RETRIES_BY_SITE.get(site, 0) + 1
        _LAST_SITE = site
    # Surface through the installed run's telemetry (inert default
    # records nothing): the site label rides the heartbeat for
    # `status`.  The fault_retries_total GAUGE is owned by the driver's
    # round-boundary emission, which subtracts its run-start baseline —
    # setting the raw process total here would fight it.
    try:
        from ..telemetry import runtime as tele_runtime
        tele_runtime.get_run().tick(fault_last_site=site)
    except Exception:  # noqa: BLE001 - accounting must never take a run down
        pass


def retry_counters() -> Dict[str, Any]:
    """Process-cumulative retry accounting: {"total", "by_site",
    "last_site"} — the driver emits total per round."""
    with _COUNTERS_LOCK:
        return {"total": _RETRIES_TOTAL,
                "by_site": dict(_RETRIES_BY_SITE),
                "last_site": _LAST_SITE}


class RetryPolicy:
    """Bounded, classified retry around one operation.

    ``site`` is a free-form metrics label (it names the retried
    OPERATION for fault_retries_total attribution; the injection-site
    registry in registry.SITES is a separate, closed namespace).
    ``classify`` maps an exception to TRANSIENT/OOM/FATAL and is
    REQUIRED — trace_lint check 8 rejects constructions without it.
    """

    def __init__(self, site: str, classify: Callable[[BaseException], str],
                 max_attempts: int = 3, base_delay_s: float = 0.05,
                 max_delay_s: float = 2.0, wall_budget_s: float = 30.0):
        if classify is None:
            raise ValueError(
                f"RetryPolicy({site!r}): classify is required — every "
                "call site states its transient-vs-fatal rule")
        self.site = site
        self.classify = classify
        self.max_attempts = max(1, int(max_attempts))
        self.base_delay_s = float(base_delay_s)
        self.max_delay_s = float(max_delay_s)
        self.wall_budget_s = float(wall_budget_s)
        self._jitter = random.Random(f"retry:{site}")

    def call(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Run ``fn(*args, **kwargs)``; retry classified-TRANSIENT
        failures with exponential backoff + jitter until the attempt or
        wall budget runs out, then re-raise the last failure.  OOM and
        FATAL re-raise immediately (see classify_exception)."""
        t0 = time.monotonic()
        attempt = 0
        while True:
            attempt += 1
            try:
                return fn(*args, **kwargs)
            except BaseException as exc:  # noqa: BLE001 - classified below
                kind = self.classify(exc)
                if kind != TRANSIENT:
                    raise
                if attempt >= self.max_attempts:
                    raise
                if time.monotonic() - t0 >= self.wall_budget_s:
                    raise
                delay = min(self.max_delay_s,
                            self.base_delay_s * (2 ** (attempt - 1)))
                delay *= 0.5 + self._jitter.random()  # [0.5x, 1.5x)
                _record_retry(self.site)
                try:
                    from ..utils.logging import get_logger
                    get_logger().warning(
                        f"retry[{self.site}] attempt {attempt}/"
                        f"{self.max_attempts} failed with "
                        f"{type(exc).__name__}: {exc}; retrying in "
                        f"{delay * 1000:.0f} ms")
                except Exception:  # noqa: BLE001 - logging is best-effort
                    pass
                time.sleep(delay)
