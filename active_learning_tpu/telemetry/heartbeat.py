"""Liveness: the atomically-rewritten ``heartbeat.json`` + stall watchdog.

A multi-hour pod run whose host stalls (hung NFS, lost runtime, wedged
collective) previously produced NO signal at all until the outer timeout
killed it.  The heartbeat file is the liveness contract: the driver
ticks it on every progress event (round/phase/epoch/step transitions),
the writer rewrites the file atomically (tmp + rename — a reader polling
mid-run can never see a torn file) at a bounded cadence, and any
external observer — the ``status`` CLI verb, a k8s liveness probe, cron
— reads staleness straight off the file's mtime: older than the
embedded ``stall_deadline_s`` means the process stopped making progress
(or died).

The in-process watchdog is the same check without an external observer:
a daemon thread samples the writer's progress counter and calls
``on_stall`` once per stall episode when it freezes past the deadline
(re-arming when progress resumes).  Both clocks are injectable so the
tests drive a frozen fake clock instead of sleeping.

Per-process on pods: every process writes its own ``heartbeat_p{i}.json``
(process 0 of a single-process run writes plain ``heartbeat.json``), so
a stalled non-coordinator host is visible even while process 0 keeps
ticking.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

from . import spans as spans_lib


def heartbeat_filename(process_index: int = 0, process_count: int = 1) -> str:
    if process_count > 1:
        return f"heartbeat_p{process_index}.json"
    return "heartbeat.json"


class HeartbeatWriter:
    """Rate-limited atomic rewriter of one heartbeat file.

    ``tick(**fields)`` bumps the progress counter and merges the fields
    (round/phase/epoch/step/...) into the payload; the file is rewritten
    when ``every_s`` has elapsed since the last write (or on
    ``force=True`` — phase transitions force so the file never lags a
    whole cadence behind a phase change).  A tick is one lock + dict
    merge + monotonic compare when rate-limited — cheap enough for the
    per-step call sites.
    """

    def __init__(self, path: str, every_s: float = 5.0,
                 stall_deadline_s: float = 600.0,
                 static_fields: Optional[Dict[str, Any]] = None,
                 time_fn: Callable[[], float] = time.time,
                 monotonic_fn: Callable[[], float] = time.monotonic):
        self.path = path
        self.every_s = float(every_s)
        self.stall_deadline_s = float(stall_deadline_s)
        self._time = time_fn
        self._monotonic = monotonic_fn
        self._lock = threading.Lock()
        self._fields: Dict[str, Any] = dict(static_fields or {})
        self._last_write = float("-inf")
        self.progress = 0  # monotonically increasing; the watchdog's pulse
        self.writes = 0

    def tick(self, force: bool = False, **fields: Any) -> bool:
        """Record progress; rewrite the file if the cadence allows.
        Returns True when the file was (re)written."""
        with self._lock:
            self.progress += 1
            for k, v in fields.items():
                if v is not None:
                    self._fields[k] = v
            now = self._monotonic()
            if not force and now - self._last_write < self.every_s:
                return False
            self._last_write = now
            payload = self._payload()
        self._write(payload)
        return True

    def write_now(self, **fields: Any) -> None:
        """Unconditional rewrite (final status, stall marker)."""
        with self._lock:
            for k, v in fields.items():
                if v is not None:
                    self._fields[k] = v
            self._last_write = self._monotonic()
            payload = self._payload()
        self._write(payload)

    def _payload(self) -> Dict[str, Any]:
        return {
            **self._fields,
            "ts": self._time(),
            "pid": os.getpid(),
            "progress": self.progress,
            "every_s": self.every_s,
            "stall_deadline_s": self.stall_deadline_s,
        }

    def _write(self, payload: Dict[str, Any]) -> None:
        try:
            with spans_lib.get_tracer().span("heartbeat/write"):
                directory = os.path.dirname(os.path.abspath(self.path))
                os.makedirs(directory, exist_ok=True)
                tmp = f"{self.path}.tmp.{os.getpid()}"
                with open(tmp, "w") as fh:
                    json.dump(payload, fh)
                os.replace(tmp, self.path)
            self.writes += 1
        except OSError:
            # Liveness reporting must never take the run down (full disk,
            # yanked NFS) — the log already records real progress.
            pass


def read_heartbeat(path: str) -> Optional[Dict[str, Any]]:
    """The heartbeat payload, or None when absent/unparseable (a torn
    file is impossible by construction; a missing one just means the run
    never started or predates telemetry)."""
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


def heartbeat_age_s(path: str, now: Optional[float] = None
                    ) -> Optional[float]:
    """Seconds since the file was last rewritten (mtime-based, so it
    works even when clocks inside the payload drift)."""
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return None
    return (time.time() if now is None else now) - mtime


def is_stale(path: str, deadline_s: Optional[float] = None,
             now: Optional[float] = None) -> Optional[bool]:
    """True when the heartbeat's mtime exceeds the deadline (the file's
    own embedded ``stall_deadline_s`` unless overridden); None when
    there is no heartbeat to judge."""
    age = heartbeat_age_s(path, now=now)
    if age is None:
        return None
    if deadline_s is None:
        hb = read_heartbeat(path) or {}
        deadline_s = float(hb.get("stall_deadline_s", 600.0))
    return age > deadline_s


class StallWatchdog:
    """Daemon thread that fires ``on_stall(stalled_s)`` when the
    heartbeat's progress counter freezes past ``deadline_s``.

    One callback per DEADLINE WINDOW: firing opens a new window, so a
    wedged collective logs one loud event per deadline — not one per
    poll, and (the fixed re-arm edge) not exactly-once-forever either.
    The old rule re-armed only when progress resumed, so a stall that
    NEVER resumed — the same phase, frozen for hours — fired exactly
    once and went quiet, which with ``--watchdog_action degrade`` would
    mean exactly one escalation attempt no matter how wedged the run
    was.  Now each full deadline of continued stall fires another
    episode (``stalled_s`` reports the TOTAL stall, not the window), and
    progress resuming resets everything.  ``check(now)`` is the whole
    decision function — public so tests drive it with a fake clock
    instead of sleeping.
    """

    def __init__(self, heartbeat: HeartbeatWriter, deadline_s: float,
                 on_stall: Callable[[float], None],
                 monotonic_fn: Callable[[], float] = time.monotonic,
                 poll_s: Optional[float] = None):
        self.heartbeat = heartbeat
        self.deadline_s = float(deadline_s)
        self.on_stall = on_stall
        self._monotonic = monotonic_fn
        self.poll_s = float(poll_s if poll_s is not None
                            else max(1.0, deadline_s / 4.0))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_progress = heartbeat.progress
        self._last_change = monotonic_fn()
        self._last_fire: Optional[float] = None
        self.stalls_detected = 0

    def check(self, now: Optional[float] = None) -> bool:
        """One watchdog evaluation; returns True iff a stall fired."""
        now = self._monotonic() if now is None else now
        progress = self.heartbeat.progress
        if progress != self._last_progress:
            self._last_progress = progress
            self._last_change = now
            self._last_fire = None
            return False
        stalled_s = now - self._last_change
        window_start = (self._last_fire if self._last_fire is not None
                        else self._last_change)
        if now - window_start > self.deadline_s:
            self._last_fire = now
            self.stalls_detected += 1
            try:
                self.on_stall(stalled_s)
            except Exception:  # noqa: BLE001 - the watchdog must survive
                pass
            return True
        return False

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run,
                                        name="al-telemetry-watchdog",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            self.check()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
