"""The atomic round journal: one JSON file recording where the run IS.

``round_journal.json`` lives in --log_dir next to the heartbeat and is
rewritten atomically (tmp + rename — the publish_best idiom) with a
monotonic ``seq`` tag, so an external reader (the ``status`` verb's
--strict exit-code contract, a post-mortem after preemption) always
sees a complete, ordered record: current round/phase/attempt, the
labeled-set size + CRC, whether the pipelined round is armed, the
active degradation rungs, and the terminal status (finished / preempted
/ stalled / crashed).

Unlike the heartbeat (liveness: WHEN did it last move) the journal is
state (WHERE is it, and in what mode): a healthy heartbeat with a
non-empty ``degrade`` list is exactly the "alive but degraded" state an
orchestrator wants a distinct exit code for.

Resume continuity: a new RoundJournal over an existing file continues
its ``seq`` — the monotonic tag never restarts within an experiment
directory, so two records can always be ordered even across process
restarts within one filesystem.

Stdlib-only on purpose: telemetry/status.py reads it with NO jax import
(the span around a write comes from telemetry/spans.py, which imports no
jax either).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Optional

from ..telemetry import spans as spans_lib

JOURNAL_FILE = "round_journal.json"


def read_journal(path: str) -> Optional[Dict[str, Any]]:
    """The journal payload, or None when absent/unparseable (a torn file
    is impossible by construction; missing means the run predates the
    journal or never started)."""
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


class RoundJournal:
    """Merge-and-rewrite journal writer (field semantics like the
    heartbeat: a write merges its fields over the retained ones, so a
    ``status="preempted"`` snapshot keeps the round/phase context of the
    last regular write).  ``enabled=False`` (non-coordinator processes)
    makes every write a no-op.  Never raises: a full disk must not take
    the run down — the log already records real progress."""

    def __init__(self, path: str, enabled: bool = True):
        self.path = path
        self.enabled = enabled
        self._lock = threading.Lock()
        self._fields: Dict[str, Any] = {}
        prior = read_journal(path) if enabled else None
        self._seq = int(prior.get("seq", 0)) if prior else 0

    def write(self, **fields: Any) -> Optional[Dict[str, Any]]:
        """Merge ``fields`` (None values delete), bump seq, rewrite
        atomically.  Returns the written payload (None when disabled or
        the write failed)."""
        if not self.enabled:
            return None
        with spans_lib.get_tracer().span("journal/write"):
            return self._write(fields)

    def _write(self, fields: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        with self._lock:
            for k, v in fields.items():
                if v is None:
                    self._fields.pop(k, None)
                else:
                    self._fields[k] = v
            self._seq += 1
            payload = {**self._fields, "seq": self._seq, "ts": time.time()}
        try:
            directory = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(directory, exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(payload, fh)
            os.replace(tmp, self.path)
        except OSError:
            return None
        return payload
