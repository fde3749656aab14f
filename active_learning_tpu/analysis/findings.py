"""The findings model: what a checker reports and how a report renders.

One ``Finding`` is one violation at one place: a check id, a
repo-relative path, a line (0 = file/registry-level), a human message,
and an optional fix hint.  The ported trace_lint checks render their
findings byte-for-byte as the legacy strings (``path:line: message`` /
``path: message``), which is what lets scripts/trace_lint.py stay a thin
shim with identical verdicts.

Suppressions: the four deep checkers (lock-discipline, donation-safety,
recompile-hazard, collective-axis) honor a source-line annotation

    # al-lint: <token> <reason>

where ``token`` is the checker's ``suppress_token`` (e.g. ``donated-ok``).
A suppression REQUIRES a non-empty reason — one without a reason is
itself a finding, and suppressed findings are counted and carried in the
``--json`` report rather than vanishing (the operator always sees how
much of the tree is annotated away).  The legacy checks deliberately
accept no suppressions: their verdicts must stay identical to the
monolith they replace.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass
class Finding:
    check: str               # check id (see checks/__init__.CHECKERS)
    path: str                # repo-relative path
    line: int                # 1-based; 0 = file/registry-level finding
    message: str             # human-readable defect statement
    hint: str = ""           # how to fix (empty for legacy-ported checks)
    suppressed: bool = False
    suppress_reason: str = ""

    def render(self) -> str:
        """The legacy trace_lint string shape: ``path:line: message`` (or
        ``path: message`` for file-level findings).  The hint rides after
        the message so the shim's strings stay supersets of the legacy
        text, never rewrites of it."""
        loc = f"{self.path}:{self.line}" if self.line else self.path
        text = f"{loc}: {self.message}"
        if self.hint:
            text += f"  [fix: {self.hint}]"
        return text

    def to_json(self) -> dict:
        return {
            "check": self.check,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "hint": self.hint,
            "suppressed": self.suppressed,
            "suppress_reason": self.suppress_reason,
        }


# ``# al-lint: <token> <reason...>`` — reason is everything after the
# token (may be empty, which is itself a finding).
_SUPPRESS_RE = re.compile(r"#\s*al-lint:\s*(?P<token>[A-Za-z0-9_-]+)"
                          r"(?P<reason>[^#]*)")


def suppression_on_line(src_line: str, token: str):
    """Parse an ``# al-lint:`` annotation on ``src_line`` for ``token``.
    Returns None (no annotation for this token) or the reason string
    (possibly empty — the caller must treat empty as a violation)."""
    for m in _SUPPRESS_RE.finditer(src_line):
        if m.group("token") == token:
            return m.group("reason").strip()
    return None


def apply_suppressions(findings, token, source_lines):
    """Resolve ``# al-lint: <token> <reason>`` annotations against a
    checker's findings.  ``source_lines`` maps repo-relative path -> list
    of source lines.  A finding whose line (or the line above it, for
    annotations placed on their own line) carries the token is marked
    suppressed with the reason; an empty reason converts the finding
    into a "suppression without a reason" violation instead.  Returns
    the findings list (mutated in place)."""
    if not token:
        return findings
    out = []
    for f in findings:
        lines = source_lines.get(f.path)
        reason = None
        if lines and f.line:
            for ln in (f.line, f.line - 1):
                if 1 <= ln <= len(lines):
                    reason = suppression_on_line(lines[ln - 1], token)
                    if reason is not None:
                        break
        if reason is None:
            out.append(f)
        elif reason:
            f.suppressed = True
            f.suppress_reason = reason
            out.append(f)
        else:
            out.append(Finding(
                check=f.check, path=f.path, line=f.line,
                message=(f"suppression '# al-lint: {token}' without a "
                         f"reason string (suppressing: {f.message})"),
                hint="every suppression carries a reason: "
                     f"# al-lint: {token} <why this is safe>"))
    findings[:] = out
    return findings


@dataclass
class Report:
    """One engine run: findings (live + suppressed), per-check counts,
    and the parse accounting that pins the single-parse contract."""

    findings: list = field(default_factory=list)
    checks_run: list = field(default_factory=list)
    files_scanned: int = 0
    parse_counts: dict = field(default_factory=dict)
    elapsed_s: float = 0.0
    # The run's own CPU seconds (the calling thread's): what the
    # shared-parse budget is held to — the wall also counts whoever else
    # had the cores (six pytest workers, in the tier-1 run).
    cpu_s: float = 0.0

    @property
    def unsuppressed(self):
        return [f for f in self.findings if not f.suppressed]

    @property
    def suppressed(self):
        return [f for f in self.findings if f.suppressed]

    def counts(self) -> dict:
        by_check: dict = {}
        for f in self.findings:
            entry = by_check.setdefault(f.check,
                                        {"findings": 0, "suppressed": 0})
            entry["suppressed" if f.suppressed else "findings"] += 1
        return by_check

    def to_json(self) -> dict:
        return {
            "checks_run": list(self.checks_run),
            "files_scanned": self.files_scanned,
            "max_parses_per_file": max(self.parse_counts.values(),
                                       default=0),
            "elapsed_s": round(self.elapsed_s, 3),
            "cpu_s": round(self.cpu_s, 3),
            "counts": self.counts(),
            "total_findings": len(self.unsuppressed),
            "total_suppressed": len(self.suppressed),
            "findings": [f.to_json() for f in self.findings],
        }
