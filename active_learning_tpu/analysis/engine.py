"""The analysis engine: one parse per file, many checkers over it.

The legacy ``scripts/trace_lint.py`` re-opened and re-parsed the package
once per check — 10 checks × ~80 files of redundant ``ast.parse``.  The
engine inverts that: an ``AstCache`` owns exactly one parse (and one
read) per file for the whole run, every checker receives the same
``Context``, and the cache COUNTS its parses so the single-parse
contract is an assertable property (tests/test_analysis.py pins
``max_parses_per_file <= 1`` and <5 s of the run's own CPU time for the
whole package).

Stdlib only, no jax import anywhere in this package: the lint must run
against a wedged, OOM'd, or backend-less tree (the same constraint the
status verb carries).

Adding a check (DESIGN.md §12): subclass ``Checker`` in
``analysis/checks/``, give it a unique ``id``, ``title``, and (if it
accepts suppressions) a ``suppress_token``, implement ``check(ctx)``
returning ``Finding``s, and append it to ``checks.CHECKERS``.  The CLI
(--list/--check) and the per-check fixture test pick it up from the
registry; a new check with no fixture under tests/fixtures/analysis/
fails the fixture-coverage test, so every checker lands with its golden
negative case.
"""

from __future__ import annotations

import ast
import os
import time
from typing import Dict, Iterable, List, Optional, Tuple

from .findings import Finding, Report, apply_suppressions

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PKG = os.path.join(REPO, "active_learning_tpu")

# The analyzers themselves are not analysis targets.
_SELF = ("trace_lint.py", "al_lint.py")


def default_files(repo: str = REPO) -> List[str]:
    """The whole-package file set: every .py under active_learning_tpu/
    and scripts/ (minus the lint entry points)."""
    pkg = os.path.join(repo, "active_learning_tpu")
    out: List[str] = []
    for root, dirs, files in os.walk(pkg):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in sorted(files):
            if name.endswith(".py"):
                out.append(os.path.join(root, name))
    scripts = os.path.join(repo, "scripts")
    if os.path.isdir(scripts):
        for name in sorted(os.listdir(scripts)):
            if name.endswith(".py") and name not in _SELF:
                out.append(os.path.join(scripts, name))
    return out


class AstCache:
    """Parse-once cache: path -> (tree, error).  ``parse_counts`` records
    how many times each file was ACTUALLY read+parsed — the single-parse
    contract is asserted on it, not assumed."""

    def __init__(self):
        self._entries: Dict[str, Tuple[Optional[ast.AST],
                                       Optional[Exception]]] = {}
        self._sources: Dict[str, str] = {}
        self.parse_counts: Dict[str, int] = {}

    def get(self, path: str) -> Tuple[Optional[ast.AST],
                                      Optional[Exception]]:
        """(tree, None) on success, (None, exc) on read/parse failure —
        each checker formats the failure in its own message (the legacy
        checks' per-check wording survives the port)."""
        path = os.path.abspath(path)
        if path not in self._entries:
            self.parse_counts[path] = self.parse_counts.get(path, 0) + 1
            try:
                with open(path) as fh:
                    src = fh.read()
                self._sources[path] = src
                self._entries[path] = (ast.parse(src), None)
            except (OSError, SyntaxError) as exc:
                self._entries[path] = (None, exc)
        return self._entries[path]

    def source(self, path: str) -> str:
        """The cached source text ('' when unreadable).  Reads the file
        at most once, shared with the parse."""
        path = os.path.abspath(path)
        if path not in self._entries:
            self.get(path)
        return self._sources.get(path, "")


class Context:
    """Everything a checker sees: the file set, the shared cache, and
    repo-relative path helpers."""

    def __init__(self, files: Iterable[str], cache: Optional[AstCache] = None,
                 repo: str = REPO):
        self.repo = repo
        self.files = [os.path.abspath(f) for f in files]
        self.cache = cache or AstCache()

    def rel(self, path: str) -> str:
        return os.path.relpath(os.path.abspath(path), self.repo)

    def tree(self, path: str):
        return self.cache.get(path)

    def source_lines(self, path: str) -> List[str]:
        return self.cache.source(path).splitlines()


class Checker:
    """Plugin base.  Subclasses set ``id`` (unique, kebab-case — the
    --check selector and the fixture filename), ``title`` (one line for
    --list), ``suppress_token`` (None = no suppressions honored), and
    implement ``check(ctx) -> List[Finding]``."""

    id: str = ""
    title: str = ""
    suppress_token: Optional[str] = None

    def check(self, ctx: Context) -> List[Finding]:
        raise NotImplementedError

    def finding(self, ctx: Context, path: str, line: int, message: str,
                hint: str = "") -> Finding:
        return Finding(check=self.id, path=ctx.rel(path), line=line,
                       message=message, hint=hint)


class Engine:
    """Run a set of checkers over one shared-parse file set."""

    def __init__(self, files: Optional[Iterable[str]] = None,
                 repo: str = REPO):
        self.ctx = Context(files if files is not None
                           else default_files(repo), repo=repo)

    def run(self, checkers: Iterable[Checker],
            check_ids: Optional[Iterable[str]] = None) -> Report:
        wanted = set(check_ids) if check_ids else None
        selected = [c for c in checkers
                    if wanted is None or c.id in wanted]
        if wanted:
            unknown = wanted - {c.id for c in selected}
            if unknown:
                raise ValueError(
                    f"unknown check id(s): {', '.join(sorted(unknown))} "
                    f"(--list shows the registry)")
        t0, cpu0 = time.perf_counter(), time.thread_time()
        report = Report(checks_run=[c.id for c in selected],
                        files_scanned=len(self.ctx.files))
        for checker in selected:
            found = checker.check(self.ctx)
            if checker.suppress_token and found:
                # Only the files that actually have findings need their
                # source lines — apply_suppressions never looks anywhere
                # else.
                flagged = {f.path for f in found}
                src_lines = {self.ctx.rel(p): self.ctx.source_lines(p)
                             for p in self.ctx.files
                             if self.ctx.rel(p) in flagged}
                apply_suppressions(found, checker.suppress_token,
                                   src_lines)
            report.findings.extend(found)
        report.parse_counts = dict(self.ctx.cache.parse_counts)
        report.elapsed_s = time.perf_counter() - t0
        report.cpu_s = time.thread_time() - cpu0
        return report
