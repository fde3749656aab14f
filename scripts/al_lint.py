#!/usr/bin/env python
"""al_lint: the whole-package static-analysis CLI (DESIGN.md §12).

Runs the 18-check registry (10 legacy trace_lint invariants + the
lock-discipline / donation-safety / recompile-hazard /
collective-axis / diagnostics-inert / wal-before-ack
deep checkers) over active_learning_tpu/ and scripts/ through ONE
shared-parse AST cache.

    python scripts/al_lint.py                 # run everything
    python scripts/al_lint.py --list          # show the registry
    python scripts/al_lint.py --check lock-discipline --check fault-sites
    python scripts/al_lint.py --json          # machine-readable report

Exit codes: 0 clean (suppressed findings allowed — they are counted in
the report), 1 unsuppressed findings, 2 usage error.  Stdlib only; safe
to run against a wedged or backend-less tree.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from active_learning_tpu.analysis import run_package_analysis  # noqa: E402
from active_learning_tpu.analysis.checks import CHECKERS  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="al_lint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--check", action="append", default=None,
                        metavar="ID",
                        help="run only this check id (repeatable)")
    parser.add_argument("--list", action="store_true",
                        help="list the check registry and exit")
    parser.add_argument("--json", action="store_true",
                        help="emit the findings report as JSON on stdout")
    args = parser.parse_args(argv)

    if args.list:
        width = max(len(c.id) for c in CHECKERS)
        for c in CHECKERS:
            tok = f"  [# al-lint: {c.suppress_token}]" \
                if c.suppress_token else ""
            print(f"{c.id:<{width}}  {c.title}{tok}")
        return 0

    try:
        report = run_package_analysis(check_ids=args.check)
    except ValueError as exc:
        print(f"al_lint: {exc}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(report.to_json(), indent=1))
    else:
        for f in report.unsuppressed:
            print(f"al_lint: {f.check}: {f.render()}", file=sys.stderr)
        for f in report.suppressed:
            print(f"al_lint: suppressed [{f.check}] {f.render()} "
                  f"(reason: {f.suppress_reason})", file=sys.stderr)
        if not report.unsuppressed:
            n = len(report.checks_run)
            s = len(report.suppressed)
            sup = f", {s} suppressed finding(s)" if s else ""
            print(f"al_lint: ok — {n} check(s) over "
                  f"{report.files_scanned} files in "
                  f"{report.elapsed_s:.2f}s{sup}")
    return 1 if report.unsuppressed else 0


if __name__ == "__main__":
    raise SystemExit(main())
