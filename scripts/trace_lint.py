#!/usr/bin/env python
"""trace_lint: compatibility shim over the analysis engine.

The 10 checks this script used to implement as a 773-line monolith now
live in ``active_learning_tpu/analysis/checks/legacy.py``, ported
verbatim onto the shared-parse engine (DESIGN.md §12) — same verdicts,
same messages, one ``ast.parse`` per file instead of one per check.
This shim keeps the historical import surface alive so every existing
entry point (tests/test_telemetry.py's fragment tests, the tier-1
subprocess run, monkeypatched ``_py_files``) works unchanged:

  1  phase_timer derives its seconds from ONE tracer span
  2  nobody else defines a phase_timer
  3  call sites import phase_timer from utils.tracing
  4  jax.profiler.TraceAnnotation is opened by SpanTracer.span only
  5  the resident train feed never materializes images on host
  6  the row-sharded selection backend never un-shards the pool
  7  the speculative-scoring coordinator never syncs the train stream
  8  the fault-site registry is closed, wired, and classify='d
  9  custom VJPs are registered in ops/backward.py and parity-tested
  10 jax.profiler stays confined to telemetry/profiler.py

The four NEW checkers (lock-discipline, donation-safety,
recompile-hazard, collective-axis) are deliberately NOT run here — this
shim's contract is "identical verdicts to the legacy monolith";
``scripts/al_lint.py`` is the full 18-check CLI.

Stdlib + the (jax-free) analysis package only; exits 0 clean / 1 with
findings on stderr.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from active_learning_tpu.analysis.checks import legacy as _legacy  # noqa: E402
from active_learning_tpu.analysis.engine import AstCache  # noqa: E402

PKG = os.path.join(REPO, "active_learning_tpu")

# Historical constants, re-exported for callers that introspect them
# (tests assert the FN tuples stay in lockstep with the modules).
TRACING = _legacy.TRACING
PROFILER = _legacy.PROFILER
ANNOTATION_WHITELIST = _legacy.ANNOTATION_WHITELIST
TRAINER = _legacy.TRAINER
RESIDENT_FEED_FNS = _legacy.RESIDENT_FEED_FNS
KCENTER = _legacy.KCENTER
SHARDED_DEVICE_FNS = _legacy.SHARDED_DEVICE_FNS
SHARDED_ORCHESTRATOR_FNS = _legacy.SHARDED_ORCHESTRATOR_FNS
PIPELINE = _legacy.PIPELINE
PIPELINE_COORDINATOR_FNS = _legacy.PIPELINE_COORDINATOR_FNS
FAULTS_REGISTRY = _legacy.FAULTS_REGISTRY
OPS_BACKWARD = _legacy.OPS_BACKWARD
OPTIM = _legacy.OPTIM
BACKWARD_TESTS = _legacy.BACKWARD_TESTS


def _py_files():
    """The package walk (monkeypatched by tests to point the whole lint
    at fixture fragments — every package-wide check below resolves its
    file set through THIS module-level function)."""
    from active_learning_tpu.analysis.engine import default_files
    return default_files(REPO)


def _render(findings) -> list:
    return [f.render() for f in findings]


def check() -> list:
    """All 10 legacy checks over the tree, one shared parse per file —
    identical verdicts to the monolithic implementation."""
    cache = AstCache()
    files = list(_py_files())
    problems = []
    problems += _legacy.check_phase_timer_span(cache=cache)
    problems += _legacy.check_phase_timer_fork(files=files, cache=cache)
    problems += _legacy.check_phase_timer_import(files=files, cache=cache)
    problems += _legacy.check_trace_annotation(files=files, cache=cache)
    problems += _legacy.check_resident_feed(cache=cache)
    problems += _legacy.check_sharded_selection(cache=cache)
    problems += _legacy.check_pipeline_coordinator(cache=cache)
    problems += _legacy.check_fault_sites(files=files, cache=cache,
                                          full_tree=True)
    problems += _legacy.check_backward_registry(files=files, cache=cache,
                                                full_tree=True)
    problems += _legacy.check_profiler_confinement(files=files,
                                                   cache=cache,
                                                   full_tree=True)
    return _render(problems)


def check_resident_feed(trainer_path: str = None) -> list:
    return _render(_legacy.check_resident_feed(
        trainer_path if trainer_path is not None else TRAINER))


def check_sharded_selection(kcenter_path: str = None) -> list:
    return _render(_legacy.check_sharded_selection(
        kcenter_path if kcenter_path is not None else KCENTER))


def check_pipeline_coordinator(pipeline_path: str = None) -> list:
    return _render(_legacy.check_pipeline_coordinator(
        pipeline_path if pipeline_path is not None else PIPELINE))


def check_fault_sites(files=None, registry_path: str = None) -> list:
    full_tree = files is None
    return _render(_legacy.check_fault_sites(
        files=files if files is not None else list(_py_files()),
        registry_path=(registry_path if registry_path is not None
                       else FAULTS_REGISTRY),
        full_tree=full_tree))


def check_backward_registry(files=None, ops_path: str = None,
                            optim_path: str = None,
                            tests_path: str = None) -> list:
    full_tree = files is None
    return _render(_legacy.check_backward_registry(
        files=files if files is not None else list(_py_files()),
        ops_path=ops_path if ops_path is not None else OPS_BACKWARD,
        optim_path=optim_path if optim_path is not None else OPTIM,
        tests_path=tests_path if tests_path is not None else BACKWARD_TESTS,
        full_tree=full_tree))


def check_profiler_confinement(files=None, profiler_path: str = None
                               ) -> list:
    full_tree = files is None
    return _render(_legacy.check_profiler_confinement(
        files=files if files is not None else list(_py_files()),
        profiler_path=(profiler_path if profiler_path is not None
                       else PROFILER),
        full_tree=full_tree))


def _registered_fault_sites(registry_path: str, problems: list):
    """Legacy helper: parse the SITES tuple, appending rendered problem
    strings into the caller's list."""
    inner = []
    names = _legacy.registered_fault_sites(registry_path, inner)
    problems.extend(_render(inner))
    return names


def main() -> int:
    problems = check()
    for p in problems:
        print(f"trace_lint: {p}", file=sys.stderr)
    if problems:
        return 1
    print("trace_lint: ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
