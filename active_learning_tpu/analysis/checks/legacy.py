"""The 10 legacy trace_lint checks, ported verbatim onto the engine.

Each function here is a line-for-line port of one check from the old
773-line ``scripts/trace_lint.py`` monolith: same inputs, same verdicts,
same message TEXT (tests/test_telemetry.py asserts on those substrings
and fragment counts), now reading every tree through the shared
``AstCache`` instead of re-parsing per check.  ``scripts/trace_lint.py``
survives as a thin compatibility shim over these functions, so its
import surface (check(), check_resident_feed(), _registered_fault_sites,
the FN-tuple constants) keeps working unchanged.

The check numbering (1-10) and the invariant each enforces are
documented in the shim's module docstring and DESIGN.md §12; ids here:

  1  phase-timer-span      phase_timer derives its seconds from a span
  2  phase-timer-fork      nobody else defines a phase_timer
  3  phase-timer-import    call sites import it from utils.tracing
  4  trace-annotation      TraceAnnotation is opened by SpanTracer.span only
  5  resident-feed         zero-host-copy resident train feed
  6  sharded-selection     row-sharded selection never un-shards
  7  pipeline-coordinator  speculative scorer never syncs the train stream
  8  fault-sites           closed fault registry, classify= at retries
  9  backward-registry     custom VJPs registered + parity-tested
  10 profiler-confinement  jax.profiler confined to the gate module

No suppressions: the ported checks must produce IDENTICAL verdicts to
the monolith they replace (the acceptance contract of the port), so the
``# al-lint:`` annotation machinery deliberately does not apply here.
"""

from __future__ import annotations

import ast
import os
import re
from typing import List, Optional

from ..engine import AstCache, Checker, Context, PKG, REPO, default_files
from ..findings import Finding

TRACING = os.path.join(PKG, "utils", "tracing.py")
PROFILER = os.path.join(PKG, "telemetry", "profiler.py")

# The one module allowed to touch jax.profiler (TraceAnnotation included):
# the device-truth layer.  Its trace_annotation is handed to the span
# tracer as a hook (runtime.start_run) and CALLED nowhere else: one
# annotation per span, under the span's own name.
ANNOTATION_WHITELIST = {PROFILER}

_CAPTURE_CALLS = {"start_trace", "stop_trace"}
_PROFILER_GATE_FNS = ("start_capture", "finish_capture", "capture_window",
                      "trace_annotation")

TRAINER = os.path.join(PKG, "train", "trainer.py")
RESIDENT_FEED_FNS = ("_resident_feed_arrays", "_build_resident_batch_step")
_HOST_COPY_CALLS = {"gather", "asarray", "concatenate", "ascontiguousarray",
                    "stack", "copy"}

KCENTER = os.path.join(PKG, "strategies", "kcenter.py")
SHARDED_DEVICE_FNS = ("_build_sharded_fns",)
SHARDED_ORCHESTRATOR_FNS = ("_kcenter_greedy_sharded",)
_SHARDED_HOST_CALLS = {"device_get", "asarray"}
_SHARDED_REPLICATE_CALLS = {"replicate", "replicated_sharding"}

PIPELINE = os.path.join(PKG, "experiment", "pipeline.py")
PIPELINE_COORDINATOR_FNS = ("_worker", "_worker_loop", "_score_slice",
                            "_score_chunk", "publish_best", "finalize",
                            "consume")
_PIPELINE_SYNC_CALLS = {"block_until_ready", "device_get"}

FAULTS_REGISTRY = os.path.join(PKG, "faults", "registry.py")

OPS_BACKWARD = os.path.join(PKG, "ops", "backward.py")
OPTIM = os.path.join(PKG, "train", "optim.py")
BACKWARD_TESTS = os.path.join(REPO, "tests", "test_backward.py")
_FUSED_HOST_CALLS = {"asarray", "device_get", "block_until_ready",
                     "gather"}


def _rel(path: str) -> str:
    return os.path.relpath(os.path.abspath(path), REPO)


def _mk(check_id: str, path: str, line: int, message: str) -> Finding:
    return Finding(check=check_id, path=_rel(path), line=line,
                   message=message)


def _tree(cache: Optional[AstCache], path: str):
    """(tree, error) through the shared cache (a private one when the
    caller runs a fragment outside an engine run)."""
    return (cache or AstCache()).get(path)


# -- checks 1-3: phase_timer is ONE measurement ------------------------------

def check_phase_timer_span(tracing_path: str = TRACING,
                           cache: Optional[AstCache] = None
                           ) -> List[Finding]:
    """Check 1: ``phase_timer`` itself opens a tracer span, reports the
    span's own seconds (two clocks = metric/trace drift) and opens no
    device annotation of its own (the span carries one: a second would
    put two names on one interval)."""
    cache = cache or AstCache()
    problems: List[Finding] = []
    src = cache.source(tracing_path)
    if not src:
        tree, err = cache.get(tracing_path)
        if err is not None:
            return [_mk("phase-timer-span", tracing_path, 0,
                        f"unreadable for the phase-timer check ({err})")]
    timer_body = src.split("def phase_timer", 1)
    if len(timer_body) != 2:
        problems.append(_mk("phase-timer-span", tracing_path, 0,
                            "phase_timer not found"))
        timer_src = ""
    else:
        # Up to the next top-level def.
        timer_src = re.split(r"\n@|\ndef ", timer_body[1], maxsplit=1)[0]
    if ".span(" not in timer_src:
        problems.append(_mk(
            "phase-timer-span", tracing_path, 0,
            "phase_timer does not open a tracer span — phase metrics "
            "would fork from the trace"))
    if "duration_s" not in timer_src:
        problems.append(_mk(
            "phase-timer-span", tracing_path, 0,
            "phase_timer does not take its seconds from the span (two "
            "clocks = metric/trace drift)"))
    if re.search(r"\b(annotate|trace_annotation)\(", timer_src):
        problems.append(_mk(
            "phase-timer-span", tracing_path, 0,
            "phase_timer opens a device annotation of its own — the "
            "span it opens already carries one (two names per span)"))
    return problems


def check_phase_timer_fork(files=None, tracing_path: str = TRACING,
                           cache: Optional[AstCache] = None
                           ) -> List[Finding]:
    """Check 2: no competing ``phase_timer`` definitions anywhere.  This
    check also owns the one 'unparseable' finding per broken file (the
    legacy per-file loop emitted it once for checks 2-4 together)."""
    cache = cache or AstCache()
    problems: List[Finding] = []
    for path in (files if files is not None else default_files()):
        if os.path.abspath(path) == os.path.abspath(tracing_path):
            continue
        tree, err = cache.get(path)
        if err is not None:
            problems.append(_mk("phase-timer-fork", path, 0,
                                f"unparseable ({err})"))
            continue
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == "phase_timer":
                problems.append(_mk(
                    "phase-timer-fork", path, node.lineno,
                    "defines its own phase_timer — route through "
                    "utils.tracing"))
    return problems


def _imports_phase_timer_from_tracing(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.endswith("tracing") and any(
                    a.name == "phase_timer" for a in node.names):
                return True
    return False


def check_phase_timer_import(files=None, tracing_path: str = TRACING,
                             cache: Optional[AstCache] = None
                             ) -> List[Finding]:
    """Check 3: every ``phase_timer(`` call site imports it from
    utils.tracing — no copies, no local re-implementations."""
    cache = cache or AstCache()
    problems: List[Finding] = []
    for path in (files if files is not None else default_files()):
        if os.path.abspath(path) == os.path.abspath(tracing_path):
            continue
        tree, err = cache.get(path)
        if err is not None:
            continue  # check 2 already reported the parse failure
        calls = [n for n in ast.walk(tree)
                 if isinstance(n, ast.Call)
                 and isinstance(n.func, ast.Name)
                 and n.func.id == "phase_timer"]
        if calls and not _imports_phase_timer_from_tracing(tree):
            problems.append(_mk(
                "phase-timer-import", path, calls[0].lineno,
                "calls phase_timer without importing it from "
                "utils.tracing"))
    return problems


def check_trace_annotation(files=None, whitelist=None,
                           cache: Optional[AstCache] = None
                           ) -> List[Finding]:
    """Check 4: a device annotation is opened by ``SpanTracer.span``
    and nobody else.  ``jax.profiler.TraceAnnotation`` stays inside the
    gate module (AST-level: docstring mentions are fine, attribute uses
    are not), and the gate's ``trace_annotation`` is CALLED nowhere
    outside it — the run hands the function to the tracer as its
    ``annotate`` hook (a reference, not a call), so every annotation has
    a span of the same name and no span has two."""
    cache = cache or AstCache()
    whitelist = ({os.path.abspath(p) for p in whitelist}
                 if whitelist is not None
                 else {os.path.abspath(p) for p in ANNOTATION_WHITELIST})
    problems: List[Finding] = []
    for path in (files if files is not None else default_files()):
        if os.path.abspath(path) in whitelist:
            continue
        tree, err = cache.get(path)
        if err is not None:
            continue  # check 2 already reported the parse failure
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "TraceAnnotation":
                problems.append(_mk(
                    "trace-annotation", path, node.lineno,
                    "uses jax.profiler.TraceAnnotation directly — open a "
                    "span (SpanTracer.span annotates under the span's "
                    "own name) so device spans keep one naming "
                    "convention"))
            if isinstance(node, ast.Call):
                fn = node.func
                called = (fn.attr if isinstance(fn, ast.Attribute)
                          else fn.id if isinstance(fn, ast.Name) else "")
                if called == "trace_annotation":
                    problems.append(_mk(
                        "trace-annotation", path, node.lineno,
                        "calls trace_annotation() — a device annotation "
                        "without a span; open a span instead "
                        "(SpanTracer.span is the only opener, through "
                        "its annotate hook)"))
    return problems


# -- check 5: the resident train feed stays zero-host-copy -------------------

def check_resident_feed(trainer_path: str = TRAINER,
                        cache: Optional[AstCache] = None) -> List[Finding]:
    """The zero-host-copy invariant, statically: the trainer functions in
    RESIDENT_FEED_FNS may look up the shared device cache and do index
    math, but any ``np.`` reference or host-materializing call
    (``.gather``/``.asarray``/``.concatenate``/...) inside them means an
    image array crossed back to the host on the resident feed path."""
    problems: List[Finding] = []
    tree, err = _tree(cache, trainer_path)
    if err is not None:
        return [_mk("resident-feed", trainer_path, 0,
                    f"unreadable for the resident-feed check ({err})")]
    fns = {node.name: node for node in ast.walk(tree)
           if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for name in RESIDENT_FEED_FNS:
        fn = fns.get(name)
        if fn is None:
            problems.append(_mk(
                "resident-feed", trainer_path, 0,
                f"resident-feed function {name} not found — the "
                "zero-host-copy enforcement has nothing to check"))
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id == "np":
                problems.append(_mk(
                    "resident-feed", trainer_path, node.lineno,
                    f"{name} references np — the resident train feed "
                    "must never materialize image arrays on the host"))
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _HOST_COPY_CALLS:
                problems.append(_mk(
                    "resident-feed", trainer_path, node.lineno,
                    f"{name} calls .{node.func.attr}() — host "
                    "materialization on the resident train feed path"))
    return problems


# -- check 6: the sharded selection backend never un-shards ------------------

def check_sharded_selection(kcenter_path: str = KCENTER,
                            cache: Optional[AstCache] = None
                            ) -> List[Finding]:
    """The sharded pool's scale-out invariant, statically (check 6): the
    row-sharded selection backend may move O(N) vectors and O(q) rows,
    but a ``jax.device_get``/``np.asarray`` of the pool, an ``np.``
    reference in the device tier, or a ``replicate``/
    ``replicated_sharding`` call means the [N, D] factor matrix came
    back whole onto one host or chip."""
    problems: List[Finding] = []
    tree, err = _tree(cache, kcenter_path)
    if err is not None:
        return [_mk("sharded-selection", kcenter_path, 0,
                    f"unreadable for the sharded-selection check ({err})")]
    fns = {node.name: node for node in ast.walk(tree)
           if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))}

    def call_name(node) -> str:
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Attribute):
                return node.func.attr
            if isinstance(node.func, ast.Name):
                return node.func.id
        return ""

    for name in SHARDED_DEVICE_FNS + SHARDED_ORCHESTRATOR_FNS:
        fn = fns.get(name)
        if fn is None:
            problems.append(_mk(
                "sharded-selection", kcenter_path, 0,
                f"sharded-selection function {name} not found — the "
                "scale-out enforcement has nothing to check"))
            continue
        device_tier = name in SHARDED_DEVICE_FNS
        for node in ast.walk(fn):
            if device_tier and isinstance(node, ast.Name) \
                    and node.id == "np":
                problems.append(_mk(
                    "sharded-selection", kcenter_path, node.lineno,
                    f"{name} references np — the sharded selection "
                    "backend must never materialize pool state on the "
                    "host"))
            called = call_name(node)
            if device_tier and called in _SHARDED_HOST_CALLS:
                problems.append(_mk(
                    "sharded-selection", kcenter_path, node.lineno,
                    f"{name} calls .{called}() — host materialization "
                    "inside the sharded selection backend"))
            if not device_tier and called == "device_get":
                problems.append(_mk(
                    "sharded-selection", kcenter_path, node.lineno,
                    f"{name} calls device_get — the sharded pool must "
                    "never round-trip to host"))
            if called in _SHARDED_REPLICATE_CALLS:
                problems.append(_mk(
                    "sharded-selection", kcenter_path, node.lineno,
                    f"{name} calls {called}() — replicating a "
                    "row-sharded array rebuilds the single-chip ceiling "
                    "the sharded pool removes"))
    return problems


# -- check 7: the pipeline coordinator never syncs the train stream ----------

def check_pipeline_coordinator(pipeline_path: str = PIPELINE,
                               cache: Optional[AstCache] = None
                               ) -> List[Finding]:
    """The pipelined round's overlap invariant, statically (check 7):
    the speculative-scoring coordinator functions may enqueue device
    work and wait on host-side conditions, but a ``block_until_ready``
    or ``device_get`` call inside them would sync the train stream's
    arrays."""
    problems: List[Finding] = []
    tree, err = _tree(cache, pipeline_path)
    if err is not None:
        return [_mk("pipeline-coordinator", pipeline_path, 0,
                    "unreadable for the pipeline-coordinator check "
                    f"({err})")]
    fns = {node.name: node for node in ast.walk(tree)
           if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for name in PIPELINE_COORDINATOR_FNS:
        fn = fns.get(name)
        if fn is None:
            problems.append(_mk(
                "pipeline-coordinator", pipeline_path, 0,
                f"pipeline coordinator function {name} not found — the "
                "never-sync enforcement has nothing to check"))
            continue
        for node in ast.walk(fn):
            called = ""
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Attribute):
                    called = node.func.attr
                elif isinstance(node.func, ast.Name):
                    called = node.func.id
            if called in _PIPELINE_SYNC_CALLS:
                problems.append(_mk(
                    "pipeline-coordinator", pipeline_path, node.lineno,
                    f"{name} calls {called} — the speculative-scoring "
                    "coordinator must never sync the train stream "
                    "(DESIGN.md §8)"))
    return problems


# -- check 8: the fault registry is closed, wired, and classified ------------

def registered_fault_sites(registry_path: str, problems: List[Finding],
                           cache: Optional[AstCache] = None):
    """Parse faults/registry.py's ``SITES`` tuple; duplicate names are a
    finding (each site registered EXACTLY once)."""
    tree, err = _tree(cache, registry_path)
    if err is not None:
        problems.append(_mk("fault-sites", registry_path, 0,
                            f"unreadable for the fault-site check ({err})"))
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "SITES"
                for t in node.targets):
            if not isinstance(node.value, (ast.Tuple, ast.List)):
                break
            names = []
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value,
                                                                str):
                    names.append(elt.value)
                else:
                    problems.append(_mk(
                        "fault-sites", registry_path, elt.lineno,
                        "SITES holds a non-literal entry — the registry "
                        "must be statically checkable"))
            for name in set(names):
                if names.count(name) > 1:
                    problems.append(_mk(
                        "fault-sites", registry_path, 0,
                        f"site {name!r} registered more than once in "
                        "SITES — each site is registered exactly once"))
            return names
    problems.append(_mk("fault-sites", registry_path, 0,
                        "SITES tuple not found — the fault-site registry "
                        "has nothing to check against"))
    return None


def check_fault_sites(files=None, registry_path: str = FAULTS_REGISTRY,
                      cache: Optional[AstCache] = None,
                      full_tree: Optional[bool] = None) -> List[Finding]:
    """The failure model's closed-registry invariant, statically
    (check 8): every ``faults.site()``/``site()`` call names a
    registered site as a string literal, every registered site is wired
    at ≥1 call site (full-tree mode only — ``files`` given means a
    negative-case unit test on a fragment), and every ``RetryPolicy``
    construction passes ``classify=`` explicitly.  ``full_tree`` lets
    the trace_lint shim pass an explicit (possibly monkeypatched) file
    list while keeping full-tree semantics."""
    cache = cache or AstCache()
    problems: List[Finding] = []
    registered = registered_fault_sites(registry_path, problems,
                                        cache=cache)
    if registered is None:
        return problems
    if full_tree is None:
        full_tree = files is None
    paths = list(files) if files is not None else list(default_files())
    wired = set()
    for path in paths:
        if os.path.abspath(path) == os.path.abspath(registry_path):
            continue  # the definition site, not a call site
        tree, err = cache.get(path)
        if err is not None:
            problems.append(_mk(
                "fault-sites", path, 0,
                f"unreadable for the fault-site check ({err})"))
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            is_site = (
                (isinstance(fn, ast.Attribute) and fn.attr == "site"
                 and isinstance(fn.value, ast.Name)
                 and fn.value.id == "faults")
                or (isinstance(fn, ast.Name) and fn.id == "site"))
            is_retry = ((isinstance(fn, ast.Attribute)
                         and fn.attr == "RetryPolicy")
                        or (isinstance(fn, ast.Name)
                            and fn.id == "RetryPolicy"))
            if is_site:
                arg = node.args[0] if node.args else None
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)):
                    problems.append(_mk(
                        "fault-sites", path, node.lineno,
                        "faults.site() with a non-literal site name — "
                        "the closed registry cannot be checked"))
                elif arg.value not in registered:
                    problems.append(_mk(
                        "fault-sites", path, node.lineno,
                        f"faults.site({arg.value!r}) names an "
                        "unregistered site (registry: faults/registry.py "
                        "SITES)"))
                else:
                    wired.add(arg.value)
            if is_retry and not any(kw.arg == "classify"
                                    for kw in node.keywords):
                problems.append(_mk(
                    "fault-sites", path, node.lineno,
                    "RetryPolicy(...) without an explicit classify= — "
                    "every retry call site states its transient-vs-fatal "
                    "rule (no bare retries)"))
    if full_tree:
        for name in registered:
            if name not in wired:
                problems.append(Finding(
                    check="fault-sites", path="faults/registry.py", line=0,
                    message=(f"site {name!r} is registered but wired at "
                             "no call site — chaos coverage for it is "
                             "vacuous")))
    return problems


# -- check 9: every custom VJP is registered and parity-tested ---------------

def _str_tuple(tree: ast.AST, name: str, rel: str,
               problems: List[Finding], check_id: str):
    """Parse a module-level ``NAME = ("a", "b", ...)`` tuple of string
    literals; returns None (with a finding) when absent/non-literal."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets):
            if not isinstance(node.value, (ast.Tuple, ast.List)):
                break
            names = []
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value,
                                                                str):
                    names.append(elt.value)
                else:
                    problems.append(Finding(
                        check=check_id, path=rel, line=elt.lineno,
                        message=(f"{name} holds a non-literal entry — "
                                 "the registry must be statically "
                                 "checkable")))
            return names
    problems.append(Finding(
        check=check_id, path=rel, line=0,
        message=(f"{name} tuple not found — the backward registry has "
                 "nothing to check against")))
    return None


def check_backward_registry(files=None, ops_path: str = OPS_BACKWARD,
                            optim_path: str = OPTIM,
                            tests_path: str = BACKWARD_TESTS,
                            cache: Optional[AstCache] = None,
                            full_tree: Optional[bool] = None
                            ) -> List[Finding]:
    """The gradient path's proven-backward invariant, statically
    (check 9): custom VJPs only in ops/backward.py, every one named in
    its ``TRAIN_PATH_VJPS`` and matched by ``PARITY_TESTED_VJPS`` in
    tests/test_backward.py, and the fused optimizer-update functions
    free of host materialization.  ``files`` given = a negative-case
    unit test on a fragment (the custom_vjp location scan only);
    ``full_tree`` lets the shim pass an explicit file list while keeping
    full-tree semantics."""
    cache = cache or AstCache()
    problems: List[Finding] = []

    # a) custom_vjp usage is confined to ops/backward.py.
    if full_tree is None:
        full_tree = files is None
    paths = list(files) if files is not None else list(default_files())
    for path in paths:
        if os.path.abspath(path) == os.path.abspath(ops_path):
            continue
        tree, err = cache.get(path)
        if err is not None:
            problems.append(_mk(
                "backward-registry", path, 0,
                f"unreadable for the backward-registry check ({err})"))
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "custom_vjp":
                problems.append(_mk(
                    "backward-registry", path, node.lineno,
                    "jax.custom_vjp outside ops/backward.py — "
                    "hand-written backwards live in the closed registry "
                    "(TRAIN_PATH_VJPS) so each one carries a "
                    "gradient-parity test"))
    if not full_tree:
        return problems

    # b) the registry itself: TRAIN_PATH_VJPS names exist as defs and
    # the module really uses custom_vjp.
    rel_ops = _rel(ops_path)
    ops_tree, err = cache.get(ops_path)
    if err is not None:
        return problems + [_mk(
            "backward-registry", ops_path, 0,
            f"unreadable for the backward-registry check ({err})")]
    registered = _str_tuple(ops_tree, "TRAIN_PATH_VJPS", rel_ops, problems,
                            "backward-registry")
    if registered is None:
        return problems
    defs = {n.name for n in ast.walk(ops_tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for name in registered:
        if name not in defs:
            problems.append(_mk(
                "backward-registry", ops_path, 0,
                f"TRAIN_PATH_VJPS names {name!r} but no such function is "
                "defined — the registry drifted from the module"))
    if not any(isinstance(n, ast.Attribute) and n.attr == "custom_vjp"
               for n in ast.walk(ops_tree)):
        problems.append(_mk(
            "backward-registry", ops_path, 0,
            "no jax.custom_vjp usage found — TRAIN_PATH_VJPS registers "
            "backwards that do not exist"))

    # c) every registered VJP has a registered parity test.
    rel_tests = _rel(tests_path)
    tests_tree, err = cache.get(tests_path)
    if err is not None:
        return problems + [_mk(
            "backward-registry", tests_path, 0,
            f"unreadable — every custom VJP must carry a parity test "
            f"({err})")]
    tested = _str_tuple(tests_tree, "PARITY_TESTED_VJPS", rel_tests,
                        problems, "backward-registry")
    if tested is not None and set(tested) != set(registered):
        problems.append(_mk(
            "backward-registry", tests_path, 0,
            f"PARITY_TESTED_VJPS {sorted(tested)} != TRAIN_PATH_VJPS "
            f"{sorted(registered)} — a custom backward without a "
            "registered gradient-parity test (or a stale test "
            "registration) can never land"))

    # d) the fused update functions never touch the host.
    optim_tree, err = cache.get(optim_path)
    if err is not None:
        return problems + [_mk(
            "backward-registry", optim_path, 0,
            f"unreadable for the fused-update check ({err})")]
    fused = _str_tuple(optim_tree, "FUSED_UPDATE_FNS", _rel(optim_path),
                       problems, "backward-registry")
    if fused is None:
        return problems
    fns = {n.name: n for n in ast.walk(optim_tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for name in fused:
        fn = fns.get(name)
        if fn is None:
            problems.append(_mk(
                "backward-registry", optim_path, 0,
                f"FUSED_UPDATE_FNS names {name!r} but no such function "
                "is defined"))
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id == "np":
                problems.append(_mk(
                    "backward-registry", optim_path, node.lineno,
                    f"{name} references np — the fused update traces "
                    "inside the donated train step and must never "
                    "materialize state on the host"))
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _FUSED_HOST_CALLS:
                problems.append(_mk(
                    "backward-registry", optim_path, node.lineno,
                    f"{name} calls .{node.func.attr}() — host "
                    "materialization inside the fused optimizer update"))
    return problems


# -- check 10: jax.profiler stays confined to the gate -----------------------

def check_profiler_confinement(files=None, profiler_path: str = PROFILER,
                               cache: Optional[AstCache] = None,
                               full_tree: Optional[bool] = None
                               ) -> List[Finding]:
    """The device-truth layer's one-gate invariant, statically
    (check 10): ``jax.profiler`` imports/attribute access and
    ``start_trace``/``stop_trace`` calls are confined to
    telemetry/profiler.py, and that module really defines the gated API
    and touches jax.profiler.  ``files`` given = a negative-case unit
    test on a fragment (the confinement scan only); ``full_tree`` lets
    the shim pass an explicit file list while keeping full-tree
    semantics."""
    cache = cache or AstCache()
    problems: List[Finding] = []
    if full_tree is None:
        full_tree = files is None
    paths = list(files) if files is not None else list(default_files())
    for path in paths:
        if os.path.abspath(path) == os.path.abspath(profiler_path):
            continue
        tree, err = cache.get(path)
        if err is not None:
            problems.append(_mk(
                "profiler-confinement", path, 0,
                f"unreadable for the profiler-confinement check ({err})"))
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax.profiler" \
                            or alias.name.startswith("jax.profiler."):
                        problems.append(_mk(
                            "profiler-confinement", path, node.lineno,
                            "imports jax.profiler outside telemetry/"
                            "profiler.py — capture windows and device "
                            "annotations go through the gated API "
                            "(DESIGN.md §11)"))
            if isinstance(node, ast.ImportFrom) and node.module:
                if (node.module == "jax"
                        and any(a.name == "profiler"
                                for a in node.names)) \
                        or node.module.startswith("jax.profiler"):
                    problems.append(_mk(
                        "profiler-confinement", path, node.lineno,
                        "imports jax's profiler outside telemetry/"
                        "profiler.py — use the gated API"))
            if isinstance(node, ast.Attribute) \
                    and node.attr == "profiler" \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "jax":
                problems.append(_mk(
                    "profiler-confinement", path, node.lineno,
                    "touches jax.profiler outside telemetry/profiler.py "
                    "— the device-truth layer is the one gate"))
            if isinstance(node, ast.Call):
                fn = node.func
                called = (fn.attr if isinstance(fn, ast.Attribute)
                          else fn.id if isinstance(fn, ast.Name) else "")
                if called in _CAPTURE_CALLS:
                    problems.append(_mk(
                        "profiler-confinement", path, node.lineno,
                        f"calls {called}() outside telemetry/profiler.py "
                        "— every capture window goes through the gated "
                        "API (capture_window/start_capture/"
                        "finish_capture)"))
    if not full_tree:
        return problems

    # The gate module itself: the API exists and jax.profiler is really
    # touched (otherwise the confinement above confines nothing).
    gate_tree, err = cache.get(profiler_path)
    if err is not None:
        return problems + [_mk(
            "profiler-confinement", profiler_path, 0,
            f"unreadable for the profiler-gate check ({err})")]
    defs = {n.name for n in ast.walk(gate_tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    for name in _PROFILER_GATE_FNS:
        if name not in defs:
            problems.append(_mk(
                "profiler-confinement", profiler_path, 0,
                f"gated API function {name} not found — the "
                "capture-window enforcement has nothing to point at"))
    touches = any(
        isinstance(n, ast.Import) and any(
            a.name == "jax.profiler" for a in n.names)
        for n in ast.walk(gate_tree))
    if not touches:
        problems.append(_mk(
            "profiler-confinement", profiler_path, 0,
            "never imports jax.profiler — the gate module is not "
            "actually the gate"))
    return problems


# -- Checker plugins over the functions above --------------------------------

class _LegacyChecker(Checker):
    """Bind one ported function into the plugin registry.  ``files_arg``
    True = the function takes the engine's file set (the package-wide
    scans); False = it targets fixed module paths only.
    ``full_tree_arg`` True = the function distinguishes fragment mode
    from whole-tree mode (the registry-level sub-checks: unwired fault
    sites, VJP parity, the profiler gate module) — the engine's file
    set IS the whole tree, so the plugin passes full_tree=True; without
    it those sub-checks would silently not run on the al_lint path."""

    def __init__(self, check_id: str, title: str, fn, files_arg: bool,
                 full_tree_arg: bool = False):
        self.id = check_id
        self.title = title
        self._fn = fn
        self._files_arg = files_arg
        self._full_tree_arg = full_tree_arg

    def check(self, ctx: Context) -> List[Finding]:
        if self._full_tree_arg:
            return self._fn(files=ctx.files, cache=ctx.cache,
                            full_tree=True)
        if self._files_arg:
            return self._fn(files=ctx.files, cache=ctx.cache)
        return self._fn(cache=ctx.cache)


LEGACY_CHECKERS = (
    _LegacyChecker("phase-timer-span",
                   "phase_timer derives its seconds from ONE tracer span",
                   check_phase_timer_span, files_arg=False),
    _LegacyChecker("phase-timer-fork",
                   "no competing phase_timer definitions",
                   check_phase_timer_fork, files_arg=True),
    _LegacyChecker("phase-timer-import",
                   "phase_timer call sites import it from utils.tracing",
                   check_phase_timer_import, files_arg=True),
    _LegacyChecker("trace-annotation",
                   "jax.profiler.TraceAnnotation is opened by "
                   "SpanTracer.span only",
                   check_trace_annotation, files_arg=True),
    _LegacyChecker("resident-feed",
                   "resident train feed never materializes images on host",
                   check_resident_feed, files_arg=False),
    _LegacyChecker("sharded-selection",
                   "row-sharded selection never un-shards the pool",
                   check_sharded_selection, files_arg=False),
    _LegacyChecker("pipeline-coordinator",
                   "speculative-scoring coordinator never syncs the train "
                   "stream",
                   check_pipeline_coordinator, files_arg=False),
    _LegacyChecker("fault-sites",
                   "closed fault-site registry, explicit classify= at "
                   "every RetryPolicy",
                   check_fault_sites, files_arg=True,
                   full_tree_arg=True),
    _LegacyChecker("backward-registry",
                   "custom VJPs registered in ops/backward.py and "
                   "parity-tested",
                   check_backward_registry, files_arg=True,
                   full_tree_arg=True),
    _LegacyChecker("profiler-confinement",
                   "jax.profiler confined to the telemetry/profiler.py "
                   "gate",
                   check_profiler_confinement, files_arg=True,
                   full_tree_arg=True),
)
