"""recompile-hazard: the zero-recompile warm-round invariant, statically.

The whole performance story of warm AL rounds rests on "round N+1 adds
zero XLA compiles" (tests/test_compile_reuse.py pins it dynamically; the
``jit_cache_miss_delta`` metric watches it in production).  The two ways
the invariant historically eroded are (a) a ``jax.jit`` sprouting in a
hot-path module outside the step-builder discipline — per-call or
per-round jits whose signatures drift with round state — and (b) a
static operand that is a fresh object every call (an f-string, a
dict/list literal, a lambda): jit hashes statics by value or identity,
so each call is a new cache entry, i.e. a silent recompile per step.

Rules, per hot-path module (train/, strategies/, parallel/, serve/):

  * a module that calls ``jax.jit`` anywhere must declare

        _STEP_BUILDERS = ("_build_train_step", "get_runner", ...)

    and every ``jax.jit`` use must be lexically inside one of those
    functions (module-level jitted defs register their OWN def name —
    they compile once per shape by construction, the registry makes
    them enumerable).  Registry names that match nothing are drift.
  * ``static_argnames``/``static_argnums`` must be literal — a computed
    static set cannot be audited;
  * at same-module call sites of a jitted def, arguments bound to its
    static parameters must not be f-strings (JoinedStr), dict/list/set
    literals or comprehensions, ``dict()``/``list()``/``set()`` calls,
    or lambdas — each is a fresh unhashable/identity-hashed object per
    call: a guaranteed per-call recompile (or TypeError) on a hot path.

Modules outside the hot paths (scripts/) may jit freely — they are
tools, not round code.

Suppression: ``# al-lint: recompile-ok <reason>``.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Tuple

from ..engine import Checker, Context, PKG
from ..findings import Finding

# The hot-path tree: every module under these package dirs is round/
# request code — a stray jit there is a warm-round hazard.
HOT_PATH_DIRS = ("train", "strategies", "parallel", "serve", "experiment",
                 "models", "data", "ops")

_FRESH_OBJECT_CALLS = {"dict", "list", "set"}


def _is_load(node) -> bool:
    ctx = getattr(node, "ctx", None)
    return ctx is None or isinstance(ctx, ast.Load)


def _is_hot_path(path: str) -> bool:
    ap = os.path.abspath(path)
    return any(ap.startswith(os.path.join(PKG, d) + os.sep)
               for d in HOT_PATH_DIRS)


def _jit_call_in(node) -> Optional[ast.Call]:
    """The jit(...) call inside a decorator/assignment expression:
    ``jax.jit`` mentioned anywhere in a Call's func or args."""
    if not isinstance(node, ast.Call):
        return None
    for n in ast.walk(node):
        if (isinstance(n, ast.Attribute) and n.attr == "jit") \
                or (isinstance(n, ast.Name) and n.id == "jit"):
            return node
    return None


def _literal_statics(call: ast.Call, rel: str, problems: List[Finding]
                     ) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """(static names, static positions) from a jit call's keywords;
    non-literal specs are findings."""
    names: Tuple[str, ...] = ()
    nums: Tuple[int, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names = (v.value,)
            elif isinstance(v, (ast.Tuple, ast.List)) and all(
                    isinstance(e, ast.Constant)
                    and isinstance(e.value, str) for e in v.elts):
                names = tuple(e.value for e in v.elts)
            else:
                problems.append(Finding(
                    check="recompile-hazard", path=rel, line=call.lineno,
                    message="static_argnames is not a literal str/tuple "
                            "— the static operand set must be "
                            "statically auditable",
                    hint="spell the statics as a literal tuple of "
                         "strings"))
        elif kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = (v.value,)
            elif isinstance(v, (ast.Tuple, ast.List)) and all(
                    isinstance(e, ast.Constant)
                    and isinstance(e.value, int) for e in v.elts):
                nums = tuple(e.value for e in v.elts)
            else:
                problems.append(Finding(
                    check="recompile-hazard", path=rel, line=call.lineno,
                    message="static_argnums is not a literal int/tuple "
                            "— the static operand set must be "
                            "statically auditable",
                    hint="spell the statics as a literal tuple of ints"))
    return names, nums


def _fresh_object(node) -> Optional[str]:
    """A fresh-per-call object that can never hash stably as a jit
    static: returns a short description or None."""
    if isinstance(node, ast.JoinedStr):
        return "an f-string"
    if isinstance(node, ast.Lambda):
        return "a lambda"
    if isinstance(node, (ast.Dict, ast.DictComp)):
        return "a dict literal"
    if isinstance(node, (ast.List, ast.ListComp)):
        return "a list literal"
    if isinstance(node, (ast.Set, ast.SetComp, ast.GeneratorExp)):
        return "a set/generator literal"
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in _FRESH_OBJECT_CALLS:
        return f"a fresh {node.func.id}() object"
    return None


class _JitDef:
    def __init__(self, fn: ast.FunctionDef, statics: Tuple[str, ...],
                 nums: Tuple[int, ...]):
        self.fn = fn
        self.params = [a.arg for a in fn.args.args]
        self.static_names = set(statics)
        self.static_positions = set(nums) | {
            i for i, a in enumerate(self.params) if a in self.static_names}


class RecompileHazardChecker(Checker):
    id = "recompile-hazard"
    title = ("jax.jit confined to registered step-builders; no "
             "fresh-object static operands")
    suppress_token = "recompile-ok"

    def check(self, ctx: Context) -> List[Finding]:
        problems: List[Finding] = []
        for path in ctx.files:
            tree, err = ctx.tree(path)
            if err is not None:
                continue
            self._check_module(tree, ctx.rel(path),
                               _is_hot_path(path), problems)
        return problems

    def _check_module(self, tree, rel, hot, problems):
        builders = self._builders(tree, rel, problems)
        # Scope: the package hot paths are mandatory; any other module
        # opts IN by declaring _STEP_BUILDERS.  No script does today:
        # the opt-in is how this check's own fixtures, which live
        # outside the package, get it enforced.
        if not hot and builders is None:
            return

        # Function defs that carry a jit decorator, with their statics.
        jit_defs: Dict[str, _JitDef] = {}
        # Walk with the enclosing-builder-fn stack to enforce confinement.
        matched_builders = set()

        handled: set = set()  # jit mentions already reported via a def

        def visit(node, fn_stack):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn_stack = fn_stack + [node.name]
                for dec in node.decorator_list:
                    call = _jit_call_in(dec)
                    if call is None and not (
                            isinstance(dec, ast.Attribute)
                            and dec.attr == "jit") and not (
                            isinstance(dec, ast.Name) and dec.id == "jit"):
                        continue
                    for n in ast.walk(dec):
                        handled.add(id(n))
                    statics, nums = ((), ())
                    if call is not None:
                        statics, nums = _literal_statics(call, rel,
                                                         problems)
                    jit_defs[node.name] = _JitDef(node, statics, nums)
                    self._confine(node.lineno, node.name, fn_stack,
                                  builders, matched_builders, rel,
                                  problems)
            elif ((isinstance(node, ast.Attribute) and node.attr == "jit")
                  or (isinstance(node, ast.Name) and node.id == "jit"
                      and _is_load(node))) \
                    and id(node) not in handled:
                # Any other jit touch — jax.jit, or a bare aliased name
                # (``from jax import jit``) — must also sit inside a
                # registered builder; the import alias is the cheapest
                # evasion of the discipline otherwise.
                self._confine(node.lineno, None, fn_stack, builders,
                              matched_builders, rel, problems)
            for child in ast.iter_child_nodes(node):
                visit(child, fn_stack)

        visit(tree, [])

        if builders is not None:
            for name in sorted(set(builders) - matched_builders):
                problems.append(Finding(
                    check=self.id, path=rel, line=0,
                    message=f"_STEP_BUILDERS names {name!r} but no "
                            "jax.jit use sits inside it — the registry "
                            "drifted from the module",
                    hint="remove the stale entry or restore the builder"))

        self._check_static_call_sites(tree, rel, jit_defs, problems)

    def _builders(self, tree, rel, problems) -> Optional[Tuple[str, ...]]:
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "_STEP_BUILDERS"
                    for t in node.targets):
                if isinstance(node.value, (ast.Tuple, ast.List)) and all(
                        isinstance(e, ast.Constant)
                        and isinstance(e.value, str)
                        for e in node.value.elts):
                    return tuple(e.value for e in node.value.elts)
                problems.append(Finding(
                    check=self.id, path=rel, line=node.lineno,
                    message="_STEP_BUILDERS must be a literal tuple of "
                            "function-name strings"))
                return ()
        return None

    def _confine(self, lineno, def_name, fn_stack, builders,
                 matched_builders, rel, problems):
        if builders is None:
            problems.append(Finding(
                check=self.id, path=rel, line=lineno,
                message="jax.jit in a hot-path module with no "
                        "_STEP_BUILDERS registry — warm-round compile "
                        "discipline cannot be audited",
                hint="declare _STEP_BUILDERS = (...) naming the "
                     "step-builder functions (or the jitted def itself)"))
            return
        hits = [n for n in fn_stack if n in builders]
        if hits:
            matched_builders.update(hits)
            return
        problems.append(Finding(
            check=self.id, path=rel, line=lineno,
            message=("jax.jit outside the registered step-builders "
                     f"({', '.join(builders) or 'none declared'}) — "
                     "every hot-path jit flows through a registered "
                     "builder so warm rounds provably add zero compiles"),
            hint="move the jit into a registered builder or add the "
                 "containing function to _STEP_BUILDERS"))

    def _check_static_call_sites(self, tree, rel, jit_defs, problems):
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in jit_defs):
                continue
            jd = jit_defs[node.func.id]
            starred = next((i for i, a in enumerate(node.args)
                            if isinstance(a, ast.Starred)),
                           len(node.args))
            for i, arg in enumerate(node.args):
                if i >= starred:
                    break
                if i in jd.static_positions:
                    desc = _fresh_object(arg)
                    if desc:
                        self._static_finding(node, rel, jd, i, desc,
                                             problems)
            for kw in node.keywords:
                if kw.arg in jd.static_names:
                    desc = _fresh_object(kw.value)
                    if desc:
                        self._static_finding(node, rel, jd, kw.arg, desc,
                                             problems)

    def _static_finding(self, call, rel, jd, which, desc, problems):
        problems.append(Finding(
            check=self.id, path=rel, line=call.lineno,
            message=(f"{jd.fn.name}() receives {desc} as static operand "
                     f"{which!r} — a fresh object per call means a "
                     "recompile per call on a hot path"),
            hint="pass a hashable, value-stable static (str/int/bool/"
                 "frozen config) or make the operand traced"))
