"""donation-safety: no use-after-donate of jitted buffers.

``donate_argnums`` hands an argument's device buffer to XLA for in-place
reuse — after the call the Python reference points at a DELETED array,
and touching it again raises (or, with a stale view, silently reads
garbage).  PR 9's ``reinit_optimizer`` dodged exactly this by hand; this
checker proves it for the whole tree:

  * every ``jax.jit(..., donate_argnums=...)`` def is discovered (the
    ``@functools.partial(jax.jit, donate_argnums=(...))`` decorator
    spelling and direct ``jax.jit(fn, ...)`` calls), and its
    ``donate_argnums`` must be a LITERAL int/tuple — a computed donation
    set cannot be checked;
  * modules whose donating steps are stored on attributes (the trainer's
    ``self._train_step`` family) declare them:

        _DONATES = {"_train_step": (0,), "_epoch_scan": (0,)}

    and every declared name must actually be assigned somewhere in the
    module (registry drift is a finding);
  * at every call site of a donating callable — by local name inside the
    def's own enclosing scope, or by attribute name from ``_DONATES`` —
    the argument expression at each donated position (a plain name,
    dotted path, or literal-keyed subscript) must not be READ again in
    the enclosing function after the call: a statement that rebinds the
    path (``state, ... = step(state, ...)``) clears it; a later
    rebinding kills the taint; a call inside a loop without a same-
    statement rebind taints the whole loop body (the next iteration
    reads the donated buffer).

  Calls inside jit-decorated functions are SKIPPED: donation of a traced
  value inside another trace is a no-op, not a hazard.  Arguments that
  are fresh expressions (``f(jnp.asarray(x))``) are unobservable after
  the call and therefore safe.  Positions hidden behind ``*args``
  splats are not resolvable statically and are skipped.

Suppression: ``# al-lint: donated-ok <reason>`` on the call (or use)
line; the reason string is REQUIRED and rides into the --json report.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from ..engine import Checker, Context
from ..findings import Finding


def _is_jit_expr(node) -> bool:
    """True when the expression mentions ``jit`` (jax.jit / an aliased
    jit name) — used both for decorator detection and traced-context
    exemption."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == "jit":
            return True
        if isinstance(n, ast.Name) and n.id == "jit":
            return True
    return False


def _donate_positions(call: ast.Call):
    """The literal donate_argnums of a jit(...) call expression:
    (positions tuple, None) or (None, error string) when non-literal."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,), None
        if isinstance(v, (ast.Tuple, ast.List)) and all(
                isinstance(e, ast.Constant) and isinstance(e.value, int)
                for e in v.elts):
            return tuple(e.value for e in v.elts), None
        return None, ("donate_argnums is not a literal int/tuple — the "
                      "donation set must be statically checkable")
    return None, None


def _path_of(node) -> Optional[Tuple[str, ...]]:
    """A checkable access path: Name -> ("x",), Attribute chains ->
    ("self", "vaal_state"), literal-keyed Subscripts -> ("oh", "['p']").
    None for anything else (fresh temporaries are safe by construction).
    """
    if isinstance(node, ast.Name):
        return (node.id,)
    if isinstance(node, ast.Attribute):
        base = _path_of(node.value)
        return None if base is None else base + (node.attr,)
    if isinstance(node, ast.Subscript) \
            and isinstance(node.slice, ast.Constant) \
            and isinstance(node.slice.value, (str, int)):
        base = _path_of(node.value)
        return None if base is None else base + (f"[{node.slice.value!r}]",)
    return None


def _assigned_paths(stmt) -> List[Tuple[str, ...]]:
    """Paths a statement REBINDS (Assign/AnnAssign/AugAssign/For
    targets; tuple/list targets flattened — but not walked deeper:
    ``state.opt_state = x`` rebinds the attribute path, not ``state``
    itself)."""
    targets = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        targets = [stmt.target]
    flat = []
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            flat.extend(t.elts)
        else:
            flat.append(t)
    out = []
    for t in flat:
        p = _path_of(t)
        if p is not None:
            out.append(p)
    return out


def _is_load(node) -> bool:
    ctx = getattr(node, "ctx", None)
    return ctx is None or isinstance(ctx, ast.Load)


def _reads_path(stmt, path) -> bool:
    """True when ``stmt`` LOADS ``path`` or any extension of it (reading
    ``state.params`` after donating ``state`` is still a read of the
    dead buffer's tree).  Store/Del contexts don't count — an
    assignment TARGET is a rebind, not a read."""
    for n in ast.walk(stmt):
        if not _is_load(n):
            continue
        p = _path_of(n)
        if p is not None and len(p) >= len(path) \
                and p[:len(path)] == path:
            return True
    return False


def _contains(root, node) -> bool:
    for n in ast.walk(root):
        if n is node:
            return True
    return False


class _Scope:
    """One discovered donating callable: name, donated positions, and
    the AST scope its bare name is visible in (module or enclosing
    function)."""

    def __init__(self, name: str, positions: Tuple[int, ...], scope_node):
        self.name = name
        self.positions = positions
        self.scope_node = scope_node


class DonationSafetyChecker(Checker):
    id = "donation-safety"
    title = ("arguments at donate_argnums positions are never read "
             "after the donating call")
    suppress_token = "donated-ok"

    def check(self, ctx: Context) -> List[Finding]:
        problems: List[Finding] = []
        # Pass 1: collect every module's _DONATES registry.  The union
        # is applied package-wide — the trainer's donating steps are
        # called through attributes from the strategies, and an
        # attribute call site doesn't care which module declared the
        # step.
        union: Dict[str, Tuple[int, ...]] = {}
        for path in ctx.files:
            tree, err = ctx.tree(path)
            if err is not None:
                continue
            union.update(self._registry(tree, ctx.rel(path), problems))
        for path in ctx.files:
            tree, err = ctx.tree(path)
            if err is not None:
                continue
            self._check_module(tree, ctx.rel(path), union, problems)
        return problems

    # -- discovery --------------------------------------------------------

    def _registry(self, tree, rel, problems) -> Dict[str, Tuple[int, ...]]:
        """One module's _DONATES declaration (attribute-stored donating
        steps), validated: literal entries only, every declared name
        assigned somewhere in the declaring module."""
        registry: Dict[str, Tuple[int, ...]] = {}
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "_DONATES"
                    for t in node.targets):
                if not isinstance(node.value, ast.Dict):
                    problems.append(Finding(
                        check=self.id, path=rel, line=node.lineno,
                        message="_DONATES must be a literal dict of "
                                "{'name': (positions...)} — the registry "
                                "must be statically checkable"))
                    continue
                for k, v in zip(node.value.keys, node.value.values):
                    ok = (isinstance(k, ast.Constant)
                          and isinstance(k.value, str)
                          and isinstance(v, (ast.Tuple, ast.List))
                          and all(isinstance(e, ast.Constant)
                                  and isinstance(e.value, int)
                                  for e in v.elts))
                    if ok:
                        registry[k.value] = tuple(e.value for e in v.elts)
                    else:
                        problems.append(Finding(
                            check=self.id, path=rel,
                            line=getattr(k, "lineno", node.lineno),
                            message="_DONATES holds a non-literal entry"))

        # Registry drift: every declared name must be assigned somewhere.
        if registry:
            assigned = set()
            for n in ast.walk(tree):
                if isinstance(n, (ast.Assign, ast.AnnAssign)):
                    targets = (n.targets if isinstance(n, ast.Assign)
                               else [n.target])
                    for t in targets:
                        if isinstance(t, ast.Attribute):
                            assigned.add(t.attr)
                        elif isinstance(t, ast.Name):
                            assigned.add(t.id)
            for name in sorted(set(registry) - assigned):
                problems.append(Finding(
                    check=self.id, path=rel, line=0,
                    message=f"_DONATES names {name!r} but nothing in the "
                            "module assigns it — the registry drifted",
                    hint="fix or remove the registry entry"))
        return registry

    def _check_module(self, tree, rel, registry, problems):
        donating: List[_Scope] = []       # local jit defs

        # Local jit-with-donate defs, with their visibility scope.
        parents: Dict[int, ast.AST] = {}
        for n in ast.walk(tree):
            for c in ast.iter_child_nodes(n):
                parents[id(c)] = n

        def enclosing_fn(node):
            cur = parents.get(id(node))
            while cur is not None and not isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cur = parents.get(id(cur))
            return cur

        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in n.decorator_list:
                    if not (isinstance(dec, ast.Call)
                            and _is_jit_expr(dec)):
                        continue
                    pos, perr = _donate_positions(dec)
                    if perr:
                        problems.append(Finding(
                            check=self.id, path=rel, line=dec.lineno,
                            message=f"{n.name}: {perr}"))
                    elif pos:
                        scope = enclosing_fn(n) or tree
                        donating.append(_Scope(n.name, pos, scope))
            elif isinstance(n, ast.Call) and _is_jit_expr(n) \
                    and not isinstance(parents.get(id(n)),
                                       (ast.Call, ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                # Direct jax.jit(fn, donate_argnums=...) — bind under the
                # assigned name when there is one.
                pos, perr = _donate_positions(n)
                if perr:
                    problems.append(Finding(
                        check=self.id, path=rel, line=n.lineno,
                        message=perr))
                elif pos:
                    parent = parents.get(id(n))
                    if isinstance(parent, ast.Assign):
                        for t in parent.targets:
                            if isinstance(t, ast.Name):
                                scope = enclosing_fn(n) or tree
                                donating.append(
                                    _Scope(t.id, pos, scope))

        if not donating and not registry:
            return

        self._check_calls(tree, rel, donating, registry, parents,
                          problems)

    # -- call-site analysis ----------------------------------------------

    def _check_calls(self, tree, rel, donating, registry, parents,
                     problems):
        by_name: Dict[str, List[_Scope]] = {}
        for d in donating:
            by_name.setdefault(d.name, []).append(d)

        def in_traced_context(node) -> bool:
            cur = parents.get(id(node))
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for dec in cur.decorator_list:
                        if _is_jit_expr(dec):
                            return True
                cur = parents.get(id(cur))
            return False

        def enclosing_function(node):
            cur = parents.get(id(node))
            while cur is not None and not isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cur = parents.get(id(cur))
            return cur if cur is not None else tree

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            positions = None
            callee = ""
            if isinstance(node.func, ast.Name) \
                    and node.func.id in by_name:
                for cand in by_name[node.func.id]:
                    if _contains(cand.scope_node, node):
                        positions = cand.positions
                        callee = cand.name
                        break
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in registry:
                positions = registry[node.func.attr]
                callee = node.func.attr
            if positions is None:
                continue
            if in_traced_context(node):
                continue  # donation inside another trace is a no-op
            starred_at = next((i for i, a in enumerate(node.args)
                               if isinstance(a, ast.Starred)),
                              len(node.args))
            fn = enclosing_function(node)
            for p in positions:
                if p >= len(node.args):
                    continue  # passed by keyword — jit binds it itself
                if p >= starred_at:
                    # The donated position hides behind a *splat: the
                    # lint cannot see which expression lands there, so
                    # it cannot prove no-use-after.  Demand a human
                    # annotation instead of staying silent.
                    problems.append(Finding(
                        check=self.id, path=rel, line=node.lineno,
                        message=(f"donated position {p} of {callee}() "
                                 "is hidden behind a *splat — "
                                 "use-after-donate cannot be audited "
                                 "statically"),
                        hint="pass the donated argument positionally, "
                             "or annotate '# al-lint: donated-ok "
                             "<why the donated value is not reused>'"))
                    continue
                path = _path_of(node.args[p])
                if path is None:
                    continue  # fresh temporary — unobservable after
                self._check_use_after(fn, node, rel, callee, p, path,
                                      parents, problems)

    def _check_use_after(self, fn, call, rel, callee, pos, path, parents,
                         problems):
        # The chain of (parent, block, index) block positions from the
        # call's innermost containing statement out to ``fn``.
        chain = []
        cur = call
        while True:
            parent = parents.get(id(cur))
            if parent is None:
                break
            for field in ("body", "orelse", "finalbody"):
                block = getattr(parent, field, None)
                if isinstance(block, list) and cur in block:
                    chain.append((parent, block, block.index(cur)))
                    break
            if parent is fn:
                break
            cur = parent
        if not chain:
            return
        stmt = chain[0][1][chain[0][2]]

        # Same-statement rebind (state, ... = step(state, ...)): safe —
        # every later read sees the call's RESULT, not the dead buffer.
        if any(ap == path for ap in _assigned_paths(stmt)):
            return

        def report(line, where):
            label = path[0] + "".join(
                p if p.startswith("[") else "." + p for p in path[1:])
            problems.append(Finding(
                check=self.id, path=rel, line=line,
                message=(label
                         + f" is donated at position {pos} of "
                         f"{callee}() (line {call.lineno}) and read "
                         f"again {where} — use-after-donate of a "
                         "deleted device buffer"),
                hint="rebind the result over the donated name, copy "
                     "before donating, or annotate "
                     "'# al-lint: donated-ok <reason>'"))

        # Walk outward: later statements in each enclosing block; loop
        # ancestors taint their whole body (the next iteration re-reads
        # the donated buffer).
        for parent, block, idx in chain:
            if isinstance(parent, (ast.For, ast.AsyncFor, ast.While)):
                for n in ast.walk(parent):
                    if not _is_load(n):
                        continue
                    p = _path_of(n)
                    if p is not None and p[:len(path)] == path \
                            and not _contains(stmt, n):
                        report(n.lineno, "inside the enclosing loop "
                                         "(next iteration)")
                        return
            for later in block[idx + 1:]:
                if any(ap == path for ap in _assigned_paths(later)):
                    # A rebind kills the taint for everything AFTER it —
                    # but its own right-hand side still executes against
                    # the dead buffer: ``state = state.replace(...)``
                    # after donating ``state`` is a use-after-donate
                    # dressed as the fix.
                    if _reads_path(later, path):
                        report(later.lineno, "by the statement that "
                                             "rebinds it")
                    return
                if _reads_path(later, path):
                    report(later.lineno, "after the call")
                    return
