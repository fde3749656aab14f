"""The static-analysis engine (DESIGN.md §12) — tier-1 fail-fast.

This file sorts FIRST in the suite (test_analysis < test_backward), so
a lint violation anywhere in the package reds out in ~2 s before any
slow jax suite spins up — and the red NAMES its check id instead of
"trace_lint failed".

Pinned here:
  * the whole 18-check run over the live tree is CLEAN (unsuppressed),
    completes under the 5 s budget, and parses each file at most once
    (the shared-AST-cache contract — the reason the engine exists);
  * every checker in the registry has a golden negative-case fixture
    under tests/fixtures/analysis/<check-id>.py, and flags it — one
    parametrized test per check id;
  * the 10 ported legacy checks produce IDENTICAL verdicts through the
    engine and through the scripts/trace_lint.py shim, live tree and
    fixtures both;
  * suppression semantics: ``# al-lint: <token> <reason>`` suppresses
    with a reason (counted in --json), converts to its own finding
    without one, and the legacy checks accept no suppressions;
  * the al_lint CLI: --list names every check, --json emits the
    machine-readable report, --check selects subsets, unknown ids exit 2.

No jax import anywhere on these paths — the lint must work against a
wedged tree, and this suite must stay cheap.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")

sys.path.insert(0, REPO) if REPO not in sys.path else None

from active_learning_tpu.analysis import (  # noqa: E402
    Engine, run_package_analysis)
from active_learning_tpu.analysis.checks import (  # noqa: E402
    CHECK_IDS, CHECKERS)
from active_learning_tpu.analysis.checks import legacy  # noqa: E402

LEGACY_IDS = tuple(c.id for c in legacy.LEGACY_CHECKERS)
DEEP_IDS = tuple(i for i in CHECK_IDS if i not in LEGACY_IDS)


def fixture(check_id: str) -> str:
    return os.path.join(FIXTURES, f"{check_id}.py")


def checker_by_id(check_id: str):
    return next(c for c in CHECKERS if c.id == check_id)


# How each check runs against its single-file fixture.  Fixed-path
# checks take the fixture as their target module; package-scan checks
# take it as the file set; the deep checkers run through a real Engine
# so suppression handling is exercised on the same path production uses.
def run_fixture(check_id: str):
    path = fixture(check_id)
    if check_id == "phase-timer-span":
        return legacy.check_phase_timer_span(tracing_path=path)
    if check_id == "resident-feed":
        return legacy.check_resident_feed(trainer_path=path)
    if check_id == "sharded-selection":
        return legacy.check_sharded_selection(kcenter_path=path)
    if check_id == "pipeline-coordinator":
        return legacy.check_pipeline_coordinator(pipeline_path=path)
    if check_id in LEGACY_IDS:
        checker_fn = {
            "phase-timer-fork": legacy.check_phase_timer_fork,
            "phase-timer-import": legacy.check_phase_timer_import,
            "trace-annotation": legacy.check_trace_annotation,
            "fault-sites": legacy.check_fault_sites,
            "backward-registry": legacy.check_backward_registry,
            "profiler-confinement": legacy.check_profiler_confinement,
        }[check_id]
        return checker_fn(files=[path])
    return Engine(files=[path]).run([checker_by_id(check_id)]).findings


class TestPackageClean:
    def test_full_run_clean_fast_single_parse(self):
        """THE tier-1 gate: 18 checks over the whole package — zero
        unsuppressed findings, every suppression carries a reason, the
        run fits the 5 s budget of its OWN CPU time (the wall also
        counts the other tier-1 workers on the cores: alone it reads
        the same), and no file parses twice."""
        report = run_package_analysis()
        assert sorted(report.checks_run) == sorted(CHECK_IDS)
        bad = [f.render() for f in report.unsuppressed]
        assert not bad, "al_lint findings on the tree:\n" + "\n".join(bad)
        for f in report.suppressed:
            assert f.suppress_reason.strip(), f.render()
        assert report.cpu_s < 5.0, (
            f"whole-package analysis took {report.cpu_s:.2f}s of CPU "
            f"({report.elapsed_s:.2f}s wall) — the shared-parse budget "
            "is 5s")
        assert report.files_scanned > 50
        assert report.parse_counts, "cache recorded no parses"
        worst = max(report.parse_counts.values())
        assert worst <= 1, (
            "a file was parsed more than once — the single-parse AST "
            f"cache contract broke (max={worst})")

    def test_default_targets_are_the_package_and_scripts(self):
        """What al_lint walks by default: every .py of the package and
        of scripts/ (less the two lint entry points), nothing at the
        repo root, and every target is a file that exists."""
        from active_learning_tpu.analysis import engine
        files = engine.default_files()
        assert files and all(os.path.isfile(f) for f in files)
        rels = [os.path.relpath(f, REPO) for f in files]
        tops = {r.split(os.sep)[0] for r in rels}
        assert tops == {"active_learning_tpu", "scripts"}
        scripts = sorted(n for n in os.listdir(os.path.join(REPO, "scripts"))
                         if n.endswith(".py"))
        assert sorted(os.path.basename(r) for r in rels
                      if r.startswith("scripts" + os.sep)) == [
            n for n in scripts if n not in engine._SELF]

    def test_shim_matches_engine_on_live_tree(self):
        """The 10 legacy checks produce identical verdicts through the
        shim and through the engine registry (both clean here; fixture
        parity is pinned per-check below)."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        shim = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(shim)
        shim_problems = shim.check()
        engine_report = Engine().run(legacy.LEGACY_CHECKERS)
        engine_problems = [f.render() for f in engine_report.findings]
        assert shim_problems == engine_problems == []


class TestFixtures:
    def test_every_checker_has_a_fixture(self):
        """A new checker cannot land without its golden negative case."""
        missing = [cid for cid in CHECK_IDS
                   if not os.path.exists(fixture(cid))]
        assert not missing, (
            f"checkers without a fixture under tests/fixtures/analysis/: "
            f"{missing}")
        stray = sorted(
            f for f in os.listdir(FIXTURES)
            if f.endswith(".py") and f[:-3] not in CHECK_IDS)
        assert not stray, f"fixtures naming no registered check: {stray}"

    @pytest.mark.parametrize("check_id", CHECK_IDS)
    def test_fixture_flags_its_check(self, check_id):
        """Each golden fixture is flagged BY ITS OWN check — a red here
        names the broken checker instead of 'trace_lint failed'."""
        findings = run_fixture(check_id)
        assert findings, f"{check_id}: fixture produced no findings"
        assert all(f.check == check_id for f in findings), (
            f"{check_id}: findings carry foreign check ids: "
            f"{[f.check for f in findings]}")
        assert all(not f.suppressed for f in findings)

    # phase-timer-span targets the fixed utils/tracing.py path in the
    # shim (exactly as the monolith did — check() has no tracing_path
    # parameter), so its fixture parity is the engine-side test above.
    @pytest.mark.parametrize(
        "check_id",
        sorted(i for i in LEGACY_IDS if i != "phase-timer-span"))
    def test_legacy_fixture_verdicts_match_shim(self, check_id):
        """Identical verdicts, engine vs shim, on the negative fixtures
        (message strings included — the shim renders the same
        Findings)."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        shim = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(shim)
        path = fixture(check_id)
        engine_msgs = [f.render() for f in run_fixture(check_id)]
        shim_fn = {
            "phase-timer-span": None,  # shim exposes it only via check()
            "phase-timer-fork": None,
            "phase-timer-import": None,
            "trace-annotation": None,
            "resident-feed": lambda: shim.check_resident_feed(path),
            "sharded-selection": lambda: shim.check_sharded_selection(
                path),
            "pipeline-coordinator":
                lambda: shim.check_pipeline_coordinator(path),
            "fault-sites": lambda: shim.check_fault_sites([path]),
            "backward-registry":
                lambda: shim.check_backward_registry([path]),
            "profiler-confinement":
                lambda: shim.check_profiler_confinement([path]),
        }[check_id]
        if shim_fn is None:
            # The whole-tree checks ride shim.check() with a
            # monkeypatched walk.
            orig = shim._py_files
            try:
                shim._py_files = lambda: [path]
                shim_msgs = [p for p in shim.check()
                             if any(m in p for m in engine_msgs)
                             or p in engine_msgs]
            finally:
                shim._py_files = orig
            assert set(engine_msgs) <= set(shim_msgs), (
                engine_msgs, shim_msgs)
        else:
            assert shim_fn() == engine_msgs

    def test_lock_fixture_names_field_and_lock(self):
        msgs = [f.message for f in run_fixture("lock-discipline")]
        assert any("'_queue'" in m and "'_lock'" in m for m in msgs)

    def test_donation_fixture_names_path_and_line(self):
        f = run_fixture("donation-safety")[0]
        assert "state" in f.message and "donated" in f.message
        assert "use-after-donate" in f.message

    def test_recompile_fixture_flags_both_rules(self):
        msgs = [f.message for f in run_fixture("recompile-hazard")]
        assert any("outside the registered step-builders" in m
                   for m in msgs)
        assert any("f-string" in m and "static operand" in m
                   for m in msgs)

    def test_collective_fixture_flags_both_rules(self):
        msgs = [f.message for f in run_fixture("collective-axis")]
        assert any("unregistered/unresolvable axis" in m and "'rows'" in m
                   for m in msgs)
        assert any("owner-gather idiom" in m for m in msgs)

    def test_collective_fixture_flags_pod_tier_spellings(self):
        """ISSUE 15's new idioms: a masked psum_scatter outside
        owner_rows_scattered, and a hand-rolled ring ppermute outside
        mesh_lib.ring_shift, are both findings with home-naming hints."""
        msgs = [f.message for f in run_fixture("collective-axis")]
        assert any("masked-psum_scatter" in m
                   and "owner_rows_scattered" in m for m in msgs)
        assert any("ring-permute feed spelled by hand" in m
                   and "ring_shift" in m for m in msgs)


class TestSuppressions:
    def _one_violation(self, tmp_path, annotation=""):
        src = (
            "import functools\n"
            "import jax\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))\n"
            "def step(state):\n"
            "    return state\n"
            "def train(state):\n"
            f"    out = step(state){annotation}\n"
            "    return out + state\n")
        p = tmp_path / "frag.py"
        p.write_text(src)
        checker = checker_by_id("donation-safety")
        return Engine(files=[str(p)]).run([checker])

    def test_reasoned_suppression_counts_but_passes(self, tmp_path):
        report = self._one_violation(
            tmp_path, "  # al-lint: donated-ok buffers are host copies")
        assert not report.unsuppressed
        assert len(report.suppressed) == 1
        assert report.suppressed[0].suppress_reason == \
            "buffers are host copies"
        j = report.to_json()
        assert j["total_suppressed"] == 1
        assert j["counts"]["donation-safety"]["suppressed"] == 1

    def test_reasonless_suppression_is_itself_a_finding(self, tmp_path):
        report = self._one_violation(tmp_path, "  # al-lint: donated-ok")
        assert len(report.unsuppressed) == 1
        assert "without a reason" in report.unsuppressed[0].message

    def test_unannotated_violation_fails(self, tmp_path):
        report = self._one_violation(tmp_path)
        assert len(report.unsuppressed) == 1
        assert "use-after-donate" in report.unsuppressed[0].message

    def test_wrong_token_does_not_suppress(self, tmp_path):
        report = self._one_violation(
            tmp_path, "  # al-lint: lock-ok not the right token")
        assert len(report.unsuppressed) == 1

    def test_donates_registry_is_package_global(self, tmp_path):
        """The trainer's donating steps are called through attributes
        from the strategies — a _DONATES declared in one module must
        cover call sites in every other."""
        a = tmp_path / "a.py"
        a.write_text("_DONATES = {'_train_step': (0,)}\n"
                     "class T:\n"
                     "    def __init__(self):\n"
                     "        self._train_step = None\n")
        b = tmp_path / "b.py"
        b.write_text("def bench(trainer, state, batch):\n"
                     "    out = trainer._train_step(state, batch)\n"
                     "    return out, state\n")
        checker = checker_by_id("donation-safety")
        report = Engine(files=[str(a), str(b)]).run([checker])
        assert len(report.unsuppressed) == 1
        assert report.unsuppressed[0].path.endswith("b.py")
        # Rebinding in the same statement clears it.
        b.write_text("def bench(trainer, state, batch):\n"
                     "    state, loss = trainer._train_step(state, batch)\n"
                     "    return state, loss\n")
        report = Engine(files=[str(a), str(b)]).run([checker])
        assert not report.unsuppressed

    def test_rebind_rhs_is_still_a_use_after_donate(self, tmp_path):
        """``state = state.replace(...)`` after donating ``state`` reads
        the dead buffer on its right-hand side — the rebind must not
        launder it (code-review regression pin)."""
        p = tmp_path / "frag.py"
        p.write_text(
            "import functools\n"
            "import jax\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))\n"
            "def step(state):\n"
            "    return state\n"
            "def train(state):\n"
            "    out = step(state)\n"
            "    state = state.replace(n=1)\n"
            "    return out, state\n")
        checker = checker_by_id("donation-safety")
        report = Engine(files=[str(p)]).run([checker])
        assert len(report.unsuppressed) == 1
        assert "rebinds it" in report.unsuppressed[0].message
        # A rebind from a FRESH value genuinely clears the taint.
        p.write_text(
            "import functools\n"
            "import jax\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))\n"
            "def step(state):\n"
            "    return state\n"
            "def train(state, fresh):\n"
            "    out = step(state)\n"
            "    state = fresh()\n"
            "    return out, state\n")
        report = Engine(files=[str(p)]).run([checker])
        assert not report.unsuppressed

    def test_legacy_checks_accept_no_suppressions(self, tmp_path):
        """The ported checks must keep identical verdicts — an
        annotation cannot silence them."""
        p = tmp_path / "rogue.py"
        p.write_text("def phase_timer(name):  # al-lint: lock-ok nope\n"
                     "    return name\n")
        checker = checker_by_id("phase-timer-fork")
        assert checker.suppress_token is None
        report = Engine(files=[str(p)]).run([checker])
        assert len(report.unsuppressed) == 1


class TestFullTreeSemantics:
    def test_fault_sites_plugin_runs_registry_sub_checks(self, tmp_path):
        """The engine path must pass full_tree=True: the unwired-site
        sub-check lives only in whole-tree mode, and a file set that
        wires one site must report the rest of the REAL registry as
        unwired (code-review regression pin — without the flag the
        al_lint path silently skipped this, while the shim caught it)."""
        p = tmp_path / "one_site.py"
        p.write_text("from active_learning_tpu import faults\n"
                     "def up():\n"
                     "    faults.site('h2d_upload')\n")
        checker = checker_by_id("fault-sites")
        report = Engine(files=[str(p)]).run([checker])
        msgs = [f.message for f in report.unsuppressed]
        assert any("wired at no call site" in m for m in msgs), msgs

    def test_bare_jit_alias_is_confined_too(self, tmp_path):
        """``from jax import jit; step = jit(fn)`` is the cheapest
        evasion of the step-builder discipline — the bare-name spelling
        must be confined like jax.jit (code-review regression pin)."""
        p = tmp_path / "frag.py"
        p.write_text("from jax import jit\n"
                     "_STEP_BUILDERS = ('build',)\n"
                     "def build(fn):\n"
                     "    return jit(fn)\n"
                     "def rogue(fn):\n"
                     "    return jit(fn)\n")
        checker = checker_by_id("recompile-hazard")
        report = Engine(files=[str(p)]).run([checker])
        assert len(report.unsuppressed) == 1
        assert report.unsuppressed[0].line == 6


class TestCLI:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "al_lint.py"),
             *args],
            capture_output=True, text=True, timeout=120, cwd=REPO)

    def test_json_report_shape(self):
        proc = self._run("--json")
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout)
        assert sorted(out["checks_run"]) == sorted(CHECK_IDS)
        assert out["max_parses_per_file"] <= 1
        assert out["total_findings"] == 0
        # Every suppression in the report carries its reason string.
        for f in out["findings"]:
            if f["suppressed"]:
                assert f["suppress_reason"].strip()

    def test_list_names_every_check(self):
        proc = self._run("--list")
        assert proc.returncode == 0
        for cid in CHECK_IDS:
            assert cid in proc.stdout

    def test_check_subset_and_unknown_id(self):
        proc = self._run("--check", "lock-discipline", "--json")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert json.loads(proc.stdout)["checks_run"] == \
            ["lock-discipline"]
        proc = self._run("--check", "no-such-check")
        assert proc.returncode == 2
        assert "no-such-check" in proc.stderr

    def test_plain_run_green(self):
        proc = self._run()
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "al_lint: ok" in proc.stdout
