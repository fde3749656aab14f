"""Do the documents describe the repository as it is?

Over the documents a user follows and the ones the PRs plan from:

  * every token that names a path of this repo exists: anything under
    ``active_learning_tpu/``, ``benchmarks/``, ``scripts/``, ``tests/``
    or ``native/``, and any bare file name with a source or record
    extension, which must be a file of the tree by its base name or,
    for what a run writes (``trace.json``), a name the program's own
    source spells;
  * every ``--flag`` is defined by one of the repo's argument parsers,
    read from their source text (no import of jax), or belongs to a
    tool the documents drive (pytest, the chip tool, XLA, git);
  * ``scripts/preflight.sh`` parses and runs only files that exist.

``GONE`` is the short list of paths a document may still name: each
must stand there in a passage that says it went.
"""

import glob
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ("README.md", "MIGRATION.md", ".claude/skills/verify/SKILL.md",
        "scripts/preflight.sh", "PERF.md", "ROADMAP.md", "DESIGN.md",
        "PARITY.md")

# Paths a document names as deleted, per document.
_OLD_STACK = {"bench.py", "bench_cache.json", "bench_evidence_r05.json",
              "BENCH_r0*.json", "MULTICHIP_r0*.json",
              "mfu_decomposition.json", "scripts/mfu_decomposition.py",
              "scripts/perf_report.py", "tests/test_bench_json.py"}
GONE = {
    "PERF.md": {"bench.py"},
    "ROADMAP.md": _OLD_STACK | {"span_run.py", "BENCH_r05.json"},
}
_SAYS_GONE = re.compile(
    r"\b(deleted?|deletes|gone|went|retired?|removed?|is out|no longer)\b",
    re.I)

_TREES = ("active_learning_tpu", "benchmarks", "scripts", "tests", "native")
_EXT = r"(?:py|sh|cpp|ini|md|jsonl|json)"
_PATH = re.compile(
    r"(?<![\w./*$<-])((?:%s)/[\w./*-]*|[\w.*-]+\.%s)(?![\w/*<-])"
    % ("|".join(_TREES), _EXT))
# A path in prose ends at its sentence's full stop or at ``:line``.
_TAIL = re.compile(r"(?::[\d,:-]*)?[.,;:]*$")

_SKIP_DIRS = {".git", "chiprun_out", "__pycache__", "logs", "checkpoint",
              ".jax_cache", ".parent", ".scratch", ".committed_copy",
              ".chip_smoke", ".bench_work", "build"}

# Where the repo's argument parsers live.
PARSERS = ("active_learning_tpu/experiment/cli.py",
           "active_learning_tpu/experiment/gen_jobs.py",
           "active_learning_tpu/serve/cli.py",
           "active_learning_tpu/stream/cli.py",
           "active_learning_tpu/fleet/cli.py",
           "active_learning_tpu/telemetry/status.py",
           "active_learning_tpu/telemetry/report.py",
           "benchmarks/run.py", "chip_smoke.py", "scripts/*.py")
# Flags of the tools the documents drive, not of this repo.
FOREIGN = {
    # pytest and pytest-xdist
    "--collect-only", "--continue-on-collection-errors", "--dist",
    "--junitxml", "--durations",
    # the chip tool
    "--chips", "--timeout", "--status",
    # git
    "--stat",
    # the reference's own CLI, where a document says what replaced it
    "--enable_comet",
}
_FLAG = re.compile(r"(?<![\w-])(--[A-Za-z][\w-]*)")


def _read(rel):
    with open(os.path.join(REPO, rel)) as fh:
        return fh.read()


@pytest.fixture(scope="module")
def bases():
    """The base name of every file of the tree."""
    out = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        out.update(files)
    return out


@pytest.fixture(scope="module")
def program_text():
    """The source of everything that runs: a bare ``x.json`` in a
    document is a file of the tree or a name one of these writes."""
    parts = []
    for pat in ("active_learning_tpu/**/*.py", "benchmarks/**/*.py",
                "scripts/*.py", "*.py"):
        for path in glob.glob(os.path.join(REPO, pat), recursive=True):
            with open(path) as fh:
                parts.append(fh.read())
    return "\n".join(parts)


def path_tokens(text):
    out = set()
    for tok in _PATH.findall(text):
        tok = _TAIL.sub("", tok.split("::")[0])
        if tok and not tok.startswith(("*", ".")):
            out.add(tok)
    return out


def names_nothing(tok, bases, program_text):
    if "/" in tok:
        if "*" in tok:
            return not glob.glob(os.path.join(REPO, tok))
        return not os.path.exists(os.path.join(REPO, tok))
    if "*" in tok:
        return not any(glob.fnmatch.fnmatch(b, tok) for b in bases)
    return tok not in bases and tok not in program_text


@pytest.mark.parametrize("doc", DOCS)
def test_every_named_path_exists(doc, bases, program_text):
    gone = GONE.get(doc, set())
    missing = sorted(t for t in path_tokens(_read(doc)) - gone
                     if names_nothing(t, bases, program_text))
    assert not missing, f"{doc} names paths that are not in the tree"


@pytest.fixture(scope="module")
def known_flags():
    flags = set(FOREIGN)
    for pat in PARSERS:
        for path in glob.glob(os.path.join(REPO, pat)):
            with open(path) as fh:
                flags.update(re.findall(r"[\"'](--[A-Za-z][\w-]*)[\"']",
                                        fh.read()))
    return flags


@pytest.mark.parametrize("doc", DOCS)
def test_every_flag_has_a_parser(doc, known_flags):
    unknown = sorted(f for f in set(_FLAG.findall(_read(doc))) - known_flags
                     if not f.startswith("--xla_"))
    assert not unknown, f"{doc} names flags no parser of the repo defines"


def test_gone_paths_stand_in_passages_that_say_so():
    """The exemptions are not a hiding place: in the document that
    names it, a gone path stands only in paragraphs or list items that
    say it was deleted."""
    for doc, gone in GONE.items():
        blocks = re.split(r"\n\s*\n|\n(?=\s*(?:[-*]|\w{1,3}\.)\s)",
                          _read(doc))
        for tok in sorted(gone):
            holding = [b for b in blocks if tok in b]
            assert holding, f"{doc} no longer names {tok}: drop it from GONE"
            silent = [b for b in holding if not _SAYS_GONE.search(b)]
            assert not silent, (doc, tok, silent[0][:200])


def test_preflight_parses_and_runs_files_that_exist():
    script = os.path.join(REPO, "scripts", "preflight.sh")
    proc = subprocess.run(["bash", "-n", script], capture_output=True,
                          text=True)
    assert proc.returncode == 0, proc.stderr
    text = _read("scripts/preflight.sh")
    ran = re.findall(r"^\s*(?:timeout[^\n]*?)?python (?!-)(\S+)", text,
                     re.M)
    assert ran, "preflight runs no python file"
    for rel in ran:
        assert os.path.isfile(os.path.join(REPO, rel)), rel
    gates = re.findall(r"== preflight (\d+)/(\d+):", text)
    assert [int(a) for a, _ in gates] == list(range(1, len(gates) + 1))
    assert {int(b) for _, b in gates} == {len(gates)} == {3}


# What PR 33 made the documents say, each with a path that exists: the pool
# row's schema, the backbone contract and the frozen/trainable split.
_PR33_NAMES = ("active_learning_tpu/models/backbone.py",
               "active_learning_tpu/data/core.py",
               "active_learning_tpu/models/mla_moe.py")


@pytest.mark.parametrize("doc", ("README.md", "MIGRATION.md", "DESIGN.md"))
def test_documents_name_the_row_schema_the_contract_and_the_split(doc):
    with open(os.path.join(REPO, doc)) as fh:
        text = fh.read()
    assert "check_rows" in text and "frozen" in text
    assert "models/backbone.py" in text
    assert "TrainState.frozen" in text or "TrainState.params" in text \
        or "frozen by the backbone" in text
    for path in _PR33_NAMES:
        assert os.path.isfile(os.path.join(REPO, path))


def test_readme_lists_the_models_the_registry_holds():
    """README's model list is the registry's (read from the source text:
    no import of jax)."""
    with open(os.path.join(REPO, "README.md")) as fh:
        readme = fh.read()
    names = set()
    for rel in ("active_learning_tpu/models/factory.py",
                "active_learning_tpu/models/mla_moe.py"):
        with open(os.path.join(REPO, rel)) as fh:
            names |= set(re.findall(r'MODELS\.register\("(\w+)"', fh.read()))
    assert names >= {"SSLResNet18", "SSLResNet50", "AXK1_TOY",
                     "AXK1_EP16_L7"}
    assert all(f"`{n}`" in readme for n in names)
