"""Shared helpers of the benchmark's tests (not a test module)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
TOY = os.path.join(ROOT, "tests", "benchmark", "toy")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load(rel):
    with open(os.path.join(ROOT, rel)) as fh:
        return json.load(fh)


def family(rel="benchmarks/families/resnet.py"):
    """A family module, loaded as ``run.py`` loads it: by its path."""
    import families
    return families.load(rel, ROOT)


# What the parent commit (PR 26, ``ed27e8e``) read in the walks these tests
# make, recorded before PR 27 moved the ResNet-only code behind the family
# seam: (cell, seed, planted fault) -> its ``check`` and ``uncompared``
# numbers.  The same bytes go into the same program and the same reference
# arithmetic, so the change reads them to every printed digit (a broken walk
# too: what it breaks is broken the same way on both sides).  The assertions
# sit inside the walks' existing tests, not in tests of their own: pytest-xdist
# starts the files with the most tests first, and the two files that run
# walks have to stay the last to start, as at the parent, or their processes
# take the cores from under the suite's collective tests, whose rendezvous
# then aborts a worker (six whole runs of eight, PR 27).
PARENT_WALKS = {
    ("toy.margin_ft", 2 ** 31 + 11, ""): {
        "loss3": 0.0, "gnorm1": 3.764739047489397e-07,
        "dparam": 8.584310305818633e-08, "score_gap": 6.20084552902134e-07,
        "pick_regret": 0.0, "test_rows": 0.0, "test_gap": 0.0},
    ("toy.margin_ft", 21, "state_unchanged"): {
        "loss3": 0.0, "gnorm1": 5.191305311151785e-07, "dparam": 1.0,
        "score_gap": 0.377947475234082, "pick_regret": 0.0,
        "test_rows": 0.0, "test_gap": 0.046875},
    ("toy.margin_ft", 22, "half_batch"): {
        "loss3": 0.006448893029165927, "gnorm1": 0.18093531133058133,
        "dparam": 0.3289703312038443, "score_gap": 0.4333412627746613,
        "pick_regret": 0.0, "test_rows": 0.0, "test_gap": 0.015625},
    ("toy.coreset_lin", 23, "score_altered"): {
        "loss3": 8.522875696670513e-08, "gnorm1": 1.1107059502628097e-07,
        "dparam": 2.0660647970100967e-08, "score_gap": 0.5000000063964712,
        "pick_regret": 0.0, "test_rows": 0.0, "test_gap": 0.0},
    ("toy.coreset_lin", 24, ""): {
        "loss3": 8.560304822182352e-08, "gnorm1": 0.0,
        "dparam": 2.15740005332452e-08, "score_gap": 2.374323958732949e-07,
        "pick_regret": 0.0, "test_rows": 0.0, "test_gap": 0.0}}


def assert_reads_the_parents_numbers(last, cell, seed, fault=""):
    got = {k: v for k, (v, _) in last["check"].items()}
    got.update(last["uncompared"])
    want = PARENT_WALKS[(cell, seed, fault)]
    assert {k: repr(v) for k, v in got.items()} == {
        k: repr(v) for k, v in want.items()}


def walk_cmd(cell, seed, extra=()):
    return [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
            "--workload-file", os.path.join(TOY, f"{cell}.json"),
            "--config-file", os.path.join(TOY, "config.json"),
            "--seed", str(seed), "--seconds", "1", *extra]


def walk_env(devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)      # one CPU device: the one-chip cell
    if devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    return env


def start_walk(cell, seed, extra=(), devices=1):
    return subprocess.Popen(walk_cmd(cell, seed, extra),
                            env=walk_env(devices),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd=ROOT)


def finish_walk(proc, timeout=600):
    out, err = proc.communicate(timeout=timeout)
    lines = [ln for ln in out.splitlines() if ln.strip()]
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc.returncode, last, err
