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


def walk_cmd(cell, seed, extra=()):
    return [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
            "--workload-file", os.path.join(TOY, f"{cell}.json"),
            "--config-file", os.path.join(TOY, "config.json"),
            "--seed", str(seed), "--seconds", "1", *extra]


def walk_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)      # one CPU device: the one-chip cell
    return env


def start_walk(cell, seed, extra=()):
    return subprocess.Popen(walk_cmd(cell, seed, extra), env=walk_env(),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd=ROOT)


def finish_walk(proc, timeout=600):
    out, err = proc.communicate(timeout=timeout)
    lines = [ln for ln in out.splitlines() if ln.strip()]
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return proc.returncode, last, err
