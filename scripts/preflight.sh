#!/usr/bin/env bash
# preflight.sh — the merge gate, reproduced locally with one command.
#
#   bash scripts/preflight.sh
#
# Chains the three gates a change must clear, fail-fast, in cost order:
#
#   1. al_lint         the 18-check static analysis (seconds, no jax)
#   2. tier-1 tests    the ROADMAP.md tier-1 recipe (CPU 8-device mesh);
#                      it holds the end-to-end walks of every subsystem
#                      (the AL round, the stream service's ingest ->
#                      trigger -> round, the fleet's kill -> requeue ->
#                      resume)
#   3. run_report      scripts/run_report.py --selftest (the reporting
#                      layer renders synthetic runs end to end)
#
# Speed is not gated here: it is measured on the chip by
# benchmarks/run.py (BENCHMARK.json, PERF.md).
#
# Exit codes: 0 = every gate green; otherwise the exit code of the
# FIRST failing gate (1 = lint findings or test/selftest failures,
# 2 = usage/collection errors, >=124 = a timeout) — `set -e` stops at
# the first red, so the last line printed names the failing gate.
set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO"

echo "== preflight 1/3: al_lint (static analysis) =="
python scripts/al_lint.py

echo "== preflight 2/3: tier-1 tests =="
# The tier-1 recipe (ROADMAP.md): CPU backend, virtual 8-device mesh
# via tests/conftest.py, slow tier excluded.
set -o pipefail
rm -f /tmp/_preflight_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_preflight_t1.log

echo "== preflight 3/3: run_report selftest =="
python scripts/run_report.py --selftest

echo "preflight: ALL GATES GREEN"
