"""The readers of ``lib/host.py``: the process counters the program's round
spans carry, the device's idle time that no bookkeeping span names, and a
walk of the runner that reads the counters off the toy cell's record."""

import subprocess
import types

import pytest

from bench_testlib import ROOT, finish_walk, load, manifest, walk_cmd, walk_env

from lib import host  # noqa: F401  (registers the readers)
from lib import readers

import run as bench

HOST_METRICS = ("gc_s", "idle_unnamed_s")


def span(name, i, parent, rd, **args):
    return {"name": name, "id": i, "parent": parent, "round": rd, "tid": 1,
            "t0": float(i), "t1": float(i) + 0.5, "args": args}


TREE = [
    span("ckpt/round_snapshot", 1, None, 2, gc_s=0.001),
    span("round", 2, None, 2, gc_s=0.010),
    span("train_time", 3, 2, 2, gc_s=0.009),    # inside round
    span("round_epilogue", 4, None, 2, gc_s=0.002),
    span("ckpt/round_snapshot", 5, None, 3, gc_s=0.0),
    span("round", 6, None, 3, gc_s=0.020),
    span("round_epilogue", 7, None, 3, gc_s=0.001),
    span("round", 8, None, 4, gc_s=9.0),        # not a window round
]
CTX = {"rounds": [{"round": 2}, {"round": 3}], "spans": {"spans": TREE},
       "trace": {"idle_by_span": {"round": 0.002, "round_epilogue": 0.001,
                                  "experiment": 0.0005,
                                  "unattributed": 0.0001,
                                  "journal/write": 0.004,
                                  "ckpt/publish_best": 0.3}}}


def test_span_counter_sums_the_round_spans_per_round():
    r = readers.READERS["span_counter"]
    # The phase's counters lie inside the round's and are not added twice.
    assert r(CTX, counter="gc_s") == pytest.approx(
        (0.001 + 0.010 + 0.002 + 0.0 + 0.020 + 0.001) / 2)
    assert r(CTX, counter="gc_s", names=["train_time"]) == \
        pytest.approx(0.009 / 2)
    assert r(CTX, counter="absent") is None


def test_span_counter_reads_zero_and_nothing():
    r = readers.READERS["span_counter"]
    still = [span(n, i, None, 2, gc_s=0.0) for i, n in
             enumerate(("ckpt/round_snapshot", "round", "round_epilogue"))]
    ctx = {"rounds": [{"round": 2}], "spans": {"spans": still}}
    assert r(ctx, counter="gc_s") == 0.0
    # A program older than the counters: its round spans carry none.
    bare = [span(n, i, None, 2) for i, n in
            enumerate(("ckpt/round_snapshot", "round", "round_epilogue"))]
    assert r({"rounds": [{"round": 2}], "spans": {"spans": bare}},
             counter="gc_s") is None
    assert r({"rounds": [{"round": 2}], "spans": None, "trace": None},
             counter="gc_s") is None
    assert r({"rounds": [], "spans": {"spans": TREE}},
             counter="gc_s") is None


def test_idle_unnamed_reads_the_shallow_spans_only():
    r = readers.READERS["idle_unnamed"]
    assert r(CTX) == pytest.approx(0.002 + 0.001 + 0.0005 + 0.0001)
    named = {"rounds": [], "trace": {"idle_by_span": {"journal/write": 0.1}}}
    assert r(named) == 0.0
    assert r({"rounds": [], "trace": None}) is None
    assert r({"rounds": [], "trace": {"programs": {}}}) is None


@pytest.mark.parametrize("metric", HOST_METRICS)
def test_host_metric_file_and_entry(metric):
    body = load(f"benchmarks/metrics/{metric}.json")
    entry = next(m for m in manifest()["per_layer"] if m["name"] == metric)
    assert body["module"] == "host" and body["moves"] == "round_s"
    assert "workloads" not in entry and "workloads" not in body
    fn = readers.reader_for(body)
    assert fn.__module__ == "lib.host"
    assert fn(CTX, **body["params"]) is not None
    # Every cell reads it.
    for cell in (w["name"] for w in manifest()["workloads"]):
        loaded = bench.load_cell(types.SimpleNamespace(
            workload=cell, workload_file=None))
        assert metric in {m["name"] for m in loaded["metrics"]}


def test_traced_walk_reads_the_counters():
    """Off the chip the record is read all the same: the collector's
    seconds come out, and the idle metric, a device one, does not."""
    # At the lowest priority: the suite's collective tests run beside it,
    # and a starved rendezvous aborts their worker.
    proc = subprocess.Popen(
        ["nice", "-n", "19",
         *walk_cmd("toy.margin_ft", 2 ** 31 + 37, ["--trace", "1"])],
        env=walk_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=ROOT)
    rc, last, err = finish_walk(proc)
    assert rc == 3 and last["correct"] is True, err[-2000:]
    got = last["metrics"]
    assert got["gc_s"]["value"] >= 0 and got["gc_s"]["unit"] == "s/round"
    assert "idle_unnamed_s" not in got
