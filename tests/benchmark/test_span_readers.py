"""The span record read from outside the program: self time, the deepest-span
attribution of idle time, scopes, the readers (which ``run.py`` finds through
their data files' ``module``), and the glue that runs on the chip."""

import json
import os
import types

import pytest

from bench_testlib import BENCH, ROOT, load, manifest

from lib import readers
from lib import spans as spans_lib
from lib import trace as trace_lib

import run as bench

LISTED = {m["name"] for m in manifest()["per_layer"]}
# The metrics whose reader is registered outside ``lib/readers.py``: until
# PR 27 their files waited beside ``BENCHMARK.json`` for ``run.py`` to look
# a reader up by its module.
SPAN_METRICS = sorted(
    name for name in LISTED
    if load(f"benchmarks/metrics/{name}.json").get("module") == "spans")


def span(name, i, parent, t0, t1, tid=1, rd=2, **args):
    return {"name": name, "id": i, "parent": parent, "round": rd,
            "tid": tid, "t0": t0, "t1": t1, "args": args}


TREE = [
    span("round", 1, None, 0.0, 10.0),
    span("query_time", 2, 1, 1.0, 4.0),
    span("collect_pool", 3, 2, 1.5, 3.5, rows=100, rows_run=128),
    span("train_time", 4, 1, 4.0, 9.0),
    span("epoch", 5, 4, 4.0, 5.0, steps_real=33, steps_run=48),
    span("epoch", 6, 4, 6.0, 7.0, steps_real=33, steps_run=48),
    span("ckpt/publish_best", 7, 4, 8.0, 9.0, bytes=5),
    span("collect_pool", 8, None, 2.0, 3.0, tid=2),     # the spec-scorer
    span("ckpt/save_experiment", 9, 1, 9.0, 9.5),
    span("round", 10, None, 10.0, 20.0, rd=3),
    span("ckpt/publish_best", 11, 10, 18.0, 19.5, rd=3, bytes=5),
]


def test_self_time_agrees_with_the_programs_own():
    from active_learning_tpu.telemetry import spans as program
    events = [{"name": s["name"], "ph": "X", "ts": s["t0"] * 1e6,
               "dur": (s["t1"] - s["t0"]) * 1e6, "tid": s["tid"],
               "args": {"id": s["id"], "parent": s["parent"],
                        "round": s["round"]}} for s in TREE]
    mine = spans_lib.self_seconds(TREE)
    assert mine == pytest.approx(program.self_seconds(events))
    assert mine[1] == pytest.approx(1.5) and mine[4] == pytest.approx(2.0)
    assert mine[2] == pytest.approx(1.0)    # the other thread takes nothing


def test_load_record_places_spans_on_perf_counter(tmp_path):
    doc = {"otherData": {"perf_origin": 1000.0}, "traceEvents": [
        {"name": "round", "ph": "X", "ts": 2e6, "dur": 3e6, "tid": 7,
         "args": {"id": 4, "parent": None, "round": 1, "attempt": 0}},
        {"name": "old", "ph": "X", "ts": 0.0, "dur": 1.0, "tid": 7},
        {"name": "thread_name", "ph": "M", "tid": 7, "args": {}}]}
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(doc))
    rec = spans_lib.load_record(str(path))
    assert rec["perf_origin"] == 1000.0 and len(rec["spans"]) == 1
    s = rec["spans"][0]
    assert (s["t0"], s["t1"], s["round"], s["id"]) == (1002.0, 1005.0, 1, 4)


def test_deepest_span_gets_every_idle_instant():
    timeline = spans_lib.deepest_timeline(TREE, 1)
    assert [(a, b, n) for a, b, n in timeline][:6] == [
        (0.0, 1.0, "round"), (1.0, 1.5, "query_time"),
        (1.5, 3.5, "collect_pool"), (3.5, 4.0, "query_time"),
        (4.0, 5.0, "epoch"), (5.0, 6.0, "train_time")]
    # Pieces neither overlap nor leave a hole inside the root.
    assert all(timeline[i][1] == timeline[i + 1][0]
               for i in range(len(timeline) - 1))
    gaps = [(0.5, 1.2), (3.0, 4.5), (8.5, 9.2), (19.0, 21.0)]
    idle = spans_lib.idle_by_span(gaps, timeline)
    assert idle == pytest.approx({
        "query_time": 0.2 + 0.5, "collect_pool": 0.5, "epoch": 0.5,
        "ckpt/publish_best": 0.5 + 0.5, "ckpt/save_experiment": 0.2,
        # [0.5, 1) lies in round 2 itself and [19.5, 20) in round 3;
        # after 20 no span is open.
        "round": 0.5 + 0.5, "unattributed": 1.0})
    assert sum(idle.values()) == pytest.approx(
        sum(b - a for a, b in gaps))


def test_device_gaps_are_what_no_operation_covers():
    ops = [("a", 10, 5), ("b", 12, 10), ("c", 30, 5), ("d", 50, 10)]
    assert spans_lib.device_gaps(ops, 0, 40) == [(0, 10), (22, 30),
                                                 (35, 40)]
    assert spans_lib.device_gaps(ops, 12, 22) == []


V5E_EVENT = ('%copy.7 = u8[32768,224,224,3]{2,1,3,0:T(8,128)(4,1)} copy('
             'u8[32768,224,224,3]{0,2,3,1:T(8,128)(4,1)} %images.1), '
             'sharding={replicated}')

# Two compiled modules of one name (two shape buckets), as the trace's
# metadata plane holds them: the second numbers its instructions otherwise.
HLO = {"jit_run_score_x": [{"copy.7": "images",
                            "fusion.1": "jit(run_score_x)/pool_gather/gather",
                            "fusion.2": "jit(run_score_x)/forward/conv"}],
       "jit_epoch_scan": [
           {"while.5": "jit(epoch_scan)/while",
            "fusion.1": "jit(epoch_scan)/while/body/closed_call/jit(train_"
                        "step)/forward_backward/dot"},
           {"while.9": "jit(epoch_scan)/while",
            "fusion.1": "jit(epoch_scan)/while/body/pool_gather/gather",
            "fusion.7": "jit(epoch_scan)/while/body/closed_call/jit(train_"
                        "step)/optimizer/mul"}]}


def test_scope_of_an_operation():
    assert trace_lib.op_name(V5E_EVENT) == "copy.7"     # the old name holds
    gather = "jit(run_score_prob_stats)/pool_gather/gather"
    assert spans_lib.scope_of(gather, bench.SCOPES) == "pool_gather"
    inner = "jit(epoch_scan)/while/body/forward_backward/view/mul"
    assert spans_lib.scope_of(inner, bench.SCOPES) == "view"
    assert spans_lib.scope_of("jit(f)/mul", bench.SCOPES) is None
    assert spans_lib.scope_of("images", bench.SCOPES) is None


def test_operations_are_named_from_their_programs_module():
    modules = [("jit_run_score_x(11)", 0, 300),
               ("jit_epoch_scan(22)", 400, 2000),       # the second bucket
               ("jit_unknown(33)", 3000, 100)]
    ops = [("copy.7", 0, 100), ("fusion.1", 100, 100), ("fusion.2", 200, 50),
           ("while.9", 400, 2000), ("fusion.1", 500, 100),
           ("fusion.7", 600, 1000), ("fusion.1", 3000, 50),
           ("stray", 5000, 10)]
    named = spans_lib.name_ops(modules, ops, HLO)
    assert [(p.rsplit("/", 2)[-2:] if p else p, key)
            for p, _, _, key in named] == [
        (["images"], "jit_run_score_x/copy.7"),
        (["pool_gather", "gather"], "jit_run_score_x/fusion.1"),
        (["forward", "conv"], "jit_run_score_x/fusion.2"),
        (["jit(epoch_scan)", "while"], "jit_epoch_scan/while.9"),
        # fusion.1 of the bucket that ran, not of the one compiled first.
        (["pool_gather", "gather"], "jit_epoch_scan/fusion.1"),
        (["optimizer", "mul"], "jit_epoch_scan/fusion.7"),
        ("", "jit_unknown/fusion.1"), ("", "stray")]
    got = spans_lib.scope_seconds_of(named, 150, 1100, bench.SCOPES)
    # The while event covers its body's operations and has no scope.
    assert got == pytest.approx({"pool_gather": 50e-9 + 100e-9,
                                 "forward": 50e-9, "optimizer": 500e-9})
    overlapping = [("jit(f)/forward/call", 0, 100, "f/call"),
                   ("jit(f)/forward/call/dot", 10, 50, "f/dot")]
    assert spans_lib.scope_seconds_of(overlapping, 0, 100, ("forward",)) \
        == pytest.approx({"forward": 100e-9})


def test_a_real_trace_carries_its_programs_op_names(tmp_path):
    """The profiler's own file, made here on the CPU: the metadata plane
    holds each compiled module, and its instructions' ``op_name`` the
    scopes."""
    import jax
    import jax.numpy as jnp

    def run_score_demo(x):
        with jax.named_scope("pool_gather"):
            y = x[jnp.arange(8)]
        with jax.named_scope("forward"):
            return (y @ y.T).sum()

    fn = jax.jit(run_score_demo)
    x = jnp.ones((64, 64))
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("collect_pool"):
            fn(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    extras = spans_lib.load_trace_extras(
        trace_lib.find_xplane(str(tmp_path)), {"collect_pool"})
    assert [h[0] for h in extras["host"]] == ["collect_pool"]
    tables = extras["hlo"]["jit_run_score_demo"]
    assert len(tables) == 1
    found = {spans_lib.scope_of(path, bench.SCOPES)
             for path in tables[0].values()}
    assert {"pool_gather", "forward"} <= found
    assert spans_lib.hlo_op_names(b"") == {}


def test_the_two_clocks_are_compared_span_by_span():
    spans = [span("round", 1, None, 1.0, 2.0),
             span("epoch", 2, 1, 1.2, 1.4),
             span("epoch", 3, 1, 1.6, 1.8),
             span("before_the_trace", 4, None, 0.1, 0.2)]
    host = [("round", 1_000_200_000, 1_000_000_000, "python3"),
            ("epoch", 1_200_050_000, 200_000_000, "python3"),
            ("epoch", 1_600_000_000, 200_400_000, "python3")]
    pairs = spans_lib.paired(spans, host, lambda t: int(t * 1e9))
    assert [c - a for a, _, c, _ in pairs] == [200_000, 50_000, 0]
    got = spans_lib.clock_disagreement(pairs)
    assert got["spans_compared"] == 3
    assert got["largest_s"] == pytest.approx(4e-4)
    assert spans_lib.clock_disagreement(
        spans_lib.paired(spans, [], int)) is None


CTX = {"rounds": [{"round": 2}, {"round": 3}],
       "spans": {"spans": TREE},
       "trace": {"idle_by_span": {"ckpt/publish_best": 0.4,
                                  "ckpt/load_best": 0.1,
                                  "reinit/overlay": 0.2, "round": 0.05},
                 "scope_s": {"pool_gather": 2.5, "forward": 0.7}}}


def test_readers_read_spans_counters_and_scopes():
    r = readers.READERS
    assert r["span_seconds"](CTX, names=["ckpt/*"]) == pytest.approx(
        (1.0 + 0.5 + 1.5) / 2)
    # ``under``: the spec-scorer's pass has no query_time above it.
    assert r["span_seconds"](CTX, names=["collect_pool"],
                             under="query_time") == pytest.approx(1.0)
    assert r["span_seconds"](CTX, names=["no_such"]) is None
    assert r["span_ratio"](CTX, span="epoch", num="steps_real",
                           den="steps_run") == pytest.approx(68.75)
    assert r["span_idle"](CTX, names=["ckpt/*"]) == pytest.approx(0.5)
    assert r["span_idle"](CTX, names=["reinit/*"]) == pytest.approx(0.2)
    assert r["scope_seconds"](CTX, scope="pool_gather") == 2.5
    assert r["scope_seconds"](CTX, scope="kcenter") is None


def test_readers_are_silent_on_a_program_without_the_span_tree():
    """The parent commit: no record, no scopes — every new reader returns
    nothing and the line leaves the metric out."""
    bare = {"rounds": [{"round": 2}], "trace": {"programs": {}}}
    r = readers.READERS
    assert r["span_seconds"](bare, names=["ckpt/*"]) is None
    assert r["span_ratio"](bare, span="epoch", num="a", den="b") is None
    assert r["span_idle"](bare, names=["ckpt/*"]) is None
    assert r["scope_seconds"](bare, scope="pool_gather") is None
    assert r["span_seconds"]({"rounds": [], "trace": None},
                             names=["x"]) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_waiting_metric_file(metric):
    """A metric whose reader ``lib/spans.py`` registers: ``run.py`` finds it
    through the data file's ``module`` and reads it like any other."""
    with open(os.path.join(BENCH, "metrics", f"{metric}.json")) as fh:
        body = json.load(fh)
    assert body["name"] == metric and body["moves"] == "round_s"
    assert body["reader"] in ("span_seconds", "span_idle", "span_ratio",
                              "scope_seconds")
    assert body["reader"] in readers.READERS
    assert body["source"] in ("device_trace", "program_span",
                              "program_counter")
    assert body["better"] in ("lower", "higher") and body["unit"]
    cell = bench.load_cell(types.SimpleNamespace(
        workload="r18_in224.margin_ft", workload_file=None))
    mine = next(m for m in cell["metrics"] if m["name"] == metric)
    assert mine["module"] == "spans" and mine["unit"] == body["unit"]
    assert readers.reader_for(mine)(CTX, **mine["params"]) is not None


def test_six_metrics_wait_and_two_are_listed():
    """They waited; now all six are entries, and the duplicate is gone."""
    assert SPAN_METRICS == ["ckpt_s", "fit_step_useful", "gather_s",
                            "idle_ckpt_s", "idle_reinit_s", "score_pass_s"]
    assert {"reinit_s", "score_step_roofline"} <= LISTED
    assert "score_roofline" not in LISTED
    assert not os.path.exists(os.path.join(BENCH, "span_run.py"))


def test_read_spans_lays_the_record_over_the_reduction(tmp_path,
                                                       monkeypatch):
    """The glue that runs on the chip, on a hand-made trace: 10 s traced
    from perf_counter 100.0, the device busy except [3.2, 3.9) (inside a
    reinit span) and [8.0, 8.6) (a checkpoint write)."""
    work = tmp_path / "cell"
    (work / "trace").mkdir(parents=True)
    (work / "logs" / "exp").mkdir(parents=True)

    def ev(name, i, parent, t0, t1, rd=2, **args):
        return {"name": name, "ph": "X", "ts": t0 * 1e6,
                "dur": (t1 - t0) * 1e6, "tid": 1,
                "args": {"id": i, "parent": parent, "round": rd, **args}}
    doc = {"otherData": {"perf_origin": 100.0}, "traceEvents": [
        ev("experiment", 1, None, -50.0, 30.0, rd=None),
        ev("round", 2, 1, 0.1, 9.9),
        ev("query_time", 3, 2, 0.1, 3.0),
        ev("init_network_weights_time", 4, 2, 3.0, 4.0),
        ev("reinit/model_init", 5, 4, 3.1, 3.95),
        ev("train_time", 6, 2, 4.0, 9.0),
        ev("ckpt/publish_best", 7, 6, 7.9, 8.7, bytes=10),
        ev("round_epilogue", 8, 1, 9.9, 10.0)]}
    (work / "logs" / "exp" / "trace.json").write_text(json.dumps(doc))
    ns = 10 ** 9
    busy = [("%fusion.1 = f32[] fusion()", 0, int(3.2 * ns)),
            ("%copy.7 = u8[] copy()", int(3.9 * ns), int(4.1 * ns)),
            ("%while.5 = () while()", int(8.6 * ns), int(1.4 * ns))]
    planes = [{"name": "/device:TPU:0", "lines": {
        trace_lib.MODULE_LINE: [("jit_bench_anchor(1)", 0, 10 * ns)],
        trace_lib.OP_LINE: [(trace_lib.op_name(n), s, d)
                            for n, s, d in busy]}}]
    host = [("round", int(0.1 * ns) + 300_000, int(9.8 * ns), "python3"),
            ("train_time", int(4.0 * ns) + 300_000, int(5.0 * ns),
             "python3")]
    monkeypatch.setattr(trace_lib, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(
        spans_lib, "load_trace_extras", lambda path, names: {
            "host": host, "hlo": {"jit_bench_anchor": [{
                "copy.7": "jit(run_score_x)/pool_gather/gather",
                "while.5": "jit(epoch_scan)/while/body/forward_backward/"
                           "dot"}]}})
    ctl = types.SimpleNamespace(trace_dir=str(work / "trace"),
                                trace_anchor=100.0,
                                trace_span=[100.0, 110.0],
                                pauses=[(109.92, 109.97)])
    red = {"idle_gaps": [("rd_train_time", 1.3)],
           "top_ops": [("jit_bench_anchor/copy.7", 4.1)]}
    extras = spans_lib.read_spans(ctl, red, planes, bench.SCOPES)
    idle = red["idle_by_span"]
    assert idle["reinit/model_init"] == pytest.approx(0.7, abs=1e-3)
    assert idle["ckpt/publish_best"] == pytest.approx(0.6, abs=1e-3)
    assert sum(idle.values()) == pytest.approx(1.3, abs=1e-6)
    assert red["idle_gaps"][0][0] == "reinit/model_init"
    assert red["scope_s"] == pytest.approx({"pool_gather": 4.1,
                                            "forward_backward": 1.4})
    assert extras["ops_named"] == [2, 3, 1]
    assert extras["traced_round"] == 2
    assert extras["clock"]["largest_s"] == pytest.approx(3e-4, rel=1e-3)
    assert extras["idle_deeper_than_phase_share"] == pytest.approx(1.0)
    assert extras["idle_unattributed_share"] == 0.0
    assert extras["subtree_self_sum_s"] == pytest.approx(
        extras["round_span_s"], rel=1e-6)
    # The runner's pause at the boundary is not the epilogue's time.
    assert dict(extras["host_self"])["round_epilogue"] == pytest.approx(
        0.1 - 0.05)
    assert extras["host_self"][0][0] == "train_time"
    # A program without the recorder: nothing is laid over.
    os.remove(work / "logs" / "exp" / "trace.json")
    red2 = {"idle_gaps": [("rd_train_time", 1.3)]}
    assert spans_lib.read_spans(ctl, red2, planes, bench.SCOPES) == {}
    assert red2 == {"idle_gaps": [("rd_train_time", 1.3)]}
    assert os.path.isdir(os.path.join(ROOT, "benchmarks"))
