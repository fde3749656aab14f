"""The trace reduction on a hand-made trace and on a recorded v5e one."""

import json
import os

import pytest

from bench_testlib import BENCH

MS = 1_000_000


def hand_made():
    # two programs (fit 40+30 ms, run 10 ms), one 15 ms gap, one host span
    return [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": [("jit_epoch_scan(111)", 0, 40 * MS),
                        ("jit_run(222)", 40 * MS, 10 * MS),
                        ("jit_epoch_scan(111)", 65 * MS, 30 * MS)],
        "XLA Ops": [("fusion.1", 0, 25 * MS), ("convolution.2", 25 * MS,
                                                15 * MS),
                    ("fusion.3", 40 * MS, 10 * MS),
                    ("fusion.1", 65 * MS, 30 * MS)]}}]


def test_per_program_time_idle_share_and_gap_attribution():
    from lib import trace
    spans = [("rd_query_time", 38 * MS, 52 * MS),
             ("rd_init_network_weights_time", 52 * MS, 66 * MS)]
    red = trace.reduce_trace(hand_made(), 0, 100 * MS, spans)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["programs"] == {"jit_epoch_scan": pytest.approx(0.07),
                               "jit_run": pytest.approx(0.01)}
    assert red["busy_s"] == pytest.approx(0.08)
    gaps = dict(red["idle_gaps"])
    assert gaps["rd_init_network_weights_time"] == pytest.approx(0.015)
    assert gaps["unattributed"] == pytest.approx(0.005)
    assert red["top_ops"][0] == ("jit_epoch_scan/fusion.1",
                                 pytest.approx(0.055))
    assert trace.program_seconds(red, ["epoch_scan"]) == pytest.approx(0.07)
    assert trace.program_seconds(
        red, ["jit_run"], [(38 * MS, 52 * MS)]) == pytest.approx(0.01)
    assert trace.program_seconds(red, ["jit_run"], [(0, 10)]) == 0.0


def test_window_clips_events_and_overlaps_count_once():
    from lib import trace
    planes = hand_made()
    planes[0]["lines"]["XLA Ops"].append(("copy.9", 5 * MS, 10 * MS))
    red = trace.reduce_trace(planes, 20 * MS, 70 * MS)
    assert red["busy_s"] == pytest.approx(0.035)
    assert red["programs"]["jit_epoch_scan"] == pytest.approx(0.025)
    assert trace.first_event_ns(planes, "jit_run") == 40 * MS
    assert trace.first_event_ns(planes, "absent") is None


def test_readers_return_nothing_without_a_trace():
    from lib import readers
    ctx = {"rounds": [], "trace": None, "work": {}, "counters": {}}
    assert readers.READERS["trace_idle"](ctx) is None
    assert readers.READERS["flops_share"](ctx, kinds=["fit"]) is None
    assert readers.READERS["trace_program"](ctx, match=["x"],
                                            kinds=["fit"]) is None
    assert readers.READERS["sink_phase"](ctx, metric="rd_query_time") is None


def test_roofline_reader_on_the_hand_made_trace():
    from lib import peaks, readers, trace
    red = trace.reduce_trace(hand_made(), 0, 100 * MS)
    pk = peaks.peaks_for("TPU v5 lite")
    ctx = {"trace": red, "peaks": pk, "chips": 1,
           "work": {"fit": {"flops": 0.035 * pk["flops_bf16"],
                            "bytes": 1.0}}}
    value = readers.READERS["trace_program"](ctx, match=["epoch_scan"],
                                             kinds=["fit"])
    assert value == pytest.approx(50.0)
    assert ctx["bounds"]["epoch_scan"] == "compute"
    assert readers.READERS["flops_share"](ctx, kinds=["fit"]) == \
        pytest.approx(35.0)
    assert readers.READERS["trace_idle"](ctx) == pytest.approx(20.0)


RECORDED = os.path.join(BENCH, "testdata", "v5e_round_trimmed.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded v5e trace in the tree yet")
def test_recorded_v5e_trace():
    from lib import trace
    with open(RECORDED) as fh:
        rec = json.load(fh)
    planes = [{"name": p["name"],
               "lines": {k: [tuple(e) for e in v]
                         for k, v in p["lines"].items()}}
              for p in rec["planes"]]
    red = trace.reduce_trace(planes, rec["t0"], rec["t1"])
    assert red["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-6)
    for name, seconds in rec["expect"]["programs"].items():
        assert red["programs"][name] == pytest.approx(seconds, rel=1e-6)
