"""The data generator, the window arithmetic and two toy walks of the runner:
one chip, and four virtual devices with the pool sharded by rows."""

import numpy as np
import pytest

from bench_testlib import (
    assert_reads_the_parents_numbers, family, finish_walk, load, start_walk)

TOY = load("tests/benchmark/toy/config.json")


def test_data_is_a_function_of_the_seed_alone():
    data = family()
    a = data.make_data(2 ** 31 + 7, TOY, 96, 16)
    b = data.make_data(2 ** 31 + 7, TOY, 96, 16)
    c = data.make_data(2 ** 31 + 8, TOY, 96, 16)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.uint8 and a[0].shape == (96, 32, 32, 3)
    assert a[0].min() >= 32 and a[0].max() <= 222
    # class structure: rows of one class are closer than rows of two
    lab = a[1]
    same = np.flatnonzero(lab == lab[0])
    other = np.flatnonzero(lab != lab[0])
    if len(same) > 1:
        d_same = np.abs(a[0][same[0]].astype(int) - a[0][same[1]]).mean()
        d_other = np.abs(a[0][same[0]].astype(int) - a[0][other[0]]).mean()
        assert d_same < d_other


def test_generator_makes_no_float_array_of_the_pool(monkeypatch):
    data = family()
    seen = []
    real = data._fill_chunk

    def spy(images, labels, templates, seed, salt, start, rows):
        seen.append(rows)
        return real(images, labels, templates, seed, salt, start, rows)
    monkeypatch.setattr(data, "_fill_chunk", spy)
    monkeypatch.setattr(data, "CHUNK_ROWS", 32)
    monkeypatch.setattr(data, "GEN_THREADS", 1)
    images, _ = data.make_split(3, 21, 80, 32, 3, 16,
                                data.make_templates(3, 16, 3))
    assert seen == [32, 32, 16] and images.dtype == np.uint8


def test_weights_are_a_function_of_the_seed_and_cover_the_model():
    data = flops = family()
    r50 = load("benchmarks/configs/sslresnet50_in224.json")
    shapes = {k: v.shape for k, v in data.make_weights(1, TOY).items()}
    again = data.make_weights(1, TOY)
    other = data.make_weights(2, TOY)
    assert all(np.array_equal(again[k], v)
               for k, v in data.make_weights(1, TOY).items())
    assert not np.array_equal(again["linear.weight"], other["linear.weight"])
    assert shapes["encoder.conv1.weight"] == (64, 3, 7, 7)
    assert shapes["linear.weight"] == (16, 512)
    n = sum(int(np.prod(s)) for k, s in shapes.items() if "running" not in k)
    assert n == flops.param_count(TOY)
    assert data.flax_path("encoder.layer2.0.downsample.1.weight") == (
        "encoder", "stage2_block0", "downsample_bn", "scale")
    assert data.flax_path("encoder.layer1.1.conv2.weight") == (
        "encoder", "stage1_block1", "Conv_1", "kernel")
    assert len(data.block_keys(r50)) == 16


def events(*rows):
    return [(t, name, v, step) for t, name, v, step in rows]


END = "jit_cache_miss_delta"


def test_round_s_counts_whole_rounds_and_every_second():
    from lib import window
    ev = events((10.0, END, 0, 0), (20.0, END, 0, 1),
                (24.0, "rd_query_time", 1.5, 2), (29.0, "rd_train_time", 4.0, 2),
                (30.0, END, 0, 2), (45.0, "rd_train_time", 13.0, 3),
                (46.0, END, 2, 3))
    rounds = window.window_rounds(ev, t_open=20.0)
    assert [r["round"] for r in rounds] == [2, 3]
    # the stall inside round 3 is counted: (10 + 16) / 2
    assert window.round_seconds(rounds) == pytest.approx(13.0)
    assert rounds[0]["phases"] == {"rd_query_time": 1.5, "rd_train_time": 4.0}
    assert window.counter_sum(ev, END, rounds) == 2.0
    # a window that opens inside round 2 does not count it
    late = window.window_rounds(ev, t_open=25.0)
    assert [r["round"] for r in late] == [3]
    with pytest.raises(ValueError):
        window.round_seconds(window.window_rounds(ev, t_open=40.0))


def test_a_pause_at_a_boundary_is_not_the_rounds_time():
    from lib import window
    ev = events((20.0, END, 0, 1), (30.0, END, 0, 2), (43.0, END, 0, 3))
    rounds = window.window_rounds(ev, t_open=21.0,
                                  pauses=[(20.0, 21.0), (30.0, 33.0)])
    assert [r["seconds"] for r in rounds] == [pytest.approx(9.0),
                                              pytest.approx(10.0)]


def test_sink_calls_back_at_round_ends_only():
    from lib import window
    seen = []
    ticks = iter(range(100))
    sink = window.RecordingSink(lambda rd, now: seen.append((rd, now)),
                                clock=lambda: float(next(ticks)))
    sink.log_metrics({"rd_query_time": 1.0, "rd_train_time": 2.0}, step=4)
    sink.log_metric(END, 0, step=4)
    sink.log_asset("x", "y")
    sink.log_parameters({})
    assert seen == [(4, 2.0)] and len(sink.events) == 3


@pytest.fixture(scope="module")
def walk():
    proc = start_walk("toy.margin_ft", 2 ** 31 + 11,
                      ["--trace", "0", "--control",
                       "fp8,half_batch,state_unchanged"])
    return finish_walk(proc)


def test_toy_walk_reaches_its_last_line_and_ends_as_a_rehearsal(walk):
    rc, last, err = walk
    assert rc == 3, err[-2000:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device", "check"}
    assert list(last)[-2:] == ["check", "rehearsal"]
    assert last["correct"] is True, last["check"]
    assert last["attempted"] >= 1 and last["failed"] == 0
    assert set(last["metrics"]) == {"round_s", "setup_s"}
    assert last["metrics"]["round_s"]["value"] > 0
    assert last["device"]["platform"] == "cpu"
    assert "check loss3:" in err and "check pick_regret:" in err
    assert_reads_the_parents_numbers(last, "toy.margin_ft", 2 ** 31 + 11)


@pytest.mark.slow
def test_four_device_walk_passes_the_pinned_rows_check():
    """``chips: 4`` and ``pool_sharding: row`` on four virtual devices: each
    holds a quarter of the pool, the health check adds them up, and the walk
    goes on to the trace-free result line.  Marked slow: a second process
    that runs CPU collectives beside the suite's workers starves their
    rendezvous, and a worker aborts (three whole runs of three, PR 27)."""
    rc, last, err = finish_walk(start_walk("toy.margin_ft_x4", 7,
                                           ["--trace", "0"], devices=4))
    assert rc == 3 and last is not None, err[-2000:]
    assert "the pool is not pinned" not in err
    assert "per chip, row layout" in err
    # The pool's own entry: 256 rows in four parts (the test set's 48 rows,
    # replicated on four devices, add up to no pool).
    assert ("the pool is pinned: 256 rows, row-sharded over 4 device(s)"
            in err)
    assert last["correct"] is True, last["check"]
    assert last["device"]["count"] == 4
    assert set(last["metrics"]) == {"round_s", "setup_s"}
    assert list(last)[-2:] == ["check", "rehearsal"]


def test_the_control_and_the_planted_faults_fail_the_toy_cell(walk):
    _, last, _ = walk
    limits = {k: v[1] for k, v in last["check"].items()}
    for variant in ("fp8", "half_batch", "state_unchanged"):
        numbers = last["control"][variant]
        failed = [k for k, v in numbers.items()
                  if k in limits and not v <= limits[k]]
        assert failed, (variant, numbers)
    assert last["control"]["state_unchanged"]["dparam"] == pytest.approx(1.0)
