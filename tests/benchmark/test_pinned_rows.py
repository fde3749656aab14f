"""The run's health check finds the pool among the pinned arrays by what the
array itself says (``run.pinned_arrays``: its rows, and the rows each device's
shard holds), not by a sum that replicas of another array could make up:
replicated, row-sharded, padded, and a pool short of rows.  The walk that
takes a row-sharded pool through it on four virtual devices is in
``test_data_and_window.py``."""

import types

import pytest

import bench_testlib  # noqa: F401  (puts the benchmark on sys.path)

import run as bench


def array(rows, *shards):
    return {"rows": rows, "shards": {str(i): list(s)
                                     for i, s in enumerate(shards)}}


QUARTERS = [(0, 64), (64, 128), (128, 192), (192, 256)]


@pytest.mark.parametrize("pinned,layout", [
    ([array(256, (0, 256))], "replicated"),                   # one chip
    ([array(256, *[(0, 256)] * 4)], "replicated"),
    ([array(256, *QUARTERS)], "row-sharded"),
    ([array(64, *[(0, 64)] * 4), array(256, *QUARTERS)],
     "row-sharded"),                                          # test set first
    ([array(261, (0, 87), (87, 174), (174, 261))],
     None),                                                   # another array
    ([array(260, (0, 65), (65, 130), (130, 195), (195, 260))],
     None),                                                   # 4 pad rows of 4
    ([array(64, *[(0, 64)] * 4)], None),     # replicas that add up to the pool
    ([array(192, *QUARTERS[:3])], None),                      # a shard short
    ([array(256, *QUARTERS[:3])], None),                      # a part not held
    ([array(256, (0, 64), (128, 192), (192, 256), (192, 256))], None),
    ([array(128, (0, 128))], None),                           # half a pool
    ([array(256)], None), ([], None)])
def test_pool_is_pinned_reads_the_array_not_a_sum(pinned, layout):
    got = bench.pool_is_pinned(pinned, 256)
    assert (got and got["layout"]) == layout


def test_a_padded_row_sharded_pool_is_the_pool():
    thirds = array(258, (0, 86), (86, 172), (172, 258))
    assert bench.pool_is_pinned([thirds], 256)["layout"] == "row-sharded"
    assert bench.pool_is_pinned([thirds], 255) is None        # 3 pad rows of 3


def test_pinned_arrays_reads_rows_and_shards_off_the_array():
    def shard(dev, start, stop):
        return types.SimpleNamespace(
            device=types.SimpleNamespace(id=dev),
            index=(slice(start, stop), slice(None), slice(None)))
    pool = types.SimpleNamespace(shape=(256, 24, 128), addressable_shards=[
        shard(i, *QUARTERS[i]) for i in range(4)])
    test = types.SimpleNamespace(shape=(64, 24, 128), addressable_shards=[
        shard(i, None, None) for i in range(4)])
    cache = {"images": {("a", 64): ("ds", test, "labels"),
                        ("b", 256): ("ds", pool, "labels")}}
    got = bench.pinned_arrays(cache)
    assert got == [array(64, *[(0, 64)] * 4), array(256, *QUARTERS)]
    assert bench.pool_is_pinned(got, 256)["layout"] == "row-sharded"
    assert bench.pinned_arrays(None) == [] and bench.pinned_arrays({}) == []
