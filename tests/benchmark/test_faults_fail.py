"""Drive the rest of a run with the timed path broken underneath: ``correct``
has to come out false, once for each fault a cell can have."""

import pytest

from bench_testlib import (
    assert_reads_the_parents_numbers, finish_walk, start_walk)

FAULTS = {"state_unchanged": ("toy.margin_ft", 21),
          "half_batch": ("toy.margin_ft", 22),
          "score_altered": ("toy.coreset_lin", 23)}


@pytest.fixture(scope="module")
def walks():
    procs = {f: start_walk(cell, seed, ["--trace", "0", "--break", f])
             for f, (cell, seed) in FAULTS.items()}
    return {f: finish_walk(p) for f, p in procs.items()}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(walks, fault):
    rc, last, err = walks[fault]
    assert rc == 3 and last is not None, err[-2000:]
    assert last["correct"] is False, last["check"]
    over = [k for k, (v, lim) in last["check"].items() if not v <= lim]
    assert over
    # ... and it breaks what the parent commit's walk broke, digit for digit.
    assert_reads_the_parents_numbers(last, *FAULTS[fault], fault)


def test_sound_frozen_walk_is_correct_with_a_traced_line():
    rc, last, err = finish_walk(start_walk("toy.coreset_lin", 24,
                                           ["--trace", "1"]))
    assert rc == 3 and last["correct"] is True, err[-2000:]
    assert {"query_s", "fit_s", "test_s", "round_other_s",
            "window_compiles"} <= set(last["metrics"])
    # ``--trace 1`` switches the program's span recorder on, and the span
    # and counter readers read its record off the chip too.
    assert {"ckpt_s", "fit_step_useful"} <= set(last["metrics"])
    assert 0 < last["metrics"]["fit_step_useful"]["value"] <= 100
    # off the chip no device metric is ever printed
    assert not {"round_mfu", "fit_roofline", "device_idle", "gather_s",
                "idle_ckpt_s", "idle_reinit_s", "score_roofline"} & set(
        last["metrics"])
    assert "busy_s" not in last["device"]
    assert_reads_the_parents_numbers(last, "toy.coreset_lin", 24)
