"""The ResNet family's FLOP/byte count, the peaks table, the no-TPU refusal
and the allocator's pin."""

import subprocess
import sys

import pytest

from bench_testlib import BENCH, ROOT, family, load, walk_env

R50 = load("benchmarks/configs/sslresnet50_in224.json")
R18 = load("benchmarks/configs/sslresnet18_in224.json")


@pytest.mark.parametrize("config,gmac,params", [
    (R50, 4.09, 25.56e6), (R18, 1.82, 11.69e6)])
def test_forward_macs_match_the_published_figures(config, gmac, params):
    flops = family()
    assert flops.forward_macs(config) / 1e9 == pytest.approx(gmac, rel=0.01)
    assert flops.param_count(config) == pytest.approx(params, rel=0.01)
    assert flops.embed_dim(config) == config["embed_dim"]


@pytest.mark.parametrize("config", [R50, R18])
def test_backward_ratios(config):
    flops = family()
    fwd = flops.forward_macs(config)
    stem = 49 * 3 * 64 * 112 * 112
    assert flops.backward_macs(config) == 2 * fwd - stem
    head = config["embed_dim"] * config["num_classes"]
    assert flops.backward_macs(config, head_only=True) == head
    full = flops.work(config, "fit", 128, 1)
    lin = flops.work(config, "fit", 128, 1, head_only=True)
    fwd_only = flops.work(config, "forward", 128, 1)
    assert full["flops"] / fwd_only["flops"] == pytest.approx(3.0, abs=0.07)
    assert lin["flops"] / fwd_only["flops"] == pytest.approx(1.0, abs=0.01)
    assert lin["bytes"] < full["bytes"]


def test_least_seconds_names_the_bound():
    from lib import peaks
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = peaks.least_seconds(family().work(R50, "fit", 128, 1), pk)
    assert bound == "compute"
    assert t == pytest.approx(128 * 2 * (3 * 4.089e9 - 0.118e9) / 197e12,
                              rel=0.01)
    t, bound = peaks.least_seconds({"flops": 1.0, "bytes": 819e9}, pk)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_peaks_table_refuses_an_unknown_device():
    from lib import peaks
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_runner_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload",
         "r18_in224.margin_ft", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=walk_env(), cwd=ROOT, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_the_allocator_is_pinned_before_anything_is_made():
    """``run.pin_allocator`` in a process of its own (it would re-tune the
    test worker's): glibc takes both thresholds, and ``main`` calls it before
    it loads the cell."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import run, inspect; print(run.pin_allocator()); "
         "src = inspect.getsource(run.main); "
         "print(src.index('pin_allocator()') < src.index('load_cell('))"],
        env=walk_env(), cwd=BENCH, capture_output=True, text=True,
        timeout=120)
    assert proc.stdout.split() == ["True", "True"], proc.stderr
