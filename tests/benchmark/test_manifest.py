"""BENCHMARK.json is consistent with the benchmark's files."""

import os
import re

import pytest

from bench_testlib import ROOT, load, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = [m["name"] for m in M["per_layer"]]
E2E = {m["name"]: m for m in M["end_to_end"]}


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in M["end_to_end"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_paths_cover_every_benchmark_file():
    for c in M["configs"]:
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
    assert not any(w.startswith("/") or ".." in w for w in M["command"])
    assert M["command"][1].startswith(M["paths"][0] + "/")


@pytest.mark.parametrize("config", [c["name"] for c in M["configs"]])
def test_config_entry_and_file(config):
    entry = next(c for c in M["configs"] if c["name"] == config)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config) and 1 <= len(entry["source"]) <= 200
    body = load(entry["file"])
    assert body["name"] == config and body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] and body["assumed"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and key in body["scale"]
        assert not re.search(r"(_dim|_rank|hidden|width|head)", key)
        assert key in body["published_scale"]
    assert any(w["config"] == config for w in M["workloads"])


CONFIG_FILES = sorted({c["file"] for c in M["configs"]} | {
    "tests/benchmark/toy/config.json", "tests/benchmark/toy/config_rows.json"})


@pytest.mark.parametrize("path", CONFIG_FILES)
def test_config_names_a_family_that_keeps_the_contract(path):
    """Every configuration file, the tests' own too, names its family by a
    path under ``paths``; the file is there and exports what ``run.py``
    calls."""
    import families
    rel = load(path)["family"]
    assert any(rel.startswith(p + "/") for p in M["paths"])
    assert os.path.isfile(os.path.join(ROOT, rel))
    module = families.load(rel, ROOT)
    assert all(callable(getattr(module, n)) for n in families.CONTRACT)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_entry_and_files(cell):
    entry = next(w for w in M["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    body = load(f"{M['paths'][0]}/workloads/{cell}.json")
    assert body["name"] == cell and body["config"] == entry["config"]
    assert body["chips"] == entry["chips"]
    assert any(c["name"] == entry["config"] for c in M["configs"])
    for key in ("strategy", "freeze_feature", "train", "check"):
        assert key in body
    assert set(body["check"]["limits"]) >= {
        "loss3", "gnorm1", "dparam", "test_rows", "score_gap", "pick_regret"}
    # every cell reports setup_s, one more end-to-end and a per-layer metric
    assert any("workloads" not in m or cell in m["workloads"]
               for m in M["per_layer"])


@pytest.mark.parametrize("metric", METRICS)
def test_per_layer_metric(metric):
    from lib import readers
    entry = next(m for m in M["per_layer"] if m["name"] == metric)
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert NAME.match(metric) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert entry["moves"] in E2E and entry["moves"] != "setup_s"
    body = load(f"{M['paths'][0]}/metrics/{metric}.json")
    # The reader is registered by the file of ``lib/`` the data file names.
    module = body.get("module", "readers")
    assert os.path.isfile(os.path.join(
        ROOT, M["paths"][0], "lib", f"{module}.py"))
    fn = readers.reader_for(body)
    assert fn is readers.READERS[body["reader"]]
    assert fn.__module__ == f"lib.{module}"
    assert body["layer"] == entry["layer"] and "\n" not in entry["layer"]
    assert body.get("workloads") == entry.get("workloads")
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS
    if metric.endswith("_roofline") or "mfu" in metric:
        assert entry["unit"] == "%" and entry["source"] == "device_trace"


def test_a_reader_that_no_file_registers_is_refused():
    from lib import readers
    with pytest.raises(KeyError):
        readers.reader_for({"name": "x", "reader": "no_such_reader"})
    with pytest.raises(ValueError):
        readers.reader_for({"name": "x", "module": "../run",
                            "reader": "counter"})
    with pytest.raises(ImportError):
        readers.reader_for({"name": "x", "module": "no_such_file",
                            "reader": "counter"})


@pytest.mark.parametrize("metric", sorted(E2E))
def test_end_to_end_metric(metric):
    entry = E2E[metric]
    assert set(entry) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert NAME.match(metric) and UNIT.match(entry["unit"])
    assert entry["source"] in ("host_clock", "device_trace")
