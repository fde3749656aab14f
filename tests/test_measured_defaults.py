"""What a user runs by default, and that the ledger's cells run the same.

Fourteen flags pick between implementations of one thing (ROADMAP D3).
``TABLE`` below is the one place that says, for each, which field it
sets, what the default is and which implementation that default selects
on a one-device accelerator mesh.  Two families of cases hold it true:

  * per flag: the CLI with no arguments, through to the config it
    builds, and the bare dataclasses give the same value, it is the
    table's, and where the code's own resolver can be asked without a
    device it names the table's implementation;
  * per accepted cell of ``BENCHMARK.json``: the workload's overrides
    and the configuration file leave all fourteen at the table's value,
    so what ``PERF_LEDGER.jsonl`` measures is what a user gets.

A changed default fails here: it is then a deliberate act, made with the
table in the same diff, and D3's "one path wins on both cells" is argued
from this file.
"""

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Callable, Optional

import numpy as np
import pytest

from active_learning_tpu.config import ExperimentConfig, TrainConfig
from active_learning_tpu.experiment import cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

# The experiment-level default of a flag that defers to the arg pool's
# TrainConfig field of the same name.
DEFERS = None
# A flag with no TrainConfig field behind it.
NO_FIELD = object()


def _mesh1():
    from active_learning_tpu.parallel import mesh as mesh_lib
    return mesh_lib.make_mesh(1)


def _dtype(monkeypatch):
    import jax
    import jax.numpy as jnp
    from active_learning_tpu.models import factory
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return {jnp.bfloat16: "bfloat16", jnp.float32: "float32"}[
        factory.resolve_dtype(TrainConfig().dtype)]


def _bn_stats(monkeypatch):
    import jax.numpy as jnp
    from active_learning_tpu.models import factory
    got = factory.resolve_bn_stats_dtype(TrainConfig().bn_stats_dtype,
                                         jnp.bfloat16)
    return "FusedBatchNorm" if got == jnp.bfloat16 else "flax BatchNorm"


def _stem(monkeypatch):
    from active_learning_tpu.models.factory import get_network
    model = get_network("imagenet", "SSLResNet50", dtype="float32",
                        stem=TrainConfig().stem)
    return "s2d 4x4/s1" if model.stem == "s2d" else "7x7/s2"


def _resident(monkeypatch):
    from active_learning_tpu.parallel import resident
    v5e = {"bytes_limit": 16 << 30, "bytes_in_use": 0}
    budget = resident.resolve_budget(TrainConfig().resident_scoring_bytes,
                                     stats=v5e, cache={})
    return "pinned, auto budget" if 0 < budget < 16 << 30 else "off"


def _sharding(monkeypatch):
    from active_learning_tpu.parallel import resident
    return resident.resolve_sharding(TrainConfig().pool_sharding, _mesh1())


def _backend(monkeypatch):
    from active_learning_tpu.data import diskpool
    # The one-chip machine's host (40 GiB) and a cell's pool (4.93 GB).
    monkeypatch.setattr(diskpool, "host_ram_bytes", lambda: 40 << 30)
    cfg = TrainConfig()
    return diskpool.resolve_pool_backend(
        cfg.pool_backend, 32768 * 224 * 224 * 3,
        cfg.pool_disk_watermark_frac)


def _fused(monkeypatch):
    from active_learning_tpu.train import optim
    tx = optim.make_fused_optimizer(TrainConfig())
    return "optax chain" if tx is None else type(tx).__name__


def _optim_state(monkeypatch):
    import jax.numpy as jnp
    from active_learning_tpu.train import optim
    got = optim.resolve_optim_state_dtype(TrainConfig().optim_state_dtype)
    return {jnp.float32: "float32", jnp.bfloat16: "bfloat16"}[got]


def _grad_allreduce(monkeypatch):
    from active_learning_tpu.parallel import mesh as mesh_lib
    return mesh_lib.resolve_grad_allreduce(TrainConfig().grad_allreduce,
                                           _mesh1())


def _round_pipeline(monkeypatch):
    from active_learning_tpu.experiment import pipeline
    return pipeline.resolve_round_pipeline(ExperimentConfig().round_pipeline,
                                           _mesh1())


def _kcenter(monkeypatch):
    from active_learning_tpu.strategies import kcenter
    rows = np.random.default_rng(0).standard_normal((64, 8)).astype(
        np.float32)
    labeled = np.zeros(64, bool)
    labeled[:4] = True
    kcenter.kcenter_greedy([rows], labeled, 8,
                           rng=np.random.default_rng(0),
                           batch_q=ExperimentConfig().kcenter_batch)
    return kcenter.LAST_BACKEND


@dataclasses.dataclass(frozen=True)
class Row:
    flag: str
    field: str                   # on ExperimentConfig (and TrainConfig)
    experiment_default: Any      # DEFERS = the TrainConfig field decides
    train_default: Any           # NO_FIELD = ExperimentConfig alone
    selects: str                 # on a one-device accelerator mesh
    # The code's own answer, where it can be had without a device; None
    # where only a run can say (the cells' ``expect_feed`` then does).
    resolver: Optional[Callable] = None


TABLE = (
    Row("--dtype", "dtype", DEFERS, "auto", "bfloat16", _dtype),
    Row("--bn_stats_dtype", "bn_stats_dtype", DEFERS, "auto",
        "FusedBatchNorm", _bn_stats),
    Row("--stem", "stem", DEFERS, "default", "7x7/s2", _stem),
    Row("--resident_scoring_bytes", "resident_scoring_bytes", DEFERS, None,
        "pinned, auto budget", _resident),
    Row("--pool_sharding", "pool_sharding", DEFERS, "auto", "replicated",
        _sharding),
    Row("--pool_backend", "pool_backend", DEFERS, "auto", "memory",
        _backend),
    Row("--train_feed", "train_feed", DEFERS, "auto",
        "resident gather from the pinned pool, epoch scan"),
    Row("--feed_workers", "feed_workers", DEFERS, None,
        "loader_tr.num_workers; unused by the resident feed"),
    Row("--fused_optimizer", "fused_optimizer", DEFERS, "auto", "FusedSGD",
        _fused),
    Row("--optim_state_dtype", "optim_state_dtype", DEFERS, "f32",
        "float32", _optim_state),
    Row("--grad_allreduce", "grad_allreduce", DEFERS, "f32", "f32",
        _grad_allreduce),
    Row("--scale_batch", "scale_batch", None, NO_FIELD,
        "off: the arg pool's batch is the global batch"),
    Row("--round_pipeline", "round_pipeline", "auto", NO_FIELD, "off",
        _round_pipeline),
    Row("--kcenter_batch", "kcenter_batch", 8, NO_FIELD, "xla-batched",
        _kcenter),
)

# ROADMAP D3's list, in its order.
D3_FLAGS = ("--dtype --bn_stats_dtype --stem --resident_scoring_bytes "
            "--pool_sharding --pool_backend --train_feed --feed_workers "
            "--fused_optimizer --optim_state_dtype --grad_allreduce "
            "--scale_batch --round_pipeline --kcenter_batch").split()


def test_table_is_d3s_fourteen():
    assert [r.flag for r in TABLE] == D3_FLAGS


@pytest.mark.parametrize("row", TABLE, ids=lambda r: r.flag.lstrip("-"))
def test_default_is_the_tables(row, monkeypatch):
    parser = cli.get_parser()
    flags = {s for a in parser._actions for s in a.option_strings}
    assert row.flag in flags
    from_cli = cli.args_to_config(parser.parse_args([]))
    assert getattr(from_cli, row.field) == row.experiment_default
    assert getattr(ExperimentConfig(), row.field) == row.experiment_default
    if row.train_default is NO_FIELD:
        assert not hasattr(TrainConfig(), row.field)
    else:
        assert row.experiment_default is DEFERS
        assert getattr(TrainConfig(), row.field) == row.train_default
    if row.resolver is not None:
        assert row.resolver(monkeypatch) == row.selects


def _cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return [w["name"] for w in json.load(fh)["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_cell_runs_the_defaults(name, tmp_path):
    """Read through the runner's own ``load_cell`` / ``build_configs``
    (read only): the configs a cell hands ``run_experiment`` hold the
    table's value in every one of the fourteen fields."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import run as bench
    cell = bench.load_cell(argparse.Namespace(
        workload=name, workload_file=None, config_file=None))
    workload, config = cell["workload"], cell["config"]
    cfg, train_cfg = bench.build_configs(cell, 1, str(tmp_path),
                                         str(tmp_path / "ckpt.pth"))
    for row in TABLE:
        assert getattr(cfg, row.field) == row.experiment_default, row.flag
        if row.train_default is not NO_FIELD:
            assert getattr(train_cfg, row.field) == row.train_default, \
                row.flag
    # The default said aloud, and the feed the harness holds a run to.
    assert config["compute_dtype"] == TrainConfig().dtype
    assert workload["expect_feed"] == {"source": "resident", "form": "scan"}
    assert train_cfg.device_resident is None
