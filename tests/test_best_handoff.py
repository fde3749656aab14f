"""The fit's best weights have ONE home on each side (DESIGN.md §10):
the device tree ``load_best_ckpt`` installs without a file read, and the
one host copy ``ckpt/publish_best`` fetched, which the ladder's round
snapshot shares and whose serialisation ``rd_{n}.msgpack`` is written
from when the best IS the final state.  The short path is taken on what
the code observes (tag, state identity, ``best_epoch == epochs_run``);
today's path is the other branch of each test and gives the same bits."""

import dataclasses
import gc
import glob
import itertools
import json
import os
import signal
import weakref

import jax
import numpy as np
import pytest

from active_learning_tpu import faults
from active_learning_tpu.config import ExperimentConfig, TelemetryConfig
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.experiment.driver import (_restore_round_snapshot,
                                                   _round_snapshot,
                                                   run_experiment)
from active_learning_tpu.faults import preempt as preempt_lib
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.telemetry import spans as spans_lib
from active_learning_tpu.train import checkpoint as ckpt_lib
from active_learning_tpu.utils.metrics import NullSink
from helpers import TinyClassifier, make_strategy, tiny_train_config

N_EPOCH = 3
ROUNDS = 3

# mode -> (scripted validation accuracies of a fit's epochs, the epoch
# that is best).  ">=" lets a later epoch win a tie, so "last" rises.
MODES = {
    "best_earlier": ((0.9, 0.5, 0.4), 1),
    "best_last": ((0.1, 0.2, 0.3), N_EPOCH),
    "no_validation": (None, N_EPOCH),
}


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_same_bits(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb) > 0
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _read(path):
    with open(path, "rb") as fh:
        return fh.read()


class Rounds:
    """A strategy driven round by round, verb for verb as
    ``run_experiment`` drives it, under a recording tracer."""

    def __init__(self, mode, **overrides):
        accs, self.best_epoch = MODES[mode]
        self.strategy = s = make_strategy(
            "MarginSampler", n_train=96, n_epoch=N_EPOCH, **overrides)
        if accs is None:
            s.cfg = dataclasses.replace(s.cfg, early_stop_patience=0)
        else:
            script = itertools.cycle(accs)
            real = s.trainer.evaluate

            def evaluate(state, dataset, idxs):
                perf = dict(real(state, dataset, idxs))
                if dataset is s.al_set:     # the fit's validation pass
                    perf["accuracy"] = next(script)
                return perf
            s.trainer.evaluate = evaluate
        self.tracer = spans_lib.set_tracer(spans_lib.SpanTracer(enabled=True))

    def close(self):
        spans_lib.set_tracer(None)

    def until_load(self, rd, after_init=None):
        """Round ``rd`` up to (not including) ``load_best_ckpt``."""
        s = self.strategy
        s.round = rd
        if rd > 0:
            idxs, cost = s.query(s.cfg.round_budget)
            s.update(idxs, cost)
        s.init_network_weights()
        if after_init is not None:
            after_init()
        s.train()
        return s.weight_paths()

    def run(self, rd, after_init=None):
        paths = self.until_load(rd, after_init)
        self.strategy.load_best_ckpt()
        self.strategy.test()
        return paths

    def spans(self, name):
        return [e["args"] for e in self.tracer.snapshot_events()
                if e.get("ph") == "X" and e["name"] == name]


@pytest.fixture
def rounds():
    made = []

    def make(mode, **overrides):
        made.append(Rounds(mode, **overrides))
        return made[-1]
    yield make
    for r in made:
        r.close()


# -- (a) the device path gives the file's bits -------------------------------

@pytest.mark.parametrize("mode", sorted(MODES))
def test_load_best_installs_the_files_bits_from_the_device(rounds, mode):
    r = rounds(mode)
    s = r.strategy
    held = []

    def previous_state_is_released():
        # Nothing keeps the previous round's state (a tree of device
        # memory) alive past its re-initialisation.
        gc.collect()
        assert all(ref() is None for ref in held)

    for rd in range(ROUNDS):
        paths = r.run(rd, after_init=previous_state_is_released)
        assert s.best_epoch == r.best_epoch
        on_disk = ckpt_lib.load_variables(paths["best_ckpt"])
        _assert_same_bits(s.state.trainable_variables, on_disk)
        assert ckpt_lib.read_best_tag(paths["best_ckpt"]) == (
            rd, r.best_epoch)
        for leaf in jax.tree_util.tree_leaves(s.state.params):
            # The layout every later program was compiled against.
            assert leaf.sharding.is_equivalent_to(
                mesh_lib.replicated_sharding(s.mesh), leaf.ndim)
        # Installed: the second reference to the device tree is gone.
        assert s.kept_best.installed and s.kept_best.variables is None
        held.append(weakref.ref(s.state))
    loads = r.spans("ckpt/load_best")
    assert [a["source"] for a in loads] == ["device"] * ROUNDS
    assert all(a["bytes"] > 0 for a in loads)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_fit_result_carries_the_best_tree_and_its_host_copy(rounds, mode):
    r = rounds(mode)
    s = r.strategy
    r.until_load(0)
    kept = s.kept_best
    assert kept.tag == (0, r.best_epoch) and kept.belongs_to(s.state)
    assert not kept.installed
    _assert_same_bits(kept.variables, kept.host)
    final = s.state.trainable_variables
    shares_leaves = all(
        a is b for a, b in zip(jax.tree_util.tree_leaves(kept.variables),
                               jax.tree_util.tree_leaves(final)))
    # Without an improving validation epoch the best IS the final
    # state's own leaves; otherwise it is the copy taken at that epoch.
    assert shares_leaves == (mode == "no_validation")


# -- (b) the other branch of each test: the file, the same bits --------------

def _drop_tree(s):
    s.kept_best.variables = None


def _drop_record(s):
    s.kept_best = None


def _other_round(s):
    s.kept_best.tag = (s.round + 1, s.kept_best.tag[1])


def _other_epoch(s):
    s.kept_best.tag = (s.round, s.kept_best.tag[1] + 1)


def _replace_state(s):
    s.state = s.state.replace(step=s.state.step)


@pytest.mark.parametrize("mode", ["best_earlier", "no_validation"])
@pytest.mark.parametrize("spoil", [_drop_tree, _drop_record, _other_round,
                                   _other_epoch, _replace_state],
                         ids=lambda f: f.__name__.strip("_"))
def test_load_best_reads_the_file_when_the_copy_is_not_provably_its(
        rounds, mode, spoil):
    r = rounds(mode)
    s = r.strategy
    r.run(0)
    paths = r.until_load(1)
    expect = s.kept_best.host          # what the device path would install
    spoil(s)
    s.load_best_ckpt()
    assert [a["source"] for a in r.spans("ckpt/load_best")] == [
        "device", "file"]
    _assert_same_bits(s.state.trainable_variables, expect)
    _assert_same_bits(s.state.trainable_variables,
                      ckpt_lib.load_variables(paths["best_ckpt"]))
    # No host copy is claimed for a state the file made...
    assert s.kept_best is None and s.host_variables() is None
    snap = _round_snapshot(s)
    assert snap["fetched"] == ckpt_lib.tree_bytes(snap["variables"]) > 0
    _assert_same_bits(snap["variables"], expect)
    # ...and the next round takes the device path again.
    r.run(2)
    assert r.spans("ckpt/load_best")[-1]["source"] == "device"


def test_resumed_experiment_loads_from_the_file(tmp_path):
    """Experiment resume builds a skeleton state and calls
    ``load_best_ckpt``: this process made no weights, so the file."""
    data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                              image_size=8, seed=5)
    run_experiment(_cfg("rs", str(tmp_path), rounds=1), sink=NullSink(),
                   data=data, train_cfg=tiny_train_config(),
                   model=TinyClassifier(num_classes=4))
    cfg = dataclasses.replace(
        _cfg("rs", str(tmp_path), rounds=2, resume=True),
        telemetry=TelemetryConfig(enabled=True, export_trace=True,
                                  heartbeat_every_s=0.0))
    run_experiment(cfg, sink=NullSink(), data=data,
                   train_cfg=tiny_train_config(),
                   model=TinyClassifier(num_classes=4))
    with open(os.path.join(cfg.log_dir, "trace.json")) as fh:
        events = [e for e in json.load(fh)["traceEvents"]
                  if e.get("ph") == "X"]
    loads = [e["args"]["source"] for e in events
             if e["name"] == "ckpt/load_best"]
    assert loads == ["file", "device"]      # the resume's, then round 1's
    snaps = [e["args"] for e in events if e["name"] == "ckpt/round_snapshot"]
    assert [a["fetched"] > 0 for a in snaps] == [True]


# -- (c) two files, one serialisation when they hold the same bytes ----------

@pytest.mark.parametrize("mode", sorted(MODES))
def test_current_file_is_the_best_files_bytes_iff_best_is_last(rounds, mode):
    r = rounds(mode)
    s = r.strategy
    for rd in range(ROUNDS):
        paths = r.run(rd)
        best, cur = _read(paths["best_ckpt"]), _read(paths["current_ckpt"])
        same = r.best_epoch == N_EPOCH
        assert (best == cur) == same
        # Both load, whichever way they were written; the current file
        # holds the final epoch's state.
        ckpt_lib.load_variables(paths["best_ckpt"])
        cur_tree = ckpt_lib.load_variables(paths["current_ckpt"])
        if same:
            _assert_same_bits(cur_tree, s.state.trainable_variables)
        # The tag sidecar follows the weights.
        assert ckpt_lib.read_best_tag(paths["best_ckpt"]) == (
            rd, r.best_epoch)
        assert os.path.getmtime(paths["best_ckpt"] + ".tag.json") >= \
            os.path.getmtime(paths["best_ckpt"])
        assert not glob.glob(os.path.join(paths["dir"], "*.tmp"))
    shared = [a["shared"] for a in r.spans("ckpt/save_current")]
    assert shared == [r.best_epoch == N_EPOCH] * ROUNDS
    assert len(r.spans("ckpt/publish_best")) == ROUNDS


def test_periodic_saves_serialise_on_their_own(rounds):
    """At the cadence the current file is fetched and serialised as
    before; only the end-of-fit save may share, and only when the best
    is the last epoch (here it is: the accuracies rise)."""
    r = rounds("best_last")
    r.strategy.trainer.current_ckpt_every = 1
    paths = r.run(0)
    assert [a["shared"] for a in r.spans("ckpt/save_current")] == [
        False] * N_EPOCH + [True]
    # Every epoch improved and was published at the cadence; the end of
    # the fit had nothing newer to publish and shares the last one's.
    assert len(r.spans("ckpt/publish_best")) == N_EPOCH
    assert _read(paths["best_ckpt"]) == _read(paths["current_ckpt"])


def test_serialize_and_write_bytes_make_save_variables(tmp_path):
    tree = {"params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
            "batch_stats": {}}
    a, b = str(tmp_path / "a.msgpack"), str(tmp_path / "b.msgpack")
    ckpt_lib.save_variables(a, jax.device_put(tree))
    ckpt_lib.write_bytes(b, ckpt_lib.serialize(tree))
    assert _read(a) == _read(b)
    ckpt_lib.publish_best_bytes(b, ckpt_lib.serialize(tree), round_idx=2,
                                epoch=5)
    assert ckpt_lib.read_best_tag(b) == (2, 5)
    _assert_same_bits(ckpt_lib.load_variables(b), tree)
    assert sorted(os.listdir(tmp_path)) == [
        "a.msgpack", "b.msgpack", "b.msgpack.tag.json"]


# -- (d) the ladder's snapshot shares the host copy --------------------------

@pytest.mark.parametrize("mode", sorted(MODES))
def test_round_snapshot_shares_the_host_copy_and_restores_from_it(
        rounds, mode):
    r = rounds(mode)
    s = r.strategy
    r.run(0)
    paths = r.run(1)
    snap = _round_snapshot(s)
    assert snap["fetched"] == 0
    assert snap["variables"] is s.kept_best.host is s.host_variables()
    _assert_same_bits(snap["variables"], s.state.trainable_variables)
    before = [x.copy() for x in _leaves(snap["variables"])]
    pre_pool = s.pool.to_arrays()
    # A failed attempt of round 2: its fit donates the state it was
    # given and re-binds the strategy's state, then the round fails.
    r.until_load(2)
    _restore_round_snapshot(s, snap, 2)
    for leaf in jax.tree_util.tree_leaves(s.state.trainable_variables):
        assert not leaf.is_deleted()
    _assert_same_bits(s.state.trainable_variables, before)
    _assert_same_bits(s.state.trainable_variables,
                      ckpt_lib.load_variables(paths["best_ckpt"]))
    for k, v in pre_pool.items():
        assert np.array_equal(v, s.pool.to_arrays()[k])
    # The restored state is a new one: its copy is fetched again.
    assert s.host_variables() is None
    # The retried round runs to its end on the restored state, and the
    # rollback point it was restored from is still what it was.
    r.run(2)
    _assert_same_bits(snap["variables"], before)
    assert _round_snapshot(s)["fetched"] == 0
    assert r.spans("ckpt/load_best")[-1]["source"] == "device"


def test_snapshot_before_any_model_copies_nothing():
    s = make_strategy("MarginSampler", init_weights=False)
    snap = _round_snapshot(s)
    assert snap["variables"] is None and snap["fetched"] == 0


# -- (e) a resumed run ends where the uninterrupted one does -----------------

def _cfg(tag, root, *, rounds=ROUNDS, resume=False, patience=N_EPOCH):
    return ExperimentConfig(
        dataset="synthetic", arg_pool="synthetic", strategy="MarginSampler",
        rounds=rounds, round_budget=8, n_epoch=N_EPOCH,
        early_stop_patience=patience, run_seed=7, exp_hash=tag,
        exp_name="handoff", ckpt_path=os.path.join(root, "ckpt"),
        log_dir=os.path.join(root, "logs"), round_pipeline="off",
        resume_training=resume,
        telemetry=TelemetryConfig(enabled=True, heartbeat_every_s=0.0))


def _files(cfg):
    d = glob.glob(os.path.join(cfg.ckpt_path, "*"))[0]
    out = {name: _read(os.path.join(d, name)) for name in os.listdir(d)
           if name.endswith((".msgpack", ".tag.json"))}
    out["experiment_state"] = {
        k: v for k, v in np.load(
            os.path.join(d, "experiment_state.npz")).items()}
    return out


class _PreemptAt(NullSink):
    """A recorded SIGTERM when round ``rd``'s fit reaches ``epoch``."""

    def __init__(self, rd, epoch, patience):
        self.name = (f"rd_{rd}_validation_accuracy" if patience
                     else "cumulative_budget")
        self.rd, self.epoch, self.patience = rd, epoch, patience
        self.fired = False

    def log_metric(self, name, value, step=None):
        at = self.epoch if self.patience else self.rd
        if not self.fired and name == self.name and step == at:
            self.fired = True
            preempt_lib._handler(signal.SIGTERM, None)


@pytest.fixture(scope="module")
def handoff_data():
    return get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                              image_size=8, seed=5)


@pytest.fixture(scope="module")
def uninterrupted(handoff_data, tmp_path_factory):
    out = {}
    for patience in (N_EPOCH, 0):
        cfg = _cfg(f"base{patience}",
                   str(tmp_path_factory.mktemp(f"handoff_base{patience}")),
                   patience=patience)
        run_experiment(cfg, sink=NullSink(), data=handoff_data,
                       train_cfg=tiny_train_config(),
                       model=TinyClassifier(num_classes=4))
        out[patience] = _files(cfg)
    return out


@pytest.mark.parametrize("patience", [N_EPOCH, 0],
                         ids=["validation", "no_validation"])
@pytest.mark.parametrize("rd", [1, 2])
def test_resumed_run_is_bit_identical_to_the_uninterrupted_one(
        handoff_data, uninterrupted, tmp_path, rd, patience):
    """Preempted in round ``rd`` (with validation: inside its fit, so
    the resumed fit's best may be the FILE's; without: at the boundary
    after the query), resumed with ``--resume_training``: every
    checkpoint file and the experiment state end as the uninterrupted
    run's, byte for byte."""
    cfg = _cfg(f"p{rd}", str(tmp_path), patience=patience)
    sink = _PreemptAt(rd, 1, patience)
    with pytest.raises(preempt_lib.PreemptionRequested):
        run_experiment(cfg, sink=sink, data=handoff_data,
                       train_cfg=tiny_train_config(),
                       model=TinyClassifier(num_classes=4))
    assert sink.fired
    preempt_lib.reset()
    run_experiment(_cfg(f"p{rd}", str(tmp_path), resume=True,
                        patience=patience),
                   sink=NullSink(), data=handoff_data,
                   train_cfg=tiny_train_config(),
                   model=TinyClassifier(num_classes=4))
    got, want = _files(cfg), uninterrupted[patience]
    assert set(got) == set(want)
    for name in want:
        if name == "experiment_state":
            assert set(got[name]) == set(want[name])
            for k in want[name]:
                assert np.array_equal(got[name][k], want[name][k]), k
        else:
            assert got[name] == want[name], name


# -- (f) a failed write is retried per file ----------------------------------

@pytest.mark.parametrize("spec,file", [
    ("ckpt_write:raise@1", "best_ckpt"),
    ("ckpt_write:raise@2", "current_ckpt"),
    ("ckpt_write:torn@1", "best_ckpt"),
])
def test_each_file_passes_its_own_fault_site_and_is_retried(
        rounds, spec, file):
    r = rounds("no_validation")
    s = r.strategy
    retries = faults.retry_counters()["total"]
    faults.configure(spec, seed=1)
    try:
        paths = r.run(0)
        fired = faults.fault_counters()["ckpt_write"]["fires"]
    finally:
        faults.configure(None)
    assert fired == 1
    assert faults.retry_counters()["total"] == retries + 1
    # The retried file and its neighbour are both whole, the tag follows
    # the weights, nothing is left half-written.
    assert _read(paths["best_ckpt"]) == _read(paths["current_ckpt"])
    _assert_same_bits(ckpt_lib.load_variables(paths[file]),
                      s.state.trainable_variables)
    assert ckpt_lib.read_best_tag(paths["best_ckpt"]) == (0, N_EPOCH)
    assert not glob.glob(os.path.join(paths["dir"], "*.tmp"))
    assert r.spans("ckpt/load_best")[-1]["source"] == "device"
    assert r.spans("ckpt/save_current")[-1]["shared"] is True
