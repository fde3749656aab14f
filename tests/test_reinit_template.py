"""Re-initialisation from a device-resident template (ISSUE 32): the
variables a round starts from come out of ONE compiled program whose
inputs are the round's key and the pretrained checkpoint's leaves, read
and overlaid once.  Held here, at a toy size on the CPU mesh: covered
leaves byte-equal to ``apply_pretrained``'s in every round (the one after
a fit that donated its state included), drawn leaves on the ``_init_key``
stream and equal to the eager ``model.init`` to the last place, one file
read, no eager ``model.init``, one compile, replicated outputs — in the
three shapes of use: everything covered, the head drawn (``skip_key``),
no checkpoint."""

import dataclasses
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from flax.traverse_util import flatten_dict

from active_learning_tpu.config import PretrainedConfig
from active_learning_tpu.models.resnet import BasicBlock, SSLClassifier
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.telemetry import spans as spans_lib
from active_learning_tpu.utils import pretrained as pretrained_lib
from helpers import make_strategy, tiny_train_config

SHAPES = ("covered", "head_drawn", "scratch")
N_LEAVES = 17            # 11 parameters, 6 stored statistics
COPIED = {"covered": 17, "head_drawn": 15, "scratch": 0}
ROUNDS = (0, 1, 2, 3)    # a fit runs before round 2; round 3 reads file B
FILE_OF_ROUND = ("a", "a", "a", "b")


def _torch_state(seed):
    """Every tensor of the toy model under its torchvision name."""
    g = torch.Generator().manual_seed(seed)

    def bn(name):
        return {f"{name}.weight": torch.rand(64, generator=g) + 0.5,
                f"{name}.bias": torch.randn(64, generator=g),
                f"{name}.running_mean": torch.randn(64, generator=g),
                f"{name}.running_var": torch.rand(64, generator=g) + 0.5,
                f"{name}.num_batches_tracked": torch.tensor(3)}

    state = {"encoder.conv1.weight": torch.randn(64, 3, 3, 3, generator=g),
             **bn("encoder.bn1")}
    for i in (1, 2):
        state[f"encoder.layer1.0.conv{i}.weight"] = torch.randn(
            64, 64, 3, 3, generator=g)
        state.update(bn(f"encoder.layer1.0.bn{i}"))
    state["linear.weight"] = torch.randn(4, 64, generator=g)
    state["linear.bias"] = torch.randn(4, generator=g)
    return state


def _host(tree):
    """Fetched through a device copy: on the CPU ``np.asarray`` of a leaf
    is a view that pins its buffer, and a pinned buffer is not donated."""
    return {k: np.asarray(jnp.copy(v)) for k, v in flatten_dict(tree).items()}


class _Counts:
    """Calls of ``nn.Module.init`` (eager and traced apart) and of
    ``load_torch_state_dict`` while the patch is in."""

    def __init__(self, mp):
        self.eager = self.traced = self.reads = 0
        real_init = nn.Module.init
        real_load = pretrained_lib.load_torch_state_dict

        def init(module, rngs, *args, **kwargs):
            if isinstance(rngs, jax.core.Tracer):
                self.traced += 1
            else:
                self.eager += 1
            return real_init(module, rngs, *args, **kwargs)

        def load(path):
            self.reads += 1
            return real_load(path)

        mp.setattr(nn.Module, "init", init)
        mp.setattr(pretrained_lib, "load_torch_state_dict", load)


def _strategy(shape, path, with_state=False):
    pre = {"covered": PretrainedConfig(path=path),
           "head_drawn": PretrainedConfig(path=path, skip_key=("linear",)),
           "scratch": PretrainedConfig()}[shape]
    strategy = make_strategy(
        "MarginSampler", n_epoch=1, init_weights=False,
        model=SSLClassifier(stage_sizes=(1,), block_cls=BasicBlock,
                            num_classes=4, cifar_stem=True),
        train_cfg=dataclasses.replace(tiny_train_config(), pretrained=pre))
    if with_state:
        # As experiment/resume.py leaves it: a state from a throwaway key.
        strategy.state = strategy.trainer.init_state(
            jax.random.PRNGKey(0),
            strategy.train_set.gather(np.zeros(1, dtype=np.int64)))
    return strategy


def _save(state, path, later_than=None):
    torch.save({"state_dict": state}, path)
    if later_than is not None:
        # A replaced file shows in (mtime, size) whatever the clock's grain.
        os.utime(path, ns=(later_than.st_atime_ns,
                           later_than.st_mtime_ns + 10 ** 9))


@pytest.fixture(scope="module", params=SHAPES)
def walk(request, tmp_path_factory):
    """One strategy through rounds 0, 1, a fit, 2, then the file replaced
    and round 3 — and what the parent's code gives for the same keys:
    the eager ``model.init``, then ``apply_pretrained`` on the host."""
    shape = request.param
    tmp = tmp_path_factory.mktemp(f"reinit_{shape}")
    path = str(tmp / "ssl.pth")
    files = {"a": str(tmp / "a.pth"), "b": str(tmp / "b.pth")}
    _save(_torch_state(7), files["a"])
    _save(_torch_state(8), files["b"])
    _save(_torch_state(7), path)
    tracer = spans_lib.SpanTracer(enabled=True)
    rec = {"shape": shape, "got": [], "cache": [], "replicated": [],
           "counts": []}
    with pytest.MonkeyPatch.context() as mp:
        counts = _Counts(mp)
        spans_lib.set_tracer(tracer)
        try:
            strategy = _strategy(shape, path)
            key = np.asarray(strategy._init_key)
            want = mesh_lib.replicated_sharding(strategy.mesh)
            for rd in ROUNDS:
                if rd == 2:
                    before = jax.tree.leaves(strategy.state.params)
                    strategy.train()
                    rec["fit_donated"] = all(leaf.is_deleted()
                                             for leaf in before)
                if rd == 3 and shape != "scratch":
                    _save(_torch_state(8), path, later_than=os.stat(path))
                strategy.init_network_weights()
                leaves = jax.tree.leaves(strategy.state.variables)
                rec["got"].append(_host(strategy.state.variables))
                rec["cache"].append(strategy._reinit._cache_size())
                rec["replicated"].append(all(
                    leaf.sharding.is_equivalent_to(want, leaf.ndim)
                    for leaf in leaves))
                rec["counts"].append((counts.eager, counts.traced,
                                      counts.reads))
            rec["last_key"] = np.asarray(strategy._init_key)
        finally:
            spans_lib.set_tracer(None)
    rec["spans"] = [e for e in tracer.events if e.get("ph") == "X"]
    # The parent's result, round by round, from the same key stream.
    sample = strategy.train_set.gather(np.zeros(1, dtype=np.int64))
    rec["want"], rec["covered"] = [], []
    for rd in ROUNDS:
        key, sub = jax.random.split(key)
        eager = jax.tree.map(np.asarray, dict(strategy.model.init(
            sub, sample.astype(np.float32), train=False)))
        covered = {}
        if shape != "scratch":
            cfg = dataclasses.replace(strategy.train_cfg.pretrained,
                                      path=files[FILE_OF_ROUND[rd]])
            covered = pretrained_lib.pretrained_leaves(
                flatten_dict(eager), cfg,
                pretrained_lib.load_torch_state_dict(cfg.path))
            eager = pretrained_lib.apply_pretrained(eager, cfg)
        rec["want"].append(_host(eager))
        rec["covered"].append(set(covered))
    rec["key_after"] = np.asarray(key)
    return rec


@pytest.mark.parametrize("rd", ROUNDS)
def test_covered_leaves_are_the_overlays_bytes(walk, rd):
    """Rounds 0-2 (round 2 follows a fit that donated the state the
    template was copied into) and the round after the file is replaced."""
    got, want, covered = walk["got"][rd], walk["want"][rd], walk["covered"][rd]
    assert len(covered) == COPIED[walk["shape"]] and len(got) == N_LEAVES
    for path in covered:
        assert got[path].dtype == want[path].dtype
        assert got[path].tobytes() == want[path].tobytes(), path
    if rd == 2:
        assert walk["fit_donated"]


@pytest.mark.parametrize("rd", ROUNDS)
def test_drawn_leaves_follow_the_key_stream(walk, rd):
    """Same initializer, key, shape and dtype as the eager ``model.init``
    of the round's split; equal to one unit in the last place (under
    ``jit`` XLA folds the initializer's scale into the normal's)."""
    got, want = walk["got"][rd], walk["want"][rd]
    drawn = set(got) - walk["covered"][rd]
    assert len(drawn) == N_LEAVES - COPIED[walk["shape"]]
    for path in drawn:
        assert got[path].dtype == want[path].dtype
        assert got[path].shape == want[path].shape
        np.testing.assert_allclose(got[path], want[path], rtol=1e-6, atol=0)
    # Exactly one split a call: the resume contract.
    np.testing.assert_array_equal(walk["last_key"], walk["key_after"])


def test_drawn_leaves_differ_from_round_to_round(walk):
    if walk["shape"] == "covered":
        assert all(not (set(g) - c)
                   for g, c in zip(walk["got"], walk["covered"]))
        return
    kernel = ("params", "linear", "kernel")
    draws = [g[kernel].tobytes() for g in walk["got"]]
    assert len(set(draws)) == len(ROUNDS)


def test_one_read_no_eager_init_one_compile(walk):
    """Over three rounds the file is read once, and ``model.init`` runs
    only under a trace (the abstract tree, then the program's one compile
    with the template's leaves: both in round 0), never eagerly.  The
    replaced file is read once more and, covering the same leaves, traces
    and compiles nothing."""
    reads, traces = (0, 1) if walk["shape"] == "scratch" else (1, 2)
    assert walk["counts"][:3] == [(0, traces, reads)] * 3
    assert walk["counts"][3] == (0, traces, 2 * reads)
    assert walk["cache"] == [1, 1, 1, 1]
    assert all(walk["replicated"])


def test_counters_say_what_engaged(walk):
    apply = [e for e in walk["spans"] if e["name"] == "reinit/apply"]
    assert len(apply) == len(ROUNDS)
    rebuilt = "hit" if walk["shape"] == "scratch" else "built"
    assert [e["args"]["template"] for e in apply] == [
        "built", "hit", "hit", rebuilt]
    nbytes = sum(v.nbytes for v in walk["got"][0].values())
    for e in apply:
        assert e["args"]["leaves_copied"] == COPIED[walk["shape"]]
        assert e["args"]["leaves_drawn"] == N_LEAVES - COPIED[walk["shape"]]
        assert e["args"]["bytes"] == nbytes
    builds = 0 if walk["shape"] == "scratch" else 2
    for name in ("reinit/pretrained_read", "reinit/overlay"):
        assert sum(e["name"] == name for e in walk["spans"]) == builds
    assert not any(e["name"] == "reinit/model_init" for e in walk["spans"])


@pytest.mark.parametrize("with_state", [False, True],
                         ids=["state_is_none", "state_exists"])
def test_a_second_run_of_the_seed_draws_the_same_bits(walk, with_state,
                                                      tmp_path):
    """Two runs of one seed, and a run whose state already exists when
    the first round re-initialises (a resumed run), start every round
    from the same bits: every leaf comes from the one program."""
    path = str(tmp_path / "ssl.pth")
    _save(_torch_state(7), path)
    strategy = _strategy(walk["shape"], path, with_state=with_state)
    for rd in (0, 1):
        strategy.init_network_weights()
        got = _host(strategy.state.variables)
        assert set(got) == set(walk["got"][rd])
        for leaf_path, value in got.items():
            assert value.tobytes() == walk["got"][rd][leaf_path].tobytes()
