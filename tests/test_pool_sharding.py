"""The row-sharded resident pool (ISSUE 6, DESIGN.md §2b), pinned.

Three claims make the sharded pool safe to default on:

  1. PICK IDENTITY — row-sharded k-center selection (collective backend,
     strategies/kcenter._build_sharded_fns) produces the IDENTICAL pick
     sequence to the replicated scans at the same seeds, for the
     deterministic (batched and q=1), randomized (D^2), and
     empty-labeled (minimax seed) modes, single- and two-factor.
  2. BIT IDENTITY — sharded collect_pool scores and resident-gather
     train batches are bit-for-bit the replicated (and host) results:
     the layout is a throughput/HBM choice, never a numerics one.
  3. THE HBM MATH — per-device resident bytes for a row-sharded pool
     are <= replicated bytes / num_devices + one row of pad slack, and
     the shared budget accounting (eligible's shard_ways) admits pools
     ~ndev x larger.

Everything runs on the conftest 8-device CPU mesh — the same virtual
mesh the sharding/collective code paths compile for on real chips.
"""

import dataclasses

import jax
import numpy as np
import pytest

from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.parallel import resident as resident_lib
from active_learning_tpu.strategies import kcenter as kc
from active_learning_tpu.strategies import scoring
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.train.trainer import Trainer

from helpers import TinyClassifier, tiny_train_config


def oracle_kcenter(emb, labeled_mask, budget):
    """The reference greedy loop (also in test_kcenter.py)."""
    d = ((emb[:, None, :] - emb[None, :, :]) ** 2).sum(-1)
    lab = labeled_mask.copy()
    picks = []
    for _ in range(budget):
        if lab.sum() > 0:
            q = int(d[:, lab].min(axis=1).argmax())
        else:
            q = int(d.max(axis=1).argmin())
        picks.append(q)
        lab[q] = True
    return np.asarray(picks)


class TestPickIdentity:
    """Acceptance: on a multi-device CPU mesh, row-sharded k-center
    produces the identical pick sequence to the replicated backend."""

    def _both(self, emb, labeled, budget, q, randomize=False, seed=1):
        factors = emb if isinstance(emb, tuple) else (emb,)
        rep = kc.kcenter_greedy(factors, labeled, budget,
                                randomize=randomize,
                                rng=np.random.default_rng(seed),
                                batch_q=q, pool_sharding="replicated")
        assert kc.LAST_SHARDING == "replicated"
        row = kc.kcenter_greedy(factors, labeled, budget,
                                randomize=randomize,
                                rng=np.random.default_rng(seed),
                                batch_q=q, mesh=mesh_lib.make_mesh(),
                                pool_sharding="row")
        assert kc.LAST_SHARDING == "row"
        return rep, row

    @pytest.mark.parametrize("q", [1, 3, 8])
    def test_deterministic_matches_replicated_and_oracle(self, q):
        rng = np.random.default_rng(11)
        emb = rng.normal(size=(70, 6)).astype(np.float32)
        labeled = np.zeros(70, dtype=bool)
        labeled[rng.choice(70, 9, replace=False)] = True
        rep, row = self._both(emb, labeled, 13, q)
        np.testing.assert_array_equal(row, rep)
        np.testing.assert_array_equal(row, oracle_kcenter(emb, labeled, 13))

    def test_empty_labeled_minimax_seed(self):
        """Nothing labeled: the sharded minimax seed (host column blocks
        folded into a sharded row-max, pad rows masked from the argmin)
        replays the replicated seed and the oracle."""
        rng = np.random.default_rng(12)
        emb = rng.normal(size=(40, 4)).astype(np.float32)
        labeled = np.zeros(40, dtype=bool)
        rep, row = self._both(emb, labeled, 9, 4)
        np.testing.assert_array_equal(row, rep)
        np.testing.assert_array_equal(row, oracle_kcenter(emb, labeled, 9))

    def test_randomized_d2_identical_draws(self):
        """BADGE mode: the sharded D^2 draw all_gathers the O(N) weight
        vector and consumes the SAME key chain — identical picks, not
        merely identically-distributed ones."""
        rng = np.random.default_rng(13)
        emb = rng.normal(size=(60, 6)).astype(np.float32)
        labeled = np.zeros(60, dtype=bool)
        labeled[:10] = True
        rep, row = self._both(emb, labeled, 15, 1, randomize=True, seed=5)
        np.testing.assert_array_equal(row, rep)

    def test_two_factor_badge_layout(self):
        rng = np.random.default_rng(14)
        a = rng.normal(size=(30, 5)).astype(np.float32)
        e = rng.normal(size=(30, 7)).astype(np.float32)
        g = np.einsum("nc,nd->ncd", a, e).reshape(30, -1)
        labeled = np.zeros(30, dtype=bool)
        labeled[[2, 17]] = True
        rep, row = self._both((a, e), labeled, 7, 4)
        np.testing.assert_array_equal(row, rep)
        np.testing.assert_array_equal(row, oracle_kcenter(g, labeled, 7))

    def test_single_device_mesh_falls_back_to_replicated(self):
        rng = np.random.default_rng(15)
        emb = rng.normal(size=(32, 4)).astype(np.float32)
        labeled = np.zeros(32, dtype=bool)
        labeled[:4] = True
        kc.kcenter_greedy((emb,), labeled, 5,
                          rng=np.random.default_rng(1),
                          mesh=mesh_lib.make_mesh(1), pool_sharding="row")
        assert kc.LAST_SHARDING == "replicated"


class TestShardedScoring:
    """collect_pool over a row-sharded resident pool returns bit-for-bit
    the replicated-resident and host-streamed scores."""

    def _setup(self):
        _, _, al_set = get_data_synthetic(n_train=96, n_test=16,
                                          num_classes=4, image_size=8,
                                          seed=3)
        mesh = mesh_lib.make_mesh()
        model = TinyClassifier(num_classes=4)
        variables = model.init(jax.random.PRNGKey(0),
                               al_set.gather(np.zeros(1, np.int64)),
                               train=False)
        variables = mesh_lib.replicate(variables, mesh)
        step = scoring.make_prob_stats_step(model, al_set.view)
        return al_set, mesh, variables, step

    def test_scores_bit_identical_across_layouts(self):
        al_set, mesh, variables, step = self._setup()
        idxs = np.arange(len(al_set))
        kwargs = dict(batch_size=16, step_fn=step, variables=variables,
                      mesh=mesh)
        host = scoring.collect_pool(al_set, idxs, **kwargs)
        rep_cache, row_cache = {}, {}
        rep = scoring.collect_pool(al_set, idxs, resident_cache=rep_cache,
                                   resident_max_bytes=2 ** 31,
                                   pool_sharding="replicated", **kwargs)
        row = scoring.collect_pool(al_set, idxs, resident_cache=row_cache,
                                   resident_max_bytes=2 ** 31,
                                   pool_sharding="row", **kwargs)
        images_dev = row_cache["images"][next(
            iter(row_cache["images"]))][1]
        assert mesh_lib.is_row_sharded(images_dev)
        assert not mesh_lib.is_row_sharded(
            rep_cache["images"][next(iter(rep_cache["images"]))][1])
        for k in ("confidence", "margin", "entropy", "pred"):
            np.testing.assert_array_equal(row[k], rep[k])
            np.testing.assert_array_equal(row[k], host[k])

    def test_row_entry_reused_zero_new_compiles_on_second_pass(self):
        """Warm-round regression for sharded scoring: a second pass over
        the same row-sharded pool reuses the entry AND the runner
        executable — zero new compiles."""
        al_set, mesh, variables, step = self._setup()
        idxs = np.arange(len(al_set))
        cache = {}
        kwargs = dict(batch_size=16, step_fn=step, variables=variables,
                      mesh=mesh, resident_cache=cache,
                      resident_max_bytes=2 ** 31, pool_sharding="row")
        first = scoring.collect_pool(al_set, idxs, **kwargs)
        assert len(cache["images"]) == 1 and len(cache["steps"]) == 1
        runner = next(iter(cache["steps"].values()))
        compiles = runner._cache_size()
        second = scoring.collect_pool(al_set, idxs, **kwargs)
        assert len(cache["images"]) == 1 and len(cache["steps"]) == 1
        assert runner._cache_size() == compiles
        for k in first:
            np.testing.assert_array_equal(first[k], second[k])


class TestShardedTrainFeed:
    """The resident-gather train feed over a row-sharded pool trains to
    BITWISE-identical parameters vs the replicated layout (same seeds,
    same batch stream, same sharded step program)."""

    def _fit(self, pool_sharding):
        train_set, _, al_set = get_data_synthetic(
            n_train=90, n_test=16, num_classes=4, image_size=8, seed=6)
        cfg = dataclasses.replace(tiny_train_config(),
                                  train_feed="resident",
                                  pool_sharding=pool_sharding)
        mesh = mesh_lib.make_mesh(8)
        trainer = Trainer(TinyClassifier(), cfg, mesh, 4)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.zeros(1, np.int64)))
        # 83 labeled with batch 16: a PADDED last batch — padding
        # isolation must survive the sharded gather too.
        result = trainer.fit(state, train_set, np.arange(83), al_set,
                             np.arange(83, 90), n_epoch=3,
                             es_patience=0, rng=np.random.default_rng(42))
        return trainer, result

    @staticmethod
    def _leaves(result):
        return jax.tree_util.tree_leaves(
            jax.tree.map(np.asarray, result.state.variables))

    def test_row_fit_bitwise_identical_to_replicated(self):
        t_row, row = self._fit("row")
        assert t_row.last_feed["source"] == "resident"
        assert t_row.pool_sharding == "row"
        images_dev = t_row.resident_pool["images"][next(
            iter(t_row.resident_pool["images"]))][1]
        assert mesh_lib.is_row_sharded(images_dev)
        t_rep, rep = self._fit("replicated")
        assert t_rep.last_feed["source"] == "resident"
        assert t_rep.pool_sharding == "replicated"
        for a, b in zip(self._leaves(row), self._leaves(rep)):
            np.testing.assert_array_equal(a, b)

    def test_auto_resolves_row_on_multi_device_mesh(self):
        t, _ = self._fit("auto")
        assert t.pool_sharding == "row"
        assert t._shard_ways == 8

    def test_sharded_eval_counts_match_replicated(self):
        t_row, row = self._fit("row")
        t_rep, rep = self._fit("replicated")
        _, _, al_set = get_data_synthetic(
            n_train=90, n_test=16, num_classes=4, image_size=8, seed=6)
        # Evaluate over each trainer's own cached dataset object so the
        # resident entries (one row-sharded, one replicated) are reused.
        def ev(trainer, result):
            ds = trainer.resident_pool["images"][next(
                iter(trainer.resident_pool["images"]))][0]
            return trainer.evaluate(result.state, ds, np.arange(24))
        pr, pp = ev(t_row, row), ev(t_rep, rep)
        assert float(pr["accuracy"]) == float(pp["accuracy"])
        np.testing.assert_array_equal(np.asarray(pr["accuracy_byclass"]),
                                      np.asarray(pp["accuracy_byclass"]))


class TestResidentBytesAndBudget:
    """The HBM math: per-device bytes, eligibility scaling, and the
    resolve_sharding gates."""

    def test_per_device_bytes_scale_with_devices(self):
        """Acceptance: per-device resident bytes for the same pool are
        <= replicated bytes / num_devices + one row of pad slack."""
        _, _, al_set = get_data_synthetic(n_train=96, n_test=16,
                                          num_classes=4, image_size=8)
        mesh = mesh_lib.make_mesh()
        ndev = mesh.devices.size
        rep_cache, row_cache = {}, {}
        resident_lib.pool_arrays(rep_cache, al_set, mesh,
                                 sharding="replicated")
        resident_lib.pool_arrays(row_cache, al_set, mesh, sharding="row")
        rep_bytes = resident_lib.pinned_bytes(rep_cache)
        row_bytes = resident_lib.pinned_bytes(row_cache)
        assert rep_bytes == al_set.images[:96].nbytes
        per_row = int(np.prod(al_set.images.shape[1:])) \
            * al_set.images.itemsize
        assert row_bytes <= rep_bytes / ndev + per_row
        assert row_bytes == -(-96 // ndev) * per_row

    def test_sharded_gather_returns_exact_rows(self):
        _, _, al_set = get_data_synthetic(n_train=96, n_test=16,
                                          num_classes=4, image_size=8)
        mesh = mesh_lib.make_mesh()
        cache = {}
        images_dev, labels_dev = resident_lib.pool_arrays(
            cache, al_set, mesh, sharding="row")
        ids = np.asarray([3, 50, 95, 0, 17, 88, 41, 2], np.int32)
        img, lab = jax.jit(
            lambda im, lb, i: resident_lib.sharded_pool_gather(
                im, i, mesh, labels=lb))(images_dev, labels_dev,
                                         jax.numpy.asarray(ids))
        np.testing.assert_array_equal(
            resident_lib.from_pinned(np.asarray(img), al_set.image_shape),
            al_set.images[ids])
        np.testing.assert_array_equal(
            np.asarray(lab), al_set.targets[ids].astype(np.int32))

    def test_eligible_shard_ways_scales_the_budget(self):
        _, _, al_set = get_data_synthetic(n_train=96, n_test=16,
                                          num_classes=4, image_size=8)
        full = al_set.images[:96].nbytes
        # Replicated: the pool must fit whole.
        assert resident_lib.eligible(al_set, full, cache={})
        assert not resident_lib.eligible(al_set, full - 1, cache={})
        # Row-sharded over 8: an eighth (rounded up to whole rows) fits.
        per_row = int(np.prod(al_set.images.shape[1:])) \
            * al_set.images.itemsize
        need = -(-96 // 8) * per_row
        assert resident_lib.eligible(al_set, need, cache={},
                                     shard_ways=8)
        assert not resident_lib.eligible(al_set, need - 1, cache={},
                                         shard_ways=8)

    def test_resolve_sharding_rules(self):
        mesh8 = mesh_lib.make_mesh()
        mesh1 = mesh_lib.make_mesh(1)
        assert resident_lib.resolve_sharding("auto", mesh8) == "row"
        assert resident_lib.resolve_sharding(None, mesh8) == "row"
        assert resident_lib.resolve_sharding("replicated", mesh8) \
            == "replicated"
        assert resident_lib.resolve_sharding("auto", mesh1) == "replicated"
        assert resident_lib.resolve_sharding("row", mesh1) == "replicated"
        with pytest.raises(ValueError):
            resident_lib.resolve_sharding("diagonal", mesh8)


class TestRowCapableGate:
    """kcenter.row_capable IS kcenter_greedy's layout gate, exported so
    callers that must know the layout BEFORE paying for a selection (the
    kcenter_select_maxn bench climbs ndev-times-larger pools on the row
    rungs) can refuse an attempt instead of discovering a silent
    replicated fallback — at ndev times the per-chip bytes — after the
    run."""

    def test_capable_on_the_divisible_mesh(self):
        assert kc.row_capable(4096, 64, mesh_lib.make_mesh())

    def test_not_capable_when_bucket_does_not_split(self):
        # 3 of the 8 CPU devices: bucket_size(4096) = 4096 rows never
        # split 3 ways...
        mesh3 = mesh_lib.make_mesh(3)
        assert not kc.row_capable(4096, 64, mesh3)
        # ...but a bucket that happens to (3072 = 6 * 512) does.
        assert kc.row_capable(3072, 64, mesh3)

    def test_not_capable_when_shards_smaller_than_q(self):
        # bucket_size(64) = 256 rows over 8 devices = 32 per shard,
        # fewer than a q=512 candidate batch.
        mesh = mesh_lib.make_mesh()
        assert not kc.row_capable(64, 512, mesh, batch_q=512)
        assert kc.row_capable(64, 512, mesh, batch_q=8)

    def test_never_capable_without_a_mesh_or_alone(self):
        assert not kc.row_capable(4096, 64, None)
        assert not kc.row_capable(4096, 64, mesh_lib.make_mesh(1))

    def test_greedy_fallback_agrees_with_the_gate(self):
        """Row requested on a mesh the gate rejects: the greedy runs
        replicated (LAST_SHARDING tells the truth) and still returns
        the replicated picks — the gate predicted the fallback."""
        mesh3 = mesh_lib.make_mesh(3)
        rng = np.random.default_rng(21)
        emb = rng.normal(size=(40, 4)).astype(np.float32)
        labeled = np.zeros(40, dtype=bool)
        labeled[:5] = True
        assert not kc.row_capable(40, 7, mesh3)
        row = kc.kcenter_greedy((emb,), labeled, 7,
                                rng=np.random.default_rng(1),
                                mesh=mesh3, pool_sharding="row")
        assert kc.LAST_SHARDING == "replicated"
        rep = kc.kcenter_greedy((emb,), labeled, 7,
                                rng=np.random.default_rng(1),
                                pool_sharding="replicated")
        np.testing.assert_array_equal(row, rep)


class TestShardRowsUpload:
    """shard_rows builds the device array PER SHARD — the pad (and the
    contiguous copy) materialize one shard at a time, never as a second
    full-size host array."""

    def test_rows_param_pads_to_target_bucket(self):
        mesh = mesh_lib.make_mesh()
        rng = np.random.default_rng(31)
        a = rng.integers(0, 255, size=(70, 3), dtype=np.uint8)
        out = mesh_lib.shard_rows(a, mesh, rows=96)
        assert out.shape == (96, 3)
        assert mesh_lib.is_row_sharded(out)
        host = np.asarray(out)
        np.testing.assert_array_equal(host[:70], a)
        assert not host[70:].any()
        assert max(s.data.shape[0]
                   for s in out.addressable_shards) == 96 // 8

    def test_default_rows_pads_to_divide_evenly(self):
        mesh = mesh_lib.make_mesh()
        a = np.arange(70 * 2, dtype=np.float32).reshape(70, 2)
        out = mesh_lib.shard_rows(a, mesh)
        assert out.shape[0] == 72  # 70 + pad to /8
        np.testing.assert_array_equal(np.asarray(out)[:70], a)

    def test_rows_below_array_length_rejected(self):
        mesh = mesh_lib.make_mesh()
        with pytest.raises(ValueError):
            mesh_lib.shard_rows(np.zeros((16, 2), np.float32), mesh,
                                rows=8)
