"""Multi-device trainer/eval correctness on the virtual 8-device CPU mesh.

These are the distributed-semantics tests the reference cannot have (it
needs a real multi-GPU node): the 8-way sharded train step must produce the
SAME parameters as a 1-device run of the identical global batch (gradient
psum == DDP allreduce, strategy.py:336), global-batch BN statistics must
match (SyncBatchNorm, strategy.py:292), padding rows must not leak into
gradients, and sharded eval counts must match a NumPy oracle
(gather_parallel_eval, evaluation.py:69-98).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from active_learning_tpu.config import (LoaderConfig, OptimizerConfig,
                                        SchedulerConfig, TrainConfig)
from active_learning_tpu.data.core import Normalization, ViewSpec
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.train.trainer import Trainer

from helpers import TinyClassifier, tiny_train_config

VIEW = ViewSpec(Normalization((0.5,) * 3, (0.25,) * 3), augment=False)


class BNClassifier(nn.Module):
    """Conv + BatchNorm + head: exercises the global-batch BN path."""

    num_classes: int = 4

    @nn.compact
    def __call__(self, x, train: bool = True, return_features: bool = False):
        x = x.astype(jnp.float32)
        x = nn.Conv(8, (3, 3), name="conv")(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         name="bn")(x)
        x = nn.relu(x)
        emb = x.mean(axis=(1, 2))
        logits = nn.Dense(self.num_classes, name="linear")(emb)
        if return_features:
            return logits, emb
        return logits


def make_batch(rng, n, hw=8, num_classes=4):
    return {
        "image": rng.integers(0, 256, size=(n, hw, hw, 3), dtype=np.uint8),
        "label": rng.integers(0, num_classes, size=n).astype(np.int32),
        "index": np.arange(n, dtype=np.int32),
        "mask": np.ones(n, dtype=np.float32),
    }


def one_step(trainer, mesh, batch, seed=0):
    state = trainer.init_state(jax.random.PRNGKey(seed),
                               batch["image"][:2])
    cw = jnp.ones(trainer.num_classes, jnp.float32)
    new_state, loss, _gnorm = trainer._train_step(
        state, mesh_lib.shard_batch(batch, mesh), jax.random.PRNGKey(7),
        jnp.float32(0.1), cw, view=VIEW)
    return jax.tree.map(np.asarray, new_state.variables), float(loss)


class TestShardedStepEqualsSingleDevice:
    def test_params_and_bn_stats_match(self):
        """8-way data-sharded step == 1-device step on the same global
        batch: gradients psum correctly and BN stats are global-batch."""
        batch = make_batch(np.random.default_rng(0), 16)
        cfg = tiny_train_config()
        model = BNClassifier()

        mesh8 = mesh_lib.make_mesh(8)
        mesh1 = mesh_lib.make_mesh(1)
        t8 = Trainer(model, cfg, mesh8, 4, train_bn=True)
        t1 = Trainer(model, cfg, mesh1, 4, train_bn=True)
        vars8, loss8 = one_step(t8, mesh8, batch)
        vars1, loss1 = one_step(t1, mesh1, batch)

        assert abs(loss8 - loss1) < 1e-5
        flat8 = jax.tree_util.tree_leaves_with_path(vars8)
        flat1 = dict(jax.tree_util.tree_leaves_with_path(vars1))
        assert len(flat8) > 0
        for path, leaf in flat8:
            np.testing.assert_allclose(
                leaf, flat1[path], rtol=1e-4, atol=1e-5,
                err_msg=f"mismatch at {jax.tree_util.keystr(path)}")

    def test_bn_stats_are_global_batch(self):
        """The updated running mean must reflect the FULL 16-row batch, not
        any single shard's 2 rows (SyncBatchNorm semantics)."""
        batch = make_batch(np.random.default_rng(1), 16)
        cfg = tiny_train_config()
        model = BNClassifier()
        mesh8 = mesh_lib.make_mesh(8)
        trainer = Trainer(model, cfg, mesh8, 4, train_bn=True)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   batch["image"][:2])
        params = jax.tree.map(np.asarray, state.params)

        new_vars, _ = one_step(trainer, mesh8, batch)
        # Oracle: batch mean of the conv output over the whole batch.
        from active_learning_tpu.data.augment import apply_view
        x = apply_view(jnp.asarray(batch["image"]), VIEW, train=False)
        conv_out = jax.lax.conv_general_dilated(
            np.asarray(x), params["conv"]["kernel"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + params["conv"]["bias"]
        batch_mean = np.asarray(conv_out).mean(axis=(0, 1, 2))
        # momentum 0.9: new_running = 0.9 * 0 + 0.1 * batch_mean
        np.testing.assert_allclose(new_vars["batch_stats"]["bn"]["mean"],
                                   0.1 * batch_mean, rtol=1e-3, atol=1e-5)

    def test_padding_rows_do_not_affect_gradients(self):
        """A batch padded from 10 real rows to 16 must produce the same
        update as the 10 real rows alone (padding weight 0)."""
        rng = np.random.default_rng(2)
        real = make_batch(rng, 10)
        cfg = tiny_train_config()
        model = TinyClassifier()  # no BN: padding can't leak via stats

        from active_learning_tpu.data.pipeline import gather_batch

        class _DS:
            targets = real["label"].astype(np.int64)

            def gather(self, idxs):
                return real["image"][idxs]

        padded = gather_batch(_DS(), np.arange(10), 16)
        mesh8 = mesh_lib.make_mesh(8)
        mesh1 = mesh_lib.make_mesh(1)
        t8 = Trainer(model, cfg, mesh8, 4, train_bn=False)
        t1 = Trainer(model, cfg, mesh1, 4, train_bn=False)
        vars_padded, _ = one_step(t8, mesh8, padded)
        vars_real, _ = one_step(t1, mesh1, real)
        for a, b in zip(jax.tree_util.tree_leaves(vars_padded),
                        jax.tree_util.tree_leaves(vars_real)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


class TestFitAndEval:
    def test_fit_decreases_loss(self):
        train_set, _, al_set = get_data_synthetic(n_train=96, n_test=16,
                                                  num_classes=4,
                                                  image_size=8, seed=3)
        model = TinyClassifier()
        mesh = mesh_lib.make_mesh(8)
        trainer = Trainer(model, tiny_train_config(), mesh, 4)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.zeros(1, np.int64)))
        labeled = np.arange(64)
        result = trainer.fit(state, train_set, labeled, al_set,
                             np.arange(64, 80), n_epoch=5, es_patience=0,
                             rng=np.random.default_rng(0))
        losses = [h["train_loss"] for h in result.history]
        assert losses[-1] < losses[0]
        assert result.epochs_run == 5
        # The returned history is plain floats: the per-epoch loss fetch
        # is deferred to the end of the fit, and a device array leaking
        # out here would mean a consumer can accidentally sync or
        # serialize live buffers.
        assert all(isinstance(v, float) for v in losses)

    def test_eval_matches_numpy_oracle(self):
        train_set, test_set, al_set = get_data_synthetic(
            n_train=64, n_test=48, num_classes=4, image_size=8, seed=4)
        model = TinyClassifier()
        mesh = mesh_lib.make_mesh(8)
        trainer = Trainer(model, tiny_train_config(), mesh, 4)
        state = trainer.init_state(jax.random.PRNGKey(1),
                                   test_set.gather(np.zeros(1, np.int64)))
        idxs = np.arange(len(test_set))
        perf = trainer.evaluate(state, test_set, idxs)

        # Oracle: direct unsharded forward.
        from active_learning_tpu.data.augment import apply_view
        x = apply_view(jnp.asarray(test_set.gather(idxs)), test_set.view,
                       train=False)
        logits = np.asarray(model.apply(state.variables, x, train=False))
        labels = test_set.targets[idxs]
        top1 = logits.argmax(1) == labels
        order = np.argsort(-logits, axis=1)[:, :4]  # top_k = num_classes
        topk = (order == labels[:, None]).any(1)
        assert perf["count"] == len(idxs)
        np.testing.assert_allclose(perf["accuracy"], top1.mean(), atol=1e-6)
        np.testing.assert_allclose(perf["top_5_accuracy"], topk.mean(),
                                   atol=1e-6)
        for c in range(4):
            sel = labels == c
            np.testing.assert_allclose(perf["accuracy_byclass"][c],
                                       top1[sel].mean(), atol=1e-6)

    def test_empty_eval_set_reports_zero(self):
        from active_learning_tpu.train.evaluation import accumulate_metrics
        out = accumulate_metrics(iter([]))
        assert out["accuracy"] == 0.0 and out["count"] == 0.0


class TestDeviceResidentEpochs:
    def _fit_pair(self, device_resident):
        import dataclasses
        train_set, _, al_set = get_data_synthetic(n_train=90, n_test=16,
                                                  num_classes=4,
                                                  image_size=8, seed=6)
        cfg = dataclasses.replace(tiny_train_config(),
                                  device_resident=device_resident)
        model = BNClassifier()
        mesh = mesh_lib.make_mesh(8)
        trainer = Trainer(model, cfg, mesh, 4, train_bn=True)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.zeros(1, np.int64)))
        # 90 labeled, batch 16 -> 6 steps with a padded last batch: the
        # padding-row BN semantics are part of what must match.
        result = trainer.fit(state, train_set, np.arange(90), al_set,
                             np.arange(80, 90), n_epoch=3, es_patience=0,
                             rng=np.random.default_rng(42))
        return result

    def test_matches_host_batched_path_exactly(self):
        """Same rng, same key chain, same padding rows: the scanned
        device-resident epoch must reproduce the host-batched epoch."""
        dr = self._fit_pair(device_resident=True)
        host = self._fit_pair(device_resident=False)
        assert [h["train_loss"] for h in dr.history] == pytest.approx(
            [h["train_loss"] for h in host.history], rel=1e-5)
        leaves_dr = jax.tree_util.tree_leaves(
            jax.tree.map(np.asarray, dr.state.variables))
        leaves_host = jax.tree_util.tree_leaves(
            jax.tree.map(np.asarray, host.state.variables))
        for a, b in zip(leaves_dr, leaves_host):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_vaal_hook_forces_host_path(self):
        """batch_hook needs host batches -> device-resident must not
        engage (VAAL co-training)."""
        train_set, _, al_set = get_data_synthetic(n_train=32, n_test=8,
                                                  num_classes=4,
                                                  image_size=8, seed=7)
        trainer = Trainer(TinyClassifier(), tiny_train_config(),
                          mesh_lib.make_mesh(8), 4)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.zeros(1, np.int64)))
        seen = []
        trainer.fit(state, train_set, np.arange(24), al_set,
                    np.arange(24, 32), n_epoch=1, es_patience=0,
                    rng=np.random.default_rng(0),
                    batch_hook=lambda epoch, b: seen.append(epoch))
        assert len(seen) > 0  # hook ran => host path was used


@pytest.mark.usefixtures("collective_lock")
class TestResidentGatherFeed:
    """The resident-gather train feed (DESIGN.md §2a): train batches are
    on-device gathers of labeled indices from the SAME pinned pool that
    serves scoring/evaluation — zero host image copies, and a batch
    stream bit-identical to every other feed at the same seeds."""

    def _fit(self, cfg, n_labeled=83, seed=6, pool=None):
        import dataclasses as dc
        if pool is None:
            train_set, _, al_set = get_data_synthetic(
                n_train=90, n_test=16, num_classes=4, image_size=8,
                seed=seed)
        else:
            train_set, al_set = pool
        mesh = mesh_lib.make_mesh(8)
        trainer = Trainer(BNClassifier(), cfg, mesh, 4, train_bn=True)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.zeros(1, np.int64)))
        # n_labeled=83 with batch 16: a PADDED last batch — padding
        # isolation is part of what must match bit for bit.
        result = trainer.fit(state, train_set, np.arange(n_labeled),
                             al_set, np.arange(83, 90), n_epoch=3,
                             es_patience=0, rng=np.random.default_rng(42))
        return trainer, result

    @staticmethod
    def _leaves(result):
        return jax.tree_util.tree_leaves(
            jax.tree.map(np.asarray, result.state.variables))

    def test_bitwise_identical_to_copy_scan_and_matches_host(self):
        import dataclasses as dc
        base = tiny_train_config()
        # Scan form (forced by device_resident=True): gathers from the
        # pinned pool inside the SAME scan body the legacy copy path
        # runs.  Same gathered bytes, same program => bitwise-identical
        # parameters.
        t_scan, scan = self._fit(dc.replace(base, train_feed="resident",
                                            device_resident=True))
        assert t_scan.last_feed["source"] == "resident"
        assert t_scan.last_feed["form"] == "scan"
        t_copy, copy = self._fit(dc.replace(base, device_resident=True,
                                            resident_scoring_bytes=0))
        assert t_copy.last_feed["source"] == "resident_copy"
        for a, b in zip(self._leaves(scan), self._leaves(copy)):
            np.testing.assert_array_equal(a, b)
        # Per-batch form (the CPU-mesh execution form): same batch
        # stream through a per-batch jitted gather+step.
        t_res, res = self._fit(dc.replace(base, train_feed="resident"))
        assert t_res.last_feed["source"] == "resident"
        assert t_res.last_feed["form"] == "step"
        for a, b in zip(self._leaves(res), self._leaves(scan)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        # And the host-batched stream is the same batches through the
        # same step — numerically identical within fusion-order noise.
        t_host, host = self._fit(dc.replace(base, device_resident=False))
        assert t_host.last_feed["source"].startswith("host")
        assert [h["train_loss"] for h in res.history] == pytest.approx(
            [h["train_loss"] for h in host.history], rel=1e-5)
        for a, b in zip(self._leaves(res), self._leaves(host)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_unlabeled_pool_rows_never_leak_into_training(self):
        """The resident feed gathers from the FULL pool array; rows
        outside the labeled set must be complete no-ops — two pools
        identical on the labeled rows but wildly different elsewhere
        must train to bitwise-identical parameters."""
        import dataclasses as dc
        from active_learning_tpu.data.core import ArrayDataset
        train_set, _, al_set = get_data_synthetic(
            n_train=90, n_test=16, num_classes=4, image_size=8, seed=6)
        cfg = dc.replace(tiny_train_config(), train_feed="resident")
        labeled = np.arange(40)
        poisoned = train_set.images.copy()
        poisoned[60:] = 255  # never-labeled rows scrambled
        pool_a = (train_set, al_set)
        ds_b = ArrayDataset(poisoned, train_set.targets, 4, train_set.view)
        pool_b = (ds_b, ds_b.with_view(al_set.view))
        _, ra = self._fit(cfg, n_labeled=40, pool=pool_a)
        _, rb = self._fit(cfg, n_labeled=40, pool=pool_b)
        for a, b in zip(self._leaves(ra), self._leaves(rb)):
            np.testing.assert_array_equal(a, b)

    def test_one_pinned_pool_serves_training_and_evaluation(self):
        """After a resident-feed fit, evaluation over the al view (shared
        storage) reuses the SAME upload — one cache entry, and the
        budget accounting sees one array's bytes."""
        import dataclasses as dc
        from active_learning_tpu.parallel import resident as resident_lib
        cfg = dc.replace(tiny_train_config(), train_feed="resident")
        trainer, result = self._fit(cfg)
        assert len(trainer.resident_pool["images"]) == 1
        pinned = resident_lib.pinned_bytes(trainer.resident_pool)
        train_set, _, al_set = get_data_synthetic(
            n_train=90, n_test=16, num_classes=4, image_size=8, seed=6)
        # (fresh dataset objects share nothing with the fit's — re-fit on
        # the trainer's own cached dataset instead)
        ds = trainer.resident_pool["images"][next(
            iter(trainer.resident_pool["images"]))][0]
        trainer.evaluate(result.state, ds, np.arange(8))
        assert len(trainer.resident_pool["images"]) == 1
        assert resident_lib.pinned_bytes(trainer.resident_pool) == pinned

    def test_feed_resolution_hierarchy(self):
        """resolve_train_feed walks resident > resident_copy >
        host_prefetch > host_serial; a pinned pool auto-selects the
        resident feed on accelerators (the acceptance invariant)."""
        import dataclasses as dc
        from active_learning_tpu.parallel import resident as resident_lib
        train_set, _, _ = get_data_synthetic(
            n_train=64, n_test=8, num_classes=4, image_size=8, seed=1)
        idxs = np.arange(64)

        def mk(**over):
            return Trainer(TinyClassifier(), dc.replace(
                tiny_train_config(), **over), mesh_lib.make_mesh(), 4,
                train_bn=False)

        class FakeDev:
            platform = "tpu"

        def on_accel(trainer):
            class FakeMesh:
                class devices:  # noqa: N801 - mimic ndarray .flat/.size
                    flat = [FakeDev()]
                    size = trainer.n_devices
            trainer.mesh = FakeMesh()
            return trainer

        # Accelerator + pool fits the budget => resident, even unpinned.
        assert on_accel(mk()).resolve_train_feed(train_set, idxs) \
            == "resident"
        # Pinned pool => resident even when the budget later reads 0
        # (its bytes are already in HBM — parallel/resident.cached).
        t = mk()
        resident_lib.pool_arrays(t.resident_pool, train_set, t.mesh)
        on_accel(t)  # pin on the REAL mesh, then resolve as-if-on-TPU
        t.resident_budget = 0
        assert t.resolve_train_feed(train_set, idxs) == "resident"
        # Budget 0 (residency disabled / mid-run demote), auto mode: the
        # resident_copy upload is HBM like any pinned array and is
        # charged against the SAME budget — the fallback must be the
        # host path, never an unaccounted re-upload.
        t2 = on_accel(mk(resident_scoring_bytes=0))
        t2.resident_budget = 0
        assert t2.resolve_train_feed(train_set, idxs) == "host_prefetch"
        # ... while an EXPLICIT device_resident=True keeps its legacy
        # force-the-scan meaning regardless of the budget.
        t2f = on_accel(mk(resident_scoring_bytes=0, device_resident=True))
        t2f.resident_budget = 0
        assert t2f.resolve_train_feed(train_set, idxs) == "resident_copy"
        # device_resident=False pins the host leg; prefetch>0 => threaded.
        assert on_accel(mk(device_resident=False)).resolve_train_feed(
            train_set, idxs) == "host_prefetch"
        import dataclasses
        serial = mk(device_resident=False,
                    loader_tr=dataclasses.replace(
                        tiny_train_config().loader_tr, prefetch=0))
        assert on_accel(serial).resolve_train_feed(train_set, idxs) \
            == "host_serial"
        # A batch_hook (VAAL) always takes the serial host leg.
        assert on_accel(mk()).resolve_train_feed(
            train_set, idxs, batch_hook=lambda e, b: None) == "host_serial"
        # CPU auto keeps small fits on the host (scan compile must
        # amortize); a disk-style dataset (no .images) can never pin.
        assert mk().resolve_train_feed(train_set, idxs).startswith("host")

    def test_host_prefetch_stream_identical_to_serial(self):
        import dataclasses as dc
        base = tiny_train_config()
        _, pre = self._fit(dc.replace(base, device_resident=False))
        _, ser = self._fit(dc.replace(
            base, device_resident=False,
            loader_tr=dc.replace(base.loader_tr, prefetch=0)))
        for a, b in zip(self._leaves(pre), self._leaves(ser)):
            np.testing.assert_array_equal(a, b)


class TestImbalancedTrainingWeights:
    """The reference's class-weighted loss (strategy.py:444-457 +
    CrossEntropyLoss(weight=w), strategy.py:352-356)."""

    def test_class_weights_reference_semantics(self):
        import dataclasses
        cfg = dataclasses.replace(tiny_train_config(),
                                  imbalanced_training=True)
        trainer = Trainer(TinyClassifier(), cfg, mesh_lib.make_mesh(), 4)
        labels = np.array([0, 0, 0, 1, 1, 2])  # class 3 unobserved
        got = trainer.class_weights(labels)
        raw = np.array([6 / 3, 6 / 2, 6 / 1, 1.0])  # total/count, else 1
        np.testing.assert_allclose(got, raw / raw.sum(), rtol=1e-6)
        assert abs(got.sum() - 1.0) < 1e-6
        # Flag off: identity weights.
        off = Trainer(TinyClassifier(), tiny_train_config(),
                      mesh_lib.make_mesh(), 4)
        assert (off.class_weights(labels) == 1.0).all()

    def test_weighted_ce_matches_torch(self):
        """weighted_cross_entropy == torch CrossEntropyLoss(weight=w,
        reduction='mean'): sum(w_y * ce) / sum(w_y)."""
        import torch

        from active_learning_tpu.train.trainer import weighted_cross_entropy
        rng = np.random.default_rng(3)
        logits = rng.normal(size=(12, 5)).astype(np.float32)
        labels = rng.integers(0, 5, size=12)
        class_w = rng.uniform(0.2, 2.0, size=5).astype(np.float32)
        ours = float(weighted_cross_entropy(
            jnp.asarray(logits), jnp.asarray(labels),
            jnp.asarray(class_w[labels])))
        ref = torch.nn.CrossEntropyLoss(weight=torch.tensor(class_w))(
            torch.tensor(logits), torch.tensor(labels))
        assert abs(ours - float(ref)) < 1e-5

    def test_zero_weight_rows_do_not_move_the_loss(self):
        """Padding rows enter with weight 0 (mask multiplied in the train
        step) and must be exact no-ops on the loss."""
        from active_learning_tpu.train.trainer import weighted_cross_entropy
        rng = np.random.default_rng(4)
        logits = rng.normal(size=(6, 4)).astype(np.float32)
        labels = rng.integers(0, 4, size=6)
        w = np.ones(6, dtype=np.float32)
        base = float(weighted_cross_entropy(
            jnp.asarray(logits), jnp.asarray(labels), jnp.asarray(w)))
        pad_logits = np.concatenate([logits, rng.normal(size=(3, 4))
                                     .astype(np.float32)])
        pad_labels = np.concatenate([labels, np.array([0, 1, 2])])
        pad_w = np.concatenate([w, np.zeros(3, np.float32)])
        padded = float(weighted_cross_entropy(
            jnp.asarray(pad_logits), jnp.asarray(pad_labels),
            jnp.asarray(pad_w)))
        assert abs(base - padded) < 1e-6


class TestResidentEvaluation:
    """In-memory eval/test rows stay device-resident across epochs and
    rounds; results must be identical to the host-batched path."""

    def test_matches_host_batched_evaluate(self):
        import dataclasses
        train_set, _, al_set = get_data_synthetic(
            n_train=100, n_test=16, num_classes=4, image_size=8, seed=9)
        mesh = mesh_lib.make_mesh()
        res = Trainer(BNClassifier(), tiny_train_config(), mesh, 4,
                      train_bn=True)
        host = Trainer(BNClassifier(),
                       dataclasses.replace(tiny_train_config(),
                                           resident_scoring_bytes=0),
                       mesh, 4, train_bn=True)
        state = res.init_state(jax.random.PRNGKey(1),
                               train_set.gather(np.arange(2)))
        idxs = np.arange(37, 100)  # padded last batch included
        a = res.evaluate(state, al_set, idxs)
        b = host.evaluate(state, al_set, idxs)
        assert len(res.resident_pool["images"]) == 1
        for k in a:
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       rtol=1e-6, atol=1e-6, err_msg=k)

    def test_views_share_one_upload_and_no_host_gathers(self):
        """al/train views share storage -> one upload; repeated evaluate
        calls (per-epoch validation) never touch the host dataset again."""
        train_set, _, al_set = get_data_synthetic(
            n_train=64, n_test=16, num_classes=4, image_size=8, seed=9)
        mesh = mesh_lib.make_mesh()
        trainer = Trainer(BNClassifier(), tiny_train_config(), mesh, 4,
                          train_bn=True)
        state = trainer.init_state(jax.random.PRNGKey(1),
                                   train_set.gather(np.arange(2)))
        calls = {"n": 0}
        orig = al_set.gather

        def counting(idxs):
            calls["n"] += 1
            return orig(idxs)

        al_set.gather = counting
        for _ in range(3):  # three "epochs" of validation
            trainer.evaluate(state, al_set, np.arange(48, 64))
        trainer.evaluate(state, train_set.with_view(al_set.view),
                         np.arange(8))  # shares the images array
        assert calls["n"] == 0
        assert len(trainer.resident_pool["images"]) == 1  # one upload for both


def test_eval_batch_floor_cpu_keeps_reference_batch():
    """On the CPU test mesh, evaluation uses the reference's test-loader
    batch unchanged; the accelerator floor (>=128 rows/chip) applies the
    same throughput-only policy as acquisition scoring."""
    from helpers import TinyClassifier, tiny_train_config
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.train.trainer import Trainer

    trainer = Trainer(TinyClassifier(num_classes=4),
                      tiny_train_config(batch_size=16),
                      mesh_lib.make_mesh(), num_classes=4)
    assert trainer.eval_batch_size() == trainer.cfg.loader_te.batch_size

    class FakeDev:
        platform = "tpu"

    class FakeMesh:
        class devices:  # noqa: N801 — mimic np.ndarray .flat/.size
            flat = [FakeDev()]
            size = trainer.n_devices

    real = trainer.mesh
    trainer.mesh = FakeMesh()
    try:
        # Unknown row shape: conservative 128/chip floor.
        assert trainer.eval_batch_size() == 128 * trainer.n_devices

        class Small:  # 32px rows: 512/chip (v5e probe: +47% over 256)
            image_shape = (32, 32, 3)

        class Large:  # ImageNet-res rows: 256/chip (+11% over 128)
            image_shape = (224, 224, 3)

        assert trainer.eval_batch_size(Small()) == 512 * trainer.n_devices
        assert trainer.eval_batch_size(Large()) == 256 * trainer.n_devices
    finally:
        trainer.mesh = real


def test_cosine_warmup_schedule():
    """warmup_epochs=0 is exactly torch CosineAnnealingLR; warmup>0 ramps
    linearly (never starting at 0) then runs the cosine over the
    remaining epochs — the re-init-every-round cold-start fix
    (SchedulerConfig.warmup_epochs)."""
    import math

    from active_learning_tpu.config import SchedulerConfig
    from active_learning_tpu.train.optim import make_lr_schedule

    plain = make_lr_schedule(SchedulerConfig(name="cosine", t_max=10), 0.1)
    for e in range(10):
        expected = 0.1 * (1 + math.cos(math.pi * e / 10)) / 2
        assert abs(plain(e) - expected) < 1e-12

    warm = make_lr_schedule(
        SchedulerConfig(name="cosine", t_max=10, warmup_epochs=3), 0.1)
    assert abs(warm(0) - 0.1 / 3) < 1e-12
    assert abs(warm(1) - 0.2 / 3) < 1e-12
    assert abs(warm(2) - 0.1) < 1e-12
    # Cosine span starts after the ramp and ends where t_max says.
    assert abs(warm(3) - 0.1) < 1e-12
    assert warm(9) < warm(3)
    assert abs(warm(9) - 0.1 * (1 + math.cos(math.pi * 6 / 7)) / 2) < 1e-12
