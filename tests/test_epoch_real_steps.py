"""The epoch program runs an epoch's REAL steps: its shapes are bucketed
(``Trainer.bucket_steps``) but its trip count is a value it reads off
``valid``, so a padded step costs index bytes and never a train step.

One device throughout: what is pinned here is the loop, not a collective
(the row-sharded composition is in test_trainer_parallel.py and, for the
chip, test_chip_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.parallel import resident as resident_lib
from active_learning_tpu.train.trainer import Trainer

from helpers import tiny_train_config
from test_trainer_parallel import BNClassifier

BS = 16


class _Rig:
    """A trainer, its pinned pool and both execution forms of one epoch."""

    def __init__(self):
        self.train_set, _, _ = get_data_synthetic(
            n_train=32 * BS, n_test=8, num_classes=4, image_size=8, seed=6)
        self.mesh = mesh_lib.make_mesh(1)
        self.trainer = Trainer(BNClassifier(), tiny_train_config(BS),
                               self.mesh, 4, train_bn=True)
        self.images = jnp.asarray(
            resident_lib.to_pinned(self.train_set.images))
        self.labels = jnp.asarray(self.train_set.targets.astype(np.int32))
        self.lr = jnp.float32(0.05)
        self.class_weights = jnp.ones((4,), jnp.float32)

    def state(self):
        return self.trainer.init_state(
            jax.random.PRNGKey(0),
            self.train_set.gather(np.zeros(1, np.int64)))

    def matrices(self, steps_real):
        # Three rows short of full: the last real batch is a padded one.
        return Trainer._epoch_index_matrix(
            steps_real * BS - 3, BS, np.random.default_rng(42))

    def run_program(self, scan, idx_mat, mask_mat, valid):
        return scan(self.state(), self.images, self.labels,
                    jnp.asarray(idx_mat), jnp.asarray(mask_mat),
                    jnp.asarray(valid), jax.random.PRNGKey(7), self.lr,
                    self.class_weights, view=self.train_set.view)

    def run_per_batch(self, step, idx_mat, mask_mat, steps_real):
        state, key = self.state(), jax.random.PRNGKey(7)
        losses, gnorms = [], []
        for i in range(steps_real):
            state, key, loss, gnorm = step(
                state, self.images, self.labels, jnp.asarray(idx_mat[i]),
                jnp.asarray(mask_mat[i]), key, self.lr, self.class_weights,
                view=self.train_set.view)
            losses.append(loss)
            gnorms.append(gnorm)
        return state, key, np.asarray(losses), np.asarray(gnorms)


@pytest.fixture(scope="module")
def rig():
    """One rig and its two compiled forms for the tests that leave the
    trainer as it is."""
    rig = _Rig()
    row_shape = rig.train_set.image_shape
    rig.scan = rig.trainer._build_epoch_scan(row_shape)
    rig.step = rig.trainer._build_resident_batch_step(row_shape)
    return rig


def _leaves(tree):
    return jax.tree_util.tree_leaves(jax.tree.map(np.asarray, tree))


@pytest.mark.parametrize("steps_real,steps", [(1, 16), (5, 16), (16, 16),
                                              (17, 32), (32, 32)])
def test_program_runs_the_real_steps_of_the_per_batch_form(rig, steps_real,
                                                           steps):
    """State, key and the real steps' losses are those of the per-batch
    form on the same rng (the key chain bit for bit; the arithmetic as
    close as XLA:CPU brings a loop body to the same step dispatched
    alone: a last-place difference), and bit for bit those of the same
    program handed the matrices WITHOUT their padding; past the real
    steps the program wrote nothing."""
    idx_mat, mask_mat, valid, real = rig.matrices(steps_real)
    assert (real, len(valid)) == (steps_real, steps)
    state, key, losses, gnorms = rig.run_program(rig.scan, idx_mat,
                                                 mask_mat, valid)
    assert losses.shape == gnorms.shape == (steps,)
    losses, gnorms = np.asarray(losses), np.asarray(gnorms)
    assert int(state.step) == steps_real
    assert np.all(losses[:steps_real] > 0)
    np.testing.assert_array_equal(losses[steps_real:], 0.0)
    np.testing.assert_array_equal(gnorms[steps_real:], 0.0)

    want_state, want_key, want_losses, want_gnorms = rig.run_per_batch(
        rig.step, idx_mat, mask_mat, steps_real)
    np.testing.assert_array_equal(np.asarray(key), np.asarray(want_key))
    for got, want in zip(_leaves(state), _leaves(want_state)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(losses[:steps_real], want_losses, rtol=1e-5)
    np.testing.assert_allclose(gnorms[:steps_real], want_gnorms, rtol=1e-5)

    bare = rig.run_program(rig.scan, idx_mat[:steps_real],
                           mask_mat[:steps_real], valid[:steps_real])
    for got, want in zip(_leaves(state), _leaves(bare[0])):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(key), np.asarray(bare[1]))
    np.testing.assert_array_equal(losses[:steps_real], np.asarray(bare[2]))
    np.testing.assert_array_equal(gnorms[:steps_real], np.asarray(bare[3]))


@pytest.mark.parametrize("steps_real,steps", [(1, 16), (5, 16), (16, 16),
                                              (17, 32)])
def test_train_step_runs_once_per_real_step(steps_real, steps):
    """The train step inside the program executes ``steps_real`` times,
    not ``steps``: counted by a host callback planted in front of it."""
    rig = _Rig()
    calls = []
    real_step = rig.trainer._train_step

    def counting(state, batch, key, lr, class_weights, view):
        jax.debug.callback(lambda: calls.append(1))
        return real_step(state, batch, key, lr, class_weights, view=view)

    rig.trainer._train_step = counting
    scan = rig.trainer._build_epoch_scan(rig.train_set.image_shape)
    idx_mat, mask_mat, valid, _ = rig.matrices(steps_real)
    assert len(valid) == steps
    out = rig.run_program(scan, idx_mat, mask_mat, valid)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert len(calls) == steps_real


def test_the_trip_count_is_a_value_not_a_shape(rig):
    """Every ``steps_real`` of one bucket, none included, runs the one
    compiled program."""
    scan = rig.trainer._build_epoch_scan(rig.train_set.image_shape)
    for steps_real in (1, 5, 16):
        idx_mat, mask_mat, valid, _ = rig.matrices(steps_real)
        state, _, _, _ = rig.run_program(scan, idx_mat, mask_mat, valid)
        assert int(state.step) == steps_real
    assert scan._cache_size() == 1
    # An all-padding matrix is an epoch of no steps: the state comes back.
    state, key, losses, _ = rig.run_program(
        scan, idx_mat, mask_mat, np.zeros_like(valid))
    for got, want in zip(_leaves(state), _leaves(rig.state())):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(key),
                                  np.asarray(jax.random.PRNGKey(7)))
    assert not np.asarray(losses).any()
    assert scan._cache_size() == 1
