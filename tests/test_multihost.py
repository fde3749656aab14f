"""Multi-host data parallelism: per-process batch slicing + a real
2-process CPU smoke run.

The reference is single-node only (MASTER_ADDR hardcoded to 127.0.0.1,
strategy.py:288); its per-rank data split is DistributedSampler
(strategy.py:312-314).  Here the per-host split is ``process_local_rows``
(read off the sharding itself) feeding ``gather_batch(..., local=...)``,
and the cross-host pieces (batch assembly, gradient reduction, score
gather) are exercised for real by spawning two coordinated JAX processes
over localhost — the CPU stand-in for a pod slice.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

# Spawns real 2-process jax.distributed runs (fresh interpreters, fresh
# XLA compiles per process).
pytestmark = pytest.mark.slow

from active_learning_tpu.data.pipeline import gather_batch, padded_batch_layout
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.parallel import mesh as mesh_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestLocalSliceMath:
    def test_single_process_owns_everything(self):
        mesh = mesh_lib.make_mesh(8)
        assert mesh_lib.process_local_rows(mesh, 16) == slice(0, 16)
        assert not mesh_lib.is_multiprocess(mesh)

    def test_local_gather_matches_rows_of_full_gather(self):
        """gather_batch(local=s) must equal rows s of the full batch for
        every field, including padding rows of a partial batch."""
        train_set, _, _ = get_data_synthetic(n_train=32, n_test=8,
                                             num_classes=4, image_size=8,
                                             seed=0)
        idxs = np.array([5, 9, 2, 17, 11])  # partial batch of 8 -> 3 pad
        full = gather_batch(train_set, idxs, 8)
        for s in (slice(0, 4), slice(4, 8), slice(2, 6)):
            part = gather_batch(train_set, idxs, 8, local=s)
            for k in full:
                np.testing.assert_array_equal(part[k], full[k][s], err_msg=k)

    def test_padded_layout_is_deterministic(self):
        idxs = np.array([3, 1, 4])
        padded, mask = padded_batch_layout(idxs, 8)
        np.testing.assert_array_equal(padded, [3, 1, 4, 3, 3, 3, 3, 3])
        np.testing.assert_array_equal(mask, [1, 1, 1, 0, 0, 0, 0, 0])
        # Full batch: untouched.
        padded, mask = padded_batch_layout(np.arange(8), 8)
        np.testing.assert_array_equal(padded, np.arange(8))
        assert mask.min() == 1.0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _single_process_oracle():
    """The worker's computation on a 4-device single-process mesh."""
    import jax

    from active_learning_tpu.strategies import scoring
    from active_learning_tpu.train.trainer import Trainer
    from helpers import TinyClassifier, tiny_train_config

    mesh = mesh_lib.make_mesh(4)
    train_set, _, al_set = get_data_synthetic(
        n_train=64, n_test=16, num_classes=4, image_size=8, seed=3)
    model = TinyClassifier()
    trainer = Trainer(model, tiny_train_config(batch_size=8), mesh,
                      num_classes=4)
    state = trainer.init_state(jax.random.PRNGKey(0),
                               train_set.gather(np.arange(2)))
    result = trainer.fit(state, train_set, np.arange(32), al_set,
                         np.arange(32, 48), n_epoch=2, es_patience=2,
                         rng=np.random.default_rng(0))
    leaves = jax.tree_util.tree_leaves(
        jax.tree.map(np.asarray, result.state.params))
    flat = np.concatenate([p.ravel() for p in leaves])
    step = scoring.make_prob_stats_step(model, al_set.view)
    scores = scoring.collect_pool(al_set, np.arange(48, 64), 8, step,
                                  result.state.variables, mesh)
    return float(flat.sum()), np.asarray(scores["margin"], np.float64)


class TestTwoProcessSmoke:
    def test_two_processes_match_single_process(self, tmp_path):
        """2 processes x 2 CPU devices == 1 process x 4 CPU devices:
        same trained parameters, same pool scores, and each process
        gathered only its half of every batch."""
        port = _free_port()
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        # The workers must not inherit pytest's 8-device flag.
        procs, outs = [], []
        for pid in range(2):
            out = tmp_path / f"worker_{pid}.json"
            outs.append(out)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(REPO, "tests",
                                              "multihost_worker.py"),
                 f"127.0.0.1:{port}", "2", str(pid), str(out)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
        results = []
        for p in procs:
            try:
                stdout, stderr = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("multi-host worker timed out")
            assert p.returncode == 0, f"worker failed:\n{stderr[-3000:]}"
        for out in outs:
            results.append(json.loads(out.read_text()))

        by_pid = {r["process_index"]: r for r in results}
        assert set(by_pid) == {0, 1}
        for r in results:
            assert r["process_count"] == 2
            assert r["n_devices_global"] == 4
        # Each process owns one contiguous half of every global batch.
        assert by_pid[0]["local_rows"] == [0, 4]
        assert by_pid[1]["local_rows"] == [4, 8]
        # Both processes agree bit-for-bit (replicated state, gathered
        # scores are global).
        assert by_pid[0]["param_sum"] == by_pid[1]["param_sum"]
        assert by_pid[0]["margin"] == by_pid[1]["margin"]
        # Decoded-pool disk cache under jax.distributed: both processes
        # scored through their own per-process cache files and the warm
        # margins agreed with the raw dataset (asserted in-worker) AND
        # across processes here.  A missing margin is only acceptable
        # with an explicit skip reason (PIL absent) — any other failure
        # already crashed the worker above.
        if by_pid[0]["decoded_cache_margin"] is None:
            assert by_pid[0]["decoded_cache_skip"], by_pid[0]
        else:
            assert by_pid[0]["decoded_cache_margin"] == \
                by_pid[1]["decoded_cache_margin"]

        oracle_sum, oracle_margin = _single_process_oracle()
        assert by_pid[0]["param_sum"] == pytest.approx(oracle_sum, rel=1e-5)
        np.testing.assert_allclose(np.array(by_pid[0]["margin"]),
                                   oracle_margin, rtol=1e-5, atol=1e-6)

        # BalancingSampler's cross-process pick loop: both processes agree
        # and match the host-NumPy selection over the same seeded inputs.
        assert by_pid[0]["balancing_picks"] == by_pid[1]["balancing_picks"]
        assert by_pid[0]["balancing_picks"] == _balancing_picks_oracle()


def _balancing_picks_oracle():
    """Host-NumPy replay of the worker's 4 seeded balancing picks."""
    brng = np.random.default_rng(5)
    emb = brng.normal(size=(37, 6)).astype(np.float32)
    eligible = np.ones(37, bool)
    eligible[::7] = False
    centers = brng.normal(size=(4, 6)).astype(np.float32)
    maj = np.array([True, True, False, False])
    rarest = 2
    picks = []
    for _ in range(4):
        d_rare = ((emb - centers[rarest]) ** 2).sum(1)
        a2 = (emb ** 2).sum(1, keepdims=True)
        b2 = (centers ** 2).sum(1)[None, :]
        d_all = a2 + b2 - 2.0 * emb @ centers.T
        norm = np.where(maj[None, :], d_all, -np.inf).max(1)
        score = np.where(eligible, d_rare / norm, np.inf)
        q = int(np.argmin(score))
        eligible[q] = False
        picks.append(q)
    return picks
