"""The pod tier (ISSUE 15, DESIGN.md §15), pinned.

Four claims make the pod tier safe to turn on:

  1. WIRE TRUTH — the quantized reduce-scatter gradient sync
     (mesh.int8_reduce_scatter) moves FEWER bytes than the all-gather
     form at ndev >= 8, in the wire-model table AND in MEASURED
     optimized-HLO collective payload bytes (the collective_bytes_total
     methodology of PR 10, applied to the compiled executables), while
     staying inside its documented error bound, deterministic and
     replicated, and poisoning non-finite blocks like the f32 path
     would surface them.
  2. RING TRUTH — ring_shift rotates blocks so every shard sees every
     block exactly once, owner_rows_scattered assembles center blocks
     exactly (zeros + owner bits), and the ring-fed k-center scans stay
     bit-identical to the replicated scans (tests/test_pool_sharding.py
     pins the picks; the primitives are pinned here).
  3. GATING TRUTH — the reduce-scatter path sits behind the SAME
     learning probe + sticky-degrade journal machinery as PR 9's int8
     path (chaos-cased), and warm rounds under it add zero compiles.
  4. POD TRUTH — a REAL 2-process mesh (jax.distributed over localhost,
     gloo CPU collectives) produces experiment_state BIT-IDENTICAL to
     the single-process run at the same seeds, for Margin AND Coreset
     (slow-marked subprocess harness, tests/pod_harness.py).
"""

import dataclasses
import glob
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.strategies import kcenter as kc
from active_learning_tpu.strategies import scoring

from helpers import TinyClassifier, tiny_train_config

NDEV = 8


def _run_sync(fn, x_global):
    """Run a gradient-sync tree function over the 8-device mesh; the
    result rides out PER DEVICE (each shard returns its full replicated
    copy) so replication is assertable, not assumed."""
    mesh = mesh_lib.make_mesh()

    def body(v):
        return fn({"g": v})["g"]

    out = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"),),
                            out_specs=P("data"), check_vma=False))(
        jnp.asarray(x_global).reshape(-1))
    return np.asarray(out).reshape(NDEV, -1)


class TestWireResolution:
    def test_resolve_grad_allreduce_modes(self):
        one = mesh_lib.make_mesh(1)
        full = mesh_lib.make_mesh()
        for mode in ("int8", "int8_rs", "auto"):
            assert mesh_lib.resolve_grad_allreduce(mode, one) == "f32"
            assert mesh_lib.resolve_grad_allreduce(mode, full) == "int8"
        assert mesh_lib.resolve_grad_allreduce("f32", full) == "f32"
        with pytest.raises(ValueError):
            mesh_lib.resolve_grad_allreduce("int4", full)

    def test_resolve_int8_wire_crossover(self):
        full = mesh_lib.make_mesh()  # 8 devices: at the crossover
        assert mesh_lib.resolve_int8_wire("int8", full) == "allgather"
        assert mesh_lib.resolve_int8_wire("auto", full) == "allgather"
        assert mesh_lib.resolve_int8_wire("int8_rs", full) \
            == "reduce_scatter"

    def test_wire_model_table(self):
        """The pod-tier wire-model table: the all-gather form's bytes
        grow linearly with ndev (inverted vs the ~8n f32 ring past ~9
        devices — the documented PR 9 blowup), the reduce-scatter form
        stays ~2n regardless, and sits BELOW the all-gather form at
        every ndev >= 8 (the acceptance row)."""
        n = 10 ** 6
        for ndev in (8, 9, 16, 64, 256):
            ag = mesh_lib.wire_model_bytes("allgather", ndev, n)
            rs = mesh_lib.wire_model_bytes("reduce_scatter", ndev, n)
            f32 = mesh_lib.wire_model_bytes("f32", ndev, n)
            assert rs < ag, (ndev, rs, ag)
            assert rs < f32
            assert rs < 2 * (n + 4 * n // 256) + 1
        # The inversion the crossover rule encodes: past ~9 devices the
        # all-gather form moves MORE than the f32 ring it was meant to
        # beat.
        assert mesh_lib.wire_model_bytes("allgather", 9, n) \
            > mesh_lib.wire_model_bytes("f32", 9, n)
        assert mesh_lib.wire_model_bytes("allgather", 4, n) \
            < mesh_lib.wire_model_bytes("f32", 4, n)
        assert mesh_lib.wire_model_bytes("f32", 1, n) == 0
        with pytest.raises(ValueError):
            mesh_lib.wire_model_bytes("int4", 8, n)


class TestMeasuredWireBytes:
    def test_reduce_scatter_measures_below_allgather(self):
        """MEASURED wire bytes, not just modeled: compile both quantized
        sync forms for the same gradient size and read the collective
        payload bytes off the optimized HLO (telemetry/profiler.
        hlo_text_collective_bytes — the exact-shape half of PR 10's
        collective_bytes_total).  At the 8-device mesh the
        reduce-scatter form's total collective payload must land BELOW
        the all-gather form's — the wire claim, proven on the
        executables that would actually run."""
        from active_learning_tpu.telemetry import profiler as prof

        mesh = mesh_lib.make_mesh()
        n = NDEV * 100_000

        def compiled(fn):
            body = lambda v: fn({"g": v})["g"]  # noqa: E731
            return jax.jit(shard_map(
                body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                check_vma=False)).lower(
                    jnp.zeros((n,), jnp.float32)).compile()

        ag = prof.hlo_text_collective_bytes(
            compiled(lambda t: mesh_lib.int8_allreduce(
                t, "data")).as_text())
        rs = prof.hlo_text_collective_bytes(
            compiled(lambda t: mesh_lib.int8_reduce_scatter(
                t, NDEV, "data")).as_text())
        assert ag and rs, "no collectives parsed from the optimized HLO"
        ag_total, rs_total = sum(ag.values()), sum(rs.values())
        assert rs_total < ag_total, (rs, ag)
        # The dominant ag payload is the full gathered int8 matrix
        # (~n * 1 byte per shard result); rs's biggest ops are the
        # 1/ndev-shard all_to_all + all_gather.
        assert ag_total > 0.9 * (n // NDEV) * NDEV
        assert rs_total < 3 * (n // NDEV) + 8192

    def test_int8_payloads_actually_int8(self):
        """The quantized payload rides the wire as s8, not a float that
        was quantized and silently promoted back before the collective:
        the optimized HLO's biggest all-to-all/all-gather carry 1-byte
        elements."""
        from active_learning_tpu.telemetry import profiler as prof

        mesh = mesh_lib.make_mesh()
        n = NDEV * 65536
        body = lambda v: mesh_lib.int8_reduce_scatter(  # noqa: E731
            {"g": v}, NDEV, "data")["g"]
        text = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False)).lower(
                jnp.zeros((n,), jnp.float32)).compile().as_text()
        table = prof.hlo_text_collective_bytes(text)
        per_shard = n // NDEV
        # all-to-all result: my shard's int8 blocks from every peer —
        # exactly per_shard bytes.  A f32 payload would read 4x.
        a2a = [v for k, v in table.items() if k.startswith("all-to-all")]
        assert a2a and min(a2a) <= per_shard + 1024


@pytest.mark.usefixtures("collective_lock")
class TestInt8ReduceScatter:
    def _exact_and_rs(self, x):
        exact = x.reshape(NDEV, -1).sum(0)
        rs = _run_sync(lambda t: mesh_lib.int8_reduce_scatter(
            t, NDEV, "data"), x)
        return exact, rs

    def test_bounded_error_and_replicated(self):
        rng = np.random.default_rng(3)
        x = (rng.normal(size=(NDEV, 4096)) * 0.01).astype(np.float32)
        exact, rs = self._exact_and_rs(x)
        # Replicated: every device holds the SAME dequantized bytes
        # (all consume the owner's all_gathered payload).
        for d in range(1, NDEV):
            np.testing.assert_array_equal(rs[d], rs[0])
        # Documented bound: first quantization <= ndev * scale1 / 2
        # summed, requantization <= scale2 / 2 — scale2 bounded via
        # |reduced| <= |exact| + ndev * scale1 / 2.
        block = mesh_lib.INT8_BLOCK
        blocks = x.reshape(NDEV, -1, block)
        s1 = np.abs(blocks).max(axis=(0, 2)) / 127.0  # shared pmax
        sum_err = NDEV * s1 / 2.0
        eblk = np.abs(exact.reshape(-1, block)).max(axis=1)
        s2 = (eblk + sum_err) / 127.0
        bound = np.repeat(sum_err + s2 / 2.0, block)
        assert (np.abs(rs[0] - exact) <= bound * 1.0001).all()

    def test_deterministic(self):
        rng = np.random.default_rng(4)
        x = (rng.normal(size=(NDEV, 1024)) * 3.0).astype(np.float32)
        _, a = self._exact_and_rs(x)
        _, b = self._exact_and_rs(x)
        np.testing.assert_array_equal(a, b)

    def test_nonfinite_block_poisons_to_nan(self):
        rng = np.random.default_rng(5)
        x = (rng.normal(size=(NDEV, 1024)) * 0.1).astype(np.float32)
        x[3, 7] = np.inf
        _, rs = self._exact_and_rs(x)
        blk = mesh_lib.INT8_BLOCK
        assert np.isnan(rs[0][:blk]).all()
        assert np.isfinite(rs[0][blk:]).all()

    def test_non_float_leaves_psum_exactly(self):
        ints = np.arange(NDEV * 16, dtype=np.int32)
        out = _run_sync(lambda t: mesh_lib.int8_reduce_scatter(
            t, NDEV, "data"), ints)
        np.testing.assert_array_equal(out[0],
                                      ints.reshape(NDEV, -1).sum(0))

    def test_padding_preserves_shape_and_tail(self):
        """A leaf whose size doesn't divide block * ndev round-trips at
        its own shape with the tail synced correctly (the pad is
        internal)."""
        rng = np.random.default_rng(6)
        x = (rng.normal(size=(NDEV, 333)) * 0.05).astype(np.float32)
        exact, rs = self._exact_and_rs(x)
        assert rs.shape[1] == 333
        assert np.abs(rs[0] - exact).max() < 0.05


@pytest.mark.usefixtures("collective_lock")
class TestRingPrimitives:
    def test_ring_shift_rotates_right_and_closes(self):
        mesh = mesh_lib.make_mesh()
        x = np.arange(NDEV * 4, dtype=np.float32)

        def body(v):
            one = mesh_lib.ring_shift(v, NDEV)
            closed = one
            for _ in range(NDEV - 1):
                closed = mesh_lib.ring_shift(closed, NDEV)
            return one, closed

        one, closed = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("data"),),
            out_specs=(P("data"), P("data")), check_vma=False))(
                jnp.asarray(x))
        # One shift: shard i holds shard i-1's block (right rotation).
        np.testing.assert_array_equal(np.asarray(one),
                                      np.roll(x.reshape(NDEV, 4), 1,
                                              axis=0).reshape(-1))
        # ndev shifts: home again — the every-block-exactly-once closure
        # the column scans rely on.
        np.testing.assert_array_equal(np.asarray(closed), x)

    def test_owner_rows_scattered_exact_slices(self):
        """Each shard receives ITS K/ndev slice of the owner-gathered
        rows, bit-exact (zeros + the owner's value), with unowned
        (sentinel) ids coming back as zero rows."""
        mesh = mesh_lib.make_mesh()
        rng = np.random.default_rng(7)
        arr = rng.normal(size=(NDEV * 4, 3)).astype(np.float32)
        ids = np.asarray([5, 31, 0, 17, 22, 9, 30, 2,
                          11, 4, 28, 3, 19, 7, 32, 32], np.int32)

        def body(a, i):
            return mesh_lib.owner_rows_scattered(a, i, "data")

        out = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("data", None), P()),
            out_specs=P("data", None), check_vma=False))(
                jnp.asarray(arr), jnp.asarray(ids))
        got = np.asarray(out)
        want = np.where((ids < NDEV * 4)[:, None], arr[np.minimum(
            ids, NDEV * 4 - 1)], 0.0).astype(np.float32)
        np.testing.assert_array_equal(got, want)

    def test_ring_center_layout(self):
        cidx, cvalid = scoring.ring_center_layout(
            np.asarray([3, 9, 40]), sentinel=512, ndev=8, floor=64)
        assert len(cidx) == len(cvalid) and len(cidx) % 8 == 0
        assert len(cidx) >= 64
        np.testing.assert_array_equal(cidx[:3], [3, 9, 40])
        assert (cidx[3:] == 512).all()
        np.testing.assert_array_equal(cvalid[:3], [1.0, 1.0, 1.0])
        assert (cvalid[3:] == 0).all()
        # Bucketed: two labeled counts inside one bucket share a layout
        # length (compile reuse round over round).
        a, _ = scoring.ring_center_layout(np.arange(20), 512, 8)
        b, _ = scoring.ring_center_layout(np.arange(800), 512, 8)
        assert len(a) == len(b)

    def test_ring_feed_attribution(self):
        """kcenter_greedy publishes whether the ring feed ran — the
        bench rider's source of truth."""
        rng = np.random.default_rng(8)
        emb = rng.normal(size=(64, 4)).astype(np.float32)
        labeled = np.zeros(64, dtype=bool)
        labeled[:5] = True
        kc.kcenter_greedy((emb,), labeled, 5,
                          rng=np.random.default_rng(1),
                          pool_sharding="replicated")
        assert kc.LAST_RING_FEED is False
        kc.kcenter_greedy((emb,), labeled, 5,
                          rng=np.random.default_rng(1),
                          mesh=mesh_lib.make_mesh(), pool_sharding="row")
        assert kc.LAST_RING_FEED is True


class TestBatchScaling:
    def test_identity_at_scale_one(self):
        from active_learning_tpu.train.optim import apply_batch_scaling
        cfg = tiny_train_config()
        out, changed = apply_batch_scaling(cfg, 1)
        assert out is cfg and not changed

    def test_linear_rules_at_scale_eight(self):
        from active_learning_tpu.config import SchedulerConfig
        from active_learning_tpu.train.optim import apply_batch_scaling
        cfg = dataclasses.replace(
            tiny_train_config(batch_size=32),
            scheduler=SchedulerConfig(name="cosine", t_max=40,
                                      warmup_epochs=0))
        out, changed = apply_batch_scaling(cfg, 8)
        assert changed
        assert out.loader_tr.batch_size == 256
        assert out.optimizer.lr == pytest.approx(cfg.optimizer.lr * 8)
        assert out.scheduler.warmup_epochs == 5
        # A pre-configured LONGER warmup is never shortened.
        cfg2 = dataclasses.replace(
            cfg, scheduler=SchedulerConfig(name="cosine", t_max=40,
                                           warmup_epochs=9))
        out2, _ = apply_batch_scaling(cfg2, 8)
        assert out2.scheduler.warmup_epochs == 9

    def test_warmup_clamped_below_t_max(self):
        """A short schedule must not get a warmup _cosine_lr rejects
        (warm >= t_max raises)."""
        from active_learning_tpu.config import SchedulerConfig
        from active_learning_tpu.train.optim import (apply_batch_scaling,
                                                     make_lr_schedule)
        cfg = dataclasses.replace(
            tiny_train_config(),
            scheduler=SchedulerConfig(name="cosine", t_max=3,
                                      warmup_epochs=0))
        out, _ = apply_batch_scaling(cfg, 8)
        assert out.scheduler.warmup_epochs < out.scheduler.t_max
        make_lr_schedule(out.scheduler, out.optimizer.lr)  # must not raise

    def test_step_schedule_keeps_milestones(self):
        from active_learning_tpu.config import SchedulerConfig
        from active_learning_tpu.train.optim import apply_batch_scaling
        cfg = dataclasses.replace(
            tiny_train_config(),
            scheduler=SchedulerConfig(name="step", step_size=30,
                                      gamma=0.2))
        out, changed = apply_batch_scaling(cfg, 4)
        assert changed and out.scheduler == cfg.scheduler

    def test_driver_rejects_unknown_mode(self, tmp_path):
        from active_learning_tpu.config import ExperimentConfig
        from active_learning_tpu.experiment.driver import build_experiment
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.experiment import arg_pools  # noqa: F401
        cfg = ExperimentConfig(dataset="synthetic", arg_pool="synthetic",
                               scale_batch="always",
                               log_dir=str(tmp_path),
                               ckpt_path=str(tmp_path))
        data = get_data_synthetic(n_train=32, n_test=16)
        with pytest.raises(ValueError, match="scale_batch"):
            build_experiment(cfg, data=data,
                             train_cfg=tiny_train_config(),
                             model=TinyClassifier(num_classes=4))


@pytest.mark.usefixtures("collective_lock")
class TestReduceScatterGating:
    def test_probe_passes_on_reduce_scatter_form(self):
        """The learning probe actually trains through the reduce-scatter
        step when the run requests it (int8_rs forces the form on the
        8-device mesh) and lands inside the pinned accuracy bound."""
        from active_learning_tpu.experiment import driver
        ok, delta = driver.run_grad_allreduce_probe(
            mesh_lib.make_mesh(), "int8_rs")
        assert ok, f"reduce-scatter probe failed (delta {delta})"
        assert delta is not None \
            and delta <= driver.INT8_PROBE_MAX_ACC_DELTA

    def test_trainer_resolves_wire_form(self):
        mesh = mesh_lib.make_mesh()
        from active_learning_tpu.train.trainer import Trainer
        t_rs = Trainer(TinyClassifier(),
                       dataclasses.replace(tiny_train_config(),
                                           grad_allreduce="int8_rs"),
                       mesh, 4)
        assert t_rs.grad_allreduce == "int8"
        assert t_rs.grad_sync_form == "reduce_scatter"
        t_ag = Trainer(TinyClassifier(),
                       dataclasses.replace(tiny_train_config(),
                                           grad_allreduce="int8"),
                       mesh, 4)
        assert t_ag.grad_sync_form == "allgather"
        t_f32 = Trainer(TinyClassifier(), tiny_train_config(), mesh, 4)
        assert t_f32.grad_sync_form is None

    def test_probe_failure_degrades_reduce_scatter_to_f32(self, tmp_path):
        """Chaos case (the grad_probe contract, extended to the new
        path): --grad_allreduce int8_rs with a broken probe completes
        on the bit-exact f32 sync — experiment_state identical to the
        f32 baseline — with the degrade journaled (the same sticky
        record a resume honors)."""
        from active_learning_tpu import faults
        from active_learning_tpu.config import (ExperimentConfig,
                                                TelemetryConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.experiment import arg_pools  # noqa: F401
        from active_learning_tpu.experiment.driver import run_experiment

        data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                                  image_size=8, seed=5)

        def run(sub, **over):
            d = os.path.join(str(tmp_path), sub)
            cfg = ExperimentConfig(
                dataset="synthetic", arg_pool="synthetic",
                strategy="MarginSampler", rounds=2, round_budget=8,
                n_epoch=2, early_stop_patience=2, log_dir=d,
                ckpt_path=d, exp_hash=sub, round_pipeline="off",
                telemetry=TelemetryConfig(enabled=False), **over)
            run_experiment(cfg, data=data,
                           train_cfg=tiny_train_config(),
                           model=TinyClassifier(num_classes=4))
            state = dict(np.load(glob.glob(os.path.join(
                d, "*", "experiment_state.npz"))[0]))
            return d, state

        _, baseline = run("f32base")
        d, degraded = run("rsfault", grad_allreduce="int8_rs",
                          fault_spec="grad_probe:raise@1")
        for k in baseline:
            np.testing.assert_array_equal(baseline[k], degraded[k])
        jr = faults.read_journal(os.path.join(d, faults.JOURNAL_FILE))
        assert jr["status"] == "finished"
        assert jr["grad_allreduce"] == "f32_degraded"


@pytest.mark.usefixtures("collective_lock")
class TestReduceScatterCompileReuse:
    def test_warm_rounds_zero_new_compiles_under_int8_rs(self, tmp_path):
        """The acceptance's every-new-path compile-freeness, on the
        reduce-scatter wire: 3 driver rounds under grad_allreduce=
        int8_rs (+ row sharding + ring feed via the default auto
        layout), rounds 1-2 at jit cache-miss delta 0 — probe and ring
        compiles all land in round 0's cold tax."""
        from active_learning_tpu.config import (ExperimentConfig,
                                                TelemetryConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.experiment import arg_pools  # noqa: F401
        from active_learning_tpu.experiment.driver import run_experiment
        from active_learning_tpu.utils.metrics import JsonlSink

        tmp = str(tmp_path)
        cfg = ExperimentConfig(
            dataset="synthetic", arg_pool="synthetic",
            strategy="CoresetSampler", rounds=3, round_budget=8,
            n_epoch=2, early_stop_patience=2, log_dir=tmp, ckpt_path=tmp,
            exp_hash="rswarm", round_pipeline="off",
            grad_allreduce="int8_rs",
            telemetry=TelemetryConfig(enabled=True,
                                      heartbeat_every_s=0.0))
        data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                                  image_size=8, seed=5)
        strategy = run_experiment(
            cfg, sink=JsonlSink(tmp, experiment_key="rswarm"),
            data=data, train_cfg=tiny_train_config(),
            model=TinyClassifier(num_classes=4))
        assert strategy.trainer.grad_allreduce == "int8"
        assert strategy.trainer.grad_sync_form == "reduce_scatter"
        assert not strategy.trainer.grad_allreduce_degraded
        assert kc.LAST_RING_FEED is True  # coreset ran the ring feed
        deltas = {}
        with open(os.path.join(tmp, "metrics.jsonl")) as fh:
            for line in fh:
                ev = json.loads(line)
                if (ev.get("kind") == "metric"
                        and "jit_cache_miss_delta" in ev.get("metrics",
                                                             {})):
                    deltas[ev.get("step")] = \
                        ev["metrics"]["jit_cache_miss_delta"]
        assert set(deltas) == {0, 1, 2}
        assert deltas[0] > 0
        for rd in (1, 2):
            assert deltas[rd] == 0, (
                f"warm round {rd} compiled under int8_rs + ring feed: "
                f"{deltas[rd]} jit cache misses")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


HARNESS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "pod_harness.py")


def _spawn(cfg: dict) -> subprocess.Popen:
    env = dict(os.environ)
    # The child pins its OWN platform/device-count env before importing
    # jax; the conftest's 8-device flags must not leak in.
    env.pop("XLA_FLAGS", None)
    return subprocess.Popen(
        [sys.executable, HARNESS, json.dumps(cfg)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)


def _state(ckpt_path: str) -> dict:
    paths = glob.glob(os.path.join(ckpt_path, "*",
                                   "experiment_state.npz"))
    assert len(paths) == 1, paths
    return dict(np.load(paths[0]))


@pytest.mark.slow
class TestTwoProcessPod:
    """The pod acceptance: a REAL 2-process mesh (2 hosts x 2 devices
    over localhost DCN, gloo CPU collectives) runs the PRODUCTION
    driver end to end — row-sharded pool with per-process shard
    assembly, collective k-center with the ring column feed, the
    full fit/eval stack — and its experiment_state is bit-identical
    to the single-process 4-device run at the same seeds."""

    @pytest.mark.parametrize("strategy", ["MarginSampler",
                                          "CoresetSampler"])
    def test_two_process_state_bit_identical(self, tmp_path, strategy):
        base = str(tmp_path)
        sp_dir = os.path.join(base, "sp")
        mp_dir = os.path.join(base, "mp")
        os.makedirs(sp_dir)
        os.makedirs(mp_dir)
        port = _free_port()
        common = {"strategy": strategy, "exp_hash": "podtier"}
        sp = _spawn(dict(common, log_dir=sp_dir, ckpt_path=sp_dir,
                         local_devices=4))
        procs = [
            _spawn(dict(common, log_dir=mp_dir, ckpt_path=mp_dir,
                        local_devices=2,
                        coordinator=f"127.0.0.1:{port}",
                        num_processes=2, process_id=pid))
            for pid in (0, 1)
        ]
        outs = []
        for p in [sp] + procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
        for p, out in zip([sp] + procs, outs):
            assert p.returncode == 0, out[-3000:]
            assert "POD_HARNESS_OK" in out, out[-3000:]
        sp_state = _state(sp_dir)
        mp_state = _state(mp_dir)
        assert set(sp_state) == set(mp_state)
        for k in sp_state:
            np.testing.assert_array_equal(
                sp_state[k], mp_state[k],
                err_msg=f"experiment_state[{k!r}] diverged between the "
                        "2-process pod and the single-process run")
