"""Recompile-count regression tests for the shape-bucketing scheme.

The AL round loop's shapes drift every round — the labeled set grows, a
subset-capped selection pool shrinks — and every drifted shape is a
fresh XLA compile unless it is bucketed away (pool.bucket_size).  These
tests pin the contract: two consecutive rounds whose sizes stay inside
one bucket trigger ZERO new jit compilations, measured directly off the
jitted functions' compilation caches.
"""

import os

import numpy as np
import pytest

import jax

from active_learning_tpu.pool import bucket_size


def _cache_size(jitted) -> int:
    return jitted._cache_size()


class TestBucketSize:
    def test_values(self):
        assert bucket_size(1, floor=16) == 16
        assert bucket_size(16, floor=16) == 16
        assert bucket_size(17, floor=16) == 32
        assert bucket_size(300) == 512
        assert bucket_size(512) == 512
        # 1/8-octave granularity, NOT pure pow2: past a boundary the
        # bucket grows by the granule (256 here), not by doubling —
        # padded pool rows still execute (and padded epoch steps are
        # still uploaded as index rows), so waste must stay bounded.
        assert bucket_size(513) == 768
        assert bucket_size(130000) == 131072

    def test_monotone_and_bounded_waste(self):
        for n in (1, 7, 255, 256, 1000, 4097, 70000, 130000):
            b = bucket_size(n)
            assert b >= n and b >= 256
            if n > 256:
                # Recurring-compute waste cap: granule is 1/8 of the
                # enclosing pow2, so padding < ~14% of n.
                assert b - n < max(256, b // 4)
                assert b < 2 * max(n, 256)


class TestKCenterCompileReuse:
    def _run(self, n, n_labeled, budget, seed=0, batch_q=8):
        from active_learning_tpu.strategies.kcenter import kcenter_greedy
        rng = np.random.default_rng(seed)
        emb = rng.normal(size=(n, 24)).astype(np.float32)
        labeled = np.zeros(n, dtype=bool)
        labeled[rng.choice(n, n_labeled, replace=False)] = True
        picks = kcenter_greedy((emb,), labeled, budget,
                               rng=np.random.default_rng(1),
                               batch_q=batch_q)
        assert len(picks) == budget

    def test_grown_pool_same_bucket_zero_new_compiles(self):
        """Round N -> N+1 with a drifted pool size and a grown labeled
        set, both inside one power-of-two bucket: the selection scan AND
        the chunked initial-min pass reuse their executables."""
        from active_learning_tpu.strategies import kcenter as kc

        self._run(300, 20, 10)  # pool bucket 512, warm
        scan = _cache_size(kc._kcenter_scan_batched)
        chunk = _cache_size(kc._min_dist_chunk)
        self._run(340, 50, 10, seed=5)  # grown; same 512 bucket
        assert _cache_size(kc._kcenter_scan_batched) == scan
        assert _cache_size(kc._min_dist_chunk) == chunk

    def test_bucket_boundary_recompiles_once(self):
        from active_learning_tpu.strategies import kcenter as kc

        self._run(300, 20, 10)
        scan = _cache_size(kc._kcenter_scan_batched)
        self._run(600, 20, 10, seed=6)  # crosses into the 1024 bucket
        assert _cache_size(kc._kcenter_scan_batched) == scan + 1


class TestShardedKCenterCompileReuse:
    """The row-sharded selection backend under the same bucket contract:
    warm AL rounds (drifted pool size, grown labeled set, same bucket)
    add ZERO compiles to the per-mesh sharded executables."""

    def _run(self, mesh, n, n_labeled, budget, seed=0, batch_q=8):
        from active_learning_tpu.strategies import kcenter as kc
        rng = np.random.default_rng(seed)
        emb = rng.normal(size=(n, 24)).astype(np.float32)
        labeled = np.zeros(n, dtype=bool)
        labeled[rng.choice(n, n_labeled, replace=False)] = True
        picks = kc.kcenter_greedy((emb,), labeled, budget,
                                  rng=np.random.default_rng(1),
                                  batch_q=batch_q, mesh=mesh,
                                  pool_sharding="row")
        assert kc.LAST_SHARDING == "row"
        assert len(picks) == budget

    def test_grown_pool_same_bucket_zero_new_compiles(self):
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.strategies import kcenter as kc

        mesh = mesh_lib.make_mesh()
        self._run(mesh, 300, 20, 10)  # pool bucket 512, warm
        fns = kc._SHARDED_JITS[(mesh, 1)]
        sizes = {k: _cache_size(v) for k, v in fns.items()}
        self._run(mesh, 340, 50, 10, seed=5)  # grown; same 512 bucket
        assert {k: _cache_size(v) for k, v in fns.items()} == sizes

    def test_bucket_boundary_recompiles_scan_once(self):
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.strategies import kcenter as kc

        mesh = mesh_lib.make_mesh()
        self._run(mesh, 300, 20, 10)
        fns = kc._SHARDED_JITS[(mesh, 1)]
        scan = _cache_size(fns["scan_batched"])
        self._run(mesh, 600, 20, 10, seed=6)  # crosses into 1024
        assert _cache_size(fns["scan_batched"]) == scan + 1


class TestEpochScanCompileReuse:
    @pytest.mark.parametrize("first,grown", [(24, 60), (8, 90), (90, 24)])
    def test_two_rounds_grown_labeled_zero_new_compiles(self, first, grown):
        """The device-resident epoch scan across two AL 'rounds' whose
        labeled sets differ but land in the same step bucket compiles
        exactly once: the real step count is a value of the program
        (it runs 2, then 4 steps of the 16-step shape; 1 then 6; 6 then
        2), never a shape."""
        from helpers import TinyClassifier, tiny_train_config
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.train.trainer import Trainer
        import dataclasses

        train_set, _, al_set = get_data_synthetic(n_train=96, n_test=16)
        cfg = dataclasses.replace(tiny_train_config(batch_size=16),
                                  device_resident=True)
        mesh = mesh_lib.make_mesh()
        trainer = Trainer(TinyClassifier(), cfg, mesh, 4)

        def fit_round(n_labeled, seed):
            # Fresh state per round, as the driver's init_network_weights
            # does (the fitted state's buffers are donated into the scan).
            state = trainer.init_state(jax.random.PRNGKey(seed),
                                       train_set.gather(np.arange(2)))
            rng = np.random.default_rng(seed)
            labeled = np.sort(rng.choice(96, n_labeled, replace=False))
            return trainer.fit(state, train_set, labeled, al_set,
                               np.arange(90, 96), n_epoch=2, es_patience=0,
                               rng=rng, round_idx=0)

        res = fit_round(first, 0)  # e.g. 2 steps -> the 16-step floor bucket
        assert trainer._epoch_scan is not None
        assert trainer.last_feed["form"] == "scan"
        assert int(res.state.step) == 2 * -(-first // 16)
        scans = _cache_size(trainer._epoch_scan)
        steps = _cache_size(trainer._train_step)
        res = fit_round(grown, 1)  # another real step count, same bucket
        assert int(res.state.step) == 2 * -(-grown // 16)
        assert _cache_size(trainer._epoch_scan) == scans
        assert _cache_size(trainer._train_step) == steps

    def test_bucket_steps_rule(self):
        from active_learning_tpu.train.trainer import Trainer

        assert Trainer.bucket_steps(1) == Trainer.STEP_BUCKET
        assert Trainer.bucket_steps(16) == 16
        assert Trainer.bucket_steps(17) == 32
        assert Trainer.bucket_steps(33) == 48
        assert Trainer.bucket_steps(64) == 64
        # The case the pure-pow2 rule got wrong: 157 steps must not be
        # shaped as 256 (99 padded index rows an epoch), only as 160.
        assert Trainer.bucket_steps(157) == 160


class TestShardedFeedCompileReuse:
    def test_warm_rounds_on_row_sharded_feed_zero_new_compiles(self):
        """Warm AL rounds under row sharding add zero XLA compiles: the
        pool entry (constant shape) and the sharded per-batch step are
        both reused round over round — the jit-cache delta invariant of
        test_telemetry, pinned directly on the executables here."""
        import dataclasses
        from helpers import TinyClassifier, tiny_train_config
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib
        from active_learning_tpu.train.trainer import Trainer

        train_set, _, al_set = get_data_synthetic(n_train=96, n_test=16)
        cfg = dataclasses.replace(tiny_train_config(batch_size=16),
                                  train_feed="resident",
                                  pool_sharding="row")
        mesh = mesh_lib.make_mesh()
        trainer = Trainer(TinyClassifier(), cfg, mesh, 4)
        assert trainer.pool_sharding == "row"

        def fit_round(n_labeled, seed):
            state = trainer.init_state(jax.random.PRNGKey(seed),
                                       train_set.gather(np.arange(2)))
            rng = np.random.default_rng(seed)
            labeled = np.sort(rng.choice(96, n_labeled, replace=False))
            return trainer.fit(state, train_set, labeled, al_set,
                               np.arange(90, 96), n_epoch=2,
                               es_patience=0, rng=rng)

        fit_round(24, 0)  # round N: pins the pool, compiles the step
        assert trainer.last_feed["source"] == "resident"
        assert resident_lib.pinned_bytes(trainer.resident_pool) > 0
        step = _cache_size(trainer._resident_batch_step)
        entries = len(trainer.resident_pool["images"])
        fit_round(60, 1)  # round N+1: grown labeled set, same pool
        assert trainer.last_feed["source"] == "resident"
        assert _cache_size(trainer._resident_batch_step) == step
        assert len(trainer.resident_pool["images"]) == entries


class TestResidentBudgetDemotion:
    def test_mid_run_shrink_demotes_cleanly_without_recompile(self):
        """Budget-sharing: shrinking the resident budget mid-run demotes
        the pinned pool LRU-first (parallel/resident.enforce_budget) and
        the NEXT fit falls back to the host feed — with no batch-shape
        change and ZERO new XLA compiles, because the host step was
        already compiled at the same bucketed shapes."""
        import dataclasses
        from helpers import TinyClassifier, tiny_train_config
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib
        from active_learning_tpu.train.trainer import Trainer

        train_set, _, al_set = get_data_synthetic(n_train=96, n_test=16)
        cfg = dataclasses.replace(tiny_train_config(batch_size=16),
                                  train_feed="auto", device_resident=None)
        mesh = mesh_lib.make_mesh()
        trainer = Trainer(TinyClassifier(), cfg, mesh, 4)

        def fit_round(seed, feed=None):
            c = cfg if feed is None else dataclasses.replace(
                cfg, train_feed=feed)
            trainer.cfg = c
            state = trainer.init_state(jax.random.PRNGKey(seed),
                                       train_set.gather(np.arange(2)))
            rng = np.random.default_rng(seed)
            labeled = np.sort(rng.choice(96, 60, replace=False))
            return trainer.fit(state, train_set, labeled, al_set,
                               np.arange(90, 96), n_epoch=2,
                               es_patience=0, rng=rng)

        fit_round(0, feed="host")      # warm the host step's executable
        fit_round(1, feed="resident")  # pin + warm the resident step
        assert trainer.last_feed["source"] == "resident"
        assert resident_lib.pinned_bytes(trainer.resident_pool) > 0
        chained = _cache_size(trainer._chained_train_step)
        resident_step = _cache_size(trainer._resident_batch_step)

        demoted = trainer.set_resident_budget(1)  # mid-run shrink
        assert demoted and not trainer.resident_pool.get("images")

        fit_round(2)  # auto now resolves down the hierarchy
        assert trainer.last_feed["source"].startswith("host")
        # No shape change, no recompile: both executables' caches are
        # exactly where the warm-up left them.
        assert _cache_size(trainer._chained_train_step) == chained
        assert _cache_size(trainer._resident_batch_step) == resident_step

    def test_shared_budget_accounting_and_lru_order(self):
        """eligible() charges the WHOLE cache against one budget, the
        al/train views' shared storage counts once, and eviction walks
        least-recently-used first."""
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib

        train_set, test_set, al_set = get_data_synthetic(
            n_train=64, n_test=64, num_classes=4, image_size=8)
        mesh = mesh_lib.make_mesh()
        cache = {}
        resident_lib.pool_arrays(cache, al_set, mesh)
        one = resident_lib.pinned_bytes(cache)
        assert one == al_set.images[:64].nbytes
        # The train view shares storage: same entry, same bytes.
        resident_lib.pool_arrays(cache, train_set, mesh)
        assert resident_lib.pinned_bytes(cache) == one
        # A second array is only eligible if it fits ALONGSIDE the first.
        assert resident_lib.eligible(test_set, 2 * one, cache=cache)
        assert not resident_lib.eligible(test_set, one + 1, cache=cache)
        # An already-pinned pool stays eligible under any budget.
        assert resident_lib.eligible(al_set, 1, cache=cache)
        resident_lib.pool_arrays(cache, test_set, mesh)
        # Touch the al pool so the TEST set is now least-recently-used.
        resident_lib.pool_arrays(cache, al_set, mesh)
        demoted = resident_lib.enforce_budget(cache, one)
        assert demoted == [(id(test_set.images), 64)]
        assert resident_lib.cached(cache, al_set)
        assert not resident_lib.cached(cache, test_set)

    def test_auto_budget_adds_pinned_back_as_total_cap(self):
        """A live-headroom auto budget has already-pinned pools netted
        OUT of bytes_in_use's headroom; the shared eligible() accounting
        charges them against the budget as a TOTAL cap, so the refresh
        must add them back — otherwise every pinned pool is billed
        twice and a second pool that actually fits gets rejected."""
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib

        _, test_set, al_set = get_data_synthetic(
            n_train=64, n_test=64, num_classes=4, image_size=8)
        cache = {}
        resident_lib.pool_arrays(cache, al_set, mesh_lib.make_mesh())
        pinned = resident_lib.pinned_bytes(cache)
        need = test_set.images[:64].nbytes
        reserve = resident_lib.AUTO_RESERVE_BYTES
        # Live stats where headroom (net of the pinned pool) covers the
        # second pool exactly: bytes_in_use INCLUDES the pinned bytes.
        stats = {"bytes_limit": reserve + pinned + need + 1024,
                 "bytes_in_use": pinned}
        budget = resident_lib.resolve_budget(None, stats=stats,
                                             cache=cache)
        # Total cap = headroom + pinned, so the second pool is eligible
        # alongside the first under the shared accounting.
        assert budget == need + 1024 + pinned
        assert resident_lib.eligible(test_set, budget, cache=cache)
        # Without the add-back the same scenario double-counts and
        # rejects it.
        assert not resident_lib.eligible(
            test_set, resident_lib.resolve_budget(None, stats=stats),
            cache=cache)


class TestPipelinedRoundCompileReuse:
    def test_warm_pipelined_rounds_zero_new_compiles(self, tmp_path):
        """The pipelined round's compile-freeness (DESIGN.md §8): the
        speculative scorer dispatches THE SAME jitted score step the
        sequential query uses (over batch-constant chunk shapes), and
        the select-time prefetch pre-builds the very execution form the
        fit would build — so warm pipelined rounds add ZERO compiles.
        3 rounds so round 1 is a fully-warm ARMING round: it consumes
        round 0's speculation, runs the scorer through its own fit, and
        prefetches round 2's feed — the whole pipeline surface, jit
        delta 0 (the same registry-counted metric the production driver
        exports)."""
        import json
        import os

        from active_learning_tpu.config import (ExperimentConfig,
                                                TelemetryConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.experiment import arg_pools  # noqa: F401
        from active_learning_tpu.experiment.driver import run_experiment
        from active_learning_tpu.utils.metrics import JsonlSink

        from helpers import TinyClassifier, tiny_train_config

        tmp = str(tmp_path)
        cfg = ExperimentConfig(
            dataset="synthetic", arg_pool="synthetic",
            strategy="MarginSampler", rounds=3, round_budget=8,
            n_epoch=2, early_stop_patience=2, log_dir=tmp, ckpt_path=tmp,
            exp_hash="pipewarm", round_pipeline="speculative",
            telemetry=TelemetryConfig(enabled=True,
                                      heartbeat_every_s=0.0))
        data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                                  image_size=8, seed=5)
        strategy = run_experiment(
            cfg, sink=JsonlSink(tmp, experiment_key="pipewarm"),
            data=data, train_cfg=tiny_train_config(),
            model=TinyClassifier(num_classes=4))
        assert strategy.pipeline is not None
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
        assert deltas[0] > 0  # round 0 pays the cold compiles ...
        for rd in (1, 2):  # ... and warm pipelined rounds pay none.
            assert deltas[rd] == 0, (
                f"warm pipelined round {rd} compiled: "
                f"{deltas[rd]} jit cache misses")


class TestGradPathCompileReuse:
    def test_warm_rounds_zero_new_compiles_under_all_new_flags(
            self, tmp_path):
        """ISSUE 10's compile-freeness acceptance: the fused donated
        optimizer (bf16 momentum), the donated round-boundary reinit,
        AND the int8 quantized gradient sync together — 3 driver rounds
        on the multi-device CPU mesh, rounds 1-2 at jit delta 0 (the
        same registry-counted metric the production driver exports).
        The int8 learning probe runs inside round 0's cold window, so
        its compiles land in the cold tax, never the warm rounds."""
        import json
        import os

        from active_learning_tpu.config import (ExperimentConfig,
                                                TelemetryConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.experiment import arg_pools  # noqa: F401
        from active_learning_tpu.experiment.driver import run_experiment
        from active_learning_tpu.utils.metrics import JsonlSink

        from helpers import TinyClassifier, tiny_train_config

        tmp = str(tmp_path)
        cfg = ExperimentConfig(
            dataset="synthetic", arg_pool="synthetic",
            strategy="MarginSampler", rounds=3, round_budget=8,
            n_epoch=2, early_stop_patience=2, log_dir=tmp, ckpt_path=tmp,
            exp_hash="gradwarm", round_pipeline="off",
            fused_optimizer="on", optim_state_dtype="bf16",
            grad_allreduce="int8",
            telemetry=TelemetryConfig(enabled=True,
                                      heartbeat_every_s=0.0))
        data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                                  image_size=8, seed=5)
        strategy = run_experiment(
            cfg, sink=JsonlSink(tmp, experiment_key="gradwarm"),
            data=data, train_cfg=tiny_train_config(),
            model=TinyClassifier(num_classes=4))
        assert strategy.trainer.fused_tx is not None
        assert strategy.trainer.grad_allreduce == "int8"
        assert not strategy.trainer.grad_allreduce_degraded
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
        assert deltas[0] > 0  # cold round pays the compiles ...
        for rd in (1, 2):  # ... warm rounds pay none, under every flag.
            assert deltas[rd] == 0, (
                f"warm round {rd} compiled under the gradient-path "
                f"flags: {deltas[rd]} jit cache misses")


class TestStreamExtentCompileReuse:
    def test_appended_extents_keep_warm_round_delta_zero(self, tmp_path):
        """ISSUE 14's zero-new-compiles acceptance: a streaming run that
        ingests rows BETWEEN rounds recompiles at most once per extent
        boundary, never once per append.  Round 1 may pay the growth
        tax (the pool crosses from its base length onto the extent
        ladder, plus the first drift probe and first query); rounds 2-3
        ingest MORE rows inside the same extent and must land at jit
        cache-miss delta 0 — the same registry-counted metric the
        production driver exports."""
        import base64
        import http.client
        import json
        import os
        import signal
        import threading
        import time

        from helpers import TinyClassifier, tiny_train_config
        from active_learning_tpu.config import (ExperimentConfig,
                                                StreamConfig,
                                                TelemetryConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.faults import preempt as preempt_lib
        from active_learning_tpu.stream.service import StreamService
        from active_learning_tpu.utils.metrics import JsonlSink

        tmp = str(tmp_path)
        cfg = ExperimentConfig(
            dataset="synthetic", arg_pool="synthetic",
            strategy="MarginSampler", rounds=4, round_budget=8,
            n_epoch=2, early_stop_patience=2, log_dir=tmp, ckpt_path=tmp,
            exp_hash="streamwarm", round_pipeline="off",
            telemetry=TelemetryConfig(enabled=True,
                                      heartbeat_every_s=0.0))
        # Floor 64: the first 8-row append grows the 96-row base onto
        # the 128-slot extent; the next two appends stay INSIDE it.
        scfg = StreamConfig(port=0, max_rounds=4, watermark_rows=8,
                            drift_psi=0.0, max_interval_s=0.0,
                            poll_s=0.02, extent_floor=64)
        data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                                  image_size=8, seed=5)
        svc = StreamService(cfg, scfg,
                            sink=JsonlSink(tmp,
                                           experiment_key="streamwarm"),
                            data=data, train_cfg=tiny_train_config(),
                            model=TinyClassifier(num_classes=4))
        box = {}

        def run():
            try:
                box["strategy"] = svc.run()
            except BaseException as e:  # noqa: BLE001 - re-raised below
                box["err"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        try:
            assert svc.ready.wait(240)

            def post_rows(n, seed):
                rng = np.random.default_rng(seed)
                rows = rng.integers(0, 256, size=(n, 8, 8, 3),
                                    dtype=np.uint8)
                body = json.dumps({
                    "rows_b64":
                        base64.b64encode(rows.tobytes()).decode(),
                    "shape": [n, 8, 8, 3],
                    "labels": [int(i) % 4 for i in range(n)]}).encode()
                conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                                  timeout=30)
                try:
                    conn.request("POST", "/v1/pool", body=body)
                    assert conn.getresponse().status == 200
                finally:
                    conn.close()

            # One 8-row append between every pair of rounds: each lands
            # in its own drain (watermark 8 fires the next round).
            for prev_rounds, seed in ((1, 20), (2, 21), (3, 22)):
                deadline = time.monotonic() + 240
                while svc.rounds_run < prev_rounds and t.is_alive() \
                        and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert svc.rounds_run >= prev_rounds, (
                    f"round {prev_rounds - 1} never completed")
                post_rows(8, seed)
            t.join(timeout=300)
            assert not t.is_alive()
            if "err" in box:
                raise box["err"]
        finally:
            if t.is_alive():
                preempt_lib._handler(signal.SIGTERM, None)
                t.join(timeout=60)
        strategy = box["strategy"]
        assert svc.store.n_rows == 96 + 24
        assert strategy.pool.n_pool == 128  # ONE extent, three appends
        # The streaming-aware run report (ISSUE 15 satellite): every
        # round left a row joined by its stream block — trigger cause,
        # ingest totals — renderable by the `report` verb.
        with open(os.path.join(tmp, "run_report.json")) as fh:
            report = json.load(fh)
        assert report.get("stream") is True
        rows = report["rounds"]
        assert [r["round"] for r in rows] == [0, 1, 2, 3]
        causes = [r["stream"]["trigger_cause"] for r in rows]
        assert causes[0] == "bootstrap"
        assert all(c == "watermark" for c in causes[1:])
        assert rows[-1]["stream"]["ingest_rows_total"] == 24
        deltas = {}
        with open(os.path.join(tmp, "metrics.jsonl")) as fh:
            for line in fh:
                ev = json.loads(line)
                if (ev.get("kind") == "metric"
                        and "jit_cache_miss_delta" in ev.get("metrics",
                                                             {})):
                    deltas[ev.get("step")] = \
                        ev["metrics"]["jit_cache_miss_delta"]
        assert set(deltas) == {0, 1, 2, 3}
        assert deltas[0] > 0  # the cold round pays the compiles ...
        # Round 1 crosses the extent boundary (96 -> 128): at most one
        # retrace per grown executable, tolerated once per boundary.
        for rd in (2, 3):  # ... appends INSIDE the extent pay nothing.
            assert deltas[rd] == 0, (
                f"round {rd} compiled after an in-extent append: "
                f"{deltas[rd]} jit cache misses")


class TestCompilationCacheConfig:
    def test_driver_enables_persistent_cache(self, tmp_path, monkeypatch):
        from active_learning_tpu.experiment import driver

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        target = str(tmp_path / "xla_cache")
        old = jax.config.jax_compilation_cache_dir
        try:
            got = driver.enable_compilation_cache(target)
            assert got == target
            assert jax.config.jax_compilation_cache_dir == target
        finally:
            # Undo the process-wide config leak: the rest of the session
            # must keep running cache-less (the CPU default-off gate of
            # enable_compilation_cache, see conftest.py) — a leaked cache
            # dir here would turn the cache on for every later test.
            jax.config.update("jax_compilation_cache_dir", old)

    def test_empty_string_disables(self):
        from active_learning_tpu.experiment import driver

        assert driver.enable_compilation_cache("") is None

    @pytest.mark.parametrize("flag", [None, "flag_cache"])
    def test_environment_places_the_cache_and_nothing_else_is_set(
            self, tmp_path, monkeypatch, flag):
        """Where $JAX_COMPILATION_CACHE_DIR is set, that directory is the
        one in use whatever the flag says, and NO jax_compilation_cache_dir
        update is made (JAX read the variable itself at start-up)."""
        from active_learning_tpu.experiment import driver

        env_dir = str(tmp_path / "env_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        updates = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updates.append(k), real_update(k, v))[1])
        old_min = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            flag_dir = flag and str(tmp_path / flag)
            assert driver.resolve_compilation_cache_dir(flag_dir) == env_dir
            assert driver.enable_compilation_cache(flag_dir) == env_dir
            assert "jax_compilation_cache_dir" not in updates
        finally:
            real_update("jax_persistent_cache_min_compile_time_secs",
                        old_min)

    def test_unset_gives_the_fixed_in_checkout_path(self, monkeypatch):
        """No variable, no flag, an accelerator platform: ONE constant
        path inside the checkout — never a temporary name, pid or time
        (the path is part of the cache lookup; one that moves never
        hits)."""
        from active_learning_tpu.experiment import driver

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(driver, "_platform_is_cpu", lambda: False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert driver.DEFAULT_COMPILATION_CACHE_DIR == os.path.join(
            repo, ".jax_cache")
        # Resolution only: enabling it would turn the cache on for the
        # rest of this CPU test session.
        assert (driver.resolve_compilation_cache_dir(None)
                == driver.DEFAULT_COMPILATION_CACHE_DIR)
        assert (driver.resolve_compilation_cache_dir(None)
                == driver.resolve_compilation_cache_dir(None))

    def test_cpu_default_stays_off(self, monkeypatch):
        from active_learning_tpu.experiment import driver

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        old = jax.config.jax_compilation_cache_dir
        assert driver.resolve_compilation_cache_dir(None) is None
        assert driver.enable_compilation_cache(None) is None
        assert jax.config.jax_compilation_cache_dir == old
