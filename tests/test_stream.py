"""The streaming subsystem (active_learning_tpu/stream/, DESIGN.md §14).

Pinned here:
  * WAL durability: fsync'd append, torn-tail drop (never corruption),
    seq continuity across segments/restarts, rotation sealing, the
    wal_write fault site's torn injection;
  * the growable pool: bucket-aligned extent growth, PoolState
    grow/valid/invalid semantics and their (de)serialization;
  * ingest handlers: 400/413/429 admission semantics, WAL-before-ack
    behaviorally (seq advanced before the ack exists);
  * the trigger policy's decision table;
  * the HTTP service end to end (POST /v1/pool + /v1/label over a live
    loopback listener, driven by the loadgen's ingest mode);
  * THE equivalence pins: a zero-ingest stream run is bit-identical to
    the batch driver; ingest chunking (one big request vs many small)
    cannot change picks; chunked-incremental scoring over appended
    rows equals the monolithic pass bit for bit;
  * THE chaos pin: preemption mid-triggered-round -> resume completes
    with zero accepted-row loss and experiment_state bit-identical to
    the uninterrupted run;
  * stream gauges reach BOTH channels (metrics.jsonl + the Prometheus
    scrape, labeled trigger-cause samples included) and `status` grows
    the stream tail + the --strict exit-5 ingest-starved contract.
"""

import base64
import glob
import http.client
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from helpers import TinyClassifier, load_script, tiny_train_config

from active_learning_tpu import faults
from active_learning_tpu.config import (ExperimentConfig, StreamConfig,
                                        TelemetryConfig)
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.experiment.driver import (STREAM_GAUGES,
                                                   run_experiment)
from active_learning_tpu.faults import preempt as preempt_lib
from active_learning_tpu.pool import PoolState, bucket_size
from active_learning_tpu.stream import ingest as ingest_lib
from active_learning_tpu.stream import store as store_lib
from active_learning_tpu.stream.scheduler import TriggerPolicy
from active_learning_tpu.stream.service import StreamService
from active_learning_tpu.stream.wal import (IngestWAL, iter_payloads,
                                            replay_wal)
from active_learning_tpu.telemetry import prom as prom_lib
from active_learning_tpu.telemetry import status as status_lib
from active_learning_tpu.utils.metrics import JsonlSink, NullSink


def _rows(n, px=8, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(n, px, px, 3), dtype=np.uint8)


def _pool_record(rows, labels=None):
    rec = {"kind": "pool",
           "shape": [int(d) for d in rows.shape],
           "rows_b64": base64.b64encode(rows.tobytes()).decode(),
           "labels": labels}
    return rec


# ---------------------------------------------------------------------------
# WAL
# ---------------------------------------------------------------------------

class TestWAL:
    def test_append_replay_roundtrip_and_seq(self, tmp_path):
        d = str(tmp_path)
        wal = IngestWAL(d)
        rows = _rows(4)
        assert wal.append(_pool_record(rows, [0, 1, 2, 3])) == 1
        assert wal.append({"kind": "label", "ids": [1], "labels": [2]}) == 2
        wal.close()
        records, dropped = replay_wal(d)
        assert dropped == 0
        payloads = list(iter_payloads(records))
        assert [r["seq"] for r in payloads] == [1, 2]
        got, labels = store_lib.decode_pool_payload(payloads[0], (8, 8, 3))
        assert np.array_equal(got, rows) and labels == [0, 1, 2, 3]
        # Seq continues across restarts.
        wal2 = IngestWAL(d)
        assert wal2.append({"kind": "label", "ids": [0],
                            "labels": [1]}) == 3
        wal2.close()

    def test_torn_tail_dropped_never_served(self, tmp_path):
        d = str(tmp_path)
        wal = IngestWAL(d)
        wal.append(_pool_record(_rows(2), [0, 1]))
        wal.close()
        # Simulate a kill mid-append: a half-written (newline-less) line.
        with open(os.path.join(d, "wal.jsonl"), "ab") as fh:
            fh.write(b'{"seq": 2, "kind": "label", "ids"')
        records, dropped = replay_wal(d)
        assert dropped == 1
        assert [r["seq"] for r in records] == [1]
        # Reopening truncates the fragment; the next record is clean.
        wal = IngestWAL(d)
        assert wal.append({"kind": "label", "ids": [0],
                           "labels": [1]}) == 2
        wal.close()
        records, dropped = replay_wal(d)
        assert dropped == 0 and [r["seq"] for r in records] == [1, 2]

    def test_mid_file_corruption_raises(self, tmp_path):
        d = str(tmp_path)
        wal = IngestWAL(d)
        wal.append({"kind": "label", "ids": [0], "labels": [1]})
        wal.append({"kind": "label", "ids": [1], "labels": [1]})
        wal.close()
        path = os.path.join(d, "wal.jsonl")
        lines = open(path, "rb").read().splitlines(keepends=True)
        with open(path, "wb") as fh:
            fh.write(b"garbage\n" + lines[1])
        with pytest.raises(ValueError, match="corrupt WAL record"):
            replay_wal(d)

    def test_rotation_seals_segments_in_replay_order(self, tmp_path):
        d = str(tmp_path)
        wal = IngestWAL(d, rotate_bytes=200)
        for i in range(6):
            wal.append({"kind": "label", "ids": [i], "labels": [0]})
        wal.close()
        sealed = glob.glob(os.path.join(d, "wal_*.jsonl"))
        assert sealed, "no sealed segments despite the tiny rotate bound"
        records, dropped = replay_wal(d)
        assert dropped == 0
        assert [r["seq"] for r in records] == list(range(1, 7))

    def test_crc_guards_tampered_records(self, tmp_path):
        d = str(tmp_path)
        wal = IngestWAL(d)
        wal.append({"kind": "label", "ids": [0], "labels": [1]})
        wal.append({"kind": "label", "ids": [1], "labels": [1]})
        wal.close()
        path = os.path.join(d, "wal.jsonl")
        text = open(path).read().replace('"ids": [0]', '"ids": [9]', 1)
        open(path, "w").write(text)
        with pytest.raises(ValueError, match="crc mismatch"):
            replay_wal(d)

    def test_torn_fault_site_loses_only_the_unacked_record(self, tmp_path):
        d = str(tmp_path)
        wal = IngestWAL(d)
        wal.append({"kind": "label", "ids": [0], "labels": [1]})
        faults.configure("wal_write:torn@1", seed=0)
        try:
            with pytest.raises(faults.InjectedFault):
                wal.append({"kind": "label", "ids": [1], "labels": [1]})
        finally:
            faults.configure(None)
        wal.close()
        records, dropped = replay_wal(d)
        # The interrupted record was never acked: dropping it is the
        # contract, corruption would be the bug.
        assert [r["seq"] for r in records] == [1]
        assert dropped == 1


# ---------------------------------------------------------------------------
# PoolState growth + the growable store
# ---------------------------------------------------------------------------

class TestPoolGrowth:
    def test_grow_set_valid_and_query_masks(self):
        pool = PoolState.create(10, eval_idxs=[8, 9])
        pool.grow(16)
        assert pool.n_pool == 16
        assert pool.invalid[10:].all() and not pool.invalid[:10].any()
        # Padding slots are neither queryable nor labelable.
        assert pool.available_mask()[10:].sum() == 0
        with pytest.raises(ValueError, match="invalid"):
            pool.update([12], 1.0)
        pool.mark_valid([10, 11])
        assert pool.available_mask()[[10, 11]].all()
        with pytest.raises(ValueError, match="shrink"):
            pool.grow(8)

    def test_absorb_labels_skips_budget_and_recent(self):
        pool = PoolState.create(8, eval_idxs=[])
        pool.update([0, 1], 2.0)
        recent = pool.recent.copy()
        pool.grow(12)
        pool.absorb_labels([9, 10])
        assert pool.labeled[[9, 10]].all()
        assert not pool.invalid[[9, 10]].any()
        assert pool.cumulative_cost == 2.0  # no budget charged
        assert np.array_equal(pool.recent, recent)
        with pytest.raises(ValueError, match="already labeled"):
            pool.absorb_labels([9])

    def test_serialization_roundtrip_with_invalid(self):
        pool = PoolState.create(6, eval_idxs=[5])
        pool.grow(8)
        pool.update([0], 1.0)
        back = PoolState.from_arrays(pool.to_arrays())
        assert np.array_equal(back.invalid, pool.invalid)
        assert np.array_equal(back.labeled, pool.labeled)
        # Pre-stream saves (no invalid key) load as all-real slots.
        arrs = pool.to_arrays()
        del arrs["invalid"]
        legacy = PoolState.from_arrays(arrs)
        assert not legacy.invalid.any()

    def test_store_grows_by_bucket_extents(self, tmp_path):
        st = store_lib.PoolStore(str(tmp_path), (8, 8, 3), 4,
                                 base_images=_rows(20),
                                 base_targets=np.arange(20) % 4,
                                 extent_floor=16)
        assert st.capacity == bucket_size(20, floor=16)
        ids = st.apply_pool_record(_pool_record(_rows(30, seed=1),
                                                list(range(30))))
        assert np.array_equal(ids, np.arange(20, 50))
        assert st.capacity == bucket_size(50, floor=16)
        assert st.n_rows == 50
        # Targets of padding slots read UNKNOWN, never class 0.
        assert (st.snapshot()[1][50:] == store_lib.UNKNOWN_LABEL).all()


# ---------------------------------------------------------------------------
# Ingest handlers: admission + WAL-before-ack, behaviorally
# ---------------------------------------------------------------------------

class TestIngestHandlers:
    def _stack(self, tmp_path, max_backlog=64):
        wal = IngestWAL(str(tmp_path))
        queue = ingest_lib.PendingQueue(max_backlog)
        ids = ingest_lib.IdSpace(10)
        return wal, queue, ids

    def _pool_req(self, n, labels=False):
        rows = _rows(n)
        return {"rows_b64": base64.b64encode(rows.tobytes()).decode(),
                "shape": [n, 8, 8, 3],
                "labels": list(range(n)) if labels else None}

    def test_pool_append_durable_before_ack(self, tmp_path):
        wal, queue, ids = self._stack(tmp_path)
        out = ingest_lib.handle_pool_append(wal, queue, ids,
                                            self._pool_req(4), (8, 8, 3),
                                            max_request_rows=8)
        assert out["ok"] and out["ids"] == [10, 11, 12, 13]
        # The ack's seq IS on disk: the WAL already holds it.
        records, _ = replay_wal(str(tmp_path))
        assert records[-1]["seq"] == out["seq"] == 1
        assert queue.counters()["pending_rows"] == 4
        wal.close()

    def test_oversize_is_413_backlog_is_429(self, tmp_path):
        wal, queue, ids = self._stack(tmp_path, max_backlog=6)
        with pytest.raises(ingest_lib.IngestError) as e:
            ingest_lib.handle_pool_append(wal, queue, ids,
                                          self._pool_req(9), (8, 8, 3),
                                          max_request_rows=8)
        assert e.value.status == 413
        ingest_lib.handle_pool_append(wal, queue, ids, self._pool_req(4),
                                      (8, 8, 3), max_request_rows=8)
        with pytest.raises(ingest_lib.IngestError) as e:
            ingest_lib.handle_pool_append(wal, queue, ids,
                                          self._pool_req(4), (8, 8, 3),
                                          max_request_rows=8)
        assert e.value.status == 429 and e.value.retry_after is not None
        # The refused request left NOTHING durable: no seq consumed.
        assert wal.last_seq == 1
        wal.close()

    def test_label_validates_against_acked_id_space(self, tmp_path):
        wal, queue, ids = self._stack(tmp_path)
        with pytest.raises(ingest_lib.IngestError) as e:
            ingest_lib.handle_label_attach(
                wal, queue, ids, {"ids": [10], "labels": [1]})
        assert e.value.status == 400  # id 10 was never acked
        # Eval-split rows are REJECTED before the WAL write: a durable
        # label record the drain could never absorb would replay into
        # the same failure on every restart — a poison pill.
        ids_eval = ingest_lib.IdSpace(10, unlabelable=[3])
        with pytest.raises(ingest_lib.IngestError) as e:
            ingest_lib.handle_label_attach(
                wal, queue, ids_eval, {"ids": [3], "labels": [1]})
        assert e.value.status == 400
        assert "validation rows" in e.value.message
        assert wal.last_seq == 0  # nothing rejected became durable
        out = ingest_lib.handle_label_attach(
            wal, queue, ids, {"ids": [3, 4], "labels": [1, 2]})
        assert out["ok"] and wal.last_seq == 1
        for bad in ({"ids": [1], "labels": [1, 2]},
                    {"ids": [1, 1], "labels": [0, 0]},
                    {"ids": [], "labels": []},
                    {"ids": [0], "labels": [-1]}):
            with pytest.raises(ingest_lib.IngestError):
                ingest_lib.handle_label_attach(wal, queue, ids, bad)
        wal.close()

    def test_malformed_pool_payload_is_400(self, tmp_path):
        wal, queue, ids = self._stack(tmp_path)
        for req in ({"shape": [2, 8, 8, 3]},                 # no rows
                    {"rows_b64": "aaaa", "shape": [1, 4, 4, 3]},  # shape
                    {"rows_b64": "!!", "shape": [1, 8, 8, 3]}):  # b64
            with pytest.raises(ingest_lib.IngestError) as e:
                ingest_lib.handle_pool_append(wal, queue, ids, req,
                                              (8, 8, 3),
                                              max_request_rows=8)
            assert e.value.status == 400
        assert wal.last_seq == 0  # nothing malformed became durable
        wal.close()


# ---------------------------------------------------------------------------
# Trigger policy
# ---------------------------------------------------------------------------

class TestTriggerPolicy:
    def test_decision_table(self):
        p = TriggerPolicy(watermark_rows=100, drift_psi=0.25,
                          max_interval_s=60.0)
        dec = p.decide
        assert dec(100, 0, None, 0.0, 50) == "watermark"
        assert dec(99, 0, None, 0.0, 50) is None
        assert dec(0, 0, 0.25, 0.0, 50) == "drift"
        assert dec(0, 0, 0.24, 0.0, 50) is None
        assert dec(0, 0, None, 61.0, 50) == "interval"
        # Interval never fires an empty loop: no pending work, no
        # queryable rows -> idle, not a round that re-picks nothing.
        assert dec(0, 0, None, 61.0, 0) is None
        assert dec(0, 1, None, 61.0, 0) == "interval"
        # Disabled conditions never fire.
        off = TriggerPolicy(watermark_rows=0, drift_psi=0.0,
                            max_interval_s=0.0)
        assert off.decide(10**6, 10**6, 9.9, 10**6, 10**6) is None

    def test_watermark_wins_attribution(self):
        p = TriggerPolicy(watermark_rows=1, drift_psi=0.01,
                          max_interval_s=0.01)
        assert p.decide(5, 0, 1.0, 100.0, 5) == "watermark"


# ---------------------------------------------------------------------------
# Service end to end (shared fixtures)
# ---------------------------------------------------------------------------

N_EPOCH = 2


def _cfg(tag, root, *, resume=False, rounds=2, pipeline="off"):
    return ExperimentConfig(
        dataset="synthetic", arg_pool="synthetic",
        strategy="MarginSampler", rounds=rounds, round_budget=8,
        n_epoch=N_EPOCH, early_stop_patience=N_EPOCH, run_seed=7,
        exp_hash=tag, exp_name="stream", resume_training=resume,
        ckpt_path=os.path.join(root, "ckpt"),
        log_dir=os.path.join(root, "logs"), round_pipeline=pipeline,
        telemetry=TelemetryConfig(enabled=True, heartbeat_every_s=0.0))


def _scfg(**over):
    base = dict(port=0, max_rounds=2, watermark_rows=0, drift_psi=0.0,
                max_interval_s=0.01, poll_s=0.02, extent_floor=16)
    base.update(over)
    return StreamConfig(**base)


@pytest.fixture(scope="module")
def stream_data():
    return get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                              image_size=8, seed=5)


def _state_of(cfg):
    path = glob.glob(os.path.join(cfg.ckpt_path, "*",
                                  "experiment_state.npz"))[0]
    return dict(np.load(path))


def _run_service(cfg, scfg, data, sink=None):
    svc = StreamService(cfg, scfg, sink=sink or NullSink(), data=data,
                        train_cfg=tiny_train_config(),
                        model=TinyClassifier(num_classes=4))
    svc.run()
    return svc


def _prefill_wal(log_dir, records):
    wal = IngestWAL(os.path.join(log_dir, "ingest_wal"))
    for rec in records:
        wal.append(rec)
    wal.close()


class TestEquivalencePins:
    def test_zero_ingest_stream_matches_batch_driver(self, stream_data,
                                                     tmp_path):
        """A stream run that never ingests IS the batch driver: same
        seeds, same data -> experiment_state bit-identical.  Every
        batch-mode guarantee (resume, ladder, pipelining) transfers to
        the streaming loop through this pin."""
        a = _cfg("batch", str(tmp_path / "a"))
        run_experiment(a, sink=NullSink(), data=stream_data,
                       train_cfg=tiny_train_config(),
                       model=TinyClassifier(num_classes=4))
        base = _state_of(a)
        b = _cfg("streamed", str(tmp_path / "b"))
        _run_service(b, _scfg(), stream_data)
        state = _state_of(b)
        assert set(state) == set(base)
        for k in base:
            assert np.array_equal(base[k], state[k]), (
                f"experiment_state[{k!r}] diverged between the batch "
                "driver and the zero-ingest stream loop")

    def test_ingest_chunking_cannot_change_picks(self, stream_data,
                                                 tmp_path):
        """The equivalence pin: the SAME appended rows presented as one
        big request vs many small ones -> identical pool, scores, and
        picks (chunked-incremental == monolithic, extended to appended
        extents)."""
        rows = _rows(24, seed=3)
        labels = [int(v) % 4 for v in range(24)]
        runs = {}
        for tag, chunks in (("mono", [rows]),
                            ("chunked", [rows[:8], rows[8:16],
                                         rows[16:]])):
            cfg = _cfg(tag, str(tmp_path / tag))
            os.makedirs(cfg.log_dir, exist_ok=True)
            off = 0
            recs = []
            for c in chunks:
                recs.append(_pool_record(c, labels[off:off + len(c)]))
                off += len(c)
            _prefill_wal(cfg.log_dir, recs)
            _run_service(cfg, _scfg(), stream_data)
            runs[tag] = _state_of(cfg)
        for k in runs["mono"]:
            assert np.array_equal(runs["mono"][k], runs["chunked"][k]), (
                f"experiment_state[{k!r}] depends on ingest chunking")
        # The grown pool really was in play: extents + labeled picks.
        assert int(runs["mono"]["n_pool"]) == bucket_size(120, floor=16)

    def test_incremental_chunk_scores_match_monolithic(self, stream_data,
                                                       tmp_path):
        """Scoring only the appended row range in chunk_row_slices plans
        and splicing == scoring the grown pool monolithically, bit for
        bit (the PR 7 contract over appended extents)."""
        import jax
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.strategies import scoring

        st = store_lib.PoolStore(str(tmp_path), (8, 8, 3), 4,
                                 base_images=_rows(40, seed=1),
                                 base_targets=np.arange(40) % 4,
                                 extent_floor=16)
        st.apply_pool_record(_pool_record(_rows(33, seed=2),
                                          [0] * 33))
        train_sd, al_sd = st.make_datasets(
            stream_data[0].view, stream_data[2].view)
        al_sd.refresh()  # full capacity view
        model = TinyClassifier(num_classes=4)
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 8, 8, 3), np.float32),
                               train=False)
        mesh = mesh_lib.make_mesh()
        step = scoring.make_prob_stats_step(model, al_sd.view)
        idxs = np.arange(40, 73, dtype=np.int64)  # the appended range
        bs = 16
        mono = scoring.collect_pool(al_sd, idxs, bs, step, variables,
                                    mesh, keys=("margin", "entropy"))
        chunks = [scoring.collect_pool(al_sd, idxs[sl], bs, step,
                                       variables, mesh,
                                       keys=("margin", "entropy"))
                  for sl in scoring.chunk_row_slices(len(idxs), bs, 1)]
        spliced = scoring.splice_chunks(chunks)
        for k in mono:
            assert np.array_equal(mono[k], spliced[k]), k


class TestHTTPServiceEndToEnd:
    def _spawn(self, cfg, scfg, data, sink=None):
        svc = StreamService(cfg, scfg, sink=sink or NullSink(),
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
        assert svc.ready.wait(240), "service never became ready"
        return svc, t, box

    def _post(self, port, path, payload):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("POST", path, body=json.dumps(payload).encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read().decode())
        finally:
            conn.close()

    def test_ingest_trigger_round_metrics_and_status(self, stream_data,
                                                     tmp_path):
        """One live service: HTTP ingest (pool + label), watermark
        trigger, a completed round over the grown pool, stream gauges
        in BOTH channels, and the status verb's stream tail."""
        cfg = _cfg("http", str(tmp_path))
        cfg.telemetry = TelemetryConfig(
            enabled=True, heartbeat_every_s=0.0,
            prometheus_file=os.path.join(cfg.log_dir, "run.prom"))
        sink = JsonlSink(cfg.log_dir, experiment_key="http")
        scfg = _scfg(max_rounds=2, watermark_rows=24, max_interval_s=0.0)
        svc, t, box = self._spawn(cfg, scfg, stream_data, sink=sink)
        try:
            # Let the bootstrap round finish first so all 24 posted
            # rows land in ONE drain window and the watermark trigger
            # (24) is what fires round 1.
            deadline = time.monotonic() + 240
            while svc.rounds_run < 1 and t.is_alive() \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert svc.rounds_run >= 1, "bootstrap round never completed"
            rows = _rows(16, seed=11)
            status, out = self._post(svc.port, "/v1/pool", {
                "rows_b64": base64.b64encode(rows.tobytes()).decode(),
                "shape": [16, 8, 8, 3]})
            assert status == 200 and out["accepted"] == 16
            no_oracle_ids = out["ids"]
            # Attach labels to half the oracle-less rows.
            status, _ = self._post(svc.port, "/v1/label", {
                "ids": no_oracle_ids[:8],
                "labels": [i % 4 for i in range(8)]})
            assert status == 200
            rows2 = _rows(8, seed=12)
            status, out2 = self._post(svc.port, "/v1/pool", {
                "rows_b64": base64.b64encode(rows2.tobytes()).decode(),
                "shape": [8, 8, 8, 3],
                "labels": [i % 4 for i in range(8)]})
            assert status == 200
            t.join(timeout=300)
            assert not t.is_alive(), "service never finished"
            if "err" in box:
                raise box["err"]
        finally:
            if t.is_alive():
                preempt_lib._handler(signal.SIGTERM, None)
                t.join(timeout=60)
        strategy = box["strategy"]
        # The pool grew by one 16-aligned extent; the 8 labeled-by-
        # /v1/label rows joined the labeled set without budget.
        assert svc.store.n_rows == 96 + 24
        assert strategy.pool.n_pool == bucket_size(120, floor=16)
        assert strategy.pool.labeled[no_oracle_ids[:8]].all()
        # Oracle-less, unlabeled rows stay out of the queryable set.
        assert strategy.pool.invalid[no_oracle_ids[8:]].all()
        assert svc.rounds_run == 2
        assert svc.last_trigger["cause"] == "watermark"

        # Gauges: every stream gauge that reached metrics.jsonl also
        # rides the scrape (the PER_ROUND_GAUGES completeness rule),
        # and the per-cause trigger counter carries its label.
        sink.close()
        names = set()
        for line in open(os.path.join(cfg.log_dir, "metrics.jsonl")):
            ev = json.loads(line)
            if ev.get("kind") == "metric":
                names.update(ev["metrics"])
        parsed = prom_lib.parse(
            open(os.path.join(cfg.log_dir, "run.prom")).read())
        for name in STREAM_GAUGES:
            if name in names:
                assert f"al_run_{name}" in parsed, name
        assert "ingest_rows_total" in names
        assert parsed["al_run_ingest_rows_total"][()] == 24.0
        assert any(lbl == (("cause", "watermark"),)
                   for lbl in parsed.get("al_run_rounds_triggered", {}))

        # The status verb's stream tail + healthy strict exit.
        summary = status_lib.summarize(cfg.log_dir)
        assert summary["stream"]["pool_rows_total"] == 120
        assert summary["stream"]["last_trigger_cause"] == "watermark"
        text = status_lib.render_text(summary)
        assert "stream:" in text and "wal_backlog" in text

    def test_loadgen_ingest_mode_drives_both_endpoints(self, stream_data,
                                                       tmp_path):
        loadgen = load_script("serve_loadgen")

        cfg = _cfg("loadgen", str(tmp_path))
        # Run-forever: the test stops the service itself.
        scfg = _scfg(max_rounds=0, watermark_rows=10**9,
                     max_interval_s=0.0, max_backlog_rows=10**6)
        svc, t, box = self._spawn(cfg, scfg, stream_data)
        try:
            out = loadgen.run_ingest_closed(
                f"http://127.0.0.1:{svc.port}", duration_s=1.0,
                workers=2, rows=4, label_frac=0.5, image_shape=(8, 8, 3))
            assert out["mode"] == "ingest_closed"
            assert out["n_ok"] > 0 and out["n_err"] == 0
            assert out["p50_ms"] is not None
            health = loadgen.fetch_health(f"http://127.0.0.1:{svc.port}")
            assert health["image_shape"] == [8, 8, 3]
            assert health["pool_rows"] > 96
        finally:
            preempt_lib._handler(signal.SIGTERM, None)
            t.join(timeout=120)
        assert isinstance(box.get("err"),
                          preempt_lib.PreemptionRequested)


# ---------------------------------------------------------------------------
# THE chaos pin: kill mid-round -> resume, zero loss, bit-identical
# ---------------------------------------------------------------------------

class _PreemptAtEpochSink(NullSink):
    """Records a preemption request (what the real SIGTERM handler
    does) when round ``rd``'s fit reaches ``epoch`` — the deterministic
    in-process kill of tests/test_faults.py, reused for the stream
    loop."""

    def __init__(self, rd, epoch):
        self.name = f"rd_{rd}_validation_accuracy"
        self.epoch = epoch
        self.fired = False

    def log_metric(self, name, value, step=None):
        if not self.fired and step == self.epoch and name == self.name:
            self.fired = True
            preempt_lib._handler(signal.SIGTERM, None)


class TestChaosPin:
    WAL_ROWS = 24

    def _records(self):
        rows = _rows(self.WAL_ROWS, seed=9)
        return [_pool_record(rows[:16],
                             [i % 4 for i in range(16)]),
                _pool_record(rows[16:], None),
                {"kind": "label", "ids": [96 + 16, 96 + 17],
                 "labels": [1, 2]}]

    def _launch(self, tag, root, data, sink=None, resume=False,
                prefill=True):
        cfg = _cfg(tag, root, resume=resume)
        if prefill and not resume:
            os.makedirs(cfg.log_dir, exist_ok=True)
            _prefill_wal(cfg.log_dir, self._records())
        svc = StreamService(cfg, _scfg(), sink=sink or NullSink(),
                            data=data, train_cfg=tiny_train_config(),
                            model=TinyClassifier(num_classes=4))
        return cfg, svc

    def test_preempt_mid_triggered_round_resumes_bit_identical(
            self, stream_data, tmp_path):
        """Ingest (via a pre-accepted WAL) -> bootstrap -> kill DURING
        the triggered round's fit -> resume completes: zero accepted-row
        loss, experiment_state bit-identical to the uninterrupted
        twin."""
        # The uninterrupted twin.
        cfg_a, svc_a = self._launch("uninter", str(tmp_path / "a"),
                                    stream_data)
        svc_a.run()
        baseline = _state_of(cfg_a)
        assert svc_a.store.n_rows == 96 + self.WAL_ROWS

        # The killed run: preempted at round 1, epoch 1 (mid-fit).
        sink = _PreemptAtEpochSink(rd=1, epoch=1)
        cfg_b, svc_b = self._launch("killed", str(tmp_path / "b"),
                                    stream_data, sink=sink)
        with pytest.raises(preempt_lib.PreemptionRequested):
            svc_b.run()
        assert sink.fired
        jr = faults.read_journal(
            os.path.join(cfg_b.log_dir, faults.JOURNAL_FILE))
        assert jr["status"] == "preempted"

        # Resume: same dirs, --resume_training.
        cfg_c, svc_c = self._launch("killed", str(tmp_path / "b"),
                                    stream_data, resume=True)
        svc_c.run()
        # Zero accepted-row loss: every WAL row is back in the pool.
        assert svc_c.store.n_rows == 96 + self.WAL_ROWS
        state = _state_of(cfg_c)
        assert set(state) == set(baseline)
        for k in baseline:
            assert np.array_equal(baseline[k], state[k]), (
                f"experiment_state[{k!r}] diverged after mid-round "
                "preemption resume")

    def test_preempt_mid_round0_resumes_bit_identical(self, stream_data,
                                                      tmp_path):
        """Preempted DURING the bootstrap round's fit — before any
        save_experiment exists — the journal's round-0 preemption
        record (which the resume path must read BEFORE this run's
        journal writes anything) unlocks the replay, and the result is
        bit-identical to the uninterrupted twin."""
        cfg_a, svc_a = self._launch("uninter0", str(tmp_path / "a"),
                                    stream_data)
        svc_a.run()
        baseline = _state_of(cfg_a)

        sink = _PreemptAtEpochSink(rd=0, epoch=1)
        cfg_b, svc_b = self._launch("killed0", str(tmp_path / "b"),
                                    stream_data, sink=sink)
        with pytest.raises(preempt_lib.PreemptionRequested):
            svc_b.run()
        assert sink.fired
        assert not glob.glob(os.path.join(cfg_b.ckpt_path, "*",
                                          "experiment_state.npz"))
        cfg_c, svc_c = self._launch("killed0", str(tmp_path / "b"),
                                    stream_data, resume=True)
        svc_c.run()
        assert svc_c.store.n_rows == 96 + self.WAL_ROWS
        state = _state_of(cfg_c)
        for k in baseline:
            assert np.array_equal(baseline[k], state[k]), (
                f"experiment_state[{k!r}] diverged after round-0 "
                "preemption resume")

    def test_drain_fault_crashes_clean_and_restart_loses_nothing(
            self, stream_data, tmp_path):
        """An injected stream_drain failure crashes the service BEFORE
        any round consumes a half-applied pool (the site's contract) —
        rows stay durable in the WAL, and a restart over the same
        log_dir replays them all."""
        cfg, svc = self._launch("drainfault", str(tmp_path),
                                stream_data)
        faults.configure("stream_drain:raise@1", seed=0)
        try:
            with pytest.raises(faults.InjectedFault):
                svc.run()
        finally:
            faults.configure(None)
        # Restart over the SAME dirs (no resume flag: round 0 never
        # completed): the WAL replay rebuilds the queue and the run
        # completes with every accepted row present.
        cfg2, svc2 = self._launch("drainfault", str(tmp_path),
                                  stream_data, prefill=False)
        svc2.run()
        assert svc2.store.n_rows == 96 + self.WAL_ROWS


# ---------------------------------------------------------------------------
# status --strict: the ingest-starved exit-5 contract
# ---------------------------------------------------------------------------

class TestStatusIngestStarved:
    def _dir(self, tmp_path, *, backlog, trigger_age_s, status="running"):
        from active_learning_tpu.faults.journal import RoundJournal
        from active_learning_tpu.telemetry import heartbeat as hb_lib
        d = str(tmp_path)
        os.makedirs(d, exist_ok=True)
        hb = hb_lib.HeartbeatWriter(os.path.join(d, "heartbeat.json"),
                                    every_s=0.0, stall_deadline_s=600.0)
        hb.tick(round=1, phase="stream_wait", status="running")
        j = RoundJournal(os.path.join(d, faults.JOURNAL_FILE))
        j.write(status=status, stream=True, stream_pool_rows=128,
                stream_wal_backlog=backlog, stream_rounds_run=2,
                stream_last_trigger_cause="watermark",
                stream_last_trigger_ts=time.time() - trigger_age_s)
        return d

    def test_backlog_past_deadline_is_5_only_under_strict(self, tmp_path):
        d = self._dir(tmp_path, backlog=500, trigger_age_s=10_000)
        assert status_lib.main(["--log_dir", d]) == 0
        assert status_lib.main(["--log_dir", d, "--strict"]) == 5
        text = status_lib.render_text(status_lib.summarize(d))
        assert "INGEST-STARVED" in text

    def test_recent_trigger_or_empty_backlog_is_healthy(self, tmp_path):
        d = self._dir(tmp_path / "a", backlog=500, trigger_age_s=1.0)
        assert status_lib.main(["--log_dir", d, "--strict"]) == 0
        d = self._dir(tmp_path / "b", backlog=0, trigger_age_s=10_000)
        assert status_lib.main(["--log_dir", d, "--strict"]) == 0

    def test_terminal_status_is_never_starved(self, tmp_path):
        d = self._dir(tmp_path, backlog=500, trigger_age_s=10_000,
                      status="preempted")
        assert status_lib.main(["--log_dir", d, "--strict"]) == 0


# ---------------------------------------------------------------------------
# Labeled-gauge convention (telemetry/prom)
# ---------------------------------------------------------------------------

class TestLabeledGauges:
    def test_bracketed_key_renders_with_label(self):
        samples = prom_lib.gauge_samples(
            {"rounds_triggered{cause=drift}": 2, "plain": 1.5},
            prefix="al_run_")
        text = prom_lib.render(samples)
        parsed = prom_lib.parse(text)
        assert parsed["al_run_rounds_triggered"][(("cause", "drift"),)] \
            == 2.0
        assert parsed["al_run_plain"][()] == 1.5


# ---------------------------------------------------------------------------
# Incremental resident row update (ISSUE 15 satellite: the drain stops
# re-uploading the pinned extent)
# ---------------------------------------------------------------------------

class TestIncrementalResidentUpdate:
    """parallel/resident.update_rows: an in-extent streaming drain
    refreshes a PINNED pool entry by dynamic_update_slice of ONLY the
    new rows (plus a tiny whole-labels device_put) — never a full
    re-upload of the pinned extent, never a compile once prewarmed."""

    def _pin(self, sharding):
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib
        _, _, al_set = get_data_synthetic(n_train=96, n_test=16,
                                          num_classes=4, image_size=8,
                                          seed=9)
        # A writable copy: the synthetic arrays may be shared across
        # tests and the point here is to mutate rows in place.
        al_set.images = al_set.images.copy()
        al_set.targets = al_set.targets.copy()
        mesh = mesh_lib.make_mesh()
        cache = {}
        resident_lib.pool_arrays(cache, al_set, mesh, sharding=sharding)
        return cache, al_set, mesh, resident_lib, mesh_lib

    @pytest.mark.parametrize("sharding", ["replicated", "row"])
    def test_update_refreshes_rows_and_labels_in_place(self, sharding):
        cache, ds, mesh, resident_lib, mesh_lib = self._pin(sharding)
        rng = np.random.default_rng(0)
        ds.images[80:96] = rng.integers(0, 255, ds.images[80:96].shape,
                                        dtype=np.uint8)
        ds.targets[80:96] = (ds.targets[80:96] + 1) % 4
        assert resident_lib.update_rows(cache, ds, mesh, 80, 96)
        key = (id(ds.images), 96)
        _, images_dev, labels_dev = cache["images"][key]
        got = resident_lib.from_pinned(np.asarray(images_dev)[:96],
                                       ds.image_shape)
        np.testing.assert_array_equal(got, ds.images[:96])
        np.testing.assert_array_equal(
            np.asarray(labels_dev)[:96],
            ds.targets[:96].astype(np.int32))
        assert mesh_lib.is_row_sharded(images_dev) == (sharding == "row")

    @pytest.mark.parametrize("sharding", ["replicated", "row"])
    def test_no_full_image_reupload(self, sharding, monkeypatch):
        """THE satellite pin: during an in-extent update no image array
        crosses the host->device boundary through the upload primitives
        — only the [capacity]-labels vector does (1-D).  A regression
        back to release + re-upload would ship the whole pinned extent
        again and fail here."""
        cache, ds, mesh, resident_lib, mesh_lib = self._pin(sharding)
        uploads = []

        real_shard_rows = mesh_lib.shard_rows
        real_replicate = mesh_lib.replicate

        def spy_shard_rows(array, *a, **k):
            uploads.append(np.asarray(array).ndim)
            return real_shard_rows(array, *a, **k)

        def spy_replicate(tree, *a, **k):
            for leaf in np.asarray(tree, dtype=object).reshape(-1) \
                    if isinstance(tree, (list, tuple)) else [tree]:
                uploads.append(np.asarray(leaf).ndim)
            return real_replicate(tree, *a, **k)

        monkeypatch.setattr(mesh_lib, "shard_rows", spy_shard_rows)
        monkeypatch.setattr(mesh_lib, "replicate", spy_replicate)
        ds.images[90:96] ^= 1
        assert resident_lib.update_rows(cache, ds, mesh, 90, 96)
        assert uploads and all(nd == 1 for nd in uploads), uploads

    def test_unpinned_entry_returns_false(self):
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib
        _, _, al_set = get_data_synthetic(n_train=96, n_test=16,
                                          num_classes=4, image_size=8)
        assert not resident_lib.update_rows({}, al_set,
                                            mesh_lib.make_mesh(), 0, 8)

    def test_pool_smaller_than_one_window_falls_back(self):
        """A pool the fixed window cannot express (fewer rows than
        UPDATE_BLOCK_FLOOR) refuses — the caller's release + re-upload
        path owns it (re-uploading a tiny pool is trivially cheap)."""
        from active_learning_tpu.data.core import ArrayDataset
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib
        rng = np.random.default_rng(2)
        tiny = ArrayDataset(
            rng.integers(0, 255, (32, 8, 8, 3), dtype=np.uint8),
            np.zeros(32, dtype=np.int64), 4,
            get_data_synthetic(n_train=8, n_test=8)[2].view)
        mesh = mesh_lib.make_mesh()
        cache = {}
        resident_lib.pool_arrays(cache, tiny, mesh)
        assert not resident_lib.update_rows(cache, tiny, mesh, 0, 8)
        assert not resident_lib.prewarm_update(cache, tiny, mesh)

    @pytest.mark.parametrize("sharding", ["replicated", "row"])
    def test_prewarmed_update_adds_zero_compiles(self, sharding):
        """The delta-0 contract: prewarm_update builds + warms the ONE
        fixed-width updater; every real in-extent drain after it —
        narrow OR wider than the window (drains chunk into fixed-width
        blocks) — dispatches the SAME executable, zero new compiles
        (the in-extent rounds of TestStreamExtentCompileReuse rest on
        this)."""
        cache, ds, mesh, resident_lib, _ = self._pin(sharding)
        assert resident_lib.prewarm_update(cache, ds, mesh)
        runners = {k: v for k, v in cache["steps"].items()
                   if isinstance(k, tuple) and k and k[0] == "update_rows"}
        assert runners
        sizes = {k: v._cache_size() for k, v in runners.items()}
        ds.images[88:96] ^= 1
        assert resident_lib.update_rows(cache, ds, mesh, 88, 96)
        # A drain WIDER than the window must reuse the same executable
        # too (the review finding: a watermark > window once compiled a
        # fresh width inside a warm round).
        ds.images[0:96] ^= 2
        assert resident_lib.update_rows(cache, ds, mesh, 0, 96)
        assert {k: v._cache_size() for k, v in runners.items()} == sizes
        np.testing.assert_array_equal(
            resident_lib.from_pinned(
                np.asarray(cache["images"][(id(ds.images), 96)][1])[:96],
                ds.image_shape),
            ds.images[:96])

    def test_prewarm_is_noop_once_warm(self, monkeypatch):
        """Once the (layout, shape) pair is warmed, prewarm_update does
        NOTHING — no label re-upload, no identity dispatch — so the
        per-round service call stays free on drainless rounds."""
        cache, ds, mesh, resident_lib, mesh_lib = self._pin("replicated")
        assert resident_lib.prewarm_update(cache, ds, mesh)
        calls = []
        monkeypatch.setattr(
            mesh_lib, "replicate",
            lambda *a, **k: calls.append(1) or (_ for _ in ()).throw(
                AssertionError("prewarm re-uploaded after warm")))
        assert resident_lib.prewarm_update(cache, ds, mesh)
        assert not calls

    def test_label_upload_failure_leaves_entry_intact(self, monkeypatch):
        """Labels upload BEFORE the donating image dispatch (and under
        the upload RetryPolicy): a label-upload failure propagates with
        the pinned entry untouched and still valid."""
        cache, ds, mesh, resident_lib, mesh_lib = self._pin("replicated")

        def boom(*a, **k):
            raise RuntimeError("injected label-upload failure")

        monkeypatch.setattr(mesh_lib, "replicate", boom)
        with pytest.raises(RuntimeError, match="label-upload"):
            resident_lib.update_rows(cache, ds, mesh, 80, 96)
        monkeypatch.undo()
        assert resident_lib.cached(cache, ds)
        # The untouched entry still serves reads.
        key = (id(ds.images), 96)
        np.testing.assert_array_equal(
            resident_lib.from_pinned(
                np.asarray(cache["images"][key][1])[:96], ds.image_shape),
            ds.images[:96])

    def test_failed_donating_update_drops_entry(self, monkeypatch):
        """A failure inside the donating image dispatch may have
        consumed the old buffer: the entry must be DROPPED before the
        exception propagates — a cache entry pointing at a deleted
        array would poison every retry (the review finding).  The next
        access re-uploads cleanly."""
        cache, ds, mesh, resident_lib, _ = self._pin("replicated")

        def boom(*a, **k):
            def run(*aa, **kk):
                raise RuntimeError("injected dispatch failure")
            return run

        monkeypatch.setattr(resident_lib, "_update_runner", boom)
        with pytest.raises(RuntimeError, match="injected"):
            resident_lib.update_rows(cache, ds, mesh, 80, 96)
        assert not resident_lib.cached(cache, ds)
        monkeypatch.undo()
        # Recovery: the next pool_arrays call re-pins from host.
        resident_lib.pool_arrays(cache, ds, mesh)
        assert resident_lib.cached(cache, ds)
