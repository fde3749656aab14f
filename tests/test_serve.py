"""Serving-subsystem tests (active_learning_tpu/serve/), tier-1.

Everything runs over loopback on the virtual 8-device CPU mesh — real
HTTP, real microbatching, the real executor thread — so the whole
online path executes exactly as it would in front of a chip.  Pinned
contracts:

  * batcher flush ordering — full-batch flushes immediately, a partial
    batch flushes at the deadline, an overflowing entry carries whole;
  * bucket-padding isolation — padded rows (whatever their content)
    never change a real row's output, checked against an unbatched
    forward;
  * served == offline — /v1/predict and /v1/score reproduce the offline
    scoring path bit-for-bit at the same batch shape;
  * zero request-path compiles after warmup (the test_compile_reuse
    counter);
  * 429 + Retry-After under queue overflow; 503/closed during drain;
  * graceful drain — in-flight requests complete, SIGTERM exits 0
    (subprocess test through the CLI's signal path).
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from active_learning_tpu.config import ServeConfig
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.serve.batcher import (BatcherClosedError,
                                               MicroBatcher,
                                               QueueFullError,
                                               serve_buckets)
from active_learning_tpu.serve.executor import DeviceExecutor
from active_learning_tpu.serve.server import ScoringServer
from active_learning_tpu.train import checkpoint as ckpt_lib

from helpers import TinyClassifier, load_script, tiny_train_config

IMG = (8, 8, 3)


# ---------------------------------------------------------------------------
# Bucket ladder
# ---------------------------------------------------------------------------

class TestServeBuckets:
    def test_ladder_covers_and_orders(self):
        b = serve_buckets(64, floor=8)
        assert b == sorted(set(b)) and b[0] == 8 and b[-1] >= 64
        for n in range(1, 65):
            assert any(x >= n for x in b)

    def test_mesh_divisibility(self):
        for nd in (1, 3, 8):
            for b in serve_buckets(64, floor=8, n_devices=nd):
                assert b % nd == 0

    def test_single_bucket_config(self):
        assert serve_buckets(8, floor=8) == [8]


# ---------------------------------------------------------------------------
# Microbatcher (pure asyncio; no device work)
# ---------------------------------------------------------------------------

def _rows(n, start=0):
    """n distinguishable uint8 rows: row i is constant-valued start+i."""
    out = np.zeros((n, *IMG), dtype=np.uint8)
    for i in range(n):
        out[i] = (start + i) % 256
    return out


class _EchoDispatch:
    """Records every flushed batch; resolves each entry with its own
    rows' first-pixel values so tests can check slicing/ordering."""

    def __init__(self, auto_resolve=True):
        self.batches = []
        self.auto_resolve = auto_resolve
        self.pending = []

    def __call__(self, host_batch, entries, want_embed):
        self.batches.append({
            "t": time.monotonic(),
            "bucket": host_batch["image"].shape[0],
            "rows": int(host_batch["mask"].sum()),
            "mask": host_batch["mask"].copy(),
        })
        if self.auto_resolve:
            self.resolve(host_batch, entries)
        else:
            self.pending.append((host_batch, entries))

    def resolve(self, host_batch, entries):
        vals = host_batch["image"][:, 0, 0, 0].astype(np.int64)
        for e in entries:
            e.future.set_result(
                {"val": vals[e.offset:e.offset + e.n], "round": 0})

    def resolve_all(self):
        for host_batch, entries in self.pending:
            self.resolve(host_batch, entries)
        self.pending.clear()


def _make_batcher(dispatch, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_latency_ms", 50.0)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("bucket_floor", 4)
    b = MicroBatcher(dispatch, **kw)
    b.start()
    return b


class TestMicroBatcher:
    def test_full_batch_flushes_before_deadline(self):
        async def run():
            d = _EchoDispatch()
            b = _make_batcher(d, max_latency_ms=10_000.0)
            t0 = time.monotonic()
            r1, r2 = await asyncio.gather(b.submit(_rows(4)),
                                          b.submit(_rows(4, 100)))
            elapsed = time.monotonic() - t0
            # One coalesced full batch, dispatched WITHOUT waiting for
            # the 10-second deadline.
            assert len(d.batches) == 1
            assert d.batches[0]["rows"] == 8
            assert elapsed < 5.0
            # Ordering: each request got ITS rows, in submit order.
            assert r1["val"].tolist() == [0, 1, 2, 3]
            assert r2["val"].tolist() == [100, 101, 102, 103]
            return True

        assert asyncio.run(run())

    def test_deadline_flushes_partial_batch(self):
        async def run():
            d = _EchoDispatch()
            b = _make_batcher(d, max_latency_ms=60.0)
            t0 = time.monotonic()
            r = await b.submit(_rows(3))
            waited = time.monotonic() - t0
            assert len(d.batches) == 1
            assert d.batches[0]["rows"] == 3
            assert d.batches[0]["bucket"] == 4  # floor bucket, padded
            # Flushed BY the deadline, not before it (scheduling slack
            # allowed upward, never a full-batch-early flush).
            assert waited >= 0.05
            assert r["val"].tolist() == [0, 1, 2]
            return True

        assert asyncio.run(run())

    def test_overflowing_entry_carries_whole(self):
        async def run():
            d = _EchoDispatch()
            b = _make_batcher(d, max_latency_ms=40.0)
            r1, r2 = await asyncio.gather(b.submit(_rows(5)),
                                          b.submit(_rows(5, 50)))
            # 5 + 5 > max_batch=8: the second entry must carry into its
            # own batch — entries are never split across batches.
            assert [x["rows"] for x in d.batches] == [5, 5]
            assert r1["val"].tolist() == [0, 1, 2, 3, 4]
            assert r2["val"].tolist() == [50, 51, 52, 53, 54]
            return True

        assert asyncio.run(run())

    def test_oversized_request_chunks_and_reassembles(self):
        async def run():
            d = _EchoDispatch()
            b = _make_batcher(d, max_latency_ms=20.0)
            r = await b.submit(_rows(19))  # > 2x max_batch
            assert r["val"].tolist() == list(range(19))
            assert sum(x["rows"] for x in d.batches) == 19
            return True

        assert asyncio.run(run())

    def test_queue_full_raises_429_material(self):
        async def run():
            d = _EchoDispatch(auto_resolve=False)  # rows stay pending
            b = _make_batcher(d, queue_depth=8, max_latency_ms=5.0)
            t1 = asyncio.ensure_future(b.submit(_rows(8)))
            await asyncio.sleep(0.05)  # admitted + dispatched, unresolved
            with pytest.raises(QueueFullError):
                await b.submit(_rows(1))
            d.resolve_all()
            r = await t1
            assert len(r["val"]) == 8
            # Completion released the admission: a new request fits.
            r2 = await asyncio.wait_for(_retry_submit(b, d), timeout=2)
            assert len(r2["val"]) == 1
            return True

        assert asyncio.run(run())

    def test_drain_completes_inflight_then_rejects(self):
        async def run():
            d = _EchoDispatch(auto_resolve=False)
            b = _make_batcher(d, max_latency_ms=5.0)
            t1 = asyncio.ensure_future(b.submit(_rows(3)))
            await asyncio.sleep(0.05)
            drain = asyncio.ensure_future(b.drain(timeout_s=5))
            await asyncio.sleep(0.02)
            assert not drain.done()  # waiting on the in-flight rows
            d.resolve_all()
            await asyncio.wait_for(drain, timeout=5)
            r = await t1
            assert r["val"].tolist() == [0, 1, 2]  # completed, not dropped
            with pytest.raises(BatcherClosedError):
                await b.submit(_rows(1))
            return True

        assert asyncio.run(run())


async def _retry_submit(b, d, tries=20):
    for _ in range(tries):
        try:
            task = asyncio.ensure_future(b.submit(_rows(1)))
            await asyncio.sleep(0.03)
            d.resolve_all()
            return await task
        except QueueFullError:
            await asyncio.sleep(0.02)
    raise AssertionError("queue never freed")


# ---------------------------------------------------------------------------
# Executor-level: padding isolation + compile accounting
# ---------------------------------------------------------------------------

def _make_executor(variables=None, ckpt_dir=None, reload_every_s=5.0):
    _, _, al_set = get_data_synthetic(n_train=32, n_test=8, num_classes=4,
                                      image_size=IMG[0], seed=3)
    model = TinyClassifier(num_classes=4)
    mesh = mesh_lib.make_mesh()
    if variables is None and ckpt_dir is None:
        variables = jax.tree.map(np.asarray, model.init(
            jax.random.PRNGKey(0), np.zeros((1, *IMG), np.float32),
            train=False))
    return DeviceExecutor(model, al_set.view, mesh, image_shape=IMG,
                          variables=variables, ckpt_dir=ckpt_dir,
                          reload_every_s=reload_every_s), al_set


class TestPaddingIsolation:
    def test_padding_content_cannot_touch_real_rows(self):
        """Real rows' scores are identical whether the pad rows repeat
        row 0 (the production layout) or hold adversarial garbage — and
        both match the unbatched forward at the real rows' count."""
        ex, _ = _make_executor()
        step = ex._steps["prob_stats"]
        real = _rows(3, 7)
        mask = np.r_[np.ones(3, np.float32), np.zeros(5, np.float32)]

        def run(pad_rows):
            batch = {"image": np.concatenate([real, pad_rows]),
                     "mask": mask}
            out = step(ex._variables, mesh_lib.shard_batch(batch, ex.mesh))
            return {k: np.asarray(v)[:3] for k, v in out.items()}

        repeat = run(np.repeat(real[:1], 5, axis=0))
        garbage = run(_rows(5, 200))
        for k in repeat:
            assert np.array_equal(repeat[k], garbage[k]), k

        # Unbatched pin: the same 3 rows alone through the same step.
        alone = step(ex._variables, mesh_lib.shard_batch(
            {"image": np.concatenate([real, real[:1].repeat(5, axis=0)]),
             "mask": mask}, ex.mesh))
        for k in repeat:
            assert np.array_equal(repeat[k], np.asarray(alone[k])[:3]), k

    def test_unbatched_forward_oracle(self):
        """The served margin equals a hand-computed (no batching, no
        padding, no jit) softmax margin on the same pixels."""
        import jax.numpy as jnp
        from active_learning_tpu.data.augment import apply_view

        ex, al_set = _make_executor()
        rows = al_set.gather(np.arange(3))
        x = apply_view(jnp.asarray(rows), al_set.view, train=False)
        logits = np.asarray(ex.model.apply(
            jax.tree.map(np.asarray, ex._variables), x, train=False))
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        srt = np.sort(probs, axis=-1)
        oracle_margin = srt[:, -1] - srt[:, -2]

        mask = np.r_[np.ones(3, np.float32), np.zeros(5, np.float32)]
        batch = {"image": np.concatenate([rows, rows[:1].repeat(5, 0)]),
                 "mask": mask}
        out = ex._steps["prob_stats"](ex._variables,
                                      mesh_lib.shard_batch(batch, ex.mesh))
        np.testing.assert_allclose(np.asarray(out["margin"])[:3],
                                   oracle_margin, rtol=0, atol=1e-6)


class TestCompileReuse:
    def test_zero_request_path_compiles_across_buckets(self):
        """Warmup compiles every ladder shape; requests of every size
        after that — including ones that land in every bucket — add
        ZERO jit-cache entries (the test_compile_reuse counter)."""
        ex, _ = _make_executor()
        buckets = serve_buckets(12, floor=4,
                                n_devices=ex.mesh.devices.size)
        ex.warmup(buckets)
        baseline = ex.compile_counts()

        for n in (1, 3, 4, 5, 9, 12):
            bucket = next(b for b in buckets if b >= n)
            mask = np.zeros(bucket, np.float32)
            mask[:n] = 1.0
            img = np.concatenate([_rows(n), _rows(bucket - n)]) \
                if bucket > n else _rows(n)
            out = ex._steps["prob_stats"](
                ex._variables,
                mesh_lib.shard_batch({"image": img, "mask": mask},
                                     ex.mesh))
            np.asarray(out["margin"])
        assert ex.compile_counts() == baseline
        assert ex.request_path_compiles() == 0


# ---------------------------------------------------------------------------
# End-to-end over loopback HTTP, from a REAL experiment dir
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def experiment_dir(tmp_path_factory):
    """A real 1-round experiment through the production driver: its
    checkpoint dir (best_rd_0.msgpack + experiment_state.json) is what
    `serve` starts from."""
    from active_learning_tpu.config import ExperimentConfig
    from active_learning_tpu.experiment.driver import run_experiment
    from active_learning_tpu.utils.metrics import NullSink

    tmp = tmp_path_factory.mktemp("serve_exp")
    data = get_data_synthetic(n_train=64, n_test=16, num_classes=4,
                              image_size=IMG[0], seed=3)
    cfg = ExperimentConfig(
        dataset="synthetic", strategy="MarginSampler", rounds=1,
        round_budget=8, n_epoch=2, early_stop_patience=0,
        exp_name="serve_e2e", exp_hash="servetest",
        ckpt_path=str(tmp / "ckpt"), log_dir=str(tmp / "logs"))
    run_experiment(cfg, sink=NullSink(), data=data,
                   train_cfg=tiny_train_config(),
                   model=TinyClassifier(num_classes=4))
    exp_dir = os.path.join(str(tmp / "ckpt"), "serve_e2e_servetest")
    assert ckpt_lib.latest_best_ckpt(exp_dir)[0] is not None
    return exp_dir


class _Stack:
    """Server + executor on a private event-loop thread, with plain
    urllib client helpers."""

    def __init__(self, executor, cfg, start_executor=True):
        self.executor = executor
        self.server = ScoringServer(executor, cfg)
        self._start_executor = start_executor
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(
            target=lambda: (asyncio.set_event_loop(self.loop),
                            self.loop.run_forever()), daemon=True)
        self.thread.start()
        if not start_executor:
            # Swap start() to a no-op so admitted work stays queued
            # until the test releases it.
            executor._real_start = executor.start
            executor.start = lambda: None
        self.call(self.server.start(), timeout=120)
        self.port = self.server.port

    def call(self, coro, timeout=60):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout)

    def url(self, path):
        return f"http://127.0.0.1:{self.port}{path}"

    def get(self, path, timeout=30):
        with urllib.request.urlopen(self.url(path), timeout=timeout) as r:
            return r.status, json.loads(r.read())

    def post(self, path, obj, timeout=60):
        req = urllib.request.Request(
            self.url(path), data=json.dumps(obj).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read()), dict(r.headers)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}"), dict(e.headers)

    def close(self):
        try:
            self.call(self.server.drain(), timeout=60)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(timeout=10)


@pytest.fixture()
def stack(experiment_dir):
    _, _, al_set = get_data_synthetic(n_train=64, n_test=16, num_classes=4,
                                      image_size=IMG[0], seed=3)
    ex = DeviceExecutor(TinyClassifier(num_classes=4), al_set.view,
                        mesh_lib.make_mesh(), image_shape=IMG,
                        ckpt_dir=experiment_dir, reload_every_s=0.0)
    st = _Stack(ex, ServeConfig(port=0, max_batch=8, max_latency_ms=5.0,
                                queue_depth=64, bucket_floor=8))
    st.al_set = al_set
    yield st
    st.close()


class TestServeEndToEnd:
    def test_score_matches_offline_bitforbit(self, stack, experiment_dir):
        """/v1/score over HTTP == the offline scoring path (the same
        collect_pool machinery every sampler uses) at the same batch
        shape, bit for bit."""
        from active_learning_tpu.strategies import scoring

        idxs = np.arange(8)
        rows = stack.al_set.gather(idxs)
        status, resp, _ = stack.post(
            "/v1/score", {"instances": rows.tolist()})
        assert status == 200
        served = {k: np.asarray([r[k] for r in resp["scores"]],
                                np.float32)
                  for k in ("margin", "confidence", "entropy")}

        # The offline path, from the same checkpoint file: a FRESH jit
        # of the same factory over the same view + weights, through
        # collect_pool at the served bucket's batch shape.
        best, _rd = ckpt_lib.latest_best_ckpt(experiment_dir)
        variables = mesh_lib.replicate(ckpt_lib.load_variables(best),
                                       stack.executor.mesh)
        step = scoring.make_prob_stats_step(stack.executor.model,
                                            stack.al_set.view)
        offline = scoring.collect_pool(
            stack.al_set, idxs, 8, step, variables, stack.executor.mesh)
        for k in served:
            assert np.array_equal(served[k],
                                  offline[k].astype(np.float32)), k
        pred_served = np.asarray([r["pred"] for r in resp["scores"]])
        assert np.array_equal(pred_served, offline["pred"])

    def test_predict_and_embedding(self, stack):
        rows = stack.al_set.gather(np.arange(3))
        status, resp, _ = stack.post("/v1/predict",
                                     {"instances": rows.tolist()})
        assert status == 200 and len(resp["predictions"]) == 3
        assert {"pred", "confidence", "margin"} <= set(
            resp["predictions"][0])
        status, resp, _ = stack.post(
            "/v1/score", {"instances": rows.tolist(), "embedding": True})
        assert status == 200
        emb = np.asarray(resp["embedding"], np.float32)
        assert emb.shape == (3, 8)  # TinyClassifier feat_dim

    def test_healthz_metrics_and_compile_counter(self, stack):
        status, h = stack.get("/healthz")
        assert status == 200 and h["ok"] and h["image_shape"] == list(IMG)
        assert h["buckets"] == stack.server.batcher.buckets
        rows = stack.al_set.gather(np.arange(2))
        stack.post("/v1/score", {"instances": rows.tolist()})
        status, m = stack.get("/metrics")
        assert status == 200
        assert m["compiles"]["request_path_compiles"] == 0
        assert m["latency_ms"]["n"] >= 1
        assert m["batch_occupancy"]  # at least one dispatched bucket
        assert m["rows_served"] >= 2

    def test_b64_wire_format(self, stack):
        rows = stack.al_set.gather(np.arange(2))
        import base64
        status, resp, _ = stack.post("/v1/score", {
            "b64": base64.b64encode(rows.tobytes()).decode(),
            "shape": list(rows.shape)})
        assert status == 200 and len(resp["scores"]) == 2
        # And a nested-list request of the same pixels matches exactly.
        _, resp2, _ = stack.post("/v1/score",
                                 {"instances": rows.tolist()})
        assert resp["scores"] == resp2["scores"]

    def test_bad_requests_rejected(self, stack):
        assert stack.post("/v1/score", {"instances": []})[0] == 400
        assert stack.post("/v1/score", {})[0] == 400
        wrong = np.zeros((1, 4, 4, 3), np.uint8)
        assert stack.post("/v1/score",
                          {"instances": wrong.tolist()})[0] == 400
        # Malformed b64 shapes are client errors (400), never a 500
        # out of reshape.
        assert stack.post("/v1/score",
                          {"b64": "AAAA", "shape": [1, 8.5, 8, 3]})[0] \
            == 400
        assert stack.post("/v1/score",
                          {"b64": "AAAA",
                           "shape": ["1", "8", "8", "3"]})[0] == 400
        status, _, _ = stack.post("/v2/unknown", {"instances": [[0]]})
        assert status == 404

    def test_malformed_content_length_gets_400(self, stack):
        """A garbage Content-Length answers 400 and closes — never an
        unhandled task exception."""
        import socket

        with socket.create_connection(("127.0.0.1", stack.port),
                                      timeout=10) as s:
            s.sendall(b"POST /v1/score HTTP/1.1\r\n"
                      b"Content-Length: abc\r\n\r\n")
            data = s.recv(4096)
        assert b"400" in data.split(b"\r\n")[0]

    def test_hot_reload_serves_new_round(self, stack, experiment_dir):
        """A new best_rd_1 appearing (a live experiment finishing its
        next round) is picked up between batches: responses flip to the
        new round's weights without a restart."""
        rows = stack.al_set.gather(np.arange(2))
        _, before, _ = stack.post("/v1/score",
                                  {"instances": rows.tolist()})
        assert before["round"] == 0
        # Perturb the head bias hard enough to change every margin.
        best, _ = ckpt_lib.latest_best_ckpt(experiment_dir)
        variables = ckpt_lib.load_variables(best)
        variables["params"]["linear"]["bias"] = (
            np.asarray(variables["params"]["linear"]["bias"])
            + np.array([5.0, -5.0, 0.0, 0.0], np.float32))
        ckpt_lib.save_variables(
            os.path.join(experiment_dir, "best_rd_1.msgpack"), variables)
        try:
            _, after, _ = stack.post("/v1/score",
                                     {"instances": rows.tolist()})
            assert after["round"] == 1
            assert after["scores"] != before["scores"]
            _, m = stack.get("/metrics")
            assert m["executor"]["reloads"] == 1
        finally:
            os.remove(os.path.join(experiment_dir, "best_rd_1.msgpack"))


class TestBackpressure:
    def test_429_with_retry_after_then_completion(self, experiment_dir):
        """With the device loop held, admission fills queue_depth and
        the NEXT request gets 429 + Retry-After; releasing the executor
        completes the admitted requests with 200 — overflow never
        cancels admitted work."""
        _, _, al_set = get_data_synthetic(n_train=64, n_test=16,
                                          num_classes=4,
                                          image_size=IMG[0], seed=3)
        ex = DeviceExecutor(TinyClassifier(num_classes=4), al_set.view,
                            mesh_lib.make_mesh(), image_shape=IMG,
                            ckpt_dir=experiment_dir)
        st = _Stack(ex, ServeConfig(port=0, max_batch=8,
                                    max_latency_ms=5.0, queue_depth=8,
                                    bucket_floor=8),
                    start_executor=False)
        try:
            rows = al_set.gather(np.arange(4)).tolist()
            results = {}

            def bg(key):
                results[key] = st.post("/v1/score", {"instances": rows},
                                       timeout=60)

            threads = [threading.Thread(target=bg, args=(i,), daemon=True)
                       for i in range(2)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 5
            while st.server.batcher.pending_rows < 8:
                assert time.monotonic() < deadline, "admission stalled"
                time.sleep(0.01)
            status, body, headers = st.post("/v1/score",
                                            {"instances": rows})
            assert status == 429
            assert headers.get("Retry-After") == "1"
            assert "error" in body
            # Release the device loop: admitted requests must complete.
            ex._real_start()
            for t in threads:
                t.join(timeout=60)
            assert {s for s, _, _ in results.values()} == {200}
        finally:
            st.close()


class TestRobustness:
    def test_oversize_request_gets_413_not_429(self, stack):
        """A request larger than queue_depth could NEVER be admitted:
        it must get a non-retryable 413 at the door, not a 429 a
        compliant client would retry forever."""
        depth = stack.server.cfg.queue_depth
        rows = np.zeros((depth + 1, *IMG), np.uint8)
        import base64
        status, body, headers = stack.post("/v1/score", {
            "b64": base64.b64encode(rows.tobytes()).decode(),
            "shape": list(rows.shape)})
        assert status == 413
        assert "queue_depth" in body["error"]
        assert "Retry-After" not in headers

    def test_failed_chunk_releases_only_its_rows(self):
        """Per-chunk admission release: when one chunk of a multi-chunk
        request fails while siblings are still pending, only the failed
        chunk's rows free up — the queued+in-flight bound holds."""
        async def run():
            d = _EchoDispatch(auto_resolve=False)
            b = _make_batcher(d, max_batch=4, queue_depth=64,
                              max_latency_ms=5.0)
            task = asyncio.ensure_future(b.submit(_rows(10)))  # 3 chunks
            await asyncio.sleep(0.05)
            assert b.pending_rows == 10
            # Fail the FIRST chunk only; the other two stay in flight.
            host, entries = d.pending.pop(0)
            for e in entries:
                e.future.set_exception(RuntimeError("boom"))
            await asyncio.sleep(0.02)
            assert b.pending_rows == 10 - entries[0].n  # partial release
            d.resolve_all()
            with pytest.raises(RuntimeError):
                await task
            await asyncio.sleep(0.02)
            assert b.pending_rows == 0  # everything released in the end
            return True

        assert asyncio.run(run())

    def test_shard_failure_fails_batch_not_executor(self, monkeypatch):
        """One transient H2D failure rejects ITS batch's futures and the
        executor keeps serving — it must never die with futures
        hanging."""
        from active_learning_tpu.serve import executor as ex_mod

        ex, _ = _make_executor()
        real_shard = ex_mod.mesh_lib.shard_batch
        boom = {"left": 1}

        def flaky(batch, mesh):
            if boom["left"]:
                boom["left"] -= 1
                raise RuntimeError("transient device_put failure")
            return real_shard(batch, mesh)

        monkeypatch.setattr(ex_mod.mesh_lib, "shard_batch", flaky)
        ex.start()
        loop = asyncio.new_event_loop()
        try:
            f1, f2 = loop.create_future(), loop.create_future()
            host = {"image": _rows(8), "mask": np.ones(8, np.float32)}

            class E:
                def __init__(self, fut):
                    self.future, self.n, self.offset = fut, 8, 0
                    self.want_embed = False

            ex.submit_batch(dict(host), [E(f1)], False)
            ex.submit_batch(dict(host), [E(f2)], False)

            async def wait_both():
                r1 = await asyncio.wait_for(
                    asyncio.shield(_swallow(f1)), 30)
                r2 = await asyncio.wait_for(
                    asyncio.shield(_swallow(f2)), 30)
                return r1, r2

            r1, r2 = loop.run_until_complete(wait_both())
            # First batch rejected with the transient error...
            assert isinstance(r1, RuntimeError)
            # ...second batch served normally by the SAME executor.
            assert isinstance(r2, dict) and "margin" in r2
        finally:
            ex.stop()
            loop.close()


async def _swallow(fut):
    try:
        return await fut
    except Exception as e:  # noqa: BLE001 - the exception IS the result
        return e


class TestGracefulDrain:
    def test_drain_completes_inflight_requests(self, experiment_dir):
        """Drain with work queued and the device loop held: the drain
        blocks, the executor release completes the request with 200,
        then the drain finishes and new connections are refused."""
        _, _, al_set = get_data_synthetic(n_train=64, n_test=16,
                                          num_classes=4,
                                          image_size=IMG[0], seed=3)
        ex = DeviceExecutor(TinyClassifier(num_classes=4), al_set.view,
                            mesh_lib.make_mesh(), image_shape=IMG,
                            ckpt_dir=experiment_dir)
        st = _Stack(ex, ServeConfig(port=0, max_batch=8,
                                    max_latency_ms=5.0, queue_depth=64,
                                    bucket_floor=8),
                    start_executor=False)
        rows = al_set.gather(np.arange(2)).tolist()
        result = {}

        def bg():
            result["r"] = st.post("/v1/score", {"instances": rows},
                                  timeout=60)

        t = threading.Thread(target=bg, daemon=True)
        t.start()
        deadline = time.monotonic() + 5
        while st.server.batcher.pending_rows < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        drain = asyncio.run_coroutine_threadsafe(st.server.drain(),
                                                 st.loop)
        time.sleep(0.1)
        assert not drain.done()  # waiting on the in-flight request
        ex._real_start()
        drain.result(timeout=60)
        t.join(timeout=60)
        status, resp, _ = result["r"]
        assert status == 200 and len(resp["scores"]) == 2  # never dropped
        # Post-drain: the listener is closed (refused) or answers 503.
        try:
            status, _, _ = st.post("/v1/score", {"instances": rows},
                                   timeout=5)
            assert status == 503
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        st.loop.call_soon_threadsafe(st.loop.stop)
        st.thread.join(timeout=10)


_SIGTERM_CHILD = r"""
import asyncio, os, sys, numpy as np
sys.path.insert(0, {repo!r}); sys.path.insert(0, {tests!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from helpers import TinyClassifier
from active_learning_tpu.config import ServeConfig
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.serve.cli import _serve_until_signal
from active_learning_tpu.serve.executor import DeviceExecutor
from active_learning_tpu.serve.server import ScoringServer

_, _, al_set = get_data_synthetic(n_train=16, n_test=8, num_classes=4,
                                  image_size=8, seed=3)
ex = DeviceExecutor(TinyClassifier(num_classes=4), al_set.view,
                    mesh_lib.make_mesh(), image_shape=(8, 8, 3),
                    ckpt_dir={exp_dir!r})
server = ScoringServer(ex, ServeConfig(port=0, max_batch=8,
                                       max_latency_ms=5.0))

async def main():
    task = asyncio.ensure_future(_serve_until_signal(server))
    while server.port is None:
        await asyncio.sleep(0.01)
    print(f"PORT={{server.port}}", flush=True)
    await task
    print("DRAINED", flush=True)

asyncio.run(main())
"""


class TestSigterm:
    def test_sigterm_drains_and_exits_zero(self, experiment_dir):
        """The CLI's signal path end to end in a real process: serve,
        answer a request, SIGTERM, drain cleanly, exit 0."""
        code = _SIGTERM_CHILD.format(
            repo=os.path.dirname(os.path.dirname(os.path.abspath(
                __file__))),
            tests=os.path.dirname(os.path.abspath(__file__)),
            exp_dir=experiment_dir)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True,
                                env=env)
        try:
            port = None
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                line = proc.stdout.readline()
                if line.startswith("PORT="):
                    port = int(line.strip().split("=")[1])
                    break
            assert port, "server never reported its port"
            rows = np.zeros((2, *IMG), np.uint8)
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/score",
                data=json.dumps({"instances": rows.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.status == 200
            proc.send_signal(signal.SIGTERM)
            out, err = proc.communicate(timeout=60)
            assert proc.returncode == 0, err[-2000:]
            assert "DRAINED" in out
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()


# ---------------------------------------------------------------------------
# CLI verb + experiment-dir resolution
# ---------------------------------------------------------------------------

class TestServeCli:
    def test_verb_routes_from_main_cli(self, tmp_path):
        """`python -m active_learning_tpu serve ...` reaches the serve
        CLI (and its argument errors), not the experiment parser."""
        from active_learning_tpu.experiment.cli import main

        with pytest.raises(SystemExit) as exc:
            main(["serve", "--experiment_dir", str(tmp_path / "nope"),
                  "--compilation_cache_dir", ""])
        assert "best_rd" in str(exc.value)

    def test_resolution_from_experiment_dir(self, experiment_dir):
        """Dataset/model come from the saved config echo; num_classes
        from the checkpoint's own head; image size from the dataset."""
        from active_learning_tpu.serve.cli import (get_parser,
                                                   resolve_serve_setup)

        args = get_parser().parse_args(
            ["--experiment_dir", experiment_dir, "--image_size", "8"])
        model, variables, view, image_size, exp_dir = \
            resolve_serve_setup(args)
        assert exp_dir == experiment_dir
        assert image_size == 8
        assert variables["params"]["linear"]["bias"].shape == (4,)
        assert view.augment is False

    def test_missing_dir_exits_loudly(self):
        from active_learning_tpu.serve.cli import (get_parser,
                                                   resolve_serve_setup)

        args = get_parser().parse_args([])
        with pytest.raises(SystemExit):
            resolve_serve_setup(args)

    def test_stem_resolution_follows_config_echo(self, tmp_path):
        """An experiment trained with --stem s2d saved a FOLDED stem
        kernel; the serve model must be built with the same stem (and
        the executor fed space-to-depth input) or warmup dies on the
        param-shape mismatch."""
        from active_learning_tpu.serve.cli import (get_parser,
                                                   resolve_serve_setup)

        exp = tmp_path / "exp_s2d"
        exp.mkdir()
        ckpt_lib.save_variables(
            str(exp / "best_rd_0.msgpack"),
            {"params": {"linear": {"bias": np.zeros(7, np.float32)}}})
        (exp / "experiment_state.json").write_text(json.dumps({
            "round": 0,
            "config": {"dataset": "imagenet", "model": "SSLResNet50",
                       "arg_pool": "default", "stem": "s2d"}}))
        args = get_parser().parse_args(["--experiment_dir", str(exp)])
        model, variables, _view, image_size, _ = resolve_serve_setup(args)
        assert getattr(model, "stem", None) == "s2d"
        assert image_size == 224
        assert variables["params"]["linear"]["bias"].shape == (7,)


class TestHostS2d:
    def test_executor_transforms_input_host_side(self):
        """host_s2d executors accept client-shaped (H, W, 3) rows and
        feed the step the space-to-depth layout — same transform as the
        offline pipeline (TinyClassifier flattens, so the step accepts
        either layout; what's pinned is that the transform HAPPENED and
        the scores equal a hand-applied space_to_depth forward)."""
        from active_learning_tpu.data.pipeline import space_to_depth

        ex, al_set = _make_executor()
        ex.host_s2d = True
        ex.warmup([8])
        assert ex.request_path_compiles() == 0

        rows = al_set.gather(np.arange(3))
        host = {"image": np.concatenate([rows, rows[:1].repeat(5, 0)]),
                "mask": np.r_[np.ones(3, np.float32),
                              np.zeros(5, np.float32)]}
        dev, _entries, _we, exc = ex._put((host, [], False))
        assert exc is None
        assert dev["image"].shape == (8, 4, 4, 12)  # s2d happened
        out = ex._steps["prob_stats"](ex._variables, dev)
        # Oracle: the same step over a hand-transformed batch.
        ref = ex._steps["prob_stats"](
            ex._variables,
            mesh_lib.shard_batch(
                dict(host, image=space_to_depth(host["image"])),
                ex.mesh))
        assert np.array_equal(np.asarray(out["margin"])[:3],
                              np.asarray(ref["margin"])[:3])
        # Warmup covered the s2d shape: still zero request-path compiles.
        assert ex.request_path_compiles() == 0


# ---------------------------------------------------------------------------
# Under load: the closed-loop generator against the in-process server
# ---------------------------------------------------------------------------

class TestClosedLoopLoad:
    def test_load_after_warmup_adds_no_compile(self, stack):
        """Concurrent clients sending back to back (scripts/
        serve_loadgen.py's closed loop, the generator an operator points
        at a deployment) land partial and full batches in every bucket;
        every shape was compiled at startup, so the request path
        compiles nothing and every request is answered."""
        loadgen = load_script("serve_loadgen")
        url = f"http://127.0.0.1:{stack.port}"
        workers, rows, warm = 3, 3, 2
        out = loadgen.run_closed(url, 1.0, workers, rows, IMG,
                                 warmup_requests=warm)
        assert out["n_ok"] > 0 and out["n_err"] == 0 and out["n_429"] == 0
        assert out["p99_ms"] is not None
        _, m = stack.get("/metrics")
        assert m["compiles"]["request_path_compiles"] == 0
        assert m["rows_served"] == rows * (out["n_ok"] + workers * warm)
        assert m["batch_occupancy"]
