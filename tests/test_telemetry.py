"""Run-wide telemetry (active_learning_tpu/telemetry/, DESIGN.md §7):
span nesting + Chrome-trace validity, heartbeat atomicity + staleness,
the watchdog on a frozen fake clock, Prometheus exposition, the
telemetry-off no-per-step-work contract, the status verb, trace_lint,
and the end-to-end CPU-mesh smoke run the acceptance criteria pin."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from active_learning_tpu.telemetry import heartbeat as hb_lib
from active_learning_tpu.telemetry import prom as prom_lib
from active_learning_tpu.telemetry import runtime as rt_lib
from active_learning_tpu.telemetry import spans as spans_lib
from active_learning_tpu.telemetry import status as status_lib

REPO = os.path.join(os.path.dirname(__file__), "..")


class TestSpanTracer:
    def test_nesting_and_chrome_trace_validity(self, tmp_path):
        tracer = spans_lib.SpanTracer(enabled=True)
        with tracer.span("experiment", args={"exp": "t"}):
            assert tracer.depth() == 1
            for rd in range(2):
                with tracer.span("round", args={"round": rd}):
                    with tracer.span("train_time", args={"round": rd}):
                        with tracer.span("epoch", args={"epoch": 1}):
                            assert tracer.depth() == 4
        assert tracer.depth() == 0
        path = str(tmp_path / "trace.json")
        assert tracer.export(path) == path

        with open(path) as fh:
            trace = json.load(fh)  # strict JSON
        events = trace["traceEvents"]
        assert {e["name"] for e in events} == {"experiment", "round",
                                               "train_time", "epoch"}
        for e in events:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], float) and isinstance(e["dur"],
                                                             float)
            assert e["dur"] >= 0 and "pid" in e and "tid" in e
        # Interval nesting: every child lies inside its parent's span.
        by_name = {e["name"]: e for e in events}
        exp = by_name["experiment"]
        for name in ("round", "train_time", "epoch"):
            child = by_name[name]
            assert child["ts"] >= exp["ts"] - 1e-6
            assert (child["ts"] + child["dur"]
                    <= exp["ts"] + exp["dur"] + 1e-6)

    def test_disabled_tracer_still_times_but_records_nothing(self):
        tracer = spans_lib.SpanTracer(enabled=False)
        with tracer.span("phase") as sp:
            time.sleep(0.01)
        assert sp.duration_s >= 0.01
        assert tracer.events == []

    def test_complete_and_instant_and_cap(self, tmp_path):
        tracer = spans_lib.SpanTracer(enabled=True, max_events=2)
        t0 = time.perf_counter()
        tracer.complete("chunk", t0, t0 + 0.5, args={"rows": 32})
        tracer.instant("stall_suspected", args={"stalled_s": 3.0})
        tracer.complete("chunk", t0, t0 + 1.0)  # over the cap: dropped
        assert len(tracer.events) == 2 and tracer.dropped == 1
        path = str(tmp_path / "t.json")
        tracer.export(path)
        with open(path) as fh:
            out = json.load(fh)
        assert out["otherData"]["dropped_events"] == 1

    def test_thread_safety_of_event_buffer(self):
        tracer = spans_lib.SpanTracer(enabled=True)

        def worker():
            for _ in range(200):
                with tracer.span("w"):
                    pass

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tracer.events) == 800


class TestHeartbeat:
    def test_tick_writes_atomic_json_and_rate_limits(self, tmp_path):
        path = str(tmp_path / "heartbeat.json")
        clock = {"t": 100.0}
        hb = hb_lib.HeartbeatWriter(path, every_s=5.0,
                                    stall_deadline_s=60.0,
                                    monotonic_fn=lambda: clock["t"])
        assert hb.tick(round=0, phase="query") is True
        first = hb_lib.read_heartbeat(path)
        assert first["round"] == 0 and first["phase"] == "query"
        assert first["progress"] == 1
        assert first["stall_deadline_s"] == 60.0
        # Within the cadence: progress advances, file does not.
        clock["t"] += 1.0
        assert hb.tick(round=0, phase="train", epoch=3) is False
        assert hb_lib.read_heartbeat(path)["phase"] == "query"
        assert hb.progress == 2
        # force=True (phase transitions) writes regardless.
        assert hb.tick(force=True, phase="test") is True
        now = hb_lib.read_heartbeat(path)
        assert now["phase"] == "test" and now["epoch"] == 3
        # No torn temp files left behind.
        assert [f for f in os.listdir(tmp_path)
                if f.startswith("heartbeat.json.tmp")] == []

    def test_staleness_from_mtime_vs_embedded_deadline(self, tmp_path):
        path = str(tmp_path / "heartbeat.json")
        hb = hb_lib.HeartbeatWriter(path, every_s=0.0,
                                    stall_deadline_s=30.0)
        hb.tick(round=1)
        assert hb_lib.is_stale(path) is False
        # Age the FILE (the mtime is the contract, not the payload ts).
        old = time.time() - 100.0
        os.utime(path, (old, old))
        assert hb_lib.is_stale(path) is True          # 100s > 30s
        assert hb_lib.is_stale(path, deadline_s=1000.0) is False
        assert hb_lib.is_stale(str(tmp_path / "absent.json")) is None
        age = hb_lib.heartbeat_age_s(path)
        assert age == pytest.approx(100.0, abs=5.0)

    def test_watchdog_fires_once_per_stall_on_fake_clock(self, tmp_path):
        clock = {"t": 0.0}
        hb = hb_lib.HeartbeatWriter(str(tmp_path / "hb.json"), every_s=0.0,
                                    monotonic_fn=lambda: clock["t"])
        stalls = []
        wd = hb_lib.StallWatchdog(hb, deadline_s=10.0,
                                  on_stall=stalls.append,
                                  monotonic_fn=lambda: clock["t"])
        hb.tick(round=0)
        clock["t"] = 5.0
        assert wd.check() is False          # under the deadline
        clock["t"] = 11.0
        assert wd.check() is False          # progress moved at t=0... still
        clock["t"] = 12.0
        hb.tick(round=0)                    # progress resumes
        assert wd.check() is False
        clock["t"] = 23.0                   # frozen 11s > 10s deadline
        assert wd.check() is True
        assert len(stalls) == 1 and stalls[0] > 10.0
        clock["t"] = 24.0                   # inside the fire's window
        assert wd.check() is False
        clock["t"] = 40.0                   # STILL stalled one more full
        assert wd.check() is True           # deadline: fires again (the
        assert stalls[1] > 25.0             # fixed re-arm edge; reports
        hb.tick(round=1)                    # the TOTAL stall), and
        clock["t"] = 41.0                   # progress still re-arms
        assert wd.check() is False
        clock["t"] = 60.0
        assert wd.check() is True           # next episode fires again
        assert wd.stalls_detected == 3


class TestPrometheus:
    def test_render_parses_and_round_trips(self):
        text = prom_lib.render([
            ("al_run_round", None, 3),
            ("al_serve_requests_total", {"endpoint": "/v1/score"}, 17),
            ("al_serve_requests_total", {"endpoint": "/v1/predict"}, 4),
            ("al_serve_request_latency_ms", {"quantile": "0.99"}, 12.75),
            ("weird-name.with dots", None, 1.5),
            ("dropped_none", None, None),
            ("bool_gauge", None, True),
        ])
        parsed = prom_lib.parse(text)
        assert parsed["al_run_round"][()] == 3
        assert parsed["al_serve_requests_total"][
            (("endpoint", "/v1/score"),)] == 17
        assert parsed["al_serve_request_latency_ms"][
            (("quantile", "0.99"),)] == 12.75
        assert parsed["weird_name_with_dots"][()] == 1.5
        assert parsed["bool_gauge"][()] == 1
        assert "dropped_none" not in parsed
        # One TYPE header per metric name, before its samples.
        assert text.count("# TYPE al_serve_requests_total gauge") == 1

    def test_label_escaping(self):
        text = prom_lib.render([("m", {"k": 'a"b\\c\nd'}, 1)])
        parsed = prom_lib.parse(text)
        assert parsed["m"][(("k", 'a"b\\c\nd'),)] == 1

    def test_serve_metrics_endpoint_prometheus_view(self):
        """GET /metrics?format=prometheus through the real router over a
        stub executor/batcher: valid exposition, text content type, and
        the serving contract (request_path_compiles) scrapable."""
        import asyncio

        from active_learning_tpu.config import ServeConfig
        from active_learning_tpu.serve.server import ScoringServer

        class StubExecutor:
            _lock = threading.Lock()
            stats = {"batches": 3, "rows": 170, "reloads": 1,
                     "warm_buckets": [8, 16]}
            served_round = 2

            def compile_counts(self):
                return {"prob_stats": 2, "embed": 2}

            def request_path_compiles(self):
                return 0

        class StubBatcher:
            pending_rows = 5
            buckets = (8, 16)

        server = ScoringServer(StubExecutor(), ServeConfig(queue_depth=64))
        server.batcher = StubBatcher()
        server.metrics.record_request("/v1/score")
        server.metrics.record_response(200, 0.012, rows=8)
        server.metrics.record_batch(8, 5)

        status, payload, headers = asyncio.run(
            server._route("GET", "/metrics?format=prometheus", b""))
        assert status == 200 and isinstance(payload, str)
        assert headers["Content-Type"].startswith("text/plain")
        parsed = prom_lib.parse(payload)
        assert parsed["al_serve_request_path_compiles"][()] == 0
        assert parsed["al_serve_served_round"][()] == 2
        assert parsed["al_serve_requests_total"][
            (("endpoint", "/v1/score"),)] == 1
        assert parsed["al_serve_batch_occupancy_total"][
            (("bucket", "8"), ("rows", "5"))] == 1
        assert parsed["al_serve_queue_pending_rows"][()] == 5
        # The JSON view is unchanged, and a junk format is a 400.
        status, payload, _ = asyncio.run(
            server._route("GET", "/metrics", b""))
        assert status == 200 and isinstance(payload, dict)
        status, _, _ = asyncio.run(
            server._route("GET", "/metrics?format=xml", b""))
        assert status == 400

    def test_scrape_file_write_is_atomic(self, tmp_path):
        path = str(tmp_path / "run.prom")
        assert prom_lib.write_textfile(path, "# TYPE a gauge\na 1\n")
        assert prom_lib.parse(open(path).read())["a"][()] == 1
        assert [f for f in os.listdir(tmp_path)
                if f.startswith("run.prom.tmp")] == []


class TestTelemetryOffPath:
    def test_default_runtime_is_inert(self, tmp_path):
        rt = rt_lib.get_run()
        assert rt.train_metrics is False
        rt.tick(round=1)                      # no heartbeat, no file
        rt.register_jit("x", lambda: None)    # no registry growth
        assert rt.jit_cache_sizes() == {}
        assert rt.export_trace() is None
        assert os.listdir(tmp_path) == []
        assert spans_lib.get_tracer().enabled is False

    def test_fit_emits_no_step_metrics_when_off(self, tmp_path):
        """With no run installed, the trainer's metric_cb sees exactly
        the pre-telemetry names — no step_time/imgs_per_sec/EMA series,
        no per-step timing work."""
        import dataclasses

        import jax

        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.train import checkpoint as ckpt_lib
        from active_learning_tpu.train.trainer import Trainer
        from helpers import TinyClassifier, tiny_train_config

        train_set, _, al_set = get_data_synthetic(
            n_train=32, n_test=8, num_classes=4, image_size=8, seed=3)
        cfg = dataclasses.replace(tiny_train_config(batch_size=16),
                                  device_resident=False)
        trainer = Trainer(TinyClassifier(), cfg, mesh_lib.make_mesh(),
                          num_classes=4, train_bn=True)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.arange(2)))
        names = []
        trainer.fit(state, train_set, np.arange(24), al_set,
                    np.arange(24, 32), n_epoch=2, es_patience=2,
                    rng=np.random.default_rng(0), round_idx=0,
                    weight_paths=ckpt_lib.weight_paths(
                        str(tmp_path), "t", "off", 0),
                    metric_cb=lambda n, v, s: names.append(n))
        assert not any(n.startswith(("step_time", "imgs_per_sec",
                                     "train_loss_ema", "grad_norm_ema"))
                       for n in names)
        assert any("validation_accuracy" in n for n in names)

    def test_per_step_record_cost_supports_overhead_budget(self, tmp_path):
        """The default-on per-step work is a perf_counter delta + list
        append + rate-limited heartbeat tick.  Bound it hard: 10k
        simulated steps well under 0.5 s total (<50 µs/step — noise
        against ms-scale real steps: the DESIGN §7 overhead budget)."""
        hb = hb_lib.HeartbeatWriter(str(tmp_path / "hb.json"),
                                    every_s=3600.0)
        t0 = time.perf_counter()
        times = []
        prev = time.perf_counter()
        for i in range(10_000):
            now = time.perf_counter()
            times.append(now - prev)
            prev = now
            hb.tick(epoch=1, step=i)
        assert time.perf_counter() - t0 < 0.5
        assert len(times) == 10_000


class TestRunTelemetryLifecycle:
    def test_start_finish_install_uninstall(self, tmp_path):
        from active_learning_tpu.config import TelemetryConfig

        cfg = TelemetryConfig(enabled=True, export_trace=True,
                              watchdog=True, heartbeat_every_s=0.0,
                              stall_deadline_s=60.0,
                              prometheus_file=str(tmp_path / "g.prom"))
        rt = rt_lib.start_run(cfg, log_dir=str(tmp_path))
        try:
            assert rt_lib.get_run() is rt
            assert spans_lib.get_tracer() is rt.tracer
            assert rt.train_metrics is True
            with spans_lib.get_tracer().span("experiment"):
                rt.tick(round=0, phase="query")
            rt.set_gauges(round=0, imgs_per_sec=123.4)
        finally:
            rt.finish("finished")
            rt_lib.uninstall(rt)
        hb = hb_lib.read_heartbeat(str(tmp_path / "heartbeat.json"))
        assert hb["status"] == "finished" and hb["round"] == 0
        trace = json.load(open(tmp_path / "trace.json"))
        assert trace["otherData"]["status"] == "finished"
        parsed = prom_lib.parse(open(tmp_path / "g.prom").read())
        assert parsed["al_run_imgs_per_sec"][()] == pytest.approx(123.4)
        # Uninstalled: back to the inert default.
        assert rt_lib.get_run().train_metrics is False
        assert spans_lib.get_tracer().enabled is False

    def test_disabled_config_installs_inert_runtime(self, tmp_path):
        from active_learning_tpu.config import TelemetryConfig

        rt = rt_lib.start_run(TelemetryConfig(enabled=False),
                              log_dir=str(tmp_path))
        try:
            assert rt.train_metrics is False
            assert rt.heartbeat is None
            rt.tick(round=1)
            assert os.listdir(tmp_path) == []
        finally:
            rt.finish()
            rt_lib.uninstall(rt)

    def test_multiprocess_heartbeat_filename(self):
        assert hb_lib.heartbeat_filename(0, 1) == "heartbeat.json"
        assert hb_lib.heartbeat_filename(0, 4) == "heartbeat_p0.json"
        assert hb_lib.heartbeat_filename(3, 4) == "heartbeat_p3.json"


class TestEndToEndSmoke:
    """The acceptance-criteria smoke: a CPU-mesh synthetic run with
    telemetry on produces (a) nested Chrome-trace spans, (b) a fresh
    heartbeat the status verb flags stale once its mtime ages past the
    deadline, (c) per-epoch step_time_ms_p50/p99 + imgs_per_sec in
    metrics.jsonl."""

    @pytest.fixture(scope="class")
    def smoke_run(self, tmp_path_factory):
        from active_learning_tpu.config import (ExperimentConfig,
                                                TelemetryConfig)
        from active_learning_tpu.experiment.driver import run_experiment

        tmp = str(tmp_path_factory.mktemp("tele_smoke"))
        cfg = ExperimentConfig(
            dataset="synthetic", arg_pool="synthetic",
            strategy="MarginSampler", rounds=2, round_budget=16,
            n_epoch=2, early_stop_patience=2, log_dir=tmp, ckpt_path=tmp,
            exp_hash="telesmoke",
            telemetry=TelemetryConfig(enabled=True, export_trace=True,
                                      watchdog=True,
                                      heartbeat_every_s=0.0,
                                      stall_deadline_s=120.0))
        run_experiment(cfg)
        return tmp

    def test_trace_json_is_valid_and_nested(self, smoke_run):
        trace = json.load(open(os.path.join(smoke_run, "trace.json")))
        events = trace["traceEvents"]
        names = {e["name"] for e in events}
        # The span tree of DESIGN §7: experiment → round → phase → the
        # work inside the phase.  This run scores from the pinned pool:
        # collect_pool_chunk lives on the stream path only, where a
        # chunk ends at a real fetch (tests/test_span_tree.py asserts it
        # there).
        for expected in ("experiment", "round", "round_epilogue",
                         "train_time", "test_time", "query_time", "epoch",
                         "collect_pool", "fit/validate",
                         "ckpt/publish_best", "test/evaluate"):
            assert expected in names, f"missing span {expected!r}"
        assert "collect_pool_chunk" not in names
        spans = {e["name"]: e for e in events}
        exp = spans["experiment"]
        for e in events:
            if e.get("ph") == "M":
                # Metadata events (the pipelined round's thread_name
                # track labels) carry no timestamp by the trace-event
                # spec.
                continue
            assert e["ts"] >= exp["ts"] - 1e-6
            assert (e["ts"] + e.get("dur", 0.0)
                    <= exp["ts"] + exp["dur"] + 1e-6)
        rounds = [e for e in events if e["name"] == "round"]
        assert len(rounds) == 2
        # Every epoch span nests inside some train phase span.
        trains = [e for e in events if e["name"] == "train_time"]
        for ep in (e for e in events if e["name"] == "epoch"):
            assert any(t["ts"] <= ep["ts"]
                       and ep["ts"] + ep["dur"] <= t["ts"] + t["dur"] + 1e-6
                       for t in trains)

    def test_heartbeat_fresh_then_stale_via_status(self, smoke_run):
        hb_path = os.path.join(smoke_run, "heartbeat.json")
        hb = hb_lib.read_heartbeat(hb_path)
        assert hb["status"] == "finished"
        assert hb["round"] == 1
        summary = status_lib.summarize(smoke_run)
        assert summary["state"] == "ok"  # finished runs are never stale
        # A RUNNING heartbeat whose mtime ages past the deadline reads
        # STALE through the same summarize path the CLI verb uses.
        hb_run = hb_lib.HeartbeatWriter(hb_path, every_s=0.0,
                                        stall_deadline_s=120.0)
        hb_run.tick(round=1, phase="train", status="running")
        old = time.time() - 1000.0
        os.utime(hb_path, (old, old))
        summary = status_lib.summarize(smoke_run)
        assert summary["state"] == "stale"
        assert summary["heartbeats"][0]["stale"] is True
        assert summary["metrics"].get("rd_test_accuracy") is not None
        text = status_lib.render_text(summary)
        assert "STALE" in text and "rd_test_accuracy" in text

    def test_per_epoch_telemetry_lands_in_metrics_jsonl(self, smoke_run):
        by_name = {}
        for line in open(os.path.join(smoke_run, "metrics.jsonl")):
            ev = json.loads(line)
            if ev.get("kind") == "metric":
                for k, v in ev["metrics"].items():
                    by_name.setdefault(k, []).append((ev.get("step"), v))
        for name in ("step_time_ms_p50", "step_time_ms_p99",
                     "imgs_per_sec", "train_loss_ema", "grad_norm_ema",
                     "pool_rows_per_sec", "jit_cache_miss_delta"):
            assert name in by_name, f"missing {name}"
        # 2 rounds x 2 epochs of step-time series, positive values,
        # p99 >= p50, monotonic round-folded step axis.
        p50 = by_name["step_time_ms_p50"]
        p99 = by_name["step_time_ms_p99"]
        assert len(p50) == 4 and len(p99) == 4
        steps = [s for s, _ in p50]
        assert steps == sorted(steps) and len(set(steps)) == 4
        assert all(v > 0 for _, v in p50)
        assert all(q >= p for (_, p), (_, q) in zip(p50, p99))
        assert all(v > 0 for _, v in by_name["imgs_per_sec"])
        assert all(v > 0 for _, v in by_name["grad_norm_ema"])
        # Warm rounds must not compile: the round-1 miss delta is 0.
        deltas = dict(by_name["jit_cache_miss_delta"])
        assert deltas[1] == 0, f"round-1 jit cache misses: {deltas[1]}"

    def test_status_cli_subprocess_no_jax(self, smoke_run):
        """The status verb answers from a plain subprocess — and never
        imports jax (it must work against a wedged run)."""
        code = (
            "import sys\n"
            "from active_learning_tpu.telemetry.status import main\n"
            f"rc = main(['--log_dir', {smoke_run!r}, '--json'])\n"
            "assert 'jax' not in sys.modules, 'status imported jax'\n"
            "sys.exit(rc)\n")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=60,
            cwd=os.path.abspath(REPO))
        assert proc.returncode in (0, 3), proc.stderr[-2000:]
        out = json.loads(proc.stdout)
        assert out["heartbeats"]


class TestTraceLint:
    def test_trace_lint_passes_from_tier1(self):
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_lint.py")],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr

    # The negative case, without polluting the real tree:
    def test_lint_logic_flags_competing_definition(self, tmp_path,
                                                   monkeypatch):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)
        bad = tmp_path / "rogue.py"
        bad.write_text("def phase_timer(name):\n    return name\n")
        monkeypatch.setattr(
            lint, "_py_files",
            lambda: [str(bad)])
        problems = lint.check()
        assert any("defines its own phase_timer" in p for p in problems)

    def test_lint_flags_host_copies_on_resident_feed_path(self, tmp_path):
        """The zero-host-copy invariant (DESIGN.md §2a): a resident-feed
        function that materializes image arrays on the host (np.*, a
        .gather()/.asarray() call) must fail the lint, and deleting the
        function entirely must too — the enforcement cannot be renamed
        away."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)

        bad = tmp_path / "trainer.py"
        bad.write_text(
            "import numpy as np\n"
            "def _resident_feed_arrays(self, train_set):\n"
            "    rows = np.asarray(train_set.gather(self.idxs))\n"
            "    return rows, None\n")
        problems = lint.check_resident_feed(str(bad))
        assert any("references np" in p for p in problems)
        assert any(".gather()" in p for p in problems)

        empty = tmp_path / "empty_trainer.py"
        empty.write_text("def unrelated():\n    pass\n")
        problems = lint.check_resident_feed(str(empty))
        assert any("not found" in p for p in problems)

        # The REAL trainer is clean (also covered by the subprocess run
        # above, but pinned here against the specific check).
        assert lint.check_resident_feed() == []

    def test_lint_flags_unsharding_on_sharded_selection_path(self,
                                                             tmp_path):
        """The sharded pool's scale-out invariant (check 6, DESIGN.md
        §2b): a sharded-selection function that pulls the pool to host
        (np in the device tier, jax.device_get anywhere) or replicates
        a row-sharded array must fail the lint; deleting a function
        drops to 'not found' — the enforcement cannot be renamed away."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)

        bad = tmp_path / "kcenter.py"
        bad.write_text(
            "import numpy as np\n"
            "import jax\n"
            "def _build_sharded_fns(mesh, nf):\n"
            "    rows = np.asarray(jax.device_get(mesh))\n"
            "    return rows\n"
            "def _kcenter_greedy_sharded(factors, mask, budget):\n"
            "    full = jax.device_get(factors)\n"
            "    rep = mesh_lib.replicate(factors, None)\n"
            "    return full, rep\n")
        problems = lint.check_sharded_selection(str(bad))
        assert any("references np" in p for p in problems)
        assert any(".device_get()" in p or "device_get" in p
                   for p in problems)
        assert any("replicate()" in p for p in problems)

        # The orchestrator tier ALLOWS np (it owns the host factor
        # copy) — only fetches/replication are flagged there.
        ok_np = tmp_path / "kcenter_np_ok.py"
        ok_np.write_text(
            "import numpy as np\n"
            "def _build_sharded_fns(mesh, nf):\n"
            "    return mesh\n"
            "def _kcenter_greedy_sharded(factors, mask, budget):\n"
            "    return np.flatnonzero(mask)\n")
        assert lint.check_sharded_selection(str(ok_np)) == []

        empty = tmp_path / "empty_kcenter.py"
        empty.write_text("def unrelated():\n    pass\n")
        problems = lint.check_sharded_selection(str(empty))
        assert any("not found" in p for p in problems)

    def test_lint_flags_train_stream_sync_in_pipeline_coordinator(
            self, tmp_path):
        """The pipelined round's never-sync-the-train-stream invariant
        (check 7, DESIGN.md §8): a coordinator function calling
        block_until_ready or device_get must fail the lint, and deleting
        a coordinator function drops to 'not found' — the enforcement
        cannot be renamed away."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)

        bad = tmp_path / "pipeline.py"
        bad.write_text(
            "import jax\n"
            "def _worker(self):\n"
            "    jax.block_until_ready(self.out)\n"
            "def _worker_loop(self):\n"
            "    pass\n"
            "def _score_slice(self, plan, sl, variables):\n"
            "    return jax.device_get(variables)\n"
            "def _score_chunk(self, plan, sl, tag, variables, i):\n"
            "    return None\n"
            "def publish_best(self, r, e, v):\n"
            "    pass\n"
            "def finalize(self, r, e):\n"
            "    pass\n"
            "def consume(self, kind, keys, idxs, bs, variables):\n"
            "    return None\n")
        problems = lint.check_pipeline_coordinator(str(bad))
        assert any("_worker" in p and "block_until_ready" in p
                   for p in problems)
        assert any("_score_slice" in p and "device_get" in p
                   for p in problems)
        assert len(problems) == 2  # the clean coordinators stay clean

        # Renaming a coordinator away is itself a finding.
        missing = tmp_path / "pipeline_missing.py"
        missing.write_text("def unrelated():\n    pass\n")
        problems = lint.check_pipeline_coordinator(str(missing))
        assert any("not found" in p for p in problems)

        # The REAL pipeline module is clean, and the lint's fn list
        # mirrors the module's own (kept in both places so the lint
        # works without importing jax).
        assert lint.check_pipeline_coordinator() == []
        from active_learning_tpu.experiment import pipeline as pipe_lib
        assert tuple(lint.PIPELINE_COORDINATOR_FNS) == tuple(
            pipe_lib.PIPELINE_COORDINATOR_FNS)

        # The REAL backend is clean, and the module's own fn list stays
        # in lockstep with the lint's mirror (renames can't silently
        # drop enforcement on either side).
        assert lint.check_sharded_selection() == []
        from active_learning_tpu.strategies import kcenter as kc
        assert set(kc.SHARDED_SELECTION_FNS) == set(
            lint.SHARDED_DEVICE_FNS + lint.SHARDED_ORCHESTRATOR_FNS)

    def test_lint_flags_fault_site_violations(self, tmp_path):
        """The failure model's closed-registry invariant (check 8,
        DESIGN.md §10): an unregistered site name, a non-literal site
        name, and a RetryPolicy without an explicit classify= must each
        fail the lint; duplicate registration and a registered-but-
        never-wired site are findings too."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)

        bad = tmp_path / "bad_sites.py"
        bad.write_text(
            "from active_learning_tpu import faults\n"
            "def upload(name):\n"
            "    faults.site('h2d_uplaod')\n"          # typo'd site
            "    faults.site(name)\n"                  # non-literal
            "    faults.site('ckpt_write')\n"          # fine
            "    p = faults.RetryPolicy(site='x')\n"   # no classify=
            "    q = faults.RetryPolicy(site='y', "
            "classify=faults.classify_exception)\n")   # fine
        problems = lint.check_fault_sites([str(bad)])
        assert any("unregistered site" in p and "h2d_uplaod" in p
                   for p in problems)
        assert any("non-literal site name" in p for p in problems)
        assert any("without an explicit classify=" in p for p in problems)
        assert len(problems) == 3  # the two clean calls stay clean

        # Duplicate registration is a finding against the registry.
        dup_reg = tmp_path / "dup_registry.py"
        dup_reg.write_text("SITES = ('a', 'b', 'a')\n")
        problems = lint.check_fault_sites([str(bad)],
                                          registry_path=str(dup_reg))
        assert any("registered more than once" in p for p in problems)

        # Full-tree mode: a registered site wired at no call site makes
        # its chaos coverage vacuous.
        lone = tmp_path / "lone_registry.py"
        lone.write_text("SITES = ('never_wired',)\n")
        orig = lint._py_files
        try:
            lint._py_files = lambda: [str(bad)]
            problems = lint.check_fault_sites(
                registry_path=str(lone))
        finally:
            lint._py_files = orig
        assert any("never_wired" in p and "wired at no call site" in p
                   for p in problems)

        # The REAL tree is clean against the REAL registry, and the
        # lint's view of the registry matches the package's.
        assert lint.check_fault_sites() == []
        from active_learning_tpu import faults
        assert tuple(lint._registered_fault_sites(
            lint.FAULTS_REGISTRY, [])) == tuple(faults.SITES)

    def test_lint_flags_stray_jax_profiler_use(self, tmp_path):
        """The device-truth layer's one-gate invariant (check 10,
        DESIGN.md §11): importing jax.profiler, touching the
        jax.profiler attribute, or calling start_trace/stop_trace under
        ANY alias outside telemetry/profiler.py must each fail the
        lint — and the gate module itself must define the gated API and
        really import jax.profiler (the closed-registry handshake,
        matching check 9)."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)

        bad = tmp_path / "rogue_profiler.py"
        bad.write_text(
            "import jax.profiler\n"                      # direct import
            "from jax import profiler as jp\n"           # aliased import
            "def capture(d):\n"
            "    jax.profiler.start_trace(d)\n"          # attr + call
            "    jp.stop_trace()\n"                      # aliased call
            "def fine():\n"
            "    from active_learning_tpu.telemetry import profiler\n"
            "    with profiler.capture_window('/tmp/x'):\n"
            "        pass\n")
        problems = lint.check_profiler_confinement([str(bad)])
        assert any("imports jax.profiler" in p for p in problems)
        assert any("imports jax's profiler" in p for p in problems)
        assert any("touches jax.profiler" in p for p in problems)
        assert any("start_trace()" in p for p in problems)
        assert any("stop_trace()" in p for p in problems)
        # The gated-API path is clean — exactly the rogue uses flag.
        clean = tmp_path / "clean_caller.py"
        clean.write_text(
            "from active_learning_tpu.telemetry import profiler\n"
            "def go(d):\n"
            "    with profiler.capture_window(d):\n"
            "        pass\n")
        assert lint.check_profiler_confinement([str(clean)]) == []

        # A renamed-away gate makes the check vacuous: full-tree mode
        # verifies the module defines the API and touches jax.profiler.
        hollow = tmp_path / "hollow_gate.py"
        hollow.write_text("def unrelated():\n    pass\n")
        orig = lint._py_files
        try:
            lint._py_files = lambda: [str(clean)]
            problems = lint.check_profiler_confinement(
                profiler_path=str(hollow))
        finally:
            lint._py_files = orig
        assert any("gated API function" in p and "not found" in p
                   for p in problems)
        assert any("never imports jax.profiler" in p for p in problems)

        # The REAL tree is clean against the REAL gate.
        assert lint.check_profiler_confinement() == []

    def test_lint_flags_backward_registry_violations(self, tmp_path):
        """The gradient path's proven-backward invariant (check 9,
        DESIGN.md §4): a jax.custom_vjp outside ops/backward.py, a
        registry entry with no definition, a PARITY_TESTED_VJPS drift,
        and host materialization inside a fused-update function must
        each fail the lint."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_lint", os.path.join(REPO, "scripts", "trace_lint.py"))
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)

        # a) a custom VJP dodging the registry, flagged on a fragment.
        stray = tmp_path / "stray_vjp.py"
        stray.write_text(
            "import jax\n"
            "@jax.custom_vjp\n"
            "def sneaky(x):\n"
            "    return x\n")
        problems = lint.check_backward_registry([str(stray)])
        assert any("custom_vjp outside ops/backward.py" in p
                   for p in problems)

        # b) registry drift: a registered name with no definition.
        ops_bad = tmp_path / "ops_bad.py"
        ops_bad.write_text(
            "import jax\n"
            "TRAIN_PATH_VJPS = ('ghost',)\n"
            "@jax.custom_vjp\n"
            "def real(x):\n"
            "    return x\n")
        problems = lint.check_backward_registry(
            ops_path=str(ops_bad), optim_path=lint.OPTIM,
            tests_path=lint.BACKWARD_TESTS)
        assert any("'ghost'" in p and "no such function" in p
                   for p in problems)

        # c) a custom backward without a registered parity test.
        tests_bad = tmp_path / "tests_bad.py"
        tests_bad.write_text("PARITY_TESTED_VJPS = ('stem_conv',)\n")
        problems = lint.check_backward_registry(
            ops_path=lint.OPS_BACKWARD, optim_path=lint.OPTIM,
            tests_path=str(tests_bad))
        assert any("PARITY_TESTED_VJPS" in p and "TRAIN_PATH_VJPS" in p
                   for p in problems)

        # d) host materialization inside a fused-update function.
        optim_bad = tmp_path / "optim_bad.py"
        optim_bad.write_text(
            "import numpy as np\n"
            "FUSED_UPDATE_FNS = ('fused_sgd_update',)\n"
            "def fused_sgd_update(grads, state, params, lr):\n"
            "    host = np.asarray(grads)\n"
            "    return params, state\n")
        problems = lint.check_backward_registry(
            ops_path=lint.OPS_BACKWARD, optim_path=str(optim_bad),
            tests_path=lint.BACKWARD_TESTS)
        assert any("references np" in p for p in problems)

        # The REAL tree is clean, and the registered half matches the
        # tested half (the closed-registry handshake).
        assert lint.check_backward_registry() == []
        from active_learning_tpu.ops import backward as backward_ops
        import importlib
        tb = importlib.import_module("test_backward")
        assert set(tb.PARITY_TESTED_VJPS) == \
            set(backward_ops.TRAIN_PATH_VJPS)


class TestSatelliteFixes:
    def test_setup_logging_appends_on_resume(self, tmp_path):
        """The resume log-loss fix: a second setup_logging over the same
        file (resume) must APPEND, not truncate prior rounds' lines."""
        from active_learning_tpu.utils.logging import setup_logging

        logger = setup_logging(str(tmp_path), "run.log")
        logger.info("round 0 done")
        for h in list(logger.handlers):
            h.close()
        logger = setup_logging(str(tmp_path), "run.log")  # resume
        logger.info("resumed at round 1")
        for h in list(logger.handlers):
            h.close()
            logger.removeHandler(h)
        content = open(tmp_path / "run.log").read()
        assert "round 0 done" in content        # survived the resume
        assert "resumed at round 1" in content
        # A FRESH file still starts clean (mode "w" path).
        logger = setup_logging(str(tmp_path), "fresh.log")
        logger.info("fresh line")
        for h in list(logger.handlers):
            h.close()
            logger.removeHandler(h)
        assert open(tmp_path / "fresh.log").read().count("\n") == 1

    def test_tensorboard_auto_step_is_per_name(self):
        """TensorBoardSink._auto_step satellite: call sites omitting
        ``step`` get a PER-NAME 1,2,3,... axis, not a shared counter
        scrambled across unrelated series.  (Fake writer: importing the
        real SummaryWriter drags in TensorFlow, slow-tier only.)"""
        from active_learning_tpu.utils.metrics import TensorBoardSink

        calls = []

        class FakeWriter:
            def add_scalar(self, name, value, global_step=None):
                calls.append((name, value, global_step))

            def flush(self):
                pass

        sink = TensorBoardSink.__new__(TensorBoardSink)
        sink._writer = FakeWriter()
        sink.log_metrics({"a": 1.0})
        sink.log_metrics({"b": 10.0})
        sink.log_metrics({"a": 2.0, "b": 20.0})
        sink.log_metrics({"a": 3.0}, step=99)  # explicit step untouched
        sink.log_metrics({"a": 4.0})
        assert calls == [
            ("a", 1.0, 1), ("b", 10.0, 1),
            ("a", 2.0, 2), ("b", 20.0, 2),
            ("a", 3.0, 99),
            ("a", 4.0, 3),
        ]

    def test_compilation_cache_default_off_on_cpu(self, tmp_path,
                                                  monkeypatch):
        """The CPU gate: on a CPU-configured platform the DEFAULT
        persistent cache stays off; an explicit dir still wins
        (deliberate operator choice, and what the existing
        test_compile_reuse config test exercises)."""
        import jax

        from active_learning_tpu.experiment import driver

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        old = jax.config.jax_compilation_cache_dir
        try:
            assert driver.enable_compilation_cache(None) is None
            explicit = str(tmp_path / "explicit_cache")
            assert driver.enable_compilation_cache(explicit) == explicit
            # $JAX_COMPILATION_CACHE_DIR is the same explicit opt-in as
            # the flag — the CPU gate suppresses only the implicit
            # default — and it BEATS the flag: the cache is placed from
            # outside.
            env_dir = str(tmp_path / "env_cache")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            assert driver.enable_compilation_cache(None) == env_dir
            assert driver.enable_compilation_cache(explicit) == env_dir
        finally:
            # The enable leaks process-wide jax config; the REST of the
            # session must keep running cache-less.
            jax.config.update("jax_compilation_cache_dir", old)
