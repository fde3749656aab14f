"""The device-truth layer (telemetry/profiler.py, DESIGN.md §11):
round-window selection (never round 0), the op-classification table,
the HLO collective-bytes table, capture summarisation, the merged
host+device timeline, the off-path inertness bound, the serve
``POST /v1/profile`` verb, and the end-to-end CPU-mesh acceptance smoke
through the production CLI."""

import contextlib
import json
import os
import subprocess
import sys
import time

import pytest

from active_learning_tpu.telemetry import profiler as prof
from active_learning_tpu.telemetry import spans as spans_lib

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class TestProfileRounds:
    def test_default_is_first_warm_round(self):
        for spec in (None, "", "  ", "warm"):
            rounds, rejected = prof.parse_profile_rounds(spec)
            assert rounds == (1,) and rejected == []

    def test_explicit_list_dedup_sorted(self):
        rounds, rejected = prof.parse_profile_rounds("3,1,3, 2")
        assert rounds == (1, 2, 3) and rejected == []

    def test_round_zero_and_junk_rejected_never_armed(self):
        rounds, rejected = prof.parse_profile_rounds("0,-2,x,1")
        assert rounds == (1,)
        assert 0 in rejected and -2 in rejected and "x" in rejected

    def test_round_profiler_never_captures_round_zero(self, tmp_path):
        # Even a RoundProfiler constructed WITH round 0 (bypassing the
        # parser) refuses it: the second lock on the same door.
        rp = prof.RoundProfiler(str(tmp_path), rounds=(0, 1))
        assert rp.should_capture(0) is False
        assert rp.should_capture(1) is True
        assert rp.should_capture(2) is False


class TestClassification:
    @pytest.mark.parametrize("name,cls", [
        ("all-reduce.1", "collective"),
        ("all-gather-start.2", "collective"),
        ("all-gather-done.2", "collective"),
        ("collective-permute.7", "collective"),
        ("reduce-scatter.3", "collective"),
        ("all-to-all", "collective"),
        ("copy.3", "transfer"),
        ("D2D Dispatch", "transfer"),
        ("infeed", "transfer"),
        ("h2d stream", "transfer"),
        ("ThreadpoolListener::Record", "infra"),
        ("ThunkExecutor::Execute (wait for completion)", "infra"),
        ("TfrtCpuBuffer::Await", "infra"),
        ("$builtins isinstance", "infra"),
        ("fusion.12", "compute"),
        ("dot.3", "compute"),
        ("reduce.8", "compute"),     # plain reduce is NOT a collective
        ("convolution.4", "compute"),
    ])
    def test_classify_table(self, name, cls):
        assert prof.classify_op(name) == cls

    def test_collective_primitive_and_async_done(self):
        assert prof.collective_primitive("all-reduce-start.17") \
            == "all-reduce"
        assert prof.collective_primitive("fusion.2") is None
        assert prof._is_async_done("all-gather-done.2") is True
        assert prof._is_async_done("all-gather-start.2") is False
        assert prof._is_async_done("all-gather.2") is False


class TestHloCollectiveBytes:
    def _write_dump(self, tmp_path, name, text):
        path = tmp_path / name
        path.write_text(text)
        return str(path)

    def test_bytes_from_after_optimizations_text(self, tmp_path):
        self._write_dump(
            tmp_path, "module_0001.jit_step.cpu_after_optimizations.txt",
            "HloModule jit_step, is_scheduled=true\n\n"
            "ENTRY %main {\n"
            "  %all-reduce.1 = f32[32,16]{1,0} all-reduce(f32[32,16]"
            "{1,0} %p), channel_id=1\n"
            "  ROOT %all-gather.3 = bf16[8,128]{1,0} all-gather(bf16"
            "[1,128]{1,0} %q), dimensions={0}\n"
            "  %all-reduce.2 = (f32[4]{0}, f32[8]{0}) all-reduce(...)\n"
            "  %reduce.9 = f32[32]{0} reduce(f32[8,32]{1,0} %r)\n"
            "}\n")
        table = prof.hlo_collective_bytes(str(tmp_path))
        assert table[("jit_step", "all-reduce.1")] == 32 * 16 * 4
        assert table[("jit_step", "all-gather.3")] == 8 * 128 * 2
        assert table[("jit_step", "all-reduce.2")] == 4 * 4 + 8 * 4
        # The plain reduce is compute, never in the byte table.
        assert not any(op == "reduce.9" for _, op in table)

    def test_async_start_collectives_attribute_bytes(self, tmp_path):
        """TPU's async lowering emits '-start'/'-done' pairs: the
        -start instruction (whose NAME the trace's hlo_op references)
        must land in the byte table, or every collective on the primary
        platform would read as unattributed."""
        self._write_dump(
            tmp_path, "module_0004.jit_tr.tpu_after_optimizations.txt",
            "HloModule jit_tr\n"
            "  %all-reduce-start.1 = f32[64]{0} all-reduce-start(f32"
            "[64]{0} %p), channel_id=5\n"
            "  %all-reduce-done.1 = f32[64]{0} all-reduce-done(%all-"
            "reduce-start.1)\n")
        table = prof.hlo_collective_bytes(str(tmp_path))
        assert table[("jit_tr", "all-reduce-start.1")] == 64 * 4
        # The -done half is a completion marker, not a second payload.
        assert ("jit_tr", "all-reduce-done.1") not in table

    def test_shape_bucket_collision_keeps_largest(self, tmp_path):
        body = ("HloModule jit_step\n"
                "  %all-reduce.1 = f32[{n},16]{{1,0}} all-reduce(%p)\n")
        self._write_dump(
            tmp_path, "module_0001.jit_step.cpu_after_optimizations.txt",
            body.format(n=8))
        self._write_dump(
            tmp_path, "module_0002.jit_step.cpu_after_optimizations.txt",
            body.format(n=64))
        table = prof.hlo_collective_bytes(str(tmp_path))
        # A bound, not a fabrication: the bucketed recompile's largest
        # shape wins the shared (module, op) key.
        assert table[("jit_step", "all-reduce.1")] == 64 * 16 * 4

    def test_missing_dir_is_empty_table(self, tmp_path):
        assert prof.hlo_collective_bytes(None) == {}
        assert prof.hlo_collective_bytes(str(tmp_path / "absent")) == {}


def _synth_trace():
    """A hand-built parsed trace: one TPU device plane (whose 'Steps'
    line must be excluded in favor of 'XLA Ops'), one CPU XLA thread,
    one python host thread (never a device track)."""
    processes = {1: "/device:TPU:0", 2: "/host:CPU"}
    threads = {(1, 10): "XLA Ops #1", (1, 11): "Steps",
               (2, 20): "tf_XLAEigen/7", (2, 21): "python"}

    def x(pid, tid, name, ts, dur, args=None):
        e = {"ph": "X", "pid": pid, "tid": tid, "name": name,
             "ts": float(ts), "dur": float(dur)}
        if args:
            e["args"] = args
        return e

    events = [
        x(1, 10, "all-reduce.1", 0, 200_000,
          {"hlo_module": "jit_step", "hlo_op": "all-reduce.1"}),
        x(1, 10, "all-reduce-done.1", 200_000, 50_000,
          {"hlo_module": "jit_step", "hlo_op": "all-reduce-done.1"}),
        x(1, 10, "fusion.2", 250_000, 250_000),
        x(2, 20, "copy.3", 100_000, 100_000),
        x(2, 20, "ThunkExecutor::Execute (wait)", 0, 900_000),  # infra
        x(1, 11, "train_step", 0, 1_000_000),  # Steps line: excluded
        x(2, 21, prof.ANCHOR_NAME, 1_000, 5),  # the re-basing anchor
        x(2, 21, "$builtins isinstance", 0, 10),
    ]
    return {"events": events, "processes": processes, "threads": threads}


class TestSummarize:
    def test_device_tracks_prefer_xla_ops_line(self):
        tracks = prof.device_tracks(_synth_trace())
        assert (1, 10) in tracks and (2, 20) in tracks
        assert (1, 11) not in tracks      # Steps double-counts XLA Ops
        assert (2, 21) not in tracks      # python is the HOST side

    def test_summary_fracs_counts_and_bytes(self):
        table = {("jit_step", "all-reduce.1"): 2048}
        s = prof.summarize_capture(_synth_trace(), window_s=1.0,
                                   byte_table=table)
        # Busy union over [0,250k],[250k,500k],[100k,200k] = 500k of 1s.
        assert s["device_busy_frac"] == pytest.approx(0.5)
        # Op time: collective 250k, compute 250k, transfer 100k.
        assert s["collective_frac"] == pytest.approx(250 / 600, abs=1e-3)
        assert s["transfer_frac"] == pytest.approx(100 / 600, abs=1e-3)
        ar = s["collectives"]["all-reduce"]
        # The -done half carries time but never a second count/payload.
        assert ar["count"] == 1
        assert ar["bytes"] == 2048
        assert s["collective_bytes_total"] == 2048
        assert s["collective_events_unattributed"] == 0

    def test_bytes_none_when_dump_absent_zero_when_no_collectives(self):
        s = prof.summarize_capture(_synth_trace(), window_s=1.0,
                                   byte_table={})
        # Collectives ran but the dump was not armed: counts measured,
        # bytes honestly unknown — never a guess.
        assert s["collectives"]["all-reduce"]["bytes"] is None
        assert s["collective_bytes_total"] is None
        assert s["collective_events_unattributed"] == 1
        quiet = {"events": [], "processes": {}, "threads": {}}
        s2 = prof.summarize_capture(quiet, window_s=1.0)
        assert s2["collective_bytes_total"] == 0


class TestMergedTimeline:
    def _handle(self):
        h = prof.CaptureHandle("/nowhere", "test")
        # Host clock: origin 0; window [2.0 s, 3.0 s]; the anchor was
        # emitted at 2.0 s and appears in the trace at ts=1000 µs.
        h.t0_pc, h.t1_pc, h.anchor_pc = 2.0, 3.0, 2.0
        return h

    def test_rebase_filter_and_metadata(self):
        events, dropped, alignment = prof.build_device_track_events(
            _synth_trace(), self._handle(), host_origin_pc=0.0)
        assert alignment == "anchor"
        xs = [e for e in events if e["ph"] == "X"]
        metas = [e for e in events if e["ph"] == "M"]
        # Infra and the excluded tracks never splice.
        assert all(e["args"]["class"] != "infra" for e in xs)
        assert {e["name"] for e in xs} == {"all-reduce.1",
                                           "all-reduce-done.1",
                                           "fusion.2", "copy.3"}
        # Exact re-base: trace ts 0 == anchor ts 1000 µs - 1000 µs ==
        # host 2.0 s - 1 ms.
        ar = next(e for e in xs if e["name"] == "all-reduce.1")
        assert ar["ts"] == pytest.approx(2.0e6 - 1000.0)
        # Every spliced op lies inside the window (± slack).
        for e in xs:
            assert 2.0e6 - 2e5 <= e["ts"] <= 3.0e6 + 2e5
        # Device tracks render under their own named processes, away
        # from any real pid.
        procs = [e for e in metas if e["name"] == "process_name"]
        assert procs and all(e["pid"] >= prof.DEVICE_PID_BASE
                             for e in procs)
        assert any("XLA device ops" in e["args"]["name"] for e in procs)
        assert dropped == 0

    def test_out_of_window_ops_drop_instead_of_ghost_tracks(self):
        trace = _synth_trace()
        trace["events"].append({"ph": "X", "pid": 2, "tid": 20,
                                "name": "dot.9", "ts": 9e7, "dur": 10.0})
        events, dropped, _ = prof.build_device_track_events(
            trace, self._handle(), host_origin_pc=0.0)
        assert dropped == 1
        assert all(e.get("name") != "dot.9" for e in events)

    def test_phase_device_attribution_intersects_host_spans(self):
        """Per-phase attribution: device ops clipped to the round's
        host phase spans — a phase with no device ops reads busy 0
        (the gap was HOST side), collective share is per-phase."""
        host = [
            {"ph": "X", "name": "train_time", "ts": 0.0,
             "dur": 1_000_000.0, "args": {"round": 1}},
            {"ph": "X", "name": "test_time", "ts": 1_000_000.0,
             "dur": 500_000.0, "args": {"round": 1}},
            # Another round's span never attributes this capture.
            {"ph": "X", "name": "train_time", "ts": 0.0,
             "dur": 9_000_000.0, "args": {"round": 0}},
        ]
        ops = [
            {"ph": "X", "name": "all-reduce.1", "ts": 100_000.0,
             "dur": 200_000.0, "args": {"class": "collective"}},
            {"ph": "X", "name": "fusion.2", "ts": 300_000.0,
             "dur": 300_000.0, "args": {"class": "compute"}},
            # Straddles the train/test boundary: split proportionally.
            {"ph": "X", "name": "copy.3", "ts": 900_000.0,
             "dur": 200_000.0, "args": {"class": "transfer"}},
        ]
        out = prof.phase_device_attribution(host, 1, ops)
        assert set(out) == {"train_time", "test_time"}
        tr = out["train_time"]
        # 200k + 300k + the copy's first 100k = 600k busy of 1s.
        assert tr["busy_frac"] == pytest.approx(0.6)
        assert tr["collective_frac"] == pytest.approx(200 / 600,
                                                      abs=1e-3)
        te = out["test_time"]
        assert te["busy_frac"] == pytest.approx(100_000 / 500_000)
        assert te["collective_frac"] == pytest.approx(0.0)

    def test_splice_into_tracer_merges_host_and_device(self, tmp_path):
        tracer = spans_lib.SpanTracer(enabled=True)
        with tracer.span("round", args={"round": 1}):
            time.sleep(0.001)
        h = prof.CaptureHandle("/nowhere", "test")
        h.t0_pc = tracer.origin + 2.0
        h.t1_pc = tracer.origin + 3.0
        h.anchor_pc = tracer.origin + 2.0
        stats, ops = prof.splice_into_tracer(tracer, _synth_trace(), h)
        assert stats["spliced_events"] > 0
        assert ops and all(e["ph"] == "X" for e in ops)
        path = str(tmp_path / "merged.json")
        tracer.export(path)
        out = json.load(open(path))
        cats = {e.get("cat") for e in out["traceEvents"]}
        assert "host" in cats and "device" in cats
        # A disabled tracer refuses the splice (recording is opt-in).
        off = spans_lib.SpanTracer(enabled=False)
        assert off.splice_events([{"ph": "M"}]) == 0


class TestOffPathInertness:
    def test_unarmed_round_scope_is_nanoseconds(self):
        """--profile_rounds unset => the driver's per-round hook is a
        None check returning a shared nullcontext: 100k rounds' worth
        of hook under 0.25 s (<2.5 µs/call — the same bound style as
        the telemetry-off and faults-disarmed paths)."""
        t0 = time.perf_counter()
        for rd in range(100_000):
            with prof.round_scope(None, rd):
                pass
        assert time.perf_counter() - t0 < 0.25

    def test_armed_profiler_round_zero_is_null_scope(self, tmp_path):
        rp = prof.RoundProfiler(str(tmp_path), rounds=(0, 1, 2))
        scope = prof.round_scope(rp, 0)
        assert isinstance(scope, contextlib.nullcontext().__class__)
        # ... and stays cheap: an armed profiler skipping a round must
        # not pay capture costs either.
        t0 = time.perf_counter()
        for _ in range(50_000):
            with prof.round_scope(rp, 0):
                pass
        assert time.perf_counter() - t0 < 0.25


class TestCaptureWindowGate:
    def test_one_window_at_a_time_and_artifacts(self, tmp_path):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((64, 64))
        f(x).block_until_ready()
        out = str(tmp_path / "cap")
        with prof.capture_window(out) as handle:
            with pytest.raises(prof.CaptureBusyError):
                prof.start_capture(str(tmp_path / "other"))
            f(x).block_until_ready()
        assert handle.window_s and handle.window_s > 0
        trace_path = prof.find_trace_file(out)
        assert trace_path and trace_path.endswith(".trace.json.gz")
        trace = prof.parse_trace(trace_path)
        assert trace["events"]
        # The anchor annotation really landed (exact re-basing works).
        assert any(e.get("name") == prof.ANCHOR_NAME
                   for e in trace["events"])

    def test_window_closes_on_exception(self, tmp_path):
        with pytest.raises(RuntimeError, match="boom"):
            with prof.capture_window(str(tmp_path / "a")):
                raise RuntimeError("boom")
        # The global gate released: a fresh window opens cleanly.
        with prof.capture_window(str(tmp_path / "b")):
            pass


class TestServeProfileVerb:
    def _server(self):
        import threading

        from active_learning_tpu.config import ServeConfig
        from active_learning_tpu.serve.server import ScoringServer

        class StubExecutor:
            _lock = threading.Lock()
            stats = {"batches": 0, "rows": 0, "reloads": 0}
            served_round = 1

            def compile_counts(self):
                return {}

            def request_path_compiles(self):
                return 0

        class StubBatcher:
            pending_rows = 0
            buckets = (8,)

        server = ScoringServer(StubExecutor(), ServeConfig())
        server.batcher = StubBatcher()
        return server

    def test_profile_verb_returns_summary(self):
        import asyncio

        server = self._server()
        body = json.dumps({"seconds": 0.1}).encode()
        status, payload, _ = asyncio.run(
            server._route("POST", "/v1/profile", body))
        assert status == 200, payload
        assert payload["ok"] is True
        assert "device_busy_frac" in payload
        assert "collectives" in payload
        # Artifacts land in a SERVER-chosen dir named in the response.
        assert payload["out_dir"].startswith("/")
        assert os.path.exists(payload["summary_path"])

    def test_profile_verb_bad_requests_are_400(self):
        import asyncio

        server = self._server()
        for bad in ({"seconds": "fast"}, {"seconds": -1},
                    {"seconds": True},
                    # A client-chosen output path is refused outright:
                    # no remote filesystem-write primitive.
                    {"seconds": 0.1, "dir": "/etc/anywhere"}):
            status, payload, _ = asyncio.run(server._route(
                "POST", "/v1/profile", json.dumps(bad).encode()))
            assert status == 400, (bad, payload)

    def test_concurrent_capture_is_409(self, tmp_path):
        import asyncio

        server = self._server()
        handle = prof.start_capture(str(tmp_path / "held"))
        try:
            status, payload, _ = asyncio.run(server._route(
                "POST", "/v1/profile",
                json.dumps({"seconds": 0.05}).encode()))
            assert status == 409, payload
        finally:
            prof.finish_capture(handle)


class TestEndToEndDeviceTruth:
    """The acceptance criteria, pinned through the PRODUCTION CLI in a
    fresh subprocess (the HLO byte-table dump can only arm before
    backend init): one merged Chrome trace carrying host spans AND
    device-op events on named tracks, device_busy_frac /
    collective_bytes_total in metrics.jsonl AND the Prometheus scrape
    file for the profiled round, no capture for round 0, and the
    scrape-file completeness contract (PER_ROUND_GAUGES)."""

    @pytest.fixture(scope="class")
    def smoke(self, tmp_path_factory):
        tmp = str(tmp_path_factory.mktemp("device_truth"))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if "xla_force_host_platform_device_count" not in env.get(
                "XLA_FLAGS", ""):
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_"
                                  "count=8").strip()
        cmd = [sys.executable, "-m", "active_learning_tpu",
               "--dataset", "synthetic", "--arg_pool", "synthetic",
               "--strategy", "MarginSampler", "--rounds", "2",
               "--round_budget", "16", "--n_epoch", "2",
               "--early_stop_patience", "2", "--log_dir", tmp,
               "--ckpt_path", tmp, "--exp_hash", "devtruth",
               "--export_trace", "--profile_rounds", "1",
               "--prometheus_file", os.path.join(tmp, "run.prom")]
        proc = subprocess.run(cmd, cwd=REPO, env=env, text=True,
                              capture_output=True, timeout=540)
        assert proc.returncode == 0, proc.stderr[-4000:]
        return tmp

    def test_merged_trace_has_host_and_device_tracks(self, smoke):
        trace = json.load(open(os.path.join(smoke, "trace.json")))
        events = trace["traceEvents"]
        host = [e for e in events
                if e.get("ph") == "X" and e.get("cat") == "host"]
        device = [e for e in events
                  if e.get("ph") == "X" and e.get("cat") == "device"]
        assert host and device
        # Named device tracks, on their own synthetic pids.
        procs = {e["pid"]: e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        dev_procs = [n for n in procs.values()
                     if n.startswith("XLA device ops")]
        assert dev_procs
        # Device ops land INSIDE the profiled round's host span.
        r1 = next(e for e in host if e["name"] == "round"
                  and (e.get("args") or {}).get("round") == 1)
        slack = 2e5
        inside = [e for e in device
                  if r1["ts"] - slack <= e["ts"]
                  <= r1["ts"] + r1["dur"] + slack]
        assert len(inside) == len(device)
        # Every spliced op is classified; collectives are present (the
        # 8-device mesh psums gradients every step).
        classes = {(e.get("args") or {}).get("class") for e in device}
        assert "collective" in classes and "compute" in classes
        assert "infra" not in classes

    def test_round0_never_captures(self, smoke):
        profile_dir = os.path.join(smoke, "profile")
        assert os.path.isdir(os.path.join(profile_dir, "round_1"))
        assert not os.path.exists(os.path.join(profile_dir, "round_0"))

    def test_summary_and_measured_bytes(self, smoke):
        path = os.path.join(smoke, "profile", "round_1",
                            "device_profile_rd1.json")
        summary = json.load(open(path))
        assert summary["round"] == 1
        assert 0 < summary["device_busy_frac"] <= 1
        assert summary["collective_frac"] > 0
        # The fresh-subprocess dump armed, so the bytes are MEASURED
        # (counts from the trace x exact HLO payload shapes).
        assert summary["byte_table_entries"] > 0
        assert summary["collective_bytes_total"] > 0
        assert summary["collectives"].get("all-reduce", {}).get(
            "count", 0) > 0
        # Per-phase attribution against the round's host spans: the
        # train phase dominates a synthetic round, and it shows device
        # work (gradient psums at minimum).
        attribution = summary["phase_attribution"]
        assert "train_time" in attribution
        assert attribution["train_time"]["busy_frac"] > 0

    def test_device_metrics_in_jsonl_and_scrape(self, smoke):
        from active_learning_tpu.experiment.driver import PER_ROUND_GAUGES
        from active_learning_tpu.telemetry import prom as prom_lib

        by_name = {}
        for line in open(os.path.join(smoke, "metrics.jsonl")):
            ev = json.loads(line)
            if ev.get("kind") == "metric":
                for k, v in ev["metrics"].items():
                    by_name.setdefault(k, []).append((ev.get("step"), v))
        for name in ("device_busy_frac", "collective_frac",
                     "collective_bytes_total"):
            assert name in by_name, f"missing {name}"
            steps = [s for s, _ in by_name[name]]
            assert steps == [1], f"{name} emitted at {steps}, not the " \
                                 "profiled round only"
        assert by_name["collective_bytes_total"][0][1] > 0
        parsed = prom_lib.parse(
            open(os.path.join(smoke, "run.prom")).read())
        # The completeness contract: every per-round driver metric that
        # reached the sink also rides the scrape file.
        for name in PER_ROUND_GAUGES:
            if name in by_name:
                assert f"al_run_{name}" in parsed, \
                    f"{name} in metrics.jsonl but not the scrape file"
        for name in ("device_busy_frac", "collective_bytes_total",
                     "span_events_dropped"):
            assert f"al_run_{name}" in parsed
        assert parsed["al_run_span_events_dropped"][()] == 0

    def test_status_renders_pipeline_health_tail(self, smoke):
        """Satellite: overlap_frac / round_vs_max_phase (and
        spec_hit_frac when a speculation hit occurred) in the status
        CLI's rendered metrics tail."""
        from active_learning_tpu.telemetry import status as status_lib

        summary = status_lib.summarize(smoke)
        text = status_lib.render_text(summary)
        assert "overlap_frac" in text
        assert "round_vs_max_phase" in text
