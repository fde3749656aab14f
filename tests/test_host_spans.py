"""What the host does besides the program's own code, in the span record:
the collector's pauses (``host/gc``), JAX's compile stages (``jax/compile``)
and the process counters the round's spans carry — all only while the
recorder is on."""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from active_learning_tpu.config import TelemetryConfig
from active_learning_tpu.telemetry import jax_events
from active_learning_tpu.telemetry import runtime as tele_runtime
from active_learning_tpu.telemetry import spans as spans_lib
from active_learning_tpu.utils.tracing import phase_timer


@pytest.fixture
def recording():
    """A recording tracer with the collector hook, installed process-wide
    and removed afterwards."""
    tracer = spans_lib.SpanTracer(enabled=True)
    spans_lib.set_tracer(tracer)
    tracer.start_host_hooks()
    try:
        yield tracer
    finally:
        tracer.stop_host_hooks()
        spans_lib.set_tracer(None)


def _named(tracer, name):
    return [e for e in tracer.snapshot_events() if e["name"] == name]


def test_gc_span_carries_its_parent_round_and_generation(recording):
    with recording.span("round", args={"round": 3}) as rd:
        with recording.span("train_time") as phase:
            gc.collect(2)
        gc.collect(1)
    got = {e["args"]["parent"]: e["args"] for e in _named(recording,
                                                            spans_lib.GC_SPAN)
           if e["args"]["parent"] is not None}
    assert got[phase.id]["generation"] == 2
    assert got[rd.id]["generation"] == 1
    for args in got.values():
        assert args["round"] == 3
        assert {"collected", "uncollectable"} <= set(args)
    assert recording.gc_s > 0


def test_short_young_collections_are_counted_not_recorded(recording,
                                                          monkeypatch):
    monkeypatch.setattr(spans_lib, "GC_SPAN_MIN_S", 3600.0)
    before = recording.gc_s
    gc.collect(0)
    assert recording.gc_s > before
    assert _named(recording, spans_lib.GC_SPAN) == []


def test_a_collection_under_the_tracers_lock_cannot_deadlock(recording):
    """The lock is not re-entrant, and a collection may start inside
    ``_record`` while it is held: the hook stages the span instead, and
    the next drain records it."""
    done = threading.Event()

    def collect_under_the_lock():
        with recording._lock:
            gc.collect(1)
        done.set()

    worker = threading.Thread(target=collect_under_the_lock, daemon=True)
    worker.start()
    worker.join(timeout=30)
    assert done.is_set(), "the collector hook blocked on the tracer's lock"
    with recording._lock:
        assert not any(e["name"] == spans_lib.GC_SPAN
                       for e in recording.events)
    staged = [e for e in _named(recording, spans_lib.GC_SPAN)
              if e["tid"] == worker.ident % 2**31]
    assert [e["args"]["generation"] for e in staged] == [1]


def test_compile_stages_lie_inside_the_enclosing_span(recording):
    def demo_compiled(x):
        return jnp.tanh(x * 3.0).sum()

    with recording.span("outer", args={"round": 1}) as outer:
        jax.jit(demo_compiled)(jnp.ones(16)).block_until_ready()
    mine = [e for e in _named(recording, jax_events.COMPILE_SPAN)
            if "demo_compiled" in e["args"]["fun_name"]]
    assert {e["args"]["stage"] for e in mine} == {"trace", "lower",
                                                  "compile"}
    t0 = (outer.t0 - recording.origin) * 1e6
    t1 = (outer.t1 - recording.origin) * 1e6
    for e in mine:
        # On the record's clock: the stages' wall stamps, placed through
        # the tracer's pair of origins.
        assert t0 - 100 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 100
        assert e["args"]["round"] == 1
        assert e["args"]["parent"] == outer.id
    assert recording.depth() == 0


def test_counters_are_deltas_over_the_span(recording):
    with recording.span("round", args={"round": 2}, counters=True):
        gc.collect(2)
    with recording.span("quiet", counters=True):
        pass
    with recording.span("uncounted"):
        gc.collect(1)
    rd, = _named(recording, "round")
    assert rd["args"]["gc_s"] > 0 and rd["args"]["round"] == 2
    quiet, = _named(recording, "quiet")
    assert quiet["args"]["gc_s"] == 0.0
    uncounted, = _named(recording, "uncounted")
    assert "gc_s" not in uncounted["args"]


def test_nested_traces_are_left_out(recording):
    """A function traced inside another's trace (every jnp function is a
    jit of its own) is not a stage of its own: only the outer program's
    three stages are recorded."""
    @jax.jit
    def demo_inner(x):
        return jnp.cos(x) * 2.0

    @jax.jit
    def demo_outer(x):
        return demo_inner(x + 1.0).sum()

    with recording.span("outer") as outer:
        demo_outer(jnp.ones(8)).block_until_ready()
    stages = _named(recording, jax_events.COMPILE_SPAN)
    assert not [e for e in stages if "demo_inner" in e["args"]["fun_name"]]
    mine = [e for e in stages if "demo_outer" in e["args"]["fun_name"]]
    assert sorted(e["args"]["stage"] for e in mine) == ["compile", "lower",
                                                        "trace"]
    assert {e["args"]["parent"] for e in stages} == {outer.id}


def test_recorder_off_adds_no_hook_no_counter_and_no_event(tmp_path,
                                                           monkeypatch):
    calls = []
    monkeypatch.setattr(spans_lib.SpanTracer, "process_counters",
                        lambda self: calls.append(self))
    before = list(gc.callbacks)
    rt = tele_runtime.start_run(TelemetryConfig(enabled=True),
                                str(tmp_path))
    try:
        tracer = spans_lib.get_tracer()
        assert tracer is rt.tracer and not tracer.enabled
        assert gc.callbacks == before
        with phase_timer("train_time", 0):
            gc.collect(1)
            jax.jit(lambda x: x - 5.0)(jnp.ones(3)).block_until_ready()
        with tracer.span("round", counters=True):
            pass
        assert tracer.events == [] and not tracer._staged
    finally:
        rt.finish()
        tele_runtime.uninstall(rt)
    assert calls == [] and gc.callbacks == before


def test_the_run_installs_the_hook_and_finish_removes_it(tmp_path):
    rt = tele_runtime.start_run(
        TelemetryConfig(enabled=True, export_trace=True), str(tmp_path))
    try:
        assert rt.tracer._on_gc in gc.callbacks
    finally:
        rt.finish()
        assert rt.tracer._on_gc not in gc.callbacks
        tele_runtime.uninstall(rt)
    assert spans_lib.get_tracer()._on_gc not in gc.callbacks


def test_the_cache_counter_has_one_home(monkeypatch):
    from active_learning_tpu.experiment import driver
    assert driver.compilation_cache_counts is \
        jax_events.compilation_cache_counts
    monkeypatch.setattr(jax_events, "_cache_counts", {"hits": 0, "misses": 0})
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert driver.compilation_cache_counts() == {"hits": 2, "misses": 1}


def test_a_stage_ends_when_jax_reports_it(recording):
    """A stage lasts what its wall stamps say and ends on the record's
    clock when JAX reports it, inside the span open then."""
    t_wall = time.time()
    with recording.span("outer") as outer:
        before = time.perf_counter()
        jax_events._stage_ended("/jax/core/compile/backend_compile_duration",
                                t_wall - 0.5, t_wall, fun_name="jit(f)")
        after = time.perf_counter()
        jax_events._stage_ended("/jax/unrelated_duration",
                                t_wall - 0.5, t_wall)
    e, = _named(recording, jax_events.COMPILE_SPAN)
    assert e["args"]["stage"] == "compile"
    assert e["args"]["fun_name"] == "jit(f)"
    assert e["args"]["parent"] == outer.id
    assert e["dur"] == pytest.approx(0.5e6, rel=1e-6)
    end = recording.origin + (e["ts"] + e["dur"]) / 1e6
    assert before - 1e-6 <= end <= after + 1e-6


def test_a_retroactive_span_adopts_what_ran_inside_it(recording):
    """A collection during a compile stage is recorded before the stage
    is: the stage becomes its parent, so no two children of one span
    overlap and the self times add up to the span's."""
    with recording.span("epoch", args={"round": 1}) as epoch:
        t0 = time.perf_counter()
        gc.collect(1)
        with recording.span("before_stage_end"):
            pass
        jax_events._stage_ended("/jax/core/compile/jaxpr_to_mlir_module_"
                                "duration", time.time() -
                                (time.perf_counter() - t0), time.time(),
                                fun_name="jit(g)")
        gc.collect(1)       # after the stage: stays the epoch's
    events = recording.snapshot_events()
    stage, = [e for e in events if e["name"] == jax_events.COMPILE_SPAN]
    inside = [e for e in events if e["name"] in (spans_lib.GC_SPAN,
                                                 "before_stage_end")
              and e["ts"] >= stage["ts"]
              and e["ts"] + e["dur"] <= stage["ts"] + stage["dur"]]
    assert inside and all(e["args"]["parent"] == stage["args"]["id"]
                          for e in inside)
    assert stage["args"]["parent"] == epoch.id
    later = [e for e in events if e["name"] == spans_lib.GC_SPAN
             and e["ts"] >= stage["ts"] + stage["dur"]]
    assert later and all(e["args"]["parent"] == epoch.id for e in later)
    ep, = [e for e in events if e["name"] == "epoch"]
    selfs = spans_lib.self_seconds(events)
    total = sum(v for k, v in selfs.items())
    assert total == pytest.approx(ep["dur"] / 1e6, rel=1e-6)


def test_no_collection_is_lost_under_threads(recording):
    """Spans recorded and collections staged from more threads than cores,
    with the interpreter switching threads often: every collection of
    generation 1 or 2 that a second hook saw is in the record once."""
    import os
    import sys
    seen = []

    def count(phase, info):
        if phase == "stop" and info["generation"] > 0:
            seen.append(info["generation"])

    gc.callbacks.append(count)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for i in range(40):
            with recording.span("w"):
                if i % 4 == 0:
                    gc.collect(1 + i % 2)

    threads = [threading.Thread(target=work)
               for _ in range(2 * (os.cpu_count() or 2))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
        gc.callbacks.remove(count)
    assert not any(t.is_alive() for t in threads)
    recorded = [e for e in _named(recording, spans_lib.GC_SPAN)
                if e["args"]["generation"] > 0]
    # A forced collection that finds another running returns without one,
    # so fewer than were asked for may run; none may go unrecorded.
    assert len(recorded) == len(seen) > 0
    assert len({e["args"]["id"] for e in recorded}) == len(recorded)
