"""The span tree inside the round (DESIGN.md §7): ids and parents, self
time, every span site with its parent on a toy round, spans that end
where the host has the result, and the names on the device side."""

import dataclasses
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from active_learning_tpu.analysis.checks import legacy
from active_learning_tpu.config import (ExperimentConfig, PretrainedConfig,
                                        TelemetryConfig)
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.parallel import resident as resident_lib
from active_learning_tpu.strategies import scoring
from active_learning_tpu.telemetry import runtime as tele_runtime
from active_learning_tpu.telemetry import spans as spans_lib
from active_learning_tpu.train.evaluation import make_eval_step
from active_learning_tpu.train.trainer import Trainer
from helpers import TinyClassifier, tiny_train_config


def _spans(events):
    return [e for e in events if e.get("ph") == "X"]


class TestSpanRecord:
    def test_ids_and_parents_across_threads_and_complete(self):
        tracer = spans_lib.SpanTracer(enabled=True)
        seen = {}

        def worker(tag):
            with tracer.span(f"root_{tag}", args={"round": tag}) as root:
                with tracer.span("child") as child:
                    t0 = time.perf_counter()
                    tracer.complete("chunk", t0, t0 + 0.001)
                seen[tag] = (root, child)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        with tracer.span("main_root"):
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        events = _spans(tracer.snapshot_events())
        ids = [e["args"]["id"] for e in events]
        assert len(set(ids)) == len(ids) == 13
        by_id = {e["args"]["id"]: e for e in events}
        for tag, (root, child) in seen.items():
            # A thread's first span has no parent: the parent is the top
            # of the OPENING thread's stack, and main's is another thread.
            assert by_id[root.id]["args"]["parent"] is None
            assert by_id[child.id]["args"]["parent"] == root.id
            assert by_id[child.id]["args"]["round"] == tag   # inherited
            # complete() takes its parent from the stack at the call.
            chunk = next(e for e in events if e["name"] == "chunk"
                         and e["args"]["parent"] == child.id)
            assert chunk["args"]["round"] == tag
        assert tracer.depth() == 0

    def test_self_seconds_on_a_hand_made_tree(self):
        def ev(name, i, parent, ts, dur, tid=1):
            return {"name": name, "ph": "X", "ts": ts * 1e6,
                    "dur": dur * 1e6, "tid": tid,
                    "args": {"id": i, "parent": parent, "round": 0}}
        events = [
            ev("round", 1, None, 0.0, 10.0),
            ev("query", 2, 1, 1.0, 3.0),
            ev("collect", 3, 2, 1.5, 2.0),
            ev("fit", 4, 1, 4.0, 5.0),
            ev("ckpt_a", 5, 4, 5.0, 2.0),
            ev("ckpt_b", 6, 4, 6.0, 2.0),          # overlaps ckpt_a by 1 s
            ev("beside", 7, 4, 4.0, 5.0, tid=2),   # another thread
            {"name": "thread_name", "ph": "M", "tid": 1, "args": {}},
        ]
        got = spans_lib.self_seconds(events)
        assert got == pytest.approx({1: 2.0, 2: 1.0, 3: 2.0, 4: 2.0,
                                     5: 2.0, 6: 2.0, 7: 5.0})
        # A subtree's self times on one thread add up to its root.
        assert sum(got[i] for i in (1, 2, 3, 4)) + 3.0 == pytest.approx(10.0)

    def test_export_carries_perf_origin_and_annotate_hook(self, tmp_path):
        opened = []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                opened.append(("enter", self.name))

            def __exit__(self, *exc):
                opened.append(("exit", self.name))

        # Recorder off: the span still annotates (a profiler trace opened
        # by someone else names every span) and records nothing.
        tracer = spans_lib.SpanTracer(enabled=False, annotate=Ann)
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
        assert opened == [("enter", "outer"), ("enter", "inner"),
                          ("exit", "inner"), ("exit", "outer")]
        assert tracer.events == []
        tracer = spans_lib.SpanTracer(enabled=True)
        before = time.perf_counter()
        with tracer.span("s"):
            pass
        path = tracer.export(str(tmp_path / "t.json"))
        doc = json.load(open(path))
        origin = doc["otherData"]["perf_origin"]
        assert origin <= before
        assert origin + doc["traceEvents"][0]["ts"] / 1e6 >= before


# -- every span site, on a toy round -----------------------------------------

# span -> (parent, how many per round); epochs = 2.
TABLE = {
    "round": ("experiment", 1),
    "round_epilogue": ("experiment", 1),
    "query_time": ("round", 1),
    "init_network_weights_time": ("round", 1),
    "train_time": ("round", 1),
    "load_best_ckpt_time": ("round", 1),
    "test_time": ("round", 1),
    "collect_pool": ("query_time", 1),
    "query/select": ("query_time", 1),
    "reinit/apply": ("init_network_weights_time", 1),
    "fit/prepare": ("train_time", 1),
    "epoch": ("train_time", 2),
    "fit/validate": ("train_time", 2),
    "ckpt/publish_best": ("train_time", 1),
    "ckpt/save_current": ("train_time", 1),
    "ckpt/save_fit_state": ("train_time", 1),
    "fit/finish": ("train_time", 1),
    "ckpt/load_best": ("load_best_ckpt_time", 1),
    "test/evaluate": ("test_time", 1),
    "ckpt/save_experiment": ("round", 1),
    # Not in the issue's table: the ladder's rollback copy of the model,
    # a device->host fetch before the round span opens.
    "ckpt/round_snapshot": ("experiment", 1),
}


@pytest.fixture(scope="module")
def toy_dir(tmp_path_factory):
    """The toy run's directory: logs, trace and checkpoints."""
    import torch
    from active_learning_tpu.experiment.driver import run_experiment
    tmp = str(tmp_path_factory.mktemp("span_tree"))
    ckpt = os.path.join(tmp, "head.pth")
    torch.save({"state_dict": {"linear.weight": torch.zeros(4, 8),
                               "linear.bias": torch.zeros(4)}}, ckpt)
    data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                              image_size=8, seed=1)
    # current_ckpt_every=1: the periodic writers (and the fit state at
    # epoch 1 of 2) run inside the toy fit as they do at scale.
    train_cfg = dataclasses.replace(
        tiny_train_config(batch_size=8), current_ckpt_every=1,
        pretrained=PretrainedConfig(path=ckpt))
    cfg = ExperimentConfig(
        dataset="synthetic", arg_pool="synthetic", strategy="MarginSampler",
        rounds=3, round_budget=8, init_pool_size=16, n_epoch=2,
        early_stop_patience=2, round_pipeline="off", log_dir=tmp,
        ckpt_path=tmp, exp_hash="spantree",
        telemetry=TelemetryConfig(enabled=True, export_trace=True))
    run_experiment(cfg, data=data, train_cfg=train_cfg,
                   model=TinyClassifier(num_classes=4))
    return tmp


@pytest.fixture(scope="module")
def toy_run(toy_dir):
    with open(os.path.join(toy_dir, "trace.json")) as fh:
        return json.load(fh)


class TestSpanSites:
    @pytest.mark.parametrize("name", sorted(TABLE))
    def test_span_has_its_parent_once_per_round(self, toy_run, name):
        events = _spans(toy_run["traceEvents"])
        by_id = {e["args"]["id"]: e for e in events}
        parent, per_round = TABLE[name]
        for rd in (1, 2):       # round 0 runs no query
            mine = [e for e in events
                    if e["name"] == name and e["args"]["round"] == rd]
            if name == "ckpt/publish_best":
                # Once at the cadence for every improving epoch, once
                # more at the end when the last epoch improved again.
                assert 1 <= len(mine) <= 3
            elif name == "ckpt/save_current":
                assert len(mine) == 3    # two epochs + the end of the fit
            else:
                assert len(mine) == per_round, (name, rd, len(mine))
            for e in mine:
                assert by_id[e["args"]["parent"]]["name"] == parent

    @pytest.mark.parametrize("name", ["reinit/pretrained_read",
                                      "reinit/overlay"])
    def test_template_is_built_in_round_0_only(self, toy_run, name):
        """The file is read and overlaid in the round that builds the
        re-initialisation template and in no later one."""
        events = _spans(toy_run["traceEvents"])
        by_id = {e["args"]["id"]: e for e in events}
        mine = [e for e in events if e["name"] == name]
        assert [e["args"]["round"] for e in mine] == [0]
        assert by_id[mine[0]["args"]["parent"]]["name"] == \
            "init_network_weights_time"
        assert not any(e["name"] == "reinit/model_init" for e in events)

    def test_counters_ride_the_spans(self, toy_run):
        events = _spans(toy_run["traceEvents"])
        applies = [e["args"] for e in events if e["name"] == "reinit/apply"]
        assert [a["round"] for a in applies] == [0, 1, 2]
        assert [a["template"] for a in applies] == ["built", "hit", "hit"]
        for a in applies:
            # The toy file holds the head; TinyClassifier's ``proj`` is
            # drawn.  8x8x3 rows: proj 192x8 + 8, linear 8x4 + 4, float32.
            assert (a["leaves_copied"], a["leaves_drawn"]) == (2, 2)
            assert a["bytes"] == 4 * (192 * 8 + 8 + 8 * 4 + 4)
        for e in events:
            if e["name"] == "epoch":
                assert e["args"]["steps_run"] >= e["args"]["steps_real"] >= 1
                assert e["args"]["rows"] >= 16
            if e["name"] == "collect_pool":
                a = e["args"]
                assert a["rows_run"] >= a["rows"] > 0
                assert a["rows_run"] == a["batches"] * (
                    a["rows_run"] // a["batches"])
                assert a["path"] in ("resident", "stream")
            if e["name"] == "ckpt/round_snapshot":
                # Round 0 opens before a model exists: nothing to copy.
                assert (e["args"]["bytes"] > 0) == (e["args"]["round"] > 0)
            elif e["name"].startswith("ckpt/") \
                    and e["name"] != "ckpt/save_experiment":
                assert e["args"]["bytes"] > 0
        # Round 0 compiles; the epilogue names the programs that did.
        first = next(e for e in events if e["name"] == "round_epilogue"
                     and e["args"]["round"] == 0)
        assert first["args"]["recompiled"]
        last = [e for e in events if e["name"] == "round_epilogue"][-1]
        assert "recompiled" not in last["args"]

    def test_best_handoff_counters(self, toy_run, toy_dir):
        """The three counters that say the best weights stayed where
        they were: ``load_best`` installed the device tree, the snapshot
        moved nothing, and the end-of-fit current file was written from
        the best file's serialisation exactly when the best is the last
        epoch (the periodic saves serialise on their own)."""
        from active_learning_tpu.train import checkpoint as ckpt_lib
        events = _spans(toy_run["traceEvents"])

        def of(name, rd):
            return [e["args"] for e in events
                    if e["name"] == name and e["args"]["round"] == rd]
        for rd in (0, 1, 2):
            assert [a["source"] for a in of("ckpt/load_best", rd)] == [
                "device"]
            snap, = of("ckpt/round_snapshot", rd)
            # Round 0 has no model yet; from round 1 on the host copy
            # ckpt/publish_best fetched is the snapshot's.
            assert snap["fetched"] == 0
            assert (snap["bytes"] > 0) == (rd > 0)
            paths = ckpt_lib.weight_paths(
                toy_dir, ExperimentConfig.exp_name, "spantree", rd)
            best, cur = paths["best_ckpt"], paths["current_ckpt"]
            best_is_last = ckpt_lib.read_best_tag(best)[1] == 2
            assert [a["shared"] for a in of("ckpt/save_current", rd)] == [
                False, False, best_is_last]
            with open(best, "rb") as a, open(cur, "rb") as b:
                assert (a.read() == b.read()) == best_is_last

    def test_round_subtree_self_times_add_up(self, toy_run):
        events = _spans(toy_run["traceEvents"])
        selfs = spans_lib.self_seconds(events)
        by_id = {e["args"]["id"]: e for e in events}

        def under_round(e, root):
            while e is not None:
                if e["args"]["id"] == root:
                    return True
                e = by_id.get(e["args"]["parent"])
            return False

        for root in (e for e in events if e["name"] == "round"):
            total = sum(selfs[e["args"]["id"]] for e in events
                        if under_round(e, root["args"]["id"]))
            assert total == pytest.approx(root["dur"] / 1e6, rel=1e-3)
        assert toy_run["otherData"]["perf_origin"] > 0


class TestSpansEndAtTheResult:
    def test_collect_pool_ends_after_the_fetch(self):
        """The span's end is not before the scores exist: the step sleeps
        in a host callback, so an end taken at the last enqueue (where
        the resident path used to close the span) falls before the
        callback of the last batch returns.  (One device: a host
        callback has no batch-sharded form.)"""
        mesh = mesh_lib.make_mesh(1)
        _, _, al_set = get_data_synthetic(n_train=64, n_test=8,
                                          num_classes=4, image_size=8,
                                          seed=2)
        done = []

        def slow(x):
            time.sleep(0.15)
            done.append(time.perf_counter())
            return x

        @jax.jit
        def score_slow(variables, batch):
            s = jnp.sum(batch["image"].astype(jnp.float32), axis=(1, 2, 3))
            return {"score": jax.pure_callback(
                slow, jax.ShapeDtypeStruct(s.shape, s.dtype), s)}

        tracer = spans_lib.SpanTracer(enabled=True)
        spans_lib.set_tracer(tracer)
        try:
            out = scoring.collect_pool(
                al_set, np.arange(40), 16, score_slow, {}, mesh,
                resident_cache={}, resident_max_bytes=1 << 30)
        finally:
            spans_lib.set_tracer(None)
        assert out["score"].shape == (40,)
        ev = next(e for e in tracer.events if e["name"] == "collect_pool")
        assert ev["args"]["path"] == "resident"
        assert (ev["args"]["rows"], ev["args"]["batches"],
                ev["args"]["rows_run"]) == (40, 3, 48)
        end = tracer.origin + (ev["ts"] + ev["dur"]) / 1e6
        assert len(done) >= 3 and end >= max(done)
        assert not any(e["name"] == "collect_pool_chunk"
                       for e in tracer.events)

    def test_stream_path_keeps_its_chunks(self, monkeypatch):
        """On the stream path a chunk ends at ``flush()``, a real fetch:
        the ``collect_pool_chunk`` spans stay there, under the pass."""
        monkeypatch.setattr(scoring, "FETCH_EVERY", 2)
        mesh = mesh_lib.make_mesh()
        _, _, al_set = get_data_synthetic(n_train=64, n_test=8,
                                          num_classes=4, image_size=8,
                                          seed=2)
        model = TinyClassifier(num_classes=4)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8, 8, 3)), train=False)
        step = scoring.make_prob_stats_step(model, al_set.view)
        tracer = spans_lib.SpanTracer(enabled=True)
        spans_lib.set_tracer(tracer)
        try:
            scoring.collect_pool(al_set, np.arange(40), 8, step, variables,
                                 mesh, resident_cache=None)
        finally:
            spans_lib.set_tracer(None)
        events = _spans(tracer.events)
        whole = next(e for e in events if e["name"] == "collect_pool")
        chunks = [e for e in events if e["name"] == "collect_pool_chunk"]
        assert whole["args"]["path"] == "stream" and len(chunks) == 3
        assert all(c["args"]["parent"] == whole["args"]["id"]
                   for c in chunks)
        assert sum(c["args"]["batches"] for c in chunks) == 5


class TestEpochCounters:
    @pytest.mark.parametrize("n_labeled,steps_real", [(8, 1), (40, 5),
                                                      (96, 12)])
    def test_epoch_span_on_the_scan_path_runs_the_real_steps(self, n_labeled,
                                                             steps_real):
        """The epoch program takes its trip count from ``valid``: the
        16-step bucket is its shape, and ``steps_run`` says what the
        device executes."""
        mesh = mesh_lib.make_mesh(1)
        train_set, _, al_set = get_data_synthetic(n_train=104, n_test=8,
                                                  num_classes=4,
                                                  image_size=8, seed=2)
        cfg = dataclasses.replace(tiny_train_config(batch_size=8),
                                  device_resident=True)
        trainer = Trainer(TinyClassifier(num_classes=4), cfg, mesh,
                          num_classes=4)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.arange(2)))
        tracer = spans_lib.SpanTracer(enabled=True)
        spans_lib.set_tracer(tracer)
        try:
            res = trainer.fit(state, train_set, np.arange(n_labeled), al_set,
                              np.arange(96, 104), n_epoch=2, es_patience=0,
                              rng=np.random.default_rng(0))
        finally:
            spans_lib.set_tracer(None)
        assert trainer.last_feed["form"] == "scan"
        assert Trainer.bucket_steps(steps_real) == 16
        epochs = [e for e in _spans(tracer.events) if e["name"] == "epoch"]
        assert len(epochs) == 2
        for e in epochs:
            assert (e["args"]["steps_real"], e["args"]["steps_run"],
                    e["args"]["rows"]) == (steps_real, steps_real, n_labeled)
        assert int(res.state.step) == 2 * steps_real


# -- names on the device side -------------------------------------------------

class TestDeviceNames:
    def test_runner_names_and_scopes_in_the_hlo(self):
        """The compiled program's own HLO text (``op_name`` metadata) is
        where a device trace reads an operation's scope from."""
        mesh = mesh_lib.make_mesh()
        _, _, al_set = get_data_synthetic(n_train=64, n_test=8,
                                          num_classes=4, image_size=8,
                                          seed=2)
        model = TinyClassifier(num_classes=4)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8, 8, 3)), train=False)
        cache = {}
        images, labels = resident_lib.pool_arrays(cache, al_set, mesh)
        ids = jnp.zeros((8,), jnp.int32)
        mask = jnp.ones((8,), jnp.float32)
        for kind, step in (
                ("prob_stats",
                 scoring.make_prob_stats_step(model, al_set.view)),
                ("embed_margin",
                 scoring.make_embed_step(model, al_set.view,
                                         with_probs=True)),
                ("badge", scoring.make_badge_step(model, al_set.view)),
                ("mase", scoring.make_mase_step(model, al_set.view))):
            run = resident_lib.get_runner(cache, step, mesh,
                                          scoring._runner_name(step),
                                          al_set.image_shape)
            text = run.lower(variables, images, ids,
                             mask).compile().as_text()
            assert f"jit_run_score_{kind}" in text
            for scope in ("pool_gather", "view", "forward", "score_head"):
                assert f"/{scope}/" in text, (kind, scope)
        run = resident_lib.get_runner(
            cache, make_eval_step(model, al_set.view, 4), mesh, "run_eval",
            al_set.image_shape, with_labels=True)
        text = run.lower(variables, images, labels, ids,
                         mask).compile().as_text()
        assert "jit_run_eval" in text
        for scope in ("pool_gather", "view", "forward", "score_head"):
            assert f"/{scope}/" in text

    def test_epoch_step_scopes_in_the_hlo(self):
        mesh = mesh_lib.make_mesh()
        train_set, _, _ = get_data_synthetic(n_train=64, n_test=8,
                                             num_classes=4, image_size=8,
                                             seed=2)
        trainer = Trainer(TinyClassifier(num_classes=4),
                          tiny_train_config(batch_size=8), mesh,
                          num_classes=4)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.arange(2)))
        scan = trainer._build_epoch_scan(train_set.image_shape)
        images = jnp.asarray(resident_lib.to_pinned(train_set.images))
        labels = jnp.asarray(train_set.targets)
        idx = jnp.zeros((2, 8), jnp.int32)
        text = scan.lower(
            state, images, labels, idx, jnp.ones((2, 8), jnp.float32),
            jnp.ones((2,), jnp.float32), jax.random.PRNGKey(0),
            jnp.float32(0.1), jnp.ones((4,), jnp.float32),
            view=train_set.view).compile().as_text()
        assert "jit_epoch_scan" in text
        for scope in ("pool_gather", "view", "forward_backward",
                      "optimizer"):
            assert f"/{scope}/" in text, scope


# -- the rule the lint holds the tracing to ----------------------------------

class TestSpanLint:
    def test_phase_timer_may_not_annotate_on_its_own(self, tmp_path):
        bad = tmp_path / "tracing.py"
        bad.write_text(
            "import contextlib\n"
            "@contextlib.contextmanager\n"
            "def phase_timer(name, round_idx, sink=None):\n"
            "    with get_tracer().span(name) as sp:\n"
            "        with trace_annotation(f'{name}/rd{round_idx}'):\n"
            "            yield sp\n"
            "    sink.log_metric(f'rd_{name}', sp.duration_s)\n")
        problems = legacy.check_phase_timer_span(tracing_path=str(bad))
        assert [p for p in problems if "two names per span" in p.message]
        assert legacy.check_phase_timer_span() == []

    def test_only_the_span_tracer_opens_annotations(self, tmp_path):
        bad = tmp_path / "rogue.py"
        bad.write_text(
            "from active_learning_tpu.telemetry import profiler\n"
            "def scored(fn):\n"
            "    with profiler.trace_annotation('scoring'):\n"
            "        return fn()\n")
        problems = legacy.check_trace_annotation(files=[str(bad)])
        assert len(problems) == 1
        assert "without a span" in problems[0].message
        # Handing the function over as the tracer's hook is a reference,
        # not a call: the run does exactly that.
        ok = tmp_path / "hook.py"
        ok.write_text(
            "from active_learning_tpu.telemetry import profiler, spans\n"
            "tracer = spans.SpanTracer(annotate=profiler.trace_annotation)\n")
        assert legacy.check_trace_annotation(files=[str(ok)]) == []

    def test_the_run_installs_the_gated_annotation(self, tmp_path):
        from active_learning_tpu.telemetry import profiler
        rt = tele_runtime.start_run(TelemetryConfig(enabled=True),
                                    str(tmp_path))
        try:
            tracer = spans_lib.get_tracer()
            assert tracer is rt.tracer and not tracer.enabled
            assert tracer.annotate is profiler.trace_annotation
        finally:
            rt.finish()
            tele_runtime.uninstall(rt)
        assert spans_lib.get_tracer().annotate is None
