"""The benchmark runner: one cell, one run.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Drives warm active-learning rounds through the program's own
``experiment.driver.run_experiment`` on rows and weights made from the seed,
measures ``round_s`` over a window that opens and closes at round boundaries,
then checks what the timed rounds produced against the plain reference
(``lib/reference.py``).  Everything that belongs to one cell, configuration
or per-layer metric is a data file found by its name in ``BENCHMARK.json``;
everything that belongs to one kind of model and row is the module the
configuration names as its ``family`` (``families/__init__.py``), and a
metric's reader is looked up in the file of ``lib/`` its data file names.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

WARM_ROUNDS = 2          # round 0 fits cold, round 1 runs the first query
REHEARSAL_EXIT = 3
# The program's named scopes: device time is summed per scope.
SCOPES = ("pool_gather", "view", "forward", "score_head",
          "forward_backward", "optimizer")


def pin_allocator() -> bool:
    """Fix glibc's two thresholds for the whole run, so that the host's work
    on the model's bytes costs every process the same.

    Left to itself glibc maps a block of 128 KiB or more afresh and hands it
    back when freed, until the free of such a block raises the threshold to
    its size: whether and when that happens depends on the order of a
    process's frees, and a page touched for the first time costs about 5 us
    on the machines the cells run on.  That is the two levels ``round_s``
    ran at (``PERF.md`` section 2).  With the thresholds set, blocks of up to
    32 MiB (glibc's cap) are recycled from a heap that is never trimmed, in
    every process from its start."""
    import ctypes
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:          # not glibc: nothing to pin
        return False
    m_trim_threshold, m_mmap_threshold = -1, -3      # <malloc.h>
    return bool(libc.mallopt(m_mmap_threshold, 32 << 20)
                and libc.mallopt(m_trim_threshold, 1 << 30))


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_cell(args) -> dict:
    """The cell's manifest entry, its data files and its family."""
    import families
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    base = manifest["paths"][0]
    if args.workload_file:
        workload = load_json(args.workload_file)
        config = load_json(args.config_file)
        entry = {"name": workload["name"], "chips": workload.get("chips", 1)}
    else:
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == args.workload), None)
        if entry is None:
            raise SystemExit(f"unknown workload {args.workload!r}")
        workload = load_json(os.path.join(
            ROOT, base, "workloads", f"{entry['name']}.json"))
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == entry["config"])
        config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    metrics = []
    for m in manifest["per_layer"]:
        if "workloads" in m and entry["name"] not in m["workloads"] \
                and not args.workload_file:
            continue
        spec = load_json(os.path.join(ROOT, base, "metrics",
                                      f"{m['name']}.json"))
        metrics.append({**m, **spec})
    scale = dict(config["scale"])
    scale.update(workload.get("scale", {}))
    return {"entry": entry, "workload": workload, "config": config,
            "scale": scale, "metrics": metrics,
            "family": families.load(config["family"], ROOT)}


# -- what the hooks record ---------------------------------------------------

class Record:
    """What the benchmark reads off the program while it runs: references to
    arrays the program already made, never a computation of its own (the one
    exception: a device copy of the parameters after each round's
    ``load_best_ckpt``, about 0.3 ms of device time)."""

    def __init__(self):
        self.strategy = None
        self.fits = {}
        self.current_fit = None
        self.scores = {}
        self.queries = {}
        self.evals = []
        self.params = {}


def install_hooks(strategy, rec: Record, break_how: str = "") -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    rec.strategy = strategy
    trainer = strategy.trainer

    orig_collect = strategy.collect_scores
    if break_how == "score_altered":
        orig_collect = _altered_scores(orig_collect)

    def collect_scores(idxs, kind, keys=None):
        out = orig_collect(idxs, kind, keys=keys)
        rec.scores[strategy.round] = {
            "idxs": np.asarray(idxs).copy(), "kind": kind, "out": out,
            "batch": int(strategy._score_batch_size())}
        return out
    strategy.collect_scores = collect_scores

    orig_query = strategy.query

    def query(budget):
        labeled = np.asarray(strategy.already_labeled_idxs()).copy()
        picked, cost = orig_query(budget)
        rec.queries[strategy.round] = {
            "picked": np.asarray(picked).copy(), "labeled_before": labeled}
        return picked, cost
    strategy.query = query

    def wrap_epoch_scan():
        orig_scan = trainer._epoch_scan
        if break_how in ("state_unchanged", "half_batch"):
            orig_scan = _broken_scan(orig_scan, break_how)

        def epoch_scan(state, images, labels, idx_mat, mask_mat, valid, key,
                       lr, class_weights, view, sharded=False):
            out = orig_scan(state, images, labels, idx_mat, mask_mat, valid,
                            key, lr, class_weights, view=view,
                            sharded=sharded)
            if rec.current_fit is not None:
                rec.current_fit["epochs"].append({
                    "idx": idx_mat, "mask": mask_mat, "valid": valid,
                    "key": key, "lr": lr, "augment": bool(view.augment),
                    "losses": out[2], "gnorms": out[3]})
            return out
        epoch_scan._bench_wrapped = True
        trainer._epoch_scan = epoch_scan

    orig_fit = trainer.fit

    def fit(state, train_set, labeled_idxs, *a, **kw):
        rd = int(kw.get("round_idx", strategy.round))
        rec.current_fit = {"round": rd, "epochs": [],
                           "labeled": int(len(labeled_idxs))}
        res = orig_fit(state, train_set, labeled_idxs, *a, **kw)
        rec.current_fit.update(best_epoch=int(res.best_epoch),
                               epochs_run=int(res.epochs_run),
                               feed=dict(trainer.last_feed))
        rec.fits[rd] = rec.current_fit
        rec.current_fit = None
        scan = getattr(trainer, "_epoch_scan", None)
        if scan is not None and not getattr(scan, "_bench_wrapped", False):
            wrap_epoch_scan()
        return res
    trainer.fit = fit

    orig_eval = trainer.evaluate

    def evaluate(state, dataset, idxs):
        perf = orig_eval(state, dataset, idxs)
        count = float(perf["count"])
        rec.evals.append({
            "round": strategy.round, "rows": int(len(idxs)),
            "batch": int(trainer.padded_batch_size(
                trainer.eval_batch_size(dataset))),
            "count": int(round(count)),
            "test": dataset is strategy.test_set,
            "top1": int(round(float(perf["accuracy"]) * count)),
            "top5": int(round(float(perf["top_5_accuracy"]) * count))})
        return perf
    trainer.evaluate = evaluate

    orig_load = strategy.load_best_ckpt

    def load_best_ckpt():
        orig_load()
        rec.params[strategy.round] = jax.tree.map(jnp.copy,
                                                  strategy.state.params)
        for old in [r for r in rec.params if r < strategy.round - 1]:
            del rec.params[old]
    strategy.load_best_ckpt = load_best_ckpt


# -- the window --------------------------------------------------------------

class Controller:
    """Opens the window at the end of the warm-up rounds, traces the first
    window round when asked, and asks the program to stop (its own SIGTERM
    path) at the first round boundary after ``seconds``."""

    def __init__(self, seconds: float, trace_dir, anchor_fn,
                 clock=time.perf_counter):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.anchor_fn = anchor_fn   # a warm, named program: the clock tie
        self.clock = clock
        self.t_open = None
        self.setup_s = None
        self.pauses = []
        self.tracing = False
        self.trace_anchor = None     # (host perf_counter at dispatch)
        self.trace_span = None       # (host t0, host t1)
        self.counters_open = None
        self.counters_close = None
        self.stop_sent = False

    @staticmethod
    def counters() -> dict:
        from active_learning_tpu.experiment import driver
        return driver.compilation_cache_counts()

    def on_round_end(self, rd: int, now: float) -> None:
        if self.stop_sent:
            return
        if self.t_open is None:
            if rd + 1 < WARM_ROUNDS:
                return
            self.t_open = now
            if self.trace_dir:
                self._start_trace()
                self.t_open = self.clock()
                self.pauses.append((now, self.t_open))
            self.setup_s = self.t_open - T_START
            self.counters_open = self.counters()
            log(f"window opens after round {rd}: setup_s={self.setup_s:.2f}")
            return
        if self.tracing:
            self._stop_trace()
            self.pauses.append((now, self.clock()))
        if self.clock() - self.t_open >= self.seconds:
            self.counters_close = self.counters()
            self.stop_sent = True
            log(f"window closes after round {rd}")
            os.kill(os.getpid(), signal.SIGTERM)

    def _start_trace(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True
        t0 = self.clock()
        self.anchor_fn(1.0).block_until_ready()
        self.trace_anchor = t0
        self.trace_span = [self.clock(), None]

    def _stop_trace(self) -> None:
        import jax
        self.trace_span[1] = self.clock()
        jax.profiler.stop_trace()
        self.tracing = False
        log("trace stopped")


# -- set-up ------------------------------------------------------------------

def build_configs(cell: dict, seed: int, work_dir: str, ckpt_file: str,
                  rehearse: bool = False, trace: bool = False):
    from active_learning_tpu.config import (
        ExperimentConfig, LoaderConfig, OptimizerConfig, PretrainedConfig,
        SchedulerConfig, TrainConfig)
    wl, cfgf, scale = cell["workload"], cell["config"], cell["scale"]
    tr = wl["train"]
    batch = int(cfgf["train_batch"])
    train_cfg = TrainConfig(
        eval_split=float(cfgf["eval_split"]),
        dtype=cfgf["compute_dtype"],
        loader_tr=LoaderConfig(batch_size=batch, num_workers=0),
        loader_te=LoaderConfig(batch_size=batch, num_workers=0),
        optimizer=OptimizerConfig("sgd", lr=float(tr["lr"]),
                                  weight_decay=float(tr["weight_decay"]),
                                  momentum=float(tr["momentum"])),
        scheduler=SchedulerConfig(**tr["scheduler"]),
        pretrained=PretrainedConfig(path=ckpt_file))
    train_cfg = dataclasses.replace(train_cfg, **wl.get("train_cfg", {}))
    cfg = ExperimentConfig(
        exp_name=cell["entry"]["name"].replace(".", "_"),
        exp_hash="bench", log_dir=os.path.join(work_dir, "logs"),
        ckpt_path=os.path.join(work_dir, "ckpt"),
        arg_pool="benchmark", strategy=wl["strategy"],
        **cell["family"].experiment(cfgf),
        freeze_feature=bool(wl["freeze_feature"]),
        rounds=int(wl.get("rounds", 100000)),
        round_budget=int(scale["round_budget"]),
        init_pool_size=int(scale["init_pool_size"]),
        n_epoch=int(scale["n_epoch"]),
        early_stop_patience=int(scale["early_stop_patience"]),
        run_seed=int(seed % (2 ** 31 - 1)),
        compilation_cache_dir=(
            "" if rehearse else os.path.join(ROOT, ".jax_cache")),
        **wl.get("experiment", {}))
    if trace:
        # The program's span recorder, through its documented switch: the
        # span, counter and scope metrics read its record.  A ``--trace 0``
        # run, which gives the end-to-end metrics, leaves it off.
        cfg = dataclasses.replace(cfg, telemetry=dataclasses.replace(
            cfg.telemetry, export_trace=True))
    return cfg, train_cfg


def make_inputs(cell: dict, seed: int):
    """(host arrays, the program's datasets over them, weights), all from the
    seed through the configuration's family."""
    family, cfgf, scale = cell["family"], cell["config"], cell["scale"]
    rows, labels, t_rows, t_labels = family.make_data(
        seed, cfgf, int(scale["pool_rows"]), int(scale["test_rows"]))
    data = family.datasets(cfgf, (rows, labels), (t_rows, t_labels))
    weights = family.make_weights(seed, cfgf)
    return (rows, labels, t_rows, t_labels), data, weights


# -- after the window --------------------------------------------------------

def program_outputs(rec: Record, weights, cell: dict, seed: int, rd: int):
    """What the timed rounds ``rd - 1`` (fit, test) and ``rd`` (query)
    produced, as host arrays, and the decisions the reference follows."""
    import numpy as np
    fit = rec.fits[rd - 1]
    if not fit["epochs"]:
        raise RuntimeError("the fit's epoch program was not seen: the "
                           "resident scan feed was not taken")
    epochs = []
    for ep in fit["epochs"]:
        valid = np.asarray(ep["valid"]) > 0
        epochs.append({"idx": np.asarray(ep["idx"])[valid],
                       "mask": np.asarray(ep["mask"])[valid],
                       "key": np.asarray(ep["key"]),
                       "lr": float(ep["lr"]), "augment": ep["augment"]})
    first = fit["epochs"][0]
    losses = [float(v) for v in np.asarray(first["losses"])[:3]]
    gnorms = [float(v) for v in np.asarray(first["gnorms"])[:3]]
    params = cell["family"].program_params(rec.params[rd - 1], weights)
    test = [e for e in rec.evals if e["round"] == rd - 1 and e["test"]][-1]
    q = rec.queries[rd]
    sc = rec.scores[max(r for r in rec.scores if r <= rd)]
    idxs = sc["idxs"]
    pos_of = {int(v): i for i, v in enumerate(idxs)}
    picked_pos = np.array([pos_of[int(v)] for v in q["picked"]])
    rng = np.random.default_rng([int(seed), 41])
    n_sample = min(int(cell["workload"]["check"]["sample_rows"]), len(idxs))
    extra = rng.choice(len(idxs), size=n_sample, replace=False)
    sample_pos = np.unique(np.concatenate([picked_pos, extra]))
    kind = "margin" if sc["kind"] == "prob_stats" else "embedding"
    values = np.asarray(sc["out"][kind])
    out = {"losses": losses, "gnorms": gnorms, "params": params,
           "test_counts": (test["top1"], test["top5"]),
           "test_rows": test["count"],
           "scores": values[sample_pos]}
    record = {"fit": {"epochs": epochs, "best_epoch": fit["best_epoch"]},
              "score": {"kind": kind, "sample_rows": idxs[sample_pos]}}
    select = {"kind": kind, "values": values, "picked_pos": picked_pos,
              "labeled_pos": np.array([pos_of[int(v)]
                                       for v in q["labeled_before"]
                                       if int(v) in pos_of])}
    return out, record, select


def required_work(rec: Record, cell: dict, rounds) -> dict:
    """Required FLOPs and bytes of the given rounds, by kind of work."""
    work = cell["family"].work
    cfgf = cell["config"]
    frozen = bool(cell["workload"]["freeze_feature"])
    batch = int(cfgf["train_batch"])
    total = {}

    def add(kind, w):
        t = total.setdefault(kind, {"flops": 0.0, "bytes": 0.0})
        for f in t:
            t[f] += w[f]

    for rd in rounds:
        fit = rec.fits.get(rd)
        if fit:
            steps = -(-fit["labeled"] // batch) * fit["epochs_run"]
            add("fit", work(cfgf, "fit", fit["labeled"] * fit["epochs_run"],
                            steps, head_only=frozen))
        sc = rec.scores.get(rd)
        if sc:
            n = len(sc["idxs"])
            add("score", work(cfgf, "forward", n, -(-n // sc["batch"])))
        for ev in rec.evals:
            if ev["round"] == rd:
                add("test" if ev["test"] else "validate", work(
                    cfgf, "forward", ev["rows"],
                    -(-ev["rows"] // ev["batch"])))
    return total


def read_trace(ctl: Controller, sink):
    """The traced round's reduction, on the host's clock."""
    from lib import spans as spans_lib
    from lib import trace as trace_lib
    from lib import window as window_lib
    planes = trace_lib.load_xplane(trace_lib.find_xplane(ctl.trace_dir))
    anchor = trace_lib.first_event_ns(planes, "bench_anchor")
    if anchor is None:
        raise RuntimeError("the anchor program is not in the trace")
    offset = anchor - int(ctl.trace_anchor * 1e9)

    def to_ns(t):
        return int(t * 1e9) + offset

    t0, t1 = to_ns(ctl.trace_span[0]), to_ns(ctl.trace_span[1])
    spans = []
    for t, name, value, _ in sink.events:
        if name in window_lib.PHASE_METRICS:
            spans.append((name, to_ns(t - float(value)), to_ns(t)))
    red = trace_lib.reduce_trace(planes, t0, t1, spans)
    red["spans"] = spans
    # The program's span record over it: idle by deepest span, scopes.
    extras = spans_lib.read_spans(ctl, red, planes, SCOPES)
    if not extras:
        log("no span record: the program's recorder was off")
    return red, planes, extras


def free_program(rec: Record) -> None:
    import jax
    s = rec.strategy
    if s is not None:
        s.trainer.resident_pool.clear()
        s.state = None
        if hasattr(s, "_saved_factors"):     # the coreset factor cache
            s._saved_factors = None
    rec.params.clear()
    rec.fits.clear()
    rec.scores.clear()
    rec.strategy = None
    gc.collect()
    jax.clear_caches()
    gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", default="",
                    help="comma list of fp8,half_batch,state_unchanged: "
                         "also read the control / planted faults")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the runner off the chip; ends non-zero and "
                         "prints no device metric")
    ap.add_argument("--workload-file", default=None)
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--break", dest="break_", default="",
                    help="tests only: break the timed path underneath")
    ap.add_argument("--dump-trace", default=None,
                    help="write a trimmed record of the trace here")
    args = ap.parse_args(argv)
    if not pin_allocator():
        log("the allocator's thresholds were not set")
    cell = load_cell(args)
    # One fixed work directory per cell inside the checkout (checkpoints,
    # logs, the trace), emptied before and after the run; walks off the
    # chip may run side by side, so each takes a directory of its own.
    if args.rehearse:
        import tempfile
        work_dir = tempfile.mkdtemp(prefix="bench_walk_")
    else:
        work_dir = os.path.join(ROOT, ".bench_work",
                                cell["entry"]["name"].replace(".", "_"))
        shutil.rmtree(work_dir, ignore_errors=True)
        os.makedirs(work_dir)
    try:
        return run(args, cell, work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def run(args, cell: dict, work_dir: str) -> int:
    chips = int(cell["entry"]["chips"])

    import jax
    import numpy as np
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse:
        from lib import peaks as peaks_lib
        if platform != "tpu" or len(devices) < chips:
            log(f"needs {chips} TPU chip(s); found {len(devices)} x "
                f"{platform}")
            return 2
        peaks = peaks_lib.peaks_for(kind)
    else:
        peaks = None

    from active_learning_tpu.experiment import driver
    from active_learning_tpu.faults import preempt as preempt_lib
    from lib import reference as ref_lib
    from lib import readers as readers_lib
    from lib import window as window_lib

    family = cell["family"]
    trace_dir = os.path.join(work_dir, "trace") if args.trace else None

    arrays, data, weights = make_inputs(cell, args.seed)
    ckpt_file = family.save_checkpoint(weights, work_dir)
    cfg, train_cfg = build_configs(cell, args.seed, work_dir, ckpt_file,
                                   rehearse=args.rehearse,
                                   trace=bool(args.trace))
    log(f"inputs made: pool {arrays[0].dtype}{list(arrays[0].shape)}, "
        f"device {kind} x {len(devices)}")

    rec = Record()
    anchor_fn = jax.jit(_anchor)
    anchor_fn(1.0).block_until_ready()
    ctl = Controller(args.seconds, trace_dir, anchor_fn)
    sink = window_lib.RecordingSink(on_round_end=ctl.on_round_end)

    orig_build = driver.build_experiment

    def build_experiment(*a, **kw):
        strategy = orig_build(*a, **kw)
        install_hooks(strategy, rec, args.break_)
        return strategy

    driver.build_experiment = build_experiment
    try:
        driver.run_experiment(cfg, sink=sink, data=data, train_cfg=train_cfg)
        log("the round loop ended by itself before the window closed")
        return 1
    except preempt_lib.PreemptionRequested:
        pass
    finally:
        driver.build_experiment = orig_build
        if ctl.tracing:
            ctl._stop_trace()
    t_stop = time.perf_counter()

    rounds = window_lib.window_rounds(sink.events, ctl.t_open, ctl.pauses)
    if not rounds:
        log("no round completed inside the window")
        return 1
    round_s = window_lib.round_seconds(rounds)
    last = rounds[-1]["round"]

    peak = 0
    for dev in jax.local_devices()[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    # The run's health: the window's fits took the expected feed from the
    # pinned pool.  A run that fell back to a host feed is a failed run.
    expect = cell["workload"].get("expect_feed",
                                  {"source": "resident", "form": "scan"})
    pinned = pinned_arrays(rec.strategy.trainer.resident_pool)
    pool = pool_is_pinned(pinned, int(cell["scale"]["pool_rows"]))
    if pool is None:
        log(f"the pool is not pinned on the device: {pinned}")
        return 1
    log(f"the pool is pinned: {pool['rows']} rows, {pool['layout']} over "
        f"{len(pool['shards'])} device(s)")
    for r in rounds:
        feed = rec.fits[r["round"]]["feed"]
        if any(feed.get(k) != v for k, v in expect.items()):
            log(f"round {r['round']} fit through {feed}, not {expect}")
            return 1

    # Per-layer readings.
    ctx = {"rounds": rounds, "peaks": peaks, "chips": chips,
           "counters": {
               "jit_cache_miss_delta": window_lib.counter_sum(
                   sink.events, "jit_cache_miss_delta", rounds),
               "persistent_cache_misses": float(
                   ctl.counters_close["misses"]
                   - ctl.counters_open["misses"]),
               "persistent_cache_hits": float(
                   ctl.counters_close["hits"] - ctl.counters_open["hits"])},
           "trace": None, "spans": None, "work": {}}
    breakdown, span_extras = None, {}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if args.trace and not args.rehearse:
        red, planes, span_extras = read_trace(ctl, sink)
        ctx["trace"] = red
        ctx["spans"] = red.get("spans_record")
        ctx["work"] = required_work(rec, cell, [rounds[0]["round"]])
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [[n[:64], s] for n, s in red["top_ops"]],
                     "idle_gaps": [[n, s] for n, s in red["idle_gaps"]]}
        if "host_self" in span_extras:
            breakdown["host_self"] = [
                [n, s] for n, s in span_extras.pop("host_self")]
            # The two clocks and the tree's checks: for the reader of the
            # log, no metric reads them.
            log("[spans] " + json.dumps(span_extras))
        if args.dump_trace:
            _dump_trace(args.dump_trace, planes)
    elif args.trace:
        # Off the chip there is no device trace; the span record is read all
        # the same, so the walk drives the span and counter readers.
        from lib import spans as spans_lib
        ctx["spans"] = spans_lib.load_span_record(trace_dir)
    metrics = {}
    if args.trace:
        for m in cell["metrics"]:
            value = readers_lib.reader_for(m)(ctx, **m["params"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics["round_s"] = {"value": round_s, "unit": "s/round"}
        metrics["setup_s"] = {"value": ctl.setup_s, "unit": "s"}

    # The comparison, once the window has closed and the peak is read.
    out, record, select = program_outputs(rec, weights, cell, args.seed,
                                          last)
    images, labels, t_images, t_labels = arrays
    free_program(rec)
    t_ref0 = time.perf_counter()
    frozen = bool(cell["workload"]["freeze_feature"])
    hyper = cell["workload"]["train"]
    micro = int(cell["workload"]["check"].get("micro_rows", 32))
    common = (family, weights, images, labels, t_images, t_labels, record,
              cell["config"], hyper, frozen)
    ref = ref_lib.reference_outputs(*common, micro=micro)
    numbers = ref_lib.compare(out, ref, weights, len(t_labels))
    numbers["test_rows"] = abs(out["test_rows"] - len(t_labels)) / len(
        t_labels)
    if select["kind"] == "margin":
        numbers["pick_regret"] = ref_lib.margin_pick_regret(
            select["values"], select["picked_pos"])
    else:
        numbers["pick_regret"] = ref_lib.kcenter_regret(
            select["values"], select["labeled_pos"], select["picked_pos"])
    t_ref = time.perf_counter() - t_ref0
    limits = cell["workload"]["check"]["limits"]
    check = {k: [numbers[k], limits[k]] for k in limits}
    correct = all(np.isfinite(v) and v <= lim for v, lim in check.values())

    controls = {}
    for variant in [v for v in args.control.split(",") if v]:
        kw = ({"quant": variant} if variant == "fp8" else {"fault": variant})
        cand = ref_lib.reference_outputs(*common, micro=micro, **kw)
        controls[variant] = ref_lib.compare(cand, ref, weights,
                                            len(t_labels))

    result = {"correct": bool(correct), "attempted": len(rounds),
              "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["rounds"] = [{"round": r["round"], "seconds": r["seconds"],
                         **r["phases"]} for r in rounds]
    result["reference_s"] = t_ref
    result["after_window_s"] = time.perf_counter() - t_stop
    result["bounds"] = ctx.get("bounds", {})
    if controls:
        result["control"] = controls
    result["uncompared"] = {k: v for k, v in numbers.items()
                            if k not in limits}
    result["check"] = check
    for name, (value, limit) in check.items():
        print(f"check {name}: {value:.6g} (limit {limit:g})",
              file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result))
    return REHEARSAL_EXIT if args.rehearse else 0


def pinned_arrays(cache) -> list:
    """Every array the program pinned (``trainer.resident_pool``), read off
    the array itself: its rows and, per device, the rows ``[start, stop)``
    of the shard that device holds."""
    out = []
    for entry in ((cache or {}).get("images") or {}).values():
        array = entry[1]
        rows = int(array.shape[0])
        out.append({"rows": rows, "shards": {
            str(sh.device.id): [sh.index[0].start or 0,
                                rows if sh.index[0].stop is None
                                else sh.index[0].stop]
            for sh in array.addressable_shards}})
    return out


def pool_is_pinned(pinned: list, n_pool: int):
    """The pinned array (``pinned_arrays``) that is the whole pool, with its
    ``layout``, or None.  The pool has ``n_pool`` rows itself (row sharding
    may pad it to divide evenly: fewer pad rows than shards), and its
    devices' distinct shards tile them: one shard on every device
    (replicated) or one part each (row-sharded).  Another array whose
    replicas happen to add up to ``n_pool`` is not the pool."""
    for array in pinned:
        parts = sorted({tuple(span) for span in array["shards"].values()})
        if not parts or not n_pool <= array["rows"] < n_pool + len(parts):
            continue
        edge = 0
        for start, stop in parts:
            if start != edge:
                break
            edge = stop
        else:
            if edge == array["rows"]:
                return {**array, "layout": ("replicated" if len(parts) == 1
                                            else "row-sharded")}
    return None


def _anchor(x):
    return x + 1.0


_anchor.__name__ = "bench_anchor"


def _dump_trace(path: str, planes) -> None:
    """The trimmed record the tests keep (``testdata/``): the first 2,000
    operations of the first device with the programs that cover them, and
    what the reduction gives for them."""
    from lib import trace as trace_lib
    first = planes[0]
    ops = sorted(first["lines"].get(trace_lib.OP_LINE, ()),
                 key=lambda e: e[1])[:2000]
    if not ops:
        return
    t0, t1 = ops[0][1], ops[-1][1] + ops[-1][2]
    mods = [e for e in first["lines"].get(trace_lib.MODULE_LINE, ())
            if e[1] < t1 and e[1] + e[2] > t0]
    trimmed = [{"name": first["name"],
                "lines": {trace_lib.OP_LINE: ops,
                          trace_lib.MODULE_LINE: mods}}]
    small = trace_lib.reduce_trace(trimmed, t0, t1)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"planes": trimmed, "t0": t0, "t1": t1,
                   "expect": {"busy_s": small["busy_s"],
                              "programs": small["programs"]}}, fh)


def _altered_scores(collect):
    """Tests only: an answer altered where it is produced."""
    import numpy as np

    def collect_scores(idxs, kind, keys=None):
        out = dict(collect(idxs, kind, keys=keys))
        for k in ("margin", "embedding"):
            if k in out:
                out[k] = np.asarray(out[k]) * 1.5
        return out
    return collect_scores


def _broken_scan(scan, how: str):
    """Tests only: the epoch program with a fault planted under it."""
    import jax
    import jax.numpy as jnp

    def broken(state, images, labels, idx_mat, mask_mat, valid, *rest, **kw):
        if how == "half_batch":
            # Half of every batch left out, the mean taken over the rest.
            half = mask_mat.shape[1] // 2
            return scan(state, images, labels, idx_mat,
                        mask_mat.at[:, half:].set(0.0), valid, *rest, **kw)
        keep = jax.tree.map(jnp.copy, state)      # the state comes back
        out = scan(state, images, labels, idx_mat, mask_mat, valid, *rest,
                   **kw)
        return (keep,) + tuple(out[1:])
    return broken


if __name__ == "__main__":
    sys.exit(main())
