"""The experiment driver: build everything from an ExperimentConfig and run
the active-learning round loop.

TPU-native counterpart of ``main(args)`` (src/main_al.py:43-184).  The loop
body is the reference's, verb for verb:

    for rd in start_round..rounds:
        query -> update          [skipped at rd 0 unless init_pool_size==0]
        init_network_weights     (random re-init, then SSL overlay)
        train                    (per-round fit with early stopping)
        load_best_ckpt
        test
        save_experiment

Differences by design: ONE persistent JAX runtime/mesh across all rounds (no
per-round mp.spawn, strategy.py:288-315), typed configs instead of
argparse+exec, and a JSONL metrics sink instead of Comet — with the same
metric names (main_al.py:24-40).
"""

from __future__ import annotations

import copy
import dataclasses
import os
import time
import uuid
import zlib
from datetime import date
from typing import Optional, Tuple

import jax
import numpy as np

from .. import faults
from ..config import ExperimentConfig, TrainConfig, config_to_dict
from ..data import get_data
from ..faults import ladder as ladder_lib
from ..faults import preempt as preempt_lib
from ..initial_pool import generate_eval_idxs, generate_init_lb_idxs
from ..models.factory import get_network
from ..parallel import mesh as mesh_lib
from ..pool import PoolState
from ..strategies import get_strategy
from ..telemetry import diagnostics as diag_lib
from ..telemetry import jax_events as tele_jax_events
from ..telemetry import profiler as tele_profiler
from ..telemetry import runtime as tele_runtime
from ..telemetry import spans as tele_spans
from ..train import checkpoint as ckpt_lib
from ..utils.logging import get_logger, setup_logging
from ..utils.metrics import MetricsSink, make_sink
from ..utils.tracing import phase_timer
from ..train.trainer import Trainer
from . import arg_pools as arg_pools_lib
from . import pipeline as pipeline_lib
from . import resume as resume_lib


# The one "is the configured platform CPU" rule (parallel/mesh.py), under
# the name this module's cache gate — and its tests — know it by.
_platform_is_cpu = mesh_lib.platform_is_cpu


# Where the persistent compilation cache lives when nothing outside the
# program places it: ONE fixed directory inside the checkout (git-ignored).
# The path is part of every cache key's lookup, so it must never carry a
# temporary name, pid or time — a directory that moves never hits.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_compilation_cache_dir(cache_dir: Optional[str] = None
                                  ) -> Optional[str]:
    """WHERE the persistent compilation cache goes — the one rule, no
    side effects (``enable_compilation_cache`` applies it).

    The cache is PLACED FROM OUTSIDE: where ``$JAX_COMPILATION_CACHE_DIR``
    is set, that directory is used whatever ``cache_dir`` says (a
    launcher that mounts a cache volume must find every entry point
    caching there and nowhere else).  Unset: ``cache_dir`` (the
    --compilation_cache_dir flag) if given, else
    ``DEFAULT_COMPILATION_CACHE_DIR``; ``cache_dir == ""`` asks for
    none (None).

    CPU backends get NO cache by default (the gate was written against
    jax 0.4.37, whose CPU runtime corrupted donated buffers in
    cache-deserialized executables; not re-verified on 0.9.0, and
    compiles are cheap on CPU anyway): only an EXPLICIT choice — the
    environment variable or ``cache_dir`` — enables it there.
    Accelerators are unaffected."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if cache_dir == "" or (not cache_dir and _platform_is_cpu()):
        return None
    return cache_dir or DEFAULT_COMPILATION_CACHE_DIR


# Persistent-cache traffic of THIS process (telemetry/jax_events.py, the
# home of every jax.monitoring listener): journaled at every round end.
compilation_cache_counts = tele_jax_events.compilation_cache_counts


def enable_compilation_cache(cache_dir: Optional[str] = None
                             ) -> Optional[str]:
    """Turn on JAX's persistent (on-disk) compilation cache for the whole
    process, so AL round N+1 — and the next RUN of the same protocol —
    reuse round N's compiled executables instead of re-paying the
    cold-compile tax.  Shape bucketing (pool.bucket_size in the trainer
    and k-center) keeps the keys stable as the labeled set grows; this
    cache keeps the hits across process restarts.

    The directory comes from ``resolve_compilation_cache_dir``.  When
    the environment variable placed it, JAX has already read it and NO
    ``jax_compilation_cache_dir`` update is made here at all.  Returns
    the directory in use, or None when this call enabled none."""
    resolved = resolve_compilation_cache_dir(cache_dir)
    if resolved is None:
        if cache_dir != "":
            get_logger().info(
                "persistent compilation cache off on the CPU backend by "
                "default; pass --compilation_cache_dir or set "
                "$JAX_COMPILATION_CACHE_DIR to force it")
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", resolved)
    # Sub-second compiles aren't worth a disk entry; everything else
    # is (the round tax is dominated by a handful of large modules).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return resolved


# The int8 gradient sync's pinned accuracy-delta bound: the probe model
# trained through the quantized step must land within this much test
# accuracy of its bit-exact f32 twin (same seeds, same data) or the run
# degrades to f32.  Pinned by tests/test_backward.py.
INT8_PROBE_MAX_ACC_DELTA = 0.05


def run_grad_allreduce_probe(mesh, mode: str = "int8"
                             ) -> Tuple[bool, Optional[float]]:
    """The multichip learning probe gating the quantized gradient sync
    (DESIGN.md §4 + §15): train one tiny probe model twice over the
    live mesh — once through the bit-exact f32 step, once through the
    quantized-sync step EXACTLY as the run would build it (``mode``
    is the run's requested grad_allreduce, so the Trainer resolves the
    same wire form: the all-gather int8 sync on 2-8 device meshes, the
    pod-tier reduce-scatter form above the crossover or under
    ``int8_rs``), same seeds — and compare test accuracy.  The
    same prove-it-learns discipline as ``__graft_entry__``'s dryrun
    gate: a subtly wrong quantized reduction keeps params finite and
    loss moving while computing the wrong numbers; only an accuracy
    comparison catches it.  Returns ``(ok, delta)``; any probe failure
    reads as not-ok (the caller degrades to f32 loudly, never crashes
    the run for an optional optimization)."""
    try:
        # Chaos seam (tests/test_faults.py): an injected failure here is
        # exactly a broken probe — the run must degrade to f32, loudly.
        faults.site("grad_probe")

        import dataclasses as _dc

        import flax.linen as nn
        import jax.numpy as jnp

        from ..config import (LoaderConfig, OptimizerConfig,
                              SchedulerConfig)
        from ..data.synthetic import get_data_synthetic

        class _Probe(nn.Module):
            """Minimal SSLClassifier-interface model for the gate."""

            num_classes: int = 4
            feat_dim: int = 32
            freeze_feature: bool = False

            @nn.compact
            def __call__(self, x, train: bool = True,
                         return_features: bool = False):
                emb = x.reshape((x.shape[0], -1)).astype(jnp.float32)
                emb = nn.tanh(nn.Dense(self.feat_dim, name="proj")(emb))
                logits = nn.Dense(self.num_classes, name="linear")(emb)
                return (logits, emb) if return_features else logits

        data = get_data_synthetic(n_train=96, n_test=128, num_classes=4,
                                  image_size=16, seed=7)
        base_cfg = TrainConfig(
            eval_split=0.1, loader_tr=LoaderConfig(batch_size=16),
            loader_te=LoaderConfig(batch_size=16),
            optimizer=OptimizerConfig(name="sgd", lr=0.3),
            scheduler=SchedulerConfig(name="cosine", t_max=8),
            resident_scoring_bytes=0)

        def fit_acc(ar_mode: str) -> float:
            trainer = Trainer(_Probe(),
                              _dc.replace(base_cfg,
                                          grad_allreduce=ar_mode),
                              mesh, num_classes=4)
            # The probe fits on the DETERMINISTIC (al) view: the int8
            # step decorrelates per-shard augmentation keys, so an
            # augmented view would compare two different data streams
            # and the delta would measure augmentation luck, not
            # quantization.  On the template+noise synthetic both paths
            # saturate (~100%); a broken quantized reduction does not.
            state = trainer.init_state(
                jax.random.PRNGKey(1),
                data[2].gather(np.zeros(1, dtype=np.int64)))
            result = trainer.fit(
                state, data[2], np.arange(len(data[2])), data[2],
                np.array([], dtype=np.int64), n_epoch=8, es_patience=0,
                rng=np.random.default_rng(1))
            metrics = trainer.evaluate(result.state, data[1],
                                       np.arange(len(data[1])))
            return float(metrics["accuracy"])

        delta = round(abs(fit_acc("f32") - fit_acc(mode)), 4)
        return delta <= INT8_PROBE_MAX_ACC_DELTA, delta
    except (Exception, faults.ThreadDeath) as e:  # noqa: BLE001
        # Degrade, never crash: ThreadDeath included deliberately — the
        # probe runs on the MAIN thread, where an injected
        # grad_probe:die would otherwise kill the whole run instead of
        # the f32 fallback this site's contract promises.
        get_logger().warning(f"grad_allreduce probe failed to run: {e!r}")
        return False, None


def build_experiment(
    cfg: ExperimentConfig,
    sink: Optional[MetricsSink] = None,
    data=None,
    mesh=None,
    train_cfg: Optional[TrainConfig] = None,
    model=None,
    skip_init_pool: bool = False,
):
    """Wire the full stack (data -> model -> mesh -> trainer -> pool ->
    strategy) from one config (main_al.py:48-120).

    ``data`` (a (train_set, test_set, al_set) triple), ``mesh``,
    ``train_cfg`` and ``model`` can be injected for tests and benchmarks.
    ``skip_init_pool`` is set on resume: the restored pool replaces the
    init pool, so labeling one here would emit a stale round-0 metric and
    rewrite the round-0 audit asset.
    """
    if train_cfg is None:
        train_cfg = arg_pools_lib.get_train_config(
            cfg.arg_pool, cfg.dataset, pretrained_root=cfg.pretrained_root)
    if data is None:
        # Pass the ImbalanceConfig itself: the dataset factories read it
        # by attribute (a dict here crashed every config-driven
        # imbalanced run with AttributeError).
        data = get_data(cfg.dataset, data_path=cfg.dataset_dir,
                        debug_mode=cfg.debug_mode,
                        imbalance_args=cfg.imbalance,
                        download=cfg.download_data)
    train_set, test_set, al_set = data
    # Disk datasets with deterministic views get the experiment-lifetime
    # decode-once memmap cache: every acquisition round re-scores the full
    # pool and every round re-evaluates the full test set, so decode —
    # ~30x slower than device scoring on ImageNet trees — must be paid
    # once, not per round (data/cache.DecodedPoolCache).
    from ..data.cache import DecodedPoolCache, maybe_wrap_decoded

    # Default under ~/.cache, NOT tempfile.gettempdir(): /tmp is commonly
    # tmpfs, where a multi-GB "disk" memmap would silently consume host
    # RAM past every configured RAM budget.
    cache_dir = (train_cfg.decoded_cache_dir
                 or os.path.join(os.path.expanduser("~"), ".cache",
                                 "al_tpu_decoded"))
    budget = train_cfg.cache_decoded_bytes
    al_set = maybe_wrap_decoded(al_set, cache_dir, budget)
    if isinstance(al_set, DecodedPoolCache):
        # One byte budget bounds the DIRECTORY, not each wrap: the test
        # set only caches into what the al pool left.
        budget -= len(al_set) * int(np.prod(al_set.image_shape))
    if test_set is not None:
        test_set = maybe_wrap_decoded(test_set, cache_dir, budget)
    num_classes = al_set.num_classes

    if model is None:
        # --dtype/--stem/--bn_stats_dtype beat the arg pool's TrainConfig;
        # "auto" dtype lands on bfloat16 when the live backend is TPU, and
        # auto BN stats follow the compute dtype (models/factory.py).
        model = get_network(cfg.dataset, cfg.model,
                            freeze_feature=cfg.freeze_feature,
                            num_classes=num_classes,
                            dtype=cfg.dtype or train_cfg.dtype,
                            stem=cfg.stem or train_cfg.stem,
                            bn_stats_dtype=(cfg.bn_stats_dtype
                                            or train_cfg.bn_stats_dtype))
    if cfg.resident_scoring_bytes is not None:
        # --resident_scoring_bytes beats the arg pool: HBM sizing is a
        # per-chip deployment choice, not a dataset hyperparameter.  (The
        # arg-pool default is None = auto-size from live HBM headroom.)
        train_cfg = dataclasses.replace(
            train_cfg, resident_scoring_bytes=cfg.resident_scoring_bytes)
    if cfg.train_feed is not None:
        # --train_feed beats the arg pool for the same reason: which leg
        # of the feed hierarchy wins is a deployment/HBM question, and
        # every leg is bit-identical at the same seeds anyway.
        train_cfg = dataclasses.replace(train_cfg,
                                        train_feed=cfg.train_feed)
    if cfg.pool_sharding is not None:
        # --pool_sharding beats the arg pool: the resident layout is a
        # mesh/HBM deployment choice, and every layout is bit-identical
        # (scores, batches, picks) anyway.
        train_cfg = dataclasses.replace(train_cfg,
                                        pool_sharding=cfg.pool_sharding)
    if cfg.feed_workers is not None:
        train_cfg = dataclasses.replace(train_cfg,
                                        feed_workers=cfg.feed_workers)
    if cfg.pool_backend is not None:
        # --pool_backend beats the arg pool: which storage tier holds the
        # pool is a host-RAM deployment choice, and the disk backend is
        # bit-identical to memory by contract (DESIGN.md §16).
        train_cfg = dataclasses.replace(train_cfg,
                                        pool_backend=cfg.pool_backend)
    if cfg.fused_optimizer is not None:
        # --fused_optimizer beats the arg pool: bit-identical to optax
        # at f32 state, so this is a throughput/HBM deployment choice.
        train_cfg = dataclasses.replace(train_cfg,
                                        fused_optimizer=cfg.fused_optimizer)
    if cfg.optim_state_dtype is not None:
        train_cfg = dataclasses.replace(
            train_cfg, optim_state_dtype=cfg.optim_state_dtype)
    if cfg.grad_allreduce is not None:
        train_cfg = dataclasses.replace(train_cfg,
                                        grad_allreduce=cfg.grad_allreduce)
    if mesh is None:
        mesh = mesh_lib.make_mesh(cfg.num_devices)
    # Large-batch scaling (--scale_batch auto, DESIGN.md §15): as the
    # global batch grows with the mesh, the large-batch ConvNet rules
    # (train/optim.apply_batch_scaling — batch x ndev so the arg pool's
    # batch becomes per-chip, lr x ndev, >=5-epoch gradual warmup) keep
    # accuracy from silently eroding at pod-scale batch sizes.  Off by
    # default: the arg pool's batch stays the reference's GLOBAL batch.
    scale_mode = getattr(cfg, "scale_batch", None) or "off"
    if scale_mode not in ("auto", "off"):
        raise ValueError(
            f"scale_batch={scale_mode!r} is not one of 'auto'/'off'")
    if scale_mode == "auto":
        from ..train.optim import apply_batch_scaling
        train_cfg, scaled = apply_batch_scaling(train_cfg,
                                                mesh.devices.size)
        if scaled:
            get_logger().info(
                "scale_batch=auto: global batch "
                f"{train_cfg.loader_tr.batch_size} "
                f"({mesh.devices.size} devices x per-chip "
                f"{train_cfg.loader_tr.batch_size // mesh.devices.size}),"
                f" lr {train_cfg.optimizer.lr:g}, warmup "
                f"{train_cfg.scheduler.warmup_epochs} epochs "
                "(large-batch scaling rules)")
    # The quantized gradient sync is GATED, not just flagged
    # (DESIGN.md §4): int8 only engages when the mesh is multi-device
    # (resolve_grad_allreduce) AND the multichip learning probe passes —
    # a tiny probe model trained through the int8 step must match its
    # bit-exact-f32 twin's test accuracy within the pinned bound.  A
    # probe failure (or injected grad_probe fault) degrades the run to
    # f32 LOUDLY: logged here, journaled + metric'd by run_experiment
    # via trainer.grad_allreduce_degraded.
    grad_allreduce_degraded = False
    requested_ar = getattr(train_cfg, "grad_allreduce", "f32") or "f32"
    if mesh_lib.resolve_grad_allreduce(requested_ar, mesh) == "int8":
        wire = mesh_lib.resolve_int8_wire(requested_ar, mesh)
        ok, delta = run_grad_allreduce_probe(mesh, requested_ar)
        if not ok:
            get_logger().warning(
                f"grad_allreduce={requested_ar} ({wire} wire form) "
                "FAILED the multichip learning probe "
                f"(accuracy delta {delta if delta is not None else 'n/a'} "
                f"vs bound {INT8_PROBE_MAX_ACC_DELTA}); degrading this "
                "run to the bit-exact f32 gradient sync")
            train_cfg = dataclasses.replace(train_cfg, grad_allreduce="f32")
            grad_allreduce_degraded = True
        else:
            get_logger().info(
                f"grad_allreduce={requested_ar}: learning probe passed "
                f"on the {wire} wire form "
                f"(accuracy delta {delta} <= {INT8_PROBE_MAX_ACC_DELTA})")
    trainer = Trainer(model, train_cfg, mesh, num_classes)
    trainer.grad_allreduce_degraded = grad_allreduce_degraded

    # The disk tier (data/diskpool.py, DESIGN.md §16): pools bigger than
    # any host's RAM spill to demand-paged disk extents — auto-engaged
    # above the host-RAM watermark, forced with --pool_backend disk.
    # Only fully-decoded in-RAM pools are wrapped: DecodedPoolCache and
    # the stream service's StreamDataset are ALREADY disk/memmap-backed
    # (their ``images`` is an np.memmap — an ndarray subclass — so the
    # isinstance gate below must exclude it), and imperative-view
    # datasets never expose a whole-pool array to spill in the first
    # place.  On a multi-process mesh each host spills ONLY its own
    # mesh.shard_rows row range (process_pool_rows) — the full array
    # never lands on any one host's disk tier.
    from ..data import diskpool as diskpool_lib
    pool_images = getattr(al_set, "images", None)
    if (isinstance(pool_images, np.ndarray)
            and not isinstance(pool_images, np.memmap)):
        pool_bytes = len(al_set) * int(np.prod(al_set.image_shape))
        backend = diskpool_lib.resolve_pool_backend(
            getattr(train_cfg, "pool_backend", "auto") or "auto",
            pool_bytes,
            getattr(train_cfg, "pool_disk_watermark_frac", 0.5))
        if backend == "disk":
            local_rows = (mesh_lib.process_pool_rows(mesh, len(al_set))
                          if mesh_lib.is_multiprocess(mesh) else None)
            train_set, al_set = diskpool_lib.wrap_pool(
                train_set, al_set,
                os.path.join(cfg.log_dir, "disk_pool"),
                page_rows=train_cfg.pool_page_rows,
                host_cache_bytes=train_cfg.pool_host_cache_bytes,
                local_rows=local_rows)
            get_logger().info(
                f"pool_backend=disk: {pool_bytes / 1e9:.2f} GB pool "
                f"demand-paged from {cfg.log_dir}/disk_pool "
                f"(page_rows={train_cfg.pool_page_rows}, host cache "
                f"{train_cfg.pool_host_cache_bytes / 1e9:.2f} GB"
                + (f", local rows {local_rows.start}:{local_rows.stop}"
                   if local_rows is not None else "") + ")")

    targets = train_set.targets[: len(train_set)]
    init_pool_size = cfg.resolved_init_pool_size()
    if cfg.debug_mode:
        # Tiny fixed pools for smoke runs (main_al.py:87-92).
        init_idxs = (np.zeros(0, dtype=np.int64) if init_pool_size == 0
                     else np.arange(5, dtype=np.int64))
        eval_idxs = np.arange(15, 20, dtype=np.int64)
    else:
        eval_idxs = generate_eval_idxs(targets, num_classes,
                                       ratio=train_cfg.eval_split,
                                       random_seed=cfg.eval_split_seed)
        if init_pool_size == 0 or skip_init_pool:
            # On resume the restored pool replaces the init pool — skip the
            # (ImageNet-scale) balanced-index generation entirely.
            init_idxs = np.zeros(0, dtype=np.int64)
        else:
            init_idxs = generate_init_lb_idxs(
                targets, num_classes, eval_idxs, init_pool_size,
                init_pool_type=cfg.init_pool_type,
                random_seed=cfg.init_pool_seed)

    pool = PoolState.create(len(al_set), eval_idxs)
    rng = np.random.default_rng(cfg.run_seed)
    strategy_cls = get_strategy(cfg.strategy)
    strategy = strategy_cls(train_set, al_set, test_set, model, trainer,
                            pool, cfg, train_cfg, sink=sink, rng=rng)
    if not skip_init_pool:
        strategy.update(init_idxs, len(init_idxs))
    return strategy


# Every per-round metric the DRIVER emits through the MetricsSink, by
# name.  The Prometheus scrape file (--prometheus_file) must carry each
# of these as an ``al_run_`` gauge whenever the driver emitted it that
# round — the completeness contract tests/test_profiler.py diffs sink
# names against scrape samples with (the per-epoch trainer/strategy
# series — step_time, imgs_per_sec, spec_hit_frac — are per-EPOCH or
# strategy-owned and ride the heartbeat/status path instead).  The
# device-truth metrics (telemetry/profiler.RoundProfiler.emit_metrics)
# register dynamically the same way: sink + gauges from one dict.
# The experiment-truth diagnostics gauges (telemetry/diagnostics.py,
# DESIGN.md §13): score-distribution summary + inter-round drift,
# selection composition, k-center pick distances, calibration — emitted
# through _emit_round_gauges whenever the strategy's diagnostics layer
# produced them that round, and POPPED from the scrape gauges on any
# round that did not (the honesty rule reaches the scrape: a drift the
# current round could not compute must not linger looking current).
DIAGNOSTICS_GAUGES = (
    "rd_score_mean", "rd_score_std", "rd_score_drift_psi",
    "rd_score_drift_js", "rd_pick_class_balance", "rd_pick_novelty",
    "rd_pick_min_dist", "rd_pick_mean_dist", "rd_ece",
)

# The streaming service's per-round gauges (stream/service.py): ingest
# volume, WAL backlog, trigger accounting, and ack-latency percentiles.
# Flat names only — the per-cause trigger counters ride the
# ``name{label=value}`` labeled-gauge convention (telemetry/prom.
# gauge_samples) and are completeness-checked by tests/test_stream.py.
STREAM_GAUGES = (
    "ingest_rows_total", "ingest_labels_total", "pool_rows_total",
    "wal_backlog_rows", "rounds_triggered_total", "ingest_ack_ms_p50",
    "ingest_ack_ms_p99",
)

# The disk tier's paging gauges (data/diskpool.py, DESIGN.md §16):
# rows resident on disk, the host block cache's hit fraction, paging
# throughput, and the gather-observed page-in stall percentiles.
# Emitted only on rounds where the pool runs on the disk backend — the
# memory backend pops them from the scrape (None drops, the same
# honesty rule as the diagnostics gauges).
PAGING_GAUGES = (
    "pool_disk_rows", "pool_cache_hit_frac", "page_in_rows_per_sec",
    "page_in_stall_ms_p50", "page_in_stall_ms_p99",
)

PER_ROUND_GAUGES = (
    "rd_round_time", "overlap_frac", "round_vs_max_phase",
    "rd_spec_score_time", "jit_cache_miss_delta", "fault_retries_total",
    "degrade_events", "hbm_peak_gb",
) + DIAGNOSTICS_GAUGES + STREAM_GAUGES + PAGING_GAUGES


def _emit_round_gauges(telemetry, sink: MetricsSink, rd: int,
                       metrics: dict) -> None:
    """One dict -> BOTH channels: the metrics sink (per-round history)
    and the Prometheus gauges (latest-value scrape).  Emitting through
    one spelling is what makes the scrape-file completeness auditable —
    a metric added to one channel cannot silently miss the other."""
    numeric = {k: v for k, v in metrics.items() if v is not None}
    for name, value in numeric.items():
        sink.log_metric(name, value, step=rd)
    telemetry.set_gauges(**numeric)


def _emit_overlap_telemetry(telemetry, sink: MetricsSink, rd: int,
                            round_s: float, phase_s: dict,
                            spec_s: float, pipeline_mode: str) -> None:
    """The pipelined round's proof-of-overlap metrics, from the driver's
    OWN telemetry stream:

      rd_round_time       the round span's wall;
      overlap_frac        1 − round / (Σ phase walls + speculative-
                          scorer busy) — the fraction of serial-
                          equivalent work hidden by overlap (a
                          sequential round reads ~0);
      round_vs_max_phase  round / max(phase, spec) — 1.0 is the
                          theoretical floor (round == its longest
                          stream), the sum/max gap still on the table.
    """
    if not telemetry.train_metrics or not phase_s:
        return
    serial = sum(phase_s.values()) + spec_s
    longest = max(max(phase_s.values()), spec_s)
    if serial <= 0 or longest <= 0:
        return
    _emit_round_gauges(telemetry, sink, rd, {
        "rd_round_time": round(round_s, 3),
        "overlap_frac": round(max(0.0, 1.0 - round_s / serial), 4),
        "round_vs_max_phase": round(round_s / longest, 3),
        "rd_spec_score_time": (round(spec_s, 3)
                               if pipeline_mode != "off" else None),
    })


def _emit_round_telemetry(telemetry, sink: MetricsSink, rd: int,
                          strategy, ladder=None,
                          retries_baseline: int = 0) -> None:
    """Round-boundary telemetry: the jit-compile miss delta (round 0
    carries the cold tax; ANY nonzero delta after it is a shape leak —
    the test_compile_reuse regression, now visible in production
    metrics), the HBM high-water where the backend exposes
    memory_stats, the failure-model counters (fault_retries_total
    cumulative, degrade_events — DESIGN.md §10), the Prometheus gauge
    refresh, and an incremental
    trace export so a crash mid-run still leaves trace.json on disk."""
    if not telemetry.train_metrics:
        return
    # The experiment-truth layer's round close-out (DESIGN.md §13):
    # drift vs the previous scored round, score summary, composition,
    # calibration — through the SAME one-dict-two-channels spelling as
    # every other per-round metric (the PER_ROUND_GAUGES completeness
    # contract covers them automatically).
    diag = getattr(strategy, "diagnostics", None)
    if diag is not None:
        diag_gauges = diag.finish_round(rd)
        _emit_round_gauges(telemetry, sink, rd, diag_gauges)
        # Any diagnostics gauge THIS round produced no value for is
        # popped from the scrape set (set_gauges drops on None): a
        # below-MIN_DRIFT_N round must retract last round's drift, not
        # let it scrape as current.
        stale = {k: None for k in DIAGNOSTICS_GAUGES
                 if diag_gauges.get(k) is None}
        if stale:
            telemetry.set_gauges(**stale)
    # Per-RUN retries: the process counter is cumulative across every
    # run sharing this interpreter (a fleet worker, pytest), so the
    # run-start baseline is subtracted — a run reports only the retries
    # its own rounds absorbed.
    retries = faults.retry_counters()
    run_retries = retries["total"] - retries_baseline
    hbm = tele_runtime.hbm_high_water_gb()
    # Per-round history + latest-value gauges from ONE dict (the scrape
    # completeness contract, PER_ROUND_GAUGES).
    _emit_round_gauges(telemetry, sink, rd, {
        "jit_cache_miss_delta": telemetry.jit_cache_delta(),
        "fault_retries_total": run_retries,
        "degrade_events": ladder.events if ladder is not None else 0,
        "hbm_peak_gb": hbm,
    })
    # The disk tier's per-round paging accounting (PAGING_GAUGES):
    # take_round_stats drains and resets the counters, so each round's
    # numbers are that round's alone.  On the memory backend the
    # dataset has no disk tier and the gauges retract from the scrape
    # (None values drop, same as stale diagnostics).
    take_stats = getattr(strategy.al_set, "take_round_stats", None)
    paging = take_stats() if callable(take_stats) else {}
    _emit_round_gauges(telemetry, sink, rd,
                       {k: paging.get(k) for k in PAGING_GAUGES})
    stale_paging = {k: None for k in PAGING_GAUGES
                    if paging.get(k) is None}
    if stale_paging:
        telemetry.set_gauges(**stale_paging)
    # Feed-boundedness gauges from the round's fit (trainer.last_feed):
    # a host-bound warm round reads off the Prometheus scrape / `status`
    # without a profiler.  feed_source is non-numeric, so it rides the
    # heartbeat detail instead (the trainer ticks `feed=` every epoch;
    # `status` renders it).  The span-buffer drop counter rides here
    # too: a capped trace silently truncates evidence, and the only
    # place that shows is the tracer's own counter — nonzero
    # al_run_span_events_dropped on a scrape means trace.json is no
    # longer the whole story.
    feed = strategy.trainer.last_feed
    telemetry.set_gauges(
        round=rd, cumulative_budget=strategy.pool.cumulative_cost,
        labeled=strategy.pool.num_labeled,
        jit_cache_total=telemetry.jit_cache_total(),
        degrade_active=(len(ladder.active) if ladder is not None else 0),
        feed_stall_frac=feed.get("feed_stall_frac"),
        host_wait_ms_p50=feed.get("host_wait_ms_p50"),
        span_events_dropped=tele_spans.get_tracer().dropped)
    telemetry.write_prometheus()
    telemetry.export_trace()
    telemetry.tick(force=True, phase="round_end", round=rd)


def _runtime_record(strategy, xla_cache_dir: Optional[str]) -> dict:
    """The resolved side of every device-dependent ``auto``: the mesh's
    devices as JAX reports them, the model's compute dtype, the pool
    layout, where the resident budget comes from (``memory_stats`` on a
    device that keeps them, the static default otherwise, ``explicit``
    under --resident_scoring_bytes), the ImageFolder decode path (None
    for in-memory datasets), the global evaluation and scoring batches
    (raised on accelerators) and the persistent compile cache directory
    (None = off)."""
    from ..parallel import resident as resident_lib
    dev = strategy.mesh.devices.flat[0]
    dtype = getattr(strategy.model, "dtype", None)  # test models: none
    if strategy.train_cfg.resident_scoring_bytes is not None:
        budget_source = "explicit"
    else:
        budget_source = ("memory_stats"
                         if resident_lib.local_headroom_stats()
                         else "static_default")
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": int(strategy.mesh.devices.size),
        "compute_dtype": None if dtype is None else np.dtype(dtype).name,
        "pool_sharding": strategy.trainer.pool_sharding,
        "resident_budget_source": budget_source,
        "decoder": getattr(strategy.al_set, "decoder", None),
        "eval_batch": strategy.trainer.eval_batch_size(strategy.al_set),
        "score_batch": strategy._score_batch_size(),
        "compilation_cache_dir": xla_cache_dir,
    }


def _placement_record(strategy) -> dict:
    """WHERE the run's state sits, asked of EVERY local device — a pool
    or a parameter tree that landed whole on the first chip of a mesh is
    invisible from device 0's statistics alone.  ``pool_rows``: rows of
    each pinned pool array held per device id (a quarter each under the
    row layout on four chips, all of them under replicated);
    ``param_devices``: how many devices hold the parameters; ``hbm``:
    each device's ``memory_stats`` in-use and peak bytes (None where the
    backend keeps none)."""
    from ..parallel import resident as resident_lib
    pool_rows = resident_lib.rows_per_device(strategy.trainer.resident_pool)
    leaves = jax.tree.leaves(strategy.state.params)
    hbm = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        hbm.append({"id": dev.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return {
        "pool_rows": pool_rows,
        "param_devices": (len(leaves[0].sharding.device_set)
                          if leaves else 0),
        "hbm": hbm,
    }


def _labeled_crc(pool: PoolState) -> int:
    """CRC of the labeled mask — the round journal's cheap labeled-set
    digest (a resume/retry that diverged would show a different CRC at
    the same round, without dumping 1.2M indices into a JSON file)."""
    return int(zlib.crc32(np.ascontiguousarray(pool.labeled).tobytes()))


def _round_snapshot(strategy) -> dict:
    """Everything a ROUND mutates, captured at its start so a failed
    attempt can be rolled back and retried bit-identically (the
    degradation ladder, DESIGN.md §10): pool state, the host rng chain,
    the per-experiment init key, and a host copy of the model variables
    (round r's query scores with round r-1's best weights — re-running
    the query without restoring them would score with the failed
    attempt's re-initialized model)."""
    variables, fetched = None, 0
    if strategy.state is not None:
        # The trainable leaves: a frozen one cannot have moved.  The
        # host copy the fit fetched for best_ckpt is shared while the
        # state is still the one it was fetched from; anything that
        # replaced the state since is fetched here.
        variables = strategy.host_variables()
        if variables is None:
            variables = jax.device_get(strategy.state.trainable_variables)
            fetched = ckpt_lib.tree_bytes(variables)
    return {
        "pool": strategy.pool.to_arrays(),
        "rng_state": copy.deepcopy(strategy.rng.bit_generator.state),
        "init_key": np.asarray(strategy._init_key).copy(),
        "best_epoch": int(strategy.best_epoch),
        "resume_next_fit": bool(strategy.resume_next_fit),
        "variables": variables,
        # Bytes this snapshot moved device -> host (0: the copy is shared).
        "fetched": fetched,
    }


def _restore_round_snapshot(strategy, snap: dict,
                            round_idx: Optional[int] = None) -> None:
    """Roll the strategy back to the round-start snapshot.  The
    ATTEMPTED round's stale mid-fit state is deleted too: it was written
    under an rng chain this restore just rewound, and resuming from it
    would splice two divergent attempts together.  (``round_idx`` names
    that round explicitly — the pool restore rewinds ``strategy.round``
    to the previous round's value, so weight_paths() alone would point
    at the wrong fit state.)"""
    if round_idx is not None:
        fit_state = ckpt_lib.weight_paths(
            strategy.cfg.ckpt_path, strategy.cfg.exp_name,
            strategy.exp_hash, round_idx)["fit_state"]
        ckpt_lib.delete_fit_state(fit_state)
    strategy.pool = PoolState.from_arrays(snap["pool"])
    strategy.rng.bit_generator.state = copy.deepcopy(snap["rng_state"])
    strategy._init_key = jax.numpy.asarray(snap["init_key"])
    strategy.best_epoch = snap["best_epoch"]
    strategy.resume_next_fit = snap["resume_next_fit"]
    if snap["variables"] is None:
        strategy.state = None
    elif strategy.state is not None:
        # Re-replicates from the host copies — fresh device buffers, so
        # arrays the failed attempt donated are never read again.
        strategy.state = strategy.trainer.replace_variables(
            strategy.state, snap["variables"])
    # The failed attempt's partial diagnostics must not double-count
    # into the retried round (the previous round's drift reference
    # survives — reset_round clears the CURRENT accumulators only).
    if strategy.diagnostics is not None:
        strategy.diagnostics.reset_round()


def run_experiment(cfg: ExperimentConfig, sink: Optional[MetricsSink] = None,
                   data=None, mesh=None,
                   train_cfg: Optional[TrainConfig] = None, model=None):
    """Run the full experiment; returns the finished Strategy.

    Mirrors main_al.py:124-184: fresh or resumed setup, then the round loop
    with per-phase wall-clock timers (the reference prints them,
    main_al.py:160-178; here they also land in the metrics sink).
    """
    # Device-truth profiling (telemetry/profiler.py, DESIGN.md §11):
    # when capture windows are armed, the HLO byte-table dump must be
    # pointed at its sidecar dir BEFORE the first backend touch — XLA
    # latches XLA_FLAGS at backend init, and the rendezvous below is
    # that first touch.  Env-only here (no logger yet); the
    # RoundProfiler itself is built after logging setup.
    profiling_armed = bool(cfg.profile_rounds or cfg.profile_dir)
    profile_dir = hlo_dump_dir = None
    # XLA_FLAGS is restored at run exit: XLA latched it at backend init,
    # so the env var is dead weight for THIS process afterwards — but a
    # leaked --xla_dump_to would arm dumping in every later subprocess
    # (fleet children, status probes) against a dir this run owns.
    prev_xla_flags = os.environ.get("XLA_FLAGS")
    if profiling_armed:
        profile_dir = cfg.profile_dir or os.path.join(cfg.log_dir,
                                                      "profile")
        hlo_dump_dir = tele_profiler.arm_hlo_dump(
            os.path.join(profile_dir, "hlo"))
    # Multi-host rendezvous first — nothing above this may touch a JAX
    # backend.  A no-op unless the config carries the multi-host fields.
    mesh_lib.initialize_distributed(cfg.coordinator_address,
                                    cfg.num_processes, cfg.process_id)
    # Persistent executable reuse across rounds AND runs (config update
    # only — safe before or after backend init).
    xla_cache_dir = enable_compilation_cache(cfg.compilation_cache_dir)
    # Arm the fault-injection registry (DESIGN.md §10) ONLY when a spec
    # is explicitly given — a run with neither --fault_spec nor
    # $AL_FAULT_SPEC must not clobber an arming a test installed
    # programmatically before calling run_experiment.  What this run
    # arms, its finally disarms: the registry is process-global, and a
    # spec leaking into the NEXT in-process run (pytest, a benchmark's
    # warm-up) would corrupt a clean run with no indication why.
    fault_spec = cfg.fault_spec or os.environ.get("AL_FAULT_SPEC")
    if fault_spec:
        faults.configure(fault_spec, seed=cfg.run_seed)

    if cfg.exp_hash is None:
        cfg.exp_hash = uuid.uuid4().hex[:9]
        if jax.process_count() > 1:
            # Every process must agree on the hash — it names the shared
            # checkpoint/resume directories that non-coordinators read.
            from jax.experimental import multihost_utils
            agreed = multihost_utils.broadcast_one_to_all(
                np.uint64(int(cfg.exp_hash, 16)))
            cfg.exp_hash = f"{int(agreed):09x}"

    today = date.today()
    log_filename = (f"{cfg.exp_hash}_{today.month:02d}{today.day:02d}.log")
    if jax.process_count() > 1:
        # Per-process log files, like the reference's per-rank logging.
        log_filename = log_filename.replace(
            ".log", f"_p{jax.process_index()}.log")
    logger = setup_logging(cfg.log_dir, log_filename)
    if fault_spec:
        logger.warning(f"fault injection ARMED: {fault_spec} "
                       f"(seed {cfg.run_seed}); disarmed at run exit")

    # The per-round capture windows (coordinator only: one process's
    # profiler session; pod-wide capture is a ROADMAP pod-tier item).
    # Unarmed, round_profiler stays None and the loop's hook is a null
    # context — zero per-round work (tests/test_profiler.py bounds it).
    round_profiler = None
    if profiling_armed and mesh_lib.is_coordinator():
        rounds, rejected = tele_profiler.parse_profile_rounds(
            cfg.profile_rounds)
        if rejected:
            logger.warning(
                f"profiler: --profile_rounds entries {rejected} ignored "
                "(round 0 pays the cold compile tax and never captures; "
                "rounds are positive integers)")
        reachable = [r for r in rounds if r < cfg.rounds]
        if not reachable:
            # e.g. --profile_dir on a rounds=1 run: the default warm
            # window (round 1) does not exist.  Say so and arm NOTHING
            # — a "capture armed" log followed by an empty profile_dir
            # would read as a profiler bug, not a config gap.
            logger.warning(
                f"profiler: no selected round {list(rounds)} exists in "
                f"a {cfg.rounds}-round run — nothing will be captured "
                "(round 0 never captures; run >= 2 rounds or pass "
                "--profile_rounds inside the run)")
        else:
            if len(reachable) < len(rounds):
                logger.warning(
                    "profiler: rounds "
                    f"{[r for r in rounds if r >= cfg.rounds]} exceed "
                    f"the {cfg.rounds}-round run and will not capture")
            round_profiler = tele_profiler.RoundProfiler(
                profile_dir, rounds=reachable, hlo_dump_dir=hlo_dump_dir,
                logger=logger)
            logger.info(
                f"profiler: device-truth capture armed for rounds "
                f"{reachable} -> {profile_dir} "
                f"(HLO byte table: {hlo_dump_dir or 'unavailable'})")

    resuming = cfg.resume_training and resume_lib.has_saved_experiment(cfg)
    preempted_round0 = False
    if cfg.resume_training and not resuming:
        # No completed round on disk.  One legitimate way to get here:
        # preempted (SIGTERM/SIGINT) DURING round 0, before the first
        # save_experiment — the journal records it, and the mid-fit
        # state (epoch-granular, saved by the trainer's preemption
        # boundary) is the only durable progress.  Restart round 0 and
        # let its first fit consume that state; everything before the
        # fit (init pool, eval split, init weights) is a deterministic
        # replay of the same seeds, so the resumed run still reproduces
        # the uninterrupted one bit-identically (tests/test_faults.py).
        prior = faults.read_journal(
            os.path.join(cfg.log_dir, faults.JOURNAL_FILE))
        if (prior is not None and prior.get("status") == "preempted"
                and prior.get("exp_hash") == cfg.exp_hash
                and prior.get("exp_name") == cfg.exp_name
                and int(prior.get("round", -1)) == 0):
            # The identity check matters: the journal is keyed by
            # log_dir, not by experiment — a forgotten --exp_hash (a
            # fresh uuid was just minted above) or a preemption at
            # round N re-run against the wrong --ckpt_path must still
            # hit the explicit error below, not silently restart.
            preempted_round0 = True
        else:
            # Never silently restart a run the user asked to resume (the
            # reference would die unpickling a missing file,
            # resume_training.py:13).
            raise FileNotFoundError(
                f"--resume_training: no saved experiment state for "
                f"exp_name={cfg.exp_name!r} exp_hash={cfg.exp_hash!r} under "
                f"{cfg.ckpt_path!r}; pass the original --exp_hash/--ckpt_path")
    if sink is None:
        key = (resume_lib.saved_experiment_key(cfg) if resuming
               else cfg.exp_hash)
        # Metrics/assets are run-level side effects: process 0 only.
        sink = make_sink(cfg.enable_metrics and mesh_lib.is_coordinator(),
                         cfg.log_dir, experiment_key=key,
                         backend=cfg.metrics_backend,
                         rotate_bytes=cfg.metrics_rotate_bytes)
    # The round journal (faults/journal.py): WHERE the run is — round/
    # phase/attempt, labeled-set digest, active degradation rungs,
    # terminal status — atomically rewritten next to the heartbeat so
    # `status --strict` and post-mortems read it with no jax import.
    journal = faults.RoundJournal(
        os.path.join(cfg.log_dir, faults.JOURNAL_FILE),
        enabled=mesh_lib.is_coordinator())
    # A resumed run must not silently FLIP the gradient-sync precision
    # mid-experiment: if the original launch's int8 probe failed (the
    # journal records grad_allreduce=f32_degraded), every later segment
    # of the same run stays on f32 — re-running the probe on resume and
    # having it pass would splice bounded-delta int8 rounds onto
    # bit-exact f32 ones under a journal that still says degraded.
    # (The other direction — int8 run resumed, probe now fails — keeps
    # the normal probe path: degrading TOWARD the bit-exact sync is
    # always safe, and gets journaled again.)
    prior_journal = faults.read_journal(
        os.path.join(cfg.log_dir, faults.JOURNAL_FILE))
    sticky_degrade = bool(
        (resuming or preempted_round0) and prior_journal
        and prior_journal.get("grad_allreduce") == "f32_degraded")
    if sticky_degrade:
        logger.info(
            "resume: the original run degraded grad_allreduce to f32 "
            "(journaled); keeping f32 for the resumed segment instead "
            "of re-probing")
        cfg.grad_allreduce = "f32"
    # Identity first: a preemption at ANY later point leaves a journal
    # the round-0 resume path above can verify belongs to THIS
    # experiment (the journal is keyed by log_dir, not exp_hash).
    journal.write(exp_name=cfg.exp_name, exp_hash=cfg.exp_hash)
    if sticky_degrade:
        # Re-assert the provenance the identity write just preserved
        # alongside (merge-write keeps other fields; this keeps the
        # degrade record explicit for `status --strict`/post-mortems).
        journal.write(grad_allreduce="f32_degraded")
    # The ladder is built after the strategy exists; the watchdog's
    # callback closes over this box so a stall can reach it.
    ladder_box: dict = {}

    # Run-wide telemetry (DESIGN.md §7): heartbeat + spans + per-step
    # metrics + optional watchdog/trace/scrape file, installed BEFORE the
    # stack is built so the trainer/strategies register their jitted
    # steps with the compile counter.  The watchdog's stall event rides
    # the metrics sink (thread-safe by JsonlSink's lock); with
    # --watchdog_action snapshot/degrade it also journals the stall, and
    # degrade additionally asks the ladder for escalation at the next
    # safe point (the watchdog thread itself never mutates run state).
    def _on_stall(stalled_s: float) -> None:
        logger.warning(
            f"watchdog: no progress for {stalled_s:.0f}s (deadline "
            f"{cfg.telemetry.stall_deadline_s:.0f}s) — stall suspected")
        sink.log_metric("stall_suspected", round(stalled_s, 1))
        tele_spans.get_tracer().instant(
            "stall_suspected", args={"stalled_s": round(stalled_s, 1)})
        action = getattr(cfg.telemetry, "watchdog_action", "log")
        if action in ("snapshot", "degrade"):
            journal.write(status="stalled", stalled_s=round(stalled_s, 1))
        if action == "degrade" and ladder_box.get("ladder") is not None:
            ladder_box["ladder"].request_stall()

    telemetry = tele_runtime.start_run(
        cfg.telemetry, log_dir=cfg.log_dir,
        process_index=jax.process_index(),
        process_count=jax.process_count(), logger=logger,
        on_stall=_on_stall)

    # Everything from here runs under the run's telemetry; the finally
    # below both finishes it (final heartbeat status + trace export) and
    # UNINSTALLS it — an exception anywhere, including setup, must not
    # leak an installed runtime into the next in-process run.  Preemption
    # handlers install for the same span: SIGTERM/SIGINT record a
    # request that the trainer's epoch boundaries and the driver's phase
    # boundaries turn into checkpoint-and-exit (faults/preempt.py).
    status = "crashed"
    pipeline = None
    # Per-run retry baseline: the process counter never resets (other
    # runs/phases in this interpreter own their own slices of it).
    run_retries0 = faults.retry_counters()["total"]
    preempt_lib.reset()
    prev_handlers = preempt_lib.install(logger)
    try:
        strategy = build_experiment(cfg, sink=sink, data=data, mesh=mesh,
                                    train_cfg=train_cfg, model=model,
                                    skip_init_pool=resuming)
        # What the run actually got, as opposed to what the config asked
        # for — every "auto" resolves against the live device, and the
        # side it took is otherwise only visible in behavior: journaled
        # once (and logged) so `status`, post-mortems and chip_smoke.py
        # read it with no jax import.
        runtime = _runtime_record(strategy, xla_cache_dir)
        journal.write(runtime=runtime)
        logger.info(f"Runtime: {runtime}")
        if getattr(strategy.trainer, "grad_allreduce_degraded", False):
            # The int8 learning probe failed (build_experiment already
            # fell back to f32 and logged): surface it LOUDLY through
            # the same channels a ladder escalation uses — the journal
            # (status --strict renders degrade lists) and the
            # degrade_events metric — so a run that silently trains
            # bit-exact when int8 was asked for is impossible to miss.
            journal.write(grad_allreduce="f32_degraded")
            sink.log_metric("degrade_events", 1, step=-1)
            sink.log_metric("grad_allreduce_degraded", 1, step=-1)
        if resuming:
            start_round = resume_lib.load_experiment(strategy, cfg)
            # The first fit of a resumed run may consume a mid-round fit
            # state (epoch-level recovery); non-resumed runs discard
            # stale ones.
            strategy.resume_next_fit = True
        else:
            start_round = 0
            sink.log_parameters(config_to_dict(cfg))
            if preempted_round0:
                # Preempted mid-round-0: replay the round from its seeds
                # but let the first fit consume the mid-fit state the
                # preemption boundary saved.
                logger.info(
                    "resume: journal records a round-0 preemption; "
                    "replaying round 0 and consuming its mid-fit state")
                strategy.resume_next_fit = True

        init_pool_size = cfg.resolved_init_pool_size()
        logger.info(f"Experiment Name: {cfg.exp_name}")
        logger.info(f"Dataset: {cfg.dataset}")
        logger.info(f"Strategy: {cfg.strategy}")
        logger.info(
            f"Budget used before starting: {strategy.pool.num_labeled}")
        logger.info(f"Log file name: {log_filename}")
        logger.info(f"Mesh: {strategy.mesh.devices.size} devices")

        # The per-run report artifact (telemetry/diagnostics.py,
        # DESIGN.md §13): the label-efficiency curve — accuracy vs
        # labeled count vs wall-clock per round, plus the round's
        # drift/composition/calibration diagnostics — atomically
        # rewritten as run_report.json after every round, so a crashed
        # or preempted run still leaves a renderable artifact
        # (`python -m active_learning_tpu report <log_dir>` /
        # scripts/run_report.py).  On resume, completed rounds' rows
        # are merged back from the prior file.
        run_report_path = os.path.join(cfg.log_dir,
                                       diag_lib.RUN_REPORT_FILE)
        write_report = mesh_lib.is_coordinator() and cfg.enable_metrics
        report_rows: list = []
        # Resumed segments continue the CUMULATIVE wall clock from the
        # last merged row (accuracy-vs-time must stay monotone across a
        # preemption; a fresh-zero clock would make round N+1 look
        # cheaper than round N).  Preemption downtime is not counted —
        # the curve measures compute time spent, not queue luck.  ONE
        # resume-merge rule, shared with the stream service
        # (diag_lib.resume_report_rows).
        report_wall_base = 0.0
        if write_report and start_round > 0:
            report_rows, report_wall_base = diag_lib.resume_report_rows(
                run_report_path, cfg.exp_hash, start_round)
        report_header = {
            "exp_name": cfg.exp_name, "exp_hash": cfg.exp_hash,
            "strategy": cfg.strategy, "dataset": cfg.dataset,
            "model": cfg.model, "run_seed": cfg.run_seed,
            "rounds_planned": cfg.rounds,
            "round_budget": cfg.round_budget,
            "init_pool_size": cfg.resolved_init_pool_size(),
        }
        run_t0 = time.monotonic()

        # The pipelined round coordinator (experiment/pipeline.py,
        # DESIGN.md §8): armed before each fit so the next query's pool
        # scoring overlaps the fit's patience tail, consumed by
        # Strategy.collect_scores at the next query.  Installed on the
        # strategy (train() wires the best-ckpt publish into fit);
        # bit-identical to the sequential loop by contract.  The
        # degradation ladder may detach it for a degraded round
        # (strategy.pipeline is the live switch; this local keeps the
        # shutdown handle either way).
        pipeline_mode = pipeline_lib.resolve_round_pipeline(
            cfg.round_pipeline, strategy.mesh)
        if pipeline_mode == "speculative":
            pipeline = pipeline_lib.RoundPipeline(strategy)
            strategy.pipeline = pipeline
        logger.info(f"Round pipeline: {pipeline_mode}")

        # The degradation ladder (faults/ladder.py, DESIGN.md §10): a
        # failure that survives the site-level retries costs a ROUND
        # ATTEMPT, not the run — the round rolls back to its snapshot
        # and re-runs one rung down.  The save below rides the unified
        # retry policy too (transient IO never loses a completed round).
        ladder = ladder_lib.DegradationLadder(strategy, logger=logger,
                                              sink=sink, journal=journal)
        ladder_box["ladder"] = ladder
        save_retry = faults.RetryPolicy(site="experiment_save",
                                        classify=faults.classify_exception)

        def _boundary(rd: int, phase: str) -> None:
            """A driver safe point: journal where we are, then honor a
            recorded preemption or a watchdog degrade request.  The
            durable state is consistent at every boundary by
            construction (atomic saves, monotonic tags)."""
            journal.write(round=rd, phase=phase)
            preempt_lib.check()
            ladder.check_stall()

        def _run_round(rd: int, attempt: int):
            """One round attempt — the reference loop body, verb for
            verb.  Returns (phase walls, round span) for the overlap
            accounting; raises to the attempt loop on failure."""
            phase_s = {}
            with tele_spans.get_tracer().span(
                    "round", args={"round": rd, "attempt": attempt},
                    counters=True) as round_sp:
                strategy.round = rd
                telemetry.tick(force=True, round=rd,
                               phase="round_start", epoch=0, step=0)
                journal.write(status="running", round=rd,
                              phase="round_start", attempt=attempt,
                              labeled=strategy.pool.num_labeled,
                              labeled_crc=_labeled_crc(strategy.pool),
                              degrade=list(ladder.active),
                              pipeline_armed=bool(strategy.pipeline))
                logger.info(f"Active Learning Round {rd} start.")
                # Pool residency is default behavior: re-size the auto
                # budget from live HBM headroom at every round start (a
                # no-op for explicit integer budgets; already-uploaded
                # pools stay resident regardless —
                # parallel/resident.cached).
                with tele_spans.get_tracer().span("round/resident_budget"):
                    budget = strategy.trainer.refresh_resident_budget()
                logger.info(
                    f"Resident pool budget for round {rd}: "
                    f"{budget / 1e9:.2f} GB "
                    f"({'auto' if strategy.train_cfg.resident_scoring_bytes is None else 'explicit'}, "
                    f"per chip, {strategy.trainer.pool_sharding} layout)")

                # Round 0 only queries when there is no initial pool —
                # with an SSL or transfer-learned init the model can
                # score the pool before any labels exist
                # (main_al.py:149-157).
                al_round_0 = rd == 0 and init_pool_size == 0
                if rd > 0 or al_round_0:
                    if al_round_0:
                        strategy.init_network_weights()
                    with phase_timer("query_time", rd, sink,
                                     logger) as sp:
                        labeled_idxs, cur_cost = strategy.query(
                            cfg.round_budget)
                    phase_s["query"] = sp.duration_s
                    with tele_spans.get_tracer().span("query/update"):
                        strategy.update(labeled_idxs, cur_cost)
                    _boundary(rd, "query")

                with phase_timer("init_network_weights_time", rd, sink,
                                 logger) as sp:
                    strategy.init_network_weights()
                phase_s["init"] = sp.duration_s
                _boundary(rd, "init")
                # Arm the speculative plan for the NEXT round's query
                # before the fit starts publishing best checkpoints —
                # the scorer overlaps the fit's patience tail.  The
                # last round has no next query: nothing to speculate.
                if strategy.pipeline is not None and rd + 1 < cfg.rounds:
                    strategy.pipeline.arm(rd)
                with phase_timer("train_time", rd, sink, logger) as sp:
                    strategy.train()
                phase_s["train"] = sp.duration_s
                _boundary(rd, "train")
                with phase_timer("load_best_ckpt_time", rd, sink,
                                 logger) as sp:
                    strategy.load_best_ckpt()
                phase_s["load_best"] = sp.duration_s
                with phase_timer("test_time", rd, sink, logger) as sp:
                    strategy.test()
                phase_s["test"] = sp.duration_s

                # No preemption check between test and save: the round's
                # work is done, so the completed round is persisted
                # FIRST and the signal honored at the next boundary.
                if mesh_lib.is_coordinator():
                    with tele_spans.get_tracer().span(
                            "ckpt/save_experiment"):
                        save_retry.call(resume_lib.save_experiment,
                                        strategy, cfg)
                cfg.resume_training = True  # crash after this resumes (main_al.py:181)
                journal.write(round=rd, phase="round_end",
                              labeled=strategy.pool.num_labeled,
                              labeled_crc=_labeled_crc(strategy.pool))
            return phase_s, round_sp

        with tele_spans.get_tracer().span(
                "experiment", args={"exp_name": cfg.exp_name,
                                    "exp_hash": cfg.exp_hash}):
            for rd in range(start_round, cfg.rounds):
                preempt_lib.check()
                # Degradation is per-round: every round starts at full
                # capability; a systematic fault re-engages the ladder,
                # a transient one stays recovered.
                ladder.relax(rd)
                # A host copy of the model every round (the ladder's
                # rollback point): a device->host fetch outside the
                # round span, so it is one of the ckpt/* spans.
                with tele_spans.get_tracer().span(
                        "ckpt/round_snapshot", args={"round": rd},
                        counters=True) as sp:
                    snapshot = _round_snapshot(strategy)
                    sp.args["bytes"] = ckpt_lib.tree_bytes(
                        snapshot["variables"])
                    sp.args["fetched"] = snapshot["fetched"]
                for attempt in range(ladder.max_attempts()):
                    try:
                        # The device-truth capture window (DESIGN.md
                        # §11): a selected WARM round runs inside one
                        # jax.profiler window; on exit the device ops
                        # splice into the span trace and the
                        # device_busy_frac / collective_bytes metrics
                        # emit.  Inside the try: a failed attempt stops
                        # the trace on its way to the ladder.
                        with tele_profiler.round_scope(
                                round_profiler, rd,
                                tracer=tele_spans.get_tracer(),
                                sink=sink, telemetry=telemetry):
                            phase_s, round_sp = _run_round(rd, attempt)
                        break
                    except preempt_lib.PreemptionRequested:
                        raise
                    except ladder_lib.DegradeRequested as exc:
                        if ladder.escalate(exc, rd) is None:
                            raise
                        _restore_round_snapshot(strategy, snapshot, rd)
                    except (Exception, faults.ThreadDeath) as exc:
                        # Quiesce a possibly mid-chunk scorer before
                        # rolling back (escalate's pipeline_off rung
                        # also disarms; this covers the other rungs).
                        if strategy.pipeline is not None:
                            strategy.pipeline.disarm()
                        if ladder.escalate(exc, rd) is None:
                            raise
                        _restore_round_snapshot(strategy, snapshot, rd)
                # Everything between the round span and the next round:
                # the per-round emitters, the journal, the run report and
                # the incremental trace export — host work with the
                # device idle, so it gets a span of its own.
                with tele_spans.get_tracer().span(
                        "round_epilogue", args={"round": rd},
                        counters=True) as epilogue:
                    pipe = strategy.pipeline
                    if pipe is not None:
                        # Scorer busy minus the round's gate contention on
                        # BOTH sides: chunk busy already excludes the
                        # scorer's own gate waits (pipeline._score_chunk),
                        # and the main thread's waits on scorer holds are
                        # inside the phase walls — leaving them in spec_s
                        # would double-count serialized time as overlap
                        # (most visible in drain-mode CPU rounds, where a
                        # chunk's whole execution can stall the fit).
                        spec_s = max(
                            0.0, pipe.take_busy_s()
                            - strategy.trainer.dispatch_lock.take_wait_s())
                    else:
                        spec_s = 0.0
                    # The round's metrics, gauges and scrape file; the
                    # trace's rewrite and the heartbeat inside it have
                    # spans of their own.
                    with tele_spans.get_tracer().span("round/emit"):
                        _emit_overlap_telemetry(
                            telemetry, sink, rd, round_sp.duration_s,
                            phase_s, spec_s,
                            pipeline_mode if pipe is not None else "off")
                        _emit_round_telemetry(telemetry, sink, rd, strategy,
                                              ladder,
                                              retries_baseline=run_retries0)
                    if telemetry.jit_grown:
                        # A recompile says which step it was: the
                        # registered programs whose cache grew since the
                        # last round's jit_cache_miss_delta.
                        epilogue.args["recompiled"] = list(
                            telemetry.jit_grown)
                    with tele_spans.get_tracer().span("round/placement"):
                        placement = _placement_record(strategy)
                    journal.write(compile_cache=compilation_cache_counts(),
                                  placement=placement)
                    if write_report:
                        row = {
                            "round": rd,
                            "labeled": int(strategy.pool.num_labeled),
                            "cumulative_budget":
                                float(strategy.pool.cumulative_cost),
                            "test_accuracy": strategy.last_test_acc,
                            "round_time_s": round(round_sp.duration_s, 3),
                            "wall_clock_s": round(
                                report_wall_base
                                + (time.monotonic() - run_t0), 3),
                            "phases_s": {k: round(v, 3)
                                         for k, v in phase_s.items()},
                            # The feed this round's fit resolved and the
                            # execution form it ran in (trainer.last_feed).
                            "feed": strategy.trainer.last_feed.get("source"),
                            "feed_form":
                                strategy.trainer.last_feed.get("form"),
                        }
                        diag = getattr(strategy, "diagnostics", None)
                        if diag is not None:
                            row.update(diag.last_row)
                        report_rows.append(row)
                        with tele_spans.get_tracer().span("report/write"):
                            diag_lib.write_run_report(
                                run_report_path, report_header, report_rows)
                if len(strategy.available_query_idxs(shuffle=False)) == 0:
                    logger.info("Finished querying all Images!")
                    break
        status = "finished"
        journal.write(status="finished")
    except preempt_lib.PreemptionRequested as exc:
        # Checkpoint-and-exit: every durable artifact (experiment state,
        # mid-round fit state, best checkpoints, this journal) is
        # already consistent — the resumed run reproduces the
        # uninterrupted one bit-identically (tests/test_faults.py).
        status = "preempted"
        journal.write(status="preempted", signal=int(exc.signum))
        logger.info(
            "preemption: durable state checkpointed; re-run with "
            "--resume_training to continue bit-identically")
        raise
    finally:
        if profiling_armed:
            # Un-leak the HLO dump arming (see prev_xla_flags above).
            if prev_xla_flags is None:
                os.environ.pop("XLA_FLAGS", None)
            else:
                os.environ["XLA_FLAGS"] = prev_xla_flags
        if fault_spec:
            # Disarm only what THIS run armed (cleanup runs fault-free;
            # a programmatic arming by the caller is left alone).
            faults.configure(None)
        preempt_lib.uninstall(prev_handlers)
        # Stop the speculative scorer BEFORE telemetry teardown: its
        # thread ticks the heartbeat and records spans, both of which
        # must not outlive the run they belong to.
        if pipeline is not None:
            pipeline.shutdown()
        telemetry.finish(status)
        tele_runtime.uninstall(telemetry)
    return strategy
