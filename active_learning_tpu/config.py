"""Typed configuration for the TPU-native active-learning framework.

Replaces the reference's argparse + ``arg_pools`` dict + ``eval()``-string
system (reference: src/utils/parser.py, src/arg_pools/*.py, and the
``eval(f"optim.{...}")`` calls at src/query_strategies/strategy.py:345-350)
with explicit dataclasses and registries.  No ``eval``/``exec`` anywhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

# Fallback budget for device-resident acquisition-scoring pools when the
# backend keeps no HBM statistics to auto-size from (CPU) — see
# TrainConfig.resident_scoring_bytes and
# parallel/resident.resolve_budget.
RESIDENT_SCORING_BYTES_DEFAULT = 2 ** 31


@dataclasses.dataclass(frozen=True)
class LoaderConfig:
    """Host->device input-pipeline parameters.

    Mirrors the reference's DataLoader kwargs (``loader_tr_args`` /
    ``loader_te_args``, e.g. src/arg_pools/default.py:7-8).  ``num_workers``
    maps to prefetch threads in our pipeline; on TPU the heavy lifting
    (normalize/augment) runs on-device inside the jitted step, so the host
    only gathers uint8 rows.
    """

    batch_size: int = 128
    num_workers: int = 0
    prefetch: int = 2


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Optimizer selection.  Reference: ``optimizer``/``optimizer_args`` in
    arg pools (src/arg_pools/default.py:9-10), instantiated by name via
    ``eval`` at src/query_strategies/strategy.py:345.  Here: a plain name
    resolved through an explicit factory in train/optim.py.
    """

    name: str = "sgd"
    lr: float = 0.1
    weight_decay: float = 5e-4
    momentum: float = 0.9


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """LR schedule stepped once per *epoch*, matching torch's
    StepLR/CosineAnnealingLR semantics (``scheduler.step()`` per epoch at
    src/query_strategies/strategy.py:369).

    name: "step" (step_size/gamma) or "cosine" (t_max, warmup_epochs).

    warmup_epochs: cosine only — linear ramp from base_lr/warmup to
    base_lr over the first ``warmup_epochs`` epochs, cosine over the
    remainder.  0 (default) reproduces torch CosineAnnealingLR exactly.
    Measured need: from-scratch ResNet training re-initialized every AL
    round is bistable at small label counts without it (runs sit at
    chance while an identical config escapes to 78%+ — BN statistics and
    momentum at full lr on the first few hundred steps).
    """

    name: str = "cosine"
    step_size: int = 60
    gamma: float = 0.1
    t_max: int = 200
    warmup_epochs: int = 0


@dataclasses.dataclass(frozen=True)
class PretrainedConfig:
    """SSL / transfer-learning checkpoint ingestion.

    Mirrors ``init_pretrained_ckpt_path`` + ``required_key``/``skip_key``/
    ``replace_key`` state-dict surgery configured per arg pool
    (src/arg_pools/ssp_finetuning.py:13-16,34-37) and applied in
    src/utils/load_pretrained_weights.py.
    """

    path: Optional[str] = None
    required_key: Optional[Tuple[str, ...]] = None
    skip_key: Optional[Tuple[str, ...]] = None
    replace_key: Optional[Tuple[Tuple[str, str], ...]] = None

    @property
    def replace_map(self) -> Dict[str, str]:
        return dict(self.replace_key or ())


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Per-dataset training hyperparameters: one entry of an "arg pool"
    (reference: the per-dataset dicts in src/arg_pools/*.py).
    """

    eval_split: float = 0.01
    # Compute precision for the model's conv/matmul path (parameters,
    # batch-norm statistics, the classifier head, and all acquisition math
    # stay float32 — see models/resnet.py).  "auto" = bfloat16 on TPU,
    # float32 elsewhere; the reference trains float32 everywhere
    # (src/utils/get_networks.py:28-29 builds torch fp32 modules), but on
    # TPU the MXU's native precision is bf16 and fp32 would halve
    # throughput for no accuracy win at these model scales.
    dtype: str = "auto"
    # BatchNorm batch-statistics read precision.  "auto" follows the
    # compute dtype: bf16 models compute batch mean/var by reducing the
    # bf16 activations directly with float32 ACCUMULATION
    # (models/resnet.FusedBatchNorm) instead of flax's
    # materialize-as-float32-then-reduce, which doubles the bytes the
    # stats pass reads and breaks its fusion with the producer.
    # "float32" forces the flax path; running statistics are float32
    # either way.
    bn_stats_dtype: str = "auto"
    # ResNet stem layout: "default" keeps the reference 7x7/s2 conv;
    # "s2d" folds it into an exact 4x4/s1 conv over space-to-depth
    # (112x112x12) input on the 224px path — same arithmetic, 4x the
    # contraction channels for the MXU (models/resnet.py; CIFAR-stem
    # models ignore this).
    stem: str = "default"
    loader_tr: LoaderConfig = dataclasses.field(default_factory=LoaderConfig)
    loader_te: LoaderConfig = dataclasses.field(
        default_factory=lambda: LoaderConfig(batch_size=100))
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    pretrained: PretrainedConfig = dataclasses.field(default_factory=PretrainedConfig)
    imbalanced_training: bool = False
    # Fused optimizer update (train/optim.FusedSGD, DESIGN.md §4): the
    # SGD+momentum+weight-decay update as ONE tree-fused expression
    # inside the donated train step instead of the optax chain's four
    # tree traversals.  "auto" (default) = fused whenever the optimizer
    # is SGD-family; "on" forces it (fails fast on non-SGD); "off"
    # keeps the optax chain.  At f32 optimizer state the fused path is
    # BIT-identical to optax (pinned in tests/test_backward.py) — this
    # knob is throughput-only there.
    fused_optimizer: str = "auto"
    # Momentum-buffer storage dtype for the fused path: "f32" (default,
    # bit-parity with optax) or "bf16" (HALF the optimizer HBM; buffers
    # read bf16, accumulate f32, round once on store — bounded-delta,
    # learn-tested).  Ignored on the optax path.
    optim_state_dtype: str = "f32"
    # Gradient all-reduce precision across the mesh (parallel/mesh.py,
    # DESIGN.md §4): "f32" (default) is the partitioner's bit-exact
    # psum; "int8" is the EQuARX-style block-scaled quantized sync —
    # ~4x fewer wire bytes per gradient — with global-batch BN kept via
    # explicit pmean'd statistics.  int8 is bounded-delta (never
    # bit-exact), OFF on single-device meshes, and gated on the
    # multichip learning probe at driver startup: a probe failure
    # degrades the run to f32 loudly (journaled).
    grad_allreduce: str = "f32"
    # Device-resident epochs for in-memory datasets (one jitted scan per
    # epoch instead of per-batch dispatch).  None = auto (on when the
    # images fit in HBM and the labeled set is large enough to amortize
    # the extra compile), True = force on, False = host-batched path.
    device_resident: Optional[bool] = None
    # Train-feed selection (the feed hierarchy, DESIGN.md §2a:
    # resident-gather > prefetched-host > serial-host).
    #   "auto"     — when the pool is pinned in HBM (or fits the resident
    #                budget) and the device-resident scan is worthwhile
    #                (see device_resident), train batches are ON-DEVICE
    #                gathers of labeled indices from that SAME pinned
    #                array — zero host image copies; otherwise the legacy
    #                labeled-subset upload, then the host feed.
    #   "resident" — force the resident-gather feed (falls back down the
    #                hierarchy with a logged warning when impossible:
    #                disk-backed pool, VAAL batch_hook, budget 0).
    #   "host"     — force the host feed (multi-worker + device-prefetch
    #                when feed_workers/prefetch allow, else serial).
    # Every feed produces a bit-identical batch stream at the same seeds
    # (tests/test_trainer_parallel.py) — this knob is throughput-only.
    train_feed: str = "auto"
    # Gather/decode worker threads for the host train feed; None defers
    # to loader_tr.num_workers (the reference's DataLoader num_workers).
    # The double-buffered device prefetch depth rides loader_tr.prefetch.
    feed_workers: Optional[int] = None
    # Epoch cadence for the current-weights checkpoint AND the mid-round
    # fit-state save (the reference writes rd_{n}.pth every epoch,
    # strategy.py:440; a full-variable host transfer per epoch would
    # dominate small-model epochs on TPU, so both are periodic here).
    current_ckpt_every: int = 25
    # Cache decoded eval rows across validation epochs for disk-backed
    # datasets (the val view is deterministic, so decoding each eval row
    # once per ROUND instead of once per EPOCH is exact); bounded by
    # cache_eval_bytes, falling back to per-epoch decode past the budget.
    cache_eval: bool = True
    cache_eval_bytes: int = 4 << 30
    # Disk-memmap decode-once cache for the WHOLE deterministic pool view
    # (al scoring + test set, data/cache.DecodedPoolCache): each row is
    # JPEG-decoded exactly once per experiment lifetime instead of once
    # per round/epoch, so steady-state ImageNet scoring is bounded by
    # host->device bandwidth, not decode (a capture from before the
    # ledger: 1,048 img/s/core decode vs 3,133 img/s h2d vs 9,742 img/s
    # device).  Applied only
    # when the FULL pool fits the byte budget (sparse file; a partial
    # cache would still thrash).  dir=None -> <tempdir>/al_tpu_decoded.
    cache_decoded_bytes: int = 32 << 30
    decoded_cache_dir: Optional[str] = None
    # Global batch for acquisition-scoring passes.  None = auto: the
    # reference scores with its test-loader batch (100, e.g.
    # src/arg_pools/default.py loader_te_args), which on an 8-chip mesh is
    # ~12 rows per chip — far below MXU-efficient occupancy.  Auto keeps
    # the reference batch on CPU (tests, parity) and raises it to a
    # row-size-scaled floor PER CHIP on accelerators (512 for <=64px
    # rows, 256 above, 128 when the row shape is unknown — v5e-measured,
    # Trainer.eval_batch_size).  Scores are per-example
    # statistics under eval-mode BN, so the batch size changes throughput
    # only, never a score.
    score_batch_size: Optional[int] = None
    # Resident-pool LAYOUT over the mesh (DESIGN.md §2b):
    #   "auto"       — row-sharded whenever the single-process mesh has
    #                  more than one device (each chip pins rows/ndev of
    #                  the pool and of every factor matrix, so residency
    #                  scales with chip count), replicated otherwise
    #                  (single device, multi-process pods).
    #   "row"        — force row sharding (downgraded with the same
    #                  gates as auto where impossible).
    #   "replicated" — one full copy per chip, the pre-sharding layout.
    # Scores, train batches, and k-center picks are bit-identical across
    # layouts (tests/test_pool_sharding.py) — throughput/HBM only.
    pool_sharding: str = "auto"
    # Pool storage backend (the disk tier, DESIGN.md §16):
    #   "auto"   — the in-memory pool unless it would cross the
    #              host-RAM watermark (pool_disk_watermark_frac of
    #              physical RAM), where the run takes the disk tier;
    #   "memory" — the classic whole-pool host array;
    #   "disk"   — demand-paged disk extents (data/diskpool.DiskPool):
    #              rows live in one sparse extent file per host, gathers
    #              page bucket-aligned blocks through a byte-bounded
    #              host cache (pool_host_cache_bytes), and the labeled
    #              hot set pins in HBM via the resident machinery.
    # Picks, scores, and experiment_state are bit-identical across
    # backends at the same seeds (tests/test_disk_pool.py) — this knob
    # trades host RAM for paged-read bandwidth only.
    pool_backend: str = "auto"
    # Rows per paged block (snapped onto the pool.bucket_size ladder).
    pool_page_rows: int = 2048
    # Host block-cache budget for the warm tier, in bytes.
    pool_host_cache_bytes: int = 1 << 30
    # "auto" backend watermark: take the disk tier when the pool exceeds
    # this fraction of physical host RAM.
    pool_disk_watermark_frac: float = 0.5
    # Keep in-memory datasets resident on device (replicated) for the
    # whole experiment — ONE shared upload serves every round's
    # acquisition scoring AND the per-epoch validation/test evaluation
    # (parallel/resident.py).  None = AUTO (the default): the budget is
    # sized from live HBM headroom at round start (bytes_limit −
    # bytes_in_use − a training-activation reserve), so any pool that
    # fits the chip pins by default; backends without memory statistics
    # fall back to a conservative 2 GB.  An explicit integer pins the
    # budget (0 disables both resident paths).  The budget is accounted
    # across the WHOLE resident cache (parallel/resident.pinned_bytes):
    # the AL pool, the test set, and the train feed share one pot, and
    # the al/train views' shared storage counts ONCE — one pinned pool
    # serves scoring, evaluation, AND training for one array's worth of
    # HBM.  Shrinking an explicit budget mid-run demotes pinned pools
    # LRU-first (parallel/resident.enforce_budget).
    resident_scoring_bytes: Optional[int] = None

    @property
    def has_pretrained(self) -> bool:
        return self.pretrained.path is not None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The online scoring service (active_learning_tpu/serve/): the
    ``serve`` CLI verb's knobs.  Unlike every other config here this has
    no reference counterpart — the reference has no serving path at all
    (PARITY.md); request latency, not round wall-clock, is its metric.
    """

    host: str = "127.0.0.1"
    # 0 = ephemeral (the bound port is logged and exposed on the server
    # object) — tests run over loopback this way.
    port: int = 8000
    # Rows per dispatched device batch, upper bound.  Served shapes are
    # the geometric bucket ladder serve_buckets(max_batch, bucket_floor)
    # — every one pre-compiled at startup.
    max_batch: int = 64
    # Microbatch deadline: a batch closes at max_batch rows or this many
    # ms after its first row, whichever comes first.
    max_latency_ms: float = 5.0
    # Admission bound in ROWS (queued + in flight); beyond it requests
    # get 429 + Retry-After.  Explicit backpressure, never unbounded
    # queueing.
    queue_depth: int = 512
    # Floor of the bucket ladder (pool.bucket_size floor): the smallest
    # padded batch a lone request is served at.
    bucket_floor: int = 8
    # Hot-reload poll cadence for a newer best_rd_{n} checkpoint; 0
    # checks before every batch.
    reload_every_s: float = 5.0
    # Bound on the SIGTERM graceful drain (in-flight completion).
    drain_timeout_s: float = 30.0


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """The streaming active-learning service (active_learning_tpu/stream/,
    DESIGN.md §14): the ``stream`` CLI verb's knobs.  Like ServeConfig
    this has no reference counterpart — the reference's AL loop is an
    offline batch job over a frozen disk pool (PARITY.md row 58)."""

    host: str = "127.0.0.1"
    # 0 = ephemeral (the bound port is logged and exposed on the service
    # object) — tests run over loopback this way.
    port: int = 8008
    # Rows one POST /v1/pool may carry; beyond it the request is a
    # non-retryable 413 (it could never be admitted — split it).
    max_request_rows: int = 512
    # Accepted-but-undrained rows the service will hold; beyond it
    # ingest gets 429 + Retry-After until a round drains the backlog.
    # Explicit backpressure, never unbounded queueing (the serve
    # admission contract, applied to durability instead of batching).
    max_backlog_rows: int = 65536
    # Ingest-WAL segment rotation bound (stream/wal.py): the active
    # wal.jsonl seals (atomic rename) past this many bytes.
    wal_rotate_bytes: int = 64 << 20
    # Trigger policy (stream/scheduler.TriggerPolicy): a round fires on
    # the new-row watermark, on ServeScoreDrift PSI, or on the max wall
    # interval — whichever first.  0 disables a condition.
    watermark_rows: int = 1024
    drift_psi: float = 0.25
    max_interval_s: float = 3600.0
    # Scheduler poll cadence between rounds.
    poll_s: float = 0.5
    # Stop after this many total rounds (the driver's ``rounds``
    # semantics — a resumed run continues the same count); 0 = run
    # indefinitely (the production mode; SIGTERM checkpoint-and-exits).
    max_rounds: int = 0
    # Extent floor for pool growth (pool.bucket_size's floor): appended
    # capacity lands on this shape ladder so the resident upload and
    # its gather runners recompile at most once per bucket boundary.
    extent_floor: int = 256
    # How many whole batches one incremental drift-scoring chunk covers
    # (scoring.chunk_row_slices — the PR 7 chunk plan, reused over
    # appended row ranges).
    chunk_batches: int = 8


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Run-wide telemetry (active_learning_tpu/telemetry/, DESIGN.md §7):
    per-step/per-epoch train + scoring metrics through the MetricsSink,
    heartbeat liveness, host-span traces, and Prometheus exposition.

    ``enabled`` is the master switch and is ON by default — the
    default-on pieces (step-time/imgs-per-sec/grad-norm collection, the
    heartbeat file, the jit-compile counter) cost two perf_counter
    calls and a rate-limited dict merge per step.  Trace export and the
    stall watchdog are opt-in on top.
    """

    enabled: bool = True
    # Heartbeat rewrite cadence floor (phase transitions force a write
    # regardless); heartbeat.json lands in --log_dir, per-process on
    # pods (heartbeat_p{i}.json).
    heartbeat_every_s: float = 5.0
    # Chrome trace-event export: log_dir/trace.json, loadable in
    # Perfetto / chrome://tracing.  Off by default (the event buffer is
    # bounded either way).
    export_trace: bool = False
    # In-process stall watchdog: logs + emits a ``stall_suspected``
    # metric when the progress counter freezes past the deadline.  The
    # same deadline is embedded in heartbeat.json for EXTERNAL probes
    # (the ``status`` verb flags staleness off the file's mtime).
    watchdog: bool = False
    stall_deadline_s: float = 600.0
    # Prometheus textfile-collector scrape file (atomic rewrite); None
    # disables.  The serve path exposes the same exposition format live
    # at /metrics?format=prometheus.
    prometheus_file: Optional[str] = None
    # The experiment-truth diagnostics layer (telemetry/diagnostics.py,
    # DESIGN.md §13): per-round acquisition-score histograms + PSI/JS
    # drift, selection composition (class balance / novelty / k-center
    # pick distances), and eval-piggybacked calibration, emitted through
    # the sink + al_run_* gauges and persisted into run_report.json.
    # Default ON (it rides numbers that already exist on host — zero
    # extra pool passes, zero device syncs, picks bit-identical on/off);
    # requires ``enabled``.  Off = one None check per hook site.
    diagnostics: bool = True
    # What a CONFIRMED stall does beyond logging (DESIGN.md §10):
    #   "log"       log + stall_suspected metric (the pre-fault-model
    #               behavior);
    #   "snapshot"  also journal the stall into round_journal.json
    #               (status="stalled", stalled_s) for post-mortems and
    #               `status --strict`;
    #   "degrade"   snapshot + ask the degradation ladder to escalate at
    #               the driver's next safe point (the watchdog thread
    #               itself never mutates run state).
    watchdog_action: str = "log"


@dataclasses.dataclass(frozen=True)
class ImbalanceConfig:
    """Synthetic class-imbalance parameters.

    Reference: --imbalance_type/--imbalance_factor/--imbalance_seed
    (src/utils/parser.py:30-39) consumed by
    src/data_utils/custom_imbalanced_cifar10.py:16-27.
    """

    imbalance_type: Optional[str] = None  # "exp" | "step" | None
    imbalance_factor: float = 0.1
    imbalance_seed: int = 0


@dataclasses.dataclass(frozen=True)
class VAALConfig:
    """VAAL hyperparameters (reference: src/utils/parser.py:81-92)."""

    vae_latent_dim: int = 64
    adversary_param: float = 10.0
    lr_vae: float = 5e-5
    lr_discriminator: float = 1e-3


@dataclasses.dataclass
class ExperimentConfig:
    """Top-level experiment configuration: the 30 CLI flags of
    src/utils/parser.py as one typed object.
    """

    # Experiment identity / logging
    project_name: str = "active-learning"
    exp_name: str = "active_learning"
    exp_hash: Optional[str] = None
    log_dir: str = "./logs"
    ckpt_path: str = "./checkpoint"
    enable_metrics: bool = True
    # Comma-separated sink backends (utils/metrics.SINK_BACKENDS):
    # "jsonl", "csv", "tensorboard", or combinations ("jsonl,tensorboard").
    metrics_backend: str = "jsonl"
    # JsonlSink size-based rotation: when metrics.jsonl would exceed
    # this many bytes it rotates to metrics.jsonl.1 (atomic, lock-held,
    # no line ever split across the boundary — utils/metrics.JsonlSink).
    # 0 (default) = unbounded, the historical behavior; a
    # run-indefinitely service (ROADMAP item 3) sets a cap.
    metrics_rotate_bytes: int = 0

    # Dataset
    dataset: str = "cifar10"
    dataset_dir: Optional[str] = None
    arg_pool: str = "default"
    # Root onto which an arg pool's relative pretrained-ckpt path is rebased
    # (the reference hardcodes a ../pretrained_ckpt layout,
    # ssp_finetuning.py:13).
    pretrained_root: Optional[str] = None
    imbalance: ImbalanceConfig = dataclasses.field(default_factory=ImbalanceConfig)

    # Active-learning globals
    strategy: str = "RandomSampler"
    rounds: int = 5
    round_budget: int = 5000
    freeze_feature: bool = False
    init_pool_size: int = -1  # -1 => round_budget (main_al.py:74-76)
    init_pool_type: str = "random"  # "random" | "random_balance"

    # Training
    model: str = "SSLResNet18"
    resume_training: bool = False
    n_epoch: int = 60
    early_stop_patience: int = 30

    # Self-provision disk datasets when absent (the reference's
    # torchvision download=True, custom_cifar10.py:30-33).
    download_data: bool = False

    # Debug
    debug_mode: bool = False
    # The device-truth layer (telemetry/profiler.py, DESIGN.md §11):
    # bounded XLA profiler capture windows around chosen AL rounds.
    # profile_dir names where the trace artifacts + per-round
    # device_profile_rd{n}.json summaries land (set alone it captures
    # the default window); profile_rounds picks WHICH rounds capture —
    # a comma-separated list or "warm" (default: round 1, the first
    # warm round).  Round 0 NEVER captures: it pays the cold compile
    # tax and its trace would answer "how slow is compilation", not
    # "where does the steady-state round go".  Setting profile_rounds
    # without profile_dir lands artifacts under <log_dir>/profile.
    # Unset, the capture hooks are inert (no per-step or per-round
    # work — pinned in tests/test_profiler.py).
    profile_dir: Optional[str] = None
    profile_rounds: Optional[str] = None

    # Compute-precision override: None defers to the arg pool's
    # TrainConfig.dtype ("auto" = bf16 on TPU / f32 elsewhere).
    dtype: Optional[str] = None

    # BN batch-statistics precision override: None defers to the arg
    # pool's TrainConfig.bn_stats_dtype ("auto" = fused bf16 stats on
    # bf16 models).
    bn_stats_dtype: Optional[str] = None

    # ResNet stem override ("default"/"s2d"): None defers to the arg
    # pool's TrainConfig.stem.  See TrainConfig.stem.
    stem: Optional[str] = None

    # Device-resident pool budget override (bytes): None defers to the
    # arg pool's TrainConfig.resident_scoring_bytes, whose default is
    # AUTO — sized from live HBM headroom at round start, so pools that
    # fit the chip pin in HBM by default and every later query/eval pass
    # is on-device gathers (no per-batch host->device image traffic).
    # Pass an explicit integer to pin the budget, 0 to disable residency.
    resident_scoring_bytes: Optional[int] = None

    # Train-feed override ("auto"/"resident"/"host"): None defers to the
    # arg pool's TrainConfig.train_feed.  See TrainConfig.train_feed for
    # the feed hierarchy (resident-gather > prefetched-host >
    # serial-host); every feed is bit-identical at the same seeds.
    train_feed: Optional[str] = None

    # Fused optimizer-update override ("auto"/"on"/"off"): None defers
    # to the arg pool's TrainConfig.fused_optimizer.  Bit-identical to
    # the optax chain at f32 optimizer state.
    fused_optimizer: Optional[str] = None

    # Momentum-buffer dtype override ("f32"/"bf16") for the fused
    # optimizer path: None defers to the arg pool.  bf16 halves
    # optimizer HBM (bounded-delta; f32 is bit-parity with optax).
    optim_state_dtype: Optional[str] = None

    # Gradient all-reduce precision override
    # ("f32"/"int8"/"int8_rs"/"auto"): None defers to the arg pool
    # (default f32 = the bit-exact psum).  The quantized modes
    # (EQuARX-style block-scaled sync) are bounded-delta, default-off,
    # OFF on single-device meshes, and gated on the multichip learning
    # probe at run start (a failed probe degrades to f32 loudly —
    # journaled, sticky across resume).  The WIRE form is resolved per
    # mesh (parallel/mesh.resolve_int8_wire): the all-gather form on
    # 2-8 device meshes, the pod-tier reduce-scatter form
    # (int8_reduce_scatter, ~2n bytes regardless of device count) above
    # the crossover; "int8_rs" forces reduce-scatter, "auto" =
    # quantized wherever a multi-device mesh makes it worth probing.
    grad_allreduce: Optional[str] = None

    # Large-batch scaling ("auto"/"off"/None=off, DESIGN.md §15): auto
    # applies the large-batch ConvNet scaling rules as the mesh grows —
    # train batch x ndev (the arg pool's batch becomes PER-CHIP),
    # linear lr x ndev, and a >=5-epoch gradual cosine warmup — so the
    # pod-scale global batch doesn't silently cost accuracy.  Off keeps
    # the arg pool's batch as the reference's global batch.
    scale_batch: Optional[str] = None

    # Resident-pool layout override ("auto"/"replicated"/"row"): None
    # defers to the arg pool's TrainConfig.pool_sharding, whose default
    # auto row-shards pool rows over any single-process multi-device
    # mesh (per-chip residency = rows/ndev).  Scores, batches, and
    # k-center picks are bit-identical across layouts.
    pool_sharding: Optional[str] = None

    # Host train-feed gather/decode worker threads: None defers to the
    # arg pool (TrainConfig.feed_workers -> loader_tr.num_workers, the
    # reference's DataLoader num_workers row).
    feed_workers: Optional[int] = None

    # Pool storage backend override ("auto"/"memory"/"disk"): None
    # defers to the arg pool's TrainConfig.pool_backend, whose default
    # auto keeps the in-memory pool until it would cross the host-RAM
    # watermark, then takes the demand-paged disk tier (DESIGN.md §16).
    # Bit-identical picks/scores/experiment_state across backends.
    pool_backend: Optional[str] = None

    # Pipelined AL round (experiment/pipeline.py, DESIGN.md §8):
    # "speculative" overlaps the next query's pool-scoring pass with the
    # current fit's early-stop patience tail (chunks scored from each
    # published best checkpoint, invalidated when a later epoch improves
    # best) and prefetches the coming fit's train feed while selection
    # runs — round wall moves from sum(train, score, select) toward
    # max(train, score).  "off" is the reference's strictly sequential
    # loop.  "auto" (the default) picks speculative on any
    # single-process multi-device mesh.  Picks, scores, and
    # experiment_state are bit-identical across modes at the same seeds
    # (tests/test_pipeline.py) — this is a wall-clock choice only.
    round_pipeline: str = "auto"

    # Coreset / BADGE partitioning (parser.py:74-79)
    subset_labeled: Optional[int] = None
    subset_unlabeled: Optional[int] = None
    partitions: int = 1
    # Batched greedy k-center: provisionally-farthest picks folded into
    # the min-distance vector per pool pass, with an exact in-batch
    # re-check so the selection is pick-for-pick identical to q=1
    # (strategies/kcenter.py).  8 = the f32 sublane tile; 1 restores
    # the sequential scan.  Randomized (BADGE D^2) selection always
    # draws one pick at a time regardless.
    kcenter_batch: int = 8

    # Persistent XLA compilation-cache directory: round N+1 and run M+1
    # reuse round N's compiled executables from disk instead of paying
    # the cold-compile tax again (experiment/driver.py applies it
    # process-wide at run start).  None = ~/.cache/al_tpu_xla_cache
    # (or $JAX_COMPILATION_CACHE_DIR); "" disables.
    compilation_cache_dir: Optional[str] = None

    # Deterministic fault injection (active_learning_tpu/faults/,
    # DESIGN.md §10): a comma-separated arming spec like
    # "h2d_upload:raise@3,ckpt_write:torn@1,spec_scorer:die@0.5" —
    # site:action[@arg] with int args = Nth-hit triggers (fire once),
    # float args = seeded per-hit probabilities, "delay" args = seconds.
    # None defers to $AL_FAULT_SPEC; unset leaves every site a
    # zero-cost no-op.  Chaos tests arm this to make every recovery
    # claim replayable (tests/test_faults.py).
    fault_spec: Optional[str] = None

    # VAAL
    vaal: VAALConfig = dataclasses.field(default_factory=VAALConfig)

    # Run-wide telemetry (heartbeat/spans/per-step metrics/Prometheus).
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig)

    # Seeds (reference hard-codes eval split seed 99 and init pool seed 98,
    # main_al.py:71,83; the rest of the run uses the global np.random state —
    # here everything is explicit).
    eval_split_seed: int = 99
    init_pool_seed: int = 98
    run_seed: int = 0

    # Mesh / parallelism (replaces world_size = torch.cuda.device_count(),
    # main_al.py:96; -1 = all local devices)
    num_devices: int = -1

    # Multi-host (DCN): jax.distributed rendezvous, the run-once equivalent
    # of the reference's per-round NCCL process group (strategy.py:288-315).
    # All None = single process, or TPU-pod auto-discovery when only
    # num_processes is given.  ckpt_path must be a shared filesystem on
    # multi-host runs (only process 0 writes; every process reads).
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    def resolved_init_pool_size(self) -> int:
        if self.init_pool_size == -1:
            return int(self.round_budget)
        return int(self.init_pool_size)


def config_to_dict(cfg: Any) -> Dict[str, Any]:
    """Flatten a (possibly nested) dataclass config into a plain dict for
    metric-parameter logging (reference logs vars(args) at main_al.py:114)."""
    out: Dict[str, Any] = {}

    def _walk(prefix: str, obj: Any) -> None:
        if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
            for f in dataclasses.fields(obj):
                _walk(f"{prefix}{f.name}.", getattr(obj, f.name))
        else:
            out[prefix[:-1]] = obj

    _walk("", cfg)
    return out
