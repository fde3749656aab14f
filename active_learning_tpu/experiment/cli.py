"""Command-line entry point.

The reference's 30 argparse flags (src/utils/parser.py:7-92) mapped onto
``ExperimentConfig``.  Run as:

    python -m active_learning_tpu --dataset cifar10 --strategy MarginSampler \
        --rounds 30 --round_budget 1000 --n_epoch 200 --early_stop_patience 50

Flag names match the reference so published commands (README.md:53,
src/gen_jobs.py) translate directly; comet-specific flags are replaced by
the JSONL metrics sink (--disable_metrics).
"""

from __future__ import annotations

import argparse
from typing import List, Optional

from ..config import (ExperimentConfig, ImbalanceConfig, TelemetryConfig,
                      VAALConfig)


def _model_names() -> List[str]:
    """What ``--model`` takes: the backbones the registry holds."""
    from ..models import factory  # noqa: F401  (registers the models)
    from ..registry import MODELS
    return MODELS.names()


def get_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU-native active learning (parity with "
                    "zeyademam/active_learning)")
    # Experiment identity / logging (parser.py:9-25)
    p.add_argument("--project_name", type=str, default="active-learning")
    p.add_argument("--exp_name", type=str, default="active_learning")
    p.add_argument("--exp_hash", type=str, default=None)
    p.add_argument("--log_dir", type=str, default="./logs")
    p.add_argument("--ckpt_path", type=str, default="./checkpoint")
    p.add_argument("--disable_metrics", action="store_true",
                   help="replaces --enable_comet (metrics on by default)")
    p.add_argument("--metrics_backend", type=str, default="jsonl",
                   help="comma-separated sinks: jsonl, csv, tensorboard")
    p.add_argument("--metrics_rotate_bytes", type=int, default=0,
                   help="rotate metrics.jsonl to metrics.jsonl.1 past "
                        "this many bytes (atomic, no lost lines); 0 = "
                        "unbounded (default)")
    # Dataset (parser.py:27-39)
    p.add_argument("--dataset", type=str, default="cifar10",
                   choices=["cifar10", "imbalanced_cifar10", "imagenet",
                            "imbalanced_imagenet", "synthetic",
                            "synthetic_tokens"])
    p.add_argument("--dataset_dir", type=str, default=None)
    p.add_argument("--arg_pool", type=str, default="default")
    p.add_argument("--pretrained_root", type=str, default=None,
                   help="rebase an arg pool's relative pretrained-ckpt path")
    p.add_argument("--imbalance_type", type=str, default=None,
                   choices=[None, "exp", "step"])
    p.add_argument("--imbalance_factor", type=float, default=0.1)
    p.add_argument("--imbalance_seed", type=int, default=0)
    # AL globals (parser.py:41-58)
    p.add_argument("--strategy", type=str, default="RandomSampler")
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--round_budget", type=int, default=5000)
    p.add_argument("--freeze_feature", action="store_true")
    p.add_argument("--init_pool_size", type=int, default=-1,
                   help="-1 => round_budget; 0 => query at round 0")
    p.add_argument("--init_pool_type", type=str, default="random",
                   choices=["random", "random_balance"])
    # Training (parser.py:60-69)
    p.add_argument("--model", type=str, default="SSLResNet18",
                   choices=_model_names(),
                   help="a backbone the registry holds (models/factory.py)")
    p.add_argument("--resume_training", action="store_true")
    p.add_argument("--n_epoch", type=int, default=60)
    p.add_argument("--early_stop_patience", type=int, default=30,
                   help="0 disables early stopping")
    p.add_argument("--download_data", action="store_true",
                   help="fetch CIFAR-10 (md5-verified) when absent — the "
                        "reference's torchvision download=True")
    # Debug (parser.py:70-71)
    p.add_argument("--debug_mode", action="store_true")
    p.add_argument("--profile_dir", type=str, default=None,
                   help="device-truth profiling (DESIGN.md §11): bounded "
                        "XLA profiler capture windows land their trace "
                        "artifacts + device_profile_rd{n}.json summaries "
                        "here (set alone: the default warm-round window)")
    p.add_argument("--profile_rounds", type=str, default=None,
                   help="which AL rounds get a capture window: a comma-"
                        "separated list or 'warm' (default: round 1, the "
                        "first warm round).  Round 0 never captures — it "
                        "pays the cold compile tax.  Device ops splice "
                        "into the --export_trace timeline and the "
                        "device_busy_frac / collective_bytes_total "
                        "metrics ride the sink + Prometheus")
    # Run-wide telemetry (active_learning_tpu/telemetry/, DESIGN.md §7).
    # Default ON: per-step/per-epoch metrics through the sink + the
    # heartbeat file; trace export and the watchdog are opt-in.
    p.add_argument("--disable_telemetry", action="store_true",
                   help="turn off per-step metrics, heartbeat, and the "
                        "compile counter (trace/watchdog imply nothing "
                        "when this is set)")
    p.add_argument("--heartbeat_every_s", type=float, default=5.0,
                   help="heartbeat.json rewrite cadence floor (phase "
                        "transitions always force a write)")
    p.add_argument("--export_trace", action="store_true",
                   help="export nested host spans as Chrome trace-event "
                        "JSON to <log_dir>/trace.json (Perfetto / "
                        "chrome://tracing)")
    p.add_argument("--watchdog", action="store_true",
                   help="in-process stall watchdog: log + emit a "
                        "stall_suspected metric when progress halts past "
                        "--stall_deadline_s")
    p.add_argument("--stall_deadline_s", type=float, default=600.0,
                   help="stall deadline for the watchdog AND the "
                        "staleness threshold embedded in heartbeat.json "
                        "(the `status` verb reads it)")
    p.add_argument("--prometheus_file", type=str, default=None,
                   help="atomically rewrite this Prometheus textfile-"
                        "collector scrape file with run gauges")
    p.add_argument("--disable_diagnostics", action="store_true",
                   help="turn off the experiment-truth diagnostics "
                        "layer (score histograms + rd_score_drift_*, "
                        "selection composition, calibration — "
                        "DESIGN.md §13).  On by default; picks and "
                        "experiment state are bit-identical either way")
    p.add_argument("--watchdog_action", type=str, default="log",
                   choices=["log", "snapshot", "degrade"],
                   help="what a confirmed stall does beyond logging: "
                        "snapshot journals it into round_journal.json; "
                        "degrade also triggers the degradation ladder at "
                        "the next safe point (DESIGN.md §10)")
    p.add_argument("--fault_spec", type=str, default=None,
                   help="deterministic fault injection, e.g. "
                        "'h2d_upload:raise@3,ckpt_write:torn@1' — "
                        "site:action[@arg]; defaults to $AL_FAULT_SPEC; "
                        "unset = every site is a zero-cost no-op "
                        "(DESIGN.md §10)")
    # Compute precision (TPU-specific; the reference is fp32-only,
    # get_networks.py:28-29).  Default defers to the arg pool's
    # TrainConfig.dtype, whose "auto" means bf16 on TPU / f32 elsewhere.
    p.add_argument("--dtype", type=str, default=None,
                   choices=["auto", "bfloat16", "float32"],
                   help="model compute precision (params/BN stay float32)")
    p.add_argument("--bn_stats_dtype", type=str, default=None,
                   choices=["auto", "bfloat16", "float32"],
                   help="BN batch-statistics read precision: auto = fused "
                        "bf16-read/f32-accumulate stats on bf16 models "
                        "(the flax f32 promotion costs ~23%% of ResNet-50 "
                        "forward); float32 forces the flax path")
    p.add_argument("--stem", type=str, default=None,
                   choices=["default", "s2d"],
                   help="ResNet stem layout: s2d folds the 224px 7x7/s2 "
                        "stem conv into an exact 4x4/s1 conv over "
                        "space-to-depth (112x112x12) input — same math, "
                        "MXU-shaped (ignored by CIFAR-stem models)")
    p.add_argument("--resident_scoring_bytes", type=int, default=None,
                   help="device-resident pool budget in bytes.  Default "
                        "(unset) AUTO-sizes from live HBM headroom at "
                        "each round start, so pools that fit the chip pin "
                        "in HBM and later query/eval passes are on-device "
                        "gathers.  Pass an integer to pin the budget, 0 "
                        "to disable residency.")
    p.add_argument("--pool_sharding", type=str, default=None,
                   choices=["auto", "replicated", "row"],
                   help="resident-pool layout over the mesh: row shards "
                        "pool rows (and k-center factor matrices) over "
                        "the data axis so per-chip residency scales "
                        "1/ndev with chip count; replicated pins one "
                        "full copy per chip.  auto (the default) picks "
                        "row on any single-process multi-device mesh.  "
                        "Scores, batches, and k-center picks are "
                        "bit-identical across layouts")
    p.add_argument("--pool_backend", type=str, default=None,
                   choices=["auto", "memory", "disk"],
                   help="pool storage backend (DESIGN.md §16): memory "
                        "holds the whole pool in host RAM; disk pages "
                        "bucket-aligned row blocks from a per-host "
                        "extent file through a bounded host cache, so "
                        "pools bigger than any host's RAM run on the "
                        "same hardware.  auto (the default) takes the "
                        "disk tier only past a host-RAM watermark.  "
                        "Picks and experiment state are bit-identical "
                        "across backends")
    p.add_argument("--train_feed", type=str, default=None,
                   choices=["auto", "resident", "host"],
                   help="train-batch feed: auto picks the top of the "
                        "hierarchy (resident-gather from the pinned pool "
                        "> prefetched-host > serial-host); resident/host "
                        "force a leg.  All feeds are bit-identical at "
                        "the same seeds — throughput only")
    p.add_argument("--feed_workers", type=int, default=None,
                   help="gather/decode worker threads for the host train "
                        "feed (the reference DataLoader's num_workers); "
                        "default defers to the arg pool's train loader")
    p.add_argument("--fused_optimizer", type=str, default=None,
                   choices=["auto", "on", "off"],
                   help="fused SGD+momentum+weight-decay update inside "
                        "the donated train step (one tree pass instead "
                        "of the optax chain's four; bit-identical to "
                        "optax at f32 state).  auto = on for SGD-family "
                        "optimizers")
    p.add_argument("--optim_state_dtype", type=str, default=None,
                   choices=["f32", "bf16"],
                   help="momentum-buffer dtype on the fused optimizer "
                        "path: f32 (default, bit-parity with optax) or "
                        "bf16 (half the optimizer HBM; read bf16, "
                        "accumulate f32, bounded-delta)")
    p.add_argument("--grad_allreduce", type=str, default=None,
                   choices=["f32", "int8", "int8_rs", "auto"],
                   help="gradient sync precision across the mesh: f32 "
                        "(default, bit-exact psum); int8 (EQuARX-style "
                        "block-scaled quantized sync, int8 wire "
                        "payload); int8_rs forces the pod-tier "
                        "reduce-scatter wire form (~2n bytes regardless "
                        "of device count — auto-picked above the "
                        "~8-device crossover anyway); auto = quantized "
                        "on any multi-device mesh.  All quantized modes "
                        "are bounded-delta, off on single-device "
                        "meshes, and gated on the multichip learning "
                        "probe — a failed probe degrades the run to "
                        "f32 loudly")
    p.add_argument("--scale_batch", type=str, default=None,
                   choices=["auto", "off"],
                   help="large-batch scaling rules as the mesh grows "
                        "(DESIGN.md §15): auto multiplies the train "
                        "batch by the device count (the arg pool's "
                        "batch becomes per-chip), scales lr linearly, "
                        "and raises the cosine warmup to a >=5-epoch "
                        "gradual ramp — so a pod-scale global batch "
                        "doesn't silently cost accuracy")
    p.add_argument("--round_pipeline", type=str, default="auto",
                   choices=["auto", "off", "speculative"],
                   help="pipelined AL round: speculative overlaps the "
                        "next query's pool scoring with the fit's "
                        "early-stop patience tail (restarting from any "
                        "later best checkpoint) and prefetches the "
                        "coming fit's feed while selection runs.  auto "
                        "(the default) picks speculative on any "
                        "single-process multi-device mesh.  Picks and "
                        "experiment state are bit-identical to off at "
                        "the same seeds — wall-clock only")
    # Coreset / BADGE scale controls (parser.py:74-79)
    p.add_argument("--subset_labeled", type=int, default=None)
    p.add_argument("--subset_unlabeled", type=int, default=None)
    p.add_argument("--partitions", type=int, default=1)
    p.add_argument("--kcenter_batch", type=int, default=8,
                   help="batched greedy k-center: picks folded per pool "
                        "pass (exact re-check keeps selection identical "
                        "to 1); 1 = sequential scan")
    p.add_argument("--compilation_cache_dir", type=str, default=None,
                   help="persistent XLA compilation cache directory; "
                        "$JAX_COMPILATION_CACHE_DIR wins when set, the "
                        "default is <checkout>/.jax_cache, '' sets none")
    # VAAL (parser.py:81-92)
    p.add_argument("--vae_latent_dim", type=int, default=64)
    # Reference spelling (parser.py:84); --adversary_param kept as an alias
    # for commands written against earlier versions of this CLI.
    p.add_argument("--vaal_adversary_param", "--adversary_param",
                   dest="vaal_adversary_param", type=float, default=10.0)
    p.add_argument("--lr_vae", type=float, default=5e-5)
    p.add_argument("--lr_discriminator", type=float, default=1e-3)
    # Seeds / mesh (TPU-specific)
    p.add_argument("--run_seed", type=int, default=0)
    p.add_argument("--num_devices", type=int, default=-1,
                   help="-1 = all local devices")
    # Multi-host: jax.distributed over DCN (the reference is single-node
    # only, strategy.py:288; these flags are the pod-scale replacement).
    p.add_argument("--coordinator_address", type=str, default=None,
                   help="host:port of process 0 (TPU pods auto-discover)")
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    return p


def args_to_config(args: argparse.Namespace) -> ExperimentConfig:
    return ExperimentConfig(
        project_name=args.project_name,
        exp_name=args.exp_name,
        exp_hash=args.exp_hash,
        log_dir=args.log_dir,
        ckpt_path=args.ckpt_path,
        enable_metrics=not args.disable_metrics,
        metrics_backend=args.metrics_backend,
        metrics_rotate_bytes=args.metrics_rotate_bytes,
        dataset=args.dataset,
        dataset_dir=args.dataset_dir,
        arg_pool=args.arg_pool,
        pretrained_root=args.pretrained_root,
        imbalance=ImbalanceConfig(
            imbalance_type=args.imbalance_type,
            imbalance_factor=args.imbalance_factor,
            imbalance_seed=args.imbalance_seed),
        strategy=args.strategy,
        rounds=args.rounds,
        round_budget=args.round_budget,
        freeze_feature=args.freeze_feature,
        init_pool_size=args.init_pool_size,
        init_pool_type=args.init_pool_type,
        model=args.model,
        resume_training=args.resume_training,
        n_epoch=args.n_epoch,
        early_stop_patience=args.early_stop_patience,
        download_data=args.download_data,
        debug_mode=args.debug_mode,
        profile_dir=args.profile_dir,
        profile_rounds=args.profile_rounds,
        telemetry=TelemetryConfig(
            enabled=not args.disable_telemetry,
            heartbeat_every_s=args.heartbeat_every_s,
            export_trace=args.export_trace,
            watchdog=args.watchdog,
            stall_deadline_s=args.stall_deadline_s,
            prometheus_file=args.prometheus_file,
            diagnostics=not args.disable_diagnostics,
            watchdog_action=args.watchdog_action),
        fault_spec=args.fault_spec,
        dtype=args.dtype,
        bn_stats_dtype=args.bn_stats_dtype,
        stem=args.stem,
        resident_scoring_bytes=args.resident_scoring_bytes,
        train_feed=args.train_feed,
        pool_sharding=args.pool_sharding,
        feed_workers=args.feed_workers,
        pool_backend=args.pool_backend,
        fused_optimizer=args.fused_optimizer,
        optim_state_dtype=args.optim_state_dtype,
        grad_allreduce=args.grad_allreduce,
        scale_batch=args.scale_batch,
        round_pipeline=args.round_pipeline,
        subset_labeled=args.subset_labeled,
        subset_unlabeled=args.subset_unlabeled,
        partitions=args.partitions,
        kcenter_batch=args.kcenter_batch,
        compilation_cache_dir=args.compilation_cache_dir,
        vaal=VAALConfig(
            vae_latent_dim=args.vae_latent_dim,
            adversary_param=args.vaal_adversary_param,
            lr_vae=args.lr_vae,
            lr_discriminator=args.lr_discriminator),
        run_seed=args.run_seed,
        num_devices=args.num_devices,
        coordinator_address=args.coordinator_address,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )


def main(argv: Optional[List[str]] = None):
    import sys

    argv = list(sys.argv[1:]) if argv is None else list(argv)
    # The verbs this CLI carries beyond the reference's flat flag
    # surface: ``serve`` opens the ONLINE path (predictions +
    # acquisition scores over HTTP from an experiment's best
    # checkpoint — active_learning_tpu/serve/) and ``status`` renders a
    # live run summary (telemetry/status.py).  Flat invocations stay
    # byte-compatible with every published reference command.
    if argv and argv[0] == "serve":
        from ..serve.cli import main as serve_main
        return serve_main(argv[1:])
    # ``stream``: the continual ingest -> score -> select service
    # (active_learning_tpu/stream/, DESIGN.md §14) — serving-side ingest
    # and the AL loop as one long-lived process on one persistent mesh.
    if argv and argv[0] == "stream":
        from ..stream.cli import main as stream_main
        return stream_main(argv[1:])
    # ``status``: render a live run summary from heartbeat + metrics —
    # stdlib only, answers in milliseconds with NO jax import (it must
    # work from any shell against a wedged run).
    if argv and argv[0] == "status":
        from ..telemetry.status import main as status_main
        return status_main(argv[1:])
    # ``report``: render a run's label-efficiency curve — or a
    # cross-run strategy comparison at matched label budgets — from
    # run_report.json / metrics.jsonl (telemetry/report.py; stdlib
    # only, no jax import, same contract as ``status``).
    if argv and argv[0] == "report":
        from ..telemetry.report import main as report_main
        return report_main(argv[1:])
    # ``fleet``: many experiments on preemptible capacity — the sweep
    # controller (active_learning_tpu/fleet/, DESIGN.md §17).  Host-pure
    # like ``status``/``report``: the head node never imports jax.
    if argv and argv[0] == "fleet":
        from ..fleet.cli import main as fleet_main
        return fleet_main(argv[1:])
    from ..faults.preempt import PreemptionRequested
    from .driver import run_experiment
    args = get_parser().parse_args(argv)
    # run_experiment performs the jax.distributed rendezvous itself (a
    # no-op without the multi-host config fields), so programmatic callers
    # get the same behavior as the CLI.
    try:
        return run_experiment(args_to_config(args))
    except PreemptionRequested:
        # Graceful preemption (SIGTERM/SIGINT): the durable state is
        # checkpointed and consistent — exit 0 so orchestrators treat
        # the eviction as clean; --resume_training continues the run
        # bit-identically.
        return 0


if __name__ == "__main__":
    main()
