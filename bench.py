"""Headline benchmark: the framework's hot loops on real hardware.

Eight phases, bfloat16 over the full local mesh:

  * resnet50_imagenet train/score — the paper's north-star protocol model
    (SSLResNet50 at 224px, reference src/gen_jobs.py:8-13, README.md:53):
    train-step images/sec/chip with achieved TFLOP/s and MFU, plus
    mesh-parallel pool-scoring throughput.
  * resnet18_cifar train/score — the CIFAR-10 protocol model
    (SSLResNet18, SimCLR CIFAR stem, 32px): same two phases.
  * imagenet_datapath — a 50k synthetic JPEG tree through the native C++
    decoder into the mesh scoring pass (per-core decode rate, h2d
    bandwidth, end-to-end images/sec).
  * kcenter_select — greedy selection at protocol scale (10k picks over a
    [50k, 2048] pool) through the production batched-greedy XLA scan
    (the Pallas kernel was deleted per the r5 verdict — DESIGN.md §5).
  * serve_throughput — the ONLINE path: a loopback scoring service
    (active_learning_tpu/serve/) under the closed+open-loop load
    generator, recording qps, p50/p99 request latency, the
    batch-occupancy histogram, and asserting zero request-path XLA
    compiles after the bucket warmup.
  * al_round_cifar / al_round_imagenet — BASELINE.md metric #1: one REAL
    end-to-end AL round (query -> train -> test) through the production
    driver (experiment/driver.py), with the per-phase wall-clock the
    driver already timers.  Two rounds run so the warm round (all XLA
    compiles cached) is reported separately from the cold one.

Prints exactly ONE COMPACT JSON line (<= MAX_LINE_BYTES, guaranteed) to
stdout and always exits 0.  The headline triple is {"metric", "value",
"unit", "vs_baseline"}; per-phase numbers ride along in "phases" as
{ips, mfu, cached} only.  The FULL evidence (every field every phase
produced, probe record, failure strings) is written to
bench_evidence.json, whose path the line carries under "evidence" — the
harness that consumes this output keeps only a ~2 KB tail of stdout, so
a fat line is truncated past parseability (round 4's parsed=null) while
a file survives at any size.  On a dead or degraded backend the line
still appears with value null and the failure reasons recorded — a
flaky remote runtime must never cost a round its performance evidence.

Robustness (the round-3 driver capture died rc=124 with a full cache on
disk; none of these may regress):
  * A <=90 s health probe (tiny jitted matmul in a subprocess) runs
    BEFORE any long phase attempt; a dead/degraded backend routes
    straight to emitting the cached numbers instead of burning the
    wall-clock budget on doomed 900-second attempts.
  * The would-be-final JSON is rewritten to bench_partial.json after
    every phase, so even a SIGKILL leaves the evidence on disk.
  * SIGTERM/SIGINT print the final JSON line immediately and exit 0 — an
    outer `timeout` on this process yields a parsed result, not rc=124.
  * Every phase runs in its own subprocess with a hard timeout (a hung
    remote dispatch cannot wedge the parent), the retry ladder is capped
    at 2 attempts, iteration counts shrink on retry, and batch sizes
    shrink on OOM.  Total fresh-capture time is bounded by
    AL_BENCH_BUDGET_S (default 1400 s) so the guaranteed line lands well
    inside a 30-minute outer timeout.
  * Timing forces a host fetch of a value data-dependent on every step —
    block_until_ready can return early on remote-execution backends,
    host fetches cannot.

vs_baseline: the reference publishes no throughput numbers (BASELINE.md)
so the comparison points are the documented envelope of its hardware —
the 1x V100-SXM2 node (reference README.md:44-47): ~400 images/sec for
fp32 ResNet-50/ImageNet training and ~1,800 images/sec for fp32
ResNet-18/CIFAR-10 training.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time


def _finite(x) -> bool:
    """True for a real, finite number (bools excluded): the ONE spelling
    of 'usable rate' shared by the headline filter and the sanitizer."""
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))

V100_BASELINE_IPS = {
    "resnet50_imagenet_train": 400.0,
    "resnet18_cifar_train": 1800.0,
}

# Peak bf16 TFLOP/s per chip by device_kind substring, for MFU.
PEAK_TFLOPS_BF16 = [
    ("v5 lite", 197.0), ("v5e", 197.0), ("v5p", 459.0),
    ("v6", 918.0), ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
]

# Where the cache/partial/evidence files live: the repo dir by default;
# AL_BENCH_STATE_DIR redirects all three so tests (and parallel bench
# invocations) can exercise the full emit path without touching the real
# captured evidence (tests/test_bench_json.py pins the degraded-mode
# JSON-line guarantee through this).
_STATE_DIR = (os.environ.get("AL_BENCH_STATE_DIR")
              or os.path.dirname(os.path.abspath(__file__)))

# Successful phase results are persisted here (with a capture timestamp)
# and reused — marked "cached": true — when a later invocation can't
# capture that phase fresh: an accelerator that is unavailable at harness
# time must not erase real numbers captured hours earlier on the same
# hardware.  (This cache-replay contract is replaced by the first
# benchmark PR — ROADMAP S1/D2; chip_smoke.py is the opposite contract.)
CACHE_PATH = os.path.join(_STATE_DIR, "bench_cache.json")

PHASES = [
    # (name, iters, per-chip batch, first-attempt timeout seconds).
    # Iteration counts are sized for timing stability on a HEALTHY backend
    # while still fitting the first attempt when the backend runs several
    # times slower than its best observed window.
    ("resnet50_imagenet_train", 30, 128, 900),
    ("resnet18_cifar_train", 100, 256, 600),
    ("resnet50_imagenet_score", 20, 128, 600),
    # ImageNet-scale data-path rehearsal (SURVEY hard part (e)): a 50k
    # synthetic JPEG tree (1/25 of ImageNet) through ImageFolderDataset +
    # native C++ decode + the mesh-parallel scoring pass.  iters is in
    # THOUSANDS of images so the retry halving shrinks the tree.
    ("imagenet_datapath", 50, 128, 900),
    # The train-feed hierarchy, measured (DESIGN.md §2a): identical fits
    # over an in-memory 224px pool under each leg — resident-gather
    # (on-device gather + augment from the pinned pool) vs
    # prefetched-host (worker threads behind the double-buffered device
    # prefetch) vs serial-host — so the auto feed choice is justified on
    # THIS hardware.  iters is the per-leg epoch count.
    ("imagenet_train_feed", 2, 64, 900),
    # PRIMARY at the 512-rows/chip production floor (trainer.py
    # eval_batch_size: <=64px rows score at 512/chip — +47% measured over
    # 256); the automatic alt probe then covers 1024 as the beyond-floor
    # data point.  Earlier rounds captured 256 primary / 512 alt, so the
    # README's production number came from the alt probe — now it IS the
    # primary capture.
    ("resnet18_cifar_score", 30, 512, 420),
    # The disk tier (DESIGN.md §16): the same 2-round experiment under
    # the memory backend and the demand-paged disk backend with the
    # pool pinned at 4x the residency budgets — asserts bit-identical
    # picks/accuracy and records the paging tax (hit fraction, page-in
    # rate, stall percentiles).  iters is the per-round epoch count;
    # per-chip batch is unused (the production config decides).
    ("disk_pool_feed", 2, 64, 900),
    # The selection hot loop (SURVEY hard part (a)): greedy k-center over
    # a 50k-row, 2048-dim pool — the reference's paper protocol subsets
    # the pool to 50k and picks 10k per round (gen_jobs.py:8-13).  iters
    # is the budget (picks); per-chip batch is unused.  XLA scan only
    # since the r5 verdict deleted the Pallas kernel.
    ("kcenter_select", 10000, 128, 600),
    # The same selection at the PAPER'S pool size: the protocol scores a
    # 130k subset (50k labeled cap + 80k unlabeled cap, gen_jobs.py:8-13)
    # that the reference can only handle partitioned — this phase times
    # the full-pool no-partition scan and records peak HBM.
    ("kcenter_select_130k", 10000, 128, 900),
    # Where does no-partition selection actually stop?  Climb + bisect
    # toward the FULL 1.28M x 2048 f32 factor matrix, recording picks/s
    # and peak HBM at each pool size; the largest completed N is the
    # measured envelope DESIGN.md §3's analytic one must match.  iters is
    # the per-attempt pick budget (small: the question is residency, not
    # selection throughput).
    ("kcenter_select_maxn", 256, 128, 900),
    # First on-TPU VAAL execution record: one VAE+discriminator co-train
    # epoch over the synthetic in-memory pool through the production
    # VAALSampler step, with finite-loss/learning assertions.  iters is
    # the epoch count.
    ("vaal_cotrain", 1, 64, 600),
    # The ONLINE path (active_learning_tpu/serve/): a loopback scoring
    # service driven by the closed+open-loop load generator.  iters is
    # the closed-loop window in SECONDS; per-chip batch is the service's
    # max_batch.  Records qps, p50/p99 request latency, the
    # batch-occupancy histogram, and asserts ZERO request-path compiles
    # after the bucket warmup (the test_compile_reuse counter).
    ("serve_throughput", 8, 64, 600),
    # The STREAMING loop (active_learning_tpu/stream/): a real
    # StreamService on loopback — ingest N synthetic rows through
    # POST /v1/pool (+ labels through /v1/label) via the loadgen's
    # ingest mode, the watermark trigger fires, a full AL round
    # completes over the grown (extent-aligned) pool.  iters is the
    # round count (bootstrap + triggered); per-chip batch bounds
    # max_request_rows.  Records ingest rows/sec (WAL-fsync bound),
    # ack p50/p99, and the trigger cause.
    ("stream_round", 2, 64, 600),
    # The fleet tier (DESIGN.md §17): a 2-run sweep on two localhost
    # workers through the real controller, one child SIGKILL'd after
    # its round-0 checkpoint — must resume and finish with the merged
    # scrape + matched-budget comparison rendered.  iters is the
    # per-run round count (floored at 2: the kill waits for a resumable
    # checkpoint); per-chip batch is unused.  CPU-only (host-pure
    # controller + the tests/fleet_child.py harness), so it never
    # competes for the accelerator.
    ("fleet_smoke", 2, 64, 900),
    # BASELINE.md metric #1: real end-to-end AL rounds through the
    # production driver.  iters is the per-round epoch count.
    ("al_round_cifar", 4, 128, 900),
    # Cold round-0 query alone decodes the full 50k JPEG tree (~420s
    # in the 2026-07-31 captures), so the first attempt needs the
    # largest window of any phase.
    ("al_round_imagenet", 2, 128, 1800),
]
# Stop launching fresh attempts past this wall-clock: the guaranteed JSON
# line must land WELL inside the driver's outer timeout (round 3 died at
# rc=124 against a ~50-minute ladder).  Probe + phases + emit fit in this.
TOTAL_BUDGET_S = float(os.environ.get("AL_BENCH_BUDGET_S", "1400"))
# Probe slower than this => the backend is degraded; don't start fresh
# 900-second phase attempts against it.
PROBE_DEGRADED_S = 60.0
# The would-be-final JSON is rewritten here after every phase, so even a
# SIGKILL mid-run leaves complete evidence of everything captured so far.
PARTIAL_PATH = os.path.join(_STATE_DIR, "bench_partial.json")
# The FULL final evidence lands here; the stdout line only references it.
EVIDENCE_PATH = os.path.join(_STATE_DIR, "bench_evidence.json")
# Hard bound on the ONE stdout line: the consuming harness records a
# ~2,000-byte tail of stdout — which carries nothing but this line — so
# the bound needs enough margin for tail-window slop, not another whole
# line.  1950 fits the 16-phase realistic-maximal rich form (every
# phase cached with every optional
# rider: the feed-hierarchy fields, unit/backend on BOTH paper-scale
# selection phases, the sharded-ceiling probe's pool_sharding tag,
# pipeline/overlap on both end-to-end round phases — ISSUE 7, ~90
# bytes — the failure-model counters retries/degraded on both round
# phases — ISSUE 8, worst case '"retries":NN,"degraded":N,' x2 ≈ 50
# bytes — the gradient-path riders on both TRAIN phases — ISSUE 10,
# worst case '"bwd_frac":0.NNN,"grad_ar":"int8",' x2 ≈ 68 bytes — and
# now the experiment-truth drift rider on both round phases — ISSUE
# 13, worst case '"drift":0.NNNNNN,' x2 ≈ 36 bytes — and the streaming
# phase — ISSUE 14: one more phase entry (~30 bytes) plus its riders,
# worst case '"ack_p99":NNN.NNN,"trigger":"watermark",' ≈ 40 bytes —
# and the pod-tier riders — ISSUE 15: the quantized wire form on both
# train phases ('"grad_sync":"rs",' x2 ≈ 36 bytes; grad_wire_mb stays
# in the evidence file) plus the ring-feed tag on both round phases and
# the maxn probe ('"ring":true,' x3 ≈ 36 bytes) — and the disk-tier
# phase — ISSUE 16: one more phase entry (~30 bytes) plus its riders,
# worst case '"hit":0.NNN,"stall_ms":NN.NN,' ≈ 30 bytes; the finer
# paging figures (page-in rate, p50, the memory-leg comparison) stay in
# the evidence file) without truncation; staged truncation in
# _compact_line still guards the pathological cases.  NOTE the
# accounting above counts COMPACT spellings ('"ack_p99":NNN.NNN,' — no
# spaces), which json.dumps only emits under explicit
# separators=(",", ":"); the default ", "/": " separators spent one
# unbudgeted tail byte per key and comma (~150 bytes across the rich
# form) until ISSUE 16's 15th phase pushed the spaced form past the
# bound and exposed the gap — _compact_line now dumps compact.  The
# fleet tier (ISSUE 18) adds the 16th phase entry (~35 bytes) plus its
# riders, worst case '"runs":N,"resumed":N,"wall_s":NNN.N,' ≈ 37 bytes
# and its long unit string ('"unit":"runs finished/min (2-worker
# localhost fleet)",' ≈ 52 bytes) — which pushed the 15-phase 1782-byte
# maximal past 1950.  16 phases ride; the measured realistic-maximal
# rich form is 1958 bytes
# (pinned ≤ MAX_LINE_BYTES by test_compact_line_bounded_all_phases_full
# with every phase's riders present AND a pytest-length evidence path —
# ~44 bytes longer than the production ~/.cache path), 2000 leaves ~40
# bytes of tail-window slop (the tail carries nothing but this line and
# its newline), and the all-failed degraded form stays under the
# 1750-byte tail-slop pin in tests/test_bench_json.py.  Pinned by unit
# tests at both extremes.
MAX_LINE_BYTES = 2000


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Child: one phase, one process, own backend.
# ---------------------------------------------------------------------------

def _peak_tflops(device_kind: str):
    kind = device_kind.lower()
    for sub, peak in PEAK_TFLOPS_BF16:
        if sub in kind:
            return peak
    return None


def _model_and_views(config: str):
    import jax.numpy as jnp
    from active_learning_tpu.data.core import (CIFAR10_NORM, IMAGENET_NORM,
                                               ViewSpec)
    from active_learning_tpu.models.resnet import resnet18, resnet50

    # The bench measures the production bf16 configuration: fused bf16 BN
    # statistics (TrainConfig.bn_stats_dtype "auto" on a bf16 model) and,
    # for the 224px model, the space-to-depth stem.  AL_BENCH_S2D=0 /
    # AL_BENCH_BN_STATS=f32 restore the old stem/stats for A/Bs.
    s2d = os.environ.get("AL_BENCH_S2D", "1") != "0"
    bf16_stats = os.environ.get("AL_BENCH_BN_STATS", "bf16") != "f32"
    bn_stats = jnp.bfloat16 if bf16_stats else None
    if config == "resnet50_imagenet":
        model = resnet50(num_classes=1000, dtype=jnp.bfloat16,
                         stem="s2d" if s2d else "default",
                         bn_stats_dtype=bn_stats)
        # ImageNet: crop happens at decode; the device view only flips
        # (data/imagenet.py:257).
        return (model, 224, 1000,
                ViewSpec(IMAGENET_NORM, augment=True, pad=0),
                ViewSpec(IMAGENET_NORM, augment=False))
    model = resnet18(num_classes=10, cifar_stem=True, dtype=jnp.bfloat16,
                     bn_stats_dtype=bn_stats)
    return (model, 32, 10, ViewSpec(CIFAR10_NORM, augment=True, pad=4),
            ViewSpec(CIFAR10_NORM, augment=False))


def _model_config_fields(model) -> dict:
    """The stem/BN-stats configuration a train/score phase measured —
    recorded in the phase JSON so every number is attributable to its
    compute configuration."""
    import jax.numpy as jnp
    return {
        "s2d": getattr(model, "stem", "default") == "s2d",
        "bn_stats_dtype": ("bfloat16"
                          if getattr(model, "bn_stats_dtype", None)
                          == jnp.bfloat16 else "float32"),
    }


def _ensure_jpeg_tree(root: str, n_images: int, n_classes: int = 100
                      ) -> float:
    """Synthetic ImageNet-like JPEG tree: ``n_classes`` class directories,
    variable image sizes (224-320px), seeded per index so the tree is
    reproducible and resumable.  ONE shared root that only ever grows: a
    retry with a smaller target reuses the existing files (smaller runs
    read a ``limit=`` of them), so generation cost is paid once, not per
    attempt.  Returns generation seconds (0.0 when enough images exist)."""
    import numpy as np
    from PIL import Image

    marker = os.path.join(root, ".generated")
    have = 0
    try:
        with open(marker) as fh:
            have = int(fh.read().strip() or 0)
    except (OSError, ValueError):
        pass
    if have >= n_images:
        return 0.0
    t0 = time.perf_counter()
    for c in range(n_classes):
        os.makedirs(os.path.join(root, f"cls_{c:04d}"), exist_ok=True)
    for i in range(n_images):
        path = os.path.join(root, f"cls_{i % n_classes:04d}",
                            f"img_{i:06d}.jpg")
        if os.path.exists(path):
            continue
        rng = np.random.default_rng(i)
        h = int(rng.integers(224, 321))
        w = int(rng.integers(224, 321))
        base = rng.integers(0, 256, size=(12, 16, 3), dtype=np.uint8)
        Image.fromarray(base).resize((w, h), Image.BILINEAR).save(
            path, quality=75)
    with open(marker, "w") as fh:
        fh.write(str(n_images))
    return time.perf_counter() - t0


def run_datapath_phase(n_images: int, per_chip: int):
    """End-to-end rehearsal of the ImageNet scoring data path: disk JPEGs
    -> native C++ batch decode/crop/resize -> threaded prefetch ->
    mesh-sharded ResNet-50 scoring via collect_pool (which also enforces
    score/index alignment over the whole pass).  Reports the end-to-end
    scoring rate, the decode-only rate, and the per-core decode rate —
    the number that says how many host cores a full-size run needs to
    keep the mesh fed.

    GENERATOR: yields the result after each completed measurement (cold
    scored pass, warm scored pass, warm gather decomposition) so a
    timeout mid-phase loses only the unfinished measurement — the caller
    prints each snapshot as its own JSON line and the parent keeps the
    last parseable one."""
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    from active_learning_tpu.data.core import IMAGENET_NORM, ViewSpec
    from active_learning_tpu.data.imagenet import ImageFolderDataset
    from active_learning_tpu.data.pipeline import iterate_batches
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.strategies import scoring

    root = os.path.join(tempfile.gettempdir(), "al_tpu_datapath")
    gen_sec = _ensure_jpeg_tree(root, n_images)
    mesh = mesh_lib.make_mesh(-1)
    n_chips = int(mesh.devices.size)
    batch_size = per_chip * n_chips
    device_kind = jax.devices()[0].device_kind
    cores = (len(os.sched_getaffinity(0))
             if hasattr(os, "sched_getaffinity") else os.cpu_count() or 1)
    threads = max(2, min(16, 2 * cores))
    log(f"[imagenet_datapath] {n_images} JPEGs (gen {gen_sec:.0f}s), "
        f"{n_chips}x {device_kind}, batch {batch_size}, {cores} host cores")

    view = ViewSpec(IMAGENET_NORM, augment=False)
    dataset = ImageFolderDataset(root, view, train_transform=False,
                                 num_classes=1000, limit=n_images)
    dataset.gather(np.arange(8))  # warm-up: builds/loads the native lib

    # Decode-only: the host side in isolation (native decode + crop +
    # resize + batch assembly through the threaded prefetcher).
    n_decode = min(len(dataset), 5000)
    t0 = time.perf_counter()
    rows = 0
    for b in iterate_batches(dataset, np.arange(n_decode), batch_size,
                             num_threads=threads):
        rows += int(b["mask"].sum())
    decode_ips = rows / (time.perf_counter() - t0)

    result = {
        "phase": "imagenet_datapath",
        "n_chips": n_chips,
        "batch_per_chip": per_chip,
        "n_images": len(dataset),
        "decode_ips": round(decode_ips, 1),
        "host_cores": cores,
        "decode_ips_per_core": round(decode_ips / cores, 1),
        "gen_sec": round(gen_sec, 1),
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
    }
    if jax.devices()[0].platform != "cpu":
        # Host->device bandwidth for one decoded batch (19 MB per
        # 128-row 224px batch): reported so a slow end-to-end rate is
        # attributable to the transfer or not.  Skipped on the CPU-fallback backend, where a
        # device_put is a host memcpy describing no real transfer path.
        probe = np.zeros((batch_size, 224, 224, 3), dtype=np.uint8)
        jax.device_put(probe).block_until_ready()  # warm the path
        t0 = time.perf_counter()
        jax.device_put(probe).block_until_ready()
        h2d_mb_s = probe.nbytes / 1e6 / (time.perf_counter() - t0)
        result["h2d_mb_per_sec"] = round(h2d_mb_s, 1)
        result["h2d_ips_ceiling"] = round(h2d_mb_s * 1e6 / (224 * 224 * 3),
                                          1)
    if os.environ.get("AL_BENCH_DATAPATH_DECODE_ONLY") == "1":
        # Accelerator unreachable: report the host-side numbers (the
        # phase's real subject) and skip the model pass.
        result.update(ips=round(decode_ips, 1),
                      ips_per_chip=round(decode_ips / n_chips, 1),
                      decode_only=True)
        yield result
        return

    # Full scoring pass over the whole tree, decode overlapped with device
    # compute exactly as a real acquisition round runs it — INCLUDING the
    # production decoded-pool memmap cache (driver wires it the same way),
    # so this timed pass is round 0 (decode + cache write) and the second
    # pass below is every later round (pure cache read, bounded by
    # h2d/page cache instead of JPEG decode).
    import shutil

    from active_learning_tpu.data.cache import maybe_wrap_decoded
    # Same location family as the production driver (~/.cache), NOT
    # tempfile.gettempdir(): /tmp is commonly tmpfs, where a pool-sized
    # uint8 "disk" cache is actually host RAM and can OOM the bench.
    # The fixed "decoded_bench" leaf is ALWAYS appended — this dir is
    # rmtree'd below, and an env override naming a shared parent (or the
    # production cache) must never make that recursive delete eat it.
    cache_dir = os.path.join(
        os.environ.get("AL_BENCH_CACHE_DIR")
        or os.path.join(os.path.expanduser("~"), ".cache", "al_tpu"),
        "decoded_bench")
    shutil.rmtree(cache_dir, ignore_errors=True)  # measure a COLD round 0
    cached_set = maybe_wrap_decoded(dataset, cache_dir, 32 << 30)
    result["decoded_cache"] = cached_set is not dataset
    try:
        yield from _datapath_model_passes(result, dataset, cached_set,
                                          batch_size, threads, mesh)
    finally:
        # Pool-sized uint8 data must not squat in persistent ~/.cache
        # after the bench (and the next run's round 0 must start cold).
        shutil.rmtree(cache_dir, ignore_errors=True)


def _datapath_model_passes(result, dataset, cached_set, batch_size,
                           threads, mesh):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from active_learning_tpu.strategies import scoring

    n_chips = result["n_chips"]
    model, _, _, _, score_view = _model_and_views("resnet50_imagenet")
    result.update(_model_config_fields(model))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((8, 224, 224, 3), jnp.float32),
                           train=False)
    step = scoring.make_prob_stats_step(model, score_view)
    # Untimed warm-up at the real batch shape: the jitted step's XLA
    # compile (tens of seconds for ResNet-50 on TPU) must not pollute the
    # measured pass, same as every other phase's 3 warm-up iterations.
    scoring.collect_pool(dataset, np.arange(min(batch_size, len(dataset))),
                         batch_size, step, variables, mesh,
                         keys=("margin",))
    all_idxs = np.arange(len(dataset))
    t0 = time.perf_counter()
    out = scoring.collect_pool(cached_set, all_idxs, batch_size, step,
                               variables, mesh, num_workers=threads,
                               prefetch=4, keys=("margin",))
    score_sec = time.perf_counter() - t0
    assert len(out["margin"]) == len(dataset)
    ips = len(dataset) / score_sec
    # Field semantics (the r5 naming trap: "warm" 157.7 reading LOWER
    # than "cold" 348.6 looked like a regression): the COLD pass is the
    # decode-once round-0 pass that ALSO writes the memmap cache, run
    # with every decode thread busy; the WARM pass is the steady-state
    # rounds-1+ memmap feed, whose rate is bounded by page-cache/gather
    # bandwidth, not decode parallelism — on a many-core host cold decode
    # can legitimately out-rate the single-stream warm gather.  The
    # canonical names (cold_populate_ips / warm_memmap_ips) are the ONLY
    # spellings; the deprecated ips_warm alias and its deprecated_keys
    # shim served their one release (PR 5) and are gone.  ``ips`` stays
    # as the generic phase-schema throughput key every phase carries.
    result.update(
        ips=round(ips, 1), ips_per_chip=round(ips / n_chips, 1),
        cold_populate_ips=round(ips, 1),
        score_sec=round(score_sec, 1))
    yield dict(result)  # cold pass is safe with the parent
    if cached_set is not dataset:
        # Steady state: rounds 1+ re-score the pool from the warm cache.
        t0 = time.perf_counter()
        out = scoring.collect_pool(cached_set, all_idxs, batch_size, step,
                                   variables, mesh, num_workers=threads,
                                   prefetch=4, keys=("margin",))
        warm_sec = time.perf_counter() - t0
        assert len(out["margin"]) == len(dataset)
        result.update(warm_memmap_ips=round(len(dataset) / warm_sec, 1),
                      warm_score_sec=round(warm_sec, 1))
        yield dict(result)  # warm pass is safe with the parent
        # Host-side-only warm rate (cache gather + batch assembly, no
        # device work): decomposes warm_memmap_ips into host vs
        # device+h2d the way decode_ips does for the cold pass — on a
        # 1-core sandbox the warm pass is HOST-bound and this number
        # says by how much.
        t0 = time.perf_counter()
        rows = 0
        for start in range(0, len(dataset), batch_size):
            rows += len(cached_set.gather(
                all_idxs[start:start + batch_size]))
        gather_sec = time.perf_counter() - t0
        result.update(warm_gather_ips=round(rows / gather_sec, 1),
                      warm_gather_sec=round(gather_sec, 1))
        yield dict(result)
        # Device-resident warm pass: the fully-populated cache promotes
        # to .images (data/cache.py), and with the budget raised over the
        # pool (the documented --resident_scoring_bytes deployment choice
        # for 16 GB chips) rounds 1+ score via on-device gathers — no
        # per-batch image h2d at all.  Timed including the one-off pool
        # upload, reported separately so steady state is attributable.
        cache = None
        try:
            from active_learning_tpu.parallel import resident as res_lib
            pool_bytes = len(dataset) * int(np.prod(
                cached_set.image_shape))
            if res_lib.eligible(cached_set, pool_bytes + 1):
                cache = {}
                t0 = time.perf_counter()
                # block_until_ready: device_put is async, and an in-flight
                # multi-GB transfer leaking into the scoring timer would
                # defeat the point of reporting the upload separately.
                jax.block_until_ready(
                    res_lib.pool_arrays(cache, cached_set, mesh))
                upload_sec = time.perf_counter() - t0
        except Exception as e:
            # Genuinely environmental: HBM/upload failure.  Correctness
            # of the scoring pass itself is NOT handled here — see below.
            log(f"[imagenet_datapath] resident warm pass unavailable: "
                f"{e!r}")
            result["resident_warm_error"] = repr(e)[:160]
            yield dict(result)
            cache = None
        if cache is not None:
            run_kwargs = dict(keys=("margin",), resident_cache=cache,
                              resident_max_bytes=pool_bytes + 1)
            # Untimed warm-up: the resident gather runner is a fresh jit
            # that has never executed — its compile (tens of seconds on
            # TPU) must not pollute the steady-state number, same as
            # every other phase's warm-up.
            scoring.collect_pool(cached_set, all_idxs[:batch_size],
                                 batch_size, step, variables, mesh,
                                 **run_kwargs)
            t0 = time.perf_counter()
            out = scoring.collect_pool(cached_set, all_idxs, batch_size,
                                       step, variables, mesh, **run_kwargs)
            resident_sec = time.perf_counter() - t0
            if len(out["margin"]) != len(dataset):
                # A row-count mismatch is a scoring correctness bug and
                # must read as one — never as "unavailable".
                result["resident_warm_error"] = (
                    f"CORRECTNESS: resident pass returned "
                    f"{len(out['margin'])} rows for {len(dataset)}")
            else:
                result.update(
                    warm_resident_ips=round(len(dataset) / resident_sec,
                                            1),
                    warm_resident_sec=round(resident_sec, 1),
                    resident_upload_sec=round(upload_sec, 1))
            yield dict(result)


def run_train_feed_phase(epochs: int, per_chip: int):
    """The train-feed hierarchy, leg by leg: identical fits (same pool,
    same seeds, bit-identical batch streams) through the PRODUCTION
    Trainer.fit under each feed —

      * resident       on-device gather + augment from the pinned pool
                       (zero host image copies after the one upload);
      * host_prefetch  worker-threaded gather behind the double-buffered
                       device prefetch (data/pipeline.train_feed_batches);
      * host_serial    the per-batch gather -> shard -> step loop.

    The measured host feed (BENCH_r05: 157.7 warm memmap ips) against an
    8-chip device demand of ~21k ips is the ~100x host-bound gap this
    phase exists to close; feed_stall_frac on the host legs quantifies
    it directly.  GENERATOR: yields after each completed leg so a
    timeout loses only the unfinished ones."""
    import numpy as np

    import jax
    from active_learning_tpu.config import (LoaderConfig, TelemetryConfig,
                                            TrainConfig)
    from active_learning_tpu.data.core import ArrayDataset
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.telemetry import runtime as tele_runtime
    from active_learning_tpu.train.trainer import Trainer

    smoke = (os.environ.get("AL_BENCH_ROUND_SMOKE") == "1"
             or jax.devices()[0].platform == "cpu")
    config = "smoke_tinyconv" if smoke else "resnet50_imagenet"
    if smoke:
        # CPU/CI smoke: a tiny conv net — ResNet steps cost ~6 s each on
        # one CPU core, and the smoke exists to exercise every feed leg
        # end-to-end, not to measure ResNet.  Tagged "smoke" so the
        # parent's cache can never bill it as a real capture's config.
        import flax.linen as nn
        import jax.numpy as jnp
        from active_learning_tpu.data.core import CIFAR10_NORM, ViewSpec

        class _SmokeNet(nn.Module):
            @nn.compact
            def __call__(self, x, train=True, return_features=False):
                x = x.astype(jnp.float32)
                x = nn.relu(nn.Conv(8, (3, 3))(x))
                emb = x.mean(axis=(1, 2))
                logits = nn.Dense(10, name="linear")(emb)
                return (logits, emb) if return_features else logits

        model, px, n_classes = _SmokeNet(), 32, 10
        train_view = ViewSpec(CIFAR10_NORM, augment=True, pad=4)
    else:
        model, px, n_classes, train_view, _score_view = _model_and_views(
            "resnet50_imagenet")
    mesh = mesh_lib.make_mesh(-1)
    n_chips = int(mesh.devices.size)
    device_kind = jax.devices()[0].device_kind
    batch_size = per_chip * n_chips
    pool_n = max(4 * batch_size, 256 if smoke else 4096)
    cores = (len(os.sched_getaffinity(0))
             if hasattr(os, "sched_getaffinity") else os.cpu_count() or 1)
    workers = max(2, min(16, 2 * cores))
    log(f"[imagenet_train_feed] {config} x{n_chips} {device_kind}, pool "
        f"{pool_n}x{px}px, batch {batch_size}, {epochs} epochs/leg")

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(pool_n, px, px, 3), dtype=np.uint8)
    targets = rng.integers(0, n_classes, size=pool_n).astype(np.int64)

    # feed_stall_frac/host_wait collection needs an ENABLED telemetry
    # runtime (the trainer's collect gate); no heartbeat/trace — just the
    # per-step collection flag.
    rt = tele_runtime.RunTelemetry(cfg=TelemetryConfig(enabled=True))
    tele_runtime.install(rt)
    result = {
        "phase": "imagenet_train_feed",
        "ips": None, "ips_per_chip": None,
        "unit": "train images/sec (in-fit)",
        "n_chips": n_chips, "batch_per_chip": per_chip,
        "pool_n": pool_n, "px": px, "epochs": epochs, "smoke": smoke,
        "model_config": config, "feed_workers": workers,
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
        **_model_config_fields(model),
    }
    legs = (
        ("resident", dict(train_feed="resident",
                          loader=dict(num_workers=0, prefetch=2))),
        ("host_prefetch", dict(train_feed="host", feed_workers=workers,
                               loader=dict(num_workers=0, prefetch=4))),
        ("host_serial", dict(train_feed="host", feed_workers=0,
                             loader=dict(num_workers=0, prefetch=0))),
    )
    try:
        for leg, spec in legs:
            loader = spec.pop("loader")
            cfg = TrainConfig(
                loader_tr=LoaderConfig(batch_size=batch_size, **loader),
                **spec)
            train_set = ArrayDataset(images, targets, n_classes, train_view)
            trainer = Trainer(model, cfg, mesh, n_classes, train_bn=True)
            labeled = np.arange(pool_n)

            def one_fit(n_ep: int):
                state = trainer.init_state(jax.random.PRNGKey(0),
                                           images[:8])
                return trainer.fit(state, train_set, labeled, train_set,
                                   np.zeros(0, np.int64), n_epoch=n_ep,
                                   es_patience=0,
                                   rng=np.random.default_rng(1))

            one_fit(1)  # warm-up: compiles (and the resident upload)
            t0 = time.perf_counter()
            fit = one_fit(epochs)
            # fit materializes every epoch loss to host floats before
            # returning — a data-dependent fetch, so the wall is real.
            assert all(
                isinstance(h["train_loss"], float) for h in fit.history)
            dt = time.perf_counter() - t0
            got = trainer.last_feed
            ips = pool_n * epochs / dt
            if got["source"] == leg:
                result[f"ips_{leg}"] = round(ips, 1)
                result[f"stall_{leg}"] = got.get("feed_stall_frac")
            else:
                # e.g. the pool didn't fit the resident budget: the leg
                # degraded — record what actually ran under a DEGRADED
                # key, never as the leg's number (resident_x_serial and
                # the compact line's legs array derive only from true
                # per-leg captures).
                result[f"feed_degraded_{leg}"] = got["source"]
                result[f"ips_{leg}_degraded"] = round(ips, 1)
            log(f"[imagenet_train_feed] {leg}: {ips:,.1f} img/s "
                f"(feed={got['source']}, "
                f"stall={got.get('feed_stall_frac')})")
            if leg == "resident" and got["source"] == "resident":
                result["ips"] = round(ips, 1)
                result["ips_per_chip"] = round(ips / n_chips, 1)
                result["feed_source"] = got["source"]
                result["feed_stall_frac"] = got.get("feed_stall_frac")
            yield dict(result)
    finally:
        tele_runtime.uninstall(rt)
    if result.get("ips_host_serial") and result.get("ips_resident"):
        result["resident_x_serial"] = round(
            result["ips_resident"] / result["ips_host_serial"], 2)
    # An auto-resolved trainer must land on the top of the hierarchy —
    # the acceptance invariant "resident-gather is the auto-selected
    # path whenever the pool is pinned", asserted LIVE on accelerator
    # runs (the CPU smoke's auto rule deliberately keeps small fits on
    # the host leg — the scan compile doesn't amortize there).
    if not smoke:
        auto_trainer = Trainer(model, TrainConfig(
            loader_tr=LoaderConfig(batch_size=batch_size)), mesh,
            n_classes, train_bn=True)
        train_set = ArrayDataset(images, targets, n_classes, train_view)
        from active_learning_tpu.parallel import resident as resident_lib
        if resident_lib.eligible(train_set, auto_trainer.resident_budget):
            # Pinned, exactly as a round's scoring pass pins it.
            resident_lib.pool_arrays(auto_trainer.resident_pool,
                                     train_set, mesh)
            auto = auto_trainer.resolve_train_feed(train_set,
                                                   np.arange(pool_n))
            result["auto_feed_with_pinned_pool"] = auto
            if auto != "resident":
                result["auto_feed_error"] = (
                    "CORRECTNESS: pinned pool did not auto-select the "
                    f"resident feed (got {auto})")
    yield result


def run_kcenter_phase(budget: int, dim: int = 2048, pool_n: int = 50000
                      ) -> dict:
    """Greedy k-center selection at the paper's protocol scale over a
    [50k, 2048] embedding pool (the reference's subset cap,
    gen_jobs.py:8-13; its host loop does one np.random.choice +
    full-matrix min per pick, coreset_sampler.py:66-105).  Times the
    PRODUCTION path: batched farthest-first (q = DEFAULT_BATCH_Q picks
    per pool pass) on the XLA scan — since the r5 verdict deleted the
    Pallas kernel this is the only backend; the scan that answered
    still rides in "backend" for attribution.  Reports picks/sec; "ips"
    carries picks/sec so the parent's schema checks hold (unit field
    says which)."""
    import numpy as np

    import jax
    from active_learning_tpu.strategies import kcenter as kc
    from active_learning_tpu.strategies.kcenter import (DEFAULT_BATCH_Q,
                                                        kcenter_greedy)

    device_kind = jax.devices()[0].device_kind
    log(f"[kcenter_select] pool [{pool_n}, {dim}], budget {budget} on "
        f"{device_kind}")
    host_rng = np.random.default_rng(0)
    emb = host_rng.normal(size=(pool_n, dim)).astype(np.float32)
    labeled = np.zeros(pool_n, dtype=bool)
    labeled[host_rng.choice(pool_n, min(1000, pool_n // 8),
                            replace=False)] = True

    # Warm-up at the SAME budget/shapes (budget is a static scan length):
    # the first call pays the XLA compile, the timed call does not.
    kcenter_greedy((emb,), labeled, budget, rng=np.random.default_rng(1))
    t0 = time.perf_counter()
    picks = kcenter_greedy((emb,), labeled, budget,
                           rng=np.random.default_rng(2))
    dt = time.perf_counter() - t0
    assert len(picks) == budget and len(set(picks.tolist())) == budget
    rate = budget / dt
    result = {
        "phase": "kcenter_select",
        "ips": round(rate, 1),
        "ips_per_chip": round(rate, 1),
        "unit": "picks/sec",
        "n_chips": 1,  # the sequential scan runs on one chip
        "pool_n": pool_n,
        "dim": dim,
        "budget": budget,
        "batch_q": DEFAULT_BATCH_Q,
        "backend": kc.LAST_BACKEND,
        "pool_sharding": kc.LAST_SHARDING,
        "ring_feed": kc.LAST_RING_FEED,
        "select_sec": round(dt, 2),
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
    }
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak:
            result["peak_hbm_gb"] = round(peak / 2**30, 2)
    except Exception:
        pass  # memory_stats is backend-dependent; absence is fine
    return result, picks


def run_kcenter_maxn_phase(budget: int, dim: int = 2048):
    """Climb + bisect toward the largest pool the no-partition k-center
    scan completes — now under BOTH resident layouts (ISSUE 6):

      1. REPLICATED: the single-chip ceiling, 160k -> 320k -> 640k ->
         1.28M rows of [N, 2048] f32 factors (1.28M x 2048 x 4 =
         10.5 GB — the FULL ImageNet pool), with a couple of bisection
         steps between the last success and the first failure.  This is
         the pre-sharding envelope (``replicated_max_n`` /
         ``no_partition_holds_to_n``).
      2. ROW-SHARDED (multi-device meshes): the same climb with the
         ladder scaled by the device count — each chip holds rows/ndev
         of the factor matrix (strategies/kcenter._build_sharded_fns),
         so max-N should scale ~linearly with chips.  The phase ASSERTS
         ``max_n >= 2 * replicated_max_n`` whenever both layouts
         completed a climb on a >=2-device mesh at equal per-chip HBM
         (``row_scale_x`` records the measured ratio) — the acceptance
         gate for breaking, not just finding, the ceiling.

    Each attempt records picks/s, its analytic per-chip factor bytes
    (``factor_gb_per_chip`` — the equal-per-chip-HBM evidence), and the
    measured per-chip / mesh-total peak HBM; ``peak_bytes_in_use`` is a
    process-lifetime high-water mark, so an attempt that peaked below an
    earlier one carries ``peak_hbm_carryover`` instead of claiming the
    stale figure as its own.  Row rungs whose bucketed pool cannot split
    over the mesh (``kcenter.row_capable``) are refused before any
    compute — the greedy would silently run them replicated at ndev
    times the per-chip bytes.  Failures past the envelope
    (RESOURCE_EXHAUSTED) are recorded, not fatal.  GENERATOR: yields
    after every completed attempt so a timeout loses only the unfinished
    pool size.  CPU backends climb a tiny ladder instead — the envelope
    question is an HBM question; the layout-scaling question still
    answers structurally."""
    import numpy as np

    import jax
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.strategies import kcenter as kc

    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind
    n_chips = len(jax.devices())
    mesh = mesh_lib.make_mesh() if n_chips > 1 else None
    sharding = "row" if mesh is not None else "replicated"
    if platform == "cpu":
        ladder = [4096, 8192, 16384]
        budget = min(budget, 64)
    else:
        ladder = [160_000, 320_000, 640_000, 1_280_000]
    row_ladder = [n * n_chips for n in ladder]
    result = {
        "phase": "kcenter_select_maxn",
        "ips": None, "ips_per_chip": None, "unit": "picks/sec",
        "n_chips": n_chips, "dim": dim, "budget": budget,
        "pool_sharding": sharding, "max_n": 0, "replicated_max_n": 0,
        "target_n": (row_ladder if mesh is not None else ladder)[-1],
        "attempts": [],
        "device_kind": device_kind, "platform": platform,
    }

    def hbm_peaks():
        per = []
        try:
            for d in jax.local_devices():
                stats = d.memory_stats() or {}
                p = stats.get("peak_bytes_in_use")
                if p:
                    per.append(int(p))
        except Exception:
            pass  # memory_stats is backend-dependent; absence is fine
        if not per:
            return None, None
        return max(per), sum(per)

    def attempt(n: int, use_mesh):
        layout = "row" if use_mesh is not None else "replicated"
        if use_mesh is not None and not kc.row_capable(n, budget,
                                                       use_mesh):
            # The greedy's own gate would silently fall back to the
            # replicated backend (e.g. a bucketed pool that doesn't
            # divide over a non-power-of-two mesh) — which on a row
            # rung means ndev times the intended per-chip bytes and a
            # wrong-layout timing.  Refuse BEFORE any compute so the
            # climb records a layout-capability skip, never a
            # misattributed OOM.
            raise RuntimeError(
                f"row layout unavailable for n={n}: the bucketed pool "
                f"does not split over {use_mesh.devices.size} devices "
                "(kcenter.row_capable) — skipped before any compute")
        log(f"[kcenter_select_maxn] trying pool [{n}, {dim}] "
            f"({n * dim * 4 / 2**30:.1f} GB of factors, {layout})")
        pre_peak, _ = hbm_peaks()
        rng = np.random.default_rng(0)
        # Chunked generation: a 1.28M-row normal draw in one call holds
        # two 10.5 GB temporaries on the host.
        emb = np.empty((n, dim), dtype=np.float32)
        for lo in range(0, n, 131072):
            hi = min(n, lo + 131072)
            emb[lo:hi] = rng.standard_normal(
                (hi - lo, dim), dtype=np.float32)
        labeled = np.zeros(n, dtype=bool)
        labeled[rng.choice(n, min(1000, n // 8), replace=False)] = True
        kcenter_greedy = kc.kcenter_greedy
        kcenter_greedy((emb,), labeled, budget,
                       rng=np.random.default_rng(1), mesh=use_mesh,
                       pool_sharding=layout)  # compile
        t0 = time.perf_counter()
        picks = kcenter_greedy((emb,), labeled, budget,
                               rng=np.random.default_rng(2),
                               mesh=use_mesh, pool_sharding=layout)
        dt = time.perf_counter() - t0
        assert len(set(picks.tolist())) == budget
        assert kc.LAST_SHARDING == layout, (
            f"requested {layout} but selection ran {kc.LAST_SHARDING}")
        entry = {"n": n, "ok": True, "ips": round(budget / dt, 1),
                 "select_sec": round(dt, 2), "pool_sharding": layout}
        # The attempt's true per-chip factor residency, analytically —
        # the number the "equal per-chip HBM" comparison actually
        # rests on (a row rung at n = ndev*m holds the same per-chip
        # factor bytes as the replicated rung at m).
        ways = use_mesh.devices.size if use_mesh is not None else 1
        entry["factor_gb_per_chip"] = round(n * dim * 4 / ways / 2**30, 2)
        per_chip, total = hbm_peaks()
        if per_chip:
            entry["peak_hbm_gb"] = round(per_chip / 2**30, 2)
            entry["mesh_peak_hbm_gb"] = round(total / 2**30, 2)
            if pre_peak is not None and per_chip <= pre_peak:
                # peak_bytes_in_use is a PROCESS-LIFETIME high-water
                # mark: an attempt that peaked below an earlier one
                # (every row rung after the replicated climb hit the
                # single-chip ceiling) reads the old mark, not its
                # own.  Flag it — factor_gb_per_chip above carries the
                # attempt's true residency either way.
                entry["peak_hbm_carryover"] = True
        return entry

    def climb(steps, use_mesh, max_key):
        """Ladder climb + two bisection steps; updates result[max_key]
        and yields a snapshot after every attempt."""
        lo, hi = 0, None  # largest success / smallest failure

        def record(entry):
            result["attempts"].append(entry)
            if entry["ok"] and entry["n"] > result[max_key]:
                result[max_key] = entry["n"]
                # The headline follows the most capable climb that
                # actually SUCCEEDED: the replicated rungs set it, row
                # successes (climbed second, at ndev x the rows)
                # overwrite it — so a row climb with no surviving rung
                # still leaves the measured replicated ceiling on the
                # line instead of a null headline.  Per-chip rate
                # divides by the chips the entry's selection actually
                # used: a replicated attempt runs on ONE device
                # whatever the host holds.
                div = n_chips if entry["pool_sharding"] == "row" else 1
                result["ips"] = entry["ips"]
                result["ips_per_chip"] = round(entry["ips"] / div, 1)
                # The column-feed attribution (ISSUE 15): row-layout
                # headline rungs fed their initial-min/minimax columns
                # over the ring-permute feed; replicated rungs did not.
                result["ring_feed"] = kc.LAST_RING_FEED

        for n in steps:
            try:
                entry = attempt(n, use_mesh)
            except Exception as e:
                log(f"[kcenter_select_maxn] pool {n} failed: {e!r}")
                result["attempts"].append(
                    {"n": n, "ok": False, "error": repr(e)[:160],
                     "pool_sharding": ("row" if use_mesh is not None
                                       else "replicated")})
                hi = n
                yield dict(result)
                break
            record(entry)
            lo = n
            yield dict(result)
        # Two bisection steps sharpen the boundary w/o unbounded retries.
        for _ in range(2):
            if hi is None or hi - lo <= max(lo // 8, 1):
                break
            mid = (lo + hi) // 2 // 2048 * 2048
            if mid <= lo:
                break
            try:
                entry = attempt(mid, use_mesh)
            except Exception as e:
                log(f"[kcenter_select_maxn] pool {mid} failed: {e!r}")
                result["attempts"].append(
                    {"n": mid, "ok": False, "error": repr(e)[:160],
                     "pool_sharding": ("row" if use_mesh is not None
                                       else "replicated")})
                hi = mid
                yield dict(result)
                continue
            record(entry)
            lo = mid
            yield dict(result)

    # 1. The replicated (single-chip) envelope — the number DESIGN.md
    # §3's N ~ 1.8M arithmetic must reproduce on a 16 GB chip.
    yield from climb(ladder, None, "replicated_max_n")
    result["no_partition_holds_to_n"] = result["replicated_max_n"]
    if mesh is None:
        result["max_n"] = result["replicated_max_n"]
        yield dict(result)
        return
    # 2. The row-sharded climb: same per-chip rows, ndev x the pool.
    yield from climb(row_ladder, mesh, "max_n")
    if result["replicated_max_n"] > 0 and result["max_n"] > 0:
        scale = result["max_n"] / result["replicated_max_n"]
        result["row_scale_x"] = round(scale, 2)
        if n_chips >= 2:
            # The acceptance gate (ISSUE 6): row sharding must SUSTAIN
            # at least 2x the replicated ceiling at equal per-chip HBM
            # (each row attempt holds replicated-sized shards per chip).
            assert scale >= 2.0, (
                f"row-sharded max_n {result['max_n']} is only "
                f"{scale:.2f}x the replicated ceiling "
                f"{result['replicated_max_n']} on {n_chips} devices")
    elif result["max_n"] == 0:
        # No row rung survived (a gate-refused mesh geometry, or the
        # collectives' overhead pushed the first rung past the
        # envelope): the phase's honest ceiling is the replicated one —
        # emit it, tagged with the layout the headline now actually
        # describes, rather than max_n=0/ips=null discarding the
        # completed replicated climb.
        result["max_n"] = result["replicated_max_n"]
        result["pool_sharding"] = "replicated"
    yield dict(result)


def run_vaal_phase(epochs: int, per_chip: int):
    """One VAE+discriminator co-train epoch over the synthetic in-memory
    pool through the PRODUCTION VAALSampler step (strategies/vaal.py),
    asserted finite and learning (reconstruction loss falls over the
    epoch) — the first on-accelerator execution record for the VAAL path;
    until now it had only CPU-mesh unit tests (tests/test_vaal.py)."""
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    from active_learning_tpu.config import ExperimentConfig
    from active_learning_tpu.data.pipeline import iterate_batches
    from active_learning_tpu.data.synthetic import get_data_synthetic
    from active_learning_tpu.experiment.driver import build_experiment
    from active_learning_tpu.parallel import mesh as mesh_lib

    n_chips = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    smoke = os.environ.get("AL_BENCH_ROUND_SMOKE") == "1"
    pool_n = 512 if smoke else 4096
    tmp = tempfile.mkdtemp(prefix="al_bench_vaal_")
    data = get_data_synthetic(n_train=pool_n, n_test=64)
    cfg = ExperimentConfig(
        dataset="synthetic", arg_pool="synthetic", strategy="VAALSampler",
        rounds=1, round_budget=min(256, pool_n // 4), model="SSLResNet18",
        n_epoch=epochs, enable_metrics=False, log_dir=tmp, ckpt_path=tmp,
        exp_hash="bench")
    strategy = build_experiment(cfg, data=data)
    strategy.init_network_weights()
    bs = strategy.trainer.padded_batch_size(per_chip * n_chips)
    labeled = strategy.already_labeled_idxs()
    unlabeled = strategy.available_query_idxs(shuffle=False)
    log(f"[vaal_cotrain] {n_chips}x {device_kind}, pool {pool_n}, "
        f"batch {bs}, {epochs} epoch(s)")

    def epoch_batches():
        u_iter = iterate_batches(strategy.train_set, unlabeled, bs)
        for b_l in iterate_batches(strategy.train_set, labeled, bs):
            b_u = next(u_iter, None)
            if b_u is None:
                u_iter = iterate_batches(strategy.train_set, unlabeled, bs)
                b_u = next(u_iter)
            yield b_l, b_u

    key = jax.random.PRNGKey(0)
    losses = []
    steps = 0
    vs = strategy.vaal_state
    t0 = time.perf_counter()
    for _ in range(epochs):
        for b_l, b_u in epoch_batches():
            key, sub = jax.random.split(key)
            vs, step_losses = strategy._vaal_step(
                vs, mesh_lib.shard_batch(b_l, strategy.mesh),
                mesh_lib.shard_batch(b_u, strategy.mesh),
                sub, jnp.float32(cfg.vaal.lr_vae),
                jnp.float32(cfg.vaal.lr_discriminator))
            losses.append(step_losses)  # device scalars; fetched below
            steps += 1
    vae = [float(d["vae_loss"]) for d in losses]
    d_l = [float(d["d_loss"]) for d in losses]
    dt = time.perf_counter() - t0
    # The execution-record assertions: every loss finite, and the VAE
    # actually learned (mean reconstruction+KL over the last quarter of
    # the epoch below the first quarter).  A violation fails the phase.
    assert all(np.isfinite(v) for v in vae + d_l), "non-finite VAAL loss"
    q = max(1, len(vae) // 4)
    learned = float(np.mean(vae[-q:])) < float(np.mean(vae[:q]))
    assert learned, (f"VAE loss did not fall: first-quarter "
                     f"{np.mean(vae[:q]):.3f} vs last {np.mean(vae[-q:]):.3f}")
    ips = 2 * bs * steps / dt  # labeled + unlabeled rows per step
    import shutil
    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "phase": "vaal_cotrain",
        "ips": round(ips, 1),
        "ips_per_chip": round(ips / n_chips, 1),
        "unit": "cotrain images/sec",
        "n_chips": n_chips,
        "batch_per_chip": per_chip,
        "pool_n": pool_n,
        "steps": steps,
        "vae_loss_first": round(vae[0], 4),
        "vae_loss_last": round(vae[-1], 4),
        "d_loss_first": round(d_l[0], 4),
        "d_loss_last": round(d_l[-1], 4),
        "finite_losses": True,
        "learned": bool(learned),
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
    }


def run_serve_phase(duration_s: int, max_batch: int) -> dict:
    """The ONLINE path's throughput/latency record: a real loopback
    scoring service (active_learning_tpu/serve/ — asyncio HTTP server,
    microbatcher, device executor) driven by the closed+open-loop load
    generator (scripts/serve_loadgen.py).  Request latency, not round
    wall-clock, is the metric here; "ips" carries served images/sec so
    the parent's schema checks hold (the unit field says which).

    The phase also asserts the serving contract the subsystem was built
    around: after the startup bucket warmup, the request path performs
    ZERO XLA compiles (the tests/test_compile_reuse.py counter, read
    back through /metrics) — a violation fails the phase loudly.

    AL_BENCH_SERVE_SMOKE=1 shrinks to a tiny linear model at 8px for
    CI; the production capture serves SSLResNet18 at the CIFAR shape in
    bf16, the same model resnet18_cifar_score measures offline."""
    import asyncio
    import importlib.util
    import threading

    import numpy as np

    import jax
    from active_learning_tpu.config import ServeConfig
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.serve.executor import DeviceExecutor
    from active_learning_tpu.serve.server import ScoringServer

    smoke = os.environ.get("AL_BENCH_SERVE_SMOKE") == "1"
    n_chips = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    if smoke:
        import flax.linen as nn
        import jax.numpy as jnp
        from active_learning_tpu.data.core import CIFAR10_NORM, ViewSpec

        class _Probe(nn.Module):
            @nn.compact
            def __call__(self, x, train=True, return_features=False):
                emb = x.reshape((x.shape[0], -1)).astype(jnp.float32)
                logits = nn.Dense(10, name="linear")(emb)
                return (logits, emb) if return_features else logits

        model, px = _Probe(), 8
        score_view = ViewSpec(CIFAR10_NORM, augment=False)
        duration_s = min(int(duration_s), 3)
        max_batch = min(int(max_batch), 16)
        workers, rows = 2, 4
    else:
        model, px, _n_classes, _tv, score_view = _model_and_views(
            "resnet18_cifar")
        workers, rows = 4, max(1, max_batch // 4)
    mesh = mesh_lib.make_mesh(-1)
    variables = jax.tree.map(np.asarray, model.init(
        jax.random.PRNGKey(0), np.zeros((2, px, px, 3), np.float32),
        train=False))
    executor = DeviceExecutor(model, score_view, mesh,
                              image_shape=(px, px, 3),
                              variables=variables)
    serve_cfg = ServeConfig(host="127.0.0.1", port=0, max_batch=max_batch,
                            max_latency_ms=5.0,
                            queue_depth=max(128, 8 * max_batch))
    server = ScoringServer(executor, serve_cfg)
    log(f"[serve_throughput] {n_chips}x {device_kind}, max_batch "
        f"{max_batch}, {duration_s}s closed window, {workers} workers x "
        f"{rows} rows")

    loop = asyncio.new_event_loop()
    thread = threading.Thread(
        target=lambda: (asyncio.set_event_loop(loop), loop.run_forever()),
        daemon=True, name="al-bench-serve-loop")
    thread.start()
    spec = importlib.util.spec_from_file_location(
        "serve_loadgen", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts", "serve_loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)
    try:
        asyncio.run_coroutine_threadsafe(server.start(), loop).result(600)
        url = f"http://127.0.0.1:{server.port}"
        shape = (px, px, 3)
        closed = loadgen.run_closed(url, duration_s, workers, rows, shape)
        open_qps = max(1.0, 0.7 * closed["qps"])
        opened = loadgen.run_open(url, max(1.0, duration_s / 2),
                                  open_qps, rows, shape)
        snap = server._metrics()
    finally:
        try:
            asyncio.run_coroutine_threadsafe(server.drain(), loop).result(60)
        finally:
            loop.call_soon_threadsafe(loop.stop)
            thread.join(timeout=10)
    compiles = snap["compiles"]["request_path_compiles"]
    # THE contract: every served shape was pre-compiled at startup.
    assert compiles == 0, (
        f"request path compiled {compiles}x after warmup — a served "
        "shape escaped the bucket ladder")
    return {
        "phase": "serve_throughput",
        "ips": closed["ips"],
        "ips_per_chip": round(closed["ips"] / n_chips, 1),
        "unit": "scored images/sec (served)",
        "n_chips": n_chips,
        "batch_per_chip": max_batch,
        "qps_closed": closed["qps"],
        "p50_ms_closed": closed["p50_ms"],
        "p99_ms_closed": closed["p99_ms"],
        "qps_open_offered": opened.get("offered_qps"),
        "qps_open": opened["qps"],
        "p50_ms_open": opened["p50_ms"],
        "p99_ms_open": opened["p99_ms"],
        "n_429": closed["n_429"] + opened["n_429"],
        "workers": workers,
        "rows_per_request": rows,
        "batch_occupancy": snap["batch_occupancy"],
        "request_path_compiles": compiles,
        "buckets": list(server.batcher.buckets),
        "smoke": smoke,
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
    }


def run_stream_phase(rounds: int, max_batch: int) -> dict:
    """The streaming-loop smoke: a real StreamService (ingest WAL +
    growable pool + trigger scheduler + driver-phase rounds,
    active_learning_tpu/stream/) on loopback, driven by the load
    generator's ingest mode — N synthetic rows through POST /v1/pool
    (+ a label fraction through /v1/label), the watermark trigger
    fires, and a full AL round completes over the grown pool.  Records
    ingest throughput (rows acked/sec — WAL-fsync bound), ack p50/p99,
    the trigger cause, and the triggered round's wall.

    AL_BENCH_STREAM_SMOKE=1 shrinks to a tiny linear model for CI; the
    production capture streams into SSLResNet18 at the CIFAR shape —
    the same model the serve phase scores."""
    import importlib.util
    import shutil
    import tempfile
    import threading

    import jax
    from active_learning_tpu.config import (ExperimentConfig,
                                            StreamConfig,
                                            TelemetryConfig)
    from active_learning_tpu.data.synthetic import get_data_synthetic
    from active_learning_tpu.faults import preempt as preempt_lib
    from active_learning_tpu.faults.preempt import PreemptionRequested
    from active_learning_tpu.stream.service import StreamService
    from active_learning_tpu.utils.metrics import NullSink

    smoke = os.environ.get("AL_BENCH_STREAM_SMOKE") == "1"
    n_chips = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    if smoke:
        import sys as _sys
        _sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tests"))
        from helpers import TinyClassifier, tiny_train_config
        model, train_cfg = TinyClassifier(num_classes=4), \
            tiny_train_config()
        pool_n, px, n_classes, epochs, budget = 96, 8, 4, 2, 8
        ingest_rows, workers, watermark = 16, 2, 24
    else:
        model, train_cfg = None, None
        pool_n, px, n_classes, epochs, budget = 2000, 32, 10, 2, 64
        ingest_rows, workers, watermark = 64, 4, 256
    rounds = max(2, int(rounds))  # bootstrap + >=1 triggered round
    data = get_data_synthetic(n_train=pool_n, n_test=max(64, pool_n // 8),
                              num_classes=n_classes, image_size=px,
                              seed=7)
    tmp = tempfile.mkdtemp(prefix="al_bench_stream_")
    cfg = ExperimentConfig(
        dataset="synthetic", arg_pool="synthetic",
        strategy="MarginSampler", rounds=rounds, round_budget=budget,
        model="SSLResNet18", n_epoch=epochs, early_stop_patience=epochs,
        enable_metrics=False, log_dir=tmp, ckpt_path=tmp,
        exp_hash="benchstream", round_pipeline="off",
        telemetry=TelemetryConfig(enabled=True, heartbeat_every_s=0.0))
    # max_rounds=0 (run forever): the phase stops the service itself
    # once the triggered round lands, via the driver's own in-process
    # preemption flag — exercising the SIGTERM checkpoint path for free.
    scfg = StreamConfig(port=0, max_rounds=0, watermark_rows=watermark,
                        drift_psi=0.0, max_interval_s=0.0, poll_s=0.05,
                        max_request_rows=max(ingest_rows, max_batch),
                        extent_floor=64 if smoke else 256)
    service = StreamService(cfg, scfg, sink=NullSink(), data=data,
                            train_cfg=train_cfg, model=model)
    log(f"[stream_round] {n_chips}x {device_kind}, pool {pool_n}, "
        f"watermark {watermark} rows, {workers} ingest workers x "
        f"{ingest_rows} rows")
    result_box: dict = {}

    def run():
        try:
            result_box["strategy"] = service.run()
        except BaseException as e:  # noqa: BLE001 - examined below
            result_box["error"] = e

    thread = threading.Thread(target=run, daemon=True,
                              name="al-bench-stream")
    t0 = time.perf_counter()
    thread.start()
    try:
        assert service.ready.wait(300), "stream service never came up"
        spec = importlib.util.spec_from_file_location(
            "serve_loadgen", os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "scripts",
                "serve_loadgen.py"))
        loadgen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(loadgen)
        url = f"http://127.0.0.1:{service.port}"
        ingest = loadgen.run_ingest_closed(
            url, duration_s=2.0 if smoke else 5.0, workers=workers,
            rows=ingest_rows, label_frac=0.25, image_shape=(px, px, 3))
        # Bootstrap (round 0) + at least one TRIGGERED round.
        deadline = time.monotonic() + 540
        while service.rounds_run < 2 and thread.is_alive() \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        assert service.rounds_run >= 2, (
            f"no triggered round completed (rounds_run="
            f"{service.rounds_run})")
    finally:
        # Stop the run-forever loop through the preemption flag — the
        # same checkpoint-and-exit path a real SIGTERM takes.
        preempt_lib._handler(signal.SIGTERM, None)
        thread.join(timeout=120)
    total_sec = time.perf_counter() - t0
    err = result_box.get("error")
    if err is not None and not isinstance(err, PreemptionRequested):
        raise err
    shutil.rmtree(tmp, ignore_errors=True)
    snap = service.metrics.snapshot()
    lat = snap.get("latency_ms") or {}
    return {
        "phase": "stream_round",
        # Headline: acked ingest rows/sec (the WAL-fsync-bound rate).
        "ips": ingest["ips"],
        "ips_per_chip": round(ingest["ips"] / n_chips, 1),
        "unit": "ingested rows/sec (acked)",
        "n_chips": n_chips,
        "batch_per_chip": max_batch,
        "pool_n": pool_n,
        "rounds_run": service.rounds_run,  # bootstrap + triggered
        "trigger_cause": service.last_trigger.get("cause"),
        "ingest_qps": ingest["qps"],
        "ack_p50_ms": lat.get("p50"),
        "ack_p99_ms": lat.get("p99"),
        "n_429": ingest["n_429"],
        "labels_sent": ingest.get("labels_sent"),
        "pool_rows_final": service.store.n_rows,
        "pool_capacity_final": service.store.capacity,
        "total_sec": round(total_sec, 1),
        "smoke": smoke,
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
    }


def _last_ring_feed():
    """kcenter.LAST_RING_FEED, imported lazily like every other child-
    side touch of the package (bench parents never import jax)."""
    from active_learning_tpu.strategies import kcenter as kc
    return kc.LAST_RING_FEED


def run_al_round_phase(config: str, epochs: int) -> dict:
    """One REAL end-to-end AL experiment through the production driver —
    BASELINE.md metric #1 ("AL round wall-clock"), mirroring the
    reference's per-phase prints (src/main_al.py:160-178).

    Runs TWO rounds with ``init_pool_size=0`` so round 0 exercises the
    full query -> train -> test loop cold (XLA compiles included) and
    round 1 repeats it warm: the warm round is the steady-state number an
    8/30-round protocol run amortizes to.  Configs:

      * cifar: the CIFAR-10 protocol shape (BASELINE.md config #2) —
        50k-image in-memory pool at 32px, SSLResNet18, MarginSampler,
        budget 1000, the default arg pool's hyperparameters.
      * imagenet: the ImageNet protocol scaled 1/25 (BASELINE.md #4/#5)
        — the shared 50k synthetic JPEG tree via ImageFolderDataset +
        native decode, SSLResNet50, MarginSampler, budget 2000.

    The model precision is whatever the production path resolves
    ("auto" => bf16 on TPU), NOT a bench-only override — this phase
    exists to measure the loop users actually run."""
    import shutil
    import tempfile

    import jax
    from active_learning_tpu.config import ExperimentConfig
    from active_learning_tpu.experiment.arg_pools import get_train_config
    from active_learning_tpu.experiment.driver import run_experiment
    from active_learning_tpu.utils.metrics import MetricsSink

    class CaptureSink(MetricsSink):
        def __init__(self):
            self.metrics = []  # (name, value, step)

        def log_parameters(self, params):
            pass

        def log_metrics(self, metrics, step=None):
            for k, v in metrics.items():
                self.metrics.append((k, float(v), step))

        def log_asset(self, name, data):
            pass

    # Smoke scale (CI / CPU): shrunk so the phase's full code path —
    # driver, sink capture, both dataset kinds — runs on a single CPU
    # core.  ImageNet smoke is far smaller than CIFAR smoke because every
    # forward is ResNet-50 at 224px (~3-5 img/s on one core).
    smoke = os.environ.get("AL_BENCH_ROUND_SMOKE") == "1"
    if smoke:
        pool_n, test_n = (2000, 500) if config == "cifar" else (320, 96)
    else:
        pool_n, test_n = 50000, 10000
    if config == "cifar":
        from active_learning_tpu.data.synthetic import get_data_synthetic
        data = get_data_synthetic(n_train=pool_n, n_test=test_n)
        train_cfg = get_train_config("default", "cifar10")
        dataset, model_name = "cifar10", "SSLResNet18"
        budget = 40 if smoke else 1000
    else:
        from active_learning_tpu.data.core import IMAGENET_NORM, ViewSpec
        from active_learning_tpu.data.imagenet import ImageFolderDataset
        root = os.path.join(tempfile.gettempdir(), "al_tpu_datapath")
        _ensure_jpeg_tree(root, pool_n)
        train_view = ViewSpec(IMAGENET_NORM, augment=True, pad=0)
        val_view = ViewSpec(IMAGENET_NORM, augment=False)
        train_set = ImageFolderDataset(root, train_view, True, limit=pool_n)
        al_set = ImageFolderDataset(root, val_view, False, limit=pool_n)
        test_set = ImageFolderDataset(root, val_view, False,
                                      limit=min(5000, test_n))
        data = (train_set, test_set, al_set)
        train_cfg = get_train_config("default", "imagenet")
        dataset, model_name = "imagenet", "SSLResNet50"
        budget = 16 if smoke else 2000

    tmp = tempfile.mkdtemp(prefix="al_bench_round_")
    sink = CaptureSink()
    # The decoded-pool cache lives inside this phase's tmp dir (deleted on
    # exit): round 0 must pay real JPEG decode every bench invocation —
    # the driver's persistent default dir would make later runs' "cold"
    # round silently warm.
    import dataclasses
    train_cfg = dataclasses.replace(
        train_cfg, decoded_cache_dir=os.path.join(tmp, "decoded"))
    device_kind = jax.devices()[0].device_kind
    n_chips = len(jax.devices())
    # The pipelined round (DESIGN.md §8) needs a WARM arming round to
    # measure: the last round never arms (no next query to speculate
    # for), so a 2-round run only overlaps inside the cold compile-laden
    # round 0.  Where --round_pipeline auto resolves speculative
    # (single-process multi-device), run 3 rounds: round 1 is THE warm
    # pipelined round — it consumes round 0's speculation, arms round
    # 2's, and its overlap_frac from the driver's own telemetry is the
    # phase's acceptance gate.
    pipelined = jax.process_count() == 1 and n_chips > 1
    n_rounds = 3 if pipelined else 2
    cfg = ExperimentConfig(
        dataset=dataset, strategy="MarginSampler", rounds=n_rounds,
        round_budget=budget, init_pool_size=0, model=model_name,
        n_epoch=epochs, early_stop_patience=epochs, enable_metrics=True,
        log_dir=tmp, ckpt_path=tmp, exp_hash="bench")
    # The production driver enables the persistent XLA compilation cache
    # (experiment/driver.py:enable_compilation_cache): whether its
    # directory already holds entries decides if this run's "cold"
    # round 0 pays real compiles or warm disk hits — recorded so the
    # cold-warm compile-tax gap is attributable across bench rounds.
    # The directory (and whether there is one at all: the default is
    # off on CPU) comes from the driver's one rule, so a CPU smoke run
    # with a leftover non-empty dir is not misreported as cache-warm
    # while the child actually ran uncached.
    from active_learning_tpu.experiment.driver import (
        resolve_compilation_cache_dir)
    xla_cache_dir = resolve_compilation_cache_dir(cfg.compilation_cache_dir)
    cache_enabled = xla_cache_dir is not None
    cache_prewarmed = bool(cache_enabled and os.path.isdir(xla_cache_dir)
                           and os.listdir(xla_cache_dir))
    log(f"[al_round_{config}] {model_name} x{n_chips} {device_kind}, "
        f"budget {budget}, {epochs} epochs, 2 rounds "
        f"(compile cache {'warm' if cache_prewarmed else 'cold'})")
    t0 = time.perf_counter()
    try:
        strategy = run_experiment(cfg, sink=sink, data=data,
                                  train_cfg=train_cfg)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    total_sec = time.perf_counter() - t0
    # Residency attribution: whether the pool actually pinned in HBM
    # (auto-sized budget) or the query streamed through the async
    # double-buffered prefetch fallback — the phase's query_time is
    # meaningless without knowing which feed path produced it.
    pinned = len((strategy.trainer.resident_pool or {}).get("images", {}))
    residency = {
        "mode": "resident" if pinned else "prefetch",
        "pinned_arrays": pinned,
        "resident_budget_bytes": int(strategy.trainer.resident_budget),
        "budget_source": ("auto"
                          if train_cfg.resident_scoring_bytes is None
                          else "explicit"),
    }

    def phase_sec(name, rd):
        for k, v, step in sink.metrics:
            if k == f"rd_{name}" and step == rd:
                return round(v, 2)
        return None

    def step_pct(name):
        # The driver's per-epoch telemetry (trainer._emit_epoch_telemetry)
        # on the WARM round only: its step axis is round*(epochs+1)+epoch,
        # so round 1 is strictly past epochs+1.  Median over the round's
        # epochs — one number per phase for the bench line.
        vals = sorted(v for k, v, s in sink.metrics
                      if k == name and s is not None and s > epochs + 1)
        return round(vals[len(vals) // 2], 3) if vals else None

    names = ("query_time", "init_network_weights_time", "train_time",
             "load_best_ckpt_time", "test_time")
    rounds = {
        f"round{rd}": {n: phase_sec(n, rd) for n in names}
        for rd in range(n_rounds)
    }
    warm = sum(v for v in rounds["round1"].values() if v)
    cold = sum(v for v in rounds["round0"].values() if v)
    # Warm-round training throughput: round 1 trains on 2*budget labeled
    # rows for `epochs` epochs (init_pool_size=0: round 0 labeled the
    # first `budget`).
    # A missing round-1 train time yields ips None, never NaN: json would
    # serialize NaN as a non-standard token strict parsers reject.
    train_sec = rounds["round1"]["train_time"]
    ips = (2 * budget * epochs / train_sec) if train_sec else None
    test_acc = next((v for k, v, s in sink.metrics
                     if k == "rd_test_accuracy" and s == 1), None)

    def round_metric(name, rd):
        return next((v for k, v, s in sink.metrics
                     if k == name and s == rd), None)

    def run_total(name):
        # The driver emits the failure-model counters CUMULATIVELY at
        # each round boundary: the run total is the largest value seen.
        vals = [v for k, v, s in sink.metrics if k == name]
        return max(vals) if vals else None

    # The pipelined round's proof-of-overlap numbers, from the DRIVER'S
    # own telemetry stream (experiment/driver._emit_overlap_telemetry —
    # bench never times the loop a second time): the warm arming round's
    # overlap_frac is 1 − round_wall / (Σ phase walls + speculative-
    # scorer busy), and round_vs_max_phase is round_wall / max(stream) —
    # 1.0 would mean the round costs exactly its longest stream.
    # Keyed off the driver's ACTUAL resolution (strategy.pipeline), not
    # the n_rounds prediction above: if the auto rule ever drifts from
    # the prediction, the worst case is a missing overlap field — never
    # a spurious gate failure.
    pipeline_mode = ("speculative" if strategy.pipeline is not None
                     else "off")
    warm_rd = 1 if (pipeline_mode == "speculative"
                    and n_rounds >= 3) else None
    overlap = (round_metric("overlap_frac", warm_rd)
               if warm_rd is not None else None)
    vs_max = (round_metric("round_vs_max_phase", warm_rd)
              if warm_rd is not None else None)
    spec_hit = (round_metric("spec_hit_frac", warm_rd)
                if warm_rd is not None else None)
    if warm_rd is not None and not smoke and n_chips >= 2:
        # The acceptance gate (ISSUE 7): a warm pipelined round must
        # complete in <= 0.85x its serial-equivalent wall — which is
        # exactly overlap_frac >= 0.15.  Smoke scale is exempt (the
        # tiny fit ends before the scorer can overlap anything).
        assert overlap is not None and overlap >= 0.15, (
            f"warm pipelined round overlapped only "
            f"{overlap if overlap is not None else 'nothing'} of its "
            f"serial-equivalent work on {n_chips} devices (need >= 0.15 "
            f"== round <= 0.85x sequential)")
    return {
        "phase": f"al_round_{config}",
        "ips": round(ips, 1) if ips is not None else None,
        "ips_per_chip": (round(ips / n_chips, 1) if ips is not None
                         else None),
        "unit": "train images/sec (in-loop)",
        "n_chips": n_chips,
        "budget": budget,
        "epochs": epochs,
        "pool_n": pool_n,
        "round_sec_warm": round(warm, 2),
        "round_sec_cold": round(cold, 2),
        # The per-run compile tax: everything round 0 pays that round 1
        # does not (XLA compiles dominate it).  The persistent compile
        # cache + shape bucketing exist to shrink this gap.
        "compile_tax_sec": round(cold - warm, 2),
        "compile_cache_enabled": cache_enabled,
        "compile_cache_prewarmed": cache_prewarmed,
        # Warm-round step-time percentiles from the driver's own
        # per-epoch telemetry stream (the run-wide telemetry subsystem
        # measuring a real driver loop, not a bench-only timer).
        "step_time_ms_p50": step_pct("step_time_ms_p50"),
        "step_time_ms_p99": step_pct("step_time_ms_p99"),
        # Which leg of the train-feed hierarchy the production fit
        # resolved (trainer.last_feed), and the warm-round median
        # fraction of each epoch's train wall spent blocked on the host
        # feed — "done" for the feed work is feed_stall_frac <= 0.1 with
        # the resident feed on live hardware.
        "feed_source": strategy.trainer.last_feed.get("source"),
        "feed_stall_frac": step_pct("feed_stall_frac"),
        "host_wait_ms_p50": step_pct("host_wait_ms_p50"),
        # The pipelined round (DESIGN.md §8): which mode the driver
        # resolved, and the warm arming round's overlap evidence (None
        # when the mesh runs sequential — nothing was overlapped).
        "round_pipeline": pipeline_mode,
        "overlap_frac": overlap,
        "round_vs_max_phase": vs_max,
        "spec_hit_frac": spec_hit,
        # The experiment-truth rider (DESIGN.md §13): round 1's
        # score-distribution drift vs round 0 from the driver's own
        # diagnostics stream — an end-to-end round capture now records
        # whether the acquisition distribution moved while it was being
        # timed (None when diagnostics were off or round 0 never
        # scored).
        "rd_score_drift_psi": round_metric("rd_score_drift_psi", 1),
        "rd_score_drift_js": round_metric("rd_score_drift_js", 1),
        # The failure model's self-healing counters (DESIGN.md §10),
        # from the same driver stream: site-level retries absorbed and
        # degradation-ladder escalations taken during the measured
        # rounds — an end-to-end wall-clock claim is dishonest if the
        # run quietly self-healed mid-measurement.
        "fault_retries_total": run_total("fault_retries_total"),
        "degrade_events": run_total("degrade_events"),
        # The pod-tier column-feed rider (DESIGN.md §15): whether the
        # measured rounds' k-center scans fed their initial-min/minimax
        # columns over the ring-permute feed (the row-sharded backend's
        # only column feed) — None when the strategy never ran a
        # k-center selection.
        "ring_feed": _last_ring_feed(),
        "total_sec": round(total_sec, 1),
        "residency": residency,
        **_model_config_fields(strategy.model),
        "phases_sec": rounds,
        "test_accuracy_rd1": test_acc,
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
    }


def run_disk_pool_feed_phase(epochs: int) -> dict:
    """The disk tier measured (DESIGN.md §16): the SAME 2-round AL
    experiment through the production driver twice — once on the
    in-memory pool backend, once on the demand-paged disk backend with
    the pool held at >= 4x both residency budgets (HBM pin AND host
    block cache) — asserting the backends pick the SAME rows and land
    the SAME accuracy (the tier's bit-identity contract), and recording
    what the paging actually cost: the disk leg's in-loop train rate,
    its warm-round block-cache hit fraction, page-in throughput, and
    the gather-observed stall percentiles, all from the driver's own
    PAGING_GAUGES telemetry stream (bench never times the pager
    itself).

    The pool is the CIFAR protocol shape (synthetic, so the phase is
    data-path-pure): 50k rows at 32px f32 = ~614 MB, budgets capped at
    a quarter of that.  Absolute RAM is modest — the phase's subject is
    the PAGING MACHINERY at a pinned pool:budget ratio, not exhausting
    this host's DIMMs."""
    import dataclasses
    import shutil
    import tempfile

    import jax
    import numpy as np
    from active_learning_tpu.config import ExperimentConfig
    from active_learning_tpu.data.synthetic import get_data_synthetic
    from active_learning_tpu.experiment.arg_pools import get_train_config
    from active_learning_tpu.experiment.driver import run_experiment
    from active_learning_tpu.utils.metrics import MetricsSink

    class CaptureSink(MetricsSink):
        def __init__(self):
            self.metrics = []  # (name, value, step)

        def log_parameters(self, params):
            pass

        def log_metrics(self, metrics, step=None):
            for k, v in metrics.items():
                self.metrics.append((k, float(v), step))

        def log_asset(self, name, data):
            pass

    smoke = os.environ.get("AL_BENCH_ROUND_SMOKE") == "1"
    if smoke:
        pool_n, test_n, budget, page_rows = 2000, 500, 40, 256
    else:
        pool_n, test_n, budget, page_rows = 50000, 10000, 1000, 2048
    pool_bytes = pool_n * 32 * 32 * 3 * 4  # f32 rows, CIFAR shape
    # BOTH residency tiers capped at a quarter of the pool: the HBM pin
    # (resident_scoring_bytes) and the host block cache — a disk leg
    # that could cache the whole pool would measure the memory backend
    # with extra steps.
    budget_bytes = pool_bytes // 4
    train_cfg = dataclasses.replace(
        get_train_config("default", "cifar10"),
        resident_scoring_bytes=budget_bytes,
        pool_host_cache_bytes=budget_bytes,
        pool_page_rows=page_rows)
    device_kind = jax.devices()[0].device_kind
    n_chips = len(jax.devices())
    log(f"[disk_pool_feed] {n_chips}x {device_kind}, pool {pool_n} rows "
        f"({pool_bytes / 1e6:.0f} MB) at 4.0x the "
        f"{budget_bytes / 1e6:.0f} MB residency budget, budget {budget}, "
        f"{epochs} epochs, 2 rounds per leg")

    def leg(backend):
        # Fresh data per leg from the SAME seed: bit-identity must hold
        # over identical inputs, and the driver absorbs labels into the
        # datasets it is handed.
        data = get_data_synthetic(n_train=pool_n, n_test=test_n)
        tmp = tempfile.mkdtemp(prefix=f"al_bench_diskfeed_{backend}_")
        sink = CaptureSink()
        cfg = ExperimentConfig(
            dataset="cifar10", strategy="MarginSampler", rounds=2,
            round_budget=budget, init_pool_size=0, model="SSLResNet18",
            n_epoch=epochs, early_stop_patience=epochs,
            enable_metrics=True, run_seed=17, pool_backend=backend,
            log_dir=tmp, ckpt_path=tmp, exp_hash="bench")
        t0 = time.perf_counter()
        try:
            strategy = run_experiment(cfg, sink=sink, data=data,
                                      train_cfg=train_cfg)
            return {
                "backend": backend,
                "labeled": np.array(strategy.pool.labeled, copy=True),
                "acc": strategy.last_test_acc,
                "sink": sink,
                "al_set_kind": type(strategy.al_set).__name__,
                "total_sec": time.perf_counter() - t0,
            }
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    mem = leg("memory")
    disk = leg("disk")

    def gauge(run, name, rd):
        return next((v for k, v, s in run["sink"].metrics
                     if k == name and s == rd), None)

    # The tier's whole contract, asserted where the numbers are minted:
    # a disk-leg rate for DIFFERENT picks would be a benchmark of a
    # different experiment.
    assert disk["al_set_kind"] == "DiskPool", (
        f"--pool_backend disk resolved to {disk['al_set_kind']} — the "
        "leg never left host memory, so there is nothing to measure")
    assert np.array_equal(mem["labeled"], disk["labeled"]), (
        "disk backend picked different rows than memory — the paging "
        "tier broke bit-identity (DESIGN.md §16)")
    assert mem["acc"] == disk["acc"], (
        f"accuracy diverged across backends: memory {mem['acc']} vs "
        f"disk {disk['acc']} over identical picks")
    disk_rows = gauge(disk, "pool_disk_rows", 1)
    assert disk_rows, ("the disk leg emitted no paging telemetry — "
                       "PAGING_GAUGES never saw a disk-backed round")

    def ips_of(run):
        # Round 1 trains on 2*budget labeled rows (init_pool_size=0).
        train_sec = gauge(run, "rd_train_time", 1)
        return (2 * budget * epochs / train_sec) if train_sec else None

    ips, ips_mem = ips_of(disk), ips_of(mem)
    return {
        "phase": "disk_pool_feed",
        "ips": round(ips, 1) if ips is not None else None,
        "ips_per_chip": (round(ips / n_chips, 1) if ips is not None
                         else None),
        "unit": "train images/sec (disk-backed pool)",
        "n_chips": n_chips,
        "pool_n": pool_n,
        "budget": budget,
        "epochs": epochs,
        "pool_bytes": pool_bytes,
        "resident_budget_bytes": budget_bytes,
        "pool_over_budget_x": round(pool_bytes / budget_bytes, 1),
        # The paging tax, directly: the same fit on the same picks under
        # the in-memory backend — vs_mem < 1 is what the disk tier costs.
        "ips_memory": (round(ips_mem, 1) if ips_mem is not None
                       else None),
        "disk_vs_memory": (round(ips / ips_mem, 3)
                           if ips and ips_mem else None),
        # Warm-round paging evidence from the driver's PAGING_GAUGES.
        "cache_hit_frac": gauge(disk, "pool_cache_hit_frac", 1),
        "page_in_rows_per_sec": gauge(disk, "page_in_rows_per_sec", 1),
        "page_stall_ms_p50": gauge(disk, "page_in_stall_ms_p50", 1),
        "page_stall_ms_p99": gauge(disk, "page_in_stall_ms_p99", 1),
        "pool_disk_rows": disk_rows,
        "picks_identical": True,  # asserted above; recorded as evidence
        "test_accuracy_rd1": gauge(disk, "rd_test_accuracy", 1),
        "total_sec": round(mem["total_sec"] + disk["total_sec"], 1),
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
    }


def run_fleet_smoke_phase(rounds: int) -> dict:
    """The fleet tier end to end at bench scale (DESIGN.md §17): a
    2-run sweep (Margin vs Random) on two localhost worker slots
    through the REAL controller — spec expansion, journal, packing,
    health polling, the CLI child launch path — with one child
    SIGKILL'd after its round-0 checkpoint.  The controller must
    re-queue it with ``--resume_training`` and the fleet must finish
    with every run accounted; the phase records the resume/preemption
    counters and the merged-scrape coverage as evidence.  The children
    are the tests/fleet_child.py harness (the production driver behind
    the production CLI flags, at TinyClassifier/synthetic-pool size) on
    the CPU backend — the controller never touches an accelerator
    (al_lint fleet-host-pure), so the scheduling claim is
    backend-independent and this phase never competes for the
    accelerator."""
    import shutil
    import tempfile
    import threading

    from active_learning_tpu.fleet import (FLEET_JOURNAL_FILE,
                                           FleetController, Worker,
                                           read_fleet_journal)
    from active_learning_tpu.fleet import report as fleet_report
    from active_learning_tpu.telemetry import heartbeat as hb_lib

    child = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tests", "fleet_child.py")
    rounds = max(2, int(rounds))  # the kill waits for a round-0 ckpt
    spec = {
        "name": "bench_fleet_smoke",
        "defaults": {
            "dataset": "synthetic", "arg_pool": "synthetic",
            "rounds": rounds, "round_budget": 8, "n_epoch": 3,
            "early_stop_patience": 3, "round_pipeline": "speculative",
            "heartbeat_every_s": 0.0, "run_seed": 0,
        },
        "grid": {"strategy": ["MarginSampler", "RandomSampler"]},
    }
    fleet_dir = tempfile.mkdtemp(prefix="al_bench_fleet_")
    cpu_env = {"JAX_PLATFORMS": "cpu"}
    ctrl = FleetController(
        fleet_dir, spec,
        [Worker("w0", env=cpu_env), Worker("w1", env=cpu_env)],
        base_cmd=[sys.executable, child], poll_every_s=0.2)
    log(f"[fleet_smoke] 2 runs x {rounds} rounds on 2 workers "
        f"(children: {os.path.basename(child)})")
    t0 = time.perf_counter()
    thread = threading.Thread(target=ctrl.run, daemon=True)
    thread.start()
    # Preempt one worker the moment its run has a checkpoint to resume
    # from: heartbeat round >= 1 means round 0 committed.
    journal_path = os.path.join(fleet_dir, FLEET_JOURNAL_FILE)
    killed = None
    deadline = time.monotonic() + 420
    while killed is None and thread.is_alive() \
            and time.monotonic() < deadline:
        journal = read_fleet_journal(journal_path) or {}
        for rid, rec in (journal.get("runs") or {}).items():
            if rec.get("state") != "running" or not rec.get("pid"):
                continue
            hb = hb_lib.read_heartbeat(os.path.join(
                fleet_dir, "runs", rid, "logs", "heartbeat.json")) or {}
            if (hb.get("round") or 0) >= 1 and hb.get("status") == "running":
                try:
                    os.kill(rec["pid"], signal.SIGKILL)
                except OSError:
                    continue
                killed = rid
                log(f"[fleet_smoke] SIGKILL'd {rid} (pid {rec['pid']}) "
                    f"at round {hb.get('round')}")
                break
        time.sleep(0.05)
    thread.join(timeout=480)
    total_sec = time.perf_counter() - t0
    if thread.is_alive():
        ctrl.stop()
        thread.join(timeout=60)
        raise RuntimeError("fleet_smoke: controller never converged")
    if killed is None:
        raise RuntimeError("fleet_smoke: no run ever reached round 1 — "
                           "the preemption was never injected")
    counts = ctrl.counts()
    resumes = sum(r["resumes"] for r in ctrl.runs.values())
    attempts = sum(r["attempts"] for r in ctrl.runs.values())
    if counts["finished"] != 2:
        raise RuntimeError(f"fleet_smoke: fleet ended {counts}")
    if resumes < 1:
        raise RuntimeError("fleet_smoke: the SIGKILL'd run was not "
                           "resumed from its checkpoint")
    _, merged = fleet_report.merge_prom(fleet_dir)
    payload = fleet_report.fleet_payload(fleet_dir)
    shutil.rmtree(fleet_dir, ignore_errors=True)
    return {
        "phase": "fleet_smoke",
        # Headline: fleet throughput (a scheduling rate, not a device
        # rate — the controller is host-pure).
        "ips": round(60.0 * counts["finished"] / total_sec, 2),
        "ips_per_chip": round(60.0 * counts["finished"] / total_sec, 2),
        "unit": "runs finished/min (2-worker localhost fleet)",
        "runs_finished": counts["finished"],
        "runs_failed": counts["failed"],
        "runs_resumed": resumes,
        "attempts_total": attempts,
        "killed_run": killed,
        "merged_prom_runs": merged,
        "comparison_rendered": payload.get("comparison") is not None,
        "total_sec": round(total_sec, 1),
        "workers": 2,
    }


def _phase_setup(config: str, batch_size: int):
    """Shared model/trainer/batch construction for the timing child and
    the CPU FLOPs child: the batch schema and step signatures live in ONE
    place so the two paths cannot drift.  ``batch_size`` is the GLOBAL
    batch over the current backend's mesh."""
    import numpy as np

    import jax
    from active_learning_tpu.config import LoaderConfig, TrainConfig
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.train.trainer import Trainer

    mesh = mesh_lib.make_mesh(-1)
    model, px, n_classes, train_view, score_view = _model_and_views(config)
    cfg = TrainConfig(loader_tr=LoaderConfig(batch_size=batch_size))
    trainer = Trainer(model, cfg, mesh, num_classes=n_classes, train_bn=True)
    rng = np.random.default_rng(0)
    host_batch = {
        "image": rng.integers(0, 256, size=(batch_size, px, px, 3),
                              dtype=np.uint8),
        "label": rng.integers(0, n_classes,
                              size=batch_size).astype(np.int32),
        "index": np.arange(batch_size, dtype=np.int32),
        "mask": np.ones(batch_size, dtype=np.float32),
    }
    batch = mesh_lib.shard_batch(host_batch, mesh)
    state = trainer.init_state(jax.random.PRNGKey(0),
                               host_batch["image"][:min(8, batch_size)])
    return (mesh, model, n_classes, train_view, score_view, trainer, batch,
            state)


def run_flops_cpu(phase: str, batch_size: int) -> dict:
    """Per-image FLOPs of a phase's step, lowered on the CPU backend.

    Where the on-device ``cost_analysis`` enrichment timed or errored
    out: the FLOP count is a property of the computation, not the device —
    lowering the identical step on CPU (run with JAX_PLATFORMS=cpu) gives
    the same number, and the parent combines it with the TPU-measured
    images/sec to report achieved TFLOP/s and MFU."""
    import jax
    import jax.numpy as jnp

    config, kind = phase.rsplit("_", 1)
    (mesh, model, n_classes, train_view, score_view, trainer, batch,
     state) = _phase_setup(config, batch_size)
    if kind == "train":
        flops = _flops_per_step(
            trainer._train_step, phase, state, batch, jax.random.PRNGKey(1),
            jnp.float32(0.1), jnp.ones(n_classes, jnp.float32),
            view=train_view)
    else:
        from active_learning_tpu.strategies import scoring
        sstep = scoring.make_prob_stats_step(model, score_view)
        flops = _flops_per_step(sstep, phase,
                                state.variables,
                                {"image": batch["image"],
                                 "mask": batch["mask"]})
    n_local = int(mesh.devices.size)
    return {"phase": phase, "flops_source": "cpu-lowering",
            # cost_analysis reports the per-device partitioned module, so
            # divide by the rows one device saw.
            "flops_per_image": (flops * n_local / batch_size
                                if flops else None)}


def _flops_per_step(jitted, phase: str, *args, **kwargs):
    """Per-device flops of one step via AOT lower/compile.  This is a
    SECOND full XLA compile (it does not reuse the jit cache), so callers
    emit their timing result BEFORE calling this — a backend that dies or
    crawls inside the optional compile must not take a completed
    measurement down with it."""
    try:
        cost = jitted.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return float(cost.get("flops", 0.0)) or None
    except Exception as e:
        log(f"[{phase}] cost analysis unavailable: {e!r}")
        return None


def _time_loop(step_once, sync, iters: int, warmup: int = 3,
               step_times=None) -> float:
    """The ONE timing discipline for every measured step — primary and
    alt-batch, train and score: ``warmup`` untimed iterations, a
    data-dependent host fetch (``sync``) so the device really finished,
    then ``iters`` timed iterations closed by the same fetch
    (block_until_ready can return early on remote-execution backends;
    host fetches cannot).  ``step_times`` (a list) collects the per-
    iteration host deltas for the step-time percentiles — see
    _step_percentiles for when those deltas are trustworthy."""
    for _ in range(warmup):
        step_once()
    sync()
    t0 = time.perf_counter()
    prev = t0
    for _ in range(iters):
        step_once()
        if step_times is not None:
            now = time.perf_counter()
            step_times.append(now - prev)
            prev = now
    sync()
    return time.perf_counter() - t0


def _pctile(vals, q: float):
    """Nearest-rank percentile (the serve/metrics + telemetry
    convention, re-spelled here so the bench child stays importable
    without the package)."""
    if not vals:
        return None
    vals = sorted(vals)
    return float(vals[min(len(vals) - 1,
                          max(0, int(round(q * (len(vals) - 1)))))])


def _step_percentiles(result: dict, step_times, dt: float,
                      iters: int) -> None:
    """step_time_ms_p50/p99 onto a phase result.  Host-side per-
    iteration deltas are real step cadence only while the dispatch queue
    backpressures (donated buffers + data-dependent chaining do this in
    steady state); when the host ran far ahead (sum of deltas << the
    synced wall time — fully async backend), percentiles degrade to the
    loop average and say so in step_time_source."""
    if iters <= 0 or dt <= 0:
        return
    if step_times and sum(step_times) >= 0.8 * dt:
        result["step_time_ms_p50"] = round(
            _pctile(step_times, 0.50) * 1000, 3)
        result["step_time_ms_p99"] = round(
            _pctile(step_times, 0.99) * 1000, 3)
        result["step_time_source"] = "host-cadence"
    else:
        result["step_time_ms_p50"] = result["step_time_ms_p99"] = round(
            dt / iters * 1000, 3)
        result["step_time_source"] = "loop-average"


def _train_runner(trainer, batch, state, n_classes, view, seed: int):
    """(step_once, sync, holder) driving one train step per call with ONE
    dispatch per iteration — the PRODUCTION chained step (PRNG split
    folded into the jitted call, trainer._chained_train_step), so the
    bench measures exactly the dispatch pattern the host-batched fit
    loop runs.  The holder chains state/key so the final loss fetch is
    data-dependent on every step."""
    import jax
    import jax.numpy as jnp

    cw = jnp.ones(n_classes, jnp.float32)
    lr = jnp.float32(0.1)
    h = {"state": state, "key": jax.random.PRNGKey(seed), "loss": None}

    def step_once():
        h["state"], h["key"], h["loss"], h["gnorm"] = \
            trainer._chained_train_step(
                h["state"], batch, h["key"], lr, cw, view=view)

    return step_once, (lambda: float(h["loss"])), h


def _grad_path_fields(trainer, holder, batch, n_classes, view,
                      step_sec: float, iters: int) -> dict:
    """The backward-decomposition riders for a train phase (ISSUE 10):
    time a forward-only step and the fused optimizer update alone with
    the SAME timing discipline as the primary loop, and attribute the
    remainder of the measured step to the backward pass —
    ``bwd_frac`` — alongside ``opt_update_ms`` and the gradient-path
    flags (``optim_state_dtype``/``grad_allreduce``/``fused_optimizer``)
    so every train number is attributable to its gradient-path
    configuration.  Short loops (max(4, iters//4)): these are
    decomposition ratios, not headline rates."""
    import functools

    import jax
    import jax.numpy as jnp

    from active_learning_tpu.data.augment import apply_view
    from active_learning_tpu.train.trainer import weighted_cross_entropy

    model = trainer.model
    train_bn = trainer.train_bn
    cw = jnp.ones(n_classes, jnp.float32)
    sub_iters = max(4, iters // 4)
    variables = holder["state"].variables

    @jax.jit
    def fwd_once(variables, batch, key, carry):
        x = apply_view(batch["image"], view, key=key, train=True)
        if train_bn:
            logits, _ = model.apply(variables, x, train=True,
                                    mutable=["batch_stats"])
        else:
            logits = model.apply(variables, x, train=False)
        w = cw[batch["label"]] * batch["mask"]
        return carry + weighted_cross_entropy(logits, batch["label"], w)

    h = {"carry": jnp.float32(0.0), "k": jax.random.PRNGKey(7)}

    def fwd_step():
        h["k"], sub = jax.random.split(h["k"])
        h["carry"] = fwd_once(variables, batch, sub, h["carry"])

    fwd_dt = _time_loop(fwd_step, lambda: float(h["carry"]), sub_iters)
    fields = {
        "optim_state_dtype": getattr(trainer.cfg, "optim_state_dtype",
                                     "f32"),
        "grad_allreduce": trainer.grad_allreduce,
        "fused_optimizer": trainer.fused_tx is not None,
    }
    if trainer.grad_allreduce == "int8":
        # The pod-tier wire riders (DESIGN.md §15): WHICH quantized
        # wire the step synced over (allgather vs the reduce-scatter
        # form) and its per-device per-step wire model MB
        # (mesh_lib.wire_model_bytes — the same table the measured
        # collective_bytes_total cross-check in tests/test_pod_tier.py
        # pins against the optimized HLO).
        from active_learning_tpu.parallel import mesh as _mesh_lib
        form = getattr(trainer, "grad_sync_form", None) or "allgather"
        n_params = sum(int(p.size)
                       for p in jax.tree.leaves(variables["params"]))
        fields["grad_sync"] = form
        fields["grad_wire_mb"] = round(
            _mesh_lib.wire_model_bytes(form, trainer.n_devices,
                                       n_params) / 1e6, 2)
    # The optimizer-update loop times WHICHEVER path the measured step
    # ran — fused single-pass or the optax chain — so bwd_frac never
    # attributes optimizer time to the backward (a fused-on/off A/B
    # must show the win under opt_update_ms, not as a phantom
    # backward-pass change).
    import optax

    fused = trainer.fused_tx
    tx = trainer.tx

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def opt_once(params, opt_state, grads, lr):
        if fused is not None:
            return fused.update(grads, opt_state, params, lr)
        updates, new_state = tx.update(grads, opt_state, params)
        updates = jax.tree.map(lambda u: -lr * u, updates)
        return optax.apply_updates(params, updates), new_state

    params = jax.tree.map(jnp.copy, variables["params"])
    grads = jax.tree.map(lambda p: jnp.full(p.shape, 1e-4, p.dtype),
                         params)
    oh = {"p": params,
          "o": fused.init(params) if fused is not None
          else tx.init(params)}

    def opt_step():
        oh["p"], oh["o"] = opt_once(oh["p"], oh["o"], grads,
                                    jnp.float32(0.1))

    def opt_sync():
        return float(jax.tree.leaves(oh["p"])[0].reshape(-1)[0])

    opt_dt = _time_loop(opt_step, opt_sync, sub_iters)
    opt_sec = opt_dt / sub_iters
    fields["opt_update_ms"] = round(opt_sec * 1000.0, 3)
    fwd_sec = fwd_dt / sub_iters
    if step_sec > 0:
        fields["bwd_frac"] = round(
            max(0.0, (step_sec - fwd_sec - opt_sec) / step_sec), 3)
    return fields


def _score_runner(model, score_view, variables, batch):
    """(step_once, sync, sstep, sbatch) for the scoring pass.  A scalar is
    chained through every iteration INSIDE one jitted call so the final
    host fetch is data-dependent on all of them with exactly one dispatch
    per iteration — per-iteration eager ops (indexing + add) are each a
    dispatch of their own and can dwarf the compute being measured."""
    import jax
    import jax.numpy as jnp
    from active_learning_tpu.strategies import scoring

    sbatch = {"image": batch["image"], "mask": batch["mask"]}
    sstep = scoring.make_prob_stats_step(model, score_view)

    @jax.jit
    def chained(variables, batch, carry):
        return carry + sstep(variables, batch)["margin"][0]

    h = {"carry": jnp.float32(0.0)}

    def step_once():
        h["carry"] = chained(variables, sbatch, h["carry"])

    return step_once, (lambda: float(h["carry"])), sstep, sbatch


def run_child_phase(phase: str, iters: int, per_chip: int):
    """Yields the phase result dict, then — for train/score phases — the
    same result enriched with flops/MFU.  The caller prints each as its
    own JSON line and the parent keeps the LAST parseable one, so the
    enrichment compile is strictly best-effort."""
    import jax
    import jax.numpy as jnp

    if phase == "imagenet_datapath":
        yield from run_datapath_phase(iters * 1000, per_chip)
        return
    if phase == "imagenet_train_feed":
        yield from run_train_feed_phase(iters, per_chip)
        return
    if phase.startswith("al_round_"):
        yield run_al_round_phase(phase[len("al_round_"):], iters)
        return
    if phase == "kcenter_select":
        result, _picks = run_kcenter_phase(iters)
        yield result
        return
    if phase == "kcenter_select_130k":
        # Paper scale, production path (batched greedy + auto dispatch —
        # the backend chosen rides in "backend"); the forced-backend A/B
        # question is answered at 50k, so no second run here.
        result, _ = run_kcenter_phase(iters, pool_n=130000)
        result["phase"] = phase
        yield result
        return
    if phase == "kcenter_select_maxn":
        yield from run_kcenter_maxn_phase(iters)
        return
    if phase == "vaal_cotrain":
        yield run_vaal_phase(iters, per_chip)
        return
    if phase == "serve_throughput":
        yield run_serve_phase(iters, per_chip)
        return
    if phase == "stream_round":
        yield run_stream_phase(iters, per_chip)
        return
    if phase == "disk_pool_feed":
        yield run_disk_pool_feed_phase(iters)
        return
    if phase == "fleet_smoke":
        yield run_fleet_smoke_phase(iters)
        return
    config, kind = phase.rsplit("_", 1)
    n_chips = len(jax.devices())
    batch_size = per_chip * n_chips
    device_kind = jax.devices()[0].device_kind
    log(f"[{phase}] {n_chips}x {device_kind}, batch {batch_size} "
        f"({per_chip}/chip), {iters} iters")

    (mesh, model, n_classes, train_view, score_view, trainer, batch,
     state) = _phase_setup(config, batch_size)

    if kind == "train":
        step_once, sync, holder = _train_runner(trainer, batch, state,
                                                n_classes, train_view, 1)

        def flops_fn():
            return _flops_per_step(
                trainer._train_step, phase, holder["state"], batch,
                holder["key"], jnp.float32(0.1),
                jnp.ones(n_classes, jnp.float32), view=train_view)
    else:
        variables = state.variables
        step_once, sync, sstep, sbatch = _score_runner(
            model, score_view, variables, batch)

        def flops_fn():
            return _flops_per_step(sstep, phase, variables, sbatch)

    profile_dir = os.environ.get("AL_BENCH_PROFILE_DIR")
    device_truth = None
    if profile_dir:
        # XLA trace of the measured loop (VERDICT r3 #4, train AND score
        # MFU) through the gated capture API — telemetry/profiler.py is
        # the ONLY module allowed to touch jax.profiler (trace_lint
        # check 10).  Warmup runs outside the trace so the capture is
        # steady-state steps only.  Trace collection adds overhead to
        # the timed loop, so the result is tagged "profiled" and the
        # parent keeps it OUT of the cross-round cache.
        from active_learning_tpu.telemetry import profiler as prof_lib

        _time_loop(step_once, sync, 0, warmup=3)
        with prof_lib.capture_window(os.path.join(profile_dir, phase),
                                     label=phase) as cap:
            step_times = []
            dt = _time_loop(step_once, sync, iters, warmup=0,
                            step_times=step_times)
        log(f"[{phase}] profiler trace written to "
            f"{os.path.join(profile_dir, phase)}")
        try:
            # Device-truth riders on the profiled result (best-effort:
            # the capture is evidence, never a phase failure): what
            # share of the window the device was actually busy, and how
            # much of its op time was collectives.
            trace_path = prof_lib.find_trace_file(cap.out_dir)
            if trace_path:
                device_truth = prof_lib.summarize_capture(
                    prof_lib.parse_trace(trace_path), cap.window_s)
        except Exception as e:  # noqa: BLE001 - riders only
            log(f"[{phase}] device-truth summary unavailable: {e!r}")
    else:
        step_times = []
        dt = _time_loop(step_once, sync, iters, step_times=step_times)

    ips = batch_size * iters / dt
    result = {
        "phase": phase,
        "ips": round(ips, 1),
        "ips_per_chip": round(ips / n_chips, 1),
        "n_chips": n_chips,
        "batch_per_chip": per_chip,
        "iters": iters,
        "device_kind": device_kind,
        "platform": jax.devices()[0].platform,
        **_model_config_fields(model),
    }
    if kind == "train":
        # Feed attribution: the timed loop steps over ONE pre-sharded
        # HBM-resident batch — the feed is device-resident by
        # construction, and zero wall-clock in the loop is host-feed
        # stall.  The imagenet_train_feed phase is where the hierarchy's
        # legs are actually compared.
        result["feed_source"] = "resident"
        result["feed_stall_frac"] = 0.0
    _step_percentiles(result, step_times, dt, iters)
    if profile_dir:
        result["profiled"] = True  # trace overhead in dt: never cached
        if device_truth:
            for key in ("device_busy_frac", "collective_frac",
                        "transfer_frac", "collective_bytes_total"):
                if device_truth.get(key) is not None:
                    result[key] = device_truth[key]
    yield dict(result)  # the measurement is safe with the parent now

    if kind == "train":
        # Backward decomposition riders (best-effort AFTER the primary
        # number is safe): bwd_frac / opt_update_ms + the gradient-path
        # flags, from short fwd-only and optimizer-only loops under the
        # same timing discipline.
        try:
            result.update(_grad_path_fields(
                trainer, holder, batch, n_classes, train_view,
                dt / iters, iters))
            log(f"[{phase}] bwd_frac={result.get('bwd_frac')} "
                f"opt_update_ms={result.get('opt_update_ms')} "
                f"grad_allreduce={result.get('grad_allreduce')}")
            yield dict(result)
        except Exception as e:
            log(f"[{phase}] backward decomposition unavailable: {e!r}")

    if jax.devices()[0].platform == "tpu":
        # Batch-size lever for the MFU question (VERDICT r3 #4: train MFU
        # 32% vs 39% scoring, CIFAR scoring 26%): measure the same step at
        # 2x per-chip batch.  Kept separate from the primary number so the
        # series stays comparable across rounds.
        try:
            alt_pc = per_chip * 2
            (_m2, model2, n_cls2, tv2, sv2, trainer2, batch2,
             state2) = _phase_setup(config, alt_pc * n_chips)
            alt_iters = max(10, iters // 2)
            if kind == "train":
                alt_once, alt_sync, _h2 = _train_runner(
                    trainer2, batch2, state2, n_cls2, tv2, 2)
            else:
                alt_once, alt_sync, _s2, _b2 = _score_runner(
                    model2, sv2, state2.variables, batch2)
            alt_dt = _time_loop(alt_once, alt_sync, alt_iters)
            result["alt_batch_per_chip"] = alt_pc
            result["alt_ips_per_chip"] = round(
                alt_pc * alt_iters / alt_dt, 1)
            log(f"[{phase}] batch {alt_pc}/chip: "
                f"{result['alt_ips_per_chip']:,.0f} img/s/chip "
                f"(vs {result['ips_per_chip']:,.0f} at {per_chip})")
            yield dict(result)
        except Exception as e:
            log(f"[{phase}] alt-batch probe failed: {e!r}")

    flops_per_step = flops_fn()
    if flops_per_step:
        # cost_analysis on a jitted SPMD executable reports the PER-DEVICE
        # partitioned module's flops (verified empirically: an 8-way
        # sharded matmul reports 1/8 the single-device figure), so this is
        # per-chip achieved throughput and MFU divides by one chip's peak.
        # Same schema as the CPU-lowering back-fill: per-image flops +
        # flops_source.
        tflops_chip = flops_per_step * iters / dt / 1e12
        result["gflop_per_image"] = round(flops_per_step / per_chip / 1e9,
                                          2)
        result["tflops_per_sec_per_chip"] = round(tflops_chip, 1)
        result["flops_source"] = "device-cost-analysis"
        peak = _peak_tflops(device_kind)
        if peak:
            result["mfu"] = round(tflops_chip / peak, 3)
            result["peak_tflops_per_chip"] = peak
        yield result


# ---------------------------------------------------------------------------
# Parent: orchestrate phases in subprocesses; always print one JSON line.
# ---------------------------------------------------------------------------

def _parse_child_json(stdout: str, required=("ips", "ips_per_chip")):
    """Last stdout line that parses as a dict carrying all ``required``
    keys — stray JSON-ish lines from libraries must not masquerade as a
    phase result."""
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                result = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(result, dict) and all(k in result
                                                for k in required):
                return result
    return None


def _halve_iters(iters: int) -> int:
    """Retry iteration cut that can never INCREASE the work: the floor of
    10 exists for timing stability of per-step phases (iters >= 20), but
    the al_round phases count EPOCHS (2-4) — flooring those at 10 made a
    timed-out attempt's retry strictly longer than the attempt that
    already died (observed: al_round_imagenet 2 epochs -> retry at 10)."""
    return max(10, iters // 2) if iters > 10 else max(1, iters // 2)


def run_phase_with_retries(name: str, iters: int, per_chip: int,
                           timeout: float, deadline: float,
                           max_attempts: int = 2):
    """Capped retry ladder (default 2 attempts — a third attempt against a
    backend that already ate two timeouts is how round 3 burned its whole
    budget on one phase); iters halve per retry, batch halves on OOM.
    The datapath phase gets one extra attempt on the CPU backend: its
    headline metrics (decode imgs/sec, per-core rate) are host-side, so
    an unavailable accelerator must not erase them — the result is tagged
    with platform "cpu" by the child itself.
    Returns (result dict | None, failure string | None)."""
    failure = None
    # A partial snapshot from a child that OOM-crashed after printing a
    # completed measurement: kept as a fallback, but the halved-batch
    # retry still runs — the retry may recover the measurements the crash
    # cut short (warm/resident passes), and only if it also fails does
    # the snapshot become the answer.
    stashed = None
    attempts = max_attempts + 1 if name == "imagenet_datapath" else max_attempts
    for attempt in range(attempts):
        cpu_fallback = name == "imagenet_datapath" and attempt == attempts - 1
        remaining = deadline - time.monotonic()
        if remaining <= 30:
            if stashed is not None:
                return stashed, None
            return None, failure or "wall-clock budget exhausted"
        # Reserve ~90s of budget past any single attempt: a hung child
        # granted the full remainder would starve the cached-evidence
        # fallback, MFU back-fill, and the final emit (phase timeouts can
        # legitimately exceed the DEFAULT total budget — al_round_imagenet
        # at 1800s is sized for AL_BENCH_BUDGET_S-raised runs, and under
        # the default it degrades to whatever window this cap grants).
        attempt_timeout = min(timeout if attempt == 0 else timeout * 0.75,
                              max(60.0, remaining - 90.0), remaining)
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
               "--iters", str(iters), "--per-chip-batch", str(per_chip)]
        env = None
        if cpu_fallback:
            # Decode-only: the ResNet-50 scoring pass is pointless on one
            # CPU core and would blow the timeout; the host-side decode
            # rate is the number this fallback exists to save.
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       AL_BENCH_DATAPATH_DECODE_ONLY="1")
            log(f"[parent] {name}: accelerator attempts failed; measuring "
                "the host-side data path (decode only) on the CPU backend")
        log(f"[parent] {name} attempt {attempt + 1}: iters={iters} "
            f"batch/chip={per_chip} timeout={attempt_timeout:.0f}s")
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=attempt_timeout, env=env)
        except subprocess.TimeoutExpired as e:
            partial = e.stderr or ""
            if isinstance(partial, bytes):
                partial = partial.decode(errors="replace")
            sys.stderr.write(partial[-2000:])
            # The child prints each completed measurement as its own line
            # BEFORE the optional flops-enrichment compile — a timeout
            # inside the enrichment must not discard a finished number.
            out = e.stdout or ""
            if isinstance(out, bytes):
                out = out.decode(errors="replace")
            result = _parse_child_json(out)
            if result is not None:
                log(f"[parent] {name}: timed out during enrichment; "
                    "keeping the completed measurement")
                return result, None
            failure = f"timeout after {attempt_timeout:.0f}s"
            log(f"[parent] {name}: {failure}")
            if "RESOURCE_EXHAUSTED" in partial:
                per_chip = max(16, per_chip // 2)
            iters = _halve_iters(iters)
            continue
        sys.stderr.write(proc.stderr[-4000:])
        if proc.returncode == 0:
            result = _parse_child_json(proc.stdout)
            if result is not None:
                return result, None
            failure = "child emitted no JSON"
            continue
        # A child that printed a complete measurement and THEN died (e.g.
        # in a later optional pass) still produced evidence — same
        # discipline as the timeout path above.  Exception: an OOM death
        # (RESOURCE_EXHAUSTED) is recoverable by the batch-halving retry,
        # which may capture the measurements the crash cut short — stash
        # the snapshot and keep climbing the ladder instead of returning
        # a partial result as success.
        tail = (proc.stderr or "")[-2000:]
        result = _parse_child_json(proc.stdout)
        if result is not None:
            if "RESOURCE_EXHAUSTED" in tail and attempt < attempts - 1:
                log(f"[parent] {name}: child OOMed (exit "
                    f"{proc.returncode}) after a completed measurement; "
                    "stashing it and retrying at half batch")
                stashed = result
            else:
                log(f"[parent] {name}: child exited {proc.returncode} "
                    "after a completed measurement; keeping it")
                return result, None
        else:
            failure = f"exit {proc.returncode}: {tail.strip().splitlines()[-1] if tail.strip() else 'no stderr'}"
            log(f"[parent] {name}: {failure}")
        if "RESOURCE_EXHAUSTED" in tail:
            per_chip = max(16, per_chip // 2)
        elif "UNAVAILABLE" in tail or "DEADLINE_EXCEEDED" in tail \
                or "failed to initialize" in tail.lower():
            time.sleep(15)  # transient backend trouble; let it settle
        iters = _halve_iters(iters)
    if stashed is not None:
        log(f"[parent] {name}: retries failed; returning the stashed "
            "pre-OOM snapshot")
        return stashed, None
    return None, failure


# Mutable orchestration state shared with the signal handler: the final
# JSON can be assembled and printed at ANY moment.  ``run_id`` stamps
# this process's partial snapshots so crash recovery can never attribute
# a PREVIOUS run's numbers to this one.
_STATE: dict = {"start": None, "phases": {}, "failures": {}, "cache": {},
                "probe": None, "emitted": False, "run_id": None}


def _probe_health(timeout: float = 90.0) -> dict:
    """Health-probe the default backend in a subprocess BEFORE any long
    phase attempt: backend init + one tiny jitted matmul with a host
    fetch.  Returns {"ok", "seconds", "device_kind", "n_devices",
    "platform"} or {"ok": False, "error"}.  A backend that cannot be
    reached may hang inside the child, so the subprocess timeout IS the
    detection.  The probe is a CHILD because this parent must stay off
    JAX: a process that has touched JAX holds the chip."""
    code = (
        "import time; t0 = time.time()\n"
        "import jax, jax.numpy as jnp\n"
        "d = jax.devices()\n"
        "x = jnp.ones((512, 512), jnp.bfloat16)\n"
        "float((x @ x).sum())\n"
        "print('PROBE|%s|%d|%s|%.1f'\n"
        "      % (d[0].device_kind, len(d), d[0].platform,\n"
        "         time.time() - t0), flush=True)\n")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.SubprocessError as e:
        return {"ok": False,
                "error": f"probe {type(e).__name__} after {timeout:.0f}s"}
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("PROBE|"):
            _, kind, n, platform, secs = line.split("|")
            return {"ok": True, "device_kind": kind, "n_devices": int(n),
                    "platform": platform, "seconds": float(secs),
                    "probe_wall_sec": round(time.perf_counter() - t0, 1)}
    tail = (proc.stderr or "").strip().splitlines()
    return {"ok": False, "error": f"probe exit {proc.returncode}: "
                                  f"{tail[-1] if tail else 'no output'}"}


def _load_cache() -> dict:
    try:
        with open(CACHE_PATH) as fh:
            cache = json.load(fh)
        if not isinstance(cache, dict):
            return {}
        for entry in cache.values():
            # Pre-rename caches (<= PR 5) spell the resident warm rate
            # ips_warm_resident; migrate on load so the canonical
            # warm_resident_ips is the only spelling downstream — the
            # same one-spelling rule as warm_memmap_ips, without an
            # alias riding the evidence.
            if isinstance(entry, dict) and "ips_warm_resident" in entry:
                entry.setdefault("warm_resident_ips",
                                 entry.pop("ips_warm_resident"))
        return cache
    except (OSError, json.JSONDecodeError):
        return {}


def _save_cache(cache: dict) -> None:
    try:
        tmp = f"{CACHE_PATH}.tmp"
        with open(tmp, "w") as fh:
            json.dump(cache, fh, indent=1)
        os.replace(tmp, CACHE_PATH)
    except OSError as e:
        log(f"[parent] cache write failed: {e!r}")


def _finalize() -> dict:
    """Assemble the final output dict from _STATE at ANY moment: phases
    not (yet) freshly captured fall back to cache entries whose hardware
    matches the probed backend (unverifiable when the probe failed —
    marked, not dropped)."""
    phases = dict(_STATE["phases"])
    failures = dict(_STATE["failures"])
    cache = _STATE["cache"]
    probe = _STATE["probe"] or {}
    hw = ((probe.get("device_kind"), probe.get("n_devices"))
          if probe.get("ok") else None)
    configured_batch = {name: per_chip for name, _, per_chip, _ in PHASES}
    for name, _, _, _ in PHASES:
        if name in phases or name not in cache:
            continue
        entry = cache[name]
        if hw is not None and (entry.get("device_kind"),
                               entry.get("n_chips")) != hw:
            failures.setdefault(
                name, f"cached result is from {entry.get('device_kind')} "
                      f"x{entry.get('n_chips')}, live is {hw[0]} x{hw[1]}")
            continue
        if (entry.get("batch_per_chip") is not None
                and entry["batch_per_chip"] != configured_batch[name]):
            # A phase whose primary batch config changed (e.g.
            # resnet18_cifar_score 256 -> 512) must not have the OLD
            # config's capture silently billed as the new primary.
            failures.setdefault(
                name, f"cached result is at batch "
                      f"{entry['batch_per_chip']}/chip; the phase now "
                      f"captures {configured_batch[name]}/chip")
            continue
        phases[name] = dict(entry, cached=True,
                            fresh_failure=failures.pop(
                                name, "not attempted"))
        if hw is None:
            phases[name]["device_unverified"] = True
    for name, _, _, _ in PHASES:
        if name not in phases:
            # No fresh capture AND no cache: the phase must show up as an
            # explicit failure, not silently vanish from the evidence.
            # The cause names the backend only when the probe actually
            # failed — mid-run partials on a healthy backend just have
            # queued phases.
            cause = ("not attempted (backend unreachable)"
                     if _STATE["probe"] is not None and not probe.get("ok")
                     else "not attempted")
            failures.setdefault(name, f"{cause}; no cached entry")

    # Headline: the north-star model if captured, else the CIFAR model.
    headline = None
    for name in ("resnet50_imagenet_train", "resnet18_cifar_train",
                 "resnet50_imagenet_score", "resnet18_cifar_score",
                 "imagenet_datapath"):
        # A decode-only datapath result is a host decode rate, a profiled
        # run's timings carry trace overhead, and a malformed entry whose
        # rate is missing or non-finite (a NaN can ride in via a stale
        # cache file: json.load accepts the token) has no number to
        # headline — none may be it.
        if name in phases and not phases[name].get("decode_only") \
                and not phases[name].get("profiled") \
                and _finite(phases[name].get("ips_per_chip")):
            headline = name
            break

    out = {
        "metric": (f"{headline}_images_per_sec_per_chip" if headline
                   else "train_images_per_sec_per_chip"),
        "value": phases[headline].get("ips_per_chip") if headline else None,
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "phases": phases,
        "backend_probe": probe,
        "elapsed_sec": round(time.monotonic() - _STATE["start"], 1),
    }
    if headline:
        base = V100_BASELINE_IPS.get(headline)
        if base and out["value"] is not None:
            out["vs_baseline"] = round(out["value"] / base, 3)
        if phases[headline].get("cached"):
            out["headline_cached"] = True
    if failures:
        out["failed_phases"] = failures
    return out


def _dump_json_file(out: dict, path: str) -> bool:
    """Atomic, sanitized, never-raising evidence write: NaN/Inf become
    null (strict parsers must accept the file), and NO exception — OSError
    or a TypeError from an unserializable field — may escape to suppress
    the stdout line this write precedes.  Returns False on failure so the
    caller can avoid pointing the stdout line at a stale file."""
    try:
        tmp = f"{path}.tmp"
        with open(tmp, "w") as fh:
            json.dump(_sanitize(out), fh, indent=1, default=repr,
                      allow_nan=False)
        os.replace(tmp, path)
        return True
    except Exception as e:
        log(f"[parent] evidence write to {path} failed: {e!r}")
        return False


def _write_partial() -> None:
    """Persist the would-be-final JSON after every phase: a SIGKILL (which
    no handler can catch) still leaves the full evidence on disk."""
    try:
        out = dict(_finalize(), partial=True, run_id=_STATE["run_id"])
    except Exception as e:
        log(f"[parent] partial assembly failed: {e!r}")
        return
    _dump_json_file(out, PARTIAL_PATH)


def _sanitize(obj):
    """NaN/Inf never reach json.dumps: a missing round-1 train time once
    produced ips=NaN, whose non-standard `NaN` token strict parsers (the
    consuming harness) reject — the parsed=null failure mode again."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    return obj


def _compact_line(out: dict, evidence_ok: bool = True) -> str:
    """The ONE stdout line, guaranteed <= MAX_LINE_BYTES: headline triple
    + per-phase {ips, mfu, cached} + the evidence-file path.  Staged
    truncation (shorten failures -> names only -> ips only -> headline
    only) keeps the line parseable no matter what the full evidence
    holds.  ``evidence_ok=False`` (the write failed) nulls the path so a
    STALE previous file is never attributed to this run."""
    evidence = EVIDENCE_PATH if evidence_ok else None
    phases = {}
    for name, e in (out.get("phases") or {}).items():
        c = {"ips": e.get("ips_per_chip")}
        if e.get("mfu") is not None:
            c["mfu"] = e["mfu"]
        if e.get("unit") and "images/sec" not in str(e["unit"]):
            c["unit"] = e["unit"]
        if e.get("cached"):
            c["cached"] = True
        # The warm-round / warm-cache / backend / serving / feed /
        # pool-layout numbers are round-level headline evidence — small
        # enough to ride the line.  warm_memmap_ips is the ONLY spelling
        # of the datapath's steady-state rate (the deprecated ips_warm
        # fallback is gone with its shim).
        for src, dst in (("warm_memmap_ips", "warm_ips"),
                         ("round_sec_warm", "warm_s"),
                         ("round_sec_cold", "cold_s"),
                         ("compile_tax_sec", "tax_s"),
                         ("test_accuracy_rd1", "acc"),
                         ("qps_closed", "qps"),
                         ("p99_ms_closed", "p99_ms"),
                         ("request_path_compiles", "req_compiles"),
                         ("step_time_ms_p50", "step_time_ms_p50"),
                         ("step_time_ms_p99", "step_time_ms_p99"),
                         ("backend", "be"),
                         # The streaming phase's riders: the ack tail
                         # latency (the WAL-fsync bound clients feel)
                         # and which trigger fired the measured round —
                         # an ingest-rate claim is ambiguous without
                         # them.  The rest (qps, labels, pool growth)
                         # stays in the evidence file.
                         *((("ack_p99_ms", "ack_p99"),
                            ("trigger_cause", "trigger"))
                           if name == "stream_round" else ()),
                         # The disk tier's riders (ISSUE 16): the warm
                         # block-cache hit fraction and the page-in
                         # stall tail — a disk-backed train rate is
                         # ambiguous without knowing how often the
                         # gather actually touched disk and what the
                         # misses cost.  The finer figures (page-in
                         # rate, p50, the memory-leg comparison) stay
                         # in the evidence file.
                         *((("cache_hit_frac", "hit"),
                            ("page_stall_ms_p99", "stall_ms"))
                           if name == "disk_pool_feed" else ()),
                         # The fleet tier's riders (ISSUE 18): how many
                         # runs finished, how many came back from a
                         # preemption, and the fleet's wall — a
                         # scheduling-rate headline is ambiguous
                         # without them.  The rest (attempts, merged
                         # scrape coverage, the killed run's id) stays
                         # in the evidence file.
                         *((("runs_finished", "runs"),
                            ("runs_resumed", "resumed"),
                            ("total_sec", "wall_s"))
                           if name == "fleet_smoke" else ()),
                         # The resident-pool layout rides the line only
                         # where it is the phase's SUBJECT (the
                         # sharded-ceiling probe) — a row-sharded max-N
                         # is meaningless without the layout tag, but
                         # claiming it on every selection phase pushed
                         # the realistic-maximal line past the tail
                         # bound (same rule as feed_source below; the
                         # other phases keep it in the evidence file).
                         *((("pool_sharding", "pool_sharding"),
                            # The pod-tier column feed (ISSUE 15):
                            # whether the row scans fed their columns
                            # over the ring-permute feed — a row-layout
                            # max-N is ambiguous without it.
                            ("ring_feed", "ring"))
                           if name == "kcenter_select_maxn" else ()),
                         # Feed attribution rides the line only where it
                         # is the phase's subject (the hierarchy
                         # comparison and the end-to-end rounds) — the
                         # plain train phases' feed_source lives in the
                         # evidence file; putting it on 3 more phases
                         # pushed the realistic-maximal line past the
                         # tail bound.
                         *((("feed_source", "feed"),
                            ("feed_stall_frac", "stall"))
                           if name == "imagenet_train_feed"
                           or name.startswith("al_round") else ()),
                         # The pipelined round's mode + warm overlap
                         # ride only the end-to-end round phases (their
                         # SUBJECT since ISSUE 7); the full overlap
                         # breakdown stays in the evidence file.
                         # ... plus the failure model's counters
                         # (ISSUE 8): how many site-level retries the
                         # run absorbed and how many degradation-ladder
                         # escalations it took — an end-to-end round
                         # number is dishonest without knowing it
                         # self-healed.
                         *((("round_pipeline", "pipeline"),
                            ("overlap_frac", "overlap"),
                            ("fault_retries_total", "retries"),
                            ("degrade_events", "degraded"),
                            # The experiment-truth drift rider (ISSUE
                            # 13): a timed round's score-distribution
                            # shift rides the line; the JS twin stays
                            # in the evidence file.
                            ("rd_score_drift_psi", "drift"),
                            # The pod-tier column-feed rider (ISSUE
                            # 15): did the measured rounds' k-center
                            # scans run the ring feed (absent when the
                            # strategy never ran k-center).
                            ("ring_feed", "ring"))
                           if name.startswith("al_round") else ()),
                         # The gradient-path riders (ISSUE 10 + 15)
                         # ride only the TRAIN phases (their subject):
                         # the backward's share of the step, the sync
                         # precision the number was measured under,
                         # and — when quantized — WHICH wire form
                         # synced it and its per-step wire-model MB
                         # (allgather vs the pod-tier reduce-scatter).
                         # opt_update_ms stays in the evidence file.
                         *((("bwd_frac", "bwd_frac"),
                            ("grad_allreduce", "grad_ar"),
                            ("grad_sync", "grad_sync"))
                           if name.endswith("_train") else ())):
            if e.get(src) is not None and dst not in c:
                c[dst] = e[src]
        if name == "imagenet_train_feed":
            # The hierarchy comparison, positionally: [resident,
            # host_prefetch, host_serial] img/s (full spellings in the
            # evidence file) — the array form keeps the line bounded.
            legs = [e.get("ips_resident"), e.get("ips_host_prefetch"),
                    e.get("ips_host_serial")]
            if any(v is not None for v in legs):
                c["legs"] = legs
        if c.get("grad_sync"):
            # Line spelling of the wire form: "ag"/"rs" (the full
            # spelling + grad_wire_mb stay in the evidence file — the
            # same finer-figures rule as opt_update_ms).
            c["grad_sync"] = {"allgather": "ag",
                              "reduce_scatter": "rs"}.get(
                                  c["grad_sync"], c["grad_sync"])
        if isinstance(e.get("residency"), dict) and "feed" not in c:
            # feed_source subsumes the older scoring-residency tag on
            # the line (feed == "resident" implies the pool pinned);
            # the full residency dict stays in the evidence file.
            c["resid"] = e["residency"].get("mode")
        if e.get("s2d"):
            c["s2d"] = True
        phases[name] = c
    compact = {
        "metric": out.get("metric"), "value": out.get("value"),
        "unit": out.get("unit"), "vs_baseline": out.get("vs_baseline"),
        "phases": phases,
        "probe_ok": bool((out.get("backend_probe") or {}).get("ok")),
        "elapsed_sec": out.get("elapsed_sec"),
        "evidence": evidence,
    }
    if out.get("headline_cached"):
        compact["headline_cached"] = True
    for k in ("partial", "interrupted_by_signal", "error"):
        if out.get(k) is not None:
            compact[k] = (out[k][:120] if isinstance(out[k], str)
                          else out[k])
    failed = out.get("failed_phases") or {}
    if failed:
        compact["failed"] = {n: str(m)[:40] for n, m in failed.items()}

    def dumps(o):
        # Compact separators: the margin accounting at MAX_LINE_BYTES
        # counts spellings like '"ack_p99":NNN.NNN,' — json's default
        # ", "/": " separators were silently spending one tail byte per
        # key and comma (~150 bytes across the 15-phase rich form) that
        # the accounting never budgeted.
        return json.dumps(_sanitize(o), allow_nan=False,
                          separators=(",", ":"))

    line = dumps(compact)
    if len(line) > MAX_LINE_BYTES and failed:
        compact["failed"] = sorted(failed)
        line = dumps(compact)
    if len(line) > MAX_LINE_BYTES:
        compact["phases"] = {n: c.get("ips") for n, c in phases.items()}
        line = dumps(compact)
    if len(line) > MAX_LINE_BYTES:
        line = dumps({"metric": out.get("metric"), "value": out.get("value"),
                      "unit": out.get("unit"),
                      "vs_baseline": out.get("vs_baseline"),
                      "evidence": evidence})
    return line


def _emit_final(extra: dict = None) -> None:
    """Print THE one compact JSON line (exactly once, no matter how many
    paths race to it), after writing the FULL evidence to
    bench_evidence.json (+ the bench_partial.json mirror).  SIGTERM/
    SIGINT are masked for the duration: without the mask, a signal
    landing between flag-set and print would find 'emitted' already True
    in the handler and os._exit before the main thread's print runs —
    zero output, the exact rc=124/parsed=null failure this machinery
    exists to prevent.  A _finalize crash (e.g. a malformed cache entry)
    degrades to a minimal error line rather than suppressing output
    entirely."""
    old_mask = signal.pthread_sigmask(
        signal.SIG_BLOCK, {signal.SIGTERM, signal.SIGINT})
    try:
        if _STATE["emitted"]:
            return
        finalize_error = None
        try:
            out = _finalize()
            if extra:
                out.update(extra)
        except Exception as e:
            log(f"[parent] finalize failed: {e!r}")
            # The repr is truncated: an exception quoting a malformed
            # cache entry must not push THIS line past the bound either.
            finalize_error = f"finalize failed: {e!r}"[:300]
            out = {"metric": "train_images_per_sec_per_chip", "value": None,
                   "unit": "images/sec/chip", "vs_baseline": None,
                   "error": finalize_error}
            # The per-phase snapshot rewritten after every phase is the
            # best evidence still standing — attach the error to it
            # rather than clobbering it with the minimal dict.  The
            # run_id match keeps a PREVIOUS run's snapshot from being
            # attributed to this one.
            try:
                with open(PARTIAL_PATH) as fh:
                    prev = json.load(fh)
                if isinstance(prev, dict) and prev.get("phases") \
                        and prev.get("run_id") == _STATE["run_id"]:
                    out = dict(prev, error=finalize_error)
            except Exception:
                pass
        # Evidence first, line second: the line only names the file when
        # the write actually landed.  On the finalize-error path the
        # partial mirror is left alone — it may hold the last good
        # snapshot this error path just recovered.
        evidence_ok = _dump_json_file(out, EVIDENCE_PATH)
        if finalize_error is None:
            _dump_json_file(out, PARTIAL_PATH)
        try:
            line = _compact_line(out, evidence_ok=evidence_ok)
        except Exception as e:
            log(f"[parent] compact-line failed: {e!r}")
            line = json.dumps(_sanitize(
                {"metric": out.get("metric"), "value": out.get("value"),
                 "unit": out.get("unit"), "vs_baseline": None,
                 "error": f"compact failed: {e!r}"[:300],
                 "evidence": EVIDENCE_PATH if evidence_ok else None}),
                allow_nan=False)
        print(line, flush=True)
        _STATE["emitted"] = True
    finally:
        signal.pthread_sigmask(signal.SIG_SETMASK, old_mask)


def _signal_emit(signum, frame):
    """An outer `timeout`'s SIGTERM (or a ^C) becomes a parsed result: the
    round-3 harness recorded rc=124/parsed=null while a complete cache sat
    on disk — the line must go out BEFORE the process dies."""
    log(f"[parent] caught signal {signum}; emitting evidence now")
    _emit_final(extra={"interrupted_by_signal": signum})
    os._exit(0)


def main() -> None:
    _STATE["start"] = time.monotonic()
    _STATE["run_id"] = f"{os.getpid()}-{time.time_ns()}"
    _STATE["cache"] = _load_cache()
    signal.signal(signal.SIGTERM, _signal_emit)
    signal.signal(signal.SIGINT, _signal_emit)
    try:
        _main_inner()
        _emit_final()
    except Exception as e:  # the JSON line must appear no matter what
        log(f"[parent] fatal: {e!r}")
        _emit_final(extra={"error": repr(e)})


def _main_inner() -> None:
    deadline = _STATE["start"] + TOTAL_BUDGET_S
    cache = _STATE["cache"]
    phases: dict = _STATE["phases"]
    failures: dict = _STATE["failures"]

    probe = _probe_health()
    _STATE["probe"] = probe
    if not probe.get("ok"):
        log(f"[parent] backend probe failed ({probe.get('error')}); "
            "emitting cached evidence without fresh attempts")
        return
    log(f"[parent] backend healthy: {probe['device_kind']} "
        f"x{probe['n_devices']} ({probe['platform']}), probe "
        f"{probe['seconds']:.1f}s")
    degraded = probe["seconds"] > PROBE_DEGRADED_S
    if degraded:
        log(f"[parent] probe took {probe['seconds']:.0f}s — degraded "
            "backend: single attempts, fresh-only phases first")

    # Phases with no cache entry carry the only NEW evidence this run can
    # produce — capture them first so a mid-run death costs the least.
    order = sorted(PHASES, key=lambda p: (
        p[0] in cache, cache.get(p[0], {}).get("captured_utc", "")))
    for name, iters, per_chip, timeout in order:
        result, failure = run_phase_with_retries(
            name, iters, per_chip, timeout, deadline,
            max_attempts=1 if degraded else 2)
        if result is not None:
            result["captured_utc"] = time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime())
            phases[name] = result
            if not result.get("decode_only") and not result.get("profiled"):
                # A decode-only CPU fallback is a degraded capture, and a
                # profiled run's timings carry trace overhead; neither may
                # clobber a clean accelerator entry in the cache (the
                # cache exists to preserve those).
                cache[name] = result
                _save_cache(cache)
            if isinstance(result.get("ips"), (int, float)):
                log(f"[parent] {name}: {result['ips']:,.0f} img/s total, "
                    f"{result['ips_per_chip']:,.0f} img/s/chip")
            else:
                log(f"[parent] {name}: captured without a rate "
                    "(see phase entry)")
        else:
            failures[name] = failure
        _write_partial()

    # MFU back-fill: phases that timed or errored out of the on-device
    # flops enrichment get their FLOP count from an identical CPU
    # lowering (a property of the computation, not the device) combined
    # with the TPU-measured throughput.  Runs over fresh AND
    # cache-fallback entries, in a child held to the CPU.
    for name, entry in list(phases.items()) + [
            (n, cache[n]) for n, _, _, _ in PHASES
            if n in cache and n not in phases]:
        if not name.endswith(("_train", "_score")) or entry.get("mfu") \
                or not entry.get("ips_per_chip"):
            continue
        remaining = deadline - time.monotonic()
        if remaining <= 60:
            break
        # FLOPs scale linearly in batch, so lower a small batch (cheap CPU
        # compile) and let the child normalize per image.
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
               "--flops-cpu", "--per-chip-batch",
               str(min(32, entry.get("batch_per_chip", 128)))]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        log(f"[parent] {name}: computing FLOPs via CPU lowering")
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=min(600, remaining), env=env)
        except subprocess.SubprocessError as e:
            log(f"[parent] {name}: flops child failed: {e!r}")
            continue
        parsed = _parse_child_json(proc.stdout,
                                   required=("flops_per_image",))
        flops = (parsed or {}).get("flops_per_image")
        if not flops:
            log(f"[parent] {name}: CPU flops lowering gave nothing "
                f"(rc={proc.returncode})")
            continue
        tflops_chip = flops * entry["ips_per_chip"] / 1e12
        entry["gflop_per_image"] = round(flops / 1e9, 2)
        entry["tflops_per_sec_per_chip"] = round(tflops_chip, 1)
        entry["flops_source"] = "cpu-lowering"
        peak = _peak_tflops(entry.get("device_kind", ""))
        if peak:
            entry["mfu"] = round(tflops_chip / peak, 3)
            entry["peak_tflops_per_chip"] = peak
        if name in cache and not entry.get("decode_only") \
                and not entry.get("profiled"):
            # Same rule as the capture loop: profiled timings never
            # clobber a clean cache entry.
            cache[name] = {k: v for k, v in entry.items()
                           if k not in ("cached", "fresh_failure",
                                        "device_unverified")}
            _save_cache(cache)
        _write_partial()


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--phase", default=None)
    parser.add_argument("--iters", type=int, default=50)
    parser.add_argument("--per-chip-batch", type=int, default=128)
    parser.add_argument("--flops-cpu", action="store_true")
    parser.add_argument(
        "--assert_no_regression", action="store_true",
        help="after emitting the compact line, run the perf-regression "
             "gate (scripts/perf_report.py) over BENCH_r*.json + this "
             "run's evidence and exit NONZERO on a pinned regression "
             "(warm al_round seconds or train ips/chip >10%% worse than "
             "best-known; exit 3 when this run produced no usable "
             "evidence to judge).  Opt-in: it deliberately breaks the "
             "always-exit-0 contract so a hardware window produces a "
             "machine-checked verdict")
    args = parser.parse_args()
    if args.phase and args.flops_cpu:
        print(json.dumps(run_flops_cpu(args.phase, args.per_chip_batch)),
              flush=True)
    elif args.phase:
        for result in run_child_phase(args.phase, args.iters,
                                      args.per_chip_batch):
            print(json.dumps(result), flush=True)
    else:
        main()
        if args.assert_no_regression:
            # The gate reads the historical series from the repo root
            # and THIS run's full evidence as the latest point; its
            # table goes to stderr (stdout already carried the one
            # compact line) and its exit code is the verdict.
            import contextlib
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "perf_report", os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "scripts", "perf_report.py"))
            perf_report = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(perf_report)
            argv = perf_report.default_series_paths() + [
                "--current", EVIDENCE_PATH]
            with contextlib.redirect_stdout(sys.stderr):
                rc = perf_report.main(argv)
            sys.exit(rc)
