"""Measurement-backed decomposition of the ResNet-50 train-step MFU gap.

VERDICT r4 #5 asks for train MFU >= 0.45 *or a profile-backed written
explanation of the ceiling*.  This script decomposes the gap by
measurement, without a profiler trace: it times, on the SAME live chip
with the SAME timing discipline
as bench.py (untimed warmup, data-dependent host fetch),

  1. the full production train step (fwd + loss + bwd + SGD, BN
     batch-stats mutation) — the number behind bench.py's mfu;
  2. the same step with train_bn=False (BN in inference mode:
     identical matmul/conv work minus the batch-stat reductions and
     their layer-serialized dependency chain);
  3. the forward pass alone under training BN semantics;
  4. the scoring forward (eval BN) — bench.py's resnet50_imagenet_score;
  5. the two measured-ceiling responses, decomposed the same way:
     fused bf16 BN statistics alone (train_full_bf16stats — the −23%
     BN-stats cost reclaimed without touching the stem), the
     space-to-depth stem alone (score_fwd_s2d), and the production
     combination (train_full_s2d_bf16stats — bench.py's new
     resnet50_imagenet_train configuration);
  6. the BACKWARD decomposition (the gradient path, DESIGN.md §4):
     ``bwd_only`` (fwd+bwd, every gradient consumed, no optimizer),
     ``bwd_frozen_bn`` (the same under frozen BN), and
     ``optimizer_update`` (the fused SGD+momentum+wd update alone over
     a ResNet-50 state) — so the decomposition finally NAMES where the
     backward time goes instead of implying it.  The script asserts the
     decomposition is self-consistent (bwd_only + optimizer_update
     within tolerance of train_full) and derives ``bwd_mfu`` (the
     backward pass's isolated MFU) and ``bwd_frac``.

Each timing is converted to achieved TFLOP/s with the phase's own
XLA-reported flop count (cost_analysis via CPU lowering, the same
source bench.py uses), so the deltas attribute the MFU gap to (a) the
backward pass's lower-occupancy conv gradients and (b) BN's cross-layer
reduction serialization.  Writes one JSON evidence file.

Run on the live chip:  python scripts/mfu_decomposition.py --out FILE
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _time_loop(step_once, sync, iters: int, warmup: int = 3) -> float:
    for _ in range(warmup):
        step_once()
    sync()
    t0 = time.perf_counter()
    for _ in range(iters):
        step_once()
    sync()
    return time.perf_counter() - t0


def measure(batch_per_chip: int, iters: int, warmup: int = 3) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from active_learning_tpu.data.core import IMAGENET_NORM, ViewSpec
    from active_learning_tpu.models.resnet import resnet50
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.strategies import scoring
    from active_learning_tpu.data.augment import apply_view
    from active_learning_tpu.train import optim as optim_lib
    from active_learning_tpu.train.trainer import weighted_cross_entropy

    mesh = mesh_lib.make_mesh(-1)
    n_chips = int(mesh.devices.size)
    batch = batch_per_chip * n_chips
    model = resnet50(num_classes=1000, dtype=jnp.bfloat16)
    # Variant models for the ceiling responses: fused bf16 BN statistics
    # (same topology, different stats path) and the space-to-depth stem
    # (exact conv refactoring — random init is fine for THROUGHPUT; the
    # logits-equivalence question lives in tests/test_s2d_stem.py).
    MODELS = {
        "base": model,
        "bnfused": resnet50(num_classes=1000, dtype=jnp.bfloat16,
                            bn_stats_dtype=jnp.bfloat16),
        "s2d": resnet50(num_classes=1000, dtype=jnp.bfloat16, stem="s2d",
                        bn_stats_dtype=jnp.bfloat16),
    }
    train_view = ViewSpec(IMAGENET_NORM, augment=True, pad=0)
    score_view = ViewSpec(IMAGENET_NORM, augment=False)

    rng = np.random.default_rng(0)
    host = {
        "image": rng.integers(0, 256, (batch, 224, 224, 3), dtype=np.uint8),
        "label": rng.integers(0, 1000, batch).astype(np.int32),
        "mask": np.ones(batch, np.float32),
    }
    sharded = mesh_lib.shard_batch(host, mesh)
    VARS = {}
    for vname, m in MODELS.items():
        v = m.init(jax.random.PRNGKey(0), jnp.asarray(host["image"][:8]),
                   train=False)
        VARS[vname] = mesh_lib.replicate(v, mesh)
    # Same convention as the production optimizer (train/optim.py): the
    # transform returns RAW momentum-traced grads and the step applies
    # ``-lr`` itself — optax.sgd would already negate, and a second
    # negation below would ascend the loss.  Optimizer STATE is built
    # per-variant inside build_train (a shared ResNet-50 momentum tree
    # would pin ~100 MB of HBM across every timed variant).
    tx = optax.trace(decay=0.9)
    cw = jnp.ones(1000, jnp.float32)

    def loss_fn(params, batch_stats, x, labels, weights, train_bn,
                variant):
        m = MODELS[variant]
        v = {"params": params, "batch_stats": batch_stats}
        if train_bn:
            logits, mut = m.apply(v, x, train=True,
                                  mutable=["batch_stats"])
            return (weighted_cross_entropy(logits, labels, weights),
                    mut["batch_stats"])
        logits = m.apply(v, x, train=False)
        return weighted_cross_entropy(logits, labels, weights), batch_stats

    @functools.partial(jax.jit, static_argnames=("train_bn", "variant"),
                       donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, key, batch, train_bn,
                   variant):
        x = apply_view(batch["image"], train_view, key=key, train=True)
        w = cw[batch["label"]] * batch["mask"]
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, x, batch["label"],
                                   w, train_bn, variant)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(
            params, jax.tree.map(lambda u: -0.1 * u, updates))
        return params, new_stats, opt_state, loss

    @functools.partial(jax.jit, static_argnames=("train_bn", "variant"))
    def fwd_step(params, batch_stats, key, batch, carry, train_bn,
                 variant):
        x = apply_view(batch["image"], train_view, key=key, train=True)
        loss, _ = loss_fn(params, batch_stats, x, batch["label"],
                          cw[batch["label"]] * batch["mask"], train_bn,
                          variant)
        return carry + loss

    SCORE_STEPS = {vname: scoring.make_prob_stats_step(m, score_view)
                   for vname, m in MODELS.items()}

    @functools.partial(jax.jit, static_argnames=("variant",))
    def score_chained(variables, batch, carry, variant):
        return carry + SCORE_STEPS[variant](variables, batch)["margin"][0]

    device_kind = jax.devices()[0].device_kind
    out = {"device_kind": device_kind, "n_chips": n_chips,
           "batch_per_chip": batch_per_chip, "iters": iters,
           "captured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
           "timings": {}}

    def run(name, build, per_image=True):
        step_once, sync = build()
        dt = _time_loop(step_once, sync, iters, warmup=warmup)
        entry = {"sec": round(dt, 3)}
        if per_image:
            ips = batch * iters / dt
            entry["ips_per_chip"] = round(ips / n_chips, 1)
            print(f"[{name}] {ips / n_chips:,.0f} img/s/chip",
                  file=sys.stderr)
        else:
            entry["ms_per_update"] = round(dt / iters * 1000.0, 3)
            print(f"[{name}] {entry['ms_per_update']} ms/update",
                  file=sys.stderr)
        out["timings"][name] = entry

    def build_train(train_bn, variant="base"):
        # Fresh device copies: train_step donates its state trees, and
        # both train variants (plus the fwd/score runs) must start from
        # live buffers — donating the shared originals would poison the
        # next build.
        v = VARS[variant]
        h = {"p": jax.tree.map(jnp.copy, v["params"]),
             "bs": jax.tree.map(jnp.copy, v["batch_stats"]),
             "o": mesh_lib.replicate(tx.init(
                 jax.tree.map(np.asarray, v["params"])), mesh),
             "k": jax.random.PRNGKey(1), "loss": None}

        def once():
            h["k"], sub = jax.random.split(h["k"])
            h["p"], h["bs"], h["o"], h["loss"] = train_step(
                h["p"], h["bs"], h["o"], sub, sharded, train_bn=train_bn,
                variant=variant)

        return once, lambda: float(h["loss"])

    def build_fwd(train_bn, variant="base"):
        v = VARS[variant]
        h = {"carry": jnp.float32(0.0), "k": jax.random.PRNGKey(2)}

        def once():
            h["k"], sub = jax.random.split(h["k"])
            h["carry"] = fwd_step(v["params"], v["batch_stats"], sub,
                                  sharded, h["carry"], train_bn=train_bn,
                                  variant=variant)

        return once, lambda: float(h["carry"])

    def build_score(variant="base"):
        sbatch = {"image": sharded["image"], "mask": sharded["mask"]}
        h = {"carry": jnp.float32(0.0)}

        def once():
            h["carry"] = score_chained(VARS[variant], sbatch, h["carry"],
                                       variant=variant)

        return once, lambda: float(h["carry"])

    # The backward decomposition (point 6 of the module docstring): the
    # gradient computation isolated from the optimizer.  The grads tree
    # is RETURNED (not reduced to a scalar): outputs can't be
    # dead-code-eliminated, so the whole backward runs — and funneling
    # ~25M gradients into one scalar was measured to push XLA:CPU into
    # a ~5x-slower schedule, which would have failed the consistency
    # check against the grads-returning train step it decomposes.
    @functools.partial(jax.jit, static_argnames=("train_bn", "variant"))
    def bwd_step(params, batch_stats, key, batch, carry, train_bn,
                 variant):
        x = apply_view(batch["image"], train_view, key=key, train=True)
        w = cw[batch["label"]] * batch["mask"]
        (loss, _), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, x, batch["label"],
                                   w, train_bn, variant)
        return carry + loss, grads

    def build_bwd(train_bn, variant="base"):
        v = VARS[variant]
        h = {"carry": jnp.float32(0.0), "k": jax.random.PRNGKey(3),
             "grads": None}

        def once():
            h["k"], sub = jax.random.split(h["k"])
            h["carry"], h["grads"] = bwd_step(
                v["params"], v["batch_stats"], sub, sharded, h["carry"],
                train_bn=train_bn, variant=variant)

        return once, lambda: float(h["carry"])

    # The optimizer update alone: the production FUSED path
    # (train/optim.fused_sgd_update — SGD+momentum+wd in one tree pass,
    # state donated) over a ResNet-50-shaped state, with a fixed grads
    # tree so the timing is pure update.
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def opt_step(params, trace, grads, lr):
        new_params, new_state = optim_lib.fused_sgd_update(
            grads, {"trace": trace}, params, lr, 0.9, 5e-4, jnp.float32)
        return new_params, new_state["trace"]

    def build_opt_update():
        v = VARS["base"]
        grads = jax.tree.map(lambda p: jnp.full(p.shape, 1e-4, p.dtype),
                             v["params"])
        h = {"p": jax.tree.map(jnp.copy, v["params"]),
             "t": jax.tree.map(lambda p: jnp.zeros_like(p), v["params"])}

        def once():
            h["p"], h["t"] = opt_step(h["p"], h["t"], grads,
                                      jnp.float32(0.1))

        def sync():
            return float(jax.tree.leaves(h["p"])[0].reshape(-1)[0])

        return once, sync

    run("score_fwd_eval_bn", build_score)
    run("fwd_only_train_bn", lambda: build_fwd(True))
    run("fwd_only_frozen_bn", lambda: build_fwd(False))
    run("train_frozen_bn", lambda: build_train(False))
    run("train_full", lambda: build_train(True))
    # The measured-ceiling responses, isolated then combined: bf16 BN
    # statistics reclaim the stats tax with the stem untouched; the s2d
    # stem re-shapes the 7x7/s2 conv for the MXU; the combination is the
    # production bench configuration (bench.py resnet50_imagenet_train).
    run("fwd_only_train_bn_bf16stats", lambda: build_fwd(True, "bnfused"))
    run("train_full_bf16stats", lambda: build_train(True, "bnfused"))
    run("score_fwd_s2d", lambda: build_score("s2d"))
    run("train_full_s2d_bf16stats", lambda: build_train(True, "s2d"))
    # The backward decomposition (gradient path, DESIGN.md §4).
    run("bwd_only", lambda: build_bwd(True))
    run("bwd_frozen_bn", lambda: build_bwd(False))
    run("optimizer_update", build_opt_update, per_image=False)
    return out


# Consistency tolerance for (bwd_only + optimizer_update) vs train_full:
# bwd_only already contains the forward, so the two sides time the same
# computation split at the optimizer boundary.  Generous because the
# split runs lose the step's cross-phase fusion and CPU schema runs are
# noisy; a decomposition outside this band is measuring the wrong thing
# and must fail loudly rather than publish.
CONSISTENCY_TOL = 0.35


def check_consistency(out: dict, tol: float = CONSISTENCY_TOL) -> dict:
    """fwd + bwd + optimizer must reassemble into the full step: asserts
    |(bwd_only + optimizer_update) − train_full| <= tol·train_full and
    records the arithmetic in the evidence JSON."""
    t = out["timings"]
    lhs = t["bwd_only"]["sec"] + t["optimizer_update"]["sec"]
    full = t["train_full"]["sec"]
    consistency = {
        "bwd_only_plus_optimizer_sec": round(lhs, 3),
        "train_full_sec": full,
        "ratio": round(lhs / full, 3) if full else None,
        "tol": tol,
        "ok": bool(full and abs(lhs - full) <= tol * full),
    }
    out["consistency"] = consistency
    assert consistency["ok"], (
        f"decomposition inconsistent: bwd_only + optimizer_update = "
        f"{lhs:.3f}s vs train_full = {full:.3f}s (tol {tol:.0%}) — the "
        "variants are not timing the computation they claim")
    return consistency


def device_truth_crosscheck(out: dict, profile_path: str) -> dict:
    """The device-truth cross-check column (ISSUE 11): fold a driver
    capture summary (telemetry/profiler.py's device_profile_rd{n}.json)
    into the decomposition evidence.  The decomposition's host timings
    say how long each variant TOOK; the capture says what the device
    DID during a real round — busy fraction, collective share, measured
    collective bytes.  A host-derived mfu far above device_busy_frac
    means the host timer flattered the device (dispatch gaps hidden by
    async); far below means the device idled on host stalls the
    decomposition never sees.  Stored verbatim + derived deltas, never
    merged into the host numbers."""
    with open(profile_path) as fh:
        capture = json.load(fh)
    cross = {
        "source": profile_path,
        "round": capture.get("round"),
        "device_busy_frac": capture.get("device_busy_frac"),
        "collective_frac": capture.get("collective_frac"),
        "transfer_frac": capture.get("transfer_frac"),
        "collective_bytes_total": capture.get("collective_bytes_total"),
    }
    train = out.get("timings", {}).get("train_full", {})
    host_mfu = train.get("mfu")
    busy = capture.get("device_busy_frac")
    if host_mfu is not None and busy:
        # MFU <= busy always (you cannot achieve flops while idle); the
        # gap busy − mfu is the device-side inefficiency (low-occupancy
        # kernels, collectives), while 1 − busy is the HOST-side gap.
        cross["host_mfu_train_full"] = host_mfu
        cross["device_side_gap"] = round(busy - host_mfu, 3)
        cross["host_side_gap"] = round(1.0 - busy, 3)
    out["device_truth"] = cross
    return cross


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-per-chip", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3,
                    help="untimed warmup iterations per variant (lower "
                         "for CPU schema-regeneration runs)")
    ap.add_argument("--consistency-tol", type=float,
                    default=CONSISTENCY_TOL)
    ap.add_argument("--device_profile", type=str, default=None,
                    help="a device_profile_rd{n}.json from a "
                         "--profile_rounds driver run: folded in as the "
                         "device-truth cross-check column "
                         "(device_busy_frac vs host-derived mfu)")
    ap.add_argument("--out", default=os.path.join(
        REPO, "mfu_decomposition.json"))
    args = ap.parse_args()
    prior = None
    try:
        with open(args.out) as fh:
            prior = json.load(fh)
    except (OSError, ValueError):
        pass
    out = measure(args.batch_per_chip, args.iters, warmup=args.warmup)
    # Per-image GF from bench.py's device-cost-analysis captures: the
    # train step (fwd+bwd+SGD) and the scoring forward.  The fwd-only
    # variants share the scoring conv/matmul structure plus the loss.
    GF = {"train_full": 23.91, "train_frozen_bn": 23.91,
          "fwd_only_train_bn": 7.97, "fwd_only_frozen_bn": 7.97,
          "score_fwd_eval_bn": 7.97,
          # bf16 BN statistics change the stats path's memory traffic,
          # not its flop count.
          "fwd_only_train_bn_bf16stats": 7.97,
          "train_full_bf16stats": 23.91,
          # The s2d stem's folded 4x4x12 kernel carries 192 taps where
          # the 7x7x3 had 147 (the pad row/col is structural zeros XLA
          # still multiplies): +0.07 GF/img forward, +0.22 on the train
          # step (analytic; MFU over these counts the zero taps as work,
          # so the s2d MFU figures are conservative for useful flops).
          "score_fwd_s2d": 8.04,
          "train_full_s2d_bf16stats": 24.13,
          # bwd_only = fwd + bwd, no optimizer (the SGD update's flops
          # are ~2 per param — noise at 23.91 GF/img).
          "bwd_only": 23.91, "bwd_frozen_bn": 23.91}
    # Explicit device-kind match: a bare "v5" substring also matches v5p
    # (bf16 peak ~459 TFLOP/s), which would inflate reported MFU ~2.3x.
    # Unknown kinds leave mfu unset rather than guess a peak.
    kind = out["device_kind"].lower()
    peak = 197.0 if ("v5e" in kind or "v5 lite" in kind) else None
    for name, entry in out["timings"].items():
        gf = GF.get(name)
        if gf is None or "ips_per_chip" not in entry:
            continue  # optimizer_update: ms/update, not img/s
        tf = entry["ips_per_chip"] * gf / 1000.0
        entry["tflops_per_sec_per_chip"] = round(tf, 1)
        if peak:
            entry["mfu"] = round(tf / peak, 3)
    # Derived backward figures (the numbers ROADMAP item 4 asks the
    # decomposition to name): the backward pass isolated by subtracting
    # the same-BN forward from bwd_only, its share of the full step, and
    # its own MFU over the 23.91 − 7.97 GF/img it computes.
    t = out["timings"]
    bwd_sec = t["bwd_only"]["sec"] - t["fwd_only_train_bn"]["sec"]
    batch = out["batch_per_chip"] * out["n_chips"]
    if bwd_sec > 0:
        ips_bwd = batch * args.iters / bwd_sec / out["n_chips"]
        tf_bwd = ips_bwd * (GF["bwd_only"] - GF["fwd_only_train_bn"]) \
            / 1000.0
        out["bwd_sec"] = round(bwd_sec, 3)
        out["bwd_frac"] = round(bwd_sec / t["train_full"]["sec"], 3)
        out["bwd_tflops_per_sec_per_chip"] = round(tf_bwd, 1)
        if peak:
            out["bwd_mfu"] = round(tf_bwd / peak, 3)
    out["opt_update_ms"] = t["optimizer_update"]["ms_per_update"]
    check_consistency(out, tol=args.consistency_tol)
    if args.device_profile:
        try:
            cross = device_truth_crosscheck(out, args.device_profile)
            print(f"[device_truth] busy={cross.get('device_busy_frac')} "
                  f"collective={cross.get('collective_frac')} "
                  f"bytes={cross.get('collective_bytes_total')}",
                  file=sys.stderr)
        except (OSError, ValueError) as e:
            print(f"[device_truth] cross-check unavailable: {e!r}",
                  file=sys.stderr)
    out["gf_per_image_source"] = "bench.py device-cost-analysis (r5)"
    out["gf_note"] = ("train_frozen_bn reuses the full-BN 23.91 GF/img "
                      "(no separate cost-analysis capture); its achieved "
                      "TFLOP/s is therefore a slight overcount")
    # CPU device only: an unknown ACCELERATOR kind (v4/v5p/...) leaves
    # mfu unset because the peak table doesn't know it — that capture
    # is still hardware truth and must not be labeled otherwise.
    if "cpu" in kind:
        out["schema_note"] = (
            "schema-validation capture (no accelerator reachable): the "
            "backward-decomposition variants ran end-to-end but the "
            "rates are not hardware truth; live-TPU capture queued for "
            "the next hardware window")
    # Never discard the last HARDWARE capture when regenerating: the
    # file keeps ONE prior_capture slot, filled with the most valuable
    # non-current capture available — hardware beats CPU schema runs,
    # and the more recent of two hardware captures wins.  So the v5e
    # truth survives any number of CPU schema regens (CPU over
    # CPU-with-nested-v5e keeps v5e), and a fresh TPU capture keeps the
    # previous TPU one as its prior.
    def _strip(cap):
        return {k: cap[k]
                for k in ("device_kind", "captured_utc", "timings")
                if k in cap}

    candidates = []
    if prior:
        candidates.append(_strip(prior))  # most recent first
        if isinstance(prior.get("prior_capture"), dict):
            candidates.append(_strip(prior["prior_capture"]))
    hardware = [c for c in candidates
                if "cpu" not in str(c.get("device_kind", "")).lower()]
    keep = (hardware or candidates)[:1]
    if keep:
        out["prior_capture"] = keep[0]
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps({k: v for k, v in out["timings"].items()}))


if __name__ == "__main__":
    main()
