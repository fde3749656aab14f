#!/usr/bin/env python3
"""Prove on the chip that the main path starts, compiles, fits and takes
the accelerator branches — through the entry points a user types.

    python chip_smoke.py             # one TPU chip: phases f, a-e, ~13 min cold
    python chip_smoke.py --chips 4   # four chips: the sharded pair only

This process NEVER imports JAX: a process that has touched JAX holds the
chip, and a child that needs it then fails or hangs.  Every phase is a child
(``python -m active_learning_tpu ...``) run one at a time under a hard time
limit of its own, and everything asserted is read from what the run itself
recorded (``round_journal.json``, ``metrics.jsonl``, ``run_report.json``,
``trace.json``, ``experiment_state.npz``, HTTP answers).

Default run, in order:

  f  the pinned form: the scoring and the evaluation runner compiled for
     the benchmark's pool (32,768 rows of 224 px, 4.93 GB, as shapes) must
     read it in place: no pool-sized instruction but the parameter, and
     temporaries under a quarter of the pool
     (``resident.assert_pool_read_in_place``).  First, because it is cheap
     and the phases after it pin pools in that form.
  a  ImageNet shape at full width: a seeded ImageFolder tree of JPEGs,
     SSLResNet50 / 1000-way head / batch 128 / 224 px, three MarginSampler
     rounds.  Host-prefetch feed with the native decoder; later rounds score
     from the pool pinned in HBM.
  b  CIFAR shape: the format-exact 50,000/10,000 archive, SSLResNet18, two
     rounds.  Resident feed in the one-dispatch-per-epoch scan form.
  c  k-center at the 2048-d embedding width: phase a's tree and model with
     CoresetSampler + --freeze_feature; reports persistent-cache hits for
     the programs phase a compiled.
  d  serve: phase a's experiment behind ``python -m active_learning_tpu
     serve``; three requests; SIGTERM drains, exit 0.
  e  stream: bootstrap + one interval-triggered round, one POST /v1/pool,
     SIGTERM, exit 0.

One JSON line per phase on stdout, then as the LAST line exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed assertion, child exit code or time limit gives ``"ok": false``
and a non-zero exit.  Without a TPU (``JAX_PLATFORMS=cpu``, no accelerator)
it fails on the probe and names the missing chip.

``--rehearse`` walks the same control flow on the CPU at a tiny size (with
``--chips 4``: on four virtual host devices) to find wrong paths before chip
time is spent.  It skips the assertions only a chip can meet and ALWAYS
ends ``"ok": false`` with a non-zero exit: a rehearsal is not a result.
"""

from __future__ import annotations

import argparse
import base64
import concurrent.futures
import glob
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")
SEED = 1234
PY = sys.executable

# Every child this script starts, so that none outlives it.
_CHILDREN: list = []


class PhaseFailed(Exception):
    pass


# What the chip tool brings back from its machine: the phase lines and the
# small records of every run, for the post-mortem of a failed phase.
# (A rehearsal keeps them in the work directory instead.)
KEEP = os.path.join(REPO, "chiprun_out", "chip_smoke")


def emit(obj: dict) -> None:
    line = json.dumps(obj, sort_keys=True)
    print(line, flush=True)
    os.makedirs(KEEP, exist_ok=True)
    with open(os.path.join(KEEP, "phases.jsonl"), "a") as fh:
        fh.write(line + "\n")


def keep_records() -> None:
    """Copy the children's output and each run's records (never data,
    checkpoints or traces of HLO) from the work directory to ``KEEP``."""
    for pattern in ("*.out", "*/logs/*.json", "*/logs/*.jsonl",
                    "*/logs/*.log", "*/ckpt/*/experiment_state.npz"):
        for path in glob.glob(os.path.join(WORK, pattern)):
            if os.path.getsize(path) <= 4 << 20:
                dest = os.path.join(KEEP, os.path.relpath(path, WORK))
                os.makedirs(os.path.dirname(dest), exist_ok=True)
                shutil.copyfile(path, dest)


def check(cond: bool, what: str) -> str:
    if not cond:
        raise PhaseFailed(what)
    return what


def tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            fh.seek(max(0, fh.tell() - n))
            return fh.read().decode("utf-8", "replace")
    except OSError:
        return ""


# -- children -----------------------------------------------------------------

def child_env(rehearse: bool, chips: int, extra: dict = None) -> dict:
    """The children's environment: the caller's, with HOME inside the work
    directory (the decoded-pool cache defaults under ~/.cache) and the repo
    importable.  JAX's platform and compile-cache variables are NOT set: the
    chip and the cache are placed from outside.  A rehearsal pins the CPU
    (and the virtual device count for the four-chip pair)."""
    env = dict(os.environ)
    env["HOME"] = os.path.join(WORK, "home")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={chips}").strip()
    env.update(extra or {})
    return env


def start(cmd: list, log_path: str, env: dict) -> subprocess.Popen:
    log = open(log_path, "wb")
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=log,
                            stderr=subprocess.STDOUT,
                            start_new_session=True)
    log.close()
    _CHILDREN.append(proc)
    return proc


def kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()


def run(name: str, cmd: list, env: dict, limit_s: float) -> None:
    """Run one child to its end under ``limit_s``.  A non-zero exit or
    the time limit fails the phase by name."""
    log_path = os.path.join(WORK, f"{name}.out")
    proc = start(cmd, log_path, env)
    try:
        rc = proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        kill(proc)
        raise PhaseFailed(f"{name}: no exit within its {limit_s:.0f}s limit "
                          f"(hang?); last output: {tail(log_path, 800)!r}")
    if rc != 0:
        raise PhaseFailed(f"{name}: exit code {rc}; last output: "
                          f"{tail(log_path, 1500)!r}")


def wait_for(proc: subprocess.Popen, log_path: str, pattern: str,
             limit_s: float, what: str) -> re.Match:
    deadline = time.monotonic() + limit_s
    rx = re.compile(pattern)
    while time.monotonic() < deadline:
        try:
            with open(log_path, errors="replace") as fh:
                m = rx.search(fh.read())
        except OSError:
            m = None
        if m:
            return m
        if proc.poll() is not None:
            raise PhaseFailed(f"{what}: child exited {proc.returncode} "
                              f"first; last output: {tail(log_path, 1500)!r}")
        time.sleep(0.5)
    raise PhaseFailed(f"{what}: not seen within {limit_s:.0f}s; last "
                      f"output: {tail(log_path, 800)!r}")


def sigterm_exits_zero(proc: subprocess.Popen, log_path: str,
                       limit_s: float, what: str) -> str:
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        kill(proc)
        raise PhaseFailed(f"{what}: no exit within {limit_s:.0f}s of SIGTERM")
    return check(rc == 0, f"{what}: SIGTERM exit code 0 (got {rc}; "
                          f"{tail(log_path, 600)!r})")


def http(url: str, payload: dict = None, timeout: float = 120.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


# -- data, made from the seed ---------------------------------------------------

def _write_jpeg(job) -> None:
    import numpy as np
    from PIL import Image
    path, seed = job
    rng = np.random.default_rng(seed)
    h, w = (int(v) for v in rng.integers(224, 321, size=2))
    base = rng.integers(0, 256, size=(12, 16, 3), dtype=np.uint8)
    Image.fromarray(base).resize((w, h), Image.BILINEAR).save(path,
                                                              quality=75)


def make_imagefolder(root: str, n_train: int, n_val: int,
                     n_classes: int) -> None:
    """train/ and val/ class directories of 224-320 px JPEGs, each image a
    function of (SEED, index) alone."""
    jobs = []
    for split, n, offset in (("train", n_train, 0), ("val", n_val, n_train)):
        for c in range(n_classes):
            os.makedirs(os.path.join(root, split, f"cls_{c:04d}"),
                        exist_ok=True)
        for i in range(n):
            jobs.append((os.path.join(root, split, f"cls_{i % n_classes:04d}",
                                      f"img_{i:06d}.jpg"),
                         SEED * 1_000_003 + offset + i))
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(_write_jpeg, jobs, chunksize=64))


_CIFAR_CHILD = """
import os, sys
from active_learning_tpu.data import cifar10 as c10
from active_learning_tpu.data.facsimile import write_cifar10_facsimile
d, n_train, n_test, seed = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), \
int(sys.argv[4])
path, md5 = write_cifar10_facsimile(
    os.path.join(d, "cifar-10-python.tar.gz"), n_train=n_train,
    n_test=n_test, seed=seed, noise_sigma=60.0, contrast=0.10)
c10.fetch_cifar10(d, url="file://" + path, expected_md5=md5)
"""


def start_cifar_child(data_dir: str, n_train: int, n_test: int,
                      rehearse: bool, chips: int) -> subprocess.Popen:
    """The format-exact CIFAR-10 archive, written and unpacked by the
    repo's own code in a child that is held to the CPU (it imports the
    package, and must never reach for the chip)."""
    os.makedirs(data_dir, exist_ok=True)
    env = child_env(rehearse, chips, {"JAX_PLATFORMS": "cpu"})
    return start([PY, "-c", _CIFAR_CHILD, data_dir, str(n_train),
                  str(n_test), str(SEED)],
                 os.path.join(WORK, "make_cifar.out"), env)


_FORM_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from active_learning_tpu.data.core import IMAGENET_NORM, ViewSpec
from active_learning_tpu.models.factory import get_network
from active_learning_tpu.parallel import mesh as mesh_lib, resident
from active_learning_tpu.strategies import scoring
from active_learning_tpu.train.evaluation import make_eval_step
name, rows, px, batch = sys.argv[1], *(int(v) for v in sys.argv[2:5])
mesh = mesh_lib.make_mesh()
rep = mesh_lib.replicated_sharding(mesh)
spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
row = (px, px, 3)
model = get_network("imagenet", name, dtype="auto")
variables = jax.tree.map(
    lambda s: spec(s.shape, s.dtype),
    jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, *row), jnp.float32),
                                        train=False), jax.random.PRNGKey(0)))
view = ViewSpec(IMAGENET_NORM, augment=False)
images = spec(resident.pinned_shape((rows, *row)), jnp.uint8)
small = (spec((batch,), jnp.int32), spec((batch,), jnp.float32))
step = scoring.make_prob_stats_step(model, view)
out = {"pinned_shape": list(images.shape)}
out["score"] = resident.assert_pool_read_in_place(
    resident.get_runner({}, step, mesh, scoring._runner_name(step), row),
    (variables, images, *small), pool_arg=1)
out["eval"] = resident.assert_pool_read_in_place(
    resident.get_runner({}, make_eval_step(model, view, model.num_classes),
                        mesh, "run_eval", row, with_labels=True),
    (variables, images, spec((rows,), jnp.int32), *small), pool_arg=1)
print("pool_form " + json.dumps(out), flush=True)
"""


def finish_cifar_child(proc: subprocess.Popen) -> None:
    try:
        rc = proc.wait(timeout=300)
    except subprocess.TimeoutExpired:
        kill(proc)
        raise PhaseFailed("CIFAR archive child: no exit within 300s")
    check(rc == 0, "CIFAR archive written (child exit "
                   f"{rc}; {tail(os.path.join(WORK, 'make_cifar.out'))!r})")


# -- reading a run's own records ------------------------------------------------

def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def read_metrics(log_dir: str) -> dict:
    """metrics.jsonl -> {name: [(step, value), ...]} in file order."""
    out: dict = {}
    with open(os.path.join(log_dir, "metrics.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            if rec.get("kind") == "metric":
                for k, v in rec["metrics"].items():
                    out.setdefault(k, []).append((rec.get("step"), v))
    return out


def pool_passes_by_round(log_dir: str) -> dict:
    """trace.json -> {round: [path of each collect_pool pass that STARTED
    inside that round's span]} ("resident" = gathered on device from the
    pinned pool, "stream" = host->device)."""
    trace = read_json(os.path.join(log_dir, "trace.json"))
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    rounds = [(e["ts"], e["ts"] + e["dur"], int(e["args"]["round"]))
              for e in events if e.get("name") == "round" and "dur" in e]
    out: dict = {rd: [] for _, _, rd in rounds}
    for e in events:
        if e.get("name") != "collect_pool":
            continue
        for t0, t1, rd in rounds:
            if t0 <= e["ts"] <= t1:
                out[rd].append(e["args"]["path"])
    return out


def is_number(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


def check_experiment(dirs: dict, rounds: int, budget: int, chips: int,
                     rehearse: bool, want: dict) -> dict:
    """The assertions every experiment phase shares, each read from the
    run's records.  ``want``: feed / feed_form expected of every fit,
    floor of the per-chip eval batch, whether a decoder must be named,
    from which round on the query must score from the pinned pool, and
    which round must compile nothing (default: the last).
    Returns what the phase line reports."""
    asserted = []
    ok = asserted.append
    journal = read_json(os.path.join(dirs["log"], "round_journal.json"))
    rt = journal.get("runtime") or {}
    metrics = read_metrics(dirs["log"])
    report = read_json(os.path.join(dirs["log"], "run_report.json"))
    rows = {int(r["round"]): r for r in report["rounds"]}

    ok(check(journal.get("status") == "finished",
             f"journal status finished (got {journal.get('status')!r})"))
    ok(check(journal.get("degrade") == [],
             f"no ladder rung active at exit ({journal.get('degrade')})"))
    if not rehearse:
        ok(check(rt.get("platform") == "tpu" and
                 rt.get("device_count") == chips,
                 f"devices are {chips} x tpu (run recorded "
                 f"{rt.get('device_count')} x {rt.get('platform')})"))
        ok(check(rt.get("compute_dtype") == "bfloat16",
                 f"compute dtype bfloat16 (got {rt.get('compute_dtype')})"))
        ok(check(rt.get("resident_budget_source") == "memory_stats",
                 "resident budget sized from memory_stats (got "
                 f"{rt.get('resident_budget_source')})"))
        ok(check(rt.get("eval_batch", 0) >= want["eval_floor"] * chips,
                 f"evaluation at the raised accelerator batch (>= "
                 f"{want['eval_floor']} rows per chip; got "
                 f"{rt.get('eval_batch')} over {chips})"))
    if want.get("decoder"):
        ok(check(rt.get("decoder") in ("native", "pil"),
                 f"decode path named (got {rt.get('decoder')!r})"))
        if not rehearse:
            ok(check(rt.get("decoder") == "native",
                     "native decoder built from this checkout's decode.cpp "
                     f"(got {rt.get('decoder')!r})"))

    ok(check(sorted(rows) == list(range(rounds)),
             f"{rounds} rounds reported (got {sorted(rows)})"))
    for rd in range(rounds):
        ok(check(rows[rd]["labeled"] == budget * (rd + 1),
                 f"round {rd} labeled exactly its budget "
                 f"({rows[rd]['labeled']} == {budget * (rd + 1)})"))
        ok(check(is_number(rows[rd]["test_accuracy"]),
                 f"round {rd} test accuracy finite "
                 f"({rows[rd]['test_accuracy']})"))
    if not rehearse:
        for rd in range(rounds):
            ok(check(rows[rd]["feed"] == want["feed"] and
                     rows[rd]["feed_form"] == want["feed_form"],
                     f"round {rd} fit ran the {want['feed']} feed in the "
                     f"{want['feed_form']} form (got {rows[rd]['feed']}/"
                     f"{rows[rd]['feed_form']})"))
    losses = [v for _, v in metrics.get("train_loss_ema", [])]
    ok(check(bool(losses) and all(is_number(v) for v in losses),
             f"train loss finite over {len(losses)} epochs"))
    degr = [v for _, v in metrics.get("degrade_events", [])]
    ok(check(len(degr) == rounds and not any(degr),
             f"degrade_events == 0 every round ({degr})"))
    misses = dict(metrics.get("jit_cache_miss_delta", []))
    warm = want.get("warm_round", rounds - 1)
    ok(check(misses.get(warm) == 0,
             f"jit_cache_miss_delta == 0 on round {warm}, the warm one "
             f"({misses})"))
    hbm = [v for _, v in metrics.get("hbm_peak_gb", [])]
    if not rehearse:
        ok(check(len(hbm) == rounds and all(is_number(v) for v in hbm),
                 f"hbm_peak_gb is a number every round ({hbm})"))

    passes = pool_passes_by_round(dirs["log"])
    # Under the pipelined round a query's scoring may run inside the
    # PREVIOUS round's span (the speculative scorer); what matters is
    # that every pass from ``resident_from`` on gathers on device.
    late = [p for rd, ps in passes.items() if rd >= want["resident_from"]
            for p in ps]
    ok(check(bool(late) and set(late) == {"resident"},
             f"queries from round {want['resident_from']} on scored from "
             f"the pool pinned in HBM (passes by round: {passes})"))

    status = subprocess.run(
        [PY, "-m", "active_learning_tpu", "status", "--strict",
         "--log_dir", dirs["log"]],
        cwd=REPO, env=child_env(True, 1), capture_output=True, text=True,
        timeout=60)
    ok(check(status.returncode == 0,
             f"status --strict exits 0 (got {status.returncode}: "
             f"{(status.stdout + status.stderr)[-300:]!r})"))
    return {
        "asserted": asserted,
        "path": {"runtime": rt, "pool_passes": passes,
                 "feeds": {rd: [rows[rd]["feed"], rows[rd]["feed_form"]]
                           for rd in rows},
                 "jit_cache_miss_delta": misses, "hbm_peak_gb": hbm,
                 "compile_cache": journal.get("compile_cache"),
                 "placement": journal.get("placement"),
                 "test_accuracy": [rows[rd]["test_accuracy"]
                                   for rd in sorted(rows)]},
    }


def experiment_cmd(dirs: dict, exp_hash: str, flags: list) -> list:
    return [PY, "-m", "active_learning_tpu", *flags,
            "--exp_hash", exp_hash, "--log_dir", dirs["log"],
            "--ckpt_path", dirs["ckpt"], "--export_trace"]


def phase_dirs(name: str) -> dict:
    return {"log": os.path.join(WORK, name, "logs"),
            "ckpt": os.path.join(WORK, name, "ckpt")}


# -- the phases -------------------------------------------------------------------

def phase_f(ctx) -> dict:
    """Nothing runs and nothing is pinned: the programs are compiled for
    shapes, on the device at hand, and the check raises in the child."""
    size = ctx["size"]
    run("f", [PY, "-c", _FORM_CHILD, size["form_model"],
              str(size["form_rows"]), str(size["form_px"]),
              str(size["form_batch"])],
        ctx["env"], ctx["limits"]["f"])
    m = re.search(r"^pool_form (\{.*\})$", tail(os.path.join(WORK, "f.out"),
                                               4000), re.M)
    check(m is not None, "the form child printed its figures")
    got = json.loads(m.group(1))
    pool = size["form_rows"] * size["form_px"] ** 2 * 3
    asserted = [check(
        got[k]["pool_bytes"] == pool,
        f"the {k} runner reads its {pool}-byte pinned pool in place "
        f"({got[k]['temp_bytes']} bytes of temporaries, no pool-sized "
        "instruction but the parameter)") for k in ("score", "eval")]
    return {"asserted": asserted, "path": got}


def phase_a(ctx) -> dict:
    size = ctx["size"]
    t0 = time.monotonic()
    make_imagefolder(ctx["tree"], size["pool"], size["test"],
                     size["classes"])
    made_s = time.monotonic() - t0
    dirs = phase_dirs("a")
    run("a", experiment_cmd(dirs, "smokea", [
        "--dataset", "imagenet", "--dataset_dir", ctx["tree"],
        "--model", size["big_model"], "--arg_pool", "default",
        "--strategy", "MarginSampler", "--rounds", "3",
        "--init_pool_size", "0", "--round_budget", str(size["budget"]),
        "--n_epoch", "2", "--early_stop_patience", "2"]),
        ctx["env"], ctx["limits"]["a"])
    out = check_experiment(dirs, 3, size["budget"], 1, ctx["rehearse"],
                           {"feed": "host_prefetch", "feed_form": "loop",
                            "eval_floor": 256, "decoder": True,
                            "resident_from": 1})
    out["path"]["tree_seconds"] = round(made_s, 1)
    ctx["a_dirs"] = dirs
    return out


def phase_b(ctx) -> dict:
    size = ctx["size"]
    finish_cifar_child(ctx["cifar_child"])
    dirs = phase_dirs("b")
    run("b", experiment_cmd(dirs, "smokeb", [
        "--dataset", "cifar10", "--dataset_dir", ctx["cifar"],
        "--model", "SSLResNet18", "--arg_pool", "default",
        "--strategy", "MarginSampler", "--rounds", "2",
        "--init_pool_size", "0", "--round_budget", str(size["cifar_budget"]),
        "--n_epoch", "2", "--early_stop_patience", "2"]),
        ctx["env"], ctx["limits"]["b"])
    return check_experiment(dirs, 2, size["cifar_budget"], 1,
                            ctx["rehearse"],
                            {"feed": "resident", "feed_form": "scan",
                             "eval_floor": 512, "resident_from": 0})


def phase_c(ctx) -> dict:
    size = ctx["size"]
    dirs = phase_dirs("c")
    run("c", experiment_cmd(dirs, "smokec", [
        "--dataset", "imagenet", "--dataset_dir", ctx["tree"],
        "--model", size["big_model"], "--arg_pool", "default",
        "--strategy", "CoresetSampler", "--freeze_feature", "--rounds", "2",
        "--init_pool_size", "0", "--round_budget", str(size["budget"]),
        "--n_epoch", "2", "--early_stop_patience", "2"]),
        ctx["env"], ctx["limits"]["c"])
    # Phase a left the decoded pool on disk, so this run's pool is fully
    # decoded from its first query and pins at round 0; with the encoder
    # frozen the embeddings are computed once, so round 1 has no pass.
    out = check_experiment(dirs, 2, size["budget"], 1, ctx["rehearse"],
                           {"feed": "host_prefetch", "feed_form": "loop",
                            "eval_floor": 256, "decoder": True,
                            "resident_from": 0})
    cache = out["path"]["compile_cache"] or {}
    if not ctx["rehearse"]:
        out["asserted"].append(check(
            cache.get("hits", 0) > 0,
            "persistent compile cache hit for programs phase a compiled "
            f"(hits {cache.get('hits')}, misses {cache.get('misses')})"))
    return out


def _rows_b64(rng: random.Random, n: int, px: int) -> dict:
    raw = rng.randbytes(n * px * px * 3)
    return {"b64": base64.b64encode(raw).decode(), "shape": [n, px, px, 3]}


def phase_d(ctx) -> dict:
    asserted = []
    ok = asserted.append
    exp_dir = os.path.join(ctx["a_dirs"]["ckpt"], "active_learning_smokea")
    log_path = os.path.join(WORK, "d.out")
    t0 = time.monotonic()
    proc = start([PY, "-m", "active_learning_tpu", "serve",
                  "--experiment_dir", exp_dir, "--port", "0",
                  "--log_dir", os.path.join(WORK, "d", "logs")],
                 log_path, ctx["env"])
    try:
        m = wait_for(proc, log_path,
                     r"serve: listening on http://127\.0\.0\.1:(\d+)",
                     ctx["limits"]["d"], "serve: listener")
        base = f"http://127.0.0.1:{m.group(1)}"
        warm_s = time.monotonic() - t0
        _, health = http(base + "/healthz")
        ok(check(health.get("ok") is True, "/healthz answers ok"))
        ok(check(health.get("round") == 2,
                 f"serves the last round's checkpoint (round "
                 f"{health.get('round')})"))
        ok(check(health.get("image_shape") == [224, 224, 3],
                 f"input shape 224 px ({health.get('image_shape')})"))
        _, before = http(base + "/metrics")
        rng = random.Random(SEED)
        _, pred = http(base + "/v1/predict", _rows_b64(rng, 3, 224))
        _, score = http(base + "/v1/score", _rows_b64(rng, 5, 224))
        _, emb = http(base + "/v1/score",
                      dict(_rows_b64(rng, 2, 224), embedding=True))
        for resp in (pred, score, emb):
            ok(check(resp.get("round") == 2,
                     f"response stamped round 2 ({resp.get('round')})"))
        ok(check(len(pred["predictions"]) == 3 and all(
            is_number(r["confidence"]) and 0 <= r["pred"] < 1000
            for r in pred["predictions"]),
            "/v1/predict: 3 finite predictions over the 1000-way head"))
        ok(check(len(score["scores"]) == 5 and all(
            is_number(r[k]) for r in score["scores"]
            for k in ("confidence", "margin", "entropy")),
            "/v1/score: 5 rows of finite confidence/margin/entropy"))
        vecs = emb.get("embedding") or []
        ok(check(len(vecs) == 2 and all(
            len(v) == ctx["size"]["embed_dim"] and all(map(is_number, v))
            for v in vecs),
            f"/v1/score embedding: 2 finite {ctx['size']['embed_dim']}-d "
            "rows"))
        _, after = http(base + "/metrics")
        ok(check(after["compiles"]["request_path_compiles"] == 0 and
                 after["compiles"]["per_step"] ==
                 before["compiles"]["per_step"],
                 "compile counter did not move after warm-up "
                 f"({after['compiles']})"))
        ok(sigterm_exits_zero(proc, log_path, 90, "serve"))
    finally:
        kill(proc)
    return {"asserted": asserted,
            "path": {"warmup_seconds": round(warm_s, 1),
                     "buckets": health.get("buckets"),
                     "compiles": after["compiles"]}}


def phase_e(ctx) -> dict:
    asserted = []
    ok = asserted.append
    dirs = phase_dirs("e")
    log_path = os.path.join(WORK, "e.out")
    proc = start([PY, "-m", "active_learning_tpu", "stream",
                  "--dataset", "synthetic", "--arg_pool", "synthetic",
                  "--model", "SSLResNet18", "--strategy", "MarginSampler",
                  "--round_budget", "8", "--n_epoch", "2",
                  "--early_stop_patience", "2", "--stream_port", "0",
                  "--watermark_rows", "0", "--drift_psi", "0",
                  "--max_interval_s", "1", "--exp_hash", "smokee",
                  "--log_dir", dirs["log"], "--ckpt_path", dirs["ckpt"]],
                 log_path, ctx["env"])
    try:
        m = wait_for(proc, log_path,
                     r"stream: ingest listening on http://127\.0\.0\.1:(\d+)",
                     ctx["limits"]["e"], "stream: ingest listener")
        base = f"http://127.0.0.1:{m.group(1)}"
        rng = random.Random(SEED + 1)
        body = _rows_b64(rng, 4, 32)
        body["labels"] = [rng.randrange(10) for _ in range(4)]
        status, ack = http(base + "/v1/pool", body)
        ok(check(status == 200 and ack.get("ok") is True and
                 len(ack.get("ids", [])) == 4,
                 f"POST /v1/pool acknowledged 4 rows ({ack})"))
        wait_for(proc, log_path, r"stream: round 1 triggered by interval",
                 ctx["limits"]["e"], "stream: interval-triggered round")
        wait_for(proc, log_path, r"Saved experiment state for round 1",
                 ctx["limits"]["e"], "stream: round 1 saved")
        ok("bootstrap round and one interval-triggered round completed")
        journal = read_json(os.path.join(dirs["log"], "round_journal.json"))
        ok(check(journal.get("degrade") in ([], None),
                 f"no ladder rung active ({journal.get('degrade')})"))
        ok(sigterm_exits_zero(proc, log_path, 120, "stream"))
    finally:
        kill(proc)
    return {"asserted": asserted, "path": {"ack": ack}}


# -- four chips: the sharded pair ---------------------------------------------------

def _four_run(ctx, layout: str, step: str) -> dict:
    """One run of the pair: the CIFAR protocol shape with CoresetSampler on
    the whole mesh under ``--pool_sharding layout``, XLA dumping the
    optimized HLO of the train step."""
    name = f"four_{layout}"
    dirs = phase_dirs(name)
    dirs["hlo"] = os.path.join(WORK, name, "hlo")
    dirs["state"] = os.path.join(dirs["ckpt"],
                                 f"active_learning_smoke4{layout[:3]}",
                                 "experiment_state.npz")
    env = dict(ctx["env"])
    # The optimized HLO is only written when XLA compiles: a hit in a
    # persistent cache that came with the machine would leave nothing to
    # read.  So this launcher places the pair's cache itself, in a
    # directory that starts empty (the program obeys the variable).
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(WORK, "four_cache")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + f" --xla_dump_to={dirs['hlo']} "
        f"--xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*{step}.*").strip()
    t0 = time.monotonic()
    run(name, experiment_cmd(dirs, f"smoke4{layout[:3]}", [
        "--dataset", "cifar10", "--dataset_dir", ctx["cifar"],
        "--model", "SSLResNet18", "--arg_pool", "default",
        "--strategy", "CoresetSampler", "--rounds", "3",
        "--init_pool_size", "0",
        "--round_budget", str(ctx["size"]["cifar_budget"]),
        "--n_epoch", "2", "--early_stop_patience", "2",
        "--pool_sharding", layout]),
        env, ctx["limits"]["four"])
    dirs["seconds"] = round(time.monotonic() - t0, 1)
    return dirs


def _four_checks(ctx, layout: str, step: str, dirs: dict) -> dict:
    chips, n = ctx["chips"], ctx["size"]["cifar_pool"]
    # Round 1 is the warm one here: round 2's fit crosses the epoch
    # scan's step bucket (3,000 labeled rows = 24 steps, past the
    # 16-step bucket) and retraces once, by design (Trainer.STEP_BUCKET).
    out = check_experiment(dirs, 3, ctx["size"]["cifar_budget"], chips,
                           ctx["rehearse"],
                           {"feed": "resident", "feed_form": "scan",
                            "eval_floor": 512, "resident_from": 0,
                            "warm_round": 1})
    ok = out["asserted"].append
    journal = read_json(os.path.join(dirs["log"], "round_journal.json"))
    rt, place = journal["runtime"], journal["placement"]
    ok(check(rt["pool_sharding"] == layout,
             f"pool layout resolved to {rt['pool_sharding']}"))
    ok(check(journal.get("pipeline_armed") is True,
             "round_pipeline auto armed the speculative scorer on the "
             "multi-device mesh"))
    # Every pinned array (the pool, and the test set beside it) sits on
    # all devices in equal parts: a quarter each under row, a whole copy
    # each under replicated.
    rows = place["pool_rows"]
    want = n // chips if layout == "row" else n
    ok(check(bool(rows) and all(
        len(r) == chips and len(set(r.values())) == 1 for r in rows) and
        any(set(r.values()) == {want} for r in rows),
        f"every device holds {want} of the {n} pool rows ({rows})"))
    ok(check(place["param_devices"] == chips,
             f"parameters live on all {chips} devices "
             f"({place['param_devices']})"))
    if not ctx["rehearse"]:
        used = [d["bytes_in_use"] for d in place["hbm"]]
        ok(check(len(used) == chips and all(
            is_number(u) and u > 0 for u in used) and
            max(used) <= 1.25 * min(used),
            "memory_stats of every device show a balanced share of the "
            f"state (bytes_in_use {used})"))
    modules = glob.glob(os.path.join(
        dirs["hlo"], f"*{step}*after_optimizations.txt"))
    with_ar = 0
    for path in modules:
        with open(path, errors="replace") as fh:
            with_ar += "all-reduce" in fh.read()
    ok(check(bool(modules) and with_ar == len(modules),
             f"the compiled {step} step carries the gradient all-reduce "
             f"({with_ar} of {len(modules)} optimized modules)"))
    out["path"]["seconds"] = dirs["seconds"]
    return out


def phase_four(ctx) -> dict:
    """Both runs first, every assertion after: a failed assertion then
    still leaves the records of BOTH runs to read."""
    import numpy as np
    finish_cifar_child(ctx["cifar_child"])
    # The program the fit dispatches: the resident epoch scan on the chip
    # (asserted through the feed), the chained step on a CPU mesh.
    step = "chained" if ctx["rehearse"] else "epoch_scan"
    layouts = ("row", "replicated")
    dirs = {layout: _four_run(ctx, layout, step) for layout in layouts}
    asserted, paths, states = [], {}, {}
    for layout in layouts:
        out = _four_checks(ctx, layout, step, dirs[layout])
        asserted += [f"{layout}: {a}" for a in out["asserted"]]
        paths[layout] = out["path"]
        with np.load(dirs[layout]["state"]) as z:
            states[layout] = {k: np.asarray(z[k]) for k in z.files}
    picks, lines = compare_layouts(
        states, {layout: read_metrics(dirs[layout]["log"])
                 for layout in layouts}, ctx["chips"])
    paths["picks"] = picks
    return {"asserted": asserted + lines, "path": paths}


def compare_layouts(states: dict, metrics: dict, chips: int) -> tuple:
    """Row vs replicated: everything that does not pass through a matmul's
    rounding must be IDENTICAL (pool size, eval split, init key, costs);
    the picks are identical on a CPU mesh (the PR 6 contract, where dots
    are exact-order) and are compared bit for bit here too — but on the
    TPU they may part ways, and then the phase says so and holds the pair
    to what CAN hold.

    Why they part (measured on 4 x TPU v5 lite with the same code, PR 21):
    the embeddings are bit-identical across the layouts; greedy k-center
    over IDENTICAL factors picks the same 1000 of 50,000 well-separated
    rows, but over a random-init encoder's nearly parallel embeddings
    (norm ~27, pick distances ~8) the two layouts run differently shaped
    distance matmuls whose default-precision rounding (~|a||b| 2^-8)
    exceeds the gap between candidates: same first 47 picks, then a 3 %
    near-tie goes two ways.  Reduction order, not placement.  So when the
    picks differ: every round's labeled count is already exact, the picks
    of each run must come from EVERY device's quarter of the rows (a shard
    that cannot be selected is a placement bug), and round 0 — the one
    round both runs score with the same weights — must pick at the same
    distances (mean and minimum pick distance within 1e-3)."""
    import numpy as np
    a, b = states["row"], states["replicated"]
    lines = [check(sorted(a) == sorted(b), f"same state arrays ({sorted(a)})")]
    picked = ("labeled", "recent")
    for k in sorted(set(a) - set(picked)):
        lines.append(check(
            a[k].shape == b[k].shape and np.array_equal(a[k], b[k]),
            f"experiment_state['{k}'] identical under row and replicated"))
    same = all(np.array_equal(a[k], b[k]) for k in picked)
    info = {"identical": same,
            "labeled_both": int((a["labeled"] & b["labeled"]).sum()),
            "labeled_each": int(a["labeled"].sum())}
    if same:
        lines.append("row and replicated picks identical (labeled, recent)")
        return info, lines
    diff = np.flatnonzero(a["recent"] != b["recent"])
    info["first_differing_recent_pick"] = {
        "position": int(diff[0]) if len(diff) else None,
        "row": a["recent"][diff[:4]].tolist(),
        "replicated": b["recent"][diff[:4]].tolist()}
    n = len(a["labeled"])
    for layout, st in (("row", a), ("replicated", b)):
        share = [float(q.mean()) for q in np.array_split(st["labeled"], chips)]
        total = float(st["labeled"].mean())
        info[f"{layout}_share_by_quarter"] = [round(x / total / chips, 3)
                                              for x in share]
        lines.append(check(
            min(share) >= 0.6 * total,
            f"{layout}: picks come from every device's quarter of the "
            f"{n} rows (share of picks by quarter "
            f"{info[f'{layout}_share_by_quarter']})"))
    for name in ("rd_pick_mean_dist", "rd_pick_min_dist"):
        ra = dict(metrics["row"].get(name, [])).get(0)
        rb = dict(metrics["replicated"].get(name, [])).get(0)
        info[name + "_round0"] = [ra, rb]
        lines.append(check(
            is_number(ra) and is_number(rb) and
            abs(ra - rb) <= 1e-3 * max(abs(ra), abs(rb)),
            f"round 0 {name} agrees across layouts within 1e-3 "
            f"({ra} vs {rb})"))
    lines.append(
        "row and replicated picks DIFFER on this device "
        f"({info['labeled_both']} of {info['labeled_each']} labeled rows in "
        "common): matmul rounding under two program shapes, not placement "
        "— see compare_layouts")
    return info, lines


# -- main -------------------------------------------------------------------------

_PROBE = ("import jax, json; d = jax.devices(); print(json.dumps({"
          "'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")

SIZES = {
    "real": {"pool": 4096, "test": 512, "classes": 128, "budget": 512,
             "big_model": "SSLResNet50", "embed_dim": 2048,
             "form_model": "SSLResNet18", "form_rows": 32768, "form_px": 224,
             "form_batch": 256,
             "cifar_pool": 50000, "cifar_test": 10000, "cifar_budget": 1000},
    # CPU rehearsal: same control flow, toy rows (never a result).
    "rehearse": {"pool": 96, "test": 32, "classes": 8, "budget": 16,
                 "big_model": "SSLResNet18", "embed_dim": 512,
                 "form_model": "SSLResNet18", "form_rows": 200000,
                 "form_px": 32, "form_batch": 8,
                 "cifar_pool": 2000, "cifar_test": 400, "cifar_budget": 64},
}
LIMITS = {"f": 240.0, "a": 420.0, "b": 240.0, "c": 300.0, "d": 360.0,
          "e": 240.0, "four": 600.0}
PHASES = {"f": phase_f, "a": phase_a, "b": phase_b, "c": phase_c,
          "d": phase_d, "e": phase_e}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the sharded pair on a four-device mesh")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk-through at a tiny size; never ok")
    args = ap.parse_args()
    if args.rehearse:
        global KEEP
        KEEP = os.path.join(WORK, "kept")
    device = None
    failed = None
    t_start = time.monotonic()
    try:
        if not os.path.isdir(os.path.join(REPO, "active_learning_tpu")):
            raise PhaseFailed(f"no active_learning_tpu package beside "
                              f"{os.path.basename(__file__)}")
        shutil.rmtree(KEEP, ignore_errors=True)
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(os.path.join(WORK, "home"))
        env = child_env(args.rehearse, args.chips)
        probe = subprocess.run([PY, "-c", _PROBE], env=env, cwd=REPO,
                               capture_output=True, text=True, timeout=300)
        if probe.returncode != 0:
            raise PhaseFailed("probe: JAX found no device (exit "
                              f"{probe.returncode}): {probe.stderr[-600:]!r}")
        device = json.loads(probe.stdout.strip().splitlines()[-1])
        emit({"phase": "probe", "device": device,
              "ok": device["platform"] == "tpu" or args.rehearse})
        if not args.rehearse:
            check(device["platform"] == "tpu",
                  f"no TPU chip: JAX reports {device['count']} x "
                  f"{device['platform']} — this script proves the chip "
                  "path and does not fall back")
            check(device["count"] == args.chips,
                  f"--chips {args.chips} needs exactly {args.chips} tpu "
                  f"device(s), JAX reports {device['count']}")
        size = SIZES["rehearse" if args.rehearse else "real"]
        ctx = {"size": size, "env": env, "rehearse": args.rehearse,
               "chips": args.chips, "limits": LIMITS,
               "tree": os.path.join(WORK, "imagenet"),
               "cifar": os.path.join(WORK, "cifar10")}
        plan = ["four"] if args.chips == 4 else list("fabcde")
        # Phase b's (and the pair's) archive is written beside phase a.
        ctx["cifar_child"] = start_cifar_child(
            ctx["cifar"], size["cifar_pool"], size["cifar_test"],
            args.rehearse, args.chips)
        for name in plan:
            t0 = time.monotonic()
            fn = phase_four if name == "four" else PHASES[name]
            try:
                out = fn(ctx)
            except PhaseFailed as e:
                emit({"phase": name, "ok": False, "error": str(e),
                      "seconds": round(time.monotonic() - t0, 1)})
                raise
            emit({"phase": name, "ok": True,
                  "seconds": round(time.monotonic() - t0, 1), **out})
        if args.rehearse:
            raise PhaseFailed("rehearsal on the CPU: not a result")
    except PhaseFailed as e:
        failed = str(e)
    except Exception as e:  # noqa: BLE001 - any other fault is a failure too
        failed = f"{type(e).__name__}: {e}"
    finally:
        for proc in _CHILDREN:
            kill(proc)
        keep_records()
    total = round(time.monotonic() - t_start, 1)
    if failed is not None:
        emit({"phase": "end", "seconds": total, "error": failed})
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    emit({"phase": "end", "seconds": total})
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
