"""The seam between the runner and a configuration's family, driven with a
second family that is files only (``toy/family_rows.py``, named by the path
in ``toy/config_rows.json``): the fit-following, the forward in blocks and
the comparison's inputs against a dozen lines of numpy, the work count
against a count by hand, and the runner's parameter map.  Nothing under
``benchmarks/`` is edited for it, and nothing there names a model."""

import glob
import os
import re

import numpy as np
import pytest

from bench_testlib import BENCH, ROOT, family, load

import run as bench
from lib import peaks
from lib import reference as ref

CONFIG = load("tests/benchmark/toy/config_rows.json")
HYPER = {"momentum": 0.9, "weight_decay": 0.01}


@pytest.fixture(scope="module")
def fam():
    return family(CONFIG["family"])


@pytest.fixture(scope="module")
def inputs(fam):
    rows, labels, t_rows, t_labels = fam.make_data(2 ** 31 + 5, CONFIG, 96, 24)
    rng = np.random.default_rng(3)
    epochs = []
    for e in range(2):
        idx = rng.permutation(96)[:40].reshape(5, 8)
        mask = np.ones((5, 8), np.float32)
        mask[-1, 5:] = 0.0                       # a short last batch
        epochs.append({"idx": idx, "mask": mask, "lr": 0.1 / (e + 1),
                       "key": np.array([e, 7], np.uint32), "augment": True})
    return {"rows": rows, "labels": labels, "t_rows": t_rows,
            "t_labels": t_labels, "weights": fam.make_weights(9, CONFIG),
            "fit": {"epochs": epochs, "best_epoch": 2}}


# -- a dozen lines of numpy: the forward, its gradients, SGD with momentum ---

def np_forward(p, rows):
    emb = p["table"].astype(np.float64)[rows].mean(axis=1)
    return emb @ p["linear.weight"].astype(np.float64).T + p[
        "linear.bias"], emb


def np_fit(weights, rows, labels, fit, frozen):
    p = {k: v.astype(np.float64) for k, v in weights.items()}
    keys = [k for k in p if not frozen or k.startswith("linear.")]
    mom = {k: np.zeros_like(p[k]) for k in keys}
    losses, gnorms = [], []
    for ep in fit["epochs"][:fit["best_epoch"]]:
        for idx, w in zip(ep["idx"], ep["mask"].astype(np.float64)):
            x, y = rows[idx], labels[idx]
            z, emb = np_forward(p, x)
            z = z - z.max(axis=1, keepdims=True)
            prob = np.exp(z) / np.exp(z).sum(axis=1, keepdims=True)
            losses.append(-(np.log(prob[np.arange(len(y)), y]) * w).sum()
                          / w.sum())
            dz = prob.copy()
            dz[np.arange(len(y)), y] -= 1.0
            dz *= (w / w.sum())[:, None]
            g = {"linear.weight": dz.T @ emb, "linear.bias": dz.sum(axis=0),
                 "table": np.zeros_like(p["table"])}
            d_emb = dz @ p["linear.weight"] / x.shape[1]
            np.add.at(g["table"], x, np.repeat(d_emb[:, None, :],
                                               x.shape[1], axis=1))
            gnorms.append(np.sqrt(sum((g[k] ** 2).sum() for k in keys)))
            for k in keys:
                mom[k] = g[k] + HYPER["weight_decay"] * p[k] + HYPER[
                    "momentum"] * mom[k]
                p[k] = p[k] - ep["lr"] * mom[k]
    return p, losses, gnorms


def test_family_is_loaded_by_the_path_in_its_configuration(fam):
    import families
    assert fam.__file__ == os.path.join(ROOT, CONFIG["family"])
    assert family(CONFIG["family"]) is fam          # one module per path
    assert all(callable(getattr(fam, n)) for n in families.CONTRACT)
    assert fam.experiment(CONFIG) == {"dataset": "token_rows",
                                      "model": "toy_rows"}
    with pytest.raises(ValueError):
        families.load("../outside.py", ROOT)
    with pytest.raises(ValueError):
        families.load("/etc/passwd", ROOT)
    with pytest.raises(ValueError):                 # lacks the contract
        families.load("tests/benchmark/bench_testlib.py", ROOT)
    with pytest.raises(NotImplementedError):        # ROADMAP R5, R6
        fam.datasets(CONFIG, None, None)


def test_rows_and_weights_come_from_the_seed_alone(fam, inputs, tmp_path):
    again = fam.make_data(2 ** 31 + 5, CONFIG, 96, 24)
    other = fam.make_data(2 ** 31 + 6, CONFIG, 96, 24)
    assert inputs["rows"].dtype == np.int32
    assert inputs["rows"].shape == (96, 12)
    assert 0 <= inputs["rows"].min() and inputs["rows"].max() < 64
    assert np.array_equal(again[0], inputs["rows"])
    assert not np.array_equal(other[0], inputs["rows"])
    path = fam.save_checkpoint(inputs["weights"], str(tmp_path))
    with np.load(path) as back:
        assert all(np.array_equal(back[k], v)
                   for k, v in inputs["weights"].items())


@pytest.mark.parametrize("frozen", [False, True])
def test_follow_fit_is_sgd_with_momentum_on_the_familys_forward(
        fam, inputs, frozen):
    got = ref.follow_fit(fam, inputs["weights"], inputs["rows"],
                         inputs["labels"], inputs["fit"], CONFIG, HYPER,
                         frozen, micro=4)
    want, losses, gnorms = np_fit(inputs["weights"], inputs["rows"],
                                  inputs["labels"], inputs["fit"], frozen)
    assert got["trained"] == fam.trainable_keys(inputs["weights"], frozen)
    assert got["losses"] == pytest.approx(losses[:3], rel=1e-5)
    assert got["gnorms"] == pytest.approx(gnorms[:3], rel=1e-5)
    for k, v in want.items():
        assert np.allclose(got["params"][k], v, rtol=1e-4, atol=1e-6), k
    moved = [k for k in want
             if not np.array_equal(got["params"][k], inputs["weights"][k])]
    assert moved == got["trained"]


def test_follow_fit_plants_the_faults(fam, inputs):
    kw = dict(config=CONFIG, hyper=HYPER, frozen=False, micro=8)
    args = (fam, inputs["weights"], inputs["rows"], inputs["labels"],
            inputs["fit"])
    sound = ref.follow_fit(*args, **kw)
    still = ref.follow_fit(*args, fault="state_unchanged", **kw)
    half = ref.follow_fit(*args, fault="half_batch", **kw)
    assert all(np.array_equal(still["params"][k], inputs["weights"][k])
               for k in inputs["weights"])
    assert still["losses"][0] == pytest.approx(sound["losses"][0])
    assert half["losses"][0] != pytest.approx(sound["losses"][0], rel=1e-3)
    assert ref.leaf_change_gap(still["params"], sound["params"],
                               inputs["weights"], sound["trained"]) == \
        pytest.approx(1.0)


def test_forward_rows_runs_the_familys_forward_in_blocks(fam, inputs):
    import jax.numpy as jnp
    p = {k: jnp.asarray(v) for k, v in inputs["weights"].items()}
    idxs = np.arange(5, 75)                       # 70 rows: 32 + 32 + 6
    logits, emb = ref.forward_rows(fam, p, inputs["rows"], idxs, CONFIG,
                                   block=32)
    want, want_emb = np_forward(inputs["weights"], inputs["rows"][idxs])
    assert logits.shape == (70, 4) and emb.shape == (70, 8)
    assert np.allclose(logits, want, rtol=1e-5, atol=1e-6)
    assert np.allclose(emb, want_emb, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind,frozen", [("margin", False),
                                         ("embedding", True)])
def test_reference_outputs_and_the_comparison(fam, inputs, kind, frozen):
    sample = np.array([3, 8, 21, 55, 90])
    record = {"fit": inputs["fit"],
              "score": {"kind": kind, "sample_rows": sample}}
    common = (fam, inputs["weights"], inputs["rows"], inputs["labels"],
              inputs["t_rows"], inputs["t_labels"], record, CONFIG, HYPER,
              frozen)
    out = ref.reference_outputs(*common, micro=8)
    want, _, _ = np_fit(inputs["weights"], inputs["rows"], inputs["labels"],
                        inputs["fit"], frozen)
    t_logits, _ = np_forward(want, inputs["t_rows"])
    assert out["test_counts"] == ref.top_counts(t_logits, inputs["t_labels"])
    # Scores with the kept epoch's parameters; a frozen encoder's embedding
    # with the seed's (the embedding never sees the head).
    s_logits, s_emb = np_forward(inputs["weights"] if frozen else want,
                                 inputs["rows"][sample])
    if kind == "margin":
        assert out["scores"] == pytest.approx(ref.margins(s_logits),
                                              rel=1e-4, abs=1e-6)
    else:
        assert np.allclose(out["scores"], s_emb, rtol=1e-5, atol=1e-6)
    same = ref.compare(out, out, inputs["weights"], 24)
    assert all(v == 0.0 for v in same.values())
    # The control and a planted fault read something; a state left unchanged
    # reads 1.
    fp8 = ref.compare(ref.reference_outputs(*common, micro=8, quant="fp8"),
                      out, inputs["weights"], 24)
    assert fp8["loss3"] > 1e-4
    still = ref.compare(
        ref.reference_outputs(*common, micro=8, fault="state_unchanged"),
        out, inputs["weights"], 24)
    assert still["dparam"] == pytest.approx(1.0)


def hand_count(kind, rows, batches, head_only=False):
    mean, head = 12 * 8, 2 * 8 * 4
    p_bytes = 4 * (64 * 8 + 8 * 4 + 4)
    if kind == "forward":
        return {"flops": (mean + head) * rows,
                "bytes": 48 * rows + batches * p_bytes}
    back = head if head_only else 2 * head + mean
    trained = 4 * (8 * 4 + 4) if head_only else p_bytes
    return {"flops": (mean + head + back) * rows,
            "bytes": 48 * rows + batches * (p_bytes + 3 * trained)}


@pytest.mark.parametrize("frozen", [False, True])
def test_required_work_is_the_familys_count(fam, frozen):
    rec = bench.Record()
    rec.fits = {4: {"labeled": 40, "epochs_run": 2}}
    # The dispatch sizes are the program's own, as the hooks record them.
    rec.scores = {4: {"idxs": np.arange(600), "batch": 256}}
    rec.evals = [{"round": 4, "rows": 24, "batch": 16, "test": True},
                 {"round": 4, "rows": 10, "batch": 16, "test": False},
                 {"round": 3, "rows": 24, "batch": 16, "test": True}]
    cell = {"config": CONFIG, "family": fam,
            "workload": {"freeze_feature": frozen}}
    got = bench.required_work(rec, cell, [4])
    assert got == {
        "fit": hand_count("fit", 80, 5 * 2, frozen),       # 40 rows / 8
        "score": hand_count("forward", 600, 3),            # 600 / 256
        "test": hand_count("forward", 24, 2),              # 24 / 16
        "validate": hand_count("forward", 10, 1)}
    assert bench.required_work(rec, cell, [7]) == {}
    # Tokens in, a small table: the toy is bound by memory; the rooflines'
    # least time is the bytes over the chip's bandwidth.
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = peaks.least_seconds(got["score"], pk)
    assert bound == "memory"
    assert t == pytest.approx(got["score"]["bytes"] / 819e9)


def test_program_outputs_maps_the_programs_tree_through_the_family(fam,
                                                                   inputs):
    w = inputs["weights"]
    tree = {"encoder": {"embedding": w["table"] + 1.0},
            "linear": {"kernel": w["linear.weight"].T * 2.0,
                       "bias": w["linear.bias"] - 1.0}}
    ep = inputs["fit"]["epochs"][0]
    valid = np.array([1, 1, 1, 1, 1, 0, 0, 0], np.float32)   # padded to 8
    pad = np.zeros((3, 8), ep["idx"].dtype)
    rec = bench.Record()
    rec.fits = {2: {"round": 2, "labeled": 40, "best_epoch": 1,
                    "epochs_run": 1, "epochs": [{
                        "idx": np.concatenate([ep["idx"], pad]),
                        "mask": np.concatenate([ep["mask"], pad]),
                        "valid": valid, "key": ep["key"], "lr": 0.1,
                        "augment": True,
                        "losses": np.arange(8.0), "gnorms": np.ones(8)}]}}
    rec.params = {2: tree}
    rec.evals = [{"round": 2, "rows": 24, "count": 24, "test": True,
                  "top1": 5, "top5": 20}]
    pool = np.arange(10, 70)
    values = np.linspace(1.0, 0.0, 60)
    rec.scores = {3: {"idxs": pool, "kind": "prob_stats",
                      "out": {"margin": values}}}
    rec.queries = {3: {"picked": pool[-4:], "labeled_before": np.arange(10)}}
    cell = {"family": fam, "workload": {"check": {"sample_rows": 8}}}
    out, record, select = bench.program_outputs(rec, w, cell, 2 ** 31 + 5, 3)
    assert set(out["params"]) == set(fam.trainable_keys(w))
    assert np.array_equal(out["params"]["table"], w["table"] + 1.0)
    assert np.array_equal(out["params"]["linear.weight"],
                          w["linear.weight"] * 2.0)       # [classes, d] again
    assert out["params"]["linear.weight"].shape == (4, 8)
    assert out["losses"] == [0.0, 1.0, 2.0] and out["test_rows"] == 24
    assert record["fit"]["epochs"][0]["idx"].shape == (5, 8)   # valid steps
    assert select["kind"] == "margin" and len(select["picked_pos"]) == 4
    assert set(pool[select["picked_pos"]]) <= set(record["score"][
        "sample_rows"])
    assert ref.margin_pick_regret(select["values"],
                                  select["picked_pos"]) == 0.0


FAMILY_WORDS = re.compile(
    r"image_size|in_channels|conv|imagenet|resnet", re.IGNORECASE)


def test_the_runner_and_its_library_name_no_model():
    files = [os.path.join(BENCH, "run.py")] + sorted(
        glob.glob(os.path.join(BENCH, "lib", "*.py")))
    assert len(files) >= 7
    hits = []
    for path in files:
        with open(path) as fh:
            hits += [f"{os.path.relpath(path, ROOT)}:{n}: {line.strip()}"
                     for n, line in enumerate(fh, 1)
                     if FAMILY_WORDS.search(line)]
    assert hits == []
    # ... and the family that holds them is where the configurations point.
    with open(os.path.join(BENCH, "families", "resnet.py")) as fh:
        assert len(FAMILY_WORDS.findall(fh.read())) > 50
    assert not os.path.exists(os.path.join(BENCH, "lib", "data.py"))
    assert not os.path.exists(os.path.join(BENCH, "lib", "flops.py"))
