"""The MLA + experts family: its arithmetic, its seeded tensors and a walk of
its toy cell through the runner (the reference against the program, end to
end, on the CPU)."""

import numpy as np
import pytest

from bench_testlib import (BENCH, TOY, ROOT, family, finish_walk, load,
                           walk_env)

FAMILY = "benchmarks/families/mla_moe.py"
FULL = load("benchmarks/configs/axk1_ep16_l7.json")
TOY_CONFIG = load("tests/benchmark/toy/config_axk1.json")


def test_parameter_count_is_the_cuts():
    """4.694 B: MLA 101.12 M a layer, an expert 44.04 M, an expert layer
    outside its routed experts 146.54 M and 675.0 M with its 12 held, the
    dense layer 497.5 M, the embedding slice 146.8 M."""
    fam = family(FAMILY)
    assert fam.param_count(FULL) == 4_694_645_776
    sizes = {}
    for name, shape, _ in fam.tensor_specs(FULL):
        key = ("embed" if "embed_tokens" in name else "experts"
               if ".experts." in name else "attn" if ".self_attn." in name
               else "rest")
        layer = name.split(".")[2] if name.startswith("model.layers") else "-"
        sizes[(layer, key)] = sizes.get((layer, key), 0) + int(np.prod(shape))
    assert sizes[("0", "attn")] == pytest.approx(101.12e6, rel=1e-3)
    assert sizes[("1", "experts")] == pytest.approx(12 * 44.04e6, rel=1e-3)
    assert sizes[("-", "embed")] == pytest.approx(146.8e6, rel=1e-3)
    layer1 = sum(v for (layer, _), v in sizes.items() if layer == "1")
    layer0 = sum(v for (layer, _), v in sizes.items() if layer == "0")
    assert layer1 == pytest.approx(675.0e6, rel=1e-3)
    assert layer0 == pytest.approx(497.5e6, rel=1e-3)


def test_forward_flops_a_token():
    """3.09 GFLOP a token at T = 512: 0.995 the dense layer, 0.337 each
    expert layer (0.044 of it the held experts' expected share), 0.074 the
    attention cores."""
    fam = family(FAMILY)
    m = fam.layer_macs(FULL)
    assert 2 * (m["mla"] + m["dense_ffn"]) == pytest.approx(0.995e9, rel=2e-3)
    assert 2 * (m["mla"] + m["router"] + m["shared"] + m["held_experts"]
                ) == pytest.approx(0.337e9, rel=2e-3)
    assert 2 * m["held_experts"] == pytest.approx(0.044e9, rel=2e-3)
    assert fam.forward_flops_per_token(FULL) == pytest.approx(3.09e9,
                                                              rel=3e-3)


def test_a_fit_is_the_forward_and_the_head():
    fam = family(FAMILY)
    fwd = fam.work(FULL, "forward", 16, 1)
    fit = fam.work(FULL, "fit", 16, 1, head_only=True)
    head = 2.0 * 7168 * 16 * 16
    assert fit["flops"] - fwd["flops"] == pytest.approx(head, rel=1e-6)
    assert fwd["flops"] == pytest.approx(16 * 512 * 3.09e9, rel=3e-3)
    # Bytes: every parameter once a step, bfloat16 as stored.
    assert fwd["bytes"] == pytest.approx(9.39e9, rel=2e-3)
    assert fit["bytes"] - fwd["bytes"] == 3 * 4 * (7168 + 1) * 16
    with pytest.raises(KeyError):
        fam.work(FULL, "decode", 1)


@pytest.mark.parametrize("ask", ["work", "trainable_keys"])
def test_a_fit_of_the_whole_encoder_is_refused(ask):
    """``head_only`` means something: the family has no reference for a fit
    that moves the encoder and says so, as the program's factory does."""
    fam = family(FAMILY)
    with pytest.raises(NotImplementedError, match="freeze_feature"):
        if ask == "work":
            fam.work(FULL, "fit", 16, 1, head_only=False)
        else:
            fam.trainable_keys(fam.make_weights(3, TOY_CONFIG))
    keys = fam.trainable_keys(fam.make_weights(3, TOY_CONFIG),
                              head_only=True)
    assert keys == ["linear.weight", "linear.bias"]


def test_a_round_of_the_cell_is_0_61_pflop():
    fam = family(FAMILY)
    total = (fam.work(FULL, "forward", 320 - 80, 15)["flops"]
             + fam.work(FULL, "fit", 80, 5, head_only=True)["flops"]
             + fam.work(FULL, "forward", 64, 4)["flops"])
    assert total == pytest.approx(0.61e15, rel=0.01)


def test_tensors_are_a_function_of_key_and_name_and_are_bfloat16():
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    fam = family(FAMILY)
    key = jnp.asarray(fam.make_weights(7, TOY_CONFIG)["encoder.key"])
    name, shape = "model.layers.1.mlp.experts.3.up_proj.weight", (256, 512)
    eager = np.asarray(fam.draw(key, name, shape, 0.05))
    jitted = np.asarray(jax.jit(
        lambda k: fam.draw(k, name, shape, 0.05) * 1.0)(key))
    assert eager.tobytes() == jitted.tobytes()
    assert (eager.astype(ml_dtypes.bfloat16).astype(np.float32)
            == eager).all()
    assert eager.std() == pytest.approx(0.05, rel=0.02)
    assert abs(eager.mean()) < 0.002
    other = np.asarray(fam.draw(key, name.replace(".3.", ".4."), shape, 0.05))
    assert (other != eager).mean() > 0.9
    again = fam.make_weights(7, TOY_CONFIG)
    assert (np.asarray(again["encoder.key"]) == np.asarray(key)).all()
    assert (fam.make_weights(8, TOY_CONFIG)["encoder.key"]
            != np.asarray(key)).any()


def test_rows_come_from_the_vocabulary_slice():
    fam = family(FAMILY)
    rows, labels, t_rows, _ = fam.make_data(3, TOY_CONFIG, 40, 8)
    assert rows.dtype == np.int32 and rows.shape == (40, 32)
    assert rows.min() >= 0 and rows.max() < TOY_CONFIG["vocab_size"]
    own = (rows // (256 // 16)) == labels[:, None]
    assert 0.45 < own.mean() < 0.65
    again = fam.make_data(3, TOY_CONFIG, 40, 8)
    assert (again[0] == rows).all() and (again[2] == t_rows).all()


def test_the_checkpoint_file_names_tensors_as_the_published_one(tmp_path):
    import torch
    fam = family(FAMILY)
    weights = fam.make_weights(5, TOY_CONFIG)
    state = torch.load(fam.save_checkpoint(weights, str(tmp_path)))
    names = [n for n, _, _ in fam.tensor_specs(TOY_CONFIG)]
    assert set(state) == set(names) | {"linear.weight", "linear.bias"}
    assert "model.layers.1.mlp.experts.3.down_proj.weight" in state
    assert "model.layers.1.mlp.experts.4.down_proj.weight" not in state
    assert state["model.layers.0.self_attn.kv_b_proj.weight"].dtype == \
        torch.bfloat16
    assert state["linear.weight"].dtype == torch.float32


@pytest.fixture(scope="module")
def walk():
    import subprocess
    import sys
    import os
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
           "--workload-file", os.path.join(TOY, "toy.margin_lin.json"),
           "--config-file", os.path.join(TOY, "config_axk1.json"),
           "--seed", str(2 ** 31 + 29), "--seconds", "1", "--trace", "1",
           "--control", "fp8"]
    proc = subprocess.Popen(cmd, env=walk_env(), stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, cwd=ROOT)
    return finish_walk(proc)


def test_the_toy_cell_walks_the_runner_and_reads_correct(walk):
    """The cell says ``freeze_feature`` true, as linear evaluation is run.
    ``score_gap`` is not among its limits: the harness scores a frozen
    margin cell with the seed's head (``lib/reference.py``
    ``reference_outputs``), so that number is no reading of the program and
    rides in ``uncompared`` until a ``benchmark`` PR mends the line."""
    rc, last, err = walk
    assert rc == 3 and last is not None, err[-3000:]
    assert last["correct"] is True and last["rehearsal"] is True
    check = {k: v for k, (v, _) in last["check"].items()}
    assert check["pick_regret"] == 0.0 and check["test_rows"] == 0.0
    assert max(check["loss3"], check["gnorm1"], check["dparam"]) < 1e-4
    assert set(last["uncompared"]) == {"score_gap", "test_gap"}
    assert last["uncompared"]["test_gap"] == 0.0
    assert "the pool is pinned: 96 rows" in err
    # The span and counter readers found what the program records.
    for name in ("query_s", "fit_s", "test_s", "reinit_s", "score_pass_s",
                 "ckpt_s", "fit_step_useful"):
        assert name in last["metrics"], name
    assert last["metrics"]["window_compiles"]["value"] == 0


def test_the_float8_control_fails_the_toy_cell(walk):
    _, last, _ = walk
    limits = {k: lim for k, (_, lim) in last["check"].items()}
    control = last["control"]["fp8"]
    assert any(control[k] > limits[k] for k in control if k in limits)
