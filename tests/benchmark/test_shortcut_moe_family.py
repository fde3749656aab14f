"""The shortcut-connected MoE family (LongCat-Flash): the arithmetic of its
cut, its FLOPs and bytes, and a walk of its toy cell through the runner (the
reference against the program, end to end, on the CPU)."""

import numpy as np
import pytest

from bench_testlib import BENCH, TOY, ROOT, family, finish_walk, load, walk_env

FAMILY = "benchmarks/families/shortcut_moe.py"
FULL = load("benchmarks/configs/longcat_flash_ep32_l4.json")
TOY_CONFIG = load("tests/benchmark/toy/config_longcat.json")


def test_parameter_count_is_the_cuts():
    """5.07 B, 10.14 GB bfloat16: one MLA 90,572,800 (two 181,145,600), one
    dense FFN 226,492,416 (two 452,984,832), the router 4,718,592 and its
    768 biases, four RMSNorm scales 24,576: 638.9 M a double layer beside
    the experts; an expert 37,748,736, the 16 held 603,979,776: 1,242.9 M a
    layer; four layers 4,971.4 M; the embedding's eighth 100.7 M."""
    fam = family(FAMILY)
    sizes = {}
    for name, shape, _ in fam.tensor_specs(FULL):
        part = name.split(".")
        layer = part[2] if name.startswith("model.layers") else "-"
        key = ("embed" if "embed_tokens" in name else
               "experts" if ".experts." in name else
               "attn" + part[4] if ".self_attn." in name else
               "ffn" + part[4] if ".mlps." in name else
               "router" if ".router." in name else "norms")
        sizes[(layer, key)] = sizes.get((layer, key), 0) + int(np.prod(shape))
    for layer in "0123":
        assert sizes[(layer, "attn0")] == sizes[(layer, "attn1")] == 90_572_800
        assert sizes[(layer, "ffn0")] == sizes[(layer, "ffn1")] == 226_492_416
        assert sizes[(layer, "router")] == 4_718_592 + 768
        assert sizes[(layer, "norms")] == 24_576
        assert sizes[(layer, "experts")] == 16 * 37_748_736 == 603_979_776
        whole = sum(v for (at, _), v in sizes.items() if at == layer)
        assert whole == 1_242_854_144
        assert whole - sizes[(layer, "experts")] == 638_874_368
    assert sizes[("-", "embed")] == 16_384 * 6_144 == 100_663_296
    encoder = sum(sizes.values())
    assert encoder == 4 * 1_242_854_144 + 100_663_296 + 6_144
    assert fam.param_count(FULL) == encoder + (6_144 + 1) * 16 \
        == 5_072_184_336
    # 10.14 GB as the program stores it: the biases and the head float32.
    stored = fam.work(FULL, "forward", 0, 1)["bytes"]
    assert stored == 2 * encoder + 2 * 4 * 768 + 4 * (6_144 + 1) * 16
    assert stored == pytest.approx(10.14e9, rel=1e-3)


def test_forward_flops_a_token():
    """5.27 GFLOP a token at T = 512.  A double layer's MAC: the two MLAs
    191.6 M with their attention cores, the two FFNs 453.0 M, the router
    4.7 M, the held experts 9.4 M (12 picks x 16 of 768 outputs), the zero
    experts nothing."""
    fam = family(FAMILY)
    m = fam.layer_macs(FULL)
    cores = 64 * (128 + 64 + 128) * (512 + 1) / 2.0
    assert m["mla"] == 90_570_752
    assert 2 * (m["mla"] + cores) == pytest.approx(191.6e6, rel=1e-3)
    assert 2 * m["dense_ffn"] == pytest.approx(453.0e6, rel=1e-3)
    assert m["router"] == 768 * 6_144
    assert m["held_experts"] == 0.25 * 37_748_736
    assert set(m) == {"mla", "dense_ffn", "router", "held_experts"}
    assert fam.forward_flops_per_token(FULL) == pytest.approx(5.27e9,
                                                              rel=1e-3)


def test_a_fit_is_the_forward_and_the_head():
    fam = family(FAMILY)
    fwd = fam.work(FULL, "forward", 16, 1)
    fit = fam.work(FULL, "fit", 16, 1, head_only=True)
    assert fit["flops"] - fwd["flops"] == pytest.approx(
        2.0 * 6144 * 16 * 16, rel=1e-6)
    assert fwd["flops"] == pytest.approx(16 * 512 * 5.27e9, rel=1e-3)
    assert fit["bytes"] - fwd["bytes"] == 3 * 4 * (6144 + 1) * 16
    with pytest.raises(KeyError):
        fam.work(FULL, "decode", 1)
    with pytest.raises(NotImplementedError, match="freeze_feature"):
        fam.work(FULL, "fit", 16, 1, head_only=False)
    with pytest.raises(NotImplementedError, match="freeze_feature"):
        fam.trainable_keys(fam.make_weights(3, TOY_CONFIG))
    assert fam.trainable_keys(fam.make_weights(3, TOY_CONFIG),
                              head_only=True) == ["linear.weight",
                                                  "linear.bias"]


def test_a_round_of_the_cell_is_0_73_pflop():
    """Window round k scores 176 - 16k rows (the pool less what is labeled
    when the query runs), fits 32 + 16k, tests 64: 272 rows a round in 17
    steps of 16 x 512, whatever the round."""
    fam = family(FAMILY)
    for k in (2, 6):
        scored, fitted = 176 - 16 * k, 32 + 16 * k
        total = (fam.work(FULL, "forward", scored, scored // 16)["flops"]
                 + fam.work(FULL, "fit", fitted, fitted // 16,
                            head_only=True)["flops"]
                 + fam.work(FULL, "forward", 64, 4)["flops"])
        assert total == pytest.approx(0.734e15, rel=2e-3)


def test_the_cell_is_the_issues_parameters():
    cell = load("benchmarks/workloads/longcat_flash_ep32_l4.margin_lin.json")
    assert (cell["strategy"], cell["freeze_feature"]) == ("MarginSampler",
                                                          True)
    assert FULL["scale"] == {
        "num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
        "pool_rows": 192, "test_rows": 64, "init_pool_size": 32,
        "round_budget": 16, "n_epoch": 1, "early_stop_patience": 0}
    assert (FULL["row_len"], FULL["num_classes"], FULL["train_batch"]) == (
        512, 16, 16)
    assert (cell["train"]["lr"], cell["train"]["momentum"],
            cell["train"]["weight_decay"]) == (0.1, 0.9, 0.0)
    # A step's tokens are the deployment's global batch: a held expert's
    # expected load is 128 tokens, the configuration's tile.
    tokens = FULL["train_batch"] * FULL["row_len"]
    per_expert = tokens * FULL["moe_topk"] / (
        FULL["experts_routed_over"] + FULL["zero_expert_num"])
    assert per_expert == 128 and FULL["expert_tile"] in (128, 256)


def test_the_bias_is_drawn_float32_at_the_configurations_scale(tmp_path):
    import torch
    fam = family(FAMILY)
    weights = fam.make_weights(5, TOY_CONFIG)
    state = torch.load(fam.save_checkpoint(weights, str(tmp_path)))
    names = [n for n, _, _ in fam.tensor_specs(TOY_CONFIG)]
    assert set(state) == set(names) | {"linear.weight", "linear.bias"}
    bias = state["model.layers.0.mlp.router.e_score_correction_bias"]
    assert bias.dtype == torch.float32 and tuple(bias.shape) == (24,)
    assert 0.4 < float(bias.std()) / TOY_CONFIG["router_bias_std"] < 2.0
    assert state["model.layers.0.mlps.1.down_proj.weight"].dtype == \
        torch.bfloat16


@pytest.fixture(scope="module")
def walk():
    import subprocess
    import sys
    import os
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
           "--workload-file", os.path.join(TOY, "toy.margin_lin.json"),
           "--config-file", os.path.join(TOY, "config_longcat.json"),
           "--seed", str(2 ** 31 + 35), "--seconds", "1", "--trace", "1",
           "--control", "fp8"]
    proc = subprocess.Popen(cmd, env=walk_env(), stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, cwd=ROOT)
    return finish_walk(proc)


def test_the_toy_cell_walks_the_runner_and_reads_correct(walk):
    """The new cell's mix at toy size, ``freeze_feature`` true, through
    ``benchmarks/run.py`` as the driver runs it; ``score_gap`` rides in
    ``uncompared`` (``lib/reference.py`` scores a frozen margin cell with
    the seed's head: PERF.md section 7, question 8a).  The float8 control
    fails it by one of the limits."""
    rc, last, err = walk
    assert rc == 3 and last is not None, err[-3000:]
    assert last["correct"] is True and last["rehearsal"] is True
    check = {k: v for k, (v, _) in last["check"].items()}
    assert check["pick_regret"] == 0.0 and check["test_rows"] == 0.0
    assert max(check["loss3"], check["gnorm1"], check["dparam"]) < 1e-4
    assert set(last["uncompared"]) == {"score_gap", "test_gap"}
    assert "the pool is pinned: 96 rows" in err
    metrics = last["metrics"]
    for name in ("query_s", "fit_s", "test_s", "reinit_s",
                 "lcf_pairs_useful", "lcf_zero_share"):
        assert name in metrics, name
    assert metrics["window_compiles"]["value"] == 0
    assert 50 < metrics["lcf_pairs_useful"]["value"] <= 100
    assert 20 < metrics["lcf_zero_share"]["value"] < 50
    limits = {k: lim for k, (_, lim) in last["check"].items()}
    control = last["control"]["fp8"]
    assert any(control[k] > limits[k] for k in control if k in limits)
