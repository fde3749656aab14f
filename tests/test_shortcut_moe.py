"""The shortcut-connected MoE token encoder (models/shortcut_moe.py) against
its plain reference (benchmarks/families/shortcut_moe.py) at toy widths on the
CPU: seeded weights through the checkpoint file the program loads, float32
both sides, so the two agree to rounding.  Forward (logits, embedding, the
picks and the counters of every layer), the shares of the expert layer and of
a whole double layer, what a dropped correction bias or zero-expert term
does, the checkpoint's names, the frozen leaves after rounds, and the A.X-K1
toy through the tile loop the two encoders now share."""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict, unflatten_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from active_learning_tpu.models import mla_moe, shortcut_moe  # noqa: E402
from active_learning_tpu.utils import pretrained  # noqa: E402

SEEDS = (5, 2 ** 31 + 17, 77)
TOL = dict(rtol=2e-4, atol=2e-5)
FROZEN_LEAVES = 89        # 29 a double layer, the embedding, the final norm


def _toy_config(**over):
    with open(os.path.join(
            REPO, "tests/benchmark/toy/config_longcat.json")) as fh:
        return {**json.load(fh), **over}


@pytest.fixture(scope="module")
def fam():
    import families
    return families.load("benchmarks/families/shortcut_moe.py", REPO)


def _load(model, path, row_len):
    like = flatten_dict(jax.eval_shape(
        lambda k: {"params": model.init(
            k, jnp.zeros((1, row_len), jnp.float32),
            train=False)["params"]}, jax.random.PRNGKey(0)))
    covered = pretrained.map_torch_state(
        like, pretrained.load_torch_state_dict(path),
        key_map=model.torch_key_to_flax)
    assert set(covered) == set(like)
    return covered


def _program(fam, config, seed, tmp):
    """(model, variables, weights): the toy preset holding the experts the
    configuration holds, its leaves read from the family's checkpoint file
    as ``Strategy`` reads them."""
    cfg = dataclasses.replace(
        shortcut_moe.LONGCAT_FLASH_TOY,
        held_first=int(config["experts_held_first"]),
        held_count=int(config["n_routed_experts"]))
    model = shortcut_moe.ShortcutMoeClassifier(
        cfg, int(config["num_classes"]), dtype=jnp.float32)
    weights = fam.make_weights(seed, config)
    covered = _load(model, fam.save_checkpoint(weights, str(tmp)),
                    config["row_len"])
    return model, jax.tree.map(jnp.asarray, unflatten_dict(covered)), weights


def _rows(fam, config, seed, n=6):
    return fam.make_data(seed, config, n, 2)[:2]


def _ref_params(weights):
    return {k: jnp.asarray(v) for k, v in weights.items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_matches_the_reference(fam, seed, tmp_path):
    config = _toy_config()
    model, variables, weights = _program(fam, config, seed, tmp_path)
    rows, _ = _rows(fam, config, seed)
    logits, emb = model.apply(variables, jnp.asarray(rows), train=False,
                              return_features=True)
    p = _ref_params(weights)
    want_emb = fam.embed(p, jnp.asarray(rows), config)
    np.testing.assert_allclose(emb, want_emb, **TOL)
    np.testing.assert_allclose(logits, fam.head(p, want_emb), **TOL)
    assert emb.shape == (len(rows), config["hidden_size"])
    assert emb.dtype == jnp.float32


def _reference_picks(fam, weights, rows, config):
    """Per layer the [N, outputs] mask of what the reference picked."""
    seen, orig = [], fam.router_gates

    def spy(x, w, bias, cfg):
        gates = orig(x, w, bias, cfg)
        seen.append(np.asarray(gates) > 0)
        return gates
    fam.router_gates = spy
    try:
        with jax.disable_jit():
            fam.embed(_ref_params(weights), jnp.asarray(rows),
                      {**config, "ref_block_rows": len(rows)})
    finally:
        fam.router_gates = orig
    return seen


def test_every_layers_picks_and_counters_are_the_references(fam, tmp_path):
    config = _toy_config()
    model, variables, weights = _program(fam, config, 13, tmp_path)
    rows, _ = _rows(fam, config, 13)
    picked, orig = [], shortcut_moe.route

    def spy(p, bias, cfg):
        idx, gate = orig(p, bias, cfg)
        picked.append(np.asarray(idx))
        return idx, gate
    shortcut_moe.route = spy
    try:
        with jax.disable_jit():
            _, state = model.apply(variables, jnp.asarray(rows), train=False,
                                   mutable=["counters"])
    finally:
        shortcut_moe.route = orig
    want = _reference_picks(fam, weights, rows, config)
    routed_over, k = config["experts_routed_over"], config["moe_topk"]
    held = config["n_routed_experts"]
    assert len(picked) == len(want) == config["num_layers"]
    for layer, (idx, mask) in enumerate(zip(picked, want)):
        mine = np.zeros_like(mask)
        np.put_along_axis(mine, idx, True, axis=1)
        assert (mine == mask).all(), layer
        assert (mask.sum(1) == k).all()
        c = state["counters"]["encoder"][f"layers_{layer}"]["mlp"]
        per_row = mask.reshape(len(rows), -1, mask.shape[-1])
        assert (np.asarray(c["pairs_real"][0])
                == per_row[..., :held].sum((1, 2))).all()
        assert (np.asarray(c["pairs_zero"][0])
                == per_row[..., routed_over:].sum((1, 2))).all()
        assert (np.asarray(c["pairs_routed"][0])
                == k * config["row_len"]).all()
        run = int(c["pairs_run"][0])
        assert run >= int(np.sum(c["pairs_real"][0])) and run % 8 == 0
    # Zero experts take their share of the picks: a third of the outputs.
    zero = np.mean([m[:, routed_over:].sum() / m.sum() for m in want])
    assert 0.2 < zero < 0.5


@pytest.mark.parametrize("first", (0, 4, 8, 12))
def test_a_share_matches_the_references_share(fam, first, tmp_path):
    config = _toy_config(experts_held_first=first)
    model, variables, weights = _program(fam, config, 11, tmp_path)
    rows, _ = _rows(fam, config, 11)
    _, emb = model.apply(variables, jnp.asarray(rows), train=False,
                         return_features=True)
    want = fam.embed(_ref_params(weights), jnp.asarray(rows), config)
    np.testing.assert_allclose(emb, want, **TOL)


def _layer_inputs(fam, config, seed, rows=2):
    key = jnp.asarray(fam.make_weights(seed, config)["encoder.key"])
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal(
        (rows, 24, config["hidden_size"])).astype(np.float32))
    return key, h


def _x0(fam, h, w, config):
    """What the expert layer of a double layer reads."""
    eps = config["rms_norm_eps"]
    a0 = h + fam.mla(fam._rms(h, w["input_layernorm.0"], eps),
                     {k[len("self_attn.0."):]: v for k, v in w.items()
                      if k.startswith("self_attn.0.")}, config)
    x0 = fam._rms(a0, w["post_attention_layernorm.0"], eps)
    return x0.reshape(-1, x0.shape[-1])


def test_the_shares_add_up_to_the_uncut_layer(fam):
    """The routed parts of the shares 0-3, 4-7, 8-11, 12-15, with the
    zero-expert term and the dense path counted once, are the uncut
    reference's layer; and because the shortcut joins last, a whole double
    layer is a share's double layer plus the other shares' routed parts."""
    uncut = _toy_config(n_routed_experts=16)
    key, h = _layer_inputs(fam, uncut, 3)
    w_all = fam.layer_tensors(key, 1, uncut)
    x0 = _x0(fam, h, w_all, uncut)
    routed_all, zero = fam.moe_parts(x0, w_all, uncut)
    gates = np.asarray(fam.router_gates(
        x0, w_all["mlp.router.classifier"],
        w_all["mlp.router.e_score_correction_bias"], uncut))
    assert (np.sum(gates > 0, axis=1) == uncut["moe_topk"]).all()
    assert np.abs(np.asarray(zero)).max() > 0
    parts = {}
    for first in (0, 4, 8, 12):
        share = _toy_config(experts_held_first=first)
        routed, zero_here = fam.moe_parts(
            x0, fam.layer_tensors(key, 1, share), share)
        np.testing.assert_array_equal(zero_here, zero)
        parts[first] = routed
    np.testing.assert_allclose(sum(parts.values()) + zero,
                               routed_all + zero, rtol=1e-5, atol=1e-6)
    full = fam.block(h, w_all, 1, uncut)
    for first in (0, 4, 8, 12):
        share = _toy_config(experts_held_first=first)
        mine = fam.block(h, fam.layer_tensors(key, 1, share), 1, share)
        others = sum(v for k, v in parts.items() if k != first)
        np.testing.assert_allclose(mine + others.reshape(h.shape), full,
                                   rtol=1e-5, atol=1e-5)


def _block_params(fam, w, config):
    """A double layer's reference tensors as ``_DoubleBlock``'s params."""
    model = shortcut_moe.ShortcutMoeClassifier(
        shortcut_moe.LONGCAT_FLASH_TOY, 16)
    held = list(fam.held_experts(config))
    tree = {}
    for short, value in w.items():
        if ".experts." in short:
            continue
        name = "model.layers.0." + short + (
            "" if short.endswith("bias") else ".weight")
        path, _ = model.torch_key_to_flax(name)
        node = tree
        for part in path[3:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    for part in ("gate_proj", "up_proj", "down_proj"):
        tree["mlp"][f"experts_{part}"] = jnp.stack(
            [w[f"mlp.experts.{e}.{part}"] for e in held])
    return {"params": tree}


def test_the_programs_shares_add_up_to_the_uncut_layer(fam):
    uncut = _toy_config(n_routed_experts=16)
    key, h = _layer_inputs(fam, uncut, 4)
    w_all = fam.layer_tensors(key, 1, uncut)
    full = fam.block(h, w_all, 1, uncut)
    x0 = _x0(fam, h, w_all, uncut)
    cos, sin = shortcut_moe.rope_tables(shortcut_moe.LONGCAT_FLASH_TOY,
                                        h.shape[1])
    routed = {first: fam.moe_parts(x0, fam.layer_tensors(
        key, 1, _toy_config(experts_held_first=first)),
        _toy_config(experts_held_first=first))[0]
        for first in (0, 4, 8, 12)}
    for first in (0, 4, 8, 12):
        share = _toy_config(experts_held_first=first)
        cfg = dataclasses.replace(shortcut_moe.LONGCAT_FLASH_TOY,
                                  held_first=first)
        mine = shortcut_moe._DoubleBlock(cfg, jnp.float32).apply(
            _block_params(fam, fam.layer_tensors(key, 1, share), share),
            h, cos, sin)
        others = sum(v for k, v in routed.items() if k != first)
        np.testing.assert_allclose(mine + others.reshape(h.shape), full,
                                   **TOL)


@pytest.mark.parametrize("planted", ((2,), (0, 1, 2, 3)))
def test_no_token_is_dropped_under_a_lopsided_router(fam, planted):
    """Every token to the planted held experts: to ONE, its 48 tokens fill
    six tiles of eight rows, twice the 24 rows a chunk gives an expert, and
    the other held experts' bring the slots to 144; to all four, the slots
    are 192, in two chunks too.  Each token gets every planted expert's
    part."""
    config = _toy_config()
    key, h = _layer_inputs(fam, config, 9)
    x = h.reshape(-1, h.shape[-1]) + 4.0
    w = dict(fam.layer_tensors(key, 1, config))
    lop = np.asarray(w["mlp.router.classifier"]).copy()
    lop[list(planted)] = 0.05         # each one's logit ~ 13 on every token
    w["mlp.router.classifier"] = jnp.asarray(lop)
    params = {"params": _block_params(fam, w, config)["params"]["mlp"]}
    out, state = shortcut_moe._ShortcutExperts(
        shortcut_moe.LONGCAT_FLASH_TOY, jnp.float32).apply(
        params, x[None], mutable=["counters"])
    routed, zero = fam.moe_parts(x, w, config)
    gates = np.asarray(fam.router_gates(
        x, w["mlp.router.classifier"],
        w["mlp.router.e_score_correction_bias"], config))
    assert (gates[:, list(planted)] > 0).all()
    np.testing.assert_allclose(out[0], routed + zero, **TOL)
    real = int(np.sum(state["counters"]["pairs_real"][0]))
    run = int(state["counters"]["pairs_run"][0])
    trips = int(state["counters"]["expert_trips"][0])
    assert real == int(np.sum(gates[:, :4] > 0)) >= len(x) * len(planted)
    assert run >= real and run % 8 == 0 and run - real < 4 * 8
    chunk = mla_moe.chunk_rows(len(x), 4, 24, 8)
    assert chunk == 24 and trips == 2
    assert run == (144 if len(planted) == 1 else 192)


@pytest.mark.parametrize("fault", ("zero_bias", "no_zero_experts"))
def test_a_dropped_bias_or_zero_expert_term_fails(fam, fault, tmp_path):
    """The comparison is tight enough to see both mechanisms: a program
    whose correction bias is zero, or a layer without its zero-expert term,
    is far from the reference."""
    config = _toy_config()
    model, variables, weights = _program(fam, config, 17, tmp_path)
    rows, _ = _rows(fam, config, 17, n=8)
    if fault == "zero_bias":
        flat = flatten_dict(variables)
        biases = [p for p in flat
                  if p[-1] == "router_e_score_correction_bias"]
        assert len(biases) == config["num_layers"]
        for p in biases:
            assert np.abs(np.asarray(flat[p])).max() > 0
            flat[p] = jnp.zeros_like(flat[p])
        variables = unflatten_dict(flat)
        # It is picks that move: 5-20 % of a layer's tokens.
        moved = _reference_picks(fam, weights, rows,
                                 {**config, "router_bias_std": 0.0})
        kept = _reference_picks(fam, weights, rows, config)
        assert 0.03 < (moved[0] != kept[0]).any(1).mean() < 0.3
    _, got = model.apply(variables, jnp.asarray(rows), train=False,
                         return_features=True)
    orig = fam.moe_parts
    if fault == "no_zero_experts":
        fam.moe_parts = lambda *a, **kw: (orig(*a, **kw)[0], 0.0)
    try:
        want = fam.embed(_ref_params(weights), jnp.asarray(rows), config)
    finally:
        fam.moe_parts = orig
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_the_checkpoint_round_trip(fam, tmp_path):
    """Published names in, the program's leaves out: an absent expert maps
    to nothing, the correction bias arrives float32 among bfloat16 leaves,
    every leaf is the drawn tensor."""
    import torch
    config = _toy_config()
    model, variables, weights = _program(fam, config, 1, tmp_path)
    state = torch.load(os.path.join(str(tmp_path), "seed_weights.pth"))
    names = [n for n, _, _ in fam.tensor_specs(config)]
    assert set(state) == set(names) | {"linear.weight", "linear.bias"}
    assert "model.layers.2.mlp.experts.3.down_proj.weight" in state
    assert "model.layers.2.mlp.experts.4.down_proj.weight" not in state
    assert model.torch_key_to_flax(
        "model.layers.2.mlp.experts.4.down_proj.weight") is None
    assert model.torch_key_to_flax(
        "model.layers.1.self_attn.1.kv_b_proj.weight")[0][2:] == (
        "layers_1", "self_attn_1", "kv_b_proj")
    assert model.torch_key_to_flax(
        "model.layers.0.post_attention_layernorm.1.weight")[0][2:] == (
        "layers_0", "post_attention_layernorm_1")
    assert model.torch_key_to_flax(
        "model.layers.0.mlps.0.up_proj.weight")[0][2:] == (
        "layers_0", "mlps_0", "up_proj")
    with pytest.raises(KeyError):
        model.torch_key_to_flax("model.layers.0.mlp.router.bias")
    bias_key = "model.layers.1.mlp.router.e_score_correction_bias"
    assert state[bias_key].dtype == torch.float32
    assert state["model.layers.1.mlp.router.classifier.weight"].dtype == \
        torch.bfloat16
    flat = flatten_dict(variables)
    enc = {p: v for p, v in flat.items() if p[1] == "encoder"}
    assert len(enc) == FROZEN_LEAVES
    f32 = {p for p, v in enc.items() if v.dtype == jnp.float32}
    assert f32 == {("params", "encoder", f"layers_{n}", "mlp",
                    "router_e_score_correction_bias") for n in range(3)}
    assert {v.dtype.name for p, v in enc.items() if p not in f32} == {
        "bfloat16"}
    key = jnp.asarray(weights["encoder.key"])
    specs = {n: (s, d) for n, s, d in fam.tensor_specs(config)}
    got = np.asarray(flat[("params", "encoder", "layers_1", "mlp",
                           "router_e_score_correction_bias")])
    np.testing.assert_array_equal(got, fam.draw(key, bias_key,
                                                *specs[bias_key]))
    assert 0.5 * config["router_bias_std"] < got.std() < 2 * config[
        "router_bias_std"]


@pytest.fixture(scope="module")
def rounds(tmp_path_factory, fam):
    """Three rounds of the toy encoder through ``run_experiment``, its
    weights from the family's checkpoint file, the recorder on: no
    family-specific branch is taken anywhere on the way."""
    from active_learning_tpu.config import (ExperimentConfig,
                                            PretrainedConfig,
                                            TelemetryConfig)
    from active_learning_tpu.experiment import arg_pools, driver
    tmp = tmp_path_factory.mktemp("lcf_rounds")
    config = _toy_config()
    weights = fam.make_weights(21, config)
    path = fam.save_checkpoint(weights, str(tmp))
    made = fam.make_data(21, config, 96, 24)
    data = fam.datasets(config, made[:2], made[2:])
    train_cfg = dataclasses.replace(
        arg_pools.get_train_config("ssp_linear_evaluation",
                                   "synthetic_tokens"),
        pretrained=PretrainedConfig(path=path))
    cfg = ExperimentConfig(
        exp_name="lcf", exp_hash="t", dataset="synthetic_tokens",
        model="LONGCAT_FLASH_TOY", strategy="MarginSampler",
        freeze_feature=True, rounds=3, round_budget=8, init_pool_size=32,
        n_epoch=2, early_stop_patience=0, log_dir=str(tmp / "logs"),
        ckpt_path=str(tmp / "ckpt"),
        telemetry=TelemetryConfig(export_trace=True))
    strategy = driver.run_experiment(cfg, data=data, train_cfg=train_cfg)
    import glob
    trace, = glob.glob(str(tmp / "logs" / "**" / "trace.json"),
                       recursive=True)
    with open(trace) as fh:
        events = [e for e in json.load(fh)["traceEvents"]
                  if e.get("ph") == "X"]
    return {"strategy": strategy, "events": events,
            "file": pretrained.load_torch_state_dict(path),
            "ckpt_dir": str(tmp / "ckpt")}


def test_frozen_leaves_are_the_files_bytes_after_rounds(rounds):
    strategy = rounds["strategy"]
    assert set(strategy.state.params) == {"linear"}
    flat = flatten_dict({"params": strategy.state.frozen})
    want = pretrained.map_torch_state(
        {**flat, ("params", "linear", "kernel"): strategy.state.params[
            "linear"]["kernel"], ("params", "linear", "bias"):
            strategy.state.params["linear"]["bias"]},
        rounds["file"], key_map=strategy.model.torch_key_to_flax)
    assert len(flat) == FROZEN_LEAVES
    for path, leaf in flat.items():
        assert np.asarray(leaf).tobytes() == want[path].tobytes(), path
    template = flatten_dict({"params": strategy._reinit_template["frozen"]})
    assert all(flat[p] is template[p] for p in flat)


def test_no_frozen_leaf_in_any_checkpoint_or_reinit_bytes(rounds):
    from active_learning_tpu.train import checkpoint as ckpt_lib
    names = [n for n in os.listdir(os.path.join(rounds["ckpt_dir"], "lcf_t"))
             if n.endswith(".msgpack")]
    assert len(names) >= 6            # best and current of three rounds
    for name in names:
        tree = ckpt_lib.load_variables(
            os.path.join(rounds["ckpt_dir"], "lcf_t", name))
        assert set(tree["params"]) == {"linear"}
    by = {}
    for e in rounds["events"]:
        by.setdefault(e["name"], []).append(e["args"])
    head = (64 + 1) * 16 * 4
    for name in ("ckpt/publish_best", "ckpt/save_current", "ckpt/load_best",
                 "reinit/apply"):
        assert {a["bytes"] for a in by[name]} == {head}, name
    assert {a["bytes"] for a in by["ckpt/round_snapshot"]} <= {0, head}
    assert {a["leaves_frozen"] for a in by["reinit/apply"]} == {
        FROZEN_LEAVES}
    assert len(by["encoder/load"]) == 1          # read and uploaded once
    assert by["encoder/load"][0]["leaves"] == FROZEN_LEAVES


def test_spans_carry_the_four_pair_counters(rounds):
    by = {}
    for e in rounds["events"]:
        by.setdefault(e["name"], []).append(e["args"])
    for a in by["collect_pool"] + by["epoch"]:
        assert a["tokens"] == a["rows"] * 32
        assert 0 < a["pairs_real"] <= a["pairs_run"]
        # A chunk of slots a trip, each trip at least a tile of eight.
        assert 0 < a["expert_trips"] <= a["pairs_run"] // 8
        # k x tokens x layers, and about a third of it on zero experts.
        assert a["pairs_routed"] == 4 * a["tokens"] * 3
        assert 0.2 < a["pairs_zero"] / a["pairs_routed"] < 0.5


def test_presets_are_the_benchmarks_configurations():
    """The program's presets and the benchmark's configuration files state
    the same model, key for key."""
    with open(os.path.join(
            REPO, "benchmarks/configs/longcat_flash_ep32_l4.json")) as fh:
        full = json.load(fh)
    for config, preset in ((full, shortcut_moe.LONGCAT_FLASH_EP32_L4),
                           (_toy_config(), shortcut_moe.LONGCAT_FLASH_TOY)):
        named = 0
        for field in dataclasses.fields(preset):
            if field.name in config and field.name != "n_routed_experts":
                assert getattr(preset, field.name) == config[field.name]
                named += 1
        assert named >= 19
        assert preset.n_routed_experts == config["experts_routed_over"]
        assert preset.held_count == config["n_routed_experts"]
        assert preset.held_first == config["experts_held_first"]
    full_preset = shortcut_moe.LONGCAT_FLASH_EP32_L4
    assert full_preset.mla_q_scale == 2.0
    assert full_preset.mla_kv_scale == pytest.approx(12 ** 0.5)
    assert full_preset.softmax_scale == pytest.approx(192 ** -0.5)
    assert full_preset.router_outputs == 768
    assert (mla_moe.AXK1_EP16_L7.mla_q_scale,
            mla_moe.AXK1_EP16_L7.mla_kv_scale) == (1.0, 1.0)


def test_the_registry_and_the_cli_take_the_encoder():
    """``--model LONGCAT_FLASH_*`` is what the registry holds; without
    ``--freeze_feature`` the factory refuses, with it the frozen set is the
    encoder."""
    from active_learning_tpu.experiment import cli
    from active_learning_tpu.models import backbone, factory
    from active_learning_tpu.registry import MODELS
    assert {"LONGCAT_FLASH_EP32_L4", "LONGCAT_FLASH_TOY"} <= set(
        MODELS.names())
    args = cli.get_parser().parse_args(
        ["--model", "LONGCAT_FLASH_EP32_L4", "--dataset", "synthetic_tokens",
         "--freeze_feature"])
    assert args.model == "LONGCAT_FLASH_EP32_L4"
    with pytest.raises(ValueError, match="--freeze_feature"):
        factory.get_network("synthetic_tokens", "LONGCAT_FLASH_TOY")
    model = factory.get_network("synthetic_tokens", "LONGCAT_FLASH_TOY",
                                freeze_feature=True)
    assert isinstance(model, shortcut_moe.ShortcutMoeClassifier)
    assert model.freeze_feature is True
    assert backbone.frozen_prefixes(model) == ("encoder",)
    assert model.row_counters == ("pairs_real", "pairs_run", "expert_trips",
                                  "pairs_zero", "pairs_routed")


# What the parent commits gave for the A.X-K1 toy on seed 5's checkpoint and
# six rows, eagerly and under jit: SHA-256 of the logits' and the embedding's
# bytes (PR 34, ``029b215``, read again at PR 35, ``c01e7df``), the
# counters' sums, and the first four logits and embedding entries of each
# row.  PR 36 runs the held experts as batched matmuls with a rank-split
# combine in place of the tile loop; on the CPU the same products are added
# in the same order, so the digests still stand, and the numbers bound what
# a change of order may move (rounding).
AXK1_TOY_AT_THE_PARENT = {
    False: "c2ce475ede0b3a507f0ad8789c01e106955bc44691013123e76d4a9ca149f16f",
    True: "65b8c415ca70aba01f998e46116664698b206fa976cd45203e03b952458324d3"}
AXK1_TOY_COUNTERS_AT_THE_PARENT = [179, 192, 235, 256]
AXK1_TOY_LOGITS_AT_THE_PARENT = [
    [-0.569698, 0.8512685, -0.0449729, 0.2893252],
    [0.0601626, -0.5961638, 0.9875636, 1.5175548],
    [-0.836235, 1.6962279, -2.1775069, -0.8681808],
    [-1.6422144, 0.9140904, -0.5834407, 1.0271271],
    [1.4848852, 1.1694596, 0.0245845, 0.7964868],
    [0.8117865, 0.5022219, 0.0094892, 0.9988652]]
AXK1_TOY_EMBEDDING_AT_THE_PARENT = [
    [-1.5283871, 1.898744, 0.6113962, 1.0297315],
    [-1.7751796, 1.1563722, -2.0223563, 0.2461153],
    [0.5952956, 0.650323, -0.7714401, 0.8016225],
    [-1.0611624, 0.2789146, 1.4928739, 1.5292411],
    [-0.2133103, -0.5460781, 0.7902985, -1.1981463],
    [1.7705948, -0.8023418, -1.5696836, -0.8596426]]


@pytest.mark.parametrize("jit", (False, True))
def test_the_axk1_toy_reads_the_parents_bits_through_the_shared_loop(
        jit, tmp_path):
    import families
    fam = families.load("benchmarks/families/mla_moe.py", REPO)
    with open(os.path.join(
            REPO, "tests/benchmark/toy/config_axk1.json")) as fh:
        config = json.load(fh)
    model = mla_moe.MlaMoeClassifier(mla_moe.AXK1_TOY, 16, dtype=jnp.float32)
    weights = fam.make_weights(5, config)
    covered = _load(model, fam.save_checkpoint(weights, str(tmp_path)), 32)
    variables = jax.tree.map(jnp.asarray, unflatten_dict(covered))
    rows = fam.make_data(5, config, 6, 2)[0]

    def forward(v, r):
        return model.apply(v, r, train=False, return_features=True,
                           mutable=["counters"])
    (logits, emb), state = (jax.jit(forward) if jit else forward)(
        variables, jnp.asarray(rows))
    np.testing.assert_allclose(np.asarray(logits)[:, :4],
                               AXK1_TOY_LOGITS_AT_THE_PARENT, atol=2e-6)
    np.testing.assert_allclose(np.asarray(emb)[:, :4],
                               AXK1_TOY_EMBEDDING_AT_THE_PARENT, atol=2e-6)
    digest = hashlib.sha256(np.asarray(logits).tobytes()
                            + np.asarray(emb).tobytes()).hexdigest()
    assert digest == AXK1_TOY_AT_THE_PARENT[jit]
    counters = flatten_dict(state["counters"])
    # The same slots give the same counters; one chunk an expert layer.
    assert [int(np.asarray(v).sum()) for k, v in sorted(counters.items())
            if k[-1] != "expert_trips"] == AXK1_TOY_COUNTERS_AT_THE_PARENT
    assert [int(np.asarray(v).sum()) for k, v in sorted(counters.items())
            if k[-1] == "expert_trips"] == [1, 1]
