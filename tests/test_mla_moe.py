"""The token encoder (models/mla_moe.py) against its plain reference
(benchmarks/families/mla_moe.py) at toy widths on the CPU: seeded weights
through the checkpoint file the program loads, float32 both sides, so the
two agree to rounding.  Forward (logits, embedding), one fit step (loss,
gradient norm, the head after it), the expert layer's shares, a router made
lopsided on purpose, and the frozen leaves after a fit and after rounds."""

import dataclasses
import json
import os
import subprocess
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

from active_learning_tpu.models import mla_moe  # noqa: E402
from active_learning_tpu.utils import pretrained  # noqa: E402

SEEDS = (5, 2 ** 31 + 17, 77)


def _toy_config(**over):
    with open(os.path.join(REPO, "tests/benchmark/toy/config_axk1.json")) as fh:
        return {**json.load(fh), **over}


@pytest.fixture(scope="module")
def fam():
    import families
    return families.load("benchmarks/families/mla_moe.py", REPO)


def _program(fam, config, seed, tmp, preset=mla_moe.AXK1_TOY):
    """(model, variables, weights): the preset holding the experts the
    configuration holds, its leaves read from the family's checkpoint file
    as ``Strategy`` reads them."""
    cfg = dataclasses.replace(
        preset, held_first=int(config["experts_held_first"]),
        held_count=int(config["n_routed_experts"]))
    model = mla_moe.MlaMoeClassifier(cfg, int(config["num_classes"]),
                                     dtype=jnp.float32)
    weights = fam.make_weights(seed, config)
    path = fam.save_checkpoint(weights, str(tmp))
    like = flatten_dict(jax.eval_shape(
        lambda k: {"params": model.init(
            k, jnp.zeros((1, config["row_len"]), jnp.float32),
            train=False)["params"]}, jax.random.PRNGKey(0)))
    covered = pretrained.map_torch_state(
        like, pretrained.load_torch_state_dict(path),
        key_map=model.torch_key_to_flax)
    assert set(covered) == set(like)
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
    want_logits = fam.head(p, want_emb)
    np.testing.assert_allclose(emb, want_emb, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
    assert emb.shape == (len(rows), config["hidden_size"])
    assert emb.dtype == jnp.float32


def test_encoder_leaves_are_bfloat16_and_the_head_float32(fam, tmp_path):
    _, variables, _ = _program(fam, _toy_config(), 1, tmp_path)
    flat = flatten_dict(variables)
    assert {v.dtype.name for p, v in flat.items() if p[1] == "encoder"} == {
        "bfloat16"}
    assert {v.dtype.name for p, v in flat.items() if p[1] == "linear"} == {
        "float32"}


@pytest.mark.parametrize("first", (0, 4, 8, 12))
def test_a_share_matches_the_references_share(fam, first, tmp_path):
    """Told to hold experts first .. first+3, the program computes what the
    reference computes when given the same share."""
    config = _toy_config(experts_held_first=first)
    model, variables, weights = _program(fam, config, 11, tmp_path)
    rows, _ = _rows(fam, config, 11)
    _, emb = model.apply(variables, jnp.asarray(rows), train=False,
                         return_features=True)
    want = fam.embed(_ref_params(weights), jnp.asarray(rows), config)
    np.testing.assert_allclose(emb, want, rtol=2e-4, atol=2e-5)


def _layer_inputs(fam, config, seed, n=48):
    key = jnp.asarray(fam.make_weights(seed, config)["encoder.key"])
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(
        (n, config["hidden_size"])).astype(np.float32))
    return key, x


def test_the_shares_add_up_to_the_uncut_layer(fam):
    """The parts that the shares 0-3, 4-7, 8-11, 12-15 give, the shared
    expert counted once, are what the uncut layer gives."""
    uncut = _toy_config(n_routed_experts=16)
    key, x = _layer_inputs(fam, uncut, 3)
    routed_all, shared = fam.moe_parts(
        x, fam.layer_tensors(key, 1, uncut), uncut)
    total = jnp.zeros_like(x)
    for first in (0, 4, 8, 12):
        share = _toy_config(experts_held_first=first)
        routed, shared_here = fam.moe_parts(
            x, fam.layer_tensors(key, 1, share), share)
        np.testing.assert_array_equal(shared_here, shared)
        total = total + routed
    np.testing.assert_allclose(total + shared, routed_all + shared,
                               rtol=1e-5, atol=1e-6)
    # Every token picked its k experts, somewhere.
    gates = fam.router_gates(x, fam.layer_tensors(key, 1, uncut)["mlp.gate"],
                             uncut)
    assert (np.sum(np.asarray(gates) > 0, axis=1)
            == uncut["num_experts_per_tok"]).all()
    np.testing.assert_allclose(np.sum(gates, axis=1),
                               uncut["routed_scaling_factor"], rtol=1e-5)


def _experts_module(config):
    cfg = dataclasses.replace(
        mla_moe.AXK1_TOY, held_first=int(config["experts_held_first"]),
        held_count=int(config["n_routed_experts"]))
    return mla_moe._Experts(cfg, jnp.float32)


def _experts_params(fam, w, config):
    held = list(fam.held_experts(config))
    p = {"gate": w["mlp.gate"]}
    for part in ("gate_proj", "up_proj", "down_proj"):
        p[f"experts_{part}"] = jnp.stack(
            [w[f"mlp.experts.{e}.{part}"] for e in held])
        p[f"shared_experts_{part}"] = w[f"mlp.shared_experts.{part}"]
    return {"params": p}


def test_the_programs_shares_add_up_to_the_uncut_layer(fam):
    uncut = _toy_config(n_routed_experts=16)
    key, x = _layer_inputs(fam, uncut, 4)
    routed_all, shared = fam.moe_parts(
        x, fam.layer_tensors(key, 1, uncut), uncut)
    total = jnp.zeros_like(x)
    for first in (0, 4, 8, 12):
        share = _toy_config(experts_held_first=first)
        w = fam.layer_tensors(key, 1, share)
        out = _experts_module(share).apply(_experts_params(fam, w, share),
                                           x[None])[0]
        total = total + (out - shared)
    np.testing.assert_allclose(total + shared, routed_all + shared,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("planted", ((2,), (0, 1, 2, 3)))
def test_no_token_is_dropped_under_a_lopsided_router(fam, planted):
    """Every token to the planted held experts: to ONE, its 48 tokens fill
    six tiles of eight rows, twice the 24 rows a chunk gives an expert, and
    the other held experts' tokens bring the slots to 120; to all four, the
    slots are 192, in two chunks too.  Each token gets every planted
    expert's part."""
    config = _toy_config()
    key, x = _layer_inputs(fam, config, 9)
    x = x + 4.0                       # a component the router can key on
    w = dict(fam.layer_tensors(key, 1, config))
    lop = np.asarray(w["mlp.gate"]).copy()
    lop[list(planted)] = 1.0          # each scores sigmoid(~256) on all
    w["mlp.gate"] = jnp.asarray(lop)
    out, state = _experts_module(config).apply(
        _experts_params(fam, w, config), x[None], mutable=["counters"])
    routed, shared = fam.moe_parts(x, w, config)
    gates = np.asarray(fam.router_gates(x, w["mlp.gate"], config))
    assert (gates[:, list(planted)] > 0).all()
    np.testing.assert_allclose(out[0], routed + shared, rtol=2e-4, atol=2e-5)
    counters = state["counters"]
    real = int(np.sum(counters["pairs_real"][0]))
    run = int(counters["pairs_run"][0])
    trips = int(counters["expert_trips"][0])
    assert real == int(np.sum(gates[:, :4] > 0)) >= len(x) * len(planted)
    assert run >= real and run % 8 == 0 and run - real < 4 * 8
    chunk = mla_moe.chunk_rows(len(x), 4, 16, 8)
    assert chunk == 24 and trips == 2
    assert run == (120 if len(planted) == 1 else 192)


def _plain_sum(flat, gates, stacks):
    """The held experts' part as a plain float32 sum: every expert over
    every token, weighted by its gate (0 where it was not chosen)."""
    out = jnp.zeros(flat.shape, jnp.float32)
    for e in range(gates.shape[1]):
        y = mla_moe._swiglu(flat, stacks[0][e], stacks[1][e], stacks[2][e],
                            jnp.float32)
        out = out + gates[:, e:e + 1] * y
    return out


def _routing(case, n, rng):
    """Seeded [n, 4] gates: what each held expert was chosen by."""
    gates = np.zeros((n, 4), np.float32)
    if case == "an_expert_empty":
        for t in range(n):
            gates[t, rng.choice([0, 1, 3], size=rng.integers(0, 3),
                                replace=False)] = 1.0
    elif case == "one_expert_many_chunks":
        gates[:, 1] = 1.0
    elif case == "padding_slots":
        for e, c in enumerate((3, 9, 17, 8)):
            gates[rng.choice(n, size=c, replace=False), e] = 1.0
    elif case == "every_token_on_every_expert":
        gates[:] = 1.0
    return gates * rng.uniform(0.1, 2.5, gates.shape).astype(np.float32)


# (routing, rows an expert takes a chunk: None for what chunk_rows gives a
# toy A.X-K1 layer of 40 tokens)
GROUPED_CASES = (("an_expert_empty", None), ("one_expert_many_chunks", 16),
                 ("padding_slots", 16), ("every_token_on_every_expert", None),
                 ("no_held_pair", None))


@pytest.mark.parametrize("case,chunk", GROUPED_CASES)
def test_the_grouped_layer_is_the_plain_per_expert_sum(case, chunk):
    """``run_held_pairs`` against a plain float32 sum over experts: an
    expert no token chose, every token on one expert (several chunks),
    counts that leave padding slots (chunks that split an expert), every
    token on every expert, no held pair at all.  The slots it reports are
    the parent's tile loop's (each expert's tokens rounded up to tiles of
    eight: ``tile_end[-1] * tile``), its trips the chunks the busiest
    expert's tokens take."""
    n, d, f, tile = 40, 16, 8, 8
    rng = np.random.default_rng(sum(map(ord, case)))
    flat = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    gates = jnp.asarray(_routing(case, n, rng))
    stacks = tuple(jnp.asarray(rng.standard_normal(s).astype(np.float32)
                               * s[-1] ** -0.5)
                   for s in ((4, f, d), (4, f, d), (4, d, f)))
    chunk = chunk or mla_moe.chunk_rows(n, 4, 16, tile)
    got, run, trips = jax.jit(
        lambda x, g, s: mla_moe.run_held_pairs(x, g, s, tile, chunk,
                                               jnp.float32))(
        flat, gates, stacks)
    np.testing.assert_allclose(got, _plain_sum(flat, gates, stacks),
                               rtol=1e-5, atol=1e-6)
    count = np.sum(np.asarray(gates) > 0, axis=0)
    assert int(run) == int(np.sum(-(-count // tile))) * tile
    assert int(trips) == -(-int(count.max()) // chunk)
    if case in ("one_expert_many_chunks", "every_token_on_every_expert"):
        assert int(trips) >= 2
    if case == "no_held_pair":
        assert int(run) == int(trips) == 0 and not np.any(np.asarray(got))


def test_a_chunk_holds_the_cells_routing():
    """An expert's rows a chunk, from the layer's shapes alone: an even
    routing's tokens an expert and three standard deviations, in whole
    tiles.  The A.X-K1 cell's experts see about 341 tokens a step (at most
    367 in the chip probe of PR 36), the LongCat cell's about 128 (at most
    153): one chunk each."""
    assert mla_moe.chunk_rows(8192, 8, 192, 512) == 512
    assert mla_moe.chunk_rows(8192, 12, 768, 128) == 256
    # Never more tiles than n tokens fill; a tile no larger than n.
    assert mla_moe.chunk_rows(48, 4, 4, 8) == 48
    assert mla_moe.chunk_rows(6, 4, 16, 8) == 6


def _attention_inputs(cfg, heads, t=512, seed=0):
    """``q`` ``[1, t, heads, d_qk]``, ``kv`` ``[1, t, heads, d_nope +
    d_v]`` and the shared rotary key ``[1, t, d_rope]``, float32 at a
    configuration's head widths (unit-variance heads, so the scores spread
    as the cells' do)."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(s), jnp.float32)
                 for s in ((1, t, heads, cfg.qk_head_dim),
                           (1, t, heads, cfg.qk_nope_head_dim
                            + cfg.v_head_dim),
                           (1, t, cfg.qk_rope_head_dim)))


def _encoder_presets():
    from active_learning_tpu.models import shortcut_moe
    return (mla_moe.AXK1_EP16_L7, shortcut_moe.LONGCAT_FLASH_EP32_L4)


@pytest.mark.parametrize("cfg", _encoder_presets(), ids=("axk1", "longcat"))
def test_the_fused_core_is_the_einsum_core(cfg):
    """The fused causal kernel (interpret mode) against the einsum form the
    CPU runs, at the cells' head widths (qk 128 + 64, v 128) and T = 512
    over four heads: both bfloat16 outputs of the same float32 steps, the
    kernel adding the key part's scores to the rotary part's where the
    einsum adds them in one product, so nearly every output is the same
    bits and the rest differ by one bfloat16 rounding, of the output or,
    where the output cancels to near zero, of a probability (a few
    2**-12 of the output's scale); the count says which form ran."""
    q, kv, k_r = _attention_inputs(cfg, heads=4)
    how = dict(scale=cfg.softmax_scale, dtype=jnp.bfloat16)
    want, none = mla_moe.causal_core(q, kv, k_r, **how)
    got, ran = mla_moe.fused_causal_core(q, kv, k_r, interpret=True, **how)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert got.shape == want.shape == (1, 512, 4, cfg.v_head_dim)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # One bfloat16 step (2**-7 of the value's binade) at each output.
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 2 ** -30)))
                  - 7)
    near_zero = 2.0 ** -12 * np.max(np.abs(want))
    assert np.all(np.abs(got - want) <= np.maximum(ulp, near_zero))
    assert np.mean(got == want) > 0.99
    assert none.tolist() == [0] and ran.tolist() == [1]


@pytest.mark.parametrize("cfg", _encoder_presets(), ids=("axk1", "longcat"))
def test_the_fused_core_is_causal(cfg):
    """A token changed at position s (its query, key, value and rotary key)
    leaves every output before s bit-identical and moves the outputs from
    s on: the kernel's mask is the causal one, across its blocks of
    queries."""
    inputs = _attention_inputs(cfg, heads=2)
    how = dict(scale=cfg.softmax_scale, dtype=jnp.bfloat16, interpret=True)
    base = np.asarray(mla_moe.fused_causal_core(*inputs, **how)[0],
                      np.float32)
    for s in (1, 127, 255, 256, 300, 511):
        moved = np.asarray(mla_moe.fused_causal_core(
            *(a.at[:, s].add(1.0) for a in inputs), **how)[0], np.float32)
        assert np.array_equal(moved[:, :s], base[:, :s]), s
        assert not np.array_equal(moved[:, s], base[:, s]), s


def test_a_step_holds_the_heads_its_vmem_allows():
    """Heads a grid step holds, from the sequence and head widths: eight of
    the cells' 64 at T = 512 in bfloat16, fewer as T grows, and none where
    one head's blocks outgrow VMEM or a key or value width is not whole
    vregs (the model then keeps the einsum form on every platform)."""
    from active_learning_tpu.ops.attention import heads_per_step
    assert heads_per_step(64, 512, 128, 64, 128, 2) == 8
    assert heads_per_step(64, 512, 128, 64, 128, 4) == 4
    assert heads_per_step(64, 2048, 128, 64, 128, 2) == 1
    assert heads_per_step(64, 4096, 128, 64, 128, 2) == 0
    assert heads_per_step(4, 32, 16, 8, 16, 4) == 0


def test_the_model_factory_imports_no_pallas():
    """The kernel's module is imported by the programs that run it: building
    any model, the ResNets' among them, and the experiment driver leave
    Pallas out of the process."""
    code = ("import sys, active_learning_tpu.models.factory, "
            "active_learning_tpu.experiment.driver; "
            "print(sorted(m for m in sys.modules if 'pallas' in m "
            "or m.endswith('ops.attention')))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "[]"


def _trainer(model, lr=0.1):
    from active_learning_tpu.config import (LoaderConfig, OptimizerConfig,
                                            SchedulerConfig, TrainConfig)
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.train.trainer import Trainer
    train_cfg = TrainConfig(
        eval_split=0.0, loader_tr=LoaderConfig(batch_size=8),
        loader_te=LoaderConfig(batch_size=8),
        optimizer=OptimizerConfig("sgd", lr=lr, weight_decay=0.0,
                                  momentum=0.9),
        scheduler=SchedulerConfig("step", step_size=20, gamma=0.1))
    mesh = mesh_lib.make_mesh()
    return Trainer(model, train_cfg, mesh, model.num_classes), mesh


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_one_fit_step_matches_the_reference(fam, seed, tmp_path):
    """Loss, gradient norm and the head after one SGD step; the optimizer
    state and the gradient cover the head alone."""
    from active_learning_tpu.data.core import TOKEN_VIEW
    from active_learning_tpu.parallel import mesh as mesh_lib
    from lib import reference as ref_lib
    config = _toy_config()
    model, variables, weights = _program(fam, config, seed, tmp_path)
    rows, labels = _rows(fam, config, seed, n=8)
    trainer, mesh = _trainer(model)
    state = trainer.state_of(mesh_lib.replicate(variables, mesh))
    assert set(state.params) == {"linear"} and set(state.frozen) == {
        "encoder"}
    assert set(state.opt_state["trace"]) == {"linear"}
    frozen_before = jax.tree.leaves(state.frozen)
    batch = mesh_lib.shard_batch(
        {"image": rows, "label": labels.astype(np.int32),
         "mask": np.ones(8, np.float32)}, mesh)
    new_state, loss, gnorm = trainer._train_step(
        state, batch, jax.random.PRNGKey(0), jnp.float32(0.1),
        jnp.ones(config["num_classes"], jnp.float32), view=TOKEN_VIEW)
    step = ref_lib._step_fn(fam, ref_lib._config_key(config), None, True,
                            0.9, 0.0, True)
    trained = {k: jnp.asarray(weights[k]) for k in fam.trainable_keys(
        weights, head_only=True)}
    fixed = {"encoder.key": jnp.asarray(weights["encoder.key"])}
    momentum = {k: jnp.zeros_like(v) for k, v in trained.items()}
    want, _, want_loss, want_gnorm = step(
        trained, momentum, fixed, jnp.asarray(rows)[None],
        jnp.asarray(labels.astype(np.int32))[None], jnp.ones((1, 8)),
        jnp.asarray(np.zeros(2, np.uint32)), jnp.asarray(False),
        jnp.float32(0.1))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-4)
    assert float(gnorm) == pytest.approx(float(want_gnorm), rel=1e-3)
    got = fam.program_params(jax.tree.map(np.asarray, new_state.params),
                             weights)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-3, atol=1e-6)
    # The frozen leaves are the very arrays that went in: not donated, not
    # copied, still alive.
    for before, after in zip(frozen_before,
                             jax.tree.leaves(new_state.frozen)):
        assert after is before and not before.is_deleted()
    assert {k: int(v) for k, v in new_state.counters.items()}[
        "pairs_real"] > 0


@pytest.fixture(scope="module")
def rounds(tmp_path_factory, fam):
    """Three rounds of the toy encoder through ``run_experiment``, its
    weights from the family's checkpoint file, the recorder on."""
    from active_learning_tpu.config import (ExperimentConfig,
                                            PretrainedConfig,
                                            TelemetryConfig)
    from active_learning_tpu.experiment import arg_pools, driver
    tmp = tmp_path_factory.mktemp("axk1_rounds")
    config = _toy_config()
    weights = fam.make_weights(21, config)
    path = fam.save_checkpoint(weights, str(tmp))
    data = fam.datasets(config, fam.make_data(21, config, 96, 24)[:2],
                        fam.make_data(21, config, 96, 24)[2:])
    train_cfg = dataclasses.replace(
        arg_pools.get_train_config("ssp_linear_evaluation",
                                   "synthetic_tokens"),
        pretrained=PretrainedConfig(path=path))
    cfg = ExperimentConfig(
        exp_name="axk1", exp_hash="t", dataset="synthetic_tokens",
        model="AXK1_TOY", strategy="MarginSampler", freeze_feature=True,
        rounds=3, round_budget=8, init_pool_size=32, n_epoch=2,
        early_stop_patience=0, log_dir=str(tmp / "logs"),
        ckpt_path=str(tmp / "ckpt"),
        telemetry=TelemetryConfig(export_trace=True))
    strategy = driver.run_experiment(cfg, data=data, train_cfg=train_cfg)
    import glob
    trace, = glob.glob(str(tmp / "logs" / "**" / "trace.json"),
                       recursive=True)
    with open(trace) as fh:
        events = [e for e in json.load(fh)["traceEvents"]
                  if e.get("ph") == "X"]
    state = pretrained.load_torch_state_dict(path)
    return {"strategy": strategy, "events": events, "file": state,
            "ckpt_dir": str(tmp / "ckpt"), "weights": weights}


def test_frozen_leaves_are_the_files_bytes_after_rounds(rounds):
    strategy = rounds["strategy"]
    flat = flatten_dict({"params": strategy.state.frozen})
    like = {p: v for p, v in flat.items()}
    want = pretrained.map_torch_state(
        {**like, ("params", "linear", "kernel"): strategy.state.params[
            "linear"]["kernel"], ("params", "linear", "bias"):
            strategy.state.params["linear"]["bias"]},
        rounds["file"], key_map=strategy.model.torch_key_to_flax)
    assert len(flat) == 46
    for path, leaf in flat.items():
        assert np.asarray(leaf).tobytes() == want[path].tobytes(), path
    # ... and the arrays the strategy loaded once, not copies of them.
    template = flatten_dict({"params": strategy._reinit_template["frozen"]})
    assert all(flat[p] is template[p] for p in flat)


def test_no_checkpoint_file_holds_a_frozen_leaf(rounds):
    from active_learning_tpu.train import checkpoint as ckpt_lib
    names = [n for n in os.listdir(os.path.join(rounds["ckpt_dir"], "axk1_t"))
             if n.endswith(".msgpack")]
    assert len(names) >= 6            # best and current of three rounds
    for name in names:
        tree = ckpt_lib.load_variables(
            os.path.join(rounds["ckpt_dir"], "axk1_t", name))
        assert set(tree["params"]) == {"linear"}
        assert not tree.get("batch_stats")


def test_spans_count_the_head_alone_and_the_pairs(rounds):
    by = {}
    for e in rounds["events"]:
        by.setdefault(e["name"], []).append(e["args"])
    head = (64 + 1) * 16 * 4
    for name in ("ckpt/publish_best", "ckpt/save_current", "ckpt/load_best"):
        assert {a["bytes"] for a in by[name]} == {head}, name
    assert {a["bytes"] for a in by["ckpt/round_snapshot"]} <= {0, head}
    assert {a["bytes"] for a in by["reinit/apply"]} == {head}
    assert {a["leaves_frozen"] for a in by["reinit/apply"]} == {46}
    assert {a["leaves_copied"] for a in by["reinit/apply"]} == {2}
    assert len(by["encoder/load"]) == 1          # read and uploaded once
    assert by["encoder/load"][0]["leaves"] == 46
    for a in by["collect_pool"] + by["epoch"]:
        assert a["tokens"] == a["rows"] * 32
        assert 0 < a["pairs_real"] <= a["pairs_run"]
        # A chunk of slots a trip, each trip at least a tile of eight.
        assert 0 < a["expert_trips"] <= a["pairs_run"] // 8
        # The CPU runs the einsum core: no call ran the fused kernel.
        assert a["attn_fused"] == 0


def test_presets_are_the_benchmarks_configurations():
    """The program's presets and the benchmark's configuration files state
    the same model, key for key."""
    with open(os.path.join(
            REPO, "benchmarks/configs/axk1_ep16_l7.json")) as fh:
        full = json.load(fh)
    for config, preset in ((full, mla_moe.AXK1_EP16_L7),
                           (_toy_config(), mla_moe.AXK1_TOY)):
        for field in dataclasses.fields(preset):
            if field.name in config and field.name != "n_routed_experts":
                assert getattr(preset, field.name) == config[field.name]
        assert preset.n_routed_experts == config["experts_routed_over"]
        assert preset.held_count == config["n_routed_experts"]
        assert preset.held_first == config["experts_held_first"]
        rs = config["rope_scaling"]
        assert (preset.rope_factor, preset.rope_original_max_position,
                preset.rope_beta_fast, preset.rope_beta_slow) == (
            rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"])
    assert mla_moe.AXK1_EP16_L7.softmax_scale == pytest.approx(0.130861,
                                                               rel=1e-5)


def test_cli_takes_what_the_registry_holds():
    from active_learning_tpu.experiment import cli
    from active_learning_tpu.registry import MODELS
    args = cli.get_parser().parse_args(
        ["--model", "AXK1_TOY", "--dataset", "synthetic_tokens",
         "--freeze_feature"])
    assert args.model in MODELS.names() and "SSLResNet50" in MODELS.names()
    with pytest.raises(SystemExit):
        cli.get_parser().parse_args(["--model", "no_such_model"])


def test_the_encoder_is_built_for_linear_evaluation_only():
    """``--freeze_feature`` is no dead option for this backbone: without it
    the registry's factory refuses, with it the frozen set is the encoder."""
    from active_learning_tpu.models import backbone, factory
    with pytest.raises(ValueError, match="--freeze_feature"):
        factory.get_network("synthetic_tokens", "AXK1_TOY")
    model = factory.get_network("synthetic_tokens", "AXK1_TOY",
                                freeze_feature=True)
    assert model.freeze_feature is True
    assert backbone.frozen_prefixes(model) == ("encoder",)
