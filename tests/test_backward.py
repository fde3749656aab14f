"""The gradient path, proven (ISSUE 10, DESIGN.md §4):

  * the two hand-written backwards (ops/backward.py: the s2d stem
    conv's f32-accumulated dW, FusedBatchNorm's bf16-reads/f32-
    accumulation backward) are gradient-equivalent to the flax/XLA-
    derived backward — proven the same way the s2d FORWARD was:
    rounding-order tolerance at bf16, ~1e-10 identity at f64;
  * the fused optimizer update is BIT-identical to the optax chain at
    f32 state (and at bf16 momentum still learns, bounded-delta),
    end-to-end: a 2-round driver run with the fused path on vs off
    produces bit-identical experiment_state;
  * ``Trainer.reinit_optimizer`` reuses the donated momentum buffers at
    round boundaries instead of re-allocating;
  * the int8 block-scaled gradient all-reduce stays inside its error
    bound on the multi-device CPU mesh and the driver's learning-probe
    gate passes (its accuracy-delta bound pinned here).

``PARITY_TESTED_VJPS`` is the registered half of trace_lint check 9's
closed registry: it must match ops/backward.TRAIN_PATH_VJPS exactly, so
a custom backward without a parity test here can never land.
"""

import dataclasses
import gc
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax import lax

from active_learning_tpu.ops import backward as backward_ops

# The closed-registry handshake with scripts/trace_lint.py check 9:
# every entry of ops/backward.TRAIN_PATH_VJPS must appear here, and the
# classes below must actually test each one.
PARITY_TESTED_VJPS = ("stem_conv", "fused_bn_train")

PAD = ((2, 1), (2, 1))
_DN = ("NHWC", "HWIO", "NHWC")


def test_registry_matches_ops_module():
    assert set(PARITY_TESTED_VJPS) == set(backward_ops.TRAIN_PATH_VJPS)


def _ref_stem_conv(x, k, dt):
    """The exact flax nn.Conv chain stem_conv replaces: promote both
    operands to the compute dtype, stride-1 NHWC conv."""
    return lax.conv_general_dilated(x.astype(dt), k.astype(dt), (1, 1),
                                    PAD, dimension_numbers=_DN)


class TestStemConvVJP:
    def _data(self, seed=0, b=2, hw=12, c=12, f=16):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(b, hw, hw, c)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(4, 4, c, f)), jnp.float32)
        cot = jnp.asarray(rng.normal(size=(b, hw, hw, f)), jnp.float32)
        return x, k, cot

    def test_forward_bit_identical_to_nn_conv(self):
        """The primal is the SAME conv flax emits — forward parity
        contracts (s2d logits equivalence, checkpoint trees) hold
        bit-for-bit in both compute dtypes."""
        x, k, _ = self._data()
        for dt in (jnp.float32, jnp.bfloat16):
            ref = _ref_stem_conv(x, k, dt)
            got = backward_ops.stem_conv(x, k, dtype=dt, padding=PAD)
            assert got.dtype == ref.dtype
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(ref, np.float32))

    def _grads(self, fn, x, k, cot):
        def loss(x_, k_):
            return jnp.sum((fn(x_, k_) * cot.astype(fn(x_, k_).dtype))
                           .astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1))(x, k)

    def test_grads_match_xla_derived_f32(self):
        """At f32 the hand-written backward emits the same convs XLA's
        transpose rule derives — grads agree to reduction-order
        rounding (measured bit-identical on XLA:CPU; pinned to 1e-6)."""
        x, k, cot = self._data()
        gx_r, gk_r = self._grads(
            lambda a, b: _ref_stem_conv(a, b, jnp.float32), x, k, cot)
        gx_c, gk_c = self._grads(
            lambda a, b: backward_ops.stem_conv(a, b, dtype=jnp.float32,
                                                padding=PAD), x, k, cot)
        np.testing.assert_allclose(gx_c, gx_r, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(gk_c, gk_r, rtol=1e-6, atol=1e-6)

    def test_grads_match_xla_derived_bf16_tolerance(self):
        """bf16 compute: dx identical (same transposed conv); dW agrees
        to bf16 rounding order — the f32 ACCUMULATION changes rounding,
        never the math (the f64 test below pins the identity)."""
        x, k, cot = self._data(seed=1)
        xb = x.astype(jnp.bfloat16)
        gx_r, gk_r = self._grads(
            lambda a, b: _ref_stem_conv(a, b, jnp.bfloat16), xb, k, cot)
        gx_c, gk_c = self._grads(
            lambda a, b: backward_ops.stem_conv(a, b, dtype=jnp.bfloat16,
                                                padding=PAD), xb, k, cot)
        np.testing.assert_array_equal(np.asarray(gx_c, np.float32),
                                      np.asarray(gx_r, np.float32))
        np.testing.assert_allclose(np.asarray(gk_c), np.asarray(gk_r),
                                   rtol=2e-2, atol=2e-2)

    def test_f64_identity(self):
        """The identity proof: at f64 every cast is a no-op and the
        hand-written formulas must reproduce autodiff to accumulated
        rounding noise (~1e-10) — the bf16 delta above is rounding
        order, not an algebraic error."""
        with jax.enable_x64(True):
            rng = np.random.default_rng(2)
            x = jnp.asarray(rng.normal(size=(2, 10, 10, 12)))
            k = jnp.asarray(rng.normal(size=(4, 4, 12, 8)))
            cot = jnp.asarray(rng.normal(size=(2, 10, 10, 8)))
            gx_r, gk_r = self._grads(
                lambda a, b: _ref_stem_conv(a, b, jnp.float64), x, k, cot)
            gx_c, gk_c = self._grads(
                lambda a, b: backward_ops.stem_conv(
                    a, b, dtype=jnp.float64, padding=PAD), x, k, cot)
            np.testing.assert_allclose(gx_c, gx_r, rtol=1e-10, atol=1e-10)
            np.testing.assert_allclose(gk_c, gk_r, rtol=1e-10, atol=1e-10)

    def test_bf16_dw_no_less_accurate_than_xla_derivation(self):
        """The point of the custom dW: f32 accumulation over bf16 reads
        is at least as close to the f64 truth as XLA's bf16-accumulate-
        then-cast derivation (strictly closer as the contraction
        grows; never worse)."""
        x, k, cot = self._data(seed=3, b=4, hw=16, c=12, f=24)
        with jax.enable_x64(True):
            x64 = jnp.asarray(np.asarray(x), jnp.float64)
            k64 = jnp.asarray(np.asarray(k), jnp.float64)
            cot64 = jnp.asarray(np.asarray(cot), jnp.float64)
            dw_true = np.asarray(jax.grad(
                lambda k_: jnp.sum(_ref_stem_conv(x64, k_, jnp.float64)
                                   * cot64))(k64))
        _, dw_xla = self._grads(
            lambda a, b: _ref_stem_conv(a, b, jnp.bfloat16),
            x.astype(jnp.bfloat16), k, cot)
        _, dw_cust = self._grads(
            lambda a, b: backward_ops.stem_conv(a, b, dtype=jnp.bfloat16,
                                                padding=PAD),
            x.astype(jnp.bfloat16), k, cot)
        e_xla = np.linalg.norm(np.asarray(dw_xla, np.float64) - dw_true)
        e_cust = np.linalg.norm(np.asarray(dw_cust, np.float64) - dw_true)
        assert e_cust <= e_xla * 1.05, (
            f"f32-accumulated dW err {e_cust:.3e} worse than XLA's "
            f"bf16 derivation {e_xla:.3e}")

    def test_model_level_s2d_grads_match_nn_conv_model(self):
        """Through the real module: an s2d-stem encoder's gradients
        (S2DStemConv, custom VJP) match a twin whose stem is the plain
        nn.Conv it replaced — at f32, to reduction-order rounding."""
        from flax import linen as nn

        from active_learning_tpu.models import resnet

        class _Twin(nn.Module):
            custom: bool = True

            @nn.compact
            def __call__(self, x):
                if self.custom:
                    y = resnet.S2DStemConv(8, dtype=jnp.float32,
                                           name="conv_stem")(x)
                else:
                    y = nn.Conv(8, (4, 4), (1, 1),
                                padding=[(2, 1), (2, 1)], use_bias=False,
                                dtype=jnp.float32,
                                kernel_init=resnet.conv_kernel_init,
                                name="conv_stem")(x)
                return jnp.sum(y.astype(jnp.float32) ** 2)

        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(2, 8, 8, 12)), jnp.float32)
        v = _Twin(custom=True).init(jax.random.PRNGKey(0), x)
        g_c = jax.grad(lambda p: _Twin(custom=True).apply(p, x))(v)
        g_r = jax.grad(lambda p: _Twin(custom=False).apply(p, x))(v)
        leaves_c = jax.tree.leaves(g_c)
        leaves_r = jax.tree.leaves(g_r)
        for a, b in zip(leaves_c, leaves_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


class TestFusedBNVJP:
    def _data(self, seed=0, shape=(4, 6, 6, 16)):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=shape) * 2 + 1, jnp.float32)
        scale = jnp.asarray(rng.normal(size=shape[-1:]) + 1.0, jnp.float32)
        bias = jnp.asarray(rng.normal(size=shape[-1:]), jnp.float32)
        cot = jnp.asarray(rng.normal(size=shape), jnp.float32)
        return x, scale, bias, cot

    @staticmethod
    def _ref(x, scale, bias, dt, eps=1e-5):
        """The pre-custom-VJP FusedBatchNorm train-branch math, inline
        (autodiff of THIS is the XLA-derived backward being matched)."""
        acc = jnp.promote_types(dt, jnp.float32)
        xs = x.astype(dt)
        mean = jnp.mean(xs, (0, 1, 2), dtype=acc)
        mean2 = jnp.mean(lax.square(xs.astype(acc)), (0, 1, 2))
        var = jnp.maximum(mean2 - lax.square(mean), 0.0)
        mul = (scale * lax.rsqrt(var + eps)).astype(dt)
        sub = mean.astype(dt) * mul - bias.astype(dt)
        return x.astype(dt) * mul - sub

    @staticmethod
    def _cust(x, scale, bias, dt, eps=1e-5):
        return backward_ops.fused_bn_train(x, scale, bias, dtype=dt,
                                           epsilon=eps)[0]

    def _grads(self, fn, x, scale, bias, cot, dt):
        def loss(x_, s_, b_):
            y = fn(x_, s_, b_, dt)
            return jnp.sum((y * cot.astype(y.dtype)).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(x, scale, bias)

    def test_forward_bit_identical(self):
        x, scale, bias, _ = self._data()
        for dt in (jnp.float32, jnp.bfloat16):
            ref = self._ref(x.astype(dt) if dt == jnp.bfloat16 else x,
                            scale, bias, dt)
            got = self._cust(x.astype(dt) if dt == jnp.bfloat16 else x,
                             scale, bias, dt)
            assert got.dtype == ref.dtype
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(ref, np.float32))

    def test_grads_match_xla_derived_f32(self):
        x, scale, bias, cot = self._data(seed=1)
        g_r = self._grads(self._ref, x, scale, bias, cot, jnp.float32)
        g_c = self._grads(self._cust, x, scale, bias, cot, jnp.float32)
        for a, b in zip(g_c, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)

    def test_grads_match_xla_derived_bf16_tolerance(self):
        x, scale, bias, cot = self._data(seed=2)
        xb = x.astype(jnp.bfloat16)
        g_r = self._grads(self._ref, xb, scale, bias, cot, jnp.bfloat16)
        g_c = self._grads(self._cust, xb, scale, bias, cot, jnp.bfloat16)
        # dscale/dbias fold Σgy·x − Σgy·mean style cancellations whose
        # bf16 reduction-order differences reach a few percent of the
        # tensor max — rounding order, not algebra (the f64 test pins
        # the identity at 1e-10).
        for a, b, tol in zip(g_c, g_r, (3e-2, 6e-2, 6e-2)):
            a32 = np.asarray(a, np.float32)
            b32 = np.asarray(b, np.float32)
            ref_mag = float(np.max(np.abs(b32))) + 1e-12
            assert float(np.max(np.abs(a32 - b32))) <= tol * ref_mag

    def test_f64_identity(self):
        with jax.enable_x64(True):
            rng = np.random.default_rng(3)
            x = jnp.asarray(rng.normal(size=(3, 5, 5, 8)) + 0.5)
            scale = jnp.asarray(rng.normal(size=(8,)) + 1.0)
            bias = jnp.asarray(rng.normal(size=(8,)))
            cot = jnp.asarray(rng.normal(size=x.shape))
            g_r = self._grads(self._ref, x, scale, bias, cot, jnp.float64)
            g_c = self._grads(self._cust, x, scale, bias, cot, jnp.float64)
            for a, b in zip(g_c, g_r):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-10, atol=1e-10)

    def test_f64_identity_vs_flax_batchnorm(self):
        """At f64 the fused-stats math and flax's materialize-as-f32
        BatchNorm are the SAME function — gradients through the real
        modules (custom VJP vs flax autodiff) agree to ~1e-10, tying
        the custom backward to the flax reference, not just to our own
        forward."""
        from flax import linen as nn

        from active_learning_tpu.models.resnet import FusedBatchNorm

        with jax.enable_x64(True):
            rng = np.random.default_rng(4)
            x = jnp.asarray(rng.normal(size=(4, 5, 5, 6)) + 1.0)
            cot = jnp.asarray(rng.normal(size=x.shape))
            fused = FusedBatchNorm(use_running_average=False,
                                   dtype=jnp.float64)
            ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                               epsilon=1e-5, dtype=jnp.float64)
            v = fused.init(jax.random.PRNGKey(0), x)
            v = jax.tree.map(
                lambda l: l + 0.1 * np.arange(l.size).reshape(l.shape)
                if l.ndim else l, v)

            def loss(module):
                def inner(params):
                    y, _ = module.apply(
                        {"params": params,
                         "batch_stats": v["batch_stats"]},
                        x, mutable=["batch_stats"])
                    return jnp.sum(y * cot)
                return inner

            g_f = jax.grad(loss(fused))(v["params"])
            g_r = jax.grad(loss(ref))(v["params"])
            for a, b in zip(jax.tree.leaves(g_f), jax.tree.leaves(g_r)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-10, atol=1e-10)

    def test_running_stats_update_unchanged(self):
        """The EMA update rides the custom VJP's returned mean/var —
        batch_stats after one train-mode apply are bit-identical to the
        inline-math module the custom replaced."""
        from active_learning_tpu.models.resnet import FusedBatchNorm

        x, scale, bias, _ = self._data(seed=5)
        xb = x.astype(jnp.bfloat16)
        mod = FusedBatchNorm(use_running_average=False,
                             dtype=jnp.bfloat16)
        v = mod.init(jax.random.PRNGKey(0), xb)
        _, mut = mod.apply(v, xb, mutable=["batch_stats"])
        # Reference EMA from the same forward math.
        acc = jnp.float32
        mean = jnp.mean(xb, (0, 1, 2), dtype=acc)
        mean2 = jnp.mean(lax.square(xb.astype(acc)), (0, 1, 2))
        var = jnp.maximum(mean2 - lax.square(mean), 0.0)
        np.testing.assert_array_equal(
            np.asarray(mut["batch_stats"]["mean"]),
            np.asarray(0.9 * v["batch_stats"]["mean"] + 0.1 * mean))
        np.testing.assert_array_equal(
            np.asarray(mut["batch_stats"]["var"]),
            np.asarray(0.9 * v["batch_stats"]["var"] + 0.1 * var))


class TestFusedOptimizerParity:
    def _trees(self, seed=0):
        rng = np.random.default_rng(seed)
        params = {"a": jnp.asarray(rng.normal(size=(33, 7)), jnp.float32),
                  "b": {"w": jnp.asarray(rng.normal(size=(130,)),
                                         jnp.float32)}}
        return params

    @pytest.mark.parametrize("wd", [0.0, 5e-4])
    def test_bit_parity_vs_optax_chain(self, wd):
        """The fused leaf expression is the optax chain's scalar op
        sequence exactly: several steps of both paths stay bit-equal,
        with and without weight decay."""
        from active_learning_tpu.config import OptimizerConfig, TrainConfig
        from active_learning_tpu.train import optim as optim_lib

        cfg = TrainConfig(optimizer=OptimizerConfig(
            name="sgd", lr=0.1, momentum=0.9, weight_decay=wd))
        fused = optim_lib.make_fused_optimizer(cfg)
        assert fused is not None
        tx = optim_lib.make_optimizer(cfg.optimizer)

        params_f = self._trees()
        params_o = jax.tree.map(jnp.copy, params_f)
        state_f = fused.init(params_f)
        state_o = tx.init(params_o)
        rng = np.random.default_rng(1)
        for step in range(5):
            grads = jax.tree.map(
                lambda p: jnp.asarray(rng.normal(size=p.shape),
                                      jnp.float32), params_f)
            lr = jnp.float32(0.1 * (0.9 ** step))
            params_f, state_f = fused.update(grads, state_f, params_f, lr)
            updates, state_o = tx.update(grads, state_o, params_o)
            updates = jax.tree.map(lambda u: -lr * u, updates)
            params_o = optax.apply_updates(params_o, updates)
            for a, b in zip(jax.tree.leaves(params_f),
                            jax.tree.leaves(params_o)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fused_on_rule(self):
        from active_learning_tpu.config import OptimizerConfig, TrainConfig
        from active_learning_tpu.train import optim as optim_lib

        sgd = TrainConfig(optimizer=OptimizerConfig(name="sgd"))
        adam = TrainConfig(optimizer=OptimizerConfig(name="adam"))
        assert optim_lib.make_fused_optimizer(sgd) is not None
        assert optim_lib.make_fused_optimizer(
            dataclasses.replace(sgd, fused_optimizer="off")) is None
        assert optim_lib.make_fused_optimizer(adam) is None
        with pytest.raises(ValueError):
            optim_lib.make_fused_optimizer(
                dataclasses.replace(adam, fused_optimizer="on"))

    def test_bf16_state_halves_bytes_and_learns(self):
        """bf16 momentum: half the optimizer HBM, and the bounded-delta
        learn contract — the probe fit reaches the f32 twin's accuracy
        within 0.1 on the deterministic synthetic task."""
        from active_learning_tpu.config import (LoaderConfig,
                                                OptimizerConfig,
                                                SchedulerConfig,
                                                TrainConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.train.trainer import Trainer

        from helpers import TinyClassifier

        data = get_data_synthetic(n_train=96, n_test=128, num_classes=4,
                                  image_size=16, seed=7)
        mesh = mesh_lib.make_mesh()
        base = TrainConfig(
            loader_tr=LoaderConfig(batch_size=16),
            loader_te=LoaderConfig(batch_size=16),
            optimizer=OptimizerConfig(name="sgd", lr=0.3,
                                      weight_decay=5e-4),
            scheduler=SchedulerConfig(name="cosine", t_max=8),
            resident_scoring_bytes=0)

        def fit_acc(state_dtype):
            cfg = dataclasses.replace(base,
                                      optim_state_dtype=state_dtype)
            tr = Trainer(TinyClassifier(), cfg, mesh, 4)
            st = tr.init_state(jax.random.PRNGKey(1),
                               data[2].gather(np.zeros(1, np.int64)))
            if state_dtype == "bf16":
                trace = jax.tree.leaves(st.opt_state)
                assert all(t.dtype == jnp.bfloat16 for t in trace)
                f32_bytes = sum(p.nbytes for p in
                                jax.tree.leaves(st.params))
                assert sum(t.nbytes for t in trace) == f32_bytes // 2
            res = tr.fit(st, data[2], np.arange(len(data[2])), data[2],
                         np.array([], np.int64), n_epoch=8,
                         es_patience=0, rng=np.random.default_rng(1))
            m = tr.evaluate(res.state, data[1],
                            np.arange(len(data[1])))
            return float(m["accuracy"])

        acc_f32 = fit_acc("f32")
        acc_bf16 = fit_acc("bf16")
        assert acc_f32 >= 0.9  # the task saturates; a broken path won't
        assert abs(acc_f32 - acc_bf16) <= 0.1, (
            f"bf16 momentum delta too large: {acc_f32} vs {acc_bf16}")


class TestReinitOptimizerReuse:
    def _trainer_and_state(self):
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.train.trainer import Trainer

        from helpers import TinyClassifier, tiny_train_config

        train_set, _, al_set = get_data_synthetic(n_train=64, n_test=16)
        mesh = mesh_lib.make_mesh()
        trainer = Trainer(TinyClassifier(), tiny_train_config(), mesh, 4)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_set.gather(np.arange(2)))
        return trainer, state, train_set, al_set

    def test_round_boundary_reuses_buffers_without_reallocation(self):
        """The satellite pin: reinit zeroes the donated momentum tree
        through ONE jitted executable (no per-round host re-build +
        re-upload), keeps shapes/dtypes/sharding, and no extra device
        allocation survives the round boundary (live-array census flat
        across repeated reinits; on TPU the donation also reuses the
        buffers in place — CPU lacks aliasing, so the census is the
        portable assertion)."""
        trainer, state, train_set, al_set = self._trainer_and_state()
        assert trainer.fused_tx is not None
        # Make the momentum non-zero so zeroing is observable.
        res = trainer.fit(state, train_set, np.arange(32), al_set,
                          np.arange(56, 64), n_epoch=1, es_patience=0,
                          rng=np.random.default_rng(0))
        state = res.state
        shapes = jax.tree.map(lambda l: (l.shape, str(l.dtype)),
                              state.opt_state)
        state = trainer.reinit_optimizer(state)
        assert trainer._reinit_opt is not None
        assert trainer._reinit_opt._cache_size() == 1
        assert jax.tree.map(lambda l: (l.shape, str(l.dtype)),
                            state.opt_state) == shapes
        assert all(float(jnp.max(jnp.abs(l))) == 0.0
                   for l in jax.tree.leaves(state.opt_state))
        gc.collect()
        census = len(jax.live_arrays())
        for _ in range(3):
            state = trainer.reinit_optimizer(state)
        gc.collect()
        assert len(jax.live_arrays()) <= census
        # ... and still exactly one compiled executable (warm rounds
        # add zero compiles).
        assert trainer._reinit_opt._cache_size() == 1

    def test_stale_optax_fit_state_discarded_not_crashed(self, tmp_path):
        """A mid-round fit state written by the OPTAX path (pre-fused
        checkpoint, or a --fused_optimizer flip between launch and
        resume) has a different opt_state pytree layout: the fused
        trainer must discard it and restart the round from scratch —
        never crash the resume on the layout mismatch."""
        from active_learning_tpu.train import checkpoint as ckpt_lib

        trainer, state, train_set, al_set = self._trainer_and_state()
        assert trainer.fused_tx is not None
        # An optax-layout opt_state, serialized the way save_fit_state
        # would have under fused_optimizer=off.
        optax_state = trainer.tx.init(
            jax.tree.map(np.asarray, state.params))
        paths = ckpt_lib.weight_paths(str(tmp_path), "fusedmig", "t", 0)
        ckpt_lib.save_fit_state(
            paths["fit_state"], variables=state.variables,
            opt_state=optax_state, step=jnp.int32(4), epoch=1,
            round_idx=0, best_perf=0.5, best_epoch=1, es_count=0,
            key=jax.random.PRNGKey(3), rng=np.random.default_rng(3))
        res = trainer.fit(state, train_set, np.arange(32), al_set,
                          np.arange(56, 64), n_epoch=2, es_patience=2,
                          rng=np.random.default_rng(0), round_idx=0,
                          weight_paths=paths, resume_fit_state=True)
        # The round ran FROM SCRATCH (both epochs), and the stale state
        # is gone so a later resume can't trip over it either.
        assert res.epochs_run == 2
        assert ckpt_lib.load_fit_state(paths["fit_state"], 0) is None

    def test_reinit_falls_back_on_dead_buffers(self):
        """A failed round attempt's restore leaves the donated
        opt_state of the crashed fit behind — reinit must detect the
        dead buffers and re-init fresh instead of reading them."""
        trainer, state, _, _ = self._trainer_and_state()
        # Simulate the donated-away state: delete the buffers.
        for leaf in jax.tree.leaves(state.opt_state):
            leaf.delete()
        state2 = trainer.reinit_optimizer(state)
        assert all(float(jnp.max(jnp.abs(l))) == 0.0
                   for l in jax.tree.leaves(state2.opt_state))


class TestInt8Allreduce:
    def test_matches_exact_psum_within_bound(self):
        """The unit contract on the multi-device CPU mesh: the
        block-scaled int8 sum lands within ndev * scale / 2 of the
        exact f32 psum per element, and is identical across devices."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from active_learning_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh()
        ndev = mesh.devices.size
        assert ndev > 1
        rng = np.random.default_rng(0)
        # Per-device distinct values, including a >1e3 outlier block to
        # exercise the per-block scales.
        local = rng.normal(size=(ndev, 1000)).astype(np.float32)
        local[:, :8] *= 1e3
        full = jnp.asarray(local.reshape(-1))

        def body(x):
            return mesh_lib.int8_allreduce({"g": x}, "data")["g"]

        got = shard_map(body, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(full)
        got = np.asarray(got).reshape(ndev, -1)
        # Replicated result: every device's copy identical.
        assert all(np.array_equal(got[0], got[i]) for i in range(ndev))
        exact = local.sum(axis=0)
        block = mesh_lib.INT8_BLOCK
        padded = np.zeros(((local.shape[1] + block - 1) // block * block,),
                          np.float32)
        bound = np.zeros_like(padded)
        for d in range(ndev):
            padded[:local.shape[1]] = np.abs(local[d])
            bound = np.maximum(bound, padded)
        scales = bound.reshape(-1, block).max(axis=1) / 127.0
        per_elem = np.repeat(scales, block)[:local.shape[1]]
        err = np.abs(got[0] - exact)
        assert np.all(err <= ndev * per_elem / 2 + 1e-6), (
            f"int8 allreduce outside its error bound: "
            f"max excess {np.max(err - ndev * per_elem / 2)}")
        # And it is genuinely close: quantization, not garbage.
        assert np.linalg.norm(got[0] - exact) <= \
            0.05 * np.linalg.norm(exact) + 1e-6

    def test_nonfinite_blocks_poison_to_nan(self):
        """A loss spike must stay VISIBLE: an inf/NaN gradient block
        comes back NaN (like the f32 psum would surface it), never
        quantized to silent zeros."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from active_learning_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh()
        ndev = mesh.devices.size
        block = mesh_lib.INT8_BLOCK
        local = np.ones((ndev, 2 * block), np.float32)
        local[0, 0] = np.inf  # one bad element on one device

        def body(x):
            return mesh_lib.int8_allreduce({"g": x}, "data")["g"]

        got = np.asarray(shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False)(jnp.asarray(local.reshape(-1))))
        got = got.reshape(ndev, -1)
        # The poisoned BLOCK is all-NaN; the clean block sums exactly.
        assert np.all(np.isnan(got[0][:block]))
        np.testing.assert_array_equal(got[0][block:],
                                      np.full(block, float(ndev)))

    def test_int8_refuses_unsyncable_bn_model(self):
        """A train-mode-BN model with no axis_name field cannot sync
        its statistics inside the shard_map step — fit must refuse
        loudly instead of training divergent per-shard BN."""
        from flax import linen as nn

        from active_learning_tpu.config import (LoaderConfig,
                                                OptimizerConfig,
                                                TrainConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.train.trainer import Trainer

        class _BnNoAxis(nn.Module):
            num_classes: int = 4
            freeze_feature: bool = False

            @nn.compact
            def __call__(self, x, train: bool = True,
                         return_features: bool = False):
                emb = x.reshape((x.shape[0], -1)).astype(jnp.float32)
                emb = nn.BatchNorm(use_running_average=not train)(emb)
                logits = nn.Dense(self.num_classes, name="linear")(emb)
                return (logits, emb) if return_features else logits

        data = get_data_synthetic(n_train=64, n_test=16)
        cfg = TrainConfig(loader_tr=LoaderConfig(batch_size=16),
                          loader_te=LoaderConfig(batch_size=16),
                          optimizer=OptimizerConfig(name="sgd", lr=0.05),
                          grad_allreduce="int8",
                          resident_scoring_bytes=0)
        tr = Trainer(_BnNoAxis(), cfg, mesh_lib.make_mesh(), 4)
        st = tr.init_state(jax.random.PRNGKey(0),
                           data[0].gather(np.arange(2)))
        with pytest.raises(ValueError, match="no axis_name"):
            tr.fit(st, data[0], np.arange(32), data[2],
                   np.array([], np.int64), n_epoch=1, es_patience=0,
                   rng=np.random.default_rng(0))

    def test_int_leaves_psum_exactly(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from active_learning_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh()
        ndev = mesh.devices.size
        x = jnp.arange(ndev * 4, dtype=jnp.int32)

        def body(v):
            return mesh_lib.int8_allreduce({"c": v}, "data")["c"]

        got = shard_map(body, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(x)
        exact = np.asarray(x).reshape(ndev, -1).sum(axis=0)
        assert np.array_equal(np.asarray(got).reshape(ndev, -1)[0], exact)

    def test_resolve_rule_off_on_single_device(self):
        from active_learning_tpu.parallel import mesh as mesh_lib

        one = mesh_lib.make_mesh(1)
        full = mesh_lib.make_mesh()
        assert mesh_lib.resolve_grad_allreduce("int8", one) == "f32"
        assert mesh_lib.resolve_grad_allreduce("int8", full) == "int8"
        assert mesh_lib.resolve_grad_allreduce("f32", full) == "f32"
        with pytest.raises(ValueError):
            mesh_lib.resolve_grad_allreduce("int4", full)

    def test_learning_probe_passes_and_bound_pinned(self):
        """The driver gate: on the healthy 8-device CPU mesh the probe
        must PASS (delta within the pinned 0.05 bound) — and the bound
        itself is pinned so a silent loosening shows up here."""
        from active_learning_tpu.experiment import driver
        from active_learning_tpu.parallel import mesh as mesh_lib

        assert driver.INT8_PROBE_MAX_ACC_DELTA == 0.05
        ok, delta = driver.run_grad_allreduce_probe(mesh_lib.make_mesh())
        assert ok, f"int8 learning probe failed: delta={delta}"
        assert delta is not None and delta <= 0.05


class TestFusedE2EBitIdentity:
    def _run(self, tmp_path, name, fused_mode):
        from active_learning_tpu.config import (ExperimentConfig,
                                                TelemetryConfig)
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.experiment import arg_pools  # noqa: F401
        from active_learning_tpu.experiment.driver import run_experiment
        from active_learning_tpu.utils.metrics import JsonlSink

        from helpers import TinyClassifier, tiny_train_config

        cfg = ExperimentConfig(
            dataset="synthetic", arg_pool="synthetic",
            strategy="MarginSampler", rounds=2, round_budget=8,
            n_epoch=3, early_stop_patience=3, run_seed=7,
            exp_hash=name, exp_name="fusedab",
            ckpt_path=str(tmp_path / f"ckpt_{name}"),
            log_dir=str(tmp_path / f"logs_{name}"),
            fused_optimizer=fused_mode,
            telemetry=TelemetryConfig(enabled=False))
        data = get_data_synthetic(n_train=96, n_test=32, num_classes=4,
                                  image_size=8, seed=5)
        sink = JsonlSink(cfg.log_dir, experiment_key=name)
        strategy = run_experiment(cfg, sink=sink, data=data,
                                  train_cfg=tiny_train_config(),
                                  model=TinyClassifier(num_classes=4))
        state_path = glob.glob(os.path.join(
            cfg.ckpt_path, "*", "experiment_state.npz"))[0]
        return strategy, dict(np.load(state_path))

    def test_two_round_experiment_state_bit_identical(self, tmp_path):
        """The acceptance pin: the FULL driver, 2 rounds on the
        multi-device CPU mesh, fused path on vs off at f32 — every
        experiment_state array identical to the bit."""
        on, on_state = self._run(tmp_path, "fon", "on")
        off, off_state = self._run(tmp_path, "foff", "off")
        assert on.trainer.fused_tx is not None
        assert off.trainer.fused_tx is None
        assert set(on_state) == set(off_state)
        for k in on_state:
            assert np.array_equal(on_state[k], off_state[k]), (
                f"experiment_state[{k!r}] diverged between the fused "
                "and optax optimizer paths at f32")
