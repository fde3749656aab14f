"""Space-to-depth stem: exact equivalence with the baseline 7x7/s2 stem,
weight-transform round-trip, s2d view transforms, and the resident-budget
auto-sizing that makes pool residency default behavior.

The s2d fold (models/resnet.s2d_stem_kernel) is pure re-indexing — every
product of the 7x7 convolution appears exactly once — so it is exact in
exact arithmetic.  XLA's conv lowering may SUM those products in a
different order for the two shapes, so float32 logits agree to
reduction-order rounding (pinned tight here) and a float64 run pins the
identity itself to ~1e-12.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from flax.traverse_util import flatten_dict, unflatten_dict

from active_learning_tpu.data.augment import apply_view, s2d_flip
from active_learning_tpu.data.core import IMAGENET_NORM, ViewSpec
from active_learning_tpu.data import pipeline
from active_learning_tpu.models import resnet
from active_learning_tpu.models.factory import (get_network,
                                                resolve_bn_stats_dtype)
from active_learning_tpu.parallel import resident


def _s2d_variables_from_baseline(variables):
    """Copy a baseline-stem variable tree, folding conv_stem 7x7 -> 4x4."""
    flat = flatten_dict(jax.tree.map(np.asarray, variables))
    out = {}
    for path, leaf in flat.items():
        if path[-2:] == ("conv_stem", "kernel") and leaf.shape[:2] == (7, 7):
            leaf = np.asarray(resnet.s2d_stem_kernel(leaf))
        out[path] = leaf
    return unflatten_dict(out)


class TestS2DEquivalence:
    def _models(self, dtype=jnp.float32):
        base = resnet.resnet50(num_classes=12, dtype=dtype)
        s2d = resnet.resnet50(num_classes=12, dtype=dtype, stem="s2d")
        return base, s2d

    def test_logits_match_baseline_stem_f32(self):
        """Baseline-stem vs s2d-stem ResNet-50 logits on random input,
        float32, identical (transformed) weights — agreement to
        reduction-order rounding."""
        base, s2d = self._models()
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, size=(2, 64, 64, 3), dtype=np.uint8)
        xf = jnp.asarray(x, jnp.float32)
        variables = base.init(jax.random.PRNGKey(0), xf, train=False)
        variables_s2d = _s2d_variables_from_baseline(variables)
        y_base = np.asarray(base.apply(variables, xf, train=False))
        y_s2d = np.asarray(s2d.apply(variables_s2d, xf, train=False))
        # The logits are sums of terms up to ~300 in magnitude, so a
        # small logit carries the rounding of the large terms: the
        # absolute floor scales with the largest logit (about eight f32
        # ulps there).  A fixed 2e-5 held on jax 0.4.37's XLA:CPU; the
        # 0.9.0 conv sums in another order and leaves one small logit
        # 9e-5 off — the f64 test below shows the fold itself is exact.
        np.testing.assert_allclose(
            y_s2d, y_base, rtol=2e-5,
            atol=1e-6 * float(np.abs(y_base).max()))
        # Host-side pre-transformed input must land in the same place.
        x12 = jnp.asarray(pipeline.space_to_depth(x), jnp.float32)
        y_host = np.asarray(s2d.apply(variables_s2d, x12, train=False))
        np.testing.assert_array_equal(y_host, y_s2d)

    def test_stem_conv_identity_is_exact_in_f64(self):
        """The fold itself is exact: in float64 the two stems agree to
        accumulated-rounding noise (~1e-12), proving the f32 delta above
        is summation order, not an algebraic error."""
        with jax.enable_x64(True):
            rng = np.random.default_rng(1)
            x = jnp.asarray(rng.normal(size=(1, 32, 32, 3)))
            k7 = jnp.asarray(rng.normal(size=(7, 7, 3, 16)))
            y7 = nn.Conv(16, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                         use_bias=False).apply(
                             {"params": {"kernel": k7}}, x)
            y4 = nn.Conv(16, (4, 4), (1, 1), padding=[(2, 1), (2, 1)],
                         use_bias=False).apply(
                             {"params": {"kernel": resnet.s2d_stem_kernel(
                                 k7)}}, resnet.space_to_depth(x))
            np.testing.assert_allclose(np.asarray(y4), np.asarray(y7),
                                       rtol=1e-10, atol=1e-10)

    def test_weight_transform_round_trip(self):
        rng = np.random.default_rng(2)
        k7 = rng.normal(size=(7, 7, 3, 64)).astype(np.float32)
        k4 = np.asarray(resnet.s2d_stem_kernel(k7))
        assert k4.shape == (4, 4, 12, 64)
        np.testing.assert_array_equal(
            np.asarray(resnet.stem_kernel_from_s2d(k4)), k7)
        # The pad row/col the fold introduces is structurally zero.
        assert float(np.abs(k4).sum()) == pytest.approx(
            float(np.abs(k7).sum()))

    def test_host_and_device_s2d_agree(self):
        rng = np.random.default_rng(3)
        x = rng.integers(0, 256, size=(3, 8, 8, 3), dtype=np.uint8)
        np.testing.assert_array_equal(
            pipeline.space_to_depth(x),
            np.asarray(resnet.space_to_depth(jnp.asarray(x))))

    def test_s2d_flip_commutes_with_space_to_depth(self):
        rng = np.random.default_rng(4)
        x = rng.integers(0, 256, size=(4, 8, 8, 3), dtype=np.uint8)
        flip = jnp.asarray([True, False, True, False])
        flipped = np.where(np.asarray(flip)[:, None, None, None],
                           x[:, :, ::-1, :], x)
        np.testing.assert_array_equal(
            np.asarray(s2d_flip(jnp.asarray(pipeline.space_to_depth(x)),
                                flip)),
            pipeline.space_to_depth(flipped))

    def test_apply_view_s2d_matches_baseline_view(self):
        """The full train view (flip + normalize) over an s2d batch equals
        space-to-depth of the baseline view's output, key-for-key."""
        rng = np.random.default_rng(5)
        x = rng.integers(0, 256, size=(4, 8, 8, 3), dtype=np.uint8)
        view = ViewSpec(IMAGENET_NORM, augment=True, pad=0)
        key = jax.random.PRNGKey(7)
        y_base = np.asarray(apply_view(jnp.asarray(x), view, key=key,
                                       train=True))
        y_s2d = np.asarray(apply_view(
            jnp.asarray(pipeline.space_to_depth(x)), view, key=key,
            train=True))
        b, h, w, c = y_base.shape
        y_base_s2d = y_base.reshape(b, h // 2, 2, w // 2, 2, c).transpose(
            0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
        np.testing.assert_allclose(y_s2d, y_base_s2d, rtol=1e-6, atol=1e-6)

    def test_factory_guards(self):
        with pytest.raises(ValueError):
            resnet.resnet50(num_classes=10, cifar_stem=True, stem="s2d")
        # Factory-level: a global --stem s2d quietly keeps the CIFAR stem.
        m = get_network("cifar10", "SSLResNet18", stem="s2d")
        assert m.stem == "default"
        m = get_network("imagenet", "SSLResNet50", stem="s2d")
        assert m.stem == "s2d"


class TestFusedBatchNorm:
    def test_matches_flax_batchnorm(self):
        """Train-mode stats, running-stat EMA, and eval-mode output agree
        with nn.BatchNorm within bf16-read rounding."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 4, 4, 8)).astype(np.float32))
        ref = nn.BatchNorm(momentum=0.9, epsilon=1e-5)
        fused = resnet.FusedBatchNorm(momentum=0.9, epsilon=1e-5)
        vr = ref.init(jax.random.PRNGKey(0), x, use_running_average=False)
        vf = fused.init(jax.random.PRNGKey(0), x,
                        use_running_average=False)
        yr, mr = ref.apply(vr, x, use_running_average=False,
                           mutable=["batch_stats"])
        yf, mf = fused.apply(vf, x, use_running_average=False,
                             mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(yf), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            mf["batch_stats"], mr["batch_stats"])
        # Eval mode from the updated stats.
        ye = ref.apply({"params": vr["params"], **mr},
                       x, use_running_average=True)
        yfe = fused.apply({"params": vf["params"], **mf},
                          x, use_running_average=True)
        np.testing.assert_allclose(np.asarray(yfe), np.asarray(ye),
                                   rtol=1e-5, atol=1e-5)

    def test_resolution_follows_compute_dtype(self):
        assert resolve_bn_stats_dtype("auto", jnp.bfloat16) == jnp.bfloat16
        assert resolve_bn_stats_dtype("auto", jnp.float32) is None
        assert resolve_bn_stats_dtype("float32", jnp.bfloat16) is None
        assert resolve_bn_stats_dtype("bfloat16",
                                      jnp.bfloat16) == jnp.bfloat16

    def test_variable_tree_structure_matches_flax_path(self):
        """Checkpoints interop across stats modes: the fused-stats model
        must produce the exact variable tree of the flax-BN model (the
        FusedBatchNorm class advertises the BatchNorm auto-name)."""
        x = jnp.zeros((2, 16, 16, 3), jnp.float32)
        v_f = resnet.resnet18(num_classes=12).init(
            jax.random.PRNGKey(0), x, train=False)
        v_b = resnet.resnet18(
            num_classes=12, dtype=jnp.bfloat16,
            bn_stats_dtype=jnp.bfloat16).init(
                jax.random.PRNGKey(0), x, train=False)
        assert jax.tree_util.tree_structure(v_f) \
            == jax.tree_util.tree_structure(v_b)

    def test_bf16_model_uses_fused_stats_and_keeps_f32_state(self):
        m = resnet.resnet18(num_classes=12, dtype=jnp.bfloat16,
                            bn_stats_dtype=jnp.bfloat16)
        x = jnp.zeros((2, 16, 16, 3), jnp.float32)
        variables = m.init(jax.random.PRNGKey(0), x, train=False)
        stats = jax.tree.leaves(variables["batch_stats"])
        assert stats and all(s.dtype == jnp.float32 for s in stats)
        logits, mut = m.apply(variables, x, train=True,
                              mutable=["batch_stats"])
        assert logits.dtype == jnp.float32
        assert all(s.dtype == jnp.float32
                   for s in jax.tree.leaves(mut["batch_stats"]))


class TestDevicePrefetch:
    """The async double-buffered feed behind the residency fallback."""

    def test_order_preserved_and_put_applied(self):
        from active_learning_tpu.data.cache import device_prefetch
        got = list(device_prefetch(iter(range(20)), lambda x: x * 10,
                                   depth=2))
        assert got == [x * 10 for x in range(20)]

    def test_feeder_errors_reraise_at_consumer(self):
        from active_learning_tpu.data.cache import device_prefetch

        def batches():
            yield 1
            raise RuntimeError("decode failed")

        it = device_prefetch(batches(), lambda x: x)
        assert next(it) == 1
        with pytest.raises(RuntimeError, match="decode failed"):
            list(it)

    def test_abandoned_generator_joins_feeder(self):
        import threading

        from active_learning_tpu.data.cache import device_prefetch
        before = threading.active_count()
        it = device_prefetch(iter(range(1000)), lambda x: x, depth=2)
        assert next(it) == 0
        it.close()  # consumer walks away mid-stream
        assert threading.active_count() <= before + 1

    def test_collect_pool_host_path_uses_prefetch_and_aligns(self):
        """End to end through collect_pool's host path (resident cache
        disabled): results aligned with idxs, s2d host batches accepted."""
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.strategies import scoring

        _, _, al_set = get_data_synthetic(n_train=48, n_test=8,
                                          image_size=8)
        mesh = mesh_lib.make_mesh()

        def step(variables, batch):
            assert batch["image"].shape[-1] == 12  # host s2d applied
            return {"m": jnp.sum(batch["image"].astype(jnp.float32),
                                 axis=(1, 2, 3))}

        idxs = np.arange(40)
        out = scoring.collect_pool(al_set, idxs, 16, step, {}, mesh,
                                   host_s2d=True)
        expect = al_set.gather(idxs).astype(np.float32).sum(axis=(1, 2, 3))
        np.testing.assert_allclose(out["m"], expect, rtol=1e-6)


class TestResidentBudgetAutoSizing:
    """resolve_budget/auto_budget: pool residency as default behavior."""

    def test_pool_fits_headroom(self):
        stats = {"bytes_limit": 16 << 30, "bytes_in_use": 2 << 30}
        budget = resident.auto_budget(stats=stats)
        assert budget == (16 << 30) - (2 << 30) - resident.AUTO_RESERVE_BYTES
        # A 7.5 GB decoded pool fits this headroom -> resident by default.
        assert budget >= int(7.5 * 2 ** 30)

    def test_pool_does_not_fit(self):
        """Headroom minus the activation reserve can go to zero — the
        budget floors at 0 (prefetch fallback), never negative."""
        stats = {"bytes_limit": 8 << 30, "bytes_in_use": 5 << 30}
        assert resident.auto_budget(stats=stats) == 0

    def test_headroom_minus_activation_reserve(self):
        stats = {"bytes_limit": 16 << 30, "bytes_in_use": 0}
        assert resident.auto_budget(reserve_bytes=6 << 30, stats=stats) \
            == (16 << 30) - (6 << 30)

    def test_no_memory_stats_falls_back_to_static_default(self):
        from active_learning_tpu.config import RESIDENT_SCORING_BYTES_DEFAULT
        assert resident.auto_budget(stats={}) \
            == RESIDENT_SCORING_BYTES_DEFAULT

    def test_resolve_budget_explicit_and_auto(self):
        assert resident.resolve_budget(0) == 0
        assert resident.resolve_budget(123) == 123
        stats = {"bytes_limit": 16 << 30, "bytes_in_use": 2 << 30}
        assert resident.resolve_budget(None, stats=stats) \
            == resident.auto_budget(stats=stats)

    def test_cached_pool_survives_budget_shrink(self):
        """A pool uploaded under a generous budget keeps its resident
        fast path after a refresh shrinks the budget below its size."""
        from active_learning_tpu.data.synthetic import get_data_synthetic
        from active_learning_tpu.parallel import mesh as mesh_lib
        _, _, al_set = get_data_synthetic(n_train=32, n_test=8,
                                          image_size=8)
        mesh = mesh_lib.make_mesh()
        cache = {}
        assert not resident.cached(cache, al_set)
        resident.pool_arrays(cache, al_set, mesh)
        assert resident.cached(cache, al_set)
        assert not resident.eligible(al_set, 0)  # budget shrank to zero
        # collect_pool's gate is eligible(...) OR cached(...): still fast.
