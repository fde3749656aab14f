"""Disk-dataset decode path + threaded pipeline tests.

Covers the properties the reference's DataLoader stack gets from torch and
we must guarantee ourselves: N-worker gather with ORDERED reassembly, and
crop randomness that is a pure function of (seed, epoch, index) — identical
whatever the gather order or thread interleaving.
"""

import os

import numpy as np
import pytest

from active_learning_tpu.data.core import IMAGENET_NORM, ViewSpec
from active_learning_tpu.data.imagenet import ImageFolderDataset
from active_learning_tpu.data.pipeline import iterate_batches
from active_learning_tpu.data.synthetic import get_data_synthetic


@pytest.fixture(scope="module")
def jpeg_tree(tmp_path_factory):
    pytest.importorskip("PIL.Image")
    from helpers import build_jpeg_tree
    return build_jpeg_tree(str(tmp_path_factory.mktemp("imgs") / "tree"))


def make_ds(jpeg_tree, train=True, seed=0):
    view = ViewSpec(IMAGENET_NORM, augment=train, pad=0)
    return ImageFolderDataset(jpeg_tree, view, train, num_classes=3,
                              seed=seed)


class TestDecodeRNG:
    def test_crops_pure_function_of_seed_epoch_index(self, jpeg_tree):
        ds = make_ds(jpeg_tree)
        a = ds.gather(np.asarray([3, 7, 11]))
        # Different order, interleaved with other decodes: same result.
        ds.gather(np.asarray([0, 1, 2]))
        b = ds.gather(np.asarray([11, 7, 3]))
        np.testing.assert_array_equal(a, b[::-1])

    def test_epoch_advances_crops(self, jpeg_tree):
        ds = make_ds(jpeg_tree)
        a = ds.gather(np.asarray([3]))
        ds.set_epoch(1)
        b = ds.gather(np.asarray([3]))
        assert not np.array_equal(a, b)

    def test_val_transform_deterministic(self, jpeg_tree):
        ds = make_ds(jpeg_tree, train=False)
        a = ds.gather(np.asarray([5]))
        ds.set_epoch(3)
        b = ds.gather(np.asarray([5]))
        np.testing.assert_array_equal(a, b)
        assert a.shape == (1, 224, 224, 3)


class TestEvalDecodeCache:
    def test_cached_rows_exact_and_decode_once(self, jpeg_tree):
        from active_learning_tpu.data.cache import CachedEvalRows
        ds = make_ds(jpeg_tree, train=False)
        calls = {"n": 0}
        orig = ds.gather

        def counting(idxs):
            calls["n"] += len(idxs)
            return orig(idxs)

        ds.gather = counting
        cache = CachedEvalRows(ds)
        idxs = np.asarray([5, 2, 9, 2])
        a = cache.gather(idxs)
        np.testing.assert_array_equal(a, orig(idxs))
        assert calls["n"] == 3  # unique rows only
        b = cache.gather(idxs)
        np.testing.assert_array_equal(a, b)
        assert calls["n"] == 3  # second pass: zero decodes

    def test_empty_gather_preserves_shape_contract(self, jpeg_tree):
        """A multi-host last batch can leave a process zero real rows; the
        cache must pass the empty gather through, not np.stack([])."""
        from active_learning_tpu.data.cache import CachedEvalRows
        ds = make_ds(jpeg_tree, train=False)
        cache = CachedEvalRows(ds)
        empty = cache.gather(np.zeros(0, dtype=np.int64))
        assert empty.shape == ds.gather(np.zeros(0, dtype=np.int64)).shape
        assert empty.shape[0] == 0

    def test_concurrent_gathers_consistent_and_within_budget(self,
                                                             jpeg_tree):
        """The eval pipeline gathers from num_workers threads; hammering
        the cache concurrently must stay exact and never admit past the
        byte budget."""
        from concurrent.futures import ThreadPoolExecutor

        from active_learning_tpu.data.cache import CachedEvalRows
        ds = make_ds(jpeg_tree, train=False)
        want = ds.gather(np.arange(18))
        row_bytes = want[0].nbytes
        cache = CachedEvalRows(ds, max_bytes=10 * row_bytes)
        batches = [np.asarray(b) for b in
                   (range(0, 6), range(6, 12), range(12, 18),
                    range(3, 9), range(9, 15), range(0, 18))] * 4
        with ThreadPoolExecutor(max_workers=6) as ex:
            results = list(ex.map(cache.gather, batches))
        for idxs, got in zip(batches, results):
            np.testing.assert_array_equal(got, want[idxs])
        assert cache._bytes <= 10 * row_bytes
        assert len(cache._rows) <= 10

    def test_budget_overflow_falls_through_exactly(self, jpeg_tree):
        from active_learning_tpu.data.cache import CachedEvalRows
        ds = make_ds(jpeg_tree, train=False)
        cache = CachedEvalRows(ds, max_bytes=1)
        idxs = np.asarray([1, 4])
        a = cache.gather(idxs)
        b = cache.gather(idxs)
        np.testing.assert_array_equal(a, ds.gather(idxs))
        np.testing.assert_array_equal(a, b)

    def test_fit_decodes_eval_rows_once_per_round(self, jpeg_tree):
        """Through Trainer.fit: a 3-epoch fit over a disk dataset decodes
        each eval row ONCE, not once per epoch (and the padding row reuse
        comes along for free)."""
        import jax

        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.train.trainer import Trainer
        from helpers import TinyClassifier, tiny_train_config

        train_ds = make_ds(jpeg_tree, train=True)
        al_ds = make_ds(jpeg_tree, train=False)
        calls = {"n": 0}
        orig = al_ds.gather

        def counting(idxs):
            calls["n"] += len(idxs)
            return orig(idxs)

        al_ds.gather = counting
        trainer = Trainer(TinyClassifier(num_classes=3),
                          tiny_train_config(batch_size=8),
                          mesh_lib.make_mesh(), num_classes=3)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   train_ds.gather(np.arange(2)))
        trainer.fit(state, train_ds, np.arange(12), al_ds,
                    np.arange(12, 18), n_epoch=3, es_patience=5,
                    rng=np.random.default_rng(0))
        assert calls["n"] == 6, calls["n"]  # 6 eval rows, 3 epochs


class TestThreadedPipeline:
    def test_threaded_matches_sync_in_order(self, jpeg_tree):
        ds = make_ds(jpeg_tree)
        idxs = np.arange(len(ds))
        sync = list(iterate_batches(ds, idxs, 4, num_threads=0))
        threaded = list(iterate_batches(ds, idxs, 4, num_threads=4,
                                        prefetch=2))
        assert len(sync) == len(threaded)
        for s, t in zip(sync, threaded):
            for k in s:
                np.testing.assert_array_equal(s[k], t[k])

    def test_threaded_matches_sync_in_memory_dataset(self):
        train_set, _, _ = get_data_synthetic(n_train=50, n_test=8)
        idxs = np.arange(50)
        sync = list(iterate_batches(train_set, idxs, 8, num_threads=0))
        threaded = list(iterate_batches(train_set, idxs, 8, num_threads=3))
        for s, t in zip(sync, threaded):
            np.testing.assert_array_equal(s["image"], t["image"])
            np.testing.assert_array_equal(s["index"], t["index"])

    def test_error_propagates_from_worker(self):
        class Boom:
            targets = np.zeros(10, dtype=np.int64)

            def gather(self, idxs):
                raise RuntimeError("decode failed")

        with pytest.raises(RuntimeError, match="decode failed"):
            list(iterate_batches(Boom(), np.arange(10), 4, num_threads=2))

    def test_early_close_does_not_hang(self, jpeg_tree):
        ds = make_ds(jpeg_tree)
        gen = iterate_batches(ds, np.arange(len(ds)), 2, num_threads=2)
        next(gen)
        gen.close()  # must not deadlock or leak


class TestNativeBuiltFromThisCheckout:
    """Only what the committed decode.cpp produces is ever loaded: the
    library's file name carries a hash of the source and the compile
    command, so a binary left on disk by another source (the build dir is
    git-ignored, and a copied disk brings it along) has another name.
    Driven on a private copy of ``native/`` through the functions that
    take the directory — the process-wide cached handle is never touched
    (prefetch threads of earlier tests may still be using it)."""

    @pytest.fixture
    def sandbox(self, tmp_path):
        import shutil

        from active_learning_tpu.data import native
        root = tmp_path / "native"
        (root / "build").mkdir(parents=True)
        shutil.copy(os.path.join(native._NATIVE_DIR, "decode.cpp"),
                    root / "decode.cpp")
        return native, root

    def test_name_follows_the_source(self, sandbox):
        native, root = sandbox
        first = native.so_path(str(root))
        assert os.path.basename(first).startswith("libaldata-")
        assert os.path.dirname(first) == str(root / "build")
        with open(root / "decode.cpp", "a") as fh:
            fh.write("\n// another source\n")
        assert native.so_path(str(root)) != first

    def test_name_follows_the_command(self, sandbox, monkeypatch):
        native, root = sandbox
        first = native.so_path(str(root))
        real = native._build_cmd
        monkeypatch.setattr(native, "_build_cmd",
                            lambda s, o: [*real(s, o), "-DOTHER"])
        assert native.so_path(str(root)) != first

    def test_no_source_no_library(self, sandbox):
        native, root = sandbox
        os.unlink(root / "decode.cpp")
        (root / "build" / "libaldata.so").write_bytes(b"stale")
        assert native.so_path(str(root)) is None
        assert native._open_library(str(root)) is None

    def test_a_foreign_binary_is_refused(self, sandbox):
        """Stale binaries under the old fixed name AND under another
        source's hash sit in build/; the library opened is the one built
        from THIS source (CDLL on either stale file would raise — they
        are not ELF)."""
        native, root = sandbox
        theirs = native.so_path(str(root))
        with open(root / "decode.cpp", "a") as fh:
            fh.write("\n// the source this checkout really has\n")
        mine = native.so_path(str(root))
        for stale in (root / "build" / "libaldata.so", theirs):
            with open(stale, "wb") as fh:
                fh.write(b"built elsewhere from another decode.cpp")
        assert not os.path.exists(mine)
        lib = native._open_library(str(root))
        if lib is None:
            pytest.skip("no toolchain to build the native library")
        assert os.path.exists(mine)
        assert lib._name == mine


class TestNativeDecode:
    def test_identity_decode_matches_pil_exactly(self, tmp_path):
        """Whole-image rect + same-size output is a pure decode: must match
        PIL pixel-for-pixel (both are IJG-compatible JPEG decoders)."""
        PIL = pytest.importorskip("PIL.Image")
        from active_learning_tpu.data import native
        if native.load() is None:
            pytest.skip("native decode unavailable")
        rng = np.random.default_rng(1)
        # Smooth image: JPEG is lossy, but decode-vs-decode is exact.
        base = np.linspace(0, 255, 48 * 48 * 3).reshape(48, 48, 3)
        arr = (base + rng.normal(0, 4, base.shape)).clip(0, 255).astype(
            np.uint8)
        p = tmp_path / "a.jpg"
        PIL.fromarray(arr).save(p, quality=90)

        dims = native.jpeg_dims([str(p)])
        np.testing.assert_array_equal(dims, [[48, 48]])
        out, failed = native.decode_crop_resize(
            [str(p)], np.asarray([[0, 0, 48, 48]], dtype=np.int32), 48)
        assert not failed.any()
        pil = np.asarray(PIL.open(p).convert("RGB"))
        np.testing.assert_array_equal(out[0], pil)

    def test_dataset_native_and_pil_paths_agree(self, jpeg_tree):
        """Same crop rects (RNG lives in Python), near-identical pixels —
        only the resize filter differs between the two paths."""
        from active_learning_tpu.data import native
        if native.load() is None:
            pytest.skip("native decode unavailable")
        nat = make_ds(jpeg_tree, train=True, seed=3)
        pil = make_ds(jpeg_tree, train=True, seed=3)
        pil._use_native = False
        assert nat._use_native
        idxs = np.asarray([0, 5, 9])
        a = nat.gather(idxs)
        b = pil.gather(idxs)
        assert a.shape == b.shape == (3, 224, 224, 3)
        # Same crop windows: the images should be nearly identical, not
        # merely correlated.
        diff = np.abs(a.astype(np.int32) - b.astype(np.int32)).mean()
        assert diff < 12.0, f"native/PIL paths diverged: mean abs {diff}"

    def test_val_transform_native_matches_shape_and_determinism(
            self, jpeg_tree):
        from active_learning_tpu.data import native
        if native.load() is None:
            pytest.skip("native decode unavailable")
        ds = make_ds(jpeg_tree, train=False)
        a = ds.gather(np.asarray([2]))
        b = ds.gather(np.asarray([2]))
        np.testing.assert_array_equal(a, b)
        assert a.shape == (1, 224, 224, 3)

    def test_non_jpeg_falls_back_to_pil(self, tmp_path):
        PIL = pytest.importorskip("PIL.Image")
        root = tmp_path / "pngs" / "class0"
        os.makedirs(root)
        arr = np.zeros((40, 40, 3), dtype=np.uint8)
        PIL.fromarray(arr).save(root / "img.png")
        ds = ImageFolderDataset(str(tmp_path / "pngs"),
                                ViewSpec(IMAGENET_NORM, augment=False),
                                False, num_classes=1)
        out = ds.gather(np.asarray([0]))
        assert out.shape == (1, 224, 224, 3)

    def test_cmyk_jpeg_falls_back_per_file_without_disabling_native(
            self, tmp_path):
        """Real ImageNet contains a handful of CMYK JPEGs libjpeg can't
        emit as RGB; they must fall back to PIL individually while the
        rest of the batch stays on the native path."""
        PIL = pytest.importorskip("PIL.Image")
        from active_learning_tpu.data import native
        if native.load() is None:
            pytest.skip("native decode unavailable")
        root = tmp_path / "mixed" / "class0"
        os.makedirs(root)
        rng = np.random.default_rng(0)
        for i in range(3):
            arr = rng.integers(0, 256, size=(60, 60, 3), dtype=np.uint8)
            PIL.fromarray(arr).save(root / f"a{i}.jpg")
        PIL.fromarray(
            rng.integers(0, 256, size=(60, 60, 4), dtype=np.uint8),
            mode="CMYK").save(root / "cmyk.jpg")
        ds = ImageFolderDataset(str(tmp_path / "mixed"),
                                ViewSpec(IMAGENET_NORM, augment=False),
                                False, num_classes=1)
        out = ds.gather(np.arange(4))
        assert out.shape == (4, 224, 224, 3)
        assert ds._use_native  # one odd file must not kill the fast path
        # The CMYK slot decoded through PIL is not all zeros.
        assert all(out[i].any() for i in range(4))


class TestDecodedPoolCache:
    """Experiment-lifetime memmap decode cache (data/cache.DecodedPoolCache):
    exact rows, decode-once-ever semantics, persistence across instances,
    torn-write safety, and the eligibility gates of maybe_wrap_decoded."""

    def test_rows_exact_and_decoded_once_across_instances(self, jpeg_tree,
                                                          tmp_path):
        from active_learning_tpu.data.cache import (DecodedPoolCache,
                                                    maybe_wrap_decoded)
        ds = make_ds(jpeg_tree, train=False)
        want = ds.gather(np.arange(len(ds)))

        calls = []
        real_gather = ds.gather

        def counting(idxs):
            calls.append(np.asarray(idxs))
            return real_gather(idxs)

        ds.gather = counting
        cached = maybe_wrap_decoded(ds, str(tmp_path), 1 << 30)
        assert isinstance(cached, DecodedPoolCache)
        out1 = cached.gather(np.asarray([3, 1, 3]))
        np.testing.assert_array_equal(out1, want[[3, 1, 3]])
        out2 = cached.gather(np.arange(len(ds)))
        np.testing.assert_array_equal(out2, want)
        decoded = np.concatenate(calls)
        assert len(decoded) == len(np.unique(decoded)) == len(ds)

        # A second instance over the same tree (fresh process in real
        # life) must reuse the file: zero further decodes.
        calls.clear()
        cached2 = maybe_wrap_decoded(ds, str(tmp_path), 1 << 30)
        np.testing.assert_array_equal(cached2.gather(np.arange(len(ds))),
                                      want)
        assert calls == []

    def test_full_cache_promotes_to_device_residency(self, jpeg_tree,
                                                     tmp_path):
        """A fully-populated cache exposes the memmap as ``.images`` and
        thereby qualifies for the device-resident scoring path
        (parallel/resident.py:eligible) — rounds 1+ of a disk-pool
        experiment score via on-device gathers when the HBM budget
        covers the pool.  While partial it must NOT qualify: a
        half-empty memmap uploaded as real data would score zeros."""
        import jax

        from active_learning_tpu.data.cache import DecodedPoolCache
        from active_learning_tpu.parallel import mesh as mesh_lib
        from active_learning_tpu.parallel import resident as resident_lib
        from active_learning_tpu.strategies import scoring as scoring_lib

        ds = make_ds(jpeg_tree, train=False)
        cached = DecodedPoolCache(ds, str(tmp_path))
        budget = 1 << 30

        # Partial: one row decoded — no .images, not eligible.
        cached.gather(np.asarray([0]))
        assert getattr(cached, "images", None) is None
        assert not resident_lib.eligible(cached, budget)

        # Fully populated: promoted, and the resident scoring pass over
        # the cache matches the host-batched pass bit for bit.
        cached.gather(np.arange(len(cached)))
        assert isinstance(cached.images, np.ndarray)
        assert resident_lib.eligible(cached, budget)
        assert not resident_lib.eligible(cached, cached.images.nbytes - 1)

        from flax import linen as nn

        class Probe(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                return nn.Dense(4)(x.reshape(x.shape[0], -1)
                                   .astype(np.float32))

        mesh = mesh_lib.make_mesh(1)
        model = Probe()
        variables = model.init(jax.random.PRNGKey(0),
                               cached.gather(np.arange(2)))
        step = scoring_lib.make_prob_stats_step(model, cached.view)
        idxs = np.arange(len(cached), dtype=np.int64)
        host = scoring_lib.collect_pool(cached, idxs, 8, step, variables,
                                        mesh)
        res = scoring_lib.collect_pool(cached, idxs, 8, step, variables,
                                       mesh, resident_cache={})
        for k in host:
            np.testing.assert_allclose(res[k], host[k], rtol=1e-6,
                                       atol=1e-6, err_msg=k)

    def test_torn_write_not_served(self, jpeg_tree, tmp_path):
        """A row whose bytes landed but whose valid flag did not (crash
        between the two) must be re-decoded, and vice versa a zeroed row
        with no flag never surfaces."""
        from active_learning_tpu.data.cache import DecodedPoolCache
        ds = make_ds(jpeg_tree, train=False)
        cached = DecodedPoolCache(ds, str(tmp_path))
        want = ds.gather(np.asarray([0]))[0]
        cached.gather(np.asarray([0]))
        # Simulate the torn state: flag cleared after a "crash".
        cached._valid[0] = 0
        cached._rows[0] = 0
        np.testing.assert_array_equal(cached.gather(np.asarray([0]))[0],
                                      want)

    def test_eligibility_gates(self, jpeg_tree, tmp_path):
        from active_learning_tpu.data.cache import maybe_wrap_decoded
        val_ds = make_ds(jpeg_tree, train=False)
        # Train views (non-deterministic crops) must never be wrapped.
        train_ds = make_ds(jpeg_tree, train=True)
        assert maybe_wrap_decoded(train_ds, str(tmp_path), 1 << 30) \
            is train_ds
        # A pool larger than the budget stays unwrapped (partial caches
        # thrash; the scoring pass touches every row).
        assert maybe_wrap_decoded(val_ds, str(tmp_path), 10) is val_ds
        # In-memory datasets have no paths: unwrapped.
        arr_ds = get_data_synthetic(n_train=8, n_test=4)[2]
        assert maybe_wrap_decoded(arr_ds, str(tmp_path), 1 << 30) is arr_ds
        # Disabled dir/budget: unwrapped.
        assert maybe_wrap_decoded(val_ds, None, 1 << 30) is val_ds
        assert maybe_wrap_decoded(val_ds, str(tmp_path), 0) is val_ds

    def test_driver_wraps_disk_pool_and_scoring_uses_it(self, jpeg_tree,
                                                        tmp_path):
        """build_experiment must hand the strategy a cache-wrapped al/test
        set for disk datasets, and the sampler's scoring pass must flow
        through it (attribute passthrough intact)."""
        import dataclasses

        from active_learning_tpu.config import ExperimentConfig
        from active_learning_tpu.data.cache import DecodedPoolCache
        from active_learning_tpu.experiment.driver import build_experiment
        from helpers import tiny_train_config

        train_ds = make_ds(jpeg_tree, train=True)
        al_ds = make_ds(jpeg_tree, train=False)
        test_ds = make_ds(jpeg_tree, train=False)
        train_cfg = dataclasses.replace(
            tiny_train_config(), decoded_cache_dir=str(tmp_path / "cache"))
        cfg = ExperimentConfig(
            dataset="imagenet", strategy="MarginSampler", rounds=1,
            round_budget=4, init_pool_size=4, n_epoch=1, exp_hash="t",
            enable_metrics=False,
            log_dir=str(tmp_path / "logs"), ckpt_path=str(tmp_path / "ck"))
        strategy = build_experiment(cfg, data=(train_ds, test_ds, al_ds),
                                    train_cfg=train_cfg)
        strategy.init_network_weights()
        assert isinstance(strategy.al_set, DecodedPoolCache)
        assert isinstance(strategy.test_set, DecodedPoolCache)
        assert strategy.train_set is train_ds  # train view never cached
        assert strategy.al_set.num_classes == al_ds.num_classes
        got, cost = strategy.query(4)
        assert cost == 4 and len(got) == 4
        # The query populated the cache for exactly the scored rows.
        assert int(np.count_nonzero(strategy.al_set._valid)) > 0

    def test_stale_cache_eviction(self, jpeg_tree, tmp_path):
        """Old cache triples must be LRU-evicted when a new cache would
        push the directory past its byte budget; in-use and same-
        signature files survive."""
        import time as time_mod

        from active_learning_tpu.data.cache import (DecodedPoolCache,
                                                    maybe_wrap_decoded)
        ds = make_ds(jpeg_tree, train=False)
        full = len(ds) * int(np.prod(ds.image_shape))
        # Plant a fake stale triple, old mtime, bigger than the slack.
        stale = tmp_path / "decoded_deadbeef00000000_p0"
        for ext in (".u8", ".valid", ".json"):
            with open(str(stale) + ext, "wb") as fh:
                fh.write(b"x" * 4096)
        old = time_mod.time() - 1e6
        for ext in (".u8", ".valid", ".json"):
            os.utime(str(stale) + ext, (old, old))
        DecodedPoolCache._IN_USE.clear()
        cached = maybe_wrap_decoded(ds, str(tmp_path), full + 2048)
        assert isinstance(cached, DecodedPoolCache)
        assert not os.path.exists(str(stale) + ".u8")
        # A second wrap (same signature, now in use) evicts nothing.
        assert os.path.exists(cached._data_path)
