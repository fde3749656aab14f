"""Worker for the 2-process CPU multi-host smoke test.

Each process owns 2 virtual CPU devices; together they form one 4-device
global mesh, the CPU stand-in for a 2-host TPU pod slice over DCN.  The
worker runs a real multi-epoch ``Trainer.fit`` (per-process batch slicing,
cross-process gradient reduction, global-batch BN-free tiny model,
sharded validation) plus a ``collect_pool`` scoring pass with the
cross-host result gather, then writes one JSON summary.

Manual smoke recipe (also driven by tests/test_multihost.py):

    PORT=$(python -c "import socket; s=socket.socket(); \
           s.bind(('127.0.0.1', 0)); print(s.getsockname()[1])")
    for P in 0 1; do
      JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      python tests/multihost_worker.py 127.0.0.1:$PORT 2 $P /tmp/mh_$P.json &
    done; wait; cat /tmp/mh_*.json

The same flags reach the real CLI as --coordinator_address /
--num_processes / --process_id (experiment/cli.py).
"""

from __future__ import annotations

import json
import os
import sys


def main() -> None:
    coordinator, nprocs, pid, out_path = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    sys.path.insert(0, os.path.join(repo, "tests"))
    # Through the production rendezvous, not a bare
    # jax.distributed.initialize: initialize_distributed arms the gloo
    # CPU collectives a cross-process CPU mesh needs — without them
    # XLA:CPU refuses multiprocess computations outright (the reason
    # this smoke was red before the pod tier, ISSUE 15).
    from active_learning_tpu.parallel import mesh as _mesh_boot
    _mesh_boot.initialize_distributed(coordinator_address=coordinator,
                                      num_processes=nprocs,
                                      process_id=pid)
    import numpy as np

    from active_learning_tpu.data.synthetic import get_data_synthetic
    from active_learning_tpu.parallel import mesh as mesh_lib
    from active_learning_tpu.strategies import scoring
    from active_learning_tpu.train.trainer import Trainer
    from helpers import TinyClassifier, tiny_train_config

    mesh = mesh_lib.make_mesh()
    bs = 8
    local = mesh_lib.process_local_rows(mesh, bs)

    train_set, _, al_set = get_data_synthetic(
        n_train=64, n_test=16, num_classes=4, image_size=8, seed=3)
    model = TinyClassifier()
    trainer = Trainer(model, tiny_train_config(batch_size=bs), mesh,
                      num_classes=4)
    state = trainer.init_state(jax.random.PRNGKey(0),
                               train_set.gather(np.arange(2)))
    result = trainer.fit(state, train_set, np.arange(32), al_set,
                         np.arange(32, 48), n_epoch=2, es_patience=2,
                         rng=np.random.default_rng(0))
    leaves = jax.tree_util.tree_leaves(
        jax.tree.map(np.asarray, result.state.params))
    flat = np.concatenate([p.ravel() for p in leaves])

    step = scoring.make_prob_stats_step(model, al_set.view)
    scores = scoring.collect_pool(al_set, np.arange(48, 64), bs, step,
                                  result.state.variables, mesh)
    # The device-resident path on a multi-process mesh (what a pod run
    # with an in-memory pool uses): pool upload via the replicated
    # make_array_from_callback branch, per-batch on-device gathers, one
    # cross-host fetch — must agree with the host-batched scores above.
    res_scores = scoring.collect_pool(al_set, np.arange(48, 64), bs, step,
                                      result.state.variables, mesh,
                                      resident_cache={})
    np.testing.assert_allclose(
        np.asarray(res_scores["margin"]), np.asarray(scores["margin"]),
        rtol=1e-6, atol=1e-6)

    # BalancingSampler's device pick loop across processes: the sharded
    # pool upload takes the make_array_from_process_local_data branch, and
    # the argmin + eligibility scatter run as cross-process SPMD.  Inputs
    # are seeded so every process (and the single-process oracle in
    # test_multihost.py) computes from identical data; 37 rows on 4
    # devices also exercises the pad-row ineligibility.
    from active_learning_tpu.strategies.balancing import (
        _balancing_pick, _mark_taken, device_pool_state)
    brng = np.random.default_rng(5)
    emb = brng.normal(size=(37, 6)).astype(np.float32)
    eligible = np.ones(37, bool)
    eligible[::7] = False
    centers = brng.normal(size=(4, 6)).astype(np.float32)
    maj = np.array([True, True, False, False])
    emb_dev, elig_dev = device_pool_state(mesh, emb, eligible)
    picks = []
    for _ in range(4):
        small = mesh_lib.replicate(
            (centers, maj, np.int32(2), np.bool_(False)), mesh)
        q = int(_balancing_pick(emb_dev, elig_dev, *small))
        elig_dev = _mark_taken(elig_dev,
                               mesh_lib.replicate(np.int32(q), mesh))
        picks.append(q)

    # Decoded-pool disk cache across processes: cache files are
    # process-suffixed (no cross-process locking), each process decodes
    # only its local rows, and scoring THROUGH the cache must equal
    # scoring the raw disk dataset.  Only PIL's availability is optional
    # (recorded as a skip reason); any other failure in this block is a
    # real bug and must crash the worker loudly.
    decoded_margin = None
    decoded_skip = None
    try:
        from PIL import Image  # noqa: F401 — availability probe only
    except ImportError:
        decoded_skip = "PIL unavailable"
    if decoded_skip is None:
        from active_learning_tpu.data.cache import (DecodedPoolCache,
                                                    maybe_wrap_decoded)
        from active_learning_tpu.data.core import IMAGENET_NORM, ViewSpec
        from active_learning_tpu.data.imagenet import ImageFolderDataset
        from helpers import build_jpeg_tree
        from jax.experimental import multihost_utils

        # SHARED scratch (both workers' out paths live in one directory):
        # process 0 writes the tree (atomic rename inside the builder —
        # an interrupted manual run never leaves a reusable partial
        # tree), the barrier publishes it to all.
        scratch = os.path.join(os.path.dirname(os.path.abspath(out_path)),
                               "mh_scratch")
        tree = os.path.join(scratch, "tree")
        if jax.process_index() == 0:
            os.makedirs(scratch, exist_ok=True)
            build_jpeg_tree(tree, n_classes=3, n_per_class=4, seed=9,
                            min_hw=48, max_hw=56)
        multihost_utils.sync_global_devices("jpeg_tree_built")
        view = ViewSpec(IMAGENET_NORM, augment=False)
        ds = ImageFolderDataset(tree, view, False, num_classes=3)
        cached = maybe_wrap_decoded(ds, os.path.join(scratch, "dcache"),
                                    1 << 30)
        assert isinstance(cached, DecodedPoolCache)
        assert cached._data_path.endswith(f"_p{jax.process_index()}.u8")
        dmodel = TinyClassifier(num_classes=3)
        dvars = dmodel.init(jax.random.PRNGKey(1),
                            ds.gather(np.zeros(1, np.int64)), train=False)
        dstep = scoring.make_prob_stats_step(dmodel, view)
        raw = scoring.collect_pool(ds, np.arange(len(ds)), 4, dstep, dvars,
                                   mesh)
        warm = scoring.collect_pool(cached, np.arange(len(ds)), 4, dstep,
                                    dvars, mesh)
        np.testing.assert_allclose(np.asarray(warm["margin"]),
                                   np.asarray(raw["margin"]),
                                   rtol=1e-6, atol=1e-6)
        decoded_margin = np.asarray(warm["margin"], np.float64).tolist()

    out = {
        "balancing_picks": picks,
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "n_devices_global": int(mesh.devices.size),
        "local_rows": [local.start, local.stop],
        "best_perf": float(result.best_perf),
        "param_sum": float(flat.sum()),
        "margin": np.asarray(scores["margin"], np.float64).tolist(),
        "decoded_cache_margin": decoded_margin,
        "decoded_cache_skip": decoded_skip,
    }
    with open(out_path, "w") as fh:
        json.dump(out, fh)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
