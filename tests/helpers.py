"""Shared test fixtures: a tiny Flax classifier with the reference model
interface (split encoder / ``linear`` head, return_features, head-only
mode — resnet_simclr.py:29-41) and a factory that wires a full Strategy
stack (synthetic data + mesh + trainer + pool) small enough for fast CPU
tests on the virtual 8-device mesh."""

from __future__ import annotations

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from active_learning_tpu.config import (ExperimentConfig, LoaderConfig,
                                        OptimizerConfig, SchedulerConfig,
                                        TrainConfig)
from active_learning_tpu.data.synthetic import get_data_synthetic
from active_learning_tpu.initial_pool import (generate_eval_idxs,
                                              generate_init_lb_idxs)
from active_learning_tpu.parallel import mesh as mesh_lib
from active_learning_tpu.pool import PoolState
from active_learning_tpu.strategies import get_strategy
from active_learning_tpu.train.trainer import Trainer


class TinyClassifier(nn.Module):
    """Minimal model with the SSLClassifier interface: encoder -> embedding,
    separate ``linear`` head, three forward modes."""

    num_classes: int = 4
    feat_dim: int = 8
    freeze_feature: bool = False

    def setup(self):
        self.proj = nn.Dense(self.feat_dim, name="proj")
        self.linear = nn.Dense(self.num_classes, name="linear")

    def __call__(self, x, train: bool = True, return_features: bool = False):
        emb = x.reshape((x.shape[0], -1)).astype(jnp.float32)
        emb = nn.tanh(self.proj(emb))
        if self.freeze_feature:
            emb = jax.lax.stop_gradient(emb)
        logits = self.linear(emb)
        if return_features:
            return logits, emb
        return logits

    def head(self, embedding):
        return self.linear(embedding)


def tiny_train_config(batch_size: int = 16) -> TrainConfig:
    return TrainConfig(
        eval_split=0.1,
        loader_tr=LoaderConfig(batch_size=batch_size),
        loader_te=LoaderConfig(batch_size=batch_size),
        optimizer=OptimizerConfig(name="sgd", lr=0.05, weight_decay=0.0,
                                  momentum=0.9),
        scheduler=SchedulerConfig(name="constant"),
    )


def make_strategy(name: str = "RandomSampler", n_train: int = 64,
                  n_test: int = 32, num_classes: int = 4, image_size: int = 8,
                  seed: int = 0, init_pool: int = 8, eval_count: int = 8,
                  n_epoch: int = 2, sink=None, model=None, train_cfg=None,
                  init_weights: bool = True, **cfg_overrides):
    """Build a fully wired Strategy over synthetic data on the 8-device CPU
    mesh."""
    train_set, test_set, al_set = get_data_synthetic(
        n_train=n_train, n_test=n_test, num_classes=num_classes,
        image_size=image_size, seed=seed)
    model = model or TinyClassifier(num_classes=num_classes)
    mesh = mesh_lib.make_mesh()
    train_cfg = train_cfg or tiny_train_config()
    cfg_overrides.setdefault(
        "ckpt_path", tempfile.mkdtemp(prefix="al_tpu_test_ckpt_"))
    cfg_overrides.setdefault(
        "log_dir", tempfile.mkdtemp(prefix="al_tpu_test_log_"))
    cfg = ExperimentConfig(
        dataset="synthetic", strategy=name, n_epoch=n_epoch,
        early_stop_patience=2, rounds=2, round_budget=init_pool,
        exp_hash="test", **cfg_overrides)
    trainer = Trainer(model, train_cfg, mesh, num_classes)

    targets = train_set.targets
    eval_idxs = generate_eval_idxs(targets, num_classes,
                                   ratio=eval_count / n_train,
                                   random_seed=cfg.eval_split_seed)
    pool = PoolState.create(len(al_set), eval_idxs)
    rng = np.random.default_rng(cfg.run_seed)
    strategy = get_strategy(name)(
        train_set, al_set, test_set, model, trainer, pool, cfg, train_cfg,
        sink=sink, rng=rng)
    if init_pool:
        init_idxs = generate_init_lb_idxs(
            targets, num_classes, eval_idxs, init_pool,
            random_seed=cfg.init_pool_seed)
        strategy.update(init_idxs, len(init_idxs))
    if init_weights:
        strategy.init_network_weights()
    return strategy


def load_script(name: str):
    """``scripts/<name>.py`` as a module (scripts/ is not a package)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_jpeg_tree(root: str, n_classes: int = 3, n_per_class: int = 6,
                    seed: int = 0, min_hw: int = 40, max_hw: int = 80) -> str:
    """Seeded class-per-subdirectory JPEG tree, built ATOMICALLY (written
    to a sibling temp dir, then renamed into place) so an interrupted
    build can never leave a partial tree that later runs silently reuse.
    Shared by the pytest jpeg_tree fixture and the multihost worker."""
    import json
    import shutil

    from PIL import Image

    # Reuse only a tree whose manifest matches EVERY build parameter: a
    # persistent root (the worker's manual-recipe scratch lives in /tmp)
    # must never hand back a tree built by older code after a param edit.
    params = {"n_classes": n_classes, "n_per_class": n_per_class,
              "seed": seed, "min_hw": min_hw, "max_hw": max_hw}
    manifest = os.path.join(root, "manifest.json")
    if os.path.isdir(root):
        try:
            with open(manifest) as fh:
                if json.load(fh) == params:
                    return root
        except (OSError, json.JSONDecodeError):
            pass
        shutil.rmtree(root)
    tmp = root + ".building"
    shutil.rmtree(tmp, ignore_errors=True)
    rng = np.random.default_rng(seed)
    for c in range(n_classes):
        cdir = os.path.join(tmp, f"class{c}")
        os.makedirs(cdir)
        for i in range(n_per_class):
            hw = int(rng.integers(min_hw, max_hw))
            arr = rng.integers(0, 256, size=(hw, hw + 10, 3), dtype=np.uint8)
            Image.fromarray(arr).save(os.path.join(cdir, f"img{i}.jpg"))
    with open(os.path.join(tmp, "manifest.json"), "w") as fh:
        json.dump(params, fh)
    os.rename(tmp, root)
    return root
