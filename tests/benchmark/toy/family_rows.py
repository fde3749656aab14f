"""A second family at toy size, for the tests of the seam only: rows are
``int32[N, 12]`` token ids from a 64-id vocabulary, the plain forward is the
mean of an embedding table's rows and a linear head, and ``work`` is counted
by hand.  It is loaded by the path in ``config_rows.json``, as ``run.py``
loads any family, and nothing under ``benchmarks/`` knows it.

The program cannot run such rows yet (ROADMAP R5: a pool row that is not a
uint8 image; R6: a backbone that is not a BatchNorm convnet), so this family
goes through no ``--rehearse`` walk and ``datasets`` says so.  The contract:
``benchmarks/families/__init__.py``.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from lib.reference import q


def make_data(seed: int, config: Dict, n_pool: int, n_test: int):
    """Rows whose tokens lean towards their class's quarter of the
    vocabulary, and their labels."""
    vocab, length = int(config["vocab"]), int(config["row_len"])
    nc = int(config["num_classes"])
    out = []
    for salt, n in ((21, n_pool), (22, n_test)):
        rng = np.random.default_rng([int(seed), salt])
        labels = rng.integers(0, nc, size=n).astype(np.int64)
        anywhere = rng.integers(0, vocab, size=(n, length))
        own = labels[:, None] * (vocab // nc) + rng.integers(
            0, vocab // nc, size=(n, length))
        rows = np.where(rng.random((n, length)) < 0.5, own, anywhere)
        out += [rows.astype(np.int32), labels]
    return tuple(out)


def datasets(config: Dict, pool, test):
    raise NotImplementedError(
        "the program has no dataset for rows of token ids yet (ROADMAP R5, "
        "R6): this family drives the reference and the arithmetic only")


def experiment(config: Dict) -> Dict:
    return {"dataset": "token_rows", "model": config["model"]}


def make_weights(seed: int, config: Dict) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng([int(seed), 31])
    d, nc = int(config["embed_dim"]), int(config["num_classes"])
    return {
        "table": rng.standard_normal((int(config["vocab"]), d),
                                     dtype=np.float32),
        "linear.weight": rng.standard_normal((nc, d), dtype=np.float32)
        * np.float32(0.5),
        "linear.bias": rng.standard_normal(nc, dtype=np.float32)
        * np.float32(0.1)}


def save_checkpoint(weights: Dict[str, np.ndarray], directory: str) -> str:
    path = os.path.join(directory, "seed_weights.npz")
    np.savez(path, **weights)
    return path


def trainable_keys(weights: Dict[str, np.ndarray],
                   head_only: bool = False) -> List[str]:
    return [k for k in weights
            if not head_only or k.startswith("linear.")]


# Where each tensor would sit in a program's tree, and how it is laid out
# there: a flax ``Embed`` and a ``Dense`` (kernel [in, out]).
TREE_PATH = {"table": ("encoder", "embedding"),
             "linear.weight": ("linear", "kernel"),
             "linear.bias": ("linear", "bias")}


def program_params(tree, weights: Dict[str, np.ndarray]
                   ) -> Dict[str, np.ndarray]:
    out = {}
    for key in trainable_keys(weights):
        leaf = tree
        for part in TREE_PATH[key]:
            leaf = leaf[part]
        leaf = np.asarray(leaf)
        out[key] = leaf.T if key == "linear.weight" else leaf
    return out


def work(config: Dict, kind: str, rows: int, batches: int = 1,
         head_only: bool = False) -> Dict[str, float]:
    """By hand, per row: the mean adds ``row_len * d`` numbers; the head is
    ``d * classes`` multiply-accumulates (two operations each).  A fitted
    row adds the head's weight gradient, and unless ``head_only`` its input
    gradient and the scatter of the mean's gradient into the table.  Bytes:
    the int32 row read once; per step the float32 parameters read once, and
    for a fit what is trained read and written with its momentum."""
    length, d = int(config["row_len"]), int(config["embed_dim"])
    nc, vocab = int(config["num_classes"]), int(config["vocab"])
    mean, head_ops = length * d, 2 * d * nc
    p_bytes = 4 * (vocab * d + d * nc + nc)
    if kind == "forward":
        flops = (mean + head_ops) * rows
        byts = 4 * length * rows + batches * p_bytes
    elif kind == "fit":
        back = head_ops if head_only else 2 * head_ops + mean
        flops = (mean + head_ops + back) * rows
        trained = 4 * (d * nc + nc) if head_only else p_bytes
        byts = 4 * length * rows + batches * (p_bytes + 3 * trained)
    else:
        raise KeyError(f"unknown kind of work {kind!r}")
    return {"flops": float(flops), "bytes": float(byts)}


def embed(p: Dict, rows, config: Dict, quant=None):
    import jax.numpy as jnp
    return jnp.mean(p["table"][rows], axis=1)


def head(p: Dict, emb, quant=None):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(q(emb, quant), q(p["linear.weight"], quant).T,
                      precision=jax.lax.Precision.HIGHEST) + p["linear.bias"]


def train_view(rows, step_key, augment):
    return rows
