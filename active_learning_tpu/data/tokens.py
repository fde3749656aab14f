"""Synthetic rows of token ids: the token encoder's counterpart of
data/synthetic.py.  A pool row is ``int32 [T]`` (``core.check_rows``): half
of its tokens lean towards its class's share of the vocabulary, the rest
fall anywhere, so a linear head over a frozen encoder can learn the classes
and no two rows tie.  Rows have one fixed length (rows with a length of
their own and a second bucketing axis: ROADMAP R5).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..registry import DATASETS
from .core import TOKEN_VIEW, ArrayDataset


def make_token_rows(seed: int, salt: int, n: int, length: int, vocab: int,
                    num_classes: int) -> Tuple[np.ndarray, np.ndarray]:
    """(rows int32 [n, length], labels int64 [n]) from the seed alone."""
    rng = np.random.default_rng([int(seed), int(salt)])
    labels = rng.integers(0, num_classes, size=n).astype(np.int64)
    share = vocab // num_classes
    anywhere = rng.integers(0, vocab, size=(n, length))
    own = labels[:, None] * share + rng.integers(0, share, size=(n, length))
    rows = np.where(rng.random((n, length)) < 0.5, own, anywhere)
    return rows.astype(np.int32), labels


def token_datasets(pool: Tuple[np.ndarray, np.ndarray],
                   test: Tuple[np.ndarray, np.ndarray], num_classes: int,
                   limit: Optional[int] = None):
    """(train_set, test_set, al_set) over host arrays of token rows: one
    view (a row of ids has no augmentation), shared storage."""
    train_set = ArrayDataset(pool[0], pool[1], num_classes, TOKEN_VIEW,
                             limit=limit)
    return (train_set,
            ArrayDataset(test[0], test[1], num_classes, TOKEN_VIEW,
                         limit=limit),
            train_set.with_view(TOKEN_VIEW))


def get_data_synthetic_tokens(
    data_path: Optional[str] = None,
    n_train: int = 256,
    n_test: int = 64,
    num_classes: int = 16,
    row_len: int = 32,
    vocab: int = 256,
    seed: int = 1234,
    debug_mode: bool = False,
    **_unused,
):
    pool = make_token_rows(seed, 21, n_train, row_len, vocab, num_classes)
    test = make_token_rows(seed, 22, n_test, row_len, vocab, num_classes)
    return token_datasets(pool, test, num_classes,
                          limit=50 if debug_mode else None)


DATASETS.register("synthetic_tokens", get_data_synthetic_tokens)
