"""Dataset abstraction for the TPU-native pipeline.

The reference builds three torch datasets over the SAME underlying training
data (src/data_utils/custom_cifar10.py:28-40): ``train_set`` (augmented),
``al_set`` (validation transforms only), ``test_set`` — every ``__getitem__``
returns ``(x, y, index)`` so scores map back to pool indices
(custom_cifar10.py:23-25).

The TPU-first design is different: datasets hand the host pipeline raw
**uint8** batches (4x less host->device DMA than float32), and all math —
normalization and augmentation — runs on-device *inside* the jitted step
where XLA fuses it into the first conv (see data/augment.py).  A "view"
(train vs al) is therefore just a flag choosing the on-device transform, not
a separate dataset copy.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Normalization:
    mean: Tuple[float, ...]
    std: Tuple[float, ...]


# Reference normalization constants (custom_cifar10.py:50-54,
# custom_imagenet.py:49).
CIFAR10_NORM = Normalization((0.4914, 0.4822, 0.4465),
                             (0.2023, 0.1994, 0.2010))
IMAGENET_NORM = Normalization((0.485, 0.456, 0.406), (0.229, 0.224, 0.225))


@dataclasses.dataclass(frozen=True)
class ViewSpec:
    """On-device transform selection for a dataset view.

    augment: random crop (with ``pad`` zero-padding) + horizontal flip — the
      reference's train transform (custom_cifar10.py:47-49).  The al/test
      views use augment=False (custom_cifar10.py:36-40).
    normalization None: rows that are not pixels (token ids) pass through
      as they are; the backbone's own input stage (its embedding lookup)
      is their view (models/backbone.py).
    """

    normalization: Optional[Normalization]
    augment: bool = False
    pad: int = 4


# The view of a row of token ids: nothing to normalise, nothing to flip.
TOKEN_VIEW = ViewSpec(None, augment=False, pad=0)


def check_rows(rows: np.ndarray) -> None:
    """The row schema: a pool row is its dtype and its shape, ``uint8
    [H, W, C]`` an image or ``int32 [T]`` a sequence of T token ids.  The
    pinned form, the gather and the view carry rows of either kind as they
    are (parallel/resident.py, data/augment.py); nothing follows a model's
    name."""
    if not (rows.dtype == np.uint8 and rows.ndim == 4
            or rows.dtype == np.int32 and rows.ndim == 2):
        raise ValueError(
            f"rows must be uint8 [N,H,W,C] images or int32 [N,T] token "
            f"ids, not {rows.dtype}{list(rows.shape)}")


def rows_are_tokens(dataset) -> bool:
    """The dataset's rows are ``[T]`` token ids, not ``[H, W, C]`` images:
    a row is T positions of work, counted in tokens."""
    return len(dataset.image_shape) == 1


class Dataset:
    """Base: in-memory or disk-backed; always indexable by pool index."""

    num_classes: int
    targets: np.ndarray  # int64 [N]
    view: ViewSpec
    # Shape of a gathered batch row: (H, W, C) of an image, (T,) of a row
    # of token ids (``check_rows``).
    image_shape: Tuple[int, ...]

    def __len__(self) -> int:
        raise NotImplementedError

    def gather(self, idxs: np.ndarray) -> np.ndarray:
        """Return the rows ``[len(idxs), *image_shape]`` for the given
        indices (uint8 images; int32 token ids)."""
        raise NotImplementedError

    def class_counts(self) -> np.ndarray:
        return np.bincount(self.targets[: len(self)],
                           minlength=self.num_classes)


class ArrayDataset(Dataset):
    """In-memory dataset (CIFAR-scale data; fits in host RAM) of rows of
    one schema: uint8 images or int32 token ids (``check_rows``).

    ``limit`` implements the reference's debug_mode truncation to 50
    samples (custom_cifar10.py:14-17) without copying.
    """

    def __init__(self, images: np.ndarray, targets: Sequence[int],
                 num_classes: int, view: ViewSpec,
                 limit: Optional[int] = None):
        check_rows(images)
        self.images = images
        self.targets = np.asarray(targets, dtype=np.int64)
        assert len(self.images) == len(self.targets)
        self.num_classes = num_classes
        self.view = view
        self._limit = limit
        self.image_shape = tuple(images.shape[1:])

    def __len__(self) -> int:
        if self._limit is not None:
            return min(self._limit, len(self.images))
        return len(self.images)

    def gather(self, idxs: np.ndarray) -> np.ndarray:
        return self.images[np.asarray(idxs)]

    def with_view(self, view: ViewSpec) -> "ArrayDataset":
        """A second view over the same arrays (zero-copy) — how the
        train_set/al_set pair shares storage."""
        return ArrayDataset(self.images, self.targets, self.num_classes,
                            view, limit=self._limit)
