"""Data layer: dataset registry + triple factory.

``get_data`` mirrors src/data_utils/top_level_data_utils.py:7-19 (name ->
(train_set, test_set, al_set)), dispatching through the DATASETS registry
instead of an if/elif chain.
"""

from ..registry import DATASETS
from .core import (ArrayDataset, CIFAR10_NORM, Dataset, IMAGENET_NORM,
                   Normalization, TOKEN_VIEW, ViewSpec, check_rows,
                   rows_are_tokens)

# Register datasets.
from . import cifar10 as _cifar10  # noqa: F401
from . import imbalance as _imbalance  # noqa: F401
from . import synthetic as _synthetic  # noqa: F401
from . import imagenet as _imagenet  # noqa: F401
from . import tokens as _tokens  # noqa: F401


def get_data(data_name: str, data_path=None, debug_mode: bool = False,
             imbalance_args=None, **kwargs):
    factory = DATASETS.get(data_name)
    return factory(data_path, debug_mode=debug_mode,
                   imbalance_args=imbalance_args, **kwargs)
