"""A configuration's family: everything the runner needs to know about one
kind of model and row, in one file that the configuration names by its path
from the repo's root (``"family": "benchmarks/families/resnet.py"``).

``run.py`` and ``lib/`` know no model: rows, weights, the checkpoint file,
the map from the program's tree to the reference's keys, the required work
and the plain forward all come from the family module.  A configuration of
another family is that module, its configuration file, its cells and its
metric files: no file that is here is edited.  The module exports:

  ``make_data(seed, config, n_pool, n_test)``
      -> (pool rows, pool labels, test rows, test labels): host arrays of any
      dtype and shape, from the seed alone.
  ``datasets(config, pool, test)``
      -> the program's ``(train_set, test_set, al_set)`` over those arrays;
      ``pool`` and ``test`` are (rows, labels).
  ``experiment(config)``
      -> the ``ExperimentConfig`` fields the family fixes (``dataset``,
      ``model``).
  ``make_weights(seed, config)``
      -> {key: float32 array}: every tensor of the model, the plain
      reference's parameters, from the seed alone.
  ``save_checkpoint(weights, directory)``
      -> the path of the file, written there, that the program's pretrained
      overlay reads.
  ``trainable_keys(weights, head_only=False)``
      -> the keys a fit moves (under ``head_only``, ``freeze_feature``: the
      head's).
  ``program_params(tree, weights)``
      -> the program's trainable leaves as host arrays under the reference's
      keys and layout.
  ``work(config, kind, rows, batches=1, head_only=False)``
      -> {"flops", "bytes"} that ``rows`` rows of ``kind`` (``forward``,
      ``fit``) need, done in ``batches`` program steps: every roofline and
      ``round_mfu`` divide by it.
  ``embed(p, rows, config, quant=None)``, ``head(p, emb, quant=None)``
      -> the plain float32 forward (matmul precision ``highest``); ``quant``
      goes to ``lib.reference.q`` in front of every contraction (the control).
  ``train_view(rows, step_key, augment)``
      -> a batch of rows as the fit's step sees it, drawn from the step's
      key as the program draws it; traced under ``jit``.

The embedding and the head may import ``lib`` (``lib.reference.q``); nothing
but ``datasets`` touches the program.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys

CONTRACT = ("make_data", "datasets", "experiment", "make_weights",
            "save_checkpoint", "trainable_keys", "program_params", "work",
            "embed", "head", "train_view")


def load(path: str, root: str):
    """The family module at ``path`` (relative to ``root``, inside it)."""
    if os.path.isabs(path) or ".." in path.split("/"):
        raise ValueError(f"a family is named by a path inside the repo, "
                         f"not {path!r}")
    name = "bench_family_" + re.sub(r"\W", "_", path)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, path))
    if spec is None:
        raise ValueError(f"{path!r} is not a Python file")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [n for n in CONTRACT if not callable(getattr(module, n, None))]
    if missing:
        raise ValueError(f"family {path!r} lacks {missing}")
    sys.modules[name] = module
    return module
