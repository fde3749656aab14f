"""Round-level experiment save/restore.

The reference pickles the ENTIRE strategy object + args + status every round
(src/utils/resume_training.py:38-52) and unpickles it to resume
(:8-35).  Pickling live objects is fragile (any code change breaks old
checkpoints) so here the state is explicit arrays + json:

  * pool state (labeled mask, eval idxs, recent, cost, round) — npz;
  * the host RNG's bit-generator state and the per-experiment JAX init key —
    resuming reproduces the SAME round-(n+1) query an uninterrupted run
    would make;
  * a config echo — compared on load with a warning on mismatch, like the
    reference's args comparison (resume_training.py:22-25);
  * the metrics experiment key, so the sink continues the same stream
    (the reference reattaches the comet ExistingExperiment,
    resume_training.py:29-32).

Model weights are NOT duplicated here: the per-round best checkpoint
(best_rd_{n}.msgpack, train/checkpoint.py) is the model state of record and
is reloaded on resume.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .. import faults
from ..config import ExperimentConfig, config_to_dict
from ..pool import PoolState
# Shared weight-compatibility version: see its definition site for when it
# bumps.  Both resume surfaces (this file and the mid-round fit state in
# train/checkpoint.py) check it.
from ..train.checkpoint import MODEL_FORMAT_VERSION
from ..utils.logging import get_logger

STATE_FILE = "experiment_state.npz"
META_FILE = "experiment_state.json"
# Sampler-owned aux state (Strategy.aux_state_bytes — e.g. VAAL's
# VAE/discriminator/optimizers), msgpack via flax.serialization.
AUX_FILE = "aux_state.msgpack"


def _state_dir(cfg: ExperimentConfig) -> str:
    exp_hash = cfg.exp_hash or "no_hash"
    return os.path.join(cfg.ckpt_path, f"{cfg.exp_name}_{exp_hash}")


def save_experiment(strategy, cfg: ExperimentConfig) -> str:
    """Persist end-of-round state.  Called once per round after ``test()``
    (reference: main_al.py:180 → save_experiment)."""
    faults.site("ckpt_write")
    directory = _state_dir(cfg)
    os.makedirs(directory, exist_ok=True)
    arrays = strategy.pool.to_arrays()
    arrays["init_key"] = np.asarray(strategy._init_key)
    # Atomic writes (tmp + rename), meta LAST: has_saved_experiment checks
    # both files, so a crash mid-save can never leave a round-N state file
    # paired with a stale or truncated meta.
    state_path = os.path.join(directory, STATE_FILE)
    np.savez(state_path + ".tmp.npz", **arrays)
    os.replace(state_path + ".tmp.npz", state_path)
    aux_path = os.path.join(directory, AUX_FILE)
    aux = strategy.aux_state_bytes()
    if aux is not None:
        with open(aux_path + ".tmp", "wb") as fh:
            fh.write(aux)
        os.replace(aux_path + ".tmp", aux_path)
    elif os.path.exists(aux_path):
        # A stale aux blob from an older round of a sampler that stopped
        # producing one must not be restored later.
        os.remove(aux_path)
    # Torn point between the state npz and the meta json: a crash here
    # leaves a round-N state file with round-(N-1) (or no) meta — which
    # has_saved_experiment/meta-last ordering reads as the LAST COMPLETE
    # round, never a spliced pair (chaos-tested via ckpt_write:torn@N).
    faults.site("ckpt_write", point="torn")
    meta = {
        "round": int(strategy.round),
        "model_format": MODEL_FORMAT_VERSION,
        "rng_state": strategy.rng.bit_generator.state,
        "config": {k: _jsonable(v) for k, v in config_to_dict(cfg).items()},
        "experiment_key": getattr(strategy.sink, "experiment_key", None),
        "best_epoch": int(strategy.best_epoch),
    }
    meta_path = os.path.join(directory, META_FILE)
    with open(meta_path + ".tmp", "w") as fh:
        json.dump(meta, fh, indent=2)
    os.replace(meta_path + ".tmp", meta_path)
    get_logger().info(f"Saved experiment state for round {strategy.round} "
                      f"to {directory}")
    return directory


def has_saved_experiment(cfg: ExperimentConfig) -> bool:
    d = _state_dir(cfg)
    return (os.path.exists(os.path.join(d, STATE_FILE))
            and os.path.exists(os.path.join(d, META_FILE)))


def load_experiment(strategy, cfg: ExperimentConfig) -> int:
    """Restore ``strategy`` in place from the last completed round; returns
    the round to resume from (reference: load_experiment returns
    ``previous_round + 1``, resume_training.py:35)."""
    logger = get_logger()
    directory = _state_dir(cfg)
    with np.load(os.path.join(directory, STATE_FILE)) as arrs:
        arrays = {k: arrs[k] for k in arrs.files}
    with open(os.path.join(directory, META_FILE)) as fh:
        meta = json.load(fh)

    saved_fmt = int(meta.get("model_format", 1))
    if saved_fmt != MODEL_FORMAT_VERSION:
        # Shapes would match, so the npz/msgpack loads would succeed and
        # the run would silently diverge — refuse instead.
        raise RuntimeError(
            f"Saved experiment in {directory} uses model format "
            f"{saved_fmt}, this code writes {MODEL_FORMAT_VERSION}: its "
            "checkpointed weights are not alignment-compatible with the "
            "current conv padding. Restart the experiment (or re-run with "
            "the code version that wrote it).")

    # Warn (don't fail) on config drift, mirroring resume_training.py:22-25.
    current = {k: _jsonable(v) for k, v in config_to_dict(cfg).items()}
    saved = meta.get("config", {})
    for key in sorted(set(saved) | set(current)):
        if key in ("resume_training",):
            continue
        if saved.get(key) != current.get(key):
            logger.warning(
                f"Resume config mismatch for '{key}': saved "
                f"{saved.get(key)!r} != current {current.get(key)!r}")

    init_key = arrays.pop("init_key")
    strategy.pool = PoolState.from_arrays(arrays)
    import jax
    strategy._init_key = jax.numpy.asarray(init_key)
    strategy.rng.bit_generator.state = meta["rng_state"]
    strategy.best_epoch = int(meta.get("best_epoch", 0))

    prev_round = int(meta["round"])
    strategy.round = prev_round
    # Reload the trained model of the completed round so the next round's
    # query scores with it (the reference gets this for free by pickling the
    # whole object with its weights).  The state skeleton is built with a
    # throwaway key — NOT init_network_weights, which would consume a split
    # of the restored _init_key (diverging post-resume training from an
    # uninterrupted run).  It holds the frozen leaves, which no best
    # checkpoint does (Strategy.skeleton_state).
    best = strategy.weight_paths()["best_ckpt"]
    if os.path.exists(best):
        if strategy.state is None:
            strategy.state = strategy.skeleton_state()
        strategy.load_best_ckpt()
    aux_path = os.path.join(directory, AUX_FILE)
    if os.path.exists(aux_path):
        with open(aux_path, "rb") as fh:
            strategy.restore_aux_state(fh.read())
        logger.info("Restored sampler aux state (VAE/discriminator)")
    logger.info(f"Resuming experiment from round {prev_round + 1}")
    return prev_round + 1


def saved_experiment_key(cfg: ExperimentConfig) -> Optional[str]:
    """The metrics experiment key of a saved run (for sink reattachment)."""
    path = os.path.join(_state_dir(cfg), META_FILE)
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        return json.load(fh).get("experiment_key")


def _jsonable(v: Any):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (tuple, list)):
        return [_jsonable(e) for e in v]
    return str(v)
