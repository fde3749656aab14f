"""Checkpoint I/O.

Replaces the reference's ``torch.save(state_dict)`` per-round best/current
checkpoints (src/query_strategies/strategy.py:425-440) and the whole-object
pickle resume (src/utils/resume_training.py) with explicit artifacts:
  * model variables (params + batch_stats) as msgpack (flax.serialization);
  * experiment state (pool masks, round, rng, config echo) as npz + json —
    see experiment/resume.py.

Checkpoint paths follow the reference's layout
(strategy.py:165-173): ``{ckpt_root}/{exp_name}_{exp_hash}/best_rd_{n}`` and
``rd_{n}``.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from flax import serialization

from .. import faults

# Bumped whenever saved model weights stop being interchangeable across
# code versions even though their SHAPES still match — e.g. the conv
# padding fix (models/resnet.py: strided 3x3 convs moved from XLA-SAME to
# torch-exact (1, 1) padding), where old weights would load cleanly into
# the new graph and silently score through one-pixel-shifted windows.
# Checked by BOTH resume surfaces: experiment-level (experiment/resume.py,
# hard error) and mid-round fit state (below, discard + warn — the round
# safely restarts from scratch).  Version 1 = states saved before the
# field existed, i.e. the pre-padding-fix alignment.
MODEL_FORMAT_VERSION = 2


def tree_bytes(tree: Any) -> int:
    """Bytes the arrays of a pytree hold, from their shapes (no fetch) —
    the ``bytes`` counter of the ``ckpt/*`` spans."""
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree.leaves(tree)))


def serialize(tree: Any) -> bytes:
    """The bytes of a checkpoint file: msgpack of the tree's host copy
    (``jax.device_get``: every leaf's transfer is started before the
    first is waited for; a tree already on the host passes through).
    Two files that hold the same tree may be written from one
    serialisation."""
    return serialization.msgpack_serialize(jax.device_get(tree))


def write_bytes(path: str, data: bytes) -> None:
    """Atomic write (tmp + rename): a reader never sees a half-written
    checkpoint — mid-round resume (experiment/resume.py) and non-writer
    pod processes both read these files.  Every file passes its own
    ``ckpt_write`` fault site."""
    faults.site("ckpt_write")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as fh:
        fh.write(data)
    os.replace(tmp, path)


def save_variables(path: str, variables: Dict[str, Any]) -> None:
    """Fetch, serialise, write atomically."""
    write_bytes(path, serialize(variables))


def load_variables(path: str, like: Dict[str, Any] = None) -> Dict[str, Any]:
    with open(path, "rb") as fh:
        restored = serialization.msgpack_restore(fh.read())
    if like is not None:
        restored = serialization.from_state_dict(like, restored)
    return restored


def weight_paths(ckpt_root: str, exp_name: str, exp_hash: str,
                 round_idx: int) -> Dict[str, str]:
    """best/current/previous checkpoint paths for a round
    (strategy.py:165-173; ``previous_ckpt`` kept for parity though the
    reference never consumes it).  ``fit_state`` is this framework's
    addition: the mid-round resume state (the reference writes rd_{n}.pth
    every epoch but never reads it back — strategy.py:440,
    resume_training.py:8-52 resume at round granularity only)."""
    ckpt_dir = os.path.join(ckpt_root, f"{exp_name}_{exp_hash}")
    os.makedirs(ckpt_dir, exist_ok=True)
    return {
        "best_ckpt": os.path.join(ckpt_dir, f"best_rd_{round_idx}.msgpack"),
        "previous_ckpt": os.path.join(ckpt_dir, f"rd_{round_idx - 1}.msgpack"),
        "current_ckpt": os.path.join(ckpt_dir, f"rd_{round_idx}.msgpack"),
        "fit_state": os.path.join(ckpt_dir, f"fit_state_rd_{round_idx}"),
        "dir": ckpt_dir,
    }


def latest_best_ckpt(ckpt_dir: str) -> Tuple[Optional[str], int]:
    """(path, round) of the newest round's ``best_rd_{n}.msgpack`` under
    ``ckpt_dir``, or (None, -1) when none exists.

    The scoring service's hot-reload probe (serve/executor.py): a
    running AL experiment appends best checkpoints round by round, and
    the service polls this between batches to serve the freshest model
    without a restart.  Writes are atomic (save_variables), so whatever
    this returns is always a complete file."""
    best: Tuple[Optional[str], int] = (None, -1)
    try:
        names = os.listdir(ckpt_dir)
    except (FileNotFoundError, NotADirectoryError):
        return best
    for name in names:
        m = _BEST_CKPT_RE.match(name)
        if m and int(m.group(1)) > best[1]:
            best = (os.path.join(ckpt_dir, name), int(m.group(1)))
    return best


_BEST_CKPT_RE = re.compile(r"^best_rd_(\d+)\.msgpack$")


# -- best-ckpt publish/subscribe --------------------------------------------
#
# The best checkpoint has CONCURRENT READERS now: the serve executor's
# hot reload (between batches) and the speculative scorer of the
# pipelined round (experiment/pipeline.py) both load ``best_rd_{n}``
# while the trainer is still writing newer ones.  Atomic tmp+rename
# (save_variables) already guarantees no reader sees a torn FILE; what
# it cannot guarantee is freshness attribution — two publishes inside
# one mtime granule look identical to an mtime-stamped poller, and a
# reader that pairs new weights with a stale version guess would score
# pool chunks it later trusts as current.  So every best-ckpt publish
# also writes a TAG sidecar (``best_rd_{n}.msgpack.tag.json``, atomic)
# carrying the monotonic (round, epoch) the weights were best at:
# within one round the best epoch only ever increases, so the tag is a
# strictly monotonic version — never reused, never clock-dependent.
# Write order is weights THEN tag; BestCkptWatcher re-reads the tag
# after loading and treats any disagreement as not-ready (retry next
# poll), so a poll result's (variables, tag) pairing is always either
# exact or attributed to an OLDER tag than the weights — which the
# pipeline's invalidation rule (anything not the final best is
# recomputed) turns into wasted work, never a wrong score.

def publish_best(path: str, variables: Dict[str, Any], *, round_idx: int,
                 epoch: int) -> None:
    """Atomically publish a best checkpoint plus its monotonic
    (round, epoch) tag — the writer side of the best-ckpt bus."""
    publish_best_bytes(path, serialize(variables), round_idx=round_idx,
                       epoch=epoch)


def publish_best_bytes(path: str, data: bytes, *, round_idx: int,
                       epoch: int) -> None:
    """``publish_best`` of a tree already serialised (``serialize``): a
    retried publish writes the same bytes again, weights THEN tag."""
    write_bytes(path, data)
    # Torn point between the pair's two renames: a crash here leaves
    # weights WITHOUT their tag — exactly the partial publish the
    # watcher's legacy/tag-mismatch rules must absorb (chaos-tested via
    # ckpt_write:torn@N).
    faults.site("ckpt_write", point="torn")
    tag = {"round": int(round_idx), "epoch": int(epoch)}
    tmp = f"{path}.tag.json.tmp"
    with open(tmp, "w") as fh:
        json.dump(tag, fh)
    os.replace(tmp, f"{path}.tag.json")


def read_best_tag(path: str) -> Optional[Tuple[int, int]]:
    """The (round, epoch) tag published alongside ``path``; None when the
    sidecar is absent (a pre-tag writer) or unreadable."""
    try:
        with open(f"{path}.tag.json") as fh:
            tag = json.load(fh)
        return (int(tag["round"]), int(tag["epoch"]))
    except (OSError, ValueError, KeyError, TypeError):
        return None


class BestCkptWatcher:
    """Shared hot-reload probe over an experiment's checkpoint directory
    — ONE spelling of "give me the newest fully-published best ckpt"
    for every concurrent reader (the serve executor between batches,
    the speculative scorer of the pipelined round).

    ``poll()`` returns ``(variables, round, tag)`` when a best ckpt
    NEWER than the last successful poll is completely published, else
    None.  Newness is judged by the monotonic (round, epoch) tag when
    one exists and falls back to (round, mtime) for pre-tag writers; the
    tag is re-read after the weight load and any disagreement reads as
    not-ready (the writer raced between the two renames — the next poll
    sees the settled pair).  A torn or half-written file is impossible
    by construction (every rename is atomic)."""

    def __init__(self, ckpt_dir: str):
        self.ckpt_dir = ckpt_dir
        self._stamp: Optional[Tuple] = None

    @staticmethod
    def _stamp_of(rd: int, tag, mtime: float) -> Tuple:
        # The tag orders publishes exactly; mtime rides along only for
        # tag-less (legacy) writers, where it is the best available.  A
        # tagged publish always supersedes an untagged one at the same
        # round (the tagged writer is the newer code), and the tuple
        # shape keeps every stamp comparable.
        return ((rd, 0, (-1, -1), mtime) if tag is None
                else (rd, 1, tag, 0.0))

    def prime(self) -> None:
        """Mark the CURRENT newest publish as already-seen WITHOUT
        loading it.  A subscriber that only cares about future
        publishes (the speculative scorer arming at round start, when
        the newest file on disk is the previous round's best) would
        otherwise deserialize a full checkpoint on its first poll just
        to discard it by round."""
        path, rd = latest_best_ckpt(self.ckpt_dir)
        if path is None:
            return
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            return
        stamp = self._stamp_of(rd, read_best_tag(path), mtime)
        if self._stamp is None or stamp > self._stamp:
            self._stamp = stamp

    def poll(self):
        path, rd = latest_best_ckpt(self.ckpt_dir)
        if path is None:
            return None
        tag = read_best_tag(path)
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            return None
        stamp = self._stamp_of(rd, tag, mtime)
        if self._stamp is not None and stamp <= self._stamp:
            return None
        try:
            variables = load_variables(path)
        except (OSError, ValueError):
            # The file rotated away mid-read (a newer round replaced
            # it); the next poll sees the settled state.
            return None
        if read_best_tag(path) != tag:
            # Writer raced between the weight rename and the tag rename:
            # the pairing cannot be proven, so report nothing and let the
            # next poll observe the completed publish.
            return None
        self._stamp = stamp
        return variables, rd, tag


# -- mid-round fit state ----------------------------------------------------
#
# Everything needed to continue an interrupted Trainer.fit from the last
# completed epoch, bit-for-bit: model variables, optimizer state, the
# early-stopping bookkeeping, the jax PRNG-key chain, and the numpy
# Generator state that drives batch shuffling.  Two files per round:
# {path}.msgpack (the big trees) + {path}.json (counters + rng state).
# Each file is written atomically, and both carry the same (round, epoch)
# stamp, cross-checked at load: a crash part-way through the pair — before
# the json exists, or between the two os.replace calls when OVERWRITING an
# earlier save — can never pair one epoch's weights with another epoch's
# counters; the torn state reads as nothing-to-resume instead.

def save_fit_state(path: str, *, variables: Dict[str, Any], opt_state: Any,
                   step: Any, epoch: int, round_idx: int, best_perf: float,
                   best_epoch: int, es_count: int, key: Any,
                   rng: np.random.Generator) -> None:
    faults.site("ckpt_write")
    trees = {
        "variables": serialization.to_state_dict(
            jax.tree.map(np.asarray, variables)),
        "opt_state": serialization.to_state_dict(
            jax.tree.map(np.asarray, opt_state)),
        "stamp": np.asarray([int(round_idx), int(epoch)]),
    }
    with open(path + ".msgpack.tmp", "wb") as fh:
        fh.write(serialization.msgpack_serialize(trees))
    os.replace(path + ".msgpack.tmp", path + ".msgpack")
    # Torn point between the pair's renames: trees without counters — the
    # stamp cross-check in load_fit_state reads it as nothing-to-resume.
    faults.site("ckpt_write", point="torn")
    meta = {
        "epoch": int(epoch),
        "round_idx": int(round_idx),
        "model_format": MODEL_FORMAT_VERSION,
        "step": int(np.asarray(step)),
        "best_perf": float(best_perf),
        "best_epoch": int(best_epoch),
        "es_count": int(es_count),
        "key": np.asarray(key).tolist(),
        "rng_state": rng.bit_generator.state,
    }
    with open(path + ".json.tmp", "w") as fh:
        json.dump(meta, fh)
    os.replace(path + ".json.tmp", path + ".json")


def load_fit_state(path: str, round_idx: int) -> Optional[Dict[str, Any]]:
    """Return the saved mid-round state, or None when there is nothing to
    resume (no file, or a state belonging to a different round)."""
    if not (os.path.exists(path + ".msgpack")
            and os.path.exists(path + ".json")):
        return None
    with open(path + ".json") as fh:
        meta = json.load(fh)
    if meta.get("round_idx") != int(round_idx):
        return None
    if int(meta.get("model_format", 1)) != MODEL_FORMAT_VERSION:
        from ..utils.logging import get_logger
        get_logger().warning(
            f"Discarding mid-round fit state with model format "
            f"{meta.get('model_format', 1)} (this code writes "
            f"{MODEL_FORMAT_VERSION}); the round restarts from scratch")
        return None
    with open(path + ".msgpack", "rb") as fh:
        trees = serialization.msgpack_restore(fh.read())
    stamp = np.asarray(trees.pop("stamp", [-1, -1])).tolist()
    if stamp != [meta["round_idx"], meta["epoch"]]:
        # Torn or corrupt pair (a missing stamp included): the weight
        # trees and the counters cannot be proven to be from the same
        # epoch, so there is nothing safe to resume.
        return None
    return {**meta, **trees}


def delete_fit_state(path: str) -> None:
    for suffix in (".msgpack", ".json"):
        try:
            os.remove(path + suffix)
        except FileNotFoundError:
            pass
