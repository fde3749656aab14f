"""The plain reference and the comparison that decides ``correct``.

The configuration's family (``families/__init__.py``) gives the plain
forward (float32, matmul precision ``highest``) and the train view as
arguments; here are its loss and gradients, plain SGD with momentum, greedy
k-center, and the comparison of what the timed path produced with what this
gives on the same rows.  It imports nothing of the program and takes no
tensor the program made: its parameters come from the family's
``make_weights`` and the rows from its ``make_data``.  From the program it
takes only decisions: which rows each step drew, the step's augmentation
key, the epoch the program kept, what it picked.

``quant="fp8"`` is the control: the same reference with the inputs of every
contraction rounded to float8 (e4m3, per-tensor scale), the step below the
bfloat16 the configurations state.  ``fault`` plants the faults a cell can
have in the reference put in the program's place.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def q(x, quant: Optional[str]):
    """Round to the control's precision, straight-through for gradients."""
    if quant is None:
        return x
    import jax
    import jax.numpy as jnp
    if quant != "fp8":
        raise KeyError(f"unknown control precision {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(rounded * scale - x)


@functools.lru_cache(maxsize=None)
def _forward_fn(family, config_key: str, quant: Optional[str]):
    import json
    import jax
    config = json.loads(config_key)

    def forward(p, rows):
        emb = family.embed(p, rows, config, quant)
        return family.head(p, emb, quant), emb

    return jax.jit(forward)


@functools.lru_cache(maxsize=None)
def _step_fn(family, config_key: str, quant: Optional[str], frozen: bool,
             mu: float, wd: float, update: bool):
    """One SGD step over a batch given in micro-batches."""
    import json
    import jax
    import jax.numpy as jnp
    config = json.loads(config_key)

    def loss_sum(trained, fixed, rows, labels, weights):
        p = {**fixed, **trained}
        emb = family.embed(p, rows, config, quant)
        if frozen:
            emb = jax.lax.stop_gradient(emb)
        logp = jax.nn.log_softmax(family.head(p, emb, quant))
        ce = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        return jnp.sum(ce * weights)

    def step(trained, momentum, fixed, xs, labels, weights, step_key,
             augment, lr):
        """``xs`` [micro-batches, rows, ...]: the batch's gradient is the
        sum of its micro-batches' (the forward treats its rows one by one),
        over the batch's weight.  The train view is taken of the whole
        batch, as the program's step draws it from the step's key."""
        xs = family.train_view(xs.reshape((-1,) + xs.shape[2:]), step_key,
                               augment).reshape(xs.shape)

        def body(carry, inp):
            total, grads = carry
            val, g = jax.value_and_grad(loss_sum)(trained, fixed, *inp)
            return (total + val, jax.tree.map(jnp.add, grads, g)), None
        zero = jax.tree.map(jnp.zeros_like, trained)
        (total, grads), _ = jax.lax.scan(
            body, (jnp.float32(0.0), zero), (xs, labels, weights))
        denom = jnp.maximum(jnp.sum(weights), 1e-12)
        grads = jax.tree.map(lambda g: g / denom, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in grads.values()))
        if update:
            new_m, new_p = {}, {}
            for k in trained:
                d = grads[k] + wd * trained[k] if wd else grads[k]
                new_m[k] = d + mu * momentum[k]
                new_p[k] = trained[k] - lr * new_m[k]
            trained, momentum = new_p, new_m
        return trained, momentum, total / denom, gnorm

    return jax.jit(step, donate_argnums=(0, 1))


def _config_key(config: Dict) -> str:
    import json
    return json.dumps(config, sort_keys=True)


def step_keys(epoch_key: np.ndarray, steps: int) -> np.ndarray:
    """The key of each step of one epoch, [steps, 2] uint32: the program's
    key chain splits once per step (carry, step key).  What a step draws
    from its key is the family's ``train_view``."""
    import jax
    key = jax.numpy.asarray(np.asarray(epoch_key, dtype=np.uint32))
    out = np.zeros((steps, 2), dtype=np.uint32)
    for s in range(steps):
        key, sub = jax.random.split(key)
        out[s] = np.asarray(sub)
    return out


def forward_rows(family, p: Dict, images: np.ndarray, idxs: np.ndarray,
                 config: Dict, quant=None, block: int = 128
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(logits, embeddings) of ``images[idxs]``, in blocks of rows."""
    import jax.numpy as jnp
    fwd = _forward_fn(family, _config_key(config), quant)
    logits, embs = [], []
    idxs = np.asarray(idxs)
    for s in range(0, len(idxs), block):
        part = idxs[s:s + block]
        pad = block - len(part)
        rows = images[np.concatenate([part, np.repeat(part[:1], pad)])
                      if pad else part]
        lg, em = fwd(p, jnp.asarray(rows))
        logits.append(np.asarray(lg)[:len(part)])
        embs.append(np.asarray(em)[:len(part)])
    return np.concatenate(logits), np.concatenate(embs)


def follow_fit(family, weights: Dict[str, np.ndarray], images: np.ndarray,
               labels: np.ndarray, fit: Dict, config: Dict, hyper: Dict,
               frozen: bool, quant=None, fault: Optional[str] = None,
               micro: int = 32) -> Dict[str, Any]:
    """Follow one recorded fit step for step from the seed's weights.

    ``fit["epochs"]`` holds, per epoch, the rows of every real step
    (``idx`` [steps, batch] pool rows, ``mask``), the epoch's augmentation
    key and learning rate.  Returns the per-step losses and gradient norms of
    the first epoch and the parameters after the epoch the program kept."""
    import jax.numpy as jnp
    step = _step_fn(family, _config_key(config), quant, frozen,
                    float(hyper["momentum"]), float(hyper["weight_decay"]),
                    fault != "state_unchanged")
    train_keys = family.trainable_keys(weights, head_only=frozen)
    trained = {k: jnp.array(weights[k]) for k in train_keys}
    fixed = {k: jnp.asarray(v) for k, v in weights.items()
             if k not in trained}
    momentum = {k: jnp.zeros_like(v) for k, v in trained.items()}
    losses: List[Any] = []
    gnorms: List[Any] = []
    for e, ep in enumerate(fit["epochs"][:int(fit["best_epoch"])]):
        idx, mask = np.asarray(ep["idx"]), np.asarray(ep["mask"])
        batch = idx.shape[1]
        m = min(micro, batch)
        assert batch % m == 0, "micro-batch must divide the batch"
        keys = step_keys(ep["key"], len(idx))
        augment = bool(ep.get("augment", True))
        for s in range(len(idx)):
            rows, w = idx[s], mask[s].astype(np.float32)
            if fault == "half_batch":
                # Half of the batch left out, the mean taken over the rest.
                w = w.copy()
                w[len(w) // 2:] = 0.0
            shape = (batch // m, m)
            trained, momentum, loss, gnorm = step(
                trained, momentum, fixed,
                jnp.asarray(images[rows].reshape(shape + images.shape[1:])),
                jnp.asarray(labels[rows].astype(np.int32).reshape(shape)),
                jnp.asarray(w.reshape(shape)),
                jnp.asarray(keys[s]), jnp.asarray(augment),
                jnp.float32(ep["lr"]))
            if e == 0 and s < 3:
                losses.append(loss)
                gnorms.append(gnorm)
    params = {**{k: np.asarray(v) for k, v in fixed.items()},
              **{k: np.asarray(v) for k, v in trained.items()}}
    return {"losses": [float(v) for v in losses],
            "gnorms": [float(v) for v in gnorms], "params": params,
            "trained": train_keys}


def top_counts(logits: np.ndarray, labels: np.ndarray) -> Tuple[int, int]:
    order = np.argsort(-logits, axis=1, kind="stable")[:, :5]
    top1 = int(np.sum(order[:, 0] == labels))
    top5 = int(np.sum((order == labels[:, None]).any(axis=1)))
    return top1, top5


def margins(logits: np.ndarray) -> np.ndarray:
    z = logits.astype(np.float64)
    z = z - z.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    top2 = -np.sort(-p, axis=1)[:, :2]
    return top2[:, 0] - top2[:, 1]


@functools.lru_cache(maxsize=None)
def _kcenter_fns():
    """(min distance to a block of centres, the pick-by-pick follow); the
    embeddings are arguments, never constants of the compiled programs."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def min_to(x, sqn, centres):
        c = x[centres]
        d = (sqn[:, None] + jnp.sum(c * c, axis=1)[None, :]
             - 2.0 * jnp.matmul(x, c.T, precision=hi))
        return jnp.min(d, axis=1)

    @jax.jit
    def follow(x, sqn, min_d, picks):
        def body(min_d, pick):
            far = jnp.max(min_d)
            short = (far - min_d[pick]) / jnp.maximum(far, 1e-30)
            c = x[pick]
            d = sqn + jnp.sum(c * c) - 2.0 * jnp.matmul(x, c, precision=hi)
            return jnp.minimum(min_d, d), short
        return jax.lax.scan(body, min_d, picks)[1]

    return min_to, follow


def kcenter_regret(emb: np.ndarray, labeled_rows: np.ndarray,
                   picks: np.ndarray) -> float:
    """Greedy k-center, followed pick by pick over the program's own
    embeddings: before each pick the reference computes every row's squared
    distance to the nearest centre so far (float32, precision ``highest``),
    and reads how far the program's pick falls short of the farthest row, as
    a share of that row's distance.  ``emb`` is [n, d] over the rows the
    selection ran over; ``labeled_rows`` and ``picks`` index into it."""
    import jax.numpy as jnp
    min_to, follow = _kcenter_fns()
    x = jnp.asarray(emb, jnp.float32)
    sqn = jnp.sum(x * x, axis=1)
    min_d = jnp.full((x.shape[0],), jnp.inf, jnp.float32)
    labeled_rows = np.asarray(labeled_rows)
    for s in range(0, len(labeled_rows), 1024):
        part = labeled_rows[s:s + 1024]
        pad = 1024 - len(part)
        if pad:
            part = np.concatenate([part, np.repeat(part[:1], pad)])
        min_d = jnp.minimum(min_d, min_to(x, sqn, jnp.asarray(part)))
    # A labeled row is its own centre: never a candidate.
    min_d = min_d.at[jnp.asarray(labeled_rows)].set(0.0)
    short = follow(x, sqn, min_d, jnp.asarray(np.asarray(picks)))
    return float(jnp.max(short))


def leaf_change_gap(cand: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                    start: Dict[str, np.ndarray], keys: List[str]) -> float:
    """Worst leaf's gap between the candidate's and the reference's norm of
    change, against the reference's norm of that leaf's change or of the
    median leaf's, whichever is larger."""
    ref_n = np.array([np.linalg.norm((ref[k] - start[k]).astype(np.float64))
                      for k in keys])
    cand_n = np.array([np.linalg.norm((cand[k] - start[k]).astype(np.float64))
                       for k in keys])
    floor = float(np.median(ref_n))
    return float(np.max(np.abs(cand_n - ref_n)
                        / np.maximum(np.maximum(ref_n, floor), 1e-30)))


def reference_outputs(family, weights, images, labels, test_images,
                      test_labels, record: Dict, config: Dict, hyper: Dict,
                      frozen: bool, quant=None, fault=None,
                      micro: int = 32) -> Dict:
    """Everything the comparison reads, computed by the reference (or by the
    control / a planted fault) on the rows the program's record names."""
    fit = follow_fit(family, weights, images, labels, record["fit"], config,
                     hyper, frozen, quant=quant, fault=fault, micro=micro)
    import jax.numpy as jnp
    params = {k: jnp.asarray(v) for k, v in fit["params"].items()}
    t_logits, _ = forward_rows(family, params, test_images,
                               np.arange(len(test_images)), config, quant)
    out = {"losses": fit["losses"], "gnorms": fit["gnorms"],
           "params": fit["params"], "trained": fit["trained"],
           "test_counts": top_counts(t_logits, test_labels)}
    sample = np.asarray(record["score"]["sample_rows"])
    seed_p = {k: jnp.asarray(v) for k, v in weights.items()}
    # Scores are taken with the parameters the program scored with: the
    # kept epoch's under fine-tuning, the seed's encoder when it is frozen
    # (the embedding never sees the head).
    s_logits, s_emb = forward_rows(family, seed_p if frozen else params,
                                   images, sample, config, quant)
    if record["score"]["kind"] == "margin":
        out["scores"] = margins(s_logits)
    else:
        out["scores"] = s_emb
    return out


def compare(cand: Dict, ref: Dict, start: Dict[str, np.ndarray],
            n_test: int) -> Dict[str, float]:
    """The numbers compared: the candidate (the program, the control or a
    planted fault) against the reference.  ``score_gap`` is the gap of the
    sampled scores (margins, or embeddings row by row) as a whole: the norm
    of the difference over the norm of the reference's."""
    loss3 = max(abs(c - r) / max(abs(r), 1e-30)
                for c, r in zip(cand["losses"], ref["losses"]))
    gnorm1 = abs(cand["gnorms"][0] - ref["gnorms"][0]) / max(
        ref["gnorms"][0], 1e-30)
    dparam = leaf_change_gap(cand["params"], ref["params"], start,
                             ref["trained"])
    test_gap = max(abs(c - r) for c, r in zip(cand["test_counts"],
                                              ref["test_counts"])) / n_test
    c_s = np.asarray(cand["scores"], dtype=np.float64)
    r_s = np.asarray(ref["scores"], dtype=np.float64)
    score_gap = float(np.linalg.norm(c_s - r_s)
                      / max(float(np.linalg.norm(r_s)), 1e-30))
    return {"loss3": float(loss3), "gnorm1": float(gnorm1),
            "dparam": float(dparam), "test_gap": float(test_gap),
            "score_gap": score_gap}


def margin_pick_regret(scores: np.ndarray, picked_pos: np.ndarray) -> float:
    """Smallest-margin selection, given the program's own scores: how far
    the largest picked score lies above the smallest score left unpicked
    (0 when the picks are the smallest, ties aside)."""
    mask = np.zeros(len(scores), dtype=bool)
    mask[np.asarray(picked_pos)] = True
    if mask.all() or not mask.any():
        return 0.0
    return float(max(0.0, scores[mask].max() - scores[~mask].min()))
