"""The comparison's own arithmetic, on small hand-checked inputs."""

import numpy as np
import pytest

from bench_testlib import family


def test_margin_pick_regret_is_zero_only_for_the_smallest_scores():
    from lib import reference as ref
    scores = np.array([0.5, 0.1, 0.3, 0.2, 0.4])
    assert ref.margin_pick_regret(scores, np.array([1, 3])) == 0.0
    assert ref.margin_pick_regret(scores, np.array([1, 0])) == \
        pytest.approx(0.3)
    ties = np.array([0.1, 0.1, 0.1, 0.9])
    assert ref.margin_pick_regret(ties, np.array([0, 2])) == 0.0


def greedy(emb, labeled, k):
    d = ((emb[:, None, :] - emb[None, labeled, :]) ** 2).sum(-1).min(1)
    d[labeled] = 0.0
    picks = []
    for _ in range(k):
        i = int(np.argmax(d))
        picks.append(i)
        d = np.minimum(d, ((emb - emb[i]) ** 2).sum(-1))
    return np.array(picks)


def test_kcenter_regret_follows_the_greedy_picks():
    from lib import reference as ref
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((96, 8)).astype(np.float32)
    labeled = np.arange(0, 96, 7)
    picks = greedy(emb.astype(np.float64), labeled, 6)
    assert ref.kcenter_regret(emb, labeled, picks) < 1e-5
    wrong = picks.copy()
    wrong[2] = labeled[0]            # a row that is already a centre
    assert ref.kcenter_regret(emb, labeled, wrong) == pytest.approx(1.0)


def test_leaf_change_gap_reads_one_for_an_unmoved_leaf():
    from lib import reference as ref
    start = {"a": np.zeros(4, np.float32), "b": np.zeros(3, np.float32)}
    moved = {"a": np.ones(4, np.float32), "b": np.full(3, 0.01, np.float32)}
    keys = ["a", "b"]
    assert ref.leaf_change_gap(moved, moved, start, keys) == 0.0
    assert ref.leaf_change_gap(start, moved, start, keys) == \
        pytest.approx(1.0)
    double = {k: 2 * v for k, v in moved.items()}
    assert ref.leaf_change_gap(double, moved, start, keys) == \
        pytest.approx(1.0)
    # a small leaf is measured against the median leaf's change
    off = {"a": moved["a"], "b": np.full(3, 0.02, np.float32)}
    assert ref.leaf_change_gap(off, moved, start, keys) < 0.02


def test_counts_and_margins():
    from lib import reference as ref
    logits = np.array([[3.0, 1.0, 0.0, -1.0, -2.0, -3.0],
                       [0.0, 0.1, 0.2, 0.3, 0.4, 5.0]])
    assert ref.top_counts(logits, np.array([0, 0])) == (1, 1)
    assert ref.top_counts(logits, np.array([1, 4])) == (0, 2)
    m = ref.margins(logits)
    p = np.exp(logits[0]) / np.exp(logits[0]).sum()
    assert m[0] == pytest.approx(p[0] - p[1])


def test_compare_names_every_number_and_reads_zero_on_itself():
    from lib import reference as ref
    out = {"losses": [2.0, 1.9, 1.8], "gnorms": [0.5, 0.4, 0.3],
           "params": {"w": np.ones(3, np.float32)}, "trained": ["w"],
           "test_counts": (3, 9), "scores": np.array([0.1, 0.2, 0.4])}
    start = {"w": np.zeros(3, np.float32)}
    same = ref.compare(out, out, start, 64)
    assert set(same) == {"loss3", "gnorm1", "dparam", "test_gap",
                         "score_gap"}
    assert all(v == 0.0 for v in same.values())
    off = dict(out, losses=[2.0, 1.9, 1.98], test_counts=(3, 1))
    got = ref.compare(off, out, start, 64)
    assert got["loss3"] == pytest.approx(0.1)
    assert got["test_gap"] == pytest.approx(8 / 64)
    scaled = dict(out, scores=out["scores"] * 1.1)
    assert ref.compare(scaled, out, start, 64)["score_gap"] == \
        pytest.approx(0.1)


def test_fp8_control_rounds_values_and_passes_gradients_straight_through():
    import jax
    import jax.numpy as jnp
    from lib import reference as ref
    x = jnp.linspace(-1.0, 1.0, 101)
    q = ref.q(x, "fp8")
    assert float(jnp.max(jnp.abs(q - x))) > 1e-3       # coarser than bf16
    assert float(jnp.max(jnp.abs(q - x))) < 0.07
    g = jax.grad(lambda v: jnp.sum(ref.q(v, "fp8") ** 2))(x)
    assert np.allclose(np.asarray(g), 2 * np.asarray(q), atol=1e-6)
    with pytest.raises(KeyError):
        ref.q(x, "int3")


def test_step_flips_follow_the_key_chain():
    """The chain is ``lib/reference``'s, what a step draws from its key the
    family's: together they flip what the one function used to."""
    import jax
    import jax.numpy as jnp
    from lib import reference as ref
    key = np.asarray(jax.random.PRNGKey(5))
    a = ref.step_keys(key, 3)
    assert a.shape == (3, 2) and np.array_equal(a, ref.step_keys(key, 3))
    k1, sub = jax.random.split(jnp.asarray(key))
    assert np.array_equal(a[0], np.asarray(sub))
    assert np.array_equal(a[1], np.asarray(jax.random.split(k1)[1]))
    rows = np.arange(16 * 2 * 4 * 3, dtype=np.uint8).reshape(16, 2, 4, 3)
    view = jax.jit(family().train_view)

    def flipped(step_key):
        got = np.asarray(view(jnp.asarray(rows), jnp.asarray(step_key),
                              jnp.asarray(True)))
        bits = np.array([not np.array_equal(g, r)
                         for g, r in zip(got, rows)])
        assert all(np.array_equal(g, r[:, ::-1] if b else r)
                   for g, r, b in zip(got, rows, bits))
        return bits
    _, kf = jax.random.split(sub)
    assert np.array_equal(flipped(a[0]), np.asarray(
        jax.random.bernoulli(kf, 0.5, (16,))))
    assert not np.array_equal(flipped(a[0]), flipped(a[1]))
    still = view(jnp.asarray(rows), jnp.asarray(a[0]), jnp.asarray(False))
    assert np.array_equal(np.asarray(still), rows)
