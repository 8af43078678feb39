"""Dirichlet partitioner: exactness, skew monotonicity, HD calibration."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)

from hypothesis import given, settings, strategies as st

from repro.core.hellinger import average_hd
from repro.data.partition import (
    calibrate_alpha,
    dirichlet_partition,
    label_histograms,
    pack_clients,
)
from repro.data.synthetic import make_classification


@given(
    st.integers(2, 12),            # clients
    st.floats(0.05, 10.0),         # alpha
    st.integers(0, 10**6),         # seed
)
@settings(max_examples=25, deadline=None)
def test_partition_is_exact(k, alpha, seed):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 5, 400)
    parts = dirichlet_partition(labels, k, alpha, seed=seed)
    allidx = np.concatenate(parts)
    assert len(allidx) == 400
    assert len(np.unique(allidx)) == 400           # every sample exactly once
    assert all(len(p) >= 8 for p in parts)          # min-size guarantee


def test_skew_monotone_in_alpha():
    """Monotone in the practical range (extreme-skew top-up causes known
    mild non-monotonicity below ~0.05 — see calibrate_alpha docstring)."""
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, 5000)
    hds = []
    for alpha in [0.1, 0.5, 2.0, 10.0]:
        parts = dirichlet_partition(labels, 20, alpha, seed=0)
        h = label_histograms(labels, parts, 10)
        hds.append(float(average_hd(h)))
    assert hds[0] > hds[-1]                        # more alpha → more IID
    assert hds == sorted(hds, reverse=True)


def test_calibrate_alpha_hits_target():
    rng = np.random.default_rng(1)
    labels = rng.integers(0, 10, 8000)
    for target in [0.9, 0.7]:
        alpha = calibrate_alpha(labels, 50, target, 10, seed=1)
        parts = dirichlet_partition(labels, 50, alpha, seed=1)
        hd = float(average_hd(label_histograms(labels, parts, 10)))
        assert abs(hd - target) < 0.06


def test_pack_clients_masks_padding():
    ds = make_classification(300, n_features=64 * 1, n_classes=4, seed=0)
    # n_features must be square: use 64 → 8×8
    parts = dirichlet_partition(ds.y, 6, 0.3, seed=0)
    xs, ys, mask = pack_clients(ds.x, ds.y, parts)
    assert xs.shape[0] == 6 and xs.shape[1] == max(len(p) for p in parts)
    for i, p in enumerate(parts):
        assert mask[i].sum() == len(p)
        np.testing.assert_array_equal(ys[i, : len(p)], ds.y[p])


def test_shard_partition_balanced_and_skewed():
    from repro.data.partition import calibrate_shards, shard_partition

    rng = np.random.default_rng(3)
    labels = rng.integers(0, 10, 10_000)
    parts = shard_partition(labels, 100, shards_per_client=1, seed=0)
    allidx = np.concatenate(parts)
    assert len(np.unique(allidx)) == 10_000           # exact partition
    sizes = np.array([len(p) for p in parts])
    assert sizes.max() - sizes.min() <= 2              # balanced
    h = label_histograms(labels, parts, 10)
    # 1 shard/client ⇒ (almost) single-class clients ⇒ HD ≈ 0.909
    hd = float(average_hd(h))
    assert 0.85 < hd < 0.95
    # calibration picks more shards for milder targets
    s_mild = calibrate_shards(labels, 100, 0.6, 10, seed=0)
    assert s_mild > 1


def test_histograms_normalized():
    rng = np.random.default_rng(2)
    labels = rng.integers(0, 7, 900)
    parts = dirichlet_partition(labels, 9, 0.2, seed=2)
    h = label_histograms(labels, parts, 7)
    np.testing.assert_allclose(h.sum(1), 1.0, atol=1e-9)


@pytest.mark.parametrize("n,k,s,classes", [(10_000, 100, 1, range(10)),
                                           (9_973, 37, 3, range(10)),
                                           (5_000, 64, 8, (0, 2, 3, 7, 9))])
def test_shard_histograms_match_the_built_partition(n, k, s, classes):
    """The calibration's closed-form histograms are those of the
    partition ``shard_partition`` builds from the same seed, exactly —
    uneven shard sizes and classes absent from the data included — so
    it picks the shard count a search over built partitions picks."""
    from repro.data.partition import (_class_shuffled_order, _shard_histograms,
                                      calibrate_shards, shard_partition)

    labels = np.random.default_rng(n).choice(np.asarray(classes), n)
    rng = np.random.default_rng(5)
    _, bounds = _class_shuffled_order(labels, rng)
    got = _shard_histograms(labels, k, s, 10, bounds, rng)
    want = label_histograms(labels, shard_partition(labels, k, s, seed=5), 10)
    np.testing.assert_array_equal(got, want)

    def searched(target):
        hds = {c: float(average_hd(label_histograms(
            labels, shard_partition(labels, k, c, seed=5), 10)))
            for c in (1, 2, 3, 4, 6, 8)}
        return min(hds, key=lambda c: (abs(hds[c] - target), c))

    for target in (0.9, 0.6, 0.3):
        assert calibrate_shards(labels, k, target, 10, seed=5) == searched(target)


def test_pack_clients_matches_a_client_by_client_copy():
    """Packed on threads, every client's rows, padding and mask are the
    plain loop's, empty clients included."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((500, 3, 2)).astype(np.float32)
    y = rng.integers(0, 10, 500)
    parts = [rng.choice(500, size, replace=False) for size in (7, 0, 31, 1, 31)]
    xs, ys, mask = pack_clients(x, y, parts)
    assert xs.shape == (5, 31, 3, 2) and ys.shape == (5, 31)
    for i, ix in enumerate(parts):
        pad = np.concatenate([ix, np.full(31 - len(ix), ix[0] if len(ix) else 0)])
        want_x = x[pad] if len(ix) else np.zeros_like(xs[i])
        np.testing.assert_array_equal(xs[i], want_x)
        np.testing.assert_array_equal(ys[i], y[pad] if len(ix) else 0)
        np.testing.assert_array_equal(mask[i], np.arange(31) < len(ix))
