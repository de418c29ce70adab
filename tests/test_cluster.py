"""KMeans tests — convergence on separable blobs, sharded-fit equivalence on
the virtual mesh, balanced variant list-size uniformity."""

import numpy as np
import pytest

from raft_tpu.cluster import (
    KMeansParams,
    kmeans_fit,
    kmeans_fit_predict,
    kmeans_predict,
    kmeans_transform,
    kmeans_balanced_fit,
    kmeans_balanced_fit_predict,
    kmeans_plus_plus_init,
)
from raft_tpu.random import RngState, make_blobs
from raft_tpu.stats import adjusted_rand_index


def _blobs(rng, n=512, d=8, k=5, seed=7):
    x, y = make_blobs(RngState(seed), n, d, n_clusters=k, cluster_std=0.3)
    return np.asarray(x), np.asarray(y)


def test_kmeans_recovers_blobs(rng):
    x, y = _blobs(rng)
    p = KMeansParams(n_clusters=5, max_iter=50, seed=1)
    c, labels, inertia, n_iter = kmeans_fit_predict(x, p)
    assert c.shape == (5, 8)
    ari = float(adjusted_rand_index(np.asarray(labels), y))
    assert ari > 0.95, f"ARI {ari}"
    assert float(inertia) > 0


def test_kmeans_inertia_decreases(rng):
    x, _ = _blobs(rng, n=256, k=4)
    p1 = KMeansParams(n_clusters=4, max_iter=1, seed=0)
    p2 = KMeansParams(n_clusters=4, max_iter=30, seed=0)
    _, i1, _ = kmeans_fit(x, p1)
    _, i2, _ = kmeans_fit(x, p2)
    assert float(i2) <= float(i1) + 1e-3


def test_kmeans_predict_transform(rng):
    x, _ = _blobs(rng, n=128, k=3)
    c, _, _ = kmeans_fit(x, KMeansParams(n_clusters=3, max_iter=20))
    labels = np.asarray(kmeans_predict(x, c))
    t = np.asarray(kmeans_transform(x, c))
    assert t.shape == (128, 3)
    np.testing.assert_array_equal(labels, t.argmin(1))


def test_kmeans_plus_plus_spread(rng):
    x, _ = _blobs(rng, n=200, k=4, seed=9)
    import jax

    c = np.asarray(kmeans_plus_plus_init(jax.random.PRNGKey(0), x, 4))
    # seeding should pick 4 distinct, well-separated points
    from scipy.spatial.distance import pdist

    assert pdist(c).min() > 1.0


def test_kmeans_sharded_fit(rng, mesh8):
    x, y = _blobs(rng, n=512, k=4, seed=11)
    p = KMeansParams(n_clusters=4, max_iter=25, seed=2)
    c, inertia, _ = kmeans_fit(x, p, mesh=mesh8)
    labels = np.asarray(kmeans_predict(x, c))
    ari = float(adjusted_rand_index(labels, y))
    assert ari > 0.9, f"sharded ARI {ari}"


def test_kmeans_balanced_sizes(rng):
    x, _ = _blobs(rng, n=480, d=6, k=3, seed=5)
    p = KMeansParams(n_clusters=8, max_iter=30, balanced_penalty=2.0, seed=0)
    c, sizes, inertia = kmeans_balanced_fit(x, p)
    sizes = np.asarray(sizes)
    assert sizes.sum() == 480
    # balanced: no list more than 3x the target size
    assert sizes.max() <= 3 * 480 / 8, sizes


def test_kmeans_balanced_bf16_assign_tier(rng):
    """balanced_assign_precision="bf16" speeds the TRAINING gemm only:
    the returned partition stays valid and the quality (inertia, measured
    exactly in both cases) stays within a 5% tolerance of the
    exact-assignment fit — loose enough to hold on TPU, where DEFAULT
    precision really is bf16 and assignments can flip near ties."""
    x, _ = _blobs(rng, n=480, d=6, k=3, seed=5)
    exact = KMeansParams(n_clusters=8, max_iter=30, balanced_penalty=2.0,
                         seed=0)
    fast = KMeansParams(n_clusters=8, max_iter=30, balanced_penalty=2.0,
                        seed=0, balanced_assign_precision="bf16")
    _, sizes_e, inertia_e = kmeans_balanced_fit(x, exact)
    _, sizes_f, inertia_f = kmeans_balanced_fit(x, fast)
    assert np.asarray(sizes_f).sum() == 480
    assert float(inertia_f) <= float(inertia_e) * 1.05

    with pytest.raises(Exception, match="balanced_assign_precision"):
        kmeans_balanced_fit(x, KMeansParams(n_clusters=8,
                                            balanced_assign_precision="bf17"))
    # the plain fit rejects the balanced-only knob instead of ignoring it
    with pytest.raises(Exception, match="balanced_assign_precision"):
        kmeans_fit(x, KMeansParams(n_clusters=8,
                                   balanced_assign_precision="bf16"))


def test_kmeans_balanced_fit_predict(rng):
    x, y = _blobs(rng, n=300, d=5, k=5, seed=13)
    p = KMeansParams(n_clusters=5, max_iter=40, balanced_penalty=0.5, seed=4)
    c, labels, sizes, _ = kmeans_balanced_fit_predict(x, p)
    ari = float(adjusted_rand_index(np.asarray(labels), y))
    assert ari > 0.8, f"balanced ARI {ari}"


def test_kmeans_sample_weight():
    """Weighted fit (classic cluster::kmeans sample_weights parity):
    heavily-weighted points dominate their centroid."""
    import jax.numpy as jnp

    from raft_tpu.cluster import KMeansParams, kmeans_fit

    rng = np.random.default_rng(5)
    a = rng.normal(0.0, 0.05, (100, 2)).astype(np.float32)
    b = rng.normal(4.0, 0.05, (100, 2)).astype(np.float32)
    outlier = np.array([[100.0, 100.0]], np.float32)
    x = np.concatenate([a, b, outlier])
    w = np.ones(201, np.float32)
    w[-1] = 1e-6  # the outlier is almost weightless
    c, inertia, _ = kmeans_fit(x, KMeansParams(n_clusters=2, seed=3),
                               sample_weight=w)
    c = np.sort(np.asarray(c)[:, 0])
    # both centroids land on the real clusters, not the outlier
    assert abs(c[0] - 0.0) < 0.5 and abs(c[1] - 4.0) < 0.5, c
    # weighted inertia excludes (almost all of) the outlier's huge d2
    assert float(inertia) < 100.0


def test_kmeans_sample_weight_validation():
    from raft_tpu.cluster import KMeansParams, kmeans_fit
    from raft_tpu.core.errors import LogicError

    x = np.random.default_rng(0).random((50, 4)).astype(np.float32)
    with pytest.raises(LogicError):
        kmeans_fit(x, KMeansParams(n_clusters=4), sample_weight=np.ones(10))


def test_kmeans_uniform_small_weights_match_unweighted():
    """sample_weight=c (any constant) must reproduce the unweighted fit —
    the fractional-mass normalization regression test."""
    from raft_tpu.cluster import KMeansParams, kmeans_fit

    x = np.random.default_rng(7).normal(size=(300, 4)).astype(np.float32)
    p = KMeansParams(n_clusters=8, seed=1, init="random")
    c0, i0, _ = kmeans_fit(x, p)
    c1, i1, _ = kmeans_fit(x, p, sample_weight=np.full(300, 0.01, np.float32))
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c0), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(float(i1), 0.01 * float(i0), rtol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_within_group_rank_is_the_lexsort_rank(dtype):
    """Ranks within each group by ascending score, ties by position: the
    same as a stable two-key lexsort, with ties, +inf and empty groups."""
    import jax.numpy as jnp

    from raft_tpu.utils.segment import within_group_rank

    rs = np.random.default_rng(5)
    groups = rs.integers(0, 40, 3000).astype(np.int32)
    scores = rs.integers(0, 50, 3000).astype(dtype)
    if dtype == np.float32:
        scores[rs.random(3000) < 0.1] = np.inf
    perm = np.lexsort((scores, groups))
    starts = np.searchsorted(groups[perm], np.arange(41))
    want = np.empty(3000, np.int32)
    want[perm] = np.arange(3000) - starts[groups[perm]]
    got = within_group_rank(jnp.asarray(groups), jnp.asarray(scores), 41)
    np.testing.assert_array_equal(np.asarray(got), want)
