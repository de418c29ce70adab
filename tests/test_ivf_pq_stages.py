"""Each stage of an IVF-PQ build and search against the float64 stage
reference (``tests/ivf_pq_stage_reference.py``), at a small size: the
lists rows land in, their sub-codes, the decoded reconstruction slab and
its norms, and the ADC distances both search tiers compute.  Then the
row-tiled forms a full-size build takes (a 1M-row trainset over 4096
lists) against the whole-block forms they replace."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ivf_pq_stage_reference as ref
from raft_tpu.cluster import kmeans
from raft_tpu.neighbors import ivf_pq

F32_EPS = float(np.finfo(np.float32).eps)


@pytest.fixture(scope="module")
def built():
    rs = np.random.default_rng(11)
    centers = rs.standard_normal((30, 32)) * 3
    x = (centers[rs.integers(0, 30, 6000)]
         + rs.standard_normal((6000, 32))).astype(np.float32)
    q = (centers[rs.integers(0, 30, 24)]
         + rs.standard_normal((24, 32))).astype(np.float32)
    # room for every row in its nearest list: no spill
    index = ivf_pq.build(jnp.asarray(x), ivf_pq.IvfPqIndexParams(
        n_lists=16, pq_dim=16, pq_bits=8, list_cap_ratio=16.0, seed=2))
    ids = np.asarray(index.ids)
    lists, slots = np.nonzero(ids >= 0)
    rows = ids[lists, slots]
    return x, q, index, rows, lists, slots


def test_rows_land_in_their_nearest_list(built):
    x, _, index, rows, lists, _ = built
    near, _ = ref.nearest_lists(x[rows], np.asarray(index.centroids))
    assert len(rows) == len(x)
    assert np.mean(near == lists) == 1.0


def test_sub_codes_are_the_nearest_codewords(built):
    x, _, index, rows, lists, slots = built
    cent = np.asarray(index.centroids, np.float64)
    codes = np.asarray(index.codes)[lists, slots]
    exact, with_ties = ref.code_agreement(
        x[rows] - cent[lists], np.asarray(index.codebooks), codes)
    assert with_ties == 1.0 and exact >= 0.999


def test_recon_slab_is_the_decode_rounded_once_and_its_norms(built):
    _, _, index, rows, lists, slots = built
    codes = np.asarray(index.codes)[lists, slots]
    xhat, _ = ref.decode(codes, lists, np.asarray(index.centroids),
                         np.asarray(index.codebooks))
    slab = np.asarray(index.recon, np.float64)[lists, slots]
    d = xhat.shape[1]
    assert not slab[:, d:].any()          # zero columns up to whole lanes
    slab = slab[:, :d]
    np.testing.assert_array_equal(
        slab, xhat.astype(np.float32).astype(jnp.bfloat16).astype(
            np.float64))
    norms = np.asarray(index.recon_norms, np.float64)[lists, slots]
    np.testing.assert_allclose(norms, (slab * slab).sum(1), rtol=4e-7)
    pads = np.asarray(index.ids) < 0
    assert np.isinf(np.asarray(index.recon_norms)[pads]).all()


def _home(index):
    ids = np.asarray(index.ids)
    lists, slots = np.nonzero(ids >= 0)
    out = np.full((ids.max() + 1, 2), -1)
    out[ids[lists, slots]] = np.stack([lists, slots], 1)
    return out


def test_lut_tier_distances_are_the_adc_distances(built):
    _, q, index, *_ = built
    dv, di = ivf_pq.search(index, q, 20, ivf_pq.IvfPqSearchParams(
        n_probes=4, mode="lut"))
    dv, di = np.asarray(dv, np.float64), np.asarray(di)
    home = _home(index)
    codes = np.asarray(index.codes)
    cent, cb = np.asarray(index.centroids), np.asarray(index.codebooks)
    for r in range(len(q)):
        ls, ss = home[di[r]].T
        want = ref.adc(q[r:r + 1], codes[ls, ss], ls, cent, cb)[0]
        scale = (q[r].astype(np.float64) ** 2).sum() + ref.decode(
            codes[ls, ss], ls, cent, cb)[1]
        assert np.max(np.abs(dv[r] - want) / (F32_EPS * scale)) < 64


def test_recon_tier_distances_are_to_the_stored_slab(built):
    """The recon tier scores the bf16 slab against the bf16-rounded
    query: its distances are the float64 ones of those two, and no more
    than a bf16 query rounding from the float64 ADC distance."""
    _, q, index, *_ = built
    dv, di = ivf_pq.search(index, q, 20, ivf_pq.IvfPqSearchParams(
        n_probes=4, mode="recon"))
    dv, di = np.asarray(dv, np.float64), np.asarray(di)
    home = _home(index)
    slab = np.asarray(index.recon, np.float64)[..., :q.shape[1]]
    qb = np.asarray(jnp.asarray(q).astype(jnp.bfloat16), np.float64)
    q64 = q.astype(np.float64)
    for r in range(len(q)):
        y = slab[tuple(home[di[r]].T)]
        # ‖q‖² of the f32 query with ⟨q, x̂⟩ of its bf16 rounding
        mixed = (q64[r] ** 2).sum() - 2 * y @ qb[r] + (y * y).sum(1)
        scale = (q64[r] ** 2).sum() + (y * y).sum(1)
        assert np.max(np.abs(dv[r] - mixed) / (F32_EPS * scale)) < 64
        exact = ((y - q64[r]) ** 2).sum(1)
        assert np.max(np.abs(dv[r] - exact) / scale) < 2.0 ** -7


def _whole(fn, *args, **kw):
    jax.clear_caches()
    try:
        return fn(*args, **kw)
    finally:
        jax.clear_caches()


def test_capped_assignment_tiled_equals_whole(built):
    x, _, index, *_ = built
    c = index.centroids
    want = _whole(kmeans.capped_assign, jnp.asarray(x), c, 400)
    with pytest.MonkeyPatch.context() as mp:
        jax.clear_caches()
        mp.setattr(kmeans, "TILE_ELEMS", 2000)
        assert kmeans._tile_rows(len(x), c.shape[0]) == 120
        got = kmeans.capped_assign(jnp.asarray(x), c, 400)
    jax.clear_caches()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_balanced_fit_and_nearest_lists_tiled_equal_whole(built):
    x, _, index, *_ = built
    p = kmeans.KMeansParams(n_clusters=16, max_iter=5, seed=3)
    want = _whole(kmeans.kmeans_balanced_fit_predict, jnp.asarray(x), p)
    near = _whole(ivf_pq._nearest_lists, jnp.asarray(x), index.centroids)
    with pytest.MonkeyPatch.context() as mp:
        jax.clear_caches()
        mp.setattr(kmeans, "TILE_ELEMS", 2000)
        got = kmeans.kmeans_balanced_fit_predict(jnp.asarray(x), p)
        got_near = ivf_pq._nearest_lists(jnp.asarray(x), index.centroids)
    jax.clear_caches()
    for a, b in zip(got[:3], want[:3]):       # centroids, labels, sizes
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(float(got[3]), float(want[3]), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_near), np.asarray(near))
    assert np.mean(np.asarray(near) == ref.nearest_lists(
        x, np.asarray(index.centroids))[0]) == 1.0
