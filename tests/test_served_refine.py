"""IVF-PQ served with exact re-ranking: ``refine.Refined`` through
``SearchServer`` / ``make_searcher``.

The view searches ``k·ratio`` PQ candidates and re-ranks them exactly in
the same program; served answers must equal ``ivf_pq.search`` followed
by ``refine.refine`` bit for bit, and their distances must be exact.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core.errors import RaftError
from raft_tpu.neighbors import ivf_pq, mutation, refine
from raft_tpu.obs.metrics import registry
from raft_tpu.serve import SearchServer, ServerConfig, make_searcher
from raft_tpu.serve.searchers import (family_of, index_dim, index_size,
                                      query_dtype_of)

K, RATIO = 10, 4
PARAMS = ivf_pq.IvfPqSearchParams(n_probes=8)
F32_EPS = float(np.finfo(np.float32).eps)


@pytest.fixture(scope="module")
def data():
    rs = np.random.default_rng(3)
    centers = rs.standard_normal((40, 32)).astype(np.float32) * 4
    x = (centers[rs.integers(0, 40, 4000)]
         + rs.standard_normal((4000, 32)).astype(np.float32))
    q = (centers[rs.integers(0, 40, 70)]
         + rs.standard_normal((70, 32)).astype(np.float32))
    return x, q


@pytest.fixture(scope="module")
def index(data):
    x, _ = data
    return ivf_pq.build(jnp.asarray(x), ivf_pq.IvfPqIndexParams(
        n_lists=16, pq_dim=8, seed=1))


def _direct(index, x, q, params=PARAMS, keep=None):
    _, cand = ivf_pq.search(index, q, K * RATIO, params, filter=keep)
    return refine.refine(jnp.asarray(x), q, cand, K)


def _served(view, q, params=PARAMS):
    srv = SearchServer(view, k=K, params=params,
                       config=ServerConfig(ladder=(1, 8, 64)))
    srv.start()
    try:
        return srv.search(q)
    finally:
        srv.stop()


def test_served_view_is_bit_identical_to_search_then_refine(data, index):
    x, q = data
    view = refine.Refined(index, jnp.asarray(x), RATIO)
    for rows in (1, 5, 64):
        dv, di = _served(view, q[:rows])
        rd, ri = _direct(index, x, q[:rows])
        np.testing.assert_array_equal(di, np.asarray(ri))
        np.testing.assert_array_equal(dv, np.asarray(rd))


def test_served_distances_exact_and_recall_at_floor(data, index):
    x, q = data
    dv, di = _served(refine.Refined(index, jnp.asarray(x), RATIO), q)
    x64, q64 = x.astype(np.float64), q.astype(np.float64)
    exact = ((q64[:, None, :] - x64[di]) ** 2).sum(-1)
    scale = F32_EPS * ((q64 ** 2).sum(1)[:, None] + (x64[di] ** 2).sum(-1))
    assert np.max(np.abs(dv - exact) / scale) <= 16
    d_all = ((q64[:, None, :] - x64[None]) ** 2).sum(-1)
    truth = np.argsort(d_all, axis=1)[:, :K]

    def recall(ids):
        return np.mean([len(set(a) & set(b)) / K for a, b in zip(ids, truth)])

    # the float64 re-rank of the same PQ candidates: what an exact re-rank
    # can reach; this tiny index (8 of 16 lists, 40 candidates) reaches 0.897
    _, cand = ivf_pq.search(index, q, K * RATIO, PARAMS)
    cand = np.asarray(cand)
    d_cand = np.take_along_axis(d_all, cand, axis=1)
    best = np.take_along_axis(cand, np.argsort(d_cand, axis=1)[:, :K], 1)
    assert recall(di) == pytest.approx(recall(best))
    assert recall(di) >= 0.85


def test_tombstoned_index_inside_the_view_keeps_its_filter(data, index):
    x, q = data
    dead = np.arange(0, 4000, 3, dtype=np.int32)
    t = mutation.delete(index, dead)
    dv, di = _served(refine.Refined(t, jnp.asarray(x), RATIO), q[:20])
    assert not np.isin(di, dead).any()
    rd, ri = _direct(index, x, q[:20], keep=t.keep)
    np.testing.assert_array_equal(di, np.asarray(ri))
    np.testing.assert_array_equal(dv, np.asarray(rd))


def test_effort_scale_scales_only_the_probes(data, index):
    x, q = data
    view = refine.Refined(index, jnp.asarray(x), RATIO)
    fn, ops = make_searcher(view, K, PARAMS, effort_scale=0.5)
    dv, di = fn(jnp.asarray(q[:8]), *ops)
    rd, ri = _direct(index, x, q[:8],
                     dataclasses.replace(PARAMS, n_probes=4))
    np.testing.assert_array_equal(np.asarray(di), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(rd))


def test_view_reports_its_family_and_shape(data, index):
    x, _ = data
    view = refine.Refined(index, jnp.asarray(x), RATIO)
    assert family_of(view) == "ivf_pq"
    assert index_dim(view) == 32 and index_size(view) == 4000
    assert query_dtype_of(view) == jnp.float32
    with pytest.raises(RaftError):
        refine.Refined(index, jnp.asarray(x), 0)
    with pytest.raises(RaftError):
        make_searcher(mutation.delete(view, [1]), K, PARAMS)


def test_fleet_refuses_the_view(data, index, mesh8):
    from raft_tpu.serve.fleet import make_fleet_searcher

    x, _ = data
    with pytest.raises(RaftError, match="Refined"):
        make_fleet_searcher(refine.Refined(index, jnp.asarray(x), RATIO),
                            K, PARAMS, mesh=mesh8)


def _samples(name):
    family = registry().get(name)
    return {} if family is None else {
        tuple(sorted(labels.items())): v for labels, v in family.samples()}


def test_search_counter_and_build_gauges_are_recorded(data, index):
    x, q = data
    key = (("refine", "1"), ("tier", "recon"))
    before = _samples("raft_ivf_pq_search_total").get(key, 0)
    _served(refine.Refined(index, jnp.asarray(x), RATIO), q[:3])
    after = _samples("raft_ivf_pq_search_total").get(key, 0)
    assert after - before == 3            # one program per ladder bucket
    ivf_pq.build(jnp.asarray(x[:2000]), ivf_pq.IvfPqIndexParams(
        n_lists=8, pq_dim=8))
    stages = {dict(k)["stage"]: v
              for k, v in _samples("raft_index_build_seconds").items()
              if dict(k)["family"] == "ivf_pq"}
    assert {"train", "assign", "encode", "pack", "decode"} <= set(stages)
    assert all(v >= 0 for v in stages.values())
    ivf_pq.build_chunked(x[:2000], ivf_pq.IvfPqIndexParams(
        n_lists=8, pq_dim=8), chunk_rows=512)
    stages = {dict(k)["stage"]
              for k in _samples("raft_index_build_seconds")}
    assert "stream" in stages
