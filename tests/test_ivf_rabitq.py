"""raft_tpu.neighbors.ivf_rabitq — the 1-bit RaBitQ IVF tier.

The contract under test (ISSUE 13):

* **rerank-everything oracle** — with ``rerank_k = n`` every stored row
  reaches the exact rerank, so results must be bit-identical (values AND
  ids) to ``brute_force.knn``: the estimator may only *order* candidates,
  never change what an admitted candidate scores.
* **estimator quality** — at practical ``rerank_k`` the unbiased 1-bit
  estimate must recover near the probe-coverage recall ceiling.
* **lifecycle** — extend / delete / compact / serialize compose exactly
  as for the other IVF families (extend-from-empty ≡ build bit-identity
  with capacity headroom, compaction preserves search results, v4
  artifacts round-trip, steady-state extend is retrace/transfer-free).

Bitwise comparisons use integer-valued f32 data (each arithmetic step
exact in f32); gaussian data checks ids + allclose (einsum tilings of
different shapes may differ in the last ulp).
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import TraceGuard
from raft_tpu.core.errors import RaftError
from raft_tpu.neighbors import brute_force, ivf_rabitq, mutation, serialize
from raft_tpu.neighbors.ivf_rabitq import (IvfRabitqIndex,
                                           IvfRabitqIndexParams,
                                           IvfRabitqSearchParams)
from raft_tpu.ops import blocked_scan

N, D, NQ, K = 3000, 64, 16, 10
# capacity headroom: the extend-vs-rebuild oracle (like ivf_flat's) is
# only exact while no list saturates — capped assignment spills
# differently between the one-shot and chunked engines at the cap
PARAMS = IvfRabitqIndexParams(n_lists=8, kmeans_n_iters=10,
                              list_cap_ratio=3.0)


def _int_data(rng, rows, d=D):
    """Integer-valued f32: every arithmetic step lands on exact floats,
    enabling bitwise comparisons across accumulation orders."""
    return rng.integers(0, 256, size=(rows, d)).astype(np.float32)


@pytest.fixture(scope="module")
def db():
    return jnp.asarray(_int_data(np.random.default_rng(7), N))


@pytest.fixture(scope="module")
def queries():
    return jnp.asarray(_int_data(np.random.default_rng(8), NQ))


@pytest.fixture(scope="module")
def index(db):
    return ivf_rabitq.build(db, PARAMS)


# ---------------------------------------------------------------------------
# packed-sign primitives (the quantized-scan sub-API)


def test_sign_bits_roundtrip(rng):
    x = rng.standard_normal((5, 7, 33)).astype(np.float32)
    packed = blocked_scan.pack_sign_bits(jnp.asarray(x))
    assert packed.dtype == jnp.uint8 and packed.shape == (5, 7, 5)
    bits = blocked_scan.unpack_sign_bits(packed, 33)
    np.testing.assert_array_equal(np.asarray(bits), (x >= 0).astype(np.int8))


def test_packed_sign_dots_exact(rng):
    nq, b, c, d = 3, 4, 6, 48
    x = rng.standard_normal((nq, b, c, d)).astype(np.float32)
    q8 = rng.integers(-127, 128, size=(nq, d)).astype(np.int8)
    packed = blocked_scan.pack_sign_bits(jnp.asarray(x))
    got = blocked_scan.packed_sign_dots(packed, jnp.asarray(q8))
    signs = np.where(x >= 0, 1.0, -1.0)
    want = np.einsum("qbcd,qd->qbc", signs, q8.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_slab_dots_packed_sign_dispatch(rng):
    x = rng.standard_normal((2, 3, 5, 32)).astype(np.float32)
    q8 = rng.integers(-127, 128, size=(2, 32)).astype(np.int8)
    packed = blocked_scan.pack_sign_bits(jnp.asarray(x))
    via_slab = blocked_scan.slab_dots(packed, jnp.asarray(q8),
                                      packed_sign=True)
    direct = blocked_scan.packed_sign_dots(packed, jnp.asarray(q8))
    np.testing.assert_array_equal(np.asarray(via_slab), np.asarray(direct))


# ---------------------------------------------------------------------------
# the rerank-everything oracle


def test_rerank_all_bit_identical_to_brute_force(index, db, queries):
    """rerank_k = n: values AND ids bitwise equal to brute force — the
    estimator gates nothing, the exact rerank recomputes everything in
    brute-force accumulation order."""
    p = IvfRabitqSearchParams(n_probes=PARAMS.n_lists, rerank_k=N)
    dv, di = ivf_rabitq.search(index, queries, K, p)
    bv, bi = brute_force.knn(queries, db, K)
    np.testing.assert_array_equal(np.asarray(di), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(bv))


@pytest.mark.parametrize("metric", ["sqeuclidean", "euclidean",
                                    "inner_product"])
def test_rerank_all_matches_brute_all_metrics(metric):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1200, 48)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((8, 48)).astype(np.float32))
    idx = ivf_rabitq.build(x, dataclasses.replace(PARAMS, metric=metric))
    p = IvfRabitqSearchParams(n_probes=PARAMS.n_lists, rerank_k=1200)
    dv, di = ivf_rabitq.search(idx, q, K, p)
    bv, bi = brute_force.knn(q, x, K, metric=metric)
    np.testing.assert_array_equal(np.asarray(di), np.asarray(bi))
    np.testing.assert_allclose(np.asarray(dv), np.asarray(bv),
                               rtol=1e-5, atol=1e-4)


def test_estimator_recall_near_coverage_ceiling(index, db, queries):
    """At rerank_k ≪ n the 1-bit estimate must still surface most true
    neighbors the probes cover — uniform data is the estimator's worst
    case (1-bit relative error ~ 1/√d against near-equidistant rows),
    so the gate sits at ~10 % of n and recall must grow with rerank_k."""
    _, gt = brute_force.knn(queries, db, K)
    gt = np.asarray(gt)

    def recall_at(rk):
        p = IvfRabitqSearchParams(n_probes=PARAMS.n_lists, rerank_k=rk)
        _, ids = ivf_rabitq.search(index, queries, K, p)
        return np.mean([len(set(a) & set(b)) / K
                        for a, b in zip(np.asarray(ids), gt)])

    lo, hi = recall_at(8 * K), recall_at(32 * K)
    assert hi >= 0.95, (lo, hi)
    assert hi >= lo  # more exact-reranked candidates never hurts


# ---------------------------------------------------------------------------
# knob resolution + invariances


def test_resolve_rerank_k_contract():
    assert ivf_rabitq.resolve_rerank_k(100, 10, 8, 500) == 100
    assert ivf_rabitq.resolve_rerank_k(10 ** 9, 10, 8, 500) == 8 * 500
    auto = ivf_rabitq.resolve_rerank_k(0, 10, 8, 500)
    assert 10 <= auto <= 8 * 500
    with pytest.raises(RaftError):
        ivf_rabitq.resolve_rerank_k(5, 10, 8, 500)  # requested < k


def test_probe_block_invariance(index, queries):
    base = None
    for pb in (1, 2, 4):
        p = IvfRabitqSearchParams(n_probes=8, rerank_k=64, probe_block=pb)
        dv, di = ivf_rabitq.search(index, queries, K, p)
        if base is None:
            base = (np.asarray(dv), np.asarray(di))
        else:
            np.testing.assert_array_equal(base[0], np.asarray(dv))
            np.testing.assert_array_equal(base[1], np.asarray(di))


def test_scan_kernel_arms_agree(index, queries):
    """The estimator scan has one arm, so the params carry no
    ``scan_kernel``; ``rerank_k=0`` resolves to ``max(32, 8·k)`` and
    returns what that width asked for by name returns."""
    assert not hasattr(IvfRabitqSearchParams(), "scan_kernel")
    assert ivf_rabitq.resolve_rerank_k(0, K, 8, index.list_cap) == max(
        32, 8 * K)
    outs = []
    for rk in (0, max(32, 8 * K)):
        p = IvfRabitqSearchParams(n_probes=8, rerank_k=rk)
        dv, di = ivf_rabitq.search(index, queries, K, p)
        outs.append((np.asarray(dv), np.asarray(di)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_searcher_matches_search(index, queries):
    p = IvfRabitqSearchParams(n_probes=8, rerank_k=64)
    dv, di = ivf_rabitq.search(index, queries, K, p)
    fn, ops = ivf_rabitq.searcher(index, K, p)
    dv2, di2 = fn(queries, *ops)
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(dv2))
    np.testing.assert_array_equal(np.asarray(di), np.asarray(di2))


def test_filtered_search_excludes(index, queries):
    p = IvfRabitqSearchParams(n_probes=8, rerank_k=64)
    _, di = ivf_rabitq.search(index, queries, K, p)
    banned = sorted({int(i) for i in np.asarray(di)[:, 0]})[:4]
    keep = np.ones(N, bool)
    keep[banned] = False
    _, df = ivf_rabitq.search(index, queries, K, p, filter=keep)
    assert not np.isin(np.asarray(df), banned).any()


def test_build_validation(db):
    with pytest.raises(RaftError):
        ivf_rabitq.build(db, dataclasses.replace(PARAMS, metric="cosine"))


# ---------------------------------------------------------------------------
# chunked build + extend


def test_chunked_engines_bit_identical(db):
    a = ivf_rabitq.build_chunked(np.asarray(db), PARAMS, chunk_rows=700)
    b = ivf_rabitq._build_chunked_perop(np.asarray(db), PARAMS,
                                        chunk_rows=700)
    for f in ("centroids", "rotation", "codes", "sabs", "res_norms",
              "code_cdots", "data", "ids", "counts"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f)


def _empty_like(full: IvfRabitqIndex) -> IvfRabitqIndex:
    return IvfRabitqIndex(
        full.centroids, full.rotation,
        jnp.zeros_like(full.codes), jnp.zeros_like(full.sabs),
        jnp.zeros_like(full.res_norms), jnp.zeros_like(full.code_cdots),
        jnp.zeros_like(full.data), jnp.full_like(full.ids, -1),
        jnp.zeros_like(full.counts), full.metric)


def test_extend_bit_identical_to_build(index, db):
    """Extending an empty clone (same centroids/rotation) with the full
    dataset reproduces the built index bit-for-bit — the encode path is
    batch-size invariant.  Needs capacity headroom (see PARAMS note)."""
    grown = ivf_rabitq.extend(_empty_like(index), db, np.arange(N))
    for f in ("codes", "sabs", "res_norms", "code_cdots", "data", "ids",
              "counts"):
        np.testing.assert_array_equal(np.asarray(getattr(index, f)),
                                      np.asarray(getattr(grown, f)),
                                      err_msg=f)


def test_extend_grows_capacity(db):
    rng = np.random.default_rng(5)
    small = ivf_rabitq.build(db[:200], dataclasses.replace(PARAMS,
                                                           n_lists=4))
    extra = jnp.asarray(_int_data(rng, 800))
    grown = ivf_rabitq.extend(small, extra, np.arange(200, 1000))
    assert grown.size == 1000
    assert grown.list_cap > small.list_cap


def test_extend_steady_state_trace_guard(db):
    """After one warm insert, further same-sized inserts run with zero
    retraces, zero compiles, zero implicit transfers."""
    rng = np.random.default_rng(22)
    idx = ivf_rabitq.build(db, PARAMS)
    nxt = N
    idx = ivf_rabitq.extend(idx, _int_data(rng, 16), np.arange(nxt, nxt + 16))
    nxt += 16
    jax.block_until_ready(idx.counts)
    with TraceGuard() as tg:
        for _ in range(4):
            idx = ivf_rabitq.extend(idx, _int_data(rng, 16),
                                    np.arange(nxt, nxt + 16))
            nxt += 16
        jax.block_until_ready(idx.counts)
    tg.assert_steady_state()
    assert idx.size == N + 5 * 16


def test_search_steady_state_trace_guard(index, queries):
    p = IvfRabitqSearchParams(n_probes=8, rerank_k=64)
    fn, ops = ivf_rabitq.searcher(index, K, p)
    jax.block_until_ready(fn(queries, *ops))
    with TraceGuard() as tg:
        for _ in range(4):
            out = fn(queries, *ops)
        jax.block_until_ready(out)
    tg.assert_steady_state()


# ---------------------------------------------------------------------------
# delete / compact


def test_delete_and_compact_preserve_results(index, queries):
    p = IvfRabitqSearchParams(n_probes=8, rerank_k=64)
    _, di = ivf_rabitq.search(index, queries, K, p)
    dead = sorted({int(i) for i in np.asarray(di)[:, 0]})[:3]
    ts = mutation.delete(index, dead)
    dv_t, di_t = mutation.search(ts, queries, K, p)
    assert not np.isin(np.asarray(di_t), dead).any()
    comp = mutation.compact(ts)
    assert isinstance(comp, IvfRabitqIndex)
    assert comp.size == index.size - len(dead)
    dv_c, di_c = ivf_rabitq.search(comp, queries, K, p)
    np.testing.assert_array_equal(np.asarray(di_t), np.asarray(di_c))
    np.testing.assert_array_equal(np.asarray(dv_t), np.asarray(dv_c))


def test_compact_roundtrip_is_identity(index):
    """Compacting with no tombstones repacks every live row (cap may
    shrink) — same rows per list, correction scalars verbatim."""
    comp = mutation.compact(index, headroom=3.0)
    assert comp.size == index.size
    for lst in range(PARAMS.n_lists):
        c0 = int(np.asarray(index.counts)[lst])
        c1 = int(np.asarray(comp.counts)[lst])
        assert c0 == c1
        np.testing.assert_array_equal(
            np.asarray(index.ids)[lst, :c0], np.asarray(comp.ids)[lst, :c1])
        np.testing.assert_array_equal(
            np.asarray(index.sabs)[lst, :c0], np.asarray(comp.sabs)[lst, :c1])


# ---------------------------------------------------------------------------
# serialization (format v4 + compat)


def test_serialize_roundtrip_v4(index, queries, tmp_path):
    path = tmp_path / "rq"
    serialize.save_index(path, index, manifest={"lsn": 11})
    meta = json.loads((path / "meta.json").read_text())
    assert meta["metadata"]["format_version"] == 4
    assert serialize.verify_index(path) == []
    assert serialize.index_manifest(path) == {"lsn": 11}
    loaded = serialize.load_index(path, verify=True)
    for f in ("centroids", "rotation", "codes", "sabs", "res_norms",
              "code_cdots", "data", "ids", "counts"):
        np.testing.assert_array_equal(np.asarray(getattr(index, f)),
                                      np.asarray(getattr(loaded, f)),
                                      err_msg=f)
    assert loaded.metric == index.metric
    p = IvfRabitqSearchParams(n_probes=8, rerank_k=64)
    dv, di = ivf_rabitq.search(index, queries, K, p)
    dv2, di2 = ivf_rabitq.search(loaded, queries, K, p)
    np.testing.assert_array_equal(np.asarray(di), np.asarray(di2))
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(dv2))


def test_serialize_tombstoned_stamps_v4(index, tmp_path):
    ts = mutation.delete(index, [1, 2])
    path = tmp_path / "rq_ts"
    serialize.save_index(path, ts)
    meta = json.loads((path / "meta.json").read_text())
    assert meta["metadata"]["format_version"] == 4
    back = serialize.load_index(path)
    assert isinstance(back, mutation.Tombstoned)
    assert isinstance(back.index, IvfRabitqIndex)


def test_legacy_artifacts_still_write_old_versions(db, tmp_path):
    """The version bump must not inflate non-RaBitQ artifacts: a flat
    index still stamps v1 (readable by every deployed reader)."""
    from raft_tpu.neighbors import ivf_flat

    fidx = ivf_flat.build(db, ivf_flat.IvfFlatIndexParams(
        n_lists=8, kmeans_n_iters=4))
    path = tmp_path / "flat"
    serialize.save_index(path, fidx)
    meta = json.loads((path / "meta.json").read_text())
    assert meta["metadata"]["format_version"] == 1
    ts = mutation.delete(fidx, [1])
    path2 = tmp_path / "flat_ts"
    serialize.save_index(path2, ts)
    meta2 = json.loads((path2 / "meta.json").read_text())
    assert meta2["metadata"]["format_version"] == 3


def test_v4_rejected_by_v3_reader(index, tmp_path, monkeypatch):
    """A reader from before this format bump must refuse a v4 artifact
    loudly (not mis-parse it)."""
    path = tmp_path / "rq"
    serialize.save_index(path, index)
    monkeypatch.setattr(serialize, "_FORMAT_VERSION", 3)
    with pytest.raises(ValueError, match="newer than supported"):
        serialize.load_index(path)
    assert any("newer than supported" in p
               for p in serialize.verify_index(path))


def test_future_version_rejected(index, tmp_path):
    path = tmp_path / "rq"
    serialize.save_index(path, index)
    mpath = path / "meta.json"
    meta = json.loads(mpath.read_text())
    meta["metadata"]["format_version"] = serialize._FORMAT_VERSION + 1
    mpath.write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="newer than supported"):
        serialize.load_index(path)


# ---------------------------------------------------------------------------
# serve / observability coverage


def test_family_and_searcher_dispatch(index, queries):
    from raft_tpu.serve.searchers import family_of, make_searcher

    assert family_of(index) == "ivf_rabitq"
    assert family_of(mutation.delete(index, [0])) == "ivf_rabitq"
    p = IvfRabitqSearchParams(n_probes=8, rerank_k=64)
    fn, ops = make_searcher(index, K, p)
    dv, di = fn(queries, *ops)
    dv0, di0 = ivf_rabitq.search(index, queries, K, p)
    np.testing.assert_array_equal(np.asarray(di0), np.asarray(di))
    np.testing.assert_array_equal(np.asarray(dv0), np.asarray(dv))
    # effort scaling shrinks n_probes but still returns K valid results
    fn2, ops2 = make_searcher(index, K, p, effort_scale=0.25)
    _, di2 = fn2(queries, *ops2)
    assert (np.asarray(di2) >= 0).all()


def test_index_health(index):
    from raft_tpu.neighbors.health import index_health

    h = index_health(index)
    assert h["family"] == "ivf_rabitq"
    assert h["rows"] == N
    assert h["residual_energy_mean"] > 0
    assert h["residual_energy_p95"] >= h["residual_energy_mean"] * 0.1
    ts = mutation.delete(index, [0, 1])
    h2 = index_health(ts)
    assert h2["dead"] == 2.0


def test_oracle_database_covers_rabitq(index):
    from raft_tpu.obs.quality import oracle_database

    vecs, ids = oracle_database(index)
    assert vecs.shape == (N, D)
    assert sorted(ids.tolist()) == list(range(N))
    dead = [4, 9]
    vecs2, ids2 = oracle_database(mutation.delete(index, dead))
    assert ids2.shape[0] == N - len(dead)
    assert not np.isin(ids2, dead).any()
