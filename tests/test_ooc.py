"""raft_tpu.neighbors.ooc — the out-of-core cooperative search tier.

The contract under test (ISSUE 14):

* **rerank-everything oracle** — with ``rerank_k = n`` every stored row
  crosses the host round-trip into the exact rerank, so results must be
  bit-identical (values AND ids) to ``brute_force.knn``: fetching rows
  from the mmap-backed shard store must reproduce the device slab
  rescore exactly.
* **rabitq parity** — same build params ⇒ the device half (centroids,
  codes, slabs) is bit-identical to ``ivf_rabitq.build_chunked`` and
  search results match bitwise at every ``(n_probes, rerank_k)``.
* **overlap transparency** — ``device_prefetch`` double-buffering is a
  wall-clock optimisation only: overlap on/off and any query chunking
  are bit-identical.
* **device-memory boundedness** — the search loop's only H2D path is
  ``_stage_to_device``; under ``jax.transfer_guard("disallow")`` the
  largest single staging put is bounded by the resolved query chunk,
  never the whole raw slab.
* **zero steady-state allocation** — all staging buffers come from the
  host pool at fixed shapes: no pool misses after the first chunk.

Bitwise comparisons use integer-valued f32 data (each arithmetic step
exact in f32) — the tie-free fixture pinning the acceptance criterion.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core.errors import RaftError
from raft_tpu.core.host_memory import default_host_pool
from raft_tpu.io.shards import ShardedVectorStore, ShardWriter, write_store
from raft_tpu.neighbors import brute_force, ivf_rabitq, ooc, serialize
from raft_tpu.neighbors.ooc import (OocIndex, OocIndexParams,
                                    OocSearchParams)

N, D, NQ, K = 3000, 64, 16, 10
PARAMS = OocIndexParams(n_lists=8, kmeans_n_iters=10, list_cap_ratio=3.0)
RQ_PARAMS = ivf_rabitq.IvfRabitqIndexParams(n_lists=8, kmeans_n_iters=10,
                                            list_cap_ratio=3.0)


def _int_data(rng, rows, d=D):
    """Integer-valued f32: every arithmetic step lands on exact floats,
    enabling bitwise comparisons across accumulation orders — and
    making the brute-force oracle tie-free for this seed (distinct
    distances ⇒ a unique top-k ordering to pin bit-identity against)."""
    return rng.integers(0, 256, size=(rows, d)).astype(np.float32)


@pytest.fixture(scope="module")
def db():
    return _int_data(np.random.default_rng(7), N)


@pytest.fixture(scope="module")
def queries():
    return jnp.asarray(_int_data(np.random.default_rng(8), NQ))


@pytest.fixture(scope="module")
def index(db, tmp_path_factory):
    path = tmp_path_factory.mktemp("ooc") / "store"
    return ooc.build(db, PARAMS, store_path=str(path))


# ---------------------------------------------------------------------------
# the sharded host store


def test_store_roundtrip_and_gather(tmp_path, rng):
    x = rng.standard_normal((10_000, 24)).astype(np.float32)
    store = write_store(str(tmp_path / "s"), x, rows_per_shard=3000,
                        chunk_rows=1111)
    assert (store.rows, store.dim, store.n_shards) == (10_000, 24, 4)
    assert store.dtype == np.float32 and len(store) == 10_000
    np.testing.assert_array_equal(store.read_rows(2500, 6500), x[2500:6500])
    ids = rng.integers(0, 10_000, size=777)
    np.testing.assert_array_equal(store.gather(ids), x[ids])
    # out-of-range ids clip (masked downstream by the search path)
    ids2 = np.array([-5, 0, 9999, 123456])
    np.testing.assert_array_equal(store.gather(ids2),
                                  x[np.clip(ids2, 0, 9999)])
    assert store.verify() == []


def test_store_partial_final_shard(tmp_path, rng):
    """A dataset that doesn't divide rows_per_shard ends in a short
    shard: the writer rewrites that shard's header in place at close."""
    x = rng.standard_normal((701, 8)).astype(np.float32)
    w = ShardWriter(str(tmp_path / "s"), 8, np.dtype(np.float32),
                    rows_per_shard=256)
    for lo in range(0, 701, 97):
        w.append(x[lo:lo + 97])
    store = w.close()
    assert store.rows == 701 and store.n_shards == 3
    np.testing.assert_array_equal(store.read_rows(0, 701), x)
    # each shard is a plain np.load-able .npy — the format is inspectable
    last = np.load(str(tmp_path / "s" / "shard-00002.npy"))
    np.testing.assert_array_equal(last, x[512:])
    assert store.verify() == []


def test_store_crc_detects_corruption(tmp_path, rng):
    x = rng.standard_normal((100, 8)).astype(np.float32)
    store = write_store(str(tmp_path / "s"), x, rows_per_shard=64)
    shard = tmp_path / "s" / "shard-00001.npy"
    raw = bytearray(shard.read_bytes())
    raw[-3] ^= 0xFF
    shard.write_bytes(bytes(raw))
    problems = ShardedVectorStore.open(str(tmp_path / "s")).verify()
    assert problems and any("shard-00001" in p for p in problems)


def test_store_gather_native_fallback_parity(tmp_path, rng):
    """The pure-NumPy mmap path and the native pread path return the
    same bytes (whichever is active, forcing the fallback must agree)."""
    from raft_tpu.io import native

    x = rng.standard_normal((5000, 16)).astype(np.float32)
    store = write_store(str(tmp_path / "s"), x, rows_per_shard=2048)
    # dense-ish windows trigger the pread branch when native is present
    ids = np.arange(100, 1600)
    got = store.gather(ids, fetch_batch=2000)
    try:
        native._reset_for_tests(None)        # pin the NumPy fallback
        fallback = store.gather(ids, fetch_batch=2000)
    finally:
        native._reset_for_tests()
    np.testing.assert_array_equal(got, x[ids])
    np.testing.assert_array_equal(fallback, x[ids])


# ---------------------------------------------------------------------------
# search correctness


def test_rerank_everything_bitwise_vs_brute(index, db, queries):
    """rerank_k = n: the estimator admits everything, so the host
    round-trip + exact rerank must reproduce brute force bit-for-bit
    (values AND ids) — the ISSUE 14 acceptance pin."""
    dv, di = ooc.search(index, queries, K, OocSearchParams(
        n_probes=PARAMS.n_lists, rerank_k=N))
    bv, bi = brute_force.knn(queries, jnp.asarray(db), K)
    np.testing.assert_array_equal(np.asarray(di), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(bv))


def test_device_half_matches_ivf_rabitq(index, db):
    """Same params ⇒ the resident device arrays are bit-identical to the
    all-on-device rabitq tier (shared training, rotation, encode)."""
    ridx = ivf_rabitq.build_chunked(db, RQ_PARAMS)
    for f in ("centroids", "rotation", "codes", "sabs", "res_norms",
              "code_cdots", "ids", "counts"):
        np.testing.assert_array_equal(np.asarray(getattr(index, f)),
                                      np.asarray(getattr(ridx, f)), err_msg=f)
    assert index.list_cap == ridx.list_cap


def test_search_parity_vs_ivf_rabitq(index, db, queries):
    """At practical (n_probes, rerank_k) the ooc tier returns exactly
    what rabitq returns: fetching survivors host-side instead of
    gathering the device slab must not change a single bit."""
    ridx = ivf_rabitq.build_chunked(db, RQ_PARAMS)
    for n_probes, rerank_k in [(2, 32), (4, 64), (8, 128)]:
        rv, ri = ivf_rabitq.search(ridx, queries, K,
                                   ivf_rabitq.IvfRabitqSearchParams(
                                       n_probes=n_probes, rerank_k=rerank_k))
        ov, oi = ooc.search(index, queries, K, OocSearchParams(
            n_probes=n_probes, rerank_k=rerank_k))
        np.testing.assert_array_equal(np.asarray(oi), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))


def test_overlap_and_chunking_bit_identity(index, queries):
    base = ooc.search(index, queries, K,
                      OocSearchParams(n_probes=4, rerank_k=64))
    for overlap in (True, False):
        for chunk in (5, 16, 1024):
            dv, di = ooc.search(index, queries, K, OocSearchParams(
                n_probes=4, rerank_k=64, overlap=overlap,
                query_chunk=chunk))
            np.testing.assert_array_equal(np.asarray(di),
                                          np.asarray(base[1]))
            np.testing.assert_array_equal(np.asarray(dv),
                                          np.asarray(base[0]))


def test_estimator_recall(index, db, queries):
    """Practical rerank_k: the 1-bit estimator must recover near the
    probe-coverage ceiling — same data, gates, and bound as the rabitq
    tier's worst-case (uniform) recall test, and recall must grow with
    the rerank gate."""
    _, bi = brute_force.knn(queries, jnp.asarray(db), K)
    gt = np.asarray(bi)

    def recall_at(rk):
        _, di = ooc.search(index, queries, K, OocSearchParams(
            n_probes=PARAMS.n_lists, rerank_k=rk))
        return np.mean([len(set(a) & set(b)) / K
                        for a, b in zip(np.asarray(di), gt)])

    lo, hi = recall_at(8 * K), recall_at(32 * K)
    assert hi >= 0.95, (lo, hi)
    assert hi >= lo


def test_filtered_search(index, db, queries):
    _, oi = ooc.search(index, queries, K,
                       OocSearchParams(n_probes=8, rerank_k=N))
    keep = np.ones(N, dtype=bool)
    keep[np.asarray(oi).reshape(-1)[:50]] = False
    kv, ki = ooc.search(index, queries, K,
                        OocSearchParams(n_probes=8, rerank_k=N), filter=keep)
    bv, bi = brute_force.knn(queries, jnp.asarray(db), K,
                             filter=jnp.asarray(keep))
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(bv))


def test_metric_and_dim_validation(index, queries):
    with pytest.raises(RaftError):
        ooc.search(index, jnp.zeros((2, D + 1), jnp.float32), K)
    with pytest.raises(RaftError):
        ooc.build(np.zeros((10, 4), np.float32),
                  OocIndexParams(n_lists=20), store_path="/tmp/unused")


# ---------------------------------------------------------------------------
# build engines


def test_build_perop_pipelined_parity(db, tmp_path):
    """The double-buffered streaming build and the blocking per-op
    reference produce bit-identical device state AND shard bytes."""
    a = ooc.build_chunked(db, PARAMS, store_path=str(tmp_path / "a"),
                          chunk_rows=512)
    b = ooc._build_chunked_perop(db, PARAMS, store_path=str(tmp_path / "b"),
                                 chunk_rows=512)
    for f in ("centroids", "rotation", "codes", "sabs", "res_norms",
              "code_cdots", "ids", "counts"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    np.testing.assert_array_equal(a.store.read_rows(0, N),
                                  b.store.read_rows(0, N))
    np.testing.assert_array_equal(a.store.read_rows(0, N), db)


def test_build_streams_store_in_chunks(db, tmp_path):
    """rows_per_shard below n forces multiple shards; the rows land in
    dataset order so stored ids are positional."""
    p = dataclasses.replace(PARAMS, rows_per_shard=1024)
    idx = ooc.build(db, p, store_path=str(tmp_path / "s"), chunk_rows=500)
    assert idx.store.n_shards == 3
    np.testing.assert_array_equal(idx.store.read_rows(0, N), db)


# ---------------------------------------------------------------------------
# resource contracts


def test_device_memory_boundedness(index, queries):
    """The search loop never device_puts more than one staged chunk:
    codes tier + bounded staging, no hidden full-slab transfer.  All H2D
    goes through _stage_to_device (explicit device_put), so the loop is
    clean under a disallow transfer guard and the accounting is total."""
    p = OocSearchParams(n_probes=4, rerank_k=64, query_chunk=4)
    ooc.search(index, queries, K, p)          # warm the executables
    ooc.reset_transfer_stats()
    with jax.transfer_guard("disallow"):
        ooc.search(index, queries, K, p)
    ts = ooc.transfer_stats()
    chunk_bytes = 4 * 64 * D * 4 + 4 * D * 4  # staged slab + staged queries
    assert 0 < ts["max_put_bytes"] <= chunk_bytes
    raw_slab_bytes = N * D * 4
    assert ts["put_bytes"] < raw_slab_bytes
    assert int(index.resident_bytes) < raw_slab_bytes
    assert int(index.host_bytes) == raw_slab_bytes


def test_pool_zero_misses_after_warmup(index, queries):
    """Fixed staging shapes ⇒ after the first search every buffer is a
    pool hit: the hot loop allocates nothing."""
    p = OocSearchParams(n_probes=4, rerank_k=64, query_chunk=4)
    ooc.search(index, queries, K, p)          # warm up pool shapes
    before = default_host_pool().stats()
    ooc.search(index, queries, K, p)
    after = default_host_pool().stats()
    assert after["misses"] == before["misses"]
    assert after["hits"] > before["hits"]


def test_fetch_counter_and_transfer_stats(index, queries):
    from raft_tpu.obs.metrics import registry

    c = registry().counter("raft_ooc_rerank_fetch_bytes_total",
                           "host rows fetched for exact rerank")

    def total():
        return sum(v for _, v in c.samples())

    before = total()
    ooc.reset_transfer_stats()
    ooc.search(index, queries, K, OocSearchParams(n_probes=4, rerank_k=64))
    assert total() - before == NQ * 64 * D * 4
    assert ooc.transfer_stats()["fetch_bytes"] == NQ * 64 * D * 4


# ---------------------------------------------------------------------------
# serve integration


def test_family_and_searcher_dispatch(index, queries):
    from raft_tpu.serve import searchers

    assert searchers.family_of(index) == "ooc"
    assert searchers.index_dim(index) == D
    assert searchers.index_size(index) == N
    assert searchers.query_dtype_of(index) == jnp.float32
    p = OocSearchParams(n_probes=4, rerank_k=64)
    ov, oi = ooc.search(index, queries, K, p)
    fn, ops = searchers.make_searcher(index, K, p)
    sv, si = fn(queries, *ops)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(ov))


def test_searcher_aot_compiles(index, queries):
    """The serve contract: queries are the only shape-varying input and
    the host gather rides inside via pure_callback, so the searcher
    lowers and compiles ahead of time."""
    p = OocSearchParams(n_probes=4, rerank_k=64)
    fn, ops = ooc.searcher(index, K, p)
    compiled = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((NQ, D), jnp.float32), *ops).compile()
    cv, ci = compiled(queries, *ops)
    ov, oi = ooc.search(index, queries, K, p)
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(ov))


def test_health_reports_memory_split(index):
    from raft_tpu.neighbors import health

    stats = health.index_health(index)
    assert stats["family"] == "ooc"
    assert stats["rows"] == N
    assert stats["resident_bytes"] == float(index.resident_bytes)
    assert stats["host_bytes"] == float(N * D * 4)
    assert stats["rerank_fetch_bytes"] >= 0.0
    assert stats["residual_energy_mean"] > 0.0


def test_quality_oracle_reads_store(index, db):
    from raft_tpu.obs import quality

    vecs, ids = quality.oracle_database(index)
    assert vecs.shape == (N, D) and ids.shape == (N,)
    np.testing.assert_array_equal(vecs[np.argsort(ids)], db)


# ---------------------------------------------------------------------------
# persistence (format v5: manifest directory + sharded store)


def test_serialize_v5_roundtrip(index, queries, tmp_path):
    path = str(tmp_path / "idx")
    serialize.save_index(path, index, manifest={"note": "t"})
    assert serialize.verify_index(path) == []
    assert serialize.index_manifest(path)["note"] == "t"
    p = OocSearchParams(n_probes=4, rerank_k=64)
    ov, oi = ooc.search(index, queries, K, p)
    idx2 = serialize.load_index(path, verify=True)
    assert isinstance(idx2, OocIndex)
    rv, ri = ooc.search(idx2, queries, K, p)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(ov))


def test_open_is_lazy_and_verify_catches_corruption(index, tmp_path):
    path = str(tmp_path / "idx")
    ooc.save(path, index)
    idx2 = ooc.open(path)
    assert int(idx2.size) == N
    # store shards are opened lazily: no mmap until a row is read
    assert all(m is None for m in idx2.store._maps)
    shard = next(p for p in (tmp_path / "idx" / "shards").iterdir()
                 if p.name.endswith(".npy"))
    raw = bytearray(shard.read_bytes())
    raw[-1] ^= 0xFF
    shard.write_bytes(bytes(raw))
    assert ooc.verify(path) != []
    assert serialize.verify_index(path) != []


def test_future_version_rejected(index, tmp_path):
    path = str(tmp_path / "idx")
    ooc.save(path, index)
    meta_path = os.path.join(path, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["format_version"] = 99
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError):
        ooc.open(path)
