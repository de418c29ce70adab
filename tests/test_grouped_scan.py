"""List-major ("grouped") IVF probe scan against the query-major scan:
IVF-Flat's f32 slab, and the IVF-PQ recon tier's bf16 slab.

The grouped scan sorts a batch's (query, list) pairs by list, cuts each
list's run into tiles of query slots, and scores a tile with one MXU
product against its list (``ops/pallas/grouped_scan.py``, interpret mode
here, with the product the chip runs).  Its candidate set is the query-major scan's; only the dot's
accumulation order differs.  So: ids equal wherever no two candidates
tie within a few ulps, and distances within a few f32 ulps of
‖q‖² + ‖y‖².  The plan's tile count never exceeds its static bound, and
the path counters count one lowering per compiled program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from raft_tpu.neighbors import ivf_flat
from raft_tpu.obs.metrics import MetricRegistry, set_registry
from raft_tpu.ops import blocked_scan as bs
from raft_tpu.random.datagen import make_blobs

K = 10
N_LISTS = 37
N_PROBES = 11          # a multiple of no probe block the scan would pick
METRICS = ("sqeuclidean", "euclidean", "inner_product")
ULPS = 8


@pytest.fixture(scope="module")
def data():
    x, _ = make_blobs(jax.random.PRNGKey(5), n_samples=3000, n_features=32,
                      n_clusters=30, cluster_std=1.5)
    x = np.asarray(x)
    rng = np.random.default_rng(1)
    q = x[rng.choice(len(x), 48, replace=False)] + rng.normal(
        0, 0.3, (48, 32)).astype(np.float32)
    return x, q.astype(np.float32)


@pytest.fixture(scope="module")
def indexes(data):
    x, _ = data
    return {m: ivf_flat.build(x, ivf_flat.IvfFlatIndexParams(
        n_lists=N_LISTS, metric=m, seed=3)) for m in METRICS}


def _search(index, q, kernel, filt=None, n_probes=N_PROBES, probe_block=0):
    p = ivf_flat.IvfFlatSearchParams(n_probes=n_probes, scan_kernel=kernel,
                                     probe_block=probe_block)
    d, i = ivf_flat.search(index, q, K, p, filter=filt)
    return np.asarray(d), np.asarray(i)


def _assert_agree(x, q, ref, got, metric):
    """Distances within ULPS f32 ulps of ‖q‖²+‖y‖² at every rank; ids
    equal at every rank whose distance is apart from its neighbours'."""
    (rd, ri), (gd, gi) = ref, got
    assert rd.shape == gd.shape and ri.shape == gi.shape
    live = ri >= 0
    np.testing.assert_array_equal(live, gi >= 0)
    yn = np.where(live, (x[np.maximum(ri, 0)].astype(np.float64) ** 2
                         ).sum(-1), 0.0)
    scale = (q.astype(np.float64) ** 2).sum(-1)[:, None] + yn
    if metric == "euclidean":  # compare squared distances
        rd, gd = rd.astype(np.float64) ** 2, gd.astype(np.float64) ** 2
    tol = ULPS * np.spacing(scale.astype(np.float32)).astype(np.float64)
    gap = np.abs(np.where(live, gd, 0.0) - np.where(live, rd, 0.0))
    assert np.all(gap <= tol), float(np.max(gap / tol))
    # ids at a rank whose distance ties no neighbouring rank within tol
    pad = np.full((rd.shape[0], 1), np.inf)
    r = np.where(live, rd, np.inf)
    left = np.abs(r - np.concatenate([pad, r[:, :-1]], 1))
    right = np.abs(r - np.concatenate([r[:, 1:], pad], 1))
    apart = live & (left > 2 * tol) & (right > 2 * tol)
    assert apart.mean() > 0.8
    np.testing.assert_array_equal(np.where(apart, gi, 0),
                                  np.where(apart, ri, 0))


@pytest.mark.parametrize("metric", METRICS)
def test_grouped_matches_query_major(data, indexes, metric):
    x, q = data
    index = indexes[metric]
    ref = _search(index, q, "xla")
    _assert_agree(x, q, ref, _search(index, q, "grouped"), metric)


@pytest.mark.parametrize("probe_block", [1, 4, N_PROBES])
def test_grouped_ignores_probe_block(data, indexes, probe_block):
    """The grouped path takes no probe blocks: every block size gives the
    same answer, bit for bit, and it matches each query-major block."""
    x, q = data
    index = indexes["sqeuclidean"]
    got = _search(index, q, "grouped", probe_block=probe_block)
    base = _search(index, q, "grouped", probe_block=2)
    np.testing.assert_array_equal(got[0], base[0])
    np.testing.assert_array_equal(got[1], base[1])
    _assert_agree(x, q, _search(index, q, "xla", probe_block=probe_block),
                  got, "sqeuclidean")


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
def test_grouped_keep_filter(data, indexes, metric):
    x, q = data
    index = indexes[metric]
    keep = np.ones(len(x), bool)
    keep[::3] = False
    ref = _search(index, q, "xla", filt=keep)
    got = _search(index, q, "grouped", filt=keep)
    assert not np.isin(got[1][got[1] >= 0], np.flatnonzero(~keep)).any()
    _assert_agree(x, q, ref, got, metric)


def test_grouped_short_and_empty_lists(data, indexes):
    """Lists cut short by their counts, and lists emptied outright: the
    rows past a list's count never surface on either path."""
    x, q = data
    index = indexes["sqeuclidean"]
    counts = np.asarray(index.counts).copy()
    counts[:6] = 0                       # six empty lists
    counts[6:20] = counts[6:20] // 3     # fourteen lists cut short
    cut = dataclasses.replace(index, counts=jnp.asarray(counts))
    ref = _search(cut, q, "xla")
    got = _search(cut, q, "grouped")
    ids = np.asarray(index.ids)
    dead = np.concatenate([ids[j, counts[j]:] for j in range(N_LISTS)])
    assert not np.isin(got[1], dead[dead >= 0]).any()
    _assert_agree(x, q, ref, got, "sqeuclidean")


def test_grouped_worst_skew(data, indexes):
    """Every query probes the same P lists: each list's run is the whole
    batch, several tiles long, and the static tile bound still holds."""
    x, q = data
    index = indexes["sqeuclidean"]
    qs = np.repeat(q[:1], 48, axis=0) + np.linspace(
        0, 1e-2, 48, dtype=np.float32)[:, None]
    ref = _search(index, qs, "xla")
    got = _search(index, qs, "grouped")
    _assert_agree(x, qs, ref, got, "sqeuclidean")


def _check_plan(lists, n_lists, qt, pair_valid=None):
    tile_list, n_used, slot_query, pair_pos = map(
        np.asarray, bs.grouped_plan(jnp.asarray(lists), n_lists, qt,
                                    None if pair_valid is None
                                    else jnp.asarray(pair_valid)))
    nq, p = lists.shape
    n_tiles = bs.grouped_tile_bound(nq * p, n_lists, qt)
    assert tile_list.shape == (n_tiles,) and slot_query.shape == (n_tiles * qt,)
    valid = np.ones_like(lists, bool) if pair_valid is None else pair_valid
    per = np.bincount(lists[valid], minlength=n_lists)
    assert int(n_used[0]) == int(np.sum(-(-per // qt))) <= n_tiles
    pos = pair_pos[valid]
    assert np.all(pair_pos[~valid] == n_tiles * qt)
    assert len(np.unique(pos)) == len(pos) and np.all(pos < n_used[0] * qt)
    # every slot holds its pair's query, every tile one list
    np.testing.assert_array_equal(slot_query[pos], np.nonzero(valid)[0])
    np.testing.assert_array_equal(tile_list[pos // qt], lists[valid])
    assert np.all(np.diff(tile_list) >= 0)  # list-major, idle tiles last


@pytest.mark.parametrize("qt", [1, 8, 16])
def test_grouped_plan_stays_within_bound(qt):
    rng = np.random.default_rng(qt)
    n_lists = 40
    uniform = np.stack([rng.choice(n_lists, 7, replace=False)
                        for _ in range(33)]).astype(np.int32)
    same = np.tile(np.arange(7, dtype=np.int32), (33, 1))
    for lists in (uniform, same):
        _check_plan(lists, n_lists, qt)
        _check_plan(lists, n_lists, qt, rng.random(lists.shape) < 0.5)
    # every pair on its own list: the min(L, nq·P) term of the bound
    _check_plan(np.arange(12, dtype=np.int32).reshape(4, 3), 12, qt)
    _check_plan(np.zeros((4, 3), np.int32), 12, qt,
                np.zeros((4, 3), bool))


def test_scan_path_rule(monkeypatch):
    """The rule at the SIFT-1M-class cell's index, 1024 lists of 1960 ×
    128 f32 rows, 32 probes, k = 10: the kernel takes the index, and a
    batch takes the grouped scan from 16 rows (nq·P ≥ L / 2).  The kernel
    takes bf16 lists too (the IVF-PQ recon slab), at their own itemsize,
    but IVF-Flat, which scores f32 queries at HIGHEST, keeps a bf16 slab
    query-major."""
    from raft_tpu.ops.pallas import gate

    takes = lambda **kw: ivf_flat.grouped_takes(
        kw.get("cap", 1960), 128, kw.get("k", 10),
        kw.get("dtype", jnp.float32), kw.get("keep", 0))
    assert takes() and takes(keep=1)
    assert not takes(dtype=jnp.uint8)
    assert takes(dtype=jnp.bfloat16)
    assert not takes(keep=2)
    assert not takes(k=256)
    assert not takes(cap=16384)
    assert takes(cap=16384, dtype=jnp.bfloat16)      # 4 MiB at 2 bytes
    assert not takes(cap=16400, dtype=jnp.bfloat16)
    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    lists, d = 8, 128
    bf16 = ivf_flat.IvfFlatIndex(
        jnp.zeros((lists, d)), jnp.zeros((lists, 16, d), jnp.bfloat16),
        jnp.zeros((lists, 16), jnp.int32), jnp.zeros((lists,), jnp.int32),
        jnp.zeros((lists, 16)), "sqeuclidean")
    assert ivf_flat.resolve_scan("auto", bf16, 10, 1) != "grouped"
    with pytest.raises(Exception, match="grouped scan takes"):
        ivf_flat.resolve_scan("grouped", bf16, 10, 1)
    assert [ivf_flat.grouped_batch(b, 32, 1024)
            for b in (1, 8, 15, 16, 32, 64, 512)] == [
        False, False, False, True, True, True, True]


@pytest.mark.parametrize("kernel", ["auto", "grouped", "xla", "fused"])
def test_resolve_scan(indexes, monkeypatch, kernel):
    """``"auto"`` takes the grouped scan on a TPU only; the other values
    are kept as given; a per-query bitmap keeps the query-major scan."""
    from raft_tpu.ops.pallas import gate

    index = indexes["sqeuclidean"]
    keep = np.ones((3, int(index.ids.max()) + 1), bool)   # per-query bitmap
    want = "xla" if kernel == "auto" else kernel
    assert ivf_flat.resolve_scan(kernel, index, K, 4) == want
    monkeypatch.setattr(gate, "on_tpu", lambda: True)
    want = "grouped" if kernel == "auto" else kernel
    assert ivf_flat.resolve_scan(kernel, index, K, 4) == want
    if kernel == "grouped":
        with pytest.raises(Exception, match="grouped scan takes"):
            ivf_flat.resolve_scan(kernel, index, K, 4, keep)
    else:
        assert ivf_flat.resolve_scan(kernel, index, K, 4, keep) != "grouped"


def test_path_counter_counts_lowerings(data, indexes):
    _, q = data
    index = indexes["sqeuclidean"]
    fn, ops = ivf_flat.searcher(index, 9, ivf_flat.IvfFlatSearchParams(
        n_probes=N_PROBES, scan_kernel="grouped"))
    was = set_registry(MetricRegistry())
    try:
        from raft_tpu.obs.metrics import registry

        def paths():
            return {lb["path"]: v for lb, v in registry().counter(
                "raft_ivf_scan_path_total").samples()}

        prog = jax.jit(fn)
        for _ in range(2):
            jax.block_until_ready(prog(q[:23], *ops))
        assert paths() == {"grouped": 1.0}
        jax.block_until_ready(prog(q[:1], *ops))   # 11 pairs < 37 / 2
        assert paths() == {"grouped": 1.0, "query_major": 1.0}
        xfn, xops = ivf_flat.searcher(index, 9, ivf_flat.IvfFlatSearchParams(
            n_probes=N_PROBES, scan_kernel="xla"))
        jax.block_until_ready(jax.jit(xfn)(q[:23], *xops))
        assert paths() == {"grouped": 1.0, "query_major": 2.0}
        dispatch = {(lb["kernel"], lb["mode"]): v for lb, v in registry(
        ).counter("raft_pallas_dispatch_total").samples()}
        assert dispatch == {("ivf_grouped_scan", "interpret"): 1.0}
    finally:
        set_registry(was)


def test_fleet_grouped_bit_identical_under_skew(data, indexes, devices):
    """A batch whose lists each span several tiles, through the fleet's
    four shards and through one device: both take the grouped path and
    agree bit for bit."""
    from raft_tpu.serve import make_fleet_searcher, make_searcher

    _, q = data
    qs = np.concatenate([np.repeat(q[:1], 24, axis=0) + np.linspace(
        0, 1e-2, 24, dtype=np.float32)[:, None], q[:24]])
    index = indexes["sqeuclidean"]
    p = ivf_flat.IvfFlatSearchParams(n_probes=N_PROBES, scan_kernel="grouped")
    fn, ops = make_fleet_searcher(index, K, p, mesh=Mesh(
        np.asarray(devices[:4]), ("shard",)))
    rfn, rops = make_searcher(index, K, p)
    got, want = fn(qs, *ops), rfn(qs, *rops)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_slab_capacity_pads_storage_only(data):
    """The slab's lists are padded to the sublane tile; how many rows a
    list may hold, and so which rows each list holds, is unchanged."""
    x, _ = data
    assert ivf_flat.slab_capacity(1954, jnp.float32) == 1960
    assert ivf_flat.slab_capacity(4, jnp.bfloat16) == 16
    assert ivf_flat.slab_capacity(4, jnp.uint8) == 32
    p = ivf_flat.IvfFlatIndexParams(n_lists=7, list_cap_ratio=1.05)
    index = ivf_flat.build(x[:1000], p)
    cap = int(np.ceil(1.05 * 1000 / 7))
    assert index.list_cap == ivf_flat.slab_capacity(cap, jnp.float32) > cap
    counts = np.asarray(index.counts)
    assert counts.max() == cap and counts.sum() == 1000   # lists at cap
    ids = np.asarray(index.ids)
    assert np.all(ids[:, cap:] == -1)
    chunked = ivf_flat.build_chunked(x[:1000], p, chunk_rows=256)
    assert chunked.list_cap == index.list_cap
    assert np.asarray(chunked.counts).max() <= cap


# ---------------------------------------------------------------------------
# IVF-PQ recon tier: the grouped kernel over the bf16 reconstruction slab
# ---------------------------------------------------------------------------

PQ_METRICS = ("sqeuclidean", "inner_product")


@pytest.fixture(scope="module")
def pq_indexes(data):
    from raft_tpu.neighbors import ivf_pq

    x, _ = data
    return {m: ivf_pq.build(x, ivf_pq.IvfPqIndexParams(
        n_lists=N_LISTS, pq_dim=8, metric=m, seed=3)) for m in PQ_METRICS}


def _pq_search(index, q, kernel, filt=None, k=K):
    from raft_tpu.neighbors import ivf_pq

    p = ivf_pq.IvfPqSearchParams(n_probes=N_PROBES, scan_kernel=kernel)
    d, i = ivf_pq.search(index, q, k, p, filter=filt)
    return np.asarray(d), np.asarray(i)


def _recon_rows(index):
    """Each source id's bf16 reconstruction, as f32 (the scale of the
    recon tier's distances)."""
    ids = np.asarray(index.ids).reshape(-1)
    rec = np.asarray(index.recon.astype(jnp.float32)).reshape(ids.size, -1)
    out = np.zeros((ids.max() + 1, rec.shape[1]), np.float32)
    out[ids[ids >= 0]] = rec[ids >= 0]
    return out


@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("metric", PQ_METRICS)
def test_ivf_pq_grouped_matches_query_major(data, pq_indexes, metric,
                                            filtered):
    """The recon tier's grouped scan over the bf16 slab: the query-major
    scan's candidates, distances within a few f32 ulps, with and without
    a shared keep filter."""
    x, q = data
    index = pq_indexes[metric]
    assert index.recon.dtype == jnp.bfloat16
    keep = None
    if filtered:
        keep = np.ones(len(x), bool)
        keep[::3] = False
    ref = _pq_search(index, q, "xla", keep)
    got = _pq_search(index, q, "grouped", keep)
    if filtered:
        assert not np.isin(got[1][got[1] >= 0], np.flatnonzero(~keep)).any()
    _assert_agree(_recon_rows(index), q, ref, got, metric)


@pytest.mark.parametrize("case", ["short_and_empty", "worst_skew"])
def test_ivf_pq_grouped_short_empty_and_skewed_lists(data, pq_indexes, case):
    """Lists cut short and emptied (pad slots: id −1, ``+inf`` norm), and
    a batch whose every query probes the same lists."""
    x, q = data
    index = pq_indexes["sqeuclidean"]
    if case == "short_and_empty":
        ids = np.asarray(index.ids).copy()
        counts = np.asarray(index.counts).copy()
        counts[:6] = 0
        counts[6:20] //= 3
        dead = np.arange(index.list_cap)[None, :] >= counts[:, None]
        gone = ids[dead & (ids >= 0)]
        ids[dead] = -1
        norms = np.where(dead, np.inf, np.asarray(index.recon_norms))
        index = dataclasses.replace(
            index, ids=jnp.asarray(ids), counts=jnp.asarray(counts),
            recon_norms=jnp.asarray(norms, jnp.float32))
    else:
        q = np.repeat(q[:1], 48, axis=0) + np.linspace(
            0, 1e-2, 48, dtype=np.float32)[:, None]
    ref = _pq_search(index, q, "xla")
    got = _pq_search(index, q, "grouped")
    if case == "short_and_empty":
        assert not np.isin(got[1], gone).any()
    _assert_agree(_recon_rows(pq_indexes["sqeuclidean"]), q, ref, got,
                  "sqeuclidean")


def test_ivf_pq_grouped_refined_searcher(data, pq_indexes):
    """The served program of a ``refine.Refined`` view: the recon tier at
    k·ratio = 40 candidates, then the exact re-rank, grouped as
    query-major."""
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.refine import Refined
    from raft_tpu.serve import make_searcher

    x, q = data
    view = Refined(pq_indexes["sqeuclidean"], jnp.asarray(x), 4)
    out = {}
    for kernel in ("xla", "grouped"):
        fn, ops = make_searcher(view, K, ivf_pq.IvfPqSearchParams(
            n_probes=N_PROBES, scan_kernel=kernel))
        out[kernel] = [np.asarray(a) for a in jax.jit(fn)(q, *ops)]
    _assert_agree(x, q, out["xla"], out["grouped"], "sqeuclidean")


def test_ivf_pq_scan_path_rule_and_counter(data, pq_indexes, monkeypatch):
    """``"auto"`` resolves to the grouped scan on a TPU only; its program
    then scans a bucket grouped where nq·P ≥ L / 2 (2 rows and up here,
    11 probes over 37 lists) and query-major below, one count per
    compiled bucket in ``raft_ivf_pq_scan_path_total``.  Lists too large
    for the TPU to gather whole take the grouped scan at every size."""
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs.metrics import registry
    from raft_tpu.ops.pallas import gate

    _, q = data
    index = pq_indexes["sqeuclidean"]
    p = ivf_pq.IvfPqSearchParams(n_probes=N_PROBES)
    assert ivf_pq.recon_scan("auto", index, 40, 1) == "xla"
    assert ivf_pq.recon_scan("auto", index, 200, 1) == "xla"
    rule = ivf_pq.grouped_recon_batch
    assert [rule(b, 64, 4096, 2048, 128) for b in (1, 8, 16, 32, 512)] == [
        False, False, False, True, True]
    assert all(rule(b, 64, 4096, 3664, 128) for b in (1, 8, 16, 32, 512))
    assert rule(1, 64, 4096, 2048, 256) and not rule(1, 64, 4096, 4096, 64)
    keep = np.ones((3, len(data[0])), bool)            # per-query bitmap
    with monkeypatch.context() as m:
        m.setattr(gate, "on_tpu", lambda: True)
        assert ivf_pq.recon_scan("auto", index, 40, 1) == "grouped"
        assert ivf_pq.recon_scan("auto", index, 200, 1) == "xla"
        assert ivf_pq.recon_scan("auto", index, 40, 1, keep) == "xla"
        fn, ops = ivf_pq.searcher(index, 40, p)     # resolved on a "TPU"
    was = set_registry(MetricRegistry())
    try:
        def paths():
            return {lb["path"]: v for lb, v in registry().counter(
                "raft_ivf_pq_scan_path_total").samples()}

        prog = jax.jit(fn)
        for rows in (1, 2, 8, 8):
            jax.block_until_ready(prog(q[:rows], *ops))
        assert [ivf_flat.grouped_batch(r, N_PROBES, N_LISTS)
                for r in (1, 2)] == [False, True]
        assert paths() == {"query_major": 1.0, "grouped": 2.0}
        ref = _pq_search(index, q[:8], "xla", k=40)
        got = [np.asarray(a) for a in prog(q[:8], *ops)]
        _assert_agree(_recon_rows(index), q[:8], ref, got, "sqeuclidean")
    finally:
        set_registry(was)


@pytest.mark.parametrize("entry", ["build", "build_chunked"])
def test_ivf_pq_build_pads_storage_only(data, entry, tmp_path):
    """The IVF-PQ build stores ``slab_capacity(cap, bfloat16)`` slots per
    list: a list holds at most ``cap`` rows, and ``build`` puts in each
    list the rows the capped assignment gives it; pad slots are id −1
    with a ``+inf`` recon norm; ``save``/``load`` round-trips."""
    from raft_tpu.cluster.kmeans import capped_assign
    from raft_tpu.neighbors import ivf_pq, load_index, save_index

    x, q = data
    n = 1000
    p = ivf_pq.IvfPqIndexParams(n_lists=7, pq_dim=8, list_cap_ratio=1.05,
                                seed=3)
    cap = int(np.ceil(1.05 * n / 7))
    index = (ivf_pq.build(x[:n], p) if entry == "build"
             else ivf_pq.build_chunked(x[:n], p, chunk_rows=256))
    slots = ivf_flat.slab_capacity(cap, jnp.bfloat16)
    assert slots > cap and ivf_pq.slab_slots(cap) == slots
    for a in (index.codes, index.code_norms, index.ids, index.recon,
              index.recon_norms, index.adc_norms):
        assert a.shape[:2] == (7, slots)
    counts, ids = np.asarray(index.counts), np.asarray(index.ids)
    assert counts.max() <= cap and counts.sum() == n
    pad = np.arange(slots)[None, :] >= counts[:, None]
    assert np.all(ids[pad] == -1) and np.all(ids[~pad] >= 0)
    assert np.all(np.isinf(np.asarray(index.recon_norms)[pad]))
    if entry == "build":
        assert counts.max() == cap                   # lists at cap
        labels = np.asarray(capped_assign(jnp.asarray(x[:n]),
                                          index.centroids, cap)[0])
        for j in range(7):
            np.testing.assert_array_equal(
                np.sort(ids[j][~pad[j]]), np.flatnonzero(labels == j))
    save_index(tmp_path / "pq", index)
    back = load_index(tmp_path / "pq").with_recon()
    assert back.list_cap == slots
    for a, b in ((index.ids, back.ids), (index.codes, back.codes),
                 (index.recon_norms, back.recon_norms)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for kernel in ("xla", "grouped"):
        want = _pq_search(index, q, kernel)
        got = _pq_search(back, q, kernel)
        np.testing.assert_array_equal(want[1], got[1])
        np.testing.assert_array_equal(want[0], got[0])
