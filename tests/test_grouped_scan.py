"""List-major ("grouped") IVF-Flat probe scan against the query-major scan.

The grouped scan sorts a batch's (query, list) pairs by list, cuts each
list's run into tiles of query slots, and scores a tile with one MXU
product against its list (``ops/pallas/grouped_scan.py``, interpret mode
here, with the product the chip runs).  Its candidate set is the query-major scan's; only the dot's
accumulation order differs.  So: ids equal wherever no two candidates
tie within a few ulps, and distances within a few f32 ulps of
‖q‖² + ‖y‖².  The plan's tile count never exceeds its static bound, and
the path counter counts one lowering per compiled program.
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


def test_scan_path_rule():
    """The rule at the SIFT-1M-class cell's index, 1024 lists of 1960 ×
    128 f32 rows, 32 probes, k = 10: the kernel takes the index, and a
    batch takes the grouped scan from 16 rows (nq·P ≥ L / 2)."""
    takes = lambda **kw: ivf_flat.grouped_takes(
        kw.get("cap", 1960), 128, kw.get("k", 10),
        kw.get("dtype", jnp.float32), kw.get("keep", 0))
    assert takes() and takes(keep=1)
    assert not takes(dtype=jnp.uint8)
    assert not takes(dtype=jnp.bfloat16)
    assert not takes(keep=2)
    assert not takes(k=256)
    assert not takes(cap=16384)
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
