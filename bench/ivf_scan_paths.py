"""IVF-Flat probe-scan paths on the chip: query-major against grouped.

Builds the SIFT-1M-class index of ``benchmark/configs/sift1m-ivf_flat.json``
(1M × 128 f32 Gaussian-mixture rows, 1024 lists, 32 probes).  Per batch
size (the serving buckets 1, 8, 64, 512 rows, and 16 and 32 about the
rule ``ivf_flat.grouped_batch``) it reports which scan the served program
lowered, and times one search with the query-major scan
(``scan_kernel="xla"``) and one with the list-major grouped scan whatever
the rule says, each the median of ``--reps`` calls that end in
``jax.block_until_ready``.  At 512 rows it also times the grouped scan at
several tile heights,
and checks the grouped answers against the query-major ones: ids equal
where no two candidates tie, distance gap in f32 ulps of ‖q‖²+‖y‖².
Last it compacts the index (a wider slab of the same lists) and checks
that the served grouped program answers bit for bit as before.

    python bench/ivf_scan_paths.py [--seed N] [--reps N] [--out FILE]

Needs a TPU.  One JSON line per measurement on stdout; with ``--out``,
all of them also as one JSON list in FILE.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from raft_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BUCKETS = (1, 8, 16, 32, 64, 512)
TILES = (8, 16, 32, 64)


def _median_seconds(fn, reps: int) -> float:
    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _paths(registry) -> dict:
    return {labels["path"]: n for labels, n in
            registry().counter("raft_ivf_scan_path_total").samples()}


def _grouped_at(qt: int, k: int, n_probes: int):
    """The grouped search program at tile height ``qt`` (L2)."""
    from raft_tpu.distance.pairwise import sq_l2
    from raft_tpu.neighbors.ivf_flat import slot_bias
    from raft_tpu.ops import blocked_scan as bs

    @jax.jit
    def run(q, centroids, data, ids, counts, norms):
        qn = bs.row_sq_norms(q)
        _, probes = jax.lax.top_k(-sq_l2(q, centroids), n_probes)
        bias = slot_bias(norms, ids, counts, "sqeuclidean")
        return bs.scan_topk_grouped(q, qn, data, bias, ids, probes, k,
                                    l2=True, qt=qt)
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2400000001)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 3

    from benchmark import mixture
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs.metrics import registry
    from raft_tpu.ops.blocked_scan import GROUPED_TILE

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sift1m-ivf_flat.json")) as f:
        cfg = json.load(f)
    k = int(cfg["data"]["k"])
    n_probes = int(cfg["search"]["n_probes"])
    base, queries = mixture.make(cfg["data"], cfg["mixture"], args.seed)
    index = jax.block_until_ready(ivf_flat.build(
        base, ivf_flat.IvfFlatIndexParams(**cfg["index"])))
    ops = (index.centroids, index.data, index.ids, index.counts, index.norms)
    out = []

    def emit(rec):
        rec["device"] = jax.devices()[0].device_kind
        out.append(rec)
        print(json.dumps(rec), flush=True)

    emit({"what": "index", "n_lists": index.n_lists, "list_cap": index.list_cap,
          "max_count": int(jnp.max(index.counts))})
    fn, served_ops = ivf_flat.searcher(index, k, ivf_flat.IvfFlatSearchParams(
        **cfg["search"]))
    for b in BUCKETS:
        q = queries[:b]
        before = _paths(registry)
        served = jax.jit(fn).lower(q, *served_ops).compile()
        jax.block_until_ready(served(q, *served_ops))
        after = _paths(registry)
        emit({"what": "served", "rows": b, "lowered": {
            p: after[p] - before.get(p, 0) for p in after
            if after[p] > before.get(p, 0)}})
        grouped = _grouped_at(GROUPED_TILE, k, n_probes)
        for path, call in (
                ("query_major", lambda q=q: ivf_flat._search_impl(
                    *ops, q, k, n_probes, index.metric, None, 8, "xla")),
                ("grouped", lambda q=q: grouped(q, *ops))):
            emit({"what": "bucket", "rows": b, "path": path,
                  "median_ms": 1e3 * _median_seconds(call, args.reps)})
    q = queries[:BUCKETS[-1]]
    for qt in TILES:
        run = _grouped_at(qt, k, n_probes)
        emit({"what": "tile", "rows": int(q.shape[0]), "qt": qt,
              "median_ms": 1e3 * _median_seconds(lambda: run(q, *ops),
                                                 args.reps)})
    for b in (64, 512):
        qb = queries[:b]
        xd, xi = map(np.asarray, ivf_flat._search_impl(
            *ops, qb, k, n_probes, index.metric, None, 8, "xla"))
        gd, gi = map(np.asarray, ivf_flat._search_impl(
            *ops, qb, k, n_probes, index.metric, None, 8, "grouped"))
        qh = np.asarray(qb, np.float64)
        yh = np.asarray(base, np.float64)
        scale = ((qh ** 2).sum(1)[:, None] + (yh[xi] ** 2).sum(-1))
        ulp = np.spacing(scale.astype(np.float32)).astype(np.float64)
        emit({"what": "agree", "rows": b,
              "ids_equal": float((xi == gi).mean()),
              "rows_ids_equal": float((xi == gi).all(1).mean()),
              "max_gap_ulps": float(np.max(np.abs(gd - xd) / ulp))})
    from raft_tpu.neighbors import mutation

    q = queries[:BUCKETS[-1]]
    compacted = mutation.compact(index)
    before = [np.asarray(a) for a in jax.jit(fn)(q, *served_ops)]
    cfn, cops = ivf_flat.searcher(compacted, k, ivf_flat.IvfFlatSearchParams(
        **cfg["search"]))
    after = [np.asarray(a) for a in jax.jit(cfn)(q, *cops)]
    emit({"what": "compact", "rows": int(q.shape[0]),
          "list_cap": [index.list_cap, compacted.list_cap],
          "dists_equal": bool(np.array_equal(before[0], after[0])),
          "ids_equal": bool(np.array_equal(before[1], after[1]))})
    emit({"what": "counters", **{
        name: {",".join(f"{k}={v}" for k, v in labels.items()): n
               for labels, n in registry().counter(name).samples()}
        for name in ("raft_ivf_scan_path_total",
                     "raft_pallas_dispatch_total")}})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
