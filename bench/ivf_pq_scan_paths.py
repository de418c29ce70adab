"""IVF-PQ recon-tier probe-scan paths on the chip: query-major against grouped.

Lays out a recon slab of the ``deep10m-ivf_pq`` configuration's shape on
the device (4096 lists of 3664 slots, 96 bf16 columns stored as 128
lanes, about 2441 live rows a list), its lists centred on rows of the
configuration's Gaussian mixture, and queries from the same mixture.  No
index is built: the scan's cost depends on the slab's shape and on how
the batch's probes spread over the lists, not on the codes.  Per batch
size (the serving buckets 1, 8, 64, 512 rows, and 16 and 32 about the
rule ``ivf_flat.grouped_batch``) it reports which scan the served
program lowers (``raft_ivf_pq_scan_path_total``), and times the recon
tier's search at 40 candidates and 64 probes with the query-major scan
(``scan_kernel="xla"``) and with the grouped scan whatever the rule
says, each the median of ``--reps`` calls that end in
``jax.block_until_ready``.  At 64 and 512 rows it checks the grouped
answers against the query-major ones: ids equal where no two candidates
tie, distance gap in f32 ulps of ‖q‖²+‖x̂‖².

    python bench/ivf_pq_scan_paths.py [--seed N] [--reps N] [--out FILE]

Needs a TPU.  One JSON line per measurement on stdout; with ``--out``,
all of them also as one JSON list in FILE.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "bench"))

from raft_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ivf_scan_paths import _median_seconds  # noqa: E402

BUCKETS = (1, 8, 16, 32, 64, 512)
CAP = 3664


@partial(jax.jit, static_argnames=("cap", "lanes"))
def _slab(key, centroids, *, cap: int, lanes: int):
    """``[L, cap, lanes]`` bf16 rows about each list's centroid, zero past
    d; ids and ``+inf`` norms past each list's count."""
    n_lists, d = centroids.shape
    kc, kr = jax.random.split(key)
    counts = jax.random.randint(kc, (n_lists,), cap // 2, cap + 1)
    block = 256

    def fill(i, out):
        rec, norms = out
        lo = i * block
        c = jax.lax.dynamic_slice_in_dim(centroids, lo, block)
        noise = jax.random.normal(jax.random.fold_in(kr, i),
                                  (block, cap, d), jnp.float32)
        x = (c[:, None, :] + 0.3 * noise).astype(jnp.bfloat16)
        xf = x.astype(jnp.float32)
        live = (jnp.arange(cap)[None, :]
                < jax.lax.dynamic_slice_in_dim(counts, lo, block)[:, None])
        nb = jnp.where(live, jnp.sum(xf * xf, axis=2), jnp.inf)
        xb = jnp.pad(x, ((0, 0), (0, 0), (0, lanes - d)))
        return (jax.lax.dynamic_update_slice_in_dim(rec, xb, lo, 0),
                jax.lax.dynamic_update_slice_in_dim(norms, nb, lo, 0))

    rec, norms = jax.lax.fori_loop(
        0, n_lists // block, fill,
        (jnp.zeros((n_lists, cap, lanes), jnp.bfloat16),
         jnp.zeros((n_lists, cap), jnp.float32)))
    slot = jnp.arange(cap, dtype=jnp.int32)[None, :]
    ids = jnp.where(slot < counts[:, None],
                    jnp.arange(n_lists, dtype=jnp.int32)[:, None] * cap
                    + slot, -1)
    return rec, norms, ids, counts


def _grouped(k: int, n_probes: int):
    """The recon tier's grouped scan, whatever the batch's size."""
    from raft_tpu.distance.pairwise import sq_l2
    from raft_tpu.ops import blocked_scan as bs

    @jax.jit
    def run(q, centroids, recon, recon_norms, ids):
        qn = bs.row_sq_norms(q)
        _, probes = jax.lax.top_k(-sq_l2(q, centroids), n_probes)
        qb = jnp.pad(q.astype(jnp.bfloat16),
                     ((0, 0), (0, recon.shape[2] - q.shape[1])))
        return bs.scan_topk_grouped(qb, qn, recon, recon_norms[:, None, :],
                                    ids, probes, k, l2=True, clamp=False)
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2600000001)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 3

    from benchmark import mixture
    from raft_tpu.neighbors import ivf_flat, ivf_pq
    from raft_tpu.neighbors._packing import resolve_probe_block
    from raft_tpu.obs.metrics import registry

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deep10m-ivf_pq.json")) as f:
        cfg = json.load(f)
    k = int(cfg["data"]["k"]) * int(cfg["refine_ratio"])
    n_probes = int(cfg["search"]["n_probes"])
    n_lists = int(cfg["index"]["n_lists"])
    data = dict(cfg["data"], rows=n_lists)
    centroids, queries = mixture.make(data, cfg["mixture"], args.seed)
    d = int(centroids.shape[1])
    recon, norms, ids, counts = jax.block_until_ready(_slab(
        mixture.seed_key(args.seed + 1), centroids, cap=CAP,
        lanes=ivf_pq.recon_lanes(d)))
    ops = (centroids, recon, norms, ids)
    out = []

    def emit(rec):
        rec["device"] = jax.devices()[0].device_kind
        out.append(rec)
        print(json.dumps(rec), flush=True)

    emit({"what": "slab", "shape": list(recon.shape),
          "mean_count": float(jnp.mean(counts))})
    m = int(cfg["index"]["pq_dim"])
    index = ivf_pq.IvfPqIndex(
        centroids, jnp.zeros((m, 256, d // m), jnp.float32),
        jnp.zeros((n_lists, CAP, 1), jnp.uint8), norms, ids, counts,
        "sqeuclidean", recon=recon, recon_norms=norms)
    fn, served_ops = ivf_pq.searcher(index, k, ivf_pq.IvfPqSearchParams(
        **cfg["search"]))
    probe_block = resolve_probe_block(0, n_probes, CAP, "ivf_pq")
    grouped = _grouped(k, n_probes)

    def paths():
        return {lb["path"]: n for lb, n in registry().counter(
            "raft_ivf_pq_scan_path_total").samples()}

    for b in BUCKETS:
        q = queries[:b]
        before = paths()
        jax.jit(fn).lower(q, *served_ops)
        after = paths()
        emit({"what": "served", "rows": b, "lowered": {
            p: after[p] - before.get(p, 0) for p in after
            if after[p] > before.get(p, 0)},
            "rule_grouped": ivf_flat.grouped_batch(b, n_probes, n_lists)})
        for path, call in (
                ("query_major", lambda q=q: ivf_pq._search_recon_impl(
                    *ops, q, k, n_probes, "sqeuclidean", None, probe_block,
                    "xla")),
                ("grouped", lambda q=q: grouped(q, *ops))):
            emit({"what": "bucket", "rows": b, "path": path,
                  "probe_block": probe_block,
                  "median_ms": 1e3 * _median_seconds(call, args.reps)})
    for b in (64, 512):
        qb = queries[:b]
        xd, xi = map(np.asarray, ivf_pq._search_recon_impl(
            *ops, qb, k, n_probes, "sqeuclidean", None, probe_block, "xla"))
        gd, gi = map(np.asarray, ivf_pq._search_recon_impl(
            *ops, qb, k, n_probes, "sqeuclidean", None, probe_block,
            "grouped"))
        qh = np.asarray(qb, np.float64)
        rn = np.asarray(norms, np.float64).reshape(-1)
        scale = (qh ** 2).sum(1)[:, None] + rn[np.maximum(xi, 0)]
        ulp = np.spacing(scale.astype(np.float32)).astype(np.float64)
        emit({"what": "agree", "rows": b,
              "ids_equal": float((xi == gi).mean()),
              "sets_equal": float(np.mean([set(a) == set(c) for a, c
                                           in zip(xi, gi)])),
              "max_gap_ulps": float(np.max(np.abs(gd - xd) / ulp))})
    emit({"what": "counters", **{
        name: {",".join(f"{k}={v}" for k, v in labels.items()): n
               for labels, n in registry().counter(name).samples()}
        for name in ("raft_ivf_pq_scan_path_total",
                     "raft_pallas_dispatch_total")}})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
