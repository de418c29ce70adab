"""Each stage of a built IVF-PQ index against the float64 stage reference
(``tests/ivf_pq_stage_reference.py``), then the served recall with exact
re-ranking: finds which stage a wrong answer comes from.

    python bench/ivf_pq_stages.py --config deep10m-ivf_pq --seed <n> \
        [--rows <n>] [--probes 16,32,64] [--sample-rows 200000]

The corpus and the index are the benchmark configuration's
(``benchmark/configs/<config>.json``, built by
``benchmark/families/<family>.py``), with ``--rows`` cutting the corpus.
One JSON line per stage on standard output, also written to
``chiprun_out/ivf_pq_stages-<config>-<rows>-<seed>-<platform>.jsonl``:

- ``build``: seconds of each build stage (``raft_index_build_seconds``);
- ``coarse``: share of sampled stored rows whose list is their nearest
  (ties at float32 resolution counted), and the share of full lists;
- ``codes``: share of sampled sub-codes equal to the float64 encode of the
  row's residual against its list (exact, and with ties);
- ``decode``: the recon slab against the float64 decode of the stored
  codes, in bf16 half-ulps (at most 1 where the slab is x̂ rounded once),
  and the stored ``‖x̂‖²`` against the slab's own, relative;
- ``scan``: for ``--queries`` queries, the program's ``k·ratio``
  candidates against the float64 top ``k·ratio`` by distance to the
  stored slab over the float64 probe set: the share found, the share
  returned from a list no query should probe, and the gap between the
  program's distance and the float64 one in units of
  ``2^-8·(‖q‖² + ‖x̂‖²)`` (the bf16 query's rounding);
- ``select_k``: ``matrix.select_k`` at the scan's fold shape against
  NumPy, on random values (share of the true top-k set found);
- ``served``: recall@k of ``SearchServer`` over the ``Refined`` view at
  each probe count, against the benchmark's plain reference (computed
  before the build: at full size it needs most of the chip).

Runs on whatever JAX finds; with ``JAX_PLATFORMS=cpu`` it gives the CPU's
readings of the same code and data.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import numpy as np  # noqa: E402

_OUT = []


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)
    _OUT.append(line)


def slots_of(ids: np.ndarray):
    """``(list, slot)`` of every stored id, indexed by id."""
    lists, slots = np.nonzero(ids >= 0)
    n = int(ids.max()) + 1
    li = np.full(n, -1, np.int64)
    sl = np.full(n, -1, np.int64)
    li[ids[lists, slots]] = lists
    sl[ids[lists, slots]] = slots
    return li, sl


def stage_checks(index, base, rows, seed):
    import jax.numpy as jnp

    import ivf_pq_stage_reference as ref

    cent = np.asarray(index.centroids, np.float64)
    cb = np.asarray(index.codebooks, np.float64)
    ids = np.asarray(index.ids)
    counts = np.asarray(index.counts)
    li, sl = slots_of(ids)
    rs = np.random.default_rng(seed)
    stored = np.nonzero(li >= 0)[0]
    pick = np.sort(rs.choice(stored, min(rows, len(stored)), replace=False))
    x = np.asarray(jnp.take(base, jnp.asarray(pick), axis=0), np.float64)
    lst, slt = li[pick], sl[pick]

    near, dmin = ref.nearest_lists(x, cent)
    d_got = ((x - cent[lst]) ** 2).sum(1)
    scale = (x * x).sum(1) + (cent[lst] ** 2).sum(1)
    emit({"stage": "coarse", "rows": len(pick),
          "nearest": float(np.mean(near == lst)),
          "nearest_with_ties": float(np.mean(
              (near == lst) | (d_got - dmin <= 4 * ref.F32_EPS * scale))),
          "lists_full": float(np.mean(counts >= ids.shape[1]))})

    codes = np.asarray(index.codes)[lst, slt].astype(np.int64)
    exact, ties = ref.code_agreement(x - cent[lst], cb, codes)
    emit({"stage": "codes", "rows": len(pick), "sub_codes": codes.size,
          "agree": exact, "agree_with_ties": ties})

    xhat, _ = ref.decode(codes, lst, cent, cb)
    slab = np.asarray(index.recon[jnp.asarray(lst), jnp.asarray(slt)],
                      np.float64)[:, :index.dim]
    half_ulp = np.maximum(np.abs(xhat), 1e-30) * 2.0 ** -8
    gap = np.abs(slab - xhat) / half_ulp
    norms = np.asarray(index.recon_norms[jnp.asarray(lst), jnp.asarray(slt)],
                       np.float64)
    slab_sq = (slab * slab).sum(1)
    emit({"stage": "decode", "rows": len(pick),
          "slab_gap_half_ulps_max": float(gap.max()),
          "slab_beyond_one_rounding": float(np.mean(gap > 1.0 + 1e-6)),
          "norm_rel_err_max": float(np.max(np.abs(norms - slab_sq)
                                           / np.maximum(slab_sq, 1e-30)))})
    return li, sl


def scan_check(index, queries, k_cand, params, li, sl):
    import jax.numpy as jnp

    import ivf_pq_stage_reference as ref
    from raft_tpu.neighbors import ivf_pq

    dv, di = ivf_pq.search(index, queries, k_cand, params)
    dv, di = np.asarray(dv, np.float64), np.asarray(di)
    q = np.asarray(queries, np.float64)
    cent = np.asarray(index.centroids, np.float64)
    p = min(int(params.n_probes), len(cent))
    probes = np.argsort(ref.sq_dists(q, cent), axis=1, kind="stable")[:, :p]
    ids = np.asarray(index.ids)
    found = unprobed = 0
    gaps = []
    for r in range(len(q)):
        lists = probes[r]
        cand = ids[lists].reshape(-1)
        live = cand >= 0
        cand = cand[live]
        slab = np.asarray(index.recon[jnp.asarray(lists)], np.float64)[
            ..., :q.shape[1]].reshape(-1, q.shape[1])[live]
        d = ((slab - q[r]) ** 2).sum(1)
        top = set(cand[np.argsort(d, kind="stable")[:k_cand]].tolist())
        got = di[r][di[r] >= 0]
        found += len(top & set(got.tolist()))
        unprobed += int(np.sum(~np.isin(li[got], lists)))
        pos = {int(c): j for j, c in enumerate(cand)}
        for g, dg in zip(got, dv[r][di[r] >= 0]):
            j = pos.get(int(g))
            if j is not None:
                unit = 2.0 ** -8 * (q[r] @ q[r] + slab[j] @ slab[j])
                gaps.append(abs(dg - d[j]) / unit)
    gaps = np.asarray(gaps)
    emit({"stage": "scan", "queries": len(q), "k_cand": k_cand,
          "n_probes": p, "found": found / (len(q) * k_cand),
          "from_unprobed_lists": unprobed / (len(q) * k_cand),
          "dist_gap_bf16_units_median": float(np.median(gaps)),
          "dist_gap_bf16_units_max": float(gaps.max())})


def select_k_check(nq, length, k, seed, empty_carry):
    import jax.numpy as jnp

    from raft_tpu.matrix.select_k import select_k

    rs = np.random.default_rng(seed)
    x = rs.standard_normal((nq, length)).astype(np.float32) * 100 + 1000
    if empty_carry:                         # a scan's first fold
        x[:, :k] = np.inf
    _, idx = select_k(jnp.asarray(x), k, select_min=True, sorted=False)
    idx = np.asarray(idx)
    want = np.argpartition(x, k - 1, axis=1)[:, :k]
    # the empty slots tie at +inf: only the finite winners are compared
    hit = np.mean([len(set(a) & set(b[np.isfinite(r[b])])) /
                   max(1, int(np.isfinite(r[b]).sum()))
                   for a, b, r in zip(idx, want, x)])
    emit({"stage": "select_k", "shape": [nq, length], "k": k,
          "empty_carry": empty_carry, "found": float(hit)})


def served(view, queries, k, params, probes, ref_ids):
    from benchmark import reference
    from raft_tpu.serve import SearchServer, ServerConfig

    for p in probes:
        sp = dataclasses.replace(params, n_probes=p)
        srv = SearchServer(view, k=k, params=sp, config=ServerConfig(
            ladder=(512,), max_wait_ms=1.0, default_deadline_ms=600_000.0))
        srv.start()
        try:
            got = np.concatenate([srv.search(queries[lo:lo + 512])[1]
                                  for lo in range(0, len(queries), 512)])
        finally:
            srv.stop()
        emit({"stage": "served", "n_probes": p, "ratio": view.ratio,
              "recall": reference.recall(got, ref_ids)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--probes", default="")
    ap.add_argument("--sample-rows", type=int, default=200_000)
    ap.add_argument("--queries", type=int, default=256)
    args = ap.parse_args(argv)

    import jax

    from benchmark import harness, mixture, reference, spec
    from raft_tpu.obs.metrics import registry

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        harness.enable_compile_cache(ROOT)
    dev = jax.devices()[0]
    emit({"stage": "device", "platform": dev.platform,
          "kind": dev.device_kind})
    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    if args.rows:
        cfg["data"]["rows"] = args.rows
    k = int(cfg["data"]["k"])
    t = time.monotonic()
    base, queries = mixture.make(cfg["data"], cfg["mixture"], args.seed)
    base = jax.block_until_ready(base)
    queries = np.asarray(queries)
    emit({"stage": "data", "rows": int(base.shape[0]),
          "seconds": time.monotonic() - t})
    _, ref_ids = reference.exact_knn(base, queries, k)
    t = time.monotonic()
    fam = spec.load_module(ROOT, "families", cfg["family"])
    view, params = fam.build(base, cfg)
    gauge = registry().get("raft_index_build_seconds")
    emit({"stage": "build", "seconds": time.monotonic() - t,
          "stages": {s["stage"]: v for s, v in gauge.samples()},
          "list_cap": view.index.list_cap})

    li, sl = stage_checks(view.index, base, args.sample_rows, args.seed)
    scan_check(view.index, queries[:args.queries], k * view.ratio, params,
               li, sl)
    cap = view.index.list_cap
    for blocks in (1, 2, 4, 8):
        for empty in (True, False):
            select_k_check(512, k * view.ratio + blocks * cap,
                           k * view.ratio, args.seed, empty)
    probes = [int(p) for p in args.probes.split(",") if p] or [
        int(params.n_probes)]
    served(view, queries, k, params, probes, ref_ids)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = (f"ivf_pq_stages-{args.config}-{int(base.shape[0])}-{args.seed}-"
            f"{dev.platform}.jsonl")
    with open(os.path.join(out, name), "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in _OUT)
    return 0


if __name__ == "__main__":
    sys.exit(main())
