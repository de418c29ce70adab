"""Bring-up check: the library's main path on one TPU chip, through its
public entry points, at a deployment size users run.

The deployment is SIFT-1M class (ann-benchmarks ``sift-128-euclidean``:
1,000,000 × 128 float32 base vectors, 10,000 queries, L2, k = 10).  The
vectors are Gaussian blobs made on the device from ``--seed`` with
``raft_tpu.random.make_blobs``; nothing is downloaded.  Phases, each
printing one JSON line (seconds, compile seconds, recall, peak device
bytes):

1. device     — ``jax.devices()`` must be TPUs; there is no CPU branch.
2. exact      — ``brute_force.knn(mode="exact")`` ground truth for every
                query, spot-checked against a float64 NumPy brute force.
3. fast       — ``knn(mode="fast")`` through the Mosaic ``fused_l2_topk``.
4. ivf_flat   — 1024-list IVF-Flat served by ``serve.SearchServer``, then
                one ``scan_kernel="grouped"`` search against ``"xla"``.
5. ivf_pq     — IVF-PQ served by ``serve.SearchServer`` as a
                ``refine.Refined`` view: 32k PQ candidates re-ranked
                exactly in the served program.
6. kernels    — every Pallas kernel against a plain XLA reference.

Every Pallas lowering must resolve to Mosaic (``raft_pallas_dispatch_total``).

``--multichip`` runs only the path that spans chips: ``serve.FleetServer``
and ``make_fleet_searcher`` over a 4-chip mesh serving a DEEP-10M-class
IVF-Flat index (10,000,000 × 96 float32), compared with ``make_searcher``
on one chip.

Any failed check raises, so the exit code is nonzero.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Deployment:
    rows: int
    dim: int
    queries: int
    k: int = 10
    rows_per_blob: int = 1000
    n_lists: int = 1024
    n_probes: int = 32
    list_cap_ratio: float = 2.0


# ann-benchmarks sift-128-euclidean / big-ann-benchmarks deep-10M widths
SIFT_1M = Deployment(rows=1_000_000, dim=128, queries=10_000)
# list_cap_ratio 1.25 keeps the one-chip reference searcher's program
# inside 16 GB: it holds the whole 4.9 GB slab and XLA adds ~9 GB of
# temporaries (compiled for v5e, PR 21).  At that headroom 1000-row blobs
# overfill lists: whole blobs spill to lists no query probes (recall
# 0.773 on the chip, 0.717 in a 2M-row CPU analog); 100-row blobs balance
# (0.967 in the same analog; my CPU runs, PR 21).
DEEP_10M = Deployment(rows=10_000_000, dim=96, queries=10_000,
                      rows_per_blob=100, list_cap_ratio=1.25)
LADDER = (1, 8, 64, 512)
FAST_RECALL, IVF_RECALL = 0.999, 0.95
# the DEEP-10M-class reference at 25% list headroom still spills ~7% of
# rows (CPU analog, PR 21); this floor catches a layout that strands
# whole blobs (0.773), not that spill
DEEP_RECALL = 0.9
# 8× re-ranked candidates hold 0.936 of the true top-10 on a v5e and 0.946
# on the CPU at this deployment's size (seed 0): both under the 0.95 floor,
# which 32× clears (0.979 on the CPU; 0.974 on the chip when its slab
# norms were still of the unrounded reconstruction; PERF.md)
REFINE_RATIO = 32
# scan_kernel="grouped" scores the XLA scan's candidates with the same
# algebra in another accumulation order, so it keeps the XLA scan's ids
# but where two candidates tie within a few ulps
GROUPED_AGREE = 0.99
# both scans re-score in f32 as ‖q‖² + ‖y‖² − 2⟨q, y⟩, in different
# orders: their distances may differ by a few f32 ulps of ‖q‖² + ‖y‖².
# A bf16 re-score, checked as the control, is ~1000 ulps off.
DIST_ULPS = 16
# an IVF search gathers [queries, probe_block, cap, d] per scan step;
# these batch sizes keep that under ~2 GB of the chip's 16 GB.  At 32
# probes over 1024 lists, 256 queries probe each list 8 times on
# average, past the grouped scan's 0.5 a list
GROUPED_QUERIES = 256


class _Compiles:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _dispatches():
    from raft_tpu.obs.metrics import registry

    return {(lb["kernel"], lb["mode"]): v for lb, v in
            registry().counter("raft_pallas_dispatch_total").samples()}


class Phase:
    """Times one phase and prints its JSON line, with the error if one was
    raised; the exception propagates."""

    def __init__(self, name, compiles):
        self.name, self.compiles, self.info = name, compiles, {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.compiles.total
        self.d0 = _dispatches()
        return self

    def __exit__(self, exc_type, exc, _tb):
        lowered = {f"{k}:{m}": v - self.d0.get((k, m), 0)
                   for (k, m), v in _dispatches().items()
                   if v > self.d0.get((k, m), 0)}
        wrong = [km for km in lowered if not km.endswith(":mosaic")]
        if exc_type is not None:
            self.info["error"] = f"{exc_type.__name__}: {exc}"
        line = {"phase": self.name,
                "seconds": time.perf_counter() - self.t0,
                "compile_seconds": self.compiles.total - self.c0,
                "peak_bytes_in_use": _peak_bytes(),
                "pallas_lowerings": lowered, **self.info}
        print(json.dumps(line), flush=True)
        if wrong and exc_type is None:
            raise AssertionError(f"{self.name}: Pallas lowered as {wrong}")
        return False


def _recall(ids, ref_ids):
    from raft_tpu.stats import neighborhood_recall

    return float(neighborhood_recall(ids, ref_ids))


def _require(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _ids_match_untied(ref_d, ref_i, ids):
    """Ids equal wherever the reference's distances are untied (a tie may
    come back in either order)."""
    import numpy as np

    tied = np.zeros_like(ref_d, bool)
    tied[:, 1:] |= ref_d[:, 1:] == ref_d[:, :-1]
    tied[:, :-1] |= ref_d[:, :-1] == ref_d[:, 1:]
    return bool(np.all((ids == ref_i) | tied))


def device_check(count):
    """The chip or nothing: raise unless JAX's devices are ``count`` TPUs."""
    import jax

    devs = jax.devices()
    _require(devs[0].platform == "tpu",
             f"no TPU: JAX found {devs[0].platform} devices")
    _require(len(devs) >= count, f"need {count} TPU chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_data(dep, seed, chunk=1_000_000):
    """Base rows and queries (host arrays) from one blob mixture, made on
    the device by ``make_blobs`` a chunk of rows at a time: a whole
    10M-row mixture in one eager call needs ~3x its size in device
    temporaries.  Blob labels are drawn independently per row, so rows
    come in random order without a shuffle."""
    import jax
    import numpy as np

    from raft_tpu.random import make_blobs

    n = dep.rows + dep.queries
    kc, kr = jax.random.split(jax.random.PRNGKey(seed))
    centers = jax.random.uniform(kc, (max(1, n // dep.rows_per_blob),
                                      dep.dim), minval=-10.0, maxval=10.0)
    x = np.concatenate([
        np.asarray(make_blobs(jax.random.fold_in(kr, i), min(chunk, n - lo),
                              dep.dim, centers=centers, shuffle=False)[0])
        for i, lo in enumerate(range(0, n, chunk))])
    return x[:dep.rows], x[dep.rows:]


def _numpy_knn(base, queries, k):
    """Plain float64 brute force, independent of the code under test."""
    import numpy as np

    b = np.asarray(base, np.float64)
    bn = np.einsum("ij,ij->i", b, b)
    out_d, out_i = [], []
    for q in np.asarray(queries, np.float64):
        d = bn - 2.0 * (b @ q) + q @ q
        i = np.argpartition(d, k)[:k]
        i = i[np.argsort(d[i], kind="stable")]
        out_d.append(d[i])
        out_i.append(i)
    return np.stack(out_d), np.stack(out_i)


def _serve(index, queries, k, params):
    """Answer one request of each ladder size, then every query (split
    into top-bucket parts by the server); returns the full answer."""
    import numpy as np

    from raft_tpu.serve import SearchServer, ServerConfig

    # a bring-up run, not a latency benchmark: deadlines only bound a hang
    srv = SearchServer(index, k=k, params=params,
                       config=ServerConfig(ladder=LADDER, max_wait_ms=1.0,
                                           default_deadline_ms=120_000.0))
    t0 = time.perf_counter()
    srv.start()
    warm = time.perf_counter() - t0
    try:
        q = np.asarray(queries)
        lo = 0
        for rows in LADDER:
            dv, di = srv.search(q[lo:lo + rows])
            _require(di.shape == (rows, k) and np.isfinite(dv).all(),
                     f"bad answer to a {rows}-row request")
            lo += rows
        t0 = time.perf_counter()
        dv, di = srv.search(q)
        served = time.perf_counter() - t0
    finally:
        srv.stop()
    return dv, di, {"warmup_seconds": warm, "serve_seconds": served,
                    "requests": len(LADDER) + 1,
                    "executables": srv.cache.compiles,
                    "served_qps": len(q) / served}


def run_single(dep, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq, refine

    compiles = _Compiles()
    with Phase("data", compiles) as ph:
        host_base, queries = make_data(dep, seed)
        base = jax.block_until_ready(jnp.asarray(host_base))
        queries = jnp.asarray(queries)
        ph.info.update(rows=dep.rows, dim=dep.dim, queries=dep.queries,
                       base_bytes=base.nbytes)
    k = dep.k

    with Phase("exact", compiles) as ph:
        gt_d, gt_i = jax.block_until_ready(
            brute_force.knn(queries, base, k, mode="exact"))
        gt_d, gt_i = np.asarray(gt_d), np.asarray(gt_i)
        ref_d, ref_i = _numpy_knn(host_base, queries[:100], k)
        r = _recall(gt_i[:100], ref_i)
        err = float(np.max(np.abs(gt_d[:100] - ref_d)
                           / np.maximum(np.abs(ref_d), 1.0)))
        ph.info.update(recall_vs_float64=r, max_rel_err_vs_float64=err)
        _require(r >= 0.999, f"exact knn recall {r} vs float64 reference")
        _require(err < 1e-4, f"exact knn distance error {err}")

    with Phase("fast", compiles) as ph:
        fd, fi = jax.block_until_ready(
            brute_force.knn(queries, base, k, mode="fast"))
        r = _recall(np.asarray(fi), gt_i)
        ph.info.update(recall=r, floor=FAST_RECALL)
        _require(_dispatches().get(("fused_l2_topk", "mosaic")),
                 "fast knn never lowered fused_l2_topk")
        _require(r >= FAST_RECALL, f"fast knn recall {r} < {FAST_RECALL}")

    with Phase("ivf_flat", compiles) as ph:
        t0 = time.perf_counter()
        index = jax.block_until_ready(ivf_flat.build(
            base, ivf_flat.IvfFlatIndexParams(
                n_lists=dep.n_lists, list_cap_ratio=dep.list_cap_ratio,
                seed=seed)))
        ph.info["build_seconds"] = time.perf_counter() - t0
        sp = ivf_flat.IvfFlatSearchParams(n_probes=dep.n_probes)
        _, di, served = _serve(index, queries, k, sp)
        r = _recall(di, gt_i)
        ph.info.update(recall=r, floor=IVF_RECALL, **served)
        _require(r >= IVF_RECALL, f"ivf_flat recall {r} < {IVF_RECALL}")
        q = queries[:GROUPED_QUERIES]
        xd, xi = ivf_flat.search(index, q, k, dataclasses.replace(
            sp, scan_kernel="xla"))
        zd, zi = ivf_flat.search(index, q, k, dataclasses.replace(
            sp, scan_kernel="grouped"))
        xd, xi, zd, zi = map(np.asarray, (xd, xi, zd, zi))
        agree = _recall(zi, xi)
        ulps, ctrl = _ulps_apart(np.asarray(q), host_base, xi, xd, zi, zd)
        ph.info.update(grouped_vs_xla_recall=agree, grouped_vs_xla_ulps=ulps,
                       bf16_control_ulps=ctrl,
                       grouped_vs_xla_bitwise=bool(
                           np.array_equal(xd, zd)
                           and np.array_equal(xi, zi)))
        _require(_dispatches().get(("ivf_grouped_scan", "mosaic")),
                 "scan_kernel='grouped' never lowered ivf_grouped_scan")
        _require(agree >= GROUPED_AGREE,
                 f"grouped scan recall vs xla {agree}")
        _require(ulps <= DIST_ULPS, f"grouped scan distances {ulps} ulps off")
        _require(ctrl > DIST_ULPS, f"bf16 control only {ctrl} ulps off")
        del index

    with Phase("ivf_pq", compiles) as ph:
        t0 = time.perf_counter()
        index = jax.block_until_ready(ivf_pq.build(
            base, ivf_pq.IvfPqIndexParams(n_lists=dep.n_lists, seed=seed)))
        ph.info["build_seconds"] = time.perf_counter() - t0
        sp = ivf_pq.IvfPqSearchParams(n_probes=dep.n_probes)
        _, ri, served = _serve(refine.Refined(index, base, REFINE_RATIO),
                               queries, k, sp)
        r = _recall(ri, gt_i)
        _, cand = ivf_pq.search(index, queries[:512], k, sp)
        ph.info.update(recall=r, floor=IVF_RECALL, refine_ratio=REFINE_RATIO,
                       unrefined_recall_512=_recall(np.asarray(cand),
                                                    gt_i[:512]), **served)
        _require(r >= IVF_RECALL, f"ivf_pq+refine recall {r} < {IVF_RECALL}")
        del index

    with Phase("kernels", compiles) as ph:
        ph.info.update(kernel_checks())


def _ulps_apart(q, base, xi, xd, zi, zd):
    """How far the grouped scan's distances are from the XLA scan's, where
    both returned the same id, in f32 ulps of ‖q‖² + ‖y‖²; and the same for
    a control that re-scores those pairs from bf16 inputs."""
    import jax.numpy as jnp
    import numpy as np

    rows, cols = np.nonzero(xi == zi)
    qf = np.asarray(q, np.float64)[rows]
    yf = np.asarray(base[xi[rows, cols]], np.float64)
    ulp = np.finfo(np.float32).eps * (np.sum(qf * qf, 1) + np.sum(yf * yf, 1))
    xd = xd[rows, cols]

    def bf16(a):
        return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)

    ctrl = (np.sum(qf * qf, 1) + np.sum(yf * yf, 1)
            - 2.0 * np.sum(bf16(qf) * bf16(yf), 1))
    return (float(np.max(np.abs(zd[rows, cols] - xd) / ulp)),
            float(np.max(np.abs(ctrl - xd) / ulp)))


def kernel_checks(seed=7):
    """Each Pallas kernel against plain XLA on the same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.ops.pallas.fused_l2_topk import (fused_shortlist,
                                                   int8_surrogate_norms)
    from raft_tpu.ops.pallas.select_k import select_k_pallas

    key = jax.random.PRNGKey(seed)
    out = {}
    # the last case is IVF-PQ's k=80 fold over 8 probed lists of 1465 rows
    for rows, cols, k in ((1024, 2048, 64), (256, 16384, 32),
                          (512, 11800, 80)):
        x = jax.random.normal(jax.random.fold_in(key, cols), (rows, cols))
        pv, pi = select_k_pallas(x, k)
        nv, ni = jax.lax.top_k(-x, k)
        exact = bool(np.array_equal(np.asarray(pv), -np.asarray(nv))
                     and np.array_equal(np.asarray(pi), np.asarray(ni)))
        out[f"select_k_{rows}x{cols}_k{k}_exact"] = exact
        _require(exact, f"select_k_pallas != lax.top_k at {rows}x{cols}")

    def shortlist_recall(ids, dist, k=10):
        true = np.argsort(np.asarray(dist), axis=1)[:, :k]
        ids = np.asarray(ids)
        return float(np.mean([len(set(t) & set(s)) / k
                              for t, s in zip(true, ids)]))

    for dtype in ("bfloat16", "int8"):
        kx, ky = jax.random.split(jax.random.fold_in(key, len(dtype)))
        if dtype == "int8":
            x = jax.random.randint(kx, (256, 128), 0, 256).astype(jnp.uint8)
            y = jax.random.randint(ky, (8192, 128), 0, 256).astype(jnp.uint8)
            yn = int8_surrogate_norms(y)
        else:
            x = jax.random.normal(kx, (256, 128))
            y = jax.random.normal(ky, (8192, 128))
            yn = jnp.sum(y * y, axis=1)
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        dist = (jnp.sum(yf * yf, axis=1)[None, :]
                - 2.0 * jnp.matmul(xf, yf.T, precision="highest"))
        _, si = fused_shortlist(x, y, yn)
        r = shortlist_recall(si, dist)
        out[f"fused_l2_topk_{dtype}_recall"] = r
        _require(r >= 0.999, f"fused_l2_topk {dtype} shortlist recall {r}")
    return out


def run_multichip(dep, seed):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from raft_tpu.neighbors import brute_force, ivf_flat
    from raft_tpu.serve import FleetServer, ServerConfig, make_searcher
    from raft_tpu.serve.fleet import make_fleet_searcher

    compiles = _Compiles()
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs), ("shard",))
    k = dep.k
    with Phase("data", compiles) as ph:
        host, queries = make_data(dep, seed)
        # the one-chip searcher gathers [queries, cap, d] per probe: 64
        # queries keep its program inside 16 GB (compiled for v5e)
        q = queries[:64]
        base = jax.device_put(host)
        gt_i = np.asarray(brute_force.knn(q, base, k, mode="exact")[1])
        del base
        ph.info.update(rows=dep.rows, dim=dep.dim, queries=len(q),
                       base_bytes=host.nbytes)
    with Phase("build", compiles) as ph:
        index = jax.block_until_ready(ivf_flat.build_chunked(
            host, ivf_flat.IvfFlatIndexParams(
                n_lists=dep.n_lists, list_cap_ratio=dep.list_cap_ratio,
                seed=seed)))
        del host
        ph.info.update(list_cap=index.list_cap,
                       index_bytes=index.data.nbytes)
    sp = ivf_flat.IvfFlatSearchParams(n_probes=dep.n_probes)
    with Phase("single_device", compiles) as ph:
        fn, ops = make_searcher(index, k, sp)
        sd, si = map(np.asarray, jax.jit(fn)(q, *ops))
        ref_recall = _recall(si, gt_i)
        ph.info.update(recall=ref_recall, floor=DEEP_RECALL,
                       device=str(ops[1].devices()),
                       **_layout(index, q, gt_i, dep.n_probes))
    with Phase("fleet", compiles) as ph:
        ffn, fops = make_fleet_searcher(index, k, sp, mesh=mesh)
        fd, fi = map(np.asarray, jax.jit(ffn)(q, *fops))
        homes = [str(s.device) for s in fops[1].addressable_shards]
        _require(len(set(homes)) == len(devs),
                 f"shards not one per device: {homes}")
        _require(all(s.data.shape[0] == fops[1].shape[0] // len(devs)
                     for s in fops[1].addressable_shards),
                 "shards are not equal slices of the lists")
        bitwise = bool(np.array_equal(sd, fd) and np.array_equal(si, fi))
        id_ok = _ids_match_untied(sd, si, fi)
        rel = float(np.max(np.abs(fd - sd) / np.maximum(np.abs(sd), 1e-6)))
        ph.info.update(recall=_recall(fi, gt_i), shard_devices=homes,
                       bitwise=bitwise, ids_match_untied=id_ok,
                       max_rel_dist=rel)
        _require(id_ok, "fleet ids differ from the single-device searcher")
        _require(rel <= 1e-5, f"fleet distances off by {rel} relative")
        del ffn, fops
    with Phase("fleet_server", compiles) as ph:
        fleet = FleetServer(index, k, sp, mesh=mesh,
                            config=ServerConfig(
                                ladder=LADDER[:3], max_wait_ms=1.0,
                                default_deadline_ms=120_000.0))
        fleet.start()
        try:
            lo, rows_ok = 0, 0
            for rows in (1, 8, 48, 7):      # buckets 1, 8, 64, 8 of 64 rows
                dv, di = fleet.search(q[lo:lo + rows])
                dv, di = np.asarray(dv), np.asarray(di)
                want_d, want_i = sd[lo:lo + rows], si[lo:lo + rows]
                _require(_ids_match_untied(want_d, want_i, di),
                         f"fleet server ids differ on a {rows}-row request")
                _require(np.allclose(dv, want_d, rtol=1e-5),
                         f"fleet server distances differ ({rows} rows)")
                rows_ok += rows
                lo += rows
        finally:
            fleet.stop()
        ph.info.update(requests=4, rows=rows_ok,
                       selftest=bool(fleet.selftest_results is not None))
    # gated last, so a low reading still leaves the fleet comparison
    _require(ref_recall >= DEEP_RECALL,
             f"one-chip recall {ref_recall} < {DEEP_RECALL}")


def _layout(index, q, gt_i, n_probes):
    """Where the true neighbours were stored: the share of lists filled
    to capacity, and the share of true top-k ids in lists the queries
    probe (the recall any exact scan of those lists can reach)."""
    import numpy as np

    ids = np.asarray(index.ids)
    home = np.full(ids.max() + 1, -1, np.int64)
    lists, _ = np.nonzero(ids >= 0)
    home[ids[ids >= 0]] = lists
    c = np.asarray(index.centroids, np.float64)
    qf = np.asarray(q, np.float64)
    d = (qf * qf).sum(1)[:, None] - 2 * qf @ c.T + (c * c).sum(1)[None]
    probed = np.argsort(d, axis=1)[:, :n_probes]
    found = [np.isin(home[g], p) for g, p in zip(gt_i, probed)]
    return {"lists_at_cap": float(np.mean(np.asarray(index.counts)
                                          >= index.list_cap)),
            "gt_in_probed_lists": float(np.mean(found))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="run the fleet path on four chips, and nothing else")
    args = ap.parse_args(argv)

    from raft_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.perf_counter()
    device = device_check(4 if args.multichip else 1)
    print(json.dumps({"phase": "device", **device,
                      "seconds": time.perf_counter() - t0}), flush=True)
    if args.multichip:
        run_multichip(DEEP_10M, args.seed)
    else:
        run_single(SIFT_1M, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
