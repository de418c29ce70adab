"""Pod-scale serving fleet: sharded fan-out, replica routing, durability.

Three layers compose here (ISSUE 16 / ROADMAP "pod-scale serving"):

1. **Sharded query fan-out** — :func:`make_fleet_searcher` builds the
   uniform ``(fn, operands)`` serving searcher whose executable is a
   ``shard_map`` over a device mesh: every shard scans ITS slice of the
   index through the same :mod:`~raft_tpu.ops.blocked_scan` core the
   single-device searchers use, folds a local top-k, and one
   ``all_gather`` + ranked ``select_k`` finishes the merge.  The result
   is **bit-identical** to the single-device searcher — values AND ids —
   because per-candidate scores never depend on slab partitioning
   (``slab_dots`` pins the block axis as a batch dim; a shard's grouped
   IVF-Flat plan keeps the single-device tiles of the lists it owns) and
   the global top-k of a union of per-shard top-ks equals the top-k of
   all candidates.  ``tests/test_fleet.py`` pins this across mesh widths.

2. **Replica groups + routing** — :class:`FleetServer` runs N
   :class:`_FleetReplicaServer` replicas (each a full
   :class:`~raft_tpu.serve.server.SearchServer`: micro-batching,
   deadline admission, per-replica degradation ladder + recall guard)
   behind a :class:`FleetRouter` that places each request on the
   least-loaded live replica, spills on ``QueueFull``, and sheds load
   from dead replicas to survivors within the request deadline.

3. **Fleet durability** — :meth:`FleetServer.attach_durability` slices
   the index into per-shard sub-indexes, gives each shard a
   :class:`~raft_tpu.neighbors.wal.DurableStore` + WAL and anti-affinity
   standbys (:mod:`.placement` — a shard's follower never lands on its
   primary's host), ships the log via the multi-follower
   :class:`~raft_tpu.serve.replication.LogShipper`, and promotes on
   lease expiry through the same
   :class:`~raft_tpu.serve.replication.EpochFence` tokens PR 15
   introduced.

Startup refuses to serve over a broken collective:
:func:`~raft_tpu.comms.bootstrap.verify_comms` runs the
:mod:`~raft_tpu.comms.selftest` battery before the first replica warms.
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import lru_cache
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import lockdep
from ..core.compat import shard_map
from ..core.errors import expects
from ..distance.pairwise import sq_l2
from ..matrix.select_k import select_k
from ..neighbors import brute_force as _bf
from ..neighbors import ivf_flat as _ivf
from ..neighbors import ivf_rabitq as _irq
from ..neighbors._packing import (as_keep_mask, blocked_probe_plan,
                                  check_filter_covers_ids, keep_lookup,
                                  resolve_probe_block,
                                  sentinel_filtered_ids)
from ..neighbors.wal import DurableStore
from ..obs import metrics as obs_metrics
from ..obs.prometheus import render, render_labeled
from ..ops import blocked_scan as _scan
from .admission import QueueFull, ServeError
from .placement import Assignment, PlacementPlan, plan_placement
from .replication import (LogShipper, QueuePair, ReplicationConfig,
                          StandbyReplica)
from .searchers import (BruteForceSearchParams, _scaled, family_of,
                        unwrap_tombstones)
from .server import SearchServer, ServerConfig

__all__ = ["make_fleet_searcher", "FleetServer", "FleetRouter",
           "LocalReplica", "ReplicaDead", "FleetDurability",
           "ShardDurability", "shard_sub_indexes"]


class ReplicaDead(ServeError):
    """The targeted replica is gone (process kill / transport closed);
    the router retries survivors within the deadline."""


# ---------------------------------------------------------------------------
# Sharded fan-out programs (one cached shard_map per static config)
# ---------------------------------------------------------------------------
#
# House rules for bit-identity with the single-device searchers:
#
# * per-candidate scores go through the SAME blocked_scan primitives
#   (slab_dots pins the block axis as batch dims, so a candidate's value
#   never depends on which slab/shard it was scored in);
# * non-owned gathers are CLIPPED into the local slab and masked invalid
#   (+inf) — never clamp-and-count, which would double-score the edge
#   lists of a shard;
# * the merge is one all_gather of the per-shard unsorted top-k carries
#   plus ONE ranked select_k — exactly the single searcher's ranked
#   exit over the same candidate multiset;
# * metric exit transforms (euclidean sqrt, inner-product sign) happen
#   once, after the merge, as in the single-device ``_search_impl``s.


@lru_cache(maxsize=32)
def _brute_fleet_program(mesh: Mesh, axis: str, k: int, metric: str,
                         tile: int, per: int):
    """shard_map'd brute-force fan-out: rows split contiguously, local
    exact scan via ``_knn_impl``, ids globalized, merged ranked."""

    def local(q, ysh, msh):
        shard = jax.lax.axis_index(axis)
        bv, bi = _bf._knn_impl(q, ysh, k, metric, tile, msh)
        if metric == "inner_product":
            bv = -bv                       # back to min-selectable
        gi = bi + shard * per              # local row -> global row id
        av = jax.lax.all_gather(bv, axis, tiled=False)   # [S, nq, k]
        ai = jax.lax.all_gather(gi, axis, tiled=False)
        av = jnp.moveaxis(av, 0, 1).reshape(q.shape[0], -1)
        ai = jnp.moveaxis(ai, 0, 1).reshape(q.shape[0], -1)
        dv, di = _scan.ranked_finish(av, ai, k)
        if metric == "inner_product":
            dv = -dv
        return dv, di

    return shard_map(local, mesh=mesh,
                     in_specs=(P(), P(axis), P(axis)),
                     out_specs=(P(), P()), check_vma=False)


@lru_cache(maxsize=32)
def _ivf_flat_fleet_program(mesh: Mesh, axis: str, k: int, n_probes: int,
                            metric: str, probe_block: int, lp: int,
                            has_keep: bool, grouped: bool, n_lists: int):
    """shard_map'd IVF-Flat fan-out: replicated (padded) centroid table
    ranks the SAME global probe list everywhere; each shard scans only
    the probed lists it owns (owned-mask, not clamp-and-count) and the
    merge is one all_gather + ranked finish.

    ``grouped``: the program resolved to the grouped scan, as the
    single-device one does (``ivf_flat.resolve_scan``); a batch takes it
    where ``ivf_flat.grouped_batch`` says so for the whole index's
    ``n_lists``, as on one device.  A shard's plan drops the pairs of
    lists it does not own, and the pairs it keeps form the single-device
    tiles."""

    def local(q, cen, data, ids, counts, norms, *rest):
        keep = rest[0] if has_keep else None
        nq = q.shape[0]
        cap = data.shape[1]
        qf = q.astype(jnp.float32)
        qn = _scan.row_sq_norms(qf)
        cd = sq_l2(q, cen)                       # [nq, L_pad] replicated
        _, probes = jax.lax.top_k(-cd, n_probes)  # pads rank last
        shard = jax.lax.axis_index(axis)
        lo = shard * lp
        on_grouped = grouped and _ivf.grouped_batch(nq, n_probes, n_lists)
        _ivf.count_scan_path("grouped" if on_grouped else "query_major")
        if on_grouped:
            owned = (probes >= lo) & (probes < lo + lp)
            bias = _ivf.slot_bias(norms, ids, counts, metric, keep)
            bv, bi = _scan.scan_topk_grouped(
                qf, qn, data, bias, ids, jnp.clip(probes - lo, 0, lp - 1), k,
                l2=metric != "inner_product", pair_valid=owned)
            return _merge(bv, bi)
        lists_xs, pvalid = blocked_probe_plan(probes, probe_block)

        def score(inp):
            lists, pv = inp                       # GLOBAL lists [nq, B]
            ll = jnp.clip(lists - lo, 0, lp - 1)  # local slab rows
            owned = (lists >= lo) & (lists < lo + lp)
            bcap = lists.shape[1] * cap
            vecs = data[ll]
            vids = ids[ll].reshape(nq, bcap)
            valid = (jnp.arange(cap)[None, None, :]
                     < counts[ll][:, :, None]).reshape(nq, bcap)
            valid = valid & (vids >= 0) & jnp.repeat(pv, cap)[None, :]
            valid = valid & jnp.repeat(owned, cap, axis=1)
            if keep is not None:
                valid = valid & keep_lookup(keep, vids)
            dots = _scan.slab_dots(vecs, q).reshape(nq, -1)
            if metric == "inner_product":
                dist = -dots
            else:
                dist = norms[ll].reshape(nq, dots.shape[1]) - 2.0 * dots \
                    + qn[:, None]
                dist = jnp.maximum(dist, 0.0)
            return jnp.where(valid, dist, jnp.inf), vids

        def step(carry, inp):
            bv, bi = carry
            dist, vids = score(inp)
            return _scan.fold_topk(bv, bi, dist, vids, k,
                                   sorted=False), None

        (bv, bi), _ = jax.lax.scan(step, _scan.topk_carry(nq, k),
                                   (lists_xs, pvalid))
        return _merge(bv, bi)

    def _merge(bv, bi):
        nq = bv.shape[0]
        av = jax.lax.all_gather(bv, axis, tiled=False)
        ai = jax.lax.all_gather(bi, axis, tiled=False)
        av = jnp.moveaxis(av, 0, 1).reshape(nq, -1)
        ai = jnp.moveaxis(ai, 0, 1).reshape(nq, -1)
        dv, di = _scan.ranked_finish(av, ai, k)
        if metric == "euclidean":
            dv = jnp.sqrt(jnp.maximum(dv, 0.0))
        elif metric == "inner_product":
            dv = -dv
        return dv, di

    specs = [P(), P()] + [P(axis)] * 4
    if has_keep:
        specs.append(P())                         # keep masks GLOBAL ids
    return shard_map(local, mesh=mesh, in_specs=tuple(specs),
                     out_specs=(P(), P()), check_vma=False)


@lru_cache(maxsize=32)
def _rabitq_fleet_program(mesh: Mesh, axis: str, k: int, n_probes: int,
                          rerank_k: int, metric: str, probe_block: int,
                          lp: int, has_keep: bool):
    """shard_map'd IVF-RaBitQ fan-out.  The estimator scan is local
    (owned lists only); the GLOBAL ``rerank_k`` survivor set is selected
    identically on every shard from the all-gathered estimator carries,
    each shard exact-rescores the survivors it owns (flat-slab pointers
    stay local — equal slab shapes make foreign pointers in-range
    garbage under the owner mask), and a ``pmin`` assembles the exact
    distances before the single ranked finish.  This mirrors the
    single-device estimate→rerank contract exactly: same survivor set,
    same rescore arithmetic (norm-free brute order), same final
    selection."""

    def local(q, cen, rot, codes, sabs, res_norms, code_cdots, data, ids,
              counts, *rest):
        keep = rest[0] if has_keep else None
        nq = q.shape[0]
        cap = codes.shape[1]
        qf = q.astype(jnp.float32)
        qn = _scan.row_sq_norms(qf)
        cd = sq_l2(q, cen)
        _, probes = jax.lax.top_k(-cd, n_probes)
        shard = jax.lax.axis_index(axis)
        lo = shard * lp
        lists_xs, pvalid = blocked_probe_plan(probes, probe_block)

        # hoisted query prep — identical on every shard (replicated rot)
        qrot = jnp.einsum("qd,ed->qe", qf, rot,
                          precision=jax.lax.Precision.HIGHEST)
        delta = jnp.max(jnp.abs(qrot), axis=1) / 127.0
        delta = jnp.where(delta > 0.0, delta, 1.0)
        q8 = jnp.round(qrot / delta[:, None]).astype(jnp.int8)
        qc = (jnp.einsum("qd,ld->ql", qf, cen.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
              if metric == "inner_product" else None)

        def score(inp):
            lists, pv = inp
            ll = jnp.clip(lists - lo, 0, lp - 1)
            owned = (lists >= lo) & (lists < lo + lp)
            bcap = lists.shape[1] * cap
            sq = _scan.slab_dots(codes[ll], q8,
                                 packed_sign=True).reshape(nq, bcap)
            sa = sabs[ll].reshape(nq, bcap)
            rn2 = res_norms[ll].reshape(nq, bcap)
            vids = ids[ll].reshape(nq, bcap)
            g = jnp.where(sa > 0.0, rn2 / sa, 0.0)
            sqf = delta[:, None] * sq
            if metric == "inner_product":
                qcl = jnp.repeat(jnp.take_along_axis(qc, lists, axis=1),
                                 cap, axis=1)
                est = -(qcl + g * sqf)
            else:
                cs = code_cdots[ll].reshape(nq, bcap)
                cdl = jnp.repeat(jnp.take_along_axis(cd, lists, axis=1),
                                 cap, axis=1)
                est = jnp.maximum(cdl + rn2 - 2.0 * g * (sqf - cs), 0.0)
            valid = (jnp.arange(cap)[None, None, :]
                     < counts[ll][:, :, None]).reshape(nq, bcap)
            valid = valid & (vids >= 0) & jnp.repeat(pv, cap)[None, :]
            valid = valid & jnp.repeat(owned, cap, axis=1)
            if keep is not None:
                valid = valid & keep_lookup(keep, vids)
            ptr = _scan.list_slab_ptr(ll, cap)    # LOCAL flat pointers
            return jnp.where(valid, est, jnp.inf), vids, ptr

        def step(carry, inp):
            bv, bi, bp = carry
            est, vids, ptr = score(inp)
            mv, mi, (mp,) = _scan.fold_topk_payload(
                bv, bi, (bp,), est, vids, (ptr,), rerank_k)
            return (mv, mi, mp), None

        bv0, bi0 = _scan.topk_carry(nq, rerank_k)
        bp0 = jnp.zeros((nq, rerank_k), jnp.int32)
        (bv, bi, bp), _ = jax.lax.scan(step, (bv0, bi0, bp0),
                                       (lists_xs, pvalid))

        # global survivor selection — replicated input, so every shard
        # computes the IDENTICAL (sv, spos) and agrees on ownership
        av = jnp.moveaxis(jax.lax.all_gather(bv, axis, tiled=False),
                          0, 1).reshape(nq, -1)
        ai = jnp.moveaxis(jax.lax.all_gather(bi, axis, tiled=False),
                          0, 1).reshape(nq, -1)
        ap = jnp.moveaxis(jax.lax.all_gather(bp, axis, tiled=False),
                          0, 1).reshape(nq, -1)
        pos = jnp.broadcast_to(jnp.arange(av.shape[1]), av.shape)
        sv, spos = select_k(av, rerank_k, in_idx=pos, select_min=True,
                            sorted=False)
        sids = jnp.take_along_axis(ai, spos, axis=1)
        sptr = jnp.take_along_axis(ap, spos, axis=1)
        sowner = spos // rerank_k
        rescore = _scan.l2_rescorer(data, None, q, qn, metric)
        dist = rescore(sptr, sids)
        mine = (sowner == shard) & jnp.isfinite(sv) & (sids >= 0)
        dist = jnp.where(mine, dist, jnp.inf)
        dist = jax.lax.pmin(dist, axis)           # owner's exact value
        dv, di = _scan.ranked_finish(dist, sids, k)
        if metric == "euclidean":
            dv = jnp.sqrt(jnp.maximum(dv, 0.0))
        elif metric == "inner_product":
            dv = -dv
        return dv, di

    specs = [P(), P(), P()] + [P(axis)] * 7
    if has_keep:
        specs.append(P())
    return shard_map(local, mesh=mesh, in_specs=tuple(specs),
                     out_specs=(P(), P()), check_vma=False)


# ---------------------------------------------------------------------------
# make_fleet_searcher — the sharded analog of searchers.make_searcher
# ---------------------------------------------------------------------------


def make_fleet_searcher(index, k: int, params=None, *, mesh: Mesh,
                        axis: str = "shard", effort_scale: float = 1.0,
                        seed: int = 0, filter=None, slices=None):
    """Build the sharded ``(fn, operands)`` serving searcher for
    ``index`` over ``mesh[axis]``.

    Same contract as :func:`.searchers.make_searcher` — bit-identical to
    the single-device searcher at ``effort_scale=1.0`` (values AND ids),
    one shape-varying input (queries, replicated), index state riding as
    operands (sharded/replicated ``NamedSharding``-committed arrays, so
    the AOT executables record matching input shardings).  A
    ``mutation.Tombstoned`` view unwraps to the shared prefilter, ANDed
    with an explicit ``filter``.

    ``slices``: pre-built ``fleet_slices`` for this exact index view
    (the replica server caches them so the degradation ladder's levels
    share device slabs instead of re-slicing per level).

    IVF-Flat fan-out dispatches the grouped scan where
    ``scan_kernel="auto"`` takes it on one device
    (``ivf_flat.resolve_scan``), else the bit-exact ``"xla"`` blocked
    scan; ``brute_force`` ``mode="fast"`` is rejected — its approximate
    shortlist cannot be bit-pinned across shard boundaries.
    ``seed`` is accepted for signature parity (no stochastic family is
    fleet-enabled yet)."""
    del seed
    expects(0.0 < effort_scale <= 1.0,
            f"effort_scale must be in (0, 1], got {effort_scale}")
    expects(axis in mesh.axis_names, f"axis {axis!r} not in mesh")
    _refuse_refined(index)
    index, keep = unwrap_tombstones(index)
    if keep is not None and filter is not None:
        from ..neighbors.mutation import _combined_keep

        filter = _combined_keep(keep, filter)
    elif keep is not None:
        filter = keep
    fam = family_of(index)
    filtered = filter is not None

    if fam == "brute_force":
        p = params or BruteForceSearchParams()
        expects(p.mode == "exact",
                "fleet fan-out serves brute_force exact mode only — the "
                "fast shortlist is approximate and cannot be bit-pinned "
                "across shard boundaries")
        sl = slices if slices is not None else _bf.fleet_slices(
            index, mesh, axis=axis, filter=filter)
        t = int(min(p.tile, max(sl.per, 1)))
        prog = _brute_fleet_program(mesh, axis, int(k), p.metric, t,
                                    sl.per)
        if filtered:
            def fn(q, y, m):
                dv, di = prog(q, y, m)
                return dv, sentinel_filtered_ids(dv, di)
            return fn, (sl.data, sl.mask)
        return prog, (sl.data, sl.mask)

    rep = NamedSharding(mesh, P())
    if fam == "ivf_flat":
        p = params or _ivf.IvfFlatSearchParams()
        if effort_scale < 1.0:
            p = dataclasses.replace(
                p, n_probes=_scaled(min(p.n_probes, index.n_lists),
                                    effort_scale, 1))
        keep_arr = as_keep_mask(filter)
        if keep_arr is not None:
            expects(keep_arr.ndim == 1,
                    "fleet filters are shared bitsets (1-D)")
            check_filter_covers_ids(keep_arr, index.ids)
        sl = slices if slices is not None else _ivf.fleet_slices(
            index, mesh, axis=axis)
        n_probes = int(min(p.n_probes, index.n_lists))
        probe_block = resolve_probe_block(p.probe_block, n_probes,
                                          index.list_cap, "ivf_flat")
        grouped = _ivf.resolve_scan(p.scan_kernel, index, k, probe_block,
                                    keep_arr) == "grouped"
        prog = _ivf_flat_fleet_program(mesh, axis, int(k), n_probes,
                                       index.metric, probe_block,
                                       sl.lists_per, keep_arr is not None,
                                       grouped, sl.n_lists)
        ops = (sl.centroids, sl.data, sl.ids, sl.counts, sl.norms)
        if keep_arr is not None:
            kp = jax.device_put(keep_arr, rep)

            def fn(q, *operands):
                dv, di = prog(q, *operands)
                return dv, sentinel_filtered_ids(dv, di)
            return fn, ops + (kp,)
        return prog, ops

    if fam == "ivf_rabitq":
        p = params or _irq.IvfRabitqSearchParams()
        if effort_scale < 1.0:
            p = dataclasses.replace(
                p, n_probes=_scaled(min(p.n_probes, index.n_lists),
                                    effort_scale, 1))
        keep_arr = as_keep_mask(filter)
        if keep_arr is not None:
            expects(keep_arr.ndim == 1,
                    "fleet filters are shared bitsets (1-D)")
            check_filter_covers_ids(keep_arr, index.ids)
        # statics resolve on the UNSHARDED index — same n_probes /
        # rerank_k the single-device searcher would pick
        n_probes, probe_block, rerank_k, _ = _irq._resolved_static(
            index, k, p)
        sl = slices if slices is not None else _irq.fleet_slices(
            index, mesh, axis=axis)
        prog = _rabitq_fleet_program(mesh, axis, int(k), n_probes,
                                     rerank_k, index.metric, probe_block,
                                     sl.lists_per, keep_arr is not None)
        ops = (sl.centroids, sl.rotation, sl.codes, sl.sabs, sl.res_norms,
               sl.code_cdots, sl.data, sl.ids, sl.counts)
        if keep_arr is not None:
            kp = jax.device_put(keep_arr, rep)

            def fn(q, *operands):
                dv, di = prog(q, *operands)
                return dv, sentinel_filtered_ids(dv, di)
            return fn, ops + (kp,)
        return prog, ops

    raise NotImplementedError(
        f"no fleet fan-out for family {fam!r} yet — supported: "
        "brute_force (exact), ivf_flat, ivf_rabitq (ROADMAP: ivf_pq / "
        "cagra fan-out)")


def _refuse_refined(index) -> None:
    from ..neighbors.refine import Refined

    expects(not isinstance(index, Refined),
            "the fleet serves no refine.Refined view: exact re-ranking is "
            "one-device only (fleet IVF-PQ is not built yet); serve it "
            "through SearchServer")


def _fleet_slices_for(index, mesh: Mesh, axis: str):
    """Family-dispatched ``fleet_slices`` for a (possibly Tombstoned)
    index view — the brute family folds the tombstone mask into its
    sharded validity mask; the IVF families carry it replicated."""
    _refuse_refined(index)
    base, keep = unwrap_tombstones(index)
    fam = family_of(base)
    if fam == "brute_force":
        return _bf.fleet_slices(base, mesh, axis=axis, filter=keep)
    if fam == "ivf_flat":
        return _ivf.fleet_slices(base, mesh, axis=axis)
    if fam == "ivf_rabitq":
        return _irq.fleet_slices(base, mesh, axis=axis)
    raise NotImplementedError(f"no fleet fan-out for family {fam!r}")


# ---------------------------------------------------------------------------
# Replica server: a SearchServer whose searchers fan out over the mesh
# ---------------------------------------------------------------------------


class _FleetReplicaServer(SearchServer):
    """A :class:`SearchServer` whose executables are mesh fan-outs.

    Overrides exactly the three seams the base class exposes:
    ``_make_parts`` (build the sharded searcher), ``_query_spec`` /
    ``_stage_queries`` (AOT executables record a replicated query
    sharding, and dispatch must stage queries with the SAME sharding —
    a plain ``device_put`` would commit to device 0 and miss the
    executable's layout).  Everything else — batching, admission,
    deadlines, the degradation ladder, metrics — is inherited, which is
    what makes per-replica degradation "the PR 10 ladder, per replica"
    rather than new machinery."""

    def __init__(self, index, k: int = 10, params=None, *, mesh: Mesh,
                 axis: str = "shard", name: str = "r0", **kw) -> None:
        self.mesh = mesh
        self.axis = axis
        self.name = str(name)
        self._slice_cache: Dict[int, Tuple[Any, Any]] = {}
        super().__init__(index, k, params, **kw)

    def _make_parts(self, index, k: int, scale: float):
        return make_fleet_searcher(index, k, self.params, mesh=self.mesh,
                                   axis=self.axis, effort_scale=scale,
                                   seed=self.seed,
                                   slices=self._slices(index))

    def _slices(self, index):
        # one slicing per generation view: ladder levels and k values
        # share the device slabs (the cache holds a strong ref, so the
        # id key stays valid while cached; two entries cover the
        # swap-prewarm window where old + new generations coexist)
        key = id(index)
        hit = self._slice_cache.get(key)
        if hit is not None and hit[0] is index:
            return hit[1]
        sl = _fleet_slices_for(index, self.mesh, self.axis)
        if len(self._slice_cache) >= 2:
            self._slice_cache.pop(next(iter(self._slice_cache)))
        self._slice_cache[key] = (index, sl)
        return sl

    def _stage_queries(self, qpad):
        return jax.device_put(qpad, NamedSharding(self.mesh, P()))

    def _query_spec(self, bucket: int, dtype):
        return jax.ShapeDtypeStruct(
            (bucket, self._dim), dtype,
            sharding=NamedSharding(self.mesh, P()))


# ---------------------------------------------------------------------------
# Router: least-loaded live replica, QueueFull spill, dead shedding
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LocalReplica:
    """In-process replica handle: the router's duck type (``name`` /
    ``alive`` / ``load()`` / ``submit()`` / ``search()``).  The
    multi-process bench driver implements the same surface over a
    socket."""

    name: str
    server: SearchServer
    alive: bool = True

    def load(self) -> int:
        return self.server.queue_depth()

    def submit(self, queries, k=None, deadline_ms=None):
        if not self.alive:
            raise ReplicaDead(f"replica {self.name} is dead")
        return self.server.submit(queries, k, deadline_ms)

    def search(self, queries, k=None, deadline_ms=None):
        if not self.alive:
            raise ReplicaDead(f"replica {self.name} is dead")
        return self.server.search(queries, k, deadline_ms)


class FleetRouter:
    """Load-balanced request placement over a replica group.

    Placement is least-queued-first over LIVE replicas;  a replica at
    queue capacity spills the request to the next candidate instead of
    rejecting it (``QueueFull`` reaches the caller only when EVERY live
    replica is saturated).  A replica that dies mid-request is marked
    dead, counted (``raft_fleet_reroutes_total``), and the request
    retries on survivors — the replica-kill drill pins "zero dropped
    in-deadline requests" on exactly this path."""

    def __init__(self, replicas: Sequence[Any], *, registry=None,
                 clock=time.monotonic) -> None:
        expects(len(replicas) >= 1, "router needs at least one replica")
        self.replicas: List[Any] = list(replicas)
        self.clock = clock
        self._lock = lockdep.lock("FleetRouter._lock")
        reg = registry if registry is not None else obs_metrics.registry()
        self.registry = reg
        self._routed = reg.counter(
            "raft_fleet_routed_total",
            "requests placed on a replica by the fleet router")
        self._spills = reg.counter(
            "raft_fleet_queue_spills_total",
            "requests spilled to another replica on QueueFull")
        self._reroutes = reg.counter(
            "raft_fleet_reroutes_total",
            "requests rerouted off a dead replica to a survivor")
        self._depth_g = reg.gauge(
            "raft_fleet_replica_queue_depth",
            "per-replica pending queue depth at last export")
        self._live_g = reg.gauge("raft_fleet_replicas_live",
                                 "replicas currently routable")

    def live(self) -> List[Any]:
        return [r for r in self.replicas if r.alive]

    def mark_dead(self, name: str) -> None:
        with self._lock:
            for r in self.replicas:
                if r.name == name:
                    r.alive = False

    def export_gauges(self) -> None:
        for r in self.replicas:
            try:
                depth = float(r.load()) if r.alive else 0.0
            except Exception:
                depth = 0.0
            self._depth_g.set(depth, replica=r.name)
        self._live_g.set(float(len(self.live())))

    def _candidates(self) -> List[Any]:
        live = self.live()
        if not live:
            raise ReplicaDead("no live replicas")
        # snapshot loads once so one placement sorts one consistent view
        return sorted(live, key=lambda r: (r.load(), r.name))

    def submit(self, queries, k=None, deadline_ms=None):
        """Place one request; returns ``(future, replica)``.  Spills on
        ``QueueFull``, sheds dead replicas, raises ``QueueFull`` only
        when every live replica is saturated."""
        saturated = None
        for r in self._candidates():
            try:
                fut = r.submit(queries, k, deadline_ms)
                self._routed.inc(replica=r.name)
                return fut, r
            except QueueFull as e:
                saturated = e
                self._spills.inc(replica=r.name)
                continue
            except ReplicaDead:
                self.mark_dead(r.name)
                self._reroutes.inc(replica=r.name)
                continue
        if saturated is not None:
            raise saturated
        raise ReplicaDead("no live replicas")

    def search(self, queries, k=None, deadline_ms=None):
        """Synchronous search with dead-replica retry: each attempt runs
        on the current least-loaded live replica; a replica that dies
        mid-flight is marked dead and the request retries on a survivor
        (each attempt re-spans the full deadline — the caller's deadline
        governs queue wait within a replica, not the retry budget)."""
        last: Optional[Exception] = None
        for _ in range(max(1, len(self.replicas))):
            saturated = None
            placed = False
            for r in self._candidates():
                try:
                    out = r.search(queries, k, deadline_ms)
                    placed = True
                except QueueFull as e:
                    saturated = e
                    self._spills.inc(replica=r.name)
                    continue
                except ReplicaDead as e:
                    self.mark_dead(r.name)
                    self._reroutes.inc(replica=r.name)
                    last = e
                    break                      # re-sort and retry
                self._routed.inc(replica=r.name)
                return out
            if not placed and saturated is not None:
                raise saturated
            if not placed and last is None:
                raise ReplicaDead("no live replicas")
        raise last if last is not None else ReplicaDead("no live replicas")


# ---------------------------------------------------------------------------
# Fleet durability: per-shard stores, anti-affinity standbys, promotion
# ---------------------------------------------------------------------------


def shard_sub_indexes(index, n_shards: int) -> List[Any]:
    """Slice an index into ``n_shards`` host-side sub-indexes matching
    the fan-out's contiguous layout — shard *s* of the serving mesh owns
    exactly ``sub_indexes[s]``'s rows/lists.  These are what each
    shard's :class:`~raft_tpu.neighbors.wal.DurableStore` snapshots: a
    shard recovers (or a standby promotes) from state that maps 1:1 onto
    its slice of the serving operands."""
    index, _ = unwrap_tombstones(index)
    fam = family_of(index)
    n_shards = int(n_shards)
    expects(n_shards >= 1, "need at least one shard")

    if fam == "brute_force":
        y = np.asarray(index)
        n = y.shape[0]
        expects(n >= n_shards,
                f"{n} rows cannot populate {n_shards} shards")
        per = (n + n_shards - 1) // n_shards
        return [y[s * per:min(n, (s + 1) * per)] for s in range(n_shards)]

    def _pad(x, fill, pad):
        x = np.asarray(x)
        if not pad:
            return x
        shape = (pad,) + x.shape[1:]
        return np.concatenate([x, np.full(shape, fill, x.dtype)], axis=0)

    # IVF families: each shard's store is a SELF-CONTAINED sub-index over
    # its own list slice — centroids included (the build_sharded model:
    # shard s owns lists [s*lp, (s+1)*lp)).  A durable extend on a shard
    # therefore assigns into that shard's lists only, which is exactly
    # what the contiguous fan-out layout expects back at reslice time.
    # The list-axis pad (far-but-finite centroid, empty list) rides into
    # the last shard's sub-index as a never-chosen empty list.
    L = index.n_lists
    lp = (L + n_shards - 1) // n_shards
    pad = lp * n_shards - L
    cen = _pad(index.centroids, _ivf._FLEET_CENTROID_PAD, pad)
    sl = lambda x, s: x[s * lp:(s + 1) * lp]
    if fam == "ivf_flat":
        data = _pad(index.data, 0, pad)
        ids = _pad(index.ids, -1, pad)
        counts = _pad(index.counts, 0, pad)
        norms = _pad(index.norms, 0, pad)
        return [
            _ivf.IvfFlatIndex(sl(cen, s), sl(data, s), sl(ids, s),
                              sl(counts, s), sl(norms, s), index.metric)
            for s in range(n_shards)]
    if fam == "ivf_rabitq":
        rot = np.asarray(index.rotation)
        codes = _pad(index.codes, 0, pad)
        sabs = _pad(index.sabs, 0, pad)
        rn = _pad(index.res_norms, 0, pad)
        cdots = _pad(index.code_cdots, 0, pad)
        data = _pad(index.data, 0, pad)
        ids = _pad(index.ids, -1, pad)
        counts = _pad(index.counts, 0, pad)
        return [
            _irq.IvfRabitqIndex(sl(cen, s), rot, sl(codes, s), sl(sabs, s),
                                sl(rn, s), sl(cdots, s), sl(data, s),
                                sl(ids, s), sl(counts, s), index.metric)
            for s in range(n_shards)]
    raise NotImplementedError(
        f"no per-shard durability slicing for family {fam!r}")


@dataclasses.dataclass
class ShardDurability:
    """One shard's durability column: primary store + WAL, the
    multi-follower shipper, and its anti-affinity standbys."""

    shard: int
    assignment: Assignment
    store: DurableStore
    shipper: Optional[LogShipper]
    standbys: Tuple[StandbyReplica, ...]


class FleetDurability:
    """The PR 15 durability stack, fleet-wide.

    Each shard gets a primary :class:`DurableStore` (own WAL + snapshot
    lineage under ``<root>/shardNNN/primary``) and one
    :class:`LogShipper` fanning its log out to the shard's standbys —
    placed by :func:`.placement.plan_placement` so no standby shares a
    host with its primary.  :meth:`pump` drives heartbeats, shipping,
    and standby applies deterministically (tests; a deployment calls
    ``start()`` on the shippers/standbys instead); :meth:`promote_expired`
    is the fleet-level failover sweep — any shard whose primary lease
    expired promotes its first standby through the shared
    :class:`~raft_tpu.serve.replication.EpochFence` protocol."""

    def __init__(self, sub_indexes: Sequence[Any], root, *,
                 plan: PlacementPlan,
                 config: Optional[ReplicationConfig] = None,
                 registry=None, clock=time.monotonic) -> None:
        expects(len(sub_indexes) == len(plan.assignments),
                f"{len(sub_indexes)} sub-indexes for "
                f"{len(plan.assignments)} placement assignments")
        plan.validate()
        self.plan = plan
        self.root = os.fspath(root)
        self.config = config or ReplicationConfig()
        self.clock = clock
        self.promoted: List[int] = []
        shards: List[ShardDurability] = []
        for a in plan.assignments:
            base = os.path.join(self.root, f"shard{a.shard:03d}")
            store = DurableStore.create(os.path.join(base, "primary"),
                                        sub_indexes[a.shard], clock=clock)
            links: List[Any] = []
            standbys: List[StandbyReplica] = []
            for host in a.standbys:
                t_primary, t_standby = QueuePair.create()
                links.append(t_primary)
                standbys.append(StandbyReplica(
                    os.path.join(base, f"standby-{host}"), t_standby,
                    config=self.config, registry=registry,
                    node_id=f"shard{a.shard}-{host}", clock=clock))
            shipper = LogShipper(store, links, config=self.config,
                                 node_id=f"shard{a.shard}-primary",
                                 registry=registry,
                                 clock=clock) if links else None
            shards.append(ShardDurability(a.shard, a, store, shipper,
                                          tuple(standbys)))
        self.shards = shards
        self.pump()           # serve the hellos: snapshot bootstraps

    def pump(self, timeout: float = 0.0) -> int:
        """One deterministic replication round for every shard:
        heartbeat + ship + standby apply + ack collection.  Returns the
        number of messages processed."""
        n = 0
        for sh in self.shards:
            if sh.shipper is not None:
                sh.shipper.beat()
                n += sh.shipper.pump(timeout)
            for sb in sh.standbys:
                n += sb.poll()
        for sh in self.shards:   # collect the acks the applies produced
            if sh.shipper is not None:
                n += sh.shipper.pump(0.0)
        return n

    def promote_expired(self, now: Optional[float] = None) -> List[int]:
        """Fleet failover sweep: every shard whose primary lease has
        expired promotes its first (placement-ordered) bootstrapped
        standby.  Returns the shard ids promoted this sweep."""
        done: List[int] = []
        for sh in self.shards:
            for sb in sh.standbys:
                if sb.store is None or sb.promoted:
                    continue
                if not sb.primary_alive(now):
                    sb.promote()
                    done.append(sh.shard)
                break            # only the first standby per sweep
        self.promoted.extend(done)
        return done

    def lag(self) -> Dict[int, Dict[str, int]]:
        """Per-shard follower watermark lag (primary lsn − acked)."""
        out: Dict[int, Dict[str, int]] = {}
        for sh in self.shards:
            lsn = sh.store.wal_lsn
            out[sh.shard] = {fid: max(0, lsn - acked)
                             for fid, acked in sh.store.followers().items()}
        return out

    def stop(self) -> None:
        for sh in self.shards:
            if sh.shipper is not None:
                sh.shipper.stop()
            for sb in sh.standbys:
                sb.stop()


# ---------------------------------------------------------------------------
# FleetServer: bootstrap + replicas + router + durability, one object
# ---------------------------------------------------------------------------


class FleetServer:
    """Pod-scale serving: a replica group of mesh fan-out servers.

    Bootstrap: pass a ``mesh`` (tests), or let the constructor call
    :func:`~raft_tpu.comms.bootstrap.init_distributed` (which validates
    ``axis_shape`` against the visible devices).  Unless
    ``selftest=False``, the :mod:`~raft_tpu.comms.selftest` battery runs
    over the bootstrapped communicator first and a broken collective
    REFUSES to serve — a fleet that merges top-k through a faulty
    all-gather would return wrong neighbors with healthy-looking
    latency.

    ``n_replicas`` full :class:`SearchServer` replicas share the mesh
    (time-multiplexed on one process's devices here; one process per
    replica in the multi-process bench driver).  Each replica keeps its
    own admission controller, degradation ladder, and metrics registry —
    degradation is per-replica state, so one overloaded replica degrades
    while its peers keep serving at full effort.  The
    :class:`FleetRouter` in front places requests least-loaded-first and
    sheds from dead replicas to survivors.

    Durability (:meth:`attach_durability`) slices the index per shard
    and runs the PR 15 store/WAL/standby stack under an anti-affinity
    placement; :meth:`promote_expired` is the lease-expiry failover
    sweep.
    """

    def __init__(self, index, k: int = 10, params=None, *,
                 mesh: Optional[Mesh] = None, axis: str = "shard",
                 n_replicas: int = 1,
                 config: Optional[ServerConfig] = None,
                 comms=None, selftest: bool = True, seed: int = 0,
                 clock=time.monotonic, **server_kw) -> None:
        from ..comms import Comms
        from ..comms.bootstrap import init_distributed, verify_comms

        if mesh is None:
            if comms is None:
                comms = init_distributed(axis_names=(axis,))
            mesh = comms.mesh
        elif comms is None:
            comms = Comms(mesh, axis)
        expects(axis in mesh.axis_names,
                f"axis {axis!r} not in mesh axes {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.comms = comms
        self.n_shards = int(mesh.shape[axis])
        expects(n_replicas >= 1, "need at least one replica")
        # startup gate: don't take traffic over a broken collective
        self.selftest_results = verify_comms(comms) if selftest else None
        self._index = index
        self.k = int(k)
        self.params = params
        self.registry = obs_metrics.MetricRegistry()
        self.registry.gauge("raft_fleet_shards",
                            "index shards in the fan-out").set(
                                float(self.n_shards))
        self.replicas: List[LocalReplica] = []
        for r in range(int(n_replicas)):
            name = f"r{r}"
            srv = _FleetReplicaServer(index, k, params, mesh=mesh,
                                      axis=axis, name=name, config=config,
                                      seed=seed + r, clock=clock,
                                      **server_kw)
            self.replicas.append(LocalReplica(name, srv))
        self.router = FleetRouter(self.replicas, registry=self.registry,
                                  clock=clock)
        self.durability: Optional[FleetDurability] = None

    # -- lifecycle ----------------------------------------------------

    def start(self, warmup: bool = True) -> "FleetServer":
        for r in self.replicas:
            r.server.start(warmup=warmup)
        return self

    def stop(self, timeout: Optional[float] = 30.0) -> None:
        for r in self.replicas:
            r.server.stop(timeout=timeout)
        if self.durability is not None:
            self.durability.stop()

    def warmup(self) -> int:
        return sum(r.server.warmup() for r in self.replicas)

    def __enter__(self) -> "FleetServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- serving ------------------------------------------------------

    def submit(self, queries, k=None, deadline_ms=None):
        fut, _ = self.router.submit(queries, k, deadline_ms)
        return fut

    def search(self, queries, k=None, deadline_ms=None):
        return self.router.search(queries, k, deadline_ms)

    def step(self, now: Optional[float] = None) -> int:
        """Manual-drive mode: one batch step on every live replica."""
        return sum(r.server.step(now) for r in self.router.live())

    def kill_replica(self, name: str) -> None:
        """Drill hook: mark a replica dead (the router sheds to
        survivors) and stop its dispatch thread."""
        self.router.mark_dead(name)
        for r in self.replicas:
            if r.name == name:
                r.server.stop(timeout=5.0)

    # -- durability ---------------------------------------------------

    def attach_durability(self, root, hosts: Sequence[str], *,
                          n_standbys: int = 1,
                          config: Optional[ReplicationConfig] = None
                          ) -> FleetDurability:
        """Give every shard a durable store + WAL and ``n_standbys``
        warm standbys placed under anti-affinity over ``hosts``."""
        plan = plan_placement(self.n_shards, hosts,
                              n_standbys=n_standbys)
        subs = shard_sub_indexes(self._index, self.n_shards)
        self.durability = FleetDurability(
            subs, root, plan=plan, config=config, registry=self.registry,
            clock=self.replicas[0].server.clock)
        return self.durability

    def promote_expired(self, now: Optional[float] = None) -> List[int]:
        expects(self.durability is not None,
                "attach_durability() first — nothing to promote")
        return self.durability.promote_expired(now)

    # -- observability ------------------------------------------------

    def prometheus_text(self) -> str:
        """One scrape body: fleet-level families (router counters,
        shard/replica gauges) plus every replica's serving families
        disambiguated by an injected ``replica`` label."""
        self.router.export_gauges()
        per_replica = {r.name: r.server.metrics.registry
                       for r in self.replicas}
        return render(self.registry) + render_labeled(per_replica,
                                                      label="replica")

    def metrics_snapshot(self) -> dict:
        return {
            "shards": self.n_shards,
            "replicas_live": len(self.router.live()),
            "replicas": {r.name: r.server.metrics_snapshot()
                         for r in self.replicas},
        }

    def describe(self) -> str:
        """Operator-facing topology summary (runbook output)."""
        lines = [f"fleet: {self.n_shards} shards over mesh "
                 f"{dict(self.mesh.shape)} (axis {self.axis!r}), "
                 f"{len(self.replicas)} replicas "
                 f"({len(self.router.live())} live)"]
        for r in self.replicas:
            state = "live" if r.alive else "dead"
            lines.append(f"  replica {r.name}: {state}, "
                         f"queue={r.load() if r.alive else '-'}")
        if self.durability is not None:
            lines.append(self.durability.plan.describe())
        return "\n".join(lines)
