"""Out-of-core cooperative search tier — codes in device memory, raw
rows on host, only survivors cross the bus.

The FusionANNS split (PAPERS.md) on top of the PR 13 RaBitQ tier: the
device keeps ONLY the packed 1-bit code slabs + centroids (~20 B/vec at
d=64, ~13× under the raw f32 slab), while the full-precision vectors
live host-side as an mmap-backed sharded store
(:class:`raft_tpu.io.shards.ShardedVectorStore`).  Search is a
three-phase cooperative loop per query chunk:

1. **estimate** (device) — the probe-blocked RaBitQ estimator scan
   (:func:`~raft_tpu.neighbors.ivf_rabitq._estimate_survivors`, shared
   code, bit-identical candidates) keeps the top-``rerank_k`` survivor
   ids;
2. **fetch** (host) — survivor rows gather from the sharded store
   grouped by shard (native threaded pread / mmap fallback), staged
   through :class:`~raft_tpu.core.host_memory.HostBufferPool` buffers so
   the steady-state loop allocates nothing;
3. **rerank** (device) — ONE explicit ``device_put`` of the
   ``[chunk, rerank_k, d]`` slab (never the full database), then the
   exact re-score through :func:`~raft_tpu.ops.blocked_scan.l2_rescorer`
   in brute accumulation order — ``rerank_k = n`` is bit-identical
   (values AND ids) to ``brute_force.knn``, the same contract ivf_rabitq
   pinned (tests/test_ooc.py).

With ``overlap=True`` (default) the chunks ride
:func:`~raft_tpu.core.double_buffer.device_prefetch`: chunk t+1's
estimate + host fetch + slab put run while chunk t's rerank executes, so
the bus transfer hides behind device compute (TPU-KNN's overlapped
model).  Peak device memory is ``codes_bytes + slab_budget`` by
construction — every transfer funnels through one accounting seam
(:func:`transfer_stats`), which the tier-1 boundedness test audits under
``jax.transfer_guard("disallow")``.

Build streams chunks through the PR 4 pipelined path and writes the
shard store as it goes: peak host memory is bounded by ``chunk_rows``
(+ the fixed OS page cache behind the mmaps), peak device memory by the
code slabs + two staged chunks.  ``save()`` / ``open()`` persist a
format-v5 manifest-directory layout (device bundle with per-array CRCs
+ the shard store with per-shard CRCs); ``open()`` maps shards lazily,
so opening a store costs metadata only.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import lockdep, tracing
from ..core.array import wrap_array
from ..core.double_buffer import device_prefetch
from ..core.errors import expects
from ..core.host_memory import default_host_pool
from ..distance.pairwise import sq_l2
from ..io.shards import ShardedVectorStore, ShardWriter
from ..ops import blocked_scan as _scan
from . import ivf_rabitq as _rq

__all__ = [
    "OocIndexParams",
    "OocSearchParams",
    "OocIndex",
    "build",
    "build_chunked",
    "search",
    "searcher",
    "save",
    "open",
    "verify",
    "transfer_stats",
    "reset_transfer_stats",
]

_META = "meta.json"
_DEVICE_DIR = "device"
_SHARDS_DIR = "shards"
_FORMAT_VERSION = 5
_ARRAY_FIELDS = ("centroids", "rotation", "codes", "sabs", "res_norms",
                 "code_cdots", "ids", "counts")


@dataclasses.dataclass(frozen=True)
class OocIndexParams:
    """Build configuration.  The device-side knobs mirror
    :class:`~raft_tpu.neighbors.ivf_rabitq.IvfRabitqIndexParams` (same
    coarse quantizer, same encoder); ``rows_per_shard`` fixes the
    host-store shard granularity (global row id = shard·rows_per_shard
    + local row, so the store IS the id space)."""

    n_lists: int = 1024
    metric: str = "sqeuclidean"  # sqeuclidean | euclidean | inner_product
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.1
    list_cap_ratio: float = 2.0
    rows_per_shard: int = 1 << 20
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class OocSearchParams:
    n_probes: int = 32
    # exact-rerank candidate count (estimator survivors fetched from the
    # host store per query).  0 = auto (ivf_rabitq's max(32, 8·k)).  rerank_k = index.size is bit-identical to
    # brute force.  Unlike ivf_rabitq this is ALSO the bus knob: each
    # query moves rerank_k · d · itemsize bytes up per search.
    rerank_k: int = 0
    query_chunk: int = 1024   # queries per cooperative step (cap)
    probe_block: int = 0      # 0 = auto; bit-identical for every value
    # hard cap on ONE staged rerank slab, in bytes: the query chunk
    # shrinks until chunk · rerank_k · d · itemsize fits.  Peak device
    # memory = codes slabs + (overlap+1) slabs under this budget — the
    # knob that makes "fits on device" a configuration, not an accident.
    slab_budget: int = 256 << 20
    # host-store read granularity: survivor gathers fetch dense spans up
    # to fetch_batch rows through one threaded pread (pooled staging
    # buffers are keyed by this, so it also fixes the pool working set)
    fetch_batch: int = 8192
    # double-buffer the cooperative loop: stage chunk t+1 (estimate +
    # host fetch + slab put) while chunk t reranks on device.  Results
    # are bit-identical either way — pure overlap knob (the A/B in
    # bench/ooc_bench.py)
    overlap: bool = True


@dataclasses.dataclass(frozen=True)
class OocIndex:
    """Device half = RaBitQ code slabs (+ centroids, rotation, per-vector
    scalars, ids, counts); host half = the sharded raw-row store.  NOT a
    pytree — the store is host state; the search loop passes the device
    fields into jit explicitly."""

    centroids: jax.Array    # [L, d]
    rotation: jax.Array     # [d, d] f32 orthonormal
    codes: jax.Array        # [L, cap, ceil(d/8)] uint8 packed signs
    sabs: jax.Array         # [L, cap] f32
    res_norms: jax.Array    # [L, cap] f32
    code_cdots: jax.Array   # [L, cap] f32
    ids: jax.Array          # [L, cap] int32 (== store row; -1 pad)
    counts: jax.Array       # [L] int32
    store: ShardedVectorStore
    metric: str = "sqeuclidean"

    @property
    def n_lists(self) -> int:
        return int(self.codes.shape[0])

    @property
    def list_cap(self) -> int:
        return int(self.codes.shape[1])

    @property
    def dim(self) -> int:
        return int(self.rotation.shape[0])

    @property
    def size(self) -> int:
        return int(jnp.sum(self.counts))  # jaxlint: disable=JX01 size is a host-facing API scalar, not on the search path

    @property
    def resident_bytes(self) -> int:
        """Device-resident bytes: everything search keeps in accelerator
        memory (the codes tier — NOT the raw rows)."""
        return sum(int(np.dtype(getattr(self, f).dtype).itemsize)
                   * int(np.prod(getattr(self, f).shape))
                   for f in _ARRAY_FIELDS)

    @property
    def host_bytes(self) -> int:
        """Host-side bytes of the full-precision row store."""
        return int(self.store.nbytes)


# ---------------------------------------------------------------------------
# Transfer accounting — the boundedness seam.  EVERY host→device transfer
# the search loop performs goes through _stage_to_device, so a test (or a
# budget audit) can assert that no staged put ever exceeds slab_budget —
# i.e. there is no hidden full-slab device_put anywhere in the tier.
# ---------------------------------------------------------------------------

_transfer_lock = lockdep.lock("ooc._transfer_lock")
_transfer = {"puts": 0, "put_bytes": 0, "max_put_bytes": 0,  # guarded_by: _transfer_lock
             "fetch_bytes": 0}


def transfer_stats() -> dict:
    """Snapshot of the search loop's transfer accounting: ``puts`` /
    ``put_bytes`` / ``max_put_bytes`` (host→device stages) and
    ``fetch_bytes`` (host store → staging buffers)."""
    with _transfer_lock:
        return dict(_transfer)


def reset_transfer_stats() -> None:
    with _transfer_lock:
        for k in _transfer:
            _transfer[k] = 0


def _stage_to_device(host_arr):
    """The ONLY host→device path in the search loop: explicit
    ``device_put`` (guard-clean under ``transfer_guard("disallow")``)
    plus byte accounting."""
    nb = int(host_arr.nbytes)
    with _transfer_lock:
        _transfer["puts"] += 1
        _transfer["put_bytes"] += nb
        _transfer["max_put_bytes"] = max(_transfer["max_put_bytes"], nb)
    return jax.device_put(host_arr)


def _note_fetch(nbytes: int) -> None:
    with _transfer_lock:
        _transfer["fetch_bytes"] += int(nbytes)
    from ..obs.metrics import registry

    registry().counter(
        "raft_ooc_rerank_fetch_bytes_total",
        "bytes gathered from the host shard store for exact rerank",
    ).inc(n=float(nbytes))


# ---------------------------------------------------------------------------
# Build — the PR 4 pipelined streaming path, writing shards as it goes.
# ---------------------------------------------------------------------------


def _ooc_step_impl(slabs, counts, centroids, rotation, rotc, xc, idc, *,
                   n_lists: int, cap: int):
    """The ivf_rabitq chunk step minus the raw-data slab: masked capped
    assignment + RaBitQ encode + scatter-append over FIVE payload slabs
    (codes, sabs, rn2, cs, ids).  The raw rows never touch the device on
    the packing side — they stream to the shard store host-side."""
    from ..cluster.kmeans import _capped_assign_impl
    from ._packing import _scatter_append_impl

    valid = idc >= 0
    labels, _ = _capped_assign_impl(xc, centroids, cap - counts, valid)
    codes, sabs, rn2, cs = _rq._encode(xc, labels, centroids, rotation,
                                       rotc)
    return _scatter_append_impl(slabs, counts, labels,
                                (codes, sabs, rn2, cs, idc),
                                n_lists=n_lists, cap=cap)


_ooc_chunk_step = partial(jax.jit, static_argnames=("n_lists", "cap"),
                          donate_argnums=(0, 1))(_ooc_step_impl)


def _empty_slabs(n_lists: int, cap: int, d: int):
    from ._packing import device_full

    db = -(-d // 8)
    return (device_full((n_lists, cap, db), 0, jnp.uint8),
            device_full((n_lists, cap), 0.0, jnp.float32),
            device_full((n_lists, cap), 0.0, jnp.float32),
            device_full((n_lists, cap), 0.0, jnp.float32),
            device_full((n_lists, cap), -1, jnp.int32))


def _stream_pipelined(dataset, centroids, rotation, p: OocIndexParams,
                      n: int, cap: int, chunk_rows: int, writer, dtype,
                      heartbeat=None):
    """Pipelined chunk engine: background host reads
    (:func:`~._packing.prefetch_chunks`), the shard write on the staging
    thread (so disk IO overlaps device compute), fixed-shape padded
    device staging one chunk ahead, and the fused donated chunk step —
    one executable, one dispatch per chunk."""
    from ._packing import device_full, prefetch_chunks

    d = dataset.shape[1]
    slabs = _empty_slabs(p.n_lists, cap, d)
    counts = device_full((p.n_lists,), 0, jnp.int32)
    rotc = _rq._rotated_centroids(centroids, rotation)
    store_dtype = writer.dtype if writer is not None else None

    def stage(item):
        lo, hi, xc_h, idc_h = item
        xc_h = np.asarray(xc_h)
        if dtype is not None:
            xc_h = xc_h.astype(np.dtype(str(dtype)), copy=False)
        if writer is not None:
            writer.append(xc_h.astype(store_dtype, copy=False))
        idc_h = np.asarray(idc_h, np.int32)
        rows = hi - lo
        if rows < chunk_rows:  # pad the tail to the one fixed shape
            xp = np.zeros((chunk_rows, d), xc_h.dtype)
            xp[:rows] = xc_h
            ip = np.full((chunk_rows,), -1, np.int32)
            ip[:rows] = idc_h
            xc_h, idc_h = xp, ip
        return lo, hi, jax.device_put(xc_h), jax.device_put(idc_h)

    for lo, hi, xc, idc in device_prefetch(
            prefetch_chunks(dataset, chunk_rows, None), stage):
        slabs, counts = _ooc_chunk_step(slabs, counts, centroids, rotation,
                                        rotc, xc, idc, n_lists=p.n_lists,
                                        cap=cap)
        if heartbeat is not None:
            heartbeat(hi)
    return slabs, counts


def _stream_perop(dataset, centroids, rotation, p: OocIndexParams, n: int,
                  cap: int, chunk_rows: int, writer, dtype):
    """Reference per-op chunk loop (blocking H2D, separate dispatches,
    sequential shard writes) — the bit-parity oracle for the pipelined
    engine and the A/B baseline of ``bench/build_throughput.py``."""
    from ..cluster.kmeans import capped_assign_room
    from ._packing import prefetch_chunks, scatter_append

    d = dataset.shape[1]
    db = -(-d // 8)
    slabs = (jnp.zeros((p.n_lists, cap, db), jnp.uint8),
             jnp.zeros((p.n_lists, cap), jnp.float32),
             jnp.zeros((p.n_lists, cap), jnp.float32),
             jnp.zeros((p.n_lists, cap), jnp.float32),
             jnp.full((p.n_lists, cap), -1, jnp.int32))
    counts = jnp.zeros((p.n_lists,), jnp.int32)
    rotc = _rq._rotated_centroids(centroids, rotation)
    for lo, hi, xc_h, idc_h in prefetch_chunks(dataset, chunk_rows, None):
        if writer is not None:
            writer.append(np.asarray(xc_h).astype(writer.dtype, copy=False))
        xc = jnp.asarray(xc_h, dtype)
        idc = jnp.asarray(idc_h, jnp.int32)
        labels, _ = capped_assign_room(xc, centroids, cap - counts)
        codes, sabs, rn2, cs = _rq._encode(xc, labels, centroids, rotation,
                                           rotc)
        slabs, counts = scatter_append(
            slabs, counts, labels, (codes, sabs, rn2, cs, idc),
            n_lists=p.n_lists, cap=cap)
    return slabs, counts


def _build_with(stream, dataset, params, store_path, chunk_rows):
    from .ivf_flat import _coarse_train_chunked
    from ._packing import build_heartbeat, resolve_chunk_rows

    p = params or OocIndexParams()
    n, d = dataset.shape
    expects(p.n_lists >= 1 and p.n_lists <= n, "n_lists out of range")
    expects(p.metric in ("sqeuclidean", "euclidean", "inner_product"),
            f"unsupported metric {p.metric!r}")
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))
    dtype = jnp.asarray(np.asarray(dataset[:1])).dtype
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_rabitq")

    centroids = _coarse_train_chunked(dataset, p, n)
    rotation = _rq._rotation(d, p.seed)
    writer = ShardWriter(store_path, d, np.dtype(str(dtype)),
                         p.rows_per_shard)
    hb = (build_heartbeat("ooc.build_chunked", n)
          if stream is _stream_pipelined else None)
    kwargs = {"heartbeat": hb} if hb is not None else {}
    (codes, sabs, rn2, cs, ids_slab), counts = stream(
        dataset, centroids, rotation, p, n, cap, chunk_rows, writer, dtype,
        **kwargs)
    store = writer.close()
    return OocIndex(centroids, rotation, codes, sabs, rn2, cs, ids_slab,
                    counts, store, p.metric)


@tracing.annotate("ooc.build_chunked")
def build_chunked(dataset, params: Optional[OocIndexParams] = None, *,
                  store_path: str, chunk_rows: int = 0,
                  res=None) -> OocIndex:
    """Streaming out-of-core build: the dataset flows once through the
    fused slab-donating chunk step (device side: assign + encode +
    scatter over the FIVE code slabs) while the same host chunks append
    to the shard store at ``store_path``.  Peak host memory is bounded
    by ``chunk_rows``; peak device memory by the code slabs + two staged
    chunks — the raw rows never materialize on device.  Store row ids
    are positional (row ``i`` of ``dataset`` = store row ``i``), which
    is the id space the search tier's survivors address."""
    return _build_with(_stream_pipelined, dataset, params, store_path,
                       chunk_rows)


@tracing.annotate("ooc.build")
def build(dataset, params: Optional[OocIndexParams] = None, *,
          store_path: str, chunk_rows: int = 0, res=None) -> OocIndex:
    """Alias of :func:`build_chunked` — the out-of-core family has no
    resident one-shot path by design (an index whose point is not
    holding the rows should not require holding the rows to build)."""
    return build_chunked(dataset, params, store_path=store_path,
                         chunk_rows=chunk_rows, res=res)


def _build_chunked_perop(dataset, params: Optional[OocIndexParams] = None,
                         *, store_path: str,
                         chunk_rows: int = 0) -> OocIndex:
    """:func:`build_chunked` on the reference per-op loop — parity
    oracle / A/B baseline; not public API."""
    return _build_with(_stream_perop, dataset, params, store_path,
                       chunk_rows)


# ---------------------------------------------------------------------------
# Search — the three-phase cooperative loop.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_probes", "rerank_k", "metric",
                                   "probe_block"))
def _survivors_impl(centroids, rotation, codes, sabs, res_norms,
                    code_cdots, ids, counts, q, n_probes: int,
                    rerank_k: int, metric: str, keep=None,
                    probe_block: int = 1):
    qf = q.astype(jnp.float32)
    cd = sq_l2(q, centroids)
    _, probes = jax.lax.top_k(-cd, n_probes)
    bv, bi, _ = _rq._estimate_survivors(qf, cd, centroids, rotation, codes,
                                        sabs, res_norms, code_cdots, ids,
                                        counts, probes, rerank_k, metric,
                                        keep, probe_block)
    return bv, bi


def _rerank_core(slab, bv, bi, q, k: int, metric: str):
    """Exact re-score of the fetched survivor slab in brute accumulation
    order: the slab flattens to ``[nq·rerank_k, d]`` and re-scores
    through the SAME stored-norm-free :func:`~raft_tpu.ops.blocked_scan
    .l2_rescorer` seam ivf_rabitq uses — identical algebra on identical
    row values, which is what carries the ``rerank_k = n`` bitwise
    contract across the host round-trip."""
    nq, rk = bi.shape
    flat = slab.reshape(nq * rk, slab.shape[-1])
    qf = q.astype(jnp.float32)
    qn = _scan.row_sq_norms(qf)
    rescore = _scan.l2_rescorer(flat, q, qn, metric)
    ptr = jnp.arange(nq * rk, dtype=jnp.int32).reshape(nq, rk)
    dist = rescore(ptr, bi)
    dist = jnp.where(jnp.isfinite(bv) & (bi >= 0), dist, jnp.inf)
    dv, di = _scan.ranked_finish(dist, bi, k)
    if metric == "euclidean":
        dv = jnp.sqrt(jnp.maximum(dv, 0.0))
    elif metric == "inner_product":
        dv = -dv
    return dv, di


_rerank_impl = partial(jax.jit, static_argnames=("k", "metric"))(
    _rerank_core)


def _resolved_static(index: OocIndex, k: int, p: OocSearchParams):
    """(n_probes, probe_block, rerank_k) — ivf_rabitq's resolution
    verbatim (same estimator scan)."""
    return _rq._resolved_static(index, k, p)


def _resolve_query_chunk(p: OocSearchParams, nq: int, rerank_k: int,
                         d: int, itemsize: int) -> int:
    """Queries per cooperative step: ``query_chunk`` capped so one
    staged rerank slab (``chunk · rerank_k · d · itemsize``) fits
    ``slab_budget``.  Host-int arithmetic only."""
    per_q = int(rerank_k) * int(d) * int(itemsize)
    expects(int(p.slab_budget) >= per_q,
            f"slab_budget ({int(p.slab_budget)} B) is below one query's "
            f"rerank slab ({per_q} B = rerank_k·d·itemsize); raise "
            "slab_budget or lower rerank_k")
    budget_rows = int(p.slab_budget) // per_q
    return max(1, min(int(nq), int(p.query_chunk) or 1024, budget_rows))


@tracing.annotate("ooc.search")
def search(index: OocIndex, queries, k: int,
           params: Optional[OocSearchParams] = None, *, filter=None,
           res=None) -> Tuple[jax.Array, jax.Array]:
    """Out-of-core kNN with EXACT returned values.  Per query chunk:
    device estimator scan → host gather of the top-``rerank_k`` survivor
    rows from the shard store → one bounded slab put → exact rerank.
    ``filter``: optional shared (1-D) bitset prefilter by source id —
    per-query bitmaps don't ride the cooperative loop's fixed operand
    shapes."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           sentinel_filtered_ids)
    from ..obs.spans import recorder

    p = params or OocSearchParams()
    expects(k >= 1, "k must be >= 1")
    q = wrap_array(queries, ndim=2, name="queries")
    expects(q.shape[1] == index.dim, "query dim mismatch")
    n_probes, probe_block, rerank_k = _resolved_static(index, k, p)
    keep = as_keep_mask(filter)
    if keep is not None:
        expects(keep.ndim == 1,
                "ooc filters are shared bitsets (1-D); per-query bitmaps "
                "can't ride the cooperative loop's fixed operand shapes")
        check_filter_covers_ids(keep, index.ids)
    store = index.store
    d = index.dim
    itemsize = store.dtype.itemsize
    nq = int(q.shape[0])
    chunk = _resolve_query_chunk(p, nq, rerank_k, d, itemsize)
    pool = default_host_pool(res)
    rec = recorder()
    qh = np.asarray(jax.device_get(q))  # jaxlint: disable=JX01 the cooperative tier is host-driven by design: queries chunk host-side, survivors fetch host-side
    bounds = [(lo, min(nq, lo + chunk)) for lo in range(0, nq, chunk)]
    metric = index.metric

    def stage(b):
        lo, hi = b
        rows = hi - lo
        qbuf = pool.acquire((chunk, d), qh.dtype)
        qbuf[:rows] = qh[lo:hi]
        if rows < chunk:
            qbuf[rows:] = 0
        qd = _stage_to_device(qbuf)
        with rec.span("ooc.estimate", nq=rows):
            bv, bi = _survivors_impl(
                index.centroids, index.rotation, index.codes, index.sabs,
                index.res_norms, index.code_cdots, index.ids, index.counts,
                qd, n_probes, rerank_k, metric, keep, probe_block)
            bi_h = np.asarray(jax.device_get(bi))  # jaxlint: disable=JX01 phase boundary: survivor ids must reach the host to address the shard store
        with rec.span("ooc.fetch", rows=int(bi_h.size)):
            slab_h = pool.acquire((chunk, rerank_k, d), store.dtype)
            store.gather(bi_h, out=slab_h.reshape(chunk * rerank_k, d),
                         fetch_batch=int(p.fetch_batch), pool=pool)
            _note_fetch(slab_h.nbytes)
        slab = _stage_to_device(slab_h)
        return lo, hi, qd, slab, bv, bi, (qbuf, slab_h)

    it = (device_prefetch(bounds, stage) if p.overlap and len(bounds) > 1
          else (stage(b) for b in bounds))
    outs = []
    pending = None  # (prev chunk's dv, its pooled host buffers)
    for lo, hi, qd, slab, bv, bi, bufs in it:
        with rec.span("ooc.rerank", nq=hi - lo):
            dv, di = _rerank_impl(slab, bv, bi, qd, k=int(k),
                                  metric=metric)
        outs.append((lo, hi, dv, di))
        if pending is not None:
            pdv, pbufs = pending
            # the previous rerank consumed its staged buffers once this
            # is ready — only then may the pool hand them out again
            jax.block_until_ready(pdv)  # jaxlint: disable=JX05 pool-buffer lifetime barrier: device_put may alias host memory, so the staging buffers return to the pool only after the rerank that read them completes
            for buf in pbufs:
                pool.release(buf)
        pending = (dv, bufs)
    if pending is not None:
        pdv, pbufs = pending
        jax.block_until_ready(pdv)  # jaxlint: disable=JX05 final pool-buffer lifetime barrier (see above): last chunk's staging buffers return only after its rerank completes
        for buf in pbufs:
            pool.release(buf)
    if len(outs) == 1:
        lo, hi, dv, di = outs[0]
        dv, di = dv[:hi - lo], di[:hi - lo]
    else:
        dv = jnp.concatenate([o[2][:o[1] - o[0]] for o in outs], axis=0)
        di = jnp.concatenate([o[3][:o[1] - o[0]] for o in outs], axis=0)
    if keep is not None:  # sub-k survivors: sentinel tail, not real ids
        di = sentinel_filtered_ids(dv, di)
    from ..core.host_memory import export_host_pool_metrics

    export_host_pool_metrics(pool)
    return dv, di


def searcher(index: OocIndex, k: int,
             params: Optional[OocSearchParams] = None, *, filter=None):
    """Uniform serving entry point (``raft_tpu.serve`` contract):
    ``(fn, operands)`` with ``fn(queries, *operands)`` equal to
    :func:`search` for batches up to the resolved query chunk.  The
    host gather rides INSIDE the traced function as a
    ``jax.pure_callback`` with a static ``[nq, rerank_k, d]`` result
    shape, so the searcher AOT-compiles through the serve executable
    cache like every resident family — the callback closure holds the
    shard store, the device slabs ride as operands."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           sentinel_filtered_ids)

    p = params or OocSearchParams()
    expects(k >= 1, "k must be >= 1")
    n_probes, probe_block, rerank_k = _resolved_static(index, k, p)
    metric = index.metric
    store = index.store
    d = index.dim
    fetch_batch = int(p.fetch_batch)
    keep = as_keep_mask(filter)
    if keep is not None:
        expects(keep.ndim == 1,
                "serving filters are shared bitsets (1-D)")
        check_filter_covers_ids(keep, index.ids)

    def fetch_host(bi):
        bi = np.asarray(bi)
        out = np.empty((bi.size, d), store.dtype)
        store.gather(bi, out=out, fetch_batch=fetch_batch)
        _note_fetch(out.nbytes)
        return out.reshape(bi.shape[0], rerank_k, d)

    def core(q, centroids, rotation, codes, sabs, res_norms, code_cdots,
             ids, counts, kp):
        bv, bi = _survivors_impl(centroids, rotation, codes, sabs,
                                 res_norms, code_cdots, ids, counts, q,
                                 n_probes, rerank_k, metric, kp,
                                 probe_block)
        slab = jax.pure_callback(
            fetch_host,
            jax.ShapeDtypeStruct((q.shape[0], rerank_k, d), store.dtype),
            bi)
        return _rerank_core(slab, bv, bi, q, int(k), metric)

    if keep is not None:
        def fn(q, centroids, rotation, codes, sabs, res_norms, code_cdots,
               ids, counts, kp):
            dv, di = core(q, centroids, rotation, codes, sabs, res_norms,
                          code_cdots, ids, counts, kp)
            return dv, sentinel_filtered_ids(dv, di)

        return fn, (index.centroids, index.rotation, index.codes,
                    index.sabs, index.res_norms, index.code_cdots,
                    index.ids, index.counts, keep)

    def fn(q, centroids, rotation, codes, sabs, res_norms, code_cdots,
           ids, counts):
        return core(q, centroids, rotation, codes, sabs, res_norms,
                    code_cdots, ids, counts, None)

    return fn, (index.centroids, index.rotation, index.codes, index.sabs,
                index.res_norms, index.code_cdots, index.ids, index.counts)


# ---------------------------------------------------------------------------
# Persistence — format v5: a manifest directory wrapping a device bundle
# (per-array CRCs) and the shard store (per-shard CRCs).
# ---------------------------------------------------------------------------


def save(path, index: OocIndex, *, manifest: Optional[dict] = None,
         fsync: bool = True) -> None:
    """Persist to a v5 manifest directory::

        path/meta.json   index_type/format_version/static + manifest
        path/device/     the eight device arrays (save_arrays bundle,
                         atomic, per-array CRC32)
        path/shards/     the raw-row store (copied in unless the index
                         already built it here; per-shard CRC32)

    ``meta.json`` publishes LAST, so a reader (or :func:`verify`) never
    sees a half-written artifact as openable."""
    from ..core.serialize import fsync_dir, save_arrays, write_text_atomic

    path = os.fspath(path)
    os.makedirs(path, exist_ok=True)
    arrays = {f: np.asarray(jax.device_get(getattr(index, f)))  # jaxlint: disable=JX01 checkpoint write, off the search path
              for f in _ARRAY_FIELDS}
    save_arrays(os.path.join(path, _DEVICE_DIR), arrays,
                metadata={"index_type": "OocIndex"},
                atomic=True, fsync=fsync)
    dst = os.path.join(path, _SHARDS_DIR)
    src = os.path.abspath(index.store.path)
    if src != os.path.abspath(dst):
        if os.path.exists(dst):
            shutil.rmtree(dst)
        shutil.copytree(src, dst)
    meta = {
        "index_type": "OocIndex",
        "format_version": _FORMAT_VERSION,
        "static": {"metric": index.metric},
        "store": _SHARDS_DIR,
        "manifest": dict(manifest or {}),
    }
    write_text_atomic(os.path.join(path, _META), json.dumps(meta, indent=1))
    if fsync:
        fsync_dir(path)


def _read_meta(path: str) -> dict:
    import pathlib

    mp = pathlib.Path(path) / _META
    expects(mp.exists(), f"ooc.open: no {_META} under {path!r}")
    meta = json.loads(mp.read_text())
    expects(meta.get("index_type") == "OocIndex",
            f"{path!r} is not an OocIndex artifact "
            f"(index_type={meta.get('index_type')!r})")
    if meta.get("format_version", 0) > _FORMAT_VERSION:
        raise ValueError(
            f"{path!r}: format_version {meta['format_version']} is newer "
            f"than supported {_FORMAT_VERSION}")
    return meta


def open(path, *, verify: bool = False, res=None) -> OocIndex:
    """Open a :func:`save` artifact.  The device bundle loads (and
    ``device_put``s) the code tier; the shard store maps LAZILY — no
    shard is read until a survivor gather touches it, so opening a
    TB-scale store costs metadata only.  ``verify=True`` checks the
    device arrays' CRCs first (shard CRCs are a :func:`verify` sweep —
    re-reading terabytes at open time would defeat the layout)."""
    from ..core.serialize import load_arrays

    path = os.fspath(path)
    meta = _read_meta(path)
    arrays, _ = load_arrays(os.path.join(path, _DEVICE_DIR), verify=verify)
    store = ShardedVectorStore.open(
        os.path.join(path, meta.get("store", _SHARDS_DIR)))
    fields = {f: jax.device_put(arrays[f]) for f in _ARRAY_FIELDS}
    return OocIndex(store=store,
                    metric=meta.get("static", {}).get("metric",
                                                      "sqeuclidean"),
                    **fields)


def verify(path) -> list:
    """Integrity-check a :func:`save` artifact without opening it:
    meta.json well-formed, device bundle CRCs, per-shard store CRCs.
    Returns a list of problems (empty = intact)."""
    from ..core.serialize import verify_arrays

    path = os.fspath(path)
    problems = []
    try:
        meta = _read_meta(path)
    except Exception as exc:
        return [str(exc)]
    problems.extend(verify_arrays(os.path.join(path, _DEVICE_DIR)))
    store_dir = os.path.join(path, meta.get("store", _SHARDS_DIR))
    try:
        store = ShardedVectorStore.open(store_dir)
        problems.extend(store.verify())
    except Exception as exc:
        problems.append(f"shard store unreadable: {exc}")
    return problems
