"""IVF-Flat — inverted-file index with flat (uncompressed) lists.

No in-tree CUDA ancestor (cuVS migration, SURVEY.md scope note); designed
from the north-star capability list (``BASELINE.json`` configs: ivf_flat +
kmeans_balanced on SIFT-1M) and the TPU-KNN paper (PAPERS.md).

TPU-first design:
* **Coarse quantizer** = :func:`raft_tpu.cluster.kmeans_balanced_fit` — the
  balanced variant exists precisely because dense padded lists need a hard
  size bound (list capacity is a static shape).
* **List layout**: one dense ``[n_lists, cap, d]`` slab + ``[n_lists, cap]``
  source ids, pad entries masked by per-list counts.  Gathers of whole lists
  are contiguous HBM reads; no pointer-chasing.
* **Stored capacity**: a list holds at most ``⌈list_cap_ratio·n/L⌉``
  rows; its slab row is padded up to the dtype's sublane tile
  (:func:`slab_capacity`), so a TPU stores the slab list-major and no
  search program relayouts it.
* **Search**: query→centroid distances on the MXU, ``top_k`` probe pick,
  then one of two probe scans, chosen per compiled program from the
  platform and its static shapes (``ops.blocked_scan.scan_path``,
  ``scan_kernel="auto"``):

  - **grouped** (list-major) on a TPU for batches that probe a list
    half a time or more on average, wherever its kernel takes the f32
    slab: the (query, list) pairs are sorted by list and cut into tiles
    of 16 query slots; one Pallas kernel scores each tile with one
    ``[16, d]·[d, cap]`` MXU product at ``precision=HIGHEST`` and keeps
    its exact top-k, so a list is read once per run of tiles instead of
    once per query (``ops.blocked_scan.scan_topk_grouped``).  Same
    candidate set as the query-major scan; distances within a few ulps.
  - **query-major** off a TPU, for smaller batches, 8-bit and bf16
    slabs, per-query filter bitmaps, k > 128, lists too large for VMEM,
    ``scan_kernel="xla"`` and :func:`search_sharded`: one scan
    iteration per **probe block** of B probe ranks: one
    ``[nq, B·cap, d]`` slab gather, one batched dot, pads masked, ONE
    merge into the running top-k via ``select_k`` — ⌈n_probes/B⌉ merges
    instead of n_probes, with unsorted intermediate carries and a single
    ranked selection after the scan.  Results are bit-identical for
    every B (the cross-block invariance contract holds for this path);
    B defaults from the measured ``_probe_block_table``
    (``bench/tune_probe_block.py``).

  Everything static-shape, jit-compiled once per
  (nq, k, n_probes, probe_block, scan_kernel) config.
* **Sharded variant**: lists are partitioned round-robin over the mesh axis;
  every shard searches its local lists with the same program and the
  per-shard candidates merge with one ``all_gather`` + ``select_k`` -- the
  index-shard MNMG model of SURVEY.md §5.7 on ICI instead of NCCL.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..cluster.kmeans import KMeansParams, capped_assign, kmeans_balanced_fit
from ..core import tracing
from ..core.array import wrap_array
from ..core.compat import shard_map
from ..core.errors import expects
from ..distance.pairwise import sq_l2

__all__ = [
    "IvfFlatIndexParams",
    "IvfFlatSearchParams",
    "IvfFlatIndex",
    "build",
    "build_chunked",
    "build_chunked_sharded",
    "search",
    "searcher",
    "extend",
    "build_sharded",
    "search_sharded",
    "fleet_slices",
    "IvfFlatFleetSlices",
]


@dataclasses.dataclass(frozen=True)
class IvfFlatIndexParams:
    """Build configuration (per-call parameter struct idiom, SURVEY.md §5.6b)."""

    n_lists: int = 1024
    metric: str = "sqeuclidean"  # sqeuclidean | euclidean | inner_product
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.1
    list_cap_ratio: float = 2.0  # capacity = ratio * n / n_lists
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class IvfFlatSearchParams:
    n_probes: int = 32
    query_chunk: int = 4096  # cap on the [chunk, cap, d] gather working set
    # probes gathered+scored+merged per query-major scan step; 0 = auto
    # (measured table via bench/tune_probe_block.py, else a working-set
    # heuristic).  Results are bit-identical for every value — this is a
    # pure latency/throughput knob (docs/tuning_guide.md); the grouped
    # scan takes no probe blocks.
    probe_block: int = 0
    # probe-scan engine: "auto" | "grouped" | "xla".  "xla" is the
    # bit-exact query-major scan; "grouped" forces the list-major scan
    # (one MXU product per tile of queries sharing a list) on any
    # platform; "auto" takes it on a TPU only.  Either way each compiled
    # program decides by ops.blocked_scan.scan_path, and a batch too
    # small for the grouped scan scans query-major.
    scan_kernel: str = "auto"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IvfFlatIndex:
    centroids: jax.Array   # [L, d]
    data: jax.Array        # [L, cap, d]
    ids: jax.Array         # [L, cap] int32, -1 pad
    counts: jax.Array      # [L] int32
    norms: jax.Array       # [L, cap] f32 squared L2 of stored vectors
    metric: str = dataclasses.field(metadata=dict(static=True))

    @property
    def n_lists(self) -> int:
        return int(self.data.shape[0])

    @property
    def list_cap(self) -> int:
        return int(self.data.shape[1])

    @property
    def dim(self) -> int:
        return int(self.data.shape[2])

    @property
    def size(self) -> int:
        return int(jnp.sum(self.counts))  # jaxlint: disable=JX01 size is a host-facing API scalar, not on the search path


def slab_capacity(cap: int, dtype) -> int:
    """Stored rows per list of a slab whose lists hold at most ``cap``
    rows: ``cap`` rounded up to the dtype's sublane tile (8 rows of f32,
    16 of bf16, 32 of 8-bit); the extra slots are padding.  On a TPU a
    ``[L, cap, d]`` slab whose ``cap`` is not a multiple of the tile is
    stored with the list axis second-minor, so every list-major read
    first relayouts the whole slab; an aligned ``cap`` is stored
    list-major."""
    tile = max(8, 32 // jnp.dtype(dtype).itemsize)
    return -(-int(cap) // tile) * tile


@tracing.annotate("ivf_flat.build")
def build(dataset, params: Optional[IvfFlatIndexParams] = None, *,
          source_ids=None, res=None) -> IvfFlatIndex:
    """Train the coarse quantizer and pack inverted lists (all on device —
    the packing is one jitted sort+scatter, :mod:`._packing`)."""
    p = params or IvfFlatIndexParams()
    x = wrap_array(dataset, ndim=2, name="dataset")
    n, d = x.shape
    expects(p.n_lists >= 1 and p.n_lists <= n, "n_lists out of range")
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))

    # 1. train balanced kmeans on a subsample (trainset_fraction idiom)
    n_train = max(p.n_lists * 4, int(n * p.kmeans_trainset_fraction))
    n_train = min(n, n_train)
    key = jax.random.PRNGKey(p.seed)
    sel = (jax.random.permutation(key, n)[:n_train] if n_train < n
           else jnp.arange(n))
    kp = KMeansParams(n_clusters=p.n_lists, max_iter=p.kmeans_n_iters,
                      seed=p.seed)
    centroids, _, _ = kmeans_balanced_fit(x[sel], kp)

    # 2. capacity-constrained assignment of the full dataset
    labels, _ = capped_assign(x, centroids, cap)

    # 3. pack lists — jitted sort+scatter, no host round-trip
    from ._packing import pack_lists

    ids = (jnp.asarray(source_ids, jnp.int32) if source_ids is not None
           else jnp.arange(n, dtype=jnp.int32))
    (data, out_ids), counts = pack_lists(
        labels, (x, ids), n_lists=p.n_lists, cap=slab_capacity(cap, x.dtype),
        fills=(0.0, -1))
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
    return IvfFlatIndex(centroids, data, out_ids, counts, norms, p.metric)


def _train_subsample(n: int, n_train: int, seed: int):
    """Host-side subsample indices for quantizer training (sorted for
    memmap-friendly reads)."""
    if n_train >= n:
        return np.arange(n)
    rs = np.random.default_rng(seed)
    return np.sort(rs.choice(n, n_train, replace=False))


def _coarse_train_chunked(dataset, p: IvfFlatIndexParams, n: int):
    """Coarse-quantizer training for the streaming builds: balanced kmeans
    over a host-sampled subset (the only phase that touches more than one
    chunk of host data at a time)."""
    n_train = min(n, max(p.n_lists * 4, int(n * p.kmeans_trainset_fraction)))
    sel = _train_subsample(n, n_train, p.seed)
    kp = KMeansParams(n_clusters=p.n_lists, max_iter=p.kmeans_n_iters,
                      seed=p.seed)
    centroids, _, _ = kmeans_balanced_fit(np.asarray(dataset[sel]), kp)
    return centroids


def _flat_step_impl(slabs, counts, centroids, xc, idc, *,
                    n_lists: int, cap: int):
    """ONE fused program per chunk: masked capped assignment against
    remaining room + scatter-append, fused so XLA sees (and schedules) the
    whole chunk as a single dispatch — no host round-trip for ``counts``
    between the stages.  Pad rows (``idc < 0``, from the fixed-shape tail
    padding) never request a list, never consume capacity, and
    scatter-drop via label −1, so the padded stream is bit-identical to
    the unpadded per-op loop.  ``cap`` bounds each list's rows; the slabs
    may store more slots (:func:`slab_capacity`).

    Two jitted forms: :func:`_flat_chunk_step` donates the slabs (build
    loops own their buffers); :func:`_flat_chunk_step_cow` leaves the
    inputs alive — the copy-on-write first step of the online
    :func:`extend`, whose input slabs belong to the LIVE index a serving
    snapshot may still be dispatching against."""
    from ..cluster.kmeans import _capped_assign_impl
    from ._packing import _scatter_append_impl

    valid = idc >= 0
    labels, _ = _capped_assign_impl(xc, centroids, cap - counts, valid)
    return _scatter_append_impl(slabs, counts, labels, (xc, idc),
                                n_lists=n_lists, cap=slabs[0].shape[1])


_flat_chunk_step = partial(jax.jit, static_argnames=("n_lists", "cap"),
                           donate_argnums=(0, 1))(_flat_step_impl)
_flat_chunk_step_cow = partial(jax.jit, static_argnames=("n_lists", "cap"))(
    _flat_step_impl)


def _stream_pipelined(dataset, centroids, p: IvfFlatIndexParams, n: int,
                      cap: int, chunk_rows: int, source_ids, dtype,
                      heartbeat=None):
    """Pipelined chunk engine: fixed-shape double-buffered device staging
    (:func:`~._packing.prefetch_chunks_padded`) feeding the fused donated
    :func:`_flat_chunk_step` — one executable, one dispatch per chunk."""
    from ._packing import device_full, prefetch_chunks_padded

    d, slab_cap = dataset.shape[1], slab_capacity(cap, dtype)
    data = device_full((p.n_lists, slab_cap, d), 0, dtype)
    ids_slab = device_full((p.n_lists, slab_cap), -1, jnp.int32)
    counts = device_full((p.n_lists,), 0, jnp.int32)
    for lo, hi, xc, idc in prefetch_chunks_padded(dataset, chunk_rows,
                                                  source_ids, dtype=dtype):
        (data, ids_slab), counts = _flat_chunk_step(
            (data, ids_slab), counts, centroids, xc, idc,
            n_lists=p.n_lists, cap=cap)
        if heartbeat is not None:
            heartbeat(hi)
    return data, ids_slab, counts


def _stream_perop(dataset, centroids, p: IvfFlatIndexParams, n: int,
                  cap: int, chunk_rows: int, source_ids, dtype):
    """Reference per-op chunk loop (the pre-pipelining engine): blocking
    H2D ``jnp.asarray``, separate assign / scatter dispatches, tail chunk
    at its own shape.  Kept verbatim as the bit-parity oracle for the
    fused engine (tests/test_chunked_builds.py) and the A/B baseline of
    ``bench/build_throughput.py``."""
    from ..cluster.kmeans import capped_assign_room
    from ._packing import prefetch_chunks, scatter_append

    slab_cap = slab_capacity(cap, dtype)
    data = jnp.zeros((p.n_lists, slab_cap, dataset.shape[1]), dtype)
    ids_slab = jnp.full((p.n_lists, slab_cap), -1, jnp.int32)
    counts = jnp.zeros((p.n_lists,), jnp.int32)
    for lo, hi, xc_h, idc_h in prefetch_chunks(dataset, chunk_rows,
                                               source_ids):
        xc = jnp.asarray(xc_h, dtype)
        idc = jnp.asarray(idc_h, jnp.int32)
        labels, _ = capped_assign_room(xc, centroids, cap - counts)
        (data, ids_slab), counts = scatter_append(
            (data, ids_slab), counts, labels, (xc, idc),
            n_lists=p.n_lists, cap=slab_cap)
    return data, ids_slab, counts


def build_chunked(dataset, params: Optional[IvfFlatIndexParams] = None, *,
                  chunk_rows: int = 0, source_ids=None,
                  res=None) -> IvfFlatIndex:
    """Out-of-core build: the dataset stays on host (any numpy-indexable —
    ``np.ndarray``, ``np.memmap``, an ``io.BatchLoader``-backed array) and
    streams through the device in fixed-size chunks.

    Device peak = list slabs + two staged chunks + one (chunk, n_lists)
    distance block — never the whole dataset (the r2 builds were
    whole-dataset-resident; VERDICT r2 missing #2).  The chunk engine is
    pipelined: each chunk is ONE jitted, slab-donating program
    (:func:`_flat_chunk_step` — capped assign against remaining room fused
    with the scatter-append), the tail chunk is padded to ``chunk_rows``
    with masked rows so a single executable serves the whole stream (zero
    steady-state recompiles, assertable under
    :class:`~raft_tpu.core.TraceGuard`), and chunk t+1 is staged
    host→device with a non-blocking ``device_put`` while chunk t computes
    (:func:`~raft_tpu.core.device_prefetch`).

    ``chunk_rows=0`` (default) = auto: the measured table written by
    ``bench/tune_chunk_rows.py``, else 65536
    (:func:`~._packing.resolve_chunk_rows`) — a pure throughput knob, the
    built index is identical for every value.

    Reference analog: the SNMG streaming/batch build model
    (``core/device_resources_snmg.hpp:36``) without a CUDA ancestor for the
    chunk loop itself (cuVS migration).
    """
    from ._packing import build_heartbeat, resolve_chunk_rows

    p = params or IvfFlatIndexParams()
    n, d = dataset.shape
    expects(p.n_lists >= 1 and p.n_lists <= n, "n_lists out of range")
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))
    dtype = jnp.asarray(np.asarray(dataset[:1])).dtype
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_flat")

    centroids = _coarse_train_chunked(dataset, p, n)
    data, ids_slab, counts = _stream_pipelined(
        dataset, centroids, p, n, cap, chunk_rows, source_ids, dtype,
        heartbeat=build_heartbeat("ivf_flat.build_chunked", n))
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
    return IvfFlatIndex(centroids, data, ids_slab, counts, norms, p.metric)


def _build_chunked_perop(dataset, params: Optional[IvfFlatIndexParams] = None,
                         *, chunk_rows: int = 0,
                         source_ids=None) -> IvfFlatIndex:
    """:func:`build_chunked` on the reference per-op chunk loop
    (:func:`_stream_perop`) — the parity oracle / A/B baseline; not part
    of the public API."""
    from ._packing import resolve_chunk_rows

    p = params or IvfFlatIndexParams()
    n, d = dataset.shape
    expects(p.n_lists >= 1 and p.n_lists <= n, "n_lists out of range")
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))
    dtype = jnp.asarray(np.asarray(dataset[:1])).dtype
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_flat")
    centroids = _coarse_train_chunked(dataset, p, n)
    data, ids_slab, counts = _stream_perop(
        dataset, centroids, p, n, cap, chunk_rows, source_ids, dtype)
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
    return IvfFlatIndex(centroids, data, ids_slab, counts, norms, p.metric)


def extend(index: IvfFlatIndex, new_vectors, new_ids=None, *,
           insert_chunk: int = 0) -> IvfFlatIndex:
    """Online streaming insert (cuVS ``extend`` parity), rebuilt around
    the chunked builder's fused slab-donating step.

    The insert batch is host-padded to a fixed ``insert_chunk`` row bucket
    (0 = :data:`~._packing.DEFAULT_INSERT_CHUNK`; pad rows carry id −1 and
    are masked out of assignment and capacity) and streamed through
    :func:`_flat_chunk_step`: ONE jitted executable serves every insert
    size, counts never leave the device between assign and scatter, and
    the only host↔device crossings are the explicit per-chunk
    ``device_put`` and one scalar spill check — the steady-state insert
    path is zero-retrace / zero-implicit-transfer under
    :class:`~raft_tpu.core.TraceGuard`.

    Copy-on-write: the first chunk step is the non-donating
    :func:`_flat_chunk_step_cow` (the source slabs may back a live serving
    snapshot mid-dispatch), later chunks donate the fresh private buffers.
    The source ``index`` stays fully usable after the call.

    When the batch overflows list capacity the slab grows (a host-sized
    static shape — the padded layout's rebuild price) with geometric
    headroom and the stream re-runs from the untouched source slabs.
    With capacity to spare, capped assignment degenerates to
    nearest-centroid for every row, so extending is bit-identical (values
    AND ids) to a from-scratch pack at the same centroids
    (tests/test_mutation.py pins this).
    """
    from ._packing import (DEFAULT_INSERT_CHUNK, host_rows,
                           staged_insert_chunks)

    L, cap, d = index.n_lists, index.list_cap, index.dim
    x = host_rows(new_vectors)
    expects(x.ndim == 2 and x.shape[1] == d, "vector dim mismatch")
    n_new = x.shape[0]
    expects(n_new >= 1, "no rows to insert")
    base = int(jax.device_get(jnp.sum(index.counts)))  # jaxlint: disable=JX01 one scalar sync per extend call: sizes auto-assigned ids and the spill check baseline
    ids = (np.asarray(host_rows(new_ids), np.int32) if new_ids is not None
           else np.arange(base, base + n_new, dtype=np.int32))
    expects(ids.shape == (n_new,), "new_ids must be one id per row")
    expects(int(ids.min()) >= 0, "source ids must be >= 0 (−1 is the pad)")
    chunk = int(insert_chunk) or DEFAULT_INSERT_CHUNK

    def stream(slabs, counts, slab_cap):
        step = _flat_chunk_step_cow  # inputs may back a live snapshot
        for xc, idc in staged_insert_chunks(x, ids, chunk, index.data.dtype):
            slabs, counts = step(slabs, counts, index.centroids, xc, idc,
                                 n_lists=L, cap=slab_cap)
            step = _flat_chunk_step  # fresh private buffers: donate
        return slabs, counts

    (data, out_ids), counts = stream((index.data, index.ids), index.counts,
                                     cap)
    placed = int(jax.device_get(jnp.sum(counts))) - base  # jaxlint: disable=JX01 explicit spill check: one scalar per extend gates the rare slab-growth path
    if placed < n_new:  # capacity exhausted — grow + re-run (rare)
        xd = jnp.asarray(x.astype(index.data.dtype, copy=False))
        labels = jnp.argmin(sq_l2(xd, index.centroids), axis=1)
        added = jax.ops.segment_sum(jnp.ones_like(labels, jnp.int32),
                                    labels, num_segments=L)
        need = int(jnp.max(index.counts + added))  # jaxlint: disable=JX01 slab capacity must be a host int at extend time (static shapes)
        new_cap = slab_capacity(max(need, cap + (cap + 1) // 2),
                                index.data.dtype)  # geometric headroom
        pad = new_cap - cap
        grown = (jnp.pad(index.data, ((0, 0), (0, pad), (0, 0))),
                 jnp.pad(index.ids, ((0, 0), (0, pad)), constant_values=-1))
        (data, out_ids), counts = stream(grown, index.counts, new_cap)
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
    return IvfFlatIndex(index.centroids, data, out_ids, counts, norms,
                        index.metric)


def _probe_scan(q, qn, data, ids, counts, norms, probes, k: int, metric: str,
                keep=None, probe_block: int = 1, path: str = "query_major"):
    """Scan the probed lists through the shared ``ops.blocked_scan`` core.

    q: [nq, d]; probes: [nq, P].  ``path="grouped"`` runs the list-major
    scan (``blocked_scan.scan_topk_grouped``): pairs sorted by list, one
    MXU product per tile of queries sharing a list, each list read once
    per run of tiles; distances within a few ulps of the query-major
    scan's, the same candidate set, and ``probe_block`` unused.

    The query-major scan gathers, per iteration, the next B probed lists
    of every query (one ``[nq, B·cap, d]`` slab), scores it with
    ``slab_dots`` (B pinned in the einsum's batch dims — the
    bit-invariance contract: results identical across block sizes) and
    folds it into the running top-k — ⌈P/B⌉ merges instead of P.  Pad
    probes (P not divisible by B) are masked to +inf, never duplicated.
    ``keep``: optional bool prefilter by source id."""
    from ..ops import blocked_scan as _scan
    from ._packing import blocked_probe_plan

    nq = q.shape[0]
    cap = data.shape[1]
    if path == "grouped":
        bias = _scan.slot_bias(norms, ids, counts, metric, keep)
        return _scan.scan_topk_grouped(
            q.astype(jnp.float32), qn, data, bias, ids, probes, k,
            l2=metric != "inner_product")
    lists_xs, pvalid = blocked_probe_plan(probes, probe_block)

    def score(inp):
        lists, pv = inp                           # [nq, B], [B]
        bcap = lists.shape[1] * cap
        vecs = data[lists]                        # [nq, B, cap, d] gather
        vids = ids[lists].reshape(nq, bcap)       # [nq, B·cap]
        valid = (jnp.arange(cap)[None, None, :]
                 < counts[lists][:, :, None]).reshape(nq, bcap)
        valid = valid & (vids >= 0) & jnp.repeat(pv, cap)[None, :]
        if keep is not None:
            from ._packing import keep_lookup

            valid = valid & keep_lookup(keep, vids)
        dots = _scan.slab_dots(vecs, q).reshape(nq, -1)
        if metric == "inner_product":
            dist = -dots
        else:  # sqeuclidean / euclidean rank by squared L2
            dist = norms[lists].reshape(nq, dots.shape[1]) - 2.0 * dots \
                + qn[:, None]
            dist = jnp.maximum(dist, 0.0)
        return jnp.where(valid, dist, jnp.inf), vids

    return _scan.scan_topk(score, (lists_xs, pvalid), nq, k)


@partial(jax.jit, static_argnames=("k", "n_probes", "metric", "probe_block",
                                   "scan_kernel"))
def _search_impl(centroids, data, ids, counts, norms, q, k: int,
                 n_probes: int, metric: str, keep=None,
                 probe_block: int = 1, scan_kernel: str = "xla"):
    from ..ops.blocked_scan import count_scan_path, row_sq_norms, scan_path

    # IVF-Flat scores at precision HIGHEST, which the grouped kernel
    # keeps only for f32 lists
    path = scan_path(scan_kernel, q.shape[0], n_probes, data.shape[0],
                     data.shape[1], data.shape[2], data.dtype, k,
                     0 if keep is None else keep.ndim,
                     grouped_dtype=jnp.float32)
    count_scan_path("ivf_flat", path)
    qf = q.astype(jnp.float32)
    qn = row_sq_norms(qf)   # dot-contraction: rounds the same in the
    # fleet's SPMD executable (serve bit-identity, ops.blocked_scan doc)
    cd = sq_l2(q, centroids)                      # [nq, L] MXU block
    _, probes = jax.lax.top_k(-cd, n_probes)      # nearest lists
    bv, bi = _probe_scan(q, qn, data, ids, counts, norms, probes, k, metric,
                         keep, probe_block, path)
    if metric == "euclidean":
        bv = jnp.sqrt(jnp.maximum(bv, 0.0))
    elif metric == "inner_product":
        bv = -bv
    return bv, bi


@tracing.annotate("ivf_flat.search")
def search(index: IvfFlatIndex, queries, k: int,
           params: Optional[IvfFlatSearchParams] = None, *, filter=None,
           res=None) -> Tuple[jax.Array, jax.Array]:
    """Approximate kNN: returns ``(distances, ids)`` of (nq, k), best first.

    ``filter``: optional prefilter by source id over the ORIGINAL row
    numbering, True = keep — a shared ``core.Bitset``/(n,) bools (cuVS
    bitset filter) or a per-query ``core.Bitmap``/(nq, n) bools (bitmap
    filter)."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           chunked_filtered_queries, resolve_probe_block,
                           sentinel_filtered_ids)

    p = params or IvfFlatSearchParams()
    q = wrap_array(queries, ndim=2, name="queries")
    expects(q.shape[1] == index.dim, "query dim mismatch")
    n_probes = min(p.n_probes, index.n_lists)
    probe_block = resolve_probe_block(p.probe_block, int(n_probes),
                                      index.list_cap, "ivf_flat")
    keep = as_keep_mask(filter, nq=q.shape[0])  # indexes source ids
    if keep is not None:
        check_filter_covers_ids(keep, index.ids)

    impl = lambda qc, kc: _search_impl(
        index.centroids, index.data, index.ids, index.counts,
        index.norms, qc, int(k), int(n_probes), index.metric, kc,
        probe_block, p.scan_kernel)
    dv, di = chunked_filtered_queries(impl, q, int(p.query_chunk), keep)
    if keep is not None:  # sub-k survivors: sentinel tail, not real ids
        di = sentinel_filtered_ids(dv, di)
    return dv, di


def searcher(index: IvfFlatIndex, k: int,
             params: Optional[IvfFlatSearchParams] = None, *, filter=None):
    """Uniform serving entry point (``raft_tpu.serve`` contract): returns
    ``(fn, operands)`` with ``fn(queries, *operands)`` equal to
    :func:`search` for query batches up to ``params.query_chunk`` rows
    (above that :func:`search` chunks; serving buckets stay well below).
    ``fn`` AOT-compiles via
    ``jax.jit(fn).lower(q_spec, *operands).compile()``; the index slabs
    ride as operands so bucket executables share them instead of baking
    per-bucket constants.

    ``filter``: optional shared prefilter (``core.Bitset`` / 1-D bools
    over source ids, True = keep) — rides as one more operand, so
    tombstone deletes (:func:`raft_tpu.neighbors.mutation.delete`) swap
    in a new mask without recompiling.  Per-query bitmaps can't ride a
    fixed operand across variable-row buckets and are rejected."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           resolve_probe_block, sentinel_filtered_ids)

    p = params or IvfFlatSearchParams()
    expects(k >= 1, "k must be >= 1")
    n_probes = int(min(p.n_probes, index.n_lists))
    probe_block = resolve_probe_block(p.probe_block, n_probes,
                                      index.list_cap, "ivf_flat")
    metric = index.metric
    keep = as_keep_mask(filter)
    if keep is not None:
        expects(keep.ndim == 1,
                "serving filters are shared bitsets (1-D); per-query "
                "bitmaps can't ride a fixed operand across buckets")
        check_filter_covers_ids(keep, index.ids)

        def fn(q, centroids, data, ids, counts, norms, kp):
            dv, di = _search_impl(centroids, data, ids, counts, norms, q,
                                  int(k), n_probes, metric, kp, probe_block,
                                  p.scan_kernel)
            return dv, sentinel_filtered_ids(dv, di)

        return fn, (index.centroids, index.data, index.ids, index.counts,
                    index.norms, keep)

    def fn(q, centroids, data, ids, counts, norms):
        return _search_impl(centroids, data, ids, counts, norms, q,
                            int(k), n_probes, metric, None, probe_block,
                            p.scan_kernel)

    return fn, (index.centroids, index.data, index.ids, index.counts,
                index.norms)


# ---------------------------------------------------------------------------
# Sharded (multi-chip) variant: lists partitioned over the mesh axis.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=16)
def _sharded_build_program(mesh: Mesh, axis: str, n_orig: int, per: int,
                           n_lists_local: int, cap: int, n_train: int,
                           max_iter: int, penalty: float, bal_cap: int,
                           seed: int):
    """Compile-once distributed build: every device trains a coarse
    quantizer on ITS rows and packs ITS lists — no single-device
    whole-dataset build, no post-hoc device_put (the r2 shape;
    VERDICT r2 missing #2).  SNMG model of
    ``core/device_resources_snmg.hpp:36``: shard-local sub-indexes,
    global ids ``shard·per + local``."""
    from ..cluster.kmeans import _balanced_fit_impl
    from ._packing import pack_lists

    def local(x_l):
        shard = jax.lax.axis_index(axis)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), shard)
        sel = jax.random.permutation(key, per)[:n_train]
        c, _, _, _ = _balanced_fit_impl(
            x_l[sel], key, n_lists_local, max_iter, penalty, bal_cap)
        gid = (shard * per + jnp.arange(per)).astype(jnp.int32)
        labels, _ = capped_assign(x_l, c, cap)
        # rows padded to even out the shards are dropped here, not stored
        labels = jnp.where(gid < n_orig, labels, -1)
        (data, out_ids), counts = pack_lists(
            labels, (x_l, gid), n_lists=n_lists_local,
            cap=slab_capacity(cap, x_l.dtype),
            fills=(0.0, -1))
        norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
        # centroids keep the fit dtype (f32 for integer corpora —
        # rounding to uint8 would quantize the probe routing)
        return c, data, out_ids, counts, norms

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=P(axis),
        out_specs=(P(axis),) * 5, check_vma=False,
    ))


def build_sharded(dataset, mesh: Mesh, params: Optional[IvfFlatIndexParams] = None,
                  *, axis: str = "shard") -> IvfFlatIndex:
    """Distributed build: rows are sharded over the mesh axis and **each
    device builds its own sub-index from its own rows** (one shard_map
    program — S parallel builds, one compile).  Device d owns lists
    ``[d·L/S, (d+1)·L/S)`` trained on its row shard; ids are global row
    positions.  :func:`search_sharded` probes every shard's local lists and
    merges, so the union covers the globally nearest lists."""
    from ._packing import shard_rows, sharded_train_sizes

    p = params or IvfFlatIndexParams()
    n_dev = int(mesh.shape[axis])
    x_sh, n, per = shard_rows(dataset, mesh, axis)
    n_lists_local = max(1, (p.n_lists + n_dev - 1) // n_dev)
    expects(n_lists_local <= per, "n_lists exceeds rows per shard")
    cap = max(1, int(np.ceil(p.list_cap_ratio * per / n_lists_local)))
    kp = KMeansParams()  # balanced-cap ratio for the trainset fit
    n_train, bal_cap = sharded_train_sizes(
        per, n_lists_local, p.kmeans_trainset_fraction, kp.balanced_max_ratio)
    prog = _sharded_build_program(
        mesh, axis, n, per, n_lists_local, cap, n_train,
        p.kmeans_n_iters, float(kp.balanced_penalty), bal_cap, p.seed)
    c, data, ids, counts, norms = prog(x_sh)
    return IvfFlatIndex(c, data, ids, counts, norms, p.metric)


@lru_cache(maxsize=16)
def _sharded_chunk_train_program(mesh: Mesh, axis: str, n_lists_local: int,
                                 max_iter: int, penalty: float, bal_cap: int,
                                 seed: int):
    """Per-shard coarse-quantizer fit for the sharded streaming build:
    each device balanced-fits ITS local centroids on ITS host-sampled
    trainset stripe (``[S·n_train, d]`` sharded in) — one shard_map
    program, S parallel fits, one compile."""
    from ..cluster.kmeans import _balanced_fit_impl

    def local(xt_l):
        shard = jax.lax.axis_index(axis)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), shard)
        c, _, _, _ = _balanced_fit_impl(
            xt_l, key, n_lists_local, max_iter, penalty, bal_cap)
        return c

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False))


@lru_cache(maxsize=16)
def _sharded_chunk_step_program(mesh: Mesh, axis: str, n_lists_local: int,
                                cap: int):
    """Data-parallel fused chunk step: every device runs
    :func:`_flat_chunk_step`'s body on ITS slice of the chunk against ITS
    local lists — one jitted shard_map program per chunk, slabs donated,
    zero cross-device data movement (rows only ever land in the lists of
    the shard they streamed through)."""
    from ..cluster.kmeans import _capped_assign_impl
    from ._packing import _scatter_append_impl

    def local(data_l, ids_l, counts_l, c_l, xc_l, idc_l):
        valid = idc_l >= 0
        labels, _ = _capped_assign_impl(xc_l, c_l, cap - counts_l, valid)
        (data_l, ids_l), counts_l = _scatter_append_impl(
            (data_l, ids_l), counts_l, labels, (xc_l, idc_l),
            n_lists=n_lists_local, cap=data_l.shape[1])
        return data_l, ids_l, counts_l

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(axis),) * 6, out_specs=(P(axis),) * 3,
        check_vma=False), donate_argnums=(0, 1, 2))


def build_chunked_sharded(dataset, mesh: Mesh,
                          params: Optional[IvfFlatIndexParams] = None, *,
                          chunk_rows: int = 0, source_ids=None,
                          axis: str = "shard") -> IvfFlatIndex:
    """Distributed streaming build — the build-side analog of
    :func:`search_sharded`: the dataset stays on host and each fixed-size
    chunk is split contiguously over the mesh axis (one sharded
    ``device_put``, staged a chunk ahead), with every device appending its
    slice into ITS OWN local lists via the fused donated chunk step.
    Combines :func:`build_chunked`'s out-of-core pipeline (fixed shapes,
    padded tail, single executable) with :func:`build_sharded`'s
    shard-local sub-index model (device s owns lists
    ``[s·L/S, (s+1)·L/S)`` trained on its own row stripes; ids are global
    row positions; :func:`search_sharded` probes every shard and merges).
    Per-device peak = local slabs + its chunk slice — corpora larger than
    ONE chip's HBM stream through S chips in parallel."""
    from jax.sharding import NamedSharding

    from ._packing import (build_heartbeat, chunked_shard_rows,
                           chunked_shard_trainsets, prefetch_chunks_padded,
                           resolve_chunk_rows, sharded_train_sizes)

    p = params or IvfFlatIndexParams()
    n, d = dataset.shape
    n_dev = int(mesh.shape[axis])
    n_lists_local = max(1, (p.n_lists + n_dev - 1) // n_dev)
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_flat")
    # chunks split evenly over the axis; never a chunk beyond one padded pass
    chunk_rows = min(-(-chunk_rows // n_dev), -(-n // n_dev)) * n_dev
    shard_valid = chunked_shard_rows(n, chunk_rows, n_dev)
    expects(int(shard_valid.min()) >= 1,
            f"chunk layout leaves a shard with no rows (n={n}, "
            f"chunk_rows={chunk_rows}, shards={n_dev}): lower chunk_rows "
            f"or use fewer shards")
    per = int(shard_valid.max())
    expects(n_lists_local <= per, "n_lists exceeds rows per shard")
    cap = max(1, int(np.ceil(p.list_cap_ratio * per / n_lists_local)))
    kp = KMeansParams()
    n_train, bal_cap = sharded_train_sizes(
        per, n_lists_local, p.kmeans_trainset_fraction, kp.balanced_max_ratio)
    dtype = jnp.asarray(np.asarray(dataset[:1])).dtype
    sharding = NamedSharding(mesh, P(axis))

    xt = chunked_shard_trainsets(dataset, n, chunk_rows, n_dev, n_train,
                                 p.seed)
    xt_sh = jax.device_put(xt.reshape(n_dev * n_train, d), sharding)
    train = _sharded_chunk_train_program(
        mesh, axis, n_lists_local, p.kmeans_n_iters,
        float(kp.balanced_penalty), bal_cap, p.seed)
    centroids = train(xt_sh)

    L = n_dev * n_lists_local
    slab_cap = slab_capacity(cap, dtype)
    data = jax.device_put(jnp.zeros((L, slab_cap, d), dtype), sharding)
    ids_slab = jax.device_put(jnp.full((L, slab_cap), -1, jnp.int32),
                              sharding)
    counts = jax.device_put(jnp.zeros((L,), jnp.int32), sharding)
    step = _sharded_chunk_step_program(mesh, axis, n_lists_local, cap)
    heartbeat = build_heartbeat("ivf_flat.build_chunked_sharded", n)
    for lo, hi, xc, idc in prefetch_chunks_padded(
            dataset, chunk_rows, source_ids, dtype=dtype, sharding=sharding):
        data, ids_slab, counts = step(data, ids_slab, counts, centroids,
                                      xc, idc)
        heartbeat(hi)
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
    return IvfFlatIndex(centroids, data, ids_slab, counts, norms, p.metric)


@partial(jax.jit, static_argnames=("k", "n_probes", "metric", "axis", "mesh",
                                   "data_axis", "probe_block"))
def _search_sharded_impl(mesh, axis, centroids, data, ids, counts, norms, q,
                         k: int, n_probes: int, metric: str,
                         data_axis: Optional[str] = None, keep=None,
                         probe_block: int = 1):
    def local(centroids_l, data_l, ids_l, counts_l, norms_l, q_l, keep_l):
        bv, bi = _search_impl(centroids_l, data_l, ids_l, counts_l, norms_l,
                              q_l, k, n_probes, metric, keep_l, probe_block)
        # candidates from all shards → final top-k everywhere
        if metric == "inner_product":
            bv = -bv  # back to min-selectable
        av = jax.lax.all_gather(bv, axis, tiled=False)  # [S, nq, k]
        ai = jax.lax.all_gather(bi, axis, tiled=False)
        av = jnp.moveaxis(av, 0, 1).reshape(q_l.shape[0], -1)
        ai = jnp.moveaxis(ai, 0, 1).reshape(q_l.shape[0], -1)
        from ..matrix.select_k import select_k

        fv, fi = select_k(av, k, in_idx=ai, select_min=True)
        if metric == "inner_product":
            fv = -fv
        return fv, fi

    qspec = P(data_axis) if data_axis else P()
    # keep masks GLOBAL source ids, so it rides replicated over the shard
    # axis; a 2-D bitmap's query rows follow the query partitioning
    kspec = (P(data_axis) if (keep is not None and keep.ndim == 2
                              and data_axis) else P())
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), qspec, kspec),
        out_specs=(qspec, qspec),
        check_vma=False,
    )(centroids, data, ids, counts, norms, q, keep)


def search_sharded(index: IvfFlatIndex, queries, k: int,
                   params: Optional[IvfFlatSearchParams] = None, *,
                   mesh: Mesh, axis: str = "shard",
                   data_axis: Optional[str] = None, filter=None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Multi-chip search: each shard probes its local lists (n_probes per
    shard — recall ≥ single-chip at equal n_probes), one all_gather merges.

    Per-shard probing searches each shard's nearest local lists, so the union
    over shards always covers the globally nearest lists.  On a 2-D mesh,
    ``data_axis`` partitions the queries over that axis (merges stay on the
    shard axis — see :func:`raft_tpu.core.make_hybrid_mesh`).

    ``filter``: bitset/bitmap prefilter over GLOBAL source ids, same
    contract as :func:`search` (replicated over the shard axis).
    """
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           resolve_probe_block, sentinel_filtered_ids)

    p = params or IvfFlatSearchParams()
    q = wrap_array(queries, ndim=2, name="queries")
    n_dev = int(mesh.shape[axis])
    local_lists = index.n_lists // n_dev
    n_probes = min(p.n_probes, local_lists)
    probe_block = resolve_probe_block(p.probe_block, int(n_probes),
                                      index.list_cap, "ivf_flat")
    if data_axis is not None:
        expects(data_axis in mesh.axis_names, f"axis {data_axis!r} not in mesh")
        expects(q.shape[0] % int(mesh.shape[data_axis]) == 0,
                "queries not divisible by data axis")
    keep = as_keep_mask(filter, nq=q.shape[0])
    if keep is not None:
        check_filter_covers_ids(keep, index.ids)
    dv, di = _search_sharded_impl(mesh, axis, index.centroids, index.data,
                                  index.ids, index.counts, index.norms, q,
                                  int(k), int(n_probes), index.metric,
                                  data_axis, keep, probe_block)
    if keep is not None:
        di = sentinel_filtered_ids(dv, di)
    return dv, di


@dataclasses.dataclass(frozen=True)
class IvfFlatFleetSlices:
    """Device-mesh layout of an IVF-Flat index for the serving fleet
    (:mod:`raft_tpu.serve.fleet`): the list axis padded to a multiple of
    the mesh axis and split contiguously — shard *s* owns global lists
    ``[s*lists_per, (s+1)*lists_per)`` — with the (padded) centroid
    table replicated so every shard ranks the SAME probe order as the
    single-device searcher."""

    centroids: jax.Array  # [S*lists_per, d] replicated; pads finite-far
    data: jax.Array       # [S*lists_per, cap, d] sharded P(axis)
    ids: jax.Array        # [S*lists_per, cap] sharded; pads -1
    counts: jax.Array     # [S*lists_per] sharded; pads 0
    norms: jax.Array      # [S*lists_per, cap] sharded; pads 0
    lists_per: int        # lists per shard (padded count / S)
    n_lists: int          # original (unpadded) list count


# far-but-finite centroid pad: +inf would reach the probe ranking as
# 0*inf = NaN through sq_l2's dot-product expansion; 1e15 ranks last in
# f32 against any real squared distance while staying NaN-free.
_FLEET_CENTROID_PAD = 1e15


def fleet_slices(index: IvfFlatIndex, mesh: Mesh, *,
                 axis: str = "shard") -> IvfFlatFleetSlices:
    """Slice an :class:`IvfFlatIndex` over ``mesh[axis]`` for the fleet
    fan-out.  All padding happens host-side (numpy) and the slabs are
    ``device_put`` with their target sharding, so the single-device peak
    is one shard's slice — never the whole index."""
    from jax.sharding import NamedSharding

    expects(axis in mesh.axis_names, f"axis {axis!r} not in mesh")
    expects(jnp.issubdtype(jnp.asarray(index.centroids).dtype,
                           jnp.floating),
            "fleet slicing needs a float centroid table (the list-axis "
            "pad is a finite-far float sentinel)")
    n_dev = int(mesh.shape[axis])
    L = index.n_lists
    lp = (L + n_dev - 1) // n_dev
    pad = lp * n_dev - L

    def _pad0(x, fill):
        x = np.asarray(x)
        if not pad:
            return x
        shape = (pad,) + x.shape[1:]
        return np.concatenate([x, np.full(shape, fill, x.dtype)], axis=0)

    cen = _pad0(index.centroids, _FLEET_CENTROID_PAD)
    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P(axis))
    return IvfFlatFleetSlices(
        centroids=jax.device_put(jnp.asarray(cen), rep),
        data=jax.device_put(jnp.asarray(_pad0(index.data, 0)), sh),
        ids=jax.device_put(jnp.asarray(_pad0(index.ids, -1)), sh),
        counts=jax.device_put(jnp.asarray(_pad0(index.counts, 0)), sh),
        norms=jax.device_put(jnp.asarray(_pad0(index.norms, 0)), sh),
        lists_per=int(lp), n_lists=int(L))
