"""IVF-RaBitQ — inverted-file index with 1-bit random-rotation codes.

The third rung of the memory-vs-recall ladder (flat → pq → rabitq,
docs/perf_analysis.md): each stored vector keeps only the SIGN of its
randomly-rotated residual — ⌈d/8⌉ bytes of code versus ``d`` bytes of
int8-PQ or ``4d`` of f32 — plus three f32 correction scalars, and the
scan estimates distances from those codes with RaBitQ's unbiased
estimator (PAPERS.md).  Returned values are EXACT: the estimator only
gates the candidate set (an unsorted top-``rerank_k`` fold over
estimates), and the survivors re-score against the raw row slab through
the same ``exact_gathered_dots`` tier every exact engine uses.

Design deltas vs :mod:`.ivf_flat` (everything else is shared):

* **No trained codebook.**  The encoder is one seeded random rotation
  (QR of a gaussian, a per-index constant) — no PQ codebook k-means, so
  building is assignment-bound and beats ``ivf_pq.build`` rows/s
  (bench/RABITQ_CPU.json).
* **Packed-binary scoring path.**  The probe scan gathers packed code
  bytes (8 dims/byte — the HBM read is 32× below the f32 slab's),
  unpacks AFTER the gather, and scores ``⟨sign(r), q8⟩`` as ONE int8
  MXU einsum per block (:func:`raft_tpu.ops.blocked_scan
  .packed_sign_dots` — popcount-as-int8-einsum).  The query-side work
  (rotation, int8 quantization) hoists once per query, the PR 3
  ADC-LUT pattern.
* **Estimate → rerank.**  Per block the unbiased estimate folds into an
  unsorted top-``rerank_k`` carry with the flat-slab pointer as a
  payload lane; after the scan the finalists re-gather from ``data``
  and re-score exactly, then ONE ranked selection cuts to k.  With
  ``rerank_k = n`` every candidate survives, making results
  bit-identical to ``brute_force`` (values AND ids) — the
  tests/test_ivf_rabitq.py oracle.

Estimator algebra (RaBitQ, PAPERS.md): store ``s = sign(P(x−c))``
packed, ``sabs = Σ|P(x−c)| = ⟨s, P(x−c)⟩``, ``rn2 = ‖x−c‖²`` and
``cs = ⟨s, Pc⟩``.  With the hoisted ``⟨s, Pq⟩ ≈ Δ·⟨s, q8⟩``:

    ⟨x−c, q−c⟩ ≈ (rn2 / sabs) · (Δ·⟨s, q8⟩ − cs)
    ‖q−x‖²     ≈ ‖q−c‖² + rn2 − 2·⟨x−c, q−c⟩
    ⟨q, x⟩     ≈ ⟨q, c⟩ + (rn2 / sabs) · Δ·⟨s, q8⟩

(the projection of the unit residual onto its sign code, ``sabs``,
normalizes the estimate — the codebook-free unbiasing that lets a plain
sign code rank; ``sabs ≤ 0`` degenerates to the centroid distance).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cluster.kmeans import KMeansParams, capped_assign, kmeans_balanced_fit
from ..core import tracing
from ..core.array import wrap_array
from ..core.errors import expects
from ..distance.pairwise import sq_l2
from ..ops import blocked_scan as _scan

__all__ = [
    "IvfRabitqIndexParams",
    "IvfRabitqSearchParams",
    "IvfRabitqIndex",
    "build",
    "build_chunked",
    "search",
    "searcher",
    "extend",
    "resolve_rerank_k",
    "fleet_slices",
    "IvfRabitqFleetSlices",
]


@dataclasses.dataclass(frozen=True)
class IvfRabitqIndexParams:
    """Build configuration (per-call parameter struct idiom).  No
    codebook knobs: the encoder is one seeded random rotation."""

    n_lists: int = 1024
    metric: str = "sqeuclidean"  # sqeuclidean | euclidean | inner_product
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.1
    list_cap_ratio: float = 2.0  # capacity = ratio * n / n_lists
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class IvfRabitqSearchParams:
    n_probes: int = 32
    # exact-rerank candidate count: the estimator scan keeps this many
    # best estimates (unsorted fold), survivors re-score exactly.  0 =
    # auto (max(32, 8·k)).  rerank_k = index.size makes results
    # bit-identical to brute force; this is THE recall knob
    # (docs/tuning_guide.md).
    rerank_k: int = 0
    query_chunk: int = 4096  # cap on the per-dispatch gather working set
    # probes gathered+scored+merged per scan step; 0 = auto (the shared
    # probe_block table/heuristic).  Results are bit-identical for every
    # value — pure speed knob.
    probe_block: int = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IvfRabitqIndex:
    centroids: jax.Array    # [L, d]
    rotation: jax.Array     # [d, d] f32 orthonormal P (rows = new basis)
    codes: jax.Array        # [L, cap, ceil(d/8)] uint8 packed sign bits
    sabs: jax.Array         # [L, cap] f32  Σ|P(x−c)|  (estimator scale)
    res_norms: jax.Array    # [L, cap] f32  ‖x−c‖²
    code_cdots: jax.Array   # [L, cap] f32  ⟨sign(P(x−c)), Pc⟩
    data: jax.Array         # [L, cap, d] raw rows (exact-rerank tier)
    ids: jax.Array          # [L, cap] int32, -1 pad
    counts: jax.Array       # [L] int32
    metric: str = dataclasses.field(metadata=dict(static=True))

    @property
    def n_lists(self) -> int:
        return int(self.codes.shape[0])

    @property
    def list_cap(self) -> int:
        return int(self.codes.shape[1])

    @property
    def dim(self) -> int:
        return int(self.data.shape[2])

    @property
    def size(self) -> int:
        return int(jnp.sum(self.counts))  # jaxlint: disable=JX01 size is a host-facing API scalar, not on the search path


def _rotation(d: int, seed: int) -> jax.Array:
    """The per-index random rotation: QR of a seeded gaussian, sign-fixed
    so the factorization is deterministic.  Rows are the rotated basis —
    apply as ``x @ rotation.T``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x5AB1)
    g = jax.random.normal(key, (d, d), jnp.float32)
    q, r = jnp.linalg.qr(g)
    return (q * jnp.sign(jnp.diagonal(r))[None, :]).T


def _rotated_centroids(centroids, rotation) -> jax.Array:
    """``Pc`` [L, d] — encode-time constant (search never needs it; the
    per-vector ``cs`` scalars already carry ``⟨s, Pc⟩``)."""
    return jnp.einsum("ld,ed->le", centroids.astype(jnp.float32), rotation,
                      precision=jax.lax.Precision.HIGHEST)


def _encode(x, labels, centroids, rotation, rotc):
    """Per-row RaBitQ encoding: packed sign codes + the three correction
    scalars.  Rows with label −1 (pad/dropped) encode garbage the
    scatter/pack drops — values never matter.  All arithmetic in f32 at
    HIGHEST precision: the encode must be bit-stable across batch
    slicing so chunked builds and online extends reproduce the one-shot
    build exactly (tests/test_ivf_rabitq.py pins this)."""
    xf = x.astype(jnp.float32)
    cl = jnp.clip(labels, 0, centroids.shape[0] - 1)
    r = xf - centroids.astype(jnp.float32)[cl]
    rr = jnp.einsum("nd,ed->ne", r, rotation,
                    precision=jax.lax.Precision.HIGHEST)
    codes = _scan.pack_sign_bits(rr)
    s = jnp.where(rr >= 0, 1.0, -1.0)
    sabs = jnp.sum(jnp.abs(rr), axis=1)
    rn2 = jnp.sum(r * r, axis=1)
    cs = jnp.sum(s * rotc[cl], axis=1)
    return codes, sabs, rn2, cs


@tracing.annotate("ivf_rabitq.build")
def build(dataset, params: Optional[IvfRabitqIndexParams] = None, *,
          source_ids=None, res=None) -> IvfRabitqIndex:
    """Train the coarse quantizer, encode every row (one rotation einsum
    + sign pack — no codebook training, the rows/s edge over
    ``ivf_pq.build``), and pack inverted lists on device."""
    p = params or IvfRabitqIndexParams()
    x = wrap_array(dataset, ndim=2, name="dataset")
    n, d = x.shape
    expects(p.n_lists >= 1 and p.n_lists <= n, "n_lists out of range")
    expects(p.metric in ("sqeuclidean", "euclidean", "inner_product"),
            f"unsupported metric {p.metric!r}")
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))

    n_train = min(n, max(p.n_lists * 4, int(n * p.kmeans_trainset_fraction)))
    key = jax.random.PRNGKey(p.seed)
    sel = (jax.random.permutation(key, n)[:n_train] if n_train < n
           else jnp.arange(n))
    kp = KMeansParams(n_clusters=p.n_lists, max_iter=p.kmeans_n_iters,
                      seed=p.seed)
    centroids, _, _ = kmeans_balanced_fit(x[sel], kp)

    rotation = _rotation(d, p.seed)
    rotc = _rotated_centroids(centroids, rotation)
    labels, _ = capped_assign(x, centroids, cap)
    codes, sabs, rn2, cs = _encode(x, labels, centroids, rotation, rotc)

    from ._packing import pack_lists

    ids = (jnp.asarray(source_ids, jnp.int32) if source_ids is not None
           else jnp.arange(n, dtype=jnp.int32))
    (codes, sabs, rn2, cs, data, out_ids), counts = pack_lists(
        labels, (codes, sabs, rn2, cs, x, ids), n_lists=p.n_lists, cap=cap,
        fills=(0, 0.0, 0.0, 0.0, 0.0, -1))
    return IvfRabitqIndex(centroids, rotation, codes, sabs, rn2, cs,
                          data, out_ids, counts, p.metric)


def _rabitq_step_impl(slabs, counts, centroids, rotation, rotc, xc, idc, *,
                      n_lists: int, cap: int):
    """ONE fused program per chunk (the PR 4 slab-donating pipeline):
    masked capped assignment against remaining room + RaBitQ encode +
    scatter-append over all six payload slabs.  Pad rows (``idc < 0``)
    never request a list, never consume capacity, and scatter-drop via
    label −1 — the padded stream is bit-identical to the per-op loop.

    Two jitted forms, exactly the flat pattern:
    :func:`_rabitq_chunk_step` donates the slabs (build loops own their
    buffers); :func:`_rabitq_chunk_step_cow` leaves the inputs alive for
    the copy-on-write first step of the online :func:`extend`."""
    from ..cluster.kmeans import _capped_assign_impl
    from ._packing import _scatter_append_impl

    valid = idc >= 0
    labels, _ = _capped_assign_impl(xc, centroids, cap - counts, valid)
    codes, sabs, rn2, cs = _encode(xc, labels, centroids, rotation, rotc)
    return _scatter_append_impl(slabs, counts, labels,
                                (codes, sabs, rn2, cs, xc, idc),
                                n_lists=n_lists, cap=cap)


_rabitq_chunk_step = partial(jax.jit, static_argnames=("n_lists", "cap"),
                             donate_argnums=(0, 1))(_rabitq_step_impl)
_rabitq_chunk_step_cow = partial(
    jax.jit, static_argnames=("n_lists", "cap"))(_rabitq_step_impl)


def _empty_slabs(n_lists: int, cap: int, d: int, dtype):
    """Fresh device slab set (compiled fills — guard-clean under
    ``transfer_guard("disallow")``)."""
    from ._packing import device_full

    db = -(-d // 8)
    return (device_full((n_lists, cap, db), 0, jnp.uint8),
            device_full((n_lists, cap), 0.0, jnp.float32),
            device_full((n_lists, cap), 0.0, jnp.float32),
            device_full((n_lists, cap), 0.0, jnp.float32),
            device_full((n_lists, cap, d), 0, dtype),
            device_full((n_lists, cap), -1, jnp.int32))


def _stream_pipelined(dataset, centroids, rotation, p: IvfRabitqIndexParams,
                      n: int, cap: int, chunk_rows: int, source_ids, dtype,
                      heartbeat=None):
    """Pipelined chunk engine: fixed-shape double-buffered device staging
    feeding the fused donated :func:`_rabitq_chunk_step` — one
    executable, one dispatch per chunk."""
    from ._packing import device_full, prefetch_chunks_padded

    d = dataset.shape[1]
    slabs = _empty_slabs(p.n_lists, cap, d, dtype)
    counts = device_full((p.n_lists,), 0, jnp.int32)
    rotc = _rotated_centroids(centroids, rotation)
    for lo, hi, xc, idc in prefetch_chunks_padded(dataset, chunk_rows,
                                                  source_ids, dtype=dtype):
        slabs, counts = _rabitq_chunk_step(
            slabs, counts, centroids, rotation, rotc, xc, idc,
            n_lists=p.n_lists, cap=cap)
        if heartbeat is not None:
            heartbeat(hi)
    return slabs, counts


def _stream_perop(dataset, centroids, rotation, p: IvfRabitqIndexParams,
                  n: int, cap: int, chunk_rows: int, source_ids, dtype):
    """Reference per-op chunk loop: blocking H2D ``jnp.asarray``,
    separate assign / encode / scatter dispatches, tail chunk at its own
    shape.  The bit-parity oracle for the fused engine and the A/B
    baseline of ``bench/build_throughput.py``."""
    from ..cluster.kmeans import capped_assign_room
    from ._packing import prefetch_chunks, scatter_append

    d = dataset.shape[1]
    db = -(-d // 8)
    slabs = (jnp.zeros((p.n_lists, cap, db), jnp.uint8),
             jnp.zeros((p.n_lists, cap), jnp.float32),
             jnp.zeros((p.n_lists, cap), jnp.float32),
             jnp.zeros((p.n_lists, cap), jnp.float32),
             jnp.zeros((p.n_lists, cap, d), dtype),
             jnp.full((p.n_lists, cap), -1, jnp.int32))
    counts = jnp.zeros((p.n_lists,), jnp.int32)
    rotc = _rotated_centroids(centroids, rotation)
    for lo, hi, xc_h, idc_h in prefetch_chunks(dataset, chunk_rows,
                                               source_ids):
        xc = jnp.asarray(xc_h, dtype)
        idc = jnp.asarray(idc_h, jnp.int32)
        labels, _ = capped_assign_room(xc, centroids, cap - counts)
        codes, sabs, rn2, cs = _encode(xc, labels, centroids, rotation, rotc)
        slabs, counts = scatter_append(
            slabs, counts, labels, (codes, sabs, rn2, cs, xc, idc),
            n_lists=p.n_lists, cap=cap)
    return slabs, counts


def build_chunked(dataset, params: Optional[IvfRabitqIndexParams] = None, *,
                  chunk_rows: int = 0, source_ids=None,
                  res=None) -> IvfRabitqIndex:
    """Out-of-core build on the PR 4 pipeline: the dataset stays on host
    and streams through the fused slab-donating chunk step (see
    :func:`raft_tpu.neighbors.ivf_flat.build_chunked` — same engine, the
    encode rides inside the chunk program).  Device peak = six list
    slabs + two staged chunks; ``chunk_rows=0`` = auto
    (:func:`~._packing.resolve_chunk_rows`)."""
    from .ivf_flat import _coarse_train_chunked
    from ._packing import build_heartbeat, resolve_chunk_rows

    p = params or IvfRabitqIndexParams()
    n, d = dataset.shape
    expects(p.n_lists >= 1 and p.n_lists <= n, "n_lists out of range")
    expects(p.metric in ("sqeuclidean", "euclidean", "inner_product"),
            f"unsupported metric {p.metric!r}")
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))
    dtype = jnp.asarray(np.asarray(dataset[:1])).dtype
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_rabitq")

    centroids = _coarse_train_chunked(dataset, p, n)
    rotation = _rotation(d, p.seed)
    (codes, sabs, rn2, cs, data, ids_slab), counts = _stream_pipelined(
        dataset, centroids, rotation, p, n, cap, chunk_rows, source_ids,
        dtype, heartbeat=build_heartbeat("ivf_rabitq.build_chunked", n))
    return IvfRabitqIndex(centroids, rotation, codes, sabs, rn2, cs,
                          data, ids_slab, counts, p.metric)


def _build_chunked_perop(dataset,
                         params: Optional[IvfRabitqIndexParams] = None, *,
                         chunk_rows: int = 0,
                         source_ids=None) -> IvfRabitqIndex:
    """:func:`build_chunked` on the reference per-op chunk loop — the
    parity oracle / A/B baseline; not part of the public API."""
    from .ivf_flat import _coarse_train_chunked
    from ._packing import resolve_chunk_rows

    p = params or IvfRabitqIndexParams()
    n, d = dataset.shape
    expects(p.n_lists >= 1 and p.n_lists <= n, "n_lists out of range")
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))
    dtype = jnp.asarray(np.asarray(dataset[:1])).dtype
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_rabitq")
    centroids = _coarse_train_chunked(dataset, p, n)
    rotation = _rotation(d, p.seed)
    (codes, sabs, rn2, cs, data, ids_slab), counts = _stream_perop(
        dataset, centroids, rotation, p, n, cap, chunk_rows, source_ids,
        dtype)
    return IvfRabitqIndex(centroids, rotation, codes, sabs, rn2, cs,
                          data, ids_slab, counts, p.metric)


def extend(index: IvfRabitqIndex, new_vectors, new_ids=None, *,
           insert_chunk: int = 0) -> IvfRabitqIndex:
    """Online streaming insert through the fused slab-donating chunk
    step — the :func:`raft_tpu.neighbors.ivf_flat.extend` contract
    verbatim (copy-on-write first step, fixed insert bucket, one scalar
    spill check, geometric slab growth), with the RaBitQ encode fused
    into the chunk program.  With capacity to spare, extending is
    bit-identical to a from-scratch pack at the same centroids."""
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
    rotc = _rotated_centroids(index.centroids, index.rotation)

    def stream(slabs, counts, slab_cap):
        step = _rabitq_chunk_step_cow  # inputs may back a live snapshot
        for xc, idc in staged_insert_chunks(x, ids, chunk, index.data.dtype):
            slabs, counts = step(slabs, counts, index.centroids,
                                 index.rotation, rotc, xc, idc,
                                 n_lists=L, cap=slab_cap)
            step = _rabitq_chunk_step  # fresh private buffers: donate
        return slabs, counts

    src = (index.codes, index.sabs, index.res_norms, index.code_cdots,
           index.data, index.ids)
    slabs, counts = stream(src, index.counts, cap)
    placed = int(jax.device_get(jnp.sum(counts))) - base  # jaxlint: disable=JX01 explicit spill check: one scalar per extend gates the rare slab-growth path
    if placed < n_new:  # capacity exhausted — grow + re-run (rare)
        xd = jnp.asarray(x.astype(index.data.dtype, copy=False))
        labels = jnp.argmin(sq_l2(xd, index.centroids), axis=1)
        added = jax.ops.segment_sum(jnp.ones_like(labels, jnp.int32),
                                    labels, num_segments=L)
        need = int(jnp.max(index.counts + added))  # jaxlint: disable=JX01 slab capacity must be a host int at extend time (static shapes)
        new_cap = max(need, cap + (cap + 1) // 2)  # geometric headroom
        pad = new_cap - cap

        def grow(slab, fill):
            width = ((0, 0), (0, pad)) + ((0, 0),) * (slab.ndim - 2)
            return jnp.pad(slab, width, constant_values=fill)

        grown = tuple(grow(s, f) for s, f in
                      zip(src, (0, 0.0, 0.0, 0.0, 0.0, -1)))
        slabs, counts = stream(grown, index.counts, new_cap)
    codes, sabs, rn2, cs, data, out_ids = slabs
    return IvfRabitqIndex(index.centroids, index.rotation, codes, sabs,
                          rn2, cs, data, out_ids, counts, index.metric)


def _estimate_survivors(qf, cd, centroids, rotation, codes, sabs,
                        res_norms, code_cdots, ids, counts, probes,
                        rerank_k: int, metric: str, keep=None,
                        probe_block: int = 1):
    """Probe-blocked estimator scan: the device half shared by the
    in-memory rerank (:func:`_estimate_scan`) and the out-of-core tier
    (:mod:`~raft_tpu.neighbors.ooc`, which reranks against host shards).

    Per block: gather PACKED code bytes (the bandwidth win — ⌈d/8⌉
    bytes/row move, not 4d), score ``⟨s, q8⟩`` via the shared
    packed-binary path, apply the unbiased estimator with the gathered
    per-vector scalars, and fold an unsorted top-``rerank_k`` carrying
    the flat-slab pointer payload.  Returns ``(bv, bi, bp)``: estimator
    values, survivor source ids, and flat raw-slab pointers (meaningful
    only when a raw slab exists — the out-of-core tier ignores it)."""
    from ._packing import blocked_probe_plan, keep_lookup

    nq = qf.shape[0]
    cap = codes.shape[1]
    lists_xs, pvalid = blocked_probe_plan(probes, probe_block)

    # hoisted once per query (the PR 3 ADC-LUT pattern): rotate the
    # query and quantize to int8 for the MXU popcount-einsum
    qrot = jnp.einsum("qd,ed->qe", qf, rotation,
                      precision=jax.lax.Precision.HIGHEST)
    delta = jnp.max(jnp.abs(qrot), axis=1) / 127.0
    delta = jnp.where(delta > 0.0, delta, 1.0)
    q8 = jnp.round(qrot / delta[:, None]).astype(jnp.int8)
    qc = (jnp.einsum("qd,ld->ql", qf, centroids.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
          if metric == "inner_product" else None)

    def score(inp):
        lists, pv = inp                            # [nq, B], [B]
        bcap = lists.shape[1] * cap
        sq = _scan.slab_dots(codes[lists], q8,
                             packed_sign=True).reshape(nq, bcap)
        sa = sabs[lists].reshape(nq, bcap)
        rn2 = res_norms[lists].reshape(nq, bcap)
        vids = ids[lists].reshape(nq, bcap)
        g = jnp.where(sa > 0.0, rn2 / sa, 0.0)     # estimator scale
        sqf = delta[:, None] * sq                  # ≈ ⟨s, Pq⟩
        if metric == "inner_product":
            qcl = jnp.repeat(jnp.take_along_axis(qc, lists, axis=1),
                             cap, axis=1)
            est = -(qcl + g * sqf)
        else:
            cs = code_cdots[lists].reshape(nq, bcap)
            cdl = jnp.repeat(jnp.take_along_axis(cd, lists, axis=1),
                             cap, axis=1)
            est = jnp.maximum(cdl + rn2 - 2.0 * g * (sqf - cs), 0.0)
        valid = (jnp.arange(cap)[None, None, :]
                 < counts[lists][:, :, None]).reshape(nq, bcap)
        valid = valid & (vids >= 0) & jnp.repeat(pv, cap)[None, :]
        if keep is not None:
            valid = valid & keep_lookup(keep, vids)
        ptr = _scan.list_slab_ptr(lists, cap)
        return jnp.where(valid, est, jnp.inf), vids, ptr

    def step(carry, inp):
        bv, bi, bp = carry
        est, vids, ptr = score(inp)
        mv, mi, (mp,) = _scan.fold_topk_payload(bv, bi, (bp,), est, vids,
                                                (ptr,), rerank_k)
        return (mv, mi, mp), None

    bv0, bi0 = _scan.topk_carry(nq, rerank_k)
    bp0 = jnp.zeros((nq, rerank_k), jnp.int32)
    (bv, bi, bp), _ = jax.lax.scan(step, (bv0, bi0, bp0),
                                   (lists_xs, pvalid))
    return bv, bi, bp


def _estimate_scan(q, qf, qn, cd, centroids, rotation, codes, sabs,
                   res_norms, code_cdots, data, ids, counts, probes,
                   k: int, rerank_k: int, metric: str, keep=None,
                   probe_block: int = 1):
    """Estimator scan + exact rerank.  After
    :func:`_estimate_survivors`, the survivors re-gather from the raw
    slab and re-score exactly through the
    :func:`~raft_tpu.ops.blocked_scan.l2_rescorer` seam (stored-norm-free
    form — the norms recompute from the gathered rows in brute-force
    accumulation order, which is what makes ``rerank_k = n`` bit-match
    ``brute_force.knn``); ONE ranked selection cuts to k."""
    bv, bi, bp = _estimate_survivors(qf, cd, centroids, rotation, codes,
                                     sabs, res_norms, code_cdots, ids,
                                     counts, probes, rerank_k, metric,
                                     keep, probe_block)
    rescore = _scan.l2_rescorer(data, q, qn, metric)
    dist = rescore(bp, bi)
    dist = jnp.where(jnp.isfinite(bv) & (bi >= 0), dist, jnp.inf)
    return _scan.ranked_finish(dist, bi, k)


@partial(jax.jit, static_argnames=("k", "n_probes", "rerank_k", "metric",
                                   "probe_block"))
def _search_impl(centroids, rotation, codes, sabs, res_norms, code_cdots,
                 data, ids, counts, q, k: int, n_probes: int,
                 rerank_k: int, metric: str, keep=None,
                 probe_block: int = 1):
    qf = q.astype(jnp.float32)
    qn = _scan.row_sq_norms(qf)   # dot-contraction; bit-stable across
    # the single-device and fleet SPMD executables (serve bit-identity)
    cd = sq_l2(q, centroids)                      # [nq, L] MXU block
    _, probes = jax.lax.top_k(-cd, n_probes)      # nearest lists
    bv, bi = _estimate_scan(q, qf, qn, cd, centroids, rotation, codes,
                            sabs, res_norms, code_cdots, data, ids, counts,
                            probes, k, rerank_k, metric, keep, probe_block)
    if metric == "euclidean":
        bv = jnp.sqrt(jnp.maximum(bv, 0.0))
    elif metric == "inner_product":
        bv = -bv
    return bv, bi


def resolve_rerank_k(requested: int, k: int, n_probes: int,
                     cap: int) -> int:
    """Static exact-rerank width.  ``requested > 0`` wins (must be ≥ k);
    ``0`` = auto: ``max(32, 8·k)``.  Unlike ``probe_block`` this knob
    changes RESULTS (it gates the candidate set).  Clamped to the
    probed-candidate total.  Pure host-int arithmetic."""
    total = max(1, int(n_probes) * int(cap))
    if requested:
        expects(int(requested) >= int(k),
                f"rerank_k ({requested}) must be >= k ({k})")
        return max(int(k), min(int(requested), total))
    return max(int(k), min(max(32, 8 * int(k)), total))


def _resolved_static(index, k: int, p) -> Tuple[int, int, int]:
    """The shared search/searcher static-knob resolution: (n_probes,
    probe_block, rerank_k).  Also serves the out-of-core tier
    (:mod:`~raft_tpu.neighbors.ooc`), whose device half is this
    family's estimator scan — ``index`` only needs ``n_lists`` and
    ``list_cap``."""
    from ._packing import resolve_probe_block

    n_probes = int(min(p.n_probes, index.n_lists))
    probe_block = resolve_probe_block(p.probe_block, n_probes,
                                      index.list_cap, "ivf_rabitq")
    rerank_k = resolve_rerank_k(p.rerank_k, int(k), n_probes,
                                index.list_cap)
    return n_probes, probe_block, rerank_k


@tracing.annotate("ivf_rabitq.search")
def search(index: IvfRabitqIndex, queries, k: int,
           params: Optional[IvfRabitqSearchParams] = None, *, filter=None,
           res=None) -> Tuple[jax.Array, jax.Array]:
    """Approximate kNN with EXACT returned values: the estimator gates
    the candidate set (recall rides ``n_probes`` × ``rerank_k``), the
    survivors re-score against the raw rows.  ``filter``: optional
    prefilter by source id, the shared bitset/bitmap contract."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           chunked_filtered_queries, sentinel_filtered_ids)

    p = params or IvfRabitqSearchParams()
    q = wrap_array(queries, ndim=2, name="queries")
    expects(q.shape[1] == index.dim, "query dim mismatch")
    n_probes, probe_block, rerank_k = _resolved_static(index, k, p)
    keep = as_keep_mask(filter, nq=q.shape[0])  # indexes source ids
    if keep is not None:
        check_filter_covers_ids(keep, index.ids)

    impl = lambda qc, kc: _search_impl(
        index.centroids, index.rotation, index.codes, index.sabs,
        index.res_norms, index.code_cdots, index.data, index.ids,
        index.counts, qc, int(k), n_probes, rerank_k, index.metric, kc,
        probe_block)
    dv, di = chunked_filtered_queries(impl, q, int(p.query_chunk), keep)
    if keep is not None:  # sub-k survivors: sentinel tail, not real ids
        di = sentinel_filtered_ids(dv, di)
    return dv, di


def searcher(index: IvfRabitqIndex, k: int,
             params: Optional[IvfRabitqSearchParams] = None, *,
             filter=None):
    """Uniform serving entry point (``raft_tpu.serve`` contract):
    ``(fn, operands)`` with ``fn(queries, *operands)`` equal to
    :func:`search` for batches up to ``params.query_chunk`` rows.  The
    slabs ride as operands so bucket executables share them; an optional
    shared bitset filter rides as one more operand (tombstone deletes
    swap the mask without recompiling)."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           sentinel_filtered_ids)

    p = params or IvfRabitqSearchParams()
    expects(k >= 1, "k must be >= 1")
    n_probes, probe_block, rerank_k = _resolved_static(index, k, p)
    metric = index.metric
    keep = as_keep_mask(filter)
    if keep is not None:
        expects(keep.ndim == 1,
                "serving filters are shared bitsets (1-D); per-query "
                "bitmaps can't ride a fixed operand across buckets")
        check_filter_covers_ids(keep, index.ids)

        def fn(q, centroids, rotation, codes, sabs, res_norms, code_cdots,
               data, ids, counts, kp):
            dv, di = _search_impl(centroids, rotation, codes, sabs,
                                  res_norms, code_cdots, data, ids, counts,
                                  q, int(k), n_probes, rerank_k, metric,
                                  kp, probe_block)
            return dv, sentinel_filtered_ids(dv, di)

        return fn, (index.centroids, index.rotation, index.codes,
                    index.sabs, index.res_norms, index.code_cdots,
                    index.data, index.ids, index.counts, keep)

    def fn(q, centroids, rotation, codes, sabs, res_norms, code_cdots,
           data, ids, counts):
        return _search_impl(centroids, rotation, codes, sabs, res_norms,
                            code_cdots, data, ids, counts, q, int(k),
                            n_probes, rerank_k, metric, None, probe_block)

    return fn, (index.centroids, index.rotation, index.codes, index.sabs,
                index.res_norms, index.code_cdots, index.data, index.ids,
                index.counts)


@dataclasses.dataclass(frozen=True)
class IvfRabitqFleetSlices:
    """Device-mesh layout of an IVF-RaBitQ index for the serving fleet
    (:mod:`raft_tpu.serve.fleet`): list axis padded to a multiple of the
    mesh axis and split contiguously (shard *s* owns global lists
    ``[s*lists_per, (s+1)*lists_per)``); the padded centroid table and
    the rotation are replicated so every shard quantizes the query and
    ranks probes identically to the single-device searcher."""

    centroids: jax.Array    # [S*lists_per, d] replicated; pads finite-far
    rotation: jax.Array     # [d, d] replicated
    codes: jax.Array        # [S*lists_per, cap, d/8] sharded P(axis)
    sabs: jax.Array         # [S*lists_per, cap] sharded; pads 0
    res_norms: jax.Array    # [S*lists_per, cap] sharded; pads 0
    code_cdots: jax.Array   # [S*lists_per, cap] sharded; pads 0
    data: jax.Array         # [S*lists_per, cap, d] sharded; pads 0
    ids: jax.Array          # [S*lists_per, cap] sharded; pads -1
    counts: jax.Array       # [S*lists_per] sharded; pads 0
    lists_per: int
    n_lists: int


def fleet_slices(index: IvfRabitqIndex, mesh, *,
                 axis: str = "shard") -> IvfRabitqFleetSlices:
    """Slice an :class:`IvfRabitqIndex` over ``mesh[axis]`` for the
    fleet fan-out.  Padding is host-side numpy and every slab is
    ``device_put`` with its target sharding (single-device peak = one
    shard's slice).  The centroid pad is the same far-but-finite
    sentinel as :func:`ivf_flat.fleet_slices` — +inf turns into NaN
    through ``sq_l2``'s dot expansion."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .ivf_flat import _FLEET_CENTROID_PAD

    expects(axis in mesh.axis_names, f"axis {axis!r} not in mesh")
    expects(jnp.issubdtype(jnp.asarray(index.centroids).dtype,
                           jnp.floating),
            "fleet slicing needs a float centroid table")
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

    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P(axis))
    return IvfRabitqFleetSlices(
        centroids=jax.device_put(
            jnp.asarray(_pad0(index.centroids, _FLEET_CENTROID_PAD)), rep),
        rotation=jax.device_put(jnp.asarray(np.asarray(index.rotation)),
                                rep),
        codes=jax.device_put(jnp.asarray(_pad0(index.codes, 0)), sh),
        sabs=jax.device_put(jnp.asarray(_pad0(index.sabs, 0)), sh),
        res_norms=jax.device_put(jnp.asarray(_pad0(index.res_norms, 0)), sh),
        code_cdots=jax.device_put(jnp.asarray(_pad0(index.code_cdots, 0)),
                                  sh),
        data=jax.device_put(jnp.asarray(_pad0(index.data, 0)), sh),
        ids=jax.device_put(jnp.asarray(_pad0(index.ids, -1)), sh),
        counts=jax.device_put(jnp.asarray(_pad0(index.counts, 0)), sh),
        lists_per=int(lp), n_lists=int(L))
