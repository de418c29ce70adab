"""One blocked-scan core for every neighbors engine.

The probe-blocked IVF engines (PR 3), the frontier-blocked CAGRA engine
(PR 5), and the tiled brute-force scan all share one shape:

    slab gather → batch-dim distance einsum → select_k(sorted=False) fold
    per block → ONE ranked selection at exit

but until this module each engine carried its own copy of the fold/carry
boilerplate, so there was no single place to land a fused kernel.  This
module owns the contract:

* :func:`slab_dots` — the batch-dim scoring einsum with the **pinned
  per-candidate accumulation shape**: the block axis stays a *batch*
  dimension (``"qbcd,qbd->qbc"``), so the inner ``[cap, d]·[d]`` f32
  accumulation order is identical for every block size.  Folding the
  block axis into the candidate axis would retile the reduction and break
  the PR 3/5 bit-invariance contract (blocked results bit-identical to
  the per-item reference engines for ANY block size).
* :func:`fold_topk` / :func:`fold_topk_payload` — the
  ``select_k(sorted=False)`` fold, without and with payload lanes
  (CAGRA's explored flags, IVF-RaBitQ's slab pointers).
* :func:`scan_topk` — the ``scan(carry, slab) -> carry`` driver: carry
  init, per-block fold, ranked exit selection.
* :func:`scan_topk_grouped` — the list-major scan of IVF-Flat and of the
  IVF-PQ recon tier, for batches that share lists: :func:`grouped_plan`
  sorts the (query, list) pairs by list into tiles of
  :data:`GROUPED_TILE` query slots, and one Pallas kernel
  (``ops/pallas/grouped_scan.py``) scores each tile with one MXU product
  against its list and keeps each row's exact top-k.  The cross-block
  bit-invariance contract of :func:`slab_dots` is the query-major path's;
  the grouped path guarantees instead the same candidate set, distances
  within a few f32 ulps of the query-major ones (only the dot's
  accumulation order differs), and results independent of
  ``probe_block``.
* :func:`scan_path` — the ONE rule that picks between the two probe
  scans, made while a search program is traced, from its static shapes
  and the platform.

Quantized-scan sub-API
----------------------

The scan core also owns the *quantized* scoring tier — the packed-code
helpers every compressed engine shares, promoted here from private
``ivf_pq``/``_packing`` homes so 4-bit PQ codes and 1-bit RaBitQ codes
go through one documented seam:

* :func:`int8_tier_eligible` — the ONE eligibility rule for the exact
  single-pass bf16 MXU tier over 8-bit operands.
* :func:`exact_gathered_dots` — the tiered gathered-dots einsum itself.
* :func:`pack_codes4` / :func:`unpack_codes4` — 4-bit sub-quantizer
  codes packed two-per-byte (IVF-PQ's storage tier; HBM reads halve,
  codes unpack AFTER the gather).
* :func:`pack_sign_bits` / :func:`unpack_sign_bits` — 1-bit sign codes
  packed eight-per-byte (IVF-RaBitQ's storage tier; HBM reads shrink
  8× vs int8, 32× vs f32).
* :func:`packed_sign_dots` — the packed-binary scoring path:
  ``⟨sign(r), q8⟩`` computed as ``2·⟨bits, q8⟩ − Σq8`` with the bits
  unpacked post-gather and the dot taken on the int8 MXU tier
  (popcount-as-int8-einsum; exact, see the function doc).
  :func:`slab_dots` dispatches here via ``packed_sign=True``.

:func:`exact_gathered_dots` and :func:`int8_tier_eligible` originally
moved here from ``neighbors/_packing.py``: the scoring-tier rule is
owned by the scan core, and ``ops`` must not import from ``neighbors``.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["int8_tier_eligible", "exact_gathered_dots", "slab_dots",
           "pack_codes4", "unpack_codes4", "pack_sign_bits",
           "unpack_sign_bits", "packed_sign_dots",
           "row_sq_norms",
           "fold_topk", "fold_topk_payload", "topk_carry", "ranked_finish",
           "scan_topk", "grouped_plan", "grouped_tile_bound",
           "scan_topk_grouped", "slot_bias", "scan_path",
           "count_scan_path", "list_slab_ptr", "l2_rescorer"]


def int8_tier_eligible(a, b, d: int) -> bool:
    """True when the single-pass bf16 scoring tier is EXACT for a·b dots
    over contraction length ``d`` — the ONE home of the eligibility rule
    (every call site must agree or a raw integer query silently reverts a
    path to the 6× slower HIGHEST einsum).

    Exactness needs every f32 partial sum to stay an exact integer
    (< 2²⁴): uint8 products reach 255² ⇒ d ≤ 256; int8 reach 128² ⇒
    d ≤ 1024.  Beyond the bound integer dot gaps of 1 could round away —
    HIGHEST was exact there, so the tier must not regress it."""
    kinds = (jnp.uint8, jnp.int8)
    if a.dtype not in kinds or b.dtype not in kinds:
        return False
    lim = 256 if jnp.uint8 in (a.dtype, b.dtype) else 1024
    return d <= lim


def exact_gathered_dots(subscripts: str, vecs, q):
    """Query·candidate dots for gathered rows — the shared scoring einsum
    of the IVF-Flat probe scan, the CAGRA beam step, and the brute-force
    exact/refine paths.

    Eligible 8-bit corpora (:func:`int8_tier_eligible`) take ONE bf16 MXU
    pass: the values are bf16-exact and the MXU accumulates products in
    f32, so the result matches the f32 path exactly at ~6× the MXU rate of
    ``Precision.HIGHEST``.  Everything else keeps the bf16x6 HIGHEST
    passes — a single pass would genuinely lose ranking precision there."""
    if int8_tier_eligible(vecs, q, int(vecs.shape[-1])):
        return jnp.einsum(subscripts, vecs.astype(jnp.bfloat16),
                          q.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(subscripts, vecs, q,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def pack_codes4(codes):
    """Pack 4-bit sub-quantizer codes two-per-byte along the last axis:
    ``[..., m] uint8 (values < 16) → [..., ⌈m/2⌉] uint8`` with the even
    sub-quantizer in the low nibble.  Odd ``m`` pads one zero nibble —
    :func:`unpack_codes4` takes ``m`` to strip it.  The IVF-PQ packed
    storage tier (``ivf_pq.with_packed_codes``) stores this form; codes
    unpack AFTER the probe gather so HBM reads move half the bytes."""
    m = codes.shape[-1]
    if m % 2:
        codes = jnp.pad(codes, [(0, 0)] * (codes.ndim - 1) + [(0, 1)])
    return (codes[..., 0::2] | (codes[..., 1::2] << 4)).astype(jnp.uint8)


def unpack_codes4(packed, m: int):
    """Inverse of :func:`pack_codes4`: ``[..., ⌈m/2⌉] → [..., m] uint8``
    (low nibble first, pad nibble dropped)."""
    lo = packed & 0xF
    hi = packed >> 4
    inter = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)
    return inter[..., :m].astype(jnp.uint8)


def pack_sign_bits(x):
    """Sign codes packed eight-per-byte along the last axis:
    ``[..., d] → [..., ⌈d/8⌉] uint8`` with bit ``i % 8`` of byte
    ``i // 8`` set iff ``x[..., i] >= 0`` (little bit order).  The
    IVF-RaBitQ storage tier: one byte stores eight dimensions, so the
    estimator scan's HBM traffic is 32× below the f32 slab's."""
    bits = (x >= 0).astype(jnp.uint8)
    return jnp.packbits(bits, axis=-1, bitorder="little")


def unpack_sign_bits(packed, d: int):
    """Inverse of :func:`pack_sign_bits`: ``[..., ⌈d/8⌉] uint8 →
    [..., d]`` int8 in {0, 1} (pad bits dropped).  int8 output feeds the
    int8 MXU tier of :func:`exact_gathered_dots` directly."""
    return jnp.unpackbits(packed, axis=-1, count=d,
                          bitorder="little").astype(jnp.int8)


def packed_sign_dots(packed, q8):
    """Packed-binary slab scoring: ``[nq, B, C, ⌈d/8⌉] uint8 ·
    [nq, d] int8 → [nq, B, C] f32`` = ``⟨sign(r), q8⟩`` where
    ``sign(r) ∈ {−1, +1}`` is the stored code and ``q8`` the int8-
    quantized rotated query.

    The popcount-as-int8-einsum formulation: with bits ``b ∈ {0, 1}``,
    ``⟨2b − 1, q8⟩ = 2·⟨b, q8⟩ − Σq8``, so the scan unpacks the gathered
    bytes to {0, 1} int8 **after** the gather (HBM moved only packed
    bytes) and takes ONE bf16 MXU pass via :func:`exact_gathered_dots` —
    exact, because every product is an integer ≤ 127 and every partial
    sum stays < 2²⁴.  The block axis ``B`` stays a batch dimension
    (:func:`slab_dots` pinned-shape contract)."""
    nq, b = packed.shape[0], packed.shape[1]
    d = q8.shape[-1]
    bits = unpack_sign_bits(packed, d)
    qb = jnp.broadcast_to(q8[:, None, :], (nq, b, d))
    dots = exact_gathered_dots("qbcd,qbd->qbc", bits, qb)
    q8sum = jnp.sum(q8.astype(jnp.float32), axis=-1)
    return 2.0 * dots - q8sum[:, None, None]


def slab_dots(vecs, q, *, exact: bool = True, packed_sign: bool = False):
    """Score one gathered slab: ``[nq, B, C, d] · [nq, d] → [nq, B, C]``.

    This is THE blocked-scan distance einsum — the single insertion point
    every engine routes through — with the block axis ``B`` pinned as a
    batch dimension (bit-invariance across block sizes, see module doc).

    ``exact=True`` (IVF-Flat, CAGRA, brute-force refine) dispatches via
    :func:`exact_gathered_dots`; ``exact=False`` is the IVF-PQ recon
    tier's contract — ONE bf16 MXU pass with f32 accumulation over
    already-lossy reconstructions, where HIGHEST would triple the cost for
    precision the codes don't carry.  ``packed_sign=True`` is the 1-bit
    scoring path: ``vecs`` holds packed sign bytes and ``q`` the int8
    rotated query — dispatches to :func:`packed_sign_dots` (exact
    ``⟨sign, q8⟩``; the estimator algebra lives with the engine)."""
    if packed_sign:
        return packed_sign_dots(vecs, q)
    nq, b = vecs.shape[0], vecs.shape[1]
    qb = jnp.broadcast_to(q[:, None, :], (nq, b, q.shape[-1]))
    if exact:
        return exact_gathered_dots("qbcd,qbd->qbc", vecs, qb)
    return jnp.einsum("qbcd,qbd->qbc", vecs, qb,
                      preferred_element_type=jnp.float32)


def row_sq_norms(qf):
    """Squared L2 norms over the last axis ``[..., d] → [...]`` as a
    batched dot contraction, NOT ``jnp.sum(qf * qf, axis=-1)``.

    These norms land in every served distance (``qn + yn − 2·dots``), so
    the fleet fan-out's bit-identity contract needs them to round the
    same way in the single-device executable and the shard_map'd SPMD
    executable.  Elementwise IEEE ops are deterministic per element, and
    a ``dot_general`` contraction's accumulation order is fixed by its
    shape — but a mul+``reduce`` lowering's association order is a
    per-module codegen choice, and the two programs were observed to
    round query norms one ulp apart on CPU.  Routing every norm that
    reaches a reported distance through the same dot machinery as the
    candidate scores pins it."""
    flat = qf.reshape(-1, qf.shape[-1])
    out = jax.lax.dot_general(
        flat, flat, (((1,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST)
    return out.reshape(qf.shape[:-1])


def fold_topk(best_val, best_idx, tile_val, tile_idx, k: int, *,
              sorted: bool = True):
    """Merge a new candidate block into the running (m, k) best buffers via
    ``matrix.select_k`` — one selection primitive owns all top-k tuning.

    ``sorted=False`` keeps the carry an unordered top-k set (exact values
    and ids, unspecified row order) — the right form for intermediate scan
    carries, where only the FINAL merge needs ranked output."""
    from ..matrix.select_k import select_k

    vals = jnp.concatenate([best_val, tile_val], axis=1)
    idxs = jnp.concatenate([best_idx, tile_idx], axis=1)
    return select_k(vals, k, in_idx=idxs, select_min=True, sorted=sorted)


def fold_topk_payload(best_val, best_idx, best_payload: Sequence,
                      tile_val, tile_idx, tile_payload: Sequence, k: int):
    """:func:`fold_topk` with payload lanes riding the selection (CAGRA's
    explored flags, IVF-RaBitQ's slab pointers, build's counts).

    Selects by *concat position*, then gathers ids and every payload lane
    through the winning positions — bit-identical to the direct
    ``in_idx=ids`` fold (``select_k`` picks positions internally either
    way), which is what lets the payload-free engines share the same
    selection primitive.  Unsorted carry form (``sorted=False``)."""
    from ..matrix.select_k import select_k

    cat_val = jnp.concatenate([best_val, tile_val], axis=1)
    cat_idx = jnp.concatenate([best_idx, tile_idx], axis=1)
    cpos = jnp.tile(jnp.arange(cat_val.shape[1], dtype=jnp.int32)[None, :],
                    (cat_val.shape[0], 1))
    mv, mpos = select_k(cat_val, k, in_idx=cpos, select_min=True,
                        sorted=False)
    mi = jnp.take_along_axis(cat_idx, mpos, axis=1)
    out = tuple(
        jnp.take_along_axis(jnp.concatenate([bp, tp], axis=1), mpos, axis=1)
        for bp, tp in zip(best_payload, tile_payload))
    return mv, mi, out


def topk_carry(nq: int, k: int, *, id_fill: int = -1):
    """Fresh (values, ids) scan carry: +inf distances, ``id_fill`` ids
    (brute-force historically fills 0, the IVF engines −1 — preserved so
    the refactor stays bit-identical in the ids of sub-k result rows)."""
    return (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), id_fill, jnp.int32))


def ranked_finish(vals, ids, k: int):
    """The ONE ranked selection at scan exit: intermediate carries are
    unordered top-k sets; rank once here."""
    from ..matrix.select_k import select_k

    return select_k(vals, k, in_idx=ids, select_min=True)


def scan_topk(score_step: Callable, xs, nq: int, k: int, *,
              id_fill: int = -1) -> Tuple[jax.Array, jax.Array]:
    """The shared blocked-scan driver (XLA path).

    ``score_step(slab_inputs) -> (dist [nq, L], ids [nq, L])`` owns the
    engine-specific slab gather + scoring + validity masking (invalid
    lanes must carry ``+inf``); this driver owns the carry init, the
    per-block :func:`fold_topk` (unsorted), and the ranked exit — the
    ``scan(carry, slab) -> carry`` contract in one place."""

    def step(carry, inp):
        bv, bi = carry
        dist, ids = score_step(inp)
        return fold_topk(bv, bi, dist, ids, k, sorted=False), None

    (bv, bi), _ = jax.lax.scan(step, topk_carry(nq, k, id_fill=id_fill), xs)
    return ranked_finish(bv, bi, k)


#: query slots of one list-major tile: the rows of one MXU product
#: against one list.  Sixteen is the smallest tile of two f32 sublane
#: groups; a batch probes each list ~16 times at the SIFT-1M-class cell's
#: 512 queries × 32 probes over 1024 lists, so tiles are mostly full there.
GROUPED_TILE = 16


def grouped_tile_bound(n_pairs: int, n_lists: int, qt: int) -> int:
    """Static tile count of :func:`grouped_plan`: every list's run of
    pairs takes ⌈c/Qt⌉ < c/Qt + 1 tiles, so the total stays within
    ⌈n_pairs/Qt⌉ + min(L, n_pairs) under any skew."""
    return -(-n_pairs // qt) + min(n_lists, n_pairs)


def grouped_plan(lists, n_lists: int, qt: int, pair_valid=None):
    """List-major plan of a ``[nq, P]`` probe table (slab rows).

    The nq·P (query, list) pairs are sorted by list and each list's run
    is cut into tiles of ``qt`` query slots, so every tile belongs to
    exactly one list.  Pairs with ``pair_valid`` False take no slot.
    Returns ``(tile_list [T], n_used [1], slot_query [T·qt],
    pair_pos [nq, P])``: each tile's list (idle tiles past ``n_used``
    repeat the last used list, so they fetch nothing), each slot's query
    row (0 in unused slots), and each pair's slot (``T·qt`` for an
    invalid pair).  ``T`` is :func:`grouped_tile_bound`."""
    nq, n_probes = lists.shape
    n = nq * n_probes
    n_tiles = grouped_tile_bound(n, n_lists, qt)
    flat = lists.reshape(-1).astype(jnp.int32)
    if pair_valid is not None:  # sentinel list n_lists sorts last
        flat = jnp.where(pair_valid.reshape(-1), flat, n_lists)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sl = flat[order]
    per_list = jnp.zeros((n_lists + 1,), jnp.int32).at[flat].add(1)[:n_lists]
    tiles_per = (per_list + qt - 1) // qt
    tile_start = jnp.cumsum(tiles_per) - tiles_per
    pair_start = jnp.cumsum(per_list) - per_list
    live = sl < n_lists
    slc = jnp.minimum(sl, n_lists - 1)
    rank = jnp.arange(n, dtype=jnp.int32) - pair_start[slc]
    tile = jnp.where(live, tile_start[slc] + rank // qt, n_tiles)
    pos = jnp.where(live, tile * qt + rank % qt, n_tiles * qt)
    slot_query = jnp.zeros((n_tiles * qt,), jnp.int32).at[pos].set(
        order // n_probes, mode="drop")
    tile_list = jnp.full((n_tiles,), -1, jnp.int32).at[tile].set(
        sl, mode="drop")
    tile_list = jnp.maximum(jax.lax.cummax(tile_list), 0)
    pair_pos = jnp.zeros((n,), jnp.int32).at[order].set(pos)
    n_used = jnp.sum(tiles_per, keepdims=True)
    return tile_list, n_used, slot_query, pair_pos.reshape(nq, n_probes)


def scan_topk_grouped(qf, qn, data, bias, ids, lists, k: int, *, l2: bool,
                      clamp: bool = True, pair_valid=None,
                      qt: int = GROUPED_TILE
                      ) -> Tuple[jax.Array, jax.Array]:
    """List-major probe scan (IVF-Flat's and the IVF-PQ recon tier's).

    ``lists`` [nq, P]: each query's probed slab rows; ``data`` [L, cap, d]
    of ``qf``'s dtype and ``ids`` [L, cap]; ``bias`` [L, 1, cap]: the
    stored squared norm (``l2``) or 0 at a live slot, ``+inf`` elsewhere.
    One :func:`grouped_plan`, one ``ivf_grouped_scan`` kernel (each
    tile's exact top-k of its list), the tiles' rows scattered back to
    ``[nq, P·k]`` through the plan, and one ranked selection per query.

    The candidate set is the query-major scan's: every live row of every
    probed list, scored by the same algebra at the same precision
    (``precision=HIGHEST`` for f32, one bf16 pass for bf16), with L2
    floored at 0 under ``clamp`` as IVF-Flat's query-major scan floors it.
    Only the dot's accumulation order differs (one MXU product per tile,
    not a per-query mat-vec), so distances agree within a few f32 ulps
    and ids agree wherever no two candidates tie that closely.  Results
    do not depend on ``probe_block``.  ``pair_valid`` [nq, P] drops pairs
    from the plan (a fleet shard's probes of lists it does not own): the
    pairs that remain form the same tiles, in the same slots, as in the
    whole index's plan, so a shard scores them as the whole index does."""
    from .pallas.grouped_scan import grouped_list_topk

    nq, n_probes = lists.shape
    n_lists, cap = ids.shape
    tile_list, n_used, slot_query, pair_pos = grouped_plan(
        lists, n_lists, qt, pair_valid)
    n_tiles = tile_list.shape[0]
    qs = qf[slot_query].reshape(n_tiles, qt, qf.shape[1])
    qns = qn[slot_query].reshape(n_tiles, qt, 1)
    vals, slots = grouped_list_topk(tile_list, n_used, qs, qns, data, bias,
                                    k, l2=l2, clamp=clamp)
    row = jnp.minimum(pair_pos.reshape(-1), n_tiles * qt - 1)
    pv = vals.reshape(n_tiles * qt, k)[row].reshape(nq, n_probes, k)
    ps = slots.reshape(n_tiles * qt, k)[row].reshape(nq, n_probes, k)
    live = jnp.isfinite(pv) & (pair_pos < n_tiles * qt)[..., None]
    pv = jnp.where(live, pv, jnp.inf).reshape(nq, n_probes * k)
    # select on slab pointers and look up the k winners' ids only: an
    # id gather per candidate is nq·P·k scalar reads
    ptr = jnp.where(live, lists[..., None] * cap + ps, -1)
    bv, bp = ranked_finish(pv, ptr.reshape(nq, n_probes * k), k)
    return bv, jnp.where(bp >= 0, ids.reshape(-1)[jnp.maximum(bp, 0)], -1)


#: the grouped scan's least share of probes per list in one batch,
#: nq·P / L.  Measured on a v5e at 1024 lists × 32 probes (one search):
#: the query-major scan is faster at 8 rows, 0.25 a list (1.58 against
#: 1.94 ms), the grouped one from 16 rows, 0.5 a list (2.77 against
#: 3.43 ms; 4.70 against 10.59 at 64 rows).  Below that most tiles hold
#: a single live query slot.
GROUPED_MIN_PROBES_PER_LIST = 0.5
#: the grouped kernel keeps one whole list in VMEM, double-buffered
GROUPED_MAX_LIST_BYTES = 4 << 20
#: the grouped kernel's k min-extraction passes run per tile row
GROUPED_MAX_K = 128
#: elements (rows × stored lanes) of the largest list the TPU's compiler
#: gathers whole.  Past it, the query-major scan's gather is cut into
#: pieces that each first slice the whole slab, at every scan step
#: (compiled for v5e: 2048 × 128 gathers whole, 2064 × 128 and
#: 2048 × 256 in pieces)
GATHER_WHOLE_ELEMS = 2048 * 128
#: the values of an IVF search's ``scan_kernel``
SCAN_KERNELS = ("auto", "grouped", "xla")


def slot_bias(norms, ids, counts, metric: str, keep=None):
    """``[L, 1, cap]`` per-slot offset of the grouped scan: the stored
    squared norm (L2 metrics) or 0 (inner product) where the slot holds
    a live row (below ``counts`` where given, id ≥ 0, kept by the 1-D
    ``keep`` over source ids), ``+inf`` where it does not — the masks of
    the query-major scan."""
    cap = ids.shape[1]
    live = ids >= 0
    if counts is not None:
        live = live & (jnp.arange(cap)[None, :] < counts[:, None])
    if keep is not None:
        live = live & keep[jnp.maximum(ids, 0)]
    base = (jnp.zeros(norms.shape, jnp.float32) if metric == "inner_product"
            else norms.astype(jnp.float32))
    return jnp.where(live, base, jnp.inf)[:, None, :]


def scan_path(requested: str, nq: int, n_probes: int, n_lists: int,
              cap: int, lanes: int, dtype, k: int, keep_ndim: int = 0, *,
              grouped_dtype, gather_whole: bool = False) -> str:
    """The probe scan of one compiled IVF search program: ``"grouped"``
    (:func:`scan_topk_grouped`) or ``"query_major"``.  Called while the
    program is traced, where the batch ``nq`` and every shape are static.

    The grouped kernel takes a ``[n_lists, cap, lanes]`` slab of
    ``grouped_dtype`` — the one dtype the caller's grouped scan scores
    as its query-major scan does (IVF-Flat: f32 at ``HIGHEST``; the
    IVF-PQ recon tier: its bf16 slab) — whose list fits
    ``GROUPED_MAX_LIST_BYTES``, with k ≤ ``GROUPED_MAX_K`` and no
    per-query filter bitmap (``keep_ndim`` 2).  Where it takes the
    slab, a batch scans grouped if it probes each list
    ``GROUPED_MIN_PROBES_PER_LIST`` times or more on average, or, under
    ``gather_whole``, at any size where a list exceeds
    ``GATHER_WHOLE_ELEMS``: there the query-major gather copies the slab
    at every step (on a v5e over 4096 bf16 lists of 3664 × 128, 187 ms a
    search at 1 row against 2.3 ms grouped).  A fleet shard passes the
    whole index's ``n_lists`` and so decides as one device does.

    ``requested``: ``"auto"`` takes the grouped scan only on a TPU (off
    it the kernel runs interpreted, slower than the XLA gather);
    ``"grouped"`` takes it on any platform and raises where the kernel
    cannot take the slab; ``"xla"`` always scans query-major."""
    from ..core.errors import expects
    from .pallas.gate import on_tpu

    expects(requested in SCAN_KERNELS,
            f"scan_kernel must be auto|grouped|xla, got {requested!r}")
    if requested == "xla":
        return "query_major"
    dt = jnp.dtype(dtype)
    takes = (dt == jnp.dtype(grouped_dtype) and keep_ndim != 2
             and k <= GROUPED_MAX_K
             and cap * lanes * dt.itemsize <= GROUPED_MAX_LIST_BYTES)
    if requested == "grouped":
        expects(takes, "the grouped scan takes IVF-Flat's f32 lists or the "
                f"IVF-PQ recon tier's bf16 lists of at most "
                f"{GROUPED_MAX_LIST_BYTES} bytes, k <= {GROUPED_MAX_K} and "
                "no per-query bitmap")
    elif not (takes and on_tpu()):
        return "query_major"
    grouped = (nq * n_probes >= GROUPED_MIN_PROBES_PER_LIST * n_lists
               or (gather_whole and cap * lanes > GATHER_WHOLE_ELEMS))
    return "grouped" if grouped else "query_major"


def count_scan_path(family: str, path: str) -> None:
    """Count one lowering of an IVF ``family``'s probe scan in
    ``raft_ivf_scan_path_total{family,path}`` (a program's trace runs
    once per compiled program, so a chip run shows which buckets took
    which path)."""
    from ..obs.metrics import registry

    registry().counter(
        "raft_ivf_scan_path_total",
        "IVF probe-scan lowerings by family and path",
    ).inc(family=family, path=path)


def list_slab_ptr(lists, cap: int):
    """Storage pointers for a gathered ``[nq, B]`` list block over a
    ``[L, cap, …]`` slab: flat row ``list·cap + slot``, shaped
    ``[nq, B·cap]`` to match the block's candidate lanes — the payload
    lane a scan carries so :func:`l2_rescorer` can re-gather its
    survivors from the flattened slab."""
    nq, b = lists.shape
    slot = jnp.arange(cap, dtype=jnp.int32)
    return (lists[:, :, None].astype(jnp.int32) * cap
            + slot[None, None, :]).reshape(nq, b * cap)


def l2_rescorer(data, q, qn, metric: str) -> Callable:
    """Build the ``rescore(ptr, vids)`` closure of an exact re-rank:
    re-gather the survivor rows from the flattened ``[L·cap, d]`` slab
    and re-score them with :func:`exact_gathered_dots`.  The squared
    norms recompute from the gathered rows (the RaBitQ exact-rerank tier
    keeps no norm slab) and the algebra runs in ``brute_force``'s
    accumulation order (``qn + yn − 2·dots``, clamped) — f32 addition is
    not associative, and matching the oracle's order is what lets a
    rerank-everything search bit-match ``brute_force.knn``."""
    flat_data = data.reshape(-1, data.shape[-1])

    def rescore(ptr, _vids):
        rows = flat_data[ptr]                     # [nq, kc, d] survivors
        dots = exact_gathered_dots("qkd,qd->qk", rows, q)
        if metric == "inner_product":
            return -dots
        yn = row_sq_norms(rows.astype(jnp.float32))
        return jnp.maximum(qn[:, None] + yn - 2.0 * dots, 0.0)

    return rescore


def _scan_kernel_table():
    """No tuned scan-kernel table exists.  Kept by name for
    ``benchmark/tests/test_compile_v5e.py``'s ``as_on_chip`` fixture,
    which patches it and is its only reader."""
    return {}
