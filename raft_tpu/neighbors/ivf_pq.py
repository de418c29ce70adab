"""IVF-PQ — inverted-file index with product-quantized residuals.

No in-tree CUDA ancestor (cuVS migration); designed from the north-star
configs (``BASELINE.json``: ivf_pq on DEEP-10M) and standard IVF-PQ
(Jégou et al.) restructured for the TPU:

* **Residual PQ**: each vector stores ``pq_dim`` sub-codes indexing
  per-subspace codebooks trained on coarse residuals (x − centroid).
* **Two search tiers** (two points on the memory/bandwidth curve):

  - ``mode="recon"`` (default): at build time the codes are decoded once
    into a bf16 *reconstruction slab* ``[n_lists, cap, d]`` (x̂ = c + r̂,
    rows zero-padded to whole lanes, :func:`recon_lanes`; exact f32 ‖x̂‖²
    kept separately).  Search scores the probed lists with one bf16 MXU
    pass — ``‖q−x̂‖² = ‖q‖² − 2⟨q,x̂⟩ + ‖x̂‖²`` — so the hot loop is a
    dense bf16 contraction, the shape TPUs are built for.  On a TPU a
    batch takes the list-major *grouped* scan (:func:`grouped_recon_batch`):
    the (query, list) pairs are sorted by list and one Pallas kernel
    scores each tile of 16 queries against its list in one product and
    keeps its top-k, so a list is read once per run of tiles
    (``blocked_scan.scan_topk_grouped``).  Other batches gather each
    query's probed lists (query-major).  The slab is *derived* state: it
    is rebuilt from the codes on load and never serialized, so the
    persisted index stays PQ-compressed.
  - ``mode="lut"``: classic ADC from the uint8 codes via lookup tables,
    with the table algebra split so NOTHING query×probe-dependent is
    recomputed inside the probe loop:
    ``⟨q−c, r̂⟩ = ⟨q, r̂⟩ − ⟨c, r̂⟩`` — the probe-invariant query LUT
    ``⟨q, codebooks⟩`` is one einsum per query chunk *outside* the scan,
    and the query-invariant centroid cross term is precomputed at build
    time (``centroid_lut`` ``[L, m, c]`` f32, ~8 MB at typical shapes)
    and folded per slot into ``adc_norms = ‖r̂‖² + 2⟨c, r̂⟩`` (the
    FAISS precomputed-tables identity).  The per-probe work is then just
    a code gather + table lookup.  4× less HBM gather traffic per
    candidate than recon at pq_dim = d/2·…, but the table gather is
    VPU-bound on TPU; use it when HBM capacity, not speed, binds (the
    slab is 2·d bytes/vector vs pq_dim bytes/vector).

* **Probe blocking**: both tiers scan ``probe_block`` probes per step —
  one ``[nq, B·cap]`` slab gather, one fused distance block, ONE top-k
  merge per block (unsorted carries, a single ranked selection after the
  scan).  Results are bit-identical for every block size; B defaults from
  the measured ``_probe_block_table`` (``bench/tune_probe_block.py``).

* Lists reuse the IVF-Flat padded-slab layout (device-packed via
  :mod:`._packing`).  A list holds at most ``⌈list_cap_ratio·n/L⌉`` rows;
  every per-slot array stores that many slots rounded up to bf16's
  sublane tile (:func:`slab_slots`), so a TPU stores the recon slab
  list-major and no search program relayouts it.  Optional exact
  re-ranking lives in :mod:`raft_tpu.neighbors.refine`.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cluster.kmeans import KMeansParams, capped_assign, kmeans_balanced_fit
from ..core import tracing
from ..core.array import wrap_array
from ..core.compat import shard_map
from ..core.errors import expects
from ..distance.pairwise import sq_l2
from ._packing import chunked_filtered_queries, pack_lists
from .ivf_flat import (grouped_batch, grouped_takes, pick_scan,
                       slab_capacity, slot_bias)

__all__ = [
    "IvfPqIndexParams",
    "IvfPqSearchParams",
    "IvfPqIndex",
    "build",
    "build_chunked",
    "extend",
    "search",
    "search_tier",
    "searcher",
    "build_sharded",
    "build_chunked_sharded",
    "search_sharded",
]


@dataclasses.dataclass(frozen=True)
class IvfPqIndexParams:
    n_lists: int = 1024
    pq_dim: int = 0          # number of sub-quantizers; 0 → dim // 4
    pq_bits: int = 8         # codebook size = 2^pq_bits (4..8)
    metric: str = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.1
    pq_kmeans_n_iters: int = 15
    # capacity = ratio · n/n_lists; capped_assign spills overflow to the
    # next-nearest list, so 1.25–1.5 loses nothing and pads far less than
    # the r1 default of 2.0 (padding = wasted gather bandwidth at search)
    list_cap_ratio: float = 1.5
    store_recon: bool = True  # build the bf16 reconstruction slab
    # 4-bit packing of the stored codes (requires pq_bits <= 4): halves
    # code HBM/disk; the LUT tier unpacks per probed list post-gather
    pack_codes: bool = False
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class IvfPqSearchParams:
    n_probes: int = 32
    mode: str = "auto"       # auto | recon | lut
    query_chunk: int = 4096  # cap on [chunk, cap, d] gather working set
    # probes gathered+scored+merged per scan step; 0 = auto (measured
    # table via bench/tune_probe_block.py, else a working-set heuristic).
    # Bit-identical results at every value — a pure speed knob.
    probe_block: int = 0
    # recon-tier scan kernel: "auto" | "grouped" | "xla" | "fused" — same
    # contract as IvfFlatSearchParams.scan_kernel, over the bf16 slab.
    # The LUT tier has no distance einsum to fuse and always runs the
    # XLA scan.
    scan_kernel: str = "auto"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IvfPqIndex:
    centroids: jax.Array     # [L, d] coarse
    codebooks: jax.Array     # [M, C, ds] per-subspace
    codes: jax.Array         # [L, cap, M] uint8
    code_norms: jax.Array    # [L, cap] f32 ‖r̂‖² of decoded residuals
    ids: jax.Array           # [L, cap] int32, -1 pad
    counts: jax.Array        # [L]
    metric: str = dataclasses.field(metadata=dict(static=True))
    # Derived tier (never serialized; rebuilt from codes via with_recon()):
    # [L, cap, recon_lanes(d)] bf16 x̂ slab, zero columns past d
    recon: Optional[jax.Array] = None
    recon_norms: Optional[jax.Array] = None  # [L, cap] f32 ‖x̂‖², +inf pads
    # 4-bit packed storage (pq_bits ≤ 4): codes hold TWO sub-codes per
    # byte, [L, cap, ceil(m/2)] — half the HBM/disk of byte codes
    packed: bool = dataclasses.field(default=False,
                                     metadata=dict(static=True))
    # Hoisted-ADC tier (derived like recon — never serialized, rebuilt on
    # load via with_adc_luts(), so old artifacts round-trip unchanged):
    # ⟨c_list, codebooks⟩ per subspace entry, and the per-slot adjusted
    # norm ‖r̂‖² + 2⟨c_list, r̂⟩ that absorbs the centroid cross term of
    # ⟨q−c, r̂⟩ = ⟨q, r̂⟩ − ⟨c, r̂⟩ (FAISS precomputed-tables identity)
    centroid_lut: Optional[jax.Array] = None  # [L, m, c] f32
    adc_norms: Optional[jax.Array] = None     # [L, cap] f32

    # save_index skips these; load_index restores them via with_recon()
    # and with_adc_luts()
    _derived_fields = ("recon", "recon_norms", "centroid_lut", "adc_norms")

    @property
    def n_lists(self) -> int:
        return int(self.codes.shape[0])

    @property
    def list_cap(self) -> int:
        return int(self.codes.shape[1])

    @property
    def pq_dim(self) -> int:
        # codebooks carry the logical m; codes.shape[2] is ceil(m/2) when
        # the 4-bit packing is active
        return int(self.codebooks.shape[0])

    @property
    def dim(self) -> int:
        return int(self.centroids.shape[1])

    @property
    def size(self) -> int:
        return int(jnp.sum(self.counts))  # jaxlint: disable=JX01 size is a host-facing API scalar, not on the search path

    def with_recon(self) -> "IvfPqIndex":
        """Return a copy with the derived reconstruction slab materialized
        (idempotent).  Used after :func:`load_index`, which persists only
        the PQ-compressed state."""
        if self.recon is not None:
            return self
        recon, recon_norms = _decode_slab(
            self.codes, self.centroids, self.codebooks, self.ids)
        return dataclasses.replace(self, recon=recon, recon_norms=recon_norms)

    def without_recon(self) -> "IvfPqIndex":
        """Drop the derived slab (memory tier / pre-serialization)."""
        if self.recon is None:
            return self
        return dataclasses.replace(self, recon=None, recon_norms=None)

    def with_adc_luts(self) -> "IvfPqIndex":
        """Return a copy with the hoisted-ADC tables materialized
        (idempotent): ``centroid_lut`` [L, m, c] and ``adc_norms``
        [L, cap].  Derived state like the recon slab — rebuilt after
        :func:`load_index`, never serialized.  ``search(mode="lut")``
        computes them on the fly when absent; materializing once here
        amortizes that across calls.  Valid for packed and unpacked
        codes alike (``adc_norms`` depends on code *values*, which
        packing preserves)."""
        if self.centroid_lut is not None and self.adc_norms is not None:
            return self
        clut, anorms = _adc_tables(self.codes, self.centroids,
                                   self.codebooks, self.code_norms)
        return dataclasses.replace(self, centroid_lut=clut,
                                   adc_norms=anorms)

    def with_packed_codes(self) -> "IvfPqIndex":
        """4-bit packing: two sub-codes per byte (requires ``pq_bits ≤ 4``
        at build).  Halves code HBM/disk; the LUT tier unpacks per probed
        list after the gather (so gather traffic is halved too).
        ``extend`` requires unpacked codes — round-trip via
        :meth:`with_unpacked_codes`."""
        if self.packed:
            return self
        # static precondition: codebook size 2^pq_bits bounds every code
        expects(self.codebooks.shape[1] <= 16,
                "with_packed_codes needs 4-bit codes (build with pq_bits<=4)")
        return dataclasses.replace(self, codes=_pack_codes4(self.codes),
                                   packed=True)

    def with_unpacked_codes(self) -> "IvfPqIndex":
        if not self.packed:
            return self
        return dataclasses.replace(
            self, codes=_unpack_codes4(self.codes, self.pq_dim),
            packed=False)


def _subspace_dots(x, codebooks, precision=None):
    """``⟨x_j, codebooks[j, e]⟩`` for every subspace j and entry e:
    ``[n, m·ds] × [m, c, ds] → [n, m, c]`` as ONE plain product against
    the block-diagonal ``[m·ds, m·c]`` codebook matrix.  The TPU compiler
    made the batched form (batch m, contraction ds) a dilated convolution,
    and the encode built on it got 4 of 32 subspaces right on the chip
    (my chip run, PR 21).  With this product and a fused ``jnp.argmin`` it
    got 8 of 32; with :func:`_first_min`, all 32.  The batched form was
    not run again with :func:`_first_min`."""
    m, c, ds = codebooks.shape
    cbt = jnp.swapaxes(codebooks, 1, 2).astype(jnp.float32)       # [m, ds, c]
    eye = jnp.eye(m, dtype=jnp.float32)
    w = (cbt[:, :, None, :] * eye[:, None, :, None]).reshape(m * ds, m * c)
    # opaque to XLA, which would otherwise see the block-diagonal product
    # through and turn it back into the same batched convolution
    w = jax.lax.optimization_barrier(w)
    dots = jnp.dot(x.astype(jnp.float32), w, precision=precision,
                   preferred_element_type=jnp.float32)
    return dots.reshape(x.shape[0], m, c)


def _first_min(d2):
    """Index of the first minimum along the last axis, from two plain
    min-reductions.  ``jnp.argmin`` fused into the encode's distance
    program put a quarter of the codes right on the TPU, while alone it
    was right (my chip runs, PR 21)."""
    c = d2.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, d2.shape, d2.ndim - 1)
    hit = d2 == jnp.min(d2, axis=-1, keepdims=True)
    return jnp.min(jnp.where(hit, lane, c), axis=-1)


@jax.jit
def _nearest_lists(x, centroids):
    """Each row's nearest centroid, a row tile at a time where the whole
    ``[n, L]`` distance block would not fit (``kmeans.TILE_ELEMS``)."""
    from ..cluster.kmeans import _map_row_tiles, _tile_rows

    def nearest(xt):
        return _first_min(sq_l2(xt, centroids))

    rows = _tile_rows(x.shape[0], centroids.shape[0])
    return _map_row_tiles(nearest, x, rows) if rows else nearest(x)


@partial(jax.jit, static_argnames=("m", "c", "iters"))
def _train_codebooks(residuals, key, m: int, c: int, iters: int):
    """Per-subspace Lloyd kmeans over residual slices, every subspace in
    each step at once.  Small trainsets (< codebook size) seed with
    replacement: duplicate seeds merge over the iterations, matching the
    reference's tolerance of n_train < 2^pq_bits."""
    n, d = residuals.shape
    ds = d // m
    # component t of every subspace, [n, m] each: an [n, m, ds] array
    # would be stored with its ds-wide minor dimension padded to 128
    # lanes on the TPU (24.6 GB for a 1M-row trainset at ds = 2)
    comps = [residuals[:, t::ds] for t in range(ds)]
    keys = jax.random.split(key, m)
    idx = jax.vmap(lambda k: jax.random.choice(k, n, (c,), replace=n < c))(
        keys)                                                     # [m, c]
    cb0 = jnp.stack([x[idx, jnp.arange(m)[:, None]] for x in comps],
                    axis=-1)                                      # [m, c, ds]
    base = jnp.arange(m, dtype=jnp.int32) * c

    def body(cb, _):
        # the encode's own nearest codewords, _ENCODE_ROWS rows at a time
        codes, _ = _encode(residuals, cb, m)
        seg = (codes.astype(jnp.int32) + base).reshape(-1)       # [n·m]
        sums = jnp.stack([jax.ops.segment_sum(x.reshape(-1), seg,
                                              num_segments=m * c)
                          for x in comps], axis=-1)
        counts = jax.ops.segment_sum(jnp.ones_like(seg, jnp.float32), seg,
                                     num_segments=m * c)[:, None]
        newc = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0),
                         cb.reshape(m * c, ds))
        return newc.reshape(m, c, ds), None

    cb, _ = jax.lax.scan(body, cb0, None, length=iters)
    return cb


#: rows encoded per step: the [m, rows, c] f32 distance block of a whole
#: 1M-row dataset (32·1M·256·4 B) does not fit one 16 GB chip
_ENCODE_ROWS = 65536


def _encode_rows(residuals, codebooks, m: int):
    d2 = (jnp.sum(codebooks * codebooks, axis=2)[None]
          - 2.0 * _subspace_dots(residuals, codebooks,
                                 jax.lax.Precision.HIGHEST))      # [n, m, c]
    codes = _first_min(d2)                                        # [n, m]
    c = codebooks.shape[1]
    cn = jnp.sum(codebooks.astype(jnp.float32) ** 2, axis=2).reshape(-1)
    norms = cn[codes + jnp.arange(m, dtype=codes.dtype) * c]     # [n, m]
    return codes, jnp.sum(norms, axis=1)


@partial(jax.jit, static_argnames=("m",))
def _encode(residuals, codebooks, m: int):
    """codes[n, m] = argmin_c ‖res_m − cb[m, c]‖² and decoded-residual norms,
    ``_ENCODE_ROWS`` rows at a time (each row's codes depend on that row
    only)."""
    n, d = residuals.shape
    rows = min(n, _ENCODE_ROWS)
    pad = (-n) % rows
    blocks = jnp.pad(residuals, ((0, pad), (0, 0))).reshape(-1, rows, d)
    codes, norms = jax.lax.map(
        lambda blk: _encode_rows(blk, codebooks, m), blocks)
    return (codes.reshape(-1, m)[:n].astype(jnp.uint8),
            norms.reshape(-1)[:n])


def recon_lanes(d: int) -> int:
    """Stored width of the recon slab's rows: ``d`` rounded up to the
    TPU's 128 lanes, the extra columns zero.  A TPU stores a
    ``[L, cap, 96]`` bf16 slab with the list axis minor (its default
    layout spares the lane padding), so every list-major read of it
    first relayouts the whole slab; ``[L, cap, 128]`` is stored
    list-major.  The zero columns change no dot and no norm."""
    return -(-int(d) // 128) * 128


@jax.jit
def _decode_slab(codes, centroids, codebooks, ids):
    """Decode packed codes → bf16 reconstruction slab + exact f32 ‖x̂‖².

    The slab is ``[L, cap, recon_lanes(d)]``: rows zero-padded to whole
    lanes (:func:`recon_lanes`).

    A block of ~2^24 f32 elements of lists at a time, each written in
    place into the one output slab, so the program holds the slab once
    (the last block starts early and rewrites a few lists with the same
    values); pad entries (id < 0) get ‖x̂‖² = +inf so the L2 search path
    masks them for free.  Stacking blocks (``lax.map``) held the slab
    twice and more: 14 GB for a 10M-row slab of 2.9 GB, compiled for v5e.
    """
    L, cap, mc = codes.shape
    m = codebooks.shape[0]  # logical sub-code count (mc = ceil(m/2) packed)
    d = centroids.shape[1]
    block = max(1, min(L, max(1, (1 << 24) // max(cap * d, 1))))
    c, ds = codebooks.shape[1], codebooks.shape[2]
    # element e of a decoded row is codebook entry (e // ds, code, e % ds):
    # one scalar gather a row element, as [.., d] and never [.., m, ds],
    # whose ds-wide minor dimension the TPU pads to 128 lanes
    flat_cb = codebooks.reshape(-1)
    sub = jnp.arange(d) // ds
    lane = jnp.arange(d) % ds

    def decode_block(i, out):
        rec_out, norms_out = out
        lo = jnp.minimum(i * block, L - block)
        cb_codes = jax.lax.dynamic_slice_in_dim(codes, lo, block)
        cb_cent = jax.lax.dynamic_slice_in_dim(centroids, lo, block)
        cb_ids = jax.lax.dynamic_slice_in_dim(ids, lo, block)
        if mc != m:  # 4-bit packed: unpack one block at a time
            cb_codes = _unpack_codes4(cb_codes, m)
        code = cb_codes.astype(jnp.int32)[:, :, sub]      # [block, cap, d]
        g = flat_cb[(sub * c + code) * ds + lane]
        rec = (g.astype(jnp.float32)
               + cb_cent[:, None, :].astype(jnp.float32))
        # norms of the *rounded* slab: the search dot sees bf16 x̂, so a
        # consistent ‖x̂‖² makes the score the exact distance to the stored
        # point (an inconsistent f32 norm injects rank noise ~2ε‖q‖‖x̂‖).
        # The rounding is reduce_precision, which the compiler keeps: the
        # TPU's dropped an f32 → bf16 → f32 round trip here as excess
        # precision, and its norms were of the unrounded x̂ (on a v5e up
        # to 0.24% off the slab's own, against 2e-7 on the CPU)
        rec_f = jax.lax.reduce_precision(rec, exponent_bits=8,
                                         mantissa_bits=7)
        norms = jnp.sum(rec_f * rec_f, axis=2)
        norms = jnp.where(cb_ids >= 0, norms, jnp.inf)
        rec_b = jnp.pad(rec_f.astype(jnp.bfloat16),
                        ((0, 0), (0, 0), (0, lanes - d)))
        return (jax.lax.dynamic_update_slice_in_dim(rec_out, rec_b, lo, 0),
                jax.lax.dynamic_update_slice_in_dim(norms_out, norms, lo, 0))

    lanes = recon_lanes(d)
    out = (jnp.zeros((L, cap, lanes), jnp.bfloat16),
           jnp.zeros((L, cap), jnp.float32))
    return jax.lax.fori_loop(0, -(-L // block), decode_block, out)


@jax.jit
def _adc_tables(codes, centroids, codebooks, code_norms):
    """Build the hoisted-ADC tables: ``centroid_lut[l, m, c] =
    ⟨centroid_l restricted to subspace m, codebook entry c⟩`` and the
    per-slot adjusted norms ``adc_norms[l, j] = ‖r̂_{l,j}‖² +
    2·Σ_m centroid_lut[l, m, codes[l, j, m]]``.

    With these, LUT-mode ADC needs only the probe-invariant query LUT:
    ``‖q−c−r̂‖² = ‖q−c‖² − 2⟨q, r̂⟩ + adc_norms`` — no per-probe einsum.
    Chunked over list blocks (lax.map) so the [block, m, cap] gather
    intermediate stays bounded, mirroring :func:`_decode_slab`.
    """
    L, cap, mc = codes.shape
    m, c, ds = codebooks.shape
    clut = _subspace_dots(centroids, codebooks)
    block = max(1, min(L, max(1, (1 << 24) // max(cap * m, 1))))
    pad = (-L) % block
    codes_p = jnp.pad(codes, ((0, pad), (0, 0), (0, 0)))
    clut_p = jnp.pad(clut, ((0, pad), (0, 0), (0, 0)))
    norms_p = jnp.pad(code_norms, ((0, pad), (0, 0)))

    def cross_block(args):
        cb_codes, cb_clut, cb_norms = args
        if mc != m:  # 4-bit packed storage: unpack one block at a time
            cb_codes = _unpack_codes4(cb_codes, m)
        g = jnp.take_along_axis(
            cb_clut, cb_codes.astype(jnp.int32).transpose(0, 2, 1), axis=2)
        return cb_norms + 2.0 * jnp.sum(g, axis=1)

    anorms = jax.lax.map(
        cross_block,
        (codes_p.reshape(-1, block, cap, mc),
         clut_p.reshape(-1, block, m, c),
         norms_p.reshape(-1, block, cap)),
    )
    return clut, anorms.reshape(-1, cap)[:L]


def slab_slots(cap: int) -> int:
    """Stored slots of a list that holds at most ``cap`` rows: ``cap``
    rounded up to the bf16 recon slab's sublane tile
    (``ivf_flat.slab_capacity``), so a TPU stores the slab list-major.
    Every per-slot array (codes, norms, ids, recon) has this many; the
    extra slots are padding (id −1, ``+inf`` recon norm)."""
    return slab_capacity(cap, jnp.bfloat16)


def search_tier(index: IvfPqIndex, params: IvfPqSearchParams) -> str:
    """The tier a search runs: ``params.mode``, with ``"auto"`` the recon
    tier where the slab is materialized and the LUT tier otherwise."""
    if params.mode != "auto":
        return params.mode
    return "recon" if index.recon is not None else "lut"


def count_search(tier: str, refine: bool) -> None:
    """Count one compiled serving program in
    ``raft_ivf_pq_search_total{tier,refine}`` (called from the program's
    trace, which runs once per compiled program)."""
    from ..obs.metrics import registry

    registry().counter(
        "raft_ivf_pq_search_total",
        "IVF-PQ serving programs compiled, by tier and exact re-rank",
    ).inc(tier=tier, refine="1" if refine else "0")


def count_scan_path(path: str) -> None:
    """Count one lowering of the recon tier's probe scan in
    ``raft_ivf_pq_scan_path_total{path}``, the sibling of IVF-Flat's
    ``raft_ivf_scan_path_total`` (one count per compiled program)."""
    from ..obs.metrics import registry

    registry().counter(
        "raft_ivf_pq_scan_path_total",
        "IVF-PQ recon-tier probe-scan lowerings by path",
    ).inc(path=path)


#: elements (rows × stored lanes) of the largest list the TPU's compiler
#: gathers whole.  Past it, the query-major scan's gather is cut into
#: pieces that each first slice the whole slab, at every scan step
#: (compiled for v5e: 2048 × 128 gathers whole, 2064 × 128 and
#: 2048 × 256 in pieces)
GATHER_WHOLE_ELEMS = 2048 * 128


def grouped_recon_batch(nq: int, n_probes: int, n_lists: int, cap: int,
                        lanes: int) -> bool:
    """Whether a recon-tier batch of ``nq`` rows over a
    ``[n_lists, cap, lanes]`` slab takes the grouped scan: where it
    probes each list half a time or more (``ivf_flat.grouped_batch``),
    and at any size where a list is too large for the TPU to gather
    whole (:data:`GATHER_WHOLE_ELEMS`): there the query-major scan
    copies the slab at every step (on a v5e over 4096 lists of
    3664 × 128, 187 ms a search at 1 row against 2.3 ms grouped)."""
    return (grouped_batch(nq, n_probes, n_lists)
            or cap * lanes > GATHER_WHOLE_ELEMS)


def recon_scan(requested: str, index: IvfPqIndex, k: int, probe_block: int,
               keep=None) -> str:
    """The recon tier's probe scan (``ivf_flat.pick_scan``): ``"auto"``
    takes the grouped scan on a TPU where the kernel takes the bf16 slab
    (``ivf_flat.grouped_takes``); a program whose batch
    :func:`grouped_recon_batch` leaves out scans query-major."""
    takes = grouped_takes(index.list_cap, index.dim, int(k),
                          index.recon.dtype,
                          0 if keep is None else keep.ndim)
    return pick_scan(requested, "ivf_pq", takes,
                     probe_block * index.list_cap, k)


def _stage(name: str, fn, *args, **kwargs):
    """One build stage as the ``tracing`` range ``ivf_pq.build:<name>``,
    its seconds (to its results being ready on the device) in the gauge
    ``raft_index_build_seconds{family="ivf_pq",stage}``."""
    import time

    from ..obs.metrics import registry

    t = time.monotonic()
    with tracing.range("ivf_pq.build:%s", name):
        out = jax.block_until_ready(fn(*args, **kwargs))  # jaxlint: disable=JX05 a stage's seconds end when its results are ready; a build is not on the dispatch path
    registry().gauge(
        "raft_index_build_seconds",
        "seconds of the last index build's stages",
    ).set(time.monotonic() - t, family="ivf_pq", stage=name)
    return out


# 4-bit code packing moved to the quantized-scan sub-API (shared with the
# 1-bit RaBitQ codes); these aliases keep the historical private names
from ..ops.blocked_scan import (  # noqa: E402
    pack_codes4 as _pack_codes4,
    unpack_codes4 as _unpack_codes4,
)


@tracing.annotate("ivf_pq.build")
def build(dataset, params: Optional[IvfPqIndexParams] = None, *,
          source_ids=None, res=None) -> IvfPqIndex:
    p = params or IvfPqIndexParams()
    x = wrap_array(dataset, ndim=2, name="dataset")
    n, d = x.shape
    m = p.pq_dim or max(1, d // 4)
    expects(d % m == 0, f"dim {d} must divide by pq_dim {m}")
    expects(4 <= p.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expects(not p.pack_codes or p.pq_bits <= 4,
            "pack_codes requires pq_bits <= 4")
    c = 1 << p.pq_bits
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))

    key = jax.random.PRNGKey(p.seed)

    def train():
        # coarse quantizer (shared shape with IVF-Flat build)
        n_train = min(n, max(p.n_lists * 4,
                             int(n * p.kmeans_trainset_fraction)))
        sel = (jax.random.permutation(key, n)[:n_train] if n_train < n
               else jnp.arange(n))
        kp = KMeansParams(n_clusters=p.n_lists, max_iter=p.kmeans_n_iters,
                          seed=p.seed)
        centroids, _, _ = kmeans_balanced_fit(x[sel], kp)
        # PQ codebooks on training residuals
        res_train = x[sel] - centroids[_nearest_lists(x[sel], centroids)]
        return centroids, _train_codebooks(
            res_train, jax.random.fold_in(key, 7), m, c, p.pq_kmeans_n_iters)

    centroids, codebooks = _stage("train", train)
    labels, _ = _stage("assign", capped_assign, x, centroids, cap)

    # encode the full dataset against its assigned centroid
    def encode():
        residuals = x - centroids[jnp.clip(labels, 0, p.n_lists - 1)]
        return _encode(residuals, codebooks, m)

    codes, cnorms = _stage("encode", encode)

    # pack lists on device (jitted sort+scatter)
    ids = (jnp.asarray(source_ids, jnp.int32) if source_ids is not None
           else jnp.arange(n, dtype=jnp.int32))
    (pk_codes, pk_norms, pk_ids), counts = _stage(
        "pack", pack_lists, labels, (codes, cnorms, ids),
        n_lists=p.n_lists, cap=slab_slots(cap), fills=(0, 0.0, -1))

    index = IvfPqIndex(centroids, codebooks, pk_codes, pk_norms, pk_ids,
                       counts, p.metric)
    return _stage("decode", _derived_tiers, index, p)


def _derived_tiers(index: IvfPqIndex, p: IvfPqIndexParams) -> IvfPqIndex:
    """The hoisted-ADC tables (while the codes are unpacked), the recon
    slab if ``p.store_recon``, then the 4-bit packing if asked for."""
    index = index.with_adc_luts()
    index = index.with_recon() if p.store_recon else index
    return index.with_packed_codes() if p.pack_codes else index


def extend(index: IvfPqIndex, new_vectors, new_ids=None, *,
           insert_chunk: int = 0) -> IvfPqIndex:
    """Online streaming insert (cuVS ``extend`` parity), rebuilt around
    the chunked builder's fused slab-donating step.

    The insert batch is host-padded to a fixed ``insert_chunk`` row bucket
    (0 = :data:`~._packing.DEFAULT_INSERT_CHUNK`; pad rows carry id −1 and
    are masked out of assignment and capacity) and streamed through
    :func:`_pq_chunk_step` (capped assign → residual → PQ encode →
    scatter-append, one dispatch per chunk): ONE jitted executable serves
    every insert size, counts never leave the device between the stages,
    and the only host↔device crossings are the explicit per-chunk
    ``device_put`` and one scalar spill check — the steady-state insert
    path is zero-retrace / zero-implicit-transfer under
    :class:`~raft_tpu.core.TraceGuard`.

    Copy-on-write: the first chunk step is the non-donating
    :func:`_pq_chunk_step_cow` (the source slabs may back a live serving
    snapshot mid-dispatch), later chunks donate the fresh private buffers;
    the source ``index`` stays fully usable.  Derived tiers (hoisted-ADC
    tables, recon slab) are re-derived through their fixed-shape jitted
    rebuilds when the source index carried them.

    Capacity overflow grows the slab (host-sized static shape) with
    geometric headroom and re-runs the stream from the untouched source
    slabs; with capacity to spare, capped assignment degenerates to
    nearest-centroid, so extending is bit-identical (values AND ids) to a
    from-scratch pack at the same centroids/codebooks
    (tests/test_mutation.py pins this)."""
    from ._packing import (DEFAULT_INSERT_CHUNK, host_rows,
                           staged_insert_chunks)

    expects(not index.packed,
            "extend needs unpacked codes: index.with_unpacked_codes() "
            "first, then re-pack with with_packed_codes()")
    m = index.pq_dim
    L, cap = index.n_lists, index.list_cap
    x = host_rows(new_vectors)
    expects(x.ndim == 2 and x.shape[1] == index.dim, "vector dim mismatch")
    n_new = x.shape[0]
    expects(n_new >= 1, "no rows to insert")
    base = int(jax.device_get(jnp.sum(index.counts)))  # jaxlint: disable=JX01 one scalar sync per extend call: sizes auto-assigned ids and the spill check baseline
    ids = (np.asarray(host_rows(new_ids), np.int32) if new_ids is not None
           else np.arange(base, base + n_new, dtype=np.int32))
    expects(ids.shape == (n_new,), "new_ids must be one id per row")
    expects(int(ids.min()) >= 0, "source ids must be >= 0 (−1 is the pad)")
    chunk = int(insert_chunk) or DEFAULT_INSERT_CHUNK
    dtype = index.centroids.dtype

    def stream(slabs, counts, slab_cap):
        step = _pq_chunk_step_cow  # inputs may back a live snapshot
        for xc, idc in staged_insert_chunks(x, ids, chunk, dtype):
            slabs, counts = step(slabs, counts, index.centroids,
                                 index.codebooks, xc, idc,
                                 n_lists=L, cap=slab_cap, m=m)
            step = _pq_chunk_step  # fresh private buffers: donate
        return slabs, counts

    (codes, cnorms, slab_ids), counts = stream(
        (index.codes, index.code_norms, index.ids), index.counts, cap)
    placed = int(jax.device_get(jnp.sum(counts))) - base  # jaxlint: disable=JX01 explicit spill check: one scalar per extend gates the rare slab-growth path
    if placed < n_new:  # capacity exhausted — grow + re-run (rare)
        xd = jnp.asarray(x.astype(dtype, copy=False))
        labels = jnp.argmin(sq_l2(xd, index.centroids), axis=1)
        added = jax.ops.segment_sum(jnp.ones_like(labels, jnp.int32),
                                    labels, num_segments=L)
        need = int(jnp.max(index.counts + added))  # jaxlint: disable=JX01 slab capacity must be a host int at extend time (static shapes)
        # geometric headroom
        new_cap = slab_slots(max(need, cap + (cap + 1) // 2))
        pad = new_cap - cap
        grown = (jnp.pad(index.codes, ((0, 0), (0, pad), (0, 0))),
                 jnp.pad(index.code_norms, ((0, 0), (0, pad))),
                 jnp.pad(index.ids, ((0, 0), (0, pad)), constant_values=-1))
        (codes, cnorms, slab_ids), counts = stream(grown, index.counts,
                                                   new_cap)
    out = IvfPqIndex(index.centroids, index.codebooks, codes, cnorms,
                     slab_ids, counts, index.metric)
    if index.adc_norms is not None:  # fixed-shape jitted rebuild
        out = out.with_adc_luts()
    return out.with_recon() if index.recon is not None else out


def _pq_train_chunked(dataset, p: IvfPqIndexParams, n: int, m: int, c: int):
    """Coarse quantizer + PQ codebooks from one host-sampled trainset —
    the training phase shared by the pipelined and per-op chunk engines."""
    from .ivf_flat import _train_subsample

    n_train = min(n, max(p.n_lists * 4, int(n * p.kmeans_trainset_fraction)))
    sel = _train_subsample(n, n_train, p.seed)
    xt = jnp.asarray(np.asarray(dataset[sel]))
    kp = KMeansParams(n_clusters=p.n_lists, max_iter=p.kmeans_n_iters,
                      seed=p.seed)
    centroids, _, _ = kmeans_balanced_fit(xt, kp)
    res_train = xt - centroids[_nearest_lists(xt, centroids)]
    key = jax.random.PRNGKey(p.seed)
    codebooks = _train_codebooks(res_train, jax.random.fold_in(key, 7), m, c,
                                 p.pq_kmeans_n_iters)
    return centroids, codebooks


def _pq_step_impl(slabs, counts, centroids, codebooks, xc, idc, *,
                  n_lists: int, cap: int, m: int):
    """ONE fused program per chunk: masked capped assign → residual → PQ
    encode → scatter-append, fused so the whole chunk is a single dispatch
    with no host round-trip for ``counts``.  Pad rows (``idc < 0``) never
    request a list, never consume capacity, and scatter-drop via label −1
    — the padded fixed-shape stream is bit-identical to the unpadded
    per-op loop.  ``cap`` bounds each list's rows; the slabs may store
    more slots (:func:`slab_slots`).

    Two jitted forms: :func:`_pq_chunk_step` donates the slabs (build
    loops own their buffers); :func:`_pq_chunk_step_cow` leaves the inputs
    alive — the copy-on-write first step of the online :func:`extend`,
    whose input slabs belong to the LIVE index a serving snapshot may
    still be dispatching against."""
    from ..cluster.kmeans import _capped_assign_impl
    from ._packing import _scatter_append_impl

    valid = idc >= 0
    labels, _ = _capped_assign_impl(xc, centroids, cap - counts, valid)
    residuals = xc - centroids[jnp.clip(labels, 0, n_lists - 1)]
    ch_codes, ch_norms = _encode(residuals, codebooks, m)
    return _scatter_append_impl(slabs, counts, labels,
                                (ch_codes, ch_norms, idc),
                                n_lists=n_lists, cap=slabs[0].shape[1])


_pq_chunk_step = partial(jax.jit, static_argnames=("n_lists", "cap", "m"),
                         donate_argnums=(0, 1))(_pq_step_impl)
_pq_chunk_step_cow = partial(jax.jit,
                             static_argnames=("n_lists", "cap", "m"))(
    _pq_step_impl)


def _pq_stream_pipelined(dataset, centroids, codebooks,
                         p: IvfPqIndexParams, n: int, m: int, cap: int,
                         chunk_rows: int, source_ids, heartbeat=None):
    """Pipelined chunk engine: fixed-shape double-buffered device staging
    (:func:`~._packing.prefetch_chunks_padded`) feeding the fused donated
    :func:`_pq_chunk_step` — one executable, one dispatch per chunk."""
    from ._packing import device_full, prefetch_chunks_padded

    slots = slab_slots(cap)
    codes = device_full((p.n_lists, slots, m), 0, jnp.uint8)
    cnorms = device_full((p.n_lists, slots), 0, jnp.float32)
    ids_slab = device_full((p.n_lists, slots), -1, jnp.int32)
    counts = device_full((p.n_lists,), 0, jnp.int32)
    for lo, hi, xc, idc in prefetch_chunks_padded(dataset, chunk_rows,
                                                  source_ids):
        (codes, cnorms, ids_slab), counts = _pq_chunk_step(
            (codes, cnorms, ids_slab), counts, centroids, codebooks, xc,
            idc, n_lists=p.n_lists, cap=cap, m=m)
        if heartbeat is not None:
            heartbeat(hi)
    return codes, cnorms, ids_slab, counts


def _pq_stream_perop(dataset, centroids, codebooks, p: IvfPqIndexParams,
                     n: int, m: int, cap: int, chunk_rows: int, source_ids):
    """Reference per-op chunk loop (the pre-pipelining engine): blocking
    H2D ``jnp.asarray``, separate assign / residual / encode / scatter
    dispatches, tail chunk at its own shape.  Kept verbatim as the
    bit-parity oracle for the fused engine and the A/B baseline of
    ``bench/build_throughput.py``."""
    from ..cluster.kmeans import capped_assign_room
    from ._packing import prefetch_chunks, scatter_append

    slots = slab_slots(cap)
    codes = jnp.zeros((p.n_lists, slots, m), jnp.uint8)
    cnorms = jnp.zeros((p.n_lists, slots), jnp.float32)
    ids_slab = jnp.full((p.n_lists, slots), -1, jnp.int32)
    counts = jnp.zeros((p.n_lists,), jnp.int32)
    for lo, hi, xc_h, idc_h in prefetch_chunks(dataset, chunk_rows,
                                               source_ids):
        xc = jnp.asarray(xc_h)
        idc = jnp.asarray(idc_h, jnp.int32)
        labels, _ = capped_assign_room(xc, centroids, cap - counts)
        residuals = xc - centroids[jnp.clip(labels, 0, p.n_lists - 1)]
        ch_codes, ch_norms = _encode(residuals, codebooks, m)
        (codes, cnorms, ids_slab), counts = scatter_append(
            (codes, cnorms, ids_slab), counts, labels,
            (ch_codes, ch_norms, idc), n_lists=p.n_lists, cap=slots)
    return codes, cnorms, ids_slab, counts


def build_chunked(dataset, params: Optional[IvfPqIndexParams] = None, *,
                  chunk_rows: int = 0, source_ids=None,
                  res=None) -> IvfPqIndex:
    """Out-of-core build: the dataset stays on host (numpy-indexable —
    ``np.ndarray``/``np.memmap``) and streams through the device in chunks.

    Device peak = PQ slabs (``n·cap_ratio·pq_dim`` **bytes**, ~16× smaller
    than the f32 dataset at the defaults) + two staged chunks + one
    (chunk, L) distance block — a dataset larger than one chip's HBM is
    buildable as long as its *codes* fit (VERDICT r2 missing #2).
    Defaults to ``store_recon=False`` semantics during the stream; call
    ``index.with_recon()`` afterwards if the bf16 slab tier fits.

    The chunk engine is pipelined: each chunk is ONE jitted,
    slab-donating program (:func:`_pq_chunk_step` — capped assign against
    remaining room → residual → PQ encode → scatter-append, fused), the
    tail chunk is padded to ``chunk_rows`` with masked rows so a single
    executable serves the whole stream (zero steady-state recompiles,
    assertable under :class:`~raft_tpu.core.TraceGuard`), and chunk t+1
    is staged host→device with a non-blocking ``device_put`` while chunk
    t computes (:func:`~raft_tpu.core.device_prefetch`).

    ``chunk_rows=0`` (default) = auto: the measured table written by
    ``bench/tune_chunk_rows.py``, else 65536
    (:func:`~._packing.resolve_chunk_rows`) — a pure throughput knob, the
    built index is identical for every value.
    """
    from ._packing import build_heartbeat, resolve_chunk_rows

    p = params or IvfPqIndexParams()
    n, d = dataset.shape
    m = p.pq_dim or max(1, d // 4)
    expects(d % m == 0, f"dim {d} must divide by pq_dim {m}")
    expects(4 <= p.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expects(not p.pack_codes or p.pq_bits <= 4,
            "pack_codes requires pq_bits <= 4")
    c = 1 << p.pq_bits
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_pq")

    centroids, codebooks = _stage("train", _pq_train_chunked, dataset, p,
                                  n, m, c)
    # assign, encode and pack run fused, one program a chunk
    codes, cnorms, ids_slab, counts = _stage(
        "stream", _pq_stream_pipelined, dataset, centroids, codebooks, p, n,
        m, cap, chunk_rows, source_ids,
        heartbeat=build_heartbeat("ivf_pq.build_chunked", n))

    index = IvfPqIndex(centroids, codebooks, codes, cnorms, ids_slab,
                       counts, p.metric)
    return _stage("decode", _derived_tiers, index, p)


def _build_chunked_perop(dataset, params: Optional[IvfPqIndexParams] = None,
                         *, chunk_rows: int = 0,
                         source_ids=None) -> IvfPqIndex:
    """:func:`build_chunked` on the reference per-op chunk loop
    (:func:`_pq_stream_perop`) — the parity oracle / A/B baseline; not
    part of the public API."""
    from ._packing import resolve_chunk_rows

    p = params or IvfPqIndexParams()
    n, d = dataset.shape
    m = p.pq_dim or max(1, d // 4)
    expects(d % m == 0, f"dim {d} must divide by pq_dim {m}")
    expects(4 <= p.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expects(not p.pack_codes or p.pq_bits <= 4,
            "pack_codes requires pq_bits <= 4")
    c = 1 << p.pq_bits
    cap = max(1, int(np.ceil(p.list_cap_ratio * n / p.n_lists)))
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_pq")
    centroids, codebooks = _pq_train_chunked(dataset, p, n, m, c)
    codes, cnorms, ids_slab, counts = _pq_stream_perop(
        dataset, centroids, codebooks, p, n, m, cap, chunk_rows, source_ids)
    index = IvfPqIndex(centroids, codebooks, codes, cnorms, ids_slab,
                       counts, p.metric)
    return _derived_tiers(index, p)


# ---------------------------------------------------------------------------
# Search — recon tier (dense bf16 MXU scoring over the decoded slab).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("k", "n_probes", "metric", "probe_block",
                                   "scan_kernel"))
def _search_recon_impl(centroids, recon, recon_norms, ids, q,
                       k: int, n_probes: int, metric: str, keep=None,
                       probe_block: int = 1, scan_kernel: str = "xla"):
    from ..ops import blocked_scan as _scan
    from ._packing import blocked_probe_plan

    nq, d = q.shape
    n_lists, cap = recon.shape[0], recon.shape[1]
    if scan_kernel == "grouped" and not grouped_recon_batch(
            nq, n_probes, n_lists, cap, recon.shape[2]):
        scan_kernel = _scan.resolve_scan_kernel("auto", "ivf_pq",
                                                probe_block * cap, k)
    count_scan_path("grouped" if scan_kernel == "grouped" else "query_major")
    qf = q.astype(jnp.float32)
    qn = _scan.row_sq_norms(qf)
    dp = recon.shape[2]                           # recon_lanes(d)
    qb = jnp.pad(q.astype(jnp.bfloat16), ((0, 0), (0, dp - d)))
    cd = sq_l2(q, centroids)                      # [nq, L]
    _, probes = jax.lax.top_k(-cd, n_probes)
    if scan_kernel == "grouped":
        # list-major: each probed list read once per run of 16-query
        # tiles; recon_norms' +inf pads mask L2, ids and keep mask both
        bias = slot_bias(recon_norms, ids, None, metric, keep)
        bv, bi = _scan.scan_topk_grouped(
            qb, qn, recon, bias, ids, probes, k,
            l2=metric != "inner_product", clamp=False)
        return _finish(bv, bi, metric)
    lists_xs, pvalid = blocked_probe_plan(probes, probe_block)

    def gather(inp):
        lists, pv = inp                           # [nq, B], [B]
        bcap = lists.shape[1] * cap
        slab = recon[lists]                       # one [nq, B, cap, d] gather
        vids = ids[lists].reshape(nq, bcap)
        return lists, pv, slab, vids

    def mask(dist, lists, pv, vids):
        # pad probes (n_probes % B != 0) contribute nothing
        dist = jnp.where(jnp.repeat(pv, cap)[None, :], dist, jnp.inf)
        if keep is not None:  # prefilter by source id (True = keep)
            from ._packing import keep_lookup

            dist = jnp.where(keep_lookup(keep, vids), dist, jnp.inf)
        return dist

    if scan_kernel == "fused":
        def slab_step(inp):
            lists, pv, slab, vids = gather(inp)
            bcap = vids.shape[1]
            if metric == "inner_product":
                base = jnp.where(vids >= 0, 0.0, jnp.inf)
            else:
                # recon_norms carries +inf on pad entries — they self-mask
                base = recon_norms[lists].reshape(nq, bcap)
            return (slab.reshape(nq, bcap, dp), mask(base, lists, pv, vids),
                    vids, _scan.list_slab_ptr(lists, cap))

        rescore = _scan.l2_rescorer(recon, recon_norms, qb, qn, metric,
                                    exact=False, clamp=False)
        bv, bi = _scan.scan_topk_fused(qb, slab_step, (lists_xs, pvalid),
                                       rescore, nq, k)
    else:
        def score(inp):
            lists, pv, slab, vids = gather(inp)
            # B stays in slab_dots' *batch* dims so the inner [cap, d]·[d]
            # contraction — and with it the f32 accumulation order — is
            # identical for every probe_block (the bit-parity contract);
            # exact=False keeps the recon tier's single bf16 MXU pass.
            dots = _scan.slab_dots(slab, qb, exact=False).reshape(
                nq, vids.shape[1])
            if metric == "inner_product":
                dist = jnp.where(vids >= 0, -dots, jnp.inf)
            else:
                # recon_norms carries +inf on pad entries — they self-mask
                dist = qn[:, None] - 2.0 * dots + recon_norms[lists].reshape(
                    nq, dots.shape[1])
            return mask(dist, lists, pv, vids), vids

        bv, bi = _scan.scan_topk(score, (lists_xs, pvalid), nq, k)
    return _finish(bv, bi, metric)


def _finish(bv, bi, metric: str):
    """Scan distances to the metric's: √ for euclidean, − for inner
    product."""
    if metric == "euclidean":
        bv = jnp.sqrt(jnp.maximum(bv, 0.0))
    elif metric == "inner_product":
        bv = -bv
    return bv, bi


# ---------------------------------------------------------------------------
# Search — LUT/ADC tier (uint8 codes, per-query lookup tables).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("k", "n_probes", "metric", "probe_block"))
def _search_lut_impl(centroids, codebooks, codes, adc_norms, ids, counts, q,
                     k: int, n_probes: int, metric: str, keep=None,
                     probe_block: int = 1):
    """Hoisted-ADC scan: the probe loop does NO einsum.

    ``⟨q−c, r̂⟩ = ⟨q, r̂⟩ − ⟨c, r̂⟩`` splits the classic residual LUT into
    the probe-invariant query LUT (one einsum per query chunk, below) and
    the query-invariant centroid cross term, pre-folded per slot into
    ``adc_norms = ‖r̂‖² + 2⟨c, r̂⟩`` at build time (:func:`_adc_tables`).
    Per probe block that leaves a code gather + table lookup:
    ``‖q−c−r̂‖² = ‖q−c‖² − 2·Σ_m qlut[m, code_m] + adc_norms``.
    """
    from ._packing import blocked_probe_plan

    nq, d = q.shape
    m, c, ds = codebooks.shape
    cap = codes.shape[1]

    qf = q.astype(jnp.float32)
    cd = sq_l2(q, centroids)                      # [nq, L]
    _, probes = jax.lax.top_k(-cd, n_probes)
    # probe-invariant query LUT ⟨q, codebooks⟩ — hoisted out of the scan
    qlut = _subspace_dots(qf, codebooks)
    if metric == "inner_product":
        qc = qf @ centroids.T                     # [nq, L] ⟨q, c⟩, hoisted
    lists_xs, pvalid = blocked_probe_plan(probes, probe_block)

    def score(inp):
        lists, pv = inp                           # [nq, B], [B]
        B = lists.shape[1]
        bcap = B * cap
        lcodes = codes[lists]                     # [nq, B, cap, m or ⌈m/2⌉]
        if lcodes.shape[-1] != m:                 # 4-bit packed storage:
            lcodes = _unpack_codes4(lcodes, m)    # unpack AFTER the gather
        lcodes = lcodes.astype(jnp.int32).reshape(nq, bcap, m)
        # lookup: ip[nq, B·cap] = Σ_m qlut[q, m, code[q, j, m]]
        ip = jnp.sum(
            jnp.take_along_axis(qlut, lcodes.transpose(0, 2, 1), axis=2),
            axis=1,
        )
        vids = ids[lists].reshape(nq, bcap)
        if metric == "inner_product":
            # ⟨q, c + r̂⟩ = ⟨q, c⟩ + ⟨q, r̂⟩ — both terms precomputed
            qc_sel = jnp.take_along_axis(qc, lists, axis=1)   # [nq, B]
            dist = -(qc_sel[:, :, None]
                     + ip.reshape(nq, B, cap)).reshape(nq, bcap)
        else:
            cd_sel = jnp.take_along_axis(cd, lists, axis=1)   # [nq, B]
            dist = (cd_sel[:, :, None] - 2.0 * ip.reshape(nq, B, cap)
                    + adc_norms[lists]).reshape(nq, bcap)
            dist = jnp.maximum(dist, 0.0)
        valid = (jnp.arange(cap)[None, None, :]
                 < counts[lists][:, :, None]).reshape(nq, bcap)
        valid = valid & (vids >= 0) & jnp.repeat(pv, cap)[None, :]
        if keep is not None:  # prefilter by source id (True = keep)
            from ._packing import keep_lookup

            valid = valid & keep_lookup(keep, vids)
        return jnp.where(valid, dist, jnp.inf), vids

    from ..ops.blocked_scan import scan_topk

    bv, bi = scan_topk(score, (lists_xs, pvalid), nq, k)
    return _finish(bv, bi, metric)


@tracing.annotate("ivf_pq.search")
def search(index: IvfPqIndex, queries, k: int,
           params: Optional[IvfPqSearchParams] = None, *, filter=None,
           res=None) -> Tuple[jax.Array, jax.Array]:
    """Approximate kNN over the PQ index; combine with
    :func:`raft_tpu.neighbors.refine.refine` for exact re-ranking.

    ``filter``: optional prefilter by source id, True = keep — a shared
    ``core.Bitset``/(n,) bools or a per-query ``core.Bitmap``/(nq, n)
    bools (cuVS bitset/bitmap filter parity)."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           resolve_probe_block, sentinel_filtered_ids)

    p = params or IvfPqSearchParams()
    q = wrap_array(queries, ndim=2, name="queries")
    expects(q.shape[1] == index.dim, "query dim mismatch")
    expects(p.mode in ("auto", "recon", "lut"), f"unknown mode {p.mode!r}")
    n_probes = min(p.n_probes, index.n_lists)
    probe_block = resolve_probe_block(p.probe_block, int(n_probes),
                                      index.list_cap, "ivf_pq")
    keep = as_keep_mask(filter, nq=q.shape[0])  # indexes source ids
    if keep is not None:
        check_filter_covers_ids(keep, index.ids)
    mode = search_tier(index, p)
    if mode == "recon":
        expects(index.recon is not None,
                "mode='recon' needs the reconstruction slab — call "
                "index.with_recon() (e.g. after load_index)")
        scan_kernel = recon_scan(p.scan_kernel, index, k, probe_block, keep)
        impl = lambda qc, kc: _search_recon_impl(
            index.centroids, index.recon, index.recon_norms, index.ids,
            qc, int(k), int(n_probes), index.metric, kc, probe_block,
            scan_kernel)
    else:
        # legacy/hand-built indexes without the hoisted-ADC tables:
        # derive them here (per call — materialize with with_adc_luts()
        # once to amortize, as build/load already do)
        index = index.with_adc_luts()
        impl = lambda qc, kc: _search_lut_impl(
            index.centroids, index.codebooks, index.codes, index.adc_norms,
            index.ids, index.counts, qc, int(k), int(n_probes), index.metric,
            kc, probe_block)
    dv, di = chunked_filtered_queries(impl, q, int(p.query_chunk), keep)
    if keep is not None:  # sub-k survivors: sentinel tail, not real ids
        di = sentinel_filtered_ids(dv, di)
    return dv, di


def searcher(index: IvfPqIndex, k: int,
             params: Optional[IvfPqSearchParams] = None, *, filter=None):
    """Uniform serving entry point (``raft_tpu.serve`` contract): returns
    ``(fn, operands)`` with ``fn(queries, *operands)`` equal to
    :func:`search` for query batches up to ``params.query_chunk`` rows.
    Mode resolution matches :func:`search` (``auto`` → recon tier when the
    slab is materialized, LUT otherwise); index state rides as operands so
    per-bucket executables never embed slab copies.

    ``filter``: optional shared prefilter (``core.Bitset`` / 1-D bools
    over source ids, True = keep) — rides as one more operand, so
    tombstone deletes (:func:`raft_tpu.neighbors.mutation.delete`) swap
    in a new mask without recompiling.  Per-query bitmaps can't ride a
    fixed operand across variable-row buckets and are rejected."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           resolve_probe_block, sentinel_filtered_ids)

    p = params or IvfPqSearchParams()
    expects(k >= 1, "k must be >= 1")
    expects(p.mode in ("auto", "recon", "lut"), f"unknown mode {p.mode!r}")
    n_probes = int(min(p.n_probes, index.n_lists))
    probe_block = resolve_probe_block(p.probe_block, n_probes,
                                      index.list_cap, "ivf_pq")
    metric = index.metric
    keep = as_keep_mask(filter)
    if keep is not None:
        expects(keep.ndim == 1,
                "serving filters are shared bitsets (1-D); per-query "
                "bitmaps can't ride a fixed operand across buckets")
        check_filter_covers_ids(keep, index.ids)
    mode = search_tier(index, p)
    if mode == "recon":
        expects(index.recon is not None,
                "mode='recon' needs the reconstruction slab — call "
                "index.with_recon() (e.g. after load_index)")
        scan_kernel = recon_scan(p.scan_kernel, index, k, probe_block, keep)
        if keep is not None:

            def fn(q, centroids, recon, recon_norms, ids, kp):
                dv, di = _search_recon_impl(centroids, recon, recon_norms,
                                            ids, q, int(k), n_probes,
                                            metric, kp, probe_block,
                                            scan_kernel)
                return dv, sentinel_filtered_ids(dv, di)

            return fn, (index.centroids, index.recon, index.recon_norms,
                        index.ids, keep)

        def fn(q, centroids, recon, recon_norms, ids):
            return _search_recon_impl(centroids, recon, recon_norms, ids,
                                      q, int(k), n_probes, metric, None,
                                      probe_block, scan_kernel)

        return fn, (index.centroids, index.recon, index.recon_norms,
                    index.ids)

    index = index.with_adc_luts()  # once, here — operands carry the tables
    if keep is not None:

        def fn(q, centroids, codebooks, codes, adc_norms, ids, counts, kp):
            dv, di = _search_lut_impl(centroids, codebooks, codes,
                                      adc_norms, ids, counts, q, int(k),
                                      n_probes, metric, kp, probe_block)
            return dv, sentinel_filtered_ids(dv, di)

        return fn, (index.centroids, index.codebooks, index.codes,
                    index.adc_norms, index.ids, index.counts, keep)

    def fn(q, centroids, codebooks, codes, adc_norms, ids, counts):
        return _search_lut_impl(centroids, codebooks, codes, adc_norms,
                                ids, counts, q, int(k), n_probes, metric,
                                None, probe_block)

    return fn, (index.centroids, index.codebooks, index.codes,
                index.adc_norms, index.ids, index.counts)


# ---------------------------------------------------------------------------
# Sharded (multi-chip) variant: lists partitioned over the mesh axis,
# codebooks replicated (they are tiny: m * 2^bits * ds floats).
# Mirrors ivf_flat.build_sharded/search_sharded; the TPU analog of the
# reference's MNMG rank-sharded indexes over comms_t (SURVEY.md §5.7).
# ---------------------------------------------------------------------------


@lru_cache(maxsize=16)
def _sharded_coarse_program(mesh, axis: str, per: int, n_lists_local: int,
                            n_train: int, max_iter: int, penalty: float,
                            bal_cap: int, seed: int):
    """Phase A of the distributed build: every device trains its coarse
    quantizer on ITS rows and emits a residual sample for the (tiny,
    shared) PQ codebook fit."""
    from jax.sharding import PartitionSpec as P

    from ..cluster.kmeans import _balanced_fit_impl

    def local(x_l):
        shard = jax.lax.axis_index(axis)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), shard)
        sel = jax.random.permutation(key, per)[:n_train]
        xt = x_l[sel]
        c, _, _, _ = _balanced_fit_impl(
            xt, key, n_lists_local, max_iter, penalty, bal_cap)
        lbl = jnp.argmin(sq_l2(xt, c), axis=1)
        # residual arithmetic in f32: integer subtraction would wrap
        # (cluster._centroid_dtype rationale); c is already f32 for
        # integer corpora
        return c, xt.astype(c.dtype) - c[lbl]

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=P(axis), out_specs=(P(axis), P(axis)),
        check_vma=False,
    ))


@lru_cache(maxsize=16)
def _sharded_encode_program(mesh, axis: str, n_orig: int, per: int,
                            n_lists_local: int, cap: int, m: int,
                            store_recon: bool):
    """Phase B: every device cap-assigns, PQ-encodes and packs ITS rows
    against ITS centroids (codebooks replicated — they are tiny), and
    decodes its recon slab in place when requested."""
    from jax.sharding import PartitionSpec as P

    def local(x_l, c_l, codebooks):
        shard = jax.lax.axis_index(axis)
        gid = (shard * per + jnp.arange(per)).astype(jnp.int32)
        labels, _ = capped_assign(x_l, c_l, cap)
        labels = jnp.where(gid < n_orig, labels, -1)
        residuals = x_l - c_l[jnp.clip(labels, 0, n_lists_local - 1)]
        codes, cnorms = _encode(residuals, codebooks, m)
        (pk_codes, pk_norms, pk_ids), counts = pack_lists(
            labels, (codes, cnorms, gid),
            n_lists=n_lists_local, cap=slab_slots(cap), fills=(0, 0.0, -1))
        if store_recon:
            rec, rnorms = _decode_slab(pk_codes, c_l, codebooks, pk_ids)
        else:  # static-shape placeholders dropped by the caller
            rec = jnp.zeros((n_lists_local, 1, 1), jnp.bfloat16)
            rnorms = jnp.zeros((n_lists_local, 1), jnp.float32)
        # hoisted-ADC tables per LOCAL lists — elementwise over the list
        # axis, so the shard layout is preserved without cross-device moves
        clut, anorms = _adc_tables(pk_codes, c_l, codebooks, pk_norms)
        return pk_codes, pk_norms, pk_ids, counts, rec, rnorms, clut, anorms

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(axis), P()),
        out_specs=(P(axis),) * 8, check_vma=False,
    ))


def build_sharded(dataset, mesh, params: Optional[IvfPqIndexParams] = None,
                  *, axis: str = "shard") -> IvfPqIndex:
    """Distributed build: rows sharded over the mesh axis; **each device
    builds its own lists from its own rows on its own device** (two
    shard_map programs — coarse+sample, then encode+pack+decode), with only
    the tiny PQ codebook fit centralized on a gathered residual sample.
    Replaces the r2 build-once-then-device_put shape (VERDICT r2 missing
    #2); SNMG model of ``core/device_resources_snmg.hpp:36``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ._packing import shard_rows, sharded_train_sizes

    p = params or IvfPqIndexParams()
    d = int(dataset.shape[1])
    m = p.pq_dim or max(1, d // 4)
    expects(d % m == 0, f"dim {d} must divide by pq_dim {m}")
    expects(4 <= p.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expects(not p.pack_codes or p.pq_bits <= 4,
            "pack_codes requires pq_bits <= 4")
    cc = 1 << p.pq_bits
    n_dev = int(mesh.shape[axis])
    x_sh, n, per = shard_rows(dataset, mesh, axis)
    n_lists_local = max(1, (p.n_lists + n_dev - 1) // n_dev)
    expects(n_lists_local <= per, "n_lists exceeds rows per shard")
    cap = max(1, int(np.ceil(p.list_cap_ratio * per / n_lists_local)))
    kp = KMeansParams()
    n_train, bal_cap = sharded_train_sizes(
        per, n_lists_local, p.kmeans_trainset_fraction, kp.balanced_max_ratio)

    coarse = _sharded_coarse_program(
        mesh, axis, per, n_lists_local, n_train, p.kmeans_n_iters,
        float(kp.balanced_penalty), bal_cap, p.seed)
    centroids, res_sample = coarse(x_sh)
    # codebooks: tiny (m·2^bits·ds floats) — one central fit, replicated
    codebooks = _train_codebooks(
        res_sample, jax.random.fold_in(jax.random.PRNGKey(p.seed), 7),
        m, cc, p.pq_kmeans_n_iters)
    codebooks = jax.device_put(codebooks, NamedSharding(mesh, P()))

    encode = _sharded_encode_program(
        mesh, axis, n, per, n_lists_local, cap, m, bool(p.store_recon))
    codes, cnorms, ids, counts, rec, rnorms, clut, anorms = encode(
        x_sh, centroids, codebooks)
    index = IvfPqIndex(
        centroids, codebooks, codes, cnorms, ids, counts, p.metric,
        rec if p.store_recon else None,
        rnorms if p.store_recon else None,
        centroid_lut=clut, adc_norms=anorms,
    )
    # packing is elementwise, so it preserves the per-shard layout
    return index.with_packed_codes() if p.pack_codes else index


@lru_cache(maxsize=16)
def _sharded_chunk_coarse_program(mesh, axis: str, n_lists_local: int,
                                  max_iter: int, penalty: float,
                                  bal_cap: int, seed: int):
    """Per-shard coarse fit for the sharded streaming build: each device
    balanced-fits ITS local centroids on ITS host-sampled trainset stripe
    and emits a residual sample for the central (tiny) codebook fit —
    the chunked analog of :func:`_sharded_coarse_program`, taking the
    trainset directly instead of sampling device-resident rows."""
    from jax.sharding import PartitionSpec as P

    from ..cluster.kmeans import _balanced_fit_impl

    def local(xt_l):
        shard = jax.lax.axis_index(axis)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), shard)
        c, _, _, _ = _balanced_fit_impl(
            xt_l, key, n_lists_local, max_iter, penalty, bal_cap)
        lbl = jnp.argmin(sq_l2(xt_l, c), axis=1)
        return c, xt_l.astype(c.dtype) - c[lbl]

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=P(axis), out_specs=(P(axis), P(axis)),
        check_vma=False))


@lru_cache(maxsize=16)
def _sharded_chunk_step_program(mesh, axis: str, n_lists_local: int,
                                cap: int, m: int):
    """Data-parallel fused chunk step: every device runs
    :func:`_pq_chunk_step`'s body (assign → residual → encode → scatter)
    on ITS slice of the chunk against ITS local lists — one jitted
    shard_map program per chunk, slabs donated, codebooks replicated,
    zero cross-device data movement."""
    from jax.sharding import PartitionSpec as P

    from ..cluster.kmeans import _capped_assign_impl
    from ._packing import _scatter_append_impl

    def local(codes_l, cn_l, ids_l, counts_l, c_l, cb, xc_l, idc_l):
        valid = idc_l >= 0
        labels, _ = _capped_assign_impl(xc_l, c_l, cap - counts_l, valid)
        residuals = xc_l - c_l[jnp.clip(labels, 0, n_lists_local - 1)]
        ch_codes, ch_norms = _encode(residuals, cb, m)
        (codes_l, cn_l, ids_l), counts_l = _scatter_append_impl(
            (codes_l, cn_l, ids_l), counts_l, labels,
            (ch_codes, ch_norms, idc_l), n_lists=n_lists_local,
            cap=codes_l.shape[1])
        return codes_l, cn_l, ids_l, counts_l

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(axis),) * 5 + (P(), P(axis), P(axis)),
        out_specs=(P(axis),) * 4, check_vma=False),
        donate_argnums=(0, 1, 2, 3))


@lru_cache(maxsize=16)
def _sharded_chunk_finalize_program(mesh, axis: str, n_lists_local: int,
                                    store_recon: bool):
    """Derived-tier finalize for the sharded streaming build: per-shard
    recon slab decode and hoisted-ADC tables, elementwise over the local
    list axis so the shard layout is preserved (same shape as the tail of
    :func:`_sharded_encode_program`)."""
    from jax.sharding import PartitionSpec as P

    def local(codes_l, cnorms_l, ids_l, c_l, cb):
        if store_recon:
            rec, rnorms = _decode_slab(codes_l, c_l, cb, ids_l)
        else:  # static-shape placeholders dropped by the caller
            rec = jnp.zeros((n_lists_local, 1, 1), jnp.bfloat16)
            rnorms = jnp.zeros((n_lists_local, 1), jnp.float32)
        clut, anorms = _adc_tables(codes_l, c_l, cb, cnorms_l)
        return rec, rnorms, clut, anorms

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(axis),) * 4 + (P(),),
        out_specs=(P(axis),) * 4, check_vma=False))


def build_chunked_sharded(dataset, mesh,
                          params: Optional[IvfPqIndexParams] = None, *,
                          chunk_rows: int = 0, source_ids=None,
                          axis: str = "shard") -> IvfPqIndex:
    """Distributed streaming build — the build-side analog of
    :func:`search_sharded`: the dataset stays on host and each fixed-size
    chunk splits contiguously over the mesh axis (one sharded
    ``device_put``, staged a chunk ahead), every device encoding and
    appending its slice into ITS OWN local lists via the fused donated
    chunk step.  :func:`build_chunked`'s out-of-core pipeline (fixed
    shapes, padded tail, single executable) on
    :func:`build_sharded`'s shard-local sub-index model; only the tiny PQ
    codebook fit is centralized (on a gathered per-shard residual
    sample), then replicated.  Per-device peak = local code slabs + its
    chunk slice."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ._packing import (build_heartbeat, chunked_shard_rows,
                           chunked_shard_trainsets, prefetch_chunks_padded,
                           resolve_chunk_rows, sharded_train_sizes)

    p = params or IvfPqIndexParams()
    n, d = dataset.shape
    m = p.pq_dim or max(1, d // 4)
    expects(d % m == 0, f"dim {d} must divide by pq_dim {m}")
    expects(4 <= p.pq_bits <= 8, "pq_bits must be in [4, 8]")
    expects(not p.pack_codes or p.pq_bits <= 4,
            "pack_codes requires pq_bits <= 4")
    cc = 1 << p.pq_bits
    n_dev = int(mesh.shape[axis])
    n_lists_local = max(1, (p.n_lists + n_dev - 1) // n_dev)
    chunk_rows = resolve_chunk_rows(chunk_rows, n, d, "ivf_pq")
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
    sharding = NamedSharding(mesh, P(axis))

    xt = chunked_shard_trainsets(dataset, n, chunk_rows, n_dev, n_train,
                                 p.seed)
    xt_sh = jax.device_put(xt.reshape(n_dev * n_train, d), sharding)
    coarse = _sharded_chunk_coarse_program(
        mesh, axis, n_lists_local, p.kmeans_n_iters,
        float(kp.balanced_penalty), bal_cap, p.seed)
    centroids, res_sample = coarse(xt_sh)
    # codebooks: tiny (m·2^bits·ds floats) — one central fit, replicated
    codebooks = _train_codebooks(
        res_sample, jax.random.fold_in(jax.random.PRNGKey(p.seed), 7),
        m, cc, p.pq_kmeans_n_iters)
    codebooks = jax.device_put(codebooks, NamedSharding(mesh, P()))

    L, slots = n_dev * n_lists_local, slab_slots(cap)
    codes = jax.device_put(jnp.zeros((L, slots, m), jnp.uint8), sharding)
    cnorms = jax.device_put(jnp.zeros((L, slots), jnp.float32), sharding)
    ids_slab = jax.device_put(jnp.full((L, slots), -1, jnp.int32),
                              sharding)
    counts = jax.device_put(jnp.zeros((L,), jnp.int32), sharding)
    step = _sharded_chunk_step_program(mesh, axis, n_lists_local, cap, m)
    heartbeat = build_heartbeat("ivf_pq.build_chunked_sharded", n)
    for lo, hi, xc, idc in prefetch_chunks_padded(
            dataset, chunk_rows, source_ids, sharding=sharding):
        codes, cnorms, ids_slab, counts = step(
            codes, cnorms, ids_slab, counts, centroids, codebooks, xc, idc)
        heartbeat(hi)

    finalize = _sharded_chunk_finalize_program(
        mesh, axis, n_lists_local, bool(p.store_recon))
    rec, rnorms, clut, anorms = finalize(codes, cnorms, ids_slab, centroids,
                                         codebooks)
    index = IvfPqIndex(
        centroids, codebooks, codes, cnorms, ids_slab, counts, p.metric,
        rec if p.store_recon else None,
        rnorms if p.store_recon else None,
        centroid_lut=clut, adc_norms=anorms,
    )
    return index.with_packed_codes() if p.pack_codes else index


@partial(jax.jit, static_argnames=("k", "n_probes", "metric", "axis", "mesh",
                                   "mode", "data_axis", "probe_block"))
def _search_sharded_impl(mesh, axis, centroids, codebooks, codes, adc_norms,
                         ids, counts, recon, recon_norms, q,
                         k: int, n_probes: int, metric: str, mode: str,
                         data_axis: Optional[str] = None, keep=None,
                         probe_block: int = 1):
    from jax.sharding import PartitionSpec as P

    def merge(bv, bi, nq_l):
        if metric == "inner_product":
            bv = -bv  # back to min-selectable for the cross-shard merge
        av = jax.lax.all_gather(bv, axis, tiled=False)   # [S, nq, k]
        ai = jax.lax.all_gather(bi, axis, tiled=False)
        av = jnp.moveaxis(av, 0, 1).reshape(nq_l, -1)
        ai = jnp.moveaxis(ai, 0, 1).reshape(nq_l, -1)
        from ..matrix.select_k import select_k

        fv, fi = select_k(av, k, in_idx=ai, select_min=True)
        if metric == "inner_product":
            fv = -fv
        return fv, fi

    qspec = P(data_axis) if data_axis else P()
    # keep masks GLOBAL source ids → replicated over the shard axis; a 2-D
    # bitmap's query rows follow the query partitioning
    kspec = (P(data_axis) if (keep is not None and keep.ndim == 2
                              and data_axis) else P())
    if mode == "recon":
        def local(centroids_l, recon_l, recon_norms_l, ids_l, q_l, keep_l):
            bv, bi = _search_recon_impl(centroids_l, recon_l, recon_norms_l,
                                        ids_l, q_l, k, n_probes, metric,
                                        keep_l, probe_block)
            return merge(bv, bi, q_l.shape[0])

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), qspec, kspec),
            out_specs=(qspec, qspec), check_vma=False,
        )(centroids, recon, recon_norms, ids, q, keep)

    def local(centroids_l, codebooks_l, codes_l, adc_norms_l, ids_l,
              counts_l, q_l, keep_l):
        bv, bi = _search_lut_impl(centroids_l, codebooks_l, codes_l,
                                  adc_norms_l, ids_l, counts_l, q_l,
                                  k, n_probes, metric, keep_l, probe_block)
        return merge(bv, bi, q_l.shape[0])

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis), P(axis), P(axis), qspec,
                  kspec),
        out_specs=(qspec, qspec), check_vma=False,
    )(centroids, codebooks, codes, adc_norms, ids, counts, q, keep)


def search_sharded(index: IvfPqIndex, queries, k: int,
                   params: Optional[IvfPqSearchParams] = None, *,
                   mesh, axis: str = "shard",
                   data_axis: Optional[str] = None, filter=None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Multi-chip search: each shard probes its ``n_probes`` nearest
    *local* lists (union over shards covers the globally nearest lists),
    one all_gather of (nq, k) candidates merges over ICI.  On a 2-D mesh,
    ``data_axis`` partitions the queries over that axis.

    ``filter``: bitset/bitmap prefilter over GLOBAL source ids, same
    contract as :func:`search` (replicated over the shard axis)."""
    from ._packing import (as_keep_mask, check_filter_covers_ids,
                           resolve_probe_block, sentinel_filtered_ids)

    p = params or IvfPqSearchParams()
    q = wrap_array(queries, ndim=2, name="queries")
    expects(q.shape[1] == index.dim, "query dim mismatch")
    expects(p.mode in ("auto", "recon", "lut"), f"unknown mode {p.mode!r}")
    n_dev = int(mesh.shape[axis])
    local_lists = index.n_lists // n_dev
    n_probes = min(p.n_probes, local_lists)
    probe_block = resolve_probe_block(p.probe_block, int(n_probes),
                                      index.list_cap, "ivf_pq")
    if data_axis is not None:
        expects(data_axis in mesh.axis_names, f"axis {data_axis!r} not in mesh")
        expects(q.shape[0] % int(mesh.shape[data_axis]) == 0,
                "queries not divisible by data axis")
    keep = as_keep_mask(filter, nq=q.shape[0])
    if keep is not None:
        check_filter_covers_ids(keep, index.ids)
    mode = search_tier(index, p)
    if mode == "recon":
        expects(index.recon is not None,
                "mode='recon' needs the reconstruction slab — call "
                "index.with_recon() (e.g. after load_index)")
    elif index.adc_norms is None:
        # hoisted-ADC tables are elementwise over the list axis, so this
        # preserves a sharded index's layout (build_sharded pre-computes
        # them inside the encode program; this covers hand-built indexes)
        index = index.with_adc_luts()
    dv, di = _search_sharded_impl(mesh, axis, index.centroids,
                                  index.codebooks, index.codes,
                                  index.adc_norms if mode == "lut"
                                  else index.code_norms,
                                  index.ids, index.counts,
                                  index.recon, index.recon_norms,
                                  q, int(k), int(n_probes), index.metric,
                                  mode, data_axis, keep, probe_block)
    if keep is not None:
        di = sentinel_filtered_ids(dv, di)
    return dv, di
