"""Mutable-index lifecycle: tombstone deletes, filtered views, compaction.

Production corpora mutate; the indexes here are built once.  This module
closes the gap without touching any search kernel:

* **insert** — the per-family ``extend()`` (ivf_flat/ivf_pq/ivf_rabitq)
  streams new rows through the slab-donating chunk step; :func:`extend`
  below adds a tombstone-preserving dispatch over the IVF families.
* **delete** — :func:`delete` records dead *source ids* in a
  ``core.Bitset`` keep-mask (True = live) and wraps the untouched index
  in a :class:`Tombstoned` view.  Every family's filtered-search path
  already consumes bitsets, so deletes cost one word-sized mask update —
  no slab rewrite, no recompile (the mask rides as a searcher operand of
  fixed shape).
* **compact** — :func:`compact` rewrites the slabs through the same
  device packer the chunked builder uses, dropping tombstoned/overfull
  rows and shrinking ``list_cap`` to the live maximum.

``Tombstoned`` is a pytree, so it serializes/shards like the index it
wraps.  The id space defaults to ``max stored id + 1``; serving loops
that interleave insert + delete should pass ``id_space=`` with headroom
so the mask keeps ONE shape across the whole lifecycle (a growing mask
is a new operand shape → a recompile).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bitset import Bitset
from ..core.errors import expects
from ._packing import (_max_source_id, as_keep_mask, host_rows, keep_lookup,
                       pack_lists)

__all__ = ["Tombstoned", "delete", "deleted_count", "extend", "search",
           "compact"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Tombstoned:
    """An index plus its tombstone keep-mask (True = live source id).

    The wrapped ``index`` is never modified — deletes are O(mask) and a
    ``Tombstoned`` built from a live snapshot shares every slab with it.
    ``raft_tpu.serve`` unwraps this transparently (the mask becomes the
    searcher's shared prefilter operand)."""

    index: Any
    keep: Bitset

    @property
    def dim(self) -> int:
        return self.index.dim if hasattr(self.index, "dim") \
            else self.index.shape[1]

    @property
    def size(self) -> int:
        """Stored rows (tombstoned rows still occupy slots until
        :func:`compact`).  Brute databases are sized by rows — a raw
        array's ``.size`` attribute counts elements, not rows."""
        if getattr(self.index, "ndim", None) == 2:
            return int(self.index.shape[0])
        return int(self.index.size)


def _default_id_space(index) -> int:
    """The smallest keep-mask that covers every stored id."""
    ids = getattr(index, "ids", None)
    if ids is not None and getattr(ids, "ndim", 0) == 2:  # IVF slab ids
        return _max_source_id(ids) + 1
    if getattr(index, "ndim", None) == 2:  # brute database: row numbers
        return int(index.shape[0])
    expects(hasattr(index, "size"),
            "cannot infer an id space: expected an IVF index, a CagraIndex "
            "or a 2-D brute-force database")
    return int(index.size)  # cagra: positional row ids


def delete(index, ids, *, id_space: int = 0) -> Tombstoned:
    """Tombstone ``ids`` (source ids for IVF, row numbers for
    cagra/brute-force).  Returns a :class:`Tombstoned` view; compose
    freely — deleting from a ``Tombstoned`` accumulates into the same
    mask.  ``id_space`` fixes the mask size (serving: pick it once, with
    insert headroom, so the mask shape never changes); 0 infers the
    smallest cover.  Deleting an id twice is a no-op, not an error."""
    base, keep = (index.index, index.keep) if isinstance(index, Tombstoned) \
        else (index, None)
    idh = np.asarray(host_rows(ids), np.int64).reshape(-1)
    expects(idh.size >= 1, "no ids to delete")
    expects(int(idh.min()) >= 0, "ids must be >= 0 (−1 is the pad value)")
    if keep is None:
        keep = Bitset.create(int(id_space) or _default_id_space(base), True)
    elif id_space:
        expects(int(id_space) >= keep.n_bits,
                "id_space cannot shrink an existing tombstone mask")
        if int(id_space) > keep.n_bits:
            keep = keep.resize(int(id_space), True)
    expects(int(idh.max()) < keep.n_bits,
            f"id {int(idh.max())} outside id space {keep.n_bits} — pass "
            f"id_space= with headroom at the first delete")
    return Tombstoned(base, keep.set(jnp.asarray(idh, jnp.int32), False))


def deleted_count(t: Tombstoned) -> int:
    """Number of tombstoned ids (host int — one explicit transfer)."""
    return int(t.keep.n_bits - jax.device_get(t.keep.count()))  # jaxlint: disable=JX01 host-facing API scalar, not on the search path


def extend(index, new_vectors, new_ids=None, *, insert_chunk: int = 0):
    """Tombstone-preserving insert dispatch for the IVF families: extends
    the wrapped index and re-wraps with the same mask (grown — with live
    defaults — only if the new ids overflow it, which changes the mask
    shape; serving loops avoid that by sizing ``id_space`` up front)."""
    from . import ivf_flat, ivf_pq, ivf_rabitq

    base, keep = (index.index, index.keep) if isinstance(index, Tombstoned) \
        else (index, None)
    if isinstance(base, ivf_pq.IvfPqIndex):
        out = ivf_pq.extend(base, new_vectors, new_ids,
                            insert_chunk=insert_chunk)
    elif isinstance(base, ivf_rabitq.IvfRabitqIndex):
        out = ivf_rabitq.extend(base, new_vectors, new_ids,
                                insert_chunk=insert_chunk)
    else:
        expects(isinstance(base, ivf_flat.IvfFlatIndex),
                "online extend is an IVF-family operation (cagra/brute "
                "rebuild; see docs/mutability_guide.md)")
        out = ivf_flat.extend(base, new_vectors, new_ids,
                              insert_chunk=insert_chunk)
    if keep is None:
        return out
    top = _max_source_id(out.ids) + 1
    if top > keep.n_bits:
        keep = keep.resize(top, True)
    return Tombstoned(out, keep)


def _combined_keep(keep: Bitset, filter):
    """AND an extra caller filter into the tombstone mask (bool arrays —
    the per-call search path, not the fixed-operand serving path)."""
    if filter is None:
        return keep
    extra = as_keep_mask(filter)
    mask = keep.to_bool_array()
    expects(extra.shape[-1] == mask.shape[0],
            f"filter covers {extra.shape[-1]} ids, tombstone mask covers "
            f"{mask.shape[0]}")
    return extra & mask


def search(t: Tombstoned, queries, k: int, params=None, *, filter=None,
           **kw):
    """Family-dispatched search over a tombstoned view — deleted ids never
    appear in results (empty slots report id −1 / ±inf, the filtered-
    search contract).  An extra ``filter`` is ANDed with the mask."""
    from . import brute_force, cagra, ivf_flat, ivf_pq, ivf_rabitq

    expects(isinstance(t, Tombstoned), "search() takes a Tombstoned view")
    keep = _combined_keep(t.keep, filter)
    base = t.index
    if isinstance(base, ivf_flat.IvfFlatIndex):
        return ivf_flat.search(base, queries, k, params, filter=keep, **kw)
    if isinstance(base, ivf_pq.IvfPqIndex):
        return ivf_pq.search(base, queries, k, params, filter=keep, **kw)
    if isinstance(base, ivf_rabitq.IvfRabitqIndex):
        return ivf_rabitq.search(base, queries, k, params, filter=keep, **kw)
    if isinstance(base, cagra.CagraIndex):
        return cagra.search(base, queries, k, params, filter=keep, **kw)
    return brute_force.knn(queries, base, k, filter=keep, **kw)


def _compact_labels(ids, counts, cap: int, keep: Optional[Bitset]):
    """Per-slot destination list (its own list index) or −1 to drop: pad
    slots, −1 ids, and tombstoned ids all drop; survivors keep their slab
    order (``pack_lists``' stable sort preserves it)."""
    L = ids.shape[0]
    col = jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = (col < counts[:, None]) & (ids >= 0)
    if keep is not None:
        valid &= keep_lookup(as_keep_mask(keep), ids)
    labels = jnp.where(valid, jnp.arange(L, dtype=jnp.int32)[:, None], -1)
    return labels.reshape(-1), jnp.sum(valid, axis=1)


def compact(index, *, headroom: float = 2.0):
    """Rewrite an (optionally tombstoned) IVF index's slabs: drop dead
    rows, shrink ``list_cap`` to ``headroom ×`` the live per-list maximum
    (≥ the build-time ``list_cap_ratio`` default, so post-compact inserts
    have room).  Returns a PLAIN index — tombstones are consumed.  One
    device pass through the chunked builder's packer; derived IVF-PQ
    tiers (recon / ADC LUTs / 4-bit packing) are re-derived to match the
    input.

    A tombstoned **brute-force** database compacts too: dead rows drop
    into a fresh contiguous slab (ROADMAP item 5's reclaim story).  Brute
    ids are positional, so compaction renumbers survivors — new row ``i``
    is old row ``kept[i]`` with ``kept`` the sorted live row numbers
    (``headroom`` is meaningless, there are no lists).  Cagra has no slab
    to rewrite — rebuild it."""
    from . import ivf_flat, ivf_pq, ivf_rabitq

    base, keep = (index.index, index.keep) if isinstance(index, Tombstoned) \
        else (index, None)
    expects(headroom >= 1.0, "headroom must be >= 1.0")
    if getattr(base, "ndim", None) == 2:  # brute-force database
        if keep is None:
            return jnp.asarray(base)
        n = int(base.shape[0])
        # the kept-row gather index is a static shape: one explicit host
        # transfer per compaction, never on the search path
        mask = np.asarray(host_rows(keep.to_bool_array()))[:n]
        kept = np.flatnonzero(mask)
        expects(kept.size >= 1, "compact would drop every row")
        return jnp.asarray(base)[jnp.asarray(kept, jnp.int32)]
    is_pq = isinstance(base, ivf_pq.IvfPqIndex)
    is_rabitq = isinstance(base, ivf_rabitq.IvfRabitqIndex)
    expects(is_pq or is_rabitq or isinstance(base, ivf_flat.IvfFlatIndex),
            "compact is an IVF-family operation (plus tombstoned brute-"
            "force slabs): cagra stores rows positionally — rebuild it")
    was_packed = False
    if is_pq and base.packed:
        was_packed, base = True, base.with_unpacked_codes()
    L, cap = base.n_lists, base.list_cap
    labels, live = _compact_labels(base.ids, base.counts, cap, keep)
    # list_cap is a static slab shape: one explicit host transfer per
    # compaction, never on the search path
    new_cap = max(1, int(float(headroom) *
                         int(jax.device_get(jnp.max(live)))))  # jaxlint: disable=JX01 static slab shape: one explicit transfer per compaction, never on the search path
    if is_pq:
        new_cap = ivf_pq.slab_slots(new_cap)
    elif not is_rabitq:
        new_cap = ivf_flat.slab_capacity(new_cap, base.data.dtype)
    if is_pq:
        flat = (base.codes.reshape(L * cap, -1),
                base.code_norms.reshape(L * cap),
                base.ids.reshape(L * cap))
        (codes, cnorms, ids), counts = pack_lists(
            labels, flat, n_lists=L, cap=new_cap, fills=(0, 0.0, -1))
        out = ivf_pq.IvfPqIndex(base.centroids, base.codebooks, codes,
                                cnorms, ids, counts, base.metric)
        if base.adc_norms is not None:
            out = out.with_adc_luts()
        if base.recon is not None:
            out = out.with_recon()
        return out.with_packed_codes() if was_packed else out
    if is_rabitq:
        # codes + correction scalars are per-row, centroid-relative — a
        # slot keeps them verbatim through the repack (no re-encode)
        flat = (base.codes.reshape(L * cap, -1),
                base.sabs.reshape(L * cap),
                base.res_norms.reshape(L * cap),
                base.code_cdots.reshape(L * cap),
                base.data.reshape(L * cap, -1),
                base.ids.reshape(L * cap))
        (codes, sabs, rn2, cs, data, ids), counts = pack_lists(
            labels, flat, n_lists=L, cap=new_cap,
            fills=(0, 0.0, 0.0, 0.0, 0.0, -1))
        return ivf_rabitq.IvfRabitqIndex(
            base.centroids, base.rotation,
            codes.reshape(L, new_cap, -1), sabs, rn2, cs,
            data.reshape(L, new_cap, base.dim), ids, counts, base.metric)
    flat = (base.data.reshape(L * cap, -1), base.ids.reshape(L * cap))
    (data, ids), counts = pack_lists(labels, flat, n_lists=L, cap=new_cap,
                                     fills=(0.0, -1))
    data = data.reshape(L, new_cap, base.dim)
    norms = jnp.sum(data.astype(jnp.float32) ** 2, axis=2)
    return ivf_flat.IvfFlatIndex(base.centroids, data, ids, counts, norms,
                                 base.metric)
