"""Family dispatch — map any built index to its uniform ``searcher()``
entry point, with degradation-level effort scaling.

Every index family exposes ``searcher(index, k, params) -> (fn,
operands)`` where ``fn(queries, *operands)`` matches a direct
``search()`` call bit-for-bit and AOT-compiles with ``queries`` as the
only shape-varying input.  This module owns (a) the type→family mapping
and (b) the per-family *effort knob* a degradation level shrinks:

* ``ivf_flat`` / ``ivf_pq`` / ``ivf_rabitq`` — ``n_probes`` (fewer
  lists scanned),
* ``cagra`` — ``itopk_size`` (narrower beam; iterations follow),
* ``brute_force`` fast mode — ``cand`` (shorter shortlist); exact mode
  has no quality knob and degrades to itself.

Scaled knobs are floored so a degraded searcher still returns k valid
results (``n_probes >= 1``, ``itopk >= k``, ``cand >= k``).

Two views wrap an index and serve transparently: ``mutation.Tombstoned``
(its keep-mask becomes the searcher's prefilter) and ``refine.Refined``
(the family searches ``k·ratio`` candidates and the same program
re-ranks them exactly over the view's dataset).  A ``Refined`` view may
hold a ``Tombstoned`` one, never the other way round.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from ..core.errors import expects

__all__ = ["BruteForceSearchParams", "family_of", "make_searcher",
           "index_dim", "index_size", "query_dtype_of",
           "unwrap_refined", "unwrap_tombstones"]


@dataclasses.dataclass(frozen=True)
class BruteForceSearchParams:
    """Search-time knobs for serving a raw (n, d) database with
    :func:`raft_tpu.neighbors.brute_force.knn` semantics (the family has
    no index object, so the params struct lives here)."""

    metric: str = "sqeuclidean"
    mode: str = "exact"          # exact | fast
    tile: int = 8192
    cand: int = 64               # fast-mode shortlist width
    cut: str = "exact"
    refine_precision: str = "highest"


def unwrap_refined(index):
    """Split a ``refine.Refined`` view into ``(index, dataset, ratio)``
    — ``(index, None, 1)`` for anything else."""
    from ..neighbors.refine import Refined

    if isinstance(index, Refined):
        return index.index, index.dataset, index.ratio
    return index, None, 1


def unwrap_tombstones(index):
    """Split a ``mutation.Tombstoned`` view into ``(index, keep_bitset)``
    — ``(index, None)`` for a plain index.  The serve layer does this at
    every entry point so tombstoned views serve transparently (the mask
    becomes the searcher's shared prefilter operand).  A
    ``refine.Refined`` view around it is looked through."""
    from ..neighbors.mutation import Tombstoned

    from ..neighbors.refine import Refined

    index, _, _ = unwrap_refined(index)
    if isinstance(index, Tombstoned):
        expects(not isinstance(index.index, Refined),
                "a Tombstoned view cannot hold a Refined one: wrap it as "
                "Refined(Tombstoned(index), dataset, ratio)")
        return index.index, index.keep
    return index, None


def family_of(index) -> str:
    """Index family name for cache keys / metrics labels."""
    from ..neighbors.cagra import CagraIndex
    from ..neighbors.ivf_flat import IvfFlatIndex
    from ..neighbors.ivf_pq import IvfPqIndex
    from ..neighbors.ivf_rabitq import IvfRabitqIndex
    from ..neighbors.ooc import OocIndex

    index, _ = unwrap_tombstones(index)
    if isinstance(index, IvfFlatIndex):
        return "ivf_flat"
    if isinstance(index, IvfPqIndex):
        return "ivf_pq"
    if isinstance(index, IvfRabitqIndex):
        return "ivf_rabitq"
    if isinstance(index, OocIndex):
        return "ooc"
    if isinstance(index, CagraIndex):
        return "cagra"
    if isinstance(index, (jax.Array, np.ndarray)) and index.ndim == 2:
        return "brute_force"
    raise TypeError(f"no serving searcher for {type(index).__name__}; "
                    "expected IvfFlatIndex/IvfPqIndex/IvfRabitqIndex/"
                    "OocIndex/CagraIndex, a mutation.Tombstoned view of "
                    "one, or a 2-D database array")


def index_dim(index) -> int:
    _, dataset, _ = unwrap_refined(index)
    if dataset is not None:
        return int(dataset.shape[1])
    index, _ = unwrap_tombstones(index)
    return int(index.shape[1]) if family_of(index) == "brute_force" \
        else int(index.dim)


def index_size(index) -> int:
    index, _ = unwrap_tombstones(index)
    return int(index.shape[0]) if family_of(index) == "brute_force" \
        else int(index.size)


def query_dtype_of(index):
    """The dtype warm-up should precompile for — the dtype the stored
    vectors expect queries in (requests with another dtype compile their
    own bucket set on first use).  A ``Refined`` view takes queries in
    the dtype of its family's index."""
    index, _ = unwrap_tombstones(index)
    fam = family_of(index)
    if fam == "brute_force":
        return jax.numpy.asarray(index[:1]).dtype if isinstance(
            index, np.ndarray) else index.dtype
    if fam == "cagra":
        return index.dataset.dtype
    return index.centroids.dtype


def _scaled(value: int, scale: float, floor: int) -> int:
    return max(int(floor), int(round(value * float(scale))))


def make_searcher(index, k: int, params=None, *, effort_scale: float = 1.0,
                  seed: int = 0, filter=None):
    """Build the ``(fn, operands)`` searcher for ``index`` at one
    degradation point.  ``effort_scale`` in (0, 1] multiplies the
    family's effort knob; 1.0 reproduces direct ``search()`` exactly
    (the serve bit-identity contract).

    Only the effort knob is scaled — every other search param passes
    through unchanged.  In particular the IVF families' ``probe_block``
    (blocked probe scan; 0 = auto-tuned) reaches the baked executable
    as given: it changes wall-clock only, never results, so degradation
    ladders keep one blocking choice across all effort levels.

    A ``mutation.Tombstoned`` view is unwrapped here: its keep-mask
    becomes the family searcher's shared ``filter=`` operand (deleted
    ids report as −1/±inf sentinels, never as results), composed with an
    explicit ``filter`` by AND when both are present.

    A ``refine.Refined`` view is unwrapped first: the family's searcher
    runs at ``k·ratio`` candidates and ``refine``'s re-rank follows in the
    same program, with the view's dataset as one more operand.
    ``effort_scale`` still scales only the family's knob."""
    expects(0.0 < effort_scale <= 1.0,
            f"effort_scale must be in (0, 1], got {effort_scale}")
    index, dataset, ratio = unwrap_refined(index)
    if dataset is not None:
        from ..neighbors.refine import refined_searcher

        fn, operands = _family_searcher(index, k * ratio, params,
                                        effort_scale, seed, filter,
                                        refine=True)
        metric = getattr(unwrap_tombstones(index)[0], "metric",
                         "sqeuclidean")
        return refined_searcher(fn, operands, dataset, k, metric)
    return _family_searcher(index, k, params, effort_scale, seed, filter)


def _family_searcher(index, k: int, params, effort_scale: float, seed: int,
                     filter, refine: bool = False):
    index, keep = unwrap_tombstones(index)
    if keep is not None and filter is not None:
        from ..neighbors.mutation import _combined_keep

        filter = _combined_keep(keep, filter)
    elif keep is not None:
        filter = keep
    fam = family_of(index)
    if fam == "brute_force":
        from ..neighbors import brute_force

        p = params or BruteForceSearchParams()
        cand = _scaled(p.cand, effort_scale, k) if p.mode == "fast" \
            else p.cand
        return brute_force.searcher(
            index, k, metric=p.metric, mode=p.mode, tile=p.tile, cand=cand,
            cut=p.cut, refine_precision=p.refine_precision, filter=filter)
    if fam == "ivf_flat":
        from ..neighbors import ivf_flat

        p = params or ivf_flat.IvfFlatSearchParams()
        if effort_scale < 1.0:
            p = dataclasses.replace(
                p, n_probes=_scaled(min(p.n_probes, index.n_lists),
                                    effort_scale, 1))
        return ivf_flat.searcher(index, k, p, filter=filter)
    if fam == "ivf_pq":
        from ..neighbors import ivf_pq

        p = params or ivf_pq.IvfPqSearchParams()
        if effort_scale < 1.0:
            p = dataclasses.replace(
                p, n_probes=_scaled(min(p.n_probes, index.n_lists),
                                    effort_scale, 1))
        fn, operands = ivf_pq.searcher(index, k, p, filter=filter)
        tier = ivf_pq.search_tier(index, p)

        def counted(q, *ops):
            ivf_pq.count_search(tier, refine)
            return fn(q, *ops)

        return counted, operands
    if fam == "ivf_rabitq":
        from ..neighbors import ivf_rabitq

        p = params or ivf_rabitq.IvfRabitqSearchParams()
        if effort_scale < 1.0:
            p = dataclasses.replace(
                p, n_probes=_scaled(min(p.n_probes, index.n_lists),
                                    effort_scale, 1))
        return ivf_rabitq.searcher(index, k, p, filter=filter)
    if fam == "ooc":
        from ..neighbors import ooc

        p = params or ooc.OocSearchParams()
        if effort_scale < 1.0:
            p = dataclasses.replace(
                p, n_probes=_scaled(min(p.n_probes, index.n_lists),
                                    effort_scale, 1))
        return ooc.searcher(index, k, p, filter=filter)
    from ..neighbors import cagra

    # resolve 0 = auto itopk/width from the tuned table FIRST — scaling
    # the raw params would multiply the auto sentinel, not the beam
    p = cagra.resolved_search_params(index, k, params)
    if effort_scale < 1.0:
        p = dataclasses.replace(
            p, itopk_size=_scaled(max(p.itopk_size, k), effort_scale, k))
    return cagra.searcher(index, k, p, seed=seed, filter=filter)
