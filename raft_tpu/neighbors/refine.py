"""Exact candidate re-ranking — the cuVS ``refine`` stage.

Takes approximate candidates (e.g. IVF-PQ output oversampled at
``k·refine_ratio``) and recomputes exact distances against the original
dataset, returning the true top-k.  The gather of candidate vectors plus one
batched MXU dot is exactly how TPU-KNN (PAPERS.md) re-ranks, and it recovers
most of the recall PQ compression loses.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..core.array import wrap_array
from ..core.errors import expects
from ..matrix.select_k import select_k

__all__ = ["Refined", "refine", "refined_searcher"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Refined:
    """An index served with exact re-ranking: the family's search runs at
    ``k·ratio`` candidates and :func:`refine` re-ranks them over
    ``dataset`` in the same program.

    The index's stored ids must be row numbers of ``dataset`` (the
    default ids of every IVF build).  ``index`` may be a
    ``mutation.Tombstoned`` view, whose filter still holds.  ``dataset``
    rides as one more searcher operand and is never copied.  A pytree,
    like ``Tombstoned``; ``ratio`` is static."""

    index: Any
    dataset: jax.Array
    ratio: int = dataclasses.field(metadata=dict(static=True))

    def __post_init__(self):
        expects(isinstance(self.ratio, int) and self.ratio >= 1,
                f"refine ratio must be an int >= 1, got {self.ratio!r}")

    @property
    def dim(self) -> int:
        return int(self.dataset.shape[1])

    @property
    def size(self) -> int:
        return int(self.index.size)


@partial(jax.jit, static_argnames=("k", "metric"))
def _refine_impl(dataset, queries, candidates, k: int, metric: str):
    nq, cand = candidates.shape
    safe = jnp.maximum(candidates, 0)
    vecs = dataset[safe]                          # [nq, cand, d]
    qf = queries.astype(jnp.float32)
    dots = jnp.einsum("qcd,qd->qc", vecs, qf,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
    if metric == "inner_product":
        dist = -dots
    else:
        from ..ops.blocked_scan import row_sq_norms

        vn = row_sq_norms(vecs.astype(jnp.float32))
        qn = row_sq_norms(qf)
        dist = jnp.maximum(vn - 2.0 * dots + qn[:, None], 0.0)
    dist = jnp.where(candidates >= 0, dist, jnp.inf)
    vals, idx = select_k(dist, k, in_idx=candidates, select_min=True)
    if metric == "euclidean":
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    elif metric == "inner_product":
        vals = -vals
    return vals, idx


def refine(dataset, queries, candidates, k: int, *,
           metric: str = "sqeuclidean", res=None) -> Tuple[jax.Array, jax.Array]:
    """Re-rank ``candidates[nq, n_cand]`` (−1 = missing) with exact distances
    over ``dataset``; returns ``(distances, ids)`` of (nq, k)."""
    d = wrap_array(dataset, ndim=2, name="dataset")
    q = wrap_array(queries, ndim=2, name="queries")
    c = jnp.asarray(candidates, jnp.int32)
    expects(c.ndim == 2 and c.shape[0] == q.shape[0], "candidates shape mismatch")
    expects(k <= c.shape[1], "k exceeds candidate count")
    return _refine_impl(d, q, c, int(k), metric)


def refined_searcher(fn, operands, dataset, k: int, metric: str):
    """Wrap a family searcher ``(fn, operands)`` that returns ``k·ratio``
    candidates into one that re-ranks them exactly over ``dataset``:
    ``(fn', (dataset, *operands))``, the ``raft_tpu.serve`` contract,
    equal to the family's search followed by :func:`refine`."""

    def refined(q, data, *ops):
        _, cand = fn(q, *ops)
        return _refine_impl(data, q, cand, int(k), metric)

    return refined, (dataset,) + tuple(operands)
