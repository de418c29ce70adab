"""Device-side segment utilities shared across subsystems.

The reference expresses these with CUB segmented primitives / atomics
(e.g. ``cpp/include/raft/util/reduction.cuh``); on TPU they are sort +
``segment_sum`` formulations usable inside ``jit``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["within_group_rank"]


def within_group_rank(groups, scores, k: int):
    """Rank of each element among its group, ordered by ascending score.

    ``groups``: (n,) int32 in [0, k); ``scores``: (n,) sort key within the
    group (ties broken by position via the stable lexsort).  Returns (n,)
    int32 ranks.  Used by capacity-capped assignment
    (:func:`raft_tpu.cluster.kmeans.capped_assign`) and the CAGRA reverse-
    edge builder (:mod:`raft_tpu.neighbors.cagra`).
    """
    n = groups.shape[0]
    # lexsort((scores, groups))'s order as two stable one-key sorts, the
    # minor key first: the TPU compiler takes ~200 s over the two-key
    # sort at 65,536 rows and ~23 s over a one-key one (compiled for v5e)
    by_score = jnp.argsort(scores, stable=True)
    perm = by_score[jnp.argsort(groups[by_score], stable=True)]
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), groups,
                                 num_segments=k)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts[groups[perm]]
    return jnp.zeros((n,), jnp.int32).at[perm].set(rank_sorted)
