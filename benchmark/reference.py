"""The plain reference, recall, and the comparison that decides ``correct``.

Nothing here imports the program under test.  The reference is exact
brute force in plain ``jax.numpy``: squared L2 as ``‖q‖² + ‖y‖² − 2⟨q, y⟩``
with the dot at ``precision="highest"``, then ``lax.top_k``, a block of
queries at a time so that the distance block fits beside the base.

``precision="high"`` is the control: the same reference with the dot in
three bf16 passes (``hi·hi + hi·lo + lo·hi``), written out so that it
rounds the same way on every backend.  ``hi`` is rounded with
``lax.reduce_precision``, which the compiler keeps; a float32 → bf16 →
float32 round trip it may drop as excess precision, which on the TPU
left ``lo`` zero and the control a single bf16 pass.  It is the step
below the configured float32-at-highest that a later change would be
tempted by.

The comparison checks every answer the timed path returned:

- ``recall_miss``: the share of the reference's ``k`` nearest that the
  answers left out (1 − recall@k), so that the answers are the nearest
  neighbours and not only well-formed ones;
- ``dist_gap_ulps``: the widest gap between a returned distance and the
  float64 distance of the id returned with it, in float32 ulps of
  ``‖q‖² + ‖y‖²`` (the scale a float32 ``‖q‖² + ‖y‖² − 2⟨q, y⟩`` rounds at);
- ``bad_rows``: answer rows with an id outside the base, a repeated id, a
  distance that is not finite, or distances out of ascending order.

Recall@k is also reported as an end-to-end metric.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)


def _dots(q, base, precision):
    if precision == "highest":
        return jnp.matmul(q, base.T, precision="highest")
    if precision != "high":
        raise ValueError(f"unknown reference precision {precision!r}")

    def split(a):
        hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)

    qh, ql = split(q)
    bh, bl = split(base)

    def mm(a, b):
        return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)

    return mm(qh, bh) + (mm(qh, bl) + mm(ql, bh))


@partial(jax.jit, static_argnames=("k", "precision"))
def _knn_block(q, base, base_sq, *, k, precision):
    q = q.astype(jnp.float32)
    q_sq = jnp.sum(q * q, axis=1)
    d = q_sq[:, None] + base_sq[None, :] - 2.0 * _dots(q, base, precision)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def exact_knn(base, queries, k: int, *, precision: str = "highest",
              block: int = 256):
    """``(distances, ids)`` host arrays of the ``k`` nearest base rows of
    every query, nearest first."""
    base = jnp.asarray(base, jnp.float32)
    base_sq = jnp.sum(base * base, axis=1)
    queries = np.asarray(queries, np.float32)
    n = len(queries)
    pad = -n % block
    q = np.concatenate([queries, np.zeros((pad, queries.shape[1]),
                                          np.float32)]) if pad else queries
    out_d, out_i = [], []
    for lo in range(0, len(q), block):
        d, i = _knn_block(jnp.asarray(q[lo:lo + block]), base, base_sq, k=k,
                          precision=precision)
        out_d.append(d)
        out_i.append(i)
    d = np.concatenate([np.asarray(x) for x in out_d])[:n]
    i = np.concatenate([np.asarray(x) for x in out_i])[:n]
    return d, i


def recall(ids, ref_ids) -> float:
    """Mean share of each row's reference ids found among its returned
    ids (recall@k with k the width of ``ref_ids``)."""
    ids = np.asarray(ids)
    ref_ids = np.asarray(ref_ids)
    hit = (ids[:, :, None] == ref_ids[:, None, :]).any(axis=1)
    return float(hit.mean())


def bad_rows(dist, ids, n_base: int) -> int:
    """Answer rows no correct search can return (see module doc)."""
    dist = np.asarray(dist)
    ids = np.asarray(ids)
    out_of_range = ((ids < 0) | (ids >= n_base)).any(axis=1)
    srt = np.sort(ids, axis=1)
    repeated = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    not_finite = ~np.isfinite(dist).all(axis=1)
    unordered = (np.diff(dist, axis=1) < 0).any(axis=1)
    return int(np.sum(out_of_range | repeated | not_finite | unordered))


def _distinct_rows(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    row = np.dtype((np.void, a.dtype.itemsize * a.shape[1]))
    return np.unique(a.view(row).ravel()).view(a.dtype).reshape(-1,
                                                                a.shape[1])


def dist_gap_ulps(base, queries, pool_idx, dist, ids,
                  chunk: int = 65536) -> float:
    """Widest gap between a returned distance and the float64 squared
    distance of the id it came with, in float32 ulps of ``‖q‖² + ‖y‖²``.

    ``pool_idx[r]`` is the row of ``queries`` that answer row ``r``
    answered.  Ids outside the base and distances that are not finite
    are left to :func:`bad_rows`.  A query answered the same way many
    times is computed once."""
    queries = np.asarray(queries)
    n_base = int(base.shape[0])
    k = ids.shape[1]
    answers = _distinct_rows(np.concatenate(
        [np.asarray(pool_idx, np.int64)[:, None], np.asarray(ids, np.int64),
         np.asarray(dist, np.float64).view(np.int64)], axis=1))
    q_of = np.repeat(answers[:, 0], k)
    flat_i = answers[:, 1:k + 1].reshape(-1)
    flat_d = answers[:, k + 1:].reshape(-1).view(np.float64)
    ok = (flat_i >= 0) & (flat_i < n_base) & np.isfinite(flat_d)
    if not ok.any():
        return float("inf")
    q_of, flat_i, flat_d = q_of[ok], flat_i[ok], flat_d[ok]
    rows, inverse = np.unique(flat_i, return_inverse=True)
    y_all = np.asarray(jnp.take(jnp.asarray(base), jnp.asarray(rows), axis=0),
                       np.float64)
    worst = 0.0
    for lo in range(0, len(flat_i), chunk):
        q = queries[q_of[lo:lo + chunk]].astype(np.float64)
        y = y_all[inverse[lo:lo + chunk]]
        exact = np.sum((q - y) ** 2, axis=1)
        scale = F32_EPS * (np.sum(q * q, axis=1) + np.sum(y * y, axis=1))
        gap = np.abs(flat_d[lo:lo + chunk] - exact) / scale
        worst = max(worst, float(np.max(gap)))
    return worst
