"""Fused L2-distance + lane-bucketed shortlist — the flagship kNN kernel.

TPU-KNN (PAPERS.md, arXiv 2206.14286) reaches peak FLOP/s by folding
top-k selection into the distance matmul's epilogue so the ``(m, n)``
distance matrix never touches HBM.  This kernel is that design in Pallas:

* grid ``(m_blocks, n_blocks)`` with the database dimension innermost;
  each step computes a ``(BM, BN)`` block of ``‖y‖² − 2·x·yᵀ`` on the MXU
  (bf16 inputs, f32 accumulation),
* every *lane position* ``p ∈ [0, BN)`` is a shortlist bucket holding the
  columns ``{p, p+BN, p+2BN, …}``; the kernel keeps each bucket's
  **running top-2** (value + column id) in VMEM-resident output refs.
  The update is branch-free elementwise compare/select on the VPU — no
  argmin, no cross-lane reduction (that was measured 3× slower), the
  PartialReduce trick from the TPU-KNN paper with a 2-deep per-bucket
  queue,
* a true neighbor is missed only when ≥ 3 of the query's top-k collide
  in one of the BN buckets: P ≈ C(k,3)/BN² per query (< 3e-5 for k=10,
  BN = 2048), so the ``(m, 2·BN)`` shortlist is effectively exact; the
  caller (``neighbors.brute_force``) re-scores it in f32, removing bf16
  rounding from the final ranking.

HBM traffic: x and y are read (y: ``⌈m/BM⌉`` times), the distance matrix
itself never leaves VMEM.  Compare ``matrix/detail/select_radix.cuh`` +
``linalg/detail/contractions.cuh`` for the reference's (separate) CUDA
kernels.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_shortlist"]


def _kernel(x_ref, y_ref, yn_ref, v1_ref, i1_ref, v2_ref, i2_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        v1_ref[:] = jnp.full_like(v1_ref, jnp.inf)
        i1_ref[:] = jnp.full_like(i1_ref, -1)
        v2_ref[:] = jnp.full_like(v2_ref, jnp.inf)
        i2_ref[:] = jnp.full_like(i2_ref, -1)

    if x_ref.dtype == jnp.int8:
        # int8 MXU pass (2x bf16 rate, int32 accumulation — exact)
        dots = jnp.dot(x_ref[:], y_ref[:].T,
                       preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        dots = jnp.dot(x_ref[:], y_ref[:].T,
                       preferred_element_type=jnp.float32)
    dist = yn_ref[:] - 2.0 * dots                     # (BM, BN); ‖x‖² added later
    # a bucket's winning column ≡ its lane position (mod BN): storing the
    # int16 n-block id alone identifies the column — no per-lane iota pass
    blk = j.astype(jnp.int16)

    # branch-free running top-2 merge per lane bucket
    r1, r2 = v1_ref[:], v2_ref[:]
    first = dist < r1
    loser = jnp.where(first, r1, dist)                # max(dist, r1)
    li = jnp.where(first, i1_ref[:], blk)
    v1_ref[:] = jnp.where(first, dist, r1)
    i1_ref[:] = jnp.where(first, blk, i1_ref[:])
    second = loser < r2
    v2_ref[:] = jnp.where(second, loser, r2)
    i2_ref[:] = jnp.where(second, li, i2_ref[:])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_l2_topk(xb, yb, yn, bm, bn, interpret):
    m = xb.shape[0]
    n = yb.shape[0]
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    out_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, 0), memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((grid[0] * bm, bn), jnp.float32)
    idx_shape = jax.ShapeDtypeStruct((grid[0] * bm, bn), jnp.int16)
    v1, i1, v2, i2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, xb.shape[1]), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, yb.shape[1]), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=(out_spec, out_spec, out_spec, out_spec),
        out_shape=(out_shape, idx_shape, out_shape, idx_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_l2_topk",
    )(xb, yb, yn)
    # reconstruct column ids: col = block_id * BN + lane position
    lane = jax.lax.broadcasted_iota(jnp.int32, (m, bn), 1)
    vals = jnp.concatenate([v1[:m], v2[:m]], axis=1)
    idx = jnp.concatenate(
        [i1[:m].astype(jnp.int32) * bn + lane, i2[:m].astype(jnp.int32) * bn + lane],
        axis=1,
    )
    # unfilled buckets (possible when n < bn) carry block id -1 and +inf
    # values: clamp the id so downstream gathers stay in-bounds (the +inf
    # value keeps them out of every top-k)
    return vals, jnp.maximum(idx, 0)


def fused_shortlist(
    x: jax.Array,
    y: jax.Array,
    yn: jax.Array,
    *,
    bm: int = 256,
    bn: int = 2048,
) -> Tuple[jax.Array, jax.Array]:
    """Per-query shortlist of ``2*bn`` nearest candidates by
    ``yn − 2·x·yᵀ`` (monotone in L2 distance for fixed query when ``yn``
    is ``‖y‖²`` — or any per-column offset with the same property).

    Float inputs are cast to bf16 for the MXU pass.  **int8 inputs run an
    int8 MXU pass** (2× the bf16 rate, exact int32 accumulation) —
    ``uint8`` corpora (SIFT/bigann-style) are centered to int8 with the
    correction folded into ``yn`` (see :func:`int8_surrogate_norms`; the
    per-*query* correction term is constant within a row and drops out of
    the ranking).  ``yn`` must be f32.  Returns ``(values, column_ids)``
    of shape ``(m, 2*bn)`` — *unsorted*; exact re-scoring is the caller's
    job.  Padded database rows get ``yn = +inf`` so they never surface.

    The int16 block-id encoding bounds the database at ``32767 * bn`` rows
    (~67M at the default ``bn``) per call; shard larger databases.
    """
    from ...core.errors import expects

    m, d = x.shape
    n = y.shape[0]
    expects(n <= 32767 * bn,
            f"database rows {n} exceed int16 block-id range ({32767 * bn}) "
            f"at bn={bn}; shard the database or raise bn")
    expects(x.dtype == y.dtype, f"x/y dtype mismatch {x.dtype} vs {y.dtype}")
    if x.dtype == jnp.uint8:
        # center to int8 BEFORE padding (pad zeros must stay zeros)
        x = center_int8(x)
        y = center_int8(y)
    # pad feature dim to lane width for the MXU (zeros don't change dots)
    dpad = (-d) % 128
    if dpad:
        x = jnp.pad(x, ((0, 0), (0, dpad)))
        y = jnp.pad(y, ((0, 0), (0, dpad)))
    npad = (-n) % bn
    if npad:
        y = jnp.pad(y, ((0, npad), (0, 0)))
        yn = jnp.pad(yn, (0, npad), constant_values=jnp.inf)
    bm = min(bm, max(8, m))
    if x.dtype != jnp.int8:
        x = x.astype(jnp.bfloat16)
        y = y.astype(jnp.bfloat16)
    yn = yn.reshape(1, -1).astype(jnp.float32)
    from .gate import interpret

    return fused_l2_topk(x, y, yn, bm, bn, interpret("fused_l2_topk"))


def center_int8(a: jax.Array) -> jax.Array:
    """``uint8 → int8`` zero-point shift (``a − 128``) — THE centering the
    int8 kernel path scores; :func:`int8_surrogate_norms` is its paired
    ``yn`` convention.  int8 passes through unchanged."""
    if a.dtype == jnp.uint8:
        return (a.astype(jnp.int16) - 128).astype(jnp.int8)
    return a


def int8_surrogate_norms(y: jax.Array) -> jax.Array:
    """The ``yn`` vector for integer datasets fed to :func:`fused_shortlist`.

    For ``int8`` rows this is plainly ``‖y‖²``.  For ``uint8`` rows the
    kernel scores centered values ``y' = y − 128``, so the surrogate
    needs ``yn' = ‖y‖² − 256·Σy``: with ``x' = x − 128``,

    ``‖y‖² − 2·x·y = (‖y‖² − 256·Σy) − 2·x'·y' − 256·Σx' − 32768·d``

    and the last two terms are constant per *query*, leaving the per-row
    ranking unchanged.  Exact in f32 (both terms ≤ 2²³ for d ≤ 128).
    """
    yf = y.astype(jnp.float32)
    yn = jnp.sum(yf * yf, axis=1)
    if y.dtype == jnp.uint8:
        return yn - 256.0 * jnp.sum(yf, axis=1)
    return yn
