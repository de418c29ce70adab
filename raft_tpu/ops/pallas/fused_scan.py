"""Fused per-query slab distance + partial top-k — the blocked-scan kernel.

:func:`fused_l2_topk.fused_shortlist` fuses the *shared-database* matmul
(every query scores the same rows).  The blocked engines are different:
each query gathers its OWN candidate slab (its probed lists, its frontier
neighborhood), so the distance tile is a batched ``[C, d] · [d]``
contraction — the pinned accumulation shape of
``ops/blocked_scan.slab_dots``.  This kernel fuses that tile with an
in-register approximate partial top-k per TPU-KNN's PartialReduce scheme
(PAPERS.md, arXiv 2206.14286):

* grid ``(q_blocks, c_blocks)``, candidate dimension innermost; each step
  scores a ``(BM, BN)`` block of ``base − 2·⟨q, vec⟩`` via a batched
  ``dot_general`` (bf16 inputs, f32 accumulation) without the ``[nq, C]``
  distance block ever reaching HBM,
* every lane position is a shortlist bucket keeping its branch-free
  **running top-2** (value + int32 c-block id) in VMEM-resident output
  refs — the same 2-deep per-bucket queue as ``fused_l2_topk``, so a true
  neighbor is shed only when ≥ 3 of a query's top-k collide in one of the
  BN buckets within a single slab,
* the caller (``ops/blocked_scan.scan_topk_fused``) folds the
  ``(nq, 2·BN)`` shortlist into the scan carry and exactly re-scores
  4k finalists, so values stay f32-exact and only the candidate *set* is
  approximate (recall-gated).

Dispatch rides :mod:`ops.pallas.gate`: Mosaic on a TPU,
``interpret=True`` parity everywhere else.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_slab_topk"]


def _kernel(q_ref, v_ref, b_ref, v1_ref, i1_ref, v2_ref, i2_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        v1_ref[:] = jnp.full_like(v1_ref, jnp.inf)
        i1_ref[:] = jnp.full_like(i1_ref, -1)
        v2_ref[:] = jnp.full_like(v2_ref, jnp.inf)
        i2_ref[:] = jnp.full_like(i2_ref, -1)

    # batched [BN, d] · [d] contraction — one query row against its own
    # slab block, f32 accumulation (the slab_dots accumulation shape).
    # Mosaic needs a non-contracting lhs dimension: the query rides a
    # unit middle axis, (BM, 1, d) · (BM, BN, d) → (BM, 1, BN)
    dots = jax.lax.dot_general(
        q_ref[:][:, None, :], v_ref[:],
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)[:, 0, :]      # (BM, BN)
    dist = b_ref[:] - 2.0 * dots
    # a bucket's winning candidate ≡ its lane position (mod BN): the int32
    # c-block id alone identifies the slab position
    blk = j.astype(jnp.int32)

    # branch-free running top-2 merge per lane bucket
    r1, r2 = v1_ref[:], v2_ref[:]
    first = dist < r1
    loser = jnp.where(first, r1, dist)                    # max(dist, r1)
    li = jnp.where(first, i1_ref[:], blk)
    v1_ref[:] = jnp.where(first, dist, r1)
    i1_ref[:] = jnp.where(first, blk, i1_ref[:])
    second = loser < r2
    v2_ref[:] = jnp.where(second, loser, r2)
    i2_ref[:] = jnp.where(second, li, i2_ref[:])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_scan(q, vecs, base, bm, bn, interpret):
    nq, c, d = vecs.shape
    grid = (pl.cdiv(nq, bm), c // bn)
    out_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, 0),
                            memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((grid[0] * bm, bn), jnp.float32)
    idx_shape = jax.ShapeDtypeStruct((grid[0] * bm, bn), jnp.int32)
    v1, i1, v2, i2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, bn, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(out_spec, out_spec, out_spec, out_spec),
        out_shape=(out_shape, idx_shape, out_shape, idx_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_scan",
    )(q, vecs, base)
    # reconstruct slab positions: pos = c_block_id * BN + lane position
    lane = jax.lax.broadcasted_iota(jnp.int32, (nq, bn), 1)
    vals = jnp.concatenate([v1[:nq], v2[:nq]], axis=1)
    pos = jnp.concatenate([i1[:nq] * bn + lane, i2[:nq] * bn + lane], axis=1)
    # unfilled buckets carry block id -1 and +inf values: clamp so
    # downstream gathers stay in-bounds (+inf keeps them out of any top-k)
    return vals, jnp.maximum(pos, 0)


def fused_slab_topk(
    vecs: jax.Array,
    base: jax.Array,
    q: jax.Array,
    *,
    bm: int = 8,
    bn: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-query shortlist of ``2*bn`` best slab positions by
    ``base − 2·⟨q, vec⟩`` (monotone in L2 for ``base = ‖vec‖²``; use
    ``base = 0`` for inner product, where the surrogate is ``−2·dots``).

    ``vecs`` is the gathered ``(nq, C, d)`` slab, ``base`` the f32
    ``(nq, C)`` per-candidate offset — invalid/padded lanes must carry
    ``base = +inf`` so they never surface.  Inputs are cast to bf16 for
    the MXU pass (f32 accumulation): this is the *approximate-partial*
    arm — the caller re-scores survivors exactly.  Returns
    ``(values, slab_positions)`` of shape ``(nq, 2*bn)``, unsorted.

    ``interpret=None`` takes the platform's mode (``ops/pallas/gate``:
    Mosaic on a TPU, interpret elsewhere); pass ``True`` to force
    interpret mode.
    """
    from ...core.errors import expects

    nq, c, d = vecs.shape
    expects(base.shape == (nq, c), f"base shape {base.shape} != ({nq}, {c})")
    expects(q.shape == (nq, d), f"q shape {q.shape} != ({nq}, {d})")
    if interpret is None:
        from .gate import interpret as _interpret

        interpret = _interpret("fused_slab_topk")
    bn = min(bn, ((max(c, 1) + 127) // 128) * 128)  # keep lane alignment
    dpad = (-d) % 128
    if dpad:  # lane-width pad (zeros don't change dots)
        vecs = jnp.pad(vecs, ((0, 0), (0, 0), (0, dpad)))
        q = jnp.pad(q, ((0, 0), (0, dpad)))
    cpad = (-c) % bn
    if cpad:
        vecs = jnp.pad(vecs, ((0, 0), (0, cpad), (0, 0)))
        base = jnp.pad(base, ((0, 0), (0, cpad)), constant_values=jnp.inf)
    bm = min(bm, max(1, nq))
    return fused_scan(q.astype(jnp.bfloat16), vecs.astype(jnp.bfloat16),
                      base.astype(jnp.float32), bm, bn, interpret)
