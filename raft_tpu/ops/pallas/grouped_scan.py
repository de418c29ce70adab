"""List-major IVF probe scan: one MXU product per (list, query tile).

The query-major probe scan gathers every query's probed lists into a
``[nq, B·cap, d]`` slab and scores it with a batched mat-vec, so a list
probed by many queries of a batch is copied and read once per query.
Here the (query, list) pairs are grouped by list
(``ops/blocked_scan.grouped_plan``): each grid step is one *tile* of
``Qt`` query slots that all probe the same list, and scores them as one
``[Qt, d] · [d, cap]`` product with f32 accumulation.  The product's
precision follows the operands' dtype: f32 tiles (IVF-Flat) take
``precision=HIGHEST``, bf16 tiles (the IVF-PQ recon slab) one bf16 MXU
pass, the recon tier's query-major contract.  The tile → list table is
scalar-prefetched into the slab's ``index_map``, so consecutive tiles of
one list reuse the block already in VMEM and nothing of size
``[tiles, cap, d]`` reaches HBM.

Each tile row keeps its exact top-k by ``k`` min-extraction passes in
VMEM (the passes of ``ops/pallas/select_k.py``), so only ``[Qt, k]``
values and list slots leave the kernel.  Per-slot validity rides the
``bias`` row: the stored squared norm (L2) or 0 (inner product) where a
slot holds a live row, ``+inf`` where it does not.

Dispatch rides :mod:`ops.pallas.gate`: Mosaic on a TPU,
``interpret=True`` everywhere else.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_list_topk"]

_LANES = 128


def _kernel(tile_list_ref, n_used_ref, q_ref, qn_ref, x_ref, b_ref,
            val_ref, idx_ref, *, k: int, l2: bool, clamp: bool):
    del tile_list_ref  # consumed by the index maps
    t = pl.program_id(0)

    @pl.when(t < n_used_ref[0])
    def _score():
        exact = q_ref.dtype == jnp.float32
        dots = jax.lax.dot_general(                      # (Qt, cap)
            q_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST if exact else None,
            preferred_element_type=jnp.float32)
        if l2:  # the query-major scan's order: (‖y‖² − 2·dot) + ‖q‖²
            dist = b_ref[...] - 2.0 * dots + qn_ref[...]
            if clamp:
                dist = jnp.maximum(dist, 0.0)
        else:
            dist = b_ref[...] - dots
        qt, cap = dist.shape
        kpad = val_ref.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (qt, cap), 1)
        kslot = jax.lax.broadcasted_iota(jnp.int32, (qt, kpad), 1)

        def extract(s, carry):
            dist, vals, slots = carry
            m = jnp.min(dist, axis=1, keepdims=True)             # (Qt, 1)
            # first slot holding the min, from a second min-reduction
            am = jnp.min(jnp.where(dist == m, lane, cap), axis=1,
                         keepdims=True)
            vals = jnp.where(kslot == s, m, vals)
            slots = jnp.where(kslot == s, am, slots)
            return jnp.where(lane == am, jnp.inf, dist), vals, slots

        _, vals, slots = jax.lax.fori_loop(
            0, k, extract,
            (dist, jnp.full((qt, kpad), jnp.inf, jnp.float32),
             jnp.full((qt, kpad), -1, jnp.int32)))
        val_ref[...] = vals
        idx_ref[...] = slots

    @pl.when(t >= n_used_ref[0])
    def _idle():
        val_ref[...] = jnp.full(val_ref.shape, jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "l2", "clamp", "interpret"))
def grouped_scan(tile_list, n_used, q, qn, data, bias, k: int, l2: bool,
                 clamp: bool, interpret: bool):
    n_tiles, qt, d = q.shape
    cap = data.shape[1]
    kpad = max(_LANES, -(-k // _LANES) * _LANES)
    out = jax.ShapeDtypeStruct((n_tiles, qt, kpad), jnp.float32)
    out_idx = jax.ShapeDtypeStruct((n_tiles, qt, kpad), jnp.int32)
    out_spec = pl.BlockSpec((None, qt, kpad), lambda t, tl, nu: (t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((None, qt, d), lambda t, tl, nu: (t, 0, 0)),
            pl.BlockSpec((None, qt, 1), lambda t, tl, nu: (t, 0, 0)),
            pl.BlockSpec((None, cap, d), lambda t, tl, nu: (tl[t], 0, 0)),
            pl.BlockSpec((None, 1, cap), lambda t, tl, nu: (tl[t], 0, 0)),
        ],
        out_specs=(out_spec, out_spec),
    )
    return pl.pallas_call(
        functools.partial(_kernel, k=k, l2=l2, clamp=clamp),
        grid_spec=grid_spec,
        out_shape=(out, out_idx),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ivf_grouped_scan",
    )(tile_list, n_used, q, qn, data, bias)


def grouped_list_topk(tile_list, n_used, q, qn, data, bias, k: int, *,
                      l2: bool, clamp: bool = True
                      ) -> Tuple[jax.Array, jax.Array]:
    """Per tile row, the exact ``k`` smallest distances to the tile's list
    and their slots in it.

    ``tile_list`` [T] int32: the slab row (list) of each tile; ``n_used``
    [1] int32: tiles past it are idle and return ``+inf``/``-1``.  ``q``
    [T, Qt, d] and ``qn`` [T, Qt, 1] f32: the queries of each tile's
    slots and their squared norms.  ``data`` [L, cap, d] of ``q``'s
    dtype, f32 (scored at ``precision=HIGHEST``) or bf16 (one bf16
    pass): the list slab, read in its stored layout.  ``bias``
    [L, 1, cap] f32: the stored squared norm (``l2``) or 0 where a slot
    is live, ``+inf`` where it is not.  Distance ``bias − 2·dot + qn``
    (``l2``; floored at 0 with ``clamp``, as IVF-Flat's are and the
    recon tier's are not) or ``bias − dot``.  Returns ``(values,
    slots)`` of ``[T, Qt, k]``, ascending; a row with fewer than ``k``
    live slots ends in ``+inf``.
    """
    from .gate import interpret

    vals, slots = grouped_scan(tile_list, n_used, q, qn, data, bias, int(k),
                               bool(l2), bool(clamp),
                               interpret("ivf_grouped_scan"))
    return vals[..., :k], slots[..., :k]
