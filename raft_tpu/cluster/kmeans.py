"""KMeans (Lloyd + k-means++ init) and balanced KMeans, TPU-native.

Capability parity targets (no in-tree CUDA ancestor — migrated to cuVS):
``cluster::kmeans`` fit/predict/transform and ``cluster::kmeans_balanced``
(the IVF coarse quantizer; north-star config #3).  Design:

* assignment  — fused L2 argmin (`distance.fused_l2_nn`): one MXU gemm per
  database tile, never materializing (n, k) unless k is tiny.
* update      — `segment_sum` scatter-add of points into centroids.
* fit loop    — `lax.while_loop` on (centroids, inertia, iter): the entire
  fit is ONE compiled XLA program.
* sharded fit — rows sharded over a mesh axis; each shard computes partial
  (sums, counts, inertia) and a `psum` merges them — the SPMD analog of the
  reference's MNMG kmeans-over-comms_t pattern (SURVEY.md §2.9.4).
* balanced    — Lloyd with a size-penalty term folded into the assignment
  cost, yielding near-uniform list sizes for IVF layouts.
"""

from __future__ import annotations

import dataclasses
import functools as _functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.array import wrap_array
from ..core.compat import shard_map
from ..core.errors import expects
from ..distance.fused import _fused_l2_nn
from ..distance.pairwise import sq_l2
from ..utils.segment import within_group_rank as _within_group_rank

__all__ = [
    "KMeansParams",
    "capped_assign",
    "capped_assign_room",
    "kmeans_plus_plus_init",
    "kmeans_fit",
    "kmeans_predict",
    "kmeans_fit_predict",
    "kmeans_transform",
    "kmeans_balanced_fit",
    "kmeans_balanced_predict",
    "kmeans_balanced_fit_predict",
]


@dataclasses.dataclass(frozen=True)
class KMeansParams:
    """Fit configuration (per-call parameter struct, the reference's config
    idiom — SURVEY.md §5.6b)."""

    n_clusters: int = 8
    max_iter: int = 20
    tol: float = 1e-4
    seed: int = 0
    init: str = "kmeans++"  # "kmeans++" | "random"
    balanced_penalty: float = 1.0   # soft size penalty during balanced training
    balanced_max_ratio: float = 2.0  # hard cap = ratio · n/k for balanced lists
    # "highest" = exact 3-pass gemm for training assignments (default);
    # "bf16" = single-pass MXU gemm (~3x assignment rate) for the balanced
    # TRAINING loop only — the final capped assignment and the returned
    # inertia always use the exact gemm, so the hard size bound and the
    # reported quality are precision-independent
    balanced_assign_precision: str = "highest"  # "highest" | "bf16"


def _centroid_dtype(x):
    """Centroids are continuous quantities: float inputs keep their dtype
    (bf16 stays bf16), integer corpora (uint8/int8 SIFT-class) get f32 —
    rounding means back to uint8 would wrap residuals and quantize the
    probe routing (the reference's kmeans also emits float centroids for
    integer data)."""
    return x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32


def _assign(x, centroids, tile: int = 4096):
    """(labels, sq_dists) for each row of x against centroids."""
    d, i = _fused_l2_nn(x, centroids, False, min(tile, centroids.shape[0]))
    return i, d


def _update(x, labels, k: int, w=None):
    xf = x.astype(jnp.float32)
    if w is None:
        sums = jax.ops.segment_sum(xf, labels, num_segments=k)
        counts = jax.ops.segment_sum(jnp.ones((x.shape[0],), jnp.float32),
                                     labels, num_segments=k)
    else:  # weighted centroid update: Σ wᵢxᵢ / Σ wᵢ
        sums = jax.ops.segment_sum(xf * w[:, None], labels, num_segments=k)
        counts = jax.ops.segment_sum(w, labels, num_segments=k)
    return sums, counts


def _new_centroids(sums, counts, old):
    # divide by the actual (possibly fractional, with sample_weight) mass;
    # clamping to 1.0 would leave sub-unit-weight clusters unnormalized
    safe = jnp.where(counts[:, None] > 0, counts[:, None], 1.0)
    fresh = sums / safe
    # empty clusters keep their previous position (reference keeps/reseeds)
    return jnp.where(counts[:, None] > 0, fresh, old)


def kmeans_plus_plus_init(key, x, k: int, *, tile: int = 4096,
                          sample_weight=None) -> jax.Array:
    """k-means++ seeding: (w·D²)-weighted sequential sampling, one lax.scan."""
    x = jnp.asarray(x)
    n = x.shape[0]
    k0, key = jax.random.split(key)
    w = None if sample_weight is None else jnp.asarray(sample_weight,
                                                       jnp.float32)
    if w is None:
        first = x[jax.random.randint(k0, (), 0, n)]
    else:  # the first center is weight-sampled too
        first = x[jax.random.choice(k0, n, p=w / jnp.maximum(jnp.sum(w),
                                                             1e-30))]
    xf = x.astype(jnp.float32)

    def d2_to(c):
        diff = xf - c[None, :].astype(jnp.float32)
        return jnp.sum(diff * diff, axis=1)

    def step(carry, sk):
        mind2 = carry
        score = mind2 if w is None else mind2 * w
        p = score / jnp.maximum(jnp.sum(score), 1e-30)
        idx = jax.random.choice(sk, n, p=p)
        c = x[idx]
        mind2 = jnp.minimum(mind2, d2_to(c))
        return mind2, c

    keys = jax.random.split(key, k - 1)
    _, rest = jax.lax.scan(step, d2_to(first), keys)
    return jnp.concatenate([first[None, :], rest], axis=0).astype(x.dtype)


@partial(jax.jit, static_argnames=("k", "max_iter", "init"))
def _fit_impl(x, key, k: int, max_iter: int, tol: float, init: str, w=None):
    if init == "kmeans++":
        c0 = kmeans_plus_plus_init(key, x, k, sample_weight=w)
    else:
        idx = jax.random.choice(key, x.shape[0], (k,), replace=False)
        c0 = x[idx]

    def inertia_of(d2):
        return jnp.sum(d2) if w is None else jnp.sum(d2 * w)

    def cond(state):
        _, prev_inertia, inertia, it = state
        return (it < max_iter) & (
            jnp.abs(prev_inertia - inertia) > tol * jnp.maximum(inertia, 1e-30)
        )

    def body(state):
        c, _, inertia, it = state
        labels, d2 = _assign(x, c)
        sums, counts = _update(x, labels, k, w)
        c2 = _new_centroids(sums, counts, c)
        return c2, inertia, inertia_of(d2), it + 1

    # one warmup Lloyd step so `inertia` holds a real value entering the loop
    c0 = c0.astype(jnp.float32)
    labels, d2 = _assign(x, c0)
    sums, counts = _update(x, labels, k, w)
    state = (_new_centroids(sums, counts, c0), jnp.float32(jnp.inf),
             inertia_of(d2), jnp.int32(1))
    c, _, inertia, n_iter = jax.lax.while_loop(cond, body, state)
    labels, d2 = _assign(x, c)
    return c.astype(_centroid_dtype(x)), labels, inertia_of(d2), n_iter


def kmeans_fit(
    x,
    params: Optional[KMeansParams] = None,
    *,
    sample_weight=None,
    mesh: Optional[Mesh] = None,
    axis: str = "shard",
    res=None,
):
    """Fit centroids. Returns ``(centroids, inertia, n_iter)``.

    ``sample_weight``: optional (n,) per-row weights (classic
    ``cluster::kmeans`` sample_weights parity) — weighted centroid
    updates, weighted inertia, and (w·D²)-weighted k-means++ seeding.

    With ``mesh``, rows are sharded over ``axis`` and each Lloyd step psums
    partial statistics over ICI (multi-chip data-parallel fit).
    ``sample_weight`` is single-device-only for now (the sharded program
    rejects it rather than silently ignoring the weights).
    """
    p = params or KMeansParams()
    x = wrap_array(x, ndim=2, name="x")
    expects(p.n_clusters <= x.shape[0], "n_clusters exceeds n_rows")
    # balanced-only knob (its name says so): reject rather than silently
    # run the plain fit at a precision the caller didn't get
    expects(p.balanced_assign_precision == "highest",
            "balanced_assign_precision applies to kmeans_balanced_fit* "
            "only; the plain fit always assigns at Precision.HIGHEST")
    w = None
    if sample_weight is not None:
        w = jnp.asarray(sample_weight, jnp.float32)
        expects(w.shape == (x.shape[0],),
                f"sample_weight shape {w.shape} != ({x.shape[0]},)")
    key = jax.random.PRNGKey(p.seed)
    if mesh is None:
        c, _, inertia, n_iter = _fit_impl(x, key, p.n_clusters, p.max_iter,
                                          p.tol, p.init, w)
        return c, inertia, n_iter
    expects(w is None, "sample_weight with mesh= is not supported yet; "
                       "fit per-shard weights via the single-device path")
    return _fit_sharded(x, key, p, mesh, axis)


@_functools.lru_cache(maxsize=64)
def _sharded_fit_program(mesh: Mesh, axis: str, k: int, max_iter: int, tol: float):
    """Compile-once sharded Lloyd loop (jit keyed on the static config, not a
    per-call closure — otherwise every kmeans_fit(mesh=...) call re-traces)."""

    def step_fn(c, xs):
        # xs: local (n/nsh, d) rows; c replicated
        labels, d2 = _assign(xs, c)
        sums, counts = _update(xs, labels, k)
        sums = jax.lax.psum(sums, axis)
        counts = jax.lax.psum(counts, axis)
        inertia = jax.lax.psum(jnp.sum(d2), axis)
        return _new_centroids(sums, counts, c), inertia

    def fit(xs, c0):
        def cond(carry):
            _, prev, inertia, it = carry
            return (it < max_iter) & (
                jnp.abs(prev - inertia) > tol * jnp.maximum(inertia, 1e-30)
            )

        def body(carry):
            c, _, inertia, it = carry
            c2, new_inertia = step_fn(c, xs)
            return c2, inertia, new_inertia, it + 1

        c, inertia0 = step_fn(c0, xs)
        c, _, inertia, it = jax.lax.while_loop(
            cond, body, (c, jnp.float32(jnp.inf), inertia0, jnp.int32(1))
        )
        return c, inertia, it

    return jax.jit(
        shard_map(
            fit, mesh=mesh, in_specs=(P(axis), P()), out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )


def _fit_sharded(x, key, p: KMeansParams, mesh: Mesh, axis: str):
    nsh = mesh.shape[axis]
    n, d = x.shape
    expects(n % nsh == 0, f"rows {n} not divisible by shards {nsh}")
    k = p.n_clusters

    if p.init == "kmeans++":
        # k++ on a subsample (the reference trains coarse centroids on a
        # subsample too); full-data k++ would serialize n steps
        sub = x[:: max(1, n // (k * 32))]
        c0 = kmeans_plus_plus_init(key, sub, k).astype(jnp.float32)
    else:
        idx = jax.random.choice(key, n, (k,), replace=False)
        c0 = x[idx].astype(jnp.float32)

    fit = _sharded_fit_program(mesh, axis, k, p.max_iter, float(p.tol))
    c, inertia, n_iter = fit(x, c0)
    return c.astype(_centroid_dtype(x)), inertia, n_iter


def kmeans_predict(x, centroids, *, res=None) -> jax.Array:
    x = wrap_array(x, ndim=2, name="x")
    centroids = wrap_array(centroids, ndim=2, name="centroids")
    return _assign(x, centroids)[0]


def kmeans_fit_predict(x, params: Optional[KMeansParams] = None, **kw):
    c, inertia, n_iter = kmeans_fit(x, params, **kw)
    return c, kmeans_predict(x, c), inertia, n_iter


def kmeans_transform(x, centroids, *, res=None) -> jax.Array:
    """Distance from every row to every centroid (n, k) — L2."""
    from ..distance.pairwise import pairwise_distance

    return pairwise_distance(x, centroids, "euclidean")


# --------------------------------------------------------------------------
# Balanced variant — the IVF coarse quantizer.
# --------------------------------------------------------------------------

def _assign_balanced(x, c, counts, penalty, n_per,
                     precision=jax.lax.Precision.HIGHEST):
    """Assignment with multiplicative size penalty:
    ``cost = d² · (1 + λ·size/target)``.

    Multiplicative scaling keeps the penalty proportional to the local
    distance scale: points well inside a cluster stay put, boundary points
    migrate to less-crowded neighbors — additive penalties either do nothing
    (scale too small) or shuffle points across unrelated clusters (too
    large)."""
    d2 = sq_l2(x, c, precision=precision)
    cost = d2 * (1.0 + penalty * counts[None, :] / jnp.maximum(n_per, 1.0))
    labels = jnp.argmin(cost, axis=1)
    real = jnp.take_along_axis(d2, labels[:, None], axis=1)[:, 0]
    return labels, real


#: elements of the largest ``[rows, k]`` f32 distance block the balanced
#: fit and the capped assignment hold at once (1 GiB).  Above it they
#: score row tiles in turn: a 1M-row trainset over 4096 lists is a 16 GB
#: block, more than one 16 GB chip holds beside anything else.
TILE_ELEMS = 1 << 28


def _tile_rows(n: int, k: int) -> int:
    """Rows of one distance tile, or 0 where the whole block fits."""
    if n * k <= TILE_ELEMS:
        return 0
    return max(8, TILE_ELEMS // k // 8 * 8)


def _map_row_tiles(fn, x, rows: int):
    """``fn(x_tile)`` over ``rows``-row tiles of ``x`` (the last padded),
    its per-row outputs concatenated back to ``n`` rows."""
    n = x.shape[0]
    pad = -n % rows
    tiles = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, rows, x.shape[1])
    out = jax.lax.map(fn, tiles)
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:n], out)


def _capped_assign_impl(x, centroids, room, valid=None):
    """Shared core of :func:`capped_assign` / :func:`capped_assign_room`:
    ``room`` is a traced per-cluster capacity vector (k,) int32.

    ``valid``: optional (n,) bool row mask — invalid rows never request a
    cluster, never consume capacity, and keep label −1 (the pipelined
    chunked builds pad the tail chunk to a fixed shape and mask the pads
    here).  With ``valid=None`` (or all-True) the computation is
    bit-identical to the unmasked form: masked rows only ever add
    +inf-distance requests, which :func:`~raft_tpu.utils.segment.
    within_group_rank` ranks after every finite (real) request, so real
    rows' ranks — and therefore acceptance — are unchanged.
    """
    n = x.shape[0]
    k = centroids.shape[0]
    INF = jnp.float32(jnp.inf)
    if valid is None:
        valid = jnp.ones((n,), bool)

    def open_nearest(d2, full):
        """Each row's nearest cluster with room, and its distance."""
        cost = jnp.where(full[None, :], INF, d2)
        cand = jnp.argmin(cost, axis=1).astype(jnp.int32)
        return cand, jnp.take_along_axis(d2, cand[:, None], 1)[:, 0]

    rows = _tile_rows(n, k)
    if rows:  # recompute each tile's distances every round
        def nearest(full):
            return _map_row_tiles(
                lambda xt: open_nearest(sq_l2(xt, centroids), full), x, rows)
    else:
        d2 = sq_l2(x, centroids)

        def nearest(full):
            return open_nearest(d2, full)

    def pending(labels):
        return jnp.sum(((labels < 0) & valid).astype(jnp.int32))

    def cond(carry):
        labels, counts, prev_left = carry
        left = pending(labels)
        return (left > 0) & (left != prev_left)

    def round_fn(carry):
        labels, counts, _ = carry
        prev_left = pending(labels)
        unassigned = (labels < 0) & valid
        cand, cand_d2 = nearest(counts >= room)
        req_d2 = jnp.where(unassigned, cand_d2, INF)
        rank = _within_group_rank(cand, req_d2, k)
        left_room = (room - counts)[cand]
        accept = unassigned & (rank < left_room)
        labels = jnp.where(accept, cand, labels)
        counts = counts + jax.ops.segment_sum(
            accept.astype(jnp.int32), cand, num_segments=k
        )
        return labels, counts, prev_left

    labels0 = jnp.full((n,), -1, jnp.int32)
    counts0 = jnp.zeros((k,), jnp.int32)
    labels, counts, _ = jax.lax.while_loop(
        cond, round_fn, (labels0, counts0, jnp.int32(-1))
    )
    return labels, counts


@partial(jax.jit, static_argnames=("cap",))
def capped_assign(x, centroids, cap: int):
    """Capacity-constrained nearest-centroid assignment.

    Every cluster receives at most ``cap`` points; overflow spills to the
    next-nearest cluster with room.  Per round: each unassigned point
    requests its nearest non-full cluster, requests are ranked by distance
    within each cluster, and the closest ``capacity_left`` are accepted.
    Deterministic, O(rounds · n log n), and the workhorse behind balanced
    IVF list layouts (dense padded lists need a hard size bound).

    Runs until every point is placed or no progress is possible (all
    remaining capacity exhausted — only when ``cap·k < n``); leftover points
    then keep label -1.  While capacity remains, each round accepts at least
    one point, so termination ≡ completion.
    """
    k = centroids.shape[0]
    return _capped_assign_impl(x, centroids, jnp.full((k,), cap, jnp.int32))


@jax.jit
def capped_assign_room(x, centroids, room, valid=None):
    """:func:`capped_assign` against a traced per-cluster ``room`` vector
    (k,) — the streaming-build variant: chunked index builds pass the
    *remaining* capacity of each list (``cap - counts_so_far``) so a chunk
    can never overflow lists filled by earlier chunks.  ``valid``: optional
    (n,) bool row mask (padded fixed-shape chunks); masked rows keep
    label −1 and consume no capacity."""
    return _capped_assign_impl(x, centroids, jnp.asarray(room, jnp.int32),
                               valid)


@partial(jax.jit, static_argnames=("k", "max_iter", "cap", "precision"))
def _balanced_fit_impl(x, key, k: int, max_iter: int, penalty: float, cap: int,
                       precision=jax.lax.Precision.HIGHEST):
    n = x.shape[0]
    n_per = jnp.float32(n / k)
    c0 = kmeans_plus_plus_init(key, x, k).astype(jnp.float32)
    counts0 = jnp.zeros((k,), jnp.float32)

    rows = _tile_rows(n, k)

    def assign(c, counts_s):
        if not rows:
            return _assign_balanced(x, c, counts_s, penalty, n_per, precision)
        return _map_row_tiles(
            lambda xt: _assign_balanced(xt, c, counts_s, penalty, n_per,
                                        precision), x, rows)

    def body(it, carry):
        c, counts_s, _ = carry
        labels, d2 = assign(c, counts_s)
        sums, cnts = _update(x, labels, k)
        c2 = _new_centroids(sums, cnts, c)
        # revive genuinely empty clusters (otherwise frozen forever): slot
        # j-th empty centroid onto the j-th worst-assigned point
        empty = cnts == 0
        _, worst = jax.lax.top_k(d2, k)
        slot = jnp.clip(jnp.cumsum(empty.astype(jnp.int32)) - 1, 0, k - 1)
        c2 = jnp.where(empty[:, None], x[worst[slot]].astype(jnp.float32), c2)
        # smoothed counts damp the penalty feedback loop (no oscillation)
        return c2, 0.5 * counts_s + 0.5 * cnts, jnp.sum(d2)

    c, _, _ = jax.lax.fori_loop(0, max_iter, body, (c0, counts0, jnp.float32(0)))
    # final assignment is capacity-constrained — a hard size bound, which the
    # soft penalty alone cannot give (winner-take-all between co-located
    # centroids); one more Lloyd update from the capped labels re-centers.
    labels, counts = capped_assign(x, c, cap)
    safe = jnp.maximum(labels, 0)
    assigned = (labels >= 0).astype(jnp.float32)
    sums = jax.ops.segment_sum(x.astype(jnp.float32) * assigned[:, None], safe, num_segments=k)
    cnts = jax.ops.segment_sum(assigned, safe, num_segments=k)
    c = _new_centroids(sums, cnts, c)
    # inertia measured against the RETURNED centroids and labels (a stale
    # training-loop value would mislead seed/penalty sweeps)
    if rows:
        real = jnp.sum(jnp.square(x.astype(jnp.float32) - c[safe]), axis=1)
    else:
        d2_final = sq_l2(x, c)
        real = jnp.take_along_axis(d2_final, safe[:, None], axis=1)[:, 0]
    inertia = jnp.sum(real * assigned)
    return c.astype(_centroid_dtype(x)), labels, counts, inertia


def _balanced_cap(p: KMeansParams, n: int) -> int:
    return int(-(-p.balanced_max_ratio * n // p.n_clusters))


def kmeans_balanced_fit_predict(x, params: Optional[KMeansParams] = None, *, res=None):
    """Returns ``(centroids, capped_labels, cluster_sizes, inertia)`` — the
    labels respect the hard bound ``balanced_max_ratio · n/k`` (what an IVF
    build consumes).  ``balanced_max_ratio`` must be ≥ 1: below that total
    capacity cannot hold the dataset and points would be dropped."""
    p = params or KMeansParams()
    x = wrap_array(x, ndim=2, name="x")
    expects(p.n_clusters <= x.shape[0], "n_clusters exceeds n_rows")
    expects(
        p.balanced_max_ratio >= 1.0,
        f"balanced_max_ratio={p.balanced_max_ratio} < 1 cannot hold all points",
    )
    expects(p.balanced_assign_precision in ("highest", "bf16"),
            f"balanced_assign_precision={p.balanced_assign_precision!r} (want highest|bf16)")
    key = jax.random.PRNGKey(p.seed)
    precision = (jax.lax.Precision.DEFAULT if p.balanced_assign_precision == "bf16"
                 else jax.lax.Precision.HIGHEST)
    return _balanced_fit_impl(
        x, key, p.n_clusters, p.max_iter, p.balanced_penalty,
        _balanced_cap(p, x.shape[0]), precision=precision
    )


def kmeans_balanced_fit(x, params: Optional[KMeansParams] = None, *, res=None):
    """Balanced fit → ``(centroids, cluster_sizes, inertia)``; see
    :func:`kmeans_balanced_fit_predict` for the size-bound contract."""
    c, _, counts, inertia = kmeans_balanced_fit_predict(x, params, res=res)
    return c, counts, inertia


def kmeans_balanced_predict(x, centroids, *, res=None) -> jax.Array:
    """Plain nearest-centroid labels (the cap only shapes the build)."""
    return kmeans_predict(x, centroids)
