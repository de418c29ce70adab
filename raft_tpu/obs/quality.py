"""Search-quality telemetry — shadow-sampled online recall (ISSUE 11).

PR 9 made the serving stack's *performance* observable; this module
makes its *quality* observable.  The serving runtime silently trades
recall for latency in three places — the admission ladder scales effort
down under load, the Pallas gate swaps scan kernels, and
compaction/swap rewrite indexes — and none of them used to measure what
they did to result quality.

:class:`RecallEstimator` closes the loop with the FusionANNS trick: the
cheap way to hold quality online is to re-rank a *small sampled subset*
exactly.  A deterministic, seeded hash over the request sequence number
picks ``sample_fraction`` of live requests on the hot path (one integer
multiply per request, no RNG state, replayable); sampled requests are
copied onto a **bounded work queue** (full queue = drop and count — the
oracle must never backpressure serving) and an off-hot-path worker
re-scores them against an **exact brute-force oracle** built from the
serving index's stored vectors via the shared
:mod:`raft_tpu.ops.blocked_scan` core.  Per-request recall@k streams
into registry metrics labeled by degradation level / scan kernel /
index generation, with Wilson confidence intervals per level — the
signal :mod:`raft_tpu.obs.slo`'s quality guard consumes.

The oracle is *ground truth for the stored representation*: IVF-Flat /
CAGRA / brute oracles scan the exact stored vectors; the IVF-PQ oracle
scans the reconstruction slab, so it measures candidate-selection loss
(probes/beam/kernel effects) rather than quantization loss — exactly
the part the degradation ladder and kernel dispatch can change.

Pure stdlib at import time (the jax/numpy oracle machinery loads
lazily), like the rest of :mod:`raft_tpu.obs`.
"""

from __future__ import annotations

import dataclasses
import math
import queue
import threading
from collections import deque
from typing import Dict, NamedTuple, Optional

__all__ = ["QualityConfig", "RecallEstimate", "RecallEstimator",
           "oracle_database", "wilson_interval"]

#: recall@k lives in [0, 1]; the ladder resolves the interesting top end
#: (0.9 / 0.95 / 0.99) where production floors sit.
RECALL_BOUNDARIES = (0.1, 0.25, 0.5, 0.7, 0.8, 0.9, 0.95, 0.99, 1.0)

_HASH_MULT = 0x9E3779B1        # Fibonacci hashing multiplier (Knuth)


def wilson_interval(successes: float, total: float,
                    z: float = 1.96) -> tuple:
    """Wilson score interval for a binomial proportion — the CI that
    stays honest at small n and extreme p (a plain normal interval
    collapses to a point at recall 1.0, claiming false certainty).
    Returns ``(low, high)``; ``(0, 1)`` when there is no data."""
    if total <= 0:
        return (0.0, 1.0)
    n = float(total)
    p = float(successes) / n
    zz = z * z
    denom = 1.0 + zz / n
    center = (p + zz / (2.0 * n)) / denom
    half = z * math.sqrt(p * (1.0 - p) / n + zz / (4.0 * n * n)) / denom
    return (max(0.0, center - half), min(1.0, center + half))


@dataclasses.dataclass(frozen=True)
class QualityConfig:
    """Knobs for :class:`RecallEstimator` (see
    ``docs/observability_guide.md`` for sizing guidance).

    ``sample_fraction``: fraction of requests shadow-sampled (the hash
    threshold — deterministic given ``seed`` and the request sequence);
    ``window``: per-degradation-level rolling window of sampled requests
    the CI is computed over (quality moves with load, so the estimate
    must age out); ``queue_max``: bound on the oracle work queue —
    overflow is dropped and counted, never blocks ``submit``;
    ``rows_cap``: sampled requests are truncated to this many query rows
    and padded to exactly this many, so ONE oracle executable serves
    every sample (zero steady-state recompiles); ``oracle_block``: rows
    per blocked-scan step of the oracle."""

    sample_fraction: float = 0.01
    seed: int = 0
    window: int = 256
    queue_max: int = 64
    rows_cap: int = 8
    oracle_block: int = 4096
    z: float = 1.96

    def __post_init__(self):
        from ..core.errors import expects

        expects(0.0 < self.sample_fraction <= 1.0,
                "sample_fraction must lie in (0, 1]")
        expects(self.window >= 1, "window must be >= 1")
        expects(self.queue_max >= 1, "queue_max must be >= 1")
        expects(self.rows_cap >= 1, "rows_cap must be >= 1")
        expects(self.oracle_block >= 1, "oracle_block must be >= 1")
        expects(self.z > 0, "z must be > 0")


class RecallEstimate(NamedTuple):
    """Windowed recall@k estimate for one degradation level."""

    mean: float        # sampled neighbor slots recovered / slots total
    ci_low: float      # Wilson interval over the window's slots
    ci_high: float
    samples: int       # sampled requests in the window
    slots: int         # neighbor slots (rows × k) in the window


class _Sample(NamedTuple):
    queries: object    # np [rows<=rows_cap, d] f32 copy
    ids: object        # np [rows, k] served neighbor ids
    level: int
    generation: int
    scan_kernel: str


def oracle_database(index):
    """Extract ``(vectors [n, d] f32, ids [n] int64)`` numpy arrays — the
    exact-scan corpus for ``index``'s oracle.

    * brute (2-D array) — the array itself, ids = row numbers;
    * ``ivf_flat`` — the list slabs, flattened, pad slots dropped;
    * ``ivf_pq`` — the bf16 reconstruction slab (materialized on demand),
      so the oracle is exact over the stored representation;
    * ``ivf_rabitq`` — the raw rerank slab (rerank returns exact
      distances, so the oracle corpus is the raw vectors);
    * ``ooc`` — the raw rows gathered from the host shard store (the
      device half holds only codes);
    * ``cagra`` — the dataset, ids = row numbers;
    * ``mutation.Tombstoned`` — the wrapped index's corpus with deleted
      source ids removed (a tombstoned id must never count as a miss
      against results that correctly exclude it);
    * ``refine.Refined`` — its dataset, ids = row numbers (the re-rank is
      exact over it), less the ids a ``Tombstoned`` index inside deletes.
    """
    import numpy as np

    import jax

    from ..neighbors.mutation import Tombstoned
    from ..neighbors.refine import Refined

    keep = None
    if isinstance(index, Refined):
        inner = index.index
        index = index.dataset
        if isinstance(inner, Tombstoned):
            index = Tombstoned(index, inner.keep)
    if isinstance(index, Tombstoned):
        keep = np.asarray(jax.device_get(index.keep.to_bool_array()))  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
        index = index.index
    if getattr(index, "ndim", None) == 2:              # brute database
        vecs = np.asarray(jax.device_get(index), dtype=np.float32)  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
        ids = np.arange(vecs.shape[0], dtype=np.int64)
    elif hasattr(index, "graph"):                      # cagra
        vecs = np.asarray(jax.device_get(index.dataset), dtype=np.float32)  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
        ids = np.arange(vecs.shape[0], dtype=np.int64)
    elif hasattr(index, "store"):                      # ooc
        # the raw rows live host-side: gather every live slot's row from
        # the shard store (shadow-sample scale — the oracle corpus is
        # bounded by the sampled index, not re-read per query)
        ids = np.asarray(jax.device_get(index.ids), dtype=np.int64).reshape(-1)  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
        vecs = np.asarray(index.store.gather(ids), dtype=np.float32)
    elif hasattr(index, "rotation"):                   # ivf_rabitq
        # rerank is exact over the raw slab, so the oracle corpus is the
        # raw vectors (not the 1-bit codes) — same shape as ivf_flat
        vecs = np.asarray(jax.device_get(index.data),  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
                          dtype=np.float32).reshape(-1, index.dim)
        ids = np.asarray(jax.device_get(index.ids), dtype=np.int64).reshape(-1)  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
    elif hasattr(index, "codes"):                      # ivf_pq
        idx = index.with_recon() if index.recon is None else index
        vecs = np.asarray(jax.device_get(idx.recon),  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
                          dtype=np.float32)[..., :idx.dim].reshape(-1, idx.dim)
        ids = np.asarray(jax.device_get(idx.ids), dtype=np.int64).reshape(-1)  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
    elif hasattr(index, "data"):                       # ivf_flat
        vecs = np.asarray(jax.device_get(index.data),  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
                          dtype=np.float32).reshape(-1, index.dim)
        ids = np.asarray(jax.device_get(index.ids), dtype=np.int64).reshape(-1)  # jaxlint: disable=JX01 one-time oracle corpus extraction, off the hot path
    else:
        raise TypeError(f"no oracle corpus for {type(index).__name__}")
    valid = ids >= 0
    vecs, ids = vecs[valid], ids[valid]
    if keep is not None:
        live = keep[np.clip(ids, 0, keep.shape[0] - 1)] & (ids < keep.shape[0])
        vecs, ids = vecs[live], ids[live]
    return vecs, ids


class RecallEstimator:
    """Shadow-sample live requests and measure recall@k against an exact
    oracle, off the hot path.

    Hot-path surface: :meth:`maybe_sample` — one hash per request;
    sampled requests are copied onto the bounded queue (overflow =
    drop + count).  Oracle surface: :meth:`drain` processes queued
    samples inline (the deterministic test mode); :meth:`start` runs the
    same drain on a daemon worker for real deployments.

    ``registry`` receives the streamed metrics (histogram
    ``raft_quality_recall{level,scan_kernel,generation}``, per-level
    mean/CI gauges, sample/drop counters); ``metrics`` (optional
    :class:`raft_tpu.serve.ServingMetrics`) additionally carries the
    ``quality_samples`` / ``quality_sample_drops`` counters into the
    serving JSON schema."""

    def __init__(self, index, k: int, config: Optional[QualityConfig] = None,
                 *, metric: Optional[str] = None, registry=None,
                 metrics=None, recorder=None) -> None:
        from ..core.errors import expects
        from .metrics import registry as default_registry
        from .spans import recorder as default_recorder

        self.config = config or QualityConfig()
        self.k = int(k)
        expects(self.k >= 1, "k must be >= 1")
        self.metric = metric if metric is not None \
            else getattr(index, "metric", "sqeuclidean")
        self.registry = registry if registry is not None \
            else default_registry()
        self.metrics = metrics
        self.recorder = recorder if recorder is not None \
            else default_recorder()
        self.drift = None          # optional obs.drift.DriftDetector
        self._index = index        # corpus extracted lazily, off hot path
        self._oracle = None        # (fn, device operands) once built
        from ..core import lockdep
        self._seq = 0  # guarded_by: _seq_lock
        self._seq_lock = lockdep.lock("RecallEstimator._seq_lock")
        self._queue: "queue.Queue[_Sample]" = queue.Queue(
            maxsize=self.config.queue_max)
        self._state_lock = lockdep.lock("RecallEstimator._state_lock")
        self._windows: Dict[int, deque] = {}   # guarded_by: _state_lock
        self.samples_total = 0       # guarded_by: _state_lock
        self.samples_below_floor = 0  # guarded_by: _state_lock
        self._floor: Optional[float] = None    # guarded_by: _state_lock
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # registry families (idempotent getters)
        self._hist = self.registry.histogram(
            "raft_quality_recall",
            "sampled online recall@k vs the exact oracle",
            RECALL_BOUNDARIES)
        self._g_mean = self.registry.gauge(
            "raft_quality_recall_mean", "windowed mean recall per level")
        self._g_lo = self.registry.gauge(
            "raft_quality_recall_ci_low",
            "Wilson CI lower bound of windowed recall per level")
        self._g_hi = self.registry.gauge(
            "raft_quality_recall_ci_high",
            "Wilson CI upper bound of windowed recall per level")
        self._g_n = self.registry.gauge(
            "raft_quality_recall_window",
            "sampled requests in the per-level window")
        self._c_sampled = self.registry.counter(
            "raft_quality_samples_total", "requests shadow-sampled")
        self._c_dropped = self.registry.counter(
            "raft_quality_sample_dropped_total",
            "samples dropped at the bounded oracle queue")
        self._c_errors = self.registry.counter(
            "raft_quality_oracle_errors_total",
            "oracle evaluations that raised (sample discarded)")

    # -- hot path -----------------------------------------------------------

    def _selected(self, seq: int) -> bool:
        """Deterministic seeded membership: Fibonacci-hash the sequence
        number into [0, 1) and threshold — replayable, no RNG state, and
        uniform enough that 1% means 1% at every window size."""
        h = ((seq ^ (self.config.seed * 0x85EBCA6B)) * _HASH_MULT) \
            & 0xFFFFFFFF
        return h < self.config.sample_fraction * 4294967296.0

    def maybe_sample(self, queries, ids, *, level: int, generation: int = 0,
                     scan_kernel: str = "xla") -> bool:
        """Hot-path hook: consider one answered request for shadow
        sampling.  ``queries`` [rows, d], ``ids`` [rows, k] (numpy, the
        reply the client saw).  Returns True when the request was
        enqueued for oracle scoring."""
        with self._seq_lock:
            seq = self._seq
            self._seq += 1
        if not self._selected(seq):
            return False
        import numpy as np

        cap = self.config.rows_cap
        sample = _Sample(np.array(queries[:cap], dtype=np.float32, copy=True),
                         np.array(ids[:cap], copy=True),
                         int(level), int(generation), str(scan_kernel))
        try:
            self._queue.put_nowait(sample)
        except queue.Full:
            # drop-and-count backpressure: the oracle must never block
            # or slow the serving path it is measuring
            self._c_dropped.inc()
            if self.metrics is not None:
                self.metrics.count("quality_sample_drops")
            return False
        self._c_sampled.inc(level=str(int(level)))
        if self.metrics is not None:
            self.metrics.count("quality_samples")
        return True

    # -- oracle -------------------------------------------------------------

    def _build_oracle(self):
        """Jit ONE fixed-shape executable over the corpus (queries padded
        to ``rows_cap``), routed through the shared blocked-scan core."""
        from functools import partial

        import numpy as np

        import jax
        import jax.numpy as jnp

        from ..ops import blocked_scan as bs

        vecs, ids = oracle_database(self._index)
        n, d = vecs.shape
        block = min(self.config.oracle_block, max(1, n))
        nb = -(-n // block)
        pad = nb * block - n
        vecs = np.pad(vecs, ((0, pad), (0, 0)))
        pids = np.pad(ids.astype(np.int32), (0, pad), constant_values=-1)
        norms = (vecs * vecs).sum(axis=1).astype(np.float32)
        blocks = jax.device_put(vecs.reshape(nb, block, d))
        bids = jax.device_put(pids.reshape(nb, block))
        bnorms = jax.device_put(norms.reshape(nb, block))
        metric = "inner_product" if self.metric == "inner_product" \
            else "sqeuclidean"

        @partial(jax.jit, static_argnames=("k",))
        def oracle(q, blocks, bids, bnorms, k):
            def score_step(inp):
                bvecs, bvids, bvnorms = inp
                dots = bs.exact_gathered_dots("cd,qd->qc", bvecs, q)
                dist = -dots if metric == "inner_product" \
                    else bvnorms[None, :] - 2.0 * dots
                dist = jnp.where(bvids[None, :] >= 0, dist, jnp.inf)
                return dist, jnp.broadcast_to(bvids[None, :], dist.shape)

            return bs.scan_topk(score_step, (blocks, bids, bnorms),
                                q.shape[0], k)

        self._oracle = (oracle, blocks, bids, bnorms)

    def oracle_ids(self, queries):
        """Exact top-k ids for ``queries`` (any row count ≤ ``rows_cap``;
        rows are padded to the cap so the jit runs one executable)."""
        import numpy as np

        import jax

        if self._oracle is None:
            self._build_oracle()
        fn, blocks, bids, bnorms = self._oracle
        q = np.asarray(queries, dtype=np.float32)
        rows = q.shape[0]
        cap = self.config.rows_cap
        if rows < cap:
            q = np.pad(q, ((0, cap - rows), (0, 0)))
        _, oids = fn(jax.device_put(q[:cap]), blocks, bids, bnorms,
                     k=self.k)
        return np.asarray(jax.device_get(oids))[:rows]  # jaxlint: disable=JX01 oracle worker result fetch, off the hot path

    # -- scoring ------------------------------------------------------------

    def _score(self, s: _Sample) -> None:
        import numpy as np

        oids = self.oracle_ids(s.queries)
        served = np.asarray(s.ids)[:, :self.k]
        hits = 0
        slots = 0
        for row in range(served.shape[0]):
            truth = set(int(v) for v in oids[row] if v >= 0)
            if not truth:
                continue
            got = sum(1 for v in served[row] if int(v) in truth)
            hits += got
            slots += len(truth)
        if slots == 0:
            return
        recall = hits / slots
        labels = dict(level=str(s.level), scan_kernel=s.scan_kernel,
                      generation=str(s.generation))
        self._hist.observe(recall, **labels)
        with self._state_lock:
            win = self._windows.get(s.level)
            if win is None:
                win = deque(maxlen=self.config.window)
                self._windows[s.level] = win
            win.append((hits, slots))
            self.samples_total += 1
            if self._floor is not None and recall < self._floor:
                self.samples_below_floor += 1
        est = self.estimate(s.level)
        lab = dict(level=str(s.level))
        self._g_mean.set(est.mean, **lab)
        self._g_lo.set(est.ci_low, **lab)
        self._g_hi.set(est.ci_high, **lab)
        self._g_n.set(est.samples, **lab)
        if self.drift is not None:
            self.drift.observe_queries(s.queries, generation=s.generation)

    # -- worker -------------------------------------------------------------

    def drain(self, max_items: Optional[int] = None) -> int:
        """Process queued samples inline; returns the number scored.
        The deterministic surface the drill tests drive (no thread)."""
        done = 0
        while max_items is None or done < max_items:
            try:
                s = self._queue.get_nowait()
            except queue.Empty:
                return done
            try:
                with self.recorder.span("obs.quality_oracle",
                                        level=s.level,
                                        generation=s.generation):
                    self._score(s)
            except Exception as exc:  # noqa: BLE001 — oracle must not kill the worker
                self._c_errors.inc()
                self.recorder.event("obs.quality_oracle_error",
                                    error=type(exc).__name__)
            done += 1
        return done

    def start(self) -> "RecallEstimator":
        """Run :meth:`drain` on a daemon worker (real deployments)."""
        from ..core.errors import expects

        expects(self._thread is None, "estimator already started")
        self._stop.clear()
        self._thread = threading.Thread(  # racelint: disable=JX14 the oracle worker owns its compiled exact-scan executable; it was built through the gated searcher path before start()
            target=self._loop, daemon=True, name="raft-tpu-quality")
        self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = 5.0) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout)
        self._thread = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                s = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            try:
                with self.recorder.span("obs.quality_oracle",
                                        level=s.level,
                                        generation=s.generation):
                    self._score(s)
            except Exception as exc:  # noqa: BLE001
                self._c_errors.inc()
                self.recorder.event("obs.quality_oracle_error",
                                    error=type(exc).__name__)

    def __enter__(self) -> "RecallEstimator":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- estimates ----------------------------------------------------------

    def track_floor(self, floor: float) -> None:
        """Record the recall floor (set by the SLO evaluator) so the
        cumulative below-floor counter the burn-rate windows consume is
        maintained at scoring time."""
        with self._state_lock:
            self._floor = float(floor)

    def estimate(self, level: int = 0) -> RecallEstimate:
        """Windowed recall estimate (+ Wilson CI over neighbor slots)
        for one degradation level; all-zero slots → the vacuous
        ``(0, [0, 1])`` estimate, which the guard treats as *unknown*."""
        with self._state_lock:
            win = list(self._windows.get(int(level), ()))
        hits = sum(h for h, _ in win)
        slots = sum(s for _, s in win)
        if slots == 0:
            return RecallEstimate(0.0, 0.0, 1.0, 0, 0)
        lo, hi = wilson_interval(hits, slots, self.config.z)
        return RecallEstimate(hits / slots, lo, hi, len(win), slots)

    def levels(self):
        """Degradation levels with at least one scored sample."""
        with self._state_lock:
            return sorted(self._windows)

    def stats(self) -> dict:
        """JSON-ready snapshot (per-level estimates + queue/counter
        state) for ``metrics_snapshot()['quality']``."""
        with self._state_lock:
            pending = self._queue.qsize()
        return {
            "sample_fraction": self.config.sample_fraction,
            "pending": pending,
            "samples_total": self.samples_total,
            "samples_below_floor": self.samples_below_floor,
            "levels": {
                str(lvl): dict(self.estimate(lvl)._asdict())
                for lvl in self.levels()
            },
        }
