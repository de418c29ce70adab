"""Query-distribution drift — a streaming PSI sketch vs a build baseline.

An ANN index is tuned to the query distribution it was built and
calibrated against: IVF probe counts assume queries land near the same
centroids the corpus clustered into, CAGRA's router seeds assume the
same regions stay hot.  When the *live* query distribution walks away
from the build-time baseline, recall degrades even though nothing in
the serving stack changed — the drift is invisible to latency metrics
and only shows up in the online recall estimate after the damage.

:class:`DriftDetector` makes drift a first-class metric.  The sketch is
the classic monitoring one: a scalar *summary statistic* per query —
its squared distance to the nearest index reference point (IVF / CAGRA
centroids; a row subsample for brute databases) — histogrammed into
quantile buckets fitted on the **baseline** distribution, then compared
against the live window with the Population Stability Index

    PSI = Σ_i (q_i − p_i) · ln(q_i / p_i)

(p = baseline fraction, q = live fraction per bucket; ε-smoothed).  PSI
is symmetric-KL-flavored, zero iff the distributions match, and has
industry-standard alert thresholds: < 0.1 stable, 0.1–0.25 moderate
shift, ≥ 0.25 shifted.  Observations are fed from the quality
estimator's shadow-sample worker, so the sketch costs nothing on the
hot path and sees exactly the sampled traffic.

Pure stdlib + numpy at call time; jax only to pull reference points out
of device-resident indexes.
"""

from __future__ import annotations

from collections import deque

__all__ = ["DriftDetector", "PSI_MODERATE", "PSI_SHIFTED",
           "centroid_distances", "reference_points"]

PSI_MODERATE = 0.1
PSI_SHIFTED = 0.25
_EPS = 1e-4


def reference_points(index, m: int = 256, seed: int = 0):
    """Reference points the drift statistic measures distance to:
    coarse centroids for the IVF families, router centroids for CAGRA,
    a seeded ``m``-row subsample for a brute database.  Returns a numpy
    ``[r, d]`` f32 array."""
    import numpy as np

    import jax

    from ..neighbors.mutation import Tombstoned
    from ..neighbors.refine import Refined

    if isinstance(index, Refined):
        index = index.index
    if isinstance(index, Tombstoned):
        index = index.index
    if hasattr(index, "centroids"):                    # ivf_flat / ivf_pq
        pts = index.centroids
    elif hasattr(index, "graph"):                      # cagra
        pts = index.router_centroids
    elif getattr(index, "ndim", None) == 2:            # brute database
        arr = np.asarray(jax.device_get(index), dtype=np.float32)  # jaxlint: disable=JX01 one-time baseline extraction, never on the search path
        rows = np.random.default_rng(seed).choice(
            arr.shape[0], size=min(m, arr.shape[0]), replace=False)
        return arr[np.sort(rows)]
    else:
        raise TypeError(f"no reference points for {type(index).__name__}")
    return np.asarray(jax.device_get(pts), dtype=np.float32)  # jaxlint: disable=JX01 one-time baseline extraction, never on the search path


def centroid_distances(points, queries):
    """Squared L2 distance from each query to its nearest reference
    point — the per-query drift statistic.  Plain numpy (runs on the
    oracle worker, not under jit)."""
    import numpy as np

    q = np.asarray(queries, dtype=np.float32)
    p = np.asarray(points, dtype=np.float32)
    d2 = ((q * q).sum(axis=1)[:, None] - 2.0 * (q @ p.T)
          + (p * p).sum(axis=1)[None, :])
    return np.maximum(d2.min(axis=1), 0.0)


class DriftDetector:
    """Streaming PSI of a scalar statistic vs its baseline distribution.

    ``baseline_values`` (1-D) fits the bucket boundaries (baseline
    quantiles, so every baseline bucket holds equal mass — the PSI
    binning with maximum sensitivity) and the baseline fractions; live
    values stream through :meth:`observe` into a bounded window.
    Attach ``points`` (or build via :meth:`from_index`) to enable
    :meth:`observe_queries`, the hook the quality estimator's worker
    calls with each shadow-sampled query batch.

    Sampling bias: even with NO drift, a finite live window reads
    E[PSI] ≈ (buckets − 1) / window — keep the window an order of
    magnitude above the bucket count (the defaults are 8 buckets /
    1024 window → bias ≈ 0.007, far under the 0.1 alert line)."""

    def __init__(self, baseline_values, *, n_buckets: int = 8,
                 window: int = 1024, points=None, registry=None) -> None:
        import numpy as np

        from ..core.errors import expects
        from .metrics import registry as default_registry

        base = np.asarray(baseline_values, dtype=np.float32).reshape(-1)
        expects(base.size >= 2, "drift baseline needs >= 2 values")
        expects(n_buckets >= 2, "n_buckets must be >= 2")
        expects(window >= 1, "window must be >= 1")
        # interior quantile cuts; dedup because a spiky baseline can
        # repeat a quantile, and boundaries must increase strictly
        qs = np.linspace(0.0, 1.0, n_buckets + 1)[1:-1]
        cuts = np.unique(np.quantile(base, qs))
        self.boundaries = tuple(float(c) for c in cuts)
        counts = np.histogram(base, bins=self._edges())[0]
        self._baseline_frac = counts / counts.sum()
        self.window = int(window)
        self._live: deque = deque(maxlen=self.window)
        self.points = points
        self.registry = registry if registry is not None \
            else default_registry()
        self._g_psi = self.registry.gauge(
            "raft_quality_drift_psi",
            "PSI of live query-to-centroid distances vs build baseline")
        self._g_n = self.registry.gauge(
            "raft_quality_drift_window", "live observations in the window")
        self._g_psi.set(0.0)
        self._g_n.set(0)

    @classmethod
    def from_index(cls, index, baseline_queries, *, m: int = 256,
                   seed: int = 0, **kw) -> "DriftDetector":
        """Fit a detector for ``index``: reference points from the index,
        baseline distances from a representative query sample (e.g. the
        tuning/calibration query set)."""
        pts = reference_points(index, m=m, seed=seed)
        return cls(centroid_distances(pts, baseline_queries),
                   points=pts, **kw)

    def _edges(self):
        import numpy as np

        return np.concatenate(([-np.inf], self.boundaries, [np.inf]))

    # -- streaming ----------------------------------------------------------

    def observe(self, values) -> None:
        """Fold scalar statistic values into the live window and refresh
        the exported PSI gauge."""
        import numpy as np

        for v in np.asarray(values, dtype=np.float32).reshape(-1):
            self._live.append(float(v))
        self._g_psi.set(self.psi())
        self._g_n.set(len(self._live))

    def observe_queries(self, queries, *, generation: int = 0) -> None:
        """Fold a raw query batch (distance-to-nearest-reference computed
        here) — the quality-worker hook.  Requires ``points``."""
        from ..core.errors import expects

        expects(self.points is not None,
                "observe_queries needs reference points — build with "
                "from_index() or pass points=")
        del generation  # one live window; labels would split the sketch
        self.observe(centroid_distances(self.points, queries))

    # -- scoring ------------------------------------------------------------

    def psi(self) -> float:
        """Population Stability Index of the live window vs the baseline
        (0.0 while the window is empty)."""
        import numpy as np

        if not self._live:
            return 0.0
        live = np.histogram(np.asarray(self._live), bins=self._edges())[0]
        q = (live + _EPS) / (live.sum() + _EPS * live.size)
        p = (self._baseline_frac * 1.0 + _EPS) \
            / (1.0 + _EPS * live.size)
        return float(((q - p) * np.log(q / p)).sum())

    def status(self) -> str:
        """``stable`` / ``moderate`` / ``shifted`` per the standard PSI
        thresholds (0.1 / 0.25)."""
        v = self.psi()
        if v >= PSI_SHIFTED:
            return "shifted"
        if v >= PSI_MODERATE:
            return "moderate"
        return "stable"

    def stats(self) -> dict:
        return {"psi": self.psi(), "status": self.status(),
                "window": len(self._live), "buckets": len(self.boundaries) + 1}
