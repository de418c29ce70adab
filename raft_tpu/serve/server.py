"""SearchServer — the online serving front-end over any built index.

Composition (one instance each): a FIFO request queue guarded by a
condition variable, the :mod:`.batcher` plan, the :mod:`.cache` of
AOT bucket executables, the :mod:`.admission` controller, and
:mod:`.metrics`.  A single dispatch thread owns the accelerator —
requests enter via ``submit()`` from any number of client threads and
resolve through ``concurrent.futures.Future``.

Determinism hooks for tests: construct with a fake ``clock``, skip
``start()``, and drive dispatches synchronously with ``step()``.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from concurrent.futures import Future
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import lockdep, tracing
from ..core.errors import expects
from ..core.logging import default_logger
from ..obs import spans as obs_spans
from .admission import (AdmissionController, AdmissionPolicy,
                        DeadlineExceeded, QueueFull, RetryPolicy,
                        ServeError)
from .batcher import Request, SplitSink, plan_batch
from .bucketing import DEFAULT_LADDER, normalize_ladder, pad_rows, split_rows
from .cache import ExecutableCache
from .faults import TRANSIENT_FAULTS, FaultInjector, SwapFailed
from .metrics import ServingMetrics
from .registry import IndexRegistry
from .searchers import (family_of, index_dim, index_size, make_searcher,
                        query_dtype_of)

__all__ = ["ServerConfig", "SearchServer"]


def _host_pool_stats() -> dict:
    """Process staging-pool stats, exported to the global registry
    gauges on every snapshot (``core.host_memory
    .export_host_pool_metrics``)."""
    from ..core.host_memory import export_host_pool_metrics

    return export_host_pool_metrics()


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Serving knobs (see ``docs/serving_guide.md`` for sizing).

    ``ladder``: the shape buckets; ``max_wait_ms``: how long the batcher
    holds a non-full batch open for more arrivals; ``warm_levels``: how
    many degradation levels ``start()`` precompiles (level 0 is the
    bit-identical full-quality tier; deeper levels compile on first
    pressure unless warmed here); ``retry``: backoff schedule for
    transient dispatch faults (wedge/OOM — see :mod:`.faults`).
    """

    ladder: Tuple[int, ...] = DEFAULT_LADDER
    max_wait_ms: float = 2.0
    max_queue: int = 1024
    default_deadline_ms: float = 1000.0
    degrade_queue_fractions: Tuple[float, ...] = (0.5, 0.8)
    degrade_effort_scales: Tuple[float, ...] = (1.0, 0.5, 0.25)
    warm_levels: int = 1
    latency_window: int = 4096
    retry: RetryPolicy = RetryPolicy()

    def __post_init__(self):
        expects(len(self.degrade_effort_scales)
                == len(self.degrade_queue_fractions) + 1,
                "need one effort scale per degradation level (fractions"
                " define levels 1.., scales include level 0)")
        expects(self.degrade_effort_scales[0] == 1.0,
                "level 0 must be full quality (scale 1.0) — the serve"
                " bit-identity contract")
        expects(1 <= self.warm_levels <= len(self.degrade_effort_scales),
                "warm_levels out of range")
        expects(self.max_wait_ms >= 0, "max_wait_ms must be >= 0")


class SearchServer:
    """Micro-batching, deadline-aware serving wrapper around one index.

    ``index`` is any built index (IvfFlatIndex / IvfPqIndex / CagraIndex)
    or a raw (n, d) database array (brute force).  ``params`` is that
    family's SearchParams (``serve.searchers.BruteForceSearchParams`` for
    raw arrays).  Results are bit-identical to the family's direct
    ``search()`` at degradation level 0.

    ``clock`` (monotonic seconds) is injectable for deterministic tests;
    the dispatch thread's *waits* always use real time, so a fake clock
    only makes sense with manual ``step()`` driving.

    The index lives in a generation registry (:mod:`.registry`):
    :meth:`swap_index` installs a replacement with zero dropped requests
    and — for a same-shaped generation — zero recompiles (executable
    cache keys carry operand shapes, not the arrays).  ``faults`` is an
    optional :class:`.faults.FaultInjector` (default: armed from
    ``RAFT_SERVE_FAULTS`` if set, else inert); ``sleep`` injects the
    retry-backoff sleeper for deterministic fault tests.
    """

    def __init__(self, index, k: int = 10, params=None, *,
                 config: Optional[ServerConfig] = None,
                 clock=time.monotonic, seed: int = 0, res=None,
                 faults: Optional[FaultInjector] = None,
                 sleep=time.sleep, recorder=None) -> None:
        self._registry = IndexRegistry(index)
        self.family = family_of(index)
        expects(1 <= k <= index_size(index),
                f"k={k} out of range for index of {index_size(index)} rows")
        self.k = int(k)
        self.params = params
        self.config = config or ServerConfig()
        self.ladder = normalize_ladder(self.config.ladder)
        self.clock = clock
        self.seed = int(seed)
        self._dim = index_dim(index)
        self._qdtype = query_dtype_of(index)
        self.cache = ExecutableCache()
        self.metrics = ServingMetrics(self.config.latency_window)
        self.admission = AdmissionController(AdmissionPolicy(
            max_queue=self.config.max_queue,
            default_deadline_ms=self.config.default_deadline_ms,
            degrade_queue_fractions=self.config.degrade_queue_fractions))
        self.faults = faults if faults is not None \
            else FaultInjector.from_env(sleep=sleep)
        self._sleep = sleep
        # retry jitter draws from a seeded stream so fault tests replay
        # exactly; distinct replicas pass distinct seeds to decorrelate
        self._retry_rng = random.Random(self.seed ^ 0x9E3779B9)
        self.durable_store = None  # neighbors.wal.DurableStore, if adopted
        self.fence = None          # replication.EpochFence, if replicated
        self.replication = None    # LogShipper / StandbyReplica, if any
        # flight recorder: the process-wide ring unless the caller wires
        # its own (tests; multi-server hosts separating evidence)
        self.recorder = recorder if recorder is not None \
            else obs_spans.recorder()
        # _inflight is deliberately lock-free: a single tuple reference
        # swapped whole by the dispatch thread, read racily by observers
        self._inflight = None      # (site, t0) while a dispatch is on-device
        self._log = default_logger() if res is None else None
        self._cond = lockdep.condition("SearchServer._cond")
        self._parts_lock = lockdep.lock("SearchServer._parts_lock")
        self._searchers: dict = {}   # guarded_by: _parts_lock
        self._pending: list = []     # guarded_by: _cond
        self._thread: Optional[threading.Thread] = None
        self._running = False        # guarded_by: _cond
        # quality telemetry (opt-in via attach_quality); index-health
        # gauges are always on — recomputed for every swapped-in
        # generation so a bad compaction is visible in one scrape
        self.quality = None        # obs.quality.RecallEstimator
        self.slo = None            # obs.slo.SloEvaluator
        self._scan_kernel = str(
            getattr(self.params, "scan_kernel", None) or "xla")
        self._registry.on_swap = self._export_health
        self._export_health()

    @property
    def index(self):
        """The currently-serving generation's index (immutable snapshot —
        read it once per use; a concurrent swap replaces the reference,
        never the object)."""
        return self._registry.current.index

    # -- durability ---------------------------------------------------------

    def adopt_store(self, store) -> None:
        """Wire a ``neighbors.wal.DurableStore`` into this server: its
        accumulated counters (``wal_appends``/``wal_replayed``/
        ``quarantined_files``/``recoveries``/``snapshots``) transfer into
        the serving metrics, future store activity counts live, and the
        snapshot gains the WAL LSN watermark.  The store's index should
        be (or become, via :meth:`swap_index`) the serving generation."""
        self.durable_store = store
        for name, n in store.counters.items():
            self.metrics.count(name, n)
        store.metrics = self.metrics

    @classmethod
    def recover(cls, root, k: int = 10, params=None, *,
                store_config=None, **kw) -> "SearchServer":
        """Restore a crashed durable deployment and resume serving:
        ``DurableStore.recover(root)`` rebuilds the index (newest valid
        snapshot + WAL-tail replay, corrupt artifacts quarantined), the
        restored index becomes generation 0 of a fresh server, and the
        store is adopted (counters + watermark).  Remaining ``kw`` are
        :class:`SearchServer` constructor arguments; call ``start()`` (or
        drive ``step()``) on the result as usual."""
        from ..neighbors.wal import DurableStore

        store = DurableStore.recover(root, config=store_config,
                                     faults=kw.get("faults"))
        srv = cls(store.index, k, params, **kw)
        srv.adopt_store(store)
        return srv

    def attach_replication(self, role: str, transport=None, *,
                           config=None, node_id=None, root=None,
                           store_config=None, replica=None):
        """Wire WAL replication (:mod:`.replication`) onto this server.

        ``role="primary"`` hooks a :class:`.replication.LogShipper` onto
        the adopted :class:`~raft_tpu.neighbors.wal.DurableStore`: every
        committed mutation ships to the follower on ``transport``, acks
        flow back (``pump()`` manually or ``start()`` the background
        thread on the returned shipper), and the store + this server
        inherit the epoch fence — once deposed, appends and swaps raise
        :class:`.faults.FencedError`.

        ``role="standby"`` attaches a
        :class:`.replication.StandbyReplica` (pass ``root=`` for its
        durable directory, or a pre-built ``replica=``): applied records
        refresh the serving generation at the configured staleness
        bound, and ``replica.promote()`` fails this server over to
        primary.  Replication gauges/counters land on this server's
        metric registry, so ``prometheus_text()`` scrapes
        ``raft_replication_lag_{lsn,seconds}``,
        ``raft_replication_acks_total`` and ``raft_failovers_total``."""
        from .replication import LogShipper, StandbyReplica

        expects(role in ("primary", "standby"),
                f"role must be 'primary' or 'standby', got {role!r}")
        if role == "primary":
            expects(self.durable_store is not None,
                    "replicating a primary needs an adopted DurableStore "
                    "(SearchServer.recover or adopt_store first)")
            expects(transport is not None, "primary role needs a transport")
            shipper = LogShipper(self.durable_store, transport,
                                 config=config,
                                 node_id=node_id or "primary",
                                 registry=self.metrics.registry,
                                 faults=self.faults, clock=self.clock)
            self.fence = shipper.fence
            self.replication = shipper
            return shipper
        if replica is None:
            expects(transport is not None and root is not None,
                    "standby role needs transport= + root= "
                    "(or a pre-built replica=)")
            replica = StandbyReplica(root, transport, config=config,
                                     node_id=node_id or "standby",
                                     registry=self.metrics.registry,
                                     faults=self.faults, clock=self.clock,
                                     store_config=store_config)
        replica.attach_server(self)
        return replica

    @property
    def generation(self) -> int:
        return self._registry.gen_id

    # -- lifecycle ----------------------------------------------------------

    def warmup(self) -> int:
        """Precompile the bucket ladder (× ``warm_levels`` degradation
        tiers) for the default k and query dtype; returns the number of
        executables compiled.  Idempotent — the cache makes reruns free."""
        before = self.cache.compiles
        with tracing.range("serve.warmup(%s)", self.family):
            for level in range(self.config.warm_levels):
                for bucket in self.ladder:
                    self._compiled(bucket, self.k, self._qdtype, level)
        n = self.cache.compiles - before
        if self._log is not None and n:
            self._log.info(
                "serve warmup: %d executables (%s, ladder=%s, k=%d) in %.2fs",
                n, self.family, self.ladder, self.k, self.cache.compile_s)
        return n

    def start(self, warmup: bool = True) -> "SearchServer":
        """Warm the executable cache and start the dispatch thread."""
        expects(self._thread is None, "server already started")
        if warmup:
            self.warmup()
        with self._cond:
            self._running = True
        self._thread = threading.Thread(  # racelint: disable=JX14 dispatch thread owns its compiled executables (ExecutableCache built them under the pallas gate before serving)
            target=self._worker, name="raft-tpu-serve", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = 30.0) -> None:
        """Stop the dispatch thread; queued requests are drained first."""
        if self._thread is None:
            return
        with self._cond:
            self._running = False
            self._cond.notify_all()
        self._thread.join(timeout)
        self._thread = None

    def __enter__(self) -> "SearchServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client surface -----------------------------------------------------

    def submit(self, queries, k: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> Future:
        """Enqueue a search; returns a Future resolving to
        ``(distances, indices)`` numpy arrays of shape (rows, k).

        Raises :class:`QueueFull` when the bounded queue is at capacity
        (client backpressure); the Future raises
        :class:`DeadlineExceeded` when the deadline passes before
        dispatch.  Requests wider than the largest bucket are split and
        reassembled transparently."""
        q = np.asarray(queries)
        if q.ndim == 1:
            q = q[None, :]
        expects(q.ndim == 2, "queries must be 1-D or 2-D")
        expects(q.shape[0] >= 1, "empty query batch")
        expects(q.shape[1] == self._dim,
                f"query dim {q.shape[1]} != index dim {self._dim}")
        kk = self.k if k is None else int(k)
        expects(1 <= kk <= index_size(self.index),
                f"k={kk} out of range for index of "
                f"{index_size(self.index)} rows")
        now = self.clock()
        deadline = self.admission.deadline(now, deadline_ms)
        future: Future = Future()
        parts = split_rows(q.shape[0], self.ladder[-1])
        # the request's root span: opened here on the client thread,
        # finished by whichever thread resolves/rejects it — every later
        # lifecycle span (enqueue/batch-form/dispatch/device-exec/reply)
        # parents under it, forming one connected tree per request
        root = self.recorder.start("serve.request", rows=int(q.shape[0]),
                                   k=kk, parts=len(parts))
        t_enq = self.recorder.clock_ns() if root is not None else 0
        rejected_depth = None
        with self._cond:
            if not self.admission.admit(len(self._pending) + len(parts) - 1):
                rejected_depth = len(self._pending)
            else:
                if len(parts) == 1:
                    self._pending.append(Request(q, kk, deadline, now,
                                                 future=future, span=root))
                else:
                    sink = SplitSink(future, len(parts))
                    lo = 0
                    for i, rows in enumerate(parts):
                        self._pending.append(Request(q[lo:lo + rows], kk,
                                                     deadline, now, sink=sink,
                                                     part=i, span=root))
                        lo += rows
                self.metrics.count("submitted")
                self._cond.notify_all()
        if rejected_depth is not None:
            self.metrics.count("rejected_queue_full")
            self.recorder.finish(root, status="rejected_queue_full",
                                 queue_depth=rejected_depth)
            raise QueueFull(
                f"queue at capacity ({self.admission.policy.max_queue});"
                " retry with backoff or raise max_queue")
        if root is not None:
            self.recorder.record("serve.enqueue", t_enq,
                                 self.recorder.clock_ns(), parent=root,
                                 deadline_ms=round(1e3 * (deadline - now), 3))
        return future

    def search(self, queries, k: Optional[int] = None,
               deadline_ms: Optional[float] = None):
        """Synchronous convenience: ``submit()`` + wait.  Without a
        running dispatch thread this drives ``step()`` inline (the
        deterministic single-threaded mode the unit tests use)."""
        fut = self.submit(queries, k, deadline_ms)
        if self._thread is None:
            while not fut.done() and self.step():
                pass
        return fut.result(timeout=None if self._thread is None else
                          self.admission.policy.default_deadline_ms / 1e3
                          + 300.0)

    # -- dispatch -----------------------------------------------------------

    def step(self, now: Optional[float] = None) -> int:
        """Process one batch synchronously; returns the number of queue
        entries retired (0 = queue empty).  Expired entries are rejected
        before planning, so a step may retire requests without touching
        the accelerator."""
        if now is None:
            now = self.clock()
        with tracing.range("serve.dispatch(%s):form", self.family) as form:
            t_plan = self.recorder.clock_ns() if self.recorder.enabled \
                else 0
            with self._cond:
                expired = [r for r in self._pending if r.deadline < now]
                if expired:
                    self._pending = [r for r in self._pending
                                     if r.deadline >= now]
                if not self._pending:
                    batch = None
                else:
                    depth = len(self._pending)
                    batch, bucket = plan_batch(self._pending, self.ladder)
                    chosen = set(map(id, batch))
                    self._pending = [r for r in self._pending
                                     if id(r) not in chosen]
            for req in expired:
                self.metrics.count("rejected_deadline")
                self.recorder.finish(req.span, status="rejected_deadline")
                req.reject(DeadlineExceeded(
                    f"deadline passed {1e3 * (now - req.deadline):.1f}ms"
                    " before dispatch (queue wait exceeded the budget)"))
            if batch is None:
                return len(expired)
            if self.recorder.enabled:
                # post-hoc: planning ran under the queue lock; the span is
                # recorded after release, parented to the batch head's
                # request
                self.recorder.record("serve.batch_form", t_plan,
                                     self.recorder.clock_ns(),
                                     parent=batch[0].span,
                                     n_requests=len(batch),
                                     queue_depth=depth)
            level = self.admission.guarded_level(
                depth, self._apply_quality_guard,
                max_level=len(self.config.degrade_effort_scales) - 1)
        self._execute(batch, bucket, level, form)
        return len(expired) + len(batch)

    def _apply_quality_guard(self, level: int) -> int:
        """Ask the SLO evaluator's recall guard before entering a ladder
        level; a refusal (guard picks a shallower level) is counted and
        recorded — the scrapeable trace of quality overriding load."""
        if self.slo is None:
            return level
        allowed = self.slo.quality_guard(level)
        if allowed != level:
            self.slo.overrides += 1
            self.metrics.count("quality_guard_overrides")
            self.recorder.event("serve.quality_guard",
                                requested=int(level), allowed=int(allowed))
        return allowed

    def _parts(self, k: int, level: int, gen=None):
        """(fn, operands) for one (generation, k, level) — memoized so the
        steady-state dispatch path never re-runs ``make_searcher`` (which
        would rebuild keep-mask/LUT operands per batch).  Older
        generations' entries are purged on first use of a newer one; any
        in-flight dispatch holds its own operand references, so the old
        arrays live exactly as long as requests that captured them."""
        gen = self._registry.current if gen is None else gen
        key = (gen.gen_id, int(k), int(level))
        with self._parts_lock:
            hit = self._searchers.get(key)
            if hit is not None:
                return hit
        scale = self.config.degrade_effort_scales[level]
        fn, operands = self._make_parts(gen.index, k, scale)
        with self._parts_lock:
            current = self._registry.gen_id
            for old in [kk for kk in self._searchers if kk[0] < current]:
                del self._searchers[old]
            self._searchers.setdefault(key, (fn, operands))
            return self._searchers[key]

    def _make_parts(self, index, k: int, scale: float):
        """Searcher-factory seam: build the ``(fn, operands)`` pair for
        one effort scale.  The fleet tier's per-replica servers override
        this with :func:`raft_tpu.serve.fleet.make_fleet_searcher` (the
        mesh-sharded fan-out) — everything else about dispatch (buckets,
        cache, admission, degradation) is topology-agnostic."""
        return make_searcher(index, k, self.params, effort_scale=scale,
                             seed=self.seed)

    def _stage_queries(self, qpad):
        """Host→device transfer seam for the padded query batch; fleet
        servers override to place the batch replicated over their mesh
        (an AOT executable's input sharding is part of its signature)."""
        return jax.device_put(qpad)

    def _query_spec(self, bucket: int, dtype):
        """The AOT lowering spec for one query bucket; fleet servers
        attach the replicated mesh sharding here so the compiled
        executable and :meth:`_stage_queries` agree."""
        return jax.ShapeDtypeStruct((bucket, self._dim), dtype)

    def queue_depth(self) -> int:
        """Requests waiting in the queue (lock-guarded read) — the
        router's load signal."""
        with self._cond:
            return len(self._pending)

    @staticmethod
    def _operand_scope(operands):
        """Shapes + dtypes of the searcher operands — the generation-
        INVARIANT part of an executable's identity.  Cache keys carry
        this instead of the arrays, so a swapped-in generation with the
        same slab shapes reuses every compiled program."""
        return tuple((tuple(a.shape), str(a.dtype)) for a in operands)

    def _compiled(self, bucket: int, k: int, dtype, level: int, gen=None):
        fn, operands = self._parts(k, level, gen)
        key = (self.family, int(bucket), int(k), str(jnp.dtype(dtype)),
               int(level), self._operand_scope(operands))

        def build():
            return fn, operands, self._query_spec(bucket, dtype)

        return self.cache.get(key, build), operands

    def _execute(self, batch, bucket: int, level: int, form) -> None:
        """Run one planned batch: the host phases ``stage`` (pad and put
        the queries), ``launch`` (look up and call the executable),
        ``fetch`` (wait for and copy back the answers) and ``reply`` are
        sibling ``tracing`` ranges named ``serve.dispatch(<family>,b=,k=,
        lvl=):<phase>``, each carrying ``dispatch=`` the id of this
        batch's ``serve.dispatch`` span (``form``, the ``step()`` range
        that planned the batch, gets it too)."""
        rows = sum(r.rows for r in batch)
        k = batch[0].k
        retry = self.config.retry
        backoffs = retry.start(self._retry_rng)
        attempt = 0
        # dispatch span: parented to the batch head's request (the other
        # requests are linked through `request_spans`); the in-flight
        # marker is what the stall watchdog polls — it stays set through
        # retries, so a wedge that burns backoff still reads as ONE stall
        dispatch = self.recorder.start(
            "serve.dispatch", parent=batch[0].span, bucket=int(bucket),
            level=int(level), n_requests=len(batch),
            request_spans=[r.span.span_id for r in batch
                           if r.span is not None])
        phase = {"dispatch": dispatch.span_id} if dispatch is not None \
            else {}
        if form is not None:
            form.attrs.update(phase)
        name = "serve.dispatch(%s,b=%d,k=%d,lvl=%d)" % (
            self.family, bucket, k, level)
        self._inflight = ("execute", self.clock())
        try:
            while True:
                try:
                    # explicit transfers at the serving boundary:
                    # device_put / device_get pass
                    # ``jax.transfer_guard("disallow")``, so a
                    # TraceGuard-wrapped serve loop proves these are the
                    # ONLY host<->device crossings on the path
                    with tracing.range(name + ":stage", **phase):
                        qpad = pad_rows(
                            np.concatenate([r.queries for r in batch], axis=0)
                            if len(batch) > 1 else batch[0].queries, bucket)
                        q = self._stage_queries(qpad)
                    with tracing.range(name + ":launch", **phase):
                        self.faults.fire("execute")
                        compiled, operands = self._compiled(
                            bucket, k, qpad.dtype, level)
                        d, i = compiled(q, *operands)
                    with tracing.range(name + ":fetch", **phase):
                        # host fetch = completion barrier
                        d, i = jax.device_get((d, i))
                        d = np.asarray(d)
                        i = np.asarray(i)
                    break
                except TRANSIENT_FAULTS as exc:
                    attempt += 1
                    backoff = backoffs.next_s()
                    earliest = min(r.deadline for r in batch)
                    if attempt > retry.max_retries:
                        self.metrics.count("faulted_batches")
                        self.recorder.finish(dispatch, status="faulted",
                                             error=type(exc).__name__)
                        for req in batch:
                            self.recorder.finish(req.span, status="faulted")
                            req.reject(exc)
                        return
                    if self.clock() + backoff > earliest:
                        # deadline-aware retry budget: don't burn backoff on
                        # answers nobody will be waiting for
                        self.metrics.count("faulted_batches")
                        err = DeadlineExceeded(
                            f"transient fault ({exc!r}) and the next "
                            f"{1e3 * backoff:.1f}ms backoff outlives the "
                            "batch deadline")
                        self.recorder.finish(dispatch, status="faulted",
                                             error=type(exc).__name__)
                        for req in batch:
                            self.recorder.finish(req.span, status="faulted")
                            req.reject(err)
                        return
                    self.metrics.count("retries")
                    self.recorder.event("serve.retry", parent=dispatch,
                                        attempt=attempt,
                                        backoff_ms=round(1e3 * backoff, 3),
                                        error=type(exc).__name__)
                    self._sleep(backoff)
                except Exception as exc:  # noqa: BLE001 — fail the batch, not the server
                    self.recorder.finish(dispatch, status="error",
                                         error=type(exc).__name__)
                    for req in batch:
                        self.recorder.finish(req.span, status="error")
                        req.reject(ServeError(f"dispatch failed: {exc!r}"))
                    raise
        finally:
            self._inflight = None
        self.recorder.finish(dispatch, status="ok", attempts=attempt + 1)
        done = self.clock()
        with tracing.range(name + ":reply", **phase):
            self.metrics.observe_batch(bucket, rows, level)
            lo = 0
            for req in batch:
                hi = lo + req.rows
                reply_ns = self.recorder.clock_ns() \
                    if self.recorder.enabled else 0
                req.resolve(d[lo:hi], i[lo:hi])
                if self.quality is not None:
                    # shadow-sampling hook: one hash per request; selected
                    # requests copy onto the bounded oracle queue (overflow
                    # drops) — the reply above is already on its way
                    self.quality.maybe_sample(
                        req.queries, i[lo:hi], level=level,
                        generation=self._registry.gen_id,
                        scan_kernel=self._scan_kernel)
                if req.span is not None:
                    self.recorder.record("serve.reply", reply_ns,
                                         self.recorder.clock_ns(),
                                         parent=req.span, part=req.part)
                    self.recorder.finish(req.span, status="ok")
                self.metrics.observe_latency(1e3 * (done - req.t_submit),
                                             late=done > req.deadline)
                lo = hi

    # -- generation handoff -------------------------------------------------

    def swap_index(self, new_index=None, *, build=None):
        """Install a new index generation with zero dropped requests.

        Pass either a built ``new_index`` or a zero-arg ``build``
        callable (run here, with transient-fault retry — the
        OOM-on-extend recovery path).  The new generation is validated
        (family / dim / query dtype / size ≥ k) and its level-0 ladder
        pre-warmed **before** the atomic registry swap, so traffic never
        waits on a compile; a same-shaped generation reuses every cached
        executable (zero recompiles).  Any failure raises
        :class:`.faults.SwapFailed` and leaves the old generation
        serving.  In-flight batches that captured old-generation operands
        complete against them — the swap never interrupts a dispatch."""
        expects((new_index is None) != (build is None),
                "pass exactly one of new_index= or build=")
        if self.fence is not None:  # a deposed primary must not swap
            self.fence.check("swap", count=self.metrics.count)
        old = self._registry.current
        retry = self.config.retry
        try:
            if build is not None:
                attempt = 0
                backoffs = retry.start(self._retry_rng)
                while True:
                    try:
                        self.faults.fire("extend")
                        new_index = build()
                        break
                    except TRANSIENT_FAULTS:
                        attempt += 1
                        if attempt > retry.max_retries:
                            raise
                        self.metrics.count("retries")
                        self._sleep(backoffs.next_s())
            self.faults.fire("swap")
            expects(family_of(new_index) == self.family,
                    f"swap changes index family ({self.family} -> "
                    f"{family_of(new_index)})")
            expects(index_dim(new_index) == self._dim,
                    f"swap changes vector dim ({self._dim} -> "
                    f"{index_dim(new_index)})")
            expects(str(jnp.dtype(query_dtype_of(new_index)))
                    == str(jnp.dtype(self._qdtype)),
                    "swap changes the query dtype")
            expects(self.k <= index_size(new_index),
                    f"new generation has {index_size(new_index)} rows < "
                    f"k={self.k}")
            # pre-warm the prospective generation OUTSIDE the registry —
            # its compiles (zero, when shapes match) happen while the old
            # generation keeps serving
            prospective = type(old)(new_index, old.gen_id + 1)
            for level in range(self.config.warm_levels):
                for bucket in self.ladder:
                    self._compiled(bucket, self.k, self._qdtype, level,
                                   gen=prospective)
        except Exception as exc:
            self.metrics.count("failed_swaps")
            raise SwapFailed(
                f"swap aborted, generation {old.gen_id} still serving: "
                f"{exc}") from exc
        gen = self._registry.swap(new_index)
        with self._parts_lock:
            # re-key the pre-warmed parts under the REAL gen_id (a racing
            # swap may have bumped it past the prospective one)
            for (g, k, lvl) in list(self._searchers):
                if g == prospective.gen_id and g != gen.gen_id:
                    self._searchers[(gen.gen_id, k, lvl)] = \
                        self._searchers.pop((g, k, lvl))
        self.metrics.count("swaps")
        if self._log is not None:
            self._log.info("serve swap: generation %d -> %d (%s, %d rows)",
                           old.gen_id, gen.gen_id, self.family,
                           index_size(new_index))
        return gen

    def _worker(self) -> None:
        max_rows = self.ladder[-1]
        wait_s = self.config.max_wait_ms / 1e3
        while True:
            with self._cond:
                full = sum(r.rows for r in self._pending) >= max_rows
                if self._running and (not self._pending
                                      or (wait_s > 0 and not full)):
                    with tracing.range("serve.dispatch(%s):wait",
                                       self.family):
                        self._wait_for_batch(max_rows, wait_s)
                if not self._running and not self._pending:
                    return
            while self.step():
                pass

    def _wait_for_batch(self, max_rows: int, wait_s: float) -> None:  # racelint: holds _cond
        """Hold the queue lock's condition until something is queued,
        then for the batching window: until the largest bucket fills or
        the window elapses (real time — see the clock note in the class
        docstring)."""
        while self._running and not self._pending:
            self._cond.wait(0.05)
        t0 = time.monotonic()
        while (self._running
               and sum(r.rows for r in self._pending) < max_rows):
            rem = t0 + wait_s - time.monotonic()
            if rem <= 0:
                break
            self._cond.wait(rem)

    # -- observability ------------------------------------------------------

    def dispatch_inflight(self):
        """``(site, t0)`` while a dispatch is executing on-device (server
        clock seconds), else ``None`` — the marker
        :class:`raft_tpu.obs.StallWatchdog` polls for the wedge failure
        mode.  Reads are lock-free: a Python tuple swap is atomic."""
        return self._inflight

    def _export_health(self, gen=None) -> dict:
        """Compute + export :func:`raft_tpu.neighbors.health.index_health`
        gauges for one generation (the ``IndexRegistry.on_swap`` hook;
        also runs at construction for generation 0).  Health telemetry
        must never take down serving, so failures degrade to an empty
        dict instead of raising out of a swap."""
        from ..neighbors.health import export_index_health

        gen = self._registry.current if gen is None else gen
        try:
            return export_index_health(self.metrics.registry, gen.index,
                                       generation=gen.gen_id)
        except Exception as exc:  # noqa: BLE001 — telemetry, not control
            self.recorder.event("serve.health_export_error",
                                generation=gen.gen_id,
                                error=type(exc).__name__)
            return {}

    def attach_quality(self, config=None, *, policy=None,
                       baseline_queries=None):
        """Wire the search-quality telemetry loop onto this server:
        a :class:`raft_tpu.obs.quality.RecallEstimator` shadow-sampling
        live requests (``config``: its ``QualityConfig``), an
        :class:`raft_tpu.obs.slo.SloEvaluator` over latency /
        availability / recall (``policy``: its ``SloPolicy``) whose
        recall guard the degradation ladder now consults, and — when
        ``baseline_queries`` is given — a
        :class:`raft_tpu.obs.drift.DriftDetector` fed from the sampled
        queries.  All metrics land in this server's registry, so
        :meth:`prometheus_text` carries them.

        Returns the estimator.  Call ``.start()`` on it for a background
        oracle worker, or drive ``.drain()`` inline in deterministic
        tests.  Attach before ``start()``; re-attaching replaces the
        previous wiring."""
        from ..obs.quality import RecallEstimator
        from ..obs.slo import SloEvaluator

        self.quality = RecallEstimator(
            self.index, self.k, config, registry=self.metrics.registry,
            metrics=self.metrics, recorder=self.recorder)
        if baseline_queries is not None:
            from ..obs.drift import DriftDetector

            self.quality.drift = DriftDetector.from_index(
                self.index, baseline_queries,
                registry=self.metrics.registry)
        self.slo = SloEvaluator(self.metrics, self.quality, policy,
                                recorder=self.recorder)
        return self.quality

    def attach_watchdog(self, quarantine_dir, **kw):
        """Construct (NOT start) a :class:`raft_tpu.obs.StallWatchdog`
        over this server's dispatch marker, flight recorder and metrics;
        kwargs forward (``stall_timeout_s``, ``poll_interval_s``,
        ``capture_s``...).  Call ``.start()`` on the result, or drive
        ``.check()`` inline in deterministic tests."""
        from ..obs.watchdog import StallWatchdog

        kw.setdefault("recorder", self.recorder)
        return StallWatchdog(self, quarantine_dir, **kw)

    def prometheus_text(self) -> str:
        """Prometheus text exposition for a scrape handler: the serving
        counters/histogram plus live gauges (queue depth/rows, degrade
        level, executable-cache and flight-recorder occupancy) and the
        process-global registry (Pallas gate fallbacks etc.)."""
        with self._cond:
            depth = len(self._pending)
            qrows = sum(r.rows for r in self._pending)
        reg = self.metrics.registry
        reg.gauge("raft_serve_queue_depth",
                  "requests waiting in the queue").set(depth)
        reg.gauge("raft_serve_queue_rows",
                  "query rows waiting in the queue").set(qrows)
        reg.gauge("raft_serve_degrade_level",
                  "current admission degradation level").set(
                      self.admission.level(depth))
        reg.gauge("raft_serve_generation",
                  "serving index generation").set(self._registry.gen_id)
        reg.gauge("raft_serve_index_rows",
                  "rows in the serving generation").set(
                      index_size(self.index))
        cache = self.cache.snapshot()
        reg.gauge("raft_serve_cache_hits", "executable cache hits").set(
            cache.get("hits", 0))
        reg.gauge("raft_serve_cache_compiles",
                  "executable cache compiles").set(cache.get("compiles", 0))
        rec = self.recorder.stats()
        reg.gauge("raft_obs_flight_recorder_spans",
                  "spans retained in the flight recorder").set(
                      rec["retained"])
        return self.metrics.prometheus_text()

    def metrics_snapshot(self) -> dict:
        """Serving metrics + live gauges + compile-cache counters (the
        ``docs/serving_guide.md`` schema).  ``host_pool`` surfaces the
        process staging-pool occupancy/hit-rate (the out-of-core tier's
        zero-alloc contract) and refreshes the
        ``raft_host_pool_{idle_bytes,hits,misses}`` gauges."""
        with self._cond:
            depth = len(self._pending)
            qrows = sum(r.rows for r in self._pending)
        snap = self.metrics.snapshot()
        snap.update({
            "queue_depth": depth,
            "queue_rows": qrows,
            "degrade_level": self.admission.level(depth),
            "cache": self.cache.snapshot(),
            "obs": self.recorder.stats(),
            "quality": (self.quality.stats()
                        if self.quality is not None else None),
            "slo": self.slo.stats() if self.slo is not None else None,
            "host_pool": _host_pool_stats(),
            "server": {"family": self.family, "k": self.k,
                       "ladder": list(self.ladder),
                       "index_rows": index_size(self.index),
                       "generation": self._registry.gen_id,
                       "wal_lsn": (self.durable_store.wal_lsn
                                   if self.durable_store is not None
                                   else None)},
        })
        return snap

    def dump_metrics(self, path=None) -> str:
        """JSON-serialize :meth:`metrics_snapshot` (optionally to a
        file) — the bench harness's ingestion format.  File writes use
        the ``core/serialize`` temp + fsync + atomic-rename discipline:
        a crash mid-dump leaves the previous complete file, never a torn
        one."""
        import json

        text = json.dumps(self.metrics_snapshot(), indent=2, sort_keys=True)
        if path:
            from ..core.serialize import write_text_atomic

            write_text_atomic(path, text + "\n")
        return text
