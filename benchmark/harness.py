"""Run one cell once: set up, measure one window, check every answer.

Set-up (timed from process start to the window's first request) makes
the corpus on the device from the seed, builds the family's index
through the public API, starts ``serve.SearchServer`` and sends one
request for each bucket the traffic will use, which compiles its
program or loads it from JAX's persistent cache.  The window runs the
traffic mix for ``seconds``.  With ``trace`` on, the profiler records it
and the per-layer metrics are read instead of the end-to-end ones.

After the window the server and the index are dropped, the plain
reference (:mod:`.reference`) answers every query that was answered, and
the comparison decides ``correct``.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
from typing import List, Optional, Tuple

import numpy as np

from . import loadgen, mixture, reference, spec, xplane

SPAN_RING = 4096      # the program's default ring, whatever the environment


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_start() -> float:
    """This process's start, on the ``time.monotonic`` clock."""
    import psutil

    age = time.time() - psutil.Process().create_time()
    return time.monotonic() - age


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path in the checkout; every
    program goes in, so a run after the first compiles nothing."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Compiles:
    """Tracing, lowering and compiling that JAX reports, counted from its
    monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.count += 1
            self.seconds += duration


@dataclasses.dataclass
class Context:
    """What a metric reader sees of one run."""

    cell: spec.Cell
    window: loadgen.Window
    setup_s: float
    recall: Optional[float]
    peaks: dict
    trace: Optional[xplane.Reduction] = None
    work: List[Tuple[float, float]] = dataclasses.field(default_factory=list)


def warm_buckets(ladder, traffic: dict) -> List[int]:
    """The buckets the mix's batches can land in: from its request size
    up to what its clients can have queued together."""
    ladder = sorted(int(b) for b in ladder)
    rows = int(traffic["rows_per_request"])
    if rows > ladder[-1]:
        return ladder
    most = min(ladder[-1], rows * int(traffic["clients"]))
    lo = min(b for b in ladder if b >= rows)
    hi = min(b for b in ladder if b >= most)
    return [b for b in ladder if lo <= b <= hi]


def make_data(cell: spec.Cell, seed: int):
    """The corpus on the device and the query pool on the host."""
    import jax
    import jax.numpy as jnp

    t = time.monotonic()
    jax.block_until_ready(jnp.zeros(()))
    t_first = time.monotonic() - t
    t = time.monotonic()
    cfg = cell.config
    base, queries = mixture.make(cfg["data"], cfg["mixture"], seed)
    base = jax.block_until_ready(base)
    queries = np.asarray(queries)
    log(f"setup: first device op {t_first:.3f} s, data "
        f"{time.monotonic() - t:.3f} s ({base.shape[0]} x {base.shape[1]} "
        f"{base.dtype}, {len(queries)} queries)")
    return base, queries


def build_server(cell: spec.Cell, base, queries, log_fn=log):
    """Index, server and warm-up of one cell; returns the server, the
    index and the search parameters."""
    import jax

    from raft_tpu.obs.spans import SpanRecorder
    from raft_tpu.serve import SearchServer, ServerConfig

    cfg = cell.config
    t = time.monotonic()
    index, params = cell.module("families", cfg["family"]).build(base, cfg)
    jax.block_until_ready(index)
    t_build = time.monotonic() - t
    server_cfg = {k: tuple(v) if isinstance(v, list) else v
                  for k, v in cfg["server"].items()}
    srv = SearchServer(index, k=int(cfg["data"]["k"]), params=params,
                       config=ServerConfig(**server_cfg),
                       recorder=SpanRecorder(SPAN_RING))
    t = time.monotonic()
    srv.start(warmup=False)
    buckets = warm_buckets(srv.ladder, cell.traffic)
    for b in buckets:
        srv.search(queries[:b], deadline_ms=float(
            cell.traffic["deadline_ms"]))
    log_fn(f"setup: build {t_build:.3f} s, warm buckets {buckets} "
           f"{time.monotonic() - t:.3f} s")
    return srv, index, params


def run_window(cell: spec.Cell, srv, queries, seed: int, seconds: float,
               trace_dir: Optional[str]):
    import jax

    order = loadgen.pool_order(len(queries), seed)
    traffic = cell.traffic
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    if trace_dir is None:
        return loadgen.closed_loop(srv, queries, order, traffic, seconds)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            return loadgen.closed_loop(srv, queries, order, traffic, seconds)
    finally:
        jax.profiler.stop_trace()


def answers(window: loadgen.Window):
    """Pool rows, distances and ids of every answered request."""
    ok = [o for o in window.outcomes if o.error is None and o.done]
    if not ok:
        return np.zeros(0, np.int64), None, None
    return (np.concatenate([o.pool_idx for o in ok]),
            np.concatenate([o.dist for o in ok]),
            np.concatenate([o.ids for o in ok]))


def check(cell: spec.Cell, base, queries, window, pool_idx, dist, ids):
    """The numbers that decide ``correct``, each ``(value, limit)``, and
    recall@k against the plain reference."""
    k = int(cell.config["data"]["k"])
    limits = cell.config["limits"]
    unanswered = sum(1 for o in window.outcomes if not o.done)
    checks = {"unanswered": (unanswered, 0)}
    if dist is None:
        checks["no_answer"] = (1, 0)
        return checks, None
    uniq, inv = np.unique(pool_idx, return_inverse=True)
    _, ref_ids = reference.exact_knn(base, queries[uniq], k)
    rec = reference.recall(ids, ref_ids[inv])
    checks["recall_miss"] = (1.0 - rec, float(limits["recall_miss"]))
    checks["bad_rows"] = (reference.bad_rows(dist, ids, int(base.shape[0])),
                          0)
    checks["dist_gap_ulps"] = (
        reference.dist_gap_ulps(base, queries, pool_idx, dist, ids),
        float(limits["dist_gap_ulps"]))
    return checks, rec


def passed(checks: dict) -> bool:
    return all(value <= limit for value, limit in checks.values())


def read_metrics(ctx: Context, entries, kind: str) -> dict:
    out = {}
    for m in entries:
        value = ctx.cell.module(kind, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = spec.ROOT, require_chip: bool = True,
        cell: Optional[spec.Cell] = None) -> dict:
    """One run of one cell; returns the result line's object."""
    t_start = process_start()
    cell = cell or spec.load_cell(workload, root)
    enable_compile_cache(root)
    import jax

    device = device_info(int(cell.workload["chips"]), require_chip)
    peaks = spec.peaks(device["kind"], root) if require_chip else {}
    compiles = Compiles()
    cfg = cell.config
    base, queries = make_data(cell, seed)
    srv, index, params = build_server(cell, base, queries)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        n_before, s_before = compiles.count, compiles.seconds
        setup_s = time.monotonic() - t_start
        win = run_window(cell, srv, queries, seed, seconds, trace_dir)
        srv.stop()
        log(f"window: {compiles.count - n_before} compile events "
            f"({compiles.seconds - s_before:.3f} s) inside the window")
        mem_peak = memory_peak_bytes(int(cell.workload["chips"]))
        reduction = None
        if trace_dir is not None:
            path = xplane.find_trace(trace_dir)
            reduction = xplane.load(path) if path else None
        pool_idx, dist, ids = answers(win)
        work = []
        if trace:
            counter = cell.module("work", cfg["family"])
            state = counter.prepare(index, params, cfg, queries)
            work = [counter.request(state, o.pool_idx)
                    for o in win.outcomes if o.error is None and o.done]
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del srv, index, params
    gc.collect()

    t = time.monotonic()
    checks, rec = check(cell, base, queries, win, pool_idx, dist, ids)
    log(f"reference: {time.monotonic() - t:.3f} s over "
        f"{len(np.unique(pool_idx))} distinct queries, "
        f"{len(pool_idx)} answered rows")

    ctx = Context(cell, win, setup_s, rec, peaks, reduction, work)
    if trace:
        metrics = read_metrics(ctx, cell.per_layer, "layer_metrics")
    else:
        metrics = read_metrics(ctx, cell.end_to_end, "end_to_end")
    device = dict(device, memory_peak_bytes=mem_peak)
    result = {"correct": passed(checks),
              "attempted": len(win.outcomes),
              "failed": sum(1 for o in win.outcomes
                            if o.error is not None or not o.done),
              "metrics": metrics, "device": device}
    if trace and reduction is not None:
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduction.device_ops],
            "idle_gaps": [[n, s] for n, s in reduction.idle_gaps]}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, (v, lim) in checks.items()}
    for n, (v, lim) in checks.items():
        log(f"check {n}: {v} (limit {lim})")
    return result
