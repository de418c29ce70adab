"""Stall-triggered flight-recorder + profiler capture.

A recurring production failure mode is a *wedged*
device dispatch: the call into the accelerator neither returns nor
raises, the serve queue backs up, and — before this module — the only
evidence was a bench timeout hours later.  :class:`StallWatchdog`
watches the server's in-flight dispatch marker
(``SearchServer.dispatch_inflight()``) from its own daemon thread; when
one dispatch has been in flight longer than ``stall_timeout_s`` it

1. counts a ``stalls`` metric (``raft_serve_stalls_total`` on the
   Prometheus surface) — the alertable signal,
2. dumps the flight recorder (Chrome-trace JSON) + the live metrics
   snapshot into a fresh ``stall-<n>-<site>/`` directory under
   ``quarantine_dir`` (same quarantine discipline as corrupt WAL
   artifacts: evidence is renamed aside, never overwritten), and
3. attempts a short ``jax.profiler`` capture beside them — if the
   runtime can still trace, the device timeline of the wedge lands in
   ``profile/``; if the profiler itself is wedged the failure is
   recorded in ``capture.json`` instead of hanging the watchdog.

One dump per stall *episode*: the marker's start time latches, so a
600 s wedge produces one directory, not 600.  ``check()`` is the
deterministic inline surface (fake clocks welcome); ``start()`` runs the
same check on a daemon poll loop for real deployments.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

__all__ = ["StallWatchdog"]


class StallWatchdog:
    """Watch one server's dispatch thread for wedged device calls.

    ``server`` needs ``dispatch_inflight()``, ``clock``, ``metrics`` and
    ``metrics_snapshot()`` (duck-typed —
    :class:`raft_tpu.serve.SearchServer` and the tests' fakes both
    qualify).  ``capture_s`` bounds the profiler capture; 0 disables it
    (flight recorder + metrics still dump).

    ``max_dumps`` is the quarantine retention policy: after each dump,
    only the newest ``max_dumps`` ``stall-*`` directories are kept and
    the rest are pruned (counted — ``stall_dumps_pruned``).  A flapping
    wedge used to fill the disk with one directory per episode; the
    newest dumps are the ones being debugged.  0 disables pruning."""

    def __init__(self, server, quarantine_dir, *,
                 stall_timeout_s: float = 30.0,
                 poll_interval_s: float = 1.0,
                 capture_s: float = 0.25,
                 max_dumps: int = 16,
                 recorder=None, clock=None, sleep=time.sleep) -> None:
        from ..core.errors import expects

        expects(stall_timeout_s > 0, "stall_timeout_s must be > 0")
        expects(poll_interval_s > 0, "poll_interval_s must be > 0")
        expects(capture_s >= 0, "capture_s must be >= 0")
        expects(max_dumps >= 0, "max_dumps must be >= 0")
        self.server = server
        self.quarantine_dir = os.fspath(quarantine_dir)
        self.stall_timeout_s = float(stall_timeout_s)
        self.poll_interval_s = float(poll_interval_s)
        self.capture_s = float(capture_s)
        self.max_dumps = int(max_dumps)
        self.clock = clock if clock is not None else server.clock
        self._sleep = sleep
        if recorder is None:
            from .spans import recorder as default_recorder

            recorder = default_recorder()
        self.recorder = recorder
        self.stalls_detected = 0
        self.pruned_total = 0
        self.dumps: list = []          # dump dir paths, oldest first
        self._latched_t0: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- detection ----------------------------------------------------------

    def check(self, now: Optional[float] = None) -> Optional[str]:
        """One poll: returns the new dump directory when a *fresh* stall
        episode was detected, else ``None``.  Safe to drive inline with a
        fake clock (no thread required)."""
        inflight = self.server.dispatch_inflight()
        if inflight is None:
            self._latched_t0 = None       # episode over; re-arm
            return None
        site, t0 = inflight
        now = self.clock() if now is None else now
        if now - t0 < self.stall_timeout_s:
            return None
        if self._latched_t0 == t0:
            return None                   # already dumped this episode
        self._latched_t0 = t0
        self.stalls_detected += 1
        self.server.metrics.count("stalls")
        self.recorder.event("obs.stall_detected", site=site,
                            stalled_s=round(now - t0, 3))
        path = self._dump(site, now - t0)
        self.dumps.append(path)
        self._prune()
        return path

    # -- evidence -----------------------------------------------------------

    def _dump(self, site: str, stalled_s: float) -> str:
        from ..core.logging import default_logger
        from ..core.serialize import write_text_atomic
        from .perfetto import export_chrome_trace

        os.makedirs(self.quarantine_dir, exist_ok=True)
        n = self.stalls_detected
        out = os.path.join(self.quarantine_dir, f"stall-{n:03d}-{site}")
        suffix = 0
        while os.path.exists(out):        # never overwrite evidence
            suffix += 1
            out = os.path.join(self.quarantine_dir,
                               f"stall-{n:03d}-{site}.{suffix}")
        os.makedirs(out)
        export_chrome_trace(os.path.join(out, "flight.trace.json"),
                            self.recorder.snapshot())
        write_text_atomic(
            os.path.join(out, "metrics.json"),
            json.dumps(self.server.metrics_snapshot(), indent=2,
                       sort_keys=True, default=repr) + "\n")
        capture = {"requested_s": self.capture_s}
        if self.capture_s > 0:
            capture.update(self._profiler_capture(
                os.path.join(out, "profile")))
        write_text_atomic(os.path.join(out, "capture.json"),
                          json.dumps(capture, indent=2) + "\n")
        default_logger().error(
            "stall watchdog: dispatch at %r in flight for %.1fs "
            "(timeout %.1fs) — flight recorder + profiler capture dumped "
            "to %s", site, stalled_s, self.stall_timeout_s, out)
        return out

    def _prune(self) -> int:
        """Apply the retention policy: drop the oldest ``stall-*``
        directories beyond ``max_dumps``.  Ordered by the zero-padded
        episode number in the name (stall-001 < stall-002 < ...), so
        retention is deterministic and independent of filesystem
        timestamps; directories from a previous process count too —
        retention is a property of the quarantine dir, not this run."""
        if self.max_dumps <= 0:
            return 0
        import shutil

        try:
            entries = sorted(
                e for e in os.listdir(self.quarantine_dir)
                if e.startswith("stall-")
                and os.path.isdir(os.path.join(self.quarantine_dir, e)))
        except OSError:
            return 0
        pruned = 0
        for name in entries[:-self.max_dumps]:
            path = os.path.join(self.quarantine_dir, name)
            try:
                shutil.rmtree(path)
            except OSError:
                continue                  # busy/foreign dir: keep it
            pruned += 1
            if path in self.dumps:
                self.dumps.remove(path)
        if pruned:
            self.pruned_total += pruned
            try:
                self.server.metrics.count("stall_dumps_pruned", pruned)
            except Exception:  # noqa: BLE001 — fakes without the counter
                pass
            self.recorder.event("obs.stall_dumps_pruned", n=pruned,
                                kept=self.max_dumps)
        return pruned

    def _profiler_capture(self, logdir: str) -> dict:
        """Best-effort ``jax.profiler`` capture.  The profiler runs on
        *this* thread — a wedge that blocks the dispatch thread usually
        leaves the runtime traceable; when it does not, the error string
        is the evidence.  The capture holds one ``obs.stall_capture``
        annotation whose ``span_id`` and ``t_ns`` (monotonic ns) are
        returned too: its ``start_ns - t_ns`` in the capture is the
        offset onto ``flight.trace.json``."""
        try:
            import jax

            from ..core import tracing

            jax.profiler.start_trace(logdir)
            try:
                with tracing.range("obs.stall_capture") as span:
                    self._sleep(self.capture_s)
            finally:
                jax.profiler.stop_trace()
            out = {"ok": True, "logdir": logdir}
            if span is not None:
                out.update(span_id=span.span_id, t_ns=span.t_start_ns)
            return out
        except Exception as exc:  # noqa: BLE001 - evidence, not control flow
            return {"ok": False, "error": repr(exc)}

    # -- daemon loop --------------------------------------------------------

    def start(self) -> "StallWatchdog":
        from ..core.errors import expects

        expects(self._thread is None, "watchdog already started")
        self._stop.clear()
        self._thread = threading.Thread(  # racelint: disable=JX14 the watchdog's only jax touch is the profiler capture on the stall path — collecting that evidence is its whole job
            target=self._loop, daemon=True, name="raft-tpu-stall-watchdog")
        self._thread.start()
        return self

    def stop(self, timeout: Optional[float] = 5.0) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout)
        self._thread = None

    def __enter__(self) -> "StallWatchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            try:
                self.check()
            except Exception:  # noqa: BLE001 - the watchdog must outlive
                from ..core.logging import default_logger

                default_logger().exception("stall watchdog check failed")
