"""raft_tpu.obs — telemetry subsystem tests (ISSUE 9).

All tier-1 (CPU, fast).  The observability contract under test:

* spans nest per-thread, parent explicitly across threads, and survive
  in fixed-capacity per-thread rings (the flight recorder);
* one serve request produces a **connected span tree**
  (request -> enqueue/batch_form/dispatch/reply) visible in
  the exported Chrome-trace JSON — the acceptance criterion;
* the Prometheus exposition parses, and its histogram-derived p95 agrees
  with the JSON snapshot's exact reservoir p95 within one bucket width;
* ``ServingMetrics.count()`` raises :class:`UnknownCounter` on typos
  (the old ``AttributeError``-in-``setattr`` bug) and ``declare()`` is
  the documented dynamic-create path;
* ``dump_metrics`` / ``write_text_atomic`` never leave a torn file;
* ``tracing.pop_range`` is balanced-safe and exception-safe;
* an injected ``wedge`` fault trips the stall watchdog and leaves a
  flight-recorder dump on disk;
* the whole telemetry surface adds **zero** retraces / recompiles /
  transfers to the warmed serve hot path (TraceGuard).
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

import jax

from raft_tpu.core import tracing
from raft_tpu.core.errors import RaftError
from raft_tpu.core.serialize import write_text_atomic
from raft_tpu.core.trace_guard import TraceGuard
from raft_tpu.obs import (DEFAULT_LATENCY_BOUNDARIES_MS, Counter, Gauge,
                          Histogram, MetricRegistry, SpanRecorder,
                          StallWatchdog, chrome_trace, export_chrome_trace,
                          parse_text, render)
from raft_tpu.obs import spans as obs_spans
from raft_tpu.serve import (FaultInjector, RetryPolicy, SearchServer,
                            ServerConfig, ServingMetrics, UnknownCounter)

N, D = 160, 16


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeNsClock:
    """Deterministic monotonic_ns stand-in for span timing tests."""

    def __init__(self, t: int = 1_000) -> None:
        self.t = t

    def __call__(self) -> int:
        self.t += 1_000
        return self.t


@pytest.fixture(scope="module")
def db():
    return np.random.default_rng(90).standard_normal((N, D)).astype(np.float32)


@pytest.fixture(scope="module")
def queries(db):
    return db[:3]


@pytest.fixture()
def isolated_recorder():
    """Fresh process-default recorder per test, restored afterwards."""
    rec = SpanRecorder(256)
    prev = obs_spans.set_recorder(rec)
    yield rec
    obs_spans.set_recorder(prev)


# ---------------------------------------------------------------------------
# span recorder


def test_span_nesting_auto_parents():
    rec = SpanRecorder(16, clock_ns=FakeNsClock())
    with rec.span("outer", rows=4) as outer:
        with rec.span("inner") as inner:
            assert rec.current() is inner
        assert rec.current() is outer
    assert rec.current() is None
    spans = rec.snapshot()
    assert [s.name for s in spans] == ["outer", "inner"]
    o, i = spans
    assert i.parent_id == o.span_id and i.trace_id == o.trace_id
    assert o.parent_id is None and o.trace_id == o.span_id
    assert o.attrs == {"rows": 4}
    assert o.t_end_ns > o.t_start_ns and i.duration_ns > 0


def test_span_explicit_parent_crosses_threads():
    rec = SpanRecorder(16)
    root = rec.start("request")
    got = {}

    def worker():
        with rec.span("dispatch", parent=root):
            pass
        got["tid"] = threading.get_ident()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    rec.finish(root, status="ok")
    spans = {s.name: s for s in rec.snapshot()}
    assert spans["dispatch"].parent_id == spans["request"].span_id
    assert spans["dispatch"].trace_id == spans["request"].trace_id
    assert spans["dispatch"].tid == got["tid"] != spans["request"].tid


def test_ring_overwrites_oldest_keeps_order():
    rec = SpanRecorder(4, clock_ns=FakeNsClock())
    for j in range(7):
        rec.event(f"e{j}")
    names = [s.name for s in rec.snapshot()]
    assert names == ["e3", "e4", "e5", "e6"]
    st = rec.stats()
    assert st["retained"] == 4 and st["recorded"] == 7


def test_record_and_event_forms():
    rec = SpanRecorder(16)
    sp = rec.record("measured", 100, 300, bucket=8)
    ev = rec.event("marker", reason="stale")
    assert sp.duration_ns == 200 and sp.attrs == {"bucket": 8}
    assert ev.duration_ns == 0
    assert [s.name for s in rec.snapshot()] == ["measured", "marker"]


def test_finish_is_idempotent_one_ring_entry():
    # split requests share one root span; every part's resolve calls
    # finish on it — the ring must retain it exactly once
    rec = SpanRecorder(16)
    root = rec.start("request")
    rec.finish(root, status="ok", part=0)
    end = root.t_end_ns
    rec.finish(root, part=1)
    assert root.t_end_ns == end           # not re-stamped
    assert root.attrs["part"] == 1        # attrs still update
    assert len(rec.snapshot()) == 1


def test_disabled_recorder_records_nothing():
    rec = SpanRecorder(16, enabled=False)
    assert rec.start("x") is None
    rec.finish(None)
    with rec.span("y") as sp:
        assert sp is None
    assert rec.event("z") is None
    assert rec.snapshot() == [] and rec.stats()["recorded"] == 0


def test_span_records_error_attr_and_pops_on_raise():
    rec = SpanRecorder(16)
    with pytest.raises(ValueError):
        with rec.span("boom"):
            raise ValueError("x")
    (sp,) = rec.snapshot()
    assert sp.attrs["error"] == "ValueError" and sp.t_end_ns > 0
    assert rec.current() is None


def test_clear_and_capacity_validation():
    rec = SpanRecorder(8)
    rec.event("a")
    rec.clear()
    assert rec.snapshot() == []
    with pytest.raises(RaftError):
        SpanRecorder(0)


# ---------------------------------------------------------------------------
# metrics registry


def test_counter_labels_and_monotonicity():
    c = Counter("hits")
    c.inc()
    c.inc(2, kernel="fused")
    c.inc(kernel="fused")
    assert c.value() == 1.0 and c.value(kernel="fused") == 3.0
    assert c.samples() == [({}, 1.0), ({"kernel": "fused"}, 3.0)]
    with pytest.raises(RaftError):
        c.inc(-1)


def test_gauge_sets_point_in_time():
    g = Gauge("depth")
    g.set(3)
    g.set(7)
    assert g.value() == 7.0


def test_histogram_buckets_quantile_width():
    h = Histogram("lat", boundaries=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 3.5, 10.0):
        h.observe(v)
    ((labels, counts, total),) = h.samples()
    assert labels == {} and counts == [1, 1, 2, 1]   # last slot = +Inf
    assert total == pytest.approx(18.5) and h.count() == 5
    assert h.quantile(0.2) == 1.0
    assert h.quantile(0.8) == 4.0
    assert h.quantile(1.0) == 4.0   # overflow clamps to top boundary
    assert h.bucket_width(1.5) == 1.0 and h.bucket_width(3.0) == 2.0
    assert h.bucket_width(99.0) == 2.0
    assert Histogram("empty").quantile(0.95) == 0.0
    with pytest.raises(RaftError):
        Histogram("bad", boundaries=(2.0, 1.0))
    with pytest.raises(RaftError):
        h.quantile(0.0)


def test_histogram_interpolated_quantile():
    h = Histogram("lat", boundaries=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 3.5, 10.0):
        h.observe(v)
    # linear placement inside the bucket: q=0.7 -> need 3.5 of 5, bucket
    # (2, 4] holds ranks 3..4, so 2 + (3.5-2)/2 * 2 = 3.5
    assert h.quantile(0.7, interpolate=True) == pytest.approx(3.5)
    # both estimates always land in the SAME bucket, interpolated <= edge
    for q in (0.2, 0.5, 0.7, 0.8):
        edge = h.quantile(q)
        interp = h.quantile(q, interpolate=True)
        assert edge - h.bucket_width(edge) <= interp <= edge
    # overflow and empty behave exactly like the conservative default
    assert h.quantile(1.0, interpolate=True) == 4.0
    assert Histogram("empty").quantile(0.95, interpolate=True) == 0.0


def test_family_and_histogram_remove_label_set():
    c = Counter("x")
    c.inc(3, generation="1")
    c.inc(5, generation="2")
    assert c.remove(generation="1") and not c.remove(generation="1")
    assert c.samples() == [({"generation": "2"}, 5.0)]
    h = Histogram("lat", boundaries=(1.0,))
    h.observe(0.5, generation="1")
    h.observe(0.5, generation="2")
    assert h.remove(generation="1") and not h.remove(generation="1")
    assert [labels for labels, _, _ in h.samples()] == [{"generation": "2"}]


def test_registry_idempotent_and_type_checked():
    reg = MetricRegistry()
    c1 = reg.counter("x", "help")
    assert reg.counter("x") is c1
    with pytest.raises(RaftError):
        reg.gauge("x")
    reg.histogram("h")
    assert [m.name for m in reg.collect()] == ["x", "h"]
    assert reg.get("h") is not None and reg.get("nope") is None


# ---------------------------------------------------------------------------
# prometheus exposition


def test_render_parse_roundtrip():
    reg = MetricRegistry()
    reg.counter("req_total", "requests").inc(5, route="search")
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_ms", "latency", (1.0, 4.0))
    h.observe(0.5)
    h.observe(2.0)
    h.observe(9.0)
    text = render(reg)
    assert "# TYPE req_total counter" in text
    assert "# TYPE lat_ms histogram" in text
    parsed = parse_text(text)
    assert parsed["req_total"] == [({"route": "search"}, 5.0)]
    assert parsed["depth"] == [({}, 2.0)]
    buckets = {l["le"]: v for l, v in parsed["lat_ms_bucket"]}
    assert buckets == {"1": 1.0, "4": 2.0, "+Inf": 3.0}  # cumulative
    assert parsed["lat_ms_count"] == [({}, 3.0)]
    assert parsed["lat_ms_sum"][0][1] == pytest.approx(11.5)


def test_render_escapes_and_dedups():
    reg1, reg2 = MetricRegistry(), MetricRegistry()
    reg1.counter("c", 'a "quoted" \\ help\nline').inc(msg='x"y\\z\nw')
    reg2.counter("c", "shadowed duplicate").inc(9)
    text = render((reg1, reg2))
    assert text.count("# TYPE c counter") == 1   # first registry wins
    parsed = parse_text(text)
    ((labels, v),) = parsed["c"]
    assert labels == {"msg": 'x"y\\z\nw'} and v == 1.0
    with pytest.raises(ValueError):
        parse_text("what even is this line")


def test_render_registered_but_empty_family():
    reg = MetricRegistry()
    reg.counter("quiet_total", "never fired")
    assert parse_text(render(reg))["quiet_total"] == [({}, 0.0)]


# ---------------------------------------------------------------------------
# perfetto / chrome trace export


def test_chrome_trace_events_and_flows():
    rec = SpanRecorder(32)
    root = rec.start("request", rows=2)

    def worker():
        with rec.span("dispatch", parent=root):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    rec.finish(root)
    open_span = rec.start("still-open")     # must be skipped
    doc = chrome_trace(rec.snapshot() + [open_span])
    evs = doc["traceEvents"]
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"request", "dispatch"}
    assert xs["dispatch"]["args"]["parent_id"] == \
        xs["request"]["args"]["span_id"]
    assert xs["request"]["args"]["rows"] == 2
    # cross-thread lineage draws a flow arrow pair
    assert [e["ph"] for e in evs if e.get("cat") == "flow"] == ["s", "f"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert len(names) == 2
    assert json.loads(json.dumps(doc))      # strictly JSON-serializable


def test_export_chrome_trace_atomic(tmp_path):
    rec = SpanRecorder(8)
    rec.event("e", arr=np.arange(2))        # non-JSON attr -> repr()
    path = export_chrome_trace(tmp_path / "t.json", rec.snapshot())
    doc = json.loads(open(path).read())
    (ev,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert isinstance(ev["args"]["arr"], str)
    assert not [p for p in os.listdir(tmp_path) if ".tmp-" in p]


# ---------------------------------------------------------------------------
# serving metrics (satellite 1: UnknownCounter regression)


def test_count_unknown_counter_raises_with_registered_names():
    m = ServingMetrics()
    with pytest.raises(UnknownCounter) as ei:
        m.count("compleeted")          # the historical typo class
    assert "compleeted" in str(ei.value) and "completed" in str(ei.value)
    with pytest.raises(UnknownCounter):
        m.counter_value("nope")


def test_declare_is_the_dynamic_create_path():
    m = ServingMetrics()
    m.declare("frobnications", "custom host counter")
    m.declare("frobnications")               # idempotent
    m.count("frobnications", 3)
    assert m.frobnications == 3
    assert m.snapshot()["frobnications"] == 3
    assert parse_text(m.prometheus_text())[
        "raft_serve_frobnications_total"][0][1] == 3.0


def test_counters_read_as_attributes_and_snapshot_schema():
    m = ServingMetrics()
    m.count("submitted")
    m.observe_batch(8, rows=5, level=1)
    m.observe_latency(3.0)
    m.observe_latency(12.0, late=True)
    assert m.submitted == 1 and m.batches == 1 and m.completed == 2
    assert m.late_completions == 1
    with pytest.raises(AttributeError):
        m.not_a_counter
    snap = m.snapshot()
    # the historical JSON schema survives...
    for key in ("submitted", "completed", "batches", "batch_fill_ratio",
                "degrade_dispatches", "latency_ms"):
        assert key in snap
    assert snap["batch_fill_ratio"] == pytest.approx(5 / 8)
    assert snap["degrade_dispatches"] == {"1": 1}
    # ...plus the mergeable histogram block
    hist = snap["latency_hist"]
    assert hist["boundaries_ms"] == list(DEFAULT_LATENCY_BOUNDARIES_MS)
    assert sum(hist["counts"]) == 2
    assert hist["sum_ms"] == pytest.approx(15.0)


# ---------------------------------------------------------------------------
# crash-consistent dumps (satellite 2)


def test_write_text_atomic_no_torn_file(tmp_path, monkeypatch):
    target = tmp_path / "m.json"
    write_text_atomic(target, "old\n")
    calls = {"n": 0}
    real_replace = os.replace

    def failing_replace(src, dst):
        calls["n"] += 1
        raise OSError("disk went away")

    monkeypatch.setattr(os, "replace", failing_replace)
    with pytest.raises(OSError):
        write_text_atomic(target, "new\n")
    monkeypatch.setattr(os, "replace", real_replace)
    assert calls["n"] == 1
    assert target.read_text() == "old\n"            # old content intact
    assert not [p for p in os.listdir(tmp_path) if ".tmp-" in p]  # no litter
    write_text_atomic(target, "new\n")
    assert target.read_text() == "new\n"


def test_dump_metrics_writes_valid_json_atomically(db, tmp_path):
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       clock=FakeClock())
    fut = srv.submit(db[:2])
    srv.step()
    fut.result(timeout=5)
    path = tmp_path / "metrics.json"
    srv.dump_metrics(path)
    snap = json.loads(path.read_text())
    assert snap["completed"] == 1 and "cache" in snap
    assert not [p for p in os.listdir(tmp_path) if ".tmp-" in p]


# ---------------------------------------------------------------------------
# tracing push/pop (satellite 3)


def test_pop_range_empty_stack_is_counted_noop(isolated_recorder):
    from raft_tpu.obs.metrics import registry

    c = registry().counter("raft_tracing_unbalanced_pops_total")
    before = c.value()
    assert tracing.pop_range() is False
    assert tracing.stack_depth() == 0
    assert c.value() == before + 1


def test_push_pop_balanced_records_spans(isolated_recorder):
    tracing.push_range("outer(%d)", 1)
    tracing.push_range("inner")
    assert tracing.stack_depth() == 2
    assert tracing.pop_range() is True
    assert tracing.pop_range() is True
    assert tracing.stack_depth() == 0
    names = [s.name for s in isolated_recorder.snapshot()]
    assert names == ["outer(1)", "inner"]   # snapshot orders by start time


def test_push_pop_stacks_are_per_thread(isolated_recorder):
    tracing.push_range("main-range")
    depths = {}

    def worker():
        depths["start"] = tracing.stack_depth()   # fresh stack, not 1
        tracing.push_range("worker-range")
        depths["pushed"] = tracing.stack_depth()
        tracing.pop_range()
        depths["end"] = tracing.stack_depth()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert depths == {"start": 0, "pushed": 1, "end": 0}
    assert tracing.stack_depth() == 1
    assert tracing.pop_range() is True


def test_pop_range_finishes_span_when_exit_raises(isolated_recorder):
    class ExplodingAnnotation:
        def __exit__(self, *exc):
            raise RuntimeError("profiler backend fell over")

    span = isolated_recorder.start("doomed")
    tracing._stack().append((ExplodingAnnotation(), span))
    with pytest.raises(RuntimeError):
        tracing.pop_range()
    assert tracing.stack_depth() == 0               # stack still popped
    assert [s.name for s in isolated_recorder.snapshot()] == ["doomed"]
    assert span.t_end_ns > 0                        # span still finished


def test_range_is_exception_safe(isolated_recorder):
    with pytest.raises(KeyError):
        with tracing.range("risky"):
            raise KeyError("x")
    (sp,) = isolated_recorder.snapshot()
    assert sp.name == "risky" and sp.attrs["error"] == "KeyError"


# ---------------------------------------------------------------------------
# the serve span tree (ACCEPTANCE: connected request tree in the export)


def test_one_request_produces_connected_span_tree(db, queries, tmp_path,
                                                  isolated_recorder):
    rec = SpanRecorder(512)
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       clock=FakeClock(), recorder=rec)
    fut = srv.submit(queries)
    srv.step()
    d, i = fut.result(timeout=5)
    assert np.asarray(i).shape == (3, 3)

    by_name = {}
    for s in rec.snapshot():
        by_name.setdefault(s.name, []).append(s)
    root = by_name["serve.request"][0]
    assert root.attrs["rows"] == 3 and root.attrs["status"] == "ok"
    for name in ("serve.enqueue", "serve.batch_form", "serve.dispatch",
                 "serve.reply"):
        (sp,) = by_name[name]
        assert sp.parent_id == root.span_id, name
        assert sp.trace_id == root.trace_id, name
    (dispatch,) = by_name["serve.dispatch"]
    assert "serve.device_exec" not in by_name
    # the batch's host phases, in the process-wide recorder, joined to
    # the dispatch span by id
    phases = [s for s in isolated_recorder.snapshot()
              if s.attrs.get("dispatch") == dispatch.span_id]
    assert [s.name.rsplit(":", 1)[1] for s in phases] == [
        "form", "stage", "launch", "fetch", "reply"]
    assert dispatch.attrs["status"] == "ok" and dispatch.attrs["attempts"] == 1

    # ...and the same tree is reachable in the exported chrome trace
    path = export_chrome_trace(tmp_path / "req.json", rec.snapshot())
    doc = json.loads(open(path).read())
    xs = {e["args"]["span_id"]: e for e in doc["traceEvents"]
          if e["ph"] == "X" and e["name"].startswith("serve.")}
    root_ev = [e for e in xs.values() if e["name"] == "serve.request"]
    assert len(root_ev) == 1
    root_id = root_ev[0]["args"]["span_id"]

    def climbs_to_root(ev, hops=10):
        while hops:
            pid = ev["args"]["parent_id"]
            if pid is None:
                return ev["args"]["span_id"] == root_id
            ev = xs[pid]
            hops -= 1
        return False

    for ev in xs.values():
        assert climbs_to_root(ev), ev["name"]


def test_split_request_parts_share_one_root(db):
    rec = SpanRecorder(512)
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       clock=FakeClock(), recorder=rec)
    fut = srv.submit(db[:7])      # 7 rows over a (4,) ladder: two parts
    while not fut.done():
        srv.step()
    d, i = fut.result(timeout=5)
    assert np.asarray(i).shape == (7, 3)
    spans = rec.snapshot()
    roots = [s for s in spans if s.name == "serve.request"]
    assert len(roots) == 1                      # one ring entry, not two
    dispatches = [s for s in spans if s.name == "serve.dispatch"]
    assert len(dispatches) == 2
    assert all(sp.parent_id == roots[0].span_id for sp in dispatches)


def test_rejected_requests_finish_their_spans(db):
    rec = SpanRecorder(128)
    clock = FakeClock()
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       clock=clock, recorder=rec)
    fut = srv.submit(db[:2], deadline_ms=10.0)
    clock.advance(1.0)            # expire in queue
    srv.step()
    with pytest.raises(Exception):
        fut.result(timeout=5)
    roots = [s for s in rec.snapshot() if s.name == "serve.request"]
    assert len(roots) == 1
    assert roots[0].attrs["status"] == "rejected_deadline"


# ---------------------------------------------------------------------------
# prometheus <-> snapshot agreement (ACCEPTANCE: p95 within a bucket)


def test_prometheus_p95_agrees_with_snapshot_within_bucket(db):
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       clock=FakeClock(), recorder=SpanRecorder(64))
    for j in range(20):
        fut = srv.submit(db[j:j + 2])
        srv.step()
        fut.result(timeout=5)
    snap = srv.metrics.snapshot()
    text = srv.prometheus_text()
    parsed = parse_text(text)

    # rebuild the histogram p95 FROM THE EXPOSITION, the way a scraper
    # would (cumulative buckets -> first le= at the 95th percentile rank)
    buckets = sorted(
        ((float("inf") if l["le"] == "+Inf" else float(l["le"])), v)
        for l, v in parsed["raft_serve_latency_ms_bucket"])
    total = parsed["raft_serve_latency_ms_count"][0][1]
    assert total == 20.0 == float(snap["completed"])
    need = 0.95 * total
    p95_prom = next(le for le, cum in buckets if cum >= need)
    p95_snap = snap["latency_ms"]["p95"]
    width = srv.metrics.latency_hist.bucket_width(
        min(p95_prom, DEFAULT_LATENCY_BOUNDARIES_MS[-1]))
    # tightened from PR 9's two-sided slack: the exposition p95 is the
    # conservative bucket edge, so it NEVER understates the exact
    # reservoir p95 and overstates by at most one bucket width
    assert 0 <= p95_prom - p95_snap <= width
    # the interpolated estimate lands inside that same bucket
    p95_interp = srv.metrics.latency_hist.quantile(0.95, interpolate=True)
    assert p95_prom - width <= p95_interp <= p95_prom
    # and the library-level gauges ride along in the same scrape body
    assert "raft_serve_queue_depth" in parsed
    assert "raft_obs_flight_recorder_spans" in parsed


def test_metrics_snapshot_carries_obs_stats(db):
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       clock=FakeClock(), recorder=SpanRecorder(64))
    snap = srv.metrics_snapshot()
    assert snap["obs"]["capacity_per_thread"] == 64
    assert snap["obs"]["enabled"] is True


# ---------------------------------------------------------------------------
# stall watchdog (ACCEPTANCE: wedge fault -> dump on disk)


def _wedged_server(db, tmp_path, *, times=2):
    clock = FakeClock()
    probes = {"dumps": []}

    faults = FaultInjector(sleep=lambda s: clock.advance(s))
    rec = SpanRecorder(256)
    srv = SearchServer(
        db, k=3, config=ServerConfig(
            ladder=(4,), retry=RetryPolicy(max_retries=times,
                                           backoff_ms=50.0)),
        clock=clock, faults=faults, recorder=rec,
        sleep=lambda s: probes["poll"]())
    wd = srv.attach_watchdog(tmp_path / "quarantine",
                             stall_timeout_s=0.01, capture_s=0.0)

    def poll():
        # backoff sleep during the wedge: the dispatch marker is live;
        # advance past the stall timeout and run one watchdog poll
        clock.advance(0.1)
        out = wd.check()
        if out:
            probes["dumps"].append(out)

    probes["poll"] = poll
    srv.faults.arm("execute", "wedge", times=times)
    return srv, wd, probes


def test_wedge_fault_trips_watchdog_and_dumps(db, queries, tmp_path):
    srv, wd, probes = _wedged_server(db, tmp_path)
    fut = srv.submit(queries)
    srv.step()
    d, i = fut.result(timeout=5)          # wedge retried through; answered
    assert np.asarray(i).shape == (3, 3)

    assert len(probes["dumps"]) == 1      # one episode -> ONE dump
    dump = probes["dumps"][0]
    assert os.path.basename(dump).startswith("stall-001-execute")
    flight = json.loads(open(os.path.join(dump, "flight.trace.json")).read())
    names = {e["name"] for e in flight["traceEvents"] if e["ph"] == "X"}
    assert "serve.retry" in names         # the wedge evidence
    assert "obs.stall_detected" in names
    metrics = json.loads(open(os.path.join(dump, "metrics.json")).read())
    assert metrics["stalls"] == 1
    capture = json.loads(open(os.path.join(dump, "capture.json")).read())
    assert capture == {"requested_s": 0.0}
    assert srv.metrics.stalls == 1 and wd.stalls_detected == 1
    # episode over: the marker cleared, the latch re-arms
    assert srv.dispatch_inflight() is None
    assert wd.check() is None


def test_watchdog_latches_one_dump_per_episode(db, tmp_path):
    clock = FakeClock()
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       clock=clock, recorder=SpanRecorder(32))
    wd = srv.attach_watchdog(tmp_path, stall_timeout_s=5.0, capture_s=0.0)
    assert wd.check() is None             # nothing in flight
    srv._inflight = ("execute", clock())
    clock.advance(1.0)
    assert wd.check() is None             # in flight but under timeout
    clock.advance(10.0)
    first = wd.check()
    assert first is not None
    assert wd.check() is None             # latched: same episode
    srv._inflight = None
    assert wd.check() is None             # re-armed
    srv._inflight = ("execute", clock())
    clock.advance(10.0)
    second = wd.check()                   # fresh episode -> fresh dump
    assert second is not None and second != first
    assert srv.metrics.stalls == 2
    assert sorted(os.listdir(tmp_path)) == [os.path.basename(first),
                                            os.path.basename(second)]


def test_watchdog_thread_lifecycle(db, tmp_path):
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       recorder=SpanRecorder(32))
    with srv.attach_watchdog(tmp_path, stall_timeout_s=30.0,
                             poll_interval_s=0.01) as wd:
        assert wd._thread.is_alive()
    assert wd._thread is None
    assert wd.stalls_detected == 0


# ---------------------------------------------------------------------------
# zero-overhead steady state (satellite 4: TraceGuard + exporters)


@pytest.mark.parametrize("family_build", [
    pytest.param(lambda db: db, id="brute_force"),
])
def test_serve_hot_path_steady_state_with_telemetry(db, family_build,
                                                    isolated_recorder):
    rec = SpanRecorder(1024)
    srv = SearchServer(family_build(db), k=3,
                       config=ServerConfig(ladder=(4,)),
                       clock=FakeClock(), recorder=rec)
    assert rec.enabled
    srv.warmup()
    # one dispatch outside the guard absorbs first-call layout quirks
    fut = srv.submit(db[:4])
    srv.step()
    fut.result(timeout=5)

    with TraceGuard() as tg, jax.transfer_guard("disallow"):
        for j in range(6):
            fut = srv.submit(db[j:j + 4])
            srv.step()
            fut.result(timeout=5)
        # the exporters themselves must also be trace-free
        srv.prometheus_text()
        srv.metrics.snapshot()
        chrome_trace(rec.snapshot())
    tg.assert_steady_state()
    assert srv.metrics.completed == 7
    fetches = [s for s in isolated_recorder.snapshot()
               if s.name.endswith(":fetch")]
    assert len(fetches) == 7
    assert {s.attrs["dispatch"] for s in fetches} == {
        s.span_id for s in rec.snapshot() if s.name == "serve.dispatch"}
