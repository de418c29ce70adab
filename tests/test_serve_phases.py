"""The host phases of a served batch, on the flight recorder and on the
profiler's clock, and the benchmark readers that read them.

* one ``step()`` records ``form``/``stage``/``launch``/``fetch``/``reply``
  as sibling ``tracing`` ranges on the calling thread, each joined to the
  server's ``serve.dispatch`` span by ``dispatch=``;
* under ``jax.profiler`` every phase annotation carries its span's
  ``span_id`` and ``t_ns``, and ``start_ns - t_ns`` is one offset;
* the benchmark's trace reduction names an idle gap after the phase it
  falls in, and the ``host_ms.batch`` and ``fused_l2_topk_roofline.brute``
  readers give a value on hand-built inputs and ``None`` without them.
"""

from __future__ import annotations

import os
import statistics
import sys
import types

import jax
import numpy as np
import pytest

from raft_tpu.core import tracing
from raft_tpu.obs import spans as obs_spans
from raft_tpu.obs.spans import SpanRecorder
from raft_tpu.serve import SearchServer, ServerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import spec, xplane  # noqa: E402

PHASES = ["form", "stage", "launch", "fetch", "reply"]


@pytest.fixture
def process_recorder():
    """A fresh process-wide recorder (the one ``tracing`` writes into)."""
    rec = SpanRecorder(4096)
    prev = obs_spans.set_recorder(rec)
    yield rec
    obs_spans.set_recorder(prev)


@pytest.fixture(scope="module")
def db():
    return np.random.default_rng(0).standard_normal((256, 16)).astype(
        np.float32)


def _server(db):
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       recorder=SpanRecorder(256))
    srv.warmup()
    return srv


def _phase(span):
    return span.name.rsplit(":", 1)[1]


def test_one_step_records_sibling_phases(db, process_recorder):
    srv = _server(db)
    fut = srv.submit(db[:3])
    assert srv.step() == 1
    fut.result(timeout=5)

    (dispatch,) = [s for s in srv.recorder.snapshot()
                   if s.name == "serve.dispatch"]
    spans = [s for s in process_recorder.snapshot()
             if s.name.startswith("serve.dispatch(")]
    assert [_phase(s) for s in spans] == PHASES
    assert spans[0].name == "serve.dispatch(brute_force):form"
    assert all(s.name.startswith("serve.dispatch(brute_force,b=4,k=3,lvl=0):")
               for s in spans[1:])
    assert len({s.tid for s in spans}) == 1
    for s in spans:
        assert s.attrs["dispatch"] == dispatch.span_id, s.name
        assert s.parent_id is None, s.name           # none encloses another
    for a, b in zip(spans, spans[1:]):
        assert a.t_end_ns <= b.t_start_ns, (a.name, b.name)


def test_empty_step_forms_nothing_to_dispatch(db, process_recorder):
    srv = _server(db)
    assert srv.step() == 0
    (form,) = [s for s in process_recorder.snapshot()
               if s.name.startswith("serve.dispatch(")]
    assert _phase(form) == "form" and "dispatch" not in form.attrs


def test_worker_wait_is_its_own_phase(db, process_recorder):
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,),
                                                    max_wait_ms=5.0),
                       recorder=SpanRecorder(256))
    with srv:
        srv.search(db[:1])           # one row: the batching window waits
    spans = [s for s in process_recorder.snapshot()
             if s.name.startswith("serve.dispatch(")]
    waits = [s for s in spans if _phase(s) == "wait"]
    assert waits and waits[0].name == "serve.dispatch(brute_force):wait"
    fetch = [s for s in spans if _phase(s) == "fetch"]
    assert len(fetch) == 1
    for w in waits:                  # a wait never overlaps a batch's phases
        assert w.t_end_ns <= fetch[0].t_start_ns or \
            w.t_start_ns >= fetch[0].t_end_ns


def test_range_yields_its_span_and_attrs(process_recorder):
    with tracing.range("outer(%d)", 1, dispatch=7) as outer:
        with tracing.range("inner") as inner:
            pass
    assert outer.name == "outer(1)" and outer.attrs == {"dispatch": 7}
    assert inner.parent_id == outer.span_id
    assert outer.t_start_ns <= inner.t_start_ns <= inner.t_end_ns \
        <= outer.t_end_ns


def _host_events(log_dir, prefix):
    from jax.profiler import ProfileData

    path = xplane.find_trace(str(log_dir))
    assert path is not None
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns, dict(ev.stats)))
    return out


def test_phase_annotations_carry_the_recorder_clock(db, process_recorder,
                                                    tmp_path):
    srv = _server(db)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for j in range(25):
            fut = srv.submit(db[j:j + 4])
            srv.step()
            fut.result(timeout=5)
    finally:
        jax.profiler.stop_trace()

    spans = {s.span_id: s for s in process_recorder.snapshot()
             if s.name.startswith("serve.dispatch(")}
    events = _host_events(tmp_path, "serve.dispatch(")
    assert len(events) >= 100
    offsets = []
    for name, start_ns, stats in events:
        assert "span_id" in stats and "t_ns" in stats, name
        span = spans[int(stats["span_id"])]
        assert span.name == name                  # metadata leaves the name
        assert int(stats["t_ns"]) == span.t_start_ns
        offsets.append(start_ns - int(stats["t_ns"]))
    # one offset maps the capture onto the ring: the two stamps are taken
    # microseconds apart, so all but the rare stamp pair split by a
    # collection or a preemption lie within 50 us of the median offset
    mid = statistics.median(offsets)
    near = sum(abs(o - mid) < 50_000 for o in offsets)
    assert near >= 0.95 * len(offsets), sorted(o - mid for o in offsets)


def test_watchdog_capture_holds_its_alignment_annotation(db, tmp_path):
    srv = SearchServer(db, k=3, config=ServerConfig(ladder=(4,)),
                       recorder=SpanRecorder(32))
    wd = srv.attach_watchdog(tmp_path, capture_s=0.01)
    capture = wd._profiler_capture(str(tmp_path / "profile"))
    assert capture["ok"] is True
    (ev,) = _host_events(tmp_path / "profile", "obs.stall_capture")
    assert int(ev[2]["span_id"]) == capture["span_id"]
    assert int(ev[2]["t_ns"]) == capture["t_ns"]


# -- the benchmark's reduction and readers ---------------------------------


def test_reduction_names_a_gap_after_its_phase():
    tag = "serve.dispatch(brute_force,b=512,k=10,lvl=0)"
    # device busy [0, 10) and [14, 20) and [21, 30): gaps [10, 14), [20, 21)
    ops = {"/device:TPU:0": [("p/fused_l2_topk.1", 0, 10),
                             ("p/fused_l2_topk.1", 14, 20),
                             ("p/fused_l2_topk.1", 21, 30)]}
    host = [(tag + ":fetch", 2, 10.5),
            (tag + ":reply", 10.5, 11),
            ("serve.dispatch(brute_force):form", 11, 11.5),
            (tag + ":stage", 11.5, 12),
            (tag + ":launch", 12, 14.2),
            (tag + ":reply", 19.8, 21)]
    r = xplane.reduce_events((0, 30), ops, host)
    assert [name for name, _ in r.idle_gaps] == [tag + ":launch",
                                                 tag + ":reply"]
    assert [s for _, s in r.idle_gaps] == pytest.approx([4e-9, 1e-9])
    assert r.device_ops == [("p/fused_l2_topk.1", pytest.approx(25e-9))]


def _reduction(ops, busy_s=2.0):
    return xplane.Reduction(window_s=3.0, busy_s=busy_s, devices=1,
                            device_ops=ops, idle_gaps=[])


def _reader(name):
    return spec.load_module(ROOT, "layer_metrics", name)


def _record_batches(rec, n, t0_ns, period_ns, fetch_ns, host_ns):
    """``n`` batches, each a fetch then ``host_ns`` of host phases."""
    tag = "serve.dispatch(brute_force,b=512,k=10,lvl=0)"
    t = t0_ns
    for _ in range(n):
        rec.record(tag + ":fetch", t, t + fetch_ns)
        rec.record(tag + ":reply", t + fetch_ns, t + fetch_ns + host_ns)
        t += period_ns


def test_host_ms_reads_the_median_gap_between_fetches(process_recorder):
    # 120 batches every 6 ms, each fetch 4 ms: 2 ms from one fetch's end
    # to the next one's start
    _record_batches(process_recorder, 120, 10**9, 6_000_000, 4_000_000,
                    1_000_000)
    window = types.SimpleNamespace(t0=1.0, t1=1.0 + 120 * 0.006)
    ctx = types.SimpleNamespace(window=window, trace=_reduction([]))
    read = _reader("host_ms.batch").read
    assert read(ctx) == pytest.approx(2.0)
    off_chip = types.SimpleNamespace(window=window, trace=None)
    assert read(off_chip) is None                 # no device trace


def test_host_ms_is_none_without_enough_batches(process_recorder):
    ctx = types.SimpleNamespace(window=types.SimpleNamespace(t0=1.0, t1=2.0),
                                trace=_reduction([]))
    read = _reader("host_ms.batch").read
    assert read(ctx) is None                      # no phase spans at all
    _record_batches(process_recorder, 40, 10**9, 6_000_000, 4_000_000,
                    1_000_000)
    assert read(ctx) is None                      # 39 gaps < 50
    late = types.SimpleNamespace(window=types.SimpleNamespace(t0=5.0,
                                                              t1=6.0),
                                 trace=_reduction([]))
    _record_batches(process_recorder, 80, 2 * 10**9, 6_000_000, 4_000_000,
                    1_000_000)
    assert read(late) is None                     # none inside the window


def test_kernel_roofline_reads_the_kernel_self_time():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    # two requests: ops-bound 0.3 s and bytes-bound 0.2 s of least time
    work = [(3e11, 1e9), (1e11, 2e10)]
    ops = [("jit_p/fused_l2_topk.1", 0.8), ("jit_p/pad.6", 0.5),
           ("jit_q/fused_l2_topk.3", 0.2), ("jit_p/sort.3", 0.1)]
    ctx = types.SimpleNamespace(trace=_reduction(ops), work=work,
                                peaks=peaks)
    read = _reader("fused_l2_topk_roofline.brute").read
    assert read(ctx) == pytest.approx(100.0 * 0.5 / 1.0)


@pytest.mark.parametrize("trace", [
    None,
    _reduction([("jit_p/_call.1", 0.8), ("jit_p/pad.6", 0.5)]),
    _reduction([("jit_p/select_k.4", 0.8)]),
], ids=["no_trace", "unnamed_kernel", "other_kernel"])
def test_kernel_roofline_is_none_without_the_kernel(trace):
    ctx = types.SimpleNamespace(trace=trace, work=[(1e9, 1e9)],
                                peaks={"bf16_flops_per_s": 1e12,
                                       "hbm_bytes_per_s": 1e11})
    assert _reader("fused_l2_topk_roofline.brute").read(ctx) is None
