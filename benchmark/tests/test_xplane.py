"""The trace reduction: busy union, idle gaps and their attribution, on
hand-made events and on a small trace recorded on a TPU v5e."""

import os

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def test_busy_union_gaps_and_attribution():
    ops = {"/device:TPU:0": [("a", 10, 30), ("b", 30, 40), ("a", 60, 70),
                             ("c", 95, 120)]}
    host = [("serve.dispatch(x)", 35, 65)]
    r = xplane.reduce_events((0, 100), ops, host)
    assert r.window_s == pytest.approx(100e-9)
    # [10, 40) + [60, 70) + [95, 100)
    assert r.busy_s == pytest.approx(45e-9)
    assert r.devices == 1
    assert r.device_ops == [("a", pytest.approx(30e-9)),
                            ("b", pytest.approx(10e-9)),
                            ("c", pytest.approx(5e-9))]
    assert r.idle_gaps == [("none", pytest.approx(25e-9)),
                           ("serve.dispatch(x)", pytest.approx(20e-9)),
                           ("none", pytest.approx(10e-9))]


def test_busy_is_averaged_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 0, 10)]}
    r = xplane.reduce_events((0, 100), ops, [])
    assert r.busy_s == pytest.approx(30e-9)
    assert r.device_ops == [("a", pytest.approx(30e-9))]


def test_union_merges_touching_and_nested_intervals():
    import numpy as np

    iv = np.array([[5, 9], [0, 3], [3, 4], [6, 7], [10, 12]], float)
    assert xplane.union(iv).tolist() == [[0, 4], [5, 9], [10, 12]]


def test_nested_operations_are_charged_their_self_time():
    ops = [("while", 0, 100), ("body.a", 10, 40), ("body.b", 50, 60),
           ("after", 100, 120)]
    t = xplane.self_times(ops, 0, 110)
    assert t == {"while": 60, "body.a": 30, "body.b": 10, "after": 10}


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e: an IVF-Flat SearchServer (200k x
    128 rows, 256 lists, 16 probes) answering three 512-row requests and
    twenty 1-row requests inside the ``bench.window`` annotation."""
    r = xplane.load(os.path.join(HERE, "data", "ivf_flat_v5e.xplane.pb"))
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.169887639)
    assert r.busy_s == pytest.approx(0.110080995)
    # the 512-row program's slab gather and its scoring lead
    assert r.device_ops[0] == (
        "jit_fn(14876877707800552097)/fusion.24", pytest.approx(0.063532517))
    assert r.device_ops[1][0].endswith("/multiply_reduce_fusion.4")
    assert sum(t for _, t in r.device_ops) <= r.busy_s
    assert len(r.idle_gaps) == 10
    assert r.idle_gaps[0] == ("none", pytest.approx(0.002384266))
    assert {n for n, _ in r.idle_gaps} == {
        "none", "serve.dispatch(ivf_flat,b=1,k=10,lvl=0)",
        "serve.dispatch(ivf_flat,b=512,k=10,lvl=0)"}


def test_idle_and_roofline_shares_by_hand():
    r = xplane.Reduction(window_s=2.0, busy_s=0.5, devices=1,
                         device_ops=[], idle_gaps=[])
    assert xplane.idle_share(r) == pytest.approx(75.0)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # 0.1 s of operations, then 0.2 s of bytes: 0.3 s of 0.5 s busy
    work = [(1e11, 1e7), (1e9, 2e8)]
    assert xplane.roofline_share(r, work, peaks) == pytest.approx(60.0)
    # nothing to read gives nothing, never 0
    assert xplane.idle_share(None) is None
    assert xplane.roofline_share(r, [], peaks) is None
