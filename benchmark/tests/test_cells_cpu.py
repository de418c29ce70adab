"""Each cell rehearsed on the CPU at a tiny size through the harness's
own run (the look for a chip skipped), and the comparison shown to fail
on the control and on a timed path broken underneath the server."""

import contextlib

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import control, harness
from benchmark.tests import tiny

E2E = {"qps", "recall_at_10", "setup_s"}


def _run(workload, trace=False, seconds=1.0):
    return harness.run(workload, tiny.SEED, seconds, trace,
                       require_chip=False, cell=tiny.cell(workload))


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
def test_cell_runs_correct_on_cpu(workload):
    r = _run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == E2E
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


def test_traced_run_reports_no_device_numbers_on_cpu():
    r = _run("sift1m-ivf_flat.batch", trace=True)
    assert r["correct"], r["checks"]
    # the CPU trace holds no TPU plane: no idle share, roofline or busy time
    assert r["metrics"] == {}
    assert "busy_s" not in r["device"]


@contextlib.contextmanager
def _broken(fault):
    """The program's searcher with one fault planted in its answers."""
    import raft_tpu.serve.server as server_mod

    real = server_mod.make_searcher

    def make(index, k, *a, **kw):
        fn, ops = real(index, k, *a, **kw)

        def broken(q, *operands):
            d, i = fn(q, *operands)
            if fault == "answer_altered":
                return d, jnp.where(i >= 0, i + 1, i)
            # the second half of the batch left out, answered from the first
            src = jnp.arange(q.shape[0]) % ((q.shape[0] + 1) // 2)
            return d[src], i[src]
        return broken, ops

    server_mod.make_searcher = make
    try:
        yield
    finally:
        server_mod.make_searcher = real


def _over(checks):
    return {n for n, c in checks.items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_left_out"])
def test_fault_under_the_server_comes_out_not_correct(workload, fault):
    with _broken(fault):
        r = _run(workload)
    assert not r["correct"], r["checks"]
    assert "dist_gap_ulps" in _over(r["checks"])


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
def test_reference_one_precision_step_down_comes_out_not_correct(workload):
    lines = control.run_control(workload, [tiny.SEED], 1.0,
                                "reference_high", require_chip=False,
                                cell_fn=tiny.shrink)
    assert not lines[0]["correct"], lines[0]["checks"]
    assert _over(lines[0]["checks"]) == {"dist_gap_ulps"}


@pytest.mark.parametrize("workload", tiny.WORKLOADS)
def test_half_scan_comes_out_not_correct(workload):
    """Half of the base (brute force) or of the probes (IVF-Flat) searched:
    well-formed answers with exact distances, but not the nearest."""
    lines = control.run_control(workload, [tiny.SEED], 1.0, "half_scan",
                                require_chip=False, cell_fn=tiny.shrink)
    assert not lines[0]["correct"], lines[0]["checks"]
    assert _over(lines[0]["checks"]) == {"recall_miss"}


def test_recall_and_bad_rows_by_hand():
    from benchmark import reference

    ids = np.array([[1, 2, 3], [4, 5, 6]])
    ref = np.array([[3, 2, 9], [7, 8, 9]])
    assert reference.recall(ids, ref) == pytest.approx(2 / 6)
    d = np.array([[0.0, 1.0, 2.0], [0.0, 1.0, 2.0], [0.0, 2.0, 1.0],
                  [0.0, np.inf, 3.0]])
    i = np.array([[0, 1, 2], [0, 0, 2], [0, 1, 2], [0, 1, 2]])
    assert reference.bad_rows(d, i, n_base=3) == 3
    assert reference.bad_rows(d[:1], np.array([[0, 1, 3]]), n_base=3) == 1
