"""The DEEP-10M-class IVF-PQ cell rehearsed on the CPU at a tiny size
through the harness's own run (the look for a chip skipped), the
comparison shown to fail on an answer altered under the server, and the
cell's work counted by hand."""

import contextlib
import types

import jax.numpy as jnp
import numpy as np

from benchmark import harness, spec
from benchmark.tests import tiny

WORKLOAD = "deep10m-ivf_pq.batch"
E2E = {"qps", "recall_at_10", "setup_s"}


def _cell():
    cell = tiny.shrink(spec.load_cell(WORKLOAD))
    assert cell.config["index"]["n_lists"] == 64
    return cell


def _run(trace=False):
    return harness.run(WORKLOAD, tiny.SEED, 1.0, trace, require_chip=False,
                       cell=_cell())


def test_cell_runs_correct_on_cpu():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == E2E
    assert r["checks"]["dist_gap_ulps"]["value"] <= 16


def test_traced_run_reports_no_device_numbers_on_cpu():
    r = _run(trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"] == {}


@contextlib.contextmanager
def _altered():
    import raft_tpu.serve.server as server_mod

    real = server_mod.make_searcher

    def make(index, k, *a, **kw):
        fn, ops = real(index, k, *a, **kw)

        def altered(q, *operands):
            d, i = fn(q, *operands)
            return d, jnp.where(i >= 0, i + 1, i)
        return altered, ops

    server_mod.make_searcher = make
    try:
        yield
    finally:
        server_mod.make_searcher = real


def test_answer_altered_under_the_server_comes_out_not_correct():
    with _altered():
        r = _run()
    assert not r["correct"], r["checks"]
    assert r["checks"]["dist_gap_ulps"]["value"] > 16


def test_work_counts_tables_probed_codes_and_rerank():
    w = spec.load_module(spec.ROOT, "work", "ivf_pq")
    index = types.SimpleNamespace(
        centroids=np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0]]),
        counts=np.array([5, 7, 2]),
        codebooks=np.zeros((2, 16, 1), np.float32))
    view = types.SimpleNamespace(index=index, ratio=4)
    params = types.SimpleNamespace(n_probes=2)
    queries = np.array([[1.0, 0.0], [0.0, 9.0]])
    state = w.prepare(view, params, {"data": {"k": 3}}, queries)
    assert sorted(state["probes"][0]) == [0, 1]
    ops, nbytes = w.request(state, np.array([0, 1]))
    # per query: 2·d·(3 centroids + 16 codewords) + 2·d·12 re-ranked;
    # per probed row: pq_dim = 2 (12 rows for query 0, 7 for query 1)
    assert ops == 2 * (2 * 2 * (3 + 16) + 2 * 2 * 12) + 2 * (12 + 7)
    # centroids, codebooks, queries and re-ranked rows at 4·d bytes;
    # the codes of lists 0, 1, 2 (14 rows) once at pq_dim bytes
    assert nbytes == 4 * 2 * (3 + 16 + 2 + 2 * 12) + 2 * 14
