"""The work functions against hand-counted cases."""

import types

import numpy as np

from benchmark import spec


def _work(family):
    return spec.load_module(spec.ROOT, "work", family)


def test_brute_force_work_is_every_pair_and_one_read_of_the_base():
    w = _work("brute_force")
    base = np.zeros((1000, 128), np.float32)
    state = w.prepare(base, None, {}, None)
    ops, nbytes = w.request(state, np.arange(4))
    assert ops == 2 * 4 * 1000 * 128
    assert nbytes == (1000 + 4) * 128 * 4


def test_ivf_flat_work_counts_probed_rows_and_distinct_lists():
    w = _work("ivf_flat")
    index = types.SimpleNamespace(
        centroids=np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0]]),
        counts=np.array([5, 7, 2]),
        data=np.zeros((3, 8, 2), np.float32))
    params = types.SimpleNamespace(n_probes=2)
    queries = np.array([[1.0, 0.0], [0.0, 9.0]])
    state = w.prepare(index, params, {}, queries)
    # query 0 probes lists 0 and 1 (12 rows), query 1 lists 2 and 0 (7)
    assert sorted(state["probes"][0]) == [0, 1]
    assert sorted(state["probes"][1]) == [0, 2]
    ops, nbytes = w.request(state, np.array([0, 1]))
    assert ops == 2 * 2 * (2 * 3 + 12 + 7)
    # lists 0, 1, 2 read once (14 rows), 3 centroids, 2 queries; 2 floats
    assert nbytes == (14 + 3 + 2) * 2 * 4
    ops, nbytes = w.request(state, np.array([0]))
    assert ops == 2 * 2 * (3 + 12)
    assert nbytes == (12 + 3 + 1) * 2 * 4
