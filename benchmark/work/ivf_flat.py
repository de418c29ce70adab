"""Least work of one IVF-Flat request, from the problem.

Each query is compared with every centroid and with every stored row of
the lists it probes (2·d operations a pair, over the lists' real sizes).
The bytes are the centroids, the queries, and the rows of the distinct
lists the request probes, each read once.  The probed lists are worked
out here from the index's centroids in float64, not taken from the
program.
"""

import numpy as np


def prepare(index, params, config, queries):
    c = np.asarray(index.centroids, np.float64)
    q = np.asarray(queries, np.float64)
    n_probes = min(int(params.n_probes), len(c))
    probes = np.empty((len(q), n_probes), np.int64)
    for lo in range(0, len(q), 1024):
        qq = q[lo:lo + 1024]
        d = (qq * qq).sum(1)[:, None] - 2.0 * qq @ c.T + (c * c).sum(1)[None]
        probes[lo:lo + 1024] = np.argsort(d, axis=1)[:, :n_probes]
    return {"probes": probes, "counts": np.asarray(index.counts, np.int64),
            "d": int(c.shape[1]), "lists": int(len(c)),
            "itemsize": int(index.data.dtype.itemsize)}


def request(state, pool_idx):
    probes = state["probes"][np.asarray(pool_idx)]
    counts, d, size = state["counts"], state["d"], state["itemsize"]
    rows = len(probes)
    ops = 2.0 * d * (rows * state["lists"] + counts[probes].sum())
    distinct = np.unique(probes)
    nbytes = float((counts[distinct].sum() + state["lists"] + rows)
                   * d * size)
    return ops, nbytes
