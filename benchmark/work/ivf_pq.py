"""Least work of one IVF-PQ request with exact re-ranking, from the
problem, whatever implements it.

Operations: each query is compared with every centroid (2·d a pair),
builds its lookup tables against every codeword (2·d per codeword of the
2^bits in each subspace, 2·d·2^bits in all), adds ``pq_dim`` table entries
for every stored row of the lists it probes (over the lists' real sizes),
and re-ranks ``k·ratio`` candidates exactly (2·d each).  Bytes: the
centroids and codebooks once, the codes of the distinct lists the
request probes once (``pq_dim`` bytes a row), ``4·d`` for each re-ranked
candidate's stored vector, and the queries.  The probed lists are worked
out here from the index's centroids in float64, not taken from the
program.
"""

import numpy as np


def prepare(view, params, config, queries):
    index = view.index
    c = np.asarray(index.centroids, np.float64)
    q = np.asarray(queries, np.float64)
    n_probes = min(int(params.n_probes), len(c))
    probes = np.empty((len(q), n_probes), np.int64)
    for lo in range(0, len(q), 1024):
        qq = q[lo:lo + 1024]
        d = (qq * qq).sum(1)[:, None] - 2.0 * qq @ c.T + (c * c).sum(1)[None]
        probes[lo:lo + 1024] = np.argsort(d, axis=1)[:, :n_probes]
    m, book, _ = index.codebooks.shape
    return {"probes": probes, "counts": np.asarray(index.counts, np.int64),
            "d": int(c.shape[1]), "lists": int(len(c)), "pq_dim": int(m),
            "book": int(book),
            "candidates": int(config["data"]["k"]) * int(view.ratio)}


def request(state, pool_idx):
    probes = state["probes"][np.asarray(pool_idx)]
    counts, d, m = state["counts"], state["d"], state["pq_dim"]
    rows, cand, lists, book = (len(probes), state["candidates"],
                               state["lists"], state["book"])
    ops = (rows * (2.0 * d * (lists + book) + 2.0 * d * cand)
           + float(m) * counts[probes].sum())
    distinct = np.unique(probes)
    nbytes = (4.0 * d * (lists + book + rows + rows * cand)
              + float(m) * counts[distinct].sum())
    return ops, nbytes
