"""Least work of one brute-force request, from the problem: every query
row is compared with every base row (2·d operations a pair), and the
stored base is read once, with the queries."""


def prepare(index, params, config, queries):
    n, d = index.shape
    return {"n": int(n), "d": int(d), "itemsize": int(index.dtype.itemsize)}


def request(state, pool_idx):
    rows, n, d = len(pool_idx), state["n"], state["d"]
    ops = 2.0 * rows * n * d
    nbytes = float((n + rows) * d * state["itemsize"])
    return ops, nbytes
