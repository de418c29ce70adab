"""A plain float64 NumPy reference of IVF-PQ's stages, independent of the
code under test.

Given an index's trained coarse centroids ``[L, d]`` and per-subspace
codebooks ``[m, c, d/m]``, it computes what each stage of the build and
the search should give:

- :func:`nearest_lists`: each row's nearest list;
- :func:`encode`: each sub-code, the nearest codeword of the row's
  residual in each subspace;
- :func:`decode`: the decoded ``x̂ = centroid + r̂`` and ``‖x̂‖²``;
- :func:`adc`: the distance of a query to a stored row, ``‖q − x̂‖²``.

:func:`code_agreement` compares a program's sub-codes with the
reference's, counting a tie at float32 resolution as agreement: the
program computes distances in float32, so where two codewords lie within
float32 rounding of each other either is a right answer.

Everything is computed in float64 a block of rows at a time.
"""

from __future__ import annotations

import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)
BLOCK = 16384


def _f64(a):
    return np.asarray(a, np.float64)


def sq_dists(x, y):
    """``[n, k]`` squared L2 distances between the rows of ``x`` and ``y``."""
    x, y = _f64(x), _f64(y)
    d = (x * x).sum(1)[:, None] - 2.0 * x @ y.T + (y * y).sum(1)[None, :]
    return np.maximum(d, 0.0)


def nearest_lists(x, centroids):
    """``(lists [n], d_min [n])``: each row's nearest centroid and its
    squared distance."""
    lists, dmin = [], []
    for lo in range(0, len(x), BLOCK):
        d = sq_dists(x[lo:lo + BLOCK], centroids)
        i = d.argmin(1)
        lists.append(i)
        dmin.append(d[np.arange(len(i)), i])
    return np.concatenate(lists), np.concatenate(dmin)


def _subspace_dists(residuals, codebooks):
    """``[n, m, c]`` squared distances of each residual's subspace slice
    to each codeword of that subspace."""
    m, c, ds = codebooks.shape
    r = _f64(residuals).reshape(len(residuals), m, ds)
    cb = _f64(codebooks)
    return np.maximum((r * r).sum(2)[:, :, None]
                      - 2.0 * np.einsum("nmd,mcd->nmc", r, cb)
                      + (cb * cb).sum(2)[None], 0.0)


def encode(residuals, codebooks):
    """``codes [n, m]``: the nearest codeword of each subspace slice."""
    return np.concatenate([
        _subspace_dists(residuals[lo:lo + BLOCK], codebooks).argmin(2)
        for lo in range(0, len(residuals), BLOCK)])


def code_agreement(residuals, codebooks, codes):
    """``(exact, with_ties)``: the share of sub-codes equal to the
    reference's, and the share whose codeword is as near as the
    reference's within float32 rounding of ``‖r_j‖² + ‖cb_e‖²``."""
    m, c, ds = codebooks.shape
    codes = np.asarray(codes, np.int64)
    cb2 = (_f64(codebooks) ** 2).sum(2)
    same = tie = 0
    for lo in range(0, len(residuals), BLOCK):
        r = residuals[lo:lo + BLOCK]
        got = codes[lo:lo + BLOCK]
        d = _subspace_dists(r, codebooks)
        best = d.argmin(2)
        d_got = np.take_along_axis(d, got[:, :, None], 2)[:, :, 0]
        d_best = d.min(2)
        r2 = (_f64(r).reshape(len(r), m, ds) ** 2).sum(2)
        scale = r2 + cb2[np.arange(m)[None, :], got]
        same += int((got == best).sum())
        tie += int(((got == best) | (d_got - d_best <= 4 * F32_EPS * scale))
                   .sum())
    total = codes.size
    return same / total, tie / total


def decode(codes, lists, centroids, codebooks):
    """``(x̂ [n, d], ‖x̂‖² [n])`` of stored rows: the list's centroid plus
    each subspace's codeword."""
    m, c, ds = codebooks.shape
    codes = np.asarray(codes, np.int64)
    r = _f64(codebooks)[np.arange(m)[None, :], codes].reshape(len(codes),
                                                              m * ds)
    x = _f64(centroids)[np.asarray(lists, np.int64)] + r
    return x, (x * x).sum(1)


def adc(q, codes, lists, centroids, codebooks):
    """``[nq, n]`` asymmetric distances ``‖q − x̂‖²`` of every query to
    every given stored row."""
    x, _ = decode(codes, lists, centroids, codebooks)
    return sq_dists(q, x)
