"""Plain references that decide ``correct``; NumPy and SciPy only.

Nothing here imports the program or takes anything it made: a solve is
checked against SciPy's SuperLU in fp64 and by its own fp64 residual, and a
plan's symbolic factor against an elimination tree and column counts worked
out here from the pattern and the plan's permutation.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla

__all__ = ["matrix", "relative_residual", "solve", "relative_error",
           "is_permutation", "symbolic", "min_degree_nnz_l"]


def matrix(indptr, indices, data) -> sp.csr_matrix:
    n = len(indptr) - 1
    return sp.csr_matrix((np.asarray(data, np.float64), indices, indptr),
                         shape=(n, n))


def relative_residual(a: sp.csr_matrix, x: np.ndarray, b: np.ndarray
                      ) -> float:
    """||b - A x|| / ||b|| in fp64."""
    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))


def solve(a: sp.csr_matrix, b: np.ndarray) -> np.ndarray:
    """x = A^-1 b by SuperLU in fp64 (its own COLAMD ordering)."""
    return spla.spsolve(a.tocsc(), b)


def relative_error(x: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64) - ref)
                 / np.linalg.norm(ref))


def is_permutation(perm, n: int) -> bool:
    perm = np.asarray(perm)
    return (perm.shape == (n,) and perm.dtype.kind in "iu"
            and np.array_equal(np.sort(perm), np.arange(n)))


def symbolic(indptr, indices, perm) -> tuple:
    """(parent, counts) of the Cholesky factor of ``P A P^T``, where
    ``perm[new] = old``: the elimination tree (-1 at roots) and the number
    of entries of each column of L, diagonal included.

    Liu's elimination tree with path compression, then each row's subtree
    walked up the tree from the row's entries, one count per first visit.
    """
    n = len(perm)
    perm = np.asarray(perm, np.int64)
    iperm = np.empty(n, np.int64)
    iperm[perm] = np.arange(n)
    lower = []
    for i in range(n):
        old = perm[i]
        cols = iperm[indices[indptr[old]:indptr[old + 1]]]
        lower.append(cols[cols < i].tolist())
    parent = [-1] * n
    ancestor = [-1] * n
    for i in range(n):
        for j in lower[i]:
            while j != -1 and j < i:
                nxt = ancestor[j]
                ancestor[j] = i
                if nxt == -1:
                    parent[j] = i
                j = nxt
    counts = [1] * n
    mark = [-1] * n
    for i in range(n):
        mark[i] = i
        for j in lower[i]:
            while j != -1 and mark[j] != i:
                mark[j] = i
                counts[j] += 1
                j = parent[j]
    return np.asarray(parent, np.int64), np.asarray(counts, np.int64)


def min_degree_nnz_l(a: sp.csr_matrix) -> int:
    """nnz(L) of the Cholesky factor under SuperLU's multiple minimum
    degree ordering of ``A + A^T``: the fill a fill-reducing ordering is
    held against."""
    lu = spla.splu(a.tocsc(), permc_spec="MMD_AT_PLUS_A",
                   diag_pivot_thresh=0.0, options=dict(SymmetricMode=True))
    perm = np.empty_like(lu.perm_c)         # perm_c[old] = new
    perm[lu.perm_c] = np.arange(perm.size)
    _, counts = symbolic(a.indptr, a.indices, perm)
    return int(counts.sum())
