"""Plain references that decide ``correct``; NumPy and SciPy only.

Nothing here imports the program or takes anything it made: a solve is
checked by its own fp64 residual and against an fp64 conjugate-gradient
solution whose error is certified from the matrix itself, and a plan's
symbolic factor against an elimination tree and column counts worked out
here from the pattern and the plan's permutation.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla

__all__ = ["matrix", "relative_residual", "gershgorin_min", "solve",
           "relative_error", "is_permutation", "symbolic",
           "min_degree_nnz_l"]

#: the largest certified ``||x_ref - x*|| / ||x_ref||`` a reference solution
#: may carry: 1,000 times below the forward-error limit of ``correct``
CERTIFIED_MAX = 1e-12
#: passes of conjugate gradients, each on the residual the last one left
PASSES = 2


def matrix(indptr, indices, data) -> sp.csr_matrix:
    n = len(indptr) - 1
    return sp.csr_matrix((np.asarray(data, np.float64), indices, indptr),
                         shape=(n, n))


def relative_residual(a: sp.csr_matrix, x: np.ndarray, b: np.ndarray
                      ) -> float:
    """||b - A x|| / ||b|| in fp64."""
    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))


def gershgorin_min(a: sp.csr_matrix) -> float:
    """min_i (a_ii - sum_{j != i} |a_ij|): for a symmetric ``a``, a lower
    bound on its smallest eigenvalue (Gershgorin)."""
    d = a.diagonal()
    off = np.asarray(abs(a).sum(axis=1)).ravel() - np.abs(d)
    return float(np.min(d - off))


def solve(a: sp.csr_matrix, b: np.ndarray) -> np.ndarray:
    """x = A^-1 b in fp64 by Jacobi-preconditioned conjugate gradients, one
    column of ``b`` at a time, for a symmetric ``a`` that is strictly
    diagonally dominant with a positive diagonal.

    Two passes, the second on the residual the first left, and then each
    column is certified: with g = :func:`gershgorin_min` > 0,
    ``||x - x*|| <= ||b - A x|| / g``, and that bound over ``||x||`` must be
    at most :data:`CERTIFIED_MAX`, else this raises with the reason.
    """
    b = np.asarray(b, np.float64)
    if b.ndim == 2:
        return np.column_stack([solve(a, b[:, j]) for j in range(b.shape[1])])
    if abs(a - a.T).max() != 0:
        raise ValueError("reference solve: the matrix is not symmetric")
    g = gershgorin_min(a)
    if not g > 0:
        raise ValueError(f"reference solve: no error certificate, the "
                         f"Gershgorin bound on the smallest eigenvalue is "
                         f"{g!r} <= 0")
    jacobi = sp.diags(1.0 / a.diagonal())
    x = np.zeros_like(b)
    for _ in range(PASSES):
        dx, _ = spla.cg(a, b - a @ x, rtol=1e-14, atol=0.0, M=jacobi)
        x = x + dx
    bound = np.linalg.norm(b - a @ x) / g
    if not bound <= CERTIFIED_MAX * np.linalg.norm(x):
        raise ValueError(f"reference solve: certified error {bound!r} over "
                         f"||x|| = {np.linalg.norm(x)!r}, above "
                         f"{CERTIFIED_MAX} of it")
    return x


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
