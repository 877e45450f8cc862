"""Matrices of a deployment, made from the seed with NumPy and SciPy alone.

A configuration file names a stencil and a grid; the traffic mix draws the
coefficients. A stencil is its list of neighbour offsets, one per edge
direction, each lexicographically positive (its first nonzero entry is +1),
so the neighbour has the larger row-major index. Every matrix is a weighted
graph Laplacian of the stencil's grid plus a positive diagonal shift:
off-diagonal entry ``-w`` per edge, diagonal the row's weight sum plus the
shift. That is symmetric, positive definite and diagonally dominant, so
every factorization succeeds.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np
import scipy.sparse as sp

__all__ = ["Pattern", "stencil_edges", "pattern", "values"]

#: stencil name -> its neighbour offsets; a grid has as many dimensions as
#: an offset has entries
STENCILS = {
    "5-point": [(1, 0), (0, 1)],
    "7-point": [(1, 0, 0), (0, 1, 0), (0, 0, 1)],
    # HPCG's operator: the 13 positive offsets of the 3 x 3 x 3 box
    "27-point": [o for o in itertools.product((-1, 0, 1), repeat=3)
                 if o > (0, 0, 0)],
}


@dataclass
class Pattern:
    """A symmetric CSR pattern with diagonal, and where each edge weight and
    each diagonal shift lands in the data array."""

    n: int
    indptr: np.ndarray        # int32
    indices: np.ndarray       # int32, sorted per row
    src: np.ndarray           # data[i] comes from source entry src[i]
    u: np.ndarray             # edge endpoints, u < v
    v: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


def stencil_edges(config: dict) -> tuple:
    """(n, u, v): one block of edges per stencil offset, in the stencil's
    order, each edge ``u < v``."""
    grid = tuple(int(g) for g in config["grid"])
    offsets = STENCILS.get(config["stencil"])
    if offsets is None or len(offsets[0]) != len(grid):
        raise ValueError(f"stencil {config['stencil']!r} does not fit grid "
                         f"{grid}")
    idx = np.arange(int(np.prod(grid)), dtype=np.int64).reshape(grid)
    # an offset entry -> the slices of the edges' u and v ends on its axis
    ends = {-1: (slice(1, None), slice(None, -1)), 0: (slice(None),) * 2,
            1: (slice(None, -1), slice(1, None))}
    us, vs = [], []
    for off in offsets:
        lo, hi = zip(*(ends[d] for d in off))
        us.append(idx[lo].ravel())
        vs.append(idx[hi].ravel())
    return idx.size, np.concatenate(us), np.concatenate(vs)


def pattern(n: int, u: np.ndarray, v: np.ndarray) -> Pattern:
    """The CSR pattern of the Laplacian on edges (u, v), with a map from
    the source entries (edge e as (u, v), then as (v, u), then the
    diagonal) to the CSR data positions."""
    m = u.shape[0]
    rows = np.concatenate([u, v, np.arange(n)])
    cols = np.concatenate([v, u, np.arange(n)])
    code = np.arange(1, 2 * m + n + 1, dtype=np.float64)
    a = sp.csr_matrix((code, (rows, cols)), shape=(n, n))
    a.sort_indices()
    return Pattern(n, a.indptr.astype(np.int32), a.indices.astype(np.int32),
                   a.data.astype(np.int64) - 1, u, v)


def values(pat: Pattern, rng: np.random.Generator, weight, shift
           ) -> np.ndarray:
    """One coefficient set on ``pat``: edge weights uniform in ``weight``,
    diagonal = row weight sum + a shift uniform in ``shift``."""
    m = pat.u.shape[0]
    w = rng.uniform(weight[0], weight[1], m)
    diag = rng.uniform(shift[0], shift[1], pat.n)
    np.add.at(diag, pat.u, w)
    np.add.at(diag, pat.v, w)
    src = np.concatenate([-w, -w, diag])
    return src[pat.src]
