"""Operations and bytes of the solver's kernels, counted from the algorithm.

A front holds pivot columns ``j`` of the Cholesky factor L, each with
``c_j`` entries below the diagonal, in a dense symmetric ``m x m`` matrix.
Factoring column ``j`` takes ``1 + c_j + c_j (c_j + 1)`` operations (square
root, scaling, rank-1 update; a multiply-add is two), the same count as the
symbolic analysis', so the fronts' operations add up to the exact Cholesky
work whatever the amalgamation pads in. The front is read and written once,
its lower triangle in f32. A triangular sweep over a front solves its
``p x p`` pivot block against ``k`` right-hand sides (``p^2 k``
operations), reading the triangle once and the RHS slab in and out.

Every count uses the true sizes and the caller's true RHS count, never the
padded bucket shapes the kernels run at, so a change to the pad policy or
to a kernel leaves these numbers where they are. The least time of a front
is the larger of its operations over the peak rate and its bytes over the
peak bandwidth; a kernel's least time is the sum over its fronts.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

__all__ = ["F32", "factor_front", "sweep_front", "total", "fronts_of"]

F32 = 4  # bytes of one float32


def _tri(p: int) -> int:
    return p * (p + 1) // 2


def factor_front(counts, m: int) -> Tuple[int, int]:
    """(operations, bytes) of factoring a front of ``m`` rows whose pivot
    columns have ``counts`` entries each in L, diagonal included."""
    c = np.asarray(counts, np.int64) - 1
    flops = int((1 + c + c * (c + 1)).sum())
    nbytes = 2 * F32 * _tri(int(m))          # lower triangle in, then out
    return flops, nbytes


def sweep_front(p: int, k: int) -> Tuple[int, int]:
    """(operations, bytes) of one triangular solve of a front's ``p x p``
    pivot block against ``k`` right-hand sides (one direction)."""
    flops = p * p * k
    nbytes = F32 * (_tri(p) + 2 * p * k)    # triangle in, RHS slab in + out
    return flops, nbytes


def total(works: Iterable[Tuple[int, int]], peaks: dict
          ) -> Tuple[int, int, float]:
    """(operations, bytes, least seconds) of a list of per-front works;
    ``peaks`` holds ``flops_per_s`` and ``bytes_per_s``."""
    flops = nbytes = 0
    least = 0.0
    for f, b in works:
        flops += f
        nbytes += b
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
    return flops, nbytes, least


def fronts_of(schedule, counts) -> list:
    """(pivot column counts, m, p) of every front of a level schedule
    (``.fronts`` with ``c0``/``c1``/``m``), given L's column counts."""
    counts = np.asarray(counts)
    return [(counts[fp.c0:fp.c1], int(fp.m), int(fp.c1 - fp.c0))
            for fp in schedule.fronts]
