"""Stencils as neighbour offsets: the committed configurations' edges stay
bit for bit what they were, and every stencil's edges are those a
brute-force scan of the grid finds."""
import hashlib
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import deploy, reference  # noqa: E402

#: sha256 of (n, u, v) as int64 bytes, from the axis-only edge loop
#: these configurations were first measured with
PINNED = {
    "poisson2d": (22500, "9bd59652407264ba32c87815a8f8a4e8"
                         "390152d496341ca8c9c7959fff8619b7"),
    "stencil3d": (13824, "e0fee44a430fc13e7baf9557211d37f6"
                         "8fa8943cd66af1c55ec20a97dbf69cdc"),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_committed_configurations_keep_their_edges(name):
    config = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                        .read_text())
    n, u, v = deploy.stencil_edges(config)
    h = hashlib.sha256()
    for part in (np.int64(n), u.astype(np.int64), v.astype(np.int64)):
        h.update(np.ascontiguousarray(part).tobytes())
    assert (n, h.hexdigest()) == PINNED[name]


def _scan(grid, near):
    """Every pair of grid points ``near`` each other, as (low, high) flat
    indices, found by looking at all pairs."""
    points = list(itertools.product(*(range(g) for g in grid)))
    flat = {p: i for i, p in enumerate(points)}
    return {(flat[p], flat[q]) for p, q in itertools.combinations(points, 2)
            if near(np.subtract(q, p))}


@pytest.mark.parametrize("stencil, grid, near", [
    ("5-point", (4, 7), lambda d: np.abs(d).sum() == 1),
    ("7-point", (4, 5, 6), lambda d: np.abs(d).sum() == 1),
    ("27-point", (4, 5, 6), lambda d: np.abs(d).max() == 1),
])
def test_edges_are_the_grid_neighbours(stencil, grid, near):
    n, u, v = deploy.stencil_edges({"stencil": stencil, "grid": list(grid)})
    assert n == int(np.prod(grid))
    assert np.all(u < v)
    edges = list(zip(u.tolist(), v.tolist()))
    assert len(edges) == len(set(edges))
    assert set(edges) == _scan(grid, near)


def test_27_point_has_the_13_positive_offsets_of_the_box():
    offsets = deploy.STENCILS["27-point"]
    assert len(offsets) == len(set(offsets)) == 13
    assert {tuple(-x for x in o) for o in offsets} | set(offsets) == \
        set(itertools.product((-1, 0, 1), repeat=3)) - {(0, 0, 0)}


@pytest.mark.parametrize("stencil, grid", [("5-point", [4, 4, 4]),
                                           ("27-point", [5, 5]),
                                           ("9-point", [5, 5])])
def test_a_stencil_off_its_grid_is_refused(stencil, grid):
    with pytest.raises(ValueError):
        deploy.stencil_edges({"stencil": stencil, "grid": grid})


def test_27_point_matrix_is_spd_and_diagonally_dominant():
    n, u, v = deploy.stencil_edges({"stencil": "27-point",
                                    "grid": [3, 4, 5]})
    pat = deploy.pattern(n, u, v)
    rng = np.random.default_rng(1)
    a = reference.matrix(pat.indptr, pat.indices,
                         deploy.values(pat, rng, (1, 2), (0.5, 1.5)))
    dense = a.toarray()
    assert np.array_equal(dense, dense.T)
    assert np.count_nonzero(dense[1 * 20 + 1 * 5 + 2]) == 27
    assert reference.gershgorin_min(a) >= 0.5
    assert np.linalg.eigvalsh(dense).min() > 0.5
