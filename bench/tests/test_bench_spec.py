"""The benchmark's files on the CPU: the spec resolves, names keep to their
characters, run.py refuses to run without a TPU, the kernel work counts do
not move with the pad policy, and the plain references agree with an
independent witness."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import deploy, harness, reference, work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    assert c["config"]["name"] == c["cell"]["config"]
    assert c["traffic"]["name"] == c["cell"]["traffic"]
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["traffic"]["end_to_end"] in names
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        assert m["moves"] in names


def test_names_units_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("bench/")
        assert json.loads(path.read_text())["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", SPEC["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 3, p.stderr
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    with pytest.raises(harness.Refused) as e:
        harness.import_program(tmp_path)
    assert e.value.code == 4


def _schedule(pad):
    from repro.core.plan import PlanBuilder
    from repro.sparse.dataset import grid2d
    from repro.sparse.schedule import build_schedule

    plan = PlanBuilder(None).build(grid2d(30, 30, "g"), algorithm="scotch")
    return plan, build_schedule(plan.sym, pad=pad)


def test_work_counts_do_not_move_with_the_pad_policy():
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    plan, pow2 = _schedule("pow2")
    _, mult8 = _schedule("mult8")
    assert {b.M for lv in pow2.buckets for b in lv} != \
        {b.M for lv in mult8.buckets for b in lv}
    counts = plan.sym.counts
    for fn in (lambda c, m, p: work.factor_front(c, m),
               lambda c, m, p: work.sweep_front(p, 1)):
        a = work.total([fn(*f) for f in work.fronts_of(pow2, counts)], peaks)
        b = work.total([fn(*f) for f in work.fronts_of(mult8, counts)],
                       peaks)
        assert a == b
    flops = work.total([work.factor_front(c, m)
                        for c, m, _ in work.fronts_of(pow2, counts)],
                       peaks)[0]
    assert flops == plan.sym.flops


def test_sweep_work_counts_the_true_rhs():
    assert work.sweep_front(16, 1)[0] * 8 == work.sweep_front(16, 8)[0]


def test_reference_symbolic_matches_dense_cholesky():
    rng = np.random.default_rng(3)
    n, u, v = deploy.stencil_edges({"stencil": "5-point", "grid": [6, 7]})
    keep = rng.random(u.shape[0]) > 0.2
    pat = deploy.pattern(n, u[keep], v[keep])
    data = deploy.values(pat, rng, (1.0, 2.0), (0.5, 1.5))
    a = reference.matrix(pat.indptr, pat.indices, data)
    perm = rng.permutation(n)
    parent, counts = reference.symbolic(pat.indptr, pat.indices, perm)
    dense = a.toarray()[np.ix_(perm, perm)]
    lower = np.abs(np.linalg.cholesky(dense)) > 1e-300
    assert np.array_equal(counts, lower.sum(axis=0))
    for j in range(n):
        below = np.nonzero(lower[j + 1:, j])[0]
        assert parent[j] == (j + 1 + below[0] if below.size else -1)


def test_reference_solve_and_residual():
    n, u, v = deploy.stencil_edges({"stencil": "7-point", "grid": [4, 4, 5]})
    pat = deploy.pattern(n, u, v)
    rng = np.random.default_rng(0)
    a = reference.matrix(pat.indptr, pat.indices,
                         deploy.values(pat, rng, (1, 2), (0.5, 1.5)))
    assert np.allclose(a.toarray(), a.toarray().T)
    assert np.all(np.linalg.eigvalsh(a.toarray()) > 0.4)
    b = rng.standard_normal(n)
    x = reference.solve(a, b)
    assert reference.relative_residual(a, x, b) < 1e-13
    assert reference.relative_error(x, np.linalg.solve(a.toarray(), b)) \
        < 1e-12
    assert reference.is_permutation(np.arange(n)[::-1], n)
    assert not reference.is_permutation(np.zeros(n, int), n)


def _stencil_matrix(stencil, grid, seed=4, shift=(0.5, 1.5)):
    n, u, v = deploy.stencil_edges({"stencil": stencil, "grid": grid})
    pat = deploy.pattern(n, u, v)
    rng = np.random.default_rng(seed)
    return reference.matrix(pat.indptr, pat.indices,
                            deploy.values(pat, rng, (1, 2), shift)), rng


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("stencil, grid", [("5-point", [17, 13]),
                                           ("7-point", [7, 6, 5]),
                                           ("27-point", [7, 6, 5])])
def test_reference_solve_is_certified_and_agrees_with_superlu(stencil, grid,
                                                              k):
    import scipy.sparse.linalg as spla

    a, rng = _stencil_matrix(stencil, grid)
    b = rng.standard_normal((a.shape[0], k) if k > 1 else a.shape[0])
    x = reference.solve(a, b)
    assert x.shape == b.shape
    g = reference.gershgorin_min(a)
    xs = spla.spsolve(a.tocsc(), b).reshape(b.shape)
    for j in range(k):
        xj, bj = x.reshape(-1, k)[:, j], b.reshape(-1, k)[:, j]
        assert np.linalg.norm(bj - a @ xj) / g <= 1e-12 * np.linalg.norm(xj)
        assert reference.relative_error(xj, xs.reshape(-1, k)[:, j]) <= 1e-12


def test_reference_solve_of_a_zero_rhs_is_zero():
    a, _ = _stencil_matrix("7-point", [4, 4, 4])
    assert not np.any(reference.solve(a, np.zeros(a.shape[0])))


def _no_certificate():
    """The Laplacian with no diagonal shift: Gershgorin's bound is 0."""
    return _stencil_matrix("7-point", [4, 4, 4], shift=(0.0, 0.0))[0]


def _not_symmetric():
    a = _stencil_matrix("7-point", [4, 4, 4])[0].tolil()
    a[0, 1] *= 0.5
    return a.tocsr()


@pytest.mark.parametrize("make", [_no_certificate, _not_symmetric])
def test_reference_solve_refuses_what_it_cannot_certify(make):
    a = make()
    with pytest.raises(ValueError, match="reference solve"):
        reference.solve(a, np.ones(a.shape[0]))
