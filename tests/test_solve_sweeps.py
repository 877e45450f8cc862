"""Device-resident triangular sweeps: the tri-solve kernel, multi-RHS
solve parity across sweep modes, on-device refinement edge cases, the
sweep knobs through execute_plan / EngineConfig, and the extended
SolvePolicy persistence."""
import dataclasses
import json
import os

import numpy as np
import pytest
import scipy.linalg

from repro.sparse.csr import make_spd
from repro.sparse.dataset import block_arrow, grid2d, scalefree
from repro.sparse.multifrontal import (multifrontal_cholesky,
                                       multifrontal_solve)
from repro.sparse.symbolic import symbolic_cholesky


@pytest.fixture(scope="module")
def spd_grid():
    return make_spd(grid2d(12, 12, "g12"))


@pytest.fixture(scope="module")
def factored(spd_grid):
    return multifrontal_cholesky(spd_grid, backend="pipelined")


# -- batched triangular-solve kernel ------------------------------------------

@pytest.mark.parametrize("lower", [True, False])
def test_tri_solve_batch_matches_scipy(lower):
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    B, P, K = 3, 16, 5
    l = np.tril(rng.standard_normal((B, P, P))).astype(np.float32)
    l += 4 * np.eye(P, dtype=np.float32)  # well-conditioned
    x = rng.standard_normal((B, P, K)).astype(np.float32)
    got = np.asarray(ops.tri_solve_batch(l, x, lower=lower))
    for i in range(B):
        ref = scipy.linalg.solve_triangular(
            l[i] if lower else l[i].T, x[i], lower=lower)
        np.testing.assert_allclose(got[i], ref, rtol=1e-4, atol=1e-4)


def test_tri_solve_batch_rhs_tile_padding():
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    l = np.tril(rng.standard_normal((2, 8, 8))).astype(np.float32)
    l += 4 * np.eye(8, dtype=np.float32)
    x = rng.standard_normal((2, 8, 3)).astype(np.float32)  # 3 % rt != 0
    base = np.asarray(ops.tri_solve_batch(l, x))
    tiled = np.asarray(ops.tri_solve_batch(l, x, rt=2))
    assert tiled.shape == x.shape
    np.testing.assert_allclose(tiled, base, rtol=1e-5, atol=1e-6)


# -- sweep-mode parity (single and multi-RHS) ---------------------------------

@pytest.mark.parametrize("k", [1, 3, 8])
def test_device_sweeps_match_sequential_multi_rhs(factored, spd_grid, k):
    rng = np.random.default_rng(2)
    B = rng.standard_normal((spd_grid.n, k))
    xs = multifrontal_solve(factored, B, mode="seq")
    xd = multifrontal_solve(factored, B, mode="device")
    assert xd.shape == B.shape
    # f32 sweeps against the fp64 reference
    np.testing.assert_allclose(xd, xs, rtol=5e-4, atol=5e-5)


def test_level_sweeps_match_sequential_multi_rhs(factored, spd_grid):
    rng = np.random.default_rng(3)
    B = rng.standard_normal((spd_grid.n, 4))
    xs = multifrontal_solve(factored, B, mode="seq")
    xl = multifrontal_solve(factored, B, mode="level")
    np.testing.assert_allclose(xl, xs, rtol=1e-12, atol=1e-12)


def test_multi_rhs_columns_match_single_solves(factored, spd_grid):
    rng = np.random.default_rng(4)
    B = rng.standard_normal((spd_grid.n, 3))
    X = multifrontal_solve(factored, B, mode="device")
    for j in range(3):
        xj = multifrontal_solve(factored, B[:, j], mode="device")
        assert xj.ndim == 1
        np.testing.assert_allclose(X[:, j], xj, rtol=1e-5, atol=1e-6)


def test_device_sweeps_on_host_factor(spd_grid):
    # a numpy-backend (fp64 host) factor uploads its sweeps on first use
    f = multifrontal_cholesky(spd_grid, backend="numpy")
    rng = np.random.default_rng(5)
    b = rng.standard_normal(spd_grid.n)
    xs = multifrontal_solve(f, b, mode="seq")
    xd = multifrontal_solve(f, b, mode="device")
    np.testing.assert_allclose(xd, xs, rtol=5e-4, atol=5e-5)


def test_device_sweep_knobs_change_nothing_numerically(factored, spd_grid):
    rng = np.random.default_rng(6)
    B = rng.standard_normal((spd_grid.n, 5))
    base = multifrontal_solve(factored, B, mode="device")
    knobbed = multifrontal_solve(factored, B, mode="device",
                                 sweep_bs=8, rt=2)
    np.testing.assert_allclose(knobbed, base, rtol=1e-5, atol=1e-6)


# -- device-resident refinement -----------------------------------------------

def test_refine_device_reaches_fp64_floor(factored, spd_grid):
    from repro.sparse.refine import refine_solve_device

    rng = np.random.default_rng(7)
    b = rng.standard_normal(spd_grid.n)
    x, info = refine_solve_device(spd_grid, factored, b)
    resid = (np.linalg.norm(spd_grid.matvec(x) - b)
             / np.linalg.norm(b))
    assert info.converged
    assert resid < 1e-10
    assert info.t_sweep >= 0.0 and info.t_residual >= 0.0


def test_refine_device_multi_rhs(factored, spd_grid):
    from repro.sparse.refine import refine_solve_device

    rng = np.random.default_rng(8)
    B = rng.standard_normal((spd_grid.n, 4))
    X, info = refine_solve_device(spd_grid, factored, B)
    assert X.shape == B.shape
    assert info.converged
    resid = np.linalg.norm(spd_grid.matvec(X) - B) / np.linalg.norm(B)
    assert resid < 1e-10


def test_refine_device_zero_rhs(factored, spd_grid):
    from repro.sparse.refine import refine_solve_device

    x, info = refine_solve_device(spd_grid, factored,
                                  np.zeros(spd_grid.n))
    assert not x.any()
    assert info.converged and info.iterations == 0


def test_refine_device_max_iter_zero_stops_unconverged(factored, spd_grid):
    from repro.sparse.refine import refine_solve_device

    b = np.random.default_rng(9).standard_normal(spd_grid.n)
    x, info = refine_solve_device(spd_grid, factored, b, max_iter=0)
    assert info.iterations == 0
    assert not info.converged
    # still returns the raw f32 solve, good to the f32 floor
    resid = np.linalg.norm(spd_grid.matvec(x) - b) / np.linalg.norm(b)
    assert resid < 1e-5


def test_refine_device_stall_guard_ends_loop(factored, spd_grid):
    from repro.sparse.refine import refine_solve_device

    # tol=0 is unreachable: once the residual bottoms out at the fp64
    # floor the stall guard must end the loop, not cycle to max_iter
    b = np.random.default_rng(14).standard_normal(spd_grid.n)
    x, info = refine_solve_device(spd_grid, factored, b,
                                  tol=0.0, max_iter=50)
    assert not info.converged
    assert info.iterations < 50
    assert info.final_residual < 1e-10  # stalled at the floor, not broken


# -- execute_plan / engine plumbing -------------------------------------------

@pytest.mark.parametrize("solve_dtype", ["fp64", "fp32", "fp32_refine"])
def test_execute_plan_device_sweep(spd_grid, solve_dtype):
    from repro.core.plan import PlanBuilder, execute_plan

    plan = PlanBuilder().build(spd_grid, algorithm="rcm")
    b = np.random.default_rng(10).standard_normal(spd_grid.n)
    r = execute_plan(spd_grid, plan, b, backend="pipelined",
                     solve_dtype=solve_dtype, sweep="device")
    assert r["sweep"] == "device"
    assert plan.meta["solve_sweep"] == "device"
    if solve_dtype == "fp32":
        assert r["solve_dtype"] == "fp32"
        assert r["residual"] < 1e-4
    else:
        # fp64 promotes to fp32_refine on the f32 device sweeps
        assert r["solve_dtype"] == "fp32_refine"
        assert r["residual"] < 1e-10
        assert r["refine_iterations"] is not None


def test_execute_plan_multi_rhs(spd_grid):
    from repro.core.plan import PlanBuilder, execute_plan

    plan = PlanBuilder().build(spd_grid, algorithm="rcm")
    B = np.random.default_rng(11).standard_normal((spd_grid.n, 4))
    r = execute_plan(spd_grid, plan, B, backend="pipelined",
                     solve_dtype="fp32_refine", sweep="device")
    assert r["x"].shape == B.shape
    assert r["residual"] < 1e-10


def test_execute_plan_rejects_bad_sweep(spd_grid):
    from repro.core.plan import PlanBuilder, execute_plan

    plan = PlanBuilder().build(spd_grid, algorithm="rcm")
    with pytest.raises(ValueError, match="sweep"):
        execute_plan(spd_grid, plan, sweep="bogus")


def test_execute_plan_sweep_metrics(spd_grid):
    from repro.core.metrics import MetricsRegistry
    from repro.core.plan import PlanBuilder, execute_plan

    plan = PlanBuilder().build(spd_grid, algorithm="rcm")
    m = MetricsRegistry()
    execute_plan(spd_grid, plan, backend="pipelined",
                 solve_dtype="fp32_refine", sweep="device", metrics=m)
    snap = m.snapshot()
    assert snap.get("solve.sweep.device") == 1
    assert snap.get("solve.refine_iterations.count") == 1
    assert any(k.startswith("solve.refine_iters.") for k in snap)
    assert "stage.solve.refine.count" in snap
    assert snap.get("solve.refine.residual.device") == 1
    assert "solve.refine.unconverged" not in snap


def test_residual_path_is_chosen_by_platform(spd_grid, monkeypatch):
    """A platform without a device residual path refines with the host
    fp64 matvec around the device sweeps — and says so."""
    import repro.sparse.refine as refine
    from repro.core.metrics import MetricsRegistry
    from repro.core.plan import PlanBuilder, execute_plan

    assert refine.residual_path() == "device"   # the CPU backend
    monkeypatch.setattr(refine, "_RESIDUAL_PATHS", {})
    assert refine.residual_path() == "host"
    plan = PlanBuilder().build(spd_grid, algorithm="rcm")
    b = np.random.default_rng(15).standard_normal((spd_grid.n, 2))
    m = MetricsRegistry()
    r = execute_plan(spd_grid, plan, b, backend="pipelined",
                     solve_dtype="fp32_refine", sweep="device", metrics=m)
    assert r["refine_residual"] == "host" and r["sweep"] == "device"
    assert r["refine_converged"] and r["residual"] < 1e-10
    assert m.snapshot().get("solve.refine.residual.host") == 1


def test_unconverged_refinement_is_reported(spd_grid, monkeypatch):
    import repro.sparse.refine as refine
    from repro.core.metrics import MetricsRegistry
    from repro.core.plan import PlanBuilder, execute_plan

    monkeypatch.setattr(refine, "_should_stop", lambda *a: (True, False))
    plan = PlanBuilder().build(spd_grid, algorithm="rcm")
    m = MetricsRegistry()
    r = execute_plan(spd_grid, plan, backend="pipelined",
                     solve_dtype="fp32_refine", sweep="device", metrics=m)
    assert r["refine_converged"] is False
    assert m.snapshot().get("solve.refine.unconverged") == 1


def test_engine_config_sweep_validation():
    from repro.engine.config import EngineConfig

    with pytest.raises(ValueError, match="sweep"):
        EngineConfig(sweep="bogus")
    with pytest.warns(UserWarning, match="fp32_refine"):
        EngineConfig(backend="numpy", solve_dtype="fp64", sweep="device")


def test_engine_threads_sweep_knobs_into_solve_kwargs(tmp_path):
    from repro.autotune.solve_tuner import SolvePolicy, save_policy
    from repro.engine import EngineConfig, SolverEngine

    pol = SolvePolicy(bs=32, pad="pow2", backend="pipelined",
                      source="tuned", sweep_bs=16, rt=8)
    import repro.autotune.solve_tuner as st

    save_policy(dataclasses.replace(pol, device_kind=st.device_kind()),
                str(tmp_path / "tune"))
    cfg = EngineConfig(cache_dir=str(tmp_path / "cache"),
                       backend="pipelined", solve_dtype="fp32_refine",
                       sweep="device", autotune_dir=str(tmp_path / "tune"))
    kw = SolverEngine(cfg)._solve_kwargs()
    assert kw["sweep"] == "device"
    assert kw["sweep_bs"] == 16 and kw["rt"] == 8


# -- SolvePolicy persistence --------------------------------------------------

def test_solve_policy_sweep_fields_round_trip(tmp_path):
    from repro.autotune.solve_tuner import (SolvePolicy, load_policy,
                                            save_policy)

    pol = SolvePolicy(bs=32, pad="pow2", device_kind="cpu",
                      backend="pipelined", warm_factor_s=0.1,
                      source="tuned", sweep_bs=16, rt=8,
                      warm_sweep_s=0.02)
    save_policy(pol, str(tmp_path))
    back = load_policy(str(tmp_path), "cpu", backend="pipelined")
    assert back.sweep_bs == 16 and back.rt == 8
    assert back.warm_sweep_s == pytest.approx(0.02)
    assert back.source == "cached"


def test_solve_policy_pre_sweep_records_still_load(tmp_path):
    from repro.autotune.solve_tuner import (SolvePolicy, load_policy,
                                            policy_path, save_policy)

    save_policy(SolvePolicy(bs=16, pad="mult8", device_kind="cpu",
                            backend="pipelined", source="tuned"),
                str(tmp_path))
    path = policy_path(str(tmp_path), "cpu")
    with open(path) as fh:
        doc = json.load(fh)
    for key in ("sweep_bs", "rt", "warm_sweep_s"):
        doc.pop(key)
    with open(path, "w") as fh:
        json.dump(doc, fh)
    back = load_policy(str(tmp_path), "cpu", backend="pipelined")
    assert back is not None
    assert back.sweep_bs is None and back.rt is None
    assert back.bs == 16 and back.pad == "mult8"


# -- bell SpMV multi-RHS ------------------------------------------------------

def test_bell_spmv_multi_rhs_matches_csr(spd_grid):
    from repro.kernels.ops import _interpret
    from repro.kernels.spmv_bell import bell_spmv, csr_to_bell

    rng = np.random.default_rng(13)
    n = spd_grid.n
    blocks, idx, npad = csr_to_bell(spd_grid.indptr, spd_grid.indices,
                                    spd_grid.data, n)
    X = rng.standard_normal((npad, 3)).astype(np.float32)
    X[n:] = 0.0
    got = np.asarray(bell_spmv(blocks.astype(np.float32), idx, X,
                               interpret=_interpret()))
    assert got.shape == (npad, 3)
    ref = spd_grid.matvec(X[:n].astype(np.float64))
    np.testing.assert_allclose(got[:n], ref, rtol=1e-4, atol=1e-4)
