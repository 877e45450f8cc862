"""Pipelined device-resident backend: kernel parity, backend parity,
pad/bs policy plumbing, the autotuner, and refinement edge cases."""
import json
import os

import numpy as np
import pytest

from repro.sparse.csr import make_spd
from repro.sparse.dataset import block_arrow, grid2d
from repro.sparse.multifrontal import (factor_and_solve_timed,
                                       multifrontal_cholesky,
                                       multifrontal_solve)
from repro.sparse.schedule import build_schedule
from repro.sparse.symbolic import symbolic_cholesky


@pytest.fixture(scope="module")
def spd_grid():
    return make_spd(grid2d(12, 12, "g12"))


# -- on-device extend-add kernel ---------------------------------------------

def _ref_extend_add(w, u, dst, rows):
    w = np.array(w)
    for c in range(u.shape[0]):
        act = rows[c] >= 0
        idx = rows[c][act]
        w[dst[c]][np.ix_(idx, idx)] += u[c][np.ix_(act, act)]
    return w


def test_extend_add_kernel_matches_reference():
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    B, M, C, R = 3, 16, 6, 8
    w = rng.standard_normal((B, M, M)).astype(np.float32)
    u = rng.standard_normal((C, R, R)).astype(np.float32)
    dst = np.array([0, 0, 0, 1, 2, 2], dtype=np.int32)  # sorted, repeats
    rows = np.full((C, R), -1, dtype=np.int32)
    for c in range(C):
        k = int(rng.integers(1, R + 1))
        rows[c, :k] = np.sort(rng.choice(M, size=k, replace=False))
    got = np.asarray(ops.extend_add_batch(w, u, dst, rows))
    np.testing.assert_allclose(got, _ref_extend_add(w, u, dst, rows),
                               rtol=1e-5, atol=1e-5)


def test_extend_add_all_masked_rows_are_inert():
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    w = rng.standard_normal((2, 16, 16)).astype(np.float32)
    u = rng.standard_normal((2, 8, 8)).astype(np.float32)
    dst = np.array([0, 1], dtype=np.int32)
    rows = np.full((2, 8), -1, dtype=np.int32)  # fully masked
    got = np.asarray(ops.extend_add_batch(w, u, dst, rows))
    np.testing.assert_array_equal(got, w)


# -- backend parity ----------------------------------------------------------

@pytest.mark.parametrize("pad", ["pow2", "mult8"])
def test_pipelined_matches_batched_exactly(spd_grid, pad):
    a = spd_grid
    b = np.random.default_rng(3).standard_normal(a.n)
    fb = multifrontal_cholesky(a, backend="batched", pad=pad)
    fp_ = multifrontal_cholesky(a, backend="pipelined", pad=pad)
    xb = multifrontal_solve(fb, b)
    xp = multifrontal_solve(fp_, b)
    # same kernels, same schedule — the two paths agree to the last bit
    np.testing.assert_array_equal(xp, xb)


def test_pipelined_end_to_end_residual(small_suite):
    for a in small_suite:
        a = make_spd(a)
        b = np.random.default_rng(0).standard_normal(a.n)
        f = multifrontal_cholesky(a, backend="pipelined")
        x = multifrontal_solve(f, b)
        resid = np.linalg.norm(a.matvec(x) - b) / np.linalg.norm(b)
        assert resid < 1e-5, (a.name, resid)


def test_pipelined_reports_overlap_stats(spd_grid):
    f = multifrontal_cholesky(spd_grid, backend="pipelined")
    s = f.stats
    for k in ("t_factor_assemble", "t_factor_dispatch", "t_factor_sync",
              "overlap_efficiency"):
        assert k in s
    assert 0.0 <= s["overlap_efficiency"] <= 1.0
    assert s["t_factor_assemble"] > 0


def test_factor_and_solve_timed_forwards_pad_bs(spd_grid):
    r = factor_and_solve_timed(spd_grid, backend="pipelined", pad="mult8",
                               bs=16)
    assert r["bs"] == 16
    assert r["residual"] < 1e-5


# -- schedule pad policy + per-level occupancy -------------------------------

def test_mult8_schedule_invariants(spd_grid):
    sym = symbolic_cholesky(spd_grid)
    s8 = build_schedule(sym, pad="mult8")
    s2 = build_schedule(sym, pad="pow2")
    assert s8.pad == "mult8" and s2.pad == "pow2"
    for lvl in s8.buckets:
        for bkt in lvl:
            assert bkt.P % 8 == 0
            assert bkt.R % 8 == 0
            for k in bkt.members:
                fp = s8.fronts[k]
                assert fp.npiv <= bkt.P and fp.nrest <= bkt.R
    st8, st2 = s8.stats(), s2.stats()
    # tighter padding can only improve (or match) occupancy
    assert st8["occupancy"] >= st2["occupancy"]
    assert len(st8["per_level_occupancy"]) == s8.nlevels
    assert all(0 < o <= 1 for o in st8["per_level_occupancy"])
    assert st8["min_level_occupancy"] == min(st8["per_level_occupancy"])


def test_unknown_pad_policy_rejected(spd_grid):
    sym = symbolic_cholesky(spd_grid)
    with pytest.raises(ValueError, match="pad policy"):
        build_schedule(sym, pad="pow3")


# -- autotuner ---------------------------------------------------------------

def test_tuner_persists_and_round_trips(tmp_path):
    from repro.autotune.solve_tuner import (device_kind, get_policy,
                                            load_policy, policy_path, tune)

    d = str(tmp_path / "autotune")
    rng = np.random.default_rng(0)
    mats = [make_spd(block_arrow(3, 12, 6, rng, "t"))]
    pol = tune(mats, backend="pipelined", bs_grid=(16, 32),
               pads=("pow2",), repeats=1, out_dir=d)
    assert pol.source == "tuned" and pol.bs in (16, 32)
    path = policy_path(d, device_kind())
    assert os.path.exists(path)
    got = load_policy(d, device_kind(), backend="pipelined")
    assert got is not None and (got.bs, got.pad) == (pol.bs, pol.pad)
    assert got.source == "cached"
    # get_policy serves the cached record without re-measuring
    assert get_policy(d, backend="pipelined").source == "cached"
    # invalidation: device-kind or backend mismatch is a miss
    assert load_policy(d, "TPU v9", backend="pipelined") is None
    assert load_policy(d, device_kind(), backend="batched") is None
    # corrupt file is a miss, not a crash
    with open(path, "w") as fh:
        fh.write("{not json")
    assert load_policy(d, device_kind()) is None
    assert get_policy(d, backend="pipelined").source == "default"


def test_policy_meta_round_trips_through_plan_cache(tmp_path, spd_grid):
    from repro.core.plan import PlanBuilder, execute_plan
    from repro.core.plan_cache import TwoTierPlanCache, matrix_fingerprint

    cache = TwoTierPlanCache(8, str(tmp_path / "plans"), version="t1")
    builder = PlanBuilder(cache=cache)
    a = spd_grid
    key = matrix_fingerprint(a)
    plan = builder.build(a, algorithm="amd", fingerprint=key)
    r = execute_plan(a, plan, backend="pipelined", solve_dtype="fp32_refine",
                     pad="mult8", bs=16)
    assert r["residual"] < 1e-9
    assert plan.meta["solve_bs"] == 16
    assert plan.meta["solve_pad"] == "mult8"
    cache.put(key, plan)
    # a fresh cold-tier cache (same dir/version) must serve the meta back
    cache2 = TwoTierPlanCache(8, str(tmp_path / "plans"), version="t1")
    back = cache2.get(key)
    assert back is not None
    assert back.meta["solve_bs"] == 16
    assert back.meta["solve_pad"] == "mult8"


def test_execute_plan_promotes_fp64_on_pipelined(spd_grid):
    from repro.core.plan import PlanBuilder, execute_plan

    plan = PlanBuilder().build(spd_grid, algorithm="amd")
    r = execute_plan(spd_grid, plan, backend="pipelined", solve_dtype="fp64")
    assert r["solve_dtype"] == "fp32_refine"
    assert r["refine_converged"]
    assert r["overlap_efficiency"] is not None


# -- refinement edge cases ---------------------------------------------------

def test_refine_zero_iterations_when_inner_solver_exact():
    from repro.sparse.refine import refine_solve

    rng = np.random.default_rng(0)
    A = np.diag(rng.uniform(1.0, 2.0, 32))
    b = rng.standard_normal(32)
    x, info = refine_solve(lambda v: A @ v, lambda r: np.linalg.solve(A, r),
                           b)
    assert info.iterations == 0
    assert info.converged
    np.testing.assert_allclose(A @ x, b, rtol=1e-12)


def test_refine_zero_rhs_short_circuits():
    from repro.sparse.refine import refine_solve

    called = []
    x, info = refine_solve(lambda v: v, lambda r: called.append(1) or r,
                           np.zeros(8))
    assert not called  # no solve for b = 0
    assert info.converged and info.iterations == 0
    np.testing.assert_array_equal(x, np.zeros(8))


def test_refine_stall_detection_on_singularish_system():
    from repro.sparse.refine import refine_solve

    rng = np.random.default_rng(0)
    n = 24
    # near-singular: tiny eigenvalue makes fp32 corrections cycle
    A = np.diag(np.concatenate([np.ones(n - 1), [1e-14]]))
    b = rng.standard_normal(n)
    # inner solver that is badly wrong in the tiny direction (as an fp32
    # factorization would be): refinement cannot contract the residual
    bad = np.diag(np.concatenate([np.ones(n - 1), [1.0]]))
    x, info = refine_solve(lambda v: A @ v, lambda r: bad @ r, b,
                           max_iter=10)
    assert not info.converged
    assert info.iterations < 10  # stall guard fired before max_iter
    assert len(info.residuals) >= 2
    assert info.residuals[-1] > 0.5 * info.residuals[-2] * 0.99


def test_engine_config_warns_on_fp64_device_backend():
    from repro.engine.config import EngineConfig

    for backend in ("batched", "pipelined"):
        with pytest.warns(UserWarning, match="fp32_refine"):
            cfg = EngineConfig(backend=backend, solve_dtype="fp64")
        assert cfg.backend == backend
    # explicit fp32_refine is silent
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        EngineConfig(backend="pipelined", solve_dtype="fp32_refine")


def test_engine_config_accepts_pipelined_and_autotune_knobs(tmp_path):
    from repro.engine.config import EngineConfig

    cfg = EngineConfig(backend="pipelined", solve_dtype="fp32_refine",
                       autotune_solve=True,
                       autotune_dir=str(tmp_path / "at"))
    assert cfg.autotune_solve
    with pytest.raises(ValueError, match="backend"):
        EngineConfig(backend="vectorized")


def test_schedule_programs_compile_ahead(monkeypatch):
    """Every kernel program of a pipelined factorization (and of the
    device sweeps) is compiled up front by ``compile_ahead``; the dispatch
    loop that follows compiles nothing."""
    from jax import monitoring

    from repro.kernels import ops

    log, live = [], [True]
    monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: live[0] and ev.endswith("backend_compile_duration")
        and log.append("compile"))
    real = ops.compile_ahead

    def ahead(calls):
        real(calls)
        log.append("ahead")

    monkeypatch.setattr(ops, "compile_ahead", ahead)
    try:
        import jax

        jax.clear_caches()                # nothing compiled yet
        a = grid2d(7, 13, "g7x13")
        log.clear()
        f = multifrontal_cholesky(a, backend="pipelined")
        assert "ahead" in log and "compile" in log[:log.index("ahead")]
        assert "compile" not in log[log.index("ahead"):]
        log.clear()
        b = np.random.default_rng(0).standard_normal(a.n)
        x = multifrontal_solve(f, b, mode="device")
        i = log.index("ahead")
        # after the sweeps only the final slice of x may compile
        assert log[i + 1:].count("compile") <= 2
        np.testing.assert_allclose(a.matvec(x), b, rtol=1e-4, atol=1e-4)
    finally:
        live[0] = False
