"""The factorization's pattern-only structure, kept on the plan.

``execute_plan`` hands ``multifrontal_cholesky`` the plan's
``FactorStructure`` for its policy (``structure_key``): the first
factorization builds the level schedule, the extend-add plans and the
compiled programs, every later one on the plan reads them. These tests
hold it to that (same schedule object, no routes built, no programs
lowered, the hit counted), to one entry per policy, to bit-identical
answers, to a plan that pickles without it, and to one build when two
requests race on a cold plan.
"""
import glob
import os
import pickle
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import repro.sparse.multifrontal as mf
from repro.core.metrics import MetricsRegistry
from repro.core.plan import ExecutionPlan, PlanBuilder, execute_plan
from repro.core.reqctx import RequestContext
from repro.sparse.csr import make_spd
from repro.sparse.dataset import grid2d

DEVICE = dict(backend="pipelined", solve_dtype="fp32_refine",
              sweep="device")


@pytest.fixture(scope="module")
def grid():
    return make_spd(grid2d(8, 8, "g8"))


@pytest.fixture
def plan(grid):
    return PlanBuilder().build(grid, algorithm="rcm")


@pytest.fixture
def rhs(grid):
    return np.random.default_rng(0).standard_normal(grid.n)


@pytest.fixture
def counted(monkeypatch):
    """Calls of ``_route_contributions`` (each taking ``delay`` s longer)
    and programs handed to ``compile_ahead``."""
    from repro.kernels import ops

    seen = {"routes": 0, "programs": [], "delay": 0.0}
    routes, ahead = mf._route_contributions, ops.compile_ahead

    def count_routes(schedule):
        seen["routes"] += 1
        time.sleep(seen["delay"])
        return routes(schedule)

    def count_ahead(calls):
        calls = list(calls)
        seen["programs"].append(len(calls))
        ahead(calls)

    monkeypatch.setattr(mf, "_route_contributions", count_routes)
    monkeypatch.setattr(ops, "compile_ahead", count_ahead)
    return seen


def _schedule_of(plan, key):
    return plan.structures[key].schedule


def _compile_ahead_stats(log_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return [dict(e.stats)
                for p in ProfileData.from_file(path).planes
                if not p.name.startswith("/device")
                for line in p.lines for e in line.events
                if e.name == "factor.compile_ahead"]


def test_warm_factorization_reuses_the_plans_structure(grid, plan, rhs,
                                                        counted, tmp_path):
    import jax

    key = mf.structure_key("pipelined", "pow2", None)
    cold = RequestContext.mint()
    execute_plan(grid, plan, rhs, ctx=cold, **DEVICE)
    assert list(plan.structures) == [key]
    schedule = _schedule_of(plan, key)
    assert counted["routes"] == 1
    assert cold.counts["factor.structure.misses"] == 1
    assert "factor.structure.hits" not in cold.counts
    lowered_cold = counted["programs"][0]
    assert lowered_cold > 0

    warm, m = RequestContext.mint(), MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        r = execute_plan(grid, plan, rhs, ctx=warm, metrics=m, **DEVICE)
    finally:
        jax.profiler.stop_trace()
    assert r["residual"] < 1e-10
    assert _schedule_of(plan, key) is schedule
    assert counted["routes"] == 1                  # no routes built
    assert warm.counts["factor.structure.hits"] == 1
    assert "factor.structure.misses" not in warm.counts
    snap = m.snapshot()
    assert snap["factor.structure.hits"] == 1
    assert "factor.structure.misses" not in snap
    # the factor's programs are not lowered again; the per-factor sweep
    # set-up is, and is all the warm count holds
    assert counted["programs"][2] == 0
    assert warm.counts["compile_ahead.programs"] == counted["programs"][3]
    (stats,) = _compile_ahead_stats(tmp_path)
    assert stats["programs"] == 0
    # the structure spans still time their look-ups
    for stage in ("factor.schedule", "factor.routes",
                  "factor.compile_ahead"):
        assert stage in warm.spans


@pytest.mark.parametrize("change", [dict(pad="mult8"), dict(bs=16)],
                         ids=["pad", "bs"])
def test_another_policy_builds_its_own_entry(grid, plan, rhs, counted,
                                             change):
    execute_plan(grid, plan, rhs, **DEVICE)
    policy = dict(pad="pow2", bs=None, **DEVICE)
    ctx = RequestContext.mint()
    r = execute_plan(grid, plan, rhs, ctx=ctx, **{**policy, **change})
    assert r["residual"] < 1e-10
    first = mf.structure_key("pipelined", "pow2", None)
    second = mf.structure_key("pipelined", change.get("pad", "pow2"),
                              change.get("bs"))
    assert set(plan.structures) == {first, second}
    assert ctx.counts["factor.structure.misses"] == 1
    assert counted["routes"] == 2
    assert _schedule_of(plan, second).pad == change.get("pad", "pow2")
    assert _schedule_of(plan, first).pad == "pow2"


def test_schedule_backends_share_a_family(grid, plan, rhs):
    execute_plan(grid, plan, rhs, backend="numpy")
    ctx = RequestContext.mint()
    execute_plan(grid, plan, rhs, backend="batched", ctx=ctx)
    assert ctx.counts["factor.structure.hits"] == 1
    (st,) = plan.structures.values()
    assert st.ea_plans is None and not st.compiled


@pytest.mark.parametrize("kw", [DEVICE, dict(backend="numpy")],
                         ids=["pipelined", "numpy"])
def test_cold_and_warm_answers_are_bit_identical(grid, plan, rhs, kw):
    cold = execute_plan(grid, plan, rhs, **kw)
    warm = execute_plan(grid, plan, rhs, **kw)
    assert len(plan.structures) == 1
    np.testing.assert_array_equal(cold["x"], warm["x"])
    assert warm["residual"] < 1e-10


def test_a_pickled_plan_carries_no_structure(grid, plan, rhs):
    # a twin sharing every pickled field, never factored
    twin = ExecutionPlan(plan.fingerprint, plan.algorithm, plan.perm,
                         plan.sym, plan.predicted_flops, meta=plan.meta)
    execute_plan(grid, plan, rhs, **DEVICE)
    assert plan.structures and not twin.structures
    assert "structures" not in repr(plan)
    blob = pickle.dumps(plan)
    assert len(blob) == len(pickle.dumps(twin))
    loaded = pickle.loads(blob)
    assert loaded.structures == {}
    ctx = RequestContext.mint()
    r = execute_plan(grid, loaded, rhs, ctx=ctx, **DEVICE)
    assert r["residual"] < 1e-10
    assert ctx.counts["factor.structure.misses"] == 1
    assert len(loaded.structures) == 1


@pytest.mark.parametrize("workers", [2, (os.cpu_count() or 1) + 1],
                         ids=["two", "more-than-cores"])
def test_racing_requests_on_a_cold_plan_build_once(grid, plan, rhs,
                                                   counted, workers):
    counted["delay"] = 0.3      # the others arrive mid-build
    barrier = threading.Barrier(workers)
    ctxs = [RequestContext.mint() for _ in range(workers)]
    results, errors = [None] * workers, []

    def solve(i):
        try:
            barrier.wait()
            results[i] = execute_plan(grid, plan, rhs, ctx=ctxs[i],
                                      **DEVICE)
        except Exception as exc:  # surfaced below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=solve, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert all(r["residual"] < 1e-10 for r in results)
    assert list(plan.structures) == [mf.structure_key("pipelined", "pow2",
                                                      None)]
    assert counted["routes"] == 1
    assert sum(c.counts.get("factor.structure.misses", 0)
               for c in ctxs) == 1
    assert sum(c.counts.get("factor.structure.hits", 0)
               for c in ctxs) == workers - 1
