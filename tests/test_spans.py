"""Spans: the one timing primitive of the plan and solve paths.

``repro.core.reqctx.span`` accumulates wall time into a request context and
opens a ``jax.profiler.TraceAnnotation`` of the same name, so each stage is
on the device trace's clock. These tests hold the primitive to that (on
raise, nested, in a live CPU trace) and the solve and plan paths to the
breakdown the benchmark reads: every stage of ``SOLVE_STAGES`` recorded,
each child within its parent, the structure spans inside the remainder of
``factor``, and the backends' ``t_factor_*`` stats without a context.
"""
import glob
import os
import time
import warnings

import numpy as np
import pytest

from repro.core.metrics import MetricsRegistry
from repro.core.plan import SOLVE_STAGES, PlanBuilder, execute_plan
from repro.core.plan_cache import PlanCache
from repro.core.reqctx import RequestContext, span
from repro.sparse.csr import make_spd
from repro.sparse.dataset import grid2d
from repro.sparse.multifrontal import multifrontal_cholesky


def _trace_events(log_dir):
    """name -> [((plane, line index), stats dict)] of every host event
    traced; a host line is one thread."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1
    out = {}
    with warnings.catch_warnings():
        # jaxlib's event-stats type warns about its own __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(paths[0]).planes:
            if plane.name.startswith("/device"):
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        ((plane.name, i), dict(e.stats)))
    return out


def _traced(log_dir, fn):
    import jax

    jax.profiler.start_trace(str(log_dir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


# -- the primitive -------------------------------------------------------------

def test_span_accumulates_on_raise_and_nests():
    ctx = RequestContext.mint()
    with pytest.raises(ValueError):
        with span(ctx, "outer"):
            with span(ctx, "inner"):
                time.sleep(0.002)
            with span(ctx, "inner"):
                raise ValueError("boom")
    assert ctx.spans["inner"] >= 0.002
    assert ctx.spans["outer"] >= ctx.spans["inner"]
    # re-entering a stage adds to it; the method form is the same primitive
    before = ctx.spans["inner"]
    with ctx.span("inner"):
        time.sleep(0.001)
    assert ctx.spans["inner"] >= before + 0.001
    assert ctx.spans_since({"outer": ctx.spans["outer"]}) == {
        "inner": ctx.spans["inner"]}


def test_span_without_a_context_times_nothing():
    with span(None, "nowhere") as ann:
        ann.set_metadata(programs=1)   # fine with no profiler running


def test_span_is_a_trace_event_with_the_bare_name(tmp_path):
    ctx = RequestContext.mint()

    def body():
        with span(ctx, "stage.outer"):
            with span(ctx, "stage.inner") as sp:
                sp.set_metadata(programs=3)
        with span(None, "stage.free", request_id="given"):
            pass

    _traced(tmp_path, body)
    ev = _trace_events(str(tmp_path))
    (_, outer), = ev["stage.outer"]
    (_, inner), = ev["stage.inner"]
    (_, free), = ev["stage.free"]
    assert outer["request_id"] == ctx.request_id
    assert "request_id" not in inner      # its parent carries it
    assert inner["programs"] == 3
    assert free["request_id"] == "given"
    assert set(ctx.spans) == {"stage.outer", "stage.inner"}


# -- the solve path --------------------------------------------------------------

@pytest.fixture(scope="module")
def grid():
    return make_spd(grid2d(8, 8, "g8"))


@pytest.fixture(scope="module")
def solved(grid):
    """A warm pipelined / device / fp32_refine execute_plan, and its
    context, metrics and result."""
    plan = PlanBuilder().build(grid, algorithm="rcm")
    b = np.random.default_rng(0).standard_normal(grid.n)
    execute_plan(grid, plan, b, backend="pipelined",
                 solve_dtype="fp32_refine", sweep="device")   # compiles
    ctx, m = RequestContext.mint(), MetricsRegistry()
    r = execute_plan(grid, plan, b, backend="pipelined",
                     solve_dtype="fp32_refine", sweep="device", ctx=ctx,
                     metrics=m)
    assert r["residual"] < 1e-10
    return plan, b, ctx, m, r


def test_execute_plan_records_every_solve_stage(solved):
    _, _, ctx, m, _ = solved
    assert set(SOLVE_STAGES) <= set(ctx.spans)
    snap = m.snapshot()
    for stage in SOLVE_STAGES:
        assert snap[f"stage.{stage}.count"] == 1, stage


@pytest.mark.parametrize("stage", [s for s, p in SOLVE_STAGES.items() if p])
def test_child_span_within_its_parent(solved, stage):
    spans = solved[2].spans
    assert spans[stage] <= spans[SOLVE_STAGES[stage]]


def test_structure_spans_lie_inside_the_factor_remainder(solved):
    s = solved[2].spans
    rest = s["factor"] - s["factor.assemble"] - s["factor.device"]
    assert rest >= (s["factor.schedule"] + s["factor.routes"]
                    + s["factor.compile_ahead"])


def test_compile_ahead_programs_are_counted(solved):
    _, _, ctx, m, _ = solved
    n = ctx.counts["compile_ahead.programs"]
    assert n > 0
    assert m.snapshot()["compile_ahead.programs"] == n


def test_result_times_are_the_spans(solved):
    _, _, ctx, _, r = solved
    assert r["t_permute"] == ctx.spans["permute"]
    assert r["t_factor"] == ctx.spans["factor"]
    assert r["t_solve"] == ctx.spans["solve"]


def test_solve_spans_are_trace_events(grid, solved, tmp_path):
    plan, b, _, _, _ = solved
    ctx = RequestContext.mint()
    _traced(tmp_path, lambda: execute_plan(
        grid, plan, b, backend="pipelined", solve_dtype="fp32_refine",
        sweep="device", ctx=ctx))
    ev = _trace_events(str(tmp_path))
    for stage, parent in SOLVE_STAGES.items():
        assert stage in ev, stage
        ids = {stats.get("request_id") for _, stats in ev[stage]}
        assert ids == ({ctx.request_id} if parent is None else {None}), \
            stage
    # the plan's structure is warm: its programs are compiled and its
    # schedule is looked up; the per-factor sweep stacks still compile
    (_, stats), = ev["factor.compile_ahead"]
    assert stats["programs"] == 0
    (_, stats), = ev["factor.schedule"]
    assert stats["cached"] == 1
    (_, stats), = ev["solve.sweep.setup"]
    assert stats["programs"] > 0


@pytest.mark.parametrize("backend", ["batched", "pipelined"])
def test_factor_stats_without_a_context(grid, backend):
    f = multifrontal_cholesky(grid, backend=backend)
    s = f.stats
    for k in ("t_factor_assemble", "t_factor_dispatch", "t_factor_sync",
              "overlap_efficiency"):
        assert k in s
    assert s["t_factor_assemble"] > 0 and s["t_factor_sync"] > 0
    assert s["t_factor_dispatch"] >= 0
    assert "t_factor_compile" not in s


def test_factor_stats_count_this_call_only(grid):
    ctx = RequestContext.mint()
    ctx.add_span("factor.assemble", 100.0)
    f = multifrontal_cholesky(grid, backend="pipelined", ctx=ctx)
    assert f.stats["t_factor_assemble"] < 100.0
    assert ctx.spans["factor.assemble"] == pytest.approx(
        100.0 + f.stats["t_factor_assemble"])
    assert ctx.spans["factor.device"] == pytest.approx(
        f.stats["t_factor_dispatch"] + f.stats["t_factor_sync"])


# -- the plan path ---------------------------------------------------------------

class _Selector:
    def select_batch(self, batch, path="host", use_pallas=False):
        return ["rcm"] * len(batch), 0.0

    def select(self, a):
        return "rcm", 0.0


def test_get_or_build_spans(grid):
    builder = PlanBuilder(_Selector(), PlanCache(4))
    ctx = RequestContext.mint()
    builder.get_or_build(grid, ctx=ctx)
    assert {"fingerprint", "cache", "select", "reorder",
            "symbolic"} <= set(ctx.spans)
    warm = RequestContext.mint()
    builder.get_or_build(grid, ctx=warm)
    assert set(warm.spans) == {"fingerprint", "cache"}


def test_select_names_writes_no_infer_metrics(grid):
    m = MetricsRegistry()
    builder = PlanBuilder(_Selector(), PlanCache(4), path="host", metrics=m)
    assert builder.select_names([grid, grid]) == ["rcm", "rcm"]
    assert builder.select_calls == 1
    assert not [k for k in m.snapshot() if k.startswith("infer.")]


def test_dispatcher_spans_on_their_threads(grid, tmp_path):
    from repro.core.dispatch import PlanDispatcher

    disp = PlanDispatcher(PlanBuilder(_Selector(), PlanCache(4)),
                          batch_size=1, max_wait_ms=1.0, build_workers=1)
    ctx = RequestContext.mint()
    try:
        plan = _traced(tmp_path,
                       lambda: disp.submit(grid, ctx=ctx).result(timeout=60))
    finally:
        disp.close()
    assert plan.algorithm == "rcm"
    ev = _trace_events(str(tmp_path))
    (sel_line, sel), = ev["select"]
    (build_line, build), = ev["build"]
    assert sel["request_id"] == ctx.request_id
    assert build["request_id"] == ctx.request_id
    for stage in ("reorder", "symbolic"):
        (line, stats), = ev[stage]
        assert line == build_line and "request_id" not in stats
    assert sel_line != build_line
    assert {"queue", "select", "build", "reorder",
            "symbolic"} <= set(ctx.spans)
