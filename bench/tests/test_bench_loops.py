"""Each traffic mix drives a few requests through the harness on the CPU at a
tiny size (Pallas kernels in interpret mode; the TPU check is lifted by
calling ``run_cell`` with the device described), and ``correct`` comes out
false for the control and for each fault a cell can have, planted in the
timed path."""
import copy
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import control, harness, traffic  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _cell(workload, grid, **mix):
    cell = harness.load_cell(workload)
    cell = copy.deepcopy(cell)
    cell["config"]["grid"] = grid
    cell["traffic"].update(mix)
    return cell


@pytest.fixture(scope="module")
def solve_cell():
    return _cell("poisson2d.solve", [10, 10], value_sets=4)


@pytest.fixture(scope="module")
def plan_cell():
    return _cell("poisson2d.plan_cold", [24, 24], patterns=3)


@pytest.fixture(scope="module")
def engine(solve_cell):
    return harness.build_engine(solve_cell["config"])


class _CpuPeaks:
    """Loops that take the roofline peaks as given (the CPU has none)."""

    @staticmethod
    def peaks():
        return PEAKS


class RefactorLoop(_CpuPeaks, traffic.RefactorLoop):
    pass


class NewPatternLoop(_CpuPeaks, traffic.NewPatternLoop):
    pass


def _run(cell, engine, loop_cls, seconds=1.0, trace=False, seed=2**31 + 7):
    loop = loop_cls(cell["config"], cell["traffic"], seed)
    return harness.run_cell(cell, seed, seconds, trace, CPU,
                            time.perf_counter(), loop=loop, engine=engine)


def test_refactor_mix_runs_correct(solve_cell, engine):
    r = _run(solve_cell, engine, RefactorLoop)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"solve_ms", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["residual_max"]["value"] < 1e-12
    assert r["checks"]["unanswered"] == {"value": 0, "limit": 0}


def test_refactor_mix_traced(solve_cell, engine):
    r = _run(solve_cell, engine, RefactorLoop, trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    for name in ("factor.structure_ms", "factor.assemble_ms",
                 "factor.device_ms", "solve.sweep_ms", "solve.refine_ms",
                 "device.idle_pct.solve"):
        assert name in m, name
    # no device plane on the CPU: no kernel events, so no roofline
    assert "frontal_factor_roofline" not in m
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_plan_mix_runs_correct(plan_cell, engine):
    r = _run(plan_cell, engine, NewPatternLoop, seconds=0.5)
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"plan_ms", "setup_s"}
    assert r["checks"]["fill_ratio_max"]["value"] < 2.0


class _Faulty:
    """An engine whose ``solve`` is broken underneath the timed path."""

    def __init__(self, engine, fault):
        self._engine = engine
        self._fault = fault
        self._prev = None

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def solve(self, a, b, ctx=None):
        if self._fault == "stale_factor":
            # the factorization keeps the previous request's coefficients
            prev, self._prev = self._prev, a
            return self._engine.solve(prev if prev is not None else a, b,
                                      ctx=ctx)
        r = self._engine.solve(a, b, ctx=ctx)
        if self._fault == "altered_answer":
            x = r["x"].copy()
            x[len(x) // 2] *= 1 + 1e-6
            r["x"] = x
        return r


@pytest.mark.parametrize("fault", ["stale_factor", "altered_answer"])
def test_refactor_faults_are_not_correct(solve_cell, engine, fault):
    r = _run(solve_cell, _Faulty(engine, fault), RefactorLoop)
    assert r["correct"] is False
    assert r["checks"]["residual_max"]["value"] > \
        r["checks"]["residual_max"]["limit"]


def test_refactor_control_fp32_is_not_correct(solve_cell, engine):
    """The control: the program's own fp32 path, no refinement."""
    r = _run(solve_cell, control.ControlEngine(engine), RefactorLoop)
    assert r["correct"] is False
    res = r["checks"]["residual_max"]
    assert res["value"] > res["limit"]
    assert r["checks"]["forward_error_max"]["value"] > \
        r["checks"]["forward_error_max"]["limit"]


class _StalePlan(NewPatternLoop):
    """The server answers each request with the previous request's plan."""

    def _send(self, i):
        rec = super()._send(i)
        prev, self._prev = getattr(self, "_prev", None), rec["plan"]
        if prev is not None:
            rec["plan"] = prev
        return rec


class _AlteredPerm(NewPatternLoop):
    """Two entries of each served permutation swapped."""

    def _send(self, i):
        rec = super()._send(i)
        plan = copy.copy(rec["plan"])
        plan.perm = plan.perm.copy()
        plan.perm[[0, -1]] = plan.perm[[-1, 0]]
        rec["plan"] = plan
        return rec


@pytest.mark.parametrize("loop_cls", [_StalePlan, _AlteredPerm])
def test_plan_faults_are_not_correct(plan_cell, engine, loop_cls):
    r = _run(plan_cell, engine, loop_cls, seconds=3.0)
    assert r["correct"] is False
    assert r["checks"]["symbolic_mismatch"]["value"] > 0


def test_plan_control_natural_ordering_is_not_correct(plan_cell, engine):
    """The control: plans without a fill-reducing ordering."""
    undo = control.natural_selection(engine)
    try:
        r = _run(plan_cell, engine, NewPatternLoop, seconds=0.5, seed=5)
    finally:
        undo()
    assert r["correct"] is False
    fill = r["checks"]["fill_ratio_max"]
    assert fill["value"] > fill["limit"]


def test_seeds_make_the_same_inputs(solve_cell, engine):
    a = traffic.make_loop(solve_cell, 2**31 + 11)
    b = traffic.make_loop(solve_cell, 2**31 + 11)
    for loop in (a, b):
        loop.engine = engine
    from bench import deploy
    n, u, v = deploy.stencil_edges(solve_cell["config"])
    pat = deploy.pattern(n, u, v)
    va = deploy.values(pat, traffic._rng(a.seed, 0), (1, 2), (0.5, 1.5))
    vb = deploy.values(pat, traffic._rng(b.seed, 0), (1, 2), (0.5, 1.5))
    assert np.array_equal(va, vb)


def test_control_readings(solve_cell, engine):
    recs = list(control.readings(solve_cell, engine, [2**31 + 1],
                                 [2**31 + 2], 0.3))
    assert [r["control"] for r in recs] == [False, True]
    lim = traffic.LIMITS["refactor"]["residual_max"]
    assert recs[0]["checks"]["residual_max"] <= lim
    assert recs[1]["checks"]["residual_max"] > lim


class _Raising(_Faulty):
    """Every solve raises: no request is answered."""

    def solve(self, a, b, ctx=None):
        if ctx is None:        # the warm-up request, outside the window
            return self._engine.solve(a, b)
        raise RuntimeError("planted: no answer")


def test_unanswered_requests_are_not_correct(solve_cell, engine):
    r = _run(solve_cell, _Raising(engine, None), RefactorLoop, seconds=0.2)
    assert r["correct"] is False and r["failed"] == r["attempted"] > 0
    assert r["checks"]["unanswered"]["value"] == r["failed"]


@pytest.fixture(scope="module")
def hpcg_cell(tmp_path_factory):
    """A 27-point configuration and its cell, added by files alone: a copy
    of the benchmark with one more configuration file and one more cell,
    resolved by ``load_cell`` as a run resolves it."""
    root = tmp_path_factory.mktemp("bench27")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench/configs/stencil3d.json").read_text())
    config.update(name="hpcg27", stencil="27-point", grid=[6, 6, 6],
                  source="https://github.com/hpcg-benchmark/hpcg")
    spec["configs"].append({"name": "hpcg27", "source": config["source"],
                            "file": "bench/configs/hpcg27.json",
                            "reduced": ["grid"], "why": "27-point 3-D"})
    spec["workloads"].append({"name": "hpcg27.solve", "config": "hpcg27",
                              "traffic": "refactor_1rhs", "chips": 1,
                              "why": "the refactor loop on HPCG's operator"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "stencil3d.solve" in m.get("workloads", []):
            m["workloads"].append("hpcg27.solve")
    (root / "bench/configs").mkdir(parents=True)
    (root / "bench/traffic").mkdir()
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/configs/hpcg27.json").write_text(json.dumps(config))
    shutil.copy(ROOT / "bench/traffic/refactor_1rhs.json",
                root / "bench/traffic")
    cell = harness.load_cell("hpcg27.solve", root)
    cell["traffic"]["value_sets"] = 4
    return cell


def test_27_point_cell_from_files_runs_correct(hpcg_cell, engine, capsys):
    assert {m["name"] for m in hpcg_cell["end_to_end"]} == \
        {"solve_ms", "setup_s"}
    assert hpcg_cell["per_layer"]
    r = _run(hpcg_cell, engine, RefactorLoop)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["forward_error_max"]["value"] < 1e-12
    # the checks' cost is on a progress line, not in the result
    assert "[bench] checks of the window's answers: " in capsys.readouterr().out
    assert "check_s" not in r


@pytest.mark.parametrize("fault", ["stale_factor", "altered_answer",
                                   "control"])
def test_27_point_faults_are_not_correct(hpcg_cell, engine, fault):
    bad = (control.ControlEngine(engine) if fault == "control"
           else _Faulty(engine, fault))
    r = _run(hpcg_cell, bad, RefactorLoop)
    assert r["correct"] is False
    assert r["checks"]["residual_max"]["value"] > \
        r["checks"]["residual_max"]["limit"]
