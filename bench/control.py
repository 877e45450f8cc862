#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 5

In one process, for each seed: the cell's own set-up and a short window at
its own load, checked as a benchmark run checks it (the lower readings),
then the same with the control switched on (the upper readings). The
control is the step below what the configuration states:

* ``refactor`` mixes: the program's own fp32 solve path with no fp64
  refinement (``solve_dtype="fp32"``), where the configuration states
  fp32 factors refined to the fp64 floor;
* ``new_pattern`` mixes: plans without a fill-reducing ordering (the
  program's ``natural`` ordering), where the configuration states one.

The benchmark's own runs never run this. Each reading is printed as one
JSON line ``{"seed", "control", "checks"}``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, traffic  # noqa: E402

__all__ = ["ControlEngine", "natural_selection", "readings"]


class ControlEngine:
    """An engine that solves through the program's fp32 path, no
    refinement, and reports the configured path so only the numbers can
    tell."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def solve(self, a, b, ctx=None):
        from repro.core.plan import execute_plan

        kw = dict(self._engine._solve_kwargs(), solve_dtype="fp32")
        r = execute_plan(a, self._engine.plan(a, ctx=ctx), b, ctx=ctx, **kw)
        r["solve_dtype"] = self._engine.config.solve_dtype
        return r


def natural_selection(engine):
    """Make every selection, device and host path alike, pick the
    ``natural`` ordering; returns a function that undoes it."""
    sel = engine.selector
    sel.select_batch = lambda mats, **kw: (["natural"] * len(mats), 0.0)
    return lambda: sel.__dict__.pop("select_batch", None)


def _reading(cell, engine, seed, seconds, warm):
    mix = dict(cell["traffic"])
    if not warm:
        mix["warmup_requests"] = 0
    loop = traffic.KINDS[mix["kind"]](cell["config"], mix, seed)
    loop.setup(engine)
    try:
        window = loop.run(seconds)
        checks = loop.check(window.records)
    finally:
        loop.close()
    return {c["name"]: c["value"] for c in checks}


def readings(cell, engine, seeds, control_seeds, seconds):
    """The program's readings on ``seeds``, then the control's on
    ``control_seeds``; yields one record per run."""
    for i, seed in enumerate(seeds):
        yield {"seed": seed, "control": False,
               "checks": _reading(cell, engine, seed, seconds, i == 0)}
    kind = cell["traffic"]["kind"]
    undo = natural_selection(engine) if kind == "new_pattern" else None
    ctl = ControlEngine(engine) if kind == "refactor" else engine
    try:
        for i, seed in enumerate(control_seeds):
            yield {"seed": seed, "control": True,
                   "checks": _reading(cell, ctl, seed, seconds, i == 0)}
    finally:
        if undo is not None:
            undo()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.check_device(int(cell["cell"]["chips"]))
        harness.import_program()
    except harness.Refused as exc:
        print(f"control: {exc}", file=sys.stderr)
        return exc.code
    harness.configure_cache()
    engine = harness.build_engine(cell["config"])
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = [int(s) for s in args.control_seeds.split(",")]
    for rec in readings(cell, engine, seeds, ctl, args.seconds):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
