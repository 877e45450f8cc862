"""Extend-add routes and launch plans per factorization (ms): the
program's ``factor.routes`` span (``_route_contributions`` and every
``_extend_add_plan``), one part of ``factor.structure_ms``."""


def read(run):
    return run.mean_ms("factor.routes")
