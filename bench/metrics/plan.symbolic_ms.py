"""Permutation and symbolic analysis per plan request (ms): the build
worker's ``symbolic`` span, returned in the response's ``spans_ms``; the
other part of ``plan.build_ms``."""


def read(run):
    return run.mean_ms("symbolic")
