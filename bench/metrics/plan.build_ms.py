"""Reorder + symbolic analysis per plan request (ms): the dispatcher's
``build`` span, returned in the response's ``spans_ms``."""


def read(run):
    return run.mean_ms("build")
