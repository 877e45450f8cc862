"""Featurize + infer per plan request (ms): the dispatcher's ``select``
span, returned in the response's ``spans_ms``."""


def read(run):
    return run.mean_ms("select")
