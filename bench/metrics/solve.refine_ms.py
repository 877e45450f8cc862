"""Refinement residuals per request (ms): the program's ``solve.refine``
span (the fp64 residual and its norm, where the queued sweeps complete)."""


def read(run):
    return run.mean_ms("solve.refine")
