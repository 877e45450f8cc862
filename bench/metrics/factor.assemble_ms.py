"""Host assembly of the bucket workspaces per factorization (ms): the
program's ``factor.assemble`` span."""


def read(run):
    return run.mean_ms("factor.assemble")
