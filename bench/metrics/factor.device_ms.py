"""Time the factorization waits on the device per request (ms): the
program's ``factor.device`` span (dispatch of the extend-add and factor
kernels, and the drain of every factored stack to the host)."""


def read(run):
    return run.mean_ms("factor.device")
