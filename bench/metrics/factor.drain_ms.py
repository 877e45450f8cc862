"""The factorization's blocking fetch of the factored stacks per request
(ms): the program's ``factor.drain`` span, the part of ``factor.device``
that waits on the device; the rest of it is dispatch."""


def read(run):
    return run.mean_ms("factor.drain")
