"""Wait in the dispatcher's queue per plan request (ms): the ``queue``
span, returned in the response's ``spans_ms``."""


def read(run):
    return run.mean_ms("queue")
