"""The ordering per plan request (ms): the build worker's ``reorder``
span, returned in the response's ``spans_ms``; one part of
``plan.build_ms``."""


def read(run):
    return run.mean_ms("reorder")
