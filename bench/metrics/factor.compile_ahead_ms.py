"""Lowering and look-up of the factorization's kernel programs per
factorization (ms): the program's ``factor.compile_ahead`` span (the
schedule's calls built and passed to ``ops.compile_ahead``), one part of
``factor.structure_ms``."""


def read(run):
    return run.mean_ms("factor.compile_ahead")
