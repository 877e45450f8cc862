"""Supernode partition and level schedule per factorization (ms): the
program's ``factor.schedule`` span (``supernodes`` + ``build_schedule``),
one part of ``factor.structure_ms``."""


def read(run):
    return run.mean_ms("factor.schedule")
