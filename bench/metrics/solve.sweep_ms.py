"""Triangular sweeps per request (ms): the program's ``solve.sweep`` span
(``tri_solve_batch`` and the L21 products, every refinement pass)."""


def read(run):
    return run.mean_ms("solve.sweep")
