"""Set-up of the device sweeps per request (ms): the program's
``solve.sweep.setup`` span (the per-factor sweep stacks and the sweeps'
``compile_ahead``), the part of ``solve.sweep`` that dispatches no sweep."""


def read(run):
    return run.mean_ms("solve.sweep.setup")
