"""The triangular-solve kernel's share of its roofline (%): the least time
of the sweeps' true pivot blocks at the true RHS count (bench/work.py) over
the trace time of the kernel's events."""


def read(run):
    return run.roofline_pct("tri_solve")
