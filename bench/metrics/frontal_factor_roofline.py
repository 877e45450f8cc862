"""The frontal factorization kernel's share of its roofline (%): the least
time of the true fronts factored in the traced window (bench/work.py) over
the trace time of the kernel's events."""


def read(run):
    return run.roofline_pct("frontal_factor")
