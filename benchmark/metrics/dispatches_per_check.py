"""Device dispatches per check (``XlaChecker.metrics()["dispatches"]``),
averaged over the window's checks."""


def read(run):
    return sum(c.dispatches for c in run.checks) / len(run.checks)
