"""Percent of the traced window in which no operation ran on the device:
100 * (1 - union of ``XLA Ops`` intervals / window), averaged over the
chips. None without a trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
