"""The 90th percentile of per-check seconds, from when a check was due to
its verdict, over every check of the window. None with fewer than ten
checks: too few for a tail."""

import statistics


def read(run):
    latencies = [c.latency for c in run.checks]
    if len(latencies) < 10:
        return None
    return statistics.quantiles(latencies, n=10, method="inclusive")[-1]
