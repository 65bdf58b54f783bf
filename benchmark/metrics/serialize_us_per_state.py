"""Device microseconds of the superstep operations under the ``serialize``
scope (``scope_trace.py``) per unique state of the traced window's checks:
the cost of one row the tester evaluated, padding included. None without
a trace or where the program names no such scope."""

import scope_trace


def read(run):
    secs = scope_trace.for_run(run, "serialize")
    unique = sum(c.unique for c in run.checks)
    if secs is None or unique == 0:
        return None
    return 1e6 * secs / unique
