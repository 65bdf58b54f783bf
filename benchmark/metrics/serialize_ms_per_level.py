"""Device milliseconds of the superstep operations under the ``serialize``
scope (the property stage's blocked loop over live rows, nested in
``properties``; ``scope_trace.py``) per BFS level committed in the traced
window. None without a trace or where the program names no such scope.
Also logs the whole stage split (``[stage_trace]``)."""

import scope_trace
import stage_trace


def read(run):
    stage_trace.for_run(run)
    secs = scope_trace.for_run(run, "serialize")
    levels = sum(c.levels for c in run.checks)
    if secs is None or levels == 0:
        return None
    return 1e3 * secs / levels
