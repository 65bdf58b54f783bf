"""Device milliseconds of the superstep's ``properties`` and ``terminal``
stages (property evaluation with the device tester, and the terminal pass
for eventually properties; ``stage_trace.py``) per BFS level committed in
the traced window. None without a trace or where the program names no
stage."""

import stage_trace


def read(run):
    return stage_trace.stage_ms_per_level(run, ("properties", "terminal"))
