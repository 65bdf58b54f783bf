"""Device milliseconds of the superstep's ``compact`` stage (operations under
that ``jax.named_scope`` in the profiler trace, ``stage_trace.py``) per
BFS level committed in the traced window. None without a trace or where
the program names no stage."""

import stage_trace


def read(run):
    return stage_trace.stage_ms_per_level(run, ("compact",))
