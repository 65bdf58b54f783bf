"""Device-idle milliseconds inside the engine's host-side dispatch work
(``dispatch:prepare``, ``dispatch:commit``, ``host_verify``,
``grow_table``) per device dispatch of the traced window
(``stage_trace.py``). None without a trace or where the program puts no
``dispatch:prepare`` span on the profiler's host plane."""

import stage_trace

LABELS = ("dispatch:prepare", "dispatch:commit", "host_verify", "grow_table")


def read(run):
    r = stage_trace.for_run(run)
    dispatches = sum(c.dispatches for c in run.checks)
    if r is None or "dispatch:prepare" not in r.spans or dispatches == 0:
        return None
    return 1e3 * r.idle_s(LABELS) / dispatches
