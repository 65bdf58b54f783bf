"""Device-idle milliseconds inside the engine's ``spawn`` span and its
parts (``spawn:table``, ``spawn:init_insert``, ``spawn:frontier``) per
check of the traced window (``stage_trace.py``). None without a trace or
where the program puts no ``spawn`` span on the profiler's host plane."""

import stage_trace

LABELS = ("spawn", "spawn:table", "spawn:init_insert", "spawn:frontier")


def read(run):
    r = stage_trace.for_run(run)
    if r is None or "spawn" not in r.spans:
        return None
    return 1e3 * r.idle_s(LABELS) / len(run.checks)
