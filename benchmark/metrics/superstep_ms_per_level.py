"""Device milliseconds of the superstep programs (``jit_fused`` and
``jit_superstep`` in the profiler trace's ``XLA Modules`` line) per BFS
level committed in the traced window. None without a trace or without such
programs in it."""

NAMES = ("fused", "superstep")


def read(run):
    if run.trace is None:
        return None
    secs = sum(s for name, s in run.trace.programs.items()
               if any(n in name for n in NAMES))
    levels = sum(c.levels for c in run.checks)
    if secs == 0 or levels == 0:
        return None
    return 1e3 * secs / levels
