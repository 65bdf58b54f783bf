"""Seconds of set-up spent tracing functions to jaxprs and lowering them
to MLIR (the program's ``obs.compile_log``: ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration`` events that ended before the window's
first check, as the union of their intervals). None where the program
keeps no such log."""

import stage_trace


def read(run):
    return stage_trace.compile_log_s(
        run, ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration"))
