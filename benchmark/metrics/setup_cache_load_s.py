"""Seconds of set-up spent loading programs from the persistent compile
cache (the program's ``obs.compile_log``: ``cache_retrieval_time_sec``
events that ended before the window's first check). None where the
program keeps no such log."""

import stage_trace


def read(run):
    return stage_trace.compile_log_s(run, ("cache_retrieval_time_sec",))
