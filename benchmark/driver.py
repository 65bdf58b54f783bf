"""The one window driver: runs checks as a traffic file describes.

A traffic file (``benchmark/traffic/<mix>.json``) holds only parameters:

- ``warm_checks_max``: set-up runs warm checks until one compiles no
  program (loads from the persistent cache are allowed), at most this
  many;
- ``trace_seconds``: the length of a traced run's window (at least one
  whole check).

Checks run closed loop on one warmed model instance: a check is due when
the previous one ends. Each does what a user's check does to reach a
verdict: ``model.checker().spawn_xla(**caps).join()``, then the counts and
which properties have a discovery. A check that is running when the
window's time is up finishes, and its work and time count: the window runs
from the first check's start to the last check's end.

Each check also records where its host time went (its three phases, the
process's CPU seconds, involuntary context switches and garbage-collection
pauses), so that a check far slower than the others can be told apart: a
host phase that computes, a host that was descheduled, or a wait on the
device.
"""

from __future__ import annotations

import contextlib
import gc
import resource
import time
from dataclasses import dataclass
from typing import List, Optional, Tuple

TRAFFIC_KEYS = {"warm_checks_max", "trace_seconds"}


@dataclass
class Check:
    due: float
    start: float
    end: float
    generated: int
    unique: int
    found: Tuple[str, ...]
    dispatches: int
    levels: int
    #: Seconds in ``spawn_xla``, ``join`` and the verdict read.
    phases: Tuple[float, float, float] = (0.0, 0.0, 0.0)
    #: The process's CPU seconds (every thread) during the check.
    cpu_s: float = 0.0
    #: Involuntary context switches of the process during the check.
    preempted: int = 0
    #: Seconds of garbage-collection pauses during the check.
    gc_s: float = 0.0

    @property
    def latency(self) -> float:
        """Seconds from when the check was due to its verdict."""
        return self.end - self.due


def validate_traffic(traffic: dict) -> dict:
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    return traffic


class GcClock:
    """Seconds the collector has paused the process, from ``gc.callbacks``."""

    def __init__(self):
        self.total = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.total += time.perf_counter() - self._t


GC = GcClock()


def _annotate(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def one_check(model, caps: dict, due: float) -> Tuple[Check, object]:
    """One check to its verdict; returns its record and the checker."""
    cpu0, gc0 = time.process_time(), GC.total
    ivcsw0 = resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
    start = time.monotonic()
    with _annotate("spawn_xla"):
        checker = model.checker().spawn_xla(**caps)
    spawned = time.monotonic()
    with _annotate("join"):
        checker.join()
    joined = time.monotonic()
    with _annotate("verdict"):
        generated = checker.state_count()
        unique = checker.unique_state_count()
        # The names of the properties with a discovery, without rebuilding
        # the witness paths (that pulls the whole visited set to the host).
        found = tuple(sorted(checker._found_names))
        m = checker.metrics()
    end = time.monotonic()
    return Check(
        due, start, end, generated, unique, found, m["dispatches"], m["levels_committed"],
        phases=(spawned - start, joined - spawned, end - joined),
        cpu_s=time.process_time() - cpu0,
        preempted=resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw - ivcsw0,
        gc_s=GC.total - gc0,
    ), checker


def run_window(model, caps: dict, seconds: float) -> Tuple[List[Check], object, float]:
    """Runs checks back to back for ``seconds``. Returns the checks, the
    last checker and the window's length in seconds."""
    checks: List[Check] = []
    due = time.monotonic()
    while True:
        check, checker = one_check(model, caps, due)
        checks.append(check)
        if check.end - checks[0].start >= seconds:
            break
        # Free this check's device state before the next one allocates.
        del checker
        due = check.end
    return checks, checker, checks[-1].end - checks[0].start


def slow_checks(checks: List[Check], factor: float = 1.5) -> List[Tuple[int, Check]]:
    """The checks that took over ``factor`` times the median, slowest
    first."""
    times = sorted(c.end - c.start for c in checks)
    median = times[len(times) // 2]
    slow = [(i, c) for i, c in enumerate(checks) if c.end - c.start > factor * median]
    return sorted(slow, key=lambda ic: ic[1].start - ic[1].end)


def warm(model, caps: dict, traffic: dict, events) -> int:
    """Set-up's warm checks: until one compiles nothing. Returns how many
    ran."""
    limit = int(traffic.get("warm_checks_max", 3))
    for n in range(1, limit + 1):
        before = events.snapshot()
        _check, checker = one_check(model, caps, time.monotonic())
        del checker
        if CompileEvents.compiles(before, events.snapshot()) == 0:
            return n
    return limit


class CompileEvents:
    """Counts JAX's program requests (each compile or persistent-cache load
    of a program not yet in memory) and persistent-cache hits."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.requests = 0
        self.hits = 0

    def install(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == self.REQUEST:
            self.requests += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1

    def snapshot(self) -> Tuple[int, int]:
        return (self.requests, self.hits)

    @staticmethod
    def compiles(before: Tuple[int, int], after: Tuple[int, int]) -> int:
        """Programs compiled (requested and not found in the cache)."""
        return (after[0] - before[0]) - (after[1] - before[1])


@contextlib.contextmanager
def profiled(trace_dir: Optional[str]):
    """Records JAX's profiler trace into ``trace_dir`` when it is given."""
    if trace_dir is None:
        yield
        return
    import jax

    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
