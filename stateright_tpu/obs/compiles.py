"""JAX's compile durations, on the ``time.monotonic`` clock.

One process-wide listener (:func:`install`, called by the first
``spawn_xla``) appends each of these JAX monitoring events to :data:`LOG`
as ``(event, end, seconds)``, ``end`` on ``time.monotonic``:

``jaxpr_trace_duration``
    Python tracing of a function to a jaxpr.
``jaxpr_to_mlir_module_duration``
    Lowering a jaxpr to an MLIR module.
``cache_retrieval_time_sec``
    Loading a program from the persistent compile cache (a hit).
``backend_compile_duration``
    The whole program request: a compile, or a cache load.

A jitted function traced inside another is timed in both; the inner
event ends first, and when the outer one arrives, the entries of its kind
that lie inside it are folded into it. So the entries of one kind do not
overlap, and their seconds add up to the time spent. The log is for
readers with a time bound, such as a benchmark's set-up;
``checker.metrics()`` does not report it.
"""

from __future__ import annotations

import collections
import time

#: The recorded events, by the last component of JAX's event name.
EVENTS = (
    "jaxpr_trace_duration",
    "jaxpr_to_mlir_module_duration",
    "cache_retrieval_time_sec",
    "backend_compile_duration",
)

#: ``(event, end on time.monotonic, seconds)`` in the order the events
#: ended; bounded, so a long-lived process keeps the newest.
LOG: collections.deque = collections.deque(maxlen=1 << 16)

_installed = False


def _record(event: str, secs: float, **_kw) -> None:
    name = event.rpartition("/")[2]
    if name not in EVENTS:
        return
    end = time.monotonic()
    start = end - secs
    # Fold the events of this kind nested inside this one: they ended
    # after it started, so they are the newest entries of its kind.
    inner = []
    while LOG and LOG[-1][1] - LOG[-1][2] >= start:
        entry = LOG.pop()
        if entry[0] != name:
            inner.append(entry)
    LOG.extend(reversed(inner))
    LOG.append((name, end, secs))


def install() -> None:
    """Registers the listener once per process (imports jax)."""
    global _installed
    if _installed:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_record)
    _installed = True
