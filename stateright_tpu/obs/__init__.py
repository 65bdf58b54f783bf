"""Run-trace observability: spans, counters, heartbeat.

The engine's telemetry grew per-round as ad-hoc lists (``checker.level_log``,
``dispatch_log``, ``cand_retries``, ``hv_stats``) and bench-side logging;
this package is the one structured home for the pieces that need *wall-clock*
and *liveness*:

- :class:`~stateright_tpu.obs.trace.Tracer` — host-side wall-clock spans
  around every host↔device boundary (dispatch, compile-carrying dispatch,
  table growth/rehash, delta flush, host-verify round-trip), appended as
  JSONL (``STPU_TRACE=path`` / ``spawn_xla(trace=...)``) with a Chrome
  trace-event exporter (``export_chrome``) so runs open directly in
  Perfetto (``STPU_TRACE_CHROME=path`` auto-exports at interpreter exit).
- :class:`~stateright_tpu.obs.metrics.Counters` — the counter half of
  ``checker.metrics()``: growth events, shrink-exits, delta flushes.
  Gauges (occupancy, capacities, counts) are computed at snapshot time
  from live engine state, so the registry costs nothing on the hot path.
- :class:`~stateright_tpu.obs.heartbeat.Heartbeat` — a small JSON file the
  engine rewrites around every device dispatch (``STPU_HEARTBEAT=path`` /
  ``spawn_xla(heartbeat=...)``): phase ``"dispatch"`` before entering the
  device (with a ``compile`` flag when this call traces a fresh program),
  phase ``"idle"`` with ``seq`` incremented after it returns. Watchdogs
  (bench.py, ``supervise.py``) read staleness + phase to distinguish a
  hung dispatch from a long XLA compile in-band.
- :class:`~stateright_tpu.obs.timeseries.MetricsRecorder` — the snapshot
  layer over time (``STPU_METRICS_TO=path`` / ``spawn_xla(metrics_to=...)``):
  append-only rotating ``metrics.jsonl`` of ``checker.metrics()`` rows
  sampled at quiescent superstep boundaries on a level/wall-clock
  cadence; :func:`~stateright_tpu.obs.timeseries.read_series` reassembles
  the rotation chain.
- :mod:`~stateright_tpu.obs.promexport` — OpenMetrics rendering of any
  snapshot or series tail (``stpu_*`` counter/gauge families with
  ``job``/``engine``/``dedup`` labels), served by the Explorer as
  ``GET /.metrics``; ships the validating parser the tests and smoke
  stage scrape with.

Everything here is OFF by default and adds **no device syncs** when on:
spans only wrap host boundaries and reuse scalars the host already fetches.
With tracing off the engines hold the shared :data:`NULL_TRACER`, whose
``span()`` returns a no-op context — no files, no clocks, no allocation.

Schemas are documented in ``docs/observability.md`` and pinned by
``tests/test_obs.py``.
"""

from __future__ import annotations

import os
from typing import Optional, Union

from . import collect
from .heartbeat import Heartbeat
from .metrics import Counters
from .timeseries import MetricsRecorder, read_series
from .trace import (
    CTX_ENV,
    NULL_TRACER,
    Span,
    Tracer,
    export_chrome,
    format_ctx,
    new_trace_id,
    parse_ctx,
)

__all__ = [
    "CTX_ENV",
    "Counters",
    "Heartbeat",
    "MetricsRecorder",
    "NULL_TRACER",
    "Span",
    "Tracer",
    "collect",
    "export_chrome",
    "format_ctx",
    "new_trace_id",
    "parse_ctx",
    "read_series",
    "resolve_heartbeat",
    "resolve_recorder",
    "resolve_tracer",
]


#: Process-wide live tracers by absolute path: several checkers in one
#: process (bench primary pass + matrix entries) must SHARE one tracer —
#: one epoch, one ``trace_start`` — or the appended file's timestamps
#: restart at zero mid-run and the Chrome/roofline timeline garbles.
_TRACERS: dict = {}


def resolve_tracer(trace: Union[None, str, Tracer] = None):
    """The tracer a checker should hold: an explicit :class:`Tracer`, a
    path (``spawn_xla(trace="...")``), the ``STPU_TRACE`` env default, or
    — the common case — the shared no-op :data:`NULL_TRACER`. Path
    resolution is cached process-wide (one tracer per file).

    ``STPU_TRACE_CHROME`` (env) or ``Tracer(chrome_path=...)`` additionally
    exports the Chrome trace-event form when the tracer closes."""
    if isinstance(trace, Tracer):
        return trace
    if trace is None:
        trace = os.environ.get("STPU_TRACE") or None
    if trace is None:
        return NULL_TRACER
    path = os.path.abspath(trace)
    tracer = _TRACERS.get(path)
    if tracer is None or tracer.closed:
        tracer = Tracer(
            path, chrome_path=os.environ.get("STPU_TRACE_CHROME") or None
        )
        _TRACERS[path] = tracer
    return tracer


def resolve_heartbeat(heartbeat: Union[None, str, Heartbeat] = None) -> Optional[Heartbeat]:
    """The heartbeat a checker should beat, or None (the default — the
    protocol is for watchdog-supervised runs, not every spawn)."""
    if isinstance(heartbeat, Heartbeat):
        return heartbeat
    if heartbeat is None:
        heartbeat = os.environ.get("STPU_HEARTBEAT") or None
    if heartbeat is None:
        return None
    return Heartbeat(heartbeat)


def resolve_recorder(metrics_to=None, metrics_every=None, metrics_keep=None):
    """The metrics recorder a checker should sample into, or None (the
    default — same off-by-default pin discipline as the tracer). Accepts
    a live :class:`MetricsRecorder` (shared-series embedders), a path, or
    the ``STPU_METRICS_{TO,EVERY,KEEP}`` env knobs."""
    if isinstance(metrics_to, MetricsRecorder):
        return metrics_to
    return MetricsRecorder.resolve(metrics_to, metrics_every, metrics_keep)
