"""Device time of the superstep's operations under one ``jax.named_scope``
name, wherever it nests in the op_name (``properties/serialize/...``).

``stage_trace.py`` gives each operation to the outermost stage name on its
path; a scope nested inside a stage (the blocked property stage's
``serialize``) is read here instead, with the same rules: each ``XLA Ops``
event counts its own time less its children's, and only operations inside
a superstep program (``stage_trace.SUPERSTEP``) count.
"""

from __future__ import annotations

import bisect
import os
from typing import Dict, Optional

import stage_trace
import trace_reduce

_CACHE: Dict[tuple, float] = {}


def _op_paths(plane) -> Dict[int, str]:
    """The op_name (``tf_op`` stat) of every event metadata id of
    ``plane``."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    tf_op = {k for k, n in names.items() if n == "tf_op"}
    out = {}
    for mid, md in plane.event_metadata.items():
        out[mid] = next((st.str_value or names.get(st.ref_value, "")
                         for st in md.stats if st.metadata_id in tf_op), "")
    return out


def scope_s(xs, scope: str) -> float:
    """Device seconds, over every device plane of the decoded ``XSpace``
    ``xs``, of superstep operations with ``scope`` on their op_name path."""
    ps = 0
    for plane in xs.planes:
        if not plane.name.startswith("/device:") or plane.name.startswith("/device:CPU"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if trace_reduce.OPS_LINE not in lines:
            continue
        ops = sorted(stage_trace._events(lines[trace_reduce.OPS_LINE]),
                     key=lambda e: (e[0], -e[1]))
        md = plane.event_metadata
        modules = lines.get(trace_reduce.MODULES_LINE)
        progs = sorted(
            (a, b) for a, b, m in (stage_trace._events(modules) if modules else [])
            if any(n in trace_reduce.program_name(md[m].name) for n in stage_trace.SUPERSTEP))
        starts = [a for a, _b in progs]
        paths = _op_paths(plane)
        for (a, b, m), own in zip(ops, stage_trace._self_times(ops)):
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= progs[i][1] and scope in paths[m].split("/"):
                ps += own
    return ps * 1e-12


def for_run(run, scope: str) -> Optional[float]:
    """Device seconds under ``scope`` in a traced run's trace
    (``benchmark/out/trace/<cell>``); None for an untraced run or where no
    operation carries the name."""
    if run.trace is None:
        return None
    path = trace_reduce.find_xplane(os.path.join(stage_trace.HERE, "out", "trace",
                                                 run.cell.name))
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size, scope)
    if key not in _CACHE:
        _CACHE[key] = scope_s(stage_trace.load_xspace(path), scope)
    return _CACHE[key] or None
