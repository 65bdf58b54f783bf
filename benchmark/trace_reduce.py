"""Reduce a JAX profiler trace to device busy time, time per program and
idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``:

- device planes are those named ``/device:<kind>:<n>`` other than the CPU;
  on each, the ``XLA Ops`` line holds one event per operation run, and
  ``XLA Modules`` one per program run;
- the host plane (``/host:CPU``) holds the benchmark's own annotations
  (``jax.profiler.TraceAnnotation``) on its thread lines.

The window is the span of the benchmark's annotations when there are any,
else of all device events. Busy time is the union of operation intervals
inside the window; the idle gaps are its complement, each labelled by the
innermost annotation that covers the gap's middle (``"none"`` where the
host was outside every annotation).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over the devices
    devices: int
    #: Device seconds per program (``XLA Modules`` events), all devices.
    programs: Dict[str, float] = field(default_factory=dict)
    #: Idle gaps of the first device: (host annotation, seconds), longest first.
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_by_annotation(self) -> List[Tuple[str, float]]:
        """Idle seconds of the first device summed by host annotation,
        most first."""
        total: Dict[str, float] = {}
        for label, secs in self.gaps:
            total[label] = total.get(label, 0.0) + secs
        return sorted(total.items(), key=lambda kv: -kv[1])


def program_name(event_name: str) -> str:
    """``jit_fused(12)`` and ``jit_fused`` are one program."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_profile(profile, annotations: Sequence[str]) -> Optional[Reduction]:
    """The reduction of a ``jax.profiler.ProfileData``; None where the trace
    holds no device operation."""
    device_ops: List[List[Interval]] = []
    programs: Dict[str, float] = {}
    marks: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        name = plane.name
        if name.startswith("/device:") and not name.startswith("/device:CPU"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
            if not ops:
                continue
            device_ops.append([(a, b) for _n, a, b in ops])
            for n, a, b in lines.get(MODULES_LINE, []):
                key = program_name(n)
                programs[key] = programs.get(key, 0.0) + (b - a)
        elif name.startswith("/host:"):
            for ln in plane.lines:
                marks += [m for m in _events(ln) if m[0] in annotations]
    if not device_ops:
        return None
    if marks:
        lo, hi = min(a for _n, a, _b in marks), max(b for _n, _a, b in marks)
    else:
        lo = min(a for ops in device_ops for a, _b in ops)
        hi = max(b for ops in device_ops for _a, b in ops)
    window = hi - lo
    busy = [union(clip(ops, lo, hi)) for ops in device_ops]
    busy_s = sum(b - a for dev in busy for a, b in dev) / len(busy)
    gaps = []
    for a, b in complement(busy[0], lo, hi):
        mid = 0.5 * (a + b)
        covering = [m for m in marks if m[1] <= mid <= m[2]]
        label = min(covering, key=lambda m: m[2] - m[1])[0] if covering else "none"
        gaps.append((label, b - a))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window, busy_s, len(busy), programs, gaps)


def reduce_trace(trace_dir: str, annotations: Sequence[str]) -> Optional[Reduction]:
    """Reads the newest trace under ``trace_dir`` and reduces it."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)), annotations)
