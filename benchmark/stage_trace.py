"""Reduce a profiler trace to the engine's own labels: device time per
superstep stage, and device-idle time per engine span.

The engine names the stages of its superstep with ``jax.named_scope``
(``properties``, ``expand``, ``compact``, ``fingerprint``, ``insert``,
``terminal``) and the fused loop's own work ``ladder``; the names ride in
each device operation's op_name, the ``tf_op`` stat of the event metadata
in the raw ``XSpace`` (``jax.profiler.ProfileData`` does not expose it).
Its host spans (``spawn``, ``dispatch:prepare`` ...) are profiler
annotations on the host plane, on the device events' clock.

- Stage time: on each device plane, the ``XLA Ops`` events nest (the
  ``while``, its ``conditional``, the operations inside), so each event
  counts its own time less its children's. An operation inside a
  superstep program (``XLA Modules`` events named like ``fused`` or
  ``superstep``, as ``superstep_ms_per_level`` counts them) goes to the
  first component of its op_name that is a stage name, else to
  ``unnamed``.
- Idle time: as in ``trace_reduce``, the window is the span of the
  harness's annotations and the idle gaps are the complement of the first
  device's operations in it; each gap goes to the innermost engine span or
  harness annotation over its middle.

A program without the names reads as all ``unnamed`` and no engine span;
the metrics that read this then report nothing.

    python3 benchmark/stage_trace.py <trace dir | .xplane.pb[.gz]> [annotation ...]
    python3 benchmark/stage_trace.py --cut <trace dir> <check> <out.pb.gz>

The first form prints the reduction as JSON; the second writes one check
(the ``check``-th ``spawn_xla`` to the end of its ``verdict``) with only
the lines this module and ``trace_reduce`` read.
"""

from __future__ import annotations

import bisect
import gzip
import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

STAGES = ("properties", "expand", "compact", "fingerprint", "insert", "terminal",
          "ladder")
UNNAMED = "unnamed"
#: Program names of the superstep, as ``superstep_ms_per_level`` reads them.
SUPERSTEP = ("fused", "superstep")
#: The engine's host spans (``stateright_tpu/obs/trace.py``).
ENGINE_SPANS = (
    "spawn", "spawn:table", "spawn:init_insert", "spawn:frontier",
    "dispatch", "dispatch:prepare", "dispatch:run", "dispatch:commit",
    "grow_table", "host_verify", "delta_flush", "checkpoint",
)
#: The harness's own annotations (``harness.ANNOTATIONS``).
HARNESS = ("spawn_xla", "join", "verdict")

Interval = Tuple[int, int]  # picoseconds


@dataclass
class StageReduction:
    #: Device seconds of superstep operations per stage, ``unnamed`` too.
    stage_s: Dict[str, float]
    #: Device seconds of the superstep programs (``XLA Modules``).
    superstep_s: float
    #: Idle gaps of the first device: (innermost label, harness annotation
    #: over the gap or "none", seconds).
    gaps: List[Tuple[str, str, float]] = field(default_factory=list)
    #: Engine spans on the host plane, by name.
    spans: Dict[str, int] = field(default_factory=dict)

    @property
    def named(self) -> bool:
        """Whether the program names its stages."""
        return any(self.stage_s.get(s, 0.0) > 0 for s in STAGES)

    def idle_s(self, labels: Sequence[str]) -> float:
        return sum(s for label, _h, s in self.gaps if label in labels)

    def idle_by_label(self) -> List[Tuple[str, float]]:
        total: Dict[str, float] = {}
        for label, _h, secs in self.gaps:
            total[label] = total.get(label, 0.0) + secs
        return sorted(total.items(), key=lambda kv: -kv[1])

    def engine_share(self) -> Optional[float]:
        """Share of the idle time inside the harness's ``spawn_xla`` and
        ``join`` that an engine span labels; None without such idle time."""
        inside = [(label, s) for label, h, s in self.gaps if h in ("spawn_xla", "join")]
        total = sum(s for _l, s in inside)
        if total == 0:
            return None
        return sum(s for label, s in inside if label in ENGINE_SPANS) / total

    def summary(self) -> dict:
        return {
            "stage_s": self.stage_s,
            "superstep_s": self.superstep_s,
            "unnamed_share": (self.stage_s.get(UNNAMED, 0.0) / self.superstep_s
                              if self.superstep_s else None),
            "idle_by_label": self.idle_by_label(),
            "engine_share_of_spawn_xla_join_idle": self.engine_share(),
            "spans": self.spans,
        }


_PB2 = None


def xplane_pb2():
    """The generated ``XSpace`` message module, loaded by its path from
    the installed ``tensorflow`` without importing that package: it needs
    only ``google.protobuf``, and importing tensorflow would start a
    second runtime beside JAX's."""
    global _PB2
    if _PB2 is None:
        rel = os.path.join("tensorflow", "tsl", "profiler", "protobuf", "xplane_pb2.py")
        path = next((os.path.join(base, rel) for base in sys.path
                     if os.path.isfile(os.path.join(base, rel))), None)
        if path is None:
            raise RuntimeError(f"stage_trace: nothing decodes an XSpace here: no {rel} "
                               "on sys.path")
        spec = importlib.util.spec_from_file_location("stage_trace_xplane_pb2", path)
        _PB2 = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_PB2)
    return _PB2


def load_xspace(path: str):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    xs = xplane_pb2().XSpace()
    xs.ParseFromString(data)
    return xs


def _events(line) -> List[Tuple[int, int, int]]:
    """(start ps, end ps, metadata id) of each event on ``line``."""
    base = line.timestamp_ns * 1000
    return [(base + e.offset_ps, base + e.offset_ps + e.duration_ps, e.metadata_id)
            for e in line.events]


def _stage_of(plane) -> Dict[int, str]:
    """Stage (or ``unnamed``) of every event metadata id of ``plane``."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    tf_op = [k for k, n in names.items() if n == "tf_op"]
    out = {}
    for mid, md in plane.event_metadata.items():
        path = ""
        for st in md.stats:
            if st.metadata_id in tf_op:
                path = st.str_value or names.get(st.ref_value, "")
                break
        out[mid] = next((p for p in path.split("/") if p in STAGES), UNNAMED)
    return out


def _self_times(ops: List[Tuple[int, int, int]]) -> List[int]:
    """Each event's own time less that of the events nested in it."""
    own = [b - a for a, b, _m in ops]
    stack: List[int] = []
    for i, (a, b, _m) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def reduce_xspace(xs, annotations: Sequence[str] = HARNESS) -> Optional[StageReduction]:
    """The reduction of a decoded ``XSpace``; None where it holds no device
    operation."""
    stage_ps = {s: 0 for s in STAGES + (UNNAMED,)}
    superstep_ps = 0
    first_ops: Optional[List[Interval]] = None
    marks: List[Tuple[str, int, int]] = []
    for plane in xs.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            lines = {ln.name: ln for ln in plane.lines}
            if trace_reduce.OPS_LINE not in lines:
                continue
            ops = sorted(_events(lines[trace_reduce.OPS_LINE]), key=lambda e: (e[0], -e[1]))
            if first_ops is None:
                first_ops = [(a, b) for a, b, _m in ops]
            md = plane.event_metadata
            modules = lines.get(trace_reduce.MODULES_LINE)
            progs = sorted(
                (a, b) for a, b, m in (_events(modules) if modules else [])
                if any(n in trace_reduce.program_name(md[m].name) for n in SUPERSTEP))
            superstep_ps += sum(b - a for a, b in progs)
            starts = [a for a, _b in progs]
            stage = _stage_of(plane)
            for (a, b, m), own in zip(ops, _self_times(ops)):
                i = bisect.bisect_right(starts, a) - 1
                if i >= 0 and b <= progs[i][1]:
                    stage_ps[stage[m]] += own
        elif plane.name.startswith("/host:"):
            md = plane.event_metadata
            keep = set(ENGINE_SPANS) | set(annotations)
            for ln in plane.lines:
                marks += [(md[m].name, a, b) for a, b, m in _events(ln)
                          if md[m].name in keep]
    if first_ops is None:
        return None
    harness = [m for m in marks if m[0] in annotations]
    window = harness or marks
    gaps = []
    if window:
        lo, hi = min(a for _n, a, _b in window), max(b for _n, _a, b in window)
        busy = trace_reduce.union(trace_reduce.clip(first_ops, lo, hi))
        for a, b in trace_reduce.complement(busy, lo, hi):
            mid = (a + b) // 2
            over = [m for m in marks if m[1] <= mid <= m[2]]
            label = min(over, key=lambda m: m[2] - m[1])[0] if over else "none"
            h_over = [m for m in over if m[0] in annotations]
            h = min(h_over, key=lambda m: m[2] - m[1])[0] if h_over else "none"
            gaps.append((label, h, (b - a) * 1e-12))
    spans: Dict[str, int] = {}
    for name, _a, _b in marks:
        if name in ENGINE_SPANS:
            spans[name] = spans.get(name, 0) + 1
    return StageReduction({k: v * 1e-12 for k, v in stage_ps.items()},
                          superstep_ps * 1e-12, gaps, spans)


_CACHE: Dict[tuple, Optional[StageReduction]] = {}


def reduce_path(path: str, annotations: Sequence[str] = HARNESS) -> Optional[StageReduction]:
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size, tuple(annotations))
    if key not in _CACHE:
        _CACHE[key] = reduce_xspace(load_xspace(path), annotations)
    return _CACHE[key]


def for_run(run) -> Optional[StageReduction]:
    """The reduction of a traced run's trace (``benchmark/out/trace/<cell>``);
    None for an untraced run. Logs the whole split once per trace."""
    if run.trace is None:
        return None
    path = trace_reduce.find_xplane(os.path.join(HERE, "out", "trace", run.cell.name))
    fresh = path not in {k[0] for k in _CACHE}
    r = reduce_path(path)
    if fresh and r is not None:
        levels = sum(c.levels for c in run.checks)
        per_level = {k: 1e3 * v / levels for k, v in r.stage_s.items()} if levels else {}
        print(f"[stage_trace] {json.dumps(dict(r.summary(), ms_per_level=per_level))}",
              file=sys.stderr, flush=True)
    return r


def stage_ms_per_level(run, stages: Sequence[str]) -> Optional[float]:
    """Device ms of ``stages`` per level committed in the traced window;
    None without a trace, without levels or where no stage is named."""
    r = for_run(run)
    levels = sum(c.levels for c in run.checks)
    if r is None or not r.named or levels == 0:
        return None
    return 1e3 * sum(r.stage_s[s] for s in stages) / levels


def compile_log_s(run, events: Sequence[str]) -> Optional[float]:
    """Seconds of the program's compile-log ``events`` (``obs.compile_log``)
    that ended before the window's first check: the union of their
    intervals. None where the program keeps no such log."""
    obs = sys.modules.get("stateright_tpu.obs")
    log = getattr(obs, "compile_log", None)
    if log is None or not run.checks:
        return None
    start = run.checks[0].start
    spans = [(end - secs, end) for name, end, secs in list(log)
             if name in events and end <= start]
    secs = float(sum(b - a for a, b in trace_reduce.union(spans)))
    print(f"[stage_trace] set-up {run.setup_s:.3f} s: {secs:.3f} s in "
          f"{'+'.join(events)} ({len(spans)} events)", file=sys.stderr, flush=True)
    return secs


# --- the testdata cut -------------------------------------------------------


def cut(xs, check: int, annotations: Sequence[str] = HARNESS):
    """A copy of ``xs`` holding the ``check``-th check only (from its
    ``spawn_xla`` to the end of its ``verdict``): the device planes'
    ``XLA Ops`` and ``XLA Modules`` events in that span, with their
    metadata, and the host plane's harness and engine annotations."""
    host = next(p for p in xs.planes if p.name.startswith("/host:CPU"))
    hmd = host.event_metadata
    marks = sorted((a, b, hmd[m].name) for ln in host.lines for a, b, m in _events(ln)
                   if hmd[m].name in annotations)
    starts = [m for m in marks if m[2] == annotations[0]]
    lo = starts[check][0]
    hi = next(b for a, b, n in marks if n == annotations[-1] and a >= lo)
    out = xplane_pb2().XSpace()
    keep_names = set(annotations) | set(ENGINE_SPANS)
    for plane in xs.planes:
        is_dev = plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU")
        if not (is_dev or plane is host):
            continue
        p = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        for ln in plane.lines:
            if is_dev and ln.name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
                continue
            nl = p.lines.add(id=ln.id, display_id=ln.display_id, name=ln.name,
                             display_name=ln.display_name, timestamp_ns=ln.timestamp_ns)
            base = ln.timestamp_ns * 1000
            for e in ln.events:
                a = base + e.offset_ps
                if not lo <= a <= a + e.duration_ps <= hi:
                    continue
                if not is_dev and plane.event_metadata[e.metadata_id].name not in keep_names:
                    continue
                ne = nl.events.add()
                ne.CopyFrom(e)
                used.add(e.metadata_id)
        for mid in used:
            p.event_metadata[mid].CopyFrom(plane.event_metadata[mid])
        for sid, sm in plane.stat_metadata.items():
            p.stat_metadata[sid].CopyFrom(sm)
    return out


def main(argv: Sequence[str]) -> int:
    try:
        if argv and argv[0] == "--cut":
            src, check, dst = argv[1], int(argv[2]), argv[3]
            path = src if os.path.isfile(src) else trace_reduce.find_xplane(src)
            data = cut(load_xspace(path), check).SerializeToString()
            with gzip.open(dst, "wb") as f:
                f.write(data)
            print(f"{dst}: {len(data)} bytes before compression", file=sys.stderr)
            return 0
        src = argv[0]
        path = src if os.path.isfile(src) else trace_reduce.find_xplane(src)
        r = reduce_xspace(load_xspace(path), tuple(argv[1:]) or HARNESS)
    except RuntimeError as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps(None if r is None else r.summary()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
