"""The observability layer (stateright_tpu/obs; docs/observability.md):
span JSONL schema, Chrome trace-event export validity, the heartbeat
protocol, the unified ``checker.metrics()`` snapshot, the normalized
``dispatch_log`` shape, the metrics time-series recorder (row schema,
keep-K rotation, quiescent-boundary-only sampling), the engine's spans
and stage names on the ``jax.profiler`` trace, the compile-duration log,
and the zero-overhead guarantee with tracing/recording off.

These are SCHEMA pins: consumers (tools/roofline.py --measured, the
bench watchdog, supervise.py, Perfetto, obs/promexport.py, the
``/.dash`` dashboard) parse these artifacts, so a key rename here is a
breaking change, not a refactor.
"""

import glob
import json
import os
import re
import subprocess
import sys
import time
from collections import Counter

import pytest

from stateright_tpu import obs
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys
from stateright_tpu.obs import heartbeat as hb_mod
from stateright_tpu.parallel import default_mesh

KW = dict(frontier_capacity=1 << 10, table_capacity=1 << 13)

#: ONE shared model instance: compiled supersteps cache on the model, so
#: every test after the first reuses the XLA programs instead of paying a
#: fresh compile per spawn (~3 s each on this 1-core box). Every spawn in
#: this file passes explicit capacities, so learned capacity hints from
#: growth-exercising tests never change another test's schedule.
MODEL = PackedTwoPhaseSys(3)


def _spawn(**kw):
    merged = {**KW, **kw}
    return MODEL.checker().spawn_xla(**merged)

#: The span-line schema (exactly these keys, docs/observability.md).
#: ``span_id`` joined the pin in the distributed-tracing round; records
#: from a tracer carrying a trace CONTEXT additionally hold
#: ``trace_id``/``parent_id`` (CTX_SPAN_KEYS) — absent otherwise, so
#: context-less traces stay byte-compatible with older consumers.
SPAN_KEYS = {"ts", "dur", "name", "span_id", "attrs"}
CTX_SPAN_KEYS = SPAN_KEYS | {"trace_id", "parent_id"}
#: Attributes every dispatch span carries.
DISPATCH_ATTRS = {
    "flavor", "bucket", "cand", "committed", "compile", "retry",
    "dedup", "compaction",
}
#: The stable device-engine metrics key set (single-chip engine; the mesh
#: engine adds mesh gauges on top of the same set).
METRIC_KEYS = {
    "engine", "backend", "dedup", "compaction", "symmetry", "ladder",
    "cand_ladder_k",
    "shrink_exit", "levels_per_dispatch", "state_count",
    "unique_state_count", "depth", "max_depth", "frontier_count",
    "frontier_capacity", "table_capacity", "table_occupancy", "dispatches",
    "levels_committed", "cand_retries", "hv", "table_grows",
    "frontier_grows", "cand_grows", "delta_flushes", "shrink_exits",
    "ladder_jumps",
    # recovery keys (docs/observability.md "Recovery"): the auto-
    # checkpoint config gauge, resume provenance, the last checkpointed
    # level, and the write counter.
    "checkpoint_to", "resumed_from", "last_checkpoint_level",
    "checkpoints_written",
    # time-series config gauge (docs/observability.md "Time series").
    "metrics_to",
}
#: Keys the single-chip engine adds: the property stage's row counters
#: (docs/observability.md "checker.metrics()").
SINGLE_CHIP_METRIC_KEYS = {"property_rows", "property_block_rows"}

#: The metrics time-series row schema (exactly these keys;
#: docs/observability.md "Time series" — promexport, the dashboard, and
#: roofline's series mode parse these).
RECORDER_ROW_KEYS = {"v", "unix_ts", "t", "seq", "kind", "metrics"}


def _spans(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh]


# --- span JSONL -----------------------------------------------------------


def test_span_jsonl_schema(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    c = _spawn(trace=trace).join()
    assert c.unique_state_count() == 288
    lines = _spans(trace)
    assert lines, "trace is empty"
    for rec in lines:
        assert set(rec) == SPAN_KEYS, rec  # no ctx set -> no ctx keys
        assert isinstance(rec["ts"], (int, float)) and rec["ts"] >= 0
        assert isinstance(rec["dur"], (int, float)) and rec["dur"] >= 0
        assert isinstance(rec["name"], str)
        assert isinstance(rec["span_id"], str)
        assert isinstance(rec["attrs"], dict)
    assert len({r["span_id"] for r in lines}) == len(lines)
    assert lines[0]["name"] == "trace_start"
    assert {"pid", "unix_ts"} <= set(lines[0]["attrs"])
    disp = [r for r in lines if r["name"] == "dispatch"]
    assert disp, "no dispatch spans"
    for rec in disp:
        assert DISPATCH_ATTRS <= set(rec["attrs"]), rec["attrs"]
    # Span-level accounting agrees with the engine's own telemetry: one
    # span per device call, committed levels summing to the level log.
    assert len(disp) == len(c.dispatch_log)
    assert sum(r["attrs"]["committed"] for r in disp) == len(c.level_log)
    # The first call of each bucket compiles; 2pc(3) from a cold model
    # compiles at least its first program.
    assert any(r["attrs"]["compile"] for r in disp)


def test_trace_env_knob(tmp_path, monkeypatch):
    trace = str(tmp_path / "env_trace.jsonl")
    monkeypatch.setenv("STPU_TRACE", trace)
    c = _spawn().join()
    assert c._tracer.enabled
    assert any(r["name"] == "dispatch" for r in _spans(trace))


# --- Chrome export --------------------------------------------------------


def test_chrome_export_valid(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    out = str(tmp_path / "chrome.json")
    _spawn(trace=trace).join()
    n = obs.export_chrome(trace, out)
    assert n > 0
    with open(out) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"]
    assert len(events) == n
    for ev in events:
        # The Chrome trace-event contract Perfetto loads: complete ("X")
        # events with microsecond ts/dur and pid/tid lanes, plus "C"
        # counter samples for spans carrying mux-lane telemetry.
        assert ev["ph"] in ("X", "C")
        if ev["ph"] == "X":
            assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                    "args"} <= set(ev)
            assert isinstance(ev["dur"], (int, float))
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["args"], dict)


def test_chrome_env_knob_exports_on_close(tmp_path, monkeypatch):
    trace = str(tmp_path / "trace.jsonl")
    chrome = str(tmp_path / "chrome.json")
    monkeypatch.setenv("STPU_TRACE", trace)
    monkeypatch.setenv("STPU_TRACE_CHROME", chrome)
    c = _spawn().join()
    c._tracer.close()  # atexit does this in real runs
    with open(chrome) as fh:
        assert json.load(fh)["traceEvents"]


def test_chrome_mux_lane_counter_track():
    """A span carrying ``lanes_active`` renders as a Perfetto counter
    track ("C" event, lanes_active + derived lanes_idle) next to its
    slice — the mux lane-occupancy chart."""
    from stateright_tpu.obs.trace import chrome_events

    rec = {"ts": 1.5, "dur": 0.25, "name": "dispatch", "span_id": "a.1",
           "attrs": {"flavor": "mux", "lanes": 4, "lanes_active": 3}}
    evs = chrome_events(rec, pid=7, tid=2)
    assert [e["ph"] for e in evs] == ["X", "C"]
    slice_, counter = evs
    assert slice_["ts"] == counter["ts"] == 1.5e6
    assert counter["name"] == "mux lanes"
    assert counter["args"] == {"lanes_active": 3, "lanes_idle": 1}
    # Context ids ride in the slice's args when present.
    rec2 = dict(rec, trace_id="t" * 16, parent_id="a.0")
    args = chrome_events(rec2, pid=7, tid=2)[0]["args"]
    assert args["trace_id"] == "t" * 16 and args["parent_id"] == "a.0"


# --- distributed tracing (docs/observability.md "Distributed tracing") ----


def test_trace_ctx_env_inheritance(tmp_path, monkeypatch):
    """STPU_TRACE_CTX is the cross-process seam: a tracer constructed
    under it stamps every record with the trace id and defaults parents
    to the context's span — engine spans in a worker join the
    submission's trace with zero engine changes."""
    from stateright_tpu.obs import trace as trace_mod

    tid = trace_mod.new_trace_id()
    assert len(tid) == 16
    monkeypatch.setenv(trace_mod.CTX_ENV, trace_mod.format_ctx(tid, "p.9"))
    trace = str(tmp_path / "trace.jsonl")
    c = _spawn(trace=trace).join()
    assert c._tracer.trace_id == tid
    lines = _spans(trace)
    for rec in lines:
        assert SPAN_KEYS <= set(rec) <= CTX_SPAN_KEYS, rec
        assert rec["trace_id"] == tid
        assert rec["parent_id"] == "p.9"
    # Malformed ctx degrades to context-less tracing, not a failure.
    assert trace_mod.parse_ctx(":") is None
    assert trace_mod.parse_ctx("") is None
    assert trace_mod.parse_ctx("abc") == ("abc", None)


def test_tracer_emit_overrides_and_preallocated_ids(tmp_path):
    """Tracer.emit's per-record overrides: a shared tracer (one service
    file, many jobs) stamps per-job trace ids without mutating ambient
    state, and new_span_id pre-allocates so children can reference a
    span emitted after they finish (the attempt span)."""
    from stateright_tpu.obs.trace import Tracer

    path = str(tmp_path / "t.jsonl")
    tr = Tracer(path)
    pre = tr.new_span_id()
    child = tr.emit("child", t0=0.0, dur=0.1, parent_id=pre,
                    trace_id="aaaa", attrs={"k": 1})
    got = tr.emit("parent", t0=0.0, dur=0.2, trace_id="bbbb", span_id=pre)
    assert got == pre and child != pre
    tr.emit("ambient", t0=0.0, dur=0.0)
    tr.close()
    recs = {r["name"]: r for r in _spans(path)}
    assert recs["child"]["trace_id"] == "aaaa"
    assert recs["child"]["parent_id"] == pre
    assert recs["parent"]["trace_id"] == "bbbb"
    assert recs["parent"]["span_id"] == pre
    assert "trace_id" not in recs["ambient"]  # no ambient ctx set


# --- the profiler's clock (docs/observability.md "Profiler plane") ---------

#: The superstep's named stages and the fused loop's own scope.
STAGES = ("properties", "expand", "compact", "fingerprint", "insert",
          "terminal", "ladder")

#: One compiled-HLO instruction of the kinds that carry the superstep's
#: work, with its op_name.
HEAVY_RE = re.compile(
    r'^\s*(?:ROOT )?%\S+ = .*?\b(?:fusion|sort|gather|scatter)\(.*'
    r'op_name="([^"]*)"', re.M,
)


def _fused_hlo(model, **kw):
    """The compiled text of the planes engine's first fused program for a
    fresh ``model`` (``dedup="sorted"`` selects the planes superstep the
    chip runs; the CPU default is the rows engine)."""
    c = model.checker().spawn_xla(dedup="sorted", **KW, **kw)
    run_cap = c._run_cap_for(c._frontier_count)
    fn = c._fused_for(run_cap)
    calls = []

    def spy(*args):
        calls.append(args)
        return fn(*args)

    c._superstep_cache[c._fused_key(run_cap)] = spy
    c.join()
    return fn.lower(*calls[0]).compile().as_text()


def _eventually_graph():
    from stateright_tpu.core import Property
    from stateright_tpu.test_util import DGraph, PackedDGraph

    return PackedDGraph(
        DGraph.with_property(Property.eventually("odd", lambda _, s: s % 2 == 1))
        .with_path([0, 2, 4])
        .with_path([0, 6, 8, 10])
    )


@pytest.mark.parametrize("case", ["2pc", "eventually"])
def test_superstep_stages_named_in_compiled_program(case):
    """Each stage of the planes superstep and the fused loop's own work
    run under a ``jax.named_scope``: the names reach the compiled
    program's op_name metadata, which a profiler trace carries on every
    device operation. 2pc has no eventually property, so its terminal
    pass compiles to nothing."""
    if case == "2pc":
        model, expect = PackedTwoPhaseSys(3), set(STAGES) - {"terminal"}
    else:
        model, expect = _eventually_graph(), set(STAGES)
    hlo = _fused_hlo(model)
    named = {p for n in re.findall(r'op_name="([^"]*)"', hlo)
             for p in n.split("/") if p in STAGES}
    assert expect <= named, expect - named
    body = [n for n in HEAVY_RE.findall(hlo) if "/while/body/" in n]
    assert body
    staged = [n for n in body if set(n.split("/")) & set(STAGES)]
    assert len(staged) > 0.9 * len(body), [n for n in body if n not in staged]


@pytest.mark.parametrize("compaction", ["sort", "gather"])
def test_sort_compaction_recovers_parents_without_gathers(compaction, monkeypatch):
    """Where the sort lowering of the grid compaction recovers candidate
    parents by merge (here at every width), no ``gather`` in the compiled
    program carries the ``compact`` scope. The gather lowering, which
    indexes by the permutation, shows that the pattern finds them."""
    from stateright_tpu import xla

    monkeypatch.setattr(xla, "PARENT_MERGE_MIN", 1)
    hlo = _fused_hlo(PackedTwoPhaseSys(3), compaction=compaction)
    gathers = [
        n for n in re.findall(r'\bgather\(.*op_name="([^"]*)"', hlo)
        if "compact" in n.split("/")
    ]
    assert bool(gathers) == (compaction == "gather"), gathers


def test_engine_spans_on_the_profiler_host_plane(tmp_path):
    """Engine spans are ``jax.profiler`` annotations: a check under a
    profiler session puts ``spawn`` and the three parts of each
    ``dispatch`` on the trace's host plane, one ``dispatch`` per
    ``dispatch_log`` entry."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        c = _spawn(levels_per_dispatch=4).join()
    assert c.unique_state_count() == 288
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = Counter(
        e.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    )
    assert names["spawn"] == names["spawn:init_insert"] == 1
    assert names["dispatch"] == len(c.dispatch_log) > 1
    for part in ("dispatch:prepare", "dispatch:run", "dispatch:commit"):
        assert names[part] == names["dispatch"], part


def test_compile_log_records_lowering_with_end_times():
    """``obs.compile_log``: JAX's tracing and lowering durations of a fresh
    model's programs, each with its end on ``time.monotonic``; entries of
    one kind never overlap (nested traces fold into the outer one)."""
    t0 = time.monotonic()
    PackedTwoPhaseSys(3).checker().spawn_xla(**KW).join()
    t1 = time.monotonic()
    fresh = [e for e in obs.compile_log if e[1] >= t0]
    assert {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration"} <= {
        name for name, _, _ in fresh
    }
    for name, end, secs in fresh:
        assert name in obs.compiles.EVENTS
        assert t0 <= end <= t1 and 0 <= secs <= t1 - t0
    for kind in obs.compiles.EVENTS:
        spans = sorted((end - secs, end) for name, end, secs in fresh if name == kind)
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert start >= end - 1e-6, kind


def test_obs_imports_without_jax():
    """``obs`` and ``supervise`` import, and spans open, with jax absent
    (the supervisor process stays free of it)."""
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "import stateright_tpu.obs as obs, stateright_tpu.supervise\n"
        "with obs.NULL_TRACER.span('spawn'): pass\n"
        "print(len(obs.compile_log))\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "0"


# --- heartbeat ------------------------------------------------------------


def test_heartbeat_advances_once_per_committed_dispatch(tmp_path):
    hb = str(tmp_path / "hb.json")
    c = _spawn(heartbeat=hb, levels_per_dispatch=1)
    mtime0 = None
    while not c.is_done():
        c._run_block()
        rec = hb_mod.read(hb)
        # One seq bump per completed device dispatch — the same unit as
        # one dispatch_log entry — and the commit beat marks idle.
        assert rec is not None
        assert rec["seq"] == len(c.dispatch_log)
        assert rec["phase"] == "idle"
        mtime = os.stat(hb).st_mtime_ns
        if mtime0 is not None:
            assert mtime >= mtime0
        mtime0 = mtime
    assert c.unique_state_count() == 288
    rec = hb_mod.read(hb)
    assert rec["seq"] == len(c.dispatch_log) > 0
    assert {"ts", "seq", "phase", "depth", "states"} <= set(rec)
    assert hb_mod.age_s(hb) is not None


def test_heartbeat_mtime_advances_between_dispatches(tmp_path):
    hb = str(tmp_path / "hb.json")
    c = _spawn(heartbeat=hb, levels_per_dispatch=1)
    stamps = []
    while not c.is_done():
        c._run_block()
        stamps.append((os.stat(hb).st_mtime_ns, hb_mod.read(hb)["seq"]))
    seqs = [s for _, s in stamps]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    mts = [m for m, _ in stamps]
    assert mts == sorted(mts)
    assert mts[-1] > mts[0]


# --- metrics --------------------------------------------------------------


@pytest.mark.parametrize("dedup", ["hash", "sorted", "delta"])
def test_metrics_keys_across_dedups(dedup):
    c = _spawn(dedup=dedup).join()
    m = c.metrics()
    assert METRIC_KEYS <= set(m), METRIC_KEYS - set(m)
    assert SINGLE_CHIP_METRIC_KEYS <= set(m)
    assert m["engine"] == "xla"
    assert m["dedup"] == dedup
    # 2pc declares no property block: whole buckets every level.
    assert m["property_block_rows"] == 0
    assert m["property_rows"] == sum(lv["bucket"] for lv in c.level_log)
    assert m["state_count"] == c.state_count() == 1146
    assert m["unique_state_count"] == 288
    assert m["dispatches"] == len(c.dispatch_log)
    assert m["levels_committed"] == len(c.level_log)
    assert 0 < m["table_occupancy"] <= 1
    for counter in (
        "table_grows", "frontier_grows", "cand_grows", "delta_flushes",
        "shrink_exits", "ladder_jumps", "checkpoints_written",
    ):
        assert isinstance(m[counter], int) and m[counter] >= 0
    # No checkpointing configured on this spawn: the recovery gauges read
    # as the documented "off" values.
    assert m["checkpoint_to"] is None
    assert m["resumed_from"] is None
    assert m["last_checkpoint_level"] is None
    assert m["checkpoints_written"] == 0
    json.dumps(m)  # the snapshot is JSON-serializable as-is


def test_metrics_counts_growth_events():
    # A deliberately undersized table forces visited-set growth; the
    # event lands in the unified snapshot.
    c = _spawn(table_capacity=1 << 6).join()
    assert c.unique_state_count() == 288
    assert c.metrics()["table_grows"] >= 1


def test_base_checker_metrics():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    c = TwoPhaseSys(2).checker().spawn_bfs().join()
    m = c.metrics()
    assert {"engine", "state_count", "unique_state_count", "max_depth"} <= set(m)
    assert m["state_count"] == c.state_count()


def test_explorer_status_carries_metrics():
    from stateright_tpu.checker.explorer import make_app

    app, _ = make_app(
        PackedTwoPhaseSys(2).checker(),
        frontier_capacity=1 << 8, table_capacity=1 << 10,
    )
    status = app.status()
    m = status["metrics"]
    assert m["engine"] == "xla"
    assert "pending_pool" in m and "waiting" in m  # on-demand gauges
    # Recovery state is part of the status surface: a wedged interactive
    # session must be diagnosable (and resumable) from /.status alone.
    assert "last_checkpoint" in status
    # Liveness too: heartbeat_age_s rides next to last_checkpoint — None
    # here (no heartbeat configured), a float age when the protocol is on.
    assert status["heartbeat_age_s"] is None


def test_checkpoint_span_per_write(tmp_path):
    # Every auto-checkpoint write emits one "checkpoint" span whose attrs
    # name the file, the depth it captured, and the rotation bound — and
    # the span count agrees with the checkpoints_written counter.
    trace = str(tmp_path / "ck_trace.jsonl")
    ck = str(tmp_path / "ck.npz")
    c = _spawn(
        trace=trace, checkpoint_to=ck, checkpoint_every=1,
        levels_per_dispatch=1,
    ).join()
    m = c.metrics()
    assert m["checkpoints_written"] >= 1
    assert m["checkpoint_to"] == ck
    assert m["last_checkpoint_level"] is not None
    spans = [r for r in _spans(trace) if r["name"] == "checkpoint"]
    assert len(spans) == m["checkpoints_written"]
    for rec in spans:
        assert {"path", "depth", "keep"} <= set(rec["attrs"])


# --- metrics time-series recorder ----------------------------------------


def test_recorder_rows_schema_and_quiescent_cadence(tmp_path):
    from stateright_tpu.obs import read_series

    series = str(tmp_path / "metrics.jsonl")
    # Level cadence 1 + one level per dispatch: a sample opportunity at
    # every quiescent boundary, so the series traces the whole run.
    c = _spawn(metrics_to=series, metrics_every=1, levels_per_dispatch=1).join()
    assert c.unique_state_count() == 288
    assert c.metrics()["metrics_to"] == series
    rows = read_series(series)
    assert rows, "series is empty"
    for rec in rows:
        assert set(rec) == RECORDER_ROW_KEYS, rec
        assert rec["v"] == 1
        assert rec["kind"] == "engine"
        assert isinstance(rec["t"], (int, float)) and rec["t"] >= 0
        # Each row embeds a full metrics() snapshot (stable key set).
        assert METRIC_KEYS <= set(rec["metrics"]), rec["metrics"]
    assert [r["seq"] for r in rows] == list(range(len(rows)))
    # Quiescent-boundary-only sampling: never more samples than device
    # dispatches (each dispatch ends in at most one quiescent point) and
    # the embedded progress gauges advance monotonically.
    assert len(rows) <= len(c.dispatch_log)
    depths = [r["metrics"]["depth"] for r in rows]
    states = [r["metrics"]["state_count"] for r in rows]
    assert depths == sorted(depths)
    assert states == sorted(states)
    # The wall-clock cadence spec parses too (no run needed to pin the
    # grammar — it is the checkpoint module's).
    from stateright_tpu.obs import MetricsRecorder

    r = MetricsRecorder(str(tmp_path / "w.jsonl"), every="2.5s")
    assert r.every_seconds == 2.5 and r.every_levels is None
    with pytest.raises(ValueError):
        MetricsRecorder(str(tmp_path / "bad.jsonl"), every="nope")


def test_recorder_rotation_and_torn_tail(tmp_path):
    from stateright_tpu.obs import MetricsRecorder, read_series
    from stateright_tpu.obs.timeseries import series_files

    base = str(tmp_path / "metrics.jsonl")
    rec = MetricsRecorder(base, every=1, keep=3, rotate_rows=4)
    for i in range(10):
        rec.sample({"state_count": i})
    # 10 rows at 4/file: two full rotations + 2 live rows, keep=3 retains
    # all of them; the chain reads back oldest-first and in order.
    assert series_files(base) == [f"{base}.2", f"{base}.1", base]
    rows = read_series(base)
    assert [r["metrics"]["state_count"] for r in rows] == list(range(10))
    assert [r["seq"] for r in rows] == list(range(10))
    # keep bounds the chain: 8 more rows shift two more rotations and the
    # oldest files fall off the end.
    for i in range(10, 18):
        rec.sample({"state_count": i})
    assert series_files(base) == [f"{base}.2", f"{base}.1", base]
    rows = read_series(base)
    # rows 0..7 fell off the end of the keep=3 chain; 8..17 survive.
    assert [r["metrics"]["state_count"] for r in rows] == list(range(8, 18))
    # A torn tail (kill mid-append) is skipped, not fatal; the window
    # argument trims to the newest N.
    rec.sample({"state_count": 99})
    rec.close()
    with open(base, "a") as fh:
        fh.write('{"v": 1, "metrics": {"state_coun')
    rows = read_series(base)
    assert rows[-1]["metrics"]["state_count"] == 99
    assert [r["metrics"]["state_count"] for r in read_series(base, window=2)] == [17, 99]
    # A recorder RE-OPENED over the torn file (the requeued worker's
    # resume path) repairs the tail first: its next row lands on its own
    # line instead of concatenating onto the fragment and vanishing.
    rec2 = MetricsRecorder(base, every=1, keep=3, rotate_rows=100)
    rec2.sample({"state_count": 100})
    rows = read_series(base)
    assert [r["metrics"]["state_count"] for r in rows[-2:]] == [99, 100]
    rec2.close()


def test_recorder_env_knob(tmp_path, monkeypatch):
    from stateright_tpu.obs import read_series

    series = str(tmp_path / "env_metrics.jsonl")
    monkeypatch.setenv("STPU_METRICS_TO", series)
    monkeypatch.setenv("STPU_METRICS_EVERY", "1")
    c = _spawn().join()
    assert c._recorder is not None and c._recorder.path == series
    assert read_series(series)


# --- dispatch_log contract ------------------------------------------------


def _check_dispatch_log_shape(log):
    for entry in log:
        assert isinstance(entry, tuple) and len(entry) == 2, entry
        cap, committed = entry
        assert isinstance(cap, int) and cap > 0
        assert isinstance(committed, int) and committed >= 0


def test_dispatch_log_contract_single_vs_fused():
    # ONE documented shape on both dispatch paths (xla.py): one
    # (run_cap, committed_levels) per device call; the one-level path is
    # the committed∈{0,1} special case; on both, committed levels sum to
    # the level log.
    single = _spawn(levels_per_dispatch=1).join()
    fused = _spawn().join()
    for c in (single, fused):
        _check_dispatch_log_shape(c.dispatch_log)
        assert sum(n for _, n in c.dispatch_log) == len(c.level_log)
    assert all(n in (0, 1) for _, n in single.dispatch_log)
    assert any(n > 1 for _, n in fused.dispatch_log)


def test_dispatch_log_records_uncommitted_dispatches():
    # A frontier capacity below the space's peak width forces
    # grow-and-retry rounds. On the one-level path the overflowed level's
    # device call is a committed == 0 entry; a fused block instead
    # commits the pre-overflow prefix (possibly > 0) and re-enters. Both
    # keep the sum invariant.
    # Fresh models here, NOT the shared one: the jump ladder prefers an
    # already-compiled larger bucket, and the shared model's program
    # cache would let the run sidestep the forced overflow entirely.
    single = PackedTwoPhaseSys(3).checker().spawn_xla(
        frontier_capacity=16, table_capacity=1 << 13,
        levels_per_dispatch=1,
    ).join()
    assert single.unique_state_count() == 288
    _check_dispatch_log_shape(single.dispatch_log)
    assert sum(n for _, n in single.dispatch_log) == len(single.level_log)
    assert any(n == 0 for _, n in single.dispatch_log)
    assert single.metrics()["frontier_grows"] >= 1

    # (The fused path's prefix-commit behavior under the same squeeze is
    # covered by the sum invariant asserted in every other test here —
    # not re-run with a second fresh model, which would cost another
    # cold-compile schedule on this 1-core box.)


# --- mesh engine ----------------------------------------------------------


def test_sharded_dispatch_log_metrics_and_heartbeat(tmp_path):
    from stateright_tpu.obs import read_series

    trace = str(tmp_path / "mesh.jsonl")
    hb = str(tmp_path / "mesh_hb.json")
    series = str(tmp_path / "mesh_metrics.jsonl")
    c = _spawn(
        mesh=default_mesh(), trace=trace, heartbeat=hb,
        metrics_to=series, metrics_every=1,
    ).join()
    assert c.unique_state_count() == 288
    _check_dispatch_log_shape(c.dispatch_log)
    m = c.metrics()
    # Same stable key set as the single-chip engine, plus mesh gauges.
    assert METRIC_KEYS <= set(m), METRIC_KEYS - set(m)
    assert m["engine"] == "xla-sharded"
    assert m["shards"] == 8 and "route_grows" in m
    disp = [r for r in _spans(trace) if r["name"] == "dispatch"]
    assert len(disp) == len(c.dispatch_log)
    assert hb_mod.read(hb)["seq"] == len(c.dispatch_log)
    # The mesh engine records the same time-series contract: full
    # snapshots at quiescent boundaries only.
    rows = read_series(series)
    assert rows and all(set(r) == RECORDER_ROW_KEYS for r in rows)
    assert len(rows) <= len(c.dispatch_log)
    assert rows[-1]["metrics"]["engine"] == "xla-sharded"


# --- zero overhead when off ----------------------------------------------


def test_tracing_off_is_nulled_and_bit_identical(tmp_path):
    from stateright_tpu.obs.trace import NULL_TRACER

    off = _spawn().join()
    # No obs machinery on the hot path: the shared no-op tracer (no
    # clocks, no file), no heartbeat file, no metrics recorder — the
    # recorder shares the tracer's off-by-default pin discipline.
    assert off._tracer is NULL_TRACER
    assert off._heartbeat is None
    assert off._recorder is None

    trace = str(tmp_path / "trace.jsonl")
    hb = str(tmp_path / "hb.json")
    on = _spawn(
        trace=trace, heartbeat=hb,
        metrics_to=str(tmp_path / "metrics.jsonl"), metrics_every=1,
    ).join()
    # Engine results are bit-identical with tracing on: same counts, same
    # schedule, same per-level telemetry (spans only *observe* host
    # boundaries; they never change what runs on the device).
    assert (off.state_count(), off.unique_state_count(), off.max_depth()) == (
        on.state_count(), on.unique_state_count(), on.max_depth(),
    )
    assert off.level_log == on.level_log
    assert off.dispatch_log == on.dispatch_log
    assert {n: p.into_actions() for n, p in off.discoveries().items()} == {
        n: p.into_actions() for n, p in on.discoveries().items()
    }


def test_no_profiler_no_trace_writes_nothing(tmp_path, monkeypatch):
    """With no profiler session and no ``STPU_TRACE`` the spans are bare
    annotations: the run writes no file, and its results are
    bit-identical to a run whose spans a profiler session records."""
    import jax
    from stateright_tpu.obs.trace import NULL_TRACER

    for var in ("STPU_TRACE", "STPU_TRACE_CHROME", "STPU_HEARTBEAT",
                "STPU_METRICS_TO"):
        monkeypatch.delenv(var, raising=False)
    quiet = tmp_path / "quiet"
    quiet.mkdir()
    monkeypatch.chdir(quiet)
    off = _spawn().join()
    assert off._tracer is NULL_TRACER
    assert list(quiet.iterdir()) == []
    with jax.profiler.trace(str(tmp_path / "profile")):
        on = _spawn().join()
    assert (off.state_count(), off.unique_state_count(), off.max_depth()) == (
        on.state_count(), on.unique_state_count(), on.max_depth(),
    )
    assert off.level_log == on.level_log
    assert off.dispatch_log == on.dispatch_log
    assert {n: p.into_actions() for n, p in off.discoveries().items()} == {
        n: p.into_actions() for n, p in on.discoveries().items()
    }
