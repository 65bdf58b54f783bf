"""The distributed-trace merge (stateright_tpu/obs/collect.py) and the
tier-0 trace drill (docs/observability.md "Distributed tracing").

Unit pins: run-dir trace discovery order, session parsing under torn
heads and garbage lines, and the flow-arrow contract (one arc per
trace_id over the anchor spans, Chrome "s"/"t"/"f" phases with the
arrowhead bound to the enclosing slice).

``test_smoke_trace_merge`` is the <30s drill that rides in
``tools/smoke.sh``: one traced packed-model run, one 2-job service round
with tracing on, merged via ``obs.collect`` into a single Chrome trace —
validated for schema, per-process time alignment, resolvable flow arrows
(every admitted job's spans share one trace_id from submit through
dispatch), and the ``dispatch:*`` parts nested in their dispatch.
"""

import json
import os
from collections import Counter

from stateright_tpu.obs import collect
from stateright_tpu.service import CheckerService, ServiceConfig

#: Pinned full-coverage (generated, unique) counts for 2pc:3.
PINNED_2PC3 = (1_146, 288)

#: The four chrome event kinds the merger may emit, plus flow phases.
SLICE_KEYS = {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"}


def _write_trace(path, records, unix_ts=1000.0, pid=7):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as fh:
        fh.write(json.dumps({
            "ts": 0.0, "dur": 0.0, "name": "trace_start", "span_id": "x.0",
            "attrs": {"pid": pid, "unix_ts": unix_ts},
        }) + "\n")
        for rec in records:
            fh.write(json.dumps(rec) + "\n")


def _span(name, ts, dur=0.1, sid="x.1", **extra):
    rec = {"ts": ts, "dur": dur, "name": name, "span_id": sid, "attrs": {}}
    rec.update(extra)
    return rec


# --- unit pins --------------------------------------------------------------


def test_trace_files_discovery_order(tmp_path):
    root = str(tmp_path / "run")
    for rel in ("device-1/job-0001", "device-0", "."):
        _write_trace(os.path.join(root, rel, "trace.jsonl"), [])
    rels = [os.path.relpath(p, root) for p in collect.trace_files(root)]
    assert rels == [
        "device-0/trace.jsonl", "device-1/job-0001/trace.jsonl",
        "trace.jsonl",
    ]
    assert collect.trace_files(str(tmp_path / "nope")) == []


def test_sessions_tolerate_torn_head_and_garbage(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps(_span("dispatch", 0.5)) + "\n")  # torn head
        fh.write("{not json\n")
        fh.write(json.dumps({"v": 1}) + "\n")  # dict, but not a span
    _write_trace(path, [_span("dispatch", 1.0)])  # appended real session
    sessions = collect._read_sessions(path)
    assert len(sessions) == 2
    assert sessions[0]["unix_ts"] is None  # synthetic, for the torn head
    assert len(sessions[0]["records"]) == 1
    assert sessions[1]["unix_ts"] == 1000.0


def test_merge_aligns_sessions_and_draws_flows(tmp_path):
    """Two processes, staggered wall clocks, one shared trace_id: the
    merged timeline rebases onto the earliest session and draws one
    s→t→f arc over the anchors in causal-time order."""
    root = str(tmp_path / "run")
    tid = "ab" * 8
    _write_trace(
        os.path.join(root, "trace.jsonl"),
        [_span("submit", 0.0, sid="a.1", trace_id=tid),
         _span("route", 0.001, sid="a.2", trace_id=tid)],
        unix_ts=1000.0,
    )
    _write_trace(
        os.path.join(root, "svc", "job-0001", "trace.jsonl"),
        [_span("job", 0.0, dur=1.0, sid="b.1", trace_id=tid,
               parent_id="a.1")],
        unix_ts=1002.0,  # this process started 2s later
    )
    doc = collect.collect(root)
    assert doc["otherData"]["traces"] == [tid]
    assert doc["otherData"]["trace_files"] == [
        "svc/job-0001/trace.jsonl", "trace.jsonl",
    ]
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {(m["name"], m["pid"]) for m in meta} >= {
        ("process_name", 1), ("process_name", 2),
    }
    # The later process's job span lands 2s (2e6 us) after the epoch.
    job = next(e for e in evs if e["ph"] == "X" and e["name"] == "job")
    assert job["ts"] == 2e6
    assert job["args"]["trace_id"] == tid
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert [f["ph"] for f in flows] == ["s", "t", "f"]
    assert all(f["id"] == tid for f in flows)
    assert flows[-1]["bp"] == "e"
    assert [f["ts"] for f in flows] == sorted(f["ts"] for f in flows)
    # submit (ts 0) starts the arc; the job anchor ends it on pid 1
    # (the job-dir file sorts first and so owns pid 1).
    assert flows[0]["pid"] == 2 and flows[-1]["pid"] == 1
    # A single-anchor trace draws no arrows (nothing to connect).
    _write_trace(os.path.join(root, "trace.jsonl"),
                 [_span("submit", 5.0, sid="a.9", trace_id="cd" * 8)],
                 unix_ts=1010.0)
    doc2 = collect.collect(root)
    assert "cd" * 8 in doc2["otherData"]["traces"]
    assert all(e["id"] == tid for e in doc2["traceEvents"]
               if e["ph"] in ("s", "t", "f"))


def test_write_dumps_valid_json(tmp_path):
    root = str(tmp_path / "run")
    _write_trace(os.path.join(root, "trace.jsonl"), [_span("submit", 0.0)])
    out = str(tmp_path / "merged.json")
    n = collect.write(root, out)
    with open(out) as fh:
        doc = json.load(fh)
    assert len(doc["traceEvents"]) == n > 0


def test_explorer_merged_trace_route(tmp_path):
    """``GET /.trace.json``: 404 without a service or without any trace
    in the run dir; 200 = the mtime-cached merged export's raw bytes."""
    from stateright_tpu.checker.explorer import ExplorerApp
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    ck = PackedTwoPhaseSys(3).checker().spawn_xla(
        frontier_capacity=1 << 10, table_capacity=1 << 13,
    )
    assert ExplorerApp(ck).merged_trace()[0] == 404  # no service

    base = dict(
        platform="cpu", probe_auto=False, admission_lint=False,
        max_inflight=0,
    )
    dark = CheckerService(ServiceConfig(
        run_dir=str(tmp_path / "dark"), **base))
    try:
        # Tracing off: nothing to merge.
        assert ExplorerApp(ck, service=dark).merged_trace()[0] == 404
    finally:
        dark.close()

    svc = CheckerService(ServiceConfig(
        run_dir=str(tmp_path / "svc"), trace=True, **base))
    try:
        app = ExplorerApp(ck, service=svc)
        code, body = app.merged_trace()
        assert code == 200
        doc = json.loads(body)
        assert any(e.get("ph") == "M" for e in doc["traceEvents"])
        assert doc["otherData"]["trace_files"] == ["trace.jsonl"]
        # Second hit serves the cached export (same bytes, no rewrite).
        merged = os.path.join(str(tmp_path / "svc"), "trace.merged.json")
        mtime = os.stat(merged).st_mtime_ns
        assert app.merged_trace()[0] == 200
        assert os.stat(merged).st_mtime_ns == mtime
    finally:
        svc.close()


# --- the tier-0 drill -------------------------------------------------------


def test_smoke_trace_merge(tmp_path):
    """The <30s smoke drill (tools/smoke.sh): a traced packed model plus
    a traced 2-job service round merge into one valid Chrome trace with
    resolvable flow arrows; each dispatch holds its three parts."""
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    run_dir = str(tmp_path / "run")
    # Tier 1 of the merge: an in-process engine run.
    model_trace = os.path.join(run_dir, "model", "trace.jsonl")
    ck = PackedTwoPhaseSys(3).checker().spawn_xla(
        trace=model_trace,
        frontier_capacity=1 << 10, table_capacity=1 << 13,
    ).join()
    assert ck.unique_state_count() == PINNED_2PC3[1]
    assert len(ck.dispatch_log) > 0

    # Tier 2: a real 2-job service round, service-level tracing on.
    svc = CheckerService(ServiceConfig(
        run_dir=run_dir, platform="cpu", trace=True,
        default_max_seconds=420.0, stall_s=8.0, startup_grace_s=240.0,
        poll_s=0.2, backoff_s=0.1, probe_auto=False, admission_lint=False,
        max_inflight=2,
    ))
    try:
        jobs = [svc.submit("2pc:3"), svc.submit("2pc:3")]
        assert svc.wait_all(timeout=240), svc.metrics()
        for job in jobs:
            assert job.status == "done", job.error
            assert (job.result["generated"], job.result["unique"]) \
                == PINNED_2PC3
        trace_ids = {j.trace_id for j in jobs}
        assert len(trace_ids) == 2 and all(trace_ids)
        merged = svc.merged_trace_chrome()
    finally:
        svc.close()

    assert merged == os.path.join(run_dir, "trace.merged.json")
    with open(merged) as fh:
        doc = json.load(fh)
    evs = doc["traceEvents"]
    assert doc["otherData"]["traces"] == sorted(trace_ids)
    assert len(doc["otherData"]["trace_files"]) == 4  # model + svc + 2 jobs

    # Chrome schema: only the event kinds the merger emits, X slices
    # complete, and the slice/counter timeline monotonic (meta first,
    # flows last — the order Perfetto ingests).
    phases_seen = set()
    last_ts = None
    for ev in evs:
        assert ev["ph"] in ("X", "C", "M", "s", "t", "f"), ev
        phases_seen.add(ev["ph"])
        if ev["ph"] == "X":
            assert SLICE_KEYS <= set(ev)
            assert ev["dur"] >= 0
        if ev["ph"] in ("X", "C"):
            if last_ts is not None:
                assert ev["ts"] >= last_ts
            last_ts = ev["ts"]
    assert {"X", "M", "s", "f"} <= phases_seen

    # Flow arrows resolve: one arc per admitted job, s first / f last,
    # every arrow's id a known trace_id, timestamps non-decreasing.
    arcs = {}
    for ev in evs:
        if ev["ph"] in ("s", "t", "f"):
            assert ev["id"] in trace_ids
            arcs.setdefault(ev["id"], []).append(ev)
    assert set(arcs) == trace_ids
    for arc in arcs.values():
        assert arc[0]["ph"] == "s" and arc[-1]["ph"] == "f"
        assert arc[-1]["bp"] == "e"
        assert [e["ts"] for e in arc] == sorted(e["ts"] for e in arc)

    # Every admitted job's spans share ONE trace id from submit through
    # engine dispatch, with parent links resolving across files.
    slices = [e for e in evs if e["ph"] == "X"]
    by_trace = {}
    sids = set()
    for e in slices:
        sids.add(e["args"].get("span_id"))
        t = e["args"].get("trace_id")
        if t:
            by_trace.setdefault(t, set()).add(e["name"])
    for t in trace_ids:
        assert {"submit", "attempt", "job", "dispatch"} <= by_trace[t]
    for e in slices:
        parent = e["args"].get("parent_id")
        if parent is not None:
            assert parent in sids, e

    # Each single-chip dispatch holds its three parts, in order, inside
    # its own slice on the same lane.
    disp = [e for e in slices if e["name"] == "dispatch"]
    parts = [e for e in slices if e["name"].startswith("dispatch:")]
    assert parts, "the engine tiers emit dispatch parts"
    for e in parts:
        assert any(
            d["pid"] == e["pid"] and d["tid"] == e["tid"]
            and d["ts"] <= e["ts"] and e["ts"] + e["dur"] <= d["ts"] + d["dur"] + 1
            for d in disp
        ), e
    by_name = Counter(e["name"] for e in parts)
    assert by_name["dispatch:prepare"] == by_name["dispatch:run"] \
        == by_name["dispatch:commit"]
