"""Bandwidth accounting + modeled ceiling for the device engine.

Three modes:

``python tools/roofline.py [runs/bench_detail.json]``
    Post-hoc accounting of a measured run (as before): logical bytes per
    stage divided by measured wall-clock, reported against the chip's
    HBM peak. Numbers far below peak mean latency/serialization bound,
    not traffic bound.

``python tools/roofline.py --measured [trace.jsonl] [bench_detail.json]``
    Per-stage WALL-CLOCK from the obs span trace (STPU_TRACE;
    docs/observability.md) next to the modeled ceiling: spans aggregate
    into host-boundary stages (compile-carrying dispatches, steady
    dispatches, overflow-recovery growth/flush work, host-verify) with
    count/total/share per stage. When a bench_detail.json is present
    (second arg, or the default paths) the modeled ceiling for the same
    recorded schedule prints alongside — the gap between measured
    dispatch wall-clock and the modeled traffic floor is the
    optimization headroom, now engine-measured instead of hand-derived.

    The same flag also reads a METRICS TIME-SERIES (``metrics.jsonl``,
    the MetricsRecorder rotation — docs/observability.md "Time series"):
    a .jsonl argument is sniffed by schema, and with no trace at all the
    detail file's recorded ``metrics_series`` path is the fallback
    source. A series yields run-level rates (wall-clock, dispatch/level
    counts, gen/s between samples), not per-stage wall-clock — spans
    wrap each host boundary, samples only bracket quiescent points.
    Precedence when both artifacts exist: the span trace wins; the
    series is the coarse answer for runs that only recorded metrics.

    ``--measured`` also accepts a **service/fleet run dir**: every span
    ``trace.jsonl`` under it (service, per-device pools, per-job workers,
    mux lanes) aggregates into one per-stage report, and the run dir's
    ``journal.jsonl`` (auto-discovered) contributes the job→spec map as
    provenance. Source precedence: an explicit span-trace path wins, then
    a run dir's discovered traces, then the detail file's recorded
    ``trace``, then a metrics series (coarse run-level rates only).

``python tools/roofline.py --model [runs/bench_detail.json]``
    The DESIGN's traffic-bound ceiling on v5e-1 (VERDICT r4 item 3): for
    each committed level of the recorded schedule, the minimum HBM bytes
    each stage must move, divided by an achievable fraction of peak
    bandwidth, plus per-level dispatch latency and the measured sort
    constant. This is what the engine would run at if every stage hit
    ``EFFICIENCY`` of peak — the gap between this and a measured run is
    the optimization headroom; the stage with the largest modeled share
    is the binding constraint. Overridables (env):
      ROOFLINE_EFFICIENCY   fraction of peak HBM each stage can achieve
                            (default 0.4 — sorts move data ~log passes,
                            gathers stride; 40% of peak is a strong
                            sustained figure for this mix)
      ROOFLINE_SORT_PASSES  effective full-data passes per bitonic-style
                            device sort (default 3; measured two-key sort
                            at 2^22 = 3.3 ms ~= 2.9 passes at peak)
      ROOFLINE_RTT_S        per-dispatch host latency (default 30e-6,
                            measured on an earlier chip setup)

The model is deliberately *optimistic per stage* (logical bytes, no
re-reads beyond declared passes): it is a ceiling, not a prediction.

Stage byte model per level (bucket B, actions A, words W, generated M_l,
table capacity C, candidate cap = B*A/4):
  expand     read frontier B*W*4, write grid B*A*W*4
  fingerprint  read grid, write 2 key lanes: B*A*(W+2)*4
  compact    key sort B*A*8*passes + survivor gather M_l*(W+3)*4
  insert     3-operand sort of [C + cand] rows: (C + B*A/4)*12*passes
  frontier   survivor pull M_l*(W+1)*4
"""

from __future__ import annotations

import json
import os
import statistics
import sys

PEAK_GBPS = 819.0  # TPU v5e HBM
EFFICIENCY = float(os.environ.get("ROOFLINE_EFFICIENCY", "0.4"))
SORT_PASSES = float(os.environ.get("ROOFLINE_SORT_PASSES", "3"))
RTT_S = float(os.environ.get("ROOFLINE_RTT_S", "30e-6"))


def _levels(detail):
    for block in detail.get("levels", []):
        for lv in block.get("levels", []):
            yield lv


def _bucket_for(F: int, floor: int = 64) -> int:
    bucket = floor
    while bucket < 4 * F:
        bucket *= 4
    return bucket


def _table_capacity(detail) -> int:
    """Recorded capacity, else derived from the unique count under the
    sorted set's 3/4-load growth rule (older bench_detail files predate
    the table_capacity key; defaulting to 2^22 would overstate the
    insert stage ~100x on small schedules)."""
    if "table_capacity" in detail:
        return detail["table_capacity"]
    uniq = max(int(detail.get("unique_states", 0)), 1)
    cap = 1 << 10
    while uniq * 4 > cap * 3:
        cap *= 2
    return cap


def model_ceiling(detail) -> dict:
    """Modeled stage seconds for the recorded level schedule on v5e-1."""
    rm = detail.get("rm", 8)
    # Action width: explicit "actions" key wins (non-2pc models);
    # otherwise the 2pc formula from rm.
    A = detail.get("actions") or (2 + 5 * rm)
    W = detail.get("state_words", 2)
    C = _table_capacity(detail)
    bw = PEAK_GBPS * 1e9 * EFFICIENCY
    stages = {"expand": 0.0, "fingerprint": 0.0, "compact": 0.0,
              "insert": 0.0, "frontier": 0.0, "dispatch": 0.0}
    gen_total = 0
    n_levels = 0
    for lv in _levels(detail):
        F = max(int(lv.get("frontier", 0)), 1)
        M = max(int(lv.get("generated", 0)), 1)
        gen_total += M
        n_levels += 1
        B = _bucket_for(F)
        grid = B * A
        stages["expand"] += (B * W + grid * W) * 4 / bw
        stages["fingerprint"] += grid * (W + 2) * 4 / bw
        stages["compact"] += (grid * 8 * SORT_PASSES + M * (W + 3) * 4) / bw
        stages["insert"] += (C + grid // 4) * 12 * SORT_PASSES / bw
        stages["frontier"] += M * (W + 1) * 4 / bw
    # Fused dispatch: one RTT per ~32-level block, not per level.
    stages["dispatch"] = max(1, n_levels / 32) * RTT_S
    total = sum(stages.values())
    return {
        "rm": rm, "levels": n_levels, "generated": gen_total,
        "stage_sec": {k: round(v, 4) for k, v in stages.items()},
        "modeled_sec": round(total, 4),
        "ceiling_states_per_sec": round(gen_total / max(total, 1e-12), 0),
        "binding_stage": max(stages, key=stages.get),
        "assumptions": {
            "efficiency": EFFICIENCY, "sort_passes": SORT_PASSES,
            "rtt_s": RTT_S, "peak_gbps": PEAK_GBPS,
        },
    }


def cost_law_rows(detail) -> list:
    """Predicted-vs-measured cost-law rows from the engine's per-level
    sorted-lane-words telemetry (level rows carry ``lane_words`` /
    ``cand_cap`` / ``bucket`` since the candidate-ladder round — the
    ACTUAL static sort shapes the compiled program ran, so this replaces
    the hand-derived per-level figure the byte model above guesses at).
    One row per dispatch block: the block's wall-clock is the
    host-visible measured unit; its predicted sort seconds are
    lane-words x 4 bytes x SORT_PASSES / achievable bandwidth."""
    bw = PEAK_GBPS * 1e9 * EFFICIENCY
    rows = []
    for block in detail.get("levels", []):
        lvls = block.get("levels", [])
        lw = [l.get("lane_words") for l in lvls]
        if not lvls or any(w is None for w in lw):
            continue
        total_lw = sum(lw)
        rows.append(
            {
                "levels": len(lvls),
                "lane_words": total_lw,
                "cand_caps": sorted({l.get("cand_cap") for l in lvls}),
                "predicted_sort_s": round(total_lw * 4 * SORT_PASSES / bw, 5),
                "measured_s": block.get("sec"),
            }
        )
    return rows


#: Where a detail file lives when unspecified: fresh runs land under
#: runs/ (bench.py), with the legacy repo-root path as fallback.
DEFAULT_DETAIL = ("runs/bench_detail.json", "bench_detail.json")


def _load_default_detail():
    for p in DEFAULT_DETAIL:
        if os.path.exists(p):
            with open(p) as fh:
                return json.load(fh), p
    return None, None


def measured_stages(trace_path: str) -> dict:
    """Aggregates the span JSONL into host-boundary stages: wall-clock
    seconds + event counts per stage, plus a per-bucket dispatch split
    (the bucket ladder's cost profile, engine-measured)."""
    stages = {}
    buckets = {}
    wall = 0.0
    # Rebase multiple appended tracer sessions (bench retries) onto the
    # first session's clock via each trace_start's unix_ts — mirrors
    # obs.export_chrome, so trace_span_sec covers the whole file.
    base_unix = None
    offset = 0.0
    with open(trace_path) as fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            name = rec.get("name")
            if name == "trace_start":
                u = rec.get("attrs", {}).get("unix_ts")
                if u is not None:
                    if base_unix is None:
                        base_unix = u
                    offset = u - base_unix
                continue
            if name is None or ":" in name:
                # ``dispatch:run`` etc. are sub-spans of a span counted
                # here already.
                continue
            attrs = rec.get("attrs", {})
            if name == "dispatch":
                stage = "compile_dispatch" if attrs.get("compile") else "dispatch"
                b = attrs.get("bucket")
                if b is not None and not attrs.get("compile"):
                    row = buckets.setdefault(b, {"count": 0, "sec": 0.0, "levels": 0})
                    row["count"] += 1
                    row["sec"] += rec["dur"]
                    row["levels"] += attrs.get("committed") or 0
            elif name in ("grow_table", "grow_frontier", "delta_flush"):
                stage = "overflow_recovery"
            else:
                stage = name
            row = stages.setdefault(stage, {"count": 0, "sec": 0.0})
            row["count"] += 1
            row["sec"] += rec["dur"]
            wall = max(wall, rec["ts"] + offset + rec["dur"])
    total = sum(r["sec"] for r in stages.values())
    for r in stages.values():
        r["sec"] = round(r["sec"], 4)
        r["share"] = round(r["sec"] / max(total, 1e-12), 3)
    return {
        "trace": trace_path,
        "stages": stages,
        "dispatch_by_bucket": {
            str(b): {**row, "sec": round(row["sec"], 4)}
            for b, row in sorted(buckets.items())
        },
        "instrumented_sec": round(total, 4),
        "trace_span_sec": round(wall, 4),
    }


def discover_traces(run_dir: str) -> list:
    """Every span ``trace.jsonl`` under a service/fleet run dir, sorted
    by relative path (service root first, then per-job worker dirs,
    then fleet pool subtrees) — the same discovery rule as
    ``stateright_tpu.obs.collect.trace_files``, inlined so this tool
    stays import-free of the package."""
    out = []
    for root, _dirs, files in os.walk(run_dir):
        if "trace.jsonl" in files:
            out.append(os.path.join(root, "trace.jsonl"))
    out.sort(key=lambda p: os.path.relpath(p, run_dir))
    return out


def discover_jobs(run_dir: str) -> dict:
    """Auto-discovered journal provenance for a run dir: the job→spec
    map folded from every ``journal.jsonl`` under it (``submitted``
    records; torn/partial lines skipped, same reader tolerance as the
    service's replay)."""
    jobs = {}
    for root, _dirs, files in os.walk(run_dir):
        for name in files:
            if name != "journal.jsonl" and not name.startswith("journal.jsonl."):
                continue
            try:
                with open(os.path.join(root, name)) as fh:
                    for line in fh:
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        if not isinstance(rec, dict):
                            continue
                        body = rec.get("rec", rec)
                        if body.get("event") == "submitted" and body.get("job"):
                            jobs[body["job"]] = body.get("spec")
            except OSError:
                continue
    return jobs


def measured_stages_multi(trace_paths: list) -> dict:
    """``measured_stages`` summed across every trace of a run dir (one
    per process: service, workers, mux lanes). Per-file clocks are not
    aligned, so ``trace_span_sec`` is the max single-file span; stage
    seconds/counts and the per-bucket dispatch split sum exactly."""
    if len(trace_paths) == 1:
        return measured_stages(trace_paths[0])
    stages = {}
    buckets = {}
    wall = 0.0
    total = 0.0
    for p in trace_paths:
        one = measured_stages(p)
        for k, row in one["stages"].items():
            agg = stages.setdefault(k, {"count": 0, "sec": 0.0})
            agg["count"] += row["count"]
            agg["sec"] += row["sec"]
        for b, row in one["dispatch_by_bucket"].items():
            agg = buckets.setdefault(b, {"count": 0, "sec": 0.0, "levels": 0})
            for k in agg:
                agg[k] += row[k]
        wall = max(wall, one["trace_span_sec"])
        total += one["instrumented_sec"]
    for r in stages.values():
        r["sec"] = round(r["sec"], 4)
        r["share"] = round(r["sec"] / max(total, 1e-12), 3)
    return {
        "trace": trace_paths,
        "stages": stages,
        "dispatch_by_bucket": {
            b: {**row, "sec": round(row["sec"], 4)}
            for b, row in sorted(buckets.items())
        },
        "instrumented_sec": round(total, 4),
        "trace_span_sec": round(wall, 4),
    }


def _jsonl_kind(path: str) -> str | None:
    """Sniff a .jsonl artifact: "trace" (span lines: name + dur),
    "series" (MetricsRecorder rows: v + metrics), or None."""
    try:
        with open(path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    if "name" in rec and "dur" in rec:
                        return "trace"
                    if "v" in rec and "metrics" in rec:
                        return "series"
    except OSError:
        return None
    return None


def measured_from_series(series_path: str) -> dict:
    """Run-level rates from a metrics time-series (the coarse fallback
    when no span trace exists): wall-clock between the first and last
    sample, dispatch/level/state deltas, and the per-interval gen/s
    profile. The rotation chain (``.K`` ... live) reassembles oldest
    first; torn lines are skipped — same reader contract as
    ``stateright_tpu.obs.read_series``, inlined so this tool stays
    import-free of the package."""
    paths = []
    i = 1
    while os.path.exists(f"{series_path}.{i}"):
        paths.append(f"{series_path}.{i}")
        i += 1
    paths.reverse()
    paths.append(series_path)
    rows = []
    for p in paths:
        try:
            with open(p) as fh:
                for line in fh:
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if isinstance(rec, dict) and "v" in rec and "metrics" in rec:
                        rows.append(rec)
        except OSError:
            continue
    if not rows:
        return {"series": series_path, "samples": 0}
    first, last = rows[0]["metrics"], rows[-1]["metrics"]
    wall = rows[-1]["unix_ts"] - rows[0]["unix_ts"]
    gen = last.get("state_count", 0) - first.get("state_count", 0)
    rates = []
    for a, b in zip(rows, rows[1:]):
        dt = b["unix_ts"] - a["unix_ts"]
        ds = b["metrics"].get("state_count", 0) - a["metrics"].get("state_count", 0)
        if dt > 0:
            rates.append(ds / dt)
    return {
        "source": "metrics_series",
        "series": series_path,
        "samples": len(rows),
        "wall_s": round(wall, 4),
        "dispatches": last.get("dispatches", 0) - first.get("dispatches", 0),
        "levels_committed": (
            last.get("levels_committed", 0) - first.get("levels_committed", 0)
        ),
        "generated": gen,
        "gen_per_s": round(gen / max(wall, 1e-9), 1),
        "gen_per_s_intervals": {
            "min": round(min(rates), 1) if rates else None,
            "median": round(statistics.median(rates), 1) if rates else None,
            "max": round(max(rates), 1) if rates else None,
        },
        "checkpoints_written": last.get("checkpoints_written", 0),
        "final": {
            k: last.get(k)
            for k in ("engine", "dedup", "depth", "frontier_count",
                      "table_occupancy", "state_count", "unique_state_count")
        },
    }


def _measured_main(args: list) -> None:
    """``--measured``: per-stage wall-clock from the trace, next to the
    modeled ceiling when a detail file for the run is available. A
    metrics time-series (by schema sniff, or the detail file's
    ``metrics_series`` fallback when no trace exists) yields the coarse
    run-level report instead. Precedence: explicit span trace > run-dir
    discovered traces > the detail file's recorded trace > series."""
    detail = detail_path = None
    trace = None
    series = None
    run_dir = None
    dir_traces = []
    for a in args:
        if os.path.isdir(a):
            run_dir = a
            dir_traces = discover_traces(a)
        elif a.endswith(".jsonl"):
            if _jsonl_kind(a) == "series":
                series = a
            else:
                trace = a
        else:
            with open(a) as fh:
                detail = json.load(fh)
            detail_path = a
    if trace is None and len(dir_traces) == 1:
        trace = dir_traces[0]
    elif trace is None and dir_traces:
        out = measured_stages_multi(dir_traces)
        out["run_dir"] = run_dir
        jobs = discover_jobs(run_dir)
        if jobs:
            out["jobs"] = jobs
        if detail is not None:
            out["detail"] = detail_path
            out["model_ceiling"] = model_ceiling(detail)
        print(json.dumps(out, indent=1))
        st = out["stages"]
        steady = st.get("dispatch", {"sec": 0.0, "count": 0})
        comp = st.get("compile_dispatch", {"sec": 0.0, "count": 0})
        print(
            f"# run-dir report: {len(dir_traces)} traces, "
            f"{len(jobs)} journaled jobs; dispatch {steady['sec']:.3f}s "
            f"({steady['count']} calls), compile-carrying {comp['sec']:.3f}s "
            f"({comp['count']} calls)"
        )
        return
    if detail is None:
        detail, detail_path = _load_default_detail()
    if trace is None and detail is not None:
        trace = detail.get("trace")
    if (trace is None or not os.path.exists(trace)) and series is None and (
        detail is not None
    ):
        # Fallback artifact family: the run recorded a metrics series
        # even though no span trace exists.
        ms = detail.get("metrics_series")
        if ms and os.path.exists(ms):
            series = ms
    if (trace is None or not os.path.exists(trace)) and series is not None:
        out = measured_from_series(series)
        if detail is not None:
            out["detail"] = detail_path
            out["model_ceiling"] = model_ceiling(detail)
        print(json.dumps(out, indent=1))
        print(
            f"# metrics-series report ({out.get('samples', 0)} samples): "
            f"{out.get('generated', 0):,} generated over "
            f"{out.get('wall_s', 0.0):.3f}s -> {out.get('gen_per_s', 0.0):,.0f} "
            "gen/s; per-stage wall-clock needs a span trace (STPU_TRACE) — "
            "series samples only bracket quiescent points"
        )
        return
    if trace is None or not os.path.exists(trace):
        print(
            "no trace: pass a span JSONL (tools/roofline.py --measured "
            "trace.jsonl), a metrics series (STPU_METRICS_TO), or run "
            "bench.py with STPU_TRACE set "
            f"(detail file: {detail_path or 'none found'})"
        )
        sys.exit(1)
    out = measured_stages(trace)
    if detail is not None:
        out["detail"] = detail_path
        out["model_ceiling"] = model_ceiling(detail)
    print(json.dumps(out, indent=1))
    st = out["stages"]
    steady = st.get("dispatch", {"sec": 0.0, "count": 0})
    comp = st.get("compile_dispatch", {"sec": 0.0, "count": 0})
    print(
        f"# measured wall-clock by stage: dispatch {steady['sec']:.3f}s "
        f"({steady['count']} calls), compile-carrying {comp['sec']:.3f}s "
        f"({comp['count']} calls), overflow recovery "
        f"{st.get('overflow_recovery', {}).get('sec', 0.0):.3f}s, "
        f"host-verify {st.get('host_verify', {}).get('sec', 0.0):.3f}s"
    )
    if detail is not None:
        mc = out["model_ceiling"]
        gap = steady["sec"] / max(mc["modeled_sec"], 1e-12)
        print(
            f"# modeled ceiling for the recorded schedule: "
            f"{mc['modeled_sec']:.3f}s ({mc['ceiling_states_per_sec']/1e6:.1f} "
            f"M gen/s, binding: {mc['binding_stage']}); measured steady "
            f"dispatch is {gap:.1f}x the modeled floor — that ratio is the "
            "optimization headroom"
        )


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    if "--measured" in sys.argv:
        _measured_main(args)
        return
    if args:
        path = args[0]
    else:
        _detail, path = _load_default_detail()
        path = path or "runs/bench_detail.json"
    with open(path) as fh:
        detail = json.load(fh)

    if "--model" in sys.argv:
        out = model_ceiling(detail)
        law = cost_law_rows(detail)
        if law:
            levels = [l for b in detail["levels"] for l in b.get("levels", [])]
            # Mirror cost_law_rows' guard: a mixed detail file (a block
            # appended from a pre-ladder run) must degrade, not KeyError.
            per_level = sorted(
                w for l in levels if (w := l.get("lane_words")) is not None
            )
            out["cost_law"] = {
                "rows": law,
                "instrumented_levels": len(per_level),
                "lane_words_total": sum(per_level),
                "lane_words_per_level": {
                    # statistics.median matches bench.py and cand_ab.py.
                    "median": statistics.median(per_level),
                    "mean": round(sum(per_level) / len(per_level)),
                    "max": per_level[-1],
                },
                "predicted_sort_s": round(
                    sum(r["predicted_sort_s"] for r in law), 4
                ),
                "measured_s": round(
                    sum(r["measured_s"] or 0 for r in law), 4
                ),
            }
        print(json.dumps(out, indent=1))
        if law:
            cl = out["cost_law"]
            print(
                f"# engine-measured cost law: {cl['lane_words_total']:,} "
                f"sorted lane-words over {cl['instrumented_levels']} "
                f"instrumented levels (of {out['levels']}) "
                f"(median {cl['lane_words_per_level']['median']:,}/level, "
                f"mean {cl['lane_words_per_level']['mean']:,}/level); "
                f"predicted sort time {cl['predicted_sort_s']:.3f}s vs "
                f"measured {cl['measured_s']:.3f}s"
            )
        ns_gap = 50e6 / max(out["ceiling_states_per_sec"], 1)
        print(
            f"# modeled ceiling {out['ceiling_states_per_sec']/1e6:.1f} M gen/s "
            f"on this schedule (binding: {out['binding_stage']}); "
            f"north star 50M is {ns_gap:.2f}x {'above' if ns_gap > 1 else 'below'} it"
        )
        # The traffic floor above is NOT what measured runs see: round-3
        # on-chip profiling put the per-superstep FIXED cost (kernel
        # launches, XLA:TPU serialization, tiling tax) at ~475 ms — for a
        # 26-level run that is ~12.4 s of the measured 14.8 s, i.e. the
        # engine is fixed-cost-bound, not traffic-bound. This sweep shows
        # what the same schedule delivers as the fixed cost falls (the
        # round-5 attacks: plane-major buffers, fewer fused kernels).
        gen = out["generated"]
        L = out["levels"]
        traffic = out["modeled_sec"]
        print("# fixed-cost sweep (per-level overhead -> ceiling):")
        for label, fixed in [
            ("r3 measured 475 ms", 0.475),
            ("50 ms", 0.050),
            ("5 ms", 0.005),
            ("traffic floor only", 0.0),
        ]:
            total = traffic + L * fixed
            print(
                f"#   {label:>20}: {gen/total/1e6:8.2f} M gen/s "
                f"({total:.3f} s total)"
            )
        return

    rm = detail.get("rm", 8)
    A = 2 + 5 * rm
    W = 2
    C = detail.get("table_capacity", 1 << 22)

    total_bytes = 0.0
    total_sec = 0.0
    gen_total = 0
    for block in detail.get("levels", []):
        sec = block.get("sec", 0.0)
        total_sec += sec
        for lv in block.get("levels", []):
            F = max(int(lv.get("frontier", 0)), 1)
            gen = int(lv.get("generated", 0))
            gen_total += gen
            bucket = _bucket_for(F, floor=1024)
            grid = bucket * A
            M = max(gen, 1)
            expand_b = (bucket * W + grid * W) * 4
            compact_b = grid * 8 + M * (W + 3) * 4
            insert_b = (C + M) * 12
            frontier_b = M * (W + 1) * 4
            total_bytes += expand_b + compact_b + insert_b + frontier_b
    if total_sec == 0:
        print("no measured levels in", path)
        return
    gbps = total_bytes / total_sec / 1e9
    print(
        f"platform={detail.get('platform')} rm={rm} gen={gen_total:,} "
        f"measured={total_sec:.2f}s"
    )
    print(
        f"logical traffic {total_bytes/1e9:.1f} GB -> achieved "
        f"{gbps:.2f} GB/s logical ({100*gbps/PEAK_GBPS:.2f}% of v5e peak; "
        "sort stages move data ~log-n passes, so >15-25% logical is "
        "already traffic-bound)"
    )
    print(
        f"throughput {gen_total/max(total_sec,1e-9)/1e6:.2f} M gen states/s; "
        f"north-star gap { (50e6 * total_sec) / max(gen_total,1):.1f}x"
    )


if __name__ == "__main__":
    main()
