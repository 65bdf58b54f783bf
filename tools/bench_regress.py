#!/usr/bin/env python
"""Perf-regression gate: a fresh bench line judged against the archived
trajectory.

The perf record (CPU 129k -> 772k, chip 1.25M -> 2.72M gen/s) lives in
``runs/archive/BENCH_r*.json`` and the service SLO line in
``runs/service_chaos.json`` — but until this tool, nothing compared a
fresh run against them mechanically: a regression would only be noticed
by a person re-reading JSON. This gate loads the trajectory, compares the
fresh primary line (gen/s, count_ok, resumed, lint_ok) and the chaos SLO
line (admission p99, turnaround p99) against per-platform baselines with
explicit tolerances, and emits ONE typed verdict JSON line to
``runs/regress.json`` (and stdout):

    {"tool": "bench_regress", "verdict": "pass" | "fail" | "no_baseline",
     "platform": ..., "checks": [...], ...}

Verdicts are typed, never a crash:

- ``pass``        — every applicable check passed;
- ``fail``        — at least one check failed (throughput below
                    ``(1 - tolerance) x`` the platform's archived best,
                    ``count_ok`` false, ``lint_ok`` false, SLO p99 above
                    its limit, or a failed chaos sweep);
- ``no_baseline`` — the archive has no parseable ``BENCH_r*.json`` at all
                    (fresh clones; satellite: a typed non-failure, exit 0).

Per-check ``skip`` verdicts cover the honest gaps: a platform with no
archived line yet (e.g. the first chip line), a ``resumed`` fresh line
(it measures the tail of a space from a checkpoint — not comparable to a
cold full pass), a line whose ``fleet`` provenance records cross-device
migrations (the box was running a fleet failover sweep concurrently —
throughput measured amid evacuations judges the chaos harness, not the
engine), tri-state ``count_ok``/``lint_ok`` = None, and a missing chaos
artifact.

Inputs: the fresh line defaults to ``runs/bench_detail.json`` (it carries
everything the primary stdout line does, plus resume/lint provenance) and
also accepts a raw primary-line JSON file (``--fresh line.json``).

``--self-test`` proves the gate's three verdicts against the real
archived trajectory (pass on the newest real line, fail on a synthetically
degraded copy, no_baseline on an empty dir) — the smoke-stage form, no
jax, <5 s. Exit codes: 0 pass/no_baseline/self-test-ok, 1 fail,
2 tool error (unreadable fresh line).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ARCHIVE = os.path.join(REPO, "runs", "archive")
DEFAULT_FRESH = os.path.join(REPO, "runs", "bench_detail.json")
DEFAULT_CHAOS = os.path.join(REPO, "runs", "service_chaos.json")
DEFAULT_OUT = os.path.join(REPO, "runs", "regress.json")

#: Fresh throughput must reach (1 - tolerance) x the platform's archived
#: best. 0.35 accommodates the honest run-to-run spread of the 1-core CPU
#: box (runs/archive r02->r04: 600k..772k, a 22% band) while still
#: catching a real regression (an engine bug typically costs 2x+).
DEFAULT_TOLERANCE = 0.35
#: SLO limits for the chaos line (tools/service_chaos.py percentiles);
#: generous absolutes — the archive has no banked SLO trajectory yet, so
#: these are explicit flags, not derived baselines.
DEFAULT_ADMISSION_P99_MS = 5000.0
DEFAULT_TURNAROUND_P99_S = 300.0


def _platform_of(metric: str) -> str:
    """The platform label a primary line carries: the suffix after the
    last comma of its metric string ("... spawn_xla, cpu" -> "cpu")."""
    return metric.rsplit(",", 1)[-1].strip() if "," in metric else "unknown"


def load_trajectory(archive_dir: str) -> Dict[str, Dict[str, Any]]:
    """Per-platform baselines from ``BENCH_r*.json``: each file is the
    driver's wrapper ({"n", "parsed": {primary line}}) or a raw primary
    line; unparseable files are skipped (the verdict reports how many
    lines were read). Baseline = the platform's best archived value (the
    trajectory's high-water mark — rm varies across rounds, but gen/s is
    the platform's throughput metric throughout the archive)."""
    out: Dict[str, Dict[str, Any]] = {}
    for path in sorted(glob.glob(os.path.join(archive_dir, "BENCH_r*.json"))):
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        line = doc.get("parsed") if isinstance(doc, dict) else None
        if line is None and isinstance(doc, dict) and "metric" in doc:
            line = doc
        if not isinstance(line, dict) or "value" not in line or "metric" not in line:
            continue
        platform = _platform_of(line["metric"])
        entry = out.setdefault(
            platform, {"best": 0.0, "best_metric": None, "lines": 0}
        )
        entry["lines"] += 1
        if float(line["value"]) > entry["best"]:
            entry["best"] = float(line["value"])
            entry["best_metric"] = line["metric"]
            entry["best_file"] = os.path.basename(path)
        # Batched-scheduling baseline (BENCH_MUX; docs/service.md
        # "Batched scheduling"): archived rounds that ran the mux
        # throughput probe carry its row — the per-platform best
        # jobs_per_sec becomes the mux trajectory. Absent everywhere
        # until a round banks one (the mux check skips, no_baseline-safe).
        mux = (doc.get("mux") if isinstance(doc, dict) else None) or line.get("mux")
        if isinstance(mux, dict) and mux.get("jobs_per_sec"):
            if float(mux["jobs_per_sec"]) > entry.get("mux_best", 0.0):
                entry["mux_best"] = float(mux["jobs_per_sec"])
                entry["mux_best_file"] = os.path.basename(path)
        # Symmetry-reduction baseline (BENCH_SYM; docs/symmetry.md):
        # archived rounds that ran the sym A/B carry its row — the
        # per-platform best off/on wall-clock ratio becomes the sym
        # trajectory (same no_baseline-safe contract as mux).
        sym = (doc.get("sym") if isinstance(doc, dict) else None) or line.get("sym")
        if isinstance(sym, dict) and sym.get("speedup"):
            if float(sym["speedup"]) > entry.get("sym_best", 0.0):
                entry["sym_best"] = float(sym["speedup"])
                entry["sym_best_file"] = os.path.basename(path)
    return out


def normalize_fresh(doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """One shape for the two fresh sources: a primary stdout line
    ({"metric", "value", ...}) or a ``bench_detail.json``. Returns
    {platform, value, count_ok, resumed, lint_ok, full_coverage} or None
    when the document is neither."""
    if "metric" in doc and "value" in doc:
        return {
            "platform": _platform_of(doc["metric"]),
            "value": float(doc["value"]),
            "count_ok": doc.get("count_ok"),
            "resumed": doc.get("resumed"),
            "lint_ok": doc.get("lint_ok"),
            "fleet": doc.get("fleet"),
            "mux": doc.get("mux"),
            "sym": doc.get("sym"),
            "full_coverage": doc.get("count_ok") is not None,
            "metric": doc["metric"],
        }
    if "states_per_sec" in doc:
        resume = doc.get("resume") or {}
        return {
            "platform": doc.get("platform", "unknown"),
            "value": float(doc["states_per_sec"]),
            "count_ok": doc.get("count_ok"),
            "resumed": resume.get("phase"),
            "lint_ok": doc.get("lint_ok"),
            "fleet": doc.get("fleet"),
            "mux": doc.get("mux"),
            "sym": doc.get("sym"),
            "full_coverage": doc.get("full_coverage"),
            "metric": f"bench_detail rm={doc.get('rm')}",
        }
    return None


def _check(name: str, verdict: str, detail: str, **extra: Any) -> Dict[str, Any]:
    return {"name": name, "verdict": verdict, "detail": detail, **extra}


def judge(
    fresh: Dict[str, Any],
    trajectory: Dict[str, Dict[str, Any]],
    chaos: Optional[Dict[str, Any]],
    *,
    tolerance: float = DEFAULT_TOLERANCE,
    admission_p99_ms: float = DEFAULT_ADMISSION_P99_MS,
    turnaround_p99_s: float = DEFAULT_TURNAROUND_P99_S,
) -> Dict[str, Any]:
    """The pure verdict (no I/O): check list + overall verdict."""
    checks: List[Dict[str, Any]] = []
    platform = fresh["platform"]
    base = trajectory.get(platform)

    # -- throughput vs the platform's archived best -----------------------
    if not trajectory:
        pass  # overall no_baseline below; no throughput check to run
    elif base is None:
        checks.append(
            _check(
                "throughput", "skip",
                f"no archived {platform} line yet (archive covers "
                f"{sorted(trajectory)}); banking this one starts the "
                "trajectory",
            )
        )
    elif fresh.get("resumed"):
        checks.append(
            _check(
                "throughput", "skip",
                f"fresh line resumed from a {fresh['resumed']!r} checkpoint "
                "— it measures the tail of the space, not a cold full "
                "pass; not comparable",
            )
        )
    elif (fresh.get("fleet") or {}).get("migrations"):
        fleet = fresh["fleet"]
        checks.append(
            _check(
                "throughput", "skip",
                f"fleet provenance records {fleet['migrations']} "
                f"cross-device migration(s) over {fleet.get('devices')} "
                "device(s) — throughput measured amid failover "
                "evacuations judges the chaos harness, not the engine; "
                "not comparable",
            )
        )
    else:
        floor = base["best"] * (1.0 - tolerance)
        ok = fresh["value"] >= floor
        checks.append(
            _check(
                "throughput", "pass" if ok else "fail",
                f"{fresh['value']:,.0f} gen/s vs {platform} best "
                f"{base['best']:,.0f} ({base.get('best_file')}); floor "
                f"{floor:,.0f} at tolerance {tolerance}",
                value=fresh["value"], baseline=base["best"], floor=round(floor, 1),
            )
        )

    # -- exactness / provenance -------------------------------------------
    count_ok = fresh.get("count_ok")
    if count_ok is None:
        checks.append(
            _check(
                "count_ok", "skip",
                "no exact-count verdict (partial coverage or unpinned rm)",
            )
        )
    else:
        checks.append(
            _check(
                "count_ok", "pass" if count_ok else "fail",
                "exact-count contract "
                + ("holds" if count_ok else "VIOLATED on this platform"),
            )
        )
    lint_ok = fresh.get("lint_ok")
    if lint_ok is None:
        checks.append(
            _check("lint_ok", "skip", "no fresh stpu-lint artifact")
        )
    else:
        checks.append(
            _check(
                "lint_ok", "pass" if lint_ok else "fail",
                "stpu-lint " + ("clean" if lint_ok else "has unwaived findings"),
            )
        )

    # -- batched-scheduling throughput (BENCH_MUX) -------------------------
    mux = fresh.get("mux")
    if isinstance(mux, dict):
        if mux.get("error") or mux.get("jobs_failed"):
            checks.append(
                _check(
                    "mux", "fail",
                    "mux throughput probe "
                    + (f"errored: {mux['error']}" if mux.get("error") else
                       f"lost {mux['jobs_failed']} of {mux.get('k')} jobs"),
                )
            )
        elif base is None or not base.get("mux_best"):
            checks.append(
                _check(
                    "mux", "skip",
                    f"no archived {platform} mux baseline yet "
                    f"({mux.get('jobs_per_sec')} jobs/s at k={mux.get('k')}, "
                    f"{mux.get('dispatches_per_job')} dispatches/job); "
                    "banking this one starts the trajectory",
                )
            )
        else:
            floor = base["mux_best"] * (1.0 - tolerance)
            ok = float(mux.get("jobs_per_sec", 0.0)) >= floor
            checks.append(
                _check(
                    "mux", "pass" if ok else "fail",
                    f"{mux.get('jobs_per_sec')} jobs/s at k={mux.get('k')} "
                    f"vs {platform} mux best {base['mux_best']} "
                    f"({base.get('mux_best_file')}); floor {floor:.3f} at "
                    f"tolerance {tolerance}",
                    value=mux.get("jobs_per_sec"), baseline=base["mux_best"],
                    floor=round(floor, 3),
                )
            )
    # No "skip" row when the probe never ran: the mux mode is an env
    # opt-in (BENCH_MUX), not a default stage of every bench.

    # -- symmetry-reduction A/B (BENCH_SYM) --------------------------------
    sym = fresh.get("sym")
    if isinstance(sym, dict):
        audit = sym.get("audit") or {}
        if sym.get("error") or audit.get("ok") is False:
            checks.append(
                _check(
                    "sym", "fail",
                    "sym A/B probe "
                    + (f"errored: {sym['error']}" if sym.get("error") else
                       f"failed the reduced-run audit: {audit}"),
                )
            )
        elif base is None or not base.get("sym_best"):
            checks.append(
                _check(
                    "sym", "skip",
                    f"no archived {platform} sym baseline yet "
                    f"({sym.get('spec')}: {sym.get('unique_full')} -> "
                    f"{sym.get('unique_reduced')} uniques, speedup "
                    f"{sym.get('speedup')}); banking this one starts the "
                    "trajectory",
                )
            )
        else:
            floor = base["sym_best"] * (1.0 - tolerance)
            ok = float(sym.get("speedup", 0.0)) >= floor
            checks.append(
                _check(
                    "sym", "pass" if ok else "fail",
                    f"speedup {sym.get('speedup')} on {sym.get('spec')} "
                    f"({sym.get('unique_full')} -> "
                    f"{sym.get('unique_reduced')} uniques) vs {platform} "
                    f"sym best {base['sym_best']} "
                    f"({base.get('sym_best_file')}); floor {floor:.3f} at "
                    f"tolerance {tolerance}",
                    value=sym.get("speedup"), baseline=base["sym_best"],
                    floor=round(floor, 3),
                )
            )
    # Same opt-in contract as mux: no row when BENCH_SYM never ran.

    # -- chaos SLO line ----------------------------------------------------
    if chaos is None:
        checks.append(
            _check(
                "slo", "skip",
                "no runs/service_chaos.json (run tools/service_chaos.py)",
            )
        )
    else:
        if not chaos.get("ok", False):
            checks.append(
                _check("slo", "fail", "chaos sweep itself failed (ok: false)")
            )
        else:
            slo_fail = []
            slo_detail = []
            for scen, rep in (chaos.get("scenarios") or {}).items():
                adm = (rep.get("admission_latency_ms") or {}).get("p99")
                turn = (rep.get("turnaround_s") or {}).get("p99")
                if adm is not None:
                    slo_detail.append(f"{scen}: admission p99 {adm}ms")
                    if adm > admission_p99_ms:
                        slo_fail.append(
                            f"{scen} admission p99 {adm}ms > {admission_p99_ms}ms"
                        )
                if turn is not None:
                    slo_detail.append(f"{scen}: turnaround p99 {turn}s")
                    if turn > turnaround_p99_s:
                        slo_fail.append(
                            f"{scen} turnaround p99 {turn}s > {turnaround_p99_s}s"
                        )
                # Per-class SLO gate (ISSUE 18): present only on
                # QoS-era chaos lines — each class's p99s ride under
                # the same ceilings, and an inverted pair (interactive
                # p99 at or above best_effort's) is a scheduling
                # regression in its own right.
                classes = rep.get("classes")
                if isinstance(classes, dict):
                    for cls, crow in sorted(classes.items()):
                        cturn = ((crow or {}).get("turnaround_s")
                                 or {}).get("p99")
                        if cturn is None:
                            continue
                        slo_detail.append(
                            f"{scen}/{cls}: turnaround p99 {cturn}s"
                        )
                        if cturn > turnaround_p99_s:
                            slo_fail.append(
                                f"{scen} {cls} turnaround p99 {cturn}s"
                                f" > {turnaround_p99_s}s"
                            )
                    if rep.get("priority_inversion"):
                        # The harness only fails the scenario when both
                        # classes had enough samples; surface the
                        # low-sample case as detail, not a gate fail.
                        slo_detail.append(
                            f"{scen}: priority_inversion flagged"
                        )
            if not slo_detail:
                checks.append(
                    _check("slo", "skip", "chaos line carries no percentiles")
                )
            else:
                checks.append(
                    _check(
                        "slo", "fail" if slo_fail else "pass",
                        "; ".join(slo_fail or slo_detail),
                    )
                )

    # Failure wins over no_baseline: a missing archive only excuses the
    # throughput comparison — a count_ok/lint_ok/SLO failure must never
    # ride out of the gate under a "no_baseline" exit 0.
    if any(c["verdict"] == "fail" for c in checks):
        verdict = "fail"
    elif not trajectory:
        verdict = "no_baseline"
    else:
        verdict = "pass"
    return {
        "tool": "bench_regress",
        "verdict": verdict,
        "platform": platform,
        "fresh": {k: fresh.get(k) for k in
                  ("metric", "value", "count_ok", "resumed", "lint_ok",
                   "fleet", "mux")},
        "baseline": base,
        "platforms_archived": sorted(trajectory),
        "tolerances": {
            "throughput": tolerance,
            "admission_p99_ms": admission_p99_ms,
            "turnaround_p99_s": turnaround_p99_s,
        },
        "checks": checks,
    }


def _load_json(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


def _emit(line: Dict[str, Any], out_path: Optional[str]) -> None:
    print(json.dumps(line))
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = f"{out_path}.tmp-{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(line, fh, indent=1)
        os.replace(tmp, out_path)


def self_test(args) -> int:
    """The gate judging its own three verdicts against the REAL archive:
    the newest archived line must pass, a synthetically degraded copy
    must fail, an empty archive must report no_baseline. The smoke-stage
    form (tools/smoke.sh) — no jax, no device, <5 s."""
    trajectory = load_trajectory(args.archive)
    cases: Dict[str, Any] = {}
    ok = True
    if not trajectory:
        cases["archive"] = "no parseable BENCH_r*.json under " + args.archive
        ok = False
    else:
        # Newest real line per the best platform = a known-good fresh line.
        platform = sorted(trajectory)[0]
        base = trajectory[platform]
        real = {
            "metric": base["best_metric"],
            "value": base["best"],
            "count_ok": True,
        }
        v = judge(normalize_fresh(real), trajectory, None,
                  tolerance=args.tolerance)["verdict"]
        cases["real_line"] = v
        ok &= v == "pass"
        degraded = dict(real, value=base["best"] * 0.1)
        v = judge(normalize_fresh(degraded), trajectory, None,
                  tolerance=args.tolerance)["verdict"]
        cases["degraded_line"] = v
        ok &= v == "fail"
    with tempfile.TemporaryDirectory() as empty:
        v = judge(
            normalize_fresh({"metric": "x, cpu", "value": 1.0}),
            load_trajectory(empty), None,
        )["verdict"]
        cases["empty_archive"] = v
        ok &= v == "no_baseline"
    print(json.dumps({"tool": "bench_regress", "self_test": True,
                      "ok": bool(ok), "cases": cases}))
    return 0 if ok else 1


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--archive", default=DEFAULT_ARCHIVE,
                   help="dir of BENCH_r*.json trajectory files")
    p.add_argument("--fresh", default=DEFAULT_FRESH,
                   help="fresh line: bench_detail.json or a primary-line JSON")
    p.add_argument("--chaos", default=DEFAULT_CHAOS,
                   help="service_chaos SLO line (skipped when missing)")
    p.add_argument("--out", default=DEFAULT_OUT,
                   help="verdict JSON destination ('' disables)")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE)
    p.add_argument("--admission-p99-ms", type=float,
                   default=DEFAULT_ADMISSION_P99_MS)
    p.add_argument("--turnaround-p99-s", type=float,
                   default=DEFAULT_TURNAROUND_P99_S)
    p.add_argument("--self-test", action="store_true")
    args = p.parse_args(argv)

    if args.self_test:
        return self_test(args)

    doc = _load_json(args.fresh)
    fresh = normalize_fresh(doc) if doc else None
    if fresh is None:
        _emit(
            {
                "tool": "bench_regress",
                "verdict": "error",
                "error": f"no readable fresh line at {args.fresh} "
                         "(run python bench.py first, or pass --fresh)",
            },
            args.out or None,
        )
        return 2
    line = judge(
        fresh,
        load_trajectory(args.archive),
        _load_json(args.chaos),
        tolerance=args.tolerance,
        admission_p99_ms=args.admission_p99_ms,
        turnaround_p99_s=args.turnaround_p99_s,
    )
    _emit(line, args.out or None)
    return 0 if line["verdict"] in ("pass", "no_baseline") else 1


if __name__ == "__main__":
    sys.exit(main())
