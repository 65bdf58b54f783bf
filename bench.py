"""Benchmark: states/sec of the XLA checker on two-phase commit.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": "states/sec", "vs_baseline": N}``.

The metric is generated-states per second (the reference's own notion of
throughput: ``state_count / sec`` from its reporter output, report.rs:66-73)
over a full-coverage check of 2pc with ``BENCH_RM`` resource managers
(default 8 — large enough that steady-state frontiers keep the chip busy).

Methodology: the check runs TWICE. The first run compiles every superstep
bucket the level schedule touches (compilations are cached in-process and
in the persistent compile cache, ``stateright_tpu/backend.py``); the second
run is the measured, steady-state one. ``vs_baseline`` is the ratio against
the driver-defined north-star of 50M states/sec.

All device work runs in a child process under the heartbeat-aware
watchdog of ``stateright_tpu/supervise.py`` (docs/observability.md): the
worker's engines rewrite ``runs/heartbeat.json`` around every device
dispatch, so the parent kills a worker whose dispatch hangs (a beat
mid-``phase="dispatch"`` gone stale past ``BENCH_STALL_S``; the leash
stretches 3x while the beat says the dispatch carries a fresh XLA compile),
while a beating worker may run to the hard ``BENCH_WORKER_TIMEOUT_S`` cap.
``BENCH_TPU_RETRIES`` retries follow — each retry RESUMES from the latest
valid checkpoint the killed worker auto-wrote (``BENCH_CHECKPOINT=0``
disables; ``BENCH_CHECKPOINT_EVERY`` sets the cadence, default 60s). After
the retries the harness still falls back to a CPU child; the benchmark PR
(ROADMAP S1) removes that fallback. Probe diagnostics and per-pass
progress go to stderr and ``runs/bench_probe.log``.

Per-level timing detail is written to ``runs/bench_detail.json`` (levels,
frontier widths, per-level seconds, compile vs steady split).
``BENCH_SYM=1`` adds the symmetry-reduction A/B probe (one shipped spec
full-space vs symmetry-reduced back to back; class collapse + wall-clock
ratio + reduced-run audit in the detail's ``sym`` dict — knob
``BENCH_SYM_SPEC``, docs/symmetry.md). With ``STPU_TRACE`` set the workers additionally
emit the span JSONL (``tools/roofline.py --measured`` consumes it); the
trace and heartbeat paths are recorded in ``runs/bench_detail.json``.
Adding ``STPU_PHASES=1`` turns on the dispatch-phase profiler: the
measured pass's host_prep/enqueue/device_compute/readback split lands in
the detail's ``phases`` dict (``tools/roofline.py --phases`` is the full
report; docs/observability.md "Distributed tracing").
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

NORTH_STAR = 50_000_000.0
REPO = os.path.dirname(os.path.abspath(__file__))
# Fresh run artifacts (detail JSON, probe log, heartbeat, traces) land
# under runs/ — the repo root stays clean (.gitignore rules match).
RUNS = os.path.join(REPO, "runs")
# Auto-checkpoint bases for the primary passes (rotated .npz files; the
# worker resumes from the latest VALID rotation after a watchdog kill).
CK_WARM = os.path.join(RUNS, "bench_ck_warm.npz")
CK_MEASURED = os.path.join(RUNS, "bench_ck_measured.npz")

# Pinned full-coverage (generated, unique) counts. Exact counts are the
# product guarantee (the reference asserts them in its example tests, e.g.
# /root/reference/examples/paxos.rs:321, examples/2pc.rs:156-170), so the
# bench re-asserts them on EVERY platform and emits ``count_ok`` — a drift
# like round 3's on-chip paxos 17,198-vs-16,668 must fail loudly, not sit
# in a log. Sources: rm=3/5 from the reference anchors; the rest pinned by
# this package's host BFS/DFS oracle and re-verified cross-engine
# (README "Exact counts"; tests/test_two_phase_commit.py, tests/test_paxos.py).
EXPECTED_2PC = {
    3: (1_146, 288),
    4: (8_258, 1_568),
    5: (58_146, 8_832),
    6: (402_306, 50_816),
    7: (2_744_706, 296_448),
    8: (18_507_778, 1_745_408),
}
EXPECTED_MATRIX = {
    "linearizable-register (ABD) 2c/2s packed": (875, 544),
    "linearizable-register (ABD) 2c/2s ordered packed": (813, 564),
    "paxos 2c/3s packed": (32_971, 16_668),
    "single-copy-register 3c/1s packed": (6_778, 4_243),
    "increment_lock 3t packed": (61, 61),
}


def _count_check(name: str, expected, states: int, unique: int) -> bool | None:
    """True/False against a pinned (generated, unique) pair; None when the
    config has no pin. A False is logged CRITICAL — it means the engine's
    exact-count contract broke on this platform."""
    if expected is None:
        return None
    ok = (states, unique) == tuple(expected)
    if not ok:
        _log(
            f"COUNT DRIFT on {name}: got generated={states} unique={unique}, "
            f"pinned={expected[0]}/{expected[1]} — exact-count contract "
            "violated on this platform; see stateright_tpu/audit.py"
        )
    return ok


def _audit(checker) -> dict:
    """Host-side duplicate-key audit of the visited set (audit.py); never
    lets an audit failure take down the bench."""
    try:
        from stateright_tpu.audit import audit_table

        return audit_table(checker)
    except Exception as e:  # pragma: no cover - diagnostic path
        return {"error": f"{type(e).__name__}: {e}"}


def _phase_summary(rows) -> dict | None:
    """Folds the checker's ``phase_log`` (the dispatch-phase profiler,
    STPU_PHASES=1) into the bench_detail ``phases`` provenance dict:
    steady-state per-phase seconds, host-RTT share, device occupancy,
    and the projected pipelined wall — the same numbers
    ``tools/roofline.py --phases`` reports from the span trace. None
    when the profiler was off (no rows)."""
    if not rows:
        return None
    names = ("host_prep", "enqueue", "device_compute", "readback")
    steady = [r for r in rows if not r.get("compile")]
    tot = {k: round(sum(r[k] for r in steady), 4) for k in names}
    host = tot["host_prep"] + tot["enqueue"] + tot["readback"]
    dev = tot["device_compute"]
    total = host + dev
    return {
        "dispatches": len(rows),
        "steady_dispatches": len(steady),
        "steady": tot,
        "host_share": round(host / max(total, 1e-12), 3),
        "device_occupancy": round(dev / max(total, 1e-12), 3),
        "projected_pipelined_sec": round(max(host, dev), 4),
    }


#: This bench process's start, for concurrency checks against artifacts
#: other tools write (a sweep that ended before we started never
#: perturbed this run's measurement).
_T0_UNIX = time.time()


def _artifact_fresh(path: str) -> bool:
    """Whether a lint-family artifact is FRESH: newer than every package
    source file and the waiver file. An artifact older than any of its
    inputs is a verdict about some other tree. Raises on a missing
    artifact (callers treat any failure as None-provenance)."""
    mtime = os.path.getmtime(path)
    inputs = [os.path.join(REPO, ".stpu-lint-waivers.toml")]
    pkg = os.path.join(REPO, "stateright_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        inputs += [
            os.path.join(dirpath, fn)
            for fn in filenames
            if fn.endswith(".py")
        ]
    return all(
        os.path.getmtime(p) <= mtime
        for p in inputs
        if os.path.exists(p)
    )


def _lint_ok() -> bool | None:
    """The stpu-lint verdict from runs/lint.json (written by
    tools/smoke.sh's lint stage / tools/stpu_lint.py --json-out), as
    tri-state provenance: True/False, or None when no artifact exists,
    it does not parse, it records a PARTIAL (--only/--rules filtered)
    run, or it is STALE (_artifact_fresh). An absent, partial, or stale
    lint run is not a pass."""
    try:
        path = os.path.join(RUNS, "lint.json")
        if not _artifact_fresh(path):
            return None
        with open(path) as fh:
            report = json.load(fh)
            if report.get("partial"):
                return None
            return bool(report["ok"])
    except Exception:
        return None


def _compile_plan() -> dict | None:
    """STPU007 compile-plan provenance from runs/compile_plan.json (the
    census a full stpu-lint run banks): per-spec distinct program-shape
    counts, or None when the artifact is missing, unparseable, or STALE
    (_artifact_fresh — a census about some other tree). The bench's own
    run may compile MORE shapes than the census (growth events double
    capacities); the census records the declared plan."""
    try:
        path = os.path.join(RUNS, "compile_plan.json")
        if not _artifact_fresh(path):
            return None
        with open(path) as fh:
            census = json.load(fh)
        return {
            "tree": census.get("tree"),
            "distinct_programs": {
                spec: {p: plan["distinct_programs"] for p, plan in plans.items()}
                for spec, plans in census["specs"].items()
            },
        }
    except Exception:
        return None


def _journal_provenance() -> dict | None:
    """Durable-service journal provenance from runs/service_chaos.json
    (the SLO line tools/service_chaos.py banks): per-scenario records
    replayed / jobs re-adopted on restart, or None when the artifact is
    missing, unparseable, or STALE (_artifact_fresh). Sits next to the
    "resume" dict: resume is THIS run's recovery story, journal is the
    service tier's."""
    try:
        path = os.path.join(RUNS, "service_chaos.json")
        if not _artifact_fresh(path):
            return None
        with open(path) as fh:
            line = json.load(fh)
        return {
            "seed": line.get("seed"),
            "ok": line.get("ok"),
            "scenarios": {
                name: rep.get("journal")
                for name, rep in line.get("scenarios", {}).items()
            },
        }
    except Exception:
        return None


def _fleet_provenance() -> dict | None:
    """Fleet-service provenance from the latest runs/service_chaos.json
    sweep (docs/service.md "Fleet"): device count and migration totals
    across the scenarios — next to "journal"/"resume" so
    tools/bench_regress.py can tell a clean line from one measured while
    the fleet was migrating work between devices. None when the sweep
    never ran in fleet mode (or is stale). `migrations` (bench_regress's
    throughput-skip trigger, whose claim is "measured AMID failover")
    only reports a sweep still writing after this bench started — an
    older sweep is device/ok provenance, not a perturbation, and must
    not permanently disable the regression gate."""
    try:
        path = os.path.join(RUNS, "service_chaos.json")
        if not _artifact_fresh(path):
            return None
        concurrent = os.path.getmtime(path) >= _T0_UNIX
        with open(path) as fh:
            line = json.load(fh)
        if not line.get("fleet_devices"):
            return None
        return {
            "devices": line["fleet_devices"],
            "ok": line.get("ok"),
            "migrations": (
                sum(
                    (rep.get("fleet") or {}).get("migrations") or 0
                    for rep in line.get("scenarios", {}).values()
                )
                if concurrent
                else 0
            ),
            "concurrent": concurrent,
            "sessions": line.get("sessions"),
        }
    except Exception:
        return None


def _regress_provenance() -> dict | None:
    """The latest perf-regression verdict from runs/regress.json (written
    by tools/bench_regress.py — the gate judging a fresh primary line
    against the archived runs/archive/BENCH_r*.json trajectory), or None
    when no verdict has been produced. Tolerant of a missing or empty
    archive by construction: the gate itself reports the typed
    "no_baseline" verdict there (fresh clones carry no trajectory), so
    this hook never crashes the bench over absent history."""
    try:
        with open(os.path.join(RUNS, "regress.json")) as fh:
            line = json.load(fh)
        return {
            "verdict": line.get("verdict"),
            "platform": line.get("platform"),
            "baseline": (line.get("baseline") or {}).get("best"),
        }
    except Exception:
        return None


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
    os.makedirs(RUNS, exist_ok=True)
    with open(os.path.join(RUNS, "bench_probe.log"), "a") as fh:
        fh.write(f"{time.strftime('%H:%M:%S')} {msg}\n")


def _tpu_available(timeout_s: int) -> bool:
    """Probe TPU availability in a subprocess: a killed probe counts as
    unavailable. The probe's own stderr is logged, not swallowed."""
    code = (
        "import jax; ds = jax.devices(); "
        "print('ok', [str(d) for d in ds], ds[0].platform)"
    )
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            timeout=timeout_s,
            capture_output=True,
            text=True,
        )
    except subprocess.TimeoutExpired as e:
        _log(
            f"TPU probe timed out after {timeout_s}s; stderr tail: "
            f"{(e.stderr or b'')[-500:] if isinstance(e.stderr, bytes) else (e.stderr or '')[-500:]}"
        )
        return False
    _log(
        f"TPU probe rc={proc.returncode} in {time.monotonic()-t0:.1f}s; "
        f"stdout={proc.stdout.strip()[:200]!r} stderr tail={proc.stderr[-500:]!r}"
    )
    return proc.returncode == 0 and "ok" in proc.stdout


def _run_check(model, detail: list | None, budget_s: float = float("inf"), **spawn_kwargs):
    """A check bounded by wall-clock ``budget_s``: runs whole dispatch
    blocks until done or out of budget; returns (generated_states, seconds,
    checker, completed, states_at_start). The budget means an arbitrarily
    large ``BENCH_RM`` space still yields a steady-state number in bounded
    time. ``states_at_start`` is nonzero only on a checkpoint resume — the
    throughput numerator is the states generated by THIS process."""
    # Deliberately IDENTICAL capacity kwargs for the warm and measured
    # passes (the learned-capacity hints are NOT merged in): every grown
    # capacity changes array shapes, so a measured pass spawned at the warm
    # pass's grown capacities re-traces every bucket program — paying
    # minutes of XLA compile to save a millisecond rehash. With identical
    # kwargs the measured pass replays the warm schedule (including the
    # same proactive growth points) and hits the compile cache at every
    # step. (checkpoint_to/checkpoint_every ride along freely: they change
    # no array shapes.)
    checker = model.checker().spawn_xla(**spawn_kwargs)
    states0 = checker.state_count() if spawn_kwargs.get("checkpoint") else 0
    t0 = time.monotonic()
    while not checker.is_done():
        if time.monotonic() - t0 > budget_s:
            _log(
                f"budget {budget_s:.0f}s exhausted at depth {checker._depth} "
                f"({checker.state_count()} states generated); "
                "reporting partial-coverage throughput"
            )
            break
        lvl_t0 = time.monotonic()
        log_mark = len(checker.level_log)
        checker._run_block()
        if detail is not None:
            # One row per device dispatch (its wall-clock is the host-
            # visible unit) carrying the engine's per-level telemetry.
            detail.append(
                {
                    "sec": round(time.monotonic() - lvl_t0, 4),
                    "levels": checker.level_log[log_mark:],
                }
            )
    elapsed = time.monotonic() - t0
    completed = checker.is_done()
    if completed:
        checker.assert_properties()
    # state_count() includes init states (the reference's reporter counts
    # them too, report.rs:66-73) — generated >= unique at every scale.
    return checker.state_count(), elapsed, checker, completed, states0


def _run_matrix(platform: str) -> list:
    """Secondary configs (BASELINE.json metric: states/sec/chip AND
    time-to-full-coverage): the flagship actor examples on the device
    engine. Warm + measured pass each; small spaces, so these anchor
    time-to-coverage rather than steady-state throughput."""
    from stateright_tpu.models.increment_lock import PackedIncrementLock
    from stateright_tpu.models.linearizable_register import (
        PackedAbd,
        PackedAbdOrdered,
    )
    from stateright_tpu.models.paxos import PackedPaxos
    from stateright_tpu.models.single_copy_register import PackedSingleCopyRegister

    rows = []
    for name, build, kwargs in [
        (
            "linearizable-register (ABD) 2c/2s packed",
            lambda: PackedAbd(2, 2),
            dict(frontier_capacity=1 << 10, table_capacity=1 << 12),
        ),
        (
            # The reference harness's ordered-channel config: BASELINE.json's
            # `linearizable-register check 2 ordered` (bench.sh:33 runs the
            # same model at 3 clients) — ABD over FifoLanes.
            "linearizable-register (ABD) 2c/2s ordered packed",
            lambda: PackedAbdOrdered(2, 2),
            dict(frontier_capacity=1 << 10, table_capacity=1 << 12),
        ),
        (
            "paxos 2c/3s packed",
            lambda: PackedPaxos(2, 3),
            dict(frontier_capacity=1 << 12, table_capacity=1 << 16),
        ),
        (
            # BASELINE.json's "single-copy-register check 3": 3 clients,
            # linearizability checked device-exact over the 3-thread
            # interleaving enumeration.
            "single-copy-register 3c/1s packed",
            lambda: PackedSingleCopyRegister(3, 1),
            dict(frontier_capacity=1 << 11, table_capacity=1 << 14),
        ),
        (
            "increment_lock 3t packed",
            lambda: PackedIncrementLock(3),
            dict(frontier_capacity=1 << 10, table_capacity=1 << 13),
        ),
    ]:
        try:
            budget = float(os.environ.get("BENCH_MATRIX_BUDGET_S", "300"))
            model = build()
            t0 = time.monotonic()
            _run_check(model, None, budget_s=budget, **kwargs)  # warm: compiles
            warm = time.monotonic() - t0
            states, sec, checker, done, _ = _run_check(
                model, None, budget_s=budget, **kwargs
            )
            if not done:
                rows.append(
                    {"config": name, "error": f"budget {budget:.0f}s exhausted"}
                )
                _log(f"matrix {name}: budget exhausted")
                continue
            rows.append(
                {
                    "config": name,
                    "platform": platform,
                    "generated_states": states,
                    "unique_states": checker.unique_state_count(),
                    "warm_pass_sec": round(warm, 3),
                    "time_to_full_coverage_sec": round(sec, 3),
                    "states_per_sec": round(states / max(sec, 1e-9), 1),
                    "count_ok": _count_check(
                        name,
                        EXPECTED_MATRIX.get(name),
                        states,
                        checker.unique_state_count(),
                    ),
                    "audit": _audit(checker),
                }
            )
            _log(f"matrix {name}: {rows[-1]}")
        except Exception as e:  # keep the primary metric alive no matter what
            _log(f"matrix {name} FAILED: {type(e).__name__}: {e}")
            rows.append({"config": name, "error": f"{type(e).__name__}: {e}"})
    return rows


def _run_sym_ab(platform: str) -> dict:
    """``BENCH_SYM=1``: the symmetry-reduction A/B probe
    (docs/symmetry.md). One shipped spec (``BENCH_SYM_SPEC``, default
    2pc:4) runs full-space and symmetry-reduced back to back in this
    worker on the same engine configuration — reporting the class
    collapse (unique_full/unique_reduced), the wall-clock ratio (the
    in-superstep canonicalization network should be ~free against the
    table sorts it shrinks), and the reduced run's duplicate-key audit.
    Exactness pins live in tests/test_symmetry.py; this row is the
    trend line bench_regress watches."""
    from stateright_tpu.service import registry

    spec = os.environ.get("BENCH_SYM_SPEC", "2pc:4")
    runs = {}
    for mode in ("off", "on"):
        model, caps = registry.resolve(spec)
        t0 = time.monotonic()
        checker = model.checker().spawn_xla(symmetry=mode, **caps).join()
        runs[mode] = (time.monotonic() - t0, checker)
    off_sec, off_c = runs["off"]
    on_sec, on_c = runs["on"]
    full = off_c.unique_state_count()
    reduced = on_c.unique_state_count()
    return {
        "spec": spec,
        "sym_tag": on_c.metrics().get("symmetry"),
        "unique_full": full,
        "unique_reduced": reduced,
        "collapse": round(full / max(reduced, 1), 3),
        "off_sec": round(off_sec, 3),
        "on_sec": round(on_sec, 3),
        "speedup": round(off_sec / max(on_sec, 1e-9), 3),
        "audit": _audit(on_c),
    }


def _worker(platform: str) -> None:
    """Child-process body: the actual measurement, on ``platform``. Writes
    bench_detail.json and prints the final JSON line on stdout. The parent
    holds the watchdog; this process just works."""
    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from stateright_tpu.backend import configure_compile_cache

    configure_compile_cache()

    rm = int(os.environ.get("BENCH_RM", "8"))
    frontier_pow = int(os.environ.get("BENCH_FRONTIER_POW", "19"))
    # The default table size follows the EFFECTIVE dedup structure, because
    # the two families want opposite sizing: sorted/delta pay one
    # [capacity + batch] sort per level, so oversizing costs every level —
    # 2^22 holds rm=8's 1.74M uniques within the 3/4-load growth rule with
    # no growth recompiles. The hash structure wants probe-chain headroom
    # under its 1/4-load rule — 2^24 keeps an rm=8 A/B run (BENCH_DEDUP=
    # hash) from paying a mid-measurement growth recompile at 2^22, which
    # would skew exactly the hash-vs-sorted comparison the knob exists for.
    # A pallas/bsearch compaction request forces a planes-engine dedup.
    # spawn_xla's own auto resolves the same way since r5e (and raises
    # on an explicit hash + planes-only combination); mirroring it here
    # keeps the logged/reported dedup truthful.
    planes_only_compaction = os.environ.get("STPU_COMPACTION") in (
        "pallas",
        "bsearch",
    )
    effective_dedup = os.environ.get("BENCH_DEDUP") or (
        "hash" if platform == "cpu" and not planes_only_compaction
        else "sorted"
    )
    default_table_pow = "24" if effective_dedup == "hash" else "22"
    table_pow = int(os.environ.get("BENCH_TABLE_POW", default_table_pow))
    if platform == "cpu":
        rm = min(rm, int(os.environ.get("BENCH_CPU_RM", "7")))
        frontier_pow = min(frontier_pow, 17)
        table_pow = min(table_pow, 21)
    _log(
        f"worker platform={platform} rm={rm} frontier=2^{frontier_pow} "
        f"table=2^{table_pow} dedup={effective_dedup}"
    )

    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    # ONE model instance for both passes: compiled supersteps are cached on
    # the model, so pass 2 reuses every bucket compilation from pass 1.
    model = PackedTwoPhaseSys(rm)

    # TPU warm passes pay one XLA compile per superstep bucket (the TPU
    # compiler takes tens of seconds per bucket at rm=8) — the warm budget must cover them
    # or the measured pass inherits the leftovers and reads artificially low.
    default_warm = "600" if platform == "cpu" else "1500"
    warm_budget = float(os.environ.get("BENCH_WARM_BUDGET_S", default_warm))
    measure_budget = float(os.environ.get("BENCH_MEASURE_BUDGET_S", "300"))
    # Primary-pass ladder, platform-resolved. On 1-core CPU "ramp" wins:
    # every level runs at its snug bucket and padded lanes are real work.
    # On TPU the round-5 A/B measured "jump" FASTER even on the measured
    # pass (6.81s vs 8.70s at rm=8, tpu_profile_r5.log vs bench_detail):
    # padding a level costs almost nothing on-chip while every extra
    # bucket is another compiled program the dispatch pipeline switches
    # through. Both run the same count-checked full coverage.
    # BENCH_LADDER overrides for the on-chip A/B.
    spawn_kwargs = dict(
        frontier_capacity=1 << frontier_pow,
        table_capacity=1 << table_pow,
        ladder=os.environ.get("BENCH_LADDER")
        or ("ramp" if platform == "cpu" else "jump"),
    )
    # Visited-set structure: ALWAYS pinned to the dedup this worker logs
    # and records. spawn_xla's own auto resolves from the REAL jax
    # backend; pinning keeps the artifact truthful to its label on every
    # backend.
    spawn_kwargs["dedup"] = effective_dedup

    # Crash recovery (stateright_tpu/checkpoint.py + supervise.py): the
    # primary passes auto-checkpoint to rotated files under runs/, and a
    # RELAUNCHED worker (the parent's watchdog killed a wedged
    # predecessor) resumes from the latest valid rotation instead of
    # restarting from level 0 — the parent clears stale rotations at the
    # start of every bench invocation, so an on-disk checkpoint always
    # belongs to THIS bench run. A measured-pass checkpoint wins (the warm
    # compiles are already banked in .jax_cache); a warm one resumes the
    # warm pass. Validation guards the CPU fallback: its smaller
    # BENCH_CPU_RM model must not resume a checkpoint of a different
    # configuration.
    from stateright_tpu.checkpoint import (
        latest_valid_checkpoint,
        validate_model,
    )

    checkpointing = os.environ.get("BENCH_CHECKPOINT", "1") != "0"
    ck_every = os.environ.get("BENCH_CHECKPOINT_EVERY", "60s")
    prop_names = [p.name for p in model.properties()]

    def _valid_resume(base, skip_completed=False):
        # with_meta: validation already paid the decompress+digest pass —
        # at soak-scale tables a second load_checkpoint here costs minutes.
        path, meta = latest_valid_checkpoint(base, with_meta=True)
        if path is None:
            return None, None
        try:
            validate_model(meta, model, prop_names)
            # Every v3 checkpoint writes "done" (wider than the
            # exhausted/target_reached flags — see checkpoint.py); a v3
            # file without it is malformed and lands in the except arm.
            done = meta["done"]
        except Exception as e:
            _log(f"not resuming from {path}: {type(e).__name__}: {e}")
            return None, None
        if skip_completed and done:
            # A COMPLETED measured pass whose primary line never made it
            # out (killed in the gap before printing): resuming it would
            # measure zero work. Fall back to the warm checkpoint — a
            # completed warm resume is instant and the measured pass
            # re-runs fresh, yielding a real number.
            _log(f"not resuming from {path}: already-completed run")
            return None, None
        return path, meta

    resumed_from = resume_phase = resume_meta = None
    if checkpointing:
        resumed_from, resume_meta = _valid_resume(CK_MEASURED, skip_completed=True)
        if resumed_from is not None:
            resume_phase = "measured"
        else:
            resumed_from, resume_meta = _valid_resume(CK_WARM)
            if resumed_from is not None:
                resume_phase = "warm"

    def _ck_kwargs(base):
        if not checkpointing:
            return {}
        return dict(
            checkpoint_to=base, checkpoint_every=ck_every, checkpoint_keep=3
        )

    if resume_phase == "measured":
        # The wedge hit mid-measurement: skip the warm pass (its compiles
        # are on disk) and continue the measured pass where it left off.
        _log(
            f"resuming measured pass from {resumed_from} "
            f"(depth {resume_meta['depth']}, "
            f"{resume_meta['state_count']} states); warm pass skipped"
        )
        warm_states, warm_sec = 0, 0.0
    else:
        wkw = dict(spawn_kwargs, **_ck_kwargs(CK_WARM))
        if resume_phase == "warm":
            _log(
                f"resuming warm pass from {resumed_from} "
                f"(depth {resume_meta['depth']})"
            )
            wkw["checkpoint"] = resumed_from
        warm_states, warm_sec, _, _, _ = _run_check(
            model, None, budget_s=warm_budget, **wkw
        )
        _log(
            f"warm pass: {warm_states} states in {warm_sec:.2f}s "
            "(compile included)"
        )

    mkw = dict(spawn_kwargs, **_ck_kwargs(CK_MEASURED))
    if resume_phase == "measured":
        mkw["checkpoint"] = resumed_from
    detail: list = []
    states, elapsed, checker, completed, states0 = _run_check(
        model, detail, budget_s=measure_budget, **mkw
    )
    value = (states - states0) / max(elapsed, 1e-9)
    resumed_note = (
        f", resumed at depth {resume_meta['depth']}"
        if resume_phase == "measured"
        else ""
    )
    _log(
        f"measured pass: {states} states ({checker.unique_state_count()} unique, "
        f"depth {checker.max_depth()}, {'full' if completed else 'partial'} "
        f"coverage{resumed_note}) in {elapsed:.2f}s -> {value:,.0f} states/s"
    )
    # Exact-count self-check (pure host arithmetic — safe before the
    # primary print; only full coverage pins the totals). The table AUDIT
    # is a device-to-host readback of the whole key planes and therefore
    # runs AFTER the primary line is out: a hang mid-transfer must
    # not take the already-measured number with it.
    count_ok = (
        _count_check(f"2pc rm={rm}", EXPECTED_2PC.get(rm), states,
                     checker.unique_state_count())
        if completed
        else None
    )

    # The primary metric line goes out IMMEDIATELY: the matrix below may
    # outlive the parent's watchdog, and a killed worker must not take the
    # already-measured number with it (the parent salvages stdout).
    print(
        json.dumps(
            {
                "metric": f"2pc(rm={rm}) generated states/sec, spawn_xla, {platform}",
                "value": round(value, 1),
                "unit": "states/sec",
                "vs_baseline": round(value / NORTH_STAR, 4),
                "count_ok": count_ok,
                # The REAL backend, not the platform label: a
                # chip-labeled row banking CPU numbers poisons the A/B
                # record.
                "backend": jax.default_backend(),
                # Resume provenance: a resumed line measures the tail of a
                # space from a checkpoint, not a cold full pass — it must
                # be distinguishable at a glance (detail in
                # bench_detail.json's "resume" dict).
                "resumed": resume_phase,
            }
        ),
        flush=True,
    )

    # Host-side duplicate-key audit (tri-state like count_ok: an audit
    # that itself errored reports the error, not a corruption verdict).
    # The result reaches the driver via bench_detail.json and the logged
    # line in bench_probe.log.
    audit = _audit(checker)
    if "error" in audit:
        _log(f"table audit ERRORED (no verdict): {audit}")
    elif not audit.get("ok", False):
        _log(f"TABLE AUDIT FAILED: {audit}")
    else:
        _log(f"table audit: {audit}")

    # Candidate-ladder telemetry (attack #2 evidence for the A/B record):
    # the level rows inside ``detail`` carry the chosen per-level
    # bucket/cand_cap and the cost-law lane-words; summarize them here so
    # BENCH_r06+ carries the engine-measured numbers at the top level.
    import statistics

    _rows = [l for block in detail for l in block.get("levels", [])]
    _lane = sorted(l["lane_words"] for l in _rows if "lane_words" in l)
    lane_summary = (
        {
            # statistics.median everywhere (here, roofline, cand_ab) so
            # the attack-#2 evidence artifacts agree on even-length logs.
            "median": statistics.median(_lane),
            "mean": round(sum(_lane) / len(_lane)),
            "max": _lane[-1],
            "total": sum(_lane),
        }
        if _lane
        else None
    )

    # Dispatch-phase provenance (tools/roofline.py --phases): when the
    # profiler ran (STPU_PHASES=1, needs STPU_TRACE), the measured
    # pass's per-call host/enqueue/device/readback split summarizes
    # here, so a banked row carries the pipelining-attack numbers.
    phase_summary = _phase_summary(getattr(checker, "phase_log", None))

    sym_info = None

    def write_detail(matrix):
        os.makedirs(RUNS, exist_ok=True)
        with open(os.path.join(RUNS, "bench_detail.json"), "w") as fh:
            json.dump(
                {
                    "platform": platform,
                    "backend": jax.default_backend(),
                    "rm": rm,
                    # Obs artifacts of this run (docs/observability.md):
                    # the span JSONL (tools/roofline.py --measured reads
                    # it), the watchdog heartbeat, and the metrics
                    # time-series (roofline's fallback source when no
                    # span trace exists), when enabled.
                    "trace": os.environ.get("STPU_TRACE") or None,
                    "heartbeat": os.environ.get("STPU_HEARTBEAT") or None,
                    "metrics_series": os.environ.get("STPU_METRICS_TO") or None,
                    "metrics": checker.metrics(),
                    "table_capacity": checker._table.capacity,
                    "cand_ladder": checker._cand_ladder_k,
                    "cand_retries": checker.cand_retries,
                    "lane_words_per_level": lane_summary,
                    # Dispatch-phase split (STPU_PHASES=1; None when the
                    # profiler was off).
                    "phases": phase_summary,
                    # Resume provenance: which checkpoint (if any) this
                    # worker resumed from, which pass it belonged to, and
                    # the attempt index the parent stamped. levels_replayed
                    # is 0 by construction — a resume starts AT the
                    # checkpoint's depth; nothing before it re-runs (the
                    # alternative, a level-0 restart, replays everything).
                    "resume": {
                        "resumed_from": resumed_from,
                        "phase": resume_phase,
                        "attempt": int(os.environ.get("BENCH_ATTEMPT", "0")),
                        "resume_depth": (
                            resume_meta["depth"] if resume_meta else None
                        ),
                        "states_at_resume": states0,
                        "levels_replayed": 0,
                    },
                    # Durable-service provenance (docs/service.md
                    # "Durability & recovery"): the latest seeded
                    # service_chaos sweep's journal verdicts — records
                    # replayed and jobs re-adopted across restarts.
                    "journal": _journal_provenance(),
                    # Fleet provenance (docs/service.md "Fleet"): device
                    # count + migrations from the latest fleet-mode
                    # sweep — bench_regress skips honestly on lines
                    # measured amid cross-device migrations.
                    "fleet": _fleet_provenance(),
                    # Perf-regression provenance (tools/bench_regress.py):
                    # the last gate verdict against the archived
                    # trajectory, when one exists. The gate runs AFTER a
                    # bench (it consumes this very file), so this records
                    # the previous verdict — trajectory context, not this
                    # run's judgment.
                    "regress": _regress_provenance(),
                    # stpu-lint provenance (docs/static-analysis.md):
                    # the latest runs/lint.json verdict — True/False, or
                    # None when no lint artifact exists (run
                    # tools/smoke.sh or tools/stpu_lint.py --json-out
                    # runs/lint.json). A banked bench row should carry
                    # lint_ok: true — numbers measured on a tree that
                    # violates a pinned-miscompile rule are suspect.
                    "lint_ok": _lint_ok(),
                    # STPU007 census provenance: the compile-shape plan
                    # this tree declares (what warm_cache pre-seeds and
                    # a cold chip run should expect to pay).
                    "compile_plan": _compile_plan(),
                    "generated_states": states,
                    "unique_states": checker.unique_state_count(),
                    "max_depth": checker.max_depth(),
                    "warm_pass_sec": round(warm_sec, 3),
                    "measured_sec": round(elapsed, 3),
                    "full_coverage": completed,
                    "states_per_sec": round(value, 1),
                    "count_ok": count_ok,
                    "audit": audit,
                    # Symmetry-reduction A/B (BENCH_SYM=1;
                    # docs/symmetry.md): class collapse and wall-clock
                    # ratio for one spec, full-space vs reduced. None
                    # unless the knob is set.
                    "sym": sym_info,
                    "levels": detail,
                    "matrix": matrix,
                },
                fh,
                indent=1,
            )

    # Write the detail now (sans matrix) so a watchdog kill mid-matrix
    # cannot lose it, then rewrite with the matrix rows.
    write_detail([{"note": "matrix still running (or killed mid-run)"}])
    matrix = []
    if os.environ.get("BENCH_MATRIX", "1") != "0":
        try:
            matrix = _run_matrix(platform)
        except Exception as e:  # the primary metric line must survive
            _log(f"matrix runner FAILED: {type(e).__name__}: {e}")
            matrix = [{"error": f"{type(e).__name__}: {e}"}]
    if os.environ.get("BENCH_SYM", "0") not in ("", "0"):
        try:
            sym_info = _run_sym_ab(platform)
            _log(f"sym A/B: {sym_info}")
        except Exception as e:  # same contract as the matrix
            _log(f"sym A/B FAILED: {type(e).__name__}: {e}")
            sym_info = {"error": f"{type(e).__name__}: {e}"}
    write_detail(matrix)


def _json_lines(text) -> list:
    if isinstance(text, bytes):
        text = text.decode(errors="replace")
    return [l for l in (text or "").splitlines() if l.strip().startswith("{")]


def _spawn_worker(platform: str, timeout_s: float, attempt: int = 0) -> str | None:
    """Runs ``bench.py --worker <platform>`` under the heartbeat-aware
    watchdog of ``stateright_tpu/supervise.py`` (the generalized library
    form of the loop that used to live here — bench holds NO watchdog
    logic of its own); returns the worker's primary JSON line or None.

    The worker's engines rewrite the heartbeat file around every device
    dispatch (STPU_HEARTBEAT, injected by run_worker unless
    BENCH_HEARTBEAT=0), so the watchdog distinguishes in-band instead of
    guessing from one hard timeout: a stale beat in ``phase="dispatch"``
    is a hung dispatch (leash ``BENCH_STALL_S``, stretched 3x when the
    beat flags an XLA compile); a worker that never beats gets
    ``BENCH_STARTUP_GRACE_S`` (imports + init inserts can wedge before the
    first dispatch); a beating worker may run to the hard ``timeout_s``
    cap. A worker killed mid-matrix still counts as success if it printed
    the primary line first (stdout salvage below). ``attempt`` is stamped
    into the worker env as BENCH_ATTEMPT for resume provenance."""
    from stateright_tpu import supervise as sup

    os.makedirs(RUNS, exist_ok=True)
    env = dict(os.environ)
    env["BENCH_ATTEMPT"] = str(attempt)
    hb_path = None
    if platform != "cpu" and os.environ.get("BENCH_HEARTBEAT", "1") != "0":
        hb_path = os.environ.get("STPU_HEARTBEAT") or os.path.join(
            RUNS, "heartbeat.json"
        )
    if platform == "cpu":
        # On a small CPU box a long steady dispatch is routine — only
        # the hard timeout supervises the CPU fallback. Popped from the
        # child env too: an outer watcher supervising the same heartbeat
        # path must not see CPU-paced dispatch beats and kill the run.
        env.pop("STPU_HEARTBEAT", None)
    res = sup.run_worker(
        [sys.executable, os.path.abspath(__file__), "--worker", platform],
        heartbeat=hb_path,
        timeout_s=timeout_s,
        # The leash must out-wait a HEALTHY steady dispatch: a fused
        # device call covers up to levels_per_dispatch=32 BFS levels with
        # no beat in between, which at soak scale legitimately runs many
        # minutes.
        stall_s=float(os.environ.get("BENCH_STALL_S", "1200")),
        startup_grace_s=float(os.environ.get("BENCH_STARTUP_GRACE_S", "900")),
        env=env,
        cwd=REPO,
        # Worker stdout goes to a file, not a pipe: the parent never reads
        # concurrently, so a pipe could deadlock a chatty worker; a file
        # also survives for post-mortem salvage no matter how the worker
        # dies.
        stdout_path=os.path.join(RUNS, f"worker_{platform}.out"),
        log=_log,
    )
    with open(res.stdout_path) as fh:
        lines = _json_lines(fh.read())
    if res.killed is not None:
        if lines:
            _log(
                f"{platform} worker killed ({res.killed}) but the primary "
                "metric was already out; using it"
            )
            return lines[0]
        _log(f"{platform} worker killed: {res.killed}")
        return None
    if not lines:
        _log(f"{platform} worker rc={res.rc} in {res.seconds:.0f}s, no JSON line")
        return None
    if res.rc != 0:
        # Died (wedged mid-matrix and externally terminated, OOM, ...)
        # AFTER the primary metric went out: the measurement happened —
        # use it, exactly like the watchdog salvage above.
        _log(
            f"{platform} worker rc={res.rc} in {res.seconds:.0f}s but the "
            "primary metric was already out; using it"
        )
        return lines[0]
    _log(f"{platform} worker ok in {res.seconds:.0f}s")
    return lines[0]


def _clear_checkpoints() -> None:
    """A fresh bench invocation must not resume a PREVIOUS invocation's
    checkpoints: clear every rotation of both bases up front, so an
    on-disk checkpoint always means 'written by this run's earlier
    attempt'."""
    from stateright_tpu.checkpoint import rotations

    for base in (CK_WARM, CK_MEASURED):
        for path in rotations(base):
            try:
                os.unlink(path)
            except OSError:
                pass


def main() -> None:
    sys.path.insert(0, REPO)
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        _worker(sys.argv[2])
        return

    probe_s = int(os.environ.get("BENCH_TPU_PROBE_S", "300"))
    worker_timeout = float(os.environ.get("BENCH_WORKER_TIMEOUT_S", "2400"))
    retries = int(os.environ.get("BENCH_TPU_RETRIES", "2"))

    _clear_checkpoints()
    line = None
    if _tpu_available(probe_s):
        for attempt in range(1 + retries):
            if attempt:
                _log(
                    f"TPU retry {attempt}/{retries} (compile cache warm; "
                    "resuming from the latest valid checkpoint, not level 0)"
                )
            line = _spawn_worker("tpu", worker_timeout, attempt=attempt)
            if line is not None:
                break
    else:
        _log("TPU unavailable; skipping to CPU fallback")
    if line is None:
        line = _spawn_worker("cpu", worker_timeout)
    if line is None:  # last resort: the driver always gets a line
        line = json.dumps(
            {
                "metric": "2pc generated states/sec, spawn_xla, none (all workers failed)",
                "value": 0.0,
                "unit": "states/sec",
                "vs_baseline": 0.0,
            }
        )
    print(line)


if __name__ == "__main__":
    main()
