"""Durable CheckerService pins (ISSUE 12 acceptance).

The pool must survive its own death: a crash-safe job journal
(``service/journal.py``), restart recovery in ``CheckerService``
(re-adopt checkpoints, requeue in-flight work, dedupe resubmissions,
restore the breaker), and the deterministic fault-injection layer
(``stateright_tpu/chaos.py``) that drives every one of those paths on a
seeded schedule instead of hand-rolled signals.

- **Journal discipline**: sha256-per-record appends; a tail torn at a
  RANDOM byte is a typed, recoverable condition — replay succeeds minus
  the torn record; compaction rewrites the log as one snapshot,
  atomically, with keep-K rotations.
- **Restart recovery** (no workers needed — the journal is the
  contract): journal-complete jobs restore done without re-running;
  idempotent resubmission after a restart returns the SAME job; an
  in-flight job whose budget was already spent fails typed, not re-run;
  a restored-open breaker re-probes immediately.
- **Chaos layer**: zero overhead with ``STPU_CHAOS`` unset (pinned);
  seeded plans fire deterministically; the ``checkpoint.torn`` hook
  tears a real rotation that ``latest_valid_checkpoint`` then falls
  back from; ``supervise.wedge`` draws a scripted wedge verdict.
- **Restart drills** (the real service, killed for real):
  ``test_smoke_service_restart_resume`` (<30s, rides in
  ``tools/smoke.sh``) — the service dies right after journaling
  ``started``, the restart kills the orphaned worker, requeues, and
  converges to exact pinned counts; the <60s 3-concurrent-job SIGKILL
  and torn-tail convergence pins ride the ``tools/service_chaos.py``
  harness (exactly-once, counts bit-identical to the undisturbed run).
"""

import importlib.util
import json
import os
import random
import time

import pytest

from stateright_tpu import chaos
from stateright_tpu.service import (
    AdmissionError,
    CheckerService,
    FleetConfig,
    FleetService,
    Journal,
    JournalTorn,
    ServiceConfig,
    read_journal,
)
from stateright_tpu.service.core import _replay_state
from stateright_tpu.service.fleet import _fleet_replay

#: Pinned full-coverage (generated, unique) counts (bench.py EXPECTED_*).
PINNED_2PC3 = (1_146, 288)


def _harness():
    """tools/service_chaos.py as an importable module (the harness the
    restart drills drive; same trick test_analysis uses for warm_cache)."""
    path = os.path.join(
        os.path.dirname(__file__), "..", "tools", "service_chaos.py"
    )
    spec = importlib.util.spec_from_file_location("service_chaos", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _no_ambient_chaos(monkeypatch):
    """Each test starts with no installed plan and no STPU_CHAOS."""
    monkeypatch.delenv("STPU_CHAOS", raising=False)
    chaos.install(None)
    yield
    chaos.install(None)


def _config(tmp_path, **kw):
    base = dict(
        run_dir=str(tmp_path / "svc"),
        platform="cpu",
        default_max_seconds=420.0,
        stall_s=8.0,
        startup_grace_s=240.0,
        poll_s=0.2,
        backoff_s=0.1,
        probe_auto=False,
        admission_lint=False,
    )
    base.update(kw)
    return ServiceConfig(**base)


# --- the journal ------------------------------------------------------------


def test_journal_round_trip_and_digests(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    for i in range(4):
        rec = j.append("submitted", ts=100.0 + i, job=f"job-{i:04d}",
                       spec="2pc:3")
        assert rec["seq"] == i + 1 and rec["sha256"]
    replay = read_journal(path)
    assert replay.torn is None
    assert [r["job"] for r in replay.records] == [
        f"job-{i:04d}" for i in range(4)
    ]
    # A tampered mid-file record fails its digest: replay stops there,
    # typed — nothing after an untrusted record can be ordered.
    lines = open(path).read().splitlines()
    lines[1] = lines[1].replace("2pc:3", "2pc:9")
    (tmp_path / "j.jsonl").write_text("\n".join(lines) + "\n")
    tampered = read_journal(path)
    assert len(tampered.records) == 1
    assert "digest mismatch" in tampered.torn
    with pytest.raises(JournalTorn):
        read_journal(path, strict=True)


def test_journal_torn_tail_at_random_byte(tmp_path):
    """Truncate the journal at a RANDOM byte: replay returns the clean
    prefix and reports the torn tail — never raises, never wedges."""
    rng = random.Random(1234)
    for _ in range(8):
        path = str(tmp_path / f"j{rng.randint(0, 1 << 30)}.jsonl")
        j = Journal(path)
        for i in range(5):
            j.append("submitted", ts=float(i), job=f"job-{i:04d}", spec="s")
        j.close()
        data = open(path, "rb").read()
        cut = rng.randint(1, len(data) - 1)
        with open(path, "wb") as fh:
            fh.write(data[:cut])
        replay = read_journal(path)
        # Whole records before the cut replay; at most one record is
        # lost. A cut exactly ON a record boundary leaves no torn
        # evidence (the file just ends earlier) — every mid-record cut
        # is reported.
        complete = data[:cut].count(b"\n")
        assert len(replay.records) == complete
        assert (replay.torn is None) == data[:cut].endswith(b"\n")


def test_journal_compaction_snapshot_and_rotation(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = Journal(path, keep=2, compact_every=3)
    for i in range(3):
        j.append("submitted", ts=float(i), job=f"job-{i:04d}", spec="s")
    assert j.compaction_due
    j.compact({"next_id": 3, "jobs": {}}, ts=3.0)
    assert not j.compaction_due
    live = read_journal(path)
    assert [r["event"] for r in live.records] == ["snapshot"]
    assert live.records[0]["state"]["next_id"] == 3
    # The pre-compaction history rotated to .1, intact.
    rot = read_journal(path + ".1")
    assert [r["event"] for r in rot.records] == ["submitted"] * 3
    # seq is contiguous across the compaction boundary.
    assert live.records[0]["seq"] == 4


def test_replay_state_folds_snapshot_and_events():
    records = []

    def rec(event, **kw):
        r = {"v": 1, "seq": len(records) + 1, "event": event, **kw}
        records.append(r)
        return r

    rec("submitted", ts=1.0, job="job-0001", spec="2pc:3",
        max_seconds=60.0, idempotency_key="k1", dir="s/job-0001")
    rec("admitted", ts=1.0, job="job-0001", lint_ok=None)
    rec("started", ts=2.0, job="job-0001", attempt=0, engine="xla", pid=999)
    rec("breaker_tripped", ts=3.0, consecutive=3)
    rec("completed", ts=4.0, job="job-0001", status="done", error=None,
        result={"generated": 10, "unique": 5})
    state = _replay_state(records)
    assert state["breaker"] == "open"
    assert state["idem"] == {"k1": "job-0001"}
    job = state["jobs"]["job-0001"]
    assert job["status"] == "done" and job["completed_unix_ts"] == 4.0
    assert job["result"]["generated"] == 10
    assert state["counters"]["jobs_done"] == 1
    assert state["counters"]["breaker_trips"] == 1
    assert state["last_ts"] == 4.0


def test_replay_evacuated_carries_the_attempt_charge():
    """The `evacuated` event journals the killed attempt's wall-clock: a
    crash between the pool's `evacuated` append and the fleet's
    `migrated` append must not refund the budget the straggler repair
    resubmits with (evacuate() charges in memory AND in the event)."""
    records = []

    def rec(event, **kw):
        r = {"v": 1, "seq": len(records) + 1, "event": event, **kw}
        records.append(r)
        return r

    rec("submitted", ts=1.0, job="job-0001", spec="2pc:3",
        max_seconds=60.0, dir="s/job-0001")
    rec("admitted", ts=1.0, job="job-0001", lint_ok=None)
    rec("started", ts=2.0, job="job-0001", attempt=0, engine="xla", pid=999)
    rec("evacuated", ts=52.0, job="job-0001", reason="device-0 lost",
        consumed_s=50.0)
    state = _replay_state(records)
    job = state["jobs"]["job-0001"]
    assert job["status"] == "migrated"
    assert job["consumed_s"] == 50.0
    assert job["pid"] is None  # the worker group was killed, no orphan
    # A `started` journaled AFTER `evacuated` (the spawn/evacuate race's
    # window) must not resurrect the evacuated job as running — the
    # sibling pool owns the live copy.
    rec("started", ts=52.5, job="job-0001", attempt=1, engine="xla",
        pid=1000)
    state = _replay_state(records)
    job = state["jobs"]["job-0001"]
    assert job["status"] == "migrated" and job["pid"] is None


def test_harness_schedule_and_faults_are_seed_deterministic():
    """`tools/service_chaos.py --seed N` is reproducible: the submission
    schedule and the fault plan are pure functions of the seed (the full
    journal-event-sequence pin is the harness's own --check-repro)."""
    sc = _harness()
    assert sc.build_schedule(7, 3, 240.0) == sc.build_schedule(7, 3, 240.0)
    assert sc.build_schedule(7, 3, 240.0) != sc.build_schedule(8, 3, 240.0)
    for scenario in ("kill", "die", "torn"):
        assert sc.fault_plan(7, scenario) == sc.fault_plan(7, scenario)
    # Golden values pin CROSS-PROCESS stability (a per-process
    # within-run comparison would be blind to PYTHONHASHSEED-style
    # randomization — the bug the crc32 seed derivation fixed).
    assert sc.fault_plan(42, "kill") == {"kill_after_s": 4.861}
    assert sc.fault_plan(42, "die") == {"die_at_record": 9}
    assert sc.fault_plan(42, "torn") == {"torn_at_record": 6}


# --- the chaos layer --------------------------------------------------------


def test_chaos_off_is_a_noop():
    """The zero-overhead-off pin (like the obs NULL_TRACER guard): with
    STPU_CHAOS unset nothing is parsed, no plan exists, and every hook
    call is a fast None."""
    assert chaos.fire("journal.torn", size=100) is None
    assert chaos.fire("supervise.wedge") is None
    assert not chaos.active()
    assert chaos._PLAN is None  # no ChaosPlan was ever constructed


def test_chaos_plan_parse_and_triggers():
    plan = chaos.ChaosPlan("seed=9;a.b@n=2:at=17,mode=x;c.d@p=0.5;e.f")
    assert plan.seed == 9
    # @n=K: exactly the K-th invocation.
    assert plan.fire("a.b") is None
    assert plan.fire("a.b") == {"at": 17, "mode": "x"}
    assert plan.fire("a.b") is None
    # no trigger: every invocation.
    assert plan.fire("e.f") == {}
    assert plan.fire("e.f") == {}
    # unknown point: never.
    assert plan.fire("nope") is None
    # @p=F: seeded — two plans from the same spec agree exactly.
    twin = chaos.ChaosPlan("seed=9;a.b@n=2:at=17,mode=x;c.d@p=0.5;e.f")
    seq = [plan.fire("c.d") is not None for _ in range(32)]
    twin_seq = [twin.fire("c.d") is not None for _ in range(32)]
    assert seq == twin_seq and True in seq and False in seq
    # default `at` for torn faults is seeded from ctx size.
    p2 = chaos.ChaosPlan("seed=3;t.x")
    inj = p2.fire("t.x", size=50)
    assert 1 <= inj["at"] < 50
    with pytest.raises(ValueError):
        chaos.ChaosPlan("bad clause@@")


def test_chaos_install_same_spec_keeps_the_live_plan():
    """Re-installing the SAME spec is a no-op (fire counters survive):
    the fleet installs once, then each per-device pool's constructor
    installs the identical spec — a reset mid-construction would lose
    counts a pool replay already fired."""
    plan = chaos.install("seed=1;a.b@n=2")
    assert plan.fire("a.b") is None  # invocation 1 of 2
    assert chaos.install("seed=1;a.b@n=2") is plan
    assert plan.fire("a.b") == {}  # invocation 2 still fires
    # A DIFFERENT spec replaces the plan; None clears it.
    assert chaos.install("seed=1;a.b@n=3") is not plan
    assert chaos.install(None) is None


def test_chaos_supervise_wedge_verdict(tmp_path):
    """A scripted wedge verdict kills the worker group and classifies as
    wedged — the breaker/quarantine evidence path, no SIGSTOP needed."""
    import sys

    from stateright_tpu.supervise import run_worker

    chaos.install("supervise.wedge@n=2")
    res = run_worker(
        [sys.executable, "-c", "import time; time.sleep(60)"],
        poll_s=0.1,
        timeout_s=30.0,
    )
    assert res.killed == "chaos: simulated wedge verdict"
    assert res.wedged and not res.crashed
    assert res.seconds < 10.0


def test_chaos_checkpoint_torn_falls_back_a_rotation(tmp_path):
    """checkpoint.torn tears the live rotation at byte K after the
    atomic replace; latest_valid_checkpoint skips it (typed) and lands
    on the previous rotation — the designed fallback, now scriptable."""
    from stateright_tpu.checkpoint import (
        CheckpointCorrupt,
        latest_valid_checkpoint,
        load_checkpoint,
    )
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    ck = (
        PackedTwoPhaseSys(3)
        .checker()
        .spawn_xla(frontier_capacity=1 << 9, table_capacity=1 << 12)
    )
    ck.join()
    path = str(tmp_path / "ck.npz")
    ck.save_checkpoint(path, keep=2)
    chaos.install("checkpoint.torn@n=1:at=40")
    ck.save_checkpoint(path, keep=2)  # live file torn, .1 intact
    chaos.install(None)
    with pytest.raises(CheckpointCorrupt):
        load_checkpoint(path)
    assert latest_valid_checkpoint(path) == path + ".1"


def test_chaos_lint_timeout_fails_open(tmp_path):
    """lint.timeout simulates the admission-lint subprocess timing out:
    the job admits fail-open with ok=None and lint_errors counted — the
    blind-gate path, scriptable without a 240s wait."""
    svc = CheckerService(_config(
        tmp_path, max_inflight=0, admission_lint=True,
        chaos="lint.timeout@n=1",
    ))
    try:
        job = svc.submit("2pc:3")
        assert job.lint["ok"] is None
        assert any("TimeoutExpired" in e for e in job.lint["errors"])
        assert svc.gauges()["lint_errors"] == 1
    finally:
        svc.close()


def test_chaos_worker_points_map_to_job_flags(tmp_path):
    """worker.die/worker.freeze fire per SUBMIT (@n counts admissions)
    and land as the matching job-level chaos flags with the exactly-once
    marker armed by default."""
    svc = CheckerService(_config(
        tmp_path, max_inflight=0,
        chaos="worker.die@n=2:depth=5;worker.freeze@n=1:depth=4,once=0",
    ))
    try:
        first = svc.submit("2pc:3")
        second = svc.submit("2pc:3")
        assert first.chaos == {"freeze_at_depth": 4}  # once=0: no marker
        assert second.chaos["die_at_depth"] == 5
        assert second.chaos["marker"].startswith(second.dir)
    finally:
        svc.close()


# --- restart recovery (journal-driven; no workers) --------------------------


def _disarmed(tmp_path, **kw):
    """A service whose scheduler can never start a worker
    (max_inflight=0): admission + journal + recovery accounting only."""
    return CheckerService(_config(tmp_path, max_inflight=0, **kw))


def test_recovery_restores_done_jobs_and_idempotency(tmp_path):
    svc = _disarmed(tmp_path)
    job = svc.submit("2pc:3", idempotency_key="alpha", max_seconds=60.0)
    # Settle it as done the way the service would (under the lock).
    with svc._cond:
        job.status = "done"
        job.completed_unix_ts = time.time()
        job.result = {"generated": 1146, "unique": 288, "max_depth": 11,
                      "seconds": 1.0}
        svc._counters.inc("jobs_done")
        svc._jlog("completed", job=job.id, status="done", error=None,
                  result=job.result)
    svc.close()

    svc2 = _disarmed(tmp_path)
    try:
        rec = svc2.gauges()["journal"]["recovery"]
        assert rec["records_replayed"] >= 3 and rec["torn"] is None
        restored = svc2.job(job.id)
        assert restored.status == "done" and restored.recovered
        assert restored.result["generated"] == 1146
        # Idempotent resubmission after restart: the SAME job comes back,
        # nothing is re-run, the dedupe is counted.
        again = svc2.submit("2pc:3", idempotency_key="alpha")
        assert again is restored
        assert svc2.gauges()["idem_dedups"] == 1
        assert svc2.gauges()["jobs_recovered"] == 1
    finally:
        svc2.close()


def test_qos_scheduler_state_replays(tmp_path):
    """Kill -9 + restart restores the QoS scheduler exactly (ISSUE 18):
    queued jobs keep tenant/priority/deadline, the per-class fair-share
    strides fold from replayed ``started`` events, tenant quotas re-arm
    over the restored queue, and the drain-rate window reseeds from
    journaled completion timestamps so the first post-restart
    Retry-After is measured, not cold."""
    svc = _disarmed(tmp_path, tenant_max_queued=2)
    vip = svc.submit("2pc:3", tenant="t1", priority="interactive",
                     deadline_s=90.0)
    svc.submit("2pc:3", tenant="t1")  # t1's queued quota now full
    done = svc.submit("2pc:3", tenant="t2", priority="best_effort")
    with svc._cond:
        done.status = "running"
        svc._jlog("started", job=done.id, attempt=0, engine="xla",
                  resumed_from=None, pid=None)
        done.status = "done"
        done.completed_unix_ts = time.time()
        done.result = {"generated": 1146, "unique": 288, "max_depth": 11,
                       "seconds": 1.0}
        svc._counters.inc("jobs_done")
        svc._jlog("completed", job=done.id, status="done", error=None,
                  result=done.result)
    svc.close()

    svc2 = _disarmed(tmp_path, tenant_max_queued=2)
    try:
        restored = svc2.job(vip.id)
        assert restored.status == "queued"
        assert restored.priority == "interactive"
        assert restored.tenant == "t1"
        assert restored.deadline_s == 90.0
        # Per-class stride state folded from the replayed `started`.
        assert svc2._qos_served.get("best_effort") == 1
        # The tenant quota re-arms over the RESTORED queue.
        with pytest.raises(AdmissionError) as exc:
            svc2.submit("2pc:3", tenant="t1")
        assert "queued quota reached" in exc.value.reason
        assert svc2.gauges()["quota_rejects"] == 1
        # Drain window reseeded from the journaled completion.
        assert len(svc2._drain) == 1
        assert svc2._drain[0][1] == "best_effort"
    finally:
        svc2.close()


def test_recovery_requeues_inflight_and_charges_budget(tmp_path):
    """An in-flight job requeues on restart with the wall-clock it had
    already spent charged (journal last-ts bounds 'alive until here')."""
    svc = _disarmed(tmp_path)
    job = svc.submit("2pc:3", idempotency_key="b", max_seconds=500.0)
    with svc._cond:
        job.status = "running"
        svc._jlog("started", job=job.id, attempt=0, engine="xla",
                  resumed_from=None, pid=None)
        time.sleep(1.1)
        svc._jlog("breaker_closed")  # any later record advances last_ts
    svc.close()

    svc2 = _disarmed(tmp_path)
    try:
        restored = svc2.job(job.id)
        assert restored.status == "queued"
        assert restored.consumed_s >= 1.0
        rec = svc2.gauges()["journal"]["recovery"]
        assert rec["jobs_requeued"] == 1
    finally:
        svc2.close()


def test_recovery_expired_budget_fails_typed_not_rerun(tmp_path):
    """A job whose budget was already spent when the pool died must fail
    typed at recovery — never burn a fresh budget re-running."""
    svc = _disarmed(tmp_path)
    job = svc.submit("2pc:3", idempotency_key="c", max_seconds=0.5)
    with svc._cond:
        job.status = "running"
        svc._jlog("started", job=job.id, attempt=0, engine="xla",
                  resumed_from=None, pid=None)
        time.sleep(1.1)
        svc._jlog("breaker_closed")
    svc.close()

    svc2 = _disarmed(tmp_path)
    try:
        restored = svc2.job(job.id)
        assert restored.status == "failed"
        assert "budget exhausted" in restored.error
        assert "before the restart" in restored.error
        assert restored.attempts == []  # never re-run
        # The typed failure is itself journaled: a THIRD incarnation
        # restores it terminal without reconsidering.
        svc2.close()
        svc3 = _disarmed(tmp_path)
        assert svc3.job(job.id).status == "failed"
        assert svc3.job(job.id).attempts == []
        svc3.close()
    except BaseException:
        svc2.close()
        raise


def test_recovery_torn_tail_replays_prefix_and_amputates(tmp_path):
    """Service-level torn-tail recovery: truncate the live journal at a
    random byte inside the LAST record; the restart replays everything
    before it, reports the torn tail, and recompacts so the journal is
    clean again."""
    svc = _disarmed(tmp_path)
    svc.submit("2pc:3", idempotency_key="t1", max_seconds=60.0)
    svc.submit("2pc:3", idempotency_key="t2", max_seconds=60.0)
    svc.close()
    jpath = os.path.join(svc._cfg.run_dir, "journal.jsonl")
    data = open(jpath, "rb").read()
    last_line_start = data[:-1].rfind(b"\n") + 1
    cut = random.Random(7).randint(last_line_start + 1, len(data) - 2)
    with open(jpath, "wb") as fh:
        fh.write(data[:cut])

    svc2 = _disarmed(tmp_path)
    try:
        rec = svc2.gauges()["journal"]["recovery"]
        assert rec["torn"] is not None
        # Job t1 replayed fully; t2's admitted event was the torn record
        # or survived — either way the clean prefix restored exactly.
        assert "job-0001" in {j.id for j in svc2.jobs()}
        # Recompaction amputated the torn bytes: the live journal reads
        # clean end to end now.
        assert read_journal(jpath).torn is None
    finally:
        svc2.close()


def test_recovery_restores_open_breaker_and_reprobes_now(tmp_path):
    """A restart must not forget an open breaker — and the restored-open
    breaker re-probes IMMEDIATELY (not an interval later), so the first
    job after a restart never goes straight at a wedged device."""
    import sys

    svc = _disarmed(tmp_path)
    with svc._cond:
        svc._breaker = "open"
        svc._breaker_opened_unix_ts = time.time()
        svc._consecutive_wedges = 3
        svc._jlog("breaker_tripped", consecutive=3)
    svc.close()

    # probe_auto on, instant-success probe, LONG interval: only the
    # immediate restart probe can close it within the poll window.
    svc2 = CheckerService(_config(
        tmp_path, max_inflight=0, probe_auto=True,
        probe_interval_s=3600.0,
        probe_argv=[sys.executable, "-c", "pass"],
    ))
    try:
        deadline = time.monotonic() + 30.0
        while svc2.degraded and time.monotonic() < deadline:
            time.sleep(0.1)
        assert not svc2.degraded
        g = svc2.gauges()
        assert g["breaker_closes"] == 1 and g["device_probes"] == 1
        # The close is journaled: a further restart stays closed.
    finally:
        svc2.close()
    svc3 = _disarmed(tmp_path, probe_auto=False)
    assert svc3.gauges()["breaker"]["state"] == "closed"
    svc3.close()


def test_artifact_sweep_reclaims_complete_jobs(tmp_path):
    """Journal-complete jobs' run-dir artifacts are swept past the
    retention; the pool gauge records it."""
    svc = _disarmed(tmp_path, artifact_retention_s=0.0)
    job = svc.submit("2pc:3", idempotency_key="s1", max_seconds=60.0)
    for name in ("hb.json", "trace.jsonl", "ck.npz", "worker0.out"):
        with open(os.path.join(job.dir, name), "w") as fh:
            fh.write("x")
    with svc._cond:
        job.status = "done"
        job.completed_unix_ts = time.time() - 10.0
        job.result = {"generated": 1, "unique": 1}
        svc._jlog("completed", job=job.id, status="done", error=None,
                  result=job.result)
        svc._sweep_artifacts()
    assert not os.path.isdir(job.dir)
    assert svc.gauges()["artifacts_swept"] == 1
    # Sweeping is idempotent and the journal survives it.
    with svc._cond:
        svc._sweep_artifacts()
    assert svc.gauges()["artifacts_swept"] == 1
    svc.close()
    svc2 = _disarmed(tmp_path)
    assert svc2.job(job.id).status == "done"
    svc2.close()


# --- fleet durability (ISSUE 15): routing journal + restart replay ----------


def test_fleet_replay_folds_routes_and_migrations():
    records = []

    def rec(event, **kw):
        r = {"v": 1, "seq": len(records) + 1, "event": event, **kw}
        records.append(r)
        return r

    rec("routed", ts=1.0, job="fjob-0001", spec="2pc:3", device=0,
        pool_job="job-0001", idempotency_key="k1",
        tenant="t9", priority="interactive", deadline_s=120.0)
    rec("routed", ts=1.5, job="fjob-0002", spec="abd:2", device=1,
        pool_job="job-0001", idempotency_key=None)
    rec("migrated", ts=2.0, job="fjob-0001", from_device=0, to_device=1,
        pool_job="job-0002", reason="device-0 lost")
    rec("quiesced", ts=2.5, device=2, reason="idle")
    rec("quiesced", ts=2.6, device=1, reason="idle")
    rec("woken", ts=3.0, device=1, reason="pressure")
    state = _fleet_replay(records)
    assert state["next_id"] == 2
    assert state["routes"]["fjob-0001"] == {
        "device": 1, "pool_job": "job-0002", "spec": "2pc:3",
        "idempotency_key": "k1", "trace_id": None,
        "tenant": "t9", "priority": "interactive", "deadline_s": 120.0,
        "symmetry": None,
    }
    assert state["routes"]["fjob-0002"]["device"] == 1
    # A pre-QoS record (no tenant/priority) folds to the defaults.
    assert state["routes"]["fjob-0002"]["tenant"] == "default"
    assert state["routes"]["fjob-0002"]["priority"] == "batch"
    assert state["idem"] == {"k1": "fjob-0001"}
    assert state["migrations"] == {"fjob-0001": 1}
    assert state["counters"]["routed"] == 2
    assert state["counters"]["migrations"] == 1
    assert state["order"] == ["fjob-0001", "fjob-0002"]
    # Elastic events fold to the live quiesced set + counters.
    assert state["quiesced"] == {2}
    assert state["counters"]["pools_quiesced"] == 2
    assert state["counters"]["pools_woken"] == 1


def _fleet_disarmed(tmp_path, devices=3):
    return FleetService(FleetConfig(
        run_dir=str(tmp_path / "fleet"),
        devices=devices,
        monitor_interval_s=0.3,
        pool=_config(tmp_path, max_inflight=0),
    ))


def test_fleet_restart_replays_routing(tmp_path):
    """Constructing a fleet over a run dir with journals restores the
    SAME fleet-job -> (device, pool job) placement: every pool replays
    its own journal, then fleet.jsonl re-attaches the routing — and
    idempotent resubmission returns the restored FleetJob."""
    f1 = _fleet_disarmed(tmp_path)
    a = f1.submit("2pc:3", idempotency_key="fa")
    b = f1.submit("2pc:4", idempotency_key="fb")
    c = f1.submit("abd:2", idempotency_key="fc")
    routes1 = {j.id: (j.device, j.pool_job.id) for j in f1.jobs()}
    assert len({d for d, _ in routes1.values()}) == 3  # spread
    f1.close()

    f2 = _fleet_disarmed(tmp_path)
    try:
        routes2 = {j.id: (j.device, j.pool_job.id) for j in f2.jobs()}
        assert routes1 == routes2
        assert all(j.recovered for j in f2.jobs())
        rec = f2.gauges()["journal"]["recovery"]
        assert rec["torn"] is None
        assert rec["routes_recovered"] == 3 and rec["attached"] == 3
        # Pool-side: the jobs requeued through each pool's own journal.
        assert all(j.pool_job.status == "queued" for j in f2.jobs())
        # Fleet-scoped idempotency survives the restart.
        again = f2.submit("2pc:3", idempotency_key="fa")
        assert again is f2.job(a.id)
        assert f2.gauges()["idem_dedups"] == 1
    finally:
        f2.close()


def test_fleet_restart_adopts_pool_jobs_lost_from_torn_fleet_tail(tmp_path):
    """A torn fleet.jsonl tail loses a routing record, but the POOL
    journal still owns the job: the restart adopts it back by
    idempotency key instead of double-running on resubmission."""
    f1 = _fleet_disarmed(tmp_path)
    f1.submit("2pc:3", idempotency_key="ta")
    f1.submit("abd:2", idempotency_key="tb")
    f1.close()
    fpath = os.path.join(str(tmp_path / "fleet"), "fleet.jsonl")
    data = open(fpath, "rb").read()
    # Amputate the LAST routed record entirely (a boundary-cut torn
    # tail: the fleet never journaled tb's route, the pool did).
    cut = data[:-1].rfind(b"\n") + 1
    with open(fpath, "wb") as fh:
        fh.write(data[:cut])

    f2 = _fleet_disarmed(tmp_path)
    try:
        assert f2.gauges()["journal"]["recovery"]["routes_recovered"] >= 1
        # tb was adopted from its pool's journal; resubmitting it dedupes
        # to the adopted job — nothing double-runs.
        jobs_before = len(f2.jobs())
        again = f2.submit("abd:2", idempotency_key="tb")
        assert len(f2.jobs()) == jobs_before
        assert again.pool_job.idempotency_key == "tb"
        assert f2.gauges()["idem_dedups"] == 1
    finally:
        f2.close()


def test_fleet_restart_reroutes_orphans_from_journaled_spec(tmp_path):
    """A restart that cannot re-attach a routed pool job (the pool's
    journal is gone) leaves an ORPHAN — the repair pass re-routes it to
    a healthy sibling from the fleet-journaled spec instead of letting
    waiters poll forever; with no spec either, it fails typed. The spec
    survives _recover's compaction, so even a SECOND crash before the
    repair pass runs stays recoverable."""
    f1 = _fleet_disarmed(tmp_path, devices=2)
    a = f1.submit("2pc:3", idempotency_key="oa")
    victim = a.device
    f1.close()
    os.remove(os.path.join(
        str(tmp_path / "fleet"), f"device-{victim}", "journal.jsonl"
    ))

    def reopen():  # slow monitor: the repair pass is driven by hand
        return FleetService(FleetConfig(
            run_dir=str(tmp_path / "fleet"),
            devices=2,
            monitor_interval_s=60.0,
            pool=_config(tmp_path, max_inflight=0),
        ))

    f2 = reopen()
    try:
        assert f2.job(a.id).pool_job is None
        assert f2.gauges()["journal"]["recovery"]["orphaned"] == 1
    finally:
        # Die again before the repair pass ran (the recovery already
        # compacted fleet.jsonl — the orphan's spec must have survived).
        f2.close()

    f3 = reopen()
    try:
        fjob = f3.job(a.id)
        assert fjob.pool_job is None
        moved = f3._migrate_stragglers()
        assert moved == 1
        assert fjob.pool_job is not None and fjob.pool_job.spec == "2pc:3"
        # The journal-less device is healthy (only its HISTORY died), so
        # any healthy pool — the victim included — is a valid target.
        assert fjob.device is not None
        assert len(fjob.migrations) >= 1
        assert f3.gauges()["migrations"] >= 1
        # The unrecoverable shape (no journaled spec at all) settles
        # typed instead of hanging its waiters.
        fjob._orphan_spec = None
        fjob.pool_job = None
        f3._migrate_stragglers()
        assert fjob.done and "unrecoverable" in fjob.error
        assert fjob.wait(timeout=1.0)
        # An orphan whose journaled spec no longer parses (e.g. a user
        # family not registered in this incarnation) also fails typed —
        # a retry would throw identically, and the ValueError must not
        # kill the monitor sweep and stall every other migration.
        fjob._rejected = None
        fjob._orphan_spec = "not-a-registered-spec"
        f3._migrate_stragglers()
        assert fjob.done and "migration failed" in fjob.error
    finally:
        f3.close()


# --- distributed-trace continuity (docs/observability.md) -------------------


def test_trace_id_minted_journaled_and_restored(tmp_path):
    """Every submission mints a trace id — tracer on or off — and the
    journal carries it ('submitted'/'started'): a restart restores the
    SAME id, so spans from pre- and post-crash attempts stitch into one
    trace; idempotent resubmission keeps it too."""
    svc = _disarmed(tmp_path)
    job = svc.submit("2pc:3", idempotency_key="t1", max_seconds=120.0)
    tid = job.trace_id
    assert tid and len(tid) == 16
    assert job.snapshot()["trace_id"] == tid
    with svc._cond:
        job.status = "running"
        svc._jlog("started", job=job.id, attempt=0, engine="xla",
                  resumed_from=None, pid=None, trace_id=job.trace_id)
    svc.close()

    svc2 = _disarmed(tmp_path)
    try:
        assert svc2.job(job.id).trace_id == tid
        again = svc2.submit("2pc:3", idempotency_key="t1")
        assert again.trace_id == tid
    finally:
        svc2.close()


def test_replay_state_folds_trace_id():
    """'submitted' carries the trace id; a later 'started' (a migration
    resubmit journals it there too) refreshes it; journals from before
    the tracing round replay with trace_id None, not a KeyError."""
    records = []

    def rec(event, **kw):
        r = {"v": 1, "seq": len(records) + 1, "event": event, **kw}
        records.append(r)
        return r

    rec("submitted", ts=1.0, job="job-0001", spec="2pc:3",
        max_seconds=60.0, dir="s/job-0001", trace_id="aa" * 8)
    rec("submitted", ts=1.5, job="job-0002", spec="2pc:3",
        max_seconds=60.0, dir="s/job-0002")  # pre-tracing record shape
    rec("started", ts=2.0, job="job-0002", attempt=0, engine="xla",
        pid=999, trace_id="bb" * 8)
    state = _replay_state(records)
    assert state["jobs"]["job-0001"]["trace_id"] == "aa" * 8
    assert state["jobs"]["job-0002"]["trace_id"] == "bb" * 8


def test_fleet_trace_id_spans_routing_and_restart(tmp_path):
    """The fleet mints the trace id; the routed pool job JOINS it (one
    id across the fleet→pool hop), fleet.jsonl journals it, and a
    full-fleet restart restores it on both tiers."""
    f1 = _fleet_disarmed(tmp_path)
    a = f1.submit("2pc:3", idempotency_key="ft")
    tid = a.trace_id
    assert tid and a.pool_job.trace_id == tid
    assert a.snapshot()["trace_id"] == tid
    f1.close()

    f2 = _fleet_disarmed(tmp_path)
    try:
        fjob = f2.job(a.id)
        assert fjob.trace_id == tid
        assert fjob.pool_job.trace_id == tid
    finally:
        f2.close()


def test_fleet_migration_keeps_trace_id(tmp_path):
    """A migrated job's new attempt on the sibling device continues the
    ORIGINAL trace: the straggler repair resubmits with the journaled
    trace id, so the post-migration spans stitch to the pre-loss ones."""

    def reopen(interval):
        return FleetService(FleetConfig(
            run_dir=str(tmp_path / "fleet"),
            devices=2,
            monitor_interval_s=interval,
            pool=_config(tmp_path, max_inflight=0),
        ))

    f1 = reopen(60.0)
    a = f1.submit("2pc:3", idempotency_key="mt")
    tid = a.trace_id
    victim = a.device
    f1.close()
    os.remove(os.path.join(
        str(tmp_path / "fleet"), f"device-{victim}", "journal.jsonl"
    ))

    f2 = reopen(60.0)
    try:
        fjob = f2.job(a.id)
        assert fjob.trace_id == tid  # restored from fleet.jsonl's route
        assert f2._migrate_stragglers() == 1
        assert fjob.pool_job is not None
        assert fjob.pool_job.trace_id == tid
    finally:
        f2.close()


def test_fleet_pools_export_chaos_to_workers(tmp_path):
    """FleetConfig(chaos=) reaches worker processes like a single pool's
    does: the spec forwards into every pool config (the _worker_env
    STPU_CHAOS export keys on it) without resetting the fleet's
    installed plan."""
    import types

    spec = "seed=5;checkpoint.torn@n=1"
    fleet = FleetService(FleetConfig(
        run_dir=str(tmp_path / "fleet"),
        devices=2,
        pool=_config(tmp_path, max_inflight=0),
        chaos=spec,
    ))
    try:
        live = chaos.plan()
        assert live is not None and live.spec == spec
        assert all(p._cfg.chaos == spec for p in fleet.pools)
        env = fleet.pools[0]._worker_env(
            types.SimpleNamespace(trace_path="unused", symmetry=None),
            device=False,
        )
        assert env["STPU_CHAOS"] == spec
    finally:
        fleet.close()


@pytest.mark.slow
def test_fleet_sigkill_restart_replay_converges(chaos_reference):
    """ISSUE 15 acceptance: SIGKILL the WHOLE 3-device fleet at a seeded
    point mid-schedule, restart over the same run dir — the fleet journal
    replays routing, each pool replays its jobs, and every job completes
    exactly once with counts bit-identical to the undisturbed baseline."""
    sc, base, schedule, ref = chaos_reference
    rep = sc.run_scenario(
        "kill", 42, schedule, os.path.join(base, "fleet3"),
        reference=ref, max_inflight=2, fleet=3,
    )
    assert rep["ok"], rep["problems"]
    assert rep["restarts"] >= 1 or rep["faults"]["kill_after_s"] > rep["elapsed_s"]
    assert rep["fleet"]["devices"] == 3
    assert rep["turnaround_s"]["n"] == 3


@pytest.mark.slow
def test_fleet_device_lost_mid_schedule_converges(chaos_reference):
    """ISSUE 15 acceptance: a seeded device.lost kills one device's pool
    mid-schedule; its jobs migrate and the fleet converges exactly-once,
    bit-identical — with the migration PROVEN in the SLO line."""
    sc, base, schedule, ref = chaos_reference
    rep = sc.run_scenario(
        "device_lost", 42, schedule, os.path.join(base, "fleet_lost"),
        reference=ref, max_inflight=2, fleet=2,
    )
    assert rep["ok"], rep["problems"]
    assert rep["fleet"]["migrations"] >= 1


# --- restart drills (the real service, killed for real) ---------------------


def _drill_schedule(idem, specs=("2pc:3",)):
    return {
        "jobs": [
            {"idem": f"{idem}-{i}", "spec": spec, "delay_s": 0.2 * i,
             "max_seconds": 240.0}
            for i, spec in enumerate(specs)
        ]
    }


def test_smoke_service_restart_resume(tmp_path):
    """The <30s tier-0 restart drill (tools/smoke.sh): the service
    SIGKILLs itself right after journaling `started` (deterministic:
    journal.die@n=3), the restart replays the journal, kills the
    orphaned worker, requeues, and the job completes exactly once with
    exact pinned counts."""
    sc = _harness()
    run_dir = str(tmp_path / "drill")
    os.makedirs(run_dir)
    schedule = _drill_schedule("drill")
    sp = os.path.join(run_dir, "schedule.json")
    with open(sp, "w") as fh:
        json.dump(schedule, fh)
    rc = sc.run_incarnation(
        run_dir, sp, chaos="seed=1;journal.die@n=3", wait_s=120.0
    )
    assert rc == -9  # died by its own injected SIGKILL
    rc = sc.run_incarnation(run_dir, sp, wait_s=120.0)
    assert rc == 0
    inv = sc.check_invariant(run_dir, schedule, None)
    assert inv["ok"], inv["problems"]
    with open(os.path.join(run_dir, "driver_results.json")) as fh:
        results = json.load(fh)["jobs"]
    got = results["drill-0"]
    assert got["status"] == "done"
    assert (got["result"]["generated"], got["result"]["unique"]) == PINNED_2PC3
    slo = sc.slo_stats(run_dir)
    assert slo["journal"]["records_replayed"] == 3
    assert slo["journal"]["jobs_requeued"] == 1
    # The orphaned first worker was killed by journaled pid before the
    # job was rescheduled (exactly-once depends on it).
    assert slo["journal"]["orphans_killed"] in (0, 1)


@pytest.fixture(scope="module")
def chaos_reference(tmp_path_factory):
    """One undisturbed baseline run of the seeded 3-job schedule — the
    ground truth both convergence pins compare against bit-for-bit."""
    sc = _harness()
    base = str(tmp_path_factory.mktemp("chaos"))
    schedule = sc.build_schedule(42, 3, 240.0)
    rep = sc.run_scenario("baseline", 42, schedule, base, reference=None)
    assert rep["ok"], rep["problems"]
    ref = sc.reference_counts(os.path.join(base, "baseline"), schedule)
    return sc, base, schedule, ref


@pytest.mark.slow
def test_chaos_pin_service_sigkill_converges(chaos_reference):
    """ISSUE 12 acceptance: SIGKILL the CheckerService process at a
    seeded random point of a 3-concurrent-job schedule, restart from the
    same run dir — every job completes exactly once, counts bit-identical
    to the undisturbed run."""
    sc, base, schedule, ref = chaos_reference
    rep = sc.run_scenario(
        "kill", 42, schedule, base, reference=ref, max_inflight=2
    )
    assert rep["ok"], rep["problems"]
    assert rep["turnaround_s"]["n"] == 3


@pytest.mark.slow
def test_chaos_pin_torn_journal_converges(chaos_reference):
    """Same schedule with journal-append torn-tail injection: the crash
    lands MID-append, the restart recovers the typed torn tail and still
    converges exactly-once, bit-identical."""
    sc, base, schedule, ref = chaos_reference
    rep = sc.run_scenario(
        "torn", 42, schedule, base, reference=ref, max_inflight=2
    )
    assert rep["ok"], rep["problems"]
    assert rep["journal"]["torn"] is not None  # the tear really landed
