"""CheckerService chaos pins (ISSUE 9 acceptance).

The multi-tenant pool must keep faults per-job and degrade instead of
dying:

- **Admission control**: beyond the queue/session caps, ``submit`` raises
  the typed ``AdmissionError`` with a ``retry_after_s`` back-pressure hint
  — never unbounded queueing; over-cap budgets are rejected without one.
- **Kill-resume smoke** (<30s, rides in ``tools/smoke.sh``): a job
  SIGKILLed mid-superstep requeues, resumes from its own auto-checkpoint
  rotation, and converges to the exact pinned counts; its span trace
  exports as a Chrome trace.
- **Isolation pin**: with two CONCURRENT jobs, SIGSTOP-wedging one (the
  hung-dispatch signature: heartbeat frozen mid-"dispatch") draws a wedge
  verdict that kills and quarantines only that job's process group; the
  sibling's generated/unique/discovery counts are bit-identical to its
  solo run, and the victim resumes from checkpoint to exact counts.
- **Breaker pin**: K consecutive device wedge verdicts trip the breaker;
  new jobs are served by the host on-demand engine with ``degraded: true``
  and exact counts; a healthy device probe closes the breaker; the pool
  gauges record the trip and the recovery.

Supervision is the real library (``supervise.run_worker`` under
``stateright_tpu/service/core.py``); the worker body is the real service
worker (``stateright_tpu/service/worker.py``), CPU-pinned via the
service's ``platform="cpu"`` knob.
"""

import json
import os
import threading
import time

import pytest

from stateright_tpu.service import (
    AdmissionError,
    CheckerService,
    FleetConfig,
    FleetService,
    ServiceConfig,
)

#: Pinned full-coverage (generated, unique) counts (bench.py EXPECTED_*).
PINNED = {
    "2pc:3": (1_146, 288),
    "2pc:4": (8_258, 1_568),
    "scr:3,1": (6_778, 4_243),
}


@pytest.fixture(autouse=True)
def _no_ambient_chaos(monkeypatch):
    """Each test starts (and ends) with no installed chaos plan — the
    fleet failover smoke installs one process-wide."""
    from stateright_tpu import chaos as chaos_mod

    monkeypatch.delenv("STPU_CHAOS", raising=False)
    chaos_mod.install(None)
    yield
    chaos_mod.install(None)


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
        # The admission flight-check is pinned by its own tests below;
        # the chaos/breaker pins disable it so each test pays for
        # exactly the machinery it pins (a cold lint subprocess costs
        # ~20 s of jax import + traces on this 1-core box).
        admission_lint=False,
    )
    base.update(kw)
    return ServiceConfig(**base)


_SOLO_CACHE = {}


def _solo(spec):
    """Uninterrupted in-process run of the same model at the worker's
    engine settings — the ground truth a service job (and the isolation
    pin's sibling) must reproduce bit-for-bit."""
    if spec not in _SOLO_CACHE:
        from stateright_tpu.service.registry import resolve

        model, caps = resolve(spec)
        c = model.checker().spawn_xla(**caps).join()
        _SOLO_CACHE[spec] = {
            "generated": c.state_count(),
            "unique": c.unique_state_count(),
            "max_depth": c.max_depth(),
            "discoveries": {
                name: [repr(a) for a in path.into_actions()]
                for name, path in sorted(c.discoveries().items())
            },
        }
    return _SOLO_CACHE[spec]


def _assert_exact(result, spec):
    ref = _solo(spec)
    assert (result["generated"], result["unique"]) == PINNED[spec]
    assert result["generated"] == ref["generated"]
    assert result["unique"] == ref["unique"]
    assert result["max_depth"] == ref["max_depth"]
    assert result["discoveries"] == ref["discoveries"]


# --- admission control -----------------------------------------------------


def test_admission_rejection_at_caps(tmp_path):
    svc = CheckerService(_config(tmp_path, max_inflight=1, max_queue=2))
    # Admission accounting without workers: scheduling disarmed, so
    # submitted jobs stay queued.
    svc._ensure_scheduler = lambda: None
    try:
        with pytest.raises(ValueError, match="unknown model spec"):
            svc.submit("nosuchmodel:9")
        # An over-cap budget is rejected typed, with NO retry hint —
        # retrying the same request cannot help.
        with pytest.raises(AdmissionError) as exc:
            svc.submit("2pc:3", max_seconds=10_000_000.0)
        assert exc.value.retry_after_s is None
        svc.submit("2pc:3")
        svc.submit("2pc:3")
        # Queue full: typed rejection carrying Retry-After, not unbounded
        # queueing.
        with pytest.raises(AdmissionError) as exc:
            svc.submit("2pc:3")
        assert exc.value.retry_after_s is not None
        assert exc.value.retry_after_s > 0
        assert "queue full" in exc.value.reason
        g = svc.gauges()
        assert g["queued"] == 2
        assert g["rejected"] == 2
        assert g["admitted"] == 2
    finally:
        svc.close()


# --- QoS: priority classes, fair share, overload shedding (ISSUE 18) --------


def test_smoke_qos_shed(tmp_path):
    """The tier-0 QoS drill (<30s, tools/smoke.sh): overload sheds the
    lowest class FIRST — typed, class-naming, hint-carrying — while
    higher classes keep admitting up to their own thresholds, and the
    ``qos`` gauge rollup tracks the class/tenant occupancy."""
    svc = CheckerService(_config(tmp_path, max_inflight=1, max_queue=8))
    svc._ensure_scheduler = lambda: None  # admission accounting only
    try:
        with pytest.raises(ValueError, match="priority"):
            svc.submit("2pc:3", priority="platinum")
        with pytest.raises(ValueError, match="deadline_s"):
            svc.submit("2pc:3", deadline_s=-5)
        for _ in range(4):
            svc.submit("2pc:3", tenant="t-batch")  # occupancy 4 = 50 %
        # best_effort sheds at half-full; batch and interactive do not.
        with pytest.raises(AdmissionError) as exc:
            svc.submit("2pc:3", priority="best_effort")
        assert "overloaded: shedding best_effort" in exc.value.reason
        assert exc.value.retry_after_s is not None
        svc.submit("2pc:3")
        svc.submit("2pc:3")  # occupancy 6 = 75 %
        with pytest.raises(AdmissionError, match="shedding batch"):
            svc.submit("2pc:3")
        vip = svc.submit("2pc:3", priority="interactive", deadline_s=60)
        svc.submit("2pc:3", priority="interactive")  # occupancy 8 = cap
        # At the hard cap even interactive rejects — as queue-full, not
        # a shed (there is no lower class left to degrade to).
        with pytest.raises(AdmissionError, match="queue full"):
            svc.submit("2pc:3", priority="interactive")
        g = svc.gauges()
        assert g["sheds"] == 2
        qos = g["qos"]
        assert qos["classes"]["batch"]["queued"] == 6
        assert qos["classes"]["interactive"]["queued"] == 2
        assert qos["classes"]["best_effort"]["queued"] == 0
        assert qos["classes"]["interactive"]["weight"] == 4.0
        assert qos["tenants"]["t-batch"]["queued"] == 4
        assert qos["aging_s"] == svc._cfg.qos_aging_s
        snap = vip.snapshot()
        assert snap["priority"] == "interactive"
        assert snap["tenant"] == "default"
        assert snap["deadline_s"] == 60
    finally:
        svc.close()


def test_starvation_freedom(tmp_path):
    """The no-starvation guarantee: under a sustained higher-class
    backlog, stride fair share already serves best_effort at w/Σw —
    and any job older than ``qos_aging_s * (w_max + 1 - w_class)``
    jumps the rotation entirely (``aged_picks``), so no admitted job
    waits beyond the documented bound."""
    svc = CheckerService(_config(
        tmp_path, max_inflight=0, max_queue=64,
        shed_thresholds={
            "interactive": 1.0, "batch": 1.0, "best_effort": 1.0,
        },
    ))
    svc._ensure_scheduler = lambda: None
    try:
        straggler = svc.submit("2pc:3", priority="best_effort")
        hi = [
            svc.submit("2pc:3", priority="interactive") for _ in range(8)
        ]
        # Fresh jobs: the deterministic stride order gives interactive
        # exactly its 4:1 weighted share of the first 5 slots.
        with svc._lock:
            order = [
                j.priority for j in svc._qos_pick([straggler] + hi, 5)
            ]
        assert order.count("interactive") == 4
        assert order.count("best_effort") == 1
        assert svc.gauges()["aged_picks"] == 0

        # A best_effort job past the aged bound preempts EVERY fresh
        # higher-class sibling — the starvation backstop.
        aged_job = svc.submit("2pc:3", priority="best_effort")
        bound = svc._cfg.qos_aging_s * (svc._w_max + 1.0 - 1.0)
        with svc._lock:
            assert not svc._aged(aged_job, time.time())
            aged_job.created_unix_ts -= bound + 1.0
            picks = svc._qos_pick([aged_job] + hi, 1)
        assert picks == [aged_job]
        assert svc.gauges()["aged_picks"] == 1
    finally:
        svc.close()


def test_qos_edf_and_tenant_inflight_quota(tmp_path):
    """Within a class the pick is earliest-deadline-first (deadline-less
    jobs last); a tenant at its in-flight quota is skipped — not
    starved — and the slot goes to another tenant's job."""
    svc = CheckerService(_config(
        tmp_path, max_inflight=0, max_queue=64,
        tenant_quotas={"capped": {"max_inflight": 1}},
    ))
    svc._ensure_scheduler = lambda: None
    try:
        loose = svc.submit("2pc:3", priority="interactive")
        tight = svc.submit(
            "2pc:3", priority="interactive", deadline_s=30.0
        )
        with svc._lock:
            picks = svc._qos_pick([loose, tight], 1)
        assert picks == [tight]  # later submit, earlier deadline

        a = svc.submit("2pc:3", tenant="capped")
        b = svc.submit("2pc:3", tenant="capped")
        other = svc.submit("2pc:3", tenant="free")
        with svc._cond:
            a.status = "running"  # capped is at max_inflight=1
            picks = svc._qos_pick([b, other], 2)
        # b skipped (quota), other picked; b stays eligible next round.
        assert picks == [other]
        with svc._cond:
            a.status = "done"
            picks = svc._qos_pick([b], 1)
        assert picks == [b]
    finally:
        svc.close()


def test_tenant_quotas_reject_typed(tmp_path):
    """Per-tenant admission quotas: queued quota rejects with a drain
    hint (the tenant's own jobs clearing makes room), a device-seconds
    budget quota rejects with none (retrying cannot help) — both
    counted as ``quota_rejects``."""
    svc = CheckerService(_config(
        tmp_path, max_inflight=0, max_queue=64,
        tenant_max_queued=2,
        tenant_quotas={"broke": {"budget_s": 50.0}},
    ))
    svc._ensure_scheduler = lambda: None
    try:
        svc.submit("2pc:3", tenant="t1")
        svc.submit("2pc:3", tenant="t1")
        with pytest.raises(AdmissionError) as exc:
            svc.submit("2pc:3", tenant="t1")
        assert "queued quota reached" in exc.value.reason
        assert exc.value.retry_after_s is not None
        # Another tenant is untouched by t1's quota.
        svc.submit("2pc:3", tenant="t2")
        with pytest.raises(AdmissionError) as exc:
            svc.submit("2pc:3", tenant="broke", max_seconds=60.0)
        assert "budget exceeded" in exc.value.reason
        assert exc.value.retry_after_s is None
        assert svc.gauges()["quota_rejects"] == 2
    finally:
        svc.close()


def test_retry_after_uses_measured_drain_rate(tmp_path):
    """The Retry-After hint is measured, not guessed: with two or more
    completions in the drain window the hint is jobs-ahead over the
    observed completion rate (per-class when the class has its own
    settlements, pool-wide otherwise); below two it falls back to the
    conservative slot estimate."""
    import time as _time

    svc = CheckerService(_config(tmp_path, max_inflight=1, max_queue=64))
    svc._ensure_scheduler = lambda: None
    try:
        for _ in range(3):
            svc.submit("2pc:3")  # 3 batch jobs ahead
        now = _time.time()
        with svc._lock:
            cold = svc._retry_after(svc._counts(), "batch")
            # Cold pool: the static fallback (3 ahead / 1 slot * half
            # the default budget), not a measured rate.
            assert cold == 3 * svc._cfg.default_max_seconds * 0.5
            svc._drain.append((now - 8.0, "batch"))
            svc._drain.append((now - 4.0, "batch"))
            warm = svc._retry_after(svc._counts(), "batch")
        # Measured: (3 ahead + 1) / (2 completions / ~8 s) ≈ 16 s.
        assert 14.0 <= warm <= 18.0
        with svc._lock:
            # best_effort has no settlements of its own: the pool-wide
            # rate serves, with ALL 3 batch jobs counted ahead of it.
            be = svc._retry_after(svc._counts(), "best_effort")
        assert 14.0 <= be <= 18.0
    finally:
        svc.close()


def test_mux_partition_respects_class(tmp_path):
    """Mux groups form WITHIN a priority class ((spec, priority) key):
    a best_effort lane never rides — and budget-clips — an interactive
    batch."""
    svc = CheckerService(_config(
        tmp_path, max_inflight=0, max_queue=64, mux_k=4,
    ))
    svc._ensure_scheduler = lambda: None
    try:
        jobs = [
            svc.submit("2pc:3", priority="interactive") for _ in range(3)
        ] + [
            svc.submit("2pc:3", priority="best_effort") for _ in range(3)
        ]
        with svc._lock:
            groups = svc._mux_partition(list(jobs))
        assert sorted(len(g) for g in groups) == [3, 3]
        for group in groups:
            assert len({j.priority for j in group}) == 1
    finally:
        svc.close()


# --- admission flight-check (stpu-lint --admission at submit) ---------------

_EVIL_FAMILY = '''
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys


class EvilTwoPhase(PackedTwoPhaseSys):
    """The round-3/5 paxos-drift shape, resubmitted as a user model: a
    traced-index .at[] write in the transition kernel (STPU001)."""

    def packed_step(self, words):
        import jax.numpy as jnp

        nxt, valid = super().packed_step(words)
        i = words[0] & jnp.uint32(1)
        nxt = nxt.at[0, i].set(nxt[0, 0])
        return nxt, valid


def evil(args):
    rm = args[0] if args else 3
    return EvilTwoPhase(rm), dict(
        frontier_capacity=1 << 10, table_capacity=1 << 13
    )
'''


def test_admission_lint_rejects_unwaived_finding(tmp_path, monkeypatch):
    """The gate user-submitted specs (STPU_FAMILIES) pass through: a
    model whose kernel carries a pinned-fatal shape is rejected at
    submit with a typed AdmissionError naming the rule — before the
    pool ever schedules it on the device — while a shipped spec admits
    with its verdict recorded in the job snapshot (and so /.pool)."""
    (tmp_path / "evil_family_mod.py").write_text(_EVIL_FAMILY)
    # In-process (registry.parse at submit) and subprocess (the lint and
    # any worker) both resolve the family: sys.path for the former,
    # PYTHONPATH for the latter.
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setenv("PYTHONPATH", str(tmp_path))
    monkeypatch.setenv("STPU_FAMILIES", "evil=evil_family_mod:evil")
    svc = CheckerService(_config(tmp_path, admission_lint=True))
    svc._ensure_scheduler = lambda: None  # admission accounting only
    try:
        with pytest.raises(AdmissionError) as exc:
            svc.submit("evil:3")
        assert "STPU001" in str(exc.value)
        assert exc.value.retry_after_s is None  # retrying cannot help
        assert "flight-check" in exc.value.reason

        # User-family verdicts are NEVER memoized (their source lives
        # outside the tree hash): a user who FIXES the model and
        # resubmits to the same pool gets a fresh verdict and admits.
        (tmp_path / "evil_family_mod.py").write_text(
            _EVIL_FAMILY.replace(
                "nxt = nxt.at[0, i].set(nxt[0, 0])\n        ", ""
            )
        )
        fixed = svc.submit("evil:3")
        assert fixed.lint["ok"] is True and fixed.lint["cached"] is False

        # A user family whose module cannot even LOAD is a spec defect,
        # not a tooling failure: rejected (never fail-open admitted).
        monkeypatch.setenv(
            "STPU_FAMILIES",
            "evil=evil_family_mod:evil,ghost=no_such_module_xyz:f",
        )
        with pytest.raises(AdmissionError, match="flight-check"):
            svc.submit("ghost:1")

        job = svc.submit("2pc:3")  # a shipped spec admits
        assert job.lint is not None and job.lint["ok"] is True
        assert job.snapshot()["lint"]["ok"] is True
        # The per-service memo: resubmission pays no second subprocess.
        assert svc.submit("2pc:3").lint["cached"] is True

        g = svc.gauges()
        # evil (rejected) + evil (fixed, unmemoized rerun) + ghost
        # (rejected) + 2pc:3; the second 2pc:3 submit hit the memo.
        assert g["lint_checks"] == 4
        assert g["lint_rejects"] == 2
        assert g["lint_errors"] == 0
        assert g["rejected"] == 2 and g["admitted"] == 3
    finally:
        svc.close()


def test_admission_lint_fails_open_on_tooling_error(tmp_path, monkeypatch):
    """A broken lint TOOL (not a finding) must not take the pool down:
    the job admits with ok=None recorded and lint_errors counted — an
    operator sees a blind gate, tenants keep their fault isolation."""
    from stateright_tpu.service import core as svc_core

    monkeypatch.setattr(svc_core, "_LINT", "/nonexistent/stpu_lint.py")
    svc = CheckerService(_config(tmp_path, admission_lint=True))
    svc._ensure_scheduler = lambda: None
    try:
        job = svc.submit("2pc:3")
        assert job.lint["ok"] is None
        assert job.lint["errors"]
        assert svc.gauges()["lint_errors"] == 1
    finally:
        svc.close()


# --- kill-resume smoke (tools/smoke.sh; <30s) ------------------------------


def test_smoke_service_kill_resume(tmp_path):
    """The tier-0 service crash drill: one SIGKILL mid-superstep, one
    supervised requeue resuming from the job's own checkpoint rotation,
    exact pinned counts, downloadable Chrome trace."""
    svc = CheckerService(_config(tmp_path))
    try:
        job = svc.submit(
            "2pc:3",
            chaos={"die_at_depth": 3, "marker": str(tmp_path / "m1")},
        )
        assert job.wait(timeout=240), job.snapshot()
        assert job.status == "done", job.error
        # First attempt died by SIGKILL (a crash, not a wedge — no breaker
        # evidence); the requeued attempt resumed from the checkpoint.
        assert job.attempts[0]["rc"] == -9
        assert not job.attempts[0]["wedged"]
        assert job.requeues == 1
        assert job.resumed_from is not None
        assert job.result["resumed_from"] == job.resumed_from
        _assert_exact(job.result, "2pc:3")
        assert job.result["metrics"]["checkpoints_written"] >= 1
        # Per-job span trace downloads as Perfetto-loadable Chrome JSON.
        chrome = svc.job_trace_chrome(job.id)
        assert chrome is not None
        with open(chrome) as fh:
            events = json.load(fh)["traceEvents"]
        assert any(e["name"] == "dispatch" for e in events)
        g = svc.gauges()
        assert g["jobs_done"] == 1 and g["crashes"] == 1
        assert g["breaker"]["state"] == "closed"
    finally:
        svc.close()


# --- isolation pin: two concurrent jobs, one SIGSTOP-wedged ----------------


def test_sigstop_isolation_sibling_exact(tmp_path):
    """SIGSTOP freezes the victim's heartbeat mid-"dispatch" (the hung
    dispatch signature). The service must kill+quarantine ONLY the victim's
    process group and resume it from checkpoint, while the concurrently
    running sibling converges bit-identically to its solo run."""
    svc = CheckerService(_config(tmp_path, max_inflight=2))
    try:
        victim = svc.submit(
            "2pc:4",
            chaos={"freeze_at_depth": 4, "marker": str(tmp_path / "m2")},
        )
        sibling = svc.submit("scr:3,1")
        assert svc.wait_all(timeout=800), svc.metrics()

        # Sibling: untouched by the sibling-job wedge — counts, depth, and
        # discovery paths bit-identical to a solo run.
        assert sibling.status == "done", sibling.error
        assert sibling.wedges == 0 and sibling.requeues == 0
        assert len(sibling.attempts) == 1
        _assert_exact(sibling.result, "scr:3,1")

        # Victim: wedge verdict -> quarantine -> checkpoint resume ->
        # exact counts.
        assert victim.status == "done", victim.error
        assert victim.wedges == 1
        assert victim.attempts[0]["wedged"]
        assert "stale" in victim.attempts[0]["killed"]
        assert victim.resumed_from is not None
        assert victim.result["start_depth"] >= 4  # resumed AT the wedge
        _assert_exact(victim.result, "2pc:4")

        g = svc.gauges()
        assert g["wedge_verdicts"] == 1 and g["requeues"] >= 1
        # One wedge < K: no trip, the pool never degraded.
        assert g["breaker"]["state"] == "closed"
        assert g["breaker_trips"] == 0
    finally:
        svc.close()


# --- breaker: trip -> host fallback -> probe recovery ----------------------


def test_breaker_trip_host_fallback_and_recovery(tmp_path):
    import sys

    svc = CheckerService(
        _config(
            tmp_path,
            stall_s=6.0,
            requeue_limit=1,
            breaker_k=2,
            probe_argv=[sys.executable, "-c", "pass"],
        )
    )
    try:
        # No chaos marker: the sabotage trips on EVERY attempt — the
        # repeatedly-wedging-device shape. 2 attempts = 2 consecutive
        # wedge verdicts = K.
        wedger = svc.submit("2pc:3", chaos={"freeze_at_depth": 2})
        assert wedger.wait(timeout=400), wedger.snapshot()
        assert wedger.status == "failed"
        assert wedger.wedges == 2
        g = svc.gauges()
        assert g["breaker"]["state"] == "open"
        assert g["breaker"]["opened_unix_ts"] is not None
        assert g["breaker_trips"] == 1
        assert g["wedge_verdicts"] == 2
        assert svc.degraded

        # New jobs are served on the host on-demand engine: degraded,
        # exact counts — the pool degrades instead of dying.
        fallback = svc.submit("2pc:3")
        assert fallback.wait(timeout=300), fallback.snapshot()
        assert fallback.status == "done", fallback.error
        assert fallback.engine == "host"
        assert fallback.degraded
        assert fallback.snapshot()["degraded"] is True
        assert fallback.result["degraded"] is True
        assert (
            fallback.result["generated"], fallback.result["unique"]
        ) == PINNED["2pc:3"]
        # Host jobs never touch the device, hence no heartbeat supervision and no
        # device span trace to download.
        assert svc.job_trace_chrome(fallback.id) is None

        # A healthy device probe closes the breaker; the recovery is in
        # the gauges.
        assert svc.probe_device_now()
        g = svc.gauges()
        assert g["breaker"]["state"] == "closed"
        assert g["breaker"]["opened_unix_ts"] is None
        assert g["breaker_closes"] == 1
        assert g["degraded_jobs"] == 1
        assert not svc.degraded
    finally:
        svc.close()


# --- fleet: multi-device pools, failover migration (ISSUE 15) --------------


def _fleet(tmp_path, devices=2, pool_kw=None, **kw):
    pool = _config(tmp_path)  # run_dir is overwritten per device
    if pool_kw:
        for k, v in pool_kw.items():
            setattr(pool, k, v)
    base = dict(
        run_dir=str(tmp_path / "fleet"),
        devices=devices,
        monitor_interval_s=0.3,
        pool=pool,
    )
    base.update(kw)
    return FleetService(FleetConfig(**base))


def test_smoke_fleet_failover(tmp_path):
    """The <30s fleet tier-0 drill (tools/smoke.sh; ISSUE 15 acceptance):
    a 2-device fleet, `device.lost@n=1` kills the first routed job's
    device mid-job — the victim migrates to the surviving device and
    completes with counts bit-identical to an undisturbed run, while the
    sibling job (on the survivor) never notices."""
    fleet = _fleet(
        tmp_path, devices=2,
        chaos="seed=1;device.lost@n=1:after_s=2",
    )
    try:
        victim = fleet.submit("2pc:3")
        sibling = fleet.submit("2pc:3")
        first_device = victim.device
        assert {victim.device, sibling.device} == {0, 1}  # least-loaded spread
        assert fleet.wait_all(timeout=240), fleet.metrics()

        assert victim.status == "done", (victim.status, victim.error)
        assert len(victim.migrations) == 1
        assert victim.device != first_device  # finished on the survivor
        _assert_exact(victim.result, "2pc:3")

        assert sibling.status == "done", (sibling.status, sibling.error)
        assert sibling.migrations == []
        _assert_exact(sibling.result, "2pc:3")

        g = fleet.gauges()
        assert g["migrations"] == 1
        assert g["devices_lost"] == 1
        assert g["lost_devices"] == [first_device]
        assert g["jobs_evacuated"] == 1
        # The lost device's pool journaled the evacuation (terminal for
        # that pool — a restart would never requeue the job there).
        assert g["devices"][f"device-{first_device}"]["lost"] is True
        # Both fleet jobs' snapshots carry their device.
        snap = fleet.metrics()["jobs"][victim.id]
        assert snap["device"] == f"device-{victim.device}"
        assert snap["migrations"] == 1
    finally:
        fleet.close()


def test_fleet_host_last_resort_only_when_all_open(tmp_path):
    """ISSUE 15 acceptance pin: host-engine degradation happens ONLY when
    every device breaker is open/lost — one healthy sibling means device
    routing, never the host fallback. Routing-only (disarmed pools)."""
    fleet = _fleet(tmp_path, devices=2, pool_kw={"max_inflight": 0})
    try:
        # Device 0's breaker open: routing must pick the healthy sibling
        # on the DEVICE engine — not degrade.
        with fleet.pools[0]._cond:
            fleet.pools[0]._breaker = "open"
        job = fleet.submit("2pc:3")
        assert job.device == 1
        assert job.pool_job.engine_force is None
        assert not fleet.degraded
        assert fleet.gauges()["host_last_resort"] == 0

        # Every breaker open: now — and only now — the host last resort.
        with fleet.pools[1]._cond:
            fleet.pools[1]._breaker = "open"
        assert fleet.degraded
        last = fleet.submit("2pc:3")
        assert last.pool_job.engine_force == "host"
        assert fleet.gauges()["host_last_resort"] == 1
        assert fleet.gauges()["breaker"]["state"] == "open"

        # A closed breaker restores device routing immediately.
        with fleet.pools[0]._cond:
            fleet.pools[0]._breaker = "closed"
        healthy_again = fleet.submit("2pc:3")
        assert healthy_again.device == 0
        assert healthy_again.pool_job.engine_force is None
    finally:
        fleet.close()


def test_fleet_idempotency_and_admission(tmp_path):
    fleet = _fleet(tmp_path, devices=2,
                   pool_kw={"max_inflight": 0, "max_queue": 1})
    try:
        a = fleet.submit("2pc:3", idempotency_key="k1")
        assert fleet.submit("2pc:3", idempotency_key="k1") is a
        assert fleet.gauges()["idem_dedups"] == 1
        # Capacity = 1 queued per device; past both, the typed rejection
        # carries the minimum Retry-After across devices.
        fleet.submit("2pc:3")
        with pytest.raises(AdmissionError) as exc:
            fleet.submit("2pc:3")
        assert exc.value.retry_after_s is not None
        # Over-cap budgets reject identically on every device: no retry
        # hint, and the fleet does not waste submissions on siblings.
        with pytest.raises(AdmissionError) as exc:
            fleet.submit("2pc:3", max_seconds=10_000_000.0)
        assert exc.value.retry_after_s is None
    finally:
        fleet.close()


def test_fleet_concurrent_same_key_submits_dedupe(tmp_path):
    """The fleet-scoped idempotency reservation: concurrent same-key
    submits dedupe to ONE FleetJob (the key reserves under the lock
    BEFORE routing, so the race cannot place the same work on two
    devices) — and a fleet-wide rejection unwinds the reservation so
    the key can be retried."""
    fleet = _fleet(tmp_path, devices=2, pool_kw={"max_inflight": 0})
    try:
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(
                    fleet.submit("2pc:3", idempotency_key="kc")
                )
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 4
        assert len({id(r) for r in results}) == 1
        assert sum(
            1 for j in fleet.jobs() if j.idempotency_key == "kc"
        ) == 1
        assert fleet.gauges()["idem_dedups"] == 3
        # Rejection unwind: an over-budget submit fails on every device,
        # the reservation is removed, and the key stays retryable.
        with pytest.raises(AdmissionError):
            fleet.submit("2pc:3", idempotency_key="kr",
                         max_seconds=10_000_000.0)
        assert all(j.idempotency_key != "kr" for j in fleet.jobs())
        retry = fleet.submit("2pc:3", idempotency_key="kr")
        assert retry.pool_job is not None
        # Concurrent submits started exactly ONE monitor thread.
        assert sum(
            1 for t in threading.enumerate()
            if t.name == "stpu-fleet-monitor" and t.is_alive()
        ) == 1
    finally:
        fleet.close()


def test_fleet_submit_unwinds_on_non_admission_errors(tmp_path):
    """A non-admission failure mid-routing (malformed spec → ValueError
    from registry.parse) must not leak the reserved handle as a
    permanently-queued zombie FleetJob: the reservation unwinds, the
    caller sees the original error, and the key stays retryable."""
    fleet = _fleet(tmp_path, devices=2, pool_kw={"max_inflight": 0})
    try:
        with pytest.raises(ValueError):
            fleet.submit("not-a-spec", idempotency_key="kz")
        assert fleet.jobs() == []
        assert fleet.gauges()["rejected"] == 1
        good = fleet.submit("2pc:3", idempotency_key="kz")
        assert good.pool_job is not None
    finally:
        fleet.close()


def _live_monitors():
    return [
        t for t in threading.enumerate()
        if t.name == "stpu-fleet-monitor" and t.is_alive()
    ]


def test_fleet_monitor_idle_exits_and_restarts(tmp_path):
    """The monitor thread exits once every fleet job is terminal (no
    forever-sweep of every pool's locks on a long-lived fleet) and comes
    back on the next submit — and the idle check itself must not
    deadlock on the fleet lock (it runs under it; FleetJob.done would
    re-acquire)."""
    fleet = _fleet(tmp_path, devices=2)
    try:
        fleet.submit("2pc:3")
        assert fleet.wait_all(timeout=240), fleet.metrics()
        deadline = time.monotonic() + 10.0
        while _live_monitors() and time.monotonic() < deadline:
            time.sleep(0.1)
        assert not _live_monitors()  # idle-exited
        again = fleet.submit("2pc:3")
        assert _live_monitors()  # submit brought it back
        assert again.wait(timeout=240)
        assert again.status == "done"
    finally:
        fleet.close()


def test_elastic_quiesce_wake_exact(tmp_path):
    """Elastic pools (docs/service.md "QoS & overload"): a quiesced pool
    leaves routing — work lands on the remaining active pool with counts
    bit-identical to an undisturbed run — and wakes back into rotation;
    ``min_active`` refuses to quiesce the last active pool."""
    fleet = _fleet(tmp_path, devices=2, elastic=True,
                   idle_quiesce_s=3600.0, min_active=1)
    try:
        assert fleet.quiesce_pool(1, reason="test")
        assert not fleet.quiesce_pool(0, reason="test")  # min_active
        assert not fleet.quiesce_pool(1, reason="test")  # already parked
        job = fleet.submit("2pc:3")
        assert job.device == 0
        assert fleet.wait_all(timeout=240), fleet.metrics()
        assert job.status == "done", (job.status, job.error)
        assert job.migrations == []
        _assert_exact(job.result, "2pc:3")
        g = fleet.gauges()
        assert g["quiesced_devices"] == [1]
        assert g["pools_quiesced"] == 1
        assert g["devices"]["device-1"]["quiesced"] is True
        assert g["devices"]["device-1"]["lost"] is False
        assert fleet.wake_pool(1, reason="test")
        g = fleet.gauges()
        assert g["quiesced_devices"] == []
        assert g["pools_woken"] == 1
    finally:
        fleet.close()


def test_elastic_wake_on_pressure(tmp_path):
    """A submission every active pool rejects WITH a retry hint (pure
    pressure) wakes a quiesced pool and places there, instead of
    bouncing the tenant or forcing the host engine. Routing-only
    (disarmed pools)."""
    fleet = _fleet(tmp_path, devices=2, elastic=True,
                   pool_kw={"max_inflight": 0, "max_queue": 1})
    try:
        assert fleet.quiesce_pool(1, reason="test")
        a = fleet.submit("2pc:3")
        assert a.device == 0
        # Pool 0 at its shed limit: the hint-carrying rejection wakes
        # the parked sibling mid-submit.
        b = fleet.submit("2pc:3")
        assert b.device == 1
        g = fleet.gauges()
        assert g["pools_woken"] == 1
        assert g["quiesced_devices"] == []
        # A hint-less rejection (over-cap budget — identical on every
        # device) must NOT wake anything: waking cannot help.
        woken_before = fleet.gauges()["pools_woken"]
        with pytest.raises(AdmissionError) as exc:
            fleet.submit("2pc:3", max_seconds=10_000_000.0)
        assert exc.value.retry_after_s is None
        assert fleet.gauges()["pools_woken"] == woken_before
    finally:
        fleet.close()


def test_evacuate_skips_forced_host_jobs(tmp_path):
    """Forced-host work is device-independent: losing the device must
    not kill it (host attempts don't checkpoint — evacuation would
    discard the progress for zero safety gain)."""
    svc = CheckerService(_config(tmp_path, max_inflight=0))
    try:
        host_job = svc.submit("2pc:3", engine="host")
        dev_job = svc.submit("2pc:3")
        out = svc.evacuate(reason="device lost")
        assert [j.id for j in out] == [dev_job.id]
        assert dev_job.status == "migrated"
        assert host_job.status == "queued"  # rides out the outage
    finally:
        svc.close()


def test_fleet_session_cap_holds_under_concurrent_registration(tmp_path):
    """The fleet-wide max_sessions cap is atomic with registration: N
    concurrent register_interactive calls against a cap of 1 admit
    exactly one session — the rest reject typed (the per-pool caps alone
    would have let several through)."""
    import types

    fleet = _fleet(tmp_path, devices=2, max_sessions=1,
                   pool_kw={"max_inflight": 0, "max_sessions": 4})
    try:
        admitted, rejected = [], []

        def grab():
            checker = types.SimpleNamespace(
                model=lambda: object(), attach_job=lambda jid: None
            )
            try:
                admitted.append(
                    fleet.register_interactive(checker, label="swarm")
                )
            except AdmissionError as e:
                rejected.append(e)

        threads = [threading.Thread(target=grab) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(admitted) == 1
        assert len(rejected) == 5
        assert all(e.retry_after_s is not None for e in rejected)
        assert fleet.gauges()["interactive"] == 1
        fleet.release_interactive(admitted[0])
        assert fleet.gauges()["interactive"] == 0
    finally:
        fleet.close()


def test_job_snapshot_memoizes_artifact_ages(tmp_path, monkeypatch):
    """ISSUE 15 satellite: snapshot()'s heartbeat/checkpoint ages stat
    each artifact once per poll tick (snapshot_age_ttl_s), not once per
    render — and the snapshot surfaces the pool's device."""
    from stateright_tpu.service import core as svc_core

    svc = CheckerService(_config(tmp_path, max_inflight=0, device="dev7"))
    try:
        job = svc.submit("2pc:3")
        with open(os.path.join(job.dir, "hb.json"), "w") as fh:
            fh.write("{}")
        calls = []
        real = svc_core._mtime_age
        monkeypatch.setattr(
            svc_core, "_mtime_age", lambda p: calls.append(p) or real(p)
        )
        first = job.snapshot()
        assert first["device"] == "dev7"
        assert first["heartbeat_age_s"] is not None
        n = len(calls)
        assert n == 2  # hb + checkpoint, once each
        for _ in range(10):  # a 10-poll render burst within the TTL
            job.snapshot()
        assert len(calls) == n  # memo hit: zero extra stats
    finally:
        svc.close()


# --- the Explorer as one service client ------------------------------------


def test_explorer_is_a_service_client(tmp_path):
    from stateright_tpu.checker.explorer import make_app
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    svc = CheckerService(_config(tmp_path, max_sessions=1))
    try:
        app, checker = make_app(TwoPhaseSys(3).checker(), service=svc)
        status = app.status()
        # Pre-service keys unchanged for existing consumers...
        for key in (
            "done", "model", "state_count", "unique_state_count",
            "max_depth", "properties", "recent_path", "metrics",
            "last_checkpoint",
        ):
            assert key in status
        # ...plus the per-job pool fields.
        assert status["job"] is not None
        assert status["degraded"] is False
        assert status["pool"]["interactive"] == 1
        assert status["pool"]["breaker"]["state"] == "closed"
        assert status["metrics"]["job_id"] == status["job"]
        code, pool = app.pool()
        assert code == 200
        assert status["job"] in pool["jobs"]
        assert pool["jobs"][status["job"]]["kind"] == "interactive"

        # Interactive admission: the session cap rejects typed, like any
        # other tenant.
        with pytest.raises(AdmissionError, match="sessions full"):
            make_app(TwoPhaseSys(3).checker(), service=svc)
        job = svc.job(status["job"])
        svc.release_interactive(job)
        app2, _ = make_app(TwoPhaseSys(3).checker(), service=svc)
        assert app2.status()["pool"]["interactive"] == 1
    finally:
        svc.close()


def test_explorer_degrades_while_breaker_open(tmp_path):
    """With the breaker open the service does not hand the device to
    anyone: an auto/xla Explorer session is served by the host on-demand
    engine with ``degraded: true`` in /.status."""
    from stateright_tpu.checker.explorer import make_app
    from stateright_tpu.checker.on_demand import OnDemandChecker
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    svc = CheckerService(_config(tmp_path))
    try:
        with svc._cond:
            svc._breaker = "open"
        app, checker = make_app(
            PackedTwoPhaseSys(3).checker(),
            service=svc,
            frontier_capacity=1 << 8,
            table_capacity=1 << 10,
        )
        assert isinstance(checker, OnDemandChecker)
        status = app.status()
        assert status["degraded"] is True
        assert status["pool"]["breaker"]["state"] == "open"
        # The degraded session still serves the model: init states expand
        # on the host engine.
        code, inits = app.states("/")
        assert code == 200 and len(inits) == 1
    finally:
        svc.close()
