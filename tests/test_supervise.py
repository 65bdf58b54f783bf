"""The crash-recovery stack under fault injection (ISSUE 8 acceptance):

- SIGKILL a supervised worker at a random superstep; the supervisor
  relaunches it RESUMING from the latest valid checkpoint rotation, and
  the final unique/generated counts and discoveries are bit-identical to
  an uninterrupted run — on two packed models under both the single-chip
  and the sharded engine (CPU backend).
- SIGSTOP (frozen heartbeat mid-"dispatch" — the hung-dispatch signature)
  is detected by heartbeat staleness, the process group is killed, and the
  resumed run still converges exactly.
- A truncated/torn checkpoint raises the typed ``CheckpointCorrupt`` (not
  a zipfile traceback) and the supervisor's resume resolution falls back
  to the previous rotation automatically.

The worker body is ``tests/chaos_worker.py``; supervision is the real
library (``stateright_tpu/supervise.py``) — the same code bench.py and
tools/soak.py run."""

import json
import os
import random
import sys

import pytest

from stateright_tpu import checkpoint as ck_mod
from stateright_tpu import supervise as sup
from stateright_tpu.parallel import default_mesh

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "chaos_worker.py")

#: Pinned full-coverage (generated, unique) counts (bench.py EXPECTED_*).
PINNED = {
    "2pc3": (1_146, 288),
    "2pc4": (8_258, 1_568),
    "scr31": (6_778, 4_243),
}


def _build(spec):
    if spec.startswith("2pc"):
        from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

        return PackedTwoPhaseSys(int(spec[3:])), dict(
            frontier_capacity=1 << 10, table_capacity=1 << 13
        )
    from stateright_tpu.models.single_copy_register import (
        PackedSingleCopyRegister,
    )

    return PackedSingleCopyRegister(3, 1), dict(
        frontier_capacity=1 << 11, table_capacity=1 << 14
    )


_REF_CACHE = {}


def _reference(spec, engine):
    """Uninterrupted in-process run of the same (model, engine) — the
    ground truth the supervised chaos run must reproduce bit-for-bit.
    Discoveries compare per engine: the mesh's pmax witness election is a
    documented divergence from the single-chip frontier order."""
    key = (spec, engine)
    if key not in _REF_CACHE:
        model, kw = _build(spec)
        if engine == "sharded":
            kw = dict(kw, mesh=default_mesh())
        c = model.checker().spawn_xla(**kw).join()
        _REF_CACHE[key] = {
            "generated": c.state_count(),
            "unique": c.unique_state_count(),
            "max_depth": c.max_depth(),
            "discoveries": {
                name: [repr(a) for a in path.into_actions()]
                for name, path in sorted(c.discoveries().items())
            },
        }
    return _REF_CACHE[key]


def _supervised_chaos(tmp_path, spec, engine, chaos_flag, depth, *,
                      retries=2, stall_s=1200.0):
    ck = str(tmp_path / "ck.npz")
    out = str(tmp_path / "result.json")
    marker = str(tmp_path / "chaos.marker")

    def make_argv(attempt, resume):
        argv = [
            sys.executable, WORKER,
            "--model", spec, "--engine", engine,
            "--checkpoint", ck, "--out", out,
            "--every", "1", "--keep", "3",
            "--chaos-marker", marker,
            chaos_flag, str(depth),
        ]
        if resume:
            argv += ["--resume", resume]
        return argv

    res = sup.supervise(
        make_argv,
        checkpoint=ck,
        retries=retries,
        backoff_s=0.1,
        heartbeat=str(tmp_path / "hb.json"),
        timeout_s=600,
        stall_s=stall_s,
        startup_grace_s=300,
        poll_s=0.5,
        stdout_path=lambda attempt: str(tmp_path / f"worker{attempt}.out"),
    )
    assert res.ok, [(a.rc, a.killed) for a in res.attempts]
    assert os.path.exists(marker), "chaos never tripped"
    with open(out) as fh:
        return res, json.load(fh)


def _assert_exact(result, spec, engine):
    ref = _reference(spec, engine)
    assert (result["generated"], result["unique"]) == PINNED[spec]
    assert result["generated"] == ref["generated"]
    assert result["unique"] == ref["unique"]
    assert result["max_depth"] == ref["max_depth"]
    assert result["discoveries"] == ref["discoveries"]


# --- SIGKILL at a random superstep, both engines, two packed models -------


@pytest.mark.parametrize(
    "spec,engine",
    [
        ("2pc4", "single"),
        ("2pc4", "sharded"),
        ("scr31", "single"),
        ("scr31", "sharded"),
    ],
)
def test_sigkill_resume_exact(tmp_path, spec, engine):
    depth = random.randint(3, 6)  # a random superstep mid-space
    res, result = _supervised_chaos(
        tmp_path, spec, engine, "--die-at-depth", depth
    )
    # The first attempt died (SIGKILL = -9); a later attempt resumed from a
    # checkpoint (with per-level cadence the latest one is AT the kill
    # depth — zero levels replayed) and converged exactly.
    assert res.attempts[0].rc == -9
    assert len(res.attempts) >= 2
    assert res.resumed_from[-1] is not None
    assert result["resumed_from"] == res.resumed_from[-1]
    assert result["start_depth"] == depth
    _assert_exact(result, spec, engine)


# --- SIGSTOP: frozen heartbeat mid-dispatch = hung dispatch ---------------


def test_sigstop_wedge_detected_and_resumed(tmp_path):
    depth = random.randint(3, 6)
    # stall_s=10: a frozen beat in phase="dispatch" goes stale past the
    # leash and the supervisor must kill the (unkillable-by-SIGTERM,
    # SIGSTOP-frozen) process group and relaunch. Compile-carrying beats
    # get a 3x leash, so healthy first-dispatch compiles survive.
    res, result = _supervised_chaos(
        tmp_path, "2pc4", "single", "--freeze-at-depth", depth, stall_s=10.0
    )
    assert res.attempts[0].killed is not None
    assert "stale" in res.attempts[0].killed
    assert res.resumed_from[-1] is not None
    assert result["start_depth"] == depth
    _assert_exact(result, "2pc4", "single")


# --- torn checkpoint: typed error + automatic rotation fallback -----------


def test_truncated_checkpoint_typed_error_and_fallback(tmp_path):
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    ck = str(tmp_path / "ck.npz")
    partial = PackedTwoPhaseSys(4).checker().spawn_xla(
        frontier_capacity=1 << 10, table_capacity=1 << 13,
        levels_per_dispatch=1,
    )
    for _ in range(3):
        partial._run_block()
    partial.save_checkpoint(ck, keep=3)
    partial._run_block()
    partial.save_checkpoint(ck, keep=3)  # rotates the depth-4 file to .1

    # Truncate the newest rotation mid-file — a torn write from a crashed
    # foreign writer. Detection must be the TYPED error, not a zipfile
    # traceback…
    size = os.path.getsize(ck)
    with open(ck, "r+b") as fh:
        fh.truncate(size // 2)
    with pytest.raises(ck_mod.CheckpointCorrupt):
        ck_mod.load_checkpoint(ck)

    # …and the supervisor's resume resolution falls back to the previous
    # rotation automatically.
    assert ck_mod.latest_valid_checkpoint(ck) == ck + ".1"

    seen = []

    def make_argv(attempt, resume):
        seen.append(resume)
        return [sys.executable, "-c", "pass"]

    res = sup.supervise(make_argv, checkpoint=ck, retries=0, poll_s=0.2)
    assert res.ok
    assert seen == [ck + ".1"]

    # The fallback rotation resumes to the exact pinned counts.
    resumed = PackedTwoPhaseSys(4).checker().spawn_xla(
        frontier_capacity=1 << 10, table_capacity=1 << 13,
        checkpoint=ck + ".1",
    ).join()
    assert resumed.state_count() == 8_258
    assert resumed.unique_state_count() == 1_568


# --- fast kill-and-resume smoke (tools/smoke.sh) --------------------------


def test_smoke_kill_resume(tmp_path):
    """The <30s tier-0 crash drill: one SIGKILL, one supervised resume,
    exact pinned counts on the smallest packed model."""
    res, result = _supervised_chaos(
        tmp_path, "2pc3", "single", "--die-at-depth", 3, retries=1
    )
    assert res.attempts[0].rc == -9
    assert res.resumed_from[-1] is not None
    assert (result["generated"], result["unique"]) == PINNED["2pc3"]
    assert result["checkpoints_written"] >= 1
