"""Supervised device soaks: rm=10+ full-coverage runs that SURVIVE hangs.

The ROADMAP soak goal (rm=10-12 at >= 10^8-10^9 generated states) needs
runs that outlive a hung dispatch (one that never returns).
``tools/tpu_soak.py`` (the in-process soak ladder) loses the whole search
to one hang; this driver runs ONE soak
config per invocation through the crash-recovery supervisor
(``stateright_tpu/supervise.py``):

- the worker (``--worker``) checks the config's model with in-loop
  auto-checkpointing (rotated, atomic, self-verifying —
  ``stateright_tpu/checkpoint.py``) and the heartbeat the supervisor
  injects via ``STPU_HEARTBEAT``;
- the parent watches heartbeat phase+staleness (hung dispatch vs long XLA
  compile), kills the worker's process group on a wedge, and relaunches it
  RESUMING from the latest valid checkpoint rotation — a wedge costs one
  checkpoint interval, not the run;
- ``--cpu-fallback`` adds a final CPU attempt (hard timeout only) after
  the retries are spent.

Usage:
  python tools/soak.py [--config quick|rm9|rm10|rm11|paxos33] [--cpu]
                       [--budget-s N] [--retries N] [--every SPEC]
                       [--keep K] [--dedup D] [--cpu-fallback]

Artifacts land under ``runs/soak/`` (checkpoint rotations, worker stdout);
the final worker line is JSON with generated/unique/depth/done + resume
provenance. Exit code 0 = the supervised run reached full coverage (or its
state target).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SOAK_DIR = os.path.join(REPO, "runs", "soak")

#: name -> (model factory import spec, spawn kwargs, default budget_s).
#: Capacities follow tools/tpu_soak.py: pre-sized so growth recompiles
#: never interrupt the steady state.
CONFIGS = {
    "quick": ("2pc", 7, dict(frontier_capacity=1 << 17, table_capacity=1 << 19), 900),
    "rm9": ("2pc", 9, dict(frontier_capacity=1 << 20, table_capacity=1 << 24), 1800),
    "rm10": ("2pc", 10, dict(frontier_capacity=1 << 21, table_capacity=1 << 27), 2400),
    "rm11": ("2pc", 11, dict(frontier_capacity=1 << 22, table_capacity=1 << 28), 1800),
    "paxos33": ("paxos", (3, 3), dict(frontier_capacity=1 << 19, table_capacity=1 << 25), 2400),
}


def _build_model(kind, arg):
    if kind == "2pc":
        from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

        return PackedTwoPhaseSys(arg)
    from stateright_tpu.models.paxos import PackedPaxos

    return PackedPaxos(*arg)


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", default="rm10", choices=sorted(CONFIGS))
    p.add_argument("--cpu", action="store_true", help="pin the worker to CPU")
    p.add_argument("--budget-s", type=float, default=None,
                   help="worker wall-clock budget (default per config)")
    p.add_argument("--retries", type=int, default=3)
    p.add_argument("--every", default="60s",
                   help="checkpoint cadence: N levels or 'Ns' seconds")
    p.add_argument("--keep", type=int, default=3, help="checkpoint rotations")
    p.add_argument("--dedup", default=None, help="visited-set structure override")
    p.add_argument("--stall-s", type=float, default=900.0)
    p.add_argument("--startup-grace-s", type=float, default=900.0)
    p.add_argument("--cpu-fallback", action="store_true",
                   help="one final CPU attempt after retries are spent")
    p.add_argument("--audit", action="store_true",
                   help="run the duplicate-key table audit at completion")
    # worker-mode internals
    p.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--resume", default=None, help=argparse.SUPPRESS)
    return p.parse_args(argv)


def _worker(args) -> int:
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()

    kind, marg, kw, default_budget = CONFIGS[args.config]
    budget_s = args.budget_s if args.budget_s is not None else default_budget
    ck = os.path.join(SOAK_DIR, f"{args.config}.npz")
    print(
        f"[soak] worker config={args.config} platform="
        f"{jax.devices()[0].platform} resume={args.resume} budget={budget_s:.0f}s",
        flush=True,
    )
    spawn_kw = dict(
        kw,
        checkpoint_to=ck,
        checkpoint_every=args.every,
        checkpoint_keep=args.keep,
    )
    if args.dedup:
        spawn_kw["dedup"] = args.dedup
    if args.resume:
        spawn_kw["checkpoint"] = args.resume
    model = _build_model(kind, marg)
    c = model.checker().spawn_xla(**spawn_kw)
    start_depth = c._depth
    # Throughput baseline: a resume restores state_count, but only states
    # generated by THIS attempt happened inside dt (bench's _run_check
    # subtracts the same states0).
    gen0 = c.state_count()
    t0 = time.monotonic()
    last_hb = t0
    while not c.is_done() and time.monotonic() - t0 < budget_s:
        c._run_block()
        now = time.monotonic()
        if now - last_hb > 60:
            print(
                f"[soak] {args.config} progress: gen={c.state_count():,} "
                f"uniq={c.unique_state_count():,} depth={c.max_depth()} "
                f"t={now - t0:.0f}s",
                flush=True,
            )
            last_hb = now
    dt = time.monotonic() - t0
    # One last checkpoint at the final quiescent point: a budget-truncated
    # soak hands its successor exactly where it stopped.
    c.save_checkpoint(ck, keep=args.keep)
    audit = None
    if args.audit and c.is_done():
        try:
            from stateright_tpu.audit import audit_table

            audit = audit_table(c)
        except Exception as e:  # pragma: no cover - diagnostic path
            audit = {"error": f"{type(e).__name__}: {e}"}
    m = c.metrics()
    print(
        json.dumps(
            {
                "config": args.config,
                "backend": jax.default_backend(),
                "generated": c.state_count(),
                "unique": c.unique_state_count(),
                "max_depth": c.max_depth(),
                "done": c.is_done(),
                "sec": round(dt, 1),
                "generated_this_attempt": c.state_count() - gen0,
                "gen_per_sec": round(
                    (c.state_count() - gen0) / max(dt, 1e-9), 1
                ),
                "resumed_from": args.resume,
                "start_depth": start_depth,
                "checkpoints_written": m["checkpoints_written"],
                "last_checkpoint_level": m["last_checkpoint_level"],
                "audit": audit,
            }
        ),
        flush=True,
    )
    return 0 if c.is_done() else 1


def main(argv=None) -> int:
    args = _parse_args(argv)
    if args.worker:
        return _worker(args)

    from stateright_tpu import supervise as sup

    os.makedirs(SOAK_DIR, exist_ok=True)
    ck = os.path.join(SOAK_DIR, f"{args.config}.npz")
    kind, marg, kw, default_budget = CONFIGS[args.config]
    budget_s = args.budget_s if args.budget_s is not None else default_budget

    def _log(msg):
        print(f"[soak] {msg}", file=sys.stderr, flush=True)

    def _argv(cpu):
        base = [sys.executable, os.path.abspath(__file__), "--worker",
                "--config", args.config, "--every", args.every,
                "--keep", str(args.keep),
                "--budget-s", str(budget_s)]
        if args.dedup:
            base += ["--dedup", args.dedup]
        if args.audit:
            base += ["--audit"]
        if cpu:
            base += ["--cpu"]
        return base

    # A COMPLETED checkpoint is not resumable work: resuming it would
    # instantly report done=true with zero states explored this run —
    # stale data dressed as a fresh successful soak. Clear every rotation
    # and re-measure. (A PARTIAL checkpoint must survive: a restarted
    # soak resumes exactly there — that is the point.)
    from stateright_tpu.checkpoint import latest_valid_checkpoint, rotations

    done_path, done_meta = latest_valid_checkpoint(ck, with_meta=True)
    if done_meta is not None and done_meta.get("done", False):
        _log(f"clearing completed checkpoint {done_path}; re-measuring fresh")
        for f in rotations(ck):
            try:
                os.unlink(f)
            except OSError:
                pass

    def make_argv(attempt, resume):
        return _argv(args.cpu) + (["--resume", resume] if resume else [])

    def fallback_argv(attempt, resume):
        return _argv(True) + (["--resume", resume] if resume else [])

    # Nested supervision: under an outer watcher its own
    # STPU_HEARTBEAT is reused as the worker's beat file, so the outer
    # watcher (looser leash) sees the same liveness this parent does.
    hb = os.environ.get("STPU_HEARTBEAT") or os.path.join(
        SOAK_DIR, f"{args.config}.heartbeat.json"
    )
    if args.cpu:
        # Only the hard timeout supervises a CPU soak, and an outer watcher must not read CPU-paced beats
        # (bench.py's CPU fallback does the same).
        os.environ.pop("STPU_HEARTBEAT", None)
    res = sup.supervise(
        make_argv,
        checkpoint=ck,
        retries=args.retries,
        backoff_s=10.0,
        heartbeat=None if args.cpu else hb,
        timeout_s=budget_s + max(600.0, budget_s),
        stall_s=args.stall_s,
        startup_grace_s=args.startup_grace_s,
        stdout_path=lambda attempt: os.path.join(
            SOAK_DIR, f"{args.config}.worker{attempt}.out"
        ),
        fallback_make_argv=fallback_argv if args.cpu_fallback else None,
        fallback_timeout_s=budget_s + max(600.0, budget_s),
        log=_log,
        cwd=REPO,
    )
    for i, (att, resume) in enumerate(zip(res.attempts, res.resumed_from)):
        _log(
            f"attempt {i}: rc={att.rc} killed={att.killed} "
            f"{att.seconds:.0f}s resume={resume}"
        )
    if res.final is not None and res.final.stdout_path:
        try:
            with open(res.final.stdout_path) as fh:
                sys.stdout.write(fh.read())
        except OSError:
            pass
    _log(f"supervised soak {'OK' if res.ok else 'FAILED'} "
         f"({len(res.attempts)} attempts, fallback={res.used_fallback})")
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(main())
