"""Fault-injection worker for the supervisor chaos tests.

Runs a packed-model check under in-loop auto-checkpointing and (via the
``STPU_HEARTBEAT`` env the supervisor injects) the heartbeat protocol,
optionally sabotaging itself at a given depth — exactly once, gated by a
marker file, so the supervised RELAUNCH runs clean:

- ``--die-at-depth N``: SIGKILL itself at the first quiescent point at or
  past depth N (a crash mid-run; nothing gets to flush);
- ``--freeze-at-depth N``: rewrite the heartbeat to ``phase="dispatch"``
  and SIGSTOP itself — the exact signature of a hung dispatch (a frozen
  process mid-device-call), which the supervisor must detect by heartbeat
  staleness and kill.

At completion the final counts/discoveries land in ``--out`` (atomic
write), for the test to compare bit-for-bit against an uninterrupted run.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_model(spec: str):
    if spec.startswith("2pc"):
        from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

        return PackedTwoPhaseSys(int(spec[3:])), dict(
            frontier_capacity=1 << 10, table_capacity=1 << 13
        )
    if spec == "scr31":
        from stateright_tpu.models.single_copy_register import (
            PackedSingleCopyRegister,
        )

        return PackedSingleCopyRegister(3, 1), dict(
            frontier_capacity=1 << 11, table_capacity=1 << 14
        )
    raise SystemExit(f"unknown model spec {spec!r}")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", required=True)  # 2pc3 | 2pc4 | scr31
    p.add_argument("--engine", default="single")  # single | sharded
    p.add_argument("--checkpoint", required=True)  # auto-checkpoint base
    p.add_argument("--resume", default=None)
    p.add_argument("--every", default="1")  # cadence (levels by default)
    p.add_argument("--keep", type=int, default=3)
    p.add_argument("--die-at-depth", type=int, default=None)
    p.add_argument("--freeze-at-depth", type=int, default=None)
    p.add_argument("--chaos-marker", default=None)
    p.add_argument("--out", required=True)
    args = p.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    model, kw = _build_model(args.model)
    kw.update(
        # One level per dispatch: fine-grained quiescent points, so the
        # chaos depth and the checkpoint cadence line up deterministically.
        levels_per_dispatch=1,
        checkpoint_to=args.checkpoint,
        checkpoint_every=args.every,
        checkpoint_keep=args.keep,
    )
    if args.resume:
        kw["checkpoint"] = args.resume
    if args.engine == "sharded":
        from stateright_tpu.parallel import default_mesh

        kw["mesh"] = default_mesh()
    checker = model.checker().spawn_xla(**kw)
    start_depth = checker._depth

    armed = args.chaos_marker is not None and not os.path.exists(
        args.chaos_marker
    )

    def trip():
        # Exactly-once: mark BEFORE the signal so the relaunch runs clean.
        with open(args.chaos_marker, "w") as fh:
            fh.write("tripped\n")

    while not checker.is_done():
        checker._run_block()
        depth = checker._depth
        if armed and args.die_at_depth is not None and depth >= args.die_at_depth:
            trip()
            os.kill(os.getpid(), signal.SIGKILL)
        if (
            armed
            and args.freeze_at_depth is not None
            and depth >= args.freeze_at_depth
        ):
            trip()
            # A hung dispatch's signature: the engine entered a device
            # dispatch (heartbeat phase="dispatch", no compile in flight)
            # and never came back.
            if checker._heartbeat is not None:
                checker._heartbeat.beat("dispatch", compile=False)
            os.kill(os.getpid(), signal.SIGSTOP)

    result = {
        "model": args.model,
        "engine": args.engine,
        "generated": checker.state_count(),
        "unique": checker.unique_state_count(),
        "max_depth": checker.max_depth(),
        "discoveries": {
            name: [repr(a) for a in path.into_actions()]
            for name, path in sorted(checker.discoveries().items())
        },
        "resumed_from": args.resume,
        "start_depth": start_depth,
        "checkpoints_written": checker.metrics()["checkpoints_written"],
        "last_checkpoint_level": checker.metrics()["last_checkpoint_level"],
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(result, fh)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
