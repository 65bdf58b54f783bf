"""One service job's process body: the unit of fault isolation.

``CheckerService`` never touches the device from its own process — every
device job runs THIS script in its own process group under
``supervise.run_worker`` (heartbeat-polled, killable as a group), so a
hung dispatch or a runaway model takes down exactly one job and
the service requeues it from its auto-checkpoint. The script is runnable
both as ``python -m stateright_tpu.service.worker`` and by file path (the
service invokes the latter so the child needs no import-path inheritance).

Engines:

- ``--engine xla`` (default): the single-chip device engine with per-job
  in-loop auto-checkpointing (``--checkpoint``/``--every``/``--keep``),
  resume (``--resume``), and a per-job metrics time-series
  (``--metrics`` → quiescent-boundary samples plus a forced final row;
  docs/observability.md "Time series"). The heartbeat rides in via
  ``STPU_HEARTBEAT`` (injected by ``run_worker``), the span trace via
  ``STPU_TRACE`` — all per-job files under the service's run dir.
- ``--engine host``: the host on-demand engine
  (``stateright_tpu/checker/on_demand.py``) unblocked and driven in
  ``--block-size`` blocks — the breaker's graceful-degradation target;
  always pinned to the CPU backend.

Budgets: ``--max-states`` rides through ``target_state_count`` (the
checker may exceed it by one block but never runs past it while more
states exist); ``--max-seconds`` is a soft in-loop wall-clock check that
exits with code 3 at the next quiescent point (the supervisor's hard
timeout still backstops a worker that cannot reach one).

Fault injection (the chaos suite's hooks, mirroring
``tests/chaos_worker.py``): ``--chaos-die-at-depth N`` SIGKILLs the
process at the first quiescent point at or past depth N;
``--chaos-freeze-at-depth N`` rewrites the heartbeat to
``phase="dispatch"`` and SIGSTOPs — the exact signature of a hung
dispatch. With ``--chaos-marker`` the sabotage trips exactly once (the
requeued attempt runs clean); without it, every attempt trips — the
repeat-wedge shape the breaker tests need.

At completion the counts/discoveries/metrics land in ``--out`` (atomic
write) for the service to parse.

Multiplexed mode (``--mux manifest.json``; docs/service.md "Batched
scheduling"): ONE worker drives K same-spec jobs through the batched
fused engine (``stateright_tpu/xla_mux.py``). The manifest carries one
lane entry per member job — its own ``out``/``checkpoint``/``metrics``/
``resume`` paths, ``max_states``, and chaos flags — and the worker
resolves the spec ONCE, spawns K lane checkers over the shared model,
and steps a :class:`MuxChecker`. Each lane's ``result.json`` is written
the moment that lane finishes (so a crash mid-batch loses only the
unfinished lanes — the service settles finished members done and
requeues the rest), and ``--out`` receives a group summary
(``dispatches``/``dispatches_saved``) the service folds into its mux
counters. A spec that turns out mux-ineligible at resolve time (typed
``MuxError`` — e.g. lanes resuming at diverged capacities) falls back to
driving the lanes sequentially in this same process: same per-lane
results, no batching win, never a failure.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def _lane_armed(chaos: dict) -> bool:
    """Whether a lane's sabotage flags are live (marker = exactly-once)."""
    if (
        chaos.get("die_at_depth") is None
        and chaos.get("freeze_at_depth") is None
    ):
        return False
    marker = chaos.get("marker")
    return marker is None or not os.path.exists(marker)


def _lane_trip(chaos: dict) -> None:
    marker = chaos.get("marker")
    if marker is not None:
        with open(marker, "w") as fh:
            fh.write("tripped\n")


def _job_trace():
    """The worker's end of the distributed-trace seam
    (docs/observability.md "Distributed tracing"): the tracer named by
    ``STPU_TRACE`` inherits the submission context from ``STPU_TRACE_CTX``
    (both exported by the service), and the whole process body runs under
    ONE pre-allocated ``job`` span — engine dispatch spans parent to it
    via ``set_parent``. Returns ``(tracer, job_sid, attempt_sid, t0)``;
    ``job_sid`` is None when tracing or context is off."""
    from stateright_tpu import obs

    tracer = obs.resolve_tracer(None)
    ctx = obs.parse_ctx(os.environ.get(obs.CTX_ENV))
    if not (tracer.enabled and ctx):
        return tracer, None, None, time.monotonic()
    job_sid = tracer.new_span_id()
    tracer.set_parent(job_sid)
    return tracer, job_sid, ctx[1], time.monotonic()


def _end_job_trace(tracer, job_sid, attempt_sid, t0, **attrs) -> None:
    if job_sid is not None:
        tracer.emit(
            "job", t0=t0, dur=time.monotonic() - t0, attrs=attrs,
            parent_id=attempt_sid, span_id=job_sid,
        )


def _mux_main(args, device_label) -> int:
    """The ``--mux`` body: K lanes of one spec through the batched fused
    engine (falling back to sequential solo drive on ``MuxError``)."""
    import jax

    from stateright_tpu.service.registry import resolve
    from stateright_tpu.xla_mux import MuxChecker, MuxError

    tracer, job_sid, attempt_sid, jt0 = _job_trace()
    with open(args.mux) as fh:
        manifest = json.load(fh)
    lanes_cfg = manifest["lanes"]
    model, caps = resolve(args.spec)
    chaos_armed = [_lane_armed(lane.get("chaos") or {}) for lane in lanes_cfg]
    checkers = []
    for i, lane in enumerate(lanes_cfg):
        builder = model.checker()
        if lane.get("max_states"):
            builder = builder.target_state_count(lane["max_states"])
        kw = dict(caps)
        if any(chaos_armed):
            # Same contract as solo chaos runs: one level per dispatch so
            # sabotage depths and checkpoint cadence line up — for EVERY
            # lane, since the batch shares one dispatch cadence.
            kw["levels_per_dispatch"] = 1
        if lane.get("checkpoint"):
            kw.update(
                checkpoint_to=lane["checkpoint"],
                checkpoint_every=args.every,
                checkpoint_keep=args.keep,
            )
        if lane.get("metrics"):
            kw["metrics_to"] = lane["metrics"]
        if lane.get("resume"):
            kw["checkpoint"] = lane["resume"]
        checkers.append(builder.spawn_xla(**kw))
    start_depths = [ln._depth for ln in checkers]
    t0 = time.monotonic()

    def over_budget() -> bool:
        return (
            args.max_seconds is not None
            and time.monotonic() - t0 > args.max_seconds
        )

    try:
        mux = MuxChecker(checkers)
    except MuxError as e:
        # Graceful degradation: same process, same per-lane artifacts,
        # sequential device calls — the batch loses its win, not its jobs.
        print(f"mux ineligible, driving lanes solo: {e}", file=sys.stderr)
        mux = None

    written = [False] * len(checkers)

    def write_lane(i: int) -> None:
        ln = checkers[i]
        lane = lanes_cfg[i]
        metrics = dict(ln.metrics())
        # Lane attribution (docs/observability.md "Lane telemetry"): the
        # lane's own counts/rates, plus the batch context — a member's
        # metrics.json never reports the whole batch's gen/s as its own.
        metrics["mux_lanes"] = len(checkers)
        metrics["mux_dispatches_saved"] = (
            mux._dispatches_saved if mux is not None else 0
        )
        recorder = getattr(ln, "_recorder", None)
        if recorder is not None:
            recorder.sample(metrics, kind="engine")
        result = {
            "spec": args.spec,
            "engine": "xla",
            "platform": jax.default_backend(),
            "device": device_label,
            "device_ordinal": args.device,
            "degraded": False,
            "generated": ln.state_count(),
            "unique": ln.unique_state_count(),
            "max_depth": ln.max_depth(),
            "discoveries": {
                name: [repr(a) for a in path.into_actions()]
                for name, path in sorted(ln.discoveries().items())
            },
            "resumed_from": lane.get("resume"),
            "start_depth": start_depths[i],
            "seconds": time.monotonic() - t0,
            "mux": {
                "group": manifest.get("group"),
                "lanes": len(checkers),
                "lane": i,
            },
            "metrics": metrics,
        }
        tmp = lane["out"] + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(result, fh, default=str)
        os.replace(tmp, lane["out"])
        written[i] = True
        if job_sid is not None and lane.get("trace_id"):
            # Per-lane attribution in the member job's OWN trace: the
            # lane span carries that submission's trace_id (override —
            # the ambient context is the lead member's) parented to this
            # group worker's job span.
            tracer.emit(
                "lane",
                t0=jt0,
                dur=time.monotonic() - jt0,
                attrs={
                    "lane": i, "group": manifest.get("group"),
                    "job": lane.get("job"), "spec": args.spec,
                },
                parent_id=job_sid,
                trace_id=lane["trace_id"],
            )

    def lane_chaos(i: int) -> None:
        if not chaos_armed[i]:
            return
        ln = checkers[i]
        chaos = lanes_cfg[i].get("chaos") or {}
        die = chaos.get("die_at_depth")
        freeze = chaos.get("freeze_at_depth")
        if die is not None and ln._depth >= die:
            _lane_trip(chaos)
            os.kill(os.getpid(), signal.SIGKILL)
        if freeze is not None and ln._depth >= freeze:
            _lane_trip(chaos)
            hb = mux._heartbeat if mux is not None else ln._heartbeat
            if hb is not None:
                hb.beat("dispatch", compile=False)
            os.kill(os.getpid(), signal.SIGSTOP)

    if mux is not None:
        while not mux.is_done():
            mux._run_block()
            # Finished lanes land their results BEFORE any sabotage fires:
            # a chaos kill mid-batch must lose only unfinished lanes.
            for i, ln in enumerate(checkers):
                if not written[i] and ln.is_done():
                    write_lane(i)
            for i in range(len(checkers)):
                lane_chaos(i)
            if over_budget():
                return 3
    else:
        for i, ln in enumerate(checkers):
            while not ln.is_done():
                ln._run_block()
                lane_chaos(i)
                if over_budget():
                    return 3
            write_lane(i)
    for i, ln in enumerate(checkers):
        if not written[i]:
            write_lane(i)
    summary = {
        "group": manifest.get("group"),
        "spec": args.spec,
        "engine": "xla-mux" if mux is not None else "xla",
        "mux": mux is not None,
        "lanes": len(checkers),
        "dispatches": (
            len(mux.dispatch_log)
            if mux is not None
            else sum(len(ln.dispatch_log) for ln in checkers)
        ),
        "dispatches_saved": mux._dispatches_saved if mux is not None else 0,
        "seconds": time.monotonic() - t0,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(summary, fh, default=str)
    os.replace(tmp, args.out)
    _end_job_trace(
        tracer, job_sid, attempt_sid, jt0,
        spec=args.spec, engine=summary["engine"],
        group=manifest.get("group"), lanes=len(checkers),
    )
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--spec", required=True)  # service/registry.py grammar
    p.add_argument("--engine", default="xla", choices=("xla", "host"))
    p.add_argument("--platform", default="default")  # "default" | "cpu"
    # Fleet device pinning (ServiceConfig.device_ordinal): run this job's
    # engine on jax.devices()[N] — a fleet's per-device pools land their
    # workers on distinct devices of the mesh. Out-of-range ordinals fall
    # back to the backend default (recorded in the result) rather than
    # failing the job: a fleet restarted on a smaller mesh must still
    # drain its journal.
    p.add_argument("--device", type=int, default=None)
    p.add_argument("--out", required=True)
    p.add_argument("--checkpoint", default=None)  # auto-checkpoint base
    p.add_argument("--metrics", default=None)  # metrics time-series base
    p.add_argument("--resume", default=None)
    p.add_argument("--every", default="1")
    p.add_argument("--keep", type=int, default=3)
    p.add_argument("--block-size", type=int, default=1500)
    p.add_argument("--max-states", type=int, default=None)
    p.add_argument("--max-seconds", type=float, default=None)
    p.add_argument("--chaos-die-at-depth", type=int, default=None)
    p.add_argument("--chaos-freeze-at-depth", type=int, default=None)
    p.add_argument("--chaos-marker", default=None)
    # Multiplexed mode: a lane manifest path (docs/service.md "Batched
    # scheduling"). Per-lane out/checkpoint/metrics/resume/chaos ride in
    # the manifest; --out becomes the group summary.
    p.add_argument("--mux", default=None)
    args = p.parse_args()

    import jax

    if args.engine == "host" or args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from stateright_tpu.backend import configure_compile_cache

    configure_compile_cache()

    device_label = None
    if args.device is not None and args.engine == "xla":
        devices = jax.devices()
        if 0 <= args.device < len(devices):
            jax.config.update("jax_default_device", devices[args.device])
            device_label = str(devices[args.device])

    if args.mux:
        return _mux_main(args, device_label)

    from stateright_tpu.service.registry import resolve

    tracer, job_sid, attempt_sid, jt0 = _job_trace()
    model, caps = resolve(args.spec)
    builder = model.checker()
    if args.max_states:
        builder = builder.target_state_count(args.max_states)

    t0 = time.monotonic()

    def over_budget() -> bool:
        return (
            args.max_seconds is not None
            and time.monotonic() - t0 > args.max_seconds
        )

    # Chaos arming: a marker file makes sabotage exactly-once (the requeued
    # attempt runs clean); no marker means every attempt trips.
    armed = (args.chaos_die_at_depth is not None
             or args.chaos_freeze_at_depth is not None) and (
        args.chaos_marker is None or not os.path.exists(args.chaos_marker)
    )

    def trip() -> None:
        if args.chaos_marker is not None:
            with open(args.chaos_marker, "w") as fh:
                fh.write("tripped\n")

    chaos_flags = (
        args.chaos_die_at_depth is not None
        or args.chaos_freeze_at_depth is not None
    )
    if args.engine == "xla":
        kw = dict(caps)
        if chaos_flags:
            # Chaos runs force one level per dispatch: fine-grained
            # quiescent points so the sabotage depth and the checkpoint
            # cadence line up deterministically. Production jobs keep the
            # engine's fused multi-level dispatch (the core perf
            # mechanism: one host round-trip per up-to-32 levels); checkpoint
            # cadence and budget checks then apply at dispatch-block
            # granularity, as documented.
            kw["levels_per_dispatch"] = 1
        if args.checkpoint:
            kw.update(
                checkpoint_to=args.checkpoint,
                checkpoint_every=args.every,
                checkpoint_keep=args.keep,
            )
        if args.metrics:
            # Per-job metrics time-series (docs/observability.md "Time
            # series"): sampled at quiescent boundaries into the job dir;
            # a requeued attempt appends to the same rotating series.
            kw["metrics_to"] = args.metrics
        if args.resume:
            kw["checkpoint"] = args.resume
        checker = builder.spawn_xla(**kw)
        step = checker._run_block
    else:
        checker = builder.spawn_on_demand(block_size=1)
        checker.run_to_completion()
        step = lambda: checker._run_block(max(args.block_size, 1))  # noqa: E731

    start_depth = checker._depth if args.engine == "xla" else 0

    while not checker.is_done():
        step()
        if args.engine == "xla":
            depth = checker._depth
            if armed and args.chaos_die_at_depth is not None and (
                depth >= args.chaos_die_at_depth
            ):
                trip()
                os.kill(os.getpid(), signal.SIGKILL)
            if armed and args.chaos_freeze_at_depth is not None and (
                depth >= args.chaos_freeze_at_depth
            ):
                trip()
                # A hung dispatch's signature: the engine entered a device
                # dispatch and never came back.
                if checker._heartbeat is not None:
                    checker._heartbeat.beat("dispatch", compile=False)
                os.kill(os.getpid(), signal.SIGSTOP)
        if over_budget():
            return 3  # soft budget exit at a quiescent point

    metrics = checker.metrics()
    recorder = getattr(checker, "_recorder", None)
    if recorder is not None:
        # Final forced row: the series ends with the completed run's
        # exact totals regardless of cadence (dashboards and the
        # OpenMetrics tail read the last row as "current").
        recorder.sample(metrics, kind="engine")
    result = {
        "spec": args.spec,
        "engine": args.engine,
        "platform": jax.default_backend(),
        "device": device_label,
        "device_ordinal": args.device,
        "degraded": args.engine == "host",
        "generated": checker.state_count(),
        "unique": checker.unique_state_count(),
        "max_depth": checker.max_depth(),
        "discoveries": {
            name: [repr(a) for a in path.into_actions()]
            for name, path in sorted(checker.discoveries().items())
        },
        "resumed_from": args.resume,
        "start_depth": start_depth,
        "seconds": time.monotonic() - t0,
        "metrics": metrics,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(result, fh, default=str)
    os.replace(tmp, args.out)
    _end_job_trace(
        tracer, job_sid, attempt_sid, jt0,
        spec=args.spec, engine=args.engine, resumed_from=args.resume,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
