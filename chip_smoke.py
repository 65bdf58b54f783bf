"""Drive ``spawn_xla`` end to end on one TPU chip and check exact counts.

``python chip_smoke.py`` runs three phases in this one process, after one
pass of each has compiled its programs concurrently (``_prewarm``):

- 2pc: ``PackedTwoPhaseSys(8)`` at the flagship's shapes (frontier 2^19,
  table 2^22), a steady pass with the pinned counts, the duplicate-key
  audit and the model's discoveries;
- paxos: ``PackedPaxos(2, 3)`` (device consistency tester, wide-W gather
  compaction), pinned counts and ``assert_properties()``;
- cli: ``two_phase_commit.main(["check", "5"])``, the user's entry point.

``python chip_smoke.py --chips 4`` runs only 2pc rm=8 on the sharded
engine over a four-chip mesh and checks that the visited set really lives
on all four chips.

Each phase prints one line; the last line is the contract's JSON object.
The ``prewarm`` line carries the compile (first-pass) seconds, the phase
lines their steady seconds. ``peak_bytes`` is the device's peak since the
process started: the pre-warm runs the phases together, so the first
line's peak is theirs jointly.
There is no CPU fallback: without a TPU the script exits non-zero. The
pinned counts are the README's exact-count table.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time

#: Flagship shapes (bench.py's defaults for rm=8 on an accelerator).
FLAGSHIP_RM = 8
FRONTIER_CAPACITY = 1 << 19
TABLE_CAPACITY = 1 << 22

#: Pinned (generated, unique) counts, README "Exact counts".
EXPECTED_2PC = {3: (1_146, 288), 5: (58_146, 8_832), 8: (18_507_778, 1_745_408)}
EXPECTED_PAXOS = {(2, 3): (32_971, 16_668)}

#: What 2pc discovers: both agreements are reachable, consistency holds.
TWO_PC_DISCOVERIES = {"abort agreement", "commit agreement"}


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(phase: str, **fields) -> None:
    print(f"[chip_smoke] {phase}: " + json.dumps(fields), flush=True)


def _check_counts(name: str, checker, expected) -> None:
    got = (checker.state_count(), checker.unique_state_count())
    if got != tuple(expected):
        raise AssertionError(f"{name}: counts {got} != pinned {tuple(expected)}")


def _check_audit(name: str, checker) -> dict:
    from stateright_tpu.audit import audit_table

    audit = audit_table(checker)
    if not audit["ok"]:
        raise AssertionError(f"{name}: visited-set audit failed: {audit}")
    return audit


def _prewarm(passes: dict) -> dict:
    """Run one pass of each check in its own thread and return the seconds
    each took. The TPU compiler takes minutes for one large superstep
    program and keeps few cores busy, so the phases' compiles overlap
    instead of adding up. The first failure is re-raised."""
    import threading

    secs, errors = {}, []

    def run(name, check):
        t0 = time.monotonic()
        try:
            check()
        except BaseException as e:  # re-raised by the calling thread
            errors.append(e)
        secs[name] = time.monotonic() - t0

    threads = [threading.Thread(target=run, args=item) for item in passes.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return secs


def _timed(spawn, warmed: bool):
    """The check's steady pass, after a compile/warm pass unless the model
    is ``warmed`` already (the model caches its compiled programs, so the
    steady pass reuses them). Returns the checker and the seconds taken;
    ``warm_s`` is there only when this call paid the compile."""
    times = {}
    if not warmed:
        t0 = time.monotonic()
        spawn().join()
        times["warm_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    checker = spawn().join()
    times["steady_s"] = time.monotonic() - t0
    return checker, times


def phase_2pc(rm: int, frontier_capacity: int, table_capacity: int, *,
              model=None, expect_accel: bool = True, **spawn_kwargs) -> dict:
    """2pc with ``rm`` resource managers on the single-chip engine
    (``model``: an already-warmed ``PackedTwoPhaseSys(rm)``).
    ``expect_accel`` asserts that dedup and compaction resolved to the
    accelerator choices; a CPU test steers them with ``spawn_kwargs``."""
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    checker, times = _timed(
        lambda: (model or PackedTwoPhaseSys(rm)).checker().spawn_xla(
            frontier_capacity=frontier_capacity,
            table_capacity=table_capacity,
            **spawn_kwargs,
        ),
        warmed=model is not None,
    )
    name = f"2pc rm={rm}"
    if expect_accel and (checker._dedup, checker._soa) != ("sorted", True):
        raise AssertionError(
            f"{name}: resolved dedup={checker._dedup!r} soa={checker._soa}, "
            "expected the accelerator's sorted, plane-major engine"
        )
    _check_counts(name, checker, EXPECTED_2PC[rm])
    audit = _check_audit(name, checker)
    checker.assert_properties()
    found = set(checker.discoveries())
    if found != TWO_PC_DISCOVERIES:
        raise AssertionError(f"{name}: discoveries {sorted(found)}")
    return {
        "generated": checker.state_count(),
        "unique": checker.unique_state_count(),
        "dedup": checker._dedup,
        "compaction": checker._compaction,
        **times,
        "dispatches": len(checker.dispatch_log),
        "audit_entries": audit["entries"],
    }


def _spawn_paxos(model, **spawn_kwargs):
    return model.checker().spawn_xla(
        frontier_capacity=1 << 12,
        table_capacity=1 << 16,
        host_verified_cap=4096,
        **spawn_kwargs,
    )


def phase_paxos(clients: int, servers: int, *, model=None,
                expect_accel: bool = True, **spawn_kwargs) -> dict:
    """Paxos on the single-chip engine: the device consistency tester and,
    on an accelerator, the wide-W gather compaction (``model``: an
    already-warmed ``PackedPaxos(clients, servers)``)."""
    from stateright_tpu.models.paxos import PackedPaxos

    warmed = model is not None
    model = model or PackedPaxos(clients, servers)
    checker, times = _timed(
        lambda: _spawn_paxos(model, **spawn_kwargs), warmed=warmed
    )
    name = f"paxos {clients}c/{servers}s"
    if expect_accel and (checker._dedup, checker._soa, checker._compaction) != (
        "sorted", True, "gather"
    ):
        raise AssertionError(
            f"{name}: resolved dedup={checker._dedup!r} "
            f"compaction={checker._compaction!r}, expected sorted + gather"
        )
    _check_counts(name, checker, EXPECTED_PAXOS[(clients, servers)])
    _check_audit(name, checker)
    checker.assert_properties()
    return {
        "generated": checker.state_count(),
        "unique": checker.unique_state_count(),
        "dedup": checker._dedup,
        "compaction": checker._compaction,
        **times,
    }


def phase_cli(rm: int) -> dict:
    """``python -m stateright_tpu.models.two_phase_commit check <rm>`` in
    this process; its reporter's summary must carry the pinned counts."""
    from stateright_tpu.models import two_phase_commit

    out = io.StringIO()
    t0 = time.monotonic()
    with contextlib.redirect_stdout(out):
        two_phase_commit.main(["check", str(rm)])
    elapsed = time.monotonic() - t0
    text = out.getvalue()
    sys.stdout.write(text)
    gen, unique = EXPECTED_2PC[rm]
    if f"Done. states={gen}, unique={unique}," not in text:
        raise AssertionError(f"cli check {rm}: no 'Done. states={gen}, "
                             f"unique={unique}' line in its output")
    return {"generated": gen, "unique": unique, "wall_s": elapsed}


def phase_sharded(n_chips: int, rm: int, frontier_capacity: int,
                  table_capacity: int, **spawn_kwargs) -> dict:
    """2pc on the fingerprint-sharded engine over ``n_chips`` devices: the
    pinned one-chip counts, and the visited set spread over every chip.
    One pass only: the sharded engine keeps its compiled programs per
    checker, so a second checker compiles them all again unless the
    persistent cache serves them (minutes on four chips)."""
    import jax

    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys
    from stateright_tpu.parallel import default_mesh

    devices = jax.devices()[:n_chips]
    if len({d.id for d in devices}) != n_chips:
        raise AssertionError(f"need {n_chips} distinct devices: {devices}")
    t0 = time.monotonic()
    checker = PackedTwoPhaseSys(rm).checker().spawn_xla(
        mesh=default_mesh(n_chips),
        frontier_capacity=frontier_capacity,
        table_capacity=table_capacity,
        **spawn_kwargs,
    ).join()
    warm = time.monotonic() - t0
    name = f"sharded 2pc rm={rm} over {n_chips}"
    _check_counts(name, checker, EXPECTED_2PC[rm])
    _check_audit(name, checker)
    checker.assert_properties()
    for plane in checker._table:
        spanned = plane.sharding.device_set
        if len(spanned) != n_chips:
            raise AssertionError(f"{name}: a table plane spans {spanned}")
    # The CPU reports no memory stats; a chip must hold bytes on every
    # device, or the shards all landed on one of them.
    stats = [d.memory_stats() for d in devices]
    in_use = [s.get("bytes_in_use", 0) if s else None for s in stats]
    if any(b is not None and b <= 0 for b in in_use):
        raise AssertionError(f"{name}: bytes_in_use per device {in_use}")
    return {
        "generated": checker.state_count(),
        "unique": checker.unique_state_count(),
        "dedup": checker._dedup,
        "warm_s": warm,
        "bytes_in_use": in_use,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only 2pc rm=8 on the sharded engine over 4 chips")
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX selected {devices[0].platform!r}; "
              "this script never falls back to the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from stateright_tpu.backend import configure_compile_cache

    _report("start", cache=configure_compile_cache(),
            kind=devices[0].device_kind, count=len(devices))
    if args.chips == 4:
        stats = phase_sharded(4, FLAGSHIP_RM, FRONTIER_CAPACITY, TABLE_CAPACITY)
        _report("sharded", **stats, peak_bytes=[_peak_bytes(d) for d in devices[:4]])
    else:
        from stateright_tpu.models.paxos import PackedPaxos
        from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

        dev = devices[0]
        flagship, paxos = PackedTwoPhaseSys(FLAGSHIP_RM), PackedPaxos(2, 3)
        # The CLI builds its own model: its pass here fills the
        # persistent compile cache that the CLI's programs then load.
        prewarm = _prewarm({
            "2pc": lambda: flagship.checker().spawn_xla(
                frontier_capacity=FRONTIER_CAPACITY,
                table_capacity=TABLE_CAPACITY,
            ).join(),
            "paxos": lambda: _spawn_paxos(paxos).join(),
            "cli": lambda: PackedTwoPhaseSys(5).checker().spawn_xla().join(),
        })
        _report("prewarm", **{f"{k}_s": v for k, v in prewarm.items()},
                peak_bytes=_peak_bytes(dev))
        stats = phase_2pc(FLAGSHIP_RM, FRONTIER_CAPACITY, TABLE_CAPACITY,
                          model=flagship)
        _report("2pc", **stats, peak_bytes=_peak_bytes(dev))
        stats = phase_paxos(2, 3, model=paxos)
        _report("paxos", **stats, peak_bytes=_peak_bytes(dev))
        stats = phase_cli(5)
        _report("cli", **stats, peak_bytes=_peak_bytes(dev))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
