"""On-chip paxos A/B: sorted vs hash visited set, count-checked + audited.

VERDICT round-4 item 2: the round-3 on-chip paxos drift (17,198 unique vs
the pinned 16,668, `/root/reference/examples/paxos.rs:321,345`) happened
under the retired round-2 hash engine; the sorted-default engine has never
run paxos on the chip. This tool closes the question decisively:

  - run paxos 2c/3s packed under dedup=sorted (the accelerator default)
  - run it again under dedup=hash (the round-2 structure, the suspect)
  - for each: check the pinned counts (32,971 generated / 16,668 unique)
    and run the host-side duplicate-key audit of the visited planes
    (stateright_tpu/audit.py — duplicate keys prove insert-admission
    corruption; clean-but-short proves lost entries).

One JSON line per run on stdout; progress on stderr. Exit status: 0 when
every run is count-exact with a clean audit, 2 when any run drifted or
audited dirty (the drift IS the signal — it must not read as success),
1 on harness errors. Run under `timeout` (a hung dispatch never returns).

Usage: python tools/paxos_ab.py [--cpu] [--deep]
  --deep additionally runs 2pc rm=6 under hash (the other shape class:
  wide words + a mid-run table growth, the round-3 drift signature).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PINNED = {
    "paxos 2c/3s": (32_971, 16_668),
    "2pc rm=6": (402_306, 50_816),
}


def run_one(name: str, build, dedup: str, **spawn_kwargs) -> dict:
    from stateright_tpu.audit import audit_table

    model = build()
    checker = model.checker().spawn_xla(dedup=dedup, **spawn_kwargs)
    t0 = time.monotonic()
    while not checker.is_done():
        checker._run_block()
    warm = time.monotonic() - t0
    # Second, measured pass on the same model (compiled supersteps cached).
    checker = model.checker().spawn_xla(dedup=dedup, **spawn_kwargs)
    t0 = time.monotonic()
    while not checker.is_done():
        checker._run_block()
    sec = time.monotonic() - t0
    gen, uniq = checker.state_count(), checker.unique_state_count()
    exp = PINNED[name]
    row = {
        "config": name,
        "dedup": dedup,
        "generated": gen,
        "unique": uniq,
        "pinned": list(exp),
        "count_ok": (gen, uniq) == exp,
        "warm_sec": round(warm, 2),
        "measured_sec": round(sec, 3),
        "states_per_sec": round(gen / max(sec, 1e-9), 1),
    }
    try:
        row["audit"] = audit_table(checker)
    except Exception as e:  # diagnostic path must not kill the A/B
        row["audit"] = {"error": f"{type(e).__name__}: {e}"}
    # Per-level telemetry: on a drift, diffing this against the CPU run of
    # the same job pinpoints the first divergent BFS level (and hence the
    # bucket shape whose program is suspect).
    row["levels"] = checker.level_log
    return row


def main() -> None:
    import jax

    if "--cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    platform = jax.devices()[0].platform
    print(f"[paxos_ab] platform={platform}", file=sys.stderr, flush=True)

    from stateright_tpu.models.paxos import PackedPaxos

    jobs = [
        # Ladder is explicit in every job: the round-5 on-chip matrix saw a
        # DEFLATED paxos count (19,024/9,546 — lost states) under the
        # default "jump" ladder while the ramp-pinned flagship was exact in
        # the same chip run, so jump-vs-ramp is itself a variable
        # under test here, not a nuisance parameter.
        ("paxos 2c/3s", lambda: PackedPaxos(2, 3), "sorted",
         dict(frontier_capacity=1 << 12, table_capacity=1 << 16,
              ladder="jump")),
        ("paxos 2c/3s", lambda: PackedPaxos(2, 3), "sorted",
         dict(frontier_capacity=1 << 12, table_capacity=1 << 16,
              ladder="ramp")),
        ("paxos 2c/3s", lambda: PackedPaxos(2, 3), "hash",
         # 2^17 at the hash 1/4-load rule avoids a mid-run growth for
         # 16,668 uniques; a SECOND hash run below crosses growth on
         # purpose (the round-3 drift fired on a growth-crossing run).
         dict(frontier_capacity=1 << 12, table_capacity=1 << 17,
              ladder="ramp")),
        ("paxos 2c/3s", lambda: PackedPaxos(2, 3), "hash",
         dict(frontier_capacity=1 << 12, table_capacity=1 << 14,
              ladder="ramp")),
    ]
    if "--deep" in sys.argv:
        from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

        jobs.append(
            ("2pc rm=6", lambda: PackedTwoPhaseSys(6), "hash",
             dict(frontier_capacity=1 << 15, table_capacity=1 << 17))
        )
    clean = True
    for name, build, dedup, kw in jobs:
        print(f"[paxos_ab] {name} dedup={dedup} {kw} ...", file=sys.stderr, flush=True)
        try:
            row = run_one(name, build, dedup, **kw)
            if not (row["count_ok"] and row["audit"].get("ok", False)):
                clean = False
        except Exception as e:
            row = {"config": name, "dedup": dedup,
                   "error": f"{type(e).__name__}: {e}"}
            clean = False
        row["platform"] = platform
        print(json.dumps(row), flush=True)
    if not clean:
        sys.exit(2)


if __name__ == "__main__":
    main()
