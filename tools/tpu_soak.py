"""Device-scale soak: full-coverage runs at rm=9/10/11 + paxos 3c/3s.

VERDICT round-4 item 5 / SURVEY §7 hard part 1: prove the visited-set
architecture (delta flushes, table growth, 2^27-row planes in HBM) at
>= 10^8 generated states, with run-to-run count stability and the host
duplicate-key audit as the corruption guard. Extracted from
an old watcher script's heredoc so it can run standalone.

Run under `timeout` — a hung dispatch never returns.
Usage: python tools/tpu_soak.py [--cpu] [--quick]
  --quick runs a single rm=7 soak (CPU smoke / script validation) instead
  of the full rm=9/10/11 ladder.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    if "--cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    print(f"[soak] platform={jax.devices()[0].platform}", flush=True)

    def soak(name, build, runs=2, budget_s=900, audit=True, **kw):
        results = []
        for i in range(runs):
            model = build()
            # Announce BEFORE the first device call: a hung dispatch
            # blocks forever rather than failing, and twice an rm=10
            # soak froze with zero output — the starting line is what
            # localizes the hang to a config + run.
            print(f"[soak] {name} run {i} starting ({kw})", flush=True)
            c = model.checker().spawn_xla(**kw)
            t0 = time.monotonic()
            last_hb = t0
            while not c.is_done() and time.monotonic() - t0 < budget_s:
                c._run_block()
                now = time.monotonic()
                if now - last_hb > 60:
                    print(
                        f"[soak] {name} run {i} heartbeat: "
                        f"gen={c.state_count():,} uniq={c.unique_state_count():,} "
                        f"depth={c.max_depth()} t={now - t0:.0f}s",
                        flush=True,
                    )
                    last_hb = now
            dt = time.monotonic() - t0
            results.append(
                (c.state_count(), c.unique_state_count(), c.max_depth(), c.is_done())
            )
            print(
                f"[soak] {name} run {i}: gen={c.state_count():,} "
                f"uniq={c.unique_state_count():,} depth={c.max_depth()} "
                f"done={c.is_done()} in {dt:.1f}s "
                f"({c.state_count()/max(dt,1e-9):,.0f} gen/s) "
                f"table=2^{c._table.capacity.bit_length()-1}",
                flush=True,
            )
            if audit and i == runs - 1:
                try:
                    from stateright_tpu.audit import audit_table

                    print(f"[soak] {name} audit: {audit_table(c)}", flush=True)
                except Exception as e:
                    print(f"[soak] {name} audit ERRORED: {e}", flush=True)
        # Only completed runs have comparable totals: a budget-truncated
        # run stops at an arbitrary point, so comparing them would read
        # healthy truncation jitter as the corruption signal.
        done_runs = [r for r in results if r[3]]
        if len(done_runs) >= 2:
            stable = len(set(done_runs)) == 1
            print(
                f"[soak] {name}: counts {'STABLE' if stable else 'UNSTABLE'} "
                f"across {len(done_runs)} completed runs",
                flush=True,
            )
        elif not done_runs:
            print(f"[soak] {name}: TRUNCATED (no completed run) — stability n/a", flush=True)

    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    if "--quick" in sys.argv:
        soak(
            "2pc rm=7 (quick)",
            lambda: PackedTwoPhaseSys(7),
            frontier_capacity=1 << 17,
            table_capacity=1 << 19,
        )
        return
    # Unique-state growth is ~5.9x per RM (8,832 @ rm=5 ... 1,745,408 @
    # rm=8): rm=9 ~ 10M uniques, rm=10 ~ 60M. Pre-size tables — every
    # growth step at this scale is a recompile.
    if "--skip-rm9" not in sys.argv:
        soak(
            "2pc rm=9",
            lambda: PackedTwoPhaseSys(9),
            frontier_capacity=1 << 20,
            table_capacity=1 << 24,
        )
    # The delta structure is chip-blocked this round: its compiled program
    # reproducibly faults the TPU runtime ("TPU worker process crashed —
    # kernel fault") at BOTH rm=8 shapes (profile A/B, table 2^22) and
    # rm=10 shapes (this soak, table 2^27), while the same program is
    # exact on CPU — so scale is not the trigger, the program shape is.
    # Pass --delta to retry it; the default soaks the flat sorted
    # structure, which the rm=9 stage just proved at 10^8 states.
    dedup_big = "delta" if "--delta" in sys.argv else "sorted"
    soak(
        "2pc rm=10",
        lambda: PackedTwoPhaseSys(10),
        budget_s=1200,
        frontier_capacity=1 << 21,
        table_capacity=1 << 27,
        dedup=dedup_big,
    )
    # rm=11 (~360M uniques) exceeds full coverage in budget; a bounded run
    # still measures steady-state gen/s at 2^28 table scale. Audit skipped:
    # a partial-coverage readback of 2^28 planes is minutes of transfer.
    soak(
        "2pc rm=11 (bounded)",
        lambda: PackedTwoPhaseSys(11),
        runs=1,
        budget_s=900,
        audit=False,
        frontier_capacity=1 << 22,
        table_capacity=1 << 28,
        dedup=dedup_big,
    )
    from stateright_tpu.models.paxos import PackedPaxos

    soak(
        "paxos 3c/3s",
        lambda: PackedPaxos(3, 3),
        budget_s=1200,
        frontier_capacity=1 << 19,
        table_capacity=1 << 25,
    )

    # LAST, because the pre-redesign delta faulted the TPU runtime and a
    # residual fault must not cost the stages above: the delta structure
    # under its round-5 host-invoked-flush protocol, at rm=8 (vs the 8.7s
    # sorted number) and rm=10 (the regime it exists for).
    if "--no-delta-retry" not in sys.argv:
        soak(
            "2pc rm=8 delta (flush-protocol retry)",
            lambda: PackedTwoPhaseSys(8),
            frontier_capacity=1 << 19,
            table_capacity=1 << 22,
            dedup="delta",
        )
        soak(
            "2pc rm=10 delta (flush-protocol retry)",
            lambda: PackedTwoPhaseSys(10),
            runs=1,
            budget_s=1200,
            frontier_capacity=1 << 21,
            table_capacity=1 << 27,
            dedup="delta",
        )


if __name__ == "__main__":
    main()
