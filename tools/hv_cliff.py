"""Characterize the host-verified sampling cliff at 5 clients.

VERDICT r4 weak #6 / item 7: past ``MAX_PATTERNS_EXACT`` (first hit at 5
clients x 2 ops = 1.68e8 interleavings, single-copy register) the device
serializer runs a SAMPLED one-sided pass — True proves serializability,
False means unknown — and every unknown row costs an exact host
confirmation (``_confirm_hv_candidates``). This tool measures the trade
the ``pattern_limit`` knob controls, on a bounded 5c/1s run:

  flagged        rows the sampled pass could not clear
  flag rate      flagged / generated (the predicate's false-alarm rate —
                 5c/1s reaches full coverage with zero violations, so
                 EVERY flag is a false alarm)
  host share     host confirmation seconds / total seconds

One JSON line per pattern_limit on stdout; progress on stderr. Run under
`timeout`; pattern_limit sweeps small->large so a budget kill keeps the
cheap rows.

Usage: python tools/hv_cliff.py [--cpu] [--target N] [--limits a,b,c]
Defaults: target 30,000 generated states; limits 512,4096,20000.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    args = sys.argv[1:]
    if "--cpu" in args:
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    target = 30_000
    limits = [512, 4_096, 20_000]
    if "--target" in args:
        target = int(args[args.index("--target") + 1])
    if "--limits" in args:
        limits = [int(x) for x in args[args.index("--limits") + 1].split(",")]
    platform = jax.devices()[0].platform
    print(f"[hv_cliff] platform={platform} target={target}", file=sys.stderr, flush=True)

    from stateright_tpu.models.single_copy_register import PackedSingleCopyRegister

    for limit in limits:
        print(f"[hv_cliff] pattern_limit={limit} ...", file=sys.stderr, flush=True)
        try:
            model = PackedSingleCopyRegister(5, 1, pattern_limit=limit)
            checker = (
                model.checker()
                .target_state_count(target)
                .spawn_xla(
                    frontier_capacity=1 << 14,
                    table_capacity=1 << 18,
                    host_verified_cap=1 << 14,
                )
            )
            t0 = time.monotonic()
            while not checker.is_done():
                checker._run_block()
            total = time.monotonic() - t0
            s = checker.hv_stats
            gen = checker.state_count()
            row = {
                "config": "single-copy-register 5c/1s packed (bounded)",
                "platform": platform,
                "pattern_limit": limit,
                "generated": gen,
                "unique": checker.unique_state_count(),
                "depth": checker.max_depth(),
                "total_sec": round(total, 2),
                "flagged": int(s["flagged"]),
                "host_checked": int(s["host_checked"]),
                "cleared": int(s["cleared"]),
                "confirmed": int(s["confirmed"]),
                "host_sec": round(s["host_sec"], 2),
                "flag_rate": round(s["flagged"] / max(gen, 1), 5),
                "host_share": round(s["host_sec"] / max(total, 1e-9), 3),
            }
        except Exception as e:
            row = {"pattern_limit": limit, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
