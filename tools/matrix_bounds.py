"""Per-config modeled throughput bounds for the bench matrix shapes.

VERDICT r4 item 4 asks for TPU matrix rows >= 100k gen/s each "or a
documented per-config bound". The matrix configs are deep-narrow: their
state spaces are hundreds of levels of two-digit widths, so a
level-synchronous engine is bound by (levels x per-level fixed cost) no
matter how fast each level runs. This tool records each config's level
schedule (one host run on the device engine), pushes it through the
roofline model (tools/roofline.py), and prints the structural bound:

    bound(fixed) = generated / (levels * fixed + traffic_floor)

for the r3-measured 475 ms fixed cost, the attack-1 target (50 ms), and
the attack-2 target (5 ms). A config whose bound at 5 ms is below 100k
gen/s is *structurally* below the verdict line on this engine — the
honest statement is the bound, not a missed target.

One JSON line per config on stdout. Usage:
  python tools/matrix_bounds.py [--cpu]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    if "--cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    from tools.roofline import model_ceiling

    from stateright_tpu.models.increment_lock import PackedIncrementLock
    from stateright_tpu.models.linearizable_register import PackedAbd
    from stateright_tpu.models.paxos import PackedPaxos
    from stateright_tpu.models.single_copy_register import PackedSingleCopyRegister

    configs = [
        ("linearizable-register (ABD) 2c/2s packed", lambda: PackedAbd(2, 2),
         dict(frontier_capacity=1 << 10, table_capacity=1 << 12)),
        ("paxos 2c/3s packed", lambda: PackedPaxos(2, 3),
         dict(frontier_capacity=1 << 12, table_capacity=1 << 16)),
        ("single-copy-register 3c/1s packed", lambda: PackedSingleCopyRegister(3, 1),
         dict(frontier_capacity=1 << 11, table_capacity=1 << 14)),
        ("increment_lock 3t packed", lambda: PackedIncrementLock(3),
         dict(frontier_capacity=1 << 10, table_capacity=1 << 13)),
    ]
    for name, build, kw in configs:
        try:
            checker = build().checker().spawn_xla(**kw)
            while not checker.is_done():
                checker._run_block()
            detail = {
                "actions": checker._A,
                "state_words": checker._W,
                "table_capacity": checker._table.capacity,
                "levels": [{"sec": 0, "levels": checker.level_log}],
            }
            out = model_ceiling(detail)
            gen = checker.state_count()
            levels = len(checker.level_log)
            traffic = out["modeled_sec"]
            row = {
                "config": name,
                "generated": gen,
                "unique": checker.unique_state_count(),
                "levels": levels,
                "widest_level": max((l["frontier"] for l in checker.level_log), default=0),
                "traffic_floor_sec": traffic,
                "bound_at_475ms": round(gen / (levels * 0.475 + traffic), 1),
                "bound_at_50ms": round(gen / (levels * 0.050 + traffic), 1),
                "bound_at_5ms": round(gen / (levels * 0.005 + traffic), 1),
            }
        except Exception as e:
            row = {"config": name, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
