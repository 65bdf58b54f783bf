"""Sort-lowering dtype A/B: would 64-bit key packing pay on this backend?

BASELINE.md "Next attacks" #3: the engine's dominant per-level ops are
multi-operand ``lax.sort`` calls over u32 planes — the insert's merge
sort is (key_hi, key_lo, ticket, val_hi, val_lo) with num_keys=3, and
the grid compaction is (key, state_word x W) with num_keys=1. If XLA
sorts one u64 operand materially faster than two u32 operands, packing
(hi, lo) -> u64 halves the operand count of the hot sorts; if it
doesn't (a u64 lane is the same 8 bytes through the permutation
network), the attack is dead and the engine keeps its u32 planes.

This tool measures exactly that trade, including the pack/unpack
shifts the engine would have to add. Timings are HOST-READBACK-GATED:
on an earlier chip setup ``block_until_ready`` returned early for small
standalone programs, so
every timed loop ends with an ``np.asarray`` of a slice of the final
output — a real device-to-host copy that cannot complete before the
producing computation does.

Usage: python tools/sortbench.py [log2_m] [--cpu]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    import jax

    if "--cpu" in sys.argv:
        sys.argv.remove("--cpu")
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    # x64 must be on before first backend use so u64 lanes exist at all.
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    log2_m = int(sys.argv[1]) if len(sys.argv) > 1 else 23
    m = 1 << log2_m
    print(
        f"backend={jax.default_backend()} m=2^{log2_m} "
        f"(merge-sort shape of a 2^{log2_m - 1} table + 2^{log2_m - 1} cand)",
        flush=True,
    )

    rng = np.random.default_rng(7)
    hi = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    lo = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    vh = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    vl = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    ticket = jnp.arange(m, dtype=jnp.int32)

    def timed(name, fn, *args, n=3):
        fn(*args)  # compile + warm
        t0 = time.monotonic()
        out = None
        for _ in range(n):
            out = fn(*args)
        # Host readback gates the clock (see module docstring).
        first = out[0] if isinstance(out, (tuple, list)) else out
        np.asarray(first[:8])
        dt = (time.monotonic() - t0) / n
        print(f"  {name:<46} {dt * 1e3:9.2f} ms", flush=True)
        return dt

    # --- the insert merge-sort shape -----------------------------------
    print("insert merge sort (2-lane key + ticket + 2-lane value):", flush=True)

    @jax.jit
    def sort_u32(hi, lo, ticket, vh, vl):
        return jax.lax.sort((hi, lo, ticket, vh, vl), num_keys=3)

    t_u32 = timed("u32 5-operand num_keys=3 (shipping)", sort_u32, hi, lo, ticket, vh, vl)

    @jax.jit
    def sort_u64(hi, lo, ticket, vh, vl):
        # Includes the pack/unpack the engine would pay.
        k64 = (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
        v64 = (vh.astype(jnp.uint64) << 32) | vl.astype(jnp.uint64)
        sk, st, sv = jax.lax.sort((k64, ticket, v64), num_keys=1)
        return (
            (sk >> 32).astype(jnp.uint32),
            sk.astype(jnp.uint32),
            st,
            (sv >> 32).astype(jnp.uint32),
            sv.astype(jnp.uint32),
        )

    t_u64 = timed("u64 3-operand num_keys=1 (packed keys+values)", sort_u64, hi, lo, ticket, vh, vl)

    @jax.jit
    def sort_u64_key_only(hi, lo, ticket, vh, vl):
        k64 = (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
        sk, st, svh, svl = jax.lax.sort((k64, ticket, vh, vl), num_keys=1)
        return (sk >> 32).astype(jnp.uint32), sk.astype(jnp.uint32), st, svh, svl

    timed("u64 key, u32 values 4-operand", sort_u64_key_only, hi, lo, ticket, vh, vl)

    @jax.jit
    def sort_stable2(hi, lo, ticket, vh, vl):
        # Ticket demoted from key to payload via stability: inputs are in
        # ticket order, so a stable 2-key sort elects the same winners.
        return jax.lax.sort((hi, lo, ticket, vh, vl), num_keys=2, is_stable=True)

    timed("u32 5-operand num_keys=2 stable (ticket demoted)", sort_stable2, hi, lo, ticket, vh, vl)

    # --- single-key payload movement (compaction-sort shape) -----------
    print("compaction sort (1 i32 key + W payload lanes):", flush=True)
    key = jnp.asarray(rng.integers(0, 2, m, dtype=np.int32))
    W = 5
    planes = [
        jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32)) for _ in range(W)
    ]

    @jax.jit
    def comp_u32(key, *planes):
        return jax.lax.sort((key, *planes), num_keys=1, is_stable=True)

    t_c32 = timed(f"i32 key + {W} u32 payload (shipping)", comp_u32, key, *planes)

    @jax.jit
    def comp_u64(key, *planes):
        # Pair adjacent planes into u64 payloads (one leftover u32 lane).
        packed = [
            (planes[i].astype(jnp.uint64) << 32) | planes[i + 1].astype(jnp.uint64)
            for i in range(0, W - 1, 2)
        ]
        rest = list(planes[W - W % 2 :])
        out = jax.lax.sort((key, *packed, *rest), num_keys=1, is_stable=True)
        unpacked = []
        for p in out[1 : 1 + len(packed)]:
            unpacked.append((p >> 32).astype(jnp.uint32))
            unpacked.append(p.astype(jnp.uint32))
        return (out[0], *unpacked, *out[1 + len(packed) :])

    t_c64 = timed(f"i32 key + {(W + 1) // 2} u64-paired payload", comp_u64, key, *planes)

    print(
        f"verdict: merge u64/u32 = {t_u64 / t_u32:.2f}x, "
        f"compaction paired/u32 = {t_c64 / t_c32:.2f}x "
        f"(<1 means packing wins)",
        flush=True,
    )


if __name__ == "__main__":
    main()
