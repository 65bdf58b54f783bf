"""Microbenchmark the device-engine cost model on the current backend.

Separates the four costs that determine checker throughput so tuning is
evidence-driven rather than guesswork:

1. dispatch RTT — a trivial jit call (the floor for any per-level host sync;
   large when the host is far from the chip),
2. superstep compile time per bucket size,
3. steady-state superstep wall time per bucket (states/sec at that width),
4. hash-set insert cost vs batch size (the scatter-heavy op most likely to
   be TPU-hostile).

Usage: python tools/microbench.py [rm] [--cpu]

``--cpu`` pins the CPU backend at config level BEFORE first backend use;
without it the script runs on the backend JAX selects.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timeit(fn, *args, n=5):
    fn(*args)  # compile / warm
    t0 = time.monotonic()
    for _ in range(n):
        out = fn(*args)
    import jax

    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n


def main() -> None:
    import jax

    if "--cpu" in sys.argv:
        sys.argv.remove("--cpu")
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    rm = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    print(f"backend={jax.default_backend()} device={jax.devices()[0]}", flush=True)

    # 1. dispatch RTT
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.uint32)
    rtt = timeit(lambda v: f(v), x, n=20)
    print(f"dispatch RTT (trivial jit): {rtt*1e3:.2f} ms", flush=True)

    # 2+3. superstep compile + steady time per bucket
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    model = PackedTwoPhaseSys(rm)
    c = model.checker().spawn_xla(
        frontier_capacity=1 << 17, table_capacity=1 << 22, levels_per_dispatch=1
    )
    from stateright_tpu.ops import fphash, hashset

    for pow2 in (10, 12, 14, 16, 17):
        cap = 1 << pow2
        t0 = time.monotonic()
        step = c._superstep_for(cap)
        frontier = jnp.zeros((cap, model.state_words), jnp.uint32)
        ebits = jnp.zeros((cap,), jnp.uint32)
        out = step(
            frontier, ebits, jnp.int32(cap), c._table, c._disc_found, c._disc_fp
        )
        jax.block_until_ready(out)
        compile_s = time.monotonic() - t0
        dt = timeit(
            lambda: step(
                frontier, ebits, jnp.int32(cap), c._table, c._disc_found, c._disc_fp
            ),
            n=5,
        )
        cands = cap * model.max_actions
        print(
            f"superstep bucket 2^{pow2}: compile {compile_s:6.1f}s  steady "
            f"{dt*1e3:8.1f} ms  ({cands/dt/1e6:8.2f} M cand/s)",
            flush=True,
        )

    # 4. insert cost vs batch — both visited-set structures at the same
    #    shapes (the hash/scatter vs sort-merge design decision,
    #    BASELINE.md cost model).
    from stateright_tpu.ops import sortedset

    table = hashset.make(1 << 22, jnp)
    n_occ = (3 << 22) // 8  # sorted set at its 3/4-load growth ceiling's half
    rng0 = np.random.default_rng(9)
    keys = np.sort(rng0.integers(1, 2**63, n_occ, dtype=np.uint64))
    stab = sortedset.from_entries(
        (keys >> 32).astype(np.uint32), (keys & 0xFFFFFFFF).astype(np.uint32),
        np.zeros(n_occ, np.uint32), np.zeros(n_occ, np.uint32), 1 << 22, jnp,
    )
    ins = jax.jit(hashset.insert, static_argnames="max_probes")
    sins = jax.jit(sortedset.insert)
    for pow2 in (14, 17, 20, 22):
        m = 1 << pow2
        rng = np.random.default_rng(0)
        hi = jnp.asarray(rng.integers(1, 2**32, m, dtype=np.uint32))
        lo = jnp.asarray(rng.integers(1, 2**32, m, dtype=np.uint32))
        act = jnp.ones((m,), jnp.bool_)
        dt = timeit(lambda: ins(table, hi, lo, hi, lo, act), n=3)
        ds = timeit(lambda: sins(stab, hi, lo, hi, lo, act), n=3)
        print(
            f"insert m=2^{pow2}: hash {dt*1e3:8.1f} ms ({m/dt/1e6:7.2f} M/s)  "
            f"sorted {ds*1e3:8.1f} ms ({m/ds/1e6:7.2f} M/s)",
            flush=True,
        )

    # 5. cost model for the sort-based dedup alternative: a two-key sort of
    #    the candidate batch (in-batch dedup + visited-merge building block)
    #    and a pure scatter vs gather-compaction comparison at batch size.
    def sort2(hi, lo):
        return jax.lax.sort((hi, lo), num_keys=2)

    sort2j = jax.jit(sort2)
    for pow2 in (17, 20, 22, 24):
        m = 1 << pow2
        rng = np.random.default_rng(1)
        hi = jnp.asarray(rng.integers(1, 2**32, m, dtype=np.uint32))
        lo = jnp.asarray(rng.integers(1, 2**32, m, dtype=np.uint32))
        dt = timeit(lambda: sort2j(hi, lo), n=3)
        print(
            f"two-key sort m=2^{pow2}: {dt*1e3:8.1f} ms  ({m/dt/1e6:8.2f} M keys/s)",
            flush=True,
        )

    # 6. end-to-end amortization: warm full-coverage checks with the level
    #    loop on device (fused, default) vs one level per dispatch — the
    #    direct measurement of dispatch-latency amortization.
    for levels in (32, 1):
        kw = dict(
            frontier_capacity=1 << 17,
            table_capacity=1 << 21,
            levels_per_dispatch=levels,
        )
        model2 = PackedTwoPhaseSys(rm)
        model2.checker().spawn_xla(**kw).join()  # warm/compile
        t0 = time.monotonic()
        c2 = model2.checker().spawn_xla(**kw).join()
        dt = time.monotonic() - t0
        print(
            f"full check rm={rm} levels_per_dispatch={levels}: {dt:7.2f}s "
            f"({c2.state_count()/dt/1e3:8.1f} k gen/s)",
            flush=True,
        )

    W = 4
    for pow2 in (17, 20):
        m = 1 << pow2
        rng = np.random.default_rng(2)
        rows = jnp.asarray(rng.integers(0, 2**32, (m, W), dtype=np.uint32))
        keep = jnp.asarray(rng.integers(0, 2, m, dtype=np.uint32).astype(bool))

        def compact_scatter(rows, keep):
            pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
            idx = jnp.where(keep, pos, m)
            return jnp.zeros((m, W), jnp.uint32).at[idx].set(rows, mode="drop")

        def compact_gather(rows, keep):
            order = jnp.argsort(~keep, stable=True)
            return rows[order]

        ds = timeit(jax.jit(compact_scatter), rows, keep, n=3)
        dg = timeit(jax.jit(compact_gather), rows, keep, n=3)
        print(
            f"compaction m=2^{pow2} W={W}: scatter {ds*1e3:8.1f} ms vs "
            f"sort+gather {dg*1e3:8.1f} ms",
            flush=True,
        )


if __name__ == "__main__":
    main()
