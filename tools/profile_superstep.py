"""Per-stage on-chip profile of the sorted-dedup superstep at real shapes.

The committed cost model (BASELINE.md) was measured against the round-2
hash structure; after the sort-merge visited set landed the bottleneck
moved and the stage accounting must be re-measured on hardware.  This
tool times, as separate jits at the rm=8 primary-bench shapes:

  expand     vmap(packed_step) over the frontier bucket
  fingerprint  two-lane murmur over the candidate buffer
  compact    gather-based stream compaction of the F*A grid
  insert     sortedset.insert (the 5-plane 3-key sort + route-back)
  frontier   gather compaction of survivors into the next frontier
  superstep  the engine's real fused-per-level program (sum of the above)
  level-loop the fused 32-level dispatch, from the real checker

plus the same full-coverage measured pass bench.py runs, with per-level
wall time from one-level dispatches.

Usage: python tools/profile_superstep.py [rm] [--cpu]
Run under `timeout` — a hung dispatch never returns.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def timeit(fn, *args, n=5):
    import jax

    jax.block_until_ready(fn(*args))  # compile / warm
    t0 = time.monotonic()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n


def main() -> None:
    import jax

    if "--cpu" in sys.argv:
        sys.argv.remove("--cpu")
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys
    from stateright_tpu.ops import fphash, sortedset

    rm = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    print(f"backend={jax.default_backend()} rm={rm}", flush=True)

    model = PackedTwoPhaseSys(rm)
    W, A = model.state_words, model.max_actions

    # Real rm=8 shapes: the big levels run at the 2^18/2^19 buckets with a
    # 2^22-capacity sorted table.
    f_cap = 1 << 18
    table_cap = 1 << 22
    cand_cap = max(1024, 1 << (f_cap * A // 4 - 1).bit_length())
    cand_cap = min(cand_cap, 1 << (f_cap * A - 1).bit_length())
    print(f"W={W} A={A} f_cap=2^{f_cap.bit_length()-1} cand_cap=2^{cand_cap.bit_length()-1}", flush=True)

    rng = np.random.default_rng(0)
    frontier = jnp.asarray(rng.integers(0, 2**32, (f_cap, W), dtype=np.uint32))
    mask_grid = jnp.asarray(rng.integers(0, 4, f_cap * A, dtype=np.uint32) == 0)

    # --- expand ---------------------------------------------------------
    expand = jax.jit(lambda f: jax.vmap(model.packed_step)(f))
    dt = timeit(lambda: expand(frontier))
    print(f"expand       [2^{f_cap.bit_length()-1} x A]: {dt*1e3:8.1f} ms ({f_cap*A/dt/1e6:8.1f} M cand/s)", flush=True)

    # --- fingerprint ----------------------------------------------------
    cand_rows = jnp.asarray(rng.integers(0, 2**32, (cand_cap, W), dtype=np.uint32))
    fp = jax.jit(lambda r: fphash.fingerprint_words(r, jnp))
    dt = timeit(lambda: fp(cand_rows))
    print(f"fingerprint  [2^{cand_cap.bit_length()-1}]: {dt*1e3:8.1f} ms ({cand_cap/dt/1e6:8.1f} M fp/s)", flush=True)

    # --- candidate compaction (grid -> cand buffer; planes form) --------
    gplanes = jnp.asarray(rng.integers(0, 2**32, (W, f_cap * A), dtype=np.uint32))
    par = jnp.asarray(rng.integers(0, 2**32, f_cap * A, dtype=np.uint32))

    def compact_gather(mask, gp, par):
        order = jnp.argsort(~mask, stable=True)[:cand_cap]
        sm = mask[order]
        rows = jnp.where(sm[None, :], gp[:, order], 0)
        p = jnp.where(sm, par[order], 0)
        return rows, p, jnp.sum(mask, dtype=jnp.int32)

    compact_j = jax.jit(compact_gather)
    dt = timeit(compact_j, mask_grid, gplanes, par, n=3)
    print(f"compact grid [2^{(f_cap*A-1).bit_length()}]: {dt*1e3:8.1f} ms", flush=True)

    # --- sortedset insert at load --------------------------------------
    n_occ = (table_cap * 3) // 8
    keys = rng.integers(1, 2**63, table_cap, dtype=np.uint64)
    keys[n_occ:] = 0
    keys[:n_occ] = np.sort(keys[:n_occ])
    ss = sortedset.SortedSet(
        jnp.asarray((keys >> 32).astype(np.uint32)),
        jnp.asarray((keys & 0xFFFFFFFF).astype(np.uint32)),
        jnp.asarray((keys >> 32).astype(np.uint32)),
        jnp.asarray((keys & 0xFFFFFFFF).astype(np.uint32)),
        jnp.asarray(n_occ, jnp.int32),
    )
    chi = jnp.asarray(rng.integers(1, 2**32, cand_cap, dtype=np.uint32))
    clo = jnp.asarray(rng.integers(1, 2**32, cand_cap, dtype=np.uint32))
    act = jnp.asarray(rng.integers(0, 2, cand_cap, dtype=np.uint32).astype(bool))
    ins = jax.jit(sortedset.insert)
    dt = timeit(lambda: ins(ss, chi, clo, chi, clo, act))
    print(f"sorted insert[tab 2^{table_cap.bit_length()-1} + 2^{cand_cap.bit_length()-1}]: {dt*1e3:8.1f} ms", flush=True)

    # breakdown: the insert's component sorts at its [cap + m] shape
    kh = jnp.concatenate([ss.key_hi, chi])
    kl = jnp.concatenate([ss.key_lo, clo])
    tick = jnp.arange(table_cap + cand_cap, dtype=jnp.int32)
    sort3 = jax.jit(lambda a, b, t: jax.lax.sort((a, b, t), num_keys=3))
    dt = timeit(sort3, kh, kl, tick, n=3)
    print(f"  3-op 3-key sort [2^{(table_cap+cand_cap-1).bit_length()}]: {dt*1e3:8.1f} ms", flush=True)
    sort5 = jax.jit(lambda a, b, t, c, d: jax.lax.sort((a, b, t, c, d), num_keys=3))
    dt = timeit(sort5, kh, kl, tick, kh, kl, n=3)
    print(f"  5-op 3-key sort [2^{(table_cap+cand_cap-1).bit_length()}]: {dt*1e3:8.1f} ms", flush=True)
    keep = jnp.asarray(rng.integers(0, 2, table_cap + cand_cap, dtype=np.uint32).astype(bool))
    argc = jax.jit(lambda k: jnp.argsort(~k, stable=True)[:table_cap])
    dt = timeit(argc, keep, n=3)
    print(f"  argsort compaction [2^{(table_cap+cand_cap-1).bit_length()}]: {dt*1e3:8.1f} ms", flush=True)

    # --- the engine's real superstep at this bucket ---------------------
    c = model.checker().spawn_xla(
        frontier_capacity=1 << 19, table_capacity=table_cap, levels_per_dispatch=1,
        dedup="sorted",
    )
    step = c._superstep_for(f_cap)
    ebits = jnp.zeros((f_cap,), jnp.uint32)
    dt = timeit(lambda: step(frontier, ebits, jnp.int32(f_cap), ss, c._disc_found, c._disc_fp), n=3)
    print(f"real superstep [bucket 2^{f_cap.bit_length()-1}]: {dt*1e3:8.1f} ms ({f_cap*A/dt/1e6:8.1f} M grid-cand/s)", flush=True)

    # --- full measured pass, one level per dispatch, per-level times ----
    for lpd in (32, 1):
        m2 = PackedTwoPhaseSys(rm)
        kw = dict(frontier_capacity=1 << 19, table_capacity=table_cap,
                  levels_per_dispatch=lpd, dedup="sorted")
        t0 = time.monotonic()
        m2.checker().spawn_xla(**kw).join()
        warm = time.monotonic() - t0
        ck = m2.checker().spawn_xla(**kw)
        t0 = time.monotonic()
        lvl_times = []
        while not ck.is_done():
            t1 = time.monotonic()
            ck._run_block()
            lvl_times.append(time.monotonic() - t1)
        dt = time.monotonic() - t0
        print(f"full check lpd={lpd}: warm {warm:6.1f}s measured {dt:6.2f}s "
              f"({ck.state_count()/dt/1e6:6.2f} M gen/s; {ck.state_count():,} gen "
              f"{ck.unique_state_count():,} uniq depth {ck.max_depth()})", flush=True)
        if lpd != 1:
            # Bucket choices incl. tail shrink-exits: (run_cap, committed).
            print(f"  dispatches: {ck.dispatch_log}", flush=True)
        if lpd == 1:
            for lv, t in zip(ck.level_log, lvl_times):
                print(f"  depth {lv['depth']:3d} frontier {lv['frontier']:9,} gen {lv['generated']:9,} uniq {lv['unique']:9,}  {t*1e3:8.1f} ms", flush=True)

    # --- A/B: gather-family vs sort-family lowerings, end to end --------
    # (insert-values + is_new routing via STPU_SORTEDSET_VALUES, planes
    # compaction via spawn_xla(compaction=); fresh model instances so the
    # in-process superstep cache cannot mix lowerings.)
    # Decisive rows FIRST — chip calls are budgeted. Row 2 (the
    # pallas compaction, O(n) stream vs n log^2 n sort) is the defaults
    # decision; the mixed gather/sort families re-confirm the round-5
    # 2.3x split. EVERY delta row runs LAST: the delta structure
    # reproducibly faults the TPU runtime (registry #4, still open
    # post-redesign), and a fault poisons the process's device state —
    # once one row dies with a runtime error, the remaining rows are
    # unmeasurable and the loop bails with what it banked.
    for dedup, values_via, comp in (
        ("sorted", "sort", "sort"),
        ("sorted", "sort", "pallas"),
        ("sorted", "sort", "gather"),
        ("sorted", "gather", "sort"),
        ("sorted", "gather", "gather"),
        ("delta", "sort", "sort"),
        ("delta", "sort", "pallas"),
        ("delta", "gather", "sort"),
        ("delta", "gather", "gather"),
    ):
        sortedset.VALUES_VIA = values_via
        m3 = PackedTwoPhaseSys(rm)
        kw = dict(frontier_capacity=1 << 19, table_capacity=table_cap,
                  dedup=dedup, compaction=comp)
        try:
            t0 = time.monotonic()
            m3.checker().spawn_xla(**kw).join()
            warm = time.monotonic() - t0
            t0 = time.monotonic()
            ck = m3.checker().spawn_xla(**kw).join()
            dt = time.monotonic() - t0
            print(f"A/B dedup={dedup} values={values_via} compaction={comp}: "
                  f"warm {warm:6.1f}s measured {dt:6.2f}s "
                  f"({ck.state_count()/dt/1e6:6.2f} M gen/s)", flush=True)
        except Exception as e:
            import jax.errors
            print(f"A/B dedup={dedup} values={values_via} compaction={comp}: "
                  f"FAILED {type(e).__name__}: {str(e)[:300]}", flush=True)
            # Only an execution fault poisons device state; compile
            # errors also raise JaxRuntimeError and stay row-local.
            if isinstance(e, jax.errors.JaxRuntimeError) and (
                "UNAVAILABLE" in str(e) or "crashed" in str(e)
            ):
                print("device runtime fault — remaining A/B rows skipped "
                      "(restarting the client is the only recovery)",
                      flush=True)
                break
    sortedset.VALUES_VIA = "auto"


if __name__ == "__main__":
    main()
