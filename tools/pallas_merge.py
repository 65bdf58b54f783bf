"""Chip probe/A/B for the pallas streaming merge-insert
(ops/pallas_merge.py, engaged by STPU_SORTEDSET_INSERT=pallas).

Two open questions only silicon can answer (the host-side lowering
sweep already passed — registry #6's pre-flight):
  1. does Mosaic accept the kernel's ARBITRARY-offset input chunk DMAs
     (the compact kernel only ever proved chunk-aligned ones)? If not,
     the documented fallback is align-down + an in-register one-hot
     shift — build it only when this probe demands it;
  2. is the O(C+m) stream actually faster than the two table-scale
     ``lax.sort``s of the shipping insert at engine shapes?

Rows print host-readback-gated timings (``block_until_ready`` once
returned early for standalone programs on an earlier chip setup).

Usage:  python tools/pallas_merge.py [--cpu]
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _sort_insert(table, batch, cap):
    """The shipping insert's table-scale core at the same shapes: the
    (kh, kl, ticket, vh, vl) 3-key merge sort + the keep-compaction
    sort (sortedset.insert's via_sort path, stripped of the wrapper)."""
    import jax
    import jax.numpy as jnp

    m = batch.shape[1]
    full = jnp.uint32(0xFFFFFFFF)
    kh = jnp.concatenate([table[0], batch[0]])
    kl = jnp.concatenate([table[1], batch[1]])
    vh = jnp.concatenate([table[2], batch[2]])
    vl = jnp.concatenate([table[3], batch[3]])
    ticket = jnp.arange(cap + m, dtype=jnp.int32)
    skh, skl, st, svh, svl = jax.lax.sort((kh, kl, ticket, vh, vl), num_keys=3)
    run_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), (skh[1:] != skh[:-1]) | (skl[1:] != skl[:-1])]
    )
    real = ~((skh == full) & (skl == full))
    is_cand = st >= cap
    winner = run_start & is_cand & real
    keep = real & (winner | ~is_cand)
    ckey = jnp.where(keep, jnp.int32(0), jnp.int32(1))
    _, ckh, ckl, cvh, cvl = jax.lax.sort(
        (ckey, skh, skl, svh, svl), num_keys=1, is_stable=True
    )
    _, win_in_order = jax.lax.sort((st, winner.astype(jnp.int32)), num_keys=1)
    return (
        jnp.stack([ckh[:cap], ckl[:cap], cvh[:cap], cvl[:cap]]),
        win_in_order[cap:],
        jnp.sum(keep, dtype=jnp.int32),
    )


def main() -> None:
    import jax

    if "--cpu" in sys.argv:
        sys.argv.remove("--cpu")
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    import jax.numpy as jnp

    from stateright_tpu.ops.pallas_merge import merge_insert

    interpret = jax.default_backend() == "cpu"
    rng = np.random.default_rng(17)
    FULL = 0xFFFFFFFF

    def mk(C, m, n_t, n_c):
        tk = np.sort(rng.choice(2**40, n_t, replace=False).astype(np.uint64))
        table = np.full((4, C), FULL, np.uint32)
        table[0, :n_t] = (tk >> 16).astype(np.uint32)
        table[1, :n_t] = (tk & 0xFFFF).astype(np.uint32)
        ck = np.sort(rng.choice(2**40, n_c, replace=True).astype(np.uint64))
        batch = np.full((4, m), FULL, np.uint32)
        batch[0, :n_c] = (ck >> 16).astype(np.uint32)
        batch[1, :n_c] = (ck & 0xFFFF).astype(np.uint32)
        return jnp.asarray(table), jnp.asarray(batch)

    # --- correctness (vs the sort core, small shape) --------------------
    B = 512
    C, m = 1 << 13, 1 << 12
    table, batch = mk(C, m, C // 2, m // 2)
    f_mrg = jax.jit(
        functools.partial(merge_insert, block=B, interpret=interpret)
    )
    f_srt = jax.jit(functools.partial(_sort_insert, cap=C))
    mg, kb, nk = f_mrg(table, batch)
    sg, sb, sn = f_srt(table, batch)
    nk, sn = int(nk), int(sn)
    assert nk == sn, (nk, sn)
    assert np.array_equal(
        np.asarray(mg)[:, :nk], np.asarray(sg)[:, :nk]
    ), "merged planes mismatch"
    assert np.array_equal(
        np.asarray(kb), np.asarray(sb).astype(bool)
    ), "is_new mismatch"
    print(f"merge_insert OK vs sort core: n_keep={nk} of C={C}, m={m}")
    if interpret:
        return  # interpreter timings are meaningless

    # --- perf A/B at engine shapes (host-readback-gated) ----------------
    for log2_c, log2_m in ((22, 19), (22, 22), (24, 22)):
        C, m = 1 << log2_c, 1 << log2_m
        table, batch = mk(C, m, (C * 3) // 8, m // 2)
        f_mrg = jax.jit(functools.partial(merge_insert, block=B))
        f_srt = jax.jit(functools.partial(_sort_insert, cap=C))
        for name, fn in (("merge", f_mrg), ("sort2x", f_srt)):
            try:
                o = fn(table, batch)
                int(np.asarray(o[2]).reshape(-1)[0])  # force
                t0 = time.monotonic()
                for _ in range(3):
                    o = fn(table, batch)
                    int(np.asarray(o[2]).reshape(-1)[0])  # readback gate
                dt = (time.monotonic() - t0) / 3
                print(
                    f"  C=2^{log2_c} m=2^{log2_m} {name}: {dt * 1e3:8.2f} ms",
                    flush=True,
                )
            except Exception as e:
                print(
                    f"  C=2^{log2_c} m=2^{log2_m} {name}: FAILED "
                    f"{type(e).__name__}: {str(e)[:300]}",
                    flush=True,
                )


if __name__ == "__main__":
    main()
