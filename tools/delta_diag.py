"""Standalone bisector for the delta-structure TPU runtime fault.

Round-5 history: the pre-redesign delta insert (flush as a ``lax.cond``
branch carrying a main-capacity sort) reproducibly crashed the TPU
runtime ("TPU worker crashed — kernel fault") at 2^22 AND 2^27 main
tiers while staying exact on CPU. The redesign (host-invoked
``maintain``) removes that shape; the soak retries it at rm=8/rm=10.
The retry DID fault again (r5e, twice, deterministic, flush already
host-invoked), so THIS tool pins where, coarse-to-fine in one process:
each delta program standalone (insert at empty delta, maintain,
dedup-vs-main) across a ladder of main-tier shapes, then the REAL
engine at the faulting rm=8 shape — lpd=1 (no fused loop) first, then
fused. A fault kills the process, so the first faulting
(program/composition, shape) is the last stage whose "..." line has no
matching "ok" line; a ``timeout`` kill looks the same, so check the
wall clock against the stage budget before calling it a fault (the
engine stages are FULL rm=8 checks — ~minutes on chip, ~an hour on
this 1-core box; shrink with STPU_DIAG_RM=6 or skip with
--no-engine for a quick harness check). A count DRIFT in a surviving
engine stage exits 2 — silent drift is the failure class this tool
exists for.

Usage:
    [STPU_DIAG_RM=N] python tools/delta_diag.py [--cpu] [--no-engine] [max_log2_C]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    import jax

    no_engine = "--no-engine" in sys.argv
    if no_engine:
        sys.argv.remove("--no-engine")
    if "--cpu" in sys.argv:
        sys.argv.remove("--cpu")
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    import jax.numpy as jnp

    from stateright_tpu.ops import deltaset

    max_pow = int(sys.argv[1]) if len(sys.argv) > 1 else 27
    print(f"backend={jax.default_backend()} shapes up to 2^{max_pow}", flush=True)

    rng = np.random.default_rng(3)

    ins = jax.jit(deltaset.insert)

    for pow_c in range(18, max_pow + 1, 3):
        C = 1 << pow_c
        t0 = time.monotonic()
        ds = deltaset.make(C, jnp)
        # Batch sized to half the delta tier (C/16-row tier): big enough
        # to be a realistic level, small enough that the empty-delta
        # insert cannot overflow.
        m = ds.delta_capacity // 2
        hi = jnp.asarray(rng.integers(1, 2**32, m, dtype=np.uint32))
        lo = jnp.asarray(rng.integers(1, 2**32, m, dtype=np.uint32))
        vh = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
        act = jnp.ones((m,), bool)

        print(f"[delta_diag] C=2^{pow_c} insert(empty-delta) ...", flush=True)
        ds1, is_new, ovf = ins(ds, hi, lo, vh, vh, act)
        n_new = int(np.asarray(is_new).sum())
        assert not bool(ovf) and n_new > 0, (n_new, bool(ovf))
        print(
            f"[delta_diag] C=2^{pow_c} insert ok: {n_new} new "
            f"({time.monotonic() - t0:.1f}s)",
            flush=True,
        )

        print(f"[delta_diag] C=2^{pow_c} maintain(flush) ...", flush=True)
        t0 = time.monotonic()
        ds2, f_ovf = deltaset.maintain_jit(ds1)
        assert not bool(f_ovf)
        n_main = int(ds2.n_main)
        assert n_main == n_new, (n_main, n_new)
        print(
            f"[delta_diag] C=2^{pow_c} maintain ok: {n_main} main rows "
            f"({time.monotonic() - t0:.1f}s)",
            flush=True,
        )

        print(f"[delta_diag] C=2^{pow_c} insert(post-flush, dup batch) ...", flush=True)
        t0 = time.monotonic()
        # Re-inserting the same batch must find every key in main.
        _, is_new2, ovf2 = ins(ds2, hi, lo, vh, vh, act)
        assert not bool(ovf2) and int(np.asarray(is_new2).sum()) == 0
        print(
            f"[delta_diag] C=2^{pow_c} dedup-vs-main ok "
            f"({time.monotonic() - t0:.1f}s)",
            flush=True,
        )

    print("[delta_diag] ALL SHAPES CLEAN (standalone programs)", flush=True)
    if no_engine:
        return

    # --- engine composition, coarse-to-fine ------------------------------
    # The r5e window proved the fault lives past the standalone layer or
    # in a shape these ladders miss: the rm=8 delta bench faulted twice,
    # deterministically, with the flush already host-invoked. Run the
    # REAL engine at the faulting shape, least-composed first: lpd=1
    # (each level its own dispatch, no fused while_loop), then the fused
    # default. A fault kills the process, so the last line printed is
    # the first faulting composition; counts are checked against the
    # pinned rm=8 totals when a stage survives.
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    # rm=8 is the faulting shape; STPU_DIAG_RM shrinks it for CPU
    # validation of the harness itself and for faster fault iteration.
    # Pinned totals come from bench.py's table — one source of truth.
    from bench import EXPECTED_2PC

    rm = int(os.environ.get("STPU_DIAG_RM", "8"))
    want = EXPECTED_2PC.get(rm)
    f_pow = 19 if rm >= 8 else 17
    t_pow = 22 if rm >= 8 else 20
    for lpd, label in ((1, "engine lpd=1 (no fused loop)"), (32, "engine fused")):
        print(f"[delta_diag] {label} rm={rm} dedup=delta ...", flush=True)
        t0 = time.monotonic()
        ck = (
            PackedTwoPhaseSys(rm)
            .checker()
            .spawn_xla(
                frontier_capacity=1 << f_pow,
                table_capacity=1 << t_pow,
                dedup="delta",
                levels_per_dispatch=lpd,
            )
            .join()
        )
        got = (ck.state_count(), ck.unique_state_count())
        if want and got != want:
            # Silent count drift is THE failure class this tool exists
            # for — it must not be reportable as a clean pass.
            print(
                f"[delta_diag] {label} COUNT DRIFT: gen/uniq {got} "
                f"vs pinned {want} ({time.monotonic() - t0:.1f}s)",
                flush=True,
            )
            sys.exit(2)
        verdict = "EXACT" if want else "unpinned rm"
        print(
            f"[delta_diag] {label} ok: gen/uniq {got} {verdict} "
            f"({time.monotonic() - t0:.1f}s)",
            flush=True,
        )

    print("[delta_diag] ALL CLEAN incl. engine composition", flush=True)


if __name__ == "__main__":
    main()
