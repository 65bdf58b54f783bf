"""Bisect the superstep's per-level fixed cost on chip, by shape and stage.

Round-5 on-chip facts (tpu_profile_r5.log): the engine's real fused
superstep costs ~554 ms per level at bucket 2^18 / table 2^22 while its
component ops (expand, fingerprint, grid compaction, sorted insert)
measure ~0.1-1 ms standalone at the same shapes, and lpd=32 fusion does
NOT remove the cost — it is inside the compiled level body, and it
matches round 3's ~475 ms at an *empty frontier*. This tool pins where
it lives:

  sweep   time the real single-level superstep program across
          (bucket, table) shapes — the scaling law separates
          "per-kernel/serialization overhead" (flat) from "hidden
          O(table) or O(grid) data passes" (sloped)
  stages  rebuild the superstep with stages disabled one at a time
          (property eval, expansion+compaction, insert, frontier
          route-back) and time each variant at the flagship shape
  hlo     dump instruction/fusion counts of the compiled program

Usage: python tools/superstep_bisect.py [sweep|stages|hlo] [--cpu]
Run under `timeout` — a hung dispatch never returns.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _setup():
    import jax

    if "--cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    print(f"platform={jax.devices()[0].platform}", flush=True)
    return jax


def _checker(f_pow: int, t_pow: int, rm: int = 8):
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    model = PackedTwoPhaseSys(rm)
    c = model.checker().spawn_xla(
        frontier_capacity=1 << f_pow, table_capacity=1 << t_pow,
        levels_per_dispatch=1, dedup="sorted",
    )
    return model, c


def _time_step(jax, c, f_cap: int, n: int = 5) -> float:
    """Median wall time of the engine's real one-level program at run
    capacity ``f_cap``, on a synthetic full frontier (every row valid —
    the steady-state worst case), timed by host-observed readback of a
    returned scalar (immune to async-dispatch undercounting)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    frontier = jnp.asarray(
        rng.integers(0, 2**32, (f_cap, c._W), dtype=np.uint32))
    ebits = jnp.zeros((f_cap,), jnp.uint32)
    step = c._superstep_for(f_cap)
    ts = []
    for _ in range(n + 1):
        t0 = time.monotonic()
        out = step(frontier, ebits, jnp.int32(f_cap), c._table,
                   c._disc_found, c._disc_fp)
        int(out[2])  # ncount readback: forces the whole dispatch
        ts.append(time.monotonic() - t0)
    return float(np.median(ts[1:]))  # drop the compile call


def sweep(jax) -> None:
    print("bucket x table sweep (real superstep, full frontier, median of 5)")
    for f_pow in (12, 14, 16, 18):
        for t_pow in (18, 20, 22):
            _, c = _checker(f_pow, t_pow)
            dt = _time_step(jax, c, 1 << f_pow)
            print(f"  f=2^{f_pow} table=2^{t_pow}: {dt*1e3:8.1f} ms "
                  f"({(1 << f_pow) * c._A / dt / 1e6:7.1f} M cand/s)",
                  flush=True)


def stages(jax) -> None:
    """Time the flagship-shape superstep with engine stages neutralized.

    Monkeypatches build-time hooks on fresh checker instances (each gets
    its own compile): every variant keeps the program's output signature
    so the dispatch protocol still works; the measured delta against
    "full" prices the stage.
    """
    import jax.numpy as jnp

    f_pow, t_pow = 18, 22
    rows = []

    def run(tag, patch=None):
        model, c = _checker(f_pow, t_pow)
        if patch:
            patch(model, c)
        dt = _time_step(jax, c, 1 << f_pow)
        rows.append((tag, dt))
        print(f"  {tag:24s} {dt*1e3:8.1f} ms", flush=True)

    run("full")

    def no_props(model, c):
        # Property evaluation priced out: no packed properties at all.
        c._P = 0
        c._prop_names = []
        c._prop_kinds = []
        import numpy as _np
        c._disc_found = jnp.zeros((0,), bool)
        c._disc_fp = jnp.zeros((0, 2), jnp.uint32)
        model.packed_properties = lambda words: jnp.zeros((0,), bool)

    run("no-properties", no_props)

    def no_expand(model, c):
        # Expansion priced out: one self-successor per state (A=1).
        model.packed_step = lambda words: (
            words[None, :], jnp.ones((1,), bool))
        model.max_actions = 1
        c._A = 1

    run("A=1 expand", no_expand)

    def no_insert(model, c):
        # Insert priced out: every candidate arrives inactive, so the
        # structure's sort/merge machinery sees an all-pad batch. c._ds
        # is the dedup module; a proxy namespace overrides insert only.
        import types

        real = c._ds

        def fake_insert(tbl, chi, clo, vhi, vlo, active, **kw):
            # Table untouched, everything "new": the sort/merge dead-codes
            # out of the program entirely — the variant prices the whole
            # visited-set stage.
            return tbl, active, jnp.bool_(False)

        proxy = types.SimpleNamespace(
            **{k: getattr(real, k) for k in dir(real) if not k.startswith("__")}
        )
        proxy.insert = fake_insert
        c._ds = proxy

    run("insert-inactive", no_insert)

    full = rows[0][1]
    for tag, dt in rows[1:]:
        print(f"  {tag:24s} saves {1e3*(full-dt):8.1f} ms", flush=True)


def hlo(jax) -> None:
    f_pow, t_pow = 18, 22
    _, c = _checker(f_pow, t_pow)
    import jax.numpy as jnp

    f_cap = 1 << f_pow
    rng = np.random.default_rng(0)
    frontier = jnp.asarray(rng.integers(0, 2**32, (f_cap, c._W), dtype=np.uint32))
    ebits = jnp.zeros((f_cap,), jnp.uint32)
    fn = c._superstep_for(f_cap)
    txt = fn.lower(frontier, ebits, jnp.int32(f_cap), c._table,
                   c._disc_found, c._disc_fp).compile().as_text()
    lines = txt.splitlines()
    import collections
    ops = collections.Counter()
    fusion_sizes = []
    for ln in lines:
        ln = ln.strip()
        if "= " in ln and "(" in ln:
            rhs = ln.split("= ", 1)[1]
            # "type opname(" — take the opname token.
            parts = rhs.split("(", 1)[0].split()
            if parts:
                ops[parts[-1]] += 1
    print(f"total instructions: {sum(ops.values())}")
    for op, n in ops.most_common(25):
        print(f"  {op:28s} {n}")
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "superstep_hlo.txt")
    with open(out, "w") as fh:
        fh.write(txt)
    print(f"full HLO -> {out} ({len(lines)} lines)")


def main() -> None:
    jax = _setup()
    mode = next((a for a in sys.argv[1:] if not a.startswith("-")), "sweep")
    {"sweep": sweep, "stages": stages, "hlo": hlo}[mode](jax)


if __name__ == "__main__":
    main()
