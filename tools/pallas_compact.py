"""Pallas stream-compaction prototype: the grid compaction without a sort.

The engine's largest per-level op is the grid-compaction sort —
(W+1 operands) x (A*F lanes) of ``lax.sort`` — whose only job under the
state-major ("bsearch") flatten is ORDER-PRESERVING stream compaction:
move the ``mask``-selected lanes of ``[P, M]`` planes to the front of a
``[P, cap]`` output. A sort is O(n log^2 n) data passes; a streaming
kernel is O(n): TPU pallas grids execute blocks SEQUENTIALLY on a core,
so the running output position lives in SMEM scratch across grid steps
and survivors land via MXU one-hot contractions + aligned chunk DMAs —
no scatters and no dynamic-offset vector stores (the XLA:TPU scatter
pathologies AND the Mosaic alignment prover, docs/backend_pathologies.md
#2/#6, never enter the picture).

Block scheme (block size B, grid step b; the r5e Mosaic rework — the
original "compact to block front, store at running offset" shape is
exactly the dynamic-offset ``vector_store`` Mosaic rejects, see
docs/backend_pathologies.md #6 and the ops/pallas_compact.py module
docstring for the full constraint story):
  1. load mask block [B], planes block [P, B] (VMEM),
  2. local ranks: inclusive prefix sum as a triangular [B, B] MXU
     contraction (Mosaic has no in-kernel cumsum),
  3. ring-targeted scatter-as-matmul: a [B, 2B] one-hot aims survivor
     s at ring position ``rank[s] + p``; one MXU pass lands every
     survivor in place in a [P, 2B] VMEM ring updated by a full
     aligned read-modify-write,
  4. full B-chunks DMA to the output at chunk-aligned offsets; the
     ring slides by one static B (SMEM carries the running counts).
Lanes past the total survivor count are garbage the caller masks (the
engine already masks by ``n_valid``, same as the sort lowerings).

Correctness is validated in interpret mode on CPU (this file's main());
the kernel ships as ``spawn_xla(compaction="pallas")``, opt-in until
this A/B proves it on chip.
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


from stateright_tpu.ops.pallas_compact import (  # noqa: E402
    compact_pallas_staged,
)


def _sort_compact(mask, planes, cap: int):
    """The engine's sort-lowering equivalent at the same shapes: stable
    single-key sort carrying every plane (compact_1d's "sort" mode)."""
    import jax
    import jax.numpy as jnp

    key = jnp.where(mask, jnp.int32(0), jnp.int32(1))
    out = jax.lax.sort((key, *[planes[p] for p in range(planes.shape[0])]),
                       num_keys=1, is_stable=True)
    return jnp.stack([o[:cap] for o in out[1:]])


def main() -> None:
    import itertools
    import time

    import jax

    if "--cpu" in sys.argv:
        sys.argv.remove("--cpu")
        jax.config.update("jax_platforms", "cpu")
    else:
        from stateright_tpu.backend import configure_compile_cache

        configure_compile_cache()
    import jax.numpy as jnp

    interpret = jax.default_backend() == "cpu"
    rng = np.random.default_rng(9)

    # --- correctness ----------------------------------------------------
    P, M, cap, B = 8, 1 << 14, 1 << 13, 512
    mask_np = rng.integers(0, 5, M) == 0  # ~20% density, under cap
    planes_np = rng.integers(0, 2**32, (P, M), dtype=np.uint32)
    n = int(mask_np.sum())
    want = planes_np[:, mask_np]
    out_s = compact_pallas_staged(
        jnp.asarray(mask_np), jnp.asarray(planes_np), cap, block=B,
        interpret=interpret,
    )
    got_s = np.asarray(out_s)[:, :n]
    assert np.array_equal(got_s, want), "STAGED MISMATCH"
    print(f"pallas staged compact OK: {n} survivors, HBM out + VMEM ring")
    if interpret:
        return  # interpreter timings are meaningless

    # --- perf A/B vs the sort lowering (host-readback-gated) ------------
    for log2_m, B in itertools.product((20, 22), (512, 1024)):
        M = 1 << log2_m
        cap = M // 4  # VMEM-resident output probe shape
        mask_np = rng.integers(0, 8, M) == 0  # ~12% (rm=8 grid validity)
        planes_np = rng.integers(0, 2**32, (P, M), dtype=np.uint32)
        mask = jnp.asarray(mask_np)
        planes = jnp.asarray(planes_np)

        f_stg = jax.jit(functools.partial(compact_pallas_staged, cap=cap, block=B))
        f_sort = jax.jit(functools.partial(_sort_compact, cap=cap))
        for name, fn in (("staged", f_stg), ("sort", f_sort)):
            try:
                o = fn(mask, planes)
            except Exception as e:  # lowering failures are a result too
                print(f"  M=2^{log2_m} B={B} {name}: FAILED {type(e).__name__}: {e}")
                continue
            nvl = int(np.asarray(mask).sum())
            ok = np.array_equal(np.asarray(o)[:, :nvl], planes_np[:, mask_np])
            t0 = time.monotonic()
            for _ in range(5):
                o = fn(mask, planes)
            np.asarray(o[0][:8])  # readback gates the clock
            dt = (time.monotonic() - t0) / 5
            print(
                f"  M=2^{log2_m} B={B} {name}: {dt * 1e3:8.2f} ms "
                f"({'exact' if ok else 'WRONG'})",
                flush=True,
            )

    # --- the engine shape: M=2^24 grid lanes, cap=2^22 (out in HBM) -----
    # B=1024 matches the engine's STPU_PALLAS_BLOCK default (the TPU
    # compiler refuses B=512 — see the xla.py comment).
    log2_m, B = 24, 1024
    M, cap = 1 << log2_m, 1 << 22
    mask_np = rng.integers(0, 8, M) == 0
    planes_np = rng.integers(0, 2**32, (P, M), dtype=np.uint32)
    mask = jnp.asarray(mask_np)
    planes = jnp.asarray(planes_np)
    f_stg = jax.jit(functools.partial(compact_pallas_staged, cap=cap, block=B))
    f_sort = jax.jit(functools.partial(_sort_compact, cap=cap))
    for name, fn in (("staged", f_stg), ("sort", f_sort)):
        try:
            o = fn(mask, planes)
        except Exception as e:
            print(f"  M=2^{log2_m} B={B} {name}: FAILED {type(e).__name__}: {e}")
            continue
        nvl = int(mask_np.sum())
        ok = np.array_equal(np.asarray(o)[:, :nvl], planes_np[:, mask_np])
        t0 = time.monotonic()
        for _ in range(5):
            o = fn(mask, planes)
        np.asarray(o[0][:8])
        dt = (time.monotonic() - t0) / 5
        print(
            f"  M=2^{log2_m} B={B} {name} (engine shape): {dt * 1e3:8.2f} ms "
            f"({'exact' if ok else 'WRONG'})",
            flush=True,
        )


if __name__ == "__main__":
    main()
