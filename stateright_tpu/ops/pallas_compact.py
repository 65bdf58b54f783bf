"""Pallas stream-compaction kernels: order-preserving compaction in O(n).

The engine's largest per-level op is the grid-compaction sort —
(W+1 operands) x (A*F lanes) of ``lax.sort``. Under the state-major
flatten (the "bsearch" layout) its only job is ORDER-PRESERVING stream
compaction of P uint32 lane arrays by a mask into a ``[P, cap]`` output.
A sort is O(n log^2 n) data passes; these kernels are O(n): TPU pallas
grids execute blocks SEQUENTIALLY on a core, so a running output offset
lives in SMEM scratch across grid steps and every HBM write is a
contiguous, B-aligned chunk DMA — no scatters
(docs/backend_pathologies.md #2 never enters the picture).

Mosaic constrains the design twice over (registry #6 and the r5e
first-silicon compile): there is no ``cumsum`` lowering inside TC
kernels, and a ``vector_store`` at a DYNAMIC lane offset must be
provably 128-aligned — so the obvious "compact to block front, store at
running offset p" shape does not compile. Both land on the same
TPU-native answer, the MXU one-hot contraction:

Per block b of B lanes (ring state: ``stage`` [P, 2B] VMEM, SMEM carry
``(t, c)`` = survivors appended / chunks flushed, ``p = t - c*B``):
  1. local ranks: inclusive prefix sum of the mask block as a
     lower-triangular [B, B] contraction (0/1 operands, f32
     accumulation at ``Precision.HIGHEST`` — exact at any plausible B),
  2. ring-targeted scatter-as-matmul: survivor s of the block belongs
     at ring position ``i_rank[s] + p``; ``sel[s, j] = (j == i_rank[s]
     + p)`` is a [B, 2B] one-hot, and ``(lanes as two f32 16-bit
     halves) @ sel`` lands every survivor in place in one MXU pass.
     Each output column sums at most ONE nonzero product of
     16-bit-valued f32s, so the result is exact; the default bf16 MXU
     pass would silently truncate the u16 halves (8-bit mantissa) —
     the precision pin is load-bearing,
  3. the ring updates as a full aligned read-modify-write:
     ``stage = where(hit, contrib, stage)`` with ``hit`` = sel's
     column-any — no dynamic-offset store exists in the program,
  4. full B-chunks DMA to the output at ``c*B`` (chunk-aligned by
     construction) and the ring slides by one static B; the garbage
     tail past the total survivor count is UNSPECIFIED — callers
     re-mask (the engine's zero-pad contract is applied outside).

Overflow (survivors past ``cap``) is drop-safe by construction: once
flushing freezes at the cap, ``p`` grows past 2B and every sel column
test fails — nothing is written, nothing is out of bounds.

Inputs are SEPARATE 1-D lane refs (not one stacked [P, M] array): the
engine's lanes already exist as independent buffers, and a pre-kernel
``jnp.stack`` would cost a full extra read+write of the grid — against
the kernel's whole point.

``compact_pallas_staged`` is the kernel (the former separate
VMEM-output ``compact_pallas`` died in the rework — its dynamic-offset
output store was the rejected shape). Equality against the sort
lowering is pinned by
``tests/test_pallas_compact.py`` and the engine differential; whether
it is FASTER on chip is the ``tools/pallas_compact.py`` A/B's question.
"""

from __future__ import annotations

from typing import Sequence


def _as_lanes(planes):
    """Accept either a [P, M] array (tools/tests convenience) or a
    sequence of [M] lanes (the engine's zero-copy form)."""
    if hasattr(planes, "ndim"):
        assert planes.ndim == 2
        return [planes[p] for p in range(planes.shape[0])]
    return list(planes)


def tri_inclusive(m_i32, B: int):
    """Inclusive prefix sum of a 0/1 [B] vector as the lower-triangular
    MXU contraction — Mosaic has no cumsum lowering inside TC kernels
    (registry #6). 0/1 operands with <= B-term f32 accumulation are
    exact at HIGHEST at any plausible block size."""
    import jax
    import jax.numpy as jnp

    ii = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    tri = (ii >= jj).astype(jnp.float32)
    return (
        jax.lax.dot_general(
            tri,
            m_i32.astype(jnp.float32).reshape(B, 1),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        .reshape(B)
        .astype(jnp.int32)
    )


def split16(u32, jnp):
    """u32 -> (lo16, hi16) as f32 via the i32 hop (no direct u32<->f32
    cast on Mosaic; both halves <= 0xFFFF are value-exact — registry
    #6). The exactness-critical half of the scatter-as-matmul trick:
    16-bit-valued f32s survive a HIGHEST-precision contraction exactly,
    where the default bf16 pass would truncate them."""
    lo = (u32 & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    hi = (u32 >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    return lo, hi


def fuse16(lo_f32, hi_f32, jnp):
    """Inverse of :func:`split16` after an exact contraction."""
    return lo_f32.astype(jnp.int32).astype(jnp.uint32) | (
        hi_f32.astype(jnp.int32).astype(jnp.uint32) << jnp.uint32(16)
    )


def ring_fold(stage, arrays, tgt, B: int):
    """Fold u32 source lanes into a [P, 2B] VMEM ring: lane s of every
    array lands at ring position ``tgt[s]`` (-1 or >= 2B = dropped —
    the flush-frozen overflow path is drop-safe by construction, no
    out-of-bounds access exists). The scatter-as-matmul core shared by
    pallas_compact and pallas_merge: a [S, 2B] one-hot contraction of
    the 16-bit halves at ``Precision.HIGHEST`` — each output column
    sums at most ONE nonzero product of 16-bit-valued f32s, so the
    result is exact; the default bf16 MXU pass would silently truncate
    the u16 halves (8-bit mantissa) — the precision pin is
    load-bearing. Mosaic has no direct u32<->f32 cast; the i32 hop is
    value-exact for the <= 0xFFFF halves (registry #6)."""
    import jax
    import jax.numpy as jnp

    P = len(arrays)
    S = tgt.shape[0]
    jr = jax.lax.broadcasted_iota(jnp.int32, (S, 2 * B), 1)
    sel = (jr == tgt.reshape(S, 1)).astype(jnp.float32)
    blk = jnp.stack(list(arrays))  # [P, S]
    lo16, hi16 = split16(blk, jnp)
    contrib = jax.lax.dot_general(
        jnp.concatenate([lo16, hi16], axis=0),  # [2P, S]
        sel,  # [S, 2B]
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [2P, 2B]
    packed = fuse16(contrib[:P], contrib[P:], jnp)
    hit = jnp.sum(sel, axis=0, keepdims=True) > 0.5  # [1, 2B]
    stage[:, :] = jnp.where(hit, packed, stage[:, :])


def _ring_update(mask_ref, plane_refs, stage, p, B: int):
    """Block body: fold this block's mask-selected survivors into the
    ring at running offset ``p`` (compaction targets = local rank + p).
    Returns ``n_b``, the block's survivor count."""
    import jax
    import jax.numpy as jnp

    m = mask_ref[:].astype(jnp.int32)
    incl = tri_inclusive(m, B)
    # Block survivor total = the inclusive prefix sum's last element —
    # NOT jnp.sum(m): Mosaic has no integer-reduction lowering (the
    # stpu-lint STPU005 pre-flight catches the reduce_sum shape), and
    # the triangular contraction already computed the answer.
    n_b = incl[B - 1]
    tgt = jnp.where(m > 0, incl - 1 + p, -1)
    ring_fold(stage, [r[:] for r in plane_refs], tgt, B)
    return n_b


#: The engine's default block (``STPU_PALLAS_BLOCK``): the TPU compiler
#: refuses 512, whose lane blocks do not match XLA's 1024-element tiling
#: of the 1-D mask operand ("Try changing your kernel block shape to
#: (1024)"), and compiles 1024 for a described v5e
#: (tests/test_tpu_compile.py). Smaller blocks run in interpret mode only.
DEFAULT_BLOCK = 1024


def compact_pallas_staged(
    mask, planes, cap: int, *, block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """Order-preserving stream compaction of P uint32 lanes [M] by
    ``mask`` [M] into [P, cap] (HBM output): survivors stream through a
    [P, 2B] VMEM ring and flush to the output in B-aligned chunk DMAs.
    SMEM carries (total appended, flushed chunks) across the sequential
    grid. Lanes at index >= sum(mask) are UNSPECIFIED — callers mask.
    M and cap must be multiples of ``block``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes = _as_lanes(planes)
    P = len(lanes)
    M = lanes[0].shape[0]
    assert mask.shape == (M,)
    assert M % block == 0 and cap % block == 0, (M, cap, block)
    B = block
    n_blocks = M // B

    def kernel(mask_ref, *rest):
        plane_refs = rest[:P]
        out_ref, stage, cnt, sem = rest[P], rest[P + 1], rest[P + 2], rest[P + 3]
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _init():
            cnt[0] = 0  # survivors appended
            cnt[1] = 0  # chunks flushed

        t, c = cnt[0], cnt[1]
        p = t - c * B  # append position within the ring, in [0, B)
        n_b = _ring_update(mask_ref, plane_refs, stage, p, B)
        t = t + n_b
        cnt[0] = t

        def flush(chunk_idx):
            dma = pltpu.make_async_copy(
                stage.at[:, pl.ds(0, B)],
                out_ref.at[:, pl.ds(chunk_idx * B, B)],
                sem,
            )
            dma.start()
            dma.wait()

        @pl.when((t - c * B >= B) & ((c + 1) * B <= cap))
        def _flush_full():
            flush(c)
            # Slide the ring: the second half becomes the first.
            stage[:, pl.ds(0, B)] = stage[:, pl.ds(B, B)]
            cnt[1] = c + 1

        @pl.when(b == n_blocks - 1)
        def _flush_tail():
            c2 = cnt[1]

            @pl.when((cnt[0] > c2 * B) & ((c2 + 1) * B <= cap))
            def _():
                flush(c2)

    lane_spec = pl.BlockSpec((B,), lambda b: (b,))
    return pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[lane_spec] * (1 + P),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((P, cap), lanes[0].dtype),
        scratch_shapes=[
            pltpu.VMEM((P, 2 * B), lanes[0].dtype),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(mask, *lanes)


