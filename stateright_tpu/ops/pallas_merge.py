"""Pallas streaming merge-insert: the sorted-set insert in O(n).

``sortedset.insert`` pays two table-scale multi-operand ``lax.sort``s
per level — the merge of [table ‖ batch] and the keep-compaction —
~(C+m) log^2 (C+m) comparator passes each, the dominant per-level cost
in the cost law measured on an earlier chip setup. But the table is ALREADY
sorted (structure invariant) and the batch can be pre-sorted at [m]
cost, so the table-scale work is a pure two-way sorted MERGE with
adjacent-key dedup — O(C+m), and a natural sequential-grid pallas
kernel.

The kernel composes the op shapes this repo has chip evidence for and
avoids every pinned pathology (docs/backend_pathologies.md): no
scatters (#2), no wide sorts (#3), no ``lax.cond`` around big ops
(#4), no in-kernel cumsum or u32<->f32 casts and no dynamic-offset
vector stores (#6) — placement is the ring-targeted one-hot MXU
contraction proven in ``ops/pallas_compact.py``, and the only
dynamic-offset accesses are chunk DMAs.

Scheme (block B, chunk k = merged positions [kB, (k+1)B)):

  host/XLA side (``_merge_partition``): classic merge-path diagonal
  binary search, vectorized over all n_chunks+1 diagonals — [ii, jj]
  with ii[k]+jj[k] = kB such that the chunk consumes exactly
  table[ii[k]:ii[k+1]] and batch[jj[k]:jj[k+1]]. Pads (all-ones keys)
  merge like ordinary largest keys, so the partition needs no dynamic
  row counts. Ties break table-first (<=), which IS the reference
  semantics: an existing row beats an equal-key candidate
  (sortedset.insert's ticket rule, reference dfs.rs/bfs.rs dedup).

  kernel, per chunk (sequential grid, SMEM carries):
    1. DMA table[ii[k]:ii[k]+B] and batch[jj[k]:jj[k]+B] (stacked
       [4, B] planes each: key_hi, key_lo, val_hi, val_lo),
    2. block-local cross-ranks by [B, B] lexicographic pair-compare +
       row-sum: pos(a[u]) = u + ii[k] + jj[k] + #{b < a[u]} - kB,
       pos(b[v]) = v + jj[k] + ii[k] + #{a <= b[v]} - kB; the
       merge-path band theorem makes block-local ranks exact for
       in-chunk elements and provably >= B for the overhang, so
       ``pos < B`` masks the chunk's own elements,
    3. assemble the merged chunk (keys, values, is_batch flag) by one
       [2B, B] one-hot contraction,
    4. keep rule on the merged chunk: real table rows always; a real
       batch element iff its key differs from the PREVIOUS merged
       element's key (SMEM key-carry across chunks) — in-batch
       duplicate runs keep only their first (lowest ticket, by the
       presort), table-equal candidates die (table went first),
    5. survivors stream into the [4, 2B] output ring at the running
       offset (one-hot, triangular-matmul prefix sums); full chunks
       DMA to the new table at chunk-aligned offsets. Keep flags of
       the chunk's batch elements stream in batch-sorted order
       through a second [1, 2B] ring -> the ``is_new`` plane,
    6. survivor total past the output capacity freezes flushing
       (drop-safe by construction, as in pallas_compact) and reports
       overflow for the caller's grow-and-retry protocol.

``merge_insert`` wraps partition + kernel and returns the merged
planes RAW: rows at and past min(n_keep, C) are unspecified ring
garbage, and the caller MUST re-mask before treating the result as a
table (``sortedset.insert`` under ``STPU_SORTEDSET_INSERT=pallas``
zeroes them, restoring the structure's pad convention, and routes
``is_new`` back to batch order with one [m] sort — all remaining
sorts are batch-scale).

Exactness: every one-hot contraction sums at most one nonzero product
of 16-bit-valued f32 halves, and prefix sums accumulate <= 2B 0/1
terms — exact at ``Precision.HIGHEST`` (the same pin, and the same
bf16-truncation hazard, as pallas_compact).

CPU-exact via interpret mode; chip acceptance of the arbitrary-offset
input DMAs is THE open question for its first chip run
(tools/pallas_merge.py is the probe). If Mosaic's alignment rules
extend to DMA sources, the fallback is align-down + an in-register
one-hot shift; not built until the probe demands it.
"""

from __future__ import annotations

from typing import Tuple


def _pair_le(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al <= bl))


def _pair_lt(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al < bl))


def _merge_partition(tkh, tkl, ckh, ckl, B: int):
    """Merge-path diagonals for padded sorted planes: table [C], batch
    [m] -> (ii, jj) int32 [n_chunks + 1] with ii[k] + jj[k] = k*B,
    ii monotone. For diagonal d: ii[k] is the LARGEST i in
    [max(0, d-m), min(C, d)] with t[i-1] <= c[d-i] (table-first ties);
    found by log2 rounds of vectorized bisection (tiny: n_chunks+1
    lanes of [C]-gathers)."""
    import jax.numpy as jnp

    C = tkh.shape[0]
    m = ckh.shape[0]
    n_chunks = (C + m) // B
    d = jnp.arange(n_chunks + 1, dtype=jnp.int32) * B
    lo = jnp.maximum(0, d - m)
    hi = jnp.minimum(C, d)
    # Invariant: P(lo) holds (vacuous at i == max(0, d-m)), P(hi+1)
    # fails; bisect for the largest i with P(i) = t[i-1] <= c[d-i].
    steps = max(1, (C + m).bit_length())
    for _ in range(steps):
        mid = (lo + hi + 1) >> 1  # in (lo, hi]
        ti = jnp.clip(mid - 1, 0, C - 1)
        cj = jnp.clip(d - mid, 0, m - 1)
        ok = _pair_le(tkh[ti], tkl[ti], ckh[cj], ckl[cj])
        # mid == lo means the bracket is closed; d - mid < 0 cannot
        # happen (mid <= hi <= d).
        take = ok | (mid <= lo)
        lo = jnp.where(take, jnp.maximum(lo, mid), lo)
        hi = jnp.where(take, hi, jnp.minimum(hi, mid - 1))
    return lo, d - lo


def _onehot_place(stacked_f32, sel, jax, jnp):
    """[(rows), S] @ one-hot [S, T] at HIGHEST — exact placement."""
    return jax.lax.dot_general(
        stacked_f32,
        sel,
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def merge_insert(
    table,  # [4, C] u32 planes (key_hi, key_lo, val_hi, val_lo), key-sorted,
    #         pad rows carry the all-ones key
    batch,  # [4, m] u32 planes, key-sorted with ticket tie-break, all-ones pads
    *,
    block: int = 512,
    interpret: bool = False,
) -> Tuple["jax.Array", "jax.Array", "jax.Array"]:
    """Merge-dedup ``batch`` into ``table``: returns ``(merged [4, C],
    keep_batch [m] bool in BATCH-SORTED order, n_keep [] int32 — the
    TOTAL survivor count, > C meaning overflow)``. Rows of ``merged``
    at and past min(n_keep, C) are UNSPECIFIED (callers re-mask); on
    overflow the merged planes are truncated and must be discarded.
    C and m must be multiples of ``block``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .pallas_compact import fuse16, ring_fold, split16, tri_inclusive

    C = table.shape[1]
    m = batch.shape[1]
    B = block
    assert table.shape[0] == 4 and batch.shape[0] == 4
    assert C % B == 0 and m % B == 0, (C, m, B)
    n_chunks = (C + m) // B

    ii, jj = _merge_partition(table[0], table[1], batch[0], batch[1], B)

    # Overhang pad: chunk loads read [idx, idx + B) with idx <= C (resp.
    # m); one extra all-ones block keeps every DMA in bounds.
    ones = jnp.full((4, B), jnp.uint32(0xFFFFFFFF))
    tpad = jnp.concatenate([table, ones], axis=1)
    bpad = jnp.concatenate([batch, ones], axis=1)

    def kernel(ii_ref, jj_ref, t_ref, b_ref, out_ref, new_ref, n_ref,
               ablk, bblk, ring, ring2, cnt, sems):
        full = jnp.uint32(0xFFFFFFFF)
        k = pl.program_id(0)

        @pl.when(k == 0)
        def _init():
            n_ref[0] = 0
            cnt[0] = 0  # survivors appended (ring 1)
            cnt[1] = 0  # ring-1 chunks flushed
            cnt[2] = 0  # ring-2 chunks flushed
            # Carry init = the all-ones bit pattern (i32 -1): no real
            # key equals it, so the first merged element never dedups
            # against the carry.
            cnt[3] = jnp.int32(-1)  # carry key_hi (prev merged)
            cnt[4] = jnp.int32(-1)  # carry key_lo

        i0 = ii_ref[k]
        j0 = jj_ref[k]
        dj = jj_ref[k + 1] - j0

        cp_a = pltpu.make_async_copy(
            t_ref.at[:, pl.ds(i0, B)], ablk, sems.at[0]
        )
        cp_b = pltpu.make_async_copy(
            b_ref.at[:, pl.ds(j0, B)], bblk, sems.at[1]
        )
        cp_a.start()
        cp_b.start()
        cp_a.wait()
        cp_b.wait()

        akh, akl = ablk[0], ablk[1]
        bkh, bkl = bblk[0], bblk[1]
        u = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)  # a index
        v = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)  # b index
        # rank_b[u] = #{v : b[v] < a[u]}; rank_a[v] = #{u : a[u] <= b[v]}
        lt_ba = _pair_lt(bkh[None, :], bkl[None, :], akh[:, None], akl[:, None])
        # Rank counts reduce in f32 (exact: counts <= B << 2^24) — Mosaic
        # has no integer-reduction lowering (stpu-lint STPU005).
        rank_b = jnp.sum(lt_ba.astype(jnp.float32), axis=1).astype(jnp.int32)
        rank_a = jnp.sum((~lt_ba).astype(jnp.float32), axis=0).astype(
            jnp.int32
        )  # #{a <= b[v]}

        base = i0 + j0 - k * B  # == 0, kept symbolic for clarity
        pos_a = jax.lax.broadcasted_iota(jnp.int32, (B,), 0) + rank_b + base
        pos_b = jax.lax.broadcasted_iota(jnp.int32, (B,), 0) + rank_a + base
        in_a = pos_a < B
        in_b = pos_b < B

        # Merged-chunk assembly: one [2B, B] one-hot. Rows = a lanes
        # then b lanes; out-of-chunk lanes target -1 (no column).
        tgt = jnp.concatenate(
            [jnp.where(in_a, pos_a, -1), jnp.where(in_b, pos_b, -1)]
        )
        colm = jax.lax.broadcasted_iota(jnp.int32, (2 * B, B), 1)
        sel = (colm == tgt[:, None]).astype(jnp.float32)
        planes = []
        for p in range(4):
            lo_a, hi_a = split16(ablk[p], jnp)
            lo_b, hi_b = split16(bblk[p], jnp)
            planes.append(jnp.concatenate([lo_a, lo_b]))
            planes.append(jnp.concatenate([hi_a, hi_b]))
        isb = jnp.concatenate(
            [jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32)]
        )
        placed = _onehot_place(
            jnp.concatenate(
                [jnp.stack(planes), isb.reshape(1, 2 * B)], axis=0
            ),
            sel,
            jax,
            jnp,
        )  # [9, B]
        mkh = fuse16(placed[0], placed[1], jnp)
        mkl = fuse16(placed[2], placed[3], jnp)
        mvh = fuse16(placed[4], placed[5], jnp)
        mvl = fuse16(placed[6], placed[7], jnp)
        is_batch = placed[8] > 0.5

        # Keep rule (module docstring step 4). The SMEM key-carry round-
        # trips through i32 (same-width conversions are modular — bit
        # patterns survive).
        carry_kh = jnp.full((1,), cnt[3], jnp.int32).astype(jnp.uint32)
        carry_kl = jnp.full((1,), cnt[4], jnp.int32).astype(jnp.uint32)
        prev_kh = jnp.concatenate([carry_kh, mkh[:-1]])
        prev_kl = jnp.concatenate([carry_kl, mkl[:-1]])
        real = ~((mkh == full) & (mkl == full))
        differs = (mkh != prev_kh) | (mkl != prev_kl)
        keep = real & (~is_batch | differs)
        cnt[3] = mkh[B - 1].astype(jnp.int32)
        cnt[4] = mkl[B - 1].astype(jnp.int32)

        # Ring 1: survivors (4 planes) at the running offset — the
        # shared scatter-as-matmul ring fold (pallas_compact).
        t_cnt, c1 = cnt[0], cnt[1]
        p1 = t_cnt - c1 * B
        k_i32 = keep.astype(jnp.int32)
        incl = tri_inclusive(k_i32, B)
        # Survivor total = the prefix sum's last element (no integer
        # reduce_sum in Mosaic; stpu-lint STPU005).
        n_k = incl[B - 1]
        tgt1 = jnp.where(keep, incl - 1 + p1, -1)
        ring_fold(ring, [mkh, mkl, mvh, mvl], tgt1, B)
        t_cnt = t_cnt + n_k
        cnt[0] = t_cnt

        def flush1(chunk_idx):
            dma = pltpu.make_async_copy(
                ring.at[:, pl.ds(0, B)],
                out_ref.at[:, pl.ds(chunk_idx * B, B)],
                sems.at[2],
            )
            dma.start()
            dma.wait()

        @pl.when((t_cnt - c1 * B >= B) & ((c1 + 1) * B <= C))
        def _flush_full1():
            flush1(c1)
            ring[:, pl.ds(0, B)] = ring[:, pl.ds(B, B)]
            cnt[1] = c1 + 1

        # Ring 2: keep flags of this chunk's batch elements, in batch
        # order. Element v of the b block (v < dj) was consumed by this
        # chunk; its keep flag sits at merged position pos_b[v] —
        # gather it with sel's b half (one [B, B] @ [B, 1]).
        sel_b = sel[B:, :]  # [B, B]; row v one-hot at pos_b[v] (or 0)
        # flag_v[v] = keep[pos_b[v]] = sum_x keep[x] * sel_b[v, x]:
        # contract both operands on their LAST dim (no transpose — a
        # transpose fused into compute is registry #1's shape on CPU).
        flag_v = jax.lax.dot_general(
            keep.astype(jnp.float32).reshape(1, B),
            sel_b,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).reshape(B)  # [B] f32; rows past dj are 0 via empty one-hots
        c2 = cnt[2]
        p2 = j0 - c2 * B
        vv = jax.lax.broadcasted_iota(jnp.int32, (B,), 0)
        tgt2 = jnp.where(vv < dj, vv + p2, -1)
        col2 = jax.lax.broadcasted_iota(jnp.int32, (B, 2 * B), 1)
        sel2 = (col2 == tgt2[:, None]).astype(jnp.float32)
        placed2 = _onehot_place(flag_v.reshape(1, B), sel2, jax, jnp)
        hit2 = jnp.sum(sel2, axis=0, keepdims=True) > 0.5
        ring2[:, :] = jnp.where(hit2, placed2, ring2[:, :])
        j_end = j0 + dj

        def flush2(chunk_idx):
            dma = pltpu.make_async_copy(
                ring2.at[:, pl.ds(0, B)],
                new_ref.at[:, pl.ds(chunk_idx * B, B)],
                sems.at[3],
            )
            dma.start()
            dma.wait()

        # Ring 2 needs no tail flush and no freeze guard: every batch
        # element writes exactly one flag, j_end reaches exactly m
        # (a multiple of B), and eager flushing keeps the residue < B —
        # so the final residue is ≡ 0 (mod B) AND < B, i.e. zero, and
        # (c2+1)*B <= j_end <= m always holds at flush time.
        @pl.when(j_end - c2 * B >= B)
        def _flush_full2():
            flush2(c2)
            ring2[:, pl.ds(0, B)] = ring2[:, pl.ds(B, B)]
            cnt[2] = c2 + 1

        @pl.when(k == n_chunks - 1)
        def _tail():
            n_ref[0] = cnt[0]
            c1f = cnt[1]

            @pl.when((cnt[0] > c1f * B) & ((c1f + 1) * B <= C))
            def _():
                flush1(c1f)

    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    merged, flags, n_keep = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[smem_spec, smem_spec, any_spec, any_spec],
        out_specs=[any_spec, any_spec, smem_spec],
        out_shape=[
            jax.ShapeDtypeStruct((4, C), jnp.uint32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((4, B), jnp.uint32),  # a block
            pltpu.VMEM((4, B), jnp.uint32),  # b block
            pltpu.VMEM((4, 2 * B), jnp.uint32),  # ring 1
            pltpu.VMEM((1, 2 * B), jnp.float32),  # ring 2
            pltpu.SMEM((5,), jnp.int32),
            pltpu.SemaphoreType.DMA((4,)),
        ],
        interpret=interpret,
    )(ii, jj, tpad, bpad)
    return merged, flags.reshape(m) > 0.5, n_keep[0]
