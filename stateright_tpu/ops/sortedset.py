"""Sort-merge visited set: the TPU-native dedup structure.

The round-2 visited set (``ops/hashset.py``) is an open-addressing table
whose batched insert runs claim-election rounds of gathers and scatters.
That shape is right for CPUs and wrong for TPUs: XLA:TPU executes the
per-round scatters effectively serially, and the on-chip cost model
(an earlier chip setup, to be re-measured) measured the insert at 0.24 M ins/s
for a 2^22 batch — 17.3 seconds — while ``lax.sort`` moved the same batch
in ~3 ms.  On a TPU, **sort is the hash table**.

This module keeps the visited set as a key-sorted array instead.  One
multi-key ``lax.sort`` of ``[visited ‖ candidates]`` per level performs,
simultaneously:

- membership (a candidate equal to a visited key lands in that key's run,
  behind it),
- in-batch dedup with the same determinism rule as the hash insert (the
  lowest original batch index wins: the original index is the sort's
  tie-break key),
- the merge (survivors are already in key order; a compaction sort
  keyed on the survivors' own keys, every dropped row remapped to the
  pad sentinel, restores the dense sorted prefix).

Every key of the sort family's three sorts is unique (the ticket breaks
the merge's ties, survivors' keys are distinct, the ``is_new`` route
sorts tickets), so none of them asks for a stable sort: XLA:TPU lowers
a stable sort with one more operand, an iota.

It replaces the concurrent visited map of the reference's BFS core
(``/root/reference/src/checker/bfs.rs:29-31, 349-363``) just like the
hash set did, stores the same parent-fingerprint values for witness
reconstruction, and its planes keep the hash set's external layout
contract — occupied rows have non-(0,0) keys, pads are zeros — so the
checkpoint codec and the native ``ParentMap`` consume either structure
unchanged.  ``(0xFFFFFFFF, 0xFFFFFFFF)`` is additionally reserved (the
in-sort pad sentinel, remapped by ``ops/fphash.py`` exactly like (0,0)).

Unlike the hash set there is no probe budget and no rehash: growth is a
plain copy into bigger planes, and capacity overflow is detected exactly
(merged count > capacity) rather than probabilistically.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Tuple

import numpy as np

#: How ``insert`` moves the value planes and the merged keys into place:
#: ``"gather"`` sorts 3 operands and recovers values/rows with post-sort
#: gathers (fewest sorted bytes); ``"sort"`` carries them as sort payload
#: operands (no random gathers). The round-5 on-chip A/B settled it: the
#: sort family is 2.3x faster end-to-end on TPU (random gathers at table
#: scale dominate the per-level cost, tpu_profile_r5.log) while gather
#: wins on 1-core CPU — so ``"auto"`` (the default) resolves per backend
#: at trace time. Results are bit-identical; differentially tested. The
#: env var makes the on-chip A/B a process restart.
VALUES_VIA = os.environ.get("STPU_SORTEDSET_VALUES", "auto")

#: Key/value lane width for the insert's sorts: ``"pair"`` keeps the
#: (hi, lo) u32 planes (3 key operands + 2 payloads); ``"packed"`` folds
#: them into u64 lanes (2 keys + 1 payload — ~40% fewer sorted
#: lane-bytes IF the backend sorts u64 at u32 rates; CPU measured 0.62x,
#: tools/sortbench.py). Packed mode requires ``jax_enable_x64`` and the
#: sort-values family; results are bit-identical either way
#: (differential-tested). Trace-time constant like VALUES_VIA.
KEYS_VIA = os.environ.get("STPU_SORTEDSET_KEYS", "pair")

#: Insert lowering: ``"sort"`` = the two table-scale multi-operand
#: ``lax.sort``s below; ``"pallas"`` = the O(C+m) streaming merge
#: kernel (``ops/pallas_merge.py``) — the table-scale log^2 term
#: disappears and every remaining sort is batch-scale. Opt-in pending
#: the chip A/B (tools/pallas_merge.py); CPU runs the kernel in
#: interpret mode (slow, exact). Trace-time constant like VALUES_VIA.
INSERT_VIA = os.environ.get("STPU_SORTEDSET_INSERT", "sort")


def _via_sort() -> bool:
    if VALUES_VIA == "auto":
        import jax

        return jax.default_backend() != "cpu"
    return VALUES_VIA == "sort"


def _pack64(hi, lo, jnp):
    """(hi, lo) u32 pair -> one u64 lane, ordering-preserving."""
    return (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)


def _unpack64(x, jnp):
    return (x >> 32).astype(jnp.uint32), x.astype(jnp.uint32)


def _via_packed() -> bool:
    if KEYS_VIA != "packed":
        return False
    import jax

    if not jax.config.jax_enable_x64:
        raise ValueError(
            "STPU_SORTEDSET_KEYS=packed requires jax_enable_x64 (u64 sort "
            "lanes); enable it before first backend use"
        )
    if not _via_sort():
        raise ValueError(
            "STPU_SORTEDSET_KEYS=packed composes with the sort-values "
            "family only (STPU_SORTEDSET_VALUES=sort)"
        )
    return True


class SortedSet(NamedTuple):
    """First ``n`` rows of the planes are sorted ascending by (hi, lo) and
    unique; rows at ``n`` and beyond are (0, 0) pads."""

    key_hi: "jax.Array"  # [C] uint32
    key_lo: "jax.Array"  # [C] uint32
    val_hi: "jax.Array"  # [C] uint32
    val_lo: "jax.Array"  # [C] uint32
    n: "jax.Array"  # [] int32 — occupied prefix length

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]


def make(capacity: int, xp) -> SortedSet:
    """An empty sorted set with ``capacity`` row slots (power of two)."""
    if capacity & (capacity - 1):
        raise ValueError(f"capacity must be a power of two, got {capacity}")
    z = xp.zeros((capacity,), dtype=xp.uint32)
    return SortedSet(z, z, z, z, xp.asarray(0, dtype=xp.int32))


def from_entries(key_hi, key_lo, val_hi, val_lo, capacity: int, xp) -> SortedSet:
    """Host-side bulk build from unique (key, value) pairs (checkpoint
    restore, init seeding).  Sorts once with numpy; no device round-trips."""
    key_hi = np.asarray(key_hi, np.uint32)
    key_lo = np.asarray(key_lo, np.uint32)
    val_hi = np.asarray(val_hi, np.uint32)
    val_lo = np.asarray(val_lo, np.uint32)
    n = len(key_hi)
    if capacity < n or capacity & (capacity - 1):
        raise ValueError(f"capacity {capacity} cannot hold {n} sorted entries")
    order = np.lexsort((key_lo, key_hi))
    planes = []
    for a in (key_hi[order], key_lo[order], val_hi[order], val_lo[order]):
        out = np.zeros(capacity, np.uint32)
        out[:n] = a
        planes.append(xp.asarray(out))
    return SortedSet(*planes, xp.asarray(n, dtype=xp.int32))


def insert(
    ss: SortedSet,
    fp_hi,
    fp_lo,
    val_hi,
    val_lo,
    active,
    *,
    max_probes: int = 0,  # accepted for hashset signature compatibility; unused
) -> Tuple[SortedSet, "jax.Array", "jax.Array"]:
    """Insert a batch; returns ``(ss', is_new, overflow)``.

    Semantics match ``hashset.insert`` exactly: ``is_new[i]`` (in the
    original batch order) marks the single winner among in-batch
    duplicates — the lowest batch index — of a key not already present;
    winners' values are stored; ``overflow`` (scalar) reports that the
    merged set does not fit the capacity, in which case the caller grows
    and retries (the returned set is truncated and must be discarded).
    """
    import jax
    import jax.numpy as jnp

    cap = ss.capacity
    m = fp_hi.shape[0]
    full = jnp.uint32(0xFFFFFFFF)

    if INSERT_VIA == "pallas":
        blk = _pallas_insert_block(cap, m)
        if blk:
            return _insert_via_merge(ss, fp_hi, fp_lo, val_hi, val_lo,
                                     active, blk)
        # Shapes below the kernel block fall through to the sort
        # lowering, bit-identically (same convention as compact_1d).

    # Pad rows (unoccupied visited slots, inactive candidates) get the
    # reserved all-ones key so they sort to the tail as one run.
    vis_valid = jnp.arange(cap) < ss.n
    kh = jnp.concatenate([jnp.where(vis_valid, ss.key_hi, full), jnp.where(active, fp_hi, full)])
    kl = jnp.concatenate([jnp.where(vis_valid, ss.key_lo, full), jnp.where(active, fp_lo, full)])
    # Tie-break ticket = position in the concatenated input: visited row i
    # carries i (< cap), candidate i carries cap + i — so visited rows sort
    # ahead of any equal-key candidate and in-batch duplicates resolve to
    # the lowest original index, making the key triple unique (visited keys
    # are unique by invariant) and the pipeline deterministic by
    # construction. The ticket doubles as the gather index that recovers
    # values AFTER the sort: values ride one gather each instead of two
    # extra sort operands (a sort operand is ~log^2 n data passes, a gather
    # is one).
    ticket = jnp.arange(cap + m, dtype=jnp.int32)

    via_sort = _via_sort()
    via_packed = _via_packed()
    if via_packed:
        # u64-folded lanes: (key64, ticket) as keys, value64 as payload —
        # 3 operands instead of 5 on the dominant merge sort. The u64
        # key orders exactly as the (hi, lo) pair; the all-ones pad maps
        # to the all-ones u64.
        k64 = (kh.astype(jnp.uint64) << 32) | kl.astype(jnp.uint64)
        v64 = (
            jnp.concatenate([ss.val_hi, val_hi]).astype(jnp.uint64) << 32
        ) | jnp.concatenate([ss.val_lo, val_lo]).astype(jnp.uint64)
        sk64, st, sv64 = jax.lax.sort(
            (k64, ticket, v64), num_keys=2, is_stable=False
        )
        skh = (sk64 >> 32).astype(jnp.uint32)
        skl = sk64.astype(jnp.uint32)
    elif via_sort:
        vh = jnp.concatenate([ss.val_hi, val_hi])
        vl = jnp.concatenate([ss.val_lo, val_lo])
        skh, skl, st, svh, svl = jax.lax.sort(
            (kh, kl, ticket, vh, vl), num_keys=3, is_stable=False
        )
    else:
        skh, skl, st = jax.lax.sort((kh, kl, ticket), num_keys=3)

    run_start = jnp.concatenate(
        [
            jnp.ones((1,), jnp.bool_),
            (skh[1:] != skh[:-1]) | (skl[1:] != skl[:-1]),
        ]
    )
    real = ~((skh == full) & (skl == full))
    is_cand = st >= cap
    winner = run_start & is_cand & real  # run has no visited row, lowest ticket
    keep = real & (winner | ~is_cand)  # surviving = old rows + new winners
    new_n = jnp.sum(keep, dtype=jnp.int32)
    overflow = new_n > cap

    # Compaction of survivors to the front, in key order: the sort
    # family keys it on the survivors' own (unique) keys with every
    # dropped row remapped to the never-real all-ones key, so no
    # stability is needed; ties among dropped rows land past new_n,
    # where row_ok zeroes them.
    row_ok = jnp.arange(cap) < jnp.minimum(new_n, cap)
    z = jnp.uint32(0)
    if via_packed:
        ck64, cv64 = jax.lax.sort(
            (jnp.where(keep, sk64, ~jnp.uint64(0)), sv64),
            num_keys=1, is_stable=False,
        )
        nkh = jnp.where(row_ok, (ck64[:cap] >> 32).astype(jnp.uint32), z)
        nkl = jnp.where(row_ok, ck64[:cap].astype(jnp.uint32), z)
        nvh = jnp.where(row_ok, (cv64[:cap] >> 32).astype(jnp.uint32), z)
        nvl = jnp.where(row_ok, cv64[:cap].astype(jnp.uint32), z)
    elif via_sort:
        # Payload-through-sort: the compaction permutation moves every
        # plane inside one more sort, no gathers.
        ckh, ckl, cvh, cvl = jax.lax.sort(
            (jnp.where(keep, skh, full), jnp.where(keep, skl, full), svh, svl),
            num_keys=2, is_stable=False,
        )
        nkh = jnp.where(row_ok, ckh[:cap], z)
        nkl = jnp.where(row_ok, ckl[:cap], z)
        nvh = jnp.where(row_ok, cvh[:cap], z)
        nvl = jnp.where(row_ok, cvl[:cap], z)
    else:
        order = jnp.argsort(~keep, stable=True)[:cap]
        nkh = jnp.where(row_ok, skh[order], z)
        nkl = jnp.where(row_ok, skl[order], z)
        # Values of surviving rows, via their pre-sort position.
        vh = jnp.concatenate([ss.val_hi, val_hi])
        vl = jnp.concatenate([ss.val_lo, val_lo])
        src = st[order]
        nvh = jnp.where(row_ok, vh[src], z)
        nvl = jnp.where(row_ok, vl[src], z)

    # Route is_new back to original batch order.
    if via_sort:
        # Scatter-free: sorting by ticket is the inverse permutation;
        # candidate lanes are the tail cap:. The winner flag rides in the
        # ticket's low bit, so one int32 operand carries both: 2^30 lanes
        # at most, past what one chip's memory holds. (A uint32 route
        # read a 17.7 MB higher device peak at 2pc rm=8 on a TPU v5e.)
        if cap + m > 1 << 30:
            raise ValueError(
                f"sorted-set insert of {cap + m} lanes: the is_new route "
                "packs (ticket << 1) | winner into an int32, 2^30 lanes at most"
            )
        routed = jax.lax.sort(
            (st << 1) | winner.astype(jnp.int32), is_stable=False
        )
        is_new = (routed[cap:] & 1).astype(jnp.bool_)
    else:
        # Winner tickets are unique, so the scatter is conflict-free;
        # non-winners are routed out of range.
        idx = jnp.where(winner, st - cap, m)
        is_new = jnp.zeros((m,), jnp.bool_).at[idx].set(True, mode="drop")

    return SortedSet(nkh, nkl, nvh, nvl, jnp.minimum(new_n, cap)), is_new, overflow


def _insert_via_merge(ss, fp_hi, fp_lo, val_hi, val_lo, active, blk):
    """``insert`` by the O(C+m) pallas streaming merge
    (ops/pallas_merge.py): one BATCH-scale presort, the kernel, one
    batch-scale inverse sort — no table-scale sort anywhere. Returns
    the identical contract, bit-for-bit (pinned by
    tests/test_pallas_merge.py's engine differential)."""
    import jax
    import jax.numpy as jnp

    from .pallas_merge import merge_insert

    cap = ss.capacity
    m = fp_hi.shape[0]
    full = jnp.uint32(0xFFFFFFFF)

    # Batch presort by (key, ticket): lowest batch index first within
    # equal keys, so the kernel's keep-first rule elects the reference
    # winner. Inactive rows get the all-ones key (never real).
    kh = jnp.where(active, fp_hi, full)
    kl = jnp.where(active, fp_lo, full)
    ticket = jnp.arange(m, dtype=jnp.int32)
    skh, skl, st, svh, svl = jax.lax.sort(
        (kh, kl, ticket, val_hi, val_lo), num_keys=3
    )

    vis_valid = jnp.arange(cap) < ss.n
    table = jnp.stack(
        [
            jnp.where(vis_valid, ss.key_hi, full),
            jnp.where(vis_valid, ss.key_lo, full),
            ss.val_hi,
            ss.val_lo,
        ]
    )
    batch = jnp.stack([skh, skl, svh, svl])
    interp = jax.default_backend() == "cpu"
    merged, keep_sorted, n_keep = merge_insert(
        table, batch, block=blk, interpret=interp
    )

    overflow = n_keep > cap
    new_n = jnp.minimum(n_keep, cap)
    row_ok = jnp.arange(cap) < new_n
    z = jnp.uint32(0)
    out = SortedSet(
        jnp.where(row_ok, merged[0], z),
        jnp.where(row_ok, merged[1], z),
        jnp.where(row_ok, merged[2], z),
        jnp.where(row_ok, merged[3], z),
        new_n,
    )
    # is_new back to batch order: sorting (ticket, flag) by ticket is
    # the inverse permutation — batch-scale, scatter-free.
    _, in_order = jax.lax.sort(
        (st, keep_sorted.astype(jnp.int32)), num_keys=1
    )
    return out, in_order.astype(jnp.bool_), overflow


def _pallas_insert_block(cap: int, m: int) -> int:
    """The streaming-merge kernel block :func:`insert` will use at these
    shapes, or 0 when they fall through to the sort lowering — ONE
    predicate shared by the insert and its lane-words telemetry, so the
    cost law can't silently drift from the actual lowering."""
    blk = int(os.environ.get("STPU_PALLAS_BLOCK", "512"))
    if cap % blk == 0 and m % blk == 0 and cap >= blk and m >= blk:
        return blk
    return 0


def insert_lane_words(ss: SortedSet, m: int) -> int:
    """32-bit words carried as ``lax.sort`` operands by one :func:`insert`
    with an ``m``-lane batch at this table's capacity — the engine's
    cost-law telemetry (round-5 law: per-level time ~ sorted lane-words
    x log^2 n). Counts sort operands only; post-sort gathers and the
    scatter ``is_new`` route are not sorted lanes. Counts the operands
    as traced: the sort family's sorts are unstable, so XLA:TPU adds no
    iota to them. Tracks the same trace-time lowering knobs the insert
    resolves."""
    cap = ss.capacity
    if INSERT_VIA == "pallas" and _pallas_insert_block(cap, m):
        # Batch-scale only: 5-operand presort + 2-operand inverse.
        return m * 7
    n = cap + m
    if _via_sort():
        # Packed or pair, the sorted WORDS agree (packed trades operand
        # streams, not bytes): 5-word merge + 4-word compaction + the
        # inverse permutation, one word with the winner in the ticket's
        # low bit. All three sorts are unstable.
        return n * 10
    # Gather family: 3-operand merge + 2-operand compaction argsort;
    # values and is_new move by gather/scatter.
    return n * 5


def lookup(ss: SortedSet, fp_hi, fp_lo, *, max_probes: int = 0):
    """Batched membership + value lookup: ``(found, val_hi, val_lo)``.
    Branchless lower-bound descent — log2(capacity) rounds of gathers,
    no scatters (the shape ``ops/hashset.lookup`` used probe rounds for)."""
    import jax.numpy as jnp

    cap = ss.capacity
    off = jnp.zeros(fp_hi.shape, jnp.int32)
    step = cap
    while step > 1:
        step //= 2
        mid = off + step
        kh = ss.key_hi[mid - 1]
        kl = ss.key_lo[mid - 1]
        less = (kh < fp_hi) | ((kh == fp_hi) & (kl < fp_lo))
        off = jnp.where((mid <= ss.n) & less, mid, off)
    at = jnp.minimum(off, cap - 1)
    hit = (off < ss.n) & (ss.key_hi[at] == fp_hi) & (ss.key_lo[at] == fp_lo)
    vh = jnp.where(hit, ss.val_hi[at], jnp.uint32(0))
    vl = jnp.where(hit, ss.val_lo[at], jnp.uint32(0))
    return hit, vh, vl


def grow(ss: SortedSet, new_capacity: int, xp) -> SortedSet:
    """Capacity growth is a plain copy — no rehash (the sorted invariant
    is capacity-independent, unlike hash slot assignment)."""
    if new_capacity < ss.capacity:
        raise ValueError("sorted set cannot shrink")
    pad = new_capacity - ss.capacity
    planes = [
        xp.concatenate([p, xp.zeros((pad,), dtype=xp.uint32)])
        for p in (ss.key_hi, ss.key_lo, ss.val_hi, ss.val_lo)
    ]
    return SortedSet(*planes, ss.n)
