"""The sort-merge visited set (ops/sortedset.py): op-level differential
parity against the hash set, exact overflow semantics, and engine-level
parity of ``spawn_xla(dedup="sorted")`` vs ``dedup="hash"``.

The two structures implement the same contract (hashset.insert's
docstring): is_new in original batch order, lowest-batch-index winner
among in-batch duplicates, parent values stored for winners. The sorted
set is the TPU-native lowering (BASELINE.md cost model: sort ~1.3 G
keys/s on-chip vs 0.24 M ins/s for the scatter-election insert)."""

import numpy as np
import pytest

import jax.numpy as jnp

from stateright_tpu.ops import hashset, sortedset


def _rand_batch(rng, m, universe):
    hi = jnp.asarray(rng.integers(1, universe, m, dtype=np.uint32))
    lo = jnp.asarray(rng.integers(1, universe, m, dtype=np.uint32))
    vh = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    vl = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    act = jnp.asarray(rng.integers(0, 2, m).astype(bool))
    return hi, lo, vh, vl, act


@pytest.mark.parametrize("universe", [40, 2**31])  # heavy duplicates / near-unique
def test_insert_lookup_differential_vs_hashset(universe):
    rng = np.random.default_rng(11)
    ss = sortedset.make(1 << 11, jnp)
    hs = hashset.make(1 << 13, jnp)
    for rnd in range(8):
        hi, lo, vh, vl, act = _rand_batch(rng, 257, universe)
        ss, s_new, s_ovf = sortedset.insert(ss, hi, lo, vh, vl, act)
        hs, h_new, h_ovf = hashset.insert(hs, hi, lo, vh, vl, act)
        assert np.array_equal(np.asarray(s_new), np.asarray(h_new)), rnd
        assert not bool(s_ovf) and not bool(np.any(np.asarray(h_ovf)))
        qh = jnp.asarray(rng.integers(1, min(universe + 20, 2**32 - 1), 128, dtype=np.uint32))
        ql = jnp.asarray(rng.integers(1, min(universe + 20, 2**32 - 1), 128, dtype=np.uint32))
        for a, b in zip(sortedset.lookup(ss, qh, ql), hashset.lookup(hs, qh, ql)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), rnd


def test_sorted_invariant_and_grow():
    rng = np.random.default_rng(3)
    ss = sortedset.make(1 << 9, jnp)
    hi, lo, vh, vl, act = _rand_batch(rng, 300, 2**20)
    ss, _, _ = sortedset.insert(ss, hi, lo, vh, vl, act)
    n = int(ss.n)
    kh = np.asarray(ss.key_hi)
    kl = np.asarray(ss.key_lo)
    keys = (kh[:n].astype(np.uint64) << 32) | kl[:n]
    assert np.all(keys[1:] > keys[:-1]), "occupied prefix must be strictly sorted"
    assert not np.any(kh[n:]) and not np.any(kl[n:]), "pads must be zeros"

    grown = sortedset.grow(ss, 1 << 11, jnp)
    assert grown.capacity == 1 << 11 and int(grown.n) == n
    found, gvh, gvl = sortedset.lookup(grown, jnp.asarray(kh[:n]), jnp.asarray(kl[:n]))
    assert bool(jnp.all(found))
    assert np.array_equal(np.asarray(gvh), np.asarray(ss.val_hi)[:n])
    assert np.array_equal(np.asarray(gvl), np.asarray(ss.val_lo)[:n])


def test_exact_overflow_flag():
    """Unlike the hash set's probe-budget overflow, the sorted set reports
    overflow exactly when merged uniques exceed capacity."""
    ss = sortedset.make(16, jnp)
    m = 24
    hi = jnp.arange(1, m + 1, dtype=jnp.uint32)
    lo = jnp.ones((m,), jnp.uint32)
    z = jnp.zeros((m,), jnp.uint32)
    act = jnp.ones((m,), bool)
    _, _, ovf = sortedset.insert(ss, hi, lo, z, z, act)
    assert bool(ovf)
    _, _, ovf16 = sortedset.insert(ss, hi[:16], lo[:16], z[:16], z[:16], act[:16])
    assert not bool(ovf16)  # exactly at capacity: fits


def test_winner_is_lowest_batch_index():
    ss = sortedset.make(16, jnp)
    hi = jnp.asarray([5, 5, 5], dtype=jnp.uint32)
    lo = jnp.asarray([9, 9, 9], dtype=jnp.uint32)
    vh = jnp.asarray([100, 200, 300], dtype=jnp.uint32)
    vl = jnp.zeros((3,), jnp.uint32)
    ss, is_new, _ = sortedset.insert(ss, hi, lo, vh, vl, jnp.ones((3,), bool))
    assert np.asarray(is_new).tolist() == [True, False, False]
    found, got_vh, _ = sortedset.lookup(ss, hi[:1], lo[:1])
    assert bool(found[0]) and int(got_vh[0]) == 100  # winner's value stored


def test_from_entries_roundtrip():
    rng = np.random.default_rng(5)
    n = 100
    kh = rng.permutation(np.arange(1, n + 1, dtype=np.uint32))
    kl = rng.integers(1, 2**32, n, dtype=np.uint32)
    vh = rng.integers(0, 2**32, n, dtype=np.uint32)
    vl = rng.integers(0, 2**32, n, dtype=np.uint32)
    ss = sortedset.from_entries(kh, kl, vh, vl, 128, jnp)
    found, got_vh, got_vl = sortedset.lookup(ss, jnp.asarray(kh), jnp.asarray(kl))
    assert bool(jnp.all(found))
    assert np.array_equal(np.asarray(got_vh), vh)
    assert np.array_equal(np.asarray(got_vl), vl)


# --- engine-level parity ----------------------------------------------------


def _counts(c):
    return c.state_count(), c.unique_state_count(), c.max_depth()


@pytest.fixture(params=["auto", "sort"])
def values_via(request, monkeypatch):
    """The sorted set's insert lowering: the platform's own ("auto", the
    gather family on the CPU) and the sort family a TPU takes."""
    monkeypatch.setattr(sortedset, "VALUES_VIA", request.param)


def test_engine_parity_two_phase_commit(values_via):
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    a = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="hash").join()
    b = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="sorted").join()
    assert _counts(a) == _counts(b) == (1146, 288, 11)
    assert set(a.discoveries()) == set(b.discoveries())


def test_gather_compact_cap_exceeds_mask_length():
    """Regression: the gather-compact lowering must handle compaction caps
    larger than the source array (cand_cap = next_pow2 rounding past the
    grid; frontier caps above cand caps for small action counts)."""
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    c = (
        PackedTwoPhaseSys(3)
        .checker()
        .spawn_xla(dedup="sorted", table_capacity=1 << 8, frontier_capacity=1 << 5)
        .join()
    )
    assert _counts(c) == (1146, 288, 11)


def test_engine_parity_under_forced_growth(values_via):
    """Tiny capacities force the overflow-retry + growth path of both
    structures (sorted growth = plane copy, hash growth = rehash)."""
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    kw = dict(table_capacity=1 << 8, frontier_capacity=1 << 6)
    a = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="hash", **kw).join()
    b = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="sorted", **kw).join()
    assert _counts(a) == _counts(b) == (1146, 288, 11)


def test_engine_parity_discovery_model(values_via):
    """A model with a real counterexample: discovery names and witness
    paths must agree across dedup structures."""
    from stateright_tpu.models.single_copy_register import PackedSingleCopyRegister

    a = PackedSingleCopyRegister(2, 2).checker().spawn_xla(dedup="hash").join()
    b = PackedSingleCopyRegister(2, 2).checker().spawn_xla(dedup="sorted").join()
    da, db = a.discoveries(), b.discoveries()
    assert set(da) == set(db) and da
    for name in da:
        assert len(da[name]) == len(db[name])


def test_engine_parity_symmetry(values_via):
    from stateright_tpu.models.increment import PackedIncrement

    a = PackedIncrement(3).checker().symmetry().spawn_xla(dedup="hash").join()
    b = PackedIncrement(3).checker().symmetry().spawn_xla(dedup="sorted").join()
    assert _counts(a) == _counts(b)


def test_checkpoint_crosses_dedup_structures(tmp_path):
    """A checkpoint written by a hash-table run restores into a sorted-set
    run (and vice versa): the snapshot format is structure-independent."""
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    path = str(tmp_path / "ck.npz")
    a = PackedTwoPhaseSys(3).checker().spawn_xla(
        dedup="hash", levels_per_dispatch=1
    )
    for _ in range(4):
        a._run_block()
    a.save_checkpoint(path)
    resumed = PackedTwoPhaseSys(3).checker().spawn_xla(
        dedup="sorted", checkpoint=path
    ).join()
    full = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="sorted").join()
    assert _counts(resumed) == _counts(full) == (1146, 288, 11)


def test_fingerprint_planes_matches_words():
    """The plane-major fingerprint (the engine's structure-of-arrays path)
    is bit-identical to the row fingerprint, under numpy and under jit."""
    import jax

    from stateright_tpu.ops import fphash

    rng = np.random.default_rng(7)
    for W in (1, 2, 5, 12):
        rows = rng.integers(0, 2**32, (257, W), dtype=np.uint32)
        wh, wl = fphash.fingerprint_words(rows, np)
        ph, pl = fphash.fingerprint_planes(rows.T.copy(), np)
        assert np.array_equal(wh, ph) and np.array_equal(wl, pl)
        jh, jl = jax.jit(lambda p: fphash.fingerprint_planes(p, jnp))(
            jnp.asarray(rows.T.copy())
        )
        assert np.array_equal(wh, np.asarray(jh))
        assert np.array_equal(wl, np.asarray(jl))


def _distinct_keys(rng, n):
    hi = jnp.asarray(rng.permutation(np.arange(1, n + 1, dtype=np.uint32)))
    lo = jnp.asarray(rng.integers(1, 2**32 - 1, n, dtype=np.uint32))
    return hi, lo


def _keyed_batch(rng, hi, lo):
    m = hi.shape[0]
    vh = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    vl = jnp.asarray(rng.integers(0, 2**32, m, dtype=np.uint32))
    return hi, lo, vh, vl, jnp.ones((m,), bool)


def _lowering_case(case, rng):
    """``(capacity, batches, last_n, last_overflow)`` for one case of the
    sort-vs-gather differential; ``None`` leaves a final value unchecked."""
    if case == "mixed":
        return 1 << 11, [_rand_batch(rng, 257, 300) for _ in range(6)], None, False
    if case == "duplicates":
        # 7 x 7 keys: nearly every lane repeats an in-batch key or a stored one.
        return 1 << 8, [_rand_batch(rng, 257, 8) for _ in range(5)], None, False
    if case == "inactive":
        hi, lo, vh, vl, _ = _rand_batch(rng, 257, 300)
        off = (hi, lo, vh, vl, jnp.zeros((257,), bool))
        return 1 << 11, [off, _rand_batch(rng, 257, 300), off], None, False
    hi, lo = _distinct_keys(rng, 400)
    if case == "full":
        # 256 uniques into 2^8 slots, with in-batch and cross-table repeats.
        b1 = _keyed_batch(rng, jnp.concatenate([hi[:150], hi[:50]]),
                          jnp.concatenate([lo[:150], lo[:50]]))
        b2 = _keyed_batch(rng, jnp.concatenate([hi[150:256], hi[:94]]),
                          jnp.concatenate([lo[150:256], lo[:94]]))
        return 1 << 8, [b1, b2], 1 << 8, False
    assert case == "overflow"  # 400 uniques against 2^8 slots
    b1 = _keyed_batch(rng, hi[:200], lo[:200])
    b2 = _keyed_batch(rng, hi[200:], lo[200:])
    return 1 << 8, [b1, b2], 1 << 8, True


@pytest.mark.parametrize(
    "case",
    ["mixed", "duplicates", "inactive", "full", "overflow", "packed"],
)
def test_insert_values_via_sort_matches_gather(monkeypatch, case):
    """The payload-through-sort insert lowering is bit-identical to the
    gather lowering (STPU_SORTEDSET_VALUES; which is faster is a hardware
    question, correctness is not): its three unstable sorts hold on
    duplicate-heavy and all-inactive batches, a table filled to capacity,
    an overflowing batch, and packed u64 keys."""
    import jax

    rng = np.random.default_rng(23)
    base = "mixed" if case == "packed" else case
    cap, batches, last_n, last_ovf = _lowering_case(base, rng)
    prev_x64 = jax.config.jax_enable_x64
    if case == "packed":
        jax.config.update("jax_enable_x64", True)
    try:
        ss_a = sortedset.make(cap, jnp)
        ss_b = sortedset.make(cap, jnp)
        for rnd, (hi, lo, vh, vl, act) in enumerate(batches):
            monkeypatch.setattr(sortedset, "VALUES_VIA", "gather")
            monkeypatch.setattr(sortedset, "KEYS_VIA", "pair")
            ss_a, new_a, ovf_a = sortedset.insert(ss_a, hi, lo, vh, vl, act)
            monkeypatch.setattr(sortedset, "VALUES_VIA", "sort")
            if case == "packed":
                monkeypatch.setattr(sortedset, "KEYS_VIA", "packed")
            ss_b, new_b, ovf_b = sortedset.insert(ss_b, hi, lo, vh, vl, act)
            for a, b in zip(ss_a, ss_b):
                assert np.array_equal(np.asarray(a), np.asarray(b)), rnd
            assert np.array_equal(np.asarray(new_a), np.asarray(new_b)), rnd
            assert bool(ovf_a) == bool(ovf_b)
            if not bool(np.any(np.asarray(act))):
                assert not bool(np.any(np.asarray(new_b))), rnd
        if last_n is not None:
            assert int(ss_b.n) == last_n
        assert bool(ovf_b) == last_ovf
    finally:
        jax.config.update("jax_enable_x64", prev_x64)


@pytest.mark.parametrize(
    "keys_via, operands", [("pair", [5, 4, 1]), ("packed", [3, 2, 1])]
)
def test_insert_sort_family_lowers_to_unstable_sorts(
    monkeypatch, keys_via, operands
):
    """The sort family's three sorts carry 5, 4 and 1 operands on u32
    pairs (3, 2 and 1 on packed u64 lanes) and none is stable (XLA:TPU
    lowers a stable sort with one more operand, an iota), and
    ``insert_lane_words`` counts exactly the pair lowering's operands."""
    import re

    import jax

    monkeypatch.setattr(sortedset, "VALUES_VIA", "sort")
    monkeypatch.setattr(sortedset, "KEYS_VIA", keys_via)
    cap, m = 1 << 8, 96
    prev_x64 = jax.config.jax_enable_x64
    if keys_via == "packed":
        jax.config.update("jax_enable_x64", True)
    try:
        ss = sortedset.make(cap, jnp)
        z = jnp.zeros((m,), jnp.uint32)
        text = jax.jit(sortedset.insert).lower(
            ss, z, z, z, z, jnp.zeros((m,), bool)
        ).as_text()
        lane_words = sortedset.insert_lane_words(ss, m)
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
    sorts = re.findall(r'"stablehlo\.sort"\(([^)]*)\) <\{([^}]*)\}>', text)
    assert [len(ops.split(",")) for ops, _ in sorts] == operands
    assert all("is_stable = false" in attrs for _, attrs in sorts)
    assert lane_words == (5 + 4 + 1) * (cap + m)


def test_insert_route_refuses_past_int32(monkeypatch):
    """The sort family's ``is_new`` route packs ``(ticket << 1) | winner``
    into an int32, so an insert past 2^30 lanes is refused at trace time
    (abstractly traced here: nothing is allocated)."""
    import jax

    monkeypatch.setattr(sortedset, "VALUES_VIA", "sort")
    monkeypatch.setattr(sortedset, "KEYS_VIA", "pair")
    plane = jax.ShapeDtypeStruct((1 << 30,), jnp.uint32)
    ss = sortedset.SortedSet(
        plane, plane, plane, plane, jax.ShapeDtypeStruct((), jnp.int32)
    )
    lane = jax.ShapeDtypeStruct((1,), jnp.uint32)
    act = jax.ShapeDtypeStruct((1,), jnp.bool_)
    with pytest.raises(ValueError, match="2\\^30 lanes"):
        jax.eval_shape(sortedset.insert, ss, lane, lane, lane, lane, act)
    small = sortedset.SortedSet(
        *(jax.ShapeDtypeStruct((1 << 29,), jnp.uint32),) * 4,
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    _, is_new, _ = jax.eval_shape(
        sortedset.insert, small, lane, lane, lane, lane, act
    )
    assert is_new.shape == (1,)


def test_engine_compaction_lowerings_match():
    """All three compaction lowerings — "gather", "sort" (payload through
    the sorts, with the round-5 derived-parent grid sort), and "bsearch"
    (cumsum + rank binary-search) — reproduce identical counts and
    witness paths."""
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    kw = dict(frontier_capacity=1 << 6, table_capacity=1 << 9, dedup="sorted")
    a = PackedTwoPhaseSys(3).checker().spawn_xla(compaction="gather", **kw).join()
    da = a.discoveries()
    assert da
    for mode in ("sort", "bsearch"):
        b = PackedTwoPhaseSys(3).checker().spawn_xla(compaction=mode, **kw).join()
        assert _counts(a) == _counts(b), mode
        db = b.discoveries()
        assert set(da) == set(db), mode
        for name in da:
            assert da[name].into_states() == db[name].into_states(), mode


def test_insert_packed_keys_match_pair(monkeypatch):
    """STPU_SORTEDSET_KEYS=packed (u64-folded key/value lanes, 3 sort
    operands) is bit-identical to the u32-pair lowering. Needs x64 for
    the u64 lanes; restored after."""
    import jax

    monkeypatch.setattr(sortedset, "VALUES_VIA", "sort")
    rng = np.random.default_rng(41)
    ss_a = sortedset.make(1 << 11, jnp)
    ss_b = sortedset.make(1 << 11, jnp)
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        for rnd in range(6):
            hi, lo, vh, vl, act = _rand_batch(rng, 257, 300)
            monkeypatch.setattr(sortedset, "KEYS_VIA", "pair")
            ss_a, new_a, ovf_a = sortedset.insert(ss_a, hi, lo, vh, vl, act)
            monkeypatch.setattr(sortedset, "KEYS_VIA", "packed")
            ss_b, new_b, ovf_b = sortedset.insert(ss_b, hi, lo, vh, vl, act)
            for a, b in zip(ss_a, ss_b):
                assert np.array_equal(np.asarray(a), np.asarray(b)), rnd
            assert np.array_equal(np.asarray(new_a), np.asarray(new_b)), rnd
            assert bool(ovf_a) == bool(ovf_b)
    finally:
        jax.config.update("jax_enable_x64", prev_x64)


def test_packed_keys_guardrails(monkeypatch):
    """packed without x64 or with the gather values family must raise,
    not silently truncate keys to 32 bits."""
    import jax

    import pytest as _pytest

    monkeypatch.setattr(sortedset, "KEYS_VIA", "packed")
    monkeypatch.setattr(sortedset, "VALUES_VIA", "sort")
    rng = np.random.default_rng(43)
    ss = sortedset.make(1 << 8, jnp)
    hi, lo, vh, vl, act = _rand_batch(rng, 65, 300)
    with _pytest.raises(ValueError, match="x64"):
        sortedset.insert(ss, hi, lo, vh, vl, act)
    prev_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        monkeypatch.setattr(sortedset, "VALUES_VIA", "gather")
        with _pytest.raises(ValueError, match="sort-values"):
            sortedset.insert(ss, hi, lo, vh, vl, act)
    finally:
        jax.config.update("jax_enable_x64", prev_x64)
