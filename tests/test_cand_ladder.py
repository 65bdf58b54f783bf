"""In-program candidate-width ladder (``spawn_xla(cand_ladder=)`` /
``STPU_CAND_LADDER``): snug per-level candidate sorts inside the fused
superstep via ``lax.switch`` sub-width branches.

The load-bearing claims pinned here:

- counts are exact BY CONSTRUCTION under the ladder: a committed snug
  level is bit-identical to the full-width level (same candidate order,
  same winner election), and an UNDERESTIMATE of the candidate width
  falls through to the full-width branch in-program — never dropping a
  candidate and never adding a host dispatch (the growth-spike model
  below is the analogue of the committed==0 livelock guard in
  test_ladder.py);
- the ladder is per-checker state: two checkers over one model cannot
  cross-contaminate candidate sizing (the old model-level cap dict did),
  while a fresh checker still inherits learned growths via model hints;
- the per-level ``lane_words`` telemetry (the round-5 cost law's x-axis)
  drops at narrow levels with the ladder on — the engine-measured form
  of the candidate-ladder evidence;
- the K=3 fused program lowers for the TPU target from this CPU-only box
  (registry #6 pre-flight — a ``lax.switch`` branch carries the
  [table ‖ cand] merge sort, the registry-#4-adjacent shape, so the
  runtime verdict needs the chip: chip_smoke.py runs the K=3 default).
"""

import numpy as np
import pytest

from stateright_tpu.core import Model
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys
from stateright_tpu.xla import XlaChecker

KW = dict(frontier_capacity=1 << 12, table_capacity=1 << 13)


def _join(checker):
    while not checker.is_done():
        checker._run_block()
    return checker


def _summary(c):
    return (
        c.state_count(),
        c.unique_state_count(),
        c.max_depth(),
        {n: p.into_actions() for n, p in c.discoveries().items()},
    )


# --- the growth-spike fall-through -------------------------------------


class _ChainSpike(Model):
    """Synthetic PackedModel shaped to UNDERESTIMATE: 600 parallel chains
    generate 600 states/level for two levels (so the device-side growth
    extrapolation predicts ~600 * growth 1 * margin), then every chain
    state fans out 16-wide at once — 9,600 candidates against the snug
    rung's 4,096-lane buffer. The spike successors collide down to 800
    uniques, so the post-spike frontier still fits the bucket and the
    ONLY overflow in the whole run is the snug branch's in-program one.
    """

    M = 100_000  # wave stride in the packed word

    def __init__(self):
        self.state_words = 1
        self.max_actions = 16

    # Object model (witness reconstruction parity is not exercised here;
    # the packed kernel is the system under test).
    def init_states(self):
        return list(range(600))

    def actions(self, state, actions):
        wave = state // self.M
        if wave < 2:
            actions.append(0)
        elif wave == 2:
            actions.extend(range(16))

    def next_state(self, state, action):
        wave, i = divmod(state, self.M)
        if wave < 2:
            return state + self.M
        return 3 * self.M + action * 50 + i % 50

    def pack(self, state):
        return np.asarray([state], np.uint32)

    def unpack(self, words):
        return int(words[0])

    def packed_init(self):
        return np.arange(600, dtype=np.uint32)[:, None]

    def packed_step(self, words):
        import jax.numpy as jnp

        M = jnp.uint32(self.M)
        wave = words[0] // M
        i = words[0] % M
        a = jnp.arange(16, dtype=jnp.uint32)
        chain = words[0] + M  # next wave, same chain
        leaves = jnp.uint32(3) * M + a * jnp.uint32(50) + i % jnp.uint32(50)
        nxt = jnp.where(wave < 2, chain, leaves)[:, None]
        valid = jnp.where(wave < 2, a == 0, wave == jnp.uint32(2))
        return nxt, valid

    def packed_properties(self, words):
        import jax.numpy as jnp

        return jnp.zeros((0,), jnp.bool_)


# Exact totals: 600 init + (600 + 600 + 9,600) generated; uniques
# 600 * 3 waves + 800 colliding leaves; leaves counted at depth 4.
SPIKE_PINNED = dict(generated=11_400, unique=2_600, depth=4)


def _run_spike(cand_ladder):
    c = _ChainSpike().checker().spawn_xla(
        dedup="sorted",
        cand_ladder=cand_ladder,
        frontier_capacity=1 << 13,
        table_capacity=1 << 13,
    )
    return _join(c)


def test_growth_spike_falls_through_full_width():
    off = _run_spike(1)
    on = _run_spike(3)
    for c in (off, on):
        assert c.state_count() == SPIKE_PINNED["generated"]
        assert c.unique_state_count() == SPIKE_PINNED["unique"]
        assert c.max_depth() == SPIKE_PINNED["depth"]
    # The spike level picked a snug rung off the flat-growth estimate,
    # overflowed it, and fell through IN-PROGRAM: at least one retry,
    # zero added host dispatches, and the committed spike level ran (and
    # is recorded) at the full candidate width.
    assert on.cand_retries >= 1, on.level_log
    assert off.cand_retries == 0
    assert len(on.dispatch_log) == len(off.dispatch_log), (
        on.dispatch_log,
        off.dispatch_log,
    )
    spike_rows = [r for r in on.level_log if r["generated"] == 9_600]
    assert spike_rows and all(
        r["cand_cap"] == off.level_log[0]["cand_cap"] for r in spike_rows
    ), on.level_log


# --- exact counts across the packed models -----------------------------


def _models_small():
    from stateright_tpu.models.increment import PackedIncrement
    from stateright_tpu.models.increment_lock import PackedIncrementLock
    from stateright_tpu.models.puzzle import PackedPuzzle
    from stateright_tpu.models.single_copy_register import (
        PackedSingleCopyRegister,
    )

    return [
        ("2pc rm=3", lambda: PackedTwoPhaseSys(3)),
        ("increment 2t", lambda: PackedIncrement(2)),
        ("increment_lock 3t", lambda: PackedIncrementLock(3)),
        ("single-copy 2c/1s", lambda: PackedSingleCopyRegister(2, 1)),
        ("puzzle 2x2", lambda: PackedPuzzle([0, 2, 1, 3], side=2)),
    ]


def _models_slow():
    from stateright_tpu.models.linearizable_register import PackedAbd
    from stateright_tpu.models.paxos import PackedPaxos

    return [
        ("ABD 2c/2s", lambda: PackedAbd(2, 2)),
        ("paxos 2c/3s", lambda: PackedPaxos(2, 3)),
    ]


def _ladder_ab(name, build, monkeypatch, **kw):
    # Rung floor 16 pulls the ladder into the 64-row floor buckets these
    # small spaces run at, so every model genuinely executes through
    # lax.switch branches instead of the trivial K=1 program.
    monkeypatch.setattr(XlaChecker, "CAND_RUNG_FLOOR", 16)
    monkeypatch.setenv("STPU_CAND_LADDER", "3")
    on = _join(build().checker().spawn_xla(dedup="sorted", **kw))
    assert on._cand_ladder_k == 3, name
    monkeypatch.setenv("STPU_CAND_LADDER", "1")
    off = _join(build().checker().spawn_xla(dedup="sorted", **kw))
    assert _summary(on) == _summary(off), name
    return on


def test_ladder_counts_exact_small_models(monkeypatch):
    for name, build in _models_small():
        _ladder_ab(name, build, monkeypatch, **KW)


def test_ladder_counts_exact_2pc_pinned(monkeypatch):
    on = _ladder_ab("2pc rm=4", lambda: PackedTwoPhaseSys(4), monkeypatch, **KW)
    assert (on.state_count(), on.unique_state_count()) == (8_258, 1_568)


@pytest.mark.slow
def test_ladder_counts_exact_slow_models(monkeypatch):
    kw = dict(frontier_capacity=1 << 12, table_capacity=1 << 16)
    for name, build in _models_slow():
        _ladder_ab(name, build, monkeypatch, **kw)


def test_ladder_counts_exact_delta(monkeypatch):
    monkeypatch.setenv("STPU_CAND_LADDER", "3")
    c = _join(
        PackedTwoPhaseSys(4).checker().spawn_xla(dedup="delta", **KW)
    )
    assert (c.state_count(), c.unique_state_count()) == (8_258, 1_568)


# --- telemetry: the cost-law lane-words drop ---------------------------


def test_lane_words_drop_at_narrow_levels():
    """The engine-measured attack-#2 evidence at test scale: with the
    ladder on, the median level of 2pc rm=4 sorts at least 2x fewer lane
    words than the ladder-off engine, at identical counts and identical
    dispatch count (the acceptance-scale rm=6/7 A/B lives in
    tools/cand_ab.py)."""
    model = PackedTwoPhaseSys(4)
    off = _join(model.checker().spawn_xla(dedup="sorted", cand_ladder=1, **KW))
    on = _join(model.checker().spawn_xla(dedup="sorted", cand_ladder=3, **KW))
    assert _summary(on) == _summary(off)
    assert len(on.dispatch_log) == len(off.dispatch_log)
    lw_off = sorted(r["lane_words"] for r in off.level_log)
    lw_on = sorted(r["lane_words"] for r in on.level_log)
    med = len(lw_off) // 2
    assert lw_on[med] * 2 <= lw_off[med], (lw_on, lw_off)
    # Every row carries the chosen sub-widths, and no committed level
    # ever ran wider than the peak ladder-off shapes.
    peak_cand = max(r["cand_cap"] for r in off.level_log)
    for r in on.level_log:
        assert r["cand_cap"] <= peak_cand
        assert r["bucket"] <= max(cap for cap, _ in on.dispatch_log)


# --- per-checker candidate sizing (the aliasing fix) -------------------


def test_two_checkers_do_not_share_cand_caps():
    model = PackedTwoPhaseSys(3)
    model.__dict__.pop("_xla_cand_cap_hints", None)
    c1 = model.checker().spawn_xla(**KW)
    c2 = model.checker().spawn_xla(**KW)
    base = c2._cand_cap_for(1024)
    assert c1._cand_cap_for(1024) == base
    c1._grow_cand_cap(1024)
    assert c1._cand_cap_for(1024) == base * 4
    # The sibling's sizing is untouched mid-run (pre-fix the model-level
    # dict leaked the growth straight into c2's next dispatch shapes).
    assert c2._cand_cap_for(1024) == base
    # A FRESH checker inherits the learned growth via the model hint, so
    # the bench's measured pass still replays the warm pass's shapes.
    c3 = model.checker().spawn_xla(**KW)
    assert c3._cand_cap_for(1024) == base * 4


def test_grow_does_not_evict_live_sibling_programs():
    """The eviction half of the aliasing fix: the superstep cache stays
    model-shared (the bench's warm->measured handoff depends on it), so
    a growth in one checker must not delete compiled programs a LIVE
    sibling still sizes at the old cap — but once no live checker can
    reach a key, eviction resumes (stale executables are memory)."""
    import gc

    model = PackedTwoPhaseSys(3)
    model.__dict__.pop("_xla_cand_cap_hints", None)
    model.__dict__.pop("_xla_superstep_cache", None)
    c1 = model.checker().spawn_xla(**KW)
    c2 = model.checker().spawn_xla(**KW)
    base = c2._cand_cap_for(1024)
    key = (
        1024, base, c2._sym_tag, c2._max_probes, c2._dedup, c2._compaction,
    )
    c2._superstep_cache[key] = marker = object()
    c1._grow_cand_cap(1024)
    assert c1._cand_cap_for(1024) == base * 4
    # c2 still sizes bucket 1024 at base, so its program survived.
    assert c2._superstep_cache.get(key) is marker
    del c1, c2
    gc.collect()
    # With no live sibling at the old cap, the next growth cycle evicts:
    # re-grow from a fresh checker whose caps start at the hinted base*4.
    c3 = model.checker().spawn_xla(**KW)
    stale = (
        1024, base * 4, c3._sym_tag, c3._max_probes, c3._dedup,
        c3._compaction,
    )
    c3._superstep_cache[stale] = object()
    c3._grow_cand_cap(1024)
    assert stale not in c3._superstep_cache
    # ...while the base-cap key is simply not this growth's target.
    assert c3._superstep_cache.get(key) is marker


# --- knob plumbing and rung shapes -------------------------------------


def test_cand_ladder_validation():
    with pytest.raises(ValueError, match="cand_ladder"):
        PackedTwoPhaseSys(3).checker().spawn_xla(cand_ladder="sideways", **KW)
    with pytest.raises(ValueError, match="cand_ladder"):
        PackedTwoPhaseSys(3).checker().spawn_xla(
            cand_ladder=5, dedup="sorted", **KW
        )
    # Explicit ladder on the rows/hash engine is a config error (the
    # compaction-knob precedent: never silently measure the wrong engine).
    with pytest.raises(ValueError, match="plane-major"):
        PackedTwoPhaseSys(3).checker().spawn_xla(
            cand_ladder=3, dedup="hash", **KW
        )


def test_env_knob_and_hash_warning(monkeypatch):
    monkeypatch.setenv("STPU_CAND_LADDER", "2")
    c = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="sorted", **KW)
    assert c._cand_ladder_k == 2
    assert len(c._cand_rungs(1 << 14)) == 2
    # Env-driven A/B against the hash engine warns (arg raises above).
    monkeypatch.setenv("STPU_CAND_LADDER", "3")
    with pytest.warns(RuntimeWarning, match="STPU_CAND_LADDER"):
        c = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="hash", **KW)
    assert c._cand_ladder_k == 1


def test_rung_shapes():
    c = PackedTwoPhaseSys(3).checker().spawn_xla(dedup="sorted", **KW)
    assert c._cand_ladder_k == 3
    # Floor buckets have nothing to snug.
    assert c._cand_rungs(64) == [(64, c._cand_cap_for(64))]
    # The rung floor truncates K before the pow-4 ladder does.
    assert [F for F, _ in c._cand_rungs(1024)] == [256, 1024]
    rungs = c._cand_rungs(1 << 14)
    assert [F for F, _ in rungs] == [1 << 10, 1 << 12, 1 << 14]
    # Each rung is that bucket's own (rows, cand-cap) shape.
    assert all(C == c._cand_cap_for(F) for F, C in rungs)


def test_rung_caps_stay_monotone_after_subbucket_growth(monkeypatch):
    """A cc_ovf growth at a small bucket (paid on that bucket's own host
    dispatches) must not make a 'snug' rung carry a WIDER candidate
    buffer than the branch above it — the rungs clamp to a monotone
    envelope, so the ladder can only ever sort narrower, matching the
    invariant test_lane_words_drop_at_narrow_levels pins at runtime."""
    monkeypatch.setenv("STPU_CAND_FRAC", "16")  # accelerator-style start
    model = PackedTwoPhaseSys(3)
    model.__dict__.pop("_xla_cand_cap_hints", None)
    c = model.checker().spawn_xla(dedup="sorted", **KW)
    full_grid = c._next_pow2(1024 * c._A)
    while c._cand_cap_for(1024) < full_grid:
        c._grow_cand_cap(1024)
    assert c._cand_cap_for(1024) > c._cand_cap_for(4096)  # the hazard
    caps = [C for _, C in c._cand_rungs(4096)]
    assert caps == sorted(caps)
    assert caps[-1] == c._cand_cap_for(4096)


# --- registry #6 pre-flight: the chip program lowers for TPU -----------


def test_fused_ladder_lowers_for_tpu(monkeypatch):
    """Trace the accelerator-shaped K=3 fused program (sort-family
    values + sort compaction — the TPU defaults) and lower it for the
    TPU target from this CPU-only process. Catches missing lowerings for
    the new ``lax.switch``-around-big-sort shape without the chip; the
    registry-#4 class of RUNTIME fault can only be ruled out on chip
    (chip_smoke.py runs the K=3 default there)."""
    import jax
    import jax.numpy as jnp

    from stateright_tpu.ops import sortedset

    monkeypatch.setattr(sortedset, "VALUES_VIA", "sort")
    model = PackedTwoPhaseSys(3)
    c = model.checker().spawn_xla(
        dedup="sorted", compaction="sort", cand_ladder=3, **KW
    )
    rungs = tuple(c._cand_rungs(4096))
    assert len(rungs) == 3
    fn = jax.jit(c._build_fused(4096, rungs))
    args = (
        jnp.zeros((4096, model.state_words), jnp.uint32),
        jnp.zeros((4096,), jnp.uint32),
        jnp.int32(1),
        c._table,
        c._disc_found,
        c._disc_fp,
        jnp.int32(32),
        jnp.int32(2**31 - 1),
        jnp.zeros((len(c._prop_names),), jnp.bool_),
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(0),
    )
    lowered = fn.trace(*args).lower(lowering_platforms=("tpu",))
    assert lowered is not None
