"""Bucket-ladder policy: "jump" (growth-extrapolated rung skipping) vs
"ramp" (one power-of-four rung per overflow).

Each distinct run bucket is a separate XLA compilation of the full
superstep program, and compile cost is dominated by program complexity,
not bucket size (round-5 measurement: paxos 2c/3s ~11 s/bucket on 1-core
CPU at every bucket from 64 to 4096) — so skipped rungs are pure
time-to-first-result savings. Counts are bucket-independent: both
policies must land the pinned exact counts.
"""

import pytest

from stateright_tpu.core import Model
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys


def _run(ladder, model, **kw):
    checker = model.checker().spawn_xla(ladder=ladder, **kw)
    while not checker.is_done():
        checker._run_block()
    return checker


KW = dict(frontier_capacity=1 << 12, table_capacity=1 << 14)


def test_jump_compiles_fewer_buckets_same_counts():
    ramp = _run("ramp", PackedTwoPhaseSys(4), **KW)
    jump = _run("jump", PackedTwoPhaseSys(4), **KW)
    pinned = (8_258, 1_568)
    assert (ramp.state_count(), ramp.unique_state_count()) == pinned
    assert (jump.state_count(), jump.unique_state_count()) == pinned
    ramp_buckets = ramp._compiled_run_caps()
    jump_buckets = jump._compiled_run_caps()
    assert len(jump_buckets) < len(ramp_buckets), (jump_buckets, ramp_buckets)


def test_second_pass_compiles_nothing_new():
    """The measured pass must ride the warm pass's compilations: same
    model, same policy => the bucket set cannot grow on pass 2."""
    model = PackedTwoPhaseSys(4)
    warm = _run("jump", model, **KW)
    warm_buckets = set(warm._compiled_run_caps())
    measured = _run("jump", model, **KW)
    assert set(measured._compiled_run_caps()) == warm_buckets
    assert (measured.state_count(), measured.unique_state_count()) == (8_258, 1_568)


def test_deep_narrow_space_stays_on_the_floor_bucket():
    """A space that never widens past the 64-row floor must not jump:
    the floor-64 win for consistency-tester shapes (round 4) is invariant
    under the ladder policy."""
    from stateright_tpu.models.increment_lock import PackedIncrementLock

    for ladder in ("ramp", "jump"):
        checker = _run(
            ladder,
            PackedIncrementLock(3),
            frontier_capacity=1 << 10,
            table_capacity=1 << 13,
        )
        assert checker._compiled_run_caps() == {64}
        assert checker.state_count() == 61


def test_ladder_validation():
    with pytest.raises(ValueError, match="ladder"):
        PackedTwoPhaseSys(3).checker().spawn_xla(ladder="sideways", **KW)


def assert_tail_downshift(dispatch_log):
    """At least one dispatch after the peak bucket ran below it (the
    shrink-exit fired). Shared by the delta-interplay test."""
    caps = [cap for cap, _ in dispatch_log]
    peak = max(caps)
    after_peak = caps[caps.index(peak) + 1 :]
    assert after_peak and min(after_peak) < peak, dispatch_log


def test_tail_shrink_exit_redispatches_snug():
    """Once the frontier collapses past the peak, the fused loop must hand
    the tail levels back to smaller already-compiled buckets (the
    shrink-exit) instead of paying the peak bucket's grid sort per level —
    and the downshift must never compile a new bucket or change counts."""
    for ladder in ("ramp", "jump"):
        model = PackedTwoPhaseSys(4)
        checker = model.checker().spawn_xla(ladder=ladder, **KW)
        # Spy on program-cache misses per dispatch: a fresh cache key
        # appearing in a dispatch AFTER the peak bucket's first dispatch
        # would mean the downshift compiled a new bucket.
        orig = checker._fused_for
        miss_log = []

        def spying_fused_for(f_cap):
            before = set(checker._superstep_cache)
            fn = orig(f_cap)
            miss_log.append((f_cap, bool(set(checker._superstep_cache) - before)))
            return fn

        checker._fused_for = spying_fused_for
        while not checker.is_done():
            checker._run_block()
        assert (checker.state_count(), checker.unique_state_count()) == (
            8_258,
            1_568,
        ), ladder
        # The 2pc tail collapses to single digits: at least one tail
        # dispatch must run below the peak bucket...
        assert_tail_downshift(checker.dispatch_log)
        # ...with every post-peak dispatch a pure cache hit.
        caps = [cap for cap, _ in checker.dispatch_log]
        peak = max(caps)
        past_peak = False
        for f_cap, missed in miss_log:
            if f_cap == peak:
                past_peak = True
            elif past_peak:
                assert not missed, (ladder, miss_log)


class _StarModel(Model):
    """Synthetic PackedModel: one root fanning out to ``fan`` leaves in a
    single level. With fan > 64 the depth-1 level overflows the 64-row
    floor bucket while the stored frontier is a single row — the shape
    whose post-grow shrink threshold (64 // 4 = 16) exceeds the frontier."""

    def __init__(self, fan=80):
        self.fan = fan
        self.state_words = 1
        self.max_actions = fan

    def init_states(self):
        return [0]

    def actions(self, state, actions):
        if state == 0:
            actions.extend(range(self.fan))

    def next_state(self, state, action):
        return action + 1

    def pack(self, state):
        import numpy as np

        return np.asarray([state], np.uint32)

    def unpack(self, words):
        return int(words[0])

    def packed_init(self):
        import numpy as np

        return np.zeros((1, 1), np.uint32)

    def packed_step(self, words):
        import jax.numpy as jnp

        at_root = words[0] == 0
        nxt = jnp.arange(1, self.fan + 1, dtype=jnp.uint32)[:, None]
        valid = jnp.broadcast_to(at_root, (self.fan,))
        return nxt, valid

    def packed_properties(self, words):
        import jax.numpy as jnp

        return jnp.zeros((0,), jnp.bool_)


def test_overflow_grow_never_stalls_at_level_zero():
    """A frontier overflow can leave the stored frontier at or below the
    grown dispatch's shrink threshold (star root: 1 row overflows the
    64-row floor with 300 uniques; two grow rounds land at bucket 1024,
    whose threshold 256 // 4 = 64 >= 1 — fan must exceed 256 because
    buckets <= 256 never set a shrink threshold). The fused loop's
    committed==0 bypass must keep such an entry committing its first
    level; without it the checker livelocks (level-0 stall -> break ->
    identical re-entry, forever)."""
    checker = _StarModel(300).checker().spawn_xla(
        frontier_capacity=1 << 10, table_capacity=1 << 12
    )
    for _ in range(20):
        if checker.is_done():
            break
        checker._run_block()
    assert checker.is_done(), checker.dispatch_log
    assert checker.unique_state_count() == 301
    assert checker.state_count() == 301  # init + 300 generated leaves
    # Dequeue-time depth bookkeeping (bfs.rs:257-272): the terminal
    # leaves' frontier is counted at depth 2 before being found empty.
    assert checker.max_depth() == 2


def test_shrink_exit_off_never_downshifts():
    """``shrink_exit='off'`` (the accelerator auto: each tail downshift
    is a host round-trip, and on an earlier chip setup the rm=8 A/B
    measured the re-dispatch RTT above the snug-sort savings) must keep the
    dispatch caps nondecreasing with counts unchanged."""
    model = PackedTwoPhaseSys(4)
    checker = model.checker().spawn_xla(
        ladder="ramp", shrink_exit="off", **KW
    )
    while not checker.is_done():
        checker._run_block()
    assert (checker.state_count(), checker.unique_state_count()) == (8_258, 1_568)
    caps = [cap for cap, _ in checker.dispatch_log]
    assert caps == sorted(caps), checker.dispatch_log


def test_shrink_exit_validation():
    with pytest.raises(ValueError, match="shrink_exit"):
        PackedTwoPhaseSys(3).checker().spawn_xla(shrink_exit="maybe", **KW)
