"""The single-copy register's plain reference reproduces the upstream's
pinned counts and the system's, the system agrees with it number for
number at three clients, and the ``scr-4c1s.full`` control comes out not
correct. The 4-thread tester the cell runs gives the reference's verdict
on histories that are not linearizable, which no 4c/1s check can show.
Also the ``serialize`` scope reader (``scope_trace.py``) on a recorded
trace."""

import random
import time

import numpy as np
import pytest

import compare
import driver
import scope_trace
import stage_trace
from conftest import ROOT
from control import control_numbers
from reference import single_copy_register as scr
from test_stage_trace import STAGED


@pytest.mark.parametrize("clients, generated, unique", [
    (2, 121, 93), (3, 6_778, 4_243), (4, 731_789, 400_233),
])
def test_counts(clients, generated, unique):
    ref = scr.explore({"client_count": clients, "server_count": 1})
    assert (ref["generated"], ref["unique"]) == (generated, unique)
    assert ref["discoveries"] == {"value chosen": 4}


def test_two_servers_are_not_linearizable():
    # Upstream's second configuration: a stale read off the other copy.
    ref = scr.explore({"client_count": 2, "server_count": 2})
    assert set(ref["discoveries"]) == {"linearizable", "value chosen"}


def test_system_agrees_at_three_clients():
    """``PackedSingleCopyRegister(3, 1)`` on the planes engine, blocks of
    its tester engaged at the top bucket: every compared number is 0."""
    from stateright_tpu.models.single_copy_register import PackedSingleCopyRegister

    params = {"client_count": 3, "server_count": 1}
    model = PackedSingleCopyRegister(3, 1)
    caps = {"frontier_capacity": 1 << 11, "table_capacity": 1 << 14, "dedup": "sorted"}
    check, checker = driver.one_check(model, caps, time.monotonic())
    assert checker.metrics()["property_block_rows"] == 1024
    ref = scr.explore(params)
    bad = compare.witnesses(compare.discovery_paths(checker), scr, params, ref)
    numbers = compare.compare([check], ref, compare.audit_table(checker), bad)
    assert numbers == {k: 0 for k in compare.LIMITS}


def _walk_states(model, walks: int, seed: int):
    """The distinct object-level states of ``walks`` seeded random walks
    from the initial state of ``model``'s host form, each to a state with
    no successor."""
    rng = random.Random(seed)
    inner = model._inner
    seen = {}
    for _ in range(walks):
        state = rng.choice(list(inner.init_states()))
        while True:
            seen.setdefault(state, None)
            nxt = [s for _a, s in inner.next_steps(state)]
            if not nxt:
                break
            state = rng.choice(nxt)
    return list(seen)


def _tester_verdicts(model, states, block: int):
    """Column 0 of ``packed_properties`` over ``states``, evaluated as the
    cell's property stage does: ``blocked_properties`` in blocks of
    ``block`` over the live rows of a wider frontier."""
    import jax
    import jax.numpy as jnp

    from stateright_tpu import xla

    rows = np.stack([model.pack(s) for s in states])
    n = len(rows)
    F = -(-n // block) * block + block
    frontier = jnp.asarray(np.concatenate([rows, np.zeros((F - n, rows.shape[1]), rows.dtype)]))
    neutral = jnp.asarray([True, False])
    out = jax.jit(lambda f, c: xla.blocked_properties(
        model.packed_properties, f, c, block, neutral))(frontier, jnp.int32(n))
    return np.asarray(out)[:n, 0].tolist()


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_four_thread_tester_holds_to_the_reference(seed):
    """Every 4c/1s history is linearizable, so a check of the cell cannot
    tell a right tester from one that accepts too much. On two servers
    (stale reads) the tester the cell runs, the progress lattice in its
    blocks of 512, gives the reference's verdict on every state of seeded
    walks; the controls, the same tester with real-time order dropped
    (``consistency="sequential"``) and one that accepts everything, each
    disagree with the reference on some of them."""
    from stateright_tpu.models.single_copy_register import PackedSingleCopyRegister
    from stateright_tpu.semantics import device

    model = PackedSingleCopyRegister(4, 2)
    assert device.pattern_count(4, model.MAX_OPS) > device.MAX_PATTERNS
    block = PackedSingleCopyRegister(4, 1).property_block_rows
    assert block == model.property_block_rows == 512
    states = _walk_states(model, 120, seed)
    want = [scr._linearizable(scr.from_program(s, 2)[2]) for s in states]
    got = _tester_verdicts(model, states, block)
    assert len(states) % block and got == want
    seqcst = _tester_verdicts(
        PackedSingleCopyRegister(4, 2, consistency="sequential"), states, block)
    assert sum(s != w for s, w in zip(seqcst, want)) > 0
    assert not all(want)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_control_is_not_correct(seed):
    numbers = control_numbers(ROOT, "scr-4c1s.full", seed)
    assert not compare.is_correct(numbers)
    assert numbers["unique_gap"] > 0


def test_scope_reader_on_a_recorded_trace():
    """Paxos's ``properties`` stage nests no other stage, so the scope
    reader finds the stage reduction's time under that name; the program
    has no ``serialize`` scope."""
    xs = stage_trace.load_xspace(STAGED)
    staged = stage_trace.reduce_xspace(xs)
    assert scope_trace.scope_s(xs, "properties") == pytest.approx(
        staged.stage_s["properties"], rel=1e-9)
    assert scope_trace.scope_s(xs, "serialize") == 0
