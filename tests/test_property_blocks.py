"""The planes superstep's property stage in row blocks over live rows
(``xla.blocked_properties``): the block width each model derives, exact
agreement with the whole-bucket stage, the ``property_rows`` counter, and
the cell models that stay on the whole-bucket lowering."""

from __future__ import annotations

import functools
import json
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu import xla
from stateright_tpu.models.paxos import PackedPaxos
from stateright_tpu.models.single_copy_register import PackedSingleCopyRegister
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys
from stateright_tpu.semantics import device


def test_block_rows_follow_the_serializers_lanes():
    """Rows times the serializer's lanes a row fit the lane budget: the
    pattern count while the patterns run at once (paxos, 3 clients), the
    progress lattice's nodes times the value domain past that (4 clients:
    4^4 nodes x 16 values)."""
    cases = [
        (PackedPaxos(2, 3), 20, 65536),
        (PackedSingleCopyRegister(3, 1), 1680, 1024),
        (PackedSingleCopyRegister(4, 1), 4096, 512),
    ]
    for model, lanes, rows in cases:
        assert device.row_lanes(model._hist) == lanes
        assert model.property_block_rows == rows
        assert rows * lanes <= device.ROW_LANE_BUDGET < 2 * rows * lanes
    assert not hasattr(PackedTwoPhaseSys(3), "property_block_rows")


@functools.lru_cache(maxsize=None)
def _reachable_rows(servers: int) -> np.ndarray:
    """Every reachable state of the 3-client register with ``servers``
    servers, packed, in breadth-first order."""
    model = PackedSingleCopyRegister(3, servers)
    inner = model._inner
    states = list(inner.init_states())
    seen = set(states)
    queue = deque(states)
    while queue:
        for _action, nxt in inner.next_steps(queue.popleft()):
            if nxt not in seen:
                seen.add(nxt)
                states.append(nxt)
                queue.append(nxt)
    return np.stack([model.pack(s) for s in states])


@pytest.mark.parametrize("servers", [1, 2])
@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_blocked_properties_agree_row_for_row(servers, seed):
    """Blocks of 16 over 203 live rows of a 256-row frontier: every live
    row reads what the whole-bucket vmap reads, every other row the value
    that flags nothing. Two servers reach non-linearizable states."""
    model = PackedSingleCopyRegister(3, servers)
    rows = _reachable_rows(servers)
    pick = np.random.default_rng(seed).choice(len(rows), 256, replace=False)
    frontier = jnp.asarray(rows[pick])
    f_count = 203
    neutral = jnp.asarray([True, False])  # always, sometimes
    whole = np.asarray(jax.jit(jax.vmap(model.packed_properties))(frontier))
    blocked = np.asarray(jax.jit(
        lambda f, n: xla.blocked_properties(model.packed_properties, f, n, 16, neutral)
    )(frontier, jnp.int32(f_count)))
    assert (blocked[:f_count] == whole[:f_count]).all()
    assert (blocked[f_count:] == np.asarray(neutral)).all()
    if servers == 2:
        assert not whole[:f_count, 0].all()


def test_property_rows_count_live_blocks(tmp_path):
    """Blocks of 4 rows, under every bucket: the stage evaluates
    ceil(f_count / 4) blocks a level, and each dispatch span carries its
    levels' share."""
    model = PackedSingleCopyRegister(2, 1)
    model.property_block_rows = 4
    trace = tmp_path / "trace.jsonl"
    c = model.checker().spawn_xla(
        frontier_capacity=1 << 8, table_capacity=1 << 10, dedup="sorted",
        trace=str(trace),
    ).join()
    assert (c.state_count(), c.unique_state_count()) == (121, 93)
    c.assert_properties()
    m = c.metrics()
    assert m["property_block_rows"] == 4
    want = sum(-(-lv["frontier"] // 4) * 4 for lv in c.level_log)
    assert m["property_rows"] == want
    assert any(lv["frontier"] % 4 for lv in c.level_log)
    spans = [json.loads(line) for line in trace.read_text().splitlines()]
    dispatches = [r["attrs"] for r in spans if r["name"] == "dispatch"]
    assert sum(a["property_rows"] for a in dispatches) == want
    assert {a["property_block_rows"] for a in dispatches} == {4}


def test_three_client_full_check_in_blocks():
    """The exact 3-thread tester in blocks of 64 rows at every bucket and
    ladder rung above 64: the full check's counts and verdicts stand."""
    model = PackedSingleCopyRegister(3, 1)
    model.property_block_rows = 64
    c = model.checker().spawn_xla(
        frontier_capacity=1 << 10, table_capacity=1 << 14, dedup="sorted"
    ).join()
    c.assert_properties()
    assert (c.state_count(), c.unique_state_count()) == (6778, 4243)
    m = c.metrics()
    assert m["property_block_rows"] == 64
    assert m["property_rows"] == sum(
        xla.property_rows(lv["frontier"], lv["bucket"], 64) for lv in c.level_log
    )
    assert any(lv["bucket"] > 64 and lv["frontier"] > 64 for lv in c.level_log)
    assert m["property_rows"] < sum(lv["bucket"] for lv in c.level_log)


def _fused_text(model, bucket: int, **caps):
    """A planes-engine checker of ``model`` and the text of its fused
    program at ``bucket``, lowered with location metadata (named scopes
    included)."""
    c = model.checker().spawn_xla(dedup="sorted", **caps)
    f_in, e_in = c._bucket_inputs(bucket)
    args = (
        f_in, e_in, jnp.int32(c._frontier_count), c._table, c._disc_found,
        c._disc_fp, jnp.int32(32), jnp.int32(2**31 - 1),
        jnp.zeros(c._P, bool), jnp.int32(0), jnp.int32(0), jnp.int32(0),
    )
    fn = jax.jit(c._build_fused(bucket, c._cand_rungs(bucket)))
    return c, fn.trace(*args).lower().as_text(debug_info=True)


@pytest.mark.parametrize("case", ["paxos-2c3s", "2pc-rm8"])
def test_cell_models_run_unblocked(case):
    """At the benchmark cells' buckets the property stage stays one vmap
    over the bucket: no ``serialize`` scope in the program."""
    if case == "paxos-2c3s":
        c, text = _fused_text(
            PackedPaxos(2, 3), 4096, frontier_capacity=4096,
            table_capacity=1 << 16, compaction="gather",
        )
    else:
        c, text = _fused_text(
            PackedTwoPhaseSys(8), 1 << 19, frontier_capacity=1 << 19,
            table_capacity=1 << 22, compaction="sort",
        )
    assert c.metrics()["property_block_rows"] == 0
    assert "properties" in text and "serialize" not in text


def test_blocked_stage_lowers_under_serialize():
    model = PackedSingleCopyRegister(2, 1)
    model.property_block_rows = 16
    _c, text = _fused_text(model, 256, frontier_capacity=256, table_capacity=1 << 10)
    assert "properties/serialize" in text
