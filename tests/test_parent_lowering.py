"""The sort-mode grid compaction recovers candidate parents by merge.

Under ``compaction="sort"`` the plane-major superstep recovers each
candidate's parent fingerprint and eventually-bits with two passes of
one candidate-scale sort and a prefix sum (``parents_by_merge`` in
``XlaChecker._build_superstep_planes``) where it compacts at least
``PARENT_MERGE_MIN`` lanes; the gather lowering indexes the frontier by
the compaction's permutation. Both must leave the same visited set,
counts, discoveries and next frontier, and match the host oracle where
one exists. The tests lower the threshold so that the merge runs at
their small shapes.
"""

import numpy as np
import pytest

from stateright_tpu import xla
from stateright_tpu.core import Property
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys, TwoPhaseSys
from stateright_tpu.test_util import DGraph, PackedDGraph
from stateright_tpu.xla_mux import MuxChecker

KW = dict(dedup="sorted", frontier_capacity=1 << 10, table_capacity=1 << 13)


@pytest.fixture
def merge_everywhere(monkeypatch):
    monkeypatch.setattr(xla, "PARENT_MERGE_MIN", 1)


def _graph():
    """Eventually-odd over a graph whose rows clear their ebit at
    different depths, so the candidates' ebits differ by parent."""
    return (
        DGraph.with_property(Property.eventually("odd", lambda _, s: s % 2 == 1))
        .with_path([0, 1, 4, 6])
        .with_path([2, 4, 8])
        .with_path([3, 10, 12, 14])
        .with_path([16, 18, 20, 22])
    )


def _left_behind(c):
    """What a check leaves that the parent recovery feeds: counts,
    discoveries, the visited set's keys and parent values, and the next
    frontier with its ebits."""
    n = c._frontier_count
    return (
        (c.state_count(), c.unique_state_count(), c.max_depth()),
        {name: p.into_actions() for name, p in c.discoveries().items()},
        [np.asarray(plane).tobytes() for plane in c._table],
        n,
        np.asarray(c._frontier)[:n].tobytes(),
        np.asarray(c._frontier_ebits)[:n].tobytes(),
    )


def _both(make, shape=lambda b: b, merge=True):
    out = {}
    for compaction in ("sort", "gather"):
        c = shape(make().checker()).spawn_xla(compaction=compaction, **KW).join()
        assert c.metrics()["parent_lowering"] == (
            "merge" if compaction == "sort" and merge else "gather"
        )
        out[compaction] = c
    return out["sort"], out["gather"]


@pytest.mark.parametrize("target", [None, 2000], ids=["full", "mid"])
def test_merge_matches_gather_2pc_rm4(merge_everywhere, target):
    """Whole check, and one stopped mid-search by a state-count target
    (level-granular), which leaves a next frontier to compare."""
    shape = (
        (lambda b: b) if target is None else (lambda b: b.target_state_count(target))
    )
    merge, gather = _both(lambda: PackedTwoPhaseSys(4), shape)
    assert _left_behind(merge) == _left_behind(gather)
    if target is None:
        host = TwoPhaseSys(4).checker().spawn_bfs().join()
        assert merge.unique_state_count() == host.unique_state_count() == 1568
        assert merge.state_count() == host.state_count() == 8258
        assert set(merge.discoveries()) == set(host.discoveries())
    else:
        assert merge._frontier_count > 0


def test_merge_matches_gather_eventually_graph(merge_everywhere):
    merge, gather = _both(lambda: PackedDGraph(_graph()))
    assert _left_behind(merge) == _left_behind(gather)
    host = _graph().checker().spawn_bfs().join()
    assert merge.unique_state_count() == host.unique_state_count()
    assert merge.state_count() == host.state_count()
    assert (
        merge.discovery("odd").into_states()
        == host.discovery("odd").into_states()
    )


def test_merge_matches_gather_in_mux(merge_everywhere):
    """``MuxChecker`` vmaps the same superstep: every lane of a
    sort-compaction batch ends as the gather lowering's solo run."""
    model = PackedTwoPhaseSys(3)
    solo = _left_behind(model.checker().spawn_xla(compaction="gather", **KW).join())
    lanes = [model.checker().spawn_xla(compaction="sort", **KW) for _ in range(3)]
    MuxChecker(lanes).run_to_completion()
    for lane in lanes:
        assert lane.metrics()["parent_lowering"] == "merge"
        assert _left_behind(lane) == solo


def test_merge_engages_at_wide_candidate_buffers():
    """Below ``PARENT_MERGE_MIN`` compacted lanes the sort lowering keeps
    its gathers (each merge sort compiles to megabytes of code resident in
    device memory), and still matches the gather compaction; from it on,
    the merge. Other compactions gather."""
    wide = xla.PARENT_MERGE_MIN
    assert xla.parent_lowering("sort", 1 << 18, 1 << 22, 42) == "merge"
    assert xla.parent_lowering("sort", wide // 42 + 1, wide, 42) == "merge"
    assert xla.parent_lowering("sort", 1 << 18, wide // 2, 42) == "gather"
    assert xla.parent_lowering("sort", 1 << 10, 1 << 22, 42) == "gather"
    assert xla.parent_lowering("gather", 1 << 18, 1 << 22, 42) == "gather"
    sort, gather = _both(lambda: PackedTwoPhaseSys(4), merge=False)
    assert _left_behind(sort) == _left_behind(gather)
