"""The plain references reproduce the upstream's pinned counts, and each
cell's control comes out not correct at the cell's own size."""

import pytest

import compare
from conftest import ROOT
from control import control_numbers
from reference import paxos, twopc


@pytest.mark.parametrize("rm, generated, unique", [
    (3, 1_146, 288), (5, 58_146, 8_832), (8, 18_507_778, 1_745_408),
])
def test_twopc_counts(rm, generated, unique):
    ref = twopc.explore({"rm_count": rm})
    assert (ref["generated"], ref["unique"]) == (generated, unique)
    assert set(ref["discoveries"]) == {"abort agreement", "commit agreement"}


def test_paxos_counts():
    ref = paxos.explore({"client_count": 2, "server_count": 3})
    assert (ref["generated"], ref["unique"]) == (32_971, 16_668)
    assert ref["discoveries"] == {"value chosen": 9}


@pytest.mark.parametrize("workload", ["2pc-rm8.full", "paxos-2c3s.full"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_control_is_not_correct(workload, seed):
    numbers = control_numbers(ROOT, workload, seed)
    assert not compare.is_correct(numbers)
    assert numbers["unique_gap"] > 0
