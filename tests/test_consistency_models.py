"""Consistency-model variants of the single-copy register on the device
engine: sequential consistency end-to-end, N-client device-exact
linearizability, and the host-verified fallback past the interleaving
budget.

The reference defines ``SequentialConsistencyTester``
(sequential_consistency.rs:53-241) but wires no example to it; here the
single-copy register runs under either tester, on both engines, with parity
between them. ``device_exact=False`` (the default past
``semantics.device.MAX_PATTERNS_EXACT``, i.e. 5+ clients) exercises the
engine's ``host_verified_properties`` path with a diverse-subsample
conservative predicate — its first real (non-synthetic) customer.
"""

import pytest

from stateright_tpu.models.single_copy_register import (
    PackedSingleCopyRegister,
    single_copy_register_model,
)


def test_sc_one_server_full_coverage_parity():
    # One copy is linearizable, hence sequentially consistent: full
    # coverage, and the SC history (no prereq snapshots) collapses states
    # exactly like the host tester's equality (57 < the lin config's 93).
    c = (
        PackedSingleCopyRegister(2, 1, consistency="sequential")
        .checker()
        .spawn_xla(frontier_capacity=1 << 10, table_capacity=1 << 12)
        .join()
    )
    c.assert_properties()
    h = (
        single_copy_register_model(2, 1, consistency="sequential")
        .checker()
        .spawn_bfs()
        .join()
    )
    assert (c.state_count(), c.unique_state_count()) == (
        h.state_count(),
        h.unique_state_count(),
    )
    assert c.unique_state_count() == 57


def test_sc_two_servers_counterexample_parity():
    # Two copies violate SC as well (a client can read back None after its
    # own completed write — no serial order allows it): both engines find a
    # depth-minimal witness whose final history the host serializer rejects.
    c = (
        PackedSingleCopyRegister(2, 2, consistency="sequential")
        .checker()
        .spawn_xla(frontier_capacity=1 << 10, table_capacity=1 << 12)
        .join()
    )
    h = (
        single_copy_register_model(2, 2, consistency="sequential")
        .checker()
        .spawn_bfs()
        .join()
    )
    pc = c.discoveries()["sequentially consistent"]
    ph = h.discoveries()["sequentially consistent"]
    assert len(pc) == len(ph)
    assert pc.last_state().history.serialized_history() is None


def test_three_client_device_exact_full_coverage():
    # T=3 linearizability fully on device (1,680 interleavings/state):
    # exact count parity with the host oracle (BASELINE.md: 6,778/4,243).
    m = PackedSingleCopyRegister(3, 1)
    assert not getattr(m, "host_verified_properties", None)
    c = m.checker().spawn_xla(
        frontier_capacity=1 << 11, table_capacity=1 << 14
    ).join()
    c.assert_properties()
    assert (c.state_count(), c.unique_state_count()) == (6778, 4243)


@pytest.mark.slow
def test_four_client_host_verified_bounded_parity():
    # 4 threads = 369,600 interleavings. Since round 4 the default is
    # device-EXACT (chunked scan); device_exact=False pins the engine's
    # host_verified_properties machinery — the sampled one-sided device
    # predicate with host-serializer confirmation of flagged rows (the
    # production path for 5+ clients). Bounded-depth counts must still
    # match the oracle exactly.
    m = PackedSingleCopyRegister(4, 1, device_exact=False)
    assert m.host_verified_properties == frozenset({"linearizable"})
    c = (
        m.checker()
        .target_max_depth(6)
        .spawn_xla(
            frontier_capacity=1 << 12,
            table_capacity=1 << 15,
            host_verified_cap=4096,
        )
        .join()
    )
    h = (
        single_copy_register_model(4, 1)
        .checker()
        .target_max_depth(6)
        .spawn_bfs()
        .join()
    )
    assert (c.state_count(), c.unique_state_count()) == (
        h.state_count(),
        h.unique_state_count(),
    )
    assert "linearizable" not in c.discoveries()


@pytest.mark.slow
def test_four_client_host_verified_finds_real_counterexample():
    # 4c/2s reaches genuinely non-linearizable states: the hv path must
    # confirm one through the host serializer at the oracle's witness depth.
    c = (
        PackedSingleCopyRegister(4, 2, device_exact=False)
        .checker()
        .spawn_xla(
            frontier_capacity=1 << 12,
            table_capacity=1 << 15,
            host_verified_cap=4096,
        )
        .join()
    )
    h = single_copy_register_model(4, 2).checker().spawn_bfs().join()
    pc = c.discoveries()["linearizable"]
    assert len(pc) == len(h.discoveries()["linearizable"])
    assert pc.last_state().history.serialized_history() is None


@pytest.mark.slow
def test_four_client_device_exact_bounded_parity():
    # The round-4 widened regime: 4 clients checked device-EXACT (369,600
    # interleavings, decided by the progress-lattice search) with no host
    # fallback — bounded-depth counts match the oracle and nothing is
    # flagged.
    m = PackedSingleCopyRegister(4, 1)
    assert not getattr(m, "host_verified_properties", None)
    c = (
        m.checker()
        .target_max_depth(6)
        .spawn_xla(frontier_capacity=1 << 12, table_capacity=1 << 15)
        .join()
    )
    h = (
        single_copy_register_model(4, 1)
        .checker()
        .target_max_depth(6)
        .spawn_bfs()
        .join()
    )
    assert (c.state_count(), c.unique_state_count()) == (
        h.state_count(),
        h.unique_state_count(),
    )
    assert "linearizable" not in c.discoveries()
