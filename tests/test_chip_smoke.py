"""``chip_smoke.py`` on the CPU: it refuses to run, and its phases are
right at small sizes (the accelerator's engine choices passed explicitly,
since ``jax.default_backend()`` is ``cpu`` here)."""

from __future__ import annotations

import chip_smoke


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert captured.out == ""


def test_2pc_phase_small():
    stats = chip_smoke.phase_2pc(
        3, 1 << 10, 1 << 12, dedup="sorted", compaction="sort"
    )
    assert (stats["generated"], stats["unique"]) == (1_146, 288)
    assert stats["audit_entries"] == 288
    assert {"warm_s", "steady_s"} <= stats.keys()


def test_paxos_phase():
    stats = chip_smoke.phase_paxos(2, 3, dedup="sorted", compaction="gather")
    assert (stats["generated"], stats["unique"]) == (32_971, 16_668)


def test_sharded_phase_small():
    """The ``--chips 4`` path on four of conftest's virtual CPU devices:
    pinned counts, and every table plane sharded over all four."""
    stats = chip_smoke.phase_sharded(4, 3, 1 << 10, 1 << 12, dedup="sorted")
    assert (stats["generated"], stats["unique"]) == (1_146, 288)


def test_2pc_phase_prewarmed_times_one_pass():
    """A model that ``main``'s pre-warm already ran gets one timed pass:
    the compile seconds live on the pre-warm line, not the phase's."""
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    kw = dict(frontier_capacity=1 << 10, table_capacity=1 << 12,
              dedup="sorted", compaction="sort")
    model = PackedTwoPhaseSys(3)
    model.checker().spawn_xla(**kw).join()
    stats = chip_smoke.phase_2pc(3, model=model, **kw)
    assert (stats["generated"], stats["unique"]) == (1_146, 288)
    assert "steady_s" in stats and "warm_s" not in stats
