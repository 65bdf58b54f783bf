"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole cell (2pc with 3 resource managers, on the CPU)
through the harness with one fault planted in the system under test: a
step that returns its state unchanged, half of each state's actions left
out, a count altered where it is produced, and a property verdict altered
where it is produced. One chip has no exchange between chips to leave out.
"""

import json
import os
import shutil
import time

import pytest

import compare
import driver
import harness
from conftest import BENCH, ROOT


@pytest.fixture(scope="module")
def rm3_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("rm3")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "configs/2pc-rm8.json")) as f:
        config = json.load(f)
    config.update(rm_count=3, spawn_xla={"frontier_capacity": 1024, "table_capacity": 4096})
    os.makedirs(root / "benchmark/configs")
    with open(root / "benchmark/configs/2pc-rm3.json", "w") as f:
        json.dump(config, f)
    bench["configs"].append({"name": "2pc-rm3", "source": "https://example.org",
                             "file": "benchmark/configs/2pc-rm3.json",
                             "reduced": ["rm_count"], "why": "a test"})
    bench["workloads"].append({"name": "2pc-rm3.full", "config": "2pc-rm3",
                               "traffic": "full", "chips": 1, "why": "a test"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    yield root
    shutil.rmtree(root, ignore_errors=True)


def _run(root):
    import jax

    events = driver.CompileEvents()
    events.install()
    return harness.run_cell(str(root), "2pc-rm3.full", 0.3, False,
                            jax.devices()[:1], events, time.monotonic())


def _failed(result):
    return [k for k, c in result["compared"].items() if c["value"] > c["limit"]]


def test_sound_run_is_correct(rm3_root):
    result = _run(rm3_root)
    assert result["correct"] and not _failed(result)


def test_step_returns_its_state(rm3_root, monkeypatch):
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    step = PackedTwoPhaseSys.packed_step

    def stuck(self, words):
        import jax.numpy as jnp

        nxt, valid = step(self, words)
        return jnp.broadcast_to(words, nxt.shape), valid

    monkeypatch.setattr(PackedTwoPhaseSys, "packed_step", stuck)
    result = _run(rm3_root)
    assert not result["correct"]
    assert {"generated_gap", "unique_gap", "verdict_mismatches"} <= set(_failed(result))


def test_half_of_the_actions_left_out(rm3_root, monkeypatch):
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    step = PackedTwoPhaseSys.packed_step

    def half(self, words):
        import jax.numpy as jnp

        nxt, valid = step(self, words)
        return nxt, valid & (jnp.arange(valid.shape[0]) % 2 == 0)

    monkeypatch.setattr(PackedTwoPhaseSys, "packed_step", half)
    result = _run(rm3_root)
    assert not result["correct"]
    assert "unique_gap" in _failed(result)


def test_count_altered(rm3_root, monkeypatch):
    from stateright_tpu.xla import XlaChecker

    count = XlaChecker.state_count
    monkeypatch.setattr(XlaChecker, "state_count", lambda self: count(self) + 1)
    result = _run(rm3_root)
    assert not result["correct"]
    assert _failed(result) == ["generated_gap"]


def test_verdict_altered(rm3_root, monkeypatch):
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    props = PackedTwoPhaseSys.packed_properties

    def flipped(self, words):
        p = props(self, words)
        return p.at[2].set(~p[2])  # "consistent" fails everywhere

    monkeypatch.setattr(PackedTwoPhaseSys, "packed_properties", flipped)
    result = _run(rm3_root)
    assert not result["correct"]
    assert "verdict_mismatches" in _failed(result)


def test_limits_are_exact():
    assert set(compare.LIMITS.values()) == {0}
