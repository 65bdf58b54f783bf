"""The stage and idle reduction (``stage_trace.py``) on two traces recorded
on a TPU v5 lite, each one check of ``paxos-2c3s.full`` cut from a
``--trace 1`` run: ``*.staged.xspace.pb.gz`` from a program that names its
superstep stages and puts its spans on the profiler's host plane, and the
older ``*.xplane.pb.gz`` from one that does neither (the metrics that read
this then report nothing)."""

import glob
import os
import types

import pytest

import harness
import stage_trace as st
from conftest import BENCH

STAGED = os.path.join(BENCH, "testdata", "paxos-2c3s.one-check.staged.xspace.pb.gz")
METRICS = ("expand_ms_per_level", "compact_ms_per_level", "fingerprint_ms_per_level",
           "insert_ms_per_level", "properties_ms_per_level", "spawn_idle_ms_per_check",
           "host_ms_per_dispatch")


@pytest.fixture(scope="module")
def staged():
    return st.reduce_xspace(st.load_xspace(STAGED), harness.ANNOTATIONS)


@pytest.fixture(scope="module")
def unstaged():
    (path,) = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb.gz"))
    return st.reduce_xspace(st.load_xspace(path), harness.ANNOTATIONS)


def test_self_times_subtract_nested_events():
    # A while (0-10) holding a cond (1-9) holding two ops, then a sibling.
    ops = [(0, 10, 0), (1, 9, 1), (2, 4, 2), (5, 8, 3), (11, 12, 4)]
    assert st._self_times(ops) == [2, 3, 2, 3, 1]


def test_every_stage_gets_time(staged):
    assert staged.named
    for stage in ("properties", "expand", "compact", "fingerprint", "insert", "ladder"):
        assert staged.stage_s[stage] > 0, stage
    # Paxos has no eventually property: its terminal pass compiles to nothing.
    assert staged.stage_s["terminal"] == 0


def test_stages_sum_to_the_superstep_programs(staged):
    total = sum(staged.stage_s.values())
    assert total == pytest.approx(staged.superstep_s, rel=0.02)
    assert staged.stage_s[st.UNNAMED] < 0.1 * staged.superstep_s


def test_idle_gaps_carry_engine_spans(staged):
    labels = {label for label, _h, _s in staged.gaps}
    assert labels & {"spawn:init_insert", "dispatch:commit"}
    assert {"spawn", "dispatch:prepare", "dispatch:run", "dispatch:commit"} <= set(staged.spans)
    assert staged.spans["dispatch"] == staged.spans["dispatch:run"]
    share = staged.engine_share()
    assert share is not None and 0 < share <= 1


def _run(cell="paxos-2c3s.full", levels=30, dispatches=2):
    check = types.SimpleNamespace(levels=levels, dispatches=dispatches, start=0.0)
    return types.SimpleNamespace(cell=types.SimpleNamespace(name=cell), checks=[check],
                                 trace=object(), setup_s=1.0)


def _read(name, run, reduction, monkeypatch):
    monkeypatch.setattr(st, "for_run", lambda _run: reduction)
    module = harness.load_module(os.path.join(BENCH, "metrics", name + ".py"))
    return module.read(run)


@pytest.mark.parametrize("name", METRICS)
def test_metrics_read_the_staged_trace(name, staged, monkeypatch):
    value = _read(name, _run(), staged, monkeypatch)
    assert value is not None and value > 0


@pytest.mark.parametrize("name", METRICS)
def test_metrics_report_nothing_without_names(name, unstaged, monkeypatch):
    assert _read(name, _run(), unstaged, monkeypatch) is None


def test_untraced_run_reads_nothing():
    run = _run()
    run.trace = None
    assert st.for_run(run) is None
    assert st.stage_ms_per_level(run, ("expand",)) is None


def test_compile_log_reader(monkeypatch):
    import sys

    obs = types.SimpleNamespace(compile_log=[
        ("jaxpr_trace_duration", 2.0, 1.0),           # [1, 2]
        ("jaxpr_to_mlir_module_duration", 3.0, 0.5),  # [2.5, 3]
        ("cache_retrieval_time_sec", 4.0, 0.25),      # [3.75, 4]
        ("jaxpr_trace_duration", 11.0, 1.0),          # after the window's start
    ])
    run = _run()
    run.checks[0].start = 10.0
    monkeypatch.setitem(sys.modules, "stateright_tpu.obs", obs)
    lowering = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration")
    assert st.compile_log_s(run, lowering) == pytest.approx(1.5)
    assert st.compile_log_s(run, ("cache_retrieval_time_sec",)) == pytest.approx(0.25)
    monkeypatch.setitem(sys.modules, "stateright_tpu.obs", types.SimpleNamespace())
    assert st.compile_log_s(run, lowering) is None
