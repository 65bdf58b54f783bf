"""The trace reduction's sums, on synthetic intervals and on a short trace
recorded on a TPU v5 lite (``testdata/``: one check of ``paxos-2c3s.full``
from a ``--trace 1`` run, cut to that check's span and to the lines the
reduction reads: the device's ``XLA Ops`` and ``XLA Modules`` and the
benchmark's own host annotations)."""

import glob
import gzip
import os

import pytest

import trace_reduce as tr
from conftest import BENCH


def test_union_clip_complement():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.complement([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_program_name():
    assert tr.program_name("jit_fused(17)") == "jit_fused"
    assert tr.program_name("jit_superstep") == "jit_superstep"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb.gz"))
    with gzip.open(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_recorded_trace_sums(recorded):
    from harness import ANNOTATIONS

    r = tr.reduce_profile(recorded, ANNOTATIONS)
    assert r is not None and r.devices == 1
    assert 0 < r.busy_s < r.window_s
    # Busy time and the idle gaps partition the window.
    assert r.busy_s + sum(s for _, s in r.gaps) == pytest.approx(r.window_s, rel=1e-9)
    assert {label for label, _ in r.gaps} <= set(ANNOTATIONS) | {"none"}
    by_annotation = r.idle_by_annotation()
    assert sum(s for _, s in by_annotation) == pytest.approx(r.window_s - r.busy_s)
    # Programs overlap no more than the device's own busy time allows.
    assert sum(r.programs.values()) <= r.window_s
    assert any("fused" in name or "superstep" in name for name in r.programs)
