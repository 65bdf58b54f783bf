"""The TPU compiler compiles main-path programs for a described v5e.

No chip is attached: ``topologies.get_topology_desc`` describes one, and
``jit(...).lower(shapes).compile()`` runs the chip's own compiler, which
refuses what the chip would refuse (a Pallas block not aligned to XLA's
tiling, a program that does not fit). Code that asks
``jax.default_backend()`` sees ``cpu`` here, so each test steers those
branches to the accelerator's by patching the query while it builds and
lowers. Shapes are small: the TPU compiler's time grows with sort sizes.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def accel_branches(monkeypatch):
    """Every ``jax.default_backend()`` site takes its accelerator branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(args, sharding):
    def one(x):
        a = np.asarray(x)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    return jax.tree.map(one, args)


def _compile(fn, args, sharding):
    compiled = jax.jit(fn).lower(*_shapes(args, sharding)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30  # one v5e chip's HBM
    return compiled


def test_sortedset_insert_compiles(one_chip, accel_branches):
    from stateright_tpu.ops import sortedset

    cap, m = 1 << 10, 1 << 10
    table = sortedset.make(cap, np)
    batch = [np.zeros(m, np.uint32)] * 4 + [np.zeros(m, bool)]

    def insert(table, kh, kl, vh, vl, active):
        return sortedset.insert(table, kh, kl, vh, vl, active)

    _compile(insert, (table, *batch), one_chip)


def test_pallas_compaction_compiles(one_chip):
    from stateright_tpu.ops.pallas_compact import compact_pallas_staged

    M, cap = 1 << 13, 1 << 12
    compiled = _compile(
        lambda mask, planes: compact_pallas_staged(mask, planes, cap),
        (np.zeros(M, bool), np.zeros((2, M), np.uint32)),
        one_chip,
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("compaction", ["sort", "gather"])
def test_packed_superstep_compiles(one_chip, accel_branches, compaction, monkeypatch):
    """2pc rm=3's fused superstep on the accelerator's engine: sorted
    visited set, plane-major buffers and the chosen plane compaction. The
    sort compaction recovers candidate parents by merge, here at every
    width."""
    from stateright_tpu import xla
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    monkeypatch.setattr(xla, "PARENT_MERGE_MIN", 1)
    checker = PackedTwoPhaseSys(3).checker().spawn_xla(
        frontier_capacity=1 << 8,
        table_capacity=1 << 10,
        compaction=compaction,
    )
    assert (checker._dedup, checker._soa) == ("sorted", True)
    assert checker.metrics()["parent_lowering"] == (
        "merge" if compaction == "sort" else "gather"
    )
    cap = 1 << 8
    f_in, e_in = checker._bucket_inputs(cap)
    args = (
        f_in, e_in, jnp.int32(checker._frontier_count), checker._table,
        checker._disc_found, checker._disc_fp, jnp.int32(32),
        jnp.int32(2**31 - 1), jnp.zeros(checker._P, bool), jnp.int32(0),
        jnp.int32(0), jnp.int32(0),
    )
    _compile(checker._build_fused(cap, checker._cand_rungs(cap)), args, one_chip)
