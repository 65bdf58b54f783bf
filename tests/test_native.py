"""Native host-kit tests: the C++ library must agree bit-for-bit with the
Python mirrors, and the engines must work with either backend."""

import shutil

import numpy as np
import pytest

from stateright_tpu import native
from stateright_tpu.ops import fphash


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_native_builds_when_toolchain_present():
    # The build image ships g++; if this fails the lazy build broke. On
    # toolchain-less machines the package works via the Python fallbacks.
    assert native.available()


def test_library_is_named_by_source_hash():
    """Only a library built from the committed source can load: a stale
    or foreign ``.so`` beside it has another name."""
    import hashlib
    import os

    with open(os.path.join(os.path.dirname(native.__file__), "hostkit.cpp"), "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    assert os.path.basename(native._lib_path()) == f"libhostkit-{digest}.so"


def test_fingerprint_parity_with_python():
    rng = np.random.default_rng(11)
    for w in (1, 2, 3, 8):
        words = rng.integers(0, 2**32, size=(257, w), dtype=np.uint32)
        nh, nl = native.fingerprint_words(words)
        ph, pl = fphash.fingerprint_words(words, np)
        np.testing.assert_array_equal(nh, ph)
        np.testing.assert_array_equal(nl, pl)


def test_parentmap_lookup_and_chain():
    # Build a synthetic 3-link chain: c -> b -> a -> 0.
    def lanes(fp64):
        return np.uint32(fp64 >> 32), np.uint32(fp64 & 0xFFFFFFFF)

    a, b, c = 0x1111_2222_3333, 0x4444_5555_6666, 0x7777_8888_9999
    kh = np.zeros(64, np.uint32)
    kl = np.zeros(64, np.uint32)
    vh = np.zeros(64, np.uint32)
    vl = np.zeros(64, np.uint32)
    for slot, (key, parent) in enumerate([(a, 0), (b, a), (c, b)]):
        kh[slot], kl[slot] = lanes(key)
        vh[slot], vl[slot] = lanes(parent)
    pm = native.ParentMap(kh, kl, vh, vl)
    assert len(pm) == 3
    assert pm[c] == b and pm[b] == a and pm[a] == 0
    assert pm.chain(c) == [c, b, a]
    assert pm.get(0xDEAD) is None
    with pytest.raises(KeyError):
        pm.chain(0xDEAD)


def test_parentmap_python_fallback_matches(monkeypatch):
    # Force the dict fallback and compare against the native index.
    rng = np.random.default_rng(12)
    kh = rng.integers(1, 2**32, size=200, dtype=np.uint32)
    kl = rng.integers(1, 2**32, size=200, dtype=np.uint32)
    vh = rng.integers(0, 2**32, size=200, dtype=np.uint32)
    vl = rng.integers(0, 2**32, size=200, dtype=np.uint32)
    fast = native.ParentMap(kh, kl, vh, vl)
    monkeypatch.setattr(native, "_load", lambda: None)
    slow = native.ParentMap(kh, kl, vh, vl)
    assert slow._dict is not None
    assert len(fast) == len(slow)
    for i in range(0, 200, 17):
        key = (int(kh[i]) << 32) | int(kl[i])
        assert fast.get(key) == slow.get(key)


def test_fallback_chain_detects_cycles(monkeypatch):
    # a -> b -> a: the dict fallback must raise, not hang.
    def lanes(fp64):
        return np.uint32(fp64 >> 32), np.uint32(fp64 & 0xFFFFFFFF)

    a, b = 0x1111_2222_3333, 0x4444_5555_6666
    kh = np.zeros(64, np.uint32)
    kl = np.zeros(64, np.uint32)
    vh = np.zeros(64, np.uint32)
    vl = np.zeros(64, np.uint32)
    for slot, (key, parent) in enumerate([(a, b), (b, a)]):
        kh[slot], kl[slot] = lanes(key)
        vh[slot], vl[slot] = lanes(parent)
    monkeypatch.setattr(native, "_load", lambda: None)
    pm = native.ParentMap(kh, kl, vh, vl)
    with pytest.raises(RuntimeError, match="max_len"):
        pm.chain(a, max_len=100)


def test_xla_discoveries_use_native_parent_map():
    # End to end: witness reconstruction through the native index.
    from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys

    checker = (
        PackedTwoPhaseSys(3)
        .checker()
        .spawn_xla(frontier_capacity=1 << 10, table_capacity=1 << 13)
        .join()
    )
    checker.assert_properties()
    assert checker.discoveries()
