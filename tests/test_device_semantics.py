"""The generalized device serializer vs the host backtracking testers.

``semantics.device.device_serializable`` claims EXACT agreement with the
host ``BacktrackingTester`` search (the port of linearizability.rs:197-284 /
sequential_consistency.rs:127-225) for any statically-bounded history shape
under ``MAX_PATTERNS`` — over both specs (Register, WORegister) and both
consistency models (real_time=True/False). These tests fuzz random
protocol-valid histories (including invalid *semantics*: random returns) at
2x2, 3x2 and 3x3 shapes and require bit-for-bit verdict agreement; model
reachable-state differential coverage lives in
test_device_linearizability.py.
"""

import random

import numpy as np
import pytest

from stateright_tpu.actor.register import history_codecs
from stateright_tpu.packing import BoundedHistory, LayoutBuilder
from stateright_tpu.actor.write_once_register import wo_history_codecs
from stateright_tpu.semantics.device import (
    MAX_PATTERNS,
    DeviceRegister,
    DeviceWORegister,
    device_serializable,
    interleaving_tables,
    pattern_count,
)
from stateright_tpu.semantics.linearizability import LinearizabilityTester
from stateright_tpu.semantics.register import Read, ReadOk, Register, Write, WriteOk
from stateright_tpu.semantics.sequential_consistency import (
    SequentialConsistencyTester,
)
from stateright_tpu.semantics.write_once_register import (
    Read as WORead,
)
from stateright_tpu.semantics.write_once_register import (
    ReadOk as WOReadOk,
)
from stateright_tpu.semantics.write_once_register import (
    WORegister,
    WriteFail,
)
from stateright_tpu.semantics.write_once_register import (
    Write as WOWrite,
)
from stateright_tpu.semantics.write_once_register import (
    WriteOk as WOWriteOk,
)


# --- pattern table sanity ---------------------------------------------------


@pytest.mark.parametrize("T,slots", [(2, 3), (3, 3), (2, 4), (3, 4)])
def test_interleaving_tables_shape_and_uniqueness(T, slots):
    tid, slot, cnt_before = interleaving_tables(T, slots)
    P, L = tid.shape
    assert L == T * slots
    assert P == pattern_count(T, slots - 1)
    # Every pattern uses each thread exactly `slots` times, in slot order.
    assert len({tuple(r) for r in tid}) == P
    for t in range(T):
        assert (np.sum(tid == t, axis=1) == slots).all()
    rows = np.arange(P)
    running = np.zeros((P, T), dtype=np.int32)
    for l in range(L):
        assert (cnt_before[:, l, :] == running).all()
        assert (slot[:, l] == running[rows, tid[:, l]]).all()
        running[rows, tid[:, l]] += 1


def test_pattern_cap_raises_with_pointer_to_host_verified():
    # 4x2 (369,600) is device-exact since round 4 (chunked scan); the
    # refusal bound is now MAX_PATTERNS_EXACT — 5x2 = 1.68e8 exceeds it.
    b = LayoutBuilder()
    hist = BoundedHistory(
        b, thread_ids=[0, 1, 2, 3, 4], max_ops=2, op_bits=3, ret_bits=3
    )
    hist.bind(b.finish())
    words = np.zeros(hist.layout.words, dtype=np.uint32)
    with pytest.raises(NotImplementedError, match="host_verified_properties"):
        device_serializable(hist, words, DeviceRegister(), real_time=True)


# --- random-history differential fuzz --------------------------------------


def _random_events(rng, T, M, ops_of, rets_of):
    """A random protocol-valid event sequence: per thread at most M returns
    plus optionally one trailing in-flight invocation."""
    events = []
    n = [0] * T  # completed
    fl = [None] * T  # in-flight op
    budget = rng.randrange(1, 2 * T * (M + 1))
    while budget > 0:
        t = rng.randrange(T)
        if fl[t] is not None and n[t] < M and rng.random() < 0.6:
            events.append(("ret", t, rng.choice(rets_of(fl[t]))))
            n[t] += 1
            fl[t] = None
        elif fl[t] is None and n[t] + 1 <= M or (fl[t] is None and n[t] == M and rng.random() < 0.3):
            op = rng.choice(ops_of())
            events.append(("inv", t, op))
            fl[t] = op
        budget -= 1
    return events


def _replay(events, tester):
    for kind, t, x in events:
        if kind == "inv":
            tester.on_invoke(t, x)
        else:
            tester.on_return(t, x)
    return tester


def _device_verdicts(histories, T, M, op_bits, ret_bits, op_code, ret_code, spec, real_time):
    import jax
    import jax.numpy as jnp

    b = LayoutBuilder()
    hist = BoundedHistory(
        b, thread_ids=list(range(T)), max_ops=M, op_bits=op_bits, ret_bits=ret_bits
    )
    layout = b.finish()
    hist.bind(layout)
    words = np.stack(
        [
            layout.pack(**hist.from_tester(h, op_code, ret_code))
            for h in histories
        ]
    )
    fn = jax.jit(
        jax.vmap(lambda w: device_serializable(hist, w, spec, real_time=real_time))
    )
    return np.asarray(fn(jnp.asarray(words)))


@pytest.mark.parametrize(
    "T,M,trials",
    [
        (2, 2, 250),
        (3, 2, 250),
        (3, 3, 40),
        # 4x2 = 369,600 patterns: past the single-shot MAX_PATTERNS budget,
        # the exact check searches the progress lattice.
        (4, 2, 8),
    ],
)
@pytest.mark.parametrize("real_time", [True, False], ids=["lin", "seqcst"])
def test_register_fuzz_matches_host_serializer(T, M, trials, real_time):
    rng = random.Random(10_000 * T + 100 * M + real_time)
    values = [None] + [chr(ord("A") + k) for k in range(T)]
    op_code, _, ret_code, _ = history_codecs(values)
    ops_of = lambda: [Read()] + [Write(v) for v in values[1:]]
    rets_of = lambda op: (
        [ReadOk(v) for v in values] + [WriteOk()]
        if isinstance(op, Read)
        else [WriteOk()] + [ReadOk(v) for v in values]
    )
    make = (
        (lambda: LinearizabilityTester(Register(None)))
        if real_time
        else (lambda: SequentialConsistencyTester(Register(None)))
    )
    testers = [
        _replay(_random_events(rng, T, M, ops_of, rets_of), make())
        for _ in range(trials)
    ]
    got = _device_verdicts(
        testers, T, M, 3, 3, op_code, ret_code, DeviceRegister(), real_time
    )
    want = np.array([h.serialized_history() is not None for h in testers])
    assert (got == want).all(), (
        f"{int(np.sum(got != want))} disagreements; first: "
        f"{testers[int(np.argmax(got != want))].history_by_thread}"
    )
    assert want.any() and (~want).any()  # the fuzz hits both verdicts


@pytest.mark.parametrize("T,M,trials", [(2, 2, 250), (3, 2, 250)])
@pytest.mark.parametrize("real_time", [True, False], ids=["lin", "seqcst"])
def test_wo_register_fuzz_matches_host_serializer(T, M, trials, real_time):
    rng = random.Random(31_337 + 10_000 * T + 100 * M + real_time)
    values = [None] + [chr(ord("A") + k) for k in range(T)]
    op_code, _, ret_code, _ = wo_history_codecs(values)
    ops_of = lambda: [WORead()] + [WOWrite(v) for v in values[1:]]
    rets_of = lambda op: (
        [WOReadOk(v) for v in values] + [WOWriteOk(), WriteFail()]
        if isinstance(op, WORead)
        else [WOWriteOk(), WriteFail()] + [WOReadOk(v) for v in values]
    )
    make = (
        (lambda: LinearizabilityTester(WORegister(None)))
        if real_time
        else (lambda: SequentialConsistencyTester(WORegister(None)))
    )
    testers = [
        _replay(_random_events(rng, T, M, ops_of, rets_of), make())
        for _ in range(trials)
    ]
    got = _device_verdicts(
        testers, T, M, 3, 3, op_code, ret_code, DeviceWORegister(), real_time
    )
    want = np.array([h.serialized_history() is not None for h in testers])
    assert (got == want).all(), (
        f"{int(np.sum(got != want))} disagreements; first: "
        f"{testers[int(np.argmax(got != want))].history_by_thread}"
    )
    assert want.any() and (~want).any()


def test_seqcst_is_weaker_than_linearizability():
    # A history that is sequentially consistent but NOT linearizable:
    # thread 0 completes Write(A); afterwards thread 1 reads None (stale).
    # SC may reorder the read before the write; real time forbids it.
    h = LinearizabilityTester(Register(None))
    h.on_invoke(0, Write("A")).on_return(0, WriteOk())
    h.on_invoke(1, Read()).on_return(1, ReadOk(None))
    assert h.serialized_history() is None
    s = SequentialConsistencyTester(Register(None))
    s.on_invoke(0, Write("A")).on_return(0, WriteOk())
    s.on_invoke(1, Read()).on_return(1, ReadOk(None))
    assert s.serialized_history() is not None

    values = [None, "A", "B"]
    op_code, _, ret_code, _ = history_codecs(values)
    lin = _device_verdicts([h], 2, 2, 3, 3, op_code, ret_code, DeviceRegister(), True)
    sc = _device_verdicts([s], 2, 2, 3, 3, op_code, ret_code, DeviceRegister(), False)
    assert not lin[0] and sc[0]


@pytest.mark.parametrize("T,M,trials", [(2, 2, 120), (3, 2, 120), (3, 3, 40), (4, 2, 120)])
@pytest.mark.parametrize("real_time", [True, False], ids=["lin", "seqcst"])
def test_lattice_search_matches_host_serializer(T, M, trials, real_time, monkeypatch):
    """The progress-lattice search at every shape (``MAX_PATTERNS`` set to
    0 sends each exact check there), Register and WORegister specs: the
    host serializer's verdict on every fuzzed history."""
    import stateright_tpu.semantics.device as device

    monkeypatch.setattr(device, "MAX_PATTERNS", 0)
    test_register_fuzz_matches_host_serializer(T, M, trials, real_time)
    if M == 2 and T < 4:
        test_wo_register_fuzz_matches_host_serializer(T, M, trials, real_time)
