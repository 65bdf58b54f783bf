"""Racy shared counter: the canonical symmetry-reduction demo.

Mirrors ``/root/reference/examples/increment.rs``: N threads each execute
``1: t = SHARED; 2: SHARED = t + 1; 3:`` with the two instructions atomic but
interleavable, so the final counter can undercount. The ``fin`` invariant
("SHARED equals the number of finished threads") is intentionally violated.

The reference's doc comment enumerates the state space for 2 threads: 13
unique states without symmetry reduction, 8 with it (increment.rs:31-105) —
those are the exact-count oracles for the tests here.

States are plain nested tuples — hashable, orderable, and trivially
canonicalizable by sorting the per-thread slice.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Tuple

from ..core import Model, Property
from ..utils.variant import variant

Proc = Tuple[int, int]  # (thread-local value t, program counter pc)

Read = variant("Read", ["thread"])
Write = variant("Write", ["thread"])


class IncrementState(NamedTuple):
    """(shared counter, per-thread (t, pc) slices) — increment.rs:117-131."""

    i: int
    s: Tuple[Proc, ...]

    def representative(self) -> "IncrementState":
        """Threads are interchangeable: the canonical class member sorts the
        thread slice (increment.rs:142-151)."""
        return IncrementState(self.i, tuple(sorted(self.s)))


class Increment(Model):
    """The model (increment.rs:153-197): the initial state doubles as the
    model value, as in the reference."""

    def __init__(self, thread_count: int = 3):
        self.thread_count = thread_count

    def init_states(self) -> List[IncrementState]:
        return [IncrementState(0, tuple((0, 1) for _ in range(self.thread_count)))]

    def actions(self, state: IncrementState, actions: List[Any]) -> None:
        for thread_id, (_t, pc) in enumerate(state.s):
            if pc == 1:
                actions.append(Read(thread_id))
            elif pc == 2:
                actions.append(Write(thread_id))

    def next_state(self, last_state: IncrementState, action: Any):
        s = list(last_state.s)
        if isinstance(action, Read):
            s[action.thread] = (last_state.i, 2)
            return IncrementState(last_state.i, tuple(s))
        t, _pc = s[action.thread]
        s[action.thread] = (t, 3)
        return IncrementState(t + 1, tuple(s))

    def properties(self) -> List[Property]:
        return [
            Property.always(
                "fin",
                lambda _m, state: sum(1 for _t, pc in state.s if pc == 3) == state.i,
            )
        ]


class PackedIncrement(Increment):
    """The racy counter on the device engine (``spawn_xla``), declared via
    :mod:`stateright_tpu.packing`: the shared counter and per-thread
    ``(t, pc)`` slices are plain layout fields. One action slot per thread
    (its program counter enables at most one instruction, increment.rs:158-169).

    Includes ``packed_representative`` — threads sort by ``(t, pc)``
    (increment.rs:142-151) — so ``check-sym`` runs on device too.
    """

    def __init__(self, thread_count: int = 3):
        from ..packing import LayoutBuilder, bits_for

        super().__init__(thread_count)
        n = thread_count
        tb = bits_for(n)
        self._layout = (
            LayoutBuilder()
            .uint("i", bits_for(n))
            .array("t", n, tb)
            .array("pc", n, 2)  # 1..3
            .finish()
        )
        self.state_words = self._layout.words
        self.max_actions = n
        if n >= 2:
            # Declarative device symmetry (stateright_tpu/sym): thread
            # block k = its (t, pc) layout elements; both lanes key the
            # sort, so the spec kernel equals packed_representative
            # bit-for-bit (the (t, pc) pair IS the whole block — the
            # hand-written sort was already a full canonicalization).
            from ..sym import SymmetrySpec

            self.symmetry_spec = SymmetrySpec.from_layout(
                self._layout, ["t", "pc"], group="threads", name="increment"
            )

    # --- host codec --------------------------------------------------------

    def pack(self, state: IncrementState):
        return self._layout.pack(
            i=state.i,
            t=[t for t, _pc in state.s],
            pc=[pc for _t, pc in state.s],
        )

    def unpack(self, words) -> IncrementState:
        f = self._layout.unpack(words)
        return IncrementState(
            f["i"], tuple(zip((int(x) for x in f["t"]), (int(x) for x in f["pc"])))
        )

    def packed_init(self):
        import numpy as np

        return np.stack([self.pack(s) for s in self.init_states()])

    # --- device kernels -----------------------------------------------------

    def packed_step(self, words):
        """Slot k = thread k's enabled instruction: Read at pc=1 (t := i,
        pc := 2), Write at pc=2 (i := t+1, pc := 3)."""
        import jax.numpy as jnp

        L = self._layout
        n = self.thread_count
        i_val = L.get(words, "i")
        nxt, valid = [], []
        for k in range(n):
            pc = L.get(words, "pc", k)
            t = L.get(words, "t", k)
            read_w = L.set(L.set(words, "t", i_val, k), "pc", 2, k)
            write_w = L.set(L.set(words, "i", t + jnp.uint32(1)), "pc", 3, k)
            is_read = pc == 1
            w = jnp.where(is_read, read_w, write_w)
            nxt.append(w)
            valid.append(is_read | (pc == 2))
        return jnp.stack(nxt), jnp.stack(valid)

    def packed_properties(self, words):
        import jax.numpy as jnp

        L = self._layout
        n = self.thread_count
        fin = jnp.uint32(0)
        for k in range(n):
            fin = fin + (L.get(words, "pc", k) == 3).astype(jnp.uint32)
        return jnp.stack([fin == L.get(words, "i")])

    def packed_representative(self, words):
        """Sort the interchangeable thread slice by ``(t, pc)`` — the
        device form of :meth:`IncrementState.representative`."""
        import jax.numpy as jnp

        L = self._layout
        n = self.thread_count
        t = jnp.stack([L.get(words, "t", k) for k in range(n)])
        pc = jnp.stack([L.get(words, "pc", k) for k in range(n)])
        keys = t * jnp.uint32(4) + pc  # pc < 4; lexicographic (t, pc)
        order = jnp.argsort(keys, stable=True)
        t, pc = t[order], pc[order]
        w = words
        for k in range(n):
            w = L.set(L.set(w, "t", t[k], k), "pc", pc[k], k)
        return w


def main(argv=None) -> None:
    """CLI mirroring increment.rs:199-254. ``check`` runs the device (XLA)
    engine — the reference's ``check`` likewise runs its fastest checker;
    ``check-host`` is the sequential Python oracle."""
    import sys

    from ..report import WriteReporter

    args = list(sys.argv[1:] if argv is None else argv)
    cmd = args.pop(0) if args else None
    if cmd in ("check", "check-xla"):
        from ..backend import configure_compile_cache

        configure_compile_cache()
        thread_count = int(args.pop(0)) if args else 3
        print(f"Model checking increment with {thread_count} threads on XLA.")
        PackedIncrement(thread_count).checker().spawn_xla(
            frontier_capacity=1 << 12, table_capacity=1 << 16
        ).report(WriteReporter())
    elif cmd == "check-host":
        thread_count = int(args.pop(0)) if args else 3
        print(f"Model checking increment with {thread_count} threads.")
        Increment(thread_count).checker().spawn_dfs().report(WriteReporter())
    elif cmd == "check-sym":
        thread_count = int(args.pop(0)) if args else 3
        print(
            f"Model checking increment with {thread_count} threads "
            f"using symmetry reduction."
        )
        Increment(thread_count).checker().symmetry().spawn_dfs().report(
            WriteReporter()
        )
    elif cmd == "explore":
        thread_count = int(args.pop(0)) if args else 3
        address = args.pop(0) if args else "localhost:3000"
        print(
            f"Exploring the state space of increment with {thread_count} "
            f"threads on {address}."
        )
        Increment(thread_count).checker().serve(address)
    else:
        print("USAGE:")
        print("  increment check [THREAD_COUNT]        (device/XLA engine)")
        print("  increment check-host [THREAD_COUNT]   (sequential host oracle)")
        print("  increment check-sym [THREAD_COUNT]")
        print("  increment check-xla [THREAD_COUNT]    (alias of check)")
        print("  increment explore [THREAD_COUNT] [ADDRESS]")


if __name__ == "__main__":
    main()
