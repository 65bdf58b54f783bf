"""Shared counter guarded by a lock: the fixed version of ``increment``.

Mirrors ``/root/reference/examples/increment_lock.rs``: each thread executes
``0: lock; 1: t = SHARED; 2: SHARED = t + 1; 3: unlock; 4:``, so the ``fin``
invariant ("SHARED equals the number of threads past their write") and the
``mutex`` invariant ("at most one thread inside the critical section") both
hold — the checker finds no counterexample.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Tuple

from ..core import Model, Property
from ..utils.variant import variant

Proc = Tuple[int, int]  # (thread-local value t, program counter pc)

Lock = variant("Lock", ["thread"])
Read = variant("Read", ["thread"])
Write = variant("Write", ["thread"])
Release = variant("Release", ["thread"])


class IncrementLockState(NamedTuple):
    """(shared counter, lock bit, per-thread (t, pc)) — increment_lock.rs:19-33."""

    i: int
    lock: bool
    s: Tuple[Proc, ...]

    def representative(self) -> "IncrementLockState":
        """Sort the interchangeable thread slice (increment_lock.rs:36-46)."""
        return IncrementLockState(self.i, self.lock, tuple(sorted(self.s)))


class IncrementLock(Model):
    """The model (increment_lock.rs:48-107)."""

    def __init__(self, thread_count: int = 3):
        self.thread_count = thread_count

    def init_states(self) -> List[IncrementLockState]:
        return [
            IncrementLockState(0, False, tuple((0, 0) for _ in range(self.thread_count)))
        ]

    def actions(self, state: IncrementLockState, actions: List[Any]) -> None:
        for thread_id, (_t, pc) in enumerate(state.s):
            if pc == 0 and not state.lock:
                actions.append(Lock(thread_id))
            elif pc == 1:
                actions.append(Read(thread_id))
            elif pc == 2:
                actions.append(Write(thread_id))
            elif pc == 3 and state.lock:
                actions.append(Release(thread_id))

    def next_state(self, last_state: IncrementLockState, action: Any):
        s = list(last_state.s)
        t, _pc = s[action.thread]
        if isinstance(action, Lock):
            s[action.thread] = (t, 1)
            return last_state._replace(lock=True, s=tuple(s))
        if isinstance(action, Read):
            s[action.thread] = (last_state.i, 2)
            return last_state._replace(s=tuple(s))
        if isinstance(action, Write):
            s[action.thread] = (t, 3)
            return last_state._replace(i=t + 1, s=tuple(s))
        s[action.thread] = (t, 4)
        return last_state._replace(lock=False, s=tuple(s))

    def properties(self) -> List[Property]:
        return [
            Property.always(
                "fin",
                lambda _m, state: sum(1 for _t, pc in state.s if pc >= 3) == state.i,
            ),
            Property.always(
                "mutex",
                lambda _m, state: sum(1 for _t, pc in state.s if 1 <= pc < 4) <= 1,
            ),
        ]


class PackedIncrementLock(IncrementLock):
    """The lock-guarded counter on the device engine (``spawn_xla``).

    Same layout style as :class:`~stateright_tpu.models.increment.PackedIncrement`
    plus a global lock flag; one action slot per thread (each program
    counter enables at most one of Lock/Read/Write/Release,
    increment_lock.rs:61-73)."""

    def __init__(self, thread_count: int = 3):
        from ..packing import LayoutBuilder, bits_for

        super().__init__(thread_count)
        n = thread_count
        self._layout = (
            LayoutBuilder()
            .uint("i", bits_for(n))
            .flag("lock")
            .array("t", n, bits_for(n))
            .array("pc", n, 3)  # 0..4
            .finish()
        )
        self.state_words = self._layout.words
        self.max_actions = n
        if n >= 2:
            # Declarative device symmetry (stateright_tpu/sym): same
            # thread-block declaration as PackedIncrement — (t, pc) is
            # the whole block, so the spec kernel matches
            # packed_representative bit-for-bit.
            from ..sym import SymmetrySpec

            self.symmetry_spec = SymmetrySpec.from_layout(
                self._layout, ["t", "pc"], group="threads",
                name="increment-lock",
            )

    def pack(self, state: IncrementLockState):
        return self._layout.pack(
            i=state.i,
            lock=int(state.lock),
            t=[t for t, _pc in state.s],
            pc=[pc for _t, pc in state.s],
        )

    def unpack(self, words) -> IncrementLockState:
        f = self._layout.unpack(words)
        return IncrementLockState(
            f["i"],
            bool(f["lock"]),
            tuple(zip((int(x) for x in f["t"]), (int(x) for x in f["pc"]))),
        )

    def packed_init(self):
        import numpy as np

        return np.stack([self.pack(s) for s in self.init_states()])

    def packed_step(self, words):
        """Slot k: thread k's one enabled instruction, by program counter —
        Lock (pc=0, lock free), Read (1), Write (2), Release (3)."""
        import jax.numpy as jnp

        L = self._layout
        n = self.thread_count
        i_val = L.get(words, "i")
        lock = L.get(words, "lock") != 0
        nxt, valid = [], []
        for k in range(n):
            pc = L.get(words, "pc", k)
            t = L.get(words, "t", k)
            lock_w = L.set(L.set(words, "lock", 1), "pc", 1, k)
            read_w = L.set(L.set(words, "t", i_val, k), "pc", 2, k)
            write_w = L.set(L.set(words, "i", t + jnp.uint32(1)), "pc", 3, k)
            rel_w = L.set(L.set(words, "lock", 0), "pc", 4, k)
            w = jnp.where(
                pc == 0, lock_w,
                jnp.where(pc == 1, read_w, jnp.where(pc == 2, write_w, rel_w)),
            )
            ok = jnp.where(
                pc == 0, ~lock,
                jnp.where((pc == 1) | (pc == 2), jnp.bool_(True),
                          (pc == 3) & lock),
            )
            nxt.append(w)
            valid.append(ok & (pc < 4))
        return jnp.stack(nxt), jnp.stack(valid)

    def packed_properties(self, words):
        import jax.numpy as jnp

        L = self._layout
        n = self.thread_count
        fin = jnp.uint32(0)
        crit = jnp.uint32(0)
        for k in range(n):
            pc = L.get(words, "pc", k)
            fin = fin + (pc >= 3).astype(jnp.uint32)
            crit = crit + ((pc >= 1) & (pc < 4)).astype(jnp.uint32)
        return jnp.stack([fin == L.get(words, "i"), crit <= 1])

    def packed_representative(self, words):
        import jax.numpy as jnp

        L = self._layout
        n = self.thread_count
        t = jnp.stack([L.get(words, "t", k) for k in range(n)])
        pc = jnp.stack([L.get(words, "pc", k) for k in range(n)])
        keys = t * jnp.uint32(8) + pc  # pc < 8; lexicographic (t, pc)
        order = jnp.argsort(keys, stable=True)
        t, pc = t[order], pc[order]
        w = words
        for k in range(n):
            w = L.set(L.set(w, "t", t[k], k), "pc", pc[k], k)
        return w


def main(argv=None) -> None:
    """CLI mirroring increment_lock.rs:109-161. ``check`` runs the device
    (XLA) engine — the reference's ``check`` likewise runs its fastest
    checker; ``check-host`` is the sequential Python oracle."""
    import sys

    from ..report import WriteReporter

    args = list(sys.argv[1:] if argv is None else argv)
    cmd = args.pop(0) if args else None
    if cmd in ("check", "check-xla"):
        from ..backend import configure_compile_cache

        configure_compile_cache()
        thread_count = int(args.pop(0)) if args else 3
        print(f"Model checking increment_lock with {thread_count} threads on XLA.")
        PackedIncrementLock(thread_count).checker().spawn_xla(
            frontier_capacity=1 << 12, table_capacity=1 << 16
        ).report(WriteReporter())
    elif cmd == "check-host":
        thread_count = int(args.pop(0)) if args else 3
        print(f"Model checking increment_lock with {thread_count} threads.")
        IncrementLock(thread_count).checker().spawn_dfs().report(WriteReporter())
    elif cmd == "check-sym":
        thread_count = int(args.pop(0)) if args else 3
        print(
            f"Model checking increment_lock with {thread_count} threads "
            f"using symmetry reduction."
        )
        IncrementLock(thread_count).checker().symmetry().spawn_dfs().report(
            WriteReporter()
        )
    elif cmd == "explore":
        thread_count = int(args.pop(0)) if args else 3
        address = args.pop(0) if args else "localhost:3000"
        print(
            f"Exploring the state space of increment_lock with {thread_count} "
            f"threads on {address}."
        )
        IncrementLock(thread_count).checker().serve(address)
    else:
        print("USAGE:")
        print("  increment_lock check [THREAD_COUNT]        (device/XLA engine)")
        print("  increment_lock check-host [THREAD_COUNT]   (sequential host oracle)")
        print("  increment_lock check-sym [THREAD_COUNT]")
        print("  increment_lock check-xla [THREAD_COUNT]    (alias of check)")
        print("  increment_lock explore [THREAD_COUNT] [ADDRESS]")


if __name__ == "__main__":
    main()
