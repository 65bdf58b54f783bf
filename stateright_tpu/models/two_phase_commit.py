"""Two-phase commit (Gray & Lamport, "Consensus on Transaction Commit").

Same transition system as the reference example
(``/root/reference/examples/2pc.rs``): a transaction manager and ``rm_count``
resource managers exchange messages through a shared message set.  Known
state-space sizes (reference tests, 2pc.rs:151-172): 288 at rm=3, 8,832 at
rm=5, 665 at rm=5 with symmetry reduction.

Two implementations of the one system:

- :class:`TwoPhaseSys` — object-level ``Model`` for the host oracle engines.
- :class:`PackedTwoPhaseSys` — the TPU form: states bit-packed into two
  uint32 words, the action fan-out evaluated as a fixed ``2 + 5N`` slot grid
  by vectorized jnp ops, properties fused as packed predicates.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

from ..core import Model, Property

# RmState encoding; order matches the reference's derive(Ord) declaration
# order (2pc.rs:33-39), which symmetry-reduction sorting relies on.
WORKING, PREPARED, COMMITTED, ABORTED = 0, 1, 2, 3
# TmState encoding (2pc.rs:41-46).
TM_INIT, TM_COMMITTED, TM_ABORTED = 0, 1, 2

_RM_NAMES = ["Working", "Prepared", "Committed", "Aborted"]
_TM_NAMES = ["Init", "Committed", "Aborted"]


@dataclass(frozen=True)
class TwoPhaseState:
    """rm_state per RM, tm_state, tm_prepared per RM, and the message set.

    Messages are encoded in a frozenset as ``("Prepared", rm)``, ``"Commit"``,
    ``"Abort"`` (the closed message universe of 2pc.rs:26-31).
    """

    rm_state: Tuple[int, ...]
    tm_state: int
    tm_prepared: Tuple[bool, ...]
    msgs: frozenset

    def representative(self) -> "TwoPhaseState":
        """Canonical member of this state's symmetry class: RMs sorted by
        rm_state (stable), tm_prepared permuted along, message RM ids
        rewritten (2pc.rs:205-225)."""
        order = sorted(range(len(self.rm_state)), key=lambda i: self.rm_state[i])
        inverse = {old: new for new, old in enumerate(order)}
        msgs = frozenset(
            ("Prepared", inverse[m[1]]) if isinstance(m, tuple) else m
            for m in self.msgs
        )
        return TwoPhaseState(
            rm_state=tuple(self.rm_state[i] for i in order),
            tm_state=self.tm_state,
            tm_prepared=tuple(self.tm_prepared[i] for i in order),
            msgs=msgs,
        )


class TwoPhaseSys(Model):
    """Object-level two-phase commit model (2pc.rs:59-149)."""

    def __init__(self, rm_count: int):
        self.rm_count = rm_count

    def init_states(self) -> List[TwoPhaseState]:
        n = self.rm_count
        return [
            TwoPhaseState(
                rm_state=(WORKING,) * n,
                tm_state=TM_INIT,
                tm_prepared=(False,) * n,
                msgs=frozenset(),
            )
        ]

    def actions(self, state: TwoPhaseState, actions: List[Any]) -> None:
        # Mirrors the enablement conditions of 2pc.rs:72-98 (same order).
        if state.tm_state == TM_INIT and all(state.tm_prepared):
            actions.append(("TmCommit",))
        if state.tm_state == TM_INIT:
            actions.append(("TmAbort",))
        for rm in range(self.rm_count):
            if state.tm_state == TM_INIT and ("Prepared", rm) in state.msgs:
                actions.append(("TmRcvPrepared", rm))
            if state.rm_state[rm] == WORKING:
                actions.append(("RmPrepare", rm))
            if state.rm_state[rm] == WORKING:
                actions.append(("RmChooseToAbort", rm))
            if "Commit" in state.msgs:
                actions.append(("RmRcvCommitMsg", rm))
            if "Abort" in state.msgs:
                actions.append(("RmRcvAbortMsg", rm))

    def next_state(
        self, state: TwoPhaseState, action: Tuple
    ) -> Optional[TwoPhaseState]:
        kind = action[0]
        rm_state = list(state.rm_state)
        tm_prepared = list(state.tm_prepared)
        tm_state = state.tm_state
        msgs = set(state.msgs)
        if kind == "TmRcvPrepared":
            tm_prepared[action[1]] = True
        elif kind == "TmCommit":
            tm_state = TM_COMMITTED
            msgs.add("Commit")
        elif kind == "TmAbort":
            tm_state = TM_ABORTED
            msgs.add("Abort")
        elif kind == "RmPrepare":
            rm_state[action[1]] = PREPARED
            msgs.add(("Prepared", action[1]))
        elif kind == "RmChooseToAbort":
            rm_state[action[1]] = ABORTED
        elif kind == "RmRcvCommitMsg":
            rm_state[action[1]] = COMMITTED
        elif kind == "RmRcvAbortMsg":
            rm_state[action[1]] = ABORTED
        else:  # pragma: no cover
            raise ValueError(f"unknown action {action!r}")
        return TwoPhaseState(tuple(rm_state), tm_state, tuple(tm_prepared), frozenset(msgs))

    def properties(self) -> List[Property]:
        return [
            Property.sometimes(
                "abort agreement",
                lambda _, s: all(r == ABORTED for r in s.rm_state),
            ),
            Property.sometimes(
                "commit agreement",
                lambda _, s: all(r == COMMITTED for r in s.rm_state),
            ),
            Property.always(
                "consistent",
                lambda _, s: not (
                    any(r == ABORTED for r in s.rm_state)
                    and any(r == COMMITTED for r in s.rm_state)
                ),
            ),
        ]

    def format_action(self, action: Tuple) -> str:
        return action[0] if len(action) == 1 else f"{action[0]}({action[1]})"


class PackedTwoPhaseSys(TwoPhaseSys):
    """TPU-packed two-phase commit: implements the PackedModel protocol.

    Bit layout over two uint32 words (supports rm_count <= 14):

    - word0: ``rm_state[i]`` in bits ``[2i, 2i+2)``
    - word1: ``tm_state`` in bits ``[0, 2)``; ``tm_prepared[i]`` at bit
      ``2 + i``; ``Prepared{i}`` message bit at ``16 + i``; ``Commit`` at
      ``30``; ``Abort`` at ``31``.

    The action grid is ``2 + 5*rm_count`` static slots: [TmCommit, TmAbort]
    then per-RM [TmRcvPrepared, RmPrepare, RmChooseToAbort, RmRcvCommitMsg,
    RmRcvAbortMsg], mirroring the enablement conditions of 2pc.rs:72-98.
    """

    state_words = 2

    def __init__(self, rm_count: int):
        if rm_count > 14:
            raise ValueError("PackedTwoPhaseSys supports rm_count <= 14")
        super().__init__(rm_count)
        self.max_actions = 2 + 5 * rm_count
        if rm_count >= 2:
            # Declarative device symmetry (stateright_tpu/sym,
            # docs/symmetry.md): RM block i = its rm_state dibit, its
            # tm_prepared bit, and its Prepared{i} message bit. All three
            # lanes key the sort, so the spec kernel is a FULL (class-
            # invariant) canonicalization — unlike the partial rm_state
            # sort of :meth:`packed_representative`, its reduced counts
            # are traversal-order-independent (rm=5: 314 classes on any
            # engine; the partial form visits 665 under the reference
            # DFS and 508 under the device BFS).
            from ..sym import BlockGroup, SymmetrySpec

            self.symmetry_spec = SymmetrySpec(
                [
                    BlockGroup(
                        "rm",
                        rm_count,
                        (
                            SymmetrySpec.lane(
                                "rm_state", 2, word=0, count=rm_count
                            ),
                            SymmetrySpec.lane(
                                "tm_prepared", 1, word=1, shift0=2,
                                stride=1, count=rm_count,
                            ),
                            SymmetrySpec.lane(
                                "prepared_msg", 1, word=1, shift0=16,
                                stride=1, count=rm_count,
                            ),
                        ),
                    )
                ],
                name="2pc-rm",
            )

    # --- host-side codec --------------------------------------------------

    def pack(self, state: TwoPhaseState):
        import numpy as np

        w0 = 0
        for i, r in enumerate(state.rm_state):
            w0 |= r << (2 * i)
        w1 = state.tm_state
        for i, p in enumerate(state.tm_prepared):
            w1 |= int(p) << (2 + i)
        for m in state.msgs:
            if isinstance(m, tuple):
                w1 |= 1 << (16 + m[1])
            elif m == "Commit":
                w1 |= 1 << 30
            else:
                w1 |= 1 << 31
        return np.array([w0, w1], dtype=np.uint32)

    def unpack(self, words) -> TwoPhaseState:
        w0, w1 = int(words[0]), int(words[1])
        n = self.rm_count
        msgs = set()
        for i in range(n):
            if (w1 >> (16 + i)) & 1:
                msgs.add(("Prepared", i))
        if (w1 >> 30) & 1:
            msgs.add("Commit")
        if (w1 >> 31) & 1:
            msgs.add("Abort")
        return TwoPhaseState(
            rm_state=tuple((w0 >> (2 * i)) & 3 for i in range(n)),
            tm_state=w1 & 3,
            tm_prepared=tuple(bool((w1 >> (2 + i)) & 1) for i in range(n)),
            msgs=frozenset(msgs),
        )

    def packed_init(self):
        import numpy as np

        return np.stack([self.pack(s) for s in self.init_states()])

    # --- device-side kernel ----------------------------------------------

    def packed_step(self, words):
        """One state's full action fan-out: ``[2] uint32 -> ([A, 2] uint32,
        [A] bool)``. Pure jnp; vmapped over the frontier by the engine."""
        import jax.numpy as jnp

        n = self.rm_count
        w0, w1 = words[0], words[1]
        rm_ids = jnp.arange(n, dtype=jnp.uint32)
        rm_state = (w0 >> (2 * rm_ids)) & 3  # [n]
        tm_state = w1 & 3
        tm_prepared_all = ((w1 >> 2) & jnp.uint32((1 << n) - 1)) == jnp.uint32(
            (1 << n) - 1
        )
        msg_prepared = ((w1 >> (16 + rm_ids)) & 1).astype(jnp.bool_)  # [n]
        msg_commit = ((w1 >> 30) & 1).astype(jnp.bool_)
        msg_abort = ((w1 >> 31) & 1).astype(jnp.bool_)
        tm_init = tm_state == TM_INIT

        def set_rm(w0, rm, value):
            return (w0 & ~(jnp.uint32(3) << (2 * rm))) | (
                jnp.uint32(value) << (2 * rm)
            )

        # TmCommit / TmAbort (scalar slots).
        tmc_w1 = (w1 & ~jnp.uint32(3)) | jnp.uint32(TM_COMMITTED) | jnp.uint32(1 << 30)
        tma_w1 = (w1 & ~jnp.uint32(3)) | jnp.uint32(TM_ABORTED) | jnp.uint32(1 << 31)
        scalar_states = jnp.stack(
            [jnp.stack([w0, tmc_w1]), jnp.stack([w0, tma_w1])]
        )  # [2, 2]
        scalar_valid = jnp.stack([tm_init & tm_prepared_all, tm_init])  # [2]

        # Per-RM families, each vectorized over rm_ids -> [n, 2] states.
        w0b = jnp.broadcast_to(w0, (n,))
        w1b = jnp.broadcast_to(w1, (n,))
        # TmRcvPrepared(rm): set tm_prepared bit.
        rcv_prep = jnp.stack([w0b, w1b | (jnp.uint32(1) << (2 + rm_ids))], axis=1)
        rcv_prep_valid = tm_init & msg_prepared
        # RmPrepare(rm): rm -> Prepared, add Prepared{rm} msg.
        prep = jnp.stack(
            [set_rm(w0b, rm_ids, PREPARED), w1b | (jnp.uint32(1) << (16 + rm_ids))],
            axis=1,
        )
        rm_working = rm_state == WORKING
        # RmChooseToAbort(rm): rm -> Aborted.
        choose_abort = jnp.stack([set_rm(w0b, rm_ids, ABORTED), w1b], axis=1)
        # RmRcvCommitMsg(rm): rm -> Committed.
        rcv_commit = jnp.stack([set_rm(w0b, rm_ids, COMMITTED), w1b], axis=1)
        rcv_commit_valid = jnp.broadcast_to(msg_commit, (n,))
        # RmRcvAbortMsg(rm): rm -> Aborted.
        rcv_abort = jnp.stack([set_rm(w0b, rm_ids, ABORTED), w1b], axis=1)
        rcv_abort_valid = jnp.broadcast_to(msg_abort, (n,))

        per_rm_states = jnp.stack(
            [rcv_prep, prep, choose_abort, rcv_commit, rcv_abort], axis=1
        )  # [n, 5, 2]
        per_rm_valid = jnp.stack(
            [rcv_prep_valid, rm_working, rm_working, rcv_commit_valid, rcv_abort_valid],
            axis=1,
        )  # [n, 5]

        next_states = jnp.concatenate(
            [scalar_states, per_rm_states.reshape(5 * n, 2)]
        )  # [A, 2]
        valid = jnp.concatenate([scalar_valid, per_rm_valid.reshape(5 * n)])  # [A]
        return next_states, valid

    def packed_properties(self, words):
        """Property predicates on one packed state: ``[2] -> [3] bool``,
        ordered as :meth:`properties`."""
        import jax.numpy as jnp

        n = self.rm_count
        w0 = words[0]
        rm_ids = jnp.arange(n, dtype=jnp.uint32)
        rm_state = (w0 >> (2 * rm_ids)) & 3
        all_aborted = jnp.all(rm_state == ABORTED)
        all_committed = jnp.all(rm_state == COMMITTED)
        consistent = ~(jnp.any(rm_state == ABORTED) & jnp.any(rm_state == COMMITTED))
        return jnp.stack([all_aborted, all_committed, consistent])

    def packed_representative(self, words):
        """Canonical symmetry-class member of one packed state (device).

        Sorts RM slots by rm_state (stable), carrying tm_prepared and
        Prepared-message bits through the same permutation — the packed
        equivalent of :meth:`TwoPhaseState.representative`.
        """
        import jax.numpy as jnp

        n = self.rm_count
        w0, w1 = words[0], words[1]
        rm_ids = jnp.arange(n, dtype=jnp.uint32)
        rm_state = ((w0 >> (2 * rm_ids)) & 3).astype(jnp.int32)
        order = jnp.argsort(rm_state, stable=True).astype(jnp.uint32)
        sorted_rm = rm_state.astype(jnp.uint32)[order]
        u1, u2, u16 = jnp.uint32(1), jnp.uint32(2), jnp.uint32(16)
        prepared_bits = (w1 >> (u2 + order)) & u1
        msg_bits = (w1 >> (u16 + order)) & u1
        shifts = jnp.arange(n, dtype=jnp.uint32)
        new_w0 = jnp.sum(sorted_rm << (u2 * shifts), dtype=jnp.uint32)
        new_w1 = (
            (w1 & jnp.uint32(0b11 | (1 << 30) | (1 << 31)))
            | jnp.sum(prepared_bits << (u2 + shifts), dtype=jnp.uint32)
            | jnp.sum(msg_bits << (u16 + shifts), dtype=jnp.uint32)
        )
        return jnp.stack([new_w0, new_w1])


def main(argv=None) -> None:
    """CLI mirroring 2pc.rs:174-255: ``check``/``check-sym``/``check-xla``/
    ``explore`` subcommands. ``check`` runs the device (XLA) engine — the
    reference's ``check`` likewise runs its fastest checker (the 16-thread
    DFS, 2pc.rs:186-189), so the default here is the engine this framework
    is built around; ``check-host`` is the sequential Python oracle for
    semantics-exact comparison runs."""
    import sys

    from ..report import WriteReporter

    args = list(sys.argv[1:] if argv is None else argv)
    cmd = args.pop(0) if args else None
    if cmd in ("check", "check-xla"):
        from ..backend import configure_compile_cache

        configure_compile_cache()
        rm_count = int(args.pop(0)) if args else 2
        print(
            f"Checking two phase commit with {rm_count} resource managers "
            f"on the XLA engine."
        )
        PackedTwoPhaseSys(rm_count).checker().spawn_xla().report(WriteReporter())
    elif cmd == "check-host":
        rm_count = int(args.pop(0)) if args else 2
        print(f"Checking two phase commit with {rm_count} resource managers.")
        TwoPhaseSys(rm_count).checker().spawn_dfs().report(WriteReporter())
    elif cmd == "check-sym":
        rm_count = int(args.pop(0)) if args else 2
        print(
            f"Checking two phase commit with {rm_count} resource managers "
            f"using symmetry reduction."
        )
        TwoPhaseSys(rm_count).checker().symmetry().spawn_dfs().report(
            WriteReporter()
        )
    elif cmd == "explore":
        rm_count = int(args.pop(0)) if args else 2
        address = args.pop(0) if args else "localhost:3000"
        print(
            f"Exploring state space for two phase commit with {rm_count} "
            f"resource managers on {address}."
        )
        TwoPhaseSys(rm_count).checker().serve(address)
    else:
        print("USAGE:")
        print("  two-phase-commit check [RM_COUNT]        (device/XLA engine)")
        print("  two-phase-commit check-host [RM_COUNT]   (sequential host oracle)")
        print("  two-phase-commit check-sym [RM_COUNT]")
        print("  two-phase-commit check-xla [RM_COUNT]    (alias of check)")
        print("  two-phase-commit explore [RM_COUNT] [ADDRESS]")


if __name__ == "__main__":
    main()
