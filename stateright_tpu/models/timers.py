"""Timer-semantics demo: pingers driven entirely by model timers.

Mirrors ``/root/reference/examples/timers.rs``: each actor sets three timers
on start (``Even``, ``Odd``, ``NoOp``). In the model a timeout is a
nondeterministic action (the duration range is irrelevant,
actor/model.rs:59-64); firing ``Even``/``Odd`` re-arms the timer and pings
the even/odd peers, while ``NoOp`` only re-arms itself — which the no-op
detection (``is_no_op_with_timer``, actor.rs:254-264) suppresses, so ``NoOp``
timeouts never generate states.

The state space is unbounded (counters grow), so ``check`` bounds the run
with ``target_state_count`` — use the Explorer to poke at it interactively.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional

from ..actor import (
    Actor,
    ActorModel,
    Id,
    Network,
    Out,
    StateRef,
    model_peers,
    model_timeout,
)
from ..actor.network import Envelope
from ..actor.timers import Timers
from ..core import Expectation
from ..packing import PackedModelAdapter
from ..utils.variant import variant

Ping = variant("Ping", [])
Pong = variant("Pong", [])

Even = variant("Even", [])
Odd = variant("Odd", [])
NoOp = variant("NoOp", [])


class PingerState(NamedTuple):
    sent: int
    received: int


class PingerActor(Actor):
    """timers.rs:32-96."""

    def __init__(self, peer_ids):
        self.peer_ids = list(peer_ids)

    def on_start(self, id: Id, out: Out) -> PingerState:
        out.set_timer(Even(), model_timeout())
        out.set_timer(Odd(), model_timeout())
        out.set_timer(NoOp(), model_timeout())
        return PingerState(sent=0, received=0)

    def on_msg(self, id: Id, state: StateRef, src: Id, msg: Any, out: Out) -> None:
        if isinstance(msg, Ping):
            out.send(src, Pong())
        elif isinstance(msg, Pong):
            s = state.get()
            state.set(s._replace(received=s.received + 1))

    def on_timeout(self, id: Id, state: StateRef, timer: Any, out: Out) -> None:
        if isinstance(timer, NoOp):
            out.set_timer(NoOp(), model_timeout())  # pure re-arm: a no-op
            return
        parity = 0 if isinstance(timer, Even) else 1
        out.set_timer(timer, model_timeout())
        for dst in self.peer_ids:
            if int(dst) % 2 == parity:
                s = state.get()
                state.set(s._replace(sent=s.sent + 1))
                out.send(dst, Ping())


def timers_model(
    server_count: int = 3, network: Optional[Network] = None
) -> ActorModel:
    """Build the checkable model (timers.rs:104-113)."""
    if network is None:
        network = Network.new_unordered_nonduplicating()
    model = ActorModel(cfg=None)
    for i in range(server_count):
        model.actor(PingerActor(model_peers(i, server_count)))
    return model.init_network(network).property(
        Expectation.ALWAYS, "true", lambda _m, _s: True
    )


class PackedTimers(PackedModelAdapter):
    """The Pingers system on the device engine (``spawn_xla``) — timers on
    device, completing device-engine coverage of every reference example.

    Pending timers need no storage: every actor's set is constantly
    ``{Even, Odd, NoOp}`` (all three are re-armed on every firing and never
    cancelled, timers.rs:50-74). The ``NoOp`` timeout gets no action slot —
    its pure re-arm is suppressed by no-op detection in the object model
    (``is_no_op_with_timer``, actor.rs:254-264) and is statically never
    enabled here. ``Even``/``Odd`` timeout slots are statically valid
    whenever the actor has a peer of that parity, and bump ``sent`` by the
    (static) peer count while incrementing each Ping's multiset count.

    The space is unbounded (counters grow), so device runs use
    ``target_state_count``/``target_max_depth`` exactly like the object
    CLI; counters and envelope counts that outgrow their declared widths
    surface as the loud codec-overflow failure.
    """

    def __init__(self, server_count: int = 3, *, count_bits: int = 8,
                 net_bits: int = 5):
        from ..packing import LayoutBuilder

        n = server_count
        self.n = n
        self._inner = timers_model(n)
        # Closed envelope universe: Ping(i->j) then Pong(i->j), i != j.
        pairs = [(i, j) for i in range(n) for j in range(n) if i != j]
        self._pairs = pairs
        U = 2 * len(pairs)
        self._U = U
        self._ping_code = {p: c for c, p in enumerate(pairs)}
        self._pong_code = {p: len(pairs) + c for c, p in enumerate(pairs)}
        self._count_bits, self._net_bits = count_bits, net_bits
        self._layout = (
            LayoutBuilder()
            .array("sent", n, count_bits)
            .array("recv", n, count_bits)
            .array("net", U, net_bits)
            .finish()
        )
        self.state_words = self._layout.words
        # Slots: [Even timeout x n, Odd timeout x n, one delivery per code].
        self.max_actions = 2 * n + U
        # Static per-actor parity targets.
        self._targets = {
            (i, parity): [j for j in range(n) if j != i and j % 2 == parity]
            for i in range(n)
            for parity in (0, 1)
        }

    # object-level Model API: inherited from PackedModelAdapter, which
    # resolves it against ``self._inner``.

    # --- codec --------------------------------------------------------------

    def pack(self, state):
        from ..packing import OverflowError32

        sent = [s.sent for s in state.actor_states]
        recv = [s.received for s in state.actor_states]
        net = [0] * self._U
        for env, count in state.network.counts.items():
            pair = (int(env.src), int(env.dst))
            code = (
                self._ping_code.get(pair)
                if isinstance(env.msg, Ping)
                else self._pong_code.get(pair)
            )
            if code is None:
                raise OverflowError32(f"envelope outside universe: {env!r}")
            net[code] = count
        for v in sent + recv:
            if v >= 1 << self._count_bits:
                raise OverflowError32(f"counter {v} exceeds {self._count_bits} bits")
        for c in net:
            if c >= 1 << self._net_bits:
                raise OverflowError32(f"envelope count {c} exceeds {self._net_bits} bits")
        return self._layout.pack(sent=sent, recv=recv, net=net)

    def unpack(self, words):
        from ..actor.model_state import ActorModelState
        from ..actor.network import Network

        from ..actor.network import UnorderedNonDuplicatingNetwork

        f = self._layout.unpack(words)
        counts = {}
        for (i, j), c in self._ping_code.items():
            if f["net"][c]:
                counts[Envelope(Id(i), Id(j), Ping())] = int(f["net"][c])
        for (i, j), c in self._pong_code.items():
            if f["net"][c]:
                counts[Envelope(Id(i), Id(j), Pong())] = int(f["net"][c])
        timers = Timers(frozenset((Even(), Odd(), NoOp())))
        return ActorModelState(
            actor_states=tuple(
                PingerState(int(f["sent"][k]), int(f["recv"][k]))
                for k in range(self.n)
            ),
            network=UnorderedNonDuplicatingNetwork(counts),
            timers_set=tuple(timers for _ in range(self.n)),
            history=(),
        )

    # --- device kernels ------------------------------------------------------

    def packed_step(self, words):
        import jax.numpy as jnp

        L = self._layout
        n = self.n
        one = jnp.uint32(1)
        cmax = jnp.uint32((1 << self._count_bits) - 1)
        nmax = jnp.uint32((1 << self._net_bits) - 1)
        nxt, valid, ovf = [], [], []

        for i in range(n):
            for parity in (0, 1):
                targets = self._targets[(i, parity)]
                if not targets:
                    # No matching peer: the timeout is a pure re-arm, a
                    # suppressed no-op — statically invalid.
                    nxt.append(words)
                    valid.append(jnp.bool_(False))
                    ovf.append(jnp.bool_(False))
                    continue
                sent = L.get(words, "sent", i)
                w = L.set(words, "sent", sent + jnp.uint32(len(targets)), i)
                o = sent + jnp.uint32(len(targets)) > cmax
                for j in targets:
                    c = L.get(w, "net", self._ping_code[(i, j)])
                    o = o | (c == nmax)
                    w = L.set(w, "net", c + one, self._ping_code[(i, j)])
                nxt.append(w)
                valid.append(jnp.bool_(True))
                ovf.append(o)

        for (i, j), code in self._ping_code.items():
            # Deliver Ping(i->j): j replies Pong(j->i).
            c = L.get(words, "net", code)
            pong = self._pong_code[(j, i)]
            cp = L.get(words, "net", pong)
            w = L.set(words, "net", c - one, code)
            w = L.set(w, "net", cp + one, pong)
            nxt.append(w)
            valid.append(c > 0)
            ovf.append((c > 0) & (cp == nmax))
        for (i, j), code in self._pong_code.items():
            # Deliver Pong(i->j): j counts a received pong.
            c = L.get(words, "net", code)
            r = L.get(words, "recv", j)
            w = L.set(words, "net", c - one, code)
            w = L.set(w, "recv", r + one, j)
            nxt.append(w)
            valid.append(c > 0)
            ovf.append((c > 0) & (r == cmax))

        return jnp.stack(nxt), jnp.stack(valid), jnp.stack(ovf)

    def packed_properties(self, words):
        import jax.numpy as jnp

        return jnp.stack([jnp.bool_(True)])  # the object model's "true"


def main(argv=None) -> None:
    """CLI mirroring timers.rs:115-164 (``check`` bounded, see module doc)."""
    import sys

    from ..report import WriteReporter

    args = list(sys.argv[1:] if argv is None else argv)
    cmd = args.pop(0) if args else None
    if cmd in ("check", "check-xla"):
        # ``check`` runs the device (XLA) engine; custom network semantics
        # fall back to the host oracle (the packed codec models the
        # default network).
        netname = args.pop(0) if args else None
        if netname is None:
            from ..backend import configure_compile_cache

            configure_compile_cache()
            print("Model checking Pingers on XLA (bounded to 100k states).")
            (
                PackedTimers(3)
                .checker()
                .target_state_count(100_000)
                .spawn_xla(frontier_capacity=1 << 15, table_capacity=1 << 18)
                .report(WriteReporter())
            )
        else:
            network = Network.from_name(netname)
            print("Model checking Pingers (bounded to 100k states).")
            (
                timers_model(3, network)
                .checker()
                .target_state_count(100_000)
                .spawn_dfs()
                .report(WriteReporter())
            )
    elif cmd == "check-host":
        network = Network.from_name(args.pop(0)) if args else None
        print("Model checking Pingers (bounded to 100k states).")
        (
            timers_model(3, network)
            .checker()
            .target_state_count(100_000)
            .spawn_dfs()
            .report(WriteReporter())
        )
    elif cmd == "explore":
        address = args.pop(0) if args else "localhost:3000"
        network = Network.from_name(args.pop(0)) if args else None
        print(f"Exploring state space for Pingers on {address}.")
        timers_model(3, network).checker().serve(address)
    else:
        print("USAGE:")
        print("  timers check [NETWORK]       (device/XLA engine)")
        print("  timers check-host [NETWORK]  (sequential host oracle)")
        print("  timers check-xla             (alias of check)")
        print("  timers explore [ADDRESS] [NETWORK]")
        print(f"NETWORK: {' | '.join(Network.names())}")


if __name__ == "__main__":
    main()
