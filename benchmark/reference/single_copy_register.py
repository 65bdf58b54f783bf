"""Plain reference for the single-copy register (upstream
``examples/single-copy-register.rs``).

A breadth-first search over plain tuples, written from the upstream model
alone: it imports nothing of the system under test. ``server_count``
servers each hold one register value with no replication; ``client_count``
register clients (one ``Put``, then one ``Get``) talk to them over an
unordered, non-duplicating network, and a linearizability tester of a
register rides in the state. Properties: ``linearizable`` (always) and
``value chosen`` (sometimes).

Ids are ints: servers ``0..S-1``, clients ``S..S+C-1``. A server's state is
its value (``None`` before any write, the upstream's ``Value::default()``);
a client's is ``(awaiting, op_count)``. Messages are tagged tuples
(``("Put", request_id, value)``, ``("GetOk", request_id, value)``, ...). A
state is ``(actors, network, history)``: ``network`` a frozenset of
``((src, dst, msg), count)`` and ``history`` the tester, ``(completed,
in_flight, valid)``.

The states are plain values, so the visited set holds them whole: no
fingerprint, no collision. The control (``control_seed``) keys the visited
set on a 16-bit hash of the state instead, salted by the seed.
"""

from __future__ import annotations

import hashlib
from collections import deque
from typing import Dict, List, Optional

PROPERTIES = (("linearizable", "always"), ("value chosen", "sometimes"))


# --- the register tester ---------------------------------------------------


def _invoke(history, thread, op):
    completed, in_flight, valid = history
    if not valid:
        return history
    done, running = dict(completed), dict(in_flight)
    if thread in running:
        return (completed, in_flight, False)
    prereqs = tuple(sorted(
        (t, len(ops) - 1) for t, ops in done.items() if t != thread and ops
    ))
    running[thread] = (prereqs, op)
    done.setdefault(thread, ())
    return (tuple(sorted(done.items())), tuple(sorted(running.items())), True)


def _return(history, thread, ret):
    completed, in_flight, valid = history
    if not valid:
        return history
    done, running = dict(completed), dict(in_flight)
    if thread not in running:
        return (completed, in_flight, False)
    prereqs, op = running.pop(thread)
    done[thread] = done.get(thread, ()) + ((prereqs, op, ret),)
    return (tuple(sorted(done.items())), tuple(sorted(running.items())), True)


def _linearizable(history) -> bool:
    """Whether some total order of the operations agrees with a register
    and with every recorded real-time prerequisite. In-flight operations
    may take effect or not. A depth-first search over (register value,
    operations taken per thread, in-flight operations not yet taken),
    each such point searched once."""
    completed, in_flight, valid = history
    if not valid:
        return False
    ops = dict(completed)
    running = dict(in_flight)
    threads = sorted(set(ops) | set(running))
    col = {t: k for k, t in enumerate(threads)}
    sizes = tuple(len(ops.get(t, ())) for t in threads)
    seen = set()

    def blocked(prereqs, taken):
        # Unmet while the peer's operation ``i`` is not yet taken.
        return any(taken[col[t]] <= i for t, i in prereqs)

    def search(value, taken, pending):
        if taken == sizes:
            return True
        if (value, taken, pending) in seen:
            return False
        seen.add((value, taken, pending))
        for k, t in enumerate(threads):
            if taken[k] == sizes[k]:
                if t not in pending:
                    continue
                prereqs, op = running[t]
                if blocked(prereqs, taken):
                    continue
                nxt_value = op[1] if op[0] == "Write" else value
                if search(nxt_value, taken, pending - {t}):
                    return True
                continue
            prereqs, op, ret = ops[t][taken[k]]
            if blocked(prereqs, taken):
                continue
            if op[0] == "Write" and ret[0] == "WriteOk":
                nxt_value = op[1]
            elif op[0] == "Read" and ret[0] == "ReadOk" and ret[1] == value:
                nxt_value = value
            else:
                continue
            nxt = taken[:k] + (taken[k] + 1,) + taken[k + 1:]
            if search(nxt_value, nxt, pending):
                return True
        return False

    return search(None, (0,) * len(threads), frozenset(running))


# --- actors -----------------------------------------------------------------


def _server_msg(value, src, msg, send):
    """The server's new value, or None where it leaves the state unset
    (single-copy-register.rs:18-46)."""
    if msg[0] == "Put":
        send(src, ("PutOk", msg[1]))
        return (msg[2],)
    if msg[0] == "Get":
        send(src, ("GetOk", msg[1], value))
    return None


def _client_start(me, S, send):
    send(me % S, ("Put", me, chr(ord("A") + me - S)))
    return (me, 1)  # (awaiting, op_count)


def _client_msg(me, c, msg, S, send):
    """One Put, then one Get (``RegisterClient`` with ``put_count`` 1)."""
    awaiting, ops = c
    if awaiting is None:
        return None
    if msg[0] == "PutOk" and msg[1] == awaiting:
        send((me + ops) % S, ("Get", (ops + 1) * me))
        return ((ops + 1) * me, ops + 1)
    if msg[0] == "GetOk" and msg[1] == awaiting:
        return (None, ops + 1)
    return None


# --- the model ----------------------------------------------------------------


class SingleCopyRegister:
    def __init__(self, client_count: int, server_count: int):
        self.C, self.S = client_count, server_count

    def _record_out(self, history, src, msg):
        if msg[0] == "Get":
            return _invoke(history, src, ("Read",))
        if msg[0] == "Put":
            return _invoke(history, src, ("Write", msg[2]))
        return history

    def _record_in(self, history, dst, msg):
        if msg[0] == "GetOk":
            return _return(history, dst, ("ReadOk", msg[2]))
        if msg[0] == "PutOk":
            return _return(history, dst, ("WriteOk",))
        return history

    def init_state(self):
        sends: list = []
        actors = [None] * self.S
        for k in range(self.C):
            me = self.S + k
            actors.append(_client_start(me, self.S, lambda d, m, me=me: sends.append((me, d, m))))
        net: Dict = {}
        history = ((), (), True)
        for src, dst, msg in sends:
            history = self._record_out(history, src, msg)
            net[(src, dst, msg)] = net.get((src, dst, msg), 0) + 1
        return (tuple(actors), frozenset(net.items()), history)

    def successors(self, state) -> List:
        actors, network, history = state
        out = []
        for env, _count in network:
            src, dst, msg = env
            if dst >= len(actors):
                continue
            sends: list = []

            def send(d, m, dst=dst):
                sends.append((dst, d, m))

            if dst < self.S:
                new = _server_msg(actors[dst], src, msg, send)
            else:
                new = _client_msg(dst, actors[dst], msg, self.S, send)
            if new is None and not sends:
                continue  # the actor ignores this message
            h = self._record_in(history, dst, msg)
            nxt_actors = list(actors)
            if new is not None:
                nxt_actors[dst] = new[0] if dst < self.S else new
            net = dict(network)
            if net[env] == 1:
                del net[env]
            else:
                net[env] -= 1
            for s, d, m in sends:
                h = self._record_out(h, s, m)
                net[(s, d, m)] = net.get((s, d, m), 0) + 1
            out.append((tuple(nxt_actors), frozenset(net.items()), h))
        return out

    def holds(self, state, cache: dict) -> Dict[str, bool]:
        history = state[2]
        lin = cache.get(history)
        if lin is None:
            lin = cache[history] = _linearizable(history)
        chosen = any(
            msg[0] == "GetOk" and msg[2] is not None for (_s, _d, msg), _c in state[1]
        )
        return {"linearizable": lin, "value chosen": chosen}


def _canonical(x):
    if isinstance(x, frozenset):
        return tuple(sorted((_canonical(v) for v in x), key=repr))
    if isinstance(x, tuple):
        return tuple(_canonical(v) for v in x)
    return x


def _salted_hash16(state, seed: int) -> int:
    digest = hashlib.blake2b(repr(_canonical(state)).encode(), digest_size=8,
                             key=(seed % 2**64).to_bytes(8, "little"))
    return int.from_bytes(digest.digest(), "little") & 0xFFFF


def explore(params: dict, control_seed: Optional[int] = None) -> dict:
    """Full breadth-first exploration: generated and unique counts, and for
    each property the depth (states on the path) of its shortest witness.
    ``control_seed`` switches on the control's 16-bit visited-set key."""
    model = SingleCopyRegister(int(params["client_count"]), int(params["server_count"]))

    def key(state):
        return state if control_seed is None else _salted_hash16(state, control_seed)

    init = model.init_state()
    seen = {key(init)}
    queue = deque([(init, 1)])
    generated = 1
    found: Dict[str, int] = {}
    cache: dict = {}
    expect = dict(PROPERTIES)
    while queue:
        state, depth = queue.popleft()
        for name, ok in model.holds(state, cache).items():
            if name not in found and ok == (expect[name] == "sometimes"):
                found[name] = depth
        for nxt in model.successors(state):
            generated += 1
            k = key(nxt)
            if k not in seen:
                seen.add(k)
                queue.append((nxt, depth + 1))
    return {"generated": generated, "unique": len(seen), "discoveries": found}


# --- reading the system's states ------------------------------------------------


def _plain(x):
    """Tagged tuples from the system's message and operation values (tuple
    subclasses named after their variant), ints from its actor ids."""
    if isinstance(x, bool) or x is None or isinstance(x, str):
        return x
    if isinstance(x, int):
        return int(x)
    if isinstance(x, tuple):
        fields = tuple(_plain(v) for v in x)
        if hasattr(x, "_fields") and hasattr(type(x), "_variant_tag"):
            return (type(x).__name__,) + fields
        return fields
    raise TypeError(f"cannot read {type(x).__name__} {x!r}")


def from_program(state, server_count: int):
    """The reference form of the system's object-level state."""
    actors = []
    for i, a in enumerate(state.actor_states):
        if i < server_count:
            actors.append(_plain(a))
        else:
            actors.append((_plain(a.awaiting), int(a.op_count)))
    network = frozenset(
        ((int(e.src), int(e.dst), _plain(e.msg)), int(c))
        for e, c in state.network.counts.items()
    )
    h = state.history

    def prereqs(p):
        return tuple(sorted((int(t), int(i)) for t, i in p.items()))

    completed = tuple(sorted(
        (int(t), tuple((prereqs(p), _plain(op), _plain(ret)) for p, op, ret in ops))
        for t, ops in h.history_by_thread.items()
    ))
    in_flight = tuple(sorted(
        (int(t), (prereqs(p), _plain(op))) for t, (p, op) in h.in_flight_by_thread.items()
    ))
    return (tuple(actors), network, (completed, in_flight, bool(h.is_valid_history)))


def replay(states: List, name: str, params: dict, depth: int) -> Optional[str]:
    """Checks a discovery path given as the system's states: it starts at
    the initial state, every step is a transition of the reference, the
    property's discovery condition holds at its end, and it is as short as
    the reference's witness (``depth`` states). Returns what is wrong, or
    None."""
    S = int(params["server_count"])
    model = SingleCopyRegister(int(params["client_count"]), S)
    xs = [from_program(s, S) for s in states]
    if xs[0] != model.init_state():
        return "path does not start at the initial state"
    for i in range(len(xs) - 1):
        if xs[i + 1] not in model.successors(xs[i]):
            return f"step {i} is not a transition"
    ok = model.holds(xs[-1], {})[name]
    if ok != (dict(PROPERTIES)[name] == "sometimes"):
        return "the last state does not witness the property"
    if len(xs) != depth:
        return f"path has {len(xs)} states, the shortest witness {depth}"
    return None
