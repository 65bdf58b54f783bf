"""Single-copy register: each server exposes a rewritable register with no
consensus between servers.

Mirrors ``/root/reference/examples/single-copy-register.rs``: the system is
linearizable iff there is exactly one server (one copy); with two or more
servers clients can observe stale values and the ``linearizable`` property
yields a counterexample.

Exact-count oracles from the reference's own test
(single-copy-register.rs:110,136): 93 unique states at 2 clients / 1 server
(full coverage), 20 unique states at 2 clients / 2 servers (BFS stops at the
linearizability counterexample).

The reference's ``Value::default()`` (``'\\u{0}'``) is rendered as ``None``:
the "unwritten" register value, consistent with the ``Register(None)`` spec
initial state used throughout this package.
"""

from __future__ import annotations

from typing import Optional

from ..actor import Actor, ActorModel, Id, Network, Out, StateRef
from ..actor import register as reg
from ..core import Expectation
from ..packing import PackedModelAdapter, bits_for
from ..semantics import LinearizabilityTester
from ..semantics.register import Register


class SingleCopyActor(Actor):
    """A server holding one unreplicated register value
    (single-copy-register.rs:18-46). The actor state *is* the value."""

    def on_start(self, id: Id, out: Out):
        return None  # the unwritten value (Value::default())

    def on_msg(self, id: Id, state: StateRef, src: Id, msg, out: Out) -> None:
        if isinstance(msg, reg.Put):
            state.set(msg.value)
            out.send(src, reg.PutOk(msg.request_id))
        elif isinstance(msg, reg.Get):
            out.send(src, reg.GetOk(msg.request_id, state.get()))
        # Internal messages don't exist for this protocol; anything else is
        # ignored (a no-op action, suppressed by the model).


def single_copy_register_model(
    client_count: int = 2,
    server_count: int = 1,
    network: Optional[Network] = None,
    consistency: str = "linearizable",
) -> ActorModel:
    """Build the checkable model (single-copy-register.rs:55-86).

    ``consistency`` selects the tester riding in the history:
    ``"linearizable"`` (the reference's configuration) or
    ``"sequential"`` — the same protocol checked against
    ``SequentialConsistencyTester`` (sequential_consistency.rs:53-241),
    which the reference defines but never wires into an example.
    """
    if network is None:
        network = Network.new_unordered_nonduplicating()
    if consistency == "linearizable":
        tester, prop_name = LinearizabilityTester(Register(None)), "linearizable"
    elif consistency == "sequential":
        from ..semantics.sequential_consistency import SequentialConsistencyTester

        tester = SequentialConsistencyTester(Register(None))
        prop_name = "sequentially consistent"
    else:
        raise ValueError(f"unknown consistency {consistency!r}")

    model = ActorModel(cfg=None, init_history=tester)
    for _ in range(server_count):
        model.actor(SingleCopyActor())
    for _ in range(client_count):
        model.actor(reg.RegisterClient(put_count=1, server_count=server_count))
    return (
        model.init_network(network)
        .property(Expectation.ALWAYS, prop_name, reg.linearizable_condition())
        .property(Expectation.SOMETIMES, "value chosen", reg.value_chosen_condition)
        .record_msg_in(reg.record_returns)
        .record_msg_out(reg.record_invocations)
    )


class PackedSingleCopyRegister(reg.PackedClientsMixin, PackedModelAdapter):
    """The single-copy register on the device engine (``spawn_xla``) — the
    first packed model carrying a **consistency tester** in its state
    (SURVEY §7 M4 variant (a)).

    Everything is declared through :mod:`stateright_tpu.packing`:

    - per-server register values and per-client script positions are plain
      layout fields;
    - the non-duplicating multiset network packs as per-envelope counts
      over the *closed* envelope universe of this protocol (each client
      performs one Put then one Get with statically known request ids and
      targets, register.rs:94-260, so the universe is tiny);
    - the ``LinearizabilityTester`` history packs exactly via
      :class:`~stateright_tpu.packing.BoundedHistory` (2 ops/client).

    The consistency property (``linearizable``, or ``sequentially
    consistent`` under ``consistency="sequential"``) is checked on device
    via the static interleaving enumeration
    (:mod:`stateright_tpu.semantics.device`, SURVEY §7 M4 variant (b)) —
    EXACTLY while the client count keeps the enumeration under
    ``MAX_PATTERNS_EXACT`` (<= 4 clients at 2 ops each; past the
    single-shot pattern budget it searches the progress lattice); beyond that
    the model declares ``host_verified_properties`` and the device runs a
    diverse sampled one-sided pass with exact host confirmation of flagged
    rows (variant (a)). With one server the model reaches full coverage (93
    unique states at 2 clients, single-copy-register.rs:110); with two
    servers the stale-read counterexample is found on device
    (single-copy-register.rs:136).
    """

    #: Per-client op bound (one Put then one Get): sizes the packed history
    #: AND the exact-vs-sampled gate below — one constant, one contract.
    MAX_OPS = 2

    def __init__(
        self,
        client_count: int = 2,
        server_count: int = 1,
        consistency: str = "linearizable",
        device_exact: Optional[bool] = None,
        pattern_limit: int = 20_000,
    ):
        from ..actor.network import Envelope
        from ..packing import BoundedHistory, LayoutBuilder, OverflowError32
        from ..semantics.device import MAX_PATTERNS_EXACT, pattern_count
        from ..semantics.register import Read, ReadOk, Write, WriteOk

        self._inner = single_copy_register_model(
            client_count, server_count, consistency=consistency
        )
        self._consistency = consistency
        self._prop_name = self._inner.properties()[0].name
        # Device-exact serialization checking scales to the interleaving
        # budget (the progress lattice past the single-shot lane limit); past
        # it — or with ``device_exact=False`` — the property runs as a
        # conservative device pass (a diverse pattern subsample — True
        # proves serializability) with exact host confirmation of the
        # flagged remainder: the engine's host_verified_properties path
        # (xla.py M4 variant (a)).
        P = pattern_count(client_count, self.MAX_OPS)
        if device_exact is None:
            device_exact = P <= MAX_PATTERNS_EXACT
        elif device_exact and P > MAX_PATTERNS_EXACT:
            raise ValueError(
                f"{P} interleavings exceed the exact device budget "
                "(semantics.device.MAX_PATTERNS_EXACT)"
            )
        if not device_exact:
            self.host_verified_properties = frozenset({self._prop_name})
            # The sampled pass's pattern budget is the cliff's tuning
            # knob: more sampled patterns = fewer
            # device false alarms (host confirmations) but a bigger
            # compile and a wider per-level pipeline. tools/hv_cliff.py
            # characterizes the trade; 20k is the shipped default.
            self._pattern_limit = pattern_limit
        else:
            self._pattern_limit = None
        S, C = server_count, client_count
        self.S, self.C = S, C
        self.values = self._client_values()
        V = len(self.values)
        self.V = V

        # Closed envelope universe: per client k (abs id i = S+k), block of
        # 3 + V codes: Put, PutOk, Get, GetOk(value) per value.
        self._B = 3 + V
        envs = []
        for k in range(C):
            i = S + k
            envs.append(Envelope(Id(i), Id(i % S), reg.Put(1 * i, self.values[1 + k])))
            envs.append(Envelope(Id(i % S), Id(i), reg.PutOk(1 * i)))
            envs.append(Envelope(Id(i), Id((i + 1) % S), reg.Get(2 * i)))
            for v in self.values:
                envs.append(Envelope(Id((i + 1) % S), Id(i), reg.GetOk(2 * i, v)))
        self._envs = envs
        self._env_code = {env: c for c, env in enumerate(envs)}
        U = len(envs)
        self._U = U

        value_bits = bits_for(V - 1)
        op_ret_bits = max(V.bit_length(), 2)
        b = LayoutBuilder().array("srv", S, value_bits)
        self._client_layout(b)
        b.array("net", U, 2)
        self._hist = BoundedHistory(
            b,
            thread_ids=[Id(S + k) for k in range(C)],
            max_ops=self.MAX_OPS,
            op_bits=op_ret_bits,
            ret_bits=op_ret_bits,
            real_time=consistency == "linearizable",
        )
        self._layout = b.finish()
        self._hist.bind(self._layout)
        self.state_words = self._layout.words
        self.max_actions = U

        # History op/ret codes over the closed value universe.
        def op_code(op):
            if isinstance(op, Read):
                return 0
            return 1 + self.values.index(op.value)

        def code_op(c):
            return Read() if c == 0 else Write(self.values[c - 1])

        def ret_code(ret):
            if isinstance(ret, WriteOk):
                return 0
            return 1 + self.values.index(ret.value)

        def code_ret(c):
            return WriteOk() if c == 0 else ReadOk(self.values[c - 1])

        self._op_code, self._code_op = op_code, code_op
        self._ret_code, self._code_ret = ret_code, code_ret
        self._OverflowError32 = OverflowError32

    # --- codec -------------------------------------------------------------

    def pack(self, state) -> "np.ndarray":
        import numpy as np

        S, C = self.S, self.C
        srv = [self.values.index(state.actor_states[s]) for s in range(S)]
        fields = dict(srv=srv)
        self._pack_clients(fields, state)
        net = [0] * self._U
        for env, count in state.network.counts.items():
            code = self._env_code.get(env)
            if code is None:
                raise self._OverflowError32(f"envelope outside universe: {env!r}")
            if count > 3:
                raise self._OverflowError32(f"envelope count {count} > 3: {env!r}")
            net[code] = count
        fields["net"] = net
        fields.update(self._hist.from_tester(state.history, self._op_code, self._ret_code))
        return self._layout.pack(**fields)

    def unpack(self, words):
        from ..actor.model_state import ActorModelState
        from ..actor.network import UnorderedNonDuplicatingNetwork
        from ..actor.timers import Timers
        from ..semantics import LinearizabilityTester
        from ..semantics.register import Register
        from ..semantics.sequential_consistency import SequentialConsistencyTester

        f = self._layout.unpack(words)
        S, C = self.S, self.C
        actor_states = [self.values[code] for code in f["srv"]]
        self._unpack_clients(f, actor_states)
        counts = {
            self._envs[code]: count for code, count in enumerate(f["net"]) if count
        }
        make_tester = (
            (lambda: LinearizabilityTester(Register(None)))
            if self._consistency == "linearizable"
            else (lambda: SequentialConsistencyTester(Register(None)))
        )
        history = self._hist.to_tester(
            f, make_tester, self._code_op, self._code_ret
        )
        return ActorModelState(
            actor_states=tuple(actor_states),
            network=UnorderedNonDuplicatingNetwork(counts),
            timers_set=tuple(Timers() for _ in range(S + C)),
            history=history,
        )

    # --- device kernels -----------------------------------------------------

    def _net_dec(self, words, code):
        L = self._layout
        return L.set(words, "net", L.get(words, "net", code) - 1, code)

    def _net_inc(self, words, code):
        """Increment an envelope count; returns (words', overflow)."""
        import jax.numpy as jnp

        L = self._layout
        cnt = L.get(words, "net", code)
        return L.set(words, "net", cnt + 1, code), cnt == jnp.uint32(3)

    def packed_step(self, words):
        """Full action fan-out: deliver each universe envelope. No-op
        deliveries (script mismatches, model.rs:286-289) are masked
        invalid; capacity overflows are reported on the third output."""
        import jax.numpy as jnp

        L = self._layout
        S, C, V, B = self.S, self.C, self.V, self._B
        u32 = jnp.uint32

        nxt, valid, ovf = [], [], []
        for k in range(C):
            i = S + k
            base = k * B
            deliverable = lambda code: L.get(words, "net", code) > 0  # noqa: E731

            # Put -> server i%S: store the value, reply PutOk.
            code = base + 0
            w = self._net_dec(words, code)
            w = L.set(w, "srv", 1 + k, i % S)
            w, o = self._net_inc(w, base + 1)
            nxt.append(w)
            valid.append(deliverable(code))
            ovf.append(o)

            # PutOk -> client: record WriteOk return, invoke Read, send Get.
            code = base + 1
            eligible = L.get(words, "cl_await", k) == u32(1)
            w = self._net_dec(words, code)
            w = L.set(w, "cl_await", 2, k)
            w = L.set(w, "cl_ops", 2, k)
            w, o1 = self._hist.on_return(w, k, u32(0))  # WriteOk
            w = self._hist.on_invoke(w, k, u32(0))  # Read
            w, o2 = self._net_inc(w, base + 2)
            nxt.append(w)
            valid.append(deliverable(code) & eligible)
            ovf.append(o1 | o2)

            # Get -> server (i+1)%S: reply GetOk with the current value
            # (a traced index into the GetOk block of the universe).
            code = base + 2
            srv_val = L.get(words, "srv", (i + 1) % S)
            w = self._net_dec(words, code)
            w, o = self._net_inc(w, base + 3 + srv_val.astype(jnp.int32))
            nxt.append(w)
            valid.append(deliverable(code))
            ovf.append(o)

            # GetOk(value) -> client: record ReadOk return; script complete.
            for vi in range(V):
                code = base + 3 + vi
                eligible = L.get(words, "cl_await", k) == u32(2)
                w = self._net_dec(words, code)
                w = L.set(w, "cl_await", 0, k)
                w = L.set(w, "cl_ops", 3, k)
                w, o = self._hist.on_return(w, k, u32(1 + vi))  # ReadOk(value)
                nxt.append(w)
                valid.append(deliverable(code) & eligible)
                ovf.append(o)

        valid = jnp.stack(valid)
        return jnp.stack(nxt), valid, jnp.stack(ovf) & valid

    def packed_properties(self, words):
        """[serializable, value chosen] — order of ``properties()``. The
        first is the serialization check for the configured consistency
        model: device-EXACT while the interleaving count fits, or the
        diverse-subsample conservative predicate under
        ``host_verified_properties`` beyond (see ``__init__``)."""
        import jax.numpy as jnp

        L = self._layout
        if self._consistency == "linearizable":
            lin = self.device_linearizable_register(words, self._pattern_limit)
        else:
            lin = self.device_sequentially_consistent_register(
                words, self._pattern_limit
            )

        chosen = jnp.bool_(False)
        for k in range(self.C):
            for vi in range(1, self.V):  # real (written) values only
                chosen = chosen | (L.get(words, "net", k * self._B + 3 + vi) > 0)
        return jnp.stack([lin, chosen])


class PackedSingleCopyRegisterOrdered(reg.PackedClientsMixin, PackedModelAdapter):
    """The single-copy register over the **ordered** network on the device
    engine: the packed form of per-directed-pair FIFO channels where only
    flow heads are deliverable (network.rs:57-67, 221-293), encoded with
    :class:`~stateright_tpu.packing.FifoLanes`.

    One lane per directed flow: ``k`` = client k -> the server (codes
    0 = Put, 1 = Get), ``C + k`` = server -> client k (codes 0 = PutOk,
    1 + v = GetOk(values[v])). An action slot is a lane, not an envelope:
    delivering pops the head; a head whose delivery is a no-op (a reply the
    client is not awaiting) blocks its lane exactly like the object model's
    head-of-channel-only rule. The reference has no exact-count oracle for
    this configuration (its tests use unordered networks; ``bench.sh`` runs
    the ordered config as a benchmark), so parity is engine-vs-engine:
    differential action-level tests against this package's object
    ``OrderedNetwork`` model.
    """

    def __init__(self, client_count: int = 2):
        from ..packing import (
            BoundedHistory,
            FifoLanes,
            LayoutBuilder,
            OverflowError32,
            bits_for,
        )

        if client_count != 2:
            raise ValueError(
                "the packed model's exact device linearizability covers the "
                "2-client shape; other sizes run on the host engines"
            )
        C, S = client_count, 1
        self.C, self.S = C, S
        self._inner = single_copy_register_model(C, S, Network.new_ordered())
        self._OverflowError32 = OverflowError32
        self.values = self._client_values()
        NV = len(self.values)
        self.NV = NV
        self.max_actions = 2 * C  # one action slot per lane

        b = LayoutBuilder()
        b.array("srv", S, bits_for(NV - 1))
        self._client_layout(b)
        # Lane k: client k -> server; lane C+k: server -> client k. Depth 2
        # is headroom: the Put/Get script keeps at most one message in
        # flight per direction (overflow reports loudly regardless).
        self._lanes = FifoLanes(b, "flows", lanes=2 * C, depth=2, code_bits=bits_for(NV))
        code_bits = bits_for(NV)
        self._hist = BoundedHistory(
            b,
            thread_ids=[Id(S + k) for k in range(C)],
            max_ops=2,
            op_bits=code_bits,
            ret_bits=code_bits,
        )
        self._layout = b.finish()
        self._hist.bind(self._layout)
        self._lanes.bind(self._layout)
        self.state_words = self._layout.words

        codecs = reg.history_codecs(self.values)
        self._op_code, self._code_op, self._ret_code, self._code_ret = codecs

    # --- lane codec ---------------------------------------------------------

    def _lane_key(self, lane: int):
        C, S = self.C, self.S
        if lane < C:
            return (Id(S + lane), Id(0))
        return (Id(0), Id(S + (lane - C)))

    def _msg_code(self, lane: int, msg) -> int:
        k = lane if lane < self.C else lane - self.C
        i = self.S + k
        if lane < self.C:  # client -> server
            if isinstance(msg, reg.Put) and msg == reg.Put(i, self.values[1 + k]):
                return 0
            if isinstance(msg, reg.Get) and msg == reg.Get(2 * i):
                return 1
        else:  # server -> client
            if isinstance(msg, reg.PutOk) and msg == reg.PutOk(i):
                return 0
            if isinstance(msg, reg.GetOk) and msg.request_id == 2 * i:
                return 1 + self._val_code(msg.value)
        raise self._OverflowError32(f"message outside universe on lane {lane}: {msg!r}")

    def _code_msg(self, lane: int, code: int):
        k = lane if lane < self.C else lane - self.C
        i = self.S + k
        if lane < self.C:
            return reg.Put(i, self.values[1 + k]) if code == 0 else reg.Get(2 * i)
        if code == 0:
            return reg.PutOk(i)
        return reg.GetOk(2 * i, self.values[code - 1])

    # --- codec -------------------------------------------------------------

    def pack(self, state):
        C = self.C
        fields: dict = {"srv": [self._val_code(state.actor_states[0])]}
        self._pack_clients(fields, state)
        cells = [0] * (2 * C * self._lanes.depth)
        lens = [0] * (2 * C)
        flows = dict(state.network.flows)
        for lane in range(2 * C):
            msgs = flows.pop(self._lane_key(lane), ())
            lane_cells, n = self._lanes.host_pack_lane(
                [self._msg_code(lane, m) for m in msgs]
            )
            cells[lane * self._lanes.depth : (lane + 1) * self._lanes.depth] = lane_cells
            lens[lane] = n
        if flows:
            raise self._OverflowError32(f"flows outside universe: {list(flows)!r}")
        fields["flows_cells"] = cells
        fields["flows_lens"] = lens
        fields.update(
            self._hist.from_tester(state.history, self._op_code, self._ret_code)
        )
        return self._layout.pack(**fields)

    def unpack(self, words):
        from ..actor.model_state import ActorModelState
        from ..actor.network import OrderedNetwork
        from ..actor.timers import Timers
        from ..semantics import LinearizabilityTester
        from ..semantics.register import Register

        f = self._layout.unpack(words)
        C, S = self.C, self.S
        actor_states = [self.values[f["srv"][0]]]
        self._unpack_clients(f, actor_states)
        flows = {}
        for lane in range(2 * C):
            n = f["flows_lens"][lane]
            cells = f["flows_cells"][
                lane * self._lanes.depth : lane * self._lanes.depth + n
            ]
            if n:
                flows[self._lane_key(lane)] = tuple(
                    self._code_msg(lane, c - 1) for c in cells
                )
        history = self._hist.to_tester(
            f,
            lambda: LinearizabilityTester(Register(None)),
            self._code_op,
            self._code_ret,
        )
        return ActorModelState(
            actor_states=tuple(actor_states),
            network=OrderedNetwork(flows),
            timers_set=tuple(Timers() for _ in range(S + C)),
            history=history,
        )

    # --- device kernels -----------------------------------------------------

    def packed_step(self, words):
        """One action slot per lane: deliver its head (or mask the slot
        invalid when the lane is empty / the head's delivery is a no-op)."""
        import jax
        import jax.numpy as jnp

        C = self.C
        to_server = jax.vmap(self._body_to_server, in_axes=(None, 0, 0))(
            words,
            jnp.arange(C, dtype=jnp.uint32),
            jnp.asarray([[k, C + k] for k in range(C)], jnp.uint32),
        )
        to_client = jax.vmap(self._body_to_client, in_axes=(None, 0, 0))(
            words,
            jnp.arange(C, dtype=jnp.uint32),
            jnp.asarray([[C + k, k] for k in range(C)], jnp.uint32),
        )
        nxt = jnp.concatenate([to_server[0], to_client[0]])
        valid = jnp.concatenate([to_server[1], to_client[1]])
        ovf = jnp.concatenate([to_server[2], to_client[2]])
        return nxt, valid, ovf & valid

    def _body_to_server(self, words, k, prm):
        """Head of client k's lane -> the server: Put stores the value and
        acks; Get replies with the current value (single-copy-register.rs:
        18-46). Always valid when nonempty — the server never no-ops."""
        import jax.numpy as jnp

        L, u32 = self._layout, jnp.uint32
        lane, reply_lane = prm[0], prm[1]
        code, nonempty = self._lanes.head(words, lane)
        w = self._lanes.pop(words, lane, enabled=nonempty)
        is_put = code == 0
        srv_val = L.get(words, "srv", 0)
        w = L.set(w, "srv", jnp.where(is_put & nonempty, k + u32(1), srv_val), 0)
        push_code = jnp.where(is_put, u32(0), u32(1) + srv_val)
        w, ovf = self._lanes.push(w, reply_lane, push_code, enabled=nonempty)
        return w, nonempty, nonempty & ovf

    def _body_to_client(self, words, k, prm):
        """Head of the server's lane -> client k: PutOk advances the script
        (record WriteOk, invoke Read, send Get); GetOk completes it. A reply
        the client is not awaiting is a no-op and BLOCKS the lane — the
        packed form of head-of-channel-only delivery."""
        import jax.numpy as jnp

        L, u32 = self._layout, jnp.uint32
        lane, req_lane = prm[0], prm[1]
        code, nonempty = self._lanes.head(words, lane)
        is_putok = code == 0
        await_k = L.get(words, "cl_await", k)
        eligible = nonempty & jnp.where(is_putok, await_k == u32(1), await_k == u32(2))
        w = self._lanes.pop(words, lane, enabled=eligible)
        w = L.set(
            w,
            "cl_await",
            jnp.where(eligible, jnp.where(is_putok, u32(2), u32(0)), await_k),
            k,
        )
        ops_k = L.get(words, "cl_ops", k)
        w = L.set(
            w,
            "cl_ops",
            jnp.where(eligible, jnp.where(is_putok, u32(2), u32(3)), ops_k),
            k,
        )
        o = jnp.bool_(False)
        for t in range(self.C):
            on_p = eligible & is_putok & (k == u32(t))
            w, o1 = self._hist.on_return(w, t, u32(0), enabled=on_p)  # WriteOk
            w = self._hist.on_invoke(w, t, u32(0), enabled=on_p)  # Read
            # GetOk(values[v]) lane code 1+v IS the ReadOk ret code.
            on_g = eligible & ~is_putok & (k == u32(t))
            w, o2 = self._hist.on_return(w, t, code, enabled=on_g)
            o = o | o1 | o2
        w, povf = self._lanes.push(w, req_lane, 1, enabled=eligible & is_putok)
        return w, eligible, eligible & (o | povf)

    def packed_properties(self, words):
        """[linearizable, value chosen]; "chosen" checks lane
        HEADS only — under ordered semantics only heads are deliverable
        (value_chosen_condition over iter_deliverable, network.rs:275-277)."""
        import jax.numpy as jnp

        lin = self.device_linearizable_register(words)
        chosen = jnp.bool_(False)
        for k in range(self.C):
            code, nonempty = self._lanes.head(words, self.C + k)
            chosen = chosen | (nonempty & (code >= jnp.uint32(2)))
        return jnp.stack([lin, chosen])


def main(argv=None) -> None:
    """CLI mirroring single-copy-register.rs:139-233:
    ``check``/``explore``/``spawn`` subcommands."""
    import sys

    from ..report import WriteReporter

    args = list(sys.argv[1:] if argv is None else argv)
    cmd = args.pop(0) if args else None
    if cmd in ("check", "check-xla"):
        # ``check`` runs the device (XLA) engine — the reference's check
        # likewise runs its fastest checker. Network semantics the packed
        # codec does not cover fall back to the host oracle.
        client_count = int(args.pop(0)) if args and args[0].isdigit() else 2
        netname = args.pop(0) if args else None
        if netname in (None, "ordered"):
            from ..backend import configure_compile_cache

            configure_compile_cache()
            print(
                f"Model checking a single-copy register with {client_count} "
                "clients on XLA."
            )
            model = (
                PackedSingleCopyRegisterOrdered(client_count, 1)
                if netname == "ordered"
                else PackedSingleCopyRegister(client_count, 1)
            )
            (
                model.checker()
                .spawn_xla(frontier_capacity=1 << 11, table_capacity=1 << 14)
                .report(WriteReporter())
            )
        else:
            network = Network.from_name(netname)
            print(
                f"Model checking a single-copy register with {client_count} "
                "clients."
            )
            (
                single_copy_register_model(client_count, 1, network)
                .checker()
                .spawn_dfs()
                .report(WriteReporter())
            )
    elif cmd == "check-host":
        client_count = int(args.pop(0)) if args else 2
        network = Network.from_name(args.pop(0)) if args else None
        print(f"Model checking a single-copy register with {client_count} clients.")
        (
            single_copy_register_model(client_count, 1, network)
            .checker()
            .spawn_dfs()
            .report(WriteReporter())
        )
    elif cmd == "explore":
        client_count = int(args.pop(0)) if args else 2
        address = args.pop(0) if args else "localhost:3000"
        network = Network.from_name(args.pop(0)) if args else None
        print(
            f"Exploring state space for single-copy register with "
            f"{client_count} clients on {address}."
        )
        single_copy_register_model(client_count, 1, network).checker().serve(address)
    elif cmd == "spawn":
        from ..actor.spawn import json_codec, spawn

        port = 3000
        serialize, deserialize = json_codec(reg.Put, reg.Get, reg.PutOk, reg.GetOk)
        print("  A server that implements a single-copy register.")
        print("  You can interact using netcat:")
        print(f"$ nc -u localhost {port}")
        print(serialize(reg.Put(1, "X")).decode())
        print(serialize(reg.Get(2)).decode())
        spawn(
            serialize,
            deserialize,
            [(Id.from_addr("127.0.0.1", port), SingleCopyActor())],
        )
    else:
        print("USAGE:")
        print("  single-copy-register check [CLIENT_COUNT] [NETWORK]  (device/XLA engine)")
        print("  single-copy-register check-host [CLIENT_COUNT] [NETWORK]  (sequential host oracle)")
        print("  single-copy-register check-xla [NETWORK]  (alias of check)")
        print("  single-copy-register explore [CLIENT_COUNT] [ADDRESS] [NETWORK]")
        print("  single-copy-register spawn")
        print(f"NETWORK: {' | '.join(Network.names())}")


if __name__ == "__main__":
    main()
