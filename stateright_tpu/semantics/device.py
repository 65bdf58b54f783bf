"""Exact consistency checking ON DEVICE, generalized over thread count,
operation count, and sequential spec.

The host testers (``linearizability.py`` / ``sequential_consistency.py``)
run a backtracking search per history
(``/root/reference/src/semantics/linearizability.rs:197-284``,
``/root/reference/src/semantics/sequential_consistency.rs:53-241``). A
backtracking search cannot be traced into an XLA program — but for the
statically-bounded histories packed models carry
(:class:`~stateright_tpu.packing.BoundedHistory`: T threads, at most M
completed ops plus one in-flight op each), the whole search space is a
*static enumeration*: every admissible serialization is a merge of the
per-thread sequences, i.e. an arrangement of the multiset
``{0^(M+1), ..., (T-1)^(M+1)}``. This module evaluates ALL of them as one
data-parallel expression — patterns become a constant ``[P, L]`` index
table, and each BFS frontier row checks every pattern simultaneously. That
is the TPU-first shape of the problem: no control flow, one fused
gather/where pipeline, ``P`` as a vector lane axis.

Semantics replicated (differentially tested against the host serializer):

- per-thread program order is preserved by construction (a thread's slots
  appear in sequence order in every pattern);
- **linearizability** additionally checks the recorded real-time
  prerequisites: an op invoked after a peer's op completed must be
  serialized after it (linearizability.rs:221-233);
- **sequential consistency** is the same enumeration with the real-time
  constraint dropped (sequential_consistency.rs:118-130 tracks only
  in-order per-thread consumption);
- in-flight ops "need never return" (the testers may exclude them): an
  excluded in-flight op is subsumed by a pattern scheduling it after every
  constrained op, because specs here are *total* (every op is invocable in
  every spec state) and a trailing op constrains nothing — completed-op
  prerequisites reference peer *completed* ops only;
- a poisoned history (``h_valid`` cleared by protocol misuse) is never
  serializable, matching the testers' HistoryError freeze.

Sizing: P = (T·(M+1))! / ((M+1)!)^T — 20 at 2×2, 1 680 at 3×2, 34 650 at
3×3, 369 600 at 4×2. Up to ``MAX_PATTERNS`` the whole enumeration runs as
one ``[P]``-lane pipeline. Past it the exact check searches the progress
lattice instead (``_lattice_serializable``): a pattern is a path through
``{0..M+1}^T``, so reachability over its (M+2)^T nodes and the running
value decides every pattern at once — 256 nodes × 16 values at 4×2, where
the enumeration took about 2 ms of device time a state on a TPU v5e. The
pattern axis is CHUNKED under ``lax.scan`` only for a sampled
pass (``pattern_limit`` past ``MAX_PATTERNS``). Beyond
``MAX_PATTERNS_EXACT`` (5 threads × 2 ops = 1.68e8) models fall back to
the engine's ``host_verified_properties`` path (a conservative sampled
device predicate + exact host confirmation, xla.py M4 variant (a)).

The pipeline carries per-thread RUNNING counts instead of precomputed
``slot``/``cnt_before`` tables: the only embedded constant is the
``tid[P, L]`` thread schedule (int8), which keeps the 4-thread exact
enumeration's constant footprint at ~4 MB instead of ~90 MB of derived
tables baked into the executable.
"""

from __future__ import annotations

import itertools
from functools import lru_cache
from typing import Tuple

import numpy as np

#: Single-shot lane budget: up to this many interleavings run as one
#: [P]-lane pipeline with no scan overhead.
MAX_PATTERNS = 50_000
#: Interleavings past which no exact device check is made: models
#: declare ``host_verified_properties`` instead.
MAX_PATTERNS_EXACT = 2_000_000
#: Pattern-block width for the scanned (sampled) path: live intermediates
#: are [batch, PATTERN_CHUNK] lanes.
PATTERN_CHUNK = 8_192
#: Frontier rows times serializer lanes (:func:`row_lanes`) that one
#: property block evaluates (:func:`block_rows`). The pattern
#: enumeration's temporaries grow with both: at 3 threads (1,680 lanes)
#: about 0.6 MB a row (2.48 GB at 4,096 rows by XLA's memory analysis of
#: the vmapped tester for a TPU v5e), so its 1,024-row blocks stay near
#: 0.6 GB beside the visited set and the action grid. The progress
#: lattice takes about 5 KB a row (2.7 MB at 512 rows): there the width
#: only trades the padding of a level's last block against loop trips.
ROW_LANE_BUDGET = 1 << 21


@lru_cache(maxsize=None)
def interleaving_tids(T: int, slots: int, limit: int = None) -> np.ndarray:
    """The ``tid[P, L]`` thread-schedule table for merges of T sequences of
    ``slots`` slots (L = T*slots): the thread scheduled at each step. The
    per-thread slot index and preceding-count tables are derivable by a
    running count and are NOT materialized (see module docstring). With
    ``limit`` (< the full count), a deterministic uniform random sample of
    ``limit`` arrangements is generated directly — the full table is never
    built.
    """
    L = T * slots
    P_full = pattern_count(T, slots - 1)
    if limit is not None and limit < P_full:
        # A one-sided (host-confirmed) pass wants pattern DIVERSITY, and it
        # must NOT materialize the full enumeration (1.7e8 patterns at 5
        # threads x 2 ops): sample arrangements directly — each row is an
        # independent uniform shuffle of the multiset {t^slots}, which is
        # uniform over distinct patterns (duplicates merely waste lanes;
        # negligible while limit << P). Deterministic seed for stable
        # compilation caching.
        rng = np.random.default_rng(0xC0FFEE)
        base = np.repeat(np.arange(T, dtype=np.int32), slots)
        tid = np.asarray(rng.permuted(np.tile(base, (limit, 1)), axis=1))
    else:
        pats: list = []

        def rec(remaining: tuple, t: int, cur: list) -> None:
            if t == T - 1:
                pat = list(cur)
                for pos in remaining:
                    pat[pos] = t
                pats.append(pat)
                return
            for comb in itertools.combinations(remaining, slots):
                taken = set(comb)
                nxt = list(cur)
                for pos in comb:
                    nxt[pos] = t
                rec(tuple(p for p in remaining if p not in taken), t + 1, nxt)

        rec(tuple(range(L)), 0, [0] * L)
        tid = np.asarray(pats, dtype=np.int32)
    return np.ascontiguousarray(tid.astype(np.int8))


@lru_cache(maxsize=None)
def interleaving_tables(
    T: int, slots: int, limit: int = None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Back-compat view of :func:`interleaving_tids` with the derived
    tables materialized: ``(tid[P, L], slot[P, L], cnt_before[P, L, T])``.
    The serializer itself no longer consumes the derived tables (it
    carries running counts); this form remains for tests/tooling."""
    tid = interleaving_tids(T, slots, limit).astype(np.int32)
    P, L = tid.shape
    slot = np.zeros((P, L), dtype=np.int32)
    cnt_before = np.zeros((P, L, T), dtype=np.int32)
    running = np.zeros((P, T), dtype=np.int32)
    rows = np.arange(P)
    for l in range(L):
        cnt_before[:, l, :] = running
        slot[:, l] = running[rows, tid[:, l]]
        running[rows, tid[:, l]] += 1
    return tid, slot, cnt_before


def pattern_count(T: int, max_ops: int) -> int:
    """P without building the tables: (T*(M+1))! / ((M+1)!)^T."""
    import math

    slots = max_ops + 1
    return math.factorial(T * slots) // math.factorial(slots) ** T


def row_lanes(hist, pattern_limit: int = None) -> int:
    """Lanes :func:`device_serializable` holds per row at once: every
    pattern up to ``MAX_PATTERNS``, one ``PATTERN_CHUNK`` of a sampled
    pass past it, and the progress lattice's nodes times the value domain
    where it searches the lattice (exact past ``MAX_PATTERNS``)."""
    T, M = len(hist.thread_ids), hist.max_ops
    P = pattern_count(T, M)
    if pattern_limit is not None and pattern_limit < P:
        return PATTERN_CHUNK if pattern_limit > MAX_PATTERNS else pattern_limit
    if P <= MAX_PATTERNS:
        return P
    return (M + 2) ** T * value_domain(hist)


def block_rows(hist, pattern_limit: int = None) -> int:
    """The largest power of two of rows whose lanes (:func:`row_lanes`)
    fit ``ROW_LANE_BUDGET``: the row-block width of a property stage that
    runs this serializer (512 at 4 threads x 2 ops with 3-bit op codes,
    1,024 at 3 threads, 65,536 at 2)."""
    fit = ROW_LANE_BUDGET // row_lanes(hist, pattern_limit)
    return 1 << max(fit.bit_length() - 1, 0)


class DeviceRegister:
    """Device form of :class:`~stateright_tpu.semantics.register.Register`
    under the ``history_codecs`` convention (register.py:102-128): stored
    op codes — ``Read = 1``, ``Write(values[i]) = 2 + i``; stored ret
    codes — ``WriteOk = 1``, ``ReadOk(values[i]) = 2 + i``; running value
    is the ``values`` index (0 = unwritten None)."""

    def init_value(self, jnp, shape):
        return jnp.zeros(shape, jnp.uint32)

    def step(self, jnp, v, o, r, is_comp):
        is_read = o == jnp.uint32(1)
        is_write = o >= jnp.uint32(2)
        sem_ok = jnp.where(
            is_comp,
            jnp.where(is_read, r == v + jnp.uint32(2), r == jnp.uint32(1)),
            True,
        )
        v = jnp.where(is_write, o - jnp.uint32(2), v)
        return sem_ok, v


class DeviceWORegister:
    """Device form of
    :class:`~stateright_tpu.semantics.write_once_register.WORegister`
    (write_once_register.rs:9-58: first write wins, rewrites of the same
    value succeed, different-value writes fail) under ``wo_history_codecs``:
    stored op codes — ``Read = 1``, ``Write(values[i]) = 2 + i``; stored
    ret codes — ``WriteOk = 1``, ``WriteFail = 2``,
    ``ReadOk(values[i]) = 3 + i``."""

    def init_value(self, jnp, shape):
        return jnp.zeros(shape, jnp.uint32)

    def step(self, jnp, v, o, r, is_comp):
        u32 = jnp.uint32
        is_read = o == u32(1)
        is_write = o >= u32(2)
        w_val = o - u32(2)
        accepts = (v == u32(0)) | (v == w_val)  # unwritten or same value
        write_ok = jnp.where(accepts, r == u32(1), r == u32(2))
        sem_ok = jnp.where(
            is_comp,
            jnp.where(is_read, r == v + u32(3), write_ok),
            True,
        )
        v = jnp.where(is_write & accepts, w_val, v)
        return sem_ok, v


def value_domain(hist) -> int:
    """Running spec values the lattice search tracks: ``0 ..
    2^(op_bits+1) - 1``. Both device specs map a stored op code ``o`` (a
    field of ``op_bits + 1`` bits) to value ``o - 2`` and start at 0."""
    return 1 << (hist.op_bits + 1)


@lru_cache(maxsize=None)
def progress_lattice(T: int, slots: int):
    """The progress lattice of T threads of ``slots`` slots: node ``p``
    counts the slots each thread has taken, and a serialization is a path
    from ``(0,)*T`` to ``(slots,)*T`` that takes one slot a step. Returns,
    for each layer ``k`` (nodes with ``sum(p) == k``), the edges leaving it
    as ``(t, p)`` (thread ``t`` takes its slot ``p[t]``) with two 0/1
    matrices: ``src[e, i]`` (edge ``e`` leaves node ``i`` of layer k) and
    ``dst[e, j]`` (it enters node ``j`` of layer k+1)."""
    nodes = list(itertools.product(range(slots + 1), repeat=T))
    layers = [[p for p in nodes if sum(p) == k] for k in range(T * slots + 1)]
    out = []
    for k in range(T * slots):
        index = {p: j for j, p in enumerate(layers[k + 1])}
        edges = [(t, p) for p in layers[k] for t in range(T) if p[t] < slots]
        src = np.zeros((len(edges), len(layers[k])), np.float32)
        dst = np.zeros((len(edges), len(layers[k + 1])), np.float32)
        for e, (t, p) in enumerate(edges):
            src[e, layers[k].index(p)] = 1
            dst[e, index[p[:t] + (p[t] + 1,) + p[t + 1:]]] = 1
        out.append((edges, src, dst))
    return out


def _lattice_serializable(hist, spec, real_time, N, FL, OP, RET, PRE, FLPRE):
    """Exact serializability by reachability over the progress lattice
    (:func:`progress_lattice`) and the spec's running value: the same
    verdict as every pattern of :func:`interleaving_tids` at once, since a
    pattern is a lattice path and each step's check depends only on the
    node it leaves, the thread and the running value. Per row: a [nodes,
    values] boolean state a layer, no pattern table. ``N``..``FLPRE`` are
    the per-thread tables of :func:`device_serializable`."""
    import jax
    import jax.numpy as jnp

    T = len(hist.thread_ids)
    slots = hist.max_ops + 1
    D = value_domain(hist)
    u32 = jnp.uint32
    zero = u32(0)
    hi = jax.lax.Precision.HIGHEST

    # What taking slot s of thread t checks, for every (t, s): [T, slots].
    s_ = jnp.arange(slots, dtype=u32)[None, :]
    is_comp = s_ < N[:, None]
    is_fl = (s_ == N[:, None]) & (FL[:, None] != zero)
    active = is_comp | is_fl
    o = jnp.where(is_comp, OP, jnp.where(is_fl, FL[:, None], zero))
    r = jnp.where(is_comp, RET, zero)
    # Real-time prerequisites met at peer q's progress pq:
    # pre_ok[t, s, q, pq], with sched[q, pq] = min(pq, N[q]).
    b = jnp.where(
        is_comp[..., None], PRE, jnp.where(is_fl[..., None], FLPRE[:, None, :], zero)
    )
    sched = jnp.minimum(jnp.arange(slots + 1, dtype=u32)[None, :], N[:, None])
    pre_ok = (b[..., None] == zero) | (b[..., None] - u32(2) < sched[None, None])
    if not real_time:
        pre_ok = jnp.ones_like(pre_ok)

    lattice = progress_lattice(T, slots)
    edges = [e for layer in lattice for e in layer[0]]
    # Each edge's (t, s) entry and its T prerequisite entries, picked by
    # one-hot products (exact: 0/1 and small codes, HIGHEST precision).
    pick_ts = np.zeros((T * slots, len(edges)), np.float32)
    pick_pre = np.zeros((T * slots * T * (slots + 1), len(edges) * T), np.float32)
    for e, (t, p) in enumerate(edges):
        pick_ts[t * slots + p[t], e] = 1
        for q in range(T):
            pick_pre[((t * slots + p[t]) * T + q) * (slots + 1) + p[q], e * T + q] = 1
    per_ts = jnp.stack([o, r, is_comp, active]).reshape(4, -1).astype(jnp.float32)
    e_o, e_r, e_comp, e_active = jnp.dot(per_ts, pick_ts, precision=hi)
    e_o, e_r = e_o.astype(u32), e_r.astype(u32)
    e_comp, e_active = e_comp > 0.5, e_active > 0.5
    e_pre = jnp.dot(pre_ok.reshape(-1).astype(jnp.float32), pick_pre, precision=hi)
    e_rt = jnp.all(e_pre.reshape(len(edges), T) > 0.5, axis=1)

    values = jnp.arange(D, dtype=u32)
    reach = (values == spec.init_value(jnp, ()))[None, :]  # layer 0: one node
    lo = 0
    for edges_k, src, dst in lattice:
        sl = slice(lo, lo + len(edges_k))
        lo += len(edges_k)
        sem_ok, nv = spec.step(
            jnp, values[None, :], e_o[sl, None], e_r[sl, None], e_comp[sl, None]
        )
        act = e_active[sl, None]
        ok = ~act | (e_rt[sl, None] & sem_ok)  # [E_k, D]
        nv = jnp.where(act, nv, values[None, :])
        here = jnp.dot(src, reach.astype(jnp.float32), precision=hi) > 0.5  # [E_k, D]
        moved = jnp.any(
            (here & ok)[..., None] & (nv[..., None] == values[None, None, :]), axis=1
        )  # [E_k, D]
        reach = jnp.dot(dst.T, moved.astype(jnp.float32), precision=hi) > 0.5
    return jnp.any(reach)


def device_serializable(hist, words, spec, *, real_time: bool, pattern_limit=None):
    """True iff the packed history in ``words`` admits a legal serialization
    of ``spec`` — the traced, exact device form of
    ``BacktrackingTester.serialized_history() is not None``
    (real_time=True: linearizability; False: sequential consistency).

    ``hist`` is the model's bound :class:`BoundedHistory`; jnp-traceable per
    state row (vmap over the frontier).

    ``pattern_limit``: evaluate only the first N patterns. The result is
    then one-sided — True still proves serializability, False means
    *unknown* — which is exactly the conservative-predicate contract of the
    engine's ``host_verified_properties`` path: use a limited device pass to
    clear the bulk of the frontier and let the host serializer confirm the
    flagged remainder.
    """
    import jax
    import jax.numpy as jnp

    T = len(hist.thread_ids)
    M = hist.max_ops
    slots = M + 1
    P_full = pattern_count(T, M)
    limit = (
        None
        if pattern_limit is None or pattern_limit >= P_full
        else pattern_limit
    )
    if (P_full if limit is None else limit) > MAX_PATTERNS_EXACT:
        raise NotImplementedError(
            f"{P_full if limit is None else limit} interleavings "
            f"({T} threads x {M}+1 ops"
            f"{'' if limit is None else f', pattern_limit={limit}'}) exceeds "
            f"MAX_PATTERNS_EXACT={MAX_PATTERNS_EXACT}; declare the property "
            "in host_verified_properties instead (conservative device "
            "predicate — this function with a pattern_limit <= "
            f"{MAX_PATTERNS_EXACT} — plus exact host confirmation)."
        )
    L_ = hist.layout
    u32 = jnp.uint32
    Lsteps = T * slots

    N = jnp.stack([L_.get(words, f"h{t}_n") for t in range(T)])  # [T]
    FL = jnp.stack([L_.get(words, f"h{t}_fl") for t in range(T)])  # [T]
    # Completed-op tables, padded to `slots` so the slot index is always in
    # bounds (the pad row is only gathered when inactive).
    zero = jnp.uint32(0)
    OP = jnp.stack(
        [
            jnp.stack([L_.get(words, f"h{t}_op", j) for j in range(M)] + [zero])
            for t in range(T)
        ]
    )  # [T, slots]
    RET = jnp.stack(
        [
            jnp.stack([L_.get(words, f"h{t}_ret", j) for j in range(M)] + [zero])
            for t in range(T)
        ]
    )  # [T, slots]
    npeer = max(T - 1, 1)
    # Prereqs on absolute thread columns (self column stays 0 = no entry).
    PRE = jnp.zeros((T, slots, T), u32)
    FLPRE = jnp.zeros((T, T), u32)
    for t in range(T):
        for pi, q in enumerate(hist.peers[t]):
            FLPRE = FLPRE.at[t, q].set(L_.get(words, f"h{t}_flpre", pi))
            for j in range(M):
                PRE = PRE.at[t, j, q].set(L_.get(words, f"h{t}_pre", j * npeer + pi))

    if limit is None and P_full > MAX_PATTERNS:
        any_ok = _lattice_serializable(
            hist, spec, real_time, N, FL, OP, RET, PRE, FLPRE
        )
        return (L_.get(words, "h_valid") != 0) & any_ok

    tid_np = interleaving_tids(T, slots, limit)  # [P, L] int8
    P = tid_np.shape[0]
    thread_lanes = jnp.arange(T, dtype=jnp.int32)

    def eval_block(tid_blk):
        """Serializability of this state's history over one [p, L] block of
        patterns; carries per-thread running counts (see module docstring)."""
        p = tid_blk.shape[0]
        running = jnp.zeros((p, T), u32)
        v = spec.init_value(jnp, (p,))
        ok = jnp.ones((p,), bool)
        for l in range(Lsteps):
            tl = tid_blk[:, l].astype(jnp.int32)  # [p]
            onehot = tl[:, None] == thread_lanes[None, :]  # [p, T]
            # This step's per-thread slot index: how many of tl's slots ran.
            sl = jnp.sum(jnp.where(onehot, running, zero), axis=1)  # [p] u32
            sl_i = sl.astype(jnp.int32)  # < slots by construction
            n_t = N[tl]
            is_comp = sl < n_t
            is_fl = (sl == n_t) & (FL[tl] != 0)
            active = is_comp | is_fl
            o = jnp.where(is_comp, OP[tl, sl_i], jnp.where(is_fl, FL[tl], zero))
            r = jnp.where(is_comp, RET[tl, sl_i], zero)
            if real_time:
                rt = jnp.ones((p,), bool)
                for q in range(T):
                    b = jnp.where(
                        is_comp, PRE[tl, sl_i, q], jnp.where(is_fl, FLPRE[tl, q], zero)
                    )
                    # Peer q's completed ops scheduled so far: its running
                    # count, capped at its completed count (dynamic).
                    sched = jnp.minimum(running[:, q], N[q])
                    # b stores prereq index + 2; 0 = no entry. b >= 2
                    # whenever nonzero, so b - 2 cannot wrap on the checked
                    # branch.
                    rt = rt & ((b == zero) | (b - u32(2) < sched))
            else:
                rt = True
            sem_ok, nv = spec.step(jnp, v, o, r, is_comp)
            # Inactive (padding) steps constrain nothing and change nothing.
            ok = ok & (~active | (rt & sem_ok))
            v = jnp.where(active, nv, v)
            running = running + onehot.astype(u32)
        return ok

    if P <= MAX_PATTERNS:
        any_ok = jnp.any(eval_block(jnp.asarray(tid_np)))
    else:
        # A sampled pass past the single-shot budget: chunk the pattern
        # axis under lax.scan at bounded memory. The pad block repeats
        # pattern 0 — duplicates cannot change an any() reduction.
        C = -(-P // PATTERN_CHUNK)
        pad = C * PATTERN_CHUNK - P
        if pad:
            tid_np = np.concatenate([tid_np, np.tile(tid_np[:1], (pad, 1))])
        xs = jnp.asarray(tid_np.reshape(C, PATTERN_CHUNK, Lsteps))

        def body(acc, tid_blk):
            return acc | jnp.any(eval_block(tid_blk)), None

        any_ok, _ = jax.lax.scan(body, jnp.bool_(False), xs)
    return (L_.get(words, "h_valid") != 0) & any_ok
