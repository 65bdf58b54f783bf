"""Plain reference for two-phase commit (upstream ``examples/2pc.rs``).

A level-synchronous breadth-first search in NumPy over states packed into
one ``uint64`` each, written from the upstream model alone: it imports
nothing of the system under test. Its answers decide whether a check was
correct.

State bits for ``n`` resource managers (RMs):

- ``[2i, 2i+2)``: RM ``i``'s state (working, prepared, committed, aborted);
- ``[2n, 2n+2)``: the transaction manager's state (init, committed, aborted);
- ``2n+2+i``: the manager has received RM ``i``'s ``Prepared``;
- ``3n+2+i``: a ``Prepared`` message from RM ``i`` is in the message set;
- ``4n+2``: ``Commit`` is in the message set; ``4n+3``: ``Abort`` is.

The state fits in 64 bits, so the visited set holds the states themselves:
no fingerprint, no collision. The control (``control_seed``) keys the
visited set on a 32-bit hash of the state instead, salted by the seed: the
narrower fingerprint that would tempt a faster checker.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

WORKING, PREPARED, COMMITTED, ABORTED = 0, 1, 2, 3
TM_INIT, TM_COMMITTED, TM_ABORTED = 0, 1, 2

#: (name, expectation) in the upstream model's order.
PROPERTIES = (
    ("abort agreement", "sometimes"),
    ("commit agreement", "sometimes"),
    ("consistent", "always"),
)

_U = np.uint64


class _Bits:
    def __init__(self, n: int):
        if not 1 <= n <= 15:
            raise ValueError(f"rm_count {n} does not fit 64 bits")
        self.n = n
        self.tm = 2 * n
        self.prep = 2 * n + 2
        self.msg = 3 * n + 2
        self.commit = 4 * n + 2
        self.abort = 4 * n + 3


def _bit(i: int):
    return _U(1) << _U(i)


def successors(states: np.ndarray, n: int) -> np.ndarray:
    """Every enabled transition of every state, as a flat array (one entry
    per generated state, repeats kept), in the upstream's action order."""
    b = _Bits(n)
    s = np.asarray(states, dtype=np.uint64)
    rm = [(s >> _U(2 * i)) & _U(3) for i in range(n)]
    tm_init = ((s >> _U(b.tm)) & _U(3)) == _U(TM_INIT)
    all_prepared = np.ones(s.shape, bool)
    for i in range(n):
        all_prepared &= ((s >> _U(b.prep + i)) & _U(1)) == _U(1)
    has_commit = ((s >> _U(b.commit)) & _U(1)) == _U(1)
    has_abort = ((s >> _U(b.abort)) & _U(1)) == _U(1)
    clear_tm = ~(_U(3) << _U(b.tm))
    out = [
        # TmCommit / TmAbort.
        (((s & clear_tm) | (_U(TM_COMMITTED) << _U(b.tm)) | _bit(b.commit)),
         tm_init & all_prepared),
        (((s & clear_tm) | (_U(TM_ABORTED) << _U(b.tm)) | _bit(b.abort)), tm_init),
    ]
    for i in range(n):
        clear_rm = s & ~(_U(3) << _U(2 * i))
        working = rm[i] == _U(WORKING)
        msg_prepared = ((s >> _U(b.msg + i)) & _U(1)) == _U(1)
        out += [
            # TmRcvPrepared(i), RmPrepare(i), RmChooseToAbort(i),
            # RmRcvCommitMsg(i), RmRcvAbortMsg(i).
            (s | _bit(b.prep + i), tm_init & msg_prepared),
            (clear_rm | (_U(PREPARED) << _U(2 * i)) | _bit(b.msg + i), working),
            (clear_rm | (_U(ABORTED) << _U(2 * i)), working),
            (clear_rm | (_U(COMMITTED) << _U(2 * i)), has_commit),
            (clear_rm | (_U(ABORTED) << _U(2 * i)), has_abort),
        ]
    return np.concatenate([nxt[ok] for nxt, ok in out])


def holds(states: np.ndarray, n: int) -> Dict[str, np.ndarray]:
    """Each property's condition on each state."""
    s = np.asarray(states, dtype=np.uint64)
    rm = np.stack([(s >> _U(2 * i)) & _U(3) for i in range(n)])
    aborted, committed = rm == _U(ABORTED), rm == _U(COMMITTED)
    return {
        "abort agreement": aborted.all(axis=0),
        "commit agreement": committed.all(axis=0),
        "consistent": ~(aborted.any(axis=0) & committed.any(axis=0)),
    }


def _salted_hash32(states: np.ndarray, seed: int) -> np.ndarray:
    """Multiply-shift hash to 32 bits with an odd multiplier from ``seed``."""
    mult = _U((np.random.default_rng(seed).integers(1, 2**63) << 1) | 1)
    return (states * mult) >> _U(32)


def explore(params: dict, control_seed: Optional[int] = None) -> dict:
    """Full breadth-first exploration: generated and unique counts, and for
    each property the depth (states on the path) of its shortest witness.
    ``control_seed`` switches on the control's 32-bit visited-set key."""
    n = int(params["rm_count"])
    frontier = np.zeros(1, np.uint64)  # every RM working, nothing sent

    def key(states):
        return states if control_seed is None else _salted_hash32(states, control_seed)

    visited = np.unique(key(frontier))
    generated, unique, depth = 1, 1, 1
    found: Dict[str, int] = {}
    with np.errstate(over="ignore"):
        while frontier.size:
            for (name, expect), ok in zip(PROPERTIES, holds(frontier, n).values()):
                hit = ok.any() if expect == "sometimes" else (~ok).any()
                if hit and name not in found:
                    found[name] = depth
            nxt = successors(frontier, n)
            generated += int(nxt.size)
            keys, first = np.unique(key(nxt), return_index=True)
            new = ~np.isin(keys, visited, assume_unique=True)
            frontier = nxt[first[new]]
            visited = np.union1d(visited, keys[new])
            unique += int(new.sum())
            depth += 1
    return {"generated": generated, "unique": unique, "discoveries": found}


def from_program(state) -> int:
    """The reference encoding of the system's object-level 2pc state
    (fields ``rm_state``, ``tm_state``, ``tm_prepared``, ``msgs``)."""
    n = len(state.rm_state)
    b = _Bits(n)
    x = state.tm_state << b.tm
    for i, r in enumerate(state.rm_state):
        x |= int(r) << (2 * i)
    for i, p in enumerate(state.tm_prepared):
        x |= int(bool(p)) << (b.prep + i)
    for m in state.msgs:
        if isinstance(m, tuple):
            x |= 1 << (b.msg + int(m[1]))
        elif m == "Commit":
            x |= 1 << b.commit
        elif m == "Abort":
            x |= 1 << b.abort
        else:
            raise ValueError(f"unknown message {m!r}")
    return x


def replay(states: List, name: str, params: dict, depth: int) -> Optional[str]:
    """Checks a discovery path given as the system's states: it starts at
    the initial state, every step is a transition of the reference, the
    property's discovery condition holds at its end, and it is as short as
    the reference's witness (``depth`` states). Returns what is wrong, or
    None."""
    n = int(params["rm_count"])
    xs = [from_program(s) for s in states]
    if xs[0] != 0:
        return "path does not start at the initial state"
    for i in range(len(xs) - 1):
        if xs[i + 1] not in set(successors(np.array([xs[i]], np.uint64), n).tolist()):
            return f"step {i} is not a transition"
    expect = dict(PROPERTIES)[name]
    ok = bool(holds(np.array([xs[-1]], np.uint64), n)[name][0])
    if ok != (expect == "sometimes"):
        return "the last state does not witness the property"
    if len(xs) != depth:
        return f"path has {len(xs)} states, the shortest witness {depth}"
    return None
