"""Sliding puzzle: the reference's first-model doc example.

Mirrors the doc-test model in ``/root/reference/src/lib.rs:40-115``: a 3x3
(generally n x n) sliding puzzle whose single ``sometimes`` property asserts
the board configuration has a solution; ``assert_discovery`` then pins an
actual solution path. This is the "first model" of the tutorial
(``docs/tutorial.md``), in both object and packed (device-checkable) forms.

State: a tuple of ``n*n`` cell values, ``0`` marking the hole. An action
slides the named neighbour *into* the hole (``Slide::Down`` moves the tile
above the hole down, lib.rs:63-69).
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

from ..core import Model, Property

# Action = which tile slides into the hole: "Down" slides the tile above
# the hole down, etc. (lib.rs:63-69). Deltas/guards depend on the board
# side, so each form derives them where it needs them.
_MOVES = ("Down", "Up", "Right", "Left")


class Puzzle(Model):
    """Object form (lib.rs:46-88). ``board`` is row-major, 0 = hole."""

    def __init__(self, board: Sequence[int], side: int = 3):
        assert len(board) == side * side, (len(board), side)
        self.board = tuple(board)
        self.side = side

    def init_states(self) -> List[Tuple[int, ...]]:
        return [self.board]

    def actions(self, state, actions: List[Any]) -> None:
        actions.extend(_MOVES)

    def _slide_from(self, state, action):
        """Index of the tile that slides into the hole, or None (the
        reference's ``maybe_from``, lib.rs:62-70)."""
        n = self.side
        empty = state.index(0)
        ey, ex = divmod(empty, n)
        if action == "Down" and ey > 0:
            return empty - n
        if action == "Up" and ey < n - 1:
            return empty + n
        if action == "Right" and ex > 0:
            return empty - 1
        if action == "Left" and ex < n - 1:
            return empty + 1
        return None

    def next_state(self, last_state, action):
        frm = self._slide_from(last_state, action)
        if frm is None:
            return None
        s = list(last_state)
        s[last_state.index(0)] = s[frm]
        s[frm] = 0
        return tuple(s)

    def properties(self) -> List[Property]:
        solved = tuple(range(self.side * self.side))
        return [Property.sometimes("solved", lambda _m, s: s == solved)]

    def format_state(self, state) -> str:
        n = self.side
        return "\n".join(
            " ".join(f"{v}" for v in state[r * n : (r + 1) * n]) for r in range(n)
        )


class PackedPuzzle(Puzzle):
    """Device form: ``n*n`` cells of ``bits_for(n*n-1)`` bits (a 3x3 board
    packs into 2 uint32 words), four action slots, the hole located with a
    single ``argmin`` over the cell vector."""

    def __init__(self, board: Sequence[int], side: int = 3):
        from ..packing import LayoutBuilder, bits_for

        super().__init__(board, side)
        nn = side * side
        self._layout = LayoutBuilder().array("cell", nn, bits_for(nn - 1)).finish()
        self.state_words = self._layout.words
        self.max_actions = 4

    def pack(self, state):
        return self._layout.pack(cell=list(state))

    def unpack(self, words):
        return tuple(int(x) for x in self._layout.unpack(words)["cell"])

    def packed_init(self):
        import numpy as np

        return np.stack([self.pack(s) for s in self.init_states()])

    def packed_step(self, words):
        import jax.numpy as jnp

        L = self._layout
        n = self.side
        cells = jnp.stack([L.get(words, "cell", k) for k in range(n * n)])
        empty = jnp.argmin(cells).astype(jnp.uint32)  # the hole holds 0
        ey, ex = empty // n, empty % n
        nxt, valid = [], []
        for delta, ok in zip(
            (-n, n, -1, 1),  # _MOVES order: Down, Up, Right, Left
            (ey > 0, ey < n - 1, ex > 0, ex < n - 1),
        ):
            frm = jnp.where(ok, empty + jnp.int32(delta).astype(jnp.uint32), 0)
            w = L.set(L.set(words, "cell", cells[frm], empty), "cell", 0, frm)
            nxt.append(w)
            valid.append(ok)
        return jnp.stack(nxt), jnp.stack(valid)

    def packed_properties(self, words):
        import jax.numpy as jnp

        L = self._layout
        solved = jnp.bool_(True)
        for k in range(self.side * self.side):
            solved = solved & (L.get(words, "cell", k) == k)
        return jnp.stack([solved])


def main(argv=None) -> None:
    """CLI in the style of the reference examples. The doc board
    (lib.rs:93-96) is the default."""
    import sys

    from ..report import WriteReporter

    args = list(sys.argv[1:] if argv is None else argv)
    cmd = args.pop(0) if args else None

    def pop_board():
        """(board, side): the doc board (lib.rs:93-96) unless the next arg
        parses as a square board of comma-separated ints — `explore ADDRESS`
        must not eat the address."""
        from math import isqrt

        if args and all(p.strip().isdigit() for p in args[0].split(",")):
            board = [int(x) for x in args.pop(0).split(",")]
            side = isqrt(len(board))
            if side * side != len(board):
                raise SystemExit(f"board has {len(board)} cells; need a square count")
            return board, side
        return [1, 4, 2, 3, 5, 8, 6, 7, 0], 3

    if cmd == "check":
        from ..backend import configure_compile_cache

        configure_compile_cache()
        board, side = pop_board()
        print("Model checking the sliding puzzle on XLA.")
        PackedPuzzle(board, side).checker().spawn_xla(
            frontier_capacity=1 << 14, table_capacity=1 << 19
        ).report(WriteReporter())
    elif cmd == "check-host":
        board, side = pop_board()
        print("Model checking the sliding puzzle.")
        Puzzle(board, side).checker().spawn_bfs().report(WriteReporter())
    elif cmd == "explore":
        board, side = pop_board()
        address = args.pop(0) if args else "localhost:3000"
        print(f"Exploring the sliding puzzle state space on {address}.")
        Puzzle(board, side).checker().serve(address)
    else:
        print("USAGE:")
        print("  puzzle check [BOARD]        (device/XLA engine)")
        print("  puzzle check-host [BOARD]   (sequential host oracle)")
        print("  puzzle explore [BOARD] [ADDRESS]")
        print("BOARD is comma-separated, e.g. 1,4,2,3,5,8,6,7,0")


if __name__ == "__main__":
    main()
