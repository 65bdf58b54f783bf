"""Demand-driven checking ON the device engine: the Explorer's backend for
packed models.

The reference Explorer wraps its real engine (``OnDemandChecker``,
``/root/reference/src/checker/explorer.rs:81-103``); round 2's ``serve()``
wrapped only the host oracle, so browsing a packed model silently ran the
Python engine — fine at 544 states, useless at 1.7M. This checker keeps the
interactive contract (compute nothing until asked) while every expansion,
property evaluation, dedup, and witness reconstruction runs through the
device engine's compiled machinery:

- a **targeted expansion** (the user clicked a state) loads exactly that
  packed row as a one-row frontier and dispatches one compiled super-step:
  children dedup against the device hash set, properties evaluate on
  device, discoveries pin exactly as in batch runs;
- pending (discovered-but-unexpanded) rows live in a host-side pool keyed
  by device fingerprint — the on-demand analogue of the frontier;
- ``run_to_completion()`` reloads the entire pool as the frontier and
  hands over to the inherited **fused multi-level dispatch** — from that
  point this IS the batch engine (counts stay exact; with a mixed-depth
  pool the per-level depth accounting becomes approximate, exactly like
  the reference's run-to-completion from a driven state).

The Explorer passes the clicked object state (it has it in hand) via
``check_state``; host fingerprints never need translating to device ones.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..ops import fphash
from ..xla import XlaChecker


class DeviceOnDemandChecker(XlaChecker):
    """Spawned via ``CheckerBuilder.spawn_on_demand(engine="xla")`` or the
    Explorer's ``serve()`` on a packed model."""

    def __init__(self, builder, **kwargs):
        super().__init__(builder, **kwargs)
        self._waiting = True
        #: device fp64 -> (packed row, ebits, depth) of pending entries.
        self._pool: Dict[int, Tuple[np.ndarray, int, int]] = {}
        # self._depth is 1 for a fresh init frontier and the restored depth
        # after a checkpoint resume — the pool must inherit it either way.
        self._pool_add(
            self._frontier_rows_host(),
            np.asarray(self._frontier_ebits)[: self._frontier_count],
            self._depth,
        )

    def _pool_add(self, rows: np.ndarray, ebits: np.ndarray, depth: int) -> None:
        """File rows as pending entries, batch-fingerprinted (one vectorized
        dedup + hash over the whole batch, like the batch engine's init)."""
        if not len(rows):
            return
        dedup = self._dedup_words_host(np.asarray(rows, dtype=np.uint32))
        hi, lo = fphash.fingerprint_words(dedup, np)
        for i in range(len(rows)):
            key = (int(hi[i]) << 32) | int(lo[i])
            self._pool[key] = (rows[i].copy(), int(ebits[i]), depth)

    # --- control flow (the on-demand contract) -----------------------------

    def check_state(self, state: Any, fp: Optional[int] = None) -> None:
        """Evaluate and expand the pending entry for this object state, if
        any (the device form of ``OnDemandChecker.check_fingerprint``;
        unknown or already-expanded states are ignored). The state itself is
        passed — not just a fingerprint — because pending rows are keyed by
        DEVICE fingerprint, which only the packed codec can compute."""
        self.check_states([state])

    def check_states(self, states) -> None:
        """Batched :meth:`check_state`: all pending entries among ``states``
        expand in one device dispatch per depth group — one host
        round-trip where per-child expansion would pay one per state (the
        Explorer expands every child of a clicked state)."""
        if not self._waiting:
            return
        if self._target_reached or (
            self._P > 0
            and all(n in self._found_names for n in self._prop_names)
        ):
            # _run_block_single would refuse to expand (its entry checks),
            # leaving the input rows in the frontier; don't pop them.
            return
        by_depth: Dict[int, list] = {}
        for state in states:
            entry = self._pool.pop(self._packed_fp64(state), None)
            if entry is not None:
                by_depth.setdefault(entry[2], []).append(entry)
        for depth, entries in sorted(by_depth.items()):
            if self._target_reached or (
                self._P > 0
                and all(n in self._found_names for n in self._prop_names)
            ):
                # An earlier group crossed a target / pinned the last
                # property: _run_block_single would refuse to expand, so
                # put the remaining entries back untouched.
                for row, eb, d in entries:
                    key = fphash.fingerprint_u64(
                        self._dedup_words_host(row[None, :])[0], np
                    )
                    self._pool[key] = (row, eb, d)
                continue
            self._expand_rows(
                np.stack([r for r, _, _ in entries]),
                np.asarray([e for _, e, _ in entries], np.uint32),
                depth,
            )

    def check_fingerprint(self, fingerprint: int) -> None:
        """Host fingerprints cannot address device-keyed pending rows; the
        Explorer uses :meth:`check_state` (it always has the state in hand).
        Kept as an explicit no-op for API compatibility."""

    def run_to_completion(self) -> None:
        """Unblock: the whole pending pool becomes the frontier and the
        inherited fused batch engine takes over (on_demand.rs:193-198)."""
        import jax.numpy as jnp

        if not self._waiting:
            return
        self._waiting = False
        if not self._pool:
            self._frontier_count = 0
            self._exhausted = True
            return
        rows = np.stack([r for r, _, _ in self._pool.values()])
        ebits = np.asarray([e for _, e, _ in self._pool.values()], np.uint32)
        depth = min(d for _, _, d in self._pool.values())
        self._pool.clear()
        need = 1 << max(int(len(rows) - 1).bit_length(), 4)
        if need > self._frontier_capacity:
            self._frontier_capacity = need
        self._store_frontier_rows(rows)
        self._frontier_ebits = jnp.asarray(ebits)
        self._frontier_count = len(rows)
        self._depth = depth
        self._exhausted = False

    # --- engine ------------------------------------------------------------

    def _expand_rows(self, rows: np.ndarray, ebits: np.ndarray, depth: int) -> None:
        """One compiled super-step over exactly these rows; fresh children
        join the pending pool at depth + 1."""
        import jax.numpy as jnp

        self._depth = depth
        self._exhausted = False
        self._store_frontier_rows(rows)
        self._frontier_ebits = jnp.asarray(ebits)
        self._frontier_count = len(rows)
        self._run_block_single()
        # Children are table-fresh by construction, so they cannot collide
        # with an existing pending entry.
        self._pool_add(
            self._frontier_rows_host(),
            np.asarray(self._frontier_ebits)[: self._frontier_count],
            depth + 1,
        )

    def _run_block(self, max_count: int = 1500) -> None:
        if self._waiting:
            return  # computes nothing until asked (on_demand.rs:165-203)
        super()._run_block(max_count)

    def discoveries(self):
        """Explorer polls this on every request; witness paths are stable
        once found (parent chains never change under later insertions), so
        cache by the discovery set instead of re-pulling the device table
        per poll."""
        key = tuple(sorted(self._found_names.items()))
        cached = self.__dict__.get("_disc_cache")
        if cached is not None and cached[0] == key:
            return cached[1]
        out = super().discoveries()
        self.__dict__["_disc_cache"] = (key, out)
        return out

    # --- Checker API adjustments (mirror checker/on_demand.py) -------------

    def metrics(self):
        """The engine registry plus the on-demand surface's own gauges:
        the pending pool (discovered-but-unexpanded states) and whether
        the checker is still waiting (compute-nothing-until-asked).

        As the Explorer's backend this checker is one CLIENT of the
        multi-tenant ``stateright_tpu/service`` pool: ``make_app``
        registers it via ``CheckerService.register_interactive`` (typed
        admission past ``max_sessions``), ``attach_job`` (base Checker)
        threads the pool job id in here as ``job_id``, and the pool's
        breaker decides whether a session gets this engine at all — open
        means the Explorer serves degraded on the host on-demand engine
        instead."""
        out = super().metrics()
        out["pending_pool"] = len(self._pool)
        out["waiting"] = self._waiting
        return out

    def is_done(self) -> bool:
        if self._waiting:
            return (
                not self._pool
                or self._target_reached
                or (
                    self._P > 0
                    and all(n in self._found_names for n in self._prop_names)
                )
            )
        return super().is_done()

    def join(self) -> "DeviceOnDemandChecker":
        if self._waiting and not self.is_done():
            raise RuntimeError(
                "join() on an on-demand checker that was never unblocked; "
                "call run_to_completion() first"
            )
        return super().join()
