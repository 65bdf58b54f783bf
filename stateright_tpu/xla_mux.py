"""Multiplexed superstep: K independent jobs in ONE device program.

The roofline model (tools/roofline.py) says the engine is per-level fixed-cost-bound
(~260 ms/level on chip), so under interactive fleet traffic — many *small*
jobs at rm<=4 — every tenant pays the full sort + dispatch fixed cost
alone. :class:`MuxChecker` stacks K same-shape-class jobs under one
leading lane axis and drives them through a single fused device program:

- Each lane is a full :class:`~stateright_tpu.xla.XlaChecker` over the
  SAME model instance (shared compile caches, shared capacity hints) —
  the lane checkers remain the source of truth for per-lane state,
  bookkeeping, checkpoints, and metrics; the mux layer only batches the
  device calls.
- The device program is ``jax.vmap`` of the engine's single-level
  superstep wrapped in a mux-owned ``lax.while_loop``: per-lane
  ``f_count``/termination masks (a finished lane rides with a zero-width
  frontier and a per-lane commit mask, so its frontier, table, and counts
  stay bit-identical), per-lane dedup against per-lane tables (the
  vmapped table-scale sort lowers to ONE batched sort serving all K
  lanes), and per-lane exact counts/discoveries split back out at
  quiescent boundaries.
- Any active lane's overflow (table/frontier/candidate) leaves that
  iteration uncommitted for every lane — the host grows ALL lanes
  uniformly (keeping the stack rectangular; capacities affect cost, never
  counts) and re-enters, exactly the solo engine's retry discipline.

Exactness: counts are bucket-independent (pinned by the engine tests), a
superstep fed ``f_count=0`` is a fixed point, and uncommitted iterations
recompute deterministically — so every lane's generated/unique/discovery
results are bit-identical to its solo run (pinned by tests/test_mux.py).

Exclusions (typed :class:`MuxError`): host-verified properties (their
per-superstep host confirmation would serialize the lanes), the delta
dedup structure (its flush is a host-invoked maintain program), and
visitors. The service's batching scheduler (service/core.py) only groups
specs from the statically mux-eligible families
(service/registry.py:MUX_FAMILIES).

Telemetry: each lane's ``level_log`` rows gain ``lanes``/``lanes_active``,
the mux ``dispatch_log`` records ``(run_cap, committed, lanes,
lanes_active)`` per device call (each lane's own log keeps the pinned
2-tuple schema), and :meth:`MuxChecker.metrics` reports ``mux_lanes`` /
``mux_dispatches_saved`` (the dispatches the batch avoided vs solo runs,
summed as ``lanes_active - 1`` per device call).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from .xla import XlaChecker

INT32_MAX = 2**31 - 1


class MuxError(ValueError):
    """A lane set the multiplexed engine cannot batch (typed so the
    service's batching scheduler and callers can fall back to solo
    dispatch deliberately)."""


def _check_lanes(lanes: List[XlaChecker]) -> None:
    if not lanes:
        raise MuxError("mux needs at least one lane")
    for ln in lanes:
        if type(ln) is not XlaChecker:
            raise MuxError(
                f"mux lanes must be XlaChecker instances, got {type(ln).__name__}"
            )
    if len(set(map(id, lanes))) != len(lanes):
        raise MuxError("mux lanes must be distinct checker instances")
    first = lanes[0]
    for ln in lanes[1:]:
        if ln._model is not first._model:
            raise MuxError(
                "mux lanes must share ONE model instance (same shape class "
                "AND shared compile caches); resolve the spec once and "
                "build every lane from it"
            )
    if first._hv_idx:
        raise MuxError(
            "host-verified properties cannot be multiplexed (their "
            "per-superstep host confirmation would serialize the lanes)"
        )
    if first._dedup == "delta":
        raise MuxError(
            "the delta dedup structure cannot be multiplexed (its flush "
            "is a host-invoked maintain program)"
        )
    for ln in lanes:
        if ln._visitor is not None:
            raise MuxError("visitors cannot be multiplexed")
    for attr in ("_dedup", "_compaction", "_sym_tag", "_max_probes", "_soa"):
        vals = {getattr(ln, attr) for ln in lanes}
        if len(vals) != 1:
            raise MuxError(
                f"mux lanes disagree on {attr.lstrip('_')}: {sorted(map(str, vals))}"
            )
    caps = {(ln._frontier_capacity, ln._table.capacity) for ln in lanes}
    if len(caps) != 1:
        raise MuxError(
            "mux lanes must start at identical frontier/table capacities "
            f"(got {sorted(caps)}); pass the same spawn capacities to every lane"
        )


class MuxChecker:
    """Drive K lane checkers through one batched fused device program.

    The constructor takes fully-spawned lanes (``spawn_xla`` each lane
    with identical capacities over one shared model instance — per-lane
    ``checkpoint_to=``/``metrics_to=``/resume all work unchanged, since
    the lanes hold real state). ``MuxChecker`` then replaces the lanes'
    own dispatch loops: call :meth:`_run_block` until :meth:`is_done`.
    """

    def __init__(self, lanes: List[XlaChecker]):
        _check_lanes(lanes)
        self.lanes = list(lanes)
        self.k = len(self.lanes)
        lead = self.lanes[0]
        self._model = lead._model
        self._jax = lead._jax
        self._levels_per_dispatch = lead._levels_per_dispatch
        # Shared observability: the mux layer owns the dispatch spans and
        # heartbeat (one device call serves every lane); the lanes keep
        # their per-lane checkpoint/metrics hooks.
        self._tracer = lead._tracer
        self._heartbeat = lead._heartbeat
        #: One ``(run_cap, committed, lanes, lanes_active)`` per device
        #: call (the lane-axis extension of the engine's pinned 2-tuple).
        self.dispatch_log: List[Tuple[int, int, int, int]] = []
        self._dispatches_saved = 0

    # --- program cache ----------------------------------------------------

    def _mux_key(self, f_cap: int, cand_cap: int):
        lead = self.lanes[0]
        return (
            "mux", self.k, f_cap, cand_cap, self._levels_per_dispatch,
            lead._sym_tag, lead._max_probes, lead._dedup, lead._compaction,
        )

    def _mux_fused_for(self, run_cap: int, cand_cap: int):
        import jax

        cache = self._model.__dict__.setdefault("_xla_mux_cache", {})
        key = self._mux_key(run_cap, cand_cap)
        fn = cache.get(key)
        if fn is None:
            fn = jax.jit(self._build_mux_fused(run_cap, cand_cap))
            cache[key] = fn
        return fn, key

    def _build_mux_fused(self, f_cap: int, cand_cap: int):
        """The batched fused program: ``vmap`` of the single-level
        superstep inside a mux-owned ``lax.while_loop``. Per-lane commit
        masks replace the solo fused loop's scalar commit; any active
        lane's overflow leaves the whole iteration uncommitted (the host
        grows uniformly and re-enters)."""
        import jax
        import jax.numpy as jnp

        K = self.k
        L = self._levels_per_dispatch
        P = self.lanes[0]._P
        vstep = jax.vmap(self.lanes[0]._build_superstep(f_cap, cand_cap))

        def mux_fused(frontier, ebits, fcount, table, dfound, dfp,
                      budget, remaining, lane_budget):
            def active_of(fc, tot, taken, df):
                a = (fc > 0) & (tot < remaining) & (taken < lane_budget)
                if P > 0:
                    a = a & ~jnp.all(df, axis=1)
                return a

            def body(carry):
                (fr, eb, fc, tb, df, dp, tot_s, tot_u, taken, committed,
                 _go, _ovf, lv_act, lv_fr, lv_st, lv_un) = carry
                active = active_of(fc, tot_s, taken, df)
                eff = jnp.where(active, fc, jnp.int32(0))
                (nf, ne, ncount, ntb, ndf, ndp, d_s, d_u,
                 t_o, f_o, c_o, cc_o, _hw, _hf, _hc) = vstep(
                    fr, eb, eff, tb, df, dp)
                t_ovf = jnp.any(t_o & active)
                f_ovf = jnp.any(f_o & active)
                c_ovf = jnp.any(c_o & active)
                cc_ovf = jnp.any(cc_o & active)
                ok = ~(t_ovf | f_ovf | c_ovf | cc_ovf)
                cm = active & ok

                def sel(new, old):
                    m = cm.reshape((K,) + (1,) * (new.ndim - 1))
                    return jnp.where(m, new, old)

                fr = sel(nf, fr)
                eb = sel(ne, eb)
                tb = jax.tree_util.tree_map(sel, ntb, tb)
                df = sel(ndf, df)
                dp = sel(ndp, dp)
                slot = jnp.where(ok, committed, jnp.int32(L))
                cmi = cm.astype(jnp.int32)
                lv_act = lv_act.at[slot].set(cm, mode="drop")
                lv_fr = lv_fr.at[slot].set(ncount * cmi, mode="drop")
                lv_st = lv_st.at[slot].set(d_s * cmi, mode="drop")
                lv_un = lv_un.at[slot].set(d_u * cmi, mode="drop")
                fc = jnp.where(cm, ncount, fc)
                tot_s = tot_s + d_s * cmi
                tot_u = tot_u + d_u * cmi
                taken = taken + cmi
                committed = committed + ok.astype(jnp.int32)
                ovf = jnp.stack([t_ovf, f_ovf, c_ovf, cc_ovf])
                go = ok & (committed < budget) & jnp.any(
                    active_of(fc, tot_s, taken, df)
                )
                return (fr, eb, fc, tb, df, dp, tot_s, tot_u, taken,
                        committed, go, ovf, lv_act, lv_fr, lv_st, lv_un)

            z_k = jnp.zeros((K,), jnp.int32)
            carry0 = (
                frontier, ebits, fcount, table, dfound, dfp,
                z_k, z_k, z_k, jnp.int32(0),
                jnp.any(active_of(fcount, z_k, z_k, dfound)) & (budget > 0),
                jnp.zeros((4,), jnp.bool_),
                jnp.zeros((L, K), jnp.bool_),
                jnp.zeros((L, K), jnp.int32),
                jnp.zeros((L, K), jnp.int32),
                jnp.zeros((L, K), jnp.int32),
            )
            out = jax.lax.while_loop(lambda c: c[10], body, carry0)
            (fr, eb, fc, tb, df, dp, tot_s, tot_u, _taken, committed,
             _go, ovf, lv_act, lv_fr, lv_st, lv_un) = out
            return (committed, fr, eb, fc, tb, df, dp, tot_s, tot_u, ovf,
                    lv_act, lv_fr, lv_st, lv_un)

        return mux_fused

    # --- host loop --------------------------------------------------------

    def _stack(self, run_cap: int):
        """Stack the K lanes' device state under a leading lane axis."""
        import jax
        import jax.numpy as jnp

        fs, es = zip(*(ln._bucket_inputs(run_cap) for ln in self.lanes))
        frontier = jnp.stack(fs)
        ebits = jnp.stack(es)
        fcount = jnp.asarray(
            [ln._frontier_count for ln in self.lanes], jnp.int32
        )
        table = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *(ln._table for ln in self.lanes)
        )
        dfound = jnp.stack([ln._disc_found for ln in self.lanes])
        dfp = jnp.stack([ln._disc_fp for ln in self.lanes])
        return frontier, ebits, fcount, table, dfound, dfp

    def _grow_tables(self) -> None:
        for ln in self.lanes:
            ln._grow_table()

    def _grow_frontiers(self, run_cap: int) -> int:
        """Uniform frontier growth: the lead lane's ladder decides the
        next bucket; past the top every lane's capacity ceiling doubles
        together (the stack must stay rectangular)."""
        new_cap = self.lanes[0]._grow_frontier(run_cap)
        for ln in self.lanes[1:]:
            ln._counters.inc("frontier_grows")
            if ln._frontier_capacity < self.lanes[0]._frontier_capacity:
                ln._frontier_capacity = self.lanes[0]._frontier_capacity
        return new_cap

    def _maybe_grow_loaded(self) -> bool:
        """The solo engine's proactive load rule, over the whole stack:
        grow every lane while the BUSIEST lane crosses the ceiling."""
        lead = self.lanes[0]
        num, den = (
            (lead.MAX_LOAD_NUM, lead.MAX_LOAD_DEN)
            if lead._dedup == "hash"
            else (lead.SORTED_LOAD_NUM, lead.SORTED_LOAD_DEN)
        )
        grew = False
        while (
            max(ln._unique_count for ln in self.lanes) * den
            > self.lanes[0]._table.capacity * num
        ):
            self._grow_tables()
            grew = True
        return grew

    def _run_block(self, max_count: int = 1500) -> None:
        """Up to ``levels_per_dispatch`` BFS levels for every active lane
        in ONE device call per iteration (the mux analogue of the solo
        ``_run_block_fused``)."""
        import jax.numpy as jnp

        host_active = [ln._entry_checks() for ln in self.lanes]
        if not any(host_active):
            return
        lead = self.lanes[0]
        K = self.k

        budget_left = self._levels_per_dispatch
        run_cap = lead._run_cap_for(
            max(ln._frontier_count for ln, a in zip(self.lanes, host_active) if a)
        )
        retry = False
        while budget_left > 0:
            kmax = max(1, INT32_MAX // max(run_cap * lead._A, 1))
            budget = min(budget_left, kmax)
            remaining = np.full(K, INT32_MAX, dtype=np.int32)
            lane_budget = np.zeros(K, dtype=np.int32)
            for i, ln in enumerate(self.lanes):
                if not host_active[i]:
                    continue
                lane_budget[i] = budget
                if ln._target_max_depth is not None:
                    lane_budget[i] = max(
                        0, min(budget, ln._target_max_depth - ln._depth)
                    )
                if ln._target_state_count is not None:
                    remaining[i] = max(
                        1,
                        min(
                            INT32_MAX,
                            ln._target_state_count - ln._state_count,
                        ),
                    )
            if not lane_budget.any():
                break
            cand_cap = lead._cand_cap_for(run_cap)
            fn, key = self._mux_fused_for(run_cap, cand_cap)
            fresh = lead._mark_dispatch_shape(key)
            lanes_entry = int(sum(lane_budget > 0))
            if self._heartbeat is not None:
                self._heartbeat.beat(
                    "dispatch", compile=fresh, bucket=run_cap,
                    lanes=K, lanes_active=lanes_entry,
                )
            with self._tracer.span(
                "dispatch", flavor="mux", bucket=run_cap, cand=cand_cap,
                lanes=K, lanes_active=lanes_entry, compile=fresh,
                retry=retry, dedup=lead._dedup, compaction=lead._compaction,
            ) as _sp:
                args = self._stack(run_cap) + (
                    jnp.int32(budget),
                    jnp.asarray(remaining),
                    jnp.asarray(lane_budget),
                )
                (committed, nf, ne, ncount, table, dfound, dfp,
                 tot_s, tot_u, ovf, lv_act, lv_fr, lv_st, lv_un) = fn(*args)
                committed = int(committed)
                _sp.set(committed=committed)
            self.dispatch_log.append((run_cap, committed, K, lanes_entry))
            self._dispatches_saved += max(0, lanes_entry - 1)
            retry = False

            ncount = np.asarray(ncount)
            tot_s = np.asarray(tot_s)
            tot_u = np.asarray(tot_u)
            lv_act = np.asarray(lv_act)
            lv_fr = np.asarray(lv_fr)
            lv_st = np.asarray(lv_st)
            lv_un = np.asarray(lv_un)

            import jax

            for i, ln in enumerate(self.lanes):
                if not host_active[i]:
                    continue
                ln._frontier = nf[i]
                ln._frontier_ebits = ne[i]
                ln._frontier_count = int(ncount[i])
                ln._table = jax.tree_util.tree_map(lambda a, i=i: a[i], table)
                ln._disc_found = dfound[i]
                ln._disc_fp = dfp[i]
                ln._state_count += int(tot_s[i])
                ln._unique_count += int(tot_u[i])
                lane_committed = int(lv_act[:committed, i].sum()) if committed else 0
                ln.dispatch_log.append((run_cap, lane_committed))
                if lane_committed:
                    depth = ln._depth
                    for lvl in range(committed):
                        if not lv_act[lvl, i]:
                            continue
                        ln.level_log.append(
                            {
                                "depth": depth,
                                "frontier": int(lv_fr[lvl, i]),
                                "generated": int(lv_st[lvl, i]),
                                "unique": int(lv_un[lvl, i]),
                                "bucket": run_cap,
                                "cand_cap": cand_cap,
                                "lane_words": ln._level_lane_words(
                                    run_cap, cand_cap
                                ),
                                "lanes": K,
                                "lanes_active": int(lv_act[lvl].sum()),
                            }
                        )
                        depth += 1
                    ln._depth = depth
                    ln._max_depth = max(ln._max_depth, ln._depth - 1)
            if self._heartbeat is not None:
                self._heartbeat.commit(
                    depth=max(ln._depth for ln in self.lanes),
                    states=sum(ln._state_count for ln in self.lanes),
                )
            budget_left -= committed
            grew_proactively = self._maybe_grow_loaded()
            for i, ln in enumerate(self.lanes):
                if not host_active[i]:
                    continue
                ln._pin_found_names()
                if (
                    ln._target_state_count is not None
                    and ln._state_count >= ln._target_state_count
                ):
                    ln._target_reached = True
                ln._maybe_checkpoint()
                ln._maybe_record()

            t_ovf, f_ovf, c_ovf, cc_ovf = (bool(x) for x in np.asarray(ovf))
            if c_ovf:
                lead._raise_codec_overflow()
            if t_ovf:
                if not grew_proactively:
                    self._grow_tables()
                retry = True
                continue
            if f_ovf:
                run_cap = self._grow_frontiers(run_cap)
                retry = True
                continue
            if cc_ovf:
                lead._grow_cand_cap(run_cap)
                # Outgrown mux programs are dead weight (this mux always
                # looks up the grown cap; lane caps are lead-shared).
                cache = self._model.__dict__.get("_xla_mux_cache", {})
                cache.pop(self._mux_key(run_cap, cand_cap), None)
                retry = True
                continue
            if committed == 0:
                break
            host_active = [
                a and ln._entry_checks()
                for a, ln in zip(host_active, self.lanes)
            ]
            if not any(host_active):
                break

    # --- Checker-ish API --------------------------------------------------

    def is_done(self) -> bool:
        return all(ln.is_done() for ln in self.lanes)

    def run_to_completion(self) -> None:
        while not self.is_done():
            before = [
                (ln._depth, ln._state_count, ln.is_done()) for ln in self.lanes
            ]
            self._run_block()
            after = [
                (ln._depth, ln._state_count, ln.is_done()) for ln in self.lanes
            ]
            if before == after:  # pragma: no cover - livelock guard
                raise RuntimeError("mux dispatch made no progress")

    def state_count(self) -> int:
        return sum(ln.state_count() for ln in self.lanes)

    def unique_state_count(self) -> int:
        return sum(ln.unique_state_count() for ln in self.lanes)

    def max_depth(self) -> int:
        return max(ln.max_depth() for ln in self.lanes)

    def metrics(self) -> Dict[str, Any]:
        """The mux layer's own snapshot (each lane's ``metrics()`` stays
        the pinned per-engine schema; docs/observability.md "Lane
        telemetry")."""
        return {
            "engine": "xla-mux",
            "backend": self._jax.default_backend(),
            "mux_lanes": self.k,
            "mux_lanes_active": sum(1 for ln in self.lanes if not ln.is_done()),
            "mux_dispatches_saved": self._dispatches_saved,
            "dispatches": len(self.dispatch_log),
            "levels_committed": sum(c for _, c, _, _ in self.dispatch_log),
            "state_count": self.state_count(),
            "unique_state_count": self.unique_state_count(),
            "max_depth": max(ln.max_depth() for ln in self.lanes),
        }
