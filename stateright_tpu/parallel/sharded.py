"""Fingerprint-sharded frontier expansion over a ``jax.sharding.Mesh``.

One super-step per BFS level, run as a single ``shard_map``-ped program:

1. each shard evaluates properties over its local frontier rows and expands
   its local action grid (same fused kernels as the single-chip engine);
2. candidates are fingerprinted and assigned an **owner shard** from the
   fingerprint bits;
3. one ``all_to_all`` routes every candidate (state words + fingerprint +
   parent fingerprint + eventually-bits) to its owner;
4. the owner inserts into its local partition of the visited hash set —
   dedup is lock-free because exactly one shard can ever see a given
   fingerprint (vs. the insert-if-vacant race of bfs.rs:349-363);
5. newly-inserted states *are* the owner's next local frontier (children
   live where their fingerprint lives, so no return routing is needed);
6. counters and discovery flags combine with ``psum``/max.

Capacities (frontier rows per shard, table slots per shard, routing slots
per destination) are static per compiled program; overflow of any of them
sets a flag and the host grows the overflowing buffer and re-runs the same
level — safe because the step is functional.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from .. import obs
from ..checker.base import Checker
from ..core import Expectation, Model
from ..ops import deltaset, fphash, hashset, sortedset
from ..xla import ENGINE_COUNTERS, XlaChecker, _require_packed

# Owner mix constants: decorrelated from both the fingerprint lanes and the
# hash-set slot mix (ops/hashset.py:76) so shard choice, slot choice, and
# identity are pairwise independent.
_OWNER_MULT = 0x7FEB352D


def _owner_bits(fp_hi, fp_lo, n_shards: int, xp):
    u = xp.uint32
    mixed = (fp_lo ^ (fp_hi * u(_OWNER_MULT))) >> u(5)
    return (mixed % u(n_shards)).astype(xp.int32)


def default_mesh(n_devices: Optional[int] = None):
    """A 1-D ``Mesh`` over the first ``n_devices`` devices (all by default),
    with the axis name the engine expects."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("shards",))


class ShardedXlaChecker(Checker):
    """Level-synchronous BFS sharded over a device mesh.

    Spawn via ``model.checker().spawn_xla(mesh=mesh)``; with a 1-device mesh
    (or none) ``spawn_xla`` falls back to the single-chip engine.
    """

    def __init__(
        self,
        builder,
        mesh,
        *,
        frontier_capacity: Optional[int] = None,
        table_capacity: Optional[int] = None,
        route_capacity: Optional[int] = None,
        max_probes: int = 32,
        visit_cap: int = 4096,
        levels_per_dispatch: int = 32,
        checkpoint: Optional[str] = None,
        checkpoint_to: Optional[str] = None,
        checkpoint_every: Any = None,
        checkpoint_keep: Optional[int] = None,
        dedup: str = "auto",
        symmetry=None,
        host_verified_cap: int = 128,
        trace=None,
        heartbeat=None,
        metrics_to=None,
        metrics_every=None,
        metrics_keep: Optional[int] = None,
    ):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        model = builder._model
        _require_packed(model)
        self._model = model
        self._mesh = mesh
        self._D = mesh.devices.size
        if self._D & (self._D - 1):
            raise ValueError(f"mesh size must be a power of two, got {self._D}")
        # Symmetry reduction (stateright_tpu/sym, docs/symmetry.md): the
        # same resolution as the single-chip engine — shard ROUTING hashes
        # the canonical form too (owner bits come from the representative
        # fingerprint), so one class never splits across shards.
        from ..sym import SymmetryUnsupported, resolve_symmetry

        _sym = resolve_symmetry(
            symmetry, builder._symmetry is not None, model, engine="xla-mesh"
        )
        self._symmetry = _sym.enabled
        self._sym_tag = _sym.tag
        self._sym_canon = _sym.device_canon
        self._sym_canon_host = _sym.host_canon
        if self._symmetry and getattr(model, "host_verified_properties", ()):
            raise SymmetryUnsupported(
                "xla-mesh",
                f"{type(model).__name__} declares host_verified_properties; "
                f"the host-verified fallback evaluates concrete states and "
                f"cannot honor a symmetry-reduced frontier",
            )
        self._target_state_count = builder._target_state_count
        self._target_max_depth = builder._target_max_depth
        self._visitor = builder._visitor
        self._visit_cap = visit_cap
        # Same contract as the single-chip engine: the level loop runs on
        # device, up to this many levels per dispatch (visitors force 1).
        self._levels_per_dispatch = (
            1 if self._visitor is not None else max(1, levels_per_dispatch)
        )
        self._properties = model.properties()
        self._prop_names = [p.name for p in self._properties]
        self._ebit_of_prop: Dict[int, int] = {}
        for i, p in enumerate(self._properties):
            if p.expectation == Expectation.EVENTUALLY:
                self._ebit_of_prop[i] = len(self._ebit_of_prop)
        self._ebits0 = (1 << len(self._ebit_of_prop)) - 1

        self._max_probes = max_probes
        self._W = model.state_words
        self._A = model.max_actions
        self._P = len(self._properties)
        # Host-verified properties on the mesh (the single-chip contract,
        # xla.py: device flags candidate states with a conservative
        # predicate, the host confirms with the exact object-level
        # condition). Each shard compacts up to ``host_verified_cap``
        # candidate rows per super-step; the buffers stay sharded on device
        # and are only materialized host-side (``_host_read`` — an
        # allgather under ``jax.distributed``) when a level actually
        # flagged something.
        hv_names = frozenset(getattr(model, "host_verified_properties", ()))
        unknown = hv_names - {p.name for p in self._properties}
        if unknown:
            raise ValueError(f"host_verified_properties not in properties(): {unknown}")
        self._hv_idx = [
            i for i, p in enumerate(self._properties) if p.name in hv_names
        ]
        for i in self._hv_idx:
            if self._properties[i].expectation == Expectation.EVENTUALLY:
                raise ValueError(
                    "host-verified eventually-properties are not supported"
                )
        self._hv_cap = host_verified_cap

        # Per-shard visited-set structure + bulk-buffer layout, mirroring
        # the single-chip engine (xla.py): accelerators get the sort-merge
        # set, plane-major grid/payload buffers, and gather-based packing
        # and compaction; CPUs keep the hash set + scatter lowerings that
        # win there. Each shard's table partition is an independent
        # instance of the structure (ownership routing makes cross-shard
        # dedup races impossible either way).
        if dedup == "auto":
            dedup = "hash" if jax.default_backend() == "cpu" else "sorted"
        if dedup not in ("hash", "sorted", "delta"):
            raise ValueError(
                f"dedup must be 'auto', 'hash', 'sorted', or 'delta': {dedup!r}"
            )
        self._dedup = dedup
        self._ds = {"hash": hashset, "sorted": sortedset, "delta": deltaset}[dedup]

        D = self._D
        # Capacities learned by earlier checkers of this model over a
        # same-size mesh (growth events) — start there instead of repeating
        # the growth.
        # Same hint policy as the single-chip engine: hints may only raise
        # DEFAULT capacities — an explicit request (even a smaller one, e.g.
        # to exercise the growth path) wins over cross-checker state.
        hints = model.__dict__.get("_xla_sharded_cap_hints", {}).get(D, {})
        if frontier_capacity is None:
            frontier_capacity = max(1 << 15, hints.get("frontier", 0))
        if table_capacity is None:
            table_capacity = max(1 << 20, hints.get("table", 0))
        self._Fl = max(frontier_capacity // D, 16)  # frontier rows per shard
        self._Cl = max(table_capacity // D, 64)  # table slots per shard
        if self._Cl & (self._Cl - 1):
            raise ValueError("table_capacity/D must be a power of two")
        # Routing slots per (src, dst) pair. Hash uniformity spreads each
        # shard's candidates evenly over destinations; 4x slack + retry on
        # overflow covers skew.
        local_cand = self._Fl * self._A
        if route_capacity is not None:
            if route_capacity < 1:
                # K=0 could never grow out of route overflow (growth doubles).
                raise ValueError(f"route_capacity must be >= 1, got {route_capacity}")
            self._K = route_capacity  # explicit request wins over the hint
        else:
            self._K = min(local_cand, max(64, (local_cand // D) * 4))
            self._K = max(self._K, hints.get("route", 0))

        self._row_spec = P("shards", None)
        self._plane_spec = P("shards")
        self._row_sharding = NamedSharding(mesh, self._row_spec)
        self._plane_sharding = NamedSharding(mesh, self._plane_spec)
        self._rep_sharding = NamedSharding(mesh, P())

        self._found_names: Dict[str, int] = {}
        self._target_reached = False
        self._step_cache: Dict[Any, Any] = {}
        # Observability (stateright_tpu/obs): same contract as the
        # single-chip engine — spans/heartbeat around every SPMD dispatch,
        # the unified dispatch_log shape ((run_rows, committed_levels) per
        # device call, global rows here), and metrics() counters. The mesh
        # engine adds a route-buffer growth counter to the shared seed.
        self._tracer = obs.resolve_tracer(trace)
        self._heartbeat = obs.resolve_heartbeat(heartbeat)
        # Recorder gated to process 0, like save_checkpoint: under
        # jax.distributed every rank reaches the same quiescent point
        # with the same gauges, so rank 0's rows ARE the series — and
        # concurrent appenders on one base path would double-count rows
        # and double-shift the rotation chain out from under each other.
        self._recorder = (
            obs.resolve_recorder(metrics_to, metrics_every, metrics_keep)
            if jax.process_index() == 0
            else None
        )
        self._counters = obs.Counters(ENGINE_COUNTERS + ("route_grows",))
        self.dispatch_log = []
        # Recovery surface — same contract as the single-chip engine
        # (stateright_tpu/checkpoint.py): in-loop auto-checkpointing at
        # superstep boundaries plus resume-provenance gauges.
        from ..checkpoint import AutoCheckpointer

        self._autockpt = AutoCheckpointer.resolve(
            checkpoint_to, checkpoint_every, checkpoint_keep
        )
        self._last_checkpoint: Optional[Dict[str, Any]] = None
        self._resumed_from: Optional[str] = checkpoint

        if checkpoint is not None:
            # Skip init seeding entirely; _restore builds the whole state.
            self._restore(checkpoint)
            if self._autockpt is not None:
                self._autockpt.arm(self._depth)
            if self._recorder is not None:
                self._recorder.arm(self._depth)
            return

        # --- initial device state ----------------------------------------
        init_packed = np.asarray(model.packed_init(), dtype=np.uint32)
        keep = [model.within_boundary(model.unpack(row)) for row in init_packed]
        init_packed = init_packed[keep]
        n_init = len(init_packed)

        # Route init states to their owner shard host-side.
        frontier, fhi, flo, ebits, counts = self._route_frontier_host(
            init_packed, np.full(n_init, self._ebits0, dtype=np.uint32)
        )
        self._frontier = jax.device_put(
            frontier.reshape(D * self._Fl, self._W), self._row_sharding
        )
        self._frontier_ebits = jax.device_put(
            ebits.reshape(D * self._Fl), self._plane_sharding
        )
        self._counts = jax.device_put(counts, self._plane_sharding)

        self._table = self._make_table()
        # Insert init fingerprints (shard-local batches, zero parents).
        zeros = np.zeros_like(fhi)
        n_unique_init = self._bulk_insert(fhi, flo, zeros, zeros, counts)
        self._disc_found = jax.device_put(
            jnp.zeros(self._P, jnp.bool_), self._rep_sharding
        )
        self._disc_fp = jax.device_put(
            jnp.zeros((self._P, 2), jnp.uint32), self._rep_sharding
        )

        self._depth = 1
        self._max_depth = 0
        self._state_count = n_init
        self._unique_count = int(n_unique_init)
        self._frontier_total_cache = n_init
        self._exhausted = n_init == 0
        if self._autockpt is not None:
            self._autockpt.arm(self._depth)
        if self._recorder is not None:
            self._recorder.arm(self._depth)

    # --- checkpoint/resume (stateright_tpu/checkpoint.py) ------------------

    def save_checkpoint(self, path: str, keep: int = 1) -> None:
        """The single-chip implementation (atomic + rotating save, obs
        span, ``checkpoints_written`` counter, ``last_checkpoint`` gauge),
        gated to process 0: under ``jax.distributed`` every rank reaches
        the same quiescent point with the same allgathered payload
        (``_host_read``), so rank 0's write IS the complete checkpoint —
        and concurrent writers on one base path would sweep each other's
        temp files and double-shift the rotation chain."""
        import jax

        if jax.process_index() != 0:
            return
        XlaChecker.save_checkpoint(self, path, keep)

    # The in-loop auto-checkpoint hook routes through save_checkpoint
    # above, so the process-0 gate covers automatic writes too. The
    # metrics time-series hook samples at the same quiescent points
    # (metrics() here is host-side cached reads — no device dispatch, so
    # multi-process SPMD program order is safe).
    _maybe_checkpoint = XlaChecker._maybe_checkpoint
    _maybe_record = XlaChecker._maybe_record

    def _restore(self, path: str) -> None:
        """Loads a checkpoint, re-routing frontier rows and table entries to
        their owner shards — the checkpoint is layout-agnostic, so one
        written by the single-chip engine (or a different mesh size) loads
        here."""
        import jax
        import jax.numpy as jnp

        from ..checkpoint import load_checkpoint, validate_model, validate_symmetry

        ck = load_checkpoint(path)
        validate_model(ck["meta"], self._model, self._prop_names)
        validate_symmetry(ck["meta"], self._sym_tag)
        D = self._D

        # Visited set: distribute entries by owner, then bulk-insert.
        kh = np.asarray(ck["key_hi"], dtype=np.uint32)
        kl = np.asarray(ck["key_lo"], dtype=np.uint32)
        vh = np.asarray(ck["val_hi"], dtype=np.uint32)
        vl = np.asarray(ck["val_lo"], dtype=np.uint32)
        owners = _owner_bits(kh, kl, D, np)
        counts, order, pos = self._shard_positions(owners, D)
        B = max(16, int(counts.max()))
        while self._Cl < 2 * B:
            self._Cl *= 2
        self._table = self._make_table()
        blocks = [np.zeros((D, B), dtype=np.uint32) for _ in range(4)]
        shard = owners[order]
        for block, lane in zip(blocks, (kh, kl, vh, vl)):
            block[shard, pos] = lane[order]
        self._bulk_insert(*blocks, counts)

        # Frontier: re-route rows to their owners.
        rows = np.asarray(ck["frontier"], dtype=np.uint32)
        frontier, _fhi, _flo, ebits, fcounts = self._route_frontier_host(
            rows, np.asarray(ck["frontier_ebits"], dtype=np.uint32)
        )
        Fl = self._Fl
        self._frontier = jax.device_put(
            frontier.reshape(D * Fl, self._W), self._row_sharding
        )
        self._frontier_ebits = jax.device_put(
            ebits.reshape(D * Fl), self._plane_sharding
        )
        self._counts = jax.device_put(fcounts, self._plane_sharding)
        self._frontier_total_cache = int(fcounts.sum())

        meta = ck["meta"]
        self._depth = meta["depth"]
        self._max_depth = meta["max_depth"]
        self._state_count = meta["state_count"]
        self._unique_count = meta["unique_count"]
        self._found_names = dict(meta["found_names"])
        self._exhausted = meta["exhausted"]
        self._target_reached = meta["target_reached"]
        disc_found = np.zeros(self._P, dtype=bool)
        disc_fp = np.zeros((self._P, 2), dtype=np.uint32)
        for i, name in enumerate(self._prop_names):
            if name in self._found_names:
                fp64 = self._found_names[name]
                disc_found[i] = True
                disc_fp[i, 0] = fp64 >> 32
                disc_fp[i, 1] = fp64 & 0xFFFFFFFF
        self._disc_found = jax.device_put(
            jnp.asarray(disc_found), self._rep_sharding
        )
        self._disc_fp = jax.device_put(jnp.asarray(disc_fp), self._rep_sharding)

    # --- host helpers (shared semantics with the single-chip engine) ------

    _dedup_words_host = XlaChecker._dedup_words_host
    _packed_fp64 = XlaChecker._packed_fp64
    _path_for = XlaChecker._path_for
    # _parent_map is overridden below: it must gather table planes across
    # processes before indexing them.

    # --- table representation ----------------------------------------------
    #
    # The sharded table is the single-chip structure per shard, stored as
    # GLOBAL planes sharded over the mesh. hash: 4 uint32 planes [D*Cl].
    # sorted: the same 4 planes plus a [D] int32 plane of per-shard occupied
    # prefix lengths (SortedSet.n, one scalar per shard). Both reprs keep
    # the key_hi/key_lo/val_hi/val_lo attribute names and the zero-pad
    # layout contract, so checkpointing and the native ParentMap consume
    # either unchanged.

    def _delta_cap(self) -> int:
        """Per-shard delta-tier rows for dedup="delta"."""
        return deltaset._delta_cap(self._Cl)

    def _make_table(self):
        import jax
        import jax.numpy as jnp

        D = self._D
        z = jnp.zeros((D * self._Cl,), jnp.uint32)
        planes = [jax.device_put(z, self._plane_sharding) for _ in range(4)]
        if self._dedup == "delta":
            zd = jnp.zeros((D * self._delta_cap(),), jnp.uint32)
            dplanes = [jax.device_put(zd, self._plane_sharding) for _ in range(4)]
            nz = lambda: jax.device_put(
                jnp.zeros((D,), jnp.int32), self._plane_sharding
            )
            return deltaset.DeltaSet(*planes, *dplanes, nz(), nz())
        if self._dedup == "sorted":
            n = jax.device_put(jnp.zeros((D,), jnp.int32), self._plane_sharding)
            return sortedset.SortedSet(*planes, n)
        return hashset.HashSet(*planes)

    def _table_len(self) -> int:
        return {"hash": 4, "sorted": 5, "delta": 10}[self._dedup]

    def _local_table(self, table):
        """Per-shard structure from the shard-local plane blocks (inside
        shard_map: planes are [Cl] (+ [dc] delta tiers), n planes [1])."""
        if self._dedup == "delta":
            return deltaset.DeltaSet(*table[:8], table[8][0], table[9][0])
        if self._dedup == "sorted":
            return sortedset.SortedSet(
                table[0], table[1], table[2], table[3], table[4][0]
            )
        return hashset.HashSet(*table)

    @staticmethod
    def _local_table_out(new_table):
        """Back to the tuple-of-blocks form (rank-1 n so it shards)."""
        if isinstance(new_table, deltaset.DeltaSet):
            return tuple(new_table[:8]) + (
                new_table.n_main[None],
                new_table.n_delta[None],
            )
        if isinstance(new_table, sortedset.SortedSet):
            return (
                new_table.key_hi,
                new_table.key_lo,
                new_table.val_hi,
                new_table.val_lo,
                new_table.n[None],
            )
        return tuple(new_table)

    # --- device programs ---------------------------------------------------

    def _shard_map(self, fn, in_specs, out_specs):
        import jax

        smap = jax.shard_map(
            fn,
            mesh=self._mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(smap)

    @staticmethod
    def _shard_positions(owners: np.ndarray, D: int):
        """Vectorized bucket placement: for each element, its shard and its
        position within that shard (stable order). Returns
        ``(counts[D], sorted_order, pos_in_shard)``."""
        counts = np.bincount(owners, minlength=D).astype(np.int32)
        order = np.argsort(owners, kind="stable")
        offsets = np.zeros(D, dtype=np.int64)
        np.cumsum(counts[:-1], out=offsets[1:])
        pos = np.arange(len(owners), dtype=np.int64) - np.repeat(offsets, counts)
        return counts, order, pos

    def _route_frontier_host(self, rows: np.ndarray, ebits_values: np.ndarray):
        """Distribute packed rows to their owner shard (host-side,
        vectorized; used for init seeding and checkpoint restore). Grows
        ``Fl`` (and rescales the routing capacity) to fit. Returns
        ``(frontier[D,Fl,W], fhi[D,Fl], flo[D,Fl], ebits[D,Fl], counts[D])``.
        """
        D, W = self._D, self._W
        n = len(rows)
        if n:
            dedup = self._dedup_words_host(rows)
            ihi, ilo = fphash.fingerprint_words(dedup, np)
            owners = _owner_bits(ihi, ilo, D, np)
            counts, order, pos = self._shard_positions(owners, D)
            grew = False
            while self._Fl < int(counts.max()):
                self._Fl *= 2
                grew = True
            if grew:
                # Keep the routing buffers scaled with the frontier, as
                # _grow_frontier does — otherwise the first superstep would
                # churn through route-overflow recompiles.
                local_cand = self._Fl * self._A
                self._K = min(local_cand, max(self._K, (local_cand // D) * 4))
        Fl = self._Fl
        frontier = np.zeros((D, Fl, W), dtype=np.uint32)
        fhi = np.zeros((D, Fl), dtype=np.uint32)
        flo = np.zeros((D, Fl), dtype=np.uint32)
        ebits = np.zeros((D, Fl), dtype=np.uint32)
        out_counts = np.zeros((D,), dtype=np.int32)
        if n:
            shard = owners[order]
            frontier[shard, pos] = rows[order]
            fhi[shard, pos] = ihi[order]
            flo[shard, pos] = ilo[order]
            ebits[shard, pos] = np.asarray(ebits_values, dtype=np.uint32)[order]
            out_counts = counts
        return frontier, fhi, flo, ebits, out_counts

    def _bulk_insert(
        self,
        fhi: np.ndarray,
        flo: np.ndarray,
        vhi: np.ndarray,
        vlo: np.ndarray,
        counts: np.ndarray,
    ) -> int:
        """Insert per-shard blocks ``[D, B]`` of (fingerprint, value) pairs
        into the sharded table; grows the table and retries on overflow.
        Returns the number of new entries (psum over shards)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        D, B = fhi.shape
        max_probes = self._max_probes
        ds = self._ds
        TL = self._table_len()
        local_table = self._local_table
        local_table_out = self._local_table_out

        def build():
            def body(table, fh, fl, vh, vl, count):
                active = jnp.arange(B) < count[0]
                table, is_new, ovf = ds.insert(
                    local_table(table), fh, fl, vh, vl, active,
                    max_probes=max_probes,
                )
                unique = jax.lax.psum(jnp.sum(is_new, dtype=jnp.int32), "shards")
                any_ovf = jax.lax.pmax(jnp.any(ovf).astype(jnp.uint32), "shards")
                return local_table_out(table), unique, any_ovf

            return self._shard_map(
                body,
                in_specs=(
                    (P("shards"),) * TL,
                    P("shards"), P("shards"), P("shards"), P("shards"), P("shards"),
                ),
                out_specs=((P("shards"),) * TL, P(), P()),
            )

        cache = self.__dict__.setdefault("_bulk_insert_cache", {})
        put = lambda a: jax.device_put(a.reshape(-1), self._plane_sharding)
        while True:
            # Re-key per attempt: _grow_table changes the plane shapes.
            key = ("bulk", B, self._Cl)
            fn = cache.get(key)
            if fn is None:
                fn = cache[key] = build()
            planes, unique, ovf = fn(
                tuple(self._table),
                put(fhi), put(flo), put(vhi), put(vlo),
                jax.device_put(counts, self._plane_sharding),
            )
            if bool(np.asarray(ovf)):
                self._grow_table()
                continue
            self._table = self._global_table(planes)
            return int(np.asarray(unique))

    def _global_table(self, planes):
        cls = {
            "hash": hashset.HashSet,
            "sorted": sortedset.SortedSet,
            "delta": deltaset.DeltaSet,
        }[self._dedup]
        return cls(*planes)

    def _make_local_step(self, Fl: int, Cl: int, K: int):
        """The per-shard superstep body (one BFS level), without the
        ``shard_map`` wrapper — shared by the one-level and fused
        programs."""
        import jax
        import jax.numpy as jnp

        model = self._model
        prop_specs = [(i, p.expectation) for i, p in enumerate(self._properties)]
        ebit_of_prop = dict(self._ebit_of_prop)
        symmetry = self._symmetry
        A, W, D = self._A, self._W, self._D
        P_count = self._P
        max_probes = self._max_probes
        hv_idx = list(self._hv_idx)
        n_hv = len(hv_idx)
        hv_cap = self._hv_cap
        LANES = W + 5  # state words + fp_hi, fp_lo, par_hi, par_lo, ebits
        ds = self._ds
        sorted_mode = self._dedup != "hash"  # planes/gather lowering family
        local_table = self._local_table
        local_table_out = self._local_table_out

        sym_canon = self._sym_canon

        def dedup_words(words):
            return sym_canon(words) if symmetry else words

        def pick_discovery(disc_found, disc_fp, i, viol, fhi, flo):
            """Elect one witness fingerprint across shards: the local first
            match, combined by pmax (the reference lets threads race here,
            bfs.rs:291-306; pmax is simply a deterministic tiebreak)."""
            has_local = jnp.any(viol)
            first = jnp.argmax(viol)
            cand_hi = jnp.where(has_local, fhi[first], jnp.uint32(0))
            cand_lo = jnp.where(has_local, flo[first], jnp.uint32(0))
            g_hi = jax.lax.pmax(cand_hi, "shards")
            is_max_shard = cand_hi == g_hi
            g_lo = jax.lax.pmax(
                jnp.where(is_max_shard, cand_lo, jnp.uint32(0)), "shards"
            )
            has = jax.lax.pmax(has_local.astype(jnp.uint32), "shards") > 0
            take = has & ~disc_found[i]
            disc_fp = disc_fp.at[i, 0].set(jnp.where(take, g_hi, disc_fp[i, 0]))
            disc_fp = disc_fp.at[i, 1].set(jnp.where(take, g_lo, disc_fp[i, 1]))
            disc_found = disc_found.at[i].set(disc_found[i] | has)
            return disc_found, disc_fp

        def superstep(frontier, f_ebits, count, table, disc_found, disc_fp):
            # Local block shapes: frontier [Fl, W], f_ebits [Fl], count [1],
            # table planes [Cl], disc_* replicated.
            f_valid = jnp.arange(Fl) < count[0]
            dw = jax.vmap(dedup_words)(frontier)
            fhi, flo = fphash.fingerprint_words(dw, jnp)

            # 1. property evaluation over the local frontier. Host-verified
            #    properties compact up to ``hv_cap`` shard-local candidate
            #    rows instead of pinning a discovery — the host confirms
            #    with the exact condition (xla.py ``_checking_blocks``).
            #    Zero-padded rows carry fp (0, 0), which a real state never
            #    has, so the host needs no per-shard layout bookkeeping.
            def hv_compact(viol):
                k = min(hv_cap, Fl)
                order = jnp.argsort(~viol, stable=True)[:k]
                m = viol[order]
                cw = jnp.where(m[:, None], frontier[order], jnp.uint32(0))
                cf = jnp.where(
                    m[:, None],
                    jnp.stack([fhi[order], flo[order]], axis=1),
                    jnp.uint32(0),
                )
                if k < hv_cap:
                    cw = jnp.concatenate(
                        [cw, jnp.zeros((hv_cap - k, W), jnp.uint32)]
                    )
                    cf = jnp.concatenate(
                        [cf, jnp.zeros((hv_cap - k, 2), jnp.uint32)]
                    )
                return cw, cf, jnp.sum(viol, dtype=jnp.int32)

            hv_w_out, hv_f_out, hv_c_out = [], [], []
            props = jax.vmap(model.packed_properties)(frontier)  # [Fl, P]
            for i, expectation in prop_specs:
                if expectation == Expectation.EVENTUALLY:
                    bit = jnp.uint32(1 << ebit_of_prop[i])
                    sat = props[:, i] & f_valid
                    f_ebits = jnp.where(sat, f_ebits & ~bit, f_ebits)
                    continue
                if expectation == Expectation.ALWAYS:
                    viol = ~props[:, i] & f_valid
                else:
                    viol = props[:, i] & f_valid
                if i in hv_idx:
                    cw, cf, n_viol = hv_compact(viol)
                    hv_w_out.append(cw)
                    hv_f_out.append(cf)
                    hv_c_out.append(n_viol)
                    continue
                disc_found, disc_fp = pick_discovery(
                    disc_found, disc_fp, i, viol, fhi, flo
                )
            if n_hv:
                hv_w = jnp.stack(hv_w_out)  # [n_hv, hv_cap, W]
                hv_f = jnp.stack(hv_f_out)  # [n_hv, hv_cap, 2]
                hv_c = jnp.stack(hv_c_out)[:, None]  # [n_hv, 1]
            else:
                hv_w = jnp.zeros((0, hv_cap, W), jnp.uint32)
                hv_f = jnp.zeros((0, hv_cap, 2), jnp.uint32)
                hv_c = jnp.zeros((0, 1), jnp.int32)

            # 2. local action-grid expansion. An optional third output is
            #    the per-action codec-overflow mask (see xla.py superstep
            #    step 2): psum'd across shards and surfaced loudly.
            stepped = jax.vmap(model.packed_step)(frontier)  # [Fl,A,W],[Fl,A]
            if len(stepped) == 3:
                nxt, valid, step_ovf = stepped
                codec_ovf = (
                    jax.lax.pmax(
                        jnp.any(step_ovf & f_valid[:, None]).astype(jnp.uint32),
                        "shards",
                    )
                    > 0
                )
            else:
                nxt, valid = stepped
                codec_ovf = jnp.bool_(False)
            valid = valid & f_valid[:, None]
            step_states = jax.lax.psum(jnp.sum(valid, dtype=jnp.int32), "shards")

            # 3. terminal detection (bfs.rs:374-381) before routing — it
            #    needs the parent-side successor mask.
            terminal = f_valid & ~jnp.any(valid, axis=1)
            for i, expectation in prop_specs:
                if expectation != Expectation.EVENTUALLY:
                    continue
                bit = jnp.uint32(1 << ebit_of_prop[i])
                viol = terminal & ((f_ebits & bit) != 0)
                disc_found, disc_fp = pick_discovery(
                    disc_found, disc_fp, i, viol, fhi, flo
                )

            # 4-6. fingerprint candidates, assign owner shards, pack
            #    per-destination routing buffers, all_to_all. Each candidate
            #    has exactly one destination, so the pack is one
            #    O(Fl*A log) sort pass regardless of mesh size; candidates
            #    stay in state-major (frontier) order within each
            #    destination, so the receiver's insert elects the same
            #    winners as the single-chip engine. Inactive slots stay
            #    all-zero; (0,0) fingerprints mark them empty downstream.
            #
            #    Two lowerings (same results): the sorted/accelerator path
            #    keeps the grid plane-major ([W, A*Fl], lane-axis Fl — see
            #    the xla.py layout note) and GATHERS destination slots from
            #    the owner-sorted order; the hash/CPU path keeps row-major
            #    buffers and a scatter pack.
            n_cand = Fl * A
            if sorted_mode:
                grid = jnp.transpose(nxt, (2, 1, 0)).reshape(W, n_cand)
                vflat = valid.T.reshape(-1)
                if symmetry:
                    crows = jnp.stack([grid[w] for w in range(W)], axis=1)
                    cdw = jax.vmap(dedup_words)(crows)
                    chi, clo = fphash.fingerprint_words(cdw, jnp)
                else:
                    chi, clo = fphash.fingerprint_planes(grid, jnp)
                owner = _owner_bits(chi, clo, D, jnp)
                par_hi = jnp.broadcast_to(fhi[None, :], (A, Fl)).reshape(-1)
                par_lo = jnp.broadcast_to(flo[None, :], (A, Fl)).reshape(-1)
                ceb = jnp.broadcast_to(f_ebits[None, :], (A, Fl)).reshape(-1)
                j = jnp.arange(n_cand, dtype=jnp.int32)
                prio = (j % Fl) * A + (j // Fl)  # state-major rank f*A + a
                owner_eff = jnp.where(vflat, owner, D)
                if (D + 1) * n_cand < (1 << 31):
                    # Fused int32 key (owner, state-major rank): one key
                    # operand instead of two on the routing sort.
                    key = owner_eff * jnp.int32(n_cand) + prio
                    key_s, order = jax.lax.sort((key, j), num_keys=1)
                    so = key_s // jnp.int32(n_cand)
                else:  # pragma: no cover - needs a >2^31 global grid
                    so, _, order = jax.lax.sort((owner_eff, prio, j), num_keys=2)
                starts = jnp.searchsorted(so, jnp.arange(D + 1))
                cnt = starts[1:] - starts[:-1]
                route_ovf = jnp.any(cnt > K)
                src = jnp.clip(
                    starts[:-1][:, None] + jnp.arange(K)[None, :], 0, n_cand - 1
                )
                idx = order[src]  # [D, K] payload lanes per destination
                mask = jnp.arange(K)[None, :] < cnt[:, None]
                planes = [grid[w] for w in range(W)] + [chi, clo, par_hi, par_lo, ceb]
                buf = jnp.stack(
                    [jnp.where(mask, p[idx], jnp.uint32(0)) for p in planes]
                )  # [LANES, D, K]
                route_ovf = jax.lax.pmax(route_ovf.astype(jnp.uint32), "shards") > 0
                recv = jax.lax.all_to_all(
                    buf, "shards", split_axis=1, concat_axis=1, tiled=False
                ).reshape(LANES, D * K)
                r_state = recv[:W]  # [W, D*K] planes
                r_hi, r_lo = recv[W], recv[W + 1]
                r_par_hi, r_par_lo = recv[W + 2], recv[W + 3]
                r_ebits = recv[W + 4]
            else:
                cand = nxt.reshape(n_cand, W)
                cdw = jax.vmap(dedup_words)(cand)
                chi, clo = fphash.fingerprint_words(cdw, jnp)
                vflat = valid.reshape(-1)
                owner = _owner_bits(chi, clo, D, jnp)
                payload = jnp.concatenate(
                    [
                        cand,
                        chi[:, None],
                        clo[:, None],
                        jnp.broadcast_to(fhi[:, None], (Fl, A)).reshape(-1)[:, None],
                        jnp.broadcast_to(flo[:, None], (Fl, A)).reshape(-1)[:, None],
                        jnp.broadcast_to(f_ebits[:, None], (Fl, A)).reshape(-1)[:, None],
                    ],
                    axis=1,
                )  # [Fl*A, LANES]
                owner_eff = jnp.where(vflat, owner.astype(jnp.int32), D)
                order = jnp.argsort(owner_eff, stable=True)
                sorted_owner = owner_eff[order]
                starts = jnp.searchsorted(sorted_owner, jnp.arange(D + 1))
                route_ovf = jnp.any(starts[1:] - starts[:-1] > K)
                slot = jnp.arange(n_cand) - starts[jnp.clip(sorted_owner, 0, D - 1)]
                keep = (sorted_owner < D) & (slot < K)
                buf = (
                    jnp.zeros((D, K, LANES), jnp.uint32)
                    .at[
                        jnp.where(keep, sorted_owner, D),
                        jnp.where(keep, slot, K),
                        :,
                    ]
                    .set(jnp.where(keep[:, None], payload[order], 0), mode="drop")
                )
                route_ovf = jax.lax.pmax(route_ovf.astype(jnp.uint32), "shards") > 0
                recv = jax.lax.all_to_all(
                    buf, "shards", split_axis=0, concat_axis=0, tiled=False
                ).reshape(D * K, LANES)
                r_state = recv[:, :W]  # [D*K, W] rows
                r_hi, r_lo = recv[:, W], recv[:, W + 1]
                r_par_hi, r_par_lo = recv[:, W + 2], recv[:, W + 3]
                r_ebits = recv[:, W + 4]
            r_active = (r_hi != 0) | (r_lo != 0)

            # 7. owner-local dedup insert (no cross-shard races possible;
            #    both structures share the insert contract).
            new_table, is_new, ovf = ds.insert(
                local_table(table),
                r_hi,
                r_lo,
                r_par_hi,
                r_par_lo,
                r_active,
                max_probes=max_probes,
            )
            step_unique = jax.lax.psum(jnp.sum(is_new, dtype=jnp.int32), "shards")
            table_ovf = jax.lax.pmax(jnp.any(ovf).astype(jnp.uint32), "shards") > 0

            # 8. compact the owner's new states into its next local
            #    frontier (gather lowering for sorted/accelerator, scatter
            #    for hash/CPU; identical results — receiver lane order).
            new_count = jnp.sum(is_new, dtype=jnp.int32)
            frontier_ovf = (
                jax.lax.pmax((new_count > Fl).astype(jnp.uint32), "shards") > 0
            )
            if sorted_mode:
                order2 = jnp.argsort(~is_new, stable=True)[:Fl]
                sm = is_new[order2]
                new_frontier = jnp.stack(
                    [
                        jnp.where(sm, r_state[w][order2], jnp.uint32(0))
                        for w in range(W)
                    ],
                    axis=1,
                )  # [Fl, W] rows (the kernel-facing boundary)
                new_ebits = jnp.where(sm, r_ebits[order2], jnp.uint32(0))
            else:
                pos = jnp.cumsum(is_new.astype(jnp.int32)) - 1
                idx2 = jnp.where(is_new & (pos < Fl), pos, Fl)
                new_frontier = (
                    jnp.zeros((Fl, W), jnp.uint32).at[idx2].set(r_state, mode="drop")
                )
                new_ebits = (
                    jnp.zeros((Fl,), jnp.uint32).at[idx2].set(r_ebits, mode="drop")
                )

            return (
                new_frontier,
                new_ebits,
                new_count[None],
                local_table_out(new_table),
                disc_found,
                disc_fp,
                step_states,
                step_unique,
                table_ovf,
                frontier_ovf,
                route_ovf,
                codec_ovf,
                hv_w,
                hv_f,
                hv_c,
            )

        return superstep

    def _build_superstep(self, Fl: int, Cl: int, K: int):
        from jax.sharding import PartitionSpec as P

        TL = self._table_len()
        spec_rows = P("shards", None)
        spec_plane = P("shards")
        spec_rep = P()
        return self._shard_map(
            self._make_local_step(Fl, Cl, K),
            in_specs=(
                spec_rows,
                spec_plane,
                spec_plane,
                (spec_plane,) * TL,
                spec_rep,
                spec_rep,
            ),
            out_specs=(
                spec_rows,
                spec_plane,
                spec_plane,
                (spec_plane,) * TL,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                P(None, "shards", None),  # hv candidate words
                P(None, "shards", None),  # hv candidate fingerprints
                P(None, "shards"),  # hv per-shard counts
            ),
        )

    def _build_fused(self, Fl: int, Cl: int, K: int):
        """The level loop as one SPMD program: a ``lax.while_loop`` (with
        the cross-shard collectives inside its body) around the local
        superstep. Every shard computes the exit condition from replicated
        values, so the loop stays in lockstep. Exit conditions mirror the
        single-chip fused block (xla.py ``_build_fused``): level budget,
        global frontier exhaustion, any overflow (the overflowing level is
        NOT committed), every property found, or a state-count target."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        local_step = self._make_local_step(Fl, Cl, K)
        P_count = self._P
        W = self._W
        n_hv = len(self._hv_idx)
        hv_cap = self._hv_cap
        hv_idx = list(self._hv_idx)  # slot j <-> property hv_idx[j]
        hv_pos = {i: j for j, i in enumerate(self._hv_idx)}

        def fused(frontier, f_ebits, count, table, disc_found, disc_fp,
                  budget, remaining, host_found):
            def resolved(df, g_hv_c):
                """Every property found on device, already confirmed on
                host, or — host-verified — with candidates collected
                somewhere on the mesh (global counts, so all shards agree)."""
                if P_count == 0:
                    return jnp.bool_(False)
                per_prop = [
                    host_found[i]
                    | (g_hv_c[hv_pos[i]] > 0 if i in hv_pos else df[i])
                    for i in range(P_count)
                ]
                return jnp.all(jnp.stack(per_prop))

            def hv_pending(g_hv_c):
                """Any *unconfirmed* host-verified property with collected
                candidates anywhere on the mesh: exit so the host can
                confirm — the same one-level candidate budget as the
                single-chip fused block (xla.py)."""
                if not n_hv:
                    return jnp.bool_(False)
                flags = [
                    (g_hv_c[j] > 0) & ~host_found[i] for i, j in hv_pos.items()
                ]
                return jnp.any(jnp.stack(flags))

            def cond(carry):
                (lvl, committed, fr, eb, cnt, tab, df, dfp, ts, tu, ovf,
                 gcount, hv_w, hv_f, hv_c, g_hv_c) = carry
                return (
                    (lvl < budget)
                    & (gcount > 0)
                    & ~jnp.any(ovf)
                    & ~resolved(df, g_hv_c)
                    & ~hv_pending(g_hv_c)
                    & (ts < remaining)
                )

            def body(carry):
                (lvl, committed, fr, eb, cnt, tab, df, dfp, ts, tu, ovf,
                 gcount, hv_w, hv_f, hv_c, g_hv_c) = carry
                (nf, ne, ncnt, ntab, ndf, ndfp, ds, du, t_ovf, f_ovf,
                 r_ovf, c_ovf, lw, lf, lc) = local_step(fr, eb, cnt, tab, df, dfp)
                commit = ~(t_ovf | f_ovf | r_ovf | c_ovf)
                sel = lambda new, old: jax.tree_util.tree_map(
                    lambda a, b: jnp.where(commit, a, b), new, old
                )
                # Append this level's shard-local candidates to the block
                # accumulators (level order across the block, shard-local
                # frontier order within a level).
                if n_hv:
                    rows = jnp.arange(hv_cap)
                    new_w, new_f = hv_w, hv_f
                    # A property the host already confirmed collects
                    # nothing: without this mask the accumulators keep
                    # growing for confirmed properties and rows past
                    # hv_cap are dropped silently — harmless only while
                    # _confirm_hv_candidates skips confirmed props, a
                    # coupling no future consumer should inherit.
                    lc = lc * jnp.stack(
                        [(~host_found[i]).astype(lc.dtype) for i in hv_idx]
                    )[:, None]
                    for j in range(n_hv):
                        dst = hv_c[j, 0] + rows
                        ok = (rows < lc[j, 0]) & (dst < hv_cap)
                        tgt = jnp.where(ok, dst, hv_cap)
                        new_w = new_w.at[j].set(
                            new_w[j].at[tgt].set(lw[j], mode="drop")
                        )
                        new_f = new_f.at[j].set(
                            new_f[j].at[tgt].set(lf[j], mode="drop")
                        )
                    hv_w = sel(new_w, hv_w)
                    hv_f = sel(new_f, hv_f)
                    hv_c = sel(hv_c + lc, hv_c)
                    g_hv_c = sel(
                        g_hv_c + jax.lax.psum(lc[:, 0], "shards"), g_hv_c
                    )
                return (
                    lvl + 1,
                    committed + commit.astype(jnp.int32),
                    sel(nf, fr),
                    sel(ne, eb),
                    sel(ncnt, cnt),
                    sel(ntab, tab),
                    sel(ndf, df),
                    sel(ndfp, dfp),
                    ts + jnp.where(commit, ds, 0),
                    tu + jnp.where(commit, du, 0),
                    jnp.stack([t_ovf, f_ovf, r_ovf, c_ovf]),
                    jnp.where(commit, jax.lax.psum(ncnt[0], "shards"), gcount),
                    hv_w,
                    hv_f,
                    hv_c,
                    g_hv_c,
                )

            carry0 = (
                jnp.int32(0),
                jnp.int32(0),
                frontier,
                f_ebits,
                count,
                table,
                disc_found,
                disc_fp,
                jnp.int32(0),
                jnp.int32(0),
                jnp.zeros((4,), jnp.bool_),
                jax.lax.psum(count[0], "shards"),
                jnp.zeros((n_hv, hv_cap, W), jnp.uint32),
                jnp.zeros((n_hv, hv_cap, 2), jnp.uint32),
                jnp.zeros((n_hv, 1), jnp.int32),
                jnp.zeros((n_hv,), jnp.int32),
            )
            out = jax.lax.while_loop(cond, body, carry0)
            # Drop the level counter, the global count and the replicated
            # hv count (the host reads the per-shard counts plane).
            return out[1:11] + out[12:15]

        TL = self._table_len()
        spec_rows = P("shards", None)
        spec_plane = P("shards")
        spec_rep = P()
        return self._shard_map(
            fused,
            in_specs=(
                spec_rows,
                spec_plane,
                spec_plane,
                (spec_plane,) * TL,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
            ),
            out_specs=(
                spec_rep,
                spec_rows,
                spec_plane,
                spec_plane,
                (spec_plane,) * TL,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                spec_rep,
                P(None, "shards", None),  # hv candidate words
                P(None, "shards", None),  # hv candidate fingerprints
                P(None, "shards"),  # hv per-shard counts ([n_hv, D])
            ),
        )

    def _superstep(self):
        key = (self._Fl, self._Cl, self._K)
        fn = self._step_cache.get(key)
        if fn is None:
            fn = self._build_superstep(*key)
            self._step_cache[key] = fn
        return fn

    def _fused(self):
        key = ("fused", self._Fl, self._Cl, self._K)
        fn = self._step_cache.get(key)
        if fn is None:
            fn = self._build_fused(self._Fl, self._Cl, self._K)
            self._step_cache[key] = fn
        return fn

    # --- host materialization ----------------------------------------------

    def _host_read(self, arr) -> np.ndarray:
        """Materialize a (possibly cross-process) sharded device array on
        every host. Single-process: a plain transfer. Multi-process (the
        ``jax.distributed`` DCN path): an allgather of addressable shards —
        ``np.asarray`` alone raises on arrays spanning non-addressable
        devices."""
        import jax

        if jax.process_count() == 1:
            return np.asarray(arr)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))

    def _counts_total(self) -> int:
        """Global frontier size: device-side psum, replicated output, so no
        host ever touches the sharded counts plane directly. The result is
        cached host-side (``_frontier_total_cache``) for passive readers —
        ``metrics()`` must never enqueue device work (a poll from one
        process of a multi-process mesh would desync SPMD program order)."""
        import jax
        import jax.numpy as jnp

        fn = self.__dict__.get("_counts_total_fn")
        if fn is None:
            fn = jax.jit(
                lambda c: jnp.sum(c, dtype=jnp.int32),
                out_shardings=self._rep_sharding,
            )
            self.__dict__["_counts_total_fn"] = fn
        total = int(np.asarray(fn(self._counts)))
        self._frontier_total_cache = total
        return total

    def _parent_map(self):
        """The single-chip walk over a gathered copy of the table planes
        (multi-process safe via ``_host_read``)."""
        from ..native import ParentMap

        return ParentMap(
            self._host_read(self._table.key_hi),
            self._host_read(self._table.key_lo),
            self._host_read(self._table.val_hi),
            self._host_read(self._table.val_lo),
        )

    # --- growth -----------------------------------------------------------

    def _grow_table_if_loaded(self) -> None:
        """Same proactive-growth policy as the single-chip engine
        (xla.py MAX_LOAD_* / SORTED_LOAD_*): hash partitions stay at or
        below 1/4 load so inserts never pay long probe chains; sorted
        partitions run denser (3/4) because their per-level cost is the
        sort of [capacity + batch], not probe rounds. Uniform fingerprint
        ownership keeps per-shard load within noise of the global figure."""
        from ..xla import XlaChecker

        if self._dedup == "hash":
            num, den = XlaChecker.MAX_LOAD_NUM, XlaChecker.MAX_LOAD_DEN
        else:
            # Both sort-based structures take the dense (3/4) rule, and the
            # capacity term mirrors xla.py's ``self._table.capacity``: for
            # the delta structure that includes the delta tier.
            num, den = XlaChecker.SORTED_LOAD_NUM, XlaChecker.SORTED_LOAD_DEN
        cap_l = self._Cl + (self._delta_cap() if self._dedup == "delta" else 0)
        while self._unique_count * den > self._D * cap_l * num:
            self._grow_table()
            cap_l = self._Cl + (
                self._delta_cap() if self._dedup == "delta" else 0
            )

    def _grow_table(self) -> None:
        with self._tracer.span(
            "grow_table", dedup=self._dedup, shards=self._D,
            capacity=self._D * self._Cl * 2,
        ):
            self._grow_table_impl()
        self._counters.inc("table_grows")

    def _grow_table_impl(self) -> None:
        """Double every shard's table partition (ownership is capacity-
        independent, so growth stays shard-local: a plane copy for the
        sorted structure, a rehash for the hash table)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        D, Cl = self._D, self._Cl
        old = self._table
        new_Cl = Cl * 2
        max_probes = self._max_probes

        if self._dedup == "delta":
            dc = self._delta_cap()
            # The minimum delta tier (1024) can out-hold a tiny main
            # partition: the doubled main must fit main + delta.
            new_Cl = 2 * max(Cl, dc)
            new_dc = deltaset._delta_cap(new_Cl)

            def grow_delta_local(planes):
                # Fold delta into a doubled main, shard-locally: one sort
                # of [Cl + dc] (tiers are disjoint, so merged keys are
                # unique); the delta tier resets at its rescaled size.
                mkh, mkl, mvh, mvl, dkh, dkl, dvh, dvl, nm, nd = planes
                full = jnp.uint32(0xFFFFFFFF)
                m_valid = jnp.arange(Cl) < nm[0]
                d_valid = jnp.arange(dc) < nd[0]
                kh = jnp.concatenate(
                    [jnp.where(m_valid, mkh, full), jnp.where(d_valid, dkh, full)]
                )
                kl = jnp.concatenate(
                    [jnp.where(m_valid, mkl, full), jnp.where(d_valid, dkl, full)]
                )
                vh = jnp.concatenate([mvh, dvh])
                vl = jnp.concatenate([mvl, dvl])
                skh, skl, svh, svl = jax.lax.sort((kh, kl, vh, vl), num_keys=2)
                n_new = nm[0] + nd[0]
                row_ok = jnp.arange(Cl + dc) < n_new
                z = jnp.uint32(0)
                pad = jnp.zeros((new_Cl - Cl - dc,), jnp.uint32)
                out = lambda a: jnp.concatenate([jnp.where(row_ok, a, z), pad])
                zd = jnp.zeros((new_dc,), jnp.uint32)
                return (
                    out(skh), out(skl), out(svh), out(svl),
                    zd, zd, zd, zd,
                    n_new[None], jnp.zeros((1,), jnp.int32),
                )

            fn = self._shard_map(
                grow_delta_local,
                in_specs=((P("shards"),) * 10,),
                out_specs=(P("shards"),) * 10,
            )
            planes = fn(tuple(self._table))
            self._table = deltaset.DeltaSet(
                *planes[:8], *(p.reshape(-1) for p in planes[8:])
            )
            self._Cl = new_Cl
            self._cap_hints()["table"] = D * new_Cl
            return

        if self._dedup == "sorted":

            def grow_local(planes):
                kh, kl, vh, vl, n = planes
                pad = jnp.zeros((Cl,), jnp.uint32)
                return (
                    jnp.concatenate([kh, pad]),
                    jnp.concatenate([kl, pad]),
                    jnp.concatenate([vh, pad]),
                    jnp.concatenate([vl, pad]),
                    n,
                )

            fn = self._shard_map(
                grow_local,
                in_specs=((P("shards"),) * 5,),
                out_specs=(P("shards"),) * 5,
            )
            self._table = sortedset.SortedSet(*fn(tuple(old)))
            self._Cl = new_Cl
            self._cap_hints()["table"] = D * new_Cl
            return

        def rehash(old_planes):
            kh, kl, vh, vl = old_planes
            occupied = (kh != 0) | (kl != 0)
            bigger = hashset.make(new_Cl, jnp)
            bigger, _, ovf = hashset.insert(
                bigger, kh, kl, vh, vl, occupied, max_probes=max_probes
            )
            # rank-1 so the per-shard scalar shards over the axis.
            return tuple(bigger), jnp.any(ovf)[None]

        fn = self._shard_map(
            rehash,
            in_specs=((P("shards"),) * 4,),
            out_specs=((P("shards"),) * 4, P("shards")),
        )
        planes, ovf = fn(tuple(old))
        if bool(np.any(self._host_read(ovf))):  # pragma: no cover
            raise RuntimeError("rehash overflow — pathological fingerprint distribution")
        self._table = hashset.HashSet(*planes)
        self._Cl = new_Cl
        self._cap_hints()["table"] = D * new_Cl

    def _grow_route(self) -> None:
        self._counters.inc("route_grows")
        self._K = min(self._Fl * self._A, self._K * 2)
        self._cap_hints()["route"] = self._K

    def _cap_hints(self) -> dict:
        return self._model.__dict__.setdefault(
            "_xla_sharded_cap_hints", {}
        ).setdefault(self._D, {})

    def _grow_frontier(self) -> None:
        self._counters.inc("frontier_grows")
        with self._tracer.span(
            "grow_frontier", shards=self._D, rows=self._D * self._Fl * 2
        ):
            self._grow_frontier_impl()

    def _grow_frontier_impl(self) -> None:
        """Double every shard's frontier rows, shard-locally on device (a
        host round-trip here would stall every growth event at scale)."""
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        Fl, W = self._Fl, self._W
        new_Fl = Fl * 2

        def grow(rows, ebits):
            # Local blocks [Fl, W] / [Fl]: append zero rows per shard.
            return (
                jnp.concatenate([rows, jnp.zeros((Fl, W), jnp.uint32)]),
                jnp.concatenate([ebits, jnp.zeros((Fl,), jnp.uint32)]),
            )

        fn = self._shard_map(
            grow,
            in_specs=(P("shards", None), P("shards")),
            out_specs=(P("shards", None), P("shards")),
        )
        self._frontier, self._frontier_ebits = fn(
            self._frontier, self._frontier_ebits
        )
        self._Fl = new_Fl
        self._cap_hints()["frontier"] = self._D * new_Fl
        local_cand = self._Fl * self._A
        self._K = min(local_cand, max(self._K, (local_cand // self._D) * 4))

    # --- engine ------------------------------------------------------------

    def _run_block(self, max_count: int = 1500) -> None:
        if self._levels_per_dispatch > 1:
            return self._run_block_fused()
        return self._run_block_single()

    def _entry_checks(self) -> bool:
        """Shared dispatch preamble; returns False when nothing to run."""
        import numpy as np

        if self._target_reached or self._exhausted:
            return False
        if self._P > 0 and all(n in self._found_names for n in self._prop_names):
            return False
        if self._counts_total() == 0:
            self._exhausted = True
            return False
        self._max_depth = max(self._max_depth, self._depth)
        if self._target_max_depth is not None and self._depth >= self._target_max_depth:
            # Mirror the single-chip engine: a depth-halted checker reads as
            # frontier-empty to counters and checkpoint consumers alike.
            import jax.numpy as jnp

            self._counts = jnp.zeros_like(self._counts)
            self._exhausted = True
            return False
        return True

    def _raise_codec_overflow(self) -> None:
        raise RuntimeError(
            f"{type(self._model).__name__}: packed-codec capacity "
            "overflow — a reachable successor does not fit the "
            "model's declared field widths/slot counts (see "
            "stateright_tpu.packing)."
        )

    def _pin_found_names(self) -> None:
        found = np.asarray(self._disc_found)
        fps = np.asarray(self._disc_fp)
        for i, name in enumerate(self._prop_names):
            if found[i] and name not in self._found_names:
                self._found_names[name] = (int(fps[i, 0]) << 32) | int(fps[i, 1])

    def _confirm_hv_candidates(self, hv_w, hv_f, hv_c) -> None:
        with self._tracer.span("host_verify"):
            self._confirm_hv_impl(hv_w, hv_f, hv_c)

    def _confirm_hv_impl(self, hv_w, hv_f, hv_c) -> None:
        """Exact host-side re-check of device-flagged candidate states for
        host-verified properties — the single-chip contract
        (xla.py ``_confirm_hv_candidates``) over the mesh's allgathered
        candidate buffers. Confirmation order is shard-major (owner shard
        0's rows first): deterministic, but a different witness tiebreak
        than the single-chip engine's frontier order — the same documented
        divergence as ``pick_discovery``'s pmax election. Zero-fingerprint
        rows are padding (a real state never fingerprints to (0, 0))."""
        counts = self._host_read(hv_c)  # [n_hv, D]
        words = fps = None
        for j, i in enumerate(self._hv_idx):
            prop = self._properties[i]
            if prop.name in self._found_names:
                continue
            total = int(counts[j].sum())
            if total == 0:
                continue
            if words is None:
                words = self._host_read(hv_w)  # [n_hv, D*hv_cap, W]
                fps = self._host_read(hv_f)  # [n_hv, D*hv_cap, 2]
            confirmed = False
            collected = 0
            for r in range(words.shape[1]):
                fp_hi, fp_lo = int(fps[j, r, 0]), int(fps[j, r, 1])
                if fp_hi == 0 and fp_lo == 0:
                    continue
                collected += 1
                state = self._model.unpack(words[j, r])
                holds = bool(prop.condition(self._model, state))
                viol = (not holds) if prop.expectation == Expectation.ALWAYS else holds
                if viol:
                    self._found_names[prop.name] = (fp_hi << 32) | fp_lo
                    confirmed = True
                    break
            if not confirmed and total > collected:
                raise RuntimeError(
                    f"{total} candidate states for host-verified property "
                    f"{prop.name!r} in one super-step, none of the "
                    f"{collected} collected confirmed — tighten the "
                    "conservative device predicate or raise "
                    "spawn_xla(host_verified_cap=...)."
                )

    def _run_block_fused(self) -> None:
        """Up to ``levels_per_dispatch`` BFS levels in one SPMD dispatch
        (see ``_build_fused``); overflow exits commit the non-overflowing
        prefix, grow the overflowing buffer, and re-enter."""
        import jax.numpy as jnp

        if not self._entry_checks():
            return
        budget_left = self._levels_per_dispatch
        if self._target_max_depth is not None:
            budget_left = min(budget_left, self._target_max_depth - self._depth)
        retry = False  # re-entering after an overflow recovery
        while budget_left > 0:
            # Keep the block's int32 generated-state accumulator safe:
            # global candidates per level = D * Fl * A.
            kmax = max(1, (2**31 - 1) // max(self._D * self._Fl * self._A, 1))
            budget = min(budget_left, kmax)
            remaining = 2**31 - 1
            if self._target_state_count is not None:
                remaining = max(
                    1, min(remaining, self._target_state_count - self._state_count)
                )
            host_found = np.array(
                [n in self._found_names for n in self._prop_names], dtype=bool
            )
            n_cached = len(self._step_cache)
            fn = self._fused()
            fresh = len(self._step_cache) > n_cached
            run_rows = self._D * self._Fl
            if self._heartbeat is not None:
                self._heartbeat.beat(
                    "dispatch", compile=fresh, bucket=run_rows,
                    depth=self._depth, states=self._state_count,
                )
            with self._tracer.span(
                "dispatch", flavor="fused", bucket=run_rows,
                cand=self._D * self._K, compile=fresh, retry=retry,
                dedup=self._dedup, compaction="mesh", shards=self._D,
            ) as _sp:
                (
                    committed,
                    nf,
                    ne,
                    ncounts,
                    table,
                    dfound,
                    dfp,
                    tot_states,
                    tot_unique,
                    ovf,
                    hv_w,
                    hv_f,
                    hv_c,
                ) = fn(
                    self._frontier,
                    self._frontier_ebits,
                    self._counts,
                    tuple(self._table),
                    self._disc_found,
                    self._disc_fp,
                    jnp.int32(budget),
                    jnp.int32(remaining),
                    jnp.asarray(host_found),
                )
                committed = int(np.asarray(committed))
                _sp.set(committed=committed)
            self.dispatch_log.append((run_rows, committed))
            retry = False
            self._frontier, self._frontier_ebits = nf, ne
            self._counts = ncounts
            self._table = self._global_table(table)
            self._disc_found, self._disc_fp = dfound, dfp
            self._state_count += int(np.asarray(tot_states))
            self._unique_count += int(np.asarray(tot_unique))
            if self._heartbeat is not None:
                self._heartbeat.commit(
                    depth=self._depth + committed, states=self._state_count
                )
            self._depth += committed
            if committed:
                self._max_depth = max(self._max_depth, self._depth - 1)
            budget_left -= committed
            Cl_before = self._Cl
            self._grow_table_if_loaded()
            grew_proactively = self._Cl > Cl_before
            self._pin_found_names()
            if self._hv_idx:
                self._confirm_hv_candidates(hv_w, hv_f, hv_c)
            # Quiescent point: the committed prefix is fully reflected in
            # host-visible state.
            self._maybe_checkpoint()
            self._maybe_record()
            if (
                self._target_state_count is not None
                and self._state_count >= self._target_state_count
            ):
                self._target_reached = True
                return
            t_ovf, f_ovf, r_ovf, c_ovf = (bool(x) for x in np.asarray(ovf))
            if c_ovf:
                self._raise_codec_overflow()
            if t_ovf:
                # Only grow again if the proactive pass above did not just
                # double past the blockage (see xla.py).
                if not grew_proactively:
                    self._grow_table()
                retry = True
                continue
            if f_ovf:
                self._grow_frontier()
                retry = True
                continue
            if r_ovf:
                self._grow_route()
                retry = True
                continue
            if committed == 0:
                break
            if self._counts_total() == 0:
                break
            if self._P > 0 and all(
                n in self._found_names for n in self._prop_names
            ):
                break

    def _run_block_single(self) -> None:
        import numpy as np

        if not self._entry_checks():
            return
        if self._visitor is not None:
            self._visit_frontier()

        retry = False  # re-running the level after an overflow recovery
        while True:
            n_cached = len(self._step_cache)
            fn = self._superstep()
            fresh = len(self._step_cache) > n_cached
            run_rows = self._D * self._Fl
            if self._heartbeat is not None:
                self._heartbeat.beat(
                    "dispatch", compile=fresh, bucket=run_rows,
                    depth=self._depth, states=self._state_count,
                )
            with self._tracer.span(
                "dispatch", flavor="single", bucket=run_rows,
                cand=self._D * self._K, compile=fresh, retry=retry,
                dedup=self._dedup, compaction="mesh", shards=self._D,
            ) as _sp:
                out = fn(
                    self._frontier,
                    self._frontier_ebits,
                    self._counts,
                    tuple(self._table),
                    self._disc_found,
                    self._disc_fp,
                )
                (nf, ne, ncounts, table, dfound, dfp, d_states, d_unique,
                 t_ovf, f_ovf, r_ovf, c_ovf, hv_w, hv_f, hv_c) = out
                committed = not (
                    bool(np.asarray(t_ovf))
                    or bool(np.asarray(f_ovf))
                    or bool(np.asarray(r_ovf))
                )
                _sp.set(committed=int(committed))
            self.dispatch_log.append((run_rows, int(committed)))
            if self._heartbeat is not None:
                self._heartbeat.commit(
                    depth=self._depth, states=self._state_count
                )
            if bool(np.asarray(c_ovf)):
                self._raise_codec_overflow()
            if bool(np.asarray(t_ovf)):
                self._grow_table()
                retry = True
                continue
            if bool(np.asarray(f_ovf)):
                self._grow_frontier()
                retry = True
                continue
            if bool(np.asarray(r_ovf)):
                self._grow_route()
                retry = True
                continue
            break

        self._frontier, self._frontier_ebits = nf, ne
        self._counts = ncounts
        self._table = self._global_table(table)
        self._disc_found, self._disc_fp = dfound, dfp
        self._state_count += int(np.asarray(d_states))
        self._unique_count += int(np.asarray(d_unique))
        self._depth += 1
        self._grow_table_if_loaded()
        self._pin_found_names()
        if self._hv_idx:
            self._confirm_hv_candidates(hv_w, hv_f, hv_c)
        self._maybe_checkpoint()
        self._maybe_record()
        if (
            self._target_state_count is not None
            and self._state_count >= self._target_state_count
        ):
            self._target_reached = True

    def _visit_frontier(self) -> None:
        """Same visitor truncation contract as the single-chip engine: at
        most ``spawn_xla(visit_cap=...)`` states per level, loud warning."""
        rows = self._host_read(self._frontier).reshape(self._D, self._Fl, self._W)
        counts = self._host_read(self._counts)
        total = int(counts.sum())
        if total > self._visit_cap:
            import warnings

            warnings.warn(
                f"visitor: frontier has {total} states at depth {self._depth};"
                f" visiting only the first {self._visit_cap} (host-side path "
                "reconstruction per state does not scale — use visitors on "
                "small runs, or raise spawn_xla(visit_cap=...))",
                RuntimeWarning,
                stacklevel=2,
            )
        parents = self._parent_map()
        budget = self._visit_cap
        for d in range(self._D):
            for row in rows[d, : counts[d]]:
                if budget <= 0:
                    return
                budget -= 1
                fp = fphash.fingerprint_u64(
                    self._dedup_words_host(row[None, :])[0], np
                )
                self._visitor.visit(self._model, self._path_for(fp, parents))

    # --- Checker API -------------------------------------------------------

    def model(self) -> Model:
        return self._model

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        return self._unique_count

    def max_depth(self) -> int:
        return self._max_depth

    def metrics(self) -> Dict[str, Any]:
        """The mesh engine's unified telemetry snapshot — same contract
        as the single-chip ``XlaChecker.metrics()`` (stable key superset;
        docs/observability.md) plus mesh gauges (``shards``, per-shard
        capacities, route slots). Host-side reads only — frontier_count
        is the cached total from the last engine-driven reduction, never
        a fresh device dispatch (a poll from one process of a
        multi-process mesh would desync SPMD program order)."""
        import jax

        cap = self._D * (
            self._Cl + (self._delta_cap() if self._dedup == "delta" else 0)
        )
        return {
            "engine": "xla-sharded",
            "backend": jax.default_backend(),
            # -- configuration gauges ---------------------------------
            "dedup": self._dedup,
            "compaction": "mesh",
            "symmetry": self._sym_tag,
            "ladder": "none",
            "cand_ladder_k": 1,
            "shrink_exit": False,
            "levels_per_dispatch": self._levels_per_dispatch,
            "checkpoint_to": self._autockpt.path if self._autockpt else None,
            "metrics_to": self._recorder.path if self._recorder else None,
            # -- recovery gauges (docs/observability.md "Recovery") ----
            "resumed_from": self._resumed_from,
            "last_checkpoint_level": (
                self._last_checkpoint["depth"] if self._last_checkpoint else None
            ),
            "shards": self._D,
            "frontier_rows_per_shard": self._Fl,
            "table_slots_per_shard": self._Cl,
            "route_slots": self._K,
            # -- live search gauges -----------------------------------
            "state_count": self._state_count,
            "unique_state_count": self._unique_count,
            "depth": self._depth,
            "max_depth": self._max_depth,
            "frontier_count": self._frontier_total_cache,
            "frontier_capacity": self._D * self._Fl,
            "table_capacity": cap,
            "table_occupancy": self._unique_count / max(cap, 1),
            "dispatches": len(self.dispatch_log),
            "levels_committed": sum(c for _, c in self.dispatch_log),
            "cand_retries": 0,
            "hv": {},
            # -- event counters (obs.Counters, pre-seeded) ------------
            **self._counters.snapshot(),
        }

    def is_done(self) -> bool:
        if self._exhausted or self._target_reached:
            return True
        if self._P > 0 and all(n in self._found_names for n in self._prop_names):
            return True
        return self._counts_total() == 0 and self._state_count > 0

    def discoveries(self):
        parents = self._parent_map()
        return {
            name: self._path_for(fp64, parents)
            for name, fp64 in self._found_names.items()
        }
