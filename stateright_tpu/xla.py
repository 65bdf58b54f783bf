"""The TPU/XLA frontier-expansion checker: ``spawn_xla()``.

This is the engine the framework exists for.  Where the reference explores
the state graph one state at a time across CPU worker threads with a
work-stealing job market (``/root/reference/src/checker/bfs.rs:89-211``), the
XLA checker is *level-synchronous*: the entire BFS frontier is expanded in
one fused device program per super-step —

1. evaluate all property predicates over the frontier (fused, mirroring the
   per-state checks of bfs.rs:279-325),
2. expand every state's full action grid with a vmapped bit-packed
   transition kernel (the traced form of ``actions``+``next_state``,
   bfs.rs:332-333),
3. fingerprint all candidates (two uint32 murmur lanes, the device analogue
   of lib.rs:332),
4. deduplicate against a device-resident open-addressing hash set storing
   predecessor fingerprints (replacing the DashMap of bfs.rs:29-31),
5. detect terminal states for eventually-property counterexamples
   (bfs.rs:374-381), and
6. stream-compact the surviving states into the next frontier.

Only a handful of scalars (frontier count, discovery flags, overflow flags)
cross back to the host per super-step; witness paths are reconstructed from
the device parent table only on demand, by forward re-execution (the TLC
technique the reference uses, path.rs:20-97).

Work distribution needs no job market: the frontier array IS the work queue,
and every core processes it data-parallel.  Multi-chip scaling shards the
frontier and hash set by fingerprint ownership over a ``jax.sharding.Mesh``
(see ``stateright_tpu/parallel``).

## PackedModel protocol

A model checkable by this engine exposes its transition system as fixed-width
kernels over bit-packed uint32 state words:

- ``state_words: int`` — W, uint32 lanes per state.
- ``max_actions: int`` — A, static action-slot count.
- ``packed_init() -> np.ndarray[N0, W]`` — packed initial states.
- ``packed_step(words[W]) -> (next[A, W], valid[A])`` — the full action
  fan-out of one state; jnp-traceable.  ``valid=False`` covers disabled
  actions, ``next_state -> None`` no-ops, and boundary exclusion
  (bfs.rs:333-336 collapse into one mask).
- ``packed_properties(words[W]) -> bool[P]`` — property conditions, ordered
  as ``properties()``.
- ``pack(state) / unpack(words)`` — host codec between object states and
  packed words (used for witness reconstruction and the Explorer).
- ``packed_representative(words[W]) -> words[W]`` — optional, for symmetry
  reduction: the device form of ``Representative`` (representative.rs:65).
- ``host_verified_properties: frozenset[str]`` — optional. Properties whose
  exact condition cannot run on device (the linearizability testers'
  backtracking search, linearizability.rs:197-284). For these the
  ``packed_properties`` entry is a *conservative* predicate — it may be
  False (a candidate violation for ``always`` / candidate example for
  ``sometimes``... the polarity of "suspicious") only when the exact
  answer might disagree with the safe default, and must be exact in the
  other direction. The engine compacts candidate states into a small
  buffer per super-step and re-evaluates them on the host with the
  property's exact object-level condition (memoized serializer) before
  recording a discovery — SURVEY §7 M4 variant (a).
- ``property_block_rows: int`` — optional. Rows per block of the property
  stage, for models whose ``packed_properties`` needs memory per row (the
  device serializer of ``semantics.device``). Where a bucket is wider, the
  planes superstep evaluates the properties in blocks of this many rows,
  over the blocks that hold live rows only (``blocked_properties``).
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import obs
from .checker.base import Checker
from .checker.path import Path
from .core import Expectation, Model
from .ops import deltaset, fphash, hashset, sortedset

#: Counter names every engine seeds (stable ``metrics()`` key set across
#: dedup structures and runs that never grow; docs/observability.md).
ENGINE_COUNTERS = (
    "table_grows",
    "frontier_grows",
    "cand_grows",
    "delta_flushes",
    "shrink_exits",
    "ladder_jumps",
    "checkpoints_written",
)


#: The PackedModel protocol surface (module docstring above).
PACKED_ATTRS = (
    "state_words",
    "max_actions",
    "packed_init",
    "packed_step",
    "packed_properties",
)


def blocked_properties(props_fn, frontier, f_count, block: int, neutral):
    """``vmap(props_fn)`` over the first ``f_count`` rows of ``frontier``
    only, ``block`` rows at a time: a device loop over ``ceil(f_count /
    block)`` blocks, each a dynamic slice in and a dynamic update out.
    Every row past ``f_count`` reads ``neutral`` (bool[P], the value that
    flags nothing); blocks past the live rows are never evaluated."""
    import jax
    import jax.numpy as jnp

    F = frontier.shape[0]

    def body(i, out):
        start = i * block
        rows = jax.lax.dynamic_slice_in_dim(frontier, start, block)
        props = jax.vmap(props_fn)(rows).astype(out.dtype)
        return jax.lax.dynamic_update_slice_in_dim(out, props, start, axis=0)

    out = jnp.broadcast_to(neutral, (F, neutral.shape[0]))
    out = jax.lax.fori_loop(0, (f_count + block - 1) // block, body, out)
    live = jnp.arange(F) < f_count
    return jnp.where(live[:, None], out, neutral)


def property_rows(frontier: int, bucket: int, block: int) -> int:
    """Rows the property stage evaluates in one level of ``frontier`` live
    rows at ``bucket``: whole blocks over the live rows where the stage
    runs blocked (``0 < block < bucket``), else the whole bucket."""
    if 0 < block < bucket:
        return -(-frontier // block) * block
    return bucket


def is_packed(model: Model) -> bool:
    """Whether ``model`` implements the PackedModel protocol (and so can
    run on the device engines)."""
    return all(hasattr(model, attr) for attr in PACKED_ATTRS)


def _require_packed(model: Model) -> None:
    missing = [
        attr
        for attr in PACKED_ATTRS
        if not hasattr(model, attr)
    ]
    if missing:
        raise TypeError(
            f"spawn_xla() requires the PackedModel protocol; {type(model).__name__} "
            f"is missing {missing}. See stateright_tpu.xla for the contract."
        )


def accel_auto_compaction(state_words: int) -> str:
    """The planes-compaction mode the ACCELERATOR auto-policy resolves
    for a model width (the round-5 on-chip verdict: sort-family
    compaction wins at narrow W; a wide-W sort compaction is a W+3
    operand ``lax.sort`` whose XLA:TPU compile stalls). ONE definition —
    ``XlaChecker.__init__`` resolves through it, and stpu-lint
    (``analysis/surfaces.py``) traces the program it names so STPU003
    checks the sort widths the chip actually runs; a threshold change
    here re-aims both."""
    return "gather" if state_words > 8 else "sort"


#: The sort compaction recovers candidate parents by merge (two sorts and
#: a prefix sum, ``parents_by_merge``) where it compacts at least this many
#: candidate lanes, and by three gathers below. On a TPU v5e a gathered
#: element costs 8-23 ns and a sorted lane-operand about 1 ns, but each
#: sort and scan compiles to megabytes of code for its shape, and that code
#: stays resident in device memory beside the data (PERF.md §6, PR 24).
#: Below 2^20 lanes the gathers cost under about 18 ms a level.
PARENT_MERGE_MIN = 1 << 20


def parent_lowering(compaction: str, f_cap: int, cand_cap: int, max_actions: int) -> str:
    """How the plane-major grid compaction at these shapes recovers each
    candidate's parent fingerprint and ebits: "merge" or "gather"."""
    take = min(cand_cap, f_cap * max_actions)
    return "merge" if compaction == "sort" and take >= PARENT_MERGE_MIN else "gather"


# --- the ladder/rung planner, as shared pure functions ----------------------
#
# The compile-shape schedule — which run buckets the ladder can land on,
# how big each bucket's candidate buffer starts, and which sub-width rungs
# a fused program specialises — used to live only inside XlaChecker
# methods, readable by nothing but a live checker. These module-level
# functions are the ONE definition: the engine delegates to them
# (``_run_cap_for`` / ``_default_cand_cap`` / ``_cand_rungs``), and
# stpu-lint's compile-plan census (``analysis/census.py``, STPU007)
# enumerates them statically, the same way ``accel_auto_compaction``
# already re-aims both the engine and STPU003. A planner change here
# re-aims the census, the warm-cache set, and the engine together.

#: The bucket ladder's floor (see ``_run_cap_for``'s docstring: the
#: round-3 deep-narrow finding — ABD never widens past 54 rows, so a
#: 1024-row floor paid a ~1000x action-grid padding tax per level).
RUN_BUCKET_FLOOR = 64

#: In-program candidate-ladder rung floor: sub-widths below this gain
#: nothing (buckets <= 256 run full-grid candidate buffers and their
#: sorts are batch-trivial) while every rung is a full superstep traced
#: into the fused program — compile cost, not savings.
CAND_RUNG_FLOOR = 256

#: The in-program candidate-ladder depth "auto" resolves to on the
#: planes engine (``XlaChecker.__init__``; the rows/hash engine has no
#: candidate-scale sorts to snug and stays at 1).
CAND_LADDER_AUTO_K = 3


def auto_dedup(backend: str) -> str:
    """The visited-set structure "auto" resolves to per backend (the
    round-5 cost model: scatter-election hash insert is the TPU
    bottleneck, sort-merge wins there; hash + scatter wins on CPU).
    Shared with the census so the warm set prices the structure the
    engine will actually run."""
    return "hash" if backend == "cpu" else "sorted"


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def ladder_buckets(frontier_capacity: int) -> List[int]:
    """Every run bucket the ladder can land on under a frontier-capacity
    ceiling: powers of four from ``RUN_BUCKET_FLOOR``, with the ceiling
    itself as the (possibly non-power-of-four) top rung — exactly the
    values ``_run_cap_for``/``_grow_frontier`` can return before a
    growth event doubles the ceiling. Each distinct bucket is a separate
    XLA compilation; ``len(ladder_buckets(F))`` is therefore the
    compile-shape count a run plan commits to (the STPU007 budget's
    subject)."""
    out = [min(RUN_BUCKET_FLOOR, frontier_capacity)]
    while out[-1] < frontier_capacity:
        out.append(min(out[-1] * 4, frontier_capacity))
    return out


def default_cand_cap(
    run_cap: int,
    max_actions: int,
    backend: str,
    env: Optional[Dict[str, str]] = None,
) -> int:
    """The candidate-buffer capacity a so-far-unseen bucket starts at
    (before any cc_ovf growth): the full action grid for small buckets,
    a power-of-two fraction of it above (CPU m/4, accelerators m/16 —
    per-level cost there scales with sorted lane-words, round-5
    profile). ``env`` defaults to ``os.environ`` (the STPU_CAND_FRAC A/B
    knob); pass ``{}`` for the hermetic census."""
    e = os.environ if env is None else env
    m = run_cap * max_actions
    if run_cap <= 256:
        # Small buckets take the FULL grid: compaction saves nothing at
        # this scale, and an undersized buffer costs a cc_ovf -> grow ->
        # fresh-XLA-compile round per growth.
        cap = _next_pow2(m)
    else:
        den = int(e.get("STPU_CAND_FRAC", "4" if backend == "cpu" else "16"))
        cap = max(1024, _next_pow2(max(m // den, 1)))
    return min(cap, _next_pow2(m))


def cand_rungs(
    f_cap: int,
    cand_cap_of: Callable[[int], int],
    k: int,
    floor: int = CAND_RUNG_FLOOR,
) -> List[Tuple[int, int]]:
    """The in-program candidate ladder for a fused dispatch at bucket
    ``f_cap``: ascending ``[(F_k, C_k)]`` sub-width shapes, last = the
    full bucket. ``cand_cap_of`` maps a bucket to its candidate cap (a
    live checker passes its learned-cap lookup; the census passes
    :func:`default_cand_cap`)."""
    full = (f_cap, cand_cap_of(f_cap))
    if k <= 1:
        return [full]
    rungs = [full]
    Fk = f_cap
    while len(rungs) < k:
        Fk //= 4
        if Fk < floor:
            break
        # Monotone envelope: a cc_ovf growth at a SMALL bucket (its own
        # host dispatches) can push that bucket's learned cap past a
        # bigger bucket's — unclamped, the "snug" rung would then sort a
        # WIDER candidate buffer than the branch above it, inverting the
        # ladder's savings while the telemetry reports the inflated cap
        # as snug. Clamp each rung to the next rung up; an undersized
        # clamp only costs the in-program fall-through, never a dropped
        # candidate.
        rungs.append((Fk, min(cand_cap_of(Fk), rungs[-1][1])))
    rungs.reverse()
    return rungs


def capacity_hints(model: Model) -> Dict[str, int]:
    """Capacities learned from growth events in earlier single-chip checks
    of ``model`` (empty if none grew). Hints auto-apply only to DEFAULT
    capacities; a caller that passes explicit capacities may merge these in
    to pre-size a fresh run — but note that repeated runs that want the
    COMPILE cache warm should pass identical capacities instead, replaying
    the first run's (shape, bucket) schedule (every grown capacity is a new
    array shape, i.e. a recompile; bench.py's warm/measured passes)."""
    out: Dict[str, int] = {}
    table_hints = [
        v
        for k, v in model.__dict__.items()
        if k.startswith("_xla_table_cap_hint_")
    ]
    if table_hints:
        out["table_capacity"] = max(table_hints)
    if "_xla_frontier_cap_hint" in model.__dict__:
        out["frontier_capacity"] = model.__dict__["_xla_frontier_cap_hint"]
    return out


class XlaChecker(Checker):
    """Level-synchronous BFS on an accelerator. One ``_run_block`` = one
    frontier super-step (one BFS level)."""

    def __init__(
        self,
        builder,
        *,
        frontier_capacity: Optional[int] = None,
        table_capacity: Optional[int] = None,
        max_probes: int = 32,
        host_verified_cap: int = 128,
        visit_cap: int = 4096,
        levels_per_dispatch: int = 32,
        checkpoint: Optional[str] = None,
        checkpoint_to: Optional[str] = None,
        checkpoint_every: Any = None,
        checkpoint_keep: Optional[int] = None,
        dedup: str = "auto",
        compaction: str = "auto",
        ladder: str = "auto",
        shrink_exit: str = "auto",
        cand_ladder: Any = "auto",
        symmetry: Any = None,
        trace: Any = None,
        heartbeat: Any = None,
        metrics_to: Any = None,
        metrics_every: Any = None,
        metrics_keep: Optional[int] = None,
    ):
        import jax

        model = builder._model
        _require_packed(model)
        self._model = model
        self._jax = jax
        # Observability (stateright_tpu/obs, docs/observability.md): a
        # span tracer (NULL_TRACER when off — no clocks, no I/O), a
        # heartbeat writer (None when off), a metrics time-series
        # recorder (None when off — sampled only at quiescent superstep
        # boundaries), and the event-counter half of metrics(). All
        # host-side; never a device sync.
        self._tracer = obs.resolve_tracer(trace)
        # One span over the set-up below: the host-side configuration,
        # the empty visited set, the init insert and the first frontier.
        with self._tracer.span("spawn"):
            self._heartbeat = obs.resolve_heartbeat(heartbeat)
            self._recorder = obs.resolve_recorder(
                metrics_to, metrics_every, metrics_keep
            )
            self._counters = obs.Counters(ENGINE_COUNTERS)
            # Recovery surface (stateright_tpu/checkpoint.py): in-loop
            # auto-checkpointing at superstep boundaries (the quiescent
            # points), plus the resume-provenance gauges metrics() reports.
            from .checkpoint import AutoCheckpointer

            self._autockpt = AutoCheckpointer.resolve(
                checkpoint_to, checkpoint_every, checkpoint_keep
            )
            self._last_checkpoint: Optional[Dict[str, Any]] = None
            self._resumed_from: Optional[str] = checkpoint
            # Symmetry reduction (stateright_tpu/sym, docs/symmetry.md):
            # resolve the spawn_xla(symmetry=) / STPU_SYMMETRY knob against
            # the builder request and the model's capability. When on, the
            # frontier canonicalizes through either the spec-compiled
            # scatter-free kernel (tag "spec:<hash>") or the model's
            # hand-written packed_representative; unsupported paths raise
            # SymmetryUnsupported instead of silently exploring full-space.
            from .sym import SymmetryUnsupported, resolve_symmetry

            _sym = resolve_symmetry(
                symmetry, builder._symmetry is not None, model, engine="xla"
            )
            self._symmetry = _sym.enabled
            self._sym_tag = _sym.tag
            self._sym_canon = _sym.device_canon
            self._sym_canon_host = _sym.host_canon
            if self._symmetry and getattr(model, "host_verified_properties", ()):
                # The hv fallback re-runs exact host predicates on CONCRETE
                # candidate states; a symmetry-reduced frontier only surfaces
                # one member per class, so an asymmetric hv property could
                # silently miss its witness. Typed refusal, not silent
                # wrongness.
                raise SymmetryUnsupported(
                    "xla",
                    f"{type(model).__name__} declares host_verified_properties; "
                    f"the host-verified fallback evaluates concrete states and "
                    f"cannot honor a symmetry-reduced frontier",
                )
            self._target_state_count: Optional[int] = builder._target_state_count
            self._target_max_depth: Optional[int] = builder._target_max_depth
            self._visitor = builder._visitor
            self._properties = model.properties()
            self._prop_names = [p.name for p in self._properties]
            # Eventually-property bit assignment: position among the eventually
            # subset (checker.rs:540-547).
            self._ebit_of_prop: Dict[int, int] = {}
            for i, p in enumerate(self._properties):
                if p.expectation == Expectation.EVENTUALLY:
                    self._ebit_of_prop[i] = len(self._ebit_of_prop)
            self._ebits0 = (1 << len(self._ebit_of_prop)) - 1

            # Visited-set structure. The cost model measured on an earlier chip setup (to be re-measured, ROADMAP S3) showed
            # the scatter-election hash insert is the TPU bottleneck (0.24 M
            # ins/s at 2^22) while sort runs at ~1.3 G keys/s, and that stream
            # compaction inverts the same way (gather 3x over scatter) — so
            # accelerators default to the sort-merge set + gather compaction
            # (ops/sortedset.py) and CPUs keep the hash set + scatter compaction
            # that wins there.
            # A planes-only compaction request (explicit arg or the
            # STPU_COMPACTION env A/B knob behind "auto") re-aims the dedup
            # auto: "bsearch"/"pallas" exist only in the plane-major engine,
            # and resolving dedup to hash-on-CPU first would reject the
            # combination the caller asked for (the r5e watcher's CPU
            # fallback died exactly there).
            requested_compaction = (
                os.environ.get("STPU_COMPACTION") or "auto"
                if compaction == "auto"
                else compaction
            )
            if dedup == "auto":
                dedup = (
                    "sorted"
                    if requested_compaction in ("bsearch", "pallas")
                    else auto_dedup(jax.default_backend())
                )
            if dedup not in ("hash", "sorted", "delta"):
                raise ValueError(
                    f"dedup must be 'auto', 'hash', 'sorted', or 'delta': {dedup!r}"
                )
            self._dedup = dedup
            self._ds = {"hash": hashset, "sorted": sortedset, "delta": deltaset}[dedup]
            # Structure-of-arrays state layout rides with the sorted (accelerator)
            # structure: XLA:TPU tiles the minor two dims of every buffer to
            # (8, 128), so a [N, W] row-major frontier with W=2 pads 2 lanes to
            # 128 — a ~64x memory-traffic blowup on every elementwise op and
            # gather over packed states. Plane-major [W, N] buffers keep N on
            # the 128-lane axis. The planes superstep preserves the rows
            # superstep's semantics bit-for-bit (candidates are restored to
            # state-major order before the insert's winner election).
            self._soa = dedup != "hash"
            # Planes-compaction lowering: "gather" computes the permutation
            # once (one small sort) and gathers every plane by it; "sort"
            # carries the state planes as sort payload operands and, from
            # PARENT_MERGE_MIN candidate lanes, recovers the parents by
            # merge rather than by gather; "bsearch" replaces the
            # permutation sort with cumsum + rank binary-search + ascending
            # gathers. The round-5 on-chip A/Bs settled the hardware
            # question per shape class:
            #   - narrow-W (2pc W=2, rm=8): sort 8.8s vs gather 15.6s vs
            #     bsearch 29.0s measured — random gathers are the dominant
            #     per-level cost (10-20x a sorted lane-operand per element
            #     on a TPU v5e, PERF.md §5) and sorting wins;
            #   - wide-W (paxos W=25): the sort-mode grid compaction becomes
            #     a lax.sort of 1+W operands or more, whose XLA:TPU
            #     *compile* stalled for tens of minutes (two bench workers
            #     in a row), while gather compiles in ~2 min and measures
            #     fastest (3.2s vs bsearch 4.6s);
            #   - 1-core CPU: gather wins everywhere (round-3 model).
            # So "auto" resolves per backend AND per model width: sort-family
            # compaction only where its operand count stays small.
            # STPU_COMPACTION still makes the A/B a process restart.
            if compaction == "auto":
                compaction = os.environ.get("STPU_COMPACTION") or (
                    "gather"
                    if jax.default_backend() == "cpu"
                    else accel_auto_compaction(model.state_words)
                )
            # "pallas": the state-major layout of "bsearch" with the
            # compaction itself as a sequential-grid pallas streaming kernel
            # (ops/pallas_compact.py) — O(n) data movement instead of the
            # sort's O(n log^2 n). Opt-in until chip-proven; small shapes
            # (bucket below the kernel block) fall back to the stable sort
            # inside compact_1d, bit-identically.
            if compaction not in ("gather", "sort", "bsearch", "pallas"):
                raise ValueError(
                    "compaction must be 'auto', 'gather', 'sort', "
                    f"'bsearch', or 'pallas': {compaction!r}"
                )
            if compaction in ("bsearch", "pallas") and not self._soa:
                # (bsearch included: the rows superstep never consults the
                # compaction knob, and silently measuring the hash engine
                # under an STPU_COMPACTION=bsearch A/B would mislabel the
                # banked numbers.)
                raise ValueError(
                    f"compaction={compaction!r} runs in the plane-major "
                    "engine: pass dedup='sorted' or 'delta' (the hash "
                    "engine is the rows path)"
                )
            self._compaction = compaction
            # Bucket-ladder policy. "ramp" steps one power-of-four rung per
            # frontier overflow — for a space that widens to 2^19 that is 8
            # separate XLA compiles of the full superstep program, and compile
            # time is large (~10 s each on 1-core CPU; tens of seconds to
            # minutes from the TPU compiler, growing with the sort sizes), so
            # the ramp IS the warm-pass cost for ramping spaces (paxos warm
            # 47 s, 4 buckets, on CPU). "jump" extrapolates the observed level
            # growth to skip rungs (see _grow_frontier) and prefers an
            # already-compiled bucket over compiling a snug one
            # (_run_cap_for); padding a level costs milliseconds, a fresh
            # compile costs far more. The "jump" default was measured on an
            # earlier chip setup and is to be re-measured (ROADMAP S5). Counts
            # are bucket-independent; STPU_LADDER makes the A/B a process
            # restart.
            if ladder == "auto":
                ladder = os.environ.get("STPU_LADDER", "jump")
            if ladder not in ("jump", "ramp"):
                raise ValueError(f"ladder must be 'auto', 'jump', or 'ramp': {ladder!r}")
            self._ladder = ladder
            # Tail shrink-exit policy. The downshift is a pure host-side
            # dispatch decision — the threshold rides into the compiled
            # program as a runtime scalar — so this knob never costs a
            # compile. "auto": on for CPU, off for accelerators. Each tail
            # downshift is an extra host round-trip, and on an earlier chip
            # setup, whose round-trips were slow, the rm=8 A/B measured the
            # ~7 tail round-trips at ~1.1 s against ~0.15 s of grid-sort
            # savings (2.13 M -> 1.88 M gen/s, same schedule, same counts);
            # on 1-core CPU dispatch is ~free and the snug tail sorts won
            # (rm=6 ramp tail 16384 -> 4096 -> 1024 -> 256). The "off"
            # default on accelerators is to be re-measured on a TPU v5e
            # (ROADMAP S5), where round-trips may be far cheaper.
            # STPU_SHRINK_EXIT makes the A/B a process restart.
            if shrink_exit == "auto":
                shrink_exit = os.environ.get("STPU_SHRINK_EXIT") or (
                    "on" if jax.default_backend() == "cpu" else "off"
                )
            if shrink_exit not in ("on", "off"):
                raise ValueError(
                    f"shrink_exit must be 'auto', 'on', or 'off': {shrink_exit!r}"
                )
            self._shrink_exit = shrink_exit == "on"
            # In-program candidate-width ladder (delivered IN-PROGRAM per the
            # shrink-exit lesson: on the earlier chip setup any scheme that
            # added host dispatches to the tail paid ~150 ms per round-trip,
            # so snug candidate sorts ride inside the fused
            # ``lax.while_loop``). Fused dispatches
            # branch via ``lax.switch`` over up to K sub-width supersteps —
            # each rung is the (frontier rows, candidate cap) shape a smaller
            # host bucket would run, specialised into the peak program — so a
            # narrow level's candidate-scale sorts (the [table ‖ cand] insert
            # merge, the frontier compaction) and its grid-scale compaction
            # all run snug with ZERO added host round-trips. Branch selection
            # is on-device (see _build_fused); an underestimate falls through
            # to the full-width branch in-program, never dropping candidates.
            # "auto" = STPU_CAND_LADDER or 3 (on for CPU and accelerators —
            # the savings are in-program, so there is no RTT trade); 1
            # disables (one branch = the pre-ladder program, byte-for-byte).
            # Each rung is a full superstep trace, so K bounds the fused
            # program's compile cost (~11 s/bucket baseline on 1-core CPU,
            # measured on CPU). Planes engine only: the rows/hash superstep
            # has no candidate-scale sorts to snug.
            explicit_cand_ladder = cand_ladder != "auto"
            env_cand_ladder = bool(os.environ.get("STPU_CAND_LADDER"))
            if cand_ladder == "auto":
                cand_ladder = os.environ.get("STPU_CAND_LADDER") or str(
                    CAND_LADDER_AUTO_K
                )
            try:
                ladder_k = int(cand_ladder)
            except (TypeError, ValueError):
                raise ValueError(
                    f"cand_ladder must be 'auto' or an int in 1..3: {cand_ladder!r}"
                ) from None
            if not 1 <= ladder_k <= 3:
                raise ValueError(f"cand_ladder must be in 1..3: {ladder_k}")
            if ladder_k > 1 and not self._soa:
                if explicit_cand_ladder:
                    raise ValueError(
                        "cand_ladder runs in the plane-major engine: pass "
                        "dedup='sorted' or 'delta' (the hash engine's rows "
                        "superstep has no candidate-scale sorts to snug)"
                    )
                if env_cand_ladder:
                    # Only an explicit env A/B request warns; the default
                    # auto→3 resolving to 1 on the hash engine is the normal
                    # CPU configuration, not a misconfiguration.
                    import warnings

                    warnings.warn(
                        "STPU_CAND_LADDER has no effect with dedup='hash' "
                        "(rows-major superstep); the knob applies to the "
                        "sorted/delta planes engine only",
                        RuntimeWarning,
                        stacklevel=3,
                    )
                ladder_k = 1
            self._cand_ladder_k = ladder_k
            #: In-program fall-throughs (snug branch overflowed, level re-ran
            #: at full width inside the same dispatch) — the ladder's only
            #: waste case, observable for tests and the A/B harness.
            self.cand_retries = 0
            # Expand-stage layout (an A/B knob
            # for the chip window). "rows" materializes the [F, A, W] grid the
            # vmap naturally produces, then transposes to [W, A*F] planes —
            # the intermediate has W=2 on the minor axis, i.e. the (8,128)
            # tiling tax on its full traffic. "planes" asks the vmap to emit
            # [A, W, F] directly (out_axes=2), keeping F minor throughout —
            # no padded intermediate. NOT default anywhere: a transpose fused
            # INTO a vmapped kernel is the exact shape XLA:CPU (jax 0.9.0)
            # miscompiles (_build_superstep_planes docstring), so "planes" is
            # for accelerator A/Bs guarded by count_ok + the table audit.
            expand_layout = os.environ.get("STPU_EXPAND_LAYOUT", "rows")
            if expand_layout not in ("rows", "planes"):
                raise ValueError(
                    f"STPU_EXPAND_LAYOUT must be 'rows' or 'planes': {expand_layout!r}"
                )
            if expand_layout == "planes" and not self._soa:
                # The knob only exists in the planes superstep; an A/B run on
                # the rows-major (hash-dedup) builder would silently measure
                # two identical programs.
                import warnings

                warnings.warn(
                    "STPU_EXPAND_LAYOUT=planes has no effect with dedup='hash' "
                    "(rows-major superstep); the knob applies to the "
                    "sorted/delta planes engine only",
                    RuntimeWarning,
                    stacklevel=3,
                )
            self._expand_layout = expand_layout

            self._max_probes = max_probes
            self._W = model.state_words
            self._A = model.max_actions
            self._P = len(self._properties)
            # Rows per block of the planes superstep's property stage where
            # a bucket is wider (``blocked_properties``); 0 evaluates the
            # whole bucket in one vmap. The rows superstep never blocks.
            self._prop_block = (
                int(getattr(model, "property_block_rows", 0) or 0) if self._soa else 0
            )
            # Host-verified properties: device flags candidates, host confirms
            # with the exact object-level condition (see module docstring).
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
            # Candidate rows per super-step per host-verified property;
            # spawn_xla(host_verified_cap=...) raises it for models whose
            # conservative predicates flag wide swaths of the frontier.
            self._hv_cap = host_verified_cap
            # Per-level ceiling on host-side visitor path reconstruction.
            self._visit_cap = visit_cap
            # BFS levels fused into one device dispatch. Each host round-trip
            # costs real latency, so the level loop runs *on device* in a ``lax.while_loop`` that exits
            # early on frontier exhaustion, overflow, discovery resolution, or
            # a state-count target — semantically identical to dispatching one
            # level at a time, at level granularity. Visitors force 1 (they
            # need the host between levels). The default of 32 was measured on
            # an earlier chip setup and is to be re-measured (ROADMAP S5).
            self._levels_per_dispatch = (
                1 if self._visitor is not None else max(1, levels_per_dispatch)
            )

            # --- device state ------------------------------------------------
            import jax.numpy as jnp

            self._disc_found = jnp.zeros(self._P, jnp.bool_)
            self._disc_fp = jnp.zeros((self._P, 2), jnp.uint32)
            self._found_names: Dict[str, int] = {}  # name -> fp64, pinned on first find
            self._target_reached = False
            # Compiled supersteps are a property of the MODEL (its kernels and
            # properties), not of one checker run — cache on the model instance
            # so repeated checks (bench warm/measure passes, retries) reuse
            # compilations instead of paying a fresh XLA compile per bucket.
            self._superstep_cache: Dict[Any, Any] = model.__dict__.setdefault(
                "_xla_superstep_cache", {}
            )

            # Candidate-cap sizing is PER-CHECKER state seeded from per-model
            # hints: the old model-level dict let two live checkers over one
            # model object resize each other's candidate buffers mid-run
            # (latent aliasing — a cc_ovf growth in checker A silently changed
            # checker B's bucket shapes and evicted its compiled programs).
            # Growths still write back to the model hint dict, so a FRESH
            # checker (the bench measured pass) inherits learned caps and
            # replays the warm pass's shapes instead of re-paying cc_ovf
            # growth compiles.
            self._cand_caps: Dict[int, int] = dict(
                model.__dict__.get("_xla_cand_cap_hints", {})
            )
            # Live-checker registry (weakrefs): _grow_cand_cap consults it so
            # a growth in this checker never evicts shared compiled programs
            # a live sibling still sizes at the old cap.
            import weakref

            live = model.__dict__.setdefault("_xla_live_checkers", [])
            live[:] = [r for r in live if r() is not None]
            live.append(weakref.ref(self))

            # Capacities learned by earlier checkers of this model (growth
            # events) — starting there skips the rehash-and-rerun the previous
            # run already paid (bench warm pass learns, measured pass reuses).
            # Hints apply only when the caller took the defaults: an explicit
            # capacity — even a smaller one, e.g. to exercise the growth path —
            # must win over cross-checker state.
            self._table_hint_key = f"_xla_table_cap_hint_{dedup}"
            if table_capacity is None:
                table_capacity = max(
                    1 << 20, model.__dict__.get(self._table_hint_key, 0)
                )
            if frontier_capacity is None:
                frontier_capacity = max(
                    1 << 15, model.__dict__.get("_xla_frontier_cap_hint", 0)
                )

            # Per-level telemetry ({depth, frontier, generated, unique} per
            # committed BFS level) — populated by both dispatch paths so fused
            # dispatch does not cost consumers (bench_detail.json) the
            # per-level breakdown.
            self.level_log: List[Dict[str, int]] = []
            # Dispatch telemetry — ONE shape for every engine and dispatch
            # flavor (pinned by tests/test_obs.py, documented in
            # docs/observability.md): one ``(run_cap, committed_levels)``
            # tuple per device call, where ``committed_levels`` is the number
            # of BFS levels that call committed. The one-level path therefore
            # records 0 or 1 (0 = an overflow retry of the same level); a
            # fused block records 0..levels_per_dispatch. Invariant on both:
            # ``sum(committed for _, committed in dispatch_log) ==
            # len(level_log)``. Makes the bucket ladder's choices (jump
            # rungs, tail shrink-exits, lpd=1 snug picks) observable to
            # tests, metrics(), and the superstep profiler.
            self.dispatch_log: List[Tuple[int, int]] = []
            # Host-verified-path telemetry (the sampled-predicate cliff,
            # the hv cost question): how much the conservative device predicate
            # over-flags and what the exact host confirmations cost.
            #   flagged      rows the device pass could not clear (sum of
            #                per-superstep candidate counts, pre-cap)
            #   host_checked rows the host serializer actually re-checked
            #   cleared      checked rows that proved serializable (= the
            #                predicate's false alarms, pure overhead)
            #   confirmed    checked rows that confirmed a discovery
            #   host_sec     wall-clock spent in exact host confirmation
            self.hv_stats: Dict[str, float] = {
                "flagged": 0, "host_checked": 0, "cleared": 0,
                "confirmed": 0, "host_sec": 0.0,
            }

            if checkpoint is not None:
                # Skip init seeding entirely; _restore builds the whole state.
                self._frontier_capacity = max(frontier_capacity, 16)
                self._table = self._ds.make(table_capacity, jnp)
                self._restore(checkpoint)
                if self._autockpt is not None:
                    self._autockpt.arm(self._depth)
                if self._recorder is not None:
                    self._recorder.arm(self._depth)
                return

            init_packed = np.asarray(model.packed_init(), dtype=np.uint32)
            # Boundary filter on init states (bfs.rs:52-56) is the model's
            # responsibility at packed_init time; the object-level default
            # applies it here for safety.
            keep = [model.within_boundary(model.unpack(row)) for row in init_packed]
            init_packed = init_packed[keep]
            n_init = len(init_packed)

            self._frontier_capacity = max(frontier_capacity, 1 << max(n_init.bit_length(), 4))
            with self._tracer.span("spawn:table"):
                self._table = self._ds.make(table_capacity, jnp)
            # Insert init fingerprints with a zero parent (the "no predecessor"
            # marker, like the None predecessor of bfs.rs:59-65).
            with self._tracer.span("spawn:init_insert"):
                dedup_init = self._dedup_words_host(init_packed)
                ihi, ilo = fphash.fingerprint_words(dedup_init, np)
                self._table, is_new, ovf = self._ds.insert(
                    self._table,
                    jnp.asarray(ihi),
                    jnp.asarray(ilo),
                    jnp.zeros(n_init, jnp.uint32),
                    jnp.zeros(n_init, jnp.uint32),
                    jnp.ones(n_init, jnp.bool_),
                    max_probes=self._max_probes,
                )
                if bool(np.any(np.asarray(ovf))):  # pragma: no cover - tiny tables only
                    raise RuntimeError("visited-set overflow while inserting init states")
                n_unique_init = int(np.sum(np.asarray(is_new)))

            with self._tracer.span("spawn:frontier"):
                self._frontier = self._pad_rows(init_packed, self._frontier_capacity)
                self._frontier_ebits = jnp.where(
                    jnp.arange(self._frontier_capacity) < n_init, jnp.uint32(self._ebits0), jnp.uint32(0)
                )
            self._frontier_count = n_init
            self._depth = 1  # depth of states in the current frontier (bfs.rs:83)
            self._max_depth = 0
            self._state_count = n_init
            self._unique_count = n_unique_init
            self._exhausted = n_init == 0
            if self._autockpt is not None:
                self._autockpt.arm(self._depth)
            if self._recorder is not None:
                self._recorder.arm(self._depth)

    # --- checkpoint/resume (stateright_tpu/checkpoint.py) ------------------

    def save_checkpoint(self, path: str, keep: int = 1) -> None:
        """Atomic (+ rotating, with ``keep > 1``) checkpoint of the current
        search state; also the sink of the in-loop auto-checkpointer, so
        the obs span, the ``checkpoints_written`` counter, and the
        ``last_checkpoint`` gauge live here for manual and automatic saves
        alike."""
        from .checkpoint import _normalize, save_checkpoint

        with self._tracer.span(
            "checkpoint", path=path, depth=self._depth, keep=keep
        ):
            save_checkpoint(self, path, keep=keep)
        self._counters.inc("checkpoints_written")
        self._last_checkpoint = {
            "path": _normalize(path),
            "depth": self._depth,
            "states": self._state_count,
            "unique": self._unique_count,
            "unix_ts": time.time(),
        }

    def _maybe_checkpoint(self) -> None:
        """In-loop auto-checkpoint hook, called at every quiescent point
        (between supersteps, after commit bookkeeping) by both dispatch
        paths. No-op unless ``spawn_xla(checkpoint_to=...)`` /
        ``STPU_CHECKPOINT_TO`` armed a cadence."""
        if self._autockpt is not None:
            self._autockpt.maybe(self)

    def _maybe_record(self) -> None:
        """Metrics time-series hook, called at the same quiescent points
        as :meth:`_maybe_checkpoint` — ``metrics()`` is pure host-side
        reads there, so a sample never adds a device sync. No-op unless
        ``spawn_xla(metrics_to=...)`` / ``STPU_METRICS_TO`` armed a
        recorder (docs/observability.md "Time series")."""
        if self._recorder is not None:
            self._recorder.maybe(self)

    def _restore(self, path: str) -> None:
        """Replaces the freshly-initialized search state with a checkpoint's
        (the table is rebuilt by insertion, so capacities may differ)."""
        import jax
        import jax.numpy as jnp

        from .checkpoint import load_checkpoint, validate_model, validate_symmetry

        ck = load_checkpoint(path)
        validate_model(ck["meta"], self._model, self._prop_names)
        validate_symmetry(ck["meta"], self._sym_tag)

        n_entries = len(ck["key_hi"])
        # Power-of-two growth base: the delta structure's .capacity includes
        # its delta tier (not a power of two); its main tier is the base.
        cap = getattr(self._table, "main_capacity", self._table.capacity)
        while cap < 2 * n_entries:
            cap *= 2
        if self._dedup in ("sorted", "delta"):
            self._table = self._ds.from_entries(
                ck["key_hi"], ck["key_lo"], ck["val_hi"], ck["val_lo"], cap, jnp
            )
        else:
            self._table = hashset.make(cap, jnp)
            while True:
                table, _, ovf = jax.jit(hashset.insert, static_argnames="max_probes")(
                    self._table,
                    jnp.asarray(ck["key_hi"]),
                    jnp.asarray(ck["key_lo"]),
                    jnp.asarray(ck["val_hi"]),
                    jnp.asarray(ck["val_lo"]),
                    jnp.ones(n_entries, jnp.bool_),
                    max_probes=self._max_probes,
                )
                if not bool(np.any(np.asarray(ovf))):
                    self._table = table
                    break
                self._table = hashset.make(self._table.capacity * 2, jnp)

        rows = np.asarray(ck["frontier"], dtype=np.uint32)
        n = len(rows)
        while self._frontier_capacity < n:
            self._frontier_capacity *= 2
        self._frontier = self._pad_rows(rows, self._frontier_capacity)
        ebits = np.zeros(self._frontier_capacity, dtype=np.uint32)
        ebits[:n] = np.asarray(ck["frontier_ebits"], dtype=np.uint32)
        self._frontier_ebits = jnp.asarray(ebits)
        self._frontier_count = n

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
        self._disc_found = jnp.asarray(disc_found)
        self._disc_fp = jnp.asarray(disc_fp)

    # --- helpers ----------------------------------------------------------

    def _pad_rows(self, rows: np.ndarray, cap: int):
        import jax.numpy as jnp

        out = np.zeros((cap, self._W), dtype=np.uint32)
        out[: len(rows)] = rows
        return jnp.asarray(out)

    def _frontier_rows_host(self) -> np.ndarray:
        """The live frontier as host-side ``[n, W]`` rows (checkpointing,
        visitors, and the on-demand pool consume rows)."""
        return np.asarray(self._frontier)[: self._frontier_count]

    def _store_frontier_rows(self, rows: np.ndarray) -> None:
        """Replace the device frontier with these host rows; the caller
        maintains ``_frontier_count``/capacity."""
        import jax.numpy as jnp

        self._frontier = jnp.asarray(np.asarray(rows, dtype=np.uint32))

    def _dedup_words_host(self, rows: np.ndarray) -> np.ndarray:
        """Host-side dedup-key transform: representative packing when
        symmetry is on (the packed analogue of dfs.rs:357-362)."""
        if not self._symmetry:
            return rows
        if self._sym_canon_host is not None:
            # Spec path: the bit-exact numpy twin of the device kernel —
            # no object round-trip, and exact agreement with device
            # fingerprints even when the object representative() is a
            # different (partial) canonicalization.
            canon = self._sym_canon_host
            reps = [canon(np.asarray(row, dtype=np.uint32)) for row in rows]
        else:
            reps = [
                self._model.pack(self._model.unpack(row).representative())
                for row in rows
            ]
        return np.stack(reps) if reps else rows

    def _packed_fp64(self, state: Any) -> int:
        """Host fingerprint of an object state, through the packed codec —
        must agree with device fingerprints (differentially tested)."""
        words = np.asarray(self._model.pack(state), dtype=np.uint32)[None, :]
        words = self._dedup_words_host(words)
        return fphash.fingerprint_u64(words[0], np)

    # --- the fused super-step ---------------------------------------------

    def _build_superstep(self, f_cap: int, cand_cap: int):
        if self._soa:
            return self._build_superstep_planes(f_cap, cand_cap)
        return self._build_superstep_rows(f_cap, cand_cap)

    def _checking_blocks(self):
        """The checking semantics shared verbatim by the rows and planes
        supersteps: fused property evaluation (with host-verified candidate
        collection injected as ``hv_compact``) and terminal detection for
        eventually counterexamples (bfs.rs:279-325, 374-381). One
        implementation so the two layout engines cannot drift."""
        prop_specs = [(i, p.expectation) for i, p in enumerate(self._properties)]
        ebit_of_prop = dict(self._ebit_of_prop)
        hv_idx = list(self._hv_idx)
        hv_cap = self._hv_cap
        W = self._W

        def pin(viol, fhi, flo, i, disc_found, disc_fp, jnp):
            """First-witness election for property ``i`` (races in the
            reference are benign, bfs.rs:291-306; here 'first' is exact)."""
            has = jnp.any(viol)
            first = jnp.argmax(viol)
            take = has & ~disc_found[i]
            disc_fp = disc_fp.at[i, 0].set(jnp.where(take, fhi[first], disc_fp[i, 0]))
            disc_fp = disc_fp.at[i, 1].set(jnp.where(take, flo[first], disc_fp[i, 1]))
            disc_found = disc_found.at[i].set(disc_found[i] | has)
            return disc_found, disc_fp

        def eval_properties(
            props, f_valid, f_ebits, fhi, flo, disc_found, disc_fp, hv_compact, jnp
        ):
            hv_words_out = []
            hv_fp_out = []
            hv_count_out = []
            for i, expectation in prop_specs:
                if expectation == Expectation.EVENTUALLY:
                    bit = jnp.uint32(1 << ebit_of_prop[i])
                    sat = props[:, i] & f_valid
                    f_ebits = jnp.where(sat, f_ebits & ~bit, f_ebits)
                    continue
                if expectation == Expectation.ALWAYS:
                    viol = ~props[:, i] & f_valid
                else:  # SOMETIMES: an example is a "discovery" too
                    viol = props[:, i] & f_valid
                if i in hv_idx:
                    # Candidates only — the host confirms with the exact
                    # condition before anything becomes a discovery.
                    cw, cf, n_viol = hv_compact(viol)
                    hv_words_out.append(cw)
                    hv_fp_out.append(cf)
                    hv_count_out.append(n_viol)
                    continue
                disc_found, disc_fp = pin(viol, fhi, flo, i, disc_found, disc_fp, jnp)
            if hv_idx:
                hv = (
                    jnp.stack(hv_words_out),
                    jnp.stack(hv_fp_out),
                    jnp.stack(hv_count_out),
                )
            else:
                hv = (
                    jnp.zeros((0, hv_cap, W), jnp.uint32),
                    jnp.zeros((0, hv_cap, 2), jnp.uint32),
                    jnp.zeros((0,), jnp.int32),
                )
            return f_ebits, disc_found, disc_fp, hv

        def terminal_pass(terminal, f_ebits, fhi, flo, disc_found, disc_fp, jnp):
            for i, expectation in prop_specs:
                if expectation != Expectation.EVENTUALLY:
                    continue
                bit = jnp.uint32(1 << ebit_of_prop[i])
                viol = terminal & ((f_ebits & bit) != 0)
                disc_found, disc_fp = pin(viol, fhi, flo, i, disc_found, disc_fp, jnp)
            return disc_found, disc_fp

        return eval_properties, terminal_pass

    def _build_superstep_rows(self, f_cap: int, cand_cap: int):
        import jax
        import jax.numpy as jnp

        model = self._model
        symmetry = self._symmetry
        sym_canon = self._sym_canon
        A, W = self._A, self._W
        max_probes = self._max_probes
        hv_cap = self._hv_cap

        def dedup_words(words):
            return sym_canon(words) if symmetry else words

        ds = self._ds
        gather_compact = self._dedup == "sorted"

        def compact(mask, cap, arrays):
            """Stream-compact rows where ``mask`` holds into ``cap``-row
            buffers (stable: original order preserved); rows beyond ``cap``
            are truncated. Returns ``(compacted arrays, count)`` where
            ``count`` is the TOTAL mask population — count > cap means
            truncation (the caller's overflow signal).

            Two lowerings with identical results: cumsum + scatter (wins on
            XLA:CPU) and stable argsort + gather (3x cheaper on TPU, where
            XLA serializes the scatter)."""
            if gather_compact:
                # cap may exceed the mask length (cand_cap = next_pow2 can
                # round up past the grid; frontier caps can exceed cand
                # caps for small action counts) — gather what exists, pad
                # the rest with zeros.
                take = min(cap, mask.shape[0])
                order = jnp.argsort(~mask, stable=True)[:take]
                smask = mask[order]
                outs = []
                for a in arrays:
                    out = jnp.where(
                        smask.reshape((take,) + (1,) * (a.ndim - 1)),
                        a[order],
                        jnp.zeros((), a.dtype),
                    )
                    if take < cap:
                        out = jnp.concatenate(
                            [out, jnp.zeros((cap - take,) + a.shape[1:], a.dtype)]
                        )
                    outs.append(out)
                return outs, jnp.sum(mask, dtype=jnp.int32)
            pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
            idx = jnp.where(mask & (pos < cap), pos, cap)
            outs = [
                jnp.zeros((cap,) + a.shape[1:], a.dtype).at[idx].set(a, mode="drop")
                for a in arrays
            ]
            return outs, jnp.sum(mask, dtype=jnp.int32)

        eval_properties, terminal_pass = self._checking_blocks()

        def hv_compact_rows(frontier, fhi, flo):
            def hv_compact(viol):
                (cw, cf), n_viol = compact(
                    viol, hv_cap, [frontier, jnp.stack([fhi, flo], axis=1)]
                )
                return cw, cf, n_viol

            return hv_compact

        def superstep(frontier, f_ebits, f_count, table, disc_found, disc_fp):
            f_valid = jnp.arange(f_cap) < f_count
            dw = jax.vmap(dedup_words)(frontier)
            fhi, flo = fphash.fingerprint_words(dw, jnp)

            # 1. fused property evaluation over the frontier.
            props = jax.vmap(model.packed_properties)(frontier)  # [F, P]
            f_ebits, disc_found, disc_fp, (hv_words, hv_fps, hv_counts) = (
                eval_properties(
                    props, f_valid, f_ebits, fhi, flo, disc_found, disc_fp,
                    hv_compact_rows(frontier, fhi, flo), jnp,
                )
            )

            # 2. full action-grid expansion. A model may return a third
            #    per-action overflow mask: "this successor exists but does
            #    not fit my codec" — the packed analogue of the reference's
            #    capacity panics, surfaced loudly instead of silently
            #    pruning the transition (SURVEY §7 hard part 2).
            stepped = jax.vmap(model.packed_step)(frontier)  # [F,A,W], [F,A][, [F,A]]
            if len(stepped) == 3:
                nxt, valid, step_ovf = stepped
                codec_overflow = jnp.any(step_ovf & f_valid[:, None])
            else:
                nxt, valid = stepped
                codec_overflow = jnp.bool_(False)
            valid = valid & f_valid[:, None]
            step_states = jnp.sum(valid, dtype=jnp.int32)

            # 3. compact valid candidates (typically a minority of the F*A
            #    grid — disabled slots are padding) into a tight buffer, so
            #    canonicalization, fingerprinting, and the hash insert all
            #    scale with real candidates instead of grid lanes.
            cand = nxt.reshape(f_cap * A, W)
            vmask = valid.reshape(-1)
            par_hi = jnp.broadcast_to(fhi[:, None], (f_cap, A)).reshape(-1)
            par_lo = jnp.broadcast_to(flo[:, None], (f_cap, A)).reshape(-1)
            child_ebits = jnp.broadcast_to(f_ebits[:, None], (f_cap, A)).reshape(-1)
            (ccand, cpar_hi, cpar_lo, cebits), n_valid = compact(
                vmask, cand_cap, [cand, par_hi, par_lo, child_ebits]
            )
            cvalid = jnp.arange(cand_cap) < n_valid
            cand_overflow = n_valid > cand_cap
            cdw = jax.vmap(dedup_words)(ccand)
            chi, clo = fphash.fingerprint_words(cdw, jnp)

            # 4. dedup against the visited set. Compaction preserves lane
            #    order, so the insert's lowest-index winner election picks
            #    the same candidate it would have picked uncompacted. Both
            #    structures share the same contract (is_new in batch order,
            #    lowest-index winner, parent values stored).
            table, is_new, ovf = ds.insert(
                table, chi, clo, cpar_hi, cpar_lo, cvalid, max_probes=max_probes
            )
            step_unique = jnp.sum(is_new, dtype=jnp.int32)
            table_overflow = jnp.any(ovf)

            # 5. terminal detection for eventually counterexamples
            #    (bfs.rs:374-381; duplicates count as successors).
            terminal = f_valid & ~jnp.any(valid, axis=1)
            disc_found, disc_fp = terminal_pass(
                terminal, f_ebits, fhi, flo, disc_found, disc_fp, jnp
            )

            # 6. stream-compact survivors into the next frontier.
            (new_frontier, new_ebits), new_count = compact(
                is_new, f_cap, [ccand, cebits]
            )
            frontier_overflow = new_count > f_cap

            return (
                new_frontier,
                new_ebits,
                new_count,
                table,
                disc_found,
                disc_fp,
                step_states,
                step_unique,
                table_overflow,
                frontier_overflow,
                codec_overflow,
                cand_overflow,
                hv_words,
                hv_fps,
                hv_counts,
            )

        return superstep

    def _build_superstep_planes(
        self, f_cap: int, cand_cap: int, out_cap: Optional[int] = None
    ):
        """The superstep with plane-major (structure-of-arrays) bulk
        buffers: the action grid and the candidate set live as ``[W, M]``
        planes so every sort, gather, and elementwise pass over them runs
        on 128-lane-friendly 1-D arrays (see the layout note in
        ``__init__``).  The frontier itself stays ``[F, W]`` rows: it is
        the kernel-facing boundary (vmapped model kernels take ``[W]``
        rows) and two engine-measured facts pin this shape — (a) frontier
        buffers are a factor A*W smaller than the grid, so their layout is
        off the critical path, and (b) XLA:CPU (jax 0.9.0) MIScompiles a
        transpose fused INTO a vmapped kernel (a scalar-cond ``jnp.where``
        inside the kernel returns the wrong branch for batches >= 64;
        eager and jit disagree) — rows-in/transpose-out is the safe fusion
        direction, planes-in/vmap is not.

        Semantics are bit-identical to the rows superstep: the grid
        flattens a-major (``j = a*F + f``, the tiling-friendly order) and
        the candidate compaction sorts by the state-major rank ``f*A + a``,
        so the insert's lowest-index winner election, the stored parents,
        and the next frontier's order all match the rows engine (and the
        host oracle's "for each state, for each action" enumeration)
        exactly.

        ``out_cap`` (default ``f_cap``) sizes the NEXT-frontier buffers
        independently of the expanded width: a candidate-ladder branch
        expands only ``f_cap = F_k`` rows but must hand back carry-shaped
        ``[out_cap, W]`` buffers (the fused loop's full bucket), so
        survivors compact into ``out_cap`` rows and frontier overflow is
        measured against it."""
        import jax
        import jax.numpy as jnp

        if out_cap is None:
            out_cap = f_cap

        model = self._model
        symmetry = self._symmetry
        sym_canon = self._sym_canon
        A, W = self._A, self._W
        max_probes = self._max_probes
        hv_cap = self._hv_cap
        ds = self._ds
        prop_block = self._prop_block

        def prop_neutral():
            """Per property, the value that flags nothing: True for
            ``always``, False for ``sometimes`` and ``eventually``."""
            return jnp.asarray(
                [p.expectation == Expectation.ALWAYS for p in self._properties], bool
            )

        def dedup_words(words):
            return sym_canon(words) if symmetry else words

        def step3(words):
            out = model.packed_step(words)
            if len(out) == 3:
                return out
            nxt, valid = out
            return nxt, valid, jnp.zeros_like(valid)

        compaction = self._compaction
        sort_compact = compaction == "sort"
        # Pallas-lowering knobs, resolved at build time: the kernel block
        # (grid sequential-step granularity; smaller engages the kernel
        # at smaller shapes — tests use this) and interpret mode (the
        # kernel has no CPU lowering; the interpreter is the CPU
        # reference semantics).
        # Default: pallas_compact.DEFAULT_BLOCK, the smallest block the
        # TPU compiler accepts.
        from .ops.pallas_compact import DEFAULT_BLOCK

        pallas_block = int(os.environ.get("STPU_PALLAS_BLOCK", DEFAULT_BLOCK))
        pallas_interp = jax.default_backend() == "cpu"

        def compact_1d(mask, cap, arrays, prio=None, rows_out=()):
            """Stream-compact lanes where ``mask`` holds into ``cap`` slots.
            ``arrays`` are 1-D lanes or [W, M] planes (compacted along M);
            indices in ``rows_out`` mark plane entries to emit as [cap, W]
            rows instead (the kernel/host-facing shape). With ``prio``
            survivors come out in ascending prio order (the semantic-order
            restoration); otherwise stable in array order.

            Three lowerings with identical results (``spawn_xla(compaction=)``,
            see ``__init__``): "gather" computes the permutation once and
            gathers every plane; "sort" carries the planes as payload
            operands of the permutation sort — no random gathers; "bsearch"
            (stable/no-prio paths only) avoids the permutation sort
            entirely — cumsum of the mask + a branchless binary search of
            each output rank over it + ascending gathers, so the whole
            compaction is scan/gather-class work. The round-5 on-chip
            profile motivates it: at rm=8 shapes the grid-compaction sort
            over 2^24 lanes is the largest per-level sort in the program."""
            m = mask.shape[0]
            # One fused int32 key: invalid lanes get a high bit above every
            # priority (prio < m <= 2^30 here).
            assert m < (1 << 30)
            if prio is None:
                key = jnp.where(mask, jnp.int32(0), jnp.int32(1))
            else:
                key = jnp.where(mask, prio, prio + jnp.int32(1 << 30))
            take = min(cap, m)
            z32 = jnp.uint32(0)
            n_valid = jnp.sum(mask, dtype=jnp.int32)

            # Flatten the inputs into 1-D lanes (planes of 2-D entries).
            lanes = []
            shapes = []  # (kind, W) per array: "1d" | "planes" | "rows"
            for pos, a in enumerate(arrays):
                if a.ndim == 1:
                    lanes.append(a)
                    shapes.append(("1d", None))
                else:
                    for w in range(a.shape[0]):
                        lanes.append(a[w])
                    shapes.append(
                        ("rows" if pos in rows_out else "planes", a.shape[0])
                    )

            pallas_ok = (
                compaction == "pallas"
                and prio is None
                and m % pallas_block == 0
                and cap % pallas_block == 0
                and m >= pallas_block
                and cap >= pallas_block
                and all(lane.dtype == jnp.uint32 for lane in lanes)
            )
            if pallas_ok:
                # Sequential-grid streaming kernel: O(n) data movement,
                # aligned chunk DMAs, no scatters (ops/pallas_compact.py).
                # Lanes pass as separate refs — no stacked copy of the
                # grid. Shapes below the kernel block fall to the sort
                # branch.
                from .ops.pallas_compact import compact_pallas_staged

                kout = compact_pallas_staged(
                    mask, lanes, cap, block=pallas_block,
                    interpret=pallas_interp,
                )
                smask = jnp.arange(take) < n_valid
                slanes = [kout[i][:take] for i in range(len(lanes))]
            elif compaction == "bsearch" and prio is None:
                # Rank i's source lane = first j with cumsum(mask)[j] == i+1:
                # one scan + log2(m) gather rounds + one ascending gather per
                # lane. No sort, no scatter.
                cs = jnp.cumsum(mask.astype(jnp.int32))
                pos_idx = jnp.searchsorted(
                    cs, jnp.arange(1, take + 1, dtype=jnp.int32), side="left"
                )
                pos_idx = jnp.minimum(pos_idx, m - 1)
                smask = jnp.arange(take) < n_valid
                slanes = [lane[pos_idx] for lane in lanes]
            elif sort_compact or compaction in ("bsearch", "pallas"):
                # ("bsearch" with a prio falls back to the sort lowering —
                # the engine's bsearch grid build emits state-major order,
                # so no prio path stays hot under it; "pallas" lands here
                # for shapes below its kernel block.) Without a prio the
                # lane index orders each class, so every key is unique and
                # the sort need not be stable: XLA:TPU implements a stable
                # sort with one more operand, an iota.
                if prio is None:
                    iota = jnp.arange(m, dtype=jnp.int32)
                    key = jnp.where(mask, iota, iota + jnp.int32(1 << 30))
                sorted_all = jax.lax.sort(
                    (key, *lanes), num_keys=1, is_stable=False
                )
                skey = sorted_all[0][:take]
                smask = skey < jnp.int32(1 << 30)
                slanes = [s[:take] for s in sorted_all[1:]]
            else:
                iota = jnp.arange(m, dtype=jnp.int32)
                _, order = jax.lax.sort((key, iota), num_keys=1)
                order = order[:take]
                smask = mask[order]
                slanes = [lane[order] for lane in lanes]

            def pad(out, pad_shape, dtype, axis=0):
                if take < cap:
                    out = jnp.concatenate(
                        [out, jnp.zeros(pad_shape, dtype)], axis=axis
                    )
                return out

            outs = []
            k = 0
            for kind, Wn in shapes:
                if kind == "1d":
                    lane = slanes[k]
                    k += 1
                    out = pad(
                        jnp.where(smask, lane, jnp.zeros((), lane.dtype)),
                        (cap - take,),
                        lane.dtype,
                    )
                elif kind == "rows":
                    rows = [
                        jnp.where(smask, slanes[k + w], z32) for w in range(Wn)
                    ]
                    k += Wn
                    out = pad(
                        jnp.stack(rows, axis=1), (cap - take, Wn), rows[0].dtype
                    )
                else:
                    planes = [
                        jnp.where(smask, slanes[k + w], z32) for w in range(Wn)
                    ]
                    k += Wn
                    out = pad(
                        jnp.stack(planes),
                        (Wn, cap - take),
                        planes[0].dtype,
                        axis=1,
                    )
                outs.append(out)
            return outs, n_valid

        eval_properties, terminal_pass = self._checking_blocks()
        has_ebits = bool(self._ebit_of_prop)
        merge_parents = parent_lowering(compaction, f_cap, cand_cap, A) == "merge"

        def parents_by_merge(skey, fhi, flo, f_ebits):
            """Each sorted grid candidate's parent fingerprint and ebits,
            without a gather. ``skey`` holds the candidates' grid keys in
            ascending order (the state-major rank f*A + a; invalid ones
            2^30 above, after every valid one). One marker lane per
            frontier row f, keyed ``(f*A) << 1``, merges in just before its
            row's candidates, keyed ``(skey << 1) | 1``. Markers carry the
            wrapping first differences of the row arrays and candidates
            carry 0, so a uint32 prefix sum over the merged order hands
            every candidate its parent row's values exactly (the sum
            telescopes mod 2^32). The fill also re-keys the lanes, and a
            second pass of the same sort brings the candidates back to the
            front in their order. Keys are unique in both passes, so the
            sort need not be stable. The two passes run one sort in a
            two-trip loop: each sort instance compiles to megabytes of code
            for its shape, resident in device memory. Without eventually
            properties the ebits are all zero and are not carried."""
            u32 = jnp.uint32
            n = skey.shape[0]
            rows = [fhi, flo] + ([f_ebits] if has_ebits else [])
            zeros = jnp.zeros((n,), u32)
            key = jnp.concatenate([
                (skey.astype(u32) << u32(1)) | u32(1),
                (jnp.arange(f_cap, dtype=u32) * u32(A)) << u32(1),
            ])
            deltas = [r - jnp.concatenate([zeros[:1], r[:-1]]) for r in rows]

            def prefix_sum(v):
                # jnp.cumsum lowers for a TPU to this same reduce-window,
                # but outside the enclosing named scope.
                return jax.lax.platform_dependent(
                    v,
                    tpu=lambda v: jax.lax.reduce_window(
                        v, u32(0), jax.lax.add, (v.shape[0],), (1,),
                        [(v.shape[0] - 1, 0)],
                    ),
                    default=lambda v: jnp.cumsum(v, dtype=u32),
                )

            def fill(key, *vals):
                # Candidates (odd keys) first, in key order; markers last.
                back_key = (key >> u32(1)) | ((~key & u32(1)) << u32(31))
                return (back_key, *map(prefix_sum, vals))

            def sort_pass(i, lanes):
                lanes = jax.lax.sort(lanes, num_keys=1, is_stable=False)
                return jax.lax.cond(i == 0, fill, lambda *ls: ls, *lanes)

            _, *out = jax.lax.fori_loop(
                0, 2, sort_pass,
                (key, *[jnp.concatenate([zeros, d]) for d in deltas]),
            )
            out = [v[:n] for v in out]
            return out if has_ebits else [*out, zeros]

        def hv_compact_planes(frontier, fhi, flo):
            def hv_compact(viol):
                (cw, cfh, cfl), n_viol = compact_1d(
                    viol, hv_cap, [frontier.T, fhi, flo], rows_out=(0,)
                )
                return cw, jnp.stack([cfh, cfl], axis=1), n_viol

            return hv_compact

        def superstep(frontier, f_ebits, f_count, table, disc_found, disc_fp):
            # frontier: [F, W] rows (kernel-facing boundary). Each stage
            # runs under a jax.named_scope of its name, so its operations
            # carry the name in their op_name metadata and in a profiler
            # trace; the ladder's switch branches all call this function.
            f_valid = jnp.arange(f_cap) < f_count
            with jax.named_scope("fingerprint"):
                dw = jax.vmap(dedup_words)(frontier)
                fhi, flo = fphash.fingerprint_words(dw, jnp)

            # 1. fused property evaluation over the frontier: in blocks of
            #    live rows where the model's block width is under the bucket.
            with jax.named_scope("properties"):
                if 0 < prop_block < f_cap:
                    with jax.named_scope("serialize"):
                        props = blocked_properties(
                            model.packed_properties, frontier, f_count,
                            prop_block, prop_neutral(),
                        )
                else:
                    props = jax.vmap(model.packed_properties)(frontier)  # [F, P]
                f_ebits, disc_found, disc_fp, (hv_words, hv_fps, hv_counts) = (
                    eval_properties(
                        props, f_valid, f_ebits, fhi, flo, disc_found, disc_fp,
                        hv_compact_planes(frontier, fhi, flo), jnp,
                    )
                )

            # 2. action-grid expansion; codec overflow folded in as in
            #    rows mode. Layout per the STPU_EXPAND_LAYOUT knob (see
            #    __init__): "rows" = [F, A, W] + materialized transpose,
            #    "planes" = the vmap emits [A, W, F] with F minor.
            with jax.named_scope("expand"):
                if self._expand_layout == "planes":
                    nxt, valid, step_ovf = jax.vmap(step3, out_axes=(2, 0, 0))(frontier)
                else:
                    nxt, valid, step_ovf = jax.vmap(step3)(frontier)
                codec_overflow = jnp.any(step_ovf & f_valid[:, None])
                valid = valid & f_valid[:, None]
                step_states = jnp.sum(valid, dtype=jnp.int32)

            # 3. flatten the grid into [W, A*F] planes and compact in
            #    state-major rank order. Under the sort/gather compactions
            #    the flatten is a-major (F stays on the 128-lane axis — the
            #    tiling-friendly transpose) and a prio key restores the
            #    semantic order inside the compaction sort. Under "bsearch"
            #    and "pallas" the flatten is state-major (k = f*A + a) so
            #    array order IS semantic order and the compaction needs no
            #    sort at all; the [.., F, A] intermediate's minor-axis
            #    padding is fused away into the reshape consumer.
            with jax.named_scope("compact"):
                if compaction in ("bsearch", "pallas"):
                    if self._expand_layout == "planes":
                        grid = jnp.transpose(nxt, (1, 2, 0)).reshape(W, f_cap * A)
                    else:
                        grid = jnp.transpose(nxt, (2, 0, 1)).reshape(W, f_cap * A)
                    vmask = valid.reshape(f_cap * A)
                    par_hi = jnp.broadcast_to(fhi[:, None], (f_cap, A)).reshape(-1)
                    par_lo = jnp.broadcast_to(flo[:, None], (f_cap, A)).reshape(-1)
                    child_ebits = jnp.broadcast_to(
                        f_ebits[:, None], (f_cap, A)
                    ).reshape(-1)
                    prio = None
                else:
                    if self._expand_layout == "planes":
                        # [A, W, F] -> [W, A, F] moves whole F-contiguous lanes:
                        # tiling-friendly, no (8,128)-padded intermediate.
                        grid = jnp.transpose(nxt, (1, 0, 2)).reshape(W, A * f_cap)
                    else:
                        grid = jnp.transpose(nxt, (2, 1, 0)).reshape(W, A * f_cap)
                    vmask = valid.T.reshape(A * f_cap)
                    par_hi = jnp.broadcast_to(fhi[None, :], (A, f_cap)).reshape(-1)
                    par_lo = jnp.broadcast_to(flo[None, :], (A, f_cap)).reshape(-1)
                    child_ebits = jnp.broadcast_to(
                        f_ebits[None, :], (A, f_cap)
                    ).reshape(-1)
                    j = jnp.arange(A * f_cap, dtype=jnp.int32)
                    prio = (j % f_cap) * A + (j // f_cap)  # semantic rank f*A + a
                if compaction == "sort":
                    # The grid sort is the engine's largest per-level op (A*F
                    # lanes). It carries only the key and the W state planes
                    # (its keys are unique, so it need not be stable): the
                    # parent fingerprints and ebits are functions of the
                    # winning key (state-major rank k -> parent row k // A),
                    # recovered afterwards at candidate scale. Carrying them
                    # as grid payload would make the grid sort the program's
                    # memory high-water mark (+29% temp at rm=8's full rung);
                    # gathering them by row costs 10-20x a sorted
                    # lane-operand per element on a TPU v5e, so wide buffers
                    # recover them by merge (PARENT_MERGE_MIN).
                    m_grid = A * f_cap
                    gkey = jnp.where(vmask, prio, prio + jnp.int32(1 << 30))
                    take = min(cand_cap, m_grid)
                    sorted_all = jax.lax.sort(
                        (gkey, *[grid[w] for w in range(W)]),
                        num_keys=1, is_stable=False,
                    )
                    skey = sorted_all[0][:take]
                    smask = skey < jnp.int32(1 << 30)
                    z32 = jnp.uint32(0)

                    def pad_lane(lane):
                        lane = jnp.where(smask, lane, z32)
                        if take < cand_cap:
                            lane = jnp.concatenate(
                                [lane, jnp.zeros((cand_cap - take,), lane.dtype)]
                            )
                        return lane

                    ccand = jnp.stack(
                        [pad_lane(s[:take]) for s in sorted_all[1:]]
                    )
                    if merge_parents:
                        parents = parents_by_merge(skey, fhi, flo, f_ebits)
                    else:
                        f_row = jnp.clip(
                            (skey & jnp.int32((1 << 30) - 1)) // jnp.int32(A),
                            0, f_cap - 1,
                        )
                        parents = (fhi[f_row], flo[f_row], f_ebits[f_row])
                    cpar_hi, cpar_lo, cebits = map(pad_lane, parents)
                    n_valid = jnp.sum(vmask, dtype=jnp.int32)
                else:
                    (ccand, cpar_hi, cpar_lo, cebits), n_valid = compact_1d(
                        vmask, cand_cap, [grid, par_hi, par_lo, child_ebits],
                        prio=prio,
                    )
                cvalid = jnp.arange(cand_cap) < n_valid
                cand_overflow = n_valid > cand_cap
            with jax.named_scope("fingerprint"):
                if symmetry:
                    # The representative kernel needs [W] rows; gather candidate
                    # rows once (symmetry models only — the common case keeps
                    # candidates pure plane-major).
                    crows = jnp.stack([ccand[w] for w in range(W)], axis=1)
                    cdw = jax.vmap(dedup_words)(crows)
                    chi, clo = fphash.fingerprint_words(cdw, jnp)
                else:
                    chi, clo = fphash.fingerprint_planes(ccand, jnp)

            # 4. dedup (candidates are in state-major order, so the insert's
            #    default arange ticket IS the semantic winner election).
            with jax.named_scope("insert"):
                table, is_new, ovf = ds.insert(
                    table, chi, clo, cpar_hi, cpar_lo, cvalid, max_probes=max_probes
                )
                step_unique = jnp.sum(is_new, dtype=jnp.int32)
                table_overflow = jnp.any(ovf)

            # 5. terminal detection for eventually counterexamples.
            with jax.named_scope("terminal"):
                terminal = f_valid & ~jnp.any(valid, axis=1)
                disc_found, disc_fp = terminal_pass(
                    terminal, f_ebits, fhi, flo, disc_found, disc_fp, jnp
                )

            # 6. survivors -> next frontier rows (stable: semantic order).
            with jax.named_scope("compact"):
                (new_frontier, new_ebits), new_count = compact_1d(
                    is_new, out_cap, [ccand, cebits], rows_out=(0,)
                )
                frontier_overflow = new_count > out_cap

            return (
                new_frontier,
                new_ebits,
                new_count,
                table,
                disc_found,
                disc_fp,
                step_states,
                step_unique,
                table_overflow,
                frontier_overflow,
                codec_overflow,
                cand_overflow,
                hv_words,
                hv_fps,
                hv_counts,
            )

        return superstep


    def _build_fused(self, f_cap: int, rungs):
        """The level loop as a device program: a ``lax.while_loop`` around
        the superstep that commits one BFS level per iteration and exits on
        (a) the level budget, (b) frontier exhaustion, (c) any overflow —
        the overflowing level is NOT committed, so the host can grow and
        re-enter, (d) every property resolved (found on device, already
        confirmed on host, or — for host-verified properties — at least one
        candidate collected for the host to confirm), or (e) a state-count
        target. Exit conditions are evaluated at level granularity, exactly
        like the one-level-per-dispatch path; only the host round-trips
        differ.

        ``rungs`` is the in-program candidate ladder (``_cand_rungs``):
        ascending ``[(F_k, C_k)]`` sub-width shapes, last = the full
        bucket. With K > 1 each iteration picks a branch ON DEVICE via
        ``lax.switch`` — every branch is a complete superstep at its own
        static shapes, returning identical carry-shaped outputs — so a
        narrow level's grid compaction sorts ``A*F_k`` lanes and its
        insert merges ``[table ‖ C_k]`` instead of the peak shapes, with
        zero added host dispatches (the shrink-exit chip lesson,
        measured on an earlier chip setup). Selection per level:

        - the frontier side is EXACT: branch k needs ``F_k >= f_count``
          (known before expansion), so no state is ever left unexpanded;
        - the candidate side uses ``min(f_count*A, margin * prev_gen *
          clamped_growth)`` — the jump ladder's growth extrapolation run
          device-side. ``f_count*A`` is an exact bound, so when the full
          sub-grid fits the rung the choice is safe by construction; the
          estimate only ever picks a SNUGGER rung than the bound.
        - an UNDERESTIMATE (the chosen rung's candidate buffer
          overflows) is never host-visible and never drops candidates:
          the level is not committed, a carry flag forces the next
          iteration to the full-width branch, and the identical frontier
          re-runs — the structural fall-through. Counts stay exact by
          construction (a committed snug level is bit-identical to the
          full-width level: same candidate order, same winner election).

        TPU caveat, pinned for the chip A/B: registry #4
        (docs/backend_pathologies.md) faulted on a ``lax.cond`` carrying
        a main-capacity sort, and a ladder branch carries the [table ‖
        cand] merge sort — the TPU-target lowering pre-flights clean
        (tests/test_cand_ladder.py), and chip_smoke.py runs it on the
        chip (the cand_ladder=3 default).

        The loop's own per-level work (the exit test, rung selection, the
        commit select of the carry, level telemetry and the host-verified
        accumulation) runs under ``jax.named_scope("ladder")``, beside the
        superstep's named stages."""
        import jax
        import jax.numpy as jnp

        K = len(rungs)
        if self._soa:
            steps = [
                self._build_superstep_planes(Fk, Ck, out_cap=f_cap)
                for Fk, Ck in rungs
            ]
        else:
            steps = [self._build_superstep_rows(f_cap, Ck) for _, Ck in rungs]

        def make_branch(step, Fk):
            if Fk == f_cap:
                return step

            def branch(frontier, f_ebits, f_count, table, disc_found, disc_fp):
                # Static prefix slice: selection guarantees
                # f_count <= F_k, so rows beyond the slice are pads.
                return step(
                    jax.lax.slice_in_dim(frontier, 0, Fk),
                    jax.lax.slice_in_dim(f_ebits, 0, Fk),
                    f_count,
                    table,
                    disc_found,
                    disc_fp,
                )

            return branch

        branches = [make_branch(s, Fk) for s, (Fk, _) in zip(steps, rungs)]
        A = self._A
        growth_clamp = self.LADDER_GROWTH_CLAMP
        cand_margin = self.CAND_EST_MARGIN
        W = self._W
        n_hv = len(self._hv_idx)
        hv_cap = self._hv_cap
        # Map property index -> (is_hv, hv position) for the resolution mask.
        hv_pos = {i: j for j, i in enumerate(self._hv_idx)}
        P = self._P
        # Per-level telemetry slots (frontier width / generated / unique per
        # committed level) — fused dispatch must not cost the bench its
        # per-level breakdown. Static bound: the dispatch level budget.
        L = self._levels_per_dispatch

        def fused(frontier, f_ebits, f_count, table, disc_found, disc_fp,
                  budget, remaining, host_found, shrink_below,
                  prev_gen0, prev2_gen0):
            F_rungs = jnp.asarray([r[0] for r in rungs], jnp.int32)
            C_rungs = jnp.asarray([r[1] for r in rungs], jnp.int32)

            def resolved(disc_found, hv_cnt_acc):
                if P == 0:
                    return jnp.bool_(False)
                per_prop = [
                    host_found[i]
                    | (hv_cnt_acc[hv_pos[i]] > 0 if i in hv_pos else disc_found[i])
                    for i in range(P)
                ]
                return jnp.all(jnp.stack(per_prop))

            def hv_pending(hv_cnt_acc):
                """Any *unconfirmed* host-verified property with collected
                candidates: the host must confirm before exploring further,
                and exiting here keeps the candidate buffer to one level's
                worth — the same ``hv_cap`` budget the one-level path has."""
                if not n_hv:
                    return jnp.bool_(False)
                flags = [
                    (hv_cnt_acc[j] > 0) & ~host_found[i] for i, j in hv_pos.items()
                ]
                return jnp.any(jnp.stack(flags))

            def cond(carry):
                (committed, frontier, f_ebits, f_count, table, disc_found,
                 disc_fp, tot_states, tot_unique, ovf, hv_w, hv_f, hv_c,
                 lvl_frontier, lvl_states, lvl_unique, lvl_bucket, lvl_cand,
                 prev_gen, prev2_gen, force_full, retries) = carry
                # The budget bounds COMMITTED levels (the block's semantic
                # unit): a ladder fall-through retry is a non-committing
                # iteration that must not shrink the block the host asked
                # for. Total iterations stay bounded — every non-commit
                # either sets an overflow flag (exit) or force_full, and a
                # forced full-width level commits or overflows.
                with jax.named_scope("ladder"):
                    return (
                        (committed < budget)
                        & (f_count > 0)
                        # Shrink-exit: once the frontier collapses below the
                        # host-chosen threshold (derived from smaller buckets
                        # that already hold compiled programs — 0 disables),
                        # hand control back so the tail levels re-dispatch at
                        # a snug bucket instead of paying this bucket's full
                        # A*F-lane grid compaction per level. The committed==0
                        # bypass guarantees one committed level per entry: a
                        # frontier-overflow grow can land here with f_count
                        # already at or below the outgrown bucket's threshold,
                        # and exiting at level 0 would stall the checker in a
                        # grow/stall/re-enter cycle forever.
                        & ((committed == 0) | (f_count > shrink_below))
                        & ~jnp.any(ovf)
                        & ~resolved(disc_found, hv_c)
                        & ~hv_pending(hv_c)
                        & (tot_states < remaining)
                    )

            def body(carry):
                (committed, frontier, f_ebits, f_count, table, disc_found,
                 disc_fp, tot_states, tot_unique, ovf, hv_w, hv_f, hv_c,
                 lvl_frontier, lvl_states, lvl_unique, lvl_bucket, lvl_cand,
                 prev_gen, prev2_gen, force_full, retries) = carry
                hv_w0, hv_f0 = hv_w, hv_f
                if K == 1:
                    k = jnp.int32(0)
                    out = branches[0](
                        frontier, f_ebits, f_count, table, disc_found, disc_fp
                    )
                else:
                    with jax.named_scope("ladder"):
                        # Branch selection. ``bound`` is the exact candidate
                        # ceiling (every grid slot valid); the extrapolated
                        # estimate may pick a snugger rung, and the frontier
                        # constraint F_k >= f_count is always exact.
                        bound = f_count * jnp.int32(A)
                        growth = jnp.clip(
                            prev_gen.astype(jnp.float32)
                            / jnp.maximum(prev2_gen, 1).astype(jnp.float32),
                            1.0,
                            growth_clamp,
                        )
                        est = prev_gen.astype(jnp.float32) * growth * cand_margin
                        est_i = jnp.minimum(est, jnp.float32(2**30)).astype(
                            jnp.int32
                        )
                        need = jnp.where(
                            prev_gen > 0, jnp.minimum(bound, est_i), bound
                        )
                        k = jnp.int32(K - 1)
                        for j in range(K - 2, -1, -1):
                            ok = (f_count <= F_rungs[j]) & (need <= C_rungs[j])
                            k = jnp.where(ok, jnp.int32(j), k)
                        k = jnp.where(force_full, jnp.int32(K - 1), k)
                    out = jax.lax.switch(
                        k, branches, frontier, f_ebits, f_count, table,
                        disc_found, disc_fp,
                    )
                with jax.named_scope("ladder"):
                    (nf, ne, ncount, ntable, ndfound, ndfp, d_states, d_unique,
                     t_ovf, f_ovf, c_ovf, cc_ovf, lw, lf, lc) = out
                    # A snug branch's candidate overflow is the ladder's
                    # fall-through, not a host event: the level is simply not
                    # committed and the next iteration is forced full-width.
                    # Only the full-width branch's overflow is the real
                    # cc_ovf the host grows on.
                    sub_ovf = cc_ovf & (k < K - 1)
                    real_cc = cc_ovf & (k == K - 1)
                    any_ovf = t_ovf | f_ovf | c_ovf | real_cc
                    commit = ~any_ovf & ~sub_ovf
                    sel = lambda new, old: jax.tree_util.tree_map(
                        lambda a, b: jnp.where(commit, a, b), new, old
                    )
                    # Telemetry for this level, recorded only when committed
                    # (an uncommitted level is retried after growth): slot index
                    # L drops the write.
                    slot = jnp.where(commit, committed, L)
                    lvl_frontier = lvl_frontier.at[slot].set(f_count, mode="drop")
                    lvl_states = lvl_states.at[slot].set(d_states, mode="drop")
                    lvl_unique = lvl_unique.at[slot].set(d_unique, mode="drop")
                    lvl_bucket = lvl_bucket.at[slot].set(F_rungs[k], mode="drop")
                    lvl_cand = lvl_cand.at[slot].set(C_rungs[k], mode="drop")
                    # Append this level's host-verified candidates to the block
                    # accumulator (frontier order within a level, level order
                    # across the block — the confirmation order the one-level
                    # path uses).
                    if n_hv:
                        rows = jnp.arange(hv_cap)
                        for j in range(n_hv):
                            dst = hv_c[j] + rows
                            ok = (rows < lc[j]) & (dst < hv_cap)
                            tgt = jnp.where(ok, dst, hv_cap)
                            hv_w = hv_w.at[j].set(hv_w[j].at[tgt].set(lw[j], mode="drop"))
                            hv_f = hv_f.at[j].set(hv_f[j].at[tgt].set(lf[j], mode="drop"))
                        hv_c = sel(hv_c + lc, hv_c)
                        hv_w = sel(hv_w, hv_w0)
                        hv_f = sel(hv_f, hv_f0)
                    return (
                        committed + commit.astype(jnp.int32),
                        sel(nf, frontier),
                        sel(ne, f_ebits),
                        sel(ncount, f_count),
                        sel(ntable, table),
                        sel(ndfound, disc_found),
                        sel(ndfp, disc_fp),
                        tot_states + jnp.where(commit, d_states, 0),
                        tot_unique + jnp.where(commit, d_unique, 0),
                        jnp.stack([t_ovf, f_ovf, c_ovf, real_cc]),
                        hv_w,
                        hv_f,
                        hv_c,
                        lvl_frontier,
                        lvl_states,
                        lvl_unique,
                        lvl_bucket,
                        lvl_cand,
                        jnp.where(commit, d_states, prev_gen),
                        jnp.where(commit, prev_gen, prev2_gen),
                        jnp.where(commit, jnp.bool_(False), force_full | sub_ovf),
                        # Count only fall-throughs that actually re-run
                        # in-program: a snug cc_ovf coinciding with a REAL
                        # overflow exits the loop instead (the host resolves
                        # it and the level re-runs on the next dispatch).
                        retries + (sub_ovf & ~any_ovf).astype(jnp.int32),
                    )

            carry0 = (
                jnp.int32(0),
                frontier,
                f_ebits,
                f_count,
                table,
                disc_found,
                disc_fp,
                jnp.int32(0),
                jnp.int32(0),
                jnp.zeros((4,), jnp.bool_),
                jnp.zeros((n_hv, hv_cap, W), jnp.uint32),
                jnp.zeros((n_hv, hv_cap, 2), jnp.uint32),
                jnp.zeros((n_hv,), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                jnp.zeros((L,), jnp.int32),
                prev_gen0,
                prev2_gen0,
                jnp.bool_(False),
                jnp.int32(0),
            )
            return jax.lax.while_loop(cond, body, carry0)

        return fused

    def _cand_cap_for(self, run_cap: int) -> int:
        """Candidate-buffer capacity for a run bucket: a quarter of the
        action grid (valid slots are typically a minority), power-of-four
        bucketed, grown on overflow. Cached per CHECKER (so two live
        checkers over one model can't resize each other's buffers
        mid-run), seeded from and written back to per-model hints so a
        fresh checker still inherits learned growths (see __init__)."""
        caps = self._cand_caps
        cap = caps.get(run_cap)
        if cap is None:
            caps[run_cap] = cap = self._default_cand_cap(run_cap)
        return cap

    def _default_cand_cap(self, run_cap: int) -> int:
        """The cap :meth:`_cand_cap_for` would size a so-far-unseen bucket
        at — split out non-mutating so the sibling eviction guard in
        :meth:`_grow_cand_cap` can probe another live checker's would-be
        sizing without inserting entries into its cap dict. The sizing
        policy itself (full grid small, power-of-two fraction big,
        STPU_CAND_FRAC A/B) is the shared module-level
        :func:`default_cand_cap` so the compile-plan census enumerates
        the caps the engine actually starts at."""
        return default_cand_cap(
            run_cap, self._A, self._jax.default_backend()
        )

    def _parent_lowering(self, run_cap: int, cand_cap: Optional[int] = None) -> str:
        """:func:`parent_lowering` of this engine's superstep at a bucket
        (by default at the bucket's current candidate cap; a pure read)."""
        if cand_cap is None:
            cand_cap = self._cand_caps.get(run_cap, self._default_cand_cap(run_cap))
        compaction = self._compaction if self._soa else "rows"
        return parent_lowering(compaction, run_cap, cand_cap, self._A)

    @staticmethod
    def _next_pow2(n: int) -> int:
        return _next_pow2(n)

    def _grow_cand_cap(self, run_cap: int) -> None:
        self._counters.inc("cand_grows")
        m = run_cap * self._A
        old = self._cand_cap_for(run_cap)
        new = min(old * 4, self._next_pow2(m))
        self._cand_caps[run_cap] = new
        hints = self._model.__dict__.setdefault("_xla_cand_cap_hints", {})
        hints[run_cap] = max(hints.get(run_cap, 0), new)
        # Evict outgrown compiled programs — THIS checker's lookups always
        # use the grown cap, and a fresh checker seeds from the (just
        # raised) hints, so the old-cap programs are dead weight holding
        # full XLA executables — UNLESS a live, still-RUNNING sibling
        # checker sizes this bucket at the old cap (caps are per-checker,
        # the cache is model-shared): evicting under it would force it to
        # re-pay a compile for a program that is still current for it. A
        # finished sibling never dispatches again, so a lingering
        # reference to one doesn't pin its outgrown executables.
        # A fused program is stale only when its rung tuple actually
        # CHANGES under the grown caps: an outgrown sub-rung whose cap
        # was already clamped by the monotone envelope recomputes
        # identically, and evicting it would force a byte-identical
        # recompile (~11 s/bucket on CPU, minutes from the TPU compiler).
        pinning = [
            (s._sym_tag, s._max_probes, s._dedup, s._compaction)
            for s in self._siblings()
            if not s.is_done()
            and s._cand_caps.get(run_cap, s._default_cand_cap(run_cap)) == old
        ]
        for key in [
            k
            for k in self._superstep_cache
            if (
                (k[0] == run_cap and k[1] == old)
                or (
                    k[0] == "fused"
                    and any(F == run_cap and c == old for F, c in k[2])
                    and tuple(self._cand_rungs(k[1])) != k[2]
                )
            )
            # Per-key pinning: a sibling protects only keys its own
            # engine config can look up (dedup/compaction are part of
            # the key — a hash sibling can never reach a sorted key).
            and (k[3:] if k[0] == "fused" else k[2:]) not in pinning
        ]:
            del self._superstep_cache[key]

    def _siblings(self) -> List["XlaChecker"]:
        """Other live checkers over this model (weakrefs registered in
        ``__init__``; dead refs are pruned on the way out)."""
        live = self._model.__dict__.get("_xla_live_checkers", [])
        live[:] = [r for r in live if r() is not None]
        return [c for r in live if (c := r()) is not None and c is not self]

    #: In-program candidate-ladder rung floor (the shared planner's
    #: constant, re-exported on the class for the A/B harnesses that
    #: already read it here).
    CAND_RUNG_FLOOR = CAND_RUNG_FLOOR
    #: Headroom multiplier on the device-side candidate estimate. An
    #: underestimate costs one wasted snug superstep (the in-program
    #: fall-through re-runs the level full-width), so the estimate is
    #: doubled before picking a rung; the exact ``f_count * A`` bound
    #: still wins whenever the whole sub-grid fits a rung.
    CAND_EST_MARGIN = 2.0

    def _cand_rungs(self, f_cap: int) -> List[Tuple[int, int]]:
        """The in-program candidate ladder for a fused dispatch at bucket
        ``f_cap``: ascending ``[(F_k, C_k)]`` sub-width shapes, last = the
        full bucket. Each rung is exactly the (rows, candidate-cap) shape
        the host ladder would run at bucket ``F_k``, specialised into the
        peak program — so a branch's committed level is bit-identical to
        what a host re-dispatch at that bucket would have produced,
        without the re-dispatch."""
        k = self._cand_ladder_k if self._soa else 1
        return cand_rungs(f_cap, self._cand_cap_for, k)

    def _level_lane_words(self, bucket: int, cand_w: int) -> int:
        """32-bit words carried through ``lax.sort`` operands by ONE
        committed level at these dispatch shapes — the x-axis of the
        round-5 cost law (per-level time ~ sorted lane-words x log^2 n,
        tools/roofline.py). The law covers sorts only: on a TPU v5e a
        gathered element costs 10-20x a sorted lane-operand (PERF.md §5),
        and gathers are not counted here. Computed from the actual static
        sort shapes the compiled program runs (grid compaction, parent
        recovery, visited-set insert and frontier compaction at engine
        scale; the hv_cap- and
        symmetry-only side sorts are bounded and not counted), so the
        candidate-ladder A/B is engine-measured, not hand-derived. The
        rows/hash engine sorts nothing (cumsum + scatter compaction)."""
        if not self._soa:
            return 0
        W = self._W
        grid = bucket * self._A
        total = 0
        if self._compaction == "sort":
            # Grid: key + W state planes; frontier: key + W rows + ebits;
            # a merge recovery: two passes of key + parent fingerprint
            # (+ ebits) over candidates and frontier rows.
            total += grid * (1 + W) + cand_w * (2 + W)
            if self._parent_lowering(bucket, cand_w) == "merge":
                lanes = 3 + bool(self._ebit_of_prop)
                total += 2 * lanes * (min(cand_w, grid) + bucket)
        elif self._compaction == "gather":
            # Permutation sorts only (key + iota); payloads move by gather.
            total += grid * 2 + cand_w * 2
        # bsearch/pallas compactions are scan/kernel lowerings: no sorted
        # lanes at engine scale (their sub-block sort fallbacks are not
        # modeled — both modes are opt-in A/Bs).
        total += self._ds.insert_lane_words(self._table, cand_w)
        return total

    def _mark_dispatch_shape(self, program_key) -> bool:
        """Whether THIS dispatch will trace + compile: true the first
        time a given (program key, table capacity) pair is dispatched in
        this process. A bare program-cache-miss check is not enough —
        the jit cache keys on input avals, and the table capacity is the
        one dispatch input whose SHAPE changes under a fixed program key
        (an overflow-growth retry re-enters the same cached wrapper
        with a doubled table, recompiling for minutes on the TPU)
        — and both consumers of the flag need it right: the heartbeat
        watchdog's compile leash and roofline --measured's
        compile-vs-steady stage split. Keyed on the program cache key's
        CONTENT (not ``id(fn)`` — eviction by _grow_cand_cap can recycle
        an address and mislabel a real compile) and tracked
        model-shared, like the program cache itself."""
        seen = self._model.__dict__.setdefault("_xla_dispatched_shapes", set())
        key = (program_key, self._table.capacity)
        fresh = key not in seen
        seen.add(key)
        return fresh

    def _superstep_key(self, f_cap: int):
        return (
            f_cap, self._cand_cap_for(f_cap), self._sym_tag,
            self._max_probes, self._dedup, self._compaction,
        )

    def _superstep_for(self, f_cap: int):
        import jax

        key = self._superstep_key(f_cap)
        fn = self._superstep_cache.get(key)
        if fn is None:
            fn = jax.jit(self._build_superstep(f_cap, key[1]))
            self._superstep_cache[key] = fn
        return fn

    def _fused_key(self, f_cap: int):
        return (
            "fused", f_cap, tuple(self._cand_rungs(f_cap)), self._sym_tag,
            self._max_probes, self._dedup, self._compaction,
        )

    def _fused_for(self, f_cap: int):
        import jax

        key = self._fused_key(f_cap)
        fn = self._superstep_cache.get(key)
        if fn is None:
            fn = jax.jit(self._build_fused(f_cap, key[2]))
            self._superstep_cache[key] = fn
        return fn

    #: Proactive-growth trigger for the HASH structure: keep the
    #: open-addressing table at or below this load factor. Probe-chain
    #: length (the dominant insert cost)
    #: grows superlinearly with load; growing at 1/4 load bounds probe
    #: rounds at a 4x memory cost over the uniques.
    MAX_LOAD_NUM, MAX_LOAD_DEN = 1, 4
    #: For the SORTED structure the trade inverts: per-level cost is the
    #: sort of [capacity + candidates], so headroom costs sort bandwidth,
    #: not probe rounds — run it denser and grow late.
    SORTED_LOAD_NUM, SORTED_LOAD_DEN = 3, 4

    def _grow_table_if_loaded(self) -> None:
        """Double the table whenever the committed unique count crosses the
        structure's load ceiling — BEFORE inserts start paying (hash: long
        probe chains; sorted: an overflow-retry round trip). For the delta
        structure, additionally flush the delta tier proactively at 3/4
        occupancy — a flush at a dispatch boundary costs nothing extra,
        while one discovered mid-level costs the overflow-retry of that
        level."""
        num, den = (
            (self.MAX_LOAD_NUM, self.MAX_LOAD_DEN)
            if self._dedup == "hash"
            else (self.SORTED_LOAD_NUM, self.SORTED_LOAD_DEN)
        )
        while self._unique_count * den > self._table.capacity * num:
            self._grow_table()
        if self._dedup == "delta":
            ds = self._table
            if int(ds.n_delta) * 4 > ds.delta_capacity * 3:
                with self._tracer.span("delta_flush", proactive=True):
                    flushed, ovf = deltaset.maintain_jit(ds)
                    ovf = bool(ovf)
                self._counters.inc("delta_flushes")
                if ovf:  # pragma: no cover - load rule fires first
                    self._grow_table()
                else:
                    self._table = flushed

    def _resolve_table_overflow(self) -> None:
        """A table overflow from the structure: for the delta set a
        non-empty delta tier means FLUSH (``deltaset.maintain``) — the
        amortized big merge, host-invoked so no ``lax.cond`` ever carries
        a main-capacity sort (that conditional shape faults the XLA:TPU
        runtime; see deltaset.insert) — and only an empty-delta overflow
        or a flush that cannot fit main grows capacity."""
        if self._dedup == "delta" and int(self._table.n_delta) > 0:
            with self._tracer.span("delta_flush", proactive=False):
                flushed, ovf = deltaset.maintain_jit(self._table)
                ovf = bool(ovf)
            self._counters.inc("delta_flushes")
            if not ovf:
                self._table = flushed
                return
        self._grow_table()

    def _grow_table(self) -> None:
        """Double the visited-set capacity: a rehash for the hash table, a
        plain plane copy for the sorted set (its invariant is
        capacity-independent)."""
        import jax
        import jax.numpy as jnp

        old = self._table
        with self._tracer.span(
            "grow_table", dedup=self._dedup, capacity=old.capacity * 2
        ):
            if self._dedup == "delta":
                # Growth folds the delta into a doubled main tier
                # (host-side rebuild; rare by the load rule).
                self._table = deltaset.grow(old, old.main_capacity * 2, jnp)
            elif self._dedup == "sorted":
                self._table = sortedset.grow(old, old.capacity * 2, jnp)
            else:
                occupied = (old.key_hi != 0) | (old.key_lo != 0)
                bigger = hashset.make(old.capacity * 2, jnp)
                bigger, _, ovf = jax.jit(
                    hashset.insert, static_argnames="max_probes"
                )(
                    bigger,
                    old.key_hi,
                    old.key_lo,
                    old.val_hi,
                    old.val_lo,
                    occupied,
                    max_probes=self._max_probes,
                )
                if bool(np.any(np.asarray(ovf))):  # pragma: no cover
                    raise RuntimeError(
                        "rehash overflow — pathological fingerprint "
                        "distribution"
                    )
                self._table = bigger
        self._counters.inc("table_grows")
        self._model.__dict__[self._table_hint_key] = self._table.capacity

    def _raise_codec_overflow(self) -> None:
        raise RuntimeError(
            f"{type(self._model).__name__}: packed-codec capacity "
            "overflow — a reachable successor does not fit the "
            "model's declared field widths/slot counts. Raise the "
            "model's capacity bounds (this is the loud failure the "
            "packed toolkit guarantees; see stateright_tpu.packing)."
        )

    #: Reuse-first bound for the "jump" ladder: an already-compiled bucket
    #: up to this factor over the snug one is preferred to a fresh XLA
    #: compile. Bounded so a deep-narrow tail (width ~20 for thousands of
    #: levels) can never get pinned to a huge bucket — the round-4
    #: floor-64 pathology in new clothes.
    LADDER_REUSE_BOUND = 64
    #: Growth-factor clamp for the jump extrapolation: the first levels of
    #: a fanning space show the raw out-degree (17x for 2pc rm=8), which
    #: would extrapolate straight past every useful rung.
    LADDER_GROWTH_CLAMP = 16.0

    def _compiled_run_caps(self) -> set:
        """Run buckets holding a live compiled program for the dispatch
        flavor and engine config this checker would actually invoke."""
        fused = self._levels_per_dispatch > 1
        tail_want = (self._sym_tag, self._max_probes, self._dedup, self._compaction)
        caps = set()
        for k in self._superstep_cache:
            if fused != (k[0] == "fused"):
                continue
            if fused:
                f_cap, tail = k[1], k[3:]
                current = k[2] == tuple(self._cand_rungs(f_cap))
            else:
                f_cap, tail = k[0], k[2:]
                current = k[1] == self._cand_cap_for(f_cap)
            if tail == tail_want and current:
                caps.add(f_cap)
        return caps

    def _recent_growth(self) -> Optional[float]:
        """Frontier growth factor across the last two committed levels, or
        None when there is no (positive-growth) signal yet."""
        if len(self.level_log) < 2:
            return None
        a = self.level_log[-2]["frontier"]
        b = self.level_log[-1]["frontier"]
        if a <= 0 or b <= a:
            return None
        return b / a

    def _grow_frontier(self, run_cap: int) -> int:
        """Next bucket after a frontier-compaction overflow: one
        power-of-four rung ("ramp"), or a growth-extrapolated jump over
        several rungs ("jump"), or — past the top bucket — a doubled
        frontier-capacity ceiling. Returns the new run capacity.

        The jump estimate: the overflowed width is at least ``run_cap``;
        with the frontier growing by observed factor ``g`` per level and
        growth factors decaying as the peak nears, ``run_cap * g^2`` is a
        usable peak forecast — undershoot costs one more overflow round
        (exactly what ramp would have paid anyway), overshoot costs
        bounded padding. Measured on 2pc rm=8 widths this lands 3 compiled
        buckets instead of 8."""
        self._counters.inc("frontier_grows")
        if run_cap < self._frontier_capacity:
            buckets = ladder_buckets(self._frontier_capacity)
            ramp = next(b for b in buckets if b > run_cap)
            nxt = ramp
            if self._ladder == "jump":
                g = self._recent_growth()
                if g is not None and g >= 2.0:
                    est_peak = run_cap * min(g, self.LADDER_GROWTH_CLAMP) ** 2
                    jump = next(
                        (b for b in buckets if b >= 4 * est_peak), buckets[-1]
                    )
                    nxt = max(nxt, jump)
            if nxt > ramp:
                self._counters.inc("ladder_jumps")
            return nxt
        self._frontier_capacity *= 2
        self._model.__dict__["_xla_frontier_cap_hint"] = self._frontier_capacity
        return self._frontier_capacity

    def _run_cap_for(self, n: int) -> int:
        """Smallest power-of-FOUR run capacity with ~4x expansion headroom
        over the live frontier, clamped to [64, frontier_capacity].
        Powers of four keep the compiled-bucket count low (each distinct
        run capacity is a separate XLA compilation).

        The 64-row floor matters for the deep-narrow spaces the
        consistency testers produce (round-3 on-chip finding: ABD 2c/2s
        never widens past 54 rows, so a 1024-row floor paid a ~1000x
        action-grid padding tax per level — measured 66x end-to-end on
        CPU). Wide spaces ramp through at most two extra small buckets
        (64, 256), each a far cheaper XLA compile than the big ones and
        persistent-cache-amortized across runs.

        Under the "jump" ladder, an already-compiled bucket within
        ``LADDER_REUSE_BOUND`` of the snug one is preferred: re-entering
        mid-space (bench measured pass, target-bounded runs) must ride
        the warm pass's compilations, not pay fresh ones."""
        want = max(4 * max(n, 1), RUN_BUCKET_FLOOR)
        buckets = ladder_buckets(self._frontier_capacity)
        cap = next((b for b in buckets if b >= want), buckets[-1])
        if self._ladder == "jump":
            reusable = [
                c
                for c in self._compiled_run_caps()
                if cap <= c <= cap * self.LADDER_REUSE_BOUND
            ]
            if reusable:
                return min(reusable)
        return cap

    def _bucket_inputs(self, run_cap: int):
        """Pad or slice the stored frontier to this dispatch's bucket."""
        import jax
        import jax.numpy as jnp

        stored = self._frontier.shape[0]
        if stored < run_cap:
            f_in = jnp.concatenate(
                [self._frontier, jnp.zeros((run_cap - stored, self._W), jnp.uint32)]
            )
            e_in = jnp.concatenate(
                [self._frontier_ebits, jnp.zeros((run_cap - stored,), jnp.uint32)]
            )
        elif stored > run_cap:
            f_in = jax.lax.slice_in_dim(self._frontier, 0, run_cap)
            e_in = jax.lax.slice_in_dim(self._frontier_ebits, 0, run_cap)
        else:
            f_in, e_in = self._frontier, self._frontier_ebits
        return f_in, e_in

    def _property_counts(self, levels) -> Dict[str, int]:
        """``property_rows``: rows the property stage evaluated over the
        ``level_log`` rows ``levels``; ``property_block_rows``: its
        row-block width where that is under the top bucket, else 0."""
        block = self._prop_block
        return {
            "property_rows": sum(
                property_rows(lv["frontier"], lv["bucket"], block) for lv in levels
            ),
            "property_block_rows": block if block < self._frontier_capacity else 0,
        }

    def _pin_found_names(self) -> None:
        """Records first-found witness fingerprints by property name."""
        found = np.asarray(self._disc_found)
        fps = np.asarray(self._disc_fp)
        for i, name in enumerate(self._prop_names):
            if found[i] and name not in self._found_names:
                self._found_names[name] = (int(fps[i, 0]) << 32) | int(fps[i, 1])

    def _run_block(self, max_count: int = 1500) -> None:
        """One dispatch per call: one BFS level (``levels_per_dispatch=1``)
        or an on-device block of up to that many levels."""
        if self._levels_per_dispatch > 1:
            return self._run_block_fused()
        return self._run_block_single()

    def _entry_checks(self) -> bool:
        """Shared dispatch preamble; returns False when nothing to run.
        Mirrors the dequeue-time depth bookkeeping (bfs.rs:257-272): a
        frontier at the target depth is counted in max_depth but skipped."""
        if self._target_reached or self._exhausted:
            return False
        if self._P > 0 and all(n in self._found_names for n in self._prop_names):
            return False
        if self._frontier_count == 0:
            self._exhausted = True
            return False
        self._max_depth = max(self._max_depth, self._depth)
        if self._target_max_depth is not None and self._depth >= self._target_max_depth:
            self._frontier_count = 0
            self._exhausted = True
            return False
        return True

    def _run_block_fused(self) -> None:
        """Up to ``levels_per_dispatch`` BFS levels in one device call (see
        ``_build_fused``). Overflow exits commit every level before the
        overflowing one, grow, and re-enter with the remaining budget."""
        import jax.numpy as jnp

        if not self._entry_checks():
            return

        budget_left = self._levels_per_dispatch
        if self._target_max_depth is not None:
            budget_left = min(budget_left, self._target_max_depth - self._depth)
        run_cap = self._run_cap_for(self._frontier_count)
        retry = False  # re-entering after an overflow recovery
        while budget_left > 0:
            # Keep the block's int32 generated-state accumulator safe.
            kmax = max(1, (2**31 - 1) // max(run_cap * self._A, 1))
            budget = min(budget_left, kmax)
            remaining = 2**31 - 1
            if self._target_state_count is not None:
                remaining = max(
                    1, min(remaining, self._target_state_count - self._state_count)
                )
            host_found = np.array(
                [name in self._found_names for name in self._prop_names], dtype=bool
            )
            # Will THIS call trace + compile? The dispatch span and the
            # heartbeat phase carry the flag so watchdogs can tell a
            # long compile from a wedge.
            fresh = self._mark_dispatch_shape(self._fused_key(run_cap))
            # Shrink-exit threshold: the tail of a space collapses while
            # the fused loop is pinned to the peak bucket, paying the full
            # grid-compaction sort per level. If a smaller bucket already
            # holds a live compiled program, ask the device to exit once
            # the frontier fits it with 4x headroom — the re-dispatch then
            # reuses that program, so this can never trigger a compile.
            # Tiny buckets aren't worth the extra host round-trip.
            shrink_below = 0
            if self._shrink_exit and run_cap > 256:
                smaller = [c for c in self._compiled_run_caps() if c < run_cap]
                if smaller:
                    shrink_below = max(smaller) // 4
            # Seed the device-side candidate estimate with the last two
            # committed levels' generated counts (the host's level_log is
            # the cross-dispatch memory; runtime scalars, zero compiles).
            prev_gen = self.level_log[-1]["generated"] if self.level_log else 0
            prev2_gen = (
                self.level_log[-2]["generated"] if len(self.level_log) > 1 else 0
            )
            if self._heartbeat is not None:
                self._heartbeat.beat(
                    "dispatch", compile=fresh, bucket=run_cap,
                    depth=self._depth, states=self._state_count,
                )
            with self._tracer.span(
                "dispatch", flavor="fused", bucket=run_cap,
                # Attr expressions are evaluated even when the null span
                # discards them — keep the off path allocation-free by
                # gating the rung-list build on the tracer being live.
                cand=(
                    [list(r) for r in self._cand_rungs(run_cap)]
                    if self._tracer.enabled
                    else None
                ),
                compile=fresh, retry=retry, dedup=self._dedup,
                compaction=self._compaction, shrink_below=shrink_below,
            ) as _sp:
                with self._tracer.span("dispatch:prepare"):
                    f_in, e_in = self._bucket_inputs(run_cap)
                    fn = self._fused_for(run_cap)
                    _args = (
                        f_in,
                        e_in,
                        self._frontier_count,
                        self._table,
                        self._disc_found,
                        self._disc_fp,
                        jnp.int32(budget),
                        jnp.int32(remaining),
                        jnp.asarray(host_found),
                        jnp.int32(shrink_below),
                        jnp.int32(min(prev_gen, 2**31 - 1)),
                        jnp.int32(min(prev2_gen, 2**31 - 1)),
                    )
                with self._tracer.span("dispatch:run"):
                    (
                        committed,
                        nf,
                        ne,
                        ncount,
                        table,
                        dfound,
                        dfp,
                        tot_states,
                        tot_unique,
                        ovf,
                        hv_w,
                        hv_f,
                        hv_c,
                        lvl_frontier,
                        lvl_states,
                        lvl_unique,
                        lvl_bucket,
                        lvl_cand,
                        _prev_gen,
                        _prev2_gen,
                        _force_full,
                        n_retries,
                    ) = fn(*_args)
                    # Commit the non-overflowing prefix of the block. The
                    # int() blocks until the device program finishes, so
                    # this span covers the device run — and reuses a sync
                    # the commit below needs anyway.
                    committed = int(committed)
                _sp.set(committed=committed)
                with self._tracer.span("dispatch:commit"):
                    self.dispatch_log.append((run_cap, committed))
                    if self._heartbeat is not None:
                        self._heartbeat.commit(
                            depth=self._depth + committed,
                            states=self._state_count + int(tot_states),
                        )
                    retry = False
                    self._frontier, self._frontier_ebits, self._table = nf, ne, table
                    self._frontier_count = int(ncount)
                    self._disc_found, self._disc_fp = dfound, dfp
                    self._state_count += int(tot_states)
                    self._unique_count += int(tot_unique)
                    self.cand_retries += int(n_retries)
                    if committed:
                        lvf = np.asarray(lvl_frontier)
                        lvs = np.asarray(lvl_states)
                        lvu = np.asarray(lvl_unique)
                        lvb = np.asarray(lvl_bucket)
                        lvc = np.asarray(lvl_cand)
                        self.level_log.extend(
                            {
                                "depth": self._depth + i,
                                "frontier": int(lvf[i]),
                                "generated": int(lvs[i]),
                                "unique": int(lvu[i]),
                                "sym": self._sym_tag,
                                # Dispatch-shape telemetry: the (rows, cand)
                                # sub-widths this level actually ran at and the
                                # cost-law lane-words they imply (the ladder A/B's
                                # engine-measured evidence).
                                "bucket": int(lvb[i]),
                                "cand_cap": int(lvc[i]),
                                "lane_words": self._level_lane_words(
                                    int(lvb[i]), int(lvc[i])
                                ),
                            }
                            for i in range(committed)
                        )
                    if self._tracer.enabled:
                        _sp.set(**self._property_counts(
                            self.level_log[len(self.level_log) - committed:]
                        ))
                    self._depth += committed
                    if committed:
                        self._max_depth = max(self._max_depth, self._depth - 1)
                    budget_left -= committed
                    cap_before = self._table.capacity
                    self._grow_table_if_loaded()
                    grew_proactively = self._table.capacity > cap_before
                    if self._hv_idx:
                        self._confirm_hv_candidates(hv_w, hv_f, hv_c)
                    self._pin_found_names()
                    # Quiescent point: the committed prefix is fully reflected in
                    # host-visible state (even when this iteration ended on an
                    # overflow — the overflowing level was not committed).
                    self._maybe_checkpoint()
                    self._maybe_record()
                    if (
                        self._target_state_count is not None
                        and self._state_count >= self._target_state_count
                    ):
                        self._target_reached = True
                        return
                    t_ovf, f_ovf, c_ovf, cc_ovf = (bool(x) for x in np.asarray(ovf))
                    if c_ovf:
                        self._raise_codec_overflow()
                    if t_ovf:
                        # The proactive pass above may already have doubled past
                        # the blockage; only resolve again if it did not (every
                        # extra doubling is 2x memory AND a fresh shape compile).
                        if not grew_proactively:
                            self._resolve_table_overflow()
                        retry = True
                        continue
                    if f_ovf:
                        run_cap = self._grow_frontier(run_cap)
                        retry = True
                        continue
                    if cc_ovf:
                        self._grow_cand_cap(run_cap)
                        retry = True
                        continue
                    if self._frontier_count == 0 or committed == 0:
                        break
                    if self._P > 0 and all(
                        name in self._found_names for name in self._prop_names
                    ):
                        break
                    # A shrink-exit (committed block, no overflow, live frontier
                    # at or below the threshold): drop to the snuggest compiled
                    # bucket that still has 4x expansion headroom.
                    if shrink_below and self._frontier_count <= shrink_below:
                        snug = [
                            c
                            for c in self._compiled_run_caps()
                            if c < run_cap and self._frontier_count <= c // 4
                        ]
                        if snug:
                            run_cap = min(snug)
                            self._counters.inc("shrink_exits")

    def _run_block_single(self) -> None:
        """One BFS level per call (level-synchronous super-step)."""
        if not self._entry_checks():
            return

        if self._visitor is not None:
            self._visit_frontier()

        # Adaptive run capacity: BFS levels ramp up and down, but a fixed
        # [frontier_capacity, A] expansion pays full freight on padding
        # lanes every level. Run each level at the smallest compiled bucket
        # with ~4x headroom over the live frontier; a frontier overflow
        # retries at the next bucket (safe — the pre-step table is a
        # functional value, untouched until we commit). The stored frontier
        # keeps whatever row count the last level ran at (always >=
        # frontier_count — every consumer slices [:frontier_count]); it is
        # padded or sliced lazily to this level's bucket, so per-level cost
        # is O(run_cap), not O(frontier_capacity).
        run_cap = self._run_cap_for(self._frontier_count)
        retry = False  # re-running the level after an overflow recovery
        while True:  # retried only on capacity growth
            fresh = self._mark_dispatch_shape(self._superstep_key(run_cap))
            if self._heartbeat is not None:
                self._heartbeat.beat(
                    "dispatch", compile=fresh, bucket=run_cap,
                    depth=self._depth, states=self._state_count,
                )
            with self._tracer.span(
                "dispatch", flavor="single", bucket=run_cap,
                cand=self._cand_cap_for(run_cap), compile=fresh,
                retry=retry, dedup=self._dedup,
                compaction=self._compaction,
            ) as _sp:
                with self._tracer.span("dispatch:prepare"):
                    f_in, e_in = self._bucket_inputs(run_cap)
                    fn = self._superstep_for(run_cap)
                    _args = (
                        f_in,
                        e_in,
                        self._frontier_count,
                        self._table,
                        self._disc_found,
                        self._disc_fp,
                    )
                with self._tracer.span("dispatch:run"):
                    (
                        nf,
                        ne,
                        ncount,
                        table,
                        dfound,
                        dfp,
                        d_states,
                        d_unique,
                        t_ovf,
                        f_ovf,
                        c_ovf,
                        cc_ovf,
                        hv_words,
                        hv_fps,
                        hv_counts,
                    ) = fn(*_args)
                    # The bool() reads block until the device program
                    # finishes — this span covers the device run using
                    # syncs the commit logic pays anyway.
                    committed = not (bool(t_ovf) or bool(f_ovf) or bool(cc_ovf))
                _sp.set(committed=int(committed))
                with self._tracer.span("dispatch:commit"):
                    self.dispatch_log.append((run_cap, int(committed)))
                    if self._heartbeat is not None:
                        self._heartbeat.commit(
                            depth=self._depth, states=self._state_count
                        )
                    if bool(c_ovf):
                        self._raise_codec_overflow()
                    if bool(t_ovf):
                        # Functional arrays: the pre-step table is untouched;
                        # flush (delta) or grow, then re-run the same level.
                        self._resolve_table_overflow()
                        retry = True
                        continue
                    if bool(f_ovf):
                        run_cap = self._grow_frontier(run_cap)
                        retry = True
                        continue
                    if bool(cc_ovf):
                        self._grow_cand_cap(run_cap)
                        retry = True
                        continue
                    self.level_log.append(
                        {
                            "depth": self._depth,
                            "frontier": self._frontier_count,
                            "generated": int(d_states),
                            "unique": int(d_unique),
                            "sym": self._sym_tag,
                            # The one-level path picks its snug bucket host-side, so
                            # its dispatch-shape telemetry is the run bucket itself
                            # (the in-program ladder applies to fused dispatch only).
                            "bucket": run_cap,
                            "cand_cap": self._cand_cap_for(run_cap),
                            "lane_words": self._level_lane_words(
                                run_cap, self._cand_cap_for(run_cap)
                            ),
                        }
                    )
                    if self._tracer.enabled:
                        _sp.set(**self._property_counts(self.level_log[-1:]))
                    self._frontier, self._frontier_ebits, self._table = nf, ne, table
                    self._frontier_count = int(ncount)
                    self._disc_found, self._disc_fp = dfound, dfp
                    self._state_count += int(d_states)
                    self._unique_count += int(d_unique)
                    self._depth += 1
                    self._grow_table_if_loaded()
                    if self._hv_idx:
                        self._confirm_hv_candidates(hv_words, hv_fps, hv_counts)
                    self._pin_found_names()
                    self._maybe_checkpoint()
                    self._maybe_record()
                    if (
                        self._target_state_count is not None
                        and self._state_count >= self._target_state_count
                    ):
                        self._target_reached = True
                    break

    def _confirm_hv_candidates(self, hv_words, hv_fps, hv_counts) -> None:
        """Exact host-side re-check of device-flagged candidate states for
        host-verified properties (SURVEY §7 M4a): the first candidate (in
        frontier order) whose exact condition confirms the violation/example
        becomes the discovery. Conditions like the linearizability testers
        memoize per distinct history, so repeat candidates are cheap."""
        counts = np.asarray(hv_counts)
        words = fps = None
        _sp = self._tracer.span("host_verify")
        _checked0 = self.hv_stats["host_checked"]
        _conf0 = self.hv_stats["confirmed"]
        t0 = time.monotonic()
        with _sp:
            try:
                for j, i in enumerate(self._hv_idx):
                    prop = self._properties[i]
                    if prop.name in self._found_names:
                        continue
                    n = int(counts[j])
                    if n == 0:
                        continue
                    self.hv_stats["flagged"] += n
                    if words is None:
                        words = np.asarray(hv_words)
                        fps = np.asarray(hv_fps)
                    confirmed = False
                    for r in range(min(n, self._hv_cap)):
                        state = self._model.unpack(words[j, r])
                        holds = bool(prop.condition(self._model, state))
                        viol = (
                            (not holds)
                            if prop.expectation == Expectation.ALWAYS
                            else holds
                        )
                        self.hv_stats["host_checked"] += 1
                        if viol:
                            fp64 = (int(fps[j, r, 0]) << 32) | int(fps[j, r, 1])
                            self._found_names[prop.name] = fp64
                            confirmed = True
                            self.hv_stats["confirmed"] += 1
                            break
                        self.hv_stats["cleared"] += 1
                    if not confirmed and n > self._hv_cap:
                        raise RuntimeError(
                            f"{n} candidate states for host-verified property "
                            f"{prop.name!r} in one super-step, none of the "
                            f"first {self._hv_cap} confirmed — tighten the "
                            "conservative device predicate or raise the "
                            "candidate cap."
                        )
            finally:
                # Inner finally: attrs land before the span's __exit__
                # emits the line, including on the over-cap raise path.
                _sp.set(
                    checked=self.hv_stats["host_checked"] - _checked0,
                    confirmed=self.hv_stats["confirmed"] - _conf0,
                )
                self.hv_stats["host_sec"] += time.monotonic() - t0

    def _visit_frontier(self) -> None:
        """Applies the visitor to every frontier state's path (the XLA
        analogue of bfs.rs:274-276). Host-side path reconstruction re-executes
        the object model per state and would appear to hang on big frontiers,
        so levels wider than ``spawn_xla(visit_cap=...)`` are truncated with
        a loud warning — visitors are a debug/recording surface, not part of
        checking semantics."""
        n = self._frontier_count
        if n > self._visit_cap:
            import warnings

            warnings.warn(
                f"visitor: frontier has {n} states at depth {self._depth}; "
                f"visiting only the first {self._visit_cap} (host-side path "
                "reconstruction per state does not scale — use visitors on "
                "small runs, or raise spawn_xla(visit_cap=...))",
                RuntimeWarning,
                stacklevel=2,
            )
        rows = self._frontier_rows_host()[: min(n, self._visit_cap)]
        parents = self._parent_map()
        for row in rows:
            fp = fphash.fingerprint_u64(self._dedup_words_host(row[None, :])[0], np)
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
        """One unified snapshot of the engine's telemetry (the registry
        half of stateright_tpu/obs): configuration gauges, live search
        gauges, and the event counters that used to live scattered across
        ``cand_retries`` / ``dispatch_log`` / ad-hoc logs. Pure host-side
        reads — safe to poll mid-run (the Explorer's ``/.status`` does).
        Key set is stable across dedup structures (pinned by
        tests/test_obs.py); schema in docs/observability.md."""
        cap = self._table.capacity
        job = (
            {"job_id": self._service_job_id}
            if self._service_job_id is not None
            else {}
        )
        return {
            **job,
            "engine": "xla",
            "backend": self._jax.default_backend(),
            # -- configuration gauges ---------------------------------
            "dedup": self._dedup,
            "compaction": self._compaction,
            # How the widest bucket's grid compaction recovers each
            # candidate's parent fingerprint and ebits (parent_lowering).
            "parent_lowering": self._parent_lowering(self._frontier_capacity),
            "symmetry": self._sym_tag,
            "ladder": self._ladder,
            "cand_ladder_k": self._cand_ladder_k,
            "shrink_exit": self._shrink_exit,
            "levels_per_dispatch": self._levels_per_dispatch,
            "checkpoint_to": self._autockpt.path if self._autockpt else None,
            "metrics_to": self._recorder.path if self._recorder else None,
            # -- recovery gauges (docs/observability.md "Recovery") ----
            "resumed_from": self._resumed_from,
            "last_checkpoint_level": (
                self._last_checkpoint["depth"] if self._last_checkpoint else None
            ),
            # -- live search gauges -----------------------------------
            "state_count": self._state_count,
            "unique_state_count": self._unique_count,
            "depth": self._depth,
            "max_depth": self._max_depth,
            "frontier_count": self._frontier_count,
            "frontier_capacity": self._frontier_capacity,
            "table_capacity": cap,
            "table_occupancy": self._unique_count / max(cap, 1),
            "dispatches": len(self.dispatch_log),
            "levels_committed": sum(c for _, c in self.dispatch_log),
            "cand_retries": self.cand_retries,
            # Rows the property stage evaluated over the committed levels,
            # and its row-block width (0: whole buckets).
            **self._property_counts(self.level_log),
            "hv": dict(self.hv_stats),
            # -- event counters (obs.Counters, pre-seeded) ------------
            **self._counters.snapshot(),
        }

    def is_done(self) -> bool:
        if self._exhausted or self._target_reached:
            return True
        if self._P > 0 and all(n in self._found_names for n in self._prop_names):
            return True
        return self._frontier_count == 0 and self._state_count > 0

    def discoveries(self) -> Dict[str, Path]:
        parents = self._parent_map()
        return {
            name: self._path_for(fp64, parents)
            for name, fp64 in self._found_names.items()
        }

    def _parent_map(self):
        """Pulls the device table once and indexes fp64 -> parent fp64
        (C++ open-addressing index when the native toolchain is present —
        building a Python dict over millions of slots is the host hot spot
        of witness reconstruction; see stateright_tpu/native)."""
        from .native import ParentMap

        return ParentMap(
            np.asarray(self._table.key_hi),
            np.asarray(self._table.key_lo),
            np.asarray(self._table.val_hi),
            np.asarray(self._table.val_lo),
        )

    def _path_for(self, fp64: int, parents) -> Path:
        """Walks parent fingerprints back to an init state, then re-executes
        the object model forward (bfs.rs:430-459 + path.rs:20-97, with the
        packed fingerprint as the digest). ``parents`` is a
        ``native.ParentMap``; the whole walk is one native call."""
        try:
            chain: List[int] = parents.chain(fp64)
        except KeyError as e:
            raise RuntimeError(
                f"{e.args[0]} during path reconstruction; packed model "
                "host/device codecs disagree"
            ) from None
        chain.reverse()

        model = self._model
        last_state = None
        for s in model.init_states():
            if self._packed_fp64(s) == chain[0]:
                last_state = s
                break
        if last_state is None:
            raise RuntimeError(
                "No init state matches the first fingerprint of a discovery "
                "path. The packed codec (pack/packed_init) and the object "
                "model disagree, or packed_step diverges from next_state."
            )
        pairs = []
        for next_fp in chain[1:]:
            found = None
            for action, state in model.next_steps(last_state):
                if self._packed_fp64(state) == next_fp:
                    found = (action, state)
                    break
            if found is None:
                raise RuntimeError(
                    f"No successor of {last_state!r} matches fingerprint "
                    f"{next_fp:#x}: packed_step and next_state disagree."
                )
            pairs.append((last_state, found[0]))
            last_state = found[1]
        pairs.append((last_state, None))
        return Path(pairs)
