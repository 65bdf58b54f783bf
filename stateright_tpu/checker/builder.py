"""CheckerBuilder: fluent checker configuration.

Mirrors ``/root/reference/src/checker.rs:52-248``.  The strategy boundary —
``spawn_bfs`` / ``spawn_dfs`` / ``spawn_on_demand`` / ``serve`` — is preserved
and extended with ``spawn_xla()``, the TPU frontier-expansion engine.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from ..core import Model
from .base import Checker
from .visitor import as_visitor


class CheckerBuilder:
    """Instantiate via ``model.checker()`` (lib.rs:247)."""

    def __init__(self, model: Model):
        self._model = model
        self._symmetry: Optional[Callable[[Any], Any]] = None
        self._target_state_count: Optional[int] = None
        self._target_max_depth: Optional[int] = None
        self._thread_count: int = 1
        self._visitor = None

    # --- terminal strategies ---------------------------------------------

    def spawn_bfs(self) -> Checker:
        """Breadth-first search; shortest witness paths (checker.rs:155).

        With ``threads(n)`` for n > 1 (and no visitor), a level-synchronous
        multiprocess engine expands the frontier across n forked workers
        with fingerprint-sharded visited sets
        (``stateright_tpu.checker.parallel_host``) — the host analogue of
        the reference's worker pool (bfs.rs:89-211)."""
        if (self._thread_count or 1) > 1 and self._visitor is None:
            from .parallel_host import ParallelBfsChecker

            return ParallelBfsChecker(self)
        from .search import BfsChecker

        return BfsChecker(self)

    def spawn_dfs(self) -> Checker:
        """Depth-first search; smaller frontier (checker.rs:187). With
        ``threads(n)`` for n > 1 (and no visitor — visitors observe
        per-state paths sequentially, so they fall back to the sequential
        engine exactly as ``spawn_bfs`` does), the job-market parallel
        DFS — the reference's default CLI discipline (dfs.rs:42,
        92-215)."""
        if (self._thread_count or 1) > 1 and self._visitor is None:
            from .parallel_dfs import ParallelDfsChecker

            return ParallelDfsChecker(self)
        from .search import DfsChecker

        return DfsChecker(self)

    def spawn_on_demand(self, engine: str = "host", **spawn_kwargs) -> Checker:
        """Demand-driven search: computes nothing until asked
        (checker.rs:171). ``engine="xla"`` runs it on the device engine
        (packed models; ``spawn_kwargs`` are ``spawn_xla`` capacities) —
        targeted expansions dispatch compiled super-steps and
        ``run_to_completion()`` hands over to the fused batch engine.
        The host engine accepts ``block_size`` (default 1): with the
        reference's 1500 a ``check_fingerprint`` pre-computes up to that
        many states of the clicked subtree (on_demand.rs:209-218)."""
        if engine == "xla":
            from .device_on_demand import DeviceOnDemandChecker

            return DeviceOnDemandChecker(self, **spawn_kwargs)
        unknown = set(spawn_kwargs) - {"block_size"}
        if unknown:
            raise TypeError(
                f"spawn kwargs {sorted(unknown)} only apply to engine=\"xla\""
            )
        try:
            from .on_demand import OnDemandChecker
        except ImportError as e:
            raise NotImplementedError(
                "spawn_on_demand() is not available yet in this build"
            ) from e
        return OnDemandChecker(self, **spawn_kwargs)

    def spawn_xla(self, *, mesh=None, **kwargs) -> Checker:
        """TPU/XLA frontier-expansion engine: the whole BFS frontier is
        expanded per device super-step with vmapped packed transitions,
        device-resident hash-set dedup, and fused property evaluation.

        Requires the model to implement the :class:`PackedModel` protocol
        (see ``stateright_tpu.xla`` for the contract).

        Engine-tuning knobs ride through ``kwargs`` to ``XlaChecker``:
        ``dedup=``, ``compaction=``, ``ladder=``, ``shrink_exit=``, and
        ``cand_ladder=`` (the in-program candidate-width ladder: fused
        dispatches branch over up to K=3 sub-width supersteps via
        ``lax.switch``, so narrow levels sort snug candidate buffers with
        zero added host round-trips; ``STPU_CAND_LADDER`` is the env
        form, 1 disables, planes engine only).

        Observability (``stateright_tpu.obs``, docs/observability.md):
        ``trace=`` appends wall-clock spans around every host↔device
        boundary as JSONL (env ``STPU_TRACE``; ``STPU_TRACE_CHROME``
        additionally exports Chrome trace-event JSON for Perfetto), and
        ``heartbeat=`` names a small JSON file rewritten around every
        device dispatch so watchdogs can tell a hung dispatch from a
        long XLA compile (env ``STPU_HEARTBEAT``). Both off by default;
        neither adds device syncs. ``checker.metrics()`` returns the
        unified counters/gauges snapshot either way. Every span is also
        a ``jax.profiler`` annotation, so inside a profiler session the
        engine's spans sit on the device trace's clock; the first call
        installs ``obs.compile_log``, JAX's compile durations.

        With ``mesh`` (a ``jax.sharding.Mesh`` with one axis, more than one
        device), the frontier and visited set shard by fingerprint ownership
        over the mesh with all-to-all routing per super-step
        (``stateright_tpu.parallel``; the single-chip tuning knobs above
        do not apply there).
        """
        try:
            from ..xla import XlaChecker
        except ImportError as e:
            raise NotImplementedError(
                "spawn_xla() is not available yet in this build"
            ) from e
        from .. import obs

        obs.install_compile_log()
        if mesh is not None and mesh.devices.size > 1:
            from ..parallel import ShardedXlaChecker

            return ShardedXlaChecker(self, mesh, **kwargs)
        kwargs.pop("route_capacity", None)  # sharded-only tuning knob
        return XlaChecker(self, **kwargs)

    def serve(self, addresses, engine: str = "auto", **spawn_kwargs) -> Checker:
        """Starts the interactive Explorer web service (checker.rs:137).
        Packed models are explored on the DEVICE engine by default
        (``engine="auto"``); pass ``engine="host"`` to force the Python
        oracle."""
        try:
            from .explorer import serve
        except ImportError as e:
            raise NotImplementedError(
                "serve() is not available yet in this build"
            ) from e
        return serve(self, addresses, engine=engine, **spawn_kwargs)

    # --- configuration ----------------------------------------------------

    def symmetry(self) -> "CheckerBuilder":
        """Enables symmetry reduction; states must define
        ``representative()`` (checker.rs:198-203)."""
        return self.symmetry_fn(lambda s: s.representative())

    def symmetry_fn(self, representative: Callable[[Any], Any]) -> "CheckerBuilder":
        self._symmetry = representative
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        """The checker may exceed this count but never stops short of it
        while more states exist (checker.rs:215-222)."""
        self._target_state_count = count if count > 0 else None
        return self

    def target_max_depth(self, depth: int) -> "CheckerBuilder":
        self._target_max_depth = depth if depth > 0 else None
        return self

    def threads(self, thread_count: int) -> "CheckerBuilder":
        """Worker count for the host engines (checker.rs:234). With n > 1,
        ``spawn_bfs`` runs the multiprocess level-synchronous engine
        (``stateright_tpu.checker.parallel_host``) and ``spawn_dfs`` the
        job-market parallel DFS (``stateright_tpu.checker.parallel_dfs``);
        with a visitor both fall back to their sequential engines. The
        massively parallel form in this framework is the XLA engine, which
        uses every core of every chip regardless of this setting."""
        self._thread_count = thread_count
        return self

    def visitor(self, visitor) -> "CheckerBuilder":
        """A function (or CheckerVisitor) applied to every evaluated path
        (checker.rs:242-247)."""
        self._visitor = as_visitor(visitor)
        return self
