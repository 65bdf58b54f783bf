"""The registered kernel surfaces stpu-lint sweeps.

A *surface* is one traceable device program the repo ships: a packed
model's vmapped transition/property kernels, an engine superstep at a
concrete dedup x compaction configuration, a fused multi-level dispatch,
the multiplexed (K-lane-batched) superstep, one of the standalone ops
programs (deltaset ``maintain``, hashset ``insert``), or a Pallas kernel. Each surface traces to a ``ClosedJaxpr``
on the CPU backend — no device, no execution, no XLA compile — and
declares which rule scans apply:

- kernel surfaces take STPU001/STPU002 (the two pinned vmapped-kernel
  miscompiles) — these must be checked on the STANDALONE vmapped kernel,
  because engine-level programs legitimately contain scatters (the rows
  engine's cumsum+scatter compaction on CPU) that are not the pinned
  shape;
- engine surfaces take STPU003 (sort width, W-dependent) and — for
  delta-dedup programs — STPU004 (no flush under cond);
- Pallas surfaces take the STPU005 static scans plus the mandatory TPU
  lowering pre-flight (Mosaic lowering runs host-side, so
  ``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` pre-flights a
  kernel from this CPU-only box; registry #6).

Kernel tracing forces ``packing.ONE_HOT_WRITES = True`` — the
ACCELERATOR lowering of traced-index field writes — exactly like the old
``tests/test_packing.py`` HLO pin this sweep generalizes: the CPU
backend keeps its (correct, O(1)) scatter writes, and linting that path
would only measure the backend split, not the chip invariant.

The default sweep is sized for the <60 s 1-core CI budget: every shipped
spec's kernel surfaces and policy-resolved sorted-engine superstep, plus
the full config matrix (hash rows engine, delta, bsearch/pallas
compaction, fused programs) on one narrow (2pc:3, W=2) and one wide
(paxos:2,3, W=25) model — engine code is shared across models, so the
config matrix varies by W class, not by model count. ``--full`` sweeps
the whole matrix for every spec.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

from .jaxpr_lint import (
    cond_flush_sorts,
    diff_lowering_inventories,
    mosaic_kernel_rules,
    op_inventory,
    output_transposes,
    taint_scatters,
    vmem_budget,
    wide_sorts,
)
from .rules import Finding

#: Batch the kernel surfaces trace at. The pinned scatter drop needs
#: batch >= 4096 at RUNTIME; the jaxpr is structurally batch-independent,
#: but tracing at the dangerous scale keeps the pin honest.
KERNEL_BATCH = 4096

#: Engine-surface trace shapes: small (trace cost only — shapes never
#: run), but divisible by the pallas kernel block so the pallas
#: compaction path engages instead of falling back to the sort.
F_CAP = 1024
CAND_CAP = 1024
TABLE_CAP = 1 << 13
#: Delta-dedup surfaces trace with a bigger main tier: STPU004's
#: "table-scale" threshold is the main capacity C, and the legitimate
#: in-program sorts (the [Dc + batch] delta merge, the A*F_CAP grid
#: compaction inside a fused ladder branch) must sit clearly BELOW it at
#: the trace shapes or they false-positive. C = 2^15 clears the largest
#: legitimate in-cond sort the default sweep traces (2pc fused: 17 *
#: F_CAP = 17408 grid lanes) while the flush shape ([C + Dc] lanes)
#: stays >= C. A fused-delta surface for a model with max_actions *
#: F_CAP >= C would need this raised.
TABLE_CAP_DELTA = 1 << 15

#: The two models the full config matrix runs on by default: one narrow
#: and one wide state (the sort-width classes the compaction policy
#: splits on).
MATRIX_SPECS = ("2pc:3", "paxos:2,3")

#: The STPU_PALLAS_BLOCK values STPU006 prices each pallas kernel at:
#: the compaction's default (pallas_compact.DEFAULT_BLOCK, 1024, the
#: smallest the TPU compiler accepts for it) and the smaller blocks the
#: tests select in interpret mode. The VMEM footprint scales with the
#: block, so the budget must hold across the whole range.
SUPPORTED_PALLAS_BLOCKS = (256, 512, 1024)

#: The virtual CPU mesh width the sharded-engine surface traces under —
#: the same 8-device mesh tests/conftest.py forces for the mesh tests.
MESH_DEVICES = 8

#: The lane counts the multiplexed-superstep surfaces trace at
#: (xla_mux.py; docs/service.md "Batched scheduling"): the smallest real
#: batch and a mid-size one. The jaxpr is structurally K-independent —
#: like KERNEL_BATCH, two points keep the pin honest without paying a
#: trace per possible K.
MUX_KS = (2, 4)


class SurfaceSkip(Exception):
    """A surface that cannot run in THIS environment (e.g. the sharded
    surface without the 8-device virtual mesh) — reported with its
    reason, not an error: the environment, not the tree, is the cause,
    exactly like the distributed-mesh tests' probe-and-self-skip."""


@dataclass
class SurfaceReport:
    name: str
    findings: List[Finding] = field(default_factory=list)
    seconds: float = 0.0
    #: Non-empty when the surface failed to TRACE (an infrastructure
    #: failure, not a rule finding — the CLI exits 2 on these: a surface
    #: that cannot be checked is not a pass).
    error: str = ""
    #: Non-empty when the surface self-skipped (environment limitation,
    #: not a failure; the reason is the probe's verdict).
    skipped: str = ""
    #: Whether the findings came from the content-hash result cache
    #: (analysis/cache.py) instead of a fresh trace.
    cached: bool = False


def pin_cpu() -> None:
    """The analyzer never touches a device: pin the CPU backend before
    any jax backend use, whatever ``JAX_PLATFORMS`` says. Guarded: on a
    jax lineage where a post-init update raises, an already-CPU process
    proceeds; anything else is a real configuration error. Also asks the
    CPU client for the 8-device virtual mesh (read at CPU-client init,
    so it must be set here, before the first backend use) so the sharded
    engine surface can trace — a backend that initialized earlier with
    fewer devices makes that one surface self-skip, never fail."""
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={MESH_DEVICES}"
        ).strip()
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:  # pragma: no cover - backend already initialized
        if jax.default_backend() != "cpu":
            raise


def _jnp():
    import jax

    import jax.numpy as jnp

    return jax, jnp


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _trace(fn, *args):
    import jax

    return jax.make_jaxpr(fn)(*args)


def _step3(model, jnp):
    def step3(words):
        out = model.packed_step(words)
        if len(out) == 3:
            return out
        nxt, valid = out
        return nxt, valid, jnp.zeros_like(valid)

    return step3


# --- surface builders -------------------------------------------------------


def _kernel_surfaces(spec: str, model) -> List[Tuple[str, Callable[[], List[Finding]]]]:
    jax, jnp = _jnp()
    W = model.state_words
    rows = _sds((KERNEL_BATCH, W), jnp.uint32)

    def scan(name, fn):
        def run():
            jx = _trace(jax.vmap(fn), rows)
            return (
                taint_scatters(jx, name)
                + output_transposes(jx, name)
                + wide_sorts(jx, name)
            )

        return run

    out = [
        (f"kernel:{spec}:packed_step", scan(f"kernel:{spec}:packed_step", model.packed_step)),
        (
            f"kernel:{spec}:packed_properties",
            scan(f"kernel:{spec}:packed_properties", model.packed_properties),
        ),
    ]
    if hasattr(model, "packed_representative"):
        out.append(
            (
                f"kernel:{spec}:packed_representative",
                scan(
                    f"kernel:{spec}:packed_representative",
                    model.packed_representative,
                ),
            )
        )
    if getattr(model, "symmetry_spec", None) is not None:
        # The spec-compiled canonicalization kernel (stateright_tpu/sym;
        # docs/symmetry.md): fingerprinting vmaps it over every frontier
        # row when symmetry is on, so it takes the same vmapped-kernel
        # rules as the model's own transition kernels.
        name = f"kernel:{spec}:sym-canon"

        def run_sym(name=name, spec_obj=model.symmetry_spec):
            from ..sym import compile_canon

            jx = _trace(jax.vmap(compile_canon(spec_obj)), rows)
            return (
                taint_scatters(jx, name)
                + output_transposes(jx, name)
                + wide_sorts(jx, name)
            )

        out.append((name, run_sym))

    # The STPU_EXPAND_LAYOUT=planes A/B variant: vmap emits [A, W, F]
    # directly (out_axes=2) — the transpose-fused-into-vmap shape. Kept
    # in the sweep so STPU002 proves it still exists ONLY behind the
    # accelerator-gated knob (the finding is waived with that
    # justification; losing the waiver match means the shape moved).
    name = f"kernel:{spec}:packed_step:planes-expand"

    def run_planes():
        step3 = _step3(model, jnp)
        jx = _trace(jax.vmap(step3, out_axes=(2, 0, 0)), rows)
        return taint_scatters(jx, name) + output_transposes(jx, name)

    out.append((name, run_planes))
    return out


def _lowering_surface(spec: str, model) -> Tuple[str, Callable[[], List[Finding]]]:
    """STPU008: lower the spec's transition kernel for BOTH platforms
    from this CPU box (no device — the STPU005 pre-flight trick) and
    diff the StableHLO op inventories for pathology-registry ops that
    appear on one side only."""
    name = f"lower:{spec}:packed_step"

    def run():
        jax, jnp = _jnp()
        rows = _sds((KERNEL_BATCH, model.state_words), jnp.uint32)
        fn = jax.vmap(model.packed_step)
        inv = {}
        for platform in ("cpu", "tpu"):
            lowered = jax.jit(fn).trace(rows).lower(
                lowering_platforms=(platform,)
            )
            inv[platform] = op_inventory(lowered.as_text())
        return diff_lowering_inventories(name, inv["cpu"], inv["tpu"])

    return name, run


def _sym_lowering_surface(spec: str, model) -> Tuple[str, Callable[[], List[Finding]]]:
    """STPU008 for the spec-compiled canonicalization kernel: diff its
    cpu/tpu StableHLO op inventories the same way the transition kernel
    is diffed — the canon kernel rides every symmetry-on dispatch, so a
    one-sided pathology op there is the same structural miscompile class."""
    name = f"lower:{spec}:sym-canon"

    def run():
        jax, jnp = _jnp()
        from ..sym import compile_canon

        rows = _sds((KERNEL_BATCH, model.state_words), jnp.uint32)
        fn = jax.vmap(compile_canon(model.symmetry_spec))
        inv = {}
        for platform in ("cpu", "tpu"):
            lowered = jax.jit(fn).trace(rows).lower(
                lowering_platforms=(platform,)
            )
            inv[platform] = op_inventory(lowered.as_text())
        return diff_lowering_inventories(name, inv["cpu"], inv["tpu"])

    return name, run


def _superstep_args(checker, model, f_cap: int):
    _, jnp = _jnp()
    P = len(checker._prop_names)
    return (
        _sds((f_cap, model.state_words), jnp.uint32),
        _sds((f_cap,), jnp.uint32),
        _sds((), jnp.int32),
        checker._table,
        _sds((P,), jnp.bool_),
        _sds((P, 2), jnp.uint32),
    )


def _spawn(spec: str, dedup: str, compaction: str = "auto"):
    from ..service.registry import resolve

    model, _ = resolve(spec)
    checker = model.checker().spawn_xla(
        dedup=dedup,
        compaction=compaction,
        frontier_capacity=F_CAP,
        table_capacity=TABLE_CAP_DELTA if dedup == "delta" else TABLE_CAP,
    )
    return model, checker


def _flush_lanes(checker) -> Optional[int]:
    """STPU004's table-scale threshold: the delta structure's main
    capacity (the flush sort is [C + Dc] lanes, every in-program delta
    sort is [Dc + batch] — strictly below C at the trace shapes)."""
    if checker._dedup != "delta":
        return None
    return checker._table.main_capacity


def _engine_surface(spec: str, dedup: str, compaction: str):
    tag = dedup if compaction in ("auto",) else f"{dedup}-{compaction}"
    name = f"engine:{spec}:superstep:{tag}"

    def run():
        model, checker = _spawn(spec, dedup, compaction)
        step = checker._build_superstep(F_CAP, CAND_CAP)
        jx = _trace(step, *_superstep_args(checker, model, F_CAP))
        return (
            wide_sorts(jx, name)
            + cond_flush_sorts(jx, name, _flush_lanes(checker))
            + mosaic_kernel_rules(jx, name)
        )

    return name, run


def _fused_surface(spec: str, dedup: str):
    name = f"engine:{spec}:fused:{dedup}"

    def run():
        jax, jnp = _jnp()
        model, checker = _spawn(spec, dedup)
        rungs = tuple(checker._cand_rungs(F_CAP))
        fused = checker._build_fused(F_CAP, rungs)
        P = len(checker._prop_names)
        scalars = _sds((), jnp.int32)
        args = _superstep_args(checker, model, F_CAP) + (
            scalars,
            scalars,
            _sds((P,), jnp.bool_),
            scalars,
            scalars,
            scalars,
        )
        jx = _trace(fused, *args)
        return (
            wide_sorts(jx, name)
            + cond_flush_sorts(jx, name, _flush_lanes(checker))
            + mosaic_kernel_rules(jx, name)
        )

    return name, run


def _accel_policy_compaction(model) -> str:
    """The compaction the accelerator auto-policy resolves for this
    model's width (the lint runs on CPU, so 'auto' would resolve the
    CPU answer — the sweep must check the path the CHIP runs). Shared
    with the engine: one definition, no drift."""
    from ..xla import accel_auto_compaction

    return accel_auto_compaction(model.state_words)


def _ops_surfaces() -> List[Tuple[str, Callable[[], List[Finding]]]]:
    jax, jnp = _jnp()

    def maintain_run():
        from ..ops import deltaset

        ds = deltaset.make(TABLE_CAP, jnp)
        jx = _trace(deltaset.maintain, ds)
        name = "ops:deltaset-maintain"
        # The maintain sort IS table-scale — the point is that it is a
        # standalone host-invoked program, so it must carry no cond at
        # all around that sort. flush_lanes = main capacity applies.
        return wide_sorts(jx, name) + cond_flush_sorts(
            jx, name, ds.main_capacity
        )

    def hashset_run():
        from ..ops import hashset

        name = "ops:hashset-insert"
        table = hashset.make(TABLE_CAP, jnp)
        n = 512
        u32 = _sds((n,), jnp.uint32)
        active = _sds((n,), jnp.bool_)

        def insert(table, hi, lo, vh, vl, act):
            return hashset.insert(table, hi, lo, vh, vl, act, max_probes=32)

        jx = _trace(insert, table, u32, u32, u32, u32, active)
        # The open-addressing insert scatters at probed (data-dependent)
        # slots by DESIGN — correct there (not a vmapped model kernel;
        # four rounds of exact counts) and waived in
        # .stpu-lint-waivers.toml. The finding must keep firing so the
        # waiver stays honest.
        return taint_scatters(jx, name)

    return [
        ("ops:deltaset-maintain", maintain_run),
        ("ops:hashset-insert", hashset_run),
    ]


def _pallas_surfaces() -> List[Tuple[str, Callable[[], List[Finding]]]]:
    jax, jnp = _jnp()

    def preflight(name, fn, *args) -> List[Finding]:
        """Registry #6: the TPU lowering pre-flight, as a lint check.
        Mosaic lowering runs host-side; a kernel that cannot lower for
        the TPU target is a finding, not a crash."""
        try:
            jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
        except Exception as e:
            first = str(e).strip().splitlines()
            return [
                Finding(
                    rule="STPU005",
                    surface=name,
                    file="",
                    line=0,
                    message=(
                        "TPU lowering pre-flight failed "
                        f"({type(e).__name__}) — every ops/ pallas "
                        "kernel must lower for the TPU target from CPU "
                        "(registry #6)"
                    ),
                    excerpt=first[0] if first else type(e).__name__,
                )
            ]
        return []

    def compact_run():
        from ..ops.pallas_compact import compact_pallas_staged

        name = "pallas:compact"
        M, cap, P = 2048, 2048, 4
        mask = _sds((M,), jnp.bool_)
        lanes = [_sds((M,), jnp.uint32) for _ in range(P)]

        def fn(m, *ls):
            return compact_pallas_staged(m, list(ls), cap)  # default block

        jx = _trace(fn, mask, *lanes)
        return (
            mosaic_kernel_rules(jx, name)
            + vmem_budget(jx, name)
            + preflight(name, fn, mask, *lanes)
        )

    def merge_run():
        from ..ops.pallas_merge import merge_insert

        name = "pallas:merge"
        C, m = 2048, 512
        table = _sds((4, C), jnp.uint32)
        batch = _sds((4, m), jnp.uint32)

        def fn(t, b):
            return merge_insert(t, b, block=512)

        jx = _trace(fn, table, batch)
        return (
            mosaic_kernel_rules(jx, name)
            + vmem_budget(jx, name)
            + preflight(name, fn, table, batch)
        )

    def vmem_block_run(block: int):
        """STPU006 across the supported STPU_PALLAS_BLOCK range: both
        kernels re-traced at this block (shapes sized block-divisible)
        and priced against the per-core budget. The full rule scans ride
        the default-block surfaces above (compaction at its default,
        merge at 512); these price the block knob."""

        def run():
            from ..ops.pallas_compact import compact_pallas_staged
            from ..ops.pallas_merge import merge_insert

            name = f"pallas:vmem:block{block}"
            M = 4 * block
            mask = _sds((M,), jnp.bool_)
            lanes = [_sds((M,), jnp.uint32) for _ in range(4)]

            def cfn(m, *ls):
                return compact_pallas_staged(m, list(ls), M, block=block)

            out = vmem_budget(_trace(cfn, mask, *lanes), name)
            table = _sds((4, 4 * block), jnp.uint32)
            batch = _sds((4, block), jnp.uint32)

            def mfn(t, b):
                return merge_insert(t, b, block=block)

            return out + vmem_budget(_trace(mfn, table, batch), name)

        return run

    out = [("pallas:compact", compact_run), ("pallas:merge", merge_run)]
    out += [
        (f"pallas:vmem:block{b}", vmem_block_run(b))
        for b in SUPPORTED_PALLAS_BLOCKS
    ]
    return out


def _sharded_surfaces() -> List[Tuple[str, Callable[[], List[Finding]]]]:
    """The fingerprint-sharded mesh engine's superstep, traced under the
    same 8-device virtual CPU mesh the distributed tests force — the
    second surface docs/static-analysis.md listed as missing. Both dedup
    configs the mesh runs: hash (the CPU/test config) and sorted (the
    accelerator config STPU003's sort widths apply to)."""

    def make(dedup: str):
        name = f"engine:2pc:3:sharded-superstep:{dedup}"

        def run():
            jax, jnp = _jnp()
            if len(jax.devices()) < MESH_DEVICES:
                raise SurfaceSkip(
                    f"needs the {MESH_DEVICES}-device virtual CPU mesh "
                    f"(backend initialized with {len(jax.devices())} "
                    "devices before the analyzer could request it)"
                )
            from ..parallel import default_mesh
            from ..service.registry import resolve

            model, _ = resolve("2pc:3")
            checker = model.checker().spawn_xla(
                mesh=default_mesh(MESH_DEVICES),
                dedup=dedup,
                frontier_capacity=1 << 10,
                table_capacity=1 << 13,
            )
            step = checker._superstep()
            jx = _trace(
                step,
                checker._frontier,
                checker._frontier_ebits,
                checker._counts,
                tuple(checker._table),
                checker._disc_found,
                checker._disc_fp,
            )
            return wide_sorts(jx, name) + mosaic_kernel_rules(jx, name)

        return name, run

    return [make("hash"), make("sorted")]


def _mux_batched_args(checker, model, k: int):
    """The superstep's argument shapes under a leading ``k`` lane axis —
    exactly what ``MuxChecker._build_mux_fused``'s ``vmap`` of the
    single-level superstep carries (the table pytree batches leaf-wise)."""
    import jax

    return tuple(
        jax.tree_util.tree_map(lambda a: _sds((k,) + a.shape, a.dtype), arg)
        for arg in _superstep_args(checker, model, F_CAP)
    )


def _mux_surfaces() -> List[Tuple[str, Callable[[], List[Finding]]]]:
    """The multiplexed superstep (xla_mux.py): ``jax.vmap`` of the
    engine's single-level superstep under a leading K lane axis — the
    program ``worker.py --mux`` compiles. Three pins per the surface
    taxonomy above:

    - ``kernel:…:mux-packed_step:k{K}`` — STPU001/STPU002 on the
      DOUBLY-vmapped model kernel (vmap-over-lanes of the vmap-over-rows
      transition), the new vmap nesting mux introduces. The batched
      superstep itself legitimately contains engine-level scatters, the
      same exemption the solo engine surfaces get;
    - ``engine:…:mux-superstep:k{K}:{dedup}`` — the engine rules
      (STPU003 sort widths now carry the K batch dimension, STPU005
      statics) over the batched superstep, both mux-supported dedups
      (delta is ``MuxError``-ineligible, so no surface exists to lint);
    - ``lower:…:mux-superstep:k2`` — one STPU008 cross-backend lowering
      diff of the whole batched program (cheap: ~0.6 s both platforms).
    """
    out: List[Tuple[str, Callable[[], List[Finding]]]] = []
    spec = "2pc:3"

    def make_kernel(k: int):
        name = f"kernel:{spec}:mux-packed_step:k{k}"

        def run():
            jax, jnp = _jnp()
            from ..service.registry import resolve

            model, _ = resolve(spec)
            rows = _sds((k, KERNEL_BATCH, model.state_words), jnp.uint32)
            jx = _trace(jax.vmap(jax.vmap(model.packed_step)), rows)
            return (
                taint_scatters(jx, name)
                + output_transposes(jx, name)
                + wide_sorts(jx, name)
            )

        return name, run

    def make_engine(k: int, dedup: str):
        name = f"engine:{spec}:mux-superstep:k{k}:{dedup}"

        def run():
            jax, _ = _jnp()
            model, checker = _spawn(spec, dedup)
            step = checker._build_superstep(F_CAP, CAND_CAP)
            jx = _trace(jax.vmap(step), *_mux_batched_args(checker, model, k))
            return (
                wide_sorts(jx, name)
                + cond_flush_sorts(jx, name, _flush_lanes(checker))
                + mosaic_kernel_rules(jx, name)
            )

        return name, run

    def make_lowering(k: int):
        name = f"lower:{spec}:mux-superstep:k{k}"

        def run():
            jax, _ = _jnp()
            model, checker = _spawn(spec, "sorted")
            step = checker._build_superstep(F_CAP, CAND_CAP)
            args = _mux_batched_args(checker, model, k)
            inv = {}
            for platform in ("cpu", "tpu"):
                lowered = jax.jit(jax.vmap(step)).trace(*args).lower(
                    lowering_platforms=(platform,)
                )
                inv[platform] = op_inventory(lowered.as_text())
            return diff_lowering_inventories(name, inv["cpu"], inv["tpu"])

        return name, run

    for k in MUX_KS:
        out.append(make_kernel(k))
        for dedup in ("sorted", "hash"):
            out.append(make_engine(k, dedup))
    out.append(make_lowering(MUX_KS[0]))
    return out


def _census_surface(
    specs: Optional[List[str]] = None,
) -> Tuple[str, Callable[[], List[Finding]]]:
    """STPU007: the compile-plan census over the shipped specs (or one
    admission spec) — pure planner arithmetic, no tracing."""
    name = "plan:shipped" if specs is None else f"plan:{','.join(specs)}"

    def run():
        from .census import build_census, census_findings

        return census_findings(build_census(specs))

    return name, run


# --- the sweep --------------------------------------------------------------


def build_sweep(full: bool = False) -> List[Tuple[str, Callable[[], List[Finding]]]]:
    """Every (name, runner) in the sweep. Runners trace lazily, so an
    ``--only``-filtered run costs only the surfaces it touches (and a
    ``--rules`` filter naming no jaxpr rule skips the sweep entirely —
    ``cli.run_lint``)."""
    from ..service.registry import SHIPPED, resolve

    out: List[Tuple[str, Callable[[], List[Finding]]]] = []
    for spec in SHIPPED:
        model, _ = resolve(spec)
        out.extend(_kernel_surfaces(spec, model))
        # The accelerator-policy sorted-engine superstep: the program
        # the chip actually runs for this model (W-dependent sort
        # widths — STPU003's subject).
        out.append(_engine_surface(spec, "sorted", _accel_policy_compaction(model)))
        if full or spec in MATRIX_SPECS:
            out.append(_engine_surface(spec, "hash", "auto"))
            out.append(_engine_surface(spec, "delta", "gather"))
            out.append(_engine_surface(spec, "sorted", "bsearch"))
            out.append(_engine_surface(spec, "sorted", "pallas"))
        # STPU008's dual-platform lowering costs real seconds per
        # surface; the default sweep diffs the two width classes (engine
        # programs are W-class-shared; kernels differ per model, so
        # --full widens to every spec). Admission checks always diff the
        # admitted spec (build_admission_sweep).
        if full or spec in MATRIX_SPECS:
            out.append(_lowering_surface(spec, model))
            if getattr(model, "symmetry_spec", None) is not None:
                out.append(_sym_lowering_surface(spec, model))
    # Fused multi-level programs (the lax.switch ladder + while loop):
    # one narrow sorted, one narrow delta (STPU004's switch-carrying
    # delta program), one wide sorted under --full.
    out.append(_fused_surface("2pc:3", "sorted"))
    out.append(_fused_surface("2pc:3", "delta"))
    if full:
        out.append(_fused_surface("paxos:2,3", "sorted"))
    # The multiplexed superstep (worker.py --mux): batched-kernel pins at
    # the MUX_KS lane counts plus one cross-backend lowering diff.
    out.extend(_mux_surfaces())
    out.extend(_sharded_surfaces())
    out.extend(_ops_surfaces())
    out.extend(_pallas_surfaces())
    out.append(_census_surface())
    return out


def build_admission_sweep(
    spec: str,
) -> List[Tuple[str, Callable[[], List[Finding]]]]:
    """The admission-time flight-check for ONE spec (docs/service.md):
    its kernel surfaces (STPU001/002/003), its cross-backend lowering
    diff (STPU008), and its compile-plan census (STPU007) — the subset
    a user-submitted model must pass before the pool schedules it on
    the device. Engine/ops/pallas surfaces are spec-independent and
    stay the full sweep's business."""
    from ..service.registry import resolve

    model, _ = resolve(spec)
    out = _kernel_surfaces(spec, model)
    out.append(_lowering_surface(spec, model))
    if getattr(model, "symmetry_spec", None) is not None:
        out.append(_sym_lowering_surface(spec, model))
    out.append(_census_surface([spec]))
    return out


def run_sweep(
    full: bool = False,
    only: Optional[List[str]] = None,
    *,
    admission_spec: Optional[str] = None,
    use_cache: bool = True,
    cache_dir: Optional[str] = None,
) -> List[SurfaceReport]:
    """Trace and scan every surface (CPU backend, accelerator write
    lowering pinned on). ``only`` filters surface names by substring;
    ``admission_spec`` swaps the sweep for :func:`build_admission_sweep`
    over that one spec.

    The sweep is HERMETIC: every ``STPU_*`` env knob is scrubbed for the
    duration (and restored after). The knobs exist for A/B sessions —
    an exported ``STPU_SORTEDSET_KEYS=packed`` or ``STPU_COMPACTION``
    would otherwise make the lint trace a different program than the
    tree defines (or error outright on x64-requiring variants), turning
    the verdict into a function of the caller's shell. The one
    exemption is ``STPU_FAMILIES`` (service/registry.py's user-family
    hook): it selects WHICH models exist, not how a program lowers, and
    scrubbing it would make the admission check unable to see the very
    spec it was asked to verify.

    ``use_cache`` replays raw findings from the content-hash cache
    (analysis/cache.py) for surfaces whose package tree is unchanged —
    errors and skips are never cached."""
    import os as _os

    # Snapshot BEFORE pin_cpu appends the 8-virtual-device flag for the
    # sharded mesh surface: once the backend is initialized (the flag is
    # only read at CPU-client init) the caller's value is restored in
    # the finally below, so subprocesses an embedding process spawns
    # later never inherit it.
    prev_flags = _os.environ.get("XLA_FLAGS")
    pin_cpu()
    from .. import packing

    cache = None
    if use_cache and admission_spec is not None:
        # A user-submitted family (STPU_FAMILIES) lives OUTSIDE the
        # package tree the cache hashes — serving its surfaces from the
        # tree-keyed cache would replay stale verdicts across user
        # edits. Shipped families stay cacheable.
        from ..service.registry import FAMILIES, parse

        family, _ = parse(admission_spec)
        use_cache = family in FAMILIES
    if use_cache:
        from .cache import SurfaceCache

        cache = SurfaceCache(cache_dir)

    reports: List[SurfaceReport] = []
    prev = packing.ONE_HOT_WRITES
    packing.ONE_HOT_WRITES = True
    scrubbed = {
        k: _os.environ.pop(k)
        for k in list(_os.environ)
        if k.startswith("STPU_") and k != "STPU_FAMILIES"
    }
    try:
        sweep = (
            build_admission_sweep(admission_spec)
            if admission_spec is not None
            else build_sweep(full=full)
        )
        for name, runner in sweep:
            if only and not any(s in name for s in only):
                continue
            t0 = time.monotonic()
            rep = SurfaceReport(name=name)
            hit = cache.get(name) if cache is not None else None
            if hit is not None:
                rep.findings = hit
                rep.cached = True
            else:
                try:
                    rep.findings = runner()
                    if cache is not None:
                        cache.put(name, rep.findings)
                except SurfaceSkip as e:
                    rep.skipped = str(e)
                except Exception as e:  # trace failure: loud, not a pass
                    rep.error = f"{type(e).__name__}: {e}"
            rep.seconds = round(time.monotonic() - t0, 3)
            reports.append(rep)
    finally:
        packing.ONE_HOT_WRITES = prev
        _os.environ.update(scrubbed)
        if prev_flags is None:
            _os.environ.pop("XLA_FLAGS", None)
        else:
            _os.environ["XLA_FLAGS"] = prev_flags
    return reports
