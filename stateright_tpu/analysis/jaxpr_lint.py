"""The jaxpr invariant pass: STPU001-005 over lowered kernel surfaces.

Everything here operates on already-traced ``ClosedJaxpr``s (tracing is
``surfaces.py``'s job) — no device, no execution, no XLA compile. Rules
are checked against the jaxpr rather than compiled HLO on purpose: the
jaxpr is backend-independent and stable across XLA fusion decisions, so a
finding names the op the PROGRAM asked for, with ``eqn.source_info``
giving the exact repo ``file:line`` that asked. (The one HLO-adjacent
check, the STPU005 Mosaic pre-flight, goes through the real TPU lowering
pipeline in ``surfaces.py`` because Mosaic's verifier IS the checkable
artifact there.)

Shared mechanics:

- :func:`iter_eqns` walks equations recursively through every sub-jaxpr
  (cond/switch branches, while bodies, pjit calls, pallas kernels),
  yielding the primitive path from the root so rules can scope to
  "inside a cond branch" or "inside a pallas kernel".
- :func:`taint_scatters` runs the forward dataflow STPU001 needs:
  a scatter is only the pinned-fatal shape when its *index* operand is
  data-DEPENDENT (derived from the kernel's traced inputs). Static-index
  writes also appear as ``scatter`` eqns in a jaxpr, but XLA folds them
  and the round-5 drift never reproduced there — flagging those would
  bury the real signal in noise (every Layout.set of a static field).
"""

from __future__ import annotations

import os
from typing import Any, Iterator, List, Optional, Tuple

from .rules import (
    MAX_SAFE_SORT_OPERANDS,
    PATHOLOGY_LOWERING_OPS,
    VMEM_BUDGET_BYTES,
    Finding,
)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: Primitive families the rules key on.
SCATTER_PRIMS = (
    "scatter",
    "scatter-add",
    "scatter_add",
    "scatter-mul",
    "scatter_mul",
    "scatter-min",
    "scatter_min",
    "scatter-max",
    "scatter_max",
)
CUMULATIVE_PRIMS = ("cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp")
#: Pallas ref-store primitives (a dynamic-offset *vector* store is the
#: Mosaic-rejected shape; DMA copies at dynamic offsets are sanctioned).
STORE_PRIMS = ("swap", "masked_swap", "store")


def _subjaxprs(eqn) -> List[Any]:
    """Raw ``Jaxpr`` children of an equation's params (cond branches,
    while body/cond, pjit jaxpr, pallas kernel jaxpr, ...)."""
    subs = []
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(x, "jaxpr"):  # ClosedJaxpr
                subs.append(x.jaxpr)
            elif hasattr(x, "eqns"):  # Jaxpr
                subs.append(x)
    return subs


def iter_eqns(jaxpr, path: Tuple[str, ...] = ()) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Yield ``(eqn, path)`` over ``jaxpr`` and every sub-jaxpr; ``path``
    is the tuple of enclosing primitive names from the root."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        sub_path = path + (eqn.primitive.name,)
        for s in _subjaxprs(eqn):
            yield from iter_eqns(s, sub_path)


def source_of(eqn) -> Tuple[str, int]:
    """Best repo-relative ``(file, line)`` anchor for an equation, from
    jax's per-eqn source info (the deepest user frame inside the repo);
    ``("", 0)`` when the trace carries none."""
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return "", 0
    frames = [
        f
        for f in tb.frames
        if f.file_name
        and f.file_name.startswith(_REPO)
        # The lint driver's own frames (this package, the tools/
        # wrapper) are never the anchor: an op inserted by vmap
        # machinery with no user frame must report "<no-source>", not
        # blame the lint entry point.
        and f"{os.sep}analysis{os.sep}" not in f.file_name
        and not f.file_name.endswith(f"tools{os.sep}stpu_lint.py")
    ]
    if not frames:
        return "", 0
    f = frames[0]
    return os.path.relpath(f.file_name, _REPO), f.line_num


def excerpt_of(eqn, limit: int = 160) -> str:
    txt = " ".join(str(eqn).split())
    return txt if len(txt) <= limit else txt[: limit - 3] + "..."


def _is_literal(v) -> bool:
    return hasattr(v, "val")


# --- STPU001 ----------------------------------------------------------------


def taint_scatters(closed, surface: str) -> List[Finding]:
    """STPU001: scatter eqns whose index operand is derived from the
    surface's traced inputs (data-dependent — the shape XLA:TPU drops in
    vmapped kernels at batch >= 4096)."""
    findings: List[Finding] = []

    def walk(jaxpr, taint):
        for eqn in jaxpr.eqns:
            in_taint = [
                (not _is_literal(v)) and id(v) in taint for v in eqn.invars
            ]
            if eqn.primitive.name in SCATTER_PRIMS:
                # Scatter operands: (operand, indices, updates).
                if len(in_taint) > 1 and in_taint[1]:
                    file, line = source_of(eqn)
                    findings.append(
                        Finding(
                            rule="STPU001",
                            surface=surface,
                            file=file,
                            line=line,
                            message=(
                                "data-dependent scatter in a vmapped "
                                "kernel surface: route this traced-index "
                                "write through packing._word_update "
                                "(one-hot) — XLA:TPU drops this scatter "
                                "at batch >= 4096"
                            ),
                            excerpt=excerpt_of(eqn),
                        )
                    )
            # Propagate taint through this eqn and into sub-jaxprs.
            any_taint = any(in_taint)
            for s in _subjaxprs(eqn):
                walk(s, set(map(id, s.invars)) if any_taint else set())
            if any_taint:
                for o in eqn.outvars:
                    taint.add(id(o))
        return findings

    jaxpr = closed.jaxpr
    return walk(jaxpr, set(map(id, jaxpr.invars)))


# --- STPU002 ----------------------------------------------------------------


def output_transposes(closed, surface: str) -> List[Finding]:
    """STPU002: ANY transpose equation inside a kernel-surface jaxpr —
    whether it produces the surface's outputs directly (the
    ``vmap(..., out_axes != 0)`` shape) or sits mid-kernel between ops
    (e.g. a nested ``vmap(..., out_axes != 0)`` whose transpose feeds
    further kernel ops — the documented gap the first cut of this rule
    left open). Either way the transpose is FUSED into the vmapped
    kernel, which is the shape XLA:CPU miscompiles; the engine's safe
    direction materializes rows and transposes as a separate consumer
    (rows-in/transpose-out). Shipped kernels carry zero transposes, so
    the whole-body scan stays noise-free."""
    findings: List[Finding] = []
    jaxpr = closed.jaxpr
    outs = {id(v) for v in jaxpr.outvars if not _is_literal(v)}
    for eqn, _path in iter_eqns(jaxpr):
        if eqn.primitive.name != "transpose":
            continue
        direct = any(id(o) in outs for o in eqn.outvars)
        file, line = source_of(eqn)
        findings.append(
            Finding(
                rule="STPU002",
                surface=surface,
                file=file,
                line=line,
                message=(
                    (
                        "vmapped kernel hands its output straight out of "
                        "a transpose (out_axes != 0)"
                        if direct
                        else "transpose buried mid-kernel between ops in "
                        "a vmapped kernel (e.g. a nested "
                        "vmap(out_axes != 0))"
                    )
                    + ": the transpose-fused-into-vmap shape XLA:CPU "
                    "miscompiles — emit rows (out_axes=0) and transpose "
                    "outside the kernel"
                ),
                excerpt=excerpt_of(eqn),
            )
        )
    return findings


# --- STPU003 ----------------------------------------------------------------


def wide_sorts(
    closed, surface: str, max_operands: int = MAX_SAFE_SORT_OPERANDS
) -> List[Finding]:
    """STPU003: ``lax.sort`` equations carrying more operands than the
    chip-proven width (the wide-W compile-stall shape)."""
    findings: List[Finding] = []
    for eqn, _path in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "sort":
            continue
        n = len(eqn.invars)
        if n > max_operands:
            file, line = source_of(eqn)
            findings.append(
                Finding(
                    rule="STPU003",
                    surface=surface,
                    file=file,
                    line=line,
                    message=(
                        f"{n}-operand lax.sort exceeds the chip-proven "
                        f"width ({max_operands}): the W=25 sort-compaction "
                        "compile stalled XLA:TPU for tens of minutes — "
                        "use gather-family compaction for wide states"
                    ),
                    excerpt=excerpt_of(eqn),
                )
            )
    return findings


# --- STPU004 ----------------------------------------------------------------


def cond_flush_sorts(
    closed, surface: str, flush_lanes: Optional[int]
) -> List[Finding]:
    """STPU004: a sort of table-scale lanes (>= ``flush_lanes``, the
    delta structure's main capacity) inside a cond/switch branch — the
    flush-under-``lax.cond`` shape that faults the XLA:TPU runtime.
    ``flush_lanes=None`` skips the rule (surface has no delta tier)."""
    if flush_lanes is None:
        return []
    findings: List[Finding] = []
    for eqn, path in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "sort" or "cond" not in path:
            continue
        lanes = max(
            (v.aval.shape[0] for v in eqn.invars if v.aval.shape), default=0
        )
        if lanes >= flush_lanes:
            file, line = source_of(eqn)
            findings.append(
                Finding(
                    rule="STPU004",
                    surface=surface,
                    file=file,
                    line=line,
                    message=(
                        f"table-scale sort ({lanes} lanes >= main "
                        f"capacity {flush_lanes}) inside a cond/switch "
                        "branch: the deltaset flush must be the "
                        "host-invoked maintain program through the "
                        "overflow protocol — this shape faults the "
                        "XLA:TPU runtime"
                    ),
                    excerpt=excerpt_of(eqn),
                )
            )
    return findings


# --- STPU006: static VMEM budget for pallas kernels -------------------------


def _vmem_bytes(aval) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    itemsize = getattr(dtype, "itemsize", None)
    if itemsize is None:  # extended dtypes (semaphores) are space-filtered
        itemsize = 4
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(itemsize)


def pallas_vmem_footprint(eqn) -> Tuple[int, List[str]]:
    """Static per-core VMEM bytes of one ``pallas_call`` equation, from
    the kernel jaxpr's ref avals: blocked operands (default memory
    space) count TWICE — the pipeline emitter double-buffers them —
    VMEM scratch counts in full, and ANY (HBM) / SMEM / semaphore refs
    are free. Returns ``(bytes, breakdown)``."""
    total = 0
    breakdown: List[str] = []
    kernel = eqn.params.get("jaxpr")
    if kernel is None:  # not a shape this pass prices
        return 0, []
    for v in kernel.invars:
        aval = v.aval
        space = getattr(aval, "memory_space", None)
        tag = str(getattr(space, "value", space)).lower()
        if space is None:
            b = 2 * _vmem_bytes(aval)  # double-buffered pipeline block
            label = "block x2"
        elif tag == "vmem":
            b = _vmem_bytes(aval)
            label = "scratch"
        else:  # any (HBM), smem, semaphores
            continue
        total += b
        breakdown.append(
            f"{label} {tuple(getattr(aval, 'shape', ()))} = {b}B"
        )
    return total, breakdown


def vmem_budget(
    closed, surface: str, budget: int = VMEM_BUDGET_BYTES
) -> List[Finding]:
    """STPU006: every ``pallas_call`` whose static VMEM footprint
    exceeds the per-core budget (the shape that today surfaces as a
    runtime Mosaic allocation error on chip, after chip time is already
    spent)."""
    findings: List[Finding] = []
    for eqn, _path in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        total, breakdown = pallas_vmem_footprint(eqn)
        if total > budget:
            file, line = source_of(eqn)
            findings.append(
                Finding(
                    rule="STPU006",
                    surface=surface,
                    file=file,
                    line=line,
                    message=(
                        f"static VMEM footprint {total} B exceeds the "
                        f"per-core budget {budget} B "
                        f"({', '.join(breakdown)}): shrink the block "
                        "(STPU_PALLAS_BLOCK) or the scratch rings — on "
                        "chip this is a runtime Mosaic allocation error"
                    ),
                    excerpt=excerpt_of(eqn),
                )
            )
    return findings


# --- STPU008: cross-backend lowering diff ------------------------------------

#: Dialects whose ops count as the lowered inventory.
_OP_RE = None


def op_inventory(stablehlo_text: str) -> set:
    """The set of ``stablehlo.*``/``chlo.*``/``mhlo.*`` op names
    appearing in a lowered module's text."""
    import re

    global _OP_RE
    if _OP_RE is None:
        _OP_RE = re.compile(r"\b(?:stablehlo|chlo|mhlo)\.[\w.]+")
    return set(_OP_RE.findall(stablehlo_text))


def diff_lowering_inventories(
    surface: str, cpu_ops: set, tpu_ops: set
) -> List[Finding]:
    """STPU008: pathology-registry ops present in exactly ONE backend's
    lowering of the same program — the structural class both pinned
    miscompiles belong to (TPU drops the scatter CPU executes; CPU
    miscompiles the transpose TPU runs fine)."""
    findings: List[Finding] = []
    for op in PATHOLOGY_LOWERING_OPS:
        in_cpu, in_tpu = op in cpu_ops, op in tpu_ops
        if in_cpu == in_tpu:
            continue
        only, missing = ("cpu", "tpu") if in_cpu else ("tpu", "cpu")
        findings.append(
            Finding(
                rule="STPU008",
                surface=surface,
                file="",
                line=0,
                message=(
                    f"pathology-registry op {op} appears only in the "
                    f"{only} lowering (absent from {missing}): the "
                    "backends lower this program differently in exactly "
                    "the op class they have already disagreed on — "
                    "rewrite the program so both lowerings agree, or "
                    "waive with a chip-verified justification"
                ),
                excerpt=f"{only}-only: {op}",
            )
        )
    return findings


# --- STPU005 (static half; the lowering pre-flight lives in surfaces.py) ----


def _is_u32_f32_cast(eqn) -> bool:
    if eqn.primitive.name != "convert_element_type":
        return False
    new = eqn.params.get("new_dtype")
    old = eqn.invars[0].aval.dtype
    names = {str(old), str(new)}
    return names == {"uint32", "float32"}


def mosaic_kernel_rules(closed, surface: str) -> List[Finding]:
    """STPU005 static scans inside every ``pallas_call`` kernel jaxpr:
    no cumulative-scan primitives (no Mosaic TC lowering), no direct
    u32<->f32 casts (unsupported; use the value-exact i32 hop), and no
    dynamic-offset vector stores (the Mosaic alignment prover rejects
    them; stream through aligned ring buffers + chunk DMAs instead)."""
    findings: List[Finding] = []
    for eqn, path in iter_eqns(closed.jaxpr):
        if "pallas_call" not in path:
            continue
        bad: Optional[str] = None
        if eqn.primitive.name in CUMULATIVE_PRIMS:
            bad = (
                f"{eqn.primitive.name} inside a Mosaic TC kernel has no "
                "lowering: use the MXU lower-triangular one-hot "
                "contraction (ops/pallas_compact.tri_inclusive)"
            )
        elif _is_u32_f32_cast(eqn):
            bad = (
                "direct u32<->f32 cast inside a Mosaic TC kernel is "
                "unsupported: hop through i32 (value-exact for 16-bit "
                "halves — ops/pallas_compact.split16/fuse16)"
            )
        elif eqn.primitive.name in STORE_PRIMS:
            # A store whose ref indexing consumes traced operands and
            # whose stored value is a vector: the dynamic-offset
            # vector-store shape. Static slices carry no index invars.
            idx_vars = [v for v in eqn.invars[2:] if not _is_literal(v)]
            val_aval = eqn.invars[1].aval if len(eqn.invars) > 1 else None
            if idx_vars and val_aval is not None and val_aval.shape:
                bad = (
                    "dynamic-offset vector store inside a Mosaic TC "
                    "kernel: the alignment prover rejects it — place "
                    "survivors via the one-hot ring fold and flush "
                    "with B-aligned chunk DMAs"
                )
        if bad:
            file, line = source_of(eqn)
            findings.append(
                Finding(
                    rule="STPU005",
                    surface=surface,
                    file=file,
                    line=line,
                    message=bad,
                    excerpt=excerpt_of(eqn),
                )
            )
    return findings
