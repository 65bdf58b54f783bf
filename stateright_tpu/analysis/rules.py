"""stpu-lint rule registry, findings, and the waiver file.

Each rule ID names ONE pinned backend pathology (docs/backend_pathologies.md,
docs/static-analysis.md) that was root-caused on real hardware and is now
enforced mechanically instead of by tribal knowledge:

- STPU001-005 are jaxpr-level invariants checked against the lowered
  representation of every registered kernel surface
  (``stateright_tpu/analysis/surfaces.py``);
- STPU101 and STPU103 are AST-level project rules over the package source
  (``stateright_tpu/analysis/astlint.py``).

Findings that are KNOWN-correct exceptions are waived in
``.stpu-lint-waivers.toml`` at the repo root — every waiver carries a
one-line justification and matches findings by rule + glob patterns over
the surface name and file. An unmatched waiver is itself reported (a
stale waiver hides nothing but rots the record).

The waiver file is TOML restricted to ``[[waiver]]`` array-of-tables with
string values (this container runs Python 3.10 — no stdlib ``tomllib`` —
so :func:`_parse_waivers_toml` is a minimal parser for exactly that
subset, loud on anything else).
"""

from __future__ import annotations

import fnmatch
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class Rule:
    id: str
    title: str
    #: Which pass owns it: "jaxpr" or "ast".
    kind: str
    #: The measured failure this rule pins (the "why", shown by
    #: ``--list-rules`` and docs/static-analysis.md).
    history: str


RULES: Dict[str, Rule] = {
    r.id: r
    for r in (
        Rule(
            "STPU001",
            "no data-dependent scatter inside a vmapped model kernel",
            "jaxpr",
            "XLA:TPU silently DROPS data-dependent one-element scatters "
            "inside vmapped model kernels at batch >= 4096 (round-3/5 "
            "on-chip paxos count drift; bisected in tools/paxos_diag.py). "
            "Traced-index packed-field writes must lower one-hot via "
            "packing._word_update. Static-index scatters are exempt: XLA "
            "folds them and the pinned drift never reproduced there.",
        ),
        Rule(
            "STPU002",
            "no transpose fused into a vmapped kernel on the CPU path",
            "jaxpr",
            "XLA:CPU (jax 0.9.0 lineage) MIScompiles a transpose fused "
            "into a vmapped kernel: a scalar-cond jnp.where inside the "
            "kernel returns the wrong branch at batch >= 64, eager and "
            "jit disagree (_build_superstep_planes docstring). "
            "Rows-in/transpose-out is the safe fusion direction, so a "
            "kernel-surface jaxpr must not hand its outputs straight out "
            "of a transpose (the vmap out_axes != 0 shape).",
        ),
        Rule(
            "STPU003",
            "lax.sort operand count within the chip-proven width",
            "jaxpr",
            "A wide-W sort-mode grid compaction is a W+3-operand lax.sort "
            "whose XLA:TPU *compile* stalls for tens of minutes (round-5, "
            "paxos W=25: two bench workers lost at 28 operands), while "
            "narrow-W sort-family lowerings are chip-proven. The engine's "
            "auto policy caps sort-family compaction at state_words <= 8 "
            "(<= 12 sort operands); any surface carrying a wider sort "
            "re-introduces the stall shape.",
        ),
        Rule(
            "STPU004",
            "deltaset flush never under a lax.cond branch",
            "jaxpr",
            "A lax.cond carrying the main-capacity flush sort reproducibly "
            "FAULTS the XLA:TPU runtime ('TPU worker crashed - kernel "
            "fault', observed at 2^22 and 2^27 main tiers, round 5). The "
            "flush is the host-invoked maintain program through the "
            "overflow protocol; no cond/switch branch in a delta-dedup "
            "surface may contain a table-scale sort.",
        ),
        Rule(
            "STPU005",
            "Mosaic TC kernel rules + mandatory TPU lowering pre-flight",
            "jaxpr",
            "Mosaic TC kernels have no cumsum lowering, no u32<->f32 "
            "casts, and reject dynamic-offset vector stores (r5e first "
            "silicon; registry #6). Mosaic lowering runs host-side, so "
            "jit(f).trace(...).lower(lowering_platforms=('tpu',)) on CPU "
            "pre-flights every pallas kernel without a chip - the "
            "pre-flight is mandatory for every kernel in ops/, and this "
            "rule also scans kernel jaxprs for the three shapes the r5e "
            "rework banned. Lowering is not compiling: a kernel that "
            "lowers can still be refused by the TPU compiler (block "
            "alignment), which tests/test_tpu_compile.py checks.",
        ),
        Rule(
            "STPU006",
            "Pallas kernel VMEM footprint within the per-core budget",
            "jaxpr",
            "An oversized block turns into a runtime Mosaic allocation "
            "error ON CHIP — after chip time was already spent "
            "compiling it. The footprint is statically derivable from the "
            "pallas_call BlockSpecs/avals (blocked operands are "
            "double-buffered by the pipeline emitter, VMEM scratch is "
            "resident in full), so the flight-check prices every kernel "
            "across the supported STPU_PALLAS_BLOCK range against the "
            "~16 MiB/core v5e budget before any chip time is booked.",
        ),
        Rule(
            "STPU007",
            "compile-plan shape count within the declared budget",
            "jaxpr",
            "Compile time, not run time, dominates a cold chip run "
            "(paxos warm 47 s at 4 buckets on CPU; the TPU compiler takes "
            "minutes for one 2pc rm=8 superstep program). The (bucket, "
            "cand-rung) schedule a run plan commits "
            "to is statically enumerable from the shared ladder planner "
            "(xla.ladder_buckets/cand_rungs), so a plan whose distinct "
            "program count blows the budget is a finding before it is a "
            "burned chip call. The census doubles as the warm-cache set "
            "(tools/warm_cache.py derives from it).",
        ),
        Rule(
            "STPU008",
            "no pathology-class op in only ONE backend's lowering",
            "jaxpr",
            "Both pinned miscompiles are the same structural class: an op "
            "the two backends lower DIFFERENTLY (TPU drops the vmapped "
            "scatter CPU executes; CPU miscompiles the fused transpose TPU "
            "runs fine). Lowering every kernel surface for both platforms "
            "from this CPU box (the STPU005 pre-flight trick) and diffing "
            "the StableHLO op inventories catches a registry-class op that "
            "appears on one side only — the shape where the backends have "
            "already disagreed twice.",
        ),
        Rule(
            "STPU101",
            "traced-index packed-field writes go through packing",
            "ast",
            "Direct .at[...].set/.add writes in model kernel code are the "
            "exact shape STPU001 exists for, caught at the source level "
            "before anything is traced: route them through "
            "packing.Layout.set / packing._word_update, which owns the "
            "backend-split (scatter on CPU, one-hot on accelerators).",
        ),
        Rule(
            "STPU103",
            "checkpoint/heartbeat files written atomically",
            "ast",
            "Checkpoints and heartbeats are read by watchdogs and resumed "
            "from after SIGKILL; a plain open(path, 'w') can be observed "
            "torn. checkpoint.py and obs/ own the tmp + os.replace "
            "pattern (payload sha256, rotation); writes to *checkpoint* / "
            "*heartbeat* paths outside them must go through those codecs.",
        ),
    )
}

#: STPU003's chip-proven ceiling: the widest sort-family lowering the
#: round-5 A/Bs measured healthy is the W=8 sort-compaction class
#: (key + W state planes + 3 payload lanes = 12 operands); the pinned
#: compile stall was at 28 (W=25). Conservative midpoint: anything
#: above 16 operands is the stall shape.
MAX_SAFE_SORT_OPERANDS = 16

#: STPU006's per-core VMEM budget: ~16 MiB on the v5e class this project
#: targets (the Pallas guide's memory-hierarchy table). The footprint
#: model charges blocked operands twice (the pipeline emitter
#: double-buffers them) and VMEM scratch in full; SMEM/semaphores/ANY
#: (HBM) operands are free.
VMEM_BUDGET_BYTES = 16 * 2**20

#: STPU007's default compile budget: distinct (bucket, rung-schedule)
#: programs a run plan may commit to. Every shipped plan sits at 3-4
#: buckets; 8 is the "a chip call will burn on compiles" line (tens of
#: seconds to minutes per bucket from the TPU compiler). A model may
#: declare its own via an
#: ``xla_compile_budget`` attribute.
MAX_COMPILE_SHAPES = 8

#: STPU008's pathology registry: lowered-op classes a backend has
#: already miscompiled, dropped, or stalled on. An op from this set in
#: only ONE backend's StableHLO lowering of the same program is the
#: structural shape both pinned miscompiles belong to.
PATHOLOGY_LOWERING_OPS = (
    "stablehlo.scatter",          # the STPU001 dropped-write class
    "stablehlo.transpose",        # the STPU002 fused-transpose class
    "stablehlo.sort",             # the STPU003 compile-stall class
    "stablehlo.dynamic_update_slice",  # scatter's one-element sibling
    "stablehlo.select_and_scatter",
)


@dataclass
class Finding:
    rule: str
    #: Which registered surface (jaxpr pass) or file (AST pass) tripped.
    surface: str
    #: Repo-relative path and 1-based line of the best source anchor.
    file: str
    line: int
    message: str
    #: The lowered-op excerpt (jaxpr eqn) or source line that matched.
    excerpt: str
    waived: bool = False
    waiver_reason: str = ""

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "surface": self.surface,
            "file": self.file,
            "line": self.line,
            "message": self.message,
            "excerpt": self.excerpt,
            "waived": self.waived,
            "waiver_reason": self.waiver_reason,
        }

    def format(self) -> str:
        loc = f"{self.file}:{self.line}" if self.file else "<no-source>"
        tag = " [waived: %s]" % self.waiver_reason if self.waived else ""
        out = f"{loc}: {self.rule} [{self.surface}] {self.message}{tag}"
        if self.excerpt:
            out += f"\n    | {self.excerpt}"
        return out


@dataclass
class Waiver:
    rule: str
    reason: str
    surface: str = "*"
    file: str = "*"
    #: Optional ``YYYY-MM-DD`` expiry. Past it the waiver STOPS
    #: suppressing (its findings go active) and it is reported like a
    #: stale one — so a chip-A/B-pending waiver cannot rot past its
    #: window. Empty = never expires.
    expires: str = ""
    used: int = field(default=0, compare=False)

    @property
    def expired(self) -> bool:
        if not self.expires:
            return False
        import datetime

        return (
            datetime.date.fromisoformat(self.expires)
            < datetime.date.today()
        )

    def matches(self, f: Finding) -> bool:
        return (
            not self.expired
            and f.rule == self.rule
            and fnmatch.fnmatchcase(f.surface, self.surface)
            and fnmatch.fnmatchcase(f.file, self.file)
        )


class WaiverError(ValueError):
    """Malformed waiver file — typed, so the CLI exits 2 (internal/config
    error), never silently ignoring a waiver that was meant to apply."""


def _parse_waivers_toml(text: str, path: str) -> List[Waiver]:
    """Minimal TOML subset parser: ``[[waiver]]`` tables of
    ``key = "string"`` pairs; comments and blank lines. Loud on anything
    else (Python 3.10 has no tomllib; this file format is ours)."""
    waivers: List[Waiver] = []
    current: Optional[dict] = None
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line == "[[waiver]]":
            if current is not None:
                waivers.append(_finish_waiver(current, path))
            current = {"_line": lineno}
            continue
        if "=" in line and current is not None:
            key, _, val = line.partition("=")
            key = key.strip()
            val = val.strip()
            if key in ("rule", "reason", "surface", "file", "expires") and (
                len(val) >= 2 and val[0] == '"' and val[-1] == '"'
            ):
                current[key] = val[1:-1]
                continue
        raise WaiverError(
            f"{path}:{lineno}: unsupported waiver syntax {raw!r} "
            "(only [[waiver]] tables with rule/reason/surface/file/"
            'expires string keys, e.g. rule = "STPU001")'
        )
    if current is not None:
        waivers.append(_finish_waiver(current, path))
    return waivers


def _finish_waiver(d: dict, path: str) -> Waiver:
    line = d.pop("_line")
    if "rule" not in d or "reason" not in d:
        raise WaiverError(
            f"{path}:{line}: every [[waiver]] needs 'rule' and a "
            "one-line 'reason' justifying it"
        )
    if d["rule"] not in RULES:
        raise WaiverError(
            f"{path}:{line}: unknown rule {d['rule']!r}; "
            f"known: {sorted(RULES)}"
        )
    if not d["reason"].strip():
        raise WaiverError(f"{path}:{line}: empty waiver reason")
    if d.get("expires"):
        import datetime

        try:
            datetime.date.fromisoformat(d["expires"])
        except ValueError:
            raise WaiverError(
                f"{path}:{line}: expires must be YYYY-MM-DD, got "
                f"{d['expires']!r}"
            ) from None
    return Waiver(**d)


def load_waivers(path: Optional[str]) -> List[Waiver]:
    """Waivers from ``path`` (missing file = no waivers)."""
    if path is None or not os.path.exists(path):
        return []
    with open(path) as fh:
        return _parse_waivers_toml(fh.read(), path)


def apply_waivers(
    findings: List[Finding], waivers: List[Waiver]
) -> Tuple[List[Finding], List[Finding], List[Waiver]]:
    """Split findings into (active, waived); also return UNUSED waivers
    (stale entries worth pruning — reported, not fatal)."""
    active: List[Finding] = []
    waived: List[Finding] = []
    for f in findings:
        for w in waivers:
            if w.matches(f):
                f.waived = True
                f.waiver_reason = w.reason
                w.used += 1
                waived.append(f)
                break
        else:
            active.append(f)
    unused = [w for w in waivers if w.used == 0]
    return active, waived, unused
