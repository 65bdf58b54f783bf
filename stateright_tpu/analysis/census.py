"""STPU007: the compile-plan census.

Every distinct (bucket, cand-rung schedule) is a separate XLA
compilation — tens of seconds to minutes each from the TPU compiler — and
compile latency, not run time, dominates a cold chip run. The ladder
planner that decides those shapes is now ONE shared definition
(``xla.ladder_buckets`` / ``default_cand_cap`` / ``cand_rungs`` — the
engine delegates to the same functions), so the exact program shapes a
model's run plan will compile are statically enumerable with no tracing
and no device:

- :func:`plan_for` — one spec's plan on one platform: resolved dedup /
  compaction (the same policy ``XlaChecker.__init__`` applies), the
  bucket ladder for the registry capacities, and each bucket's fused
  rung schedule;
- :func:`build_census` — the full shipped census, keyed by spec; the CLI
  writes it to ``runs/compile_plan.json`` on every full run, and
  ``tools/warm_cache.py`` derives its warm set from it (the warm set is
  DERIVED, not a second hand-maintained shape list — a census/SHIPPED
  drift is a test failure, ``tests/test_analysis.py``);
- :func:`census_findings` — STPU007 proper: a plan whose distinct shape
  count blows its budget (``rules.MAX_COMPILE_SHAPES``, or the model's
  own ``xla_compile_budget`` attribute) is a finding before it is a
  burned window.

The census is hermetic: candidate-cap sizing ignores the caller's
``STPU_CAND_FRAC`` (an empty env is passed through), so the artifact
describes the TREE's plan, not the shell's.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..xla import (
    CAND_LADDER_AUTO_K,
    accel_auto_compaction,
    auto_dedup,
    cand_rungs,
    default_cand_cap,
    ladder_buckets,
)
from .rules import MAX_COMPILE_SHAPES, Finding

#: The platforms a shipped plan is enumerated for: the CPU policy (hash
#: dedup, gather compaction, no cand ladder) and the accelerator policy
#: (sorted dedup, width-resolved compaction, auto-depth cand ladder).
PLATFORMS = ("cpu", "tpu")


def plan_for(
    spec: str,
    platform: str,
    *,
    frontier_capacity: Optional[int] = None,
    table_capacity: Optional[int] = None,
    mux_k: Optional[int] = None,
    symmetry: bool = False,
    _resolved=None,
) -> Dict[str, Any]:
    """The compile plan one spec commits to on one platform, at the
    registry's shipped capacities (override for what-if probes and the
    golden-bad tests). Growth events (frontier/table doubling) are
    excluded: the census prices the DECLARED plan, which is also exactly
    the shape set ``tools/warm_cache.py`` can pre-compile.
    ``_resolved`` lets :func:`build_census` resolve each spec's model
    once instead of once per platform.

    ``mux_k`` adds the multiplexed-superstep shape classes a service
    running with ``STPU_MUX=K`` would additionally compile
    (``xla_mux.py``; docs/service.md "Batched scheduling"): one batched
    program per bucket at lane count K — the mux engine has no in-program
    cand ladder, so its shape class is exactly ``(k, bucket, cand_cap)``.
    Only mux-eligible plans get the sub-dict (family in
    ``registry.MUX_FAMILIES``, non-delta dedup); when present, the mux
    programs count toward the same STPU007 budget — batching is opt-in,
    so the default census (and the banked ``runs/compile_plan.json``)
    stays the solo plan.

    ``symmetry`` adds the symmetry-variant shape classes
    (docs/symmetry.md): every bucket program recompiles under the
    canonicalization tag in its cache key when ``STPU_SYMMETRY=1``, so a
    symmetry-on service doubles the plan. Only models shipping a
    ``symmetry_spec`` (or ``packed_representative``) get the ``sym``
    sub-dict; it counts toward the same STPU007 budget, and — like mux —
    the default census stays the symmetry-off plan."""
    if _resolved is None:
        from ..service.registry import resolve

        _resolved = resolve(spec)
    model, caps = _resolved
    W, A = model.state_words, model.max_actions
    f_cap = frontier_capacity or caps["frontier_capacity"]
    t_cap = table_capacity or caps["table_capacity"]
    # The same policy resolution XlaChecker.__init__ applies (minus env
    # A/B knobs — the census is hermetic): every constant here is the
    # ENGINE's export, so a policy change re-aims the census with it.
    dedup = auto_dedup(platform)
    compaction = "gather" if platform == "cpu" else accel_auto_compaction(W)
    k = 1 if dedup == "hash" else CAND_LADDER_AUTO_K

    def cap_of(rc: int) -> int:
        return default_cand_cap(rc, A, platform, env={})

    shapes: List[Dict[str, Any]] = []
    for bucket in ladder_buckets(f_cap):
        shapes.append(
            {
                "bucket": bucket,
                "cand_cap": cap_of(bucket),
                "rungs": [list(r) for r in cand_rungs(bucket, cap_of, k)],
            }
        )
    plan = {
        "spec": spec,
        "platform": platform,
        "state_words": W,
        "max_actions": A,
        "dedup": dedup,
        "compaction": compaction,
        "frontier_capacity": f_cap,
        "table_capacity": t_cap,
        "shapes": shapes,
        "distinct_programs": len(shapes),
        "budget": int(getattr(model, "xla_compile_budget", MAX_COMPILE_SHAPES)),
    }
    if symmetry:
        spec_obj = getattr(model, "symmetry_spec", None)
        tag = (
            f"spec:{spec_obj.spec_hash()[:12]}"
            if spec_obj is not None
            else (
                "model:packed_representative"
                if hasattr(model, "packed_representative")
                else None
            )
        )
        if tag is not None:
            plan["sym"] = {
                "tag": tag,
                # One symmetry-variant program per solo shape (same
                # buckets/rungs; the canon kernel fuses into each).
                "distinct_programs": len(shapes),
            }
    if mux_k is not None and mux_k > 1:
        from ..service.registry import MUX_FAMILIES, parse

        if parse(spec)[0] in MUX_FAMILIES and dedup != "delta":
            plan["mux"] = {
                "k": mux_k,
                "shapes": [
                    {"bucket": b, "cand_cap": cap_of(b)}
                    for b in ladder_buckets(f_cap)
                ],
            }
            plan["mux"]["distinct_programs"] = len(plan["mux"]["shapes"])
    return plan


def build_census(
    specs: Optional[List[str]] = None,
    mux_k: Optional[int] = None,
    symmetry: bool = False,
) -> Dict[str, Any]:
    """The full census: every shipped spec's plan on both platforms.
    Callers that may touch a fresh jax process (``tools/warm_cache.py``'s
    parent) must ``surfaces.pin_cpu()`` first — model resolution builds
    packed layouts, and a parent that initialized the TPU would hold the
    chip its worker children need."""
    from ..service.registry import SHIPPED, resolve

    out: Dict[str, Any] = {"specs": {}}
    for spec in specs if specs is not None else list(SHIPPED):
        resolved = resolve(spec)
        out["specs"][spec] = {
            p: plan_for(
                spec, p, mux_k=mux_k, symmetry=symmetry, _resolved=resolved
            )
            for p in PLATFORMS
        }
    return out


def census_findings(census: Dict[str, Any]) -> List[Finding]:
    """STPU007 over a built census: one finding per (spec, platform)
    plan whose distinct program count exceeds its declared budget."""
    findings: List[Finding] = []
    for spec, plans in census["specs"].items():
        for platform, plan in plans.items():
            # A mux-enabled census prices the TOTAL a batching service
            # compiles: the solo plan plus one batched program per
            # bucket at lane count K.
            n = (
                plan["distinct_programs"]
                + plan.get("mux", {}).get("distinct_programs", 0)
                + plan.get("sym", {}).get("distinct_programs", 0)
            )
            budget = plan["budget"]
            if n <= budget:
                continue
            buckets = [s["bucket"] for s in plan["shapes"]]
            findings.append(
                Finding(
                    rule="STPU007",
                    surface=f"plan:{spec}:{platform}",
                    file="",
                    line=0,
                    message=(
                        f"run plan compiles {n} distinct program shapes "
                        f"(budget {budget}): buckets {buckets} — at tens "
                        "of seconds to minutes per TPU compile this plan "
                        "burns the chip call before it measures; lower the "
                        "frontier ceiling or declare a bigger "
                        "xla_compile_budget with a justification"
                    ),
                    excerpt=f"buckets={buckets}",
                )
            )
    return findings


def warm_specs(census: Optional[Dict[str, Any]] = None) -> List[str]:
    """The warm-cache spec list, DERIVED from the census (one entry per
    censused spec, shipped order) — ``tools/warm_cache.py``'s default
    ``--specs``."""
    if census is None:
        census = build_census()
    return list(census["specs"])
