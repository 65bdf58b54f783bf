"""stpu-lint (stateright_tpu/analysis): every rule ID trips on a
deliberately-bad golden kernel (positive detection), the shipped tree
sweeps clean under the justified waivers, and the waiver file
round-trips.

The golden fixtures are the pinned pathologies rebuilt in miniature —
each one is the exact shape a backend broke on (docs/static-analysis.md
carries the history), so a rule that stops firing here has stopped
guarding the real thing.
"""

import os
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from stateright_tpu.analysis import (
    Finding,
    WaiverError,
    apply_waivers,
    load_waivers,
    run_lint,
)
from stateright_tpu.analysis.astlint import lint_file, run_ast_pass
from stateright_tpu.analysis.jaxpr_lint import (
    cond_flush_sorts,
    mosaic_kernel_rules,
    output_transposes,
    taint_scatters,
    wide_sorts,
)
from stateright_tpu.analysis.surfaces import run_sweep


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# --- STPU001: data-dependent scatter in a vmapped kernel --------------------


def test_stpu001_flags_traced_index_scatter(monkeypatch):
    import stateright_tpu.packing as packing

    monkeypatch.setattr(packing, "ONE_HOT_WRITES", True)

    def bad(words, i):  # the round-3/5 paxos-drift shape
        return words.at[i].set(jnp.uint32(1))

    jx = jax.make_jaxpr(jax.vmap(bad))(
        _sds((4096, 8), jnp.uint32), _sds((4096,), jnp.uint32)
    )
    hits = taint_scatters(jx, "golden:stpu001")
    assert [f.rule for f in hits] == ["STPU001"]
    assert "stateright_tpu" not in hits[0].file  # anchored to THIS file
    assert hits[0].line > 0


def test_stpu001_static_index_scatter_is_exempt():
    def ok(words):  # static-index write: XLA folds it, drift never repro'd
        return words.at[3].set(jnp.uint32(1))

    jx = jax.make_jaxpr(jax.vmap(ok))(_sds((4096, 8), jnp.uint32))
    assert taint_scatters(jx, "golden:static") == []


def test_stpu001_word_update_path_is_clean(monkeypatch):
    """The sanctioned lowering (packing._word_update under the
    accelerator pin) emits no scatter at all — the generalized form of
    the old test_packing HLO pin."""
    import stateright_tpu.packing as packing
    from stateright_tpu.packing import LayoutBuilder

    monkeypatch.setattr(packing, "ONE_HOT_WRITES", True)
    lay = LayoutBuilder().array("xs", 6, 4).finish()

    def good(words, i):
        return lay.set(words, "xs", 3, i)

    jx = jax.make_jaxpr(jax.vmap(good))(
        _sds((4096, lay.words), jnp.uint32), _sds((4096,), jnp.uint32)
    )
    assert taint_scatters(jx, "golden:word-update") == []


# --- STPU002: transpose fused into a vmapped kernel -------------------------


def test_stpu002_flags_out_axes_transpose():
    def kernel(words):
        return words * jnp.uint32(2)

    jx = jax.make_jaxpr(jax.vmap(kernel, out_axes=1))(_sds((64, 4), jnp.uint32))
    hits = output_transposes(jx, "golden:stpu002")
    assert [f.rule for f in hits] == ["STPU002"]
    assert "transpose" in hits[0].excerpt
    assert "out_axes != 0" in hits[0].message  # the direct-output form

    clean = jax.make_jaxpr(jax.vmap(kernel))(_sds((64, 4), jnp.uint32))
    assert output_transposes(clean, "golden:rows") == []


def test_stpu002_flags_mid_kernel_transpose():
    """The documented gap, closed: a transpose buried BETWEEN ops (here
    a nested vmap(out_axes=1) whose transpose feeds a further add, so it
    does not produce the surface's outputs directly) is still the
    transpose-fused-into-vmap shape XLA:CPU miscompiles."""

    def inner(col):
        return col + jnp.uint32(1)

    def kernel(words):  # words [4, 4]
        cols = jax.vmap(inner, out_axes=1)(words)  # transpose, mid-kernel
        return cols + jnp.uint32(1)  # ...consumed by a further op

    jx = jax.make_jaxpr(jax.vmap(kernel))(_sds((64, 4, 4), jnp.uint32))
    hits = output_transposes(jx, "golden:mid-kernel")
    assert hits and all(f.rule == "STPU002" for f in hits)
    assert any("mid-kernel" in f.message for f in hits)


# --- STPU003: the wide-W sort compile-stall shape ---------------------------


def test_stpu003_flags_wide_sort():
    W = 25  # paxos width: the round-5 stall was its W+3-operand sort

    def bad(*lanes):
        return jax.lax.sort(lanes, num_keys=1)

    args = [_sds((1024,), jnp.uint32) for _ in range(W + 3)]
    jx = jax.make_jaxpr(bad)(*args)
    hits = wide_sorts(jx, "golden:stpu003")
    assert [f.rule for f in hits] == ["STPU003"]
    assert "28-operand" in hits[0].message

    ok = jax.make_jaxpr(bad)(*args[:12])  # the chip-proven W<=8 class
    assert wide_sorts(ok, "golden:narrow") == []


# --- STPU004: deltaset flush under lax.cond ---------------------------------


def test_stpu004_flags_flush_under_cond():
    from stateright_tpu.ops import deltaset

    ds = deltaset.make(1 << 13, jnp)

    def bad(ds, pred):  # the round-5 "TPU worker crashed" shape
        return jax.lax.cond(
            pred, lambda d: deltaset.maintain(d)[0], lambda d: d, ds
        )

    jx = jax.make_jaxpr(bad)(ds, _sds((), jnp.bool_))
    hits = cond_flush_sorts(jx, "golden:stpu004", ds.main_capacity)
    assert hits and all(f.rule == "STPU004" for f in hits)
    assert "ops/deltaset.py" in hits[0].file

    # The host-invoked form (the shipped protocol) is clean.
    ok = jax.make_jaxpr(deltaset.maintain)(ds)
    assert cond_flush_sorts(ok, "golden:maintain", ds.main_capacity) == []


# --- STPU005: Mosaic TC kernel rules ----------------------------------------


def _pallas_jaxpr(kernel, n=256):
    from jax.experimental import pallas as pl

    def run(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((n,), jnp.int32)
        )(x)

    return jax.make_jaxpr(run)(_sds((n,), jnp.int32))


def test_stpu005_flags_cumsum_in_kernel():
    def bad_kernel(x_ref, o_ref):  # the r5e first-silicon lowering gap
        o_ref[...] = jnp.cumsum(x_ref[...])

    hits = mosaic_kernel_rules(_pallas_jaxpr(bad_kernel), "golden:cumsum")
    assert hits and all(f.rule == "STPU005" for f in hits)
    assert "cumsum" in hits[0].message


def test_stpu005_flags_u32_f32_cast_in_kernel():
    def bad_kernel(x_ref, o_ref):
        f = x_ref[...].astype(jnp.uint32).astype(jnp.float32)  # direct cast
        o_ref[...] = f.astype(jnp.int32)

    hits = mosaic_kernel_rules(_pallas_jaxpr(bad_kernel), "golden:cast")
    assert any("u32<->f32" in f.message for f in hits)


def test_stpu005_i32_hop_is_clean():
    def ok_kernel(x_ref, o_ref):  # the sanctioned value-exact hop
        f = x_ref[...].astype(jnp.float32)
        o_ref[...] = f.astype(jnp.int32)

    assert mosaic_kernel_rules(_pallas_jaxpr(ok_kernel), "golden:hop") == []


def test_stpu005_shipped_kernels_preflight_for_tpu():
    """Registry #6 as one command: both ops/ pallas kernels lower for
    the TPU target from this CPU-only process (this is the check that
    caught the integer-reduction Mosaic gap in both kernels)."""
    reports = {r.name: r for r in run_sweep(only=["pallas:"])}
    assert {"pallas:compact", "pallas:merge"} <= set(reports)
    for rep in reports.values():
        assert rep.error == "", rep.error
        assert rep.findings == [], [f.message for f in rep.findings]


# --- STPU006: static VMEM budget for pallas kernels -------------------------


def test_stpu006_flags_oversized_vmem_kernel():
    """A kernel whose scratch ring alone blows the ~16 MiB v5e budget —
    today this shape is a runtime Mosaic allocation error discovered ON
    CHIP; the flight-check prices it statically."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from stateright_tpu.analysis.jaxpr_lint import vmem_budget

    def kernel(x_ref, o_ref, big_scratch):
        o_ref[...] = x_ref[...]

    def run(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((256,), jnp.float32),
            scratch_shapes=[pltpu.VMEM((4096, 4096), jnp.float32)],  # 64 MiB
        )(x)

    jx = jax.make_jaxpr(run)(_sds((256,), jnp.float32))
    hits = vmem_budget(jx, "golden:stpu006")
    assert [f.rule for f in hits] == ["STPU006"]
    assert "VMEM footprint" in hits[0].message
    assert "scratch" in hits[0].message


def test_stpu006_shipped_kernels_fit_across_block_range():
    """Both shipped kernels price under the budget at every supported
    STPU_PALLAS_BLOCK (the per-block surfaces in the sweep)."""
    reports = {r.name: r for r in run_sweep(only=["pallas:vmem:"])}
    assert reports, "per-block vmem surfaces missing from the sweep"
    for rep in reports.values():
        assert rep.error == "", rep.error
        assert rep.findings == [], [f.message for f in rep.findings]


# --- STPU007: the compile-plan census ----------------------------------------


def test_stpu007_flags_over_budget_plan():
    from stateright_tpu.analysis.census import census_findings, plan_for

    plan = plan_for("2pc:3", "tpu", frontier_capacity=1 << 22)
    census = {"specs": {"2pc:3": {"tpu": plan}}}
    hits = census_findings(census)
    assert [f.rule for f in hits] == ["STPU007"]
    assert f"{plan['distinct_programs']} distinct" in hits[0].message
    assert plan["distinct_programs"] > plan["budget"]


def test_census_matches_shipped_and_planner():
    """The census is the SHIPPED registry run through the shared ladder
    planner — drift in either direction is a failure — and the warm set
    tools/warm_cache.py derives equals it exactly."""
    import importlib.util

    from stateright_tpu.analysis.census import build_census, census_findings, warm_specs
    from stateright_tpu.service.registry import SHIPPED, resolve
    from stateright_tpu.xla import default_cand_cap, ladder_buckets

    census = build_census()
    assert list(census["specs"]) == list(SHIPPED)
    assert census_findings(census) == []  # every shipped plan in budget
    assert warm_specs(census) == list(SHIPPED)

    # The census's shapes are the shared planner's, at the registry
    # capacities (spot-check one spec end to end).
    model, caps = resolve("paxos:2,3")
    plan = census["specs"]["paxos:2,3"]["tpu"]
    buckets = ladder_buckets(caps["frontier_capacity"])
    assert [s["bucket"] for s in plan["shapes"]] == buckets
    assert plan["shapes"][-1]["cand_cap"] == default_cand_cap(
        buckets[-1], model.max_actions, "tpu", env={}
    )

    # tools/warm_cache.py's default --specs goes through the same
    # derivation (the warm set is derived, not hand-maintained).
    spec = importlib.util.spec_from_file_location(
        "warm_cache", os.path.join(os.path.dirname(__file__), "..", "tools", "warm_cache.py")
    )
    wc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wc)
    assert wc.default_specs() == list(SHIPPED)


# --- STPU008: cross-backend lowering diff ------------------------------------


def test_stpu008_flags_one_sided_pathology_op():
    from stateright_tpu.analysis.jaxpr_lint import diff_lowering_inventories

    base = {"stablehlo.add", "stablehlo.compare", "stablehlo.iota"}
    hits = diff_lowering_inventories(
        "golden:stpu008",
        base | {"stablehlo.scatter"},  # cpu lowers a scatter...
        base,  # ...tpu lowers none — the dropped-write class
    )
    assert [f.rule for f in hits] == ["STPU008"]
    assert "stablehlo.scatter" in hits[0].message
    assert "cpu" in hits[0].excerpt

    # Symmetric inventories — even with pathology ops on BOTH sides —
    # are clean: the rule is about divergence, not presence (STPU001/003
    # own presence).
    both = base | {"stablehlo.sort"}
    assert diff_lowering_inventories("golden:same", both, both) == []
    # A non-registry op on one side only is noise, not a finding.
    assert (
        diff_lowering_inventories("golden:benign", base | {"stablehlo.tanh"}, base)
        == []
    )


def test_stpu008_shipped_kernels_lower_identically():
    """Both width classes' transition kernels produce identical
    pathology-op inventories on cpu and tpu lowerings (the integration
    form; the sweep runs these surfaces by default — the solo kernel,
    the ISSUE 16 batched mux superstep, and the ISSUE 19 symmetry
    canonicalization kernel)."""
    reports = {r.name: r for r in run_sweep(only=["lower:2pc:3"])}
    assert set(reports) == {
        "lower:2pc:3:packed_step",
        "lower:2pc:3:mux-superstep:k2",
        "lower:2pc:3:sym-canon",
    }
    for rep in reports.values():
        assert rep.error == "", rep.error
        assert rep.findings == [], [f.message for f in rep.findings]


# --- the sharded mesh engine is a traced surface -----------------------------


def test_sharded_superstep_is_a_registered_surface():
    """The second documented missing surface, closed: the mesh engine's
    shard_map superstep traces under the 8-device virtual CPU mesh (the
    config tests/conftest.py forces) in both dedup configs."""
    import jax as _jax

    reports = {r.name: r for r in run_sweep(only=["sharded-superstep"])}
    assert set(reports) == {
        "engine:2pc:3:sharded-superstep:hash",
        "engine:2pc:3:sharded-superstep:sorted",
    }
    for rep in reports.values():
        if len(_jax.devices()) < 8:  # pragma: no cover - conftest forces 8
            assert rep.skipped
            continue
        assert rep.error == "", rep.error
        assert rep.skipped == ""
        assert rep.findings == [], [f.message for f in rep.findings]


# --- AST rules (STPU101, STPU103) ------------------------------------------------


def _lint_source(tmp_path, rel, text):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(text))
    return lint_file(str(p), rel)


def test_stpu101_flags_at_write_in_models(tmp_path):
    hits = _lint_source(
        tmp_path,
        "models/bad_model.py",
        """
        def packed_step(self, words, i):
            return words.at[i].set(1)
        """,
    )
    assert [f.rule for f in hits] == ["STPU101"]
    assert ".at[i].set(1)" in hits[0].excerpt
    # The same write outside models/ is not this rule's business.
    assert (
        _lint_source(tmp_path, "ops/fine.py", "def f(w, i):\n    return w.at[i].set(1)\n")
        == []
    )


def test_stpu103_flags_raw_heartbeat_write(tmp_path):
    hits = _lint_source(
        tmp_path,
        "service/sloppy.py",
        """
        def beat(heartbeat_path, payload):
            with open(heartbeat_path, "w") as fh:
                fh.write(payload)
        """,
    )
    assert [f.rule for f in hits] == ["STPU103"]
    # The owning codecs (obs/) are exempt — they implement the atomic
    # tmp + os.replace pattern this rule protects.
    assert (
        _lint_source(
            tmp_path,
            "obs/heartbeat2.py",
            "def beat(heartbeat_path, s):\n"
            '    with open(heartbeat_path, "w") as fh:\n'
            "        fh.write(s)\n",
        )
        == []
    )
    # Reads are fine anywhere.
    assert (
        _lint_source(
            tmp_path,
            "service/reader.py",
            "def read(heartbeat_path):\n"
            '    with open(heartbeat_path, "r") as fh:\n'
            "        return fh.read()\n",
        )
        == []
    )


# --- waiver round-trip ------------------------------------------------------


def test_waiver_round_trip(tmp_path):
    f1 = Finding(
        rule="STPU001", surface="ops:hashset-insert", file="stateright_tpu/ops/hashset.py",
        line=5, message="m", excerpt="e",
    )
    f2 = Finding(
        rule="STPU001", surface="kernel:2pc:3:packed_step", file="stateright_tpu/models/x.py",
        line=9, message="m", excerpt="e",
    )
    wpath = tmp_path / "w.toml"
    wpath.write_text(
        "# comment\n"
        "[[waiver]]\n"
        'rule = "STPU001"\n'
        'surface = "ops:hashset-insert"\n'
        'reason = "by design"\n'
        "\n"
        "[[waiver]]\n"
        'rule = "STPU003"\n'
        'reason = "never matches"\n'
    )
    waivers = load_waivers(str(wpath))
    active, waived, unused = apply_waivers([f1, f2], waivers)
    assert [f.surface for f in active] == ["kernel:2pc:3:packed_step"]
    assert [f.surface for f in waived] == ["ops:hashset-insert"]
    assert waived[0].waiver_reason == "by design"
    assert [w.rule for w in unused] == ["STPU003"]  # stale, reported


def test_waiver_expiry_stops_suppressing(tmp_path):
    """An expired waiver is reported like a stale one and its findings
    go ACTIVE — chip-A/B-pending waivers cannot rot past their window."""
    f = Finding(
        rule="STPU001", surface="ops:hashset-insert",
        file="stateright_tpu/ops/hashset.py", line=5, message="m", excerpt="e",
    )
    wpath = tmp_path / "w.toml"
    wpath.write_text(
        "[[waiver]]\n"
        'rule = "STPU001"\n'
        'surface = "ops:hashset-insert"\n'
        'reason = "pending chip A/B"\n'
        'expires = "2026-01-01"\n'  # past (today is later)
    )
    waivers = load_waivers(str(wpath))
    assert waivers[0].expired
    active, waived, unused = apply_waivers([f], waivers)
    assert [x.surface for x in active] == ["ops:hashset-insert"]
    assert waived == []
    assert unused == waivers  # reported like stale

    # A future expiry still suppresses.
    wpath.write_text(
        "[[waiver]]\n"
        'rule = "STPU001"\n'
        'surface = "ops:hashset-insert"\n'
        'reason = "pending chip A/B"\n'
        'expires = "2099-01-01"\n'
    )
    f2 = Finding(
        rule="STPU001", surface="ops:hashset-insert",
        file="stateright_tpu/ops/hashset.py", line=5, message="m", excerpt="e",
    )
    active, waived, unused = apply_waivers([f2], load_waivers(str(wpath)))
    assert active == [] and len(waived) == 1 and unused == []

    # Garbage dates are loud, not silently never-expiring.
    wpath.write_text(
        '[[waiver]]\nrule = "STPU001"\nreason = "x"\nexpires = "soonish"\n'
    )
    with pytest.raises(WaiverError, match="YYYY-MM-DD"):
        load_waivers(str(wpath))


def test_expired_waiver_reported_in_cli_report(tmp_path):
    """run_lint marks the expired entry even on a partial run (unlike
    merely-stale waivers, an expired one is actionable on ANY run)."""
    wpath = tmp_path / "w.toml"
    wpath.write_text(
        "[[waiver]]\n"
        'rule = "STPU003"\n'
        'reason = "pending chip A/B"\n'
        'expires = "2026-01-01"\n'
    )
    report = run_lint(trace=False, ast_pass=True, waivers_path=str(wpath))
    assert report["partial"] is True  # AST-only run
    expired = [w for w in report["unused_waivers"] if w["expired"]]
    assert [w["rule"] for w in expired] == ["STPU003"]
    assert expired[0]["expires"] == "2026-01-01"


def test_waiver_file_is_loud_on_garbage(tmp_path):
    bad = tmp_path / "w.toml"
    bad.write_text("[[waiver]]\nrule = STPU001\n")  # unquoted value
    with pytest.raises(WaiverError):
        load_waivers(str(bad))
    bad.write_text('[[waiver]]\nrule = "STPU999"\nreason = "x"\n')
    with pytest.raises(WaiverError):
        load_waivers(str(bad))
    bad.write_text('[[waiver]]\nrule = "STPU001"\n')  # no reason
    with pytest.raises(WaiverError):
        load_waivers(str(bad))
    assert load_waivers(str(tmp_path / "missing.toml")) == []


# --- the shipped tree sweeps clean ------------------------------------------


def test_ast_pass_shipped_tree_clean():
    """Whole-package AST pass: every finding is covered by a justified
    waiver in .stpu-lint-waivers.toml."""
    report = run_lint(trace=False, ast_pass=True)
    assert report["errors"] == []
    assert report["findings"] == [], report["findings"]


def test_trace_sweep_shipped_subset_clean():
    """Jaxpr pass over the narrow-model surface subset (the full-tree
    sweep is tools/smoke.sh's lint stage — this keeps the tier-1 pin
    fast): kernels + engine configs + ops + pallas for 2pc, all clean
    under the justified waivers."""
    report = run_lint(
        trace=True, ast_pass=False, only=["2pc:3", "ops:", "pallas:"]
    )
    assert report["errors"] == []
    assert report["findings"] == [], report["findings"]
    # The waivers are LIVE: the hashset scatter and the planes-expand
    # transpose still fire and are waived — a waiver matching nothing
    # would mean the surface moved and the rule went blind.
    waived_rules = {f["rule"] for f in report["waived"]}
    assert {"STPU001", "STPU002"} <= waived_rules


@pytest.mark.slow
def test_full_lint_clean():
    """The complete default sweep (what `python -m stateright_tpu.analysis`
    runs; smoke.sh's lint stage budget-pins it at <60 s)."""
    report = run_lint()
    assert report["errors"] == []
    assert report["findings"] == [], report["findings"]
    assert report["unused_waivers"] == [], report["unused_waivers"]


# --- CLI exit-code-2 paths and the partial contract --------------------------


def test_cli_exit_2_on_malformed_waiver_file(tmp_path, capsys):
    from stateright_tpu.analysis.cli import main

    bad = tmp_path / "w.toml"
    bad.write_text("[[waiver]]\nrule = STPU001\n")  # unquoted value
    rc = main(["--no-trace", "--waivers", str(bad)])
    assert rc == 2
    assert "waiver file error" in capsys.readouterr().err


def test_cli_exit_2_on_surface_trace_failure(monkeypatch, tmp_path):
    """A surface that cannot be TRACED is exit 2 (not verified), never a
    silent pass — and the report's errors list names it."""
    from stateright_tpu.analysis import surfaces
    from stateright_tpu.analysis.cli import main

    def boom():
        raise RuntimeError("golden trace failure")

    monkeypatch.setattr(
        surfaces, "build_sweep", lambda full=False: [("golden:boom", boom)]
    )
    out = tmp_path / "lint.json"
    rc = main(["--no-ast", "--no-cache", "--json-out", str(out)])
    assert rc == 2
    import json as _json

    report = _json.loads(out.read_text())
    assert report["ok"] is False
    assert report["errors"] == ["golden:boom: RuntimeError: golden trace failure"]
    assert report["surfaces"][0]["error"].startswith("RuntimeError")


def test_cli_exit_2_on_unknown_admission_spec(capsys):
    from stateright_tpu.analysis.cli import main

    rc = main(["--admission", "nosuchfamily:3", "--no-cache"])
    assert rc == 2
    assert "unknown model spec" in capsys.readouterr().err


def test_partial_contract_for_lint_ok_provenance(tmp_path, monkeypatch):
    """The contract bench.py's lint_ok tri-state relies on: every
    filtered run is marked partial, and bench treats a partial artifact
    as None (not a pass, not a fail)."""
    report = run_lint(trace=False, ast_pass=True)
    assert report["partial"] is True
    report = run_lint(
        trace=True, ast_pass=False, only=["plan:shipped"], use_cache=False
    )
    assert report["partial"] is True
    report = run_lint(trace=False, ast_pass=True, rules=["STPU101"])
    assert report["partial"] is True

    import bench

    runs = tmp_path / "runs"
    runs.mkdir()
    # A partial artifact -> None, even when fresh and ok.
    (runs / "lint.json").write_text('{"ok": true, "partial": true}')
    monkeypatch.setattr(bench, "RUNS", str(runs))
    monkeypatch.setattr(bench, "REPO", str(tmp_path))  # no newer sources
    assert bench._lint_ok() is None
    # A full artifact -> its verdict.
    (runs / "lint.json").write_text('{"ok": true, "partial": false}')
    assert bench._lint_ok() is True
    (runs / "lint.json").write_text('{"ok": false, "partial": false}')
    assert bench._lint_ok() is False
    # Missing artifact -> None.
    (runs / "lint.json").unlink()
    assert bench._lint_ok() is None


def test_compile_plan_provenance_reads_census(tmp_path, monkeypatch):
    import bench

    runs = tmp_path / "runs"
    runs.mkdir()
    monkeypatch.setattr(bench, "RUNS", str(runs))
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    assert bench._compile_plan() is None  # no artifact
    (runs / "compile_plan.json").write_text(
        '{"tree": "abc", "specs": {"2pc:3": {"tpu": '
        '{"distinct_programs": 3}}}}'
    )
    plan = bench._compile_plan()
    assert plan == {
        "tree": "abc",
        "distinct_programs": {"2pc:3": {"tpu": 3}},
    }


# --- the content-hash surface cache ------------------------------------------


def test_surface_cache_round_trip(tmp_path):
    """Second run replays findings from the cache (cached=True, same
    findings); --no-cache forces a fresh trace; errors are not cached."""
    from stateright_tpu.analysis.surfaces import run_sweep as sweep

    cold = sweep(only=["plan:shipped"], cache_dir=str(tmp_path))
    assert [r.cached for r in cold] == [False]
    warm = sweep(only=["plan:shipped"], cache_dir=str(tmp_path))
    assert [r.cached for r in warm] == [True]
    assert [f.to_json() for f in warm[0].findings] == [
        f.to_json() for f in cold[0].findings
    ]
    fresh = sweep(only=["plan:shipped"], cache_dir=str(tmp_path), use_cache=False)
    assert [r.cached for r in fresh] == [False]


def test_surface_cache_invalidates_on_tree_change(tmp_path, monkeypatch):
    from stateright_tpu.analysis import cache as cache_mod

    c1 = cache_mod.SurfaceCache(str(tmp_path))
    f = Finding(rule="STPU003", surface="s", file="f.py", line=1,
                message="m", excerpt="e")
    c1.put("s", [f])
    assert [x.message for x in c1.get("s")] == ["m"]
    # A different tree hash misses; the old tree's entries stay warm
    # (within the keep-K bound) for branch switches.
    monkeypatch.setattr(cache_mod, "_tree_hash_memo", "f" * 64)
    c2 = cache_mod.SurfaceCache(str(tmp_path))
    assert c2.get("s") is None
    c2.put("s", [])
    assert "f" * 12 in os.listdir(tmp_path)
    assert c1.dir.split(os.sep)[-1] in os.listdir(tmp_path)


def test_surface_cache_bounds_tree_dirs(tmp_path, monkeypatch):
    """Per-commit tree dirs must not accumulate forever: lint startup
    keeps the newest K (current tree always included), deletes older."""
    import time as time_mod

    from stateright_tpu.analysis import cache as cache_mod

    for i in range(6):
        d = tmp_path / f"{i:012d}"
        d.mkdir()
        (d / "x.json").write_text("{}")
        old = time_mod.time() - (10 - i) * 1000
        os.utime(d, (old, old))
    monkeypatch.setattr(cache_mod, "_tree_hash_memo", "a" * 64)
    cache = cache_mod.SurfaceCache(str(tmp_path), keep_trees=3)
    survivors = sorted(os.listdir(tmp_path))
    # Newest keep-1 == 2 foreign dirs survive next to the current tree.
    assert survivors == ["000000000004", "000000000005"]
    cache.put("s", [])
    assert sorted(os.listdir(tmp_path)) == [
        "000000000004", "000000000005", "a" * 12
    ]
    # STPU_LINT_CACHE_KEEP drives the default.
    monkeypatch.setenv("STPU_LINT_CACHE_KEEP", "1")
    cache_mod.SurfaceCache(str(tmp_path))
    assert sorted(os.listdir(tmp_path)) == ["a" * 12]


# --- SARIF output ------------------------------------------------------------


def test_sarif_output(tmp_path):
    import json as _json

    from stateright_tpu.analysis.cli import write_sarif

    report = run_lint(trace=False, ast_pass=True)
    # A waived finding rides as a SARIF note with its location.
    hit = _lint_source(
        tmp_path, "models/waived.py", "def f(w, i):\n    return w.at[i].set(1)\n"
    )[0]
    hit.waived, hit.waiver_reason = True, "test waiver"
    report["waived"] = list(report["waived"]) + [hit.to_json()]
    path = tmp_path / "lint.sarif"
    write_sarif(report, str(path))
    sarif = _json.loads(path.read_text())
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "stpu-lint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"STPU001", "STPU006", "STPU007", "STPU008"} <= rule_ids
    notes = [r for r in run["results"] if r["level"] == "note"]
    assert notes, "expected the waived finding as a SARIF note"
    assert all(r["ruleId"] in rule_ids for r in run["results"])
    located = [r for r in run["results"] if "locations" in r]
    assert located and all(
        r["locations"][0]["physicalLocation"]["region"]["startLine"] >= 1
        for r in located
    )
