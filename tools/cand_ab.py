"""Engine-level A/B for the in-program candidate-width ladder.

Grid: ``cand_ladder`` 3 (on) vs 1 (off) x dedup sorted/delta x bucket
ladder ramp/jump, each a full count-checked 2pc check (warm pass
compiles, measured pass times). Every variant runs in its own
SUBPROCESS under a hard timeout (hang-proof;
``STPU_CAND_LADDER`` rides the documented process-restart convention
even though it is spawn-arg-plumbed, so a wedged child can't poison the
next variant). The parent pairs on/off rows and reports:

- ``median_lane_ratio``: ladder-off / ladder-on sorted-lane-words at the
  MEDIAN level (the acceptance metric for the candidate ladder — the
  cost law says per-level time ~ lane-words x log^2 n, so this
  ratio is the engine-measured win, provable on 1-core CPU);
- ``dispatches_equal``: the ladder must add ZERO host dispatches (the
  shrink-exit lesson: ~150 ms per round-trip on an earlier chip setup);
- ``warm_ratio`` / ``measured_ratio``: wall-clock on/off (warm includes
  the K-branch fused compiles — the compile-budget guard).

Usage: python tools/cand_ab.py [rm] [--cpu] [--quick]
  --quick: the sorted structure only (4 children instead of 8).
Per-child timeout: ``CAND_AB_TIMEOUT_S`` (default 550 s — well under the
watcher stage's 2400 s budget / 4 quick children, so one wedged child
surfaces as its own ``error`` row instead of killing the whole stage).
On CPU the persistent compile cache is skipped so warm_ratio prices the
K-branch compiles honestly; rm clamps to 6 there (the acceptance mix).
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
import jax
if {cpu!r} == "cpu":
    jax.config.update("jax_platforms", "cpu")
else:
    from stateright_tpu.backend import configure_compile_cache
    configure_compile_cache()
from stateright_tpu.models.two_phase_commit import PackedTwoPhaseSys
from bench import EXPECTED_2PC as EXPECTED

rm = {rm}
fcap, tcap = 1 << 19, 1 << 22
if {cpu!r} == "cpu":
    rm = min(rm, 6)
    # Snug table for the rm=6 mix: 2^17 holds the 50,816 uniques inside
    # the 3/4-load rule with no growth recompiles, so the insert's
    # table-scale term doesn't drown the candidate-scale one the ladder
    # attacks.
    fcap, tcap = 1 << 17, 1 << 17
kw = dict(dedup={dedup!r}, ladder={ladder!r}, frontier_capacity=fcap,
          table_capacity=tcap)
m = PackedTwoPhaseSys(rm)
t0 = time.monotonic()
m.checker().spawn_xla(**kw).join()
warm = time.monotonic() - t0
c = m.checker().spawn_xla(**kw)
t0 = time.monotonic()
c.join()
dt = time.monotonic() - t0
want = EXPECTED.get(rm)
ok = want is None or (c.state_count(), c.unique_state_count()) == want
print(json.dumps({{
    # The REAL backend, not the requested label: a chip-verdict log
    # full of silent XLA:CPU numbers is worse than no log.
    "backend": jax.default_backend(),
    "cand_ladder": c._cand_ladder_k, "dedup": {dedup!r}, "ladder": {ladder!r},
    "rm": rm, "warm_s": round(warm, 2), "measured_s": round(dt, 3),
    "gen_per_s": round(c.state_count() / dt, 1),
    "gen": c.state_count(), "uniq": c.unique_state_count(),
    "count_ok": bool(ok),
    "dispatches": len(c.dispatch_log), "retries": c.cand_retries,
    "lane_words": [r["lane_words"] for r in c.level_log],
    "cand_caps": [r["cand_cap"] for r in c.level_log],
}}))
"""


def _run_variant(cpu: str, rm: int, dedup: str, ladder: str, k: str) -> dict:
    env = dict(os.environ)
    env["STPU_CAND_LADDER"] = k
    code = CHILD.format(repo=REPO, cpu=cpu, rm=rm, dedup=dedup, ladder=ladder)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=env,
            capture_output=True,
            text=True,
            timeout=int(os.environ.get("CAND_AB_TIMEOUT_S", "550")),
        )
    except subprocess.TimeoutExpired:
        return {"dedup": dedup, "ladder": ladder, "cand_ladder": int(k),
                "error": "timeout (wedged?)"}
    line = (proc.stdout.strip().splitlines() or [""])[-1]
    if proc.returncode != 0 or not line.startswith("{"):
        return {"dedup": dedup, "ladder": ladder, "cand_ladder": int(k),
                "error": proc.stderr.strip()[-400:]}
    return json.loads(line)


def main() -> None:
    cpu = "cpu" if "--cpu" in sys.argv else "tpu"
    quick = "--quick" in sys.argv
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    rm = int(args[0]) if args else 8
    grid = (
        # --quick: the sorted structure only (the watcher's chip stage —
        # delta pairs wait on the registry-#4 fault localization).
        [("sorted", "ramp"), ("sorted", "jump")]
        if quick
        else [(d, l) for d in ("sorted", "delta") for l in ("ramp", "jump")]
    )
    for dedup, ladder in grid:
        pair = {}
        for k in ("3", "1"):
            row = _run_variant(cpu, rm, dedup, ladder, k)
            print(json.dumps(row), flush=True)
            pair[k] = row
        on, off = pair["3"], pair["1"]
        if "error" in on or "error" in off:
            continue
        med_on = statistics.median(on["lane_words"])
        med_off = statistics.median(off["lane_words"])
        print(
            json.dumps(
                {
                    "pair": f"{dedup}/{ladder}",
                    "backends": sorted(
                        {on.get("backend"), off.get("backend")} - {None}
                    ),
                    "median_lane_ratio": round(med_off / max(med_on, 1), 2),
                    "median_lane_words": {"off": med_off, "on": med_on},
                    "total_lane_ratio": round(
                        sum(off["lane_words"])
                        / max(sum(on["lane_words"]), 1),
                        2,
                    ),
                    "dispatches_equal": on["dispatches"] == off["dispatches"],
                    "retries_on": on["retries"],
                    "counts_ok": on["count_ok"] and off["count_ok"]
                    and (on["gen"], on["uniq"]) == (off["gen"], off["uniq"]),
                    "warm_ratio": round(
                        on["warm_s"] / max(off["warm_s"], 1e-9), 2
                    ),
                    "measured_ratio": round(
                        on["measured_s"] / max(off["measured_s"], 1e-9), 2
                    ),
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
