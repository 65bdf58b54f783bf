#!/usr/bin/env python
"""Pre-seed the persistent XLA compile cache with the shipped model shapes.

A fresh CheckerService's first request pays the full XLA trace+compile for
its model's bucket schedule — tens of seconds per bucket from the TPU
compiler. This tool banks those
compiles ahead of time: it runs each shipped packed-model configuration
(``stateright_tpu/service/registry.py`` :data:`SHIPPED` — the exact specs
and capacities service jobs default to, so the (shape, bucket) schedules
match and every program lands in the compile cache, see
``stateright_tpu/backend.py``) once to completion through the REAL service
worker, each under its own supervised process group — a hang mid-warm
burns one spec's budget, never the tool.

The warm set is DERIVED from the STPU007 compile-plan census
(``stateright_tpu/analysis/census.py`` — the same shared ladder planner
the engine runs), not hand-maintained: the census enumerates each
shipped spec's (bucket, cand-rung) schedule at the registry capacities,
so a registry or planner change re-aims this tool automatically
(census/SHIPPED drift is a test failure, ``tests/test_analysis.py``).

Usage::

    python tools/warm_cache.py                 # the censused shipped specs
    python tools/warm_cache.py --specs 2pc:4 paxos:2,3
    python tools/warm_cache.py --platform cpu  # warm the CPU cache (CI)
    python tools/warm_cache.py --mux 4         # + the K=4 batched programs
    python tools/warm_cache.py --sym           # + the symmetry-variant programs

``--mux K`` additionally banks the multiplexed-superstep programs a
service running with ``STPU_MUX=K`` compiles (the census's ``mux`` shape
classes — ``plan_for(..., mux_k=K)``): after each eligible spec's solo
warm, one K-lane ``worker.py --mux`` group of that spec runs to
completion, landing the batched (k, bucket, cand_cap) programs in the
same cache. Specs outside ``registry.MUX_FAMILIES`` warm solo only.

``--sym`` additionally banks the symmetry-variant programs
(docs/symmetry.md; the census's ``sym`` shape classes —
``plan_for(..., symmetry=True)``): after the solo warms, each
``registry.SYM_FAMILIES`` spec re-runs its worker with ``STPU_SYMMETRY=1``
so the canonicalization-fused bucket programs land in the same cache.

Emits one JSON line per spec and a final summary. Re-running is cheap:
already-cached programs load in seconds, so this doubles as a cache
health check. See docs/service.md ("First-request latency").
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from stateright_tpu.backend import compile_cache_dir  # noqa: E402
from stateright_tpu import supervise as sup  # noqa: E402 (path bootstrap)
from stateright_tpu.service.registry import parse  # noqa: E402

WORKER = os.path.join(REPO, "stateright_tpu", "service", "worker.py")


def default_specs():
    """The warm set, derived from the compile-plan census. The banked
    artifact (``runs/compile_plan.json``, written by every full
    stpu-lint run) is preferred — no jax import in this parent at all;
    only when it is absent does the parent build the census in-process,
    CPU-pinned first (this parent must not hold the chip its workers
    need; the workers pick their own platform via ``--platform``). The analyzer's pin appends the
    8-virtual-device XLA flag for its mesh surface; that is restored
    afterwards so warm WORKERS never inherit it."""
    try:
        with open(os.path.join(REPO, "runs", "compile_plan.json")) as fh:
            census = json.load(fh)
        # Freshness via the census's banked tree hash (tree_hash is pure
        # file hashing — no jax): a census banked for some OTHER tree
        # (e.g. before a spec joined SHIPPED) must not shape the warm
        # set — that is exactly the drift the derivation eliminates.
        from stateright_tpu.analysis.cache import tree_hash

        specs = list(census["specs"])
        if specs and census.get("tree") == tree_hash()[:12]:
            return specs
    except (OSError, json.JSONDecodeError, KeyError):
        pass
    flags = os.environ.get("XLA_FLAGS")
    from stateright_tpu.analysis.census import warm_specs
    from stateright_tpu.analysis.surfaces import pin_cpu

    pin_cpu()
    try:
        return warm_specs()
    finally:
        if flags is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = flags


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument(
        "--specs", nargs="*", default=None,
        help="default: derived from the STPU007 compile-plan census",
    )
    p.add_argument("--platform", default="default",
                   help='"default" (accelerator) or "cpu"')
    p.add_argument("--budget-s", type=float, default=900.0,
                   help="per-spec wall-clock budget")
    p.add_argument("--stall-s", type=float, default=300.0,
                   help="mid-dispatch heartbeat leash (3x while compiling)")
    p.add_argument("--out-dir", default=os.path.join(REPO, "runs", "warm"))
    p.add_argument(
        "--mux", type=int, default=0, metavar="K",
        help="also pre-warm the K-lane multiplexed programs "
             "(one worker.py --mux group per MUX_FAMILIES spec)",
    )
    p.add_argument(
        "--sym", action="store_true",
        help="also pre-warm the symmetry-variant programs "
             "(STPU_SYMMETRY=1 worker run per SYM_FAMILIES spec)",
    )
    args = p.parse_args()

    if args.specs is None:
        args.specs = default_specs()
    for spec in args.specs:
        parse(spec)  # fail fast on typos, before any jax import anywhere

    os.makedirs(args.out_dir, exist_ok=True)
    env = dict(os.environ)
    env.pop("STPU_TRACE", None)
    env.pop("STPU_CHECKPOINT_TO", None)

    summary = []
    for spec in args.specs:
        tag = spec.replace(":", "_").replace(",", "-")
        out = os.path.join(args.out_dir, f"warm_{tag}.json")
        t0 = time.monotonic()
        res = sup.run_worker(
            [
                sys.executable, WORKER,
                "--spec", spec,
                "--engine", "xla",
                "--platform", args.platform,
                "--out", out,
                "--max-seconds", str(args.budget_s),
            ],
            heartbeat=os.path.join(args.out_dir, f"warm_{tag}_hb.json"),
            timeout_s=args.budget_s * 1.5 + 60.0,
            stall_s=args.stall_s,
            startup_grace_s=600.0,
            poll_s=1.0,
            env=env,
            stdout_path=os.path.join(args.out_dir, f"warm_{tag}.out"),
        )
        row = {
            "spec": spec,
            "ok": res.ok,
            "seconds": round(time.monotonic() - t0, 2),
            "killed": res.killed,
            "rc": res.rc,
        }
        if res.ok and os.path.exists(out):
            with open(out) as fh:
                r = json.load(fh)
            row.update(
                generated=r["generated"], unique=r["unique"],
                platform=r["platform"],
            )
        summary.append(row)
        print(json.dumps(row), flush=True)

    if args.sym:
        from stateright_tpu.service.registry import SYM_FAMILIES

        for spec in args.specs:
            if parse(spec)[0] not in SYM_FAMILIES:
                continue
            tag = spec.replace(":", "_").replace(",", "-")
            out = os.path.join(args.out_dir, f"warm_{tag}_sym.json")
            t0 = time.monotonic()
            res = sup.run_worker(
                [
                    sys.executable, WORKER,
                    "--spec", spec,
                    "--engine", "xla",
                    "--platform", args.platform,
                    "--out", out,
                    "--max-seconds", str(args.budget_s),
                ],
                heartbeat=os.path.join(args.out_dir, f"warm_{tag}_sym_hb.json"),
                timeout_s=args.budget_s * 1.5 + 60.0,
                stall_s=args.stall_s,
                startup_grace_s=600.0,
                poll_s=1.0,
                env=dict(env, STPU_SYMMETRY="1"),
                stdout_path=os.path.join(args.out_dir, f"warm_{tag}_sym.out"),
            )
            row = {
                "spec": spec,
                "sym": True,
                "ok": res.ok,
                "seconds": round(time.monotonic() - t0, 2),
                "killed": res.killed,
                "rc": res.rc,
            }
            if res.ok and os.path.exists(out):
                with open(out) as fh:
                    r = json.load(fh)
                row.update(
                    generated=r["generated"], unique=r["unique"],
                    platform=r["platform"],
                )
            summary.append(row)
            print(json.dumps(row), flush=True)

    if args.mux > 1:
        from stateright_tpu.service.registry import MUX_FAMILIES

        for spec in args.specs:
            if parse(spec)[0] not in MUX_FAMILIES:
                continue
            tag = spec.replace(":", "_").replace(",", "-")
            lanes = []
            for i in range(args.mux):
                lanes.append({
                    "job": f"warm-{tag}-l{i}",
                    "out": os.path.join(
                        args.out_dir, f"warm_{tag}_mux_l{i}.json"
                    ),
                })
            manifest = os.path.join(args.out_dir, f"warm_{tag}_mux.json")
            with open(manifest, "w") as fh:
                json.dump(
                    {"group": f"warm-mux-{tag}", "spec": spec,
                     "lanes": lanes}, fh,
                )
            t0 = time.monotonic()
            res = sup.run_worker(
                [
                    sys.executable, WORKER,
                    "--mux", manifest,
                    "--spec", spec,
                    "--engine", "xla",
                    "--platform", args.platform,
                    "--out", os.path.join(
                        args.out_dir, f"warm_{tag}_mux_group.json"
                    ),
                    "--max-seconds", str(args.budget_s),
                ],
                heartbeat=os.path.join(
                    args.out_dir, f"warm_{tag}_mux_hb.json"
                ),
                timeout_s=args.budget_s * 1.5 + 60.0,
                stall_s=args.stall_s,
                startup_grace_s=600.0,
                poll_s=1.0,
                env=env,
                stdout_path=os.path.join(args.out_dir, f"warm_{tag}_mux.out"),
            )
            row = {
                "spec": spec,
                "mux": args.mux,
                "ok": res.ok,
                "seconds": round(time.monotonic() - t0, 2),
                "killed": res.killed,
                "rc": res.rc,
            }
            summary.append(row)
            print(json.dumps(row), flush=True)

    ok = sum(1 for r in summary if r["ok"])
    print(
        json.dumps(
            {
                "warmed": ok,
                "failed": len(summary) - ok,
                "cache_dir": compile_cache_dir(),
            }
        )
    )
    return 0 if ok == len(summary) else 1


if __name__ == "__main__":
    sys.exit(main())
