#!/usr/bin/env python
"""Post-mortem trace bundle for a service/fleet run dir.

One command snapshots everything a post-mortem needs into a single
self-contained directory:

- ``trace.merged.json`` — the whole run's merged distributed-trace
  timeline (``stateright_tpu.obs.collect``: every ``trace.jsonl`` under
  the run dir on one Chrome/Perfetto time axis, per-process tracks, flow
  arrows per trace id);
- ``journals/`` — every job journal (``journal.jsonl`` + rotations) and
  the fleet routing journal (``fleet.jsonl``), preserving relative
  paths, so replay forensics work offline;
- ``heartbeats/`` — the last heartbeat file of every worker
  (``hb.json``/``mux-hb.json``) — what the watchdog saw at death;
- ``metrics/`` — per-job metrics time-series rotations
  (``metrics.jsonl*``);
- ``lint.json`` — the flight-check verdict (``--lint`` path, default
  ``runs/lint.json``, skipped silently when absent);
- ``manifest.json`` — the inventory: source run dir, file lists, merged
  trace ids, and event counts.

Pure host-side file copying — no jax, no device, safe on a box whose
device dispatch just hung. Usage::

    python tools/trace_bundle.py runs/fleet            # -> runs/fleet-bundle/
    python tools/trace_bundle.py runs/svc --out /tmp/b --lint runs/lint.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stateright_tpu.obs import collect as collect_mod  # noqa: E402

#: (bundle subdir, filename predicate) — what the walker snapshots.
_JOURNALS = ("journal.jsonl", "fleet.jsonl")
_HEARTBEATS = ("hb.json", "mux-hb.json", "heartbeat.json")


def _is_journal(name: str) -> bool:
    # journal.jsonl, journal.jsonl.1.. (rotations), fleet.jsonl(.N)
    base = name.split(".jsonl")[0] + ".jsonl"
    return base in _JOURNALS and name.startswith(base.split(".jsonl")[0])


def _is_metrics(name: str) -> bool:
    return name == "metrics.jsonl" or name.startswith("metrics.jsonl.")


def bundle(run_dir: str, out_dir: str,
           lint_path: str = os.path.join("runs", "lint.json")) -> dict:
    """Builds the bundle; returns the manifest dict (also written to
    ``<out_dir>/manifest.json``)."""
    if not os.path.isdir(run_dir):
        raise SystemExit(f"not a run dir: {run_dir}")
    os.makedirs(out_dir, exist_ok=True)

    copied = {"journals": [], "heartbeats": [], "metrics": []}
    for root, _dirs, files in os.walk(run_dir):
        # Never walk into a previous bundle nested in the run dir.
        if os.path.abspath(root).startswith(os.path.abspath(out_dir)):
            continue
        for name in files:
            if _is_journal(name):
                kind = "journals"
            elif name in _HEARTBEATS:
                kind = "heartbeats"
            elif _is_metrics(name):
                kind = "metrics"
            else:
                continue
            src = os.path.join(root, name)
            rel = os.path.relpath(src, run_dir)
            dst = os.path.join(out_dir, kind, rel)
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            try:
                shutil.copy2(src, dst)
            except OSError:
                continue  # a file swept mid-walk is not fatal
            copied[kind].append(rel)

    trace_obj = collect_mod.collect(run_dir)
    trace_out = os.path.join(out_dir, "trace.merged.json")
    with open(trace_out, "w") as fh:
        json.dump(trace_obj, fh)

    lint_copied = False
    if lint_path and os.path.exists(lint_path):
        try:
            shutil.copy2(lint_path, os.path.join(out_dir, "lint.json"))
            lint_copied = True
        except OSError:
            pass

    manifest = {
        "run_dir": os.path.abspath(run_dir),
        "trace_files": trace_obj["otherData"]["trace_files"],
        "trace_ids": trace_obj["otherData"]["traces"],
        "trace_events": len(trace_obj["traceEvents"]),
        "journals": sorted(copied["journals"]),
        "heartbeats": sorted(copied["heartbeats"]),
        "metrics": sorted(copied["metrics"]),
        "lint": lint_copied,
    }
    with open(os.path.join(out_dir, "manifest.json"), "w") as fh:
        json.dump(manifest, fh, indent=2)
    return manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir", help="service/fleet run dir to snapshot")
    ap.add_argument("--out", default=None,
                    help="bundle dir (default: <run_dir>-bundle)")
    ap.add_argument("--lint", default=os.path.join("runs", "lint.json"),
                    help="lint verdict JSON to include (skipped if absent)")
    args = ap.parse_args(argv)
    out = args.out or (args.run_dir.rstrip("/\\") + "-bundle")
    manifest = bundle(args.run_dir, out, lint_path=args.lint)
    print(json.dumps({
        "bundle": os.path.abspath(out),
        "trace_events": manifest["trace_events"],
        "trace_ids": len(manifest["trace_ids"]),
        "journals": len(manifest["journals"]),
        "heartbeats": len(manifest["heartbeats"]),
        "metrics": len(manifest["metrics"]),
        "lint": manifest["lint"],
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
