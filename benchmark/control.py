"""The control: the reference in the system's place, with one guarantee
broken, put through the comparison that decides ``correct``.

    python3 benchmark/control.py --workload <name> --seed <n> [--seed <n> ...]

Each configuration's ``reference.control`` says what the control breaks
(for the cells here, the width of the visited set's key). The control
stands for one check of the window: its counts and verdicts meet the
reference's, and its visited set is its own. It must come out not
correct. Runs on the host; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import compare  # noqa: E402
import driver  # noqa: E402
import harness  # noqa: E402


def control_numbers(root: str, workload: str, seed: int) -> dict:
    """The compared numbers of the control for ``workload``'s configuration."""
    cell = harness.load_cell(root, workload)
    reference, params = harness.reference_of(cell.config)
    ref = reference.explore(params)
    ctl = reference.explore(params, control_seed=seed)
    check = driver.Check(0.0, 0.0, 0.0, ctl["generated"], ctl["unique"],
                         tuple(sorted(ctl["discoveries"])), 0, 0)
    audit = {"entries": ctl["unique"], "distinct": ctl["unique"]}
    return compare.compare([check], ref, audit, [])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, action="append", required=True)
    args = p.parse_args(argv)
    for seed in args.seed:
        numbers = control_numbers(ROOT, args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": compare.is_correct(numbers),
                          "compared": {k: {"value": v, "limit": compare.LIMITS[k]}
                                       for k, v in numbers.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
