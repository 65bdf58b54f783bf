"""The comparison that decides ``correct``.

Every number compared is a count of disagreements with the plain reference
(``benchmark/reference/``), and every limit is 0: an exact checker either
agrees with the reference or is wrong.

- ``generated_gap`` / ``unique_gap``: the largest distance, over every
  check of the window, between its generated (unique) count and the
  reference's;
- ``verdict_mismatches``: checks whose set of properties with a discovery
  differs from the reference's;
- ``bad_witnesses``: discovery paths of the window's last check that do not
  replay on the reference (start, every step, the property at the end, and
  the length of the reference's shortest witness);
- ``duplicate_keys`` / ``table_entries_gap``: the last check's visited set,
  pulled to the host: fingerprints held twice, and the distance between the
  occupied entries and the reference's unique count.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

LIMITS = {
    "generated_gap": 0,
    "unique_gap": 0,
    "verdict_mismatches": 0,
    "bad_witnesses": 0,
    "duplicate_keys": 0,
    "table_entries_gap": 0,
}


def audit_table(checker) -> Dict[str, int]:
    """Occupied entries and distinct 64-bit fingerprints of a checker's
    visited set (key planes ``key_hi``/``key_lo``; key 0 is an empty slot)."""
    table = checker._table
    keys = (np.asarray(table.key_hi, dtype=np.uint64) << np.uint64(32)) | np.asarray(
        table.key_lo, dtype=np.uint64
    )
    live = keys[keys != 0]
    return {"entries": int(live.size), "distinct": int(np.unique(live).size)}


def discovery_paths(checker):
    """Each discovery's path as the system's states, or the error the
    system raised rebuilding them."""
    try:
        return {name: path.into_states() for name, path in checker.discoveries().items()}
    except RuntimeError as e:
        return f"path reconstruction failed: {e}"


def witnesses(paths, reference, params: dict, ref: dict) -> List[str]:
    """What is wrong with each discovery path (empty when every path
    replays on the reference)."""
    if isinstance(paths, str):
        return [paths]
    wrong = []
    for name, states in paths.items():
        depth = ref["discoveries"].get(name)
        if depth is None:
            wrong.append(f"{name}: the reference finds no discovery")
            continue
        why = reference.replay(states, name, params, depth)
        if why is not None:
            wrong.append(f"{name}: {why}")
    return wrong


def compare(checks, ref: dict, audit: Dict[str, int], bad_paths: List[str]) -> Dict[str, int]:
    """The numbers compared, each against its limit in ``LIMITS``."""
    ref_found = tuple(sorted(ref["discoveries"]))
    return {
        "generated_gap": max(abs(c.generated - ref["generated"]) for c in checks),
        "unique_gap": max(abs(c.unique - ref["unique"]) for c in checks),
        "verdict_mismatches": sum(c.found != ref_found for c in checks),
        "bad_witnesses": len(bad_paths),
        "duplicate_keys": audit["entries"] - audit["distinct"],
        "table_entries_gap": abs(audit["entries"] - ref["unique"]),
    }


def failed_checks(checks, ref: dict, numbers: Dict[str, int]) -> int:
    """Checks that disagree with the reference; the last one also fails on
    its witnesses or its visited set."""
    ref_found = tuple(sorted(ref["discoveries"]))
    bad = [
        c.generated != ref["generated"] or c.unique != ref["unique"] or c.found != ref_found
        for c in checks
    ]
    last = ("bad_witnesses", "duplicate_keys", "table_entries_gap")
    if any(numbers[k] > LIMITS[k] for k in last):
        bad[-1] = True
    return sum(bad)


def is_correct(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
