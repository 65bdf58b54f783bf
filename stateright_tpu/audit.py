"""Host-side integrity audit of a device checker's visited set.

Motivation (a finding on an earlier chip setup): the one on-chip paxos 2c/3s run
recorded 17,198 unique states where the pinned oracle says 16,668 — on a
revision whose CPU run reproduces the oracle exactly. Exact state counts
are this framework's correctness contract (the reference asserts them in
its example tests, e.g. /root/reference/examples/paxos.rs:321), so a count
drift on one platform must be attributable. The audit answers the sharpest
question on the table: **does the visited set hold the same fingerprint
twice?** A duplicate entry means the device insert admitted a key that was
already present (each admission increments ``unique_count`` and re-expands
the state, inflating both counters) — the signature of a backend miscompile
of the insert program rather than a model nondeterminism.

The audit deliberately runs on the HOST in NumPy over a pulled copy of the
table planes: an audit computed by the suspect device program would prove
nothing.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def audit_table(checker) -> Dict[str, Any]:
    """Pulls the checker's visited-set key planes and cross-checks them
    against the committed ``unique_state_count()``.

    Works on any engine whose table exposes ``key_hi``/``key_lo`` planes
    (hash, sorted and delta structures on both the single-chip and sharded
    engines; the sharded engine's non-addressable shards are read through
    its ``_host_read``).

    Returns::

        {
          "entries":        occupied slots across all planes,
          "distinct_keys":  distinct 64-bit fingerprints among them,
          "duplicate_keys": entries - distinct_keys  (MUST be 0),
          "unique_count":   the checker's committed unique_state_count(),
          "ok":             duplicate_keys == 0 and entries == unique_count,
        }

    ``entries != unique_count`` with zero duplicates would instead indicate
    lost entries (growth/rehash dropping keys) or a counter bug — a
    different failure signature, also caught here.
    """
    read = getattr(checker, "_host_read", np.asarray)
    table = checker._table
    kh = np.asarray(read(table.key_hi), dtype=np.uint64)
    kl = np.asarray(read(table.key_lo), dtype=np.uint64)
    keys = (kh << np.uint64(32)) | kl
    occupied = keys != 0  # EMPTY is key == (0, 0); fphash never emits it
    live = keys[occupied]
    entries = int(live.size)
    distinct = int(np.unique(live).size)
    unique = int(checker.unique_state_count())
    return {
        "entries": entries,
        "distinct_keys": distinct,
        "duplicate_keys": entries - distinct,
        "unique_count": unique,
        "ok": (entries == distinct) and (entries == unique),
    }
