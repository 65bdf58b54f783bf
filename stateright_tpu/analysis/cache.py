"""Content-hash-keyed per-surface result cache for the lint sweep.

The sweep's cost is tracing (and, for STPU008, dual-platform lowering) —
pure functions of the package source. One ``tree_hash`` over every
``stateright_tpu/**/*.py`` keys the whole cache: any source edit
invalidates everything (conservative but correct — a surface's traced
program can depend on any module), while repeat runs on an unchanged
tree (the common smoke.sh / admission case) replay findings from disk in
milliseconds. The waiver file is deliberately NOT in the hash: waivers
are applied after the sweep, to raw findings, so cached findings stay
valid across waiver edits.

Entries live under ``runs/lint_cache/<tree12>/<slug>.json`` (``runs/``
is gitignored); growth is bounded to the NEWEST ``KEEP_TREES`` tree
dirs (by mtime; ``STPU_LINT_CACHE_KEEP`` overrides) — pruned at lint
startup and on write, so per-commit content-hash dirs never accumulate
while a couple of recent trees (branch switches, A/B edits) stay warm.
``--no-cache`` forces a fresh sweep; surfaces that ERRORED or SKIPPED
are never cached (an environment verdict is not a tree verdict).
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
from typing import List, Optional

from .rules import Finding

_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO = os.path.dirname(_PKG)
DEFAULT_CACHE_DIR = os.path.join(_REPO, "runs", "lint_cache")

#: Newest tree dirs retained (per-commit content hashes would otherwise
#: accumulate forever on a long-lived box); env STPU_LINT_CACHE_KEEP.
KEEP_TREES = 4

_tree_hash_memo: Optional[str] = None


def tree_hash(root: str = _PKG) -> str:
    """sha256 over every package source file (path + content), memoized
    per process — the key under which cached surface results are valid."""
    global _tree_hash_memo
    if _tree_hash_memo is not None and root == _PKG:
        return _tree_hash_memo
    h = hashlib.sha256()
    # The jaxpr/lowering verdicts are functions of the installed jax
    # too, not just this tree: a jax upgrade must invalidate cached
    # STPU005 pre-flights and STPU008 inventories. (Importing jax
    # initializes no backend.)
    try:
        import jax

        h.update(jax.__version__.encode())
    except Exception:  # pragma: no cover - jax-less caller
        pass
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            p = os.path.join(dirpath, fn)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as fh:
                h.update(fh.read())
    digest = h.hexdigest()
    if root == _PKG:
        _tree_hash_memo = digest
    return digest


def _slug(surface: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "_", surface)


class SurfaceCache:
    """get/put of raw (pre-waiver) surface findings under one tree hash,
    bounded to the newest :data:`KEEP_TREES` tree dirs."""

    def __init__(self, cache_dir: Optional[str] = None,
                 keep_trees: Optional[int] = None):
        self.root = cache_dir or DEFAULT_CACHE_DIR
        self.tree = tree_hash()[:12]
        self.dir = os.path.join(self.root, self.tree)
        if keep_trees is None:
            try:
                keep_trees = int(
                    os.environ.get("STPU_LINT_CACHE_KEEP", KEEP_TREES)
                )
            except ValueError:
                keep_trees = KEEP_TREES
        self.keep_trees = max(1, keep_trees)
        # Prune at startup too, not just on write: a lint run on an
        # unchanged tree (all hits, no puts) must still bound the cache.
        self._prune()

    def _prune(self) -> None:
        """Delete all but the newest ``keep_trees`` tree dirs (by mtime;
        the current tree always counts as newest — a warm hit must never
        prune the entries it is about to read)."""
        try:
            others = sorted(
                (
                    d for d in os.listdir(self.root)
                    if d != self.tree
                    and os.path.isdir(os.path.join(self.root, d))
                ),
                key=lambda d: os.path.getmtime(os.path.join(self.root, d)),
                reverse=True,
            )
        except OSError:  # pragma: no cover - cache is best-effort
            return
        for d in others[self.keep_trees - 1:]:
            shutil.rmtree(os.path.join(self.root, d), ignore_errors=True)

    def get(self, surface: str) -> Optional[List[Finding]]:
        path = os.path.join(self.dir, _slug(surface) + ".json")
        try:
            with open(path) as fh:
                rows = json.load(fh)["findings"]
        except (OSError, json.JSONDecodeError, KeyError):
            return None
        try:
            return [
                Finding(**{k: r[k] for k in (
                    "rule", "surface", "file", "line", "message", "excerpt"
                )})
                for r in rows
            ]
        except (KeyError, TypeError):
            return None

    def put(self, surface: str, findings: List[Finding]) -> None:
        try:
            os.makedirs(self.dir, exist_ok=True)
        except OSError:  # pragma: no cover - cache is best-effort
            return
        payload = {
            "findings": [
                {k: v for k, v in f.to_json().items()
                 if k not in ("waived", "waiver_reason")}
                for f in findings
            ]
        }
        tmp = os.path.join(self.dir, _slug(surface) + ".json.tmp")
        try:
            with open(tmp, "w") as fh:
                json.dump(payload, fh)
            os.replace(tmp, os.path.join(self.dir, _slug(surface) + ".json"))
        except OSError:  # pragma: no cover - cache is best-effort
            pass
