"""Checkpoint/resume for the XLA checkers.

The reference has no checkpointing — a run is memory-resident and
single-shot (SURVEY.md §5). With the visited set resident in device HBM,
host-side checkpointing becomes an explicit feature of this framework: long
checks (or preemptible TPU time) can stop after any super-step and resume
later, on a different chip count.

Format (``np.savez_compressed``): the *logical* search state, independent of
any engine's memory layout —

- the visited set as compacted ``(fingerprint, parent)`` pairs (four uint32
  lanes),
- the frontier as packed state rows + eventually-bit words,
- scalar progress counters and discovery pins,
- model identity metadata (class name + packed geometry), validated on
  restore.

Restoring *rebuilds* the hash table by insertion, so a checkpoint written by
the single-chip engine loads into the sharded engine (and vice versa), and
capacities may differ across save/restore.

Crash-safety (the recovery stack, docs/observability.md "Recovery"): a
checkpoint is the thing a run falls back to after a hung dispatch is killed, so
the file itself must survive the failure modes around it —

- **atomic**: writes land in a same-directory temp file and go live via
  ``os.replace``; a SIGKILL mid-save can never tear the live file;
- **self-verifying**: the metadata embeds a SHA-256 over every payload
  array, recomputed on load — truncation, foreign writers, or bit rot
  raise the typed :class:`CheckpointCorrupt`, never a bare zipfile
  traceback;
- **rotating**: ``save_checkpoint(..., keep=K)`` shifts the previous file
  to ``<path>.1`` (and so on, retaining the last K), so a reader that finds
  the newest rotation corrupt falls back to the one before it —
  :func:`latest_valid_checkpoint` is that fallback, and the supervisor
  (``stateright_tpu/supervise.py``) resumes from it automatically.

In-loop auto-checkpointing (``spawn_xla(checkpoint_to=...)``) rides on
:class:`AutoCheckpointer`: the engines call :meth:`AutoCheckpointer.maybe`
between supersteps — the quiescent points where the device state is a pure
function of host-visible arrays — and it decides cadence (every N committed
levels or every N seconds).
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import chaos

# v2: fingerprints moved to the Zobrist-form hash (ops/fphash.py) and the
# metadata gained the model-config digest; v1 checkpoints persist fingerprints
# under the old hash and must be rejected, not silently resumed.
# v3: the metadata embeds a payload SHA-256 (``payload_sha256``) and loads
# verify it — a v2 file has no digest to trust, so it is rejected as an
# unsupported format, like v1.
FORMAT_VERSION = 3

#: Payload members of the archive, in digest order. The order is part of the
#: format: the digest is a running hash over these arrays' bytes.
PAYLOAD_KEYS = (
    "key_hi",
    "key_lo",
    "val_hi",
    "val_lo",
    "frontier",
    "frontier_ebits",
)


class CheckpointCorrupt(Exception):
    """A checkpoint file that cannot be trusted: torn/truncated mid-write,
    unreadable as an archive, missing payload members, or failing its
    embedded payload digest. Callers (the supervisor, bench resume) catch
    this and fall back to the previous rotation — see
    :func:`latest_valid_checkpoint`."""


def _normalize(path: str) -> str:
    """np.savez appends '.npz' when absent; normalize both ends so any path
    round-trips. An existing exact FILE (a rotation like ``ck.npz.1``) wins
    over suffix normalization; a directory never does — an extensionless
    save target colliding with a directory name must still resolve to the
    deterministic ``<path>.npz``, not an IsADirectoryError at replace."""
    if path.endswith(".npz") or os.path.isfile(path):
        return path
    return path + ".npz"


def model_digest(model) -> str:
    """A digest of the model's *configuration*, not just its geometry: the
    packed initial states pin every config knob that shapes the transition
    system (field layouts, history presence, actor counts), so a checkpoint
    cannot silently resume into a differently-configured instance of the
    same model class."""
    rows = np.ascontiguousarray(np.asarray(model.packed_init(), dtype=np.uint32))
    h = hashlib.sha256()
    h.update(repr((rows.shape, model.state_words, model.max_actions)).encode())
    h.update(rows.tobytes())
    return h.hexdigest()[:16]


def _payload_digest(arrays: Dict[str, np.ndarray]) -> str:
    """SHA-256 over every payload array's identity (name, shape, dtype) and
    bytes, in :data:`PAYLOAD_KEYS` order — the self-verification the loader
    recomputes."""
    h = hashlib.sha256()
    for key in PAYLOAD_KEYS:
        a = np.ascontiguousarray(arrays[key])
        h.update(key.encode())
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def save_checkpoint(checker, path: str, keep: int = 1) -> None:
    """Writes the checker's logical search state. Valid after any number of
    ``_run_block`` calls (between super-steps the device state is quiescent).

    The write is atomic (temp file + ``os.replace``: a kill mid-save leaves
    the previous file intact, never a torn one) and rotating: with
    ``keep=K > 1`` the previous live file shifts to ``<path>.1`` (``.1`` to
    ``.2``, ...), retaining the last K checkpoints so a corrupt newest
    rotation still leaves a valid fallback."""
    if keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    # The sharded engine's planes can span non-addressable devices under
    # jax.distributed; its _host_read allgathers them. Single-chip arrays
    # are process-local, so plain np.asarray suffices there.
    read = getattr(checker, "_host_read", np.asarray)
    table = checker._table
    kh = read(table.key_hi)
    kl = read(table.key_lo)
    vh = read(table.val_hi)
    vl = read(table.val_lo)
    occ = (kh != 0) | (kl != 0)

    frontier_rows, frontier_ebits = _live_frontier(checker)

    arrays = {
        "key_hi": kh[occ],
        "key_lo": kl[occ],
        "val_hi": vh[occ],
        "val_lo": vl[occ],
        "frontier": np.asarray(frontier_rows, dtype=np.uint32),
        "frontier_ebits": np.asarray(frontier_ebits, dtype=np.uint32),
    }
    meta = {
        "format_version": FORMAT_VERSION,
        "model": type(checker._model).__name__,
        "init_digest": model_digest(checker._model),
        "state_words": checker._W,
        "max_actions": checker._A,
        "property_names": checker._prop_names,
        # Symmetry identity (stateright_tpu/sym, docs/symmetry.md): the
        # resolved tag — None (off), "spec:<hash12>" (the spec-compiled
        # kernel), or "model:packed_representative". A resume into a
        # DIFFERENT canonicalization would dedup new states against a
        # differently-keyed table, silently corrupting counts, so
        # validate_symmetry fails such resumes typed.
        "symmetry": getattr(checker, "_sym_tag", None),
        "depth": checker._depth,
        "max_depth": checker._max_depth,
        "state_count": checker._state_count,
        "unique_count": checker._unique_count,
        "found_names": {k: int(v) for k, v in checker._found_names.items()},
        "exhausted": checker._exhausted,
        "target_reached": checker._target_reached,
        # is_done() is WIDER than the two flags above (frontier-empty and
        # all-properties-found complete a run without setting either), so
        # completion checks must read this, not re-derive it from flags.
        "done": bool(checker.is_done()),
        "payload_sha256": _payload_digest(arrays),
        "written_unix_ts": time.time(),
    }
    dst = _normalize(path)
    # Same-directory temp (os.replace must not cross filesystems), with a
    # .npz suffix so np.savez does not append its own.
    tmp = f"{dst}.tmp-{os.getpid()}.npz"
    # Sweep temps orphaned by a predecessor killed mid-save — SIGKILL from
    # the watchdog is this system's DESIGNED failure mode, and the
    # finally-unlink below never runs under it. At soak scale each orphan
    # is a multi-GB file; the supervisor never overlaps two live writers
    # on one base path, so any other-pid temp is a dead worker's litter.
    for stale in glob.glob(f"{glob.escape(dst)}.tmp-*.npz"):
        if stale != tmp:
            try:
                os.unlink(stale)
            except OSError:
                pass
    try:
        np.savez_compressed(
            tmp,
            meta=np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8),
            **arrays,
        )
        if keep > 1 and os.path.exists(dst):
            for i in range(keep - 1, 1, -1):
                older = f"{dst}.{i - 1}"
                if os.path.exists(older):
                    os.replace(older, f"{dst}.{i}")
            os.replace(dst, f"{dst}.1")
        os.replace(tmp, dst)
        inj = chaos.fire("checkpoint.torn", size=os.path.getsize(dst))
        if inj is not None:
            # Deterministic fault injection (stateright_tpu/chaos.py):
            # tear the just-written live rotation at byte ``at`` — the
            # corrupt-newest shape latest_valid_checkpoint falls back
            # from. No-op unless an STPU_CHAOS plan names it.
            chaos.tear_file(dst, inj.get("at", 1))
    finally:
        # Only a failed save leaves the temp behind (success replaced it).
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _live_frontier(checker):
    """The valid frontier rows + ebits, engine-layout-agnostic."""
    from .parallel.sharded import ShardedXlaChecker

    if isinstance(checker, ShardedXlaChecker):
        D, Fl, W = checker._D, checker._Fl, checker._W
        rows = checker._host_read(checker._frontier).reshape(D, Fl, W)
        ebits = checker._host_read(checker._frontier_ebits).reshape(D, Fl)
        counts = checker._host_read(checker._counts)
        live_rows = [rows[d, : counts[d]] for d in range(D)]
        live_ebits = [ebits[d, : counts[d]] for d in range(D)]
        return (
            np.concatenate(live_rows) if live_rows else rows[:0, 0],
            np.concatenate(live_ebits) if live_ebits else ebits[:0, 0],
        )
    n = checker._frontier_count
    return (
        checker._frontier_rows_host(),
        np.asarray(checker._frontier_ebits)[:n],
    )


def _read_archive(path: str):
    """The raw (meta, arrays) of a checkpoint archive; every way a torn or
    foreign file can fail to parse is converted to the typed
    :class:`CheckpointCorrupt` (a missing file stays ``FileNotFoundError``
    — "no checkpoint yet" and "checkpoint destroyed" are different verdicts
    to a supervisor)."""
    try:
        with np.load(path) as z:
            meta = json.loads(bytes(z["meta"]).decode())
            arrays = {k: np.asarray(z[k]) for k in PAYLOAD_KEYS if k in z}
    except FileNotFoundError:
        raise
    except Exception as e:
        raise CheckpointCorrupt(
            f"{path}: unreadable checkpoint ({type(e).__name__}: {e})"
        ) from e
    return meta, arrays


def load_checkpoint(path: str) -> Dict[str, Any]:
    """Reads a checkpoint into plain host arrays + metadata. Raises
    :class:`CheckpointCorrupt` on a torn/truncated/digest-mismatched file
    (so callers can fall back to the previous rotation) and ``ValueError``
    on a readable file of an unsupported format version."""
    p = _normalize(path)
    meta, arrays = _read_archive(p)
    if meta.get("format_version") != FORMAT_VERSION:
        raise ValueError(
            f"unsupported checkpoint format {meta.get('format_version')}"
        )
    missing = [k for k in PAYLOAD_KEYS if k not in arrays]
    if missing:
        raise CheckpointCorrupt(f"{p}: missing payload members {missing}")
    digest = _payload_digest(arrays)
    if meta.get("payload_sha256") != digest:
        raise CheckpointCorrupt(
            f"{p}: payload digest mismatch "
            f"({meta.get('payload_sha256')} != {digest}) — torn or tampered"
        )
    return {"meta": meta, **arrays}


def rotations(path: str) -> List[str]:
    """Existing rotation files for ``path``, newest first: the live file,
    then ``.1``, ``.2``, ... (contiguous — the shift in
    :func:`save_checkpoint` never leaves gaps)."""
    p = _normalize(path)
    out = [p] if os.path.exists(p) else []
    i = 1
    while True:
        candidate = f"{p}.{i}"
        if not os.path.exists(candidate):
            break
        out.append(candidate)
        i += 1
    return out


def latest_valid_checkpoint(path: str, *, with_meta: bool = False):
    """The newest rotation of ``path`` that loads and verifies clean, or
    None. This is the supervisor's automatic fallback: a truncated newest
    file is skipped (typed, not crashed on) in favor of the previous
    rotation. ``with_meta=True`` returns ``(path, meta)`` instead —
    verification already paid the full decompress+digest pass, so callers
    that want the meta (bench's resume validation) must not load the
    winning file a second time; misses return ``(None, None)``."""
    for candidate in rotations(path):
        try:
            meta = load_checkpoint(candidate)["meta"]
        except (CheckpointCorrupt, ValueError):
            continue
        return (candidate, meta) if with_meta else candidate
    return (None, None) if with_meta else None


def validate_model(meta: Dict[str, Any], model, prop_names) -> None:
    """A checkpoint is only loadable into the model that wrote it."""
    problems = []
    if meta["model"] != type(model).__name__:
        problems.append(f"model {meta['model']!r} != {type(model).__name__!r}")
    if meta["state_words"] != model.state_words:
        problems.append(
            f"state_words {meta['state_words']} != {model.state_words}"
        )
    if meta["max_actions"] != model.max_actions:
        problems.append(f"max_actions {meta['max_actions']} != {model.max_actions}")
    digest = model_digest(model)
    if meta["init_digest"] != digest:
        problems.append(
            f"model config digest {meta['init_digest']} != {digest} "
            "(same class, different configuration)"
        )
    if meta["property_names"] != list(prop_names):
        problems.append(
            f"properties {meta['property_names']} != {list(prop_names)}"
        )
    if problems:
        raise ValueError(
            "checkpoint does not match this model: " + "; ".join(problems)
        )


def validate_symmetry(meta: Dict[str, Any], sym_tag) -> None:
    """A checkpoint is only loadable into a checker with the SAME
    canonicalization identity (``_sym_tag``): the visited table's keys
    are fingerprints of canonical forms, so resuming under a different
    symmetry config (off vs on, or a changed spec) would silently
    mis-dedup every state inserted after the resume. Checkpoints written
    before the symmetry tier lack the key and skip this check (they
    predate spec kernels, so their canonicalization matches whatever the
    model's packed_representative still computes)."""
    if "symmetry" not in meta:
        return
    if meta["symmetry"] != sym_tag:
        raise ValueError(
            f"checkpoint symmetry mismatch: written with "
            f"{meta['symmetry']!r}, resuming with {sym_tag!r} — a resume "
            f"must keep the same spawn_xla(symmetry=)/STPU_SYMMETRY "
            f"config (and spec) the checkpoint was written under"
        )


def _parse_every(every):
    """Cadence spec -> ``(levels, seconds)`` (exactly one is set). An int
    (or digit string) is committed BFS levels; a string with an ``s``
    suffix is wall-clock seconds (``"45s"``, ``"2.5s"``)."""
    if isinstance(every, bool):
        raise ValueError(f"checkpoint_every must be an int or 'Ns': {every!r}")
    if isinstance(every, int):
        levels = every
        if levels < 1:
            raise ValueError(f"checkpoint_every levels must be >= 1: {levels}")
        return levels, None
    s = str(every).strip()
    if s.endswith("s"):
        seconds = float(s[:-1])
        if seconds <= 0:
            raise ValueError(f"checkpoint_every seconds must be > 0: {s!r}")
        return None, seconds
    try:
        return _parse_every(int(s))
    except ValueError:
        raise ValueError(
            f"checkpoint_every must be an int (levels) or 'Ns' (seconds): "
            f"{every!r}"
        ) from None


class AutoCheckpointer:
    """In-loop auto-checkpoint cadence for the device engines.

    The engines call :meth:`maybe` at every quiescent point (between
    supersteps, after commit bookkeeping); this object decides whether a
    checkpoint is due — every ``checkpoint_every`` committed levels, or
    every that many seconds with an ``"Ns"`` spec — and routes the write
    through ``checker.save_checkpoint`` (which owns the obs span, the
    ``checkpoints_written`` counter, and the ``last_checkpoint`` gauge).
    Cadence is *checked* at dispatch boundaries, so under fused dispatch the
    effective granularity is the dispatch block (up to
    ``levels_per_dispatch`` levels), never mid-device-call.
    """

    #: Default cadence when ``checkpoint_to`` is set without an explicit
    #: ``checkpoint_every``: a wall-clock minute — soak-friendly (bounded
    #: re-exploration after a wedge) without per-level write amplification.
    DEFAULT_EVERY = "60s"
    DEFAULT_KEEP = 3

    def __init__(self, path: str, every=None, keep: Optional[int] = None):
        self.path = path
        self.every_levels, self.every_seconds = _parse_every(
            self.DEFAULT_EVERY if every is None else every
        )
        self.keep = self.DEFAULT_KEEP if keep is None else int(keep)
        if self.keep < 1:
            raise ValueError(f"checkpoint_keep must be >= 1: {self.keep}")
        self._last_depth: Optional[int] = None
        self._last_time: Optional[float] = None

    @classmethod
    def resolve(cls, checkpoint_to, checkpoint_every, checkpoint_keep):
        """The spawn-kwarg/env resolution every engine shares:
        ``checkpoint_to`` (env ``STPU_CHECKPOINT_TO``) arms auto-
        checkpointing; ``checkpoint_every`` (env ``STPU_CHECKPOINT_EVERY``)
        and ``checkpoint_keep`` (env ``STPU_CHECKPOINT_KEEP``) tune it.
        Returns None when off. NOTE: the env path arms EVERY checker in the
        process onto one file — fine for single-checker tools (soak
        workers); multi-checker processes (bench's matrix) must pass
        ``checkpoint_to`` explicitly per checker instead."""
        path = checkpoint_to or os.environ.get("STPU_CHECKPOINT_TO") or None
        if path is None:
            return None
        every = (
            checkpoint_every
            if checkpoint_every is not None
            else os.environ.get("STPU_CHECKPOINT_EVERY") or None
        )
        keep = (
            checkpoint_keep
            if checkpoint_keep is not None
            else os.environ.get("STPU_CHECKPOINT_KEEP") or None
        )
        return cls(path, every, None if keep is None else int(keep))

    def arm(self, depth: int) -> None:
        """Baseline the cadence at the checker's starting point (fresh init
        or restore) — the first interval is measured from here, so a
        just-resumed checker does not immediately rewrite the checkpoint it
        resumed from."""
        self._last_depth = depth
        self._last_time = time.monotonic()

    def due(self, depth: int) -> bool:
        if self._last_depth is None:
            self.arm(depth)
            return False
        if self.every_levels is not None:
            return depth - self._last_depth >= self.every_levels
        return time.monotonic() - self._last_time >= self.every_seconds

    def maybe(self, checker) -> bool:
        """Write a checkpoint if one is due; returns whether it wrote."""
        depth = checker._depth
        if not self.due(depth):
            return False
        checker.save_checkpoint(self.path, keep=self.keep)
        self._last_depth = depth
        self._last_time = time.monotonic()
        return True
