"""Native (C++) host runtime: fingerprinting and parent-map indexing.

The shared library builds lazily from ``hostkit.cpp`` on first use (g++,
no external deps; the binding is ctypes over a C ABI). The built file is
named by a hash of the source's contents, so only a library built from the
committed ``hostkit.cpp`` is ever loaded. Everything degrades to the
pure-Python mirrors when the build fails — the failure is written to stderr
— so the native layer is an accelerator, never a requirement.

Exposed surface:

- :func:`available` — whether the library loaded.
- :func:`fingerprint_words` — batch two-lane fingerprints, bit-identical
  with ``ops/fphash.py`` (differentially tested).
- :class:`ParentMap` — open-addressing index over the device visited-set
  planes with O(1) lookup and native chain walking; replaces the Python
  dict built by the checkers' ``_parent_map`` for witness reconstruction.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "hostkit.cpp")


def _lib_path() -> str:
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libhostkit-{digest}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build(lib_path: str) -> bool:
    # Compile to a process-unique temp name and rename into place: rename is
    # atomic, so concurrent builders (or an interrupted compile) can never
    # leave a truncated .so behind.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            print(f"stateright_tpu.native: build failed, using the Python "
                  f"mirrors:\n{proc.stderr[-2000:]}", file=sys.stderr)
            return False
        os.replace(tmp, lib_path)
        return True
    except Exception as e:
        print(f"stateright_tpu.native: build failed, using the Python "
              f"mirrors: {type(e).__name__}: {e}", file=sys.stderr)
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        lib_path = _lib_path()
        if not os.path.exists(lib_path) and not _build(lib_path):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            print(f"stateright_tpu.native: cannot load {lib_path}, using "
                  f"the Python mirrors: {e}", file=sys.stderr)
            _build_failed = True
            return None

        u32p = ctypes.POINTER(ctypes.c_uint32)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.fingerprint_words.argtypes = [
            u32p,
            ctypes.c_int64,
            ctypes.c_int64,
            u32p,
            u32p,
        ]
        lib.fingerprint_words.restype = None
        lib.parentmap_build.argtypes = [u32p, u32p, u32p, u32p, ctypes.c_int64]
        lib.parentmap_build.restype = ctypes.c_void_p
        lib.parentmap_free.argtypes = [ctypes.c_void_p]
        lib.parentmap_free.restype = None
        lib.parentmap_count.argtypes = [ctypes.c_void_p]
        lib.parentmap_count.restype = ctypes.c_int64
        lib.parentmap_get.argtypes = [ctypes.c_void_p, ctypes.c_uint64, u64p]
        lib.parentmap_get.restype = ctypes.c_int
        lib.parentmap_chain.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            u64p,
            ctypes.c_int64,
        ]
        lib.parentmap_chain.restype = ctypes.c_int64
        _lib = lib
        return lib


def available() -> bool:
    return _load() is not None


def _u32ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


def fingerprint_words(words: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Native mirror of ``ops/fphash.fingerprint_words`` for 2-D batches.

    Falls back to the numpy implementation when the library is missing.
    """
    words = np.ascontiguousarray(words, dtype=np.uint32)
    if words.ndim != 2:
        raise ValueError(f"expected [n, w] words, got shape {words.shape}")
    lib = _load()
    if lib is None:
        from ..ops import fphash

        return fphash.fingerprint_words(words, np)
    n, w = words.shape
    out_hi = np.empty(n, dtype=np.uint32)
    out_lo = np.empty(n, dtype=np.uint32)
    lib.fingerprint_words(_u32ptr(words), n, w, _u32ptr(out_hi), _u32ptr(out_lo))
    return out_hi, out_lo


class ParentMap:
    """Index over visited-set planes: fp64 -> parent fp64 (native when the
    toolchain allows, dict fallback otherwise).

    The planes are the hash set's ``key_hi/key_lo/val_hi/val_lo`` uint32
    arrays; empty slots are key == (0, 0).
    """

    def __init__(self, key_hi, key_lo, val_hi, val_lo):
        kh = np.ascontiguousarray(key_hi, dtype=np.uint32)
        kl = np.ascontiguousarray(key_lo, dtype=np.uint32)
        vh = np.ascontiguousarray(val_hi, dtype=np.uint32)
        vl = np.ascontiguousarray(val_lo, dtype=np.uint32)
        self._lib = _load()
        self._handle = None
        self._dict = None
        if self._lib is not None:
            handle = self._lib.parentmap_build(
                _u32ptr(kh), _u32ptr(kl), _u32ptr(vh), _u32ptr(vl), len(kh)
            )
            if handle:
                self._handle = handle
                return
        # Fallback: plain dict (the original Python path).
        occ = (kh != 0) | (kl != 0)
        keys = (kh[occ].astype(np.uint64) << np.uint64(32)) | kl[occ].astype(
            np.uint64
        )
        vals = (vh[occ].astype(np.uint64) << np.uint64(32)) | vl[occ].astype(
            np.uint64
        )
        self._dict = {int(k): int(v) for k, v in zip(keys, vals)}

    def __len__(self) -> int:
        if self._handle is not None:
            return int(self._lib.parentmap_count(self._handle))
        return len(self._dict)

    def __contains__(self, fp64: int) -> bool:
        return self.get(fp64) is not None

    def get(self, fp64: int) -> Optional[int]:
        if self._handle is not None:
            out = ctypes.c_uint64()
            hit = self._lib.parentmap_get(
                self._handle, ctypes.c_uint64(fp64), ctypes.byref(out)
            )
            return int(out.value) if hit else None
        return self._dict.get(fp64)

    def __getitem__(self, fp64: int) -> int:
        value = self.get(fp64)
        if value is None:
            raise KeyError(fp64)
        return value

    def chain(self, fp64: int, max_len: int = 1 << 24) -> list:
        """The parent chain [fp64, ..., init_fp]; raises KeyError if a link
        is missing (host/device codec drift) and RuntimeError on a cycle
        (chain longer than ``max_len``)."""
        if self._handle is not None:
            # Geometric buffer growth: chains are usually short (BFS depth).
            size = 1024
            while True:
                out = np.empty(size, dtype=np.uint64)
                n = self._lib.parentmap_chain(
                    self._handle,
                    ctypes.c_uint64(fp64),
                    out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                    size,
                )
                if n == -1:
                    raise KeyError(
                        f"fingerprint {fp64:#x} missing from the visited table"
                    )
                if n == -2:
                    if size >= max_len:
                        raise RuntimeError("parent chain exceeds max_len")
                    size = min(size * 8, max_len)
                    continue
                return [int(x) for x in out[:n]]
        chain = []
        cur = fp64
        while cur != 0:
            if len(chain) >= max_len:
                raise RuntimeError("parent chain exceeds max_len")
            if cur not in self._dict:
                raise KeyError(
                    f"fingerprint {cur:#x} missing from the visited table"
                )
            chain.append(cur)
            cur = self._dict[cur]
        return chain

    def __del__(self):  # pragma: no cover - interpreter teardown
        if getattr(self, "_handle", None) is not None and self._lib is not None:
            try:
                self._lib.parentmap_free(self._handle)
            except Exception:
                pass
