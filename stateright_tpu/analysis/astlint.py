"""The AST lint pass: STPU101 and STPU103 project rules over the package source.

These are source-level rules — cheaper than tracing and catching the
pinned shapes before they ever reach a jaxpr. The pass parses every
``.py`` under ``stateright_tpu/`` (no imports, no execution) and walks
the ASTs once.
"""

from __future__ import annotations

import ast
import os
from typing import Iterator, List, Tuple

from .rules import Finding

_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO = os.path.dirname(_PKG)

#: ``.at[...].<method>`` indexed-update methods STPU101 flags in model
#: kernel code.
_AT_METHODS = frozenset(
    {"set", "add", "multiply", "mul", "divide", "min", "max", "apply", "power"}
)

#: Path-name fragments that mark a write target as a checkpoint or
#: heartbeat artifact for STPU103.
_DURABLE_HINTS = ("heartbeat", "checkpoint", "ckpt", "hb_path", "hb_file")


def iter_sources(root: str = _PKG) -> Iterator[Tuple[str, str]]:
    """``(abs_path, rel_path)`` for every package source file."""
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                p = os.path.join(dirpath, fn)
                yield p, os.path.relpath(p, _REPO)


def _line_of(src_lines: List[str], node: ast.AST) -> str:
    i = getattr(node, "lineno", 0)
    if 1 <= i <= len(src_lines):
        return src_lines[i - 1].strip()
    return ""


def _is_at_update(node: ast.Call) -> bool:
    """``X.at[IDX].set(...)`` and friends."""
    f = node.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr in _AT_METHODS
        and isinstance(f.value, ast.Subscript)
        and isinstance(f.value.value, ast.Attribute)
        and f.value.value.attr == "at"
    )


def _open_write_target(node: ast.Call) -> str:
    """For ``open(path, mode)`` calls whose mode writes, the unparsed
    path expression; '' otherwise."""
    f = node.func
    if not (isinstance(f, ast.Name) and f.id == "open"):
        return ""
    mode = None
    if len(node.args) >= 2:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if mode is None:
        return ""
    if not (isinstance(mode, ast.Constant) and isinstance(mode.value, str)):
        return ""
    if not any(c in mode.value for c in "wa+x"):
        return ""
    if not node.args:
        return ""
    try:
        return ast.unparse(node.args[0])
    except Exception:  # pragma: no cover - unparse is total on 3.9+
        return ""


def lint_file(path: str, rel: str) -> List[Finding]:
    with open(path) as fh:
        src = fh.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:  # pragma: no cover - tree is import-clean
        return [
            Finding(
                rule="STPU101",
                surface=f"ast:{rel}",
                file=rel,
                line=e.lineno or 0,
                message=f"source failed to parse: {e.msg}",
                excerpt="",
            )
        ]
    lines = src.splitlines()
    in_models = f"{os.sep}models{os.sep}" in path
    in_durable_owner = (
        os.path.basename(path) == "checkpoint.py"
        or f"{os.sep}obs{os.sep}" in path
    )
    in_analysis = f"{os.sep}analysis{os.sep}" in path

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if in_models and _is_at_update(node):
            out.append(
                Finding(
                    rule="STPU101",
                    surface=f"ast:{rel}",
                    file=rel,
                    line=node.lineno,
                    message=(
                        "direct .at[...] indexed write in model kernel "
                        "code: route it through packing.Layout.set / "
                        "packing._word_update (owns the CPU-scatter vs "
                        "accelerator-one-hot split; STPU001's source "
                        "form)"
                    ),
                    excerpt=_line_of(lines, node),
                )
            )
        if not in_durable_owner and not in_analysis:
            target = _open_write_target(node)
            if target and any(h in target.lower() for h in _DURABLE_HINTS):
                out.append(
                    Finding(
                        rule="STPU103",
                        surface=f"ast:{rel}",
                        file=rel,
                        line=node.lineno,
                        message=(
                            "non-atomic write to a checkpoint/heartbeat "
                            "path outside checkpoint.py/obs/: watchdogs "
                            "and resume can observe a torn file — write "
                            "through the owning codec (tmp + os.replace)"
                        ),
                        excerpt=_line_of(lines, node),
                    )
                )
    return out


def run_ast_pass(root: str = _PKG) -> List[Finding]:
    findings: List[Finding] = []
    for path, rel in iter_sources(root):
        findings.extend(lint_file(path, rel))
    return findings
