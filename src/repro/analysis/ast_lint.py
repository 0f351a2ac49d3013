"""AST lint — source-tree invariants, no imports of the checked code.

Walks every ``.py`` under ``src/`` with the stdlib ``ast`` module (the
checked modules are never imported, so a syntax-valid tree lints in
milliseconds and a broken one is reported instead of crashing the
linter's own process):

* AS001 — raw ``jax.lax`` collectives outside ``comm/`` + ``dist/``.
  The comm registry and the mesh runtime are the only places allowed to
  issue collectives; anywhere else the per-layer plan, the wire-byte
  accounting, and the dtype contract silently don't apply.
* AS002 — kernel entry points (``kernels.ops`` / ``kernels.ref``
  functions) called outside ``kernels/`` — everything must route
  through ``kernels/dispatch.py``'s registry.
* AS003 — non-frozen dataclasses in spec modules.  Specs are hashed as
  jit static arguments; a mutable spec is a stale-compilation-cache bug
  waiting to happen.
* AS004 — mutable default arguments anywhere in ``src/``.
"""

from __future__ import annotations

import ast
import os
from typing import Iterable, Optional

from repro.analysis.findings import Finding

#: collective primitives the comm layer owns
COLLECTIVE_NAMES = frozenset({
    "psum", "psum_scatter", "all_gather", "ppermute", "all_to_all",
    "pmean", "pshuffle",
})

#: directories (repo-relative, '/'-normalized) allowed to issue raw
#: collectives: the strategy registry itself and the mesh runtime
COLLECTIVE_ALLOWED_DIRS = ("repro/comm/", "repro/dist/")

#: kernel entry-point names only ``kernels/`` may call directly
KERNEL_ENTRY_NAMES = frozenset({
    "pallas_dequant_matmul_ordered", "pallas_dequant_matmul_gidx",
    "dequant_matmul",
})
KERNEL_ALLOWED_DIRS = ("repro/kernels/",)

#: spec modules whose dataclasses must all be frozen (jit-static specs)
SPEC_MODULES = (
    "repro/core/policy.py",
    "repro/comm/spec.py",
    "repro/cache/spec.py",
    "repro/dist/topology.py",
)


def _attr_chain(node: ast.AST) -> Optional[str]:
    """``jax.lax.psum`` -> "jax.lax.psum"; None for non-name chains."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_mutable_literal(node: ast.AST) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set)):
        return True
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        return chain in ("list", "dict", "set")
    return False


def _dataclass_frozen(dec: ast.AST) -> Optional[bool]:
    """True/False for a dataclass decorator, None for other decorators."""
    target = dec.func if isinstance(dec, ast.Call) else dec
    chain = _attr_chain(target)
    if chain is None or chain.split(".")[-1] != "dataclass":
        return None
    if not isinstance(dec, ast.Call):
        return False                      # bare @dataclass
    for kw in dec.keywords:
        if kw.arg == "frozen":
            return (isinstance(kw.value, ast.Constant)
                    and bool(kw.value.value))
    return False


def _under(rel: str, dirs) -> bool:
    return any(rel.startswith(d) for d in dirs)


def lint_source(src: str, rel: str) -> list[Finding]:
    """Lint one module's source text (``rel``: '/'-normalized path
    relative to the ``src/`` root, e.g. ``"repro/core/schemes.py"``)."""
    out: list[Finding] = []
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [Finding("AS004", f"unparseable module: {e.msg}",
                        location=f"{rel}:{e.lineno or 0}")]

    check_collectives = not _under(rel, COLLECTIVE_ALLOWED_DIRS)
    check_kernels = not _under(rel, KERNEL_ALLOWED_DIRS)
    spec_module = any(rel.endswith(m) or rel == m for m in SPEC_MODULES)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain is None:
                continue
            head, leaf = chain.split(".")[0], chain.split(".")[-1]
            # AS001: lax.psum(...) / jax.lax.all_gather(...) etc.; the
            # module-qualified form is the only way these are spelled
            # (a bare `psum(...)` import is matched too, conservatively)
            if (check_collectives and leaf in COLLECTIVE_NAMES
                    and ("lax" in chain.split(".") or chain == leaf)):
                out.append(Finding(
                    "AS001",
                    f"raw collective {chain}() outside comm//dist/ — "
                    f"route it through repro.comm.dispatch",
                    location=f"{rel}:{node.lineno}"))
            # AS002: ops.pallas_dequant_matmul_*(...) / ref.dequant_matmul
            if (check_kernels and leaf in KERNEL_ENTRY_NAMES
                    and head != "kdispatch"):
                out.append(Finding(
                    "AS002",
                    f"kernel entry point {chain}() bypasses "
                    f"kernels/dispatch.py",
                    location=f"{rel}:{node.lineno}"))
        elif isinstance(node, ast.ClassDef) and spec_module:
            for dec in node.decorator_list:
                frozen = _dataclass_frozen(dec)
                if frozen is False:
                    out.append(Finding(
                        "AS003",
                        f"spec dataclass {node.name} is not frozen=True "
                        f"(specs are hashed as jit static arguments)",
                        location=f"{rel}:{node.lineno}"))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            for default in list(args.defaults) + [
                    d for d in args.kw_defaults if d is not None]:
                if _is_mutable_literal(default):
                    out.append(Finding(
                        "AS004",
                        f"mutable default argument in {node.name}()",
                        location=f"{rel}:{default.lineno}"))
    return out


def lint_tree(root: str) -> list[Finding]:
    """Lint every ``.py`` under ``root`` (the ``src/`` directory)."""
    out: list[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in ("__pycache__",))
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, encoding="utf-8") as f:
                out.extend(lint_source(f.read(), rel))
    return out


def run(src_root: Optional[str] = None) -> list[Finding]:
    """Entry point the CLI calls: lint the repo's ``src/`` tree."""
    if src_root is None:
        # .../src/repro/analysis/ast_lint.py -> .../src
        here = os.path.dirname(os.path.abspath(__file__))
        src_root = os.path.dirname(os.path.dirname(here))
    return lint_tree(src_root)
