"""DR8xx: code-vs-docs/registry drift gates.

Three inventories that historically rot apart get machine-checked:

- DR801: every ``XGBTPU_*`` env var the package READS (``os.environ.get``
  / ``os.getenv`` / ``os.environ[...]`` / ``.setdefault``, with constant
  keys or module-level constant names) must appear in the curated docs
  set. One finding per variable, anchored at its first read.
- DR802: every metric registered via ``REGISTRY.counter/gauge/
  histogram("name", ...)`` must appear in the curated docs set (the
  observability tables). One finding per metric name.
- DR803: every dispatch op in the ``register(op, impl, pref=...)`` table
  must have at least one impl whose preference tuple covers CPU (a
  ``("cpu", _)`` or ``("*", _)`` entry) — a statically-checkable proxy
  for "resolvable on CPU" that the tier-0.5 ``dispatch-report`` gate
  then verifies at runtime. Scoped to ``dispatch/ops.py`` plus external
  fixture files, and form-gated (two string args + a ``pref=`` kwarg) so
  unrelated ``register`` calls never match.

The docs scope is CURATED, not a glob: any other file that lands under
``docs/`` (a session log, an incident write-up) quotes env names
incidentally and must not satisfy the gate. When the curated docs are absent entirely (an
installed package without the repo checkout), DR801/DR802 stay silent
rather than flagging the whole inventory.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Tuple

from .lint import Finding

__all__ = ["run_pass", "CURATED_DOCS"]

# The reference documentation set the gates check against. Keep env
# tables and metric tables inside these files (docs/static_analysis.md
# documents the contract).
CURATED_DOCS = (
    "perf.md", "serving.md", "observability.md", "resilience.md",
    "distributed.md", "static_analysis.md",
)

_ENV_PREFIX = "XGBTPU_"
_METRIC_KINDS = {"counter", "gauge", "histogram"}
_METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")


def _docs_text(pkg_root: str) -> Optional[str]:
    root = os.path.join(os.path.dirname(pkg_root), "docs")
    parts: List[str] = []
    for name in CURATED_DOCS:
        p = os.path.join(root, name)
        try:
            with open(p, encoding="utf-8") as f:
                parts.append(f.read())
        except OSError:
            continue
    return "\n".join(parts) if parts else None


def _documented(name: str, docs: str) -> bool:
    return re.search(r"\b" + re.escape(name) + r"\b", docs) is not None


def _module_str_consts(tree: ast.Module) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for n in tree.body:
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name) \
                and isinstance(n.value, ast.Constant) \
                and isinstance(n.value.value, str):
            out[n.targets[0].id] = n.value.value
    return out


def _key_of(node: Optional[ast.AST],
            consts: Dict[str, str]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _env_reads(mod) -> List[Tuple[str, int]]:
    """(env name, line) for every XGBTPU_* read in one module."""
    consts = _module_str_consts(mod.tree)
    out: List[Tuple[str, int]] = []
    for n in ast.walk(mod.tree):
        key: Optional[str] = None
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
            attr = n.func.attr
            base = n.func.value
            base_src = ast.dump(base)
            if attr in ("get", "setdefault") and "environ" in base_src \
                    and n.args:
                key = _key_of(n.args[0], consts)
            elif attr == "getenv" and n.args:
                key = _key_of(n.args[0], consts)
        elif isinstance(n, ast.Subscript):
            base_src = ast.dump(n.value)
            if "environ" in base_src:
                sl = n.slice
                key = _key_of(sl, consts)
        if key and key.startswith(_ENV_PREFIX):
            out.append((key, n.lineno))
    return out


def _metric_regs(mod) -> List[Tuple[str, int]]:
    out: List[Tuple[str, int]] = []
    for n in ast.walk(mod.tree):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr in _METRIC_KINDS and n.args \
                and isinstance(n.args[0], ast.Constant) \
                and isinstance(n.args[0].value, str):
            name = n.args[0].value
            if _METRIC_NAME_RE.match(name):
                out.append((name, n.lineno))
    return out


def _dispatch_table(mod) -> Dict[str, List[Tuple[int, List[str]]]]:
    """op -> [(line, [platforms of one impl's pref])] from
    ``register(op, impl, pref=((plat, rank), ...))`` calls."""
    out: Dict[str, List[Tuple[int, List[str]]]] = {}
    for n in ast.walk(mod.tree):
        if not (isinstance(n, ast.Call)
                and ((isinstance(n.func, ast.Name)
                      and n.func.id == "register")
                     or (isinstance(n.func, ast.Attribute)
                         and n.func.attr == "register"))):
            continue
        if len(n.args) < 2 \
                or not all(isinstance(a, ast.Constant)
                           and isinstance(a.value, str)
                           for a in n.args[:2]):
            continue
        pref = None
        for kw in n.keywords:
            if kw.arg == "pref":
                pref = kw.value
        if pref is None or not isinstance(pref, (ast.Tuple, ast.List)):
            continue
        plats: List[str] = []
        for e in pref.elts:
            if isinstance(e, (ast.Tuple, ast.List)) and e.elts \
                    and isinstance(e.elts[0], ast.Constant) \
                    and isinstance(e.elts[0].value, str):
                plats.append(e.elts[0].value)
        out.setdefault(n.args[0].value, []).append((n.lineno, plats))
    return out


def run_pass(modules, pkg_root: str) -> List[Finding]:
    findings: List[Finding] = []
    docs = _docs_text(pkg_root)

    if docs is not None:
        env_first: Dict[str, Tuple[str, int]] = {}
        met_first: Dict[str, Tuple[str, int]] = {}
        for mod in sorted(modules, key=lambda m: m.relpath):
            for name, line in sorted(_env_reads(mod),
                                     key=lambda t: t[1]):
                env_first.setdefault(name, (mod.relpath, line))
            for name, line in sorted(_metric_regs(mod),
                                     key=lambda t: t[1]):
                met_first.setdefault(name, (mod.relpath, line))
        for name, (rel, line) in sorted(env_first.items()):
            if not _documented(name, docs):
                findings.append(Finding(
                    "DR801", rel, line, name,
                    f"env var {name} is read here but appears in none of "
                    f"the curated docs ({', '.join(CURATED_DOCS)}) — add "
                    f"it to an env table or baseline it with a "
                    f"justification"))
        for name, (rel, line) in sorted(met_first.items()):
            if not _documented(name, docs):
                findings.append(Finding(
                    "DR802", rel, line, name,
                    f"metric {name} is registered here but documented "
                    f"nowhere in the curated docs — add it to the "
                    f"observability tables"))

    for mod in modules:
        if mod.relpath.endswith("dispatch/ops.py") or not mod.in_package:
            for op, impls in _dispatch_table(mod).items():
                if any("cpu" in plats or "*" in plats
                       for _, plats in impls):
                    continue
                line = min(ln for ln, _ in impls)
                findings.append(Finding(
                    "DR803", mod.relpath, line, op,
                    f"dispatch op '{op}' has no impl whose preference "
                    f"covers CPU (no ('cpu', _) or ('*', _) entry) — "
                    f"every op must resolve somewhere on the default "
                    f"backend"))
    return findings
