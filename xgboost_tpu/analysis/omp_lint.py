"""OMP7xx: OpenMP float-determinism lint over the native TUs.

The native kernels promise "bit-identical regardless of thread count"
(tree_build.cpp's contract comment; the sibling-sub pins, the
per-level replay and canonical-cuts manifest all assume it). The only OpenMP
shapes compatible with that promise are disjoint-slab ``parallel for``
loops — every float write lands in a slab addressed through the loop
induction variable (or a body-local derived from it), so the result is
independent of scheduling. This pass flags the constructs that break
the promise by *reordering float accumulation across threads*:

- OMP701: ``reduction(+:x)`` (or ``*``/``-``) over a float/double —
  the combination order is the runtime's choice;
- OMP702: ``#pragma omp atomic`` updating a float/double lvalue —
  atomicity without ordering;
- OMP703: a ``parallel for`` body writing a float array through an
  index that mentions NO body-local and NOT the induction variable —
  i.e. a loop-invariant target every thread races on. Writes through
  body-declared locals (the slab-pointer idiom ``float *h = hist +
  base;``) and induction-indexed writes are the blessed discipline and
  stay silent;
- OMP704: a native TU compiled without ``-ffp-contract=off`` — FMA
  contraction is the *compiler* reordering the float math instead of
  the runtime, and splits the kernel's answers from XLA:CPU's
  (tree_build.cpp documents the precedent). Detected at the
  ``_compile(src, lib, flags)`` call sites in ``native/__init__.py``
  (and fixture stubs shaped like them), with constant folding through
  local/module assignments and ``flags + [...]`` concatenation.

INTEGER lanes are exempt from OMP701–703 (ISSUE 19): the quantized
histogram engine accumulates in int32/int64 lanes precisely BECAUSE
integer addition is associative — any reduction/merge order gives the
same bits, so thread count cannot change the result. Typing is by
nearest preceding declaration (``_type_env``), so a TU that hosts both
the float core and the integer engine can even reuse a name across
lanes without false findings.

All OMP7xx findings key on stable symbols (the reduction variable, the
written array, the TU basename) so baseline entries survive line churn.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .lint import Finding

__all__ = ["run_pass", "collect_compile_sites", "CompileSite"]

_DECL_KW = (r"(?:const\s+)?(?:unsigned\s+)?"
            r"(?:float|double|int|long|short|char|bool|auto|size_t|"
            r"std::\w+(?:<[^<>]*>)?|int\d+_t|uint\d+_t)")


@dataclass
class CompileSite:
    """One ``_compile(src, lib, flags)`` call, constants resolved."""

    relpath: str
    line: int
    func: str
    src_cpp: Optional[str]       # basename, e.g. "tree_build.cpp"
    lib_so: Optional[str]        # basename, e.g. "libtreebuild.so"
    flags: Optional[List[str]]   # None when not statically resolvable


# ---------------------------------------------------------------------------
# _compile call-site extraction (shared with the NB6xx nm probe)
# ---------------------------------------------------------------------------


def _dig_const_str(node: Optional[ast.AST], suffix: str,
                   scopes: Sequence[Dict[str, ast.AST]],
                   depth: int = 0) -> Optional[str]:
    """First string constant ending in ``suffix`` reachable from
    ``node``, following Name assignments through ``scopes``."""
    if node is None or depth > 6:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (os.path.basename(node.value)
                if node.value.endswith(suffix) else None)
    if isinstance(node, ast.Name):
        for sc in scopes:
            if node.id in sc:
                return _dig_const_str(sc[node.id], suffix, scopes,
                                      depth + 1)
        return None
    for ch in ast.iter_child_nodes(node):
        got = _dig_const_str(ch, suffix, scopes, depth + 1)
        if got:
            return got
    return None


def _resolve_str_list(node: Optional[ast.AST],
                      scopes: Sequence[Dict[str, ast.AST]],
                      depth: int = 0) -> Optional[List[str]]:
    if node is None or depth > 6:
        return None
    if isinstance(node, (ast.List, ast.Tuple)):
        out: List[str] = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                sub = _resolve_str_list(e, scopes, depth + 1)
                # a computed element ("-I" + inc()) is opaque but does
                # not hide the rest of the list from the flag check
                out.extend(sub if sub is not None else ["<dynamic>"])
        return out
    if isinstance(node, ast.Name):
        for sc in scopes:
            if node.id in sc:
                return _resolve_str_list(sc[node.id], scopes, depth + 1)
        return None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _resolve_str_list(node.left, scopes, depth + 1)
        right = _resolve_str_list(node.right, scopes, depth + 1)
        if left is not None and right is not None:
            return left + right
    return None


def _module_assigns(tree: ast.Module) -> Dict[str, ast.AST]:
    out: Dict[str, ast.AST] = {}
    for n in tree.body:
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name):
            out[n.targets[0].id] = n.value
    return out


def collect_compile_sites(modules) -> List[CompileSite]:
    sites: List[CompileSite] = []
    for mod in modules:
        mod_sc = _module_assigns(mod.tree)

        def visit(body, qual: str, local: Dict[str, ast.AST]) -> None:
            for n in body:
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    visit(n.body, f"{qual}.{n.name}" if qual else n.name,
                          {})
                    continue
                if isinstance(n, ast.ClassDef):
                    visit(n.body, f"{qual}.{n.name}" if qual else n.name,
                          {})
                    continue
                for sub in ast.walk(n):
                    if isinstance(sub, ast.Assign) \
                            and len(sub.targets) == 1 \
                            and isinstance(sub.targets[0], ast.Name):
                        local[sub.targets[0].id] = sub.value
                    if isinstance(sub, ast.Call):
                        ch = sub.func
                        name = (ch.attr if isinstance(ch, ast.Attribute)
                                else ch.id if isinstance(ch, ast.Name)
                                else None)
                        if name != "_compile" or len(sub.args) < 3:
                            continue
                        scopes = (local, mod_sc)
                        sites.append(CompileSite(
                            relpath=mod.relpath, line=sub.lineno,
                            func=qual or "<module>",
                            src_cpp=_dig_const_str(
                                sub.args[0], ".cpp", scopes),
                            lib_so=_dig_const_str(
                                sub.args[1], ".so", scopes),
                            flags=_resolve_str_list(
                                sub.args[2], scopes)))

        visit(mod.tree.body, "", {})
    return sites


# ---------------------------------------------------------------------------
# pragma analysis
# ---------------------------------------------------------------------------


_INT_KW = (r"(?:unsigned\s+)?(?:int|long(?:\s+long)?|short|size_t|"
           r"(?:std::)?u?int\d+_t)")


def _type_env(text: str) -> Dict[str, List[Tuple[int, str]]]:
    """name -> [(decl char offset, kind)] sorted by position, kind in
    {"float", "int"} — the cheap positional type environment the pragma
    checks consult. Positional because the quantized histogram engine
    (ISSUE 19) sits in the same TU as the float core and may reuse a
    name across lanes: the NEAREST PRECEDING declaration governs, so an
    ``int64_t acc`` reduction stays exempt even when a ``float acc``
    exists earlier in the file (integer adds are associative — thread
    count cannot change the result — which is the engine's entire
    determinism argument)."""
    env: Dict[str, List[Tuple[int, str]]] = {}

    def scan(pattern: str, kind: str) -> None:
        for m in re.finditer(pattern, text):
            env.setdefault(m.group(1), []).append((m.start(), kind))

    scan(r"\b(?:float|double)\s*[*&]?\s*(\w+)\s*[=;,)\[]", "float")
    scan(r"\bstd::vector<\s*(?:float|double)\s*>\s*(\w+)", "float")
    scan(r"\b(?:float|double)\s*\*\s*(?:const\s+)?(\w+)", "float")
    scan(r"\b" + _INT_KW + r"\s*[*&]?\s*(\w+)\s*[=;,)\[]", "int")
    scan(r"\bstd::vector<\s*" + _INT_KW + r"\s*>\s*(\w+)", "int")
    scan(r"\b" + _INT_KW + r"\s*\*\s*(?:const\s+)?(\w+)", "int")
    for decls in env.values():
        decls.sort()
    return env


def _is_float_at(env: Dict[str, List[Tuple[int, str]]], name: str,
                 pos: int) -> bool:
    """Whether ``name`` is float-typed at char offset ``pos``: the
    nearest preceding declaration decides; a name only declared later
    falls back to its first declaration; an undeclared name is not
    float (the original conservative behavior)."""
    decls = env.get(name)
    if not decls:
        return False
    kind = decls[0][1]
    for p, k in decls:
        if p > pos:
            break
        kind = k
    return kind == "float"


def _joined_pragmas(text: str) -> List[Tuple[int, str, int]]:
    """(line, directive-text, char-offset-after) for each ``#pragma omp``,
    with backslash continuations folded in."""
    out = []
    for m in re.finditer(r"^[ \t]*#\s*pragma\s+omp\b(.*)$", text,
                         re.MULTILINE):
        line = text.count("\n", 0, m.start()) + 1
        directive = m.group(1)
        end = m.end()
        while directive.rstrip().endswith("\\"):
            directive = directive.rstrip()[:-1]
            nl = text.find("\n", end)
            if nl < 0:
                break
            nxt = text.find("\n", nl + 1)
            nxt = nxt if nxt >= 0 else len(text)
            directive += " " + text[nl + 1:nxt]
            end = nxt
        out.append((line, directive, end))
    return out


def _body_span(text: str, start: int) -> Tuple[int, int]:
    """Span of the statement/block beginning at/after ``start``."""
    i = start
    while i < len(text) and text[i] in " \t\r\n":
        i += 1
    if i < len(text) and text[i] == "{":
        depth = 0
        j = i
        while j < len(text):
            if text[j] == "{":
                depth += 1
            elif text[j] == "}":
                depth -= 1
                if depth == 0:
                    return i, j + 1
            j += 1
        return i, len(text)
    j = text.find(";", i)
    return i, (j + 1 if j >= 0 else len(text))


def _for_loop_after(text: str, start: int):
    """(induction_var, body_start, body_end) of the ``for`` statement
    following ``start``; None when no for-header parses."""
    m = re.compile(r"for\s*\(").search(text, start)
    if not m or m.start() - start > 200:
        return None
    depth = 0
    j = m.end() - 1
    while j < len(text):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                break
        j += 1
    header = text[m.end():j]
    iv = re.search(_DECL_KW + r"\s+(\w+)\s*=", header)
    induction = iv.group(1) if iv else None
    b0, b1 = _body_span(text, j + 1)
    return induction, b0, b1


def _body_locals(body: str) -> Set[str]:
    """Names declared inside the loop body (thread-private by
    construction): plain decls, slab pointers, inner-loop inductions,
    and the trailing declarators of ``int a = 1, b = 2;`` statements."""
    out: Set[str] = set()
    for m in re.finditer(_DECL_KW + r"\s*[*&]?\s*(\w+)\s*[=;({\[]", body):
        out.add(m.group(1))
        stmt_end = body.find(";", m.end())
        stmt = body[m.end():stmt_end if stmt_end >= 0 else len(body)]
        depth = 0
        for i, c in enumerate(stmt):
            if c in "([{":
                depth += 1
            elif c in ")]}":
                depth -= 1
            elif c == "," and depth == 0:
                dm = re.match(r"\s*[*&]?\s*(\w+)\s*=", stmt[i + 1:])
                if dm:
                    out.add(dm.group(1))
    return out


def _check_parallel_for(text: str, relpath: str, pragma_line: int,
                        after: int,
                        env: Dict[str, List[Tuple[int, str]]]
                        ) -> List[Finding]:
    parsed = _for_loop_after(text, after)
    if parsed is None:
        return []
    induction, b0, b1 = parsed
    body = text[b0:b1]
    derived = _body_locals(body)
    if induction:
        derived.add(induction)
    findings: List[Finding] = []
    for m in re.finditer(
            r"(\w+)\s*\[((?:[^\[\]]|\[[^\]]*\])*)\]\s*"
            r"(\+=|-=|\*=|/=|=)(?!=)", body):
        base, index, _op = m.group(1), m.group(2), m.group(3)
        # integer-lane targets are exempt: racing integer adds would
        # still be a bug, but the determinism contract this rule guards
        # (float accumulation order) does not apply to them
        if not _is_float_at(env, base, b0 + m.start()) \
                or base in derived:
            continue
        idx_names = set(re.findall(r"[A-Za-z_]\w*", index))
        if idx_names & derived:
            continue
        line = pragma_line + body.count("\n", 0, m.start()) \
            + text.count("\n", after, b0)
        findings.append(Finding(
            "OMP703", relpath, line, base,
            f"parallel-for writes float array '{base}' through a "
            f"loop-invariant index ('{index.strip() or '0'}') — every "
            f"thread races on the same cells; address it through the "
            f"induction variable or a body-local slab pointer"))
    return findings


def _analyze_tu(path: str, relpath: str) -> List[Finding]:
    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
    except OSError:
        return []
    env = _type_env(text)
    findings: List[Finding] = []
    for line, directive, after in _joined_pragmas(text):
        for rm in re.finditer(r"reduction\s*\(\s*[^:()]+:\s*([^)]*)\)",
                              directive):
            for var in (v.strip() for v in rm.group(1).split(",")):
                if var and _is_float_at(env, var, after):
                    findings.append(Finding(
                        "OMP701", relpath, line, var,
                        f"OpenMP reduction over float '{var}' combines "
                        f"partials in runtime-chosen order — the result "
                        f"depends on the thread count"))
        if re.search(r"\batomic\b", directive):
            stmt = text[after:after + 200].lstrip()
            lm = re.match(r"([A-Za-z_]\w*)", stmt)
            if lm and _is_float_at(env, lm.group(1), after):
                findings.append(Finding(
                    "OMP702", relpath, line, lm.group(1),
                    f"omp atomic on float '{lm.group(1)}' is atomic but "
                    f"unordered — accumulation order varies per run"))
        if re.search(r"\bfor\b", directive) \
                and not re.search(r"\batomic\b", directive):
            findings += _check_parallel_for(
                text, relpath, line, after, env)
    return findings


def run_pass(cpp_files: Sequence[Tuple[str, str]], modules,
             compile_sites: Optional[List[CompileSite]] = None
             ) -> List[Finding]:
    """The OMP7xx pass over (abspath, relpath) TU pairs + the collected
    ``_compile`` sites (for OMP704)."""
    findings: List[Finding] = []
    for path, rel in cpp_files:
        findings += _analyze_tu(path, rel)
    if compile_sites is None:
        compile_sites = collect_compile_sites(modules)
    seen: Set[Tuple[str, str]] = set()
    for cs in compile_sites:
        if cs.src_cpp is None or cs.flags is None:
            continue
        if "-ffp-contract=off" in cs.flags:
            continue
        key = (cs.relpath, cs.src_cpp)
        if key in seen:
            continue  # build-variant fallbacks of the same TU
        seen.add(key)
        findings.append(Finding(
            "OMP704", cs.relpath, cs.line, cs.src_cpp,
            f"{cs.src_cpp} is compiled without -ffp-contract=off: FMA "
            f"contraction reorders the float math and splits the "
            f"kernel's answers from XLA:CPU's"))
    return findings
