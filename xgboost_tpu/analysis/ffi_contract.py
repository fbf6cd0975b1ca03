"""NB6xx: the cross-language FFI contract checker.

The native kernels (``native/*.cpp``) sit behind XLA FFI custom calls,
and nothing at runtime validates that a handler's buffer arity, element
dtypes, scalar attrs and result count still match the Python
``ffi_call`` wrapper that invokes it — a drifted signature is a silent
reinterpret of device memory (at best a shape error deep inside XLA, at
worst garbage histograms). This pass re-derives both halves of the
contract statically and cross-checks them:

* **C++ side** — a lightweight parser extracts every
  ``XLA_FFI_DEFINE_HANDLER_SYMBOL(Sym, Impl, ffi::Ffi::Bind()...)``
  builder chain (ordered ``.Arg<ffi::Buffer<dtype>>()`` element types,
  ``.Attr<T>("name")`` scalars, ``.Ret<...>()`` results) AND the
  matching ``ffi::Error Impl(...)`` parameter list, so a binder/impl
  divergence inside one TU is caught without any Python in the picture.
* **Python side** — an AST walk collects
  ``jax.ffi.register_ffi_target(name, jax.ffi.pycapsule(lib.Symbol), ...)``
  registrations (the target-name -> exported-symbol map) and every
  ``ffi_call(target, ret_specs, *operands, **attrs)`` site — the calling
  convention of ``native.boundary.ffi_call``, the one wrapper over
  ``jax.ffi.ffi_call(target, ret_specs)(*operands, **attrs)`` that every
  production and canary call goes through: result
  count + dtypes from the ``ShapeDtypeStruct`` specs, operand count,
  operand dtypes where inferable (``x.astype(jnp.i32)`` / ``jnp.i32(e)``
  / a local assigned from one), and the attr keyword names.

Rules:

- NB601: arity drift — operand count or attr name-set differs between a
  call site and its handler's binder (or binder vs impl params);
- NB602: buffer dtype mismatch across the boundary (call-site operand /
  result dtype vs binder, or binder vs impl) — positions whose Python
  dtype is not statically inferable, and ``ffi::AnyBuffer`` args, are
  skipped rather than guessed;
- NB603: result-count drift (``Ret<>`` count vs ``ShapeDtypeStruct``
  count);
- NB604: orphan — a target called but never registered, registered
  against a symbol no scanned TU defines, registered+defined but never
  called, a handler defined but never registered, or a registered
  symbol absent from the built ``.so``'s dynamic symbol table (a cheap
  ``nm -D`` probe using the src->lib map from the ``_compile`` call
  sites in ``native/__init__.py``).

Orphan directions are gated on the scan set actually containing the
other half (registrations / call sites / parsed handlers), so a
subset run over one file never reports its counterpart as missing.
Findings key on (rule, path, symbol) like every other rule family, so
the baseline machinery applies unchanged.
"""

from __future__ import annotations

import ast
import os
import re
import subprocess
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .lint import Finding

__all__ = ["run_pass", "parse_cpp_handlers", "CppHandler"]

# ffi:: element-type tokens -> numpy-style dtype names
_CPP_DTYPES = {
    "F16": "float16", "BF16": "bfloat16", "F32": "float32",
    "F64": "float64", "S8": "int8", "S16": "int16", "S32": "int32",
    "S64": "int64", "U8": "uint8", "U16": "uint16", "U32": "uint32",
    "U64": "uint64", "PRED": "bool", "C64": "complex64",
    "C128": "complex128",
}

# jnp./np. attribute names -> dtype names (bool_ -> bool)
_PY_DTYPES = {
    "float16": "float16", "bfloat16": "bfloat16", "float32": "float32",
    "float64": "float64", "int8": "int8", "int16": "int16",
    "int32": "int32", "int64": "int64", "uint8": "uint8",
    "uint16": "uint16", "uint32": "uint32", "uint64": "uint64",
    "bool_": "bool", "bool": "bool",
}

# ffi_call keywords that are call options, not handler attrs
_NON_ATTR_KW = {"vectorized", "has_side_effect", "custom_call_api_version",
                "vmap_method", "input_output_aliases", "input_layouts",
                "output_layouts"}


@dataclass
class CppHandler:
    """One XLA_FFI_DEFINE_HANDLER_SYMBOL signature (+ its impl's)."""

    symbol: str
    impl: str
    relpath: str
    line: int
    args: List[str] = field(default_factory=list)       # dtypes, 'any' ok
    attrs: List[Tuple[str, str]] = field(default_factory=list)  # (name, T)
    rets: List[str] = field(default_factory=list)
    impl_line: int = 0
    impl_args: Optional[List[str]] = None
    impl_rets: Optional[List[str]] = None
    impl_nattrs: Optional[int] = None


@dataclass
class _Registration:
    target: str
    symbol: str
    relpath: str
    line: int
    func: str


@dataclass
class _CallSite:
    targets: List[str]
    relpath: str
    line: int
    func: str
    n_args: int
    arg_dtypes: List[Optional[str]]
    attrs: List[str]
    n_rets: Optional[int]
    ret_dtypes: Optional[List[Optional[str]]]


# ---------------------------------------------------------------------------
# C++ side
# ---------------------------------------------------------------------------


def _balanced(text: str, i: int, op: str, cl: str) -> int:
    """Index one past the ``cl`` matching the ``op`` at ``text[i]``."""
    depth = 0
    while i < len(text):
        c = text[i]
        if c == op:
            depth += 1
        elif c == cl:
            depth -= 1
            if depth == 0:
                return i + 1
        i += 1
    return len(text)


def _cpp_dtype(txt: str) -> Optional[str]:
    """'any' for AnyBuffer, a dtype name for Buffer<ffi::X>, else None."""
    if "AnyBuffer" in txt:
        return "any"
    m = re.search(r"ffi::([A-Z][A-Z0-9]+)\b", txt)
    if m and m.group(1) in _CPP_DTYPES:
        return _CPP_DTYPES[m.group(1)]
    return None


def _parse_bind_chain(span: str, base_line: int, h: CppHandler) -> None:
    """Ordered .Arg<>/.Attr<>("name")/.Ret<>() extraction from the
    DEFINE_HANDLER_SYMBOL body."""
    for m in re.finditer(r"\.(Arg|Ret|Attr)\s*<", span):
        kind = m.group(1)
        end = _balanced(span, m.end() - 1, "<", ">")
        inner = span[m.end():end - 1]
        if kind == "Attr":
            nm = re.match(r'\s*\(\s*"([^"]+)"', span[end:])
            h.attrs.append((nm.group(1) if nm else "?", inner.strip()))
        elif kind == "Arg":
            h.args.append(_cpp_dtype(inner) or "any")
        else:
            h.rets.append(_cpp_dtype(inner) or "any")


def _split_depth0(s: str) -> List[str]:
    out, depth, cur = [], 0, []
    for c in s:
        if c in "<([":
            depth += 1
        elif c in ">)]":
            depth -= 1
        if c == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(c)
    if cur:
        out.append("".join(cur))
    return out


def _parse_impl(text: str, h: CppHandler) -> None:
    m = re.search(r"ffi::Error\s+" + re.escape(h.impl) + r"\s*\(", text)
    if not m:
        return
    end = _balanced(text, m.end() - 1, "(", ")")
    params = _split_depth0(text[m.end():end - 1])
    h.impl_line = text.count("\n", 0, m.start()) + 1
    args: List[str] = []
    rets: List[str] = []
    nattrs = 0
    for p in params:
        p = p.strip()
        if not p:
            continue
        if "Result" in p or "ResultBuffer" in p:
            rets.append(_cpp_dtype(p) or "any")
        elif "Buffer" in p:
            args.append(_cpp_dtype(p) or "any")
        else:
            nattrs += 1  # a scalar attr (int64_t / float / ...)
    h.impl_args, h.impl_rets, h.impl_nattrs = args, rets, nattrs


def parse_cpp_handlers(path: str, relpath: str) -> List[CppHandler]:
    """Every DEFINE_HANDLER_SYMBOL signature in one TU (empty on read
    errors — a missing TU is the nm probe's problem, not the parser's)."""
    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
    except OSError:
        return []
    out: List[CppHandler] = []
    for m in re.finditer(r"XLA_FFI_DEFINE_HANDLER_SYMBOL\s*\(", text):
        end = _balanced(text, m.end() - 1, "(", ")")
        span = text[m.end():end - 1]
        fields = _split_depth0(span)
        if len(fields) < 3:
            continue
        h = CppHandler(
            symbol=fields[0].strip(), impl=fields[1].strip(),
            relpath=relpath,
            line=text.count("\n", 0, m.start()) + 1)
        _parse_bind_chain(span, h.line, h)
        _parse_impl(text, h)
        out.append(h)
    return out


# ---------------------------------------------------------------------------
# Python side
# ---------------------------------------------------------------------------


def _chain(node: ast.AST) -> List[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return parts[::-1]


def _py_dtype(node: Optional[ast.AST]) -> Optional[str]:
    """Dtype name for jnp.float32 / np.int32 / jnp.dtype("f") / "f"."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return _PY_DTYPES.get(node.value)
    ch = _chain(node)
    if ch and ch[-1] in _PY_DTYPES:
        return _PY_DTYPES[ch[-1]]
    if isinstance(node, ast.Call):
        cch = _chain(node.func)
        if cch and cch[-1] == "dtype" and node.args:
            return _py_dtype(node.args[0])
        if cch and cch[-1] in _PY_DTYPES:  # jnp.int32(expr) cast
            return _PY_DTYPES[cch[-1]]
    return None


def _operand_dtype(node: ast.AST,
                   local: Dict[str, ast.AST], depth: int = 0
                   ) -> Optional[str]:
    """Best-effort static operand dtype: astype casts, jnp.<dtype>()
    constructors, and one level of local-name indirection."""
    if depth > 3:
        return None
    if isinstance(node, ast.Call):
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" and node.args:
            return _py_dtype(node.args[0])
        return _py_dtype(node)
    if isinstance(node, ast.Name) and node.id in local:
        return _operand_dtype(local[node.id], local, depth + 1)
    return None


def _ret_specs(node: ast.AST) -> Optional[List[Optional[str]]]:
    """Dtypes of the ShapeDtypeStruct result specs; None when the spec
    expression isn't statically recognizable."""
    elts = node.elts if isinstance(node, (ast.Tuple, ast.List)) else [node]
    out: List[Optional[str]] = []
    for e in elts:
        if isinstance(e, ast.Call):
            ch = _chain(e.func)
            if ch and ch[-1] == "ShapeDtypeStruct":
                dt = None
                if len(e.args) >= 2:
                    dt = _py_dtype(e.args[1])
                for kw in e.keywords:
                    if kw.arg == "dtype":
                        dt = _py_dtype(kw.value)
                out.append(dt)
                continue
        return None
    return out


def _resolve_targets(node: ast.AST, mod_tree: ast.Module) -> List[str]:
    """Target names an ffi_call's first arg can denote: a string constant,
    or a name assigned (anywhere in the module) a constant / conditional
    pair of constants."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if not isinstance(node, ast.Name):
        return []
    out: List[str] = []
    for n in ast.walk(mod_tree):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name) \
                and n.targets[0].id == node.id:
            v = n.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.append(v.value)
            elif isinstance(v, ast.IfExp):
                for e in (v.body, v.orelse):
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, str):
                        out.append(e.value)
    return out


def _walk_funcs(tree: ast.Module):
    """(qualname, func_node) pairs plus ("<module>", tree) last, with
    nested defs flattened as Outer.inner."""
    out: List[Tuple[str, ast.AST]] = []

    def rec(node: ast.AST, prefix: str) -> None:
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}.{ch.name}" if prefix else ch.name
                out.append((q, ch))
                rec(ch, q)
            elif isinstance(ch, ast.ClassDef):
                rec(ch, f"{prefix}.{ch.name}" if prefix else ch.name)
            else:
                rec(ch, prefix)

    rec(tree, "")
    out.append(("<module>", tree))
    return out


def _extract_python(modules) -> Tuple[List[_Registration], List[_CallSite]]:
    regs: List[_Registration] = []
    sites: List[_CallSite] = []
    for mod in modules:
        for qual, fn in _walk_funcs(mod.tree):
            local: Dict[str, ast.AST] = {}
            body_nodes = (list(ast.iter_child_nodes(fn))
                          if qual != "<module>" else list(fn.body))
            stack = list(body_nodes)
            calls: List[ast.Call] = []
            while stack:
                n = stack.pop()
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue  # nested defs get their own walk
                if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                        and isinstance(n.targets[0], ast.Name):
                    local[n.targets[0].id] = n.value
                if isinstance(n, ast.Call):
                    calls.append(n)
                stack.extend(ast.iter_child_nodes(n))
            for call in calls:
                ch = _chain(call.func)
                if not ch:
                    continue
                if ch[-1] == "register_ffi_target" and call.args:
                    tgt = call.args[0]
                    if not (isinstance(tgt, ast.Constant)
                            and isinstance(tgt.value, str)):
                        continue
                    sym = None
                    if len(call.args) >= 2 \
                            and isinstance(call.args[1], ast.Call) \
                            and call.args[1].args:
                        inner = call.args[1].args[0]
                        if isinstance(inner, ast.Attribute):
                            sym = inner.attr
                    if sym:
                        regs.append(_Registration(
                            target=tgt.value, symbol=sym,
                            relpath=mod.relpath, line=call.lineno,
                            func=qual))
                elif ch[-1] == "ffi_call" and len(call.args) >= 2:
                    targets = _resolve_targets(call.args[0], mod.tree)
                    if not targets:
                        continue
                    operands = call.args[2:]
                    sites.append(_CallSite(
                        targets=targets, relpath=mod.relpath,
                        line=call.lineno, func=qual,
                        n_args=len(operands),
                        arg_dtypes=[_operand_dtype(a, local)
                                    for a in operands],
                        attrs=[kw.arg for kw in call.keywords
                               if kw.arg and kw.arg not in _NON_ATTR_KW],
                        n_rets=(len(r) if (r := _ret_specs(call.args[1]))
                                is not None else None),
                        ret_dtypes=_ret_specs(call.args[1])))
    return regs, sites


# ---------------------------------------------------------------------------
# nm probe plumbing
# ---------------------------------------------------------------------------


def _so_symbols(so_path: str,
                cache: Dict[str, Optional[Set[str]]]) -> Optional[Set[str]]:
    if so_path in cache:
        return cache[so_path]
    syms: Optional[Set[str]] = None
    try:
        out = subprocess.run(
            ["nm", "-D", so_path], capture_output=True, timeout=30,
            check=True).stdout.decode(errors="replace")
        syms = {ln.split()[-1] for ln in out.splitlines() if ln.split()}
    except Exception:
        syms = None  # no nm / unreadable lib: the probe stays silent
    cache[so_path] = syms
    return syms


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def _dtype_mismatch(a: Optional[str], b: Optional[str]) -> bool:
    return (a is not None and b is not None
            and a != "any" and b != "any" and a != b)


def run_pass(cpp_files: Sequence[Tuple[str, str]], modules,
             compile_sites=None) -> List[Finding]:
    """The NB6xx pass. ``cpp_files`` is (abspath, relpath) pairs;
    ``modules`` the engine's collected ``_Module`` list;
    ``compile_sites`` the ``omp_lint.collect_compile_sites`` result
    (src->lib map for the nm probe), or None to skip the probe."""
    findings: List[Finding] = []
    handlers: Dict[str, CppHandler] = {}
    for path, rel in cpp_files:
        for h in parse_cpp_handlers(path, rel):
            handlers[h.symbol] = h

    # binder vs impl: one TU-internal contract check per handler
    for h in handlers.values():
        if h.impl_args is None:
            continue
        if len(h.impl_args) != len(h.args) or (
                h.impl_nattrs is not None
                and h.impl_nattrs != len(h.attrs)):
            findings.append(Finding(
                "NB601", h.relpath, h.impl_line or h.line, h.symbol,
                f"impl {h.impl} takes {len(h.impl_args)} buffers / "
                f"{h.impl_nattrs} attrs but the binder declares "
                f"{len(h.args)} / {len(h.attrs)}"))
        else:
            for i, (bi, ii) in enumerate(zip(h.args, h.impl_args)):
                if _dtype_mismatch(bi, ii):
                    findings.append(Finding(
                        "NB602", h.relpath, h.impl_line or h.line,
                        h.symbol,
                        f"impl {h.impl} arg {i} is {ii} but the binder "
                        f"declares {bi}"))
        if h.impl_rets is not None:
            if len(h.impl_rets) != len(h.rets):
                findings.append(Finding(
                    "NB603", h.relpath, h.impl_line or h.line, h.symbol,
                    f"impl {h.impl} returns {len(h.impl_rets)} buffers "
                    f"but the binder declares {len(h.rets)}"))
            else:
                for i, (bi, ii) in enumerate(zip(h.rets, h.impl_rets)):
                    if _dtype_mismatch(bi, ii):
                        findings.append(Finding(
                            "NB602", h.relpath, h.impl_line or h.line,
                            h.symbol,
                            f"impl {h.impl} result {i} is {ii} but the "
                            f"binder declares {bi}"))

    regs, sites = _extract_python(modules)
    reg_by_target = {r.target: r for r in regs}
    called: Set[str] = set()

    for site in sites:
        for tgt in site.targets:
            called.add(tgt)
            reg = reg_by_target.get(tgt)
            if reg is None:
                if regs:  # only when the scan set contains registrations
                    findings.append(Finding(
                        "NB604", site.relpath, site.line, site.func,
                        f"ffi_call target '{tgt}' is never registered "
                        f"(register_ffi_target) in the scanned sources"))
                continue
            h = handlers.get(reg.symbol)
            if h is None:
                if handlers:
                    findings.append(Finding(
                        "NB604", reg.relpath, reg.line, tgt,
                        f"registered symbol {reg.symbol} is not defined "
                        f"by any scanned native TU"))
                continue
            if site.n_args != len(h.args):
                findings.append(Finding(
                    "NB601", site.relpath, site.line, site.func,
                    f"'{tgt}' passes {site.n_args} operands but "
                    f"{h.symbol} ({h.relpath}) binds {len(h.args)}"))
            else:
                for i, (dt, hd) in enumerate(
                        zip(site.arg_dtypes, h.args)):
                    if _dtype_mismatch(dt, hd):
                        findings.append(Finding(
                            "NB602", site.relpath, site.line, site.func,
                            f"'{tgt}' operand {i} is {dt} but "
                            f"{h.symbol} binds ffi::Buffer<{hd}>"))
            want = {a for a, _ in h.attrs}
            got = set(site.attrs)
            if want != got:
                miss = sorted(want - got)
                extra = sorted(got - want)
                findings.append(Finding(
                    "NB601", site.relpath, site.line, site.func,
                    f"'{tgt}' attr set drifted from {h.symbol}: "
                    f"missing {miss or '[]'}, extra {extra or '[]'}"))
            if site.n_rets is not None:
                if site.n_rets != len(h.rets):
                    findings.append(Finding(
                        "NB603", site.relpath, site.line, site.func,
                        f"'{tgt}' declares {site.n_rets} results but "
                        f"{h.symbol} binds {len(h.rets)}"))
                elif site.ret_dtypes is not None:
                    for i, (dt, hd) in enumerate(
                            zip(site.ret_dtypes, h.rets)):
                        if _dtype_mismatch(dt, hd):
                            findings.append(Finding(
                                "NB602", site.relpath, site.line,
                                site.func,
                                f"'{tgt}' result {i} is {dt} but "
                                f"{h.symbol} binds ffi::Buffer<{hd}>"))

    if sites:
        for reg in regs:
            if reg.target not in called:
                findings.append(Finding(
                    "NB604", reg.relpath, reg.line, reg.target,
                    f"'{reg.target}' is registered but no scanned "
                    f"ffi_call site ever invokes it"))
    if regs:
        reg_syms = {r.symbol for r in regs}
        for h in handlers.values():
            if h.symbol not in reg_syms:
                findings.append(Finding(
                    "NB604", h.relpath, h.line, h.symbol,
                    f"handler {h.symbol} is defined but never "
                    f"registered with XLA"))

    # nm -D probe: a registered symbol must be exported by the lib its
    # TU builds into (src->lib pairing from the _compile call sites)
    if compile_sites:
        src_to_lib: Dict[str, str] = {}
        for cs in compile_sites:
            if cs.src_cpp and cs.lib_so:
                src_to_lib[cs.src_cpp] = cs.lib_so
        nm_cache: Dict[str, Optional[Set[str]]] = {}
        for reg in regs:
            h = handlers.get(reg.symbol)
            if h is None:
                continue
            lib = src_to_lib.get(os.path.basename(h.relpath))
            if lib is None:
                continue
            # the TU and its artifact live side by side in native/
            for path, rel in cpp_files:
                if rel == h.relpath:
                    so_path = os.path.join(os.path.dirname(path), lib)
                    if os.path.exists(so_path):
                        syms = _so_symbols(so_path, nm_cache)
                        if syms is not None and reg.symbol not in syms:
                            findings.append(Finding(
                                "NB604", reg.relpath, reg.line,
                                reg.target,
                                f"registered symbol {reg.symbol} is "
                                f"missing from {lib}'s dynamic symbol "
                                f"table (stale build?)"))
                    break
    return findings
