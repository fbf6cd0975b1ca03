"""AST lint engine: trace-safety, retrace-hygiene, dtype, concurrency.

Four passes over the package (no imports, pure ``ast`` — linting never
executes package code and runs in milliseconds):

**trace-safety (TS1xx)** — scope: functions reachable from a JAX tracing
entry point (``jax.jit`` / ``shard_map`` / ``pallas_call`` / ``vmap`` /
control-flow combinators) in the device-adjacent dirs (``tree/``,
``parallel/``, ``predictor/``, ``gbm/``). A lightweight interprocedural
taint analysis marks which names hold tracers (non-static parameters of
jit roots, values produced by ``jnp``/``lax`` ops, and anything derived
from them), then flags:

- TS101: host I/O at trace time (print / logging / span tracing / open) —
  fires once per *compile*, not per call, and on TPU stalls staging;
- TS102: host materialization of a tracer (``float()``/``int()``/
  ``bool()``/``.item()``/``.tolist()``/``np.*`` on a tainted value) —
  a ``ConcretizationTypeError`` at best, a silent constant-fold at worst;
- TS103: Python control flow (``if``/``while``/``assert``) on a tainted
  expression — tracer boolean coercion.

**retrace-hygiene (RH2xx)** — scope: whole package:

- RH201: a jit'd function taking a Python scalar or config-object
  parameter (scalar default, or config-ish name/annotation) not routed
  through ``static_argnums``/``static_argnames`` — every distinct value
  triggers a retrace (or, for unhashable configs, a TypeError);
- RH202: a traced function reading module-level *mutable* state (dict /
  list / set) — the value is baked in at trace time and silently stale
  after;
- RH203: ``jax.jit(...)`` created inside a function body — a fresh jit
  wrapper per call means a fresh compile cache per call (legitimate only
  when the caller owns an explicit program cache; baseline it there).

**dtype/precision (DT3xx)** — scope: device-adjacent dirs + ``data/``
(x64 is disabled on TPU; f64 crossing into jnp ops either downcasts
silently or — under ``jax_enable_x64`` — doubles every buffer):

- DT301: ``jnp.float64`` or ``dtype=float64`` passed to a jnp op;
- DT302: ``np.float64``/``np.double`` literals in device-adjacent code.

**concurrency (CC4xx)** — scope: whole package:

- CC401: a module-level mutable container (cache / registry / latch dict)
  mutated inside a function with no enclosing lock ``with``;
- CC402: a ``global`` scalar rebound inside a function with no enclosing
  lock (one-shot latches racing their check-then-set);
- CC405: direct kernel-backend selection (``use_pallas``-style probe
  calls, ``XGBTPU_NATIVE_*``/``XGBTPU_DEPTH_SCAN`` env reads) outside
  ``dispatch/`` — backend choice belongs to the dispatch registry.

Findings carry ``file:line`` + rule id + the enclosing symbol; the
baseline file (``baseline.py``) suppresses on (rule, file, symbol) so
entries survive unrelated line churn. See ``docs/static_analysis.md``.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "lint_paths", "run_lint", "ALL_RULES"]

ALL_RULES = {
    "TS101": "host I/O inside a traced function",
    "TS102": "host materialization of a tracer value",
    "TS103": "Python control flow on a tracer value",
    "RH201": "non-static scalar/config parameter on a jit'd function",
    "RH202": "traced function closes over module-level mutable state",
    "RH203": "jax.jit created inside a function body",
    "RH204": "host sync inside the round loop outside a blessed sync point",
    "DT301": "float64 dtype passed into a jnp op",
    "DT302": "np.float64 literal in device-adjacent code",
    "CC401": "module-level mutable state mutated outside a lock",
    "CC402": "global rebound outside a lock",
    "CC403": "module-level fallback latch outside resilience/degrade.py",
    "CC405": "direct kernel-backend selection outside dispatch/",
    "RS501": "direct collective call site outside collective.py",
    "RS502": "bare broad except swallow on the serving dispatch path",
    # cross-boundary families (ffi_contract.py / omp_lint.py / drift.py)
    "NB601": "FFI arity/attr-set drift between call site and handler",
    "NB602": "FFI buffer dtype mismatch across the native boundary",
    "NB603": "FFI result-count drift between call site and handler",
    "NB604": "FFI orphan: unregistered, uncalled, undefined, or missing "
             "from the built .so",
    "OMP701": "OpenMP float reduction reorders accumulation",
    "OMP702": "OpenMP atomic on a float accumulator",
    "OMP703": "parallel-for writes a shared float array off the "
              "induction variable",
    "OMP704": "native TU compiled without -ffp-contract=off",
    "DR801": "XGBTPU_* env var read in code but absent from the curated "
             "docs",
    "DR802": "registered metric name absent from the curated docs",
    "DR803": "dispatch op with no impl resolvable on CPU",
}

# RS501: every collective must route through the guarded entry point
# (``collective.guarded``/``process_allgather`` for host-side calls,
# ``collective.psum``/``all_gather`` for traced in-program ones) so that
# deadlines, retry classification and the elastic worker-loss signal
# apply uniformly — a stray ``lax.psum`` is a site that hangs or raises
# raw RuntimeError when a peer dies (same fencing pattern as CC403).
_RS501_NAMES = {"psum", "psum_scatter", "all_gather", "all_to_all",
                "pbroadcast", "ppermute", "pmean", "pmax", "pmin",
                "process_allgather", "broadcast_one_to_all",
                "sync_global_devices"}
_RS501_ROOTS = {"jax", "lax", "multihost_utils"}
_RS501_EXEMPT = "collective.py"

# RH204: the pipelined executor's contract (ISSUE 13) — the training
# round loop never blocks the host outside the blessed sync points
# (``pipeline.RoundPipeline``'s admit/drain, the eval/checkpoint/callback
# boundaries). A stray ``.block_until_ready()`` / ``np.asarray`` /
# ``float(<call>)`` inside the round-loop call graph silently serializes
# the pipeline: every round pays the device round-trip the async executor
# exists to overlap. The walk starts at the named round-loop roots,
# follows calls WITHIN the round-loop-owned modules (the eval/checkpoint/
# callback layers are themselves sync boundaries and are not entered),
# and skips ``pipeline.py`` — it IS the sync point. Justified syncs (the
# legacy host-prune path, custom-objective gradients) live in the
# baseline, not in code exemptions. Fixture/test roots: any function
# whose name starts with ``round_loop`` counts as a root.
_RH204_ROOTS = {
    ("training.py", "train"),
    ("learner.py", "Booster.update"),
    ("learner.py", "Booster.update_many"),
    ("learner.py", "Booster._update"),
    ("learner.py", "Booster._do_boost"),
    ("learner.py", "Booster.boost"),
}
_RH204_SCOPE_FILES = (
    "training.py", "learner.py", "gbm/gbtree.py", "tree/grow_fused.py",
    "tree/grow.py", "tree/hist_kernel.py", "pipeline.py",
)
_RH204_BLESSED_FILE = "pipeline.py"
_RH204_SYNC_METHODS = {"block_until_ready"}
_RH204_NP_MATERIALIZERS = {"asarray", "array"}

# RS502: a bare ``except Exception`` swallow on the serving dispatch
# path hides a failure from the resilience layer — it neither retries,
# bisects, trips the model's breaker, nor lands in
# faults_total/serving_faults_total, so a co-batched caller's error
# silently becomes a wrong or missing response. Failures under
# ``serving/`` must either re-raise or route through classification
# (``resilience.policy.classify``/``record_failure`` or
# ``serving.faults.record_serving_fault``); only ``serving/faults.py``
# (the isolation ladder itself) may catch broadly without that.
_RS502_SCOPE_DIR = "serving"
_RS502_EXEMPT = "serving/faults.py"
_RS502_BROAD = {"Exception", "BaseException"}
_RS502_CLASSIFIERS = {"classify", "record_failure", "record_serving_fault"}

# CC403: module-level names that read as fallback latches (broken/failed/
# blocked/... flags and blacklist dicts). Capability state belongs in the
# resilience layer (keyed, lock-guarded, metric-exported, retryable) —
# a fresh ad-hoc latch is exactly the unobservable one-off state ISSUE 5
# deleted. Only ``resilience/degrade.py`` (the state machine itself) may
# declare such names.
_CC403_WORDS = ("broken", "failed", "blocked", "latch", "disabled",
                     "blacklist", "poisoned")
_CC403_EXEMPT = "resilience/degrade.py"

# CC405: kernel-backend choice (pallas / XLA / native) belongs to the
# dispatch registry (``dispatch/``) — one table integrating pins, degrade
# state and platform preference. A `use_pallas()`-style branch or a
# direct read of a backend kill-switch env outside dispatch/ is a fresh
# scattered route the registry exists to delete (finishes the job CC403
# started for fallback latches). Blessed in-kernel residue — the platform
# probes that FEED the dispatch ctx — lives in the baseline, justified.
_CC405_ENV_PREFIX = "XGBTPU_NATIVE_"
_CC405_ENV_EXACT = ("XGBTPU_DEPTH_SCAN", "XGBTPU_DISPATCH")
_CC405_SELECTORS = ("use_pallas", "use_native_hist")
_CC405_EXEMPT_DIR = "dispatch"

# attribute (or bare imported) names that stage/trace their function args
_TRACE_ENTRIES = {
    "jit", "shard_map", "pallas_call", "vmap", "pmap", "scan", "fori_loop",
    "while_loop", "cond", "switch", "remat", "checkpoint", "grad",
    "value_and_grad", "custom_jvp", "custom_vjp", "guard_jit",
}
# entries whose static_argnums/static_argnames kwargs we understand
_JIT_LIKE = {"jit", "guard_jit"}

# module aliases whose calls produce traced values inside a traced fn
_TRACER_PRODUCER_ROOTS = {"jnp", "lax"}

_CONFIG_PARAM_NAMES = {"cfg", "config", "params", "opts", "options"}
_SCOPE_DIRS = ("tree", "parallel", "predictor", "gbm")
_DTYPE_SCOPE_DIRS = _SCOPE_DIRS + ("data",)

_MUTATOR_METHODS = {
    "append", "appendleft", "add", "insert", "extend", "update", "pop",
    "popitem", "clear", "setdefault", "remove", "discard", "move_to_end",
}
_HOST_IO_NAMES = {"print", "open", "breakpoint", "input"}
_HOST_IO_ATTR_BASES = {"logging", "warnings", "sys"}
_HOST_IO_ATTR_CALLS = {"span", "instant", "emit", "warn"}
_MATERIALIZERS = {"float", "int", "bool", "complex"}
_MATERIALIZER_METHODS = {"item", "tolist", "numpy"}


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative posix path
    line: int
    symbol: str  # enclosing function qualname, or <module>
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} [{self.symbol}] " \
               f"{self.message}"

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.symbol)


@dataclass
class _Func:
    qualname: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    module: "_Module"
    static_params: Set[str] = field(default_factory=set)
    traced: bool = False
    jit_root: bool = False  # wrapped by jit/guard_jit (decorator OR call)
    tainted_params: Set[str] = field(default_factory=set)

    @property
    def params(self) -> List[str]:
        a = self.node.args
        names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        return names

    @property
    def pos_params(self) -> List[str]:
        a = self.node.args
        return [p.arg for p in a.posonlyargs + a.args]


@dataclass
class _Module:
    path: str  # absolute
    relpath: str  # repo-relative posix
    modkey: str  # dotted module key, or relpath for external files
    tree: ast.Module
    in_package: bool
    # name -> (modkey, orig_name|None): from-imports and module imports
    imports: Dict[str, Tuple[str, Optional[str]]] = field(
        default_factory=dict)
    funcs: Dict[str, _Func] = field(default_factory=dict)  # qualname -> F
    mutable_globals: Set[str] = field(default_factory=set)
    scalar_globals: Set[str] = field(default_factory=set)

    def in_scope(self, dirs: Sequence[str]) -> bool:
        if not self.in_package:
            return True  # explicit external files are always in scope
        parts = self.relpath.split("/")
        return any(d in parts for d in dirs)


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """['jax', 'lax', 'psum'] for jax.lax.psum; None for non-name chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


def _is_mutable_ctor(node: ast.AST) -> bool:
    if isinstance(node, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                         ast.ListComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain and chain[-1] in (
                "dict", "list", "set", "OrderedDict", "defaultdict",
                "deque", "Counter"):
            return True
    return False


def _const_str_items(node: ast.AST) -> List[str]:
    """String elements of a tuple/list/lone-string literal."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _const_int_items(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


class _JitSpec:
    """A recognized tracing-entry application: which arg positions are
    functions, plus static-arg info for jit-like entries."""

    __slots__ = ("entry", "static_names", "static_nums")

    def __init__(self, entry: str, static_names: List[str],
                 static_nums: List[int]):
        self.entry = entry
        self.static_names = static_names
        self.static_nums = static_nums


def _trace_entry_spec(call_or_name: ast.AST) -> Optional[_JitSpec]:
    """Recognize a tracing-entry expression: ``jax.jit``,
    ``partial(jax.jit, static_argnames=...)``, ``guard_jit(name=...)``,
    ``pl.pallas_call`` etc. Returns the spec, or None."""
    node = call_or_name
    static_names: List[str] = []
    static_nums: List[int] = []
    # unwrap partial(jax.jit, **kw) / functools.partial(jax.jit, **kw)
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain and chain[-1] == "partial":
            inner = node.args[0] if node.args else None
            ichain = _attr_chain(inner) if inner is not None else None
            if ichain and ichain[-1] in _TRACE_ENTRIES:
                for kw in node.keywords:
                    if kw.arg == "static_argnames":
                        static_names += _const_str_items(kw.value)
                    elif kw.arg == "static_argnums":
                        static_nums += _const_int_items(kw.value)
                return _JitSpec(ichain[-1], static_names, static_nums)
            return None
        if chain and chain[-1] in _TRACE_ENTRIES:
            # direct call form: jax.jit(f, static_argnames=...) — caller
            # inspects args; or a decorator factory like guard_jit(...)
            for kw in node.keywords:
                if kw.arg == "static_argnames":
                    static_names += _const_str_items(kw.value)
                elif kw.arg == "static_argnums":
                    static_nums += _const_int_items(kw.value)
            return _JitSpec(chain[-1], static_names, static_nums)
        return None
    chain = _attr_chain(node)
    if chain and chain[-1] in _TRACE_ENTRIES:
        return _JitSpec(chain[-1], [], [])
    return None


def _fn_args_of_call(call: ast.Call) -> List[str]:
    """Names passed (directly or through one partial level) as function
    arguments to a tracing-entry call."""
    out: List[str] = []
    for a in call.args:
        if isinstance(a, ast.Name):
            out.append(a.id)
        elif isinstance(a, ast.Call):
            chain = _attr_chain(a.func)
            if chain and chain[-1] == "partial" and a.args \
                    and isinstance(a.args[0], ast.Name):
                out.append(a.args[0].id)
    return out


def _walk_skip_nested(node: ast.AST) -> Iterable[ast.AST]:
    """ast.walk that does not descend into nested function/lambda bodies
    (those are analyzed as their own symbols)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


# ---------------------------------------------------------------------------
# collection
# ---------------------------------------------------------------------------


def _package_parent() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))  # repo root


def iter_python_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        p = os.path.abspath(p)
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in sorted(dirs)
                           if d not in ("__pycache__", ".git")]
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
        elif p.endswith(".py"):
            out.append(p)
    return out


def iter_native_files(paths: Sequence[str]) -> List[str]:
    """C++ TUs under ``paths`` — the NB6xx/OMP7xx scan set."""
    out: List[str] = []
    for p in paths:
        p = os.path.abspath(p)
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in sorted(dirs)
                           if d not in ("__pycache__", ".git")]
                for f in sorted(files):
                    if f.endswith(".cpp"):
                        out.append(os.path.join(root, f))
        elif p.endswith(".cpp"):
            out.append(p)
    return out


def _native_relpath(path: str, pkg_root: str) -> str:
    """Repo-relative posix path for a TU, mirroring the module
    convention (package files anchor at the repo root, external ones at
    the cwd)."""
    root_parent = os.path.dirname(pkg_root)
    if pkg_root and os.path.commonpath([path, pkg_root]) == pkg_root:
        return os.path.relpath(path, root_parent).replace(os.sep, "/")
    rel = os.path.relpath(path, os.getcwd()).replace(os.sep, "/")
    return path.replace(os.sep, "/") if rel.startswith("..") else rel


def _collect_module(path: str, pkg_root: str) -> Optional[_Module]:
    try:
        with open(path, encoding="utf-8") as f:
            src = f.read()
        tree = ast.parse(src, filename=path)
    except (OSError, SyntaxError):
        return None
    root_parent = os.path.dirname(pkg_root)
    in_package = os.path.commonpath(
        [path, pkg_root]) == pkg_root if pkg_root else False
    if in_package:
        rel = os.path.relpath(path, root_parent).replace(os.sep, "/")
        modkey = rel[:-3].replace("/", ".")
        if modkey.endswith(".__init__"):
            modkey = modkey[: -len(".__init__")]
    else:
        rel = os.path.relpath(path, os.getcwd()).replace(os.sep, "/")
        if rel.startswith(".."):
            rel = path.replace(os.sep, "/")
        modkey = rel
    mod = _Module(path=path, relpath=rel, modkey=modkey, tree=tree,
                  in_package=in_package)
    _scan_imports(mod)
    _scan_globals(mod)
    _scan_functions(mod)
    return mod


def _scan_imports(mod: _Module) -> None:
    pkg_parts = mod.modkey.split(".")
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for al in node.names:
                mod.imports[al.asname or al.name.split(".")[0]] = (
                    al.name, None)
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative: resolve against this module
                base = pkg_parts[: len(pkg_parts) - node.level]
                src = ".".join(base + ([node.module] if node.module else []))
            else:
                src = node.module or ""
            for al in node.names:
                if al.name == "*":
                    continue
                mod.imports[al.asname or al.name] = (src, al.name)


def _scan_globals(mod: _Module) -> None:
    for node in mod.tree.body:
        targets: List[ast.expr] = []
        value = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        for t in targets:
            if isinstance(t, ast.Name):
                if _is_mutable_ctor(value):
                    mod.mutable_globals.add(t.id)
                else:
                    mod.scalar_globals.add(t.id)


def _scan_functions(mod: _Module) -> None:
    def visit(body: Iterable[ast.stmt], prefix: str) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{node.name}"
                mod.funcs[q] = _Func(qualname=q, node=node, module=mod)
                visit(node.body, f"{q}.")
            elif isinstance(node, ast.ClassDef):
                visit(node.body, f"{prefix}{node.name}.")
            elif isinstance(node, (ast.If, ast.Try, ast.With)):
                visit(node.body, prefix)
                for h in getattr(node, "handlers", []):
                    visit(h.body, prefix)
                visit(getattr(node, "orelse", []), prefix)
                visit(getattr(node, "finalbody", []), prefix)

    visit(mod.tree.body, "")


class _Project:
    def __init__(self, modules: List[_Module]):
        self.modules = modules
        self.by_key: Dict[str, _Module] = {m.modkey: m for m in modules}

    def resolve(self, mod: _Module, caller_q: str,
                name: str) -> Optional[_Func]:
        """Resolve a called name from ``caller_q``'s scope: enclosing
        nested defs, then module top-level, then from-imports."""
        parts = caller_q.split(".")
        for i in range(len(parts), 0, -1):
            q = ".".join(parts[:i] + [name])
            if q in mod.funcs:
                return mod.funcs[q]
        if name in mod.funcs:
            return mod.funcs[name]
        imp = mod.imports.get(name)
        if imp is not None:
            src, orig = imp
            target = self.by_key.get(src)
            if target is not None and orig is not None \
                    and orig in target.funcs:
                return target.funcs[orig]
        return None

    def resolve_attr(self, mod: _Module, base: str,
                     attr: str) -> Optional[_Func]:
        imp = mod.imports.get(base)
        if imp is not None and imp[1] is None:
            target = self.by_key.get(imp[0])
            if target is not None and attr in target.funcs:
                return target.funcs[attr]
        # `from . import x` style: (pkg, "x") pointing at a module
        if imp is not None and imp[1] is not None:
            target = self.by_key.get(f"{imp[0]}.{imp[1]}")
            if target is not None and attr in target.funcs:
                return target.funcs[attr]
        return None


# ---------------------------------------------------------------------------
# trace-root detection + interprocedural taint
# ---------------------------------------------------------------------------


def _statics_for(fn: _Func, spec: _JitSpec) -> Set[str]:
    names = set(spec.static_names)
    pos = fn.pos_params
    for i in spec.static_nums:
        if 0 <= i < len(pos):
            names.add(pos[i])
    return names


def _find_roots(project: _Project) -> List[_Func]:
    roots: List[_Func] = []
    for mod in project.modules:
        # decorator roots
        for fn in mod.funcs.values():
            for dec in getattr(fn.node, "decorator_list", []):
                spec = _trace_entry_spec(dec)
                if spec is not None:
                    fn.traced = True
                    if spec.entry in _JIT_LIKE:
                        fn.jit_root = True
                    fn.static_params |= _statics_for(fn, spec)
                    roots.append(fn)
        # call-site roots: jax.jit(f, ...), shard_map(f, ...), pallas_call,
        # and the applied-partial form partial(jax.jit, **kw)(f)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain and chain[-1] in _TRACE_ENTRIES:
                spec = _trace_entry_spec(node)  # kwargs live on the call
            elif isinstance(node.func, ast.Call):
                spec = _trace_entry_spec(node.func)
            else:
                continue
            if spec is None:
                continue
            for fname in _fn_args_of_call(node):
                fn = project.resolve(mod, "", fname) or mod.funcs.get(fname)
                if fn is None:
                    # nested function: search all quals ending in .fname
                    for q, cand in mod.funcs.items():
                        if q.split(".")[-1] == fname:
                            fn = cand
                            break
                if fn is not None:
                    fn.traced = True
                    if spec.entry in _JIT_LIKE:
                        fn.jit_root = True
                        fn.static_params |= _statics_for(fn, spec)
                    roots.append(fn)
    return roots


class _TaintVisitor(ast.NodeVisitor):
    """Single-function forward taint pass. Visits statements in order,
    twice (cheap loop fixpoint), tracking which local names hold tracers;
    records call sites with per-arg taint for interprocedural
    propagation."""

    def __init__(self, fn: _Func, project: _Project):
        self.fn = fn
        self.project = project
        self.taint: Set[str] = set(fn.tainted_params)
        self.calls: List[Tuple[ast.Call, List[bool], Dict[str, bool]]] = []

    # attributes of a tracer that are static Python values under jit
    _STATIC_ATTRS = ("shape", "dtype", "ndim", "size", "sharding")

    def expr_tainted(self, node: Optional[ast.AST]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.taint
        if isinstance(node, ast.Attribute) \
                and node.attr in self._STATIC_ATTRS:
            return False  # x.shape et al. are static even when x is traced
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain:
                if chain[0] in _TRACER_PRODUCER_ROOTS:
                    return True
                if chain[0] == "jax" and len(chain) > 1 \
                        and chain[1] in ("lax", "nn", "ops", "random"):
                    return True
                if chain == ["len"] or chain == ["range"]:
                    return False  # static under jit (shape-derived)
        return any(self.expr_tainted(c) for c in ast.iter_child_nodes(node))

    def _assign_names(self, target: ast.expr, tainted: bool) -> None:
        for sub in ast.walk(target):
            if isinstance(sub, ast.Name):
                if tainted:
                    self.taint.add(sub.id)
                else:
                    self.taint.discard(sub.id)

    def run(self) -> None:
        body = getattr(self.fn.node, "body", [])
        for _ in range(2):
            self.calls.clear()
            for stmt in body:
                self.visit(stmt)

    # -- statements -----------------------------------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)  # visit (not generic_visit): top-level
        t = self.expr_tainted(node.value)  # calls must reach visit_Call
        for tgt in node.targets:
            self._assign_names(tgt, t)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self.visit(node.value)
            self._assign_names(node.target, self.expr_tainted(node.value))

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.visit(node.value)
        if self.expr_tainted(node.value):
            self._assign_names(node.target, True)

    def visit_For(self, node: ast.For) -> None:
        self._assign_names(node.target, self.expr_tainted(node.iter))
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass  # nested defs analyzed separately (as their own _Func)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call) -> None:
        arg_taint = [self.expr_tainted(a) for a in node.args]
        kw_taint = {kw.arg: self.expr_tainted(kw.value)
                    for kw in node.keywords if kw.arg}
        self.calls.append((node, arg_taint, kw_taint))
        self.generic_visit(node)


def _propagate_taint(project: _Project, roots: List[_Func]) -> None:
    for fn in roots:
        fn.tainted_params = {
            p for p in fn.params
            if p not in fn.static_params and p != "self"
        }
    work = list(roots)
    seen_budget = 10000  # hard stop: the worklist is monotone, this is belt
    while work and seen_budget > 0:
        seen_budget -= 1
        fn = work.pop()
        tv = _TaintVisitor(fn, project)
        tv.run()
        for call, arg_taint, kw_taint in tv.calls:
            callee = _resolve_call(project, fn, call)
            if callee is None:
                continue
            changed = not callee.traced
            callee.traced = True
            pos = [p for p in callee.pos_params if p != "self"]
            new: Set[str] = set()
            for i, t in enumerate(arg_taint):
                if t and i < len(pos):
                    new.add(pos[i])
            for k, t in kw_taint.items():
                if t and k in callee.params:
                    new.add(k)
            new -= callee.static_params
            if not new <= callee.tainted_params:
                callee.tainted_params |= new
                changed = True
            if changed:
                work.append(callee)


def _resolve_call(project: _Project, fn: _Func,
                  call: ast.Call) -> Optional[_Func]:
    f = call.func
    if isinstance(f, ast.Name):
        return project.resolve(fn.module, fn.qualname, f.id)
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        base = f.value.id
        if base == "self":
            cls = fn.qualname.rsplit(".", 1)[0] if "." in fn.qualname else ""
            return fn.module.funcs.get(f"{cls}.{f.attr}") if cls else None
        return project.resolve_attr(fn.module, base, f.attr)
    return None


# ---------------------------------------------------------------------------
# passes
# ---------------------------------------------------------------------------


def _enclosing_lock(stack: List[ast.AST]) -> bool:
    """Whether any enclosing ``with`` in the statement stack acquires
    something lock-shaped (name contains 'lock', case-insensitive)."""
    for node in stack:
        if isinstance(node, ast.With):
            for item in node.items:
                chain = _attr_chain(item.context_expr)
                src = ".".join(chain) if chain else ast.dump(
                    item.context_expr)
                if "lock" in src.lower():
                    return True
    return False


class _StackWalker:
    """Walk a function body keeping the statement ancestor stack (for
    lock-scope checks)."""

    def __init__(self):
        self.hits: List[Tuple[ast.AST, List[ast.AST]]] = []

    def walk(self, node: ast.AST, match) -> List[Tuple[ast.AST, List[ast.AST]]]:
        out: List[Tuple[ast.AST, List[ast.AST]]] = []

        def rec(n: ast.AST, stack: List[ast.AST]) -> None:
            if match(n):
                out.append((n, list(stack)))
            for child in ast.iter_child_nodes(n):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue  # nested funcs checked as their own symbol
                rec(child, stack + [n])

        rec(node, [])
        return out


def _test_tainted(tv: "_TaintVisitor", test: ast.AST) -> bool:
    """Taint of a boolean-context test, with identity checks exempt:
    ``x is (not) None`` inspects the PYTHON value — static under tracing,
    idiomatic for optional array args — even when ``x`` holds a tracer.
    Recurses through and/or/not so ``flag and x is not None`` stays
    clean."""
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return False
    if isinstance(test, ast.BoolOp):
        return any(_test_tainted(tv, v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _test_tainted(tv, test.operand)
    return tv.expr_tainted(test)


def _pass_trace_safety(project: _Project) -> List[Finding]:
    out: List[Finding] = []
    for mod in project.modules:
        if not mod.in_scope(_SCOPE_DIRS):
            continue
        for fn in mod.funcs.values():
            if not fn.traced:
                continue
            tv = _TaintVisitor(fn, project)
            tv.run()
            for call, arg_taint, kw_taint in tv.calls:
                chain = _attr_chain(call.func)
                line = call.lineno
                # TS101: host I/O
                if chain is not None:
                    if chain[0] in _HOST_IO_NAMES and len(chain) == 1:
                        out.append(Finding(
                            "TS101", mod.relpath, line, fn.qualname,
                            f"host call '{chain[0]}()' runs at trace time "
                            f"(once per compile), not per execution"))
                        continue
                    if (chain[0] in _HOST_IO_ATTR_BASES
                            or "logger" in chain[0].lower()
                            or (len(chain) > 1
                                and chain[-1] in _HOST_IO_ATTR_CALLS)):
                        out.append(Finding(
                            "TS101", mod.relpath, line, fn.qualname,
                            f"host I/O '{'.'.join(chain)}' inside a traced "
                            f"function: fires at trace time and is absent "
                            f"from the compiled program"))
                        continue
                any_taint = any(arg_taint) or any(kw_taint.values())
                if not any_taint or chain is None:
                    continue
                # TS102: materialization
                if len(chain) == 1 and chain[0] in _MATERIALIZERS:
                    out.append(Finding(
                        "TS102", mod.relpath, line, fn.qualname,
                        f"'{chain[0]}()' on a traced value: concretization "
                        f"error (or silent constant-fold at trace time)"))
                elif chain[-1] in _MATERIALIZER_METHODS:
                    out.append(Finding(
                        "TS102", mod.relpath, line, fn.qualname,
                        f"'.{chain[-1]}()' on a traced value forces a "
                        f"host sync inside the traced region"))
                elif chain[0] == "np":
                    out.append(Finding(
                        "TS102", mod.relpath, line, fn.qualname,
                        f"numpy op 'np.{'.'.join(chain[1:])}' applied to a "
                        f"traced value: host round-trip breaks the trace"))
            # TS103: control flow on tainted exprs
            sw = _StackWalker()
            for node, _stack in sw.walk(
                    fn.node, lambda n: isinstance(
                        n, (ast.If, ast.While, ast.Assert, ast.IfExp))):
                if _test_tainted(tv, node.test):
                    kind = type(node).__name__.lower()
                    out.append(Finding(
                        "TS103", mod.relpath, node.lineno, fn.qualname,
                        f"python '{kind}' on a traced value: tracer "
                        f"boolean coercion (use lax.cond/jnp.where)"))
    return out


def _pass_retrace_hygiene(project: _Project) -> List[Finding]:
    out: List[Finding] = []
    for mod in project.modules:
        for fn in mod.funcs.values():
            node = fn.node
            # RH201: jit roots with unstatic scalar/config params —
            # decorator AND call-site forms (g = jax.jit(f) included);
            # vmap/scan/shard_map roots are exempt: their params really
            # are arrays
            if fn.jit_root:
                defaults = _param_defaults(node)
                for p in fn.params:
                    if p in fn.static_params or p == "self":
                        continue
                    d = defaults.get(p)
                    if isinstance(d, ast.Constant) and isinstance(
                            d.value, (int, float, bool, str)) \
                            and d.value is not None:
                        out.append(Finding(
                            "RH201", mod.relpath, node.lineno, fn.qualname,
                            f"jit parameter '{p}' has a Python scalar "
                            f"default but is not in static_argnames: every "
                            f"distinct value retraces"))
                    elif p in _CONFIG_PARAM_NAMES:
                        out.append(Finding(
                            "RH201", mod.relpath, node.lineno, fn.qualname,
                            f"jit parameter '{p}' looks like a config "
                            f"object but is not static: unhashable configs "
                            f"fail, hashable ones retrace per instance"))
            # RH202: traced fn reading module-level mutable state
            if fn.traced:
                local = set(fn.params)
                for sub in _walk_skip_nested(node):
                    if isinstance(sub, ast.Name) \
                            and isinstance(sub.ctx, ast.Load) \
                            and sub.id in mod.mutable_globals \
                            and sub.id not in local \
                            and sub.id != "__all__":
                        out.append(Finding(
                            "RH202", mod.relpath, sub.lineno, fn.qualname,
                            f"traced function reads module-level mutable "
                            f"'{sub.id}': its value is baked in at trace "
                            f"time and goes silently stale"))
                        break  # one per function is enough signal
            # RH203: jax.jit(...) constructed inside a function body
            for sub in _walk_skip_nested(node):
                if isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func)
                    if chain and chain[-1] == "jit" \
                            and chain[0] in ("jax",):
                        out.append(Finding(
                            "RH203", mod.relpath, sub.lineno, fn.qualname,
                            "jax.jit(...) created inside a function body: "
                            "a fresh compile cache per call (cache the "
                            "wrapper, or baseline if a program cache owns "
                            "it)"))
    return out


def _param_defaults(node: ast.AST) -> Dict[str, ast.expr]:
    a = node.args
    out: Dict[str, ast.expr] = {}
    pos = a.posonlyargs + a.args
    for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        out[p.arg] = d
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if d is not None:
            out[p.arg] = d
    return out


def _pass_dtype(project: _Project) -> List[Finding]:
    out: List[Finding] = []
    for mod in project.modules:
        if not mod.in_scope(_DTYPE_SCOPE_DIRS):
            continue
        symbols = _symbol_index(mod)
        for node in ast.walk(mod.tree):
            chain = _attr_chain(node) if isinstance(
                node, ast.Attribute) else None
            if chain == ["jnp", "float64"]:
                out.append(Finding(
                    "DT301", mod.relpath, node.lineno,
                    symbols.get(node.lineno, "<module>"),
                    "jnp.float64: x64 is disabled on TPU — this silently "
                    "downcasts (or doubles every buffer under x64)"))
            elif chain in (["np", "float64"], ["np", "double"],
                           ["numpy", "float64"]):
                out.append(Finding(
                    "DT302", mod.relpath, node.lineno,
                    symbols.get(node.lineno, "<module>"),
                    "np.float64 in device-adjacent code: f64 crossing "
                    "into jnp ops promotes or silently downcasts"))
            elif isinstance(node, ast.Call):
                fchain = _attr_chain(node.func)
                if fchain and fchain[0] == "jnp":
                    for kw in node.keywords:
                        if kw.arg == "dtype" and isinstance(
                                kw.value, ast.Constant) \
                                and kw.value.value in ("float64", "double"):
                            out.append(Finding(
                                "DT301", mod.relpath, node.lineno,
                                symbols.get(node.lineno, "<module>"),
                                "dtype='float64' passed to a jnp op"))
    return out


def _symbol_index(mod: _Module) -> Dict[int, str]:
    """line -> enclosing function qualname (coarse: by line ranges)."""
    idx: Dict[int, str] = {}
    for q, fn in mod.funcs.items():
        end = getattr(fn.node, "end_lineno", fn.node.lineno)
        for ln in range(fn.node.lineno, end + 1):
            # innermost wins: later (nested) defs overwrite in range
            if ln not in idx or len(q) > len(idx[ln]):
                idx[ln] = q
    return idx


def _pass_concurrency(project: _Project) -> List[Finding]:
    out: List[Finding] = []
    sw = _StackWalker()
    for mod in project.modules:
        if not mod.mutable_globals and not mod.scalar_globals:
            continue
        for fn in mod.funcs.values():
            node = fn.node
            global_decls: Set[str] = set()
            for sub in _walk_skip_nested(node):
                if isinstance(sub, ast.Global):
                    global_decls.update(sub.names)
            shadowed = set(fn.params)

            def is_mutation(n: ast.AST) -> bool:
                # X[k] = v / del X[k] / X[k] += v
                if isinstance(n, (ast.Assign, ast.AugAssign)):
                    tgts = n.targets if isinstance(n, ast.Assign) else [
                        n.target]
                    for t in tgts:
                        if isinstance(t, ast.Subscript) and isinstance(
                                t.value, ast.Name) \
                                and t.value.id in mod.mutable_globals \
                                and t.value.id not in shadowed:
                            return True
                        # global scalar rebind: X = ...
                        if isinstance(t, ast.Name) \
                                and t.id in global_decls:
                            return True
                if isinstance(n, ast.Delete):
                    for t in n.targets:
                        if isinstance(t, ast.Subscript) and isinstance(
                                t.value, ast.Name) \
                                and t.value.id in mod.mutable_globals:
                            return True
                # X.append(...) etc.
                if isinstance(n, ast.Call) and isinstance(
                        n.func, ast.Attribute) \
                        and n.func.attr in _MUTATOR_METHODS \
                        and isinstance(n.func.value, ast.Name) \
                        and n.func.value.id in mod.mutable_globals \
                        and n.func.value.id not in shadowed:
                    return True
                return False

            for hit, stack in sw.walk(node, is_mutation):
                if _enclosing_lock(stack + [hit]):
                    continue
                if isinstance(hit, (ast.Assign, ast.AugAssign)) and all(
                        isinstance(t, ast.Name) for t in (
                            hit.targets if isinstance(hit, ast.Assign)
                            else [hit.target])):
                    names = [t.id for t in (
                        hit.targets if isinstance(hit, ast.Assign)
                        else [hit.target])]
                    out.append(Finding(
                        "CC402", mod.relpath, hit.lineno, fn.qualname,
                        f"global {'/'.join(names)} rebound outside a lock: "
                        f"check-then-set races across threads"))
                else:
                    out.append(Finding(
                        "CC401", mod.relpath, hit.lineno, fn.qualname,
                        "module-level mutable state mutated outside a "
                        "lock: concurrent callers corrupt it"))

    # CC403: latch-shaped module-level declarations outside the resilience
    # state machine (name-based — the point is to force new fallback state
    # through degrade.CapabilityHealth, not to prove raciness)
    for mod in project.modules:
        if mod.relpath.endswith(_CC403_EXEMPT):
            continue
        for node in mod.tree.body:
            targets: List[ast.expr] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign):
                targets = [node.target]
            for t in targets:
                if not isinstance(t, ast.Name):
                    continue
                name = t.id.lower()
                if any(w in name for w in _CC403_WORDS):
                    out.append(Finding(
                        "CC403", mod.relpath, node.lineno, t.id,
                        f"module-level fallback latch {t.id!r}: use a "
                        "resilience/degrade.py capability (keyed, "
                        "lock-guarded, metric-exported) instead"))
    return out


def _cc405_env_key(node: ast.AST) -> Optional[str]:
    """The constant env-var name read by ``os.environ.get(K)`` /
    ``os.getenv(K)`` / ``environ.get(K)`` / ``os.environ[K]``, or None."""
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if not chain:
            return None
        env_get = (chain[-1] == "get" and len(chain) >= 2
                   and chain[-2] == "environ") or chain[-1] == "getenv"
        if env_get and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            return node.args[0].value
    if isinstance(node, ast.Subscript):
        chain = _attr_chain(node.value)
        if chain and chain[-1] == "environ" \
                and isinstance(node.slice, ast.Constant) \
                and isinstance(node.slice.value, str):
            return node.slice.value
    return None


def _pass_dispatch_fences(project: _Project) -> List[Finding]:
    """CC405: backend kill-switch env reads and ``use_pallas``-style
    selector calls outside ``dispatch/``. Both fire on the concrete
    artifact (the env key / the probe name), not on vague if/else shapes,
    so the rule stays precise; the justified probe residue that feeds the
    dispatch ctx is baselined, never code-exempted."""
    out: List[Finding] = []
    for mod in project.modules:
        if mod.in_package and mod.in_scope((_CC405_EXEMPT_DIR,)):
            continue
        symbols = _symbol_index(mod)
        for node in ast.walk(mod.tree):
            key = _cc405_env_key(node)
            if key is not None and (key.startswith(_CC405_ENV_PREFIX)
                                    or key in _CC405_ENV_EXACT):
                out.append(Finding(
                    "CC405", mod.relpath, node.lineno,
                    symbols.get(node.lineno, "<module>"),
                    f"backend kill-switch env {key!r} read outside "
                    f"dispatch/: the legacy envs map to dispatch pins in "
                    f"ONE shim (dispatch/core.py LEGACY_ENVS) — resolve "
                    f"the op through the registry instead (docs/perf.md, "
                    f"'Choosing a kernel')"))
                continue
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if chain and chain[-1] in _CC405_SELECTORS:
                    out.append(Finding(
                        "CC405", mod.relpath, node.lineno,
                        symbols.get(node.lineno, "<module>"),
                        f"direct backend probe '{chain[-1]}()' outside "
                        f"dispatch/: pick the impl via dispatch.resolve "
                        f"(probes that only FEED the dispatch ctx are "
                        f"blessed residue — baseline them with a "
                        f"justification)"))
    return out


def _pass_collectives(project: _Project) -> List[Finding]:
    """RS501: direct ``lax.psum``/``all_gather``/``process_allgather``/...
    call sites anywhere but ``collective.py`` (the guarded entry point).
    Matched on the attribute chain, so wrapper calls
    (``collective.psum``) never fire and shape ops that merely contain
    the words (``broadcast_to``, ``broadcasted_iota``) never fire."""
    out: List[Finding] = []
    for mod in project.modules:
        if mod.in_package and mod.relpath.endswith(
                "xgboost_tpu/" + _RS501_EXEMPT):
            continue
        symbols = _symbol_index(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if not chain or chain[-1] not in _RS501_NAMES:
                continue
            if chain[0] not in _RS501_ROOTS:
                continue
            out.append(Finding(
                "RS501", mod.relpath, node.lineno,
                symbols.get(node.lineno, "<module>"),
                f"direct collective '{'.'.join(chain)}' outside "
                f"collective.py: route host-side calls through "
                f"collective.guarded/process_allgather and traced ones "
                f"through collective.psum/all_gather, so deadlines, "
                f"retry classification and the elastic worker-loss "
                f"signal apply"))
    return out


def _rh204_is_sync(node: ast.Call) -> Optional[str]:
    """Why ``node`` is a host sync (message fragment), or None."""
    chain = _attr_chain(node.func)
    if chain and chain[-1] in _RH204_SYNC_METHODS:
        return f"'.{chain[-1]}()'"
    if chain and len(chain) >= 2 and chain[0] in ("np", "numpy") \
            and chain[-1] in _RH204_NP_MATERIALIZERS:
        return f"'{'.'.join(chain)}(...)'"
    if isinstance(node.func, ast.Name) and node.func.id in ("float", "int") \
            and node.args and isinstance(node.args[0], ast.Call):
        return f"'{node.func.id}(<call>)'"
    return None


def _pass_round_loop_sync(project: _Project) -> List[Finding]:
    """RH204: walk the round-loop call graph from the named roots (calls
    resolved within the round-loop-owned modules only; eval/checkpoint/
    callback layers are sync boundaries by contract) and flag host-sync
    expressions outside ``pipeline.py``."""
    out: List[Finding] = []
    in_scope = {}
    for mod in project.modules:
        if mod.in_package and any(
                mod.relpath.endswith("xgboost_tpu/" + s)
                for s in _RH204_SCOPE_FILES):
            in_scope[id(mod)] = mod
    roots: List[_Func] = []
    for mod in project.modules:
        for qn, fn in mod.funcs.items():
            if qn.split(".")[-1].startswith("round_loop"):
                roots.append(fn)  # fixture/test convention
            for suffix, root_qn in _RH204_ROOTS:
                if mod.relpath.endswith("xgboost_tpu/" + suffix) \
                        and qn == root_qn:
                    roots.append(fn)
    seen: Set[int] = set()
    work = list(roots)
    while work:
        fn = work.pop()
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        blessed = fn.module.relpath.endswith(
            "xgboost_tpu/" + _RH204_BLESSED_FILE)
        symbols = _symbol_index(fn.module)
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            why = None if blessed else _rh204_is_sync(node)
            if why is not None:
                out.append(Finding(
                    "RH204", fn.module.relpath, node.lineno,
                    symbols.get(node.lineno, fn.qualname),
                    f"host sync {why} inside the round-loop call graph: "
                    f"the pipelined executor (XGBTPU_PIPELINE_DEPTH) "
                    f"only overlaps rounds the host does not block on — "
                    f"sync at the blessed points (pipeline.drain, eval/"
                    f"checkpoint boundaries) or add a justified baseline "
                    f"entry"))
            callee = _resolve_call(project, fn, node)
            if callee is not None and id(callee.module) in in_scope:
                work.append(callee)
    return out


def _pass_serving_excepts(project: _Project) -> List[Finding]:
    """RS502: ``except Exception``/``except BaseException`` handlers under
    ``serving/`` (outside ``serving/faults.py``) that neither re-raise nor
    route the failure through the resilience classification entry points.
    A handler is clean if its body contains any ``raise`` or a call whose
    attribute chain ends in ``classify``/``record_failure``/
    ``record_serving_fault``."""
    out: List[Finding] = []
    for mod in project.modules:
        if not mod.in_scope((_RS502_SCOPE_DIR,)):
            continue
        if mod.relpath.endswith(_RS502_EXEMPT):
            continue
        symbols = _symbol_index(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            caught = node.type
            names: List[str] = []
            for t in (caught.elts if isinstance(caught, ast.Tuple)
                      else [caught]) if caught is not None else []:
                chain = _attr_chain(t)
                if chain:
                    names.append(chain[-1])
            if not any(n in _RS502_BROAD for n in names):
                continue
            handled = False
            for sub in ast.walk(ast.Module(body=node.body,
                                           type_ignores=[])):
                if isinstance(sub, ast.Raise):
                    handled = True
                    break
                if isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func)
                    if chain and chain[-1] in _RS502_CLASSIFIERS:
                        handled = True
                        break
            if handled:
                continue
            out.append(Finding(
                "RS502", mod.relpath, node.lineno,
                symbols.get(node.lineno, "<module>"),
                "broad except swallow on the serving dispatch path: "
                "re-raise, or classify via resilience.policy / "
                "serving.faults.record_serving_fault so retries, "
                "bisection and breakers see the failure"))
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def lint_paths(paths: Optional[Sequence[str]] = None,
               rules: Optional[Set[str]] = None) -> List[Finding]:
    """Run every pass over ``paths`` (default: the installed package) and
    return all findings, unfiltered by any baseline."""
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    if not paths:
        paths = [pkg_root]
    files = iter_python_files(paths)
    modules = [m for m in (
        _collect_module(f, pkg_root) for f in files) if m is not None]
    project = _Project(modules)
    roots = _find_roots(project)
    _propagate_taint(project, roots)
    findings: List[Finding] = []
    findings += _pass_trace_safety(project)
    findings += _pass_retrace_hygiene(project)
    findings += _pass_dtype(project)
    findings += _pass_concurrency(project)
    findings += _pass_dispatch_fences(project)
    findings += _pass_collectives(project)
    findings += _pass_round_loop_sync(project)
    findings += _pass_serving_excepts(project)
    # cross-boundary passes (lazy imports keep the pure-AST fast path
    # free of them when a --rules subset never asks)
    from . import drift, ffi_contract, omp_lint

    cpp = [(f, _native_relpath(f, pkg_root))
           for f in iter_native_files(paths)]
    compile_sites = omp_lint.collect_compile_sites(modules)
    findings += ffi_contract.run_pass(cpp, modules, compile_sites)
    findings += omp_lint.run_pass(cpp, modules, compile_sites)
    findings += drift.run_pass(modules, pkg_root)
    if rules:
        findings = [f for f in findings if f.rule in rules]
    # dedupe (two detection routes can hit the same node)
    seen: Set[Tuple] = set()
    uniq: List[Finding] = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        k = (f.rule, f.path, f.line, f.symbol)
        if k not in seen:
            seen.add(k)
            uniq.append(f)
    return uniq


def run_lint(paths: Optional[Sequence[str]] = None,
             baseline: Optional[Dict[Tuple[str, str, str], str]] = None,
             rules: Optional[Set[str]] = None):
    """Lint + baseline filter. Returns (new_findings, suppressed,
    stale_baseline_keys)."""
    findings = lint_paths(paths, rules)
    baseline = baseline or {}
    new: List[Finding] = []
    suppressed: List[Finding] = []
    matched: Set[Tuple[str, str, str]] = set()
    for f in findings:
        if f.key() in baseline:
            matched.add(f.key())
            suppressed.append(f)
        else:
            new.append(f)
    stale = [k for k in baseline if k not in matched]
    return new, suppressed, stale
