"""Which way the arrows point (ISSUE 28).

The hot path (``tree/``, ``gbm/``, ``parallel/``, ``objective/``, ``data/``,
``predictor/``, ``metric/``, ``dispatch/``, ``learner.py``, ``training.py``,
``pipeline.py``) may import from ``observability`` the names below and no
other, from ``analysis`` only ``retrace``, nothing from ``serving`` and
nothing from a script at the repository's root. Read by ``ast``, imports
inside functions included, one case a module: a profiler, a report or a
benchmark wired into a grower fails the case of the module it was wired
into."""

import ast
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "xgboost_tpu"

HOT_DIRS = ("tree", "gbm", "parallel", "objective", "data", "predictor",
            "metric", "dispatch")
HOT_FILES = ("learner.py", "training.py", "pipeline.py")

#: what the hot path uses of ``observability`` (as PR 27 left it, less the
#: mirror grower): the span emitter, the collective-bytes counters, the
#: flight recorder and the metrics registry. A name under one of the three
#: modules counts as that module.
OBSERVABILITY = {"trace", "comms", "flight", "REGISTRY", "metrics.REGISTRY"}
OBSERVABILITY_MODULES = ("trace", "comms", "flight")
ANALYSIS_MODULES = ("retrace",)
ROOT_SCRIPTS = {"bench", "chip_smoke", "benchmark", "__graft_entry__"}


def hot_modules():
    out = []
    for d in HOT_DIRS:
        for f in sorted(os.listdir(os.path.join(REPO, PKG, d))):
            if f.endswith(".py"):
                out.append(f"{PKG}/{d}/{f}")
    return out + [f"{PKG}/{f}" for f in HOT_FILES]


def imported(rel: str, source: str):
    """Every dotted name ``rel`` imports, relative ones made absolute."""
    parts = rel[:-3].split("/")
    package = parts[:-1]  # an ``__init__`` is its directory's module
    for node in ast.walk(ast.parse(source, rel)):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - (node.level - 1)] \
                if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            for a in node.names:
                yield f"{mod}.{a.name}", node.lineno


def _under(name: str, modules) -> bool:
    return any(name == m or name.startswith(m + ".") for m in modules)


def violations(rel: str, source: str):
    out = []
    for name, line in imported(rel, source):
        what = None
        if name.split(".")[0] in ROOT_SCRIPTS:
            what = "a script of the repository's root"
        elif _under(name, (f"{PKG}.serving",)):
            what = "serving"
        elif _under(name, (f"{PKG}.observability",)):
            rest = name[len(f"{PKG}.observability") + 1:]
            if rest not in OBSERVABILITY and \
                    not _under(rest, OBSERVABILITY_MODULES):
                what = "observability, off the allow-list"
        elif _under(name, (f"{PKG}.analysis",)):
            rest = name[len(f"{PKG}.analysis") + 1:]
            if not _under(rest, ANALYSIS_MODULES):
                what = "analysis, other than retrace"
        if what:
            out.append(f"{rel}:{line}: imports {name} ({what})")
    return out


@pytest.mark.parametrize("rel", hot_modules())
def test_hot_path_module_imports_only_what_the_layering_allows(rel):
    with open(os.path.join(REPO, rel)) as f:
        found = violations(rel, f.read())
    assert not found, "\n".join(found)


def test_the_hot_path_is_about_45_modules():
    # a directory renamed away would empty the cases above in silence
    mods = hot_modules()
    assert 40 <= len(mods) <= 60, len(mods)
    for must in ("gbm/gbtree.py", "tree/grow_fused.py", "parallel/grow.py",
                 "dispatch/__init__.py", "training.py"):
        assert f"{PKG}/{must}" in mods


@pytest.mark.parametrize("line, why", [
    ("from ..observability import fleet as _fleet", "allow-list"),
    ("from ..observability.report import summarize", "allow-list"),
    ("from ..observability import metrics", "allow-list"),
    ("from .. import observability", "allow-list"),
    ("from ..analysis.cli import main", "other than retrace"),
    ("from ..serving.server import ModelServer", "serving"),
    ("import xgboost_tpu.serving", "serving"),
    ("import bench", "root"),
    ("import chip_smoke", "root"),
    ("from benchmark.generators import linear_logit", "root"),
])
def test_a_forbidden_arrow_is_caught_wherever_it_is_written(line, why):
    # at module level and inside a function of the same file
    for src in (line + "\n", f"def grow_one():\n    {line}\n    return 1\n"):
        found = violations(f"{PKG}/gbm/gbtree.py", src)
        assert len(found) == 1 and why in found[0], (src, found)


@pytest.mark.parametrize("line", [
    "from ..observability import REGISTRY as _REGISTRY, trace as _trace",
    "from ..observability import comms, flight",
    "from ..observability.metrics import REGISTRY",
    "from ..observability.trace import span",
    "from ..analysis.retrace import guard_jit, note_retrace",
    # not held here: the fault handling and the native boundary
    "from ..resilience import degrade\nfrom ..native import boundary",
])
def test_the_arrows_the_hot_path_has_pass(line):
    assert violations(f"{PKG}/gbm/gbtree.py", line + "\n") == []


# ---------------------------------------------------------------------------
# one way to measure: no script banks its own record at the root
# ---------------------------------------------------------------------------

_BANK = re.compile(r"(?:BENCH|MULTICHIP)_[^\s/]*\.json")


def _strings(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node.value
        elif isinstance(node, ast.JoinedStr):  # an f-string's fixed parts
            yield "{}".join(v.value for v in node.values
                            if isinstance(v, ast.Constant)
                            and isinstance(v.value, str))


def test_no_python_file_outside_the_benchmark_names_a_bank_file():
    # BENCH_r*.json and MULTICHIP_r*.json were the records of the scripts
    # ISSUE 28 deleted; the record of a chip run is the driver's
    # PERF_LEDGER.jsonl. No *.py outside benchmark/ holds such a file name,
    # in a constant or in the fixed parts of an f-string (a comment is no
    # string: this one is not read).
    hits = []
    for dirpath, dirnames, filenames in os.walk(REPO):
        rel_dir = os.path.relpath(dirpath, REPO)
        dirnames[:] = [d for d in dirnames
                       if not d.startswith(".") and d != "__pycache__"
                       and d != "chiprun_out"
                       and not (rel_dir == "." and d == "benchmark")]
        for f in filenames:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            with open(path) as fh:
                tree = ast.parse(fh.read(), path)
            hits += [f"{os.path.relpath(path, REPO)}: {s[:80]!r}"
                     for s in _strings(tree) if _BANK.search(s)]
    assert not hits, "\n".join(hits)
