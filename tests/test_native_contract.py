"""Cross-boundary contract analyzer (ISSUE 18 acceptance scenarios):
corrupting one ffi::Buffer dtype in a fixture TU yields exactly one
NB6xx finding, a seeded float reduction yields exactly one OMP7xx
finding, and the nm -D probe catches a registered symbol missing from
its built .so."""

import os
import shutil
import subprocess
import textwrap

import pytest

from xgboost_tpu.analysis import ffi_contract, omp_lint
from xgboost_tpu.analysis.lint import _collect_module, lint_paths

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE_DIR = os.path.join(HERE, "fixtures")


def test_corrupt_impl_buffer_dtype_yields_exactly_one_nb602(tmp_path):
    """Flip ONE ffi::Buffer element type in the consistent handler's
    impl: the TU-internal binder-vs-impl check reports exactly one NB602
    and nothing else (the other fixture handlers stay self-consistent,
    and with no Python stub in scope the orphan directions stay off)."""
    src = os.path.join(FIXTURE_DIR, "ffi_contract_fixture.cpp")
    with open(src) as f:
        text = f.read()
    needle = "ffi::Error FixtureOkImpl(ffi::Buffer<ffi::F32> x"
    assert needle in text, "fixture drifted: consistent impl not found"
    corrupted = str(tmp_path / "corrupted.cpp")
    with open(corrupted, "w") as f:
        f.write(text.replace(
            needle, "ffi::Error FixtureOkImpl(ffi::Buffer<ffi::S32> x"))
    findings = lint_paths([corrupted])
    assert len(findings) == 1, [f.render() for f in findings]
    assert findings[0].rule == "NB602"
    assert "FixtureOkImpl" in findings[0].message
    assert "int32" in findings[0].message
    assert "float32" in findings[0].message


def test_seeded_float_reduction_yields_exactly_one_omp701(tmp_path):
    tu = str(tmp_path / "red.cpp")
    with open(tu, "w") as f:
        f.write(textwrap.dedent("""
            float total(const float* v, long n) {
                float acc = 0.0f;
            #pragma omp parallel for reduction(+:acc)
                for (long i = 0; i < n; ++i) acc += v[i];
                return acc;
            }
        """))
    findings = lint_paths([tu])
    assert len(findings) == 1, [f.render() for f in findings]
    assert findings[0].rule == "OMP701"
    assert findings[0].symbol == "acc"


def test_int_reduction_and_indexed_writes_stay_silent(tmp_path):
    """The determinism lint is about FLOAT accumulation order: integer
    reductions and induction-indexed float writes are fine."""
    tu = str(tmp_path / "clean.cpp")
    with open(tu, "w") as f:
        f.write(textwrap.dedent("""
            long count(const int* v, long n, float* out) {
                long c = 0;
            #pragma omp parallel for reduction(+:c)
                for (long i = 0; i < n; ++i) {
                    c += v[i];
                    out[i] = (float)v[i];
                }
                return c;
            }
        """))
    assert lint_paths([tu]) == []


def _have_tool(*cmd) -> bool:
    try:
        subprocess.run(list(cmd), capture_output=True, timeout=30,
                       check=True)
        return True
    except Exception:
        return False


def test_nm_probe_flags_symbol_missing_from_so(tmp_path):
    """A registered+defined+called symbol whose TU's build artifact does
    NOT export it (stale .so) is an NB604 from the nm -D probe."""
    if not _have_tool("g++", "--version") or not _have_tool("nm", "-V"):
        pytest.skip("g++/nm unavailable")
    # a consistent handler pair in probe.cpp ...
    cpp = str(tmp_path / "probe.cpp")
    with open(cpp, "w") as f:
        f.write(textwrap.dedent("""
            ffi::Error ProbeImpl(ffi::Buffer<ffi::F32> x,
                                 ffi::Result<ffi::Buffer<ffi::F32>> out);
            XLA_FFI_DEFINE_HANDLER_SYMBOL(
                XgbtpuProbe, ProbeImpl,
                ffi::Ffi::Bind()
                    .Arg<ffi::Buffer<ffi::F32>>()
                    .Ret<ffi::Buffer<ffi::F32>>());
        """))
    # ... a consistent registration + call site ...
    py = str(tmp_path / "probe_use.py")
    with open(py, "w") as f:
        f.write(textwrap.dedent("""
            import jax
            import jax.numpy as jnp
            from jax import ffi as jffi

            _lib = None

            jffi.register_ffi_target(
                "probe_t", jffi.pycapsule(_lib.XgbtpuProbe),
                platform="cpu")


            def call(x):
                return jffi.ffi_call(
                    "probe_t",
                    jax.ShapeDtypeStruct(x.shape, jnp.float32), x)
        """))
    # ... but the lib the TU claims to build into exports something else
    stale = str(tmp_path / "stale.cpp")
    with open(stale, "w") as f:
        f.write('extern "C" void unrelated_export() {}\n')
    so = str(tmp_path / "libprobe.so")
    subprocess.run(["g++", "-shared", "-fPIC", "-o", so, stale],
                   check=True, capture_output=True, timeout=120)

    mod = _collect_module(py, os.path.join(os.path.dirname(HERE),
                                           "xgboost_tpu"))
    assert mod is not None
    sites = [omp_lint.CompileSite(
        relpath="probe_use.py", line=1, func="build",
        src_cpp="probe.cpp", lib_so="libprobe.so",
        flags=["-ffp-contract=off"])]
    findings = ffi_contract.run_pass([(cpp, "probe.cpp")], [mod], sites)
    nb604 = [f for f in findings if f.rule == "NB604"]
    assert len(nb604) == 1, [f.render() for f in findings]
    assert "missing from libprobe.so" in nb604[0].message
    # control: with the symbol actually exported, the probe stays silent
    fixed = str(tmp_path / "fixed.cpp")
    with open(fixed, "w") as f:
        f.write('extern "C" void XgbtpuProbe() {}\n')
    subprocess.run(["g++", "-shared", "-fPIC", "-o", so, fixed],
                   check=True, capture_output=True, timeout=120)
    findings = ffi_contract.run_pass([(cpp, "probe.cpp")], [mod], sites)
    assert [f for f in findings if f.rule == "NB604"] == []


def test_package_cross_boundary_families_clean():
    """The repo itself passes NB6xx/OMP7xx/DR8xx with zero findings (no
    baseline entries were spent on the new families)."""
    findings = lint_paths(None, rules={
        "NB601", "NB602", "NB603", "NB604",
        "OMP701", "OMP702", "OMP703", "OMP704",
        "DR801", "DR802", "DR803"})
    assert findings == [], "\n".join(f.render() for f in findings)


def test_ffi_parser_reads_real_tree_kernel_contract():
    """The parser extracts the real whole-tree kernel's signature (a
    canary: if tree_build.cpp's binder changes shape, this pins that the
    checker SEES it rather than silently parsing nothing)."""
    native_dir = os.path.join(os.path.dirname(HERE),
                              "xgboost_tpu", "native")
    tu = os.path.join(native_dir, "tree_build.cpp")
    handlers = {h.symbol: h for h in ffi_contract.parse_cpp_handlers(
        tu, "xgboost_tpu/native/tree_build.cpp")}
    assert "XgbtpuTreeGrow" in handlers
    h = handlers["XgbtpuTreeGrow"]
    assert len(h.args) >= 5 and len(h.rets) >= 2 and h.attrs
    assert h.impl_args is not None, "impl signature not found"
    assert len(h.impl_args) == len(h.args)
    assert len(h.impl_rets) == len(h.rets)
