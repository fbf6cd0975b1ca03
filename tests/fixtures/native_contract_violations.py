"""Seeded cross-boundary violations — one per NB6xx/OMP704/DR8xx rule.

NEVER imported: this file is parsed by ``tests/test_lint.py`` and the CI
gate self-check alongside ``ffi_contract_fixture.cpp`` /
``omp_fixture.cpp`` to pin that every cross-boundary rule still fires.
Each violation is labeled with the rule id it seeds; the ``fixture_ok``
pair is fully consistent and pins the no-false-positive side."""

import os

import jax
import jax.numpy as jnp
from jax import ffi as jffi

_lib = None  # stands in for the dlopen'd fixture library

jffi.register_ffi_target(
    "fixture_ok", jffi.pycapsule(_lib.XgbtpuFixtureOk), platform="cpu")
jffi.register_ffi_target(
    "fixture_arity", jffi.pycapsule(_lib.XgbtpuFixtureArity),
    platform="cpu")
jffi.register_ffi_target(
    "fixture_dtype", jffi.pycapsule(_lib.XgbtpuFixtureDtype),
    platform="cpu")
jffi.register_ffi_target(
    "fixture_rets", jffi.pycapsule(_lib.XgbtpuFixtureRets),
    platform="cpu")
# NB604: registered here, but no ffi_call site below ever invokes it.
jffi.register_ffi_target(
    "fixture_orphan", jffi.pycapsule(_lib.XgbtpuFixtureOrphan),
    platform="cpu")


def call_ok(x):
    # consistent with XgbtpuFixtureOk (1 arg F32, attr n, 1 ret F32):
    # must produce NO finding.
    return jffi.ffi_call(
        "fixture_ok", jax.ShapeDtypeStruct(x.shape, jnp.float32),
        x, n=4)


def call_arity(x, y, z):
    # NB601: three operands against XgbtpuFixtureArity's two Args.
    return jffi.ffi_call(
        "fixture_arity", jax.ShapeDtypeStruct(x.shape, jnp.float32),
        x, y, z)


def call_dtype(x):
    # NB602: operand cast to int32 against an ffi::Buffer<ffi::F32> Arg.
    return jffi.ffi_call(
        "fixture_dtype", jax.ShapeDtypeStruct(x.shape, jnp.float32),
        x.astype(jnp.int32))


def call_rets(x):
    # NB603: one ShapeDtypeStruct against XgbtpuFixtureRets' two Rets.
    return jffi.ffi_call(
        "fixture_rets", jax.ShapeDtypeStruct(x.shape, jnp.float32),
        x)


def build_fixture_lib():
    # OMP704: the fixture TU is "compiled" without -ffp-contract=off.
    return _compile(  # noqa: F821 — parsed, never executed
        "omp_fixture.cpp", "libompfixture.so", ["-O3", "-march=native"])


def read_undocumented_env():
    # DR801: XGBTPU_* env read that no curated doc mentions.
    return os.environ.get("XGBTPU_FIXTURE_UNDOCUMENTED")


def register_undocumented_metric(registry):
    # DR802: metric registered but absent from the observability tables.
    return registry.counter(
        "lint_fixture_undocumented_total",
        "seeded drift-gate fixture metric")


# DR803: a dispatch op whose only impl prefers TPU — nothing resolves
# on the default CPU backend.
register(  # noqa: F821 — parsed, never executed
    "fixture_orphan_op", "pallas", pref=(("tpu", 0),))
