"""Where the benchmark lives, for its self-tests (no package: files are
loaded by path, as the runner loads them)."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402


def load(rel: str):
    return harness.load_module(os.path.join(BENCH, rel))
