#!/usr/bin/env python3
"""Record ``data/v5e_small_scoped.xplane.pb.gz`` and its phase table on one
v5e chip (no test: the recipe of a fixture, kept so that it can be made
again after the scopes move).

    chiprun -- python tests/benchmark/record_scoped_trace.py

8,192 x 12, bin256, depth 3, as ``v5e_small`` was recorded (PR 22), python
tracer off as the runner sets it. Everything is compiled and run once
before the profiler starts. Inside ``bench.window``: the resident one-hot
of a second ``DMatrix`` (``xgb.onehot_build``), a prediction of 1,024 rows
the ``Booster`` has no cache for (``xgb.predict_walk``), and one chunk of
two rounds (every phase of the round). Writes the profiler's file, gzipped,
and the table ``reduce/phases.py`` reduces it to under
``chiprun_out/scoped/``; copy both into ``tests/benchmark/data/``. The file
is the profiler's own but for the ``/host:metadata`` plane (the programs'
HLO protos, half the bytes, read by nothing here), which is left out.
"""

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))
from bench_paths import BENCH, REPO, harness, load  # noqa: E402

ROWS, HOLDOUT, COLS, DEPTH, ROUNDS = 8192, 1024, 12, 3, 2
PARAMS = {"objective": "binary:logistic", "tree_method": "tpu_hist",
          "max_depth": DEPTH, "eta": 0.3, "max_bin": 256, "seed": 1}


def phase_table(path: str) -> dict:
    """What the test holds the recording to: seconds by phase and kind on
    the chip, and the host spans' counts and seconds."""
    phases = load("reduce/phases.py")
    out = phases.reduce(phases.load(path))
    return {"window_s": out["window_s"], "phases": out["phases"],
            "host": out["host"]}


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def without_plane(raw: bytes, drop: str) -> bytes:
    """The ``XSpace`` ``raw`` with the plane named ``drop`` left out."""
    phases = load("reduce/phases.py")
    out = bytearray()
    for field, value in phases._fields(memoryview(raw)):
        if isinstance(value, int):
            out += _varint(field << 3) + _varint(value)
            continue
        name = next((phases._text(v) for f, v in phases._fields(value)
                     if f == 2), "") if field == 1 else ""
        if name != drop:
            out += _varint(field << 3 | 2) + _varint(len(value)) \
                + bytes(value)
    return bytes(out)


def main() -> int:
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.config import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record: {dev.platform} is not a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    gen = harness.load_module(os.path.join(BENCH, "generators",
                                           "linear_logit.py"))
    X, y = gen.generate(rows=ROWS + HOLDOUT, cols=COLS, seed=1)
    Xtr, ytr, Xh = X[:ROWS], y[:ROWS], X[ROWS:]

    def fresh():
        d = xgb.DMatrix(Xtr, label=ytr)
        jax.block_until_ready(d.get_binned(PARAMS["max_bin"]).bins)
        return d

    dtrain, dspare = fresh(), fresh()
    bst = xgb.Booster(PARAMS, [dtrain])
    # every program once, outside the trace
    jax.block_until_ready(
        fresh().get_binned(PARAMS["max_bin"]).fused_onehot(DEPTH))
    for start in (0, ROUNDS):
        bst.update_many(dtrain, start, ROUNDS, chunk=ROUNDS)
        bst.predict(dtrain, output_margin=True)
    bst.predict(xgb.DMatrix(Xh), output_margin=True)

    tmp = tempfile.mkdtemp(prefix="scoped_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    dhold = xgb.DMatrix(Xh)
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        # the device's events came 0.9 ms before the host span that started
        # them in this recording's first take: keep the first op inside
        time.sleep(0.005)
        jax.block_until_ready(
            dspare.get_binned(PARAMS["max_bin"]).fused_onehot(DEPTH))
        # before the chunk: the forest as the warm-up left it, so the walk
        # runs the program it compiled there
        bst.predict(dhold, output_margin=True)
        with jax.profiler.TraceAnnotation("bench.update_many"):
            bst.update_many(dtrain, 2 * ROUNDS, ROUNDS, chunk=ROUNDS)
        with jax.profiler.TraceAnnotation("bench.drain"):
            bst.predict(dtrain, output_margin=True)
    jax.profiler.stop_trace()

    path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    with open(path, "rb") as f:
        kept = without_plane(f.read(), "/host:metadata")
    path = os.path.join(tmp, "v5e_small_scoped.xplane.pb")
    with open(path, "wb") as f:
        f.write(kept)
    out = os.path.join(REPO, "chiprun_out", "scoped")
    os.makedirs(out, exist_ok=True)
    with gzip.open(os.path.join(out, "v5e_small_scoped.xplane.pb.gz"),
                   "wb", 9) as f:
        f.write(kept)
    table = phase_table(path)
    with open(os.path.join(out, "v5e_small_scoped.phases.json"), "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    print(json.dumps(table, indent=1, sort_keys=True))
    print(f"raw {os.path.getsize(path)} bytes, gzipped "
          f"{os.path.getsize(os.path.join(out, 'v5e_small_scoped.xplane.pb.gz'))}"
          f" bytes, device {dev.device_kind}")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
