"""The benchmark's runner: one cell, one run, one JSON line.

Everything that belongs to one configuration, one traffic mix, one traffic
kind, one data generator or one per-layer metric sits in a file of its own,
found here by name (``configs/<name>.json``, ``workloads/<name>.json``,
``traffic/<mix>.json`` and ``traffic/<kind>.py``, ``generators/<name>.py``,
``layer_metrics/<metric>.py``). This module holds what they share: loading
those files, the device checks, the guard that fails a run on a degraded
capability or a fall-back warning, counters, the profiler window and the
last line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
MANIFEST = os.path.join(CHECKOUT, "BENCHMARK.json")


class BenchFailure(Exception):
    """The run cannot give a result: wrong device, a degraded capability, a
    fall-back warning, a missing file. The process exits non-zero and
    prints no result line."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file of the benchmark by path (no package needed, so a
    later PR adds a file and nothing else)."""
    if not os.path.isfile(path):
        raise BenchFailure(f"no such benchmark file: {path}")
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_manifest() -> dict:
    return load_json(MANIFEST)


def load_cell(root: str, name: str) -> dict:
    """The cell ``name`` under ``root`` (``benchmark/`` for the real cells,
    ``benchmark/rehearsal/`` for the CPU stand-ins): its own file, its
    configuration's and its traffic mix's."""
    path = os.path.join(root, "workloads", f"{name}.json")
    if not os.path.isfile(path):
        raise BenchFailure(f"unknown workload {name!r}: no {path}")
    cell = load_json(path)
    cell["name"] = name
    cell["config_doc"] = load_json(
        os.path.join(root, "configs", f"{cell['config']}.json"))
    cell["mix"] = load_json(
        os.path.join(root, "traffic", f"{cell['traffic']}.json"))
    return cell


def cell_metrics(manifest: dict, cell_name: str, section: str) -> list:
    """Metric entries of ``section`` that the cell reports: those that list
    it under ``workloads``, and those that list no cells at all."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


# ---------------------------------------------------------------------------
# the run context handed to a traffic kind
# ---------------------------------------------------------------------------


class Context:
    """What a traffic kind (``traffic/<kind>.py`` ``run(ctx)``) gets."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 t_process_start: float, rehearsal: bool) -> None:
        self.cell = cell
        self.config = cell["config_doc"]
        self.mix = cell["mix"]
        self.chips = int(cell["chips"])
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_process_start = t_process_start
        self.tag = "[CPU REHEARSAL - not a chip result] " if rehearsal else ""
        self.warnings: list = []
        self.trace_dir: str | None = None
        self.setup_s: float | None = None
        self._tmp = tempfile.TemporaryDirectory(prefix="xgbtpu_bench_")
        self.tmpdir = self._tmp.name

    def say(self, msg: str) -> None:
        print(f"{self.tag}# {msg}", flush=True)

    def generator(self):
        gen = self.config["data"]["generator"]
        return load_module(os.path.join(HERE, "generators", f"{gen}.py"))

    def make_data(self):
        """(X, y) of ``rows_train + rows_holdout`` rows from ``--seed``."""
        d = self.config["data"]
        rows = int(d["rows_train"]) + int(d["rows_holdout"])
        return self.generator().generate(
            rows=rows, cols=int(d["cols"]), seed=self.seed,
            **d.get("generator_params", {}))

    def window_starts(self) -> None:
        """Set-up ends here: everything from process start counts, less the
        accelerator runtime's own start-up (``run_cell``)."""
        self.setup_s = time.perf_counter() - self.t_process_start

    # -- profiler ----------------------------------------------------------
    def start_trace(self) -> None:
        import jax

        self.trace_dir = os.path.join(self.tmpdir, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # a million host events a chunk otherwise
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()

    @staticmethod
    def span(name: str):
        """A host span on the profiler's clock (no-op when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def close(self) -> None:
        self._tmp.cleanup()


# ---------------------------------------------------------------------------
# what the program reports about itself: warnings, health, routes, counters
# ---------------------------------------------------------------------------


def hook_warnings(sink: list) -> None:
    """Record every ``console_logger.warning``: the fall-backs on the hot
    path (one-hot build -> construct, Pallas walk -> XLA walk, native
    containment) each log one before carrying on."""
    from xgboost_tpu.utils import console_logger

    orig = console_logger.warning

    def recording(*args):
        sink.append(" ".join(str(a) for a in args))
        orig(*args)

    console_logger.warning = recording


def check_health(ctx: Context, stage: str) -> None:
    from xgboost_tpu.resilience import degrade

    bad = {k: v["worst"] for k, v in degrade.snapshot().items()
           if v["worst"] != "healthy"}
    if bad:
        raise BenchFailure(f"{stage}: degraded capabilities {bad}")
    if ctx.warnings:
        raise BenchFailure(f"{stage}: fall-back warning(s) logged: "
                           f"{ctx.warnings[:3]}")


def routes() -> dict:
    """{op: {impl: count}} from ``dispatch_decisions_total``: printed, never
    compared with an expectation (a later PR may lawfully move a route)."""
    from xgboost_tpu.observability import REGISTRY

    out: dict = {}
    fam = REGISTRY.get("dispatch_decisions_total")
    if fam is not None:
        for labels, child in fam.series():
            impls = out.setdefault(labels["op"], {})
            impls[labels["impl"]] = impls.get(labels["impl"], 0) \
                + int(child.value)
    return out


def compile_count() -> int:
    """Traces counted by the program's retrace guard plus programs built by
    the serving bucket cache: what a compile inside the window shows as."""
    from xgboost_tpu.analysis.retrace import retrace_counts
    from xgboost_tpu.observability import REGISTRY

    total = sum(retrace_counts().values())
    fam = REGISTRY.get("predict_bucket_cache_misses_total")
    if fam is not None:
        total += sum(int(child.value) for _, child in fam.series())
    return int(total)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``; 0 where the backend
    reports no memory statistics (the CPU of a rehearsal)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _rehearsal_stand_ins(ctx: Context) -> None:
    """The CPU stand-ins of a rehearsal: Pallas kernel bodies interpreted,
    the Pallas routes forced on, the hoist budget from the environment (no
    ``memory_stats`` off the chip). Under a mesh the interpreter cannot
    replay a kernel inside ``shard_map``, so a four-device rehearsal takes
    the XLA level route (as ``chip_smoke.py --rehearse`` does)."""
    from xgboost_tpu import predictor
    from xgboost_tpu.tree import hist_kernel as hk

    hk._INTERPRET = True
    predictor._INTERPRET = True
    hk.use_pallas = (lambda: False) if ctx.chips > 1 else (lambda: True)
    os.environ.setdefault("XGBTPU_HOIST_BUDGET_MB", "64")


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             t_process_start: float, rehearsal: bool = False,
             keep_trace: str | None = None) -> int:
    """Run ``cell`` once and print the result line. Returns the exit code."""
    import jax

    # the accelerator runtime's own start-up, before anything of the package
    # runs: 5-15 s on the chip, swinging by seconds from run to run, and
    # nothing a change to this repository can move. It is taken out of
    # ``setup_s`` and printed beside it.
    t0 = time.perf_counter()
    devices = jax.devices()
    runtime_start_s = time.perf_counter() - t0
    try:
        import xgboost_tpu  # noqa: F401  (the system under test)
        from xgboost_tpu.config import enable_compile_cache
    except ImportError as e:
        print(f"benchmark: the system under test is not importable here: {e}",
              file=sys.stderr)
        return 3

    ctx = Context(cell, seed, seconds, trace,
                  t_process_start + runtime_start_s, rehearsal)
    try:
        dev = devices[0]
        if rehearsal:
            if dev.platform != "cpu":
                raise BenchFailure("a rehearsal runs with JAX_PLATFORMS=cpu")
            _rehearsal_stand_ins(ctx)
        elif dev.platform != "tpu":
            raise BenchFailure(
                f"JAX found platform {dev.platform!r} ({dev.device_kind}), "
                "not a TPU: the cells have no path off the chip (the CPU "
                "stand-ins are benchmark/rehearsal/rehearse.py)")
        if len(devices) < ctx.chips:
            raise BenchFailure(f"cell {cell['name']} needs {ctx.chips} "
                               f"chip(s), JAX found {len(devices)}")
        used = devices[:ctx.chips]
        # every program, however quick to compile, is found again by the
        # next run of this checkout
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        ctx.say(f"cell {cell['name']}  seed {seed}  seconds {seconds}  "
                f"trace {int(trace)}  device {dev.platform}/"
                f"{dev.device_kind} x{len(devices)} (using {ctx.chips})  "
                f"runtime start-up {runtime_start_s:.2f}s (not in setup_s)  "
                f"compile cache {enable_compile_cache() or 'off (CPU)'}")
        hook_warnings(ctx.warnings)

        kind = load_module(os.path.join(HERE, "traffic",
                                        f"{ctx.mix['kind']}.py"))
        result = kind.run(ctx)
        check_health(ctx, "end of run")
        ctx.say("routes: " + json.dumps(routes(), sort_keys=True))

        record = result["record"]
        record["memory_peak_bytes"] = memory_peak_bytes(used)
        record["device_kind"] = dev.device_kind
        record["chips"] = ctx.chips
        record["runtime_start_s"] = runtime_start_s
        end_to_end = dict(result["end_to_end"], setup_s=ctx.setup_s)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": record["memory_peak_bytes"]}
        manifest = load_manifest()
        units = {m["name"]: m["unit"]
                 for sec in ("end_to_end", "per_layer")
                 for m in manifest[sec]}
        in_manifest = any(w["name"] == cell["name"]
                          for w in manifest["workloads"])

        def wanted(section: str) -> list:
            if in_manifest:
                return [m["name"]
                        for m in cell_metrics(manifest, cell["name"], section)]
            if section == "end_to_end":  # a stand-in: whatever it measured
                return list(end_to_end)
            return sorted(f[:-3] for f in os.listdir(
                os.path.join(HERE, "layer_metrics")) if f.endswith(".py"))

        ctx.say("end_to_end: " + json.dumps(end_to_end, sort_keys=True))
        out = {"correct": bool(result["correct"]),
               "attempted": int(result["attempted"]),
               "failed": int(result["failed"])}
        if not trace:
            metrics = {k: end_to_end[k] for k in wanted("end_to_end")
                       if end_to_end.get(k) is not None}
        else:
            from_trace = load_module(os.path.join(HERE, "reduce",
                                                  "summary.py"))
            summary = from_trace.summarize_dir(ctx.trace_dir, keep=keep_trace)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            out["breakdown"] = summary["breakdown"]
            metrics = {}
            for name in wanted("per_layer"):
                reader = load_module(os.path.join(HERE, "layer_metrics",
                                                  f"{name}.py"))
                value = reader.read(summary, record, cell)
                if value is not None:
                    metrics[name] = value
            ctx.say("trace summary: " + json.dumps(
                {k: v for k, v in summary.items() if k != "breakdown"},
                sort_keys=True))
        ctx.say("record: " + json.dumps(record, sort_keys=True, default=str))
        out["metrics"] = {k: {"value": float(v),
                              "unit": units.get(k, "unlisted")}
                          for k, v in metrics.items()}
        out["device"] = device
        if rehearsal:
            out["rehearsal"] = "CPU stand-in: no number here is a chip result"
        # every number that decided ``correct`` beside its limit: the last
        # lines of standard error, and the last key of the result line (the
        # driver's record of a run that is not correct keeps the end of each)
        out["compared"] = result.get("compared", {})
        for name, c in out["compared"].items():
            print(f"{ctx.tag}compared {name}: " + "  ".join(
                f"{k} {v}" for k, v in c.items()), file=sys.stderr)
        print(f"{ctx.tag}correct: {out['correct']}", file=sys.stderr,
              flush=True)
        print(ctx.tag + json.dumps(out), flush=True)
        return 0
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        if keep_trace and ctx.trace_dir and os.path.isdir(ctx.trace_dir):
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copytree(ctx.trace_dir,
                            os.path.join(keep_trace, cell["name"]),
                            dirs_exist_ok=True)
        ctx.close()
