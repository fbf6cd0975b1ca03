"""Device time by the package's own phase names, host time by its own steps.

The package wraps each part of a boosting round in a ``jax.named_scope``
whose name starts ``xgb.`` (``xgb.gradient``, ``xgb.split_eval``, ...) and
opens its host spans as ``jax.profiler.TraceAnnotation("xgb." + name)``
(``xgb.scan_chunk``, ``xgb.chunk.prepare``, ...). ``jax.profiler.ProfileData``
shows neither scope nor source line of an op, but the profiler's file holds
them: every entry of a device plane's ``event_metadata`` carries a stat
``tf_op`` whose text is the HLO ``op_name``, the scope path of the op
(``jit(_scan_rounds_impl)/while/body/.../xgb.split_eval/jit(cumsum)/add:``).
This module reads the ``.xplane.pb`` file with a schema-less protobuf wire
reader (nothing but Python) and books each op's self time to a phase:

- *phase*: the last component of ``tf_op`` that starts ``xgb.``, the
  innermost scope; ``unscoped`` where there is none (a compiler-made
  ``copy``, a program recorded before the scopes). A fusion carries the
  ``op_name`` of one of its ops, so an op fused across a scope's edge is
  booked to one side. A container (``while``) comes without ``tf_op``: its
  own time, the loop's control between the body's ops, goes to the one
  phase the ops inside it carry (the 256-step scan of ``seq_cumsum`` is
  split evaluation's), and to ``unscoped`` where they carry several (the
  scan over the rounds).
- *kind*: Mosaic call, collective or the rest, as ``summary.kind_of`` says.
- per chip, inside ``bench.window``, then the mean over chips, as
  ``summary.summarize`` does. Per chip the cells add up to ``mosaic_s +
  xla_leaf_s + collective_exposed_s`` of ``summarize`` for the same trace.

``read(summary, record, cell)`` of a per-layer metric gets no path, so
``find_trace`` looks where ``harness.Context`` makes the profiler write:
``<tempfile.gettempdir()>/xgbtpu_bench_*/trace/``, the newest file that
holds a ``bench.window`` span. ``table`` trusts it only if its window is the
one ``summarize`` reduced.

Field numbers (``tsl/profiler/protobuf/xplane.proto``, checked on the
recorded file): ``XSpace.planes`` 1; ``XPlane.name`` 2, ``.lines`` 3,
``.event_metadata`` 4 and ``.stat_metadata`` 5 (map entries: key 1, value
2); ``XLine.name`` 2, ``.timestamp_ns`` 3, ``.events`` 4;
``XEvent.metadata_id`` 1, ``.offset_ps`` 2, ``.duration_ps`` 3;
``XEventMetadata.name`` 2, ``.stats`` 5; ``XStat.metadata_id`` 1,
``.str_value`` 5, ``.ref_value`` 7; ``XStatMetadata.name`` 2. Events join
their metadata on ``metadata_id``: one instruction text can belong to more
than one program.
"""

import functools
import glob
import os
import tempfile

from harness import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
summary = load_module(os.path.join(_HERE, "summary.py"))

SCOPE = "xgb."
WINDOW = "bench.window"
UNSCOPED = "unscoped"
CHUNK = "xgb.scan_chunk"
# the scopes a per-layer metric of its own reads; XLA time under any other
# scope is booked with the unscoped time, as what the naming misses
CLAIMED = ("xgb.gradient", "xgb.root", "xgb.split_eval", "xgb.partition",
           "xgb.finalize", "xgb.leaf_delta", "xgb.level_hist")


# ---------------------------------------------------------------------------
# the wire reader
# ---------------------------------------------------------------------------


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf) -> dict:
    """{"name", "lines": [(name, timestamp_ns, [event buffers])],
    "events": {metadata id: (name, {stat name: text})}} of one XPlane."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, value = _map_entry(v)
            event_meta[k] = value
        elif f == 5:
            k, value = _map_entry(v)
            stat_names[k] = next(
                (_text(x) for g, x in _fields(value) if g == 2), "")
    events = {}
    for k, value in event_meta.items():
        ev_name, stats = "", {}
        for f, v in _fields(value):
            if f == 2:
                ev_name = _text(v)
            elif f == 5:
                sid, text = 0, None
                for g, x in _fields(v):
                    if g == 1:
                        sid = x
                    elif g == 5:
                        text = _text(x)
                    elif g == 7:
                        text = stat_names.get(x, "")
                if text is not None:
                    stats[stat_names.get(sid, "")] = text
        events[k] = (ev_name, stats)
    out_lines = []
    for line in lines:
        line_name, t0, evs = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0 = v
            elif f == 4:
                evs.append(v)
        out_lines.append((line_name, t0, evs))
    return {"name": name, "lines": out_lines, "events": events}


def _events(t0: int, bufs):
    """(metadata id, start_ns, dur_ns) of a line's events, on the clock
    ``jax.profiler.ProfileData`` gives, which cuts the file's picoseconds
    to whole nanoseconds: the two reductions then read the same numbers."""
    for buf in bufs:
        mid = off = dur = 0
        for f, v in _fields(buf):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        yield mid, float(t0 + off // 1000), float(dur // 1000)


def planes(path: str) -> list:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v) for f, v in _fields(buf) if f == 1]


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict:
    """``{"devices": {plane: [(instruction text, tf_op or "", start_ns,
    dur_ns), ...]}, "host_spans": [(name, start_ns, dur_ns), ...]}``: the
    ``XLA Ops`` line of every chip, and the host spans that are the
    package's (``xgb.*``) or the benchmark's window."""
    devices: dict = {}
    host_spans: list = []
    for plane in planes(path):
        meta = plane["events"]
        if plane["name"].startswith("/device:TPU:"):
            ops = []
            for line_name, t0, bufs in plane["lines"]:
                if line_name != "XLA Ops":
                    continue
                for mid, start, dur in _events(t0, bufs):
                    text, stats = meta.get(mid, ("", {}))
                    ops.append((text, stats.get("tf_op", ""), start, dur))
            devices[plane["name"]] = ops
        elif plane["name"] == "/host:CPU":
            for _, t0, bufs in plane["lines"]:
                for mid, start, dur in _events(t0, bufs):
                    name = meta.get(mid, ("", {}))[0]
                    if name.startswith(SCOPE) or name == WINDOW:
                        host_spans.append((name, start, dur))
    return {"devices": devices, "host_spans": host_spans}


@functools.lru_cache(maxsize=1)
def find_trace() -> str | None:
    """The profiler's file of this run, or nothing: the newest
    ``.xplane.pb`` under a directory ``harness.Context`` made that holds a
    ``bench.window`` span. Memoised: every reader of a run asks."""
    files = glob.glob(os.path.join(
        tempfile.gettempdir(), "xgbtpu_bench_*", "trace", "plugins",
        "profile", "*", "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        try:
            if any(n == WINDOW for n, _, _ in load(path)["host_spans"]):
                return path
        except (OSError, ValueError, IndexError):
            continue
    return None


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def phase_of(tf_op: str) -> str:
    """The innermost ``xgb.`` scope of an op's path (primitives and inner
    ``jit(...)`` names follow it), else ``unscoped``."""
    for part in reversed(tf_op.split("/")):
        if part.startswith(SCOPE):
            return part.rstrip(":")
    return UNSCOPED


def window_of(trace: dict):
    """[lo, hi) of the ``bench.window`` spans; everything without one (the
    device events' own extent, as ``summarize`` takes it, drops no op)."""
    spans = [(s, s + d) for n, s, d in trace["host_spans"] if n == WINDOW]
    if not spans:
        return float("-inf"), float("inf")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(trace: dict) -> dict:
    """``{"window_s", "chips", "phases": {phase: {kind: seconds, mean
    chip}}, "per_chip": {plane: {phase: {kind: seconds}}}, "host": {span
    name: [count, seconds]}}`` of one loaded trace."""
    lo, hi = window_of(trace)
    short = summary.xplane._short
    per_chip: dict = {}
    for plane, ops in sorted(trace["devices"].items()):
        cells: dict = {}
        closed: list = []  # (start, phase) of ops no container has taken yet
        # self_times closes an op after the ops inside it
        for (text, tf_op), s, e, self_ns in summary.self_times(
                [((text, tf_op), start, dur) for text, tf_op, start, dur
                 in ops]):
            inside = set()
            while closed and closed[-1][0] >= s:
                inside.add(closed.pop()[1])
            phase = phase_of(tf_op)
            inside.discard(UNSCOPED)
            if phase == UNSCOPED and len(inside) == 1:
                phase, = inside  # a container of one phase's ops
            closed.append((s, phase))
            if e <= lo or s >= hi or self_ns <= 0:
                continue
            kinds = cells.setdefault(phase, {})
            kind = summary.kind_of(short(text))
            kinds[kind] = kinds.get(kind, 0.0) + self_ns / 1e9
        per_chip[plane] = cells
    n = max(len(per_chip), 1)
    phases: dict = {}
    for cells in per_chip.values():
        for phase, kinds in cells.items():
            mean = phases.setdefault(phase, {})
            for kind, sec in kinds.items():
                mean[kind] = mean.get(kind, 0.0) + sec / n
    host: dict = {}
    for name, s, d in trace["host_spans"]:
        if name != WINDOW and s >= lo and s + d <= hi:
            entry = host.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += d / 1e9
    return {"window_s": (hi - lo) / 1e9 if hi < float("inf") else 0.0,
            "chips": len(per_chip), "phases": phases, "per_chip": per_chip,
            "host": host}


@functools.lru_cache(maxsize=1)
def _reduced(path: str) -> dict:
    return reduce(load(path))


def table(run_summary) -> dict | None:
    """The phase table of this run's trace; nothing without a traced run,
    without a file, or where the file's window is not the one
    ``summarize`` reduced (another run's trace)."""
    if not run_summary:
        return None
    path = find_trace()
    if path is None:
        return None
    out = _reduced(path)
    want = run_summary.get("window_s", 0.0)
    if abs(out["window_s"] - want) > 1e-6 * max(want, 1e-9):
        return None
    return out


# ---------------------------------------------------------------------------
# what the per-layer readers call
# ---------------------------------------------------------------------------


def device_ms_per_round(run_summary, record, phase: str,
                        kinds=("xla",)) -> float | None:
    """Self time of ``kinds`` under ``phase`` per boosting round, mean chip;
    nothing where no op of the traced window carries the scope."""
    rounds = record.get("traced_rounds")
    out = table(run_summary) if rounds else None
    if not out or phase not in out["phases"]:
        return None
    cell = out["phases"][phase]
    return 1e3 * sum(cell.get(k, 0.0) for k in kinds) / rounds


def unclaimed_xla_ms_per_round(run_summary, record) -> float | None:
    """XLA self time no scope of ``CLAIMED`` holds, per round, mean chip."""
    rounds = record.get("traced_rounds")
    out = table(run_summary) if rounds else None
    if not out or not out["chips"]:
        return None
    return 1e3 * sum(kinds.get("xla", 0.0)
                     for phase, kinds in out["phases"].items()
                     if phase not in CLAIMED) / rounds


def host_ms_per_chunk(run_summary, names) -> float | None:
    """Mean host time of the spans ``names`` a traced chunk (a chunk is
    one ``xgb.scan_chunk`` span inside the window)."""
    out = table(run_summary)
    chunks = out["host"].get(CHUNK, [0])[0] if out else 0
    if not chunks or not any(n in out["host"] for n in names):
        return None
    return 1e3 * sum(out["host"].get(n, [0, 0.0])[1] for n in names) / chunks
