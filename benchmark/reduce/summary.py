"""From the event table of one traced window to what the metrics read.

One reduction for every cell, kept with the benchmark so that no later PR
computes a device number another way:

- *window*: the ``bench.window`` host span (the traffic kind opens it round
  the traced part of its measured window); without one, the extent of the
  device events.
- *busy*: the union of the ``XLA Modules`` intervals of a chip inside the
  window. ``device_idle_pct`` is 1 - busy/window on the idlest chip;
  ``busy_s`` is the mean over chips.
- *self time*: ops nest (a ``while`` spans its body), so an op's own time is
  its duration less its children's. Leaf time splits into Mosaic
  (``custom_call_target="tpu_custom_call"``), collectives (all-reduce and
  kin) and the rest (XLA fusions, copies). A Mosaic call carries its kernel
  function's name; those with ``level`` in it (``_hoisted_level_pallas``,
  ``_fused_level_pallas``) are the level histogram's.
- *exposed collective*: a collective op's self time on the ``XLA Ops``
  line: the core runs one op at a time there, so while an all-reduce (or the
  ``-done`` of an asynchronous one) holds the line nothing else computes.
- *gaps*: the idle intervals inside the window, each labelled with the
  benchmark's host span that overlaps it most.
"""

import gzip
import json
import os
import re

from harness import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
xplane = load_module(os.path.join(_HERE, "xplane.py"))

MOSAIC = 'custom_call_target="tpu_custom_call"'
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_HEAD = re.compile(r"^%?([\w\-]+?)(\.\d+)?\s*=\s*")
_SHAPE = re.compile(r"\b\w+\[[\d,]*\]")
_LEVEL_KERNEL = re.compile(r"level")
TOP = 10


def union(intervals):
    """Merge [start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def self_times(ops):
    """[(name, start, end, self_ns)] of nested op events on one line."""
    evs = sorted(((s, s + d, n) for n, s, d in ops),
                 key=lambda t: (t[0], -t[1]))
    out, stack = [], []  # stack of [start, end, name, child_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, n, child = stack.pop()
            out.append((n, s, e, max((e - s) - child, 0.0)))
            if stack:
                stack[-1][3] += e - s

    for s, e, n in evs:
        close(s)
        if stack and e > stack[-1][1]:
            e = stack[-1][1]  # a child that runs over its parent's end
        stack.append([s, e, n, 0.0])
    close(float("inf"))
    return out


def kind_of(name: str) -> str:
    if MOSAIC in name:
        return "mosaic"
    if _COLLECTIVE.search(name):
        return "collective"
    return "xla"


def is_level_kernel(name: str) -> bool:
    """A Mosaic call whose kernel function builds a level's histogram."""
    m = _HEAD.match(name)
    return bool(m and MOSAIC in name and _LEVEL_KERNEL.search(m.group(1)))


def label_of(name: str) -> str:
    """A short stable label: the instruction's name without its number,
    with the result shape for a Mosaic call (it tells the levels apart)."""
    m = _HEAD.match(name)
    if not m:
        return name[:60]
    base = m.group(1)
    if MOSAIC in name:
        result = name[m.end():].split(" custom-call(", 1)[0]
        return f"{base} {'+'.join(_SHAPE.findall(result))} (mosaic)"
    return base


def summarize(table: dict) -> dict:
    devices = table["devices"]
    spans = table["host_spans"]
    windows = [(s, s + d) for n, s, d, *_ in spans if n == "bench.window"]
    if windows:
        lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        ext = [(s, s + d) for dev in devices.values()
               for _, s, d in dev["modules"] + dev["ops"]]
        lo = min((s for s, _ in ext), default=0.0)
        hi = max((e for _, e in ext), default=0.0)
    window_ns = max(hi - lo, 0.0)

    per_chip = {}
    op_table: dict = {}
    chip_gaps = []  # (busy ns, idle intervals) of each chip
    for plane, dev in sorted(devices.items()):
        busy_iv = union(clip([(s, s + d) for _, s, d in dev["modules"]],
                             lo, hi))
        busy = total(busy_iv)
        kinds = {"mosaic": 0.0, "collective": 0.0, "xla": 0.0}
        level_ns = 0.0
        for name, s, e, self_ns in self_times(dev["ops"]):
            if e <= lo or s >= hi or self_ns <= 0:
                continue
            kinds[kind_of(name)] += self_ns
            if is_level_kernel(name):
                level_ns += self_ns
            label = label_of(name)
            op_table[label] = op_table.get(label, 0.0) + self_ns
        per_chip[plane] = {"busy_s": busy / 1e9,
                           "mosaic_s": kinds["mosaic"] / 1e9,
                           "level_hist_s": level_ns / 1e9,
                           "collective_exposed_s": kinds["collective"] / 1e9,
                           "xla_leaf_s": kinds["xla"] / 1e9,
                           "modules": len(dev["modules"])}
        # idle gaps of this chip inside the window
        edges = [lo] + [t for iv in busy_iv for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        chip_gaps.append((busy, gaps))

    n = max(len(per_chip), 1)
    busy_all = [c["busy_s"] for c in per_chip.values()]
    # gaps are reported for the idlest chip
    gaps = min(chip_gaps, key=lambda t: t[0])[1] if chip_gaps else []
    by_span: dict = {}
    for s, e in gaps:
        best, best_ov = "no benchmark span open", 0.0
        for name, ss, sd, *_ in spans:
            if name == "bench.window":
                continue
            ov = min(e, ss + sd) - max(s, ss)
            if ov > best_ov:
                best, best_ov = name, ov
        by_span[best] = by_span.get(best, 0.0) + (e - s)
    window_s = window_ns / 1e9
    return {
        "window_s": window_s,
        "chips": len(per_chip),
        "busy_s": sum(busy_all) / n if per_chip else 0.0,
        "busy_min_s": min(busy_all, default=0.0),
        "busy_max_s": max(busy_all, default=0.0),
        "mosaic_s": sum(c["mosaic_s"] for c in per_chip.values()) / n,
        "level_hist_s": sum(c["level_hist_s"] for c in per_chip.values()) / n,
        "collective_exposed_s": sum(c["collective_exposed_s"]
                                    for c in per_chip.values()) / n,
        "per_chip": per_chip,
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0) / 1e9,
        "breakdown": {
            "device_ops": [[k, v / 1e9 / n] for k, v in sorted(
                op_table.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v / 1e9] for k, v in sorted(
                by_span.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def summarize_dir(trace_dir: str | None, keep: str | None = None) -> dict:
    """Reduce the newest trace under ``trace_dir``. ``keep`` also writes the
    event table there (gzipped JSON), for a recording kept with the tests."""
    path = xplane.find_xplane(trace_dir) if trace_dir else None
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    table = xplane.load_table(path)
    if keep:
        os.makedirs(keep, exist_ok=True)
        with gzip.open(os.path.join(keep, "event_table.json.gz"), "wt") as f:
            json.dump(table, f)
    return summarize(table)
