"""The level histogram's share of its roofline.

The least time a round's histograms can take, from the algorithm's inputs
alone: for each level the larger of useful flops over the bf16 peak and
bytes over the HBM peak (``shapes.level_hist_min_seconds``: bins, gradients
and positions read once, the histogram written once), summed over the
levels and the round's trees, over the device time a round of the Mosaic
kernels that build level histograms (``reduce/summary.py`` picks them by
name). A level counts the nodes whose histograms the algorithm has to build
there (``shapes.built_nodes``: one at the root, one child of every split
below it, the sibling being parent - built), whatever the run built. Flops
are the useful ones (two bf16 terms a float32 gradient), not channels padded
to the 128-wide MXU tile. The run record's ``level_hist_bound`` says which
bound holds at each level and how many nodes were counted.

No chip does more than its roofline: the driver refuses a reading over
``OVER_FLOOR_PCT`` as ``impossible_gain``, so such a reading is returned as
read and said aloud (stderr, ``record["level_hist_over_floor"]``)."""

import os
import sys

from harness import HERE, load_module

shapes = load_module(os.path.join(HERE, "shapes.py"))

OVER_FLOOR_PCT = 105.0


def read(summary, record, cell):
    rounds = record.get("traced_rounds")
    if not summary or not rounds or summary.get("level_hist_s", 0) <= 0:
        return None
    n = record["rows_train"] // record["chips"]
    peaks = shapes.load_peaks(record["device_kind"])
    levels = [(k, *shapes.level_hist_min_seconds(
        n, record["cols"], record["max_bin"], k, peaks))
        for k in map(shapes.built_nodes, range(record["max_depth"]))]
    least = record.get("trees_per_round", 1) * sum(t for _, t, _ in levels)
    record["level_hist_bound"] = "; ".join(
        f"level {d}: {1e3 * t:.2f} ms ({b}, {k} built)"
        for d, (k, t, b) in enumerate(levels))
    pct = 100.0 * least / (summary["level_hist_s"] / rounds)
    if pct > OVER_FLOOR_PCT:
        record["level_hist_over_floor"] = True
        print(f"level_hist_roofline {pct:.3f}% > {OVER_FLOOR_PCT:g}%: "
              "benchmark/shapes.py counts more work than the level kernels "
              "do, or the kernels leave work out; the driver refuses this "
              "as impossible_gain", file=sys.stderr)
    return pct
