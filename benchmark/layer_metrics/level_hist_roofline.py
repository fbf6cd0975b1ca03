"""The level histogram's share of its roofline.

The least time a round's histograms can take, from the algorithm's inputs
alone: for each level the larger of useful flops over the bf16 peak and
bytes over the HBM peak (``shapes.level_hist_min_seconds``: bins, gradients
and positions read once, the histogram written once), summed over the
levels and the round's trees, over the device time a round of the Mosaic
kernels that build level histograms (``reduce/summary.py`` picks them by
name). Flops are the useful ones (two bf16 terms a float32 gradient, 2K
channels a level), not channels padded to the 128-wide MXU tile. The run
record's ``level_hist_bound`` says which bound holds at each level."""

import os

from harness import HERE, load_module

shapes = load_module(os.path.join(HERE, "shapes.py"))


def read(summary, record, cell):
    rounds = record.get("traced_rounds")
    if not summary or not rounds or summary.get("level_hist_s", 0) <= 0:
        return None
    n = record["rows_train"] // record["chips"]
    peaks = shapes.load_peaks(record["device_kind"])
    levels = [shapes.level_hist_min_seconds(
        n, record["cols"], record["max_bin"], 1 << d, peaks)
        for d in range(record["max_depth"])]
    least = record.get("trees_per_round", 1) * sum(t for t, _ in levels)
    record["level_hist_bound"] = ", ".join(
        f"level {d}: {1e3 * t:.2f} ms ({b})"
        for d, (t, b) in enumerate(levels))
    return 100.0 * least / (summary["level_hist_s"] / rounds)
