"""``partition_mosaic_ms_per_round``'s manifest entry, found by name.

``test_benchmark_partition_mosaic.py`` pins it as the manifest's *last*
entry with the two cells of PR 25, so it fails once a PR appends a reader or
a cell, as PR 26 did; that file is the benchmark's to re-pin. What it held
and still holds is kept here."""

import json
import os

from bench_paths import REPO

NAME = "partition_mosaic_ms_per_round"


def test_entry_is_its_xla_twins_but_for_the_name():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    entry, = [m for m in per_layer if m["name"] == NAME]
    twin, = [m for m in per_layer if m["name"] == "partition_ms_per_round"]
    assert entry == dict(twin, name=NAME)
    assert entry["workloads"][:2] == ["anchor_train", "higgs_train_x4"]
    assert entry["better"] == "lower" and entry["unit"] == "ms/round"
