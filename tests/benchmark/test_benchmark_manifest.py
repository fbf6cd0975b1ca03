"""BENCHMARK.json and the data files it names hold to the contract."""

import glob
import json
import os
import re

import pytest

from bench_paths import BENCH, REPO, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = harness.load_manifest()


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in MANIFEST["paths"])
    assert all(_line(w) for w in MANIFEST["command"])
    assert len(MANIFEST["command"]) <= 32


def test_configs():
    names = [c["name"] for c in MANIFEST["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        doc = harness.load_json(os.path.join(REPO, c["file"]))
        assert doc["name"] == c["name"]
        assert doc["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) and k in doc for k in c["reduced"])
        for key in ("source", "deployment", "params", "data", "quality",
                    "assumed"):
            assert key in doc, (c["name"], key)
        assert os.path.isfile(os.path.join(
            BENCH, "generators", doc["data"]["generator"] + ".py"))
        lo, hi = doc["quality"]["band"]
        # one task in every run (the label's law is fixed in the file), so
        # the band is narrow: the seed moves the sample alone
        assert 0 < hi - lo <= 0.03 and "band_from" in doc["quality"]
        assert "law_seed" in doc["data"]["generator_params"]


def test_workloads():
    cells = MANIFEST["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(len(cells) // 4, 1)
    configs = {c["name"] for c in MANIFEST["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        # the cell's own file says the same, and names files that exist
        cell = harness.load_cell(BENCH, w["name"])
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", cell["mix"]["kind"] + ".py"))


def test_every_data_file_loads_and_belongs_to_a_cell_or_the_rehearsal():
    for root in (BENCH, os.path.join(BENCH, "rehearsal")):
        names = [os.path.basename(p)[:-5] for p in
                 glob.glob(os.path.join(root, "workloads", "*.json"))]
        assert names
        for name in names:
            cell = harness.load_cell(root, name)
            assert cell["chips"] in (1, 4)
            assert os.path.isfile(os.path.join(
                BENCH, "traffic", cell["mix"]["kind"] + ".py"))
            assert os.path.isfile(os.path.join(
                BENCH, "generators",
                cell["config_doc"]["data"]["generator"] + ".py"))
    in_manifest = {w["name"] for w in MANIFEST["workloads"]}
    on_disk = {os.path.basename(p)[:-5] for p in
               glob.glob(os.path.join(BENCH, "workloads", "*.json"))}
    assert on_disk == in_manifest


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_metrics(section):
    metrics = MANIFEST[section]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    all_names = [m["name"] for s in ("end_to_end", "per_layer")
                 for m in MANIFEST[s]]
    assert len(set(all_names)) == len(all_names)
    assert 1 <= len(metrics) <= (16 if section == "end_to_end" else 128)
    for m in metrics:
        base = {"name", "unit", "better", "source"}
        base |= {"bound"} if section == "end_to_end" else {"layer", "moves"}
        assert set(m) - {"workloads"} == base, m["name"]
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        where = set(m.get("workloads", cells))
        assert where and where <= cells
        if section == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.1
        else:
            assert _line(m["layer"]) and m["moves"] in e2e
            # reported only where the metric it moves is
            assert where <= set(e2e[m["moves"]].get("workloads", cells))
            reader = harness.load_module(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py"))
            assert callable(reader.read)
            # a reader that finds nothing to read returns nothing
            assert reader.read(None, {}, {}) is None
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for w in MANIFEST["workloads"]:
        e2e = [m["name"] for m in
               harness.cell_metrics(MANIFEST, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(MANIFEST, w["name"], "per_layer")


def test_no_cell_is_named_in_the_runner():
    """The harness is driven by data: no file of code knows a cell or a
    configuration by name."""
    names = [w["name"] for w in MANIFEST["workloads"]] \
        + [c["name"] for c in MANIFEST["configs"]]
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        text = open(path).read()
        code = "\n".join(line for line in text.split("\n")
                         if not line.lstrip().startswith("#"))
        code = re.sub(r'"""(.|\n)*?"""', "", code)
        for n in names:
            assert n not in code, (path, n)


def test_files_are_small_and_named_from_name_characters():
    total = 0
    for p in MANIFEST["paths"]:
        for dirpath, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in dirpath:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
                total += os.path.getsize(os.path.join(dirpath, f))
    assert total < 8 * 1024 * 1024
    assert json.dumps(MANIFEST)  # plain JSON
