"""The phase reduction: the wire reader on the recordings, the split by scope
on a hand-made table, and the readers that stand on it."""

import glob
import gzip
import importlib.util
import json
import os

import pytest

from bench_paths import BENCH, DATA, load

phases = load("reduce/phases.py")
summary = phases.summary

RECORDINGS = ["v5e_small", "v5e_small_scoped"]
READERS = ["gradient_ms_per_round", "root_ms_per_round",
           "split_eval_ms_per_round", "partition_ms_per_round",
           "finalize_ms_per_round", "leaf_delta_ms_per_round",
           "level_glue_ms_per_round", "unscoped_xla_ms_per_round",
           "chunk_prepare_ms", "chunk_dispatch_ms", "chunk_commit_ms"]
TPU0 = "/device:TPU:0"


@pytest.fixture(scope="module")
def unpacked(tmp_path_factory):
    """{recording: path of its ``.xplane.pb``}."""
    root = tmp_path_factory.mktemp("recordings")
    out = {}
    for name in RECORDINGS:
        path = root / f"{name}.xplane.pb"
        with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz"), "rb") as f:
            path.write_bytes(f.read())
        out[name] = str(path)
    return out


@pytest.fixture(autouse=True)
def _fresh_memo():
    phases.find_trace.cache_clear()
    yield
    phases.find_trace.cache_clear()


def _tpu_plane(path):
    plane, = [p for p in phases.planes(path) if p["name"] == TPU0]
    return plane


# ---------------------------------------------------------------------------
# the wire reader
# ---------------------------------------------------------------------------


def test_wire_reader_finds_the_scope_paths_of_the_unscoped_recording(
        unpacked):
    events = _tpu_plane(unpacked["v5e_small"])["events"]
    assert len(events) == 514
    with_path = [st["tf_op"] for _, st in events.values() if "tf_op" in st]
    assert len(with_path) == 303
    assert sum(p.startswith("jit(_scan_rounds_impl)/") for p in with_path) \
        > 200
    # the file also names each op's source line
    assert sum("source" in st for _, st in events.values()) > 290


def _xplane_pb2():
    """The generated protobuf module that ships with tensorflow, loaded by
    path: it needs ``google.protobuf`` alone, and importing ``tensorflow``
    takes a third of a minute."""
    try:
        spec = importlib.util.find_spec("tensorflow")
        import google.protobuf  # noqa: F401
    except (ImportError, ValueError):
        return None
    for root in (spec.submodule_search_locations or []) if spec else []:
        path = os.path.join(root, "tsl", "profiler", "protobuf",
                            "xplane_pb2.py")
        if os.path.isfile(path):
            s = importlib.util.spec_from_file_location("_xplane_pb2", path)
            mod = importlib.util.module_from_spec(s)
            s.loader.exec_module(mod)
            return mod
    return None


@pytest.mark.parametrize("name", RECORDINGS)
def test_wire_reader_agrees_with_the_generated_protobuf(unpacked, name):
    pb2 = _xplane_pb2()
    if pb2 is None:
        pytest.skip("no tensorflow xplane_pb2 here")
    space = pb2.XSpace()
    with open(unpacked[name], "rb") as f:
        space.ParseFromString(f.read())
    want_plane, = [p for p in space.planes if p.name == TPU0]
    stat_name = {k: v.name for k, v in want_plane.stat_metadata.items()}
    want = {}
    for k, em in want_plane.event_metadata.items():
        paths = [st.str_value for st in em.stats
                 if stat_name[st.metadata_id] == "tf_op"]
        want[k] = (em.name, paths[0] if paths else None)
    got = {k: (n, st.get("tf_op"))
           for k, (n, st) in _tpu_plane(unpacked[name])["events"].items()}
    assert got == want
    line, = [ln for ln in want_plane.lines if ln.name == "XLA Ops"]
    ops = phases.load(unpacked[name])["devices"][TPU0]
    assert len(ops) == len(line.events)
    for (text, _, start, dur), ev in zip(ops, line.events):
        assert text == want_plane.event_metadata[ev.metadata_id].name
        assert start == line.timestamp_ns + ev.offset_ps // 1000
        assert dur == ev.duration_ps // 1000


@pytest.mark.parametrize("name", RECORDINGS)
def test_wire_reader_agrees_with_profile_data(unpacked, name):
    """Same events, same clock as the table ``summarize`` reduces."""
    table = summary.xplane.load_table(unpacked[name])
    ops = phases.load(unpacked[name])["devices"][TPU0]
    want = table["devices"][TPU0]["ops"]
    assert len(ops) == len(want)
    for (text, _, start, dur), (short, s, d) in zip(ops, want):
        assert summary.xplane._short(text) == short
        assert (start, dur) == (s, d)
    windows = [(s, d) for n, s, d, *_ in table["host_spans"]
               if n == "bench.window"]
    assert [(s, d) for n, s, d in phases.load(unpacked[name])["host_spans"]
            if n == "bench.window"] == pytest.approx(windows)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tf_op, phase", [
    ("jit(_scan_rounds_impl)/while/body/closed_call/"
     "jit(_grow_tree_fused_impl)/xgb.split_eval/jit(cumsum)/add:",
     "xgb.split_eval"),
    ("jit(f)/xgb.level_hist/jit(_hoisted_level_pallas)/pallas_call:",
     "xgb.level_hist"),
    ("jit(f)/xgb.predict_walk/jit(_walk_leaves)/xgb.predict_walk/gather:",
     "xgb.predict_walk"),
    ("jit(f)/xgb.root/xgb.hist_psum/psum:", "xgb.hist_psum"),
    ("xgb.root/add", "xgb.root"),
    ("jit(_scan_rounds_impl)/while/body/select_n:", "unscoped"),
    ("", "unscoped"),
])
def test_phase_is_the_innermost_scope(tf_op, phase):
    assert phases.phase_of(tf_op) == phase


@pytest.mark.parametrize("name", RECORDINGS)
def test_cells_add_up_to_summarize_per_chip(unpacked, name):
    out = phases.reduce(phases.load(unpacked[name]))
    want = summary.summarize(summary.xplane.load_table(unpacked[name]))
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["chips"] == want["chips"] == 1
    for plane, chip in want["per_chip"].items():
        cells = out["per_chip"][plane]
        for kind, key in (("mosaic", "mosaic_s"), ("xla", "xla_leaf_s"),
                          ("collective", "collective_exposed_s")):
            got = sum(kinds.get(kind, 0.0) for kinds in cells.values())
            assert got == pytest.approx(chip[key], rel=1e-9, abs=1e-15), kind


def test_a_recording_from_before_the_scopes_reads_unscoped(unpacked):
    out = phases.reduce(phases.load(unpacked["v5e_small"]))
    assert set(out["phases"]) == {phases.UNSCOPED}
    assert out["phases"][phases.UNSCOPED]["xla"] > 0
    assert out["phases"][phases.UNSCOPED]["mosaic"] > 0
    assert out["host"] == {}  # the package had no span on this clock


def test_scoped_recording_names_every_phase(unpacked):
    """Recorded on one v5e chip from this PR's tree
    (``record_scoped_trace.py``): the one-hot build, a chunk of two rounds
    and a prediction inside the window."""
    out = phases.reduce(phases.load(unpacked["v5e_small_scoped"]))
    got = out["phases"]
    for phase in phases.CLAIMED + ("xgb.onehot_build", "xgb.predict_walk"):
        assert sum(got[phase].values()) > 0, phase
    assert "xgb.hist_psum" not in got  # one chip: no collective
    assert got["xgb.level_hist"]["mosaic"] > 0
    assert got["xgb.onehot_build"]["mosaic"] > 0
    xla = sum(kinds.get("xla", 0.0) for kinds in got.values())
    assert got[phases.UNSCOPED].get("xla", 0.0) < 0.10 * xla
    assert got[phases.UNSCOPED].get("mosaic", 0.0) == 0
    # the chunk's host steps, once each, inside the chunk's span
    host = out["host"]
    assert host["xgb.scan_chunk"][0] == 1
    steps = sum(host[f"xgb.chunk.{s}"][1]
                for s in ("prepare", "dispatch", "commit"))
    assert 0.9 * host["xgb.scan_chunk"][1] <= steps \
        <= host["xgb.scan_chunk"][1]
    with open(os.path.join(DATA, "v5e_small_scoped.phases.json")) as f:
        want = json.load(f)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert {p: dict(k) for p, k in got.items()} == {
        p: pytest.approx(k, rel=1e-9) for p, k in want["phases"].items()}
    assert {n: v[0] for n, v in host.items()} == {
        n: v[0] for n, v in want["host"].items()}
    for n, v in want["host"].items():
        assert host[n][1] == pytest.approx(v[1], rel=1e-9)


MOSAIC = ('%_hoisted_level_pallas.7 = (s32[8,1]{1,0}, f32[4,128]{1,0}) '
          'custom-call(s32[8,2]{1,0} %x), '
          'custom_call_target="tpu_custom_call", frontend_attributes={}')
FUSION = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
CONVERT = "%convert.1 = s32[8,2]{1,0} convert(u8[8,2]{1,0} %b)"
ALLRED = "%all-reduce.2 = f32[4,128]{1,0} all-reduce(f32[4,128]{1,0} %h)"
WHILE = "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"
BODY = "jit(_scan_rounds_impl)/while/body/"


def _trace():
    """Two chips, window 0..1000 ns. Each runs a ``while`` without a scope
    path (the scan over the rounds, 50 ns of its own) round: the bins'
    widening and the level kernel under ``xgb.level_hist``, a second
    ``while`` (``seq_cumsum``'s scan, 20 ns of its own) round one fusion
    under ``xgb.split_eval``, a fusion with no scope, and the histogram's
    all-reduce. Chip 1's all-reduce is shorter; an op after the window and
    the spans of a chunk before it are left out."""
    def chip(ar_ns):
        end = 560.0 + ar_ns
        return [
            (WHILE, "", 100.0, end - 100.0 + 50.0),
            (CONVERT, BODY + "xgb.level_hist/convert_element_type:",
             100.0, 40.0),
            (MOSAIC, BODY + "xgb.level_hist/jit(_hoisted_level_pallas)/"
             "pallas_call:", 140.0, 260.0),
            (WHILE, "", 400.0, 100.0),
            (FUSION, BODY + "xgb.split_eval/jit(seq_cumsum)/while/body/add:",
             410.0, 80.0),
            (FUSION, BODY + "select_n:", 500.0, 60.0),
            (ALLRED, BODY + "xgb.hist_psum/psum:", 560.0, ar_ns),
            (FUSION, BODY + "xgb.split_eval/mul:", 2000.0, 100.0),
        ]
    return {"devices": {"/device:TPU:0": chip(150.0),
                        "/device:TPU:1": chip(60.0)},
            "host_spans": [
                ("bench.window", 0.0, 1000.0),
                ("xgb.scan_chunk", 10.0, 80.0),
                ("xgb.chunk.prepare", 10.0, 50.0),
                ("xgb.chunk.dispatch", 60.0, 20.0),
                ("xgb.chunk.commit", 80.0, 10.0),
                ("xgb.chunk.admit", 92.0, 4.0),
                ("xgb.predict", 100.0, 800.0),
                ("xgb.scan_chunk", -500.0, 80.0),
                ("xgb.chunk.prepare", -500.0, 70.0)]}


def test_split_by_scope_containers_and_the_mean_over_chips():
    out = phases.reduce(_trace())
    assert out["chips"] == 2 and out["window_s"] == pytest.approx(1000e-9)
    chip0 = out["per_chip"]["/device:TPU:0"]
    assert chip0["xgb.level_hist"] == {"xla": pytest.approx(40e-9),
                                       "mosaic": pytest.approx(260e-9)}
    # the innermost scope, though jit(seq_cumsum)/while/body follows it; the
    # container round it has no path, and its own 20 ns go where its body's
    # ops are
    assert chip0["xgb.split_eval"] == {"xla": pytest.approx(100e-9)}
    # the rounds' container holds ops of several phases: its own 50 ns are
    # unscoped, as is the fusion without a scope
    assert chip0[phases.UNSCOPED] == {"xla": pytest.approx(110e-9)}
    assert chip0["xgb.hist_psum"] == {"collective": pytest.approx(150e-9)}
    got = out["phases"]
    assert got["xgb.hist_psum"]["collective"] == pytest.approx(105e-9)
    assert got["xgb.split_eval"]["xla"] == pytest.approx(100e-9)
    # what summarize reads of the same events, chip by chip
    table = {"devices": {p: {"modules": [], "ops": [[t, s, d] for t, _, s, d
                                                    in ops]}
                         for p, ops in _trace()["devices"].items()},
             "host_spans": [[n, s, d, "main"]
                            for n, s, d in _trace()["host_spans"]]}
    want = summary.summarize(table)
    assert want["mosaic_s"] == pytest.approx(got["xgb.level_hist"]["mosaic"])
    assert want["collective_exposed_s"] == pytest.approx(105e-9)
    # host spans inside the window, counted and summed
    assert out["host"]["xgb.scan_chunk"] == [1, pytest.approx(80e-9)]
    assert out["host"]["xgb.chunk.prepare"] == [1, pytest.approx(50e-9)]
    assert "bench.window" not in out["host"]


def test_no_window_span_takes_every_op():
    trace = _trace()
    trace["host_spans"] = []
    out = phases.reduce(trace)
    assert out["window_s"] == 0.0 and out["host"] == {}
    assert out["phases"]["xgb.split_eval"]["xla"] == pytest.approx(200e-9)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_a_traced_run(name):
    reader = load(f"layer_metrics/{name}.py")
    assert reader.read(None, {}, {}) is None
    assert reader.read({}, {"traced_rounds": 2}, {}) is None


def test_readers_on_the_hand_made_table(monkeypatch):
    out = phases.reduce(_trace())
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    record = {"traced_rounds": 2}
    read = {n: load(f"layer_metrics/{n}.py").read({"window_s": 1e-6},
                                                  record, {})
            for n in READERS}
    assert read["level_glue_ms_per_round"] == pytest.approx(20e-6)
    assert read["split_eval_ms_per_round"] == pytest.approx(50e-6)
    assert read["unscoped_xla_ms_per_round"] == pytest.approx(55e-6)
    for absent in ("gradient", "root", "partition", "finalize",
                   "leaf_delta"):
        assert read[f"{absent}_ms_per_round"] is None
    assert read["chunk_prepare_ms"] == pytest.approx(50e-6)
    assert read["chunk_dispatch_ms"] == pytest.approx(20e-6)
    assert read["chunk_commit_ms"] == pytest.approx(14e-6)
    # phases + unscoped + collectives = the op line's non-Mosaic self time
    psum = load("layer_metrics/psum_exposed_ms_per_round.py").read(
        {"chips": 2, "collective_exposed_s": 105e-9}, record, {})
    total = sum(v for n, v in read.items()
                if v is not None and n.endswith("_ms_per_round")) + psum
    non_mosaic = sum(sec for kinds in out["phases"].values()
                     for kind, sec in kinds.items() if kind != "mosaic")
    assert total == pytest.approx(1e3 * non_mosaic / 2)
    # no round traced: no device reading
    assert load("layer_metrics/split_eval_ms_per_round.py").read(
        {"window_s": 1e-6}, {"traced_rounds": 0}, {}) is None


def test_scope_without_a_metric_is_booked_with_the_unscoped_time(
        monkeypatch):
    trace = _trace()
    for ops in trace["devices"].values():
        ops.append((FUSION, "jit(run)/xgb.predict_walk/gather:", 800.0, 30.0))
    out = phases.reduce(trace)
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    got = load("layer_metrics/unscoped_xla_ms_per_round.py").read(
        {"window_s": 1e-6}, {"traced_rounds": 1}, {})
    assert got == pytest.approx(140e-6)


def test_find_trace_in_an_empty_temp_dir(monkeypatch, tmp_path):
    monkeypatch.setattr(phases.tempfile, "tempdir", str(tmp_path))
    assert phases.find_trace() is None
    assert phases.table({"window_s": 1.0}) is None


def _plant(tmp_path, run: str, stamp: str, source: str, mtime: float):
    d = tmp_path / f"xgbtpu_bench_{run}" / "trace" / "plugins" / "profile" \
        / stamp
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    with open(source, "rb") as f:
        path.write_bytes(f.read())
    os.utime(path, (mtime, mtime))
    return str(path)


def test_find_trace_takes_the_newest_file_with_a_window(
        monkeypatch, tmp_path, unpacked):
    """As ``harness.Context`` lays its files out; a file without a
    ``bench.window`` span (another tool's) is passed over."""
    monkeypatch.setattr(phases.tempfile, "tempdir", str(tmp_path))
    old = _plant(tmp_path, "old", "t0", unpacked["v5e_small"], 1000.0)
    new = _plant(tmp_path, "new", "t1", unpacked["v5e_small_scoped"], 2000.0)
    junk = tmp_path / "xgbtpu_bench_junk" / "trace" / "plugins" / "profile" \
        / "t2"
    junk.mkdir(parents=True)
    (junk / "host.xplane.pb").write_bytes(b"\x0a\x02\x12\x00")  # one plane
    os.utime(junk / "host.xplane.pb", (3000.0, 3000.0))
    assert phases.find_trace() == new
    assert len(glob.glob(str(tmp_path / "xgbtpu_bench_*"))) == 3
    # the table is trusted only for the window summarize reduced
    want = summary.summarize(summary.xplane.load_table(new))
    out = phases.table(want)
    assert out is not None and out["chips"] == 1
    assert phases.table(dict(want, window_s=want["window_s"] * 1.01)) is None
    stale = summary.summarize(summary.xplane.load_table(old))
    assert phases.table(stale) is None
    # the readers, end to end, on the recording
    record = {"traced_rounds": 2, "chips": 1}
    got = {n: load(f"layer_metrics/{n}.py").read(want, record, {})
           for n in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    non_mosaic = want["per_chip"][TPU0]["xla_leaf_s"] \
        + want["per_chip"][TPU0]["collective_exposed_s"]
    device = sum(v for n, v in got.items() if n.endswith("_ms_per_round"))
    assert device == pytest.approx(1e3 * non_mosaic / 2, rel=1e-9)


def test_no_reader_file_names_a_cell():
    """The manifest's self-test greps ``benchmark/`` for cell names; this
    one holds the new files to their size: a docstring and one call."""
    for name in READERS:
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.py")) as f:
            text = f.read()
        assert len(text.splitlines()) <= 20, name
        assert "phases." in text
