"""The readers of the program's own spans, scopes and counter (PR 25),
checked without a chip: the ``.xplane.pb`` wire reading against
``jax.profiler.ProfileData`` on a file encoded here, every new reader on a
hand-made run with known answers, the run found by its content, the ring
that must not be read when it wrapped, a recorded piece of each cell's chip
trace that carries scopes, and the traced rehearsal of both cells."""

import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_trace, spec, xplane  # noqa: E402

MS = 1e6  # ns
SCOPES = os.path.join(ROOT, "chipbench", "testdata", "scopes")
NEW = {
    "higgs_gbdt_fit": ["gbdt_bin_ms_per_fit", "gbdt_upload_ms_per_fit", "gbdt_unpack_ms_per_fit",
                       "gbdt_fit_self_ms", "gbdt_hist_prep_ms_per_tree",
                       "hist_rows_selected_share", "gbdt_idle_spanned_share", "setup_compile_ms"],
    "resnet50_featurize_stream": ["feed_prepare_ms_per_chunk", "feed_stage_ms_per_batch",
                                  "feed_finish_ms_per_chunk", "feed_idle_spanned_share",
                                  "setup_compile_ms"],
}


# -- the wire reading ---------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value: object) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def _entry(key: int, message: bytes) -> bytes:
    return _field(1, key) + _field(2, message)


def _encoded_xplane() -> bytes:
    """One device plane with two operations (one carrying an ``op_name``
    as a string, one as a reference to a stat's name, as the profiler
    interns them) and a loop, a second line that is not ``XLA Ops``, and
    the plane that holds ``profile_start_time``."""
    stat_meta = (_field(5, _entry(1, _field(1, 1) + _field(2, "tf_op")))
                 + _field(5, _entry(2, _field(1, 2) + _field(2, "flops")))
                 + _field(5, _entry(3, _field(1, 3) + _field(2, "jit(f)/while/body/gbdt.hist.pad/pad:"))))
    mul = ("%broadcast_multiply_fusion.2 = f32[8,3]{1,0} fusion(f32[8,3]{1,0} %p), kind=kLoop")
    pad = "%pad.173 = f32[16,3]{1,0} pad(f32[8,3]{1,0} %x, f32[] %c), padding=0_8x0_0"
    loop = "%while.7 = (s32[], f32[8]) while((s32[], f32[8]) %t), condition=%c, body=%b"
    event_meta = (
        _field(4, _entry(1, _field(1, 1) + _field(2, mul) + _field(5, _field(1, 2) + _field(3, 48))
                         + _field(5, _field(1, 1) + _field(5, "jit(f)/while/body/gbdt.hist.mask/mul:"))))
        + _field(4, _entry(2, _field(1, 2) + _field(2, pad) + _field(5, _field(1, 1) + _field(7, 3))))
        + _field(4, _entry(3, _field(1, 3) + _field(2, loop))))
    ops = (_field(2, "XLA Ops") + _field(3, 5_000)
           + _field(4, _field(1, 3) + _field(2, 1_000_000) + _field(3, 9_000_000))
           + _field(4, _field(1, 1) + _field(2, 2_000_000) + _field(3, 3_000_500))
           + _field(4, _field(1, 2) + _field(2, 6_000_000) + _field(3, 1_000_000)))
    other = _field(2, "XLA Modules") + _field(3, 5_000) + _field(4, _field(1, 3) + _field(2, 0) + _field(3, 7))
    device = _field(2, "/device:TPU:0") + _field(3, ops) + _field(3, other) + event_meta + stat_meta
    env = (_field(2, "Task Environment") + _field(5, _entry(1, _field(1, 1) + _field(2, "profile_start_time")))
           + _field(6, _field(1, 1) + _field(3, 1_790_000_000_000_000_000)))
    return _field(1, device) + _field(1, env)


def test_scoped_events_read_like_profile_data_with_the_op_name_added(tmp_path):
    path = os.path.join(str(tmp_path), "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(_encoded_xplane())
    mine = program_trace.read_scoped_events(path)
    assert mine["profile_start_ns"] == 1_790_000_000_000_000_000
    ops = mine["devices"]["/device:TPU:0"]
    assert ops == [
        ["while:while.7", 5_000 + 1_000.0, 9_000.0, ""],
        ["fusion:broadcast_multiply_fusion.2", 5_000 + 2_000.0, 3_000.5,
         "jit(f)/while/body/gbdt.hist.mask/mul"],
        ["pad:pad.173", 5_000 + 6_000.0, 1_000.0, "jit(f)/while/body/gbdt.hist.pad/pad"],
    ]
    # the same names, starts and durations as the reading the accepted
    # metrics use (jax.profiler.ProfileData), which lacks the op_name
    theirs = xplane.read_events(path)["devices"]["/device:TPU:0"]
    # (which drops the fraction of a nanosecond)
    assert [o[:3] for o in ops] == [[n, pytest.approx(s, abs=1.0), pytest.approx(d, abs=1.0)]
                                    for n, s, d in theirs]


# -- a hand-made run ------------------------------------------------------------

def _span(name, sid, parent, start, end, trace="t", **attrs):
    return {"name": name, "id": sid, "parent": parent, "trace": trace,
            "start": start * MS, "end": end * MS, "attrs": attrs}


def _gbdt_run() -> program_trace.ProgramTrace:
    """A 200 ms window, two fits of 90 ms; the first is laid out below, the
    second is the first moved by 100 ms. Device: a loop 40..85 with a mask
    pass, a pad pass, a kernel call and a best-split scan inside it."""
    spans, devices = [], []
    for i, off in enumerate((5, 105)):
        f = f"f{i}"
        spans += [
            _span("gbdt.fit", f, None, off, off + 90, trace=f, rows=1000),
            _span("gbdt.gather", f + "g", f, off, off + 4, trace=f),
            _span("gbdt.bin_fit", f + "bf", f, off + 4, off + 10, trace=f),
            _span("gbdt.bin_transform", f + "bt", f, off + 10, off + 22, trace=f),
            _span("gbdt.upload", f + "u1", f, off + 22, off + 28, trace=f),
            _span("gbdt.upload", f + "u2", f, off + 29, off + 31, trace=f),
            _span("gbdt.chunk", f + "c", f, off + 32, off + 86, trace=f),
            _span("gbdt.chunk.dispatch", f + "cd", f + "c", off + 32, off + 36, trace=f),
            _span("gbdt.chunk.wait", f + "cw", f + "c", off + 36, off + 82, trace=f),
            _span("gbdt.chunk.unpack", f + "cu", f + "c", off + 82, off + 85, trace=f,
                  hist_rows_streamed=6000, hist_rows_selected=1500),
            _span("gbdt.model_string", f + "m", f, off + 87, off + 89, trace=f),
        ]
        devices += [
            ["while:while.1", (off + 35) * MS, 45 * MS, ""],
            ["fusion:broadcast_multiply_fusion.2", (off + 35) * MS, 10 * MS,
             "jit(_scan_chunk)/while/body/gbdt.hist.mask/mul"],
            ["pad:pad.173", (off + 45) * MS, 8 * MS,
             "jit(_scan_chunk)/while/body/gbdt.hist.pad/jit(_pad)/pad"],
            ["custom-call:plane_histogram.9", (off + 53) * MS, 20 * MS,
             "jit(_scan_chunk)/while/body/plane_histogram/pallas_call"],
            ["fusion:reduce.4", (off + 73) * MS, 7 * MS,
             "jit(_scan_chunk)/while/body/gbdt.best_split/reduce_max"],
        ]
    spans.append(_span("xla.compile", "x", None, -50, -20))
    return program_trace.ProgramTrace((0.0, 200 * MS), spans, {"/device:TPU:0": devices})


def _feed_run() -> program_trace.ProgramTrace:
    """A 100 ms window, one partition 10..90 of three batches; the device
    works 32..80."""
    spans = [
        _span("featurize.partition", "p", None, 10, 90, rows=6),
        _span("featurize.coerce", "co", "p", 10, 12),
        _span("xla_model.apply_batch", "a", "p", 12, 88, rows=6, batches=3),
        _span("xla_model.prepare", "pr", "a", 12, 15),
        _span("xla_model.stage", "s0", "a", 15, 29, bytes=10),
        _span("xla_model.dispatch", "d0", "a", 30, 31),
        _span("xla_model.stage", "s1", "a", 31, 41, bytes=10),
        _span("xla_model.dispatch", "d1", "a", 41, 42),
        _span("xla_model.stage", "s2", "a", 42, 54, bytes=10),
        _span("xla_model.dispatch", "d2", "a", 54, 55),
        _span("xla_model.drain", "dr", "a", 55, 82),
        _span("xla_model.concat", "cc", "a", 82, 87),
        _span("featurize.partition", "q", None, -80, -5, trace="u"),   # before the window
    ]
    devices = [["fusion:fusion.4", 32 * MS, 48 * MS, "jit(run)/ResNet/BottleneckBlock_0/Conv_0/conv"]]
    return program_trace.ProgramTrace((0.0, 100 * MS), spans, {"/device:TPU:0": devices})


def _read(monkeypatch, run, name, shapes=None, counter=None):
    reader = importlib.import_module(f"chipbench.metrics.{name}")
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    monkeypatch.setattr(program_trace, "say", lambda what, values: None)
    if counter is not None and hasattr(reader, "_counter"):
        monkeypatch.setattr(reader, "_counter", lambda: counter)
    return reader.read({"window_s": 0.2}, {"shapes": shapes or {}})


def test_gbdt_readers_on_a_hand_made_run(monkeypatch):
    run = _gbdt_run()
    assert _read(monkeypatch, run, "gbdt_bin_ms_per_fit") == pytest.approx(6 + 12)
    assert _read(monkeypatch, run, "gbdt_upload_ms_per_fit") == pytest.approx(6 + 2)
    assert _read(monkeypatch, run, "gbdt_unpack_ms_per_fit") == pytest.approx(3 + 2)
    # 90 ms less gather 4, bin 18, upload 8, chunk 54, model_string 2
    assert _read(monkeypatch, run, "gbdt_fit_self_ms") == pytest.approx(4.0)
    assert run.child_times("gbdt.chunk") == pytest.approx(
        {"gbdt.chunk.dispatch": 4 * MS, "gbdt.chunk.wait": 46 * MS, "gbdt.chunk.unpack": 3 * MS,
         "self": 1 * MS})
    # mask 10 + pad 8 ms a fit, two fits, four trees
    assert _read(monkeypatch, run, "gbdt_hist_prep_ms_per_tree", {"trees": 4}) \
        == pytest.approx(2 * 18 / 4)
    assert run.seconds_by_scope(("gbdt.best_split", "plane_histogram")) == pytest.approx(
        {"gbdt.best_split": 0.014, "plane_histogram": 0.040})
    counter = {"streamed": 18000.0, "selected": 4500.0}   # the warm-up's fit counted too
    assert _read(monkeypatch, run, "hist_rows_selected_share", counter=counter) \
        == pytest.approx(25.0)
    with pytest.raises(ValueError):   # spans that add more than the counter holds
        _read(monkeypatch, run, "hist_rows_selected_share", counter={"streamed": 100.0})
    # idle 0..40, 85..140, 185..200 = 110 ms. Outside every span: 0..5,
    # 95..105, 195..200. gbdt.fit's own: between the uploads, before the
    # chunk and around model_string, 4 ms a fit. The loop starts 3 ms into
    # the chunk (dispatch) and ends 2 ms before wait returns
    idle = run.idle_by_span(("gbdt.",))
    assert idle == pytest.approx({
        "unspanned": 0.020, "gbdt.fit:self": 0.008, "gbdt.gather": 0.008,
        "gbdt.bin_fit": 0.012, "gbdt.bin_transform": 0.024, "gbdt.upload": 0.016,
        "gbdt.chunk.dispatch": 0.006, "gbdt.chunk.wait": 0.004, "gbdt.chunk.unpack": 0.006,
        "gbdt.chunk": 0.002, "gbdt.model_string": 0.004})
    share = _read(monkeypatch, run, "gbdt_idle_spanned_share")
    assert share == pytest.approx(100 * (0.110 - 0.020 - 0.008) / 0.110)


def test_feed_readers_on_a_hand_made_run(monkeypatch):
    run = _feed_run()
    assert _read(monkeypatch, run, "feed_prepare_ms_per_chunk") == pytest.approx(20.0)
    assert _read(monkeypatch, run, "feed_stage_ms_per_batch") == pytest.approx(12.0)
    assert _read(monkeypatch, run, "feed_finish_ms_per_chunk") == pytest.approx(8.0)
    # idle 0..32 and 80..100: outside 0..10 and 90..100; partition's own
    # 88..90; apply_batch is no root, so its own 29..30 and 87..88 count
    idle = run.idle_by_span(("featurize.", "xla_model."))
    assert idle == pytest.approx({
        "unspanned": 0.020, "featurize.partition:self": 0.002, "featurize.coerce": 0.002,
        "xla_model.prepare": 0.003, "xla_model.stage": 0.015, "xla_model.dispatch": 0.001,
        "xla_model.apply_batch": 0.002, "xla_model.drain": 0.002, "xla_model.concat": 0.005})
    assert _read(monkeypatch, run, "feed_idle_spanned_share") \
        == pytest.approx(100 * 0.030 / 0.052)


def test_a_reader_that_finds_nothing_returns_nothing(monkeypatch):
    empty = program_trace.ProgramTrace((0.0, 100 * MS), [], {})
    for name in sorted(set(NEW["higgs_gbdt_fit"] + NEW["resnet50_featurize_stream"])):
        if name == "setup_compile_ms":
            continue
        assert _read(monkeypatch, empty, name, {"trees": 4}, counter={}) is None, name
    # the parent's program: spans of the fleet's kind only, no scope on the device
    parent = program_trace.ProgramTrace(
        (0.0, 100 * MS), [_span("gbdt.chunk", "c", None, 5, 95)],
        {"/device:TPU:0": [["custom-call:closed_call.95", 10 * MS, 80 * MS, ""]]})
    for name in NEW["higgs_gbdt_fit"]:
        if name != "setup_compile_ms":
            assert _read(monkeypatch, parent, name, {"trees": 4}, counter={}) is None, name
    # and no run at all: a reduction that no trace directory belongs to
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        if m["name"] in NEW["higgs_gbdt_fit"] + NEW["resnet50_featurize_stream"]:
            reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
            assert reader.read({"window_s": 0.123456}, {"shapes": {"trees": 4}}) is None


# -- the run found by its content; the ring -----------------------------------------

def test_the_runs_trace_directory_is_found_by_its_window(tmp_path):
    root = str(tmp_path)
    for cell, dur in (("cell_a", 2.5e9), ("cell_b", 6.25e9)):
        os.makedirs(os.path.join(root, cell))
        with open(os.path.join(root, cell, "spans.json"), "w") as f:
            json.dump([["chipbench.fit", 1.0e18, dur / 2], [xplane.WINDOW_SPAN, 1.0e18, dur]], f)
    found = program_trace.find_run({"window_s": 6.25}, trace_root=root)
    assert found[0] == os.path.join(root, "cell_b") and found[1][1][2] == 6.25e9
    assert program_trace.find_run({"window_s": 2.5}, trace_root=root)[0].endswith("cell_a")
    assert program_trace.find_run({"window_s": 1.0}, trace_root=root) is None
    assert program_trace.find_run({}, trace_root=root) is None


def test_a_wrapped_span_buffer_is_never_read_as_a_short_window(monkeypatch):
    import time

    from mmlspark_tpu import obs

    obs.set_enabled(True)
    obs.clear_recent_spans()
    monkeypatch.setattr(obs.BUFFER, "cap", 8)
    t_before = time.time_ns()
    for i in range(5):
        with obs.span("gbdt.fit", attrs={"i": i}):
            pass
    rows = program_trace.program_spans(t_before)
    assert [r["attrs"]["i"] for r in rows] == [0, 1, 2, 3, 4]
    assert all(r["end"] >= r["start"] >= t_before for r in rows) and rows[0]["parent"] is None
    for i in range(3):
        with obs.span("gbdt.fit"):
            pass
    # full, and everything it holds is younger than the start asked for: wrapped
    with pytest.raises(RuntimeError):
        program_trace.program_spans(t_before - 1)
    with pytest.raises(RuntimeError):
        program_trace.program_spans(None)
    # full, but it reaches back past the start asked for: whole
    assert len(program_trace.program_spans(rows[2]["start"])) == 8
    obs.clear_recent_spans()


# -- recorded pieces of the cells' chip traces, with scopes -------------------------

def _recorded(workload: str) -> tuple:
    with open(os.path.join(SCOPES, f"scoped_{workload}.json")) as f:
        piece = json.load(f)
    return piece, program_trace.ProgramTrace(tuple(piece["window"]), piece["spans"],
                                             piece["devices"])


@pytest.mark.parametrize("workload", sorted(NEW))
def test_recorded_piece_with_scopes(workload):
    piece, run = _recorded(workload)
    ops = run.first_device()
    assert ops and all(len(o) == 4 for o in ops)
    # the accepted reduction takes the piece's operations as they are
    r = xplane.reduce({"devices": {p: [o[:3] for o in v] for p, v in piece["devices"].items()},
                       "spans": [[xplane.WINDOW_SPAN, 0.0, piece["window"][1]]]})
    idle = r["window_s"] - r["busy_s_each"][0]
    prefixes = ("gbdt.",) if workload == "higgs_gbdt_fit" else ("featurize.", "xla_model.")
    by_span = run.idle_by_span(prefixes)
    assert sum(by_span.values()) == pytest.approx(idle, rel=1e-9, abs=1e-12)
    root = "gbdt.fit" if workload == "higgs_gbdt_fit" else "featurize.partition"
    # the stream's piece holds the harness's sink and the loop between two
    # chunks, which no program span covers: 85% there, as in the whole window
    floor = 90.0 if workload == "higgs_gbdt_fit" else 80.0
    assert idle > 0 and run.idle_spanned_share(prefixes, root)[0] >= floor
    # nearly all the device time carries an op_name (the weights'
    # nanosecond copy-starts and the runtime's markers carry none)
    leaves = [o for o in ops if not xplane.is_container(o[0])]
    assert sum(o[2] for o in leaves if o[3]) >= 0.99 * sum(o[2] for o in leaves)
    if workload == "higgs_gbdt_fit":
        names = ("gbdt.hist.mask", "gbdt.hist.pad", "gbdt.hist.widen", "plane_histogram",
                 "gbdt.best_split", "gbdt.apply_split")
        by_scope = run.seconds_by_scope(names)
        assert by_scope["gbdt.hist.mask"] > 0 and by_scope["gbdt.hist.pad"] > 0
        # the trace names the kernel, not closed_call.N (custom calls of a
        # few nanoseconds are the runtime's markers, as PR 24 found)
        kernels = [o for o in ops if xplane.is_kernel_call(o[0]) and o[2] > 1_000]
        assert kernels and all(o[0].startswith("custom-call:plane_histogram") for o in kernels)
        assert by_scope["plane_histogram"] == pytest.approx(
            sum(o[2] for o in kernels if o[1] + o[2] > 0 and o[1] < piece["window"][1]) * 1e-9,
            rel=1e-3)
        # the grower's scopes and its kernel cover the device time of a tree
        total = sum(v for k, v in r["op_seconds"].items())
        assert sum(by_scope.values()) >= 0.95 * total
    else:
        assert any("BottleneckBlock_" in o[3] for o in ops), "Flax's module scopes reach the trace"
        assert run.in_window("xla_model.stage"), "a chunk boundary lies inside the piece"


# -- the traced rehearsal ------------------------------------------------------------

@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_rehearsal_prints_the_span_and_counter_metrics(workload):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", workload,
         "--seed", "2147483659", "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads([ln for ln in out.stdout.splitlines() if ln.startswith("{")][-1])
    assert line["correct"] is True and line["rehearsal"] is True
    # no device on the CPU: what reads the device trace stays silent
    silent = {"gbdt_hist_prep_ms_per_tree", "gbdt_idle_spanned_share", "feed_idle_spanned_share"}
    want = set(NEW[workload]) - silent
    assert want <= set(line["metrics"]), sorted(want - set(line["metrics"]))
    assert not silent & set(line["metrics"])
    assert all(line["metrics"][m]["value"] > 0 for m in want)
    if workload == "higgs_gbdt_fit":
        assert 0 < line["metrics"]["hist_rows_selected_share"]["value"] <= 100
    listed = {m["name"] for m in spec.load_cell(ROOT, workload)["per_layer"]}
    assert set(NEW[workload]) <= listed
