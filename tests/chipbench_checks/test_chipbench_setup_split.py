"""The four readers that split ``setup_s`` (PR 35), checked without a chip:
each on hand-made spans with known answers (nested traces, an orphan
compile request, a hit with and a miss without its retrieval, spans that
straddle the window's start), silent without the spans, and their entries."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_trace, setup_trace, spec, xplane  # noqa: E402

MS = 1e6  # ns
NAMES = ["setup_program_ms", "setup_import_ms", "setup_trace_lower_ms", "setup_cache_key_ms"]
CELLS = ["resnet50_featurize_stream", "higgs_gbdt_fit", "resnet50_featurize_partitions"]
T0 = 2.0 ** 50   # the clock's start, ns: spans are floats, exact to 0.25 ns here
WINDOW = 1000.0   # the window begins that many ms after T0


def _span(name, sid, parent, start, end, **attrs):
    return {"name": name, "id": sid, "parent": parent, "trace": "t",
            "start": T0 + start * MS, "end": T0 + end * MS, "attrs": attrs}


def _cold_run() -> list:
    """A set-up of one second. 0..100 and 150..400 imports (the second holds
    a nested one); 420..470 an orphan request of the harness (a hit: key 4,
    retrieval 16); 500..900 the warm-up's root, whose first dispatch traces
    (with a nested trace and a sibling one), lowers and loads (a hit: key 30,
    retrieval 70) one program and compiles another (a miss, no retrieval);
    a span that straddles the window's start and spans inside the window."""
    return [
        _span("mmlspark.import", "i0", None, 0, 100, module="mmlspark_tpu"),
        _span("mmlspark.import", "i1", None, 150, 400, module="mmlspark_tpu.models"),
        _span("mmlspark.import", "i2", "i1", 200, 260, module="mmlspark_tpu.models.gbdt"),
        _span("xla.trace", "ot", None, 420, 430, fun="_uniform"),
        _span("xla.lower", "ol", None, 430, 450, fun="jit(_uniform)"),
        _span("xla.compile", "oc", None, 450, 470, fun="jit(_uniform)", cache="hit",
              retrieval_s=0.016, saved_s=1.5),
        _span("xla.retrieve", "or", "oc", 454, 470),
        _span("featurize.partition", "p", None, 500, 900, rows=8),
        _span("xla_model.apply_batch", "a", "p", 505, 890, program_new=True, shape=[8, 4]),
        _span("xla_model.dispatch", "d", "a", 510, 800),
        _span("xla.trace", "t0", "d", 520, 600, fun="run"),
        _span("xla.trace", "t1", "d", 530, 560, fun="kernel"),       # nested in t0
        _span("xla.trace", "t2", "d", 600, 610, fun="epilogue"),     # beside it
        _span("xla.lower", "l0", "d", 610, 650, fun="jit(run)"),
        _span("xla.compile", "c0", "d", 650, 750, fun="jit(run)", cache="hit",
              retrieval_s=0.070, saved_s=25.0),
        _span("xla.retrieve", "r0", "c0", 680, 750),
        _span("xla.lower", "l1", "d", 760, 770, fun="jit(tail)"),
        _span("xla.compile", "c1", "d", 770, 800, fun="jit(tail)", cache="miss"),
        # not the set-up's: over the window's start, and inside the window
        _span("featurize.partition", "s", None, 950, 1050),
        _span("mmlspark.import", "i3", None, 990, 1001, module="late"),
        _span("xla.trace", "t3", None, 1100, 1200, fun="reference"),
        _span("xla.compile", "c2", None, 1200, 1300, fun="jit(reference)", cache="hit"),
    ]


@pytest.fixture()
def cold(monkeypatch):
    """The hand-made run in the place of the process's ring and of the trace
    directory; what the readers say on standard error is kept."""
    said = {}
    harness = [["chipbench.setup", T0, 900 * MS], [xplane.WINDOW_SPAN, T0 + WINDOW * MS, 200 * MS]]
    monkeypatch.setattr(program_trace, "find_run", lambda reduced: ("/nowhere", harness))
    monkeypatch.setattr(program_trace, "program_spans", lambda since: _cold_run())
    monkeypatch.setattr(program_trace, "say", lambda what, values: said.update({what: values}))
    return said


def _read(name, reduced=None):
    reader = importlib.import_module(f"chipbench.metrics.{name}")
    return reader.read(reduced or {"window_s": 0.2}, {"shapes": {}})


def test_import_is_the_union_of_the_import_spans_before_the_window(cold):
    assert _read("setup_import_ms") == pytest.approx(100 + 250)   # the nested 60 inside


def test_trace_lower_is_a_union_never_a_sum(cold):
    # orphan 420..450; under the dispatch 520..650 (the nested trace inside) and 760..770
    assert _read("setup_trace_lower_ms") == pytest.approx(30 + 130 + 10)
    rows = cold["setup_first_calls"]
    assert [r["fun"] for r in rows] == ["jit(run)", "jit(tail)", "jit(_uniform)"]
    run, tail, orphan = rows
    assert run == {"fun": "jit(run)", "cache": "hit", "trace_ms": pytest.approx(90.0),
                   "lower_ms": pytest.approx(40.0), "compile_ms": pytest.approx(100.0),
                   "key_ms": pytest.approx(30.0), "retrieve_ms": pytest.approx(70.0),
                   "saved_s": 25.0, "under": "xla_model.dispatch",
                   "under_ms": pytest.approx(290.0), "shape": [8, 4],
                   "traces": [["run", pytest.approx(80.0)], ["kernel", pytest.approx(30.0)],
                              ["epilogue", pytest.approx(10.0)]]}
    assert tail["cache"] == "miss" and tail["key_ms"] is None and tail["retrieve_ms"] is None
    assert tail["trace_ms"] == 0.0 and tail["lower_ms"] == pytest.approx(10.0)
    assert tail["compile_ms"] == pytest.approx(30.0) and tail["under"] == "xla_model.dispatch"
    # the harness's own programs: under no span, summed by fun
    assert orphan["under"] is None and orphan["requests"] == 1
    assert orphan["key_ms"] == pytest.approx(4.0) and orphan["saved_s"] == 1.5


def test_cache_key_is_a_hits_time_less_its_retrieval(cold):
    # jit(_uniform) 20 - 16, jit(run) 100 - 70; the miss and the window's hit do not count
    assert _read("setup_cache_key_ms") == pytest.approx(4 + 30)


def test_program_is_the_union_of_every_span_before_the_window(cold):
    # imports 0..100, 150..400; the orphan request 420..470; the root 500..900
    assert _read("setup_program_ms") == pytest.approx(100 + 250 + 50 + 400)
    assert cold["setup_program_ms_by_root"] == pytest.approx({
        "mmlspark.import": 350.0, "featurize.partition": 400.0, "xla.trace": 10.0,
        "xla.lower": 20.0, "xla.compile": 20.0})
    assert cold["setup_spans"] == {"before_window": 18, "whole_run": 22}


def test_the_parts_lie_inside_the_whole(cold):
    compile_ms = program_trace.setup_compile_ns({"window_s": 0.2}) / MS
    assert compile_ms == pytest.approx(20 + 100 + 30)
    parts = _read("setup_import_ms") + _read("setup_trace_lower_ms") + compile_ms
    assert parts <= _read("setup_program_ms")


def test_a_hit_without_its_retrieval_counts_whole_and_a_run_of_misses_is_silent(cold, monkeypatch):
    spans = [s for s in _cold_run() if s["id"] != "or"]
    monkeypatch.setattr(program_trace, "program_spans", lambda since: spans)
    assert _read("setup_cache_key_ms") == pytest.approx(20 + 30)
    compiling = [dict(s, attrs=dict(s["attrs"], cache="miss")) for s in _cold_run()
                 if s["name"] != "xla.retrieve"]
    monkeypatch.setattr(program_trace, "program_spans", lambda since: compiling)
    assert _read("setup_cache_key_ms") is None
    assert _read("setup_trace_lower_ms") == pytest.approx(170.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_reads_nothing(cold, monkeypatch, name):
    # the parent's program: its warm-up's spans and xla.compile with retrieval_s alone
    parent = [s for s in _cold_run()
              if s["name"] not in ("mmlspark.import", "xla.trace", "xla.lower", "xla.retrieve")]
    monkeypatch.setattr(program_trace, "program_spans", lambda since: parent)
    assert _read(name) is None
    assert cold == {}
    monkeypatch.setattr(program_trace, "program_spans", lambda since: [])
    assert _read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_reduction_that_no_traced_run_left_reads_nothing(name):
    assert _read(name, {"window_s": 0.0}) is None
    assert _read(name, {"window_s": 0.123456}) is None
    assert _read(name, {}) is None


def test_the_four_entries_list_the_three_older_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-4:]] == NAMES
    for m in bench["per_layer"][-4:]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "entry points, device bootstrap",
                     "moves": "setup_s", "workloads": CELLS}
    listed = {cell: {m["name"] for m in spec.load_cell(ROOT, cell)["per_layer"]}
              for cell in [w["name"] for w in bench["workloads"]]}
    for cell, names in listed.items():
        assert set(NAMES) <= names if cell in CELLS else not set(NAMES) & names, cell
    assert listed["resnet50_featurize_stream"] == listed["resnet50_featurize_partitions"]
