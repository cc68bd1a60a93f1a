"""``gbdt_partition_ms_per_tree`` (PR 26) on a hand-made run with a known
answer, on a run that has no such scope, and its entry in BENCHMARK.json."""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_trace, spec  # noqa: E402

MS = 1e6  # ns
NAME = "gbdt_partition_ms_per_tree"


def _run(partition: bool) -> program_trace.ProgramTrace:
    """A 200 ms window, two fits; in each the loop 40..85 holds a 6 ms
    permutation and a 3 ms write-back under ``gbdt.partition``, a pad pass
    of the histogram call that shares the step, and the kernel."""
    spans, devices = [], []
    for i, off in enumerate((5, 105)):
        spans.append({"name": "gbdt.fit", "id": f"f{i}", "parent": None, "trace": f"f{i}",
                      "start": off * MS, "end": (off + 90) * MS, "attrs": {}})
        devices += [
            ["while:while.1", (off + 35) * MS, 45 * MS, ""],
            ["pad:pad.7", (off + 44) * MS, 8 * MS,
             "jit(_scan_chunk)/while/body/branch_3_fun/gbdt.hist.pad/jit(_pad)/pad"],
            ["custom-call:plane_histogram.9", (off + 53) * MS, 20 * MS,
             "jit(_scan_chunk)/while/body/branch_3_fun/plane_histogram/pallas_call"],
        ]
        if partition:
            devices += [
                ["fusion:gather_fusion.2", (off + 35) * MS, 6 * MS,
                 "jit(_scan_chunk)/while/body/gbdt.partition/branch_5_fun/gather"],
                ["fusion:dynamic-update-slice.3", (off + 41) * MS, 3 * MS,
                 "jit(_scan_chunk)/while/body/gbdt.partition/branch_5_fun/dynamic_update_slice"],
            ]
    return program_trace.ProgramTrace((0.0, 200 * MS), spans, {"/device:TPU:0": devices})


def _read(monkeypatch, run, shapes):
    reader = importlib.import_module(f"chipbench.metrics.{NAME}")
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    monkeypatch.setattr(program_trace, "say", lambda what, values: None)
    return reader.read({"window_s": 0.2}, {"shapes": shapes})


def test_partition_reader_on_a_hand_made_run(monkeypatch):
    # 6 + 3 ms a fit, two fits, four trees; the pad and the kernel of the
    # same step are the histogram's and count under their own metrics
    assert _read(monkeypatch, _run(True), {"trees": 4}) == pytest.approx(2 * 9 / 4)
    prep = importlib.import_module("chipbench.metrics.gbdt_hist_prep_ms_per_tree")
    assert prep.read({"window_s": 0.2}, {"shapes": {"trees": 4}}) == pytest.approx(2 * 8 / 4)


@pytest.mark.parametrize("case", ["no_scope", "empty", "no_trees", "no_run"])
def test_partition_reader_that_finds_nothing_returns_nothing(monkeypatch, case):
    run = {"no_scope": _run(False), "no_trees": _run(True), "no_run": None,
           "empty": program_trace.ProgramTrace((0.0, 100 * MS), [], {})}[case]
    shapes = {} if case == "no_trees" else {"trees": 4}
    assert _read(monkeypatch, run, shapes) is None


def test_partition_metric_is_declared_for_the_one_chip_fit():
    cell = spec.load_cell(ROOT, "higgs_gbdt_fit")
    entry = [m for m in cell["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1
    assert entry[0]["layer"] == "GBDT trainer" and entry[0]["source"] == "device_trace"
    assert entry[0]["moves"] == "trees_per_s" and entry[0]["unit"] == "ms"
    other = spec.load_cell(ROOT, "resnet50_featurize_stream")
    assert NAME not in [m["name"] for m in other["per_layer"]]
