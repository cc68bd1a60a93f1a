"""PR 30: the cell ``resnet50_featurize_partitions`` (one batch a chunk) is
declared as the issue gives it, loads at rehearsal size and comes out
``correct`` through the harness; ``feed_overlapped_call_share`` on
hand-made span lists with known answers, and on the spans of a program that
does not say how its calls started."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, program_trace, spec  # noqa: E402

MS = 1e6  # ns
CELL = "resnet50_featurize_partitions"
CHUNKS_CELL = "resnet50_featurize_stream"
NAME = "feed_overlapped_call_share"


def _run(starts: list) -> program_trace.ProgramTrace:
    """A 400 ms window of one-batch calls, 30 ms each, 10 ms apart;
    ``starts[i]`` is what call i says of itself (``None``: nothing). One
    more call lies across the window's end and one before its start."""
    spans = []

    def call(i: int, at: float, overlapped: object) -> None:
        attrs = {"rows": 8, "batches": 1}
        if overlapped is not None:
            attrs["overlapped"] = overlapped
        spans.append({"name": "featurize.partition", "id": f"p{i}", "parent": None,
                      "trace": f"p{i}", "start": at * MS, "end": (at + 32) * MS,
                      "attrs": {"rows": 8}})
        spans.append({"name": "xla_model.apply_batch", "id": f"a{i}", "parent": f"p{i}",
                      "trace": f"p{i}", "start": (at + 1) * MS, "end": (at + 31) * MS,
                      "attrs": attrs})
        spans.append({"name": "xla_model.dispatch", "id": f"d{i}", "parent": f"a{i}",
                      "trace": f"p{i}", "start": (at + 5) * MS, "end": (at + 6) * MS,
                      "attrs": {}})

    for i, overlapped in enumerate(starts):
        call(i, 5 + 40 * i, overlapped)
    call(98, -60, True)     # the warm-up's: before the window
    call(99, 385, True)     # across the window's end: not wholly inside
    return program_trace.ProgramTrace((0.0, 400 * MS), spans, {})


def _read(monkeypatch, run):
    reader = importlib.import_module(f"chipbench.metrics.{NAME}")
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    return reader.read({"window_s": 0.4}, {"shapes": {"batches": 8}})


@pytest.mark.parametrize("starts,want", [
    ([False] * 8, 0.0),                       # every call found the device empty
    ([False] + [True] + [False] * 6, 12.5),   # one of eight
    ([False] + [True] * 7, 87.5),             # every chunk but the first
    ([True] * 8, 100.0),
    ([None, False, True, True], 100 * 2 / 3),  # a call that does not say is not counted
])
def test_overlapped_share_on_a_hand_made_run(monkeypatch, starts, want):
    assert _read(monkeypatch, _run(starts)) == pytest.approx(want)


@pytest.mark.parametrize("case", ["parents_spans", "empty", "no_run"])
def test_overlapped_share_that_finds_nothing_returns_nothing(monkeypatch, case):
    """The parent's program records ``xla_model.apply_batch`` with rows and
    batches and nothing else: nothing to read, and nothing raised."""
    run = {"parents_spans": _run([None] * 8), "no_run": None,
           "empty": program_trace.ProgramTrace((0.0, 100 * MS), [], {})}[case]
    assert _read(monkeypatch, run) is None
    if case == "no_run":   # and unpatched: a reduction that no trace directory belongs to
        monkeypatch.undo()
        reader = importlib.import_module(f"chipbench.metrics.{NAME}")
        assert reader.read({"window_s": 0.123456}, {"shapes": {}}) is None


def test_the_cell_is_declared_as_the_issue_gives_it():
    cell = spec.load_cell(ROOT, CELL)
    assert cell["chips"] == 1 and cell["config_name"] == "resnet50_224"
    assert cell["driver"] == "featurize_stream" and cell["config"]["reduced"] == []
    traffic = cell["traffic"]
    assert traffic["kind"] == "chunk_stream" and traffic["trace_seconds"] == 6
    # one batch of the configuration's a chunk, the pixels the other cell makes
    assert traffic["chunk_rows"] == cell["config"]["batch_size"] == 2048
    other = spec.load_cell(ROOT, CHUNKS_CELL)["traffic"]
    assert traffic["chunk_rows"] * traffic["pool_chunks"] \
        == other["chunk_rows"] * other["pool_chunks"]
    assert traffic["check_rows_per_batch"] == other["check_rows_per_batch"] == 2
    small = spec.sized(traffic, True)
    assert (small["chunk_rows"], small["pool_chunks"]) == (8, 4)
    assert small["chunk_rows"] == spec.sized(cell["config"], True)["batch_size"]
    # it reports what the chunked cell reports, and the new share
    assert {m["name"] for m in cell["end_to_end"]} == {"rows_per_s", "setup_s"}
    listed = {m["name"] for m in cell["per_layer"]}
    assert listed == {m["name"] for m in spec.load_cell(ROOT, CHUNKS_CELL)["per_layer"]}
    entry = [m for m in cell["per_layer"] if m["name"] == NAME][0]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_span",
                     "layer": "DataFrame to device feed", "moves": "rows_per_s",
                     "workloads": [CHUNKS_CELL, CELL]}


@pytest.fixture()
def quiet_jax():
    """The harness sets process-wide JAX options; give them back."""
    import jax

    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)
    jax.clear_caches()


def _drive(capsys, *more: str) -> dict:
    rc = harness.main(["--workload", CELL, "--seed", "3000000023", "--seconds", "0.2",
                       "--trace", "0", "--rehearse", *more])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1])


def test_the_cell_runs_correct_at_rehearsal_size_and_its_control_does_not(capsys, quiet_jax):
    line = _drive(capsys, "--control")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0 and line["attempted"] % 8 == 0
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["control_correct"] is False, line["control"]


def test_a_fault_in_the_cell_comes_out_not_correct(monkeypatch, capsys, quiet_jax):
    """Rows that reach the sink as another chunk's (the order of a stream's
    results, which two chunks in flight could lose) are not ``correct``."""
    from mmlspark_tpu.io import stream

    in_order = stream._in_flight

    def swapped(src, fn):
        pair = []
        for chunk in in_order(src, fn):
            pair.append(chunk)
            if len(pair) == 2:
                yield pair[1]
                yield pair[0]
                pair.clear()
        yield from pair

    monkeypatch.setattr(stream, "_in_flight", swapped)
    line = _drive(capsys)
    assert line["correct"] is False
    assert any(c["name"] == "feature_rel_err_max" and c["value"] > c["limit"]
               for c in line["compared"])
