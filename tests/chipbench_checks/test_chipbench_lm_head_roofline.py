"""The reader of ``lm_head_roofline`` (PR 34) on hand-made device traces of
the three language-model cells: the head's product for the window's real
positions that have a next token, over the bf16 peak, over the device time
under ``lm.head`` — whatever runs there (the parent's loop of XLA fusions,
the kernel ``head_logprobs``); it reads nothing without the scope, and a
head that skips padded tiles cannot pass 100%."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_trace, spec, work_lm, work_lm_mla, work_lm_sparse  # noqa: E402
from chipbench.drivers import lm_score_stream  # noqa: E402
from chipbench.metrics import lm_head_ms_per_batch, lm_head_roofline as reader  # noqa: E402

CELLS = ("lfm2_score_corpus", "keye_score_long_docs", "deepseek_v2_score_docs")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1e6  # ns


def _facts(workload: str, chunks: int = 1) -> dict:
    """What the harness hands a reader after a window of ``chunks`` chunks."""
    cell = spec.load_cell(ROOT, workload)
    lengths = lm_score_stream.chunk_lengths(cell["traffic"])
    buckets = cell["traffic"]["buckets"]
    held = [lm_score_stream._bucket_of(cell["traffic"], n) for n in lengths]
    batches = sum(-(-held.count(length) // rows) for length, rows in buckets)
    return {"shapes": {"rows": chunks * len(lengths), "chunks": chunks,
                       "batches": chunks * batches,
                       "batch_tokens": max(length * rows for length, rows in buckets),
                       "tokens_real": chunks * int(lengths.sum())},
            "config": cell["config"], "traffic": cell["traffic"], "chips": 1, "devices": 1,
            "peaks": PEAKS}


def _read(monkeypatch, facts: dict, ops: list) -> "float | None":
    run = program_trace.ProgramTrace((0.0, 1e12), [], {"/device:TPU:0": ops})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    return reader.read({"window_s": 1000.0}, facts)


def _head_flops(facts: dict) -> float:
    shapes, config = facts["shapes"], facts["config"]
    return (shapes["tokens_real"] - shapes["rows"]) * 2.0 * config["hidden_size"] \
        * config["vocab_size"]


@pytest.mark.parametrize("workload,h,vocab", [
    ("lfm2_score_corpus", 2048, 65_536), ("keye_score_long_docs", 2048, 151_936),
    ("deepseek_v2_score_docs", 5120, 12_800)], ids=CELLS)
def test_the_count_is_the_heads_product_over_real_positions(workload, h, vocab):
    facts = _facts(workload)
    shapes, config = facts["shapes"], facts["config"]
    assert (config["hidden_size"], config["vocab_size"]) == (h, vocab)
    call = reader.head_call(config, shapes["tokens_real"] - shapes["rows"],
                            shapes["batches"], shapes["batch_tokens"])
    assert call["flops"] == (shapes["tokens_real"] - shapes["rows"]) * 2.0 * h * vocab
    # the matrix once a batch, the final states once, a float32 a token
    assert call["bytes"] == shapes["batches"] * (
        2.0 * vocab * h + 2.0 * shapes["batch_tokens"] * h + 4.0 * shapes["batch_tokens"])
    assert call["flops"] / 197e12 > 10 * call["bytes"] / 819e9      # compute-bound
    # padding counts for nothing: less than the padded positions' product
    assert call["flops"] < shapes["batches"] * shapes["batch_tokens"] * 2.0 * h * vocab


def test_the_count_is_the_head_term_of_each_cells_step_count():
    """``step_flops`` less the same count with a head of no ids: what the
    three accepted whole-step counts add for the head, cell by cell."""
    for workload, count in zip(CELLS, (
            lambda c, n: work_lm.step_flops(c, len(n), int(n.sum()), float((n.astype(float) ** 2).sum())),
            lambda c, n: work_lm_sparse.step_flops(c, [int(x) for x in n]),
            lambda c, n: work_lm_mla.step_flops(c, [int(x) for x in n]))):
        facts = _facts(workload)
        lengths = lm_score_stream.chunk_lengths(facts["traffic"])
        config = facts["config"]
        head = count(config, lengths) - count(dict(config, vocab_size=0), lengths)
        assert head == pytest.approx(_head_flops(facts), rel=1e-12), workload


@pytest.mark.parametrize("workload", CELLS)
def test_the_share_is_the_least_time_over_the_scopes_time(monkeypatch, workload):
    facts = _facts(workload, chunks=2)
    least_ms = 1e3 * _head_flops(facts) / 197e12
    # the parent's head: a loop (a container: counts nowhere) of fusions, the norm before it
    parent = [
        ["fusion:f.1", 0 * MS, 1 * MS, "jit(run)/lm.head/mul"],
        ["while:w.1", 1 * MS, 2 * least_ms * MS, "jit(run)/lm.head/while"],
        ["fusion:f.2", 1 * MS, (2 * least_ms - 1) * MS, "jit(run)/lm.head/while/body/dot_general"],
        ["fusion:f.3", 5e5 * MS, 7 * MS, "jit(run)/lm.mixer.attn/dot_general"],
    ]
    assert _read(monkeypatch, facts, parent) == pytest.approx(50.0)
    # the kernel's custom call carries the scope too
    change = [
        ["fusion:f.1", 0 * MS, 1 * MS, "jit(run)/lm.head/mul"],
        ["custom-call:head_logprobs.7", 1 * MS, (1.25 * least_ms - 1) * MS,
         "jit(run)/lm.head/pallas_call"],
        ["fusion:f.3", 5e5 * MS, 7 * MS, "jit(run)/lm.mixer.attn/dot_general"],
    ]
    share = _read(monkeypatch, facts, change)
    assert share == pytest.approx(80.0)
    ms = lm_head_ms_per_batch.read({"window_s": 1000.0}, facts)
    assert ms == pytest.approx(1.25 * least_ms / facts["shapes"]["batches"])


@pytest.mark.parametrize("workload", CELLS)
def test_a_head_that_skips_every_padded_position_stays_under_100(monkeypatch, workload):
    """The fastest head there can be: the padded positions' product at the
    peak, less the tiles it may skip — never less than the real positions'."""
    facts = _facts(workload)
    shapes, config = facts["shapes"], facts["config"]
    padded = shapes["batches"] * shapes["batch_tokens"]
    assert padded > shapes["tokens_real"]
    at_peak_ms = 1e3 * shapes["tokens_real"] * 2.0 * config["hidden_size"] \
        * config["vocab_size"] / 197e12
    ops = [["custom-call:head_logprobs.7", 0.0, at_peak_ms * MS, "jit(run)/lm.head/pallas_call"]]
    share = _read(monkeypatch, facts, ops)
    assert 99.0 < share < 100.0      # the rows' last positions have no next token


def test_it_reads_nothing_without_the_scope_the_peaks_or_the_run(monkeypatch):
    facts = _facts("keye_score_long_docs")
    other = [["fusion:f.3", 0.0, 7 * MS, "jit(run)/lm.mixer.attn/dot_general"]]
    assert _read(monkeypatch, facts, other) is None
    head = [["fusion:f.2", 0.0, 7 * MS, "jit(run)/lm.head/dot_general"]]
    assert _read(monkeypatch, dict(facts, peaks=None), head) is None
    assert _read(monkeypatch, dict(facts, shapes={"batches": 6, "rows": 18}), head) is None
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: None)
    assert reader.read({"window_s": 0.0}, facts) is None
