"""The reader of ``moe_gmm_tiles_visited_over_aligned`` (PR 32) on hand-made
``lm.score`` spans: the window's spans decide, the counter must hold at
least as much, and a program without the kernel says nothing."""

import pytest

from chipbench import program_trace
from chipbench.metrics import lm_pad_token_share, moe_gmm_tiles_visited_over_aligned as reader


def _spans(attrs: dict) -> list:
    # the third span lies outside the window and must not count
    return [{"name": "lm.score", "id": i + 1, "parent": None, "trace": i + 1,
             "start": at * 1e9, "end": (at + 0.5) * 1e9, "attrs": dict(attrs)}
            for i, at in enumerate((1.0, 2.0, 11.0))]


def _read(monkeypatch, spans: list, counter: dict) -> "float | None":
    run = program_trace.ProgramTrace((0.0, 10e9), spans, {})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    monkeypatch.setattr(lm_pad_token_share, "counter", lambda name, label: dict(counter))
    return reader.read({"window_s": 10.0}, {"shapes": {}, "config": {}})


def test_the_ratio_is_the_windows_visits_over_its_row_tiles(monkeypatch):
    # a chunk a span: six batches of twelve layers of 256 row tiles, 27 of the 31
    # group edges inside a tile; the counter holds the third chunk too
    spans = _spans({"gmm_tiles_visited": 6 * 12 * 283, "gmm_tiles_aligned": 6 * 12 * 256})
    ratio = _read(monkeypatch, spans, {"visited": 18 * 12 * 283, "aligned": 18 * 12 * 256})
    assert ratio == pytest.approx(283 / 256) and 1.0 <= ratio <= 1.3


def test_a_counter_that_holds_less_than_the_spans_is_an_error(monkeypatch):
    spans = _spans({"gmm_tiles_visited": 600, "gmm_tiles_aligned": 512})
    for counter in ({"visited": 1199, "aligned": 1024}, {"visited": 1200, "aligned": 1023}, {}):
        with pytest.raises(ValueError, match="more than the counter holds"):
            _read(monkeypatch, spans, counter)


@pytest.mark.parametrize("attrs", [
    {"tokens_real": 5},                                   # the parent: no such attribute
    {"gmm_tiles_visited": 0, "gmm_tiles_aligned": 0},     # off a TPU: no kernel ran
], ids=["no_attribute", "no_kernel"])
def test_a_program_without_the_kernel_says_nothing(monkeypatch, attrs):
    assert _read(monkeypatch, _spans(attrs), {}) is None


def test_no_run_at_all_says_nothing(monkeypatch):
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: None)
    assert reader.read({"window_s": 0.0}, {"shapes": {}, "config": {}}) is None
