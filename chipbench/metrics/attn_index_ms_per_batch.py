"""Sparse attention: device time under the program's ``lm.attn.index``
scope — the indexer's three projections, its LayerNorm and RoPE, and the
index scores of every block of queries — on one device inside the traced
window, per batch, all layers together. Also home of :func:`by_scope`, which
the sparse attention's readers share: device seconds of the window by the
innermost of the three ``lm.attn.*`` scopes (they lie inside
``lm.mixer.attn``, whose own reader still counts the whole mixer), and of
:func:`window_lengths` (device trace, chipbench/program_trace.py)."""

from chipbench import program_trace

SCOPES = ("lm.attn.index", "lm.attn.select", "lm.attn.sparse")


def by_scope(trace: dict) -> "dict | None":
    """Seconds by scope, or ``None`` for a program without these scopes."""
    run = program_trace.of_run(trace)
    if run is None:
        return None
    return run.seconds_by_scope(SCOPES) or None


def per_batch_ms(trace: dict, cell: dict, scopes: tuple) -> "float | None":
    batches = cell["shapes"].get("batches")
    found = by_scope(trace)
    if not found or not batches:
        return None
    seconds = sum(found.get(s, 0.0) for s in scopes)
    return 1e3 * seconds / batches if seconds > 0 else None


def window_lengths(cell: dict) -> list:
    """The real lengths of every document the window scored: the traffic
    file's chunk, once a chunk."""
    traffic = cell.get("traffic") or {}
    if "lengths" not in traffic:
        return []
    from chipbench.drivers.lm_score_stream import chunk_lengths

    return [int(n) for n in chunk_lengths(traffic)] * int(cell["shapes"].get("chunks", 0))


def read(trace: dict, cell: dict) -> "float | None":
    found = by_scope(trace)
    batches = cell["shapes"].get("batches")
    if found and batches:
        program_trace.say("attn_device_ms_per_batch_by_scope",
                          {k: 1e3 * v / batches for k, v in sorted(found.items())})
    return per_batch_ms(trace, cell, ("lm.attn.index",))
