"""Latent attention: device time under the program's ``lm.attn.latent``
scope — the low-rank projections of queries and of the key-value latent,
their norms, the rotation and the expansion to the heads' keys and values —
on one device inside the traced window, per batch, all layers together.
Also home of :func:`by_scope`, which this cell's other scope readers share:
device seconds of the window by the innermost of ``lm.attn.latent``,
``lm.attn.pairs`` (both lie inside ``lm.mixer.attn``, whose own reader still
counts the whole mixer) and ``lm.ffn.shared`` (device trace,
chipbench/program_trace.py)."""

from chipbench import program_trace

SCOPES = ("lm.attn.latent", "lm.attn.pairs", "lm.ffn.shared")


def by_scope(trace: dict) -> "dict | None":
    """Seconds by scope, or ``None`` for a program without these scopes."""
    run = program_trace.of_run(trace)
    if run is None:
        return None
    return run.seconds_by_scope(SCOPES) or None


def per_batch_ms(trace: dict, cell: dict, scope: str) -> "float | None":
    batches = cell["shapes"].get("batches")
    found = by_scope(trace)
    if not found or not batches or found.get(scope, 0.0) <= 0:
        return None
    return 1e3 * found[scope] / batches


def read(trace: dict, cell: dict) -> "float | None":
    found = by_scope(trace)
    batches = cell["shapes"].get("batches")
    if found and batches:
        program_trace.say("mla_device_ms_per_batch_by_scope",
                          {k: 1e3 * v / batches for k, v in sorted(found.items())})
    return per_batch_ms(trace, cell, "lm.attn.latent")
