"""Sparse expert layer: device time of the experts' grouped products — the
operations under the program's ``lm.moe.experts`` scope and XLA's
grouped-matmul kernel calls (``ragged-dot``: the TPU compiler names them
itself and drops the scope) — on one device inside the traced window, per
batch, all expert layers together. Also home of :func:`by_scope`, which the
other language-model readers share: device seconds of the window by the
innermost ``lm.*`` scope (device trace, chipbench/program_trace.py)."""

from chipbench import program_trace, xplane

EXPERTS = "lm.moe.experts"
SCOPES = ("lm.embed", "lm.mixer.conv", "lm.mixer.attn", "lm.ffn.dense", "lm.moe.route",
          "lm.moe.dispatch", EXPERTS, "lm.moe.combine", "lm.head")
EXPERT_KERNEL = "ragged-dot"


def by_scope(trace: dict) -> "dict | None":
    """Seconds by scope, or ``None`` for a program without these scopes."""
    run = program_trace.of_run(trace)
    if run is None:
        return None
    out = run.seconds_by_scope(SCOPES)
    if not out:
        return None
    busy = 0.0
    for name, start, dur, op_name in run.first_device():
        if xplane.is_container(name) or start + dur <= run.lo or start >= run.hi:
            continue
        busy += dur * program_trace.NS
        if EXPERT_KERNEL in name and not any(p in SCOPES for p in op_name.split("/")):
            out[EXPERTS] = out.get(EXPERTS, 0.0) + dur * program_trace.NS
    out["all operations"] = busy
    return out


def per_batch_ms(trace: dict, cell: dict, scopes: tuple) -> "float | None":
    """A reader's whole body: summed device ms of those scopes per batch."""
    batches = cell["shapes"].get("batches")
    found = by_scope(trace)
    if not found or not batches:
        return None
    seconds = sum(found.get(s, 0.0) for s in scopes)
    return 1e3 * seconds / batches if seconds > 0 else None


def read(trace: dict, cell: dict) -> "float | None":
    found = by_scope(trace)
    batches = cell["shapes"].get("batches")
    if found and batches:
        program_trace.say("lm_device_ms_per_batch_by_scope",
                          {k: 1e3 * v / batches for k, v in sorted(found.items())})
    return per_batch_ms(trace, cell, (EXPERTS,))
