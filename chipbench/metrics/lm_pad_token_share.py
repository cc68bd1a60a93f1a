"""DataFrame to device feed: of the token positions the scorer sent to the
device in the traced window, the percent that were right padding (of rows to
their bucket's length, of batches to their size) —
``mmlspark_lm_tokens_total{kind="padded"}`` over both kinds. A counter has
no history, so the window's part of it is what the ``lm.score`` spans inside
the window say they added (attrs ``tokens_real`` / ``tokens_padded``); the
counter itself must hold at least that much (program counter,
chipbench/program_trace.py)."""

from chipbench import program_trace


def counter(name: str, label: str) -> dict:
    try:
        from mmlspark_tpu import obs
    except ImportError:
        return {}
    fam = obs.REGISTRY.snapshot().get(name) or {}
    return {labels.get(label): value for labels, value in fam.get("samples", [])}


def read(trace: dict, cell: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    spans = run.in_window("lm.score")
    real = sum(s["attrs"].get("tokens_real", 0) for s in spans)
    padded = sum(s["attrs"].get("tokens_padded", 0) for s in spans)
    if real + padded <= 0:
        return None
    total = counter("mmlspark_lm_tokens_total", "kind")
    if total.get("real", 0) < real or total.get("padded", 0) < padded:
        raise ValueError(f"the window's spans add {real}/{padded} tokens, more than "
                         f"the counter holds ({total})")
    program_trace.say("lm_tokens", {"real": real, "padded": padded, "counter": total})
    return 100.0 * padded / (real + padded)
