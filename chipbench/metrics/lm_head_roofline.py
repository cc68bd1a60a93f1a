"""Language-model program: the least time the chip could take for the head's
work — the product of every real position that has a next token with the
head's matrix, ``(tokens_real - rows) x 2 h V`` operations as
``work_lm.step_flops`` counts the head (``V`` the configuration's
``vocab_size``: the held slice's width where the configuration is a share),
over the compute peak; or, if larger, the matrix read once a batch and the
final states once over the memory peak (compute-bound by two orders at
these widths) — over the device time under ``lm.head`` in the traced window
(``lm_head_ms_per_batch``). Counted on real positions and read by scope,
whatever implements the head: padding counts for nothing, so a head that
skips padded tiles cannot pass 100% (device trace,
chipbench/program_trace.py)."""

from chipbench.metrics import moe_experts_ms_per_batch as experts


def head_call(config: dict, positions: int, batches: int, batch_tokens: int) -> dict:
    """The head over ``positions`` real positions with a next token, in
    ``batches`` batches of ``batch_tokens``: one product with the (V, h)
    matrix each; the matrix and a batch's final states read once a batch
    (bfloat16), a float32 result a token."""
    h, vocab = config["hidden_size"], config["vocab_size"]
    return {"flops": 2.0 * h * vocab * positions,
            "bytes": batches * (2.0 * vocab * h + 2.0 * batch_tokens * h + 4.0 * batch_tokens)}


def read(trace: dict, cell: dict) -> "float | None":
    peaks, shapes = cell["peaks"], cell["shapes"]
    found = experts.by_scope(trace)
    if not found or not peaks or not shapes.get("tokens_real") or not shapes.get("batches"):
        return None
    seconds = found.get("lm.head", 0.0)
    if seconds <= 0:
        return None
    call = head_call(cell["config"], shapes["tokens_real"] - shapes["rows"],
                     shapes["batches"], shapes["batch_tokens"])
    least = max(call["flops"] / peaks["bf16_flops_per_s"], call["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
