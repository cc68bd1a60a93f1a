"""Sparse attention: the Pallas kernel ``sparse_attend`` (flash attention
with the selection as its mask) against its roofline — the least time the
chip could take for the attention over the selection, per layer the larger
of operations over the compute peak and bytes over the memory peak, counted
from the shapes alone (chipbench/work_lm_sparse.py: two products of the
heads' whole width for every *selected* pair of the window's real rows; q,
k, v read once; compute-bound) — over the device time of the kernel's calls:
the custom calls under the program's ``lm.attn.sparse`` scope on one device
inside the traced window. The kernel passes over every causal tile and masks,
so the keys it computes and does not count (3.5 in 4.5 on this mix) are its
loss by this count; a program without the kernel reads nothing (device
trace, chipbench/program_trace.py)."""

from chipbench import program_trace, work_lm_sparse, xplane
from chipbench.metrics import attn_index_ms_per_batch as attn

SCOPE = "lm.attn.sparse"


def kernel_seconds(trace: dict) -> "float | None":
    run = program_trace.of_run(trace)
    if run is None:
        return None
    seconds, calls = 0.0, 0
    for name, start, dur, op_name in run.first_device():
        if start + dur <= run.lo or start >= run.hi or not xplane.is_kernel_call(name):
            continue
        if SCOPE in op_name.split("/"):
            seconds += dur * program_trace.NS
            calls += 1
    if calls:
        program_trace.say("sparse_attend_kernel", {"calls": calls, "seconds": seconds})
    return seconds or None


def read(trace: dict, cell: dict) -> "float | None":
    peaks = cell["peaks"]
    lengths = attn.window_lengths(cell)
    seconds = kernel_seconds(trace)
    if not peaks or not lengths or not seconds:
        return None
    config = cell["config"]
    least = max(work_lm_sparse.attend_flops(config, lengths) / peaks["bf16_flops_per_s"],
                work_lm_sparse.attend_bytes(config, sum(lengths)) / peaks["hbm_bytes_per_s"])
    return 100.0 * least * config["num_hidden_layers"] / seconds
