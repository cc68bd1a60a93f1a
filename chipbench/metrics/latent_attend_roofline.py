"""Latent attention: the Pallas kernel ``latent_attend`` (flash attention
over the causal tiles, the shared rotated key a separate operand) against
its roofline — the least time the chip could take for the causal pairs of
the window's real rows (``attn_pairs_roofline``'s count) over the device
time of the kernel's calls: the custom calls under the program's
``lm.attn.pairs`` scope on one device inside the traced window. The kernel
computes whole tiles, so the masked half of a diagonal tile and the padded
queries of a partly real tile are its loss by this count; a program without
the kernel reads nothing (device trace, chipbench/program_trace.py)."""

from chipbench import program_trace, xplane
from chipbench.metrics import attn_pairs_roofline as pairs

SCOPE = "lm.attn.pairs"


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
        program_trace.say("latent_attend_kernel", {"calls": calls, "seconds": seconds})
    return seconds or None


def read(trace: dict, cell: dict) -> "float | None":
    seconds = kernel_seconds(trace)
    least = pairs.least_s(cell)
    if not seconds or not least:
        return None
    return 100.0 * least / seconds
