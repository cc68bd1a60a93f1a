"""GBDT trainer: percent of the device's idle time in the traced window
that lies inside a ``gbdt.*`` program span with no child over it — how much
of the idle time the program's own spans name. ``gbdt.fit``'s self time
and time outside every span do not count. The idle seconds by span go to
standard error beside it (device trace and program spans,
chipbench/program_trace.py)."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    return program_trace.idle_spanned_share(trace, ("gbdt.",), "gbdt.fit", "gbdt_idle_s_by_span")
