"""GBDT trainer: host time a fit spends handing ``train()`` its columns —
the program's ``gbdt.gather`` span (the feature matrix, the label and the
optional weight, init-score and validation columns out of the DataFrame's
partitions) under each ``gbdt.fit`` root of the traced window, mean per fit
(program spans, chipbench/program_trace.py). The part of
``gbdt_fit_fixed_ms`` that ``gbdt_bin_ms_per_fit`` and
``gbdt_upload_ms_per_fit`` do not name."""

from chipbench import program_trace


def read(trace: dict, cell: dict) -> "float | None":
    return program_trace.per_root_ms(trace, "gbdt.fit", ("gbdt.gather",))
