"""Entry points, device bootstrap: union of the ``mmlspark.import`` spans
that ended before the window began — the bodies of the package
``__init__``s that hold the heavy imports (``mmlspark_tpu``,
``mmlspark_tpu.models``, ``mmlspark_tpu.models.gbdt``), nested where one
imports another (program spans, chipbench/setup_trace.py)."""

from chipbench import setup_trace


def read(trace: dict, cell: dict) -> "float | None":
    return setup_trace.named_ms(setup_trace.before_window(trace), (setup_trace.IMPORT,))
