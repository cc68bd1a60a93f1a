"""Entry points, device bootstrap: over the compile requests of the set-up
that the persistent cache answered (``xla.compile`` spans with ``cache ==
"hit"``), the request's duration less its ``xla.retrieve`` child — the
cache key: the module serialised with its metadata
(``jax_compilation_cache_include_metadata_in_key`` is on), and hashed
(program spans, chipbench/setup_trace.py)."""

from chipbench import setup_trace


def read(trace: dict, cell: dict) -> "float | None":
    return setup_trace.cache_key_ms(setup_trace.before_window(trace))
