"""Where the persistent XLA compile cache lives — decided in ONE place.

Every entry point that compiles (the ``fleet`` roles, the benchmark's
``chipbench/harness.py``, ``chip_smoke.py``'s phases,
``tests/conftest.py``) calls
:func:`enable_compile_cache` before its first dispatch. The directory is
part of the cache key's neighbourhood: a directory that moves never hits,
so it is never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os
import threading
import time

import jax

from mmlspark_tpu import obs

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# jax.monitoring's duration events around one compile request: the whole
# request (a backend compilation, or a retrieval from the persistent cache
# in its place) and, inside it, the retrieval when the cache hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_M_COMPILES = obs.counter(
    "mmlspark_xla_compiles_total",
    "XLA compile requests of this process, by what answered them: "
    "cache=hit (loaded from the persistent compile cache) | miss (compiled "
    "by the backend)",
    labels=("cache",),
)
_tls = threading.local()
_listening = False


def _on_duration(event: str, duration: float, **kw: object) -> None:
    """Each compile request as an ``xla.compile`` span ending now, under
    the span that was open when it was made: which call compiled, and
    when, on the clock of every other span."""
    if event == _RETRIEVAL_EVENT:
        _tls.retrieved = duration
        return
    if event != _COMPILE_EVENT:
        return
    retrieved = getattr(_tls, "retrieved", None)
    _tls.retrieved = None
    cache = "miss" if retrieved is None else "hit"
    _M_COMPILES.labels(cache=cache).inc()
    end_ns = time.perf_counter_ns()
    attrs = {"event": event, "cache": cache, "fun": str(kw.get("fun_name", ""))}
    if retrieved is not None:
        attrs["retrieval_s"] = retrieved
    obs.record_span(
        "xla.compile", end_ns - int(duration * 1e9), end_ns,
        trace_id=obs.current_trace_id(), parent_id=obs.current_span_id(),
        attrs=attrs,
    )


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` set from outside always wins: JAX reads
    the variable itself, so nothing is set in code and child processes
    inherit it untouched. Otherwise the cache is ``<checkout>/.jax_cache``.
    Must run before the process's first compilation (JAX opens the cache
    once). Also starts recording the process's compile requests
    (``xla.compile`` spans, ``mmlspark_xla_compiles_total``)."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    # JAX leaves op metadata (named scopes, kernel names) out of the cache
    # key by default, so a program that differs from a cached one only by
    # names loads that one's executable, and a device trace then shows the
    # other program's names (seen on the chip: PERF.md section 6, PR 25).
    # With the metadata in the key, what a trace names is what was written
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    outer = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outer:
        return outer
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
