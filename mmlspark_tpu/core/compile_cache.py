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

# jax.monitoring's duration events of a program's first call, in the order
# they fire: the function traced to a jaxpr (a ``jit`` inside it fires its
# own, nested), the jaxpr lowered to an MLIR module (a Pallas kernel's Mosaic
# lowering happens here), then the whole compile request: the cache key (the
# module serialised with its metadata, and hashed), and a backend
# compilation or, when the persistent cache hits, a retrieval in its place
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_EVENTS = (_TRACE_EVENT, _LOWER_EVENT, _COMPILE_EVENT, _RETRIEVAL_EVENT)

_M_COMPILES = obs.counter(
    "mmlspark_xla_compiles_total",
    "XLA compile requests of this process, by what answered them: "
    "cache=hit (loaded from the persistent compile cache) | miss (compiled "
    "by the backend)",
    labels=("cache",),
)
_tls = threading.local()
_listening = False


# the library's own jitted functions (``jnp.add``, ``jax.random.uniform``,
# ...) fire a trace event each inside a program's trace: thousands a
# program, a fraction of a millisecond each, enough to push every other
# span out of the ring. A trace shorter than this is held back until the
# thread's next event tells whether a longer one contained it
_NESTED_TRACE_FLOOR_S = 0.005
_HELD_MAX = 64


def _record(name: str, start_ns: int, end_ns: int, attrs: dict, **ids: object) -> None:
    ids.setdefault("trace_id", obs.current_trace_id())
    ids.setdefault("parent_id", obs.current_span_id())
    obs.record_span(name, start_ns, end_ns, attrs=attrs, **ids)


def _flush_held(held: list) -> None:
    """Short traces that no later trace contained: each was a call's own."""
    for start_ns, end_ns, fun, ids in held:
        _record("xla.trace", start_ns, end_ns, {"fun": fun}, **ids)
    del held[:]


def _on_duration(event: str, duration: float, **kw: object) -> None:
    """A first call's stages as spans ending now, under the span that was
    open when the call was made: which call traced, lowered and compiled,
    and when, on the clock of every other span. Spans of one thread may
    nest (a ``jit`` inside a ``jit`` fires its own ``xla.trace``): a reader
    takes the union of their intervals, never the sum of their durations."""
    if event == _SAVED_EVENT:
        # fires on a hit, just before the retrieval's own event; no interval
        _tls.saved = duration
        return
    if event not in _EVENTS or not obs.enabled():
        return
    end_ns = time.perf_counter_ns()
    start_ns = end_ns - int(duration * 1e9)
    fun = str(kw.get("fun_name", ""))
    held = getattr(_tls, "held", None)
    if held is None:
        held = _tls.held = []
    # what this event contains was nested in it (lowering traces too: a
    # primitive lowered through a Python function); a thread's events nest
    # or follow each other, so those are the last ones held
    while held and held[-1][0] >= start_ns:
        held.pop()
    if event == _TRACE_EVENT:
        if duration >= _NESTED_TRACE_FLOOR_S:
            _record("xla.trace", start_ns, end_ns, {"fun": fun})
        elif len(held) < _HELD_MAX:
            # (inside one long event the list only grows, with traces that
            # event will take out: past the cap they are not kept at all)
            held.append((start_ns, end_ns, fun, {"trace_id": obs.current_trace_id(),
                                                 "parent_id": obs.current_span_id()}))
        return
    # lowering and compiling follow a call's outermost trace: none is open
    _flush_held(held)
    if event == _LOWER_EVENT:
        _record("xla.lower", start_ns, end_ns, {"fun": fun})
    elif event == _RETRIEVAL_EVENT:
        # the request's ``xla.compile`` span is recorded when the request
        # ends; its ids are minted here, so that the retrieval is its child
        ids = {"span_id": obs.new_span_id(),
               "trace_id": obs.current_trace_id() or obs.new_trace_id()}
        # saved_s: the backend time of the process that compiled this
        # program, less this retrieval
        _tls.hit = (ids, {"retrieval_s": duration, "saved_s": getattr(_tls, "saved", None)})
        _record("xla.retrieve", start_ns, end_ns, {}, trace_id=ids["trace_id"],
                parent_id=ids["span_id"])
    else:
        # with the retrieval a child, the span's self time on a hit is the
        # cache key (the module serialised and hashed) and no more
        ids, on_hit = getattr(_tls, "hit", None) or ({}, None)
        _tls.hit = _tls.saved = None
        cache = "miss" if on_hit is None else "hit"
        _M_COMPILES.labels(cache=cache).inc()
        _record("xla.compile", start_ns, end_ns,
                {"event": event, "cache": cache, "fun": fun, **(on_hit or {})}, **ids)


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` set from outside always wins: JAX reads
    the variable itself, so nothing is set in code and child processes
    inherit it untouched. Otherwise the cache is ``<checkout>/.jax_cache``.
    Must run before the process's first compilation (JAX opens the cache
    once). Also starts recording every program's first call
    (``xla.trace``, ``xla.lower``, ``xla.compile`` and ``xla.retrieve``
    spans, ``mmlspark_xla_compiles_total``)."""
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
