"""Span tracing with trace-id propagation and a scrape-able span buffer.

A :class:`Span` is a named host-side interval tied to a trace id. The
gateway mints a trace id per ingress request and stamps it into the
forwarded request's :data:`TRACE_HEADER`; the worker reads the header and
records its own spans under the same id — one logical request is one
trace across processes, with zero infrastructure (ids ride the existing
HTTP hop). :data:`PARENT_HEADER` carries the sender's span id the same
way, so a worker's spans parent under the gateway's forward span and the
trace collector (obs/traces.py) can assemble a true cross-process tree.

Spans land in two places:

- the default metrics registry, as the ``mmlspark_trace_span_seconds``
  histogram labeled by span name — so every span family gets a latency
  distribution for free on ``/metrics``;
- the process :class:`SpanBuffer` (:data:`BUFFER`) — a bounded ring of
  finished spans, with attrs, served as JSON on ``GET /traces`` by every
  instrumented server; the trace collector scrapes and joins these.

``Span.wall_ns`` is epoch nanoseconds, the clock of a profiler trace's
``profile_start_time``: ``core.profiling.trace`` and the benchmark write the
buffered spans beside a device capture and lay them on its timeline as they
are, which is how "queue wait vs. TPU dispatch" becomes visible. (Spans
enter no ``jax.profiler`` annotation: both captures keep the profiler's
host tracer off, so none would reach a trace.)

:func:`recent_spans` is the test/debug view of the same buffer.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Optional

from mmlspark_tpu.obs.registry import REGISTRY, histogram

# the headers the gateway stamps and workers read (lowercased: the
# WorkerServer parser lowercases header names on ingress)
TRACE_HEADER = "x-mmlspark-trace-id"
# the sender's span id: received spans set it as their parent_id so the
# cross-process tree has real edges, not name-matching heuristics
PARENT_HEADER = "x-mmlspark-parent-span"

_SPAN_SECONDS = histogram(
    "mmlspark_trace_span_seconds",
    "Duration of host-side trace spans, by span name",
    labels=("span",),
)

_tls = threading.local()

# process identity stamped onto every buffered span: the collector's
# per-hop attribution in the assembled tree. Fleet roles override it with
# something an operator recognizes ("serving@host:port").
_process_label = f"pid-{os.getpid()}"


def set_process_label(label: str) -> None:
    global _process_label
    _process_label = str(label)


def process_label() -> str:
    return _process_label

# span-name -> pre-resolved histogram child: labels() validates label
# sets per call, far too slow for per-request span recording
_span_children: dict = {}


def _span_child(name: str) -> Any:
    ch = _span_children.get(name)
    if ch is None:
        ch = _span_children[name] = _SPAN_SECONDS.labels(span=name)
    return ch

# id generation: uniqueness, not cryptography. uuid4 reads the OS entropy
# pool per call (~14 µs in sandboxed containers) — far too slow for a
# per-request hot path. pid + process-start nanos make ids unique across
# processes; the C-level counter makes them unique (and thread-safe)
# within one. Set again in a forked child, which must not mint its parent's
# ids (and being rebound, the base is process state, not a constant of the
# code: core/compile_cache.py's digest of the package leaves it out).
def _seed_ids() -> None:
    global _ID_BASE
    _ID_BASE = f"{os.getpid():08x}{time.time_ns() & 0xFFFFFFFFFFFF:012x}"


_seed_ids()
os.register_at_fork(after_in_child=_seed_ids)
_ID_SEQ = itertools.count()


def new_trace_id() -> str:
    return f"{_ID_BASE}{next(_ID_SEQ) & 0xFFFFFFFFFFFF:012x}"


def new_span_id() -> str:
    """Process-unique span id (pid+start-nanos base, counter suffix).
    Public because retroactive recorders (serving reply paths) mint a
    request span's id BEFORE recording it, so sibling spans can name it
    as their parent."""
    return f"{_ID_BASE[:8]}{next(_ID_SEQ) & 0xFFFFFFFFFFFFFFFF:016x}"


_new_span_id = new_span_id  # internal alias, kept for call-site brevity


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current_trace_id() -> Optional[str]:
    """The innermost open span's trace id on this thread, if any."""
    s = _stack()
    return s[-1].trace_id if s else None


def current_span_id() -> Optional[str]:
    """The innermost open span's id on this thread, if any: what a
    retroactive recorder (``record_span``) names as its parent."""
    s = _stack()
    return s[-1].span_id if s else None


class Span:
    """One named interval in a trace. Slotted plain class, not a
    dataclass: spans are created per request on the serving hot path and
    dataclass construction costs ~3x (measured ~1.6 µs vs ~0.5 µs in
    this container)."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "start_ns", "end_ns",
        "wall_ns", "attrs", "process",
    )

    def __init__(
        self,
        name: str,
        trace_id: str,
        span_id: str = "",
        parent_id: Optional[str] = None,
        start_ns: int = 0,
        end_ns: int = 0,
        wall_ns: int = 0,
        attrs: Optional[dict] = None,
        process: Optional[str] = None,
    ):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id or _new_span_id()
        self.parent_id = parent_id
        self.start_ns = start_ns  # perf_counter_ns: duration arithmetic
        self.end_ns = end_ns
        # wall-clock start (time_ns): perf_counter epochs differ per
        # process, so cross-process ordering in the assembled tree rides
        # this anchor instead
        self.wall_ns = wall_ns
        self.attrs = attrs
        self.process = process

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def duration_s(self) -> float:
        return self.duration_ns / 1e9

    def set_attr(self, key: str, value: Any) -> None:
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "wall_ns": self.wall_ns,
            "duration_ms": round(self.duration_ns / 1e6, 4),
            "attrs": self.attrs,
            "process": self.process or _process_label,
        }

    @staticmethod
    def from_dict(d: dict) -> "Span":
        dur_ns = int(round(float(d.get("duration_ms") or 0.0) * 1e6))
        return Span(
            name=d.get("name", ""),
            trace_id=d.get("trace_id", ""),
            span_id=d.get("span_id") or "",
            parent_id=d.get("parent_id"),
            start_ns=0,
            end_ns=dur_ns,
            wall_ns=int(d.get("wall_ns") or 0),
            attrs=d.get("attrs"),
            process=d.get("process"),
        )

    def __repr__(self) -> str:
        return (
            f"Span({self.name!r}, trace={self.trace_id}, "
            f"{self.duration_ns} ns)"
        )


class SpanBuffer:
    """Bounded ring of finished spans, safe for N recording threads and a
    concurrent scraper.

    Records are snapshotted at append time (attrs dict copied), so a
    caller mutating a span after exit can never tear a record a scraper
    already holds. ``snapshot()`` copies the ring under the lock;
    ``clear()`` mid-record is safe (an in-flight ``record`` lands in the
    post-clear ring, never half in each)."""

    def __init__(self, cap: int = 2048):
        self.cap = int(cap)
        self.enabled = True
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=self.cap)

    def record(self, sp: Span) -> None:
        if not self.enabled:
            return
        if sp.attrs is not None:
            # freeze attrs NOW: the recorder may keep mutating its dict
            sp.attrs = dict(sp.attrs)
        if sp.process is None:
            sp.process = _process_label
        with self._lock:
            self._buf.append(sp)

    def snapshot(
        self, name: Optional[str] = None, trace_id: Optional[str] = None
    ) -> list:
        with self._lock:
            spans = list(self._buf)
        return [
            s for s in spans
            if (name is None or s.name == name)
            and (trace_id is None or s.trace_id == trace_id)
        ]

    def trace_ids(self) -> list:
        """Distinct trace ids in the buffer, oldest first."""
        seen: dict = {}
        for s in self.snapshot():
            seen.setdefault(s.trace_id, None)
        return list(seen)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


_BUFFER_CAP = int(os.environ.get("MMLSPARK_TRACE_BUFFER_CAP", "2048"))
BUFFER = SpanBuffer(cap=_BUFFER_CAP)


def traces_payload(trace_id: Optional[str] = None) -> dict:
    """The ``GET /traces[/<id>]`` response body: this process's buffered
    spans (optionally one trace's) plus the registry's histogram
    exemplars — the bucket -> trace-id jump table ``fleet traces
    --slowest`` uses."""
    spans = BUFFER.snapshot(trace_id=trace_id)
    return {
        "process": _process_label,
        "count": len(spans),
        "spans": [s.to_dict() for s in spans],
        "exemplars": REGISTRY.exemplars() if trace_id is None else {},
    }


def render_traces(trace_id: Optional[str] = None) -> str:
    return json.dumps(traces_payload(trace_id))


def _record(sp: Span) -> None:
    if not REGISTRY._enabled:
        return
    _span_child(sp.name).observe(sp.duration_s)
    BUFFER.record(sp)


class _SpanContext:
    """Class-based context manager (not ``@contextmanager``: the
    generator protocol costs ~2 µs per use, and spans wrap every
    dispatched serving batch)."""

    __slots__ = ("_name", "_trace_id", "_parent_id", "_attrs", "_sp")

    def __init__(self, name: str, trace_id: Optional[str],
                 attrs: Optional[dict], parent_id: Optional[str] = None):
        self._name = name
        self._trace_id = trace_id
        self._parent_id = parent_id
        self._attrs = attrs

    def __enter__(self) -> Span:
        stack = _stack()
        parent = stack[-1] if stack else None
        sp = Span(
            name=self._name,
            trace_id=self._trace_id
            or (parent.trace_id if parent else new_trace_id()),
            parent_id=self._parent_id
            or (parent.span_id if parent else None),
            attrs=self._attrs,
        )
        stack.append(sp)
        self._sp = sp
        sp.wall_ns = time.time_ns()
        sp.start_ns = time.perf_counter_ns()
        return sp

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self._sp
        sp.end_ns = time.perf_counter_ns()
        _stack().pop()
        _record(sp)
        return False


def span(
    name: str,
    trace_id: Optional[str] = None,
    attrs: Optional[dict] = None,
    parent_id: Optional[str] = None,
) -> _SpanContext:
    """Open a span: ``with span("gateway.forward") as sp: ...``.

    Trace id resolution: explicit argument > enclosing span on this
    thread > freshly minted. Parent resolution: explicit ``parent_id``
    (e.g. a received :data:`PARENT_HEADER` value) > enclosing span on
    this thread. The span is recorded on BOTH clean and exceptional
    exit."""
    return _SpanContext(name, trace_id, attrs, parent_id)


def record_span(
    name: str,
    start_ns: int,
    end_ns: int,
    trace_id: Optional[str] = None,
    attrs: Optional[dict] = None,
    span_id: Optional[str] = None,
    parent_id: Optional[str] = None,
) -> Optional[Span]:
    """Retroactively record a span from already-measured timestamps — the
    hot-serving-path form (no context manager overhead; the timestamps
    are perf_counter_ns values the caller already had, e.g. a request's
    ``arrival_ns``). ``span_id`` lets the caller pre-mint the id (so
    sibling spans recorded in the same pass can parent under it);
    ``parent_id`` links into an upstream span (a received
    :data:`PARENT_HEADER`). Returns the span, or None when the registry
    is disabled."""
    if not REGISTRY._enabled:
        return None
    now_ns = time.perf_counter_ns()
    sp = Span(
        name=name,
        trace_id=trace_id or new_trace_id(),
        span_id=span_id or "",
        parent_id=parent_id,
        start_ns=start_ns,
        end_ns=end_ns,
        # wall anchor reconstructed from "how long ago did it start"
        wall_ns=time.time_ns() - (now_ns - start_ns),
        attrs=attrs,
    )
    _record(sp)
    return sp


def recent_spans(
    name: Optional[str] = None, trace_id: Optional[str] = None
) -> list:
    """Most-recent finished spans (the process SpanBuffer), filtered."""
    return BUFFER.snapshot(name=name, trace_id=trace_id)


def clear_recent_spans() -> None:
    BUFFER.clear()
