"""The program's own view of a traced run, laid on the device trace.

``xplane.read_events`` gives the per-layer metrics the device's operations
and the harness's spans (``chipbench.<name>``: one span around a whole
``fit`` or ``transform``). This helper adds what the program records about
itself, on the same clock (nanoseconds from the profile's start):

- its spans, from ``obs.recent_spans()`` of this process — the harness's
  child is the process that ran the window — with ids, parents and attrs.
  ``Span.wall_ns`` is epoch nanoseconds, the clock of the trace's
  ``profile_start_time``, so they move onto the profile's clock exactly as
  ``xplane.read_events`` moves the harness's;
- the device operations of this run's own trace with the scope each
  carries. ``jax.profiler.ProfileData`` does not hand out the statistics
  kept with an operation's *metadata*, and that is where the TPU profiler
  puts the HLO ``op_name`` (stat ``tf_op``, e.g.
  ``jit(_scan_chunk)/while/body/gbdt.hist.mask/mul:``), so the
  ``.xplane.pb`` is read here with a few lines of protobuf wire decoding
  and nothing installed. A fusion carries the ``op_name`` of its root.

A reader is handed ``(reduced, facts)`` and neither names the cell. The
run's trace directory is found by its content: the harness clears
``.chipbench_trace/<workload>/`` before a traced run and writes
``spans.json`` there, with the ``chipbench.window`` span, before any reader
runs; the directory whose window span has the duration the reduction was
made from (``reduced["window_s"]``), newest first, is this run's.

A side entrance: the next ``benchmark`` PR should hand ``obs.recent_spans()``
to ``xplane.read_events`` and retire it (PERF.md section 7).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys

from chipbench import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(ROOT, ".chipbench_trace")
PROGRAM_SPANS_FILE = "program_spans.json"
NS = 1e-9


# -- the .xplane.pb, with the statistics of each operation's metadata --------

def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """``(field, wire_type, value)`` of one protobuf message; a
    length-delimited value is a ``memoryview`` slice, not a copy."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wt} in an xplane")
        yield field, wt, val


def _text(v: object) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf: bytes) -> tuple:
    """An XStat: ``(metadata_id, value)``; a ``ref_value`` comes back as
    ``("ref", stat_metadata_id)``."""
    key, val = 0, None
    for f, _wt, v in _fields(buf):
        if f == 1:
            key = v
        elif f in (3, 4):
            val = v
        elif f in (5, 6):
            val = _text(v)
        elif f == 7:
            val = ("ref", v)
    return key, val


def _map_entry(buf: bytes) -> tuple:
    key, val = 0, b""
    for f, _wt, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf: bytes) -> dict:
    """An XPlane: its name, its lines (raw), its event and stat metadata."""
    out = {"name": "", "lines": [], "events": {}, "stats": {}, "plane_stats": []}
    for f, _wt, v in _fields(buf):
        if f == 2:
            out["name"] = _text(v)
        elif f == 3:
            out["lines"].append(v)
        elif f == 4:
            key, em = _map_entry(v)
            name, stats = "", []
            for ef, _ewt, ev in _fields(em):
                if ef == 2:
                    name = _text(ev)
                elif ef == 5:
                    stats.append(_stat(ev))
            out["events"][key] = (name, stats)
        elif f == 5:
            key, sm = _map_entry(v)
            for sf, _swt, sv in _fields(sm):
                if sf == 2:
                    out["stats"][key] = _text(sv)
        elif f == 6:
            out["plane_stats"].append(_stat(v))
    return out


SCOPE_STAT = "tf_op"   # the HLO op_name, as the TPU profiler names the stat


def read_scoped_events(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns, op_name], ...]},
    "profile_start_ns": epoch ns}``: the ``XLA Ops`` line of every device
    plane, named and timed as ``xplane.read_events`` names and times them,
    each with the HLO ``op_name`` it carries ("" where it carries none)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices: dict = {}
    start = None
    for f, _wt, v in _fields(space):
        if f != 1:
            continue
        plane = _plane(v)
        stat_names = plane["stats"]
        if plane["name"] == "Task Environment":
            for key, val in plane["plane_stats"]:
                if stat_names.get(key) == "profile_start_time":
                    start = int(val)
        if not plane["name"].startswith(xplane.DEVICE_PLANE):
            continue
        named: dict = {}
        for key, (name, stats) in plane["events"].items():
            scope = ""
            for sk, sv in stats:
                if stat_names.get(sk) == SCOPE_STAT:
                    if isinstance(sv, tuple):
                        sv = stat_names.get(sv[1], "")
                    scope = str(sv).rstrip(":")
            named[key] = (xplane.short_name(name), scope)
        ops = devices.setdefault(plane["name"], [])
        for line in plane["lines"]:
            line_name, t0_ns, events = "", 0, []
            for lf, _lwt, lv in _fields(line):
                if lf == 2:
                    line_name = _text(lv)
                elif lf == 3:
                    t0_ns = lv
                elif lf == 4:
                    events.append(lv)
            if line_name != xplane.OPS_LINE:
                continue
            for ev in events:
                meta = offset_ps = dur_ps = 0
                for ef, _ewt, evv in _fields(ev):
                    if ef == 1:
                        meta = evv
                    elif ef == 2:
                        offset_ps = evv
                    elif ef == 3:
                        dur_ps = evv
                name, scope = named.get(meta, ("", ""))
                ops.append([name, t0_ns + offset_ps / 1000.0, dur_ps / 1000.0, scope])
    return {"devices": devices, "profile_start_ns": start}


# -- the run: its trace directory, its window, the program's spans -----------

def find_run(reduced: dict, trace_root: str = TRACE_ROOT) -> "tuple | None":
    """``(trace_dir, harness_spans)`` of the traced run ``reduced`` was made
    from (see the module's docstring), or ``None``."""
    want = reduced.get("window_s")
    if not want:
        return None
    best = None
    for path in glob.glob(os.path.join(trace_root, "*", "spans.json")):
        try:
            with open(path) as f:
                spans = json.load(f)
        except (OSError, ValueError):
            continue
        for name, _start, dur in spans:
            if name == xplane.WINDOW_SPAN and abs(dur * NS - want) < 1e-6:
                mtime = os.path.getmtime(path)
                if best is None or mtime > best[0]:
                    best = (mtime, os.path.dirname(path), spans)
    return None if best is None else best[1:]


def program_spans(since_epoch_ns: "float | None") -> list:
    """This process's finished ``obs`` spans as plain rows on the epoch
    clock. The buffer is a ring: if it is full and its oldest span began
    after ``since_epoch_ns`` (``None``: the start of the process), spans of
    the asked-for stretch were pushed out, and a wrapped window is never
    read as a short one."""
    try:
        from mmlspark_tpu import obs
    except ImportError:
        return []
    spans = obs.recent_spans()
    if len(spans) >= obs.BUFFER.cap and (
            since_epoch_ns is None or min(s.wall_ns for s in spans) > since_epoch_ns):
        raise RuntimeError(
            f"the span buffer wrapped ({len(spans)} spans): it no longer holds "
            "everything since the start that was asked for")
    return [{"name": s.name, "id": s.span_id, "parent": s.parent_id, "trace": s.trace_id,
             "start": float(s.wall_ns), "end": float(s.wall_ns + s.duration_ns),
             "attrs": dict(s.attrs or {})} for s in spans]


class ProgramTrace:
    """Spans and scoped device operations of one traced window, one clock
    (ns from the profile's start)."""

    def __init__(self, window: tuple, spans: list, devices: dict):
        self.lo, self.hi = window
        self.spans = sorted(spans, key=lambda s: s["start"])
        self.devices = devices
        self._by_id = {s["id"]: s for s in self.spans}
        self._kids: dict = {}
        for s in self.spans:
            self._kids.setdefault(s["parent"], []).append(s)

    # -- spans ---------------------------------------------------------------

    def in_window(self, name: str) -> list:
        """The spans of that name that lie wholly inside the window."""
        return [s for s in self.spans
                if s["name"] == name and s["start"] >= self.lo and s["end"] <= self.hi]

    def children(self, span: dict) -> list:
        return self._kids.get(span["id"], [])

    def descendants(self, span: dict) -> list:
        out, todo = [], list(self.children(span))
        while todo:
            s = todo.pop()
            out.append(s)
            todo.extend(self.children(s))
        return out

    def is_root(self, span: dict) -> bool:
        return span["parent"] not in self._by_id

    def self_ns(self, span: dict) -> float:
        """The span's duration less what its children cover."""
        covered = xplane._clip(
            xplane._union([[c["start"], c["end"]] for c in self.children(span)]),
            span["start"], span["end"])
        return (span["end"] - span["start"]) - sum(e - s for s, e in covered)

    def per_root(self, root_name: str, names: tuple) -> "float | None":
        """Mean over the roots inside the window of the summed duration of
        their descendants of those names, in ns."""
        roots = self.in_window(root_name)
        if not roots:
            return None
        total = sum(d["end"] - d["start"] for r in roots for d in self.descendants(r)
                    if d["name"] in names)
        return total / len(roots)

    def child_times(self, root_name: str) -> dict:
        """Per root of the window, the mean ns in each direct child by name
        and in the root itself (``"self"``)."""
        roots = self.in_window(root_name)
        out: dict = {}
        for r in roots:
            for c in self.children(r):
                out[c["name"]] = out.get(c["name"], 0.0) + (c["end"] - c["start"])
            out["self"] = out.get("self", 0.0) + self.self_ns(r)
        return {k: v / len(roots) for k, v in out.items()}

    # -- device ----------------------------------------------------------------

    def first_device(self) -> list:
        planes = sorted(self.devices)
        return self.devices[planes[0]] if planes else []

    def seconds_by_scope(self, names: tuple) -> dict:
        """Device seconds inside the window of the first device's
        operations (loop containers left out, as in the per-operation sums
        of ``xplane.reduce``) by the innermost of ``names`` in the path of
        scopes each carries; operations that carry none are not counted."""
        out: dict = {}
        for name, start, dur, op_name in self.first_device():
            if xplane.is_container(name) or start + dur <= self.lo or start >= self.hi:
                continue
            parts = op_name.split("/")
            for part in reversed(parts):
                if part in names:
                    out[part] = out.get(part, 0.0) + dur * NS
                    break
        return out

    def idle_gaps(self) -> list:
        """``[start, end]`` of the first device's idle stretches inside the
        window: the complement of the busy union ``xplane.reduce`` takes."""
        ops = self.first_device()
        if not ops:
            return []
        busy = xplane._clip(xplane._union([[s, s + d] for _n, s, d, _o in ops if d > 0]),
                            self.lo, self.hi)
        gaps, at = [], self.lo
        for s, e in busy:
            if s > at:
                gaps.append([at, s])
            at = max(at, e)
        if self.hi > at:
            gaps.append([at, self.hi])
        return gaps

    def idle_by_span(self, prefixes: tuple) -> dict:
        """The idle time of the window, in seconds, by the innermost program
        span (of a name starting with one of ``prefixes``) over each
        instant; ``"<root>:self"`` for a root span with no child over it,
        ``"unspanned"`` where no such span lies."""
        spans = [s for s in self.spans if s["name"].startswith(prefixes)
                 and s["end"] > self.lo and s["start"] < self.hi]
        borders = sorted({b for s in spans for b in (s["start"], s["end"])})
        out: dict = {}
        for gs, ge in self.idle_gaps():
            cuts = [gs] + [b for b in borders if gs < b < ge] + [ge]
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                over = [s for s in spans if s["start"] <= mid < s["end"]]
                if not over:
                    label = "unspanned"
                else:
                    # spans of one thread nest: the latest to start is innermost
                    inner = max(over, key=lambda s: (s["start"], -s["end"]))
                    label = inner["name"] + (":self" if self.is_root(inner) else "")
                out[label] = out.get(label, 0.0) + (b - a) * NS
        return out

    def idle_spanned_share(self, prefixes: tuple, root_name: str) -> "tuple | None":
        """``(percent, seconds by span)``: the share of the window's device
        idle time that lies inside a program span with no child over it; a
        root span's self time does not count as spanned. ``None`` where no
        ``root_name`` span reaches into the window (a program without these
        spans)."""
        if not any(s["name"] == root_name and s["end"] > self.lo and s["start"] < self.hi
                   for s in self.spans):
            return None
        idle = self.idle_by_span(prefixes)
        total = sum(idle.values())
        if total <= 0:
            return None
        spanned = sum(v for k, v in idle.items()
                      if k != "unspanned" and not k.endswith(":self"))
        return 100.0 * spanned / total, idle


@functools.lru_cache(maxsize=2)
def _load(trace_dir: str, stamp: float) -> "ProgramTrace | None":
    del stamp  # cache key only: a new traced run rewrites spans.json
    with open(os.path.join(trace_dir, "spans.json")) as f:
        harness = json.load(f)
    window = [s for s in harness if s[0] == xplane.WINDOW_SPAN][0]
    scoped = read_scoped_events(xplane.find_xplane(trace_dir))
    start = scoped["profile_start_ns"]
    if start is None:
        raise ValueError("the trace gives no profile_start_time to place the spans by")
    spans = program_spans(window[1])
    # kept beside the trace (epoch clock), as the harness keeps its own:
    # tests/chipbench_checks/scope_fixture.py cuts the recorded fixtures from it
    with open(os.path.join(trace_dir, PROGRAM_SPANS_FILE), "w") as f:
        json.dump(spans, f)
    for s in spans:
        s["start"] -= start
        s["end"] -= start
    lo = window[1] - start
    return ProgramTrace((lo, lo + window[2]), spans, scoped["devices"])


def of_run(reduced: dict) -> "ProgramTrace | None":
    """The program's trace of the run ``reduced`` came from; ``None`` where
    that run left no trace directory (a reader called on a hand-made
    reduction). Read once per traced run, whatever number of readers ask."""
    found = find_run(reduced)
    if found is None:
        return None
    trace_dir = found[0]
    return _load(trace_dir, os.path.getmtime(os.path.join(trace_dir, "spans.json")))


def setup_compile_ns(reduced: dict) -> "float | None":
    """Summed duration of the ``xla.compile`` spans that ended before the
    window began: the whole process's, so the buffer must not have wrapped
    at all."""
    found = find_run(reduced)
    if found is None:
        return None
    window = [s for s in found[1] if s[0] == xplane.WINDOW_SPAN][0]
    spans = [s for s in program_spans(None)
             if s["name"] == "xla.compile" and s["end"] <= window[1]]
    if not spans:
        return None
    return sum(s["end"] - s["start"] for s in spans)


def per_root_ms(reduced: dict, root_name: str, names: tuple) -> "float | None":
    """A reader's whole body: mean ms per ``root_name`` span of the run's
    window in its descendants of those names; ``None`` where there are none."""
    run = of_run(reduced)
    ns = run.per_root(root_name, names) if run is not None else None
    return ns / 1e6 if ns else None


def idle_spanned_share(reduced: dict, prefixes: tuple, root_name: str,
                       what: str) -> "float | None":
    """A reader's whole body: the run's idle share named by program spans,
    with the idle seconds by span said beside it as ``what``."""
    run = of_run(reduced)
    found = run.idle_spanned_share(prefixes, root_name) if run is not None else None
    if found is None:
        return None
    say(what, found[1])
    return found[0]


def say(what: str, values: dict) -> None:
    """One line on standard error beside the harness's own, for PERF.md."""
    sys.stderr.write("chipbench: program_trace " + json.dumps({what: values}) + "\n")
    sys.stderr.flush()
