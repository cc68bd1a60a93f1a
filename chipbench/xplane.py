"""Reduction from a profiler trace to what the per-layer metrics read.

Two steps, so that the second can be checked on a small recorded trace
(``chipbench/testdata/``) without a chip:

1. :func:`read_events` — an ``.xplane.pb`` file, read with nothing but
   ``jax.profiler.ProfileData``, to plain lists: every device operation
   (``XLA Ops`` line of each ``/device:TPU:n`` plane), and the host spans
   that the harness kept for the drivers (``chipbench.<name>``, epoch
   clock) moved onto the trace's clock by its ``profile_start_time``: all
   on one clock, nanoseconds from the start of the profile.
2. :func:`reduce` — those lists to: the window (the ``chipbench.window``
   span), per device the union of the intervals in which an operation ran,
   per-operation sums, all-reduce sums, the idle gaps inside the window
   named by what the host was doing in them, and per ``chipbench.fit`` span
   its wall time and the span of its device work.
"""

from __future__ import annotations

import glob
import os

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
NS = 1e-9


CONTAINERS = ("while", "conditional", "call")


def short_name(text: str) -> str:
    """``opcode:name`` of a device operation. The TPU profiler names an
    operation by its whole HLO line (``%name = shape opcode(operands)``),
    which for a loop runs to thousands of characters."""
    if " = " not in text:
        return text[:80]
    name, rest = text.split(" = ", 1)
    depth = 0
    for i, ch in enumerate(rest):   # step over the result's shape
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == " " and depth == 0:
            rest = rest[i + 1:]
            break
    opcode = rest.split("(", 1)[0].strip() or "op"
    return f"{opcode}:{name.lstrip('%')}"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_events(path: str, host_spans: "list | None" = None) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}``; ``host_spans`` are
    ``[name, epoch_ns, dur_ns]`` and come back relative to the profile's
    start."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    start = None
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    if host_spans:
        if start is None:
            raise ValueError("the trace gives no profile_start_time to place the spans by")
        spans = [[n, s - float(start), d] for n, s, d in host_spans]
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([short_name(ev.name), float(ev.start_ns),
                                float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    return {"devices": devices, "spans": spans}


def _union(intervals: list) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _opcode(name: str) -> str:
    return name.split(":", 1)[0].lower()


def is_all_reduce(name: str) -> bool:
    return _opcode(name).startswith(("all-reduce", "all_reduce", "allreduce"))


def is_kernel_call(name: str) -> bool:
    """A Pallas (Mosaic) kernel: a custom call on the device."""
    return _opcode(name) in ("custom-call", "custom_call", "tpu_custom_call")


def is_container(name: str) -> bool:
    """A loop or call whose event spans the operations inside it: part of
    the busy union, left out of the per-operation sums."""
    return _opcode(name) in CONTAINERS


def reduce(events: dict) -> dict:
    spans = sorted(events["spans"], key=lambda s: s[1])
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = windows[0][1], windows[0][1] + windows[0][2]
    inner = [s for s in spans if s[0] != WINDOW_SPAN and s[1] < hi and s[1] + s[2] > lo]

    planes = sorted(events["devices"])
    busy_each = []
    merged_each = {}
    for plane in planes:
        ops = events["devices"][plane]
        merged = _clip(_union([[s, s + d] for _n, s, d in ops if d > 0]), lo, hi)
        merged_each[plane] = merged
        busy_each.append(sum(e - s for s, e in merged) * NS)

    out: dict = {
        "window_s": (hi - lo) * NS,
        "devices": len(planes),
        "busy_s_each": busy_each,
        "busy_s": sum(busy_each) / len(busy_each) if busy_each else 0.0,
    }
    if not planes:
        out.update(op_seconds={}, op_counts={}, idle_by_span={}, idle_gaps=[], fits=[])
        return out

    first = planes[0]
    ops = [o for o in events["devices"][first]
           if o[1] + o[2] > lo and o[1] < hi and not is_container(o[0])]
    op_seconds: dict = {}
    op_counts: dict = {}
    for name, _s, d in ops:
        op_seconds[name] = op_seconds.get(name, 0.0) + d * NS
        op_counts[name] = op_counts.get(name, 0) + 1
    out["op_seconds"] = op_seconds
    out["op_counts"] = op_counts
    out["all_reduce_s"] = sum(v for k, v in op_seconds.items() if is_all_reduce(k))
    out["kernel_s"] = sum(v for k, v in op_seconds.items() if is_kernel_call(k))
    out["kernel_calls"] = sum(v for k, v in op_counts.items() if is_kernel_call(k))

    # idle gaps of the first device inside the window, cut at host-span
    # borders and named by the innermost span and where in it the gap lies
    merged = merged_each[first]
    gaps = []
    at = lo
    for s, e in merged:
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    if hi > at:
        gaps.append([at, hi])

    def span_at(t: float) -> "list | None":
        best = None
        for sp in inner:
            if sp[1] <= t < sp[1] + sp[2] and (best is None or sp[2] < best[2]):
                best = sp
        return best

    borders = sorted({b for sp in inner for b in (sp[1], sp[1] + sp[2])})
    idle_by: dict = {}
    named_gaps = []
    for gs, ge in gaps:
        cuts = [gs] + [b for b in borders if gs < b < ge] + [ge]
        parts: dict = {}
        for a, b in zip(cuts, cuts[1:]):
            sp = span_at((a + b) / 2)
            if sp is None:
                label = "unattributed"
            else:
                s0, s1 = sp[1], sp[1] + sp[2]
                in_span = [iv for iv in merged if iv[1] > s0 and iv[0] < s1]
                if not in_span:
                    where = "no_device_work"
                elif b <= in_span[0][0]:
                    where = "before_first_op"
                elif a >= in_span[-1][1]:
                    where = "after_last_op"
                else:
                    where = "between_ops"
                label = f"{sp[0][len(SPAN_PREFIX):]}:{where}"
            parts[label] = parts.get(label, 0.0) + (b - a) * NS
            idle_by[label] = idle_by.get(label, 0.0) + (b - a) * NS
        top = max(parts, key=parts.get)
        named_gaps.append([top, (ge - gs) * NS])
    out["idle_by_span"] = idle_by
    out["idle_gaps"] = sorted(named_gaps, key=lambda g: -g[1])

    # each fit: its wall time and the span from its first to its last
    # device operation
    fits = []
    for name, s, d in inner:
        if name != SPAN_PREFIX + "fit":
            continue
        in_span = [iv for iv in merged if iv[1] > s and iv[0] < s + d]
        dev = (in_span[-1][1] - in_span[0][0]) * NS if in_span else 0.0
        fits.append({"wall_s": d * NS, "device_span_s": dev})
    out["fits"] = fits
    return out


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ten device operations that took most time and the idle time of
    the window by what the host was doing, for the result line."""
    ops = sorted(reduced.get("op_seconds", {}).items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(reduced.get("idle_by_span", {}).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
