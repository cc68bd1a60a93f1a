#!/usr/bin/env python3
"""Cut a recorded fixture for ``chipbench/testdata/`` from a cell's last
traced run.

    JAX_PLATFORMS=cpu python3 tests/chipbench_checks/trace_fixture.py <workload> <out_dir>

A ``--trace 1`` run leaves its trace and its host spans under
``.chipbench_trace/<workload>/``. This writes ``<workload>.describe.json``
(planes, lines and a few event names: what to look at by hand before
trusting a reduction written against the trace) and
``trace_<workload>.json`` (a short piece of the trace, small enough to
keep, which ``test_chipbench_xplane.py`` reduces).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import xplane  # noqa: E402


def describe(path: str, limit: int = 6) -> list:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({
                "plane": plane.name, "line": line.name, "events": len(events),
                "first": [[e.name[:200], e.start_ns, e.duration_ns] for e in events[:limit]],
            })
    return out


def trimmed(events: dict, seconds: float = 0.6, lead: float = 0.05) -> dict:
    """Everything that overlaps ``seconds`` around the middle of the
    window, with a ``chipbench.window`` span cut to that piece."""
    win = [s for s in events["spans"] if s[0] == xplane.WINDOW_SPAN][0]
    lo = win[1] + win[2] / 2 - lead * 1e9
    hi = lo + seconds * 1e9

    def keep(rows: list) -> list:
        return [[n, s - lo, d] for n, s, d in rows if s + d > lo and s < hi]

    spans = [r for r in keep(events["spans"]) if r[0] != xplane.WINDOW_SPAN]
    spans.append([xplane.WINDOW_SPAN, 0.0, hi - lo])
    return {"devices": {p: keep(ops) for p, ops in events["devices"].items()},
            "spans": spans}


def main(workload: str, out_dir: str) -> int:
    trace_dir = os.path.join(ROOT, ".chipbench_trace", workload)
    path = xplane.find_xplane(trace_dir)
    with open(os.path.join(trace_dir, "spans.json")) as f:
        spans = json.load(f)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{workload}.describe.json"), "w") as f:
        json.dump(describe(path), f)
    with open(os.path.join(out_dir, f"trace_{workload}.json"), "w") as f:
        json.dump(trimmed(xplane.read_events(path, spans)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
