#!/usr/bin/env python3
"""Cut a recorded fixture for ``chipbench/testdata/scopes/`` from a cell's
last traced run: a short piece of the device trace WITH the scope every
operation carries, and the program's spans over it.

    JAX_PLATFORMS=cpu python3 tests/chipbench_checks/scope_fixture.py <workload> <out_dir> [seconds]

A ``--trace 1`` run leaves its trace, the harness's spans (``spans.json``)
and — once a reader of ``chipbench/program_trace.py`` has run — the
program's spans (``program_spans.json``) under
``.chipbench_trace/<workload>/``. The piece is cut around the first
dispatch of the window's second root span (its first, where it holds one):
the end of a chunk's or a fit's host preparation and the start of its
device work, so that it holds idle time to attribute and operations of
every scope. ``test_chipbench_program_trace.py`` reads what this writes.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_trace, xplane  # noqa: E402

ROOTS = ("gbdt.fit", "featurize.partition")
DISPATCHES = ("gbdt.chunk.dispatch", "xla_model.dispatch")


def cut(workload: str, seconds: float = 0.6, lead: float = 0.25) -> dict:
    trace_dir = os.path.join(program_trace.TRACE_ROOT, workload)
    scoped = program_trace.read_scoped_events(xplane.find_xplane(trace_dir))
    start = scoped["profile_start_ns"]
    with open(os.path.join(trace_dir, "spans.json")) as f:
        window = [s for s in json.load(f) if s[0] == xplane.WINDOW_SPAN][0]
    with open(os.path.join(trace_dir, program_trace.PROGRAM_SPANS_FILE)) as f:
        spans = json.load(f)
    w_lo, w_hi = window[1] - start, window[1] - start + window[2]
    for s in spans:
        s["start"] -= start
        s["end"] -= start
    roots = sorted((s for s in spans if s["name"] in ROOTS and s["start"] >= w_lo
                    and s["end"] <= w_hi), key=lambda s: s["start"])
    at = (w_lo + w_hi) / 2
    if roots:
        root = roots[min(1, len(roots) - 1)]
        firsts = [s["start"] for s in spans if s["name"] in DISPATCHES
                  and root["start"] <= s["start"] <= root["end"]]
        at = min(firsts) if firsts else root["start"]
    lo = max(w_lo, at - lead * seconds * 1e9)
    hi = min(w_hi, lo + seconds * 1e9)

    def moved(s: dict) -> dict:
        return dict(s, start=s["start"] - lo, end=s["end"] - lo)

    keep_spans = [moved(s) for s in spans if s["end"] > lo and s["start"] < hi]
    devices = {p: [[n, s - lo, d, o] for n, s, d, o in ops if s + d > lo and s < hi]
               for p, ops in scoped["devices"].items()}
    return {"workload": workload, "window": [0.0, hi - lo], "spans": keep_spans,
            "devices": devices}


def main(workload: str, out_dir: str, seconds: str = "0.6") -> int:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"scoped_{workload}.json"), "w") as f:
        json.dump(cut(workload, float(seconds)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
