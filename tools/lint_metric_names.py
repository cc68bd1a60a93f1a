"""Enforce the ``mmlspark_<subsystem>_<name>_<unit>`` metric naming
convention over the source tree.

Every metric registered through ``obs.counter/gauge/histogram`` with a
string-literal name is checked:

- prefix ``mmlspark_``;
- subsystem token from the known set (one per instrumented package —
  extend :data:`SUBSYSTEMS` when a new subsystem grows instruments);
- unit suffix from :data:`UNITS` (counters conventionally end ``_total``,
  including seconds-sum counters ``_seconds_total``);
- lowercase ``[a-z0-9_]`` only.

Run directly (``python tools/lint_metric_names.py``) or via the tier-1
test (tests/test_tools.py), so metric-name drift fails CI fast. A
minimum-hits sanity gate guards the regex itself: if a refactor moves
registrations out of the pattern's reach, the linter fails loudly rather
than silently passing an empty scan.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import Iterator, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_DIRS = ("mmlspark_tpu", "tools")

# "elastic" also covers the ring data plane's wire accounting
# (mmlspark_elastic_ring_steps_total, mmlspark_elastic_payload_bytes_total,
# overlap/vote counters — PR 14) and the split-brain fencing families
# (mmlspark_elastic_parks_total, mmlspark_elastic_fenced_writes_total,
# mmlspark_elastic_fenced_publications_total — PR 16); "registry" covers
# the generation CAS verdicts (mmlspark_registry_cas_commits_total) and
# "supervisor" the fenced-respawn deferrals
# (mmlspark_supervisor_fenced_respawns_total)
SUBSYSTEMS = (
    "core", "io", "serving", "gateway", "registry", "parallel", "gbdt",
    "faults", "trace", "modelstore", "slo", "admission", "supervisor",
    "compiler", "online", "autoscaler", "elastic", "artifact", "chaos",
    "experiments",
    # the replicated push plane (PR 20, serving/artifacts.py): pushes /
    # replicas / pull_resumes counters live under the plural "artifacts"
    # family prefix (the singular "artifact" covers the pull-side
    # fetch/verify instruments that predate it)
    "artifacts",
    # stall forensics (obs/prof.py, obs/watchdog.py): sampling profiler,
    # hang watchdog
    "prof", "watchdog",
    # compile requests by cache hit / miss (core/compile_cache.py)
    "xla",
    # the language-model scorer's token positions (models/causal_lm.py) and
    # the sparse expert layer's routed tokens per expert (ops/moe.py)
    "lm", "moe",
)
# "state" is for enum-valued gauges (e.g. the circuit-breaker gauge
# mmlspark_gateway_breaker_state: 0=closed 1=open 2=half-open)
UNITS = ("total", "seconds", "requests", "count", "bytes", "ratio", "rows",
         "state")

# registration call with a literal first argument, possibly wrapped to the
# next line: obs.counter(\n    "mmlspark_io_requests_total", ...
_REG_RE = re.compile(
    r"""\b(?:obs\s*\.\s*|REGISTRY\s*\.\s*|self\s*\.\s*)?"""
    r"""(counter|gauge|histogram)\(\s*["'](mmlspark_[a-zA-Z0-9_]*)["']""",
    re.S,
)
_NAME_RE = re.compile(
    r"^mmlspark_(%s)_[a-z0-9]+(_[a-z0-9]+)*_(%s)$"
    % ("|".join(SUBSYSTEMS), "|".join(UNITS))
)
# fewer hits than this means the scan regex rotted, not that the tree is
# clean — the instrumented subsystems register far more than this
MIN_EXPECTED = 15


def iter_sources() -> Iterator[str]:
    for d in SCAN_DIRS:
        for root, dirs, files in os.walk(os.path.join(REPO, d)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            if f"{os.sep}build{os.sep}" in root + os.sep:
                continue
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def lint(paths: Optional[list] = None) -> tuple:
    """Returns (violations, n_names_checked); violations are
    (path, name, why) tuples."""
    violations: list = []
    seen = 0
    for path in paths or iter_sources():
        with open(path, encoding="utf-8") as f:
            src = f.read()
        rel = os.path.relpath(path, REPO)
        for m in _REG_RE.finditer(src):
            name = m.group(2)
            seen += 1
            if _NAME_RE.match(name):
                continue
            if not re.match(r"^mmlspark_[a-z0-9_]+$", name):
                why = "name must be lowercase [a-z0-9_]"
            elif name.split("_")[1] not in SUBSYSTEMS:
                why = (
                    f"subsystem {name.split('_')[1]!r} not in "
                    f"{SUBSYSTEMS} (extend tools/lint_metric_names.py "
                    "when adding a subsystem)"
                )
            else:
                why = f"unit suffix must be one of {UNITS}"
            violations.append((rel, name, why))
    return violations, seen


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(prog="lint_metric_names.py")
    ap.add_argument("paths", nargs="*", help="files to lint (default: tree)")
    args = ap.parse_args(argv)
    violations, seen = lint(args.paths or None)
    if seen < MIN_EXPECTED and not args.paths:
        print(
            f"lint_metric_names: only {seen} metric registrations found "
            f"(expected >= {MIN_EXPECTED}) — the scan regex no longer "
            "matches the registration idiom",
            file=sys.stderr,
        )
        return 2
    for rel, name, why in violations:
        print(f"{rel}: {name}: {why}", file=sys.stderr)
    if violations:
        print(f"lint_metric_names: {len(violations)} violation(s) in "
              f"{seen} registrations", file=sys.stderr)
        return 1
    print(f"lint_metric_names: {seen} metric names ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
