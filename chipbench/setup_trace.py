"""The cold path of a traced run, from the program's own spans (PR 35).

``setup_s`` runs from the parent's start to the start of the window. What
the program records of that stretch: ``mmlspark.import`` around the bodies
of the package ``__init__``s that hold the heavy imports, and, for every
program's first call, ``xla.trace`` (the function to a jaxpr), ``xla.lower``
(the jaxpr to an MLIR module: a Pallas kernel's Mosaic lowering lies here)
and ``xla.compile`` (the cache key, then a backend compilation or, as its
child ``xla.retrieve``, a retrieval from the persistent cache) — each under
the span that was open when the call was made (``core/compile_cache.py``).

"Before the window" is every span of the process that ended before
``chipbench.window`` began, as ``program_trace.setup_compile_ns`` takes
them. Spans of one thread may nest (a ``jit`` inside a ``jit`` fires its own
``xla.trace``) and spans of two threads overlap, so every time here is the
*union* of the spans' intervals, never the sum of their durations.
"""

from __future__ import annotations

from chipbench import program_trace, xplane

IMPORT = "mmlspark.import"
TRACE, LOWER, COMPILE, RETRIEVE = "xla.trace", "xla.lower", "xla.compile", "xla.retrieve"
MS = 1e6  # ns


def union_ns(spans: list) -> float:
    return sum(e - s for s, e in xplane._union([[s["start"], s["end"]] for s in spans]))


def before_window(reduced: dict) -> "list | None":
    """The process's ``obs`` spans that ended before the run's window began;
    ``None`` where the reduction belongs to no traced run."""
    found = program_trace.find_run(reduced)
    if found is None:
        return None
    window = [s for s in found[1] if s[0] == xplane.WINDOW_SPAN][0]
    return [s for s in program_trace.program_spans(None) if s["end"] <= window[1]]


def named_ms(spans: "list | None", names: tuple) -> "float | None":
    """Union of the spans of those names, in ms; ``None`` without any."""
    mine = [s for s in spans or [] if s["name"] in names]
    return union_ns(mine) / MS if mine else None


def program_ms(spans: "list | None") -> "float | None":
    """Union of every span, root spans and orphan ``xla.*`` spans alike: the
    time set-up spent inside the program or inside a compile request.
    ``None`` for a program that does not record its imports: there the union
    would be another quantity (the parent's warm-up and compile requests)."""
    if not any(s["name"] == IMPORT for s in spans or []):
        return None
    return union_ns(spans) / MS


def _tree(spans: list) -> program_trace.ProgramTrace:
    """The spans' parents and children (no window, no device)."""
    return program_trace.ProgramTrace((0.0, 0.0), spans, {})


def cache_key_ms(spans: "list | None") -> "float | None":
    """Over the compile requests the persistent cache answered, the
    request's time less its retrieval (its one child): the module
    serialised with its metadata, and hashed. ``None`` for a program that
    records no ``xla.retrieve``."""
    spans = spans or []
    if not any(s["name"] == RETRIEVE for s in spans):
        return None
    tree = _tree(spans)
    return sum(tree.self_ns(s) for s in spans
               if s["name"] == COMPILE and s["attrs"].get("cache") == "hit") / MS


_SUMMED = ("trace_ms", "lower_ms", "compile_ms", "key_ms", "retrieve_ms", "saved_s")


def first_calls(spans: list) -> list:
    """One row a compile request of the set-up: ``xla.trace`` and
    ``xla.lower`` spans belong to the next ``xla.compile`` that ends under
    the same parent. A request made under a program span is a row of its own
    (``under``: the parent's name and duration, ``traces``: its longest
    ``xla.trace`` spans, ``shape`` / ``length``: what the nearest ancestors
    say of the program); requests under no span (the
    harness's own weight and input programs) are summed by ``fun``."""
    tree = _tree(spans)
    by_id = {s["id"]: s for s in spans}
    groups: dict = {}
    for s in spans:
        if s["name"] in (TRACE, LOWER, COMPILE):
            groups.setdefault(s["parent"], []).append(s)
    rows, orphans = [], {}
    for parent, group in groups.items():
        pending = {TRACE: [], LOWER: []}
        for s in sorted(group, key=lambda s: s["end"]):
            if s["name"] != COMPILE:
                pending[s["name"]].append(s)
                continue
            hit = s["attrs"].get("cache") == "hit"
            whole = s["end"] - s["start"]
            row = {
                "fun": s["attrs"].get("fun", ""), "cache": s["attrs"].get("cache"),
                "trace_ms": union_ns(pending[TRACE]) / MS,
                "lower_ms": union_ns(pending[LOWER]) / MS,
                "compile_ms": whole / MS,
                "key_ms": tree.self_ns(s) / MS if hit else None,
                "retrieve_ms": (whole - tree.self_ns(s)) / MS if hit else None,
                "saved_s": s["attrs"].get("saved_s"),
            }
            traced, pending = pending[TRACE], {TRACE: [], LOWER: []}
            over = by_id.get(parent)
            if over is None:
                total = orphans.setdefault((row["fun"], row["cache"]), {
                    "fun": row["fun"], "cache": row["cache"], "under": None, "requests": 0,
                    **dict.fromkeys(_SUMMED, 0.0)})
                total["requests"] += 1
                for k in _SUMMED:
                    total[k] += row[k] or 0.0
                continue
            row["under"] = over["name"]
            row["under_ms"] = (over["end"] - over["start"]) / MS
            # the longest traces of the request, nested ones too: which
            # function's tracing the union is made of
            row["traces"] = [[t["attrs"].get("fun", ""), (t["end"] - t["start"]) / MS]
                             for t in sorted(traced, key=lambda t: t["start"] - t["end"])[:6]]
            at = over
            while at is not None:   # the nearest ancestor that says it
                for k in ("shape", "length"):
                    if k in at["attrs"]:
                        row.setdefault(k, at["attrs"][k])
                at = by_id.get(at["parent"])
            rows.append((s["start"], row))
    return ([row for _start, row in sorted(rows, key=lambda r: r[0])]
            + sorted(orphans.values(), key=lambda r: -r["compile_ms"]))


def by_root(spans: list) -> dict:
    """Milliseconds of the set-up by root span name (the union of the roots
    of one name): ``chipbench``'s warm-up is a ``featurize.partition`` /
    ``lm.score`` / ``gbdt.fit`` root, an orphan compile request a root
    ``xla.*`` span."""
    tree = _tree(spans)
    roots: dict = {}
    for s in spans:
        if tree.is_root(s):
            roots.setdefault(s["name"], []).append(s)
    return {name: union_ns(group) / MS for name, group in sorted(roots.items())}
