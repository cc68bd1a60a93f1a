"""The child of ``chipbench/run.py``: the one process that holds the chips.

Order of a run: set-up (inputs and weights from the seed, the program
built and every shape of the cell warmed) -> the window, for ``--seconds``
(traced when ``--trace 1``) -> peak device memory read -> the program's
state freed -> the comparison with the plain reference that decides
``correct`` -> the trace reduced -> one JSON line.

A driver (``chipbench/drivers/<name>.py``) gives ``setup``, ``window``,
``release``, ``check`` and ``control``; a per-layer metric
(``chipbench/metrics/<name>.py``) gives ``read(trace, cell)``. Both are
found by name; nothing here lists them.

``--control`` (the builder and the tests; never a measured run) also reads
the control of the comparison, the reference one precision lower in the
program's place, judges it by the same comparison and reports it as
``control_correct``. A traced run leaves its trace and its host spans under
``.chipbench_trace/<workload>/`` until the cell's next traced run
(``tests/chipbench_checks/trace_fixture.py`` cuts the recorded fixtures of
``chipbench/testdata/`` from them).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec as spec_mod  # noqa: E402

RESTART = 75
_COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_WRITE_EVENT = "/jax/compilation_cache/cache_misses"


class Context:
    """What a driver is handed: the cell's data files at the sizes of this
    run, the seed, the devices and a span writer."""

    def __init__(self, cell: dict, seed: int, rehearse: bool, devices: list):
        self.spans: list = []
        self.cell = cell
        self.seed = int(seed)
        self.rehearse = rehearse
        self.devices = devices
        self.config = spec_mod.sized(cell["config"], rehearse)
        self.traffic = spec_mod.sized(cell["traffic"], rehearse)
        self.chips = cell["chips"]

    @contextlib.contextmanager
    def span(self, name: str) -> object:
        """A host span: what the host was doing, for naming the device's
        idle gaps. Kept by the harness itself on the epoch clock, which is
        the clock of the trace's ``profile_start_time``: with the profiler's
        own host tracer on, the TPU runtime's per-chunk transfer events
        (millions for one chunk of images) slow the feed ~16x and fill the
        host's memory (PERF.md, Findings, PR 24)."""
        t = time.time_ns()
        try:
            yield
        finally:
            self.spans.append(["chipbench." + name, float(t), float(time.time_ns() - t)])

    def key(self) -> object:
        """A JAX key from a seed of any size."""
        import jax

        s = self.seed
        return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)

    def rng(self, *stream: int) -> object:
        import numpy as np

        return np.random.default_rng(np.random.SeedSequence([self.seed, *stream]))


def _say(msg: str) -> None:
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


def _memory_peak(devices: list) -> int:
    """Peak device memory on the fullest chip. The TPU allocator counts live
    arrays (``peak_bytes_in_use``) apart from what it holds back for the
    programs' own temporaries (``peak_bytes_reserved``); both are taken from
    the chip's memory while a program runs, so the peak is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def _host_peak_bytes() -> int:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _verdict(compared: list) -> bool:
    """Every number compared lies within its limit, and there is one."""
    return bool(compared) and all(c["ok"] for c in compared)


def run(args: argparse.Namespace) -> int:
    t0 = float(os.environ.get("CHIPBENCH_T0", time.time()))
    cell = spec_mod.load_cell(ROOT, args.workload)

    import jax

    from mmlspark_tpu.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program of the cell goes to the cache, however quick to compile,
    # so that only the first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counts = {"compiles": 0, "cache_writes": 0}

    def on_event(event: str, **kw: object) -> None:
        if event == _COMPILE_EVENT:
            counts["compiles"] += 1
        elif event == _WRITE_EVENT:
            counts["cache_writes"] += 1

    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    tag = dict(device, workload=args.workload, seed=args.seed)
    if args.rehearse:
        tag["rehearsal"] = True
    elif device["platform"] != "tpu" or device["count"] < cell["chips"]:
        _say(f"chipbench: the cell asks for {cell['chips']} TPU chip(s); JAX "
             f"found {device}: no figure")
        return 3
    _say("chipbench: " + json.dumps(dict(tag, cache_dir=cache_dir)))

    ctx = Context(cell, args.seed, args.rehearse, devices)
    driver = importlib.import_module(f"chipbench.drivers.{cell['driver']}")

    state = driver.setup(ctx)
    setup_compiles = dict(counts)
    if counts["cache_writes"] and not args.rehearse and not os.environ.get("CHIPBENCH_RESTARTED"):
        # a process that compiled its programs itself runs the featurizer's
        # window ~15% slower than one that loaded them from the cache
        # (PERF.md, Findings, PR 24): the figures are taken from the second
        # kind. run.py starts this child again; the time spent counts as set-up
        _say("chipbench: " + json.dumps(dict(
            tag, restart="set-up compiled and cached its programs; starting again",
            cache_writes_in_setup=counts["cache_writes"])))
        return RESTART
    trace_dir = os.path.join(ROOT, ".chipbench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0  # see Context.span
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    setup_s = time.time() - t0
    # a traced window may be shorter than a measured one: a trace of every
    # device operation grows with the window (the traffic file says how long)
    seconds = args.seconds
    if args.trace and ctx.traffic.get("trace_seconds"):
        seconds = min(seconds, float(ctx.traffic["trace_seconds"]))
    with ctx.span("window"):
        result = driver.window(ctx, state, seconds)
    trace_path = None
    if args.trace:
        from chipbench import work, xplane

        jax.profiler.stop_trace()
        trace_path = xplane.find_xplane(trace_dir)
        with open(os.path.join(trace_dir, "spans.json"), "w") as f:
            json.dump(ctx.spans, f)
    in_window = {k: counts[k] - setup_compiles[k] for k in counts}
    _say("chipbench: " + json.dumps(dict(
        tag, trace_bytes=os.path.getsize(trace_path) if trace_path else None,
        compiles_in_setup=setup_compiles["compiles"],
        cache_writes_in_setup=setup_compiles["cache_writes"],
        compiles_in_window=in_window["compiles"], setup_s=setup_s,
        host_peak_bytes=_host_peak_bytes(), window=result["work"])))

    device["memory_peak_bytes"] = _memory_peak(devices)
    driver.release(ctx, state)
    gc.collect()

    t_check = time.time()
    compared = driver.check(ctx, state)
    check_s = time.time() - t_check
    correct = _verdict(compared)
    # the control of "How correct is decided", held to the same comparison
    control = driver.control(ctx, state) if args.control else None
    if in_window["compiles"]:
        # a program compiled inside the window: the figures are not steady
        compared.append({"name": "compiles_in_window", "value": in_window["compiles"],
                         "limit": 0, "ok": False})
        correct = False

    end_to_end = dict(result["metrics"], setup_s=setup_s)
    metrics: dict = {}
    breakdown = None
    if args.trace:
        reduced = xplane.reduce(xplane.read_events(trace_path, ctx.spans))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = xplane.breakdown(reduced)
        facts = {
            "shapes": result["work"], "config": ctx.config, "traffic": ctx.traffic,
            "chips": cell["chips"], "devices": device["count"],
            "peaks": None if args.rehearse else work.peaks(device["kind"]),
        }
        for m in cell["per_layer"]:
            reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
            value = reader.read(reduced, facts)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            if m["name"] in end_to_end:
                metrics[m["name"]] = {"value": float(end_to_end[m["name"]]),
                                      "unit": m["unit"]}

    line: dict = {
        "correct": correct,
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["workload"] = args.workload
    line["seed"] = args.seed
    line["check_s"] = check_s
    line["host_peak_bytes"] = _host_peak_bytes()
    if args.rehearse:
        line["rehearsal"] = True
    if control is not None:
        line["control_correct"] = _verdict(control)
        line["control"] = control
    line["compared"] = [{"name": c["name"], "value": c["value"], "limit": c["limit"]}
                        for c in compared]
    for what, rows in (("control", control or []), ("compared", compared)):
        for c in rows:
            _say(f"chipbench: {what} {c['name']} = {c['value']!r} "
                 f"(limit {c['limit']!r}) {'ok' if c['ok'] else 'NOT OK'} "
                 f"[{device['platform']} {device['kind']} x{device['count']}]")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def main(argv: "list | None" = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
