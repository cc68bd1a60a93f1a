#!/usr/bin/env python3
"""chipbench — the benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs ONE cell of ``BENCHMARK.json`` on the machine it is started on and
prints one JSON result as the last line of standard output. This parent is
stdlib only and never imports JAX: a chip belongs to one process, and that
process is the child (``chipbench/harness.py``) which this file starts,
waits for and — whatever happens — reaps with its whole process group.

No TPU, no figure: the run exits non-zero and prints no result when JAX is
held off the TPU or finds fewer chips than the cell asks for. ``--rehearse``
(tests only) drives the same code on the CPU at the tiny sizes each data
file gives under ``"rehearse"``; every line it prints says so.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

T0 = time.time()
RESTART = 75  # chipbench/harness.py: "set-up compiled; start me again"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def main(argv: "list | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU, tiny sizes, interpreted kernels")
    ap.add_argument("--control", action="store_true",
                    help="builder and tests only: also judge the comparison's control")
    args = ap.parse_args(argv)

    root = ROOT
    sys.path.insert(0, root)
    from chipbench import spec as spec_mod

    try:
        spec_mod.load_cell(root, args.workload)
    except spec_mod.SpecError as e:
        sys.stderr.write(f"chipbench: {e}\n")
        return 2
    if not os.path.isdir(os.path.join(root, "mmlspark_tpu")):
        sys.stderr.write(
            f"chipbench: {root} holds no mmlspark_tpu package, so there is "
            "no system to measure\n")
        return 2

    env = dict(os.environ)
    env["CHIPBENCH_T0"] = repr(T0)
    env["PYTHONPATH"] = os.pathsep.join(
        [root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    # whatever the program installs or caches stays inside the checkout
    env["MMLSPARK_TPU_HOME"] = os.path.join(root, ".chipbench_home")
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("MMLSPARK_TPU_PALLAS", "1")
    else:
        inherited = env.get("JAX_PLATFORMS", "")
        if inherited and "tpu" not in inherited.split(","):
            sys.stderr.write(
                f"chipbench: JAX_PLATFORMS={inherited!r} holds JAX off the "
                "TPU: no accelerator, no figure\n")
            return 2
        # pinned: a TPU that fails to come up is an error, never a CPU run
        env["JAX_PLATFORMS"] = "tpu,cpu"

    cmd = [sys.executable, os.path.join(root, "chipbench", "harness.py"),
           "--workload", args.workload,
           "--seed", str(args.seed), "--seconds", repr(args.seconds),
           "--trace", str(args.trace)]
    cmd += ["--rehearse"] * args.rehearse + ["--control"] * args.control
    live: list = []

    def on_signal(signum: int, frame: object) -> None:
        for p in live:
            _kill_group(p)
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    rc = RESTART
    for attempt in range(2):
        # a child whose set-up had to compile ends with RESTART once its
        # programs are in the cache; the second child loads them from there
        if rc != RESTART:
            break
        if attempt:
            env["CHIPBENCH_RESTARTED"] = "1"
        proc = subprocess.Popen(cmd, cwd=root, env=env, start_new_session=True)
        live[:] = [proc]
        try:
            rc = proc.wait()
        finally:
            _kill_group(proc)  # also reaps anything the child left behind
    return rc


if __name__ == "__main__":
    sys.exit(main())
