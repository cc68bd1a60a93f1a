"""Find a cell's files by the names in ``BENCHMARK.json`` — stdlib only.

A cell is one entry of ``workloads``. Its configuration is the file the
``configs`` entry names, its traffic mix is
``chipbench/traffic/<traffic>.json``, its driver is
``chipbench/drivers/<driver>.py`` (named by the configuration's file), and
each of its metrics is ``chipbench/metrics/<name>.py``. A later PR adds a
cell by adding such files and entries; nothing here lists them.
"""

from __future__ import annotations

import json
import os

class SpecError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e.get("name") == name:
            return e
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}")


def _lists_cell(metric: dict, workload: str, reported: set) -> bool:
    """A metric with no ``workloads`` key belongs to every cell (for a
    per-layer metric: every cell that reports the metric it moves)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(root: str, workload: str) -> dict:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cell = _by_name(bench.get("workloads", []), workload, "workload")
    cfg_entry = _by_name(bench.get("configs", []), cell["config"], "config")
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(root, "chipbench", "traffic", cell["traffic"] + ".json"))
    driver = config.get("driver")
    if not driver or not os.path.exists(
            os.path.join(root, "chipbench", "drivers", f"{driver}.py")):
        raise SpecError(f"configuration {cell['config']!r} names driver "
                        f"{driver!r}: no chipbench/drivers/{driver}.py")
    end_to_end = [m for m in bench.get("end_to_end", [])
                  if _lists_cell(m, workload, set())]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench.get("per_layer", [])
                 if _lists_cell(m, workload, reported)]
    for m in per_layer:
        if not os.path.exists(
                os.path.join(root, "chipbench", "metrics", f"{m['name']}.py")):
            raise SpecError(f"per-layer metric {m['name']!r}: no "
                            f"chipbench/metrics/{m['name']}.py")
    return {
        "workload": workload, "chips": int(cell["chips"]),
        "config_name": cell["config"], "traffic_name": cell["traffic"],
        "config": config, "traffic": traffic, "driver": driver,
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


def sized(data: dict, rehearse: bool) -> dict:
    """A data file's sizes; in a rehearsal its ``"rehearse"`` keys win."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    if rehearse:
        out.update(data.get("rehearse", {}))
    return out
