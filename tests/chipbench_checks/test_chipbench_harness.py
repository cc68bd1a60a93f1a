"""The benchmark's harness, checked on the CPU: ``BENCHMARK.json`` keeps to
its contract, every cell's files are found by name, a cell can be added
as files plus entries alone, and the one command runs each driver end to
end at tiny sizes — and refuses to give a figure without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _workloads() -> list:
    return [w["name"] for w in _bench()["workloads"]]


def _run(root: str, *args: str, env: "dict | None" = None, timeout: int = 600):
    e = dict(os.environ)
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), *args],
        cwd=root, env=e, capture_output=True, text=True, timeout=timeout)


def _last_line(proc) -> dict:
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1])


def test_benchmark_json_has_exactly_the_contract_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench", "tests/chipbench_checks"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w for w in b["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and len(c["reduced"]) <= 16
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)


def test_every_name_and_unit_uses_only_the_allowed_characters():
    b = _bench()
    names = [c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]]
    names += [w["config"] for w in b["workloads"]] + [w["traffic"] for w in b["workloads"]]
    names += [k for c in b["configs"] for k in c["reduced"]]
    metrics = b["end_to_end"] + b["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for group in (b["configs"], b["workloads"], metrics):
        seen = [e["name"] for e in group]
        assert len(seen) == len(set(seen))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}


@pytest.mark.parametrize("workload", _workloads())
def test_cell_files_are_found_by_name(workload):
    cell = spec.load_cell(ROOT, workload)
    cfg_entry = [c for c in _bench()["configs"] if c["name"] == cell["config_name"]][0]
    assert cell["config"]["reduced"] == cfg_entry["reduced"]
    assert cell["config"]["source"] == cfg_entry["source"]
    assert cell["traffic"]["name"] == cell["traffic_name"]
    assert os.path.exists(os.path.join(ROOT, "chipbench", "drivers", cell["driver"] + ".py"))
    assert cell["per_layer"], "every cell reports at least one per-layer metric"
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    for m in cell["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".py"))
        assert m["moves"] in reported, (m["name"], "moves a metric this cell does not report")


def test_per_layer_layers_are_the_ones_perf_md_lists():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in _bench()["per_layer"]:
        assert m["layer"] in perf, m["layer"]


def _copy_benchmark(tmp: str, with_program: bool = True) -> None:
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(tmp, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_program:
        os.symlink(os.path.join(ROOT, "mmlspark_tpu"), os.path.join(tmp, "mmlspark_tpu"))


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A dummy configuration, traffic mix and per-layer metric dropped into a
    copy are found by name and run; no file that was there is edited."""
    tmp = str(tmp_path)
    _copy_benchmark(tmp)
    before = {}
    for dirpath, _d, files in os.walk(os.path.join(tmp, "chipbench")):
        for fn in files:
            p = os.path.join(dirpath, fn)
            before[p] = os.path.getmtime(p)
    with open(os.path.join(tmp, "chipbench", "configs", "higgs_gbdt.json")) as f:
        cfg = json.load(f)
    cfg.update(name="dummy_gbdt", features=9, rehearse={"num_leaves": 4})
    with open(os.path.join(tmp, "chipbench", "configs", "dummy_gbdt.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(tmp, "chipbench", "traffic", "dummy_fit.json"), "w") as f:
        json.dump({"name": "dummy_fit", "kind": "repeat_fit", "rows": 3000,
                   "trees_per_fit": 1, "why": "dummy"}, f)
    with open(os.path.join(tmp, "chipbench", "metrics", "dummy_fits.py"), "w") as f:
        f.write("def read(trace, cell):\n    return float(cell['shapes']['fits'])\n")
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "dummy_gbdt", "source": cfg["source"],
                         "file": "chipbench/configs/dummy_gbdt.json",
                         "reduced": cfg["reduced"], "why": "dummy"})
    b["workloads"].append({"name": "dummy_cell", "config": "dummy_gbdt",
                           "traffic": "dummy_fit", "chips": 1, "why": "dummy"})
    for m in b["end_to_end"]:
        if m["name"] == "trees_per_s":
            m["workloads"].append("dummy_cell")
    b["per_layer"].append({"name": "dummy_fits", "unit": "fits", "better": "higher",
                           "source": "program_counter", "layer": "GBDT trainer",
                           "moves": "trees_per_s", "workloads": ["dummy_cell"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    traced = _run(tmp, "--workload", "dummy_cell", "--seed", "11", "--seconds", "0.5",
                  "--trace", "1", "--rehearse")
    assert traced.returncode == 0, traced.stderr[-3000:]
    line = _last_line(traced)
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["metrics"]["dummy_fits"]["value"] >= 1.0
    # the old readers found nothing to read on the CPU and were left out
    assert set(line["metrics"]) == {"dummy_fits"}
    for p, mtime in before.items():
        assert os.path.getmtime(p) == mtime, f"{p} was edited"


@pytest.mark.parametrize("workload", _workloads())
def test_each_cell_runs_end_to_end_on_the_cpu(workload):
    chips = [w for w in _bench()["workloads"] if w["name"] == workload][0]["chips"]
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}"}
    a = _run(ROOT, "--workload", workload, "--seed", "3000000019", "--seconds", "1",
             "--trace", "0", "--rehearse", env=env)
    assert a.returncode == 0, a.stderr[-3000:]
    line = _last_line(a)
    cell = spec.load_cell(ROOT, workload)
    assert list(line)[-1] == "compared" and line["compared"]
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == chips
    assert line["rehearsal"] is True
    # each number compared is printed beside its limit on standard error
    tail = [ln for ln in a.stderr.splitlines() if ln.startswith("chipbench: compared")]
    assert len(tail) == len(line["compared"])
    assert all("cpu" in ln and f"x{chips}" in ln for ln in tail)


def test_no_tpu_no_figure():
    """Asked for the chip on a machine without one: non-zero, no result."""
    off = _run(ROOT, "--workload", _workloads()[0], "--seed", "1", "--seconds", "1",
               "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert off.returncode != 0
    assert not [ln for ln in off.stdout.splitlines() if ln.startswith("{")]


def test_no_program_no_figure(tmp_path):
    """In a directory that holds only BENCHMARK.json and the paths."""
    tmp = str(tmp_path)
    _copy_benchmark(tmp, with_program=False)
    bare = _run(tmp, "--workload", _workloads()[0], "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse")
    assert bare.returncode != 0
    assert not [ln for ln in bare.stdout.splitlines() if ln.startswith("{")]
    unknown = _run(ROOT, "--workload", "no_such_cell", "--seed", "1", "--seconds", "1",
                   "--trace", "0", "--rehearse")
    assert unknown.returncode != 0
