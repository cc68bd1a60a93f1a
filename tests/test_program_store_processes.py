"""The program store across processes (``core/compile_cache.py``): a second
process that finds the store computes, bit for bit, what plain ``jit``
computes, without tracing the stored programs; two processes that write one
entry at once leave one readable entry; and the digest of the package's
live code reads the same in two processes, or no entry would ever be found
again. Each process runs ``tests/program_store_child.py`` or a few lines of
its own, with the store opted in for the CPU and a cache directory of the
test's own."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(ROOT, "tests", "program_store_child.py")


def _env(cache_dir, devices: int) -> dict:
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               # the histogram without a host callback: a program the store can keep
               MMLSPARK_TPU_HIST_HOST="0",
               PYTHONPATH=os.pathsep.join([ROOT] + [p for p in [env.get("PYTHONPATH")] if p]))
    return env


def _child(cache_dir, out, devices: int, cases: list) -> dict:
    subprocess.run([sys.executable, CHILD, str(out), *cases], env=_env(cache_dir, devices),
                   cwd=ROOT, check=True, timeout=600, stdout=subprocess.DEVNULL)
    with open(out) as f:
        return json.load(f)


# case -> the functions whose trace a stored start must not record, and the
# store's requests a case makes (a bucket program each of BUCKETS; one round program)
STORED = {"lfm2": ("run", 2), "keye": ("run", 2), "deepseek": ("run", 2), "gbdt": ("_scan_chunk", 1)}


@pytest.mark.parametrize("devices,cases", [(1, ["lfm2", "keye", "deepseek", "gbdt"]),
                                           (4, ["gbdt"])])
def test_a_second_process_loads_every_program_and_computes_what_jit_does(
        tmp_path, devices, cases):
    first = _child(tmp_path, tmp_path / "first.json", devices, cases)
    second = _child(tmp_path, tmp_path / "second.json", devices, cases)
    for case in cases:
        fun, requests = STORED[case]
        a, b = first[case], second[case]
        assert a["equal"] and b["equal"], case       # the store's outputs are plain jit's
        assert a["digest"] == b["digest"], case      # and the same in both processes
        assert (a["stored"], a["unstorable"]) == (0, 0), case
        assert a["compiled"] >= requests and fun in a["traced"], case
        assert b["stored"] == requests, case
        assert fun not in b["traced"], case


_WRITER = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from mmlspark_tpu.core import compile_cache as cc
    from mmlspark_tpu.models import causal_lm  # noqa: F401  (the same modules in each)
    cc.enable_compile_cache()
    cc._STORE_PLATFORMS = ("tpu", "cpu")

    def race(x):
        return jnp.cumsum(jnp.sin(x) @ jnp.cos(x).T, axis=0)

    out = cc.stored_jit(race, name="tests.race")(jnp.ones((64, 64)))
    stored = cc._M_COMPILES.labels(cache="stored").value
    print(float(out.sum()), int(stored))
""")


def test_two_processes_writing_one_entry_at_once_leave_one_readable_entry(tmp_path):
    cmd = [sys.executable, "-c", _WRITER]
    procs = [subprocess.Popen(cmd, env=_env(tmp_path, 1), cwd=ROOT, text=True,
                              stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
             for _ in range(2)]
    outs = [p.communicate(timeout=300)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    store = tmp_path / "mmlspark-programs"
    names = sorted(os.listdir(store))
    assert sorted(n.rsplit(".", 1)[-1] for n in names) == ["modules", "prog"], names   # no .tmp
    third = subprocess.run(cmd, env=_env(tmp_path, 1), cwd=ROOT, text=True, check=True,
                           capture_output=True, timeout=300).stdout.split()
    assert third[1] == "1"                      # loaded
    assert outs[0][0] == outs[1][0] == third[0]


_DIGEST = textwrap.dedent("""
    import importlib, json, pkgutil, sys
    import mmlspark_tpu
    from mmlspark_tpu.core import compile_cache as cc
    for m in pkgutil.walk_packages(mmlspark_tpu.__path__, "mmlspark_tpu."):
        try:
            importlib.import_module(m.name)
        except Exception:
            pass
    print(json.dumps({n: cc._module_digest(sys.modules[n]) for n in cc._package_modules()}))
""")


def test_the_live_code_digest_reads_the_same_in_two_processes():
    """A module-level value made from the process (its pid, the clock) and
    never rebound would change the digest in every process: no entry would
    be found again. Every module the package has, imported twice."""
    runs = [json.loads(subprocess.run(
        [sys.executable, "-c", _DIGEST], env=_env(ROOT + "/.jax_cache", 1), cwd=ROOT,
        text=True, check=True, capture_output=True, timeout=300).stdout.splitlines()[-1])
        for _ in range(2)]
    assert len(runs[0]) > 100
    assert sorted(runs[0]) == sorted(runs[1])
    assert [n for n in runs[0] if runs[0][n] != runs[1][n]] == []
