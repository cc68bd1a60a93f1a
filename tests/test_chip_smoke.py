"""chip_smoke.py's contract, as far as a box without a chip can hold it to.

The driver runs ``python3 chip_smoke.py`` on a TPU; here the same phases
run tiny on the CPU with interpret-mode kernels (``--rehearse``), and the
ways the default mode must FAIL without an accelerator are pinned.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**kw):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "MMLSPARK_TPU_HOME")}
    env.update(kw)
    return env


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]


def test_rehearsal_passes_and_the_parent_never_imports_jax(tmp_path):
    """Every phase runs and checks out on CPU, every line is labelled a
    rehearsal, and the orchestrating process stays off JAX — a parent that
    touched JAX would hold the chip its children need."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import chip_smoke\n"
        f"rc = chip_smoke.main(['--rehearse', '--out', {str(tmp_path)!r}])\n"
        "print('PARENT_HAS_JAX', 'jax' in sys.modules)\n"
        "sys.exit(rc)\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], env=_env(), cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    assert "PARENT_HAS_JAX False" in p.stdout
    recs = _json_lines(p.stdout)
    assert [r.get("phase") for r in recs[:-1]] == [
        "featurize", "gbdt", "serve", "vw", "pipeline"
    ]
    assert all(r["ok"] and r["rehearsal"] is True for r in recs)
    assert recs[-1]["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    # the interpreted kernels ran, not the host or scatter lowerings
    lowerings = recs[1]["hist_lowerings"]
    assert lowerings and all(k.endswith(":pallas") for k in lowerings)
    # zoo weights were materialised inside the run's own directory
    assert os.listdir(tmp_path / "home" / "models")


def test_default_mode_refuses_a_cpu_only_environment(tmp_path):
    """JAX_PLATFORMS=cpu (this sandbox's global setting) must make the
    default mode fail and say why — not pass on the CPU."""
    p = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path)],
        env=_env(JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=60,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "JAX_PLATFORMS='cpu'" in p.stderr and "TPU" in p.stderr


def test_a_failed_phase_fails_the_run(tmp_path):
    """`serve` with nothing from `featurize` to post: the phase dies, its
    line says ok=false, there is no final result line, exit is non-zero."""
    p = subprocess.run(
        [sys.executable, SMOKE, "--rehearse", "--phases", "serve",
         "--out", str(tmp_path)],
        env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    recs = _json_lines(p.stdout)
    assert [r["phase"] for r in recs] == ["serve"]
    assert recs[0]["ok"] is False and "images_head.npy" in recs[0]["stderr_tail"]
    assert not any("device" in r for r in recs)


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=_env(),
        capture_output=True, text=True, timeout=60,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "mmlspark_tpu" in p.stderr
