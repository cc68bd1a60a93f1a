"""Test harness root.

The "distributed without a cluster" substrate (SURVEY.md §4): force JAX onto
the host CPU platform with 8 virtual devices so mesh/collective code paths
run for real in one process — the analogue of the reference testing LightGBM
/VW socket allreduce between local-mode Spark tasks
(VerifyLightGBMClassifier.scala:123).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

# repo root on sys.path: `pytest` (unlike `python -m pytest`) does not add
# the cwd, and tests import repo-root modules like tools.deploy.smoke
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax

from mmlspark_tpu.core.compile_cache import enable_compile_cache

jax.config.update("jax_platforms", "cpu")
# persistent compile cache shared across runs: most of the suite's
# wall-clock is XLA compiles of the same jitted programs
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
# pure_callback host growers deadlock against XLA:CPU async dispatch
# above ~6k rows (docs/gbdt-training.md "Known issues"); the flag is
# read once at CPU client creation, so it must land here, before any
# test dispatches
jax.config.update("jax_cpu_enable_async_dispatch", False)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def devices8():
    ds = jax.devices()
    assert len(ds) == 8, f"expected 8 virtual CPU devices, got {len(ds)}"
    return ds


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def make_tabular_df(n=200, d=6, n_classes=2, num_partitions=3, seed=0):
    """Synthetic linearly-separable-ish tabular DataFrame with a dense
    feature matrix column + scalar label column."""
    from mmlspark_tpu import DataFrame

    r = np.random.default_rng(seed)
    x = r.normal(size=(n, d)).astype(np.float32)
    w = r.normal(size=(d, n_classes))
    logits = x @ w + 0.5 * r.normal(size=(n, n_classes))
    y = np.argmax(logits, axis=1).astype(np.int32)
    return DataFrame.from_dict({"features": x, "label": y}, num_partitions=num_partitions)


@pytest.fixture()
def tabular_df():
    return make_tabular_df()
