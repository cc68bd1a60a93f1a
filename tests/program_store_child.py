"""One process of ``tests/test_program_store.py``: runs the package's two
stored programs at test size with the program store opted in for the CPU,
and writes what it saw as JSON.

    JAX_COMPILATION_CACHE_DIR=<dir> python tests/program_store_child.py <out.json> <case>...

Cases: ``lfm2``, ``keye``, ``deepseek`` (``CausalLMScorer`` in each
mixer's shape: its outputs through the store, then through plain ``jit``)
and ``gbdt`` (a fit's model string, the same two ways, on every device the
process has). Per case: the outputs' digest and whether the two ways agree
bit for bit, how many requests the store answered (``stored``) and how many
it took to the backend or JAX's cache (``compiled``), and the functions
whose trace the process recorded on the store's way.
"""

import hashlib
import importlib
import json
import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402
from mmlspark_tpu import obs  # noqa: E402
from mmlspark_tpu.core import compile_cache  # noqa: E402
from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.models import causal_lm as lm  # noqa: E402

BUCKETS = [[16, 4], [32, 2]]
LM_CASES = {   # case -> (configuration file, driver, the driver's variables take the kinds)
    "lfm2": ("lfm2_8b_a1b", "lm_score_stream", True),
    "keye": ("keye_vl2_30b_a3b", "lm_score_longdocs", False),
    "deepseek": ("deepseek_v2", "lm_score_share", False),
}


def _compiles() -> dict:
    fam = obs.REGISTRY.snapshot().get("mmlspark_xla_compiles_total") or {}
    return {labels["cache"]: value for labels, value in fam.get("samples", [])}


def _seen(before: dict) -> dict:
    """What the store and the tracer saw since ``before``."""
    now = _compiles()
    delta = {k: now.get(k, 0) - before.get(k, 0) for k in now}
    traced = sorted({s.attrs.get("fun", "") for s in obs.recent_spans() if s.name == "xla.trace"})
    return {"stored": delta.get("stored", 0),
            "compiled": delta.get("hit", 0) + delta.get("miss", 0),
            "unstorable": delta.get("unstorable", 0), "traced": traced}


def _digest(arrays: list) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def lm_case(case: str) -> dict:
    name, driver_name, kinds = LM_CASES[case]
    driver = importlib.import_module(f"chipbench.drivers.{driver_name}")
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        config = driver.model_config(spec.sized(json.load(f), True))
    key = jax.random.PRNGKey(37)
    variables = (driver.program_variables(config, key, lm.layer_kinds(config)) if kinds
                 else driver.program_variables(config, key))
    lo, hi = config.get("vocab_range", (0, config["vocab_size"]))
    rng = np.random.default_rng(37)
    col = np.empty(6, dtype=object)
    col[:] = [rng.integers(lo, hi, n).astype(np.int32) for n in (5, 16, 9, 30, 2, 21)]
    df = DataFrame.from_dict({"tokens": col})

    def score(stored: bool) -> list:
        scorer = lm.CausalLMScorer(input_col="tokens", output_col="logprob", config=config,
                                   variables=variables, buckets=BUCKETS)
        if not stored:
            scorer._build().program_identity = None
        return list(scorer.transform(df)["logprob"])

    before = _compiles()
    got = score(True)
    seen = _seen(before)
    plain = score(False)
    return dict(seen, digest=_digest(got), equal=all(np.array_equal(a, b)
                                                     for a, b in zip(got, plain)))


def gbdt_case() -> dict:
    from mmlspark_tpu.models.gbdt.train import TrainConfig, train

    rng = np.random.default_rng(41)
    x = rng.normal(size=(512, 6)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.float64)
    cfg = TrainConfig(objective="binary", num_iterations=3, num_leaves=7,
                      min_data_in_leaf=5, seed=0)
    shard = len(jax.devices()) > 1
    before = _compiles()
    got = train(x, y, cfg, shard=shard).to_model_string()
    seen = _seen(before)
    # plain jit: the store's platforms without this one
    compile_cache._STORE_PLATFORMS = ("tpu",)
    plain = importlib.import_module("mmlspark_tpu.models.gbdt.train")._scan_chunk
    plain._programs.clear()
    want = train(x, y, cfg, shard=shard).to_model_string()
    compile_cache._STORE_PLATFORMS = ("tpu", "cpu")
    return dict(seen, digest=hashlib.sha256(got.encode()).hexdigest(), equal=got == want)


def main() -> None:
    out, cases = sys.argv[1], sys.argv[2:]
    compile_cache.enable_compile_cache()
    compile_cache._STORE_PLATFORMS = ("tpu", "cpu")
    report = {}
    for case in cases:
        obs.clear_recent_spans()
        report[case] = lm_case(case) if case in LM_CASES else gbdt_case()
    with open(out, "w") as f:
        json.dump(report, f)


if __name__ == "__main__":
    main()
