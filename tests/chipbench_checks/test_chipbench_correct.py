"""What decides ``correct``, shown to fail: the control (the reference one
precision lower, in the program's place) and every fault a cell can have,
planted under the timed path, at a size a test run can hold.

The harness's look for a chip is skipped (``--rehearse``); the rest of a
run is driven as it is on the chip: set-up, window, release, comparison,
result line."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

FEATURIZE = "resnet50_featurize_stream"
GBDT = "higgs_gbdt_fit"
# in this process JAX has the test suite's 8 virtual CPU devices, so the GBDT
# cell's rows are sharded and its histograms summed by the plane psum


@pytest.fixture()
def quiet_jax():
    """The harness sets process-wide JAX options; give them back."""
    import jax

    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)
    jax.clear_caches()


def _drive(workload: str, capsys, *more: str, seed: int = 3000000019) -> dict:
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "0.3", "--trace", "0", "--rehearse", *more])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1])


def _failed(line: dict) -> set:
    out = set()
    for c in line["compared"]:
        v, lim = c["value"], c["limit"]
        if (v > lim) if c["name"] not in ("rows_compared", "fits_compared") else (v < lim):
            out.add(c["name"])
    return out


@pytest.mark.parametrize("workload", [FEATURIZE, GBDT])
def test_a_sound_run_is_correct(workload, capsys, quiet_jax):
    line = _drive(workload, capsys)
    assert line["correct"] is True, line["compared"]
    assert not _failed(line)


# -- faults of the featurizer cell -------------------------------------------


def _break_apply_batch(monkeypatch, fault: str) -> None:
    from mmlspark_tpu.models.xla_model import XLAModel

    sound = XLAModel.apply_batch

    def half_left_out(self, x):
        # half of the batch never reaches the device; its rows get the mean
        # of the rest
        n = len(x) // 2
        head = sound(self, x[:n])
        return np.concatenate([head, np.repeat(head.mean(0, keepdims=True), len(x) - n, 0)])

    def altered(self, x):
        return sound(self, x) * np.float32(1.1)

    monkeypatch.setattr(XLAModel, "apply_batch",
                        {"half_left_out": half_left_out, "altered": altered}[fault])


@pytest.mark.parametrize("fault", ["half_left_out", "altered"])
def test_featurizer_faults_come_out_not_correct(fault, monkeypatch, capsys, quiet_jax):
    _break_apply_batch(monkeypatch, fault)
    line = _drive(FEATURIZE, capsys)
    assert line["correct"] is False
    assert "feature_rel_err_max" in _failed(line)


# -- faults of the GBDT cells -------------------------------------------------


def _break_fit(monkeypatch, fault: str) -> None:
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.gbdt import LightGBMClassifier

    sound = LightGBMClassifier.fit

    def rewrite(model, edit) -> object:
        d = json.loads(model.get("model_string"))
        edit(d)
        model.set(model_string=json.dumps(d))
        return model

    def state_unchanged(self, df):
        # the scores never move: every tree is grown from the first tree's
        # gradients, so every tree is the first tree
        def edit(d):
            d["trees"] = [d["trees"][0] for _ in d["trees"]]
        return rewrite(sound(self, df), edit)

    def half_left_out(self, df):
        n = len(df) // 2
        half = DataFrame.from_dict({"features": df["features"][:n], "label": df["label"][:n]})
        return sound(self, half)

    def altered(self, df):
        def edit(d):
            for t in d["trees"]:
                t["values"] = [v * 1.05 for v in t["values"]]
        return rewrite(sound(self, df), edit)

    monkeypatch.setattr(LightGBMClassifier, "fit", {
        "state_unchanged": state_unchanged, "half_left_out": half_left_out,
        "altered": altered}[fault])


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", {"split_gain_gap_max", "leaf_value_gap_median"}),
    ("half_left_out", {"leaf_rows_mismatch"}),
    ("altered", {"leaf_value_gap_median"}),
])
def test_gbdt_faults_come_out_not_correct(fault, caught_by, monkeypatch, capsys, quiet_jax):
    _break_fit(monkeypatch, fault)
    line = _drive(GBDT, capsys)
    assert line["correct"] is False
    assert caught_by & _failed(line), line["compared"]


def test_gbdt_without_the_exchange_between_chips_is_not_correct(monkeypatch, capsys, quiet_jax):
    """The plane ``psum`` left out: every chip grows from its own rows."""
    import jax

    assert len(jax.devices()) > 1
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    line = _drive(GBDT, capsys)
    assert line["correct"] is False
    assert {"leaf_rows_mismatch", "split_gain_gap_max", "leaf_value_gap_median"} & _failed(line)


# -- the control ---------------------------------------------------------------


@pytest.mark.parametrize("workload", [FEATURIZE, GBDT])
def test_the_control_comes_out_not_correct(workload, capsys, quiet_jax):
    """One precision below the configuration's (float8 for the bfloat16
    featurizer, bfloat16 statistics for the float32 histograms), judged by
    the harness's own comparison in the same run, fails at least one of
    the cell's numbers, while the program passes them all."""
    line = _drive(workload, capsys, "--control")
    assert line["correct"] is True, line["compared"]
    assert line["control_correct"] is False, line["control"]
    assert any(not c["ok"] for c in line["control"])
