"""The corpus-scoring cell, through the harness's own verdict at rehearsal
size: a sound run is ``correct``, the float8 control is not, and each fault
a sparse-expert language model can have — planted under the timed path, in
the program — comes out ``correct: false``. Beside them the cell's data:
its lengths, its counts of operations, its readers on a program without the
spans."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, spec, work_lm  # noqa: E402
from chipbench.drivers import lm_score_stream as driver  # noqa: E402

CELL = "lfm2_score_corpus"


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
    # no jax.clear_caches(): every drive builds its own stage (a fault is
    # traced afresh), and the reference's programs are worth keeping


def _drive(capsys, *more: str, seed: int = 3000000019) -> dict:
    rc = harness.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "0.05", "--trace", "0", "--rehearse", *more])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1])


def _failed(line: dict) -> set:
    return {c["name"] for c in line["compared"]
            if (c["value"] < c["limit"] if c["name"] == "rows_compared"
                else c["value"] > c["limit"])}


def test_a_sound_run_is_correct_and_its_control_is_not(capsys, quiet_jax):
    line = _drive(capsys, "--control")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] % 19 == 0
    assert line["control_correct"] is False, line["control"]
    assert any(not c["ok"] for c in line["control"])
    # one row of every bucket of every chunk was compared
    rows = [c["value"] for c in line["compared"] if c["name"] == "rows_compared"]
    assert rows == [3 * line["attempted"] // 19]


def _plant(monkeypatch, fault: str) -> None:
    import jax.numpy as jnp

    from mmlspark_tpu.models import causal_lm as lm
    from mmlspark_tpu.ops import moe

    if fault == "top_k_minus_one":          # top-3 instead of top-4
        sound = moe.route
        monkeypatch.setattr(moe, "route", lambda u, r, b, k, s=1.0: sound(u, r, b, k - 1, s))
    elif fault == "bias_left_out_of_selection":
        sound = moe.select
        monkeypatch.setattr(moe, "select", lambda scores, bias, k: sound(scores, 0.0 * bias, k))
    elif fault == "bias_leaks_into_weights":
        def route(u, router, bias, top_k, scaling=1.0):
            scores = moe.router_scores(u, router)
            idx = moe.select(scores, bias.astype(jnp.float32), top_k)
            return idx, moe.combine_weights(scores + bias, idx, scaling)
        monkeypatch.setattr(moe, "route", route)
    elif fault == "conv_taps_reversed":
        sound = lm.conv_taps
        monkeypatch.setattr(lm, "conv_taps", lambda kernel, z: sound(kernel[:, ::-1], z))
    elif fault == "rope_left_out":
        monkeypatch.setattr(lm, "rope", lambda x, theta: x)
    elif fault == "qk_norm_left_out":
        monkeypatch.setattr(lm, "qk_norm", lambda x, scale, eps: x.astype(jnp.float32))
    elif fault == "expert_ffn_in_layer_0":
        sound = lm.layer_kinds
        monkeypatch.setattr(lm, "layer_kinds", lambda config: [
            (mixer, "moe" if i == 0 else ffn) for i, (mixer, ffn) in enumerate(sound(config))])
    else:
        raise KeyError(fault)


@pytest.mark.parametrize("fault", [
    "top_k_minus_one", "bias_left_out_of_selection", "bias_leaks_into_weights",
    "conv_taps_reversed", "rope_left_out", "qk_norm_left_out", "expert_ffn_in_layer_0"])
def test_faults_come_out_not_correct(fault, monkeypatch, capsys, quiet_jax):
    _plant(monkeypatch, fault)
    line = _drive(capsys)
    assert line["correct"] is False
    assert {"logprob_rel_err_median", "logprob_rel_err_p90"} & _failed(line), line["compared"]


def _cell() -> dict:
    return spec.load_cell(ROOT, CELL)


def test_the_chunk_is_what_the_issue_describes():
    cell = _cell()
    traffic, config = cell["traffic"], cell["config"]
    lengths = driver.chunk_lengths(traffic)
    assert len(lengths) == 144 and int(lengths.sum()) == 143_104
    assert (lengths[:64].min(), lengths[:64].max()) == (64, 512)
    buckets = [driver._bucket_of(traffic, n) for n in lengths]
    assert [buckets.count(b) for b in (512, 1024, 2048, 4096)] == [64, 32, 32, 16]
    assert sum(buckets) == 196_608
    assert {length * rows for length, rows in traffic["buckets"]} == {32_768}
    pool = driver.make_pool(traffic, driver.model_config(config), 2 ** 31 + 5)
    again = driver.make_pool(traffic, driver.model_config(config), 2 ** 31 + 5)
    assert sorted(len(r) for r in pool[0]) == sorted(lengths)
    assert all(np.array_equal(a, b) for a, b in zip(pool[1], again[1]))
    assert [len(r) for r in pool[0]] != [len(r) for r in pool[1]]
    assert max(int(r.max()) for r in pool[0]) > 60_000


def test_the_configuration_keeps_the_published_widths():
    config = _cell()["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    published = [r for r in rows if r["name"] == "LFM2-8B-A1B"][0]["config"]
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 14 and config["experts_held"] == config["num_experts"]
    kinds = work_lm.layer_kinds(config)
    assert kinds[:2] == [("conv", "dense")] * 2
    assert kinds[2:] == [("full_attention", "moe"), ("conv", "moe"), ("conv", "moe"),
                         ("conv", "moe")] * 3


def test_counts_of_operations_from_the_shapes():
    config = _cell()["config"]
    # per token: 11 conv mixers, 3 attention mixers' projections, 2 dense FFNs,
    # 12 routers and 4 experts of 12 layers
    per_token = (11 * 8 * 2048 ** 2 + 3 * 2 * (2 * 2048 ** 2 + 2 * 2048 * 512)
                 + 2 * 6 * 2048 * 7168 + 12 * (2 * 2048 * 32 + 4 * 6 * 2048 * 1792))
    assert work_lm.token_flops(config) == per_token
    lengths = driver.chunk_lengths(_cell()["traffic"]).astype(np.float64)
    flops = work_lm.step_flops(config, 144, int(lengths.sum()), float((lengths ** 2).sum()))
    head = (lengths.sum() - 144) * 2 * 2048 * 65536
    pairs = (lengths * (lengths + 1) / 2).sum()
    assert flops == pytest.approx(lengths.sum() * per_token + 3 * 4 * 2048 * pairs + head)
    assert 0.27e15 < flops < 0.30e15          # a chunk's real tokens
    call = work_lm.experts_call(config, 32_768)
    assert call["flops"] == 131_072 * 3 * 2 * 2048 * 1792
    assert call["bytes"] == 2 * (32 * 3 * 2048 * 1792 + 2 * 131_072 * 2048)
    assert call["flops"] / 197e12 > call["bytes"] / 819e9   # compute-bound


def test_readers_return_nothing_for_a_run_without_the_program_trace():
    import importlib

    cell = _cell()
    facts = {"shapes": {"batches": 6, "rows": 144, "batch_tokens": 32768},
             "config": cell["config"], "traffic": cell["traffic"], "chips": 1, "devices": 1,
             "peaks": None}
    reduced = {"window_s": 0.0, "busy_s": 0.0}
    names = [m["name"] for m in cell["per_layer"] if m["name"] != "setup_compile_ms"]
    assert len(names) == 9
    for name in names:
        reader = importlib.import_module(f"chipbench.metrics.{name}")
        assert reader.read(reduced, facts) is None, name


def test_scope_readers_on_a_hand_made_trace(monkeypatch):
    """Two batches of one expert layer: the grouped-matmul kernel calls
    carry no scope (the TPU compiler names them itself) and count as the
    experts'; a fusion counts under the innermost ``lm.*`` scope; the loop
    around the head's blocks is a container and counts nowhere."""
    from chipbench import program_trace
    from chipbench.metrics import (lm_head_ms_per_batch, lm_step_mfu, mixer_attn_ms_per_batch,
                                   mixer_conv_ms_per_batch, moe_experts_ms_per_batch,
                                   moe_experts_roofline, moe_route_ms_per_batch)

    ms = 1e6  # ns
    ops = [
        ["fusion:f.1", 0 * ms, 2 * ms, "jit(run)/lm.embed/gather"],
        ["fusion:f.2", 2 * ms, 10 * ms, "jit(run)/lm.mixer.conv/dot_general"],
        ["fusion:f.3", 12 * ms, 4 * ms, "jit(run)/lm.mixer.attn/exp"],
        ["fusion:f.4", 16 * ms, 3 * ms, "jit(run)/lm.moe.route/top_k"],
        ["sort:s.1", 19 * ms, 1 * ms, "jit(run)/lm.moe.dispatch/sort"],
        ["custom-call:ragged-dot-metadata", 20 * ms, 0.5 * ms, "ragged-dot-metadata"],
        ["custom-call:ragged-dot-none.3", 21 * ms, 20 * ms, "ragged-dot-none"],
        ["fusion:f.5", 41 * ms, 2 * ms, "jit(run)/lm.moe.experts/mul"],
        ["fusion:f.6", 43 * ms, 2 * ms, "jit(run)/lm.moe.combine/dot_general"],
        ["while:w.1", 45 * ms, 6 * ms, "jit(run)/lm.head/while"],
        ["fusion:f.7", 45 * ms, 6 * ms, "jit(run)/lm.head/while/body/dot_general"],
        ["copy:c.1", 51 * ms, 1 * ms, ""],
    ]
    run = program_trace.ProgramTrace((0.0, 60 * ms), [], {"/device:TPU:0": ops})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    cell = _cell()
    facts = {"shapes": {"batches": 2, "rows": 144, "batch_tokens": 32_768,
                        "tokens_real": 143_104, "tokens_real_sq": 3.0e8},
             "config": dict(cell["config"], num_hidden_layers=3), "chips": 1,
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    reduced = {"window_s": 0.06}
    found = moe_experts_ms_per_batch.by_scope(reduced)
    assert found["lm.moe.experts"] == pytest.approx(22.5e-3)
    assert found["all operations"] == pytest.approx(51.5e-3)   # the while left out
    assert moe_experts_ms_per_batch.read(reduced, facts) == pytest.approx(11.25)
    assert moe_route_ms_per_batch.read(reduced, facts) == pytest.approx(3.0)
    assert mixer_conv_ms_per_batch.read(reduced, facts) == pytest.approx(5.0)
    assert mixer_attn_ms_per_batch.read(reduced, facts) == pytest.approx(2.0)
    assert lm_head_ms_per_batch.read(reduced, facts) == pytest.approx(3.0)
    # one expert layer (layers 0-2 = dense, dense, expert): 14.65 ms at the peak
    least = 131_072 * 3 * 2 * 2048 * 1792 / 197e12
    assert moe_experts_roofline.read(reduced, facts) == pytest.approx(100 * least / 11.25e-3)
    want = work_lm.step_flops(facts["config"], 144, 143_104, 3.0e8) / (0.06 * 197e12) * 100
    assert lm_step_mfu.read(reduced, facts) == pytest.approx(want)
    # a program without the scopes (the parent): nothing, and no error
    bare = program_trace.ProgramTrace((0.0, 60 * ms), [], {"/device:TPU:0": [
        ["fusion:f.1", 0.0, 2 * ms, "jit(run)/ResNet/Conv_0"]]})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: bare)
    assert moe_experts_ms_per_batch.read(reduced, facts) is None
    assert moe_experts_roofline.read(reduced, facts) is None
