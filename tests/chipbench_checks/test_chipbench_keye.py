"""The long-document scoring cell, through the harness's own verdict at
rehearsal size: a sound run is ``correct``, the float8 control is not, and
each fault a learned sparse attention, a softmax router or an untied head can
have — planted under the timed path, in the program — comes out ``correct:
false``. Beside them the cell's data: its lengths, its counts of operations,
its readers on a hand-made trace and on a program without the spans."""

import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, spec, work_lm, work_lm_sparse  # noqa: E402
from chipbench.drivers import lm_score_longdocs as driver  # noqa: E402
from chipbench.drivers import lm_score_stream  # noqa: E402

CELL = "keye_score_long_docs"


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


def _drive(capsys, *more: str, seed: int = 3100000019) -> dict:
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
    assert line["failed"] == 0 and line["attempted"] % 15 == 0
    assert line["control_correct"] is False, line["control"]
    assert any(not c["ok"] for c in line["control"])
    # one row of every bucket of every chunk was compared
    rows = [c["value"] for c in line["compared"] if c["name"] == "rows_compared"]
    assert rows == [3 * line["attempted"] // 15]


def _plant(monkeypatch, fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops import moe
    from mmlspark_tpu.ops import sparse_attention as sa

    if fault == "selection_left_out":       # dense causal attention
        monkeypatch.setattr(sa, "select", lambda scores, causal, topk: jnp.broadcast_to(
            causal, scores.shape))
    elif fault == "top_k_minus_one":
        sound = sa.select
        monkeypatch.setattr(sa, "select", lambda s, causal, topk: sound(s, causal, topk - 1))
    elif fault == "relu_left_out_of_the_index":
        monkeypatch.setattr(sa, "index_scores", lambda qi, ki, w, reach=None: (
            w[..., None] * jnp.einsum("bjqd,bkd->bjqk", qi, ki,
                                      preferred_element_type=jnp.float32)).sum(1))
    elif fault == "head_weights_left_out_of_the_index":   # a plain sum over the indexer's heads
        monkeypatch.setattr(sa, "index_scores", lambda qi, ki, w, reach=None: jax.nn.relu(
            jnp.einsum("bjqd,bkd->bjqk", qi, ki, preferred_element_type=jnp.float32)).sum(1))
    elif fault == "top_k_over_all_keys_then_masked":
        sound = sa.select
        monkeypatch.setattr(sa, "select", lambda s, causal, topk: sound(
            s, jnp.ones_like(causal), topk) & causal)
    elif fault == "sigmoid_in_the_router":
        def route(u, router, top_k, norm_topk_prob=True):
            scores = jax.nn.sigmoid(moe.router_logits(u, router))
            idx = jax.lax.top_k(scores, top_k)[1]
            return idx, moe.softmax_weights(scores, idx, norm_topk_prob)
        monkeypatch.setattr(moe, "route_softmax", route)
    elif fault == "norm_topk_prob_left_out":
        sound = moe.softmax_weights
        monkeypatch.setattr(moe, "softmax_weights", lambda p, idx, norm: sound(p, idx, False))
    elif fault == "head_tied":
        sound = driver.model_config
        monkeypatch.setattr(driver, "model_config", lambda config: dict(
            sound(config), tie_word_embeddings=True))
    else:
        raise KeyError(fault)


@pytest.mark.parametrize("fault", [
    "selection_left_out", "top_k_minus_one", "relu_left_out_of_the_index",
    "head_weights_left_out_of_the_index", "top_k_over_all_keys_then_masked",
    "sigmoid_in_the_router", "norm_topk_prob_left_out", "head_tied"])
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
    lengths = lm_score_stream.chunk_lengths(traffic)
    assert list(lengths) == (list(range(2304, 4097, 256)) + list(range(5120, 8193, 1024))
                             + list(range(10240, 16385, 2048)) + [24576, 32768])
    assert len(lengths) == 18 and int(lengths.sum()) == 162_816
    assert lengths.min() > config["sa_config"]["topk"]       # every row selects
    buckets = [lm_score_stream._bucket_of(traffic, n) for n in lengths]
    assert [buckets.count(b) for b in (4096, 8192, 16384, 32768)] == [8, 4, 4, 2]
    assert sum(buckets) == 196_608
    assert {length * rows for length, rows in traffic["buckets"]} == {32_768}
    assert sum(-(-buckets.count(length) // rows) for length, rows in traffic["buckets"]) == 6
    model = driver.model_config(config)
    pool = lm_score_stream.make_pool(traffic, model, 2 ** 31 + 5)
    again = lm_score_stream.make_pool(traffic, model, 2 ** 31 + 5)
    assert sorted(len(r) for r in pool[0]) == sorted(lengths)
    assert all(np.array_equal(a, b) for a, b in zip(pool[1], again[1]))
    assert [len(r) for r in pool[0]] != [len(r) for r in pool[1]]
    assert max(int(r.max()) for r in pool[0]) > 150_000
    # the rehearsal's rows are all longer than its topk too
    small = spec.sized(traffic, True)
    assert lm_score_stream.chunk_lengths(small).min() > config["rehearse"]["sa_config"]["topk"]


def test_the_configuration_keeps_the_published_widths():
    config = _cell()["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    row = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"][0]
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 6 and config["experts_held"] == config["num_experts"]
    assert config["layer_types"] == ["full_attention"] * 6 and config["num_dense_layers"] == 0
    assert work_lm.layer_kinds(config) == [("full_attention", "moe")] * 6
    model = driver.model_config(config)
    assert model["sa_config"]["topk"] == 2048 and model["head_dim"] == 128
    # bfloat16 bytes of the six layers, the embedding and the head
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2 + 2 * 128
    index = 2048 * 1024 + 2048 * 64 + 2 * 64 + 2048 * 16
    layer = attn + index + 2048 * 128 + 128 * 3 * 2048 * 768 + 2 * 2048
    assert 8.7e9 < 2 * (6 * layer + 2 * 151_936 * 2048) < 8.8e9


def test_counts_of_operations_from_the_shapes():
    config = _cell()["config"]
    # per token and layer: W_q and W_o at 32 heads of 128, W_k and W_v at 4,
    # the indexer's three projections, the router, 8 experts of 768
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 1024 + 2048 * 64 + 2048 * 16
             + 2048 * 128 + 8 * 3 * 2048 * 768)
    assert work_lm_sparse.token_flops(config) == 2 * 6 * layer
    assert 709e6 < work_lm_sparse.token_flops(config) < 711e6
    assert work_lm_sparse.index_pairs([3, 5]) == 6 + 15
    assert work_lm_sparse.attended_pairs([3, 5], 2) == (1 + 2 + 2) + (1 + 2 + 2 + 2 + 2)
    assert work_lm_sparse.attended_pairs([4], 8) == 10
    lengths = lm_score_stream.chunk_lengths(_cell()["traffic"])
    pairs = work_lm_sparse.index_pairs(lengths)
    kept = work_lm_sparse.attended_pairs(lengths, 2048)
    assert pairs == sum(int(n) * (int(n) + 1) // 2 for n in lengths)
    assert kept == sum(2048 * 2049 // 2 + (int(n) - 2048) * 2048 for n in lengths)
    assert 1.2e9 < pairs < 1.4e9 and 2.8e8 < kept < 3.0e8
    assert work_lm_sparse.attention_flops(config, lengths) == pairs * 2048 + kept * 16384
    flops = work_lm_sparse.step_flops(config, lengths)
    head = (162_816 - 18) * 2 * 2048 * 151_936
    assert flops == pytest.approx(162_816 * 2 * 6 * layer + 6 * (pairs * 2048 + kept * 16384)
                                  + head)
    assert 0.25e15 < flops < 0.27e15          # a chunk's real tokens
    assert work_lm_sparse.attention_bytes(config, 10) == 10 * (
        2 * (2 * 4096 + 2 * 512 + 1024 + 64) + 64)
    assert work_lm_sparse.attend_bytes(config, 10) == 10 * 2 * (2 * 4096 + 2 * 512)
    assert work_lm_sparse.index_flops(config, lengths) == pairs * 2048
    # compute-bound: the operations take nine times the bytes' time
    assert (work_lm_sparse.attention_flops(config, lengths) / 197e12
            > 5 * work_lm_sparse.attention_bytes(config, 162_816) / 819e9)
    # the experts' count is the other cell's reader's: 262,144 routed rows
    call = work_lm.experts_call(config, 32_768)
    assert call["flops"] == 262_144 * 3 * 2 * 2048 * 768
    assert call["bytes"] == 2 * (128 * 3 * 2048 * 768 + 2 * 262_144 * 2048)


def _facts(cell: dict, **shapes: object) -> dict:
    return {"shapes": dict({"batches": 6, "rows": 18, "chunks": 1, "batch_tokens": 32_768},
                           **shapes),
            "config": cell["config"], "traffic": cell["traffic"], "chips": 1, "devices": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_return_nothing_for_a_run_without_the_program_trace():
    cell = _cell()
    facts = dict(_facts(cell), peaks=None)
    reduced = {"window_s": 0.0, "busy_s": 0.0}
    names = [m["name"] for m in cell["per_layer"] if m["name"] != "setup_compile_ms"]
    assert len(names) == 14 and "lm_step_mfu" not in names
    for name in names:
        reader = importlib.import_module(f"chipbench.metrics.{name}")
        assert reader.read(reduced, facts) is None, name


def test_scope_readers_on_a_hand_made_trace(monkeypatch):
    """One chunk of six batches, one layer: operations inside the device
    loop carry ``lm.mixer.attn`` and, within it, one of the three
    ``lm.attn.*`` scopes; the loops and the rare tie branch are containers
    and count nowhere; the mixer's own reader still holds all of it."""
    from chipbench import program_trace
    from chipbench.metrics import (attn_index_ms_per_batch, attn_select_ms_per_batch,
                                   attn_sparse_ms_per_batch, attn_sparse_roofline,
                                   lm_sparse_step_mfu, mixer_attn_ms_per_batch,
                                   moe_experts_ms_per_batch, moe_experts_roofline,
                                   sparse_attend_roofline)

    ms = 1e6  # ns
    attn = "jit(run)/lm.mixer.attn"
    ops = [
        ["fusion:f.1", 0 * ms, 6 * ms, f"{attn}/dot_general"],
        ["fusion:f.2", 6 * ms, 12 * ms, f"{attn}/lm.attn.index/dot_general"],
        ["while:w.1", 18 * ms, 600 * ms, f"{attn}/while"],
        ["fusion:f.3", 18 * ms, 120 * ms, f"{attn}/while/body/lm.attn.index/reduce_sum"],
        ["while:w.2", 138 * ms, 180 * ms, f"{attn}/while/body/lm.attn.select/while"],
        ["fusion:f.4", 138 * ms, 180 * ms, f"{attn}/while/body/lm.attn.select/while/body/ge"],
        ["conditional:c.1", 318 * ms, 0.1 * ms, f"{attn}/while/body/lm.attn.select/cond"],
        ["fusion:f.5", 318.1 * ms, 59.9 * ms, f"{attn}/while/body/lm.attn.sparse/convert"],
        ["custom-call:sparse_attend.7", 378 * ms, 240 * ms,
         f"{attn}/while/body/lm.attn.sparse/pallas_call"],
        ["custom-call:ragged-dot-none.3", 618 * ms, 60 * ms, "ragged-dot-none"],
        ["fusion:f.6", 678 * ms, 12 * ms, "jit(run)/lm.head/while/body/dot_general"],
    ]
    run = program_trace.ProgramTrace((0.0, 700 * ms), [], {"/device:TPU:0": ops})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    cell = _cell()
    facts = _facts(cell)
    facts["config"] = dict(cell["config"], num_hidden_layers=1, layer_types=["full_attention"])
    reduced = {"window_s": 0.7}
    assert attn_index_ms_per_batch.read(reduced, facts) == pytest.approx(22.0)
    assert attn_select_ms_per_batch.read(reduced, facts) == pytest.approx(30.0)
    assert attn_sparse_ms_per_batch.read(reduced, facts) == pytest.approx(299.9 / 6)
    assert mixer_attn_ms_per_batch.read(reduced, facts) == pytest.approx(617.9 / 6)
    assert moe_experts_ms_per_batch.read(reduced, facts) == pytest.approx(10.0)
    lengths = [int(n) for n in lm_score_stream.chunk_lengths(cell["traffic"])]
    least = work_lm_sparse.attention_flops(facts["config"], lengths) / 197e12
    assert attn_sparse_roofline.read(reduced, facts) == pytest.approx(100 * least / 0.6119)
    assert 0 < attn_sparse_roofline.read(reduced, facts) < 100
    # the kernel alone, by the selected pairs' products: its custom calls under the scope
    least = work_lm_sparse.attend_flops(facts["config"], lengths) / 197e12
    assert sparse_attend_roofline.read(reduced, facts) == pytest.approx(100 * least / 0.240)
    assert work_lm_sparse.attend_flops(facts["config"], lengths) == 16384 * work_lm_sparse.attended_pairs(
        lengths, 2048)
    want = work_lm_sparse.step_flops(facts["config"], lengths) / (0.7 * 197e12) * 100
    assert lm_sparse_step_mfu.read(reduced, facts) == pytest.approx(want)
    # the experts' roofline by the other cell's count: one layer, 262,144 routed rows
    least = 262_144 * 3 * 2 * 2048 * 768 / 197e12
    assert moe_experts_roofline.read(reduced, facts) == pytest.approx(100 * least / 10e-3)
    # a program without the scopes (the parent): nothing, and no error
    bare = program_trace.ProgramTrace((0.0, 60 * ms), [], {"/device:TPU:0": [
        ["fusion:f.1", 0.0, 2 * ms, "jit(run)/lm.mixer.attn/dot_general"]]})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: bare)
    for reader in (attn_index_ms_per_batch, attn_select_ms_per_batch, attn_sparse_ms_per_batch,
                   attn_sparse_roofline, sparse_attend_roofline):
        assert reader.read(reduced, facts) is None


def test_selected_key_share_reads_the_windows_spans(monkeypatch):
    from chipbench import program_trace
    from chipbench.metrics import attn_selected_key_share, lm_pad_token_share

    cell = _cell()
    lengths = lm_score_stream.chunk_lengths(cell["traffic"])
    kept = 6 * int(work_lm_sparse.attended_pairs(lengths, 2048))
    pairs = 6 * int(work_lm_sparse.index_pairs(lengths))
    spans = [{"name": "lm.score", "id": i + 1, "parent": None, "trace": i + 1,
              "start": (1 + i) * 1e9, "end": (1.5 + i) * 1e9,
              "attrs": {"attn_keys_selected": kept, "attn_keys_causal": pairs}}
             for i in range(2)]
    run = program_trace.ProgramTrace((0.0, 10e9), spans, {})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    monkeypatch.setattr(lm_pad_token_share, "counter", lambda name, label: {
        "selected": 3 * kept, "causal": 3 * pairs})
    share = attn_selected_key_share.read({"window_s": 10.0}, _facts(cell))
    assert share == pytest.approx(100.0 * kept / pairs)
    assert 21 < share < 23      # what the lengths give: 1 key in 4.5 on this mix
    monkeypatch.setattr(lm_pad_token_share, "counter", lambda name, label: {
        "selected": kept, "causal": pairs})
    with pytest.raises(ValueError, match="more than the counter holds"):
        attn_selected_key_share.read({"window_s": 10.0}, _facts(cell))
    # a program whose spans carry no such attribute (the other model): nothing
    for s in spans:
        s["attrs"] = {"tokens_real": 5}
    assert attn_selected_key_share.read({"window_s": 10.0}, _facts(cell)) is None


def test_a_checkout_without_the_mechanism_is_told_before_any_weight_is_made(monkeypatch):
    """The parent has ``causal_lm`` and no sparse attention: the driver sees
    that (an import, not a version) and ends the run with code 2."""
    import builtins

    real_import = builtins.__import__

    def no_sparse(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "mmlspark_tpu.ops" and "sparse_attention" in (fromlist or ()):
            raise ImportError("cannot import name 'sparse_attention'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_sparse)
    made = []
    monkeypatch.setattr(driver, "program_variables", lambda *a: made.append(a))
    with pytest.raises(SystemExit) as e:
        driver.setup(object())
    assert e.value.code == 2 and not made
