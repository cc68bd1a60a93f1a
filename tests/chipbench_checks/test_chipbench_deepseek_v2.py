"""The cell that scores documents with one chip's share of ``deepseek_v2``,
through the harness's own verdict at rehearsal size: a sound run is
``correct``, the float8 control is not, and each fault latent attention, a
group-limited router, shared experts or a held share can have — planted
under the timed path, in the program — comes out ``correct: false``. Beside
them the cell's data: its lengths, its configuration's cuts, its counts of
operations, its readers on a hand-made trace and on a program without the
scopes."""

import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, spec, work_lm, work_lm_mla  # noqa: E402
from chipbench.drivers import lm_score_share as driver  # noqa: E402
from chipbench.drivers import lm_score_stream  # noqa: E402

CELL = "deepseek_v2_score_docs"


@pytest.fixture(scope="module", autouse=True)
def _drop_the_sixteen_experts_series():
    """This module's router scores 16 experts where the other language-model
    tests' score 8: give the process-wide per-expert counter back without
    the series only this module adds (a later ``labels()`` starts at zero)."""
    yield
    from mmlspark_tpu.models import causal_lm

    for e in range(16):
        causal_lm._M_ROUTED.remove(expert=str(e))


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


def _drive(capsys, *more: str, seed: int = 3300000019) -> dict:
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
    import jax.numpy as jnp

    from mmlspark_tpu.models import causal_lm as lm
    from mmlspark_tpu.ops import latent_attention as la
    from mmlspark_tpu.ops import moe

    if fault == "plain_top_k_in_place_of_the_group_limited":
        monkeypatch.setattr(moe, "limit_to_groups", lambda probs, n_group, topk_group: probs)
    elif fault == "weights_renormalised":
        sound = moe.route_group_limited
        monkeypatch.setattr(moe, "route_group_limited", lambda u, r, k, g, tg, norm, scaling: sound(
            u, r, k, g, tg, True, scaling))
    elif fault == "routed_scaling_factor_left_out":
        sound = moe.route_group_limited
        monkeypatch.setattr(moe, "route_group_limited", lambda u, r, k, g, tg, norm, scaling: sound(
            u, r, k, g, tg, norm, 1.0))
    elif fault == "shared_experts_left_out":
        monkeypatch.setattr(lm, "shared_ffn", lambda w, u: jnp.zeros_like(u))
    elif fault == "a_shared_expert_counted_twice":
        sound = lm.shared_ffn
        monkeypatch.setattr(lm, "shared_ffn", lambda w, u: 2 * sound(w, u))
    elif fault == "kv_norm_left_out":
        sound = lm.rmsnorm
        rank = spec.sized(_cell()["config"], True)["kv_lora_rank"]   # no other norm is as narrow
        monkeypatch.setattr(lm, "rmsnorm", lambda x, scale, eps, dtype=jnp.bfloat16: (
            x.astype(dtype) if x.shape[-1] == rank else sound(x, scale, eps, dtype)))
    elif fault in ("rope_on_the_whole_head", "k_r_per_head_from_w_ukv"):
        sound = lm.latent_operands

        def operands(w, u, config):
            qn, qr, kn, kr, v = sound(w, u, config)
            if fault == "rope_on_the_whole_head":
                freqs = la.yarn_frequencies(qn.shape[-1], config["rope_theta"],
                                            config.get("rope_scaling"))
                qn, kn = (la.rotate_halves(x.astype(jnp.float32), freqs).astype(x.dtype)
                          for x in (qn, kn))
            else:
                # each head scores its rotated part against its own keys' first d_r dimensions
                freqs = la.yarn_frequencies(kr.shape[-1], config["rope_theta"],
                                            config.get("rope_scaling"))
                own = la.rotate_halves(kn[..., :kr.shape[-1]].astype(jnp.float32), freqs)
                kn = jnp.concatenate([kn, own.astype(kn.dtype)], -1)
                qn = jnp.concatenate([qn, qr], -1)
                qr, kr = jnp.zeros_like(qr), jnp.zeros_like(kr)
            return qn, qr, kn, kr, v

        monkeypatch.setattr(lm, "latent_operands", operands)
    elif fault == "yarn_left_out":
        sound = la.yarn_frequencies
        monkeypatch.setattr(la, "yarn_frequencies", lambda dim, theta, scaling: sound(
            dim, theta, None))
    elif fault == "mscale_squared_left_out":
        monkeypatch.setattr(la, "softmax_scale", lambda score_dim, scaling: score_dim ** -0.5)
    elif fault == "an_expert_ffn_in_layer_0":
        sound = lm.layer_kinds
        monkeypatch.setattr(lm, "layer_kinds", lambda config: [
            (mixer, "moe") for mixer, _ffn in sound(config)])
        make = driver.program_variables

        def variables(config, key):
            vs = make(config, key)
            vs["layers"][0] = dict(vs["layers"][1], **{
                k: v for k, v in vs["layers"][0].items() if k not in ("w1", "w3", "w2")})
            return vs

        monkeypatch.setattr(driver, "program_variables", variables)
    elif fault == "an_absent_experts_part_added":
        # the chip answers for the next group's tokens too, with its own experts' weights
        sound = moe.expert_ffn

        def both(u, idx, weights, w1, w3, w2, num_experts, experts=None):
            out, tiles = sound(u, idx, weights, w1, w3, w2, num_experts, experts)
            held = experts[1] - experts[0]
            more, _ = sound(u, idx, weights, w1, w3, w2, num_experts,
                            (experts[0] + held, experts[1] + held))
            return out + more, tiles

        monkeypatch.setattr(moe, "expert_ffn", both)
    else:
        raise KeyError(fault)


FAULTS = [
    "plain_top_k_in_place_of_the_group_limited", "weights_renormalised",
    "routed_scaling_factor_left_out", "shared_experts_left_out", "a_shared_expert_counted_twice",
    "rope_on_the_whole_head", "k_r_per_head_from_w_ukv", "yarn_left_out",
    "mscale_squared_left_out", "kv_norm_left_out", "an_expert_ffn_in_layer_0",
    "an_absent_experts_part_added"]


@pytest.mark.parametrize("fault", FAULTS)
def test_faults_come_out_not_correct(fault, monkeypatch, capsys, quiet_jax):
    _plant(monkeypatch, fault)
    line = _drive(capsys)
    assert line["correct"] is False
    assert {"logprob_rel_err_median", "logprob_rel_err_p90"} & _failed(line), line["compared"]


def _cell() -> dict:
    return spec.load_cell(ROOT, CELL)


def test_the_chunk_is_what_the_issue_describes():
    cell = _cell()
    traffic = cell["traffic"]
    lengths = lm_score_stream.chunk_lengths(traffic)
    assert list(lengths[8:]) == (list(range(2304, 4097, 256)) + list(range(5120, 8193, 1024))
                                 + [12288, 16384])
    assert lengths[0] == 1024 and lengths[7] == 2048 and (np.diff(lengths[:8]) > 140).all()
    assert len(lengths) == 22 and int(lengths.sum()) == 93_184
    buckets = [lm_score_stream._bucket_of(traffic, n) for n in lengths]
    assert [buckets.count(b) for b in (2048, 4096, 8192, 16384)] == [8, 8, 4, 2]
    assert sum(buckets) == 114_688
    assert {length * rows for length, rows in traffic["buckets"]} == {16_384}
    assert sum(-(-buckets.count(length) // rows) for length, rows in traffic["buckets"]) == 7
    model = driver.model_config(cell["config"])
    pool = driver.make_pool(traffic, model, 2 ** 31 + 5)
    again = driver.make_pool(traffic, model, 2 ** 31 + 5)
    assert sorted(len(r) for r in pool[0]) == sorted(lengths)
    assert all(np.array_equal(a, b) for a, b in zip(pool[1], again[1]))
    assert [len(r) for r in pool[0]] != [len(r) for r in pool[1]]
    top = max(int(r.max()) for r in pool[0])
    assert 12_700 < top < 12_800            # ids over the slice, all of it
    assert cell["chips"] == 1 and cell["traffic_name"] == "docs_22x16384"


def test_the_configuration_keeps_the_published_widths_and_states_its_three_cuts():
    config = _cell()["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    row = [r for r in rows if r["name"] == "DeepSeek-V2"][0]
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"} == set(
        config["reduced"])
    assert {k: row["config"][k] for k in differs} == {
        k: config["published"][k] for k in differs}
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        6, 20, 12_800)
    assert config["expert_range"] == [0, 20] and config["vocab_range"] == [0, 12_800]
    # the floors: a whole period and four layers after the dense one, 8 experts, an eighth
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= 102_400
    assert work_lm.layer_kinds(config) == [("full_attention", "dense")] + [
        ("full_attention", "moe")] * 5
    model = driver.model_config(config)
    assert model["n_routed_experts"] == 160 and model["vocab_size"] == 102_400
    assert model["n_group"] == 8 and model["expert_range"] == [0, 20]     # one routing group
    with pytest.raises(ValueError, match="do not hold"):
        driver.model_config(dict(config, expert_range=[0, 40]))
    # bfloat16 bytes: the issue's arithmetic
    assert work_lm_mla.attn_params(config) == 149_225_472            # 149.23 M
    assert work_lm_mla.expert_layer_params(config) == 669_089_792    # 669.1 M
    dense = work_lm_mla.attn_params(config) + 3 * 5120 * 12_288
    total = dense + 5 * work_lm_mla.expert_layer_params(config) + 2 * 12_800 * 5120
    assert 3.81e9 < total < 3.82e9 and 0.475 < 2 * total / 16e9 < 0.48


def test_counts_of_operations_from_the_shapes():
    cell = _cell()
    config = cell["config"]
    assert work_lm_mla.pair_flops(config) == 81_920
    assert work_lm_mla.held_share(config) == 0.125
    layer = 2 * 149_225_472
    sparse = 2 * (3 * 5120 * 3072 + 5120 * 160 + 3 * 5120 * 1536 * 6 / 8)
    assert work_lm_mla.token_flops(config) == 6 * layer + 2 * 3 * 5120 * 12_288 + 5 * sparse
    head = 2 * 5120 * 12_800
    assert 2.955e9 < work_lm_mla.token_flops(config) + head < 2.957e9     # 2,956 MFLOP a token
    assert work_lm_mla.causal_pairs([3, 5]) == 6 + 15
    lengths = [int(n) for n in lm_score_stream.chunk_lengths(cell["traffic"])]
    pairs = work_lm_mla.causal_pairs(lengths)
    assert 3.52e8 < pairs < 3.54e8
    flops = work_lm_mla.step_flops(config, lengths)
    assert flops == pytest.approx(93_184 * work_lm_mla.token_flops(config)
                                  + 6 * pairs * 81_920 + (93_184 - 22) * head)
    assert 448e12 < flops < 450e12                                       # 449 TFLOP a chunk
    assert 0.38 < 6 * pairs * 81_920 / flops < 0.40                      # the pairs, 39%
    assert 0.75 < (6 * pairs * 81_920 + 93_184 * 6 * layer) / flops < 0.77   # attention, 76%
    call = work_lm_mla.pairs_call(config, [10])
    assert call["flops"] == 55 * 81_920
    assert call["bytes"] == 2 * 10 * (128 * 192 + 128 * 128 + 64 + 2 * 128 * 128)
    long = work_lm_mla.pairs_call(config, [16_384])
    assert long["flops"] / 197e12 > 15 * long["bytes"] / 819e9           # compute-bound
    held = work_lm_mla.held_experts_call(config, 16_384)
    assert held["flops"] == 12_288 * 3 * 2 * 5120 * 1536
    assert held["bytes"] == 2 * (20 * 3 * 5120 * 1536 + 2 * 12_288 * 5120)
    assert held["flops"] / 197e12 > 2 * held["bytes"] / 819e9    # compute-bound, by 2
    # the accepted count knows no share: it credits every token's 6 routed rows, eight times
    # what is multiplied here, which is why the cell is not under its reader
    assert 16_384 * 6 * 3 * 2 * 5120 * 1536 == 8 * held["flops"]


def _facts(cell: dict, **shapes: object) -> dict:
    return {"shapes": dict({"batches": 7, "rows": 22, "chunks": 1, "batch_tokens": 16_384},
                           **shapes),
            "config": cell["config"], "traffic": cell["traffic"], "chips": 1, "devices": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


NEW_READERS = ("lm_mla_step_mfu", "attn_latent_ms_per_batch", "attn_pairs_ms_per_batch",
               "attn_pairs_roofline", "latent_attend_roofline", "ffn_shared_ms_per_batch",
               "moe_held_experts_roofline", "moe_held_pair_share")


def test_the_cells_metrics_are_the_issues_and_read_nothing_without_the_program_trace():
    cell = _cell()
    names = [m["name"] for m in cell["per_layer"]]
    assert names == ["setup_compile_ms", "moe_experts_ms_per_batch", "moe_route_ms_per_batch",
                     "mixer_attn_ms_per_batch", "lm_head_ms_per_batch", "lm_pad_token_share",
                     "moe_expert_load_max_over_mean", *NEW_READERS]
    assert [m["name"] for m in cell["end_to_end"]] == ["rows_per_s", "setup_s"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
        if m["name"] in ("moe_experts_roofline", "lm_step_mfu", "lm_sparse_step_mfu"):
            assert CELL not in m["workloads"]        # their counts are wrong for a held share
    facts = dict(_facts(cell), peaks=None)
    reduced = {"window_s": 0.0, "busy_s": 0.0}
    for name in names[1:]:
        reader = importlib.import_module(f"chipbench.metrics.{name}")
        assert reader.read(reduced, facts) is None, name


def test_scope_readers_on_a_hand_made_trace(monkeypatch):
    """One chunk of seven batches: the latent scopes lie inside
    ``lm.mixer.attn`` (whose reader still holds the whole mixer), the
    experts' kernel calls inside the share's device loop, the shared experts
    under a scope no accepted reader lists."""
    from chipbench import program_trace
    from chipbench.metrics import (attn_latent_ms_per_batch, attn_pairs_ms_per_batch,
                                   attn_pairs_roofline, ffn_shared_ms_per_batch,
                                   latent_attend_roofline, lm_mla_step_mfu,
                                   mixer_attn_ms_per_batch, moe_experts_ms_per_batch,
                                   moe_held_experts_roofline, moe_route_ms_per_batch)

    ms = 1e6  # ns
    attn = "jit(run)/lm.mixer.attn"
    ops = [
        ["fusion:f.1", 0 * ms, 70 * ms, f"{attn}/lm.attn.latent/dot_general"],
        ["fusion:f.2", 70 * ms, 14 * ms, f"{attn}/lm.attn.pairs/convert"],
        ["custom-call:latent_attend.3", 84 * ms, 2800 * ms, f"{attn}/lm.attn.pairs/pallas_call"],
        ["fusion:f.3", 2884 * ms, 35 * ms, f"{attn}/dot_general"],
        ["fusion:f.4", 2919 * ms, 21 * ms, "jit(run)/lm.moe.route/reduce_max"],
        ["while:w.1", 2940 * ms, 100 * ms, "jit(run)/while"],
        ["fusion:f.5", 2940 * ms, 7 * ms, "jit(run)/while/body/lm.moe.dispatch/gather"],
        ["custom-call:expert_gmm.9", 2947 * ms, 70 * ms,
         "jit(run)/while/body/lm.moe.experts/pallas_call"],
        ["fusion:f.6", 3017 * ms, 14 * ms, "jit(run)/while/body/lm.moe.combine/scatter-add"],
        ["fusion:f.7", 3040 * ms, 42 * ms, "jit(run)/lm.ffn.shared/dot_general"],
        ["fusion:f.8", 3082 * ms, 28 * ms, "jit(run)/lm.head/while/body/dot_general"],
    ]
    run = program_trace.ProgramTrace((0.0, 3200 * ms), [], {"/device:TPU:0": ops})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    cell = _cell()
    facts = _facts(cell)
    one = dict(cell["config"], num_hidden_layers=2, first_k_dense_replace=1)
    facts["config"] = one
    reduced = {"window_s": 3.2}
    assert attn_latent_ms_per_batch.read(reduced, facts) == pytest.approx(10.0)
    assert attn_pairs_ms_per_batch.read(reduced, facts) == pytest.approx(402.0)
    assert ffn_shared_ms_per_batch.read(reduced, facts) == pytest.approx(6.0)
    assert mixer_attn_ms_per_batch.read(reduced, facts) == pytest.approx(2919 / 7)
    assert moe_experts_ms_per_batch.read(reduced, facts) == pytest.approx(10.0)
    assert moe_route_ms_per_batch.read(reduced, facts) == pytest.approx(6.0)
    lengths = [int(n) for n in lm_score_stream.chunk_lengths(cell["traffic"])]
    least = 2 * work_lm_mla.causal_pairs(lengths) * 81_920 / 197e12
    assert attn_pairs_roofline.read(reduced, facts) == pytest.approx(100 * least / 2.814)
    assert latent_attend_roofline.read(reduced, facts) == pytest.approx(100 * least / 2.8)
    assert 0 < attn_pairs_roofline.read(reduced, facts) < latent_attend_roofline.read(
        reduced, facts) < 100
    want = 100 * work_lm_mla.step_flops(one, lengths) / (3.2 * 197e12)
    assert lm_mla_step_mfu.read(reduced, facts) == pytest.approx(want)
    # one expert layer, 12,288 routed rows a batch through three products
    least = 12_288 * 3 * 2 * 5120 * 1536 / 197e12
    assert moe_held_experts_roofline.read(reduced, facts) == pytest.approx(100 * least / 10e-3)
    assert 0 < moe_held_experts_roofline.read(reduced, facts) < 100
    # a program without the scopes (the parent): nothing, and no error
    bare = program_trace.ProgramTrace((0.0, 60 * ms), [], {"/device:TPU:0": [
        ["fusion:f.1", 0.0, 2 * ms, "jit(run)/lm.mixer.attn/dot_general"]]})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: bare)
    for reader in (attn_latent_ms_per_batch, attn_pairs_ms_per_batch, attn_pairs_roofline,
                   latent_attend_roofline, ffn_shared_ms_per_batch, moe_held_experts_roofline):
        assert reader.read(reduced, facts) is None


def test_held_pair_share_reads_the_windows_spans(monkeypatch):
    from chipbench import program_trace
    from chipbench.metrics import lm_pad_token_share, moe_held_pair_share

    cell = _cell()
    routed = 93_184 * 5 * 6
    spans = [{"name": "lm.score", "id": i + 1, "parent": None, "trace": i + 1,
              "start": (1 + i) * 1e9, "end": (1.5 + i) * 1e9,
              "attrs": {"moe_pairs_held": routed // 8 + 1000 * i, "moe_pairs_routed": routed}}
             for i in range(2)]
    run = program_trace.ProgramTrace((0.0, 10e9), spans, {})
    monkeypatch.setattr(program_trace, "of_run", lambda reduced: run)
    monkeypatch.setattr(lm_pad_token_share, "counter", lambda name, label: {
        str(e): 3 * routed / 160 for e in range(160)})
    share = moe_held_pair_share.read({"window_s": 10.0}, _facts(cell))
    assert share == pytest.approx(100.0 * (2 * (routed // 8) + 1000) / (2 * routed))
    assert 12.5 < share < 12.6
    monkeypatch.setattr(lm_pad_token_share, "counter", lambda name, label: {"0": routed})
    with pytest.raises(ValueError, match="more than the counter holds"):
        moe_held_pair_share.read({"window_s": 10.0}, _facts(cell))
    # a program that holds every expert says nothing of a share
    for s in spans:
        s["attrs"] = {"tokens_real": 5}
    assert moe_held_pair_share.read({"window_s": 10.0}, _facts(cell)) is None


def test_a_checkout_without_the_mechanism_is_told_before_any_weight_is_made(monkeypatch):
    """The parent has ``causal_lm``, ``sparse_attention`` and ``moe`` and no
    latent attention: the driver sees that (an import, not a version) and
    ends the run with code 2."""
    import builtins

    real_import = builtins.__import__

    def no_latent(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "mmlspark_tpu.ops" and "latent_attention" in (fromlist or ()):
            raise ImportError("cannot import name 'latent_attention'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_latent)
    made = []
    monkeypatch.setattr(driver, "program_variables", lambda *a: made.append(a))
    with pytest.raises(SystemExit) as e:
        driver.setup(object())
    assert e.value.code == 2 and not made
