"""The language-model scorer against the plain reference, at test size.

``chipbench/reference/lfm2.py`` imports nothing of the program; here the
program's layers, its expert layer's shares and the whole stage are held to
it on seeded weights (the configuration's ``"rehearse"`` sizes: hidden 64,
8 experts top-2, vocabulary 512, 6 layers dense-conv, dense-conv, attention,
conv, conv, conv)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402
from chipbench.drivers import lm_score_stream as driver  # noqa: E402
from chipbench.reference import lfm2 as ref  # noqa: E402
from mmlspark_tpu import obs  # noqa: E402
from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.models import causal_lm as lm  # noqa: E402
from mmlspark_tpu.ops import moe  # noqa: E402

BUCKETS = [[16, 16], [32, 8], [64, 8]]
KEY = jax.random.PRNGKey(11)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")) as f:
        return driver.model_config(spec.sized(json.load(f), True))


@pytest.fixture(scope="module")
def variables(config):
    return driver.program_variables(config, KEY, lm.layer_kinds(config))


def _bf16(x):
    return jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)


def _program_layer(w):
    return {k: v if k in driver._FLOAT32 else v.astype(jnp.bfloat16) for k, v in w.items()}


def _unit_rows(rng, rows, length, h):
    """bfloat16-valued inputs of unit RMS, as a norm hands a sub-layer."""
    return np.asarray(_bf16(rng.standard_normal((rows, length, h))).astype(jnp.float32))


def test_layer_pattern_of_the_test_size_keeps_every_kind(config):
    assert lm.layer_kinds(config) == [
        ("conv", "dense"), ("conv", "dense"), ("full_attention", "moe"),
        ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    assert [ref.layer_kind(config, i) for i in range(6)] == lm.layer_kinds(config)


@pytest.mark.parametrize("length,rows", BUCKETS)
def test_program_matches_reference_in_every_bucket_shape(config, variables, length, rows):
    rng = np.random.default_rng(length)
    lens = rng.integers(max(2, length // 2 + 1), length + 1, rows)
    lens[0] = length
    packed = np.zeros((rows, length + 1), np.int32)
    for b, n in enumerate(lens):
        packed[b, :n] = rng.integers(0, config["vocab_size"], n)
    packed[:, -1] = lens
    out = np.asarray(jax.jit(lambda v, p: lm.forward(v, p, config, 16, 64))(variables, packed))
    assert out.shape == (rows, length - 1 + config["num_experts"] + 2)
    want = ref.logprobs(config, KEY, [packed[b, :n] for b, n in enumerate(lens)][:3])
    gaps = np.concatenate([np.abs(out[b, :lens[b] - 1] - want[b]) for b in range(3)])
    assert np.median(gaps) < 0.01 and np.percentile(gaps, 90) < 0.05, (
        np.median(gaps), np.percentile(gaps, 90))
    for b, n in enumerate(lens):  # nothing scored from the last real token on
        assert not out[b, n - 1:length - 1].any()
    # every real token of every expert layer is routed top_k times
    moe_layers = sum(1 for _m, f in lm.layer_kinds(config) if f == "moe")
    assert out[:, length - 1:].sum() == lens.sum() * moe_layers * config["num_experts_per_tok"]


def test_conv_mixer_alone(config):
    rng = np.random.default_rng(1)
    w = ref.make_layer_weights(config, KEY, 3)
    u = _unit_rows(rng, 2, 24, config["hidden_size"])
    mixer = jax.jit(lambda w, u: lm.conv_mixer(w, u).astype(jnp.float32))
    got = np.asarray(mixer(_program_layer(w), _bf16(u)))
    want = np.asarray(jax.jit(jax.vmap(lambda r: ref.conv_mixer(w, r, config)))(u))
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()
    # causal: a later token changes nothing before it
    u2 = u.copy()
    u2[:, 20:] = 0.0
    got2 = np.asarray(mixer(_program_layer(w), _bf16(u2)))
    assert np.array_equal(got[:, :20], got2[:, :20])


@pytest.mark.parametrize("q_block", [8, 32])
def test_attention_mixer_alone_blockwise_or_whole(config, q_block):
    rng = np.random.default_rng(2)
    w = ref.make_layer_weights(config, KEY, 2)
    u = _unit_rows(rng, 2, 32, config["hidden_size"])
    got = np.asarray(jax.jit(lambda w, u: lm.attn_mixer(w, u, config, q_block).astype(
        jnp.float32))(_program_layer(w), _bf16(u)))
    want = np.asarray(jax.jit(jax.vmap(lambda r: ref.attn_mixer(w, r, config)))(u))
    assert np.abs(got - want).max() < 0.04 * np.abs(want).max()


def test_dense_ffn_alone(config):
    rng = np.random.default_rng(3)
    w = ref.make_layer_weights(config, KEY, 0)
    u = _unit_rows(rng, 1, 40, config["hidden_size"])[0]
    got = np.asarray(jax.jit(lambda w, u: lm.dense_ffn(w, u).astype(jnp.float32))(
        _program_layer(w), _bf16(u)))
    want = np.asarray(jax.jit(ref.dense_ffn)(w, u))
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()


def test_router_picks_the_references_experts_and_weights(config):
    rng = np.random.default_rng(4)
    w = ref.make_layer_weights(config, KEY, 4)
    u = _unit_rows(rng, 1, 64, config["hidden_size"])[0]
    idx, weights = moe.route(_bf16(u), w["router"], w["expert_bias"],
                             config["num_experts_per_tok"], 1.0)
    dense = np.zeros((64, config["num_experts"]), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(weights), axis=1)
    np.testing.assert_allclose(dense, np.asarray(ref.route(w, jnp.asarray(u), config)),
                               atol=2e-6)
    # the bias steers the selection for some tokens and never the weights
    plain = moe.select(moe.router_scores(_bf16(u), w["router"]), 0.0, 2)
    assert (np.sort(np.asarray(plain), 1) != np.sort(np.asarray(idx), 1)).any()


def test_expert_shares_add_up_to_the_whole_layer_and_the_reference():
    """The share test: 32 experts top-4, ranges [0,8) .. [24,32)."""
    cfg = {"hidden_size": 64, "moe_intermediate_size": 48, "num_experts": 32,
           "num_experts_per_tok": 4, "routed_scaling_factor": 1, "num_dense_layers": 0,
           "layer_types": ["conv"], "conv_L_cache": 3}
    rng = np.random.default_rng(5)
    w = ref.make_layer_weights(cfg, KEY, 0)
    u = _unit_rows(rng, 1, 96, 64)[0]
    ub = _bf16(u)
    idx, weights = moe.route(ub, w["router"], w["expert_bias"], 4, 1.0)
    w1, w3, w2 = (_bf16(w[k]) for k in ("w1", "w3", "w2"))

    def part(lo, hi):
        return np.asarray(jax.jit(lambda a, b, c: moe.expert_ffn(
            ub, idx, weights, a, b, c, 32, (lo, hi))[0].astype(jnp.float32))(
                w1[lo:hi], w3[lo:hi], w2[lo:hi]))

    whole = part(0, 32)
    shares = [part(lo, lo + 8) for lo in range(0, 32, 8)]
    moe_ref = jax.jit(lambda u, lo, hi: ref.moe_ffn(w, u, cfg, experts=(lo, hi)),
                      static_argnums=(1, 2))
    want = np.asarray(moe_ref(u, 0, 32))
    tol = 0.03 * np.abs(want).max()
    assert np.abs(sum(shares) - whole).max() < tol
    assert np.abs(sum(shares) - want).max() < tol
    assert np.abs(whole - want).max() < tol
    for lo, share in zip(range(0, 32, 8), shares):  # and each share is its experts' part
        part_ref = np.asarray(moe_ref(u, lo, lo + 8))
        assert np.abs(share - part_ref).max() < tol
        untouched = ~((np.asarray(idx) >= lo) & (np.asarray(idx) < lo + 8)).any(1)
        assert untouched.any() and not share[untouched].any()
    with pytest.raises(ValueError, match="need 8 experts"):
        moe.expert_ffn(ub, idx, weights, w1, w3, w2, 32, (0, 8))


def test_right_padding_leaves_every_real_position_unchanged(config, variables):
    rng = np.random.default_rng(6)
    row = rng.integers(0, config["vocab_size"], 13).astype(np.int32)
    fn = jax.jit(lambda v, p: lm.forward(v, p, config, 16, 64))

    def score(length, pad_id):
        packed = np.full((8, length + 1), pad_id, np.int32)
        packed[:, :13] = row
        packed[:, -1] = 13
        return np.asarray(fn(variables, packed))[0, :12]

    base = score(16, 0)
    assert np.array_equal(base, score(16, 7))      # whatever the pad holds
    np.testing.assert_allclose(base, score(32, 0), atol=1e-5)   # however long the bucket


def _scorer(config, variables, **kw):
    return lm.CausalLMScorer(input_col="tokens", output_col="logprob", config=config,
                             variables=variables, buckets=BUCKETS, **kw)


def _frame(rows):
    col = np.empty(len(rows), dtype=object)
    col[:] = rows
    return DataFrame.from_dict({"tokens": col, "doc": np.arange(len(rows))})


def test_rows_come_back_in_the_frames_order_whatever_bucket_they_took(config, variables):
    rng = np.random.default_rng(7)
    lens = [40, 5, 64, 16, 17, 2, 33, 9, 32, 12]
    rows = [rng.integers(0, config["vocab_size"], n).astype(np.int32) for n in lens]
    obs.clear_recent_spans()
    before = {k: v for k, v in _tokens_counter().items()}
    out = _scorer(config, variables).transform(_frame(rows))
    assert list(out["doc"]) == list(range(len(rows)))
    assert [len(r) for r in out["logprob"]] == [n - 1 for n in lens]
    alone = _scorer(config, variables)
    for i in (0, 1, 5, 8):  # the same numbers as the row scored by itself
        np.testing.assert_allclose(
            out["logprob"][i], alone.transform(_frame([rows[i]]))["logprob"][0], atol=1e-5)
    # spans: one root a partition, one child a bucket around its apply_batch
    root = [s for s in obs.recent_spans() if s.name == "lm.score"][0]
    assert root.attrs["rows"] == 10 and root.attrs["tokens_real"] == sum(lens)
    padded = 16 * 16 + 8 * 32 + 8 * 64 - sum(lens)
    assert root.attrs["tokens_padded"] == padded
    buckets = [s for s in obs.recent_spans() if s.name == "lm.bucket"
               and s.parent_id == root.span_id]
    assert [(s.attrs["length"], s.attrs["rows"], s.attrs["batches"]) for s in buckets] == [
        (16, 5, 1), (32, 2, 1), (64, 3, 1)]
    applies = [s for s in obs.recent_spans() if s.name == "xla_model.apply_batch"
               and s.trace_id == root.trace_id]
    assert {s.parent_id for s in applies[:3]} == {s.span_id for s in buckets}
    after = _tokens_counter()
    assert after["real"] - before.get("real", 0) >= sum(lens)
    assert after["padded"] - before.get("padded", 0) >= padded
    routed = obs.REGISTRY.snapshot()["mmlspark_moe_tokens_routed_total"]["samples"]
    assert len(routed) == config["num_experts"] and all(v > 0 for _l, v in routed)


def _tokens_counter():
    fam = obs.REGISTRY.snapshot().get("mmlspark_lm_tokens_total") or {"samples": []}
    return {labels["kind"]: v for labels, v in fam["samples"]}


def test_a_row_no_bucket_holds_is_an_error_and_warm_up_compiles_every_bucket(config, variables):
    stage = _scorer(config, variables)
    stage.warm_up()
    assert {shape for shape, _mesh in stage._build()._jit_cache} == {(16, 17), (8, 33), (8, 65)}
    with pytest.raises(ValueError, match="longest bucket"):
        stage.transform(_frame([np.zeros(65, np.int32)]))
    with pytest.raises(ValueError, match="2 tokens at least"):
        stage.transform(_frame([np.zeros(1, np.int32)]))


def test_the_stage_reaches_the_device_only_through_apply_batch(config, variables, monkeypatch):
    from mmlspark_tpu.models.xla_model import XLAModel

    calls = []
    sound = XLAModel.apply_batch

    def spy(self, x, batch_size=None):
        calls.append((x.shape, x.dtype, batch_size))
        return sound(self, x, batch_size=batch_size)

    monkeypatch.setattr(XLAModel, "apply_batch", spy)
    monkeypatch.setattr(lm, "forward", _once_jitted(lm.forward, calls))
    rows = [np.arange(2, dtype=np.int32), np.arange(20, dtype=np.int32)]
    _scorer(config, variables).transform(_frame(rows))
    assert [c for c in calls if c != "forward"] == [
        ((1, 17), np.dtype(np.int32), 16), ((1, 33), np.dtype(np.int32), 8)]
    assert calls.count("forward") == 2   # traced once a bucket, inside apply_batch's program


def _once_jitted(fn, calls):
    def wrapped(*a, **kw):
        calls.append("forward")
        return fn(*a, **kw)
    return wrapped
