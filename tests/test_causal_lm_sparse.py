"""The scorer's second language model — attention over an indexer's
selection, a softmax router, an untied head (``KeyeVL2``) — against its plain
reference, at test size.

``chipbench/reference/keye.py`` imports nothing of the program; here the
indexer, the exact selection, the attention over it, the router, the expert
layer's shares and the whole stage are held to it on seeded weights (the
configuration's ``"rehearse"`` sizes: hidden 64, 4 / 2 heads of 32, an
indexer of 4 heads of 16 that keeps 8 keys a query, 8 experts top-2,
vocabulary 512, 3 layers). ``tests/test_causal_lm.py`` holds the first
model to its own reference through the same ``forward``."""

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
from chipbench.drivers import lm_score_longdocs as driver  # noqa: E402
from chipbench.reference import keye as ref  # noqa: E402
from mmlspark_tpu import obs  # noqa: E402
from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.models import causal_lm as lm  # noqa: E402
from mmlspark_tpu.ops import moe, sparse_attention as sa  # noqa: E402

BUCKETS = [[16, 16], [32, 8], [64, 8]]
KEY = jax.random.PRNGKey(31)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "keye_vl2_30b_a3b.json")) as f:
        return driver.model_config(spec.sized(json.load(f), True))


@pytest.fixture(scope="module")
def variables(config):
    return driver.program_variables(config, KEY)


def _bf16(x):
    return jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)


def _program_layer(w):
    return {k: v if k in driver._FLOAT32 else v.astype(jnp.bfloat16) for k, v in w.items()}


def _unit_rows(rng, rows, length, h):
    """bfloat16-valued inputs of unit RMS, as a norm hands a sub-layer."""
    return np.asarray(_bf16(rng.standard_normal((rows, length, h))).astype(jnp.float32))


def _key_counts(out):
    tail = out[:, -4:].astype(np.int64)
    return tail[:, 0] * 4096 + tail[:, 1], tail[:, 2] * 4096 + tail[:, 3]


def test_the_configurations_keys_choose_mixer_router_and_head(config):
    assert lm.layer_kinds(config) == [("full_attention", "moe")] * 3
    assert lm.selects_keys(config) and lm.norm_eps(config) == 1e-6
    assert moe.router_kind(config) == "softmax"
    assert config["tie_word_embeddings"] is False and config["head_dim"] == 32
    # the first model's keys: no indexer, the sigmoid router, a tied head
    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")) as f:
        from chipbench.drivers import lm_score_stream
        first = lm_score_stream.model_config(spec.sized(json.load(f), True))
    assert not lm.selects_keys(first) and moe.router_kind(first) == "sigmoid"
    assert first.get("tie_word_embeddings", True) and "head_dim" not in first
    assert moe.router_kind({"scoring_func": "softmax", "routed_scaling_factor": 1}) == "softmax"


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
    experts = config["num_experts"]
    assert out.shape == (rows, length - 1 + experts + 2 + 4)
    # the reference runs each row padded as the program saw it
    want = ref.logprobs(config, KEY, [packed[b, :length] for b in range(3)])
    gaps = np.concatenate([np.abs(out[b, :lens[b] - 1] - want[b][:lens[b] - 1])
                           for b in range(3)])
    assert np.median(gaps) < 0.002 and np.percentile(gaps, 90) < 0.01, (
        np.median(gaps), np.percentile(gaps, 90))
    for b, n in enumerate(lens):  # nothing scored from the last real token on
        assert not out[b, n - 1:length - 1].any()
    # every real token of every layer is routed top_k times
    load = out[:, length - 1:length - 1 + experts]
    assert load.sum() == lens.sum() * 3 * config["num_experts_per_tok"]
    # every real position attended min(t + 1, topk) keys of its t + 1, in every layer
    topk = config["sa_config"]["topk"]
    selected, causal = _key_counts(out)
    assert list(selected) == [3 * sum(min(t + 1, topk) for t in range(n)) for n in lens]
    assert list(causal) == [3 * n * (n + 1) // 2 for n in lens]


def _indexer_inputs(config, rng, rows, length):
    w = ref.make_layer_weights(config, KEY, 1)
    u = _unit_rows(rng, rows, length, config["hidden_size"])
    return w, u


def test_index_scores_alone(config):
    rng = np.random.default_rng(1)
    w, u = _indexer_inputs(config, rng, 2, 48)

    def program(w, u):
        qi, ki, wt = lm.indexer(w, u, config)
        return sa.index_scores(jnp.moveaxis(qi, 1, 2), ki, jnp.moveaxis(wt, 1, 2))

    got = np.asarray(jax.jit(program)(_program_layer(w), _bf16(u)))

    def plain(row):
        p = ref.projections(w, row, config)
        return ref.index_scores(p["qi"], p["ki"], p["wt"])

    want = np.asarray(jax.jit(jax.vmap(plain))(u))
    assert got.shape == want.shape == (2, 48, 48)
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()
    # the ReLU and the heads' weights are both there: neither a plain sum
    # over the heads nor an unrectified product reads the same
    p = ref.projections(w, jnp.asarray(u[0]), config)
    dots = np.asarray(jnp.einsum("qjd,kd->qjk", p["qi"], p["ki"]))
    assert np.abs(np.maximum(dots, 0).sum(1) - want[0]).max() > 0.3 * np.abs(want).max()
    assert np.abs((np.asarray(p["wt"])[:, :, None] * dots).sum(1) - want[0]).max() \
        > 0.3 * np.abs(want).max()


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_kth_largest_is_exact_whatever_the_bits_a_pass(bits):
    rng = np.random.default_rng(bits)
    keys = rng.integers(0, 2 ** 32, (3, 5, 200), dtype=np.uint64).astype(np.uint32)
    keys[0, 0, :50] = keys[0, 0, 50]          # ties
    keys[1, 1, 7:] = 0                        # fewer than k keys above 0
    got = np.asarray(jax.jit(lambda x: sa.kth_largest(x, 9, bits))(jnp.asarray(keys)))
    want = np.sort(keys, axis=-1)[..., -9]
    assert np.array_equal(got, want)
    assert got[1, 1] == 0


def test_sortable_keeps_the_order_of_float32():
    x = np.array([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, 7e30, np.inf], np.float32)
    keys = np.asarray(sa.sortable(jnp.asarray(x)))
    assert np.all(np.diff(keys.astype(np.int64)) >= 0) and keys[0] > 0
    rng = np.random.default_rng(0)
    y = rng.standard_normal(1000).astype(np.float32)
    assert np.array_equal(np.argsort(np.asarray(sa.sortable(jnp.asarray(y))), kind="stable"),
                          np.argsort(y, kind="stable"))


@pytest.mark.parametrize("topk", [1, 8, 20])
def test_the_selection_is_top_ks_set_for_every_query(topk):
    rng = np.random.default_rng(topk)
    scores = rng.standard_normal((2, 40, 40)).astype(np.float32)
    scores[0, :, 5:9] = 0.0       # exact ties, as an all-negative indexer gives
    scores[1, 30] = 1.5           # a whole query of ties: the earliest keys stay
    causal = np.arange(40)[:, None] >= np.arange(40)[None, :]
    got = np.asarray(jax.jit(lambda s: sa.select(s, jnp.asarray(causal), topk))(scores))
    want = np.asarray(jax.vmap(lambda s: ref.selection(s, 0, topk))(jnp.asarray(scores)))
    assert np.array_equal(got, want)
    for t in range(40):           # all the causal keys while t + 1 <= topk, topk after
        assert (got[:, t].sum(-1) == min(t + 1, topk)).all()
        assert not got[:, t, t + 1:].any()
    if topk == 8:
        assert list(np.nonzero(got[1, 30])[0]) == list(range(8))


def test_a_later_key_never_enters_the_selection_whatever_it_scores():
    scores = np.zeros((1, 8, 24), np.float32)
    scores[:, :, 12:] = 1e9       # keys after every query, pads among them
    causal = (4 + np.arange(8))[:, None] >= np.arange(24)[None, :]
    got = np.asarray(sa.select(jnp.asarray(scores), jnp.asarray(causal), 3))
    assert not got[:, :, 12:].any() and (got.sum(-1) == 3).all()


def _attend_inputs(rng, rows, queries, keys, nq=4, nkv=2, d=32):
    q = _unit_rows(rng, rows, queries, nq * d).reshape(rows, queries, nq, d)
    k = _unit_rows(rng, rows, keys, nkv * d).reshape(rows, keys, nkv, d)
    v = _unit_rows(rng, rows, keys, nkv * d).reshape(rows, keys, nkv, d)
    return q, k, v


def _kernel_layout(q, k, v):
    """(B, Q, nq, d), (B, K, nkv, d) x2 -> the op's own (B, nkv, g, Q, d), (B, nkv, K, d) x2."""
    rows, queries, nq, d = q.shape
    nkv = k.shape[2]
    return (jnp.moveaxis(_bf16(q).reshape(rows, queries, nkv, nq // nkv, d), 1, 3),
            jnp.moveaxis(_bf16(k), 1, 2), jnp.moveaxis(_bf16(v), 1, 2))


def _natural(o):
    """(B, nkv, g, Q, d) -> (B, Q, nq * d) float32."""
    o = np.asarray(jnp.moveaxis(o, 3, 1).astype(jnp.float32))
    return o.reshape(*o.shape[:2], -1)


def test_attention_over_the_selection_alone(config):
    rng = np.random.default_rng(3)
    q, k, v = _attend_inputs(rng, 2, 24, 24)
    picked = np.tril(rng.random((2, 24, 24)) < 0.4) | np.eye(24, dtype=bool)
    got = _natural(jax.jit(sa.attend)(*_kernel_layout(q, k, v), jnp.asarray(picked)))
    want = np.asarray(jax.vmap(ref.attend)(q, k, v, jnp.asarray(picked)))
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()
    # a key outside a query's selection changes nothing for it: move key 3's value
    v2 = v.copy()
    v2[0, 3] += 5.0
    again = _natural(jax.jit(sa.attend)(*_kernel_layout(q, k, v2), jnp.asarray(picked)))
    without = ~picked[0, :, 3]
    assert without.sum() > 5 and np.array_equal(got[0, without], again[0, without])
    assert not np.array_equal(got[0, ~without], again[0, ~without])


@pytest.mark.parametrize("reach", [None, 300, 767])
def test_the_kernel_reads_what_the_plain_operations_read(reach):
    """The Pallas attention kernel (interpreted here; compiled for the v5e in
    tests/test_tpu_compile.py) against the XLA form the CPU runs: a block of
    64 queries that end at key ``reach`` against 768 keys, three tiles of 256."""
    rng = np.random.default_rng(12)
    last = 767 if reach is None else reach
    q, k, v = _kernel_layout(*_attend_inputs(rng, 2, 64, 768))
    causal = (last - 63 + np.arange(64))[:, None] >= np.arange(768)[None, :]
    picked = (rng.random((2, 64, 768)) < 0.1) & causal
    picked[:, :, 0] = True                       # every query keeps a key
    picked[0, 5, :300] = False
    picked[0, 5, min(last - 63 + 5, 290)] = True  # one whose first tile may hold none
    got = sa.attend_kernel(q, k, v, jnp.asarray(picked), reach, interpret=True)
    want = sa.attend_xla(q, k, v, jnp.asarray(picked))
    np.testing.assert_allclose(_natural(got), _natural(want), rtol=0.02, atol=0.02)


def test_the_whole_mechanism_is_the_same_through_the_kernel(config, monkeypatch):
    rng = np.random.default_rng(13)
    w, u = _indexer_inputs(config, rng, 2, 64)
    real = jnp.arange(64)[None, :] < jnp.array([64, 40])[:, None]
    fn = lambda: jax.jit(lambda w, u: lm.sparse_attn_mixer(w, u, config, 16, real))(  # noqa: E731
        _program_layer(w), _bf16(u))
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "0")
    plain, kept_plain = fn()
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    kernel, kept = fn()
    assert list(np.asarray(kept)) == list(np.asarray(kept_plain))
    gaps = np.abs(np.asarray(kernel.astype(jnp.float32)) - np.asarray(plain.astype(jnp.float32)))
    assert np.percentile(gaps, 99) < 1e-4 and gaps.max() < 0.6 * np.abs(np.asarray(
        plain.astype(jnp.float32))).max()


@pytest.mark.parametrize("q_block", [8, 16, 64])
def test_attention_mixer_alone_whatever_the_query_block(config, q_block):
    rng = np.random.default_rng(4)
    w, u = _indexer_inputs(config, rng, 2, 64)
    real = jnp.arange(64)[None, :] < jnp.array([64, 40])[:, None]
    got, kept = jax.jit(lambda w, u: lm.sparse_attn_mixer(w, u, config, q_block, real))(
        _program_layer(w), _bf16(u))
    fns = ref._programs(config, None)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref.attention(fns, ref.projections(w, jnp.asarray(r), config))
                                    @ w["wo"]) for r in u])
    got = np.asarray(got.astype(jnp.float32))
    # a near-tie at the selection's edge swaps one key of a query's 8 between
    # bfloat16 and float32 index scores: most positions agree to rounding,
    # whatever the block, and a swap stays within what one key carries
    gaps = np.abs(got - want) / np.abs(want).max()
    assert np.percentile(gaps, 90) < 0.02 and gaps.max() < 0.6, (np.percentile(gaps, 90), gaps.max())
    topk = config["sa_config"]["topk"]
    assert list(np.asarray(kept)) == [sum(min(t + 1, topk) for t in range(n)) for n in (64, 40)]


def test_key_spans_cover_the_blocks_once():
    for blocks in (1, 2, 3, 4, 7, 16, 128):
        spans = sa.key_spans(blocks)
        assert len(spans) == min(4, blocks) and spans[0][0] == 0 and spans[-1][1] == blocks
        assert all(a[1] == b[0] and a[0] < a[1] for a, b in zip(spans, spans[1:]))
    with pytest.raises(ValueError, match="no multiple of the query block"):
        sa.sparse_attention(jnp.zeros((1, 24, 1, 1, 8)), *[jnp.zeros((1, 24, 1, 8))] * 2,
                            jnp.zeros((1, 24, 1, 8)), jnp.zeros((1, 24, 8)), jnp.zeros((1, 24, 1)),
                            jnp.ones((1, 24), bool), 4, 16)


def test_softmax_router_picks_the_references_experts_and_weights(config):
    rng = np.random.default_rng(5)
    w = ref.make_layer_weights(config, KEY, 2)
    u = _unit_rows(rng, 1, 200, config["hidden_size"])[0]
    idx, weights = jax.jit(lambda u, r: moe.route_softmax(u, r, 2, True))(_bf16(u), w["router"])
    want_idx, want_w = ref.route(w, jnp.asarray(u), config)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(want_idx), -1))
    np.testing.assert_allclose(np.sort(np.asarray(weights), -1), np.sort(np.asarray(want_w), -1),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # norm_topk_prob false: the probabilities as they are, of a softmax over all experts
    _, raw = jax.jit(lambda u, r: moe.route_softmax(u, r, 2, False))(_bf16(u), w["router"])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(u) @ w["router"], -1))
    np.testing.assert_allclose(np.sort(np.asarray(raw), -1), np.sort(probs, -1)[:, -2:], rtol=2e-4)
    assert (np.asarray(raw).sum(-1) < 0.9).any()


def test_expert_ranges_add_up_to_the_whole_layer_and_the_reference(config):
    """The share test for the softmax router: the parts that experts [0,2),
    [2,4), [4,6), [6,8) give add up to the layer, and to the plain reference."""
    rng = np.random.default_rng(6)
    w = ref.make_layer_weights(config, KEY, 0)
    u = _unit_rows(rng, 1, 96, config["hidden_size"])[0]
    pw = _program_layer(w)
    ub = _bf16(u)
    idx, weights = moe.route_softmax(ub, pw["router"], 2, True)

    def share(lo, hi):
        return np.asarray(moe.expert_ffn(ub, idx, weights, pw["w1"][lo:hi], pw["w3"][lo:hi],
                                         pw["w2"][lo:hi], 8, (lo, hi))[0].astype(jnp.float32))

    whole = share(0, 8)
    parts = [share(lo, lo + 2) for lo in (0, 2, 4, 6)]
    ref_idx, ref_w = ref.route(w, jnp.asarray(u), config)

    def plain(lo, hi):
        tok, wt = ref.expert_table(np.asarray(ref_idx), np.asarray(ref_w), 8, (lo, hi))
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.experts_ffn(w, jnp.asarray(u), jnp.asarray(tok),
                                              jnp.asarray(wt), lo))

    want = plain(0, 8)
    tol = 0.03 * np.abs(want).max()
    assert np.abs(sum(parts) - whole).max() < tol
    assert np.abs(whole - want).max() < tol
    for lo, part in zip((0, 2, 4, 6), parts):
        assert np.abs(part - plain(lo, lo + 2)).max() < tol
        untouched = ~((np.asarray(idx) >= lo) & (np.asarray(idx) < lo + 2)).any(1)
        assert untouched.any() and not part[untouched].any()
    # and through the model's own layer: forward's moe_ffn with a range
    held = dict(pw, **{k: pw[k][2:4] for k in ("w1", "w3", "w2")})
    out, _idx, _tiles = lm.moe_ffn(held, ub, config, (2, 4))
    assert np.abs(np.asarray(out.astype(jnp.float32)) - parts[1]).max() == 0


def test_the_references_expert_table_holds_every_routed_pair_once():
    rng = np.random.default_rng(7)
    idx = np.stack([rng.permutation(8)[:2] for _ in range(50)]).astype(np.int32)
    weights = rng.random((50, 2)).astype(np.float32)
    tok, wt = ref.expert_table(idx, weights, 8)
    assert tok.shape == wt.shape and tok.shape[0] == 8
    for e in range(8):
        at = np.nonzero(wt[e])[0]
        assert sorted(tok[e, at]) == sorted(np.nonzero((idx == e).any(1))[0])
    assert np.isclose(wt.sum(), weights.sum())
    share_tok, share_wt = ref.expert_table(idx, weights, 8, (2, 4))
    assert share_tok.shape[0] == 2 and np.isclose(
        share_wt.sum(), weights[(idx >= 2) & (idx < 4)].sum())


def test_right_padding_leaves_every_real_position_unchanged_with_the_selection_on(
        config, variables):
    rng = np.random.default_rng(8)
    row = rng.integers(0, config["vocab_size"], 27).astype(np.int32)   # 27 > topk 8
    fn = jax.jit(lambda v, p: lm.forward(v, p, config, 16, 64))

    def score(length, pad_id):
        packed = np.full((8, length + 1), pad_id, np.int32)
        packed[:, :27] = row
        packed[:, -1] = 27
        out = np.asarray(fn(variables, packed))
        return out[0, :26], _key_counts(out[:1])

    base, counts = score(32, 0)
    other, counts_7 = score(32, 7)
    assert np.array_equal(base, other)             # whatever the pad holds
    longer, counts_64 = score(64, 0)
    np.testing.assert_allclose(base, longer, atol=1e-5)   # however long the bucket
    assert counts == counts_7 == counts_64         # and no pad key was ever counted


def test_the_head_is_a_matrix_of_its_own(config, variables):
    rng = np.random.default_rng(9)
    packed = np.zeros((8, 17), np.int32)
    packed[:, :16] = rng.integers(0, config["vocab_size"], (8, 16))
    packed[:, -1] = 16
    fn = jax.jit(lambda v, p, c=config: lm.forward(v, p, c, 16, 64))
    untied = np.asarray(fn(variables, packed))[:, :15]
    tied_config = dict(config, tie_word_embeddings=True)
    tied = np.asarray(jax.jit(lambda v, p: lm.forward(v, p, tied_config, 16, 64))(
        variables, packed))[:, :15]
    swapped = np.asarray(fn(dict(variables, head=variables["embed"]), packed))[:, :15]
    assert np.array_equal(tied, swapped) and np.abs(tied - untied).max() > 0.1


def _frame(rows):
    col = np.empty(len(rows), dtype=object)
    col[:] = rows
    return DataFrame.from_dict({"tokens": col, "doc": np.arange(len(rows))})


def _keys_counter():
    fam = obs.REGISTRY.snapshot().get("mmlspark_lm_attn_keys_total") or {"samples": []}
    return {labels["kind"]: v for labels, v in fam["samples"]}


def test_the_stage_counts_the_keys_attended_and_says_them_on_its_span(config, variables):
    rng = np.random.default_rng(10)
    lens = [40, 12, 64, 16, 9, 33]
    rows = [rng.integers(0, config["vocab_size"], n).astype(np.int32) for n in lens]
    stage = lm.CausalLMScorer(input_col="tokens", output_col="logprob", config=config,
                              variables=variables, buckets=BUCKETS)
    obs.clear_recent_spans()
    before = _keys_counter()
    out = stage.transform(_frame(rows))
    assert [len(r) for r in out["logprob"]] == [n - 1 for n in lens]
    topk = config["sa_config"]["topk"]
    selected = 3 * sum(min(t + 1, topk) for n in lens for t in range(n))
    causal = 3 * sum(n * (n + 1) // 2 for n in lens)
    root = [s for s in obs.recent_spans() if s.name == "lm.score"][0]
    assert root.attrs["attn_keys_selected"] == selected
    assert root.attrs["attn_keys_causal"] == causal
    after = _keys_counter()
    assert after["selected"] - before.get("selected", 0) == selected
    assert after["causal"] - before.get("causal", 0) == causal
    routed = obs.REGISTRY.snapshot()["mmlspark_moe_tokens_routed_total"]["samples"]
    assert all(v > 0 for _l, v in routed)


def test_count_columns_hold_a_long_rows_counts_exactly():
    # 48 layers of a 32,768-token row: 6.7e7 keys a layer, past float32's 2**24
    count = jnp.array([32768 * 2048 - 2047 * 1024, 5], jnp.int32)
    cols = np.asarray(sum(lm.count_columns(count) for _ in range(48)), np.float64)
    assert list(cols[:, 0] * 4096 + cols[:, 1]) == [48 * int(count[0]), 240]
