"""The scorer as one chip's share of a latent-attention model
(``deepseek_v2``) against its plain reference, at test size.

``chipbench/reference/deepseek_v2.py`` imports nothing of the program; here
the program's latent attention, its group-limited router, its shared
experts, the expert layer's shares, the sliced vocabulary and the whole
stage are held to it on seeded weights (the configuration's ``"rehearse"``
sizes: hidden 64, 4 heads of 16 + 8 / 16, ranks 24 / 16, 16 experts in 4
groups of which 2 are kept, top-4, 4 experts held, 1 shared, 512 of 2,048
ids, 1 dense + 2 expert layers, YaRN over an original context of 16)."""

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
from chipbench.drivers import lm_score_share as driver  # noqa: E402
from chipbench.reference import deepseek_v2 as ref  # noqa: E402
from mmlspark_tpu import obs  # noqa: E402
from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.models import causal_lm as lm  # noqa: E402
from mmlspark_tpu.ops import grouped_matmul, latent_attention as la, moe  # noqa: E402

BUCKETS = [[16, 16], [32, 8], [64, 8]]
KEY = jax.random.PRNGKey(33)


@pytest.fixture(scope="module", autouse=True)
def _drop_the_sixteen_experts_series():
    """This module's router scores 16 experts where the other language-model
    tests' score 8: give the process-wide per-expert counter back without
    the series only this module adds (a later ``labels()`` starts at zero)."""
    yield
    from mmlspark_tpu.models import causal_lm

    for e in range(16):
        causal_lm._M_ROUTED.remove(expert=str(e))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "deepseek_v2.json")) as f:
        return driver.model_config(spec.sized(json.load(f), True))


@pytest.fixture(scope="module")
def whole(config):
    """The same model with every expert and the whole vocabulary here."""
    return {k: v for k, v in config.items() if k not in ("expert_range", "vocab_range")}


def _bf16(x):
    return jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)


def _program_layer(w):
    return {k: v if k in driver._FLOAT32 else v.astype(jnp.bfloat16) for k, v in w.items()}


def _unit_rows(rng, rows, length, h):
    """bfloat16-valued inputs of unit RMS, as a norm hands a sub-layer."""
    return np.asarray(_bf16(rng.standard_normal((rows, length, h))).astype(jnp.float32))


def _packed(rng, length, rows, ids):
    lens = rng.integers(max(2, length // 2 + 1), length + 1, rows)
    lens[0] = length
    packed = np.zeros((rows, length + 1), np.int32)
    for b, n in enumerate(lens):
        packed[b, :n] = rng.integers(0, ids, n)
    packed[:, -1] = lens
    return packed, lens


@pytest.mark.parametrize("share", [True, False], ids=["share", "whole"])
@pytest.mark.parametrize("length,rows", BUCKETS)
def test_program_matches_reference_in_every_bucket_shape(config, whole, length, rows, share):
    cfg = config if share else whole
    variables = driver.program_variables(cfg, KEY)
    held = 4 if share else 16
    assert variables["layers"][1]["w1"].shape[0] == held
    assert variables["embed"].shape[0] == (512 if share else 2048)
    packed, lens = _packed(np.random.default_rng(length), length, rows, 512)
    out = np.asarray(jax.jit(lambda v, p: lm.forward(v, p, cfg))(variables, packed))
    assert out.shape == (rows, length - 1 + 16 + 2)      # the load over all 16 router outputs
    want = ref.logprobs(cfg, KEY, [packed[b, :length] for b in range(3)])
    gaps = np.concatenate([np.abs(out[b, :lens[b] - 1] - want[b][:lens[b] - 1]) for b in range(3)])
    spread = np.concatenate([want[b][:lens[b] - 1] for b in range(3)]).std()
    assert np.median(gaps) < 0.01 * spread and np.percentile(gaps, 90) < 0.03 * spread, (
        np.median(gaps) / spread, np.percentile(gaps, 90) / spread)
    for b, n in enumerate(lens):  # nothing scored from the last real token on
        assert not out[b, n - 1:length - 1].any()
    # every real token of both expert layers is routed top-4, held here or not
    assert out[:, length - 1:length + 15].sum() == lens.sum() * 2 * 4


def _latent_pair(config, rng, length=64, rows=2, **changed):
    """Latent attention alone, program and reference, on the same input."""
    w = ref.make_layer_weights(config, KEY, 1)
    u = _unit_rows(rng, rows, length, config["hidden_size"])
    lens = jnp.full((rows,), length, jnp.int32)
    got = np.asarray(jax.jit(lambda w, u: lm.latent_attn_mixer(
        w, u, dict(config, **changed), lens).astype(jnp.float32))(_program_layer(w), _bf16(u)))
    fns = ref._programs(config, None)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref.attention(fns, ref.projections(
            w, jnp.asarray(r), config)) @ w["wo"]) for r in u])
    return got, want


def test_latent_attention_alone(config):
    got, want = _latent_pair(config, np.random.default_rng(1))
    assert np.abs(got - want).max() < 0.04 * np.abs(want).max()


def test_latent_attention_without_mscale_squared_in_the_softmax_scale_fails(config):
    plain = dict(config["rope_scaling"], mscale_all_dim=0, mscale=0)
    got, want = _latent_pair(config, np.random.default_rng(1), rope_scaling=plain)
    assert np.abs(got - want).max() > 0.15 * np.abs(want).max()


def test_latent_attention_rotating_the_heads_own_keys_too_fails(config, monkeypatch):
    """The decoupled rotation: only ``q_r`` and the shared ``k_r`` turn with
    the position. Rotating ``q_n`` / ``k_n`` as well is another model."""
    sound = lm.latent_operands

    def rotated(w, u, cfg):
        qn, qr, kn, kr, v = sound(w, u, cfg)
        freqs = la.yarn_frequencies(qn.shape[-1], cfg["rope_theta"], cfg.get("rope_scaling"))

        def turn(x):
            return la.rotate_halves(x.astype(jnp.float32), freqs).astype(x.dtype)

        return turn(qn), qr, turn(kn), kr, v

    monkeypatch.setattr(lm, "latent_operands", rotated)
    got, want = _latent_pair(config, np.random.default_rng(1))
    assert np.abs(got - want).max() > 0.15 * np.abs(want).max()


def test_the_rotated_key_is_one_vector_a_token_for_all_heads(config):
    w = _program_layer(ref.make_layer_weights(config, KEY, 1))
    u = _bf16(_unit_rows(np.random.default_rng(2), 2, 32, 64))
    qn, qr, kn, kr, v = lm.latent_operands(w, u, config)
    assert qn.shape == (2, 4, 32, 16) and qr.shape == (2, 4, 32, 8) and kn.shape == qn.shape
    assert kr.shape == (2, 32, 8) and v.shape == (2, 4, 32, 16)
    # position 0 is not rotated: k_r there is the plain projection, pairs' first members first
    plain = np.asarray(jnp.einsum("blh,hr->blr", u, w["w_dkv"][:, 16:],
                                  preferred_element_type=jnp.float32))[:, 0]
    np.testing.assert_allclose(np.asarray(kr.astype(jnp.float32))[:, 0],
                               np.concatenate([plain[:, 0::2], plain[:, 1::2]], -1),
                               rtol=0.01, atol=0.01)


def _operands(rng, rows, heads, length, dn=16, dr=8, dv=16):
    def draw(*shape):
        return _bf16(rng.standard_normal(shape))

    return (draw(rows, heads, length, dn) * 0.5, draw(rows, heads, length, dr) * 0.5,
            draw(rows, heads, length, dn), draw(rows, length, dr), draw(rows, heads, length, dv))


def _plain_attention(qn, qr, kn, kr, v):
    s = (jnp.einsum("bhqd,bhkd->bhqk", qn, kn, preferred_element_type=jnp.float32)
         + jnp.einsum("bhqd,bkd->bhqk", qr, kr, preferred_element_type=jnp.float32))
    length = s.shape[-1]
    seen = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return np.asarray(jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)))


@pytest.mark.parametrize("q_block", [8, 32, None])
def test_the_xla_form_blockwise_or_whole(q_block):
    ops = _operands(np.random.default_rng(3), 2, 4, 64)
    got = np.asarray(jax.jit(lambda *a: la.attend_xla(*a, q_block=q_block))(*ops)
                     .astype(jnp.float32))
    want = _plain_attention(*ops)
    assert np.abs(got - want).max() < 0.02 * np.abs(want).max()


def test_the_xla_forms_block_follows_from_the_bytes():
    assert la.query_block(1, 128, 16_384) == 64      # 537 MB of scores
    assert la.query_block(8, 128, 2_048) == 64
    assert la.query_block(8, 4, 64) == 64 and la.query_block(1, 128, 1 << 20) == 8


@pytest.mark.parametrize("tiles,heads", [((128, 256), 2), ((256, 128), 4), ((128, 128), 1)])
def test_the_interpreted_kernel_agrees_with_the_xla_form(tiles, heads):
    """Key tiles wider and narrower than query tiles, right padding: a query
    tile that is all padding comes back 0, every real position as XLA's."""
    ops = _operands(np.random.default_rng(4), 2, 4, 512)
    lens = jnp.array([512, 200], jnp.int32)
    got = np.asarray(la.attend_kernel(*ops, lens, tiles=tiles, heads=heads, interpret=True)
                     .astype(jnp.float32))
    want = np.asarray(jax.jit(la.attend_xla)(*ops).astype(jnp.float32))
    assert np.abs(got[0] - want[0]).max() < 0.02 * np.abs(want).max()
    assert np.abs(got[1, :, :200] - want[1, :, :200]).max() < 0.02 * np.abs(want).max()
    dead = -(-200 // tiles[0]) * tiles[0]
    assert not got[1, :, dead:].any() and np.isfinite(got).all()
    qi, ki = la.causal_tiles(512, *tiles)
    assert len(qi) == sum(((i + 1) * tiles[0] - 1) // tiles[1] + 1 for i in range(512 // tiles[0]))
    assert all(t * tiles[1] <= (i + 1) * tiles[0] - 1 for i, t in zip(qi, ki))


def test_yarn_frequencies_at_the_published_keys():
    published = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                 "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                 "type": "yarn"}
    f = la.yarn_frequencies(64, 10000.0, published)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)            # lo = 10: kept
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)       # hi = 23: interpolated
    assert f[0] == 1.0 and f[31] == pytest.approx(10000.0 ** (-62 / 64) / 40)
    r = (16 - 10) / 13
    assert f[16] == pytest.approx(plain[16] * (1 - r) + plain[16] / 40 * r)
    assert la.yarn_mscale(published, "mscale_all_dim") == pytest.approx(
        0.1 * 0.707 * np.log(40) + 1)
    assert la.softmax_scale(192, published) == pytest.approx(192 ** -0.5 * 1.2608 ** 2, rel=1e-4)
    assert la.softmax_scale(192, None) == 192 ** -0.5
    np.testing.assert_array_equal(la.yarn_frequencies(64, 10000.0, None), plain)
    np.testing.assert_allclose(f, ref.yarn_frequencies({
        "qk_rope_head_dim": 64, "rope_theta": 10000.0, "rope_scaling": published}), rtol=1e-12)


def test_router_keeps_groups_then_experts_as_the_reference(config):
    rng = np.random.default_rng(5)
    w = ref.make_layer_weights(config, KEY, 1)
    u = _unit_rows(rng, 1, 256, 64)[0]
    idx, weights = moe.route_group_limited(_bf16(u), w["router"], 4, 4, 2, False, 16.0)
    want_idx, want_w = jax.jit(lambda u: ref.route(w, u, config))(jnp.asarray(u))
    assert np.array_equal(np.sort(np.asarray(idx), 1), np.sort(np.asarray(want_idx), 1))
    np.testing.assert_allclose(np.sort(np.asarray(weights), 1), np.sort(np.asarray(want_w), 1),
                               rtol=1e-5)
    assert (np.asarray(idx) // 4 < 4).all() and all(len(set(r // 4)) <= 2 for r in np.asarray(idx))
    # not renormalised, times 16: the weights are 16 p and sum to under 16
    probs = np.asarray(jax.nn.softmax(moe.router_logits(_bf16(u), w["router"]), -1))
    np.testing.assert_allclose(np.asarray(weights),
                               16 * np.take_along_axis(probs, np.asarray(idx), 1), rtol=1e-5)
    # for some token the plain top-4 reaches into a third group: the limit binds
    plain = np.asarray(jax.lax.top_k(probs, 4)[1])
    binds = [len(set(r // 4)) > 2 for r in plain]
    assert any(binds) and not all(binds)
    differs = (np.sort(plain, 1) != np.sort(np.asarray(idx), 1)).any(1)
    assert np.array_equal(differs, np.array(binds))
    assert moe.router_kind(config) == "group_limited"
    assert moe.router_kind({"scoring_func": "softmax"}) == "softmax"
    assert moe.router_kind({"routed_scaling_factor": 1.0}) == "sigmoid"


def test_shared_experts_alone(config):
    w = ref.make_layer_weights(config, KEY, 2)
    u = _unit_rows(np.random.default_rng(6), 1, 40, 64)[0]
    got = np.asarray(jax.jit(lambda w, u: lm.shared_ffn(w, u).astype(jnp.float32))(
        _program_layer(w), _bf16(u)))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.dense_ffn(w["ws1"], w["ws3"], w["ws2"], jnp.asarray(u)))
    assert w["ws1"].shape == (64, 48) and np.abs(got - want).max() < 0.03 * np.abs(want).max()


def _expert_layer(cfg, w, u):
    """The program's expert layer (routed part as held, plus the shared experts)."""
    held = lm.held_range(cfg, "expert_range")
    return np.asarray(jax.jit(lambda w, u: lm.moe_ffn(w, u, cfg, held)[0].astype(jnp.float32))(
        _program_layer(w), _bf16(u)))


def test_the_shares_routed_parts_and_the_shared_experts_once_add_up_to_the_whole_layer(
        config, whole):
    """The share test: 16 experts in 4 groups, shares [0,4) .. [12,16). Each
    share computes its experts' routed part and the shared experts' whole;
    the four routed parts and the shared experts counted once are the uncut
    reference's layer."""
    u = _unit_rows(np.random.default_rng(7), 1, 96, 64)[0]
    w_all = ref.make_layer_weights(whole, KEY, 1)
    fns = ref._programs(whole, None)
    with jax.default_matmul_precision("highest"):
        idx, weights = fns["route"](w_all, jnp.asarray(u))
        tok, wt = ref.expert_table(np.asarray(idx), np.asarray(weights), 16)
        want = np.asarray(fns["experts"](w_all, jnp.zeros_like(u), jnp.asarray(u),
                                         jnp.asarray(tok), jnp.asarray(wt)))
        shared = np.asarray(ref.dense_ffn(w_all["ws1"], w_all["ws3"], w_all["ws2"],
                                          jnp.asarray(u)))
    parts = []
    for lo in range(0, 16, 4):
        cfg = dict(whole, expert_range=[lo, lo + 4])
        w = ref.make_layer_weights(cfg, KEY, 1)
        # a share's weights are the whole layer's rows
        assert np.array_equal(np.asarray(w["w2"]), np.asarray(w_all["w2"][lo:lo + 4]))
        parts.append(_expert_layer(cfg, w, u))
    tol = 0.03 * np.abs(want).max()
    assert np.abs(sum(parts) - 3 * shared - want).max() < tol
    assert np.abs(_expert_layer(whole, w_all, u) - want).max() < tol
    # a token none of whose experts lie in a share gets the shared experts alone there
    untouched = ~(np.asarray(idx) < 4).any(1)
    assert untouched.any() and np.abs(parts[0][untouched] - shared[untouched]).max() < tol
    assert np.abs(parts[0][~untouched] - shared[~untouched]).max() > 10 * tol


@pytest.mark.parametrize("where", ["every_pair_here", "no_pair_here"])
def test_a_share_is_exact_at_any_imbalance(where):
    """A router that sends the held range every pair (the device loop makes
    all its trips) or none (it makes none) still gives the right answer."""
    rng = np.random.default_rng(8)
    tokens, k, h, f, experts, held = 128, 4, 64, 48, 16, 4
    u = _bf16(rng.standard_normal((tokens, h)))
    w1, w3 = (_bf16(rng.standard_normal((held, h, f)) * h ** -0.5) for _ in range(2))
    w2 = _bf16(rng.standard_normal((held, f, h)) * f ** -0.5)
    idx = np.stack([rng.permutation(held) for _ in range(tokens)]).astype(np.int32)
    idx = idx if where == "every_pair_here" else idx + held
    weights = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    block = moe.held_rows(tokens, k, held, experts)
    assert block == 256 and tokens * k == 2 * block        # two trips, or none
    got, _ = jax.jit(lambda *a: moe.expert_ffn(*a, experts, (0, held)))(
        u, jnp.asarray(idx), weights, w1, w3, w2)
    want = np.zeros((tokens, h), np.float32)
    if where == "every_pair_here":
        uf, a, b, c = (np.asarray(x.astype(jnp.float32)) for x in (u, w1, w3, w2))
        for e in range(held):
            act = uf @ a[e]
            out = np.asarray(_bf16(np.asarray(_bf16(act / (1 + np.exp(-act)) * (uf @ b[e])))
                                   .astype(np.float32) @ c[e]).astype(jnp.float32))
            col = np.asarray(weights)[np.arange(tokens), np.argmax(idx == e, 1)]
            want += col[:, None] * out
    assert np.abs(np.asarray(got.astype(jnp.float32)) - want).max() <= 0.03 * max(
        np.abs(want).max(), 1e-3)


def test_held_rows_follow_from_the_shapes_and_the_share():
    assert moe.held_rows(16_384, 6, 20, 160) == 24_576     # twice a balanced router's 12,288
    assert moe.held_rows(96, 4, 8, 32) == 256 and moe.held_rows(16, 2, 4, 16) == 256
    assert moe.held_rows(2048, 8, 64, 128) == 16_384       # never more than all the pairs


def test_logprobs_are_over_the_slice_and_an_id_outside_it_raises(config):
    variables = driver.program_variables(config, KEY)
    stage = lm.CausalLMScorer(input_col="tokens", output_col="logprob", config=config,
                              variables=variables, buckets=BUCKETS)
    rng = np.random.default_rng(9)
    rows = [rng.integers(0, 512, n).astype(np.int32) for n in (12, 30)]
    col = np.empty(2, dtype=object)
    col[:] = rows
    obs.clear_recent_spans()
    out = stage.transform(DataFrame.from_dict({"tokens": col}))["logprob"]
    assert [len(r) for r in out] == [11, 29] and all((r < 0).all() for r in out)
    # a uniform guess over the 512 ids held is log(1/512); over the model's 2,048 it would be lower
    assert abs(np.mean(np.concatenate(list(out))) + np.log(512)) < 0.5
    root = [s for s in obs.recent_spans() if s.name == "lm.score"][0]
    assert root.attrs["moe_pairs_routed"] == 42 * 2 * 4
    assert 0 < root.attrs["moe_pairs_held"] < root.attrs["moe_pairs_routed"]
    col[1] = np.array([3, 512, 5], np.int32)
    with pytest.raises(ValueError, match=r"holds the ids \[0, 512\)"):
        stage.transform(DataFrame.from_dict({"tokens": col}))


def test_right_padding_leaves_every_real_position_unchanged(config):
    variables = driver.program_variables(config, KEY)
    row = np.random.default_rng(10).integers(0, 512, 13).astype(np.int32)
    fn = jax.jit(lambda v, p: lm.forward(v, p, config))

    def score(length, pad_id):
        packed = np.full((8, length + 1), pad_id, np.int32)
        packed[:, :13] = row
        packed[:, -1] = 13
        return np.asarray(fn(variables, packed))[0, :12]

    base = score(16, 0)
    assert np.array_equal(base, score(16, 7))      # whatever the pad holds
    np.testing.assert_allclose(base, score(32, 0), atol=1e-5)   # however long the bucket


def test_the_layer_keys_of_this_family_read_as_the_other_families(config):
    assert lm.layer_kinds(config) == [("full_attention", "dense")] + [("full_attention", "moe")] * 2
    assert lm.router_width(config) == 16 and lm.held_range(config, "expert_range") == (0, 4)
    assert lm.held_range(config, "vocab_range") == (0, 512)
    assert lm.latent(config) and not lm.selects_keys(config)
    assert lm.router_width({"num_experts": 8}) == 8 and lm.held_range({}, "expert_range") is None


def test_the_experts_kernel_tiles_the_published_widths_and_agrees_with_ragged_dot():
    """5,120 x 1,536: the up-call's two matrices are 31.5 MB, so the rule
    narrows its block; the interpreted kernel against ``ragged_dot`` there at
    a small row count."""
    tm, up, down = grouped_matmul.tiling(24_576, 5120, 1536, 100 << 20)
    assert tm == grouped_matmul.ROW_TILE and 1536 % up == 0 and up < 1536 and 5120 % down == 0
    assert grouped_matmul.tiling(24_576, 5120, 1536, 16 << 20) is not None
    rng = np.random.default_rng(11)
    rows, h, f, groups = 512, 5120, 1536, 3
    x = _bf16(rng.standard_normal((rows, h)))
    w1, w3 = (_bf16(rng.standard_normal((groups, h, f)) * h ** -0.5) for _ in range(2))
    w2 = _bf16(rng.standard_normal((groups, f, h)) * f ** -0.5)
    sizes = jnp.array([200, 0, 190], jnp.int32)            # 122 rows belong to nobody
    got, tiles = grouped_matmul.expert_products(
        x, sizes, w1, w3, w2, tiling=(tm, up, down), call=(("interpret", True),))

    def gmm(a, w):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.bfloat16)

    want = gmm(jax.nn.silu(gmm(x, w1)) * gmm(x, w3), w2)
    got, want = (np.asarray(a.astype(jnp.float32))[:390] for a in (got, want))
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()
    assert [int(t) for t in tiles] == [3, 2]
