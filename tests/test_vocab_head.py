"""The head's kernel (``ops/vocab_head.py``) through the Pallas interpreter
against the XLA form of ``causal_lm.head_logprobs`` on the same seeded
bfloat16 operands; its tiling rule; its count of token tiles; and the scorer
on the kernel's path against XLA's."""

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

from mmlspark_tpu import obs  # noqa: E402
from mmlspark_tpu.core.dataframe import DataFrame  # noqa: E402
from mmlspark_tpu.models import causal_lm as lm  # noqa: E402
from mmlspark_tpu.ops import histogram, vocab_head as vh  # noqa: E402

H, TT, TV = 128, 128, 256
VMEM = 96 << 20


def _operands(tokens, vocab, seed=0, h=H):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((tokens, h)), jnp.bfloat16)
    head = jnp.asarray(rng.standard_normal((vocab, h)) * 0.25, jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, vocab, tokens), jnp.int32)
    return head, u, targets


def _xla(head, u, targets):
    """The form the kernel is tested against: the loop a CPU runs."""
    assert not histogram.use_pallas()
    return np.asarray(lm.head_logprobs(head, u, targets, 128))


def _work(lengths, length):
    return (np.arange(length)[None, :] < np.asarray(lengths)[:, None] - 1).reshape(-1)


# (vocabulary, rows' real lengths of rows of 128 positions, where the targets point)
CASES = {
    # 151,936 = 1,187 x 128: whole lanes, and no tile of 256 divides them
    "a_vocabulary_no_tile_divides": (128 * 7, [128, 128, 128], None),
    "a_vocabulary_of_no_whole_lanes": (128 * 5 + 37, [128, 128], None),
    "a_vocabulary_smaller_than_a_tile": (128 + 64, [128, 128], None),
    "a_vocabulary_the_tile_divides": (1024, [128, 128], None),
    "every_target_in_the_partial_last_tile": (128 * 7, [128, 128], (128 * 6, 128 * 7)),
    "every_target_the_last_id": (128 * 5 + 37, [128, 128], (128 * 5 + 36, 128 * 5 + 37)),
    "padding_covers_whole_token_tiles": (128 * 7, [128, 0, 60, 0], None),
    "one_real_row": (128 * 7, [0, 0, 97, 0], None),
    "a_row_of_two_tokens": (128 * 7, [2, 128], None),
    "a_row_of_one_token_has_no_work": (128 * 7, [128, 1], None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_xla_form(case):
    vocab, lengths, span = CASES[case]
    head, u, targets = _operands(128 * len(lengths), vocab, seed=len(case))
    if span:
        targets = jnp.asarray(np.random.default_rng(1).integers(*span, targets.shape), jnp.int32)
    work = _work(lengths, 128)
    want = _xla(head, u, targets)
    got = np.asarray(vh.head_kernel(head, u, targets, jnp.asarray(work), tiles=(TT, TV),
                                    interpret=True))
    # float32 rounding of the online form, on logits of a few units
    np.testing.assert_allclose(got[work], want[work], atol=2e-5, rtol=0)
    live = np.repeat([n > 1 for n in lengths], 128)
    assert not got[~live].any() and np.isfinite(got).all()
    assert (got[work] < 0).all()


def test_no_mask_means_every_position_has_work():
    head, u, targets = _operands(256, 128 * 7, seed=11)
    got = np.asarray(vh.head_kernel(head, u, targets, tiles=(TT, TV), interpret=True))
    np.testing.assert_allclose(got, _xla(head, u, targets), atol=2e-5, rtol=0)


@pytest.mark.parametrize("tiles", [(128, 128), (256, 384), (512, 1024)],
                         ids=["small", "uneven", "one_step"])
def test_other_tiles_give_the_same(tiles):
    head, u, targets = _operands(512, 128 * 7 + 5, seed=12)
    work = _work([300, 90], 256)
    got = np.asarray(vh.head_kernel(head, u, targets, jnp.asarray(work), tiles=tiles,
                                    interpret=True))
    np.testing.assert_allclose(got[work], _xla(head, u, targets)[work], atol=2e-5, rtol=0)


def test_a_held_slice_scores_ids_offset_by_its_start():
    """What ``forward`` hands the head of a share: the slice's rows and the
    ids less ``vocab_range``'s start; the log-probabilities are over the slice."""
    lo, hi = 256, 256 + 128 * 3
    full, u, _ = _operands(256, 1024, seed=13)
    ids = jnp.asarray(np.random.default_rng(2).integers(lo, hi, 256), jnp.int32)
    got = np.asarray(vh.head_kernel(full[lo:hi], u, ids - lo, tiles=(TT, TV), interpret=True))
    logits = np.asarray(jnp.einsum("th,vh->tv", u, full, preferred_element_type=jnp.float32))
    held = logits[:, lo:hi]
    want = held[np.arange(256), np.asarray(ids) - lo] - np.log(np.exp(held).sum(-1))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_tokens_the_tile_does_not_divide_are_refused():
    head, u, targets = _operands(192, 256)
    with pytest.raises(ValueError, match="no multiple of the token tile"):
        vh.head_kernel(head, u, targets, tiles=(128, 256), interpret=True)


# -- the rule ----------------------------------------------------------------------

@pytest.mark.parametrize("tokens,h,vocab,want", [
    (32_768, 2048, 65_536, "even"),       # lfm2_8b_a1b: tied, 65,536 ids
    (32_768, 2048, 151_936, "partial"),   # keye_vl2_30b_a3b: 1,187 x 128, 1,187 prime
    (16_384, 5120, 12_800, "even"),       # deepseek_v2: the held slice, 100 x 128
], ids=["lfm2_8b_a1b", "keye_vl2_30b_a3b", "deepseek_v2"])
def test_the_rule_tiles_the_three_cells(tokens, h, vocab, want):
    tt, tv = vh.tiling(tokens, h, vocab, VMEM)
    # a token tile divides every bucket's row length or holds whole rows of it
    assert tt in (512, 1024) and all(length % tt == 0 or tt % length == 0
                                     for length in (512, 1024, 2048, 4096, 8192, 16_384, 32_768))
    assert tv % 128 == 0 and vh.VOCAB_TILE // 2 <= tv <= vh.VOCAB_TILE
    assert (vocab % tv == 0) == (want == "even")
    # two buffers of both operands' blocks and the logits' temporaries fit, and no
    # tile as large as the ones that ran a third slower on the chip is chosen
    assert vh._fits(tt, tv, h, VMEM) and tt * tv <= 1 << 20


@pytest.mark.parametrize("tokens,h,vocab", [
    (512, 64, 1024),         # a width of no whole lanes
    (512, 192, 1024),
    (96, 128, 1024),         # tokens no tile of 128 divides
    (512, 128, 100),         # fewer ids than a tile's lanes
    (32_768, 1 << 17, 65_536),   # a width whose blocks fit at no tile
], ids=["h_64", "h_192", "tokens_96", "vocab_100", "h_too_wide"])
def test_the_rule_refuses(tokens, h, vocab):
    assert vh.tiling(tokens, h, vocab, VMEM) is None


def test_the_rule_narrows_tiles_to_the_budget():
    wide = vh.tiling(32_768, 2048, 65_536, VMEM)
    tight = vh.tiling(32_768, 2048, 65_536, VMEM // 8)
    assert tight is not None and tight[0] * tight[1] < wide[0] * wide[1]
    assert tight[0] >= 128 and tight[1] >= 128 and 32_768 % tight[0] == 0


def test_small_shapes_get_the_tiles_that_divide_them():
    assert vh.tiling(256, 128, 384, VMEM) == (256, 384)
    assert vh.tiling(384, 128, 1000, VMEM) == (128, 896)


def test_the_host_counts_the_tiles_the_device_marks():
    lengths = np.array([64, 40, 17, 9, 33, 0, 1, 2])
    for length, tt in ((64, 128), (64, 64), (64, 32), (64, 256)):
        work = _work(lengths, length)
        live = np.asarray(vh.tiles_with_work(jnp.asarray(work), tt))
        assert vh.count_tiles(lengths, length, tt) == (int(live.sum()), int((~live).sum()))
    # two rows a tile: [64, 40] [17, 9] [33, 0] [1, 2] -> a row of one token has no work
    assert vh.count_tiles(lengths, 64, 128) == (4, 0)
    assert vh.count_tiles(lengths, 64, 64) == (6, 2)
    # the row of 33 tokens has its work in one tile of 32: its last token has no next
    assert vh.count_tiles(lengths, 64, 32) == (2 + 2 + 1 + 1 + 1 + 0 + 0 + 1, 8)


# -- head_logprobs and the scorer ------------------------------------------------------

def test_a_width_the_rule_refuses_keeps_the_xla_form_on_a_tpu_too(monkeypatch):
    head, u, targets = _operands(256, 512, seed=14, h=64)
    want = _xla(head, u, targets)
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    assert vh.plan(256, 64, 512) is None
    text = str(jax.make_jaxpr(lambda: lm.head_logprobs(head, u, targets, 128))())
    assert "pallas_call" not in text
    assert np.array_equal(np.asarray(lm.head_logprobs(head, u, targets, 128)), want)


def test_use_pallas_is_what_chooses(monkeypatch):
    """No argument and no variable of the head's own: the histogram kernels'
    rule, which follows the device (and lets a CPU process stand in)."""
    head, u, targets = _operands(256, 512, seed=15)
    monkeypatch.delenv("MMLSPARK_TPU_PALLAS", raising=False)
    assert histogram.use_pallas() is (jax.devices()[0].platform == "tpu")
    assert (vh.plan(256, H, 512) is not None) == histogram.use_pallas()
    want = _xla(head, u, targets)
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    assert vh.plan(256, H, 512) == vh.tiling(256, H, 512, histogram._hist_vmem_mb() << 20)
    text = str(jax.make_jaxpr(lambda: lm.head_logprobs(head, u, targets, 128))())
    assert text.count("pallas_call[") == 1 and "head_logprobs" in text and "logsumexp" not in text
    work = jnp.arange(256) < 100
    got = np.asarray(lm.head_logprobs(head, u, targets, 128, work))
    np.testing.assert_allclose(got[:100], want[:100], atol=2e-5, rtol=0)


@pytest.fixture()
def scorer():
    """Two layers at a width the rule tiles (128) and 704 ids; one bucket of
    4 rows x 64 tokens = 256 positions a batch."""
    from chipbench import spec
    from chipbench.drivers import lm_score_stream as driver

    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")) as f:
        config = driver.model_config(spec.sized(json.load(f), True))
    config.update(hidden_size=128, moe_intermediate_size=128, intermediate_size=128,
                  num_experts=4, num_hidden_layers=2, num_dense_layers=1,
                  layer_types=["conv", "conv"], vocab_size=704)
    variables = driver.program_variables(config, jax.random.PRNGKey(5), lm.layer_kinds(config))
    return config, variables


def _counter(kind):
    fam = obs.REGISTRY.snapshot().get("mmlspark_lm_head_tiles_total") or {}
    return sum(v for labels, v in fam.get("samples", []) if labels.get("kind") == kind)


def test_the_scorer_counts_the_heads_tiles(monkeypatch, scorer):
    config, variables = scorer
    # tiles of 128 tokens x 256 ids: two rows a token tile, a partial third vocabulary tile
    monkeypatch.setattr(vh, "TOKEN_TILE", 128)
    monkeypatch.setattr(vh, "VOCAB_TILE", 256)
    rng = np.random.default_rng(6)
    rows = [rng.integers(0, 704, n).astype(np.int32) for n in (64, 40, 17, 9, 33)]
    col = np.empty(len(rows), dtype=object)
    col[:] = rows

    def score(pallas):
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS", pallas)
        before = {k: _counter(k) for k in ("visited", "skipped")}
        stage = lm.CausalLMScorer(input_col="tokens", output_col="logprob", config=config,
                                  variables=variables, buckets=[[64, 4]])
        out = stage.transform(DataFrame.from_dict({"tokens": col}))["logprob"]
        span = [s for s in obs.recent_spans() if s.name == "lm.score"][-1]
        return out, span.attrs, {k: _counter(k) - before[k] for k in before}

    want, attrs, added = score("0")
    assert attrs["head_tiles"] == attrs["head_tiles_skipped"] == 0
    assert added == {"visited": 0, "skipped": 0}
    got, attrs, added = score("1")
    assert vh.plan(256, 128, 704) == (128, 256)
    # two batches of two token tiles: [64, 40] [17, 9] and [33, batch padding] [padding]
    assert (attrs["head_tiles"], attrs["head_tiles_skipped"]) == (3, 1)
    assert added == {"visited": 3, "skipped": 1}
    for g, w, row in zip(got, want, rows):
        assert g.shape == (len(row) - 1,)
        np.testing.assert_allclose(g, w, atol=0.02)
