"""The experts' grouped-matmul kernel (``ops/grouped_matmul.py``) through the
Pallas interpreter, against ``jax.lax.ragged_dot`` in float32 on the same
bfloat16 operands; its tile rule; its count of visits; and the sparse expert
layer and the scorer on the kernel's path against XLA's."""

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
from mmlspark_tpu.ops import grouped_matmul as gm  # noqa: E402
from mmlspark_tpu.ops import histogram, moe  # noqa: E402

TM, K, N = 16, 64, 256
# (group sizes, rows): what a group's edge can do to a tile of 16 rows
CASES = {
    "uneven_groups": ([10, 23, 3, 28], 64),
    "an_empty_group": ([20, 0, 12, 0, 32], 64),
    "a_group_smaller_than_a_tile": ([30, 5, 4, 25], 64),
    "a_group_ends_on_a_tiles_edge": ([16, 32, 16], 64),
    "rows_past_the_last_group": ([10, 0, 3, 35], 96),
    "one_group_holds_everything": ([64], 64),
    "no_group_holds_a_row": ([0, 0], 32),
}


def _operands(sizes, rows, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, K)), jnp.bfloat16)
    w = [jnp.asarray(rng.standard_normal((len(sizes), K, N)) / 8, jnp.bfloat16) for _ in range(2)]
    return x, w[0], w[1], jnp.asarray(sizes, jnp.int32)


def _ragged32(x, w, sizes):
    return np.asarray(jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32))


def _close(got, want, held):
    """Within one bfloat16 rounding of the float32 result, on the rows a
    group holds (the others are the caller's to mask)."""
    if held:
        got, want = np.asarray(got.astype(jnp.float32))[:held], want[:held]
        assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()


@pytest.mark.parametrize("tn", [128, 256], ids=["two_column_blocks", "whole_width"])
@pytest.mark.parametrize("case", list(CASES))
def test_grouped_matmul_is_ragged_dot(case, tn):
    sizes, rows = CASES[case]
    x, w, _w3, s = _operands(sizes, rows)
    visits = gm.group_visits(s, rows, TM)
    got = gm.grouped_matmul(x, w, visits, tn, interpret=True)
    assert got.shape == (rows, N) and got.dtype == jnp.bfloat16
    _close(got, _ragged32(x, w, s), sum(sizes))


@pytest.mark.parametrize("case", list(CASES))
def test_gated_up_is_silu_of_one_product_times_the_other(case):
    sizes, rows = CASES[case]
    x, w1, w3, s = _operands(sizes, rows, seed=1)
    got = gm.gated_up(x, w1, w3, gm.group_visits(s, rows, TM), 128, interpret=True)
    a, b = _ragged32(x, w1, s), _ragged32(x, w3, s)
    _close(got, np.asarray(jax.nn.silu(a) * b), sum(sizes))


@pytest.mark.parametrize("tm", [8, 16, 32])
@pytest.mark.parametrize("case", list(CASES))
def test_visits_are_what_the_sizes_give(case, tm):
    sizes, rows = CASES[case]
    visits = gm.group_visits(jnp.asarray(sizes, jnp.int32), rows, tm)
    ends = np.cumsum(sizes)
    want = [(g, t) for g, (lo, hi) in enumerate(zip(ends - sizes, ends))
            for t in range(lo // tm, -(-hi // tm)) if hi > lo]
    count = int(visits.count[0])
    assert count == len(want) <= rows // tm + len(sizes) - 1 == visits.group.shape[0]
    assert list(zip(np.asarray(visits.group)[:count], np.asarray(visits.tile)[:count])) == want
    # the idle visits repeat the last real one: they fetch nothing new
    assert len({(int(g), int(t)) for g, t in zip(visits.group[max(count - 1, 0):],
                                                visits.tile[max(count - 1, 0):])}) == 1
    assert int(visits.tile.max()) < rows // tm and int(visits.tile.min()) >= 0
    visited, aligned = (int(n) for n in visits.tiles())
    assert visited == len(want) and aligned == -(-int(ends[-1]) // tm)
    assert aligned <= visited <= aligned + max(len(sizes) - 1, 0)
    assert list(np.asarray(visits.offsets)) == [0, *ends]


def test_rows_that_are_no_multiple_of_the_tile_are_refused():
    with pytest.raises(ValueError, match="no multiple of the row tile"):
        gm.group_visits(jnp.asarray([3, 4], jnp.int32), 20, 16)
    x, w, _w3, s = _operands([64], 64)
    with pytest.raises(ValueError, match="no multiple of the column tile"):
        gm.grouped_matmul(x, w, gm.group_visits(s, 64, TM), 96, interpret=True)


# -- the tile rule ------------------------------------------------------------------

V5E_VMEM = 96 << 20


@pytest.mark.parametrize("rows,h,f", [(131_072, 2048, 1792), (262_144, 2048, 768)],
                         ids=["lfm2_8b_a1b", "keye_vl2_30b_a3b"])
def test_tile_rule_at_the_published_widths(rows, h, f):
    tm, up, down = gm.tiling(rows, h, f, V5E_VMEM)
    assert rows % tm == 0 and tm == gm.ROW_TILE
    for tn, n in ((up, f), (down, h)):
        assert tn % 128 == 0 and n % tn == 0
    # the whole width fits the v5e's ceiling: a row tile is read once a call
    assert (up, down) == (f, h)
    blocks = 2 * 2 * (tm * h + 2 * h * up + tm * up) + 3 * 4 * tm * up
    assert blocks <= V5E_VMEM * 2 // 3
    # a device with Mosaic's default 16 MB still gets blocks that divide the widths
    tm, up, down = gm.tiling(rows, h, f, 16 << 20)
    assert (up, down) < (f, h) and f % up == 0 and h % down == 0 and up % 128 == 0
    assert 2 * 2 * (tm * h + 2 * h * up + tm * up) + 3 * 4 * tm * up <= (16 << 20) * 2 // 3


@pytest.mark.parametrize("rows,h,f", [(384, 64, 48), (512, 128, 48), (512, 64, 128), (200, 128, 128),
                                      (512, 128, 128)],
                         ids=["test_size", "f_48", "h_64", "rows_200", "nothing_fits"])
def test_tile_rule_falls_back_where_the_kernel_does_not_apply(rows, h, f):
    assert gm.tiling(rows, h, f, 1 << 16 if (rows, h, f) == (512, 128, 128) else V5E_VMEM) is None


# -- the layer and the scorer on the kernel's path -----------------------------------

def _layer(rng, tokens, h, f, experts):
    u = jnp.asarray(rng.standard_normal((tokens, h)), jnp.bfloat16)
    w1, w3 = (jnp.asarray(rng.standard_normal((experts, h, f)) * h ** -0.5, jnp.bfloat16)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((experts, f, h)) * f ** -0.5, jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((h, experts)), jnp.float32)
    return u, router, w1, w3, w2


@pytest.mark.parametrize("held", [(0, 8), (2, 5)], ids=["whole_range", "experts_2_to_5"])
def test_expert_ffn_on_the_kernels_path_is_the_ragged_dot_path(monkeypatch, held):
    rng = np.random.default_rng(3)
    u, router, w1, w3, w2 = _layer(rng, 256, 128, 128, 8)
    idx, weights = moe.route_softmax(u, router, 2)
    lo, hi = held

    def layer():
        out, tiles = jax.jit(lambda a, b, c: moe.expert_ffn(u, idx, weights, a, b, c, 8, held))(
            w1[lo:hi], w3[lo:hi], w2[lo:hi])
        return np.asarray(out.astype(jnp.float32)), [int(n) for n in tiles]

    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "0")
    want, no_tiles = layer()
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    got, tiles = layer()
    assert no_tiles == [0, 0]
    # bfloat16 rounding: the kernel's gate reads float32 products, XLA's bfloat16 ones
    assert np.abs(got - want).max() <= 2.0 ** -6 * np.abs(want).max()
    routed = np.sort(np.asarray(idx).reshape(-1))
    sizes = [int((routed == e).sum()) for e in range(lo, hi)]
    ends = np.cumsum(sizes)
    visited = sum(-(-e // gm.ROW_TILE) - s // gm.ROW_TILE
                  for s, e in zip(ends - sizes, ends) if e > s)
    assert tiles == [visited, -(-int(ends[-1]) // gm.ROW_TILE)]
    untouched = ~((np.asarray(idx) >= lo) & (np.asarray(idx) < hi)).any(1)
    assert not got[untouched].any()


def test_a_width_the_rule_cannot_tile_keeps_ragged_dot_on_a_tpu_too(monkeypatch):
    rng = np.random.default_rng(4)
    u, router, w1, w3, w2 = _layer(rng, 128, 64, 48, 4)
    idx, weights = moe.route_softmax(u, router, 2)
    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    text = str(jax.make_jaxpr(lambda: moe.expert_ffn(u, idx, weights, w1, w3, w2, 4))())
    assert text.count("ragged_dot_general[") == 3 and "pallas_call" not in text


@pytest.fixture()
def scorer():
    """Two expert layers of 4 experts top-2 at widths the rule tiles (128),
    one bucket of 4 rows x 64 tokens = 512 routed rows a layer."""
    from chipbench.drivers import lm_score_stream as driver

    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")) as f:
        from chipbench import spec
        config = driver.model_config(spec.sized(json.load(f), True))
    config.update(hidden_size=128, moe_intermediate_size=128, intermediate_size=128,
                  num_experts=4, num_hidden_layers=2, num_dense_layers=0,
                  layer_types=["conv", "conv"], vocab_size=256)
    variables = driver.program_variables(config, jax.random.PRNGKey(5), lm.layer_kinds(config))
    return config, variables


def test_the_scorer_carries_the_kernels_tiles_out_of_the_program(monkeypatch, scorer):
    config, variables = scorer
    rng = np.random.default_rng(6)
    rows = [rng.integers(0, 256, n).astype(np.int32) for n in (64, 40, 17, 9, 33)]
    col = np.empty(len(rows), dtype=object)
    col[:] = rows

    def score(pallas):
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS", pallas)
        before = {k: _counter(k) for k in ("visited", "aligned")}
        stage = lm.CausalLMScorer(input_col="tokens", output_col="logprob", config=config,
                                  variables=variables, buckets=[[64, 4]])
        out = stage.transform(DataFrame.from_dict({"tokens": col}))["logprob"]
        span = [s for s in obs.recent_spans() if s.name == "lm.score"][-1]
        added = {k: _counter(k) - before[k] for k in before}
        return out, span.attrs, added

    want, attrs, added = score("0")
    assert attrs["gmm_tiles_visited"] == attrs["gmm_tiles_aligned"] == 0
    assert added == {"visited": 0, "aligned": 0}
    got, attrs, added = score("1")
    # two batches of 4 x 64 tokens, top-2: 512 routed rows = 2 row tiles a layer,
    # and at most 3 group edges inside them
    assert attrs["gmm_tiles_aligned"] == 2 * 2 * 2
    assert 8 <= attrs["gmm_tiles_visited"] <= 8 + 2 * 2 * 3
    assert added == {"visited": attrs["gmm_tiles_visited"], "aligned": 8}
    for g, w, row in zip(got, want, rows):
        assert g.shape == (len(row) - 1,)
        np.testing.assert_allclose(g, w, atol=0.02)


def _counter(kind):
    fam = obs.REGISTRY.snapshot().get("mmlspark_moe_gmm_tiles_total") or {}
    return sum(v for labels, v in fam.get("samples", []) if labels.get("kind") == kind)


def test_use_pallas_is_what_chooses(monkeypatch):
    """No argument and no variable of the layer's own: the histogram kernels'
    rule, which follows the device (and lets a CPU process stand in)."""
    monkeypatch.delenv("MMLSPARK_TPU_PALLAS", raising=False)
    assert histogram.use_pallas() is (jax.devices()[0].platform == "tpu")
    rng = np.random.default_rng(7)
    u, router, w1, w3, w2 = _layer(rng, 256, 128, 128, 4)
    idx, weights = moe.route_softmax(u, router, 2)
    text = str(jax.make_jaxpr(lambda: moe.expert_ffn(u, idx, weights, w1, w3, w2, 4))())
    assert (text.count("pallas_call["), text.count("ragged_dot_general[")) == (
        (2, 0) if histogram.use_pallas() else (0, 3))
