"""Scan-fused GBDT round loop + host histogram/grower lowerings.

Covers the PR-8 rebuild: fused-vs-legacy loop equivalence (chunked
``lax.scan`` dispatches must never change the trained model), chunk
boundary checkpoint/resume bit-identity through explicit chunk sizes,
the host bincount lowering vs the XLA scatter, the whole-tree host
depthwise grower vs the XLA grower, the feature-parallel worker pool
(pooled == serial bit-identity, degrade-to-serial), the
O(rounds) -> O(rounds/K) dispatch-count claim, and device AUC.

The suite-wide conftest forces 8 host devices, so ``shard=True`` runs
exercise the sharded scatter+psum path and ``shard=False`` runs the host
lowerings — both matter here and are chosen per test.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from mmlspark_tpu.models.gbdt.train import TrainConfig, train


def _toy(n=600, d=8, seed=3):
    r = np.random.default_rng(seed)
    x = r.normal(size=(n, d)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.1 * r.normal(size=n) > 0)
    return x, y.astype(np.float64)


def _fit(cfg, x, y, **kw):
    return train(x, y, cfg, **kw).to_model_string()


# -- fused vs legacy loop ----------------------------------------------------


@pytest.mark.parametrize(
    "over",
    [
        {},                                             # plain gbdt
        {"growth_policy": "depthwise"},
        {"boosting_type": "goss"},
        {"boosting_type": "rf"},
        {"bagging_fraction": 0.7, "bagging_freq": 2,
         "feature_fraction": 0.6},
    ],
    ids=["gbdt", "depthwise", "goss", "rf", "sampling"],
)
def test_fused_matches_legacy_loop(over):
    """fused_rounds=1 (one dispatch per round, the legacy loop) and the
    chunked scan must produce the identical booster — chunk size is a
    dispatch-count knob, never a semantics knob."""
    x, y = _toy()
    cfg = TrainConfig(
        objective="binary", num_iterations=6, num_leaves=7, seed=9, **over
    )
    fused = _fit(cfg, x, y)
    legacy = _fit(cfg, x, y, fused_rounds=1)
    chunk2 = _fit(cfg, x, y, fused_rounds=2)
    assert fused == legacy
    assert fused == chunk2


def test_fused_matches_legacy_digits():
    """Same-trees equivalence on the real digits fixture (multiclass:
    k trees per round ride the packed record buffer together)."""
    from sklearn.datasets import load_digits

    digits = load_digits()
    x = digits.data[:600].astype(np.float32)
    y = digits.target[:600].astype(np.float64)
    cfg = TrainConfig(
        objective="multiclass", num_class=10, num_iterations=3,
        num_leaves=7, seed=0,
    )
    assert _fit(cfg, x, y) == _fit(cfg, x, y, fused_rounds=1)


def test_fused_matches_legacy_with_early_stop():
    x, y = _toy(n=800)
    vm = np.zeros(len(y), bool)
    vm[::4] = True
    cfg = TrainConfig(
        objective="binary", num_iterations=25, num_leaves=7, seed=2,
        early_stopping_round=3,
    )
    b_fast = train(x, y, cfg, valid_mask=vm)
    b_slow = train(x, y, cfg, valid_mask=vm, fused_rounds=1)
    assert b_fast.best_iteration == b_slow.best_iteration
    assert b_fast.to_model_string() == b_slow.to_model_string()


def test_fused_matches_legacy_unsharded_host_path():
    """Same equivalence through the single-device host lowering (the CPU
    fast path the bench measures)."""
    x, y = _toy()
    for policy in ("lossguide", "depthwise"):
        cfg = TrainConfig(
            objective="binary", num_iterations=5, num_leaves=7, seed=4,
            growth_policy=policy,
        )
        fused = _fit(cfg, x, y, shard=False)
        legacy = _fit(cfg, x, y, shard=False, fused_rounds=1)
        assert fused == legacy, policy


# -- chunk-boundary checkpointing -------------------------------------------


def test_checkpoint_at_chunk_boundary_resume_bit_identical(tmp_path):
    """Chunk boundaries are the checkpoint boundaries: a fit checkpointed
    with an explicit chunk size, resumed from a mid-run snapshot, must
    reproduce the uninterrupted booster bit-for-bit (extends PR 1's
    guarantee through the fused rewrite)."""
    x, y = _toy()
    cfg = TrainConfig(
        objective="binary", num_iterations=9, num_leaves=7, seed=6,
        bagging_fraction=0.8, bagging_freq=2,
    )
    ref = _fit(cfg, x, y, fused_rounds=3)
    ck = str(tmp_path / "ck")
    # stop after 6 rounds (2 chunks of 3) by training a truncated run in
    # the same dir, then resume the full run from its checkpoint
    cfg_half = TrainConfig(
        objective="binary", num_iterations=9, num_leaves=7, seed=6,
        bagging_fraction=0.8, bagging_freq=2,
    )
    from mmlspark_tpu.core import faults

    class Preempted(RuntimeError):
        pass

    plan = faults.FaultPlan().on("gbdt.round", at=(6,), error=Preempted)
    with plan.armed():
        with pytest.raises(Preempted):
            train(
                x, y, cfg_half, checkpoint_dir=ck, checkpoint_every=3,
                fused_rounds=3,
            )
    resumed = train(
        x, y, cfg, checkpoint_dir=ck, resume_from=ck, checkpoint_every=3,
        fused_rounds=3,
    )
    assert resumed.to_model_string() == ref


# -- host lowering vs XLA scatter -------------------------------------------


def test_host_plane_histogram_matches_scatter():
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.histogram import (
        _plane_histogram_host,
        _plane_histogram_scatter,
        _multi_plane_host,
        _multi_plane_scatter,
    )

    rng = np.random.default_rng(0)
    n, d, B, S = 700, 5, 32, 6
    bins = jnp.asarray(rng.integers(-2, B + 2, (n, d)), jnp.int32)  # OOB too
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    mask = jnp.asarray(
        (rng.random(n) < 0.3).astype(np.float32) * 1.7   # fractional weights
    )
    slot = jnp.asarray(rng.integers(-1, S + 1, n), jnp.int32)
    h = np.asarray(_plane_histogram_host(bins, stats, mask, B))
    s = np.asarray(
        jax.jit(lambda b, st, m: _plane_histogram_scatter(
            b, st * m[:, None], B
        ))(bins, stats, mask)
    )
    np.testing.assert_allclose(h, s, atol=2e-4, rtol=1e-5)
    hm = np.asarray(_multi_plane_host(bins, stats, slot, S, B))
    sm = np.asarray(
        jax.jit(lambda b, st, sl: _multi_plane_scatter(b, st, sl, S, B))(
            bins, stats, slot
        )
    )
    np.testing.assert_allclose(hm, sm, atol=2e-4, rtol=1e-5)


def test_leaf_stat_sums_host_matches_scatter(monkeypatch):
    import jax.numpy as jnp

    from mmlspark_tpu.ops import histogram as H

    rng = np.random.default_rng(1)
    n, L = 500, 9
    leaf = jnp.asarray(rng.integers(0, L, n), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    monkeypatch.setenv("MMLSPARK_TPU_HIST_HOST", "1")
    host = np.asarray(H.leaf_stat_sums(leaf, stats, L))
    monkeypatch.setenv("MMLSPARK_TPU_HIST_HOST", "0")
    scat = np.asarray(H.leaf_stat_sums(leaf, stats, L))
    np.testing.assert_allclose(host, scat, atol=2e-4, rtol=1e-5)


# -- host depthwise grower vs XLA grower ------------------------------------


def _grown(bins, g, h, w, monkeypatch, host: bool, cat=None, **over):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.treegrow import grow_tree_depthwise

    monkeypatch.setenv("MMLSPARK_TPU_HIST_HOST", "1" if host else "0")
    kw = dict(
        num_leaves=15, lambda_l2=1.0, min_gain=0.0, learning_rate=0.1,
        feature_mask=jnp.ones(bins.shape[1], jnp.float32),
        max_depth=-1, min_data_in_leaf=10, lambda_l1=0.1,
        min_sum_hessian=1e-3, num_bins=64,
    )
    kw.update(over)
    out = grow_tree_depthwise(bins, g, h, w, categorical_mask=cat, **kw)
    return jax.tree_util.tree_map(np.asarray, out)


def _tree_fields_equal(a, b):
    for f in a._fields:
        if f == "hist_rows":
            # how the masked grower's histogram calls went, not the tree:
            # only that grower counts them
            continue
        av, bv = getattr(a, f), getattr(b, f)
        if av.dtype.kind == "f":
            np.testing.assert_allclose(av, bv, atol=2e-4, rtol=2e-4,
                                       err_msg=f)
        else:
            np.testing.assert_array_equal(av, bv, err_msg=f)


def test_host_depthwise_grower_matches_xla(monkeypatch):
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    n, d = 3000, 6
    x = rng.normal(size=(n, d)).astype(np.float32)
    from mmlspark_tpu.models.gbdt.binning import BinMapper

    mapper = BinMapper.fit(x, max_bin=63, seed=5)
    bins = jnp.asarray(mapper.transform(x))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((np.abs(rng.normal(size=n)) + 0.1).astype(np.float32))
    w = jnp.asarray((rng.random(n) < 0.85).astype(np.float32))
    a = _grown(bins, g, h, w, monkeypatch, host=True)
    b = _grown(bins, g, h, w, monkeypatch, host=False)
    _tree_fields_equal(a, b)


def test_host_depthwise_grower_matches_xla_categorical(monkeypatch):
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    n, d = 2500, 5
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[:, 2] = rng.integers(0, 7, n)          # categorical column
    from mmlspark_tpu.models.gbdt.binning import BinMapper

    mapper = BinMapper.fit(
        x, max_bin=63, seed=8, categorical_features=(2,)
    )
    bins = jnp.asarray(mapper.transform(x))
    cat = jnp.asarray(np.arange(d) == 2)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((np.abs(rng.normal(size=n)) + 0.1).astype(np.float32))
    w = jnp.ones(n, jnp.float32)
    a = _grown(bins, g, h, w, monkeypatch, host=True, cat=cat)
    b = _grown(bins, g, h, w, monkeypatch, host=False, cat=cat)
    _tree_fields_equal(a, b)


def test_host_lossguide_grower_matches_xla(monkeypatch):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.binning import BinMapper
    from mmlspark_tpu.models.gbdt.treegrow import grow_tree

    rng = np.random.default_rng(3)
    n, d = 4000, 7
    x = rng.normal(size=(n, d)).astype(np.float32)
    mapper = BinMapper.fit(x, max_bin=63, seed=3)
    bins = jnp.asarray(mapper.transform(x))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((np.abs(rng.normal(size=n)) + 0.1).astype(np.float32))
    w = jnp.asarray((rng.random(n) < 0.8).astype(np.float32))
    kw = dict(
        num_leaves=15, lambda_l2=1.0, min_gain=0.0, learning_rate=0.1,
        feature_mask=jnp.ones(d, jnp.float32), max_depth=4,
        min_data_in_leaf=20, lambda_l1=0.1, min_sum_hessian=1e-3,
        num_bins=64,
    )
    monkeypatch.setenv("MMLSPARK_TPU_HIST_HOST", "1")
    a = jax.tree_util.tree_map(np.asarray, grow_tree(bins, g, h, w, **kw))
    monkeypatch.setenv("MMLSPARK_TPU_HIST_HOST", "0")
    b = jax.tree_util.tree_map(np.asarray, grow_tree(bins, g, h, w, **kw))
    _tree_fields_equal(a, b)


# -- worker pool -------------------------------------------------------------


def test_pooled_grower_bit_identical_to_serial(monkeypatch):
    """The feature-parallel pool must be invisible: force the pool on
    (tiny threshold) and off, compare boosters bit-for-bit."""
    from mmlspark_tpu.ops import histpool

    x, y = _toy(n=900)
    cfg = TrainConfig(
        objective="binary", num_iterations=4, num_leaves=15, seed=1,
        growth_policy="depthwise",
    )
    monkeypatch.setattr(histpool, "MIN_POOL_ITEMS", 1)
    pooled = _fit(cfg, x, y, shard=False)
    pool_obj = histpool._POOL
    monkeypatch.setattr(histpool, "MIN_POOL_ITEMS", 1 << 62)
    serial = _fit(cfg, x, y, shard=False)
    if pool_obj is None or pool_obj.dead:
        pytest.skip("pool unavailable in this environment (serial == serial)")
    assert pooled == serial


def test_pool_disabled_by_env_stays_serial(monkeypatch):
    from mmlspark_tpu.ops.histpool import _HistPool

    monkeypatch.setenv("MMLSPARK_TPU_HIST_WORKERS", "0")
    pool = _HistPool()
    b = np.zeros((100, 2), np.int32)
    res = pool.bincounts(
        b, np.zeros(100, np.int64),
        np.zeros((3, 100), np.float32), 1, 4,
    )
    assert res is None  # below threshold AND zero workers -> serial


def test_feature_candidates_matches_leaf_best():
    """The numpy split scan must reproduce make_leaf_best exactly
    (gain/threshold tie-breaks included) — it is the one duplicated
    piece of split semantics in the host grower."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.gbdt.treegrow import make_leaf_best
    from mmlspark_tpu.ops.histpool import feature_candidates

    rng = np.random.default_rng(4)
    S, d, B = 3, 4, 16
    cube = rng.normal(size=(S, d, B, 3)).astype(np.float32)
    cube[..., 1] = np.abs(cube[..., 1])          # hessians
    cube[..., 2] = rng.integers(0, 30, (S, d, B))  # counts
    fm = np.ones(d, np.float32)
    gains, bbs = feature_candidates(cube, fm, 5.0, 1e-3, 1.0, 0.0, None)
    lb = make_leaf_best(
        d, jnp.asarray(fm), 5, 1e-3, 1.0, 0.0,
        jnp.zeros(d, bool), False, num_bins=B,
    )
    got = jax.vmap(lb)(jnp.asarray(cube.reshape(S, d * B, 3)))
    # winner per slot: lowest feature among ties, then lowest bin
    bf = np.argmax(gains, axis=0)
    sl = np.arange(S)
    np.testing.assert_array_equal(bf, np.asarray(got[1]))
    np.testing.assert_array_equal(bbs[bf, sl], np.asarray(got[2]))
    np.testing.assert_allclose(
        gains[bf, sl], np.asarray(got[0]), rtol=2e-4, atol=1e-5
    )


# -- dispatch count ----------------------------------------------------------


def test_fused_dispatch_count_is_rounds_over_chunk():
    from mmlspark_tpu.obs import REGISTRY

    def chunks_total():
        fam = REGISTRY.snapshot().get("mmlspark_gbdt_fused_chunks_total")
        return sum(v for _, v in fam["samples"]) if fam else 0.0

    x, y = _toy(n=500)
    cfg = TrainConfig(
        objective="binary", num_iterations=12, num_leaves=7, seed=0
    )
    before = chunks_total()
    train(x, y, cfg)                     # auto: whole run in ONE chunk
    assert chunks_total() - before == 1
    before = chunks_total()
    train(x, y, cfg, fused_rounds=4)     # 12 rounds / 4 = 3 dispatches
    assert chunks_total() - before == 3
    before = chunks_total()
    train(x, y, cfg, fused_rounds=1)     # legacy loop: no fused chunks
    assert chunks_total() - before == 0


# -- device AUC --------------------------------------------------------------


def test_device_auc_matches_host_with_ties():
    import jax.numpy as jnp

    from mmlspark_tpu.core.metrics import binary_auc
    from mmlspark_tpu.models.gbdt.objectives import (
        binary_auc_device,
        sigmoid,
    )

    rng = np.random.default_rng(2)
    n = 1500
    s = np.round(rng.normal(size=n), 1).astype(np.float32)  # heavy ties
    y = (rng.random(n) < 0.4).astype(np.float32)
    m = rng.random(n) < 0.5
    host = binary_auc(y[m], sigmoid(s[m]))
    dev = float(
        binary_auc_device(
            jnp.asarray(s), jnp.asarray(y),
            jnp.asarray(m.astype(np.float32)),
        )
    )
    assert abs(host - dev) < 1e-5


def test_auc_early_stopping_scan_fused_matches_legacy():
    """metric='auc' used to force the per-round host loop; the device
    rank-statistic AUC keeps it scan-fused with identical stopping."""
    x, y = _toy(n=900)
    vm = np.zeros(len(y), bool)
    vm[::3] = True
    cfg = TrainConfig(
        objective="binary", num_iterations=20, num_leaves=7, seed=7,
        metric="auc", early_stopping_round=4,
    )
    b_fast = train(x, y, cfg, valid_mask=vm)
    b_slow = train(x, y, cfg, valid_mask=vm, fused_rounds=1)
    assert b_fast.best_iteration == b_slow.best_iteration
    assert b_fast.to_model_string() == b_slow.to_model_string()
