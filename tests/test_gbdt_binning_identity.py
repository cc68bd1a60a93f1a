"""Bin edges are the same whatever ``BinMapper.fit`` does to find them.

PR 28 made the fit sort each sampled column once and read the distinct
count, the midpoints and the percentiles off the sorted column. The
benchmark's reference draws the same sample and computes the same
percentiles on its own, and ``leaf_rows_mismatch`` is compared exactly, so
the edges have to come out as they always did. The oracle below is the
per-column loop as it stood before PR 28, kept verbatim.
"""

import numpy as np
import pytest

from mmlspark_tpu.models.gbdt import BinMapper


def _oracle_uppers(x, max_bin=255, sample=200_000, seed=0, categorical_features=()):
    n, d = x.shape
    if n > sample:
        idx = np.random.default_rng(seed).choice(n, sample, replace=False)
        xs = x[idx]
    else:
        xs = x
    cat = set(int(f) for f in categorical_features)
    uppers = []
    for f in range(d):
        if f in cat:
            col = x[:, f]
            col = col[~np.isnan(col)]
            hi = int(col.max()) if len(col) else 0
            uppers.append(np.arange(hi, dtype=np.float64) + 0.5)
            continue
        col = xs[:, f]
        col = col[~np.isnan(col)]
        uniq = np.unique(col)
        if len(uniq) <= 1:
            uppers.append(np.array([], dtype=np.float64))
            continue
        if len(uniq) <= max_bin - 1:
            bounds = (uniq[:-1] + uniq[1:]) / 2.0
        else:
            qs = np.linspace(0, 100, max_bin)[1:-1]
            bounds = np.unique(np.percentile(col, qs, method="linear"))
        uppers.append(bounds.astype(np.float64))
    return uppers


def _normal(n, d, dtype=np.float32, seed=0):
    return np.random.default_rng(seed).standard_normal((n, d)).astype(dtype)


def _above_sample():
    return _normal(5_000, 5), dict(sample=1_000)


def _with_nans():
    x = _normal(4_000, 4, seed=1)
    x[np.random.default_rng(2).random(x.shape) < 0.15] = np.nan
    return x, dict(sample=1_500)


def _few_distinct():
    r = np.random.default_rng(3)
    x = _normal(3_000, 3, seed=3)
    x[:, 0] = r.integers(0, 7, 3_000)                 # 7 values: midpoints
    x[:, 1] = r.integers(0, 254, 3_000) * 0.25        # exactly at the boundary
    x[:, 2] = r.integers(0, 255, 3_000) * 0.25        # one over it: percentiles
    return x, dict(sample=10_000)


def _constant_and_all_nan():
    x = _normal(2_000, 4, seed=4)
    x[:, 1] = 3.25
    x[:, 2] = np.nan
    x[:, 3] = np.where(np.arange(2_000) % 3 == 0, np.nan, -1.5)  # constant with NaNs
    return x, dict(sample=500)


def _at_most_sample():
    return _normal(1_000, 3, seed=5), dict(sample=1_000)


def _float64():
    return _normal(4_000, 3, dtype=np.float64, seed=6), dict(sample=1_000)


def _zeros_and_repeats():
    r = np.random.default_rng(7)
    x = _normal(6_000, 4, seed=7)
    x[:, 0] = np.where(r.random(6_000) < 0.5, 0.0, -0.0)           # only the two zeros
    x[:, 1] = np.round(x[:, 1], 1)                                 # ~80 distinct values: midpoints
    x[:, 2] = np.round(x[:, 2] * 100) / 100                        # ~700 distinct values, tied
    x[r.random(6_000) < 0.3, 3] = 0.0                              # a spike of +0.0 in a continuum
    x[r.random(6_000) < 0.1, 3] = -0.0
    return x, dict(sample=2_000)


def _small_max_bin():
    return _normal(3_000, 3, seed=8), dict(sample=800, max_bin=16)


def _categorical():
    r = np.random.default_rng(9)
    x = _normal(3_000, 4, seed=9)
    x[:, 1] = r.integers(0, 12, 3_000)
    x[::50, 1] = np.nan
    x[2_999, 1] = 40                                   # a tail the sample may miss
    x[:, 3] = np.nan                                   # a categorical with no value at all
    return x, dict(sample=700, categorical_features=(1, 3))


def _multihost_padded_sample():
    """What train()'s multi-host branch fits on: every process's fixed-size
    buffer, short processes leaving whole NaN rows, all-gathered."""
    d, k_s = 5, 600
    parts = []
    for p, rows in enumerate((600, 250, 0, 600)):
        buf = np.full((k_s, d), np.nan, np.float32)
        buf[:rows] = _normal(rows, d, seed=20 + p)
        parts.append(buf)
    return np.concatenate(parts), dict(sample=200_000)


def _fortran_and_strided():
    x = np.asfortranarray(_normal(3_000, 4, seed=10))
    return x[::2], dict(sample=900)


_CASES = {
    "normal_f32_above_sample": _above_sample,
    "nans": _with_nans,
    "few_distinct": _few_distinct,
    "constant_and_all_nan": _constant_and_all_nan,
    "n_at_most_sample": _at_most_sample,
    "float64": _float64,
    "zeros_and_repeats": _zeros_and_repeats,
    "small_max_bin": _small_max_bin,
    "categorical": _categorical,
    "multihost_nan_padded_sample": _multihost_padded_sample,
    "fortran_strided_input": _fortran_and_strided,
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_bin_edges_identical_to_the_per_column_loop(case):
    x, kw = _CASES[case]()
    before = x.copy()
    got = BinMapper.fit(x, seed=0, **kw).uppers
    want = _oracle_uppers(x, seed=0, **kw)
    assert len(got) == len(want) == x.shape[1]
    for f, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype == np.float64, f
        assert np.array_equal(g, w), f
    # the fit reads its input and nothing else
    assert np.array_equal(x, before, equal_nan=True)


def test_bin_edges_default_sample_draw():
    """The default 200,000-row draw itself (the benchmark's reference makes
    the same one): n just over the sample size, no ``sample=`` argument."""
    x = _normal(200_500, 2, seed=12)
    got = BinMapper.fit(x, max_bin=255, seed=0).uppers
    for g, w in zip(got, _oracle_uppers(x, max_bin=255, seed=0)):
        assert g.dtype == np.float64 and np.array_equal(g, w)


def test_sparse_bin_edges_identical_to_the_per_column_loop():
    """``_fit_sparse`` shares the one-sort rule: the stored values of each
    column give the edges the old loop gave."""
    sp = pytest.importorskip("scipy.sparse")
    r = np.random.default_rng(13)
    dense = r.standard_normal((3_000, 5))
    dense[r.random(dense.shape) < 0.6] = 0.0
    dense[:, 4] = np.where(dense[:, 4] != 0, 2.0, 0.0)   # one stored value
    x = sp.csr_matrix(dense)
    got = BinMapper.fit(x, max_bin=64, sample=500, seed=0).uppers

    rng = np.random.default_rng(0)
    xc = x.tocsc()
    for f in range(5):
        col = np.asarray(xc.data[xc.indptr[f]:xc.indptr[f + 1]], np.float64)
        want = np.array([], np.float64)
        if len(col):
            if len(col) > 500:
                col = rng.choice(col, 500, replace=False)
            col = col[~np.isnan(col)]
            uniq = np.unique(col)
            if len(uniq) > 1:
                if len(uniq) <= 63:
                    want = ((uniq[:-1] + uniq[1:]) / 2.0).astype(np.float64)
                else:
                    qs = np.linspace(0, 100, 64)[1:-1]
                    want = np.unique(np.percentile(col, qs, method="linear")).astype(np.float64)
        assert got[f].dtype == np.float64 and np.array_equal(got[f], want), f
