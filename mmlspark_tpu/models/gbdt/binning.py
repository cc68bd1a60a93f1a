"""Feature quantization for histogram GBDT.

LightGBM's BinMapper equivalent: each feature is quantized to at most
``max_bin`` bins by (approximate) quantiles; training then operates on the
uint8 bin matrix. Bin 0 is reserved for missing values (NaN), matching
LightGBM's missing-bin handling (zero_as_missing=False semantics).

Sparse input: ``fit``/``transform`` also accept a scipy-style CSR/CSC
matrix (anything with ``data``/``indices``/``indptr``/``shape``) — the
reference builds native datasets from dense rows OR sparse rows the same
way (LightGBMUtils.scala:211-265). Stored values are binned per column
without ever densifying the float matrix; absent entries map to the
missing bin (LightGBM's ``zero_as_missing=true``, its recommended setting
for sparse data). The bin matrix itself stays dense uint8 — 1 byte/cell is
the histogram substrate the device kernels consume.

Upper-bound thresholds are kept in original feature space so trained trees
carry real-valued thresholds and prediction never needs the bin mapper.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

MISSING_BIN = 0


def is_sparse(x: object) -> bool:
    return hasattr(x, "indptr") and hasattr(x, "indices") and hasattr(x, "data")


def densify_missing(x: object) -> np.ndarray:
    """Sparse -> dense float32 with ABSENT entries as NaN.

    Prediction-time companion of the zero_as_missing binning: a tree
    trained on sparse data routes absent entries through the missing bin,
    so scoring must present them as NaN, not 0.0."""
    n, d = x.shape
    out = np.full((n, d), np.nan, np.float32)
    xc = x.tocsc() if hasattr(x, "tocsc") else x
    indptr = np.asarray(xc.indptr)
    rows = np.asarray(xc.indices)
    data = np.asarray(xc.data, np.float32)
    for f in range(d):
        lo, hi = indptr[f], indptr[f + 1]
        if hi > lo:
            out[rows[lo:hi], f] = data[lo:hi]
    return out


def _csc_columns(x: object):
    """Yield (f, stored_values) for every column with stored entries."""
    xc = x.tocsc() if hasattr(x, "tocsc") else x
    indptr = np.asarray(xc.indptr)
    for f in range(x.shape[1]):
        lo, hi = indptr[f], indptr[f + 1]
        if hi > lo:
            yield f, np.asarray(xc.data[lo:hi], np.float64)


def _quantile_edges(col: np.ndarray, max_bin: int) -> np.ndarray:
    """Upper bounds of one feature's value bins from its (sampled) column:
    midpoints between its distinct values where they fit the bins, else the
    ``max_bin - 2`` interior percentiles, duplicates merged.

    One sort serves all of it — the sort ``np.unique`` would make, then the
    distinct values by one comparison pass and the percentiles read off the
    sorted column, where NumPy's selection costs a fifth of what it costs on
    an unsorted one. The edges are the ones the two independent passes gave
    (tests/test_gbdt_binning_identity.py keeps that loop as its oracle)."""
    col = col[~np.isnan(col)]
    s = np.sort(col)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] != s[:-1]
    n_uniq = int(np.count_nonzero(first))
    if n_uniq <= 1:
        return np.array([], dtype=np.float64)
    if n_uniq <= max_bin - 1:
        uniq = s[first]
        bounds = (uniq[:-1] + uniq[1:]) / 2.0
    else:
        qs = np.linspace(0, 100, max_bin)[1:-1]
        bounds = np.unique(np.percentile(s, qs, method="linear"))
    return bounds.astype(np.float64)


# columns of the sample laid out contiguously at a time: a column read off
# the row-major sample touches every cache line of it, a block's transpose
# touches each once, and a block bounds the extra memory on wide inputs
_COLUMN_BLOCK = 32


@dataclass
class BinMapper:
    # uppers[f] has length n_bins[f]-1: upper bound (inclusive) of each
    # non-missing bin except the last (which is +inf)
    uppers: list
    max_bin: int

    @property
    def num_features(self) -> int:
        return len(self.uppers)

    @staticmethod
    def fit(
        x: np.ndarray,
        max_bin: int = 255,
        sample: int = 200_000,
        seed: int = 0,
        categorical_features: tuple = (),
    ) -> "BinMapper":
        """``categorical_features``: feature indices binned by IDENTITY
        (category value v -> bin v+1, via half-integer bounds) instead of
        quantiles, so a trained categorical split's bin set corresponds 1:1
        to category values at prediction time. Categorical values must be
        integers in [0, max_bin-2]; out-of-range training values raise (a
        silent collapse would make training and prediction route the same
        row differently). Categories unseen at fit time route to the right
        child at prediction, like LightGBM's other-category default."""
        if not 2 <= max_bin <= 255:
            # bins live in a uint8 matrix (bin 0 = missing); larger values
            # would silently wrap mod 256
            raise ValueError(f"max_bin must be in [2, 255], got {max_bin}")
        if is_sparse(x):
            if categorical_features:
                raise ValueError(
                    "categorical features require dense input (sparse "
                    "columns have no stable category<->bin identity for "
                    "absent entries)"
                )
            return BinMapper._fit_sparse(x, max_bin, sample=sample, seed=seed)
        n, d = x.shape
        if n > sample:
            idx = np.random.default_rng(seed).choice(n, sample, replace=False)
            xs = x[idx]
        else:
            xs = x
        cat = set(int(f) for f in categorical_features)
        uppers = []
        for f0 in range(0, d, _COLUMN_BLOCK):
            block = np.ascontiguousarray(xs[:, f0:f0 + _COLUMN_BLOCK].T)
            for f, col in enumerate(block, start=f0):
                if f not in cat:
                    uppers.append(_quantile_edges(col, max_bin))
                    continue
                # full column, not the sample: hi must cover every category
                # actually present or training bins and prediction's
                # identity mapping would diverge for the unsampled tail
                col = x[:, f]
                col = col[~np.isnan(col)]
                if len(col) and (col.min() < 0 or col.max() > max_bin - 2):
                    raise ValueError(
                        f"categorical feature {f} has values outside "
                        f"[0, {max_bin - 2}] — re-index categories first"
                    )
                hi = int(col.max()) if len(col) else 0
                uppers.append(np.arange(hi, dtype=np.float64) + 0.5)
        return BinMapper(uppers=uppers, max_bin=max_bin)

    @staticmethod
    def _fit_sparse(
        x: object, max_bin: int, sample: int = 200_000, seed: int = 0
    ) -> "BinMapper":
        """Quantile bounds from each column's STORED values only (capped at
        the same per-fit sampling budget as the dense path)."""
        d = x.shape[1]
        rng = np.random.default_rng(seed)
        uppers = [np.array([], dtype=np.float64)] * d
        for f, col in _csc_columns(x):
            if len(col) > sample:
                col = rng.choice(col, sample, replace=False)
            uppers[f] = _quantile_edges(col, max_bin)
        return BinMapper(uppers=uppers, max_bin=max_bin)

    def _transform_sparse(self, x: object) -> np.ndarray:
        """CSR/CSC -> dense uint8 bins; absent entries stay MISSING_BIN."""
        n, d = x.shape
        out = np.zeros((n, d), dtype=np.uint8)
        xc = x.tocsc() if hasattr(x, "tocsc") else x
        indptr = np.asarray(xc.indptr)
        rows = np.asarray(xc.indices)
        data = np.asarray(xc.data, np.float32)
        for f in range(d):
            lo, hi = indptr[f], indptr[f + 1]
            if hi == lo:
                continue
            vals = data[lo:hi]
            b = np.searchsorted(self.uppers[f], vals, side="left") + 1
            b = np.where(np.isnan(vals), MISSING_BIN, b)
            out[rows[lo:hi], f] = b.astype(np.uint8)
        return out

    def transform(self, x: np.ndarray) -> np.ndarray:
        """(n, d) float -> (n, d) uint8 bins; NaN -> MISSING_BIN(0); real
        values start at bin 1."""
        if is_sparse(x):
            return self._transform_sparse(x)
        from mmlspark_tpu.ops import native_loader

        # bin at float32 on BOTH paths so results are identical with and
        # without the native toolchain (the native kernel takes float32)
        x = np.asarray(x, np.float32)
        lib = native_loader.try_load()
        if lib is not None:
            return lib.bin_features(x, self.uppers)
        n, d = x.shape
        out = np.empty((n, d), dtype=np.uint8)
        for f in range(d):
            col = x[:, f]
            b = np.searchsorted(self.uppers[f], col, side="left") + 1
            b = np.where(np.isnan(col), MISSING_BIN, b)
            out[:, f] = b.astype(np.uint8)
        return out

    def num_bins(self, f: int) -> int:
        return len(self.uppers[f]) + 2  # missing bin + len(uppers)+1 value bins

    def transform_into(
        self, x: np.ndarray, out: np.ndarray, row0: int
    ) -> None:
        """Bin a chunk straight into ``out[row0:row0+len(x)]`` — the
        out-of-core ingestion path writes uint8 rows into a preallocated
        matrix without ever holding a second float copy."""
        out[row0:row0 + len(x)] = self.transform(x)

    def threshold_value(self, f: int, bin_idx: int) -> float:
        """Upper bound of value-bin ``bin_idx`` (split 'x <= thr')."""
        u = self.uppers[f]
        i = int(bin_idx) - 1  # value bins start at 1
        if i < 0:
            return -np.inf
        if i >= len(u):
            return np.inf
        return float(u[i])


@dataclass
class BinnedDataset:
    """An already-quantized training input: the uint8 bin matrix plus
    the mapper that produced it. ``train()`` accepts one wherever it
    accepts a float matrix and skips its own fit/transform — the
    out-of-core path bins streaming chunks into this shape so the float
    matrix never exists in memory at once (docs/gbdt-training.md)."""

    bins: np.ndarray        # (n, d) uint8
    mapper: BinMapper

    def __post_init__(self) -> None:
        self.bins = np.ascontiguousarray(self.bins)
        if self.bins.dtype != np.uint8 or self.bins.ndim != 2:
            raise ValueError("BinnedDataset.bins must be a (n, d) uint8")
        if self.bins.shape[1] != self.mapper.num_features:
            raise ValueError(
                f"bins have {self.bins.shape[1]} features, mapper has "
                f"{self.mapper.num_features}"
            )

    @property
    def shape(self) -> tuple:
        return self.bins.shape
