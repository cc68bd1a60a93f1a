"""Native kernel tests: murmur parity, binning parity, CSV parse."""

from __future__ import annotations

import os

import numpy as np
import pytest

from mmlspark_tpu.ops import native_loader


@pytest.fixture(scope="module")
def lib():
    lib = native_loader.try_load()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


class TestBinFeatures:
    def test_matches_numpy_searchsorted(self, lib):
        rng = np.random.RandomState(0)
        x = rng.randn(500, 6).astype(np.float32)
        x[rng.rand(500, 6) < 0.05] = np.nan
        uppers = [np.sort(rng.randn(rng.randint(0, 20))) for _ in range(6)]
        got = lib.bin_features(x, uppers)
        want = np.empty_like(got)
        for f in range(6):
            col = x[:, f]
            b = np.searchsorted(uppers[f], col, side="left") + 1
            want[:, f] = np.where(np.isnan(col), 0, b).astype(np.uint8)
        np.testing.assert_array_equal(got, want)

    def test_float32_thresholds_count_what_float64_edges_count(self, lib):
        """The kernel compares float32 thresholds (PR 28); the bins are the
        ones ``edge < float64(value)`` gives, at every place the two
        precisions could part: edges that are float32 values themselves,
        edges between two neighbouring float32s, zeros of both signs,
        subnormals, infinities, edges beyond float32's range, a NaN edge."""
        f32 = np.float32
        vals = np.array([0.0, -0.0, 1.0, -1.0, 0.1, 1e-45, -1e-45, 1e-38, 3.4028235e38,
                         -3.4028235e38, np.inf, -np.inf, np.nan, 16777216.0, 0.33333334], f32)
        with np.errstate(over="ignore"):
            vals = np.concatenate(
                [vals, np.nextafter(vals, f32(np.inf)), np.nextafter(vals, f32(-np.inf))])
        v64 = vals.astype(np.float64)
        finite = v64[np.isfinite(v64)]
        edges = np.unique(np.concatenate([
            finite,                                        # exactly a value
            np.nextafter(finite, np.inf), np.nextafter(finite, -np.inf),  # a float64 ulp off it
            (finite[:-1] + finite[1:]) / 2.0,              # between float32 neighbours
            [1e300, -1e300, 3.4028235677973366e38, np.inf, -np.inf, np.nan],
        ]))                                                # sorted, NaN last
        rng = np.random.RandomState(5)
        x = np.stack([vals, vals[::-1], rng.permutation(vals)], axis=1)
        uppers = [edges, edges[::3].copy(), np.array([], np.float64)]
        got = lib.bin_features(x, uppers)
        for f, u in enumerate(uppers):
            col = x[:, f].astype(np.float64)
            with np.errstate(invalid="ignore"):
                want = 1 + (u[None, :] < col[:, None]).sum(axis=1)
            want = np.where(np.isnan(col), 0, want)
            np.testing.assert_array_equal(got[:, f], want.astype(np.uint8), err_msg=str(f))

    def test_binmapper_transform_same_with_and_without_native(self, lib, monkeypatch):
        from mmlspark_tpu.models.gbdt.binning import BinMapper
        from mmlspark_tpu.ops import native_loader

        rng = np.random.RandomState(6)
        x = rng.randn(20_000, 5).astype(np.float32)
        x[:, 1] = np.round(x[:, 1], 1)            # values that ARE edges' neighbours
        x[:, 2] = rng.randint(0, 9, 20_000)       # midpoint edges
        x[rng.rand(20_000, 5) < 0.03] = np.nan
        mapper = BinMapper.fit(x, max_bin=255, sample=5_000)
        with_native = mapper.transform(x)
        monkeypatch.setattr(native_loader, "try_load", lambda: None)
        np.testing.assert_array_equal(mapper.transform(x), with_native)

    def test_gbdt_binmapper_uses_native(self):
        from mmlspark_tpu.models.gbdt.binning import BinMapper

        rng = np.random.RandomState(1)
        x = rng.randn(1000, 4).astype(np.float32)
        mapper = BinMapper.fit(x, max_bin=16)
        bins = mapper.transform(x)
        assert bins.dtype == np.uint8
        assert bins.max() <= 16

    def test_large_threaded(self, lib):
        rng = np.random.RandomState(2)
        x = rng.randn(300_000, 4).astype(np.float32)
        uppers = [np.sort(rng.randn(10)) for _ in range(4)]
        got = lib.bin_features(x, uppers)
        # spot-check a few rows against numpy
        idx = rng.choice(300_000, 100)
        for f in range(4):
            want = np.searchsorted(uppers[f], x[idx, f], side="left") + 1
            np.testing.assert_array_equal(got[idx, f], want.astype(np.uint8))


class TestParseCSV:
    def test_basic(self, lib):
        out = lib.parse_csv(b"1.5,2,3\n4,,-6.25\n")
        np.testing.assert_allclose(out[0], [1.5, 2.0, 3.0])
        assert np.isnan(out[1, 1])
        np.testing.assert_allclose(out[1, [0, 2]], [4.0, -6.25])

    def test_blank_lines_and_crlf(self, lib):
        out = lib.parse_csv(b"1,2\r\n\r\n3,4\r\n")
        assert out.shape == (2, 2)
        np.testing.assert_allclose(out, [[1, 2], [3, 4]])

    def test_bad_fields_are_nan(self, lib):
        out = lib.parse_csv(b"1,abc\n2,3\n")
        assert np.isnan(out[0, 1]) and out[1, 1] == 3.0

    def test_long_fields_parse_exactly(self, lib):
        # >=64-char numeric literal whose exponent sits past the old stack
        # buffer: truncation would parse to a drastically wrong value
        long_num = "1" * 70 + "e-60"
        long_frac = "0." + "9" * 75
        data = f"{long_num},{long_frac}\n".encode()
        out = lib.parse_csv(data)
        np.testing.assert_allclose(out[0, 0], float(long_num), rtol=0)
        np.testing.assert_allclose(out[0, 1], float(long_frac), rtol=0)

    def test_long_garbage_field_is_nan(self, lib):
        out = lib.parse_csv(("x" * 100 + ",2\n").encode())
        assert np.isnan(out[0, 0]) and out[0, 1] == 2.0

    def test_trailing_garbage_is_nan(self, lib):
        # strtod partial parses must be rejected ('1.5abc' is not a number),
        # matching float() / the pure-Python fallback; whitespace is fine
        out = lib.parse_csv(b"1.5abc, 2.5 ,3\n")
        assert np.isnan(out[0, 0])
        np.testing.assert_allclose(out[0, 1:], [2.5, 3.0])
        long_garbage = "1" * 70 + "junk"
        out = lib.parse_csv(f"{long_garbage},1\n".encode())
        assert np.isnan(out[0, 0]) and out[0, 1] == 1.0


class TestReadCSV:
    def test_numeric_with_header(self, tmp_path):
        p = tmp_path / "data.csv"
        p.write_text("a,b,c\n1,2,3\n4,5,6\n7,8,9\n")
        from mmlspark_tpu.io.csv import read_csv

        df = read_csv(str(p), num_partitions=2)
        assert df.columns == ["a", "b", "c"]
        np.testing.assert_allclose(df["b"], [2.0, 5.0, 8.0])
        assert df.num_partitions == 2

    def test_mixed_types(self, tmp_path):
        p = tmp_path / "mixed.csv"
        p.write_text("name,score\nalice,1.5\nbob,2.5\n")
        from mmlspark_tpu.io.csv import read_csv

        df = read_csv(str(p))
        assert df["name"].tolist() == ["alice", "bob"]
        np.testing.assert_allclose(df["score"], [1.5, 2.5])

    def test_no_header(self, tmp_path):
        p = tmp_path / "nh.csv"
        p.write_text("1,2\n3,4\n")
        from mmlspark_tpu.io.csv import read_csv

        df = read_csv(str(p), header=False)
        assert df.columns == ["c0", "c1"]
        np.testing.assert_allclose(df["c0"], [1.0, 3.0])

    def test_python_fallback(self, tmp_path, monkeypatch):
        p = tmp_path / "fb.csv"
        p.write_text("a,b\n1,2\n3,4\n")
        from mmlspark_tpu.io import csv as csv_mod

        monkeypatch.setattr(csv_mod.native_loader, "try_load", lambda: None)
        df = csv_mod.read_csv(str(p))
        np.testing.assert_allclose(df["a"], [1.0, 3.0])

    def test_strings_past_probe_window_fall_back(self, tmp_path):
        # column 'a' is empty through the 20-line auto-detect window and
        # only shows its (string) values later; the fast path would turn it
        # into an all-NaN column — the guard must reroute to mixed parsing
        lines = ["a,b"] + [f",{i}" for i in range(25)] + ["hello,99"]
        p = tmp_path / "late.csv"
        p.write_text("\n".join(lines) + "\n")
        from mmlspark_tpu.io.csv import read_csv

        df = read_csv(str(p))
        assert df["a"].dtype == object  # mixed parse kept the strings
        assert df["a"].tolist()[-1] == "hello"
        np.testing.assert_allclose(np.asarray(df["b"], np.float64)[-1], 99.0)

    def test_empty_numeric_column_keeps_fast_path(self, tmp_path):
        # a legitimately never-populated column must NOT trigger the
        # mixed-parser reroute (or a full second parse of the file)
        lines = ["a,b"] + [f",{i}" for i in range(25)]
        p = tmp_path / "emptycol.csv"
        p.write_text("\n".join(lines) + "\n")
        from mmlspark_tpu.io.csv import read_csv

        df = read_csv(str(p))
        a = np.asarray(df["a"], np.float64)
        assert a.dtype == np.float64 and np.isnan(a).all()

    def test_forced_numeric_only_keeps_fast_path(self, tmp_path):
        lines = ["a,b"] + [f",{i}" for i in range(25)] + ["hello,99"]
        p = tmp_path / "late2.csv"
        p.write_text("\n".join(lines) + "\n")
        from mmlspark_tpu.io.csv import read_csv

        df = read_csv(str(p), numeric_only=True)
        assert np.isnan(np.asarray(df["a"], np.float64)).all()
