"""Pallas histogram kernel vs XLA scatter-add — exact agreement."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from mmlspark_tpu.ops import histogram as H


def _data(n, d, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, H.NUM_BINS, (n, d)).astype(np.int32)
    stats = rng.randn(n, 3).astype(np.float32)
    return jnp.asarray(bins), jnp.asarray(stats)


class TestPlaneHistogram:
    @pytest.mark.parametrize(
        "n,d",
        [(100, 3), (512, 8), (700, 11), (1500, 5), (1, 1), (513, 9)],
    )
    def test_pallas_matches_scatter(self, n, d, monkeypatch):
        bins, stats = _data(n, d)
        want = np.asarray(H._plane_histogram_scatter(bins, stats))
        got = np.asarray(H._plane_histogram_pallas(bins, stats))
        np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-5)

    def test_mask_zeroes_rows(self):
        bins, stats = _data(300, 4)
        mask = jnp.asarray((np.arange(300) % 2).astype(np.float32))
        full = np.asarray(H.plane_histogram(bins, stats, mask))
        manual = np.asarray(
            H._plane_histogram_scatter(bins, stats * mask[:, None])
        )
        np.testing.assert_allclose(full, manual, atol=1e-4)

    def test_counts_sum_to_n(self):
        n, d = 640, 4
        bins, _ = _data(n, d, seed=3)
        stats = jnp.concatenate(
            [jnp.zeros((n, 2), jnp.float32), jnp.ones((n, 1), jnp.float32)], axis=1
        )
        plane = np.asarray(H._plane_histogram_pallas(bins, stats))
        per_feature = plane[:, 2].reshape(d, H.NUM_BINS).sum(axis=1)
        np.testing.assert_allclose(per_feature, n)

    def test_out_of_range_bins_dropped_by_both_lowerings(self):
        bins = jnp.asarray([[0, 300], [255, -5]], jnp.int32)
        stats = jnp.ones((2, 3), jnp.float32)
        a = np.asarray(H._plane_histogram_scatter(bins, stats))
        b = np.asarray(H._plane_histogram_pallas(bins, stats))
        np.testing.assert_allclose(a, b)
        # only the two valid cells received stats
        assert a[:, 2].sum() == 2.0

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "0")
        assert not H.use_pallas()
        monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
        assert H.use_pallas()


class TestMultiPlane:
    def test_matches_per_slot_single_planes(self):
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import (
            multi_plane_histogram,
            plane_histogram,
        )

        rng = np.random.default_rng(9)
        from mmlspark_tpu.ops.histogram import NUM_BINS

        n, d, S = 1000, 6, 5
        bins = jnp.asarray(rng.integers(-2, NUM_BINS + 2, size=(n, d)).astype(np.int32))
        stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        slot = jnp.asarray(rng.integers(-1, S + 1, size=(n,)).astype(np.int32))
        cube = np.asarray(multi_plane_histogram(bins, stats, slot, S))
        assert cube.shape == (S, d * 256, 3)
        for s in range(S):
            mask = (np.asarray(slot) == s).astype(np.float32)
            single = np.asarray(plane_histogram(bins, stats, jnp.asarray(mask)))
            np.testing.assert_allclose(cube[s], single, atol=2e-4)

    def test_out_of_range_slots_drop(self):
        import jax.numpy as jnp

        from mmlspark_tpu.ops.histogram import multi_plane_histogram

        bins = jnp.zeros((4, 2), jnp.int32)
        stats = jnp.ones((4, 3), jnp.float32)
        slot = jnp.asarray([0, 1, -1, 99], jnp.int32)
        cube = np.asarray(multi_plane_histogram(bins, stats, slot, 2))
        # only the two in-range rows land: each hits d=2 features x 3 stats
        assert cube.sum() == 2 * 2 * 3


def test_plane_histogram_num_bins_variants():
    """Parameterized bin space: B=64/16 planes must equal the dense-256
    plane restricted to the live bins (same scatter/Pallas agreement)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    n, d = 1000, 5
    for b in (64, 16):
        bins = jnp.asarray(rng.integers(0, b, size=(n, d)).astype(np.int32))
        stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        small = np.asarray(H.plane_histogram(bins, stats, num_bins=b))
        full = np.asarray(H.plane_histogram(bins, stats)).reshape(d, 256, 3)
        np.testing.assert_allclose(
            small.reshape(d, b, 3), full[:, :b], rtol=1e-5, atol=1e-5
        )


def test_multi_plane_histogram_num_bins_variants():
    import jax.numpy as jnp

    rng = np.random.default_rng(10)
    n, d, S, b = 800, 4, 3, 32
    bins = jnp.asarray(rng.integers(0, b, size=(n, d)).astype(np.int32))
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    slot = jnp.asarray(rng.integers(0, S, size=(n,)).astype(np.int32))
    small = np.asarray(H.multi_plane_histogram(bins, stats, slot, S, num_bins=b))
    full = np.asarray(H.multi_plane_histogram(bins, stats, slot, S)).reshape(
        S, d, 256, 3
    )
    np.testing.assert_allclose(
        small.reshape(S, d, b, 3), full[:, :, :b], rtol=1e-5, atol=1e-5
    )


def test_plain_and_split_pallas_kernels_agree():
    """Both Pallas lowerings of the 256-bin plane (plain one-hot and the
    decomposed hi/lo kernel) must produce the same sums — the plain kernel
    stays the production path for B < 128, so it needs its own coverage
    now that B=256 auto-selects the split kernel."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    n, d = 1500, 6
    bins = jnp.asarray(rng.integers(0, 256, size=(n, d)).astype(np.int32))
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    assert H._use_split(256)
    plain = np.asarray(H._plane_histogram_pallas(bins, stats, 256, split=False))
    split = np.asarray(H._plane_histogram_pallas(bins, stats, 256))
    np.testing.assert_allclose(plain, split, rtol=1e-4, atol=1e-3)
    ref = np.asarray(H._plane_histogram_scatter(bins, stats, 256))
    np.testing.assert_allclose(split, ref, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("num_bins", [63, 132])
def test_a_bin_count_the_decomposition_cannot_tile_takes_the_plain_kernel(num_bins):
    """bin = hi * 8 + lo must tile exactly: a narrow count (63) and a wide
    one that is no multiple of 8 (132) get the plain kernel, not a
    trace-time crash."""
    import jax.numpy as jnp

    rng = np.random.default_rng(12)
    bins = jnp.asarray(rng.integers(0, num_bins, size=(500, 4)).astype(np.int32))
    stats = jnp.asarray(rng.normal(size=(500, 3)).astype(np.float32))
    assert not H._use_split(num_bins)
    got = np.asarray(H._plane_histogram_pallas(bins, stats, num_bins))
    ref = np.asarray(H._plane_histogram_scatter(bins, stats, num_bins))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)


def test_shard_map_plane_psum_in_hlo(devices8, monkeypatch):
    """The sharded Pallas lowering's collective must be the explicit
    plane psum (one all-reduce of d*B*3 f32), not a GSPMD rewrite of a
    scatter — the designed analogue of LightGBM data_parallel's
    per-iteration histogram allreduce (TrainUtils.scala:496-512)."""
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.parallel.mesh import get_mesh
    from mmlspark_tpu.parallel.sharding import shard_batch

    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    mesh = get_mesh()
    n, d, B = 1024, 8, 64
    rng = np.random.default_rng(0)
    bins = shard_batch(rng.integers(0, B, (n, d)).astype(np.int32), mesh)
    stats = shard_batch(rng.normal(size=(n, 3)).astype(np.float32), mesh)

    fn = jax.jit(
        lambda b, s: H.plane_histogram(
            b, s, num_bins=B, mesh=mesh, shard_axis="data"
        )
    )
    hlo = fn.lower(bins, stats).compile().as_text()
    sizes = [
        int(m.group(1)) * int(m.group(2))
        for m in re.finditer(r"f32\[(\d+),(\d+)\]\{[0-9,]*\} all-reduce", hlo)
    ]
    assert d * B * 3 in sizes, f"plane-sized all-reduce missing: {sizes}"
    # and it computes the right thing
    out = np.asarray(fn(bins, stats))
    ref = np.asarray(
        H._plane_histogram_scatter(
            jnp.asarray(np.asarray(bins)), jnp.asarray(np.asarray(stats)), B
        )
    )
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-3)


def test_multi_df_vmem_accounting(monkeypatch):
    """The multi-plane feature-block pick must count the kernel's full
    VMEM-resident set (bf16 one-hot block + packed f32 accumulator pair),
    not just the output block — the output-only budget chose DF=32 at
    d=64/S=32 whose real resident set (~16.1 MB) tripped Mosaic's default
    16 MB scoped-vmem ceiling on v5e (observed compile failure, BENCH r5).

    Expected values below are hand-computed, NOT re-derived through the
    implementation's formula: at NC=512, B=256 the per-DF resident set is
    DF*256*(512*2 + S*48) bytes = DF * (256 KiB + S * 12 KiB)."""
    assert (H._NC, H._DF) == (512, 8)   # what the table was computed for
    # default ceiling 96 MB -> budget 64 MB:
    #   S=32:  DF=32 -> 32*(0.25+0.375)MiB*32 = 20 MiB  -> fits, picked
    assert H._multi_df(32, 256, 64) == 32
    #   S=256: DF=32 -> 32*(0.25+3)MiB*... = 104 MiB > 64 -> DF=16 (52 MiB)
    assert H._multi_df(256, 256, 64) == 16
    #   S=1024: even DF=8 is 8*(0.25+12) = 98 MiB > 64 -> no block fits
    assert H._multi_df(1024, 256, 64) is None
    # the ceiling and the budget move together: at the Mosaic default
    # ceiling (16 MB -> 10 MiB budget) the DF=32/S=32 pick that
    # compile-failed on chip (resident 20 MiB) is rejected; DF=16 (10 MiB)
    # fits
    monkeypatch.setitem(H._VMEM_LIMIT_MB, "cpu", 16)
    assert H._multi_df(32, 256, 64) == 16


def test_multi_plane_huge_slots_uses_scatter():
    """When no feature block fits VMEM the public op must still work
    (scatter lowering), not assert or compile-fail."""
    rng = np.random.default_rng(5)
    n, d, s = 300, 4, 1024
    bins = jnp.asarray(rng.integers(0, 256, (n, d)), jnp.int32)
    stats_np = rng.normal(size=(n, 3)).astype(np.float32)
    stats_np[:, 2] = 1.0  # count column
    stats = jnp.asarray(stats_np)
    slot = jnp.asarray(rng.integers(0, s, (n,)), jnp.int32)
    out = H.multi_plane_histogram(bins, stats, slot, s)
    assert out.shape == (s, d * 256, 3)
    np.testing.assert_allclose(
        np.asarray(out.sum(axis=(0, 1))[2]), n * d, rtol=1e-6
    )


def test_pallas_call_kwargs_follow_the_target_device():
    """Interpreter off-TPU (no Mosaic options); on a TPU target Mosaic with
    the device kind's VMEM ceiling — and a kind with no known VMEM size is
    an error, not a default."""
    from types import SimpleNamespace

    assert H._pallas_call_kwargs() == {"interpret": True}
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    kw = H._pallas_call_kwargs(v5e)
    assert kw["interpret"] is False
    assert kw["compiler_params"].vmem_limit_bytes == 96 << 20
    unknown = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        H._pallas_call_kwargs(unknown)


def test_lowering_choice_is_counted_at_trace_time(monkeypatch):
    """Every histogram op records the lowering it chose — including the
    VMEM-overflow drop to the scatter — so a program that runs the
    reference in place of the kernel is visible in /metrics."""
    def count(op, lowering):
        return H._M_LOWERINGS.labels(op=op, lowering=lowering).value

    monkeypatch.setenv("MMLSPARK_TPU_PALLAS", "1")
    bins, stats = _data(64, 2)
    slot = jnp.zeros((64,), jnp.int32)
    before = count("plane", "pallas"), count("multi_plane", "pallas")
    H.plane_histogram(bins, stats)
    H.multi_plane_histogram(bins, stats, slot, 2)
    assert count("plane", "pallas") == before[0] + 1
    assert count("multi_plane", "pallas") == before[1] + 1
    # no feature block fits the VMEM budget -> scatter, and it is counted
    before_s = count("multi_plane", "scatter")
    H.multi_plane_histogram(bins, stats, slot, 4096)
    assert count("multi_plane", "scatter") == before_s + 1
