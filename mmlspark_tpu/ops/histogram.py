"""Histogram plane builder — the GBDT hot op.

LightGBM's C++ trainer spends its time building per-leaf gradient
histograms. Here the op is ``plane_histogram(bins, stats, mask)``:
scatter the (g, h, count) stats of the masked rows into a
``(d * NUM_BINS, 3)`` plane.

Lowerings:

- **Pallas (TPU, one device)**: grid over (feature-blocks, row-chunks);
  each step builds a bf16 one-hot (DF, B, rows) block in VMEM (rows on the
  128-lane dim) and accumulates ``one_hot @ stats_hi/lo`` into the output
  block — the scatter becomes an MXU matmul, which is how TPUs like their
  histograms. Stats are split hi+lo bf16 so two native MXU passes recover
  f32-grade sums. Rows stream chunk by chunk so VMEM stays bounded.
- **shard_map + Pallas (TPU, sharded meshes)**: when the caller passes the
  mesh whose ``data`` axis shards the rows, the kernel runs PER SHARD under
  ``jax.shard_map`` and the (d*B, 3) planes are combined with an explicit
  ``psum`` riding ICI — exactly LightGBM's data_parallel per-iteration
  histogram allreduce (lightgbm/TrainUtils.scala:496-512 NetworkInit +
  socket rings), with the MXU kernel intact on every chip.
- **XLA scatter-add (the reference)**: what the kernels are tested
  against, the local kernel of a sharded CPU mesh, and the lowering when
  Pallas is switched off. Under a mesh it runs PER SHARD under
  ``shard_map`` with the same explicit ``psum``.
- **Host bincount (CPU)**: XLA:CPU lowers scatter-add to an
  element-by-element update loop (~70 ns/update measured); ``np.bincount``
  does the identical accumulation at ~2 ns/update and, because the
  kernel sees the row mask/slot vector instead of pre-zeroed stats, it
  compacts to the selected rows first — per-split cost becomes
  proportional to the CHILD size, LightGBM's DataPartition cost model
  without the permutation. Runs as a ``pure_callback`` inside the jitted
  (and scan-fused) growers; on CPU the "device" is the host, so
  residency is preserved. Trade-off: callback programs are excluded
  from jax's persistent compilation cache, so CPU training programs
  recompile once per process (the ~10x runtime win repays one compile
  within a single 20-iteration fit).

Selection follows the device the call is lowered for — the caller's mesh
when it passes one, the process's default device otherwise (see
:func:`hist_lowering`). ``MMLSPARK_TPU_PALLAS=0|1`` and
``MMLSPARK_TPU_HIST_HOST=0|1`` are how a CPU process stands in for
another platform's lowering (the benchmark's and ``chip_smoke.py``'s
rehearsals, the tests); block sizes and the kernels' variants are
constants or derived from the call (:func:`_use_split`,
:func:`_hist_vmem_mb`). A call with no mesh is a ONE-DEVICE call: it takes
the kernel on any TPU host, however many chips the host has; a caller
whose rows are sharded passes its mesh. Every choice is counted in
``mmlspark_gbdt_hist_lowerings_total{op,lowering}`` at trace time and on each load of the
program from the program store; which GROWER a fit gets is ``treegrow.choose_grower``'s to say.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu import obs
from mmlspark_tpu.core.compile_cache import count_traced

# The host-kernel pure_callbacks deadlock against XLA:CPU's async
# dispatch: the callback thread's operand conversion (np.asarray on a
# jax.Array) waits on a d2h materialization that is queued behind the
# very computation suspended in the callback. The wedged pair was
# captured by the stall-forensics watchdog — MainThread in
# jax array._value under fit(), callback thread in hostgrow.py's
# np.asarray(bins) under pure_callback_impl; see docs/gbdt-training.md
# "Known issues". The flag is read ONCE at CPU client creation, so this
# import-time update only protects processes that import this module
# before their first dispatch — embedding code that runs jax first must
# set it itself (tests/conftest.py does). No effect on TPU.
jax.config.update("jax_cpu_enable_async_dispatch", False)

_M_LOWERINGS = obs.counter(
    "mmlspark_gbdt_hist_lowerings_total",
    "Histogram ops traced, by the lowering chosen at trace time (pallas | "
    "cpu = host bincount | scatter). A scatter count on a TPU means a "
    "program is running the reference in place of the kernel",
    labels=("op", "lowering"),
)


def _count_lowering(op: str, lowering: str) -> None:
    count_traced(_M_LOWERINGS, op=op, lowering=lowering)


NUM_BINS = 256

# block sizes: DF features x NC rows per grid step; the one-hot block is
# (DF, B, NC) bf16 = 8 x 256 x 512 x 2B = 2 MB VMEM by default, with rows
# on the 128-lane dim (NC must be a multiple of 128 on real TPU; DF a
# multiple of 8).
_DF = 8
_NC = 512


# Scoped-VMEM ceiling handed to Mosaic for these kernels, in MB, by
# ``device_kind``. Mosaic's default 16 MB is too tight for the multi-plane
# kernel's resident set (one-hot block + packed accumulator: ~16.1 MB at
# DF=32, B=256, 32 slots — a compile-time scoped-vmem OOM at d=64 on
# v5e); a v5e core has 128 MB of VMEM. The CPU entry sizes the
# interpreter's blocks like the v5e's so CPU tests trace the chip's block
# choices. A kind that is not listed is an error, not a default.
_VMEM_LIMIT_MB = {"TPU v5 lite": 96, "cpu": 96}


def _target_device(mesh=None):
    """The device a histogram call is lowered for: the first device of the
    caller's mesh when it passes one, the process's default device
    otherwise. A backend that failed to initialise raises here — it must
    not select a lowering silently."""
    if mesh is not None:
        return mesh.devices.flat[0]
    return jax.devices()[0]


def _hist_vmem_mb(dev=None) -> int:
    kind = (dev or _target_device()).device_kind
    if kind not in _VMEM_LIMIT_MB:
        raise ValueError(
            f"no scoped-VMEM ceiling known for device kind {kind!r}: add "
            "its VMEM size to ops/histogram.py _VMEM_LIMIT_MB"
        )
    return _VMEM_LIMIT_MB[kind]


def _pallas_call_kwargs(dev=None) -> dict:
    """``interpret=`` / ``compiler_params=`` for a kernel lowered for
    ``dev``: Mosaic with the device kind's VMEM ceiling on a TPU, the
    Pallas interpreter anywhere else."""
    dev = dev or _target_device()
    if dev.platform != "tpu":
        return {"interpret": True}
    from jax.experimental.pallas import tpu as pltpu

    return {
        "interpret": False,
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_hist_vmem_mb(dev) << 20
        ),
    }


def _env_flag(name: str) -> "bool | None":
    env = os.environ.get(name)
    return None if env is None else env not in ("0", "false", "")


def use_pallas(mesh=None) -> bool:
    """Pallas lowering choice: the target device is a TPU, or env-forced."""
    forced = _env_flag("MMLSPARK_TPU_PALLAS")
    if forced is not None:
        return forced
    return _target_device(mesh).platform == "tpu"


def use_host_hist(mesh=None) -> bool:
    """Host-bincount lowering choice (CPU target; or env-forced).

    ``MMLSPARK_TPU_HIST_HOST=0`` restores the XLA scatter lowering on CPU
    (the reference the host kernel is tested against)."""
    forced = _env_flag("MMLSPARK_TPU_HIST_HOST")
    if forced is not None:
        return forced
    return _target_device(mesh).platform == "cpu" and not use_pallas(mesh)


def hist_lowering(mesh=None) -> str:
    """Name of the one-device lowering :func:`plane_histogram` picks for
    this target right now: ``pallas`` | ``cpu`` (host bincount) |
    ``scatter``. Threaded into the growers' jit cache keys."""
    if use_pallas(mesh):
        return "pallas"
    if use_host_hist(mesh):
        return "cpu"
    return "scatter"


def _rows_sharded(mesh, shard_axis) -> bool:
    return (
        mesh is not None
        and shard_axis is not None
        and dict(mesh.shape).get(shard_axis, 1) > 1
    )


# -- host (numpy bincount) lowering -----------------------------------------
#
# One module-level kernel per op so the traced callback target is a stable
# object: jit caches of the enclosing programs stay valid across train()
# calls (a fresh closure per call would retrace every fit).


def _host_bincounts(
    out: np.ndarray, b: np.ndarray, base, s: np.ndarray, ns: int, nb: int,
    in_range: bool = False,
) -> None:
    """Shared accumulation loop: per feature, one weighted bincount per
    stat column into ``out[:, f]``. ``base`` is the per-row plane offset
    (slot * nb, or 0) with a trash value of ns*nb for dropped rows;
    out-of-range bin codes also land in the trash slot (scatter's
    mode='drop' semantics). np.bincount accumulates in f64 and the result
    is cast once — slightly MORE accurate than the f32 scatter it
    replaces."""
    g, h, c = s[:, 0], s[:, 1], s[:, 2]
    trash = ns * nb
    width = trash + 1
    # one contiguous transpose up front: per-feature rows become
    # sequential reads, and the per-feature astype goes away (~30% of the
    # kernel at bench shapes)
    bT = np.ascontiguousarray(b.T, np.int32)
    in_range = in_range or (
        bool((bT.min() >= 0) and (bT.max() < nb)) if bT.size else True
    )
    for f in range(bT.shape[0]):
        col = bT[f]
        if in_range:
            idx = base + col
        else:
            idx = np.where((col >= 0) & (col < nb), base + col, trash)
        for j, w in enumerate((g, h, c)):
            out[:, f, :, j] = np.bincount(
                idx, weights=w, minlength=width
            )[:trash].reshape(ns, nb)


def _pool_worthwhile(kept_rows: int, d: int) -> bool:
    from mmlspark_tpu.ops.histpool import MIN_POOL_ITEMS

    return kept_rows * d >= MIN_POOL_ITEMS


def _try_pool(
    b: np.ndarray, base: np.ndarray, s3: np.ndarray, ns: int, nb: int
) -> "np.ndarray | None":
    """Feature-parallel process pool (histpool.py). None = run serial.
    Bit-identical to the serial loop either way (same per-feature
    bincounts, same row order)."""
    from mmlspark_tpu.ops.histpool import pooled_bincounts

    res = pooled_bincounts(b, base, s3, ns, nb)
    if res is None:
        return None
    # the pool result aliases its shared arena (valid until the next
    # call) — copy before handing it to the callback bridge
    return res.reshape(ns, b.shape[1] * nb, 3).copy()


def _host_plane_kernel(
    num_bins: int, in_range: bool, bins, stats, mask=None
) -> np.ndarray:
    """(n, d) bins + (n, 3) stats [+ (n,) weight mask] -> (d*B, 3) f32.

    The mask arrives as the raw row selector, not pre-zeroed stats, so
    sparse selections (a leaf-wise split's moved rows) compact to the
    selected rows first: per-split cost is proportional to the CHILD
    size. At >= half the rows kept, scanning everything with zeroed
    weights beats the gather; full-width builds go to the worker pool."""
    nb = num_bins
    b = np.asarray(bins)
    n = b.shape[0]
    m = None if mask is None else np.asarray(mask, np.float32)
    n_kept = n if m is None else int(np.count_nonzero(m))
    if (
        in_range
        and b.dtype in (np.int32, np.uint8)
        and n_kept == n
        and _pool_worthwhile(n, b.shape[1])
        # fractional masks stay serial: the pool transports f32 stats, so
        # an f32 mask multiply would differ from the serial kernel's f64
        # product in the last ulp — only exact 0/1 selectors preserve the
        # pooled == serial bit-identity invariant
        and (m is None or bool(np.all((m == 0.0) | (m == 1.0))))
    ):
        s32 = np.asarray(stats, np.float32)
        s3 = np.ascontiguousarray((s32 if m is None else s32 * m[:, None]).T)
        res = _try_pool(b, np.zeros(n, np.int64), s3, 1, nb)
        if res is not None:
            return res.reshape(b.shape[1] * nb, 3)
    s = np.asarray(stats, np.float64)
    base: "np.ndarray | int" = 0
    if m is not None:
        m64 = m.astype(np.float64)
        if n_kept < (n >> 1):
            keep = np.flatnonzero(m64)
            b, s = b[keep], s[keep] * m64[keep, None]
        else:
            s = s * m64[:, None]
    out = np.empty((1, b.shape[1], nb, 3), np.float32)
    _host_bincounts(out, b, base, s, 1, nb, in_range)
    return out.reshape(b.shape[1] * nb, 3)


def _host_multi_kernel(
    num_slots: int, num_bins: int, in_range: bool, bins, stats, slot
) -> np.ndarray:
    """Multi-leaf planes: (n,) slot selects the plane; out-of-range slots
    drop, so the sibling-subtraction caller's cost is proportional to the
    rows it actually histograms, not the dataset. Large builds go to the
    worker pool (dropped rows ride along as trash offsets — cheaper than
    a main-thread compaction gather)."""
    ns, nb = num_slots, num_bins
    b = np.asarray(bins)
    sl = np.asarray(slot).astype(np.int64)
    ok = (sl >= 0) & (sl < ns)
    all_ok = bool(ok.all())
    kept = b.shape[0] if all_ok else int(ok.sum())
    # pool only when the SELECTED work is large: the pool scans dropped
    # rows too (trash offsets), so a small child inside a big dataset is
    # cheaper through the compacting serial path
    if (
        in_range
        and b.dtype in (np.int32, np.uint8)
        and _pool_worthwhile(kept, b.shape[1])
    ):
        base = sl * nb if all_ok else np.where(ok, sl * nb, ns * nb)
        res = _try_pool(
            b, base, np.ascontiguousarray(np.asarray(stats, np.float32).T),
            ns, nb,
        )
        if res is not None:
            return res
    s = np.asarray(stats, np.float64)
    if not all_ok:
        keep = np.flatnonzero(ok)
        if keep.size < (b.shape[0] >> 1):
            b, s, sl = b[keep], s[keep], sl[keep]
            base = sl * nb
        else:
            base = np.where(ok, sl * nb, ns * nb)
    else:
        base = sl * nb
    out = np.empty((ns, b.shape[1], nb, 3), np.float32)
    _host_bincounts(out, b, base, s, ns, nb, in_range)
    return out.reshape(ns, b.shape[1] * nb, 3)


def _callback(kernel, out_shape, *args) -> jnp.ndarray:
    """pure_callback; a vmapped call runs the host kernel row by row."""
    return jax.pure_callback(
        kernel, out_shape, *args, vmap_method="sequential"
    )


def _plane_histogram_host(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    mask: "jnp.ndarray | None",
    num_bins: int = NUM_BINS,
    assume_in_range: bool = False,
) -> jnp.ndarray:
    d = bins.shape[1]
    out = jax.ShapeDtypeStruct((d * num_bins, 3), jnp.float32)
    kern = functools.partial(_host_plane_kernel, num_bins, assume_in_range)
    if mask is None:
        return _callback(kern, out, bins, stats)
    return _callback(kern, out, bins, stats, mask)


def _multi_plane_host(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    slot: jnp.ndarray,
    num_slots: int,
    num_bins: int = NUM_BINS,
    assume_in_range: bool = False,
) -> jnp.ndarray:
    d = bins.shape[1]
    out = jax.ShapeDtypeStruct((num_slots, d * num_bins, 3), jnp.float32)
    kern = functools.partial(
        _host_multi_kernel, num_slots, num_bins, assume_in_range
    )
    return _callback(kern, out, bins, stats, slot)


def _hist_kernel(bins_ref, stats_ref, out_ref, *, num_bins: int):
    """One (feature-block, row-chunk) step: accumulate one-hot @ stats."""
    import jax.experimental.pallas as pl

    row_chunk = pl.program_id(1)

    @pl.when(row_chunk == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:]          # (DF, NC) int32; out-of-range = contribute nowhere
    stats = stats_ref[:]        # (NC, 3) f32 (already mask-scaled; 0 rows inert)
    df, nc = bins.shape
    b = num_bins
    # one_hot[f, v, r] = (bins[f, r] == v): a 3-D iota compare instead of a
    # repeat — Mosaic lowers the broadcast/compare on the VPU, and the
    # (features, rows) layout keeps the 128-lane dim on rows so the block
    # shape tiles legally on real TPU hardware (rows % 128 == 0).
    v = jax.lax.broadcasted_iota(jnp.int32, (df, b, nc), 1)
    one_hot = (bins[:, None, :] == v).astype(jnp.bfloat16)  # 0/1: exact in bf16
    # bf16-split matmul: the MXU's native pass truncates f32 operands to
    # bf16, which visibly perturbs gradient sums (and split decisions).
    # Stats split as hi + lo bf16 terms recovers ~f32 accuracy in 2 native
    # passes instead of Precision.HIGHEST's 6 (one-hot needs no split).
    hi = stats.astype(jnp.bfloat16)
    lo = (stats - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    both = jnp.concatenate([hi, lo], axis=1)  # (NC, 6)
    acc = jax.lax.dot_general(
        one_hot.reshape(df * b, nc), both,
        dimension_numbers=(((1,), (0,)), ((), ())),  # contract over rows -> (DF*B, 6)
        preferred_element_type=jnp.float32,
    )
    out_ref[:] += acc[:, :3] + acc[:, 3:]


def _hist_split_kernel(bins_ref, stats_ref, out_ref, *, bh: int, bl: int):
    """Decomposed one-hot step: bin = hi * BL + lo.

    The plain kernel's VPU cost is B compares per (row, feature) cell —
    the measured bound at B=256. Decomposing cuts that to
    BH compares (the hi one-hot, the matmul lhs) plus BL*6 compare-selects
    (the rhs: per (lo, stat) column, the row's stat value where its lo
    code matches). The MXU contraction then recovers every (hi, lo) bin
    pair: acc[f, hi, lo*6+j] = sum_r oh_hi * rhs. Measured ~2x the plain
    kernel on real hardware at B=256 (BH=32, BL=8). Output stays PACKED
    (df*BH, BL*6); the caller unpacks outside the kernel where layout is
    free — in-kernel recombination would need minor-dim reshapes Mosaic
    rejects."""
    import jax.experimental.pallas as pl

    row_chunk = pl.program_id(1)

    @pl.when(row_chunk == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:]          # (DF, NC) int32; sentinel -> hi code == BH
    stats = stats_ref[:]        # (NC, 3) f32
    df, nc = bins.shape
    hi_c = bins // bl
    lo_c = bins % bl
    vh = jax.lax.broadcasted_iota(jnp.int32, (df, bh, nc), 1)
    oh_hi = (hi_c[:, None, :] == vh).astype(jnp.bfloat16)
    s_hi = stats.astype(jnp.bfloat16)
    s_lo = (stats - s_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    both = jnp.concatenate([s_hi, s_lo], axis=1).T               # (6, NC)
    # rhs[f, lo*6+j, r] = both[j, r] where lo_c[f, r] == lo else 0
    vl = jax.lax.broadcasted_iota(jnp.int32, (df, bl * 6, nc), 1) // 6
    both_t = jnp.tile(both, (bl, 1))                             # (BL*6, NC)
    rhs = jnp.where(
        lo_c[:, None, :] == vl, both_t[None], 0
    ).astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        oh_hi, rhs,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                            # (DF, BH, BL*6)
    out_ref[:] += acc.reshape(df * bh, bl * 6)


# the decomposed kernel's feature block (bigger blocks amortize the rhs
# build; 32 measured within 2% of the best and halves padding waste)
_DF_SPLIT = 32
_BL_SPLIT = 8


def _use_split(num_bins: int) -> bool:
    """Decomposition pays when B is large (compare-bound); at B <= 64 the
    plain one-hot is already cheap and the split's fixed rhs cost
    (BL*6 = 48 ops/cell) stops being a win."""
    return num_bins >= 128 and num_bins % _BL_SPLIT == 0


def _plane_histogram_pallas(
    bins: jnp.ndarray, stats: jnp.ndarray, num_bins: int = NUM_BINS,
    dev=None, split: "bool | None" = None,
) -> jnp.ndarray:
    """(n, d) int32 bins + (n, 3) stats -> (d * B, 3) plane via Pallas,
    lowered for ``dev`` (default: the process's default device).
    ``split``: the decomposed kernel or the plain one; None, what every
    caller but the kernels' own tests passes, is :func:`_use_split`'s
    choice for ``num_bins``."""
    import jax.experimental.pallas as pl

    call_kw = _pallas_call_kwargs(dev)

    n, d = bins.shape
    b = num_bins
    if split is None:
        split = _use_split(b)
    df = _DF_SPLIT if split else _DF
    d_pad = ((d + df - 1) // df) * df
    n_pad = ((n + _NC - 1) // _NC) * _NC
    # sentinel: any value outside [0, B) matches no one-hot column (its hi
    # code b // BL == BH in the split kernel), so the row contributes
    # nowhere. Used for padded features AND for out-of-range caller bins —
    # the scatter lowering drops those (mode='drop') and the lowerings
    # must agree exactly.
    sentinel = b
    with jax.named_scope("gbdt.hist.pad"):
        bins = jnp.where((bins >= 0) & (bins < b), bins, sentinel)
        if d_pad != d:
            bins = jnp.pad(bins, ((0, 0), (0, d_pad - d)), constant_values=sentinel)
        if n_pad != n:
            bins = jnp.pad(bins, ((0, n_pad - n), (0, 0)), constant_values=sentinel)
            stats = jnp.pad(stats, ((0, n_pad - n), (0, 0)))
        bins_t = bins.T.astype(jnp.int32)
        stats = stats.astype(jnp.float32)

    if split:
        bl = _BL_SPLIT
        bh = b // bl
        packed = pl.pallas_call(
            functools.partial(_hist_split_kernel, bh=bh, bl=bl),
            grid=(d_pad // df, n_pad // _NC),
            in_specs=[
                pl.BlockSpec((df, _NC), lambda f, r: (f, r)),
                pl.BlockSpec((_NC, 3), lambda f, r: (r, 0)),
            ],
            out_specs=pl.BlockSpec((df * bh, bl * 6), lambda f, r: (f, 0)),
            out_shape=jax.ShapeDtypeStruct((d_pad * bh, bl * 6), jnp.float32),
            name="plane_histogram",
            **call_kw,
        )(bins_t, stats)
        un = packed.reshape(d_pad, bh, bl, 6)
        out = (un[..., :3] + un[..., 3:]).reshape(d_pad * b, 3)
        return out[: d * b]

    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_bins=b),
        grid=(d_pad // df, n_pad // _NC),
        in_specs=[
            pl.BlockSpec((df, _NC), lambda f, r: (f, r)),
            pl.BlockSpec((_NC, 3), lambda f, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((df * b, 3), lambda f, r: (f, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad * b, 3), jnp.float32),
        name="plane_histogram",
        **call_kw,
    )(bins_t, stats)
    return out[: d * b]


def _multi_kernel(
    bins_ref, stats_ref, slot_ref, out_ref, *, num_slots: int, num_bins: int
):
    """One (feature-block, row-chunk) step of the multi-leaf build: the
    bin one-hot is built ONCE and contracted against slot-masked stats
    columns, producing every leaf's plane stripe in a single wide matmul
    (rhs column s*6+j = [slot==s] * stats_hi/lo[j])."""
    import jax.experimental.pallas as pl

    row_chunk = pl.program_id(1)

    @pl.when(row_chunk == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:]          # (DF, NC) int32
    stats = stats_ref[:]        # (NC, 3) f32
    slot = slot_ref[:]          # (1, NC) int32; out-of-range = no plane
    df, nc = bins.shape
    b = num_bins
    v = jax.lax.broadcasted_iota(jnp.int32, (df, b, nc), 1)
    one_hot = (bins[:, None, :] == v).astype(jnp.bfloat16)
    s_hi = stats.astype(jnp.bfloat16).astype(jnp.float32)
    s_lo = stats - s_hi
    both = jnp.concatenate([s_hi, s_lo], axis=1)                  # (NC, 6)
    w = num_slots * 6
    both_wide = jnp.concatenate([both] * num_slots, axis=1)       # (NC, S*6)
    s_iota = jax.lax.broadcasted_iota(jnp.int32, (nc, w), 1) // 6
    slot_match = (slot[0][:, None] == s_iota).astype(jnp.float32)
    rhs = (slot_match * both_wide).astype(jnp.bfloat16)           # (NC, S*6)
    out_ref[:] += jax.lax.dot_general(
        one_hot.reshape(df * b, nc), rhs,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _multi_resident_bytes(df: int, num_slots: int, num_bins: int) -> int:
    """Estimated VMEM-resident set of one multi-kernel grid step: the
    bf16 one-hot block (DF*B*NC) plus the packed f32 accumulator and
    its dot_general result (2 x DF*B*S*6) — these dominate; row-chunk
    inputs and the slot-mask rhs are < 1 MB."""
    return df * num_bins * (_NC * 2 + num_slots * 6 * 4 * 2)


def _multi_df(
    num_slots: int, num_bins: int, d: int = 1 << 30, dev=None
) -> int | None:
    """Feature block for the multi-plane kernel: as large as the
    kernel's VMEM-resident set allows (bigger blocks amortize the
    slot-mask rhs; measured +11% at S=32), but never wider than the
    feature count needs (padding a d=4 input to a 32-wide block would
    4x the one-hot work on sentinel rows).

    The budget is 2/3 of the Mosaic ceiling :func:`_pallas_call_kwargs`
    sets for ``dev`` (same env knob), leaving headroom for
    double-buffered input DMA and Mosaic's own scratch. Returns ``None``
    when not even the smallest block fits — the caller must use the
    scatter lowering (e.g. thousands of slots at 256 bins)."""
    budget = _hist_vmem_mb(dev) * 2 // 3 << 20
    d_need = max(8, ((d + 7) // 8) * 8)
    best = None
    for df in sorted({32, 16, 8, _DF}, reverse=True):
        if _multi_resident_bytes(df, num_slots, num_bins) > budget:
            continue
        # compare resulting PADDED widths: a wider block that pads to the
        # same width does the same one-hot work in fewer grid steps (fewer
        # slot-mask rebuilds), so prefer it
        pad_w = ((d_need + df - 1) // df) * df
        if best is None or pad_w < best[0] or (pad_w == best[0] and df > best[1]):
            best = (pad_w, df)
    return best[1] if best else None


def _multi_plane_pallas(
    bins: jnp.ndarray, stats: jnp.ndarray, slot: jnp.ndarray, num_slots: int,
    num_bins: int = NUM_BINS, df: int | None = None, dev=None,
) -> jnp.ndarray:
    import jax.experimental.pallas as pl

    n, d = bins.shape
    b = num_bins
    _df_m = df if df is not None else _multi_df(num_slots, b, d, dev)
    if _df_m is None:
        raise ValueError("no feature block fits VMEM; use the scatter lowering")
    d_pad = ((d + _df_m - 1) // _df_m) * _df_m
    n_pad = ((n + _NC - 1) // _NC) * _NC
    sentinel = b
    with jax.named_scope("gbdt.hist.pad"):
        bins = jnp.where((bins >= 0) & (bins < b), bins, sentinel)
        if d_pad != d:
            bins = jnp.pad(bins, ((0, 0), (0, d_pad - d)), constant_values=sentinel)
        if n_pad != n:
            bins = jnp.pad(bins, ((0, n_pad - n), (0, 0)), constant_values=sentinel)
            stats = jnp.pad(stats, ((0, n_pad - n), (0, 0)))
            slot = jnp.pad(slot, (0, n_pad - n), constant_values=num_slots)
        bins_t = bins.T.astype(jnp.int32)
        stats = stats.astype(jnp.float32)
        slot = slot.astype(jnp.int32)[None, :]
    packed = pl.pallas_call(
        functools.partial(_multi_kernel, num_slots=num_slots, num_bins=b),
        grid=(d_pad // _df_m, n_pad // _NC),
        in_specs=[
            pl.BlockSpec((_df_m, _NC), lambda f, r: (f, r)),
            pl.BlockSpec((_NC, 3), lambda f, r: (r, 0)),
            pl.BlockSpec((1, _NC), lambda f, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((_df_m * b, num_slots * 6), lambda f, r: (f, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad * b, num_slots * 6), jnp.float32),
        name="multi_plane_histogram",
        **_pallas_call_kwargs(dev),
    )(bins_t, stats, slot)
    # (f*B+v, s*6+j) -> (s, f*B+v, j), summing hi/lo halves
    un = packed.reshape(d_pad * b, num_slots, 6)
    out = jnp.transpose(un[..., :3] + un[..., 3:], (1, 0, 2))
    return out[:, : d * b]


def _multi_plane_scatter(
    bins: jnp.ndarray, stats: jnp.ndarray, slot: jnp.ndarray, num_slots: int,
    num_bins: int = NUM_BINS,
) -> jnp.ndarray:
    n, d = bins.shape
    b = num_bins
    plane_idx = (jnp.arange(d, dtype=jnp.int32) * b)[None, :] + bins   # (n, d)
    flat = slot[:, None] * (d * b) + plane_idx
    oob = (
        (bins < 0) | (bins >= b) | (slot[:, None] < 0) | (slot[:, None] >= num_slots)
    )
    flat = jnp.where(oob, num_slots * d * b, flat)
    contrib = jnp.broadcast_to(stats[:, None, :], (n, d, 3))
    out = (
        jnp.zeros((num_slots * d * b, 3), jnp.float32)
        .at[flat]
        .add(contrib, mode="drop")
    )
    return out.reshape(num_slots, d * b, 3)


def multi_plane_histogram(
    bins: jnp.ndarray,
    stats: jnp.ndarray,
    slot: jnp.ndarray,
    num_slots: int,
    num_bins: int = NUM_BINS,
    mesh=None,
    shard_axis: str | None = None,
    bins_in_range: bool = False,
) -> jnp.ndarray:
    """Histogram planes for MANY leaves in one pass over the rows.

    ``slot``: (n,) int leaf-plane index per row; out-of-range = the row
    contributes to no plane. Returns (num_slots, d*NUM_BINS, 3). This is
    the depthwise grower's workhorse: one row pass per LEVEL instead of
    one per leaf, with the bin one-hot (the VPU-bound part) amortized
    across all the level's leaves. ``mesh``/``shard_axis`` as in
    :func:`plane_histogram` (per-shard kernel + psum of the cube).

    When the slot count is so large that no feature block fits the
    kernel's VMEM budget (thousands of planes at 256 bins — see
    :func:`_multi_df`), the scatter lowering is used whatever the
    target: slower, but it compiles instead of tripping Mosaic's
    scoped-VMEM ceiling. Like every choice here it is counted in
    ``mmlspark_gbdt_hist_lowerings_total``."""
    dev = _target_device(mesh)
    df_fit = _multi_df(num_slots, num_bins, bins.shape[1], dev)
    use_pl = df_fit is not None and use_pallas(mesh)
    bins = _widened(bins)
    slot = slot.astype(jnp.int32)
    if _rows_sharded(mesh, shard_axis):
        from jax.sharding import PartitionSpec as P

        _count_lowering("multi_plane", "pallas" if use_pl else "scatter")

        def local(b, s, sl):
            if use_pl:
                cube = _multi_plane_pallas(
                    b, s, sl, num_slots, num_bins, df=df_fit, dev=dev
                )
            else:
                # per-shard scatter partials + the same explicit allreduce
                # (LightGBM data_parallel with the MXU kernel swapped out)
                cube = _multi_plane_scatter(b, s, sl, num_slots, num_bins)
            return jax.lax.psum(cube, shard_axis)

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(shard_axis, None), P(shard_axis, None), P(shard_axis)),
            out_specs=P(),
            check_vma=False,
        )(bins, stats, slot)
    if use_pl:
        _count_lowering("multi_plane", "pallas")
        return _multi_plane_pallas(
            bins, stats, slot, num_slots, num_bins, df=df_fit, dev=dev
        )
    if use_host_hist(mesh):
        _count_lowering("multi_plane", "cpu")
        return _multi_plane_host(
            bins, stats, slot, num_slots, num_bins,
            assume_in_range=bins_in_range,
        )
    _count_lowering("multi_plane", "scatter")
    return _multi_plane_scatter(bins, stats, slot, num_slots, num_bins)


def leaf_stat_sums(
    leaf: jnp.ndarray, stats: jnp.ndarray, num_leaves: int,
    mesh=None, shard_axis: str | None = None,
) -> jnp.ndarray:
    """Per-leaf (g, h, count) totals: (n,) leaf ids + (n, 3) stats ->
    (num_leaves, 3). The growers' end-of-tree reduction — a (n,)
    scatter-add on the XLA path, one bincount pass on the host path (the
    scatters cost ~3 ms/tree at bench shapes on XLA:CPU, ~25x the host
    kernel). ``mesh``/``shard_axis`` as in :func:`plane_histogram`: rows
    sharded over the mesh keep the scatter (GSPMD partitions this (n,)
    scatter; a host callback would force a gather)."""
    if not _rows_sharded(mesh, shard_axis) and use_host_hist(mesh):
        # leaf ids are grower outputs, always in [0, num_leaves)
        return _plane_histogram_host(
            leaf[:, None].astype(jnp.int32), stats, None, num_leaves,
            assume_in_range=True,
        )
    z = jnp.zeros((num_leaves, 3), jnp.float32)
    return z.at[leaf].add(stats)


def _plane_histogram_scatter(
    bins: jnp.ndarray, stats: jnp.ndarray, num_bins: int = NUM_BINS
) -> jnp.ndarray:
    n, d = bins.shape
    b = num_bins
    plane_idx = (jnp.arange(d, dtype=jnp.int32) * b)[None, :] + bins  # (n, d)
    # out-of-range bins contribute nowhere (a negative bin would otherwise
    # alias into the previous feature's stripe; matches the Pallas lowering)
    plane_idx = jnp.where((bins >= 0) & (bins < b), plane_idx, d * b)
    contrib = jnp.broadcast_to(stats[:, None, :], (n, d, 3))
    return (
        jnp.zeros((d * b, 3), jnp.float32).at[plane_idx].add(contrib, mode="drop")
    )


def _plane_histogram_shard_map(
    bins: jnp.ndarray, stats: jnp.ndarray, mesh, shard_axis: str,
    num_bins: int,
) -> jnp.ndarray:
    """Per-shard kernel + explicit psum of the planes — LightGBM
    data_parallel's per-iteration histogram allreduce over ICI
    (TrainUtils.scala:496-512). On a TPU mesh the local kernel is the
    Pallas MXU one-hot; with Pallas off (CPU meshes) the local kernel is
    the XLA scatter — either way the allreduce is an explicit ``psum`` in
    the program, not a GSPMD inference."""
    from jax.sharding import PartitionSpec as P

    use_pl = use_pallas(mesh)
    dev = _target_device(mesh)
    _count_lowering("plane", "pallas" if use_pl else "scatter")

    def local(b: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
        if use_pl:
            h = _plane_histogram_pallas(_widened(b), s, num_bins, dev)
        else:
            h = _plane_histogram_scatter(_widened(b), s, num_bins)
        return jax.lax.psum(h, shard_axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(shard_axis, None), P(shard_axis, None)),
        out_specs=P(),
        check_vma=False,
    )(bins, stats)


def _masked(stats: jnp.ndarray, mask: "jnp.ndarray | None") -> jnp.ndarray:
    """The stats of the selected rows: a full f32 pass over the rows per
    call, named so the device trace can tell it from the kernel."""
    if mask is None:
        return stats
    with jax.named_scope("gbdt.hist.mask"):
        return stats * mask[:, None]


def _widened(bins: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("gbdt.hist.widen"):
        return bins.astype(jnp.int32)


def plane_histogram(
    bins: jnp.ndarray, stats: jnp.ndarray, mask: jnp.ndarray | None = None,
    num_bins: int = NUM_BINS, mesh=None, shard_axis: str | None = None,
    allow_host: bool = True, bins_in_range: bool = False,
) -> jnp.ndarray:
    """(d * NUM_BINS, 3) gradient-histogram plane of the masked rows.

    ``bins``: (n, d) int bin codes; ``stats``: (n, 3) per-row (g, h, count);
    ``mask``: optional (n,) row selector (0 rows contribute nothing).
    ``mesh``/``shard_axis``: when the rows are sharded over that mesh axis,
    run the local kernel (Pallas on TPU, scatter otherwise) per shard
    under shard_map and psum the planes. With no mesh the call is a
    one-device call lowered for the process's default device.
    ``allow_host=False`` keeps a CPU call off the host callback (a caller
    already inside ``shard_map``: a callback per shard would serialize the
    shards on the GIL).
    """
    if _rows_sharded(mesh, shard_axis):
        stats = _masked(stats, mask)
        return _plane_histogram_shard_map(
            bins, stats, mesh, shard_axis, num_bins
        )
    if use_pallas(mesh):
        _count_lowering("plane", "pallas")
        stats = _masked(stats, mask)
        return _plane_histogram_pallas(
            _widened(bins), stats, num_bins, _target_device(mesh)
        )
    if allow_host and use_host_hist(mesh):
        _count_lowering("plane", "cpu")
        # the host kernel takes the RAW mask: sparse selections compact
        # to the selected rows instead of scanning zeroed stats
        return _plane_histogram_host(
            _widened(bins), stats, mask, num_bins,
            assume_in_range=bins_in_range,
        )
    _count_lowering("plane", "scatter")
    stats = _masked(stats, mask)
    return _plane_histogram_scatter(_widened(bins), stats, num_bins)
