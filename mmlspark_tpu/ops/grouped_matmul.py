"""Grouped matmul over rows sorted by group, as a Pallas kernel (TPU).

``x`` (m, k) holds its rows sorted by group: group ``g`` owns rows
``offsets[g]:offsets[g + 1]`` and is multiplied by its own matrix ``w[g]``
(k, n) — the experts' products of a sparse expert layer
(:func:`mmlspark_tpu.ops.moe.expert_ffn`), what ``jax.lax.ragged_dot``
computes. Rows past the last group belong to nobody: the kernel leaves them
undefined and the caller masks them.

The rows are cut into tiles of ``tm``. A group's edge rarely falls on a
tile's, so the unit of work is a *visit*: one (group, row tile) pair whose
rows overlap. A tile that two groups share is visited once for each; a visit
multiplies the whole tile by its group's matrix and stores the rows that are
the group's (the tile's output block stays in VMEM from one visit to the
next, so the visits of a tile merge there). The visits, in order, are the
kernel's grid; which group and which tile a visit names is computed once a
layer by XLA operations (:func:`group_visits`) and handed to every call of
the layer as scalars. The whole contraction is one block: bfloat16 operands,
one float32 product in VMEM, one rounding at the store.

Two calls, one kernel body, both named ``expert_gmm`` in a device trace:
:func:`grouped_matmul` (``x @ w[g]``) and :func:`gated_up`
(``silu(x @ w1[g]) * (x @ w3[g])``: the row tile is read once and the gate
is taken from the two float32 products, so neither product ever exists in
HBM).

:func:`tiling` is the one rule that says how a layer's two calls are tiled,
from their shapes alone, or that they are not (then the caller keeps
``ragged_dot``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# rows a tile. On the v5e, a layer's two calls at the whole width (PERF.md
# section 6, PR 32): 32 groups of 4,096 rows 16.5 ms at 256, 17.1 at 512, 20.4
# at 1,024; 128 groups of 2,048 rows 15.6 / 16.6 / 19.7 — a larger tile is
# multiplied once more for every group edge inside it and gains nothing back
ROW_TILE = 256


def _column_tile(tm: int, k: int, n: int, weights: int, vmem_bytes: int) -> Optional[int]:
    """The widest block of columns, a multiple of the 128 lanes that divides
    ``n``, whose call fits two thirds of ``vmem_bytes``: two buffers each of
    the row tile, of ``weights`` blocks of a group's matrix and of the result
    in bfloat16, and the float32 products with the gate's temporary. Wider
    is faster (the row tile is read once for every block of columns)."""
    for tn in range(n, 0, -128):
        blocks = 2 * 2 * (tm * k + weights * k * tn + tm * tn)
        if n % tn == 0 and blocks + 4 * (weights + 1) * tm * tn <= vmem_bytes * 2 // 3:
            return tn
    return None


def tiling(rows: int, h: int, f: int, vmem_bytes: int) -> Optional[tuple]:
    """How an expert layer's two calls are tiled — ``(rows a tile, columns a
    block of the up-call (h -> f, two matrices), of the down-call (f -> h))``
    — from the shapes alone, or None where the kernel does not apply: widths
    that are no multiple of the 128 lanes, rows that are no multiple of the
    tile."""
    if rows % ROW_TILE or h % 128 or f % 128:
        return None
    up = _column_tile(ROW_TILE, h, f, 2, vmem_bytes)
    down = _column_tile(ROW_TILE, f, h, 1, vmem_bytes)
    return None if up is None or down is None else (ROW_TILE, up, down)


class Visits(NamedTuple):
    """A layer's group metadata for row tiles of ``tm``."""
    tm: int
    offsets: jnp.ndarray   # (G + 1,) int32: group g owns rows offsets[g]:offsets[g + 1]
    group: jnp.ndarray     # (V,) int32: the group of visit v
    tile: jnp.ndarray      # (V,) int32: its row tile
    count: jnp.ndarray     # (1,) int32: the visits that do work, the leading ones

    def tiles(self) -> jnp.ndarray:
        """(2,) int32 ``[visited, aligned]``: the visits made, and the row
        tiles that hold any group's row — the visits there would be if every
        group ended on a tile's edge."""
        return jnp.stack([self.count[0], -(-self.offsets[-1] // self.tm)])


def group_visits(sizes: jnp.ndarray, rows: int, tm: int) -> Visits:
    """(G,) int32 group sizes over ``rows`` sorted rows -> the visits of row
    tiles of ``tm``: at most ``rows / tm + G - 1`` (every tile once and every
    inner group edge once more); the unused trailing ones repeat the last
    real visit, so that they fetch nothing, and do no work."""
    if rows % tm:
        raise ValueError(f"{rows} rows are no multiple of the row tile {tm}")
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    first = starts // tm
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(per_group)
    count = visit_end[-1]
    v = jnp.minimum(jnp.arange(rows // tm + groups - 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(visit_end, v, side="right").astype(jnp.int32),
                        groups - 1)
    tile = first[group] + v - (visit_end - per_group)[group]
    # no group holds a row: the one idle visit still names a tile that exists
    tile = jnp.clip(tile, 0, rows // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return Visits(tm, offsets, group, tile, count.reshape(1))


def _gmm_kernel(offsets_ref, group_ref, tile_ref, count_ref, x_ref, *refs, tm: int):
    *w_refs, o_ref = refs
    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _():
        g, lo = group_ref[v], tile_ref[v] * tm
        start, end = offsets_ref[g], offsets_ref[g + 1]
        x = x_ref[...]
        y = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
        if len(w_refs) == 2:  # the gate, from the float32 products
            y = jax.nn.silu(y) * jnp.dot(x, w_refs[1][...], preferred_element_type=jnp.float32)
        row = lo + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        # a plain store for the tiles one group owns whole, under a branch,
        # read 1-2% slower on the v5e than this select on every visit
        o_ref[...] = jnp.where((row >= start) & (row < end), y.astype(o_ref.dtype), o_ref[...])


def _call(x: jnp.ndarray, ws: tuple, visits: Visits, tn: int, **call: object) -> jnp.ndarray:
    (rows, k), n, tm = x.shape, ws[0].shape[2], visits.tm
    if n % tn:
        raise ValueError(f"a width of {n} is no multiple of the column tile {tn}")
    w_spec = pl.BlockSpec((None, k, tn), lambda j, v, offs, group, tile, count: (group[v], 0, j))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(n // tn, visits.group.shape[0]),
        in_specs=[pl.BlockSpec((tm, k), lambda j, v, offs, group, tile, count: (tile[v], 0)),
                  *[w_spec] * len(ws)],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, offs, group, tile, count: (tile[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm), grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n * len(ws), transcendentals=rows * n * (len(ws) - 1),
            bytes_accessed=x.dtype.itemsize * (rows * k * (n // tn) + rows * n)
            + sum(w.size * w.dtype.itemsize for w in ws)),
        name="expert_gmm", **call,
    )(visits.offsets, visits.group, visits.tile, visits.count, x, *ws)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, visits: Visits, tn: int,
                   **call: object) -> jnp.ndarray:
    """``out[r] = x[r] @ w[g]`` for every row ``r`` of group ``g``. ``x``
    (m, k), ``w`` (G, k, n) -> (m, n) in ``x``'s dtype, ``tn`` columns a
    block."""
    return _call(x, (w,), visits, tn, **call)


def gated_up(x: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray, visits: Visits, tn: int,
             **call: object) -> jnp.ndarray:
    """``out[r] = silu(x[r] @ w1[g]) * (x[r] @ w3[g])``, the gate taken in
    float32. ``x`` (m, k), ``w1`` / ``w3`` (G, k, f) -> (m, f)."""
    return _call(x, (w1, w3), visits, tn, **call)


@functools.partial(jax.jit, static_argnames=("tiling", "call"))
def expert_products(x: jnp.ndarray, sizes: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                    w2: jnp.ndarray, *, tiling: tuple, call: tuple) -> tuple:
    """An expert layer's products over its sorted rows: ``(silu(x @ w1[g]) *
    (x @ w3[g])) @ w2[g]`` and the ``[visited, aligned]`` tiles, by
    ``tiling = (tm, tn_up, tn_down)`` (:func:`tiling`); ``call`` holds
    ``pallas_call``'s ``interpret`` / ``compiler_params`` as pairs. A function
    of its own under ``jit``: the layers of a model whose shapes agree are one
    trace and one lowering of the kernels, not one each (a program of twelve
    expert layers otherwise spends a second more in tracing and lowering)."""
    tm, tn_up, tn_down = tiling
    kw = dict(call)
    visits = group_visits(sizes, x.shape[0], tm)
    act = gated_up(x, w1, w3, visits, tn_up, **kw)
    return grouped_matmul(act, w2, visits, tn_down, **kw), visits.tiles()
