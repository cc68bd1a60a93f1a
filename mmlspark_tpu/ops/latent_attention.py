"""Latent attention's pairs: causal softmax attention whose score is a sum
of two products — a head's own ``q_n . k_n`` and a rotated ``q_r . k_r``
whose key part is one vector a token, shared by every head — and whose
values are narrower than its scores (DeepSeek-V2's multi-head latent
attention, in the form its prefill uses: keys and values expanded from the
latent, per head):

    s_i[t, s] = q_n,i[t] . k_n,i[s] + q_r,i[t] . k_r[s]          s <= t
    o_i[t]    = sum_s softmax_s(s_i[t, .]) v_i[s]

The queries come scaled (the softmax scale is folded into them once, where
they are made). On a TPU the pairs are a Pallas kernel (:func:`attend_kernel`,
``latent_attend`` in a device trace): flash attention over the causal tiles
alone — the (query tile, key tile) pairs at or under the diagonal are the
kernel's grid, named by two small tables, so no step is spent on a tile
above it — with ``q_n`` / ``k_n``, ``q_r`` and the shared ``k_r`` as separate
operands, so that no ``heads x (d_n + d_r)`` key is ever written; only a
tile the diagonal crosses is masked; a tile whose queries are all right
padding is neither fetched nor computed (its output is 0). Elsewhere, and
for what the kernel is tested against, plain XLA operations a block of
queries at a time in a device loop (:func:`attend_xla`), never a ``heads x
L x L`` array. ``ops.histogram.use_pallas`` decides, as for the other kernels.

Also the rotation's frequencies (:func:`yarn_frequencies`: YaRN's ramp
between kept and interpolated frequencies, static, at every length) and the
two factors YaRN puts on the rotation and on the softmax scale.

Layouts, the kernel's own: ``q_n`` / ``k_n`` (B, H, L, d_n), ``q_r`` (B, H,
L, d_r), ``k_r`` (B, L, d_r), ``v`` (B, H, L, d_v) -> (B, H, L, d_v).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import histogram
from mmlspark_tpu.ops.sparse_attention import key_spans

# queries and keys a tile of the kernel, and the heads a grid step holds. On
# the v5e, one layer's pairs of one row of 16,384 / eight rows of 2,048 (PERF.md
# section 6, PR 33): 94.2 / 19.4 ms here, 92.0 / 19.1 at 1,024 x 1,024 x 2 heads,
# 112.2 / 19.8 at 512 x 512 x 4, 93.7 / 23.6 at 512 x 2,048 x 4, 128.0 / 25.3 at
# 1,024 x 512 x 4; the narrower query tile skips more of a partly padded row
Q_TILE = 512
KEY_TILE = 1024
HEADS = 4
# the XLA form's score tensor, batch x heads x block x keys in float32, stays
# under this many bytes: the block of queries follows from it
SCORE_BYTES = 1 << 29
_NEG = -1e30


# -- YaRN ------------------------------------------------------------------------

def yarn_frequencies(dim: int, theta: float, scaling: Optional[dict]) -> np.ndarray:
    """(dim / 2,) float64 rotation frequencies of a ``dim``-wide rotated
    part: ``theta ** (-2j / dim)``, and under ``rope_scaling`` of type
    ``yarn`` a ramp from that (dimensions that turn more than ``beta_fast``
    times over the original context) to that over ``factor`` (fewer than
    ``beta_slow`` times)."""
    j = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * j / dim)
    if not scaling or scaling.get("type", scaling.get("rope_type")) != "yarn":
        return freq
    original = scaling["original_max_position_embeddings"]

    def turns(beta: float) -> float:
        return dim * math.log(original / (2 * math.pi * beta)) / (2 * math.log(theta))

    lo = max(math.floor(turns(scaling["beta_fast"])), 0)
    hi = min(math.ceil(turns(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / scaling["factor"] * ramp


def yarn_mscale(scaling: Optional[dict], key: str) -> float:
    """``0.1 * scaling[key] * ln(factor) + 1`` (1 without YaRN or the key)."""
    if not scaling or scaling.get("factor", 1.0) <= 1.0 or not scaling.get(key):
        return 1.0
    return 0.1 * scaling[key] * math.log(scaling["factor"]) + 1.0


def softmax_scale(score_dim: int, scaling: Optional[dict]) -> float:
    """``score_dim ** -0.5``, times YaRN's ``mscale_all_dim`` factor squared."""
    return score_dim ** -0.5 * yarn_mscale(scaling, "mscale_all_dim") ** 2


def rotate_halves(x: jnp.ndarray, freqs: np.ndarray, gain: float = 1.0) -> jnp.ndarray:
    """Rotate (..., L, d) float32 by position, positions from 0: dimension
    ``j`` of the first half pairs with ``j`` of the second, both at
    ``freqs[j]``; cos and sin times ``gain``. The published rotation pairs
    ``(2j, 2j + 1)``; the caller brings the pairs' first members into the
    first half (a permutation of both sides' dimensions changes no score)."""
    length, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * gain
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * gain
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


# -- the pairs -------------------------------------------------------------------

def _tile(length: int, cap: int) -> int:
    """The largest power of two of at most ``cap`` that divides ``length``
    and fills the 128 lanes; or the whole length."""
    tile = cap
    while tile >= 128 and length % tile:
        tile //= 2
    return tile if 128 <= tile <= length else length


def query_block(rows: int, heads: int, length: int) -> int:
    """Queries a block of the XLA form: the largest power of two whose
    float32 scores against all the keys fit ``SCORE_BYTES`` (at least 8)."""
    block = max(8, 1 << max(0, (SCORE_BYTES // (4 * rows * heads * length)).bit_length() - 1))
    while length % block:
        block //= 2
    return min(block, length)


def attend_xla(qn: jnp.ndarray, qr: jnp.ndarray, kn: jnp.ndarray, kr: jnp.ndarray,
               v: jnp.ndarray, q_block: Optional[int] = None) -> jnp.ndarray:
    """A block of queries against the keys up to its run's end, in a device
    loop; the rows' blocks in at most ``KEY_SPANS`` runs, each with its own
    key extent, so that the work is 5/8 of the square."""
    rows, heads, length, _ = qn.shape
    qb = q_block or query_block(rows, heads, length)
    if length % qb:
        raise ValueError(f"row length {length} is no multiple of the query block {qb}")

    def block(lo: jnp.ndarray, hi: int) -> jnp.ndarray:
        def cut(x: jnp.ndarray) -> jnp.ndarray:
            return jax.lax.dynamic_slice_in_dim(x, lo, qb, axis=2)

        s = jnp.einsum("bhqd,bhkd->bhqk", cut(qn), kn[:, :, :hi],
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhqd,bkd->bhqk", cut(qr), kr[:, :hi],
                           preferred_element_type=jnp.float32)
        seen = (lo + jnp.arange(qb))[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v[:, :, :hi],
                          preferred_element_type=jnp.float32).astype(v.dtype)

    outs = [jax.lax.map(lambda i, hi=end * qb: block(i * qb, hi), jnp.arange(first, end))
            for first, end in key_spans(length // qb)]
    out = jnp.concatenate(outs, axis=0)                  # (blocks, B, H, qb, d_v)
    return jnp.moveaxis(out, 0, 2).reshape(rows, heads, length, -1)


def causal_tiles(length: int, tq: int, tk: int) -> tuple:
    """The (query tile, key tile) pairs at or under the diagonal, a query
    tile's in a run from key tile 0 up: two (P,) int32 tables."""
    pairs = [(i, t) for i in range(length // tq) for t in range(((i + 1) * tq - 1) // tk + 1)]
    return tuple(np.array(x, np.int32) for x in zip(*pairs))


def _attend_kernel(qi_ref, ki_ref, len_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, tq: int, tk: int):
    p = pl.program_id(2)
    i, t = qi_ref[p], ki_ref[p]
    live = i * tq < len_ref[pl.program_id(0)]     # some query of the tile is no padding

    @pl.when(t == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(crossed: bool) -> None:
        kr = kr_ref[...]
        if crossed:
            seen = (i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
                    >= t * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1))
        for g in range(qn_ref.shape[0]):
            s = jax.lax.dot_general(qn_ref[g], kn_ref[g], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(qr_ref[g], kr, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
            if crossed:
                s = jnp.where(seen, s, _NEG)
            m_old = m_ref[g]
            m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
            e = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_ref[g] = alpha * l_ref[g] + e.sum(-1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                e.astype(v_ref.dtype), v_ref[g], preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    crossed = (t + 1) * tk - 1 > i * tq            # the diagonal passes through the tile
    pl.when(live & crossed)(lambda: tile(True))
    pl.when(live & jnp.logical_not(crossed))(lambda: tile(False))

    @pl.when(t == ((i + 1) * tq - 1) // tk)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def attend_kernel(qn: jnp.ndarray, qr: jnp.ndarray, kn: jnp.ndarray, kr: jnp.ndarray,
                  v: jnp.ndarray, lengths: jnp.ndarray, *, tiles: tuple = (Q_TILE, KEY_TILE),
                  heads: int = HEADS, **call: object) -> jnp.ndarray:
    """Flash attention over the causal tiles: per (row, group of ``heads``
    heads) the query tile stays in VMEM with its running maximum, sum and
    result while its key tiles pass, the shared ``k_r`` tile read once for
    the group. ``lengths`` (B,) int32: a row's real positions."""
    rows, nheads, length, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    tq, tk = _tile(length, tiles[0]), _tile(length, tiles[1])
    group = heads if nheads % heads == 0 else 1
    qi, ki = causal_tiles(length, tq, tk)

    def keys(b, p, qi, ki, lens):
        # a padded query tile names key tile 0 every step: nothing is fetched
        return jnp.where(qi[p] * tq < lens[b], ki[p], 0)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(rows, nheads // group, len(qi)),
        in_specs=[
            pl.BlockSpec((None, group, tq, dn), lambda b, h, p, qi, ki, n: (b, h, qi[p], 0)),
            pl.BlockSpec((None, group, tq, dr), lambda b, h, p, qi, ki, n: (b, h, qi[p], 0)),
            pl.BlockSpec((None, group, tk, dn),
                         lambda b, h, p, qi, ki, n: (b, h, keys(b, p, qi, ki, n), 0)),
            pl.BlockSpec((None, tk, dr), lambda b, h, p, qi, ki, n: (b, keys(b, p, qi, ki, n), 0)),
            pl.BlockSpec((None, group, tk, dv),
                         lambda b, h, p, qi, ki, n: (b, h, keys(b, p, qi, ki, n), 0)),
        ],
        out_specs=pl.BlockSpec((None, group, tq, dv), lambda b, h, p, qi, ki, n: (b, h, qi[p], 0)),
        scratch_shapes=[pltpu.VMEM((group, tq, 1), jnp.float32),
                        pltpu.VMEM((group, tq, 1), jnp.float32),
                        pltpu.VMEM((group, tq, dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_attend_kernel, tq=tq, tk=tk), grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((rows, nheads, length, dv), v.dtype),
        name="latent_attend", **call,
    )(jnp.asarray(qi), jnp.asarray(ki), lengths.astype(jnp.int32), qn, qr, kn, kr, v)


def attend(qn: jnp.ndarray, qr: jnp.ndarray, kn: jnp.ndarray, kr: jnp.ndarray,
           v: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Causal attention over latent attention's operands (the module's
    layouts; the queries scaled) -> (B, H, L, d_v). Positions from a row's
    length on are padding: what they get is undefined."""
    with jax.named_scope("lm.attn.pairs"):
        if histogram.use_pallas():
            return attend_kernel(qn, qr, kn, kr, v, lengths,
                                 **histogram._pallas_call_kwargs())
        return attend_xla(qn, qr, kn, kr, v)
