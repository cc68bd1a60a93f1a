"""Learned sparse attention: index, select, attend over the selection.

A light indexer scores every causal (query, key) pair of a row; each query
keeps the ``topk`` keys of largest score (all of them while it has no more
than ``topk``) and softmax attention runs over that selection alone, shared
by all heads (DeepSeek-V3.2-Exp's sparse attention, eq. 1):

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t
    S_t     = the topk keys s <= t of largest I[t, s]
    o[t]    = sum_{s in S_t} softmax_{s in S_t}(q[t] . k[s] / sqrt(d)) v[s]

A block of queries at a time in a device loop (``jax.lax.map``): nothing is
ever ``L x L``. The rows' blocks are cut into at most ``KEY_SPANS`` runs,
each a loop of its own over the keys up to the run's end, so that the work
is 5/8 of the square and not the whole of it.

The attention over the selection is a Pallas kernel on a TPU
(:func:`attend_kernel`: flash attention with the selection as its mask, a
tile of keys at a time, never a ``heads x Q x K`` array) and plain XLA
operations elsewhere, which the kernel is tested against;
``ops.histogram.use_pallas`` decides, as for the histogram kernels. The
index scores and the selection are XLA operations everywhere.

The selection is exact and takes no sort: the float32 scores are mapped to
unsigned integers of the same order and the k-th largest of a query's keys
is built from its top bit down, ``SELECT_BITS`` bits a pass, each pass a
count of the keys at or above a few candidate thresholds
(:func:`kth_largest`); the selection is then a comparison with that
threshold, and of the scores that tie with the k-th the earliest are kept,
as ``jax.lax.top_k`` keeps them. Keys after the query are never selected,
whatever they score, so right padding stays invisible to every real position.

Layouts, the kernel's own: ``q`` (B, nkv, g, L, d); ``k`` / ``v`` (B, nkv,
L, d); the indexer's ``qi`` (B, J, L, di), ``ki`` (B, L, di), ``w`` (B, J,
L); a block cuts ``Q`` of the ``L`` queries.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import histogram

# runs of query blocks that share a key extent (one device loop each)
KEY_SPANS = 4
# bits of the threshold settled by one pass over a block's scores (on the
# v5e a block's 32 passes of one bit take 0.86 ms, 16 of two 1.10, 8 of four 1.93)
SELECT_BITS = 1
# keys a tile of the kernel (a block of 256 queries x 32,768 keys on the v5e:
# 3.09 ms at 256 keys a tile, 2.29 at 512, 1.82 at 1,024, 1.60 at 2,048)
KEY_TILE = 2048
_NEG = -1e30


def _key_tile(keys: int) -> int:
    """The largest tile of at most ``KEY_TILE`` keys, a power of two of at
    least the 128 lanes, that divides ``keys``; or all of them."""
    tile = KEY_TILE
    while tile >= 128 and keys % tile:
        tile //= 2
    return tile if tile >= 128 else keys


def _reach(reach: Optional[jnp.ndarray], keys: int) -> jnp.ndarray:
    """The last key any query of a block may meet, as the kernel's scalar."""
    return jnp.full((1,), keys - 1, jnp.int32) if reach is None else \
        jnp.asarray(reach, jnp.int32).reshape(1)


# -- index scores ------------------------------------------------------------------

def index_scores(qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``I[b, q, k] = sum_j w[b, j, q] * relu(qi[b, j, q] . ki[b, k])``.

    ``qi`` (B, J, Q, di) and ``ki`` (B, K, di) bfloat16, ``w`` (B, J, Q)
    float32 -> (B, Q, K) float32 (products accumulate in float32). Plain XLA
    operations on every platform: the TPU compiler folds the ReLU and the
    weighted sum into the product's epilogue (0.80 ms a block of 256 queries
    x 32,768 keys, where a Pallas kernel that kept the tile in VMEM read
    0.74: PERF.md section 6, PR 31)."""
    with jax.named_scope("lm.attn.index"):
        dots = jnp.einsum("bjqd,bkd->bjqk", qi, ki, preferred_element_type=jnp.float32)
        return (w[..., None] * jax.nn.relu(dots)).sum(1)


# -- the selection -----------------------------------------------------------------

def sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 of the same order (every finite value above 0)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(keys: jnp.ndarray, k: int, bits: int = SELECT_BITS) -> jnp.ndarray:
    """(..., K) uint32 -> (...,) uint32: the largest ``t`` with at least
    ``k`` keys ``>= t`` — the k-th largest key, or 0 where there are fewer
    than ``k`` keys above 0. ``32 / bits`` passes, each counting the keys at
    or above ``2**bits - 1`` candidates (the counts fall as the candidates
    rise, so the number of candidates that still hold ``k`` keys is the next
    ``bits`` bits of the threshold)."""
    if 32 % bits:
        raise ValueError(f"{bits} bits a pass do not divide the 32 of a score")
    steps = jnp.arange(1, 1 << bits, dtype=jnp.uint32)

    def settle(i: jnp.ndarray, thr: jnp.ndarray) -> jnp.ndarray:
        shift = (32 - bits * (i + 1)).astype(jnp.uint32)
        cands = thr[..., None] | (steps << shift)
        held = (keys[..., None, :] >= cands[..., :, None]).sum(-1, dtype=jnp.int32) >= k
        return thr | (held.sum(-1).astype(jnp.uint32) << shift)

    return jax.lax.fori_loop(0, 32 // bits, settle, jnp.zeros(keys.shape[:-1], jnp.uint32))


def select(scores: jnp.ndarray, causal: jnp.ndarray, topk: int) -> jnp.ndarray:
    """(B, Q, K) float32 index scores, (Q, K) bool ``key <= query`` -> (B,
    Q, K) bool: per query its ``topk`` causal keys of largest score, every
    causal key where it has no more than ``topk``."""
    with jax.named_scope("lm.attn.select"):
        keys = jnp.where(causal, sortable(scores), jnp.uint32(0))
        thr = kth_largest(keys, topk)[..., None]
        mask = (keys >= thr) & causal

        def untie(mask: jnp.ndarray) -> jnp.ndarray:
            above = keys > thr
            tie = mask & ~above
            room = topk - above.sum(-1, keepdims=True, dtype=jnp.int32)
            return above | tie & (jnp.cumsum(tie, axis=-1, dtype=jnp.int32) <= room)

        # scores that tie with the k-th (all its heads' products negative: an
        # exact 0) are rare; only a block that holds one pays for the count
        return jax.lax.cond((mask.sum(-1, dtype=jnp.int32) > topk).any(), untie,
                            lambda mask: mask, mask)


# -- attention over the selection ------------------------------------------------------

def attend_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    s = jnp.einsum("bngqd,bnkd->bngqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bngqk,bnkd->bngqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def _attend_kernel(reach_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   tile: int, scale: float):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(t * tile <= reach_ref[0])
    def _():
        keep = mask_ref[...].astype(jnp.int32) != 0          # (Q, tile), all heads alike
        k, v = k_ref[...], v_ref[...]
        for g in range(q_ref.shape[0]):
            s = jax.lax.dot_general(q_ref[g], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _NEG)
            m_old = m_ref[g]
            m_new = jnp.maximum(m_old, s.max(-1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_ref[g] = alpha * l_ref[g] + p.sum(-1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def attend_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray,
                  reach: Optional[jnp.ndarray] = None, **call: object) -> jnp.ndarray:
    """Flash attention with the selection as its mask: per (row, key/value
    head) the group's query heads stay in VMEM with their running maximum,
    sum and result while the keys pass a tile at a time; tiles of keys past
    ``reach`` are neither fetched nor computed."""
    rows, nkv, group, queries, d = q.shape
    keys = k.shape[2]
    tile = _key_tile(keys)

    def upto(t: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
        return jnp.minimum(t, r[0] // tile)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(rows, nkv, keys // tile),
        in_specs=[
            pl.BlockSpec((None, None, group, queries, d), lambda b, n, t, r: (b, n, 0, 0, 0)),
            pl.BlockSpec((None, None, tile, d), lambda b, n, t, r: (b, n, upto(t, r), 0)),
            pl.BlockSpec((None, None, tile, d), lambda b, n, t, r: (b, n, upto(t, r), 0)),
            pl.BlockSpec((None, queries, tile), lambda b, n, t, r: (b, 0, upto(t, r))),
        ],
        out_specs=pl.BlockSpec((None, None, group, queries, d),
                               lambda b, n, t, r: (b, n, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((group, queries, 1), jnp.float32),
                        pltpu.VMEM((group, queries, 1), jnp.float32),
                        pltpu.VMEM((group, queries, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_attend_kernel, tile=tile, scale=d ** -0.5), grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="sparse_attend", **call,
    )(_reach(reach, keys), q, k, v, mask.astype(jnp.int8))


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray,
           reach: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Grouped-query softmax attention over the selected keys. ``q`` (B,
    nkv, g, Q, d), ``k`` / ``v`` (B, nkv, K, d), ``mask`` (B, Q, K) with a
    key at least a query and none after ``reach`` -> (B, nkv, g, Q, d)."""
    with jax.named_scope("lm.attn.sparse"):
        if histogram.use_pallas():
            return attend_kernel(q, k, v, mask, reach, **histogram._pallas_call_kwargs())
        return attend_xla(q, k, v, mask)


def key_spans(blocks: int, spans: int = KEY_SPANS) -> list:
    """``[(first block, end block)]``: the blocks of a row in at most
    ``spans`` runs of about equal length."""
    n = min(spans, blocks)
    ends = [round(blocks * (s + 1) / n) for s in range(n)]
    return list(zip([0] + ends[:-1], ends))


def sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, qi: jnp.ndarray,
                     ki: jnp.ndarray, w: jnp.ndarray, real: jnp.ndarray, topk: int,
                     q_block: int) -> tuple:
    """The whole mechanism over rows of ``L`` positions.

    ``q`` (B, L, nkv, g, d), ``k`` / ``v`` (B, L, nkv, d): the attention's
    own, normed and rotated; ``qi`` (B, L, J, di), ``ki`` (B, L, di), ``w``
    (B, L, J): the indexer's; ``real`` (B, L) bool: the positions that are
    no padding. -> ((B, L, nkv, g, d), (B,) int32: the keys the row's real
    positions attended)."""
    rows, length = q.shape[:2]
    qb = min(q_block, length)
    if length % qb:
        raise ValueError(f"row length {length} is no multiple of the query block {qb}")
    # the kernel's layouts, once a layer
    q, k, v = (jnp.moveaxis(q, 1, 3), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2))
    qi, w = jnp.moveaxis(qi, 1, 2), jnp.moveaxis(w, 1, 2)

    def block(lo: jnp.ndarray, hi: int) -> tuple:
        def cut(x: jnp.ndarray, axis: int) -> jnp.ndarray:
            return jax.lax.dynamic_slice_in_dim(x, lo, qb, axis=axis)

        reach = lo + qb - 1
        causal = (lo + jnp.arange(qb))[:, None] >= jnp.arange(hi)[None, :]
        mask = select(index_scores(cut(qi, 2), ki[:, :hi], cut(w, 2)), causal, topk)
        out = attend(cut(q, 3), k[:, :, :hi], v[:, :, :hi], mask, reach)
        with jax.named_scope("lm.attn.select"):
            kept = (mask & cut(real, 1)[:, :, None]).sum((1, 2), dtype=jnp.int32)
        return out, kept

    outs, kept = [], jnp.zeros((rows,), jnp.int32)
    for first, end in key_spans(length // qb):
        o, n = jax.lax.map(lambda i, hi=end * qb: block(i * qb, hi), jnp.arange(first, end))
        outs.append(o)
        kept = kept + n.sum(0)
    out = jnp.concatenate(outs, axis=0)  # (blocks, B, nkv, g, qb, d)
    out = jnp.moveaxis(out, 0, 3)        # (B, nkv, g, blocks, qb, d)
    return jnp.moveaxis(out.reshape(*out.shape[:3], length, -1), 3, 1), kept
