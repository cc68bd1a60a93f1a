"""A language model's head as a Pallas kernel (TPU): per token the
log-probability of its target, ``log softmax(u E^T)[target]``, with no logit
ever in HBM.

``u`` (T, h) are the normed final states, ``E`` (V, h) the head's matrix
where it lies (the embedding where they are tied, a held slice of the
vocabulary's rows), both bfloat16; ``targets`` (T,) the next tokens' ids.
The grid is (token tile, vocabulary tile), the vocabulary innermost: a token
tile of ``u`` stays in VMEM while ``E`` streams past a tile of ids at a time;
per step the tile's logits ``E_tile u_tile^T`` in float32 — the vocabulary
along the sublanes, the tokens along the lanes, so that the folds over the
vocabulary are elementwise — then the running maximum, the running sum of
``exp(s - m)`` and the target's logit (a compare of the tile's ids with the
targets); the last vocabulary tile stores ``picked - (m + log l)``. The
online form differs from ``logsumexp`` over whole logits by float32 rounding.

A vocabulary that no tile divides (151,936 = 1,187 x 128, 1,187 prime) ends
in a partial tile whose rows past ``V`` are masked before the maximum: the
kernel never asks for a padded matrix. A token tile none of whose positions
has work (right padding) is neither fetched nor multiplied and stores 0.

:func:`tiling` is the one rule that says how a head is tiled, from its shapes
alone, or that the kernel does not apply; :func:`plan` asks it for the device
a call is lowered for (``ops.histogram.use_pallas`` decides, as for the other
kernels). ``head_logprobs`` in a device trace.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import histogram

# tokens and ids a tile, at most. On the v5e, the head alone at 32,768 tokens x
# 2,048 x 151,936 ids (PERF.md section 6, PR 34): 111.4 ms here (93% of the
# peak; the XLA loop 156.1), 111.5 at 1,024 x 1,024, 112.1 at 512 x 1,024,
# 112.4 at 256 x 2,048, 113.9 at 512 x 512; with the last 15% of the tokens
# padding 95.9 here and 97.7 at 1,024 x 1,024: the narrower token tile skips
# more of a partly padded row (rows end on multiples of 256 tokens)
TOKEN_TILE = 512
VOCAB_TILE = 2048
_NEG = -1e30


def _fits(tt: int, tv: int, h: int, vmem_bytes: int) -> bool:
    """Two buffers of each operand's block and the tile's float32 logits with
    their temporaries (six copies: 1,024 x 1,024 at a width of 5,120, which
    four copies would allow, ran a fifth slower on the v5e than 512 x 1,280)
    in two thirds of ``vmem_bytes``."""
    return 2 * 2 * h * (tt + tv) + 6 * 4 * tt * tv <= vmem_bytes * 2 // 3


def tiling(tokens: int, h: int, vocab: int, vmem_bytes: int) -> Optional[tuple]:
    """``(tokens a tile, ids a tile)`` of a head over ``tokens`` positions of
    width ``h`` and ``vocab`` ids, or None where the kernel does not apply: a
    width that is no multiple of the 128 lanes, tokens that no tile of at
    least 128 divides, fewer ids than a tile's 128, a width whose blocks fit
    at no tile. The token tile is the largest power of two up to
    ``TOKEN_TILE`` that divides ``tokens``; the vocabulary tile at most
    ``VOCAB_TILE``: the largest multiple of 128 in the upper half of that
    which divides ``vocab``, else all of it with a partial last tile; the
    vocabulary tile halves, then the token tile, until the blocks fit
    (:func:`_fits`)."""
    if h % 128 or tokens % 128 or vocab < 128:
        return None
    tt = TOKEN_TILE
    while tokens % tt:
        tt //= 2
    while tt >= 128:
        cap = min(VOCAB_TILE, vocab // 128 * 128)
        while cap >= 128:
            tv = next((t for t in range(cap, cap // 2, -128) if vocab % t == 0), cap)
            if _fits(tt, tv, h, vmem_bytes):
                return tt, tv
            cap = cap // 2 // 128 * 128
        tt //= 2
    return None


def plan(tokens: int, h: int, vocab: int) -> Optional[tuple]:
    """:func:`tiling` for the device a call is lowered for; None off a TPU."""
    if not histogram.use_pallas():
        return None
    return tiling(tokens, h, vocab, histogram._hist_vmem_mb() << 20)


def has_next(lengths, length: int, xp=jnp):
    """(rows,) real lengths of rows of ``length`` positions -> (rows, length)
    bool: the positions that have a next token, whose result is read. ``xp``
    is ``jnp`` on the device and ``numpy`` where the host counts the tiles
    the kernel visits, from the same lengths by the same expression."""
    return xp.arange(length)[None, :] < xp.asarray(lengths)[:, None] - 1


def tiles_with_work(work, tt: int, xp=jnp):
    """(T,) bool, the positions whose result anybody reads -> (T / tt,) bool:
    the token tiles that hold one."""
    return xp.reshape(work, (-1, tt)).any(axis=1)


def _head_kernel(live_ref, src_ref, u_ref, e_ref, t_ref, o_ref, m_ref, l_ref, p_ref, *,
                 tv: int, vocab: int):
    i, j = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1
    live = live_ref[i] != 0

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)

    @pl.when(live)
    def _():
        s = jax.lax.dot_general(e_ref[...], u_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)        # (tv, tt)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if vocab % tv:
            # the last tile's rows past the vocabulary hold nothing defined. On
            # every tile: a second copy of the body for the last tile alone ran
            # 152 ms for 112 at 1,024 x 2,048 on the v5e, and the compare is hidden
            s = jnp.where(row < vocab - j * tv, s, _NEG)
        p_ref[...] += jnp.where(row == t_ref[...] - j * tv, s, 0.0).sum(0, keepdims=True)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, s.max(0, keepdims=True))
        l_ref[...] = jnp.exp(m_old - m_new) * l_ref[...] + jnp.exp(s - m_new).sum(0, keepdims=True)
        m_ref[...] = m_new

    @pl.when(live & (j == last))
    def _():
        o_ref[...] = p_ref[...] - (m_ref[...] + jnp.log(l_ref[...]))

    @pl.when(jnp.logical_not(live) & (j == last))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def head_kernel(head: jnp.ndarray, u: jnp.ndarray, targets: jnp.ndarray,
                work: Optional[jnp.ndarray] = None, *, tiles: tuple,
                **call: object) -> jnp.ndarray:
    """``head`` (V, h) and ``u`` (T, h) bfloat16, ``targets`` (T,) int32,
    ``work`` (T,) bool (the positions whose result is read; default all) ->
    (T,) float32 ``log softmax(u head^T)[target]``, 0 in the token tiles
    without work, by ``tiles = (tokens, ids)`` a tile (:func:`tiling`)."""
    (tokens, h), vocab = u.shape, head.shape[0]
    tt, tv = tiles
    if tokens % tt:
        raise ValueError(f"{tokens} tokens are no multiple of the token tile {tt}")
    nt, nv = tokens // tt, -(-vocab // tv)
    live = jnp.ones((nt,), jnp.int32) if work is None else \
        tiles_with_work(work, tt).astype(jnp.int32)
    # a tile without work names the blocks of the last tile with work before
    # it (and the matrix's last block): a skipped step moves no bytes
    src = jax.lax.cummax(jnp.where(live != 0, jnp.arange(nt, dtype=jnp.int32), 0))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((tt, h), lambda i, j, live, src: (src[i], 0)),
            pl.BlockSpec((tv, h), lambda i, j, live, src: (jnp.where(live[i] != 0, j, nv - 1), 0)),
            pl.BlockSpec((1, tt), lambda i, j, live, src: (0, src[i])),
        ],
        out_specs=pl.BlockSpec((1, tt), lambda i, j, live, src: (0, i)),
        scratch_shapes=[pltpu.VMEM((1, tt), jnp.float32)] * 3,
    )
    out = pl.pallas_call(
        functools.partial(_head_kernel, tv=tv, vocab=vocab), grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((1, tokens), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * tokens * h * vocab, transcendentals=tokens * vocab,
            bytes_accessed=2 * (nt * vocab * h + tokens * h) + 8 * tokens),
        name="head_logprobs", **call,
    )(live, src, u, head, targets.astype(jnp.int32).reshape(1, tokens))
    return out.reshape(tokens)


def count_tiles(lengths: np.ndarray, length: int, tt: int) -> tuple:
    """``(visited, skipped)`` token tiles of a batch of rows of ``length``
    positions whose real ``lengths`` (rows,) are given, as the kernel counts
    them (:func:`has_next`). On the host."""
    live = tiles_with_work(has_next(lengths, length, np).reshape(-1), tt, np)
    return int(live.sum()), int(live.size - live.sum())
