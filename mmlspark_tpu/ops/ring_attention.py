"""Ring attention — sequence-parallel exact attention over the mesh.

The long-context primitive (SURVEY §5.7): when a sequence is sharded over
a mesh axis, each device holds one Q/K/V block and K/V blocks rotate
around the ring with ``lax.ppermute`` (one neighbor hop per step — the
collective rides ICI). Per-block scores fold into the running output with
the online-softmax update (running max + rescaled accumulator), so the
result is EXACT attention over the full sequence while no device ever
materializes more than its own block pair — memory O(seq/devices) per
device, communication seq_len * d_model per ring lap.

This is the jax expression of Ring Attention (Liu et al. 2023) /
blockwise-parallel attention; causal masking uses global block offsets so
the rotated blocks mask correctly. Single-device meshes degenerate to
plain (still blockwise-stable) attention.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.parallel.mesh import get_mesh

SEQ_AXIS = "data"  # default: ride the batch axis of the standard mesh


def _block_attend(
    q: jnp.ndarray,          # (B, Tq, H, D)
    k: jnp.ndarray,          # (B, Tk, H, D)
    v: jnp.ndarray,          # (B, Tk, H, D)
    o: jnp.ndarray,          # (B, Tq, H, D) running (unnormalized) output
    m: jnp.ndarray,          # (B, Tq, H) running max
    l: jnp.ndarray,          # (B, Tq, H) running sum
    q_off: jnp.ndarray,      # scalar: global offset of this q block
    k_off: jnp.ndarray,      # scalar: global offset of this k block
    scale: float,
    causal: bool,
    kv_mask: Optional[jnp.ndarray] = None,  # (B, Tk) bool; False = pad key
) -> tuple:
    """Fold one K/V block into the online-softmax accumulators."""
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * scale  # (B, Tq, H, Tk)
    if causal:
        qi = q_off + jnp.arange(q.shape[1])
        ki = k_off + jnp.arange(k.shape[1])
        mask = qi[:, None] >= ki[None, :]            # (Tq, Tk)
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    if kv_mask is not None:
        # padding keys receive no attention; the accumulator math below
        # already tolerates fully-masked blocks (running max stays -inf)
        s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
    blk_m = s.max(axis=-1)                           # (B, Tq, H)
    new_m = jnp.maximum(m, blk_m)
    # fully-masked blocks: new_m stays -inf; exp(-inf - -inf) guards below
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    new_l = l * corr + p.sum(axis=-1)
    new_o = o * corr[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p, v)
    return new_o, new_m, new_l


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Optional[Any] = None,
    axis: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Exact attention with the SEQUENCE dim sharded over ``mesh[axis]``.

    ``q``/``k``/``v``: (batch, seq, heads, head_dim), seq sharded over the
    axis (shard_map reshards if needed). Returns the attention output in
    the same layout/sharding. ``causal=True`` applies the autoregressive
    mask with GLOBAL positions (each shard knows its ring offset).
    ``kv_mask``: optional (batch, seq) bool — False keys receive no
    attention. This is how padded sequences shard cleanly: pad to a
    multiple of the axis size, mask the tail (the pad mask rides the
    same ring rotation as its K/V block)."""
    mesh = mesh or get_mesh()
    n_shards = dict(mesh.shape).get(axis, 1)
    sc = scale if scale is not None else q.shape[-1] ** -0.5
    has_mask = kv_mask is not None

    def local(ql, kl, vl, mk) -> jnp.ndarray:
        B, Tq, H, D = ql.shape
        my = jax.lax.axis_index(axis)
        o = jnp.zeros_like(ql)
        m = jnp.full((B, Tq, H), -jnp.inf, ql.dtype)
        l = jnp.zeros((B, Tq, H), ql.dtype)
        q_off = my * Tq

        def step(i: int, carry: tuple) -> tuple:
            o, m, l, kc, vc, mc = carry
            # the block currently held arrived from shard (my + i) % n
            src = (my + i) % n_shards
            o, m, l = _block_attend(
                ql, kc, vc, o, m, l, q_off, src * kc.shape[1], sc, causal,
                mc,
            )
            # rotate K/V (and the pad mask) one hop around the ring
            perm = [(j, (j - 1) % n_shards) for j in range(n_shards)]
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            if mc is not None:
                mc = jax.lax.ppermute(mc, axis, perm)
            return o, m, l, kc, vc, mc

        # n-1 rotated steps; the LAST block attends outside the loop so the
        # ring never pays a final hop whose result would be discarded
        o, m, l, kc, vc, mc = jax.lax.fori_loop(
            0, n_shards - 1, step, (o, m, l, kl, vl, mk)
        )
        last_src = (my + n_shards - 1) % n_shards
        o, m, l = _block_attend(
            ql, kc, vc, o, m, l, q_off, last_src * kc.shape[1], sc, causal,
            mc,
        )
        # rows with no visible keys (can't happen with causal diag) -> 0
        return o / jnp.maximum(l, 1e-30)[..., None]

    if n_shards == 1:
        # degenerate single-shard mesh: same math, no collectives
        B, T, H, D = q.shape
        o = jnp.zeros_like(q)
        m = jnp.full((B, T, H), -jnp.inf, q.dtype)
        l = jnp.zeros((B, T, H), q.dtype)
        o, m, l = _block_attend(
            q, k, v, o, m, l, jnp.int32(0), jnp.int32(0), sc, causal,
            kv_mask,
        )
        return o / jnp.maximum(l, 1e-30)[..., None]

    spec = P(None, axis, None, None)
    mspec = P(None, axis)
    if has_mask:
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec, mspec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v, kv_mask)
    return jax.shard_map(
        lambda a, b, c: local(a, b, c, None),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def dense_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = False, scale: Optional[float] = None,
    kv_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Reference single-device attention (the golden for ring tests).

    A query whose keys are all masked gets 0, as the ring gives it (a plain
    softmax over a row of ``-inf`` is NaN)."""
    sc = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * sc
    if causal:
        T, S = s.shape[1], s.shape[3]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
    total = e.sum(axis=-1, keepdims=True)
    p = e / jnp.where(total > 0, total, 1.0)
    return jnp.einsum("bqhk,bkhd->bqhd", p, v)
