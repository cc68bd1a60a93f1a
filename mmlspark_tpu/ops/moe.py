"""Sparse expert layer: route, dispatch, grouped product, combine.

One layer of a sparse-expert feed-forward network as XLA operations. The
layer is *told which experts it holds* — a contiguous range ``[lo, hi)`` of
the ``num_experts`` the router scores, with their weights — and computes
those experts' part of the result for the tokens routed to them; what the
other experts would add is left out (zero), so the parts of disjoint ranges
add up to the whole layer (tests/test_causal_lm.py ties that to the plain
reference). On one chip the range is everything; a mesh of expert ranges
adds an exchange around :func:`expert_ffn`, not a change inside it.

No token is dropped and there is no capacity: the ``T * k`` (token, expert)
pairs are sorted by expert — held experts first — and the three products of
the gated FFN run over the sorted rows, one group per held expert. On a TPU
they are two calls of the Pallas kernel ``expert_gmm``
(:mod:`mmlspark_tpu.ops.grouped_matmul`): the up-call multiplies a tile of
rows by ``W1_e`` and ``W3_e`` at once and stores ``silu(a) * b`` from the
float32 products, the down-call multiplies that by ``W2_e``; the group
metadata is computed once a layer and both calls share it.
``ops.histogram.use_pallas`` decides, as for the other kernels, and
``grouped_matmul.tiling`` says from the shapes alone how the products are
tiled; elsewhere, and for widths the rule cannot tile, the products are
``jax.lax.ragged_dot`` (XLA's own grouped matmul, which the kernel is tested
against) and the gate an elementwise pass. The combine gathers the rows back
into token order and weights them in float32.

Two routers; the configuration's keys say which (:func:`router_kind`).
``"sigmoid"`` (LFM2-MoE / DeepSeek-V3 style): ``s = sigmoid(W_g u)`` in
float32; the top-k is taken over ``s + bias`` (the load-balancing expert
bias) while the combine weights come from ``s`` alone, normalised over the
selection. ``"softmax"`` (the ``qwen3_moe`` family): ``p = softmax(W_g u)``
over all the experts in float32, the top-k of ``p``, and as weights ``p``
over its sum over the selection (``norm_topk_prob``) or ``p`` as it is; no
bias.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops import grouped_matmul, histogram

ROUTE_EPS = 1e-6


def select(scores: jnp.ndarray, bias: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """(T, E) scores -> (T, k) expert ids: the top-k of ``scores + bias``."""
    return jax.lax.top_k(scores + bias, top_k)[1]


def combine_weights(scores: jnp.ndarray, idx: jnp.ndarray, scaling: float) -> jnp.ndarray:
    """(T, k) weights of the selected experts: from the scores alone (the
    bias only steers the selection), normalised to sum to ``scaling``."""
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS) * scaling


def router_logits(u: jnp.ndarray, router: jnp.ndarray) -> jnp.ndarray:
    """(T, E) float32 at full matmul precision: a near-tie decides which
    experts run, and a bfloat16 product would flip many more."""
    return jnp.einsum("th,he->te", u.astype(jnp.float32), router.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def router_scores(u: jnp.ndarray, router: jnp.ndarray) -> jnp.ndarray:
    """(T, E) sigmoid scores in float32."""
    return jax.nn.sigmoid(router_logits(u, router))


def router_kind(config: dict) -> str:
    """``"sigmoid"`` or ``"softmax"``: what ``scoring_func`` says; where a
    configuration has no such key, its family's: the sigmoid router's
    families carry ``routed_scaling_factor``, the softmax router's do not."""
    return config.get("scoring_func",
                      "sigmoid" if "routed_scaling_factor" in config else "softmax")


def softmax_weights(probs: jnp.ndarray, idx: jnp.ndarray, norm_topk_prob: bool) -> jnp.ndarray:
    """(T, k) weights of the selected experts: their probabilities, over
    their sum if ``norm_topk_prob``."""
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    return picked / picked.sum(-1, keepdims=True) if norm_topk_prob else picked


def route_softmax(u: jnp.ndarray, router: jnp.ndarray, top_k: int,
                  norm_topk_prob: bool = True) -> tuple:
    """(T, h) tokens -> ((T, k) int32 expert ids, (T, k) float32 weights)
    by the softmax router."""
    with jax.named_scope("lm.moe.route"):
        probs = jax.nn.softmax(router_logits(u, router), axis=-1)
        idx = jax.lax.top_k(probs, top_k)[1]
        return idx, softmax_weights(probs, idx, norm_topk_prob)


def route(u: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray, top_k: int,
          scaling: float = 1.0) -> tuple:
    """(T, h) tokens -> ((T, k) int32 expert ids, (T, k) float32 weights)."""
    with jax.named_scope("lm.moe.route"):
        scores = router_scores(u, router)
        idx = select(scores, bias.astype(jnp.float32), top_k)
        return idx, combine_weights(scores, idx, scaling)


def expert_ffn(u: jnp.ndarray, idx: jnp.ndarray, weights: jnp.ndarray,
               w1: jnp.ndarray, w3: jnp.ndarray, w2: jnp.ndarray, num_experts: int,
               experts: Optional[tuple] = None) -> tuple:
    """The held experts' part of ``sum_e w_e W2_e (silu(W1_e u) * W3_e u)``,
    and what the products' tiling cost.

    ``u`` (T, h); ``idx`` / ``weights`` (T, k) from :func:`route` over all
    ``num_experts``; ``w1`` / ``w3`` (n_held, h, f) and ``w2`` (n_held, f, h)
    are the weights of experts ``experts = (lo, hi)`` (default: all). Tokens
    routed to an expert outside the range get nothing from it here.
    -> ((T, h), (2,) int32 ``[visited, aligned]``: the (expert, row tile)
    visits the kernel made and the row tiles that held a routed row; zeros
    where the products ran as ``ragged_dot``)."""
    lo, hi = experts or (0, num_experts)
    held = hi - lo
    if w1.shape[0] != held:
        raise ValueError(f"experts [{lo}, {hi}) need {held} experts' weights, got {w1.shape[0]}")
    tokens, k = idx.shape
    with jax.named_scope("lm.moe.dispatch"):
        flat = idx.reshape(-1)
        # held experts sort first, in order; the rest behind them, in no group
        rank = (flat - lo) % num_experts
        order = jnp.argsort(rank, stable=True)
        rows = u[order // k]
        sizes = (rank[:, None] == jnp.arange(held)[None, :]).sum(0, dtype=jnp.int32)
    with jax.named_scope("lm.moe.experts"):
        tiling = None
        if histogram.use_pallas():
            tiling = grouped_matmul.tiling(rows.shape[0], w1.shape[1], w1.shape[2],
                                           histogram._hist_vmem_mb() << 20)
        if tiling is None:
            def gmm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
                return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=u.dtype)

            out = gmm(jax.nn.silu(gmm(rows, w1)) * gmm(rows, w3), w2)
            tiles = jnp.zeros((2,), jnp.int32)
        else:
            out, tiles = grouped_matmul.expert_products(
                rows, sizes, w1, w3, w2, tiling=tiling,
                call=tuple(histogram._pallas_call_kwargs().items()))
    with jax.named_scope("lm.moe.combine"):
        if held < num_experts:  # rows past the groups hold nothing defined
            out = jnp.where((rank[order] < held)[:, None], out, 0)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size, dtype=order.dtype))
        picked = out[back].reshape(tokens, k, -1)
        return jnp.einsum("tk,tkh->th", weights,
                          picked.astype(jnp.float32)).astype(u.dtype), tiles


def expert_load(idx: jnp.ndarray, real: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """(B, L, k) expert ids, (B, L) bool -> (B, E) float32: per row of the
    batch, the real tokens routed to each expert."""
    hot = idx[..., None] == jnp.arange(num_experts)
    return (hot & real[:, :, None, None]).sum((1, 2), dtype=jnp.float32)
