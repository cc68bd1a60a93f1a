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

Three routers; the configuration's keys say which (:func:`router_kind`).
``"sigmoid"`` (``lfm2_moe``): ``s = sigmoid(W_g u)`` in float32; the top-k
is taken over ``s + bias`` (the load-balancing expert bias) while the
combine weights come from ``s`` alone, normalised over the selection and
times ``routed_scaling_factor``. ``"softmax"`` (the ``qwen3_moe`` family):
``p = softmax(W_g u)`` over all the experts in float32, the top-k of ``p``,
and as weights ``p`` over its sum over the selection (``norm_topk_prob``) or
``p`` as it is; no bias. ``"group_limited"`` (``deepseek_v2``:
``scoring_func: softmax`` with ``topk_method: group_limited_greedy``): the
same ``p``; the experts lie in ``n_group`` groups of consecutive ids, a
token keeps the ``topk_group`` groups whose best expert scores highest and
takes its top-k of ``p`` inside them (device-limited routing: a group is a
chip's experts); the weights are ``p`` over its sum over the selection
(``norm_topk_prob``) or ``p`` times ``routed_scaling_factor``.
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
    """``"sigmoid"``, ``"softmax"`` or ``"group_limited"``: what
    ``scoring_func`` says, and for a softmax whether ``topk_method`` limits
    the selection to groups; where a configuration has no ``scoring_func``,
    its family's: of the two families that lack the key, the one with a
    ``routed_scaling_factor`` routes by sigmoid and the other by softmax."""
    kind = config.get("scoring_func",
                      "sigmoid" if "routed_scaling_factor" in config else "softmax")
    if kind == "softmax" and config.get("topk_method") == "group_limited_greedy":
        return "group_limited"
    return kind


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


def limit_to_groups(probs: jnp.ndarray, n_group: int, topk_group: int) -> jnp.ndarray:
    """(T, E) probabilities -> the same with 0 outside each token's
    ``topk_group`` groups of largest best expert (``n_group`` groups of
    ``E / n_group`` consecutive experts)."""
    tokens, experts = probs.shape
    grouped = probs.reshape(tokens, n_group, experts // n_group)
    kept = jax.lax.top_k(grouped.max(-1), topk_group)[1]
    keep = (kept[:, :, None] == jnp.arange(n_group)[None, None, :]).any(1)
    return jnp.where(keep[:, :, None], grouped, 0.0).reshape(tokens, experts)


def route_group_limited(u: jnp.ndarray, router: jnp.ndarray, top_k: int, n_group: int,
                        topk_group: int, norm_topk_prob: bool, scaling: float) -> tuple:
    """(T, h) tokens -> ((T, k) int32 expert ids, (T, k) float32 weights)
    by the softmax router with a group-limited greedy selection."""
    with jax.named_scope("lm.moe.route"):
        probs = jax.nn.softmax(router_logits(u, router), axis=-1)
        idx = jax.lax.top_k(limit_to_groups(probs, n_group, topk_group), top_k)[1]
        weights = softmax_weights(probs, idx, norm_topk_prob)
        return idx, weights if norm_topk_prob else weights * scaling


def route(u: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray, top_k: int,
          scaling: float = 1.0) -> tuple:
    """(T, h) tokens -> ((T, k) int32 expert ids, (T, k) float32 weights)."""
    with jax.named_scope("lm.moe.route"):
        scores = router_scores(u, router)
        idx = select(scores, bias.astype(jnp.float32), top_k)
        return idx, combine_weights(scores, idx, scaling)


def held_rows(tokens: int, k: int, held: int, num_experts: int) -> int:
    """Rows a block of a share's device loop: twice the ``tokens * k * held /
    num_experts`` pairs a balanced router sends a range of ``held`` experts,
    in whole row tiles of the grouped-matmul kernel, and no more than all
    the pairs there are — from the shapes and the share alone."""
    tile = grouped_matmul.ROW_TILE
    return tile * min(-(-2 * tokens * k * held // (num_experts * tile)), -(-tokens * k // tile))


def _gated_products(rows: jnp.ndarray, sizes: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                    w2: jnp.ndarray) -> tuple:
    """``(silu(rows W1_g) * rows W3_g) W2_g`` over rows sorted by group ->
    (the products, the kernel's ``[visited, aligned]`` tiles)."""
    tiling = None
    if histogram.use_pallas():
        tiling = grouped_matmul.tiling(rows.shape[0], w1.shape[1], w1.shape[2],
                                       histogram._hist_vmem_mb() << 20)
    if tiling is None:
        def gmm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
            return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=rows.dtype)

        return gmm(jax.nn.silu(gmm(rows, w1)) * gmm(rows, w3), w2), jnp.zeros((2,), jnp.int32)
    return grouped_matmul.expert_products(
        rows, sizes, w1, w3, w2, tiling=tiling,
        call=tuple(histogram._pallas_call_kwargs().items()))


def token_sums(rows: jnp.ndarray, weights: jnp.ndarray, token: jnp.ndarray, tokens: int,
               most: int) -> jnp.ndarray:
    """``out[t] = sum of weights[r] * rows[r] over the r with token[r] == t``
    in float32, (tokens, h), where no token owns more than ``most`` rows and
    ``token[r] == tokens`` marks a row that counts for nothing. No
    scatter-add (on the v5e one of 24,576 rows of 5,120 float32 takes 35.5 ms,
    1.45 us a row, sorted or with unique indices alike, where this takes 11.7:
    PERF.md section 6, PR 33): the rows are brought into token
    order by a gather, a token's run of at most ``most`` adjacent rows is
    summed by shifted adds in one pass, and each token fetches its run's
    first row."""
    block = token.shape[0]
    order = jnp.argsort(token)
    owner = token[order]
    z = jnp.where((owner < tokens)[:, None],
                  rows[order].astype(jnp.float32) * weights[order][:, None], 0.0)
    z_after = jnp.pad(z, ((0, most - 1), (0, 0)))
    owner_after = jnp.pad(owner, (0, most - 1), constant_values=-1)
    run = z + sum(
        jnp.where((owner_after[d:d + block] == owner)[:, None], z_after[d:d + block], 0.0)
        for d in range(1, most))
    first = jnp.full((tokens,), block, jnp.int32).at[owner].min(
        jnp.arange(block, dtype=jnp.int32), mode="drop")
    return jnp.where((first < block)[:, None], run[jnp.minimum(first, block - 1)], 0.0)


def _held_ffn(u: jnp.ndarray, idx: jnp.ndarray, weights: jnp.ndarray, w1: jnp.ndarray,
              w3: jnp.ndarray, w2: jnp.ndarray, num_experts: int, lo: int) -> tuple:
    """:func:`expert_ffn` for a share ``[lo, lo + held)`` of the experts: a
    device loop over blocks of :func:`held_rows` of the pairs routed to the
    range, as many trips as the data asks for."""
    (tokens, k), held = idx.shape, w1.shape[0]
    pairs = tokens * k
    block = held_rows(tokens, k, held, num_experts)
    with jax.named_scope("lm.moe.dispatch"):
        # held experts sort first, in order; the rest behind them, in no group
        rank = (idx.reshape(-1) - lo) % num_experts
        order = jnp.pad(jnp.argsort(rank, stable=True), (0, -pairs % block))
        sizes = (rank[:, None] == jnp.arange(held)[None, :]).sum(0, dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        starts, routed = ends - sizes, ends[-1]
        flat_w = weights.reshape(-1)

    def one(i: jnp.ndarray, carry: tuple) -> tuple:
        acc, tiles = carry
        first = i * block
        with jax.named_scope("lm.moe.dispatch"):
            at = jax.lax.dynamic_slice_in_dim(order, first, block)
            token = at // k
            rows = u[token]
            here = jnp.clip(ends, first, first + block) - jnp.clip(starts, first, first + block)
        with jax.named_scope("lm.moe.experts"):
            out, visited = _gated_products(rows, here, w1, w3, w2)
        with jax.named_scope("lm.moe.combine"):
            # rows past the range's pairs hold nothing defined, and add nothing
            live = first + jnp.arange(block) < routed
            return acc + token_sums(out, jnp.where(live, flat_w[at], 0.0),
                                    jnp.where(live, token, tokens), tokens,
                                    min(k, held)), tiles + visited

    acc, tiles = jax.lax.fori_loop(
        0, -(-routed // block), one,
        (jnp.zeros(u.shape, jnp.float32), jnp.zeros((2,), jnp.int32)))
    return acc.astype(u.dtype), tiles


def expert_ffn(u: jnp.ndarray, idx: jnp.ndarray, weights: jnp.ndarray,
               w1: jnp.ndarray, w3: jnp.ndarray, w2: jnp.ndarray, num_experts: int,
               experts: Optional[tuple] = None) -> tuple:
    """The held experts' part of ``sum_e w_e W2_e (silu(W1_e u) * W3_e u)``,
    and what the products' tiling cost.

    ``u`` (T, h); ``idx`` / ``weights`` (T, k) from a router over all
    ``num_experts``; ``w1`` / ``w3`` (n_held, h, f) and ``w2`` (n_held, f, h)
    are the weights of experts ``experts = (lo, hi)`` (default: all). Tokens
    routed to an expert outside the range get nothing from it here, and a
    range that is a share gathers and multiplies only the pairs routed to it
    (:func:`_held_ffn`).
    -> ((T, h), (2,) int32 ``[visited, aligned]``: the (expert, row tile)
    visits the kernel made and the row tiles that held a routed row; zeros
    where the products ran as ``ragged_dot``)."""
    lo, hi = experts or (0, num_experts)
    held = hi - lo
    if w1.shape[0] != held:
        raise ValueError(f"experts [{lo}, {hi}) need {held} experts' weights, got {w1.shape[0]}")
    if held < num_experts:
        return _held_ffn(u, idx, weights, w1, w3, w2, num_experts, lo)
    tokens, k = idx.shape
    with jax.named_scope("lm.moe.dispatch"):
        flat = idx.reshape(-1)
        rank = (flat - lo) % num_experts  # = flat here; written as the accepted programs lower it
        order = jnp.argsort(rank, stable=True)
        rows = u[order // k]
        sizes = (rank[:, None] == jnp.arange(held)[None, :]).sum(0, dtype=jnp.int32)
    with jax.named_scope("lm.moe.experts"):
        out, tiles = _gated_products(rows, sizes, w1, w3, w2)
    with jax.named_scope("lm.moe.combine"):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size, dtype=order.dtype))
        picked = out[back].reshape(tokens, k, -1)
        return jnp.einsum("tk,tkh->th", weights,
                          picked.astype(jnp.float32)).astype(u.dtype), tiles


def expert_load(idx: jnp.ndarray, real: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """(B, L, k) expert ids, (B, L) bool -> (B, E) float32: per row of the
    batch, the real tokens routed to each expert."""
    hot = idx[..., None] == jnp.arange(num_experts)
    return (hot & real[:, :, None, None]).sum((1, 2), dtype=jnp.float32)
