"""Plain histogram GBDT arithmetic (LightGBM's, as the configuration's file
states it), for judging a fitted model one decision at a time.

The benchmark's reference for the GBDT cells. It imports nothing of the
program and takes nothing the program made: bin edges come from its own
reading of the quantile rule, gradients from its own scores, histograms
from one plain one-hot contraction at ``precision=HIGHEST`` with
compensated accumulation. What it is handed from the program is only the
answer under judgement: the split records and leaf values of a fitted
model, as plain arrays.

A tree is judged the way a served token is: the reference follows the
program's own splits (so a near-tie cannot send the two down different
trees) and reads, at every split, by how much the gain of the program's
choice lies below the best gain the reference finds among all leaves that
were open at that step; and, at every final leaf, the gap between the
program's value and ``-G / (H + lambda) * learning_rate`` of the rows the
reference routes there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BINS = 256          # uint8 bin space; bin 0 is kept for missing values
BLOCK_ROWS = 4096


def bin_edges(x: np.ndarray, max_bin: int, sample: int, seed: int = 0) -> list:
    """Upper bounds of each feature's value bins: ``max_bin - 2`` interior
    percentiles (linear interpolation) of a ``sample``-row draw without
    replacement, duplicates merged."""
    n, d = x.shape
    rows = x[np.random.default_rng(seed).choice(n, sample, replace=False)] if n > sample else x
    edges = []
    qs = np.linspace(0, 100, max_bin)[1:-1]
    for f in range(d):
        col = rows[:, f]
        col = col[~np.isnan(col)]
        uniq = np.unique(col)
        if len(uniq) <= 1:
            edges.append(np.array([], np.float64))
        elif len(uniq) <= max_bin - 1:
            edges.append(((uniq[:-1] + uniq[1:]) / 2.0).astype(np.float64))
        else:
            edges.append(np.unique(np.percentile(col, qs, method="linear")).astype(np.float64))
    return edges


def bin_matrix(x: np.ndarray, edges: list) -> np.ndarray:
    """Value -> 1 + the number of edges below it (a value equal to an edge
    stays left of it); float32 values, float64 edges."""
    x = np.asarray(x, np.float32)
    out = np.empty(x.shape, np.uint8)
    for f, e in enumerate(edges):
        out[:, f] = np.searchsorted(e, x[:, f], side="left") + 1
    return out


def threshold_bin(edges: list, feature: int, threshold: float) -> int:
    """The bin whose upper bound a real-valued threshold is."""
    return int(np.searchsorted(edges[feature], threshold, side="left")) + 1


def grad_hess(scores: np.ndarray, y: np.ndarray) -> tuple:
    """Binary log-loss: g = sigmoid(s) - y, h = p (1 - p), in float64."""
    p = 1.0 / (1.0 + np.exp(-scores.astype(np.float64)))
    return p - y, p * (1.0 - p)


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def route(bins_t: jnp.ndarray, leaf: jnp.ndarray, feature: jnp.ndarray,
          bin_: jnp.ndarray, active: jnp.ndarray, num_leaves: int) -> jnp.ndarray:
    """Final leaf of every row under a tree's split records: split ``k``
    sends the rows of leaf ``leaf[k]`` whose bin is above ``bin_[k]`` to
    the new leaf ``k + 1``."""
    n = bins_t.shape[1]

    def step(k: int, row_leaf: jnp.ndarray) -> jnp.ndarray:
        col = jax.lax.dynamic_index_in_dim(bins_t, feature[k], 0, keepdims=False)
        moved = active[k] & (row_leaf == leaf[k]) & (col.astype(jnp.int32) > bin_[k])
        return jnp.where(moved, k + 1, row_leaf)

    return jax.lax.fori_loop(0, num_leaves - 1, step, jnp.zeros((n,), jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def leaf_histograms(bins: jnp.ndarray, stats: jnp.ndarray, row_leaf: jnp.ndarray,
                    num_leaves: int) -> jnp.ndarray:
    """Histograms of every final leaf of a tree in one pass over the rows.

    ``bins`` (n, d) uint8, ``stats`` (n, s) float32, ``row_leaf`` (n,) final
    leaf of each row (-1 = padding). Returns (d, BINS, num_leaves * s)."""
    n, d = bins.shape
    s = stats.shape[1]
    blocks = n // BLOCK_ROWS

    def body(carry: tuple, blk: tuple) -> tuple:
        total, comp = carry
        b, st, rl = blk
        onehot = (b[:, :, None] == jnp.arange(BINS, dtype=jnp.uint8)).astype(jnp.float32)
        in_leaf = (rl[:, None] == jnp.arange(num_leaves)).astype(jnp.float32)
        cols = (in_leaf[:, :, None] * st[:, None, :]).reshape(BLOCK_ROWS, num_leaves * s)
        part = jnp.einsum("rdb,rc->dbc", onehot, cols,
                          precision=jax.lax.Precision.HIGHEST)
        # compensated (Kahan) accumulation over the blocks
        y = part - comp
        t = total + y
        return (t, (t - total) - y), None

    zero = jnp.zeros((d, BINS, num_leaves * s), jnp.float32)
    (total, _), _ = jax.lax.scan(
        body, (zero, zero),
        (bins.reshape(blocks, BLOCK_ROWS, d), stats.reshape(blocks, BLOCK_ROWS, s),
         row_leaf.reshape(blocks, BLOCK_ROWS)))
    return total


def node_histograms(leaf_hist: np.ndarray, member: np.ndarray) -> np.ndarray:
    """A node's histogram is the sum of its final leaves': ``leaf_hist``
    (d, BINS, leaves, s) and ``member`` (leaves, nodes) to float64
    (d, BINS, nodes, s), summed on the host."""
    nodes = np.tensordot(leaf_hist.astype(np.float64), member.astype(np.float64),
                         axes=([2], [0]))            # (d, BINS, s, nodes)
    return nodes.transpose(0, 1, 3, 2)


def pad_rows(a: np.ndarray, fill: object) -> np.ndarray:
    n = a.shape[0]
    target = -(-n // BLOCK_ROWS) * BLOCK_ROWS
    if target == n:
        return a
    width = [(0, target - n)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, width, constant_values=fill)


def tree_nodes(leaf: np.ndarray, active: np.ndarray, num_leaves: int) -> tuple:
    """Node numbering of a tree from its split records: the root is node 0,
    split ``k`` makes nodes ``2k+1`` (left, keeps the leaf's id) and
    ``2k+2`` (right, leaf ``k+1``). Returns (member, parent_node, open_nodes,
    leaf_node): ``member[l, v]`` = node v lies on leaf l's path;
    ``parent_node[k]`` = the node split at step k; ``open_nodes[k]`` = the
    nodes that were leaves when step k chose; ``leaf_node[l]`` = the node
    that final leaf l is."""
    splits = num_leaves - 1
    nodes = 1 + 2 * splits
    chain = {0: [0]}
    cur = {0: 0}
    parent_node = np.full(splits, -1, np.int64)
    open_nodes = []
    for k in range(splits):
        open_nodes.append(sorted(cur.values()))
        if not active[k]:
            continue
        L = int(leaf[k])
        parent_node[k] = cur[L]
        chain[k + 1] = chain[L] + [2 * k + 2]
        chain[L] = chain[L] + [2 * k + 1]
        cur[L], cur[k + 1] = 2 * k + 1, 2 * k + 2
    member = np.zeros((num_leaves, nodes), np.float32)
    for l, path in chain.items():
        member[l, path] = 1.0
    return member, parent_node, open_nodes, cur


def split_gains(hist: np.ndarray, min_data: int, min_hess: float, lam: float) -> np.ndarray:
    """Gain of every (feature, bin) threshold of one node, float64:
    ``GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam)``; ``-inf`` where a child
    would hold fewer than ``min_data`` rows or less than ``min_hess``."""
    g, h, c = (np.cumsum(hist[..., i].astype(np.float64), axis=1) for i in range(3))
    G, H, C = g[:, -1:], h[:, -1:], c[:, -1:]

    def term(a: np.ndarray, b: np.ndarray) -> np.ndarray:
        return a * a / (b + lam)

    with np.errstate(divide="ignore", invalid="ignore"):
        gain = term(g, h) + term(G - g, H - h) - term(G, H)
    ok = (c >= min_data) & (C - c >= min_data) & (h >= min_hess) & (H - h >= min_hess)
    return np.where(ok, gain, -np.inf)


def judge_tree(hist: np.ndarray, tree: dict, params: dict, stats_at: int = 0,
               choose_at: "int | None" = None) -> dict:
    """Read one tree's gaps from its nodes' histograms.

    ``hist`` (d, BINS, nodes, s) with (g, h, count) at ``stats_at``.
    ``choose_at`` = the control: where a second, lower-precision (g, h)
    pair sits; the split judged at each step is then the one THAT
    histogram puts first among the open leaves, not the program's."""
    L = params["num_leaves"]
    lam, lr = params["lambda_l2"], params["learning_rate"]
    member, parent_node, open_nodes, cur = tree_nodes(tree["leaf"], tree["active"], L)

    def gains_of(node: int, at: int) -> np.ndarray:
        h3 = hist[:, :, node, at:at + 2]
        cnt = hist[:, :, node, stats_at + 2:stats_at + 3]
        return split_gains(np.concatenate([h3, cnt], axis=-1),
                           params["min_data_in_leaf"], params["min_sum_hessian_in_leaf"], lam)

    cache: dict = {}

    def cached_gains(node: int, at: int) -> np.ndarray:
        if (node, at) not in cache:
            cache[node, at] = gains_of(node, at)
        return cache[node, at]

    def ref_gains(node: int) -> np.ndarray:
        return cached_gains(node, stats_at)

    gaps = []
    for k in range(L - 1):
        if not tree["active"][k]:
            continue
        best_open = max(float(ref_gains(v).max()) for v in open_nodes[k])
        if choose_at is None:
            chosen = float(ref_gains(parent_node[k])[tree["feature"][k], tree["bin"][k]])
        else:
            top = None
            for v in open_nodes[k]:
                low = cached_gains(v, choose_at)
                f, b = np.unravel_index(int(np.argmax(low)), low.shape)
                if top is None or low[f, b] > top[0]:
                    top = (low[f, b], v, f, b)
            chosen = float(ref_gains(top[1])[top[2], top[3]])
        gaps.append((best_open - chosen) / best_open if best_open > 0 else float("inf"))

    # final leaves: value and row count from the reference's own sums
    leaves = sorted(cur)
    sums = np.stack([hist[0, :, cur[l], :].astype(np.float64).sum(axis=0) for l in leaves])
    at = stats_at if choose_at is None else choose_at
    want = -sums[:, stats_at] / (sums[:, stats_at + 1] + lam) * lr
    if choose_at is None:
        got = np.asarray(tree["values"], np.float64)[leaves]
    else:
        got = -sums[:, at] / (sums[:, at + 1] + lam) * lr
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    counts = np.rint(sums[:, stats_at + 2]).astype(np.int64)
    return {
        "gain_gaps": gaps,
        "value_gaps": (np.abs(got - want) / scale).tolist(),
        "count_mismatch": int(np.abs(counts - np.asarray(tree["counts"], np.int64)[leaves]).sum()),
        "splits": int(np.sum(tree["active"])),
    }
