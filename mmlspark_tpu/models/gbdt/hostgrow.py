"""Host (numpy) tree growers — the CPU lowering of
:func:`treegrow.grow_tree_depthwise` (whole-level batches) and of the
masked leaf-wise :func:`treegrow.grow_tree` (best-first splits).

Why a whole-tree host kernel and not just a host histogram: each
``pure_callback`` crossing costs ~1 ms of bridge overhead (operand/result
marshalling) on top of the kernel, and a per-LEVEL histogram callback
leaves the split search, sibling assembly and row routing as XLA:CPU ops
that cost another ~9 ms/tree — measured floor ~21 ms/tree at the bench
shape (20k x 16, 31 leaves) against sklearn's 12 ms. Growing the whole
tree behind ONE callback pays the bridge once, runs the split scan in
vectorized f64 numpy, and keeps the feature-parallel bincount pool
(ops/histpool.py) hot across levels.

Selection: only on unsharded CPU traces, by :func:`treegrow.choose_grower`
(its ``hostcall`` and ``depthwise_hostcall`` growers). TPU, sharded meshes
and ``MMLSPARK_TPU_HIST_HOST=0`` keep the XLA growers. Split semantics mirror
``treegrow.make_leaf_best`` + the vectorized level application exactly
(same tie-breaks: first-max over the (d*B) plane, stable gain ordering
across a level); gains accumulate in f64 where the XLA grower uses f32,
so near-tie splits may differ by float epsilon — the same class of
divergence the Pallas/scatter lowerings already have. tests/test_gbdt_fused.py
pins host-vs-XLA grower equivalence on clean-margin fixtures.

Rows-proportional cost: level histograms cover only the SMALLER child of
every sibling pair (LightGBM's subtraction trick, generalized from the
XLA grower's right-child-only choice), and the kernel drops non-frontier
rows before counting.
"""

from __future__ import annotations

import itertools

import numpy as np

# per-callback token for the pool's write-once arena cache: object ids are
# recyclable across trees (a freed ndarray's id can be reused by the next
# round's same-shape array, which would silently serve STALE gradients), so
# every tree draws a fresh monotonic token instead
_TREE_TOKENS = itertools.count(1)

from mmlspark_tpu.ops.histogram import _host_multi_kernel


def _soft(G: np.ndarray, l1: float) -> np.ndarray:
    return np.sign(G) * np.maximum(np.abs(G) - l1, 0.0)


def _feature_blocks(d: int) -> list:
    """Contiguous feature ranges for the build/allreduce overlap
    pipeline. Elementwise sums are blocking-invariant, so the block
    count changes only WHEN bytes move, never what they sum to.

    Blocks must stay >= 16 features wide: the histogram pool stripes
    work BY FEATURE, so narrower blocks would shrink per-call worker
    parallelism — measured at the 1M x 16 bench shape, 4-feature blocks
    cost more build time than the wire time they overlapped. Narrow
    planes therefore stay whole (one block = plain build + one
    allreduce); the pipeline engages on wide planes, where both the
    payload and the per-block parallelism are large."""
    nb = max(1, min(4, d // 16))
    return [
        (i * d // nb, (i + 1) * d // nb)
        for i in range(nb)
        if (i + 1) * d // nb > i * d // nb
    ]


def _gang_summed_cube(
    blocks_fn,
    bins: np.ndarray,
    stats: np.ndarray,
    slot: np.ndarray,
    ns_hist: int,
    B: int,
) -> np.ndarray:
    """Gang-global (ns_hist, d, B, 3) cube with compute/communication
    overlap: per-feature-block histograms are handed to the reducer as
    soon as they finish, while the NEXT block is still being built
    (GangContext.allreduce_blocks double-buffers). Bit-identical to
    building the whole cube and allreducing it in one piece."""
    d = bins.shape[1]

    def build(lo: int, hi: int):
        def _go() -> np.ndarray:
            blk = np.ascontiguousarray(bins[:, lo:hi])
            return _host_multi_kernel(
                ns_hist, B, True, blk, stats, slot
            ).reshape(ns_hist, hi - lo, B, 3)

        return _go

    bounds = _feature_blocks(d)
    outs = blocks_fn([build(lo, hi) for lo, hi in bounds])
    if len(outs) == 1:
        return outs[0]
    return np.concatenate(outs, axis=1)


def _combine_candidates(
    cube: np.ndarray,        # (S, d, B, 3)
    gains: np.ndarray,       # (d, S) f64
    bbs: np.ndarray,         # (d, S) i64
    cat_f: "np.ndarray | None",
) -> tuple:
    """Cross-feature winner per slot (lowest feature on ties — together
    with feature_candidates' lowest-bin tie-break this reproduces the
    XLA grower's flat first-max exactly) + the winner's categorical
    left-set mask."""
    S = gains.shape[1]
    bf = np.argmax(gains, axis=0)                     # (S,)
    sl = np.arange(S)
    bgain = gains[bf, sl]
    bb = bbs[bf, sl]
    B = cube.shape[2]
    catmask = np.zeros((S, B), bool)
    if cat_f is not None and cat_f[bf].any():
        hsel = cube[sl, bf].astype(np.float64)        # (S, B, 3)
        with np.errstate(divide="ignore", invalid="ignore"):
            ratio = np.where(
                hsel[..., 2] > 0, hsel[..., 0] / (hsel[..., 1] + 1e-12),
                -np.inf,
            )
        order = np.argsort(-ratio, axis=1, kind="stable")
        rank = np.argsort(order, axis=1, kind="stable")
        catmask = rank <= bb[:, None]
    return bgain, bf.astype(np.int64), bb, catmask


def _voting_combine(
    cube_local: np.ndarray,     # (S, d, B, 3) member-LOCAL histograms
    local_gains: np.ndarray,    # (d, S) f64 local best gain per feature
    fm: np.ndarray,
    cat_f: "np.ndarray | None",
    min_data: float,
    msh: float,
    lam: float,
    l1: float,
    gsum,
    top_k: int,
) -> tuple:
    """PV-Tree voting exchange (LightGBM ``voting_parallel``) for the
    gang growers: instead of allreducing the full (S, d, B, 3) plane,

    1. each member votes its local top-``K`` features per slot (ballots
       derived from the ALREADY-computed local gain scan — free);
    2. one tiny (d,) vote allreduce; the top ``2K`` vote-getters (ties
       to the lower feature id, mirroring voting.py's device tie-break)
       become the refresh's candidates — identical on every member;
    3. only the candidates' histogram columns are summed
       ((S, 2K, B, 3) instead of (S, d, B, 3)) and the exact split scan
       runs on those global columns.

    Payload per exchange drops from O(d*B) to O(d + 2K*B) — the win
    voting mode exists for when features are wide. The chosen split is
    exact over the candidate set; a feature outvoted everywhere cannot
    win, which is the mode's documented quality tolerance versus full
    data-parallel (docs/gbdt-training.md)."""
    from mmlspark_tpu.ops.histpool import feature_candidates
    from mmlspark_tpu.parallel.elastic import note_vote_round

    d, S = local_gains.shape
    K = max(1, min(int(top_k), d))
    C = min(2 * K, d)
    masked = np.where(np.isfinite(local_gains), local_gains, -np.inf)
    ballots = np.zeros(d, np.float64)
    if K < d:
        idx = np.argpartition(-masked, K - 1, axis=0)[:K]       # (K, S)
        chosen = np.take_along_axis(masked, idx, axis=0)
        np.add.at(ballots, idx[np.isfinite(chosen)], 1.0)
    else:
        ballots += np.isfinite(masked).sum(axis=1)
    votes = np.asarray(gsum(ballots), np.float64)
    if C < d:
        # ties to the LOWER feature id — the same deterministic rank
        # voting.py uses on device (scores are distinct by construction)
        score = votes * np.float64(d + 1) - np.arange(d, dtype=np.float64)
        cand = np.sort(np.argpartition(-score, C - 1)[:C])
    else:
        cand = np.arange(d)
    cand_cube = np.asarray(
        gsum(np.ascontiguousarray(cube_local[:, cand]))
    )
    cat_c = cat_f[cand] if cat_f is not None else None
    gains_c, bbs_c = feature_candidates(
        cand_cube, np.asarray(fm)[cand], float(min_data), msh, lam, l1,
        cat_c,
    )
    bg, bfc, bb, cm = _combine_candidates(cand_cube, gains_c, bbs_c, cat_c)
    note_vote_round()
    return bg, cand[bfc], bb, cm


def grow_tree_depthwise_host(
    num_leaves: int,
    n_levels: int,
    num_bins: int,
    min_data_in_leaf: int,
    sibling_subtract: bool,
    has_categorical: bool,
    min_gain,
    lambda_l2,
    lambda_l1,
    min_sum_hessian,
    learning_rate,
    bins,
    grad,
    hess,
    row_weight,
    feature_mask,
    categorical_mask,
) -> tuple:
    """One depthwise tree, entirely on host. Returns the GrownTree field
    tuple (same order/dtypes as treegrow.GrownTree). The regularization
    and learning-rate knobs arrive as 0-d arrays (they are traced values
    inside the scan-fused round loop). If the worker pool dies mid-tree
    the whole tree re-runs serially (pooled and serial paths are
    bit-identical, so the retry is invisible)."""
    from mmlspark_tpu.parallel.elastic import gang_sum

    # elastic gang training: level histograms are allreduced across gang
    # members (parallel/elastic.py), which needs the serial kernel — the
    # fork pool's split scan would run on member-LOCAL cubes
    if gang_sum() is not None:
        return _grow_host(
            num_leaves, n_levels, num_bins, min_data_in_leaf,
            sibling_subtract, has_categorical, min_gain, lambda_l2,
            lambda_l1, min_sum_hessian, learning_rate, bins, grad, hess,
            row_weight, feature_mask, categorical_mask, use_pool=False,
        )
    try:
        return _grow_host(
            num_leaves, n_levels, num_bins, min_data_in_leaf,
            sibling_subtract, has_categorical, min_gain, lambda_l2,
            lambda_l1, min_sum_hessian, learning_rate, bins, grad, hess,
            row_weight, feature_mask, categorical_mask, use_pool=True,
        )
    except _PoolLost:
        return _grow_host(
            num_leaves, n_levels, num_bins, min_data_in_leaf,
            sibling_subtract, has_categorical, min_gain, lambda_l2,
            lambda_l1, min_sum_hessian, learning_rate, bins, grad, hess,
            row_weight, feature_mask, categorical_mask, use_pool=False,
        )


class _PoolLost(Exception):
    """The pool degraded after this tree already used it for a level —
    the previous level's cube lives in a dead arena, so restart serial."""


def _grow_host(
    num_leaves: int,
    n_levels: int,
    num_bins: int,
    min_data_in_leaf: int,
    sibling_subtract: bool,
    has_categorical: bool,
    min_gain,
    lambda_l2,
    lambda_l1,
    min_sum_hessian,
    learning_rate,
    bins,
    grad,
    hess,
    row_weight,
    feature_mask,
    categorical_mask,
    use_pool: bool,
) -> tuple:
    from mmlspark_tpu.ops.histpool import feature_candidates, get_pool
    from mmlspark_tpu.parallel.elastic import gang_blocks, gang_sum

    # elastic gang: sum histograms (and child-size decisions) across the
    # gang, LightGBM data-parallel style — every member then makes the
    # identical split decision from the identical global cube.
    # gblocks: the compute/communication overlap pipeline (feature
    # blocks allreduce while later blocks build). Voting-parallel never
    # reaches this grower: PV-Tree is leaf-wise, and train() rejects
    # depthwise + voting before any grower runs.
    gsum = gang_sum()
    gblocks = gang_blocks()

    min_gain = float(np.asarray(min_gain))
    lambda_l2 = float(np.asarray(lambda_l2))
    lambda_l1 = float(np.asarray(lambda_l1))
    min_sum_hessian = float(np.asarray(min_sum_hessian))
    learning_rate = float(np.asarray(learning_rate))
    # keep the caller's dtype: mapper-binned uint8 crosses the callback
    # bridge and the pool arena at a quarter of the int32 byte volume
    b = np.ascontiguousarray(np.asarray(bins))
    n, d = b.shape
    L, B = num_leaves, num_bins
    g64 = np.asarray(grad, np.float64)
    h64 = np.asarray(hess, np.float64)
    w = np.asarray(row_weight, np.float64)
    fm = np.asarray(feature_mask)
    cat_f = np.asarray(categorical_mask, bool) if has_categorical else None
    g = g64 * w
    h = h64 * w
    stats = np.stack([g, h, w], axis=1).astype(np.float32)
    s3 = np.ascontiguousarray(stats.T)
    scan = (fm, cat_f, float(min_data_in_leaf), min_sum_hessian,
            lambda_l2, lambda_l1)
    pool = get_pool() if use_pool else None
    tree_tok = next(_TREE_TOKENS)

    row_slot = np.zeros(n, np.int64)
    k = 0
    rec_leaf = np.full(L - 1, -1, np.int32)
    rec_feature = np.full(L - 1, -1, np.int32)
    rec_bin = np.full(L - 1, -1, np.int32)
    rec_active = np.zeros(L - 1, bool)
    rec_gain = np.zeros(L - 1, np.float32)
    rec_is_cat = np.zeros(L - 1, bool)
    rec_catmask = np.zeros((L - 1, B), bool)

    lut = np.full(L, L, np.int64)
    lut[0] = 0
    inv = np.zeros(1, np.int64)              # plane index -> record slot
    cube_prev: "np.ndarray | None" = None    # serial path only
    parent_local: "np.ndarray | None" = None
    pooled_any = False
    S_prev = 1
    cur = 0

    for level in range(n_levels):
        S = len(inv)
        # slots outside the frontier carry lut == L; clamp to S, the
        # all-dropped pad index (the XLA grower's clamped-gather idiom)
        local = np.minimum(lut[row_slot], S)
        sib = sibling_subtract and level > 0
        if sib:
            # histogram only the SMALLER child of each sibling pair and
            # derive the other as parent - small
            P = S // 2
            counts = np.bincount(local, minlength=S + 1)
            if gsum is not None:
                # the smaller-child choice must be the GLOBAL one or the
                # members' summed histograms would cover different children
                counts = gsum(counts.astype(np.float64))
            right_small = counts[1:2 * P:2] <= counts[0:2 * P:2]
            pairi = local >> 1
            is_small = (local < 2 * P) & (
                (local & 1).astype(bool)
                == right_small[np.minimum(pairi, P - 1)]
            )
            slot_hist = np.where(is_small, pairi, P)
            ns_hist = P
            pair_meta = (right_small, parent_local, S_prev)
        else:
            slot_hist = local
            ns_hist = S
            pair_meta = None
        # slot_hist is already clamped into [0, ns_hist] (ns_hist = the
        # trash plane), so the offsets need no range check
        base = (slot_hist * B).astype(np.int64)
        res = None
        if pool is not None:
            res = pool.grow_level(
                b, base, s3, S, B, scan, pair_meta, cur,
                bins_token=("tree", tree_tok), stats_token=("tree", tree_tok),
            )
            if res is None and pooled_any:
                raise _PoolLost()
        if res is not None:
            cube, gains, bbs = res
            pooled_any = True
        else:
            pool = None
            if gsum is not None and gblocks is not None:
                # data-parallel gang: per-feature-block histograms hand
                # off to the reducer while later blocks still build —
                # wire time hides behind compute (bit-identical to one
                # whole-plane allreduce)
                half = _gang_summed_cube(
                    gblocks, b, stats, slot_hist, ns_hist, B
                )
            else:
                half = _host_multi_kernel(
                    ns_hist, B, True, b, stats, slot_hist
                ).reshape(ns_hist, d, B, 3)
                if gsum is not None:
                    half = gsum(half)
            if sib:
                parents_ok = parent_local >= 0
                parents = cube_prev[np.maximum(parent_local, 0)]
                other = parents - half
                if not parents_ok.all():
                    bad = ~parents_ok
                    other[bad] = 0.0
                    half = half.copy()
                    half[bad] = 0.0
                rs = right_small[:, None, None, None]
                cube = np.empty((S, d, B, 3), np.float32)
                cube[0:2 * P:2] = np.where(rs, other, half)
                cube[1:2 * P:2] = np.where(rs, half, other)
                if 2 * P < S:
                    cube[2 * P:] = 0.0
            else:
                cube = half
            cube_prev = cube
            gains, bbs = feature_candidates(
                cube, fm, float(min_data_in_leaf), min_sum_hessian,
                lambda_l2, lambda_l1, cat_f,
            )
        S_prev = S
        cur = 1 - cur
        bgains, feats, bbest, catms = _combine_candidates(
            cube, gains, bbs, cat_f
        )
        # budget: best-gain slots win the remaining record slots, in the
        # same stable descending order the XLA grower uses
        order = np.argsort(-bgains, kind="stable")
        S_next = min(2 * S, L)
        slot_s = inv[order]
        gain_s = bgains[order]
        ok = (slot_s >= 0) & np.isfinite(gain_s) & (gain_s > min_gain)
        rank = np.cumsum(ok) - ok
        ok &= (k + rank) < (L - 1)
        ks = k + rank
        new_id = ks + 1
        bf_s, bb_s, cm_s = feats[order], bbest[order], catms[order]
        is_cat_s = cat_f[bf_s] if cat_f is not None else np.zeros(S, bool)
        sel = np.flatnonzero(ok)
        rec_leaf[ks[sel]] = slot_s[sel]
        rec_feature[ks[sel]] = bf_s[sel]
        rec_bin[ks[sel]] = bb_s[sel]
        rec_active[ks[sel]] = True
        rec_gain[ks[sel]] = gain_s[sel]
        rec_is_cat[ks[sel]] = is_cat_s[sel]
        rec_catmask[ks[sel]] = np.where(
            is_cat_s[sel, None], cm_s[sel], False
        )
        # next frontier: pair p (= rank) at locals (2p, 2p+1). Indices
        # past the clipped frontier drop (the XLA grower's mode='drop'):
        # a split whose odd child index would land outside S_next keeps
        # its record but leaves the frontier.
        lut = np.full(L, L, np.int64)
        inv = np.full(S_next, -1, np.int64)
        parent_local = np.full(S_next // 2, -1, np.int64)
        even = sel[2 * rank[sel] < S_next]
        odd = sel[2 * rank[sel] + 1 < S_next]
        pok = sel[rank[sel] < (S_next // 2)]
        lut[slot_s[even]] = 2 * rank[even]
        lut[new_id[odd]] = 2 * rank[odd] + 1
        inv[2 * rank[even]] = slot_s[even]
        inv[2 * rank[odd] + 1] = new_id[odd]
        parent_local[rank[pok]] = order[pok]
        # row routing: per ORIGINAL local j, this level's chosen split
        split_ok = np.zeros(S + 1, bool)
        split_bf = np.zeros(S + 1, np.int64)
        split_bb = np.zeros(S + 1, np.int64)
        split_new = np.zeros(S + 1, np.int64)
        split_ok[order[sel]] = True
        split_bf[order[sel]] = bf_s[sel]
        split_bb[order[sel]] = bb_s[sel]
        split_new[order[sel]] = new_id[sel]
        okr = split_ok[local]
        bf_r = split_bf[local]
        row_bins = b[np.arange(n), bf_r]
        if cat_f is not None:
            split_iscat = np.zeros(S + 1, bool)
            split_cm = np.zeros((S + 1, B), bool)
            split_iscat[order[sel]] = is_cat_s[sel]
            split_cm[order[sel]] = cm_s[sel]
            goes_right = okr & np.where(
                split_iscat[local],
                ~split_cm[local, row_bins],
                row_bins > split_bb[local],
            )
        else:
            goes_right = okr & (row_bins > split_bb[local])
        row_slot = np.where(goes_right, split_new[local], row_slot)
        k += int(ok.sum())

    Gl = np.bincount(row_slot, weights=g, minlength=L)[:L]
    Hl = np.bincount(row_slot, weights=h, minlength=L)[:L]
    Cl = np.bincount(row_slot, weights=w, minlength=L)[:L]
    if gsum is not None:
        Gl, Hl, Cl = gsum(np.stack([Gl, Hl, Cl]))
    with np.errstate(divide="ignore", invalid="ignore"):
        leaf_values = np.where(
            Cl > 0,
            -_soft(Gl, lambda_l1) / (Hl + lambda_l2) * learning_rate,
            0.0,
        )
    return (
        rec_leaf,
        rec_feature,
        rec_bin,
        rec_active,
        rec_gain.astype(np.float32),
        leaf_values.astype(np.float32),
        Cl.astype(np.int32),
        row_slot.astype(np.int32),
        rec_is_cat,
        rec_catmask,
    )

# -- leaf-wise (lossguide) ---------------------------------------------------


def grow_tree_lossguide_host(
    num_leaves: int,
    max_depth: int,
    num_bins: int,
    min_data_in_leaf: int,
    has_categorical: bool,
    min_gain,
    lambda_l2,
    lambda_l1,
    min_sum_hessian,
    learning_rate,
    bins,
    grad,
    hess,
    row_weight,
    feature_mask,
    categorical_mask,
) -> tuple:
    """One leaf-wise (best-first) tree on host — the masked
    :func:`treegrow._grow_tree` semantics with the DataPartition cost
    model for free: each split histograms only the SMALLER child
    (compacted rows), derives the sibling as parent - small, and
    re-scans only the two planes the split changed (the same split-search
    cache the XLA grower carries). Early exhaustion breaks the loop — the
    XLA grower's remaining steps are provable no-ops."""
    from mmlspark_tpu.ops.histogram import _host_multi_kernel as _mk
    from mmlspark_tpu.parallel.elastic import (
        gang_blocks,
        gang_sum,
        gang_voting_k,
    )

    # elastic gang: histograms summed across members (see _grow_host);
    # voting mode keeps planes LOCAL and exchanges only ballots +
    # candidate columns per refresh
    gsum = gang_sum()
    gblocks = gang_blocks()
    gv_k = gang_voting_k()

    min_gain = float(np.asarray(min_gain))
    lambda_l2 = float(np.asarray(lambda_l2))
    lambda_l1 = float(np.asarray(lambda_l1))
    min_sum_hessian = float(np.asarray(min_sum_hessian))
    learning_rate = float(np.asarray(learning_rate))
    b = np.ascontiguousarray(np.asarray(bins))
    n, d = b.shape
    L, B = num_leaves, num_bins
    g = np.asarray(grad, np.float64) * np.asarray(row_weight, np.float64)
    h = np.asarray(hess, np.float64) * np.asarray(row_weight, np.float64)
    w = np.asarray(row_weight, np.float64)
    fm = np.asarray(feature_mask)
    cat_f = np.asarray(categorical_mask, bool) if has_categorical else None
    stats = np.stack([g, h, w], axis=1).astype(np.float32)

    from mmlspark_tpu.ops.histpool import feature_candidates

    row_leaf = np.zeros(n, np.int64)
    leaf_depth = np.zeros(L, np.int64)
    rec_leaf = np.full(L - 1, -1, np.int32)
    rec_feature = np.full(L - 1, -1, np.int32)
    rec_bin = np.full(L - 1, -1, np.int32)
    rec_active = np.zeros(L - 1, bool)
    rec_gain = np.zeros(L - 1, np.float32)
    rec_is_cat = np.zeros(L - 1, bool)
    rec_catmask = np.zeros((L - 1, B), bool)
    hist = np.zeros((L, d, B, 3), np.float32)
    cache_gain = np.full(L, -np.inf)
    cache_feat = np.zeros(L, np.int64)
    cache_bin = np.zeros(L, np.int64)
    cache_cm = np.zeros((L, B), bool)

    def _gang_cube(slot: np.ndarray, ns: int) -> np.ndarray:
        """One (ns, d, B, 3) histogram, gang-summed with the feature-
        block overlap pipeline when available."""
        if gsum is not None and gv_k is None and gblocks is not None:
            return _gang_summed_cube(gblocks, b, stats, slot, ns, B)
        cube = _mk(ns, B, True, b, stats, slot).reshape(ns, d, B, 3)
        if gsum is not None and gv_k is None:
            cube = gsum(cube)
        return cube

    # root: the only full-data histogram of the tree (pool-eligible).
    # Voting mode keeps it LOCAL — the exchange happens per refresh.
    root = _gang_cube(np.zeros(n, np.int64), 1)[0]
    hist[0] = root
    prev_pair = np.array([0, 0])

    def _refresh(pair: np.ndarray) -> None:
        cube = hist[pair]                       # (2, d, B, 3)
        gains, bbs = feature_candidates(
            cube, fm, float(min_data_in_leaf), min_sum_hessian,
            lambda_l2, lambda_l1, cat_f,
        )
        if gv_k is not None and gsum is not None:
            # PV-Tree: ballots from the local scan, then an exact scan
            # over only the top-2K candidates' GLOBAL columns
            bg, bf, bb, cm = _voting_combine(
                cube, gains, fm, cat_f, float(min_data_in_leaf),
                min_sum_hessian, lambda_l2, lambda_l1, gsum, gv_k,
            )
        else:
            bg, bf, bb, cm = _combine_candidates(cube, gains, bbs, cat_f)
        cache_gain[pair] = bg
        cache_feat[pair] = bf
        cache_bin[pair] = bb
        cache_cm[pair] = cm

    for k in range(L - 1):
        _refresh(prev_pair)
        leaf_ok = np.arange(L) < (k + 1)
        if max_depth > 0:
            leaf_ok &= leaf_depth < max_depth
        sel = np.where(leaf_ok, cache_gain, -np.inf)
        bl = int(np.argmax(sel))
        best_gain = sel[bl]
        if not (np.isfinite(best_gain) and best_gain > min_gain):
            break                               # XLA path: no-op steps
        bf = int(cache_feat[bl])
        bb = int(cache_bin[bl])
        new_id = k + 1
        in_leaf = row_leaf == bl
        row_bins = b[:, bf]
        is_cat_split = bool(cat_f is not None and cat_f[bf])
        if is_cat_split:
            goes_right = in_leaf & ~cache_cm[bl][row_bins]
        else:
            goes_right = in_leaf & (row_bins > bb)
        moved = goes_right
        n_right = int(moved.sum())
        n_left = int(in_leaf.sum()) - n_right
        if gsum is not None:
            # globalize the child sizes: members must histogram the SAME
            # child of the pair or the summed planes would be incoherent
            n_left, n_right = gsum(
                np.array([n_left, n_right], np.float64)
            )
        row_leaf = np.where(moved, new_id, row_leaf)
        # histogram the smaller child over its COMPACTED rows, derive the
        # sibling as parent - small
        small_mask = moved if n_right <= n_left else (in_leaf & ~moved)
        slot = np.where(small_mask, 0, 1).astype(np.int64)  # 1 = dropped
        small = _gang_cube(slot, 1)[0]
        parent = hist[bl]
        if n_right <= n_left:
            hist[new_id] = small
            hist[bl] = parent - small
        else:
            hist[new_id] = parent - small
            hist[bl] = small
        child_depth = leaf_depth[bl] + 1
        leaf_depth[bl] = child_depth
        leaf_depth[new_id] = child_depth
        rec_leaf[k] = bl
        rec_feature[k] = bf
        rec_bin[k] = bb
        rec_active[k] = True
        rec_gain[k] = best_gain
        rec_is_cat[k] = is_cat_split
        if is_cat_split:
            rec_catmask[k] = cache_cm[bl]
        prev_pair = np.array([bl, new_id])

    Gl = np.bincount(row_leaf, weights=g, minlength=L)[:L]
    Hl = np.bincount(row_leaf, weights=h, minlength=L)[:L]
    Cl = np.bincount(row_leaf, weights=w, minlength=L)[:L]
    if gsum is not None:
        Gl, Hl, Cl = gsum(np.stack([Gl, Hl, Cl]))
    with np.errstate(divide="ignore", invalid="ignore"):
        leaf_values = np.where(
            Cl > 0,
            -_soft(Gl, lambda_l1) / (Hl + lambda_l2) * learning_rate,
            0.0,
        )
    return (
        rec_leaf,
        rec_feature,
        rec_bin,
        rec_active,
        rec_gain.astype(np.float32),
        leaf_values.astype(np.float32),
        Cl.astype(np.int32),
        row_leaf.astype(np.int32),
        rec_is_cat,
        rec_catmask,
    )

