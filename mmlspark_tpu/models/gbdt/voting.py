"""Voting-parallel tree growth — LightGBM's ``voting_parallel`` for real.

The reference exposes two distributed GBDT modes
(lightgbm/LightGBMParams.scala:13-18, LightGBMConstants.scala:22-24):
``data_parallel`` allreduces the FULL per-leaf histogram every split, while
``voting_parallel`` (PV-Tree: Meng et al., "A Communication-Efficient
Parallel Algorithm for Decision Tree", NeurIPS 2016) cuts the exchange to
two tiny rounds:

1. **local vote** — each worker ranks features by its local split gain and
   nominates its top ``top_k``;
2. **global vote** — per-feature vote counts are summed (one (d,)
   allreduce) and the top ``2 * top_k`` features become candidates;
3. **exact phase** — only the candidates' histogram columns are summed
   (a (2, 2K, B, 3) allreduce instead of (d*B, 3)), and the split is
   chosen exactly on those.

Here a worker = a mesh shard: the grower runs under ``jax.shard_map`` over
the ``data`` axis, local histograms stay shard-resident (never allreduced
in full), and the two vote rounds are explicit ``psum``s riding ICI. Bytes
on the wire per split drop from ``d*B*3`` to ``d + 2*2K*B*3`` — the win
LightGBM's voting mode exists for when ``d >> 2K``.

Same incremental design as :mod:`treegrow`: per-leaf best-split cache,
only the two changed leaves re-voted per step. Categorical features vote
with their sorted-prefix gain and split by subset membership exactly like
the single-chip grower (the reference imposes no categorical restriction
on voting mode either, LightGBMParams.scala:13-18); the catmask is derived
from the psum'd candidate histograms, so it is identical on every shard.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.models.gbdt.treegrow import GrownTree, split_gain_term, threshold_l1
from mmlspark_tpu.ops.histogram import NUM_BINS, plane_histogram
from mmlspark_tpu.parallel.mesh import DATA_AXIS


def grow_tree_voting(
    bins: jnp.ndarray,            # (n, d) sharded over the data axis
    grad: jnp.ndarray,            # (n,)
    hess: jnp.ndarray,            # (n,)
    row_weight: jnp.ndarray,      # (n,)
    num_leaves: int,
    lambda_l2: float,
    min_gain: float,
    learning_rate: float,
    feature_mask: jnp.ndarray,    # (d,) f32 (replicated)
    max_depth: int = -1,
    min_data_in_leaf: int = 20,
    top_k: int = 20,
    mesh: Any = None,
    axis: str = DATA_AXIS,
    lambda_l1: float = 0.0,
    min_sum_hessian: float = 1e-3,
    num_bins: int = NUM_BINS,
    categorical_mask: Any = None,   # (d,) bool, replicated
) -> GrownTree:
    """Grow one tree with PV-Tree voting over ``mesh``'s ``axis``."""
    if mesh is None:
        from mmlspark_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
    has_categorical = categorical_mask is not None
    if not has_categorical:
        categorical_mask = jnp.zeros((bins.shape[1],), bool)
    program = _voting_program(
        mesh, axis, int(num_leaves), int(max_depth), int(min_data_in_leaf),
        int(top_k), int(num_bins), has_categorical,
    )
    return program(
        bins, grad, hess, row_weight,
        jnp.float32(lambda_l2), jnp.float32(min_gain),
        jnp.float32(learning_rate), feature_mask,
        jnp.float32(lambda_l1), jnp.float32(min_sum_hessian),
        categorical_mask,
    )


@functools.lru_cache(maxsize=None)
def _voting_program(
    mesh, axis, num_leaves, max_depth, min_data_in_leaf, top_k,
    num_bins=NUM_BINS, has_categorical=False,
):
    L = num_leaves
    B = num_bins

    def program(bins, grad, hess, row_weight, lambda_l2, min_gain,
                learning_rate, feature_mask, lambda_l1, min_sum_hessian,
                categorical_mask):
        # executes PER SHARD: shapes below are shard-local
        n, d = bins.shape
        K = min(top_k, d)
        C = min(2 * top_k, d)
        bins = bins.astype(jnp.int32)
        lam = lambda_l2
        l1 = lambda_l1
        msh = min_sum_hessian

        def soft(Gv):
            return threshold_l1(Gv, l1)

        def gscore(Gv, Hv):
            return split_gain_term(Gv, Hv, lam, l1)

        g = grad * row_weight
        h = hess * row_weight
        row_stats = jnp.stack([g, h, row_weight], axis=-1)

        def plane_hist(mask):
            # LOCAL histogram plane — stays on the shard: a one-device
            # call inside shard_map (the kernel on TPU, scatter on CPU;
            # allow_host=False: a host callback per shard would serialize
            # the shards on the GIL)
            return plane_histogram(
                bins, row_stats, mask, num_bins=B, allow_host=False
            )

        cat_f = categorical_mask.astype(bool)

        def _cat_prefix(hg, hh, hc):
            """Sorted-by-ratio prefix cumsums (the Fisher-optimal subset
            scan shared with treegrow.make_leaf_best). Returns
            (order, cgs, chs, ccs) over the leading axis's features."""
            ratio = jnp.where(hc > 0, hg / (hh + 1e-12), -jnp.inf)
            order = jnp.argsort(-ratio, axis=-1)
            sgs = jnp.take_along_axis(hg, order, -1)
            shs = jnp.take_along_axis(hh, order, -1)
            scs = jnp.take_along_axis(hc, order, -1)
            return (order, jnp.cumsum(sgs, -1), jnp.cumsum(shs, -1),
                    jnp.cumsum(scs, -1))

        def local_feature_gains(plane):
            """(d*B, 3) LOCAL plane -> (d,) best local gain per feature
            (the vote-phase ranking; validity from local counts)."""
            cube = plane.reshape(d, B, 3)
            hg, hh, hc = cube[..., 0], cube[..., 1], cube[..., 2]
            cg = jnp.cumsum(hg, axis=1)
            ch = jnp.cumsum(hh, axis=1)
            cc = jnp.cumsum(hc, axis=1)
            G, H, Ct = cg[:, -1:], ch[:, -1:], cc[:, -1:]
            gain = gscore(cg, ch) + gscore(G - cg, H - ch) - gscore(G, H)
            valid = (
                (feature_mask > 0)[:, None]
                & (cc >= min_data_in_leaf)
                & ((Ct - cc) >= min_data_in_leaf)
                # same hessian floor as the exact phase: a feature whose
                # splits all fail it must not win votes
                & (ch >= msh) & ((H - ch) >= msh)
            )
            best_num = jnp.where(valid, gain, -jnp.inf).max(axis=1)
            if not has_categorical:
                return best_num
            order, cgs, chs, ccs = _cat_prefix(hg, hh, hc)
            gain_cat = gscore(cgs, chs) + gscore(G - cgs, H - chs) - gscore(G, H)
            valid_cat = (
                (feature_mask > 0)[:, None]
                & (ccs >= min_data_in_leaf)
                & ((Ct - ccs) >= min_data_in_leaf)
                & (chs >= msh) & ((H - chs) >= msh)
            )
            best_cat = jnp.where(valid_cat, gain_cat, -jnp.inf).max(axis=1)
            return jnp.where(cat_f, best_cat, best_num)

        def candidate_best(cand_hist, cand_ids):
            """Exact split over the GLOBAL candidate histograms of one leaf.

            cand_hist: (C, B, 3) psum'd; cand_ids: (C,) feature ids.
            Inputs are psum results, so every shard derives the identical
            split AND catmask. Returns (gain, feature, bin/prefix, catmask).
            """
            hg, hh, hc = cand_hist[..., 0], cand_hist[..., 1], cand_hist[..., 2]
            cg = jnp.cumsum(hg, axis=1)
            ch = jnp.cumsum(hh, axis=1)
            cc = jnp.cumsum(hc, axis=1)
            G, H, Ct = cg[:, -1:], ch[:, -1:], cc[:, -1:]
            gain_num = gscore(cg, ch) + gscore(G - cg, H - ch) - gscore(G, H)
            valid = (
                (feature_mask[cand_ids] > 0)[:, None]
                & (cc >= min_data_in_leaf)
                & ((Ct - cc) >= min_data_in_leaf)
                & (ch >= msh) & ((H - ch) >= msh)
            )
            gain = jnp.where(valid, gain_num, -jnp.inf)
            if has_categorical:
                order, cgs, chs, ccs = _cat_prefix(hg, hh, hc)
                gain_cat = (
                    gscore(cgs, chs) + gscore(G - cgs, H - chs) - gscore(G, H)
                )
                valid_cat = (
                    (feature_mask[cand_ids] > 0)[:, None]
                    & (ccs >= min_data_in_leaf)
                    & ((Ct - ccs) >= min_data_in_leaf)
                    & (chs >= msh) & ((H - chs) >= msh)
                )
                gain = jnp.where(
                    cat_f[cand_ids][:, None],
                    jnp.where(valid_cat, gain_cat, -jnp.inf),
                    gain,
                )
            flat = gain.reshape(-1)
            best = jnp.argmax(flat)
            ci = (best // B).astype(jnp.int32)
            bb = (best % B).astype(jnp.int32)
            if has_categorical:
                rank = jnp.argsort(order[ci])
                catmask = (rank <= bb) & cat_f[cand_ids[ci]]
            else:
                catmask = jnp.zeros((B,), bool)
            return flat[best], cand_ids[ci], bb, catmask

        def step(k, state):
            (hist, row_leaf, leaf_depth, done,
             cache_gain, cache_feat, cache_bin, cache_catmask, prev_pair,
             rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
             rec_is_cat, rec_catmask) = state

            # -- vote phase: rank features by LOCAL gain on the two planes
            pair_planes = hist[prev_pair]                       # (2, d*B, 3)
            local_gains = jax.vmap(local_feature_gains)(pair_planes)  # (2, d)
            topv, topi = jax.lax.top_k(local_gains, K)
            ballots = jnp.zeros((2, d), jnp.float32).at[
                jnp.arange(2)[:, None], topi
            ].add(jnp.where(jnp.isfinite(topv), 1.0, 0.0))
            votes = jax.lax.psum(ballots, axis)                 # tiny: (2, d)
            # global top-C by votes, ties to the lower feature id
            score = votes * jnp.float32(d + 1) - jnp.arange(d, dtype=jnp.float32)
            _, cand = jax.lax.top_k(score, C)                   # (2, C)

            # -- exact phase: allreduce ONLY the candidates' columns
            cube = pair_planes.reshape(2, d, B, 3)
            cand_local = jnp.take_along_axis(
                cube, cand[:, :, None, None], axis=1
            )                                                   # (2, C, B, 3)
            cand_global = jax.lax.psum(cand_local, axis)
            bg, bf_, bb_, bcm_ = jax.vmap(candidate_best)(cand_global, cand)

            cache_gain = cache_gain.at[prev_pair].set(bg)
            cache_feat = cache_feat.at[prev_pair].set(bf_)
            cache_bin = cache_bin.at[prev_pair].set(bb_)
            cache_catmask = cache_catmask.at[prev_pair].set(bcm_)

            # -- selection + split (identical on every shard: inputs are
            # psum results, so the split records stay replicated)
            leaf_ids = jnp.arange(L, dtype=jnp.int32)
            leaf_ok = leaf_ids < (k + 1)
            if max_depth > 0:
                leaf_ok = leaf_ok & (leaf_depth < max_depth)
            sel = jnp.where(leaf_ok, cache_gain, -jnp.inf)
            bl = jnp.argmax(sel).astype(jnp.int32)
            best_gain = sel[bl]
            bf = cache_feat[bl]
            bb = cache_bin[bl]
            catmask = cache_catmask[bl]

            do_split = (~done) & (best_gain > min_gain) & jnp.isfinite(best_gain)
            new_id = jnp.int32(k + 1)
            in_leaf = row_leaf == bl
            row_bins = bins[:, bf]
            if has_categorical:
                is_cat_split = cat_f[bf]
                goes_right = jnp.where(
                    is_cat_split, ~catmask[row_bins], row_bins > bb
                )
            else:
                is_cat_split = jnp.asarray(False)
                goes_right = row_bins > bb
            moved = do_split & in_leaf & goes_right
            row_leaf = jnp.where(moved, new_id, row_leaf)
            right_plane = plane_hist(moved.astype(jnp.float32))  # LOCAL
            hist = hist.at[new_id].set(right_plane).at[bl].add(
                jnp.where(do_split, -right_plane, 0.0)
            )
            child_depth = leaf_depth[bl] + 1
            leaf_depth = jnp.where(
                do_split,
                leaf_depth.at[bl].set(child_depth).at[new_id].set(child_depth),
                leaf_depth,
            )
            rec_leaf = rec_leaf.at[k].set(jnp.where(do_split, bl, -1))
            rec_feature = rec_feature.at[k].set(jnp.where(do_split, bf, -1))
            rec_bin = rec_bin.at[k].set(jnp.where(do_split, bb, -1))
            rec_active = rec_active.at[k].set(do_split)
            rec_gain = rec_gain.at[k].set(jnp.where(do_split, best_gain, 0.0))
            rec_is_cat = rec_is_cat.at[k].set(do_split & is_cat_split)
            rec_catmask = rec_catmask.at[k].set(
                jnp.where(do_split & is_cat_split, catmask, False)
            )
            done = done | ~do_split
            prev_pair = jnp.stack([bl, new_id])
            return (hist, row_leaf, leaf_depth, done,
                    cache_gain, cache_feat, cache_bin, cache_catmask, prev_pair,
                    rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
                    rec_is_cat, rec_catmask)

        hist0 = (
            jnp.zeros((L, d * B, 3), jnp.float32)
            .at[0]
            .set(plane_hist(jnp.ones((n,), jnp.float32)))
        )
        init = (
            hist0,
            jnp.zeros((n,), jnp.int32),
            jnp.zeros((L,), jnp.int32),
            jnp.asarray(False),
            jnp.full((L,), -jnp.inf, jnp.float32),
            jnp.zeros((L,), jnp.int32),
            jnp.zeros((L,), jnp.int32),
            jnp.zeros((L, B), bool),
            jnp.zeros((2,), jnp.int32),
            jnp.full((L - 1,), -1, jnp.int32),
            jnp.full((L - 1,), -1, jnp.int32),
            jnp.full((L - 1,), -1, jnp.int32),
            jnp.zeros((L - 1,), bool),
            jnp.zeros((L - 1,), jnp.float32),
            jnp.zeros((L - 1,), bool),
            jnp.zeros((L - 1, B), bool),
        )
        (_, row_leaf, _, _, _, _, _, _, _,
         rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
         rec_is_cat, rec_catmask) = (
            jax.lax.fori_loop(0, L - 1, step, init)
        )

        # leaf values from GLOBAL sums (one (L,3) psum)
        sums = jnp.stack(
            [
                jnp.zeros((L,), jnp.float32).at[row_leaf].add(g),
                jnp.zeros((L,), jnp.float32).at[row_leaf].add(h),
                jnp.zeros((L,), jnp.float32).at[row_leaf].add(row_weight),
            ],
            axis=-1,
        )
        sums = jax.lax.psum(sums, axis)
        Gl, Hl, Cl = sums[:, 0], sums[:, 1], sums[:, 2]
        leaf_values = -soft(Gl) / (Hl + lam) * learning_rate
        leaf_values = jnp.where(Cl > 0, leaf_values, 0.0)
        return GrownTree(
            rec_leaf, rec_feature, rec_bin, rec_active, rec_gain,
            leaf_values, Cl.astype(jnp.int32), row_leaf,
            rec_is_cat, rec_catmask,
        )

    row = P(axis)
    rep = P()
    mapped = jax.shard_map(
        program,
        mesh=mesh,
        in_specs=(row, row, row, row, rep, rep, rep, rep, rep, rep, rep),
        out_specs=GrownTree(
            rep, rep, rep, rep, rep,   # split records
            rep, rep,                  # leaf values/counts
            row,                       # row_leaf stays sharded
            rep, rep,                  # categorical records
        ),
        check_vma=False,
    )
    return jax.jit(mapped)
